import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh and print its memory analysis and collectives:
the proof that a configuration lowers, shards and fits.

The two lines above run before ANY other import — jax locks the device
count at first init.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out report.json
"""
import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
from typing import Dict, Optional  # noqa: E402

from repro.jaxconfig import configure_jax  # noqa: E402
configure_jax()
import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCHS, SHAPES, get_arch, get_shape  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.dist.sharding import (axis_rules, axes_of,  # noqa: E402
                                 named_sharding_tree, rules_for, unbox)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.train.optimizer import AdamW  # noqa: E402

from repro.launch.hlo_analysis import collective_bytes  # noqa: E402

SLIDING_WINDOW_500K = 8192   # beyond-paper: ring-cache for dense 500k decode


def window_for(cfg: ModelConfig, shape: ShapeConfig) -> Optional[int]:
    """Sub-quadratic guard for 500k decode on pure-attention archs."""
    if shape.name != "long_500k":
        return None
    if cfg.family in ("ssm", "hybrid"):
        return None          # native sub-quadratic state
    return SLIDING_WINDOW_500K


# --------------------------------------------------------------------------
# Step functions + specs
# --------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: model_mod.init(cfg, jax.random.PRNGKey(0)))


def build_case(cfg: ModelConfig, shape: ShapeConfig, mesh, rules,
               remat: bool = True):
    """Returns (fn, arg_specs, in_shardings)."""
    boxed = abstract_params(cfg)
    pspec = unbox(boxed)
    pshard = named_sharding_tree(axes_of(boxed), mesh, rules)
    batch_axes = rules.spec(("batch", None), mesh)
    from jax.sharding import NamedSharding, PartitionSpec

    def ns(spec):
        return NamedSharding(mesh, spec)

    window = window_for(cfg, shape)
    if shape.mode == "train":
        opt = AdamW()
        ospec = jax.eval_shape(opt.init, pspec)
        oshard = type(ospec)(
            step=ns(PartitionSpec()),
            m=named_sharding_tree(axes_of(boxed), mesh, rules),
            v=named_sharding_tree(axes_of(boxed), mesh, rules))
        batch = model_mod.make_inputs(cfg, shape.global_batch, shape.seq_len,
                                      abstract=True)
        bshard = {k: ns(rules.spec(("batch",) + (None,) * (len(v.shape) - 1),
                                   mesh)) for k, v in batch.items()}

        def train_step(params, opt_state, b):
            def loss(p):
                return model_mod.loss_fn(cfg, p, b, remat=remat)
            lv, grads = jax.value_and_grad(loss)(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u, params, updates)
            return params, opt_state, lv

        return (train_step, (pspec, ospec, batch),
                (pshard, oshard, bshard), (pshard, oshard, ns(PartitionSpec())))

    if shape.mode == "prefill":
        batch = model_mod.make_inputs(cfg, shape.global_batch, shape.seq_len,
                                      abstract=True)
        bshard = {k: ns(rules.spec(("batch",) + (None,) * (len(v.shape) - 1),
                                   mesh)) for k, v in batch.items()}

        def prefill_step(params, b):
            logits, cache, _ = model_mod.forward(cfg, params, b,
                                                 return_cache=True)
            return logits[:, -1, :], cache

        return prefill_step, (pspec, batch), (pshard, bshard), None

    # decode: one token against a full cache
    B = shape.global_batch
    cache = jax.eval_shape(
        lambda: model_mod.init_decode_cache(cfg, B, shape.seq_len,
                                            window=window))
    cache_axes = model_mod.cache_logical_axes(cache)
    cshard = jax.tree.map(lambda ax: ns(rules.spec(ax, mesh)), cache_axes,
                          is_leaf=lambda x: isinstance(x, tuple))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    cur = jax.ShapeDtypeStruct((B,), jnp.int32)
    tshard = ns(rules.spec(("batch", None), mesh))
    cur_shard = ns(rules.spec(("batch",), mesh))

    def decode(params, toks, c, pos):
        return model_mod.decode_step(cfg, params, toks, c, pos,
                                     window=window)

    return (decode, (pspec, tokens, cache, cur),
            (pshard, tshard, cshard, cur_shard), None)


# --------------------------------------------------------------------------
# Runner
# --------------------------------------------------------------------------

def run_case(arch: str, shape_name: str, multi_pod: bool = False,
             remat: bool = True, verbose: bool = True) -> Dict:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model_axis = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    rules = rules_for(cfg, shape, model_axis)

    t0 = time.time()
    with axis_rules(mesh, rules):
        fn, specs, in_sh, out_sh = build_case(cfg, shape, mesh, rules,
                                              remat=remat)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*specs)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": mesh.devices.size,
        "compile_s": round(t_compile, 1),
        "collective_bytes_per_device": sum(coll.values()),
        "collectives": coll,
        "memory_analysis": {
            k: getattr(mem, k, None)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")},
    }
    if verbose:
        print(f"[{arch} x {shape_name} @ {result['mesh']}] "
              f"compile={t_compile:.0f}s "
              f"collective_bytes={result['collective_bytes_per_device']:.3e}",
              flush=True)
        print("  memory_analysis:", result["memory_analysis"], flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cases = []
    if args.all:
        for a in ARCHS:
            for s in SHAPES:
                cases.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cases = [(args.arch, args.shape)]

    results = []
    for a, s in cases:
        try:
            results.append(run_case(a, s, multi_pod=args.multi_pod,
                                    remat=not args.no_remat))
        except Exception as e:  # record failures; they are bugs to fix
            print(f"[{a} x {s}] FAILED: {type(e).__name__}: {e}",
                  flush=True)
            results.append({"arch": a, "shape": s, "error": str(e)})
            if not args.all:
                raise
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    nfail = sum(1 for r in results if "error" in r)
    print(f"{len(results) - nfail}/{len(results)} cases compiled OK")
    return 1 if nfail else 0


if __name__ == "__main__":
    sys.exit(main())
