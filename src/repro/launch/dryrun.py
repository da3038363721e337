import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production mesh, print memory/cost analysis, and derive the
three-term roofline (compute / memory / collective).

The two lines above run before ANY other import — jax locks the device
count at first init.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out report.json
"""
import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

from repro.jaxconfig import configure_jax  # noqa: E402
configure_jax()
import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402

from repro.configs import ARCHS, SHAPES, get_arch, get_shape  # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig  # noqa: E402
from repro.dist.sharding import (LONG_CTX_RULES, SERVE_RULES, TRAIN_RULES,  # noqa: E402
                                 ShardingRules, axis_rules, axes_of,
                                 named_sharding_tree, unbox)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import model as model_mod  # noqa: E402
from repro.train.optimizer import AdamW  # noqa: E402

from repro.launch.hlo_analysis import (HBM_BW, ICI_BW, PEAK_FLOPS,  # noqa: E402
                                        collective_bytes)

SLIDING_WINDOW_500K = 8192   # beyond-paper: ring-cache for dense 500k decode


# --------------------------------------------------------------------------
# Rules per (arch, shape)
# --------------------------------------------------------------------------

def rules_for(cfg: ModelConfig, shape: ShapeConfig,
              model_axis: int = 16, opts=frozenset()) -> ShardingRules:
    if shape.mode == "train":
        base = TRAIN_RULES
    elif shape.name == "long_500k":
        base = ShardingRules({**LONG_CTX_RULES, "batch": None,
                              "kv_seq": ("pod", "data")})
    else:
        base = SERVE_RULES
    rules = ShardingRules(base)
    # kv heads that don't divide the model axis: shard head_dim instead of
    # padding the KV cache 4-16x (GSPMD would pad uneven head sharding)
    if (cfg.num_kv_heads and cfg.num_kv_heads % model_axis != 0
            and not cfg.use_mla):
        rules["kv_heads"] = None
        rules["head_dim"] = "model"
    if cfg.num_heads and cfg.num_heads % model_axis != 0:
        rules["heads"] = None
    if cfg.num_experts and cfg.num_experts % model_axis != 0:
        rules["expert"] = "data"
    # ---- §Perf opt: distributed flash-decode over a model-sharded cache.
    # Replaces the head_dim-sharded contraction (which all-reduces
    # (B,H,T) fp32 scores per layer) with a kv_seq-sharded cache: softmax
    # and A@V reduce over the sharded T axis with tiny (B,H[,hd])
    # all-reduces instead.
    if ("decode_kv_shard" in opts and shape.mode == "decode"
            and shape.name != "long_500k" and not cfg.use_mla):
        rules["kv_seq"] = "model"
        rules["head_dim"] = None
        rules["kv_heads"] = None
    if "attn_no_headdim_shard" in opts:
        rules["head_dim"] = None
        rules["kv_heads"] = None
    return rules


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
             remat: bool = True, verbose: bool = True,
             probes: bool = True, opts=frozenset()) -> Dict:
    from repro.models import flags as model_flags
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    model_axis = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    rules = rules_for(cfg, shape, model_axis, opts=opts)
    model_flags.ATTN_BF16_STREAM = "bf16_stream" in opts
    model_flags.MOE_DECODE_DISPATCH = "moe_dispatch" in opts
    model_flags.WHERE_CACHE_UPDATE = "where_cache" in opts
    chips = mesh.devices.size

    t0 = time.time()
    with axis_rules(mesh, rules):
        fn, specs, in_sh, out_sh = build_case(cfg, shape, mesh, rules,
                                              remat=remat)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*specs)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    raw = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0)),
           "collective": sum(coll.values())}
    # while-loop-corrected (probe-extrapolated) per-device costs
    if probes:
        probe = probe_costs(cfg, shape, mesh, rules, remat=remat)
    else:
        probe = raw   # compile-proof only (multi-pod pass)
    flops = probe["flops"]
    bytes_acc = probe["bytes"]
    coll_total = probe["collective"]
    compute_t = flops / PEAK_FLOPS
    memory_t = bytes_acc / HBM_BW
    coll_t = coll_total / ICI_BW

    n_total = cfg.param_count()
    n_active = cfg.active_param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.mode == "train"
                                   else (shape.seq_len if shape.mode ==
                                         "prefill" else 1))
    mult = 6 if shape.mode == "train" else 2
    model_flops = mult * n_active * tokens / chips  # per device

    model_flags.ATTN_BF16_STREAM = False
    model_flags.MOE_DECODE_DISPATCH = False
    model_flags.WHERE_CACHE_UPDATE = False
    result = {
        "arch": arch, "shape": shape_name,
        "opts": sorted(opts),
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collective_bytes_per_device": coll_total,
        "collectives": coll,
        "raw_uncorrected": raw,
        "compute_t": compute_t,
        "memory_t": memory_t,
        "collective_t": coll_t,
        "bottleneck": max((("compute", compute_t), ("memory", memory_t),
                           ("collective", coll_t)), key=lambda kv: kv[1])[0],
        "model_flops_per_device": model_flops,
        "useful_flops_frac": (model_flops / flops) if flops else None,
        "memory_analysis": {
            k: getattr(mem, k, None)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")},
    }
    if verbose:
        print(f"[{arch} x {shape_name} @ {result['mesh']}] "
              f"compile={t_compile:.0f}s bottleneck={result['bottleneck']} "
              f"compute={compute_t*1e3:.2f}ms memory={memory_t*1e3:.2f}ms "
              f"collective={coll_t*1e3:.2f}ms "
              f"useful={result['useful_flops_frac'] and round(result['useful_flops_frac'],3)}",
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
    ap.add_argument("--no-probes", action="store_true",
                    help="compile-proof only (skip roofline cost probes)")
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
                                    remat=not args.no_remat,
                                    probes=not args.no_probes))
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



# --------------------------------------------------------------------------
# Probe-extrapolated cost analysis.
#
# XLA's HloCostAnalysis counts a while-loop body ONCE regardless of trip
# count, so scanned layer stacks hide per-layer FLOPs/bytes/collectives.
# We therefore compile small UNROLLED variants (2/4 layers etc.), fit
#   cost = a + sum_i L_i * c_i
# by least squares over the probe layer-count features, and extrapolate to
# the full depth.  The full-size scanned compile above remains the proof
# that the real configuration lowers and fits.
# --------------------------------------------------------------------------

def probe_variants(cfg: ModelConfig):
    if cfg.family == "audio":
        mk = lambda e, d: dataclasses.replace(cfg, encoder_layers=e,
                                              num_layers=d)
        return ([(mk(1, 1), [1, 1, 1]), (mk(2, 1), [1, 2, 1]),
                 (mk(1, 3), [1, 1, 3])],
                [1, cfg.encoder_layers, cfg.num_layers])
    if cfg.family == "hybrid":
        # cost = a + b * (Mamba2 layers) + c * (shared-block uses): three
        # depths cut before the first use, after it and after the second
        ids = cfg.hybrid_layer_ids

        def mk(L):
            kept = tuple(i for i in ids if i < L)
            return (dataclasses.replace(cfg, num_layers=L,
                                        hybrid_layer_ids=kept),
                    [1, L, len(kept)])
        return ([mk(ids[0]), mk(ids[0] + 1), mk(ids[1] + 1)],
                [1, cfg.num_layers, len(ids)])
    if cfg.num_experts and cfg.num_dense_layers:
        mk = lambda d, m: dataclasses.replace(cfg, num_dense_layers=d,
                                              num_layers=d + m)
        return ([(mk(1, 1), [1, 1, 1]), (mk(2, 1), [1, 2, 1]),
                 (mk(1, 3), [1, 1, 3])],
                [1, cfg.num_dense_layers,
                 cfg.num_layers - cfg.num_dense_layers])
    mk = lambda L: dataclasses.replace(cfg, num_layers=L)
    return ([(mk(2), [1, 2]), (mk(4), [1, 4])], [1, cfg.num_layers])


def probe_costs(cfg: ModelConfig, shape: ShapeConfig, mesh, rules,
                remat: bool = True, verbose: bool = False) -> Dict:
    from repro.models import flags as model_flags
    variants, feat_full = probe_variants(cfg)
    feats, ys = [], []
    with model_flags.unrolled_scans():
        model_flags.PROBE_BLOCK_Q = max(shape.seq_len // 4, 1024)
        try:
            for vcfg, feat in variants:
                with axis_rules(mesh, rules):
                    fn, specs, in_sh, out_sh = build_case(vcfg, shape, mesh,
                                                          rules, remat=remat)
                    compiled = jax.jit(fn, in_shardings=in_sh,
                                       out_shardings=out_sh
                                       ).lower(*specs).compile()
                cost = compiled.cost_analysis() or {}
                coll = sum(collective_bytes(compiled.as_text()).values())
                feats.append(feat)
                ys.append([float(cost.get("flops", 0.0)),
                           float(cost.get("bytes accessed", 0.0)), coll])
                if verbose:
                    print(f"  probe {feat}: flops={ys[-1][0]:.3e} "
                          f"bytes={ys[-1][1]:.3e} coll={ys[-1][2]:.3e}",
                          flush=True)
        finally:
            model_flags.PROBE_BLOCK_Q = None
    A = np.asarray(feats, float)
    Y = np.asarray(ys, float)
    coef, *_ = np.linalg.lstsq(A, Y, rcond=None)
    est = np.maximum(np.asarray(feat_full, float) @ coef, 0.0)
    return {"flops": float(est[0]), "bytes": float(est[1]),
            "collective": float(est[2])}


if __name__ == "__main__":
    sys.exit(main())
