"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and needs a TPU with as many chips as
the cell asks for; without one it exits non-zero and prints no result. It
makes the weights and traffic from ``--seed``, warms up every shape the
cell uses (that, with process start and loading, is ``setup_s``), measures
for ``--seconds``, then checks what the timed path served against the
plain reference. With ``--trace 1`` a stretch of the window is profiled
and the per-layer metrics are reported instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``check``, each compared number beside its limit. The
same comparisons are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_cache(root: Path) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every program cached, so only a checkout's first run compiles."""
    cache = str(root / ".bench_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def peaks_for(kind: str, require_chip: bool) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind in table:
        return table[kind]
    if require_chip:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return next(iter(table.values()))


def correct(checks: dict) -> bool:
    """What decides ``correct``: every number compared is at or under its
    limit. ``checks`` maps each name to (value, limit)."""
    return all(v <= lim for v, lim in checks.values())


def main(argv=None, root: Path = ROOT, require_chip: bool = True) -> int:
    """``root`` holds ``BENCHMARK.json``, ``bench/`` and the program's
    ``src/``. ``require_chip=False`` lets the tests run a cell on the CPU."""
    args = parse_args(argv)
    for p in (str(BENCH), str(root / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from benchkit.spec import load_cell, load_module

    cell = load_cell(root, args.workload)
    configure_cache(root)
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        log(f"bench: the cell needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s); nothing was run")
        return 2
    peaks = peaks_for(devs[0].device_kind, require_chip)

    import trace_reduce
    from benchkit import serving
    from benchkit.record import Run
    from benchkit.tracing import CompileCounter, Tracer

    counter = CompileCounter()
    gen = load_module(BENCH / "traffic" / "generator.py")
    driver = cell.driver()
    state = driver.prepare(cell, args.seed, args.seconds, gen)
    # what set-up left behind is never collected inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    built, loaded = counter.snapshot()
    log(f"[setup] setup_s={setup_s!r} programs_built={built} "
        f"cache_loads={loaded}")

    run = Run(config=cell.config["config"], peaks=peaks, setup_s=setup_s,
              window_s=args.seconds)
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        t = cell.traffic.get("trace", {})
        tracer = Tracer(bool(args.trace), t.get("start_frac", 0.3)
                        * args.seconds, t.get("seconds", 12), tdir)
        driver.window(state, cell, args.seconds, tracer, run)
        gc.unfreeze()
        b2, l2 = counter.snapshot()
        log(f"[window] window_s={run.window_s!r} sent={state['attempted']} "
            f"failed={state['failed']} steps={len(run.steps)} "
            f"programs_built_in_window={b2 - built} "
            f"cache_loads_in_window={l2 - loaded} "
            f"trace_stop_s={tracer.stop_s!r}")
        device = serving.device_record(cell.chips)
        state["engine"].free()
        t_check = time.perf_counter()
        checks = driver.check(state, cell, args.seed)
        log(f"[check] check_s={time.perf_counter() - t_check!r}")
        if args.trace:
            path = tracer.path()
            if path is None:
                raise RuntimeError("the traced run wrote no profile")
            run.trace = trace_reduce.load(path)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)

    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = cell.metric(m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct(checks),
              "attempted": state["attempted"], "failed": state["failed"],
              "metrics": metrics, "device": device}
    if args.trace:
        a, b = run.traced_window()
        device["busy_s"] = run.busy_s()
        device["window_s"] = run.traced_window_s()
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(run.trace, a, b),
            "idle_gaps": trace_reduce.gaps_by_span(run.trace, a, b)}
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} {v!r} limit {lim!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
