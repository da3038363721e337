"""Record the engine-span test data on a TPU host:

    python3 bench/tests/record_engine_trace.py [out_dir]

Serves the tiny chat cell's model (2 layers, width 128) through the
harness's ``Engine``, so every ``step()`` sits in a ``bench.step`` span,
and profiles up to ``STEPS`` steps inside a ``bench.window`` span, with the
Python function tracer off. Writes ``engine.xplane.pb`` and
``engine_spans.json`` (the program's in-memory span records of the
profiled stretch) into ``out_dir`` (default ``bench/tests/data``). The
trace keeps only what the readers use, so that
it stays small: the chip's ``XLA Modules`` line and the host's
``bench.*`` and ``engine.*`` annotations, without stats (``trim``; it
reads the XSpace proto with TensorFlow's ``xplane_pb2``).
"""
from __future__ import annotations

import glob
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
for p in (str(BENCH), str(HERE), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SEED = 2**33 + 5
STEPS = 12


def trim(src: Path, dst: Path) -> None:
    """Copy of the XSpace at ``src`` with the device planes' module lines
    and the host's benchmark and engine annotations only, and no stats."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(src.read_bytes())
    out = xplane_pb2.XSpace()
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU")
        if not (device or plane.name.startswith("/host:CPU")):
            continue
        keep = out.planes.add(id=plane.id, name=plane.name)
        for line in plane.lines:
            kept = keep.lines.add(id=line.id, display_id=line.display_id,
                                  name=line.name,
                                  timestamp_ns=line.timestamp_ns,
                                  duration_ps=line.duration_ps)
            for ev in line.events:
                name = plane.event_metadata[ev.metadata_id].name
                if (line.name == "XLA Modules" if device
                        else name.startswith(("bench.", "engine."))):
                    kept.events.add(metadata_id=ev.metadata_id,
                                    offset_ps=ev.offset_ps,
                                    duration_ps=ev.duration_ps)
                    keep.event_metadata[ev.metadata_id].CopyFrom(
                        plane.event_metadata[ev.metadata_id])
            if not kept.events:
                del keep.lines[-1]
    for plane in out.planes:
        for m in plane.event_metadata.values():
            m.ClearField("stats")
    dst.write_bytes(out.SerializeToString())


def main(out: Path) -> int:
    import jax
    from jax.profiler import TraceAnnotation

    import tinycell
    from benchkit import serving
    from benchkit.spec import load_cell, load_module
    from repro.serving import telemetry

    tmp = Path(tempfile.mkdtemp(prefix="engine-trace-"))
    try:
        cell = load_cell(tinycell.make_root(tmp), "tiny.chat")
        gen = load_module(BENCH / "traffic" / "generator.py")
        vocab = cell.config["config"]["vocab_size"]
        eng = serving.Engine(cell, SEED)
        eng.warm(gen.warmup(cell.traffic, vocab))
        for r in gen.open_loop(cell.traffic, 1.0, SEED, vocab)[:6]:
            eng.submit(r)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        logdir = tmp / "trace"
        telemetry.clear()
        jax.profiler.start_trace(str(logdir), profiler_options=opts)
        with TraceAnnotation("bench.window"):
            for _ in range(STEPS):
                if eng.has_work:
                    eng.step(time.perf_counter, traced=True)
        jax.profiler.stop_trace()
        out.mkdir(parents=True, exist_ok=True)
        path = glob.glob(str(logdir / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        trim(Path(path), out / "engine.xplane.pb")
        (out / "engine_spans.json").write_text(
            json.dumps(telemetry.spans()))
        print(f"{len(telemetry.spans())} span records, device "
              f"{jax.devices()[0].device_kind}, "
              f"prefills {eng.eng.prefill_count}, "
              f"decodes {eng.eng.decode_count}")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1]) if len(sys.argv) > 1
                  else HERE / "data"))
