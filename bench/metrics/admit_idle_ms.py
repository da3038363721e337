"""Device-idle milliseconds inside the engine's ``engine.admit`` spans
(scheduling, prompt upload, prefill launch, first-token pull, slot-write
launch) over the traced stretch, per ``engine.prefill``; the engine's spans
aligned to the trace by ``benchkit.engine_spans``."""
from benchkit import engine_spans


def read(run):
    return engine_spans.admit_idle_ms(run)
