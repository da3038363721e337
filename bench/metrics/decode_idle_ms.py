"""Device-idle milliseconds inside the engine's ``engine.decode`` spans
(token and position build and upload, decode launch, argmax pull, token
emit) over the traced stretch, per decode; the engine's spans aligned to
the trace by ``benchkit.engine_spans``."""
from benchkit import engine_spans


def read(run):
    return engine_spans.decode_idle_ms(run)
