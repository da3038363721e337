"""Device milliseconds per launch of the decode program (the engine's
jitted `decode_step` lambda, `jit__lambda`)."""
from benchkit import record


def read(run):
    return record.decode_ms(run)
