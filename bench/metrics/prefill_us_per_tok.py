"""Device microseconds of the prefill program (`jit__prefill_last`) per
prompt token prefilled in the traced stretch."""
from benchkit import record


def read(run):
    return record.prefill_us_per_tok(run)
