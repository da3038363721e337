"""Model FLOPs of every token the traced steps processed (prefill and
decode), over the traced stretch at the chip's peak bf16 FLOP/s, in
percent: the whole step's share of the chip."""
from benchkit import record


def read(run):
    return record.step_mfu(run)
