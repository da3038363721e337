"""Model FLOPs of the tokens decoded in the traced stretch over the decode
device time at peak bf16 FLOP/s, in percent."""
from benchkit import record


def read(run):
    return record.decode_mfu(run)
