"""Prefill programs' share of their roofline: causal-prefill FLOPs at peak
bf16 FLOP/s over the prefill device time, in percent."""
from benchkit import record


def read(run):
    return record.prefill_roofline(run)
