"""Decode programs' share of their roofline, in percent: per step the
larger of its bytes (weights once, keys and values of the live positions)
at peak HBM bandwidth and its FLOPs at peak, summed, over decode device
time. Decode is bound by bandwidth here."""
from benchkit import record


def read(run):
    return record.decode_roofline(run)
