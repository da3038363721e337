"""95th percentile of every gap between consecutive output tokens of every
request sent in the window, pooled (thousands of gaps)."""
from benchkit import record


def read(run):
    return record.itl_ms(run, 95)
