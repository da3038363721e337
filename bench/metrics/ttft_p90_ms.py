"""90th percentile over every request sent in the window of the time from
its scheduled send to the moment the client saw its first token; a request
that never produced one counts as the longest wait."""
from benchkit import record


def read(run):
    return record.ttft_ms(run, 90)
