"""90th percentile, over every request sent by the end of the traced
stretch, of the engine's own wait before admission: from ``submit`` to the
start of the request's prefill (``ServeRequest.admit_ns - submit_ns``, host
clock). Requests due while the profile stops are left out (see
``benchkit.engine_spans.queue_waits_ms``)."""
from benchkit import engine_spans, record


def read(run):
    waits = engine_spans.queue_waits_ms(run)
    return None if waits is None else record.percentile(waits, 90)
