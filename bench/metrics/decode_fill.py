"""Live rows over the rows the decodes launched, in percent, over the
traced stretch: the ``rows`` (live) and ``batch`` (launched) args of the
engine's ``engine.decode`` spans, which the program keeps only while a
profile is on (``benchkit.engine_spans.records``). A program whose decode
spans carry no ``batch`` (one that launches every slot) gives ``None``."""
from benchkit import engine_spans


def read(run, recs=None):
    recs = engine_spans.records() if recs is None else recs
    args = [r["args"] for r in recs or ()
            if r["name"] == "engine.decode" and r["end_ns"] is not None]
    if not args or any("batch" not in a for a in args):
        return None
    return 100 * sum(a["rows"] for a in args) / sum(a["batch"] for a in args)
