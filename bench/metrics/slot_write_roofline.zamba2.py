"""The slot-write program's (``jit__write_slot``) share of its bandwidth
roofline, in percent: per admission the recurrent state and the S rows of
keys, values and positions that a prefill hands to the decode cache, read
once and written once (``benchkit.hybrid_costs.slot_write_bytes``), at
peak HBM bandwidth, over the program's device time."""
from benchkit import hybrid_costs

WRITE = r"^jit__write_slot\("


def read(run):
    ns, n = run.module(WRITE)
    c = run.config
    moved = sum(2 * sum(hybrid_costs.slot_write_bytes(c, S).values())
                for st in run.traced_steps() for S in st.prefill_lens)
    return 100 * moved / run.peak_bw / (ns / 1e9) if n and moved else None
