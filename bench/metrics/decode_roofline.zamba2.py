"""Zamba2 decode programs' share of their roofline, in percent: per step
the larger of its bytes (weights once, a shared block's once per use,
each decoded row's recurrent state read and rewritten, keys and values of
the live positions) at peak HBM
bandwidth and its FLOPs at peak, summed, over decode device time
(``benchkit.hybrid_costs``). Decode is bound by bandwidth here."""
from benchkit import hybrid_costs, record


def read(run):
    ns, n = run.module(record.DECODE)
    c = run.config
    least = sum(max(hybrid_costs.decode_bytes(c, st.decode_rows)
                    / run.peak_bw,
                    hybrid_costs.decode_flops(c, st.decode_rows)
                    / run.peak_flops)
                for st in run.traced_steps() if st.decode_rows)
    return 100 * least / (ns / 1e9) if n and least else None
