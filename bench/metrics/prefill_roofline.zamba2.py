"""Zamba2 prefill programs' share of their roofline, in percent: per
prefill the larger of its FLOPs (weight matmuls, the SSD as its linear
recurrence, causal attention, the head at the last position) at peak
bf16 FLOP/s and its bytes at peak HBM bandwidth, summed, over the prefill
device time (``benchkit.hybrid_costs``). Prefill is bound by FLOPs."""
from benchkit import hybrid_costs, record


def read(run):
    ns, n = run.module(record.PREFILL)
    c = run.config
    least = sum(max(hybrid_costs.prefill_flops(c, S) / run.peak_flops,
                    hybrid_costs.prefill_bytes(c, S) / run.peak_bw)
                for st in run.traced_steps() for S in st.prefill_lens)
    return 100 * least / (ns / 1e9) if n and least else None
