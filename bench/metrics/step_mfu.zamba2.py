"""Zamba2's model FLOPs of every token the traced steps processed
(prefill and decode, ``benchkit.hybrid_costs``), over the traced stretch
at the chip's peak bf16 FLOP/s, in percent: the whole step's share of the
chip."""
from benchkit import hybrid_costs


def read(run):
    c = run.config
    flops = sum(sum(hybrid_costs.prefill_flops(c, S) for S in st.prefill_lens)
                + (hybrid_costs.decode_flops(c, st.decode_rows)
                   if st.decode_rows else 0)
                for st in run.traced_steps())
    w = run.traced_window_s()
    return 100 * flops / run.peak_flops / w if flops and w > 0 else None
