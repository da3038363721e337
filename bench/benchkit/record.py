"""What a run hands to the metric readers, and the arithmetic they share.

End-to-end readers take host-clock times from the client's records; per-
layer readers take the reduced profiler trace of the traced stretch and
the steps the harness saw inside it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import trace_reduce
from benchkit import costs

#: module names as the engine's jits produce them (see PERF.md: fragile
#: until the program names its programs)
PREFILL = r"^jit__prefill_last\("
DECODE = r"^jit__lambda\("


@dataclasses.dataclass
class Run:
    config: Dict                      # published keys of the configuration
    peaks: Dict                       # this device's row of peaks.json
    setup_s: float
    window_s: float                   # the window measured
    sent: List = dataclasses.field(default_factory=list)
    steps: List = dataclasses.field(default_factory=list)
    trace: Optional[trace_reduce.Trace] = None

    # ------------------------------------------------------ client side
    def ttfts(self) -> np.ndarray:
        """Seconds from each request's scheduled send to its first token;
        a request that never produced one counts as the longest wait."""
        out = []
        for s in self.sent:
            out.append(s.times[0] - s.req.send_s if s.times else np.inf)
        x = np.asarray(out, float)
        if np.isinf(x).any():
            finite = x[np.isfinite(x)]
            x[np.isinf(x)] = max(self.window_s, *(finite.tolist() or [0]))
        return x

    def token_gaps(self) -> np.ndarray:
        """Every gap between consecutive output tokens, pooled over all
        requests."""
        gaps = [np.diff(s.times) for s in self.sent if len(s.times) > 1]
        return np.concatenate(gaps) if gaps else np.zeros(0)

    def tokens_per_s(self) -> float:
        return sum(st.tokens for st in self.steps) / self.window_s

    # ------------------------------------------------------ traced side
    def traced_window(self) -> Tuple[int, int]:
        return self.trace.window()

    def traced_steps(self) -> List:
        return [st for st in self.steps if st.traced]

    def module(self, pattern: str) -> Tuple[float, int]:
        a, b = self.traced_window()
        return trace_reduce.module_time(self.trace, pattern, a, b)

    def busy_s(self) -> Optional[float]:
        """Device-busy seconds of the traced stretch; None where the trace
        holds no device."""
        if not self.trace.modules:
            return None
        a, b = self.traced_window()
        return trace_reduce.busy_ns(self.trace, a, b) / 1e9

    def traced_window_s(self) -> float:
        a, b = self.traced_window()
        return (b - a) / 1e9

    @property
    def peak_flops(self) -> float:
        return self.peaks["bf16_flops_per_s"]

    @property
    def peak_bw(self) -> float:
        return self.peaks["hbm_bytes_per_s"]


def percentile(x: Sequence[float], q: float) -> Optional[float]:
    x = np.asarray(x, float)
    return float(np.percentile(x, q)) if x.size else None


# ---------------------------------------------------------------- readers
def ttft_ms(run: Run, q: float) -> Optional[float]:
    v = percentile(run.ttfts(), q)
    return None if v is None else v * 1e3


def itl_ms(run: Run, q: float) -> Optional[float]:
    v = percentile(run.token_gaps(), q)
    return None if v is None else v * 1e3


def prefill_us_per_tok(run: Run) -> Optional[float]:
    ns, n = run.module(PREFILL)
    toks = sum(sum(st.prefill_lens) for st in run.traced_steps())
    return ns / 1e3 / toks if n and toks else None


def prefill_roofline(run: Run) -> Optional[float]:
    """Least time the prefills could take at peak bf16 FLOP/s (they are
    compute-bound), over their device time, in percent."""
    ns, n = run.module(PREFILL)
    flops = sum(costs.prefill_flops(run.config, S)
                for st in run.traced_steps() for S in st.prefill_lens)
    return 100 * flops / run.peak_flops / (ns / 1e9) if n and flops else None


def decode_ms(run: Run) -> Optional[float]:
    ns, n = run.module(DECODE)
    return ns / 1e6 / n if n else None


def decode_roofline(run: Run) -> Optional[float]:
    """Least time each decode could take (the larger of its bytes at peak
    bandwidth and its FLOPs at peak), summed, over decode device time."""
    ns, n = run.module(DECODE)
    c = run.config
    least = sum(max(costs.decode_bytes(c, st.decode_rows) / run.peak_bw,
                    costs.decode_flops(c, st.decode_rows) / run.peak_flops)
                for st in run.traced_steps() if st.decode_rows)
    return 100 * least / (ns / 1e9) if n and least else None


def decode_mfu(run: Run) -> Optional[float]:
    ns, n = run.module(DECODE)
    flops = sum(costs.decode_flops(run.config, st.decode_rows)
                for st in run.traced_steps())
    return 100 * flops / run.peak_flops / (ns / 1e9) if n and flops else None


def step_mfu(run: Run) -> Optional[float]:
    """Model FLOPs of every token the traced steps processed, over the
    traced window at peak: the whole step's share of the chip."""
    flops = sum(st.flops(run.config) for st in run.traced_steps())
    w = run.traced_window_s()
    return 100 * flops / run.peak_flops / w if flops and w > 0 else None


def idle_share(run: Run) -> Optional[float]:
    w, busy = run.traced_window_s(), run.busy_s()
    return 100 * (1 - busy / w) if busy is not None and w > 0 else None


def engine_host_ms(run: Run) -> Optional[float]:
    """Per ``step()`` call: its host span minus the device-busy time
    inside it, averaged over the traced steps."""
    a, b = run.traced_window()
    spans = trace_reduce.spans_named(run.trace, "bench.step", a, b)
    if not spans or not run.trace.modules:
        return None
    busy = [trace_reduce.busy_ns(run.trace, s, e) for s, e in spans]
    return sum((e - s) - u for (s, e), u in zip(spans, busy)) \
        / len(spans) / 1e6
