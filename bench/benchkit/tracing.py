"""The profiler over one stretch of the window, and the compile counter."""
from __future__ import annotations

import glob
import os
import time
from typing import Optional

from jax.profiler import TraceAnnotation


class Tracer:
    """Traces ``seconds`` of the window from ``start_s`` on, when enabled;
    ``tick(now)`` is called between steps and says whether the next step
    falls inside the traced stretch."""

    def __init__(self, enabled: bool, start_s: float, seconds: float,
                 logdir: str):
        self.enabled, self.start_s, self.end_s = \
            enabled, start_s, start_s + seconds
        self.logdir = logdir
        self.active = False
        self.done = False
        self.stop_s = 0.0
        self._span: Optional[TraceAnnotation] = None

    def tick(self, now: float) -> bool:
        if self.enabled and not self.active and not self.done \
                and now >= self.start_s:
            import jax
            jax.profiler.start_trace(self.logdir)
            self._span = TraceAnnotation("bench.window")
            self._span.__enter__()
            self.active = True
        elif self.active and now >= self.end_s:
            self.stop()
        return self.active

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self._span.__exit__(None, None, None)
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t
        self.active, self.done = False, True

    def path(self) -> Optional[str]:
        found = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        return found[0] if found else None


class CompileCounter:
    """Counts programs built and persistent-cache loads, process-wide."""

    def __init__(self):
        from jax import monitoring
        self.built = 0
        self.loaded = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.built += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def snapshot(self):
        return self.built, self.loaded
