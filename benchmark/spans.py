"""Host spans the benchmark puts AROUND its calls into the program.

Totals and counts per name are kept in memory (two clock reads a span);
in a traced run every span is also a ``jax.profiler.TraceAnnotation`` named
``host:<name>``, so the profiler records it on its own clock and
``trace_reduce`` can say what the host was doing in each device gap.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict

PREFIX = "host:"


class Spans:
    def __init__(self):
        self.traced = False   # set while the profiler is on
        self.total_s: dict[str, float] = defaultdict(float)
        self.count: dict[str, int] = defaultdict(int)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.traced:
            import jax

            ann = jax.profiler.TraceAnnotation(PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.total_s[name] += time.perf_counter() - t0
            self.count[name] += 1
            if ann is not None:
                ann.__exit__(None, None, None)

    def reset(self) -> None:
        self.total_s.clear()
        self.count.clear()
