"""Host spans the benchmark records around its calls into the program.

Each span is kept in memory as ``(name, tag, start, end)`` on the
``time.perf_counter`` clock and is also a ``jax.profiler.TraceAnnotation``,
so that in a traced run the device's idle gaps can be named by the span
that was open on the host.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Iterator


class SpanLog:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.spans: list[tuple[str, Any, float, float]] = []

    def add(self, name: str, tag: Any, start: float, end: float) -> None:
        with self._lock:
            self.spans.append((name, tag, start, end))

    @contextlib.contextmanager
    def span(self, name: str, tag: Any = None) -> Iterator[None]:
        import jax

        start = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                yield
        finally:
            self.add(name, tag, start, time.perf_counter())

    def named(self, name: str, t0: float = float("-inf"), t1: float = float("inf")):
        """Spans called ``name`` that start inside [t0, t1]."""
        with self._lock:
            return [s for s in self.spans if s[0] == name and t0 <= s[2] <= t1]
