"""Median over the traced steps of the program's own ``train.batch`` plus
``train.dispatch`` spans (``repro.monitor.trace``), in ms on the host's
``perf_counter``: the host work that the device waits for while the loop
syncs on every step's loss.

The program keeps its spans only while the profiler traces, so this covers
the steps that start inside the traced part of the window (its first
seconds, ``bench/run.py``'s ``TRACE_SECONDS``), not the whole window.  The
spans are ``result["program_spans"]`` where the run drained them, else this
drains the tracer's buffer, as the one reader of it in the run.  A program
without the tracer gives none."""
import statistics

PARTS = ("train.batch", "train.dispatch")


def read(result, trace):
    spans = result.get("program_spans")
    if spans is None:
        try:
            from repro.monitor import trace as tracer
        except ImportError:
            return None
        spans = tracer.drain()
    per_step: dict = {}
    for s in spans:
        if s.name in PARTS and s.start >= result["t0"]:
            per_step.setdefault(s.attrs.get("step"), {})[s.name] = s.end - s.start
    steps = [sum(parts.values()) for parts in per_step.values() if len(parts) == len(PARTS)]
    return 1000.0 * statistics.median(steps) if steps else None
