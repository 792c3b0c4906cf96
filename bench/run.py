"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it finds the cell's files by the names in ``BENCHMARK.json``
(``bench/common.py`` says where each lives), refuses to run without a
TPU or with fewer chips than the cell asks for, builds the cell's weights
and inputs from ``--seed``, warms up every shape the cell's traffic uses
(set-up), measures for ``--seconds`` (the window), checks what the timed
path produced against the plain reference, and prints one JSON line as
the last line of standard output.  With ``--trace 1`` the window's first
seconds are traced with the JAX profiler and the line carries the
per-layer metrics instead of the end-to-end ones.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.common import BenchError, CompileCounter, resolve_cell  # noqa: E402

#: seconds of the window the profiler records in a traced run
TRACE_SECONDS = 4.0


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says; every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Context:
    """What a driver gets from the harness: spans, compile counter, the
    devices it may use, and the profiler's start and stop."""

    def __init__(self, devices, trace: bool, trace_seconds: float = TRACE_SECONDS):
        from bench.spans import SpanLog

        self.devices = devices
        self.spans = SpanLog()
        self.compiles = CompileCounter()
        self.trace = trace
        self.trace_seconds = trace_seconds
        self.trace_dir: str | None = None
        self.trace_window: tuple[float, float] | None = None
        self._timer: threading.Timer | None = None
        self._stop_lock = threading.Lock()
        self.window_start: float | None = None
        self.window_compiles: tuple[int, int, int] | None = None

    def start_window(self, seconds: float) -> float:
        """Called by the driver at the window's first instant."""
        import jax

        self._compiles0 = self.compiles.snapshot()
        if self.trace:
            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.trace_dir)
            with jax.profiler.TraceAnnotation("bench.trace_window"):
                pass  # the mark that puts the trace's clock on the host's
            self._timer = threading.Timer(min(seconds, self.trace_seconds), self.stop_trace)
            self._timer.daemon = True
            self._timer.start()
        self.window_start = time.perf_counter()
        return self.window_start

    def stop_trace(self) -> None:
        """Stops the profiler once, from the timer or from ``end_window``;
        the second caller waits until the first has written the trace."""
        import jax

        with self._stop_lock:
            if self.trace_window is not None or self.trace_dir is None:
                return
            t1 = time.perf_counter()
            jax.profiler.stop_trace()
            self.trace_window = (self.window_start, t1)

    def end_window(self) -> None:
        """Called by the driver when the window's work is done."""
        now = self.compiles.snapshot()
        self.window_compiles = tuple(b - a for a, b in zip(self._compiles0, now))
        if self._timer is not None:
            self._timer.cancel()
            self.stop_trace()


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def per_layer_metrics(cell, result, trace_summary) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(result, trace_summary)
        if value is None:
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args, devices, trace_seconds: float = TRACE_SECONDS, cell=None) -> dict:
    """One run of one cell on ``devices``; returns the result line.  Tests
    pass a ``cell`` of their own (tiny sizes) and CPU devices."""
    from bench.peaks import peaks_for
    from bench.trace import reduce_trace_dir

    cell = cell or resolve_cell(args.workload)
    if len(devices) < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
    devices = devices[: cell.chips]
    kind = devices[0].device_kind
    ctx = Context(devices, trace=bool(args.trace), trace_seconds=trace_seconds)
    ctx.peaks = peaks_for(kind)
    ctx.process_start = PROCESS_START
    driver = cell.driver
    state = driver.setup(cell, args.seed, ctx)
    result = driver.measure(state, float(args.seconds), ctx)
    result["memory_peak_bytes"] = memory_peak(devices)
    compiles, hits, misses = ctx.window_compiles
    print(f"[window] compiles={compiles} cache_hits={hits} cache_misses={misses} "
          f"(programs built inside the window; should be 0)", file=sys.stderr, flush=True)
    driver.release(state)
    checks = driver.check(state)
    trace_summary = None
    if args.trace:
        trace_summary = reduce_trace_dir(ctx.trace_dir, devices, ctx.trace_window)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        metrics = per_layer_metrics(cell, result, trace_summary)
    else:
        metrics = {}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": result["e2e"][m["name"]], "unit": m["unit"]}
    correct = bool(result["failed"] == 0 and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values()))
    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": result["memory_peak_bytes"],
    }
    line = {"correct": correct, "attempted": result["attempted"], "failed": result["failed"],
            "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary["busy_s"]
        device["window_s"] = trace_summary["window_s"]
        line["breakdown"] = {"device_ops": trace_summary["top_ops"],
                             "idle_gaps": trace_summary["idle_gaps"]}
    line["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "BENCHMARK.json").is_file():
        print("bench: no BENCHMARK.json beside bench/", file=sys.stderr)
        return 2
    resolve_cell(args.workload)  # every file of the cell is there
    import repro  # noqa: F401  the system under test is beside the benchmark

    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 1
    line = run(args, devices)
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
