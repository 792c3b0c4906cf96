"""The reduction from a JAX profiler trace to the device numbers.

``reduce_events`` is the arithmetic, on plain event lists; ``reduce_xplane``
reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` and feeds it.

A device's busy time is the union of the intervals of its operations on
the ``XLA Ops`` line; the window is the host's traced interval, placed on
the trace's clock by the host's ``bench.trace_window`` mark; an idle gap
is a stretch between two operations (or between the window's ends and
the first and last) in which no operation ran, named by the innermost
benchmark span (``bench.*``) open on the host at the gap's middle.  A
TPU trace's device clock runs behind the host's (by about 1.3 ms in a
recorded trace); ``reduce_xplane`` corrects it to within the host's
callback latency, so gaps shorter than that may be named by a
neighbouring span.
An operation's time in the top list is its self time: a ``while`` holds
its body's operations on the same line.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Iterable

COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
                        r"|allgather|allreduce|reducescatter", re.IGNORECASE)
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_name(hlo: str) -> str:
    """``%fusion.697 = f32[...] fusion(...)`` -> ``fusion.697``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def self_times(ops: list[tuple[str, float, float]]) -> list[tuple[str, float]]:
    """Each operation's time less the operations nested inside it (a
    ``while`` holds its body's operations on the same line)."""
    out: list[list] = []
    stack: list[int] = []
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and s >= out[stack[-1]][2]:
            stack.pop()
        if stack and e <= out[stack[-1]][2]:
            out[stack[-1]][3] -= e - s
        out.append([name, s, e, e - s])
        stack.append(len(out) - 1)
    return [(n, t) for n, _, _, t in out]


def reduce_events(devices: dict[int, list[tuple[str, float, float]]],
                  modules: dict[int, list[tuple[str, float, float]]],
                  host_spans: list[tuple[str, float, float]],
                  window: tuple[float, float], top: int = 10) -> dict:
    """Times in seconds on one clock.

    devices: per device, its operations (short name, start, end);
    modules: per device, its program executions (name, start, end);
    host_spans: (name, start, end) of the host's benchmark spans;
    window: the traced interval."""
    w0, w1 = window
    span = w1 - w0
    busy, coll, op_time, gaps = [], [], defaultdict(float), []
    for dev, ops in devices.items():
        ops = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        u = union((s, e) for _, s, e in ops)
        b = sum(e - s for s, e in u)
        busy.append(b)
        c = union((s, e) for n, s, e in ops if COLLECTIVE.search(n))
        coll.append(sum(e - s for s, e in c) / b if b > 0 else 0.0)
        for n, t in self_times(ops):
            op_time[n] += t / len(devices)
        if dev == min(devices):
            edges = [w0] + [x for iv in u for x in iv] + [w1]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    mod_time: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    for dev, mods in modules.items():
        for n, s, e in mods:
            if e > w0 and s < w1:
                rec = mod_time[n]
                rec[0] += (min(e, w1) - max(s, w0)) / len(modules)
                rec[1] += 1 / len(modules)
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        open_ = [h for h in host_spans if h[1] <= mid <= h[2]]
        name = min(open_, key=lambda h: h[2] - h[1])[0] if open_ else "no benchmark span"
        named.append([name, e - s])
    n_dev = max(1, len(devices))
    return {
        "window_s": span,
        "busy_s": sum(busy) / n_dev,
        "collective_share": sum(coll) / n_dev,
        "top_ops": [[n, t] for n, t in sorted(op_time.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": named,
        "modules": {n: {"seconds": v[0], "count": v[1]} for n, v in mod_time.items()},
    }


def _line(plane, name: str):
    for line in plane.lines:
        if line.name == name:
            return line
    return None


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def reduce_xplane(path: str, device_ids: list[int], window: tuple[float, float]) -> dict:
    """``window`` is the traced interval on ``time.perf_counter``; it is
    placed on the trace's clock at the host's ``bench.trace_window`` mark.

    Each device's events are moved onto the host's clock by the smallest
    gap between a program's end on the device and the host's
    ``CompleteCallbacks`` for the same run, which cannot be negative."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, modules, ends, host, callbacks = {}, {}, {}, [], {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            dev = int(m.group(1))
            ops = _line(plane, "XLA Ops")
            mods = _line(plane, "XLA Modules")
            devices[dev] = [(short_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in (ops.events if ops else [])]
            modules[dev] = []
            for e in (mods.events if mods else []):
                modules[dev].append((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9))
                ends[(dev, _stats(e).get("run_id"))] = e.end_ns * 1e-9
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name[6:], e.start_ns * 1e-9, e.end_ns * 1e-9))
                    elif e.name == "CompleteCallbacks":
                        st = _stats(e)
                        key = (int(st.get("device_ordinal", 0)), st.get("run_id"))
                        callbacks[key] = e.start_ns * 1e-9
    for dev in devices:
        lags = [callbacks[k] - end for k, end in ends.items() if k[0] == dev and k in callbacks]
        shift = max(0.0, min(lags)) if lags else 0.0
        devices[dev] = [(n, s + shift, e + shift) for n, s, e in devices[dev]]
        modules[dev] = [(n, s + shift, e + shift) for n, s, e in modules[dev]]
    anchor = [s for n, s, _ in host if n == "trace_window"]
    starts = [s for evs in devices.values() for _, s, _ in evs] + [s for _, s, _ in host]
    t0 = anchor[0] if anchor else (min(starts) if starts else 0.0)
    return reduce_events(devices, modules, host, (t0, t0 + (window[1] - window[0])))


def reduce_trace_dir(trace_dir: str, devices, window: tuple[float, float]) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return reduce_xplane(paths[0], [d.id for d in devices], window)
