"""Device time of the train cell by phase and model scope, from one traced window.

    python3 bench/scopes.py --workload smollm-360m.train --seed <n> --seconds <s>

The program names every block of the model with a ``jax.named_scope``
(``repro.monitor.trace.SCOPES``), and ``repro.monitor.trace.op_scopes`` maps
each operation of a compiled step to a phase (forward, backward, recompute,
optimizer) and a scope.  This runs the cell's set-up and window as
``bench/run.py --trace 1`` does, builds that map from the step the window
runs, and reduces the trace with every operation's self time.  It prints the
table of self time by phase and scope on standard error, and one JSON line:
the shares of busy time that the table gives (``SHARES``) and
``host_ms_per_step.train``, device busy and window, the longest operations
with their phase and scope, and the idle gaps named by the innermost
``bench.*`` or ``repro.*`` host span.  It does not decide
``correct``; ``bench/run.py`` does.  It compiles every program cold, with
JAX's persistent cache off (``main`` says why).

``bench/run.py`` and ``bench/trace.py`` pass no map and keep only the ten
longest operations, so the harness's own traced run cannot read the shares;
``read_xplane``, ``by_scope`` and ``SHARES`` are what those files and three
readers under ``bench/metrics/`` would take over.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import shutil
import sys
import time
from collections import defaultdict

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: shares of device busy time, each the self time of the operations whose
#: (phase, scope) it takes: attention in every phase, the recompute of the
#: forward inside the backward, and the trunk scan's own operations
SHARES = {
    "attention_share.train": lambda phase, scope: scope == "attention",
    "remat_share.train": lambda phase, scope: phase == "recompute",
    "layer_scan_share.train": lambda phase, scope: scope == "layers",
}

#: host spans that name idle gaps: the benchmark's and the program's
HOST_PREFIXES = ("bench.", "repro.")


def read_xplane(path: str, device_ids: list[int]) -> tuple[dict, list, float]:
    """(device operations, host spans, the trace's window mark) as
    ``bench.trace.reduce_xplane`` reads them, with the program's ``repro.*``
    spans among the host spans."""
    from jax.profiler import ProfileData

    from bench.trace import _DEVICE_PLANE, _line, _stats, short_name

    pd = ProfileData.from_file(path)
    devices, ends, host, callbacks = {}, {}, [], {}
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            dev = int(m.group(1))
            ops, mods = _line(plane, "XLA Ops"), _line(plane, "XLA Modules")
            devices[dev] = [(short_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9)
                            for e in (ops.events if ops else [])]
            for e in (mods.events if mods else []):
                ends[(dev, _stats(e).get("run_id"))] = e.end_ns * 1e-9
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES):
                        name = e.name.split(".", 1)[1]
                        host.append((name, e.start_ns * 1e-9, e.end_ns * 1e-9))
                    elif e.name == "CompleteCallbacks":
                        st = _stats(e)
                        key = (int(st.get("device_ordinal", 0)), st.get("run_id"))
                        callbacks[key] = e.start_ns * 1e-9
    for dev in devices:
        lags = [callbacks[k] - end for k, end in ends.items() if k[0] == dev and k in callbacks]
        shift = max(0.0, min(lags)) if lags else 0.0
        devices[dev] = [(n, s + shift, e + shift) for n, s, e in devices[dev]]
    anchor = [s for n, s, _ in host if n == "trace_window"]
    starts = [s for evs in devices.values() for _, s, _ in evs] + [s for _, s, _ in host]
    t0 = anchor[0] if anchor else (min(starts) if starts else 0.0)
    return devices, host, t0


def by_scope(op_self_s: dict[str, float], op_map: dict[str, tuple[str, str]]) -> dict:
    """Self time per ``phase/scope``; an operation the map lacks counts as
    ``unmapped/unscoped``, so the values add up to the operations' time."""
    out: dict[str, float] = defaultdict(float)
    for name, t in op_self_s.items():
        phase, scope = op_map.get(name, ("unmapped", "unscoped"))
        out[f"{phase}/{scope}"] += t
    return dict(out)


def reduce(devices: dict, host: list, window: tuple[float, float], op_map: dict,
           top: int = 10) -> dict:
    """``bench.trace.reduce_events`` over every operation, with ``scope_s``
    (self time per ``phase/scope``) and each of the ``top`` operations named
    with its phase and scope."""
    from bench.trace import reduce_events

    n_ops = sum(len(ops) for ops in devices.values())
    out = reduce_events(devices, {}, host, window, top=max(top, n_ops))
    op_self_s = dict(out["top_ops"])
    out["scope_s"] = by_scope(op_self_s, op_map)
    out["top_ops"] = [[f"{n} {'/'.join(op_map.get(n, ('unmapped', 'unscoped')))}", t]
                      for n, t in out["top_ops"][:top]]
    out["idle_gaps"] = out["idle_gaps"][:top]
    return out


def shares(scope_s: dict, busy_s: float) -> dict:
    """Each of ``SHARES`` in % of busy time; ``None`` with no busy time."""
    out = {}
    for name, takes in SHARES.items():
        t = sum(t for key, t in scope_s.items() if takes(*key.split("/")))
        out[name] = 100.0 * t / busy_s if busy_s > 0 else None
    return out


def table(scope_s: dict, busy_s: float) -> str:
    """Self time per phase and scope, longest first, in seconds and as a
    share of busy time, and their sum."""
    if busy_s <= 0:
        return "no busy time"
    rows = [f"{k:<24}{t:10.4f} s {100.0 * t / busy_s:8.2f}%"
            for k, t in sorted(scope_s.items(), key=lambda x: -x[1])]
    total = sum(scope_s.values())
    rows.append(f"{'sum':<24}{total:10.4f} s {100.0 * total / busy_s:8.2f}% of busy "
                f"{busy_s:.4f} s")
    return "\n".join(rows)


def scope_run(cell, seed: int, seconds: float, devices) -> dict:
    """The cell's set-up and one traced window on ``devices``; the line."""
    from bench import run as harness
    from bench.common import BenchError
    from bench.peaks import peaks_for
    from repro.monitor import trace as tracer

    ctx = harness.Context(devices, trace=True)
    ctx.peaks = peaks_for(devices[0].device_kind)
    ctx.process_start = time.perf_counter()
    driver, t, c = cell.driver, cell.traffic, cell.config
    state = driver.setup(cell, seed, ctx)
    trainer = state.trainer
    batch = driver.batch(seed, 1, t["batch_size"], t["seq_len"], c["vocab_size"])
    t0 = time.perf_counter()
    op_map = tracer.op_scopes(trainer.step_fn.lower(trainer.state, batch).compile().as_text())
    print(f"[scopes] map of {len(op_map)} operations in {time.perf_counter() - t0:.2f} s",
          file=sys.stderr)
    if all(scope == "unscoped" for _, scope in op_map.values()):
        raise BenchError("the compiled step's metadata holds none of the program's scopes: it "
                         "was loaded from a cache entry that a program without them wrote")
    result = driver.measure(state, seconds, ctx)
    driver.release(state)
    result["program_spans"] = spans = tracer.drain()
    compiles, hits, misses = ctx.window_compiles
    by_id = {s.id: s for s in spans}
    where = [(s.attrs["in"], by_id[s.parent_id].attrs.get("step") if s.parent_id in by_id
              else None) for s in spans if s.name == "compile" and s.start >= result["t0"]]
    print(f"[window] compiles={compiles} cache_hits={hits} cache_misses={misses}; "
          f"under program spans (span, step): {where}", file=sys.stderr)
    try:
        path, = glob.glob(os.path.join(ctx.trace_dir, "**", "*.xplane.pb"), recursive=True)
        dev_ops, host, mark = read_xplane(path, [d.id for d in devices])
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    w0, w1 = ctx.trace_window
    summary = reduce(dev_ops, host, (mark, mark + w1 - w0), op_map)
    print(table(summary["scope_s"], summary["busy_s"]), file=sys.stderr)
    unmapped = sorted({n for ops in dev_ops.values() for n, _, _ in ops} - set(op_map))
    print(f"[scopes] {len(unmapped)} operation names not in the map: {unmapped[:20]}",
          file=sys.stderr)
    return {
        "workload": cell.name, "seed": seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices),
                   "busy_s": summary["busy_s"], "window_s": summary["window_s"]},
        "train_tokens_per_s": result["tokens_per_s"],
        "metrics": {**shares(summary["scope_s"], summary["busy_s"]),
                    "host_ms_per_step.train":
                        cell.metric_reader("host_ms_per_step.train").read(result, summary)},
        "scope_s": summary["scope_s"],
        "breakdown": {"device_ops": summary["top_ops"], "idle_gaps": summary["idle_gaps"]},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="smollm-360m.train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench.common import BenchError, resolve_cell

    cell = resolve_cell(args.workload)
    if cell.traffic["driver"] != "train":
        raise BenchError(f"{cell.name} is not a train cell")
    import jax

    # Compile cold: the persistent cache's key leaves out each operation's
    # op_name, so a cached step may carry the metadata of whichever program
    # wrote it (one without the scopes, say), and the map would be stale.
    jax.config.update("jax_enable_compilation_cache", False)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX's first device is {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 1
    line = scope_run(cell, args.seed, args.seconds, devices[: cell.chips])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    from bench.common import BenchError

    try:
        sys.exit(main())
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
