"""Readings that the limits in ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--controls 3] [--seconds 8] [--out calib.jsonl]

One process, on the chip.  For each seed it drives the cell's timed path
as a run does (set-up, and for serve cells a short window at the cell's
own load) and prints the numbers ``correct`` compares, without limits.
For the first ``--controls`` seeds it also reads the control (the
reference in fp8 put in the program's place) and, for train cells, the
faults planted in the reference: half of the batch left out, and on
several chips the exchange between them left out.  A state left
unchanged reads 1 on ``update_gap`` by its definition and needs no run.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class _Ctx:
    """The parts of the harness's context a driver needs, without a window
    that counts."""

    def __init__(self, devices, peaks):
        from bench.spans import SpanLog

        self.devices, self.peaks = devices, peaks
        self.spans = SpanLog()
        self.process_start = time.perf_counter()

    def start_window(self, seconds):
        return time.perf_counter()

    def end_window(self):
        pass


def serve_readings(cell, seed, ctx, seconds, control: bool) -> dict:
    from bench import common
    from bench.check import served_gaps
    from bench.drivers import serve

    state = serve.setup(cell, seed, ctx)
    serve.measure(state, seconds, ctx)
    serve.release(state)
    sample = serve.sample_requests(state, cell.traffic["check_requests"])
    gaps = served_gaps(cell.reference, cell.config, common.jax_key(seed), sample,
                       cell.traffic["engine"]["max_seq"], control="fp8" if control else None)
    out = {"served_gap": gaps["served"], "served_tokens": sum(len(t) for _, t in sample)}
    if control:
        out["control.served_gap"] = gaps["control"]
    return out


def worst_leaf(prog: dict, ref: dict) -> list:
    """[leaf, program norm, reference norm] of the leaf with the widest gap
    (a non-finite program norm first)."""
    med = statistics.median(ref.values())

    def gap(k):
        return abs(prog[k] - ref[k]) / max(ref[k], med) if math.isfinite(prog[k]) else math.inf

    k = max(ref, key=gap)
    return [k, prog[k], ref[k]]


def worst_leaves(prog: dict, ref: dict) -> dict:
    return {"grad_raw": worst_leaf(prog["grad"]["raw"], ref["grad"]["raw"]),
            "update": worst_leaf(prog["change"], ref["change"])}


def train_readings(cell, seed, ctx, control: bool) -> dict:
    from bench import common
    from bench.check import reference_train, train_gaps
    from bench.drivers import train

    state = train.setup(cell, seed, ctx)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in ctx.devices)
    train.release(state)
    t, c = cell.traffic, cell.config
    batches = [train.batch(seed, i, t["batch_size"], t["seq_len"], c["vocab_size"])
               for i in (1, 2, 3)]
    key = common.jax_key(seed)
    ref = reference_train(cell.reference, c, key, batches, t["optimizer"], ctx.devices)
    out = dict(train_gaps(state.prog, ref))
    out["losses"], out["ref_losses"] = state.prog["losses"], ref["losses"]
    out["peak_bytes_after_setup"] = peak
    out["worst"] = worst_leaves(state.prog, ref)
    if control:
        runs = {"control": {"precision": "fp8"}, "half_batch": {"fault": "half_batch"}}
        if len(ctx.devices) > 1:
            runs["no_exchange"] = {"fault": "no_exchange"}
        for name, kw in runs.items():
            low = reference_train(cell.reference, c, key, batches, t["optimizer"],
                                  ctx.devices, **kw)
            out.update({f"{name}.{k}": v for k, v in train_gaps(low, ref).items()})
            out[f"{name}.worst"] = worst_leaves(low, ref)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out")
    args = ap.parse_args()
    from bench.common import resolve_cell, with_planned
    from bench.peaks import peaks_for
    from bench.run import enable_compile_cache

    enable_compile_cache()
    import jax

    cell = resolve_cell(args.workload, with_planned(), limits={})
    devices = jax.devices()[: cell.chips]
    if devices[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    ctx = _Ctx(devices, peaks_for(devices[0].device_kind))
    out = open(args.out, "a") if args.out else None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if cell.traffic["driver"] == "train":
            rec = train_readings(cell, seed, ctx, i < args.controls)
        else:
            rec = serve_readings(cell, seed, ctx, args.seconds, i < args.controls)
        rec = {"cell": cell.name, "seed": seed, "seconds": time.perf_counter() - t0, **rec}
        print(json.dumps(rec), flush=True)
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
