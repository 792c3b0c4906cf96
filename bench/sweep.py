"""Find the highest rate an open-loop serve cell sustains, on the chip.

    python3 bench/sweep.py --workload smollm-360m.work_stream \
        --rates 1,2,3,4 --seconds 20 --seed 1

One process: one set-up, then for each rate a window of ``--seconds`` at
that rate (the cell's traffic file with only ``rate_per_s`` changed).
Prints per rate the Works sent, the median and 90th-percentile latency
from each Work's due time, and how far behind the last Work finished
(a backlog that grows through the window shows as a drain far above the
median).  The cell's rate is then set at about four fifths of the highest
rate whose drain stays flat.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from bench import common
    from bench.calibrate import _Ctx
    from bench.drivers import serve
    from bench.peaks import peaks_for
    from bench.run import enable_compile_cache

    enable_compile_cache()
    import jax

    cell = common.resolve_cell(args.workload, common.with_planned(), limits={})
    devices = jax.devices()[:1]
    if devices[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 1
    ctx = _Ctx(devices, peaks_for(devices[0].device_kind))
    state = serve.setup(cell, args.seed, ctx)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            state.traffic = {**cell.traffic, "rate_per_s": rate}
            state.done.clear()
            res = serve.measure(state, args.seconds, ctx)
            lat = res["latencies"]
            print(json.dumps({
                "rate_per_s": rate, "works": len(lat), "failed": res["failed"],
                "p50_s": common.percentile(lat, 50), "p90_s": common.percentile(lat, 90),
                "drain_s": res["t_end"] - res["t0"] - args.seconds,
                "engine_busy_s": res["engine"]["prefill_s"] + res["engine"]["decode_s"],
            }), flush=True)
    finally:
        serve.release(state)
    return 0


if __name__ == "__main__":
    sys.exit(main())
