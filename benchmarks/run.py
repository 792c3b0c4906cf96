"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Modules:
  broker     — §2.2/§3.4.3 data-aware brokering vs greedy (repro.broker)
  carousel   — Fig. 9  (fine-grained Data Carousel)
  dag        — Fig. 10/11 (Rubin 100k-job DAG release)
  eventbus   — §3.2.2 backends + Coordinator merging
  scheduling — §3.4.3 hybrid event/poll latency + overhead
  hpo        — Fig. 12 (distributed HPO)
  al         — Fig. 13 (Active Learning)
  edge       — multi-tenant front door: 10k-client sim drill + long-poll HTTP economics
  kernels    — data-plane step/op timings (regression tracking)
  roofline   — §Roofline terms from the dry-run cache
  sim        — deterministic fault-scenario throughput (repro.sim)
  serving    — continuous-batching offline inference (repro.serve)
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module list")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_al,
        bench_broker,
        bench_carousel,
        bench_dag,
        bench_edge,
        bench_eventbus,
        bench_hpo,
        bench_kernels,
        bench_scheduling,
        bench_serving,
        bench_sim,
        roofline,
    )

    modules = {
        "broker": bench_broker,
        "carousel": bench_carousel,
        "dag": bench_dag,
        "edge": bench_edge,
        "eventbus": bench_eventbus,
        "scheduling": bench_scheduling,
        "hpo": bench_hpo,
        "al": bench_al,
        "kernels": bench_kernels,
        "roofline": roofline,
        "sim": bench_sim,
        "serving": bench_serving,
    }
    selected = (
        {k: modules[k] for k in args.only.split(",")} if args.only else modules
    )
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in selected.items():
        t0 = time.time()
        try:
            rows = mod.run()
        except Exception as exc:  # noqa: BLE001
            failures += 1
            print(f"{name}/ERROR,0,{json.dumps(str(exc))}")
            continue
        for row in rows:
            print(
                f"{row['name']},{row['us_per_call']:.2f},"
                f"{json.dumps(row['derived'], sort_keys=True)}"
            )
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
