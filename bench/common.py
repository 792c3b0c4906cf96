"""Shared pieces of the benchmark: finding a cell's files by name, seeds,
stratified draws, percentiles and the compile counter.

Everything that belongs to one configuration, traffic mix or metric sits
in a file of its own under ``bench/`` and is found here by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  the sizes as run, source and deployment;
* ``bench/traffic/<traffic>.json`` the mix's parameters, read by the
  driver its ``"driver"`` key names (``bench/drivers/<driver>.py``);
* ``bench/models/<reference>.py``  the plain reference its config names;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric;
* ``bench/limits/<cell>.json``     the limits ``correct`` is judged by.

``bench/planned_cells.json`` lists cells whose files are here and tested on
the CPU but which are not measured yet: ``calibrate.py`` and ``sweep.py``
take them, ``run.py`` does not.  A cell is planned only while
``BENCHMARK.json`` lacks it: a PR that measures one adds its entry there
and its limits file, and touches no file the benchmark already has.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import statistics
import sys
from dataclasses import dataclass
from types import ModuleType
from typing import Any

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """A cell, file or device the run needs is missing or wrong."""


def load_json(path: pathlib.Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path) -> ModuleType:
    """Import a file by path (names may hold dots, as metric names do)."""
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    key = "bench_file_" + str(path.relative_to(BENCH)).replace("/", "__").replace(".", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""

    name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    limits: dict[str, Any]
    end_to_end: list[dict[str, Any]]
    per_layer: list[dict[str, Any]]

    @property
    def driver(self) -> ModuleType:
        return load_module(BENCH / "drivers" / f"{self.traffic['driver']}.py")

    @property
    def reference(self) -> ModuleType:
        return load_module(BENCH / "models" / f"{self.config['reference']}.py")

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(BENCH / "metrics" / f"{name}.py")


def _applies(metric: dict[str, Any], cell: str, reported: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def planned(bench: dict[str, Any] | None = None) -> list[dict[str, Any]]:
    """The entries of ``planned_cells.json`` that ``BENCHMARK.json`` lacks."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    gated = {w["name"] for w in bench["workloads"]}
    return [w for w in load_json(BENCH / "planned_cells.json") if w["name"] not in gated]


def with_planned() -> dict[str, Any]:
    """``BENCHMARK.json`` with the still-planned cells among its workloads."""
    bench = load_json(ROOT / "BENCHMARK.json")
    bench["workloads"] = bench["workloads"] + planned(bench)
    return bench


def resolve_cell(name: str, bench: dict[str, Any] | None = None,
                 limits: dict[str, Any] | None = None) -> Cell:
    """The cell's files; ``limits`` stands in for its limits file where the
    cell has none yet (readings are taken before limits are set)."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    config = load_json(BENCH / "configs" / f"{w['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    if limits is None:
        limits = load_json(BENCH / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e, per_layer)


# ---------------------------------------------------------------------------
# seeds and draws
# ---------------------------------------------------------------------------
def jax_key(seed: int, salt: int = 0):
    """A PRNG key that uses every bit of a seed of any size."""
    import jax

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, salt)


def np_rng(seed: int, salt: int = 0):
    import numpy as np

    return np.random.default_rng([seed % 2**64, salt % 2**64])


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int) -> list[int]:
    """``n`` lengths at the midpoints of ``n`` equal-probability strata of a
    lognormal, clipped to [lo, hi]: the same multiset for every seed."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(median * math.exp(sigma * z))))))
    return out


def exponential_quantiles(n: int, mean: float) -> list[float]:
    """Midpoint quantiles of an exponential with ``mean`` (Poisson gaps)."""
    return [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]


def uniform_grid(n: int, lo: int, hi: int) -> list[int]:
    """``n`` integers spread evenly over [lo, hi], every value equally often
    where ``n`` allows."""
    span = hi - lo + 1
    return [lo + (i * span) // n for i in range(n)]


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in 0..100); inf counts as a value."""
    if not values:
        return math.inf
    vals = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(vals)))
    return vals[rank - 1]


def median(values: list[float]) -> float | None:
    return statistics.median(values) if values else None


# ---------------------------------------------------------------------------
# compile counter (after chip_smoke.Phases)
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts JAX backend compiles and persistent-cache hits and misses.

    A cache hit still goes through the compile path, so any event at all
    inside the measured window means a program was built there."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> tuple[int, int, int]:
        return self.compiles, self.hits, self.misses
