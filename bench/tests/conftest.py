"""Tiny cells for running the harness on the CPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

The cells keep their traffic's shape and their limits; only the sizes
shrink, from one file per name under ``bench/tests/tiny/``:

* ``tiny/configs/<config>.json``  the model's sizes, over the config's file;
* ``tiny/traffic/<traffic>.json`` the mix's overrides (engine and prompt
  lengths to fit the tiny model);
* ``tiny/limits/<driver>.json``   the limits a planned cell runs with until
  its own limits file is written from chip readings: at tiny sizes only,
  from CPU runs of the program and the faults.  Their keys are the limits
  the driver reads.

A new configuration or traffic mix adds its own file there, beside its
``bench/configs`` or ``bench/traffic`` file; no table here names it.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
TINY = pathlib.Path(__file__).resolve().parent / "tiny"


def tiny(kind: str, name: str) -> dict:
    """``tiny/<kind>/<name>.json``; a missing file names the file to add."""
    from bench.common import BenchError, load_json

    path = TINY / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no tiny {kind} for {name!r}: add {path.relative_to(ROOT)}")
    return load_json(path)


def planned_names() -> set:
    from bench.common import planned

    return {w["name"] for w in planned()}


def tiny_cell(name: str):
    from bench.common import load_json, resolve_cell, with_planned

    bench = with_planned()
    w = next(w for w in bench["workloads"] if w["name"] == name)
    driver = load_json(ROOT / "bench" / "traffic" / f"{w['traffic']}.json")["driver"]
    limits = tiny("limits", driver) if name in planned_names() else None
    cell = resolve_cell(name, bench, limits=limits)
    cell.config = {**cell.config, **tiny("configs", w["config"])}
    cell.traffic = {**cell.traffic, **tiny("traffic", w["traffic"])}
    cell.chips = 1
    return cell


def run_tiny(name: str, seconds: float = 2.0, trace: int = 0, seed: int = 2**32 + 5,
             cell=None) -> dict:
    """One run of the harness past its look for a chip, on the CPU, of the
    tiny cell ``name`` or of ``cell`` as given."""
    import jax

    from bench import peaks, run

    kind = jax.devices()[0].device_kind
    peaks.PEAKS.setdefault(kind, dict(peaks.PEAKS["TPU v5 lite"]))
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace)
    return run.run(args, jax.devices(), trace_seconds=0.5, cell=cell or tiny_cell(name))


@pytest.fixture(autouse=True)
def _fresh_hub():
    yield
    from repro.serve.workload import HUB

    HUB._models.clear()
    HUB._engines.clear()
