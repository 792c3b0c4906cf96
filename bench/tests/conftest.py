"""Tiny cells for running the harness on the CPU.

    JAX_PLATFORMS=cpu python -m pytest bench/tests

The cells keep their traffic's shape and their limits; only the model's
sizes shrink (``TINY``), with the engine and prompt lengths to fit.  The
planned cells, not measured yet, run with ``TINY_LIMITS``.
"""
from __future__ import annotations

import argparse
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY = {
    "smollm-360m": dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
                        num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
                        vocab_size=2048),
    "rwkv6-1.6b": dict(hidden_size=64, num_hidden_layers=2, head_size=16,
                       intermediate_size=128, vocab_size=2048),
}
TRAFFIC = {
    "batch_serve": dict(prompt_max=64, prompt_median=24, prompts_per_work=8,
                        max_new_tokens=[8, 16, 24, 32],
                        engine={"n_slots": 8, "prefill_batch": 4, "max_seq": 160}),
    "work_stream": dict(rate_per_s=4.0),
    "train": dict(batch_size=4, seq_len=64),
    "train_4chip": dict(batch_size=4, seq_len=64),
}


#: limits for the planned cells (``bench/planned_cells.json``), which have
#: no limits file until they are measured on the chip: at these tiny sizes
#: only, from CPU runs of the program and the faults
TINY_LIMITS = {
    "serve": {"served_gap": 0.15},
    "train": {"loss_gap": 0.001, "grad_gap": 0.03, "grad_raw_gap": 0.1, "update_gap": 0.1},
}


def planned_names() -> set:
    from bench.common import BENCH, load_json

    return {w["name"] for w in load_json(BENCH / "planned_cells.json")}


def tiny_cell(name: str):
    from bench.common import load_json, resolve_cell, with_planned

    bench = with_planned()
    traffic = next(w["traffic"] for w in bench["workloads"] if w["name"] == name)
    driver = load_json(ROOT / "bench" / "traffic" / f"{traffic}.json")["driver"]
    limits = TINY_LIMITS[driver] if name in planned_names() else None
    cell = resolve_cell(name, bench, limits=limits)
    cell.config = {**cell.config, **TINY[cell.config["arch"]]}
    cell.traffic = {**cell.traffic, **TRAFFIC[traffic]}
    cell.chips = 1
    return cell


def run_tiny(name: str, seconds: float = 2.0, trace: int = 0, seed: int = 2**32 + 5) -> dict:
    """One run of the harness past its look for a chip, on the CPU."""
    import jax

    from bench import peaks, run

    kind = jax.devices()[0].device_kind
    peaks.PEAKS.setdefault(kind, dict(peaks.PEAKS["TPU v5 lite"]))
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=trace)
    return run.run(args, jax.devices(), trace_seconds=0.5, cell=tiny_cell(name))


@pytest.fixture(autouse=True)
def _fresh_hub():
    yield
    from repro.serve.workload import HUB

    HUB._models.clear()
    HUB._engines.clear()
