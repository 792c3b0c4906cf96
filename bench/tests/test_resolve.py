"""Every cell and metric of BENCHMARK.json resolves to its files by name."""
from __future__ import annotations

import json

import pytest

from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = [m["name"] for m in BENCH["per_layer"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    from bench.common import resolve_cell

    cell = resolve_cell(name)
    assert cell.driver.setup and cell.driver.measure and cell.driver.check
    assert cell.reference.make_weights and cell.reference.program_config
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    assert cell.limits, "every cell has limits"


@pytest.mark.parametrize("name", METRICS)
def test_metric_resolves(name):
    from bench.common import BENCH as DIR, load_module

    reader = load_module(DIR / "metrics" / f"{name}.py")
    assert callable(reader.read)
    metric = next(m for m in BENCH["per_layer"] if m["name"] == name)
    moves = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    assert set(metric["workloads"]) <= set(moves.get("workloads", CELLS))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_the_run(config):
    """The program's config is built from the file; the sizes match."""
    from bench.common import load_json, load_module, BENCH as DIR

    c = load_json(ROOT / config["file"])
    assert c["source"] == config["source"] and c["reduced"] == config["reduced"]
    ref = load_module(DIR / "models" / f"{c['reference']}.py")
    cfg = ref.program_config(c)
    assert cfg.vocab_size == c["vocab_size"] and cfg.d_model == c["hidden_size"]
    assert cfg.n_layers == c["num_hidden_layers"]


def test_planned_cells_resolve_apart():
    """Planned cells have their files but no limits, and stay out of
    BENCHMARK.json until they are measured; a cell is planned only while
    BENCHMARK.json lacks it."""
    from bench.common import BENCH as DIR, planned, resolve_cell, with_planned

    still = planned()
    assert not {w["name"] for w in still} & set(CELLS)
    for w in still:
        assert not (DIR / "limits" / f"{w['name']}.json").exists()
        cell = resolve_cell(w["name"], with_planned(), limits={})
        assert cell.driver.setup and cell.reference.make_weights


@pytest.mark.parametrize("name", CELLS)
def test_limits_file_is_what_its_driver_reads(name):
    """Every cell of BENCHMARK.json has its limits file, holding exactly the
    limits its driver reads (the keys of ``tiny/limits/<driver>.json``)."""
    from conftest import tiny

    from bench.common import BENCH as DIR, load_json

    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    path = DIR / "limits" / f"{name}.json"
    assert path.is_file(), f"add {path.relative_to(ROOT)}"
    driver = load_json(DIR / "traffic" / f"{w['traffic']}.json")["driver"]
    assert set(load_json(path)) == set(tiny("limits", driver))


def test_with_planned_names_each_cell_once():
    from bench.common import with_planned

    names = [w["name"] for w in with_planned()["workloads"]]
    assert len(names) == len(set(names))
    assert names[: len(CELLS)] == CELLS


def test_missing_file_is_an_error(tmp_path):
    from bench.common import BenchError, load_module

    with pytest.raises(BenchError):
        load_module(ROOT / "bench" / "metrics" / "no_such_metric.py")
