"""Device time by phase and model scope (``bench/scopes.py``), and the
reader of the program's train spans."""
from __future__ import annotations

import pytest

from conftest import ROOT

from bench.common import load_module

BENCH = ROOT / "bench"


def reader(name: str):
    return load_module(BENCH / "metrics" / f"{name}.py")


def test_scope_self_times_add_up_to_busy_and_gaps_take_program_spans():
    from bench.scopes import reduce

    devices = {0: [("while.1", 0.0, 10.0), ("fusion.1", 1.0, 3.0), ("fusion.2", 4.0, 6.0),
                   ("fusion.3", 12.0, 13.0)]}
    host = [("train_step", 0.0, 14.0), ("train.batch", 10.5, 11.5)]
    op_map = {"while.1": ("forward", "layers"), "fusion.1": ("forward", "attention"),
              "fusion.2": ("recompute", "mlp")}
    out = reduce(devices, host, (0.0, 14.0), op_map)
    assert out["busy_s"] == pytest.approx(11.0)
    assert out["scope_s"] == pytest.approx({"forward/layers": 6.0, "forward/attention": 2.0,
                                            "recompute/mlp": 2.0, "unmapped/unscoped": 1.0})
    assert sum(out["scope_s"].values()) == pytest.approx(out["busy_s"])
    # the gap (10, 12) is named by the program's span nested in the benchmark's
    assert out["idle_gaps"] == [["train.batch", pytest.approx(2.0)],
                                ["train_step", pytest.approx(1.0)]]
    assert out["top_ops"][0] == ["while.1 forward/layers", pytest.approx(6.0)]
    assert ["fusion.3 unmapped/unscoped", pytest.approx(1.0)] in out["top_ops"]


def test_read_xplane_reads_the_recorded_chip_trace_as_the_harness_does():
    from bench.scopes import read_xplane, reduce
    from bench.trace import reduce_xplane

    path = str(BENCH / "tests" / "data" / "v5e_small.xplane.pb")
    devices, host, mark = read_xplane(path, [0])
    ours = reduce(devices, host, (mark, mark + 0.065), {})
    theirs = reduce_xplane(path, [0], (0.0, 0.065))
    assert ours["busy_s"] == pytest.approx(theirs["busy_s"])
    assert ours["idle_gaps"] == theirs["idle_gaps"]
    assert sum(ours["scope_s"].values()) == pytest.approx(ours["busy_s"], rel=1e-6)


def test_table_rows_add_up():
    from bench.scopes import table

    text = table({"forward/attention": 2.0, "backward/attention": 1.0, "forward/layers": 1.0},
                 4.0)
    assert text.splitlines()[0].split() == ["forward/attention", "2.0000", "s", "50.00%"]
    assert "100.00% of busy 4.0000 s" in text.splitlines()[-1]
    assert table({}, 0.0) == "no busy time"


SUMMARY = {"busy_s": 10.0, "scope_s": {
    "forward/attention": 2.0, "recompute/attention": 1.5, "backward/attention": 2.5,
    "recompute/mlp": 1.0, "backward/layers": 0.5, "forward/layers": 0.25,
    "optimizer/optimizer": 2.0, "unmapped/unscoped": 0.25}}


@pytest.mark.parametrize("name, value", [
    ("attention_share.train", 60.0), ("remat_share.train", 25.0),
    ("layer_scan_share.train", 7.5)])
def test_scope_shares(name, value):
    from bench.scopes import shares

    assert shares(SUMMARY["scope_s"], SUMMARY["busy_s"])[name] == pytest.approx(value)
    assert shares({}, SUMMARY["busy_s"])[name] == 0.0
    assert shares({}, 0.0)[name] is None


def test_host_ms_per_step_reader(monkeypatch):
    from repro.monitor import trace
    from repro.monitor.trace import Span

    read = reader("host_ms_per_step.train").read
    spans = [
        Span("train.batch", 1, None, 0.5, 0.6, {"step": 1}),  # before the window
        Span("train.batch", 2, None, 1.000, 1.002, {"step": 2}),
        Span("train.dispatch", 3, None, 1.002, 1.005, {"step": 2}),
        Span("train.sync", 4, None, 1.005, 3.0, {"step": 2}),
        Span("train.batch", 5, None, 3.000, 3.001, {"step": 3}),
        Span("train.dispatch", 6, None, 3.001, 3.010, {"step": 3}),
        Span("train.batch", 7, None, 4.000, 4.001, {"step": 4}),  # cut before dispatch
    ]
    monkeypatch.setattr(trace, "drain", lambda: spans)
    assert read({"t0": 1.0}, None) == pytest.approx((5.0 + 10.0) / 2)
    monkeypatch.setattr(trace, "drain", lambda: [])
    assert read({"t0": 1.0}, None) is None
    # spans the run drained into its result come first
    assert read({"t0": 1.0, "program_spans": spans[:4]}, None) == pytest.approx(5.0)
