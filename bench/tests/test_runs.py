"""Whole runs of the harness on the CPU at tiny sizes: a sound run is
``correct``; the control separates from the program; and with the timed
path broken underneath, ``correct`` comes out false for each fault the
cell can have."""
from __future__ import annotations

import json

import jax.numpy as jnp
import pytest

from conftest import ROOT, planned_names, run_tiny, tiny_cell

from bench.common import with_planned

BENCH = with_planned()
CELLS = [w["name"] for w in BENCH["workloads"]]
DRIVER = {w["name"]: json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json")
                                .read_text())["driver"] for w in BENCH["workloads"]}
SERVE = [n for n in CELLS if DRIVER[n] == "serve"]
TRAIN = [n for n in CELLS if DRIVER[n] == "train"]
MULTI_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] > 1]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    line = run_tiny(name)
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] > 0 and line["failed"] == 0
    if name not in planned_names():
        assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]
                                        if "workloads" not in m or name in m["workloads"]}


@pytest.mark.parametrize("driver", sorted(set(DRIVER.values())))
def test_run_holds_each_limit_its_driver_reads(driver):
    """Every limit in ``tiny/limits/<driver>.json`` comes back in the run's
    checks beside its number; a check the limits do not set is exact."""
    from conftest import tiny

    name = next(n for n in CELLS if DRIVER[n] == driver)
    cell = tiny_cell(name)
    cell.limits = {k: 1e6 + i for i, k in enumerate(tiny("limits", driver))}
    checks = run_tiny(name, cell=cell)["checks"]
    assert {k: checks[k]["limit"] for k in cell.limits if k in checks} == cell.limits
    assert all(c["limit"] == 0.0 for k, c in checks.items() if k not in cell.limits)


@pytest.mark.parametrize("name", SERVE)
def test_token_altered_is_not_correct(name, monkeypatch):
    from repro.serve import engine

    evict = engine.SlotBatcher.evict

    def altered(self, slot, reason):
        res = evict(self, slot, reason)
        res.tokens[-1] = (res.tokens[-1] + 1) % 2048
        return res

    monkeypatch.setattr(engine.SlotBatcher, "evict", altered)
    assert not run_tiny(name)["correct"]


def _broken_step(monkeypatch, wrap):
    from repro.train import step as step_mod

    make = step_mod.make_train_step

    def broken(*a, **kw):
        return wrap(make(*a, **kw))

    monkeypatch.setattr(step_mod, "make_train_step", broken)


@pytest.mark.parametrize("name", TRAIN)
def test_state_unchanged_is_not_correct(name, monkeypatch):
    _broken_step(monkeypatch, lambda step: lambda state, batch: (state, step(state, batch)[1]))
    assert not run_tiny(name)["correct"]


@pytest.mark.parametrize("name", TRAIN)
def test_half_batch_is_not_correct(name, monkeypatch):
    def half(step):
        return lambda state, batch: step(
            state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})

    _broken_step(monkeypatch, half)
    assert not run_tiny(name)["correct"]


@pytest.mark.parametrize("name", MULTI_CHIP)
def test_exchange_left_out_is_not_correct(name, monkeypatch):
    """What one of four chips computes when the all-reduce after each
    row-parallel projection is left out: its own quarter of the sum."""
    from repro.models import lm

    def local(w):
        keep = jnp.arange(w.shape[0]) < w.shape[0] // 4
        return w * keep[:, None].astype(w.dtype)

    time_mix, channel_mix = lm.rwkv6_time_mix, lm.rwkv6_channel_mix
    monkeypatch.setattr(lm, "rwkv6_time_mix",
                        lambda p, *a, **k: time_mix({**p, "w_o": local(p["w_o"])}, *a, **k))
    monkeypatch.setattr(lm, "rwkv6_channel_mix",
                        lambda p, *a, **k: channel_mix({**p, "cm_v": local(p["cm_v"])}, *a, **k))
    assert not run_tiny(name)["correct"]


@pytest.mark.parametrize("name", SERVE)
def test_serve_control_separates(name):
    """The fp8 control's widest gap is several times the program's."""
    import jax

    from bench import calibrate
    from bench.peaks import PEAKS

    ctx = calibrate._Ctx(jax.devices()[:1], PEAKS["TPU v5 lite"])
    rec = calibrate.serve_readings(tiny_cell(name), 2**31 + 3, ctx, 2.0, control=True)
    assert rec["control.served_gap"] >= 3 * rec["served_gap"] > 0


@pytest.mark.parametrize("name", TRAIN)
def test_train_control_separates(name):
    import jax

    from bench import calibrate
    from bench.peaks import PEAKS

    cell = tiny_cell(name)
    ctx = calibrate._Ctx(jax.devices()[:1], PEAKS["TPU v5 lite"])
    rec = calibrate.train_readings(cell, 2**31 + 3, ctx, control=True)
    assert max(rec["control.grad_gap"], rec["control.loss_gap"]) >= 3 * max(
        rec["grad_gap"], rec["loss_gap"])
