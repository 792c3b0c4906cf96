"""The reduction from trace events to busy time, idle gaps and shares."""
from __future__ import annotations

import pytest

from conftest import ROOT


def test_self_times():
    from bench.trace import self_times

    ops = [("while", 0.0, 10.0), ("fusion", 1.0, 3.0), ("copy", 4.0, 5.0), ("next", 10.0, 11.0)]
    assert dict(self_times(ops)) == {"while": 7.0, "fusion": 2.0, "copy": 1.0, "next": 1.0}


def test_union():
    from bench.trace import union

    assert union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_reduce_events_known_busy_and_gaps():
    from bench.trace import reduce_events

    devices = {
        0: [("fusion.1", 0.0, 1.0), ("all-gather.2", 0.5, 2.0), ("fusion.1", 3.0, 4.0)],
        1: [("fusion.1", 0.0, 4.0)],
    }
    modules = {0: [("jit_decode_all(7)", 0.0, 2.0), ("jit_decode_all(7)", 3.0, 4.0)],
               1: [("jit_decode_all(7)", 0.0, 4.0)]}
    host = [("generate", 1.5, 3.5), ("wait", 0.0, 5.0)]
    out = reduce_events(devices, modules, host, (0.0, 5.0))
    assert out["window_s"] == 5.0
    # device 0 busy [0, 2] and [3, 4] = 3 s; device 1 busy 4 s
    assert out["busy_s"] == pytest.approx(3.5)
    # collectives: device 0 spends 1.5 of its 3 busy seconds in all-gather
    assert out["collective_share"] == pytest.approx((1.5 / 3 + 0.0) / 2)
    # gaps of device 0, longest first, named by the innermost open span
    assert out["idle_gaps"] == [["generate", 1.0], ["wait", 1.0]]
    assert out["top_ops"][0] == ["fusion.1", pytest.approx((2.0 + 4.0) / 2)]
    mod = out["modules"]["jit_decode_all(7)"]
    assert mod["seconds"] == pytest.approx(3.5) and mod["count"] == pytest.approx(1.5)


def test_reduce_events_clips_to_window():
    from bench.trace import reduce_events

    out = reduce_events({0: [("op", -1.0, 1.0), ("op", 4.0, 9.0)]}, {}, [], (0.0, 5.0))
    assert out["busy_s"] == pytest.approx(2.0)
    assert out["idle_gaps"] == [["no benchmark span", 3.0]]


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: three host ``bench.generate`` spans of
    four runs of one small program each, with 20 ms host sleeps
    (``bench.wait``) after each, traced for 65 ms from the
    ``bench.trace_window`` mark."""
    from bench.trace import reduce_xplane

    path = ROOT / "bench" / "tests" / "data" / "v5e_small.xplane.pb"
    out = reduce_xplane(str(path), [0], (0.0, 0.065))
    (name, mod), = out["modules"].items()
    assert name.startswith("jit_") and mod["count"] == 12
    assert out["busy_s"] == pytest.approx(mod["seconds"], rel=0.01)
    assert 0 < out["busy_s"] < 0.001 and out["window_s"] == 0.065
    # the three sleeps are the longest gaps, and the clock correction puts
    # the short gaps between a span's four runs inside that span
    assert [n for n, _ in out["idle_gaps"][:3]] == ["wait"] * 3
    assert all(0.015 < t < 0.03 for _, t in out["idle_gaps"][:3])
    assert {n for n, _ in out["idle_gaps"][3:]} == {"generate"}
    assert {n for n, _ in out["top_ops"]} >= {"fusion", "convolution_tanh_fusion"}
