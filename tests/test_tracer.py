"""The program's tracer (``repro.monitor.trace``): spans, counters, the
compile listener, and the named scopes read back from a compiled step."""
from __future__ import annotations

import contextlib
import importlib.util
import pathlib
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.monitor import trace


@pytest.fixture()
def tracer():
    trace.drain()
    yield trace
    trace.drain()


@pytest.fixture()
def profiling(tmp_path):
    """A JAX profiler trace, the one switch that makes the tracer keep spans."""
    @contextlib.contextmanager
    def trace_running():
        jax.profiler.start_trace(str(tmp_path))
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    return trace_running


def test_records_nothing_while_off(tracer):
    assert not tracer.recording()
    with tracer.span("train.batch", step=1):
        with tracer.span("inner"):
            pass
    assert tracer.drain() == []


def test_nested_spans_with_parents_and_rising_times(tracer, profiling):
    with profiling():
        with tracer.span("train.dispatch", step=7):
            with tracer.span("a"):
                pass
            with tracer.span("b", k="v"):
                pass
        with tracer.span("train.sync", step=7):
            pass
    with tracer.span("after"):
        pass
    a, b, outer, sync = tracer.drain()
    assert [s.name for s in (a, b, outer, sync)] == ["a", "b", "train.dispatch", "train.sync"]
    assert a.parent_id == b.parent_id == outer.id and outer.parent_id is None
    assert sync.parent_id is None and len({a.id, b.id, outer.id, sync.id}) == 4
    assert outer.attrs == {"step": 7} and b.attrs == {"k": "v"}
    assert outer.start <= a.start <= a.end <= b.start <= b.end <= outer.end <= sync.start
    assert tracer.drain() == []


def test_spans_of_other_threads_have_their_own_parents(tracer, profiling):
    with profiling(), tracer.span("main"):
        t = threading.Thread(target=_one_span, args=(tracer,))
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    spans = {s.name: s for s in tracer.drain()}
    assert spans["worker"].parent_id is None


def _one_span(tracer):
    with tracer.span("worker"):
        pass


def test_counters_add_up_across_threads(tracer):
    before = tracer.counters().get("test.hits", 0)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=lambda: [tracer.count("test.hits", 2)
                                                    for _ in range(2000)])
                   for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert tracer.counters()["test.hits"] - before == 16 * 2000 * 2


def test_records_while_a_profiler_trace_runs(tracer, profiling):
    with profiling():
        assert tracer.recording()
        with tracer.span("traced"):
            pass
    assert not tracer.recording()
    with tracer.span("untraced"):
        pass
    assert [s.name for s in tracer.drain()] == ["traced"]


def test_compile_is_counted_and_kept_under_the_open_span(tracer, profiling):
    c0 = tracer.counters()
    with profiling(), tracer.span("train.dispatch", step=3):
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones((7, 5)))
    spans = tracer.drain()
    c1 = tracer.counters()
    assert c1["compile.count"] - c0.get("compile.count", 0) >= 1
    assert c1["compile.s"] > c0.get("compile.s", 0)
    dispatch = next(s for s in spans if s.name == "train.dispatch")
    compiles = [s for s in spans if s.name == "compile"]
    assert compiles and all(s.parent_id == dispatch.id for s in compiles)
    assert all(s.attrs == {"in": "train.dispatch"} for s in compiles)
    assert all(dispatch.start <= s.start <= s.end <= dispatch.end for s in compiles)


def test_scope_rejects_unlisted_names():
    with pytest.raises(ValueError):
        trace.scope("attn")


@pytest.mark.parametrize("op_name, expected", [
    ("jit(step)/jvp(layers)/while/body/closed_call/attention/dot_general",
     ("forward", "attention")),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/attention/mul",
     ("backward", "attention")),
    ("jit(step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general", ("recompute", "mlp")),
    ("jit(step)/transpose(jvp(layers))/while/body/dynamic_update_slice", ("backward", "layers")),
    ("jit(step)/jvp(layers)/while/body/closed_call/attention/norm/mul", ("forward", "norm")),
    ("jit(step)/optimizer/sub", ("optimizer", "optimizer")),
    ("jit(step)/transpose(jvp())/broadcast_in_dim", ("backward", "unscoped")),
    ("jit(step)/jvp(embed)/gather", ("forward", "embed")),
])
def test_op_phase_scope(op_name, expected):
    assert trace.op_phase_scope(op_name) == expected


def test_op_scopes_of_a_compiled_train_step():
    from repro.configs import smoke_config
    from repro.train.step import init_train_state, make_train_step

    cfg = smoke_config("smollm-360m").replace(n_layers=2, remat="full")
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": np.zeros((2, 32), np.int32), "labels": np.ones((2, 32), np.int32)}
    hlo = jax.jit(make_train_step(cfg)).lower(state, batch).compile().as_text()
    ops = trace.op_scopes(hlo)
    scopes = {s for _, s in ops.values()}
    phases = {p for p, _ in ops.values()}
    assert {"embed", "attention", "mlp", "norm", "layers", "head_loss", "optimizer"} <= scopes
    assert set(trace.PHASES) <= phases
    assert {s for p, s in ops.values() if p == "recompute"} >= {"attention", "mlp", "norm"}
    # keyed by the names a device trace gives the step's operations
    assert any("fusion" in n for n in ops)


def test_trainer_spans_and_counters(tracer, profiling, tmp_path):
    from repro.configs import smoke_config
    from repro.train.trainer import Trainer

    cfg = smoke_config("smollm-360m").replace(n_layers=1)
    trainer = Trainer(cfg, batch_size=2, seq_len=16, total_steps=10,
                      ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
    c0 = tracer.counters()
    with profiling():
        out = trainer.run(3)
    spans = tracer.drain()
    c1 = tracer.counters()
    names = [(s.name, s.attrs["step"]) for s in spans if s.name.startswith("train.")]
    step_spans = [n for n in names if n[0] != "train.checkpoint"]
    assert step_spans == [(n, i) for i in (1, 2, 3)
                          for n in ("train.batch", "train.dispatch", "train.sync")]
    assert [n for n in names if n[0] == "train.checkpoint"] == [
        ("train.checkpoint", 2), ("train.checkpoint", 3)]
    assert c1["train.steps"] - c0.get("train.steps", 0) == 3
    assert c1["train.tokens"] - c0.get("train.tokens", 0) == 3 * 2 * 16
    # wall_s is on the spans' clock: it holds every span of the call
    timed = [s for s in spans if s.name.startswith("train.")]
    covered = max(s.end for s in timed) - min(s.start for s in timed)
    assert covered <= out["wall_s"] < covered + 0.5
    assert out["tokens_per_s"] == pytest.approx(3 * 2 * 16 / out["wall_s"])


def test_model_path_does_not_import_the_orchestrator():
    import os
    import pathlib

    src = str(pathlib.Path(trace.__file__).resolve().parents[2])
    env = {**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"}
    code = ("import sys, repro.train.trainer, repro.serve.step; "
            "print('repro.core.workflow' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=120, check=True, env=env)
    assert out.stdout.strip() == "False"


def test_chip_smoke_phase_line_reads_the_tracers_counters(capsys):
    from repro.configs import smoke_config
    from repro.train.trainer import Trainer

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    phases = chip_smoke.Phases()

    def compiled():
        trace.count("compile.s", 1.25)  # as the compile listener adds a compile's time
        return float(jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(3))[0])

    assert phases.run("jitted", compiled) == 3.0
    line = capsys.readouterr().out.strip()
    head, tail = line.split(" | ")
    fields = dict(kv.split("=") for kv in head.split()[3:])
    assert head.startswith("[phase] jitted: ok ") and tail == "3.0"
    assert set(fields) == {"wall_s", "compile_s", "cache_hits", "cache_misses"}
    assert float(fields["compile_s"]) >= 1.2

    cfg = smoke_config("smollm-360m").replace(n_layers=1)
    trainer = Trainer(cfg, batch_size=2, seq_len=16, total_steps=10)
    phases.run("train", lambda: trainer.run(2)["steps"])
    fields = dict(kv.split("=") for kv in capsys.readouterr().out.split(" | ")[0].split()[3:])
    assert fields["train_steps"] == "2" and fields["train_tokens"] == str(2 * 2 * 16)
    # on the CPU the step is traced with the chunked scan, never the kernel
    assert fields["attention_flash"] == "0" and int(fields["attention_chunked"]) >= 1
