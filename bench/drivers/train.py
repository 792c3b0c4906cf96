"""Train steps of ``Trainer`` on every chip of the cell.

Set-up builds one ``Trainer`` (on several chips it shards the state over a
(data, model) mesh with ``FSDP_RULES`` by itself), replaces its state with
the benchmark's own weights and a fresh AdamW state, and drives it through
its first three steps with the same ``Trainer.run`` call and batch feed the
window uses: that compiles and warms the step, and gives the readings that
``correct`` compares with the reference.  The window then runs whole steps
until ``--seconds`` have passed.

Batches are uniform random token rows drawn from the seed, every row
different.
"""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from bench import common
from bench.check import leaf_norms, reference_train, train_gaps, weight_change


def batch(seed: int, step: int, batch_size: int, seq_len: int, vocab: int) -> dict:
    """Step ``step``'s rows (1-based): tokens and next-token labels."""
    rows = common.np_rng(seed, 1000 + step).integers(0, vocab, (batch_size, seq_len + 1))
    return {"tokens": rows[:, :-1].astype(np.int32), "labels": rows[:, 1:].astype(np.int32)}


def feed(seed: int, batch_size: int, seq_len: int, vocab: int):
    step = 0
    while True:
        step += 1
        yield batch(seed, step, batch_size, seq_len, vocab)


@dataclass
class State:
    cell: Any
    seed: int
    ctx: Any
    trainer: Any
    traffic: dict
    config: dict
    prog: dict = field(default_factory=dict)


def program_state(ref, c: dict, key) -> dict:
    """The program's train state from the benchmark's weights: bf16 params,
    a float32 master copy, zero moments, step 0."""
    import jax.numpy as jnp

    params = ref.to_program(ref.make_weights(c, key))
    f32 = lambda p: p.astype(jnp.float32)  # noqa: E731
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)  # noqa: E731
    import jax

    return {"params": params, "opt": {
        "master": jax.tree.map(f32, params), "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params), "step": jnp.zeros((), jnp.int32)}}


def setup(cell, seed: int, ctx) -> State:
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer

    c, t, ref = cell.config, cell.traffic, cell.reference
    opt = t["optimizer"]
    if max(5, opt["total_steps"] // 20) != opt["warmup_steps"]:
        raise common.BenchError("Trainer warms up over max(5, total_steps // 20) steps")
    cfg = ref.program_config(c).replace(max_lr=opt["max_lr"])
    b, s = t["batch_size"], t["seq_len"]
    mesh = make_host_mesh(ctx.devices) if len(ctx.devices) > 1 else None
    trainer = Trainer(cfg, batch_iter=feed(seed, b, s, c["vocab_size"]), batch_size=b,
                      seq_len=s, total_steps=opt["total_steps"], mesh=mesh)
    trainer.state = None  # the program's own initial weights are not used
    key = common.jax_key(seed)
    trainer.state = jax.jit(lambda k: program_state(ref, c, k),
                            out_shardings=trainer.state_shardings)(key)
    state = State(cell, seed, ctx, trainer, t, c)
    # steps 1-3: the window's own call and feed
    trainer.run(1)
    m = jax.jit(lambda st: leaf_norms(ref.from_program(st["opt"]["m"])))(trainer.state)
    gnorm = trainer.history[0]["grad_norm"]
    clipped = {k: float(v) / (1 - opt["b1"]) for k, v in m.items()}
    scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-9))
    trainer.run(2)
    change = jax.jit(lambda st, k: weight_change(ref, c, ref.from_program(st["opt"]["master"]), k))(
        trainer.state, key)
    state.prog = {
        "losses": [h["loss"] for h in trainer.history[:3]],
        "grad": {"gnorm": gnorm, "clipped": clipped,
                 "raw": {k: v / scale for k, v in clipped.items()}},
        "change": {k: float(v) for k, v in change.items()},
    }
    return state


def measure(state: State, seconds: float, ctx) -> dict:
    trainer, t = state.trainer, state.traffic
    tokens_per_step = t["batch_size"] * t["seq_len"]
    t0 = ctx.start_window(seconds)
    setup_s = t0 - ctx.process_start
    steps = 0
    while True:
        with ctx.spans.span("train_step", tag=steps):
            trainer.run(1)
        steps += 1
        if time.perf_counter() - t0 >= seconds:
            break
    t_end = time.perf_counter()
    ctx.end_window()
    losses = [h["loss"] for h in trainer.history[3:]]
    failed = sum(1 for x in losses if not np.isfinite(x))
    rate = steps * tokens_per_step / (t_end - t0)
    print(f"[train] {steps} steps in {t_end - t0:.3f} s; {rate:.1f} tokens/s; "
          f"losses {losses[0]:.5f} .. {losses[-1]:.5f}", file=sys.stderr)
    ref = state.cell.reference
    return {
        "e2e": {"setup_s": setup_s, "train_tokens_per_s": rate},
        "attempted": steps, "failed": failed, "window_s": t_end - t0, "t0": t0,
        "t_end": t_end, "seconds": seconds, "spans": ctx.spans, "config": state.config,
        "reference": ref, "peaks": ctx.peaks, "chips": len(ctx.devices),
        "flops_per_token": ref.train_flops_per_token(state.config, t["seq_len"]),
        "tokens_per_s": rate,
    }


def release(state: State) -> None:
    state.trainer.state = None
    state.trainer = None


def check(state: State) -> dict:
    t, c = state.traffic, state.config
    batches = [batch(state.seed, i, t["batch_size"], t["seq_len"], c["vocab_size"])
               for i in (1, 2, 3)]
    ref = reference_train(state.cell.reference, c, common.jax_key(state.seed), batches,
                          t["optimizer"], state.ctx.devices)
    gaps = train_gaps(state.prog, ref)
    print(f"[check] program losses {state.prog['losses']} reference {ref['losses']}; "
          f"first gradient norm {state.prog['grad']['gnorm']} reference {ref['grad']['gnorm']}",
          file=sys.stderr)
    return {k: {"value": gaps[k], "limit": lim} for k, lim in state.cell.limits.items()}
