"""Train-step factory: loss → grads → clipped AdamW → new state.

The returned ``train_step(state, batch)`` is what the dry-run lowers with
``jax.jit(..., in_shardings, out_shardings, donate_argnums=0)``;
``jit_train_step`` does the same for the ``Trainer`` on a real mesh, and
without a mesh the same function runs on a single device.

State layout::

    {"params": bf16 tree, "opt": {"master","m","v" fp32 trees, "step"}}

Distributed-optimization tricks wired here:

* grads stay bf16 across the data-parallel reduction (2× collective bytes
  vs fp32);
* optional int8 gradient round-trip (``compress_grads=True``) to measure
  accuracy headroom for 4× compression;
* optional Megatron-SP residual sharding (``residual_sharding=True``);
* donation of the full state (params + opt) so XLA reuses the buffers.
"""
from __future__ import annotations

import contextlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from repro.models.config import ArchConfig
from repro.models.lm import forward_train
from repro.optim.adamw import adamw_update
from repro.optim.compress import compress_tree
from repro.optim.schedule import cosine_with_warmup
from repro.parallel.context import ParallelContext, activate
from repro.parallel.sharding import spec_for, tree_shardings


def make_train_step(
    cfg: ArchConfig,
    *,
    mesh: Any = None,
    rules: Any = None,
    residual_sharding: bool = False,
    compress_grads: bool = False,
    schedule: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
    weight_decay: float = 0.1,
    clip_norm: float = 1.0,
) -> Callable[[dict[str, Any], dict[str, Any]], tuple[dict[str, Any], dict[str, Any]]]:
    schedule = schedule or cosine_with_warmup(cfg.max_lr)
    ctx = (
        ParallelContext(mesh, rules, residual_sharding=residual_sharding)
        if mesh is not None
        else None
    )

    def train_step(
        state: dict[str, Any], batch: dict[str, Any]
    ) -> tuple[dict[str, Any], dict[str, Any]]:
        cm = activate(ctx) if ctx is not None else contextlib.nullcontext()
        with cm:
            def loss_fn(params):
                loss, metrics = forward_train(params, batch, cfg)
                return loss, metrics

            (loss, fwd_metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state["params"])
            if compress_grads:
                grads = compress_tree(grads)
            new_params, new_opt, opt_metrics = adamw_update(
                grads,
                state["opt"],
                schedule=schedule,
                weight_decay=weight_decay,
                clip_norm=clip_norm,
                param_dtype=jnp.dtype(cfg.dtype),
            )
            metrics = {"loss": loss, **fwd_metrics, **opt_metrics}
            return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def jit_train_step(
    cfg: ArchConfig,
    *,
    mesh: Mesh | None = None,
    rules: Any = None,
    batch_shape: tuple[int, int] | None = None,
    schedule: Callable[[jnp.ndarray], jnp.ndarray] | None = None,
) -> tuple[Callable, Any]:
    """(jitted train step with the state donated, state shardings).

    With a ``mesh`` the state is laid out by ``rules`` over its logical
    specs, the ``batch_shape`` rows split over the batch axes, and the
    step's in and out shardings match: the state never gathers on one
    device.  Without one the shardings are None (single device)."""
    step = make_train_step(cfg, mesh=mesh, rules=rules, schedule=schedule)
    if mesh is None:
        return jax.jit(step, donate_argnums=(0,)), None
    sds, specs = abstract_train_state(cfg)
    state_sh = tree_shardings(sds, specs, mesh, rules)
    batch_sh = NamedSharding(mesh, spec_for(batch_shape, ("batch", None), mesh, rules))
    jitted = jax.jit(
        step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
    return jitted, state_sh


def init_train_state(
    key: jax.Array, cfg: ArchConfig, *, shardings: Any = None
) -> dict[str, Any]:
    """Fresh state; with ``shardings`` it is built in place, shard by
    shard, so a state larger than one device never lands on one."""
    from repro.models.lm import init_params_and_specs
    from repro.optim.adamw import init_opt_state

    def init(k: jax.Array) -> dict[str, Any]:
        params, _ = init_params_and_specs(k, cfg)
        return {"params": params, "opt": init_opt_state(params)}

    if shardings is None:
        return init(key)
    return jax.jit(init, out_shardings=shardings)(key)


def abstract_train_state(cfg: ArchConfig) -> tuple[dict[str, Any], dict[str, Any]]:
    """(ShapeDtypeStruct state tree, logical-spec state tree) — dry-run."""
    from repro.models.lm import abstract_params
    from repro.optim.adamw import abstract_opt_state

    params, specs = abstract_params(cfg)
    opt = abstract_opt_state(params)
    opt_specs = {
        "master": specs,
        "m": specs,
        "v": specs,
        "step": (),
    }
    return (
        {"params": params, "opt": opt},
        {"params": specs, "opt": opt_specs},
    )
