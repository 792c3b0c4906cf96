"""AdamW with mixed precision + ZeRO-friendly state layout.

* params live in the model dtype (bf16 on TPU); a master fp32 copy plus
  fp32 (m, v) moments form the optimizer state;
* the state tree is ZeRO-1 sharded over the data axes by
  ``repro.parallel.zero_shard_specs`` (the step factory applies it);
* global-norm clipping in fp32;
* optional gradient compression hook (see ``repro.optim.compress``).
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.monitor.trace import scope


def init_opt_state(params: Any) -> dict[str, Any]:
    # copy=True: fp32 params must not ALIAS the master copy (donation!)
    f32 = lambda p: jnp.array(p, dtype=jnp.float32, copy=True)  # noqa: E731
    return {
        "master": jax.tree.map(f32, params),
        "m": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "v": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "step": jnp.zeros((), jnp.int32),
    }


def abstract_opt_state(params: Any) -> dict[str, Any]:
    """ShapeDtypeStruct version (dry-run)."""
    f32 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)  # noqa: E731
    return {
        "master": jax.tree.map(f32, params),
        "m": jax.tree.map(f32, params),
        "v": jax.tree.map(f32, params),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def global_norm(tree: Any) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)
    )


@scope("optimizer")
def adamw_update(
    grads: Any,
    opt: dict[str, Any],
    *,
    schedule: Callable[[jnp.ndarray], jnp.ndarray],
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1e-8,
    weight_decay: float = 0.1,
    clip_norm: float = 1.0,
    param_dtype: Any = jnp.bfloat16,
) -> tuple[Any, dict[str, Any], dict[str, jnp.ndarray]]:
    """One AdamW step.  Returns (new_params, new_opt, metrics)."""
    step = opt["step"] + 1
    lr = schedule(step)
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9))
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)

    def upd(g, m, v, master):
        g = g.astype(jnp.float32) * scale
        m_new = b1 * m + (1 - b1) * g
        v_new = b2 * v + (1 - b2) * jnp.square(g)
        m_hat = m_new / bc1
        v_hat = v_new / bc2
        delta = m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * master
        master_new = master - lr * delta
        return m_new, v_new, master_new

    flat_g, treedef = jax.tree.flatten(grads)
    flat_m = treedef.flatten_up_to(opt["m"])
    flat_v = treedef.flatten_up_to(opt["v"])
    flat_ma = treedef.flatten_up_to(opt["master"])
    new_m, new_v, new_master = [], [], []
    for g, m, v, ma in zip(flat_g, flat_m, flat_v, flat_ma):
        mn, vn, man = upd(g, m, v, ma)
        new_m.append(mn)
        new_v.append(vn)
        new_master.append(man)
    new_opt = {
        "m": jax.tree.unflatten(treedef, new_m),
        "v": jax.tree.unflatten(treedef, new_v),
        "master": jax.tree.unflatten(treedef, new_master),
        "step": step,
    }
    new_params = jax.tree.map(lambda ma: ma.astype(param_dtype), new_opt["master"])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_params, new_opt, metrics
