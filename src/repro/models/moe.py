"""Mixture-of-Experts with sort-based capacity dispatch (TPU-native).

Static-shape token→expert routing suitable for pjit + expert parallelism:

1. router scores → top-k experts per token;
2. flatten (token, choice) assignments and argsort by expert id;
3. slot each assignment into its expert's capacity buffer
   ``[E, C, d]`` (C = T·k/E·capacity_factor, tokens beyond capacity drop —
   sequence-order priority, GShard semantics);
4. grouped matmul ``[E,C,d]×[E,d,f]`` — MXU-aligned, and the E axis shards
   over the "model" mesh axis (expert parallelism; XLA inserts the
   all-to-all at the scatter/gather boundaries);
5. weighted scatter-add back to token order.

**Grouped dispatch** (the §Perf optimization): sorting a *globally
sharded* token axis makes GSPMD emit a distributed sort (collective
-catastrophic at 1M tokens).  With ``dispatch_groups=G`` matching the
data-parallel shard count, tokens reshape to ``[G, T/G]`` with G sharded
over (pod, data); the vmapped sort/slot then runs shard-LOCAL, and the
only cross-device traffic left is the unavoidable expert-parallel
all-to-all into the ``[G, E, C/G, d]`` buffers.  ``dispatch_groups`` is
read from the active parallel context (1 ⇒ original global semantics).

DeepSeek-style *shared experts* (always-on) run as a plain dense MLP next
to the routed path.  An auxiliary load-balancing loss (Switch-style) is
returned for training.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init
from repro.monitor.trace import scope
from repro.parallel import context as pctx

# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_moe(key: jax.Array, cfg: Any, dtype: Any) -> Params:
    d, m = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 7)
    p: Params = {
        "router": dense_init(ks[0], (d, m.n_experts), ("embed", "experts"), dtype),
        "w_gate": dense_init(
            ks[1], (m.n_experts, d, m.d_expert), ("experts", "embed", "expert_mlp"), dtype
        ),
        "w_up": dense_init(
            ks[2], (m.n_experts, d, m.d_expert), ("experts", "embed", "expert_mlp"), dtype
        ),
        "w_down": dense_init(
            ks[3], (m.n_experts, m.d_expert, d), ("experts", "expert_mlp", "embed"), dtype
        ),
    }
    if m.n_shared:
        f_sh = m.n_shared * m.d_expert
        # shared experts are SMALL (n_shared·d_expert): replicate them
        # ("shared_mlp" → None) so their down-projection needs no TP
        # all-reduce — one fewer [B,S,d] reduction per layer (§Perf).
        p["shared"] = {
            "w_gate": dense_init(ks[4], (d, f_sh), ("embed", "shared_mlp"), dtype),
            "w_up": dense_init(ks[5], (d, f_sh), ("embed", "shared_mlp"), dtype),
            "w_down": dense_init(ks[6], (f_sh, d), ("shared_mlp", "embed"), dtype),
        }
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _dispatch_groups(cfg: Any, t: int) -> int:
    """Shard-local dispatch group count from the active parallel context."""
    g = getattr(cfg, "_moe_groups_override", None)
    if g:
        return g if t % g == 0 else 1
    ctx = pctx.current()
    if ctx is None:
        return 1
    rules = ctx.rules.get("batch") or ()
    if isinstance(rules, str):
        rules = (rules,)
    g = 1
    for a in rules:
        g *= ctx.mesh.shape.get(a, 1)
    return g if g > 1 and t % g == 0 else 1


def _slot_assignments(
    gate_w: jnp.ndarray,      # [Tg, k]
    gate_e: jnp.ndarray,      # [Tg, k]
    *,
    e: int,
    cap: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort-based capacity slotting for ONE dispatch group.
    Returns (slot [Tg*k], keep [Tg*k], order [Tg*k])."""
    t, k = gate_e.shape
    flat_e = gate_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)                  # seq-order priority
    se = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(t * k, dtype=jnp.int32) - offsets[se]
    keep = pos < cap
    slot = jnp.where(keep, se * cap + pos, e * cap)
    return slot, keep, order


def _build_buf(xt_g, slot_g, keep_g, stok_g, *, n_rows, cap, d):
    """Scatter one group's tokens into (a slice of) the expert-capacity
    buffer.  ``slot_g`` already offset for local expert slices."""
    valid = keep_g & (slot_g >= 0) & (slot_g < n_rows)
    idx = jnp.where(valid, slot_g, n_rows)
    buf = jnp.zeros((n_rows + 1, d), xt_g.dtype).at[idx].set(xt_g[stok_g])
    return buf[:n_rows].reshape(n_rows // cap, cap, d)


def _combine_one_group(y_flat, slot_g, keep_g, sw_g, stok_g, *, n_rows, tg, d):
    """Scatter-add expert outputs back to token order for one group.
    ``y_flat`` holds ``n_rows`` expert-capacity rows (possibly only a local
    expert slice); slots outside [0, n_rows) contribute zero."""
    valid = keep_g & (slot_g >= 0) & (slot_g < n_rows)
    idx = jnp.clip(slot_g, 0, n_rows - 1)
    gathered = jnp.where(valid[:, None], y_flat[idx], 0.0)
    return jnp.zeros((tg, d), y_flat.dtype).at[stok_g].add(
        gathered * sw_g[:, None].astype(y_flat.dtype)
    )


def _batch_shard_count(ctx) -> int:
    rules = ctx.rules.get("batch") or ()
    if isinstance(rules, str):
        rules = (rules,)
    n = 1
    for a in rules:
        n *= ctx.mesh.shape.get(a, 1)
    return max(n, 1)


def _routed_group(
    router, w_gate, w_up, w_down, xt_g, *, e, cap, k, e_loc, e0
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Routing → dispatch → expert matmuls (a LOCAL expert slice) →
    partial combine, for one group's tokens.  Pure function of local data:
    runs identically in the auto path (e_loc=e, e0=0) and inside shard_map
    (e_loc=E/n_model, e0=shard offset).  Returns (y_partial [Tg,d],
    me_sum [E], ce_sum [E]) — the aux-loss sums over this group's tokens.
    """
    tg, d = xt_g.shape
    logits = (xt_g @ router).astype(jnp.float32)              # [Tg, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)
    me_sum = jnp.sum(probs, axis=0)
    ce_sum = jnp.sum(
        jnp.sum(jax.nn.one_hot(gate_e, e, dtype=jnp.float32), axis=1), axis=0
    ) / k
    slot, keep, order = _slot_assignments(gate_w, gate_e, e=e, cap=cap)
    sw = gate_w.reshape(-1)[order].astype(xt_g.dtype)
    stok = jnp.repeat(jnp.arange(tg, dtype=jnp.int32), k)[order]
    n_rows = e_loc * cap
    buf = _build_buf(xt_g, slot - e0 * cap, keep, stok, n_rows=n_rows, cap=cap, d=d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate))
    h = h * jnp.einsum("ecd,edf->ecf", buf, w_up)
    y_e = jnp.einsum("ecf,efd->ecd", h, w_down)
    y = _combine_one_group(
        y_e.reshape(n_rows, d).astype(xt_g.dtype),
        slot - e0 * cap, keep, sw, stok, n_rows=n_rows, tg=tg, d=d,
    )
    return y, me_sum, ce_sum


@scope("moe")
def moe_block(
    params: Params, x: jnp.ndarray, cfg: Any
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] → (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = m.top_k
    e = m.n_experts
    g = _dispatch_groups(cfg, t)
    tg = t // g
    cap = max(8, int(tg * k / e * m.capacity_factor))
    xt = x.reshape(g, tg, d)
    xt = pctx.constrain(xt, ("batch", None, None))            # G over (pod,data)

    ctx = pctx.current()
    use_shard_map = (
        ctx is not None
        and "model" in getattr(ctx.mesh, "axis_names", ())
        and (ctx.rules.get("experts") in ("model", ("model",)))
        and e % ctx.mesh.shape["model"] == 0
        and g % _batch_shard_count(ctx) == 0
    )
    if use_shard_map:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = ctx.mesh
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_model = mesh.shape["model"]
        e_loc = e // n_model

        def local_block(router, w_gate, w_up, w_down, sh_gate, sh_up, sh_down, xt_l):
            # xt_l: [G_loc, Tg, d]; expert weights: local E slice.  Tokens
            # are model-replicated ⇒ routing + dispatch are zero-comms;
            # the ONLY collective is the bf16 psum of the combined output.
            e0 = jax.lax.axis_index("model") * e_loc
            y, me_s, ce_s = jax.vmap(
                lambda xg: _routed_group(
                    router, w_gate, w_up, w_down, xg,
                    e=e, cap=cap, k=k, e_loc=e_loc, e0=e0,
                )
            )(xt_l)
            if sh_gate is not None:
                # shared experts, TP-sharded over f_sh: partial contribution
                # rides the SAME psum as the routed path (zero extra
                # collectives for the always-on experts).
                hs = jax.nn.silu(
                    jnp.einsum("gtd,df->gtf", xt_l, sh_gate)
                ) * jnp.einsum("gtd,df->gtf", xt_l, sh_up)
                y = y + jnp.einsum("gtf,fd->gtd", hs, sh_down).astype(y.dtype)
            y = jax.lax.psum(y.astype(xt_l.dtype), "model")
            # aux sums: every model shard computed identical me/ce (same
            # tokens); sum over the batch shards only.
            if batch_axes:
                me_s = jax.lax.psum(jnp.sum(me_s, axis=0), batch_axes)
                ce_s = jax.lax.psum(jnp.sum(ce_s, axis=0), batch_axes)
            else:
                me_s = jnp.sum(me_s, axis=0)
                ce_s = jnp.sum(ce_s, axis=0)
            return y, me_s, ce_s

        gaxis = batch_axes if len(batch_axes) != 1 else batch_axes[0]
        sh = params.get("shared")
        sh_specs = (
            (P(None, "model"), P(None, "model"), P("model"))
            if sh is not None
            else (P(), P(), P())
        )
        sh_args = (
            (sh["w_gate"], sh["w_up"], sh["w_down"]) if sh is not None
            else (None, None, None)
        )
        y, me_sum, ce_sum = shard_map(
            local_block,
            mesh=mesh,
            in_specs=(P(), P("model"), P("model"), P("model"),
                      *sh_specs, P(gaxis)),
            out_specs=(P(gaxis), P(), P()),
            check_vma=False,
        )(params["router"], params["w_gate"], params["w_up"],
          params["w_down"], *sh_args, xt)
    else:
        y, me_sum, ce_sum = jax.vmap(
            lambda xg: _routed_group(
                params["router"], params["w_gate"], params["w_up"],
                params["w_down"], xg, e=e, cap=cap, k=k, e_loc=e, e0=0,
            )
        )(xt)
        me_sum = jnp.sum(me_sum, axis=0)
        ce_sum = jnp.sum(ce_sum, axis=0)

    aux = e * jnp.sum((me_sum / t) * (ce_sum / t))
    y = pctx.constrain(y, ("batch", None, None)).astype(x.dtype)
    y = y.reshape(t, d)

    # shared (always-on) experts — DeepSeekMoE fine-grained design
    # (the shard_map path already fused them into the psum)
    if "shared" in params and not use_shard_map:
        sh = params["shared"]
        xf = x.reshape(t, d)
        hs = jax.nn.silu(xf @ sh["w_gate"]) * (xf @ sh["w_up"])
        y = y + hs @ sh["w_down"]

    return y.reshape(b, s, d), aux


def moe_flops_per_token(cfg: Any) -> int:
    """Active MAC-based FLOPs per token for roofline bookkeeping."""
    m = cfg.moe
    routed = 2 * 3 * cfg.d_model * m.d_expert * m.top_k
    shared = 2 * 3 * cfg.d_model * m.d_expert * m.n_shared
    router = 2 * cfg.d_model * m.n_experts
    return routed + shared + router
