"""RWKV6 WKV Pallas TPU kernel — chunked linear attention with
data-dependent per-channel decay.

TPU adaptation of the CUDA wkv kernel: instead of one thread per channel
marching through time, the sequence is cut into chunks of L tokens; the
grid is ``(B·H, n_chunks)`` with the chunk dimension *arbitrary*
(sequential) so the [K,V] fp32 state lives in VMEM scratch across chunks.
Within a chunk everything is dense linear algebra sized for the VPU/MXU:
the pairwise decay tensor exp(cum_{t-1}−cum_j) (all exponents ≤ 0 ⇒
numerically safe), an [L,L] intra-chunk attention matmul, and rank-L
state updates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(
    r_ref,      # [1, L, K]
    k_ref,      # [1, L, K]
    v_ref,      # [1, L, V]
    w_ref,      # [1, L, K]  (log-decay, <= 0)
    u_ref,      # [1, 1, K]  (bonus, per head)
    o_ref,      # [1, L, V]
    state_scr,  # VMEM [K, V] f32
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0].astype(jnp.float32)          # [L, K]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)          # [L, V]
    w = w_ref[0].astype(jnp.float32)          # [L, K]
    u = u_ref[0].astype(jnp.float32)          # [1, K]
    state = state_scr[...]

    kk = state.shape[0]
    # [L, L, K] time indices, built 3-D (Mosaic cannot reshape i1 masks)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, kk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk, kk), 1)
    # inclusive prefix sum over time as a masked reduction (Mosaic has no
    # cumsum lowering); exact f32 adds, no MXU rounding
    cum = jnp.sum(jnp.where(t_idx >= j_idx, w[None, :, :], 0.0), axis=1)  # [L, K]
    cum_prev = cum - w
    # pairwise decay exp(cum_prev[t] - cum[j]) for j < t (≤ 0 ⇒ stable)
    diff = cum_prev[:, None, :] - cum[None, :, :]          # [L, L, K]
    tri = t_idx > j_idx
    dmat = jnp.where(tri, jnp.exp(diff), 0.0)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * dmat, axis=2)   # [L, L]
    y = jax.lax.dot_general(
        att.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # diagonal bonus
    s_diag = jnp.sum(r * u * k, axis=1, keepdims=True)            # [L, 1]
    y = y + s_diag * v
    # inter-chunk from carried state
    y = y + jax.lax.dot_general(
        (r * jnp.exp(cum_prev)), state, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # state update: S ⊙ exp(cum_last) + (k ⊙ decay_to_end)ᵀ v
    cum_last = cum[chunk - 1:, :]                                  # [1, K]
    dend = jnp.exp(cum_last - cum)                                 # [L, K] ≤ 1
    kw = k * dend
    # S's rows run over K, so exp(cum_last) is needed as a [K, 1] column:
    # a masked lane reduction turns the row into one (exact f32)
    a_idx = jax.lax.broadcasted_iota(jnp.int32, (kk, kk), 0)
    b_idx = jax.lax.broadcasted_iota(jnp.int32, (kk, kk), 1)
    decay = jnp.sum(
        jnp.where(a_idx == b_idx, jnp.exp(cum_last), 0.0), axis=1, keepdims=True
    )                                                              # [K, 1]
    state_scr[...] = state * decay + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0] = y.astype(o_ref.dtype)


def wkv6_pallas(
    r: jnp.ndarray,     # [B, S, H, K]
    k: jnp.ndarray,
    v: jnp.ndarray,     # [B, S, H, V]
    logw: jnp.ndarray,  # [B, S, H, K]
    u: jnp.ndarray,     # [H, K]
    *,
    chunk: int = 32,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, kk = r.shape
    vv = v.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, f"seq {s} % chunk {chunk}"
    n_chunks = s // chunk

    def resh(x):
        return jnp.moveaxis(x, 2, 1).reshape(b * h, s, x.shape[-1])

    rt, kt, vt, wt = resh(r), resh(k), resh(v), resh(logw)
    grid = (b * h, n_chunks)

    def seq_map(bh, ci):
        return (bh, ci, 0)

    def u_map(bh, ci):
        return (bh % h, 0, 0)

    out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, kk), seq_map),
            pl.BlockSpec((1, chunk, kk), seq_map),
            pl.BlockSpec((1, chunk, vv), seq_map),
            pl.BlockSpec((1, chunk, kk), seq_map),
            # unit middle axis: the block's last two dims equal the array's
            pl.BlockSpec((1, 1, kk), u_map),
        ],
        out_specs=pl.BlockSpec((1, chunk, vv), seq_map),
        out_shape=jax.ShapeDtypeStruct((b * h, s, vv), r.dtype),
        scratch_shapes=[pltpu.VMEM((kk, vv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rt, kt, vt, wt, u.reshape(h, 1, kk))
    return out.reshape(b, h, s, vv).transpose(0, 2, 1, 3)
