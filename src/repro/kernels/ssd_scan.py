"""Mamba2 SSD Pallas TPU kernel — chunked state-space scan.

Grid ``(B·H, n_chunks)`` with the chunk axis sequential; the [P,N] fp32
SSM state is VMEM scratch carried across chunks.  Per chunk the SSD
decomposition runs as dense matmuls: segment-sum decay matrix [L,L],
intra-chunk y = (C·Bᵀ ⊙ decay)·(x·dt), chunk state contribution, and the
inter-chunk propagation from the carried state — exactly the math of
``repro.models.ssm.ssd_chunked``, restructured so every contraction hits
the MXU with L=128-aligned tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref,      # [1, L, P]
    dt_ref,     # [1, 1, L]
    a_ref,      # [1, 1, 1] (per-head A, negative)
    b_ref,      # [1, L, N]
    c_ref,      # [1, L, N]
    o_ref,      # [1, L, P]
    state_scr,  # VMEM [P, N] f32
    *,
    chunk: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)            # [L, P]
    dt_row = dt_ref[0].astype(jnp.float32)      # [1, L]
    a = a_ref[0].astype(jnp.float32)            # [1, 1]
    bb = b_ref[0].astype(jnp.float32)           # [L, N]
    cc = c_ref[0].astype(jnp.float32)           # [L, N]
    state = state_scr[...]                      # [P, N]

    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # dt is needed both as a row and as a [L, 1] column; Mosaic has no
    # cumsum and no lane→sublane reshape, so the column and the prefix
    # sums are masked reductions over [L, L] (exact f32 adds)
    dt = jnp.sum(jnp.where(t_idx == j_idx, dt_row, 0.0), axis=1, keepdims=True)
    dta = dt * a                                # [L, 1]
    cum = jnp.sum(
        jnp.where(t_idx >= j_idx, dt_row * a, 0.0), axis=1, keepdims=True
    )                                           # [L, 1]
    cum_row = jnp.sum(
        jnp.where(t_idx <= j_idx, dta, 0.0), axis=0, keepdims=True
    )                                           # [1, L]
    xdt = x * dt                                # [L, P]
    # intra-chunk: y[t] = Σ_{j<=t} exp(cum_t - cum_j) (c_t·b_j) xdt[j]
    seg = cum - cum_row                         # [L, L]
    lmat = jnp.where(t_idx >= j_idx, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(
        cc, bb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                           # [L, L]
    y = jax.lax.dot_general(
        cb * lmat, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                           # [L, P]
    # inter-chunk from carried state: y[t] += exp(cum_t) · (C_t · stateᵀ)
    cs = jax.lax.dot_general(
        cc, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                           # [L, P]
    y = y + cs * jnp.exp(cum)
    # state update: S·exp(cum_last) + Σ_j exp(cum_last - cum_j) xdt_jᵀ b_j
    cum_last = cum[chunk - 1:, :]               # [1, 1]
    dend = jnp.exp(cum_last - cum)              # [L, 1]
    state_scr[...] = state * jnp.exp(cum_last) + jax.lax.dot_general(
        xdt * dend, bb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[0] = y.astype(o_ref.dtype)


def ssd_pallas(
    x: jnp.ndarray,      # [B, S, H, P]
    dt: jnp.ndarray,     # [B, S, H]
    a: jnp.ndarray,      # [H]
    b_in: jnp.ndarray,   # [B, S, N]
    c_in: jnp.ndarray,   # [B, S, N]
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, h, p = x.shape
    n = b_in.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    n_chunks = s // chunk
    xt = jnp.moveaxis(x, 2, 1).reshape(b * h, s, p)
    # dt and a get a unit middle axis so each block's last two dims are
    # (1, chunk) / (1, 1): equal to the array's, as Mosaic requires
    dtt = jnp.moveaxis(dt, 2, 1).reshape(b * h, 1, s)
    at = a.reshape(h, 1, 1)
    grid = (b * h, n_chunks)

    def seq_map(bh, ci):
        return (bh, ci, 0)

    def dt_map(bh, ci):
        return (bh, 0, ci)

    def a_map(bh, ci):
        return (bh % h, 0, 0)

    def bc_map(bh, ci):
        return (bh // h, ci, 0)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), seq_map),
            pl.BlockSpec((1, 1, chunk), dt_map),
            pl.BlockSpec((1, 1, 1), a_map),
            pl.BlockSpec((1, chunk, n), bc_map),
            pl.BlockSpec((1, chunk, n), bc_map),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), seq_map),
        out_shape=jax.ShapeDtypeStruct((b * h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xt, dtt, at, b_in, c_in)
    return out.reshape(b, h, s, p).transpose(0, 2, 1, 3)
