"""Flash attention Pallas TPU kernel (GQA + causal + sliding window).

TPU adaptation of the flash algorithm: 3-D grid ``(batch·kv_heads·groups,
q_blocks, kv_blocks)`` with the KV dimension innermost and *arbitrary*
(sequential), so the online-softmax state (m, l, acc) lives in VMEM
scratch across KV iterations.  Blocks are block_q × d_head and
block_kv × d_head; d_head is never padded, so a block's last dim always
equals the array's (64 for smollm-360m, 128 for qwen3-4b), and the
sequence is padded up to a whole number of blocks.  Causal/window
masking is done blockwise: fully-masked KV blocks are skipped with
``pl.when`` (no wasted MXU work — unlike the pure-jnp chunked reference,
which computes the full rectangle).

Layout: inputs are pre-transposed to [BHg, S, D] (one row of heads per
grid cell), where BHg enumerates (batch, kv_head, q_group); K/V use the
kv_head only — GQA without materializing repeated KV.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,       # [1, block_q, d]
    k_ref,       # [1, block_kv, d]
    v_ref,       # [1, block_kv, d]
    o_ref,       # [1, block_q, d]
    m_scr,       # VMEM [block_q, 128] f32 (lane-padded running max)
    l_scr,       # VMEM [block_q, 128] f32
    acc_scr,     # VMEM [block_q, d] f32
    *,
    block_q: int,
    block_kv: int,
    seq_len: int,
    causal: bool,
    window: int,
    scale: float,
    n_kv_blocks: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_kv

    # blockwise skip: causal ⇒ skip blocks entirely above the diagonal;
    # window ⇒ skip blocks entirely left of the band.
    relevant = jnp.asarray(True)
    if causal:
        relevant = relevant & (k_start <= q_start + block_q - 1)
    if window:
        relevant = relevant & (k_start + block_kv - 1 >= q_start - window + 1)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_kv]
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_len
        if causal:
            mask = mask & (q_pos >= k_pos)
        if window:
            mask = mask & (q_pos - k_pos < window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[:, 0:1]                                # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                       # [bq, 1]
        l_new = l_scr[:, 0:1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[:, 0:1], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,   # [B, S, Hq, D]
    k: jnp.ndarray,   # [B, S, Hkv, D]
    v: jnp.ndarray,   # [B, S, Hkv, D]
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 256,
    block_kv: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, hq, d = q.shape
    n_kv = k.shape[2]
    g = hq // n_kv
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    pad_s = (-s) % block_q
    pad_skv = (-s) % block_kv
    # [B, S, Hq, D] -> [B*Hq, S, D]; k/v repeated per q-group via index map
    qt = jnp.moveaxis(q, 2, 1).reshape(b * hq, s, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * n_kv, s, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * n_kv, s, d)
    if pad_s:
        qt = jnp.pad(qt, ((0, 0), (0, pad_s), (0, 0)))
    if pad_skv:
        kt = jnp.pad(kt, ((0, 0), (0, pad_skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_skv), (0, 0)))
    n_q_blocks = qt.shape[1] // block_q
    n_kv_blocks = kt.shape[1] // block_kv
    grid = (b * hq, n_q_blocks, n_kv_blocks)

    def q_map(h, qi, ki):
        return (h, qi, 0)

    def kv_map(h, qi, ki):
        return (h // g, ki, 0)   # share the kv head across its q-group

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_kv=block_kv,
        seq_len=s,
        causal=causal,
        window=window,
        scale=1.0 / math.sqrt(d),
        n_kv_blocks=n_kv_blocks,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
            pl.BlockSpec((1, block_kv, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    if pad_s:
        out = out[:, :s]
    return out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
