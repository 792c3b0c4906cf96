"""Flash attention in Pallas for the TPU: GQA, causal, sliding window, with its own backward.

``flash_attention_pallas(q, k, v)`` takes q ``[B, S, Hq, D]`` and k, v
``[B, S, Hkv, D]`` and is differentiable (``jax.custom_vjp``).  Query head
``h`` attends KV head ``h // G``, ``G = Hq / Hkv``, as ``_gqa_expand`` in
``models/layers.py`` groups them.

Layout.  Outside the kernels the wrapper scales q by ``1/sqrt(D)`` in its own
dtype (what XLA's default precision does to the chunked path's f32 operands
on a TPU), puts heads before positions and pads the sequence to whole
blocks: q ``[B·Hkv, G, Sq, D]``, k and v ``[B·Hkv, Skv, D]``.  One grid cell
holds the G query heads of a KV head, so K and V are fetched once per group
and dK, dV are summed over the group in VMEM.

Kernels, FlashAttention-2 style.  Every one skips the blocks that the causal
and window mask leaves wholly empty (``pl.when``), and its index maps clamp
a skipped cell to the last block it fetched, so a skipped cell costs no DMA;
only blocks that the mask or the padding cuts through build a mask.

* forward: grid ``(B·Hkv, q blocks, kv blocks)``, the kv blocks sequential;
  the running max, sum and output stay in VMEM in f32.  For the backward it
  also writes each row's log-sum-exp ``lse``, f32, lane-padded to 128 as the
  running max and sum are (``+inf`` on a row that sees no key).
* dK/dV: grid ``(B·Hkv, kv blocks, q blocks)``, the q blocks and the G heads
  sequential; works on the transposed scores ``K·Qᵀ`` so that every product
  is a plain one, with ``lse`` and ``D = rowsum(dO∘O)`` as rows.
* dQ: grid ``(B·Hkv, q blocks, kv blocks)`` as the forward.

Both backward kernels recompute ``P = exp(QKᵀ - lse)``.  The residuals are
q, k, v, the output and ``lse``: nothing of size S².  MXU operands stay in
the input dtype with f32 accumulation; the softmax statistics, ``lse``, D and
the accumulators are f32.  Block sizes come from S, D and G (``_blocks``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


class _Geom(NamedTuple):
    seq_len: int
    block_q: int
    block_kv: int
    causal: bool
    window: int
    interpret: bool


def _blocks(s: int, d: int, g: int) -> tuple[int, int]:
    """(block_q, block_kv) for sequence length s, head size d and g query heads per KV head.

    512 by 512 was the fastest of the blocks tried on a TPU v5e, for d 64
    and 128 (PERF.md).  A cell holds g query blocks, each padded to at
    least 128 lanes, so block_q halves where g·block_q·max(d, 128) would
    pass what fitted the scoped VMEM there (g 4, d 128); g 3 with
    block_q 1024 did not fit."""
    block_q = 512
    while block_q > 128 and g * block_q * max(d, 128) > 4 * 512 * 128:
        block_q //= 2
    return min(block_q, s), min(512, s)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _lanes(x: jnp.ndarray, n: int) -> jnp.ndarray:
    """A ``[rows, 128]`` array whose lanes are equal, as ``[rows, n]``."""
    if n <= LANES:
        return x[:, :n]
    if n % LANES == 0:
        return jnp.tile(x, (1, n // LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _visible(q0, k0, geom: _Geom):
    """(some, every): whether some / every (query, key) pair of the block
    whose first query is ``q0`` and first key ``k0`` is visible."""
    q1, k1 = q0 + geom.block_q - 1, k0 + geom.block_kv - 1
    some = jnp.asarray(True)
    every = k1 < geom.seq_len
    if geom.causal:
        some = some & (k0 <= q1)
        every = every & (k1 <= q0)
    if geom.window:
        some = some & (q0 - k1 < geom.window)
        every = every & (q1 - k0 < geom.window)
    return some, every


def _mask(q0, k0, shape: tuple[int, int], q_axis: int, geom: _Geom):
    """The visible pairs of a ``shape`` block of scores whose first query is
    ``q0`` and first key ``k0``, queries along ``q_axis``."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    mask = k_pos < geom.seq_len
    if geom.causal:
        mask = mask & (q_pos >= k_pos)
    if geom.window:
        mask = mask & (q_pos - k_pos < geom.window)
    return mask


def _kv_index(qi, ki, geom: _Geom, n_kv_blocks: int):
    """The kv block that cell (qi, ki) reads: a skipped cell keeps the
    nearest relevant block, so the pipeline fetches nothing new."""
    if geom.causal:
        last = (qi * geom.block_q + geom.block_q - 1) // geom.block_kv
        ki = jnp.minimum(ki, jnp.minimum(last, n_kv_blocks - 1))
    if geom.window:
        first = jnp.maximum(qi * geom.block_q - geom.window + 1, 0) // geom.block_kv
        ki = jnp.maximum(ki, first)
    return ki


def _q_index(ki, qi, geom: _Geom, n_q_blocks: int):
    """The q block that dK/dV cell (ki, qi) reads, clamped as ``_kv_index``."""
    if geom.causal:
        qi = jnp.maximum(qi, ki * geom.block_kv // geom.block_q)
    if geom.window:
        last = (ki * geom.block_kv + geom.block_kv + geom.window - 2) // geom.block_q
        qi = jnp.minimum(qi, jnp.minimum(last, n_q_blocks - 1))
    return qi


def _run_visible(q0, k0, geom: _Geom, step) -> None:
    """``step(masked)`` on a block with visible pairs; the mask is built
    only where the block is cut."""
    some, every = _visible(q0, k0, geom)
    pl.when(some & every)(lambda: step(False))
    pl.when(some & jnp.logical_not(every))(lambda: step(True))


def _params(*semantics: str):
    return pltpu.CompilerParams(dimension_semantics=semantics)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, geom: _Geom, n_kv_blocks: int):
    lse_ref = rest[0] if len(rest) == 4 else None
    m_scr, l_scr, acc_scr = rest[-3:]
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_groups, bq, d = q_ref.shape[1:]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        k, v = k_ref[0], v_ref[0]
        if masked:
            mask = _mask(qi * bq, ki * bk, (bq, bk), 0, geom)
        for g in range(n_groups):
            s = _dot(q_ref[0, g], k, _NT)                       # [bq, bk]
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[g]                                    # [bq, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, bk))
            if masked:
                p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * _lanes(alpha, d) + _dot(p.astype(v.dtype), v, _NN)
            m_scr[g] = m_new

    _run_visible(qi * bq, ki * bk, geom, step)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        for g in range(n_groups):
            l = l_scr[g]
            o_ref[0, g] = (acc_scr[g] / _lanes(jnp.maximum(l, 1e-30), d)).astype(o_ref.dtype)
            if lse_ref is not None:
                lse_ref[0, g] = jnp.where(l > 0.0, m_scr[g] + jnp.log(l), jnp.inf)


def _forward(qt, kt, vt, geom: _Geom, *, with_lse: bool):
    bh, n_groups, sq, d = qt.shape
    bq, bk = geom.block_q, geom.block_kv
    n_q_blocks, n_kv_blocks = sq // bq, kt.shape[1] // bk

    def q_map(h, qi, ki):
        return (h, 0, qi, 0)

    def kv_map(h, qi, ki):
        return (h, _kv_index(qi, ki, geom, n_kv_blocks), 0)

    out_specs = [pl.BlockSpec((1, n_groups, bq, d), q_map)]
    out_shape = [jax.ShapeDtypeStruct(qt.shape, qt.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, n_groups, bq, LANES), q_map))
        out_shape.append(jax.ShapeDtypeStruct((bh, n_groups, sq, LANES), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, geom=geom, n_kv_blocks=n_kv_blocks),
        grid=(bh, n_q_blocks, n_kv_blocks),
        in_specs=[
            pl.BlockSpec((1, n_groups, bq, d), q_map),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, bk, d), kv_map),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((n_groups, bq, LANES), jnp.float32),   # running max
            pltpu.VMEM((n_groups, bq, LANES), jnp.float32),   # running sum
            pltpu.VMEM((n_groups, bq, d), jnp.float32),       # output
        ],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=geom.interpret,
    )(qt, kt, vt)
    return tuple(out) if with_lse else out[0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, geom: _Geom, n_q_blocks: int):
    ki, qi = pl.program_id(1), pl.program_id(2)
    n_groups, bq = q_ref.shape[1:3]
    bk = k_ref.shape[1]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        k, v = k_ref[0], v_ref[0]
        if masked:
            mask = _mask(qi * bq, ki * bk, (bk, bq), 1, geom)
        for g in range(n_groups):
            q, do = q_ref[0, g], do_ref[0, g]                    # [bq, d]
            st = _dot(k, q, _NT)                                 # [bk, bq]
            if masked:
                st = jnp.where(mask, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, g])                     # lse: [1, bq]
            dv_scr[...] += _dot(pt.astype(do.dtype), do, _NN)
            dpt = _dot(v, do, _NT)                               # [bk, bq]
            dst = pt * (dpt - di_ref[0, g])
            dk_scr[...] += _dot(dst.astype(q.dtype), q, _NN)

    _run_visible(qi * bq, ki * bk, geom, step)

    @pl.when(qi == n_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_scr,
               *, geom: _Geom, n_kv_blocks: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    n_groups, bq = q_ref.shape[1:3]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked: bool):
        k, v = k_ref[0], v_ref[0]
        if masked:
            mask = _mask(qi * bq, ki * bk, (bq, bk), 0, geom)
        for g in range(n_groups):
            s = _dot(q_ref[0, g], k, _NT)                        # [bq, bk]
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - _lanes(lse_ref[0, g], bk))
            dp = _dot(do_ref[0, g], v, _NT)
            ds = p * (dp - _lanes(di_ref[0, g], bk))
            dq_scr[g] += _dot(ds.astype(k.dtype), k, _NN)

    _run_visible(qi * bq, ki * bk, geom, step)

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _backward(geom: _Geom, res, do):
    qt, kt, vt, out, lse = res
    bh, n_groups, sq, d = qt.shape
    bq, bk = geom.block_q, geom.block_kv
    n_q_blocks, n_kv_blocks = sq // bq, kt.shape[1] // bk
    di = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)   # [bh, G, sq]
    rows = lambda x: x[:, :, None, :]                                          # noqa: E731

    def q_side(h, ki, qi):
        return (h, 0, _q_index(ki, qi, geom, n_q_blocks), 0)

    def row_side(h, ki, qi):
        return (h, 0, 0, _q_index(ki, qi, geom, n_q_blocks))

    def kv_own(h, ki, qi):
        return (h, ki, 0)

    q_block = pl.BlockSpec((1, n_groups, bq, d), q_side)
    row_block = pl.BlockSpec((1, n_groups, 1, bq), row_side)
    kv_block = pl.BlockSpec((1, bk, d), kv_own)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, geom=geom, n_q_blocks=n_q_blocks),
        grid=(bh, n_kv_blocks, n_q_blocks),
        in_specs=[q_block, kv_block, kv_block, q_block, row_block, row_block],
        out_specs=[kv_block, kv_block],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32), pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=geom.interpret,
    )(qt, kt, vt, do, rows(lse[..., 0]), rows(di))

    def q_own(h, qi, ki):
        return (h, 0, qi, 0)

    def kv_side(h, qi, ki):
        return (h, _kv_index(qi, ki, geom, n_kv_blocks), 0)

    q_block = pl.BlockSpec((1, n_groups, bq, d), q_own)
    col_block = pl.BlockSpec((1, n_groups, bq, LANES), q_own)
    kv_block = pl.BlockSpec((1, bk, d), kv_side)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, geom=geom, n_kv_blocks=n_kv_blocks),
        grid=(bh, n_q_blocks, n_kv_blocks),
        in_specs=[q_block, kv_block, kv_block, q_block, col_block, col_block],
        out_specs=q_block,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((n_groups, bq, d), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=geom.interpret,
    )(qt, kt, vt, do, lse, jnp.broadcast_to(di[..., None], lse.shape))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(qt, kt, vt, geom: _Geom):
    return _forward(qt, kt, vt, geom, with_lse=False)


def _flash_fwd(qt, kt, vt, geom: _Geom):
    out, lse = _forward(qt, kt, vt, geom, with_lse=True)
    return out, (qt, kt, vt, out, lse)


_flash.defvjp(_flash_fwd, _backward)


def flash_attention_pallas(
    q: jnp.ndarray,   # [B, S, Hq, D]
    k: jnp.ndarray,   # [B, S, Hkv, D]
    v: jnp.ndarray,   # [B, S, Hkv, D]
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, hq, d = q.shape
    n_kv = k.shape[2]
    g = hq // n_kv
    auto_q, auto_kv = _blocks(s, d, g)
    geom = _Geom(seq_len=s, block_q=min(block_q or auto_q, s),
                 block_kv=min(block_kv or auto_kv, s), causal=causal,
                 window=int(window), interpret=interpret)
    scale = 1.0 / math.sqrt(d)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qt = jnp.moveaxis(qs, 2, 1).reshape(b * n_kv, g, s, d)
    kt = jnp.moveaxis(k, 2, 1).reshape(b * n_kv, s, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * n_kv, s, d)
    pad_q, pad_kv = (-s) % geom.block_q, (-s) % geom.block_kv
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_kv:
        kt = jnp.pad(kt, ((0, 0), (0, pad_kv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, pad_kv), (0, 0)))
    out = _flash(qt, kt, vt, geom)[:, :, :s]
    return jnp.moveaxis(out.reshape(b, hq, s, d), 1, 2)
