"""Core model layers (pure JAX, functional) with logical sharding axes.

Parameters are plain nested dicts of arrays.  Every ``init_*`` function
returns a tree whose leaves are ``(array, logical_axes)`` pairs;
``split_tree`` separates values from specs.  ``repro.parallel.sharding``
maps logical axes (``"embed"``, ``"heads"``, ``"mlp"``, ``"experts"``,
``"vocab"``, ...) onto mesh axes per architecture — the MaxText/t5x
pattern.

Attention.  Without a KV cache (train, prefill) ``attention_block`` runs
the Pallas flash kernel (``repro.kernels.flash_attention``, with its own
backward) on a TPU, and on the CPU the chunked scan below
(``models.lm.attention_path`` chooses).  The jnp implementations:

* ``attention_naive``    — full score matrix; test oracle only.
* ``attention_chunked``  — online-softmax over KV chunks (the flash
  recurrence in lax ops): the CPU path and the kernel's spec.  It computes
  the full causal rectangle, and autodiff keeps every chunk's scores.
* ``attention_windowed`` — sliding-window attention scanning query chunks
  against a dynamic KV band; FLOPs ∝ S·(window+chunk), gemma3's local
  layers on the CPU.
* ``attention_decode``   — single-token decode against a KV cache, on every
  backend.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.monitor.trace import count, scope

Params = dict[str, Any]
Specs = dict[str, Any]

_NEG_INF = -1e30

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(
    key: jax.Array,
    shape: tuple[int, ...],
    axes: tuple[str | None, ...],
    dtype: Any,
    *,
    scale: float | None = None,
) -> tuple[jnp.ndarray, tuple[str | None, ...]]:
    fan_in = shape[0]
    s = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * s).astype(dtype), axes


def ones_init(
    shape: tuple[int, ...], axes: tuple[str | None, ...], dtype: Any
) -> tuple[jnp.ndarray, tuple[str | None, ...]]:
    return jnp.ones(shape, dtype=dtype), axes


def zeros_init(
    shape: tuple[int, ...], axes: tuple[str | None, ...], dtype: Any
) -> tuple[jnp.ndarray, tuple[str | None, ...]]:
    return jnp.zeros(shape, dtype=dtype), axes


def _is_pair(x: Any) -> bool:
    return (
        isinstance(x, tuple)
        and len(x) == 2
        and isinstance(x[1], tuple)
        and all(isinstance(a, (str, type(None))) for a in x[1])
    )


def split_tree(tree: Any) -> tuple[Any, Any]:
    """Split a tree of (array, axes) leaf pairs into (values, specs)."""
    values = jax.tree.map(lambda leaf: leaf[0], tree, is_leaf=_is_pair)
    specs = jax.tree.map(lambda leaf: leaf[1], tree, is_leaf=_is_pair)
    return values, specs


# ---------------------------------------------------------------------------
# norms & rotary embeddings
# ---------------------------------------------------------------------------
@scope("norm")
def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dtype)


def rope_frequencies(d_head: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, d_head, 2, dtype=np.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [B, S, H, D]; positions: [S] or [B, S]."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_frequencies(d, theta))
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., S, D/2]
    if angles.ndim == 2:  # [S, D/2] -> broadcast over batch
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# masks: window <= 0 means "no window" (works traced or static)
# ---------------------------------------------------------------------------
def _band_mask(
    q_pos: jnp.ndarray, k_pos: jnp.ndarray, *, causal: bool, window: Any
) -> jnp.ndarray:
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        mask = mask & (q_pos[:, None] >= k_pos[None, :])
    w = jnp.asarray(window)
    mask = mask & ((q_pos[:, None] - k_pos[None, :] < w) | (w <= 0))
    return mask


# ---------------------------------------------------------------------------
# attention (reference implementations)
# ---------------------------------------------------------------------------
def _gqa_expand(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """[B,S,Hq,D] -> [B,S,Hkv,G,D] grouping query heads per KV head."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def attention_naive(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
) -> jnp.ndarray:
    """Full-matrix reference.  q:[B,Sq,Hq,D] k,v:[B,Skv,Hkv,D]."""
    b, sq, hq, d = q.shape
    n_kv = k.shape[2]
    qg = _gqa_expand(q, n_kv).astype(jnp.float32)
    scores = jnp.einsum("bsKgd,btKd->bKgst", qg, k.astype(jnp.float32))
    scores = scores / math.sqrt(d)
    q_pos = jnp.arange(sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    mask = _band_mask(q_pos, k_pos, causal=causal, window=window)
    scores = jnp.where(mask[None, None, None], scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bKgst,btKd->bsKgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def attention_chunked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    chunk: int = 512,
) -> jnp.ndarray:
    """Online-softmax attention scanning KV chunks (flash recurrence)."""
    b, sq, hq, d = q.shape
    skv = k.shape[1]
    n_kv = k.shape[2]
    chunk = min(chunk, skv)
    if skv % chunk:
        pad = chunk - skv % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = k.shape[1] // chunk
    kc = jnp.moveaxis(k.reshape(b, n_chunks, chunk, n_kv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, chunk, n_kv, d), 1, 0)
    qg = _gqa_expand(q, n_kv).astype(jnp.float32) / math.sqrt(d)
    q_pos = (jnp.arange(sq) + q_offset).astype(jnp.int32)

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, idx = inputs
        k_pos = idx * chunk + jnp.arange(chunk, dtype=jnp.int32)
        s = jnp.einsum("bsKgd,btKd->bKgst", qg, kb.astype(jnp.float32))
        mask = _band_mask(q_pos, k_pos, causal=causal, window=window)
        mask = mask & (k_pos[None, :] < skv)
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bKgst,btKd->bsKgd", p, vb.astype(jnp.float32))
        acc_new = acc * alpha.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    g = hq // n_kv
    m0 = jnp.full((b, n_kv, g, sq), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, sq), dtype=jnp.float32)
    acc0 = jnp.zeros((b, sq, n_kv, g, d), dtype=jnp.float32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, acc0), (kc, vc, jnp.arange(n_chunks, dtype=jnp.int32))
    )
    denom = jnp.maximum(l, 1e-37).transpose(0, 3, 1, 2)[..., None]
    return (acc / denom).reshape(b, sq, hq, d).astype(q.dtype)


def attention_windowed(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    window: int,
    chunk: int = 512,
) -> jnp.ndarray:
    """Sliding-window causal self-attention (gemma3 local layers).

    Scans query chunks; each attends a dynamic KV band of static size
    ``window + chunk`` ending at the chunk's last position.  Total matmul
    work is S·(window+chunk) — the sub-quadratic path.
    """
    b, s, hq, d = q.shape
    n_kv = k.shape[2]
    assert window > 0
    if s <= window + chunk:  # band covers everything; fall back
        return attention_chunked(q, k, v, causal=True, window=window)
    chunk = min(chunk, s)
    pad_front = window  # so every band slice is in-bounds
    kp = jnp.pad(k, ((0, 0), (pad_front, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad_front, 0), (0, 0), (0, 0)))
    n_chunks = s // chunk
    band = window + chunk
    qg = _gqa_expand(q, n_kv).astype(jnp.float32) / math.sqrt(d)
    qc = jnp.moveaxis(qg.reshape(b, n_chunks, chunk, n_kv, hq // n_kv, d), 1, 0)

    def body(_, inputs):
        qb, idx = inputs
        start = idx * chunk  # band = positions [start-window, start+chunk)
        kb = lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        vb = lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        q_pos = start + jnp.arange(chunk, dtype=jnp.int32)
        k_pos = start - window + jnp.arange(band, dtype=jnp.int32)
        sc = jnp.einsum("bsKgd,btKd->bKgst", qb, kb.astype(jnp.float32))
        mask = _band_mask(q_pos, k_pos, causal=True, window=window)
        mask = mask & (k_pos[None, :] >= 0)
        sc = jnp.where(mask[None, None, None], sc, _NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        ob = jnp.einsum("bKgst,btKd->bsKgd", p, vb.astype(jnp.float32))
        return None, ob

    _, out = lax.scan(body, None, (qc, jnp.arange(n_chunks, dtype=jnp.int32)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, hq, d)
    return out.astype(q.dtype)


def attention_decode(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    *,
    length: jnp.ndarray,
    window: int = 0,
) -> jnp.ndarray:
    """Single-position decode: q:[B,1,Hq,D], cache:[B,Smax,Hkv,D].

    ``length`` = number of valid cache entries (new token's position + 1).
    """
    b, _, hq, d = q.shape
    n_kv = k_cache.shape[2]
    smax = k_cache.shape[1]
    qg = _gqa_expand(q, n_kv).astype(jnp.float32) / math.sqrt(d)
    s = jnp.einsum("bsKgd,btKd->bKgst", qg, k_cache.astype(jnp.float32))
    k_pos = jnp.arange(smax)
    length = jnp.asarray(length).reshape(())
    mask = k_pos < length
    w = jnp.asarray(window)
    mask = mask & ((k_pos >= length - w) | (w <= 0))
    s = jnp.where(mask[None, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bKgst,btKd->bsKgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# attention block (projections + rope + norm options)
# ---------------------------------------------------------------------------
def init_attention(key: jax.Array, cfg: Any, dtype: Any) -> Params:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads, cfg.d_head), ("embed", "heads", "head_dim"), dtype),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads, cfg.d_head), ("embed", "kv_heads", "head_dim"), dtype),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads, cfg.d_head), ("embed", "kv_heads", "head_dim"), dtype),
        "wo": dense_init(ks[3], (cfg.n_heads, cfg.d_head, d), ("heads", "head_dim", "embed"), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones_init((cfg.d_head,), (None,), dtype)
        p["k_norm"] = ones_init((cfg.d_head,), (None,), dtype)
    return p


@scope("attention")
def attention_block(
    params: Params,
    x: jnp.ndarray,
    cfg: Any,
    *,
    positions: jnp.ndarray,
    window: int = 0,
    kv_cache: tuple[jnp.ndarray, jnp.ndarray] | None = None,
    cache_length: jnp.ndarray | None = None,
    impl: str = "chunked",
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray] | None]:
    """Self-attention block; returns (out, updated_cache).

    Training/prefill: kv_cache=None → causal self-attention over x, by
    ``impl``: ``"pallas"`` or ``"interpret"`` the flash kernel, ``"chunked"``
    the chunked (or windowed) scan, ``"naive"`` the full matrix.  Tracing the
    first two counts ``attention.flash``, the chunked scan
    ``attention.chunked`` (``repro.monitor.trace.count``).
    Decode: kv_cache=(k,v) preallocated [B,Smax,Hkv,D]; x is one token and
    cache_length its position; new K/V are written at that position.
    """
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if kv_cache is None:
        # optional query-sequence sharding ("q_seq" rule; no-op by default)
        from repro.parallel.context import constrain

        q = constrain(q, ("batch", "q_seq", None, None))

    new_cache = None
    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        pos = jnp.asarray(cache_length).reshape(())
        k_cache = lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), pos, axis=1
        )
        v_cache = lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), pos, axis=1
        )
        new_cache = (k_cache, v_cache)
        out = attention_decode(q, k_cache, v_cache, length=pos + 1, window=window)
    elif impl in ("pallas", "interpret"):
        from repro.kernels.flash_attention import flash_attention_pallas

        count("attention.flash")
        out = flash_attention_pallas(
            q, k, v, causal=True, window=int(window),
            interpret=(impl == "interpret"),
        )
    elif window and impl != "naive":
        count("attention.chunked")
        out = attention_windowed(q, k, v, window=window)
    elif impl == "chunked":
        count("attention.chunked")
        out = attention_chunked(q, k, v, causal=True, window=window)
    else:
        out = attention_naive(q, k, v, causal=True, window=window)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, new_cache


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU)
# ---------------------------------------------------------------------------
def init_mlp(key: jax.Array, d_model: int, d_ff: int, dtype: Any) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), ("embed", "mlp"), dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), ("embed", "mlp"), dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), ("mlp", "embed"), dtype),
    }


@scope("mlp")
def mlp_block(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]
