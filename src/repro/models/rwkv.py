"""RWKV6 ("Finch") layer — data-dependent decay linear attention.

Two mathematically-identical WKV6 evaluation paths:

* ``wkv6_recurrent`` — the defining per-token recurrence
  (lax.scan over time; O(1) state, used for decode and as the oracle);
* ``wkv6_chunked``   — chunk-parallel form: within a chunk of L tokens the
  pairwise decay tensor exp(cum_{t-1}-cum_j) is materialized (all exponents
  ≤ 0 ⇒ stable) and contracted with MXU matmuls; chunks are linked by an
  fp32 state carry.  This is the TPU adaptation of the CUDA wkv kernel —
  and the spec for the Pallas kernel in ``repro.kernels.rwkv6``.

State per head: S ∈ R^{K×V}; y_t = r_t·(S_{t-1} + (u⊙k_t)⊗v_t);
S_t = diag(w_t)·S_{t-1} + k_t⊗v_t, with w_t = exp(-exp(ŵ_t)) data-dependent.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import Params, dense_init, ones_init, rms_norm, zeros_init


def n_rwkv_heads(cfg: Any) -> int:
    return cfg.d_model // cfg.rwkv.head_size


def wkv6_recurrent(
    r: jnp.ndarray,      # [B, S, H, K]
    k: jnp.ndarray,      # [B, S, H, K]
    v: jnp.ndarray,      # [B, S, H, V]
    logw: jnp.ndarray,   # [B, S, H, K]  (log decay, <= 0)
    u: jnp.ndarray,      # [H, K] bonus
    *,
    init_state: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Defining recurrence (oracle + decode path)."""
    bsz, s, h, kk = r.shape
    vv = v.shape[-1]
    s0 = (
        jnp.zeros((bsz, h, kk, vv), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def step(state, inputs):
        rt, kt, vt, lwt = inputs  # [B,H,K],[B,H,K],[B,H,V],[B,H,K]
        rt = rt.astype(jnp.float32)
        kt = kt.astype(jnp.float32)
        vt = vt.astype(jnp.float32)
        kv = kt[..., :, None] * vt[..., None, :]            # [B,H,K,V]
        yt = jnp.einsum(
            "bhk,bhkv->bhv", rt, state + u[None, :, :, None] * kv
        )
        new_state = jnp.exp(lwt.astype(jnp.float32))[..., None] * state + kv
        return new_state, yt

    xs = (
        jnp.moveaxis(r, 1, 0),
        jnp.moveaxis(k, 1, 0),
        jnp.moveaxis(v, 1, 0),
        jnp.moveaxis(logw, 1, 0),
    )
    final, ys = lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), final


def wkv6_chunked(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    logw: jnp.ndarray,
    u: jnp.ndarray,
    *,
    chunk: int = 32,
    init_state: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunk-parallel WKV6 (exact)."""
    bsz, s, h, kk = r.shape
    vv = v.shape[-1]
    L = min(chunk, s)
    assert s % L == 0, f"seq {s} not divisible by chunk {L}"
    nc = s // L

    def resh(x):
        return jnp.moveaxis(
            x.reshape(bsz, nc, L, h, x.shape[-1]).astype(jnp.float32), 1, 0
        )

    rc, kc, vc, wc = resh(r), resh(k), resh(v), resh(logw)
    s0 = (
        jnp.zeros((bsz, h, kk, vv), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )
    idx = jnp.arange(L)
    tri_strict = idx[:, None] > idx[None, :]                # t > j

    def body(state, inputs):
        rb, kb, vb, wb = inputs                             # [B,L,H,*]
        cum = jnp.cumsum(wb, axis=1)                        # [B,L,H,K]
        cum_prev = cum - wb                                 # exclusive cumsum
        # pairwise decay exp(cum_prev[t] - cum[j]) for j < t  (all ≤ 0).
        # The masked entries (j >= t) are large and positive under strong
        # decay; exponentiated they overflow, and the gradient through the
        # mask becomes inf·0 = NaN, so they are set to -inf before exp.
        diff = cum_prev[:, :, None] - cum[:, None, :]       # [B,L,L,H,K]
        dmat = jnp.exp(
            jnp.where(tri_strict[None, :, :, None, None], diff, -jnp.inf)
        )
        att = jnp.einsum("blhk,bmhk,blmhk->blmh", rb, kb, dmat)
        y_intra = jnp.einsum("blmh,bmhv->blhv", att, vb)
        # diagonal bonus term
        y_diag = jnp.einsum("blhk,hk,blhk,blhv->blhv", rb, u, kb, vb)
        # inter-chunk: r_t · (S_prev ⊙ exp(cum_prev_t))
        y_inter = jnp.einsum("blhk,bhkv->blhv", rb * jnp.exp(cum_prev), state)
        # state update: S ⊙ exp(cum_last) + Σ_j exp(cum_last - cum_j) k_j v_j
        dend = jnp.exp(cum[:, -1:, :] - cum)                # [B,L,H,K] ≤ 1
        kw = kb * dend
        state_new = (
            state * jnp.exp(cum[:, -1])[..., None]
            + jnp.einsum("blhk,blhv->bhkv", kw, vb)
        )
        return state_new, y_intra + y_diag + y_inter

    final, ys = lax.scan(body, s0, (rc, kc, vc, wc))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, vv)
    return y.astype(r.dtype), final


# ---------------------------------------------------------------------------
# full RWKV6 layer (time-mix + channel-mix)
# ---------------------------------------------------------------------------
def init_rwkv6(key: jax.Array, cfg: Any, dtype: Any) -> Params:
    d = cfg.d_model
    h = n_rwkv_heads(cfg)
    hs = cfg.rwkv.head_size
    ks = jax.random.split(key, 10)
    return {
        "mu": (0.5 * jnp.ones((5, d), jnp.float32), (None, "embed")),  # r,k,v,w,g mixes
        "w_r": dense_init(ks[0], (d, d), ("embed", "heads"), dtype),
        "w_k": dense_init(ks[1], (d, d), ("embed", "heads"), dtype),
        "w_v": dense_init(ks[2], (d, d), ("embed", "heads"), dtype),
        "w_g": dense_init(ks[3], (d, d), ("embed", "heads"), dtype),
        "w_w": dense_init(ks[4], (d, d), ("embed", "heads"), dtype, scale=0.1),
        "w_bias": (-2.0 * jnp.ones((d,), jnp.float32), ("heads",)),
        "u": dense_init(ks[5], (h, hs), ("heads", None), jnp.float32, scale=0.3),
        "ln_w": ones_init((d,), ("embed",), dtype),
        "w_o": dense_init(ks[6], (d, d), ("heads", "embed"), dtype),
        # channel mix
        "cm_mu": (0.5 * jnp.ones((2, d), jnp.float32), (None, "embed")),
        "cm_k": dense_init(ks[7], (d, cfg.d_ff), ("embed", "mlp"), dtype),
        "cm_v": dense_init(ks[8], (cfg.d_ff, d), ("mlp", "embed"), dtype),
        "cm_r": dense_init(ks[9], (d, d), ("embed", "embed"), dtype),
    }


def _token_shift(x: jnp.ndarray, last: jnp.ndarray | None) -> jnp.ndarray:
    """Previous-token features; ``last`` [B,1,d] carries across decode steps."""
    if last is None:
        return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return jnp.concatenate([last.astype(x.dtype), x], axis=1)[:, :-1]


def rwkv6_time_mix(
    params: Params,
    x: jnp.ndarray,
    cfg: Any,
    *,
    state: jnp.ndarray | None = None,
    last_x: jnp.ndarray | None = None,
    decode: bool = False,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    h = n_rwkv_heads(cfg)
    hs = cfg.rwkv.head_size
    bsz, s, d = x.shape
    xx = _token_shift(x, last_x)
    mu = params["mu"].astype(x.dtype)

    def mix(i: int) -> jnp.ndarray:
        return x * mu[i] + xx * (1.0 - mu[i])

    r = (mix(0) @ params["w_r"]).reshape(bsz, s, h, hs)
    k = (mix(1) @ params["w_k"]).reshape(bsz, s, h, hs)
    v = (mix(2) @ params["w_v"]).reshape(bsz, s, h, hs)
    wraw = mix(3) @ params["w_w"] + params["w_bias"].astype(x.dtype)
    logw = -jnp.exp(wraw.astype(jnp.float32)).reshape(bsz, s, h, hs)
    g = jax.nn.silu(mix(4) @ params["w_g"])
    u = params["u"]
    if decode:
        y, new_state = wkv6_recurrent(r, k, v, logw, u, init_state=state)
    else:
        y, new_state = wkv6_chunked(
            r, k, v, logw, u, chunk=min(32, s), init_state=state
        )
    y = y.reshape(bsz, s, d)
    y = rms_norm(y, params["ln_w"], cfg.norm_eps) * g
    out = y @ params["w_o"]
    return out, (new_state, x[:, -1:, :])


def rwkv6_channel_mix(
    params: Params,
    x: jnp.ndarray,
    *,
    last_x: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    xx = _token_shift(x, last_x)
    mu = params["cm_mu"].astype(x.dtype)
    xk = x * mu[0] + xx * (1.0 - mu[0])
    xr = x * mu[1] + xx * (1.0 - mu[1])
    kk = jnp.square(jax.nn.relu(xk @ params["cm_k"]))
    return jax.nn.sigmoid(xr @ params["cm_r"]) * (kk @ params["cm_v"]), x[:, -1:, :]
