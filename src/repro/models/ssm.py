"""Mamba2 (SSD) layer — chunked scan formulation, TPU-adapted.

The SSD decomposition (intra-chunk quadratic + inter-chunk recurrence)
replaces the GPU selective-scan kernel with MXU-friendly matmuls: chunk
length L=128 keeps the [L,L] intra matrices hardware-aligned, and the
inter-chunk state recurrence is a short lax.scan carrying fp32 state
[B, H, P, N].  Decode is the O(1) single-token state update — the reason
zamba2/rwkv6 are the two archs eligible for the 500k-context cell.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import Params, dense_init, ones_init, rms_norm, zeros_init
from repro.monitor.trace import scope


def d_inner(cfg: Any) -> int:
    return cfg.ssm.expand * cfg.d_model


def n_ssm_heads(cfg: Any) -> int:
    return d_inner(cfg) // cfg.ssm.d_head


def init_mamba2(key: jax.Array, cfg: Any, dtype: Any) -> Params:
    d = cfg.d_model
    din = d_inner(cfg)
    n = cfg.ssm.d_state
    h = n_ssm_heads(cfg)
    ks = jax.random.split(key, 8)
    return {
        # fused input projection: [z, x, B, C, dt]
        "w_in_z": dense_init(ks[0], (d, din), ("embed", "mlp"), dtype),
        "w_in_x": dense_init(ks[1], (d, din), ("embed", "mlp"), dtype),
        "w_in_b": dense_init(ks[2], (d, n), ("embed", None), dtype),
        "w_in_c": dense_init(ks[3], (d, n), ("embed", None), dtype),
        "w_in_dt": dense_init(ks[4], (d, h), ("embed", "heads"), dtype),
        "dt_bias": zeros_init((h,), ("heads",), jnp.float32),
        "a_log": (jnp.zeros((h,), jnp.float32), ("heads",)),
        "d_skip": ones_init((h,), ("heads",), jnp.float32),
        "conv_w": dense_init(
            ks[5], (cfg.ssm.d_conv, din + 2 * n), (None, "mlp"), dtype, scale=0.5
        ),
        "norm_w": ones_init((din,), ("mlp",), dtype),
        "w_out": dense_init(ks[6], (din, d), ("mlp", "embed"), dtype),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, cache: jnp.ndarray | None = None):
    """Depthwise causal conv.  x:[B,S,C], w:[K,C].  Returns (y, new_cache)
    where cache holds the last K-1 inputs for decode."""
    kk = w.shape[0]
    if cache is None:
        xp = jnp.pad(x, ((0, 0), (kk - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([cache.astype(x.dtype), x], axis=1)
    y = sum(
        xp[:, i : i + x.shape[1], :] * w[i][None, None, :] for i in range(kk)
    )
    new_cache = xp[:, -(kk - 1) :, :] if kk > 1 else None
    return jax.nn.silu(y), new_cache


def _segsum(dta: jnp.ndarray) -> jnp.ndarray:
    """Lower-triangular pairwise sums: out[..., i, j] = sum_{j<s<=i} dta[s].
    dta: [..., L] → [..., L, L] (=-inf above diagonal)."""
    L = dta.shape[-1]
    cum = jnp.cumsum(dta, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    idx = jnp.arange(L)
    mask = idx[:, None] >= idx[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jnp.ndarray,      # [B, S, H, P]
    dt: jnp.ndarray,     # [B, S, H]   (post-softplus)
    a: jnp.ndarray,      # [H]         (negative)
    b_in: jnp.ndarray,   # [B, S, N]
    c_in: jnp.ndarray,   # [B, S, N]
    *,
    chunk: int = 128,
    init_state: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan.  Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    L = min(chunk, s)
    assert s % L == 0, f"seq {s} not divisible by chunk {L}"
    nc = s // L
    xr = x.reshape(bsz, nc, L, h, p).astype(jnp.float32)
    dtr = dt.reshape(bsz, nc, L, h).astype(jnp.float32)
    br = b_in.reshape(bsz, nc, L, n).astype(jnp.float32)
    cr = c_in.reshape(bsz, nc, L, n).astype(jnp.float32)
    dta = dtr * a[None, None, None, :]                     # [B,NC,L,H]
    xdt = xr * dtr[..., None]                              # dt-weighted input
    cum = jnp.cumsum(dta, axis=2)                          # [B,NC,L,H]
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)        # [B,NC,L,H]
    chunk_decay = jnp.exp(cum[:, :, -1, :])                # [B,NC,H]
    # chunk-local final states: [B,NC,H,P,N]
    states = jnp.einsum("bcln,bclhp,bclh->bchpn", br, xdt, decay_to_end)
    # intra-chunk (quadratic within L)
    lmat = jnp.exp(_segsum(jnp.moveaxis(dta, 3, 2)))       # [B,NC,H,L,L]
    y_intra = jnp.einsum("bcln,bcmn,bchlm,bcmhp->bclhp", cr, br, lmat, xdt)

    # inter-chunk recurrence
    s0 = (
        jnp.zeros((bsz, h, p, n), jnp.float32)
        if init_state is None
        else init_state.astype(jnp.float32)
    )

    def body(carry, inputs):
        st_prev = carry
        st_chunk, dec = inputs                             # [B,H,P,N], [B,H]
        st_new = st_prev * dec[:, :, None, None] + st_chunk
        return st_new, st_prev

    (final_state, prev_states) = lax.scan(
        body,
        s0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    prev_states = jnp.moveaxis(prev_states, 0, 1)          # [B,NC,H,P,N]
    y_inter = jnp.einsum(
        "bcln,bchpn,bclh->bclhp", cr, prev_states, jnp.exp(cum)
    )
    y = (y_intra + y_inter).reshape(bsz, s, h, p)
    return y.astype(x.dtype), final_state


def ssd_decode_step(
    state: jnp.ndarray,  # [B, H, P, N] fp32
    x: jnp.ndarray,      # [B, 1, H, P]
    dt: jnp.ndarray,     # [B, 1, H]
    a: jnp.ndarray,      # [H]
    b_in: jnp.ndarray,   # [B, 1, N]
    c_in: jnp.ndarray,   # [B, 1, N]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """O(1) single-token SSD update.  Returns (y [B,1,H,P], new_state)."""
    xf = x[:, 0].astype(jnp.float32)
    dtf = dt[:, 0].astype(jnp.float32)
    bf = b_in[:, 0].astype(jnp.float32)
    cf = c_in[:, 0].astype(jnp.float32)
    decay = jnp.exp(dtf * a[None, :])                      # [B,H]
    upd = jnp.einsum("bhp,bn,bh->bhpn", xf, bf, dtf)
    new_state = state * decay[:, :, None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", new_state, cf)
    return y[:, None].astype(x.dtype), new_state


@scope("mamba")
def mamba2_block(
    params: Params,
    x: jnp.ndarray,
    cfg: Any,
    *,
    state: jnp.ndarray | None = None,
    conv_cache: jnp.ndarray | None = None,
    decode: bool = False,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray] | None]:
    """Full Mamba2 layer.  Training: state/conv_cache None, decode=False.
    Decode: x is [B,1,d]; returns (y, (new_state, new_conv_cache))."""
    n = cfg.ssm.d_state
    h = n_ssm_heads(cfg)
    p = cfg.ssm.d_head
    z = x @ params["w_in_z"]
    xin = x @ params["w_in_x"]
    bc = jnp.concatenate([x @ params["w_in_b"], x @ params["w_in_c"]], axis=-1)
    dt_raw = x @ params["w_in_dt"]
    conv_in = jnp.concatenate([xin, bc], axis=-1)
    conv_out, new_conv = _causal_conv(conv_in, params["conv_w"], conv_cache)
    din = xin.shape[-1]
    xc = conv_out[..., :din]
    b_in = conv_out[..., din : din + n]
    c_in = conv_out[..., din + n :]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    xh = xc.reshape(*xc.shape[:-1], h, p)
    if decode:
        assert state is not None
        y, new_state = ssd_decode_step(state, xh, dt, a, b_in, c_in)
    else:
        y, new_state = ssd_chunked(
            xh, dt, a, b_in, c_in, init_state=state,
            chunk=min(128, xh.shape[1]),
        )
    y = y + xh.astype(y.dtype) * params["d_skip"][None, None, :, None].astype(y.dtype)
    y = y.reshape(*xc.shape[:-1], din)
    # gated RMSNorm (mamba2) + output projection
    y = rms_norm(y * jax.nn.silu(z), params["norm_w"], cfg.norm_eps)
    out = y @ params["w_out"]
    caches = (new_state, new_conv) if (decode or state is not None) else None
    return out, caches
