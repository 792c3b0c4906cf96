"""Jit'd dispatch wrappers for the Pallas kernels.

``impl`` selection:
* ``"pallas"``    — real TPU lowering (production),
* ``"interpret"`` — Pallas interpret mode (CPU-correct, used by tests),
* ``"reference"`` — the pure-jnp spec from the model layers (dry-run path;
  XLA's cost model sees every op, keeping the roofline conservative).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rwkv6_wkv import wkv6_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.models.layers import attention_chunked
from repro.models.rwkv import wkv6_chunked
from repro.models.ssm import ssd_chunked


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl", "block_q", "block_kv"))
def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int = 0,
    impl: str = "reference",
    block_q: int | None = None,
    block_kv: int | None = None,
) -> jnp.ndarray:
    if impl == "pallas":
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window,
            block_q=block_q, block_kv=block_kv,
        )
    if impl == "interpret":
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window,
            block_q=block_q, block_kv=block_kv, interpret=True,
        )
    return attention_chunked(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def wkv6(
    r: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    logw: jnp.ndarray,
    u: jnp.ndarray,
    *,
    chunk: int = 32,
    impl: str = "reference",
) -> jnp.ndarray:
    if impl == "pallas":
        return wkv6_pallas(r, k, v, logw, u, chunk=chunk)
    if impl == "interpret":
        return wkv6_pallas(r, k, v, logw, u, chunk=chunk, interpret=True)
    return wkv6_chunked(r, k, v, logw, u, chunk=chunk)[0]


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    a: jnp.ndarray,
    b_in: jnp.ndarray,
    c_in: jnp.ndarray,
    *,
    chunk: int = 128,
    impl: str = "reference",
) -> jnp.ndarray:
    if impl == "pallas":
        return ssd_pallas(x, dt, a, b_in, c_in, chunk=chunk)
    if impl == "interpret":
        return ssd_pallas(x, dt, a, b_in, c_in, chunk=chunk, interpret=True)
    return ssd_chunked(x, dt, a, b_in, c_in, chunk=chunk)[0]
