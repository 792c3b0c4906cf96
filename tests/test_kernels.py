"""Pallas kernel validation: shape/dtype sweeps against the ref.py pure-jnp
oracles, executed in interpret mode on CPU."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ref import flash_attention_ref, ssd_ref, wkv6_ref
from repro.kernels.rwkv6_wkv import wkv6_pallas
from repro.kernels.ssd_scan import ssd_pallas

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 5e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,hq,hkv,d,block",
    [
        (1, 128, 4, 4, 64, 64),     # MHA
        (2, 128, 4, 2, 64, 32),     # GQA 2:1
        (1, 256, 8, 1, 128, 64),    # MQA
        (1, 192, 6, 3, 32, 64),     # non-pow2 seq (padding path)
        (2, 64, 15, 5, 64, 32),     # smollm-style 15:5 heads
    ],
)
def test_flash_attention_shapes(b, s, hq, hkv, d, block):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    ref = flash_attention_ref(q, k, v, causal=True)
    out = flash_attention_pallas(
        q, k, v, causal=True, block_q=block, block_kv=block, interpret=True
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


@pytest.mark.parametrize("window", [32, 100])
def test_flash_attention_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    out = flash_attention_pallas(
        q, k, v, causal=True, window=window, block_q=64, block_kv=64,
        interpret=True,
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64), dtype=dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64), dtype=dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64), dtype=dtype)
    ref = flash_attention_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=True,
    )
    out = flash_attention_pallas(
        q, k, v, causal=True, block_q=64, block_kv=64, interpret=True
    ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=ATOL[dtype])
    assert out.dtype == jnp.float32


def test_flash_attention_block_shape_independence():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 256, 2, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    outs = [
        flash_attention_pallas(q, k, v, causal=True, block_q=bq, block_kv=bk,
                               interpret=True)
        for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=2e-5)


# (name, b, s, hq, hkv, d, causal, window, dtype, block_q, block_kv); S is
# not a multiple of the blocks, so every case has padded rows and keys
GRAD_CASES = [
    ("gqa15_5", 1, 100, 15, 5, 64, True, 0, jnp.float32, 32, 64),
    ("gqa15_5_blocks_from_shape", 1, 100, 15, 5, 64, True, 0, jnp.float32, None, None),
    ("window", 2, 100, 4, 2, 64, True, 24, jnp.float32, 32, 32),
    ("window_empty_padded_rows", 1, 100, 4, 2, 64, True, 16, jnp.float32, 64, 32),
    ("not_causal", 1, 100, 4, 2, 64, False, 0, jnp.float32, 32, 64),
    ("bf16", 1, 100, 15, 5, 64, True, 0, jnp.bfloat16, 32, 64),
]


@pytest.mark.parametrize("case", GRAD_CASES, ids=[c[0] for c in GRAD_CASES])
def test_flash_attention_grad(case):
    """dQ, dK, dV of the kernel's own backward against autodiff of the
    full-matrix oracle in f32 on the same inputs."""
    from repro.models.layers import attention_naive

    _, b, s, hq, hkv, d, causal, window, dtype, bq, bk = case
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (b, s, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    w = jax.random.normal(ks[3], (b, s, hq, d), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w)

    flash = loss(lambda q, k, v: flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=bq, block_kv=bk, interpret=True))
    naive = loss(lambda q, k, v: attention_naive(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=causal, window=window))
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(naive, argnums=(0, 1, 2))(q, k, v)
    rtol = 1e-5 if dtype == jnp.float32 else 1e-2
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        assert g.dtype == dtype, name
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        assert np.all(np.isfinite(g)), name
        assert np.linalg.norm(g - r) <= rtol * np.linalg.norm(r), name


def test_forward_train_flash_matches_chunked():
    """Loss and gradients of the smollm smoke model: the flash kernel
    (interpret mode) against the chunked scan."""
    from repro.configs import smoke_config
    from repro.models.lm import forward_train, init_params_and_specs

    cfg = smoke_config("smollm-360m")
    params, _ = init_params_and_specs(jax.random.PRNGKey(0), cfg)
    rows = jax.random.randint(jax.random.PRNGKey(1), (2, 97), 0, cfg.vocab_size)
    batch = {"tokens": rows[:, :-1], "labels": rows[:, 1:]}

    def loss_and_grad(impl):
        c = cfg.replace(attention_impl=impl)
        return jax.value_and_grad(lambda p: forward_train(p, batch, c)[0])(params)

    (l_flash, g_flash), (l_ref, g_ref) = loss_and_grad("interpret"), loss_and_grad("chunked")
    np.testing.assert_allclose(float(l_flash), float(l_ref), rtol=1e-6)
    for a, r in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_ref)):
        a, r = np.asarray(a, np.float32), np.asarray(r, np.float32)
        assert np.linalg.norm(a - r) <= 1e-5 * max(np.linalg.norm(r), 1e-6)


@pytest.mark.parametrize("backend,taken,not_taken", [
    ("tpu", "attention.flash", "attention.chunked"),
    ("cpu", "attention.chunked", "attention.flash"),
])
def test_attention_dispatch_counts_the_path(monkeypatch, backend, taken, not_taken):
    """Without a KV cache, the default ``attention_impl`` traces the flash
    kernel on a TPU and the chunked scan elsewhere, and counts which."""
    from repro.configs import smoke_config
    from repro.models.lm import abstract_params, attention_path, forward_train
    from repro.monitor import trace

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = smoke_config("smollm-360m")
    assert cfg.attention_impl == "reference"
    assert attention_path(cfg) == ("pallas" if backend == "tpu" else "chunked")
    params, _ = abstract_params(cfg)
    batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32) for k in ("tokens", "labels")}
    before = trace.counters()
    jax.eval_shape(lambda p, b: forward_train(p, b, cfg), params, batch)
    after = trace.counters()
    assert after.get(taken, 0) > before.get(taken, 0)
    assert after.get(not_taken, 0) == before.get(not_taken, 0)


def test_attention_path_keeps_the_chunked_scan_under_a_mesh(monkeypatch):
    """A multi-device mesh keeps the chunked scan on a TPU; a one-device
    mesh takes the kernel; an explicit ``attention_impl`` is kept."""
    from types import SimpleNamespace

    from repro.configs import smoke_config
    from repro.models import lm

    cfg = smoke_config("smollm-360m")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for size, path in ((4, "chunked"), (1, "pallas")):
        ctx = SimpleNamespace(mesh=SimpleNamespace(size=size))
        monkeypatch.setattr(lm, "current", lambda ctx=ctx: ctx)
        assert lm.attention_path(cfg) == path
    assert lm.attention_path(cfg.replace(attention_impl="naive")) == "naive"


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,h,k,chunk",
    [(1, 64, 2, 64, 16), (2, 128, 4, 64, 32), (1, 96, 1, 32, 32)],
)
def test_wkv6_kernel_shapes(b, s, h, k, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (b, s, h, k)) * 0.5
    kk = jax.random.normal(ks[1], (b, s, h, k)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, k)) * 0.5
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, k)) * 0.5)
    u = jax.random.normal(ks[4], (h, k)) * 0.3
    y_ref, _ = wkv6_ref(r, kk, v, logw, u)
    y = wkv6_pallas(r, kk, v, logw, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y), atol=1e-4)


def test_wkv6_kernel_bf16():
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    B, S, H, K = 1, 64, 2, 64
    r = (jax.random.normal(ks[0], (B, S, H, K)) * 0.5).astype(jnp.bfloat16)
    k = (jax.random.normal(ks[1], (B, S, H, K)) * 0.5).astype(jnp.bfloat16)
    v = (jax.random.normal(ks[2], (B, S, H, K)) * 0.5).astype(jnp.bfloat16)
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, K)) * 0.5)
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    y_ref, _ = wkv6_ref(
        r.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        logw, u,
    )
    y = wkv6_pallas(r, k, v, logw.astype(jnp.bfloat16), u, chunk=16,
                    interpret=True).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(y_ref - y))) < 0.08


# ---------------------------------------------------------------------------
# mamba2 ssd
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "b,s,h,p,n,chunk",
    [(1, 64, 2, 64, 64, 32), (2, 128, 3, 64, 32, 64), (1, 128, 1, 32, 16, 128)],
)
def test_ssd_kernel_shapes(b, s, h, p, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    b_in = jax.random.normal(ks[3], (b, s, n)) * 0.5
    c_in = jax.random.normal(ks[4], (b, s, n)) * 0.5
    y_ref, _ = ssd_ref(x, dt, a, b_in, c_in)
    y = ssd_pallas(x, dt, a, b_in, c_in, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y), atol=2e-4)


def test_ssd_kernel_chunk_independence():
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    B, S, H, P, N = 1, 128, 2, 32, 32
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b_in = jax.random.normal(ks[3], (B, S, N)) * 0.5
    c_in = jax.random.normal(ks[4], (B, S, N)) * 0.5
    outs = [
        ssd_pallas(x, dt, a, b_in, c_in, chunk=c, interpret=True)
        for c in (16, 32, 64, 128)
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=2e-4)


def test_attention_block_pallas_impl_matches_reference():
    """Model-level wiring: attention_block(impl='interpret') == chunked."""
    from repro.configs import smoke_config
    from repro.models.layers import attention_block, init_attention, split_tree

    cfg = smoke_config("qwen3-4b")
    tree = init_attention(jax.random.PRNGKey(0), cfg, jnp.float32)
    params, _ = split_tree(tree)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    pos = jnp.arange(64)
    y_ref, _ = attention_block(params, x, cfg, positions=pos, impl="chunked")
    y_pal, _ = attention_block(params, x, cfg, positions=pos, impl="interpret")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_pal), atol=3e-5)
