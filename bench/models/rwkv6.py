"""Plain reference of the RWKV-6 ("Finch") block as the program states it,
with its weights, the map onto the program's parameter tree, and the
algorithmic operation counts.

Per layer, on x [B, S, d] (float32, highest matmul precision):

* time mix on a = RMSNorm(x): with a' the previous token's a (zero at the
  first), mix_i = a * mu_i + a' * (1 - mu_i) for i in r, k, v, w, g;
  r, k, v = mix @ W_{r,k,v} split into heads of ``head_size``; the log decay
  log w = -exp(mix_w @ W_w + b_w); g = silu(mix_g @ W_g); the WKV
  recurrence per head, S_0 = 0:
  y_t = r_t (S_{t-1} + diag(u) k_t^T v_t),  S_t = diag(w_t) S_{t-1} + k_t^T v_t;
  out = (RMSNorm(y) * g) @ W_o;  x += out.
* channel mix on c = RMSNorm(x): ck = c * m_0 + c' (1 - m_0), cr likewise
  with m_1; x += sigmoid(cr @ C_r) * (relu(ck @ C_k)^2 @ C_v).
* head: RMSNorm, then @ W_unembed.

Departures of this variant from the paper (arXiv:2404.05892), which are the
program's and which the reference therefore shares: static token-shift
mixes (no data-dependent LoRA), a decay without LoRA, RMSNorm in place of
LayerNorm, and one RMSNorm over all heads in place of the per-head
GroupNorm.  The WKV here is the defining recurrence; the program's
training path evaluates it in chunks.

``precision="fp8"`` rounds every matmul operand to float8 e4m3 (the
control).  ``exchange="local"`` keeps only the first ``shards``-th of each
row-parallel projection's inputs (W_o, C_v), what one of four chips would
hold if the all-reduce after a tensor-parallel layer were left out.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    return {
        "L": c["num_hidden_layers"],
        "d": c["hidden_size"],
        "hs": c["head_size"],
        "H": c["hidden_size"] // c["head_size"],
        "ff": c["intermediate_size"],
        "V": c["vocab_size"],
        "eps": float(c["layer_norm_epsilon"]),
    }


def program_config(c: dict):
    from repro.configs import get_config
    from repro.models.config import RWKVConfig

    n = dims(c)
    return get_config(c["arch"]).replace(
        n_layers=n["L"], d_model=n["d"], n_heads=n["H"], n_kv_heads=n["H"],
        d_head=n["hs"], d_ff=n["ff"], vocab_size=n["V"], tie_embeddings=False,
        norm_eps=n["eps"], dtype=c["torch_dtype"], rwkv=RWKVConfig(head_size=n["hs"]),
    )


_F32 = ("mu", "w_bias", "u", "cm_mu")  # the program keeps these in float32


def weight_shapes(c: dict) -> dict:
    n = dims(c)
    L, d, H, hs, ff, V = n["L"], n["d"], n["H"], n["hs"], n["ff"], n["V"]
    return {
        "embed": (V, d), "unembed": (d, V), "final_norm": (d,),
        "ln1": (L, d), "ln2": (L, d),
        "mu": (L, 5, d), "w_r": (L, d, d), "w_k": (L, d, d), "w_v": (L, d, d),
        "w_g": (L, d, d), "w_w": (L, d, d), "w_bias": (L, d), "u": (L, H, hs),
        "ln_w": (L, d), "w_o": (L, d, d),
        "cm_mu": (L, 2, d), "cm_k": (L, d, ff), "cm_v": (L, ff, d), "cm_r": (L, d, d),
    }


def make_weights(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """Projections N(0, 1/fan_in); the decay projection at a quarter of
    that and a bias uniform in [-3, -1] (per-step decays 0.69-0.95);
    mixes uniform in [0, 1]; bonus u ~ N(0, 0.3^2); norm gains
    1 + N(0, 0.1^2); embedding N(0, 1e-2)."""
    shapes = weight_shapes(c)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        k = keys[name]
        if name in ("ln1", "ln2", "ln_w", "final_norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name in ("mu", "cm_mu"):
            w = jax.random.uniform(k, shape, jnp.float32)
        elif name == "w_bias":
            w = jax.random.uniform(k, shape, jnp.float32, -3.0, -1.0)
        elif name == "u":
            w = 0.3 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            w = 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            fan_in = shape[-2]
            scale = 0.25 if name == "w_w" else 1.0
            w = scale * jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        out[name] = w if name in _F32 else w.astype(dtype)
    return out


_TOP = ("embed", "unembed", "final_norm")


def to_program(w: dict) -> dict:
    return {
        "embed": w["embed"], "unembed": w["unembed"], "final_norm": w["final_norm"],
        "layers": {k: v for k, v in w.items() if k not in _TOP},
    }


def from_program(tree: dict) -> dict:
    return {**{k: tree[k] for k in _TOP}, **tree["layers"]}


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------
def _fp8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``; the
    gradient passes straight through, so only the forward is rounded.  The
    scaled values are clipped to the format's +-448 first: a quotient a
    rounding above it would otherwise convert to NaN on some backends."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jax.lax.stop_gradient(jnp.where(amax > 0, amax / 448.0, 1.0))
    q = jnp.clip(x / scale, -448.0, 448.0)
    q = q.astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, precision: str):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def _shift(x):
    """The previous token's features along the sequence axis; zero first."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, logw, u, block: int = 64):
    """The recurrence over sequences r, k, v, logw [B, S, H, hs].

    Time steps run in blocks of ``block`` whose inner steps are
    recomputed in the backward pass, so only one state per block is kept."""
    b, s, h, hs = r.shape

    def step(state, inp):
        rt, kt, vt, lwt = inp                                  # [B, H, hs]
        kv = kt[..., :, None] * vt[..., None, :]               # [B, H, K, V]
        y = jnp.einsum("bhk,bhkv->bhv", rt, state + u[None, :, :, None] * kv)
        return jnp.exp(lwt)[..., None] * state + kv, y

    @jax.checkpoint
    def run_block(state, blk):
        return jax.lax.scan(step, state, blk)

    block = min(block, s)
    nb = s // block

    def blocks(x):  # [B, S, H, hs] -> [nb, block, B, H, hs]
        return jnp.moveaxis(x, 1, 0).reshape(nb, block, b, h, hs)

    s0 = jnp.zeros((b, h, hs, hs), jnp.float32)
    _, ys = jax.lax.scan(run_block, s0, tuple(blocks(x) for x in (r, k, v, logw)))
    return jnp.moveaxis(ys.reshape(s, b, h, hs), 0, 1)


def _local(x, shards: int):
    """The first 1/shards of the feature axis; the rest zeroed."""
    keep = x.shape[-1] // shards
    return x * (jnp.arange(x.shape[-1]) < keep)


def _layer(n: dict, precision: str, exchange: str, shards: int, x, lw):
    b, s, _ = x.shape
    H, hs = n["H"], n["hs"]
    a = _rms(x, lw["ln1"], n["eps"])
    a_prev = _shift(a)
    mu = lw["mu"].astype(jnp.float32)
    mix = [a * mu[i] + a_prev * (1.0 - mu[i]) for i in range(5)]
    r = _mm(mix[0], lw["w_r"], precision).reshape(b, s, H, hs)
    k = _mm(mix[1], lw["w_k"], precision).reshape(b, s, H, hs)
    v = _mm(mix[2], lw["w_v"], precision).reshape(b, s, H, hs)
    logw = -jnp.exp(_mm(mix[3], lw["w_w"], precision) + lw["w_bias"]).reshape(b, s, H, hs)
    g = jax.nn.silu(_mm(mix[4], lw["w_g"], precision))
    y = wkv(r, k, v, logw, lw["u"].astype(jnp.float32)).reshape(b, s, n["d"])
    y = _rms(y, lw["ln_w"], n["eps"]) * g
    if exchange == "local":
        y = _local(y, shards)
    x = x + _mm(y, lw["w_o"], precision)
    c = _rms(x, lw["ln2"], n["eps"])
    c_prev = _shift(c)
    m = lw["cm_mu"].astype(jnp.float32)
    ck = c * m[0] + c_prev * (1.0 - m[0])
    cr = c * m[1] + c_prev * (1.0 - m[1])
    kk = jnp.square(jax.nn.relu(_mm(ck, lw["cm_k"], precision)))
    if exchange == "local":
        kk = _local(kk, shards)
    return x + jax.nn.sigmoid(_mm(cr, lw["cm_r"], precision)) * _mm(kk, lw["cm_v"], precision)


def hidden(c: dict, w: dict, tokens, precision: str = "f32", exchange: str = "full",
           shards: int = 4):
    """Final-normed hidden states, tokens [B, S] -> [B, S, d]."""
    n = dims(c)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    layer = jax.checkpoint(lambda x, lw: (_layer(n, precision, exchange, shards, x, lw), None))
    x, _ = jax.lax.scan(layer, x, {k: v for k, v in w.items() if k not in _TOP})
    return _rms(x, w["final_norm"], n["eps"])


def logits(c: dict, w: dict, tokens, precision: str = "f32", exchange: str = "full",
           shards: int = 4):
    """Logits at every position, tokens [B, S] -> [B, S, V]."""
    return _mm(hidden(c, w, tokens, precision, exchange, shards), w["unembed"], precision)


def loss(c: dict, w: dict, tokens, labels, precision: str = "f32", rows=None,
         exchange: str = "full", shards: int = 4):
    """Mean cross entropy over every label of the batch (or of ``rows``);
    the head runs one row at a time."""
    if rows is not None:
        tokens, labels = tokens[rows], labels[rows]
    h = hidden(c, w, tokens, precision, exchange, shards)

    def one(args):
        hr, y = args
        lg = _mm(hr, w["unembed"], precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0])

    total = jax.lax.map(jax.checkpoint(one), (h, labels))
    return jnp.sum(total) / labels.size


# ---------------------------------------------------------------------------
# algorithmic counts
# ---------------------------------------------------------------------------
def matmul_params(c: dict) -> int:
    n = dims(c)
    d, ff = n["d"], n["ff"]
    return n["L"] * (6 * d * d + 2 * d * ff + d * d) + d * n["V"]


def param_count(c: dict) -> int:
    return sum(math.prod(s) for s in weight_shapes(c).values())


def wkv_flops_per_token(c: dict) -> float:
    """Per head: r^T (S + u k^T v) and diag(w) S + k^T v, 2 flops a
    multiply-add over the K x V state, twice."""
    n = dims(c)
    return n["L"] * n["H"] * 4.0 * n["hs"] * n["hs"]


def train_flops_per_token(c: dict, seq_len: int) -> float:
    return 3.0 * (2.0 * matmul_params(c) + wkv_flops_per_token(c))
