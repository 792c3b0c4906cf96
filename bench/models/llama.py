"""Plain reference of the Llama block layout (SmolLM uses it), with its
weights, the map onto the program's parameter tree, and the algorithmic
operation and byte counts.

The reference follows the published equations in ``jax.numpy`` at
float32 and highest matmul precision: pre-norm RMSNorm, grouped-query
attention with rotary embeddings on the two halves of each head, a SwiGLU
MLP, a final RMSNorm and a head tied to the embedding when the config
says so.  It imports nothing of the program.

``precision="fp8"`` is the control: every matmul's operands are rounded
to float8 e4m3 (per-row scale for activations, per-output-channel scale
for weights) before the float32 product, the step below bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------
def dims(c: dict) -> dict:
    return {
        "L": c["num_hidden_layers"],
        "d": c["hidden_size"],
        "H": c["num_attention_heads"],
        "Hkv": c["num_key_value_heads"],
        "dh": c["head_dim"],
        "ff": c["intermediate_size"],
        "V": c["vocab_size"],
        "tied": bool(c["tie_word_embeddings"]),
        "eps": float(c["rms_norm_eps"]),
        "theta": float(c["rope_theta"]),
    }


def program_config(c: dict):
    """The program's ``ArchConfig`` with every size taken from the file."""
    from repro.configs import get_config

    n = dims(c)
    return get_config(c["arch"]).replace(
        n_layers=n["L"], d_model=n["d"], n_heads=n["H"], n_kv_heads=n["Hkv"],
        d_head=n["dh"], d_ff=n["ff"], vocab_size=n["V"], tie_embeddings=n["tied"],
        rope_theta=n["theta"], norm_eps=n["eps"], dtype=c["torch_dtype"],
    )


# ---------------------------------------------------------------------------
# weights: one jitted call from the seed, on the device, in the served dtype
# ---------------------------------------------------------------------------
def weight_shapes(c: dict) -> dict:
    n = dims(c)
    L, d, H, Hkv, dh, ff, V = n["L"], n["d"], n["H"], n["Hkv"], n["dh"], n["ff"], n["V"]
    shapes = {
        "embed": (V, d),
        "ln1": (L, d), "wq": (L, d, H, dh), "wk": (L, d, Hkv, dh), "wv": (L, d, Hkv, dh),
        "wo": (L, H, dh, d), "ln2": (L, d),
        "w_gate": (L, d, ff), "w_up": (L, d, ff), "w_down": (L, ff, d),
        "final_norm": (d,),
    }
    if not n["tied"]:
        shapes["unembed"] = (d, V)
    return shapes


def make_weights(c: dict, key, dtype=jnp.bfloat16) -> dict:
    """Gaussian weights, std 1/sqrt(fan in); embedding std 0.02; norm
    gains 1 + N(0, 0.1)."""
    shapes = weight_shapes(c)
    keys = dict(zip(sorted(shapes), jax.random.split(key, len(shapes))))
    out = {}
    for name, shape in shapes.items():
        k = keys[name]
        if name in ("ln1", "ln2", "final_norm"):
            w = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif name == "embed":
            w = 0.02 * jax.random.normal(k, shape, jnp.float32)
        else:
            fan_in = shape[1] if name != "wo" else shape[1] * shape[2]
            if name == "unembed":
                fan_in = shape[0]
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        out[name] = w.astype(dtype)
    return out


def to_program(w: dict) -> dict:
    """The program's parameter tree (``repro.models.lm`` dense layout)."""
    tree = {
        "embed": w["embed"],
        "layers": {
            "ln1": w["ln1"],
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]},
            "ln2": w["ln2"],
            "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"], "w_down": w["w_down"]},
        },
        "final_norm": w["final_norm"],
    }
    if "unembed" in w:
        tree["unembed"] = w["unembed"]
    return tree


def from_program(tree: dict) -> dict:
    lay = tree["layers"]
    w = {
        "embed": tree["embed"], "final_norm": tree["final_norm"],
        "ln1": lay["ln1"], "ln2": lay["ln2"], **lay["attn"], **lay["mlp"],
    }
    if "unembed" in tree:
        w["unembed"] = tree["unembed"]
    return w


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


def _mm(eq: str, x, w, precision: str, w_in_axes):
    """einsum with both operands in float32, or rounded to fp8 first."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, w_in_axes)
    return jnp.einsum(eq, x, w)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g.astype(jnp.float32)


def _rope(x, theta):
    """x [S, H, dh]: rotate the pair (x[i], x[i + dh/2]) by pos * theta^(-2i/dh)."""
    s, _, dh = x.shape
    inv = theta ** (-np.arange(0, dh // 2, dtype=np.float64) * 2.0 / dh)
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _layer(n: dict, precision: str, x, lw):
    """One block over one sequence x [S, d] (float32)."""
    s = x.shape[0]
    h = _rms(x, lw["ln1"], n["eps"])
    q = _rope(_mm("sd,dhk->shk", h, lw["wq"], precision, 0), n["theta"])
    k = _rope(_mm("sd,dhk->shk", h, lw["wk"], precision, 0), n["theta"])
    v = _mm("sd,dhk->shk", h, lw["wv"], precision, 0)
    g = n["H"] // n["Hkv"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhk,thk->hqt", q, k) / math.sqrt(n["dh"])
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, axis=-1), v)
    x = x + _mm("shk,hkd->sd", att, lw["wo"], precision, (0, 1))
    h = _rms(x, lw["ln2"], n["eps"])
    gate = _mm("sd,df->sf", h, lw["w_gate"], precision, 0)
    up = _mm("sd,df->sf", h, lw["w_up"], precision, 0)
    return x + _mm("sf,fd->sd", jax.nn.silu(gate) * up, lw["w_down"], precision, 0)


_LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "ln2", "w_gate", "w_up", "w_down")


def hidden(c: dict, w: dict, tokens, precision: str = "f32"):
    """Final-normed hidden states of one sequence, tokens [S] → [S, d]."""
    n = dims(c)
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    layer = jax.checkpoint(lambda x, lw: (_layer(n, precision, x, lw), None))
    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in _LAYER_KEYS})
    return _rms(x, w["final_norm"], n["eps"])


def head(c: dict, w: dict, h, precision: str = "f32"):
    if dims(c)["tied"]:
        return _mm("sd,vd->sv", h, w["embed"], precision, 1)
    return _mm("sd,dv->sv", h, w["unembed"], precision, 0)


def logits(c: dict, w: dict, tokens, precision: str = "f32"):
    """Logits of one sequence at every position, tokens [S] → [S, V]."""
    return head(c, w, hidden(c, w, tokens, precision), precision)


# ---------------------------------------------------------------------------
# training loss (for train cells): mean next-token cross entropy
# ---------------------------------------------------------------------------
def loss(c: dict, w: dict, tokens, labels, precision: str = "f32", rows=None):
    """Mean cross entropy over every label of the batch (or of ``rows``)."""
    if rows is not None:
        tokens, labels = tokens[rows], labels[rows]

    def one(args):
        t, y = args
        lg = logits(c, w, t, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0])

    total = jax.lax.map(jax.checkpoint(one), (tokens, labels))
    return jnp.sum(total) / labels.size


# ---------------------------------------------------------------------------
# algorithmic counts: what the equations need, from the actual lengths
# ---------------------------------------------------------------------------
def matmul_params(c: dict) -> int:
    """Weights that multiply each token's activations (the head included)."""
    n = dims(c)
    d, dh = n["d"], n["dh"]
    per_layer = d * (n["H"] + 2 * n["Hkv"]) * dh + n["H"] * dh * d + 3 * d * n["ff"]
    return n["L"] * per_layer + d * n["V"]


def param_count(c: dict) -> int:
    return sum(math.prod(s) for s in weight_shapes(c).values())


def attn_flops(c: dict, context: int) -> float:
    """QK^T and PV for one query against ``context`` keys, all layers."""
    n = dims(c)
    return 4.0 * n["L"] * n["H"] * n["dh"] * context


def prefill_flops(c: dict, length: int) -> float:
    """Forward of one prompt, causal attention over its own length."""
    return 2.0 * matmul_params(c) * length + attn_flops(c, 1) * length * (length + 1) / 2


def decode_flops(c: dict, position: int) -> float:
    """One generated token at ``position`` (it attends position + 1 keys)."""
    return 2.0 * matmul_params(c) + attn_flops(c, position + 1)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) per token of a causal row."""
    return 3.0 * prefill_flops(c, seq_len) / seq_len


def weight_bytes(c: dict, itemsize: int = 2) -> int:
    return param_count(c) * itemsize


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    n = dims(c)
    return 2 * n["L"] * n["Hkv"] * n["dh"] * itemsize


def decode_step_bytes(c: dict, contexts: list[int], itemsize: int = 2) -> float:
    """One decode step: the weights read once (the embedding row gathers
    aside, the tied head reads the table once), each active slot's cache
    read up to its own length and its new entry written."""
    kv = kv_bytes_per_token(c, itemsize)
    return weight_bytes(c, itemsize) + sum(kv * (ctx + 1) for ctx in contexts)
