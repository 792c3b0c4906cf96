"""Decoder LM assembly for all assigned architecture families.

Families and their layer stacks (all scan-over-layers for O(1) HLO size —
the requirement for compiling 40-layer models on the 512-device dry-run):

* dense  — [attn + mlp] × L, one scan (mistral-nemo, smollm, qwen3).
* gemma3 — 5 local(sliding-window):1 global pattern: scan over superblocks
  (inner scan over 5 stacked local layers + 1 global layer), plus a tail
  scan for the remainder layers (34 = 5×6 + 4).
* moe    — [attn + moe] × L (olmoe, deepseek-moe w/ shared experts).
* vlm    — dense backbone; stub ViT frontend supplies patch embeddings
  spliced over the first ``n_patches`` token positions (internvl2).
* audio  — dense backbone consuming precomputed frame embeddings
  (musicgen; EnCodec frontend is a stub per the assignment).
* ssm    — [rwkv6 time-mix + channel-mix] × L (rwkv6, attention-free).
* hybrid — mamba2 × L with ONE shared attention+mlp block applied every
  ``attn_every`` layers (zamba2; weight sharing across applications).

Three entry points per family: ``forward_train`` (loss), ``forward_prefill``
(last-token logits + caches), ``forward_decode`` (one token against caches).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.config import ArchConfig
from repro.models.layers import (
    Params,
    attention_block,
    dense_init,
    init_attention,
    init_mlp,
    mlp_block,
    ones_init,
    rms_norm,
    split_tree,
)
from repro.models.moe import init_moe, moe_block
from repro.models.rwkv import (
    init_rwkv6,
    n_rwkv_heads,
    rwkv6_channel_mix,
    rwkv6_time_mix,
)
from repro.models.ssm import d_inner, init_mamba2, mamba2_block, n_ssm_heads
from repro.monitor.trace import scope
from repro.parallel.context import constrain_residual, current


# ---------------------------------------------------------------------------
# init plumbing: init fns return trees of (array, axes) pairs; axes are
# static strings, so stacking separates values (vmap-able) from specs
# (captured by tracing side-channel).
# ---------------------------------------------------------------------------
def _is_axes(x: Any) -> bool:
    return (
        isinstance(x, tuple)
        and len(x) > 0
        and all(isinstance(a, (str, type(None))) for a in x)
    )


def split_eval_shape(fn, *args) -> tuple[Any, Any]:
    """eval_shape for pair-returning init fns → (value ShapeDtypeStructs,
    specs).  Specs are captured during tracing (they are static)."""
    box: dict[str, Any] = {}

    def values_fn(*a):
        values, specs = split_tree(fn(*a))
        box["specs"] = specs
        return values

    v_sds = jax.eval_shape(values_fn, *args)
    return v_sds, box["specs"]


def map_specs(specs: Any, fn) -> Any:
    return jax.tree.map(fn, specs, is_leaf=_is_axes)


def join_pairs(values: Any, specs: Any) -> Any:
    """Zip a values tree with a specs tree (specs leaves = axes tuples)."""
    flat_v, treedef = jax.tree.flatten(values)
    flat_s = treedef.flatten_up_to(specs)
    return jax.tree.unflatten(
        treedef, [(v, s) for v, s in zip(flat_v, flat_s)]
    )


def _stack_init(key: jax.Array, n: int, fn, prefix: tuple[str, ...] = ("layers",)) -> Any:
    """vmap an init over n layer keys → stacked (value, axes) pairs with
    ``prefix`` logical axes prepended."""
    keys = jax.random.split(key, n)

    def values_fn(k):
        return split_tree(fn(k))[0]

    stacked = jax.vmap(values_fn)(keys)
    _, specs = split_eval_shape(fn, keys[0])
    specs = map_specs(specs, lambda s: (*prefix, *s))
    return join_pairs(stacked, specs)


def _stack2_init(key: jax.Array, n_outer: int, n_inner: int, fn) -> Any:
    """Doubly-stacked init: [n_outer, n_inner, ...] with
    ("layer_groups", "layers") axes prepended (gemma/zamba superblocks)."""
    flat_keys = jax.random.split(key, n_outer * n_inner)
    keys = flat_keys.reshape(n_outer, n_inner, *flat_keys.shape[1:])

    def values_fn(k):
        return split_tree(fn(k))[0]

    stacked = jax.vmap(jax.vmap(values_fn))(keys)
    _, specs = split_eval_shape(fn, flat_keys[0])
    specs = map_specs(specs, lambda s: ("layer_groups", "layers", *s))
    return join_pairs(stacked, specs)


def _dense_layer_init(cfg: ArchConfig, dtype: Any):
    def fn(k: jax.Array) -> Params:
        k1, k2 = jax.random.split(k)
        return {
            "ln1": ones_init((cfg.d_model,), ("embed",), dtype),
            "attn": init_attention(k1, cfg, dtype),
            "ln2": ones_init((cfg.d_model,), ("embed",), dtype),
            "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype),
        }

    return fn


def _moe_layer_init(cfg: ArchConfig, dtype: Any):
    def fn(k: jax.Array) -> Params:
        k1, k2 = jax.random.split(k)
        return {
            "ln1": ones_init((cfg.d_model,), ("embed",), dtype),
            "attn": init_attention(k1, cfg, dtype),
            "ln2": ones_init((cfg.d_model,), ("embed",), dtype),
            "moe": init_moe(k2, cfg, dtype),
        }

    return fn


def _rwkv_layer_init(cfg: ArchConfig, dtype: Any):
    def fn(k: jax.Array) -> Params:
        return {
            "ln1": ones_init((cfg.d_model,), ("embed",), dtype),
            "ln2": ones_init((cfg.d_model,), ("embed",), dtype),
            **init_rwkv6(k, cfg, dtype),
        }

    return fn


def _mamba_layer_init(cfg: ArchConfig, dtype: Any):
    def fn(k: jax.Array) -> Params:
        return {
            "ln": ones_init((cfg.d_model,), ("embed",), dtype),
            "mamba": init_mamba2(k, cfg, dtype),
        }

    return fn


def gemma_partition(cfg: ArchConfig) -> tuple[int, int, int]:
    """(n_superblocks, locals_per_super, tail_locals)."""
    pattern = cfg.local_global_pattern  # locals per global
    n_super = cfg.n_layers // (pattern + 1)
    tail = cfg.n_layers - n_super * (pattern + 1)
    return n_super, pattern, tail


def zamba_partition(cfg: ArchConfig) -> tuple[int, int, int]:
    """(n_superblocks, mambas_per_super, tail_mambas)."""
    per = cfg.attn_every
    n_super = cfg.n_layers // per
    tail = cfg.n_layers - n_super * per
    return n_super, per, tail


def init_lm(key: jax.Array, cfg: ArchConfig) -> Any:
    """Returns a tree of (array, logical_axes) pairs."""
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    tree: dict[str, Any] = {}
    if cfg.frontend != "audio_stub":
        tree["embed"] = dense_init(
            keys[0], (cfg.vocab_padded, cfg.d_model), ("vocab", "embed"), dtype,
            scale=0.02,  # GPT-style; keeps tied-embedding logits sane at init
        )
    if cfg.frontend == "vit_stub":
        tree["frontend"] = {
            "proj1": dense_init(
                keys[1], (cfg.d_frontend, cfg.d_model), (None, "embed"), dtype
            ),
            "proj2": dense_init(
                keys[2], (cfg.d_model, cfg.d_model), ("embed", "embed"), dtype
            ),
        }
    fam = cfg.family
    if fam in ("dense", "vlm", "audio") and not cfg.local_global_pattern:
        tree["layers"] = _stack_init(keys[3], cfg.n_layers, _dense_layer_init(cfg, dtype))
    elif fam == "dense" and cfg.local_global_pattern:
        n_super, per, tail = gemma_partition(cfg)
        k1, k2, k3 = jax.random.split(keys[3], 3)
        tree["local_layers"] = _stack2_init(k1, n_super, per, _dense_layer_init(cfg, dtype))
        tree["global_layers"] = _stack_init(k2, n_super, _dense_layer_init(cfg, dtype))
        if tail:
            tree["tail_layers"] = _stack_init(k3, tail, _dense_layer_init(cfg, dtype))
    elif fam == "moe":
        tree["layers"] = _stack_init(keys[3], cfg.n_layers, _moe_layer_init(cfg, dtype))
    elif fam == "ssm":
        tree["layers"] = _stack_init(keys[3], cfg.n_layers, _rwkv_layer_init(cfg, dtype))
    elif fam == "hybrid":
        n_super, per, tail = zamba_partition(cfg)
        k1, k2, k3 = jax.random.split(keys[3], 3)
        tree["mamba_layers"] = _stack2_init(k1, n_super, per, _mamba_layer_init(cfg, dtype))
        tree["shared_attn"] = _dense_layer_init(cfg, dtype)(k2)  # ONE shared block
        if tail:
            tree["tail_layers"] = _stack_init(k3, tail, _mamba_layer_init(cfg, dtype))
    else:
        raise ValueError(f"unknown family {fam}")
    tree["final_norm"] = ones_init((cfg.d_model,), ("embed",), dtype)
    if not cfg.tie_embeddings:
        tree["unembed"] = dense_init(
            keys[4], (cfg.d_model, cfg.vocab_padded), ("embed", "vocab"), dtype
        )
    return tree


def init_params_and_specs(key: jax.Array, cfg: ArchConfig) -> tuple[Any, Any]:
    return split_tree(init_lm(key, cfg))


def abstract_params(cfg: ArchConfig) -> tuple[Any, Any]:
    """(ShapeDtypeStruct tree, specs tree) — no allocation (dry-run path)."""
    return split_eval_shape(lambda k: init_lm(k, cfg), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------
@scope("embed")
def embed_tokens(params: Any, tokens: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    return jnp.take(params["embed"], tokens, axis=0)


def splice_patches(
    params: Any, x: jnp.ndarray, patch_embeds: jnp.ndarray, cfg: ArchConfig
) -> jnp.ndarray:
    """VLM stub frontend: project patch features and overwrite the first
    n_patches positions (image-token splicing)."""
    proj = jax.nn.gelu(patch_embeds.astype(x.dtype) @ params["frontend"]["proj1"])
    proj = proj @ params["frontend"]["proj2"]
    return lax.dynamic_update_slice_in_dim(x, proj.astype(x.dtype), 0, axis=1)


@scope("head_loss")
def lm_logits(params: Any, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return x @ w


@scope("head_loss")
def chunked_ce_loss(
    params: Any,
    x: jnp.ndarray,
    labels: jnp.ndarray,
    cfg: ArchConfig,
    *,
    chunk: int = 512,
) -> jnp.ndarray:
    """Cross-entropy over the (possibly huge) vocab without materializing
    [B, S, V] at once: scan over sequence chunks."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = x.shape[1] // chunk
    xc = jnp.moveaxis(x.reshape(b, nc, chunk, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, nc, chunk), 1, 0)

    def body(carry, inputs):
        tot, cnt = carry
        xb, lb = inputs
        logits = lm_logits(params, xb, cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(lb, 0)[..., None], axis=-1
        )[..., 0]
        valid = (lb >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((lse - tgt) * valid)
        cnt = cnt + jnp.sum(valid)
        return (tot, cnt), None

    (tot, cnt), _ = lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (xc, lc)
    )
    return tot / jnp.maximum(cnt, 1.0)


def attention_path(cfg: ArchConfig) -> str:
    """The path of attention without a KV cache (``attention_block``'s ``impl``).

    ``attention_impl="reference"`` resolves to the flash kernel on a TPU and
    to the chunked scan elsewhere.  Under a multi-device mesh it stays on the
    chunked scan: the partitioner cannot split a kernel's custom call."""
    if cfg.attention_impl != "reference":
        return cfg.attention_impl
    ctx = current()
    one_device = ctx is None or ctx.mesh.size == 1
    return "pallas" if jax.default_backend() == "tpu" and one_device else "chunked"


# ---------------------------------------------------------------------------
# layer bodies (shared by train/prefill; decode variants below)
# ---------------------------------------------------------------------------
def _dense_body(cfg: ArchConfig, positions, window: int = 0, impl: str = "chunked"):
    def body(x, lp):
        h, _ = attention_block(
            lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps), cfg,
            positions=positions, window=window, impl=impl,
        )
        x = x + _named(h, "attn_out", cfg)
        h2 = mlp_block(lp["mlp"], rms_norm(x, lp["ln2"], cfg.norm_eps))
        x = x + _named(h2, "mlp_out", cfg)
        return constrain_residual(x), None

    return body


def _moe_body(cfg: ArchConfig, positions, impl: str = "chunked"):
    def body(carry, lp):
        x, aux = carry
        h, _ = attention_block(
            lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps), cfg,
            positions=positions, impl=impl,
        )
        x = x + _named(h, "attn_out", cfg)
        m, a = moe_block(lp["moe"], rms_norm(x, lp["ln2"], cfg.norm_eps), cfg)
        return (constrain_residual(x + _named(m, "moe_out", cfg)), aux + a), None

    return body


def _rwkv_body(cfg: ArchConfig):
    def body(x, lp):
        h, _ = rwkv6_time_mix(lp, rms_norm(x, lp["ln1"], cfg.norm_eps), cfg)
        x = x + h
        h, _ = rwkv6_channel_mix(lp, rms_norm(x, lp["ln2"], cfg.norm_eps))
        return constrain_residual(x + h), None

    return body


def _mamba_body(cfg: ArchConfig):
    def body(x, lp):
        h, _ = mamba2_block(lp["mamba"], rms_norm(x, lp["ln"], cfg.norm_eps), cfg)
        return constrain_residual(x + h), None

    return body


_SAVE_NAMES = ("attn_out", "mlp_out", "moe_out", "mix_out")


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "names":
        # §Perf remat-policy: save each sub-block's output (the tensors
        # whose recomputation would REPLAY the TP/EP collectives in the
        # backward pass) while rematerializing everything else.  Trades
        # ~2×[B,S,d] saved bytes per layer for one fewer collective pass.
        policy = jax.checkpoint_policies.save_only_these_names(*_SAVE_NAMES)
        return jax.checkpoint(fn, policy=policy)
    return fn


def _named(x: jnp.ndarray, name: str, cfg: ArchConfig) -> jnp.ndarray:
    if cfg.remat == "names":
        from jax.ad_checkpoint import checkpoint_name

        return checkpoint_name(x, name)
    return x


# ---------------------------------------------------------------------------
# trunk forward (train / prefill share this)
# ---------------------------------------------------------------------------
#: ``lax.scan`` over the trunk's layers, under the ``layers`` scope
_scan_layers = scope("layers")(lax.scan)


def forward_trunk(params: Any, x: jnp.ndarray, cfg: ArchConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Run all layers; returns (hidden, aux_loss)."""
    b, s, _ = x.shape
    positions = jnp.arange(s, dtype=jnp.int32)
    impl = attention_path(cfg)
    aux = jnp.zeros((), jnp.float32)
    fam = cfg.family
    if fam in ("dense", "vlm", "audio") and not cfg.local_global_pattern:
        body = _maybe_remat(_dense_body(cfg, positions, 0, impl), cfg)
        x, _ = _scan_layers(body, x, params["layers"])
    elif fam == "dense" and cfg.local_global_pattern:
        local_body = _maybe_remat(
            _dense_body(cfg, positions, cfg.sliding_window, impl), cfg
        )
        global_body = _maybe_remat(_dense_body(cfg, positions, 0, impl), cfg)

        def super_body(xc, lp):
            xc, _ = _scan_layers(local_body, xc, lp["local"])
            xc, _ = global_body(xc, lp["global"])
            return xc, None

        stacked = {"local": params["local_layers"], "global": params["global_layers"]}
        x, _ = _scan_layers(super_body, x, stacked)
        if "tail_layers" in params:
            x, _ = _scan_layers(local_body, x, params["tail_layers"])
    elif fam == "moe":
        body = _maybe_remat(_moe_body(cfg, positions, impl), cfg)
        (x, aux), _ = _scan_layers(body, (x, aux), params["layers"])
    elif fam == "ssm":
        body = _maybe_remat(_rwkv_body(cfg), cfg)
        x, _ = _scan_layers(body, x, params["layers"])
    elif fam == "hybrid":
        mamba_body = _maybe_remat(_mamba_body(cfg), cfg)
        attn_body = _maybe_remat(
            _dense_body(cfg, positions, 0, impl), cfg
        )

        def super_body(xc, lp):
            xc, _ = _scan_layers(mamba_body, xc, lp)
            xc, _ = attn_body(xc, params["shared_attn"])  # shared weights
            return xc, None

        x, _ = _scan_layers(super_body, x, params["mamba_layers"])
        if "tail_layers" in params:
            x, _ = _scan_layers(mamba_body, x, params["tail_layers"])
    else:
        raise ValueError(fam)
    return rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def _input_embeds(params: Any, batch: dict[str, jnp.ndarray], cfg: ArchConfig) -> jnp.ndarray:
    if cfg.frontend == "audio_stub":
        x = batch["frame_embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.frontend == "vit_stub":
        x = splice_patches(params, x, batch["patch_embeds"], cfg)
    return x


def forward_train(
    params: Any, batch: dict[str, jnp.ndarray], cfg: ArchConfig
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    x = _input_embeds(params, batch, cfg)
    h, aux = forward_trunk(params, x, cfg)
    loss = chunked_ce_loss(params, h, batch["labels"], cfg)
    total = loss + 0.01 * aux
    return total, {"ce_loss": loss, "aux_loss": aux}


def forward_prefill(
    params: Any, batch: dict[str, jnp.ndarray], cfg: ArchConfig
) -> tuple[jnp.ndarray, Any]:
    """Prefill: full-context forward; returns (last-token logits, caches).

    Caches come from ``build_caches_from_prefill`` — attention K/V for every
    layer (what a serving system keeps), or SSM/RWKV states.
    """
    x = _input_embeds(params, batch, cfg)
    h, _ = forward_trunk(params, x, cfg)
    logits = lm_logits(params, h[:, -1:, :], cfg)
    caches = build_prefill_caches(params, x, cfg)
    return logits, caches


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def cache_specs(cfg: ArchConfig, batch: int, max_seq: int) -> Any:
    """ShapeDtypeStruct tree of decode caches (also the logical layout)."""
    dt = jnp.dtype(cfg.dtype)
    fam = cfg.family
    kv = lambda: (  # noqa: E731
        jax.ShapeDtypeStruct((batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt),
        jax.ShapeDtypeStruct((batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt),
    )
    if fam in ("dense", "vlm", "audio") and not cfg.local_global_pattern:
        return {
            "kv": (
                jax.ShapeDtypeStruct(
                    (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt
                ),
            )
            * 2
        }
    if fam == "dense" and cfg.local_global_pattern:
        n_super, per, tail = gemma_partition(cfg)
        out = {
            "local_kv": (
                jax.ShapeDtypeStruct(
                    (n_super, per, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt
                ),
            )
            * 2,
            "global_kv": (
                jax.ShapeDtypeStruct(
                    (n_super, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt
                ),
            )
            * 2,
        }
        if tail:
            out["tail_kv"] = (
                jax.ShapeDtypeStruct(
                    (tail, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt
                ),
            ) * 2
        return out
    if fam == "moe":
        return {
            "kv": (
                jax.ShapeDtypeStruct(
                    (cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt
                ),
            )
            * 2
        }
    if fam == "ssm":
        h = n_rwkv_heads(cfg)
        hs = cfg.rwkv.head_size
        return {
            "wkv": jax.ShapeDtypeStruct(
                (cfg.n_layers, batch, h, hs, hs), jnp.float32
            ),
            "tm_last": jax.ShapeDtypeStruct((cfg.n_layers, batch, 1, cfg.d_model), dt),
            "cm_last": jax.ShapeDtypeStruct((cfg.n_layers, batch, 1, cfg.d_model), dt),
        }
    if fam == "hybrid":
        n_super, per, tail = zamba_partition(cfg)
        h = n_ssm_heads(cfg)
        din = d_inner(cfg)
        n = cfg.ssm.d_state
        out = {
            "ssm": jax.ShapeDtypeStruct(
                (n_super, per, batch, h, cfg.ssm.d_head, n), jnp.float32
            ),
            "conv": jax.ShapeDtypeStruct(
                (n_super, per, batch, cfg.ssm.d_conv - 1, din + 2 * n), dt
            ),
            "attn_kv": (
                jax.ShapeDtypeStruct(
                    (n_super, batch, max_seq, cfg.n_kv_heads, cfg.d_head), dt
                ),
            )
            * 2,
        }
        if tail:
            out["tail_ssm"] = jax.ShapeDtypeStruct(
                (tail, batch, h, cfg.ssm.d_head, n), jnp.float32
            )
            out["tail_conv"] = jax.ShapeDtypeStruct(
                (tail, batch, cfg.ssm.d_conv - 1, din + 2 * n), dt
            )
        return out
    raise ValueError(fam)


def cache_logical_specs(cfg: ArchConfig) -> Any:
    """Logical axes mirroring ``cache_specs`` — drives decode sharding.

    KV caches carry a "kv_seq" axis: for long-context decode the sharding
    rules map it to the model axis (the KV heads then replicate via the
    rule engine's conflict fallback), which is what keeps 32k×128 and
    500k×1 caches within per-device HBM.
    """
    fam = cfg.family
    kv_axes = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
    if fam in ("dense", "vlm", "audio", "moe") and not cfg.local_global_pattern:
        return {"kv": (kv_axes, kv_axes)}
    if fam == "dense" and cfg.local_global_pattern:
        _, _, tail = gemma_partition(cfg)
        deep = ("layer_groups",) + kv_axes
        out = {"local_kv": (deep, deep), "global_kv": (kv_axes, kv_axes)}
        if tail:
            out["tail_kv"] = (kv_axes, kv_axes)
        return out
    if fam == "ssm":
        return {
            "wkv": ("layers", "batch", "heads", None, None),
            "tm_last": ("layers", "batch", None, None),
            "cm_last": ("layers", "batch", None, None),
        }
    if fam == "hybrid":
        _, _, tail = zamba_partition(cfg)
        out = {
            "ssm": ("layer_groups", "layers", "batch", "heads", None, None),
            "conv": ("layer_groups", "layers", "batch", None, "mlp"),
            "attn_kv": (
                ("layer_groups",) + kv_axes[1:],
                ("layer_groups",) + kv_axes[1:],
            ),
        }
        if tail:
            out["tail_ssm"] = ("layers", "batch", "heads", None, None)
            out["tail_conv"] = ("layers", "batch", None, "mlp")
        return out
    raise ValueError(fam)


def zero_caches(cfg: ArchConfig, batch: int, max_seq: int) -> Any:
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_specs(cfg, batch, max_seq)
    )


def build_prefill_caches(params: Any, x_embeds: jnp.ndarray, cfg: ArchConfig) -> Any:
    """Placeholder prefill-cache builder: serving keeps K/V from prefill.

    For the dry-run we lower ``forward_prefill`` whose cache cost is the
    trunk recompute of K/V projections; a production server would thread
    cache outputs through the trunk scan.  Here we return zeros of the
    right shape so the step's interface (and memory footprint) is honest.
    """
    b, s, _ = x_embeds.shape
    return zero_caches(cfg, b, s)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def forward_decode(
    params: Any,
    batch: dict[str, jnp.ndarray],
    caches: Any,
    position: jnp.ndarray,
    cfg: ArchConfig,
) -> tuple[jnp.ndarray, Any]:
    """One decode step.  batch["token"]: [B,1] (or frame embed for audio);
    ``position``: scalar int32 — the index the new token occupies.
    Returns (logits [B,1,V], updated caches)."""
    if cfg.frontend == "audio_stub":
        x = batch["frame_embeds"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, batch["token"], cfg)
    positions = position + jnp.zeros((1,), jnp.int32)
    impl = attention_path(cfg)
    fam = cfg.family
    new_caches: dict[str, Any] = {}

    def dense_decode(x, lp, kv, window=0):
        h, new_kv = attention_block(
            lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps), cfg,
            positions=positions, window=window,
            kv_cache=kv, cache_length=position, impl=impl,
        )
        x = x + h
        x = x + mlp_block(lp["mlp"], rms_norm(x, lp["ln2"], cfg.norm_eps))
        return x, new_kv

    if fam in ("dense", "vlm", "audio", "moe") and not cfg.local_global_pattern:
        kc, vc = caches["kv"]

        def body(x, inputs):
            lp, kb, vb = inputs
            if fam == "moe":
                h, new_kv = attention_block(
                    lp["attn"], rms_norm(x, lp["ln1"], cfg.norm_eps), cfg,
                    positions=positions, kv_cache=(kb, vb),
                    cache_length=position, impl=impl,
                )
                x = x + h
                m, _ = moe_block(lp["moe"], rms_norm(x, lp["ln2"], cfg.norm_eps), cfg)
                x = x + m
            else:
                x, new_kv = dense_decode(x, lp, (kb, vb))
            return x, new_kv

        x, new_kv = lax.scan(body, x, (params["layers"], kc, vc))
        new_caches["kv"] = new_kv
    elif fam == "dense" and cfg.local_global_pattern:
        lkc, lvc = caches["local_kv"]
        gkc, gvc = caches["global_kv"]

        def local_body(x, inputs):
            lp, kb, vb = inputs
            return dense_decode(x, lp, (kb, vb), window=cfg.sliding_window)

        def super_body(x, inputs):
            lp_local, lkb, lvb, lp_global, gkb, gvb = inputs
            x, new_local = lax.scan(local_body, x, (lp_local, lkb, lvb))
            x, new_global = dense_decode(x, lp_global, (gkb, gvb))
            return x, (new_local, new_global)

        x, (new_local, new_global) = lax.scan(
            super_body,
            x,
            (params["local_layers"], lkc, lvc, params["global_layers"], gkc, gvc),
        )
        new_caches["local_kv"] = new_local
        new_caches["global_kv"] = new_global
        if "tail_layers" in params:
            tkc, tvc = caches["tail_kv"]
            x, new_tail = lax.scan(
                local_body, x, (params["tail_layers"], tkc, tvc)
            )
            new_caches["tail_kv"] = new_tail
    elif fam == "ssm":
        def body(x, inputs):
            lp, st, tml, cml = inputs
            h, (new_st, new_tml) = rwkv6_time_mix(
                lp, rms_norm(x, lp["ln1"], cfg.norm_eps), cfg,
                state=st, last_x=tml, decode=True,
            )
            x = x + h
            h, new_cml = rwkv6_channel_mix(
                lp, rms_norm(x, lp["ln2"], cfg.norm_eps), last_x=cml
            )
            return x + h, (new_st, new_tml, new_cml)

        x, (new_wkv, new_tm, new_cm) = lax.scan(
            body, x, (params["layers"], caches["wkv"], caches["tm_last"], caches["cm_last"])
        )
        new_caches.update({"wkv": new_wkv, "tm_last": new_tm, "cm_last": new_cm})
    elif fam == "hybrid":
        akc, avc = caches["attn_kv"]

        def mamba_body(x, inputs):
            lp, st, cv = inputs
            h, (new_st, new_cv) = mamba2_block(
                lp["mamba"], rms_norm(x, lp["ln"], cfg.norm_eps), cfg,
                state=st, conv_cache=cv, decode=True,
            )
            return x + h, (new_st, new_cv)

        def super_body(x, inputs):
            lp, st, cv, kb, vb = inputs
            x, (new_st, new_cv) = lax.scan(mamba_body, x, (lp, st, cv))
            x, new_kv = dense_decode(x, params["shared_attn"], (kb, vb))
            return x, (new_st, new_cv, new_kv)

        x, (new_ssm, new_conv, new_akv) = lax.scan(
            super_body,
            x,
            (params["mamba_layers"], caches["ssm"], caches["conv"], akc, avc),
        )
        new_caches.update({"ssm": new_ssm, "conv": new_conv, "attn_kv": new_akv})
        if "tail_layers" in params:
            x, (new_tst, new_tcv) = lax.scan(
                mamba_body, x, (params["tail_layers"], caches["tail_ssm"], caches["tail_conv"])
            )
            new_caches["tail_ssm"] = new_tst
            new_caches["tail_conv"] = new_tcv
    else:
        raise ValueError(fam)

    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, h, cfg)
    return logits, new_caches
