"""The comparisons that decide ``correct``, and the reference runs they need.

Serving: every served token of a sample of finished requests is scored by
the plain reference run once over the prompt and its served tokens; the
number compared is the widest gap by which a served token's reference
logit lies below the reference's best logit at that position.

Training: the reference follows the program's first three steps from the
same weights and batches (AdamW with global-norm clipping, as the
configuration states) and gives the loss of each step, each leaf's norm of
the first gradient and each leaf's norm of the parameters' change after
three steps.  A leaf's gap is |program - reference| over the larger of the
reference leaf's norm and the median leaf's.

Everything runs at float32 and highest matmul precision, unless the
``precision`` argument asks for the fp8 control.
"""
from __future__ import annotations

import math
import statistics
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def served_gaps(ref, c: dict, key, sample: list[tuple[list[int], list[int]]], pad_to: int,
                control: str | None = None) -> dict[str, float]:
    """{"served": widest gap of the served tokens, "control": widest gap of
    the tokens the ``control`` precision puts first (when given)}."""
    w = jax.jit(lambda k: ref.make_weights(c, k))(key)
    with jax.default_matmul_precision("highest"):
        f32 = jax.jit(lambda w, t: ref.logits(c, w, t, "f32"))
        low = jax.jit(lambda w, t: ref.logits(c, w, t, control)) if control else None
        served = ctl = 0.0
        for prompt, tokens in sample:
            seq = np.zeros((pad_to,), np.int32)
            full = list(prompt) + list(tokens[:-1])
            seq[: len(full)] = full
            pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(tokens))
            lg = np.asarray(f32(w, seq))[pos]
            best = lg.max(axis=-1)
            served = worst([served, float((best - lg[np.arange(len(pos)), tokens]).max())])
            if low is not None:
                first = np.asarray(low(w, seq))[pos].argmax(axis=-1)
                ctl = worst([ctl, float((best - lg[np.arange(len(pos)), first]).max())])
    out = {"served": served}
    if control:
        out["control"] = ctl
    return out


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------
def lr_schedule(opt: dict) -> Callable[[int], float]:
    """Linear warm-up to ``max_lr`` over ``warmup_steps``, then cosine to
    ``min_ratio`` of it at ``total_steps``."""
    def lr(step: int) -> float:
        w, total = opt["warmup_steps"], opt["total_steps"]
        if step < w:
            return opt["max_lr"] * step / w
        p = min(1.0, max(0.0, (step - w) / max(1, total - w)))
        return opt["max_lr"] * (opt["min_ratio"] + (1 - opt["min_ratio"]) * 0.5 * (1 + math.cos(math.pi * p)))
    return lr


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


def state_shardings(shapes: dict, devices) -> Any:
    """Each leaf split over the devices along its largest divisible axis."""
    if len(devices) == 1:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("x",))
    n = len(devices)

    def one(shape):
        axes = [i for i in np.argsort(shape)[::-1] if shape[i] % n == 0]
        spec = [None] * len(shape)
        if axes:
            spec[axes[0]] = "x"
        return NamedSharding(mesh, P(*spec))

    return {k: one(s) for k, s in shapes.items()}


def reference_train(ref, c: dict, key, batches: list[dict], opt: dict, devices,
                    precision: str = "f32", fault: str | None = None) -> dict:
    """The reference's first ``len(batches)`` steps: losses, the first
    gradient's global norm and leaf norms (before and after clipping), and
    each leaf's change after the last step.

    ``fault`` plants one in the reference put in the program's place:
    ``"half_batch"`` (the mean over the first half of the rows only) or
    ``"no_exchange"`` (tensor-parallel partial sums never reduced)."""
    shapes = ref.weight_shapes(c)
    sh = state_shardings(shapes, devices)
    b1, b2, eps, wd, clip = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"], opt["clip_norm"]
    lr = lr_schedule(opt)
    kw = {}
    if fault == "half_batch":
        kw["rows"] = np.arange(batches[0]["tokens"].shape[0] // 2)
    elif fault == "no_exchange":
        kw["exchange"] = "local"
        kw["shards"] = len(devices) if len(devices) > 1 else 4

    def init(k):
        w = {n: v.astype(jnp.float32) for n, v in ref.make_weights(c, k).items()}
        zeros = {n: jnp.zeros_like(v) for n, v in w.items()}
        return {"w": w, "m": zeros, "v": dict(zeros)}

    def step(state, tokens, labels, t, lr_t):
        loss, g = jax.value_and_grad(lambda w: ref.loss(c, w, tokens, labels, precision, **kw))(state["w"])
        gn = leaf_norms(g)
        gnorm = jnp.sqrt(sum(jnp.square(x) for x in gn.values()))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        new = {"w": {}, "m": {}, "v": {}}
        for n in g:
            gc = g[n] * scale
            m = b1 * state["m"][n] + (1 - b1) * gc
            v = b2 * state["v"][n] + (1 - b2) * jnp.square(gc)
            mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
            w = state["w"][n]
            new["w"][n] = w - lr_t * (mh / (jnp.sqrt(vh) + eps) + wd * w)
            new["m"][n], new["v"][n] = m, v
        return new, loss, gn, gnorm

    state_sh = None if sh is None else {"w": sh, "m": sh, "v": sh}
    with jax.default_matmul_precision("highest"):
        state = jax.jit(init, out_shardings=state_sh)(key)
        jstep = jax.jit(step, donate_argnums=(0,), out_shardings=(state_sh, None, None, None))
        losses, first = [], None
        for i, b in enumerate(batches, start=1):
            state, loss, gn, gnorm = jstep(state, b["tokens"], b["labels"],
                                           jnp.float32(i), jnp.float32(lr(i)))
            losses.append(float(loss))
            if first is None:
                gnorm = float(gnorm)
                raw = {k: float(v) for k, v in gn.items()}
                s = min(1.0, clip / max(gnorm, 1e-9))
                first = {"gnorm": gnorm, "raw": raw, "clipped": {k: v * s for k, v in raw.items()}}
        change = jax.jit(lambda s, k: weight_change(ref, c, s["w"], k))(state, key)
    return {"losses": losses, "grad": first,
            "change": {k: float(v) for k, v in change.items()}}


def weight_change(ref, c: dict, w: dict, key) -> dict:
    """Each leaf's norm of ``w`` less the weights the seed made."""
    w0 = ref.make_weights(c, key)
    return leaf_norms({k: w[k].astype(jnp.float32) - w0[k].astype(jnp.float32) for k in w})


def worst(gaps) -> float:
    """The largest gap; a non-finite one (a NaN norm or loss) is infinite,
    where Python's ``max`` would pass over a NaN."""
    gaps = list(gaps)
    return math.inf if not all(math.isfinite(g) for g in gaps) else max(gaps)


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf: |program norm - reference norm| over the larger of the
    reference leaf's norm and the median leaf's."""
    keys = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in keys)
    return worst(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def moving_leaves(grad_raw: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's."""
    med = statistics.median(grad_raw.values())
    return {k for k, v in grad_raw.items() if v >= 1e-3 * med}


def train_gaps(prog: dict, ref: dict) -> dict[str, float]:
    loss = worst(abs(p - r) / abs(r) for p, r in zip(prog["losses"], ref["losses"]))
    keep = moving_leaves(ref["grad"]["raw"])
    return {
        "loss_gap": loss,
        "grad_gap": leaf_gap(prog["grad"]["clipped"], ref["grad"]["clipped"]),
        "grad_raw_gap": leaf_gap(prog["grad"]["raw"], ref["grad"]["raw"]),
        "update_gap": leaf_gap(prog["change"], ref["change"], keep),
    }
