"""On-chip smoke test: the serve and train paths on one TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: sharded training only

One process drives the device; nothing here starts a child.  Each phase
prints one line (wall time, compile time, persistent-cache hits and
misses, and what it checked).  Any failed check raises, so the process
exits non-zero, and the last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
is printed only after every phase has passed.  Without a TPU it exits
non-zero before running anything.

One-chip phases, at smollm-360m's published width with random weights
drawn from ``SEED``:

* ``serve_orchestrated`` — 8 prompts of 8–128 tokens, 32 new tokens
  each, through ``Orchestrator`` + ``LocalClient`` as a 2-shard serve
  Work.  The request must end ``Finished`` with no failed or retried job
  and no weight bytes moved, and every prompt comes back exactly once.
* ``serve_engine_parity`` — the same prompts through the in-process
  ``EngineHub`` engine give exactly the orchestrated tokens (greedy).
* ``numerics`` — last-position logits of two prompts, bf16 on the TPU
  against a float32 reference on the host CPU at highest matmul precision.
* ``train`` — 5 steps of ``Trainer`` at batch 8 × 512 tokens; every loss
  finite.
* ``kernels`` — each Pallas kernel compiled for the chip (never in
  interpret mode) at a real model's width against its jnp reference.

Four-chip phases (rwkv6-1.6b, whose training state does not fit one
chip): 3 steps at full depth sharded over the four chips, then a 2-layer
cut trained for 3 steps from one seed on one chip and on four, with the
losses compared.

Timings are bring-up figures, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "smollm-360m"
SEED = 0
N_PROMPTS = 8
NEW_TOKENS = 32
MAX_SEQ = 256

# Relative L2 error of the TPU's bf16 last-position logits against the
# float32 host reference.  The same comparison run wholly on the CPU
# backend (bf16 forward vs float32 at highest precision, same weights and
# prompts) measures 1.11e-2; the limit is about 3x that, for the chip's
# own bf16 rounding order.
LOGITS_RTOL = 3.5e-2

# Relative L2 error of each kernel (bf16 inputs and outputs) against its
# float32 reference at highest matmul precision.  Interpret-mode runs of
# the same kernels at the same sizes on the CPU measure 1.6e-3 each (the
# bf16 rounding of the output); the limit leaves room for the MXU's
# handling of the kernels' f32 matmuls.
KERNEL_RTOL = 2e-2

# Loss of the same rwkv6 cut, same seed and batches, on one chip and on
# four: the sharded run reduces bf16 partial sums in another order.
# Relative limit: a few bf16 epsilons (2**-8).
SHARDED_LOSS_RTOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Phases:
    """Runs phases in order and prints one line each.

    Compile time is the sum of JAX's backend-compile durations inside the
    phase (a persistent-cache hit counts its load time)."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_: object) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _on_event(self, event: str, **_: object) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def run(self, name: str, fn) -> object:
        c0, h0, m0 = self.compile_s, self.hits, self.misses
        t0 = time.perf_counter()
        out = fn()
        wall = time.perf_counter() - t0
        print(
            f"[phase] {name}: ok wall_s={wall:.1f} compile_s={self.compile_s - c0:.1f} "
            f"cache_hits={self.hits - h0} cache_misses={self.misses - m0} | {out}",
            flush=True,
        )
        return out


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def make_prompts(vocab: int) -> list[list[int]]:
    import numpy as np

    rng = np.random.default_rng(SEED)
    lengths = rng.integers(8, 129, size=N_PROMPTS)
    lengths[:2] = (8, 128)  # both ends of the range
    return [rng.integers(1, vocab, size=int(n)).tolist() for n in lengths]


def serve_orchestrated(prompts: list[list[int]]) -> tuple[list[list[int]], dict]:
    """(tokens per prompt, runtime stats) of one orchestrated request."""
    from repro.api import LocalClient
    from repro.orchestrator import Orchestrator
    from repro.runtime.executor import WorkloadRuntime
    from repro.serve.workload import collect_serve_results, publish_weights, serve_work

    runtime = WorkloadRuntime(sites={"tpu0": 4}, workers=2)
    orch = Orchestrator(runtime=runtime, poll_period_s=0.05)
    orch.start()
    try:
        client = LocalClient(orch)
        publish_weights(runtime.broker.catalog, ARCH, ["tpu0"], smoke=False, seed=SEED)
        work = serve_work(
            ARCH, prompts, smoke=False, seed=SEED, n_shards=2,
            max_new_tokens=NEW_TOKENS, max_seq=MAX_SEQ,
        )
        rid = client.submit(work)
        status = client.wait(rid, timeout=1200)
        stats = dict(runtime.stats)
        require(status == "Finished", f"serve request ended {status!r}")
        require(
            stats["failed_jobs"] == 0 and stats["retried_jobs"] == 0,
            f"serve jobs failed or were retried: {stats}",
        )
        require(stats["bytes_moved"] == 0, f"weights moved: {stats['bytes_moved']} bytes")
        _, results = client.work_status(rid, work.name)
        tokens = collect_serve_results(results, len(prompts))
    finally:
        orch.stop()
    require(
        all(len(t) == NEW_TOKENS for t in tokens),
        f"token counts {[len(t) for t in tokens]}, expected {NEW_TOKENS} each",
    )
    return tokens, stats


def serve_engine_parity(prompts: list[list[int]], orchestrated: list[list[int]]) -> str:
    from repro.serve.workload import HUB

    engine = HUB.engine(ARCH, smoke=False, seed=SEED, max_seq=MAX_SEQ)
    direct = [r.tokens for r in engine.generate(prompts, max_new_tokens=NEW_TOKENS)]
    diff = [i for i, (a, b) in enumerate(zip(direct, orchestrated)) if a != b]
    require(not diff, f"engine and orchestrated tokens differ for prompts {diff}")
    return f"{len(prompts)} prompts x {NEW_TOKENS} tokens identical"


def last_logits(params, tokens, cfg):
    """Last-position logits of the model's forward, vocab padding cut."""
    from repro.models.lm import forward_prefill

    logits, _ = forward_prefill(params, {"tokens": tokens}, cfg)
    return logits[:, -1, : cfg.vocab_size].astype("float32")


def logits_error(params, cfg, tokens, device) -> tuple[float, float]:
    """(relative L2 error of ``cfg``'s logits on ``device`` against the
    float32 reference on the host CPU, max |reference|)."""
    import jax
    import numpy as np

    with jax.default_device(device):
        got = np.asarray(jax.jit(lambda p, t: last_logits(p, t, cfg))(params, tokens))
    cpu = jax.devices("cpu")[0]
    cfg32 = cfg.replace(dtype="float32")
    p32 = jax.device_put(jax.tree.map(lambda x: np.asarray(x, np.float32), params), cpu)
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(lambda p, t: last_logits(p, t, cfg32))(p32, tokens))
    require(np.all(np.isfinite(got)), "non-finite logits")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    return rel, float(np.abs(ref).max())


def numerics_tokens(vocab: int):
    import numpy as np

    return np.random.default_rng(SEED + 1).integers(1, vocab, (2, 64), dtype=np.int32)


def numerics() -> str:
    import jax

    from repro.configs import get_config
    from repro.serve.workload import HUB

    cfg = get_config(ARCH)
    _, params, _ = HUB.load_model(ARCH, smoke=False, seed=SEED)
    tokens = numerics_tokens(cfg.vocab_size)
    rel, scale = logits_error(params, cfg, tokens, jax.devices()[0])
    require(rel <= LOGITS_RTOL, f"logits rel err {rel:.3e} > {LOGITS_RTOL:.1e}")
    return f"logits rel_l2_err={rel:.3e} (limit {LOGITS_RTOL:.1e}, |ref|max={scale:.3g})"


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def train() -> str:
    import jax

    from repro.configs import get_config
    from repro.train.trainer import Trainer

    trainer = Trainer(get_config(ARCH), batch_size=8, seq_len=512, seed=SEED)
    trainer.run(5)
    losses = [h["loss"] for h in trainer.history]
    require(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    del trainer
    return (
        f"losses {[round(x, 4) for x in losses]} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}"
    )


def kernel_cases():
    """(name, kernel, reference, make_inputs) at real widths: the
    reference is the jnp spec the kernel must match."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.ref import ssd_ref, wkv6_ref
    from repro.kernels.rwkv6_wkv import wkv6_pallas
    from repro.kernels.ssd_scan import ssd_pallas
    from repro.models.layers import attention_chunked

    bf16 = jnp.bfloat16

    def attn_inputs(b, s, hq, hkv, d):
        def make(key):
            ks = jax.random.split(key, 3)
            return (
                jax.random.normal(ks[0], (b, s, hq, d), bf16),
                jax.random.normal(ks[1], (b, s, hkv, d), bf16),
                jax.random.normal(ks[2], (b, s, hkv, d), bf16),
            )
        return make

    def wkv_inputs(key, b=1, s=2048, h=32, k=64):
        ks = jax.random.split(key, 5)
        n = lambda i, shape, sc: (jax.random.normal(ks[i], shape) * sc)  # noqa: E731
        return (
            n(0, (b, s, h, k), 0.5).astype(bf16),
            n(1, (b, s, h, k), 0.5).astype(bf16),
            n(2, (b, s, h, k), 0.5).astype(bf16),
            (-jnp.exp(n(3, (b, s, h, k), 0.5))).astype(bf16),
            n(4, (h, k), 0.3),
        )

    def ssd_inputs(key, b=1, s=2048, h=64, p=64, n=64):
        ks = jax.random.split(key, 5)
        return (
            (jax.random.normal(ks[0], (b, s, h, p)) * 0.5).astype(bf16),
            jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))),
            -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3),
            (jax.random.normal(ks[3], (b, s, n)) * 0.5).astype(bf16),
            (jax.random.normal(ks[4], (b, s, n)) * 0.5).astype(bf16),
        )

    def f32(fn):
        return lambda *xs: fn(*(x.astype(jnp.float32) for x in xs))

    flash = functools.partial(flash_attention_pallas, causal=True)
    attn_ref = f32(functools.partial(attention_chunked, causal=True))
    return [
        ("flash_attention smollm-360m (B2 S2048 15/5 heads d64)",
         flash, attn_ref, attn_inputs(2, 2048, 15, 5, 64)),
        ("flash_attention qwen3-4b (B1 S2048 32/8 heads d128)",
         flash, attn_ref, attn_inputs(1, 2048, 32, 8, 128)),
        ("wkv6 rwkv6-1.6b (B1 S2048 H32 K=V64)",
         wkv6_pallas, f32(lambda *xs: wkv6_ref(*xs)[0]), wkv_inputs),
        ("ssd zamba2-1.2b (B1 S2048 H64 P64 N64)",
         ssd_pallas, f32(lambda *xs: ssd_ref(*xs)[0]), ssd_inputs),
    ]


def kernel_error(kernel, reference, make_inputs, key) -> float:
    import jax
    import numpy as np

    xs = make_inputs(key)
    got = np.asarray(jax.jit(kernel)(*xs), np.float32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(reference)(*xs), np.float32)
    require(np.all(np.isfinite(got)), "non-finite kernel output")
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def kernels() -> str:
    import jax

    errs = []
    for i, (name, kernel, reference, make_inputs) in enumerate(kernel_cases()):
        rel = kernel_error(kernel, reference, make_inputs, jax.random.PRNGKey(SEED + i))
        require(rel <= KERNEL_RTOL, f"{name}: rel err {rel:.3e} > {KERNEL_RTOL:.1e}")
        errs.append(f"{name.split(' (')[0]}={rel:.3e}")
    return f"rel_l2_err {', '.join(errs)} (limit {KERNEL_RTOL:.1e})"


def one_chip(phases: Phases) -> None:
    from repro.configs import get_config

    prompts = make_prompts(get_config(ARCH).vocab_size)
    served: dict[str, list[list[int]]] = {}

    def orchestrated() -> str:
        served["tokens"], stats = serve_orchestrated(prompts)
        counters = ("finished_jobs", "failed_jobs", "retried_jobs", "speculated_jobs",
                    "bytes_moved")
        return (
            f"Finished, {len(prompts)} prompts x {NEW_TOKENS} tokens; "
            + " ".join(f"{k}={stats[k]}" for k in counters)
        )

    phases.run("serve_orchestrated", orchestrated)
    phases.run("serve_engine_parity", lambda: serve_engine_parity(prompts, served["tokens"]))
    phases.run("numerics", numerics)
    phases.run("train", train)
    phases.run("kernels", kernels)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------
SHARDED_ARCH = "rwkv6-1.6b"


def sharded_losses(cfg, devices, steps: int = 3) -> list[float]:
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer

    trainer = Trainer(
        cfg, batch_size=4, seq_len=512, seed=SEED, mesh=make_host_mesh(devices)
    )
    trainer.run(steps)
    losses = [h["loss"] for h in trainer.history]
    require(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    return losses


def train_sharded_full() -> str:
    import jax

    from repro.configs import get_config

    losses = sharded_losses(get_config(SHARDED_ARCH), jax.devices())
    peaks = [peak_bytes(d) for d in jax.devices()]
    return f"losses {[round(x, 4) for x in losses]} peak_bytes_in_use per device {peaks}"


def train_sharded_compare() -> str:
    import jax

    from repro.configs import get_config

    cut = get_config(SHARDED_ARCH).replace(n_layers=2)
    one = sharded_losses(cut, jax.devices()[:1])
    four = sharded_losses(cut, jax.devices())
    rel = max(abs(a - b) / abs(a) for a, b in zip(one, four))
    require(rel <= SHARDED_LOSS_RTOL, f"1 vs 4 chip losses {one} {four}: rel {rel:.3e}")
    return (
        f"1 chip {[round(x, 5) for x in one]} 4 chips {[round(x, 5) for x in four]} "
        f"max_rel_diff={rel:.3e} (limit {SHARDED_LOSS_RTOL:.1e})"
    )


def four_chips(phases: Phases) -> None:
    import jax

    require(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, found {len(jax.devices())}")
    # full depth first, so each device's peak is this run's
    phases.run("train_sharded_full", train_sharded_full)
    phases.run("train_sharded_compare", train_sharded_compare)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    # the numerics reference runs on the host CPU beside the TPU
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's first device is {dev.platform}); nothing run",
              file=sys.stderr)
        return 1
    print(f"[device] {dev.device_kind} x{len(jax.devices())} compile cache {cache_dir}",
          flush=True)
    phases = Phases()
    if args.chips == 4:
        four_chips(phases)
    else:
        one_chip(phases)
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
