"""Continuous-batching offline inference engine (MLPerf-offline style).

The engine drives the existing model step functions (``make_decode_step``
over every family: transformer, RWKV, SSM, hybrid) through two pieces:

* :class:`SlotBatcher` — a per-model request queue that packs
  variable-length prompts into fixed-width prefill batches (lengths padded
  to power-of-two buckets to bound recompiles) and owns slot assignment
  for the decode loop;
* :class:`OfflineEngine` — a fixed pool of ``n_slots`` decode slots over
  one shared cache tree.  Finished sequences (EOS / token budget) are
  evicted and their slots refilled from the queue *mid-decode*, so the
  batch never drains to finish a stragglers' tail.  Cache buffers are
  donated between steps (``donate_argnums``), so decode runs in-place.

Per-slot stepping is a ``vmap`` of a batch-1 decode over the cache tree's
batch axis (located per-leaf via ``cache_logical_specs`` — KV caches,
RWKV wkv state, and Mamba conv state all put "batch" at different ranks).
Inside the vmapped cell the singleton batch axis is re-inserted so
``forward_decode``'s internal axis arithmetic is untouched; inactive
slots keep their caches frozen via a ``where`` on the active mask.

Prefill is the same decode cell scanned over the prompt positions — exact
for recurrent state (which a padded full-forward would corrupt) and
identical numerics to the decode path, with per-row length masking so one
padded batch serves mixed prompt lengths.

Sampling is seeded per (request id, cache position) — see
``repro.serve.sampling`` — so outputs are independent of batching,
slot placement, and shard relocation.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.common.exceptions import ValidationError
from repro.models.config import ArchConfig
from repro.models.lm import cache_logical_specs, map_specs, zero_caches
from repro.serve.sampling import request_key, sample_tokens
from repro.serve.step import make_decode_step


def cache_batch_axes(cfg: ArchConfig) -> Any:
    """Per-leaf index of the "batch" axis in the decode-cache tree."""
    return map_specs(cache_logical_specs(cfg), lambda ax: ax.index("batch"))


@dataclass
class GenRequest:
    rid: int
    prompt: list[int]
    max_new_tokens: int


@dataclass
class GenResult:
    rid: int
    prompt: list[int]
    tokens: list[int]
    finish_reason: str  # "eos" | "length"


@dataclass
class _Slot:
    req: GenRequest | None = None
    generated: list[int] = field(default_factory=list)
    served: int = 0  # how many requests this slot has hosted (refill count)


class SlotBatcher:
    """Per-model request queue + slot bookkeeping for continuous batching.

    ``pack()`` pops up to ``prefill_batch`` queued requests, assigns them
    to free slots, and lays their prompts out as one padded [P, L] batch
    (L = power-of-two bucket of the longest prompt in the group, so the
    prefill step compiles once per bucket, not once per length mix).
    """

    def __init__(self, n_slots: int, prefill_batch: int, *, bucket_min: int = 8):
        if n_slots < 1 or prefill_batch < 1:
            raise ValidationError("n_slots and prefill_batch must be >= 1")
        self.n_slots = n_slots
        self.prefill_batch = prefill_batch
        self.bucket_min = bucket_min
        self.pending: deque[GenRequest] = deque()
        self.slots = [_Slot() for _ in range(n_slots)]
        self.refills = 0

    def add(self, req: GenRequest) -> None:
        self.pending.append(req)

    def free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.req is None]

    def active_slots(self) -> list[int]:
        return [i for i, s in enumerate(self.slots) if s.req is not None]

    def drained(self) -> bool:
        return not self.pending and not self.active_slots()

    def bucket(self, n: int) -> int:
        b = self.bucket_min
        while b < n:
            b <<= 1
        return b

    def pack(
        self,
    ) -> tuple[list[int], np.ndarray, np.ndarray, np.ndarray] | None:
        """Assign queued requests to free slots; returns (slot assignment
        per row, tokens [P, L], lengths [P], rids [P]) or None when there
        is nothing to pack.  Rows beyond the assignment count are padding
        (length 0) and must not be inserted by the caller."""
        free = self.free_slots()
        k = min(len(free), self.prefill_batch, len(self.pending))
        if k == 0:
            return None
        assigns: list[int] = []
        reqs: list[GenRequest] = []
        for slot in free[:k]:
            req = self.pending.popleft()
            served = self.slots[slot].served
            if served:
                self.refills += 1
            self.slots[slot] = _Slot(req=req, served=served + 1)
            assigns.append(slot)
            reqs.append(req)
        p = self.prefill_batch
        length = self.bucket(max(len(r.prompt) for r in reqs))
        tokens = np.zeros((p, length), np.int32)
        lengths = np.zeros((p,), np.int32)
        rids = np.zeros((p,), np.int32)
        for j, r in enumerate(reqs):
            tokens[j, : len(r.prompt)] = r.prompt
            lengths[j] = len(r.prompt)
            rids[j] = r.rid
        return assigns, tokens, lengths, rids

    def record(self, slot: int, token: int) -> None:
        self.slots[slot].generated.append(int(token))

    def evict(self, slot: int, reason: str) -> GenResult:
        s = self.slots[slot]
        assert s.req is not None, f"evicting empty slot {slot}"
        self.slots[slot] = _Slot(served=s.served)
        return GenResult(
            rid=s.req.rid,
            prompt=list(s.req.prompt),
            tokens=list(s.generated),
            finish_reason=reason,
        )


# Compiled step cache: tests, the sim scenario, the example, and the
# orchestrator workload all share compilations for identical
# (cfg, shape, sampling) keys — ArchConfig is frozen/hashable by design.
_COMPILE_CACHE: dict[tuple, Callable] = {}


def _cached(key: tuple, builder: Callable[[], Callable]) -> Callable:
    fn = _COMPILE_CACHE.get(key)
    if fn is None:
        fn = _COMPILE_CACHE[key] = builder()
    return fn


class OfflineEngine:
    """Offline (throughput-mode) inference over a fixed slot pool."""

    def __init__(
        self,
        cfg: ArchConfig,
        params: Any,
        *,
        n_slots: int = 4,
        prefill_batch: int = 2,
        max_seq: int = 64,
        temperature: float = 0.0,
        top_k: int = 0,
        eos_id: int | None = None,
        seed: int = 0,
        bucket_min: int = 8,
    ):
        if cfg.frontend == "audio_stub":
            raise ValidationError(
                "OfflineEngine serves token prompts; the audio frontend "
                "consumes frame embeddings"
            )
        self.cfg = cfg
        self.params = params
        self.n_slots = int(n_slots)
        self.prefill_batch = int(prefill_batch)
        self.max_seq = int(max_seq)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.eos_id = eos_id
        self.bucket_min = int(bucket_min)
        self._base_key = jax.random.PRNGKey(seed)
        self._baxes = cache_batch_axes(cfg)
        # one lock per engine: the engine IS the per-model serving queue —
        # concurrent runtime workers serialize here, FIFO via the batcher
        self._lock = threading.Lock()
        self._decode = _cached(
            ("decode", cfg, self.n_slots, self.max_seq, self.temperature, self.top_k),
            self._build_decode,
        )
        self._insert = _cached(
            ("insert", cfg, self.n_slots, self.prefill_batch, self.max_seq),
            self._build_insert,
        )
        self.stats: dict[str, float] = {
            "requests": 0,
            "generated_tokens": 0,
            "prefill_calls": 0,
            "prefill_tokens": 0,
            "padded_prefill_tokens": 0,
            "decode_steps": 0,
            "decode_slot_steps": 0,
            "decode_active_steps": 0,
            "evictions": 0,
            "refills": 0,
            "prefill_s": 0.0,
            "decode_s": 0.0,
        }

    # -- compiled steps ------------------------------------------------------
    def _build_decode(self) -> Callable:
        cfg, baxes = self.cfg, self._baxes
        temperature, top_k = self.temperature, self.top_k
        step = make_decode_step(cfg)

        def one_slot(token, caches, position, rid, active, params, base_key):
            # re-insert the singleton batch axis vmap stripped, run the
            # stock batch-1 decode, then squeeze back to per-slot leaves
            batched = jax.tree.map(
                lambda c, ax: jnp.expand_dims(c, ax), caches, baxes
            )
            logits, new_b = step(
                params, {"token": token.reshape(1, 1)}, batched, position
            )
            new = jax.tree.map(lambda c, ax: jnp.squeeze(c, axis=ax), new_b, baxes)
            # inactive slots: caches frozen, token/position held
            new = jax.tree.map(
                lambda n, o: jnp.where(active, n, o), new, caches
            )
            rng = request_key(base_key, rid, position + 1)
            tok = sample_tokens(
                logits[0, -1, : cfg.vocab_size],
                rng=rng,
                temperature=temperature,
                top_k=top_k,
            )
            tok = jnp.where(active, tok, token).astype(jnp.int32)
            return tok, new, jnp.where(active, position + 1, position)

        def decode_all(params, tokens, caches, positions, rids, active, base_key):
            return jax.vmap(
                one_slot,
                in_axes=(0, baxes, 0, 0, 0, None, None),
                out_axes=(0, baxes, 0),
            )(tokens, caches, positions, rids, active, params, base_key)

        return jax.jit(decode_all, donate_argnums=(2,))

    def _build_prefill(self, length: int) -> Callable:
        cfg, baxes, max_seq = self.cfg, self._baxes, self.max_seq
        temperature, top_k = self.temperature, self.top_k
        step = make_decode_step(cfg)

        def one_row(tokens, n, rid, params, base_key):
            # scan the decode cell over prompt positions: exact recurrent
            # state (padding never enters SSM/RWKV carries) and the same
            # numerics as decode; rows shorter than the bucket mask their
            # tail steps out
            caches = zero_caches(cfg, 1, max_seq)

            def body(carry, inp):
                caches, last = carry
                tok, pos = inp
                logits, new = step(
                    params, {"token": tok.reshape(1, 1)}, caches, pos
                )
                act = pos < n
                caches = jax.tree.map(
                    lambda nw, old: jnp.where(act, nw, old), new, caches
                )
                last = jnp.where(
                    pos == n - 1,
                    logits[0, -1, : cfg.vocab_size].astype(jnp.float32),
                    last,
                )
                return (caches, last), None

            init = (caches, jnp.zeros((cfg.vocab_size,), jnp.float32))
            (caches, last), _ = lax.scan(
                body, init, (tokens, jnp.arange(length, dtype=jnp.int32))
            )
            first = sample_tokens(
                last,
                rng=request_key(base_key, rid, n),
                temperature=temperature,
                top_k=top_k,
            )
            row = jax.tree.map(lambda c, ax: jnp.squeeze(c, axis=ax), caches, baxes)
            return first.astype(jnp.int32), row, n

        def prefill_all(params, tokens, lengths, rids, base_key):
            return jax.vmap(
                one_row, in_axes=(0, 0, 0, None, None), out_axes=(0, baxes, 0)
            )(tokens, lengths, rids, params, base_key)

        return jax.jit(prefill_all)

    def _prefill_fn(self, length: int) -> Callable:
        key = (
            "prefill", self.cfg, self.prefill_batch, self.max_seq,
            self.temperature, self.top_k, length,
        )
        return _cached(key, lambda: self._build_prefill(length))

    def _build_insert(self) -> Callable:
        baxes = self._baxes

        def insert(caches, rows, row_idx, slot):
            def one(big, stacked, ax):
                row = lax.dynamic_index_in_dim(
                    stacked, row_idx, axis=ax, keepdims=False
                )
                return lax.dynamic_update_index_in_dim(
                    big, row.astype(big.dtype), slot, axis=ax
                )

            return jax.tree.map(one, caches, rows, baxes)

        return jax.jit(insert, donate_argnums=(0,))

    # -- serving -------------------------------------------------------------
    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        *,
        max_new_tokens: int = 16,
        rids: Sequence[int] | None = None,
    ) -> list[GenResult]:
        """Run every prompt to completion; results in input order.

        ``rids`` (default: positional indices) seed the per-request
        sampling streams — pass globally-unique ids when sharding one
        logical batch across engine calls so outputs stay
        placement-independent.
        """
        if rids is None:
            rids = range(len(prompts))
        reqs: list[GenRequest] = []
        for rid, prompt in zip(rids, prompts):
            prompt = [int(t) for t in prompt]
            if not prompt:
                raise ValidationError("empty prompt")
            if len(prompt) + max_new_tokens > self.max_seq:
                raise ValidationError(
                    f"prompt ({len(prompt)}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds max_seq={self.max_seq}"
                )
            reqs.append(
                GenRequest(rid=int(rid), prompt=prompt, max_new_tokens=int(max_new_tokens))
            )
        with self._lock:
            return self._run(reqs)

    def _run(self, reqs: list[GenRequest]) -> list[GenResult]:
        n = self.n_slots
        stats = self.stats
        batcher = SlotBatcher(n, self.prefill_batch, bucket_min=self.bucket_min)
        for r in reqs:
            batcher.add(r)
        stats["requests"] += len(reqs)
        caches = zero_caches(self.cfg, n, self.max_seq)
        tokens = np.zeros((n,), np.int32)
        positions = np.zeros((n,), np.int32)
        rid_arr = np.zeros((n,), np.int32)
        done: dict[int, GenResult] = {}

        def harvest(slot: int, token: int) -> None:
            batcher.record(slot, token)
            s = batcher.slots[slot]
            assert s.req is not None
            if self.eos_id is not None and token == self.eos_id:
                done[s.req.rid] = batcher.evict(slot, "eos")
                stats["evictions"] += 1
            elif len(s.generated) >= s.req.max_new_tokens:
                done[s.req.rid] = batcher.evict(slot, "length")
                stats["evictions"] += 1

        while not batcher.drained():
            packed = batcher.pack()
            if packed is not None:
                assigns, ptoks, plens, prids = packed
                t0 = time.perf_counter()
                first, rows, poss = self._prefill_fn(ptoks.shape[1])(
                    self.params,
                    jnp.asarray(ptoks),
                    jnp.asarray(plens),
                    jnp.asarray(prids),
                    self._base_key,
                )
                first = np.array(first)
                poss = np.array(poss)
                for j, slot in enumerate(assigns):
                    caches = self._insert(caches, rows, j, slot)
                    tokens[slot] = first[j]
                    positions[slot] = poss[j]
                    rid_arr[slot] = prids[j]
                stats["prefill_calls"] += 1
                stats["prefill_tokens"] += int(plens.sum())
                stats["padded_prefill_tokens"] += int(ptoks.size)
                stats["generated_tokens"] += len(assigns)
                stats["prefill_s"] += time.perf_counter() - t0
                for j, slot in enumerate(assigns):
                    harvest(slot, int(first[j]))
                continue  # fill every free slot before decoding again

            active = batcher.active_slots()
            if not active:
                break  # nothing left but padding rows
            mask = np.zeros((n,), bool)
            mask[active] = True
            t0 = time.perf_counter()
            toks_d, caches, poss_d = self._decode(
                self.params,
                jnp.asarray(tokens),
                caches,
                jnp.asarray(positions),
                jnp.asarray(rid_arr),
                jnp.asarray(mask),
                self._base_key,
            )
            tokens = np.array(toks_d)
            positions = np.array(poss_d)
            stats["decode_steps"] += 1
            stats["decode_slot_steps"] += n
            stats["decode_active_steps"] += len(active)
            stats["generated_tokens"] += len(active)
            stats["decode_s"] += time.perf_counter() - t0
            for slot in active:
                harvest(slot, int(tokens[slot]))

        stats["refills"] += batcher.refills
        missing = [r.rid for r in reqs if r.rid not in done]
        assert not missing, f"requests lost by the decode loop: {missing}"
        return [done[r.rid] for r in reqs]

    def occupancy(self) -> float:
        steps = self.stats["decode_slot_steps"]
        return self.stats["decode_active_steps"] / steps if steps else 0.0
