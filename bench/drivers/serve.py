"""Serve Works through the orchestrator, closed or open loop.

The traffic file sets the loop (``"loop": "closed"`` with ``"clients"``
Works in flight, or ``"loop": "open"`` with Poisson arrivals at
``"rate_per_s"``), the Work sizes and the engine's shape.  Every seed gets
the same multiset of sizes and gaps, drawn at the midpoints of equal-
probability strata, in its own order, with its own prompt tokens.

Path timed: ``LocalClient.submit`` of a serve Work -> agents -> runtime
worker -> ``execute_serve_payload`` -> the ``EngineHub`` engine (prefill
scan, cached decode, greedy sampling) -> the Finisher's results ->
``collect_serve_results``.  The engine is built in set-up under the
payload's exact key, with the benchmark's own weights, and its
``generate`` is wrapped to record a span.
"""
from __future__ import annotations

import concurrent.futures as cf
import math
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from bench import common
from bench.check import served_gaps

HUB_SEED = 0  # the payload's weight seed; the weights themselves come from --seed
SITE = "tpu0"


@dataclass
class WorkSpec:
    index: int
    prompts: list[list[int]]
    max_new_tokens: int
    due: float = 0.0  # seconds after the window's start (open loop)


@dataclass
class Done:
    spec: WorkSpec
    sent: float
    submitted: float
    finished: float
    status: str
    tokens: list[list[int]] | None = None
    error: str | None = None


@dataclass
class State:
    cell: Any
    seed: int
    ctx: Any
    engine: Any
    orch: Any
    client: Any
    runtime: Any
    traffic: dict
    config: dict
    done: list[Done] = field(default_factory=list)
    stats0: dict = field(default_factory=dict)
    stats1: dict = field(default_factory=dict)
    rt0: dict = field(default_factory=dict)
    rt1: dict = field(default_factory=dict)


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
def _tokens(rng, n: int, vocab: int) -> list[int]:
    return rng.integers(1, vocab, size=n).tolist()


def closed_work(t: dict, seed: int, index: int, vocab: int) -> WorkSpec:
    """Work ``index`` of a closed loop: ``prompts_per_work`` prompts whose
    lengths are the same strata in every Work, shuffled in an order that the
    Work's index fixes and the seed does not (the engine prefills them in
    groups padded to each group's longest, so an order from the seed changed
    the padded prefill work, and the rate, by up to 10% from seed to seed);
    the seed draws the tokens, and the output length cycles through
    ``max_new_tokens`` in a seeded order per block."""
    rng = common.np_rng(seed, index)
    lengths = common.lognormal_quantiles(
        t["prompts_per_work"], t["prompt_median"], t["prompt_sigma"],
        t["prompt_min"], t["prompt_max"])
    lengths = common.np_rng(0, index).permutation(lengths)
    cycle = t["max_new_tokens"]
    block = common.np_rng(seed, 10**6 + index // len(cycle)).permutation(cycle)
    return WorkSpec(index, [_tokens(rng, int(n), vocab) for n in lengths],
                    int(block[index % len(cycle)]))


def open_schedule(t: dict, seed: int, seconds: float, vocab: int) -> list[WorkSpec]:
    """Every Work due in the window: round(rate * seconds) Works, Poisson
    gaps, 1-4 prompts and 16-64 new tokens each (uniform strata)."""
    n = max(1, round(t["rate_per_s"] * seconds))
    rng = common.np_rng(seed, 0)
    gaps = rng.permutation(common.exponential_quantiles(n, 1.0 / t["rate_per_s"]))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    counts = rng.permutation(common.uniform_grid(n, t["prompts_min"], t["prompts_max"]))
    outs = rng.permutation(common.uniform_grid(n, t["new_tokens_min"], t["new_tokens_max"]))
    lengths = rng.permutation(common.lognormal_quantiles(
        int(sum(counts)), t["prompt_median"], t["prompt_sigma"], t["prompt_min"],
        t["prompt_max"])).tolist()
    works = []
    for i in range(n):
        own = [lengths.pop() for _ in range(int(counts[i]))]
        works.append(WorkSpec(i, [_tokens(rng, int(m), vocab) for m in own],
                              int(outs[i]), float(due[i])))
    return works


def buckets(t: dict, bucket_min: int = 8) -> list[int]:
    """Every prefill bucket a group of the mix's prompts can reach."""
    out, b = [], bucket_min
    while b < t["prompt_min"]:
        b <<= 1
    while True:
        out.append(b)
        if b >= t["prompt_max"]:
            return out
        b <<= 1


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def setup(cell, seed: int, ctx) -> State:
    import jax

    from repro.api import LocalClient
    from repro.models.io import params_nbytes
    from repro.orchestrator import Orchestrator
    from repro.runtime.executor import WorkloadRuntime
    from repro.serve.workload import HUB, publish_weights

    c, t, ref = cell.config, cell.traffic, cell.reference
    cfg = ref.program_config(c)
    weights = jax.jit(lambda k: ref.make_weights(c, k))(common.jax_key(seed))
    params = ref.to_program(weights)
    # the hub has no public way to take weights: place them under the key
    # the payload's (arch, smoke, seed) names, before anything loads it
    HUB._models[(c["arch"], False, HUB_SEED)] = (cfg, params, params_nbytes(params))
    e = t["engine"]
    engine = HUB.engine(c["arch"], smoke=False, seed=HUB_SEED, n_slots=e["n_slots"],
                        prefill_batch=e["prefill_batch"], max_seq=e["max_seq"])
    generate = engine.generate

    def traced_generate(prompts, **kw):
        with ctx.spans.span("generate", tag=tuple(prompts[0][:8])):
            return generate(prompts, **kw)

    engine.generate = traced_generate
    # one generate per prefill bucket of the mix; each also runs a decode step
    hi = t["prompt_max"]
    for i, b in enumerate(buckets(t)):
        engine.generate([[1 + i] * min(b, hi)], max_new_tokens=2, rids=[0])

    runtime = WorkloadRuntime(sites={SITE: t["site_slots"]}, workers=t["runtime_workers"])
    orch = Orchestrator(runtime=runtime, poll_period_s=t["poll_period_s"]).start()
    client = LocalClient(orch)
    publish_weights(runtime.broker.catalog, c["arch"], [SITE], smoke=False, seed=HUB_SEED)
    state = State(cell, seed, ctx, engine, orch, client, runtime, t, c)
    # the scheduling plane's own first pass (no device programs are new)
    warm = WorkSpec(-1, [[2] * min(8, hi)], 2)
    _send(state, warm, time.perf_counter())
    state.done.clear()
    ctx.spans.spans.clear()
    return state


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
def _work(state: State, spec: WorkSpec):
    from repro.serve.workload import serve_work

    e = state.traffic["engine"]
    return serve_work(
        state.config["arch"], spec.prompts, n_shards=state.traffic["n_shards"],
        max_new_tokens=spec.max_new_tokens, name=f"w{spec.index}", smoke=False,
        seed=HUB_SEED, n_slots=e["n_slots"], prefill_batch=e["prefill_batch"],
        max_seq=e["max_seq"],
    )


def _send(state: State, spec: WorkSpec, sent: float, timeout: float = 600.0) -> Done:
    """Submit one Work and wait until the client sees it terminal."""
    from repro.serve.workload import collect_serve_results

    client, spans = state.client, state.ctx.spans
    work = _work(state, spec)
    tag = tuple(spec.prompts[0][:8])
    with spans.span("submit", tag=tag):
        rid = client.submit(work)
    submitted = time.perf_counter()
    with spans.span("wait", tag=tag):
        status = client.wait(rid, timeout=timeout, interval=state.traffic["client_poll_s"])
    finished = time.perf_counter()
    done = Done(spec, sent, submitted, finished, status)
    if status == "Finished":
        try:
            _, results = client.work_status(rid, work.name)
            done.tokens = collect_serve_results(results, len(spec.prompts))
        except Exception as exc:  # noqa: BLE001 - a wrong answer, reported as failed
            done.status, done.error = "Malformed", f"{type(exc).__name__}: {exc}"
    state.done.append(done)
    return done


def _closed(state: State, seconds: float, t0: float) -> None:
    t, vocab = state.traffic, state.config["vocab_size"]
    lock = threading.Lock()
    counter = iter(range(10**9))

    def client_loop():
        while time.perf_counter() - t0 < seconds:
            with lock:
                index = next(counter)
            _send(state, closed_work(t, state.seed, index, vocab), time.perf_counter())

    with cf.ThreadPoolExecutor(max_workers=t["clients"],
                               thread_name_prefix="bench-client") as pool:
        for f in [pool.submit(client_loop) for _ in range(t["clients"])]:
            f.result()


def _open(state: State, seconds: float, t0: float) -> None:
    works = open_schedule(state.traffic, state.seed, seconds, state.config["vocab_size"])
    late = []
    with cf.ThreadPoolExecutor(max_workers=state.traffic["max_waiters"],
                               thread_name_prefix="bench-waiter") as pool:
        futures = []
        for spec in works:
            delay = t0 + spec.due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sent = t0 + spec.due
            late.append(time.perf_counter() - sent)
            futures.append(pool.submit(_send, state, spec, sent))
        for f in futures:
            f.result()
    late.sort()
    print(f"[generator] {len(works)} Works sent; lateness median "
          f"{common.median(late) * 1e3:.3f} ms, max {late[-1] * 1e3:.3f} ms",
          file=sys.stderr)


def measure(state: State, seconds: float, ctx) -> dict:
    t = state.traffic
    state.stats0 = dict(state.engine.stats)
    state.rt0 = dict(state.runtime.stats)
    t0 = ctx.start_window(seconds)
    setup_s = t0 - ctx.process_start
    (_closed if t["loop"] == "closed" else _open)(state, seconds, t0)
    t_end = max(d.finished for d in state.done)
    ctx.end_window()
    state.stats1 = dict(state.engine.stats)
    state.rt1 = dict(state.runtime.stats)
    ok = [d for d in state.done if d.tokens is not None]
    failed = len(state.done) - len(ok)
    out_tokens = sum(len(tk) for d in ok for tk in d.tokens)
    latencies = [d.finished - d.sent if d.tokens is not None else math.inf for d in state.done]
    rt = {k: state.rt1[k] - state.rt0.get(k, 0) for k in state.rt1}
    print(f"[serve] Works {len(state.done)} failed {failed}; output tokens {out_tokens}; "
          f"window {t_end - t0:.3f} s; runtime {rt}", file=sys.stderr)
    for d in state.done:
        if d.error:
            print(f"[serve] Work {d.spec.index}: {d.status} {d.error}", file=sys.stderr)
    e2e = {"setup_s": setup_s}
    if t["loop"] == "closed":
        e2e["serve_tokens_per_s"] = out_tokens / (t_end - t0)
    else:
        e2e["work_latency_p90_s"] = common.percentile(latencies, 90)
    return {
        "e2e": e2e, "attempted": len(state.done), "failed": failed,
        "window_s": t_end - t0, "t0": t0, "t_end": t_end, "seconds": seconds,
        "engine": {k: state.stats1[k] - state.stats0[k] for k in state.stats1},
        "done": state.done, "spans": state.ctx.spans, "config": state.config,
        "reference": state.cell.reference, "peaks": ctx.peaks, "latencies": latencies,
    }


def release(state: State) -> None:
    """Stop the orchestrator and free the engine's weights and caches (the
    compiled steps stay, for a next set-up in the same process)."""
    from repro.serve.workload import HUB

    state.orch.stop()
    HUB._models.clear()
    HUB._engines.clear()
    state.engine = None


# ---------------------------------------------------------------------------
# correct: served tokens against the plain reference
# ---------------------------------------------------------------------------
def sample_requests(state: State, k: int) -> list[tuple[list[int], list[int]]]:
    """``k`` (prompt, served tokens) pairs drawn from the seed, the longest
    among them."""
    done = sorted(state.done, key=lambda d: d.spec.index)  # not by finish order
    reqs = [(p, tk) for d in done if d.tokens is not None
            for p, tk in zip(d.spec.prompts, d.tokens)]
    if not reqs:
        return []
    longest = max(range(len(reqs)), key=lambda i: len(reqs[i][0]) + len(reqs[i][1]))
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = common.np_rng(state.seed, 7)
    pick = [longest] + rng.permutation(rest)[: k - 1].tolist()
    return [reqs[i] for i in pick]


def check(state: State) -> dict:
    t = state.traffic
    wrong = sum(1 for d in state.done if d.tokens is not None
                for tk in d.tokens if len(tk) != d.spec.max_new_tokens)
    sample = sample_requests(state, t["check_requests"])
    gaps = served_gaps(state.cell.reference, state.config, common.jax_key(state.seed),
                       sample, t["engine"]["max_seq"])
    limits = state.cell.limits
    return {
        "served_gap": {"value": gaps["served"], "limit": limits["served_gap"]},
        "wrong_length": {"value": float(wrong), "limit": 0.0},
    }
