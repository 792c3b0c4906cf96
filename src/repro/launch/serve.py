"""Serving launcher: continuous-batching offline inference from the CLI.

Drives :class:`repro.serve.OfflineEngine` — the same engine the
orchestrator's ``serve`` payload uses — over randomly drawn prompts of
mixed length, and prints throughput plus engine counters.

``python -m repro.launch.serve --arch smollm-360m --smoke --tokens 32``
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.configs import get_config, smoke_config


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8, help="number of prompts")
    ap.add_argument("--context", type=int, default=16, help="max prompt length")
    ap.add_argument("--tokens", type=int, default=32, help="max new tokens each")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prefill-batch", type=int, default=2)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--eos", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from repro.models.lm import init_params_and_specs
    from repro.serve import OfflineEngine

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params, _ = init_params_and_specs(jax.random.PRNGKey(0), cfg)
    engine = OfflineEngine(
        cfg,
        params,
        n_slots=args.slots,
        prefill_batch=args.prefill_batch,
        max_seq=args.context + args.tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        eos_id=args.eos,
        seed=args.seed,
    )
    # mixed-length prompts exercise the batcher's pow2 length buckets
    rng = np.random.default_rng(args.seed)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=int(n)).tolist()
        for n in rng.integers(1, args.context + 1, size=args.requests)
    ]

    t0 = time.perf_counter()
    results = engine.generate(prompts, max_new_tokens=args.tokens)
    wall = time.perf_counter() - t0
    gen = sum(len(r.tokens) for r in results)
    print(
        json.dumps(
            {
                "arch": cfg.name,
                "requests": args.requests,
                "generated": [r.tokens[:8] for r in results[:4]],
                "finish_reasons": sorted({r.finish_reason for r in results}),
                "wall_s": round(wall, 3),
                "tokens_per_s": round(gen / wall, 1),
                "samples_per_s": round(args.requests / wall, 2),
                "slot_occupancy": round(engine.occupancy(), 3),
                "stats": {k: round(v, 4) for k, v in engine.stats.items()},
            },
            indent=1,
        )
    )


if __name__ == "__main__":
    main()
