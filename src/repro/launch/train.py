"""Training launcher: one process over every local device.

``python -m repro.launch.train --arch smollm-360m --smoke --steps 100``.
With one device the step is a plain jit.  With several, ``Trainer`` lays
a 1×N (data, model) mesh over all of them (``launch.mesh.make_host_mesh``),
builds the state in place with ``FSDP_RULES`` shardings, and jits the
step with matching in and out shardings.  Multi-host pods are not wired.
"""
from __future__ import annotations

import argparse
import json

from repro.configs import get_config, smoke_config
from repro.launch.cache import enable_compile_cache
from repro.train.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    trainer = Trainer(
        cfg,
        batch_size=args.batch,
        seq_len=args.seq,
        ckpt_dir=args.ckpt_dir,
        total_steps=args.steps,
    )
    if args.resume and trainer.resume():
        print(f"resumed from step {trainer.step}")
    out = trainer.run(args.steps, log_every=args.log_every)
    out["mesh"] = dict(trainer.mesh.shape) if trainer.mesh is not None else None
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
