"""Trainer: the end-to-end driver binding data pipeline, train step,
checkpointing, and (optionally) the orchestrator.

One process drives every local device: with several devices the state
is sharded over a (data, model) mesh of all of them (``FSDP_RULES``),
with one device the step is a plain jit.  Fault tolerance:
async checkpoint every ``ckpt_every`` steps; ``Trainer.resume`` rebuilds
from the latest checkpoint (used by the restart tests and by the
orchestrator's retry path — a retried training Work resumes instead of
restarting from scratch).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.launch.mesh import make_host_mesh
from repro.models.config import ArchConfig
from repro.optim.schedule import cosine_with_warmup
from repro.parallel.sharding import FSDP_RULES
from repro.train.step import init_train_state, jit_train_step


def synthetic_batches(
    cfg: ArchConfig, *, batch_size: int, seq_len: int, seed: int = 0
) -> Iterator[dict[str, np.ndarray]]:
    """Deterministic LM batches with learnable structure (a noisy periodic
    token stream, so loss decreases measurably within tens of steps)."""
    rng = np.random.default_rng(seed)
    period = 17
    base = rng.integers(0, cfg.vocab_size, size=period)
    while True:
        noise = rng.random((batch_size, seq_len + 1)) < 0.15
        idx = (np.arange(seq_len + 1)[None, :] + rng.integers(0, period, (batch_size, 1))) % period
        toks = base[idx]
        toks = np.where(noise, rng.integers(0, cfg.vocab_size, toks.shape), toks)
        yield {
            "tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32),
        }


class Trainer:
    def __init__(
        self,
        cfg: ArchConfig,
        *,
        batch_iter: Iterator[dict[str, np.ndarray]] | None = None,
        batch_size: int = 8,
        seq_len: int = 128,
        ckpt_dir: str | None = None,
        ckpt_every: int = 50,
        total_steps: int = 1000,
        seed: int = 0,
        mesh: Any = None,
        rules: Any = None,
    ):
        self.cfg = cfg
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.batch_iter = batch_iter or synthetic_batches(
            cfg, batch_size=batch_size, seq_len=seq_len, seed=seed
        )
        schedule = cosine_with_warmup(
            cfg.max_lr, warmup_steps=max(5, total_steps // 20), total_steps=total_steps
        )
        # mesh=None: every device when there are several, else one device
        if mesh is None and len(jax.devices()) > 1:
            mesh = make_host_mesh()
        if mesh is not None and rules is None:
            rules = FSDP_RULES
        self.mesh = mesh
        self.step_fn, self.state_shardings = jit_train_step(
            cfg, mesh=mesh, rules=rules, batch_shape=(batch_size, seq_len),
            schedule=schedule,
        )
        self.state = init_train_state(
            jax.random.PRNGKey(seed), cfg, shardings=self.state_shardings
        )
        self.ckpt = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.ckpt_every = ckpt_every
        self.step = 0
        self.history: list[dict[str, float]] = []

    def resume(self) -> bool:
        """Restore from the latest checkpoint if one exists."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return False
        step, self.state = self.ckpt.restore(
            self.state, shardings=self.state_shardings
        )
        self.step = step
        return True

    def run(self, n_steps: int, *, log_every: int = 0) -> dict[str, Any]:
        t0 = time.time()
        for _ in range(n_steps):
            # host arrays: jit places them by the step's batch sharding
            batch = {k: np.asarray(v) for k, v in next(self.batch_iter).items()}
            self.state, metrics = self.step_fn(self.state, batch)
            self.step += 1
            rec = {
                "step": self.step,
                "loss": float(metrics["loss"]),
                "grad_norm": float(metrics["grad_norm"]),
            }
            self.history.append(rec)
            if log_every and self.step % log_every == 0:
                print(
                    f"step {rec['step']:5d} loss {rec['loss']:.4f} "
                    f"gnorm {rec['grad_norm']:.3f}",
                    flush=True,
                )
            if self.ckpt is not None and self.step % self.ckpt_every == 0:
                self.ckpt.save(self.step, self.state)
        if self.ckpt is not None:
            self.ckpt.save(self.step, self.state, blocking=True)
        return {
            "final_loss": self.history[-1]["loss"] if self.history else None,
            "initial_loss": self.history[0]["loss"] if self.history else None,
            "steps": self.step,
            "wall_s": time.time() - t0,
            "tokens_per_s": n_steps * self.batch_size * self.seq_len
            / max(time.time() - t0, 1e-9),
        }


def make_training_task(default_cfg: ArchConfig | None = None) -> Callable[..., dict[str, Any]]:
    """Build a *registered-task* callable so the orchestrator (and HPO) can
    dispatch training runs as Work payloads."""
    from repro.configs import smoke_config

    def train_task(parameters: dict[str, Any], job_index: int, n_jobs: int, payload: dict) -> dict[str, Any]:
        cand = parameters.get("candidate") or {}
        arch = parameters.get("arch", "smollm-360m")
        cfg = default_cfg or smoke_config(arch)
        if "lr" in cand:
            cfg = cfg.replace(max_lr=float(cand["lr"]))
        n_steps = int(parameters.get("steps", 20))
        trainer = Trainer(
            cfg,
            batch_size=int(parameters.get("batch_size", 4)),
            seq_len=int(parameters.get("seq_len", 64)),
            total_steps=n_steps,
            seed=int(parameters.get("seed", 0)) + job_index,
        )
        out = trainer.run(n_steps)
        return {"objective": out["final_loss"], **out}

    return train_task
