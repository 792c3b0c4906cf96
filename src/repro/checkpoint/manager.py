"""Checkpointing: async, double-buffered, mesh-agnostic.

Fault-tolerance contract for 1000+-node runs:

* **async**: the training loop hands the state to a background thread
  (after a host-side snapshot) and keeps stepping; at most one write is in
  flight (double-buffering semantics) — a second save request blocks until
  the previous one lands, bounding data loss to one interval;
* **atomic**: writes go to ``<dir>/tmp-<step>`` then rename to
  ``<dir>/step-<step>`` — a crashed writer never corrupts the latest good
  checkpoint;
* **mesh-agnostic**: arrays are saved as *global* host arrays keyed by
  tree path; ``restore(..., shardings=...)`` lays them out on whatever
  mesh the restarted job has (elastic rescale: 256→512 chips or back);
* **rotation**: keep the most recent ``keep`` checkpoints.
"""
from __future__ import annotations

import json
import pathlib
import re
import shutil
import threading
from typing import Any

import jax
import numpy as np

from repro.common.exceptions import CheckpointError

_SEP = "/"


def _flatten_with_paths(tree: Any) -> list[tuple[str, Any]]:
    flat = jax.tree.flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = _SEP.join(_path_part(p) for p in path)
        out.append((key, leaf))
    return out


def _path_part(p: Any) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


class CheckpointManager:
    def __init__(self, directory: str | pathlib.Path, *, keep: int = 3):
        self.dir = pathlib.Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._inflight: threading.Thread | None = None
        self._lock = threading.Lock()
        self.saves = 0

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state: Any, *, blocking: bool = False) -> None:
        """Snapshot to host, then write in the background."""
        host_state = jax.tree.map(np.asarray, jax.device_get(state))
        with self._lock:
            if self._inflight is not None:
                self._inflight.join()  # double buffer: wait out the previous
            t = threading.Thread(
                target=self._write, args=(step, host_state), daemon=True,
                name=f"ckpt-write-{step}",
            )
            t.start()
            self._inflight = t
        if blocking:
            t.join()

    def wait(self) -> None:
        with self._lock:
            t = self._inflight
        if t is not None:
            t.join()

    def _write(self, step: int, host_state: Any) -> None:
        tmp = self.dir / f"tmp-{step}"
        final = self.dir / f"step-{step:010d}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        arrays = dict(_flatten_with_paths(host_state))
        np.savez(tmp / "arrays.npz", **{k.replace("/", "|"): v for k, v in arrays.items()})
        (tmp / "meta.json").write_text(
            json.dumps(
                {
                    "step": step,
                    "n_arrays": len(arrays),
                    "bytes": int(sum(np.asarray(v).nbytes for v in arrays.values())),
                }
            )
        )
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)
        self.saves += 1
        self._rotate()

    def _rotate(self) -> None:
        ckpts = self.all_steps()
        for step in ckpts[: -self.keep]:
            shutil.rmtree(self.dir / f"step-{step:010d}", ignore_errors=True)

    # -- restore --------------------------------------------------------------
    def all_steps(self) -> list[int]:
        out = []
        for p in self.dir.iterdir():
            m = re.fullmatch(r"step-(\d+)", p.name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(
        self,
        like: Any,
        *,
        step: int | None = None,
        shardings: Any = None,
    ) -> tuple[int, Any]:
        """Restore into the structure of ``like`` (values ignored).  With
        ``shardings``, arrays are device_put with the new layout — this is
        the elastic-rescale path (checkpoints carry no mesh info)."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise CheckpointError(f"no checkpoints under {self.dir}")
        path = self.dir / f"step-{step:010d}"
        if not path.exists():
            raise CheckpointError(f"checkpoint {path} missing")
        with np.load(path / "arrays.npz") as npz:
            arrays = {k.replace("|", "/"): npz[k] for k in npz.files}
        flat_like = _flatten_with_paths(like)
        missing = [k for k, _ in flat_like if k not in arrays]
        if missing:
            raise CheckpointError(f"checkpoint missing {len(missing)} arrays: {missing[:4]}")
        values = [arrays[k] for k, _ in flat_like]
        treedef = jax.tree.structure(like)
        restored = jax.tree.unflatten(treedef, values)
        if shardings is not None:
            restored = jax.tree.map(
                lambda v, s: jax.device_put(v, s), restored, shardings
            )
        else:
            restored = jax.tree.map(jax.numpy.asarray, restored)
        return step, restored
