"""Compile a cell's device programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py <cell> [<cell> ...]

For a train cell: ``Trainer``'s jitted step on the cell's chips (a 1 x N
(data, model) mesh of described devices with ``FSDP_RULES`` when N > 1).
For a serve cell: the engine's ``decode_all`` and its largest prefill
bucket on one described chip.  Prints each program's bytes per device
from ``memory_analysis()``; what the chip's compiler refuses raises here.
"""
from __future__ import annotations

import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def report(name: str, compiled, t0: float) -> None:
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes \
        - m.alias_size_in_bytes
    print(f"{name}: arguments {m.argument_size_in_bytes} outputs {m.output_size_in_bytes} "
          f"temporaries {m.temp_size_in_bytes} aliased {m.alias_size_in_bytes} "
          f"-> {total} bytes per device ({total / 2**30:.2f} GiB); "
          f"compiled in {time.perf_counter() - t0:.1f} s", flush=True)


def train(cell, topo) -> None:
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding

    from repro.launch.mesh import make_host_mesh
    from repro.parallel.sharding import FSDP_RULES, spec_for
    from repro.train.step import abstract_train_state, jit_train_step

    t = cell.traffic
    cfg = cell.reference.program_config(cell.config)
    shape = (t["batch_size"], t["seq_len"])
    devices = topo.devices[: cell.chips]
    sds, _ = abstract_train_state(cfg)
    if cell.chips > 1:
        mesh = make_host_mesh(devices)
        step, state_sh = jit_train_step(cfg, mesh=mesh, rules=FSDP_RULES, batch_shape=shape)
        batch_sh = NamedSharding(mesh, spec_for(shape, ("batch", None), mesh, FSDP_RULES))
    else:
        step, _ = jit_train_step(cfg, batch_shape=shape)
        one = SingleDeviceSharding(devices[0])
        state_sh = jax.tree.map(lambda _: one, sds)
        batch_sh = one
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                         sds, state_sh)
    batch = {k: jax.ShapeDtypeStruct(shape, "int32", sharding=batch_sh)
             for k in ("tokens", "labels")}
    t0 = time.perf_counter()
    report(f"{cell.name} train step {shape}", step.lower(state, batch).compile(), t0)


def serve(cell, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.models.lm import abstract_params, cache_specs
    from repro.serve.engine import OfflineEngine

    from bench.drivers.serve import buckets

    t, e = cell.traffic, cell.traffic["engine"]
    cfg = cell.reference.program_config(cell.config)
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)  # noqa: E731
    params = jax.tree.map(put, abstract_params(cfg)[0])
    eng = OfflineEngine(cfg, params, n_slots=e["n_slots"], prefill_batch=e["prefill_batch"],
                        max_seq=e["max_seq"])
    n, p = e["n_slots"], e["prefill_batch"]
    caches = jax.tree.map(put, cache_specs(cfg, n, e["max_seq"]))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    t0 = time.perf_counter()
    dec = eng._decode.lower(params, i32(n), caches, i32(n), i32(n),
                            jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one), key).compile()
    report(f"{cell.name} decode_all (slots {n}, max_seq {e['max_seq']})", dec, t0)
    b = buckets(t)[-1]
    t0 = time.perf_counter()
    pre = eng._prefill_fn(b).lower(params, i32(p, b), i32(p), i32(p), key).compile()
    report(f"{cell.name} prefill_all (rows {p}, bucket {b})", pre, t0)


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    from bench.common import resolve_cell, with_planned

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    for name in argv:
        cell = resolve_cell(name, with_planned(), limits={})
        (train if cell.traffic["driver"] == "train" else serve)(cell, topo)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
