"""Use-case validation against the paper's claims (§4): Data Carousel
fine-grained staging, distributed HPO, Active Learning, trainer restart."""
from __future__ import annotations

import math

import pytest

from repro.core.work import register_task
from repro.data import DataPipeline, ShardedDataset, run_carousel
from repro.hpo import HPOService, SearchSpace, SegmentedHPO, TPE, Uniform, LogUniform, make_optimizer
from repro.al import ActiveLearner


# ---------------------------------------------------------------------------
# Data Carousel (§4.1 / Fig. 9 mechanism)
# ---------------------------------------------------------------------------
def test_carousel_file_mode_beats_dataset_mode():
    files = [f"f{i}" for i in range(48)]
    m_file = run_carousel(files, mode="file", latency_s=0.002, consume_s=0.001)
    m_ds = run_carousel(files, mode="dataset", latency_s=0.002, consume_s=0.001)
    # the paper's three claims:
    assert m_file["time_to_first_consume_s"] < m_ds["time_to_first_consume_s"]
    assert m_file["disk_high_water_bytes"] < m_ds["disk_high_water_bytes"] / 4
    assert m_file["makespan_s"] <= m_ds["makespan_s"] * 1.2
    assert m_file["staged_files"] == m_ds["staged_files"] == 48


def test_pipeline_consumes_in_staging_order():
    ds = ShardedDataset("d", n_shards=8, tokens_per_shard=1024, vocab_size=100)
    pipe = DataPipeline(ds, batch_size=2, seq_len=255)
    for name in ds.file_names()[:2]:
        pipe.stage(name)
    batch = pipe.next_batch(timeout=5)
    assert batch is not None and batch["tokens"].shape == (2, 255)
    assert pipe.consumed_shards >= 1
    # deterministic shards: same shard id → same tokens
    import numpy as np

    np.testing.assert_array_equal(ds.load_shard(3), ds.load_shard(3))


def test_pipeline_blocks_until_staged():
    ds = ShardedDataset("d", n_shards=4, tokens_per_shard=512, vocab_size=100)
    pipe = DataPipeline(ds, batch_size=4, seq_len=511)
    assert pipe.next_batch(timeout=0.2) is None  # nothing staged yet
    for name in ds.file_names():
        pipe.stage(name)
    assert pipe.next_batch(timeout=5) is not None


# ---------------------------------------------------------------------------
# HPO (§4.3 / Fig. 12 mechanism)
# ---------------------------------------------------------------------------
def _branin_ish(parameters, job_index, n_jobs, payload):
    c = parameters["candidate"]
    x, lr = c["x"], c["lr"]
    return {"objective": (x - 0.3) ** 2 + 0.2 * (math.log10(lr) + 3.0) ** 2}


def test_hpo_service_finds_good_candidate(orch):
    register_task("branin", _branin_ish)
    space = SearchSpace({"x": Uniform(-1, 1), "lr": LogUniform(1e-5, 1e-1)})
    svc = HPOService(orch, space, "branin", optimizer="tpe", seed=0)
    out = svc.run(iterations=4, candidates_per_iter=6, timeout=60)
    assert out["n_trials"] == 24
    assert out["best_objective"] < 0.15
    assert abs(out["best_candidate"]["x"] - 0.3) < 0.45


def test_tpe_beats_random_on_fixed_budget():
    """Same evaluation budget, same seeds — TPE's median best must beat
    random search's (offline, no orchestrator: pure optimizer check)."""

    def f(c):
        return (c["x"] - 0.62) ** 2 + (c["y"] + 0.2) ** 2

    space = lambda: SearchSpace({"x": Uniform(-1, 1), "y": Uniform(-1, 1)})  # noqa: E731
    tpe_best, rnd_best = [], []
    for seed in range(5):
        for kind, sink in (("tpe", tpe_best), ("random", rnd_best)):
            opt = make_optimizer(kind, space(), seed=seed)
            for _ in range(40):
                c = opt.ask(1)[0]
                opt.tell(c, f(c))
            sink.append(opt.best()[1])
    tpe_best.sort(), rnd_best.sort()
    assert tpe_best[2] <= rnd_best[2]  # median comparison


def test_segmented_hpo_optimizes_multiple_models(orch):
    register_task("seg_a", lambda parameters, **kw: {"objective": (parameters["candidate"]["x"] - 0.1) ** 2})
    register_task("seg_b", lambda parameters, **kw: {"objective": (parameters["candidate"]["x"] + 0.4) ** 2})
    seg = SegmentedHPO(
        orch,
        {
            "modelA": (SearchSpace({"x": Uniform(-1, 1)}), "seg_a"),
            "modelB": (SearchSpace({"x": Uniform(-1, 1)}), "seg_b"),
        },
        seed=0,
    )
    out = seg.run(iterations=3, candidates_per_iter=4, timeout=60)
    assert abs(out["modelA"]["best_candidate"]["x"] - 0.1) < 0.4
    assert abs(out["modelB"]["best_candidate"]["x"] + 0.4) < 0.4


# ---------------------------------------------------------------------------
# Active Learning (§4.4 / Fig. 13 mechanism)
# ---------------------------------------------------------------------------
def test_active_learning_converges_to_optimum(orch):
    al = ActiveLearner(orch)
    out = al.run(iterations=6, target=2.0, timeout=60)
    assert abs(out["best_x"] - out["true_optimum_x"]) < 0.08
    assert out["best_y"] > 1.8
    assert out["n_observations"] <= 24   # efficient: far fewer than a grid


# ---------------------------------------------------------------------------
# trainer restart (fault tolerance)
# ---------------------------------------------------------------------------
def test_trainer_checkpoint_restart_bitwise(tmp_path):
    from repro.configs import smoke_config
    from repro.train.trainer import Trainer
    import numpy as np

    cfg = smoke_config("smollm-360m").replace(n_layers=2)
    a = Trainer(cfg, batch_size=2, seq_len=32, ckpt_dir=str(tmp_path / "ck"),
                ckpt_every=5, total_steps=10, seed=3)
    a.run(10)
    # crash + restart from step 10, run 5 more
    b = Trainer(cfg, batch_size=2, seq_len=32, ckpt_dir=str(tmp_path / "ck"),
                ckpt_every=5, total_steps=10, seed=3)
    assert b.resume() and b.step == 10
    # uninterrupted reference run
    c = Trainer(cfg, batch_size=2, seq_len=32, total_steps=10, seed=3)
    c.run(10)
    wa = np.asarray(jaxtree_first(a.state["params"]))
    wc = np.asarray(jaxtree_first(c.state["params"]))
    np.testing.assert_allclose(wa, wc, atol=1e-6)


def test_trainer_on_a_mesh_matches_single_device(tmp_path):
    """The sharded path (mesh-laid state built in place, jit with in/out
    shardings, restore onto the same shardings) trains exactly like the
    single-device one."""
    import jax
    from jax.sharding import NamedSharding

    from repro.configs import smoke_config
    from repro.launch.mesh import make_host_mesh
    from repro.train.trainer import Trainer

    cfg = smoke_config("rwkv6-1.6b")
    mesh = make_host_mesh(jax.devices()[:1])
    kw = dict(batch_size=2, seq_len=32, total_steps=10, seed=3)
    a = Trainer(cfg, mesh=mesh, ckpt_dir=str(tmp_path / "ck"), ckpt_every=3, **kw)
    a.run(3)
    b = Trainer(cfg, **kw)
    b.run(3)
    assert b.mesh is None and b.state_shardings is None
    assert [h["loss"] for h in a.history] == pytest.approx(
        [h["loss"] for h in b.history], abs=1e-6
    )
    c = Trainer(cfg, mesh=mesh, ckpt_dir=str(tmp_path / "ck"), **kw)
    assert c.resume() and c.step == 3
    for state in (a.state, c.state):
        assert all(
            isinstance(x.sharding, NamedSharding) and x.sharding.mesh == mesh
            for x in jax.tree.leaves(state)
        )


def jaxtree_first(tree):
    import jax

    return jax.tree.leaves(tree)[0]


def test_training_loss_decreases():
    from repro.configs import smoke_config
    from repro.train.trainer import Trainer

    cfg = smoke_config("smollm-360m").replace(n_layers=2)
    t = Trainer(cfg, batch_size=4, seq_len=64, total_steps=40, seed=0)
    out = t.run(40)
    assert out["final_loss"] < out["initial_loss"] - 0.3
