"""Dry-run machinery: HLO trip-count-aware accounting (in-process) and the
real 512-device dryrun entry point (subprocess, one cheap cell)."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_analysis import analyze_hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hlo_analysis_counts_scan_trip_counts():
    def f(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=12)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    parsed = analyze_hlo(compiled.as_text())
    assert parsed["dot_flops"] == pytest.approx(12 * 2 * 64**3, rel=0.01)


def test_hlo_analysis_counts_nested_scans():
    def f(x, w):
        def inner(c, _):
            return c @ w, None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None

        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    parsed = analyze_hlo(compiled.as_text())
    assert parsed["dot_flops"] == pytest.approx(12 * 2 * 32**3, rel=0.01)


def test_analytic_flops_close_to_hlo_parse_for_unrolled_model():
    """Cross-check the analytic FLOPs model against XLA's own count on a
    tiny unrolled config (no scans ⇒ cost_analysis is exact)."""
    from repro.configs import smoke_config
    from repro.launch.analytic import forward_flops
    from repro.models.config import ShapeConfig
    from repro.models.io import batch_specs
    from repro.models.lm import forward_train

    cfg = smoke_config("qwen3-4b").replace(remat="none")
    shape = ShapeConfig("t", 128, 2, "train")
    sds = batch_specs(cfg, shape)
    from repro.models.lm import init_params_and_specs

    params, _ = init_params_and_specs(jax.random.PRNGKey(0), cfg)
    compiled = jax.jit(lambda p, b: forward_train(p, b, cfg)[0]).lower(params, sds).compile()
    xla_flops = float(compiled.cost_analysis().get("flops", 0.0))
    ours = forward_flops(cfg, shape)
    # loss adds a vocab matmul per chunk; attention scans count once in XLA.
    # The analytic forward count must be within 2x of XLA's (sanity band).
    assert ours == pytest.approx(xla_flops, rel=1.0)


@pytest.mark.slow
def test_dryrun_subprocess_single_cell(tmp_path):
    """The real dry-run: 512 host devices, 16×16 mesh, one decode cell."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun",
         "--arch", "smollm-360m", "--shape", "decode_32k",
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=420, env=env, cwd=REPO,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "1 ok, 0 skipped, 0 errors" in out.stdout
    rec = json.loads((tmp_path / "smollm-360m_decode_32k_single.json").read_text())
    assert rec["status"] == "ok" and rec["n_chips"] == 256
    assert rec["roofline"]["dominant"] in ("compute_s", "memory_s", "collective_s")
