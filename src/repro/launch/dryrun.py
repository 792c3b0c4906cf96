import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count at first init.

_DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces:
  * ``memory_analysis`` — proves the step fits per-device HBM,
  * ``cost_analysis``   — HLO FLOPs / bytes for the roofline,
  * collective traffic  — parsed from the optimized HLO: per-collective-op
    operand bytes (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute), the §Roofline collective term's numerator.

Usage::

    python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    python -m repro.launch.dryrun --all --out results/dryrun
    python -m repro.launch.dryrun --all --multi-pod
"""
__doc__ = _DOC

import argparse
import json
import pathlib
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.launch.analytic import cell_analytics
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import TPU_V5E, make_production_mesh
from repro.models.config import SHAPES, cell_is_supported
from repro.models.io import batch_specs, decode_specs
from repro.models.lm import abstract_params, cache_logical_specs
from repro.parallel.sharding import (
    DEFAULT_RULES,
    SEQ_ATTN_RULES,
    TRAIN_RULES,
    sharding_for,
    tree_shardings,
    zero_shard_specs,
)


def optimized_rules(cfg, shape) -> tuple[dict, bool]:
    """(rules, residual_sharding) for the §Perf-optimized configuration.

    * non-MoE train cells → TRAIN_RULES (ZeRO-3-style full-DP batch,
      weight gathering; 7× less collective traffic than TP+SP);
    * archs whose head count defies the model axis → q-seq-sharded
      attention (kills attention-compute replication);
    * MoE cells keep DEFAULT_RULES — their optimization (grouped
      shard-local dispatch + fused psum combine) lives in the model code.
    """
    if shape.kind == "train" and not cfg.moe.n_experts:
        return TRAIN_RULES, False
    if cfg.n_heads % 16 != 0 and shape.kind == "prefill":
        return SEQ_ATTN_RULES, False
    return DEFAULT_RULES, True
from repro.serve.step import make_decode_step, make_prefill_step
from repro.train.step import abstract_train_state, make_train_step


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------
def _batch_shardings(sds_tree: dict[str, Any], mesh) -> dict[str, Any]:
    out = {}
    for name, sds in sds_tree.items():
        axes: tuple = ("batch",) + (None,) * (len(sds.shape) - 1)
        out[name] = sharding_for(sds, axes, mesh)
    return out


def lower_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    rules: dict | None = None,
    residual_sharding: bool = True,
    extra_cfg: dict | None = None,
    opt: bool = False,
) -> dict[str, Any]:
    t0 = time.time()
    cfg = get_config(arch)
    if extra_cfg:
        cfg = cfg.replace(**extra_cfg)
    shape = SHAPES[shape_name]
    ok, reason = cell_is_supported(cfg, shape_name)
    record: dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "config": "optimized" if opt else "baseline",
    }
    if not ok:
        record.update({"status": "skipped", "reason": reason})
        return record
    mesh = make_production_mesh(multi_pod=multi_pod)
    if opt and rules is None:
        rules, residual_sharding = optimized_rules(cfg, shape)
    rules = dict(rules or DEFAULT_RULES)
    fallbacks: list = []

    if shape.kind == "train":
        state_sds, state_specs = abstract_train_state(cfg)
        params_sh = tree_shardings(
            state_sds["params"], state_specs["params"], mesh, rules,
            fallbacks=fallbacks,
        )
        opt_sh = {
            "master": zero_shard_specs(
                state_sds["opt"]["master"], state_specs["params"], mesh, rules
            ),
            "m": zero_shard_specs(
                state_sds["opt"]["m"], state_specs["params"], mesh, rules
            ),
            "v": zero_shard_specs(
                state_sds["opt"]["v"], state_specs["params"], mesh, rules
            ),
            "step": NamedSharding(mesh, P()),
        }
        state_sh = {"params": params_sh, "opt": opt_sh}
        b_sds = batch_specs(cfg, shape)
        b_sh = _batch_shardings(b_sds, mesh)
        step = make_train_step(cfg, mesh=mesh, rules=rules,
                               residual_sharding=residual_sharding)
        jitted = jax.jit(
            step,
            in_shardings=(state_sh, b_sh),
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
        )
        lowered = jitted.lower(state_sds, b_sds)
    elif shape.kind == "prefill":
        p_sds, p_specs = abstract_params(cfg)
        p_sh = tree_shardings(p_sds, p_specs, mesh, rules, fallbacks=fallbacks)
        b_sds = batch_specs(cfg, shape)
        b_sh = _batch_shardings(b_sds, mesh)
        step = make_prefill_step(cfg, mesh=mesh, rules=rules)
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
        lowered = jitted.lower(p_sds, b_sds)
    else:  # decode
        p_sds, p_specs = abstract_params(cfg)
        p_sh = tree_shardings(p_sds, p_specs, mesh, rules, fallbacks=fallbacks)
        d = decode_specs(cfg, shape)
        b_sh = _batch_shardings(d["batch"], mesh)
        cache_sh = tree_shardings(
            d["caches"], cache_logical_specs(cfg), mesh, rules,
            fallbacks=fallbacks,
        )
        pos_sh = NamedSharding(mesh, P())
        step = make_decode_step(cfg, mesh=mesh, rules=rules)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, b_sh, cache_sh, pos_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,),
        )
        lowered = jitted.lower(p_sds, d["batch"], d["caches"], d["position"])

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    parsed = analyze_hlo(hlo)        # per-device, trip-count-aware
    n_chips = mesh.devices.size

    record.update(
        {
            "status": "ok",
            "n_chips": int(n_chips),
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            # raw XLA numbers (while bodies counted once — recorded for
            # reference, NOT used for the roofline):
            "xla_cost_flops": float(cost.get("flops", 0.0)),
            "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
            # trip-count-aware per-device numbers from the HLO parse:
            "hlo_dot_flops_per_chip": parsed["dot_flops"],
            "collectives_per_chip": parsed["collectives"],
            "fallbacks": sorted(set(f[0] for f in fallbacks)),
            "analytic": cell_analytics(cfg, shape),
        }
    )
    if mem is not None:
        record["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "peak_bytes": int(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
            ),
        }
    record["roofline"] = roofline_terms(record)
    return record


def roofline_terms(record: dict[str, Any]) -> dict[str, Any]:
    """Three-term roofline.  FLOPs: per-chip trip-aware HLO dot parse
    (falls back to analytic/chips when the parse finds nothing).  Memory:
    analytic HBM traffic / chips.  Collectives: per-chip operand bytes."""
    n = record.get("n_chips", 256)
    an = record.get("analytic", {})
    flops = record.get("hlo_dot_flops_per_chip", 0.0)
    if flops <= 0:
        flops = an.get("analytic_flops", 0.0) / n
    byt = an.get("analytic_hbm_bytes", 0.0) / n
    cbytes = record.get("collectives_per_chip", {}).get("total_bytes", 0)
    compute_s = flops / TPU_V5E["peak_bf16_flops"]
    memory_s = byt / TPU_V5E["hbm_bandwidth"]
    collective_s = cbytes / TPU_V5E["ici_bandwidth"]
    terms: dict[str, Any] = {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
    }
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["dominant"] = dom
    bound = max(compute_s, memory_s, collective_s)
    terms["roofline_fraction"] = compute_s / bound if bound > 0 else 0.0
    # useful-compute ratio: MODEL_FLOPS / executed FLOPs
    model = an.get("model_flops", 0.0)
    terms["model_flops_ratio"] = model / max(flops * n, 1.0)
    return terms


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def run_cells(
    cells: list[tuple[str, str]],
    *,
    multi_pod: bool,
    out_dir: pathlib.Path | None,
    residual_sharding: bool = True,
    opt: bool = False,
) -> list[dict[str, Any]]:
    results = []
    for arch, shape in cells:
        tag = f"{arch}|{shape}|{'multi' if multi_pod else 'single'}"
        cache_file = (
            out_dir / f"{arch}_{shape}_{'multi' if multi_pod else 'single'}.json"
            if out_dir
            else None
        )
        if cache_file and cache_file.exists():
            rec = json.loads(cache_file.read_text())
            results.append(rec)
            print(f"[cached] {tag}: {rec['status']}")
            continue
        try:
            rec = lower_cell(
                arch, shape, multi_pod=multi_pod,
                residual_sharding=residual_sharding, opt=opt,
            )
        except Exception as exc:  # noqa: BLE001
            rec = {
                "arch": arch,
                "shape": shape,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "error",
                "error": f"{type(exc).__name__}: {exc}",
                "traceback": traceback.format_exc()[-2000:],
            }
        results.append(rec)
        if cache_file:
            out_dir.mkdir(parents=True, exist_ok=True)
            cache_file.write_text(json.dumps(rec, indent=1))
        status = rec["status"]
        extra = ""
        if status == "ok":
            r = rec["roofline"]
            extra = (
                f" compile={rec['compile_s']}s"
                f" flops/chip={rec['hlo_dot_flops_per_chip']:.3g}"
                f" dom={r['dominant']} frac={r['roofline_fraction']:.2f}"
                f" useful={r['model_flops_ratio']:.2f}"
            )
        elif status == "error":
            extra = " " + rec["error"][:160]
        print(f"[{status}] {tag}{extra}", flush=True)
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--no-cache", action="store_true")
    ap.add_argument("--no-residual-sharding", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="§Perf-optimized rule selection per cell")
    args = ap.parse_args()

    out_dir = None if args.no_cache else pathlib.Path(args.out)
    if args.all:
        cells = [(a, s) for a in ARCH_IDS for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    all_results = []
    for mp in meshes:
        all_results += run_cells(
            cells, multi_pod=mp, out_dir=out_dir,
            residual_sharding=not args.no_residual_sharding,
            opt=args.opt,
        )
    n_ok = sum(1 for r in all_results if r["status"] == "ok")
    n_skip = sum(1 for r in all_results if r["status"] == "skipped")
    n_err = sum(1 for r in all_results if r["status"] == "error")
    print(f"\n=== dry-run summary: {n_ok} ok, {n_skip} skipped, {n_err} errors ===")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
