"""A configuration, a traffic mix and a cell go in as new files plus
additions to ``BENCHMARK.json``, and so does the move of a planned cell.

In a copy of ``bench/`` and ``BENCHMARK.json``, a new configuration, a new
traffic mix, a cell of the two with its limits, and their tiny sizes are
written as new files; a planned train cell, while there is one, is moved by
its ``BENCHMARK.json`` entry and its limits file.  No file the copy already had changes but
``BENCHMARK.json``, and that only by additions.  The copy's own tests then
run in a subprocess, so that every path they find by ``__file__`` is the
copy's: both cells resolve and pass a tiny run on the CPU.
"""
from __future__ import annotations

import copy as copy_mod
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT

CONFIG, TRAFFIC = "probe-llama", "probe_train"
CELL = f"{CONFIG}.{TRAFFIC}"


def _write(path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1) + "\n")


def _driver(root, w: dict) -> str:
    return json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())["driver"]


def _gate(root, bench: dict, entry: dict) -> None:
    """Add ``entry`` to the workloads, and to every metric whose list names
    the first gated cell of the same driver."""
    like = next(w["name"] for w in bench["workloads"]
                if _driver(root, w) == _driver(root, entry))
    bench["workloads"].append(entry)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(entry["name"])


def _snapshot(root) -> dict:
    return {p.relative_to(root): p.read_bytes() for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def _is_addition(old, new) -> bool:
    """``new`` holds ``old``: every key and list item of ``old`` is there,
    itself only added to; lists grow at their ends."""
    if isinstance(old, dict):
        return isinstance(new, dict) and all(k in new and _is_addition(v, new[k])
                                             for k, v in old.items())
    if isinstance(old, list):
        return isinstance(new, list) and len(new) >= len(old) and all(
            _is_addition(o, n) for o, n in zip(old, new))
    return old == new


def test_cell_and_planned_move_go_in_as_new_files(tmp_path):
    from bench.common import load_json, planned

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _snapshot(tmp_path)
    old_bench = load_json(tmp_path / "BENCHMARK.json")
    bench = copy_mod.deepcopy(old_bench)
    b = tmp_path / "bench"
    tiny = b / "tests" / "tiny"

    # a configuration, a traffic mix and their cell, as new files
    config = {**load_json(b / "configs" / "smollm-360m.json"), "num_hidden_layers": 3}
    _write(b / "configs" / f"{CONFIG}.json", config)
    _write(tiny / "configs" / f"{CONFIG}.json",
           {**load_json(tiny / "configs" / "smollm-360m.json"), "num_hidden_layers": 3,
            "num_key_value_heads": 1})
    _write(b / "traffic" / f"{TRAFFIC}.json",
           {**load_json(b / "traffic" / "train.json"), "batch_size": 4})
    _write(tiny / "traffic" / f"{TRAFFIC}.json", {"batch_size": 2, "seq_len": 32})
    _write(b / "limits" / f"{CELL}.json", load_json(tiny / "limits" / "train.json"))
    bench["configs"].append({"name": CONFIG, "source": config["source"],
                             "file": f"bench/configs/{CONFIG}.json", "reduced": [],
                             "why": "a second Llama-layout configuration"})
    _gate(tmp_path, bench, {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
                            "why": "a cell added as files"})

    # a planned train cell, while there is one, moved: its entry and its
    # limits file (a serve cell of a loop no gated cell has needs a metric
    # of its own besides)
    moved = [w for w in planned() if _driver(tmp_path, w) == "train"][:1]
    for w in moved:
        limits = load_json(tiny / "limits" / f"{_driver(tmp_path, w)}.json")
        _write(b / "limits" / f"{w['name']}.json", limits)
        _gate(tmp_path, bench, dict(w))
    _write(tmp_path / "BENCHMARK.json", bench)

    after = _snapshot(tmp_path)
    changed = {p for p in before if after[p] != before[p]}
    assert changed == {tmp_path.joinpath("BENCHMARK.json").relative_to(tmp_path)}
    assert _is_addition(old_bench, bench)

    tests = b / "tests"
    ids = [f"test_resolve.py::test_config_file_is_the_run[{CONFIG}]",
           "test_resolve.py::test_planned_cells_resolve_apart",
           "test_resolve.py::test_with_planned_names_each_cell_once",
           f"test_counts.py::test_param_count_matches_program[{CONFIG}]"]
    for name in [CELL, *(w["name"] for w in moved)]:
        ids += [f"test_resolve.py::test_cell_resolves[{name}]",
                f"test_resolve.py::test_limits_file_is_what_its_driver_reads[{name}]",
                f"test_runs.py::test_sound_run_is_correct[{name}]"]
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
                          *[str(tests / i) for i in ids]],
                         cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
    assert f"{len(ids)} passed" in out.stdout, out.stdout[-2000:]
