"""Shared fixtures: a checkout in a temporary directory whose
``BENCHMARK.json`` names tiny cells, so a whole run fits the CPU.

The tiny configurations are copies of the real ones (the same generator
module under another name) with their scale cut; nothing in the
benchmark's own files is edited to make them run.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY_CONFIGS = {
    "tiny_tpch": ("tpch_chain", {"scale_factor": 0.001}),
}
TINY_CELLS = [
    ("tiny_tpch.agg", "tiny_tpch", "agg"),
    ("tiny_tpch.minmax", "tiny_tpch", "minmax"),
]


def load_config(name: str, **overrides):
    """A real configuration's deployment file (with ``overrides``) and
    its generator module."""
    import harness

    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(overrides)
    return cfg, harness.import_file(BENCH / "configs" / f"{name}.py", f"cfg_{name}")


def make_checkout(root: Path) -> Path:
    """``root`` laid out as a checkout: the benchmark's files, the
    program (linked), and a BENCHMARK.json of tiny cells."""
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    configs = root / "benchmarks" / "chip" / "configs"
    bench["configs"] = []
    for name, (base, overrides) in TINY_CONFIGS.items():
        cfg = json.loads((configs / f"{base}.json").read_text())
        cfg.update(overrides, name=name)
        (configs / f"{name}.json").write_text(json.dumps(cfg))
        shutil.copy(configs / f"{base}.py", configs / f"{name}.py")
        bench["configs"].append({
            "name": name, "source": cfg["source"][:200],
            "file": f"benchmarks/chip/configs/{name}.json",
            "reduced": sorted(overrides), "why": "tiny copy for the CPU tests",
        })
    bench["workloads"] = [
        {"name": n, "config": c, "traffic": t, "chips": 1, "why": "CPU test"}
        for n, c, t in TINY_CELLS
    ]
    queries = [n for n, _, _ in TINY_CELLS]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    bench["end_to_end"] = [
        dict(m, workloads=queries) for m in bench["end_to_end"]
        if m["name"] == "queries_per_s"
    ] + [
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"},
    ]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture
def checkout(tmp_path):
    return make_checkout(tmp_path / "checkout")


def run_cell(root: Path, workload: str, seed: int = 5, seconds: float = 0.5,
             trace: bool = False) -> tuple[int, dict | None, str]:
    """Drive a whole run in this process with the look for a chip
    skipped; returns (exit code, result line or None, stderr)."""
    import io
    import time

    import harness

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(workload, seed, seconds, trace, t_start=time.perf_counter(),
                     root=root, require_chip=False, out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
