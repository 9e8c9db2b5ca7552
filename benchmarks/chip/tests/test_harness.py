"""The harness finds everything by name from BENCHMARK.json, and refuses
to run without the chip."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import BENCH, REPO, make_checkout, run_cell
from test_tracing import synthetic

import tracing

DUMMY_CONFIG = '''
import numpy as np


def generate(cfg, seed):
    rng = np.random.default_rng(seed)
    n = cfg["rows"]
    return {
        "R": {"a": rng.integers(0, 7, n), "k": rng.integers(0, 11, n)},
        "S": {"k": rng.integers(0, 11, n), "b": rng.integers(0, 5, n)},
    }
'''
DUMMY_METRIC = '''
def read(ctx):
    return 1000.0 * ctx.trace.window_s + ctx.completed
'''

DUMMY_KIND = '''
import loops


def make(mix, ctx):
    return Twice(mix, ctx)


class Twice(loops.ClosedLoop):
    """Each request is the query sent twice, back to back."""

    span_name = "twice"

    def __init__(self, mix, ctx):
        from repro.serve.session import Session

        super().__init__(mix)
        self.session, self.query, self.tables = Session(ctx.server), ctx.query, ctx.tables
        self.kept = []

    def request(self):
        return [self.session.query(self.query) for _ in range(2)]

    def keep(self, answer):
        if answer is not None and len(self.kept) < self.mix["check_sample"]:
            self.kept.extend(answer)

    def answers(self):
        return [(self.tables, a) for a in self.kept]

    def end_to_end(self):
        return {"pairs_per_s": len(self.latencies) / self.elapsed}
'''
DUMMY_AGGREGATE = '''
import numpy as np


def program(column):
    from repro.aggregates.semiring import Avg

    return Avg(column)


def reference(group, n, values):
    return np.bincount(group, weights=values, minlength=n) / np.bincount(group, minlength=n)
'''


def _digest(root: Path) -> dict[str, str]:
    return {
        str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts
    }


def test_new_config_traffic_and_metric_are_found_by_name(checkout, monkeypatch):
    bench_dir = checkout / "benchmarks" / "chip"
    before = _digest(bench_dir)
    # a configuration, a traffic mix and a per-layer metric, as files ...
    (bench_dir / "configs" / "dummy_chain.json").write_text(json.dumps({
        "name": "dummy_chain", "rows": 300,
        "query": {"over": ["R", "S"], "group_by": ["R.a", "S.b"]},
    }))
    (bench_dir / "configs" / "dummy_chain.py").write_text(DUMMY_CONFIG)
    (bench_dir / "traffic" / "dummy_mix.json").write_text(json.dumps({
        "kind": "query", "aggs": {"n": ["count"], "s": ["sum", "R.a"]},
        "warmup": 1, "check_sample": 3,
    }))
    (bench_dir / "metrics" / "dummy_metric.py").write_text(DUMMY_METRIC)
    # ... and entries
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "dummy_chain", "source": "none", "reduced": [], "why": "test",
        "file": "benchmarks/chip/configs/dummy_chain.json",
    })
    spec["workloads"].append({
        "name": "dummy_chain.dummy_mix", "config": "dummy_chain",
        "traffic": "dummy_mix", "chips": 1, "why": "test",
    })
    spec["end_to_end"][0]["workloads"].append("dummy_chain.dummy_mix")
    spec["per_layer"] = [{
        "name": "dummy_metric", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "test", "moves": "queries_per_s",
        "workloads": ["dummy_chain.dummy_mix"],
    }]
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before

    rc, line, err = run_cell(checkout, "dummy_chain.dummy_mix", seconds=0.3)
    assert rc == 0, err
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"queries_per_s", "setup_s"}
    assert list(line)[-1] == "checks"

    # the traced run reads the new metric; the CPU has no chip plane, so
    # the reduction is handed a synthetic chip trace in its place
    monkeypatch.setattr(tracing, "load", lambda _dir, **_: synthetic())
    rc, line, err = run_cell(checkout, "dummy_chain.dummy_mix", seconds=0.3, trace=True)
    assert rc == 0, err
    value = line["metrics"]["dummy_metric"]["value"]
    assert value == pytest.approx(1000.0 * 100e-9 + line["attempted"])
    assert line["device"]["window_s"] == pytest.approx(100e-9)
    assert line["breakdown"]["device_ops"][0][0] == "coo_spmm"


def test_run_cell_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run_cell.py"), "--workload", "tpch_chain.agg",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs 1 TPU chip" in proc.stderr


def test_run_exits_nonzero_in_a_checkout_without_the_program(tmp_path):
    root = make_checkout(tmp_path / "bare")
    (root / "src").unlink()
    rc, line, err = run_cell(root, "tiny_tpch.agg")
    assert rc != 0 and line is None
    assert "program is not in this checkout" in err
    shutil.rmtree(root)


def test_new_traffic_kind_and_aggregate_are_found_by_name(checkout):
    bench_dir = checkout / "benchmarks" / "chip"
    before = _digest(bench_dir)
    # a traffic kind, an aggregate kind and a mix that uses both, as files ...
    (bench_dir / "kinds" / "twice.py").write_text(DUMMY_KIND)
    (bench_dir / "aggregates" / "avg.py").write_text(DUMMY_AGGREGATE)
    (bench_dir / "traffic" / "mean_twice.json").write_text(json.dumps({
        "kind": "twice", "aggs": {"n": ["count"], "mean": ["avg", "lineitem.l_quantity"]},
        "warmup": 1, "check_sample": 4,
    }))
    # ... and entries: a cell and the end-to-end metric its kind reports
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "tiny_tpch.mean_twice", "config": "tiny_tpch",
        "traffic": "mean_twice", "chips": 1, "why": "test",
    })
    spec["end_to_end"].insert(0, {
        "name": "pairs_per_s", "unit": "pairs/s", "better": "higher", "bound": 0.05,
        "source": "host_clock", "workloads": ["tiny_tpch.mean_twice"],
    })
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before

    rc, line, err = run_cell(checkout, "tiny_tpch.mean_twice", seconds=0.3)
    assert rc == 0, err
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["metrics"]) == {"pairs_per_s", "setup_s"}
    assert line["checks"]["wrong_groups"]["value"] == 0


def test_an_unknown_traffic_kind_is_refused(checkout):
    (checkout / "benchmarks" / "chip" / "traffic" / "agg.json").write_text(json.dumps({
        "kind": "nowhere", "aggs": {"n": ["count"]}, "warmup": 1, "check_sample": 1,
    }))
    rc, line, err = run_cell(checkout, "tiny_tpch.agg")
    assert rc != 0 and line is None
    assert "no traffic kind" in err
