#!/usr/bin/env python3
"""The control of the comparison: the reference in the program's place,
in bfloat16, must come out as not correct.

    python3 benchmarks/chip/control.py --workload tpch_chain.agg \\
        --seeds 11 12 13 --seconds 5

For each seed this drives a whole run of the cell (data, server, plan,
warm-up, window, check) in one process, with ``Session.query`` answering
every query from the plain reference whose aggregates are rounded to
bfloat16: the nearest precision below the float32 the configurations
state.  Each run prints its result line; the control has done its job
when every line reads ``"correct": false``.  The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np


class Answer:
    """An answer as the check reads it: ``group_names`` and ``column``."""

    def __init__(self, keys: np.ndarray, values: dict[str, np.ndarray]):
        self.group_names = tuple(f"g{i}" for i in range(keys.shape[1]))
        self._cols = {g: keys[:, i] for i, g in enumerate(self.group_names)}
        self._cols.update(values)

    def column(self, name: str) -> np.ndarray:
        return self._cols[name]


def bf16(values: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return values.astype(ml_dtypes.bfloat16).astype(np.float64)


def reference_in_bf16(spec, aggs: dict, tables: dict) -> Answer:
    import reference

    keys, vals = reference.answer(spec.cfg["query"], aggs, tables, spec.bench_dir)
    return Answer(keys, {a: bf16(v) for a, v in vals.items()})


def install(spec) -> None:
    """Make every ``Session.query`` answer from the bf16 reference."""
    from repro.serve.session import Session

    cache: dict[int, Answer] = {}

    def query(self, q):
        db = self.server.db
        if id(db) not in cache:
            tables = {n: dict(r.columns) for n, r in db.relations.items()}
            cache[id(db)] = reference_in_bf16(spec, spec.mix["aggs"], tables)
        return cache[id(db)]

    Session.query = query


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness

    spec = harness.load_cell(args.workload)
    harness.import_program(harness.ROOT)
    install(spec)
    rc = 0
    for seed in args.seeds:
        rc |= harness.run(
            args.workload, seed, args.seconds, False, t_start=time.perf_counter()
        )
    return rc


if __name__ == "__main__":
    sys.exit(main())
