#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run_cell.py --workload tpch_chain.agg \\
        --seed 7 --seconds 40 --trace 0

The cell is looked up in ``BENCHMARK.json`` at the checkout's root.  The
run generates the cell's data from ``--seed``, registers it in a
``JoinAggServer``, plans and warms up the cell's query (set-up), drives
the cell's traffic for ``--seconds`` (the window; ``--trace 1`` records
a profiler trace of it), compares the window's answers with the plain
reference, and prints one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, ``breakdown`` (traced
runs) and ``checks`` (each number compared, with its limit).

It exits non-zero and prints no result line when JAX finds no TPU, or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True, help="a workloads name in BENCHMARK.json")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True, help="length of the window")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import harness

    return harness.run(
        args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START
    )


if __name__ == "__main__":
    sys.exit(main())
