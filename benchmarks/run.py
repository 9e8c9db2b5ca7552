"""Benchmark harness: one function per paper table.

Prints ``name,us_per_call,derived`` CSV and always writes the same
records as machine-readable ``BENCH_<scale>.json`` (override the path
with ``--json-out``) so CI can archive a perf datapoint per PR.
``--scale paper`` uses the paper's 500k rows/relation (slow on 1 CPU);
``tiny`` is the CI smoke config; the default ``small`` is
container-friendly and preserves every selectivity ratio.
"""
from __future__ import annotations

import argparse

from benchmarks import common, tables
from repro.launch.cache import enable_compile_cache

TABLES = [
    "1", "2", "3", "4", "5", "6", "7", "8", "9", "10", "11", "12", "13", "14",
    "15",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--scale", choices=["tiny", "small", "medium", "paper"], default="small"
    )
    ap.add_argument("--table", choices=TABLES, default=None)
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument(
        "--json-out", default=None,
        help="path for the JSON record dump (default BENCH_<scale>.json)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    n_self = {"tiny": 2000, "small": 20000, "medium": 100000, "paper": 500000}[args.scale]
    n_chain = {"tiny": 1500, "small": 8000, "medium": 40000, "paper": 500000}[args.scale]
    n_branch = {"tiny": 1500, "small": 6000, "medium": 30000, "paper": 500000}[args.scale]
    n_real = {"tiny": 2000, "small": 20000, "medium": 100000, "paper": 500000}[args.scale]
    n_cyclic = {"tiny": 1000, "small": 4000, "medium": 30000, "paper": 200000}[args.scale]
    verify = not args.no_verify and args.scale in ("tiny", "small")

    print("name,us_per_call,derived")
    run_all = args.table is None
    if run_all or args.table == "1":
        tables.table1_load(n_chain)
    if run_all or args.table == "3":
        tables.table3_selfjoin(n_self, verify)
    if run_all or args.table == "4":
        tables.table4_chain(n_chain, verify)
    if run_all or args.table == "5":
        tables.table5_branching(n_branch, verify)
    if run_all or args.table == "6":
        tables.table6_real(n_real, verify)
    if run_all or args.table == "7":
        tables.table7_cyclic(n_cyclic, verify)
    if run_all or args.table == "8":
        tables.table8_incremental(n_real, verify)
    if run_all or args.table == "9":
        tables.table9_multiagg(n_chain, verify)
    if run_all or args.table == "10":
        tables.table10_sparse(n_chain, verify)
    if run_all or args.table == "11":
        tables.table11_distributed(n_chain, verify)
    if run_all or args.table == "12":
        tables.table12_serving(n_chain, verify)
    if run_all or args.table == "13":
        tables.table13_planner(n_real, verify)
    if run_all or args.table == "14":
        tables.table14_storage(n_chain, verify)
    if run_all or args.table == "15":
        tables.table15_fused(n_chain, verify)
    if run_all or args.table == "2":
        tables.table2_memory(n_branch)

    out = args.json_out or f"BENCH_{args.scale}.json"
    common.write_json(
        out, scale=args.scale, table=args.table or "all", verify=verify
    )


if __name__ == "__main__":
    main()
