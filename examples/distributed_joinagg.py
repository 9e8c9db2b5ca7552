"""Distributed JOIN-AGG: the paper's per-source-node outer loop sharded
over a device mesh (DESIGN.md §8).  The root group attribute's
grouped-CSR row ranges are partitioned across the mesh's ``data`` axis;
every decomposition-tree hop runs device-locally under ``shard_map`` and
the per-shard group partials are combined with one final all-gather — no
dense relation tensor is ever built, on any device.

Runs on 8 virtual CPU devices; the same code path lowers onto any
device mesh.

    PYTHONPATH=src python examples/distributed_joinagg.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402

from repro.api import Q  # noqa: E402
from repro.core import distributed  # noqa: E402
from repro.core.prepare import prepare  # noqa: E402
from repro.data import synth  # noqa: E402
from repro.relational.oracle import oracle_joinagg  # noqa: E402

db, query = synth.chain("C2", n=20_000, seed=3)
prep = prepare(query, db)
mesh = jax.make_mesh((4, 2), ("data", "model"))
print(f"devices: {jax.devices()}")

t0 = time.perf_counter()
got = distributed.run(prep, mesh)
t1 = time.perf_counter()
print(f"distributed JOIN-AGG on {mesh.shape}: {len(got)} groups in {t1 - t0:.3f}s")

want = oracle_joinagg(query, db)
assert got == want, "distributed result mismatch"
print("matches materialized-join oracle ✓")

# the planner path over the same mesh, and the explain() lines the perf
# gate reads (shard axis + per-device bytes)
plan = Q.from_query(query).engine("jax").mesh(mesh).plan(db)
print()
print(plan.explain())
res = plan.execute()
assert res.to_dict() == want
print(f"planner bundle over the mesh: {res.num_rows} groups ✓")

prog = distributed.build_distributed_program(prep, (None,), mesh)
print(
    f"per-device working set: {prog.per_device_bytes() / 1e3:.1f} kB "
    f"across {prog.num_shards} shards of {prog.attr!r} (tile {prog.tile})"
)

lowered = distributed.lower_distributed(prep, mesh)
compiled = lowered.compile()
text = compiled.as_text()
colls = [ln.split("=")[0].strip() for ln in text.splitlines()
         if any(c in ln for c in ("all-reduce(", "all-gather(", "reduce-scatter("))]
cost = compiled.cost_analysis()
if isinstance(cost, list):  # older jax returns one dict per partition
    cost = cost[0] if cost else {}
print(f"partitioned HLO uses {len(colls)} collective ops; "
      f"per-device flops {cost.get('flops', 0):.3e}")
