"""End-to-end smoke of the JOIN-AGG main path at a deployment's shape.

The deployment is the paper's TPC-H chain (Table VI, built by
:func:`repro.data.queries.tpch_like`): supplier ⋈ lineitem ⋈ orders ⋈
customer, grouped by (supplier, customer zipcode), with a seeded
``lineitem.qty`` measure in 1..50.  The databases are registered in
:class:`~repro.serve.server.JoinAggServer` instances and queried through
:class:`~repro.serve.session.Session` on the ``jax`` engine:

1. ``count``      — COUNT grouped by (supplier, zipcode), at ``n_main``;
2. ``count_sum``  — COUNT + SUM(qty), at ``n_main``;
3. ``minmax``     — MIN(qty) + MAX(qty), at ``n_small``;
4. ``fused``      — query 1 with ``.fused(True)``, at ``n_small``;
5. ``count`` again, which must hit the plan cache.

Every answer must equal the ``tensor`` engine's (numpy, int64/float64)
on the same data; sums stay below 2**24, where the jax engine's f32
accumulation is exact.  Each plan must take the sparse path, and each
query must dispatch the hop kernels it is expected to.

``chip_smoke.py`` at the repository root runs :func:`run` on a TPU with
``on_chip=True``, which adds the checks that only a chip can pass: the
kernels resolve to Mosaic-compiled Pallas (no interpreter, no jnp
reference kernels) and a hop kernel lowers to a ``tpu_custom_call``.
The tests run the same function at a tiny size on the CPU backend.
"""
from __future__ import annotations

import time
from dataclasses import replace
from typing import Callable

import numpy as np

from repro.aggregates.semiring import Count, Max, Min, Sum
from repro.api.builder import Q
from repro.data.queries import tpch_like
from repro.relational.relation import Database

#: lineitem rows at TPC-H scale factor 1 (TPC-H spec v3, clause 4.2.5)
SF1_LINEITEM = 6_001_215

#: the jax engine accumulates in f32: integer results are exact below this
F32_EXACT = 2**24

class SmokeFailure(RuntimeError):
    """A smoke check failed: wrong answer, wrong path or wrong kernels."""


def smoke_database(n: int, seed: int = 0) -> Database:
    """``tpch_like(n, seed)`` plus a seeded integer ``qty`` in 1..50 on
    lineitem (small enough that every partial sum stays below 2**24)."""
    db, _ = tpch_like(n, seed=seed)
    rng = np.random.default_rng(seed + 1)
    li = db["lineitem"]
    db.add(li.with_column("qty", rng.integers(1, 51, li.num_rows)))
    return db


_RELATIONS = ("supplier", "lineitem", "orders", "customer")


def smoke_queries() -> dict[str, Q]:
    base = (
        Q.over(*_RELATIONS)
        .group_by("supplier.sname", "customer.zipcode")
        .engine("jax")
    )
    return {
        "count": base.agg(n=Count()),
        "count_sum": base.agg(n=Count(), qty=Sum("lineitem.qty")),
        "minmax": base.agg(lo=Min("lineitem.qty"), hi=Max("lineitem.qty")),
        "fused": base.agg(n=Count()).fused(True),
    }


#: dispatch stages each query must record (``ops.dispatch_counts``)
_EXPECTED_STAGES = {
    "count": {"spmm", "scatter"},
    "count_sum": {"spmm", "scatter"},
    "minmax": {"scatter"},
    "fused": {"fused"},
}


def _blocks(kernel: str) -> dict[str, int]:
    """The block sizes a hop kernel's wrapper takes when given none."""
    import inspect

    from repro.kernels import ops

    params = inspect.signature(getattr(ops, kernel)).parameters
    return {k: p.default for k, p in params.items() if k.startswith("block_")}


def hop_table(hops: list[dict]) -> list[dict]:
    """The hop kernel calls one query dispatched (``ops.dispatched_hops``)
    with each call's grid cells at the blocks it ran with, and two host
    estimates: the f32 bytes of its child messages and of its
    ``(segments, width)`` output, before any device padding.  The cells
    are those of the full grid, except ``segment_reduce``'s: the length
    of the schedule that its CSR-sorted keys take (a ``coo_spmm`` on
    row-sorted edges also runs fewer)."""
    from repro.kernels import ops

    cdiv = lambda a, b: -(-a // b)  # noqa: E731
    rows = []
    for h in hops:
        b = {**_blocks(h["kernel"]), **h}
        segs, edges = h["segments"], h["edges"]
        if h["kernel"] == "coo_spmm":
            grid = (
                cdiv(segs, b["block_m"]) * cdiv(edges, b["block_e"])
                * cdiv(h["child_rows"], b["block_k"])
            )
        elif h["kernel"] == "fused_hop":
            # the wrapper runs a zero-edge hop as one padding tile
            grid = cdiv(segs, b["block_s"]) * max(cdiv(edges, b["block_e"]), 1)
        elif h["kernel"] == "segment_reduce":
            grid = ops.pair_grid_len(edges, segs, b["block_s"], b["block_n"])
        else:  # segment_sum
            grid = cdiv(segs, b["block_s"]) * cdiv(edges, b["block_n"])
        rows.append(
            {
                "kernel": h["kernel"],
                "edges": edges,
                "segments": segs,
                "child_rows": h["child_rows"],
                "width": h["width"],
                "grid_cells": grid,
                "msg_bytes_est": 4 * h["child_rows"] * h["width"],
                "out_bytes_est": 4 * segs * h["width"],
            }
        )
    return rows


def _sorted_columns(res) -> dict[str, np.ndarray]:
    cols = {g: np.asarray(res.column(g)) for g in res.group_names}
    order = np.lexsort([cols[g] for g in reversed(res.group_names)])
    out = {g: c[order] for g, c in cols.items()}
    for a in res.agg_names:
        out[a] = np.asarray(res.column(a))[order]
    return out


def check_equal(name: str, got, want) -> None:
    """The jax answer must equal the tensor engine's: same groups, equal
    COUNT/MIN/MAX, and SUMs equal where f32 is exact (all of them here:
    a larger sum fails the check rather than being compared loosely)."""
    if got.num_rows != want.num_rows:
        raise SmokeFailure(
            f"{name}: {got.num_rows} groups from jax, {want.num_rows} "
            "from the tensor engine"
        )
    g, w = _sorted_columns(got), _sorted_columns(want)
    for col in want.group_names:
        if not np.array_equal(g[col], w[col]):
            raise SmokeFailure(f"{name}: group column {col!r} differs")
    for col in want.agg_names:
        ref = w[col].astype(np.float64)
        if np.abs(ref).max(initial=0.0) >= F32_EXACT:
            raise SmokeFailure(
                f"{name}: {col!r} reaches 2**24, past f32-exact integers"
            )
        bad = np.flatnonzero(g[col].astype(np.float64) != ref)
        if bad.size:
            i = bad[0]
            raise SmokeFailure(
                f"{name}: {col!r} differs in {bad.size} group(s), e.g. "
                f"{g[col][i]!r} vs {ref[i]!r}"
            )


def _check_chip_kernels(log: Callable[[str], None], hop: dict) -> None:
    """The kernels must resolve to Mosaic-compiled Pallas on this
    backend, and a dispatched ``coo_spmm`` hop must lower to a
    ``tpu_custom_call``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    if ops.resolve_interpret(None) or ops.use_ref_kernels(None):
        raise SmokeFailure(
            "auto mode resolves to the interpreter or the reference "
            f"kernels on backend {jax.default_backend()!r}"
        )
    e, i32 = hop["edges"], jnp.int32
    # the pair-grid form, which the engine dispatches for its row-sorted hops
    text = ops.coo_spmm_pairs.lower(
        jax.ShapeDtypeStruct((e,), i32),
        jax.ShapeDtypeStruct((e,), i32),
        jax.ShapeDtypeStruct((e,), jnp.float32),
        jax.ShapeDtypeStruct((hop["child_rows"], hop["width"]), jnp.float32),
        jax.ShapeDtypeStruct((2, ops.pair_grid_len(e, hop["segments"])), i32),
        num_rows=hop["segments"],
    ).as_text()
    if "tpu_custom_call" not in text:
        raise SmokeFailure("coo_spmm did not lower to a tpu_custom_call")
    log(
        f"kernels: Mosaic-compiled; coo_spmm at a dispatched hop "
        f"({e} edges) lowers to tpu_custom_call"
    )


def run(
    n_main: int,
    n_small: int,
    *,
    on_chip: bool,
    seed: int = 0,
    log: Callable[[str], None] = print,
) -> dict:
    """Run the five smoke queries and their checks; raise
    :class:`SmokeFailure` on the first failed check.  Returns the
    printed report as a dict."""
    import jax
    from jax import monitoring

    from repro.kernels import ops
    from repro.serve.server import JoinAggServer
    from repro.serve.session import Session

    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs

    queries = smoke_queries()
    scale = {
        "count": "main", "count_sum": "main", "minmax": "small", "fused": "small"
    }
    sizes = {"main": n_main, "small": n_small}
    dbs = {s: smoke_database(n, seed) for s, n in sizes.items()}
    report: dict = {"n_main": n_main, "n_small": n_small, "queries": {}}
    log(f"n_main={n_main} n_small={n_small} (lineitem rows; SF 1 has {SF1_LINEITEM})")
    log(f"n_main / SF1 = {n_main / SF1_LINEITEM:.6f}")
    for s, db in dbs.items():
        rows = {r: db[r].num_rows for r in _RELATIONS}
        log(f"rows at n_{s}: {rows}")

    servers = {s: JoinAggServer(db, workers=2) for s, db in dbs.items()}
    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        sessions = {s: Session(srv) for s, srv in servers.items()}

        def timed(name: str):
            ops.reset_dispatch_counts()
            t0 = time.perf_counter()
            res = sessions[scale[name]].query(queries[name])
            return res, time.perf_counter() - t0, ops.dispatch_counts()

        wants = {}
        for name, q in queries.items():
            db = dbs[scale[name]]
            plan = q.plan(db)
            text = plan.explain()
            if "jax path: sparse" not in text and "pinned: sparse" not in text:
                raise SmokeFailure(f"{name}: plan does not take the sparse path")
            before = compile_s[0]
            res, wall, counts = timed(name)
            hops = hop_table(ops.dispatched_hops())
            for h in hops:
                log(f"hop {name}@n_{scale[name]} {h}")
            log(f"{name}: {sum(h['grid_cells'] for h in hops)} grid cells in all")
            if on_chip and name == "count":
                spmm = [h for h in hops if h["kernel"] == "coo_spmm"]
                _check_chip_kernels(log, max(spmm, key=lambda h: h["grid_cells"]))
            missing = _EXPECTED_STAGES[name] - set(counts)
            if missing:
                raise SmokeFailure(
                    f"{name}: no {sorted(missing)} dispatches ({counts})"
                )
            # the oracle: the same query on the numpy tensor engine
            want = wants[name] = replace(
                q, engine_name="tensor", fused_opt=None
            ).plan(db).execute()
            check_equal(name, res, want)
            report["queries"][name] = {
                "groups": res.num_rows,
                "cold_s": wall,
                "compile_s": compile_s[0] - before,
                "dispatches": counts,
            }
            log(
                f"{name}: {res.num_rows} groups equal the tensor engine; cold "
                f"{wall:.3f} s of which backend compile {compile_s[0] - before:.3f} s; "
                f"dispatches {counts}"
            )

        srv = servers["main"]
        hits = srv.stats()["plan_cache"]["hits"]
        res, wall, _ = timed("count")
        if srv.stats()["plan_cache"]["hits"] != hits + 1:
            raise SmokeFailure("repeated count query missed the plan cache")
        check_equal("count (repeat)", res, wants["count"])
        report["queries"]["count"]["warm_s"] = wall
        log(f"count (repeat): plan-cache hit; warm {wall:.3f} s")
        for name in ("count_sum", "minmax", "fused"):
            res, wall, _ = timed(name)
            check_equal(f"{name} (repeat)", res, wants[name])
            report["queries"][name]["warm_s"] = wall
            log(f"{name} (repeat): warm {wall:.3f} s")
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
        for srv in servers.values():
            srv.close()

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    report["device_kind"] = dev.device_kind
    log(
        f"device {dev.platform} / {dev.device_kind}: peak_bytes_in_use="
        f"{report['peak_bytes_in_use'] if stats else 'not reported'}"
    )
    log("timings above are smoke wall times, not benchmark results")
    return report
