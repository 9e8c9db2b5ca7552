"""Spans and counters of the query path (``repro.obs``), read back from a
profiler trace of a served query on the CPU."""
from __future__ import annotations

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.aggregates.semiring import Count, Max, Min, Sum
from repro.api.builder import Q
from repro.api.plan import compile_plan
from repro.core import jax_engine
from repro.data.queries import tpch_like
from repro.kernels import ops
from repro.serve.server import JoinAggServer
from repro.serve.session import Session

VOCABULARY = {
    "joinagg.plan", "joinagg.walk", "joinagg.hop", "joinagg.h2d",
    "joinagg.launch", "joinagg.wait", "joinagg.d2h", "joinagg.assemble",
}
HARNESS_NAMES = {"window", "query", "plan"}


@pytest.fixture(scope="module")
def db():
    d, _ = tpch_like(2000, seed=3)
    li = d["lineitem"]
    rng = np.random.default_rng(4)
    d.add(li.with_column("qty", rng.integers(1, 51, li.num_rows)))
    return d


def chain_query(db, **aggs):
    """The TPC-H chain on the sparse path, unstreamed: the budget lies
    between the largest message and the dense program."""
    q = (
        Q.over("supplier", "lineitem", "orders", "customer")
        .group_by("supplier.sname", "customer.zipcode")
        .agg(**aggs)
        .engine("jax")
    )
    return q.memory_budget(compile_plan(q, db).message_peak)


def host_events(trace_dir) -> list[tuple[str, int, float, float, dict]]:
    """``(name, thread line, start, end, args)`` of every host event (the
    args of the program's spans only)."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                args = dict(e.stats) if e.name.startswith("joinagg.") else {}
                out.append((e.name, li, e.start_ns, e.start_ns + e.duration_ns, args))
    return out


def inside(inner, outers) -> bool:
    _, li, s, e, _ = inner
    return any(o[1] == li and o[2] <= s and e <= o[3] for o in outers)


def test_query_spans_nest_and_name_each_layer(db, tmp_path):
    q = chain_query(db, n=Count(), total=Sum("lineitem.qty"),
                    lo=Min("lineitem.qty"), hi=Max("lineitem.qty"))
    with JoinAggServer(db, workers=2) as srv:
        sess = Session(srv)
        sess.query(q)  # plan and warm outside the trace
        ops.reset_dispatch_counts()
        jax.profiler.start_trace(str(tmp_path))
        try:
            sess.query(q)
        finally:
            jax.profiler.stop_trace()
    events = host_events(tmp_path)
    ours = [ev for ev in events if ev[0].startswith("joinagg.")]
    by_name = {n: [ev for ev in ours if ev[0] == n] for n in VOCABULARY}
    assert {ev[0] for ev in ours} == VOCABULARY
    assert not HARNESS_NAMES & {ev[0] for ev in events}

    walks, hops = by_name["joinagg.walk"], by_name["joinagg.hop"]
    assert sorted(ev[4]["kind"] for ev in walks) == ["max", "min", "sum"]
    # four relations, one hop each per walk
    assert len(hops) == 4 * len(walks)
    assert all(inside(h, walks) for h in hops)
    assert {h[4]["rel"] for h in hops} == {"supplier", "lineitem", "orders", "customer"}
    # each hop names the kernel it dispatched
    assert sorted(h[4]["kernel"] for h in hops) == sorted(
        h["kernel"] for h in ops.dispatched_hops()
    )
    assert {h[4]["kernel"] for h in hops} == {"coo_spmm", "segment_sum", "segment_reduce"}
    assert all(h[4]["edges"] > 0 for h in hops)
    for name in ("joinagg.h2d", "joinagg.launch", "joinagg.wait", "joinagg.d2h"):
        assert by_name[name] and all(inside(ev, hops) for ev in by_name[name]), name
    # a hop's span covers its own work only: the children's hops have
    # closed before it opens, so no hop holds another
    assert not any(inside(a, [b]) for a in hops for b in hops if a is not b)


def test_serve_and_plan_counters(db):
    q = chain_query(db, n=Count())
    with JoinAggServer(db, workers=2) as srv:
        sess = Session(srv)
        c0 = obs.counters()
        sess.query(q)
        c1 = obs.counters()
        sess.query(q)
        sess.query(q)
        c2 = obs.counters()

    def delta(a, b, name):
        return b.get(name, 0) - a.get(name, 0)

    assert delta(c0, c1, "serve.queries") == 1
    assert delta(c1, c2, "serve.queries") == 2
    assert delta(c0, c1, "plan.builds") == 1  # a miss builds
    assert delta(c1, c2, "plan.builds") == 0  # hits do not
    assert delta(c0, c1, "plan.build_s") > 0
    assert delta(c0, c2, "serve.queue_wait_s") >= 0
    assert "serve.queue_wait_s" in c2


def test_a_fused_burst_counts_every_query_and_its_wait(db):
    q = chain_query(db, n=Count())
    with JoinAggServer(db, workers=2, fusion_window=0.25) as srv:
        srv.query(q)  # plan outside the burst
        c0 = obs.counters()
        futs = [srv.submit(q) for _ in range(5)]
        for f in futs:
            f.result(timeout=60)
        c1 = obs.counters()
        assert srv._batcher.stats.snapshot()["shared_identical"] == 5
    assert c1["serve.queries"] - c0["serve.queries"] == 5
    # each of the five waited out most of the fusion window
    assert c1["serve.queue_wait_s"] - c0["serve.queue_wait_s"] >= 5 * 0.2


class _UploadSpy:
    """``jnp`` as the engine module sees it, with ``asarray`` recording
    the bytes of every host array handed to it."""

    def __init__(self):
        self.nbytes = 0

    def asarray(self, a, *args, **kwargs):
        self.nbytes += np.asarray(a).nbytes
        return jax.numpy.asarray(a, *args, **kwargs)

    def __getattr__(self, name):
        return getattr(jax.numpy, name)


def test_transfer_bytes_count_every_upload(db, monkeypatch):
    q = chain_query(db, n=Count(), total=Sum("lineitem.qty"))
    plan = compile_plan(q, db)
    plan.execute()  # compile outside the count
    spy = _UploadSpy()
    monkeypatch.setattr(jax_engine, "jnp", spy)
    c0 = obs.counters()
    res = plan.execute()
    c1 = obs.counters()
    up = c1["transfer.h2d_bytes"] - c0.get("transfer.h2d_bytes", 0)
    down = c1["transfer.d2h_bytes"] - c0.get("transfer.d2h_bytes", 0)
    assert spy.nbytes > 0 and up == spy.nbytes
    # at least the root's (groups × channels) f32 message comes back
    assert down >= 4 * 2 * len(res.relation.columns["n"])


def test_dispatch_accounting_reads_as_before():
    obs.count("serve.queries", 0)
    ops.reset_dispatch_counts()
    assert ops.dispatch_counts() == {} and ops.dispatched_hops() == []
    hop = {"kernel": "coo_spmm", "edges": 1024}
    ops.record_dispatch("spmm", hop=hop)
    ops.record_dispatch("gather", 3)
    ops.record_dispatch("spmm")
    assert ops.dispatch_counts() == {"spmm": 2, "gather": 3}
    assert ops.dispatched_hops() == [hop]
    assert obs.counters()["dispatch.spmm"] == 2
    ops.reset_dispatch_counts()
    assert ops.dispatch_counts() == {} and ops.dispatched_hops() == []
    # only the dispatch view resets; the rest of the registry stands
    assert "serve.queries" in obs.counters()


def test_sparse_query_dispatches_one_hop_kernel_per_relation(db):
    plan = compile_plan(chain_query(db, n=Count()), db)
    plan.execute()
    ops.reset_dispatch_counts()
    plan.execute()
    counts = ops.dispatch_counts()
    hops = ops.dispatched_hops()
    assert len(hops) == 4
    assert counts.get("spmm", 0) + counts.get("scatter", 0) == 4
    assert counts.get("spmm", 0) == sum(h["kernel"] == "coo_spmm" for h in hops)


def test_every_general_and_minmax_hop_forms_its_product_on_the_device(db):
    """``hop.product_edges_device`` counts the edges of every hop that
    forms a per-edge product (the COUNT + SUM walk's leaf and
    channel-weighted hops, every hop of a MIN or MAX walk);
    ``hop.product_edges_host`` counts none."""
    from repro.core.prepare import prepare
    from repro.core.query import JoinAggQuery

    q = JoinAggQuery(
        ("supplier", "lineitem", "orders", "customer"),
        (("supplier", "sname"), ("customer", "zipcode")),
        Sum("lineitem", "qty"),
    )
    prep = prepare(q, db)
    prog = jax_engine.build_sparse_program(prep, (None, "lineitem"))
    eng = jax_engine._KernelChannelEngine(
        prep, 2, jax_engine.channel_weight_matrices(prep.encoded, (None, "lineitem"))
    )
    edges = {rel: er.num_rows for rel, er in prep.encoded.items()}
    general = [rel for rel in edges if eng._hop_kernel(rel) == "segment_sum"]
    assert "lineitem" in general and len(general) < len(edges)
    obs.reset("hop.")
    prog.run_channels()
    prog.run_minmax("min", "lineitem")
    prog.run_minmax("max", "lineitem")
    c = obs.counters()
    assert c["hop.product_edges_device"] == (
        sum(edges[r] for r in general) + 2 * sum(edges.values())
    )
    assert "hop.product_edges_host" not in c
