"""Concurrent JOIN-AGG server: oracle equality, warm cache, fusion,
TCP protocol (DESIGN.md §9, serve/server.py)."""
import threading

import numpy as np
import pytest

from repro.aggregates.semiring import Avg, Count, Max, Min, Sum
from repro.api.builder import Q
from repro.api.plan import compile_plan
from repro.data.synth import chain
from repro.relational.relation import Relation
from repro.serve.server import JoinAggServer, serve_tcp
from repro.serve.session import Session, connect


@pytest.fixture(scope="module")
def db():
    d, _ = chain("C1", 300, seed=0)
    rng = np.random.default_rng(1)
    r2 = d["R2"]
    d.add(r2.with_column("w", rng.integers(1, 50, r2.num_rows)))
    return d


def base_q():
    return Q.over("R1", "R2", "R3", "R4")


QUERIES = {
    "count": base_q().group_by("R1.g1").agg(n=Count()),
    "sum": base_q().group_by("R1.g1").agg(total=Sum("R2.w")),
    "multi": base_q().group_by("R1.g1").agg(
        n=Count(), total=Sum("R2.w"), mean=Avg("R2.w")
    ),
    "minmax": base_q().group_by("R4.g2").agg(lo=Min("R2.w"), hi=Max("R2.w")),
    "filtered": base_q().where("R2", "w", ">", 25).group_by("R1.g1").agg(
        n=Count()
    ),
}


def as_rows(res):
    return {n: res.to_dict(n) for n in res.agg_names}


def test_concurrent_mixed_queries_match_oracles(db):
    oracles = {k: as_rows(compile_plan(q, db).execute())
               for k, q in QUERIES.items()}
    failures = []
    with JoinAggServer(db, workers=6, fusion_window=0.002) as srv:
        def client(i):
            names = list(QUERIES)
            for j in range(6):
                name = names[(i + j) % len(names)]
                got = as_rows(srv.query(QUERIES[name]))
                if got != oracles[name]:
                    failures.append((i, name))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not failures


def test_warm_cache_skips_prepare_and_compile(db):
    q = QUERIES["count"]
    with JoinAggServer(db, workers=2, fuse=False) as srv:
        r1 = srv.query(q)
        stats1 = srv.plan_cache.stats.snapshot()
        r2 = srv.query(q)
        stats2 = srv.plan_cache.stats.snapshot()
    assert as_rows(r1) == as_rows(r2)
    assert stats1["compiles"] == 1
    assert stats2["compiles"] == 1  # the repeat did NOT compile
    assert stats2["hits"] == stats1["hits"] + 1


def test_identical_shape_burst_fuses_to_one_execution(db):
    q = QUERIES["sum"]
    oracle = as_rows(compile_plan(q, db).execute())
    with JoinAggServer(db, workers=4, fusion_window=0.25) as srv:
        futs = [srv.submit(q) for _ in range(6)]
        results = [f.result() for f in futs]
        fusion = srv._batcher.stats.snapshot()
        compiles = srv.plan_cache.stats.compiles
    for r in results:
        assert as_rows(r) == oracle
    assert fusion["shared_identical"] == 6
    assert fusion["batches"] == 1
    assert compiles == 1


def test_channel_merge_demuxes_per_client(db):
    qa = base_q().group_by("R1.g1").agg(n=Count())
    qb = base_q().group_by("R1.g1").agg(total=Sum("R2.w"), lo=Min("R2.w"))
    oa = as_rows(compile_plan(qa, db).execute())
    ob = as_rows(compile_plan(qb, db).execute())
    with JoinAggServer(db, workers=4, fusion_window=0.25) as srv:
        fa, fb = srv.submit(qa), srv.submit(qb)
        ra, rb = fa.result(), fb.result()
        fusion = srv._batcher.stats.snapshot()
    assert ra.agg_names == ("n",) and as_rows(ra) == oa
    assert set(rb.agg_names) == {"total", "lo"} and as_rows(rb) == ob
    assert fusion["merged_channels"] == 2 and fusion["batches"] == 1


def test_uncacheable_query_runs_solo_and_correct(db):
    q = base_q().where("R2", lambda c: c["w"] > 25).group_by("R1.g1").agg(
        n=Count()
    )
    oracle = as_rows(compile_plan(q, db).execute())
    with JoinAggServer(db, workers=2) as srv:
        got = as_rows(srv.query(q))
        stats = srv.plan_cache.stats.snapshot()
        fusion = srv._batcher.stats.snapshot()
    assert got == oracle
    assert stats["bypasses"] == 1 and fusion["solo"] == 1


def test_register_bumps_generation_and_serves_new_data(db):
    q = QUERIES["count"]
    with JoinAggServer(db, workers=2, fuse=False) as srv:
        before = srv.query(q)
        assert srv.plan_cache.stats.compiles == 1
        # double R1: every group count doubles (raw column mappings are
        # the deprecated eager-copy spelling — pass a Relation)
        r1 = srv.db["R1"]
        doubled = Relation(
            "R1", {a: np.concatenate([c, c]) for a, c in r1.columns.items()}
        )
        gen = srv.register("R1", doubled)
        after = srv.query(q)
        assert srv.plan_cache.stats.compiles == 2  # old plan unreachable
    assert gen == 1
    want = {k: 2 * v for k, v in before.to_dict("n").items()}
    assert after.to_dict("n") == want


def test_jax_engine_queries_served(db):
    q = base_q().group_by("R1.g1").agg(n=Count()).engine("jax")
    oracle = as_rows(compile_plan(q, db).execute())
    with JoinAggServer(db, workers=2) as srv:
        assert as_rows(srv.query(q)) == oracle


def test_session_prepared_statement(db):
    with JoinAggServer(db, workers=2, fuse=False) as srv:
        sess = Session(srv)
        stmt = sess.prepare(QUERIES["count"])
        r1, r2 = stmt.execute(), stmt.execute()
        assert as_rows(r1) == as_rows(r2)
        assert sess.stats.queries == 2
        assert srv.plan_cache.stats.compiles == 1


def test_tcp_roundtrip_register_query_and_errors(db):
    q = QUERIES["filtered"]
    oracle = as_rows(compile_plan(q, db).execute())
    with JoinAggServer(db, workers=2) as srv:
        tcp, _ = serve_tcp(srv)
        host, port = tcp.server_address
        try:
            with connect(host, port) as c:
                assert c.ping()
                res = c.query({
                    "relations": ["R1", "R2", "R3", "R4"],
                    "where": [["R2", "w", ">", 25]],
                    "group_by": ["R1.g1"],
                    "aggs": {"n": {"kind": "count"}},
                })
                assert as_rows(res) == oracle
                with pytest.raises(RuntimeError, match="unknown op"):
                    c.call({"op": "frobnicate"})
                with pytest.raises(RuntimeError):  # bad query still answers
                    c.query({"relations": ["NoSuch"], "group_by": []})
                assert c.ping()  # connection survived both errors
                stats = c.server_stats()
                assert stats["plan_cache"]["compiles"] >= 1
        finally:
            tcp.shutdown()


def test_stats_carry_the_program_counters(db):
    q = QUERIES["count"]
    with JoinAggServer(db, workers=2) as srv:
        before = srv.stats()["counters"].get("serve.queries", 0)
        srv.query(q)
        local = srv.stats()["counters"]
        assert local["serve.queries"] == before + 1
        assert local["plan.builds"] >= 1 and local["serve.queue_wait_s"] >= 0
        tcp, _ = serve_tcp(srv)
        try:
            with connect(*tcp.server_address) as c:
                remote = c.server_stats()["counters"]
        finally:
            tcp.shutdown()
    assert remote["serve.queries"] == local["serve.queries"]
    assert set(local) <= set(remote)


def test_closed_server_rejects_queries(db):
    srv = JoinAggServer(db, workers=2)
    srv.close()
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(QUERIES["count"])
