"""Sparse-first jax execution (DESIGN.md §7) vs the exact oracles.

Every test drives the Pallas kernels with ``interpret=True`` so the
whole sparse path runs on CPU CI; the ``kernels-interpret`` job also
runs this file under ``JAX_ENABLE_X64=1`` to catch dtype drift in the
CSR index math.
"""
import numpy as np
import pytest

from repro.aggregates.semiring import Avg, Count, Max, Min, Sum
from repro.api import Q
from repro.core.jax_engine import (
    build_sparse_program,
    choose_jax_path,
    execute_jax,
)
from repro.core.prepare import csr_restrict, grouped_csr, prepare
from repro.core.query import JoinAggQuery
from repro.relational.oracle import oracle_joinagg
from repro.relational.relation import Database

from tests.test_joinagg_core import CASES, assert_same

RNG = np.random.default_rng(21)


def measured_db(n=150, a=5, b=6):
    """Chain with a mid-tree measure relation (3 attrs on R2)."""
    return Database.from_mapping(
        {
            "R1": {"g1": RNG.integers(0, a, n), "p": RNG.integers(0, b, n)},
            "R2": {
                "p": RNG.integers(0, b, n),
                "q": RNG.integers(0, b, n),
                "m": RNG.integers(0, 10, n),
            },
            "R3": {"q": RNG.integers(0, b, n), "g2": RNG.integers(0, a, n)},
        }
    )


# ----------------------------------------------------------------------
# the full tree surface: every core case, COUNT
# ----------------------------------------------------------------------


@pytest.mark.parametrize(
    "case", ["selfjoin", "chain", "chain4g", "branching", "siblings"]
)
def test_sparse_count_matches_oracle(case):
    """Arbitrary arity + multi-child nodes — the shapes the old kernels
    mode rejected with NotImplementedError."""
    db, q = CASES[case]()
    assert_same(
        execute_jax(q, db, mode="sparse", interpret=True), oracle_joinagg(q, db)
    )


@pytest.mark.parametrize("kind", [Sum, Min, Max])
def test_sparse_measures_match_oracle(kind):
    # R2 holds 1,235 distinct (p, q) and 2,297 distinct (p, q, m) rows,
    # so its hop spans two or more 1024-edge kernel tiles and the kernels
    # accumulate across edge-tile revisits
    db = measured_db(n=2500, b=40)
    q = JoinAggQuery(
        ("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")), kind("R2", "m")
    )
    assert_same(
        execute_jax(q, db, mode="sparse", interpret=True), oracle_joinagg(q, db)
    )


def test_kernels_mode_sum_regression():
    """``mode="kernels"`` used to silently return COUNT for SUM queries
    (it always contracted ``er.count``).  The alias now runs the sparse
    program: the answer must be the correct SUM — or an explicit
    NotImplementedError — but never a silently wrong aggregate."""
    db = measured_db()
    q = JoinAggQuery(
        ("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")), Sum("R2", "m")
    )
    want = oracle_joinagg(q, db)
    count = oracle_joinagg(JoinAggQuery(q.relations, q.group_by), db)
    assert want != count  # the data actually distinguishes SUM from COUNT
    try:
        got = execute_jax(q, db, mode="kernels", interpret=True)
    except NotImplementedError:
        return  # an explicit refusal is acceptable; a wrong answer is not
    assert got == want


def test_sparse_hops_run_coo_spmm_on_its_pair_grid():
    """The engine hands ``coo_spmm`` its hops in grouped-CSR order, so
    every call runs the pair grid (no full-grid fallback) and the answer
    stays exact."""
    from repro import obs

    db = measured_db(n=2500, b=40)
    q = JoinAggQuery(("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")))
    obs.reset("kernel.")
    assert_same(
        execute_jax(q, db, mode="sparse", interpret=True), oracle_joinagg(q, db)
    )
    c = obs.counters()
    assert 0 < c["kernel.coo_spmm_steps"] <= c["kernel.coo_spmm_grid_steps"]
    assert "kernel.coo_spmm_full" not in c


@pytest.mark.parametrize("kind", [Min, Max])
def test_sparse_minmax_hops_run_segment_reduce_on_its_schedule(kind):
    """MIN/MAX hops reach ``segment_reduce`` with CSR-sorted keys, so
    every call takes the short schedule and reduces fewer windows than
    the full grid holds, and the answer stays exact."""
    from repro import obs

    db = measured_db(n=2500, b=40)
    q = JoinAggQuery(
        ("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")), kind("R2", "m")
    )
    obs.reset("kernel.")
    assert_same(
        execute_jax(q, db, mode="sparse", interpret=True), oracle_joinagg(q, db)
    )
    c = obs.counters()
    assert 0 < c["kernel.segment_reduce_windows"] < (
        c["kernel.segment_reduce_grid_windows"]
    )
    assert "kernel.segment_reduce_full" not in c


def test_sparse_matches_dense_bit_identical():
    db, q = CASES["chain"]()
    sparse = execute_jax(q, db, mode="sparse", interpret=True)
    dense = execute_jax(q, db, mode="dense")
    assert sparse == dense  # integer counts < 2^24: f32 exact on both


# ----------------------------------------------------------------------
# channel bundles + streaming through the planner
# ----------------------------------------------------------------------


def _bundle(db):
    return (
        Q.over("R1", "R2", "R3")
        .group_by("R1.g1", "R3.g2")
        .agg(
            c=Count(),
            total=Sum("R2.m"),
            lo=Min("R2.m"),
            hi=Max("R2.m"),
            mean=Avg("R2.m"),
        )
    )


def _assert_results_equal(got, want):
    assert got.relation.columns.keys() == want.relation.columns.keys()
    for col in want.relation.columns:
        np.testing.assert_array_equal(
            got.relation.columns[col], want.relation.columns[col], err_msg=col
        )


def test_jax_sparse_bundle_matches_tensor():
    db = measured_db()
    q = _bundle(db)
    want = q.engine("tensor").plan(db).execute()
    # tiny budget forces the sparse path + ≥2 stream tiles
    got = q.engine("jax").memory_budget(128).plan(db).execute()
    _assert_results_equal(got, want)


def test_jax_stream_no_longer_unsupported():
    """Regression: ``stream``/``memory_budget`` on the jax engine raised
    UnsupportedPlanOption; the sparse path now honors them."""
    db = measured_db()
    q = _bundle(db)
    want = q.engine("tensor").plan(db).execute()
    got = q.engine("jax").stream("g1", 2).plan(db).execute()
    _assert_results_equal(got, want)

    from repro.core.operator import join_agg

    jq = JoinAggQuery(("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")))
    assert join_agg(jq, db, engine="jax", stream=("g1", 2)) == join_agg(jq, db)
    assert join_agg(jq, db, engine="jax", memory_budget=64) == join_agg(jq, db)


def test_jax_sparse_ghd_bags_as_csr_inputs():
    """Cyclic query: GHD bag outputs feed the sparse path as CSR inputs."""
    from repro.data.queries import triangle_like

    db, q = triangle_like(400)
    want = (
        Q.from_query(q).engine("tensor").plan(db).execute().to_dict()
    )
    got = (
        Q.from_query(q)
        .engine("jax")
        .memory_budget(256)  # force sparse
        .plan(db)
        .execute()
        .to_dict()
    )
    assert got == want


# ----------------------------------------------------------------------
# planner path choice + explain
# ----------------------------------------------------------------------


def test_choose_jax_path_budget_and_cliff():
    db = measured_db()
    q = JoinAggQuery(("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")))
    prep = prepare(q, db)
    assert choose_jax_path(prep).path == "dense"  # tiny domains fit
    assert choose_jax_path(prep, memory_budget=64).path == "sparse"
    forced = choose_jax_path(prep, stream=("g1", 2))
    assert forced.path == "sparse" and "stream" in forced.reason
    # per-node estimates cover every surviving relation
    assert set(choose_jax_path(prep).dense_node_bytes) == set(prep.encoded)


def test_explain_renders_jax_path():
    db = measured_db()
    q = _bundle(db)
    text = q.engine("jax").memory_budget(128).plan(db).explain()
    assert "jax path: sparse" in text
    assert "est dense peak" in text
    dense_text = q.engine("jax").plan(db).explain()
    assert "jax path: dense" in dense_text
    # tensor plans say nothing about the jax path
    assert "jax path" not in q.engine("tensor").plan(db).explain()


# ----------------------------------------------------------------------
# CSR views
# ----------------------------------------------------------------------


def test_grouped_csr_view_slices():
    db = measured_db()
    q = JoinAggQuery(("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")))
    prep = prepare(q, db)
    er = prep.encoded["R2"]
    view = prep.csr_view("R2", ("p",))
    assert prep.csr_view("R2", ("p",)) is view  # memoized
    assert np.all(np.diff(view.keys) >= 0)  # CSR: keys ascending
    dom = prep.dicts["p"].size
    lo, hi = 1, max(2, dom // 2)
    rows = view.order[view.slice_range(lo, hi)]
    pcol = er.attrs.index("p")
    mask = (er.codes[:, pcol] >= lo) & (er.codes[:, pcol] < hi)
    assert sorted(rows.tolist()) == sorted(np.flatnonzero(mask).tolist())

    enc = csr_restrict(prep, "p", lo, hi)
    assert enc["R2"].num_rows == int(mask.sum())
    assert enc["R2"].codes[:, pcol].max(initial=-1) < hi - lo
    assert enc["R1"] is not prep.encoded["R1"] or "p" not in enc["R1"].attrs


def test_grouped_csr_empty_relation():
    er_codes = np.zeros((0, 2), dtype=np.int64)
    from repro.relational.encoding import EncodedRelation

    er = EncodedRelation("E", ("a", "b"), er_codes, np.zeros(0, np.int64), {})
    view = grouped_csr(er, ("a",), (4,))
    assert view.slice_range(0, 4) == slice(0, 0)


def test_sparse_program_stream_tiles_cover_domain():
    db = measured_db()
    q = JoinAggQuery(("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")))
    prep = prepare(q, db)
    prog = build_sparse_program(prep, (None,), interpret=True)
    full = prog.run_channels()[..., 0]
    tiled = np.zeros_like(full)
    tiles = 0
    for enc, domains, offsets in prog.run_stream("g1", 2):
        arr = prog.run_channels(enc, domains)[..., 0]
        tiled[offsets["g1"]: offsets["g1"] + arr.shape[0]] = arr
        tiles += 1
    assert tiles >= 2
    np.testing.assert_array_equal(tiled, full)


# ----------------------------------------------------------------------
# the general and MIN/MAX hops form their per-edge product on the device
# ----------------------------------------------------------------------

# edge counts just off the 1,024-edge bucket, so every hop has a pad tail
EDGE_COUNTS = [1023, 1025, 2049]
# both device paths: Pallas in interpret mode, and the jnp reference kernels
INTERPRET = [True, None]


def _engines(interpret):
    """A k = 2 channel engine and a MIN and a MAX engine; the blocks under
    test take their shapes from their arguments, not from this plan."""
    from repro.core.jax_engine import _KernelChannelEngine, _MinMaxKernelEngine

    q = JoinAggQuery(("R1", "R2", "R3"), (("R1", "g1"), ("R3", "g2")))
    prep = prepare(q, measured_db())
    return (
        _KernelChannelEngine(prep, 2, {}, interpret=interpret),
        {kind: _MinMaxKernelEngine(prep, kind, "R2", interpret=interpret)
         for kind in ("min", "max")},
    )


def _hop(n, children, k=None, seed=0, knum=300):
    """Random hop inputs: integer weights ``(n, k)`` (``(n,)`` when k is
    None) that differ by channel, one ``(rows, width[, k])`` message per
    child, CSR-sorted keys."""
    rng = np.random.default_rng(seed)
    chan = () if k is None else (k,)
    weights = rng.integers(0, 9, (n,) + chan).astype(np.float64)
    gathers = [
        (rng.integers(0, 9, (rows, width) + chan).astype(np.float32),
         rng.integers(0, rows, n))
        for rows, width in children
    ]
    keys = np.sort(rng.integers(0, knum, n)).astype(np.int64)
    return weights, gathers, keys, knum


def _minmax_ref(kind, weights, gathers, keys, knum):
    """The loop form of a MIN/MAX hop: candidate sums, then reduce."""
    n = len(weights)
    vals = weights.astype(np.float32).reshape(n, 1)
    for m2, idx in gathers:
        vals = (vals[:, :, None] + m2[idx][:, None, :]).reshape(n, -1)
    red = np.minimum if kind == "min" else np.maximum
    out = np.full((knum, vals.shape[1]), np.inf if kind == "min" else -np.inf,
                  np.float32)
    red.at(out, keys, vals)
    return out


SUM_HOPS = {
    # COUNT + SUM weights differ by channel: no coo_spmm
    "non-uniform": [(500, 7)],
    "two-child": [(60, 3), (40, 5)],
    "leaf": [],
}


@pytest.mark.parametrize("interpret", INTERPRET, ids=["pallas", "ref"])
@pytest.mark.parametrize("n", EDGE_COUNTS)
@pytest.mark.parametrize("hop", list(SUM_HOPS))
def test_device_product_sum_hop_bit_identical(hop, n, interpret):
    from repro.core.tensor_engine import ChannelTensorEngine

    eng, _ = _engines(interpret)
    weights, gathers, keys, knum = _hop(n, SUM_HOPS[hop], k=2)
    got = eng._contract_block(weights, gathers, keys, knum)
    want = ChannelTensorEngine._contract_block(
        eng, weights, [(m.astype(np.float64), i) for m, i in gathers], keys, knum
    )
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, want.astype(np.float32))


@pytest.mark.parametrize("interpret", INTERPRET, ids=["pallas", "ref"])
@pytest.mark.parametrize("n", EDGE_COUNTS)
@pytest.mark.parametrize("kind", ["min", "max"])
def test_device_product_minmax_hop_bit_identical(kind, n, interpret):
    """Candidate sums over child messages whose unreached rows hold the
    walk's identity, ``+inf`` for MIN and ``-inf`` for MAX, as a walk's
    messages do."""
    _, mm = _engines(interpret)
    weights, gathers, keys, knum = _hop(n, [(90, 4), (30, 3)])
    ident = np.inf if kind == "min" else -np.inf
    for m2, _ in gathers:
        m2[::4] = ident
    got = mm[kind]._contract_block(weights, gathers, keys, knum)
    want = _minmax_ref(kind, weights, gathers, keys, knum)
    assert np.isinf(want).any() and np.isfinite(want).any()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("interpret", INTERPRET, ids=["pallas", "ref"])
def test_device_product_pad_rows_reading_inf_make_no_nan(interpret):
    """The pad rows of the edge tail gather row 0 with weight 0; a child
    message whose row 0 holds inf would make them 0 · inf = NaN, which the
    one-hot ``segment_sum`` would spread over its tile.  The device zeroes
    them, as the host pad did."""
    from repro.core.tensor_engine import ChannelTensorEngine

    eng, _ = _engines(interpret)
    weights, gathers, keys, knum = _hop(1025, [(500, 7)], k=2)
    m2, idx = gathers[0]
    m2[0] = np.inf
    idx[idx == 0] = 1  # no real edge reads row 0
    got = eng._contract_block(weights, gathers, keys, knum)
    want = ChannelTensorEngine._contract_block(eng, weights, gathers, keys, knum)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want.astype(np.float32))


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
def test_hop_beyond_int32_keys_forms_its_product_on_the_host(kind, monkeypatch):
    """Row keys past the kernels' int32 take the one host product, which
    the host counter counts and the device counter does not."""
    from repro import obs
    from repro.core import jax_engine
    from repro.core.tensor_engine import ChannelTensorEngine

    eng, mm = _engines(True)
    weights, gathers, keys, knum = _hop(
        1025, [(60, 3)], k=2 if kind == "sum" else None
    )
    monkeypatch.setattr(jax_engine, "_INT32_LIMIT", knum)
    obs.reset("hop.")
    if kind == "sum":
        got = eng._contract_block(weights, gathers, keys, knum)
        want = ChannelTensorEngine._contract_block(
            eng, weights, gathers, keys, knum
        ).astype(np.float32)
    else:
        got = mm[kind]._contract_block(weights, gathers, keys, knum)
        want = _minmax_ref(kind, weights, gathers, keys, knum)
    np.testing.assert_array_equal(got, want)
    c = obs.counters()
    assert c["hop.product_edges_host"] == 1025
    assert "hop.product_edges_device" not in c
