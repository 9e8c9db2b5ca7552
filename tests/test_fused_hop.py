"""Fused JOIN-AGG hop megakernel: kernel-level oracles, engine-level
fused-vs-three-dispatch differentials, and the kernel-layer bugfix
regressions (DESIGN.md §13).

The differential suites are the fused path's correctness contract: for
every catalog query (acyclic, GHD, per-split, and — in the slow suite —
a mesh=8 shard_map run) the fused megakernel execution must be
**bit-identical** to the three-dispatch gather/product/scatter path,
which stays in-tree as the differential oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.fused_hop import fused_hop

RNG = np.random.default_rng(11)


# ----------------------------------------------------------------------
# numpy oracle — mirrors the engine's host-side product semantics
# ----------------------------------------------------------------------


def _oracle(keys, w, msgs, idxs, num_segments, k, kind):
    n = len(keys)
    if kind == "sum":
        width = 1
        vals = np.asarray(w, np.float32).reshape(n, 1, k)
        for msg, idx in zip(msgs, idxs):
            wc = msg.shape[1] // k
            rows = np.asarray(msg, np.float32).reshape(msg.shape[0], wc, k)[idx]
            vals = (vals[:, :, None, :] * rows[:, None, :, :]).reshape(
                n, width * wc, k
            )
            width *= wc
        flat = vals.reshape(n, width * k)
        out = np.zeros((num_segments, width * k), np.float32)
        np.add.at(out, np.asarray(keys), flat)
        return out
    ident = np.inf if kind == "min" else -np.inf
    width = 1
    cand = np.asarray(w, np.float32).reshape(n, 1)
    for msg, idx in zip(msgs, idxs):
        wc = msg.shape[1]
        rows = np.asarray(msg, np.float32)[idx]
        cand = (cand[:, :, None] + rows[:, None, :]).reshape(n, width * wc)
        width *= wc
    out = np.full((num_segments, width), ident, np.float32)
    red = np.minimum if kind == "min" else np.maximum
    red.at(out, np.asarray(keys), cand)
    return out


def _random_hop(n, child_rows, child_widths, segs, k, kind, rng=RNG):
    keys = rng.integers(0, segs, n).astype(np.int32)
    if kind == "sum":
        w = rng.integers(0, 4, (n, k)).astype(np.float32)
    else:
        w = rng.integers(-5, 6, (n, 1)).astype(np.float32)
    msgs, idxs = [], []
    for rows, wc in zip(child_rows, child_widths):
        if kind == "sum":
            m = rng.integers(0, 3, (rows, wc * k)).astype(np.float32)
        else:
            m = rng.integers(-4, 5, (rows, wc)).astype(np.float32)
            # sprinkle ±inf identities like real unreached message rows
            mask = rng.random((rows, wc)) < 0.25
            m[mask] = np.inf if kind == "min" else -np.inf
        msgs.append(m)
        idxs.append(rng.integers(0, rows, n).astype(np.int32))
    return keys, w, tuple(msgs), tuple(idxs)


def _run(keys, w, msgs, idxs, segs, k, kind, **blocks):
    got = fused_hop(
        jnp.asarray(keys),
        jnp.asarray(w),
        tuple(jnp.asarray(m) for m in msgs),
        tuple(jnp.asarray(i) for i in idxs),
        num_segments=segs,
        k=k,
        kind=kind,
        interpret=True,
        **blocks,
    )
    want = _oracle(keys, w, msgs, idxs, segs, k, kind)
    np.testing.assert_array_equal(np.asarray(got), want)


# ----------------------------------------------------------------------
# kernel-level oracles
# ----------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 2, 3])
@pytest.mark.parametrize("children", [(), ((40, 2),), ((40, 3), (17, 2))])
def test_fused_sum_vs_oracle(k, children):
    rows = tuple(r for r, _ in children)
    widths = tuple(w for _, w in children)
    hop = _random_hop(300, rows, widths, 37, k, "sum")
    _run(*hop, 37, k, "sum", block_e=64, block_s=16, block_r=16)


@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize("children", [((40, 2),), ((40, 3), (17, 2))])
def test_fused_minmax_vs_oracle(kind, children):
    """±inf identities in child messages must survive the one-hot gather
    (a plain matmul would turn 0·inf into nan)."""
    rows = tuple(r for r, _ in children)
    widths = tuple(w for _, w in children)
    hop = _random_hop(300, rows, widths, 23, 1, kind)
    _run(*hop, 23, 1, kind, block_e=64, block_s=16, block_r=16)


@pytest.mark.parametrize("kind", ["sum", "min", "max"])
def test_fused_zero_edges(kind):
    """A hop with no edges must still initialize its output tile: the
    wrapper forces one (all-padding) edge tile so ``@pl.when(ei == 0)``
    runs — otherwise the VMEM output is uninitialized garbage."""
    k = 2 if kind == "sum" else 1
    hop = _random_hop(0, (16,), (2,), 9, k, kind)
    _run(*hop, 9, k, kind, block_e=32, block_s=8, block_r=8)


def test_fused_single_segment_and_tiny_rows():
    """num_segments=1 and child rows smaller than block_r both pad up."""
    hop = _random_hop(50, (3,), (2,), 1, 1, "sum")
    _run(*hop, 1, 1, "sum", block_e=64, block_s=64, block_r=128)


def test_fused_odd_blocks_normalize():
    """Non-multiple-of-8 block sizes round up instead of silently
    degrading the slice step (the ``math.gcd`` regression, fused form)."""
    hop = _random_hop(1100, (30, 11), (2, 3), 19, 1, "max")
    _run(*hop, 19, 1, "max", block_e=100, block_s=60, block_r=50)


def test_fused_trailing_partial_tiles():
    """Edge/segment/row counts that are not block multiples exercise the
    padded trailing tiles on every axis (block_e rounds up to the 1024
    edge granule: two full edge tiles and a one-edge partial)."""
    hop = _random_hop(2049, (129, 65), (2, 2), 131, 2, "sum")
    _run(*hop, 131, 2, "sum", block_e=128, block_s=32, block_r=64)


def test_fused_rejects_bad_args():
    keys = jnp.zeros(4, jnp.int32)
    w = jnp.ones((4, 1), jnp.float32)
    with pytest.raises(ValueError, match="unknown hop kind"):
        fused_hop(keys, w, (), (), num_segments=3, kind="mean")
    with pytest.raises(ValueError, match="num_segments"):
        fused_hop(keys, w, (), (), num_segments=0)
    with pytest.raises(ValueError, match="single-channel"):
        fused_hop(keys, jnp.ones((4, 2)), (), (), num_segments=3, k=2, kind="min")
    with pytest.raises(ValueError, match="multiple of k"):
        fused_hop(
            keys, jnp.ones((4, 2)), (jnp.ones((8, 3)),),
            (jnp.zeros(4, jnp.int32),), num_segments=3, k=2,
        )


# ----------------------------------------------------------------------
# kernel-layer bugfix regressions
# ----------------------------------------------------------------------


def test_dimension_semantics_declared():
    """Regression: every accumulating kernel must declare its revisited
    grid axis "arbitrary" (sequential) — on GPU lowering an undeclared
    axis may parallelize and race the ``@pl.when(init)`` against the
    accumulation steps."""
    from repro.kernels import (
        coo_spmm,
        fused_hop as fused_mod,
        segment_reduce,
        segment_sum,
        semiring_matmul,
    )

    assert coo_spmm.DIM_SEMANTICS == ("arbitrary", "arbitrary")
    assert segment_sum.DIM_SEMANTICS == ("parallel", "arbitrary")
    assert segment_reduce.DIM_SEMANTICS == ("arbitrary",)
    assert fused_mod.DIM_SEMANTICS == ("parallel", "arbitrary")
    assert semiring_matmul.DIM_SEMANTICS == ("parallel", "parallel", "arbitrary")
    # the accumulation axis (last grid axis) is sequential in every kernel
    for mod in (coo_spmm, segment_sum, segment_reduce, fused_mod, semiring_matmul):
        assert mod.DIM_SEMANTICS[-1] == "arbitrary", mod.__name__


def test_block_normalization_policy():
    """Regression: ``k_step = math.gcd(block_n, 8)`` silently degraded to
    a 1-wide slice loop on odd blocks; now blocks round UP to the granule
    and ``k_step_for`` refuses non-multiples outright."""
    assert ops.normalize_block("b", 8) == 8
    assert ops.normalize_block("b", 12) == 16
    assert ops.normalize_block("b", 1) == 8
    assert ops.normalize_block("b", 128) == 128
    for bad in (0, -8):
        with pytest.raises(ValueError, match="positive"):
            ops.normalize_block("b", bad)
    with pytest.raises(ValueError, match="positive int"):
        ops.normalize_block("b", True)
    assert ops.k_step_for(64) == 8
    with pytest.raises(ValueError, match="multiple"):
        ops.k_step_for(12)


def test_interpret_policy_centralized():
    """One policy decides how a kernel runs, and it never hides the
    device: ``interpret=True`` is the interpreter, ``interpret=False``
    means Mosaic-compiled and raises on a CPU backend (it used to turn
    into interpret mode there), and only auto mode on a CPU backend may
    substitute the jnp reference kernels in the engines."""
    on_cpu = jax.default_backend() == "cpu"
    assert ops.resolve_interpret(True) is True
    assert ops.resolve_interpret(None) is on_cpu
    if on_cpu:
        with pytest.raises(RuntimeError, match="interpret=False"):
            ops.resolve_interpret(False)
        # the public wrappers reach the same check
        with pytest.raises(RuntimeError, match="interpret=False"):
            ops.segment_sum(
                jnp.ones((8, 2)), jnp.zeros(8, jnp.int32), num_segments=2,
                interpret=False,
            )
    else:
        assert ops.resolve_interpret(False) is False
    # ref kernels only when NOTHING was pinned and we're on CPU
    assert ops.use_ref_kernels(None) is on_cpu
    assert ops.use_ref_kernels(False) is False
    assert ops.use_ref_kernels(True) is False


# ----------------------------------------------------------------------
# engine-level differential: fused vs three-dispatch, bit-identical
# ----------------------------------------------------------------------


def _star_db(n=300, seed=7):
    rng = np.random.default_rng(seed)
    a, b = 9, 8
    return {
        "R1": {"g1": rng.integers(0, a, n), "p": rng.integers(0, b, n)},
        "R2": {
            "p": rng.integers(0, b, n),
            "q": rng.integers(0, b, n),
            "m": rng.integers(0, 10, n),
        },
        "R3": {"q": rng.integers(0, b, n), "g2": rng.integers(0, a, n)},
    }


def _snap(res):
    return {name: res.to_dict(name) for name in res.agg_names}


def test_fused_bundle_differential_and_dispatch_ratio():
    """The measure-weighted multi-aggregate bundle runs every fused
    variant (sum channels + min/max semiring) and must match the
    three-dispatch path bit-for-bit while cutting kernel dispatches by
    at least the 1.3× acceptance floor."""
    from repro.aggregates.semiring import Avg, Count, Max, Min, Sum
    from repro.api import Q

    db = _star_db()
    base = (
        Q.over("R1", "R2", "R3")
        .group_by("R1.g1", "R3.g2")
        .agg(
            c=Count(), total=Sum("R2.m"), lo=Min("R2.m"), hi=Max("R2.m"),
            mean=Avg("R2.m"),
        )
        .engine("jax")
        .memory_budget(1)  # pin the sparse path on both sides
    )
    ops.reset_dispatch_counts()
    unfused = _snap(base.fused(False).plan(db).execute())
    d_u = ops.dispatch_counts()
    ops.reset_dispatch_counts()
    fused = _snap(base.fused(True).plan(db).execute())
    d_f = ops.dispatch_counts()
    assert unfused == fused
    assert set(d_f) == {"fused"}, d_f
    assert "fused" not in d_u and d_u, d_u
    ratio = sum(d_u.values()) / sum(d_f.values())
    assert ratio >= 1.3, (d_u, d_f)


@pytest.mark.slow
def test_fused_catalog_differential():
    """Full-catalog bit-identity: every acyclic, GHD, and SKEWCHAIN
    (per-split) query at golden-adjacent scales, fused vs unfused."""
    from repro.api import Q
    from repro.data.queries import CYCLIC, REAL, SKEWED

    scales = {"REAL": 200, "CYCLIC": 120, "SKEWED": 200}
    for group, cat in (("REAL", REAL), ("CYCLIC", CYCLIC), ("SKEWED", SKEWED)):
        for name, gen in sorted(cat.items()):
            db, q = gen(scales[group], seed=0)
            base = Q.from_query(q).engine("jax").memory_budget(1)
            unfused = _snap(base.fused(False).plan(db).execute())
            ops.reset_dispatch_counts()
            fused = _snap(base.fused(True).plan(db).execute())
            assert "fused" in ops.dispatch_counts(), name
            assert unfused == fused, name


def test_fused_split_plan_differential():
    """The SKEWCHAIN per-split plan threads the fused flag through
    ``execute_split`` into each range's engine run."""
    from repro.api import Q
    from repro.data.queries import SKEWED

    # no memory budget: a 1-byte budget would disqualify the split plan
    # (.fused(True) already pins the sparse path inside each range)
    db, q = SKEWED["SKEWCHAIN"](600, seed=0)
    base = Q.from_query(q).engine("jax")
    plan_f = base.fused(True).plan(db)
    assert plan_f.split is not None, "SKEWCHAIN must split at this scale"
    unfused = _snap(base.fused(False).plan(db).execute())
    ops.reset_dispatch_counts()
    fused = _snap(plan_f.execute())
    assert "fused" in ops.dispatch_counts()
    assert unfused == fused


@pytest.mark.slow
def test_fused_mesh_differential():
    """mesh=8 shard_map differential: fused megakernel hops inside the
    sharded program match the unfused scatter hops bit-for-bit."""
    import json

    from tests.conftest import run_in_virtual_mesh

    script = r"""
import json
import numpy as np
from repro.aggregates.semiring import Avg, Count, Max, Min, Sum
from repro.api import Q
from repro.kernels import ops

rng = np.random.default_rng(7)
n, a, b = 300, 9, 8
db = {
    "R1": {"g1": rng.integers(0, a, n), "p": rng.integers(0, b, n)},
    "R2": {"p": rng.integers(0, b, n), "q": rng.integers(0, b, n),
           "m": rng.integers(0, 10, n)},
    "R3": {"q": rng.integers(0, b, n), "g2": rng.integers(0, a, n)},
}
base = (
    Q.over("R1", "R2", "R3").group_by("R1.g1", "R3.g2")
    .agg(c=Count(), total=Sum("R2.m"), lo=Min("R2.m"), hi=Max("R2.m"),
         mean=Avg("R2.m"))
    .engine("jax").mesh(8)
)

def snap(res):
    return {
        name: sorted(
            [list(map(float, k)), float(v)]
            for k, v in res.to_dict(name).items()
        )
        for name in res.agg_names
    }

unfused = snap(base.fused(False).plan(db).execute())
ops.reset_dispatch_counts()
fused = snap(base.fused(True).plan(db).execute())
print(json.dumps({
    "match": unfused == fused,
    "dispatches": ops.dispatch_counts(),
}))
"""
    out = run_in_virtual_mesh(script, devices=8)
    assert out["match"] is True
    assert set(out["dispatches"]) == {"fused"}, out["dispatches"]


def test_fused_env_switch():
    """``REPRO_FUSED=1`` turns the fused path on for plans that did not
    pin a choice; an explicit ``.fused(False)`` still wins."""
    from repro.api import Q

    db = _star_db(n=120)
    base = (
        Q.over("R1", "R2", "R3")
        .group_by("R1.g1", "R3.g2")
        .engine("jax")
        .memory_budget(1)
    )
    assert ops.fused_enabled(True) is True
    assert ops.fused_enabled(False) is False
    import os

    old = os.environ.pop("REPRO_FUSED", None)
    try:
        assert ops.fused_enabled(None) is False
        os.environ["REPRO_FUSED"] = "1"
        assert ops.fused_enabled(None) is True
        ops.reset_dispatch_counts()
        base.plan(db).execute()
        assert "fused" in ops.dispatch_counts()
        ops.reset_dispatch_counts()
        base.fused(False).plan(db).execute()
        assert "fused" not in ops.dispatch_counts()
    finally:
        if old is None:
            os.environ.pop("REPRO_FUSED", None)
        else:
            os.environ["REPRO_FUSED"] = old


def test_fused_option_rejected_off_jax():
    from repro.api import Q, UnsupportedPlanOption

    db = _star_db(n=60)
    q = Q.over("R1", "R2", "R3").group_by("R1.g1", "R3.g2")
    for engine in ("tensor", "ref"):
        with pytest.raises(UnsupportedPlanOption, match="fused"):
            q.engine(engine).fused(True).plan(db)


def test_explain_kernels_section():
    """``.fused(True)`` plans render a deterministic ``kernels:`` section
    (model-ranked tiles, never the measurement cache)."""
    from repro.api import Q

    db = _star_db(n=120)
    q = Q.over("R1", "R2", "R3").group_by("R1.g1", "R3.g2").engine("jax")
    ex = q.fused(True).plan(db).explain()
    assert "kernels: fused hop megakernel" in ex
    assert "acc=float32" in ex and "tiles e" in ex
    assert "kernels:" not in q.plan(db).explain()


def test_no_fitting_tile_raises_to_the_client(monkeypatch, tmp_path):
    """A hop that no candidate tile fits in VMEM raises, naming the hop
    shape and the bound, instead of launching default tiles; explain and
    the verifier report it, and the error reaches a served client."""
    from repro.analysis.verify import check_kernels
    from repro.api import Q
    from repro.kernels import autotune, fused_hop as fused_mod
    from repro.relational.relation import Database
    from repro.serve.server import JoinAggServer
    from repro.serve.session import Session

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tiles.json"))
    monkeypatch.setattr(fused_mod, "VMEM_BYTES", 1 << 16)
    autotune.reset_cache()
    try:
        db = Database.from_mapping(_star_db(n=120))
        q = (
            Q.over("R1", "R2", "R3").group_by("R1.g1", "R3.g2")
            .engine("jax").fused(True)
        )
        plan = q.plan(db)
        assert "no fused-hop tile fits VMEM" in plan.explain()
        assert any(d.code == "V-KERN" for d in check_kernels(plan))
        with JoinAggServer(db, workers=1) as srv:
            with pytest.raises(ValueError, match=r"fused_hop\|.*bound 65536 B"):
                Session(srv).query(q)
    finally:
        autotune.reset_cache()
