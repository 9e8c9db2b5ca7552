"""Property tests (hypothesis): the fused hop megakernel is
bit-identical to the three-dispatch path and the numpy oracle across
randomly drawn hop shapes, block sizes, and semiring kinds.

Runs entirely in Pallas interpret mode.  Shapes deliberately cover the
degenerate corners: trailing partial tiles on every axis (edge counts
straddle the 1024-edge block granule), zero-edge
hops, single-segment outputs, child messages with fewer rows than the
gather tile, and ±inf identity entries in min/max child messages.
"""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")  # optional dev dependency
from hypothesis import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels.fused_hop import fused_hop

BLOCKS = st.sampled_from([8, 16, 24, 50, 64, 100, 128])


@st.composite
def hop_cases(draw, kinds=("sum", "min", "max")):
    kind = draw(st.sampled_from(kinds))
    k = draw(st.integers(1, 3)) if kind == "sum" else 1
    n = draw(st.sampled_from([0, 1, 7, 64, 200, 1023, 1025, 2049]))
    segs = draw(st.sampled_from([1, 3, 17, 64, 130]))
    nchild = draw(st.integers(0, 2)) if kind == "sum" else draw(st.integers(1, 2))
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    keys = rng.integers(0, segs, n).astype(np.int32)
    if kind == "sum":
        w = rng.integers(0, 4, (n, k)).astype(np.float32)
    else:
        w = rng.integers(-5, 6, (n, 1)).astype(np.float32)
    msgs, idxs = [], []
    for _ in range(nchild):
        rows = draw(st.sampled_from([1, 3, 16, 40, 129]))
        wc = draw(st.integers(1, 3))
        if kind == "sum":
            m = rng.integers(0, 3, (rows, wc * k)).astype(np.float32)
        else:
            m = rng.integers(-4, 5, (rows, wc)).astype(np.float32)
            mask = rng.random((rows, wc)) < 0.3
            m[mask] = np.inf if kind == "min" else -np.inf
        msgs.append(m)
        idxs.append(rng.integers(0, rows, n).astype(np.int32))
    blocks = {
        "block_e": draw(BLOCKS),
        "block_s": draw(BLOCKS),
        "block_r": draw(BLOCKS),
    }
    return kind, k, n, segs, keys, w, tuple(msgs), tuple(idxs), blocks


def _oracle(keys, w, msgs, idxs, num_segments, k, kind):
    n = len(keys)
    if kind == "sum":
        width = 1
        vals = np.asarray(w, np.float32).reshape(n, 1, k)
        for msg, idx in zip(msgs, idxs):
            wc = msg.shape[1] // k
            rows = msg.reshape(msg.shape[0], wc, k)[idx]
            vals = (vals[:, :, None, :] * rows[:, None, :, :]).reshape(
                n, width * wc, k
            )
            width *= wc
        out = np.zeros((num_segments, width * k), np.float32)
        np.add.at(out, keys, vals.reshape(n, width * k))
        return out
    ident = np.inf if kind == "min" else -np.inf
    width = 1
    cand = np.asarray(w, np.float32).reshape(n, 1)
    for msg, idx in zip(msgs, idxs):
        wc = msg.shape[1]
        cand = (cand[:, :, None] + msg[idx][:, None, :]).reshape(n, width * wc)
        width *= wc
    out = np.full((num_segments, width), ident, np.float32)
    red = np.minimum if kind == "min" else np.maximum
    red.at(out, keys, cand)
    return out


@settings(max_examples=40, deadline=None)
@given(case=hop_cases())
def test_fused_hop_matches_oracle(case):
    kind, k, n, segs, keys, w, msgs, idxs, blocks = case
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


@settings(max_examples=12, deadline=None)
@given(case=hop_cases(kinds=("sum",)), seed=st.integers(0, 2**31 - 1))
def test_fused_hop_matches_three_dispatch(case, seed):
    """Integer-valued data: fused and three-dispatch results are exact
    f32, so equality is bitwise regardless of tiling."""
    from repro.kernels.ops import segment_sum

    kind, k, n, segs, keys, w, msgs, idxs, blocks = case
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
    # three dispatches: jnp gather + host-shaped product + segment_sum
    # (width tracked explicitly: -1 reshapes are ambiguous when n == 0)
    width = 1
    vals = jnp.asarray(w)[:, None, :]
    for m, ix in zip(msgs, idxs):
        wc = m.shape[1] // k
        rows = jnp.asarray(m).reshape(m.shape[0], wc, k)[jnp.asarray(ix)]
        vals = (vals[:, :, None, :] * rows[:, None, :, :]).reshape(
            n, width * wc, k
        )
        width *= wc
    flat = vals.reshape(n, width * k)
    if n:
        want = segment_sum(
            flat, jnp.asarray(keys), num_segments=segs, interpret=True
        )
    else:
        # the standalone segment_sum kernel rejects zero-row inputs (the
        # fused wrapper pads to one tile); the sum of no edges is zeros
        want = jnp.zeros((segs, width * k), jnp.float32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    kind=st.sampled_from(["count", "sum", "min", "max"]),
)
def test_fused_engine_bit_identity(seed, kind):
    """End-to-end single-aggregate property: fused vs three-dispatch
    engine runs agree bitwise across COUNT/SUM/MIN/MAX."""
    from repro.aggregates.semiring import Count, Max, Min, Sum
    from repro.api import Q

    rng = np.random.default_rng(seed)
    n, a, b = 150, 6, 5
    db = {
        "R1": {"g1": rng.integers(0, a, n), "p": rng.integers(0, b, n)},
        "R2": {"p": rng.integers(0, b, n), "q": rng.integers(0, b, n),
               "m": rng.integers(0, 9, n)},
        "R3": {"q": rng.integers(0, b, n), "g2": rng.integers(0, a, n)},
    }
    agg = {
        "count": Count(), "sum": Sum("R2.m"),
        "min": Min("R2.m"), "max": Max("R2.m"),
    }[kind]
    base = (
        Q.over("R1", "R2", "R3")
        .group_by("R1.g1", "R3.g2")
        .agg(v=agg)
        .engine("jax")
        .memory_budget(1)  # pin the sparse path on both sides
    )
    unfused = base.fused(False).plan(db).execute().to_dict("v")
    ops.reset_dispatch_counts()
    fused = base.fused(True).plan(db).execute().to_dict("v")
    assert "fused" in ops.dispatch_counts()
    assert unfused == fused
