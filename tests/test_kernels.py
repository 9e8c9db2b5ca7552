"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.kernels import ops, ref
from repro.kernels.ops import coo_spmm, segment_reduce, segment_sum, semiring_matmul

RNG = np.random.default_rng(1)


@pytest.mark.parametrize("n,d,s", [(100, 8, 16), (2049, 128, 520), (64, 256, 7), (1, 8, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_sum(n, d, s, dtype):
    data = jnp.asarray(RNG.normal(size=(n, d)), dtype=dtype)
    ids = jnp.asarray(RNG.integers(0, s, size=n), dtype=jnp.int32)
    got = segment_sum(data, ids, num_segments=s, block_s=16, block_n=64, interpret=True)
    want = ref.segment_sum_ref(data, ids, s)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def _spmm(rows, cols, vals, dense, m, block_m=128, block_e=1024, **blocks):
    """``coo_spmm`` in interpret mode with its host rows' pair list."""
    pairs = ops.spmm_pair_list(np.asarray(rows), m, block_m, block_e)
    return coo_spmm(
        rows, cols, vals, dense, num_rows=m, pairs=pairs, block_m=block_m,
        block_e=block_e, interpret=True, **blocks,
    )


@pytest.mark.parametrize(
    "nnz,m,k,n", [(200, 32, 24, 16), (2049, 130, 257, 128), (5, 8, 8, 8)]
)
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_coo_spmm(nnz, m, k, n, dtype):
    rows = jnp.asarray(RNG.integers(0, m, size=nnz), dtype=jnp.int32)
    cols = jnp.asarray(RNG.integers(0, k, size=nnz), dtype=jnp.int32)
    vals = jnp.asarray(RNG.integers(1, 5, size=nnz), dtype=dtype)
    dense = jnp.asarray(RNG.normal(size=(k, n)), dtype=dtype)
    got = _spmm(rows, cols, vals, dense, m, block_m=16, block_e=64, block_k=32)
    want = ref.coo_spmm_ref(rows, cols, vals, dense, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# coo_spmm's pair grid: the (output tile, edge block) cells that hold an
# edge, and the long list where rows are too scattered for the short one
# ----------------------------------------------------------------------

SPMM_BLOCKS = dict(block_m=16, block_e=1024, block_k=16)


def _sorted_padded_tail():
    rows = np.sort(RNG.integers(0, 200, 2500))
    return np.r_[rows, np.full(3072 - 2500, -1)], 200


def _empty_tiles():
    # rows 0–15 and 100–115: tiles 1–5 and 8–12 hold no edge
    return np.sort(RNG.choice(np.r_[0:16, 100:116], 2100)), 200


def _one_row():
    return np.full(2100, 37), 64


def _block_spans_tiles():
    # one 1024-edge block over 63 output tiles
    return np.sort(RNG.integers(0, 1000, 1024)), 1000


def _unsorted():
    return RNG.integers(0, 300, 3000), 300


SPMM_CASES = {
    "sorted_padded_tail": (_sorted_padded_tail, False),
    "empty_tiles": (_empty_tiles, False),
    "one_row": (_one_row, False),
    "block_spans_tiles": (_block_spans_tiles, False),
    "unsorted_full_grid": (_unsorted, True),
}


def _spmm_operands(rows, k=40, n=8):
    nnz = len(rows)
    return (
        jnp.asarray(rows, jnp.int32),
        jnp.asarray(RNG.integers(0, k, nnz), jnp.int32),
        jnp.asarray(RNG.integers(1, 5, nnz), jnp.float32),
        jnp.asarray(RNG.integers(0, 9, (k, n)), jnp.float32),
    )


@pytest.mark.parametrize("case", list(SPMM_CASES))
def test_coo_spmm_pair_grid(case):
    """Exact on integer-valued f32 against the reference, with the long
    list, as many steps as the full grid, only where the rows' pairs
    exceed the short one."""
    build, full = SPMM_CASES[case]
    rows, m = build()
    args = _spmm_operands(rows)
    obs.reset("kernel.")
    got = np.asarray(_spmm(*args, m, **SPMM_BLOCKS))
    want = np.asarray(ref.coo_spmm_ref(*args, m))
    assert np.array_equal(got, want)
    c = obs.counters()
    assert c.get("kernel.coo_spmm_full", 0) == (1 if full else 0)
    if full:
        assert c["kernel.coo_spmm_steps"] == c["kernel.coo_spmm_grid_steps"]
    else:  # never more steps than the full grid
        assert 0 < c["kernel.coo_spmm_steps"] <= c["kernel.coo_spmm_grid_steps"]
    # tiles no edge lands in come back zero
    hit = np.zeros(m, bool)
    hit[rows[(rows >= 0) & (rows < m)]] = True
    assert not got[~hit].any()


@pytest.mark.parametrize("nnz,m", [(2500, 200), (4096, 30), (1024, 5000), (9000, 9000)])
def test_spmm_pair_list_fits_sorted_rows(nnz, m):
    """Row-sorted edges with a padded tail never need more than
    ``m_tiles + e_tiles - 1`` entries, and the list names exactly the
    cells that hold an edge, sorted, plus one entry per empty tile."""
    bm, be = SPMM_BLOCKS["block_m"], SPMM_BLOCKS["block_e"]
    rows = np.sort(RNG.integers(0, m, nnz))
    rows = np.r_[rows, np.full(-nnz % be, -1)]
    m_tiles, e_tiles = -(-m // bm), len(rows) // be
    pairs = ops.spmm_pair_list(rows, m, block_m=bm, block_e=be)
    assert pairs.shape == (2, m_tiles + e_tiles - 1)
    assert pairs.shape[1] == ops.pair_grid_len(nnz, m, block_m=bm, block_e=be)
    tile, code = pairs
    assert np.all(np.diff(tile) >= 0)
    live = {(t, c) for t, c in zip(tile, code) if c >= 0}
    ids = np.flatnonzero(rows >= 0)
    assert live == set(zip(rows[ids] // bm, ids // be))
    assert set(tile) == set(range(m_tiles))


@pytest.mark.parametrize(
    "built_at",
    [dict(block_m=32, block_e=1024), dict(block_m=16, block_e=2048)],
    ids=["block_m", "block_e"],
)
def test_coo_spmm_refuses_a_list_built_at_other_blocks(built_at):
    """A pair list built at other blocks than the call's has neither of
    the lengths its shapes allow: the call raises instead of leaving
    tiles unwritten."""
    rows, m = _sorted_padded_tail()
    args = _spmm_operands(rows)
    pairs = ops.spmm_pair_list(rows, m, **built_at)
    with pytest.raises(ValueError, match="pair list"):
        coo_spmm(*args, num_rows=m, pairs=pairs, interpret=True, **SPMM_BLOCKS)


def test_coo_spmm_counts_steps_on_the_host():
    """Each call counts its steps, a cached compile's too: the counting
    is on the host, not in the traced function."""
    rows, m = _sorted_padded_tail()
    args = _spmm_operands(rows)
    obs.reset("kernel.")
    _spmm(*args, m, **SPMM_BLOCKS)
    once = obs.counters()
    _spmm(*args, m, **SPMM_BLOCKS)
    twice = obs.counters()
    assert once["kernel.coo_spmm_steps"] > 0
    for name in ("kernel.coo_spmm_steps", "kernel.coo_spmm_grid_steps"):
        assert twice[name] == 2 * once[name], name


@pytest.mark.parametrize("semiring", ["add_mul", "max_add", "min_add", "or_and"])
@pytest.mark.parametrize("m,k,n", [(32, 48, 16), (129, 70, 65)])
def test_semiring_matmul(semiring, m, k, n):
    if semiring == "or_and":
        a = jnp.asarray(RNG.integers(0, 2, size=(m, k)), dtype=jnp.float32)
        b = jnp.asarray(RNG.integers(0, 2, size=(k, n)), dtype=jnp.float32)
    else:
        a = jnp.asarray(RNG.normal(size=(m, k)), dtype=jnp.float32)
        b = jnp.asarray(RNG.normal(size=(k, n)), dtype=jnp.float32)
    got = semiring_matmul(a, b, semiring=semiring,
                          block_m=32, block_n=32, block_k=16, interpret=True)
    want = ref.semiring_matmul_ref(a, b, semiring)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def _reduce(data, ids, s, kind, block_s=128, block_n=1024):
    """``segment_reduce`` in interpret mode with its host ids' schedule."""
    sched = ops.reduce_schedule(np.asarray(ids), s, block_s, block_n)
    return segment_reduce(
        data, ids, num_segments=s, kind=kind, schedule=sched, block_s=block_s,
        block_n=block_n, interpret=True,
    )


@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize("n,d,s", [(100, 8, 16), (2049, 128, 130), (1, 8, 3)])
def test_segment_reduce(kind, n, d, s):
    data = jnp.asarray(RNG.normal(size=(n, d)), dtype=jnp.float32)
    ids = jnp.asarray(RNG.integers(0, s, size=n), dtype=jnp.int32)
    got = _reduce(data, ids, s, kind, block_s=16, block_n=64)
    want = ref.segment_reduce_ref(data, ids, s, kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_reduce_block_not_multiple_of_kstep():
    """Regression: block_n not divisible by the k-slice step used to drop
    the trailing rows of every block (12 // 8 == 1 loop step).  Two full
    row blocks and a partial one after rounding to the edge granule."""
    n = 2 * ops.EDGE_GRANULE + 24
    data = jnp.asarray(RNG.normal(size=(n, 4)), dtype=jnp.float32)
    ids = jnp.asarray(RNG.integers(0, 6, size=n), dtype=jnp.int32)
    got = _reduce(data, ids, 6, "min", block_s=8, block_n=12)
    want = ref.segment_reduce_ref(data, ids, 6, "min")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ----------------------------------------------------------------------
# segment_reduce's schedule: coo_spmm's pair list at its blocks, and per
# entry the 8-row windows of the block that hold a key of the tile
# ----------------------------------------------------------------------

REDUCE_BLOCKS = dict(block_s=16, block_n=1024)


def _reduce_sorted():
    # three full row blocks and a partial fourth, the tail padded with
    # -1; keys in 0–15, 40–79 and 150–199, so tiles 1, 5–8 hold none
    keys = np.sort(RNG.choice(np.r_[0:16, 40:80, 150:200], 3300))
    return np.r_[keys, np.full(3500 - 3300, -1)], 200


def _reduce_unsorted():
    return RNG.integers(-1, 200, 3500), 200


REDUCE_CASES = {"sorted": (_reduce_sorted, False), "unsorted": (_reduce_unsorted, True)}


@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize("d", [1, 25])
@pytest.mark.parametrize("case", list(REDUCE_CASES))
def test_segment_reduce_schedule(case, d, kind):
    """Bit-exact against the reference on the scheduled grid: sorted keys
    take the short list and reduce only their windows, unsorted ones the
    long list; tiles no key lands in come back as the identity."""
    build, full = REDUCE_CASES[case]
    keys, s = build()
    data = jnp.asarray(RNG.normal(size=(len(keys), d)), jnp.float32)
    ids = jnp.asarray(keys, jnp.int32)
    obs.reset("kernel.")
    got = np.asarray(_reduce(data, ids, s, kind, **REDUCE_BLOCKS))
    want = np.asarray(ref.segment_reduce_ref(data, ids, s, kind))
    np.testing.assert_array_equal(got, want)
    c = obs.counters()
    assert c.get("kernel.segment_reduce_full", 0) == (1 if full else 0)
    assert 0 < c["kernel.segment_reduce_windows"] < (
        c["kernel.segment_reduce_grid_windows"]
    )
    hit = np.zeros(s, bool)
    hit[keys[keys >= 0]] = True
    assert np.all(got[~hit] == (np.inf if kind == "min" else -np.inf))


def _brute_windows(keys, s, pairs, block_s, block_n):
    lo, hi = [], []
    for tile, code in pairs.T:
        if code < 0:
            lo.append(0), hi.append(0)
            continue
        rows = [
            r for r in range(code * block_n, min((code + 1) * block_n, len(keys)))
            if tile * block_s <= keys[r] < min((tile + 1) * block_s, s)
        ]
        lo.append(rows[0] % block_n // 8)
        hi.append(rows[-1] % block_n // 8 + 1)
    return lo, hi


@pytest.mark.parametrize("case", list(REDUCE_CASES))
def test_row_windows_match_a_scan(case):
    """Each live entry's windows run from the window of the first row of
    its block whose key lies in its tile to that of the last."""
    from repro.kernels.segment_reduce import row_windows

    keys, s = REDUCE_CASES[case][0]()
    pairs = ops.spmm_pair_list(keys, s, REDUCE_BLOCKS["block_s"], 1024)
    lo, hi = row_windows(keys, s, pairs, **REDUCE_BLOCKS)
    want = _brute_windows(keys, s, pairs, REDUCE_BLOCKS["block_s"], 1024)
    assert (lo.tolist(), hi.tolist()) == want
    assert np.array_equal(
        ops.reduce_schedule(keys, s, **REDUCE_BLOCKS), np.stack([*pairs, lo, hi])
    )


@pytest.mark.parametrize("case", list(REDUCE_CASES))
def test_segment_reduce_counters(case):
    """The three counters of one call equal what the keys and shapes
    give: the scheduled windows, ``s_tiles × n_blocks × block_n / 8``,
    and one long-list call for unsorted keys."""
    build, full = REDUCE_CASES[case]
    keys, s = build()
    bs = REDUCE_BLOCKS["block_s"]
    cells = {}
    for r, k in enumerate(keys):
        if 0 <= k < s:
            lo, hi = cells.get((k // bs, r // 1024), (r, r))
            cells[(k // bs, r // 1024)] = (min(lo, r), max(hi, r))
    windows = sum(hi % 1024 // 8 - lo % 1024 // 8 + 1 for lo, hi in cells.values())
    obs.reset()
    _reduce(jnp.ones((len(keys), 1)), jnp.asarray(keys, jnp.int32), s, "min",
            **REDUCE_BLOCKS)
    want = {
        "kernel.segment_reduce_windows": windows,
        "kernel.segment_reduce_grid_windows": -(-s // bs) * 4 * 1024 // 8,
    }
    if full:
        want["kernel.segment_reduce_full"] = 1
    assert obs.counters() == want


@pytest.mark.parametrize(
    "built_at",
    [dict(block_s=32, block_n=1024), dict(block_s=16, block_n=2048), None],
    ids=["block_s", "block_n", "truncated"],
)
def test_segment_reduce_refuses_a_schedule_of_the_wrong_length(built_at):
    """A schedule built at other blocks, or cut short, has neither length
    the call's shapes allow: the call raises instead of leaving tiles
    unwritten."""
    keys, s = _reduce_sorted()
    sched = ops.reduce_schedule(keys, s, **(built_at or REDUCE_BLOCKS))
    if built_at is None:
        sched = sched[:, :-1]
    with pytest.raises(ValueError, match="schedule"):
        segment_reduce(
            jnp.ones((len(keys), 1)), jnp.asarray(keys, jnp.int32), s,
            schedule=sched, interpret=True, **REDUCE_BLOCKS,
        )


def test_semiring_block_not_multiple_of_kstep():
    """Same trailing-slice hazard in the semiring k-step loop."""
    a = jnp.asarray(RNG.normal(size=(16, 12)), dtype=jnp.float32)
    b = jnp.asarray(RNG.normal(size=(12, 8)), dtype=jnp.float32)
    got = semiring_matmul(a, b, semiring="min_add",
                          block_m=8, block_n=8, block_k=12, interpret=True)
    want = ref.semiring_matmul_ref(a, b, "min_add")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_segment_reduce_empty_bucket_identity():
    """Buckets no row maps to hold the reduction identity (±inf)."""
    data = jnp.asarray(RNG.normal(size=(4, 8)), dtype=jnp.float32)
    ids = jnp.asarray([0, 0, 2, 2], dtype=jnp.int32)
    lo = _reduce(data, ids, 4, "min")
    hi = _reduce(data, ids, 4, "max")
    assert np.all(np.asarray(lo)[1] == np.inf)
    assert np.all(np.asarray(hi)[3] == -np.inf)


def test_spmm_counts_exact_int_in_f32():
    """Counts are integral; f32 matmul must be exact below 2^24."""
    nnz, m, k, n = 300, 20, 20, 12
    rows = jnp.asarray(RNG.integers(0, m, size=nnz), dtype=jnp.int32)
    cols = jnp.asarray(RNG.integers(0, k, size=nnz), dtype=jnp.int32)
    vals = jnp.asarray(RNG.integers(1, 100, size=nnz), dtype=jnp.float32)
    dense = jnp.asarray(RNG.integers(0, 100, size=(k, n)), dtype=jnp.float32)
    got = _spmm(rows, cols, vals, dense, m)
    want = ref.coo_spmm_ref(rows, cols, vals, dense, m)
    assert np.array_equal(np.asarray(got), np.asarray(want))
