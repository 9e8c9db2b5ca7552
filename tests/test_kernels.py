"""Per-kernel correctness: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize(
    "nnz,m,k,n", [(200, 32, 24, 16), (2049, 130, 257, 128), (5, 8, 8, 8)]
)
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_coo_spmm(nnz, m, k, n, dtype):
    rows = jnp.asarray(RNG.integers(0, m, size=nnz), dtype=jnp.int32)
    cols = jnp.asarray(RNG.integers(0, k, size=nnz), dtype=jnp.int32)
    vals = jnp.asarray(RNG.integers(1, 5, size=nnz), dtype=dtype)
    dense = jnp.asarray(RNG.normal(size=(k, n)), dtype=dtype)
    got = coo_spmm(rows, cols, vals, dense, num_rows=m,
                   block_m=16, block_e=64, block_k=32, interpret=True)
    want = ref.coo_spmm_ref(rows, cols, vals, dense, m)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


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


@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize("n,d,s", [(100, 8, 16), (2049, 128, 130), (1, 8, 3)])
def test_segment_reduce(kind, n, d, s):
    data = jnp.asarray(RNG.normal(size=(n, d)), dtype=jnp.float32)
    ids = jnp.asarray(RNG.integers(0, s, size=n), dtype=jnp.int32)
    got = segment_reduce(
        data, ids, num_segments=s, kind=kind, block_s=16, block_n=64,
        interpret=True,
    )
    want = ref.segment_reduce_ref(data, ids, s, kind)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_reduce_block_not_multiple_of_kstep():
    """Regression: block_n not divisible by the k-slice step used to drop
    the trailing rows of every block (12 // 8 == 1 loop step).  Two full
    row blocks and a partial one after rounding to the edge granule."""
    n = 2 * ops.EDGE_GRANULE + 24
    data = jnp.asarray(RNG.normal(size=(n, 4)), dtype=jnp.float32)
    ids = jnp.asarray(RNG.integers(0, 6, size=n), dtype=jnp.int32)
    got = segment_reduce(
        data, ids, num_segments=6, kind="min", block_s=8, block_n=12,
        interpret=True,
    )
    want = ref.segment_reduce_ref(data, ids, 6, "min")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


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
    lo = segment_reduce(data, ids, num_segments=4, kind="min", interpret=True)
    hi = segment_reduce(data, ids, num_segments=4, kind="max", interpret=True)
    assert np.all(np.asarray(lo)[1] == np.inf)
    assert np.all(np.asarray(hi)[3] == -np.inf)


def test_spmm_counts_exact_int_in_f32():
    """Counts are integral; f32 matmul must be exact below 2^24."""
    nnz, m, k, n = 300, 20, 20, 12
    rows = jnp.asarray(RNG.integers(0, m, size=nnz), dtype=jnp.int32)
    cols = jnp.asarray(RNG.integers(0, k, size=nnz), dtype=jnp.int32)
    vals = jnp.asarray(RNG.integers(1, 100, size=nnz), dtype=jnp.float32)
    dense = jnp.asarray(RNG.integers(0, 100, size=(k, n)), dtype=jnp.float32)
    got = coo_spmm(rows, cols, vals, dense, num_rows=m, interpret=True)
    want = ref.coo_spmm_ref(rows, cols, vals, dense, m)
    assert np.array_equal(np.asarray(got), np.asarray(want))
