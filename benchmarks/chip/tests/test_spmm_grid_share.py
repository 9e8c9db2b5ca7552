"""``spmm_grid_share``: the share of ``coo_spmm``'s full grid that the
program's calls ran, read from its ``kernel.*`` counters."""
from __future__ import annotations

import pytest

from test_program_spans import COUNTERS, ctx_of, fake_program, program_trace, reader

KERNEL = {"kernel.coo_spmm_steps": 5640, "kernel.coo_spmm_grid_steps": 169200}


def test_reads_the_kernel_counters(monkeypatch):
    fake_program(monkeypatch, {**COUNTERS, **KERNEL})
    got = reader("spmm_grid_share").read(ctx_of(program_trace()))
    assert got == pytest.approx(100 * 5640 / 169200)


@pytest.mark.parametrize(
    "counters",
    [None, COUNTERS, {**COUNTERS, "kernel.coo_spmm_grid_steps": 0}],
    ids=["no-registry", "no-kernel-counters", "no-grid"],
)
def test_none_where_the_program_counts_no_grid(monkeypatch, counters):
    """A program without ``repro.obs``, or one that ran no ``coo_spmm`` or
    keeps no kernel counters (such as the parent), gives no reading."""
    fake_program(monkeypatch, counters)
    assert reader("spmm_grid_share").read(ctx_of(program_trace())) is None


def test_reads_a_real_call(monkeypatch):
    """The counters of one pair-grid call on row-sorted edges, through the
    program's own registry."""
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.kernels import ops

    rows = np.sort(np.random.default_rng(0).integers(0, 1000, 4096))
    obs.reset("kernel.")
    ops.coo_spmm(
        jnp.asarray(rows, jnp.int32), jnp.zeros(4096, jnp.int32),
        jnp.ones(4096), jnp.ones((8, 8)), num_rows=1000,
        pairs=ops.spmm_pair_list(rows, 1000), interpret=True,
    )
    got = reader("spmm_grid_share").read(ctx_of(program_trace()))
    # 8 output tiles, 4 edge blocks, 1 child-row tile: 11 of 32 steps
    assert got == pytest.approx(100 * 11 / 32)
