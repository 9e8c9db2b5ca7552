"""``reduce_window_share``: the share of ``segment_reduce``'s full grid
of 8-row windows that the program's calls reduced, read from its
``kernel.*`` counters."""
from __future__ import annotations

import pytest

from test_program_spans import COUNTERS, ctx_of, fake_program, program_trace, reader

KERNEL = {
    "kernel.segment_reduce_windows": 39310,
    "kernel.segment_reduce_grid_windows": 2315776,
}


def test_reads_the_kernel_counters(monkeypatch):
    fake_program(monkeypatch, {**COUNTERS, **KERNEL})
    got = reader("reduce_window_share").read(ctx_of(program_trace()))
    assert got == pytest.approx(100 * 39310 / 2315776)


@pytest.mark.parametrize(
    "counters",
    [None, COUNTERS, {**COUNTERS, "kernel.segment_reduce_grid_windows": 0}],
    ids=["no-registry", "no-kernel-counters", "no-grid"],
)
def test_none_where_the_program_counts_no_windows(monkeypatch, counters):
    """A program without ``repro.obs``, or one that ran no
    ``segment_reduce`` or counts no windows (such as the parent), gives no
    reading."""
    fake_program(monkeypatch, counters)
    assert reader("reduce_window_share").read(ctx_of(program_trace())) is None


def test_reads_a_real_call(monkeypatch):
    """The counters of one scheduled call on sorted keys, through the
    program's own registry."""
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.kernels import ops

    keys = np.repeat(np.arange(4096 // 8), 8)  # one key per 8-row window
    obs.reset("kernel.")
    ops.segment_reduce(
        jnp.ones((4096, 1)), jnp.asarray(keys, jnp.int32), 512,
        schedule=ops.reduce_schedule(keys, 512), interpret=True,
    )
    got = reader("reduce_window_share").read(ctx_of(program_trace()))
    # 4 segment tiles × 4 row blocks × 128 windows; each window holds the
    # key of one tile, in the one block that holds that tile
    assert got == pytest.approx(100 * 512 / (4 * 4 * 128))
