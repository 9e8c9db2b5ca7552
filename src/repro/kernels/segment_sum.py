"""Pallas TPU segment-sum via one-hot MXU matmul.

The paper's load-time pre-aggregation and the final group reduction are
segment sums.  A TPU has no efficient scatter; the idiomatic lowering is
``out_tile += one_hot(segment_ids) @ data_tile`` — a systolic matmul per
(segment-tile × row-tile) grid cell, which keeps everything in VMEM and
runs on the MXU instead of pointer-chasing.

Grid: ``(num_segment_tiles, num_row_tiles)``; the output tile is revisited
across the row axis and accumulated in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: the segment axis writes disjoint output tiles (parallelizable); the
#: row axis revisits one output tile with a ``@pl.when(rj == 0)`` init +
#: accumulate, so it must be sequential ("arbitrary") — see coo_spmm
DIM_SEMANTICS = ("parallel", "arbitrary")


def _segment_sum_kernel(ids_ref, data_ref, out_ref, *, block_s: int):
    si = pl.program_id(0)
    rj = pl.program_id(1)

    @pl.when(rj == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # (block_n,) int32 (global segment ids)
    seg0 = si * block_s
    # one_hot[s, r] = 1 iff ids[r] == seg0 + s   -> (block_s, block_n)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_s, ids.shape[0]), 0)
    onehot = (ids[None, :] - seg0 == iota).astype(data_ref.dtype)
    out_ref[...] += jnp.dot(
        onehot,
        data_ref[...],
        preferred_element_type=out_ref.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_s", "block_n", "interpret")
)
def segment_sum(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    block_s: int = 128,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Sum rows of ``data`` (n, d) into ``num_segments`` buckets.

    ids outside [0, num_segments) are dropped (matching segment_sum_ref
    only for in-range ids; the ops wrapper guarantees in-range)."""
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret)
    block_s = ops.normalize_block("block_s", block_s)
    block_n = ops.normalize_block("block_n", block_n, ops.EDGE_GRANULE)
    n, d = data.shape
    n_pad = -n % block_n
    s_pad = -num_segments % block_s
    if n_pad:
        data = jnp.pad(data, ((0, n_pad), (0, 0)))
        # padded rows get an out-of-range id -> contribute nothing
        segment_ids = jnp.pad(segment_ids, (0, n_pad), constant_values=-1)
    s_total = num_segments + s_pad
    grid = (s_total // block_s, data.shape[0] // block_n)
    out = pl.pallas_call(
        functools.partial(_segment_sum_kernel, block_s=block_s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda si, rj: (rj,)),
            pl.BlockSpec((block_n, d), lambda si, rj: (rj, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, d), lambda si, rj: (si, 0)),
        out_shape=jax.ShapeDtypeStruct((s_total, d), data.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
        name="segment_sum",  # the custom call's name in HLO and device traces
    )(segment_ids.astype(jnp.int32), data)
    return out[:num_segments]
