"""Pallas TPU COO sparse-matrix × dense-matrix product.

This is the JOIN-AGG traversal hot-spot: propagating a dense message
through a relation's sparse multiplicity tensor
(``out[r, :] += val_e * msg[c_e, :]`` over edges ``e=(r, c)``).

TPU adaptation: no dynamic gather/scatter — both sides become one-hot
matmuls that run on the MXU:

    gathered = one_hot(cols | k-tile) @ dense_ktile        (edges × N)
    out_mtile += (one_hot(rows | m-tile) * vals) @ gathered

Grid ``(m_tiles, e_tiles, k_tiles)``; the output tile accumulates in VMEM
across the two inner axes.  Edges need no ordering — padding uses
out-of-range ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: grid-axis semantics: the m axis writes disjoint output tiles
#: (parallelizable), but the e and k axes revisit one output tile with
#: a ``@pl.when`` init + accumulate — they MUST run sequentially, which
#: only TPU's default grid order guarantees.  Declaring them
#: ``arbitrary`` makes that requirement explicit so a GPU lowering
#: cannot race the init against another revisit.
DIM_SEMANTICS = ("parallel", "arbitrary", "arbitrary")

#: full f32 contraction: a one-hot matmul must reproduce its operand
_EXACT = jax.lax.Precision.HIGHEST


def _coo_spmm_kernel(
    rows_ref, cols_ref, vals_ref, dense_ref, out_ref, *, block_m: int, block_k: int
):
    mi = pl.program_id(0)
    ei = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when((ei == 0) & (ki == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    rows = rows_ref[...]  # (block_e,)
    cols = cols_ref[...]
    vals = vals_ref[...]
    dtype = out_ref.dtype

    k0 = ki * block_k
    # gather dense rows via one-hot matmul: (block_e, block_k) @ (block_k, n)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (rows.shape[0], block_k), 1)
    sel_k = (cols[:, None] - k0 == iota_k).astype(dtype)
    gathered = jnp.dot(
        sel_k, dense_ref[...], preferred_element_type=dtype, precision=_EXACT
    )

    m0 = mi * block_m
    iota_m = jax.lax.broadcasted_iota(jnp.int32, (block_m, rows.shape[0]), 0)
    scatter_m = (rows[None, :] - m0 == iota_m).astype(dtype) * vals[None, :]
    out_ref[...] += jnp.dot(
        scatter_m, gathered, preferred_element_type=dtype, precision=_EXACT
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "block_m", "block_e", "block_k", "interpret"),
)
def coo_spmm(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    dense: jax.Array,
    num_rows: int,
    block_m: int = 128,
    block_e: int = 1024,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """out (num_rows, n) with out[rows[i]] += vals[i] * dense[cols[i]]."""
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret)
    block_m = ops.normalize_block("block_m", block_m)
    block_e = ops.normalize_block("block_e", block_e, ops.EDGE_GRANULE)
    block_k = ops.normalize_block("block_k", block_k)
    nnz = rows.shape[0]
    k, n = dense.shape
    e_pad = -nnz % block_e
    if e_pad:
        rows = jnp.pad(rows, (0, e_pad), constant_values=-1)
        cols = jnp.pad(cols, (0, e_pad), constant_values=-1)
        vals = jnp.pad(vals, (0, e_pad))
    k_pad = -k % block_k
    if k_pad:
        dense = jnp.pad(dense, ((0, k_pad), (0, 0)))
    m_pad = -num_rows % block_m
    m_total = num_rows + m_pad
    grid = (m_total // block_m, rows.shape[0] // block_e, dense.shape[0] // block_k)
    out = pl.pallas_call(
        functools.partial(_coo_spmm_kernel, block_m=block_m, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e,), lambda mi, ei, ki: (ei,)),
            pl.BlockSpec((block_e,), lambda mi, ei, ki: (ei,)),
            pl.BlockSpec((block_e,), lambda mi, ei, ki: (ei,)),
            pl.BlockSpec((block_k, n), lambda mi, ei, ki: (ki, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, n), lambda mi, ei, ki: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((m_total, n), dense.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
        name="coo_spmm",  # the custom call's name in HLO and device traces
    )(rows.astype(jnp.int32), cols.astype(jnp.int32), vals.astype(dense.dtype), dense)
    return out[:num_rows]
