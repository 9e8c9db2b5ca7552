"""Pallas TPU COO sparse-matrix × dense-matrix product.

This is the JOIN-AGG traversal hot-spot: propagating a dense message
through a relation's sparse multiplicity tensor
(``out[r, :] += val_e * msg[c_e, :]`` over edges ``e=(r, c)``).

TPU adaptation: no dynamic gather/scatter — both sides become one-hot
matmuls that run on the MXU:

    gathered = one_hot(cols | k-tile) @ dense_ktile        (edges × N)
    out_mtile += (one_hot(rows | m-tile) * vals) @ gathered

The grid is ``(G, k_tiles)``: a pair list, scalar-prefetched, names
the (output tile, edge block) cells that hold an edge, sorted by output
tile, so each output tile accumulates over its own run of consecutive
pairs and no tile steps through edge blocks that miss it.  Row-sorted
edges (the engine's grouped-CSR order) give at most ``m_tiles + e_tiles
- 1`` pairs, the short length ``G`` (:func:`pair_grid_len`); rows
scattered past it take the long length ``m_tiles * e_tiles``, which no
row order exceeds.  ``G`` is one of two static lengths, so a hop shape
compiles at most twice.

:func:`spmm_pair_list` builds the list on the host from the rows;
:func:`coo_spmm` checks that it fits the shapes and counts the steps.
Edges need no ordering for correctness — padding uses out-of-range ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

#: grid-axis semantics: the pair axis revisits an output tile over its
#: consecutive pairs and the k axis accumulates into it, each with a
#: ``@pl.when`` init + accumulate, so both MUST run sequentially.  v5e
#: has one TensorCore, so no axis loses a ``parallel`` split there.
DIM_SEMANTICS = ("arbitrary", "arbitrary")

#: full f32 contraction: a one-hot matmul must reproduce its operand
_EXACT = jax.lax.Precision.HIGHEST


def _decode(code):
    """Edge block of a pair code: ``e`` for a pair that accumulates,
    ``-(e + 1)`` for one that only holds its place (init-only or tail)."""
    return jnp.where(code < 0, -code - 1, code)


def _coo_spmm_kernel(
    pairs_ref, rows_ref, cols_ref, vals_ref, dense_ref, out_ref,
    *, block_m: int, block_k: int, grid_len: int,
):
    g = pl.program_id(0)
    ki = pl.program_id(1)
    mi = pairs_ref[g]
    first = (g == 0) | (mi != pairs_ref[jnp.maximum(g - 1, 0)])

    @pl.when(first & (ki == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(pairs_ref[grid_len + g] >= 0)
    def _accumulate():
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


def _blocks(block_m: int, block_e: int, block_k: int) -> tuple[int, int, int]:
    from repro.kernels import ops

    return (
        ops.normalize_block("block_m", block_m),
        ops.normalize_block("block_e", block_e, ops.EDGE_GRANULE),
        ops.normalize_block("block_k", block_k),
    )


def _tiles(nnz: int, num_rows: int, block_m: int, block_e: int) -> tuple[int, int]:
    """(output tiles, edge blocks); no edge still pads one edge block."""
    return -(-num_rows // block_m), max(-(-nnz // block_e), 1)


def pair_grid_len(
    nnz: int, num_rows: int, block_m: int = 128, block_e: int = 1024
) -> int:
    """Short length ``G`` of the pair list for ``nnz`` edges into
    ``num_rows`` output rows: ``m_tiles + e_tiles - 1``.  Row-sorted edges
    step to a new (tile, block) cell only where the tile or the edge block
    changes, so their pairs and the empty tiles' entries number at most
    that.  Rows scattered past it take ``m_tiles * e_tiles``."""
    block_m, block_e, _ = _blocks(block_m, block_e, 8)
    m_tiles, e_tiles = _tiles(nnz, num_rows, block_m, block_e)
    return m_tiles + e_tiles - 1


def spmm_pair_list(
    rows: np.ndarray, num_rows: int, block_m: int = 128, block_e: int = 1024
) -> np.ndarray:
    """The pair grid's schedule for host ``rows``.

    Returns int32 ``(2, G)``: ``[0]`` the output tile, ``[1]`` the pair
    code (:func:`_decode`), sorted by tile then edge block.  A cell
    (tile, block) is a pair when the block holds a row in ``[0,
    num_rows)`` of the tile; every tile without one gets an init-only
    entry, so it comes back zero; the tail repeats the last entry as
    a no-op, so no other tile is written back.  Init-only and tail
    entries keep the edge block of the entry before them, so they fetch
    nothing.  ``G`` is :func:`pair_grid_len`, or ``m_tiles * e_tiles``
    where the entries exceed it (rows too scattered); each tile holds
    at least one entry and at most ``e_tiles``, so they never exceed
    that."""
    block_m, block_e, _ = _blocks(block_m, block_e, 8)
    m_tiles, e_tiles = _tiles(len(rows), num_rows, block_m, block_e)
    ids = np.flatnonzero((rows >= 0) & (rows < num_rows))
    cell = (rows[ids] // block_m).astype(np.int64) * e_tiles + ids // block_e
    if cell.size and np.all(cell[1:] >= cell[:-1]):
        cell = cell[np.r_[True, cell[1:] != cell[:-1]]]
    else:
        cell = np.unique(cell)
    tile, block = np.divmod(cell, e_tiles)
    empty = np.ones(m_tiles, bool)
    empty[tile] = False
    n = len(cell) + int(empty.sum())
    if n == 0:  # no output row
        return np.zeros((2, 0), np.int32)
    grid_len = pair_grid_len(len(rows), num_rows, block_m, block_e)
    if n > grid_len:
        grid_len = m_tiles * e_tiles
    tile = np.concatenate([tile, np.flatnonzero(empty)])
    live = np.r_[np.ones(len(cell), bool), np.zeros(n - len(cell), bool)]
    order = np.argsort(tile, kind="stable")
    tile, live = tile[order], live[order]
    block = np.concatenate([block, np.zeros(n - len(cell), np.int64)])[order]
    # a place-holder fetches the block of the live pair before it (or 0)
    src = np.maximum.accumulate(np.where(live, np.arange(n), -1))
    block = np.where(src >= 0, block[np.maximum(src, 0)], 0)
    code = np.where(live, block, -block - 1)
    out = np.empty((2, grid_len), np.int32)
    out[0, :n], out[1, :n] = tile, code
    out[0, n:], out[1, n:] = tile[-1], -block[-1] - 1
    return out


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "block_m", "block_e", "block_k", "interpret"),
)
def coo_spmm_pairs(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    dense: jax.Array,
    pairs: jax.Array,
    num_rows: int,
    block_m: int = 128,
    block_e: int = 1024,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """:func:`coo_spmm` without its checks and counters: the jitted form
    that the chip smoke and the compile tests lower."""
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret)
    block_m, block_e, block_k = _blocks(block_m, block_e, block_k)
    nnz = rows.shape[0]
    k, n = dense.shape
    e_pad = max(-(-nnz // block_e), 1) * block_e - nnz
    if e_pad:
        rows = jnp.pad(rows, (0, e_pad), constant_values=-1)
        cols = jnp.pad(cols, (0, e_pad), constant_values=-1)
        vals = jnp.pad(vals, (0, e_pad))
    k_pad = -k % block_k
    if k_pad:
        dense = jnp.pad(dense, ((0, k_pad), (0, 0)))
    m_total = -(-num_rows // block_m) * block_m
    grid_len = pairs.shape[1]
    k_last = -(-dense.shape[0] // block_k) - 1

    def edge_block(g, ki, p):
        return (_decode(p[grid_len + g]),)

    def dense_block(g, ki, p):
        # a place-holder pins the last k tile, which the step before it
        # already holds
        return (jnp.where(p[grid_len + g] < 0, k_last, ki), 0)

    out = pl.pallas_call(
        functools.partial(
            _coo_spmm_kernel, block_m=block_m, block_k=block_k, grid_len=grid_len
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid_len, k_last + 1),
            in_specs=[
                pl.BlockSpec((block_e,), edge_block),
                pl.BlockSpec((block_e,), edge_block),
                pl.BlockSpec((block_e,), edge_block),
                pl.BlockSpec((block_k, n), dense_block),
            ],
            out_specs=pl.BlockSpec((block_m, n), lambda g, ki, p: (p[g], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_total, n), dense.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
        name="coo_spmm",  # the custom call's name in HLO and device traces
    )(
        pairs.reshape(-1), rows.astype(jnp.int32), cols.astype(jnp.int32),
        vals.astype(dense.dtype), dense,
    )
    return out[:num_rows]


def coo_spmm(
    rows,
    cols,
    vals,
    dense,
    num_rows: int,
    *,
    pairs,
    block_m: int = 128,
    block_e: int = 1024,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """out (num_rows, n) with out[rows[i]] += vals[i] * dense[cols[i]].

    ``pairs`` is :func:`spmm_pair_list` of these ``rows`` at these blocks,
    built where the rows are on the host and uploaded with them.  A list
    of neither length it can have for these shapes (built at other
    blocks, or for other edges) raises ``ValueError``.  Counts, once per
    call: ``kernel.coo_spmm_steps`` (grid steps run),
    ``kernel.coo_spmm_grid_steps`` (those of the full ``m_tiles ×
    e_tiles × k_tiles`` grid) and ``kernel.coo_spmm_full`` (calls whose
    rows took the long list)."""
    block_m, block_e, block_k = _blocks(block_m, block_e, block_k)
    m_tiles, e_tiles = _tiles(rows.shape[0], num_rows, block_m, block_e)
    short, grid_len = m_tiles + e_tiles - 1, pairs.shape[-1]
    if pairs.shape[0] != 2 or grid_len not in (short, m_tiles * e_tiles):
        raise ValueError(
            f"a pair list of shape {tuple(pairs.shape)} was not built for "
            f"{rows.shape[0]} edges into {num_rows} rows at block_m={block_m}, "
            f"block_e={block_e}"
        )
    k_tiles = -(-dense.shape[0] // block_k)
    obs.count("kernel.coo_spmm_grid_steps", m_tiles * e_tiles * k_tiles)
    obs.count("kernel.coo_spmm_steps", grid_len * k_tiles)
    if grid_len > short:
        obs.count("kernel.coo_spmm_full")
    return coo_spmm_pairs(
        rows, cols, vals, dense, pairs, num_rows=num_rows, block_m=block_m,
        block_e=block_e, block_k=block_k, interpret=interpret,
    )
