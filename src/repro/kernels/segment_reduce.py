"""Pallas TPU segment MIN/MAX reduction.

The sparse execution path (DESIGN.md §7) runs MIN/MAX aggregates as
(min, +) / (max, +) semiring message passing over the decomposition
tree; each hop is "reduce candidate rows into their group-key buckets
with min/max".  A TPU has no efficient scatter, so — exactly like
``segment_sum`` — the lowering builds a one-hot selector per
(segment-tile × row-tile) grid cell.  ``min``/``max`` have no MXU form,
so instead of a dot product the kernel reuses the
``semiring_matmul``-style k-slice loop: the selector becomes an
identity-or-±inf matrix ``A`` and the cell computes
``out[s, d] = reduce_r (A[s, r] + data[r, d])`` on the VPU.

Grid: ``(num_segment_tiles, num_row_tiles)``; the output tile is
revisited across the row axis and reduced in VMEM.  Rows with ids
outside ``[0, num_segments)`` contribute the identity (they are how the
wrapper pads).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_IDENT = {"min": jnp.inf, "max": -jnp.inf}

#: the segment axis writes disjoint output tiles (parallelizable); the
#: row axis revisits one output tile with a ``@pl.when(rj == 0)`` init +
#: reduce, so it must be sequential ("arbitrary") — see coo_spmm
DIM_SEMANTICS = ("parallel", "arbitrary")


def minmax_scatter(
    keys_ref, kcol_ref, rows_ref, out_ref, seg0, *, kind: str, k_step: int
):
    """Reduce the rows of ``rows_ref`` (n, d) into the resident output
    tile ``out_ref`` (block_s, d) with min/max: row ``r`` lands in tile
    row ``keys[r] - seg0``; keys outside the tile contribute the
    identity.

    ``k_step`` rows at a time, each window read from a ref (Mosaic has
    no in-kernel ``dynamic_slice`` of a value).  The 1-D keys are first
    staged as a ``(n, 1)`` column in the VMEM scratch ``kcol_ref``, so a
    key window is a sublane slice; the selector window
    ``A[s, j] = 0 if keys[lo + j] == seg0 + s else ±inf`` then broadcasts
    along lanes, and the window's candidates
    ``reduce_j (A[s, j] + rows[lo + j, :])`` reduce on the VPU.  Shared
    by ``segment_reduce`` and the min/max ``fused_hop``."""
    ident = _IDENT[kind]
    block_s = out_ref.shape[0]
    kcol_ref[...] = keys_ref[...][:, None]
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_s, k_step, 1), 0)
    red = jnp.minimum if kind == "min" else jnp.maximum
    red_axis = jnp.min if kind == "min" else jnp.max

    def body(i, acc):
        lo = pl.multiple_of(i * k_step, k_step)
        ids = kcol_ref[pl.ds(lo, k_step), :]  # (k_step, 1)
        a_sl = jnp.where(ids[None, :, :] - seg0 == iota, 0.0, ident).astype(
            acc.dtype
        )
        d_sl = rows_ref[pl.ds(lo, k_step), :]
        return red(acc, red_axis(a_sl + d_sl[None, :, :], axis=1))

    # exact: the wrappers pick k_step = gcd(block, 8), so it divides the
    # block row count by construction
    steps = rows_ref.shape[0] // k_step  # lint-ok: tile-floordiv
    out_ref[...] = jax.lax.fori_loop(0, steps, body, out_ref[...])


def _segment_reduce_kernel(
    ids_ref, data_ref, out_ref, kcol_ref, *, block_s: int, kind: str, k_step: int
):
    si = pl.program_id(0)
    rj = pl.program_id(1)

    @pl.when(rj == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _IDENT[kind])

    minmax_scatter(
        ids_ref, kcol_ref, data_ref, out_ref, si * block_s, kind=kind, k_step=k_step
    )


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "kind", "block_s", "block_n", "interpret"),
)
def segment_reduce(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    kind: str = "min",
    block_s: int = 128,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Reduce rows of ``data`` (n, d) into ``num_segments`` buckets with
    min/max; empty buckets hold the identity (``+inf``/``-inf``).

    ids outside [0, num_segments) are dropped, matching
    ``segment_reduce_ref`` for in-range ids."""
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret)
    block_s = ops.normalize_block("block_s", block_s)
    block_n = ops.normalize_block("block_n", block_n, ops.EDGE_GRANULE)
    if kind not in _IDENT:
        raise ValueError(f"unknown reduction {kind!r}")
    n, d = data.shape
    n_pad = -n % block_n
    s_pad = -num_segments % block_s
    if n_pad:
        data = jnp.pad(data, ((0, n_pad), (0, 0)))
        # padded rows get an out-of-range id -> contribute the identity
        segment_ids = jnp.pad(segment_ids, (0, n_pad), constant_values=-1)
    s_total = num_segments + s_pad
    grid = (s_total // block_s, data.shape[0] // block_n)
    # k_step must divide block_n exactly or the fori_loop drops the
    # trailing rows of every block; normalize_block above guarantees it
    k_step = ops.k_step_for(block_n)
    out = pl.pallas_call(
        functools.partial(
            _segment_reduce_kernel, block_s=block_s, kind=kind, k_step=k_step
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n,), lambda si, rj: (rj,)),
            pl.BlockSpec((block_n, d), lambda si, rj: (rj, 0)),
        ],
        out_specs=pl.BlockSpec((block_s, d), lambda si, rj: (si, 0)),
        out_shape=jax.ShapeDtypeStruct((s_total, d), data.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
        name="segment_reduce",  # the custom call's name in HLO and device traces
    )(segment_ids.astype(jnp.int32), data)
    return out[:num_segments]
