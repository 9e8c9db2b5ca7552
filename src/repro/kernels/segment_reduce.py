"""Pallas TPU segment MIN/MAX reduction.

The sparse execution path (DESIGN.md §7) runs MIN/MAX aggregates as
(min, +) / (max, +) semiring message passing over the decomposition
tree; each hop is "reduce candidate rows into their group-key buckets
with min/max".  A TPU has no efficient scatter, so — exactly like
``segment_sum`` — the lowering builds a one-hot selector per
(segment tile, row block) cell.  ``min``/``max`` have no MXU form,
so instead of a dot product the kernel reuses the
``semiring_matmul``-style k-slice loop: the selector becomes an
identity-or-±inf matrix ``A`` and the cell computes
``out[s, d] = reduce_r (A[s, r] + data[r, d])`` on the VPU.

Grid: ``(G,)`` over a scalar-prefetched schedule, ``coo_spmm``'s pair
list (:func:`~repro.kernels.coo_spmm.spmm_pair_list`) at this kernel's
blocks: one step per (segment tile, row block) cell that holds a key of
the tile, sorted by tile, so each output tile is reduced in VMEM over
its own run of consecutive steps.  Each step also carries the first and
last 8-row window of its block that holds a key of its tile, and
reduces only those windows.  CSR-sorted keys (the engine's order) give
the short list and, per step, the tile's own run of rows; keys in any
other order take the long list and, at worst, the whole block, and
stay exact.  Rows with ids outside ``[0, num_segments)`` contribute the
identity (they are how the wrapper pads).

:func:`reduce_schedule` builds the schedule on the host from the keys
and counts its windows; :func:`segment_reduce` checks that it fits the
shapes and counts the full grid's windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.coo_spmm import _decode, spmm_pair_list

_IDENT = {"min": jnp.inf, "max": -jnp.inf}

#: the one schedule axis revisits an output tile over its consecutive
#: steps with a ``@pl.when`` init + reduce, so it must run sequentially
#: ("arbitrary") — see coo_spmm
DIM_SEMANTICS = ("arbitrary",)


def minmax_scatter(
    keys_ref, kcol_ref, rows_ref, out_ref, seg0, *, kind: str, k_step: int,
    lo=0, hi=None,
):
    """Reduce the rows of ``rows_ref`` (n, d) into the resident output
    tile ``out_ref`` (block_s, d) with min/max: row ``r`` lands in tile
    row ``keys[r] - seg0``; keys outside the tile contribute the
    identity.

    ``k_step`` rows at a time, each window read from a ref (Mosaic has
    no in-kernel ``dynamic_slice`` of a value).  The 1-D keys are first
    staged as a ``(n, 1)`` column in the VMEM scratch ``kcol_ref``, so a
    key window is a sublane slice; the selector window
    ``A[s, j] = 0 if keys[r0 + j] == seg0 + s else ±inf`` then broadcasts
    along lanes, and the window's candidates
    ``reduce_j (A[s, j] + rows[r0 + j, :])`` reduce on the VPU.  Only
    the windows ``[lo, hi)`` are reduced (scalars, possibly read from a
    schedule), by default all of them.  Shared by ``segment_reduce`` and
    the min/max ``fused_hop``."""
    ident = _IDENT[kind]
    block_s = out_ref.shape[0]
    kcol_ref[...] = keys_ref[...][:, None]
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_s, k_step, 1), 0)
    red = jnp.minimum if kind == "min" else jnp.maximum
    red_axis = jnp.min if kind == "min" else jnp.max

    def body(i, acc):
        r0 = pl.multiple_of(i * k_step, k_step)
        ids = kcol_ref[pl.ds(r0, k_step), :]  # (k_step, 1)
        a_sl = jnp.where(ids[None, :, :] - seg0 == iota, 0.0, ident).astype(
            acc.dtype
        )
        d_sl = rows_ref[pl.ds(r0, k_step), :]
        return red(acc, red_axis(a_sl + d_sl[None, :, :], axis=1))

    if hi is None:
        # exact: the wrappers pick k_step = gcd(block, 8), so it divides
        # the block row count by construction
        hi = rows_ref.shape[0] // k_step  # lint-ok: tile-floordiv
    out_ref[...] = jax.lax.fori_loop(lo, hi, body, out_ref[...])


def _segment_reduce_kernel(
    sched_ref, ids_ref, data_ref, out_ref, kcol_ref,
    *, block_s: int, kind: str, k_step: int, grid_len: int,
):
    g = pl.program_id(0)
    si = sched_ref[g]
    first = (g == 0) | (si != sched_ref[jnp.maximum(g - 1, 0)])

    @pl.when(first)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, _IDENT[kind])

    @pl.when(sched_ref[grid_len + g] >= 0)
    def _reduce():
        minmax_scatter(
            ids_ref, kcol_ref, data_ref, out_ref, si * block_s, kind=kind,
            k_step=k_step, lo=sched_ref[2 * grid_len + g],
            hi=sched_ref[3 * grid_len + g],
        )


def _blocks(block_s: int, block_n: int) -> tuple[int, int, int]:
    """Normalized ``(block_s, block_n, k_step)``."""
    from repro.kernels import ops

    block_s = ops.normalize_block("block_s", block_s)
    block_n = ops.normalize_block("block_n", block_n, ops.EDGE_GRANULE)
    return block_s, block_n, ops.k_step_for(block_n)


def row_windows(
    keys: np.ndarray, num_segments: int, pairs: np.ndarray,
    block_s: int = 128, block_n: int = 1024,
) -> tuple[np.ndarray, np.ndarray]:
    """For each entry of the pair list ``pairs`` (:func:`spmm_pair_list`
    of ``keys``), the windows ``[lo, hi)`` of its row block that hold a
    key of its segment tile: from the window of the block's first such
    row to that of its last.  Entries that reduce nothing (init-only,
    tail) get ``lo == hi == 0``.  Rows of other tiles between the two
    still pass the kernel's key test as the identity, so any row order
    is exact; sorted keys make the range the tile's own run."""
    block_s, block_n, k_step = _blocks(block_s, block_n)
    ids = np.flatnonzero((keys >= 0) & (keys < num_segments))
    tile, block = keys[ids] // block_s, ids // block_n
    if tile.size and np.any(tile[1:] < tile[:-1]):
        # rows ascend within each (tile, block) cell after a stable sort
        cell = tile.astype(np.int64) * (block[-1] + 1) + block
        order = np.argsort(cell, kind="stable")
        tile, block, ids = tile[order], block[order], ids[order]
    # a cell's rows are a run: the entries of the list's live pairs, in order
    new = np.r_[True, (tile[1:] != tile[:-1]) | (block[1:] != block[:-1])]
    new = new[: tile.size]
    first, last = ids[new], ids[np.r_[new[1:], True][: tile.size]]
    lo = np.zeros(pairs.shape[1], np.int32)
    hi = np.zeros(pairs.shape[1], np.int32)
    live = pairs[1] >= 0
    lo[live] = first % block_n // k_step
    hi[live] = last % block_n // k_step + 1
    return lo, hi


def reduce_schedule(
    keys: np.ndarray, num_segments: int, block_s: int = 128, block_n: int = 1024
) -> np.ndarray:
    """The schedule of :func:`segment_reduce` for host ``keys``: int32
    ``(4, G)``, rows ``[0]`` the segment tile and ``[1]`` the pair code
    of :func:`spmm_pair_list` at these blocks, ``[2]``/``[3]`` the
    windows ``[lo, hi)`` of :func:`row_windows`.  Counts the windows it
    schedules in ``kernel.segment_reduce_windows`` (the engine builds
    one schedule per call; the counts of the grid are the call's)."""
    pairs = spmm_pair_list(keys, num_segments, block_s, block_n)
    lo, hi = row_windows(keys, num_segments, pairs, block_s, block_n)
    obs.count("kernel.segment_reduce_windows", int((hi - lo).sum()))
    return np.concatenate([pairs, lo[None], hi[None]])


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "kind", "block_s", "block_n", "interpret"),
)
def segment_reduce_scheduled(
    data: jax.Array,
    segment_ids: jax.Array,
    schedule: jax.Array,
    num_segments: int,
    kind: str = "min",
    block_s: int = 128,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """:func:`segment_reduce` without its checks and counters: the
    jitted form that the chip smoke and the compile tests lower."""
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret)
    block_s, block_n, k_step = _blocks(block_s, block_n)
    n, d = data.shape
    n_pad = max(-(-n // block_n), 1) * block_n - n
    if n_pad:
        data = jnp.pad(data, ((0, n_pad), (0, 0)))
        # padded rows get an out-of-range id -> contribute the identity
        segment_ids = jnp.pad(segment_ids, (0, n_pad), constant_values=-1)
    s_total = -(-num_segments // block_s) * block_s
    grid_len = schedule.shape[1]

    def row_block(g, p):
        return (_decode(p[grid_len + g]),)

    out = pl.pallas_call(
        functools.partial(
            _segment_reduce_kernel, block_s=block_s, kind=kind, k_step=k_step,
            grid_len=grid_len,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(grid_len,),
            in_specs=[
                pl.BlockSpec((block_n,), row_block),
                pl.BlockSpec((block_n, d), lambda g, p: (*row_block(g, p), 0)),
            ],
            out_specs=pl.BlockSpec((block_s, d), lambda g, p: (p[g], 0)),
            scratch_shapes=[pltpu.VMEM((block_n, 1), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((s_total, d), data.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
        name="segment_reduce",  # the custom call's name in HLO and device traces
    )(schedule.reshape(-1), segment_ids.astype(jnp.int32), data)
    return out[:num_segments]


def segment_reduce(
    data,
    segment_ids,
    num_segments: int,
    kind: str = "min",
    *,
    schedule,
    block_s: int = 128,
    block_n: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Reduce rows of ``data`` (n, d) into ``num_segments`` buckets with
    min/max; empty buckets hold the identity (``+inf``/``-inf``).

    ids outside [0, num_segments) are dropped, matching
    ``segment_reduce_ref`` for in-range ids.  ``schedule`` is
    :func:`reduce_schedule` of these ids at these blocks, built where the
    ids are on the host and uploaded with them.  A schedule of neither
    length it can have for these shapes raises ``ValueError``.  Counts,
    once per call: ``kernel.segment_reduce_grid_windows`` (the windows
    of the full ``s_tiles × n_blocks`` grid) and
    ``kernel.segment_reduce_full`` (calls whose ids took the long list);
    the schedule's own windows are counted where it is built."""
    if kind not in _IDENT:
        raise ValueError(f"unknown reduction {kind!r}")
    block_s, block_n, k_step = _blocks(block_s, block_n)
    s_tiles = -(-num_segments // block_s)
    n_blocks = max(-(-data.shape[0] // block_n), 1)
    short, grid_len = s_tiles + n_blocks - 1, schedule.shape[-1]
    if schedule.shape[0] != 4 or grid_len not in (short, s_tiles * n_blocks):
        raise ValueError(
            f"a schedule of shape {tuple(schedule.shape)} was not built for "
            f"{data.shape[0]} rows into {num_segments} segments at "
            f"block_s={block_s}, block_n={block_n}"
        )
    obs.count(
        "kernel.segment_reduce_grid_windows",
        s_tiles * n_blocks * (block_n // k_step),
    )
    if grid_len > short:
        obs.count("kernel.segment_reduce_full")
    if grid_len == 0:  # no segment
        return jnp.zeros((0, data.shape[1]), data.dtype)
    return segment_reduce_scheduled(
        data, segment_ids, schedule, num_segments=num_segments, kind=kind,
        block_s=block_s, block_n=block_n, interpret=interpret,
    )