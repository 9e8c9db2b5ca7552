"""Pallas TPU fused JOIN-AGG hop megakernel (DESIGN.md §13).

One decomposition-tree hop is gather → row-aligned channel product →
segment scatter.  The three-dispatch path runs those as separate
kernels, round-tripping the edge-sized ``(edges, width·k)`` product
through HBM twice.  This kernel fuses the whole hop: each grid cell
gathers the child message rows for one edge tile (one-hot matmuls,
``block_r`` row tiles at a time), forms the per-edge channel-diagonal
product in registers/VMEM, and reduces it straight into the resident
``(block_s, width·k)`` output tile — the edge-sized intermediate never
leaves VMEM.

Two variants share the wrapper:

* ``kind="sum"`` — (+, ×): weights multiply, child rows multiply
  channel-diagonally, the scatter is a one-hot MXU matmul.
* ``kind="min"``/``"max"`` — (min, +)/(max, +): weights and child rows
  add, the scatter is the ±inf-selector k-slice reduction from
  ``segment_reduce``.  Child messages carry ±inf identities for
  unreached rows; a gather matmul would turn those into ``0·inf = nan``,
  so the gather tracks a parallel finiteness mask and re-injects the
  identity after the product (bit-identical to the true-gather path).

Grid ``(s_tiles, e_tiles)``; the output tile is revisited across the
edge axis and accumulated/reduced in VMEM.  Edge tiles are multiples of
``ops.EDGE_GRANULE`` (the HBM tiling of 1-D arrays); the body walks each
one in ``SUB_E``-edge sub-chunks, so its VMEM temporaries do not grow
with the tile.  Edges need no ordering — padding uses key ``-1``
(matches no segment) and index ``0``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.segment_reduce import _IDENT, minmax_scatter

#: magnitudes at or above this are the ±inf identity in child messages
_FINITE_MAX = 3.0e38

#: the segment axis writes disjoint output tiles (parallelizable); the
#: edge axis revisits one output tile with a ``@pl.when(ei == 0)`` init
#: + accumulate/reduce, so it must be sequential ("arbitrary")
DIM_SEMANTICS = ("parallel", "arbitrary")

#: edges per pass of the kernel body: the gather, product and scatter
#: temporaries are ``(SUB_E, width·k)``, so the VMEM a cell needs does
#: not grow with the edge block (a multiple of ``ops.EDGE_GRANULE``)
SUB_E = 256


def _dot(a, b):
    # full f32 contraction: every one-hot / 0-1 expansion matmul here must
    # reproduce its selected operand exactly
    return jnp.dot(
        a,
        b,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


def _gather_sum(idx, msg_ref, block_r):
    """One-hot gather ``msg[idx]`` as ``block_r``-tiled MXU matmuls; each
    row tile is read from the message ref."""
    block_e = idx.shape[0]
    width_ck = msg_ref.shape[1]

    def body(ri, acc):
        r0 = pl.multiple_of(ri * block_r, block_r)
        iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_r), 1)
        sel = (idx[:, None] - r0 == iota_r).astype(jnp.float32)
        return acc + _dot(sel, msg_ref[pl.ds(r0, block_r), :])

    # exact: the wrapper pads child rows to a block_r multiple
    steps = msg_ref.shape[0] // block_r  # lint-ok: tile-floordiv
    return jax.lax.fori_loop(
        0, steps, body, jnp.zeros((block_e, width_ck), jnp.float32)
    )


def _gather_minmax(idx, msg_ref, block_r):
    """Like :func:`_gather_sum`, but ±inf identity entries gather as 0
    with a parallel 0/1 finiteness mask (a one-hot matmul against ±inf
    would produce nan)."""
    block_e = idx.shape[0]
    width_ck = msg_ref.shape[1]

    def body(ri, carry):
        acc, fin = carry
        r0 = pl.multiple_of(ri * block_r, block_r)
        iota_r = jax.lax.broadcasted_iota(jnp.int32, (block_e, block_r), 1)
        sel = (idx[:, None] - r0 == iota_r).astype(jnp.float32)
        chunk = msg_ref[pl.ds(r0, block_r), :]
        finite = (chunk > -_FINITE_MAX) & (chunk < _FINITE_MAX)
        vals = jnp.where(finite, chunk, 0.0)
        return (
            acc + _dot(sel, vals),
            fin + _dot(sel, finite.astype(jnp.float32)),
        )

    # exact: the wrapper pads child rows to a block_r multiple
    steps = msg_ref.shape[0] // block_r  # lint-ok: tile-floordiv
    zero = jnp.zeros((block_e, width_ck), jnp.float32)
    return jax.lax.fori_loop(0, steps, body, (zero, zero))


def _product_plan(widths: tuple[int, ...], k: int):
    """Static plan for the per-edge channel-diagonal product.

    With running product ``P`` (width ``wa``, layout width-major/k-minor)
    and gathered child rows ``G`` (width ``wc``), the next product is
    ``P'[(a·wc + c)·k + j] = P[a·k + j] ⊗ G[c·k + j]``.  Mosaic cannot
    reshape a lane axis into ``(width, k)``, so both sides are widened by
    0/1 expansion matmuls instead — ``P @ rep`` and ``G @ til`` — and
    combined elementwise.  An expansion that would be the identity, or a
    lane broadcast of a single column, is skipped.  Returns the per-child
    ``(rep_dot, til_dot)`` flags and the matrices in kernel-input order.
    """
    plan, mats = [], []
    wa = 1
    for wc in widths:
        q = np.arange(wa * wc * k)
        a, rem = np.divmod(q, wc * k)
        c, j = np.divmod(rem, k)
        rep_dot = wc > 1 and wa * k > 1
        til_dot = wa > 1
        if rep_dot:
            mats.append(np.arange(wa * k)[:, None] == (a * k + j)[None, :])
        if til_dot:
            mats.append(np.arange(wc * k)[:, None] == (c * k + j)[None, :])
        plan.append((rep_dot, til_dot))
        wa *= wc
    return tuple(plan), [m.astype(np.float32) for m in mats]


def _fused_hop_kernel(
    *refs,
    plan: tuple[tuple[bool, bool], ...],
    block_s: int,
    block_r: int,
    kind: str,
    k_step: int,
):
    nchild = len(plan)
    keys_ref, w_ref = refs[0], refs[1]
    idx_refs = refs[2 : 2 + nchild]
    msg_refs = refs[2 + nchild : 2 + 2 * nchild]
    rest = iter(refs[2 + 2 * nchild :])
    exp_refs = [
        (next(rest) if rep_dot else None, next(rest) if til_dot else None)
        for rep_dot, til_dot in plan
    ]
    out_ref = next(rest)
    si = pl.program_id(0)
    ei = pl.program_id(1)

    @pl.when(ei == 0)
    def _init():
        if kind == "sum":
            out_ref[...] = jnp.zeros_like(out_ref)
        else:
            out_ref[...] = jnp.full_like(out_ref, _IDENT[kind])

    def widen(p, g, *, rep_ref, til_ref):
        if rep_ref is not None:
            p = _dot(p, rep_ref[...])
        if til_ref is not None:
            g = _dot(g, til_ref[...])
        return p, g

    block_e = keys_ref.shape[0]
    sub_e = min(block_e, SUB_E)
    seg0 = si * block_s

    # the edge tile is walked in static sub-chunks of sub_e edges, so the
    # (edges, width·k) gather/product temporaries are sub_e rows deep
    # however large the HBM edge block is
    if kind == "sum":
        for c0 in range(0, block_e, sub_e):
            rows = pl.ds(c0, sub_e)
            acc = w_ref[rows, :]  # (sub_e, k)
            for idx_ref, msg_ref, (rep_ref, til_ref) in zip(
                idx_refs, msg_refs, exp_refs
            ):
                g = _gather_sum(idx_ref[rows], msg_ref, block_r)
                p, g = widen(acc, g, rep_ref=rep_ref, til_ref=til_ref)
                acc = p * g
            keys = keys_ref[rows]  # (sub_e,) int32 (global segment ids)
            iota_s = jax.lax.broadcasted_iota(jnp.int32, (block_s, sub_e), 0)
            onehot = (keys[None, :] - seg0 == iota_s).astype(jnp.float32)
            out_ref[...] += _dot(onehot, acc)  # acc: (sub_e, width·k)
        return

    # min/max: additive product with finiteness tracking; the candidates
    # go through a VMEM scratch ref so the k-slice scatter reads its
    # windows from a ref, exactly like segment_reduce
    cand_ref, kcol_ref = next(rest), next(rest)
    for c0 in range(0, block_e, sub_e):
        rows = pl.ds(c0, sub_e)
        acc = w_ref[rows, :]  # (sub_e, 1)
        ok = jnp.ones_like(acc)
        for idx_ref, msg_ref, (rep_ref, til_ref) in zip(
            idx_refs, msg_refs, exp_refs
        ):
            g, fin = _gather_minmax(idx_ref[rows], msg_ref, block_r)
            p, g = widen(acc, g, rep_ref=rep_ref, til_ref=til_ref)
            acc = p + g
            p, fin = widen(ok, fin, rep_ref=rep_ref, til_ref=til_ref)
            ok = p * fin
        cand_ref[rows, :] = jnp.where(ok > 0.5, acc, _IDENT[kind])
    minmax_scatter(
        keys_ref, kcol_ref, cand_ref, out_ref, seg0, kind=kind, k_step=k_step
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_segments",
        "k",
        "kind",
        "block_e",
        "block_s",
        "block_r",
        "interpret",
    ),
)
def fused_hop(
    keys: jax.Array,
    weights: jax.Array,
    child_msgs: tuple[jax.Array, ...],
    child_idx: tuple[jax.Array, ...],
    num_segments: int,
    k: int = 1,
    kind: str = "sum",
    block_e: int = 1024,
    block_s: int = 128,
    block_r: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused JOIN-AGG hop.

    ``keys`` (n,) raveled output segment per edge; ``weights`` (n, k)
    per-edge channel weights (k=1 additive payload for min/max);
    ``child_msgs[c]`` (rows_c, width_c·k) the c-th child's flattened
    message (width-major, k-minor); ``child_idx[c]`` (n,) the edge→row
    gather index into it.  Returns ``(num_segments, width·k)`` f32 with
    ``width = Π width_c`` — empty segments hold 0 (sum) or ±inf
    (min/max), exactly like the three-dispatch path before masking.
    """
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret)
    block_e = ops.normalize_block("block_e", block_e, ops.EDGE_GRANULE)
    block_s = ops.normalize_block("block_s", block_s)
    block_r = ops.normalize_block("block_r", block_r)
    if kind not in ("sum", "min", "max"):
        raise ValueError(f"unknown hop kind {kind!r}")
    if num_segments < 1:
        raise ValueError(f"num_segments must be >= 1, got {num_segments}")
    if kind != "sum" and k != 1:
        raise ValueError("min/max hops are single-channel (k=1)")
    if len(child_msgs) != len(child_idx):
        raise ValueError("child_msgs and child_idx must pair up")
    n = keys.shape[0]
    f32 = jnp.float32

    weights = jnp.asarray(weights, f32).reshape(n, k) if n else jnp.zeros(
        (0, k), f32
    )
    widths = []
    for msg in child_msgs:
        width_ck = msg.shape[1]
        if width_ck % k != 0:
            raise ValueError(
                f"child message width {width_ck} is not a multiple of k={k}"
            )
        widths.append(width_ck // k)
    width = 1
    for wc in widths:
        width *= wc

    # pad edges to the block grid; at least one edge tile must exist or
    # the ``@pl.when(ei == 0)`` init never runs and the output tile is
    # uninitialized garbage
    e_pad = -n % block_e
    e_total = n + e_pad
    if e_total == 0:
        e_total = block_e
    pad_to = e_total - n
    keys = jnp.pad(keys.astype(jnp.int32), (0, pad_to), constant_values=-1)
    weights = jnp.pad(weights, ((0, pad_to), (0, 0)))
    idxs = tuple(
        jnp.pad(ix.astype(jnp.int32), (0, pad_to)) for ix in child_idx
    )

    # pad child rows to the gather tile; index 0 padding rows are never
    # referenced (real indices stay in range, padded edges never land)
    msgs = []
    for msg in child_msgs:
        msg = jnp.asarray(msg, f32)
        r_pad = -msg.shape[0] % block_r
        rows_total = msg.shape[0] + r_pad
        if rows_total == 0:
            rows_total = block_r
        fill = 0.0 if kind == "sum" else float(_IDENT[kind])
        msgs.append(
            jnp.pad(
                msg,
                ((0, rows_total - msg.shape[0]), (0, 0)),
                constant_values=fill,
            )
        )

    s_pad = -num_segments % block_s
    s_total = num_segments + s_pad
    grid = (s_total // block_s, e_total // block_e)
    out_width = max(width * k, 1)

    plan, exp_mats = _product_plan(tuple(widths), k)
    e_spec = pl.BlockSpec((block_e,), lambda si, ei: (ei,))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda si, ei: (0, 0))

    in_specs = [
        e_spec,
        pl.BlockSpec((block_e, k), lambda si, ei: (ei, 0)),
        *[e_spec for _ in idxs],
        # whole child messages (and the product's expansion matrices) are
        # resident per grid cell; the autotuner keeps candidate tiles
        # within the VMEM budget
        *[whole(m) for m in msgs],
        *[whole(m) for m in exp_mats],
    ]
    scratch = []
    if kind != "sum":
        scratch = [
            pltpu.VMEM((block_e, out_width), f32),
            pltpu.VMEM((block_e, 1), jnp.int32),
        ]
    out = pl.pallas_call(
        functools.partial(
            _fused_hop_kernel,
            plan=plan,
            block_s=block_s,
            block_r=block_r,
            kind=kind,
            k_step=ops.k_step_for(block_e),
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_s, out_width), lambda si, ei: (si, 0)),
        out_shape=jax.ShapeDtypeStruct((s_total, out_width), f32),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
        name="fused_hop",  # the custom call's name in HLO and device traces
    )(keys, weights, *idxs, *msgs, *(jnp.asarray(m) for m in exp_mats))
    return out[:num_segments]


# ----------------------------------------------------------------------
# VMEM and cost model (DESIGN.md §13): the autotuner keeps candidate
# tiles within VMEM_BYTES and ranks them by fused_hop_cost
# ----------------------------------------------------------------------

#: v5e peak bf16 FLOP/s and HBM bytes/s, the two terms of fused_hop_cost
PEAK_FLOPS = 197e12
HBM_BW = 819e9

#: per-core VMEM budget the autotuner keeps a fused-hop cell within
VMEM_BYTES = 16 * 2**20


def _vmem_tile(rows: int, cols: int) -> int:  # tile-math
    """Bytes of a 32-bit ``(rows, cols)`` array in VMEM, padded to the
    ``(8, 128)`` sublane × lane tiling."""
    return 4 * (-(-max(rows, 1) // 8) * 8) * (-(-max(cols, 1) // 128) * 128)


def fused_hop_vmem_bytes(  # tile-math
    block_e: int,
    block_s: int,
    block_r: int,
    child_rows: tuple[int, ...],
    child_widths: tuple[int, ...],
    width: int,
    k: int,
    kind: str = "sum",
) -> int:
    """Bytes of VMEM one fused-hop grid cell holds, padded to the
    ``(8, 128)`` tiling.

    Pipelined windows count twice (double-buffered): the edge tile's
    1-D key/index columns and ``(block_e, k)`` weights, and the output
    tile.  Whole-array windows — the child messages and the product's
    expansion matrices — are single-buffered and count once.  Body
    temporaries count once, at the kernel's ``SUB_E``-edge sub-chunk:
    the gather selector and its iota, each child's gathered tile (twice
    for min/max, which carries a finiteness mask), the widened product
    operands, the scatter selector and its iota; for min/max add the
    whole-tile candidate and key-column scratch plus one
    ``(block_s, 8, width)`` window.  Checked against what the TPU
    compiler allocates for a described v5e: on the tested shapes it
    over-counts, never under-counts."""
    rows_pad = [max(-(-r // block_r) * block_r, block_r) for r in child_rows]
    out_w = max(width * k, 1)
    piped = 4 * block_e * (1 + len(child_rows)) + _vmem_tile(block_e, k)
    piped += _vmem_tile(block_s, out_w)
    resident = sum(
        _vmem_tile(r, wc * k) for r, wc in zip(rows_pad, child_widths)
    )
    wa = 1
    for wc in child_widths:  # expansion matrices (see fused_hop)
        if wc > 1 and wa * k > 1:
            resident += _vmem_tile(wa * k, wa * wc * k)
        if wa > 1:
            resident += _vmem_tile(wc * k, wa * wc * k)
        wa *= wc
    minmax = kind != "sum"
    sub_e = min(block_e, SUB_E)
    temps = 2 * _vmem_tile(sub_e, block_r) + 2 * _vmem_tile(block_s, sub_e)
    temps += sum(
        (2 if minmax else 1) * _vmem_tile(sub_e, wc * k)
        for wc in child_widths
    )
    temps += 3 * _vmem_tile(sub_e, out_w)
    if minmax:
        temps += _vmem_tile(block_e, out_w) + _vmem_tile(block_e, 1)
        temps += 2 * block_s * _vmem_tile(8, out_w)
    return 2 * piped + resident + temps


def fused_hop_cost(  # tile-math
    edges: int,
    child_rows: tuple[int, ...],
    child_widths: tuple[int, ...],
    num_segments: int,
    k: int = 1,
    block_e: int = 1024,
    block_s: int = 128,
    block_r: int = 128,
) -> dict[str, float]:
    """Roofline estimate for one fused hop at the given tile config.

    FLOPs per grid cell: the one-hot gather matmuls
    (``2·block_e·rows_pad_c·width_c·k`` per child — the selector dot
    spans every padded child row) plus the segment scatter
    (``2·block_s·block_e·width·k``).  Cells = s_tiles × e_tiles.  HBM
    bytes: the edge arrays and child messages are re-read once per
    segment tile (the output tile is resident, the inputs stream), the
    output is written once.  Seconds = max(flops/PEAK_FLOPS,
    bytes/HBM_BW) — the standard two-term roofline.
    """
    width = 1
    for wc in child_widths:
        width *= wc
    e_tiles = max(-(-edges // block_e), 1)
    s_tiles = max(-(-num_segments // block_s), 1)
    rows_pad = [max(-(-r // block_r) * block_r, block_r) for r in child_rows]
    gather_flops = sum(
        2.0 * block_e * rp * wc * k for rp, wc in zip(rows_pad, child_widths)
    )
    scatter_flops = 2.0 * block_s * block_e * width * k
    flops = (gather_flops + scatter_flops) * e_tiles * s_tiles

    edge_bytes = 4.0 * block_e * e_tiles * (2 + len(child_rows) + k)
    msg_bytes = 4.0 * sum(
        rp * wc * k for rp, wc in zip(rows_pad, child_widths)
    )
    hbm = (edge_bytes + msg_bytes) * s_tiles + 4.0 * s_tiles * block_s * width * k
    seconds = max(flops / PEAK_FLOPS, hbm / HBM_BW)
    return {"flops": flops, "hbm_bytes": hbm, "seconds": seconds}
