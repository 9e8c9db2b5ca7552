"""JAX lowering of the JOIN-AGG contraction plan.

Two physical paths (DESIGN.md §2, §7):

* ``dense``  — every relation becomes a dense multiplicity tensor over its
  relevant attrs; the decomposition-tree contraction lowers to one jitted
  ``jnp.einsum`` program (MXU path; shardable with NamedSharding).  Fast
  at small domains, but the per-relation ``Π|dom(attrs)|`` tensors are
  the exact intermediate blowup JOIN-AGG exists to avoid.
* ``sparse`` — relations stay in grouped-CSR coordinate form
  (:class:`~repro.core.prepare.CSRView`) and every decomposition-tree hop
  runs on the Pallas kernels: ``coo_spmm`` for single-child hops,
  ``segment_sum`` for leaf/multi-child hops (per-edge products of child
  message rows), ``segment_reduce`` for MIN/MAX semiring hops.  No dense
  relation tensor is ever built; peak memory is the largest *message*,
  and group-axis row tiles (``stream``) bound even that.
  (``mode="kernels"`` is the legacy name for this path; it used to cover
  only chain-COUNT plans and silently computed COUNT for SUM queries.)

``mode="auto"`` picks per plan via :func:`choose_jax_path`.  Counts are
exact in f32 up to 2^24 per partial product on both paths; the ops guard
against silent overflow by checking the f64 numpy result in tests.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.prepare import Prepared, csr_restrict, grouped_csr, prepare
from repro.core.query import JoinAggQuery
from repro.core.tensor_engine import (
    ChannelTensorEngine,
    TensorEngine,
    channel_weight_matrices,
)
from repro.kernels.ops import EDGE_GRANULE
from repro.relational.relation import Database
from repro.serve.cache import LRUCache

MAX_DENSE_ELEMS = 1 << 26
# a single relation tensor beyond this many elements pushes the dense
# einsum path over its memory cliff; auto mode switches to sparse
DENSE_PROMOTE_ELEMS = 1 << 24


def _axis_letters(prep: Prepared) -> dict[str, str]:
    letters = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
    attrs = sorted({a for attrs in prep.schema.relevant.values() for a in attrs})
    if len(attrs) > len(letters):
        raise ValueError("too many attributes for einsum letters")
    return {a: letters[i] for i, a in enumerate(attrs)}


def dense_tensor(prep: Prepared, rel: str, dtype=np.float32) -> np.ndarray:
    """Scatter a relation's pre-aggregated COO rows into a dense tensor."""
    er = prep.encoded[rel]
    dims = tuple(prep.dicts[a].size for a in er.attrs)
    if int(np.prod(dims, dtype=np.int64)) > MAX_DENSE_ELEMS:
        raise MemoryError(
            f"dense tensor for {rel} would have {np.prod(dims)} elems; "
            "use the numpy streaming engine"
        )
    out = np.zeros(dims, dtype=dtype)
    idx = tuple(er.codes[:, i] for i in range(len(er.attrs)))
    np.add.at(out, idx, er.count.astype(dtype))
    return out


def _to_device(*arrays) -> list[jax.Array]:
    """Upload host arrays: every input of a hop or a dense program passes
    here, under one ``joinagg.h2d`` span, its bytes counted."""
    with obs.span("joinagg.h2d"):
        out = [jnp.asarray(a) for a in arrays]
    obs.count("transfer.h2d_bytes", sum(a.nbytes for a in out))
    return out


def _to_host(out: jax.Array) -> np.ndarray:
    """Download a kernel's or a program's result: the wait for the chip
    (``joinagg.wait``), then the copy (``joinagg.d2h``), its bytes
    counted.  The one sync point per launch, as a bare ``np.asarray``."""
    with obs.span("joinagg.wait"):
        out.block_until_ready()
    with obs.span("joinagg.d2h"):
        host = np.asarray(out)
    obs.count("transfer.d2h_bytes", host.nbytes)
    return host


@dataclass
class DenseProgram:
    """A jit-able closed-form COUNT/SUM program over dense relation tensors."""

    prep: Prepared
    fn: Callable[[dict[str, jax.Array]], jax.Array]
    tensor_attrs: dict[str, tuple[str, ...]]
    # hashable einsum-plan signature; programs with equal keys are the
    # same computation, so their traces/compilations are shared
    plan_key: tuple = ()

    def input_arrays(self, dtype=np.float32) -> dict[str, jax.Array]:
        rels = list(self.prep.encoded)
        return dict(zip(rels, _to_device(
            *(dense_tensor(self.prep, r, dtype) for r in rels)
        )))


# Plan-keyed program caches.  Repeated executions of structurally equal
# queries — most importantly the incremental maintainer's fold/cyclic
# refreshes, which rebuild a fresh ``Prepared`` per delta batch — reuse
# one traced+compiled program instead of re-jitting every refresh.
# Bounded: a jit wrapper retains one executable per input-shape
# combination, so long-lived processes (the query server above all) with
# many distinct query structures or steadily growing domains would
# otherwise accumulate compiled programs without bound.  The shared
# LRUCache evicts one coldest entry at a time (the old behaviour dropped
# the whole cache on overflow) and keeps hit/miss/eviction counters,
# surfaced by ``jit_cache_stats()`` and the server's ``stats()``.
_PROGRAM_CACHE_MAX = 32
_FN_CACHE = LRUCache(_PROGRAM_CACHE_MAX, name="einsum-fns")
_JIT_CACHE = LRUCache(_PROGRAM_CACHE_MAX, name="jit-programs")


def jit_cache_stats() -> dict[str, dict[str, int]]:
    """Counters of the process-wide program memos (DESIGN.md §9)."""
    return {
        "fns": {"size": len(_FN_CACHE), **_FN_CACHE.stats.snapshot()},
        "jits": {"size": len(_JIT_CACHE), **_JIT_CACHE.stats.snapshot()},
    }


def _dense_plan(prep: Prepared) -> tuple[tuple, str]:
    """Post-order einsum plan: ((rel, expr, child rels), ...), root."""
    ax = _axis_letters(prep)
    deco = prep.decomposition
    canonical = [attr for _, attr in prep.group_attrs]
    plan: list[tuple[str, str, tuple[str, ...]]] = []

    def subtree(rel: str, parent: str | None) -> str:
        er = prep.encoded[rel]
        exprs = ["".join(ax[a] for a in er.attrs)]
        gattrs = [prep.schema.group_of[rel]] if rel in prep.schema.group_of else []
        children = tuple(deco.nodes[rel].children)
        for child in children:
            cexpr = subtree(child, rel)
            exprs.append(cexpr)
            gattrs.extend(
                a for a in canonical if ax[a] in cexpr and a not in gattrs
            )
        if parent is None:
            up: list[str] = []
        else:
            up = sorted(set(er.attrs) & set(prep.encoded[parent].attrs))
        out_attrs = list(up) + [a for a in canonical if a in gattrs]
        out_axes = "".join(ax[a] for a in out_attrs)
        plan.append((rel, ",".join(exprs) + "->" + out_axes, children))
        return out_axes

    subtree(deco.root, None)
    return tuple(plan), deco.root


def _fn_from_plan(plan: tuple, root: str) -> Callable:
    def fn(tensors: dict[str, jax.Array]) -> jax.Array:  # jit-region
        results: dict[str, jax.Array] = {}
        for rel, expr, children in plan:
            results[rel] = jnp.einsum(
                expr, tensors[rel], *[results[c] for c in children]
            )
        return results[root]

    return fn


def build_dense_program(prep: Prepared) -> DenseProgram:
    """Construct the einsum message-passing program (COUNT semantics; SUM
    works by swapping the measure relation's tensor weights)."""
    plan, root = _dense_plan(prep)
    key = (plan, root)
    fn = _FN_CACHE.get_or_create(key, lambda: _fn_from_plan(plan, root))
    return DenseProgram(
        prep, fn, {r: prep.encoded[r].attrs for r in prep.encoded}, key
    )


def _decode(prep: Prepared, arr: np.ndarray) -> dict[tuple, float]:
    nz = np.nonzero(arr)
    cols = [prep.dicts[attr].decode(codes) for (_, attr), codes in zip(prep.group_attrs, nz)]
    vals = arr[nz]
    return {tuple(c[i] for c in cols): float(v) for i, v in enumerate(vals)}


def execute_jax(
    query: JoinAggQuery,
    db: Database,
    prep: Prepared | None = None,
    mode: str = "auto",
    interpret: bool | None = None,
    memory_budget: int | None = None,
) -> dict[tuple, float]:
    """Single-aggregate jax execution.

    ``mode``: ``"auto"`` (cost-based :func:`choose_jax_path`), ``"dense"``
    (einsum; COUNT/SUM only), or ``"sparse"`` (Pallas kernels over CSR
    relations; COUNT/SUM/MIN/MAX).  ``"kernels"`` is the legacy alias for
    ``"sparse"`` — the old chain-only demo under that name silently
    computed COUNT for SUM queries; the sparse program carries the
    measure payload properly.
    """
    if prep is None:
        prep = prepare(query, db)
    query = prep.query  # fold may re-point the aggregate's measure relation
    kind = query.agg.kind
    if kind not in ("count", "sum", "min", "max"):
        raise NotImplementedError(
            "jax engine: COUNT/SUM/MIN/MAX (AVG assembles on the planner)"
        )

    if mode == "kernels":  # legacy name for the sparse path
        mode = "sparse"
    if mode == "auto":
        if kind in ("min", "max"):
            mode = "sparse"  # dense einsum has no (min, +) form
        else:
            mode = choose_jax_path(prep, memory_budget=memory_budget).path

    if mode == "dense":
        if kind not in ("count", "sum"):
            raise NotImplementedError("jax dense mode: COUNT/SUM only")
        prog = build_dense_program(prep)
        tensors = prog.input_arrays()
        if kind == "sum":
            rel = query.agg.measure[0]
            er = prep.encoded[rel]
            dims = tuple(prep.dicts[a].size for a in er.attrs)
            t = np.zeros(dims, dtype=np.float32)
            np.add.at(t, tuple(er.codes[:, i] for i in range(len(er.attrs))),
                      er.payloads["sum"].astype(np.float32))
            (tensors[rel],) = _to_device(t)
        jitted = _jit_for(prog.plan_key, prog.fn)
        with obs.span("joinagg.launch"):
            out = jitted(tensors)
        return _decode(prep, _to_host(out))

    if mode == "sparse":
        measure = query.agg.measure[0] if kind == "sum" else None
        prog = build_sparse_program(prep, (measure,), interpret=interpret)
        if kind in ("count", "sum"):
            return _decode(prep, prog.run_channels()[..., 0])
        # MIN/MAX: reachability mask from the COUNT channel (zeros can
        # be genuine MIN/MAX values, so they must be kept where joined;
        # `prog` already is the single-COUNT-channel program here)
        mask = prog.run_channels()[..., 0] > 0
        arr = prog.run_minmax(kind, query.agg.measure[0])
        out: dict[tuple, float] = {}
        nz = np.nonzero(mask)
        cols = [
            prep.dicts[attr].decode(codes)
            for (_, attr), codes in zip(prep.group_attrs, nz)
        ]
        for i, v in enumerate(arr[nz]):
            out[tuple(c[i] for c in cols)] = float(v)
        return out
    raise ValueError(mode)


def _channelize_plan(
    plan: tuple, root: str, z_flags: dict[str, bool]
) -> tuple[tuple, bool]:
    """Add a leading batch axis ``Z`` to every einsum term whose tensor (or
    subtree message) carries per-channel weights.

    ``Z`` as a batch axis gives exactly the diagonal semantics a channel
    needs: channel ``c`` of the output combines channel ``c`` of every
    channelized operand — k independent scalar programs fused into one
    einsum (DESIGN.md §6).
    """
    carries: dict[str, bool] = {}
    out_plan = []
    for rel, expr, children in plan:
        ins, out = expr.split("->")
        if "Z" in expr:
            raise ValueError("einsum axis letters exhausted (Z is reserved)")
        terms = ins.split(",")
        flags = [z_flags.get(rel, False)] + [carries[c] for c in children]
        carry = any(flags)
        if carry:
            terms = [("Z" + t) if fl else t for t, fl in zip(terms, flags)]
            out = "Z" + out
        carries[rel] = carry
        out_plan.append((rel, ",".join(terms) + "->" + out, children))
    return tuple(out_plan), carries[root]


def execute_jax_channels(
    prep: Prepared,
    channel_measures: tuple[str | None, ...],
    dtype=np.float32,
) -> np.ndarray:
    """One jitted einsum pass computing k COUNT/SUM channels at once.

    ``channel_measures[c]`` names the relation whose dense tensor carries
    its ``sum`` payload in channel ``c`` (None = COUNT weights).  Returns a
    ``(k, *group_dims)`` float array over the canonical group axes.
    Exact while every partial product stays below 2**24 (f32), like the
    single-aggregate dense path.
    """
    k = len(channel_measures)
    z_rels = sorted({r for r in channel_measures if r is not None})
    plan, root = _dense_plan(prep)

    if not z_rels:  # all-COUNT bundle: one scalar program, replicated
        prog = build_dense_program(prep)
        jitted = _jit_for(prog.plan_key, prog.fn)
        tensors = prog.input_arrays(dtype)
        with obs.span("joinagg.launch"):
            out = jitted(tensors)
        arr = _to_host(out)
        return np.broadcast_to(arr[None], (k,) + arr.shape).copy()

    chplan, root_carries = _channelize_plan(
        plan, root, {r: True for r in z_rels}
    )
    assert root_carries, z_rels
    key = ("channels", chplan, root)
    fn = _FN_CACHE.get_or_create(key, lambda: _fn_from_plan(chplan, root))

    host: dict[str, np.ndarray] = {}
    for r in prep.encoded:
        if r not in z_rels:
            host[r] = dense_tensor(prep, r, dtype)
            continue
        er = prep.encoded[r]
        dims = tuple(prep.dicts[a].size for a in er.attrs)
        cnt = dense_tensor(prep, r, dtype)
        pay = np.zeros(dims, dtype=dtype)
        np.add.at(
            pay,
            tuple(er.codes[:, i] for i in range(len(er.attrs))),
            er.payloads["sum"].astype(dtype),
        )
        host[r] = np.stack(
            [pay if channel_measures[c] == r else cnt for c in range(k)]
        )
    tensors = dict(zip(host, _to_device(*host.values())))
    jitted = _jit_for(key, fn)
    with obs.span("joinagg.launch"):
        out = jitted(tensors)
    return _to_host(out)


def _jit_for(key, fn) -> Callable:
    return _JIT_CACHE.get_or_create(key, lambda: jax.jit(fn))


# ----------------------------------------------------------------------
# sparse-first execution (DESIGN.md §7)
# ----------------------------------------------------------------------

# edge blocks are padded to the next multiple of this count so the jitted
# kernels see a handful of static shapes instead of one per relation; it
# is the kernels' 1-D edge block granule, so their own pad is a no-op
EDGE_BUCKET = EDGE_GRANULE
# the Pallas kernels index segments/rows in int32
_INT32_LIMIT = 2**31


def _pad_edges(keys: np.ndarray, *cols: np.ndarray) -> list[np.ndarray]:
    """Pad an edge block to the bucket size: key -1 rows are dropped by
    the kernels, the rows of every other column (weights, gather
    indices) are zero."""
    pad = -len(keys) % EDGE_BUCKET
    if pad == 0:
        return [keys, *cols]
    return [np.concatenate([keys, np.full(pad, -1, keys.dtype)])] + [
        np.concatenate([c, np.zeros((pad,) + c.shape[1:], c.dtype)])
        for c in cols
    ]


def _int32_indexable(knum: int, gathers) -> bool:
    """The hop's row keys and its gather indices into every child
    message, all channels stacked, fit the kernels' int32."""
    return knum < _INT32_LIMIT and all(
        m2.shape[0] * int(np.prod(m2.shape[2:], dtype=np.int64)) < _INT32_LIMIT
        for m2, _ in gathers
    )


@functools.partial(jax.jit, static_argnames=("shapes", "k", "op"))
def hop_gather(keys, w, msgs, idxs, *, shapes, k, op):
    """One edge chunk's per-edge product, formed on the device: each
    edge's weight combined with every child's message row at that edge's
    gather index (``*`` for COUNT/SUM, ``+`` for the (min, +) / (max, +)
    semirings), outer across the children's group axes, one channel at a
    time.  The weight comes first, then each child in decomposition
    order, as in the numpy engine, so results stay bit-identical to it
    (IEEE products and sums do not reassociate).

    Every array is channel-major and uploaded 1-D (such an upload takes
    no layout transpose): ``w`` holds the chunk's ``k`` weight columns
    one after another, each of ``msgs`` a child's ``(k, rows, width)``
    message raveled, ``shapes`` their ``(rows, width)``; ``idxs`` are the
    int32 gather indices and ``keys`` the chunk's padded int32 row keys.
    Channel-major, the product never puts the short channel axis minor,
    which keeps the program a second's compile.  Rows of the pad (key
    -1) come out 0, as a host pad made them, so a pad row that reads an
    inf makes no NaN for a one-hot matmul to spread.  Returns ``(n, k ·
    Π width)``, channel-major, which the engine hands to ``segment_sum``
    / ``segment_reduce`` on the device."""
    n = keys.shape[0]
    combine = jnp.multiply if op == "mul" else jnp.add
    chans = []
    for c in range(k):
        vals, p = w[c * n:(c + 1) * n].reshape(n, 1), 1  # p: width so far
        for flat, idx, (rows, width) in zip(msgs, idxs, shapes):
            got = flat.reshape(k * rows, width)[idx + c * rows]
            vals = combine(vals[:, :, None], got[:, None, :]).reshape(
                n, p * width
            )
            p *= width
        chans.append(vals)
    vals = chans[0] if k == 1 else jnp.concatenate(chans, axis=1)
    return jnp.where(keys[:, None] >= 0, vals, 0)


# the ref spmm's per-edge gather and hop_gather's product materialize
# (edges × width) in HBM; chunk the edge axis so the intermediate stays
# within this bound (the Pallas coo_spmm streams its product through
# VMEM blocks instead)
_REF_GATHER_BYTES = 64 << 20


def _ref_spmm_chunked(keys, idx, vals, flat, knum) -> np.ndarray:
    from repro.kernels import ref

    n, width = len(keys), flat.shape[1]
    chunk = max(1024, _REF_GATHER_BYTES // max(4 * width, 1))
    (dense,) = _to_device(flat)
    out = None
    for lo in range(0, n, chunk):
        sl = slice(lo, lo + chunk)
        kk, ii, vv = _to_device(
            np.asarray(keys[sl], np.int32), np.asarray(idx[sl], np.int32),
            vals[sl],
        )
        with obs.span("joinagg.launch"):
            part = ref.coo_spmm_ref(kk, ii, vv, dense, knum)
        out = part if out is None else out + part
    return _to_host(out)


class _CsrHopMixin:
    """Feed every decomposition-tree hop its relation in grouped-CSR
    order: edges sorted by the hop's raveled output key (up attrs + own
    group attr), so each output row's edges form one contiguous block.
    Relations of any arity flatten this way — the kernels only ever see
    one row-key axis and one column-index axis."""

    interpret: bool | None = None
    # fused-hop switch (None = follow the REPRO_FUSED environment)
    fused: bool | None = None
    # tile-local CSR views, shared across the engines of one stream tile
    # (channel pass + one per MinMaxRequest) so each relation sorts once
    view_cache: dict | None = None

    def _hop_key_attrs(self, rel: str, parent: str | None) -> tuple[str, ...]:
        er = self.encoded[rel]
        own_g = self.prep.schema.group_of.get(rel)
        up: tuple[str, ...] = ()
        if parent is not None:
            up = tuple(sorted(set(er.attrs) & set(self.encoded[parent].attrs)))
        return up + ((own_g,) if own_g else ())

    def message(self, rel: str, parent: str | None):
        child_msgs = {
            child: self.message(child, rel)
            for child in self.deco.nodes[rel].children
        }
        er = self.encoded[rel]
        # the hop's own host work, its transfers and its launches; the
        # children's hops are closed spans of their own before this opens
        with obs.span(
            "joinagg.hop", rel=rel, kernel=self._hop_kernel(rel),
            edges=er.num_rows,
        ):
            key_attrs = self._hop_key_attrs(rel, parent)
            if er is self.prep.encoded.get(rel):
                view = self.prep.csr_view(rel, key_attrs)
            else:  # stream tile: build a tile-local view (restricted domains)
                cache = self.view_cache
                view = None if cache is None else cache.get((rel, key_attrs))
                if view is None:
                    view = grouped_csr(er, key_attrs, self._dims(key_attrs))
                    if cache is not None:
                        cache[(rel, key_attrs)] = view
            return self.contract_rows(
                rel,
                parent,
                er.codes[view.order],
                self._weights(rel)[view.order],
                child_msgs,
            )

    def _fused_contract(self, w32, gathers, keys, knum, kind, k=1):
        """Run one hop as a single fused Pallas dispatch (DESIGN.md §13):
        gather + channel product + segment scatter in one kernel, the
        edge-sized intermediate staying in VMEM.  ``gathers`` holds
        ``(message, idx)`` pairs; sum messages are ``(rows, width_c, k)``
        and min/max messages ``(rows, width_c)`` — both flatten row-major
        to the kernel's width-major/k-minor layout."""
        from repro.kernels import autotune, ops

        msgs, idxs, child_rows, child_widths = [], [], [], []
        for m2, idx in gathers:
            flat = np.ascontiguousarray(m2, np.float32).reshape(
                m2.shape[0], -1
            )
            msgs.append(flat)
            idxs.append(np.asarray(idx, np.int32))
            child_rows.append(m2.shape[0])
            child_widths.append(m2.shape[1])
        cfg = autotune.tiles_for(
            autotune.hop_shape(
                edges=len(keys),
                child_rows=tuple(child_rows),
                k=k,
                kind=kind,
                child_widths=tuple(child_widths),
                num_segments=knum,
            )
        )
        width = int(np.prod(child_widths, dtype=np.int64))
        ops.record_dispatch(
            "fused",
            hop=dict(
                kernel="fused_hop", edges=len(keys), segments=knum,
                child_rows=sum(child_rows), width=width * k,
                block_e=cfg.block_e, block_s=cfg.block_s, block_r=cfg.block_r,
            ),
        )
        nc = len(msgs)
        keys_d, w_d, *rest = _to_device(
            np.asarray(keys, np.int32), w32, *msgs, *idxs
        )
        with obs.span("joinagg.launch"):
            out = ops.fused_hop(
                keys_d,
                w_d,
                tuple(rest[:nc]),
                tuple(rest[nc:]),
                num_segments=knum,
                k=k,
                kind=kind,
                block_e=cfg.block_e,
                block_s=cfg.block_s,
                block_r=cfg.block_r,
                interpret=self.interpret,
            )
        return _to_host(out)

    def _device_products(self, w32, gathers, keys, op, k=1):
        """Yield, per edge chunk of one hop, ``(vals, keys_d, keys)``:
        the chunk's per-edge product formed on the device by
        :func:`hop_gather` (channel-major), and its padded int32 keys on
        the device and on the host.  Up go each child message once per
        hop, then per chunk only its keys, weights and gather indices;
        the product lives only in HBM.  The edge axis is chunked so that
        product stays bounded by ``_REF_GATHER_BYTES`` instead of growing
        with the relation."""
        from repro.kernels import ops

        shapes = tuple(m2.shape[:2] for m2, _ in gathers)
        width = int(np.prod([w for _, w in shapes], dtype=np.int64))
        msgs = tuple(_to_device(*(
            np.ascontiguousarray(
                np.moveaxis(m2.reshape(*m2.shape[:2], k), 2, 0), np.float32
            ).reshape(-1)
            for m2, _ in gathers
        ))) if gathers else ()
        chunk = max(1024, _REF_GATHER_BYTES // max(4 * width * k, 1))
        for lo in range(0, len(keys), chunk):
            sl = slice(lo, lo + chunk)
            kk, ww, *ii = _pad_edges(
                np.asarray(keys[sl], np.int32), w32[sl].reshape(-1, k),
                *(np.asarray(idx[sl], np.int32) for _, idx in gathers),
            )
            kk_d, ww_d, *ii_d = _to_device(kk, ww.T.reshape(-1), *ii)
            if gathers:
                ops.record_dispatch("gather", len(gathers))
                ops.record_dispatch("product", len(gathers))
            obs.count("hop.product_edges_device", len(w32[sl]))
            with obs.span("joinagg.launch"):
                vals = hop_gather(
                    kk_d, ww_d, msgs, tuple(ii_d), shapes=shapes, k=k, op=op
                )
            yield vals, kk_d, kk


class _KernelChannelEngine(_CsrHopMixin, ChannelTensorEngine):
    """k-channel contraction whose gather-product-scatter hot loop runs
    on the Pallas kernels (f32):

    * single-child hop, channel-uniform weights → ``coo_spmm`` with the
      child message as the dense operand; the ``(k,)``-channel axis rides
      the operand's column dimension (``(rows, width·k)``).
    * leaf / multi-child / measure-weighted hop → the per-edge
      channel-diagonal product of gathered child message rows is formed
      on the device (:func:`hop_gather`) and reduced there with
      ``segment_sum``; the host uploads only the child messages, gather
      indices, weights and keys.
    """

    def __init__(
        self, *args, interpret: bool | None = None,
        fused: bool | None = None, **kwargs,
    ):
        super().__init__(*args, **kwargs)
        self.interpret = interpret
        self.fused = fused

    def _uniform(self, w) -> bool:
        """Every channel weighs each edge alike: the hop can run as one
        ``coo_spmm`` with the channels riding its columns."""
        return self.k == 1 or bool((w == w[:, :1]).all())

    def _hop_kernel(self, rel: str) -> str:
        """The kernel :meth:`_contract_block` dispatches ``rel``'s hop to."""
        from repro.kernels import ops

        if ops.fused_enabled(self.fused):
            return "fused_hop"
        if len(self.deco.nodes[rel].children) == 1 and (
            rel not in self.weights_override
            or self._uniform(self.weights_override[rel])
        ):
            return "coo_spmm"
        return "segment_sum"

    def _contract_block(self, weights, gathers, keys, knum):
        from repro.kernels import ops, ref
        from repro.kernels.ops import coo_spmm, segment_sum

        n = len(weights)
        if n == 0 or not _int32_indexable(knum, gathers):
            if n:  # beyond the kernels' int32: the numpy engine's product
                obs.count("hop.product_edges_host", n)
            out = super()._contract_block(weights, gathers, keys, knum)
            return out.astype(np.float32)
        w32 = np.asarray(weights, dtype=np.float32)  # (n, k)
        width = int(np.prod([m2.shape[1] for m2, _ in gathers], dtype=np.int64))
        if ops.fused_enabled(self.fused):
            out = self._fused_contract(
                w32, gathers, keys, knum, "sum", k=self.k
            )
            return out.reshape(knum, width, self.k)
        use_ref = ops.use_ref_kernels(self.interpret)
        if len(gathers) == 1 and self._uniform(w32):
            m2, idx = gathers[0]  # m2 (rows, width, k)
            rows = m2.shape[0]
            flat = np.ascontiguousarray(m2, dtype=np.float32).reshape(
                rows, width * self.k
            )
            ops.record_dispatch(
                "spmm",
                hop=dict(
                    kernel="coo_spmm", edges=ops.round_up(n, EDGE_BUCKET),
                    segments=knum, child_rows=rows, width=width * self.k,
                ),
            )
            if use_ref:
                out = _ref_spmm_chunked(keys, idx, w32[:, 0], flat, knum)
            else:
                kk, vv, ii = _pad_edges(keys, w32[:, 0], idx)
                kk = np.asarray(kk, np.int32)
                # the kernel's schedule, built from the host's keys and
                # uploaded with them
                *args, pairs = _to_device(
                    kk, np.asarray(ii, np.int32), vv, flat,
                    ops.spmm_pair_list(kk, knum),
                )
                with obs.span("joinagg.launch"):
                    out = coo_spmm(
                        *args, num_rows=knum, pairs=pairs,
                        interpret=self.interpret,
                    )
                out = _to_host(out)
            return out.reshape(knum, width, self.k)
        # general hop: the row-aligned product of gathered child rows,
        # formed on the device and reduced there into the CSR row keys;
        # it comes back channel-major
        out = np.zeros((knum, self.k * width), np.float32)
        for vals, kk_d, kk in self._device_products(
            w32, gathers, keys, "mul", self.k
        ):
            ops.record_dispatch(
                "scatter",
                hop=dict(
                    kernel="segment_sum", edges=len(kk), segments=knum,
                    child_rows=sum(m2.shape[0] for m2, _ in gathers),
                    width=width * self.k,
                ),
            )
            with obs.span("joinagg.launch"):
                if use_ref:
                    part = ref.segment_sum_ref(vals, kk_d, knum)
                else:
                    part = segment_sum(
                        vals, kk_d, num_segments=knum, interpret=self.interpret
                    )
            out += _to_host(part)
        return out.reshape(knum, self.k, width).transpose(0, 2, 1)


class _MinMaxKernelEngine(_CsrHopMixin, TensorEngine):
    """(min, +) / (max, +) semiring message passing over the tree: the
    measure relation contributes its per-edge payload, every other
    relation contributes 0, and each hop forms its per-edge candidate
    sums on the device (:func:`hop_gather`) and reduces them into their
    row keys there with the Pallas ``segment_reduce`` kernel.
    Unreached entries hold the identity (±inf) until :meth:`run` masks
    them.  Min/max ignore multiplicities, so no re-rooting at the
    measure relation is needed (unlike the reachability kernel)."""

    def __init__(
        self, prep, kind: str, rel_m: str, *,
        interpret: bool | None = None, fused: bool | None = None,
        domains=None, encoded=None,
    ):
        super().__init__(prep, domains=domains, encoded=encoded)
        self.kind = kind
        self.rel_m = rel_m
        self.interpret = interpret
        self.fused = fused
        self.ident = np.inf if kind == "min" else -np.inf

    def _hop_kernel(self, rel: str) -> str:
        """The kernel :meth:`_contract_block` dispatches ``rel``'s hop to."""
        from repro.kernels import ops

        return "fused_hop" if ops.fused_enabled(self.fused) else "segment_reduce"

    def _weights(self, rel):
        er = self.encoded[rel]
        if rel == self.rel_m:
            return er.payloads[self.kind].astype(np.float64)
        return np.zeros(er.num_rows)

    def _contract_block(self, weights, gathers, keys, knum):
        from repro.kernels import ops, ref
        from repro.kernels.ops import segment_reduce

        n = len(weights)
        width = int(np.prod([m2.shape[1] for m2, _ in gathers], dtype=np.int64))
        red = np.minimum if self.kind == "min" else np.maximum
        out = np.full((knum, width), self.ident, np.float32)
        if n == 0:
            return out
        w32 = np.asarray(weights, np.float32)
        if not _int32_indexable(knum, gathers):
            # beyond the kernels' int32: the one product formed on the
            # host, its edge axis chunked like the device's
            obs.count("hop.product_edges_host", n)
            chunk = max(1024, _REF_GATHER_BYTES // max(4 * width, 1))
            for lo in range(0, n, chunk):
                sl = slice(lo, lo + chunk)
                vals = w32[sl].reshape(-1, 1)
                for m2, idx in gathers:
                    rows = np.asarray(m2, np.float32)[idx[sl]]  # (c, Wc)
                    vals = (vals[:, :, None] + rows[:, None, :]).reshape(
                        vals.shape[0], -1
                    )
                red.at(out, keys[sl], vals)
            return out
        if ops.fused_enabled(self.fused):
            return self._fused_contract(w32, gathers, keys, knum, self.kind)
        use_ref = ops.use_ref_kernels(self.interpret)
        for vals, kk_d, kk in self._device_products(w32, gathers, keys, "add"):
            ops.record_dispatch(
                "scatter",
                hop=dict(
                    kernel="segment_reduce", edges=len(kk), segments=knum,
                    child_rows=sum(m2.shape[0] for m2, _ in gathers),
                    width=width,
                ),
            )
            if use_ref:
                with obs.span("joinagg.launch"):
                    part = ref.segment_reduce_ref(vals, kk_d, knum, self.kind)
            else:
                # the kernel's schedule, built from the host's keys
                (sched,) = _to_device(ops.reduce_schedule(kk, knum))
                with obs.span("joinagg.launch"):
                    part = segment_reduce(
                        vals, kk_d, num_segments=knum, kind=self.kind,
                        schedule=sched, interpret=self.interpret,
                    )
            out = red(out, _to_host(part))
        return out


@dataclass
class SparseProgram:
    """A compiled sparse execution of one ``Prepared`` (DESIGN.md §7).

    Runs every acyclic decomposition tree — arbitrary relation arity
    (grouped-CSR flattening), multi-child nodes (row-aligned products of
    child messages), GHD bag outputs as CSR inputs — as Pallas kernel
    hops, never building a dense relation tensor.  ``channel_measures``
    mirrors :func:`execute_jax_channels`: entry ``c`` names the relation
    whose ``sum`` payload rides channel ``c`` (None = COUNT).

    Memoization: grouped-CSR views cache on the ``Prepared``
    (:meth:`~repro.core.prepare.Prepared.csr_view`), and the Pallas
    kernels are jitted with static block shapes (edge blocks padded to
    ``EDGE_BUCKET`` multiples), so repeated runs — stream tiles,
    refreshes over the same plan — reuse both the sorted edge blocks
    and the compiled kernels; there is no per-program compiled artifact
    beyond those two caches.
    """

    prep: Prepared
    channel_measures: tuple[str | None, ...]
    interpret: bool | None = None
    # fused megakernel hops (None = follow REPRO_FUSED; DESIGN.md §13)
    fused: bool | None = None

    @property
    def k(self) -> int:
        return len(self.channel_measures)

    def run_channels(
        self, encoded=None, domains=None, view_cache: dict | None = None
    ) -> np.ndarray:
        """One leaves→root kernel pass; returns ``(*group_dims, k)`` f32."""
        with obs.span("joinagg.walk", kind="sum"):
            encoded = self.prep.encoded if encoded is None else encoded
            eng = _KernelChannelEngine(
                self.prep,
                self.k,
                channel_weight_matrices(encoded, self.channel_measures),
                domains=domains,
                encoded=encoded,
                interpret=self.interpret,
                fused=self.fused,
            )
            eng.view_cache = view_cache
            return eng.run()

    def run_minmax(
        self, kind: str, rel_m: str, encoded=None, domains=None,
        view_cache: dict | None = None,
    ) -> np.ndarray:
        """MIN/MAX(rel_m) over canonical group axes; unreached groups
        hold 0.0 — mask with a COUNT support before use."""
        with obs.span("joinagg.walk", kind=kind):
            encoded = self.prep.encoded if encoded is None else encoded
            eng = _MinMaxKernelEngine(
                self.prep, kind, rel_m,
                domains=domains, encoded=encoded, interpret=self.interpret,
                fused=self.fused,
            )
            eng.view_cache = view_cache
            arr = eng.run()
            return np.where(np.isfinite(arr), arr, 0.0)

    def run_stream(self, attr: str, tile: int):
        """Yield ``(encoded, domains, offsets)`` per group-axis row tile;
        relations are sliced through their grouped-CSR views, re-based to
        the tile-local code range."""
        total = self.prep.dicts[attr].size
        for lo in range(0, total, tile):
            hi = min(lo + tile, total)
            enc = csr_restrict(self.prep, attr, lo, hi)
            domains = {a: self.prep.dicts[a].size for a in self.prep.dicts}
            domains[attr] = hi - lo
            yield enc, domains, {attr: lo}


def build_sparse_program(
    prep: Prepared,
    channel_measures: tuple[str | None, ...],
    interpret: bool | None = None,
    fused: bool | None = None,
) -> SparseProgram:
    """Bind ``Prepared`` + channel spec into a :class:`SparseProgram`."""
    return SparseProgram(prep, tuple(channel_measures), interpret, fused)


# ----------------------------------------------------------------------
# cost-based dense-vs-sparse path choice
# ----------------------------------------------------------------------


@dataclass
class JaxPathChoice:
    """Outcome of :func:`choose_jax_path`, rendered by ``Plan.explain()``."""

    path: str  # "dense" | "sparse" | "distributed-sparse"
    reason: str
    dense_node_bytes: dict[str, int] = field(default_factory=dict)
    sparse_node_bytes: dict[str, int] = field(default_factory=dict)
    # meshed plans only: per-node bytes on ONE device of the shard mesh
    # (sharded relations/messages divide by the shard count, replicated
    # subtrees do not) — the currency of the distributed path's explain
    per_device_node_bytes: dict[str, int] = field(default_factory=dict)
    shards: int = 1

    @property
    def dense_peak(self) -> int:
        # the einsum program holds every relation tensor at once
        return sum(self.dense_node_bytes.values())

    @property
    def sparse_peak(self) -> int:
        return max(self.sparse_node_bytes.values(), default=0)

    @property
    def per_device_peak(self) -> int:
        return max(self.per_device_node_bytes.values(), default=0)


def _node_message_attrs(prep: Prepared) -> dict[str, set[str]]:
    """Attrs carried by each node's upward message (shared-with-parent +
    subtree group attrs) — membership only, for shard-split estimates."""
    deco = prep.decomposition

    def subtree_gattrs(rel: str) -> set[str]:
        out = set()
        g = prep.schema.group_of.get(rel)
        if g:
            out.add(g)
        for c in deco.nodes[rel].children:
            out |= subtree_gattrs(c)
        return out

    out: dict[str, set[str]] = {}
    for rel in deco.order:
        node = deco.nodes[rel]
        up: set[str] = set()
        if node.parent is not None:
            up = set(prep.schema.relevant[rel]) & set(
                prep.schema.relevant[node.parent]
            )
        out[rel] = up | subtree_gattrs(rel)
    return out


def choose_jax_path(
    prep: Prepared,
    k: int = 1,
    memory_budget: int | None = None,
    stream: tuple[str, int] | None = None,
    measured: tuple[str, ...] = (),
    shards: int | None = None,
    stats=None,
) -> JaxPathChoice:
    """Estimate per-node dense-vs-sparse peak bytes and pick the path.

    Dense cost per node: the f32 relation tensor over its attr domains
    (×k only for ``measured`` relations — the dense channel program only
    k-stacks the measure tensors, everything else keeps one tensor) plus
    the f32 einsum message (``node_message_bytes`` re-scaled; messages
    carry the channel axis only when a measure channelizes the program).
    Sparse cost per node: the CSR edge arrays plus the f32 k-channel
    message.  Sparse wins when an explicit ``stream`` is set (dense
    cannot tile), when any dense tensor crosses the 2^24 element cliff,
    or when the dense program exceeds the memory budget.

    ``shards`` (a mesh's data-axis extent) forces the third path,
    ``distributed-sparse`` — the dense program is retired on meshes —
    and fills ``per_device_node_bytes``: edge arrays and messages that
    carry the shard attribute divide by the shard count, replicated
    subtrees keep their full size (DESIGN.md §8).

    ``stats`` (a :class:`repro.stats.Statistics`, defaulting to the
    prepared plan's cached collection when one was materialized) refines
    two decisions: the per-device divisor caps at the shard attribute's
    heavy-hitter share (a skewed key pins its rows to one device, so
    dividing by the full shard count under-estimates the hot device),
    and a dense tensor whose estimated occupancy is extreme-sparse
    prefers the sparse program even under budget.
    """
    from repro.core.operator import DEFAULT_MEMORY_BUDGET, node_message_bytes

    if stats is None:
        stats = getattr(prep, "_stats_cache", None)
    budget = DEFAULT_MEMORY_BUDGET if memory_budget is None else memory_budget
    measured_set = {m for m in measured if m}
    dense_msg_k = k if measured_set else 1  # all-COUNT: one scalar einsum
    msg = node_message_bytes(prep)  # 8 bytes/elem estimates
    dense_nodes: dict[str, int] = {}
    sparse_nodes: dict[str, int] = {}
    over_cliff: str | None = None
    for rel, er in prep.encoded.items():
        elems = 1
        for a in er.attrs:
            elems *= prep.dicts[a].size
        if elems > DENSE_PROMOTE_ELEMS and over_cliff is None:
            over_cliff = rel
        msg_f32 = msg[rel] // 2
        tensor_k = k if rel in measured_set else 1
        dense_nodes[rel] = 4 * elems * tensor_k + msg_f32 * dense_msg_k
        edge_bytes = er.codes.nbytes + 4 * k * er.num_rows
        sparse_nodes[rel] = edge_bytes + msg_f32 * k
    choice = JaxPathChoice("dense", "", dense_nodes, sparse_nodes)
    if shards is not None:
        from repro.core.distributed import shard_attr

        attr = shard_attr(prep)
        msg_attrs = _node_message_attrs(prep)
        # skew caps the useful divisor: a heavy key's rows all land on one
        # device, so the hot shard holds at least max_share of the edges
        div = shards
        skew_note = ""
        if stats is not None:
            share = max(
                (
                    stats.max_share(rel, attr)
                    for rel, er in prep.encoded.items()
                    if attr in er.attrs
                ),
                default=0.0,
            )
            if share > 0.0:
                div = min(shards, max(1, int(1.0 / share)))
                if div < shards:
                    skew_note = (
                        f"; skew-capped divisor {div} "
                        f"(top share {share:.2f} of {attr!r})"
                    )
        per_dev: dict[str, int] = {}
        for rel, er in prep.encoded.items():
            edge_bytes = er.codes.nbytes + 4 * k * er.num_rows
            if attr in er.attrs:
                edge_bytes //= div
            msg_f32 = (msg[rel] // 2) * k
            if attr in msg_attrs[rel]:
                msg_f32 //= div
            per_dev[rel] = edge_bytes + msg_f32
        choice.path = "distributed-sparse"
        choice.shards = shards
        choice.per_device_node_bytes = per_dev
        choice.reason = (
            f"mesh over {shards} shard(s) of {attr!r} on the data axis "
            "(dense einsum is retired on meshes)" + skew_note
        )
        return choice
    if stream is not None:
        choice.path = "sparse"
        choice.reason = f"stream tiles over {stream[0]!r} (dense cannot tile)"
    elif over_cliff is not None:
        choice.path = "sparse"
        choice.reason = (
            f"dense tensor for {over_cliff!r} exceeds 2^24 elements"
        )
    elif choice.dense_peak > budget:
        choice.path = "sparse"
        choice.reason = (
            f"dense program needs {choice.dense_peak} B > budget {budget} B"
        )
    elif (sparse := _extreme_sparsity(prep, stats)) is not None:
        choice.path = "sparse"
        choice.reason = (
            f"stats: dense tensor for {sparse[0]!r} is extreme-sparse "
            f"(est occupancy {sparse[1]:.2e})"
        )
    else:
        choice.reason = (
            f"dense program fits ({choice.dense_peak} B ≤ budget {budget} B)"
        )
    return choice


# dense tensors this large with occupancy this low waste both the
# materialization and the einsum FLOPs; the CSR program touches only edges
SPARSITY_MIN_ELEMS = 1 << 20
SPARSITY_MAX_OCCUPANCY = 1e-3


def _extreme_sparsity(prep: Prepared, stats) -> tuple[str, float] | None:
    """Largest relation whose dense tensor's estimated occupancy (weighted
    rows / dense cells) is below ``SPARSITY_MAX_OCCUPANCY`` — ``None``
    when statistics are absent or no tensor qualifies."""
    if stats is None:
        return None
    worst: tuple[str, float] | None = None
    for rel, er in prep.encoded.items():
        elems = 1
        for a in er.attrs:
            elems *= prep.dicts[a].size
        if elems < SPARSITY_MIN_ELEMS:
            continue
        rs = stats.relations.get(rel)
        rows = rs.rows if rs is not None else er.num_rows
        occ = max(rows, 1) / elems
        if occ < SPARSITY_MAX_OCCUPANCY and (worst is None or occ < worst[1]):
            worst = (rel, occ)
    return worst
