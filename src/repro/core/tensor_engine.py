"""TPU-native JOIN-AGG: decomposition-tree sum-product contraction.

The data graph's edge multiplicities are sparse *multiplicity tensors*
``T_r[attrs...] = #tuples``; the paper's traversal + prefix-join is a
sum-product contraction of those tensors along the query decomposition
tree with group attributes kept and join attributes contracted (see
DESIGN.md §2).  Messages flow leaves -> root; each message's axes are the
attrs shared with the parent plus every group attribute in the subtree —
the exact analogue of the paper's c-pair lists, and the paper's path-id
caching is subsumed by computing each subtree message once.

This module is the exact numpy backend (int64/float64 counts) plus the
source/group-axis *streaming* mode that reproduces the paper's bounded-
memory per-source iteration.  ``jax_engine.py`` lowers the same plan to
jnp/einsum + Pallas kernels for the TPU target.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from repro.core.prepare import Prepared, prepare
from repro.core.query import JoinAggQuery
from repro.relational.relation import Database

ROW_BLOCK = 65536  # rows contracted per scatter block (memory bound)


@dataclass
class Message:
    attrs: tuple[str, ...]  # shared-with-parent attrs, then group attrs
    num_shared: int
    array: np.ndarray  # shape = domains(attrs)

    @property
    def group_attrs(self) -> tuple[str, ...]:
        return self.attrs[self.num_shared:]


def _segment_sum(keys: np.ndarray, vals: np.ndarray, num: int) -> np.ndarray:
    """Sum rows of ``vals`` (n, d) into ``num`` buckets by ``keys`` (n,)."""
    out = np.zeros((num,) + vals.shape[1:], dtype=vals.dtype)
    if len(keys) == 0:
        return out
    order = np.argsort(keys, kind="stable")
    keys_s, vals_s = keys[order], vals[order]
    bounds = np.flatnonzero(np.concatenate([[True], keys_s[1:] != keys_s[:-1]]))
    sums = np.add.reduceat(vals_s, bounds, axis=0)
    out[keys_s[bounds]] = sums
    return out


def _tree_children(prep: Prepared) -> dict[str, list[str]]:
    return {r: list(prep.decomposition.nodes[r].children) for r in prep.encoded}


class TensorEngine:
    # trailing axes carried unchanged through every message: () for the
    # scalar engine, (k,) for the k-channel subclass below
    _chan: tuple[int, ...] = ()

    def __init__(
        self,
        prep: Prepared,
        weights_override: dict[str, np.ndarray] | None = None,
        boolean: bool = False,
        domains: dict[str, int] | None = None,
        encoded=None,
    ):
        self.prep = prep
        self.deco = prep.decomposition
        self.encoded = encoded if encoded is not None else prep.encoded
        self.domains = domains or {a: prep.dicts[a].size for a in prep.dicts}
        self.weights_override = weights_override or {}
        self.boolean = boolean
        # canonical group-attr order = query group-by order
        self.canonical = [attr for _, attr in prep.group_attrs]
        self.peak_message_bytes = 0

    # --- per-node weight vector (semiring payload) ---
    def _weights(self, rel: str) -> np.ndarray:
        if rel in self.weights_override:
            return self.weights_override[rel]
        w = self.encoded[rel].count.astype(np.float64)
        if self.boolean:
            w = (w > 0).astype(np.float64)
        return w

    def _dims(self, attrs: tuple[str, ...]) -> tuple[int, ...]:
        return tuple(self.domains[a] for a in attrs)

    def _canon_sort(self, gattrs: list[str]) -> list[str]:
        return sorted(gattrs, key=self.canonical.index)

    def _contract_block(
        self,
        weights: np.ndarray,
        gathers: list[tuple[np.ndarray, np.ndarray]],
        keys: np.ndarray,
        knum: int,
    ) -> np.ndarray:
        """Gather-product-scatter hot loop of :meth:`contract_rows`:
        ``out[keys[i]] += w[i] * Π_c m2_c[idx_c[i]]`` (outer product over
        the children's group axes).  Overridable — the kernel engine in
        ``repro.incremental.jax_delta`` dispatches this to the Pallas
        ``coo_spmm``/``segment_sum`` kernels."""
        n = len(weights)
        if n == 0:  # reshape(0, -1) below is ill-defined for numpy
            width = 1
            for m2, _ in gathers:
                width *= m2.shape[1]
            return np.zeros((knum, width), dtype=np.float64)
        vals = weights.reshape(n, 1)
        for m2, idx in gathers:
            rows = m2[idx]  # (n, Gc)
            vals = (vals[:, :, None] * rows[:, None, :]).reshape(n, -1)
        return _segment_sum(keys, vals, knum)

    def contract_rows(
        self,
        rel: str,
        parent: str | None,
        codes: np.ndarray,
        weights: np.ndarray,
        child_msgs: dict[str, "Message"],
    ) -> Message:
        """Contract the given COO rows of ``rel`` against ``child_msgs``.

        The shared primitive behind both the full leaves→root pass
        (:meth:`message`, where ``codes``/``weights`` are the whole
        encoded relation) and incremental maintenance (DESIGN.md §4,
        where ``codes`` are a *delta block* — or the parent rows matched
        to one — and a child's entry is its delta message).  Children are
        always consumed in decomposition order, so the output attr order
        is identical for both callers and delta arrays add elementwise
        onto cached ones.
        """
        er = self.encoded[rel]
        node = self.deco.nodes[rel]
        n = len(weights)

        gathers: list[tuple[np.ndarray, np.ndarray]] = []  # (child m2, row idx)
        child_gattrs: list[str] = []
        for child in node.children:
            msg = child_msgs[child]
            shared = msg.attrs[: msg.num_shared]
            pos = [er.attrs.index(a) for a in shared]
            sh_dims = self._dims(shared)
            g_dims = self._dims(msg.group_attrs)
            m2 = msg.array.reshape(
                (
                    int(np.prod(sh_dims, dtype=np.int64)) if sh_dims else 1,
                    int(np.prod(g_dims, dtype=np.int64)) if g_dims else 1,
                )
                + self._chan
            )
            if pos:
                idx = np.ravel_multi_index(
                    tuple(codes[:, p] for p in pos), dims=sh_dims
                )
            else:
                idx = np.zeros(n, dtype=np.int64)
            gathers.append((m2, idx))
            child_gattrs.extend(msg.group_attrs)

        own_g = self.prep.schema.group_of.get(rel)
        up_attrs: tuple[str, ...]
        if parent is None:
            up_attrs = ()
        else:
            up_attrs = tuple(
                sorted(set(er.attrs) & set(self.encoded[parent].attrs))
            )
        kept_own = up_attrs + ((own_g,) if own_g else ())
        kept_dims = self._dims(kept_own)
        kpos = [er.attrs.index(a) for a in kept_own]
        if kpos:
            keys = np.ravel_multi_index(
                tuple(codes[:, p] for p in kpos), dims=kept_dims
            )
        else:
            keys = np.zeros(n, dtype=np.int64)
        knum = int(np.prod(kept_dims, dtype=np.int64)) if kept_dims else 1
        out2 = self._contract_block(weights, gathers, keys.astype(np.int64), knum)
        if self.boolean:
            out2 = (out2 > 0).astype(np.float64)

        # assemble axes: up_attrs, then group attrs in canonical order
        # (any trailing channel axes stay last)
        gattrs = ([own_g] if own_g else []) + child_gattrs
        raw_attrs = list(kept_own) + child_gattrs
        arr = out2.reshape(kept_dims + self._dims(tuple(child_gattrs)) + self._chan)
        want_g = self._canon_sort(gattrs)
        want = list(up_attrs) + want_g
        perm = [raw_attrs.index(a) for a in want]
        perm += list(range(len(raw_attrs), arr.ndim))
        arr = np.transpose(arr, perm) if perm != list(range(len(perm))) else arr
        self.peak_message_bytes = max(self.peak_message_bytes, arr.nbytes)
        return Message(tuple(want), len(up_attrs), arr)

    def message(self, rel: str, parent: str | None) -> Message:
        """Compute the upward message of ``rel``'s subtree."""
        er = self.encoded[rel]
        child_msgs = {
            child: self.message(child, rel)
            for child in self.deco.nodes[rel].children
        }
        return self.contract_rows(
            rel, parent, er.codes, self._weights(rel), child_msgs
        )

    def run(self) -> np.ndarray:
        """Dense result tensor over canonical group axes."""
        msg = self.message(self.deco.root, None)
        assert msg.attrs == tuple(self.canonical), (msg.attrs, self.canonical)
        return msg.array


class ChannelTensorEngine(TensorEngine):
    """``k`` semiring channels contracted in one leaves→root pass.

    Weight vectors become ``(n, k)`` matrices — column ``c`` is channel
    ``c``'s weight for that relation (its multiplicity, or a measure
    payload) — and every message carries a trailing channel axis.  Per
    channel the float operations run in the same order as a scalar
    :class:`TensorEngine` pass with that channel's weights, so one
    k-channel pass is bit-identical to k scalar passes (DESIGN.md §6).
    """

    def __init__(
        self,
        prep: Prepared,
        k: int,
        weights_override: dict[str, np.ndarray] | None = None,
        domains: dict[str, int] | None = None,
        encoded=None,
    ):
        super().__init__(prep, weights_override, False, domains, encoded)
        self.k = k
        self._chan = (k,)

    def _weights(self, rel: str) -> np.ndarray:
        w = self.weights_override.get(rel)
        if w is None:
            c = self.encoded[rel].count.astype(np.float64)
            w = np.repeat(c[:, None], self.k, axis=1)
        return w

    def _contract_block(
        self,
        weights: np.ndarray,
        gathers: list[tuple[np.ndarray, np.ndarray]],
        keys: np.ndarray,
        knum: int,
    ) -> np.ndarray:
        n = len(weights)
        if n == 0:
            width = 1
            for m2, _ in gathers:
                width *= m2.shape[1]
            return np.zeros((knum, width, self.k), dtype=np.float64)
        vals = weights.reshape(n, 1, self.k)
        for m2, idx in gathers:
            rows = m2[idx]  # (n, Gc, k)
            vals = (vals[:, :, None, :] * rows[:, None, :, :]).reshape(n, -1, self.k)
        return _segment_sum(keys, vals, knum)


def channel_weight_matrices(
    encoded, channel_measures, dtype=np.float64
) -> dict[str, np.ndarray]:
    """Per-relation (n, k) weight matrices for the measure relations:
    column c carries the ``sum`` payload where channel c measures that
    relation, its multiplicity everywhere else.  ``channel_measures[c]``
    names channel c's measure relation (None = COUNT).  Single source of
    the measure-channel weight layout — the api engine registry and the
    sparse jax path both build from it."""
    over: dict[str, np.ndarray] = {}
    for rel in {r for r in channel_measures if r is not None}:
        er = encoded[rel]
        cols = [
            er.payloads["sum"] if m == rel else er.count
            for m in channel_measures
        ]
        over[rel] = np.stack([np.asarray(c, dtype) for c in cols], axis=1)
    return over


def _decode_result(
    prep: Prepared, arr: np.ndarray, offsets: dict[str, int] | None = None
) -> dict[tuple, float]:
    nz = np.nonzero(arr)
    vals = arr[nz]
    out: dict[tuple, float] = {}
    cols = []
    for (rel, attr), codes in zip(prep.group_attrs, nz):
        off = (offsets or {}).get(attr, 0)
        cols.append(prep.dicts[attr].decode(codes + off))
    for i, v in enumerate(vals):
        out[tuple(c[i] for c in cols)] = float(v)
    return out


def _restrict(prep: Prepared, attr: str, lo: int, hi: int):
    """Encoded relations filtered to ``attr`` codes in [lo, hi), re-based."""
    enc = {}
    for rel, er in prep.encoded.items():
        if attr in er.attrs:
            c = er.attrs.index(attr)
            mask = (er.codes[:, c] >= lo) & (er.codes[:, c] < hi)
            codes = er.codes[mask].copy()
            codes[:, c] -= lo
            enc[rel] = type(er)(
                er.name, er.attrs, codes, er.count[mask],
                {k: v[mask] for k, v in er.payloads.items()},
            )
        else:
            enc[rel] = er
    return enc


def execute_tensor(
    query: JoinAggQuery,
    db: Database,
    prep: Prepared | None = None,
    stream: tuple[str, int] | None = None,
) -> dict[tuple, float]:
    """Execute on the numpy backend; returns {group values: aggregate}."""
    if prep is None:
        prep = prepare(query, db)
    query = prep.query  # fold may re-point the aggregate's measure relation
    kind = query.agg.kind

    def run_once(encoded, domains, offsets) -> dict[tuple, float]:
        if kind in ("count", "sum"):
            w_over = {}
            if kind == "sum":
                rel = query.agg.measure[0]
                w_over[rel] = encoded[rel].payloads["sum"].astype(np.float64)
            eng = TensorEngine(prep, w_over, domains=domains, encoded=encoded)
            return _decode_result(prep, eng.run(), offsets)
        if kind == "avg":
            rel = query.agg.measure[0]
            sums = TensorEngine(
                prep, {rel: encoded[rel].payloads["sum"].astype(np.float64)},
                domains=domains, encoded=encoded,
            ).run()
            cnts = TensorEngine(prep, domains=domains, encoded=encoded).run()
            with np.errstate(invalid="ignore", divide="ignore"):
                avg = np.where(cnts > 0, sums / np.maximum(cnts, 1), 0.0)
            return _decode_result(prep, avg, offsets)
        if kind in ("min", "max"):
            return _minmax(query, prep, encoded, domains, offsets)
        raise ValueError(kind)

    if stream is None:
        return run_once(prep.encoded, None, None)

    attr, tile = stream
    total = prep.dicts[attr].size
    result: dict[tuple, float] = {}
    for lo in range(0, total, tile):
        hi = min(lo + tile, total)
        enc = _restrict(prep, attr, lo, hi)
        domains = {a: prep.dicts[a].size for a in prep.dicts}
        domains[attr] = hi - lo
        result.update(run_once(enc, domains, {attr: lo}))
    return result


def minmax_arrays(
    prep: Prepared,
    encoded,
    domains,
    rel_m: str,
    kinds: tuple[str, ...],
) -> dict[str, np.ndarray]:
    """Dense MIN/MAX arrays over canonical group axes, one per ``kind``.

    One boolean-reachability pass re-rooted at the measure relation is
    shared by every requested kind (a multi-aggregate bundle asking for
    both MIN and MAX of the same measure pays for one traversal): boolean
    reachability messages flow from every subtree, then each kind runs
    its (min/max, select) reduction over the measure relation's edges.
    Unreached groups hold 0.0 — mask with a COUNT support before use,
    since zeros can also be genuine MIN/MAX values.

    The measure relation must be the root for a single upward pass; any
    root is valid for the contraction (the paper's group-relation-root
    rule only matters for its DFS anchoring), so we re-root at ``rel_m``.
    """
    from repro.core.hypergraph import Hypergraph

    hg = Hypergraph({r: frozenset(prep.schema.relevant[r]) for r in encoded})
    # decompose() requires a group-relation root; temporarily bless rel_m.
    deco = _decompose_any_root(prep, hg, rel_m)

    eng = TensorEngine(prep, boolean=True, domains=domains, encoded=encoded)
    eng.deco = deco

    er = encoded[rel_m]
    n = er.num_rows
    node = deco.nodes[rel_m]
    gathers: list[tuple[np.ndarray, np.ndarray]] = []
    child_gattrs: list[str] = []
    for child in node.children:
        msg = eng.message(child, rel_m)
        shared = msg.attrs[: msg.num_shared]
        pos = [er.attrs.index(a) for a in shared]
        sh_dims = eng._dims(shared)
        g_dims = eng._dims(msg.group_attrs)
        m2 = msg.array.reshape(
            int(np.prod(sh_dims, dtype=np.int64)) if sh_dims else 1,
            int(np.prod(g_dims, dtype=np.int64)) if g_dims else 1,
        )
        idx = (
            np.ravel_multi_index(tuple(er.codes[:, p] for p in pos), dims=sh_dims)
            if pos else np.zeros(n, dtype=np.int64)
        )
        gathers.append((m2, idx))
        child_gattrs.extend(msg.group_attrs)
    width = int(np.prod([m2.shape[1] for m2, _ in gathers], dtype=np.int64))

    own_g = prep.schema.group_of.get(rel_m)
    kept = (own_g,) if own_g else ()
    kdims = eng._dims(kept)
    if kept:
        keys = er.codes[:, er.attrs.index(own_g)].astype(np.int64)
    else:
        keys = np.zeros(n, dtype=np.int64)
    knum = int(np.prod(kdims, dtype=np.int64)) if kdims else 1
    order = np.argsort(keys, kind="stable")

    gattrs = ([own_g] if own_g else []) + child_gattrs
    raw = list(kept) + child_gattrs
    want = sorted(gattrs, key=eng.canonical.index)
    perm = [raw.index(a) for a in want]

    bads = {kind: np.inf if kind == "min" else -np.inf for kind in kinds}
    outs = {kind: np.full((knum, width), bads[kind]) for kind in kinds}
    # the (rows, width) reachability and candidate arrays grow with the
    # product of the child group domains, so edges go in key-sorted
    # chunks of bounded size; min/max are exact in any order
    chunk = max(1, _MINMAX_CHUNK_BYTES // (8 * max(width, 1)))
    for lo in range(0, n, chunk):
        rows_c = order[lo : lo + chunk]
        reach = np.ones((len(rows_c), 1))
        for m2, idx in gathers:
            g = m2[idx[rows_c]]
            reach = (reach[:, :, None] * g[:, None, :]).reshape(len(rows_c), -1)
        ks = keys[rows_c]
        bounds = np.flatnonzero(np.concatenate([[True], ks[1:] != ks[:-1]]))
        for kind in kinds:
            red_fn = np.minimum if kind == "min" else np.maximum
            m = er.payloads[kind][rows_c].astype(np.float64)
            cand = np.where(reach > 0, m[:, None], bads[kind])
            at = ks[bounds]  # distinct within a chunk
            outs[kind][at] = red_fn(
                outs[kind][at], red_fn.reduceat(cand, bounds, axis=0)
            )

    out_arrs: dict[str, np.ndarray] = {}
    for kind in kinds:
        arr = outs[kind].reshape(kdims + eng._dims(tuple(child_gattrs)))
        if perm != list(range(len(perm))):
            arr = np.transpose(arr, perm)
        out_arrs[kind] = np.where(np.isfinite(arr), arr, 0.0)
    return out_arrs


# bound on the float64 reachability/candidate chunk in minmax_arrays
_MINMAX_CHUNK_BYTES = 64 << 20


def _minmax(query, prep, encoded, domains, offsets) -> dict[tuple, float]:
    """Single-aggregate MIN/MAX(R.m) execution path (see minmax_arrays)."""
    rel_m, _ = query.agg.measure
    kind = query.agg.kind
    arr = minmax_arrays(prep, encoded, domains, rel_m, (kind,))[kind]
    # reachability mask (zeros can be genuine MIN/MAX values): a COUNT run
    cmask = TensorEngine(prep, domains=domains, encoded=encoded).run() > 0
    res: dict[tuple, float] = {}
    nzi = np.nonzero(cmask)
    cols = []
    for (r, attr), codes in zip(prep.group_attrs, nzi):
        off = (offsets or {}).get(attr, 0)
        cols.append(prep.dicts[attr].decode(codes + off))
    vals = arr[nzi]
    for i, v in enumerate(vals):
        res[tuple(c[i] for c in cols)] = float(v)
    return res


def _decompose_any_root(prep: Prepared, hg, root: str):
    """Decomposition rooted anywhere (tensor engine doesn't need the
    paper's group-relation-root rule); reuses the MST + RIP machinery."""
    from repro.core import decomposition as D

    order_all = [r for r in prep.query.relations if r in hg.edges]
    parent = D._max_spanning_tree(hg, root, order_all)
    D._check_running_intersection(hg, parent)
    nodes = {
        r: D.TreeNode(r, parent.get(r), is_group=r in prep.schema.group_of)
        for r in order_all
    }
    for r, p in parent.items():
        nodes[p].children.append(r)
    order: list[str] = []
    queue = [root]
    while queue:
        cur = queue.pop(0)
        order.append(cur)
        queue.extend(c for c in order_all if parent.get(c) == cur)
    return D.Decomposition(
        root=root, nodes=nodes, order=order,
        group_relations=[r for r in order_all if r in prep.schema.group_of],
        anchor={}, sink_anchor={}, branching_parent={},
    )
