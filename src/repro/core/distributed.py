"""Mesh-sharded **sparse** JOIN-AGG execution (DESIGN.md §8).

The paper's outer loop ("for every source node, walk the decomposition
tree") is embarrassingly parallel, and the width bounds survive
partitioned evaluation — so the distributed path shards the **root group
attribute**: its code range is cut into contiguous grouped-CSR row
ranges (:meth:`~repro.core.prepare.CSRView.shard`), one per device on
the mesh's ``data`` axis.  Each device holds only

* its slice of every relation containing the shard attribute (one
  binary-search CSR block per relation, never a COO scan), and
* the full (small) messages of subtrees that do not touch the shard
  attribute — replicated, exactly the paper's per-source iteration
  spread over the pod.

Execution is a ``shard_map`` over the static decomposition-tree hop
schedule: every hop runs device-locally as a gather → row-aligned
product → segment reduction (the same contraction the single-device
Pallas kernels compute; under ``shard_map`` the hops lower to XLA
scatter-add / scatter-min ops so the same program runs on CPU meshes),
and the per-shard group partials — disjoint along the shard axis, by the
running-intersection property — are combined with a final
``all_gather``.  No dense relation tensor is ever built; the dense
``DenseProgram`` lowering this module used to wrap is retired
(PR 4 retired it on one device, this module retires it on many).

``run`` executes on whatever devices exist (tests use virtual CPU
devices); ``lower_distributed`` AOT-lowers the sharded program for a
given mesh.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.jax_engine import EDGE_BUCKET, _INT32_LIMIT
from repro.core.prepare import Prepared, _ravel, csr_restrict
from repro.core.tensor_engine import channel_weight_matrices
from repro.kernels import ops


def mesh_axis(mesh: Mesh) -> str:
    """The axis the source partition rides: ``data`` when present."""
    return "data" if "data" in mesh.axis_names else mesh.axis_names[0]


def resolve_mesh(mesh) -> Mesh:
    """Accept a :class:`Mesh` or a shard count (``8`` = 8 devices on a
    1-D ``data`` axis)."""
    if isinstance(mesh, Mesh):
        return mesh
    n = int(mesh)
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(
            f"mesh over {n} shards needs {n} devices, have {len(devs)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            "before jax initializes for a virtual CPU mesh)"
        )
    return Mesh(np.asarray(devs[:n]), ("data",))


def mesh_shards(mesh) -> int:
    """Shard count of a mesh spec (int or Mesh) — no devices needed for
    an int, so ``Plan.explain()`` works before any mesh exists."""
    if isinstance(mesh, Mesh):
        return mesh.shape[mesh_axis(mesh)]
    return int(mesh)


def shard_attr(prep: Prepared) -> str:
    """The partitioned attribute: the root relation's group attribute."""
    root = prep.decomposition.root
    attr = prep.schema.group_of.get(root)
    if attr is None:  # decompose() always roots at a group relation
        raise ValueError(f"root {root!r} carries no group attribute")
    return attr


@dataclass(frozen=True)
class _Hop:
    """Static metadata for one decomposition-tree hop (uniform across
    shards: the shard attribute's domain is padded to the tile width).

    ``kept_attrs``/``child_shared`` are the single source of the key
    layout — the host-side ravel (:func:`_hop_arrays`) and the traced
    shapes both come from here, so they cannot drift apart."""

    rel: str
    children: tuple[str, ...]
    knum: int  # local output-key space (Π kept dims)
    width: int  # Π of child group widths
    kept_attrs: tuple[str, ...]  # up attrs + own group attr (key ravel)
    kept_dims: tuple[int, ...]
    child_shared: tuple[tuple[str, ...], ...]  # per child: gather ravel
    child_shapes: tuple[tuple[int, int], ...]  # (shared_prod, group_prod)
    gdims_all: tuple[int, ...]  # concatenated child group dims
    perm: tuple[int, ...]  # raw -> canonical-order transpose
    out_dims: tuple[int, ...]  # message dims after the transpose


def _build_schedule(prep: Prepared, domains: dict[str, int]) -> tuple[_Hop, ...]:
    """Post-order hop schedule mirroring ``TensorEngine.contract_rows``
    (same kept/shared attr math, same canonical transpose), with every
    shape static so the whole tree walk traces into one jitted program."""
    deco = prep.decomposition
    canonical = [attr for _, attr in prep.group_attrs]
    hops: list[_Hop] = []
    msg_attrs: dict[str, tuple[tuple[str, ...], int]] = {}

    def dims(attrs: tuple[str, ...]) -> tuple[int, ...]:
        return tuple(domains[a] for a in attrs)

    def prod(d: tuple[int, ...]) -> int:
        return int(np.prod(d, dtype=np.int64)) if d else 1

    def walk(rel: str, parent: str | None) -> None:
        er = prep.encoded[rel]
        children = tuple(deco.nodes[rel].children)
        for c in children:
            walk(c, rel)
        own_g = prep.schema.group_of.get(rel)
        up: tuple[str, ...] = ()
        if parent is not None:
            up = tuple(sorted(set(er.attrs) & set(prep.encoded[parent].attrs)))
        child_gattrs: list[str] = []
        child_shapes: list[tuple[int, int]] = []
        child_shared: list[tuple[str, ...]] = []
        for c in children:
            cattrs, nsh = msg_attrs[c]
            shared, gattrs = cattrs[:nsh], cattrs[nsh:]
            child_shared.append(shared)
            child_shapes.append((prod(dims(shared)), prod(dims(gattrs))))
            child_gattrs.extend(gattrs)
        kept_own = up + ((own_g,) if own_g else ())
        kept_dims = dims(kept_own)
        knum = prod(kept_dims)
        if knum >= _INT32_LIMIT:
            raise NotImplementedError(
                f"distributed-sparse: {rel!r} key space {knum} exceeds int32"
            )
        width = 1
        for _, gp in child_shapes:
            width *= gp
        gattrs_all = ([own_g] if own_g else []) + child_gattrs
        want_g = sorted(gattrs_all, key=canonical.index)
        raw = list(kept_own) + child_gattrs
        want = list(up) + want_g
        perm = tuple(raw.index(a) for a in want)
        msg_attrs[rel] = (tuple(want), len(up))
        hops.append(
            _Hop(
                rel=rel,
                children=children,
                knum=knum,
                width=width,
                kept_attrs=kept_own,
                kept_dims=kept_dims,
                child_shared=tuple(child_shared),
                child_shapes=tuple(child_shapes),
                gdims_all=dims(tuple(child_gattrs)),
                perm=perm,
                out_dims=dims(tuple(want)),
            )
        )

    walk(deco.root, None)
    root_attrs, _ = msg_attrs[deco.root]
    assert root_attrs == tuple(canonical), (root_attrs, canonical)
    return tuple(hops)


def _hop_arrays(
    hops: tuple[_Hop, ...],
    enc,
    domains: dict[str, int],
    chan_w: dict[str, np.ndarray],
    mm_w: list[dict[str, np.ndarray]],
) -> dict[str, np.ndarray]:
    """One shard's unpadded hop inputs, in grouped-CSR (key-sorted) order."""
    out: dict[str, np.ndarray] = {}
    for hop in hops:
        er = enc[hop.rel]
        kcols = [er.attrs.index(a) for a in hop.kept_attrs]
        keys = _ravel(er.codes, kcols, [domains[a] for a in hop.kept_attrs])
        order = np.argsort(keys, kind="stable")
        out[f"k:{hop.rel}"] = keys[order].astype(np.int32)
        out[f"wc:{hop.rel}"] = chan_w[hop.rel][order]
        for j, w in enumerate(mm_w):
            out[f"wm{j}:{hop.rel}"] = w[hop.rel][order]
        for child, cattrs in zip(hop.children, hop.child_shared):
            ccols = [er.attrs.index(a) for a in cattrs]
            idx = _ravel(er.codes, ccols, [domains[a] for a in cattrs])
            out[f"i:{hop.rel}:{child}"] = idx[order].astype(np.int32)
    return out


def _pad_stack(
    per_shard: list[dict[str, np.ndarray]], sentinels: dict[str, int]
) -> dict[str, np.ndarray]:
    """Pad each hop input to the max shard length (rounded up to the
    ``EDGE_BUCKET``) and stack to ``(S, n_pad, ...)``.  Key padding is an
    out-of-range sentinel the device-side scatter drops; weight padding
    is 0 and gather-index padding is 0 (a valid but inert row)."""
    names = per_shard[0].keys()
    out: dict[str, np.ndarray] = {}
    for name in names:
        arrs = [sh[name] for sh in per_shard]
        n_max = max(len(a) for a in arrs)
        n_pad = max(EDGE_BUCKET, -(-n_max // EDGE_BUCKET) * EDGE_BUCKET)
        fill = sentinels.get(name, 0)
        padded = []
        for a in arrs:
            pad = n_pad - len(a)
            if pad:
                block = np.full((pad,) + a.shape[1:], fill, a.dtype)
                a = np.concatenate([a, block])
            padded.append(a)
        out[name] = np.stack(padded)
    return out


@dataclass
class DistributedSparseProgram:
    """A sharded sparse execution of one ``Prepared`` over a device mesh.

    ``channel_measures`` mirrors :class:`~repro.core.jax_engine.
    SparseProgram`; ``minmax`` is a tuple of ``(kind, relation)`` pairs
    served by the same ``(min, +)`` / ``(max, +)`` semiring pass, sharing
    the channel pass's gather indices.  When ``fused`` the device-local
    hop bodies run as single :func:`repro.kernels.fused_hop` megakernel
    calls (tile configs resolved host-side at build time, one sum + one
    minmax config per hop) instead of the gather / product / scatter
    trio.  Built once per (plan, mesh); ``run()`` re-executes the jitted
    shard_map program.
    """

    prep: Prepared
    channel_measures: tuple[str | None, ...]
    minmax: tuple[tuple[str, str], ...]
    mesh: Mesh
    axis: str
    attr: str
    ranges: tuple[tuple[int, int], ...]  # per-shard [lo, hi) code ranges
    tile: int  # uniform (padded) local domain of the shard attr
    hops: tuple[_Hop, ...]
    inputs: dict[str, np.ndarray]  # stacked (S, n_pad, ...) hop arrays
    fused: bool = False
    # per hop: (sum-pass TileConfig, minmax-pass TileConfig); () unfused
    tile_cfgs: tuple = ()
    _jitted: Callable | None = field(default=None, repr=False)
    # device-resident copies of ``inputs``, placed once on first run()
    _dev_inputs: dict | None = field(default=None, repr=False)

    @property
    def k(self) -> int:
        return len(self.channel_measures)

    @property
    def num_shards(self) -> int:
        return len(self.ranges)

    # ------------------------------------------------------------------
    def _fn(self) -> Callable:
        hops, k, axis = self.hops, self.k, self.axis
        n_mm = len(self.minmax)
        idents = tuple(
            np.inf if kind == "min" else -np.inf for kind, _ in self.minmax
        )
        fused = self.fused
        cfgs = self.tile_cfgs if fused else ((None, None),) * len(hops)

        def fn(inputs):  # jit-region
            msgs: dict[str, jax.Array] = {}
            mm_msgs: list[dict[str, jax.Array]] = [{} for _ in range(n_mm)]
            for hop, (cfg_c, cfg_m) in zip(hops, cfgs):
                keys = inputs[f"k:{hop.rel}"][0]
                gathers = [
                    inputs[f"i:{hop.rel}:{c}"][0] for c in hop.children
                ]
                n = keys.shape[0]
                # distributive channels: row-aligned product, scatter-add
                w = inputs[f"wc:{hop.rel}"][0]  # (n, k)
                if fused:
                    # one megakernel per hop; padded keys carry the
                    # hop.knum sentinel, which either exceeds the padded
                    # segment grid or lands in rows fused_hop trims
                    seg = ops.fused_hop(
                        keys,
                        w,
                        tuple(
                            msgs[c].reshape(shp, gp * k)
                            for c, (shp, gp) in zip(
                                hop.children, hop.child_shapes
                            )
                        ),
                        tuple(gathers),
                        num_segments=hop.knum,
                        k=k,
                        kind="sum",
                        block_e=cfg_c.block_e,
                        block_s=cfg_c.block_s,
                        block_r=cfg_c.block_r,
                    )
                else:
                    vals = w[:, None, :]
                    for c, (shp, gp), idx in zip(
                        hop.children, hop.child_shapes, gathers
                    ):
                        rows = msgs[c].reshape(shp, gp, k)[idx]  # (n, gp, k)
                        vals = (
                            vals[:, :, None, :] * rows[:, None, :, :]
                        ).reshape(n, -1, k)
                    flat = vals.reshape(n, hop.width * k)
                    seg = (
                        jnp.zeros((hop.knum, hop.width * k), jnp.float32)
                        .at[keys]
                        .add(flat)
                    )
                arr = seg.reshape(hop.kept_dims + hop.gdims_all + (k,))
                perm = hop.perm + (len(hop.perm),)  # channel axis stays last
                msgs[hop.rel] = jnp.transpose(arr, perm)
                # (min, +) / (max, +) semiring passes share the gathers
                for j, ((kind, _), ident) in enumerate(
                    zip(self.minmax, idents)
                ):
                    wm = inputs[f"wm{j}:{hop.rel}"][0]  # (n,)
                    if fused:
                        red = ops.fused_hop(
                            keys,
                            wm[:, None],
                            tuple(
                                mm_msgs[j][c].reshape(shp, gp)
                                for c, (shp, gp) in zip(
                                    hop.children, hop.child_shapes
                                )
                            ),
                            tuple(gathers),
                            num_segments=hop.knum,
                            k=1,
                            kind=kind,
                            block_e=cfg_m.block_e,
                            block_s=cfg_m.block_s,
                            block_r=cfg_m.block_r,
                        )
                    else:
                        cand = wm[:, None]
                        for c, (shp, gp), idx in zip(
                            hop.children, hop.child_shapes, gathers
                        ):
                            rows = mm_msgs[j][c].reshape(shp, gp)[idx]
                            cand = (
                                cand[:, :, None] + rows[:, None, :]
                            ).reshape(n, -1)
                        base = jnp.full(
                            (hop.knum, hop.width), ident, jnp.float32
                        )
                        red = (
                            base.at[keys].min(cand)
                            if kind == "min"
                            else base.at[keys].max(cand)
                        )
                    mm_msgs[j][hop.rel] = jnp.transpose(
                        red.reshape(hop.kept_dims + hop.gdims_all), hop.perm
                    )
            root = hops[-1].rel
            outs = [msgs[root]] + [mm_msgs[j][root] for j in range(n_mm)]
            # per-shard group partials are disjoint along the shard axis
            # (running intersection property) — gather, don't psum
            return tuple(
                jax.lax.all_gather(o, axis, tiled=False) for o in outs
            )

        return fn

    def jit(self) -> Callable:
        if self._jitted is None:
            smapped = shard_map(
                self._fn(),
                mesh=self.mesh,
                in_specs=P(self.axis),
                out_specs=P(),
                # outputs ARE replicated (the final all_gather), but the
                # static rep-checker cannot see through the scatter ops
                check_rep=False,
            )
            self._jitted = jax.jit(smapped)
        return self._jitted

    def input_shardings(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.axis))

    def lower(self):
        """AOT-lower the sharded program with ShapeDtypeStruct inputs."""
        sh = self.input_shardings()
        specs = {
            name: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)
            for name, a in self.inputs.items()
        }
        return self.jit().lower(specs)

    # ------------------------------------------------------------------
    def run(self) -> list[tuple[np.ndarray, list[np.ndarray], dict[str, int]]]:
        """Execute; one ``(channels, minmax arrays, offsets)`` triple per
        shard.  ``channels`` is ``(*local_group_dims, k)`` with the shard
        axis cut back to the shard's real range; minmax arrays hold 0.0
        where unreached (mask with the COUNT channel)."""
        if self._dev_inputs is None:
            sh = self.input_shardings()
            self._dev_inputs = {
                n: jax.device_put(a, sh) for n, a in self.inputs.items()
            }
        n_passes = 1 + len(self.minmax)
        if self.fused:
            ops.record_dispatch("fused", len(self.hops) * n_passes)
        else:
            for hop in self.hops:
                nc = len(hop.children)
                ops.record_dispatch("gather", nc * n_passes)
                ops.record_dispatch("product", nc * n_passes)
                ops.record_dispatch("scatter", n_passes)
        outs = self.jit()(self._dev_inputs)
        chan = np.asarray(outs[0])  # (S, tile, ..., k)
        mms = [np.asarray(o) for o in outs[1:]]
        pos = [a for _, a in self.prep.group_attrs].index(self.attr)
        results = []
        for s, (lo, hi) in enumerate(self.ranges):
            cut = [slice(None)] * (chan.ndim - 1)
            cut[pos] = slice(0, hi - lo)
            arr = chan[s][tuple(cut)]
            mm_s = [
                np.where(
                    np.isfinite(m[s][tuple(cut[:-1])]),
                    m[s][tuple(cut[:-1])],
                    0.0,
                ).astype(np.float32)
                for m in mms
            ]
            results.append((arr, mm_s, {self.attr: lo}))
        return results

    # ------------------------------------------------------------------
    def per_device_bytes(self) -> int:
        """Per-device working set: this device's slice of the stacked hop
        inputs (real nbytes of the padded arrays) plus the peak bytes of
        simultaneously-live local messages across the tree walk — every
        message shape is static, so the walk is accounted exactly: a
        child's message stays live until its parent hop consumes it."""
        edges = sum(a.nbytes // self.num_shards for a in self.inputs.values())
        per_msg = 4 * (self.k + len(self.minmax))  # f32, channels + mm
        live: dict[str, int] = {}
        peak = 0
        for hop in self.hops:
            out_bytes = int(np.prod(hop.out_dims, dtype=np.int64)) * per_msg
            peak = max(peak, sum(live.values()) + out_bytes)
            for c in hop.children:
                live.pop(c)
            live[hop.rel] = out_bytes
        return edges + peak


def build_distributed_program(
    prep: Prepared,
    channel_measures: tuple[str | None, ...] = (None,),
    mesh: Mesh | int = 1,
    minmax: tuple[tuple[str, str], ...] = (),
    fused: bool | None = None,
) -> DistributedSparseProgram:
    """Partition ``prep`` over the mesh's data axis and bind the sharded
    hop schedule + per-shard CSR slices into a runnable program.

    ``fused=None`` defers to the ``REPRO_FUSED`` environment switch
    (:func:`repro.kernels.ops.fused_enabled`); the resolved flag joins
    the memo key, so fused and three-dispatch programs coexist.

    Memoized on the ``Prepared`` per (channels, minmax, mesh, fused):
    repeated ``Plan.execute(mesh=...)`` calls reuse one built program and
    one shard_map compile instead of re-slicing and re-tracing every
    call.  The memo is the bounded :class:`~repro.serve.cache.LRUCache`
    on ``Prepared._program_cache`` (hit/miss/eviction counters included),
    so a server-cached plan cannot pin unboundedly many shard programs."""
    mesh = resolve_mesh(mesh)
    fused = ops.fused_enabled(fused)
    cache = prep._program_cache
    key = (
        "distributed", tuple(channel_measures), tuple(minmax), mesh, fused
    )
    cached = cache.get(key)
    if cached is not None:
        return cached
    axis = mesh_axis(mesh)
    num = mesh.shape[axis]
    attr = shard_attr(prep)
    root = prep.decomposition.root
    view = prep.csr_view(root, (attr,))
    ranges = tuple((lo, hi) for lo, hi, _ in view.shard(num))
    # the uniform local domain comes FROM the ranges (not a re-derived
    # formula) so a rebased shard code can never reach the OOB sentinel
    tile = max(max((hi - lo for lo, hi in ranges), default=1), 1)

    domains = {a: prep.dicts[a].size for a in prep.dicts}
    domains[attr] = tile
    hops = _build_schedule(prep, domains)

    per_shard: list[dict[str, np.ndarray]] = []
    for lo, hi in ranges:
        enc = csr_restrict(prep, attr, lo, hi)
        over = channel_weight_matrices(enc, channel_measures, dtype=np.float32)
        k = len(channel_measures)
        chan_w = {}
        for rel, er in enc.items():
            w = over.get(rel)
            if w is None:
                c = er.count.astype(np.float32)
                w = np.repeat(c[:, None], k, axis=1)
            chan_w[rel] = np.ascontiguousarray(w, dtype=np.float32)
        mm_w = []
        for kind, rel_m in minmax:
            mm_w.append(
                {
                    rel: (
                        er.payloads[kind].astype(np.float32)
                        if rel == rel_m
                        else np.zeros(er.num_rows, np.float32)
                    )
                    for rel, er in enc.items()
                }
            )
        per_shard.append(_hop_arrays(hops, enc, domains, chan_w, mm_w))

    sentinels = {f"k:{h.rel}": h.knum for h in hops}
    inputs = _pad_stack(per_shard, sentinels)

    tile_cfgs: tuple = ()
    if fused:
        # resolve megakernel tiles host-side, once per build: the traced
        # fn must close over static block sizes
        from repro.kernels import autotune

        k = len(channel_measures)
        cfg_list = []
        for hop in hops:
            edges = inputs[f"k:{hop.rel}"].shape[1]
            rows = tuple(shp for shp, _ in hop.child_shapes)
            widths = tuple(gp for _, gp in hop.child_shapes)
            cfg_c = autotune.tiles_for(
                autotune.hop_shape(
                    edges=edges,
                    child_rows=rows,
                    k=k,
                    kind="sum",
                    child_widths=widths,
                    num_segments=hop.knum,
                )
            )
            cfg_m = cfg_c
            if minmax:
                cfg_m = autotune.tiles_for(
                    autotune.hop_shape(
                        edges=edges,
                        child_rows=rows,
                        k=1,
                        kind=minmax[0][0],
                        child_widths=widths,
                        num_segments=hop.knum,
                    )
                )
            cfg_list.append((cfg_c, cfg_m))
        tile_cfgs = tuple(cfg_list)

    return cache.setdefault(key, DistributedSparseProgram(
        prep=prep,
        channel_measures=tuple(channel_measures),
        minmax=tuple(minmax),
        mesh=mesh,
        axis=axis,
        attr=attr,
        ranges=ranges,
        tile=tile,
        hops=hops,
        inputs=inputs,
        fused=fused,
        tile_cfgs=tile_cfgs,
    ))


def run(prep: Prepared, mesh: Mesh | int) -> dict[tuple, float]:
    """Sharded COUNT over the mesh; ``{group values: count}`` (the legacy
    entry point — multi-aggregate bundles go through ``Plan.execute``)."""
    from repro.core.tensor_engine import _decode_result

    prog = build_distributed_program(prep, (None,), mesh)
    out: dict[tuple, float] = {}
    for arr, _, offsets in prog.run():
        out.update(_decode_result(prep, arr[..., 0], offsets))
    return out


def run_query(prep: Prepared, mesh: Mesh | int) -> dict[tuple, float]:
    """Sharded single-aggregate execution of ``prep.query`` — the
    distributed analogue of ``execute_jax`` (COUNT/SUM/MIN/MAX; AVG
    assembles on the planner, like everywhere else)."""
    from repro.core.tensor_engine import _decode_result

    query = prep.query
    kind = query.agg.kind
    if kind in ("count", "sum"):
        cm = (query.agg.measure[0] if kind == "sum" else None,)
        prog = build_distributed_program(prep, cm, mesh)
        out: dict[tuple, float] = {}
        for arr, _, offsets in prog.run():
            out.update(_decode_result(prep, arr[..., 0], offsets))
        return out
    if kind not in ("min", "max"):
        raise NotImplementedError(
            "distributed-sparse: COUNT/SUM/MIN/MAX (AVG assembles on the "
            "planner)"
        )
    rel_m = query.agg.measure[0]
    prog = build_distributed_program(
        prep, (None,), mesh, minmax=((kind, rel_m),)
    )
    out = {}
    for arr, mm_arrs, offsets in prog.run():
        # keep every joined group, zeros included (MIN/MAX semantics)
        nz = np.nonzero(arr[..., 0] > 0)
        cols = [
            prep.dicts[attr].decode(codes + offsets.get(attr, 0))
            for (_, attr), codes in zip(prep.group_attrs, nz)
        ]
        for i, v in enumerate(mm_arrs[0][nz]):
            out[tuple(c[i] for c in cols)] = float(v)
    return out


def lower_distributed(prep: Prepared, mesh: Mesh | int):
    """AOT-lower the sharded sparse COUNT program for ``mesh``."""
    return build_distributed_program(prep, (None,), mesh).lower()
