"""Execution engines behind the logical planner (DESIGN.md §6).

The planner compiles a query down to one :class:`Prepared` plus

* an ordered tuple of distributive semiring :class:`Channel`\\ s (COUNT, or
  SUM over a measure relation's payload) contracted **in a single pass**
  — weight vectors become weight matrices, messages carry a channel axis,
  and AVG is assembled from a SUM/COUNT pair at decode time, and
* a tuple of :class:`MinMaxRequest`\\ s, served by the shared
  boolean-reachability kernel (:func:`repro.core.tensor_engine.minmax_arrays`)
  — MIN/MAX are not multilinear, so they are engine-independent by design
  and every engine composes with the same kernel, one pass per measure
  relation regardless of how many kinds ride on it.

An :class:`Engine` turns those into sparse :class:`EngineOutput` tiles.
Engines register by name — ``tensor``, ``jax``, ``ref`` — replacing the
``engine: str`` dispatch that used to be scattered across free functions;
:func:`register_engine` admits user-defined backends under new names.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Protocol, runtime_checkable

import numpy as np

from repro import obs
from repro.core.prepare import Prepared
from repro.core.tensor_engine import (
    ChannelTensorEngine,
    _restrict,
    channel_weight_matrices,
    minmax_arrays,
)


@dataclass(frozen=True)
class Channel:
    """One distributive channel: ``count``, or ``sum`` over a measure.

    ``measure`` names the *post-rewrite* relation carrying the payload
    (the planner resolves folds and GHD bag covers before engines run).
    """

    kind: str  # "count" | "sum"
    measure: tuple[str, str] | None = None


COUNT_CHANNEL = Channel("count")


@dataclass(frozen=True)
class MinMaxRequest:
    kind: str  # "min" | "max"
    measure: tuple[str, str]


@dataclass
class EngineOutput:
    """Sparse results for one (tile of the) group space.

    ``group_codes`` rows are global dictionary codes over the canonical
    group attributes (stream tiles are already offset back); rows are the
    groups whose join is non-empty (COUNT channel > 0).
    """

    group_codes: np.ndarray  # (n, n_group_attrs) int64
    channel_values: np.ndarray  # (n, k) float64, column order = channels
    minmax_values: dict[MinMaxRequest, np.ndarray]  # (n,) each


@runtime_checkable
class Engine(Protocol):
    """The contract an execution backend implements for the planner."""

    name: str
    supports_streaming: bool

    def run(
        self,
        prep: Prepared,
        channels: tuple[Channel, ...],
        minmax: tuple[MinMaxRequest, ...],
        stream: tuple[str, int] | None = None,
        memory_budget: int | None = None,
    ) -> list[EngineOutput]:
        """Contract all channels in one pass; one output per stream tile.

        ``memory_budget`` is advisory — engines with an internal physical
        choice (the jax engine's dense-vs-sparse path) use it; others may
        ignore it (the planner already resolved ``stream`` from it).

        Engines that can execute over a device mesh set a
        ``supports_mesh = True`` class attribute and accept a ``mesh``
        keyword (a :class:`jax.sharding.Mesh` or a shard count); the
        planner raises :class:`UnsupportedPlanOption` before calling an
        engine that cannot honor a requested mesh."""
        ...


def channel_weight_overrides(
    prep: Prepared, encoded, channels: tuple[Channel, ...]
) -> dict[str, np.ndarray]:
    """Per-relation (n, k) weight matrices for the measure relations —
    thin adapter over :func:`~repro.core.tensor_engine.
    channel_weight_matrices`, the single source of the layout."""
    cm = tuple(c.measure[0] if c.kind == "sum" else None for c in channels)
    return channel_weight_matrices(encoded, cm)


def _shared_minmax(
    prep: Prepared,
    encoded,
    domains,
    minmax: tuple[MinMaxRequest, ...],
) -> dict[MinMaxRequest, np.ndarray]:
    """One reachability pass per measure relation, all kinds at once."""
    by_rel: dict[str, list[MinMaxRequest]] = {}
    for req in minmax:
        by_rel.setdefault(req.measure[0], []).append(req)
    out: dict[MinMaxRequest, np.ndarray] = {}
    for rel, reqs in by_rel.items():
        kinds = tuple(dict.fromkeys(r.kind for r in reqs))
        arrs = minmax_arrays(prep, encoded, domains, rel, kinds)
        for r in reqs:
            out[r] = arrs[r.kind]
    return out


def sparsify(
    prep: Prepared,
    channels: tuple[Channel, ...],
    arr: np.ndarray,
    mm: dict[MinMaxRequest, np.ndarray],
    offsets: dict[str, int] | None,
) -> EngineOutput:
    """Dense ``(*group_dims, k)`` channel array -> sparse EngineOutput."""
    with obs.span("joinagg.assemble"):
        ci = channels.index(COUNT_CHANNEL)
        nz = np.nonzero(arr[..., ci] > 0)
        codes = np.stack(nz, axis=1).astype(np.int64)
        if offsets:
            for i, (_, attr) in enumerate(prep.group_attrs):
                codes[:, i] += offsets.get(attr, 0)
        return EngineOutput(
            codes,
            arr[nz].astype(np.float64),
            {req: a[nz].astype(np.float64) for req, a in mm.items()},
        )


class TensorChannelEngine:
    """Numpy multi-channel contraction — the only streaming-capable
    backend (group-axis tiles bound peak message memory exactly like the
    single-aggregate tensor path)."""

    name = "tensor"
    supports_streaming = True

    def run(self, prep, channels, minmax, stream=None, memory_budget=None):
        if stream is None:
            return [self._run_once(prep, channels, minmax, prep.encoded, None, None)]
        attr, tile = stream
        total = prep.dicts[attr].size
        outs = []
        for lo in range(0, total, tile):
            hi = min(lo + tile, total)
            enc = _restrict(prep, attr, lo, hi)
            domains = {a: prep.dicts[a].size for a in prep.dicts}
            domains[attr] = hi - lo
            outs.append(
                self._run_once(prep, channels, minmax, enc, domains, {attr: lo})
            )
        return outs

    def _run_once(self, prep, channels, minmax, encoded, domains, offsets):
        over = channel_weight_overrides(prep, encoded, channels)
        eng = ChannelTensorEngine(
            prep, len(channels), over, domains=domains, encoded=encoded
        )
        arr = eng.run()  # (*group_dims, k)
        mm = _shared_minmax(prep, encoded, domains, minmax)
        return sparsify(prep, channels, arr, mm, offsets)


class JaxChannelEngine:
    """Sparse-first jax backend (f32, exact to 2**24 per partial product).

    :func:`~repro.core.jax_engine.choose_jax_path` estimates dense-vs-
    sparse peak bytes per node: the sparse
    :class:`~repro.core.jax_engine.SparseProgram` (Pallas kernel hops
    over grouped-CSR relations, group-axis stream tiles, MIN/MAX on the
    semiring kernels) runs whenever the dense einsum program would cross
    its memory cliff or a stream is requested; otherwise the jitted
    dense einsum contraction runs, with MIN/MAX riding on the shared
    numpy reachability kernel."""

    name = "jax"
    supports_streaming = True
    supports_mesh = True
    supports_fused = True

    def run(
        self,
        prep,
        channels,
        minmax,
        stream=None,
        memory_budget=None,
        mesh=None,
        fused=None,
    ):
        from repro.core.jax_engine import (
            build_sparse_program,
            choose_jax_path,
            execute_jax_channels,
        )

        cm = tuple(ch.measure[0] if ch.kind == "sum" else None for ch in channels)
        if mesh is not None:
            return self._run_distributed(
                prep, channels, minmax, cm, mesh, fused=fused
            )
        with obs.span("joinagg.plan"):
            choice = choose_jax_path(
                prep, k=len(channels), memory_budget=memory_budget,
                stream=stream, measured=cm,
            )
            prog = build_sparse_program(prep, cm, fused=fused)
        # an explicit .fused(True) pins the sparse path: fused hops have
        # no dense-einsum form (REPRO_FUSED alone does not move the
        # dense/sparse choice — it only fuses hops when sparse runs)
        if choice.path == "dense" and fused is not True:
            arr = execute_jax_channels(prep, cm)  # (k, *group_dims)
            arr = np.moveaxis(arr.astype(np.float64), 0, -1)
            mm = _shared_minmax(prep, prep.encoded, None, minmax)
            return [sparsify(prep, channels, arr, mm, None)]
        if stream is None:
            tiles = [(None, None, None)]
        else:
            tiles = prog.run_stream(*stream)
        outs = []
        for enc, domains, offsets in tiles:
            views: dict = {}  # share per-tile CSR sorts across the passes
            arr = prog.run_channels(enc, domains, view_cache=views)
            mm = {
                req: prog.run_minmax(
                    req.kind, req.measure[0], enc, domains, view_cache=views
                )
                for req in minmax
            }
            outs.append(
                sparsify(prep, channels, arr.astype(np.float64), mm, offsets)
            )
        return outs

    def _run_distributed(self, prep, channels, minmax, cm, mesh, fused=None):
        """Sharded sparse execution over the mesh's data axis: per-shard
        CSR partitions of the root group attribute under ``shard_map``,
        one :class:`EngineOutput` per shard (DESIGN.md §8).  MIN/MAX ride
        the same program as ``(min, +)`` semiring outputs, masked by the
        COUNT channel like every other sparse path."""
        from repro.core.distributed import build_distributed_program

        prog = build_distributed_program(
            prep,
            cm,
            mesh,
            minmax=tuple((r.kind, r.measure[0]) for r in minmax),
            fused=fused,
        )
        outs = []
        for arr, mm_arrs, offsets in prog.run():
            # minmax arrays already hold 0.0 where unreached; sparsify
            # keeps only COUNT>0 rows, the same support mask
            mm = dict(zip(minmax, mm_arrs))
            outs.append(
                sparsify(prep, channels, arr.astype(np.float64), mm, offsets)
            )
        return outs


class RefChannelEngine:
    """Paper-faithful data-graph DFS carrying k-channel running counts;
    MIN/MAX ride on the shared numpy reachability kernel."""

    name = "ref"
    supports_streaming = False

    def run(self, prep, channels, minmax, stream=None, memory_budget=None):
        from repro.core.ref_engine import execute_ref_channels

        assert stream is None, "validated by the planner"
        cm = tuple(ch.measure[0] if ch.kind == "sum" else None for ch in channels)
        sparse = execute_ref_channels(prep, cm)
        ci = channels.index(COUNT_CHANNEL)
        keys = sorted(k for k, v in sparse.items() if v[ci] > 0)
        codes = np.array(keys, dtype=np.int64).reshape(len(keys), len(prep.group_attrs))
        vals = (
            np.stack([sparse[k] for k in keys])
            if keys
            else np.zeros((0, len(channels)))
        )
        mm_dense = _shared_minmax(prep, prep.encoded, None, minmax)
        sel = tuple(codes[:, i] for i in range(codes.shape[1]))
        mm = {req: a[sel].astype(np.float64) for req, a in mm_dense.items()}
        return [EngineOutput(codes, vals.astype(np.float64), mm)]


_REGISTRY: dict[str, Engine] = {}


def register_engine(engine: Engine) -> Engine:
    """Register an execution backend under ``engine.name``."""
    _REGISTRY[engine.name] = engine
    return engine


def resolve_engine(engine: str | Engine) -> Engine:
    if isinstance(engine, str):
        try:
            return _REGISTRY[engine]
        except KeyError:
            raise ValueError(
                f"unknown engine {engine!r}; registered: {sorted(_REGISTRY)}"
            ) from None
    return engine


register_engine(TensorChannelEngine())
register_engine(JaxChannelEngine())
register_engine(RefChannelEngine())
