"""Per-device tile autotuning for the fused JOIN-AGG hop (DESIGN.md §13).

The fused megakernel's throughput is set by its tile sizes: ``block_e``
(edge tile), ``block_s`` (segment tile) and ``block_r`` (child-row
gather tile).  This module picks them per device:

* **model ranking** — every candidate config is scored with
  :func:`repro.kernels.fused_hop.fused_hop_cost` (the two-term
  flops/bytes roofline) after filtering configs whose per-cell VMEM
  footprint exceeds :data:`repro.kernels.fused_hop.VMEM_BYTES`.  Ranking
  is deterministic, so ``Plan.explain()`` and the plan goldens use it
  directly (:func:`model_tiles_for` — never the disk cache).
* **measurement** — on a real accelerator, the top
  :data:`MEASURE_TOP_N` model candidates are benchmarked on a synthetic
  hop of the (bucketed) shape and the fastest wins.  CPU hosts skip
  measurement: the Pallas interpreter's wall time says nothing about
  device tiles.
* **on-disk cache** — measured winners persist in a JSON file keyed by
  ``<device kind>|fused_hop|<bucketed shape>`` (``REPRO_AUTOTUNE_CACHE``
  overrides the default ``<checkout>/.cache/autotune.json``), so a
  process restart does not re-benchmark.

Hop shapes bucket to powers of two (:func:`hop_shape`) so the cache and
the jit trace count stay bounded as relation sizes drift.
"""
from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass
from pathlib import Path

import jax

from repro.kernels import fused_hop

__all__ = [
    "HopShape",
    "TileConfig",
    "candidate_tiles",
    "device_kind",
    "hop_shape",
    "model_tiles_for",
    "plan_hops",
    "plan_kernel_configs",
    "tiles_for",
]


@dataclass(frozen=True)
class TileConfig:
    """One fused-hop tile configuration (``block_e`` a multiple of
    ``ops.EDGE_GRANULE``, the others of 8)."""

    block_e: int
    block_s: int
    block_r: int

    def key(self) -> str:
        return f"e{self.block_e}.s{self.block_s}.r{self.block_r}"


@dataclass(frozen=True)
class HopShape:
    """Bucketed shape of one fused hop — the autotune cache key."""

    edges: int
    child_rows: tuple[int, ...]
    child_widths: tuple[int, ...]
    num_segments: int
    k: int = 1
    kind: str = "sum"

    @property
    def width(self) -> int:
        w = 1
        for wc in self.child_widths:
            w *= wc
        return w

    def key(self) -> str:
        rows = ",".join(str(r) for r in self.child_rows) or "-"
        widths = ",".join(str(w) for w in self.child_widths) or "-"
        return (
            f"fused_hop|e{self.edges}|r{rows}|w{widths}"
            f"|s{self.num_segments}|k{self.k}|{self.kind}"
        )


#: candidate grid the model ranks: edge tiles are ops.EDGE_GRANULE
#: multiples (1-D edge operands), the others _KSTEP_GRANULE multiples
_BLOCK_E = (1024, 2048)
_BLOCK_S = (64, 128, 256)
_BLOCK_R = (128, 256)

#: how many model-ranked candidates get measured on a real accelerator
MEASURE_TOP_N = 3

#: the table's home unless ``REPRO_AUTOTUNE_CACHE`` says otherwise: the
#: checkout's git-ignored ``.cache``, beside JAX's compile cache
_DEFAULT_CACHE = Path(__file__).resolve().parents[3] / ".cache" / "autotune.json"

_lock = threading.Lock()
_memory_cache: dict[str, TileConfig] = {}
_disk_loaded = False


def _bucket(n: int, floor: int = 8) -> int:  # tile-math
    """Round up to the next power of two (>= floor)."""
    n = max(int(n), floor)
    return 1 << (n - 1).bit_length()


def hop_shape(
    edges: int,
    child_rows: tuple[int, ...],
    width: int = 1,
    k: int = 1,
    kind: str = "sum",
    child_widths: tuple[int, ...] | None = None,
    num_segments: int = 0,
) -> HopShape:
    """Bucket a concrete hop into its autotune shape class."""
    if child_widths is None:
        # callers that only know the total width attribute it to the
        # first child (cost-equivalent for the gather/scatter terms)
        child_widths = (width,) + (1,) * (len(child_rows) - 1)
        child_widths = child_widths[: len(child_rows)]
    return HopShape(
        edges=_bucket(edges, 256),
        child_rows=tuple(_bucket(r, 8) for r in child_rows),
        child_widths=tuple(int(w) for w in child_widths),
        num_segments=_bucket(num_segments, 8) if num_segments else 0,
        k=int(k),
        kind=kind,
    )


def device_kind() -> str:
    """The default device's kind, as JAX reports it (the autotune cache
    key); raises if JAX has no backend rather than inventing a kind."""
    return jax.devices()[0].device_kind


# ----------------------------------------------------------------------
# model ranking
# ----------------------------------------------------------------------


def candidate_tiles(shape: HopShape) -> list[tuple[float, TileConfig]]:  # tile-math
    """VMEM-admissible candidates ranked by modeled seconds (ascending);
    ties break on the config key so the order is fully deterministic."""
    segments = shape.num_segments or 8
    ranked: list[tuple[float, TileConfig]] = []
    for be in _BLOCK_E:
        for bs in _BLOCK_S:
            for br in _BLOCK_R:
                cfg = TileConfig(be, bs, br)
                if _vmem_bytes(shape, cfg) > fused_hop.VMEM_BYTES:
                    continue
                cost = fused_hop.fused_hop_cost(
                    edges=shape.edges,
                    child_rows=shape.child_rows,
                    child_widths=shape.child_widths,
                    num_segments=segments,
                    k=shape.k,
                    block_e=be,
                    block_s=bs,
                    block_r=br,
                )
                ranked.append((cost["seconds"], cfg))
    ranked.sort(key=lambda t: (t[0], t[1].key()))
    return ranked


def _vmem_bytes(shape: HopShape, cfg: TileConfig) -> int:
    return fused_hop.fused_hop_vmem_bytes(
        cfg.block_e,
        cfg.block_s,
        cfg.block_r,
        shape.child_rows,
        shape.child_widths,
        shape.width,
        shape.k,
        shape.kind,
    )


def no_fit_reason(shape: HopShape) -> str:
    """Why no candidate tile fits: the hop shape, the least VMEM any
    candidate needs, and the bound."""
    least = min(
        _vmem_bytes(shape, TileConfig(be, bs, br))
        for be in _BLOCK_E
        for bs in _BLOCK_S
        for br in _BLOCK_R
    )
    return (
        f"no fused-hop tile fits VMEM for hop {shape.key()}: the smallest "
        f"candidate needs {least} B > bound {fused_hop.VMEM_BYTES} B "
        "(whole child messages stay resident in VMEM)"
    )


def model_tiles_for(shape: HopShape) -> TileConfig:
    """Deterministic model-only choice — what ``Plan.explain()`` and the
    verifier see; never touches the measurement cache.  Raises when no
    candidate fits VMEM."""
    ranked = candidate_tiles(shape)
    if not ranked:
        raise ValueError(no_fit_reason(shape))
    return ranked[0][1]


# ----------------------------------------------------------------------
# on-disk cache + measurement
# ----------------------------------------------------------------------


def _cache_path() -> Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return Path(env)
    return _DEFAULT_CACHE


def _load_disk_cache() -> None:
    global _disk_loaded
    if _disk_loaded:
        return
    _disk_loaded = True
    try:
        raw = json.loads(_cache_path().read_text())
    except (OSError, ValueError):
        return
    for key, cfg in raw.items():
        try:
            _memory_cache[key] = TileConfig(
                int(cfg["block_e"]), int(cfg["block_s"]), int(cfg["block_r"])
            )
        except (KeyError, TypeError, ValueError):
            continue


def _store_disk_cache() -> None:
    path = _cache_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            key: {
                "block_e": cfg.block_e,
                "block_s": cfg.block_s,
                "block_r": cfg.block_r,
            }
            for key, cfg in _memory_cache.items()
        }
        tmp = path.with_suffix(".tmp")
        tmp.write_text(json.dumps(payload, indent=1, sort_keys=True))
        tmp.replace(path)
    except OSError:  # cache is best-effort; never fail the query
        pass


def _measure(shape: HopShape, cfg: TileConfig) -> float:
    """Wall-time one synthetic hop of this shape at this config."""
    import time

    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    n = shape.edges
    segments = shape.num_segments or 1024
    keys = jnp.asarray(rng.integers(0, segments, n), jnp.int32)
    w = jnp.asarray(rng.random((n, shape.k)), jnp.float32)
    msgs = tuple(
        jnp.asarray(rng.random((r, wc * shape.k)), jnp.float32)
        for r, wc in zip(shape.child_rows, shape.child_widths)
    )
    idxs = tuple(
        jnp.asarray(rng.integers(0, r, n), jnp.int32) for r in shape.child_rows
    )

    def run():
        out = ops.fused_hop(
            keys, w, msgs, idxs, num_segments=segments, k=shape.k,
            kind=shape.kind, block_e=cfg.block_e, block_s=cfg.block_s,
            block_r=cfg.block_r,
        )
        out.block_until_ready()

    run()  # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def tiles_for(shape: HopShape, device: str | None = None) -> TileConfig:
    """Tile config for one hop: cached measurement on accelerators, the
    deterministic model choice on CPU hosts."""
    device = device or device_kind()
    key = f"{device}|{shape.key()}"
    with _lock:
        _load_disk_cache()
        hit = _memory_cache.get(key)
    if hit is not None:
        return hit
    ranked = candidate_tiles(shape)
    if not ranked:
        raise ValueError(no_fit_reason(shape))
    if jax.default_backend() == "cpu":
        # interpreter wall time is meaningless for device tiles — take
        # the model's pick and keep goldens/CI deterministic
        cfg = ranked[0][1]
    else:
        timed = [
            (_measure(shape, cand), cand)
            for _, cand in ranked[:MEASURE_TOP_N]
        ]
        timed.sort(key=lambda t: (t[0], t[1].key()))
        cfg = timed[0][1]
    with _lock:
        _memory_cache[key] = cfg
        _store_disk_cache()
    return cfg


# ----------------------------------------------------------------------
# plan-level shapes (explain / V-KERN)
# ----------------------------------------------------------------------


def plan_hops(prep) -> list[dict]:
    """Per-hop dimensions of a prepared plan, in tree post-order: the
    relation, its edge count, each child message's rows and width, and
    the hop's output segment count.

    Child message rows/widths come from the attribute domains: a child's
    message rows ravel the attrs it shares with its parent, its width the
    group attrs its subtree carries upward.
    """
    deco = prep.decomposition
    group_of = prep.schema.group_of

    def subtree_gattrs(rel: str) -> list[str]:
        out = []
        g = group_of.get(rel)
        if g:
            out.append(g)
        for c in deco.nodes[rel].children:
            out.extend(a for a in subtree_gattrs(c) if a not in out)
        return out

    def dim(attr: str) -> int:
        return max(prep.dicts[attr].size, 1)

    out: list[dict] = []
    for rel in deco.order:
        node = deco.nodes[rel]
        er = prep.encoded[rel]
        up: tuple[str, ...] = ()
        if node.parent is not None:
            up = tuple(
                sorted(
                    set(er.attrs) & set(prep.encoded[node.parent].attrs)
                )
            )
        own_g = group_of.get(rel)
        key_attrs = up + ((own_g,) if own_g else ())
        knum = 1
        for a in key_attrs:
            knum *= dim(a)
        child_rows, child_widths = [], []
        for child in node.children:
            shared = sorted(
                set(prep.encoded[child].attrs) & set(er.attrs)
            )
            rows = 1
            for a in shared:
                rows *= dim(a)
            width = 1
            for a in subtree_gattrs(child):
                if a not in shared:
                    width *= dim(a)
            child_rows.append(rows)
            child_widths.append(width)
        out.append(
            {
                "rel": rel,
                "edges": er.num_rows,
                "child_rows": tuple(child_rows),
                "child_widths": tuple(child_widths),
                "num_segments": knum,
            }
        )
    return out


def plan_kernel_configs(prep, k: int = 1, kind: str = "sum") -> list[dict]:
    """Per-hop fused-kernel configs for a prepared plan, in tree
    post-order — the deterministic (model-only) view that
    ``Plan.explain()`` renders and ``check_kernels`` verifies."""
    from repro.core.jax_engine import EDGE_BUCKET

    out: list[dict] = []
    for hop in plan_hops(prep):
        edges = max(
            -(-hop["edges"] // EDGE_BUCKET) * EDGE_BUCKET, EDGE_BUCKET
        )
        shape = hop_shape(
            edges=edges,
            child_rows=hop["child_rows"],
            k=k,
            kind=kind,
            child_widths=hop["child_widths"],
            num_segments=hop["num_segments"],
        )
        ranked = candidate_tiles(shape)
        out.append(
            {
                "rel": hop["rel"],
                "shape": shape,
                "num_segments": hop["num_segments"],
                "config": ranked[0][1] if ranked else None,
                "cost_seconds": ranked[0][0] if ranked else float("nan"),
                "acc_dtype": "float32",
                # set when no candidate fits VMEM (config is then None)
                "no_fit": None if ranked else no_fit_reason(shape),
            }
        )
    return out


def reset_cache() -> None:
    """Testing hook: drop the in-memory cache and force a disk reload."""
    global _disk_loaded
    with _lock:
        _memory_cache.clear()
        _disk_loaded = False
