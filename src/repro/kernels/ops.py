"""Kernel-layer policy + re-exports (DESIGN.md §13).

This module is the single home for the cross-kernel decisions that used
to be duplicated (and could disagree) per kernel file:

* **interpret resolution** — ``resolve_interpret`` is the one place
  a kernel's ``interpret`` flag becomes concrete.  ``interpret=False``
  means "compiled by Mosaic" and raises on a CPU backend (which has no
  Mosaic target) instead of quietly running the interpreter;
  ``interpret=True`` is the CPU test path; ``None`` (auto) compiles on
  an accelerator backend.  ``use_ref_kernels`` is the engine-side twin:
  the jnp reference kernels are only ever substituted in auto mode on a
  CPU backend, so one program never mixes ref and Pallas hops and an
  accelerator never runs the references.
* **block normalization** — ``normalize_block`` rounds requested block
  sizes up to the k-step granule (8) so ``k_step = gcd(block, 8)`` can
  never silently degrade to a 1-wide scalar-slice ``fori_loop``;
  ``k_step_for`` raises instead of degrading if handed an
  un-normalized block.  Blocks over 1-D edge arrays round up to
  ``EDGE_GRANULE`` instead (see there).
* **fused-path switch** — ``fused_enabled`` resolves the per-plan
  option against the ``REPRO_FUSED`` environment default.
* **dispatch accounting** — host-side counters
  (``record_dispatch``/``dispatch_counts``) that the engines bump per
  emitted kernel launch; benchmark table 15 uses them as the
  CPU-measurable proxy for the fused path's 3-dispatches→1 reduction.
  They are the ``dispatch.*`` entries of the program's counter registry
  (:mod:`repro.obs`).  Hop kernel calls also leave their shapes
  (``dispatched_hops``), which the chip smoke prints.

The kernel modules import this policy lazily (inside their wrapper
bodies) and this module re-exports the kernels at the bottom, so either
import order works without a cycle.
"""
from __future__ import annotations

import collections
import math
import os
import threading

import jax

from repro import obs

#: granule for the k-slice fori_loop inside segment_reduce /
#: semiring_matmul / fused min-max hops; blocks are rounded up to a
#: multiple of this so ``gcd(block, _KSTEP_GRANULE)`` is always exact
_KSTEP_GRANULE = 8

#: XLA lays a 1-D int32/float32 array out in HBM as ``T(1024)`` tiles;
#: Mosaic refuses a 1-D block whose own tiling (``T(block)`` up to 1024)
#: does not match, so every block over a 1-D edge operand (keys, ids,
#: values) is a multiple of this
EDGE_GRANULE = 1024

_TRUTHY = frozenset({"1", "true", "on", "yes"})


# ----------------------------------------------------------------------
# interpret policy
# ----------------------------------------------------------------------


def resolve_interpret(interpret: bool | None) -> bool:
    """Resolve a kernel ``interpret`` flag to a concrete bool.

    ``None`` (auto) → interpret on a CPU backend, Mosaic-compiled
    elsewhere.  An explicit ``False`` is a request for the compiled
    kernel: on a CPU backend it raises rather than degrading to the
    interpreter, so a run that asked for the chip cannot silently
    measure something else.
    """
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if not interpret and on_cpu:
        raise RuntimeError(
            "interpret=False asks for Mosaic-compiled Pallas kernels, but "
            "the JAX backend is cpu; pass interpret=True to run the "
            "Pallas interpreter"
        )
    return bool(interpret)


def use_ref_kernels(interpret: bool | None) -> bool:
    """True when the engines should run jnp reference kernels instead of
    Pallas: auto mode on a CPU backend only.  An explicit flag pins the
    Pallas path, so a single program can't mix ref and Pallas hops."""
    return interpret is None and jax.default_backend() == "cpu"


# ----------------------------------------------------------------------
# fused-path switch
# ----------------------------------------------------------------------


def fused_enabled(option: bool | None = None) -> bool:
    """Resolve the fused-hop switch: an explicit plan option wins,
    otherwise the ``REPRO_FUSED`` environment variable decides."""
    if option is not None:
        return bool(option)
    return os.environ.get("REPRO_FUSED", "").strip().lower() in _TRUTHY


# ----------------------------------------------------------------------
# block normalization (the gcd→1 silent-degradation fix)
# ----------------------------------------------------------------------


def round_up(value: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is >= ``value``."""
    return -(-value // multiple) * multiple


def normalize_block(name: str, value: int, granule: int = _KSTEP_GRANULE) -> int:
    """Validate and round a block size up to ``granule`` (the k-step
    granule, or :data:`EDGE_GRANULE` for blocks over 1-D edge arrays).

    Tiling is semantics-free (the wrappers pad inputs to the block
    grid), so rounding up never changes results — it only prevents
    ``gcd(block, 8) == 1`` from quietly turning the reduction loop into
    a per-row scalar slice, and 1-D edge blocks from taking a tiling the
    TPU compiler refuses."""
    if not isinstance(value, int) or isinstance(value, bool) or value <= 0:
        raise ValueError(f"{name} must be a positive int, got {value!r}")
    return round_up(value, granule)


def k_step_for(block: int) -> int:
    """k-slice step for a normalized block; raises on an un-normalized
    one instead of silently degrading to a scalar-slice loop."""
    step = math.gcd(block, _KSTEP_GRANULE)
    if step != _KSTEP_GRANULE:
        raise ValueError(
            f"block size {block} is not a multiple of {_KSTEP_GRANULE}; "
            "pass it through normalize_block() first"
        )
    return step


# ----------------------------------------------------------------------
# dispatch accounting (table 15's currency)
# ----------------------------------------------------------------------

_DISPATCH = "dispatch."
_hops_lock = threading.Lock()
#: the newest kernel dispatches' shapes (bounded: a server records forever)
_dispatch_hops: collections.deque[dict] = collections.deque(maxlen=256)


def record_dispatch(stage: str, n: int = 1, hop: dict | None = None) -> None:
    """Count ``n`` kernel dispatches attributed to ``stage`` (one of
    ``spmm``/``gather``/``product``/``scatter``/``fused``).  ``hop``, where
    given, describes one hop kernel call — ``kernel``, ``edges`` (as the
    engine passes them), ``segments``, ``child_rows``, ``width`` (output
    columns) and any non-default blocks — for :func:`dispatched_hops`."""
    obs.count(_DISPATCH + stage, n)
    if hop is not None:
        with _hops_lock:
            _dispatch_hops.append(hop)


def dispatch_counts() -> dict[str, int]:
    return {
        name[len(_DISPATCH):]: n
        for name, n in obs.counters().items()
        if name.startswith(_DISPATCH)
    }


def dispatched_hops() -> list[dict]:
    """Shapes of the hop kernel calls since the last reset, oldest first."""
    with _hops_lock:
        return list(_dispatch_hops)


def reset_dispatch_counts() -> None:
    obs.reset(_DISPATCH)
    with _hops_lock:
        _dispatch_hops.clear()


# re-exports: ops is the stable import surface for all kernels; these
# live at the bottom so the kernel modules can import the policy
# functions above from inside their wrapper bodies without a cycle
from repro.kernels.coo_spmm import (  # noqa: E402
    coo_spmm,
    coo_spmm_pairs,
    pair_grid_len,
    spmm_pair_list,
)
from repro.kernels.fused_hop import fused_hop  # noqa: E402
from repro.kernels.segment_reduce import (  # noqa: E402
    reduce_schedule,
    segment_reduce,
    segment_reduce_scheduled,
)
from repro.kernels.segment_sum import segment_sum  # noqa: E402
from repro.kernels.semiring_matmul import semiring_matmul  # noqa: E402

__all__ = [
    "coo_spmm",
    "coo_spmm_pairs",
    "EDGE_GRANULE",
    "dispatch_counts",
    "dispatched_hops",
    "fused_enabled",
    "fused_hop",
    "k_step_for",
    "normalize_block",
    "pair_grid_len",
    "record_dispatch",
    "reduce_schedule",
    "reset_dispatch_counts",
    "resolve_interpret",
    "round_up",
    "segment_sum",
    "segment_reduce",
    "segment_reduce_scheduled",
    "semiring_matmul",
    "spmm_pair_list",
    "use_ref_kernels",
]
