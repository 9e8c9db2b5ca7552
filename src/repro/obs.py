"""Spans and counters of the query path (DESIGN.md §14).

* :func:`span` — a host span around one piece of the query path.  It is
  a ``jax.profiler.TraceAnnotation``, so in a profiler trace it shares
  the clock of the device's ops; when no profiler runs it costs about
  a microsecond.  Every name starts with ``joinagg.``; relation names,
  kernels and sizes travel as keyword args (the trace event's stats),
  never in the name.
* :func:`count` / :func:`counters` / :func:`reset` — the process-wide
  counter registry, cumulative until reset.  Names are dotted by layer:
  ``serve.*`` (queries, queue wait), ``plan.*`` (plan-cache builds),
  ``transfer.*`` (host↔device bytes), ``kernel.*`` (grid steps a
  kernel ran against its full grid), ``dispatch.*`` (the kernel
  dispatch accounting that :mod:`repro.kernels.ops` views).

There is no switch: spans are always on, counters always count.
"""
from __future__ import annotations

import threading

import jax

_lock = threading.Lock()
_counters: dict[str, float] = {}  # guarded-by: _lock


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """Context manager for a host span ``name`` with ``args`` as stats."""
    return jax.profiler.TraceAnnotation(name, **args)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict[str, float]:
    """A copy of every counter."""
    with _lock:
        return dict(_counters)


def reset(prefix: str = "") -> None:
    """Zero the counters whose names start with ``prefix`` (all of them
    by default)."""
    with _lock:
        for name in [n for n in _counters if n.startswith(prefix)]:
            del _counters[name]
