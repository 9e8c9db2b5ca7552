"""What the program records of itself, read for the per-layer metrics:
its ``joinagg.*`` host spans in the traced window (``repro.obs.span``),
and its counters (``repro.obs.counters``).

Every reader gives ``None`` where the program records nothing of the
kind: a program without ``repro.obs``, or a trace without the spans.
"""
from __future__ import annotations

import tracing

#: spans inside a walk that are not the walk's own host work
WALK_INNER = ("joinagg.h2d", "joinagg.launch", "joinagg.wait", "joinagg.d2h")


def _covered(tr, names, lo, hi) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that host events ``names`` cover."""
    return tracing._union([ev for ev in tr.host if ev[0] in names], lo, hi)


def _ms_per_query(ctx, intervals) -> float:
    return sum(e - s for s, e in intervals) * 1e-6 / ctx.completed


def span_ms(ctx, *names: str) -> float | None:
    """Milliseconds per completed query that the host spans ``names``
    cover, clipped to the window."""
    if ctx.trace is None or not ctx.completed:
        return None
    tr = ctx.trace.trace
    spans = _covered(tr, names, *tracing.window(tr))
    return _ms_per_query(ctx, spans) if spans else None


def self_ms(ctx, outer: str, inner=WALK_INNER) -> float | None:
    """Milliseconds per completed query of ``outer`` spans less the time
    of the ``inner`` spans inside them, clipped to the window."""
    if ctx.trace is None or not ctx.completed:
        return None
    tr = ctx.trace.trace
    lo, hi = tracing.window(tr)
    outers = _covered(tr, (outer,), lo, hi)
    if not outers:
        return None
    inners = _covered(tr, inner, lo, hi)
    inside = [
        (max(s, i_s), min(e, i_e))
        for s, e in outers for i_s, i_e in inners
        if min(e, i_e) > max(s, i_s)
    ]
    return _ms_per_query(ctx, outers) - _ms_per_query(ctx, inside)


def counters() -> dict | None:
    """The program's counters, or ``None`` where it keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.counters()


def counter(name: str) -> float | None:
    c = counters()
    return None if c is None or name not in c else c[name]


def per_query(name: str) -> float | None:
    """Counter ``name`` divided by the queries the server executed (the
    warm-up's and the window's, all of them the cell's one query)."""
    c = counters()
    if c is None or name not in c or not c.get("serve.queries"):
        return None
    return c[name] / c["serve.queries"]
