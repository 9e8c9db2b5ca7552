"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) whose
line ``XLA Ops`` holds every operation the chip ran and whose line
``XLA Modules`` holds every program execution, and a host plane
(``/host:CPU``) with the runtime's events and the harness's own spans
(``window`` and one span per request, named by the traffic kind), all
on one clock.  From them:

* busy time: the union of the op intervals inside the harness's
  ``window`` span, averaged over the chips; the window's length;
* device time of named ops or programs (the metric readers name them);
* idle gaps: the stretches of the window in which no op ran, cut and
  named by the harness span and the innermost host event over each piece
  (an upload, a download, a layout transpose, a program launch), or
  ``host`` where the runtime recorded nothing (Python work).
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field

HARNESS_SPANS = ("window", "query")
TOP = 10
# host events shorter than this (the runtime's per-chunk events) name no gap
MIN_HOST_NS = 10_000


def profile_options():
    """Runtime events on the host, no Python tracer: it would slow the
    host path that the idle share measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


@dataclass
class Trace:
    """Events as ``(name, start_ns, end_ns)``."""

    ops: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    modules: dict[str, list[tuple[str, float, float]]] = field(default_factory=dict)
    host: list[tuple[str, float, float]] = field(default_factory=list)
    spans: list[tuple[str, float, float]] = field(default_factory=list)


def load(trace_dir, spans=HARNESS_SPANS) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    return from_profile(ProfileData.from_file(paths[0]), spans)


def from_profile(pd, spans=HARNESS_SPANS) -> Trace:
    """The trace's device ops and programs, host events, and the harness's
    own spans: those named in ``spans``."""
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                target = {"XLA Ops": tr.ops, "XLA Modules": tr.modules}.get(line.name)
                if target is not None:
                    target[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name in spans:
                        tr.spans.append(ev)
                    elif not e.name.startswith("$") and e.duration_ns >= MIN_HOST_NS:
                        tr.host.append(ev)
    return tr


def _union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(tr: Trace) -> tuple[float, float]:
    w = [(s, e) for n, s, e in tr.spans if n == "window"]
    if len(w) != 1:
        raise ValueError(f"expected one 'window' span in the trace, found {len(w)}")
    return w[0]


def op_seconds(tr: Trace, match, modules: bool = False) -> float:
    """Device seconds, summed over chips, of the ops (or programs) whose
    name ``match`` accepts, inside the window."""
    lo, hi = window(tr)
    total = 0.0
    for events in (tr.modules if modules else tr.ops).values():
        for name, s, e in events:
            if match(name):
                total += max(0.0, min(e, hi) - max(s, lo))
    return total * 1e-9


def op_label(name: str) -> str:
    """``%coo_spmm.1 = f32[...] custom-call(...)`` -> ``coo_spmm``;
    ``jit_fn(123)`` -> ``jit_fn``."""
    m = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|\(|$)", name)
    return m.group(1) if m else name


def _name_gaps(gaps, host, spans) -> dict[str, float]:
    """Seconds of idle time by ``<harness span>/<host event>``: each gap is
    cut where a harness span or a host event starts or ends, and each piece
    is named by the innermost of each that covers it (``between`` outside
    any request, ``host`` where the runtime recorded nothing)."""
    out: dict[str, float] = defaultdict(float)
    host = sorted(host, key=lambda x: x[1])
    spans = sorted(spans, key=lambda x: x[1])
    open_h: list = []
    open_s: list = []
    ih = js = 0
    for gs, ge in gaps:
        while ih < len(host) and host[ih][1] < ge:
            open_h.append(host[ih])
            ih += 1
        while js < len(spans) and spans[js][1] < ge:
            open_s.append(spans[js])
            js += 1
        open_h = [ev for ev in open_h if ev[2] > gs]
        open_s = [ev for ev in open_s if ev[2] > gs]
        cuts = {gs, ge}
        for _, s, e in open_h + open_s:
            cuts.update(t for t in (s, e) if gs < t < ge)
        pts = sorted(cuts)
        for a, b in zip(pts, pts[1:]):
            mid = (a + b) / 2
            name = []
            for events, none in ((open_s, "between"), (open_h, "host")):
                cover = [(e - s, n) for n, s, e in events if s <= mid < e]
                name.append(min(cover)[1] if cover else none)
            out["/".join(name)] += (b - a) * 1e-9
    return out


@dataclass
class Summary:
    busy_s: float
    window_s: float
    trace: Trace
    breakdown: dict


def summarize(tr: Trace) -> Summary:
    lo, hi = window(tr)
    chips = sorted(tr.ops)
    if not chips:
        raise ValueError("the trace holds no device ops")
    busy = [_union(tr.ops[c], lo, hi) for c in chips]
    busy_s = sum(e - s for b in busy for s, e in b) * 1e-9 / len(chips)

    per_op: dict[str, float] = defaultdict(float)
    for c in chips:
        for name, s, e in tr.ops[c]:
            per_op[op_label(name)] += max(0.0, min(e, hi) - max(s, lo)) * 1e-9

    b = busy[0]
    edges = [lo] + [x for iv in b for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    requests = [ev for ev in tr.spans if ev[0] != "window"]
    names = _name_gaps(gaps, tr.host, requests)
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]  # noqa: E731
    return Summary(
        busy_s=busy_s,
        window_s=(hi - lo) * 1e-9,
        trace=tr,
        breakdown={"device_ops": top(per_op), "idle_gaps": top(names)},
    )
