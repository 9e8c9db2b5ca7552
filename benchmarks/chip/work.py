"""The work a query's hops need, from the cell's data and query alone.

The join tree is the query's relations linked where they share columns.
Rooted at a relation, each relation ``v`` is one hop that reads its
pre-aggregated rows (the distinct rows over the columns the query uses),
the messages of its children, and writes one message to its parent:

* segments(v): distinct values of the columns ``v`` shares with its
  parent (1 at the root);
* width_in(v): the product of its children's widths;
  width(v) = width_in(v) × distinct values of ``v``'s own group columns;
* bytes = edges × (key + index + weight, 4 B each)
  + Σ children segments × width × channels × 4
  + segments(v) × width(v) × channels × 4;
* FLOPs = 2 × edges × width_in(v) × channels.

``channels`` is the number of aggregates.  A hop's least time is the
larger of FLOPs over the peak FLOP/s and bytes over the peak bytes/s.
The query needs the root that gives the least total; nothing here looks
at what the program dispatches, so a kernel that does less redundant
work raises its share against a fixed yardstick.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import reference

EDGE_BYTES = 12  # int32 key, int32 index, f32 weight
VALUE_BYTES = 4  # f32


def peaks(device_kind: str, bench_dir: Path) -> dict:
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def _distinct(cols: list[np.ndarray]) -> int:
    if not cols:
        return 1
    return len(np.unique(np.stack(cols, 1), axis=0))


def hops(query: dict, aggs: dict, tables: dict, root: str) -> list[dict]:
    rels = dict(reference.query_relations(query, tables))
    group = {}
    for g in query["group_by"]:
        rel, col = g.split(".", 1)
        group.setdefault(rel, []).append(col)
    channels = len(aggs)
    out: list[dict] = []

    def visit(v: str, parent: str | None, seen: set) -> tuple[int, int]:
        cols = rels[v]
        key = sorted(set(cols) & set(rels[parent])) if parent else []
        kids = [c for c in rels if c not in seen and c != v and set(rels[c]) & set(cols)]
        seen |= set(kids) | {v}
        child = [(c, *visit(c, v, seen)) for c in kids]
        used = set(key) | set(group.get(v, [])) | {
            a for c, _, _ in child for a in set(rels[c]) & set(cols)
        }
        edges = _distinct([cols[a] for a in sorted(used)])
        width_in = int(np.prod([w for _, _, w in child], dtype=np.int64)) if child else 1
        width = width_in * _distinct([cols[a] for a in group.get(v, [])])
        segs = _distinct([cols[a] for a in key])
        out.append({
            "relation": v,
            "edges": edges,
            "segments": segs,
            "width": width,
            "bytes": edges * EDGE_BYTES
            + sum(s * w for _, s, w in child) * channels * VALUE_BYTES
            + segs * width * channels * VALUE_BYTES,
            "flops": 2 * edges * width_in * channels,
        })
        return segs, width

    visit(root, None, {root})
    return out


def least_time(query: dict, aggs: dict, tables: dict, peak: dict) -> dict:
    """The least time one query's hops need on a chip with ``peak``, over
    every choice of root, and which of FLOPs and bytes bounds it."""
    best = None
    for entry in query["over"]:
        root = entry if isinstance(entry, str) else entry[0]
        hs = hops(query, aggs, tables, root)
        t_flop = sum(h["flops"] for h in hs) / peak["flops_per_s"]
        t_byte = sum(h["bytes"] for h in hs) / peak["hbm_bytes_per_s"]
        t = sum(
            max(h["flops"] / peak["flops_per_s"], h["bytes"] / peak["hbm_bytes_per_s"])
            for h in hs
        )
        if best is None or t < best["seconds"]:
            best = {
                "seconds": t,
                "root": root,
                "bound": "flops" if t_flop > t_byte else "bytes",
                "hops": hs,
            }
    return best
