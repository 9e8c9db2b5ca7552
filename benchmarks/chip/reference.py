"""The plain reference: a natural hash join, then a group-by, in numpy.

It follows the query as the deployment file and the traffic mix state it
(relations with their aliases and column renames, group attributes,
aggregates, each folded by its ``aggregates/<kind>.py``) and imports
nothing of the program under test.  Integer columns are joined and
aggregated in int64 and float64, so every answer the configurations
guarantee (integers below 2**24) is exact here.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent


def query_relations(query: dict, tables: dict) -> list[tuple[str, dict]]:
    """``(alias, columns)`` for each relation of ``query["over"]``, with
    ``query["renames"]`` applied."""
    out = []
    for entry in query["over"]:
        alias, source = (entry, entry) if isinstance(entry, str) else entry
        ren = query.get("renames", {}).get(alias, {})
        out.append((alias, {ren.get(a, a): c for a, c in tables[source].items()}))
    return out


def _hash_join(left: dict, right: dict) -> dict:
    shared = sorted(set(left) & set(right))
    if not shared:
        raise ValueError("the reference joins only relations that share columns")
    if len(shared) == 1:
        lk, rk = left[shared[0]], right[shared[0]]
    else:
        both = np.concatenate(
            [np.stack([left[a] for a in shared], 1), np.stack([right[a] for a in shared], 1)]
        )
        _, ids = np.unique(both, axis=0, return_inverse=True)
        ids = ids.reshape(-1)
        lk, rk = ids[: len(left[shared[0]])], ids[len(left[shared[0]]):]
    order = np.argsort(rk, kind="stable")
    rs = rk[order]
    lo = np.searchsorted(rs, lk, "left")
    cnt = np.searchsorted(rs, lk, "right") - lo
    li = np.repeat(np.arange(len(lk)), cnt)
    start = np.repeat(np.cumsum(cnt) - cnt, cnt)
    ri = order[np.repeat(lo, cnt) + np.arange(len(li)) - start]
    out = {a: c[li] for a, c in left.items()}
    out.update({a: c[ri] for a, c in right.items() if a not in shared})
    return out


def join(rels: list[tuple[str, dict]]) -> dict:
    """Natural join of every relation, each next one taken among those
    that share a column with what is joined so far.  Natural-join column
    names are unique, so the result is keyed by column name."""
    pending = list(rels)
    acc = dict(pending.pop(0)[1])
    while pending:
        i = next(
            (i for i, (_, c) in enumerate(pending) if set(c) & set(acc)), None
        )
        if i is None:
            raise ValueError("the reference needs a connected join")
        acc = _hash_join(acc, pending.pop(i)[1])
    return acc


def _factorize(cols: list[np.ndarray]) -> tuple[np.ndarray, np.ndarray]:
    """Group codes in key order and the distinct key rows."""
    code = np.zeros(len(cols[0]), np.int64)
    uniq = []
    for c in cols:
        u, inv = np.unique(c, return_inverse=True)
        uniq.append(u)
        code = code * len(u) + inv.reshape(-1)
    keys, inv = np.unique(code, return_inverse=True)
    rows = []
    for u in reversed(uniq):
        rows.append(u[keys % len(u)])
        keys = keys // len(u)
    return inv.reshape(-1), np.stack(rows[::-1], 1)


def _column(spec: str) -> str:
    """``"relation.column"`` -> ``"column"``."""
    return spec.split(".", 1)[1]


def aggregate(kind: str, bench_dir: Path = BENCH_DIR):
    """The aggregate kind ``aggregates/<kind>.py``, found by name; its
    ``reference(group, n, values)`` folds a column into one value per
    group (``values`` is None for an aggregate of no column)."""
    path = Path(bench_dir) / "aggregates" / f"{kind}.py"
    if not path.is_file():
        raise KeyError(f"no aggregate kind {kind!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"aggregate_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def group_by(query: dict, joined: dict, aggs: dict,
             bench_dir: Path = BENCH_DIR) -> tuple[np.ndarray, dict]:
    """``(keys, values)``: the distinct group keys of the join in sorted
    order, as an ``(n, len(group_by))`` array, and one float64 array per
    aggregate.  ``aggs`` maps a name to ``[kind]`` or ``[kind, "rel.col"]``."""
    inv, keys = _factorize([joined[_column(g)] for g in query["group_by"]])
    out = {}
    for name, (kind, *column) in aggs.items():
        values = joined[_column(column[0])].astype(np.float64) if column else None
        out[name] = aggregate(kind, bench_dir).reference(inv, len(keys), values)
    return keys, out


def answer(query: dict, aggs: dict, tables: dict,
           bench_dir: Path = BENCH_DIR) -> tuple[np.ndarray, dict]:
    """The query's answer on ``tables``: see :func:`group_by`."""
    return group_by(query, join(query_relations(query, tables)), aggs, bench_dir)
