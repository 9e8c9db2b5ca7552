"""The comparison that decides ``correct``.

Every answer the loop kept from the window is compared with the plain
reference (:mod:`reference`) on the tables that answer should reflect:
the same groups, and for each group every aggregate equal.  The
configurations guarantee exact answers (integers below 2**24), so each
number compared has the limit 0:

* ``failed_requests``: requests in the window that raised;
* ``wrong_groups``: over the kept answers, groups missing, groups extra,
  groups given twice, and groups whose value differs from the reference;
* ``max_abs_err``: the largest gap between an answer's value and the
  reference's, over groups both have.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

import reference

LIMITS = {"failed_requests": 0, "wrong_groups": 0, "max_abs_err": 0.0}


def answer_arrays(res, aggs: dict) -> tuple[np.ndarray, dict[str, np.ndarray]]:
    """Group keys ``(n, groups)`` and one float64 array per aggregate, from
    an answer with ``group_names``, ``column`` (the program's AggResult)."""
    keys = np.stack([np.asarray(res.column(g)) for g in res.group_names], 1)
    return keys, {a: np.asarray(res.column(a), np.float64) for a in aggs}


def _composite(*key_sets: np.ndarray) -> list[np.ndarray]:
    """One int64 per key row, consistent across ``key_sets``."""
    both = np.concatenate(key_sets)
    if both.size and both.dtype.kind in "iu" and both.min() >= 0:
        radix = both.max(0).astype(np.int64) + 1
        if np.prod(radix.astype(float)) < 2**62:
            weights = np.concatenate([np.cumprod(radix[::-1])[::-1][1:], [1]])
            codes = both.astype(np.int64) @ weights
            return np.split(codes, np.cumsum([len(k) for k in key_sets])[:-1])
    _, codes = np.unique(both, axis=0, return_inverse=True)
    return np.split(codes.reshape(-1), np.cumsum([len(k) for k in key_sets])[:-1])


def compare(got, want) -> tuple[int, float]:
    """``(wrong groups, largest gap)`` of one answer against the reference;
    both are ``(keys, {aggregate: values})``."""
    (gk, gv), (wk, wv) = got, want
    gc, wc = _composite(gk, wk)
    order = np.argsort(gc, kind="stable")
    gc = gc[order]
    dup = int(np.count_nonzero(gc[1:] == gc[:-1]))
    pos = np.clip(np.searchsorted(wc, gc), 0, max(len(wc) - 1, 0))
    hit = (wc[pos] == gc) if len(wc) else np.zeros(len(gc), bool)
    extra = int(np.count_nonzero(~hit))
    missing = len(wc) - len(np.unique(pos[hit]))
    bad = np.zeros(int(hit.sum()), bool)
    gap = 0.0
    for a, want_vals in wv.items():
        d = np.abs(gv[a][order][hit] - want_vals[pos[hit]])
        bad |= d != 0
        gap = max(gap, float(d.max(initial=0.0)))
    return dup + extra + missing + int(np.count_nonzero(bad)), gap


def check(spec, loop) -> SimpleNamespace:
    aggs = spec.mix["aggs"]
    refs: dict[int, tuple] = {}
    wrong = wrong_answers = 0
    gap = 0.0
    answers = loop.answers()
    for tables, res in answers:
        if res is None:
            continue
        if id(tables) not in refs:
            refs[id(tables)] = reference.answer(
                spec.cfg["query"], aggs, tables, spec.bench_dir
            )
        w, g = compare(answer_arrays(res, aggs), refs[id(tables)])
        wrong += w
        wrong_answers += w > 0
        gap = max(gap, g)
    checked = sum(res is not None for _, res in answers)
    values = {
        "failed_requests": len(loop.errors),
        "wrong_groups": wrong,
        "max_abs_err": gap,
    }
    checks = {n: {"value": v, "limit": LIMITS[n]} for n, v in values.items()}
    correct = checked > 0 and all(v <= LIMITS[n] for n, v in values.items())
    return SimpleNamespace(
        correct=correct,
        attempted=len(loop.latencies),
        failed=len(loop.errors) + wrong_answers,
        completed=len(loop.latencies) - len(loop.errors),
        checks=checks,
        end_to_end=loop.end_to_end(),
        errors=loop.errors,
    )
