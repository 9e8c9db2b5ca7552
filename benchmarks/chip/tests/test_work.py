"""The work a query's hops need, counted by hand on a two-table join."""
from __future__ import annotations

import numpy as np
import pytest

from conftest import BENCH

import work

QUERY = {"over": ["R", "S"], "group_by": ["R.a", "S.b"]}
TABLES = {
    "R": {"a": np.array([0, 0, 1, 1]), "k": np.array([0, 1, 1, 1])},  # (1, 1) twice
    "S": {"k": np.array([0, 1, 1]), "b": np.array([0, 1, 2])},
}
COUNT = {"n": ["count"]}


def test_hops_rooted_at_each_end():
    by_root = {r: {h["relation"]: h for h in work.hops(QUERY, COUNT, TABLES, r)}
               for r in ("R", "S")}
    # rooted at R: S sends 2 segments (k) x 3 columns (b); R reads its 3
    # distinct rows and writes 1 x (3 x 2)
    s, r = by_root["R"]["S"], by_root["R"]["R"]
    assert (s["edges"], s["segments"], s["width"]) == (3, 2, 3)
    assert (s["bytes"], s["flops"]) == (3 * 12 + 2 * 3 * 4, 2 * 3)
    assert (r["edges"], r["segments"], r["width"]) == (3, 1, 6)
    assert (r["bytes"], r["flops"]) == (3 * 12 + 2 * 3 * 4 + 6 * 4, 2 * 3 * 3)
    # rooted at S: R sends 2 segments x 2 columns (a)
    r, s = by_root["S"]["R"], by_root["S"]["S"]
    assert (r["bytes"], r["flops"]) == (3 * 12 + 2 * 2 * 4, 2 * 3)
    assert (s["bytes"], s["flops"]) == (3 * 12 + 2 * 2 * 4 + 6 * 4, 2 * 3 * 2)


def test_least_time_takes_the_cheaper_root_and_names_the_bound():
    peak = {"flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
    best = work.least_time(QUERY, COUNT, TABLES, peak)
    assert (best["root"], best["seconds"], best["bound"]) == ("S", 52 + 76, "bytes")
    # two channels double the messages and the FLOPs, not the edges
    two = work.least_time(QUERY, {"n": ["count"], "s": ["sum", "R.a"]}, TABLES, peak)
    assert two["seconds"] == 2 * (52 + 76) - 2 * 3 * 12


def test_peaks_are_keyed_by_device_kind():
    v5e = work.peaks("TPU v5 lite", BENCH)
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary", BENCH)
