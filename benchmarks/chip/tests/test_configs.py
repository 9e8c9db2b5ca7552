"""The generators follow their deployment files, and the plain reference
equals the program's ``tensor`` engine."""
from __future__ import annotations

import numpy as np
import pytest

from conftest import load_config

import check
import harness
import reference

TPCH_AGGS = {
    "agg": {"n": ["count"], "qty": ["sum", "lineitem.l_quantity"]},
    "minmax": {"lo": ["min", "lineitem.l_extendedprice"],
               "hi": ["max", "lineitem.l_extendedprice"]},
}


def _tensor_answer(cfg, aggs, tables):
    from repro.relational.relation import Database

    res = harness.build_query(cfg["query"], aggs, engine="tensor").plan(
        Database.from_mapping(tables)
    ).execute()
    return check.answer_arrays(res, aggs)


def _assert_same(got, want):
    wrong, gap = check.compare(got, want)
    assert (wrong, gap) == (0, 0.0)


@pytest.mark.parametrize("mix", sorted(TPCH_AGGS))
@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_tpch_reference_equals_tensor_engine(mix, seed):
    cfg, mod = load_config("tpch_chain", scale_factor=0.001)
    tables = mod.generate(cfg, seed)
    aggs = TPCH_AGGS[mix]
    _assert_same(_tensor_answer(cfg, aggs, tables),
                 reference.answer(cfg["query"], aggs, tables))


def test_compare_counts_every_kind_of_wrong_group():
    keys = np.array([[1, 1], [1, 2], [2, 1]])
    want = (keys, {"n": np.array([3.0, 4.0, 5.0])})
    assert check.compare(want, want) == (0, 0.0)
    # one value off by 2, one group missing, one extra given twice (a
    # duplicate, and two rows the reference lacks)
    got = (np.array([[1, 1], [1, 2], [3, 3], [3, 3]]),
           {"n": np.array([3.0, 6.0, 1.0, 1.0])})
    assert check.compare(got, want) == (5, 2.0)


def test_tpch_cardinalities_follow_the_deployment_file():
    cfg, mod = load_config("tpch_chain")
    t = mod.generate(cfg, 2**31 + 1)
    sf = cfg["scale_factor"]
    rows = {r: len(next(iter(c.values()))) for r, c in t.items()}
    assert rows["supplier"] == 10_000 * sf
    assert rows["customer"] == 150_000 * sf
    assert rows["orders"] == 1_500_000 * sf
    per_order = np.bincount(np.unique(t["lineitem"]["orderkey"], return_inverse=True)[1])
    assert per_order.min() == 1 and per_order.max() == 7
    assert abs(rows["lineitem"] / rows["orders"] - 4.0) < 0.01
    assert set(np.unique(t["customer"]["c_nationkey"])) == set(range(25))
    assert not np.any(t["orders"]["custkey"] % 3 == 0)
    assert np.all(np.isin(t["lineitem"]["orderkey"], t["orders"]["orderkey"]))
    # suppliers are distinct within an order: no (order, supplier) row
    # is pre-aggregated away, so the lineitem hop has the same edges on
    # every seed
    pairs = np.stack([t["lineitem"]["orderkey"], t["lineitem"]["suppkey"]], 1)
    assert len(np.unique(pairs, axis=0)) == rows["lineitem"]
    counts = np.bincount(t["lineitem"]["suppkey"], minlength=rows["supplier"] + 1)[1:]
    assert counts.min() > 0.85 * counts.mean() and counts.max() < 1.15 * counts.mean()
    assert t["lineitem"]["l_quantity"].min() == 1
    assert t["lineitem"]["l_quantity"].max() == 50
    assert t["lineitem"]["l_extendedprice"].max() < 2**24
    # sparse keys: the first 8 of every 32
    assert np.all((t["orders"]["orderkey"] - 1) % 32 < 8)

    # the same sizes from every seed
    t2 = mod.generate(cfg, 3)
    assert {r: len(next(iter(c.values()))) for r, c in t2.items()} == rows
