"""``correct`` comes out false when the timed path is broken underneath,
and when the bfloat16 reference (the control) takes the program's place.
Each run is a whole run of a tiny cell with the look for a chip skipped.
"""
from __future__ import annotations

from dataclasses import replace

import numpy as np
import pytest

from conftest import run_cell

import control
import harness

QUERY_CELLS = ["tiny_tpch.agg", "tiny_tpch.minmax"]


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    from conftest import make_checkout

    return make_checkout(tmp_path_factory.mktemp("faults") / "checkout")


@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_sound_program_is_correct(sound, cell):
    rc, line, err = run_cell(sound, cell)
    assert rc == 0 and line["correct"] is True, err
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_control_in_bfloat16_is_not_correct(sound, cell, monkeypatch):
    from repro.serve.session import Session

    monkeypatch.setattr(Session, "query", Session.query)  # restored afterwards
    control.install(harness.load_cell(cell, sound))
    rc, line, err = run_cell(sound, cell)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_groups"]["value"] > 0
    assert line["checks"]["max_abs_err"]["value"] > 0


def _altered(res, how):
    from repro.relational.relation import Relation

    cols = {k: np.array(v, copy=True) for k, v in res.relation.columns.items()}
    if how == "value":
        cols[res.agg_names[0]][len(cols[res.agg_names[0]]) // 2] += 1
    else:  # half of the groups left out
        cols = {k: v[: len(v) // 2] for k, v in cols.items()}
    return replace(res, relation=Relation(res.relation.name, cols))


@pytest.mark.parametrize("how", ["value", "half"])
@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_answer_altered_where_it_is_produced(sound, cell, how, monkeypatch):
    from repro.api.plan import Plan

    execute = Plan.execute
    monkeypatch.setattr(Plan, "execute", lambda self, *a, **k: _altered(execute(self, *a, **k), how))
    rc, line, err = run_cell(sound, cell)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["wrong_groups"]["value"] > 0


@pytest.mark.parametrize("cell", QUERY_CELLS)
def test_answer_that_never_comes(sound, cell, monkeypatch):
    from repro.api.plan import Plan

    calls = {"n": 0}
    execute = Plan.execute

    def flaky(self, *a, **k):  # warm-up passes; the window's third query raises
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("lost answer")
        return execute(self, *a, **k)

    monkeypatch.setattr(Plan, "execute", flaky)
    rc, line, err = run_cell(sound, cell)
    assert rc == 0 and line["correct"] is False
    assert line["checks"]["failed_requests"]["value"] == 1
