"""``device_product_share``: the share of the hops' per-edge products
that the program formed on the device, read from its ``hop.*``
counters."""
from __future__ import annotations

import pytest

from test_program_spans import COUNTERS, ctx_of, fake_program, program_trace, reader

HOP = {"hop.product_edges_device": 363516, "hop.product_edges_host": 1000}


def test_reads_the_hop_counters(monkeypatch):
    fake_program(monkeypatch, {**COUNTERS, **HOP})
    got = reader("device_product_share").read(ctx_of(program_trace()))
    assert got == pytest.approx(100 * 363516 / 364516)


@pytest.mark.parametrize(
    "host", [{}, {"hop.product_edges_host": 0}], ids=["no-host-counter", "host-zero"]
)
def test_every_product_on_the_device_reads_100(monkeypatch, host):
    fake_program(monkeypatch, {**COUNTERS, "hop.product_edges_device": 5, **host})
    assert reader("device_product_share").read(ctx_of(program_trace())) == 100


def test_every_product_on_the_host_reads_0(monkeypatch):
    fake_program(monkeypatch, {**COUNTERS, "hop.product_edges_host": 7})
    assert reader("device_product_share").read(ctx_of(program_trace())) == 0


@pytest.mark.parametrize(
    "counters", [None, COUNTERS], ids=["no-registry", "no-hop-counters"]
)
def test_none_where_the_program_counts_no_product(monkeypatch, counters):
    """A program without ``repro.obs``, or one that counts no hop products
    (such as the parent), gives no reading."""
    fake_program(monkeypatch, counters)
    assert reader("device_product_share").read(ctx_of(program_trace())) is None


def test_reads_a_real_hop(monkeypatch):
    """The counters of one general hop on the device, through the
    program's own registry."""
    import numpy as np

    from repro import obs
    from repro.core.jax_engine import _KernelChannelEngine
    from repro.core.prepare import prepare
    from repro.core.query import JoinAggQuery
    from repro.relational.relation import Database

    db = Database.from_mapping({"R": {"a": np.arange(4), "g": np.arange(4)}})
    prep = prepare(JoinAggQuery(("R",), (("R", "g"),)), db)
    eng = _KernelChannelEngine(prep, 2, {}, interpret=True)
    obs.reset("hop.")
    eng._contract_block(np.ones((1500, 2)), [], np.zeros(1500, np.int64), 3)
    assert reader("device_product_share").read(ctx_of(program_trace())) == 100
    assert obs.counters()["hop.product_edges_device"] == 1500
