"""The readers of what the program records of itself: its ``joinagg.*``
host spans in the trace and its counters (``repro.obs``)."""
from __future__ import annotations

import json
import sys
import types
from types import SimpleNamespace

import pytest

from conftest import BENCH, run_cell
from test_tracing import synthetic

import harness
import tracing
from tracing import Trace

SPAN_READERS = ("query_plan_ms", "walk_host_ms", "transfer_ms", "device_wait_ms", "assemble_ms")
COUNTER_READERS = ("queue_wait_ms", "plan_build_s", "h2d_mb", "d2h_mb")
MS = 1e6  # ns


def reader(name: str):
    return harness.import_file(BENCH / "metrics" / f"{name}.py", f"metric_{name}")


def program_trace() -> Trace:
    """A 100 ms window, two queries: planning, two walks with their
    transfers, launches and waits, a third walk cut by the window's end,
    assembly, and an upload outside any walk (times in ms)."""
    host = [
        ("joinagg.plan", -5, 5), ("joinagg.plan", 50, 52),
        ("joinagg.walk", 5, 40), ("joinagg.walk", 52, 90), ("joinagg.walk", 98, 110),
        ("joinagg.hop", 5.5, 35),
        ("joinagg.h2d", 6, 8), ("joinagg.launch", 7.5, 9),  # overlapping
        ("joinagg.wait", 9, 30), ("joinagg.d2h", 30, 32),
        ("joinagg.h2d", 53, 54), ("joinagg.wait", 60, 80), ("joinagg.d2h", 80, 81),
        ("joinagg.assemble", 40, 45), ("joinagg.assemble", 90, 95),
        ("joinagg.h2d", 95, 96),  # outside every walk
        ("Transpose", 10, 20),
    ]
    return Trace(
        ops={"/device:TPU:0": [("%coo_spmm.1 = f32[8] custom-call()", 10 * MS, 29 * MS)]},
        host=[(n, s * MS, e * MS) for n, s, e in host],
        spans=[("window", 0, 100 * MS), ("query", 0, 50 * MS), ("query", 50 * MS, 100 * MS)],
    )


COUNTERS = {
    "serve.queries": 4, "serve.queue_wait_s": 0.01, "plan.builds": 1,
    "plan.build_s": 0.25, "transfer.h2d_bytes": 8e6, "transfer.d2h_bytes": 2e6,
    "dispatch.spmm": 12,
}


def fake_program(monkeypatch, counters: dict | None) -> None:
    """``repro.obs`` as a registry holding ``counters``, or absent
    (``None``), as in a program that keeps no counters."""
    import repro

    if counters is None:
        monkeypatch.delattr(repro, "obs", raising=False)
        monkeypatch.setitem(sys.modules, "repro.obs", None)
        return
    fake = types.ModuleType("repro.obs")
    fake.counters = lambda: dict(counters)
    monkeypatch.setattr(repro, "obs", fake, raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", fake)


def ctx_of(tr: Trace, completed: int = 2) -> SimpleNamespace:
    return SimpleNamespace(trace=SimpleNamespace(trace=tr), completed=completed)


def test_span_readers_on_a_synthetic_trace():
    ctx = ctx_of(program_trace())
    got = {name: reader(name).read(ctx) for name in SPAN_READERS}
    assert got == pytest.approx({
        "query_plan_ms": (5 + 2) / 2,  # the first plan span starts before the window
        # walks 35 + 38 + 2 (cut at 100) less the union of their inner
        # spans, (6, 9) + 21 + 2 and 1 + 20 + 1; the upload at 95 is outside
        "walk_host_ms": ((35 - 26) + (38 - 22) + 2) / 2,
        "transfer_ms": (2 + 1 + 1 + 2 + 1) / 2,
        "device_wait_ms": (21 + 20) / 2,
        "assemble_ms": (5 + 5) / 2,
    })


def test_counter_readers_on_a_fake_registry(monkeypatch):
    fake_program(monkeypatch, COUNTERS)
    ctx = ctx_of(program_trace())
    got = {name: reader(name).read(ctx) for name in COUNTER_READERS}
    assert got == pytest.approx({
        "queue_wait_ms": 1e3 * 0.01 / 4,
        "plan_build_s": 0.25,
        "h2d_mb": 8e6 / 4 / 1e6,
        "d2h_mb": 2e6 / 4 / 1e6,
    })


def test_readers_give_none_without_the_program_records(monkeypatch):
    fake_program(monkeypatch, None)
    ctx = ctx_of(synthetic())  # the harness's own spans, runtime events only
    for name in SPAN_READERS + COUNTER_READERS:
        assert reader(name).read(ctx) is None, name


def test_a_traced_run_reports_every_program_metric(checkout, monkeypatch):
    """A whole traced run of a tiny cell on the CPU: the span readers read
    the synthetic chip trace handed in, the counter readers the run's own
    counters.  (The roofline's reader needs a chip's peaks, so the run
    reads these nine alone.)"""
    bench_file = checkout / "BENCHMARK.json"
    bench = json.loads(bench_file.read_text())
    bench["per_layer"] = [
        m for m in bench["per_layer"] if m["name"] in SPAN_READERS + COUNTER_READERS
    ]
    bench_file.write_text(json.dumps(bench))
    monkeypatch.setattr(tracing, "load", lambda _dir, **_: program_trace())
    rc, line, err = run_cell(checkout, "tiny_tpch.agg", seconds=0.3, trace=True)
    assert rc == 0, err
    metrics = line["metrics"]
    assert set(metrics) == set(SPAN_READERS + COUNTER_READERS)
    assert metrics["device_wait_ms"]["value"] == pytest.approx(41 / line["attempted"])
    assert metrics["h2d_mb"]["value"] > 0 and metrics["d2h_mb"]["value"] > 0
    assert metrics["plan_build_s"]["value"] > 0
    assert metrics["queue_wait_ms"]["value"] >= 0
    assert metrics["h2d_mb"]["unit"] == "MB"
