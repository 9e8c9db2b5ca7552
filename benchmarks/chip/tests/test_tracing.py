"""The trace reduction: busy and idle time, device time of named ops,
idle gaps named by what the host was doing."""
from __future__ import annotations

import glob
from pathlib import Path

import pytest

import tracing
from tracing import Trace

RECORDED = Path(__file__).parent / "data" / "tpch_small_trace"


def synthetic() -> Trace:
    """A 100 ns window on one chip with ops busy over [10, 40] and
    [60, 70], two queries, and two host events."""
    return Trace(
        ops={"/device:TPU:0": [
            ("%coo_spmm.1 = f32[8,2] custom-call(s32[8] %a)", 10, 30),
            ("%fusion.3 = f32[8] fusion(f32[8] %b)", 25, 40),
            ("%segment_sum.1 = f32[4,2] custom-call(s32[8] %c)", 60, 70),
            ("%copy.2 = f32[4] copy(f32[4] %d)", 95, 120),  # runs past the window
        ]},
        modules={"/device:TPU:0": [("jit_coo_spmm(77)", 9, 31), ("jit_fn(5)", 24, 41)]},
        host=[("TransferToDevice", 0, 9), ("Transpose", 41, 59)],  # ns
        spans=[("window", 0, 100), ("query", 5, 50), ("query", 55, 98)],
    )


def test_busy_idle_and_op_time_on_a_synthetic_trace():
    tr = synthetic()
    s = tracing.summarize(tr)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(45e-9)  # [10,40] + [60,70] + [95,100]
    assert tracing.op_seconds(tr, lambda n: "custom-call" in n) == pytest.approx(30e-9)
    assert tracing.op_seconds(
        tr, lambda n: tracing.op_label(n) == "jit_fn", modules=True
    ) == pytest.approx(17e-9)
    ops = dict(s.breakdown["device_ops"])
    assert ops == pytest.approx(
        {"coo_spmm": 20e-9, "fusion": 15e-9, "segment_sum": 10e-9, "copy": 5e-9}
    )
    gaps = dict(s.breakdown["idle_gaps"])
    assert gaps == pytest.approx({
        "between/TransferToDevice": 5e-9,  # [0, 5]: before the first query
        "query/TransferToDevice": 4e-9,  # [5, 9]
        "query/host": 28e-9,  # [9, 10], [40, 41], [59, 60], [70, 95]
        "query/Transpose": 13e-9,  # [41, 50], [55, 59]
        "between/Transpose": 5e-9,  # [50, 55]: between the two queries
    })
    assert sum(gaps.values()) + s.busy_s == pytest.approx(s.window_s)


def test_op_labels():
    assert tracing.op_label("%coo_spmm.1 = f32[2] custom-call(s32[2] %x)") == "coo_spmm"
    assert tracing.op_label("%segment_reduce = f32[2] custom-call()") == "segment_reduce"
    assert tracing.op_label("jit_fn(6962719590009897288)") == "jit_fn"
    assert tracing.op_label("%copy-start.2 = (s32[1024]) copy-start()") == "copy-start"


def test_recorded_chip_trace():
    """Three COUNT + SUM queries of the TPC-H chain at SF 0.002 on the
    sparse path (streamed in tiles), traced on one TPU v5e: the hop
    kernels are custom calls on the chip's plane, busy plus idle make up
    the window, and the reduction's numbers are pinned."""
    from conftest import BENCH

    import harness

    hop = harness.import_file(BENCH / "metrics" / "hop_kernel_ms.py", "metric_hop_kernel_ms")
    tr = tracing.load(RECORDED)
    s = tracing.summarize(tr)
    assert [n for n, _, _ in tr.spans].count("query") == 3
    assert s.window_s == pytest.approx(0.454699871, rel=1e-6)
    assert s.busy_s == pytest.approx(0.023774268, rel=1e-6)
    ops = dict(s.breakdown["device_ops"])
    assert ops["coo_spmm"] == pytest.approx(0.01441127, rel=1e-6)
    assert ops["segment_sum"] == pytest.approx(0.009045308, rel=1e-6)
    kernels = tracing.op_seconds(tr, hop.is_hop_kernel)
    assert kernels == pytest.approx(ops["coo_spmm"] + ops["segment_sum"], rel=1e-9)
    idle = s.window_s - s.busy_s
    named = sum(v for _, v in s.breakdown["idle_gaps"])
    assert 0.8 * idle < named <= idle * (1 + 1e-9)  # the ten largest names
    assert s.breakdown["idle_gaps"][0][0] == "query/host"
    assert glob.glob(f"{RECORDED}/**/*.xplane.pb", recursive=True)
