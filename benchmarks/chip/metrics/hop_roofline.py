"""Share of the hop kernels' device time that the work the query needs
would take at the chip's peak, in %.

The needed work comes from the cell's data and query (``work.py``), not
from what the kernels dispatch; the kernels' time is that of
``hop_kernel_ms``.  The peaks come from ``peaks.json``.
"""
import importlib.util
from pathlib import Path

import tracing
import work


def _hop_kernels():
    path = Path(__file__).with_name("hop_kernel_ms.py")
    spec = importlib.util.spec_from_file_location("hop_kernel_ms", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(ctx):
    if ctx.trace is None or not ctx.completed:
        return None
    s = tracing.op_seconds(ctx.trace.trace, _hop_kernels().is_hop_kernel)
    if s <= 0:
        return None
    peak = work.peaks(ctx.device_kind, ctx.bench_dir)
    need = work.least_time(ctx.query, ctx.aggs, ctx.tables, peak)
    return 100.0 * need["seconds"] * ctx.completed / s
