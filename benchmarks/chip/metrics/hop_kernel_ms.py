"""Device milliseconds of the Pallas hop kernels per completed query.

The kernels appear in the trace as custom calls named after the kernel
(``%coo_spmm.1 = ... custom-call(...)``), as read by hand from a trace of
one TPU v5e.
"""
import tracing

HOP_KERNELS = ("coo_spmm", "segment_sum", "segment_reduce", "fused_hop")


def is_hop_kernel(name: str) -> bool:
    return "custom-call" in name and tracing.op_label(name) in HOP_KERNELS


def read(ctx):
    if ctx.trace is None or not ctx.completed:
        return None
    s = tracing.op_seconds(ctx.trace.trace, is_hop_kernel)
    return 1e3 * s / ctx.completed if s > 0 else None
