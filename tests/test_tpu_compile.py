"""Deviceless compiles of the hop kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
refuses what the Pallas interpreter accepts: a 1-D block whose tiling
differs from XLA's ``T(1024)``, an in-kernel ``dynamic_slice`` of a
value, a body that overflows scoped VMEM.  Each test compiles one kernel
with ``interpret=False`` at 2**16 edges and the segment / child-row
counts of a hop the chip smoke runs (``repro.launch.smoke`` at
``chip_smoke.py``'s ``N_SMALL`` = 32,768 lineitem rows), or, for
``segment_reduce``, at the benchmark's minmax hops, and checks that the
compiled program calls the Mosaic kernel.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and each test worker imports every test file.

Each kernel's ``pallas_call`` carries an explicit ``name``: it names the
custom call in the compiled HLO, and so the op in a device trace, which
the chip benchmark's ``hop_kernel_ms`` finds by that label.  One test
checks the name in each wrapper's jaxpr on the CPU; another compiles
each kernel for the described chip and reads the label back.
"""
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, ops

EDGES = 2**16
# smoke hops at N_SMALL: lineitem (327 supplier segments, 8,192 orders
# rows of width 161 zipcodes), orders (8,192 segments, 3,276 customer
# rows) and supplier (327 segments, 327 lineitem-message rows)
LINEITEM = {"segments": 327, "rows": 8192, "width": 161}
ORDERS = {"segments": 8192, "rows": 3276, "width": 161}
SUPPLIER = {"segments": 327, "rows": 327, "width": 161}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_for_tpu(one_chip, monkeypatch):
    """Compile with the persistent cache off (an entry written for a
    described chip cannot be read back without one), and with the
    kernel policy seeing a TPU backend — the process itself runs on
    the CPU backend, where ``interpret=False`` raises by design."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compile_text(lowered) -> str:
        text = lowered.compile().as_text()
        assert "tpu_custom_call" in text
        return text

    yield sds, compile_text
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def test_coo_spmm_compiles(compile_for_tpu):
    """The pair-grid form, which the engine dispatches for row-sorted
    hops, with its pair list at the static length it uploads."""
    sds, compile_text = compile_for_tpu
    i32 = jnp.int32
    pairs = ops.pair_grid_len(EDGES, LINEITEM["segments"])
    compile_text(
        ops.coo_spmm_pairs.lower(
            sds((EDGES,), i32), sds((EDGES,), i32), sds((EDGES,)),
            sds((LINEITEM["rows"], LINEITEM["width"])), sds((2, pairs), i32),
            num_rows=LINEITEM["segments"], interpret=False,
        )
    )


def test_coo_spmm_full_grid_compiles(compile_for_tpu):
    """The long pair list, ``m_tiles * e_tiles`` entries, as many as the
    full grid's cells, which rows too scattered for the short one take;
    its custom call carries the same label."""
    sds, compile_text = compile_for_tpu
    i32 = jnp.int32
    cells = -(-LINEITEM["segments"] // 128) * -(-EDGES // 1024)
    text = compile_text(
        ops.coo_spmm_pairs.lower(
            sds((EDGES,), i32), sds((EDGES,), i32), sds((EDGES,)),
            sds((LINEITEM["rows"], LINEITEM["width"])), sds((2, cells), i32),
            num_rows=LINEITEM["segments"], interpret=False,
        )
    )
    assert _custom_call_labels(text) == ["coo_spmm"]


def test_segment_sum_compiles(compile_for_tpu):
    sds, compile_text = compile_for_tpu
    compile_text(
        ops.segment_sum.lower(
            sds((EDGES, LINEITEM["width"])), sds((EDGES,), jnp.int32),
            num_segments=LINEITEM["segments"], interpret=False,
        )
    )


# the benchmark's tpch_chain.minmax hops, padded to the edge granule:
# (rows, width, segments) of the customer, orders and lineitem hops
MINMAX_HOPS = [(3072, 1, 75000), (30720, 25, 30000), (120832, 25, 200)]


@pytest.mark.parametrize("long", [False, True], ids=["short", "long"])
@pytest.mark.parametrize("kind", ["min", "max"])
@pytest.mark.parametrize(
    "rows,width,segs", MINMAX_HOPS, ids=["customer", "orders", "lineitem"]
)
def test_segment_reduce_compiles(compile_for_tpu, rows, width, segs, kind, long):
    """The scheduled grid at the benchmark's hop shapes, with a schedule
    of either static length: ``s_tiles + n_blocks - 1`` (CSR-sorted keys)
    or ``s_tiles * n_blocks`` (keys too scattered for it)."""
    sds, compile_text = compile_for_tpu
    s_tiles, n_blocks = -(-segs // 128), rows // 1024
    grid_len = s_tiles * n_blocks if long else ops.pair_grid_len(rows, segs)
    text = compile_text(
        ops.segment_reduce_scheduled.lower(
            sds((rows, width)), sds((rows,), jnp.int32),
            sds((4, grid_len), jnp.int32), num_segments=segs, kind=kind,
            interpret=False,
        )
    )
    assert _custom_call_labels(text) == ["segment_reduce"]


@pytest.mark.parametrize(
    "kind,k,hop",
    [("sum", 1, LINEITEM), ("sum", 5, SUPPLIER), ("min", 1, ORDERS)],
    ids=["sum-k1", "sum-k5", "min"],
)
def test_fused_hop_compiles(compile_for_tpu, kind, k, hop):
    """At the tiles the engine would pick: the autotuner's VMEM model
    must admit only configurations the compiler accepts."""
    sds, compile_text = compile_for_tpu
    shape = autotune.hop_shape(
        edges=EDGES, child_rows=(hop["rows"],), k=k, kind=kind,
        child_widths=(hop["width"],), num_segments=hop["segments"],
    )
    cfg = autotune.model_tiles_for(shape)
    i32 = jnp.int32
    compile_text(
        ops.fused_hop.lower(
            sds((EDGES,), i32), sds((EDGES, k)),
            (sds((hop["rows"], hop["width"] * k)),), (sds((EDGES,), i32),),
            num_segments=hop["segments"], k=k, kind=kind,
            block_e=cfg.block_e, block_s=cfg.block_s, block_r=cfg.block_r,
            interpret=False,
        )
    )


# the benchmark's hops whose per-edge product ``hop_gather`` forms, padded
# to the edge granule: (edges, child rows, child width, k, op)
GATHER_HOPS = {
    "agg-lineitem": (120832, 30000, 25, 2, "mul"),
    "agg-customer-leaf": (3072, None, None, 2, "mul"),
    "minmax-lineitem": (120832, 30000, 25, 1, "add"),
    "minmax-orders": (30720, 3000, 25, 1, "add"),
}


@pytest.mark.parametrize("hop", list(GATHER_HOPS))
def test_hop_gather_compiles(compile_for_tpu, hop):
    """The device-side gather and product at the benchmark's hop shapes,
    from 1-D uploads of the weights and child messages.  The program has
    no kernel of its own, and its temporaries stay within twice the
    product as HBM tiles it (rows of ``width·k`` padded to 128 lanes):
    no intermediate keeps the narrow channel axis minor."""
    from repro.core.jax_engine import hop_gather

    sds, _ = compile_for_tpu
    n, rows, width, k, op = GATHER_HOPS[hop]
    children = [] if rows is None else [(rows, width)]
    compiled = hop_gather.lower(
        sds((n,), jnp.int32), sds((n * k,)),
        tuple(sds((r * w * k,)) for r, w in children),
        tuple(sds((n,), jnp.int32) for _ in children),
        shapes=tuple(children), k=k, op=op,
    ).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    product = 4 * n * ops.round_up((width or 1) * k, 128)
    assert compiled.memory_analysis().temp_size_in_bytes <= 2 * product


# ----------------------------------------------------------------------
# kernel names: the label a device trace gives each custom call
# ----------------------------------------------------------------------


def _named_calls(sds):
    """A small call of each Pallas kernel: ``(name, wrapper, args,
    kwargs)``, the arguments abstract."""
    i32 = jnp.int32
    e, rows, width, segs = 2048, 256, 128, 128
    fused = autotune.model_tiles_for(autotune.hop_shape(
        edges=e, child_rows=(rows,), k=1, kind="sum",
        child_widths=(width,), num_segments=segs,
    ))
    return [
        ("coo_spmm", ops.coo_spmm_pairs,
         (sds((e,), i32), sds((e,), i32), sds((e,)), sds((rows, width)),
          sds((2, ops.pair_grid_len(e, segs)), i32)),
         dict(num_rows=segs)),
        ("segment_sum", ops.segment_sum,
         (sds((e, width)), sds((e,), i32)), dict(num_segments=segs)),
        ("segment_reduce", ops.segment_reduce_scheduled,
         (sds((e, width)), sds((e,), i32),
          sds((4, ops.pair_grid_len(e, segs)), i32)),
         dict(num_segments=segs, kind="min")),
        ("fused_hop", ops.fused_hop,
         (sds((e,), i32), sds((e, 1)), (sds((rows, width)),), (sds((e,), i32),)),
         dict(num_segments=segs, k=1, kind="sum", block_e=fused.block_e,
              block_s=fused.block_s, block_r=fused.block_r)),
        ("semiring_matmul", ops.semiring_matmul,
         (sds((rows, width)), sds((width, rows))), dict(semiring="add_mul")),
    ]


KERNELS = ["coo_spmm", "segment_sum", "segment_reduce", "fused_hop", "semiring_matmul"]


def _call(sds, name):
    (call,) = [c[1:] for c in _named_calls(sds) if c[0] == name]
    return call


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_pallas_names(sub))
    return out


@pytest.mark.parametrize("name", KERNELS)
def test_pallas_call_carries_the_kernel_name(name):
    fn, args, kwargs = _call(
        lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype), name
    )
    traced = fn.trace(*args, **kwargs, interpret=True)
    assert _pallas_names(traced.jaxpr.jaxpr) == [name]


def _op_label():
    """The chip benchmark's label of a trace op (``%name.1 = ...`` ->
    ``name``), read from its own file."""
    path = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "tracing.py"
    spec = importlib.util.spec_from_file_location("chip_tracing", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # its dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod.op_label


def _custom_call_labels(text: str) -> list:
    """The trace labels of the Mosaic custom calls in compiled HLO."""
    label = _op_label()
    # an instruction as the trace names it: the HLO line without ROOT
    return [
        label(line.strip().removeprefix("ROOT ")) for line in text.splitlines()
        if re.search(r"custom-call\(", line) and "tpu_custom_call" in line
    ]


@pytest.mark.parametrize("name", KERNELS)
def test_compiled_custom_call_is_labelled_by_the_kernel_name(compile_for_tpu, name):
    sds, compile_text = compile_for_tpu
    fn, args, kwargs = _call(sds, name)
    text = compile_text(fn.lower(*args, **kwargs, interpret=False))
    assert _custom_call_labels(text) == [name]
