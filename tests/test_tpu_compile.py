"""Deviceless compiles of the hop kernels for a described TPU v5e.

The TPU compiler is installed even where no chip is attached, and it
refuses what the Pallas interpreter accepts: a 1-D block whose tiling
differs from XLA's ``T(1024)``, an in-kernel ``dynamic_slice`` of a
value, a body that overflows scoped VMEM.  Each test compiles one kernel
with ``interpret=False`` at 2**16 edges and the segment / child-row
counts of a hop the chip smoke runs (``repro.launch.smoke`` at
``chip_smoke.py``'s ``N_SMALL`` = 32,768 lineitem rows), and checks that
the compiled program calls the Mosaic kernel.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and each test worker imports every test file.
"""
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
    sds, compile_text = compile_for_tpu
    i32 = jnp.int32
    compile_text(
        ops.coo_spmm.lower(
            sds((EDGES,), i32), sds((EDGES,), i32), sds((EDGES,)),
            sds((LINEITEM["rows"], LINEITEM["width"])),
            num_rows=LINEITEM["segments"], interpret=False,
        )
    )


def test_segment_sum_compiles(compile_for_tpu):
    sds, compile_text = compile_for_tpu
    compile_text(
        ops.segment_sum.lower(
            sds((EDGES, LINEITEM["width"])), sds((EDGES,), jnp.int32),
            num_segments=LINEITEM["segments"], interpret=False,
        )
    )


@pytest.mark.parametrize("kind", ["min", "max"])
def test_segment_reduce_compiles(compile_for_tpu, kind):
    sds, compile_text = compile_for_tpu
    compile_text(
        ops.segment_reduce.lower(
            sds((EDGES, LINEITEM["width"])), sds((EDGES,), jnp.int32),
            num_segments=LINEITEM["segments"], kind=kind, interpret=False,
        )
    )


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
