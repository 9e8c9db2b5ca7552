"""Pallas TPU blocked semiring matmul.

JOIN-AGG contractions are matmuls in a configurable semiring
(Section IV-D): COUNT/SUM use (+, ×) on the MXU; MIN/MAX aggregates use
(min/max, +) and reachability uses (or, and) — those have no MXU form, so
the kernel keeps MXU for add_mul and lowers the exotic semirings to
VPU-friendly elementwise ops over k-slices while preserving the same
VMEM blocking.

Grid ``(m_tiles, n_tiles, k_tiles)``; C tile accumulates across k.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_IDENT = {"add_mul": 0.0, "max_add": -jnp.inf, "min_add": jnp.inf, "or_and": 0.0}

#: m and n axes write disjoint C tiles (parallelizable); the k axis
#: revisits one C tile with a ``@pl.when(ki == 0)`` init + accumulate,
#: so it must be sequential ("arbitrary") — see coo_spmm
DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _semiring_matmul_kernel(a_ref, b_ref, c_ref, *, semiring: str, k_step: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        c_ref[...] = jnp.full_like(c_ref, _IDENT[semiring])

    if semiring == "add_mul":
        c_ref[...] += jnp.dot(
            a_ref[...], b_ref[...], preferred_element_type=c_ref.dtype
        )
        return

    def body(i, acc):
        lo = pl.multiple_of(i * k_step, k_step)
        a_sl = a_ref[:, pl.ds(lo, k_step)]
        b_sl = b_ref[pl.ds(lo, k_step), :]
        if semiring == "max_add":
            upd = jnp.max(a_sl[:, :, None] + b_sl[None, :, :], axis=1)
            return jnp.maximum(acc, upd)
        if semiring == "min_add":
            upd = jnp.min(a_sl[:, :, None] + b_sl[None, :, :], axis=1)
            return jnp.minimum(acc, upd)
        # or_and
        hit = jnp.any((a_sl[:, :, None] > 0) & (b_sl[None, :, :] > 0), axis=1)
        return jnp.maximum(acc, hit.astype(acc.dtype))

    # exact: the wrapper picks k_step = gcd(block_k, 8), so it divides
    # the block k-width by construction
    steps = a_ref.shape[1] // k_step  # lint-ok: tile-floordiv
    acc = jax.lax.fori_loop(0, steps, body, c_ref[...])
    c_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("semiring", "block_m", "block_n", "block_k", "interpret"),
)
def semiring_matmul(
    a: jax.Array,
    b: jax.Array,
    semiring: str = "add_mul",
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """C = A ⊗ B over the chosen semiring; A (m, k), B (k, n)."""
    from repro.kernels import ops

    interpret = ops.resolve_interpret(interpret)
    block_m = ops.normalize_block("block_m", block_m)
    block_n = ops.normalize_block("block_n", block_n)
    block_k = ops.normalize_block("block_k", block_k)
    if semiring not in _IDENT:
        raise ValueError(f"unknown semiring {semiring!r}")
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    pad_fill = (
        0.0
        if semiring in ("add_mul", "or_and")
        else (jnp.inf if semiring == "min_add" else -jnp.inf)
    )
    m_pad, n_pad, k_pad = -m % block_m, -n % block_n, -k % block_k
    if m_pad or k_pad:
        a = jnp.pad(a, ((0, m_pad), (0, k_pad)), constant_values=pad_fill)
    if k_pad or n_pad:
        b = jnp.pad(b, ((0, k_pad), (0, n_pad)), constant_values=pad_fill)
    grid = (a.shape[0] // block_m, b.shape[1] // block_n, a.shape[1] // block_k)
    # k_step must divide block_k exactly or the fori_loop drops the
    # trailing k-slices of every block; normalize_block above guarantees it
    k_step = ops.k_step_for(block_k)
    out = pl.pallas_call(
        functools.partial(_semiring_matmul_kernel, semiring=semiring, k_step=k_step),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((block_k, block_n), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[1]), a.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=DIM_SEMANTICS),
        interpret=interpret,
        name="semiring_matmul",  # the custom call's name in HLO and device traces
    )(a, b)
    return out[:m, :n]
