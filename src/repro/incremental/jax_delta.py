"""Kernel-backed delta contraction (DESIGN.md §4, jax engine).

:class:`KernelDeltaEngine` is a :class:`~repro.core.tensor_engine.TensorEngine`
whose gather-product-scatter hot loop (``_contract_block``) dispatches to
the existing Pallas kernels over the *delta COO blocks*:

* one child message → ``coo_spmm``: ``out[key[i]] += w[i] * M[idx[i]]``
  is exactly the kernel's scatter-matmul contract, with the delta rows as
  the COO entries and the cached (or delta) child message as the dense
  operand;
* zero or several children → the per-row product is formed host-side and
  reduced with the Pallas ``segment_sum``.

Device results come back as float32 (exact for counts below 2^24 per
partial product — the same envelope as the batch jax engine) and the
``msg ⊕ Δmsg`` cache accumulation stays host-side: the caches are numpy
arrays, so a device-side (donated) add would pay three transfers for one
addition.  On CPU hosts the kernels run in interpret mode, so the whole
incremental path is exercisable in CI.

Program memoization: this engine keeps no memo dict of its own — the
delta blocks are padded to ``EDGE_BUCKET`` multiples so the Pallas
kernels retrace only per bucket size (jax's own jit cache), and the
plan-keyed einsum/jit memos its batch-refresh fallbacks lean on live in
:mod:`repro.core.jax_engine`, which bounds them with the shared
:class:`~repro.serve.cache.LRUCache` (hit/miss/eviction counters via
``jit_cache_stats()``; DESIGN.md §9).
"""
from __future__ import annotations

import numpy as np

from repro.core.jax_engine import _pad_edges
from repro.core.tensor_engine import TensorEngine


class KernelDeltaEngine(TensorEngine):
    """Tensor engine contracting row blocks on the Pallas kernels."""

    def __init__(self, *args, interpret: bool | None = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.interpret = interpret

    def _contract_block(
        self,
        weights: np.ndarray,
        gathers: list[tuple[np.ndarray, np.ndarray]],
        keys: np.ndarray,
        knum: int,
    ) -> np.ndarray:
        import jax.numpy as jnp

        from repro.kernels.ops import coo_spmm, segment_sum, spmm_pair_list

        n = len(weights)
        if knum >= 2**31:  # int32 segment-id space of the kernels
            return super()._contract_block(weights, gathers, keys, knum)
        if n == 0:
            width = 1
            for m2, _ in gathers:
                width *= m2.shape[1]
            return np.zeros((knum, width), dtype=np.float32)
        w32 = np.asarray(weights, dtype=np.float32)
        if len(gathers) == 1:
            m2, idx = gathers[0]
            k, w, idx = _pad_edges(keys, w32, idx)
            out = coo_spmm(
                jnp.asarray(k), jnp.asarray(idx), jnp.asarray(w),
                jnp.asarray(m2, dtype=jnp.float32), num_rows=knum,
                pairs=spmm_pair_list(k, knum), interpret=self.interpret,
            )
        else:
            vals = w32.reshape(n, 1)
            for m2, idx in gathers:
                rows = m2[idx].astype(np.float32)
                vals = (vals[:, :, None] * rows[:, None, :]).reshape(n, -1)
            k, vals = _pad_edges(keys, vals)
            out = segment_sum(
                jnp.asarray(vals), jnp.asarray(k), num_segments=knum,
                interpret=self.interpret,
            )
        return np.asarray(out, dtype=np.float32)
