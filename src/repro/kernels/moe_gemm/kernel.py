"""Grouped expert GEMM — the MASA designation kernel.

y_sorted[T, F] = x_sorted[T, D] @ W[expert_of_block(T), D, F]

Tokens arrive sorted by expert (the MoE layer's capacity buffer flattened to
[E*C, D]); each token block carries a scalar-prefetched expert id that
*designates* which expert's weight panel must be resident in VMEM — the
paper's SA_SEL, one level up. Consecutive blocks routed to the same expert map
to the same weight block index, so Mosaic skips the re-fetch: a row-buffer hit.
The SA_SEL:ACTIVATE ratio of the DRAM evaluation becomes the block-hit rate
here (benchmarks/kernel_bench.py measures it).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _body(eids_ref, x_ref, w_ref, o_ref):
    o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def grouped_matmul_kernel(x_sorted: jax.Array, w: jax.Array,
                          block_eids: jax.Array, *,
                          bt: int = 128, bf: int = 128,
                          interpret: bool = False) -> jax.Array:
    t, d = x_sorted.shape
    e, d2, f = w.shape
    assert d == d2 and t % bt == 0 and f % bf == 0, (x_sorted.shape, w.shape, bt, bf)
    assert block_eids.shape == (t // bt,)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // bt, f // bf),
        in_specs=[
            pl.BlockSpec((bt, d), lambda i, j, eids: (i, 0)),
            # the designation: block i's expert id selects the weight panel
            pl.BlockSpec((1, d, bf), lambda i, j, eids: (eids[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bf), lambda i, j, eids: (i, j)),
    )
    return pl.pallas_call(
        _body,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, f), x_sorted.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_eids, x_sorted, w)
