"""MASA-tiled matmul kernel.

C[M,N] = A[M,K] @ B[K,N] with a residency-order knob mapping the paper's
insight onto Mosaic's tile pipeline:

  order="output_stationary"  grid (M/bm, N/bn, K/bk), K innermost: the C
      accumulator tile stays resident in VMEM scratch across the K loop while
      A/B tiles stream — the SALP-1/2 fetch pipeline.

  order="weight_stationary"  grid (N/bn, M/bm), M innermost, whole-K panels:
      the B ("weight") block index is constant across consecutive M steps, so
      Mosaic skips the re-fetch — exactly a DRAM row-buffer hit on the
      "activated" weight tile (MASA designation). Best for tall activations
      over a small weight panel (MoE expert FFNs); requires the K panel to fit
      VMEM (asserted in ops.py).

The kernel body is shared; the BlockSpec index_maps encode the residency
schedule, the way SA_SEL designates which local row buffer serves the column
command.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel_os(a_ref, b_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_ws(a_ref, b_ref, o_ref):
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def masa_gemm_kernel(a: jax.Array, b: jax.Array, *,
                     bm: int = 128, bn: int = 128, bk: int = 128,
                     order: str = "output_stationary",
                     interpret: bool = False) -> jax.Array:
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0, (a.shape, b.shape, (bm, bn))
    out_shape = jax.ShapeDtypeStruct((m, n), a.dtype)

    if order == "output_stationary":
        assert k % bk == 0, (k, bk)
        nk = k // bk
        return pl.pallas_call(
            functools.partial(_kernel_os, nk=nk),
            grid=(m // bm, n // bn, nk),
            in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                      pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(a, b)

    if order == "weight_stationary":
        # whole-K panel; B block constant across the inner M loop => residency hit
        return pl.pallas_call(
            _kernel_ws,
            grid=(n // bn, m // bm),
            in_specs=[pl.BlockSpec((bm, k), lambda j, i: (i, 0)),
                      pl.BlockSpec((k, bn), lambda j, i: (0, j))],
            out_specs=pl.BlockSpec((bm, bn), lambda j, i: (i, j)),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(a, b)

    raise ValueError(order)
