"""Mamba-2 SSD chunked-scan Pallas kernel.

Grid: (batch*heads, n_chunks) with the chunk dimension sequential
("arbitrary"); the inter-chunk state [d_state, head_dim] lives in VMEM scratch
and is carried across grid steps — the SALP-1 pipeline: the state tile stays
"activated" while the next chunk's operands are DMA'd in.

Inputs are pre-arranged per (batch*head): the dt-scaled input xr, the per-step
log-decay l = dt * A, and the (group-shared) B/C projections indexed through
the head->group map in the BlockSpecs (no materialized expansion).

  xr [BH, L, hd]   l [BH, L]   b,c [B, L, ds]   ->   y [BH, L, hd], hT [BH, ds, hd]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_body(xr_ref, l_ref, b_ref, c_ref, y_ref, hT_ref, state_ref, *,
              n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xr = xr_ref[0].astype(jnp.float32)          # [Q, hd]
    l = l_ref[0].astype(jnp.float32)            # [Q]
    b = b_ref[0].astype(jnp.float32)            # [Q, ds]
    c = c_ref[0].astype(jnp.float32)            # [Q, ds]
    q = xr.shape[0]

    cum = jnp.cumsum(l)                         # [Q]
    total = cum[-1]

    # intra-chunk: y_i = sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) xr_j
    g = jnp.dot(c, b.T, preferred_element_type=jnp.float32)      # [Q,Q]
    delta = cum[:, None] - cum[None, :]
    mask = jnp.tril(jnp.ones((q, q), jnp.bool_))
    m = jnp.where(mask, jnp.exp(delta), 0.0)
    y = jnp.dot(g * m, xr, preferred_element_type=jnp.float32)   # [Q,hd]

    # inter-chunk: y_i += exp(cum_i) * C_i . state
    y = y + jnp.exp(cum)[:, None] * jnp.dot(c, state_ref[...],
                                            preferred_element_type=jnp.float32)

    # state update: S <- exp(total) S + sum_j exp(total - cum_j) B_j (x) xr_j
    w = jnp.exp(total - cum)                    # [Q]
    state_ref[...] = (jnp.exp(total) * state_ref[...]
                      + jnp.dot(b.T * w[None, :], xr,
                                preferred_element_type=jnp.float32))

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hT_ref[0] = state_ref[...].astype(hT_ref.dtype)


def ssd_scan_kernel(xr: jax.Array, l: jax.Array, b: jax.Array, c: jax.Array, *,
                    chunk: int, n_heads: int, interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array]:
    bh, L, hd = xr.shape
    ds = b.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk

    y, hT = pl.pallas_call(
        functools.partial(_ssd_body, n_chunks=nc),
        grid=(bh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk), lambda i, j: (i, j)),
            # B/C are shared across the heads of one batch element
            pl.BlockSpec((1, chunk, ds), lambda i, j: (i // n_heads, j, 0)),
            pl.BlockSpec((1, chunk, ds), lambda i, j: (i // n_heads, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, ds, hd), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, L, hd), xr.dtype),
            jax.ShapeDtypeStruct((bh, ds, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xr, l, b, c)
    return y, hT
