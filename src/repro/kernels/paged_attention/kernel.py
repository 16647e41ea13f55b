"""Paged decode attention — SALP at the KV-cache level.

One query token per sequence attends over a paged KV cache through block-table
indirection: KV pages are DRAM "rows", the VMEM page slot the Mosaic pipeline
streams through is the "local row buffer", and the scalar-prefetched block
table is the global row decoder. The serving scheduler (repro/serve) lays page
lists out so consecutive grid steps hit resident pages where possible
(prefix-shared requests) — the MASA designation benefit.

Shapes:
  q        [B, KVH, G, hd]     (G = q heads per kv head)
  k_pages  [P, page, KVH, hd]  (v_pages alike)
  block_table [B, n_pages]     page id per (seq, slot); clamped, masked by len
  seq_lens [B]                 valid KV length per sequence

Grid (B, KVH, n_pages); online softmax accumulates in VMEM scratch across the
sequential page dimension (the SALP-1 pipeline).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _body(bt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref,
          m_ref, l_ref, acc_ref, *, page: int, n_pages: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # [G, hd]
    k = k_ref[0, :, 0, :].astype(jnp.float32)         # [page, hd]
    v = v_ref[0, :, 0, :].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [G, page]
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    valid = pos < sl_ref[b]
    s = jnp.where(valid, s, _NEG_INF)

    m_prev = m_ref[:, :1]                             # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    e = jnp.exp(s - m_new)                            # [G, page]
    l_new = l_ref[:, :1] * corr + jnp.sum(e, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(e, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == n_pages - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                           block_table: jax.Array, seq_lens: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    bsz, kvh, g, hd = q.shape
    _, page, kvh2, _ = k_pages.shape
    assert kvh == kvh2
    n_pages = block_table.shape[1]
    scale = hd ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, kvh, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda b, h, p, bt, sl: (b, h, 0, 0)),
            pl.BlockSpec((1, page, 1, hd), lambda b, h, p, bt, sl: (bt[b, p], 0, h, 0)),
            pl.BlockSpec((1, page, 1, hd), lambda b, h, p, bt, sl: (bt[b, p], 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda b, h, p, bt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),   # m (broadcast stored)
            pltpu.VMEM((g, 128), jnp.float32),   # l
            pltpu.VMEM((g, hd), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_body, page=page, n_pages=n_pages, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(block_table, seq_lens, q, k_pages, v_pages)
