"""Fused attention forward (flash-attention schedule) — beyond-paper perf work
on the memory roofline term of the training/prefill shapes.

Grid (B*H, n_q_blocks, n_k_blocks), K innermost/sequential: the online-softmax
running state (m, l, acc) lives in VMEM scratch across K steps — the same
SALP-1 state-stays-activated pipeline as ssd_scan — and the S×S score matrix
never exists in HBM: per-chip attention HBM traffic drops from O(S²·H) to
O(S·H·hd), which is what the §Perf memory-bound prefill cells need.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_NEG_INF = -1e30


def _body(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
          scale: float, causal: bool, bq: int, bk: int, nk: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # [bq, bk]
    if causal:
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, _NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    e = jnp.exp(s - m_new)
    l_ref[...] = jnp.broadcast_to(l_ref[:, :1] * corr
                                  + jnp.sum(e, axis=-1, keepdims=True), l_ref.shape)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(e, v,
                                                 preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q/k/v [BH, S, hd] -> out [BH, S, hd]."""
    bh, s, hd = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nk = s // bk
    scale = hd ** -0.5

    return pl.pallas_call(
        functools.partial(_body, scale=scale, causal=causal, bq=bq, bk=bk, nk=nk),
        grid=(bh, s // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # m (broadcast stored)
            pltpu.VMEM((bq, 128), jnp.float32),   # l
            pltpu.VMEM((bq, hd), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
