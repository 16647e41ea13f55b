"""Collective matmul: all-gather ∥ GEMM overlap — SALP-1 at the ICI level.

TP computes y = x @ W with x sharded on the contraction dim (or W gathered).
The naive schedule is all-gather(x) *then* matmul: latency = T_ag + T_mm.
Here the all-gather is decomposed into per-shard chunks moved around a ring by
``ppermute`` while the MXU multiplies the chunk that already arrived — chunk
transfer ("activation" of the next subarray) overlaps compute ("column
access"), so the steady state hides whichever is smaller:

    latency ~= max(T_ag, T_mm) + one-chunk ramp

This is the paper's PRE∥ACT overlap with chunks as subarrays. On real TPUs the
overlap happens via async collective-permute; the schedule (and its numerics,
which the tests check) is identical on CPU.

Used as a beyond-paper optimization for collective-bound cells in the perf
loop (EXPERIMENTS.md Sec. Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def ag_matmul_ring(x_shard: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """Inside shard_map: x_shard [m/n, k] (sharded on rows), w [k, n] (local
    shard of a column-sharded W is fine too). Computes all_gather(x) @ w with
    the ring-overlap schedule. Returns [m, n]."""
    n_dev = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    m_shard = x_shard.shape[0]
    out = jnp.zeros((n_dev * m_shard, w.shape[1]), x_shard.dtype)

    def body(i, carry):
        out, chunk = carry
        # compute on the resident chunk ("column access" on the activated row)
        src = (idx - i) % n_dev
        y = jnp.dot(chunk, w, preferred_element_type=jnp.float32).astype(out.dtype)
        out = jax.lax.dynamic_update_slice(out, y, (src * m_shard, 0))
        # move the next chunk around the ring ("activate" the next subarray);
        # on TPU this ppermute runs async, overlapped with the dot above
        chunk = jax.lax.ppermute(chunk, axis_name, perm)
        return out, chunk

    out, _ = jax.lax.fori_loop(0, n_dev, body, (out, x_shard))
    return out


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def collective_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                      axis: str = "model") -> jax.Array:
    """y[m, n] = x[m, k] @ w[k, n], with x row-sharded over ``axis`` and the
    gather overlapped with compute. w is replicated over ``axis``."""
    fn = jax.shard_map(
        functools.partial(ag_matmul_ring, axis_name=axis),
        mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    return fn(x, w)
