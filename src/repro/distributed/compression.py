"""Compressed gradient reduction for the scarce cross-pod links.

int8 ring all-reduce via shard_map + ppermute: each hop sends per-chunk
int8-quantized payloads (absmax scale per chunk), accumulating in fp32, with
an optional **error-feedback** residual kept device-local so quantization
noise is re-injected next step (EF-SGD) — the standard trick that restores
convergence under aggressive compression.

Cross-pod traffic drops ~4x vs fp32 (1 byte payload + scale per chunk). On the
2-pod production mesh this targets the "pod" axis where per-link bandwidth is
the roofline collective term's denominator.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _quant(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def int8_ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Inside shard_map: mean-all-reduce of x over ``axis_name`` with int8
    payloads on every hop (reduce-scatter ring + all-gather ring)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % n
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(n, -1)

    # ---- reduce-scatter: after n-1 hops, device d owns the sum of chunk d+1
    def rs_body(i, carry):
        acc = carry  # [n, c] fp32 accumulator of received partials
        send_idx = (idx - i) % n
        q, s = _quant(acc[send_idx])
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        recv_idx = (idx - i - 1) % n
        acc = acc.at[recv_idx].add(_dequant(q, s))
        return acc

    acc = jax.lax.fori_loop(0, n - 1, rs_body, chunks.astype(jnp.float32))
    own = (idx + 1) % n
    mine = acc[own] / n                               # mean

    # ---- all-gather ring, also int8 per hop
    out = jnp.zeros_like(acc)
    out = out.at[own].set(mine)

    def ag_body(i, carry):
        out, cur, cur_idx = carry
        q, s = _quant(cur)
        q = jax.lax.ppermute(q, axis_name, perm)
        s = jax.lax.ppermute(s, axis_name, perm)
        cur = _dequant(q, s)
        cur_idx = (cur_idx - 1) % n
        out = out.at[cur_idx].set(cur)
        return out, cur, cur_idx

    out, _, _ = jax.lax.fori_loop(0, n - 1, ag_body, (out, mine, own))
    res = out.reshape(-1)[:x.size].reshape(x.shape)
    return res.astype(x.dtype)


def compressed_mean(x: jax.Array, mesh: Mesh, axis: str = "pod") -> jax.Array:
    """x [n_axis, ...]: row i is device-group i's local value (e.g. pod-local
    gradients). Returns the same shape with every row replaced by the mean,
    computed with int8 ring hops over ``axis``."""
    fn = jax.shard_map(
        functools.partial(int8_ring_allreduce, axis_name=axis),
        mesh=mesh,
        in_specs=P(axis),
        out_specs=P(axis),
        check_vma=False,
    )
    return fn(x)


def ef_compress_update(grads: Any, residual: Any, mesh: Mesh,
                       axis: str = "pod") -> tuple[Any, Any]:
    """Error-feedback compressed gradient mean over ``axis``.

    grads: pytree whose leaves are stacked per-pod local gradients
    [n_pod, ...]; residual: same structure (per-pod EF state). Returns
    (synced grads — every pod row equal, new residual)."""
    def one(g, r):
        corrected = g.astype(jnp.float32) + r
        synced = compressed_mean(corrected, mesh, axis)
        new_r = corrected - synced  # what compression lost, re-injected next step
        return synced.astype(g.dtype), new_r

    flat_g, treedef = jax.tree.flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    out = [one(g, r) for g, r in zip(flat_g, flat_r)]
    synced = treedef.unflatten([o[0] for o in out])
    new_res = treedef.unflatten([o[1] for o in out])
    return synced, new_res
