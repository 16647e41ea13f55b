"""Sharding rules: parameter/batch/cache PartitionSpecs for the production mesh.

Mesh axes: ("data", "model") single-pod, ("pod", "data",
"model") multi-pod. "data" carries DP+FSDP (ZeRO-3-style parameter sharding),
"model" carries TP/EP; "pod" joins "data" for the gradient reduction (pure DP
across pods — FSDP stays intra-pod so cross-pod links only carry gradients).

Rules are path-based over the parameter pytree (DESIGN.md Sec. 5):

  embed/lm_head table [V,d]        -> (model, data)        vocab TP + FSDP
  attn  wq [R,d,H,hd]              -> (_, data, model, _)  heads TP (pad if needed)
        wk/wv [R,d,Hkv,hd]         -> (_, data, model|None, _)  replicate if Hkv∤TP
        wo [R,H,hd,d]              -> (_, model, _, data)
  mlp   up/gate [R,d,f]            -> (_, data, model); down transposed
  moe   up/gate/down [R,E,d,f]     -> (_, model, data, _)  expert parallelism
  ssm   in_zx [R,d,2di]            -> (_, data, model)     head-aligned TP
        in_bcdt / conv_bc          -> replicated (n_groups=1 B/C/dt)
        conv_x/norm/out_proj       -> di over model
  norms                            -> replicated

pure_dp archs (smollm): every param replicated, batch over (data, model).
GQA divisibility fallbacks and the 40->48 head padding for llama4-maverick are
applied automatically (``pad_heads_for``).
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec

Params = Any


def dp_axes(mesh: Mesh, pure_dp: bool = False):
    """The mesh axes carrying the batch dimension."""
    multi_pod = "pod" in mesh.axis_names
    if pure_dp:
        # replicate params; spread batch over everything that divides it
        return ("data", "model")
    return ("pod", "data") if multi_pod else ("data",)


def pad_heads_for(cfg: ModelConfig, mesh: Mesh) -> int:
    """Heads added so n_heads divides the model axis (llama4: 40->48)."""
    if cfg.pure_dp or cfg.attn is None:
        return 0
    tp = mesh.shape["model"]
    return (-cfg.attn.n_heads) % tp


def _maybe(axis: str, dim: int, size: int):
    return axis if dim % size == 0 else None


def param_pspecs(cfg: ModelConfig, params_tree: Params, mesh: Mesh) -> Params:
    """PartitionSpec pytree matching ``params_tree`` (real params or
    ShapeDtypeStructs)."""
    tp = mesh.shape["model"]
    fsdp = mesh.shape["data"]

    if cfg.pure_dp:
        return jax.tree.map(lambda _: P(), params_tree)

    def rule(path, leaf) -> P:
        names = [getattr(k, "key", str(k)) for k in path]
        name = names[-1]
        shape = leaf.shape
        stacked = names[0] in ("dec", "enc")          # leading [R, ...] axis
        lead = (None,) if stacked else ()

        def spec(*rest):
            assert len(lead) + len(rest) == len(shape), (names, shape, rest)
            return P(*lead, *rest)

        if name == "table":                            # embed / lm_head [V, d]
            return P(_maybe("model", shape[0], tp), _maybe("data", shape[1], fsdp))
        if name == "scale":                            # norms: replicated
            return P(*((None,) * len(shape)))
        if name == "wq":                               # [R, d, H(+pad), hd]
            return spec(_maybe("data", shape[-3], fsdp),
                        _maybe("model", shape[-2], tp), None)
        if name in ("wk", "wv"):                       # [R, d, Hkv, hd]
            return spec(_maybe("data", shape[-3], fsdp),
                        _maybe("model", shape[-2], tp), None)
        if name == "wo":                               # [R, H, hd, d]
            return spec(_maybe("model", shape[-3], tp), None,
                        _maybe("data", shape[-1], fsdp))
        if name == "router":                           # [R, d, E]
            return spec(_maybe("data", shape[-2], fsdp), None)
        if name in ("up", "gate", "down") and len(shape) == len(lead) + 3:
            # MoE expert stacks [R, E, d, f] / [R, E, f, d]
            dsize = shape[-2] if name != "down" else shape[-1]
            if name == "down":
                return spec(_maybe("model", shape[-3], tp), None,
                            _maybe("data", shape[-1], fsdp))
            return spec(_maybe("model", shape[-3], tp),
                        _maybe("data", shape[-2], fsdp), None)
        if name in ("up", "gate", "shared_up", "shared_gate"):  # [R, d, f]
            return spec(_maybe("data", shape[-2], fsdp),
                        _maybe("model", shape[-1], tp))
        if name in ("down", "shared_down"):            # [R, f, d]
            return spec(_maybe("model", shape[-2], tp),
                        _maybe("data", shape[-1], fsdp))
        if name == "in_zx":                            # [R, d, 2di]
            return spec(_maybe("data", shape[-2], fsdp),
                        _maybe("model", shape[-1], tp))
        if name == "in_bcdt":                          # replicated output
            return spec(_maybe("data", shape[-2], fsdp), None)
        if name == "conv_x_w":                         # [R, K, di]
            return spec(None, _maybe("model", shape[-1], tp))
        if name in ("conv_x_b", "norm_scale"):         # [R, di]
            return spec(_maybe("model", shape[-1], tp))
        if name in ("conv_bc_w",):
            return spec(None, None)
        if name in ("conv_bc_b",):
            return spec(None)
        if name in ("A_log", "D", "dt_bias"):          # [R, H]
            return spec(_maybe("model", shape[-1], tp))
        if name == "out_proj":                         # [R, di, d]
            return spec(_maybe("model", shape[-2], tp),
                        _maybe("data", shape[-1], fsdp))
        # fallback: replicate
        return P(*((None,) * len(shape)))

    return jax.tree_util.tree_map_with_path(rule, params_tree)


def batch_pspecs(cfg: ModelConfig, mesh: Mesh, shape: ShapeSpec,
                 smart: bool = False) -> dict[str, P]:
    """PartitionSpecs for the input batch dict (keys from data.synth).

    ``smart``: when the preferred batch axes don't divide the global batch,
    fall back through smaller axis subsets instead of replicating (the
    pure-DP decode fix measured in EXPERIMENTS.md §Perf)."""
    b = shape.global_batch
    dp = dp_axes(mesh, cfg.pure_dp)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    bspec = dp if b % dp_size == 0 else (None,)
    if smart and bspec == (None,):
        for cand in (dp, dp[:-1], dp[:1], ("data",)):
            size = 1
            for a in cand:
                size *= mesh.shape[a]
            if cand and b % size == 0:
                bspec = cand
                break

    from repro.data.synth import batch_shapes
    shapes = batch_shapes(cfg, b, shape.seq_len)
    out = {}
    for name, (shp, _) in shapes.items():
        rest = (None,) * (len(shp) - 1)
        out[name] = P(bspec if len(bspec) > 1 or bspec[0] else None, *rest)
    return out


def cache_pspecs(cfg: ModelConfig, mesh: Mesh, batch: int,
                 smart: bool = False) -> Any:
    """PartitionSpecs for the decode cache (mirrors Model.init_cache).

    KV: batch -> data when divisible, sequence -> model (GSPMD then derives
    flash-decoding partial-softmax collectives). Batch-1 long-context decode
    shards the sequence over (data, model). SSM state: heads -> model.
    """
    tp = mesh.shape["model"]
    dsz = mesh.shape["data"]
    bat = "data" if (batch % dsz == 0 and batch > 1 and not cfg.pure_dp) else None
    seq_axes = "model" if bat == "data" else ("data", "model")
    if cfg.pure_dp:
        bat = ("data", "model") if batch % (tp * dsz) == 0 else None
        seq_axes = None
        if smart and bat is None and batch % dsz == 0:
            # pure-DP fallback fix: batch over data, KV sequence over model
            # (flash-decoding-style partial softmax) instead of replicating
            bat, seq_axes = "data", "model"

    from repro.models.attention import KVCache
    from repro.models.ssm import SSMState

    cache = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.mixer == "ssm":
            h = cfg.ssm.n_heads(cfg.d_model)
            cache[f"pos{i}"] = SSMState(
                conv_x=P(None, bat, None, _maybe("model", cfg.ssm.d_inner(cfg.d_model), tp)),
                conv_bc=P(None, bat, None, None),
                ssd=P(None, bat, _maybe("model", h, tp), None, None))
        else:
            kv = P(None, bat, seq_axes, None, None)
            cache[f"pos{i}"] = KVCache(k=kv, v=kv)
    return cache
