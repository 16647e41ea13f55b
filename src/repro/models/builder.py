"""Model assembly: config -> (init, forward, loss, prefill, decode_step).

Layers are grouped into repeated *blocks* (the config's layer pattern);
parameters carry a leading ``[n_repeats, ...]`` axis and the forward pass scans
over it, so compiled HLO is O(pattern size), not O(depth) — required to keep
the 88-layer / 779 B-parameter dry-runs compilable.

Batch conventions (see data/synth.py batch_shapes):
  text LM:  {"tokens": [B,S] int32, "labels": [B,S] int32}
  vlm:      {"patch_embeds": [B,P,D] bf16, "tokens": [B,S-P], "labels": [B,S]}
  audio enc-dec: {"enc_embeds": [B,Se,D] bf16, "tokens": [B,Sd], "labels": [B,Sd]}
Labels < 0 are masked from the loss (e.g. modality positions).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.attention import KVCache
from repro.models.layers import embed, init_embedding, init_mlp, init_rmsnorm, mlp, rmsnorm, unembed
from repro.models.ssm import SSMState

Params = dict


def _noncausal(cfg):
    return dataclasses.replace(cfg, causal=False)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    pad_heads: int = 0             # TP head padding (DESIGN.md Sec. 5)
    attn_impl: str = "naive"       # "naive" | "chunked"
    dtype: Any = jnp.bfloat16
    # Megatron-style sequence parallelism: PartitionSpec applied to the
    # scan carry at block boundaries, so the activations saved by remat are
    # sequence-sharded over the model axis (required to fit the 104 B/779 B
    # train cells in 16 GB HBM; DESIGN.md Sec. 5). None = no constraint.
    carry_spec: Any = None
    # Fully unroll the layer scans (used by the dry-run's FLOP-measurement
    # compiles: XLA cost analysis counts a while body once, unrolling makes
    # the count exact at small n_repeats).
    scan_unroll: bool = False
    # GQA decode without KV expansion (keeps the cache sequence-sharded under
    # GSPMD; see attention.decode_attention and EXPERIMENTS.md §Perf).
    decode_grouped: bool = False

    # ------------------------------------------------------------- init
    def init(self, key) -> Params:
        cfg = self.cfg
        keys = jax.random.split(key, 8)

        def init_pos(spec: LayerSpec, k):
            ks = jax.random.split(k, 4)
            p: dict = {"mixer_norm": init_rmsnorm(cfg.d_model)}
            if spec.mixer in ("attn", "cross"):
                p["mixer"] = attn_mod.init_attention(ks[0], cfg.d_model, cfg.attn,
                                                     self.pad_heads)
            else:
                p["mixer"] = ssm_mod.init_ssm(ks[0], cfg.d_model, cfg.ssm)
            if spec.ffn != "none":
                p["ffn_norm"] = init_rmsnorm(cfg.d_model)
                if spec.ffn == "dense":
                    p["ffn"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_glu)
                else:
                    p["ffn"] = moe_mod.init_moe(ks[1], cfg.d_model, cfg.moe, cfg.mlp_glu)
            return p

        def init_stack(pattern, repeats, k):
            blocks = {}
            for i, spec in enumerate(pattern):
                pos_keys = jax.random.split(jax.random.fold_in(k, i), repeats)
                blocks[f"pos{i}"] = jax.vmap(functools.partial(init_pos, spec))(pos_keys)
            return blocks

        params: Params = {
            "embed": init_embedding(keys[0], cfg.padded_vocab, cfg.d_model),
            "dec": init_stack(cfg.pattern, cfg.n_repeats, keys[1]),
            "final_norm": init_rmsnorm(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = init_embedding(keys[2], cfg.padded_vocab, cfg.d_model)
        if cfg.encoder_decoder:
            params["enc"] = init_stack(cfg.enc_pattern, cfg.enc_repeats, keys[3])
            params["enc_norm"] = init_rmsnorm(cfg.d_model)
        return params

    # ------------------------------------------------------------- pieces
    def _mixer(self, spec: LayerSpec, p, x, *, enc=None, mode="train"):
        cfg = self.cfg
        if spec.mixer == "ssm":
            return ssm_mod.ssm_forward(p, x, cfg.d_model, cfg.ssm), None
        if spec.mixer == "cross":
            return attn_mod.cross_attention(p, x, enc, _noncausal(cfg.attn)), None
        acfg = cfg.attn if mode != "encoder" else _noncausal(cfg.attn)
        return attn_mod.attention(p, x, acfg, impl=self.attn_impl,
                                  unroll=self.scan_unroll), None

    def _ffn(self, spec: LayerSpec, p, x):
        cfg = self.cfg
        if spec.ffn == "none":
            return x * 0, jnp.float32(0)
        if spec.ffn == "dense":
            return mlp(p, x, cfg.act, cfg.mlp_glu), jnp.float32(0)
        return moe_mod.moe_ffn(p, x, cfg.moe, cfg.act, cfg.mlp_glu)

    def _block(self, pattern, bp, x, *, enc=None, mode="train"):
        """Apply one block (all pattern positions). Returns (x, aux)."""
        aux = jnp.float32(0)
        for i, spec in enumerate(pattern):
            p = bp[f"pos{i}"]
            h, _ = self._mixer(spec, p["mixer"], rmsnorm(p["mixer_norm"], x,
                                                         self.cfg.norm_eps),
                               enc=enc, mode=mode)
            x = x + h
            if spec.ffn != "none":
                h, a = self._ffn(spec, p["ffn"], rmsnorm(p["ffn_norm"], x,
                                                         self.cfg.norm_eps))
                x = x + h
                aux = aux + a
        return x, aux

    def _scan_stack(self, pattern, stack, x, *, enc=None, mode="train"):
        cfg = self.cfg

        def body(carry, bp):
            x, aux = carry
            if self.carry_spec is not None:
                x = jax.lax.with_sharding_constraint(x, self.carry_spec)
            x, a = self._block(pattern, bp, x, enc=enc, mode=mode)
            if self.carry_spec is not None:
                x = jax.lax.with_sharding_constraint(x, self.carry_spec)
            return (x, aux + a), None

        if mode == "train" and cfg.remat != "none":
            policy = (jax.checkpoint_policies.dots_saveable
                      if cfg.remat == "dots" else None)
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        (x, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)), stack,
                                   unroll=True if self.scan_unroll else 1)
        return x, aux

    # ------------------------------------------------------------- embed in
    def _embed_inputs(self, params, batch) -> jax.Array:
        cfg = self.cfg
        if cfg.encoder_decoder:
            return embed(params["embed"], batch["tokens"], self.dtype)
        if cfg.modality is not None:
            txt = embed(params["embed"], batch["tokens"], self.dtype)
            return jnp.concatenate([batch["patch_embeds"].astype(self.dtype), txt], axis=1)
        return embed(params["embed"], batch["tokens"], self.dtype)

    def _encode(self, params, batch):
        enc = batch["enc_embeds"].astype(self.dtype)
        enc, _ = self._scan_stack(self.cfg.enc_pattern, params["enc"], enc,
                                  mode="encoder")
        return rmsnorm(params["enc_norm"], enc, self.cfg.norm_eps)

    def _logits(self, params, x):
        head = params.get("lm_head", params["embed"])
        return unembed(head, x, self.cfg.vocab_size)

    # ------------------------------------------------------------- train
    def forward(self, params, batch) -> tuple[jax.Array, jax.Array]:
        """Full-sequence forward -> (logits [B,S,Vpad], aux_loss)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        enc = self._encode(params, batch) if cfg.encoder_decoder else None
        x, aux = self._scan_stack(cfg.pattern, params["dec"], x, enc=enc, mode="train")
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return self._logits(params, x), aux

    def loss(self, params, batch) -> tuple[jax.Array, dict]:
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        # next-token prediction: logits[t] predicts labels[t]
        valid = labels >= 0
        safe = jnp.maximum(labels, 0)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        ce = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1)
        return ce + aux, {"ce": ce, "aux": aux}

    # ------------------------------------------------------------- prefill
    def prefill(self, params, batch):
        """Prefill -> (last-position logits, cache pytree)."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        enc = self._encode(params, batch) if cfg.encoder_decoder else None

        def body(carry, bp):
            x, = carry
            cache_block = {}
            for i, spec in enumerate(cfg.pattern):
                p = bp[f"pos{i}"]
                xin = rmsnorm(p["mixer_norm"], x, cfg.norm_eps)
                if spec.mixer == "ssm":
                    h, st = ssm_mod.ssm_forward(p["mixer"], xin, cfg.d_model,
                                                cfg.ssm, return_state=True)
                    cache_block[f"pos{i}"] = st
                elif spec.mixer == "cross":
                    h = attn_mod.cross_attention(p["mixer"], xin, enc,
                                                 _noncausal(cfg.attn))
                    cache_block[f"pos{i}"] = _cross_kv(p["mixer"], enc, self.dtype)
                else:
                    h, kv = attn_mod.prefill_attention(p["mixer"], xin, cfg.attn,
                                                       impl=self.attn_impl,
                                                       unroll=self.scan_unroll)
                    cache_block[f"pos{i}"] = kv
                x = x + h
                if spec.ffn != "none":
                    h, _ = self._ffn(spec, p["ffn"], rmsnorm(p["ffn_norm"], x,
                                                             cfg.norm_eps))
                    x = x + h
            return (x,), cache_block

        (x,), cache = jax.lax.scan(body, (x,), params["dec"],
                                   unroll=True if self.scan_unroll else 1)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return self._logits(params, x[:, -1:]), cache

    # ------------------------------------------------------------- decode
    def decode_step(self, params, tokens, cache, cur_len):
        """One-token decode. tokens [B,1]; cache from prefill/init_cache;
        cur_len: current sequence length (int32 scalar or [B])."""
        cfg = self.cfg
        x = embed(params["embed"], tokens, self.dtype)

        def body(carry, xs):
            x, = carry
            bp, cache_block = xs
            new_block = {}
            for i, spec in enumerate(cfg.pattern):
                p = bp[f"pos{i}"]
                c = cache_block[f"pos{i}"]
                xin = rmsnorm(p["mixer_norm"], x, cfg.norm_eps)
                if spec.mixer == "ssm":
                    h, st = ssm_mod.ssm_decode(p["mixer"], xin, c, cfg.d_model, cfg.ssm)
                    new_block[f"pos{i}"] = st
                elif spec.mixer == "cross":
                    h = _cross_decode(p["mixer"], xin, c, _noncausal(cfg.attn))
                    new_block[f"pos{i}"] = c
                else:
                    h, kv = attn_mod.decode_attention(p["mixer"], xin, cfg.attn,
                                                      c, cur_len,
                                                      grouped=self.decode_grouped)
                    new_block[f"pos{i}"] = kv
                x = x + h
                if spec.ffn != "none":
                    h, _ = self._ffn(spec, p["ffn"], rmsnorm(p["ffn_norm"], x,
                                                             cfg.norm_eps))
                    x = x + h
            return (x,), new_block

        (x,), new_cache = jax.lax.scan(body, (x,), (params["dec"], cache),
                                       unroll=True if self.scan_unroll else 1)
        x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
        return self._logits(params, x), new_cache

    # ------------------------------------------------------------- cache init
    def init_cache(self, batch_size: int, max_len: int, *, enc_len: int = 0) -> Any:
        """Zero-filled cache for decode-only dry-runs (shape-faithful)."""
        cfg = self.cfg
        r = cfg.n_repeats

        def zeros(*shape, dt=self.dtype):
            return jnp.zeros((r, *shape), dt)

        cache = {}
        for i, spec in enumerate(cfg.pattern):
            if spec.mixer == "ssm":
                s = cfg.ssm
                cache[f"pos{i}"] = SSMState(
                    conv_x=zeros(batch_size, s.d_conv - 1, s.d_inner(cfg.d_model)),
                    conv_bc=zeros(batch_size, s.d_conv - 1, 2 * s.n_groups * s.d_state),
                    ssd=zeros(batch_size, s.n_heads(cfg.d_model), s.d_state,
                              s.head_dim, dt=jnp.float32))
            elif spec.mixer == "cross":
                a = cfg.attn
                cache[f"pos{i}"] = KVCache(
                    k=zeros(batch_size, enc_len, a.n_kv_heads, a.head_dim),
                    v=zeros(batch_size, enc_len, a.n_kv_heads, a.head_dim))
            else:
                a = cfg.attn
                cache[f"pos{i}"] = KVCache(
                    k=zeros(batch_size, max_len, a.n_kv_heads, a.head_dim),
                    v=zeros(batch_size, max_len, a.n_kv_heads, a.head_dim))
        return cache


def _cross_kv(p, enc, dtype) -> KVCache:
    k = jnp.einsum("bsd,dhk->bshk", enc, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", enc, p["wv"].astype(dtype))
    return KVCache(k=k, v=v)


def _cross_decode(p, x, kv: KVCache, cfg) -> jax.Array:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    groups = q.shape[2] // cfg.n_kv_heads
    ke = jnp.repeat(kv.k, groups, axis=2)
    ve = jnp.repeat(kv.v, groups, axis=2)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, ke) * (cfg.head_dim ** -0.5)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
    out = jnp.einsum("bhqs,bshk->bqhk", probs, ve)
    return jnp.einsum("bqhk,hkd->bqd", out, p["wo"].astype(dt))


def build_model(cfg: ModelConfig, *, pad_heads: int = 0,
                attn_impl: str = "naive", dtype=jnp.bfloat16,
                carry_spec: Any = None, scan_unroll: bool = False,
                decode_grouped: bool = False) -> Model:
    return Model(cfg=cfg, pad_heads=pad_heads, attn_impl=attn_impl, dtype=dtype,
                 carry_spec=carry_spec, scan_unroll=scan_unroll,
                 decode_grouped=decode_grouped)
