"""Synthetic batches: the single source of truth for per-family input shapes.

``batch_shapes(cfg, B, S)`` returns {name: (shape, dtype)} — used by the
data pipeline (real arrays). Conventions (DESIGN.md Sec. 6):

  text LM        tokens/labels [B, S]
  vlm            patch_embeds [B, P, D] + tokens [B, S-P] + labels [B, S]
                 (P = cfg.modality_tokens, capped at S//2)
  audio enc-dec  enc_embeds [B, S, D] + tokens/labels [B, S//4]
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def batch_shapes(cfg: ModelConfig, batch: int, seq: int,
                 dtype=jnp.bfloat16) -> dict[str, tuple[tuple[int, ...], Any]]:
    if cfg.encoder_decoder:
        dec = max(seq // 4, 8)
        return {
            "enc_embeds": ((batch, seq, cfg.d_model), dtype),
            "tokens": ((batch, dec), jnp.int32),
            "labels": ((batch, dec), jnp.int32),
        }
    if cfg.modality is not None:
        p = min(cfg.modality_tokens, seq // 2)
        return {
            "patch_embeds": ((batch, p, cfg.d_model), dtype),
            "tokens": ((batch, seq - p), jnp.int32),
            "labels": ((batch, seq), jnp.int32),
        }
    return {
        "tokens": ((batch, seq), jnp.int32),
        "labels": ((batch, seq), jnp.int32),
    }


def make_batch(cfg: ModelConfig, batch: int, seq: int, seed: int = 0,
               dtype=jnp.bfloat16) -> dict[str, jax.Array]:
    """Deterministic synthetic batch with a learnable structure (a noisy
    periodic token process — losses drop quickly, which the training tests
    assert)."""
    rng = np.random.default_rng(seed)
    shapes = batch_shapes(cfg, batch, seq, dtype)
    out: dict[str, jax.Array] = {}

    def tokens_like(shape):
        b, s = shape
        base = (np.arange(s)[None, :] * 7 + rng.integers(0, 13, (b, 1))) % min(
            cfg.vocab_size, 1024)
        noise = rng.integers(0, cfg.vocab_size, (b, s))
        take_noise = rng.random((b, s)) < 0.1
        return np.where(take_noise, noise, base).astype(np.int32)

    for name, (shape, dt) in shapes.items():
        if name in ("tokens",):
            out[name] = jnp.asarray(tokens_like(shape))
        elif name == "labels":
            pass  # filled below from tokens
        else:  # embeddings stubs
            out[name] = jnp.asarray(
                rng.standard_normal(shape, dtype=np.float32) * 0.02, dtype=dt)

    # labels: next-token shift of the text stream; modality positions masked
    toks = np.asarray(out["tokens"])
    nxt = np.concatenate([toks[:, 1:], toks[:, :1]], axis=1).astype(np.int32)
    lab_shape = shapes["labels"][0]
    if lab_shape[1] != toks.shape[1]:  # vlm: prepend masked modality positions
        pad = -np.ones((lab_shape[0], lab_shape[1] - toks.shape[1]), np.int32)
        nxt = np.concatenate([pad, nxt], axis=1)
    out["labels"] = jnp.asarray(nxt)
    return out
