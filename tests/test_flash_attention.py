"""flash_attention kernel: allclose sweeps vs the dense oracle + consistency
with the model's naive attention path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_kernel
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref

TOLS = {jnp.float32: dict(rtol=2e-4, atol=2e-4),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}

# The whole module exercises a seed Pallas kernel (interpret mode on CPU).
# The ``seed_kernel`` marker stays for suite selection.
pytestmark = pytest.mark.seed_kernel


@pytest.mark.parametrize("bh,s,hd,bq,bk", [
    (2, 256, 64, 128, 128), (4, 512, 64, 128, 128),
    (1, 256, 128, 128, 64), (3, 384, 64, 128, 128)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_sweep(bh, s, hd, bq, bk, causal, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (bh, s, hd), dtype)
    k = jax.random.normal(ks[1], (bh, s, hd), dtype)
    v = jax.random.normal(ks[2], (bh, s, hd), dtype)
    out = flash_attention_kernel(q, k, v, causal=causal, bq=bq, bk=bk,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = TOLS[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_gqa_wrapper_matches_model_attention():
    """flash_attention == the model's naive attention core (GQA, causal)."""
    from repro.configs.base import AttnConfig
    from repro.models import attention as attn_mod

    b, s, h, hkv, hd = 2, 256, 4, 2, 64
    cfg = AttnConfig(n_heads=h, n_kv_heads=hkv, head_dim=hd)
    p = attn_mod.init_attention(jax.random.key(0), 32, cfg)
    x = jax.random.normal(jax.random.key(1), (b, s, 32), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = attn_mod._qkv(p, x, cfg, positions)

    out_flash = flash_attention(q, k, v, causal=True)
    ke = attn_mod._expand_kv(k, h // hkv)
    ve = attn_mod._expand_kv(v, h // hkv)
    scores = jnp.einsum("bqhk,bshk->bhqs", q, ke) * hd ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -1e9)
    probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
    out_ref = jnp.einsum("bhqs,bshk->bqhk", probs, ve)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref),
                               rtol=2e-4, atol=2e-4)


def test_analytic_hbm_traffic_reduction():
    """The point of the kernel: attention HBM traffic O(S^2) -> O(S.hd).

    granite prefill_32k per chip (2 batch x 3 local q heads): naive
    materializes >= 2 passes over bf16 scores; fused touches Q,K,V,O once."""
    s, hd, heads_local, batch_local = 32768, 128, 3, 2
    bh = heads_local * batch_local
    naive_scores = 2 * bh * s * s * 2            # write + read, bf16
    fused_io = 4 * bh * s * hd * 2               # Q,K,V read + O write
    assert naive_scores / fused_io > 100          # >100x less attention traffic
