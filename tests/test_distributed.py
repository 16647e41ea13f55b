"""Distribution tests. Multi-device cases run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main pytest session
keeps a single device (per the project's dry-run isolation rule)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

# Seed distributed stack, written against the installed JAX API
# (``jax.make_mesh(axis_types=)``, ``jax.shard_map``, ``jax.lax.axis_size``).
# The ``seed_kernel`` marker stays for suite selection.
pytestmark = pytest.mark.seed_kernel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """The pjit'd train step on a 2x4 mesh must produce the same loss and
    updated params as the single-device run (GSPMD correctness)."""
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.data.synth import make_batch
        from repro.distributed.sharding import batch_pspecs, param_pspecs
        from repro.models import build_model
        from repro.train import make_optimizer, make_train_step
        from repro.configs.base import ShapeSpec

        cfg = get_config("phi3-mini-3.8b").reduced(64)
        model = build_model(cfg, dtype=jnp.float32)
        opt = make_optimizer("adamw", lr=1e-3)
        step = make_train_step(model, opt)
        params = model.init(jax.random.key(0))
        state = opt.init(params)
        batch = make_batch(cfg, 8, 32, dtype=jnp.float32)

        # single device reference
        p1, s1, m1 = jax.jit(step)(params, state, batch, jnp.int32(0))

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        pspecs = param_pspecs(cfg, params, mesh)
        shard = lambda t, s: jax.device_put(t, NamedSharding(mesh, s))
        params_sh = jax.tree.map(shard, params, pspecs)
        state_sh = {"m": jax.tree.map(shard, state["m"], pspecs),
                    "v": jax.tree.map(shard, state["v"], pspecs)}
        bspecs = batch_pspecs(cfg, mesh, ShapeSpec("t", 32, 8, "train"))
        batch_sh = {k: shard(v, bspecs[k]) for k, v in batch.items()}
        with mesh:
            p2, s2, m2 = jax.jit(step)(params_sh, state_sh, batch_sh, jnp.int32(0))

        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-4)
        print("SHARDED==SINGLE OK")
    """)


def test_collective_matmul_matches_reference():
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.collective_matmul import collective_matmul
        mesh = jax.make_mesh((8,), ("model",),
                             axis_types=(AxisType.Auto,))
        x = jax.random.normal(jax.random.key(0), (64, 32), jnp.float32)
        w = jax.random.normal(jax.random.key(1), (32, 48), jnp.float32)
        y = collective_matmul(x, w, mesh, axis="model")
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   rtol=2e-4, atol=2e-4)
        print("COLLECTIVE MATMUL OK")
    """)


def test_int8_ring_allreduce_and_error_feedback():
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.distributed.compression import compressed_mean, ef_compress_update
        mesh = jax.make_mesh((8,), ("pod",),
                             axis_types=(AxisType.Auto,))
        x = jax.random.normal(jax.random.key(0), (8, 1024), jnp.float32)
        out = compressed_mean(x, mesh, axis="pod")
        want = jnp.broadcast_to(x.mean(0), (8, 1024))
        # int8 quantization error is bounded by a few quant steps per hop
        scale = float(jnp.abs(x).max()) / 127
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=16 * scale)

        # error feedback: the running average of compressed means converges to
        # the true mean (EF re-injects quantization residuals)
        grads = {"w": x}
        residual = {"w": jnp.zeros_like(x)}
        acc = jnp.zeros((8, 1024))
        for _ in range(30):
            synced, residual = ef_compress_update(grads, residual, mesh, "pod")
            acc = acc + synced["w"]
        np.testing.assert_allclose(np.asarray(acc / 30), np.asarray(want),
                                   atol=2 * scale)
        print("COMPRESSION OK")
    """)


def test_decode_sharded_equals_single():
    """Flash-decoding style seq-sharded KV decode == single-device decode."""
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.data.synth import make_batch
        from repro.distributed.sharding import cache_pspecs, param_pspecs
        from repro.models import build_model

        cfg = get_config("phi3-mini-3.8b").reduced(64)
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))
        batch = make_batch(cfg, 4, 16, dtype=jnp.float32)
        _, cache = jax.jit(model.prefill)(params, batch)
        cache = jax.tree.map(
            lambda a: jnp.pad(a, [(0,0),(0,0),(0,16)] + [(0,0)]*(a.ndim-3))
            if a.ndim >= 4 else a, cache)
        tok = jnp.full((4, 1), 7, jnp.int32)
        ref, _ = jax.jit(model.decode_step)(params, tok, cache, jnp.int32(16))

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        pspecs = param_pspecs(cfg, params, mesh)
        cspecs = cache_pspecs(cfg, mesh, batch=4)
        shard = lambda t, s: jax.device_put(t, NamedSharding(mesh, s))
        params_sh = jax.tree.map(shard, params, pspecs)
        cache_sh = jax.tree.map(shard, cache, cspecs)
        with mesh:
            out, _ = jax.jit(model.decode_step)(
                params_sh, shard(tok, P("data", None)), cache_sh, jnp.int32(16))
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)
        print("SHARDED DECODE OK")
    """)


def test_param_pspecs_cover_all_archs():
    """Every arch's param tree gets a valid spec (single process, no devices)."""
    import jax
    from jax.sharding import AxisType
    from repro.configs import get_config, list_archs
    from repro.distributed.sharding import param_pspecs
    from repro.models import build_model

    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    for arch in list_archs():
        cfg = get_config(arch)
        model = build_model(cfg.reduced())
        ps = jax.eval_shape(lambda m=model: m.init(jax.random.key(0)))
        specs = param_pspecs(cfg, ps, mesh)
        n_leaves = len(jax.tree.leaves(ps))
        n_specs = len(jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))
        assert n_specs == n_leaves, arch


def test_elastic_restore_across_meshes():
    """Checkpoint written under a 2x4 mesh restores onto a 4x2 mesh (elastic
    re-shard after losing/regaining capacity): logical state is identical."""
    run_subprocess("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.store import load_checkpoint, save_checkpoint
        from repro.configs import get_config
        from repro.distributed.sharding import param_pspecs
        from repro.models import build_model

        cfg = get_config("phi3-mini-3.8b").reduced(64)
        model = build_model(cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0))

        mesh_a = jax.make_mesh((2, 4), ("data", "model"),
                               axis_types=(AxisType.Auto,) * 2)
        specs = param_pspecs(cfg, params, mesh_a)
        sharded = jax.tree.map(
            lambda t, s: jax.device_put(t, NamedSharding(mesh_a, s)),
            params, specs)

        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 3, sharded, pspecs=specs)
            step, restored, _ = load_checkpoint(d, template=params)
            assert step == 3
            # re-shard onto a DIFFERENT mesh
            mesh_b = jax.make_mesh((4, 2), ("data", "model"),
                                   axis_types=(AxisType.Auto,) * 2)
            specs_b = param_pspecs(cfg, params, mesh_b)
            resharded = jax.tree.map(
                lambda t, s: jax.device_put(t, NamedSharding(mesh_b, s)),
                restored, specs_b)
            for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(resharded)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC RESTORE OK")
    """)
