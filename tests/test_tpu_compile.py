"""The sweep path's scan programs compile for a TPU v5e at full size.

Nothing runs: each test lowers one program of the default ``backend="scan"``
at the suites' real shapes (32 traces x 8,000 requests, 8 banks x 8
subarrays; four 4-core mixes) for a described, not attached, v5e chip and
has the TPU compiler build it. What the compiler refuses here would fail on
the chip. The topology is described inside a module fixture, never at
import, so every test worker collects the same tests and only the one that
runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dram import Policy, Scheduler, SimConfig, controller
from repro.core.dram.engine import _controller_args

B, N = 32, 8000          # the Fig. 4 grid's bucket: 32 workloads x 8,000
M, C = 4, 4              # benchmarks.multicore_bench: four 4-core mixes
HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off: a
    compile for a described chip is written to it but cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _requests(sharding, *lead):
    """Shapes of the six request fields + mlp_window, batch dims ``lead``."""
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=sharding)
    b1 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bool_,
                           sharding=sharding)
    shape = (*lead, N)
    return ([i32(shape), i32(shape), i32(shape), b1(shape), i32(shape),
             b1(shape)], i32(lead))


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < HBM_BYTES, used


@pytest.mark.parametrize("policy", [Policy.BASELINE, Policy.MASA],
                         ids=lambda p: p.name)
def test_lanes_scan_compiles(one_chip, policy):
    """Phase A's program: the lane-batched scan (refresh off, open rows)."""
    cfg = SimConfig()
    eff, _, nb, ns = _controller_args(policy, cfg)
    fields, mlp = _requests(one_chip, B)
    compiled = controller._simulate_stacked_lanes.lower(
        eff, nb, ns, cfg.timing, *fields, mlp, mlp_static=8).compile()
    _fits(compiled)


@pytest.mark.parametrize("policy", [Policy.BASELINE, Policy.MASA],
                         ids=lambda p: p.name)
def test_lanes_scan_with_darp_compiles(one_chip, policy):
    """Phase B's program: the lane-batched scan with DARP at 8 Gb,
    per-lane ``mlp_window``s."""
    cfg = SimConfig.for_tech("ddr3", density_gb=8, refresh_policy="darp")
    eff, _, nb, ns = _controller_args(policy, cfg)
    fields, mlp = _requests(one_chip, B)
    compiled = controller._simulate_stacked_lanes.lower(
        eff, nb, ns, cfg.timing, *fields, mlp,
        refresh_mode=cfg.refresh_mode).compile()
    _fits(compiled)


def test_vmapped_refresh_controller_compiles(one_chip):
    """The per-trace controller scan with DARP refresh, vmapped over the
    batch as ``engine.simulate_stacked`` still runs closed-row and
    command-export batches."""
    cfg = SimConfig.for_tech("ddr3", density_gb=8, refresh_policy="darp")
    eff, sched, nb, ns = _controller_args(Policy.MASA, cfg)
    fn = functools.partial(controller._simulate_controller, eff, sched, nb,
                           ns, cfg.timing, cfg.refresh_mode)

    def one(b, s, r, w, g, d, m):
        res, _ = fn(b[None], s[None], r[None], w[None], g[None], d[None],
                    m[None], jnp.zeros((1,), jnp.int32))
        return res

    fields, mlp = _requests(one_chip, B)
    _fits(jax.jit(jax.vmap(one)).lower(*fields, mlp).compile())


def test_multicore_controller_compiles(one_chip):
    """The per-mix C-core controller (scheduler argmin) vmapped over the
    mixes: the program the lane-major mix scan is checked against."""
    cfg = SimConfig(scheduler=Scheduler.FRFCFS)
    eff, sched, nb, ns = _controller_args(Policy.MASA, cfg)
    fields, mlp = _requests(one_chip, M, C)
    rank = jax.ShapeDtypeStruct((M, C), jnp.int32, sharding=one_chip)
    fn = jax.jit(jax.vmap(functools.partial(
        controller._simulate_controller, eff, sched, nb, ns, cfg.timing,
        cfg.refresh_mode)))
    _fits(fn.lower(*fields, mlp, rank).compile())


@pytest.mark.parametrize("scheduler", [Scheduler.FRFCFS, Scheduler.TCM],
                         ids=lambda s: s.name)
def test_mixes_lanes_compiles(one_chip, scheduler):
    """Phase C's program: the lane-major C-core scan over four 4-core mixes
    of 8,000 requests a core, as ``multicore.simulate_multicore_batch``
    runs the mixes cell's buckets."""
    cfg = SimConfig(scheduler=scheduler)
    eff, sched, nb, ns = _controller_args(Policy.MASA, cfg)
    fields, mlp = _requests(one_chip, M, C)
    rank = jax.ShapeDtypeStruct((M, C), jnp.int32, sharding=one_chip)
    compiled = controller._simulate_mixes_lanes.lower(
        eff, sched, nb, ns, cfg.timing, cfg.refresh_mode, *fields, mlp,
        rank).compile()
    _fits(compiled)
