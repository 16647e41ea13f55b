"""Quickstart: the three layers of SALP-JAX in one script.

  PYTHONPATH=src python examples/quickstart.py

1. Layer A — run the DRAM simulator on one workload under all policies
   (the paper's mechanisms) and print the IPC ladder.
2. Layer B — call a SALP-mapped Pallas kernel (grouped expert GEMM with
   SA_SEL-style designation) and check it against the oracle.
3. Layer C — one reduced-model train step + one serving decode with the
   SALP-aware scheduler.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.core.dram import (PAPER_WORKLOADS, ROW_SPACE_STRIDE, Policy, Scheduler,
                             SimConfig, generate_trace, simulate, summarize,
                             workload)
from repro.core.dram.multicore import simulate_multicore
from repro.data.synth import make_batch
from repro.kernels.moe_gemm.ops import capacity_block_eids, grouped_matmul
from repro.kernels.moe_gemm.ref import grouped_matmul_ref
from repro.models import build_model
from repro.train.optimizer import make_optimizer
from repro.train.step import make_train_step


def layer_a_dram():
    print("=== Layer A: SALP DRAM simulator (the paper's mechanisms) ===")
    prof = workload("lbm")
    trace = generate_trace(prof, 4000, seed=7)
    base = None
    for pol in (Policy.BASELINE, Policy.SALP1, Policy.SALP2, Policy.MASA,
                Policy.IDEAL):
        s = summarize(simulate(trace, pol), prof)
        base = base or s["ipc"]
        print(f"  {pol.pretty:10s} IPC={s['ipc']:.3f} (+{100*(s['ipc']/base-1):5.1f}%) "
              f"row-hit={s['row_hit_rate']:.2f} energy={s['dynamic_nj']:.0f}nJ")

    # Multi-core: the SAME controller with 4 cores, scheduler from SimConfig
    # (the paper's Sec. 4 combination: SALP x request scheduling, refresh on).
    names = ("mcf", "lbm", "soplex", "sphinx3")
    mix = [generate_trace(workload(n), 1000, seed=7,
                          row_space_offset=ROW_SPACE_STRIDE * i)
           for i, n in enumerate(names)]
    print(f"  4-core mix {'+'.join(names)} (refresh on):")
    for sched in (Scheduler.FCFS, Scheduler.FRFCFS, Scheduler.TCM):
        cfg = SimConfig(scheduler=sched, refresh=True)
        ws = simulate_multicore(mix, Policy.MASA, cfg).weighted_speedup
        print(f"    MASA + {sched.pretty:12s} weighted speedup = {ws:.2f}")


def layer_b_kernel():
    print("=== Layer B: MASA designation kernel (grouped expert GEMM) ===")
    E, C, D, F = 4, 128, 64, 128
    x = jax.random.normal(jax.random.key(0), (E * C, D))
    w = jax.random.normal(jax.random.key(1), (E, D, F)) * 0.1
    eids = capacity_block_eids(E, C, bt=64)
    y = grouped_matmul(x, w, eids, bt=64, bf=128)
    err = float(jnp.max(jnp.abs(y - grouped_matmul_ref(x, w, eids, 64))))
    print(f"  kernel vs oracle max|err| = {err:.2e} "
          f"({len(eids)} blocks, {E} experts: consecutive same-expert blocks "
          f"are row-buffer hits)")


def layer_c_train_and_serve():
    print("=== Layer C: reduced train step + SALP-aware serving ===")
    cfg = get_config("phi3-mini-3.8b").reduced(64)
    model = build_model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    opt = make_optimizer("adamw", lr=1e-3)
    step = jax.jit(make_train_step(model, opt))
    state = opt.init(params)
    batch = make_batch(cfg, 4, 32, dtype=jnp.float32)
    for i in range(3):
        params, state, metrics = step(params, state, batch, jnp.int32(i))
        print(f"  train step {i}: loss={float(metrics['loss']):.3f}")

    from repro.serve.engine import ServingEngine
    eng = ServingEngine(model, params, max_batch=4, n_pages=256, page_size=8)
    rng = np.random.default_rng(0)
    for rid in range(6):
        eng.submit(rid, rng.integers(0, 500, 24).tolist(), 8,
                   shared_prefix_of=rid - 1 if rid % 2 else None)
    stats = eng.run()
    print(f"  served {stats.tokens} tokens; SALP-scheduled page cost vs FIFO: "
          f"-{100*stats.cost_reduction:.1f}%")


if __name__ == "__main__":
    compile_cache.enable()
    layer_a_dram()
    layer_b_kernel()
    layer_c_train_and_serve()
