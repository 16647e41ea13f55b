"""Serving demo: continuous batching with the SALP-aware scheduler vs FIFO.

  PYTHONPATH=src python examples/serve_salp.py

Submits a workload with shared prefixes (the MASA residency case) and compares
the page-access cost of the SALP-aware order against FIFO under each paper
policy's cost model — the serving-layer analogue of Figure 4 — then verifies
generated tokens are identical regardless of schedule (scheduling must never
change results).
"""
import jax
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.core.dram.policies import Policy
from repro.models import build_model
from repro.serve.engine import ServingEngine


def run_policy(policy: Policy, params, model, seed: int = 0):
    # interleave_pages=False: sequential page allocation clusters banks —
    # the high-conflict regime where scheduling matters (cf. serving_bench)
    eng = ServingEngine(model, params, max_batch=10, n_pages=512, page_size=8,
                        policy=policy, interleave_pages=False)
    rng = np.random.default_rng(seed)
    for rid in range(10):
        prompt = rng.integers(0, 500, 32).tolist()
        share = rid - 1 if rid % 2 == 1 else None   # half the load shares prefixes
        eng.submit(rid, prompt, 12, shared_prefix_of=share)
    stats = eng.run()
    outs = [tuple(eng.output(r)) for r in range(10)]
    return stats, outs


def main() -> None:
    compile_cache.enable()
    cfg = get_config("phi3-mini-3.8b").reduced(64)
    model = build_model(cfg, dtype=jax.numpy.float32)
    params = model.init(jax.random.key(0))

    ref_outs = None
    print(f"{'policy':10s} {'tokens':>7s} {'sched-cost':>11s} {'fifo-cost':>10s} {'saved':>7s}")
    for policy in (Policy.BASELINE, Policy.SALP1, Policy.SALP2, Policy.MASA):
        stats, outs = run_policy(policy, params, model)
        if ref_outs is None:
            ref_outs = outs
        assert outs == ref_outs, "scheduling must not change generated tokens"
        print(f"{policy.pretty:10s} {stats.tokens:7d} {stats.scheduled_cost:11d} "
              f"{stats.fifo_cost:10d} {100*stats.cost_reduction:6.1f}%")
    print("\n(The MASA cost model turns conflicting page accesses into designated"
          "\n hits, so the scheduler finds cheaper orders — outputs are identical.)")


if __name__ == "__main__":
    main()
