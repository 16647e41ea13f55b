"""End-to-end reproduction of the paper's headline results (Figures 4 & 5).

  PYTHONPATH=src python examples/dram_paper_repro.py [--n 8000] [--out sweep.json]

Declares the 32-workload x 5-policy evaluation as ONE experiment grid and runs
it through the vectorized sweep subsystem (one vmapped, JIT-compiled simulator
call per policy; every cell content-hash cached), then prints the mean IPC
improvements, MASA's row-hit and dynamic-energy deltas, and the paper's
attribution statistics, side by side with the published numbers.
"""
import argparse

import numpy as np

from repro import compile_cache
from repro.core.dram import PAPER_WORKLOADS, Policy
from repro.experiments import SweepGrid, run_sweep, write_artifact

POLICIES = (Policy.BASELINE, Policy.SALP1, Policy.SALP2, Policy.MASA,
            Policy.IDEAL)
#: The paper's mean single-core IPC gains over the baseline, percent.
PAPER_IPC_GAIN_PCT = {Policy.SALP1: 6.6, Policy.SALP2: 13.4,
                      Policy.MASA: 16.7, Policy.IDEAL: 19.6}


def make_grid(n_requests: int = 8000, seed: int = 7) -> SweepGrid:
    """The Fig. 4 grid: 32 workloads x 5 policies on DDR3-1066, 8 x 8."""
    return SweepGrid(name="paper_repro", workloads=PAPER_WORKLOADS,
                     policies=POLICIES, n_requests=n_requests, seed=seed)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8000)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", type=str, default=None,
                    help="optionally write the repro.sweep/v1 JSON artifact here")
    args = ap.parse_args()

    compile_cache.enable()
    sweep = run_sweep(make_grid(args.n, args.seed))
    print(f"# {sweep.stats['n_cells']} cells in {sweep.stats['sim_batches']} "
          f"vmapped calls ({sweep.stats['elapsed_s']}s)\n")

    mpki = np.array([p.mpki for p in PAPER_WORKLOADS])
    ipc = {pol: sweep.metric("ipc", policy=pol) for pol in POLICIES}
    base = ipc[Policy.BASELINE]

    print(f"{'mechanism':12s} {'ours':>8s} {'paper':>8s}")
    for pol, ref in PAPER_IPC_GAIN_PCT.items():
        g = 100 * (ipc[pol] / base - 1).mean()
        print(f"{pol.pretty:12s} {g:7.2f}% {ref:7.1f}%")

    hit_b = sweep.metric("n_hit", policy=Policy.BASELINE) / args.n
    hit_m = sweep.metric("n_hit", policy=Policy.MASA) / args.n
    print(f"\nrow-hit rate: {hit_b.mean():.3f} -> {hit_m.mean():.3f} "
          f"(+{100*(hit_m-hit_b).mean():.1f}pp; paper +12.8pp)")

    eb = sweep.metric("dynamic_nj", policy=Policy.BASELINE)
    em = sweep.metric("dynamic_nj", policy=Policy.MASA)
    print(f"dynamic DRAM energy: -{100*(1-em/eb).mean():.1f}% (paper -18.6%)")

    g1 = 100 * (ipc[Policy.SALP1] / base - 1)
    print(f"\nSALP-1 >5% gainers mean MPKI: {mpki[g1 > 5].mean():.1f} vs "
          f"others {mpki[g1 <= 5].mean():.2f} (paper 18.4 vs 1.14)")
    sasel = sweep.metric("n_sasel", policy=Policy.MASA)
    acts = sweep.metric("n_act", policy=Policy.MASA)
    gm = 100 * (ipc[Policy.MASA] / base - 1)
    hi = gm > 30
    print(f"MASA SA_SEL per ACT: high-benefit apps {np.mean(sasel[hi]/acts[hi]):.2f} "
          f"vs rest {np.mean(sasel[~hi]/acts[~hi]):.2f} (paper ~0.5 vs ~0.06)")

    if args.out:
        print(f"\nartifact: {write_artifact(args.out, sweep.to_json())}")


if __name__ == "__main__":
    main()
