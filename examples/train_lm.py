"""End-to-end training driver: train a reduced LM for a few hundred steps with
checkpointing + an injected node failure (the fault-tolerance path), and show
the loss actually dropping.

  PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --steps 200

The reduced config keeps it CPU-sized. The injected failure at step 120
exercises SupervisedRun: the loop restarts from the step-100 checkpoint and
replays the exact same data (step-keyed pipeline), finishing all steps.
"""
import argparse
import tempfile

import jax

from repro import compile_cache
from repro.configs import get_config
from repro.data.pipeline import DataPipeline
from repro.models import build_model
from repro.train.loop import train
from repro.train.optimizer import make_optimizer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--width", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--fail-at-step", type=int, default=120)
    args = ap.parse_args()

    compile_cache.enable()
    cfg = get_config(args.arch).reduced(args.width)
    model = build_model(cfg, dtype=jax.numpy.float32)
    opt = make_optimizer(cfg.optimizer_mode, lr=1e-3, warmup=20,
                         total_steps=args.steps)
    pipe = DataPipeline(cfg, args.batch, args.seq, dtype=jax.numpy.float32)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        res = train(model, opt, pipe, total_steps=args.steps,
                    ckpt_dir=ckpt_dir, ckpt_every=50,
                    fail_at_step=args.fail_at_step)

    first = sum(res.losses[:10]) / 10
    last = sum(res.losses[-10:]) / 10
    print(f"\n[train_lm] {res.final_step} steps done "
          f"(restarts={res.restarts} — injected failure recovered)")
    print(f"[train_lm] loss {first:.3f} -> {last:.3f} "
          f"({'LEARNING' if last < first * 0.9 else 'check hyperparams'})")
    assert res.final_step == args.steps
    assert res.restarts >= 1, "the injected failure should have triggered a restart"
    assert last < first, "loss should decrease"


if __name__ == "__main__":
    main()
