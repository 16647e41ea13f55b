"""The training loop: data pipeline + train step + checkpointing + watchdog.

Runs reduced configs on CPU (tests/examples). Fault-tolerance contract
(DESIGN.md Sec. 7): step-keyed deterministic data, async checkpoints every
``ckpt_every`` steps, supervised restarts resuming from the latest checkpoint.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataPipeline
from repro.fault.watchdog import StepWatchdog, SupervisedRun
from repro.models.builder import Model
from repro.train.optimizer import Optimizer
from repro.train.step import make_train_step


@dataclasses.dataclass
class TrainResult:
    final_step: int
    losses: list[float]
    straggler_events: int
    restarts: int


def train(model: Model, optimizer: Optimizer, pipeline: DataPipeline, *,
          total_steps: int, ckpt_dir: str | None = None, ckpt_every: int = 50,
          grad_accum: int = 1, seed: int = 0,
          log_every: int = 10, max_restarts: int = 3,
          fail_at_step: int | None = None) -> TrainResult:
    """``fail_at_step`` injects one crash (fault-tolerance tests/examples)."""
    step_fn = jax.jit(make_train_step(model, optimizer, grad_accum))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    watchdog = StepWatchdog()
    losses: list[float] = []
    injected = {"done": False}

    params0 = model.init(jax.random.key(seed))
    state = {"params": params0, "opt": optimizer.init(params0)}

    def body(start_step: int) -> int:
        nonlocal state
        if mgr is not None and mgr.latest_step() is not None:
            _, restored, _ = mgr.restore(state)
            state = restored
        for step in range(start_step, total_steps):
            t0 = time.perf_counter()
            batch = pipeline.batch_at(step)
            if (fail_at_step is not None and step == fail_at_step
                    and not injected["done"]):
                injected["done"] = True
                raise RuntimeError("injected node failure")
            params, opt, metrics = step_fn(state["params"], state["opt"],
                                           batch, jnp.int32(step))
            state = {"params": params, "opt": opt}
            loss = float(metrics["loss"])
            losses.append(loss)
            watchdog.observe_step(step, time.perf_counter() - t0)
            watchdog.observe_heartbeat(pipeline.heartbeat)
            if step % log_every == 0:
                print(f"[train] step={step} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f}")
            if mgr is not None and (step + 1) % ckpt_every == 0:
                mgr.save(step + 1, state)
        if mgr is not None:
            mgr.save(total_steps, state, block=True)
        return total_steps

    sup = SupervisedRun(body, (mgr.latest_step if mgr else (lambda: 0)),
                        max_restarts=max_restarts)
    final = sup.run()
    if mgr is not None:
        mgr.wait()
    return TrainResult(final_step=final, losses=losses,
                       straggler_events=len(watchdog.events),
                       restarts=sup.restarts)
