"""Fault-tolerant training loop.

Features (the large-scale-runnability story, exercised in tests/examples):
* auto-resume from the newest committed checkpoint;
* periodic async checkpointing (training never blocks on IO);
* bounded step-retry on transient failures (a thrown step is re-executed
  from the last good (params, opt_state) — on real fleets this is where a
  SlurmRequeue/BarrierTimeout lands);
* straggler watchdog: per-step wall-time EWMA + sigma; steps slower than
  mean + k*sigma are logged and counted (on multi-host this feeds the
  replace-the-slow-host decision);
* loss-spike guard (skip-update on non-finite loss).
"""
from __future__ import annotations

import logging
import math
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.checkpoint import checkpoint as ckpt
from repro.runtime import trace_names as N

log = logging.getLogger("repro.train")


@dataclass
class StragglerWatchdog:
    """EWMA step-time monitor; flags steps slower than mean + k*sigma."""
    k: float = 3.0
    alpha: float = 0.1
    mean: float = 0.0
    var: float = 0.0
    steps: int = 0
    flagged: list = field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        self.steps += 1
        if self.steps == 1:
            self.mean = dt
            return False
        sigma = math.sqrt(max(self.var, 1e-12))
        is_straggler = self.steps > 5 and dt > self.mean + self.k * sigma
        if is_straggler:
            self.flagged.append((step, dt))
            log.warning("straggler step %d: %.3fs (mean %.3fs + %g sigma)",
                        step, dt, self.mean, self.k)
        d = dt - self.mean
        self.mean += self.alpha * d
        self.var = (1 - self.alpha) * (self.var + self.alpha * d * d)
        return is_straggler


@dataclass
class TrainLoopResult:
    final_step: int
    losses: list
    resumed_from: Optional[int]
    retries: int
    stragglers: int
    checkpoints: list
    # host seconds per completed step, from dispatch to the loss on the
    # host; the first includes compilation
    step_times: list = field(default_factory=list)
    params: object = None            # the trained parameters


def run(train_step: Callable, params, opt_state, data_iter_fn: Callable,
        *, total_steps: int, ckpt_dir: Optional[str] = None,
        ckpt_every: int = 50, max_retries: int = 3,
        shardings=None, watchdog: Optional[StragglerWatchdog] = None,
        fail_injector: Optional[Callable[[int], None]] = None
        ) -> TrainLoopResult:
    """data_iter_fn(step) -> batch (deterministic => restart-safe).
    fail_injector(step) may raise to simulate node failures (tests)."""
    watchdog = watchdog or StragglerWatchdog()
    resumed_from = None
    start = 0
    if ckpt_dir:
        step0, restored = ckpt.restore_latest(
            ckpt_dir, {"params": params, "opt": opt_state}, shardings)
        if step0 is not None:
            params, opt_state = restored["params"], restored["opt"]
            start = step0 + 1
            resumed_from = step0
            log.info("resumed from checkpoint step %d", step0)

    losses: list = []
    step_times: list = []
    saves: list = []
    pending_save = None
    retries = 0
    step = start
    while step < total_steps:
        with TraceAnnotation(N.TRAIN_BATCH, step=step):
            batch = data_iter_fn(step)
        t0 = time.perf_counter()
        try:
            with StepTraceAnnotation(N.TRAIN_STEP_GROUP, step_num=step), \
                    TraceAnnotation(N.TRAIN_STEP, step=step):
                if fail_injector is not None:
                    fail_injector(step)
                new_params, new_opt, metrics = train_step(params, opt_state,
                                                          batch)
                loss = float(metrics["loss"])
        except Exception as e:             # transient failure -> retry
            retries += 1
            log.warning("step %d failed (%s); retry %d/%d", step, e,
                        retries, max_retries)
            if retries > max_retries:
                raise
            continue
        if not np.isfinite(loss):          # loss spike -> skip the update
            log.warning("non-finite loss at step %d; skipping update", step)
            step += 1
            continue
        params, opt_state = new_params, new_opt
        losses.append(loss)
        step_times.append(time.perf_counter() - t0)
        watchdog.observe(step, step_times[-1])
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            if pending_save is not None:
                pending_save.join()
            pending_save = ckpt.save(ckpt_dir, step,
                                     {"params": params, "opt": opt_state},
                                     blocking=False)
            saves.append(step)
        step += 1
    if pending_save is not None:
        pending_save.join()
    if ckpt_dir and (not saves or saves[-1] != step - 1) and step > start:
        ckpt.save(ckpt_dir, step - 1, {"params": params, "opt": opt_state})
        saves.append(step - 1)
    return TrainLoopResult(final_step=step, losses=losses,
                           resumed_from=resumed_from, retries=retries,
                           stragglers=len(watchdog.flagged),
                           checkpoints=saves, step_times=step_times,
                           params=params)
