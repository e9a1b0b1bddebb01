"""Training loop: checkpoint/restart, step watchdog, metrics.

Fault-tolerance contract (DESIGN.md §7):
  * auto-resume: on start, restores the latest complete checkpoint
    (params + opt state + data-pipeline state + rng) if one exists;
  * atomic periodic checkpoints every ``ckpt_every`` steps (keep-k);
  * **watchdog**: each step is timed against a deadline derived from a
    running median (straggler detection).  On breach the configured action
    fires — ``"log"`` records the event (default), ``"checkpoint"``
    additionally snapshots so a re-slice can restart cleanly.  On real
    multi-pod deployments the action hook is where pod re-slicing /
    hot-spare swap-in integrates; the logic itself is what we test on CPU.
"""
from __future__ import annotations

import json
import os
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import ckpt as ckpt_lib
from repro.configs.base import ArchConfig, ShapeConfig
from repro.data.pipeline import TokenPipeline, with_frontend_inputs
from repro.models import model as model_lib
from repro.sharding.partition import partition_params
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import build_train_step


@dataclass
class WatchdogConfig:
    factor: float = 3.0          # deadline = factor × running median
    min_history: int = 5
    action: str = "log"          # log | checkpoint


@dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)
    seed: int = 0


class Watchdog:
    """Step-time straggler detector (tested directly; see tests)."""

    def __init__(self, cfg: WatchdogConfig):
        self.cfg = cfg
        self.history: List[float] = []
        self.events: List[Dict] = []

    def deadline(self) -> Optional[float]:
        if len(self.history) < self.cfg.min_history:
            return None
        return float(np.median(self.history)) * self.cfg.factor

    def observe(self, step: int, dt: float) -> bool:
        """Record a step time; returns True if the deadline was breached."""
        dl = self.deadline()
        breached = dl is not None and dt > dl
        if breached:
            self.events.append({"step": step, "dt": dt, "deadline": dl})
        else:
            self.history.append(dt)
            self.history = self.history[-64:]
        return breached


class Trainer:
    def __init__(self, cfg: ArchConfig, shape: ShapeConfig,
                 opt_cfg: AdamWConfig, tcfg: TrainerConfig, *,
                 mesh=None, rules=None, pipeline: Optional[TokenPipeline] = None,
                 dtype=None):
        import jax.numpy as jnp
        self.cfg, self.shape, self.opt_cfg, self.tcfg = cfg, shape, opt_cfg, tcfg
        self.mesh, self.rules = mesh, rules
        self.dtype = dtype or jnp.float32
        self.step_fn = build_train_step(cfg, shape, opt_cfg, mesh, rules,
                                        donate=False)
        self.pipeline = pipeline
        self.watchdog = Watchdog(tcfg.watchdog)
        self.metrics_log: List[Dict] = []
        self.step = 0
        self.params = None
        self.opt_state = None

    # ---- state ----
    def init_state(self):
        rng = jax.random.PRNGKey(self.tcfg.seed)
        self.params = model_lib.init_params(self.cfg, rng, dtype=self.dtype)
        if self.rules is not None:
            # lay the parameters out by the partition rules (FSDP + TP);
            # left alone, the step keeps most of them whole on every device
            self.params = jax.device_put(
                self.params, partition_params(self.params, self.rules))
        self.opt_state = init_opt_state(self.params)
        self.step = 0

    def try_restore(self) -> bool:
        d = self.tcfg.ckpt_dir
        if not d or ckpt_lib.latest_step(d) is None:
            return False
        like = {"params": jax.tree.map(lambda x: x, self.params),
                "opt": self.opt_state}
        state, extra = ckpt_lib.restore(d, like)
        self.params, self.opt_state = state["params"], state["opt"]
        self.step = int(extra["step"])
        if self.pipeline is not None and "data" in extra:
            self.pipeline.restore(extra["data"])
        return True

    def checkpoint(self):
        if not self.tcfg.ckpt_dir:
            return
        extra = {"step": self.step}
        if self.pipeline is not None:
            extra["data"] = self.pipeline.snapshot()
        ckpt_lib.save(self.tcfg.ckpt_dir, self.step,
                      {"params": self.params, "opt": self.opt_state},
                      extra=extra, keep=self.tcfg.keep)

    # ---- loop ----
    def _next_batch(self):
        import jax.numpy as jnp
        raw = self.pipeline.next_batch()
        raw = with_frontend_inputs(raw, self.cfg,
                                   n_vis=model_lib.n_vis(
                                       self.cfg, self.shape.seq_len))
        return {k: jnp.asarray(v) for k, v in raw.items()}

    def run(self) -> List[Dict]:
        if self.params is None:
            self.init_state()
            self.try_restore()
        while self.step < self.tcfg.steps:
            batch = self._next_batch()
            t0 = time.perf_counter()
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            self.step += 1
            if self.watchdog.observe(self.step, dt):
                if self.tcfg.watchdog.action == "checkpoint":
                    self.checkpoint()
            rec = {"step": self.step, "dt": dt,
                   **{k: float(v) for k, v in metrics.items()}}
            self.metrics_log.append(rec)
            if self.step % self.tcfg.log_every == 0:
                print(json.dumps({k: (round(v, 5) if isinstance(v, float)
                                      else v) for k, v in rec.items()}))
            if self.step % self.tcfg.ckpt_every == 0:
                self.checkpoint()
        self.checkpoint()
        return self.metrics_log
