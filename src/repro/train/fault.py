"""Fault-tolerant training supervisor: checkpoint/restart, stragglers,
elastic re-meshing (design-for-1000-nodes, DESIGN.md S7).

The supervisor owns the step loop.  On a device/runtime failure it restores
the latest checkpoint and replays the deterministic data stream from the
recovered step counter (bitwise identical batches).  If a mesh rebuild
callback is provided, it can resume on a *smaller* mesh (elastic restart)
-- the checkpointer reshards on load.  Straggler detection tracks a
step-time EWMA and flags z-score outliers; the flags feed a per-rank
:class:`repro.core.health.RankHealth` model whose weights the planner
consumes (DESIGN.md S13), so a detected straggler actually loses quota
instead of just being logged.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint import Checkpointer
from repro.core.health import HealthConfig, RankHealth

__all__ = ["SupervisorConfig", "Supervisor"]


@dataclasses.dataclass
class SupervisorConfig:
    checkpoint_dir: str | None      # None: no checkpoints, so no recovery
    checkpoint_every: int = 50
    max_restarts: int = 3
    straggler_zscore: float = 3.0
    ewma_decay: float = 0.9
    num_ranks: int = 1              # EP ranks tracked by the health model


class Supervisor:
    """Runs ``state = step_fn(state, batch)`` with failure recovery."""

    def __init__(self, cfg: SupervisorConfig, step_fn: Callable,
                 batch_fn: Callable[[int], Any], *,
                 state_shardings=None,
                 rebuild_fn: Callable[[], Callable] | None = None):
        self.cfg = cfg
        self.step_fn = step_fn
        self.batch_fn = batch_fn
        self.rebuild_fn = rebuild_fn
        self.state_shardings = state_shardings
        self.ckpt = (Checkpointer(cfg.checkpoint_dir)
                     if cfg.checkpoint_dir is not None else None)
        self.restarts = 0
        self.step_times: list[float] = []
        self._ewma = None
        self._ewvar = 0.0
        self.straggler_flags: list[int] = []
        self.health = RankHealth(cfg.num_ranks, HealthConfig(
            ewma_decay=cfg.ewma_decay,
            quarantine_zscore=cfg.straggler_zscore))

    def rank_health(self) -> RankHealth:
        """The live per-rank health model (planner-consumable weights)."""
        return self.health

    def _track_time(self, step: int, dt: float,
                    rank_times: np.ndarray | None = None):
        self.step_times.append(dt)
        # Per-rank times (from metrics["rank_step_times"] when the step fn
        # reports them, else the global dt broadcast) feed the health model;
        # its weights reach the planner via rank_health() -- the flag list
        # below is kept for backward compatibility but no longer the only
        # consumer of straggler detection.
        if rank_times is None:
            rank_times = np.full(self.cfg.num_ranks, dt)
        self.health.observe(np.asarray(rank_times, dtype=np.float64))
        if self._ewma is None:
            self._ewma = dt
            return
        d = self.cfg.ewma_decay
        dev = dt - self._ewma
        self._ewma = d * self._ewma + (1 - d) * dt
        self._ewvar = d * self._ewvar + (1 - d) * dev * dev
        sd = max(np.sqrt(self._ewvar), 1e-9)
        if dev / sd > self.cfg.straggler_zscore and len(self.step_times) > 8:
            self.straggler_flags.append(step)

    def run(self, state, start_step: int, num_steps: int,
            on_metrics: Callable | None = None):
        """Run to ``start_step + num_steps`` with recovery.  Returns state."""
        step = start_step
        end = start_step + num_steps
        while step < end:
            try:
                batch = self.batch_fn(step)
                # Monotonic clock: step durations must survive wall-clock
                # adjustments (NTP slew would poison the straggler z-score).
                t0 = time.monotonic()
                state, metrics = self.step_fn(state, batch)
                jax.block_until_ready(metrics["loss"])
                rank_times = metrics.get("rank_step_times") \
                    if hasattr(metrics, "get") else None
                if rank_times is not None:
                    rank_times = np.asarray(rank_times)
                self._track_time(step, time.monotonic() - t0,
                                 rank_times=rank_times)
                step += 1
                if on_metrics is not None:
                    on_metrics(step, metrics)
                if (self.ckpt is not None
                        and step % self.cfg.checkpoint_every == 0):
                    self.ckpt.save(step, state)
            except (jax.errors.JaxRuntimeError, RuntimeError) as e:
                self.restarts += 1
                if self.restarts > self.cfg.max_restarts:
                    raise RuntimeError(
                        f"supervisor: giving up after {self.restarts} restarts"
                    ) from e
                latest = (self.ckpt.latest_step()
                          if self.ckpt is not None else None)
                if latest is None:
                    raise
                if self.rebuild_fn is not None:
                    # Elastic restart: caller may hand back a step_fn bound
                    # to a rebuilt (possibly smaller) mesh.
                    self.step_fn = self.rebuild_fn()
                state, step = self.ckpt.restore(
                    state, latest, shardings=self.state_shardings)
        if self.ckpt is not None:
            self.ckpt.save(step, state, blocking=True)
        return state, step
