"""Production training loop: checkpoint/restart, preemption, stragglers.

Fault-tolerance contract:
  * `Trainer.fit()` resumes from the latest complete checkpoint (atomic
    rename commit — a torn save is invisible), restoring params/opt/kmeans
    state, step counter AND the data-iterator cursor, so a killed-and-
    restarted run produces the same step sequence as an uninterrupted one
    (tested bit-exact in tests/test_ckpt.py).
  * SIGTERM/SIGINT (preemption notice) triggers a final synchronous
    checkpoint before exit — at most `ckpt_every` steps of work lost under
    normal operation, ~0 steps under graceful preemption.
  * Straggler mitigation: per-step wall times (from the batch draw to
    the step's metrics on the host) feed a rolling median; steps
    slower than `straggler_factor` x median increment a counter and invoke
    `on_straggler` (hook for re-balancing grad-accum microbatches or
    alerting). On a real fleet this is fed per-host; here it is wired and
    tested at the controller level.
  * Elastic: `Trainer` takes the mesh as a constructor arg; restoring a
    checkpoint saved on a different mesh re-shards via CheckpointManager.
"""
from __future__ import annotations

import contextlib
import functools
import signal
import statistics
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import RunConfig
from repro.data.synthetic import SyntheticLoader
from repro.obs import JsonlSink, Registry, StepSeries, span, step_span
from repro.train.train_step import (TrainState, init_train_state,
                                    make_train_step)


class Trainer:
    def __init__(self, run: RunConfig, loader: SyntheticLoader,
                 ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
                 mesh=None, shardings=None, straggler_factor: float = 2.5,
                 on_straggler: Optional[Callable[[int, float], None]] = None,
                 async_ckpt: bool = True, step_fn=None,
                 obs_jsonl: Optional[str] = None):
        self.run = run
        self.loader = loader
        self.mesh = mesh
        self.ckpt_every = ckpt_every
        self.async_ckpt = async_ckpt
        self.mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
        self.shardings = shardings
        self.straggler_factor = straggler_factor
        self.on_straggler = on_straggler or (lambda step, t: None)
        self.straggler_count = 0
        self._times: List[float] = []
        self._preempted = False
        fn = step_fn or make_train_step(run)
        self.step_fn = jax.jit(fn, donate_argnums=(0,)) \
            if step_fn is None else step_fn
        self.state: Optional[TrainState] = None
        # per-step metrics live on the obs layer: an append-only history
        # (what metrics_history used to be) plus an optional JSONL sink
        # ("train_step" records, schema-validated in CI's obs-smoke)
        self.obs = Registry()
        self._sink = (JsonlSink(obs_jsonl, source="trainer")
                      if obs_jsonl else None)
        self._series = StepSeries(sink=self._sink, kind="train_step")

    @property
    def metrics_history(self) -> List[Dict[str, Any]]:
        """Per-step host metric dicts (unchanged public view; backed by
        the obs StepSeries since the observability PR)."""
        return self._series.history

    # ------------------------------------------------------------------
    def init_or_restore(self) -> TrainState:
        key = jax.random.PRNGKey(self.run.train.seed)
        init = functools.partial(init_train_state, self.run, mesh=self.mesh)
        # on a mesh every leaf is built in its rule layout: no transient
        # copy of the whole state (params, optimizer moments, the per-
        # device int8_ef residuals) on device 0
        state = (init(key) if self.shardings is None else
                 jax.jit(init, out_shardings=self.shardings)(key))
        if self.mgr is not None and self.mgr.latest_step() is not None:
            # checkpoints hold the field-named dict, not the bare tuple,
            # so leaves are keyed "params/...", "ef_state/..." on disk
            shardings = (self.shardings._asdict()
                         if isinstance(self.shardings, TrainState)
                         else self.shardings)
            if any(k.split("/", 1)[0] == "params" for k in self.mgr.keys()):
                d, extra = self.mgr.restore(state._asdict(),
                                            shardings=shardings)
                state = TrainState(**d)
            else:
                # legacy checkpoint (bare-tuple layout, index-keyed
                # leaves) from before the field-named format; it can
                # never hold an ef residual, so restore the 4-field part
                # and keep the freshly-zeroed ef_state
                legacy, extra = self.mgr.restore(
                    state._replace(ef_state=None), shardings=self.shardings)
                state = legacy._replace(ef_state=state.ef_state)
            if "loader" in extra:
                self.loader.restore(extra["loader"])
        self.state = state
        return state

    def close(self) -> None:
        """Flush + close the obs JSONL sink (records are flushed per
        line, so this is only needed for prompt fd release)."""
        if self._sink is not None:
            self._sink.close()

    def _install_preemption_handler(self):
        def handler(signum, frame):
            self._preempted = True
        try:
            signal.signal(signal.SIGTERM, handler)
            signal.signal(signal.SIGINT, handler)
        except ValueError:
            pass  # not main thread (tests)

    def _checkpoint(self, blocking=False):
        if self.mgr is None or self.state is None:
            return
        self.mgr.save(int(self.state.step), self.state._asdict(),
                      extra={"loader": self.loader.state()},
                      blocking=blocking or not self.async_ckpt)

    def _watch_stragglers(self, step: int, dt: float):
        self._times.append(dt)
        window = self._times[-50:]
        if len(window) >= 5:
            med = statistics.median(window)
            if dt > self.straggler_factor * med:
                self.straggler_count += 1
                self.on_straggler(step, dt / med)

    # ------------------------------------------------------------------
    def fit(self, num_steps: Optional[int] = None) -> Dict[str, Any]:
        with (self.mesh if self.mesh is not None
              else contextlib.nullcontext()):
            return self._fit(num_steps)

    def _fit(self, num_steps: Optional[int] = None) -> Dict[str, Any]:
        if self.state is None:
            self.init_or_restore()
        self._install_preemption_handler()
        target = num_steps if num_steps is not None else self.run.train.steps
        it = iter(self.loader)
        step = int(self.state.step)
        while step < target and not self._preempted:
            # host spans (repro.obs) on the profiler's timeline: the
            # device's idle gaps are named by the phase the host is in
            with step_span(step + 1):
                t0 = time.perf_counter()
                with span("train/data"):
                    batch = next(it)
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                with span("train/dispatch"):
                    self.state, metrics = self.step_fn(self.state, batch)
                with span("train/fetch"):
                    # ONE batched device_get of the whole dict; it waits
                    # for the step, so step_time_s times the step
                    metrics = jax.device_get(metrics)
                    step = int(self.state.step)
                dt = time.perf_counter() - t0
                self._watch_stragglers(step, dt)
                metrics["step_time_s"] = dt
                self.obs.histogram("train/step_time_s").record(dt)
                self._series.record(step, metrics)
                if self.mgr is not None and step % self.ckpt_every == 0:
                    with span("train/checkpoint"):
                        self._checkpoint()
        # final (or preemption) checkpoint: synchronous
        self._checkpoint(blocking=True)
        if self.mgr is not None:
            self.mgr.wait()
        return {"steps": int(self.state.step),
                "preempted": self._preempted,
                "stragglers": self.straggler_count,
                "final_loss": (self.metrics_history[-1]["loss"]
                               if self.metrics_history else None)}
