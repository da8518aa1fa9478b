"""Tracing / profiling: the observability layer the reference lacks.

The reference's entire measurement surface is one wall-clock print around
``model.fit`` on Horovod rank 0 (``/root/reference/imagenet-resnet50-hvd.py:
119-126``). SURVEY.md §5 "Tracing / profiling" calls for the TPU-native
story: ``jax.profiler`` traces (viewable in TensorBoard/XProf, with XLA HLO
and ICI collective timelines), per-step timing, and first-class
images/sec/chip reporting (the BASELINE.json headline metric).
"""

from __future__ import annotations

import contextlib
import statistics
import sys
import time
from typing import Dict, List, Optional

import jax

from pddl_tpu.train.callbacks import Callback


@contextlib.contextmanager
def trace(name: str, step: Optional[int] = None):
    """Annotate a host-side region so it shows up on the trace timeline.

    ``step`` uses :class:`jax.profiler.StepTraceAnnotation`, which lets
    XProf group device activity by training step.
    """
    if step is not None:
        ctx = jax.profiler.StepTraceAnnotation(name, step_num=step)
    else:
        ctx = jax.profiler.TraceAnnotation(name)
    with ctx:
        yield


@contextlib.contextmanager
def capture(logdir: str):
    """Capture a profiler trace for the enclosed region into ``logdir``."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class Profiler(Callback):
    """Capture a ``jax.profiler`` trace for selected steps of an epoch.

    Skips the first ``warmup_steps`` (compilation) and records
    ``num_steps`` steps of epoch ``epoch`` — the standard "profile a steady
    -state window" recipe. Coordinator-only, like all reference logging.
    """

    def __init__(self, logdir: str, epoch: int = 0, start_step: int = 2,
                 num_steps: int = 5):
        self.logdir = logdir
        self.epoch = epoch
        self.start_step = start_step
        self.num_steps = num_steps
        self._active = False
        self._epoch_step = 0
        self._in_epoch = False

    def on_epoch_begin(self, epoch, state):
        self._in_epoch = epoch == self.epoch
        self._epoch_step = 0
        return None

    def on_train_batch_end(self, step, state, logs):
        from pddl_tpu.core import dist

        if not (self._in_epoch and dist.is_coordinator()):
            return None
        if self._epoch_step == self.start_step and not self._active:
            jax.profiler.start_trace(self.logdir)
            self._active = True
        elif self._active and self._epoch_step >= self.start_step + self.num_steps:
            self._stop(state)
        self._epoch_step += 1
        return None

    def _stop(self, state):
        # Block on the last result so device work lands inside the trace.
        jax.tree.leaves(state.params)[0].block_until_ready()
        jax.profiler.stop_trace()
        self._active = False

    def on_epoch_end(self, epoch, state, logs):
        if self._active:
            self._stop(state)
        return None

    def on_train_end(self, state, logs):
        if self._active:
            self._stop(state)
        return None


class StepTimer(Callback):
    """Per-step wall-time stats (mean/p50/p90/p99, compile step
    excluded) and steady-state images/sec/chip — the per-chip number
    the strategies multiply out (BASELINE.json metric).

    :meth:`snapshot` emits the stats in the same flat-dict shape as
    ``ServeMetrics.snapshot()`` (stable keys, ``None`` before data), so
    the training step loop and the serving engine share one Prometheus
    export path (`pddl_tpu/obs/export.py`)."""

    def __init__(self, global_batch_size: Optional[int] = None,
                 skip_steps: int = 1, verbose: int = 1):
        self.global_batch_size = global_batch_size
        self.skip_steps = skip_steps  # first step(s) include compilation
        self.verbose = verbose
        self.step_times: List[float] = []
        self._last: Optional[float] = None
        self._step_in_run = 0

    def on_train_begin(self, state):
        self._last = time.perf_counter()
        return None

    def on_train_batch_end(self, step, state, logs):
        now = time.perf_counter()
        if self._step_in_run >= self.skip_steps:
            self.step_times.append(now - self._last)
        self._last = now
        self._step_in_run += 1
        return None

    @property
    def stats(self) -> Dict[str, float]:
        if not self.step_times:
            return {}
        ts = sorted(self.step_times)
        n = len(ts)
        out = {
            "step_time_mean_s": statistics.fmean(ts),
            "step_time_p50_s": ts[n // 2],
            "step_time_p90_s": ts[min(n - 1, int(0.9 * n))],
            "step_time_p99_s": ts[min(n - 1, int(0.99 * n))],
            "steps_timed": float(n),
        }
        if self.global_batch_size:
            per_sec = self.global_batch_size / out["step_time_mean_s"]
            out["images_per_sec"] = per_sec
            out["images_per_sec_per_chip"] = per_sec / jax.device_count()
        return out

    def snapshot(self) -> Dict[str, Optional[float]]:
        """The export dict (`ServeMetrics.snapshot()` discipline):
        every key always present, ``None`` where nothing was measured
        yet — render with
        ``obs.export.render_prometheus(timer.snapshot(),
        prefix="pddl_train_step")``."""
        stats = self.stats
        return {
            "step_time_mean_s": stats.get("step_time_mean_s"),
            "step_time_p50_s": stats.get("step_time_p50_s"),
            "step_time_p90_s": stats.get("step_time_p90_s"),
            "step_time_p99_s": stats.get("step_time_p99_s"),
            "steps_timed": stats.get("steps_timed", 0.0),
            "images_per_sec": stats.get("images_per_sec"),
            "images_per_sec_per_chip": stats.get("images_per_sec_per_chip"),
        }

    def on_train_end(self, state, logs):
        from pddl_tpu.core import dist

        if self.verbose and dist.is_coordinator() and self.step_times:
            parts = [f"{k}: {v:.4g}" for k, v in self.stats.items()]
            print("StepTimer: " + " - ".join(parts), file=sys.stderr)
        return None


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """Per-device HBM stats (bytes) where the backend exposes them."""
    out = {}
    for d in jax.local_devices():
        stats = {}
        try:
            stats = d.memory_stats() or {}
        except Exception:
            pass
        out[str(d)] = {
            "bytes_in_use": int(stats.get("bytes_in_use", -1)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", -1)),
            "bytes_limit": int(stats.get("bytes_limit", -1)),
        }
    return out
