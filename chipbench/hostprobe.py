"""The host under the window: a core of its own for the loop's thread, how
fast that core runs Python, and what a stalled step was. Unjudged: it
feeds the log and ``obs["facts"]``, no metric and no check.

Since PR 31 two fifths of a GPT-2 step are host Python on the loop's
thread, so the judged metrics follow the speed of whatever core that
thread wakes on (PERF.md, PR 33). ``Pin`` holds the thread on one core for
the window and keeps the process's other threads (the runtime's, the
profiler's) off it; ``python_speed_ms`` times a fixed piece of interpreter
work there, at the open and at the close, so that a run read on a slow
machine says so itself.

``HostProbe`` keeps, for every engine step (or stretch between two steps)
of ``STALL_S`` or more, the engine's own ``phase_wall_s`` of that step,
the CPU seconds of the loop's thread (near the wall time it was
computing; near 0 it was blocked, or off the CPU) and of the whole
process, and the collections that ran inside it; and every collection
inside the window (generation, seconds): the collector is frozen at the
open, but what the recorder and the engine make afterwards is still
collected. Per step it costs two clock reads.
"""

from __future__ import annotations

import gc
import os
import threading
import time

STALL_S = 0.5


class Pin:
    """The calling thread alone on the highest core the process may use
    (a machine's interrupts usually land on the lowest), from ``hold``
    to ``release``. Does nothing where the process has under three cores or
    the platform has no ``sched_setaffinity``."""

    def __init__(self):
        self.cpu = None
        self._rest = None   # the cores the other threads keep
        self._before = {}   # thread id -> the cores it had

    def hold(self):
        if not hasattr(os, "sched_setaffinity"):
            return None
        allowed = os.sched_getaffinity(0)
        if len(allowed) < 3:
            return None
        cpu = max(allowed)
        rest = allowed - {cpu}
        me = threading.get_native_id()
        for name in os.listdir("/proc/self/task"):
            tid = int(name)
            try:
                self._before[tid] = os.sched_getaffinity(tid)
                os.sched_setaffinity(tid, {cpu} if tid == me else rest)
            except OSError:     # the thread has ended
                self._before.pop(tid, None)
        self.cpu, self._rest = cpu, rest
        return cpu

    def free_this_thread(self):
        """For a thread the loop's thread starts inside the window (the
        profiler's collector): it inherits the one core, and leaves it."""
        if self.cpu is not None:
            os.sched_setaffinity(0, self._rest)

    def release(self):
        for tid, cores in self._before.items():
            try:
                os.sched_setaffinity(tid, cores)
            except OSError:
                pass
        self._before, self.cpu, self._rest = {}, None, None


def python_speed_ms(repeats: int = 5) -> float:
    """Milliseconds the calling thread takes over a fixed piece of
    interpreter work (dict, list and attribute traffic, as a step's
    bookkeeping is): the least of ``repeats``."""
    best = float("inf")
    for _ in range(repeats):
        t = time.perf_counter()
        d, acc = {}, 0
        for i in range(40000):
            d[i & 1023] = acc
            acc += len(d) + (i % 7)
        best = min(best, time.perf_counter() - t)
    return 1e3 * best


class HostProbe:
    def __init__(self):
        self.stalls = []        # one dict per stalled step or stretch
        self.collections = []   # (seconds into the window, generation, s)
        self._t0 = None         # perf_counter at the window's open
        self._gc_started = 0.0
        self._opened = None     # the clocks at the window's open
        self._last = None       # the clocks at the end of the last step
        self._last_end = None   # that step's end, on the window's clock

    @staticmethod
    def _read():
        return time.thread_time(), time.process_time()

    def open(self, t0: float) -> None:
        """At the window's open (``t0`` on ``time.perf_counter``)."""
        self._t0 = t0
        self._opened = self._last = self._read()
        self._last_end = None
        gc.callbacks.append(self._on_gc)

    def close(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_started = now
        else:
            self.collections.append((self._gc_started - self._t0,
                                     int(info["generation"]),
                                     now - self._gc_started))

    def idle(self) -> None:
        """The loop had nothing to do: the stretch up to the next step is
        a wait for an arrival, not a stall."""
        self._last_end = None

    def step(self, index: int, a: float, b: float, record: dict) -> None:
        """After the engine step ``index`` that ran from ``a`` to ``b``
        (seconds into the window); ``record`` is its telemetry record."""
        if self._t0 is None:
            return
        now, before, since = self._read(), self._last, self._last_end
        self._last, self._last_end = now, b
        gap = a - since if since is not None else 0.0
        if b - a < STALL_S and gap < STALL_S:
            return
        start = since if since is not None else a
        self.stalls.append({
            "step": index, "t0_s": a, "step_s": b - a, "gap_before_s": gap,
            "phases": {k: round(v, 4) for k, v in
                       (record.get("phase_wall_s") or {}).items() if v},
            "thread_cpu_s": now[0] - before[0],
            "process_cpu_s": now[1] - before[1],
            "gc": [c for c in self.collections if start <= c[0] <= b],
            "live": record.get("live_slots"),
            "queue": record.get("queue_depth")})

    def summary(self, steps) -> dict:
        """Over the steps of the window: the time in steps of ``STALL_S``
        or more and their count, the collector's work, the CPU seconds
        from the open to the last step, and the stalls."""
        long_ = [s["t1"] - s["t0"] for s in steps
                 if s["t1"] - s["t0"] >= STALL_S]
        by_gen = {}
        for _, gen, s in self.collections:
            n, total, worst = by_gen.get(gen, (0, 0.0, 0.0))
            by_gen[gen] = (n + 1, total + s, max(worst, s))
        return {"stall_s": STALL_S, "long_steps": len(long_),
                "long_steps_s": float(sum(long_)),
                "thread_cpu_s": self._last[0] - self._opened[0],
                "process_cpu_s": self._last[1] - self._opened[1],
                "gc": {str(g): {"count": n, "seconds": t, "longest_s": w}
                       for g, (n, t, w) in sorted(by_gen.items())},
                "stalls": self.stalls}
