"""Fixed-capacity per-tick telemetry ring for the serving engine.

`ServeMetrics` keeps lifetime aggregates; a live incident needs the
RECENT per-tick shape of the engine — was occupancy pinned, did one
site's dispatch wall time spike, did retries cluster — without an
unbounded log. This ring is that window: the engine appends one record
per ``step()`` (occupancy, queue depth, tokens emitted, per-site
``_device_call`` wall time, per-phase wall time of the step's span
tree, retries, backend compiles inside the step, degraded flag),
capacity is fixed
at construction, and the oldest record is overwritten in place.
``snapshot()`` hands benches and the drain path a stable oldest→newest
copy; ``summary()`` collapses the window into the handful of gauges the
Prometheus exposition and the drain snapshot embed.

Host-side only: records are plain dicts of scalars the engine already
computed — appending can never add a device sync.
"""

from __future__ import annotations

from typing import Dict, List, Optional

# The record's nested per-label wall-time maps (``site_wall_s``: the
# ``_device_call`` dispatch sites; ``phase_wall_s``: the step's phases,
# `serve/metrics.PHASES`).
_WALL_MAPS = ("site_wall_s", "phase_wall_s")


def _copy(record: Dict[str, object]) -> Dict[str, object]:
    """A record the caller may mutate: the nested maps copied too."""
    c = dict(record)
    for key in _WALL_MAPS:
        if isinstance(c.get(key), dict):
            c[key] = dict(c[key])
    return c


class TelemetryRing:
    """Ring buffer of per-tick telemetry records.

    A preallocated slot list plus a rolling write index (not a deque):
    capacity is enforced by construction, append is O(1) with no
    resizing, and the memory high-water mark is ``capacity`` records
    forever — the property the "bounded under sustained load" contract
    needs to be structural, not amortized.
    """

    def __init__(self, capacity: int = 512):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._slots: List[Optional[Dict[str, object]]] = \
            [None] * self.capacity
        self._next = 0          # write position
        self._count = 0         # total records ever appended

    def __len__(self) -> int:
        return min(self._count, self.capacity)

    @property
    def total_appended(self) -> int:
        """Records ever appended (>= ``len`` once the ring wrapped)."""
        return self._count

    def append(self, record: Dict[str, object]) -> None:
        self._slots[self._next] = record
        self._next = (self._next + 1) % self.capacity
        self._count += 1

    def snapshot(self) -> List[Dict[str, object]]:
        """Oldest→newest copy of the current window (safe to mutate —
        the nested wall-time maps are copied too, so post-processing a
        snapshot can never corrupt the live ring)."""
        if self._count < self.capacity:
            window = self._slots[:self._count]
        else:
            window = self._slots[self._next:] + self._slots[:self._next]
        return [_copy(r) for r in window]

    def last(self) -> Optional[Dict[str, object]]:
        """Newest record — copied like :meth:`snapshot`, so a caller
        post-processing it can never corrupt the live ring."""
        if self._count == 0:
            return None
        return _copy(self._slots[(self._next - 1) % self.capacity])

    def summary(self) -> Dict[str, object]:
        """The window collapsed to export gauges: tick-wall percentiles,
        mean queue/occupancy, totals, and per-site wall-time sums —
        what the drain snapshot embeds and ``/metrics`` exposes without
        shipping every record (the per-phase split has its cumulative
        counters in ``ServeMetrics``: ``rate()`` over those is the
        recent window)."""
        window = self.snapshot()
        if not window:
            return {"ticks": 0}
        walls = sorted(float(r.get("tick_wall_s", 0.0)) for r in window)
        n = len(walls)
        site_wall: Dict[str, float] = {}
        for r in window:
            for site, w in (r.get("site_wall_s") or {}).items():
                site_wall[site] = site_wall.get(site, 0.0) + float(w)
        return {
            "ticks": n,
            "window_first_step": window[0].get("step"),
            "window_last_step": window[-1].get("step"),
            "tick_wall_p50_s": walls[n // 2],
            "tick_wall_p99_s": walls[min(n - 1, int(0.99 * n))],
            "mean_queue_depth": (sum(float(r.get("queue_depth", 0))
                                     for r in window) / n),
            "mean_live_slots": (sum(float(r.get("live_slots", 0))
                                    for r in window) / n),
            "tokens_emitted": sum(int(r.get("tokens", 0)) for r in window),
            "retries": sum(int(r.get("retries", 0)) for r in window),
            "degraded_ticks": sum(bool(r.get("degraded"))
                                  for r in window),
            "site_wall_s": {k: round(v, 6)
                            for k, v in sorted(site_wall.items())},
        }
