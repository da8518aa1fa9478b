"""Arithmetic the readers of the engine's set-up counters share.

The engine keeps, always on, cumulative counters of its own set-up
(``ServeMetrics.snapshot()``: ``setup_wall_s`` by stage — ``build``,
``warmup:<site>``, ``warmup_wait`` — and ``compiles``,
``compile_cache_misses``, ``compile_cache_hits`` by the phase or stage
that was open), beside ``step_wall_s``; the harness carries the whole
snapshot at the window's open (``obs["facts"]["counters_open"]``), so the
steps set-up takes (warm requests, ramp, backlog, probes) are in it too.
Where the program has no such counter (an older program) there is
nothing to read and a reader returns ``None``.
"""

from __future__ import annotations


def at_open(obs, key):
    """Counter ``key`` in the snapshot taken at the window's open, or
    ``None`` where the program has no such counter."""
    facts = obs.get("facts") or {}
    return (facts.get("counters_open") or {}).get(key)


def engine_s(obs):
    """The engine's seconds before the window: every set-up stage and
    every step taken before the open."""
    stages, steps = at_open(obs, "setup_wall_s"), at_open(obs,
                                                          "step_wall_s")
    if not isinstance(stages, dict) or steps is None:
        return None
    return sum(stages.values()) + steps
