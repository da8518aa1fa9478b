"""Arithmetic the readers of the program's phase counters share.

The engine keeps, always on, cumulative counters of where a step's time
goes (``ServeMetrics.snapshot()``: ``engine_steps``, ``step_wall_s``,
``phase_wall_s`` by phase, ``decode_ticks``, ``queue_pops``,
``queue_wait_s``, ``admissions``, ``admit_wall_s``); the harness
carries the whole snapshot at the open and the close of the window
(``obs["facts"]["counters_open"]`` / ``["counters_close"]``: for an open
loop that is window + drain, the stretch the judged tails are over).
A reader takes the close less the open; where the program has no such
counter (an older program) or counted nothing, there is nothing to read
and the reader returns ``None``.
"""

from __future__ import annotations


def delta(obs, key, label=None):
    """Close less open of counter ``key`` (of its ``label`` where the
    counter is a labelled one); ``None`` where either side lacks it."""
    facts = obs.get("facts") or {}
    sides = []
    for side in ("counters_open", "counters_close"):
        value = (facts.get(side) or {}).get(key)
        if label is not None:
            value = value.get(label) if isinstance(value, dict) else None
        if value is None:
            return None
        sides.append(value)
    return sides[1] - sides[0]


def per(obs, scale, num, den):
    """``scale`` x delta of ``num`` over delta of ``den`` (each a key or
    a (key, label) pair); ``None`` on a missing key or a zero count."""
    d = [delta(obs, *([x] if isinstance(x, str) else x))
         for x in (num, den)]
    if d[0] is None or not d[1]:
        return None
    return scale * d[0] / d[1]
