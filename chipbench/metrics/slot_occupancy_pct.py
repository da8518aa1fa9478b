"""slot_occupancy_pct (%): time-weighted mean over the window's steps of live slots over max_slots (engine telemetry ring)."""

import numpy as np

from chipbench.metrics import _lib as L


def read(obs):
    steps = L.window_steps(obs)
    if not steps:
        return None
    w = [s['t1'] - s['t0'] for s in steps]
    live = [s['live'] for s in steps]
    return 100.0 * float(np.average(live, weights=w)) / obs['cfg']['engine']['max_slots']
