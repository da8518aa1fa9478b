"""moe_load_max_over_mean.lfm2_extract (ratio): the busiest expert's routed pairs over the mean expert's, in the worst of the routed layers, over the prompt tokens prefilled in the window (the engine's device-side histogram, read at the window's edges)."""

import numpy as np


def read(obs):
    load = obs.get('expert_load') or {}
    ratios = [np.max(v) / np.mean(v) for v in load.values() if np.sum(v) > 0]
    return float(max(ratios)) if ratios else None
