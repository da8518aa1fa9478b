"""admit_stall_share_pct (%): share of the engine steps' wall, window and drain, spent in the admit phase, in which no live stream gets a token (ServeMetrics phase_wall_s[admit] / step_wall_s)."""

from chipbench.metrics import _phases as P


def read(obs):
    return P.per(obs, 100.0, ('phase_wall_s', 'admit'), 'step_wall_s')
