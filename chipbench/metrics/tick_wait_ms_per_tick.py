"""tick_wait_ms_per_tick (ms): per decode tick of the window: wall the host waited for the tick's tokens (ServeMetrics phase_wall_s[tick_wait] / decode_ticks)."""

from chipbench.metrics import _phases as P


def read(obs):
    return P.per(obs, 1e3, ('phase_wall_s', 'tick_wait'), 'decode_ticks')
