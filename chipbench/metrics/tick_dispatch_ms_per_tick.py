"""tick_dispatch_ms_per_tick (ms): per decode tick of the window: wall of building the tick's arguments and dispatching it (ServeMetrics phase_wall_s[tick_dispatch] / decode_ticks); window-wide, so in a traced run, where collecting the trace slows the host for the rest of the window, it reads about double (PERF.md section 6): hold a traced reading against traced readings only, the run's log has the untraced one."""

from chipbench.metrics import _phases as P


def read(obs):
    return P.per(obs, 1e3, ('phase_wall_s', 'tick_dispatch'), 'decode_ticks')
