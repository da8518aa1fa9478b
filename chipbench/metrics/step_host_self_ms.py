"""step_host_self_ms (ms): per engine step of the window: the step's wall less its two host<-device reads (phase_wall_s[tick_wait] and [first_token_wait]), on the host's clock (ServeMetrics step_wall_s, engine_steps); window-wide, so in a traced run, where collecting the trace slows the host for the rest of the window, it reads about double (PERF.md section 6): hold a traced reading against traced readings only, the run's log has the untraced one."""

from chipbench.metrics import _phases as P


def read(obs):
    wall = P.delta(obs, 'step_wall_s')
    waits = [P.delta(obs, 'phase_wall_s', p)
             for p in ('tick_wait', 'first_token_wait')]
    steps = P.delta(obs, 'engine_steps')
    if wall is None or None in waits or not steps:
        return None
    return 1e3 * (wall - sum(waits)) / steps
