"""sched_queue_wait_mean_ms (ms): mean over the fresh requests the scheduler popped between the window's open and the drain's end of: scheduler pop minus submit(), on the engine's clock (ServeMetrics queue_wait_s / queue_pops)."""

from chipbench.metrics import _phases as P


def read(obs):
    return P.per(obs, 1e3, 'queue_wait_s', 'queue_pops')
