"""append_blocks_ms_per_step (ms): per engine step of the window: wall of growing the live slots' block tables before the tick, one RadixPrefixCache.allocate a slot at a block boundary, any reclaim it forces included (ServeMetrics phase_wall_s[append_blocks] / engine_steps); window-wide, so a traced run, where collecting the trace slows the host, reads higher: hold a traced reading against traced readings only."""

from chipbench.metrics import _phases as P


def read(obs):
    return P.per(obs, 1e3, ('phase_wall_s', 'append_blocks'), 'engine_steps')
