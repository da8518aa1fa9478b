"""kv_pool_used_pct (%): mean over the window's steps of pool blocks the live slots' tables point at, over pool blocks (engine gauge block_table_fill x live slots x table width)."""

from chipbench.metrics import _lib as L


def read(obs):
    steps = [s for s in L.window_steps(obs) if s['live']]
    if not steps:
        return None
    eng = obs['cfg']['engine']
    width = -(-obs['cfg']['n_positions'] // eng['block_size'])
    used = [s['fill'] * s['live'] * width for s in steps]
    return 100.0 * L.mean(used) / eng['pool_blocks']
