"""paged_attn_tick_share_pct (%): paged kernel device time over the tick program's device time."""

from chipbench.metrics import _lib as L


def read(obs):
    tr = obs.get('trace')
    tick = L.program_seconds(obs, 'jit__tick_paged')
    if tr is None or not tick or tr['kernel_s'] <= 0:
        return None
    return 100.0 * tr['kernel_s'] / tick
