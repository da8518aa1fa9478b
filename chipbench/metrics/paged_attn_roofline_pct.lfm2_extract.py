"""paged_attn_roofline_pct.lfm2_extract (%): least time for the K and V bytes (2,048 B a key and attention layer) and the q.k / p.v FLOPs the live slots need at their contexts over the Mosaic paged kernel's device time in the ticks (32 q heads over 8 kv heads of 64)."""

from chipbench import workmodel_lfm2 as W


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work.get('decodes'):
        return None
    spent = sum(sc['kernel_s'].values())
    if spent <= 0:
        return None
    return 100.0 * W.paged_attn_least_seconds(
        obs['cfg'], work['decodes'], obs['peaks']) / spent
