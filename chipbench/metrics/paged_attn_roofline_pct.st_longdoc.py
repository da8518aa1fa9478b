"""paged_attn_roofline_pct.st_longdoc (%): least time for the KV bytes and FLOPs the live slots need at their contexts (window layers at min(context, 4096)) over the Mosaic paged kernel's device time."""

from chipbench import workmodel_moe as W


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work.get('decodes'):
        return None
    spent = sum(sc['kernel_s'].values())
    if spent <= 0:
        return None
    return 100.0 * W.paged_attn_least_seconds(
        obs['cfg'], work['decodes'], obs['peaks']) / spent
