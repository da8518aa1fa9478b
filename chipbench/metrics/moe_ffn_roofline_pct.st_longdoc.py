"""moe_ffn_roofline_pct.st_longdoc (%): least time for the routed pairs' FLOPs and the bytes of the experts hit (per program call and layer the larger of the two; workmodel_moe) over the device time under the expert scopes moe_dispatch, moe_ffn, moe_combine."""

from chipbench import workmodel_moe as W


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work:
        return None
    spent = sum(sc['scope_s'].get(k, 0.0)
                for k in ('moe_dispatch', 'moe_ffn', 'moe_combine'))
    calls = list(work['chunk_calls']) + list(work['tick_rows'])
    if spent <= 0 or not calls:
        return None
    return 100.0 * W.moe_least_seconds(obs['cfg'], calls,
                                       obs['peaks']) / spent
