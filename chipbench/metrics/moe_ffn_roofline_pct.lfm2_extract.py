"""moe_ffn_roofline_pct.lfm2_extract (%): least time for the routed pairs' FLOPs and the bytes of the experts hit at the routing's measured skew (expert_load; per program call and routed layer the larger of the two; workmodel_lfm2) over the device time under the expert scopes moe_dispatch, moe_ffn, moe_combine."""

import numpy as np

from chipbench import workmodel_lfm2 as W


def _shares(obs):
    """Each routed layer's per-expert share of the pairs the engine
    counted in the window, or ``None`` where a layer counted none."""
    loads = [np.asarray(v, np.float64)
             for v in (obs.get('expert_load') or {}).values()]
    if len(loads) != W.layer_counts(obs['cfg'])['routed'] \
            or any(v.sum() <= 0 for v in loads):
        return None
    return [v / v.sum() for v in loads]


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work:
        return None
    spent = sum(sc['scope_s'].get(k, 0.0) for k in (
        'moe_dispatch', 'moe_ffn', 'moe_combine'))
    calls = list(work['chunk_calls']) + list(work['tick_rows'])
    if spent <= 0 or not calls:
        return None
    return 100.0 * W.moe_least_seconds(obs['cfg'], calls, obs['peaks'],
                                       _shares(obs)) / spent
