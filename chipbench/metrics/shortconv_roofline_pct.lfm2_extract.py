"""shortconv_roofline_pct.lfm2_extract (%): least time for the convolution operators' work (workmodel_lfm2: per program call and layer the larger of FLOPs over 197 TFLOP/s and bytes over 819 GB/s; chunk programs by their real tokens and one state, ticks by their live rows and a state a row; the same work counted whatever implements it) over the device time under the scope shortconv."""

from chipbench import workmodel_lfm2 as W


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work:
        return None
    spent = sc['scope_s'].get('shortconv', 0.0)
    chunks, ticks = list(work['chunk_calls']), list(work['tick_rows'])
    if spent <= 0 or not (chunks or ticks):
        return None
    return 100.0 * W.shortconv_least_seconds(
        obs['cfg'], chunks, ticks, obs['peaks']) / spent
