"""model_mfu_pct.glm_agent (%): model FLOPs (workmodel_mla_moe: projections, router, four routed experts and the shared one, the dense layer, per-head causal attention for prompt tokens and the absorbed form, 43,520 x context a layer, for decoded ones, the head on sampled rows) of every token prefilled or decoded in the traced stretch over stretch x 197 TFLOP/s: the share of the whole step."""

from chipbench import workmodel_mla_moe as W


def read(obs):
    work, tr = obs.get('work') or {}, obs.get('trace')
    if tr is None or not (work.get('prefills') or work.get('decodes')):
        return None
    flops = W.tokens_flops(obs['cfg'], work['prefills'], work['decodes'])
    return 100.0 * flops / (tr['window_s'] * tr['chips']
                            * obs['peaks']['bf16_flops_per_s'])
