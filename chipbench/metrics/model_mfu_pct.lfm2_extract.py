"""model_mfu_pct.lfm2_extract (%): model FLOPs (workmodel_lfm2: the convolution operators' and the attention layers' projections, the router and four routed experts a routed layer, the dense layer's MLP, causal attention over the two attention layers for prompt and decoded tokens, the head on sampled rows) of every token prefilled or decoded in the traced stretch over stretch x 197 TFLOP/s: the share of the whole step."""

from chipbench import workmodel_lfm2 as W


def read(obs):
    work, tr = obs.get('work') or {}, obs.get('trace')
    if tr is None or not (work.get('prefills') or work.get('decodes')):
        return None
    flops = W.tokens_flops(obs['cfg'], work['prefills'], work['decodes'])
    return 100.0 * flops / (tr['window_s'] * tr['chips']
                            * obs['peaks']['bf16_flops_per_s'])
