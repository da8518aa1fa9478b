"""prefill_attn_roofline_pct.st_longdoc (%): banded-causal attention FLOPs of the prompts prefilled in the traced stretch (window layers at min(t + 1, 4096) keys) over 197 TFLOP/s, over the device time under the attention scopes inside the chunk programs."""

from chipbench import workmodel_moe as W


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work.get('prefills') or sc['chunk_attn_s'] <= 0:
        return None
    keys = sum(W.keys_seen(obs['cfg'], 0, p) for p in work['prefills'])
    least = W.attn_flops(obs['cfg'], keys) / obs['peaks']['bf16_flops_per_s']
    return 100.0 * least / sc['chunk_attn_s']
