"""mla_prefill_roofline_pct.glm_agent (%): causal per-head score and value FLOPs (256 + 256 a head and key) of the prompts prefilled in the traced stretch over 197 TFLOP/s, over the device time under attn_latent (mla_expand inside it: the re-expansion counts as time, not as work) inside the chunk programs."""

from chipbench import workmodel_mla_moe as W
from chipbench.reference.glm47_flash import shape_of


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work.get('prefills'):
        return None
    spent = sum(sc['chunk_scope_s'].get(k, 0.0)
                for k in ('attn_latent', 'mla_expand', 'mla_absorb'))
    if spent <= 0:
        return None
    keys = shape_of(obs['cfg'])['layers'] * sum(
        W.causal_keys(0, p) for p in work['prefills'])
    least = W.prefill_attn_flops(obs['cfg'], keys) \
        / obs['peaks']['bf16_flops_per_s']
    return 100.0 * least / spent
