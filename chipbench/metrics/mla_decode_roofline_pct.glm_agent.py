"""mla_decode_roofline_pct.glm_agent (%): per tick and layer the larger of the live latent entries' bytes (counted per key, 1,152 B, so a kernel that reads padded entries or whole groups cannot pass 100 %) over 819 GB/s and the absorbed FLOPs over 197 TFLOP/s, over the Mosaic paged kernel's device time under the scope attn_latent."""

from chipbench import workmodel_mla_moe as W


def read(obs):
    work, sc = obs.get('work') or {}, obs.get('scopes')
    if not sc or not work.get('decodes'):
        return None
    spent = sc['kernel_s'].get('attn_latent', 0.0)
    if spent <= 0:
        return None
    return 100.0 * W.mla_decode_least_seconds(
        obs['cfg'], work['decodes'], obs['peaks']) / spent
