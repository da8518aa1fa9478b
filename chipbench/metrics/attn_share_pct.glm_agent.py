"""attn_share_pct.glm_agent (%): device time under the latent attention's scopes (attn_latent: cache write, chunk sweep, paged kernel; mla_absorb; mla_expand) over device busy, traced stretch."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    spent = sum(sc['scope_s'].get(k, 0.0)
                for k in ('attn_latent', 'mla_absorb', 'mla_expand'))
    return 100.0 * spent / sc['total_s'] if spent else None
