"""attn_share_pct.lfm2_extract (%): device time under the attention scope attn_global (the two GQA layers: cache write, chunk sweep, paged kernel) over device busy, traced stretch."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    spent = sc['scope_s'].get('attn_global', 0.0)
    return 100.0 * spent / sc['total_s'] if spent else None
