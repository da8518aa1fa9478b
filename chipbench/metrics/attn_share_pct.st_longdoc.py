"""attn_share_pct.st_longdoc (%): device time under the attention scopes (attn_window, attn_global: cache write, chunk sweep, paged kernel) over device busy, traced stretch."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    spent = sum(sc['scope_s'].get(k, 0.0)
                for k in ('attn_window', 'attn_global'))
    return 100.0 * spent / sc['total_s'] if spent else None
