"""moe_share_pct.lfm2_extract (%): device time under the expert scopes (moe_router, moe_dispatch, moe_ffn, moe_combine) over device busy, traced stretch."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    spent = sum(sc['scope_s'].get(k, 0.0) for k in (
        'moe_router', 'moe_dispatch', 'moe_ffn', 'moe_combine'))
    return 100.0 * spent / sc['total_s'] if spent else None
