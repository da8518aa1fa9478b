"""shortconv_share_pct.lfm2_extract (%): device time under the scope shortconv (the seven convolution operators: in projection, gates and taps, state read and write, out projection) over device busy, traced stretch."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    spent = sc['scope_s'].get('shortconv', 0.0)
    return 100.0 * spent / sc['total_s'] if spent else None
