"""prefill_busy_share_pct.lfm2_extract (%): device time of the chunk-prefill programs (jit__chunk_paged*) over device busy, traced stretch: the share of the chip that admitting the replacements takes from decoding."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    return 100.0 * sc['chunk_s'] / sc['total_s']
