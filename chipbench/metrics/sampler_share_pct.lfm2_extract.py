"""sampler_share_pct.lfm2_extract (%): device time of the tick's and the first-token program's ops outside the model (the sampling fusions and the sort over 48 x 65,536 logits) over device busy: what the cell measures besides the model."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    return 100.0 * sc['scope_s'].get('sampler', 0.0) / sc['total_s']
