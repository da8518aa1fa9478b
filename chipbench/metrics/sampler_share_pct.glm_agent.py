"""sampler_share_pct.glm_agent (%): device time of the tick's and the first-token program's ops outside the model (the sampling fusions and the sort over 40 x 154,880 logits) over device busy: what the cell measures besides the model."""

def read(obs):
    sc = obs.get('scopes')
    if not sc or sc['total_s'] <= 0:
        return None
    return 100.0 * sc['scope_s'].get('sampler', 0.0) / sc['total_s']
