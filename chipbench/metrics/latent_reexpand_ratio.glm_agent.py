"""latent_reexpand_ratio.glm_agent (ratio): cached tokens the chunk programs re-expanded to per-head keys and values over prompt tokens prefilled, window-wide (ServeMetrics latent_expanded_tokens / prefill_tokens, close less open): how many times a prompt token's entry is expanded again after its own chunk."""

from chipbench.metrics import _phases as P


def read(obs):
    return P.per(obs, 1.0, 'latent_expanded_tokens', 'prefill_tokens')
