"""tpot_mean_ms (ms): mean over the requests of (last token - first token) / (tokens - 1)."""

from chipbench.metrics import _lib as L


def read(obs):
    return 1e3 * L.mean(L.tpot_s(obs))
