"""tpot_p90_ms (ms): 90th percentile over the same requests of (last token - first token) / (tokens - 1)."""

from chipbench.metrics import _lib as L


def read(obs):
    return 1e3 * L.pct(L.tpot_s(obs), 90)
