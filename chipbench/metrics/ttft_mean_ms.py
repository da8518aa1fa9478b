"""ttft_mean_ms (ms): mean over all requests due in the window of first token minus due instant."""

from chipbench.metrics import _lib as L


def read(obs):
    return 1e3 * L.mean(L.ttft_s(obs))
