"""ttft_p90_ms.st_longdoc (ms): 90th percentile, over all requests due in the window, of first streamed token minus the due instant; a miss counts as the largest."""

from chipbench.metrics import _lib as L


def read(obs):
    return 1e3 * L.pct(L.ttft_s(obs), 90)
