"""device_idle_pct.st_longdoc (%): 1 - union of device-op intervals over the traced window."""

from chipbench.metrics import _lib as L


def read(obs):
    return L.device_idle_pct(obs)
