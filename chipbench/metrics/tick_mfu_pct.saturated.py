"""tick_mfu_pct.saturated (%): model FLOPs of every token processed (decoded or prefilled) in the traced window over window x chip peak."""

from chipbench.metrics import _lib as L


def read(obs):
    return L.tick_mfu_pct(obs)
