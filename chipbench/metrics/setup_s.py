"""setup_s (s): process start to the first measured instant, compilation included."""

from chipbench.metrics import _lib as L


def read(obs):
    return obs['setup_s']
