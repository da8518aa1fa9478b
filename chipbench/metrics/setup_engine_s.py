"""setup_engine_s (s): the engine's share of set-up: every set-up stage of ServeEngine (build, the first call of each resident program, the closing wait) and every engine step taken before the window opens (warm requests, ramp or backlog, probes), on the host's clock (ServeMetrics setup_wall_s summed + step_wall_s, both at the open)."""

from chipbench.metrics import _setup as S


def read(obs):
    return S.engine_s(obs)
