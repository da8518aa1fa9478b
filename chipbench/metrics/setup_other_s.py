"""setup_other_s (s): of set-up, what is not the engine's: imports, device discovery, the weights drawn, the harness's own work (setup_s less setup_engine_s, so the two sum to setup_s)."""

from chipbench.metrics import _setup as S


def read(obs):
    engine = S.engine_s(obs)
    if engine is None or obs.get('setup_s') is None:
        return None
    return obs['setup_s'] - engine
