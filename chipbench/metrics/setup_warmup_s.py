"""setup_warmup_s (s): of set-up, the engine's warmup: the first call of each resident program (trace, lower, compile or load from the persistent compile cache, dispatch) and the closing wait for their first execution (ServeMetrics setup_wall_s[warmup:*] and [warmup_wait] at the open)."""

from chipbench.metrics import _setup as S


def read(obs):
    stages = S.at_open(obs, 'setup_wall_s')
    if not isinstance(stages, dict):
        return None
    return sum(w for k, w in stages.items()
               if k.startswith('warmup:') or k == 'warmup_wait')
