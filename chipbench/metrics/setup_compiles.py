"""setup_compiles (compiles): compiles the engine ran before the window that the persistent compile cache took in, in its set-up stages and in the steps set-up takes (ServeMetrics compile_cache_misses summed at the open): 0 where the cache held every program, above 0 in a run that compiled; eager ops that compile under the cache's floor, in every process, are not among them."""

from chipbench.metrics import _setup as S


def read(obs):
    misses = S.at_open(obs, 'compile_cache_misses')
    if not isinstance(misses, dict):
        return None
    return sum(misses.values())
