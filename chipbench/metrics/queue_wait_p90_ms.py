"""queue_wait_p90_ms (ms): 90th percentile of: start of the engine step that admitted the request, minus its due instant."""

from chipbench.metrics import _lib as L


def read(obs):
    w = [r['admit_step_s'] - r['due_s'] for r in obs['judged'] if r['admit_step_s'] is not None]
    return None if not w else 1e3 * L.pct(w, 90)
