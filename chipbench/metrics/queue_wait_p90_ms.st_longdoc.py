"""queue_wait_p90_ms.st_longdoc (ms): 90th percentile of: start of the engine step that admitted the request, minus its due instant (the wait for a step boundary and for the admissions ahead in the step)."""

from chipbench.metrics import _lib as L


def read(obs):
    w = [r['admit_step_s'] - r['due_s'] for r in obs['judged']
         if r['admit_step_s'] is not None]
    return None if not w else 1e3 * L.pct(w, 90)
