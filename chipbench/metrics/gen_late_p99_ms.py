"""gen_late_p99_ms (ms): 99th percentile of submit instant minus due instant: how late the generator ran."""

from chipbench.metrics import _lib as L


def read(obs):
    return 1e3 * L.pct([r['submit_s'] - r['due_s'] for r in obs['judged']], 99)
