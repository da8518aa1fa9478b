"""slo_attained_pct (%): share of requests due in the window that met both limits of the traffic file (ttft and tpot); a failed request misses."""

from chipbench.metrics import _lib as L


def read(obs):
    slo = obs['traffic'].get('slo')
    if not slo or not obs['judged']:
        return None
    met = 0
    for r in obs['judged']:
        if not r['ok'] or r['first_s'] is None:
            continue
        ttft = 1e3 * (r['first_s'] - r['due_s'])
        tpot = 1e3 * (r['last_s'] - r['first_s']) / max(1, r['n'] - 1)
        met += ttft <= slo['ttft_ms'] and tpot <= slo['tpot_ms']
    return 100.0 * met / len(obs['judged'])
