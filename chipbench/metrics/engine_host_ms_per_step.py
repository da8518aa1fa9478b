"""engine_host_ms_per_step (ms): mean over the traced steps of the harness span around engine.step() less the device-busy time inside it, both on the trace's clock."""

from chipbench.metrics import _lib as L


def read(obs):
    tr = obs.get('trace')
    if tr is None or not tr['steps']:
        return None
    return 1e3 * L.mean([s['t1'] - s['t0'] - s['busy_s'] for s in tr['steps']])
