"""decode_tick_ms_p50 (ms): median harness span around decode-only engine.step() calls (steps whose only dispatch site was the tick)."""

from chipbench.metrics import _lib as L


def read(obs):
    d = [s['t1'] - s['t0'] for s in L.window_steps(obs) if set(s['sites']) == {'tick'}]
    return None if not d else 1e3 * L.pct(d, 50)
