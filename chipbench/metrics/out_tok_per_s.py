"""out_tok_per_s (tokens/s): all output tokens streamed inside the window over the window's seconds."""

from chipbench.metrics import _lib as L


def read(obs):
    steps = L.window_steps(obs)
    return sum(s['tokens'] for s in steps) / obs['facts']['window_s']
