"""Arithmetic the metric readers share. A reader takes the run's
observations (``obs``) and returns a number, or ``None`` where there is
nothing to read — the harness then leaves the metric out of the line; a
share of a roofline or of a peak is never returned as 0.

``obs`` (serving): ``requests`` (one dict per request: due_s, submit_s,
admit_step_s, first_s, last_s, n, ok, ...), ``judged`` (those the cell's
tails are over), ``steps`` (one per ``engine.step()``: t0, t1, sites,
live, fill, token counts), ``facts`` (window_s, trace step range, the engine's
``metrics.snapshot()`` at the open and close of the window),
``trace`` (``trace_reduce.reduce`` output, traced runs only), ``cfg``,
``traffic``, ``peaks``, ``seconds``, ``drain_limit_s``, ``setup_s``.
"""

from __future__ import annotations

import numpy as np

from chipbench import workmodel

def _miss_s(obs, r):
    """A miss counts as the largest: the whole wait to the drain's end."""
    return obs["seconds"] + obs["drain_limit_s"] - r["due_s"]


def ttft_s(obs):
    return [(r["first_s"] - r["due_s"]) if r["first_s"] is not None
            else _miss_s(obs, r) for r in obs["judged"]]


def tpot_s(obs):
    out = []
    for r in obs["judged"]:
        if r["ok"] and r["n"] >= 2:
            out.append((r["last_s"] - r["first_s"]) / (r["n"] - 1))
        elif not r["ok"]:
            out.append(_miss_s(obs, r))
    return out


def pct(values, q):
    return float(np.percentile(values, q)) if len(values) else None


def mean(values):
    return float(np.mean(values)) if len(values) else None


def window_steps(obs):
    w = obs["facts"]["steps_in_window"]
    return obs["steps"][:w]


def traced_steps(obs):
    t = obs["facts"].get("trace")
    if not t or obs.get("trace") is None:
        return []
    return obs["steps"][t["step0"]:t["step1"]]


def tick_mfu_pct(obs):
    steps, tr = traced_steps(obs), obs.get("trace")
    if not steps or tr is None:
        return None
    flops = workmodel.gpt_tokens_flops(
        obs["cfg"], sum(s["decode_ctx"] for s in steps),
        sum(s["decode_tokens"] for s in steps),
        sum(s["prefill_tokens"] for s in steps),
        sum(s["prefill_ctx"] for s in steps))
    if flops <= 0:
        return None
    return 100.0 * flops / (tr["window_s"] * tr["chips"]
                            * obs["peaks"]["bf16_flops_per_s"])


def paged_attn_roofline_pct(obs):
    steps, tr = traced_steps(obs), obs.get("trace")
    if not steps or tr is None or tr["kernel_s"] <= 0:
        return None
    ctx = sum(s["decode_ctx"] for s in steps)
    if ctx <= 0:
        return None
    least = workmodel.paged_attn_least_seconds(obs["cfg"], ctx,
                                               obs["peaks"])
    return 100.0 * least["seconds"] / tr["kernel_s"]


def device_idle_pct(obs):
    tr = obs.get("trace")
    return None if tr is None else tr["idle_pct"]


def program_seconds(obs, *names):
    tr = obs.get("trace")
    if tr is None:
        return None
    total = sum(p["seconds"] for k, p in tr["programs"].items()
                if any(k.startswith(n) for n in names))
    return total or None
