"""Find the knee once: the open-loop mix of a cell at several fixed rates,
in one process. For each rate: what was offered, what completed, the
tails, and whether the backlog grew (queue depth over the window's last
third against its first). The cell's traffic file then fixes the rate at
about four fifths of the highest rate that held.

    python3 chipbench/tools/sweep.py --workload gpt2l_chat_steady \\
        --rates 0.6,0.8,1.0,1.2 --seconds 40 --seed 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--residence-s", type=float, default=30.0,
                   help="a stream's mean stay: the ramp admits rate x "
                        "this many streams before the window opens")
    args = p.parse_args(argv)
    from chipbench import run
    from chipbench.metrics import _lib as L

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"sweep_{args.workload}.jsonl")
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        seen = {}

        def overrides(cfg, spec, rate=rate):
            spec["rate_per_s"] = rate
            spec["ramp_live"] = min(cfg["engine"]["max_slots"] - 2,
                                    round(rate * args.residence_s))
            cfg["check"] = dict(cfg["check"], requests=1, sampled_requests=1,
                                min_tokens=1)

        def keep(obs, seen=seen):
            seen["obs"] = obs

        out = run.run_cell(args.workload, args.seed + k, args.seconds,
                           False, overrides=overrides, observe=keep)
        obs = seen["obs"]
        steps = L.window_steps(obs)
        third = max(1, len(steps) // 3)
        ttft, tpot = L.ttft_s(obs), L.tpot_s(obs)
        line = {
            "rate_per_s": rate, "requests": len(obs["judged"]),
            "failed": out["failed"],
            "queue_first_third": float(np.mean(
                [s["queue"] for s in steps[:third]])),
            "queue_last_third": float(np.mean(
                [s["queue"] for s in steps[-third:]])),
            "live_first_third": float(np.mean(
                [s["live"] for s in steps[:third]])),
            "live_last_third": float(np.mean(
                [s["live"] for s in steps[-third:]])),
            "live_max": max(s["live"] for s in steps),
            "out_tok_per_s_window": sum(s["tokens"] for s in steps)
            / obs["facts"]["window_s"],
            "offered_tok_per_s": sum(r["max_new_tokens"]
                                     for r in obs["judged"])
            / obs["seconds"],
            "ttft_mean_ms": 1e3 * L.mean(ttft),
            "ttft_p50_ms": 1e3 * L.pct(ttft, 50),
            "ttft_p90_ms": 1e3 * L.pct(ttft, 90),
            "tpot_p50_ms": 1e3 * L.pct(tpot, 50),
            "tpot_p90_ms": 1e3 * L.pct(tpot, 90),
            "drain_s": obs["facts"]["drain_s"],
            "gen_late_p99_ms": run.metric_reader("gen_late_p99_ms")(obs),
            "step_ms_p50": 1e3 * L.pct(
                [s["t1"] - s["t0"] for s in steps], 50),
            "long_steps": obs["facts"]["stalls"]["long_steps"],
            "long_steps_s": obs["facts"]["stalls"]["long_steps_s"],
        }
        print(json.dumps(line), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
