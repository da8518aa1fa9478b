"""The tiny CPU rehearsal: the harness end to end at a toy size, with no
chip. An entry of the harness's own, apart from the driver's command
(``run.py`` never runs off a TPU). It prints what was counted and whether
the run came out correct — and NO number under a device metric's name: a
time taken here says how fast the CPU backend is, which nobody deploys.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload gpt2l_chat_steady
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

TINY_GPT = {"n_layer": 2, "n_embd": 64, "n_head": 4, "n_positions": 128,
            "n_ctx": 128, "vocab_size": 512,
            "engine": {"max_slots": 4, "block_size": 8, "pool_blocks": 96,
                       "prefill_len": 64, "max_queue_depth": 64}}


def shrink(cfg: dict, spec: dict) -> None:
    """Toy sizes for the CPU; the check keeps its shape (same comparison,
    fewer tokens asked for)."""
    if cfg["system"] == "serve_paged_gpt":
        cfg.update(TINY_GPT)
        cfg["check"] = dict(cfg["check"], max_rows=64, min_tokens=8,
                            requests=3, sampled_requests=3)
        if "ramp_live" in spec:
            spec["ramp_live"] = 2


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=4.0)
    p.add_argument("--control", default=None)
    p.add_argument("--program-path", default=None)
    args = p.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from chipbench import run

    out = run.run_cell(args.workload, args.seed, args.seconds, False,
                       allow_cpu=True, control=args.control,
                       program_path=args.program_path, overrides=shrink)
    print(json.dumps({"rehearsal": True, "correct": out["correct"],
                      "attempted": out["attempted"],
                      "failed": out["failed"], "checks": out["checks"],
                      "control": out.get("control"),
                      "device": {"platform": out["device"]["platform"]}}))
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
