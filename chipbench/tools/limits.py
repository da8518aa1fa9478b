"""Readings that a limit of ``correct`` is set from: the program's numbers
and the control's in its place, over several seeds, in ONE process (set-up
is long), each seed a window at the cell's own load, long enough to finish
the mix's longest requests. ``--control`` names the reference's lower
precisions (each judged by the harness's own ``decide`` in the program's
place), ``--program-path int8`` switches on the program's own int8 path,
``--fault`` breaks the timed path underneath (``tools/faults.py``).

    python3 chipbench/tools/limits.py --workload gpt2l_chat_saturated \\
        --seeds 11,12,13 --seconds 60 --control int8,int8w,fp8

One JSON line per seed on stdout and in ``chiprun_out/limits_<workload>.jsonl``.
Not part of a benchmark run: the driver's command never runs the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--control", default=None)
    p.add_argument("--program-path", default=None)
    p.add_argument("--fault", default=None)
    p.add_argument("--requests", type=int, default=None,
                   help="requests the reference follows (default: the "
                        "configuration's)")
    args = p.parse_args(argv)
    from chipbench import run

    if args.fault:
        from chipbench.tools import faults

        faults.plant(args.fault)

    def overrides(cfg, spec):
        if args.requests:
            cfg["check"] = dict(cfg["check"], requests=args.requests)

    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out",
                        f"limits_{args.workload}.jsonl")
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           control=args.control, overrides=overrides,
                           program_path=args.program_path)
        line = {"workload": args.workload, "seed": seed,
                "fault": args.fault, "program_path": args.program_path,
                "seconds": args.seconds, "correct": out["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "checks": out["checks"], "control": out.get("control"),
                "metrics": out["metrics"]}
        print(json.dumps(line), flush=True)
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
