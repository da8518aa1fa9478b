"""The GLM-4.7-Flash cell's timed path broken underneath, one fault at a
time (``tools/faults.py``'s stay as they are and are offered here too):
what ``chipbench/tests/test_glm47_flash.py`` plants to see ``correct`` come
out false, and what a run on the chip plants at the cell's own size:

    python3 chipbench/tools/faults_glm47_flash.py --fault top3 \\
        --workload glm47f_agent_saturated --seed 7 --seconds 50

    undo = plant("no_shared"); ...; undo()
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.tools import faults  # noqa: E402


def _top3():
    """The fourth expert dropped: the serving expert path is handed the
    gates of the three likeliest alone (the last choice's gate zeroed)."""
    import pddl_tpu.ops.moe as moe_mod

    real = moe_mod.grouped_expert_ffn

    def three(x, expert_index, gates, *args, **kw):
        return real(x, expert_index, gates.at[:, -1].set(0.0), *args, **kw)

    moe_mod.grouped_expert_ffn = three
    return lambda: setattr(moe_mod, "grouped_expert_ffn", real)


def _no_shared():
    """The shared expert dropped: the routed sum goes on alone."""
    import pddl_tpu.ops.moe as moe_mod

    real = moe_mod.SwitchFFN._plus_shared
    moe_mod.SwitchFFN._plus_shared = lambda self, y, x, hidden: y
    return lambda: setattr(moe_mod.SwitchFFN, "_plus_shared", real)


FAULTS = dict(faults.FAULTS, top3=_top3, no_shared=_no_shared)


def plant(name: str):
    """As ``tools/faults.plant``: the traced programs are dropped on the
    way in and out."""
    import jax

    jax.clear_caches()
    undo = FAULTS[name]()

    def take_out():
        undo()
        jax.clear_caches()

    return take_out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--fault", required=True, choices=sorted(FAULTS))
    p.add_argument("--workload", default="glm47f_agent_saturated")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=50.0)
    args = p.parse_args(argv)
    from chipbench import run

    plant(args.fault)
    out = run.run_cell(args.workload, args.seed, args.seconds, False)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "fault": args.fault, "correct": out["correct"],
                      "attempted": out["attempted"],
                      "failed": out["failed"], "checks": out["checks"],
                      "metrics": out["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
