"""One run of a cell with the program's per-request tracer installed.

    python3 chipbench/tools/request_tracer.py --workload <name> --seed <n> \\
        --seconds <s>

What ``pddl_tpu.obs.RequestTracer`` costs on the chip: this run's
end-to-end numbers beside those of ``run.py`` with the same seed. The
tracer is installed from outside, as ``tools/faults.py`` plants its
faults — the system module's ``build`` is wrapped so that the engine it
returns carries a ``RequestTracer()`` (spans kept in memory, no sink) —
and not by a switch in the program or in the harness. Prints the run's
result line with the tracer's own counts added under ``request_tracer``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def install(system):
    """Wrap ``system.build``; returns (the tracers built, undo)."""
    from pddl_tpu.obs import RequestTracer

    real, tracers = system.build, []

    def build(*args, **kwargs):
        engine, variables = real(*args, **kwargs)
        tracers.append(RequestTracer())
        engine.set_tracer(tracers[-1])
        return engine, variables

    system.build = build
    return tracers, lambda: setattr(system, "build", real)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--allow-cpu", action="store_true",
                   help="the CPU rehearsal of this tool (toy sizes)")
    args = p.parse_args(argv)
    from chipbench import rehearse, run

    bench = run.load_benchmark()
    _, cfg = run.find_cell(bench, args.workload)
    system = importlib.import_module("chipbench.systems." + cfg["system"])
    tracers, undo = install(system)
    try:
        out = run.run_cell(
            args.workload, args.seed, args.seconds, False,
            allow_cpu=args.allow_cpu,
            overrides=rehearse.shrink if args.allow_cpu else None)
    finally:
        undo()
    (tracer,) = tracers
    out["request_tracer"] = {
        "spans_started": tracer.spans_started,
        "spans_finished": tracer.spans_finished,
        "events_kept": sum(len(r["events"]) for r in tracer.finished),
        "events_dropped": sum(r["events_dropped"]
                              for r in tracer.finished)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
