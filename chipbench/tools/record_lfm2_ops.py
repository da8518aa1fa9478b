"""Record, on the chip, the small stretch of the LFM2 cell's device trace
that ``chipbench/tests/test_lfm2.py`` puts down to scopes: one traced run of
the cell, the events and the compiled programs' op names as the system
module's own ``reduce_scopes`` was handed them, cut to the first stretch
that holds a whole chunk program and a few ticks around it.

    chiprun -- python3 chipbench/tools/record_lfm2_ops.py --seed 7 \\
        --out chiprun_out/lfm2_extract_ops.json.gz

The file is a few hundred kilobytes; it goes to
``chipbench/recorded/lfm2_extract_ops.json.gz``.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def cut(events: dict, table: dict, t0: float, t1: float,
        ticks_around: int = 3) -> dict:
    """The stretch from ``ticks_around`` ticks before the first whole
    chunk program inside [t0, t1] to as many after it: device ops and
    modules only, times from the stretch's start, and the op names the
    stretch uses."""
    dev = events["devices"][0]
    inside = sorted((m for m in dev["modules"]
                     if m[1] >= t0 and m[1] + m[2] <= t1),
                    key=lambda m: m[1])
    first = next(i for i, m in enumerate(inside)
                 if m[0].startswith("jit__chunk_paged"))
    lo = inside[max(0, first - ticks_around)]
    hi = inside[min(len(inside) - 1, first + ticks_around)]
    a, b = lo[1], hi[1] + hi[2]
    keep = lambda s, d: s >= a and s + d <= b
    r9 = lambda x: round(x - a, 9)
    modules = [[m[0], r9(m[1]), round(m[2], 9)] for m in dev["modules"]
               if keep(m[1], m[2])]
    ops = [[o[0], r9(o[1]), round(o[2], 9), *o[3:]] for o in dev["ops"]
           if keep(o[1], o[2])]
    used = {o[0] for o in ops}
    names = {re.sub(r"\(.*$", "", m[0]) for m in modules}
    return {"events": {"host": [], "devices": [{
                "name": dev.get("name", ""), "modules": modules,
                "ops": ops}]},
            "table": {module: {k: v for k, v in t.items() if k in used}
                      for module, t in table.items() if module in names},
            "edges": [0.0, round(b - a, 9)]}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", default="lfm2_extract_saturated")
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    from chipbench import run
    from chipbench.systems import serve_paged_lfm2 as system

    seen = {}
    real = system.reduce_scopes

    def recording(events, table, t0, t1):
        seen.update(events=events, table=table, t0=t0, t1=t1)
        return real(events, table, t0, t1)

    system.reduce_scopes = recording
    out = run.run_cell(args.workload, args.seed, args.seconds, True)
    small = cut(seen["events"], seen["table"], seen["t0"], seen["t1"])
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(small, f, separators=(",", ":"))
    print(json.dumps({
        "ops": len(small["events"]["devices"][0]["ops"]),
        "modules": [m[0] for m in small["events"]["devices"][0]["modules"]],
        "bytes": os.path.getsize(args.out)}), file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
