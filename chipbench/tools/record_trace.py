"""Cut a kept trace (``CHIPBENCH_KEEP_TRACE=<file>`` on a traced run) down
to a few harness steps and store it as the small recorded trace that
``tests/test_trace_reduce.py`` checks the reduction on.

    python3 chipbench/tools/record_trace.py <events.json> <out.json.gz> <first step> <steps>
"""

from __future__ import annotations

import gzip
import json
import sys

from chipbench.trace_reduce import STEP_SPAN


def cut(events: dict, first: int, count: int) -> dict:
    spans = sorted((h for h in events["host"] if h[0] == STEP_SPAN),
                   key=lambda h: h[1])[first:first + count]
    t0, t1 = spans[0][1], spans[-1][1] + spans[-1][2]
    keep = lambda s, d: s + d > t0 and s < t1
    r6 = lambda x: round(x - t0, 9)
    return {
        "devices": [{
            "name": d["name"],
            "ops": [[o[0], r6(o[1]), round(o[2], 9), *o[3:]]
                    for o in d["ops"] if keep(o[1], o[2])],
            "modules": [[m[0], r6(m[1]), round(m[2], 9)]
                        for m in d["modules"] if keep(m[1], m[2])],
        } for d in events["devices"]],
        # host spans long enough to explain an idle gap, and the steps
        "host": [[h[0], r6(h[1]), round(h[2], 9), h[3]]
                 for h in events["host"]
                 if keep(h[1], h[2]) and (h[2] >= 20e-6 or h[0] == STEP_SPAN)],
    }


if __name__ == "__main__":
    src, dst, first, count = sys.argv[1:5]
    with open(src) as f:
        small = cut(json.load(f), int(first), int(count))
    with gzip.open(dst, "wt") as f:
        json.dump(small, f, separators=(",", ":"))
    print({"ops": sum(len(d["ops"]) for d in small["devices"]),
           "host": len(small["host"])})
