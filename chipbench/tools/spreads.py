"""The readings of sets of runs, and their spreads as PERF.md section 2
defines one: the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) over the median.

    python3 chipbench/tools/spreads.py chiprun_out/pr33/setA_*.out \\
        -- chiprun_out/pr33/setB_*.out

Each file holds one run's standard output (the result is its last line);
``--`` parts the sets. Prints, per set and metric, every reading in the
order given, the median, the spread and the spread without the run
farthest from the median; then per metric the bounds the driver's check
admits on these readings: at least twice the mean of the sets' spreads
without the farthest run (under that it is too tight), at most eight
times the widest spread (over that, too loose).
"""

from __future__ import annotations

import json
import statistics
import sys


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values):
    """Without the run farthest from the median: what the driver's check
    of a bound's tightness takes, so that one far-off run does no harm."""
    m = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - m))
    return [v for i, v in enumerate(values) if i != far]


def read_set(paths):
    out = {}
    for path in paths:
        with open(path) as f:
            line = [x for x in f.read().splitlines() if x.strip()][-1]
        result = json.loads(line)
        if not result["correct"]:
            print(f"NOT CORRECT: {path}")
        for name, m in result["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    sets, cur = [], []
    for a in argv + ["--"]:
        if a == "--":
            if cur:
                sets.append(read_set(cur))
            cur = []
        else:
            cur.append(a)
    widest, less = {}, {}
    for k, readings in enumerate(sets):
        for name, values in readings.items():
            if len(values) < 3:
                print(f"set {k + 1} {name}: {values} (too few for a spread)")
                continue
            sp, cut = spread(values), spread(trimmed(values))
            widest[name] = max(widest.get(name, 0.0), sp)
            less.setdefault(name, []).append(cut)
            print(f"set {k + 1} {name}: {[round(v, 4) for v in values]} "
                  f"median {statistics.median(values):.4f} "
                  f"spread {100 * sp:.3f} %, without the farthest run "
                  f"{100 * cut:.3f} %")
    for name, sp in widest.items():
        mean = statistics.mean(less[name])
        print(f"{name}: widest spread {100 * sp:.3f} %, mean without the "
              f"farthest runs {100 * mean:.3f} %: a bound from "
              f"{2 * mean:.4f} to {8 * sp:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
