"""Where the device idles, by the engine's own phases.

    CHIPBENCH_KEEP_TRACE=chiprun_out/steady.events.json \\
        python3 chipbench/run.py --workload ... --trace 1
    python3 chipbench/tools/phase_gaps.py chiprun_out/steady.events.json \\
        [--steps N] [--requests 8]

The program's serving engine writes a span tree into the profiler's trace
(``pddl.serve.step`` and beneath it ``pddl.serve.<phase>``; TraceMe spans
on the device trace's own clock). ``trace_reduce.reduce`` puts each idle
gap of the device down to the innermost host span of ANY kind, which is a
span of the XLA runtime; this puts it down to the engine phase that
covers the gap's midpoint, so the table says which part of the step left
the device waiting. Reads the events a traced run keeps
(``trace_reduce.extract``'s dict); the window is the harness's
``chipbench.step`` spans (the first ``--steps`` of them: what the run's
own reduction took), so the idle total is the run's ``device_idle_pct``.

Per phase: entries, wall, self wall (the phase less the phases inside
it), device-busy inside, device-idle put down to it. Per
``admit_request``: its wall, the device time busy inside it and of the
chunk programs (``jit__chunk_paged*``) that ran inside it.

A trace of a program that writes no such span (an older program) gives a
table with the one outer row only; nothing raises.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from chipbench.trace_reduce import STEP_SPAN  # noqa: E402

PREFIX = "pddl.serve."
OUTSIDE = "(outside engine.step)"
CHUNK_PROGRAMS = "jit__chunk_paged"


def _union(intervals):
    """Merged, sorted, non-overlapping [start, end] list."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap(merged, a, b):
    """Seconds of [a, b] covered by the merged interval list."""
    return sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged)


def _segments(spans, t0, t1):
    """The window cut into [start, end, label] pieces, each labelled with
    the innermost span covering it (``OUTSIDE`` where none does). Spans
    are [name, start, end] and nest as a tree."""
    cuts = []
    stack = []   # (end, label)
    cursor = t0

    def emit(upto):
        nonlocal cursor
        upto = min(max(upto, t0), t1)
        if upto > cursor:
            cuts.append([cursor, upto,
                         stack[-1][1] if stack else OUTSIDE])
            cursor = upto

    for name, start, end in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][0] <= start:
            emit(stack[-1][0])
            stack.pop()
        emit(start)
        stack.append((end, name))
    while stack:
        emit(stack[-1][0])
        stack.pop()
    emit(t1)
    return cuts


def analyse(events: dict, steps: int = None) -> dict:
    """Per-phase and per-admission totals of one kept trace (first
    device)."""
    harness = sorted((h for h in events["host"] if h[0] == STEP_SPAN),
                     key=lambda h: h[1])
    if steps:
        harness = harness[:steps]
    ops = events["devices"][0]["ops"]
    if harness:
        t0 = min(h[1] for h in harness)
        t1 = max(h[1] + h[2] for h in harness)
    else:
        t0 = min(op[1] for op in ops)
        t1 = max(op[1] + op[2] for op in ops)
    busy = _union([(max(op[1], t0), min(op[1] + op[2], t1))
                   for op in ops if op[1] + op[2] > t0 and op[1] < t1])
    spans = [[h[0][len(PREFIX):], h[1], h[1] + h[2]]
             for h in events["host"]
             if h[0].startswith(PREFIX) and h[1] >= t0
             and h[1] + h[2] <= t1]
    rows = {}

    def row(label):
        return rows.setdefault(label, {"entries": 0, "wall_s": 0.0,
                                       "self_s": 0.0, "busy_s": 0.0,
                                       "idle_s": 0.0})

    for name, start, end in spans:
        r = row(name)
        r["entries"] += 1
        r["wall_s"] += end - start
        r["busy_s"] += _overlap(busy, start, end)
    cuts = _segments(spans, t0, t1)
    for a, b, label in cuts:
        row(label)["self_s"] += b - a
    out = row(OUTSIDE)
    out["wall_s"] = out["self_s"]
    out["busy_s"] = sum(_overlap(busy, a, b) for a, b, label in cuts
                        if label == OUTSIDE)
    # Every idle gap of the device, put down to the piece that covers
    # its midpoint.
    starts = [c[0] for c in cuts]
    edges = [t0] + [x for iv in busy for x in iv] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            piece = cuts[bisect.bisect_right(starts, (a + b) / 2) - 1]
            row(piece[2])["idle_s"] += b - a
    window = t1 - t0
    busy_s = sum(b - a for a, b in busy)
    modules = [m for m in events["devices"][0]["modules"]
               if m[0].startswith(CHUNK_PROGRAMS)]
    requests = [{"t_s": start - t0, "wall_s": end - start,
                 "busy_s": _overlap(busy, start, end),
                 "chunks": sum(1 for m in modules
                               if start <= m[1] < end),
                 "chunk_device_s": sum(m[2] for m in modules
                                       if start <= m[1] < end)}
                for name, start, end in sorted(spans, key=lambda s: s[1])
                if name == "admit_request"]
    return {"window_s": window, "busy_s": busy_s,
            "idle_s": window - busy_s,
            "idle_pct": 100.0 * (1.0 - busy_s / window),
            "harness_steps": len(harness), "phases": rows,
            "requests": requests}


ORDER = ("step", "reap", "admit", "admit_request", "first_token_wait",
         "append_blocks", "tick_dispatch", "tick_wait", "emit", OUTSIDE)


def table(result: dict, requests: int = 8) -> str:
    """The result as a Markdown table (what PERF.md section 5 holds)."""
    rows = result["phases"]
    names = [n for n in ORDER if n in rows] \
        + sorted(n for n in rows if n not in ORDER)
    lines = [
        f"window {result['window_s']:.3f} s over "
        f"{result['harness_steps']} harness steps, device busy "
        f"{result['busy_s']:.3f} s, idle {result['idle_s']:.3f} s "
        f"({result['idle_pct']:.2f} %)", "",
        "| phase | entries | wall s | self s | device busy inside s "
        "| device idle put down to it s | share of idle % |",
        "|---|---|---|---|---|---|---|"]
    for n in names:
        r = rows[n]
        share = 100.0 * r["idle_s"] / result["idle_s"] \
            if result["idle_s"] else 0.0
        lines.append(
            f"| `{n}` | {r['entries']} | {r['wall_s']:.3f} | "
            f"{r['self_s']:.3f} | {r['busy_s']:.3f} | {r['idle_s']:.4f} | "
            f"{share:.1f} |")
    reqs = result["requests"]
    if reqs:
        mean = lambda k: sum(r[k] for r in reqs) / len(reqs)
        lines += ["", f"{len(reqs)} admissions: mean wall "
                  f"{1e3 * mean('wall_s'):.1f} ms, device busy inside "
                  f"{1e3 * mean('busy_s'):.1f} ms, of it chunk programs "
                  f"{1e3 * mean('chunk_device_s'):.1f} ms "
                  f"({mean('chunks'):.2f} chunks)", "",
                  "| admission at s | wall ms | device busy ms | chunks "
                  "| chunk device ms |", "|---|---|---|---|---|"]
        for r in reqs[:requests]:
            lines.append(
                f"| {r['t_s']:.3f} | {1e3 * r['wall_s']:.1f} | "
                f"{1e3 * r['busy_s']:.1f} | {r['chunks']} | "
                f"{1e3 * r['chunk_device_s']:.1f} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("events", help="the file CHIPBENCH_KEEP_TRACE named")
    p.add_argument("--steps", type=int, default=None,
                   help="keep the first N harness spans (the run's log "
                        "says how many its reduction took)")
    p.add_argument("--requests", type=int, default=8,
                   help="admissions listed one by one")
    args = p.parse_args(argv)
    with open(args.events) as f:
        events = json.load(f)
    print(table(analyse(events, args.steps), args.requests))
    return 0


if __name__ == "__main__":
    sys.exit(main())
