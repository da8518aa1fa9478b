"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers use. Kept as code with the benchmark and checked on a recorded
trace (``tests/test_trace_reduce.py``), so every PR computes the same
number in the same way.

Two steps, so that the recorded trace can be small:
  ``extract(path)``  xplane -> plain dict of device-op events and host
                     spans (what ``recorded/*.json`` holds);
  ``reduce(events)`` that dict -> busy/idle, time per op, kernel and
                     collective time, per-step device time, idle gaps by
                     what the host was doing.

Stable names searched for:
  device planes      ``/device:TPU:<n>``; op events on the line ``XLA Ops``
  paged kernel       a custom call whose target is ``tpu_custom_call``
                     (Mosaic) and whose name holds ``_decode_step``
  collectives        ops whose name starts with ``all-reduce``,
                     ``all-gather``, ``reduce-scatter`` or ``all-to-all``
  programs           events on the line ``XLA Modules`` (``jit__tick_paged``,
                     ``jit__chunk_paged``, ``jit__chunk_paged_wide``,
                     ``jit_train_step``)
  harness span       ``chipbench.step`` (a TraceAnnotation on the host)
"""

from __future__ import annotations

import glob
import os
import re

STEP_SPAN = "chipbench.step"
COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "all-to-all", "collective-permute")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


_OP = re.compile(
    r"^%?(?P<name>[\w\-.]+) = \(?(?P<shape>[a-z0-9]+\[[0-9,]*\])?"
    r".*?[\s)}](?P<op>[a-z][a-z\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(text: str):
    """(name, output shape, opcode, custom-call target) of a device op
    event, whose name in the trace is the whole HLO instruction."""
    m = _OP.match(text)
    if not m:
        return re.sub(r"^%", "", text)[:60], "", "", ""
    t = _TARGET.search(text)
    return (m.group("name"), m.group("shape") or "", m.group("op"),
            t.group(1) if t else "")


def extract(path: str) -> dict:
    """Device op events [name, start_s, dur_s, shape, opcode, target],
    module events and host spans, per plane. Times in seconds from the
    trace's origin."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        name, *rest = parse_op(e.name)
                        ops.append([name, e.start_ns * 1e-9,
                                    e.duration_ns * 1e-9, *rest])
                elif line.name == "XLA Modules":
                    for e in line.events:
                        modules.append([e.name, e.start_ns * 1e-9,
                                        e.duration_ns * 1e-9])
            devices.append({"name": plane.name, "ops": ops,
                            "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    host.append([e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9, line.name])
    return {"devices": devices, "host": host}


def _union(intervals):
    """Merged, sorted, non-overlapping [start, end] list."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _overlap(merged, a, b):
    """Seconds of [a, b] covered by the merged interval list."""
    total = 0.0
    for s, e in merged:
        if e <= a:
            continue
        if s >= b:
            break
        total += min(e, b) - max(s, a)
    return total


def _self_times(ops):
    """Per-event self time: an op that encloses others (a while loop, a
    call) keeps only what its children do not cover."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_s = [op[2] for op in ops]
    stack = []
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and stack[-1][1] <= start:
            stack.pop()
        if stack and end <= stack[-1][1] + 1e-12:
            self_s[stack[-1][0]] -= ops[i][2]
        stack.append((i, end))
    return [max(0.0, s) for s in self_s]


def op_label(op) -> str:
    """A stable label for totals: the op's name without its running
    number, with the output's type and shape
    (``copy_bf16_3456_20_16_64``)."""
    kind = re.sub(r"[.\-_]\d+$", "", op[0])
    kind = re.sub(r"\.\d+\.", ".", kind)
    if op[3]:
        kind += "_" + re.sub(r"[\[\],]+", "_", op[3]).strip("_")
    return kind


def is_collective(op) -> bool:
    return op[0].startswith(COLLECTIVE_PREFIXES) \
        or op[4].startswith(COLLECTIVE_PREFIXES)


def is_container(op) -> bool:
    return op[4] in ("while", "call", "conditional")


def is_paged_kernel(op) -> bool:
    return op[5] == "tpu_custom_call" and "_decode_step" in op[0]


def reduce(events: dict, t0: float = None, t1: float = None) -> dict:
    """All per-layer device numbers of one traced window [t0, t1]
    (default: from the first to the last device op)."""
    devs = events["devices"]
    if not devs or not any(d["ops"] for d in devs):
        raise ValueError("trace holds no device op: nothing ran on the "
                         "device inside the traced window")
    starts = [op[1] for d in devs for op in d["ops"]]
    ends = [op[1] + op[2] for d in devs for op in d["ops"]]
    t0 = min(starts) if t0 is None else t0
    t1 = max(ends) if t1 is None else t1
    window = t1 - t0
    per_dev = []
    op_totals = {}
    kernel_s = kernel_calls = 0.0
    coll_s = coll_exposed = 0.0
    for d in devs:
        ops = [op for op in d["ops"] if op[1] + op[2] > t0 and op[1] < t1]
        merged = _union([(max(op[1], t0), min(op[1] + op[2], t1))
                         for op in ops])
        busy = sum(b - a for a, b in merged)
        selfs = _self_times(ops)
        compute = _union([(op[1], op[1] + op[2]) for op in ops
                          if not is_collective(op) and not is_container(op)])
        for op, s in zip(ops, selfs):
            label = op_label(op)
            op_totals[label] = op_totals.get(label, 0.0) + s
            if is_paged_kernel(op):
                kernel_s += op[2]
                kernel_calls += 1
            if is_collective(op):
                coll_s += op[2]
                coll_exposed += op[2] - _overlap(compute, op[1],
                                                 op[1] + op[2])
        per_dev.append({"name": d["name"], "busy_s": busy,
                        "merged": merged})
    n = len(devs)
    busy_mean = sum(p["busy_s"] for p in per_dev) / n
    # Programs (XLA Modules) on the first device.
    programs = {}
    for name, start, dur in devs[0]["modules"]:
        if start + dur <= t0 or start >= t1:
            continue
        key = re.sub(r"\(.*$", "", name)
        p = programs.setdefault(key, {"seconds": 0.0, "count": 0})
        p["seconds"] += dur
        p["count"] += 1
    # Harness spans and the device time inside each (first device).
    merged0 = per_dev[0]["merged"]
    steps = []
    for name, start, dur, _ in events["host"]:
        if name == STEP_SPAN and start >= t0 and start + dur <= t1:
            steps.append({"t0": start, "t1": start + dur,
                          "busy_s": _overlap(merged0, start, start + dur)})
    steps.sort(key=lambda s: s["t0"])
    # Idle gaps by what the host was doing at the gap's midpoint: the
    # innermost host span that covers it.
    host = sorted((h for h in events["host"]
                   if h[1] < t1 and h[1] + h[2] > t0), key=lambda h: h[1])
    gaps = {}
    edges = [t0] + [x for iv in merged0 for x in iv] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < 20e-6:
            continue
        mid = (a + b) / 2
        best = None
        for h in host:
            if h[1] > mid:
                break
            if h[1] + h[2] >= mid and (best is None or h[2] < best[2]):
                best = h
        label = re.sub(r"[^A-Za-z0-9_.]+", "_", best[0])[:60] \
            if best else "no_host_span"
        gaps[label] = gaps.get(label, 0.0) + (b - a)
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    for k in op_totals:
        op_totals[k] /= n
    return {
        "window_s": window, "busy_s": busy_mean, "chips": n,
        "idle_pct": 100.0 * (1.0 - busy_mean / window),
        "ops": op_totals, "top_ops": top(op_totals),
        "idle_gaps": top(gaps),
        "kernel_s": kernel_s / n, "kernel_calls": kernel_calls / n,
        "collective_s": coll_s / n, "collective_exposed_s": coll_exposed / n,
        "programs": programs, "steps": steps,
    }
