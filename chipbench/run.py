"""chipbench: one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell names a configuration and a traffic mix; the
configuration's file (``chipbench/configs/<name>.json``) names the system
module that drives it (``chipbench/systems/<system>.py``), the traffic
file (``chipbench/traffic/<name>.json``) is read by the one generator, and
every metric has a reader of its own (``chipbench/metrics/<name>.py``).
A later PR adds a cell, a configuration, a mix or a per-layer metric by
adding files and entries, editing nothing that is here.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks`` — each number compared beside its limit —
which are also the last lines of standard error. Runs on a TPU that the
peaks table knows, or not at all.
"""

from __future__ import annotations

import time

_T_START = time.time()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")


def log(msg: str) -> None:
    print(f"[chipbench {time.time() - _T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json ({sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    return cell, cfg


def metric_reader(name: str):
    """``chipbench/metrics/<name>.py``'s ``read`` (a name may hold dots,
    so the module is loaded by path)."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metrics_for(bench: dict, group: str, workload: str, obs: dict) -> dict:
    out = {}
    for m in bench[group]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        value = metric_reader(m["name"])(obs)
        if value is None:
            continue  # nothing to read: left out, never 0
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def enable_compile_cache(jax) -> str:
    """One fixed directory inside the checkout unless the environment
    names one (then jax reads it itself and nothing is set in code)."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def require_chips(jax, chips: int, allow_cpu: bool):
    from chipbench.peaks import peaks_for

    devices = jax.devices()
    platform = devices[0].platform
    if allow_cpu:  # the rehearsal entry only; never the driver's command
        return devices[:chips], None
    if platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, found platform "
                         f"{platform!r} ({devices[0].device_kind})")
    peaks = peaks_for(devices[0].device_kind)
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, found "
                         f"{len(devices)}")
    return devices[:chips], peaks


class CompileMeter:
    """Backend compiles and persistent-cache hits, from jax's monitoring
    events (for the set-up breakdown on stderr)."""

    def __init__(self, jax):
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        return (f"compiles {self.compiles} ({self.compile_s:.1f}s), "
                f"cache hits {self.hits}, misses {self.misses}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             allow_cpu: bool = False, control=None, overrides=None,
             observe=None, program_path=None) -> dict:
    """One run; returns the result object (not printed)."""
    bench = load_benchmark()
    cell, cfg = find_cell(bench, workload)
    import jax

    cache_dir = enable_compile_cache(jax)
    meter = CompileMeter(jax)
    devices, peaks = require_chips(jax, int(cell["chips"]), allow_cpu)
    log(f"cell {workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, {len(devices)} x {devices[0].device_kind}; "
        f"compile cache {cache_dir}")
    from chipbench.traffic import load_traffic

    spec = load_traffic(cell["traffic"])
    if overrides:  # the rehearsal and the tools only
        overrides(cfg, spec)
    system = importlib.import_module("chipbench.systems." + cfg["system"])
    setup = {}

    def mark_setup_done():
        setup["s"] = time.time() - _T_START
        log(f"setup done: {setup['s']:.2f}s; {meter.line()}")

    trace_ctx = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # The window's last seconds: the profiler takes some fifteen
        # seconds to collect each second it traced, and while it collects
        # the host is not the cell's; so it collects after the close.
        length = min(6.0, 0.25 * seconds)
        trace_ctx = {"dir": TRACE_DIR, "start_s": seconds - length,
                     "length_s": length}
    ctx = {"cfg": cfg, "traffic": spec, "seed": int(seed),
           "seconds": float(seconds), "trace": trace_ctx, "log": log,
           "mark_setup_done": mark_setup_done, "devices": devices,
           "control": control, "program_path": program_path}
    result = system.run(ctx)
    log(f"after the window: {meter.line()}")
    obs = result.pop("obs")
    obs.update(setup_s=setup["s"], peaks=peaks, chips=len(devices),
               trace=None)
    if observe:  # the tools only
        observe(obs)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result.pop("memory_peak_bytes")}
    out = {"correct": result["correct"], "attempted": result["attempted"],
           "failed": result["failed"]}
    if trace:
        from chipbench import trace_reduce

        t = time.perf_counter()
        events = trace_reduce.extract(trace_reduce.find_xplane(TRACE_DIR))
        # The harness's steps of the traced stretch, and no later one (the
        # profiler stops a moment after it is asked to).
        stretch = obs["facts"]["trace"]
        spans = sorted((h for h in events["host"]
                        if h[0] == trace_reduce.STEP_SPAN),
                       key=lambda h: h[1])[
            :stretch["step1"] - stretch["step0"]]
        if spans:
            reduced = trace_reduce.reduce(
                events, min(h[1] for h in spans),
                max(h[1] + h[2] for h in spans))
        else:
            reduced = trace_reduce.reduce(events)
        log(f"trace: read and reduced in {time.perf_counter() - t:.2f}s; "
            f"{len(reduced['steps'])} harness spans, window "
            f"{reduced['window_s']:.3f}s, busy {reduced['busy_s']:.3f}s")
        if os.environ.get("CHIPBENCH_KEEP_TRACE"):
            keep = os.environ["CHIPBENCH_KEEP_TRACE"]
            os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
            with open(keep, "w") as f:
                json.dump(events, f)
            from chipbench.tools import xplane_dump

            with open(keep + ".structure.txt", "w") as f:
                xplane_dump.dump(trace_reduce.find_xplane(TRACE_DIR), 6, f)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        obs["trace"] = reduced
        out["metrics"] = metrics_for(bench, "per_layer", workload, obs)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["device"] = device
        out["breakdown"] = {"device_ops": reduced["top_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    else:
        out["metrics"] = metrics_for(bench, "end_to_end", workload, obs)
        out["device"] = device
        # For the log only: what the per-layer readers find without a
        # trace (harness spans and counters).
        if not allow_cpu:
            seen = metrics_for(bench, "per_layer", workload, obs)
            log("untraced per-layer readings: " + json.dumps(
                {k: round(v["value"], 3) for k, v in seen.items()}))
    as_dict = lambda checks: {k: {"value": v[0], "limit": v[1]}
                              for k, v in checks.items()}
    if result.get("control"):  # the tools and tests only
        out["control"] = {name: {"correct": c["correct"],
                                 "checks": as_dict(c["checks"])}
                          for name, c in result["control"].items()}
    out["checks"] = as_dict(result["checks"])
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        print(f"check {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
