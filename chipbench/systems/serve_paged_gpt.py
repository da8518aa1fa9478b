"""System under test: a GPT-2-family model through
``pddl_tpu.serve.ServeEngine(paged=True)``.

From the program this module takes the model class, the engine, its
``SamplingParams`` and its counters (``compile_counts``, ``telemetry``,
``metrics.block_table_fill``, ``scheduler.depth``). Everything that is
yardstick — traffic, clocks, the reduction to metrics, the reference and
the comparison that decides ``correct`` — is the benchmark's own.

The window drives ``engine.submit`` / ``engine.step`` from one thread and
reads tokens off each handle's stream after every step, as a user would.
"""

from __future__ import annotations

import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.hostprobe import HostProbe, Pin, python_speed_ms
from chipbench.reference import gpt2 as reference
from chipbench.trace_reduce import STEP_SPAN
from chipbench.weights import make_gpt_weights, seed_key

DRAIN_LIMIT_S = 90.0   # past the close, then unfinished = failed (a
                       # 192-token answer takes this system about a minute)


def build(cfg: dict, seed: int, log, program_path=None):
    """Weights from the seed, the model, the engine; every program the
    cell's traffic uses warmed. Returns (engine, variables).
    ``program_path="int8"`` (the tools and tests only) switches on the
    program's own weight-only int8 path: the control of the comparison
    where the program itself stands in it."""
    from pddl_tpu.models.gpt import GPT
    from pddl_tpu.serve import ServeEngine

    t = time.perf_counter()
    variables = make_gpt_weights(cfg, seed)
    jax.block_until_ready(variables)
    log(f"setup: weights {time.perf_counter() - t:.2f}s")
    eng = cfg["engine"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["precision"]]
    model = GPT(vocab_size=cfg["vocab_size"], max_len=cfg["n_positions"],
                embed_dim=cfg["n_embd"], depth=cfg["n_layer"],
                num_heads=cfg["n_head"],
                ln_eps=cfg["layer_norm_epsilon"], dtype=dtype,
                param_dtype=dtype)
    served, extra = variables, {}
    if program_path == "int8":
        from pddl_tpu.ops import quant

        served = {"params": quant.quantize_int8(variables["params"])}
        extra = {"param_transform": quant.dequantize}
    elif program_path is not None:
        raise ValueError(f"unknown program path {program_path!r}")
    t = time.perf_counter()
    engine = ServeEngine(
        model, served, paged=True, max_slots=eng["max_slots"],
        prefill_len=eng["prefill_len"],
        prefix_block_size=eng["block_size"],
        prefix_cache_blocks=eng["pool_blocks"],
        max_queue_depth=eng["max_queue_depth"], aging_s=None,
        rng=seed_key(seed + 1), telemetry_capacity=16, **extra)
    log(f"setup: engine build {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    engine.warmup()
    log(f"setup: engine.warmup {time.perf_counter() - t:.2f}s "
        f"{engine.compile_counts()}")
    return engine, variables


def _submit(engine, req):
    from pddl_tpu.serve import SamplingParams

    sp = SamplingParams(temperature=req.temperature, top_p=req.top_p)
    return engine.submit(req.prompt, req.max_new_tokens, sampling=sp)


class Recorder:
    """The harness's own spans and counts: one record per request and one
    per engine step, all on ``time.perf_counter`` relative to the window's
    start."""

    def __init__(self, engine):
        self.engine = engine
        self.requests = []      # dicts, one per planned request submitted
        self.steps = []         # dicts, one per engine.step()
        self._open = []         # (record, handle) still streaming
        self.probe = HostProbe()  # what the host did in a stalled step

    def submit(self, req, now):
        rec = {"index": req.index, "due_s": req.due_s, "submit_s": now,
               "prompt_len": int(req.prompt.size),
               "max_new_tokens": req.max_new_tokens, "greedy": req.greedy,
               "temperature": req.temperature, "top_p": req.top_p,
               "first_s": None, "last_s": None, "admit_step_s": None,
               "n": 0, "done": False, "ok": False, "prompt": req.prompt,
               "tokens": None}
        self.requests.append(rec)
        try:
            handle = _submit(self.engine, req)
        except Exception as e:  # refused or invalid: counts as failed
            rec["error"] = repr(e)
            return
        self._open.append((rec, handle))

    def step(self, t0):
        """One engine step inside a span; then read every open stream."""
        a = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation(STEP_SPAN):
            self.engine.step()
        b = time.perf_counter() - t0
        tel = self.engine.telemetry.last() or {}
        self.probe.step(len(self.steps), a, b, tel)
        step = {"t0": a, "t1": b,
                "sites": dict(tel.get("site_wall_s") or {}),
                "phases": tel.get("phase_wall_s") or {},
                "live": int(tel.get("live_slots", 0)),
                "queue": int(tel.get("queue_depth", 0)),
                "fill": float(self.engine.metrics.block_table_fill),
                "tokens": 0, "decode_tokens": 0, "decode_ctx": 0,
                "prefill_tokens": 0, "prefill_ctx": 0}
        still = []
        for rec, h in self._open:
            n = len(h.tokens)
            new = n - rec["n"]
            if new:
                if rec["first_s"] is None:
                    rec["first_s"] = b
                    rec["admit_step_s"] = a
                    plen = rec["prompt_len"]
                    step["prefill_tokens"] += plen
                    step["prefill_ctx"] += plen * (plen + 1) // 2
                    new_decode = new - 1
                else:
                    new_decode = new
                rec["last_s"] = b
                rec["n"] = n
                step["tokens"] += new
                if new_decode:
                    step["decode_tokens"] += new_decode
                    step["decode_ctx"] += rec["prompt_len"] + n - 1
            if h.done:
                rec["done"] = True
                rec["tokens"] = np.asarray(h.tokens, np.int32)
                rec["ok"] = (h.finish_reason is not None
                             and h.finish_reason.value == "length"
                             and n == rec["max_new_tokens"])
            else:
                still.append((rec, h))
        self._open = still
        self.steps.append(step)
        return step

    @property
    def open_count(self):
        return len(self._open)


def warm_requests(engine, cfg, log):
    """Two real requests through submit/step before the window: one long
    greedy prompt (wide chunk) and one short sampled one (narrow chunk,
    the sampling filter), so the host paths are warm too and
    ``compile_counts`` is read after every program has really run."""
    rng = np.random.RandomState(12345)
    eng = cfg["engine"]
    t = time.perf_counter()
    rec = Recorder(engine)
    for i, (plen, temp, top_p) in enumerate(
            [(eng["prefill_len"] - 8, 0.0, None), (40, 0.7, 0.9)]):
        rec.submit(traffic_lib.PlannedRequest(
            index=i, due_s=0.0,
            prompt=rng.randint(0, cfg["vocab_size"],
                               size=plen).astype(np.int32),
            max_new_tokens=3, temperature=temp, top_p=top_p), 0.0)
    while engine.has_work:
        rec.step(t)
    if not all(r["ok"] for r in rec.requests):
        raise RuntimeError(f"warm requests failed: {rec.requests}")
    log(f"setup: warm requests {time.perf_counter() - t:.2f}s")


def run_window(engine, cfg, spec, plan, seconds, trace, log,
               mark_setup_done, ramp=()):
    """The measured window. Returns (recorder, window facts)."""
    pin = Pin()
    try:
        return _run_window(engine, cfg, spec, plan, seconds, trace, log,
                           mark_setup_done, ramp, pin)
    finally:
        pin.release()


def _run_window(engine, cfg, spec, plan, seconds, trace, log,
                mark_setup_done, ramp, pin):
    rec = Recorder(engine)
    n = len(plan)
    backlog = spec["kind"] == "backlog"
    target = int(spec.get("queue_target", 0))
    tracing = False
    facts = {"trace": None}
    i = 0
    if backlog:
        # Set-up the cell's traffic needs: a standing backlog has every
        # slot live BEFORE the window opens (filling 48 empty slots is
        # half a minute of prefill that no window of a backlog holds).
        t = time.perf_counter()
        slots = cfg["engine"]["max_slots"]
        while True:
            while engine.scheduler.depth < target + (
                    slots - engine.live_slots):
                rec.submit(plan[i % n], 0.0)
                i += 1
            rec.step(t)
            if engine.live_slots >= slots:
                break
        log(f"setup: backlog ramp {time.perf_counter() - t:.2f}s, "
            f"{len(rec.steps)} steps, {i} submitted")
        rec.steps = []
    elif ramp:
        # An open loop below the knee is met busy, not empty: the mix's
        # ``ramp_live`` streams are admitted here, each part-way through
        # its answer, and run on into the window. Not among the requests
        # the tails are over; a failure among them still counts.
        t = time.perf_counter()
        for req in ramp:
            rec.submit(req, 0.0)
            rec.requests[-1]["ramp"] = True
        while engine.scheduler.depth > 0:
            rec.step(t)
        log(f"setup: open-loop ramp {time.perf_counter() - t:.2f}s, "
            f"{len(rec.steps)} steps, {len(ramp)} admitted, "
            f"{engine.live_slots} live at the open")
        rec.steps = []
    # The program's own counters at the open and the close of the window,
    # whole, so that a later per-layer reader finds what it needs in
    # ``obs`` without this module changing.
    facts["counters_open"] = engine.metrics.snapshot()
    # The loop's thread gets a core of its own for the window and the
    # drain (hostprobe.py); how fast that core runs Python is read here and
    # at the close, for the log.
    facts["host"] = {"cpu": pin.hold(), "speed_open_ms": python_speed_ms()}
    gc.collect()
    gc.freeze()
    mark_setup_done()
    t0 = time.perf_counter()
    rec.probe.open(t0)

    def collect_trace():
        pin.free_this_thread()
        jax.profiler.stop_trace()

    def stop_trace():
        # Off the loop's thread and off its core: collecting and writing
        # the trace takes minutes, nearly all of it with the interpreter
        # lock released; on this thread it stalled every request in flight.
        facts["trace"].update(t1=time.perf_counter() - t0,
                              step1=len(rec.steps))
        facts["trace_writer"] = threading.Thread(target=collect_trace)
        facts["trace_writer"].start()

    while True:
        now = time.perf_counter() - t0
        if trace is not None and facts["trace"] is None \
                and now >= trace["start_s"]:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # TraceMe spans only
            jax.profiler.start_trace(trace["dir"], profiler_options=options)
            tracing = True
            facts["trace"] = {"t0": time.perf_counter() - t0,
                              "step0": len(rec.steps)}
        if tracing and now >= trace["start_s"] + trace["length_s"]:
            stop_trace()
            tracing = False
        if backlog:
            if now >= seconds:
                break
            while engine.scheduler.depth < target:
                rec.submit(plan[i % n], now)
                i += 1
        else:
            while i < n and plan[i].due_s <= now:
                rec.submit(plan[i], time.perf_counter() - t0)
                i += 1
            if now >= seconds and i >= n:
                break
        if engine.has_work:
            rec.step(t0)
        else:
            rec.probe.idle()
            nxt = plan[i].due_s if i < n else seconds
            time.sleep(max(0.0, min(nxt - now, 0.002)))
    facts["window_s"] = time.perf_counter() - t0
    facts["steps_in_window"] = len(rec.steps)
    longest = sorted(s["t1"] - s["t0"] for s in rec.steps)[-3:]
    live = [s["live"] for s in rec.steps]
    k = max(1, len(live) // 3)
    thirds = [round(float(np.mean(x)), 1)
              for x in (live[:k], live[k:-k] or live, live[-k:])]
    log(f"window: {facts['window_s']:.2f}s, {len(rec.steps)} steps, the "
        f"three longest {[round(x, 3) for x in longest]}s; live slots by "
        f"thirds {thirds}")
    if tracing:  # the window closed inside the traced stretch
        stop_trace()
    if not backlog:
        # Drain: no new arrivals; every request due in the window is
        # waited for, DRAIN_LIMIT_S past the close at most.
        while rec.open_count and \
                time.perf_counter() - t0 < seconds + DRAIN_LIMIT_S:
            if engine.has_work:
                rec.step(t0)
            else:
                break
    facts["drain_s"] = time.perf_counter() - t0 - facts["window_s"]
    facts["counters_close"] = engine.metrics.snapshot()
    rec.probe.close()
    facts["host"]["speed_close_ms"] = python_speed_ms()
    gc.unfreeze()
    facts["stalls"] = rec.probe.summary(rec.steps[:facts["steps_in_window"]])
    _log_stalls(facts["stalls"], facts["host"], len(rec.steps), log)
    return rec, facts


def _log_stalls(found, host, steps, log):
    """Unjudged: the core the loop's thread held and its speed, the loop's
    CPU a step, the window's steps of half a second or more, the
    collector's work inside the window, and for every stalled step or
    stretch (the drain's too) what the host was doing in it."""
    log(f"host: the loop's thread on core {host['cpu']}, fixed Python work "
        f"in {host['speed_open_ms']:.3f} ms at the open and "
        f"{host['speed_close_ms']:.3f} at the close; the loop's CPU "
        f"{1e3 * found['thread_cpu_s'] / max(1, steps):.2f} ms a step, "
        f"the process's {1e3 * found['process_cpu_s'] / max(1, steps):.2f}")
    log(f"stalls: {found['long_steps']} steps of {found['stall_s']}s or "
        f"more in the window, {found['long_steps_s']:.3f}s in them; "
        f"collections by generation {found['gc']}")
    for s in found["stalls"]:
        log("stall: " + ", ".join(
            f"{k} {round(v, 4) if isinstance(v, float) else v}"
            for k, v in s.items()))


def check_sample(requests, seed, check):
    """The requests the reference follows, drawn from those that ran to
    their length: ``requests`` greedy ones, the longest among them, and
    ``sampled_requests`` sampled ones, their longest too; the rest drawn
    from the seed."""
    rng = np.random.RandomState((int(seed) + 7) % (2 ** 32))
    picks = []
    for greedy, count in ((True, int(check["requests"])),
                          (False, int(check.get("sampled_requests", 0)))):
        pool = [r for r in requests
                if r["greedy"] == greedy and r["done"] and r["ok"]]
        if not pool or count < 1:
            continue
        pool.sort(key=lambda r: -(r["prompt_len"] + r["n"]))
        rest = pool[1:]
        picks += [pool[0]] + [rest[j] for j in
                              rng.permutation(len(rest))[:count - 1]]
    return picks


def compare(variables, cfg, picks, controls=()):
    """The reference over each picked request, once. Of the greedy ones:
    the widest and the mean gap of a served token below the reference's
    best (and the same of the token each control puts first, at the same
    positions). Of the sampled ones: the furthest a served token lies
    outside the reference's nucleus."""
    width, max_rows = cfg["n_positions"], cfg["check"]["max_rows"]
    gaps, control_gaps, excess, tokens = [], {}, [], 0
    for r in picks:
        g = reference.served_gaps(
            variables["params"], cfg, r["prompt"], r["tokens"], width,
            max_rows, controls=controls if r["greedy"] else (),
            temperature=r["temperature"], top_p=r["top_p"])
        tokens += g["tokens"]
        if r["greedy"]:
            gaps.append(g["gaps"])
            for name, c in g["control_gaps"].items():
                control_gaps.setdefault(name, []).append(c)
        elif g["nucleus_excess"] is not None:
            excess.append(g["nucleus_excess"])

    def stats(parts):
        if not parts:
            return None
        x = np.concatenate(parts)
        return {"max": float(x.max()), "mean": float(x.mean()),
                "tokens": int(x.size)}

    return {"greedy": stats(gaps), "nucleus": stats(excess),
            "controls": {k: stats(v) for k, v in control_gaps.items()},
            "tokens": tokens, "requests": len(picks)}


def decide(check, greedy, nucleus, tokens, failed, recompiles,
           kernel_missing):
    """Each number compared beside its limit, and whether all hold. The
    one place ``correct`` is decided: a run's own tokens and — in the
    tools and tests — the control's tokens in their place go through it
    alike."""
    checks = {
        "greedy_gap_mean": [greedy["mean"] if greedy else None,
                            check["gap_mean_limit"]],
        "greedy_gap_max": [greedy["max"] if greedy else None,
                           check["gap_max_limit"]],
    }
    if nucleus is not None:
        checks["nucleus_excess_max"] = [nucleus["max"],
                                        check["nucleus_excess_limit"]]
    checks["checked_tokens_min"] = [tokens, check["min_tokens"]]
    checks["failed_requests"] = [failed, 0]
    checks["recompiles_in_window"] = [recompiles, 0]
    checks["mosaic_kernel_missing"] = [kernel_missing, 0]
    correct = all(
        v is not None and (v >= lim if name == "checked_tokens_min"
                           else v <= lim)
        for name, (v, lim) in checks.items())
    return checks, bool(correct)


def run(ctx):
    """One run of one cell. ``ctx``: cfg, traffic spec, seed, seconds,
    trace (None or {"dir","start_s","length_s"}), log, mark_setup_done,
    and for the tools and tests only: ``control`` (names of the
    reference's lower precisions, comma-separated: each is put in the
    program's place and judged by ``decide``; a benchmark run never runs
    one) and ``program_path`` (see ``build``)."""
    cfg, spec, log = ctx["cfg"], ctx["traffic"], ctx["log"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    engine, variables = build(cfg, seed, log, ctx.get("program_path"))
    warm_requests(engine, cfg, log)
    counts_before = dict(engine.compile_counts())
    eng = cfg["engine"]
    sizes = (cfg["vocab_size"], eng["prefill_len"], cfg["n_positions"])
    plan = traffic_lib.generate(spec, seed, seconds, *sizes)
    ramp = traffic_lib.ramp(spec, seed, *sizes) \
        if spec["kind"] == "open_loop" else []
    rec, facts = run_window(engine, cfg, spec, plan, seconds,
                            ctx["trace"], log, ctx["mark_setup_done"],
                            ramp=ramp)
    counts_after = dict(engine.compile_counts())
    if "trace_writer" in facts:
        facts.pop("trace_writer").join()
    lowered = engine.tick_lowering().as_text()
    has_kernel = "tpu_custom_call" in lowered
    on_tpu = ctx["devices"][0].platform == "tpu"
    memory_peak = max(int(d.memory_stats()["peak_bytes_in_use"])
                      for d in ctx["devices"]) if on_tpu else 0
    # Free the program's state before the reference runs.
    del engine, lowered
    gc.collect()

    backlog = spec["kind"] == "backlog"
    reqs = rec.requests
    if backlog:
        # In a standing backlog the requests still in flight at the close
        # are not failures; those refused or finished wrong are.
        judged = [r for r in reqs if r["done"] or "error" in r]
        counted = judged
    else:
        judged = [r for r in reqs if not r.get("ramp")]
        counted = reqs
    attempted = len(counted)
    failed = sum(1 for r in counted if not r["ok"])
    if not backlog:
        _log_thirds(judged, seconds, log)
    picks = check_sample(judged, seed, cfg["check"])
    controls = tuple(ctx["control"].split(",")) if ctx.get("control") \
        else ()
    t = time.perf_counter()
    cmp_ = compare(variables, cfg, picks, controls)
    log(f"check: reference over {cmp_['requests']} requests, "
        f"{cmp_['tokens']} tokens, {time.perf_counter() - t:.2f}s")
    recompiles = sum(counts_after.values()) - sum(counts_before.values())
    rest = (cmp_["tokens"], failed, recompiles,
            int(on_tpu and not has_kernel))
    checks, correct = decide(cfg["check"], cmp_["greedy"], cmp_["nucleus"],
                             *rest)
    in_place = {}
    for name, stats in cmp_["controls"].items():
        c_checks, c_correct = decide(cfg["check"], stats, cmp_["nucleus"],
                                     *rest)
        in_place[name] = {"correct": c_correct, "checks": c_checks}
    obs = {"kind": "serve", "cfg": cfg, "traffic": spec, "seconds": seconds,
           "requests": reqs, "judged": judged, "steps": rec.steps,
           "facts": facts, "backlog": backlog,
           "drain_limit_s": DRAIN_LIMIT_S}
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "checks": checks, "control": in_place, "obs": obs,
            "memory_peak_bytes": memory_peak}


def _log_thirds(judged, seconds, log):
    """Whether the window is of one piece: the judged latencies of the
    requests that fell due in each third of it."""
    out = []
    for k in range(3):
        part = [r for r in judged
                if k * seconds / 3 <= r["due_s"] < (k + 1) * seconds / 3
                and r["ok"] and r["n"] >= 2]
        if not part:
            continue
        ttft = np.mean([r["first_s"] - r["due_s"] for r in part])
        tpot = np.percentile([(r["last_s"] - r["first_s"]) / (r["n"] - 1)
                              for r in part], 90)
        out.append(f"{len(part)} requests ttft mean {1e3 * ttft:.0f} ms "
                   f"tpot p90 {1e3 * tpot:.0f} ms")
    log("window by thirds of the due instant: " + "; ".join(out))
