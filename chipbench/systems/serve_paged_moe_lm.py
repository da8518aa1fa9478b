"""System under test: a routed-expert, window/full-attention decoder of
the Llama block (``pddl_tpu.models.llama.Llama``) through
``pddl_tpu.serve.ServeEngine(paged=True)`` — the same engine, scheduler,
block pool, tick, sampler and spans as ``serve_paged_gpt``.

From the program this module takes the model class, the engine, its
counters (``compile_counts``, ``metrics.snapshot``, ``expert_load``), its
lowered programs (``tick_lowering``, ``program_lowerings``: where an
instruction's scope is written) and, for the expert-set check, the model's
own full forward. The window, the recorder, the sample and ``decide`` are
``serve_paged_gpt``'s; the weights, the reference, the work model and the
reduction of the trace to scopes are this configuration's own files.
"""

from __future__ import annotations

import functools
import gc
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import trace_reduce
from chipbench import traffic as traffic_lib
from chipbench import workmodel_moe
from chipbench.reference import smallthinker as reference
from chipbench.systems.serve_paged_gpt import (
    DRAIN_LIMIT_S,
    Recorder,
    _log_thirds,
    check_sample,
    decide,
    run_window,
)
from chipbench.weights import seed_key
from chipbench.weights_smallthinker import make_weights

# Scopes the program names its device work by (`jax.named_scope`): they
# reach a compiled instruction's ``op_name``, not the trace's op names,
# so a traced op is put down to a scope through the compiled text.
EXPERT_SCOPES = ("moe_dispatch", "moe_ffn", "moe_combine")
ATTN_SCOPES = ("attn_window", "attn_global")
SCOPES = ("moe_router",) + EXPERT_SCOPES + ATTN_SCOPES


def build_model(cfg: dict):
    """The program's model at the configuration's sizes. A program that
    lacks what the configuration needs (the parent of the PR that added
    it) fails here, at once."""
    from pddl_tpu.models.llama import Llama

    s = reference.shape_of(cfg)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["precision"]]
    return Llama(
        vocab_size=s["vocab"], max_len=int(cfg["max_position_embeddings"]),
        embed_dim=s["embed"], depth=s["layers"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        intermediate_dim=s["expert_width"], rope_theta=s["theta"],
        sliding_window=s["window"],
        sliding_window_layout=s["window_layout"],
        rope_layout=s["rope_layout"], moe_experts=s["experts"],
        moe_top_k=s["top_k"], moe_act="reglu", moe_router_input="attn",
        rms_eps=s["eps"], dtype=dtype, param_dtype=dtype)


def build(cfg: dict, seed: int, log):
    """The model, weights from the seed, the engine; every program the
    cell's traffic uses warmed. Returns (model, engine, variables)."""
    from pddl_tpu.serve import ServeEngine

    model = build_model(cfg)
    t = time.perf_counter()
    variables = make_weights(cfg, seed)
    jax.block_until_ready(variables)
    log(f"setup: weights {time.perf_counter() - t:.2f}s")
    eng = cfg["engine"]
    t = time.perf_counter()
    engine = ServeEngine(
        model, variables, paged=True, max_slots=eng["max_slots"],
        prefill_len=eng["prefill_len"], prefix_block_size=eng["block_size"],
        prefix_cache_blocks=eng["pool_blocks"],
        prefix_chunk=eng["prefill_chunk"],
        prefill_slice_tokens=eng.get("prefill_slice_tokens"),
        max_queue_depth=eng["max_queue_depth"], aging_s=None,
        rng=seed_key(seed + 1), telemetry_capacity=16)
    log(f"setup: engine build {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    engine.warmup()
    log(f"setup: engine.warmup {time.perf_counter() - t:.2f}s "
        f"{engine.compile_counts()}")
    return model, engine, variables


def warm_requests(engine, cfg, log):
    """Two real requests before the window: the longest prompt the engine
    takes, greedy (every chunk offset a prompt can start a chunk at), and
    a short sampled one (the sampling filter)."""
    rng = np.random.RandomState(12345)
    eng = cfg["engine"]
    t = time.perf_counter()
    rec = Recorder(engine)
    for i, (plen, temp, top_p) in enumerate(
            [(eng["prefill_len"] - 8, 0.0, None), (1100, 0.7, 0.9)]):
        rec.submit(traffic_lib.PlannedRequest(
            index=i, due_s=0.0,
            prompt=rng.randint(0, cfg["vocab_size"],
                               size=min(plen, eng["prefill_len"])
                               ).astype(np.int32),
            max_new_tokens=3, temperature=temp, top_p=top_p), 0.0)
    while engine.has_work:
        rec.step(t)
    if not all(r["ok"] for r in rec.requests):
        raise RuntimeError(f"warm requests failed: {rec.requests}")
    log(f"setup: warm requests {time.perf_counter() - t:.2f}s")


# ------------------------------------------------------------ the trace
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?"
                    r"metadata=\{[^}]*op_name=\"([^\"]*)\"")


def scope_table(engine) -> dict:
    """{module name: {instruction name: op_name}} of the engine's
    compiled tick and chunk programs (a compile the persistent cache
    answers: the same programs were compiled at warm-up)."""
    table = {}
    for lowered in engine.program_lowerings().values():
        text = lowered.compile().as_text()
        module = re.match(r"HloModule (\S+?),", text).group(1)
        names = {}
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                names[m.group(1)] = m.group(2)
        table[module] = names
    return table


def scope_of(module: str, op_name) -> str:
    """The scope a device op's time goes to. In the model: the innermost
    of the program's named scopes, else ``model_other`` (norms,
    projections, embedding, head). Outside the model in the tick or in
    the first-token program: the sampler."""
    if op_name is not None:
        for part in reversed(op_name.split("/")):
            if part in SCOPES:
                return part
        if "/Llama/" in op_name or op_name.startswith("params["):
            return "model_other"
    if module.startswith(("jit__tick_paged", "jit__sample_first")):
        return "sampler"
    return "model_other" if op_name is not None else "unnamed"


def reduce_scopes(events: dict, table: dict, t0: float, t1: float) -> dict:
    """Device self-time of the traced stretch [t0, t1] by scope and by
    program, the Mosaic kernel's time by scope, and the attention
    scopes' time inside the chunk programs."""
    dev = events["devices"][0]
    modules = sorted((m for m in dev["modules"]
                      if m[1] + m[2] > t0 and m[1] < t1),
                     key=lambda m: m[1])
    ops = [op for op in dev["ops"] if op[1] + op[2] > t0 and op[1] < t1]
    selfs = trace_reduce._self_times(ops)
    starts = [m[1] for m in modules]
    names = [re.sub(r"\(.*$", "", m[0]) for m in modules]
    out = {"scope_s": {}, "kernel_s": {}, "chunk_attn_s": 0.0,
           "chunk_scope_s": {}, "unmatched_s": 0.0, "total_s": 0.0}
    body_runs = {}   # (chunk?, layer, instruction) -> times it ran
    for op, self_s in zip(ops, selfs):
        i = int(np.searchsorted(starts, op[1], side="right")) - 1
        module = ""
        if i >= 0 and op[1] < modules[i][1] + modules[i][2]:
            module = names[i]
        op_name = table.get(module, {}).get(op[0])
        scope = scope_of(module, op_name)
        if module in table and op_name is None:
            out["unmatched_s"] += self_s
        out["total_s"] += self_s
        out["scope_s"][scope] = out["scope_s"].get(scope, 0.0) + self_s
        if module.startswith("jit__chunk_paged"):
            out["chunk_scope_s"][scope] = \
                out["chunk_scope_s"].get(scope, 0.0) + self_s
            if scope in ATTN_SCOPES:
                out["chunk_attn_s"] += self_s
        if op[5] == "tpu_custom_call":
            out["kernel_s"][scope] = out["kernel_s"].get(scope, 0.0) + op[2]
        if op_name and "/moe_ffn/while/body" in op_name:
            key = (module.startswith("jit__chunk_paged"),
                   re.search(r"/(block\d+)/", op_name).group(1), op[0])
            body_runs[key] = body_runs.get(key, 0) + 1
    # Tiles the expert loop ran: an instruction of the loop's body runs
    # once a tile, so a layer's busiest body instruction counts them.
    for chunk in (True, False):
        layers = {}
        for (is_chunk, layer, _), n in body_runs.items():
            if is_chunk == chunk:
                layers[layer] = max(layers.get(layer, 0), n)
        out["moe_tiles_chunk" if chunk else "moe_tiles_tick"] = \
            sum(layers.values())
    return out


def traced_stretch(events: dict, facts: dict):
    """[t0, t1] of the harness's steps of the traced stretch, as
    ``run.py`` cuts it."""
    stretch = facts["trace"]
    spans = sorted((h for h in events["host"]
                    if h[0] == trace_reduce.STEP_SPAN),
                   key=lambda h: h[1])[:stretch["step1"] - stretch["step0"]]
    if not spans:
        return None
    return min(h[1] for h in spans), max(h[1] + h[2] for h in spans)


# ------------------------------------------------------ the comparison
def program_expert_sets(model, variables, seq, width: int):
    """The PROGRAM's routing of one sequence: its model's own full
    forward (bf16, its flash kernel, its serving expert path) with the
    routed layers' expert choices collected. ``[L, S, k]``."""
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :seq.size] = seq
    _, state = _program_forward(model)(variables["params"],
                                       jnp.asarray(tokens))
    inter = state["intermediates"]
    sets = [inter[f"block{i}"]["moe"]["expert_index"][0][0]
            for i in range(model.depth)]
    return np.asarray(jnp.stack(sets))[:, :seq.size]


@functools.lru_cache(maxsize=2)
def _program_forward(model):
    return jax.jit(lambda params, tokens: model.apply(
        {"params": params}, tokens, train=False, features_only=True,
        mutable=["intermediates"]))


def mismatch_share(a, b) -> float:
    """Share of (layer, token) pairs whose expert SETS differ."""
    return float(np.mean(np.any(np.sort(a, -1) != np.sort(b, -1), -1)))


def compare(model, variables, cfg, picks, controls=()):
    """The reference over each picked request, once: the served tokens'
    gaps below its best (greedy), their excess over its nucleus
    (sampled), the same of the token each control puts first, and the
    share of (token, layer) pairs the program (and each control) routed
    to another expert set than the reference did."""
    check = cfg["check"]
    width = int(cfg["engine"]["prefill_len"]) + reference.PAD_TO
    gaps, control_gaps, excess, tokens = [], {}, [], 0
    differ, pairs = {}, {}   # by who routed: "program", each control

    def count(who, sets, ref_sets):
        n = sets.shape[0] * sets.shape[1]
        differ[who] = differ.get(who, 0.0) + mismatch_share(sets,
                                                            ref_sets) * n
        pairs[who] = pairs.get(who, 0) + n

    for r in picks:
        g = reference.served_gaps(
            variables["params"], cfg, r["prompt"], r["tokens"],
            check["max_rows"], controls=controls if r["greedy"] else (),
            temperature=r["temperature"], top_p=r["top_p"])
        tokens += g["tokens"]
        if r["greedy"]:
            gaps.append(g["gaps"])
            for name, c in g["control_gaps"].items():
                control_gaps.setdefault(name, []).append(c)
        elif g["nucleus_excess"] is not None:
            excess.append(g["nucleus_excess"])
        for name, sets in g["control_sets"].items():
            count(name, sets, g["expert_sets"])
        seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
        count("program", program_expert_sets(model, variables, seq, width),
              g["expert_sets"])

    def stats(parts):
        if not parts:
            return None
        x = np.concatenate(parts)
        return {"max": float(x.max()), "mean": float(x.mean()),
                "tokens": int(x.size)}

    return {"greedy": stats(gaps), "nucleus": stats(excess),
            "controls": {k: stats(v) for k, v in control_gaps.items()},
            "tokens": tokens, "requests": len(picks),
            "expert_mismatch": {who: differ[who] / pairs[who]
                                for who in differ}}


def with_mismatch(checks, correct, check, share):
    """``decide``'s checks with the expert-set mismatch share beside its
    limit."""
    checks["expert_set_mismatch_share"] = [share,
                                           check["expert_mismatch_limit"]]
    return checks, bool(correct and share is not None
                        and share <= check["expert_mismatch_limit"])


def sample(judged, seed, cfg):
    """``check_sample``'s picks (the longest of each kind among them,
    the rest drawn from the seed) and every other greedy request whose
    prompt is at most ``check.short_prompt_max`` tokens: those cost the
    reference little, lie under the window, and their tokens are what
    steadies the mean gap (a request answers with 4-64 tokens)."""
    picks = check_sample(judged, seed, cfg["check"])
    short = int(cfg["check"].get("short_prompt_max", 0))
    chosen = {id(r) for r in picks}
    return picks + [r for r in judged
                    if r["greedy"] and r["done"] and r["ok"]
                    and r["prompt_len"] <= short and id(r) not in chosen]


# --------------------------------------------------------------- a run
def stretch_work(cfg, rec, facts) -> dict:
    """What the traced stretch's steps held, for the work model: prompt
    lengths prefilled, contexts decoded at, real tokens of every chunk
    call and of every tick."""
    t, steps = facts.get("trace"), rec.steps
    if not t or "step1" not in t:
        return {}
    lo, hi = steps[t["step0"]]["t0"], steps[t["step1"] - 1]["t1"]
    chunk = int(cfg["engine"]["prefill_chunk"])
    prefills = [r["prompt_len"] for r in rec.requests
                if r["first_s"] is not None and lo <= r["first_s"] <= hi]
    calls = [min(chunk, p - off) for p in prefills
             for off in range(0, p, chunk)]
    ticks = steps[t["step0"]:t["step1"]]
    decodes = []
    for s in ticks:
        n = s["decode_tokens"]
        if n:  # the mean context stands for each of the step's rows
            decodes += [s["decode_ctx"] / n] * n
    return {"prefills": prefills, "decodes": decodes, "chunk_calls": calls,
            "tick_rows": [s["decode_tokens"] for s in ticks
                          if s["decode_tokens"]]}


def run(ctx):
    """One run of one cell; ``ctx`` as ``serve_paged_gpt.run`` takes it
    (``program_path`` is not offered here)."""
    cfg, spec, log = ctx["cfg"], ctx["traffic"], ctx["log"]
    seed, seconds = ctx["seed"], ctx["seconds"]
    if ctx.get("program_path") is not None:
        raise ValueError("this system has no alternative program path")
    model, engine, variables = build(cfg, seed, log)
    warm_requests(engine, cfg, log)
    counts_before = dict(engine.compile_counts())
    load_before = engine.expert_load()
    eng = cfg["engine"]
    sizes = (cfg["vocab_size"], eng["prefill_len"],
             int(cfg["max_position_embeddings"]))
    plan = traffic_lib.generate(spec, seed, seconds, *sizes)
    ramp = traffic_lib.ramp(spec, seed, *sizes)
    rec, facts = run_window(engine, cfg, spec, plan, seconds, ctx["trace"],
                            log, ctx["mark_setup_done"], ramp=ramp)
    counts_after = dict(engine.compile_counts())
    load_after = engine.expert_load()
    if "trace_writer" in facts:
        facts.pop("trace_writer").join()
    has_kernel = "tpu_custom_call" in engine.tick_lowering().as_text()
    on_tpu = ctx["devices"][0].platform == "tpu"
    memory_peak = max(int(d.memory_stats()["peak_bytes_in_use"])
                      for d in ctx["devices"]) if on_tpu else 0
    scopes = None
    if ctx["trace"] is not None and facts.get("trace"):
        t = time.perf_counter()
        events = trace_reduce.extract(
            trace_reduce.find_xplane(ctx["trace"]["dir"]))
        edges = traced_stretch(events, facts)
        if edges is not None:
            scopes = reduce_scopes(events, scope_table(engine), *edges)
            log("trace: scopes in "
                f"{time.perf_counter() - t:.2f}s: " + ", ".join(
                    f"{k} {v:.3f}s" for k, v in sorted(
                        scopes["scope_s"].items(), key=lambda kv: -kv[1]))
                + f"; unmatched {scopes['unmatched_s']:.3f}s of "
                f"{scopes['total_s']:.3f}s; kernel {scopes['kernel_s']}; "
                f"expert tiles run: chunk programs "
                f"{scopes['moe_tiles_chunk']}, ticks "
                f"{scopes['moe_tiles_tick']}")
    work = stretch_work(cfg, rec, facts)
    if scopes and work.get("chunk_calls"):
        from pddl_tpu.ops.moe import expert_tile

        shape = reference.shape_of(cfg)
        pairs = cfg["engine"]["prefill_chunk"] * shape["top_k"]
        rows = scopes["moe_tiles_chunk"] * expert_tile(pairs,
                                                       shape["experts"])
        routed = len(work["chunk_calls"]) * shape["layers"] * pairs
        scopes["moe_rows_over_pairs"] = rows / routed
        log(f"expert path, chunk programs of the traced stretch: {rows} "
            f"rows computed for {routed} routed pairs "
            f"({rows / routed:.3f} x)")
    counters = (facts["counters_open"], facts["counters_close"])
    if all("prefill_tokens" in c for c in counters):
        toks = counters[1]["prefill_tokens"] - counters[0]["prefill_tokens"]
        chunks = {w: n - counters[0]["prefill_chunks"].get(w, 0)
                  for w, n in counters[1]["prefill_chunks"].items()}
        run_toks = sum(int(w) * n for w, n in chunks.items())
        log(f"prefill: {toks} prompt tokens in chunks {chunks}: "
            f"{run_toks / max(toks, 1):.3f} x the tokens computed")
    # Free the program's state (the recorder holds the engine too) before
    # the reference runs: the pool's 3.2 GB is the room it runs in.
    rec.engine = None
    del engine
    gc.collect()

    reqs = rec.requests
    judged = [r for r in reqs if not r.get("ramp")]
    attempted = len(reqs)
    failed = sum(1 for r in reqs if not r["ok"])
    _log_thirds(judged, seconds, log)
    picks = sample(judged, seed, cfg)
    controls = tuple(ctx["control"].split(",")) if ctx.get("control") \
        else ()
    t = time.perf_counter()
    cmp_ = compare(model, variables, cfg, picks, controls)
    log(f"check: reference over {cmp_['requests']} requests (prompts "
        f"{sorted(r['prompt_len'] for r in picks)}), {cmp_['tokens']} "
        f"tokens, {time.perf_counter() - t:.2f}s")
    recompiles = sum(counts_after.values()) - sum(counts_before.values())
    rest = (cmp_["tokens"], failed, recompiles,
            int(on_tpu and not has_kernel))
    checks, correct = with_mismatch(
        *decide(cfg["check"], cmp_["greedy"], cmp_["nucleus"], *rest),
        cfg["check"], cmp_["expert_mismatch"].get("program"))
    in_place = {}
    for name, stats in cmp_["controls"].items():
        c_checks, c_correct = with_mismatch(
            *decide(cfg["check"], stats, cmp_["nucleus"], *rest),
            cfg["check"], cmp_["expert_mismatch"].get(name))
        in_place[name] = {"correct": c_correct, "checks": c_checks}
    load = {k: (load_after[k] - load_before[k]).tolist()
            for k in load_after if k in load_before}
    obs = {"kind": "serve", "cfg": cfg, "traffic": spec, "seconds": seconds,
           "requests": reqs, "judged": judged, "steps": rec.steps,
           "facts": facts, "backlog": False,
           "drain_limit_s": DRAIN_LIMIT_S, "scopes": scopes,
           "expert_load": load, "work": work}
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "checks": checks, "control": in_place, "obs": obs,
            "memory_peak_bytes": memory_peak}
