"""System under test: GLM-4.7-Flash's decoder (latent attention, a
leading dense layer, sigmoid-routed experts beside a shared one) of the
Llama block (``pddl_tpu.models.llama.GLM_4_7_Flash``) through
``pddl_tpu.serve.ServeEngine(paged=True)`` — the same engine, scheduler,
block pool, tick, sampler and spans as the other two system modules.

From the program this module takes the model constructor, the engine, its
counters (``compile_counts``, ``metrics.snapshot``, ``expert_load``), its
lowered programs (``tick_lowering``, ``program_lowerings``) and, for the
expert-set check, the model's own full forward. The window, the recorder,
the sample and ``decide`` are ``serve_paged_gpt``'s; the compiled text's
scope table, the traced stretch, the stretch's work, the two warm requests,
the jitted full forward and the mismatch share are ``serve_paged_moe_lm``'s; the weights, the reference, the work model
and the scopes are this configuration's own files.
"""

from __future__ import annotations

import gc
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import trace_reduce
from chipbench import traffic as traffic_lib
from chipbench.reference import glm47_flash as reference
from chipbench.systems.serve_paged_gpt import (
    DRAIN_LIMIT_S,
    check_sample,
    decide,
    run_window,
)
from chipbench.systems.serve_paged_moe_lm import (
    _program_forward,
    mismatch_share,
    scope_table,
    stretch_work,
    traced_stretch,
    warm_requests,
    with_mismatch,
)
from chipbench.weights import seed_key
from chipbench.weights_glm47_flash import make_weights

# Scopes the program names its device work by (`jax.named_scope`), the
# innermost first where they nest: `mla_absorb` and `mla_expand` lie
# inside `attn_latent`.
EXPERT_SCOPES = ("moe_dispatch", "moe_ffn", "moe_combine", "moe_shared")
ATTN_SCOPES = ("mla_absorb", "mla_expand", "attn_latent")
SCOPES = ("moe_router",) + EXPERT_SCOPES + ATTN_SCOPES


def build_model(cfg: dict):
    """The program's model at the configuration's sizes. A program that
    lacks what the configuration needs (the parent of the PR that added
    it) fails here, at once: it has no ``GLM_4_7_Flash``."""
    from pddl_tpu.models.llama import GLM_4_7_Flash

    s = reference.shape_of(cfg)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["precision"]]
    return GLM_4_7_Flash(
        depth=s["layers"], max_len=int(cfg["engine"]["max_len"]),
        vocab_size=s["vocab"], embed_dim=s["embed"], num_heads=s["heads"],
        q_lora_rank=s["q_rank"], kv_lora_rank=s["kv_rank"],
        qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
        v_head_dim=s["v_dim"], intermediate_dim=s["dense_width"],
        moe_intermediate_dim=s["expert_width"], moe_experts=s["experts"],
        moe_top_k=s["top_k"], moe_shared_experts=s["shared"],
        moe_gate_scale=s["gate_scale"], rope_theta=s["theta"],
        rms_eps=s["eps"],
        moe_layout=tuple(int(i >= s["dense_layers"])
                         for i in range(s["layers"])),
        dtype=dtype, param_dtype=dtype)


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
        max_queue_depth=eng["max_queue_depth"], aging_s=None,
        rng=seed_key(seed + 1), telemetry_capacity=16)
    log(f"setup: engine build {time.perf_counter() - t:.2f}s")
    t = time.perf_counter()
    engine.warmup()
    log(f"setup: engine.warmup {time.perf_counter() - t:.2f}s "
        f"{engine.compile_counts()}")
    return model, engine, variables


# ------------------------------------------------------------ the trace
def scope_of(module: str, op_name) -> str:
    """The scope a device op's time goes to. In the model: the innermost
    of the program's named scopes, else ``model_other`` (norms,
    projections, the dense MLP, embedding, head). Outside the model in
    the tick or in the first-token program: the sampler."""
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
    """Device self-time of the traced stretch [t0, t1] by scope, by
    scope inside the chunk programs, and in all; the Mosaic kernel's time
    by scope; the chunk programs' and the ticks' device time."""
    dev = events["devices"][0]
    modules = sorted((m for m in dev["modules"]
                      if m[1] + m[2] > t0 and m[1] < t1),
                     key=lambda m: m[1])
    ops = [op for op in dev["ops"] if op[1] + op[2] > t0 and op[1] < t1]
    selfs = trace_reduce._self_times(ops)
    starts = [m[1] for m in modules]
    names = [re.sub(r"\(.*$", "", m[0]) for m in modules]
    out = {"scope_s": {}, "kernel_s": {}, "chunk_scope_s": {},
           "chunk_s": 0.0, "tick_s": 0.0, "unmatched_s": 0.0,
           "total_s": 0.0}
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
            out["chunk_s"] += self_s
            out["chunk_scope_s"][scope] = \
                out["chunk_scope_s"].get(scope, 0.0) + self_s
        elif module.startswith("jit__tick_paged"):
            out["tick_s"] += self_s
        if op[5] == "tpu_custom_call":
            out["kernel_s"][scope] = out["kernel_s"].get(scope, 0.0) + op[2]
    return out


# ------------------------------------------------------ the comparison
def program_expert_sets(model, variables, seq, width: int, routed):
    """The PROGRAM's routing of one sequence: its model's own full
    forward (bf16, its flash kernel, its serving expert path) with the
    routed layers' expert choices collected. ``[L_routed, S, k]``."""
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :seq.size] = seq
    _, state = _program_forward(model)(variables["params"],
                                       jnp.asarray(tokens))
    inter = state["intermediates"]
    sets = [inter[f"block{i}"]["moe"]["expert_index"][0][0] for i in routed]
    return np.asarray(jnp.stack(sets))[:, :seq.size]


def compare(model, variables, cfg, picks, controls=()):
    """The reference over each picked request, once: the served tokens'
    gaps below its best (greedy), their excess over its nucleus and the
    share of them that lie outside it (sampled), the gaps of the token
    each control puts first, and the share of (token, routed layer)
    pairs the program (and each control) routed to another expert set
    than the reference did."""
    check = cfg["check"]
    width = int(cfg["engine"]["max_len"])
    s = reference.shape_of(cfg)
    routed = range(s["dense_layers"], s["layers"])
    gaps, control_gaps, excess, tokens = [], {}, [], 0
    differ, pairs = {}, {}   # by who routed: "program", each control
    by_flip = {}             # who -> (gaps with a flipped pair, without)

    def count(who, sets, ref_sets, who_gaps=None, rows=None):
        n = sets.shape[0] * sets.shape[1]
        differ[who] = differ.get(who, 0.0) + mismatch_share(sets,
                                                            ref_sets) * n
        pairs[who] = pairs.get(who, 0) + n
        if who_gaps is not None:
            # A greedy token's gap, by whether some routed layer chose
            # another expert set than the reference AT THE POSITION the
            # token was read from (earlier positions' flips reach it
            # through attention all the same: "without" is not "none").
            flipped = np.any(np.sort(sets, -1) != np.sort(ref_sets, -1),
                             -1)[:, rows].any(0)
            split = by_flip.setdefault(who, ([], []))
            split[0].append(who_gaps[flipped])
            split[1].append(who_gaps[~flipped])

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
        rows = r["prompt"].size - 1 + np.arange(g["tokens"])
        for name, sets in g["control_sets"].items():
            count(name, sets, g["expert_sets"],
                  g["control_gaps"].get(name), rows)
        seq = np.concatenate([r["prompt"], r["tokens"][:-1]])
        count("program",
              program_expert_sets(model, variables, seq, width, routed),
              g["expert_sets"], g["gaps"] if r["greedy"] else None, rows)

    def stats(parts):
        x = np.concatenate(parts) if parts else np.zeros(0)
        if not x.size:
            return None
        return {"max": float(x.max()), "mean": float(x.mean()),
                "tokens": int(x.size)}

    nucleus = stats(excess)
    if nucleus is not None:
        nucleus["outside_share"] = float(np.mean(np.concatenate(excess) > 0))
    return {"greedy": stats(gaps), "nucleus": nucleus,
            "controls": {k: stats(v) for k, v in control_gaps.items()},
            "tokens": tokens, "requests": len(picks),
            "gap_by_flip": {who: {"flipped": stats(a), "unflipped": stats(b)}
                            for who, (a, b) in by_flip.items()},
            "expert_mismatch": {who: differ[who] / pairs[who]
                                for who in differ}}


def judge(check, greedy, nucleus, mismatch, rest):
    """``decide``'s checks, with two of this configuration's own beside
    their limits: the expert-set mismatch share, and — in place of
    ``nucleus_excess_max`` — the SHARE of sampled tokens outside the
    reference's nucleus. The largest excess cannot be judged here: it
    can read 0.1 at most (the mass top-p leaves out), a dropped filter
    reads 0.09-0.1, and the program's own largest reads up to 0.093
    (PERF.md section 2: a flipped fourth expert carries a gate of 0.3-0.5
    and moves a flat distribution's nucleus edge), so no limit lies
    between them; the share reads a tenth with the filter dropped."""
    checks, correct = with_mismatch(
        *decide(check, greedy, None, *rest), check, mismatch)
    share = nucleus["outside_share"] if nucleus else None
    checks["nucleus_outside_share"] = [share, check["nucleus_outside_limit"]]
    return checks, bool(correct and share is not None
                        and share <= check["nucleus_outside_limit"])


# --------------------------------------------------------------- a run
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
    plan = traffic_lib.generate(spec, seed, seconds, cfg["vocab_size"],
                                eng["prefill_len"], eng["max_len"])
    rec, facts = run_window(engine, cfg, spec, plan, seconds, ctx["trace"],
                            log, ctx["mark_setup_done"])
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
                f"chunk programs {scopes['chunk_s']:.3f}s "
                f"{ {k: round(v, 3) for k, v in scopes['chunk_scope_s'].items()} }"
                f", ticks {scopes['tick_s']:.3f}s")
    work = stretch_work(cfg, rec, facts)
    counters = (facts["counters_open"], facts["counters_close"])
    if all("latent_expanded_tokens" in c for c in counters):
        d = {k: counters[1][k] - counters[0][k]
             for k in ("prefill_tokens", "latent_expanded_tokens")}
        log(f"prefill: {d['prefill_tokens']} prompt tokens, "
            f"{d['latent_expanded_tokens']} cached tokens re-expanded")
    # Free the program's state (the recorder holds the engine too) before
    # the reference runs: the pool's 6.3 GB is the room it runs in.
    rec.engine = None
    del engine
    gc.collect()

    # A standing backlog: the requests still in flight at the close are
    # not failures; those refused or finished wrong are.
    reqs = rec.requests
    judged = [r for r in reqs if r["done"] or "error" in r]
    attempted = len(judged)
    failed = sum(1 for r in judged if not r["ok"])
    log(f"window: {attempted} requests finished or refused, {failed} "
        f"failed, {len(reqs) - attempted} in flight at the close")
    picks = check_sample(judged, seed, cfg["check"])
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
    if cmp_["nucleus"]:
        log("check: sampled tokens' excess over the reference's nucleus: "
            f"largest {cmp_['nucleus']['max']:.4f}, mean "
            f"{cmp_['nucleus']['mean']:.4f}, over "
            f"{cmp_['nucleus']['tokens']} tokens")
    for who, split in cmp_["gap_by_flip"].items():
        log(f"check: {who}'s greedy gaps by routing at the token's own "
            "position: " + "; ".join(
                f"{k} pair: mean {v['mean']:.4f}, max {v['max']:.3f} over "
                f"{v['tokens']} tokens" for k, v in split.items() if v))
    checks, correct = judge(cfg["check"], cmp_["greedy"], cmp_["nucleus"],
                            cmp_["expert_mismatch"].get("program"), rest)
    in_place = {}
    for name, stats in cmp_["controls"].items():
        c_checks, c_correct = judge(
            cfg["check"], stats, cmp_["nucleus"],
            cmp_["expert_mismatch"].get(name), rest)
        in_place[name] = {"correct": c_correct, "checks": c_checks}
    load = {k: (load_after[k] - load_before[k]).tolist()
            for k in load_after if k in load_before}
    obs = {"kind": "serve", "cfg": cfg, "traffic": spec, "seconds": seconds,
           "requests": reqs, "judged": judged, "steps": rec.steps,
           "facts": facts, "backlog": True,
           "drain_limit_s": DRAIN_LIMIT_S, "scopes": scopes,
           "expert_load": load, "work": work}
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "checks": checks, "control": in_place, "obs": obs,
            "memory_peak_bytes": memory_peak}
