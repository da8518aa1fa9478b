"""System under test: LFM2-24B-A2B's decoder (gated short convolutions that
keep a fixed state a slot beside the paged K/V of the attention layers,
q/k-normed GQA, a leading dense layer, sigmoid-routed experts) of the
Llama block (``pddl_tpu.models.llama.LFM2_24B_A2B``) through
``pddl_tpu.serve.ServeEngine(paged=True)`` — the same engine, scheduler,
block pool, tick, sampler and spans as the other three system modules.

From the program this module takes the model constructor, the engine, its
counters (``compile_counts``, ``metrics.snapshot``, ``expert_load``), its
lowered programs (``tick_lowering``, ``program_lowerings``) and, for the
expert-set check, the model's own full forward. The window, the recorder,
the sample and ``decide`` are ``serve_paged_gpt``'s; the compiled text's
scope table, the traced stretch, the stretch's work, the two warm requests,
the jitted full forward and the mismatch share are ``serve_paged_moe_lm``'s;
``judge`` and the program's expert sets are ``serve_paged_glm47_flash``'s;
the weights, the reference, the work model and the scopes are this
configuration's own files — and the STATE PROBES: a short convolution
reaches three tokens back, so a state lost between chunks, taken from a
chunk's padding or kept from the slot's last stream moves the logits of
the few positions behind the fault and hardly any token of a long
prompt's answer. Four short greedy requests, served by the same engine
through the same programs before the window opens, put a sampled row
right behind each such place (prompts of 1 and 2 tokens: the first chunk
of a reused slot; of a chunk and 1 and 2 tokens: a last chunk of one and
two real tokens behind a carried state, then the tick behind a padded
chunk); their first tokens' gaps are one more number beside its limit.
"""

from __future__ import annotations

import gc
import re
import time

import jax.numpy as jnp
import numpy as np

from chipbench import trace_reduce
from chipbench import traffic as traffic_lib
from chipbench.reference import lfm2 as reference
from chipbench.systems.serve_paged_glm47_flash import (
    judge,
    program_expert_sets,
)
from chipbench.systems.serve_paged_gpt import (
    DRAIN_LIMIT_S,
    Recorder,
    check_sample,
    run_window,
)
from chipbench.systems.serve_paged_moe_lm import (
    mismatch_share,
    scope_table,
    stretch_work,
    traced_stretch,
    warm_requests,
)
from chipbench.weights import seed_key
from chipbench.weights_lfm2 import make_weights

# Scopes the program names its device work by (`jax.named_scope`).
SCOPES = ("moe_router", "moe_dispatch", "moe_ffn", "moe_combine",
          "shortconv", "attn_global")


def build_model(cfg: dict):
    """The program's model at the configuration's sizes. A program that
    lacks what the configuration needs (the parent of the PR that added
    it) fails here, at once: it has no ``LFM2_24B_A2B``."""
    from pddl_tpu.models.llama import LFM2_24B_A2B

    s = reference.shape_of(cfg)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        cfg["precision"]]
    return LFM2_24B_A2B(
        depth=s["layers"], max_len=int(cfg["engine"]["max_len"]),
        vocab_size=s["vocab"], embed_dim=s["embed"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        intermediate_dim=s["dense_width"],
        moe_intermediate_dim=s["expert_width"], moe_experts=s["experts"],
        moe_top_k=s["top_k"], moe_gate_scale=s["gate_scale"],
        rope_theta=s["theta"], rms_eps=s["eps"], conv_kernel=s["taps"],
        layer_types=s["layer_types"],
        moe_layout=tuple(int(i >= s["dense_layers"])
                         for i in range(s["layers"])),
        dtype=dtype, param_dtype=dtype)


def build(cfg: dict, seed: int, log):
    """The model, weights from the seed, the engine; every program the
    cell's traffic uses warmed. Returns (model, engine, variables)."""
    import jax
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


def state_probes(engine, cfg, seed, log):
    """The state probes (module docstring): greedy requests served one
    after another — so each takes the slot the one before left, and its
    state — of ``check.probe_tokens`` tokens each. Returns their
    records (``prompt``, ``tokens``)."""
    rng = np.random.RandomState((int(seed) + 0x57A7E) % (2 ** 32))
    chunk, n = int(cfg["engine"]["prefill_chunk"]), \
        int(cfg["check"]["probe_tokens"])
    t = time.perf_counter()
    rec = Recorder(engine)
    for i, plen in enumerate((chunk + 1, 1, chunk + 2, 2)):
        rec.submit(traffic_lib.PlannedRequest(
            index=i, due_s=0.0,
            prompt=rng.randint(0, cfg["vocab_size"],
                               size=plen).astype(np.int32),
            max_new_tokens=n, temperature=0.0, top_p=None), 0.0)
        while engine.has_work:
            rec.step(t)
    if not all(r["ok"] for r in rec.requests):
        raise RuntimeError(f"state probes failed: {rec.requests}")
    log(f"setup: state probes {time.perf_counter() - t:.2f}s")
    return rec.requests


def probe_gaps(variables, cfg, probes):
    """The probes' served tokens' gaps below the reference's best, all
    in one array (``probe_tokens`` a probe)."""
    return np.concatenate([reference.served_gaps(
        variables["params"], cfg, r["prompt"], r["tokens"],
        cfg["check"]["probe_tokens"])["gaps"] for r in probes])


def with_probes(checks, correct, check, gaps):
    """``judge``'s checks with the state probes' largest gap beside its
    limit."""
    worst = float(np.max(gaps))
    checks["state_probe_gap_max"] = [worst, check["state_probe_gap_limit"]]
    return checks, bool(correct and worst <= check["state_probe_gap_limit"])


# ------------------------------------------------------------ the trace
def scope_of(module: str, op_name) -> str:
    """The scope a device op's time goes to. In the model: the innermost
    of the program's named scopes, else ``model_other`` (norms, the dense
    MLP, embedding, head). Outside the model in the tick or in the
    first-token program: the sampler."""
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
    """Device self-time of the traced stretch [t0, t1] by scope, by scope
    inside the chunk programs and inside the ticks, and in all; the Mosaic
    kernel's time by scope inside the ticks; the chunk programs' and the
    ticks' device time."""
    dev = events["devices"][0]
    modules = sorted((m for m in dev["modules"]
                      if m[1] + m[2] > t0 and m[1] < t1),
                     key=lambda m: m[1])
    ops = [op for op in dev["ops"] if op[1] + op[2] > t0 and op[1] < t1]
    selfs = trace_reduce._self_times(ops)
    starts = [m[1] for m in modules]
    names = [re.sub(r"\(.*$", "", m[0]) for m in modules]
    out = {"scope_s": {}, "kernel_s": {}, "chunk_scope_s": {},
           "tick_scope_s": {}, "chunk_s": 0.0, "tick_s": 0.0,
           "unmatched_s": 0.0, "total_s": 0.0}

    def add(where, key, seconds):
        out[where][key] = out[where].get(key, 0.0) + seconds

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
        add("scope_s", scope, self_s)
        if module.startswith("jit__chunk_paged"):
            out["chunk_s"] += self_s
            add("chunk_scope_s", scope, self_s)
        elif module.startswith("jit__tick_paged"):
            out["tick_s"] += self_s
            add("tick_scope_s", scope, self_s)
            if op[5] == "tpu_custom_call":
                add("kernel_s", scope, op[2])
    return out


# ------------------------------------------------------ the comparison
def compare(model, variables, cfg, picks, controls=()):
    """The reference over each picked request, once: the served tokens'
    gaps below its best (greedy), their excess over its nucleus and the
    share of them that lie outside it (sampled), the gaps of the token
    each control puts first, and the share of (token, routed layer) pairs
    the program (and each control) routed to another expert set than the
    reference did."""
    check = cfg["check"]
    width = int(cfg["engine"]["max_len"])
    s = reference.shape_of(cfg)
    routed = range(s["dense_layers"], s["layers"])
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
        count("program",
              program_expert_sets(model, variables, seq, width, routed),
              g["expert_sets"])

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
            "expert_mismatch": {who: differ[who] / pairs[who]
                                for who in differ}}


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
    probes = state_probes(engine, cfg, seed, log)
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
            rounded = lambda d: {k: round(v, 3) for k, v in d.items()}
            log("trace: scopes in "
                f"{time.perf_counter() - t:.2f}s: " + ", ".join(
                    f"{k} {v:.3f}s" for k, v in sorted(
                        scopes["scope_s"].items(), key=lambda kv: -kv[1]))
                + f"; unmatched {scopes['unmatched_s']:.3f}s of "
                f"{scopes['total_s']:.3f}s; tick kernel "
                f"{scopes['kernel_s']}; chunk programs "
                f"{scopes['chunk_s']:.3f}s "
                f"{rounded(scopes['chunk_scope_s'])}, ticks "
                f"{scopes['tick_s']:.3f}s {rounded(scopes['tick_scope_s'])}")
    work = stretch_work(cfg, rec, facts)
    counters = (facts["counters_open"], facts["counters_close"])
    if all("state_rows_started" in c for c in counters):
        d = {k: counters[1][k] - counters[0][k]
             for k in ("admissions", "prefill_tokens", "state_rows_started",
                       "prefix_skipped_stateful")}
        log(f"window: {d['admissions']} admissions, "
            f"{d['prefill_tokens']} prompt tokens, "
            f"{d['state_rows_started']} state rows started from zeros, "
            f"{d['prefix_skipped_stateful']} admissions skipped the prefix "
            f"index; {counters[1]['state_bytes_resident']} B of state "
            "resident")
    # How much of the generator's plan the window used: a backlog that
    # runs out would repeat prompts (traffic.BACKLOG_PLAN_RATE_PER_S).
    log(f"plan: {len(rec.requests)} of {len(plan)} planned requests "
        "submitted")
    # Free the program's state (the recorder holds the engine too) before
    # the reference runs: the pool's 3.6 GB is the room it runs in.
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
    probed = probe_gaps(variables, cfg, probes)
    log(f"check: state probes' gaps {np.round(probed, 4).tolist()}")
    checks, correct = with_probes(
        *judge(cfg["check"], cmp_["greedy"], cmp_["nucleus"],
               cmp_["expert_mismatch"].get("program"), rest),
        cfg["check"], probed)
    in_place = {}
    for name, stats in cmp_["controls"].items():
        c_checks, c_correct = with_probes(
            *judge(cfg["check"], stats, cmp_["nucleus"],
                   cmp_["expert_mismatch"].get(name), rest),
            cfg["check"], probed)
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
