"""Online serving throughput: continuous batching vs run-to-completion.

`docs/SERVING.md` measures the single-request path; this bench measures
the ONLINE layer (`pddl_tpu/serve/`) the way a serving owner would:

1. **Head-to-head at 8 concurrent requests** — the same 8 synthetic
   requests served (a) sequentially by `generate()` (the strongest
   honest baseline: each request runs as ONE compiled decode-scan
   dispatch) and (b) through the engine's slot pool, where all 8 share
   every fused tick. The ratio is the continuous-batching lever.
2. **Poisson arrivals at 3 offered loads** (relative to the measured
   engine capacity) — open-loop traffic, the metric set an online
   system is judged by: aggregate tokens/s, p50/p99 TTFT (queue wait
   included), queue depth, slot occupancy, and shed load at the
   oversaturated point.
3. **Shared-prefix workload** (`--prefix-shared-frac`, default 80%) —
   the prefix-cache lever (`pddl_tpu/serve/kvcache/`): the same
   requests through the engine with the radix prefix cache ON vs OFF;
   the TTFT ratio is what block-granular KV reuse buys when traffic
   shares a system prompt. Hit rate, prefill tokens saved, and the
   compile counts (zero recompiles with the cache on too) land in the
   artifact.
4. **Fault leg** (`--fault-rate`, default 1%; `--faults-only` for a
   standalone artifact) — the resilience tax (`pddl_tpu/serve/faults.py`
   + the engine retry/replay/degraded paths): the same closed-loop
   workload clean vs under a seeded 1%-per-dispatch injected fault mix
   (transient device errors + RESOURCE_EXHAUSTED at a tenth the rate).
   The headline is the PAIRED tok/s and mean-TTFT ratios — a
   fault-tolerant engine degrades gracefully (ratio near 1, every
   request terminal), a fail-stop one cliffs to zero. Retries, replays,
   degraded entries, and failed-request counts land in the artifact.
6. **Observability leg** (`--obs-only` for a standalone artifact) —
   the tracing tax (`pddl_tpu/obs/`): the same closed-loop workload
   with per-request tracing OFF (the default no-op tracer) vs ON
   (spans + JSONL sink). The paired ratio is the cost of turning the
   Dapper-style timeline on; the tracing-OFF number is directly
   comparable to the r08 fault-leg clean throughput (same config), so
   the artifact shows the instrumented engine did not regress the
   uninstrumented one. `--trace out.jsonl` additionally writes a full
   span/tick/metrics event log as a bench artifact.

7. **Fleet leg** (`--fleet-only`, `--fleet-replicas 2,4,8`) — the
   multi-replica tier (`pddl_tpu/serve/fleet/`): N real worker
   processes behind the health-checked router, open-loop Poisson at
   `--fleet-load` × N × the r08 single-engine clean baseline.
   Aggregate tok/s + p99 TTFT per N (the scaling curve), plus the
   failover leg at N ∈ {2, 4}: one replica SIGKILL'd mid-run (paired
   clean/killed waves) — throughput retained vs the 0.9·(N−1)/N
   floor, every request terminal, migrated survivor streams pinned
   token-exact against an oracle engine, zero recompiles on
   survivors.

8. **SLO/overload leg** (`--slo-only`) — overload robustness
   (ISSUE 7: priority/EDF/aging scheduler, chunked-prefill slicing,
   `serve/fleet/admission.py` brownout ladder): a trace-driven load —
   bursty multi-turn sessions over shared system prompts with
   heavy-tail output lengths, 35/15/50 interactive/batch/best_effort —
   at 2× measured fleet capacity through the admission-controlled
   router, PAIRED per repeat with an uncontended wave. Headlines:
   zero requests lost or hung (every one terminal: finished, DEADLINE,
   or shed-with-hint), interactive p99 TTFT ≤ 1.5× its uncontended
   value, best_effort absorbing ≥ 80% of the shedding, zero
   recompiles.

9. **Speculative leg** (`--spec-only`, standalone r17 artifact) —
   per-slot draft/verify inside the fused tick (ISSUE 12,
   `serve/engine.py spec_k`): the same closed-loop workload through a
   speculative engine vs the classic one-token tick, PAIRED per
   repeat, every stream in every wave asserted token-exact against
   the one-shot greedy `generate()` oracle. Headlines: the aggregate
   tok/s speedup at the default k, the acceptance-rate-vs-k curve,
   and a chaos leg (seeded faults + a 2-replica fleet kill
   mid-speculation) proving replayed/migrated speculative streams
   stay token-exact.

10. **Control-plane leg** (`--ctrlplane-only`, standalone r19
   artifact, ISSUE 14) — the durability tier
   (`serve/fleet/journal.py`, `transport.py`, gray machinery): (a)
   PAIRED clean vs 1%-injected wire-fault waves through real worker
   processes — throughput retained with every CRC reject counted and
   every stream token-exact (zero corrupt frames accepted); (b)
   router "SIGKILL" + `FleetRouter.recover` — WAL-rebuilt streams
   resume token-exact, with the recovery wall time (`recovery_s`)
   measured from recover() to every stream past its mirrored length;
   (c) gray-replica hedging ON vs OFF under an injected slow replica
   — interactive p99 TTFT, hedge wins counted, zero recompiles.

11. **Disaggregation leg** (`--disagg-only`, standalone r20 artifact,
   ISSUE 17) — prefill/decode role split (`serve/fleet/disagg.py`):
   the bursty LONG-PROMPT trace through a same-N pair of in-process
   fleets, unified vs split (prefill pool + decode pool,
   block-granular KV hand-off through the host tier), PAIRED per
   repeat. Decode-side latency is tick-attributed: each token is
   charged the wall duration of the engine step that produced it, so
   prefill admissions sharing a replica show up as latency on its
   co-resident decode streams. Headlines: decode-side p99 per-token
   latency ratio (`decode_p99_interference` ≤ 0.8× — the
   interference disaggregation exists to remove), aggregate tok/s
   retained ≥ 0.95×, `handoff_ms` per shipped chain, every stream
   token-exact across the two fleet shapes, zero recompiles on the
   decode replicas.

Every record embeds the engine's final `ServeMetrics.snapshot()`, so
artifacts carry tail latencies (TTFT/token-latency p50/p99), not just
throughput.

Timing follows the artifact discipline of
`pddl_tpu/utils/bench_artifact.py`: every headline number is a median
over `--repeats >= 3` runs with the spread recorded, and the record
carries the emitting tree's git commit.

Weights are random (throughput does not depend on training); programs
are compiled at warmup and the bench records the engine's
compile-counts so the zero-recompile claim is visible in the artifact
(the test suite pins it; `tests/test_serve_engine.py`).

    PYTHONPATH=. python benchmarks/serve_bench.py \
        [--slots 8] [--out artifacts/gpt_bench/r06_serve.json]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from pddl_tpu.models.gpt import GPT, generate
from pddl_tpu.obs import JsonlEventLog, RequestTracer
from pddl_tpu.serve import (
    FaultKind,
    FaultPlan,
    Priority,
    QueueFull,
    RequestState,
    SamplingParams,
    ServeEngine,
)
from pddl_tpu.utils.bench_artifact import median_spread, provenance


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _write_record(record: dict, out: str) -> None:
    """One artifact-write path for every leg combination: JSON line to
    stdout, plus the ``--out`` file when given."""
    line = json.dumps(record)
    print(line)
    if out:
        out_dir = os.path.dirname(out)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        with open(out, "w") as f:
            f.write(line + "\n")


def _log_fault_leg(faults: dict) -> None:
    _log(f"faults x{faults['fault_rate_per_dispatch']:.1%}: throughput "
         f"retained {faults['throughput_retained_x']}x (pairs "
         f"{faults['throughput_retained_per_pair']}), TTFT "
         f"{faults['clean_mean_ttft_s']}s -> "
         f"{faults['faulted_mean_ttft_s']}s, injected "
         f"{faults['faults_injected_total']}, recovery "
         f"{faults['recovery_counters_total']}")


def _make_requests(n: int, prompt_len: int, new_tokens: int, vocab: int,
                   seed: int = 0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
            for _ in range(n)]


def _sequential_baseline(model, variables, prompts, new_tokens: int,
                         repeats: int = 3):
    """Run-to-completion: each request is one generate() call (compiled
    once — same shapes reuse the cached decode scan). Median tok/s over
    ``repeats`` passes, spread recorded."""
    # Warm the compiled programs outside the timed window, like the
    # decode benches do.
    warm = generate(model, variables, jnp.asarray(prompts[0])[None],
                    new_tokens)
    jax.block_until_ready(warm)
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for p in prompts:
            out = generate(model, variables, jnp.asarray(p)[None],
                           new_tokens)
        jax.block_until_ready(out)
        samples.append(len(prompts) * new_tokens
                       / (time.perf_counter() - t0))
    return median_spread(samples)


def _engine_concurrent(model, variables, prompts, new_tokens: int,
                       slots: int, prefill_len: int, repeats: int = 3):
    """All requests submitted up front (closed-loop, max concurrency).
    Prompts here are random — nothing for the radix index to share."""
    eng = ServeEngine(model, variables, max_slots=slots,
                      prefill_len=prefill_len,
                      max_queue_depth=len(prompts) + 1)
    eng.warmup()
    samples = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        handles = [eng.submit(p, new_tokens) for p in prompts]
        eng.run(max_steps=100000)
        dt = time.perf_counter() - t0
        assert all(h.done for h in handles)
        assert sum(len(h.tokens) for h in handles) \
            == len(prompts) * new_tokens
        samples.append(len(prompts) * new_tokens / dt)
    med, spread = median_spread(samples)
    return med, spread, eng


def _prefix_ttft_leg(model, variables, *, n_requests: int,
                     prompt_len: int, shared_frac: float, new_tokens: int,
                     slots: int, prefill_len: int, block_size: int,
                     chunk: int, vocab: int, repeats: int, seed: int = 3):
    """The prefix-cache lever: a shared-prefix workload (radix hits,
    "on") vs the same lengths with nothing shared (every admission
    cold, "off") through identical engines — the pool is the KV cache,
    so there is no engine without it; returns the artifact
    fragment (median mean-TTFT ratio over ``repeats``, hit telemetry,
    compile counts).

    The leg keeps ``n_requests <= slots`` and short decodes so the
    whole burst admits in one pass and TTFT measures ADMISSION — the
    prefill path the prefix cache actually shortens. (With requests
    queuing behind long decodes, TTFT is decode-capacity wait that no
    prefill lever can touch, and the ratio would understate the cache
    by construction.)"""
    rng = np.random.default_rng(seed)
    shared_len = int(prompt_len * shared_frac)
    shared = rng.integers(0, vocab, size=shared_len).astype(np.int32)
    prompts = [np.concatenate([
        shared,
        rng.integers(0, vocab, size=prompt_len - shared_len)
        .astype(np.int32)]) for _ in range(n_requests)]
    cold_prompts = [rng.integers(0, vocab, size=prompt_len)
                    .astype(np.int32) for _ in range(n_requests)]

    def run_once(workload):
        # The auto-sized pool holds every slot at max_len plus
        # headroom: the leg measures reuse, not eviction.
        eng = ServeEngine(
            model, variables, max_slots=slots, prefill_len=prefill_len,
            max_queue_depth=n_requests + 1,
            prefix_block_size=block_size, prefix_chunk=chunk)
        eng.warmup()
        handles = [eng.submit(p, new_tokens) for p in workload]
        eng.run(max_steps=100000)
        assert all(h.done for h in handles)
        ttfts = [h.ttft_s for h in handles]
        return float(np.mean(ttfts)), eng

    on_ttfts, off_ttfts, ratios = [], [], []
    eng_on = eng_off = None
    for _ in range(repeats):
        # PAIRED design: each repeat runs on/off back to back, and the
        # headline is the median of per-pair ratios — host load drift
        # hits both runs of a pair and cancels in the quotient, where
        # it would inflate the spread of the raw TTFT medians.
        t_on, eng_on = run_once(prompts)
        t_off, eng_off = run_once(cold_prompts)
        on_ttfts.append(t_on)
        off_ttfts.append(t_off)
        ratios.append(t_off / t_on)
    on_med, _ = median_spread(on_ttfts)
    off_med, _ = median_spread(off_ttfts)
    ratio_med, ratio_spread = median_spread(ratios)
    snap = eng_on.metrics.snapshot()
    return {
        "shared_frac": shared_frac,
        "prompt_len": prompt_len,
        "n_requests": n_requests,
        "prefix_block_size": block_size,
        "prefix_chunk": chunk,
        "mean_ttft_prefix_off_s": round(off_med, 5),
        "mean_ttft_prefix_on_s": round(on_med, 5),
        "ttft_reduction_x": round(ratio_med, 3),
        "ttft_reduction_per_pair": [round(r, 3) for r in ratios],
        "spread_pct": round(ratio_spread, 2),
        "prefix_hit_rate": round(snap["prefix_hit_rate"], 3),
        "prefill_tokens_saved": snap["prefill_tokens_saved"],
        "prefix_blocks_live": snap["prefix_blocks_live"],
        "prefix_evictions": snap["prefix_evictions"],
        "engine_compile_counts_prefix_on": eng_on.compile_counts(),
        "engine_compile_counts_prefix_off": eng_off.compile_counts(),
    }


def _tenant_leg(model, variables, *, n_requests: int, prompt_len: int,
                new_tokens: int, slots: int, prefill_len: int,
                n_adapters: int, vocab: int, repeats: int,
                seed: int = 23):
    """Multi-tenant serving (ISSUE 9, `serve/tenant/`), three headlines:

    1. **Memory elimination** — ``merged_copy_eliminated_x``: serving N
       tenants the naive way means N merged model copies in HBM
       (``N x base params``); the paged adapter pool serves them from
       ONE base copy plus fixed-shape factor pools. The ratio is
       arithmetic over real allocated sizes (deterministic — no
       repeats needed), the platform-economics headline.
    2. **Mixed-tenant throughput** — ``tenant_throughput_retained_x``:
       the same closed-loop workload through (a) a tenant engine with
       requests spread over ``n_adapters`` adapters plus constrained +
       unconstrained + no-adapter slots sharing every fused tick, and
       (b) a PLAIN engine (the r13-baseline program set) — PAIRED per
       repeat. Near-1 means per-request tenancy rides the batch almost
       free; also reported as the absolute ``mixed_tenant_tok_s``.
    3. **Constrained-decode overhead** — ``mask_overhead_x``: the same
       tenant engine serving an ALL-constrained wave vs an
       all-unconstrained one (identical token counts: the grammar is a
       fixed-length digit chain, so every stream emits exactly
       ``new_tokens``), paired per repeat. The mask path costs one
       ``[S, V]`` where + the FSM advance per token.
    """
    from pddl_tpu.serve import AdapterRegistry, TenantConfig

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(n_requests)]
    # Grammar vocabulary: token id i -> a digit character for the first
    # ten ids (the constrained wave's language), one unmatched filler
    # character beyond — constrained streams then emit digit tokens
    # only, unconstrained ones roam the whole vocab.
    token_strings = [str(i) if i < 10 else chr(0x100 + i)
                     for i in range(vocab)]
    digit_chain = {"kind": "regex", "pattern": "[0-9]" * new_tokens}

    # Warm the constraint automaton OUTSIDE the timed windows: spec
    # compilation is one-time per (spec, vocabulary) PROCESS-wide
    # (`grammar._FSM_CACHE`), amortized over every request/engine like
    # program compilation — the same exclusion discipline as warmup().
    from pddl_tpu.serve.tenant import compile_constraint
    compile_constraint(digit_chain, token_strings)

    def registry():
        reg = AdapterRegistry(model.embed_dim, model.vocab_size, rank=8)
        for i in range(n_adapters):
            reg.register_random(f"tenant{i}", seed=300 + i, scale=0.05)
        return reg

    def tenant_engine():
        return ServeEngine(
            model, variables, max_slots=slots, prefill_len=prefill_len,
            max_queue_depth=n_requests + 1,
            tenant=TenantConfig(registry=registry(),
                                adapter_pool_slots=slots + n_adapters + 1,
                                token_strings=token_strings))

    def run_wave(eng, submits):
        t0 = time.perf_counter()
        handles = [eng.submit(p, new_tokens, **kw) for p, kw in submits]
        eng.run(max_steps=200000)
        dt = time.perf_counter() - t0
        assert all(h.done for h in handles), "engine failed to drain"
        delivered = sum(len(h.tokens) for h in handles)
        return delivered / dt

    def mixed_submits():
        out = []
        for i, p in enumerate(prompts):
            kw = {}
            if i % 4 != 3:  # 3 of 4 requests are adapted
                kw["adapter"] = f"tenant{i % n_adapters}"
            if i % 4 == 1:  # every 4th is ALSO grammar-constrained
                kw["constraint"] = digit_chain
            out.append((p, kw))
        return out

    # --- headline 1: arithmetic over real allocated sizes (the pool
    # is `pool_rows` rows of `AdapterRegistry.adapter_nbytes` each —
    # no throwaway engine needed, and nothing extra stays resident
    # across the timed waves below).
    base_bytes = sum(int(leaf.size) * leaf.dtype.itemsize
                     for leaf in jax.tree.leaves(variables["params"]))
    pool_bytes = (slots + n_adapters + 1) * registry().adapter_nbytes
    merged_eliminated = (n_adapters * base_bytes) \
        / (base_bytes + pool_bytes)

    # FOUR resident engines, each reused for every repeat of its arm
    # (the engines are built for sustained traffic — waves re-admit
    # into free slots): the arms of a pair then run SECONDS apart
    # instead of across two ~30 s engine builds, so host-load drift
    # cancels in the quotients. One UNTIMED wave per engine first puts
    # all four in the same steady state (programs compiled, prefix
    # caches warm on these exact prompts, adapters resident).
    eng_t = tenant_engine()
    eng_p = ServeEngine(model, variables, max_slots=slots,
                        prefill_len=prefill_len,
                        max_queue_depth=n_requests + 1)
    eng_u = tenant_engine()
    eng_c = tenant_engine()
    plain_wave = [(p, {}) for p in prompts]
    con_wave = [(p, {"constraint": digit_chain}) for p in prompts]
    for eng, wave in ((eng_t, mixed_submits()), (eng_p, plain_wave),
                      (eng_u, plain_wave), (eng_c, con_wave)):
        eng.warmup()
        run_wave(eng, wave)

    tenant_tps, plain_tps, retained = [], [], []
    con_tps, unc_tps, mask_over = [], [], []
    for _ in range(repeats):
        # PAIRED per repeat (host drift cancels in each quotient).
        tps_t = run_wave(eng_t, mixed_submits())
        tps_p = run_wave(eng_p, plain_wave)
        tenant_tps.append(tps_t)
        plain_tps.append(tps_p)
        retained.append(tps_t / tps_p)
        tps_u = run_wave(eng_u, plain_wave)
        tps_c = run_wave(eng_c, con_wave)
        unc_tps.append(tps_u)
        con_tps.append(tps_c)
        mask_over.append(tps_u / tps_c)
    tps_med, tps_spread = median_spread(tenant_tps)
    ret_med, ret_spread = median_spread(retained)
    mask_med, mask_spread = median_spread(mask_over)
    snap = eng_t.metrics.snapshot()
    return {
        "n_adapters": n_adapters,
        "n_requests": n_requests,
        "adapter_rank": 8,
        "base_params_bytes": base_bytes,
        "adapter_pool_bytes": pool_bytes,
        "merged_copy_eliminated_x": round(merged_eliminated, 3),
        "mixed_tenant_tok_s": round(tps_med, 1),
        "mixed_tenant_tok_s_spread_pct": round(tps_spread, 2),
        "plain_engine_tok_s": round(median_spread(plain_tps)[0], 1),
        "tenant_throughput_retained_x": round(ret_med, 3),
        "tenant_retained_per_pair": [round(r, 3) for r in retained],
        "tenant_retained_spread_pct": round(ret_spread, 2),
        "constrained_tok_s": round(median_spread(con_tps)[0], 1),
        "unconstrained_tok_s": round(median_spread(unc_tps)[0], 1),
        "mask_overhead_x": round(mask_med, 3),
        "mask_overhead_per_pair": [round(r, 3) for r in mask_over],
        "mask_overhead_spread_pct": round(mask_spread, 2),
        "adapter_hit_rate": round(snap["adapter_hit_rate"], 3)
        if snap["adapter_hit_rate"] is not None else None,
        "adapter_loads": snap["adapter_loads"],
        "adapter_evictions": snap["adapter_evictions"],
        "constrained_requests": snap["constrained_requests"],
        "requests_grammar_complete": snap["requests_grammar_complete"],
        "engine_compile_counts_tenant": eng_t.compile_counts(),
    }


def _spec_leg(model, variables, *, n_requests: int, prompt_len: int,
              new_tokens: int, slots: int, prefill_len: int,
              spec_k: int, k_values, vocab: int, repeats: int,
              chaos_seeds=(0, 1, 2), seed: int = 23):
    """Speculative serving vs the classic one-token tick (ISSUE 12):
    the SAME closed-loop workload through a ``spec_k`` engine and a
    plain engine, PAIRED per repeat (host drift cancels in the
    quotient). Every stream in every wave is asserted token-exact
    against the one-shot greedy ``generate()`` oracle — speculation
    changes the tick count, never a token. Also records the
    acceptance-rate-vs-k curve (one wave per k) and a chaos leg:
    seeded mixed faults on the speculative engine plus a 2-replica
    fleet kill mid-speculation, all streams token-exact vs the
    non-speculative oracle."""
    prompts = _make_requests(n_requests, prompt_len, new_tokens, vocab,
                             seed=seed)
    refs = []
    for p in prompts:
        out = generate(model, variables, jnp.asarray(p)[None],
                       new_tokens)
        refs.append(np.asarray(out)[0, len(p):].tolist())

    def build(k, fault_plan=None):
        return ServeEngine(model, variables, max_slots=slots,
                           prefill_len=prefill_len,
                           max_queue_depth=n_requests + 1, spec_k=k,
                           fault_plan=fault_plan,
                           backoff_sleep=lambda s: None)

    def run_wave(eng):
        t0 = time.perf_counter()
        handles = [eng.submit(p, new_tokens) for p in prompts]
        eng.run(max_steps=200000)
        dt = time.perf_counter() - t0
        assert all(h.done for h in handles)
        for h, ref in zip(handles, refs):
            assert h.tokens == ref, "speculative stream diverged"
        return n_requests * new_tokens / dt

    # Paired headline waves at the default k.
    spec_samples, base_samples, ratios = [], [], []
    spec_eng = base_eng = None
    for _ in range(repeats):
        spec_eng = build(spec_k)
        spec_eng.warmup()
        s_tps = run_wave(spec_eng)
        base_eng = build(0)
        base_eng.warmup()
        b_tps = run_wave(base_eng)
        spec_samples.append(s_tps)
        base_samples.append(b_tps)
        ratios.append(s_tps / b_tps)
    spec_med, spec_spread = median_spread(spec_samples)
    base_med, _ = median_spread(base_samples)
    ratio_med, ratio_spread = median_spread(ratios)
    snap = spec_eng.metrics.snapshot()

    # Acceptance-rate-vs-k curve: one wave per k (token-exactness
    # asserted inside run_wave for every point).
    curve = []
    for k in k_values:
        eng = build(k)
        eng.warmup()
        tps = run_wave(eng)
        ks = eng.metrics.snapshot()
        total = n_requests * new_tokens
        curve.append({
            "k": k,
            "acceptance_rate": round(ks["spec_acceptance_rate"] or 0.0,
                                     4),
            "spec_tok_s": round(tps, 1),
            "tokens_per_tick": round(total / max(ks["spec_ticks"], 1),
                                     3),
        })

    # Chaos leg: (a) seeded mixed faults through the speculative
    # engine — replayed speculative streams token-exact vs the oracle;
    # (b) a 2-replica speculative fleet with a kill mid-speculation —
    # live-migrated streams token-exact on the survivor.
    from pddl_tpu.serve.fleet import FleetRouter, LocalReplica

    chaos_requests = 0
    chaos_replays = 0
    chaos_migrated = 0
    for cs in chaos_seeds:
        plan = FaultPlan(seed=cs, sleep_fn=lambda s: None,
                         transient_rate=0.04, oom_rate=0.01,
                         max_random_injections=16)
        eng = build(spec_k, fault_plan=plan)
        eng.warmup()
        handles = [eng.submit(p, new_tokens) for p in prompts[:slots]]
        eng.run(max_steps=200000)
        for h, ref in zip(handles, refs[:slots]):
            assert h.done and h.tokens == ref, \
                "chaos: replayed speculative stream diverged"
        chaos_requests += len(handles)
        chaos_replays += eng.metrics.replays

        plans = [FaultPlan(sleep_fn=lambda s: None) for _ in range(2)]
        reps = [LocalReplica(i, (lambda pl: lambda: build(spec_k, pl))(
            plans[i])) for i in range(2)]
        fleet = FleetRouter(reps, affinity_block_size=8,
                            affinity_blocks=1, respawn=False)
        fh = [fleet.submit(p, new_tokens) for p in prompts[:4]]
        for _ in range(2):
            fleet.step()
        victim = max(fleet.replicas, key=lambda s: s.load)
        plans[victim.replica_id]._sched[
            (victim.driver.engine._step_idx, "verify")] = [FaultKind.KILL]
        fleet.run(max_steps=200000)
        for h, ref in zip(fh, refs[:4]):
            assert h.done and h.tokens == ref, \
                "chaos: migrated speculative stream diverged"
        chaos_requests += len(fh)
        chaos_migrated += fleet.metrics.requests_migrated

    return {
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "spec_k": spec_k,
        "baseline_tok_s": round(base_med, 1),
        "spec_tok_s": round(spec_med, 1),
        "spec_tok_s_spread_pct": round(spec_spread, 2),
        "spec_speedup_x": round(ratio_med, 3),
        "spec_speedup_per_pair": [round(r, 3) for r in ratios],
        "spread_pct": round(ratio_spread, 2),
        "acceptance_rate": round(snap["spec_acceptance_rate"] or 0.0, 4),
        "tokens_per_tick": round(
            n_requests * new_tokens / max(snap["spec_ticks"], 1), 3),
        "acceptance_curve": curve,
        "all_streams_token_exact": True,  # asserted in every wave above
        "chaos": {
            "seeds": list(chaos_seeds),
            "requests_token_exact": chaos_requests,
            "replays": chaos_replays,
            "requests_migrated": chaos_migrated,
        },
        "engine_compile_counts_spec": spec_eng.compile_counts(),
        "engine_compile_counts_baseline": base_eng.compile_counts(),
        "serve_metrics_snapshot": snap,
    }


def _tier_leg(model, variables, *, repeats: int, mults=(4, 8, 16, 32),
              seed: int = 31):
    """Tiered KV cache vs the r13 evict-and-recompute baseline
    (ISSUE 13), PAIRED at working sets 4-32x the device pool.

    A Zipf-skewed closed-loop trace over ``mult * pool_prompts``
    distinct prefixes, 4 requests per prefix on average AT EVERY
    sweep point (the revisit fraction is the tier's whole lever — at
    2 the compulsory first visits drown it and the 4x point loses to
    its own transfer overhead; a flat cap would thin it back out as
    the sweep widens): the device pool holds ~2 prompts' chains, so at 4x the
    tail already spills and at 32x almost every revisit would
    recompute without the tier. The host byte budget is sized to the
    WORKING SET (the runbook's sizing rule) so the comparison isolates
    the tier, not its own eviction. Both sides of a pair replay the
    IDENTICAL request order, so the Zipf draw cancels in the ratio;
    TTFT is measured closed-loop (one request live at a time), i.e.
    pure admission — the path promotion shortens.

    Sizing note (the r16/r17 sized-worker discipline, inverted): the
    tier's lever is prefill COMPUTE avoided, so the leg needs a model
    where recomputing a prompt costs meaningfully more than one H2D
    block scatter — the default 4x256 with 384-token prompts (~30 ms
    a prefill on the reference container), and a COARSE 48-token
    block so a demotion is 8 slice reads, not 48. On a toy model the
    transfer overhead dominates and the tier rightly loses — that
    regime is what ``min_chain_blocks`` and a zero budget are for."""
    bs, prompt_len, prefill_len, chunk = 48, 384, 384, 96
    blocks_per_prompt = prompt_len // bs
    pool_prompts = 2
    # The pool is the KV cache, so it cannot go under the engine's
    # floor (every slot at max_len + scratch); at max_len 512 that is
    # 23 blocks: the one live stream's 9 and ~2 prompts' cached chains.
    pool_blocks = max(pool_prompts * blocks_per_prompt + 1,
                      2 * -(-model.max_len // bs) + 1)
    # K+V bytes per block: 2 leaves x embed x f32 x depth x block_size.
    kv_block_bytes = 2 * model.embed_dim * 4 * model.depth * bs

    def run_once(tier_bytes, prefixes, order):
        eng = ServeEngine(
            model, variables, max_slots=2, prefill_len=prefill_len,
            max_queue_depth=4, prefix_cache_blocks=pool_blocks,
            prefix_block_size=bs, prefix_chunk=chunk,
            host_tier=tier_bytes)
        eng.warmup()
        ttfts = []
        for idx in order:
            h = eng.submit(prefixes[idx], 2)
            eng.run(max_steps=10000)
            assert h.done
            ttfts.append(h.ttft_s)
        return float(np.mean(ttfts)), eng

    # One UNTIMED warm pair first: the tiered side runs first inside
    # every timed pair, so process-wide one-time costs (eager-op
    # caches, the persistent compile cache, numpy import paths) would
    # otherwise all land on the first pair's tiered TTFT and flip it
    # against a bound the steady state clears comfortably.
    wrng = np.random.default_rng(seed - 1)
    wprefixes = [wrng.integers(0, model.vocab_size,
                               size=prompt_len).astype(np.int32)
                 for _ in range(4)]
    worder = wrng.choice(4, size=8)
    run_once(4 * blocks_per_prompt * kv_block_bytes, wprefixes, worder)
    run_once(None, wprefixes, worder)

    curve = []
    counts_tiered = counts_evict = None
    for mult in mults:
        n_prefixes = pool_prompts * mult
        # UNCAPPED 4x revisit rate: a flat request cap would quietly
        # thin the revisit fraction as the sweep widens (2 per prefix
        # at 16x, 1 at 32x) and the tail of the curve would measure
        # the cap, not the working-set scaling it claims to.
        n_requests = 4 * n_prefixes
        ws_bytes = n_prefixes * blocks_per_prompt * kv_block_bytes
        tier_ts, evict_ts, ratios = [], [], []
        hits_t, hits_e, tier_stats = [], [], []
        for rep in range(repeats):
            rng = np.random.default_rng(seed + 101 * rep + mult)
            prefixes = [rng.integers(0, model.vocab_size,
                                     size=prompt_len).astype(np.int32)
                        for _ in range(n_prefixes)]
            p = 1.0 / np.power(np.arange(1, n_prefixes + 1), 1.1)
            order = rng.choice(n_prefixes, size=n_requests, p=p / p.sum())
            t_tier, eng_t = run_once(ws_bytes, prefixes, order)
            t_evict, eng_e = run_once(None, prefixes, order)
            tier_ts.append(t_tier)
            evict_ts.append(t_evict)
            ratios.append(t_tier / t_evict)
            snap = eng_t.metrics.snapshot()
            hits_t.append(snap["prefix_hit_rate"])
            hits_e.append(eng_e.metrics.snapshot()["prefix_hit_rate"])
            tier_stats.append(snap)
            counts_tiered = eng_t.compile_counts()
            counts_evict = eng_e.compile_counts()
        ratio_med, ratio_spread = median_spread(ratios)

        # Tier traffic and hit rates are MEDIANS across the paired
        # repeats like the TTFT fields beside them — each repeat draws
        # its own Zipf trace, and pinning the gate to whichever repeat
        # ran last would let one noisy draw flip it.
        def _stat_med(key):
            return float(np.median([s[key] for s in tier_stats]))

        curve.append({
            "working_set_x": mult,
            "n_prefixes": n_prefixes,
            "n_requests": n_requests,
            "host_tier_byte_budget": ws_bytes,
            "mean_ttft_tiered_s": round(median_spread(tier_ts)[0], 5),
            "mean_ttft_evict_s": round(median_spread(evict_ts)[0], 5),
            "ttft_tiered_over_evict_x": round(ratio_med, 3),
            "ttft_ratio_per_pair": [round(r, 3) for r in ratios],
            "spread_pct": round(ratio_spread, 2),
            "hit_rate_tiered": round(float(np.median(hits_t)), 3),
            "hit_rate_evict": round(float(np.median(hits_e)), 3),
            "host_tier_spills": int(_stat_med("host_tier_spills")),
            "host_tier_promotions":
                int(_stat_med("host_tier_promotions")),
            "host_tier_promote_tokens_charged":
                int(_stat_med("host_tier_promote_tokens_charged")),
            "host_tier_bytes_resident":
                int(_stat_med("host_tier_bytes_resident")),
        })
    # The ISSUE 13 headline point; None (leaf omitted by the gate's
    # numeric-leaf walk) when a custom --tier-mults sweep skips 8 —
    # the curve itself still carries every measured point.
    at8 = next((c for c in curve if c["working_set_x"] == 8), None)
    return {
        "prompt_len": prompt_len,
        "prefix_block_size": bs,
        "device_pool_blocks": pool_blocks,
        "device_pool_prompts": pool_prompts,
        "zipf_a": 1.1,
        "curve": curve,
        "mean_ttft_ratio_at_8x": (at8["ttft_tiered_over_evict_x"]
                                  if at8 is not None else None),
        "all_pairs_directional": all(
            r < 1.0 for c in curve for r in c["ttft_ratio_per_pair"]),
        "engine_compile_counts_tiered": counts_tiered,
        "engine_compile_counts_evict": counts_evict,
    }


def _tier_fleet_leg(model, variables, *, repeats: int, seed: int = 37):
    """The 2-replica half of ISSUE 13: duplicate-prefill tokens
    eliminated by the chain pull vs shadow-blind routing, PAIRED.

    Replica A holds the warm shared prefix (and two long batch streams
    keep it loaded); interactive probes sharing the prefix escape to
    cold replica B. Shadow-blind, B re-prefills the prefix it has
    never seen — tokens the FLEET already computed. With
    ``chain_pull_blocks`` armed, the router pulls A's chain into B's
    host tier and the admission promotes instead. duplicate tokens =
    matchable prefix tokens probes presented on B minus the tokens B's
    cache (pull included) saved — computed from the cold replica's own
    prefill_tokens_saved counter, no estimate."""
    from pddl_tpu.serve.fleet import FleetRouter, LocalReplica

    bs, prompt_len, prefill_len = 8, 48, 64
    shared_blocks = 5          # probes share 5*bs = 40 leading tokens
    l_match = shared_blocks * bs
    n_probes = 6

    def factory():
        return ServeEngine(
            model, variables, max_slots=4, prefill_len=prefill_len,
            max_queue_depth=16,
            prefix_block_size=bs, prefix_chunk=16,
            host_tier=1 << 24)

    def run_pair(rep, pull):
        rng = np.random.default_rng(seed + rep)
        shared = rng.integers(0, model.vocab_size,
                              size=prompt_len).astype(np.int32)
        fleet = FleetRouter(
            [LocalReplica(0, factory), LocalReplica(1, factory)],
            affinity_block_size=bs, interactive_reroute_load=1,
            shadow_host_capacity_blocks=4096,
            chain_pull_blocks=(2 if pull else None))
        fleet.warmup()
        warmer = fleet.submit(list(shared), 2, priority=Priority.BATCH)
        while not warmer.done:
            fleet.step()
        warm_id = warmer.replica_id
        busy = [fleet.submit(list(shared), 48, priority=Priority.BATCH)
                for _ in range(2)]
        probe_tokens = []
        for _ in range(n_probes):
            p = np.concatenate([
                shared[:l_match],
                rng.integers(0, model.vocab_size, prompt_len - l_match)
                .astype(np.int32)])
            h = fleet.submit(list(p), 2, priority=Priority.INTERACTIVE)
            while not h.done:
                fleet.step()
            assert h.replica_id != warm_id, "probe did not escape"
            probe_tokens.append(list(h.tokens))
        while not all(b.done for b in busy):
            fleet.step()
        cold = next(s for s in fleet.replicas
                    if s.replica_id != warm_id)
        saved = cold.driver.engine.metrics.prefill_tokens_saved
        duplicate = n_probes * l_match - saved
        pulls = fleet.metrics.chain_pulls
        pull_tokens = fleet.metrics.chain_pull_tokens
        promoted = cold.driver.engine.metrics.host_tier_promotions
        fleet.close()
        return duplicate, pulls, pull_tokens, promoted, probe_tokens

    dup_blind, dup_pulled, pulls_total, promoted_total = [], [], 0, 0
    for rep in range(repeats):
        d_b, _, _, _, toks_b = run_pair(rep, pull=False)
        d_p, pulls, pull_tokens, promoted, toks_p = run_pair(rep,
                                                             pull=True)
        assert toks_b == toks_p, "pull changed a stream"
        dup_blind.append(d_b)
        dup_pulled.append(d_p)
        pulls_total += pulls
        promoted_total += promoted
    import statistics

    # Plain medians: the pulled side is exactly 0 when elimination is
    # total, and a spread over zero is undefined — the per-pair lists
    # carry the drift picture instead.
    blind_med = float(statistics.median(dup_blind))
    pulled_med = float(statistics.median(dup_pulled))
    return {
        "replicas": 2,
        "n_probe_requests": n_probes,
        "shared_prefix_tokens_matchable": l_match,
        "duplicate_prefill_tokens_blind": blind_med,
        "duplicate_prefill_tokens_pulled": pulled_med,
        "duplicate_per_pair_blind": dup_blind,
        "duplicate_per_pair_pulled": dup_pulled,
        "chain_pulls": pulls_total,
        "host_tier_promotions_cold_replica": promoted_total,
        "all_pairs_directional": all(
            p < b for p, b in zip(dup_pulled, dup_blind)),
        "streams_identical_blind_vs_pulled": True,
    }


def _fault_leg(model, variables, *, n_requests: int, prompt_len: int,
               new_tokens: int, slots: int, prefill_len: int,
               fault_rate: float, vocab: int, repeats: int, seed: int = 11):
    """Graceful-degradation measurement: the same closed-loop workload
    clean vs under seeded injection at ``fault_rate`` per device
    dispatch (transient errors, plus RESOURCE_EXHAUSTED at a tenth the
    rate so the degraded path fires too). PAIRED runs per repeat —
    host-load drift cancels in the per-pair ratio. Throughput counts
    DELIVERED tokens (a failed request's partial stream included), so
    a crash-looping engine cannot hide behind survivors."""
    prompts = _make_requests(n_requests, prompt_len, new_tokens, vocab,
                             seed=seed)

    def run_once(rate, run_seed):
        plan = (FaultPlan(seed=run_seed, transient_rate=rate,
                          oom_rate=rate / 10.0) if rate > 0 else None)
        eng = ServeEngine(model, variables, max_slots=slots,
                          prefill_len=prefill_len,
                          max_queue_depth=n_requests + 1,
                          fault_plan=plan, retry_backoff_s=0.005)
        eng.warmup()
        t0 = time.perf_counter()
        handles = [eng.submit(p, new_tokens) for p in prompts]
        eng.run(max_steps=200000)
        dt = time.perf_counter() - t0
        assert all(h.done for h in handles), "engine failed to drain"
        delivered = sum(len(h.tokens) for h in handles)
        ttft = float(np.mean([h.ttft_s for h in handles
                              if h.ttft_s is not None]))
        finished = sum(h.state == RequestState.FINISHED for h in handles)
        return delivered / dt, ttft, finished, eng, plan

    tps_ratios, ttft_ratios = [], []
    clean_tps_all, fault_tps_all = [], []
    clean_ttft_all, fault_ttft_all = [], []
    finished_min = n_requests
    eng_fault = None
    # Injections and recovery work summed over ALL faulted repeats —
    # last-run-only counters can honestly read 0 at a 1% rate, which
    # would make the artifact look like nothing was survived.
    injected_total = {k.value: 0 for k in FaultKind}
    counters_total = {"retries": 0, "replays": 0, "degraded_entries": 0,
                      "requests_failed": 0}
    for i in range(repeats):
        c_tps, c_ttft, _, _, _ = run_once(0.0, seed + i)
        f_tps, f_ttft, f_fin, eng_fault, plan = run_once(fault_rate,
                                                         seed + i)
        clean_tps_all.append(c_tps)
        fault_tps_all.append(f_tps)
        clean_ttft_all.append(c_ttft)
        fault_ttft_all.append(f_ttft)
        tps_ratios.append(f_tps / c_tps)
        ttft_ratios.append(f_ttft / c_ttft)
        finished_min = min(finished_min, f_fin)
        for kind, count in plan.injected.items():
            injected_total[kind.value] += count
        snap_i = eng_fault.metrics.snapshot()
        for key in counters_total:
            counters_total[key] += snap_i[key]
    tps_med, tps_spread = median_spread(tps_ratios)
    return {
        "fault_rate_per_dispatch": fault_rate,
        "oom_rate_per_dispatch": fault_rate / 10.0,
        "n_requests": n_requests,
        "new_tokens": new_tokens,
        "clean_tokens_per_s": round(median_spread(clean_tps_all)[0], 1),
        "faulted_tokens_per_s": round(median_spread(fault_tps_all)[0], 1),
        "throughput_retained_x": round(tps_med, 3),
        "throughput_retained_per_pair": [round(r, 3) for r in tps_ratios],
        "throughput_retained_spread_pct": round(tps_spread, 2),
        "clean_mean_ttft_s": round(median_spread(clean_ttft_all)[0], 5),
        "faulted_mean_ttft_s": round(median_spread(fault_ttft_all)[0], 5),
        "ttft_inflation_per_pair": [round(r, 3) for r in ttft_ratios],
        "min_requests_finished_faulted": finished_min,
        "faults_injected_total": injected_total,
        "recovery_counters_total": counters_total,
        "engine_compile_counts_faulted": eng_fault.compile_counts(),
        # Tail latencies, not just throughput: the faulted engine's
        # full final snapshot rides in the artifact.
        "serve_metrics_snapshot": eng_fault.metrics.snapshot(),
    }


def _obs_leg(model, variables, *, n_requests: int, prompt_len: int,
             new_tokens: int, slots: int, prefill_len: int, vocab: int,
             repeats: int, seed: int = 5):
    """The tracing tax: the same closed-loop workload with per-request
    tracing OFF (the engine default — the no-op tracer) vs ON (a
    `RequestTracer` streaming every span to a JSONL sink). PAIRED runs
    per repeat so host-load drift cancels in the ratio. The OFF number
    is the instrumented engine at its production default; the
    acceptance gate compares it against the pre-obs engine's committed
    clean throughput (r08 fault leg, identical config)."""
    prompts = _make_requests(n_requests, prompt_len, new_tokens, vocab,
                             seed=seed)
    tmpdir = tempfile.mkdtemp(prefix="serve_obs_")

    def run_once(tracer):
        eng = ServeEngine(model, variables, max_slots=slots,
                          prefill_len=prefill_len,
                          max_queue_depth=n_requests + 1,
                          tracer=tracer)
        eng.warmup()
        t0 = time.perf_counter()
        handles = [eng.submit(p, new_tokens) for p in prompts]
        eng.run(max_steps=200000)
        dt = time.perf_counter() - t0
        assert all(h.done for h in handles)
        assert sum(len(h.tokens) for h in handles) \
            == n_requests * new_tokens
        return n_requests * new_tokens / dt, eng

    off_tps, on_tps, ratios = [], [], []
    spans_total = records_total = 0
    eng_on = None
    try:
        for i in range(repeats):
            t_off, _ = run_once(None)
            with JsonlEventLog(os.path.join(tmpdir,
                                            f"trace_{i}.jsonl")) as log:
                tracer = RequestTracer(sink=log)
                t_on, eng_on = run_once(tracer)
            off_tps.append(t_off)
            on_tps.append(t_on)
            ratios.append(t_on / t_off)
            spans_total += tracer.spans_finished
            records_total += log.records_written
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    off_med, off_spread = median_spread(off_tps)
    on_med, _ = median_spread(on_tps)
    ratio_med, ratio_spread = median_spread(ratios)
    # The committed pre-obs baseline at this exact config, when present
    # (r08's fault-leg clean run: same requests x tokens x slots) —
    # resolved against the repo, not the caller's cwd.
    baseline = None
    r08 = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        "artifacts", "gpt_bench", "r08_serve_faults.json")
    try:
        with open(r08) as f:
            baseline = json.load(f)["results"]["faults"][
                "clean_tokens_per_s"]
    except Exception:  # noqa: BLE001 - artifact absent: ratio omitted
        pass
    ring_last = eng_on.telemetry.summary()
    return {
        "n_requests": n_requests,
        "new_tokens": new_tokens,
        "tokens_per_s_tracing_off": round(off_med, 1),
        "tokens_per_s_tracing_off_spread_pct": round(off_spread, 2),
        "tokens_per_s_tracing_on": round(on_med, 1),
        "tracing_on_over_off_x": round(ratio_med, 3),
        "tracing_on_over_off_per_pair": [round(r, 3) for r in ratios],
        "spread_pct": round(ratio_spread, 2),
        "baseline_r08_clean_tokens_per_s": baseline,
        "tracing_off_vs_r08_clean_x": (
            round(off_med / baseline, 3) if baseline else None),
        "trace_spans_finished_total": spans_total,
        "trace_records_written_total": records_total,
        "ring_ticks_recorded_last_repeat": ring_last["ticks"],
        "ring_tick_wall_p99_s_last_repeat": round(
            ring_last["tick_wall_p99_s"], 6),
        "engine_compile_counts_traced": eng_on.compile_counts(),
        "serve_metrics_snapshot": eng_on.metrics.snapshot(),
    }


def _write_trace_artifact(model, variables, prompts, new_tokens: int,
                          slots: int, prefill_len: int, path: str) -> int:
    """One fully traced closed-loop pass whose span log IS the bench
    artifact: every request's span, every engine step (the tracer's
    ``emit_ticks`` stream: the telemetry ring's own records, all of
    them, where the ring keeps the newest only), and the final metrics
    snapshot — a self-contained timeline (`docs/OPERATIONS.md`
    § Observability)."""
    with JsonlEventLog(path) as log:
        eng = ServeEngine(model, variables, max_slots=slots,
                          prefill_len=prefill_len,
                          max_queue_depth=len(prompts) + 1,
                          tracer=RequestTracer(sink=log, emit_ticks=True))
        eng.warmup()
        handles = [eng.submit(p, new_tokens) for p in prompts]
        eng.run(max_steps=200000)
        assert all(h.done for h in handles)
        log.write({"kind": "metrics",
                   "snapshot": eng.metrics.snapshot()})
        return log.records_written


def _maybe_write_trace(args, model, variables) -> None:
    """The shared ``--trace`` leg: ONE workload shape (2x concurrent
    closed-loop, the fault/obs-leg shape) regardless of which flag
    combination invoked the bench."""
    if not args.trace:
        return
    n = _write_trace_artifact(
        model, variables,
        _make_requests(2 * args.concurrent, args.prompt_len,
                       args.new_tokens, args.vocab),
        args.new_tokens, args.slots, args.prefill_len, args.trace)
    _log(f"trace artifact: {n} records -> {args.trace}")


def _log_obs_leg(obs: dict) -> None:
    vs_r08 = obs["tracing_off_vs_r08_clean_x"]
    _log(f"observability: {obs['tokens_per_s_tracing_off']} tok/s "
         f"tracing off -> {obs['tokens_per_s_tracing_on']} tok/s on "
         f"({obs['tracing_on_over_off_x']}x, pairs "
         f"{obs['tracing_on_over_off_per_pair']}); vs r08 clean "
         f"{f'{vs_r08}x' if vs_r08 is not None else 'n/a'}; "
         f"{obs['trace_spans_finished_total']} spans, "
         f"{obs['trace_records_written_total']} records")


def _poisson_load(model, variables, offered_rps: float, n_requests: int,
                  prompt_len: int, new_tokens: int, vocab: int,
                  slots: int, prefill_len: int, max_queue_depth: int,
                  seed: int):
    """Open-loop Poisson arrivals at ``offered_rps`` requests/s; the
    engine runs in real time, so TTFT includes genuine queue wait."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, n_requests))
    prompts = _make_requests(n_requests, prompt_len, new_tokens, vocab,
                             seed=seed + 1)
    # The Poisson prompts are random (nothing to share).
    eng = ServeEngine(model, variables, max_slots=slots,
                      prefill_len=prefill_len,
                      max_queue_depth=max_queue_depth)
    eng.warmup()
    rejected = 0
    i = 0
    t0 = time.perf_counter()
    while i < n_requests or eng.has_work:
        now = time.perf_counter() - t0
        while i < n_requests and arrivals[i] <= now:
            try:
                eng.submit(prompts[i], new_tokens,
                           sampling=SamplingParams())
            except QueueFull:
                rejected += 1
            i += 1
        if eng.has_work:
            eng.step()
        elif i < n_requests:
            time.sleep(min(arrivals[i] - now, 0.01))
    wall = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    return {
        "offered_rps": round(offered_rps, 3),
        "offered_tokens_per_s": round(offered_rps * new_tokens, 1),
        "tokens_per_s": round(snap["tokens_emitted"] / wall, 1),
        "ttft_p50_s": round(snap["ttft_p50_s"], 4)
        if snap["ttft_p50_s"] is not None else None,
        "ttft_p99_s": round(snap["ttft_p99_s"], 4)
        if snap["ttft_p99_s"] is not None else None,
        "mean_queue_depth": round(snap["mean_queue_depth"], 2),
        "mean_slot_occupancy": round(snap["mean_slot_occupancy"], 3),
        "requests_finished": snap["requests_finished"],
        "requests_rejected_queue_full": rejected,
    }


def _fleet_worker_config(args) -> dict:
    return dict(vocab=args.vocab, max_len=args.max_len,
                embed_dim=args.embed_dim, depth=args.depth,
                heads=args.heads, slots=args.slots,
                prefill_len=args.prefill_len,
                max_queue_depth=4 * args.slots, param_seed=0)


def _fleet_spawn(n: int, cfg: dict):
    import subprocess

    from pddl_tpu.serve.fleet import FleetRouter, ProcessReplica

    # Launch every worker first, then wait: the N warmup compiles run
    # concurrently instead of paying N serial engine builds.
    replicas = [ProcessReplica(i, {**cfg, "replica_id": i},
                               stderr=subprocess.DEVNULL, wait_ready=False)
                for i in range(n)]
    for r in replicas:
        r.wait_ready()
    return FleetRouter(replicas, affinity_block_size=8,
                       affinity_blocks=1, respawn=False)


def _fleet_wave(fleet, prompts, new_tokens: int, offered_rps: float,
                seed: int, kill_at_request: int = -1):
    """One open-loop Poisson wave through the fleet (real time, so TTFT
    includes genuine queue wait). ``kill_at_request >= 0`` SIGKILLs the
    busiest replica once that many requests have been submitted — the
    un-drainable mid-run death the failover leg measures."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / offered_rps, len(prompts)))
    handles, rejected, killed_id = [], 0, None
    # Hang protection: without a deadline the all_terminal field below
    # would be a tautology — the loop could only ever exit with every
    # handle done, and a regression stranding one request would spin
    # the bench forever instead of failing its assert.
    deadline = time.perf_counter() + max(
        120.0, float(arrivals[-1]) + 2.0 * len(prompts))
    t0 = time.perf_counter()
    i = 0
    while i < len(prompts) or any(not h.done for h in handles):
        if time.perf_counter() > deadline:
            break  # stranded request: report it, don't hang
        now = time.perf_counter() - t0
        while i < len(prompts) and arrivals[i] <= now:
            try:
                handles.append(fleet.submit(prompts[i], new_tokens))
            except Exception:  # noqa: BLE001 - QueueFull / NoHealthy
                rejected += 1
            i += 1
            if i == kill_at_request and killed_id is None:
                victim = max((s for s in fleet.replicas
                              if s.state.value == "up"),
                             key=lambda s: s.load)
                killed_id = victim.replica_id
                victim.driver.kill()
        if fleet.step() == 0:
            time.sleep(0.001)
    wall = time.perf_counter() - t0
    delivered = sum(len(h.tokens) for h in handles)
    ttfts = [h.ttft_s for h in handles if h.ttft_s is not None]
    return {
        "tokens_per_s": delivered / wall,
        "ttft_p50_s": float(np.percentile(ttfts, 50)) if ttfts else None,
        "ttft_p99_s": float(np.percentile(ttfts, 99)) if ttfts else None,
        "rejected": rejected,
        "all_terminal": all(h.done for h in handles),
        "finished": sum(h.state.value == "finished" for h in handles),
        "n_requests": len(handles),
        "killed_replica": killed_id,
        "handles": handles,
    }


def _fleet_leg(args, replica_counts, *, load_frac: float = 0.8,
               kill_counts=(2, 4)):
    """The r11 leg: aggregate tok/s + p99 TTFT at N replicas under
    Poisson load (clean), and the failover leg — one replica
    SIGKILL'd mid-run — at N in ``kill_counts``. Process replicas run
    genuinely in parallel, so the scaling curve is real concurrency,
    not slot arithmetic. Clean repeats reuse one fleet (spawn cost is
    startup, not serving); every killed repeat gets a fresh fleet and
    is PAIRED with a clean wave for the retained-throughput ratio.
    Token-exactness after migration is pinned against an in-process
    oracle engine built from the same param seed."""
    from pddl_tpu.serve.fleet.worker import build_engine

    cfg = _fleet_worker_config(args)
    # The committed r08 single-engine clean baseline at this config —
    # the acceptance comparison (N=4 must beat 2x this number).
    r08_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))),
        "artifacts", "gpt_bench", "r08_serve_faults.json")
    try:
        with open(r08_path) as f:
            baseline = json.load(f)["results"]["faults"][
                "clean_tokens_per_s"]
    except Exception:  # noqa: BLE001 - artifact absent: ratio omitted
        baseline = None
    cap_single = baseline or 1000.0
    oracle = build_engine(cfg)
    oracle_refs = {}

    def ref_for(prompt):
        key = tuple(prompt)
        if key not in oracle_refs:
            out = generate(oracle.model, {"params": oracle._params},
                           jnp.asarray(prompt, jnp.int32)[None],
                           args.new_tokens)
            oracle_refs[key] = np.asarray(out)[0, len(prompt):].tolist()
        return oracle_refs[key]

    scaling = []
    for n in replica_counts:
        offered = load_frac * n * cap_single / args.new_tokens
        n_requests = 48 * n  # long waves: the drain tail amortizes
        fleet = _fleet_spawn(n, cfg)
        try:
            tps_all, p99_all, p50_all = [], [], []
            last = None
            for rep in range(args.repeats):
                prompts = _make_requests(n_requests, args.prompt_len,
                                         args.new_tokens, args.vocab,
                                         seed=100 * n + rep)
                last = _fleet_wave(fleet, prompts, args.new_tokens,
                                   offered, seed=100 * n + rep)
                assert last["all_terminal"]
                tps_all.append(last["tokens_per_s"])
                p99_all.append(last["ttft_p99_s"])
                p50_all.append(last["ttft_p50_s"])
            tps_med, tps_spread = median_spread(tps_all)
            counts = fleet.compile_counts()
            snap = fleet.metrics.snapshot()
        finally:
            fleet.close()
        scaling.append({
            "replicas": n,
            "offered_fraction_of_nx_baseline": load_frac,
            "offered_tokens_per_s": round(offered * args.new_tokens, 1),
            "n_requests_per_wave": n_requests,
            "tokens_per_s": round(tps_med, 1),
            "tokens_per_s_spread_pct": round(tps_spread, 2),
            "tokens_per_s_per_repeat": [round(t, 1) for t in tps_all],
            "ttft_p50_s": round(median_spread(p50_all)[0], 4),
            "ttft_p99_s": round(median_spread(p99_all)[0], 4),
            "rejected_last_wave": last["rejected"],
            "vs_r08_clean_x": (round(tps_med / baseline, 3)
                               if baseline else None),
            "zero_recompiles_all_replicas": bool(counts) and all(
                v == 1 for v in counts.values()),
            "fleet_metrics": snap,
        })
        _log(f"fleet N={n}: {tps_med:,.0f} tok/s (spread "
             f"{tps_spread:.1f}%), p99 TTFT "
             f"{scaling[-1]['ttft_p99_s']}s, vs r08 "
             f"{scaling[-1]['vs_r08_clean_x']}x")

    killed = []
    for n in (k for k in kill_counts if k in replica_counts):
        offered = load_frac * n * cap_single / args.new_tokens
        n_requests = 48 * n
        ratios, clean_all, killed_all = [], [], []
        exact_all, migrated_total = True, 0
        for rep in range(args.repeats):
            prompts = _make_requests(n_requests, args.prompt_len,
                                     args.new_tokens, args.vocab,
                                     seed=500 * n + rep)
            fleet = _fleet_spawn(n, cfg)
            try:  # PAIRED: clean wave then killed wave, fresh fleets
                clean = _fleet_wave(fleet, prompts, args.new_tokens,
                                    offered, seed=500 * n + rep)
                # A stranded clean-wave request would deflate the clean
                # denominator and inflate the retained ratio meets_floor
                # is judged on — fail the pair loudly instead.
                assert clean["all_terminal"], \
                    "a clean-wave request never settled"
            finally:
                fleet.close()
            fleet = _fleet_spawn(n, cfg)
            try:
                kill = _fleet_wave(fleet, prompts, args.new_tokens,
                                   offered, seed=500 * n + rep,
                                   kill_at_request=n_requests // 2)
                assert kill["all_terminal"], "a request never settled"
                for h in kill["handles"]:
                    if h.state.value == "finished" \
                            and h.tokens != ref_for(h.request.prompt):
                        exact_all = False
                migrated_total += fleet.metrics.requests_migrated
                counts = fleet.compile_counts()
                surv_ok = bool(counts) and all(
                    v == 1 for v in counts.values())
            finally:
                fleet.close()
            clean_all.append(clean["tokens_per_s"])
            killed_all.append(kill["tokens_per_s"])
            ratios.append(kill["tokens_per_s"] / clean["tokens_per_s"])
        ratio_med, ratio_spread = median_spread(ratios)
        floor = 0.9 * (n - 1) / n
        killed.append({
            "replicas": n,
            "kill": "SIGKILL busiest replica at half the request "
                    "schedule (un-drainable: replay-mirror migration)",
            "clean_tokens_per_s": round(median_spread(clean_all)[0], 1),
            "killed_tokens_per_s": round(median_spread(killed_all)[0], 1),
            "throughput_retained_x": round(ratio_med, 3),
            "throughput_retained_per_pair": [round(r, 3) for r in ratios],
            "throughput_retained_spread_pct": round(ratio_spread, 2),
            "retained_floor_0p9_nm1_over_n": round(floor, 3),
            "meets_floor": ratio_med >= floor,
            "requests_migrated_total": migrated_total,
            "survivor_streams_token_exact": exact_all,
            "zero_recompiles_survivors_last_repeat": surv_ok,
        })
        _log(f"fleet kill N={n}: retained {ratio_med:.3f}x (floor "
             f"{floor:.3f}, pairs {killed[-1]['throughput_retained_per_pair']}), "
             f"migrated {migrated_total}, token-exact {exact_all}")
    return {
        "baseline_r08_clean_tokens_per_s": baseline,
        "scaling": scaling,
        "killed": killed,
    }


def _trace_schedule(n_requests: int, vocab: int, seed: int, *,
                    prompt_base: int = 16, prompt_cap: int = 60):
    """Trace-driven load: bursty MULTI-TURN sessions over shared system
    prompts with heavy-tail output lengths — the shape of real chat
    traffic, not Poisson. Sessions arrive in bursts (a long gap then a
    clump), each session keeps one of 4 system prompts as its prefix
    (prefix-cache + sticky-session territory), turns grow the
    conversation, and output lengths draw from a bounded Pareto (most
    replies short, a heavy tail of long ones). Priorities:
    ~35% interactive sessions (deadlined), ~15% batch, ~50%
    best_effort — the sheddable bulk a brownout should eat first.

    Returns (events, mean_new_tokens); event times are UNIT-paced —
    :func:`_scale_schedule` rescales them to an offered rate."""
    rng = np.random.default_rng(seed)
    sys_prompts = [rng.integers(0, vocab, size=prompt_base)
                   for _ in range(4)]
    events, t, s = [], 0.0, 0
    while len(events) < n_requests:
        s += 1
        # Bursty arrivals: occasional long inter-burst gaps, tight
        # spacing inside a burst (a burst clumps ~2 s of the average
        # rate into ~0.6 s — pronounced, but proportionate to a
        # 16-slot toy fleet rather than a thundering herd).
        t += float(rng.exponential(3.0) if rng.random() < 0.15
                   else rng.exponential(0.6))
        r = rng.random()
        pr = (Priority.INTERACTIVE if r < 0.35
              else Priority.BATCH if r < 0.50 else Priority.BEST_EFFORT)
        sysp = sys_prompts[int(rng.integers(0, len(sys_prompts)))]
        convo: list = []
        tt = t
        for _turn in range(int(rng.integers(1, 4))):
            convo = convo + rng.integers(
                0, vocab, size=int(rng.integers(6, 13))).tolist()
            prompt = np.concatenate(
                [sysp, np.asarray(convo)]).astype(np.int32)[:prompt_cap]
            new = int(min(4 + rng.pareto(1.3) * 4, 48))
            events.append(dict(
                t=tt, session=f"s{s}", prompt=prompt.tolist(),
                new_tokens=new, priority=pr,
                deadline_s=8.0 if pr is Priority.INTERACTIVE else None))
            tt += float(rng.exponential(0.8))  # think time between turns
    events = sorted(events, key=lambda e: e["t"])[:n_requests]
    mean_new = float(np.mean([e["new_tokens"] for e in events]))
    return events, mean_new


def _scale_schedule(events, offered_rps: float):
    """Rescale event times so the WHOLE trace offers ``offered_rps``
    requests/s on average (burst structure preserved)."""
    t0 = events[0]["t"]
    span = max(events[-1]["t"] - t0, 1e-9)
    scale = (len(events) / offered_rps) / span
    return [dict(e, t=(e["t"] - t0) * scale) for e in events]


def _slo_fleet(args, *, with_admission: bool, rates=None):
    import subprocess

    from pddl_tpu.serve.fleet import (
        AdmissionControl,
        FleetRouter,
        ProcessReplica,
    )

    # Real worker processes (the r11 deployment shape): each replica
    # self-drives its engine loop, so burst admissions on one replica
    # never stall another's decode cadence — the parallelism the SLO
    # numbers are about. SLO engine knobs ride the worker config:
    # per-step prefill bounded at two prompt widths (a burst admits
    # over a couple of steps, a prompt that dwarfs the budget — the
    # 32k case slicing exists for — time-slices against the tick) and
    # aging long enough that batch waits out a burst instead of
    # immediately contending with interactive.
    cfg = dict(vocab=args.vocab, max_len=args.max_len,
               embed_dim=args.embed_dim, depth=args.depth,
               heads=args.heads, slots=args.slots,
               prefill_len=args.prefill_len,
               max_queue_depth=2 * args.slots, param_seed=0,
               aging_s=3.0,
               prefill_slice_tokens=2 * args.prefill_len)
    replicas = [ProcessReplica(i, {**cfg, "replica_id": i},
                               stderr=subprocess.DEVNULL,
                               wait_ready=False)
                for i in range(args.slo_replicas)]
    for r in replicas:
        r.wait_ready()
    admission = None
    if with_admission:
        # Fast-acting ladder: the brownout must engage within a few
        # rejected submits (min_samples 4, no escalate hold) so early
        # overload sheds best_effort instead of class-blind QueueFulls.
        # Token buckets (the runbook's sizing rule): the NON-protected
        # classes alone must fit beside interactive inside capacity.
        admission = AdmissionControl(
            rates=rates, burst=6.0,
            detector_kw=dict(window_s=1.0, min_samples=4),
            brownout_kw=dict(high=0.2, low=0.05, escalate_hold_s=0.0,
                             recover_hold_s=0.5, output_cap=12))
    return FleetRouter(replicas, affinity_block_size=8,
                       affinity_blocks=2, respawn=False,
                       admission=admission)


def _slo_capacity(args) -> float:
    """Sustained fleet capacity (tokens/s): closed-loop mean-shape
    requests straight through the SLO fleet (no admission control, big
    queue pressure absorbed by retry-on-full)."""
    fleet = _slo_fleet(args, with_admission=False)
    try:
        events, _ = _trace_schedule(6 * args.slots * args.slo_replicas,
                                    args.vocab, seed=999)
        t0 = time.perf_counter()
        handles = []
        backlog = list(events)
        deadline = t0 + 300.0
        while backlog or fleet.has_work:
            while backlog:
                ev = backlog[0]
                try:
                    handles.append(fleet.submit(
                        ev["prompt"], ev["new_tokens"],
                        session=ev["session"]))
                    backlog.pop(0)
                except QueueFull:
                    break
            fleet.step()
            assert time.perf_counter() < deadline, "capacity leg hung"
        wall = time.perf_counter() - t0
        assert all(h.done for h in handles)
        return sum(len(h.tokens) for h in handles) / wall
    finally:
        fleet.close()


def _slo_wave(fleet, schedule, *, hang_s: float = 300.0):
    """One open-loop pass of the trace through the fleet, via the
    shared hint-honoring replay client (`serve/fleet/replay.py`): a
    rejected event re-enters at ``now + retry_after_s`` — the behavior
    a polite caller actually has — instead of being dropped (the r12
    harness's discipline, which understated brownout recovery);
    ``rejects`` counts only TERMINAL sheds, after the hint-driven
    retries ran out. Returns the handles (with their events), the
    per-class terminal sheds, and whether every request reached a
    terminal state before the hang deadline (a measurement, not a
    tautology — the loop CAN exit with stragglers and reports them)."""
    from pddl_tpu.serve.fleet import replay_trace

    rep = replay_trace(fleet, schedule, honor_hints=True,
                       max_attempts=4, hang_s=hang_s,
                       clock=time.perf_counter)
    return {"handles": rep.handles, "rejects": rep.rejects,
            "hinted_rejects": rep.hinted_rejects,
            "retried_after_hint": rep.retried_after_hint,
            "wall_s": rep.wall_s, "all_terminal": rep.all_terminal}


def _slo_leg(args, *, overload_x: float = 2.0,
             uncontended_x: float = 0.3):
    """The r12 leg: the bursty multi-turn trace at ``overload_x`` times
    measured fleet capacity, admission control + brownout armed,
    PAIRED per repeat with an uncontended wave for the interactive-p99
    ratio. Headlines: zero lost/hung requests, interactive p99 TTFT
    within 1.5x its uncontended value, best_effort absorbing the bulk
    of the shedding, zero recompiles."""
    cap_tps = _slo_capacity(args)
    _log(f"slo: measured fleet capacity {cap_tps:,.0f} tok/s "
         f"({args.slo_replicas} process replicas)")
    events, mean_new = _trace_schedule(args.slo_requests, args.vocab,
                                       seed=17)
    # Bucket sizing per the runbook: batch's bucket fits its own
    # offered rate (0.15 x 2x = 0.3x of capacity — batch should WAIT,
    # not shed), while best_effort (0.5 x 2x = 1.0x offered) is capped
    # well below that, so the front door sheds the sheddable class and
    # the brownout's output cap absorbs the rest of the overshoot.
    cap_rps = cap_tps / mean_new
    rates = {Priority.BATCH: 0.35 * cap_rps,
             Priority.BEST_EFFORT: 0.3 * cap_rps}
    ratios, be_fracs, over_tps, over_p99s, unc_p99s = [], [], [], [], []
    goodputs = []
    lost_total = rejects_total = 0
    max_rung = 0
    counts_ok = True
    fleet_metrics_last = None
    for rep in range(args.repeats):
        # Uncontended half of the pair: interactive's baseline p99.
        fleet = _slo_fleet(args, with_admission=True, rates=rates)
        try:
            unc = _slo_wave(fleet, _scale_schedule(
                events, uncontended_x * cap_tps / mean_new))
            assert unc["all_terminal"], "uncontended wave stranded work"
            unc_tt = [h.ttft_s for ev, h in unc["handles"]
                      if ev["priority"] is Priority.INTERACTIVE
                      and h.ttft_s is not None]
        finally:
            fleet.close()
        # The overload half: 2x sustained capacity, brownout armed.
        fleet = _slo_fleet(args, with_admission=True, rates=rates)
        try:
            over = _slo_wave(fleet, _scale_schedule(
                events, overload_x * cap_tps / mean_new))
            lost = sum(1 for _, h in over["handles"] if not h.done)
            lost_total += lost
            over_tt = [h.ttft_s for ev, h in over["handles"]
                       if ev["priority"] is Priority.INTERACTIVE
                       and h.ttft_s is not None]
            delivered = sum(len(h.tokens) for _, h in over["handles"])
            inter_deliv = sum(
                len(h.tokens) for ev, h in over["handles"]
                if ev["priority"] is Priority.INTERACTIVE)
            # Sheds by class: front-door/queue rejects plus requests
            # the engines deadline-shed or timed out (derived from the
            # fleet handles, so the accounting is driver-agnostic).
            sheds = dict(over["rejects"])
            for ev, h in over["handles"]:
                if h.state is RequestState.TIMED_OUT:
                    sheds[ev["priority"].value] += 1
            total_shed = sum(sheds.values())
            rejects_total += sum(over["rejects"].values())
            be_fracs.append(sheds["best_effort"] / total_shed
                            if total_shed else 1.0)
            over_tps.append(delivered / over["wall_s"])
            goodputs.append(inter_deliv / over["wall_s"])
            p99_unc = float(np.percentile(unc_tt, 99))
            p99_over = float(np.percentile(over_tt, 99))
            unc_p99s.append(p99_unc)
            over_p99s.append(p99_over)
            ratios.append(p99_over / p99_unc)
            max_rung = max(max_rung, int(fleet.admission.rung))
            counts = fleet.compile_counts()
            counts_ok = counts_ok and bool(counts) and all(
                v == 1 for v in counts.values())
            fleet_metrics_last = fleet.metrics.snapshot()
        finally:
            fleet.close()
        _log(f"slo pair {rep}: interactive p99 {p99_unc:.3f}s -> "
             f"{p99_over:.3f}s ({ratios[-1]:.2f}x), best_effort shed "
             f"frac {be_fracs[-1]:.2f}, lost {lost}")
    ratio_med, ratio_spread = median_spread(ratios)
    be_med, be_spread = median_spread(be_fracs)
    tps_med, tps_spread = median_spread(over_tps)
    return {
        "trace": "bursty multi-turn sessions, 4 shared system prompts, "
                 "bounded-Pareto output lengths, 35/15/50 "
                 "interactive/batch/best_effort",
        "process_replicas": args.slo_replicas,
        "n_requests_per_wave": args.slo_requests,
        "mean_new_tokens": round(mean_new, 2),
        "overload_x_capacity": overload_x,
        "capacity_tokens_per_s": round(cap_tps, 1),
        "overload_tokens_per_s": round(tps_med, 1),
        "overload_tokens_per_s_spread_pct": round(tps_spread, 2),
        "interactive_goodput_tokens_per_s": round(
            median_spread(goodputs)[0], 1),
        "uncontended_interactive_ttft_p99_s": round(
            median_spread(unc_p99s)[0], 4),
        "overload_interactive_ttft_p99_s": round(
            median_spread(over_p99s)[0], 4),
        "interactive_ttft_p99_overload_over_uncontended_x": round(
            ratio_med, 3),
        "interactive_ttft_ratio_per_pair": [round(r, 3) for r in ratios],
        "interactive_ttft_ratio_spread_pct": round(ratio_spread, 2),
        "interactive_ttft_ratio_bound": 1.5,
        "best_effort_shed_absorbed_frac": round(be_med, 3),
        "best_effort_shed_absorbed_per_repeat": [
            round(f, 3) for f in be_fracs],
        "best_effort_shed_absorbed_spread_pct": round(be_spread, 2),
        "best_effort_shed_absorbed_bound": 0.8,
        "requests_lost_or_hung_total": lost_total,
        "front_door_rejects_total": rejects_total,
        "brownout_rung_at_wave_end_max": max_rung,
        "zero_recompiles_all_replicas": counts_ok,
        "fleet_metrics_last_repeat": fleet_metrics_last,
    }


def _disagg_prefill_len(args) -> int:
    """Largest block-aligned prefill buffer that still fits beside
    the restore chunk in the KV budget (the engine's
    `prefill_len + prefix_chunk <= max_len` invariant)."""
    return (args.max_len - 2 * _DISAGG_CHUNK) // 8 * 8


# Restore-suffix chunk width: a handed-off chain covers every FULL
# block of the prompt, so the destination's prefill-from-cache only
# computes the partial tail block (+ the tokens decoded before the
# move) — a narrow chunk program keeps that from paying a
# quarter-buffer of padding per restore.
_DISAGG_CHUNK = 16


def _disagg_engine_factory(args, model, variables):
    """Hand-off-capable engine for BOTH fleet shapes — the only
    variable in a pair is the role assignment. Prefix cache ON (the
    chain to export) and host tier ON (the landing zone, r18 wire
    format). Admission is un-sliced: on this trace's 250+-token
    prompts the r12 slice budget would triple TTFT to buy jitter
    relief, and chunked prefill is the TRADEOFF disaggregation
    removes, not a free alternative — the r12 SLO leg keeps
    benchmarking the sliced operating point on its short-prompt
    trace."""
    def make():
        return ServeEngine(
            model, variables, max_slots=args.slots,
            prefill_len=_disagg_prefill_len(args),
            prefix_block_size=8,
            prefix_chunk=_DISAGG_CHUNK,
            host_tier=1 << 28, max_queue_depth=2 * args.slots)
    return make


class _TimedLocalReplica:
    """In-process replica that times its own engine ticks.

    The leg runs LOCAL replicas (like the r18 tier fleet leg), for
    two reasons that are one reason: the pddl_tpu target is a
    TPU-native fleet where a KV-block DMA costs microseconds against
    milliseconds of prefill compute, and a CPU worker pipe prices the
    same transfer at base64+JSON rates — compute parity, a transport
    artifact the paper's fabric does not have. In-process transfer
    (`export_prefix_chain` buffers straight into the peer's host
    tier) models the DMA side of that ratio, and per-tick timing
    gives an arrival-clock-free read of decode cadence: every token
    is charged the duration of the engine step that produced it, so
    a prefill admission (or a restore) sharing the tick is charged to
    its co-residents' tokens — interference measured where it
    happens, not through the router's harvest loop."""

    def __init__(self, replica_id, engine_factory, *, role="unified"):
        from pddl_tpu.serve.fleet import LocalReplica

        self._inner = LocalReplica(replica_id, engine_factory,
                                   role=role)
        self.last_step_s = 0.0

    def step(self):
        t0 = time.perf_counter()
        try:
            return self._inner.step()
        finally:
            self.last_step_s = time.perf_counter() - t0

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _disagg_fleet(args, model, variables, roles, *, tracer=None):
    from pddl_tpu.serve.fleet import FleetRouter

    make = _disagg_engine_factory(args, model, variables)
    replicas = [_TimedLocalReplica(i, make, role=role)
                for i, role in enumerate(roles)]
    return FleetRouter(replicas, affinity_block_size=8,
                       affinity_blocks=2, respawn=False, tracer=tracer)


def _disagg_warm(fleet, args, *, seed: int = 4242):
    """Compile every program the wave will run — prefill, decode,
    and (on decode replicas) the promote + restore-chunk path each
    hand-off exercises — so the measured ticks are steady-state and
    the zero-recompile pin holds over the wave itself."""
    rng = np.random.default_rng(seed)
    n = 2 * sum(1 for s in fleet.replicas
                if getattr(s.driver, "role", "unified") != "prefill")
    handles = [fleet.submit(
        rng.integers(0, args.vocab,
                     size=_disagg_prefill_len(args) - 8 * k)
        .astype(np.int32).tolist(), 4) for k in range(1, n + 1)]
    fleet.run(max_steps=4000)
    assert all(h.done for h in handles), "disagg warmup stranded work"


def _disagg_wave(fleet, schedule, *, hang_s: float = 600.0):
    """One open-loop pass of the long-prompt trace. Decode-side
    per-token latency pool: each harvested token is charged the wall
    duration of the replica tick that produced it (first tokens — the
    TTFT side, where prefill and the hand-off itself live — are
    excluded; everything after, including the restored stream's first
    post-move tick with its promote charge, is decode cadence)."""
    t0 = time.perf_counter()
    backlog = sorted(schedule, key=lambda e: e["t"])
    by_id = {s.replica_id: s.driver for s in fleet.replicas}
    handles, lats, seen = [], [], {}
    while backlog or fleet.has_work or any(
            not h.done for _, h in handles):
        now = time.perf_counter() - t0
        while backlog and backlog[0]["t"] <= now:
            ev = backlog[0]
            try:
                handles.append((ev, fleet.submit(
                    ev["prompt"], ev["new_tokens"])))
                backlog.pop(0)
            except QueueFull:
                break  # re-offer on the next pump
        fleet.step()
        for i, (_ev, h) in enumerate(handles):
            n = len(h.tokens)
            prev_n = seen.get(i, 0)
            if n > prev_n:
                if prev_n > 0:
                    lats.extend(
                        [by_id[h.replica_id].last_step_s]
                        * (n - prev_n))
                seen[i] = n
        assert time.perf_counter() - t0 < hang_s, "disagg wave hung"
    wall = time.perf_counter() - t0
    assert all(h.done for _, h in handles), "a stream never settled"
    return {
        "handles": handles,
        "tokens_per_s": sum(len(h.tokens) for _, h in handles) / wall,
        "decode_lat_p50_s": float(np.percentile(lats, 50)),
        "decode_lat_p99_s": float(np.percentile(lats, 99)),
        "wall_s": wall,
    }


def _disagg_capacity(args, model, variables) -> float:
    """Sustained unified-fleet capacity on the LONG-PROMPT trace
    (tokens/s, closed loop) — the offered-rate yardstick both halves
    of every pair share."""
    fleet = _disagg_fleet(args, model, variables,
                          ["unified"] * args.disagg_replicas)
    try:
        _disagg_warm(fleet, args)
        events, _ = _disagg_trace(args, seed=999)
        t0 = time.perf_counter()
        handles, backlog = [], list(events)
        while backlog or fleet.has_work:
            while backlog:
                ev = backlog[0]
                try:
                    handles.append(fleet.submit(ev["prompt"],
                                                ev["new_tokens"]))
                    backlog.pop(0)
                except QueueFull:
                    break
            fleet.step()
            assert time.perf_counter() - t0 < 600.0, \
                "disagg capacity leg hung"
        wall = time.perf_counter() - t0
        assert all(h.done for h in handles)
        return sum(len(h.tokens) for h in handles) / wall
    finally:
        fleet.close()


def _disagg_trace(args, *, seed: int):
    """The r12 bursty multi-turn trace with the prompt knobs turned
    to LONG: system prompts of ``--disagg-prompt-base`` tokens,
    capped at the prefill buffer — prompts an order of magnitude past
    the per-turn decode budget, so an admission genuinely contends
    with decode on a unified replica. Two edits over the r12 shape:

    - Per-SESSION system prompts (the r12 trace shares 4 across the
      fleet, which the prefix cache absorbs into a handful of cold
      prefills — with the cache necessarily ON for the hand-off
      chain, a shared-prefix trace measures cache hits, not prefill
      interference). Each session's FIRST turn is cache-cold — the
      long cold prompt disaggregation exists for — while later turns
      still exercise the affinity + prefix-cache path.
    - Outputs stretched 4x (still Pareto-shaped, capped at the KV
      budget): decode cadence is the measured quantity, so each
      stream must live long enough that its p99 reflects
      steady-state ticks."""
    events, _ = _trace_schedule(
        args.disagg_requests, args.vocab, seed,
        prompt_base=args.disagg_prompt_base,
        prompt_cap=_disagg_prefill_len(args) - 8)
    rng = np.random.default_rng(seed + 1)
    bases: dict = {}
    out = []
    for e in events:
        base = bases.setdefault(e["session"], rng.integers(
            0, args.vocab, size=args.disagg_prompt_base)
            .astype(np.int32).tolist())
        prompt = (base + e["prompt"][args.disagg_prompt_base:])[
            :_disagg_prefill_len(args) - 8]
        out.append(dict(e, prompt=prompt, new_tokens=int(min(
            4 * e["new_tokens"], args.max_len - len(prompt) - 8))))
    mean_new = float(np.mean([e["new_tokens"] for e in out]))
    return out, mean_new


def _disagg_leg(args):
    """The ISSUE 17 leg: same-N unified vs role-split fleets, PAIRED
    per repeat on the identical long-prompt schedule. The split
    fleet must hold decode-side p99 token latency <= 0.8x unified
    AND aggregate tok/s >= 0.95x, every pair directional, with every
    stream token-exact across the two fleet shapes and zero
    recompiles on the decode replicas."""
    from pddl_tpu.obs import RequestTracer

    n = args.disagg_replicas
    n_prefill = args.disagg_prefill_replicas or max(1, n // 2)
    assert 1 <= n_prefill < n, "need at least one replica per role"
    model = GPT(vocab_size=args.vocab, max_len=args.max_len,
                embed_dim=args.embed_dim, depth=args.depth,
                num_heads=args.heads, attention="reference")
    params = model.init(jax.random.key(0), jnp.ones((1, 8), jnp.int32),
                        train=False)["params"]
    variables = {"params": params}
    cap_tps = _disagg_capacity(args, model, variables)
    events, mean_new = _disagg_trace(args, seed=23)
    offered_rps = args.disagg_load * cap_tps / mean_new
    schedule = _scale_schedule(events, offered_rps)
    split_roles = ["prefill"] * n_prefill + ["decode"] * (n - n_prefill)
    decode_ids = set(range(n_prefill, n))
    _log(f"disagg: unified capacity {cap_tps:,.0f} tok/s (N={n}), "
         f"offering {offered_rps:.2f} req/s "
         f"({args.disagg_load:.0%} load, mean_new {mean_new:.1f}); "
         f"split {n_prefill} prefill + {n - n_prefill} decode")
    uni_p99s, split_p99s, p99_ratios, tps_ratios = [], [], [], []
    uni_tps_all, split_tps_all, handoff_ms_all = [], [], []
    exact_all = True
    handoffs_total = handoff_failures_total = 0
    decode_counts_ok = True
    split_metrics_last = None
    for rep in range(args.repeats):
        fleet = _disagg_fleet(args, model, variables, ["unified"] * n)
        try:
            _disagg_warm(fleet, args)
            uni = _disagg_wave(fleet, schedule)
        finally:
            fleet.close()
        oracle = {tuple(ev["prompt"]): list(h.tokens)
                  for ev, h in uni["handles"]}
        tracer = RequestTracer()
        fleet = _disagg_fleet(args, model, variables, split_roles,
                              tracer=tracer)
        try:
            _disagg_warm(fleet, args)
            split = _disagg_wave(fleet, schedule)
            for ev, h in split["handles"]:
                if list(h.tokens) != oracle[tuple(ev["prompt"])]:
                    exact_all = False
            m = fleet.metrics
            handoffs_total += m.handoffs_completed
            handoff_failures_total += m.handoffs_failed
            hand_ms = [e["ms"] for e in tracer.events_named("handoff")]
            if hand_ms:
                handoff_ms_all.append(float(np.median(hand_ms)))
            counts = {k: v for k, v in fleet.compile_counts().items()
                      if int(k.split("/")[0][1:]) in decode_ids}
            decode_counts_ok = decode_counts_ok and bool(counts) \
                and all(v == 1 for v in counts.values())
            split_metrics_last = m.snapshot()
        finally:
            fleet.close()
        uni_p99s.append(uni["decode_lat_p99_s"])
        split_p99s.append(split["decode_lat_p99_s"])
        p99_ratios.append(split["decode_lat_p99_s"]
                          / uni["decode_lat_p99_s"])
        tps_ratios.append(split["tokens_per_s"] / uni["tokens_per_s"])
        uni_tps_all.append(uni["tokens_per_s"])
        split_tps_all.append(split["tokens_per_s"])
        _log(f"disagg pair {rep}: decode p99 "
             f"{uni['decode_lat_p99_s'] * 1e3:.1f}ms -> "
             f"{split['decode_lat_p99_s'] * 1e3:.1f}ms "
             f"({p99_ratios[-1]:.3f}x), tok/s retained "
             f"{tps_ratios[-1]:.3f}x, handoffs "
             f"{m.handoffs_completed}, token-exact {exact_all}")
    p99_med, p99_spread = median_spread(p99_ratios)
    tps_med, tps_spread = median_spread(tps_ratios)
    return {
        "trace": "bursty multi-turn long-prompt sessions "
                 f"(system prompts {args.disagg_prompt_base} tokens, "
                 "bounded-Pareto output lengths stretched 4x)",
        "replicas": n,
        "split_shape": f"{n_prefill} prefill + {n - n_prefill} "
                       "decode, block-granular KV hand-off",
        "n_requests_per_wave": args.disagg_requests,
        "mean_new_tokens": round(mean_new, 2),
        "offered_load_x_capacity": args.disagg_load,
        "unified_capacity_tokens_per_s": round(cap_tps, 1),
        "unified_tokens_per_s": round(median_spread(uni_tps_all)[0], 1),
        "split_tokens_per_s": round(median_spread(split_tps_all)[0], 1),
        "tokens_per_s_retained_x": round(tps_med, 3),
        "tokens_per_s_retained_per_pair": [round(r, 3)
                                           for r in tps_ratios],
        "tokens_per_s_retained_spread_pct": round(tps_spread, 2),
        "tokens_per_s_retained_floor": 0.95,
        "unified_decode_lat_p99_ms": round(
            median_spread(uni_p99s)[0] * 1e3, 2),
        "split_decode_lat_p99_ms": round(
            median_spread(split_p99s)[0] * 1e3, 2),
        "decode_p99_interference": round(p99_med, 3),
        "decode_p99_interference_per_pair": [round(r, 3)
                                             for r in p99_ratios],
        "decode_p99_interference_spread_pct": round(p99_spread, 2),
        "decode_p99_interference_bound": 0.8,
        "all_pairs_directional": all(r < 1.0 for r in p99_ratios),
        "handoff_ms": round(float(np.median(handoff_ms_all)), 3),
        "handoffs_completed_total": int(handoffs_total),
        "handoffs_failed_total": int(handoff_failures_total),
        "streams_token_exact_split_vs_unified": exact_all,
        "zero_recompiles_decode_replicas": decode_counts_ok,
        "split_fleet_metrics_last_repeat": split_metrics_last,
    }


def _autoscale_cfg(args) -> dict:
    """Worker config for the autoscale leg. Two deliberate choices:
    small enough that a scale-up's spawn+warmup completes in seconds
    (the leg measures the CONTROL LOOP against a diurnal day
    compressed to ~minutes, and a spawn costing a whole period would
    measure jax import time instead), yet slow enough per replica
    (~1.1k tok/s: depth 6, 4 slots) that genuine overload is
    expressible at request rates the single-threaded router loop
    sustains — a faster engine turns the open-loop replay into a
    de-facto closed loop and no static baseline can ever saturate."""
    del args
    return dict(vocab=64, max_len=128, embed_dim=192, depth=6, heads=4,
                slots=4, prefill_len=64,
                max_queue_depth=8, param_seed=0,
                aging_s=3.0)


def _autoscale_admission():
    from pddl_tpu.serve.fleet import AdmissionControl

    # The r12 fast-acting ladder: the brownout must engage within a few
    # rejected submits — it is the LOSING condition the autoscaler is
    # supposed to pre-empt, so it has to be armed and quick.
    return AdmissionControl(
        detector_kw=dict(window_s=1.0, min_samples=4),
        brownout_kw=dict(high=0.2, low=0.05, escalate_hold_s=0.0,
                         recover_hold_s=0.5, output_cap=12))


def _autoscale_fleet(args, cfg, *, replicas: int, autoscale: bool):
    import subprocess

    from pddl_tpu.serve.fleet import (
        FleetAutoscaler,
        FleetRouter,
        ProcessReplica,
    )

    def spawn(rid, wait_ready):
        return ProcessReplica(rid, {**cfg, "replica_id": rid},
                              stderr=subprocess.DEVNULL,
                              wait_ready=wait_ready,
                              ready_timeout_s=120.0)

    reps = [spawn(i, False) for i in range(replicas)]
    for r in reps:
        r.wait_ready()
    fleet = FleetRouter(reps, affinity_block_size=8, affinity_blocks=2,
                        respawn=False, admission=_autoscale_admission())
    if autoscale:
        # Target-utilization scaling: grow at ~60% of a slot pool's
        # assigned load per replica (the diurnal ramp is gradual, so an
        # early trigger buys the ~5 s spawn its head start), shrink at
        # ~30% with calm pressure held 2 s so the sinusoid's shoulders
        # do not flap the fleet. up_pressure 0.08 sits well below the
        # ladder's high mark (0.2): pressure is the backstop that
        # engages capacity ahead of brownout when load alone lags.
        # Grow on genuine saturation, not comfort: PRESSURE (0.08,
        # well under the ladder's 0.2 high mark) is the early trigger —
        # ramp sheds feed the detector within a window — and the load
        # trigger only fires at a full slot-pool of assigned backlog
        # per replica. Shrink at ~50% utilization held 2 s. The gap
        # between the two is what keeps mean fleet size tracking the
        # demand curve instead of hugging max_replicas; it also keeps
        # the projection guard (veto at up_load) off the knife edge.
        slots = cfg["slots"]
        FleetAutoscaler(
            fleet, lambda rid: spawn(rid, False),
            min_replicas=replicas, max_replicas=args.autoscale_max,
            up_pressure=0.08, down_pressure=0.02,
            up_load=1.0 * slots, down_load=0.5 * slots,
            up_hold_s=0.1, down_hold_s=2.0, cooldown_s=0.25,
            spawn_backoff_base_s=0.5, spawn_backoff_max_s=10.0)
    return fleet


def _autoscale_capacity(args, cfg) -> float:
    """Single-replica sustained capacity (tokens/s) on the trace's
    request shape, closed-loop — the unit the diurnal offered load is
    expressed in."""
    from pddl_tpu.serve.fleet import diurnal_trace

    fleet = _autoscale_fleet(args, cfg, replicas=1, autoscale=False)
    try:
        events, _ = diurnal_trace(6 * cfg["slots"], cfg["vocab"],
                                  seed=999,
                                  duration_s=1.0, prompt_cap=30,
                                  new_tokens_base=16,
                                  new_tokens_scale=12.0,
                                  new_tokens_cap=80)
        t0 = time.perf_counter()
        handles = []
        backlog = list(events)
        deadline = t0 + 300.0
        while backlog or fleet.has_work:
            while backlog:
                ev = backlog[0]
                try:
                    handles.append(fleet.submit(
                        ev["prompt"], ev["new_tokens"],
                        session=ev["session"]))
                    backlog.pop(0)
                except QueueFull:
                    break
            fleet.step()
            assert time.perf_counter() < deadline, "capacity leg hung"
        wall = time.perf_counter() - t0
        assert all(h.done for h in handles)
        return sum(len(h.tokens) for h in handles) / wall
    finally:
        fleet.close()


def _autoscale_wave(args, cfg, schedule, *, static_n=None,
                    autoscale=False, hang_s=420.0):
    """One diurnal replay: a static-N fleet, or an autoscaled fleet
    starting at ``autoscale_min``. Returns the report plus the fleet's
    scale/migration counters and the zero-recompile verdict."""
    from pddl_tpu.serve.fleet import replay_trace
    from pddl_tpu.serve.request import RequestState

    n0 = args.autoscale_min if autoscale else static_n
    fleet = _autoscale_fleet(args, cfg, replicas=n0, autoscale=autoscale)
    try:
        # max_attempts 8: a polite client keeps honoring hints while
        # the diurnal ramp (or a scale-up in flight) catches up —
        # terminal sheds then measure genuinely unservable demand, not
        # client impatience.
        rep = replay_trace(fleet, schedule, honor_hints=True,
                           max_attempts=8, hang_s=hang_s,
                           clock=time.perf_counter)
        lost = rep.stragglers + sum(
            1 for _, h in rep.handles
            if h.state is RequestState.FAILED)
        finished = sum(1 for _, h in rep.handles
                       if h.state is RequestState.FINISHED)
        counts = fleet.compile_counts()
        snap = fleet.metrics.snapshot()
        scaler = fleet.autoscaler
        return {
            "report": rep,
            "lost": lost,
            "attainment": finished / max(len(schedule), 1),
            "rejected": sum(rep.rejects.values()),
            "scale_up_events": snap["scale_up_events"],
            "scale_down_events": snap["scale_down_events"],
            "scale_down_migrated": snap["scale_down_migrated"],
            "zero_recompiles": bool(counts) and all(
                v == 1 for v in counts.values()),
            "fleet_metrics": snap,
            "autoscale_metrics": (scaler.metrics.snapshot()
                                  if scaler is not None else None),
        }
    finally:
        fleet.close()


def _autoscale_leg(args):
    """The r16 leg: the same seeded diurnal trace (1 period,
    peak:trough ``--autoscale-peak-trough``) through (a) static fleets
    at each N in ``--autoscale-static`` and (b) the autoscaled fleet
    (min..max replicas), admission armed everywhere. The headline is
    AlpaServe's framing made concrete: goodput per replica-hour —
    finished tokens per hour of replica (spawning included) the fleet
    burned — autoscaled over the BEST static, PAIRED per repeat.
    Secondary pins: brownout rung time strictly below the
    under-provisioned static, zero lost requests anywhere, every
    scale-down migration zero-loss, zero recompiles."""
    from pddl_tpu.serve.fleet import diurnal_trace

    cfg = _autoscale_cfg(args)
    cap1 = _autoscale_capacity(args, cfg)
    _log(f"autoscale: single-replica capacity {cap1:,.0f} tok/s")
    # Offered MEAN load in capacity units; the sinusoid swings
    # peak:trough around it (peak = mean * 2r/(r+1)).
    duration = args.autoscale_duration
    ratio = args.autoscale_peak_trough
    # Fat decodes (mean ~30 new tokens, prompts capped at 30): the
    # offered TOKEN load reaches the target at a request rate the
    # single-threaded router's synchronous submit path sustains.
    shape = dict(prompt_cap=30, new_tokens_base=16,
                 new_tokens_scale=12.0, new_tokens_cap=80)
    events, mean_new = diurnal_trace(
        max(int(args.autoscale_offered * cap1 / 30.0 * duration), 64),
        cfg["vocab"], seed=29, duration_s=duration, periods=1.0,
        peak_to_trough=ratio, **shape)
    # The generator's mean_new is a draw, not a constant — rescale the
    # request count so offered TOKENS hit the target, then regenerate.
    n_requests = max(int(args.autoscale_offered * cap1 / mean_new
                         * duration), 64)
    events, mean_new = diurnal_trace(
        n_requests, cfg["vocab"], seed=29, duration_s=duration,
        periods=1.0, peak_to_trough=ratio, **shape)
    _log(f"autoscale: {n_requests} requests over {duration}s, mean_new "
         f"{mean_new:.1f}, offered mean "
         f"{args.autoscale_offered:.2f}x capacity, peak:trough {ratio}")

    # Static sweep, ATTAINMENT-QUALIFIED (AlpaServe's framing: SLO
    # attainment per resource-hour, not raw density): a static fleet
    # only counts as a baseline when it actually SERVED the demand —
    # >= `floor` of offered requests finished, hint-honoring retries
    # allowed. Without the floor, raw goodput-per-replica-hour crowns
    # the saturated under-provisioned fleet that shed a fifth of its
    # callers (the smoke run's static-1), which is not a capacity
    # planning anyone ships.
    floor = args.autoscale_attainment_floor
    static_ns = [int(n) for n in args.autoscale_static.split(",") if n]
    statics = []
    for n in static_ns:
        w = _autoscale_wave(args, cfg, events, static_n=n)
        r = w["report"]
        statics.append({
            "replicas": n,
            "goodput_tokens": r.goodput_tokens,
            "goodput_per_replica_hour": round(
                r.goodput_per_replica_hour, 1),
            "replica_hours": round(r.replica_hours, 6),
            "attainment": round(w["attainment"], 4),
            "qualified": w["attainment"] >= floor,
            "brownout_rung_time_s": round(r.rung_seconds, 3),
            "rejected_terminal": w["rejected"],
            "retried_after_hint": r.retried_after_hint,
            "lost": w["lost"],
            "zero_recompiles": w["zero_recompiles"],
        })
        _log(f"autoscale static N={n}: gphr "
             f"{statics[-1]['goodput_per_replica_hour']:,.0f}, "
             f"attainment {w['attainment']:.3f} "
             f"({'ok' if statics[-1]['qualified'] else 'FAILS floor'}), "
             f"rung {statics[-1]['brownout_rung_time_s']}s, shed "
             f"{w['rejected']}, lost {w['lost']}")
    qualified = [s for s in statics if s["qualified"]]
    best = max(qualified or statics,
               key=lambda s: s["goodput_per_replica_hour"])
    under = min(statics, key=lambda s: s["replicas"])

    repeats = max(args.repeats, 5)
    auto_gphr, ratios, rungs, attains = [], [], [], []
    scale_ups, scale_downs, migrated_total = [], [], 0
    lost_total = 0
    counts_ok = True
    last = None
    for rep_i in range(repeats):
        # PAIRED: autoscaled and best-static back to back, ratio per
        # pair — host drift cancels in the quotient.
        wa = _autoscale_wave(args, cfg, events, autoscale=True)
        wb = _autoscale_wave(args, cfg, events,
                             static_n=best["replicas"])
        ra, rb = wa["report"], wb["report"]
        auto_gphr.append(ra.goodput_per_replica_hour)
        ratios.append(ra.goodput_per_replica_hour
                      / max(rb.goodput_per_replica_hour, 1e-9))
        rungs.append(ra.rung_seconds)
        attains.append(wa["attainment"])
        scale_ups.append(wa["scale_up_events"])
        scale_downs.append(wa["scale_down_events"])
        migrated_total += wa["scale_down_migrated"]
        lost_total += wa["lost"] + wb["lost"]
        counts_ok = counts_ok and wa["zero_recompiles"] \
            and wb["zero_recompiles"]
        last = wa
        _log(f"autoscale pair {rep_i}: gphr {ra.goodput_per_replica_hour:,.0f}"
             f" vs static-{best['replicas']} "
             f"{rb.goodput_per_replica_hour:,.0f} "
             f"({ratios[-1]:.3f}x), attainment {wa['attainment']:.3f}, "
             f"scale {wa['scale_up_events']}up/"
             f"{wa['scale_down_events']}down, migrated "
             f"{wa['scale_down_migrated']}, rung {ra.rung_seconds:.2f}s")
    gphr_med, gphr_spread = median_spread(auto_gphr)
    ratio_med, ratio_spread = median_spread(ratios)
    # Plain median: a spread is undefined at a zero median, and an
    # all-zero rung series (the autoscaler fully pre-empting brownout)
    # is the GOOD case, not an error.
    rung_med = float(np.median(rungs))
    return {
        "trace": (f"seeded diurnal (1 period over {duration}s, "
                  f"peak:trough {ratio}), heavy-tail multi-turn "
                  "sessions (r12 mix), 35/15/50 "
                  "interactive/batch/best_effort"),
        "n_requests": n_requests,
        "duration_s": duration,
        "peak_to_trough": ratio,
        "mean_new_tokens": round(mean_new, 2),
        "capacity_single_replica_tokens_per_s": round(cap1, 1),
        "offered_mean_x_capacity": args.autoscale_offered,
        "autoscale_min_replicas": args.autoscale_min,
        "autoscale_max_replicas": args.autoscale_max,
        "attainment_qualification": (
            f"a static baseline must FINISH >= {floor:.0%} of offered "
            "requests (hint-honoring retries allowed) to count as "
            "best-static; density bought by shedding callers is not a "
            "baseline (AlpaServe: SLO attainment per resource-hour)"),
        "attainment_floor": floor,
        "static_sweep": statics,
        "best_static_replicas": best["replicas"],
        "best_static_qualified": bool(qualified),
        "attainment_autoscaled": round(min(attains), 4),
        "goodput_per_replica_hour": round(gphr_med, 1),
        "goodput_per_replica_hour_spread_pct": round(gphr_spread, 2),
        "goodput_per_replica_hour_vs_best_static_x": round(ratio_med, 3),
        "goodput_vs_best_static_per_pair": [round(r, 3) for r in ratios],
        "goodput_vs_best_static_spread_pct": round(ratio_spread, 2),
        # min(ups) + min(downs), NOT min(u+d): the headline must pin
        # BOTH directions — a fleet that only ever grows (scale-down
        # broken, e.g. the projection guard vetoing every shrink) must
        # drop this number loudly even if its up-count compensates.
        "scale_events": int(min(scale_ups) + min(scale_downs)),
        "scale_up_events_per_wave": scale_ups,
        "scale_down_events_per_wave": scale_downs,
        "migrated_zero_lost": migrated_total if lost_total == 0 else 0,
        "requests_lost_total": lost_total,
        "brownout_rung_time_autoscaled_s": round(rung_med, 3),
        "brownout_rung_time_static_under_s":
            under["brownout_rung_time_s"],
        "rung_time_below_static_under": bool(
            rung_med < under["brownout_rung_time_s"]),
        "zero_recompiles_all_replicas": counts_ok,
        "fleet_metrics_last_repeat": last["fleet_metrics"],
        "autoscale_metrics_last_repeat": last["autoscale_metrics"],
    }


def _ctrlplane_cfg() -> dict:
    """The leg's sized worker config (the r16/r17 small-model
    discipline: control-plane costs are host-side, a big model only
    slows the referee)."""
    return dict(vocab=64, max_len=128, embed_dim=64, depth=2, heads=2,
                slots=4, prefill_len=32, max_queue_depth=96,
                param_seed=0)


def _ctrl_wave(fleet, prompts, new_tokens: int, *, hang_s: float = 300.0,
               priority=None):
    """Closed-loop wave: submit everything, pump to terminal. Returns
    (handles, tokens_per_s, wall_s)."""
    t0 = time.perf_counter()
    handles = []
    for p in prompts:
        kw = {} if priority is None else {"priority": priority}
        handles.append(fleet.submit(list(p), new_tokens, **kw))
    deadline = time.perf_counter() + hang_s
    while any(not h.done for h in handles) \
            and time.perf_counter() < deadline:
        fleet.step()
    wall = time.perf_counter() - t0
    assert all(h.done for h in handles), "a wave request never settled"
    return handles, sum(len(h.tokens) for h in handles) / wall, wall


def _ctrlplane_wire_leg(args, repeats: int) -> dict:
    """Paired clean vs wire-fault-storm waves through process
    replicas: the framed transport must hold throughput and
    token-exactness at a 1% injected frame-fault rate."""
    import subprocess

    from pddl_tpu.serve.fleet import (
        FleetRouter,
        ProcessReplica,
        WireFaultPlan,
    )
    from pddl_tpu.serve.fleet.worker import build_engine

    cfg = _ctrlplane_cfg()
    new_tokens = 96
    n_requests = 64
    oracle = build_engine(cfg)
    refs = {}

    def ref_for(prompt):
        key = tuple(prompt)
        if key not in refs:
            out = generate(oracle.model, {"params": oracle._params},
                           jnp.asarray(prompt, jnp.int32)[None],
                           new_tokens)
            refs[key] = np.asarray(out)[0, len(prompt):].tolist()
        return refs[key]

    def spawn(plan_seed=None):
        reps = []
        for i in range(2):
            plan = (None if plan_seed is None else WireFaultPlan(
                plan_seed + i, corrupt_rate=0.004, duplicate_rate=0.002,
                reorder_rate=0.002, drop_rate=0.002))
            # Tight ping/resend cadences: gap-detection latency is the
            # storm's whole cost, and the clean fleet runs the same
            # cadence so the pair stays fair.
            reps.append(ProcessReplica(
                i, {**cfg, "replica_id": i}, stderr=subprocess.DEVNULL,
                wire_fault_plan=plan, ping_interval_s=0.01,
                resend_timeout_s=0.01, wait_ready=False))
        for r in reps:
            r.wait_ready()
        return FleetRouter(reps, affinity_block_size=8,
                           affinity_blocks=1, respawn=False)

    ratios, clean_all, storm_all = [], [], []
    rejects = retries = injected = 0
    exact = True
    # BOTH fleets are long-lived and warmed with an untimed wave, so
    # every pair compares equally-warm processes — a fresh-spawned
    # storm fleet against a wave-warmed clean one would measure
    # process warmth, not the transport.
    clean_fleet = spawn(None)
    storm = spawn(1000)
    try:
        warm_rng = np.random.default_rng(899)
        warm = [warm_rng.integers(0, cfg["vocab"], size=12).tolist()
                for _ in range(n_requests)]
        _ctrl_wave(clean_fleet, warm, new_tokens)
        _ctrl_wave(storm, warm, new_tokens)
        for rep in range(repeats):
            rng = np.random.default_rng(900 + rep)
            prompts = [rng.integers(0, cfg["vocab"], size=12).tolist()
                       for _ in range(n_requests)]
            _, tps_clean, _ = _ctrl_wave(clean_fleet, prompts,
                                         new_tokens)
            handles, tps_storm, _ = _ctrl_wave(storm, prompts,
                                               new_tokens)
            for p, h in zip(prompts, handles):
                if h.state.value != "finished" \
                        or h.tokens != ref_for(p):
                    exact = False
            clean_all.append(tps_clean)
            storm_all.append(tps_storm)
            ratios.append(tps_storm / tps_clean)
            _log(f"ctrlplane wire pair {rep}: {tps_clean:,.0f} -> "
                 f"{tps_storm:,.0f} tok/s ({ratios[-1]:.3f}x)")
        rejects = storm.metrics.wire_crc_rejects
        retries = storm.metrics.wire_retries
        for slot in storm.replicas:
            injected += slot.driver._plan.total_injected
    finally:
        clean_fleet.close()
        storm.close()
    ratio_med, ratio_spread = median_spread(ratios)
    return {
        "injected_fault_rate_per_frame": 0.01,
        "n_requests_per_wave": n_requests,
        "new_tokens": new_tokens,
        "tokens_per_s_clean": round(median_spread(clean_all)[0], 1),
        "tokens_per_s_storm": round(median_spread(storm_all)[0], 1),
        "throughput_retained_x": round(ratio_med, 3),
        "throughput_retained_per_pair": [round(r, 3) for r in ratios],
        "throughput_retained_spread_pct": round(ratio_spread, 2),
        "wire_faults_injected_total": injected,
        "wire_crc_rejects_total": rejects,
        "wire_retries_total": retries,
        # Zero corrupt frames accepted is a codec property; the
        # referee is every storm stream byte-identical to the oracle.
        "corrupt_frames_accepted": 0 if exact else None,
        "streams_token_exact": exact,
    }


def _dtrace_leg(args, repeats: int) -> dict:
    """Paired tracing-off vs tracing-on waves through 2 process
    replicas: fleet-wide distributed tracing (ISSUE 19) must ride
    along at >= 0.95x throughput while every request's spans ship
    back over the pipe and stitch gap-free across processes."""
    import subprocess

    from pddl_tpu.obs.assemble import stitch
    from pddl_tpu.serve.fleet import FleetRouter, ProcessReplica
    from pddl_tpu.serve.fleet.worker import build_engine

    cfg = _ctrlplane_cfg()
    # Waves sized so one (off, on) attempt fits inside a host noise
    # burst's dwell time (~1s per wave): the per-attempt RATIO then
    # sees the same noise on both sides and cancels it.
    new_tokens = 64
    n_requests = 48
    oracle = build_engine(cfg)
    refs = {}

    def ref_for(prompt):
        key = tuple(prompt)
        if key not in refs:
            out = generate(oracle.model, {"params": oracle._params},
                           jnp.asarray(prompt, jnp.int32)[None],
                           new_tokens)
            refs[key] = np.asarray(out)[0, len(prompt):].tolist()
        return refs[key]

    def spawn(traced):
        reps = []
        for i in range(2):
            wcfg = {**cfg, "replica_id": i}
            if traced:
                wcfg["dtrace"] = True
            # The same tight ping cadence on BOTH fleets: pongs carry
            # the traced fleet's span batches AND clock samples, and
            # the untraced fleet must pay the identical ping cost so
            # the pair isolates tracing, not heartbeat traffic.
            reps.append(ProcessReplica(
                i, wcfg, stderr=subprocess.DEVNULL,
                ping_interval_s=0.01, wait_ready=False))
        for r in reps:
            r.wait_ready()
        return FleetRouter(reps, respawn=False,
                           dtrace=True if traced else None)

    ratios, off_all, on_all = [], [], []
    exact = True
    # Long-lived fleets, both warmed untimed (the r19 wire-leg
    # discipline): every pair compares equally-warm processes.
    fleet_off = spawn(False)
    fleet_on = spawn(True)
    try:
        warm_rng = np.random.default_rng(1899)
        warm = [warm_rng.integers(0, cfg["vocab"], size=12).tolist()
                for _ in range(n_requests)]
        _ctrl_wave(fleet_off, warm, new_tokens)
        _ctrl_wave(fleet_on, warm, new_tokens)
        for rep in range(repeats):
            rng = np.random.default_rng(1900 + rep)
            prompts = [rng.integers(0, cfg["vocab"], size=12).tolist()
                       for _ in range(n_requests)]
            # Nine alternated (off, on) attempts; the pair's ratio is
            # the MEDIAN of the per-attempt ratios. On a shared 1-core
            # host, noise bursts dwell for seconds — longer than any
            # wave — so a burst lands on BOTH waves of an attempt and
            # cancels in that attempt's ratio, while the median sheds
            # the attempts where it straddled only one side. The order
            # flips each attempt so neither fleet always runs first.
            attempt_ratios, attempt_off, attempt_on = [], [], []
            for k in range(9):
                first, second = ((fleet_off, fleet_on) if k % 2 == 0
                                 else (fleet_on, fleet_off))
                _, t_first, _ = _ctrl_wave(first, prompts, new_tokens)
                handles, t_second, _ = _ctrl_wave(second, prompts,
                                                  new_tokens)
                t_off, t_on = ((t_first, t_second) if k % 2 == 0
                               else (t_second, t_first))
                on_handles = handles if k % 2 == 0 else None
                if on_handles is not None:
                    for p, h in zip(prompts, on_handles):
                        if h.state.value != "finished" \
                                or h.tokens != ref_for(p):
                            exact = False
                attempt_ratios.append(t_on / t_off)
                attempt_off.append(t_off)
                attempt_on.append(t_on)
            # Burst rejection: an attempt where either side ran well
            # below its own best this pair caught external load on one
            # wave — its ratio measures the neighbour, not tracing.
            # Median the attempts that ran clean on BOTH sides.
            best_off, best_on = max(attempt_off), max(attempt_on)
            kept = [i for i in range(len(attempt_ratios))
                    if attempt_off[i] >= 0.9 * best_off
                    and attempt_on[i] >= 0.9 * best_on]
            if len(kept) < 3:  # storm ate the pair: keep everything
                kept = list(range(len(attempt_ratios)))
            tps_off = float(np.median([attempt_off[i] for i in kept]))
            tps_on = float(np.median([attempt_on[i] for i in kept]))
            off_all.append(tps_off)
            on_all.append(tps_on)
            ratios.append(float(np.median(
                [attempt_ratios[i] for i in kept])))
            _log(f"dtrace pair {rep}: {tps_off:,.0f} -> "
                 f"{tps_on:,.0f} tok/s ({ratios[-1]:.3f}x)")
        # Drain the tail: the last wave's span batches ride pong reads,
        # so pump past a few ping intervals before the referee stitches.
        drain = time.perf_counter() + 1.0
        while time.perf_counter() < drain:
            fleet_on.step()
            time.sleep(0.01)
        records = fleet_on.dtrace.records()
        traces = stitch(records)
        gap_free = sum(1 for t in traces.values() if not t.gaps())
        replica_spans = sum(1 for r in records
                            if r.get("kind") == "span")
        dropped = sum(int(getattr(slot.driver, "spans_dropped", 0))
                      for slot in fleet_on.replicas)
    finally:
        fleet_off.close()
        fleet_on.close()
    ratio_med, ratio_spread = median_spread(ratios)
    floor = 0.95
    return {
        "process_replicas": 2,
        "n_requests_per_wave": n_requests,
        "new_tokens": new_tokens,
        "tokens_per_s_tracing_off":
            round(median_spread(off_all)[0], 1),
        "tokens_per_s_tracing_on":
            round(median_spread(on_all)[0], 1),
        "tracing_on_over_off_x": round(ratio_med, 3),
        "tracing_on_over_off_per_pair": [round(r, 3) for r in ratios],
        "tracing_on_over_off_spread_pct": round(ratio_spread, 2),
        "tracing_retained_floor": floor,
        "all_pairs_above_floor": all(r >= floor for r in ratios),
        "traces_stitched_total": len(traces),
        "traces_gap_free_total": gap_free,
        "traces_all_gap_free": gap_free == len(traces),
        "replica_spans_collected_total": replica_spans,
        "spans_dropped_remote_total": dropped,
        "streams_token_exact": exact,
    }


def _ctrlplane_recovery_leg(model, variables, args,
                            repeats: int) -> dict:
    """Router WAL crash + recover: wall time from ``recover()`` until
    every revived stream moved PAST its mirrored length (the streams
    are serving again), plus full-stream token-exactness."""
    from pddl_tpu.serve.fleet import (
        FleetRouter,
        LocalReplica,
        RouterJournal,
    )

    def factory():
        return ServeEngine(model, variables, max_slots=4,
                           prefill_len=32, max_queue_depth=96)

    def replicas():
        return [LocalReplica(i, factory) for i in range(2)]

    new_tokens = 32
    recovery_all, revived_all = [], []
    exact = True
    recompile_free = True
    for rep in range(repeats):
        d = tempfile.mkdtemp(prefix="pddl-ctrlplane-wal-")
        try:
            rng = np.random.default_rng(700 + rep)
            prompts = [rng.integers(0, 64, size=12).tolist()
                       for _ in range(12)]
            refs = {tuple(p): _make_ref(model, variables, p, new_tokens)
                    for p in prompts}
            fleet = FleetRouter(replicas(), affinity_block_size=8,
                                affinity_blocks=1, respawn=False,
                                journal=RouterJournal(
                                    d, fsync_batch_records=16))
            for p in prompts:
                fleet.submit(list(p), new_tokens)
            for _ in range(10):  # mid-stream: mirrors partly populated
                fleet.step()
            # SIGKILL-equivalent: the router object is abandoned with
            # its buffers unflushed; the WAL is all that survives.
            t0 = time.perf_counter()
            recovered, revived = FleetRouter.recover(
                d, replicas(), affinity_block_size=8,
                affinity_blocks=1, respawn=False)
            at_recovery = {rid: len(fh.tokens)
                           for rid, fh in revived.items()}
            for _ in range(100000):
                if not any(len(fh.tokens) <= at_recovery[rid]
                           and not fh.done
                           for rid, fh in revived.items()):
                    break
                recovered.step()
            recovery_s = time.perf_counter() - t0
            recovered.run(max_steps=100000)
            for fh in revived.values():
                if fh.state.value != "finished" or fh.tokens != refs[
                        tuple(int(t) for t in fh.request.prompt)]:
                    exact = False
            counts = recovered.compile_counts()
            if not counts or any(v != 1 for v in counts.values()):
                recompile_free = False
            recovered.close()
            recovery_all.append(recovery_s)
            revived_all.append(len(revived))
            _log(f"ctrlplane recovery pair {rep}: {len(revived)} "
                 f"streams resumed in {recovery_s:.3f}s")
        finally:
            shutil.rmtree(d, ignore_errors=True)
    med, spread = median_spread(recovery_all)
    return {
        "kill": "router abandoned mid-stream with unflushed buffers "
                "(WAL-only recovery), fresh replicas",
        "recovery_s": round(med, 4),
        "recovery_s_spread_pct": round(spread, 2),
        "recovery_s_per_repeat": [round(r, 4) for r in recovery_all],
        "streams_revived_per_repeat": revived_all,
        "streams_token_exact": exact,
        "zero_recompiles_recovered": recompile_free,
    }


def _make_ref(model, variables, prompt, n_new):
    out = generate(model, variables,
                   jnp.asarray(prompt, jnp.int32)[None], n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


def _ctrlplane_hedge_leg(args, repeats: int) -> dict:
    """Gray-replica hedging ON vs OFF under an injected slow WORKER
    (real processes — the regime where a slow replica costs wall
    time the router does not spend): interactive p99 TTFT for traffic
    stuck to the suspect, paired per repeat."""
    import subprocess

    from pddl_tpu.serve.fleet import (
        FleetRouter,
        GrayDetector,
        ProcessReplica,
    )

    cfg = {**_ctrlplane_cfg(), "slots": 2}
    n_interactive = 8
    delay_s = 0.03

    def run_once(hedge: bool, seed: int):
        reps = [ProcessReplica(i, {**cfg, "replica_id": i},
                               stderr=subprocess.DEVNULL,
                               ping_interval_s=0.05, wait_ready=False)
                for i in range(2)]
        for r in reps:
            r.wait_ready()
        fleet = FleetRouter(
            reps, affinity_block_size=8, affinity_blocks=1,
            respawn=False,
            gray=GrayDetector(window=8, baseline=16, z_threshold=4.0,
                              min_excess_s=0.01, consecutive=2),
            gray_hedge=hedge, gray_drain=False)
        try:
            # Session-pin traffic to one replica; give the detector a
            # clean-speed baseline from its self-reported tick walls.
            pin = fleet.submit(list(range(1, 9)), 96, session="s",
                               priority=Priority.BATCH)
            victim = pin.replica_id
            t_end = time.perf_counter() + 1.5
            while time.perf_counter() < t_end:
                fleet.step()
            # Now make the worker GRAY (every tick +30 ms) and keep
            # its two slots saturated with long batch streams.
            victim_slot = next(s for s in fleet.replicas
                               if s.replica_id == victim)
            victim_slot.driver.set_tick_delay(delay_s)
            busy = [fleet.submit(list(range(2, 10)), 96, session="s",
                                 priority=Priority.BATCH)
                    for _ in range(2)]
            deadline = time.perf_counter() + 30
            while victim not in fleet.gray.suspected \
                    and time.perf_counter() < deadline:
                fleet.step()
            assert victim in fleet.gray.suspected, \
                "suspicion never fired"
            rng = np.random.default_rng(seed)
            ttfts = []
            for _ in range(n_interactive):
                p = rng.integers(0, cfg["vocab"], size=10).tolist()
                h = fleet.submit(p, 4, session="s")
                hang = time.perf_counter() + 120
                while not h.done and time.perf_counter() < hang:
                    fleet.step()
                assert h.done and h.ttft_s is not None
                ttfts.append(h.ttft_s)
            del busy  # batch streams need not finish: the leg
            #           measures the interactive tail, not them
            wins = fleet.metrics.hedge_wins
            counts = fleet.compile_counts()
            ok = bool(counts) and all(v == 1 for v in counts.values())
            return float(np.percentile(ttfts, 99)), wins, ok
        finally:
            fleet.close()

    ratios, on_all, off_all = [], [], []
    wins_total = 0
    recompile_free = True
    for rep in range(repeats):
        p99_off, _, ok_off = run_once(False, 800 + rep)
        p99_on, wins, ok_on = run_once(True, 800 + rep)
        wins_total += wins
        recompile_free = recompile_free and ok_off and ok_on
        on_all.append(p99_on)
        off_all.append(p99_off)
        ratios.append(p99_off / p99_on)
        _log(f"ctrlplane hedge pair {rep}: p99 TTFT {p99_off:.4f}s "
             f"-> {p99_on:.4f}s ({ratios[-1]:.2f}x, {wins} wins)")
    ratio_med, ratio_spread = median_spread(ratios)
    return {
        "slow_replica": f"worker tick delay {delay_s * 1000:.0f} ms "
                        "(set_tick_delay), detector-suspected from "
                        "self-reported tick walls before measuring",
        "interactive_requests_per_wave": n_interactive,
        "ttft_p99_hedge_off_s": round(median_spread(off_all)[0], 4),
        "ttft_p99_hedge_on_s": round(median_spread(on_all)[0], 4),
        "hedged_ttft_p99_reduction_x": round(ratio_med, 3),
        "hedged_ttft_reduction_per_pair": [round(r, 3) for r in ratios],
        "hedged_ttft_reduction_spread_pct": round(ratio_spread, 2),
        "hedge_wins_total": wins_total,
        "all_pairs_directional": all(r > 1.0 for r in ratios),
        "zero_recompiles": recompile_free,
    }


def _ctrlplane_leg(args) -> dict:
    repeats = max(args.repeats, 5)
    cfg = _ctrlplane_cfg()
    model = GPT(vocab_size=cfg["vocab"], max_len=cfg["max_len"],
                embed_dim=cfg["embed_dim"], depth=cfg["depth"],
                num_heads=cfg["heads"], attention="reference")
    dummy = jnp.ones((1, 16), jnp.int32)
    params = model.init(jax.random.key(0), dummy,
                        train=False)["params"]
    variables = {"params": params}
    wire = _ctrlplane_wire_leg(args, repeats)
    recovery = _ctrlplane_recovery_leg(model, variables, args, repeats)
    hedge = _ctrlplane_hedge_leg(args, repeats)
    return {"wire": wire, "recovery": recovery, "hedge": hedge}


def _ha_failover_leg(model, variables, args, repeats: int) -> dict:
    """Hot-standby failover vs the r19 cold recover path, PAIRED per
    repeat on the same workload (ISSUE 20).

    Hot: the standby tails the primary's WAL live over the shipper,
    the lease lapses when the primary goes silent, and promotion
    replays onto the SAME still-warm engines. Cold: the r19 path —
    ``FleetRouter.recover`` onto FRESH replicas, which pays the spawn
    + prefill/decode compile inside the outage window. Both clocks
    start at the moment of primary silence and stop when every revived
    stream has produced a token PAST its mirrored length (serving
    again, not merely rebuilt), so the pair isolates exactly what the
    standby buys. The deposed primary keeps commanding after each hot
    takeover; its refusal count is the split-brain headline."""
    from pddl_tpu.serve.fleet import (
        EpochFenced,
        FleetRouter,
        HotStandby,
        Lease,
        LeaseKeeper,
        LocalReplica,
        RouterJournal,
        WalShipper,
    )

    def factory():
        return ServeEngine(model, variables, max_slots=4,
                           prefill_len=32, max_queue_depth=96)

    def replicas():
        return [LocalReplica(i, factory) for i in range(2)]

    router_kw = dict(affinity_block_size=8, affinity_blocks=1,
                     respawn=False)
    new_tokens = 32
    lease_ttl_s = 0.25
    hot_all, cold_all, ratios, revived_all = [], [], [], []
    exact = True
    recompile_free = True
    acked_lost = 0
    probes_attempted = 0
    probes_refused = 0

    for rep in range(repeats):
        rng = np.random.default_rng(900 + rep)
        prompts = [rng.integers(0, 64, size=12).tolist()
                   for _ in range(12)]
        refs = {tuple(p): _make_ref(model, variables, p, new_tokens)
                for p in prompts}

        # ---- hot: WAL-shipped standby, lease-lapse promotion --------
        d = tempfile.mkdtemp(prefix="pddl-ha-hot-")
        try:
            journal = RouterJournal(d, fsync_batch_records=16)
            fleet = FleetRouter(replicas(), journal=journal,
                                **router_kw)
            lease = Lease(os.path.join(d, "ha_lease.json"),
                          ttl_s=lease_ttl_s)
            keeper = LeaseKeeper(lease, "primary", seed=rep)
            fleet.set_epoch(keeper.acquire())
            fleet.ha = keeper
            standby = HotStandby(
                d, [s.driver for s in fleet.replicas], lease=lease,
                holder="standby", router_kw=router_kw, seed=rep + 1)
            shipper = WalShipper(journal, standby.feed)
            standby.attach(shipper)
            handles = [fleet.submit(list(p), new_tokens)
                       for p in prompts]
            for _ in range(10):           # mid-stream, mirrors partial
                fleet.step()
                keeper.step()
            acked = {tuple(int(t) for t in h.request.prompt):
                     list(h.tokens) for h in handles}
            # Primary goes silent: no more steps, no more renewals.
            t0 = time.perf_counter()
            out = None
            while out is None and time.perf_counter() < t0 + 60.0:
                out = standby.step()
                time.sleep(0.002)
            assert out is not None, "standby never promoted"
            promoted, revived = out
            at_promo = {rid: len(fh.tokens)
                        for rid, fh in revived.items()}
            for _ in range(100000):
                if not any(len(fh.tokens) <= at_promo[rid]
                           and not fh.done
                           for rid, fh in revived.items()):
                    break
                promoted.step()
            failover_s = time.perf_counter() - t0
            # The deposed primary keeps commanding: every worker must
            # refuse it on the fencing epoch, not on trust.
            probes_attempted += 1
            try:
                fleet.submit([1, 2, 3], 4)
            except EpochFenced:
                probes_refused += 1
            promoted.run(max_steps=100000)
            revived_keys = set()
            for fh in revived.values():
                key = tuple(int(t) for t in fh.request.prompt)
                revived_keys.add(key)
                if fh.state.value != "finished" \
                        or fh.tokens != refs[key]:
                    exact = False
            open_keys = {k for k, t in acked.items()
                         if len(t) < len(refs[k])}
            acked_lost += len(open_keys - revived_keys)
            counts = promoted.compile_counts()
            if not counts or any(v != 1 for v in counts.values()):
                recompile_free = False
            promoted.close()
            revived_all.append(len(revived))
            hot_all.append(failover_s)
        finally:
            shutil.rmtree(d, ignore_errors=True)

        # ---- cold: the r19 recover path, same workload --------------
        d2 = tempfile.mkdtemp(prefix="pddl-ha-cold-")
        try:
            fleet = FleetRouter(replicas(),
                                journal=RouterJournal(
                                    d2, fsync_batch_records=16),
                                **router_kw)
            for p in prompts:
                fleet.submit(list(p), new_tokens)
            for _ in range(10):
                fleet.step()
            t0 = time.perf_counter()
            recovered, revived = FleetRouter.recover(
                d2, replicas(), **router_kw)
            at_rec = {rid: len(fh.tokens)
                      for rid, fh in revived.items()}
            for _ in range(100000):
                if not any(len(fh.tokens) <= at_rec[rid]
                           and not fh.done
                           for rid, fh in revived.items()):
                    break
                recovered.step()
            cold_s = time.perf_counter() - t0
            recovered.run(max_steps=100000)
            recovered.close()
            cold_all.append(cold_s)
        finally:
            shutil.rmtree(d2, ignore_errors=True)

        ratios.append(cold_all[-1] / hot_all[-1])
        _log(f"ha pair {rep}: failover {hot_all[-1]:.3f}s vs cold "
             f"recover {cold_all[-1]:.3f}s ({ratios[-1]:.1f}x)")

    med, spread = median_spread(hot_all)
    cold_med, _ = median_spread(cold_all)
    ratio_med, ratio_spread = median_spread(ratios)
    return {
        "outage": "primary partitioned mid-stream (stops stepping and "
                  "renewing; OBJECT stays alive and keeps commanding), "
                  "standby promotes on lease lapse over the same live "
                  "replicas",
        "detection_lease_ttl_s": lease_ttl_s,
        "failover_s": round(med, 4),
        "failover_s_spread_pct": round(spread, 2),
        "failover_s_per_repeat": [round(s, 4) for s in hot_all],
        "cold_recover_s": round(cold_med, 4),
        "cold_recover_s_per_repeat": [round(s, 4) for s in cold_all],
        "failover_speedup_vs_cold_x": round(ratio_med, 2),
        "failover_speedup_spread_pct": round(ratio_spread, 2),
        "all_pairs_directional": all(r > 1.0 for r in ratios),
        "streams_revived_per_repeat": revived_all,
        "acked_streams_lost_total": acked_lost,
        "streams_token_exact": exact,
        "zero_recompiles_promoted": recompile_free,
        "deposed_probes_attempted": probes_attempted,
        "deposed_probes_refused": probes_refused,
    }


def _chaosd_availability_leg(model, variables, args,
                             repeats: int) -> dict:
    """Paired clean vs persistent-EIO-storm waves over a WAL-armed
    local fleet (ISSUE 18): while EVERY disk op fails, the journal
    degrades NON_DURABLE and serving must hold ~all of its clean
    throughput — then, when the disk returns, the next due probe
    re-arms durability and the retained backlog lands on disk."""
    from pddl_tpu.serve.fleet import (
        FleetRouter,
        LocalReplica,
        RouterJournal,
    )
    from pddl_tpu.utils.faults import StorageFaultPlan

    new_tokens = 64
    n_requests = 48
    probe_s = 0.05

    def factory():
        return ServeEngine(model, variables, max_slots=4,
                           prefill_len=32, max_queue_depth=96)

    d = tempfile.mkdtemp(prefix="pddl-chaosd-wal-")
    sp = StorageFaultPlan(seed=0)
    # checkpoint_every_records is pushed out of reach: a checkpoint+
    # rotate cycle (~3 fsyncs + a full-state write) fires every ~1.3
    # waves at the default and lands on whichever wave is running —
    # storm waves skip it (degraded checkpoints fail fast), so the
    # lump lands only on CLEAN waves and whipsaws the ratio between
    # runs. Checkpoint cost has its own r19 recovery leg; this leg
    # isolates the steady-state durability tax (write+fsync batching).
    journal = RouterJournal(d, storage_plan=sp, fsync_batch_records=8,
                            retry_limit=1, retry_backoff_s=0.0,
                            rearm_interval_s=probe_s,
                            checkpoint_every_records=1 << 20)
    # ONE long-lived fleet serves both halves of every pair: the clean
    # and storm waves ride identically-warm engines, so the ratio
    # isolates the degraded journal, not compile state.
    fleet = FleetRouter([LocalReplica(i, factory) for i in range(2)],
                        journal=journal, affinity_block_size=8,
                        affinity_blocks=1, respawn=False)
    refs = {}

    def ref_for(prompt):
        key = tuple(prompt)
        if key not in refs:
            refs[key] = _make_ref(model, variables, prompt, new_tokens)
        return refs[key]

    ratios, clean_all, storm_all, rearm_all = [], [], [], []
    exact = True
    try:
        warm_rng = np.random.default_rng(949)
        warm = [warm_rng.integers(0, 64, size=12).tolist()
                for _ in range(n_requests)]
        _ctrl_wave(fleet, warm, new_tokens)
        for rep in range(repeats):
            rng = np.random.default_rng(950 + rep)
            prompts = [rng.integers(0, 64, size=12).tolist()
                       for _ in range(2 * n_requests)]

            def clean_wave():
                _, tps, _ = _ctrl_wave(fleet, prompts[:n_requests],
                                       new_tokens)
                return tps

            def storm_wave():
                nonlocal exact
                sp._rates = (1.0, 0.0, 0.0, 0.0)  # the disk dies
                handles, tps, _ = _ctrl_wave(
                    fleet, prompts[n_requests:], new_tokens)
                assert journal.non_durable, \
                    "storm never degraded the WAL"
                for p, h in zip(prompts[n_requests:], handles):
                    if h.state.value != "finished" \
                            or h.tokens != ref_for(p):
                        exact = False
                sp.quiesce()                  # the disk comes back
                t0 = time.perf_counter()
                hang = t0 + 5.0
                while journal.non_durable \
                        and time.perf_counter() < hang:
                    fleet.step()
                rearm = time.perf_counter() - t0
                assert not journal.non_durable, \
                    "journal never re-armed"
                return tps, rearm

            # Alternate the pair order per repeat: a slow drift in
            # host throughput across the run (thermal, ambient load)
            # would otherwise bias every ratio the same way.
            if rep % 2 == 0:
                tps_clean = clean_wave()
                tps_storm, rearm_s = storm_wave()
            else:
                tps_storm, rearm_s = storm_wave()
                tps_clean = clean_wave()
            clean_all.append(tps_clean)
            storm_all.append(tps_storm)
            ratios.append(tps_storm / tps_clean)
            rearm_all.append(rearm_s)
            _log(f"chaosd availability pair {rep}: {tps_clean:,.0f} -> "
                 f"{tps_storm:,.0f} tok/s ({ratios[-1]:.3f}x), "
                 f"re-armed in {rearm_s * 1000:.1f} ms")
        m = fleet.metrics
        degraded_events = m.journal_degraded_events
        rearms = m.journal_rearms
        storage_errors = m.journal_storage_errors
    finally:
        fleet.close()
        shutil.rmtree(d, ignore_errors=True)
    ratio_med, ratio_spread = median_spread(ratios)
    rearm_med, _ = median_spread(rearm_all)
    # Worst-case honest bound: the probe may have JUST failed when the
    # disk recovers, so re-arm can take up to one full interval plus
    # one idle router step of wall.
    rearm_bound_s = probe_s + 0.05
    return {
        "fault_profile": "every vfs op EIO (rate 1.0) for the whole "
                         "wave; quiesced before the re-arm measurement",
        "n_requests_per_wave": n_requests,
        "new_tokens": new_tokens,
        "tokens_per_s_clean": round(median_spread(clean_all)[0], 1),
        "tokens_per_s_storm": round(median_spread(storm_all)[0], 1),
        "non_durable_availability_x": round(ratio_med, 3),
        "non_durable_availability_per_pair": [round(r, 3)
                                              for r in ratios],
        "non_durable_availability_spread_pct": round(ratio_spread, 2),
        "rearm_probe_interval_s": probe_s,
        "rearm_latency_s": round(rearm_med, 4),
        "rearm_latency_s_per_repeat": [round(r, 4) for r in rearm_all],
        "rearm_within_one_probe_interval": bool(
            max(rearm_all) <= rearm_bound_s),
        "journal_degraded_events_total": degraded_events,
        "journal_rearms_total": rearms,
        "journal_storage_errors_total": storage_errors,
        "storage_faults_injected_total": int(sp.total_injected),
        "streams_token_exact": exact,
    }


def _chaosd_campaign_leg(args) -> dict:
    """3-seed composed-plane campaigns over PROCESS fleets (ISSUE 18):
    seeded wire storms underneath, a storage storm on the router WAL,
    a gray slow-wall span, a worker SIGKILL, then the router
    crash+recover — :class:`ChaosConductor`'s invariant referee judges
    each campaign (acked_terminal, token_exact, zero_recompiles,
    recover_idempotent, recovery_bounded, exposition)."""
    import subprocess

    from pddl_tpu.chaos import ChaosConductor, ReplicaChaos
    from pddl_tpu.serve.fleet import ProcessReplica, WireFaultPlan
    from pddl_tpu.serve.fleet.worker import build_engine
    from pddl_tpu.utils.faults import StorageFaultPlan

    cfg = _ctrlplane_cfg()
    # Enough queued work that every plane lands on a LIVE fleet: with
    # the baseline tick wall set in make_replicas, the workers chew
    # ~3k tokens over ~2 s of wall while the paced schedule (pace_s
    # below) spreads the storm/kill/crash across the same window —
    # chaos composed over traffic, not over a drained fleet.
    new_tokens = 64
    n_streams = 48
    seeds = (0, 1, 2)
    oracle = build_engine(cfg)
    refs = {}

    def ref_for(prompt, n):
        key = (tuple(prompt), int(n))
        if key not in refs:
            out = generate(oracle.model, {"params": oracle._params},
                           jnp.asarray(prompt, jnp.int32)[None], int(n))
            refs[key] = np.asarray(out)[0, len(prompt):].tolist()
        return refs[key]

    reports = []
    wire_injected = storage_injected = 0
    for seed in seeds:
        d = tempfile.mkdtemp(prefix=f"pddl-chaosd-campaign-{seed}-")

        def make_replicas():
            reps = []
            for i in range(2):
                plan = WireFaultPlan(3000 + 100 * seed + i,
                                     corrupt_rate=0.004,
                                     duplicate_rate=0.002,
                                     reorder_rate=0.002,
                                     drop_rate=0.002)
                reps.append(ProcessReplica(
                    i, {**cfg, "replica_id": i},
                    stderr=subprocess.DEVNULL, wire_fault_plan=plan,
                    ping_interval_s=0.01, resend_timeout_s=0.01,
                    wait_ready=False))
            for r in reps:
                r.wait_ready()
                # A 2x64 worker decodes ~6k tok/s: the whole campaign
                # workload would drain inside the first 3 paced steps,
                # before any span plane fires. A small baseline tick
                # wall prices each tick like a real model so the
                # storm/kill/crash land on LIVE traffic.
                r.set_tick_delay(0.004)
            return reps

        def make_chaos(fleet):
            # No GrayDetector armed: the gray PLANE here is the slow
            # wall itself composing with the other planes; detection/
            # hedging has its own paired leg in r19.
            return [ReplicaChaos(replica_id=int(s.replica_id),
                                 wire_plan=getattr(s.driver, "_plan",
                                                   None),
                                 slow_fn=s.driver.set_tick_delay,
                                 kill_fn=s.driver.kill)
                    for s in fleet.replicas]

        sp = StorageFaultPlan(seed=seed)
        cond = ChaosConductor(
            make_replicas, make_chaos, ref_for,
            journal_dir=d, storage_plan=sp,
            router_kw=dict(affinity_block_size=8, affinity_blocks=1,
                           respawn=False),
            journal_kw=dict(fsync_batch_records=4, retry_limit=1,
                            retry_backoff_s=0.0,
                            rearm_interval_s=0.05),
            recovery_bound_s=90.0, seed=seed)
        rng = np.random.default_rng(990 + seed)
        workload, seen = [], set()
        while len(workload) < n_streams:
            p = rng.integers(0, cfg["vocab"], size=12).tolist()
            if tuple(p) in seen:
                continue
            seen.add(tuple(p))
            workload.append((p, new_tokens))
        try:
            report = cond.run(
                workload,
                planes=("wire", "storage", "gray", "kill", "router"),
                horizon=40, kills=1, slow_delay_s=0.02,
                pace_s=0.04, max_wall_s=300.0)
        finally:
            shutil.rmtree(d, ignore_errors=True)
        assert report.ok, (f"campaign seed {seed} violated: "
                           f"{report.violations}")
        wire_injected += report.injected.get("wire", 0)
        storage_injected += report.injected.get("storage", 0)
        reports.append(report)
        _log(f"chaosd campaign seed {seed}: {len(report.actions)} "
             f"actions over {report.steps} steps, recovery "
             f"{report.recovery_s:.2f}s, injected {report.injected}, "
             f"ok={report.ok}")
    recovery_med, recovery_spread = median_spread(
        [r.recovery_s for r in reports])
    return {
        "planes_composed": ["wire", "storage", "gray", "kill",
                            "router"],
        "seeds": list(seeds),
        "streams_per_campaign": n_streams,
        "new_tokens": new_tokens,
        "campaigns_all_ok": all(r.ok for r in reports),
        "invariants_checked": sorted(reports[0].invariants),
        "invariants_failed": sorted(
            {name for r in reports
             for name, ok in r.invariants.items() if not ok}),
        "recovery_s": round(recovery_med, 3),
        "recovery_s_per_seed": [round(r.recovery_s, 3)
                                for r in reports],
        "recovery_s_spread_pct": round(recovery_spread, 2),
        "actions_fired_per_seed": [len(r.actions) for r in reports],
        "kills_fired_total": sum(
            1 for r in reports for a in r.actions if a.kind == "kill"),
        "router_crashes_total": sum(
            1 for r in reports for a in r.actions
            if a.kind == "router_crash"),
        "wire_faults_injected_total": wire_injected,
        "storage_faults_injected_total": storage_injected,
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--embed-dim", type=int, default=256)
    p.add_argument("--depth", type=int, default=4)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--max-len", type=int, default=512)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--new-tokens", type=int, default=64)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--prefill-len", type=int, default=64)
    p.add_argument("--concurrent", type=int, default=8,
                   help="requests in the head-to-head vs sequential "
                        "generate() (the acceptance ratio)")
    p.add_argument("--poisson-requests", type=int, default=24,
                   help="requests per Poisson load point")
    p.add_argument("--max-queue-depth", type=int, default=16)
    p.add_argument("--skip-poisson", action="store_true",
                   help="head-to-head + prefix legs only (the Poisson "
                        "curve runs in real time and dominates wall "
                        "clock)")
    p.add_argument("--prefix-requests", type=int, default=24,
                   help="requests in the shared-prefix TTFT leg (the "
                        "leg runs them at n_requests slots with short "
                        "decodes, so TTFT measures the admission "
                        "prefill the cache shortens)")
    p.add_argument("--prefix-prompt-len", type=int, default=384,
                   help="shared-prefix leg prompt length (long prompts "
                        "are the cache's home turf — suffix compute "
                        "stays 1-shared_frac of the prompt while the "
                        "per-admission fixed costs amortize)")
    p.add_argument("--prefix-new-tokens", type=int, default=8)
    p.add_argument("--prefix-shared-frac", type=float, default=0.8)
    p.add_argument("--prefix-block-size", type=int, default=8)
    p.add_argument("--prefix-chunk", type=int, default=80,
                   help="narrow suffix-chunk width (~ the uncached "
                        "suffix at the default shared fraction)")
    p.add_argument("--spec-only", action="store_true",
                   help="speculative-serving leg only (ISSUE 12): "
                        "paired spec/plain waves + acceptance-vs-k "
                        "curve + chaos leg, standalone r17 artifact")
    p.add_argument("--spec-k", type=int, default=6,
                   help="drafted tokens per slot per step for the "
                        "headline wave (the verify window is k+1 wide)")
    p.add_argument("--spec-k-curve", default="2,4,6,8",
                   help="comma-separated k values for the "
                        "acceptance-rate curve")
    p.add_argument("--tier-only", action="store_true",
                   help="run only the tiered-KV-cache leg (host-RAM "
                        "spill tier vs the r13 evict-and-recompute "
                        "baseline at 4-32x working sets, plus the "
                        "2-replica duplicate-prefill chain-pull leg) "
                        "-> the r18 artifact")
    p.add_argument("--tier-mults", default="4,8,16,32",
                   help="working-set multiples of the device pool the "
                        "tier curve sweeps")
    p.add_argument("--tenant-only", action="store_true",
                   help="run only the multi-tenant leg (paged LoRA "
                        "adapters + constrained decoding; r14 artifact)")
    p.add_argument("--tenant-adapters", type=int, default=8,
                   help="distinct LoRA adapters in the tenant leg")
    p.add_argument("--fault-rate", type=float, default=0.01,
                   help="injected fault probability per device dispatch "
                        "in the fault leg (transient; OOM rides at a "
                        "tenth of it); 0 skips the leg")
    p.add_argument("--faults-only", action="store_true",
                   help="run ONLY the fault leg and write a standalone "
                        "artifact (r08_serve_faults.json)")
    p.add_argument("--obs-only", action="store_true",
                   help="run ONLY the observability leg (tracing "
                        "on/off paired overhead) and write a "
                        "standalone artifact (r09_serve_obs.json)")
    p.add_argument("--trace", default="",
                   help="also write a fully traced pass's span/tick/"
                        "metrics event log to this JSONL path as a "
                        "bench artifact")
    p.add_argument("--repeats", type=int, default=3,
                   help="timed repetitions per headline number (median "
                        "+ spread recorded)")
    p.add_argument("--fleet-only", action="store_true",
                   help="run ONLY the multi-replica fleet leg (process "
                        "replicas behind the router) and write a "
                        "standalone artifact (r11_serve_fleet.json)")
    p.add_argument("--fleet-replicas", default="2,4,8",
                   help="comma-separated replica counts for the fleet "
                        "scaling curve")
    p.add_argument("--fleet-load", type=float, default=0.8,
                   help="offered Poisson load as a fraction of "
                        "N x the r08 single-engine clean baseline")
    p.add_argument("--slo-only", action="store_true",
                   help="run ONLY the SLO/overload leg (bursty "
                        "multi-turn trace at 2x capacity through the "
                        "admission-controlled fleet) and write a "
                        "standalone artifact (r12_serve_slo.json)")
    p.add_argument("--slo-requests", type=int, default=240,
                   help="requests per SLO trace wave")
    p.add_argument("--slo-replicas", type=int, default=2,
                   help="in-process replicas behind the "
                        "admission-controlled router in the SLO leg")
    p.add_argument("--slo-overload", type=float, default=2.0,
                   help="offered load as a multiple of measured fleet "
                        "capacity in the SLO overload wave")
    p.add_argument("--autoscale-only", action="store_true",
                   help="run ONLY the elastic-autoscaling leg (diurnal "
                        "trace through static-N fleets vs the "
                        "autoscaled fleet; goodput per replica-hour) "
                        "and write a standalone artifact "
                        "(r16_serve_autoscale.json)")
    p.add_argument("--autoscale-min", type=int, default=1,
                   help="autoscaled fleet's floor (and starting size)")
    p.add_argument("--autoscale-max", type=int, default=4,
                   help="autoscaled fleet's ceiling")
    p.add_argument("--autoscale-static", default="1,2,4",
                   help="comma-separated static replica counts swept "
                        "for the best-static baseline")
    p.add_argument("--autoscale-duration", type=float, default=120.0,
                   help="seconds one diurnal period is compressed to")
    p.add_argument("--autoscale-offered", type=float, default=2.5,
                   help="offered MEAN load as a multiple of "
                        "single-replica capacity — sized to sit "
                        "BETWEEN static fleet sizes (the regime where "
                        "no static N is both sufficient and "
                        "efficient); the sinusoid swings peak:trough "
                        "around it")
    p.add_argument("--autoscale-peak-trough", type=float, default=8.0,
                   help="diurnal peak:trough intensity ratio")
    p.add_argument("--autoscale-attainment-floor", type=float,
                   default=0.95,
                   help="fraction of offered requests a static fleet "
                        "must FINISH to qualify as the best-static "
                        "baseline (and the autoscaled fleet is held "
                        "to the same bar)")
    p.add_argument("--ctrlplane-only", action="store_true",
                   help="run ONLY the control-plane durability leg "
                        "(framed-transport wire storm, router WAL "
                        "crash recovery, gray-replica hedging; "
                        "ISSUE 14) and write a standalone artifact "
                        "(r19_serve_ctrlplane.json)")
    p.add_argument("--ha-only", action="store_true",
                   help="run ONLY the router high-availability leg "
                        "(hot-standby WAL tail + lease-lapse fenced "
                        "promotion vs the cold recover path, paired "
                        "per repeat; ISSUE 20) and write a standalone "
                        "artifact (r23_serve_ha.json)")
    p.add_argument("--chaosd-only", action="store_true",
                   help="run ONLY the storage-chaos leg (paired "
                        "clean vs persistent-EIO-storm NON_DURABLE "
                        "availability + re-arm latency, 3-seed "
                        "composed-plane ChaosConductor campaigns "
                        "over process fleets; ISSUE 18) and write a "
                        "standalone artifact "
                        "(r21_serve_chaosd.json)")
    p.add_argument("--dtrace-only", action="store_true",
                   help="run ONLY the distributed-tracing overhead "
                        "leg (paired tracing-on/off waves at N=2 "
                        "process replicas, gap-free stitch referee; "
                        "ISSUE 19) and write a standalone artifact "
                        "(r22_serve_dtrace.json)")
    p.add_argument("--disagg-only", action="store_true",
                   help="run ONLY the disaggregated prefill/decode leg "
                        "(role-split fleet, block-granular KV "
                        "hand-off; ISSUE 17) and write a standalone "
                        "artifact (r20_serve_disagg.json)")
    p.add_argument("--disagg-replicas", type=int, default=4,
                   help="fleet size N for BOTH halves of each pair: "
                        "N unified vs a same-N role split")
    p.add_argument("--disagg-prefill-replicas", type=int, default=0,
                   help="prefill-pool size inside the split fleet "
                        "(0 = auto N//2; compute share, not token "
                        "share — decode steps cost ~10x a batched "
                        "prefill token on this model)")
    p.add_argument("--disagg-requests", type=int, default=48,
                   help="trace requests per wave")
    p.add_argument("--disagg-prompt-base", type=int, default=256,
                   help="per-session system-prompt length of the "
                        "long-prompt trace (tokens)")
    p.add_argument("--disagg-load", type=float, default=0.75,
                   help="offered rate as a fraction of measured "
                        "unified capacity")
    p.add_argument("--out", default="")
    args = p.parse_args()

    if args.ha_only:
        repeats = max(args.repeats, 5)
        _log(f"ha leg only: hot-standby failover vs cold recover, "
             f"{repeats} paired runs, gpt 2x64")
        cfg = _ctrlplane_cfg()
        model = GPT(vocab_size=cfg["vocab"], max_len=cfg["max_len"],
                    embed_dim=cfg["embed_dim"], depth=cfg["depth"],
                    num_heads=cfg["heads"], attention="reference")
        dummy = jnp.ones((1, 16), jnp.int32)
        params = model.init(jax.random.key(0), dummy,
                            train=False)["params"]
        variables = {"params": params}
        ha = _ha_failover_leg(model, variables, args, repeats)
        record = {
            "metric": "fleet_serving_router_ha",
            "unit": "seconds (primary silence -> every revived stream "
                    "serving again); ratio (cold recover / hot "
                    "failover wall)",
            "config": {
                "model": "gpt 2x64 (vocab 64, max_len 128)",
                "replicas": 2,
                "standby": "WAL-shipped hot standby: live record "
                           "stream over the framed transport, disk "
                           "catch-up on join and wire gaps "
                           "(serve/fleet/standby.py)",
                "lease": f"file-backed, ttl {ha['detection_lease_ttl_s']}s, "
                         "seeded subtractive renewal jitter; holder "
                         "change bumps the fencing epoch",
                "fencing": "every worker-bound command carries the "
                           "issuing router's epoch; workers persist "
                           "the highest seen and refuse lower with a "
                           "typed reject (EpochFenced)",
                "promotion": "lease-lapse takeover replays the WAL "
                             "suffix onto the SAME live engines "
                             "(mirror-replay contract: token-exact, "
                             "zero recompiles)",
                "cold_baseline": "r19 FleetRouter.recover onto fresh "
                                 "replicas (spawn + compile inside "
                                 "the outage window), same workload",
            },
            "provenance": provenance(repeats),
            "results": {"ha": ha},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"ha: failover {ha['failover_s']}s median vs cold "
             f"{ha['cold_recover_s']}s "
             f"({ha['failover_speedup_vs_cold_x']}x, all pairs "
             f"directional {ha['all_pairs_directional']}); acked "
             f"streams lost {ha['acked_streams_lost_total']}, "
             f"token-exact {ha['streams_token_exact']}, zero "
             f"recompiles {ha['zero_recompiles_promoted']}; deposed "
             f"primary refused "
             f"{ha['deposed_probes_refused']}/"
             f"{ha['deposed_probes_attempted']}")
        _write_record(record, args.out)
        return

    if args.chaosd_only:
        repeats = max(args.repeats, 5)
        _log(f"chaosd leg only: persistent-EIO-storm availability "
             f"({repeats} paired waves) + 3-seed composed-plane "
             f"campaigns, gpt 2x64")
        cfg = _ctrlplane_cfg()
        model = GPT(vocab_size=cfg["vocab"], max_len=cfg["max_len"],
                    embed_dim=cfg["embed_dim"], depth=cfg["depth"],
                    num_heads=cfg["heads"], attention="reference")
        dummy = jnp.ones((1, 16), jnp.int32)
        params = model.init(jax.random.key(0), dummy,
                            train=False)["params"]
        variables = {"params": params}
        avail = _chaosd_availability_leg(model, variables, args,
                                         repeats)
        campaign = _chaosd_campaign_leg(args)
        record = {
            "metric": "fleet_serving_storage_chaos",
            "unit": "ratio (storm/clean tok_s while the WAL is "
                    "degraded NON_DURABLE); seconds (durability "
                    "re-arm, campaign crash recovery)",
            "config": {
                "model": "gpt 2x64 (vocab 64, max_len 128)",
                "storage_faults": "seeded StorageFaultPlan "
                                  "(EIO/ENOSPC/torn/slow) through "
                                  "the journal VFS shim "
                                  "(utils/faults.py, "
                                  "serve/fleet/journal.py)",
                "degradation": "bounded retries -> NON_DURABLE with "
                               "acks flowing, rate-limited re-arm "
                               "probes, emergency checkpoint on "
                               "ENOSPC",
                "conductor": "seeded multi-plane campaign engine + "
                             "invariant referee "
                             "(pddl_tpu/chaos/conductor.py)",
                "campaign_fleet": "2 process replicas, WireFaultPlan "
                                  "armed, worker SIGKILL + router "
                                  "crash planes",
            },
            "provenance": provenance(repeats),
            # Group key "storm", NOT "availability": metric_direction
            # substring-matches the whole leaf path, and an
            # "availability" segment would stamp higher-is-better onto
            # every leaf under it — including rearm_latency_s.
            "results": {"storm": avail, "campaign": campaign},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"chaosd: NON_DURABLE availability "
             f"{avail['non_durable_availability_x']}x "
             f"({avail['storage_faults_injected_total']} storage "
             f"faults injected, token-exact "
             f"{avail['streams_token_exact']}); re-arm "
             f"{avail['rearm_latency_s']}s median (within one probe "
             f"interval: {avail['rearm_within_one_probe_interval']}); "
             f"campaigns ok={campaign['campaigns_all_ok']} over "
             f"planes {campaign['planes_composed']}, recovery "
             f"{campaign['recovery_s']}s median, injected "
             f"wire={campaign['wire_faults_injected_total']} "
             f"storage={campaign['storage_faults_injected_total']}")
        _write_record(record, args.out)
        return

    if args.dtrace_only:
        repeats = max(args.repeats, 5)
        _log(f"dtrace leg only: paired tracing-on/off waves, 2 "
             f"process replicas, {repeats} pairs, gpt 2x64")
        dtrace = _dtrace_leg(args, repeats)
        record = {
            "metric": "fleet_serving_distributed_tracing",
            "unit": "ratio (tracing-on/off tok_s); counts (spans, "
                    "gap-free stitched traces)",
            "config": {
                "model": "gpt 2x64 (vocab 64, max_len 128)",
                "process_replicas": 2,
                "propagation": "router-stamped (trace_id, "
                               "parent_span_id) on every pipe "
                               "command; worker child spans ship "
                               "back batched on pong/event reads "
                               "(pddl_tpu/obs/propagate.py)",
                "assembly": "trace_id stitch + min-RTT clock "
                            "alignment + gap referee "
                            "(pddl_tpu/obs/assemble.py)",
                "flight_recorder": "crash-durable per-worker span "
                                   "segments through the journal "
                                   "VFS shim "
                                   "(pddl_tpu/obs/flightrec.py)",
            },
            "provenance": provenance(repeats),
            "results": {"dtrace": dtrace},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"dtrace: {dtrace['tokens_per_s_tracing_off']} -> "
             f"{dtrace['tokens_per_s_tracing_on']} tok/s "
             f"({dtrace['tracing_on_over_off_x']}x, floor "
             f"{dtrace['tracing_retained_floor']}x, all pairs above "
             f"{dtrace['all_pairs_above_floor']}); "
             f"{dtrace['traces_gap_free_total']}/"
             f"{dtrace['traces_stitched_total']} traces gap-free, "
             f"{dtrace['replica_spans_collected_total']} spans "
             f"shipped ({dtrace['spans_dropped_remote_total']} "
             f"dropped); token-exact {dtrace['streams_token_exact']}")
        _write_record(record, args.out)
        return

    if args.disagg_only:
        repeats = max(args.repeats, 5)
        args.repeats = repeats
        _log(f"disagg leg only: {args.disagg_requests} long-prompt "
             f"trace requests, N={args.disagg_replicas} unified vs "
             f"same-N role split, {repeats} paired runs")
        disagg = _disagg_leg(args)
        record = {
            "metric": "fleet_serving_disaggregated_prefill_decode",
            "unit": "ratio (split/unified decode p99 inter-token "
                    "latency; split/unified aggregate tok/s); "
                    "milliseconds (KV hand-off)",
            "config": {
                "model": (f"gpt {args.depth}x{args.embed_dim} "
                          f"(vocab {args.vocab}, max_len "
                          f"{args.max_len})"),
                "slots_per_replica": args.slots,
                "replicas": args.disagg_replicas,
                "prefill_len": _disagg_prefill_len(args),
                "prompt_base": args.disagg_prompt_base,
                "offered_load_x_capacity": args.disagg_load,
                "roles": "router-side role-aware routing + "
                         "first-token KV hand-off, WAL-journaled "
                         "rebind (pddl_tpu/serve/fleet/disagg.py)",
                "transfer": "export_prefix_chain -> host-tier "
                            "import on in-process replicas "
                            "(models TPU-DMA transfer cost << "
                            "compute; a CPU pipe would price the "
                            "copy at compute parity), fresh-rid "
                            "hedge-alias rebind",
                "latency_attribution": "per-token latency = wall "
                                       "duration of the engine tick "
                                       "that produced the token; "
                                       "first tokens excluded",
            },
            "provenance": provenance(repeats),
            "results": {"disagg": disagg},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"disagg: decode p99 "
             f"{disagg['unified_decode_lat_p99_ms']}ms -> "
             f"{disagg['split_decode_lat_p99_ms']}ms "
             f"({disagg['decode_p99_interference']}x, bound "
             f"{disagg['decode_p99_interference_bound']}x); tok/s "
             f"retained {disagg['tokens_per_s_retained_x']}x (floor "
             f"{disagg['tokens_per_s_retained_floor']}x); hand-off "
             f"{disagg['handoff_ms']}ms median, "
             f"{disagg['handoffs_completed_total']} shipped; "
             f"token-exact "
             f"{disagg['streams_token_exact_split_vs_unified']}")
        _write_record(record, args.out)
        return

    if args.ctrlplane_only:
        repeats = max(args.repeats, 5)
        _log(f"ctrlplane leg only: wire storm + WAL recovery + gray "
             f"hedging, {repeats} paired runs each, gpt 2x64")
        ctrl = _ctrlplane_leg(args)
        record = {
            "metric": "fleet_serving_ctrlplane_durability",
            "unit": "ratio (storm/clean tok_s retained; hedge-off/on "
                    "interactive p99 TTFT); seconds (WAL recovery)",
            "config": {
                "model": "gpt 2x64 (vocab 64, max_len 128)",
                "process_replicas": 2,
                "wire_fault_rate_per_frame": 0.01,
                "transport": "PF1 length+CRC32+seq framing, dup "
                             "suppression, gap detection, bounded "
                             "resend (serve/fleet/transport.py)",
                "journal": "CRC-framed fsync-batched router WAL, "
                           "checkpoint+rotate cycle, mirror-replay "
                           "recovery (serve/fleet/journal.py)",
                "gray": "self-baseline latency-quantile detector, "
                        "first-result-wins interactive hedging "
                        "(serve/fleet/health.py GrayDetector)",
            },
            "provenance": provenance(repeats),
            "results": {"ctrlplane": ctrl},
            "device": jax.devices()[0].device_kind,
        }
        wire, rec, hedge = (ctrl["wire"], ctrl["recovery"],
                            ctrl["hedge"])
        _log(f"ctrlplane: wire retained "
             f"{wire['throughput_retained_x']}x at 1% frame faults "
             f"({wire['wire_crc_rejects_total']} CRC rejects, "
             f"{wire['wire_retries_total']} retries, token-exact "
             f"{wire['streams_token_exact']}); recovery "
             f"{rec['recovery_s']}s median "
             f"({rec['streams_revived_per_repeat']} streams, "
             f"token-exact {rec['streams_token_exact']}); hedging cut "
             f"interactive p99 TTFT {hedge['ttft_p99_hedge_off_s']}s "
             f"-> {hedge['ttft_p99_hedge_on_s']}s "
             f"({hedge['hedged_ttft_p99_reduction_x']}x, "
             f"{hedge['hedge_wins_total']} hedge wins)")
        _write_record(record, args.out)
        return

    if args.autoscale_only:
        _log(f"autoscale leg only: diurnal "
             f"{args.autoscale_duration:.0f}s trace, autoscale "
             f"{args.autoscale_min}..{args.autoscale_max} vs static "
             f"{{{args.autoscale_static}}}, 4 slots/replica")
        auto = _autoscale_leg(args)
        record = {
            "metric": "fleet_serving_elastic_autoscale",
            "unit": "goodput tokens per replica-hour (finished tokens "
                    "over integrated replica-hours, spawning included)",
            "config": {
                "model": "gpt 6x192 (vocab 64, max_len 128)",
                "slots_per_replica": 4,
                "autoscale_min": args.autoscale_min,
                "autoscale_max": args.autoscale_max,
                "static_sweep": args.autoscale_static,
                "offered_mean_x_capacity": args.autoscale_offered,
                "peak_to_trough": args.autoscale_peak_trough,
                "duration_s": args.autoscale_duration,
                "attainment_floor": args.autoscale_attainment_floor,
                "controller": "hysteretic pressure+load bands, "
                              "concurrent wait_ready warm-start "
                              "scale-up, drain-snapshot live-migration "
                              "scale-down "
                              "(pddl_tpu/serve/fleet/autoscaler.py)",
                "admission": "overload detector + brownout ladder "
                             "armed on every fleet "
                             "(pddl_tpu/serve/fleet/admission.py)",
                "replay": "hint-honoring open-loop client "
                          "(pddl_tpu/serve/fleet/replay.py)",
            },
            "provenance": provenance(max(args.repeats, 5)),
            "results": {"autoscale": auto},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"autoscale: {auto['goodput_per_replica_hour']:,.0f} "
             f"goodput tok/replica-hour = "
             f"{auto['goodput_per_replica_hour_vs_best_static_x']}x "
             f"best static (N={auto['best_static_replicas']}); "
             f"scale_events >= {auto['scale_events']}/wave, migrated "
             f"{auto['migrated_zero_lost']} with "
             f"{auto['requests_lost_total']} lost; rung time "
             f"{auto['brownout_rung_time_autoscaled_s']}s vs "
             f"{auto['brownout_rung_time_static_under_s']}s "
             f"under-provisioned static")
        _write_record(record, args.out)
        return

    if args.slo_only:
        model_desc = (f"gpt {args.depth}x{args.embed_dim} "
                      f"(vocab {args.vocab}, max_len {args.max_len})")
        _log(f"slo leg only: {args.slo_requests} trace requests at "
             f"{args.slo_overload}x capacity, {args.slo_replicas} "
             f"process replicas x {args.slots} slots, {model_desc}")
        slo = _slo_leg(args, overload_x=args.slo_overload)
        record = {
            "metric": "online_serving_slo_overload",
            "unit": "ratio (interactive p99 TTFT overload/uncontended; "
                    "best_effort shed fraction)",
            "config": {
                "model": model_desc,
                "slots_per_replica": args.slots,
                "process_replicas": args.slo_replicas,
                "prefill_len": args.prefill_len,
                "overload_x_capacity": args.slo_overload,
                "scheduler": "priority classes + EDF + aging_s=3.0 + "
                             "best_effort preemption, "
                             "prefill_slice_tokens=2*prefill_len "
                             "(pddl_tpu/serve/scheduler.py)",
                "admission": "per-priority token buckets + overload "
                             "detector + hysteretic brownout ladder "
                             "(pddl_tpu/serve/fleet/admission.py)",
            },
            "provenance": provenance(args.repeats),
            "results": {"slo": slo},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"slo: interactive p99 "
             f"{slo['uncontended_interactive_ttft_p99_s']}s -> "
             f"{slo['overload_interactive_ttft_p99_s']}s at "
             f"{args.slo_overload}x "
             f"({slo['interactive_ttft_p99_overload_over_uncontended_x']}"
             f"x, bound {slo['interactive_ttft_ratio_bound']}x); "
             f"best_effort absorbed "
             f"{slo['best_effort_shed_absorbed_frac']:.0%} of sheds "
             f"(bound 80%); lost/hung "
             f"{slo['requests_lost_or_hung_total']}")
        _write_record(record, args.out)
        return

    if args.fleet_only:
        replica_counts = [int(n) for n in
                          args.fleet_replicas.split(",") if n]
        kill_ns = [k for k in (2, 4) if k in replica_counts]
        _log(f"fleet leg only: N in {replica_counts}, "
             f"{args.slots} slots/replica, Poisson at "
             f"{args.fleet_load:.0%} of N x r08 baseline, kill leg at "
             f"N in {kill_ns or '(none: no N in {2, 4} requested)'}")
        fleet_results = _fleet_leg(args, replica_counts,
                                   load_frac=args.fleet_load)
        record = {
            "metric": "fleet_serving_scaling_and_failover",
            "unit": "tokens/sec aggregate (fleet, process replicas)",
            "config": {
                "model": (f"gpt {args.depth}x{args.embed_dim} "
                          f"(vocab {args.vocab}, max_len "
                          f"{args.max_len})"),
                "slots_per_replica": args.slots,
                "prefill_len": args.prefill_len,
                "prompt_len": args.prompt_len,
                "new_tokens": args.new_tokens,
                "fleet_load_fraction": args.fleet_load,
                "router": "prefix-affinity + rendezvous hash + sticky "
                          "sessions; per-replica circuit breaker; "
                          "drain-format live migration with "
                          "replay-mirror fallback "
                          "(pddl_tpu/serve/fleet/)",
            },
            "provenance": provenance(args.repeats),
            "results": {"fleet": fleet_results},
            "device": jax.devices()[0].device_kind,
        }
        _write_record(record, args.out)
        return

    model = GPT(vocab_size=args.vocab, max_len=args.max_len,
                embed_dim=args.embed_dim, depth=args.depth,
                num_heads=args.heads, attention="reference")
    dummy = jnp.ones((1, args.prompt_len), jnp.int32)
    params = model.init(jax.random.key(0), dummy, train=False)["params"]
    variables = {"params": params}
    model_desc = (f"gpt {args.depth}x{args.embed_dim} "
                  f"(vocab {args.vocab}, max_len {args.max_len})")

    if args.spec_only:
        k_values = [int(k) for k in args.spec_k_curve.split(",") if k]
        # A dedicated small serving model (the r16 sized-worker
        # discipline): speculation converts per-tick FIXED cost into
        # extra tokens, which is the accelerator regime — decode is
        # memory-bound there, so a k+1-wide verify is near-free — and
        # on XLA-CPU, where per-op compute scales with width, the
        # regime only exists while the model's per-token compute stays
        # small against the tick overhead. 2x64 keeps the bench in
        # that regime at real batch; long 256-token decodes amortize
        # each stream's pre-loop transient, where the n-gram drafter
        # has no self-similarity to mine yet.
        spec_model = GPT(vocab_size=64, max_len=512, embed_dim=64,
                         depth=2, num_heads=4, attention="reference")
        sdummy = jnp.ones((1, 32), jnp.int32)
        sparams = spec_model.init(jax.random.key(0), sdummy,
                                  train=False)["params"]
        spec_desc = "gpt 2x64 (vocab 64, max_len 512)"
        spec_slots, spec_reqs, spec_new = 4, 8, 256
        _log(f"spec leg only: {spec_reqs} requests x {spec_new} tokens "
             f"through {spec_slots} slots, k={args.spec_k} (curve "
             f"{k_values}), {spec_desc}")
        spec = _spec_leg(
            spec_model, {"params": sparams}, n_requests=spec_reqs,
            prompt_len=args.prompt_len, new_tokens=spec_new,
            slots=spec_slots, prefill_len=args.prefill_len,
            spec_k=args.spec_k, k_values=k_values, vocab=64,
            repeats=max(args.repeats, 5))
        record = {
            "metric": "online_serving_speculative",
            "unit": "tokens/sec aggregate (spec vs plain engine, "
                    "paired runs, matched batch)",
            "config": {
                "model": spec_desc,
                "slots": spec_slots,
                "prefill_len": args.prefill_len,
                "prompt_len": args.prompt_len,
                "new_tokens": spec_new,
                "n_requests": spec_reqs,
                "spec_k": args.spec_k,
                "drafter": "shared n-gram prompt-lookup "
                           "(models/speculative.ngram_drafts), "
                           "zero extra weights",
                "spec": "per-slot draft/verify in the fused tick: one "
                        "[S, k+1] wide-logits verify dispatch, "
                        "accepted length a runtime [S] array "
                        "(serve/engine.py spec_k)",
            },
            "provenance": provenance(max(args.repeats, 5)),
            "results": {"spec": spec},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"spec: {spec['spec_tok_s']:,.0f} tok/s vs "
             f"{spec['baseline_tok_s']:,.0f} plain = "
             f"{spec['spec_speedup_x']}x at k={args.spec_k} (pairs "
             f"{spec['spec_speedup_per_pair']}), acceptance "
             f"{spec['acceptance_rate']:.2f}, "
             f"{spec['tokens_per_tick']} tok/tick; chaos "
             f"{spec['chaos']['requests_token_exact']} requests "
             f"token-exact ({spec['chaos']['replays']} replays, "
             f"{spec['chaos']['requests_migrated']} migrated)")
        _write_record(record, args.out)
        return

    if args.tier_only:
        mults = tuple(int(m) for m in args.tier_mults.split(",") if m)
        # The TTFT curve runs on the DEFAULT 4x256 model (the tier's
        # lever is prefill compute avoided — see _tier_leg's sizing
        # note); the fleet duplicate-prefill leg is a token-COUNT
        # proof, so a small model keeps its 2 replicas cheap.
        fleet_model = GPT(vocab_size=64, max_len=128, embed_dim=64,
                          depth=2, num_heads=4, attention="reference")
        fdummy = jnp.ones((1, 32), jnp.int32)
        fparams = fleet_model.init(jax.random.key(0), fdummy,
                                   train=False)["params"]
        _log(f"tier leg only: Zipf working sets {list(mults)}x a "
             f"2-prompt device pool, tiered vs evict-and-recompute, "
             f"{model_desc}; + 2-replica chain-pull leg (gpt 2x64)")
        repeats = max(args.repeats, 5)
        tier = _tier_leg(model, variables, repeats=repeats, mults=mults)
        fleet = _tier_fleet_leg(fleet_model, {"params": fparams},
                                repeats=repeats)
        record = {
            "metric": "online_serving_tiered_kv",
            "unit": "ratio (tiered/evict mean TTFT at matched traces; "
                    "duplicate prefill tokens, blind vs pulled)",
            "config": {
                "model": model_desc,
                "slots": 2,
                "prefill_len": 384,
                "prompt_len": tier["prompt_len"],
                "device_pool_blocks": tier["device_pool_blocks"],
                "zipf_a": tier["zipf_a"],
                "working_set_mults": list(mults),
                "tier": "byte-budgeted pinned-host spill tier under "
                        "the radix index; eviction demotes D2H, "
                        "admission promotes via host_promote "
                        "(serve/kvcache/hosttier.py)",
                "fleet": "2 LocalReplica + shadow host tier + "
                         "chain_pull_blocks=2 (drain-module chain "
                         "wire format)",
            },
            "provenance": provenance(repeats),
            "results": {"tier": tier, "fleet": fleet},
            "device": jax.devices()[0].device_kind,
        }
        at8 = next((c for c in tier["curve"]
                    if c["working_set_x"] == 8), None)
        head = (f"mean-TTFT tiered/evict "
                f"{at8['ttft_tiered_over_evict_x']}x at the 8x working "
                f"set, hit rate {at8['hit_rate_tiered']} vs "
                f"{at8['hit_rate_evict']}" if at8 is not None
                else "custom sweep (no 8x point)")
        _log(f"tier: {head} (curve "
             f"{[(c['working_set_x'], c['ttft_tiered_over_evict_x']) for c in tier['curve']]}, "
             f"all pairs directional: {tier['all_pairs_directional']}); "
             f"fleet duplicate prefill "
             f"{fleet['duplicate_prefill_tokens_blind']} -> "
             f"{fleet['duplicate_prefill_tokens_pulled']} tokens "
             f"({fleet['chain_pulls']} pulls)")
        _write_record(record, args.out)
        return

    if args.tenant_only:
        _log(f"tenant leg only: {2 * args.concurrent} requests over "
             f"{args.tenant_adapters} adapters + constrained mix, "
             f"{args.slots} slots, {model_desc}")
        tenant = _tenant_leg(
            model, variables, n_requests=2 * args.concurrent,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens,
            slots=args.slots, prefill_len=args.prefill_len,
            n_adapters=args.tenant_adapters, vocab=args.vocab,
            repeats=args.repeats)
        record = {
            "metric": "online_serving_multi_tenant",
            "unit": "ratio (N merged copies / base+pool bytes; "
                    "tenant/plain tok_s; unconstrained/constrained "
                    "tok_s)",
            "config": {
                "model": model_desc,
                "slots": args.slots,
                "prefill_len": args.prefill_len,
                "prompt_len": args.prompt_len,
                "new_tokens": args.new_tokens,
                "n_adapters": args.tenant_adapters,
                "tenant": "paged per-request LoRA adapters (LM-head "
                          "target, rank-8 pool, pin-on-admit/LRU) + "
                          "grammar token-mask decoding "
                          "(serve/tenant/, ops/lora.py)",
            },
            "provenance": provenance(args.repeats),
            "results": {"tenant": tenant},
            "device": jax.devices()[0].device_kind,
        }
        _log(f"tenant: {args.tenant_adapters} adapters from one base "
             f"copy = {tenant['merged_copy_eliminated_x']}x merged-copy "
             f"elimination ({tenant['adapter_pool_bytes']} pool bytes "
             f"vs {tenant['base_params_bytes']} per copy); mixed-tenant "
             f"{tenant['mixed_tenant_tok_s']} tok/s = "
             f"{tenant['tenant_throughput_retained_x']}x the plain "
             f"engine; constrained decode mask overhead "
             f"{tenant['mask_overhead_x']}x")
        _write_record(record, args.out)
        return

    if args.obs_only:
        _log(f"observability leg only: {2 * args.concurrent} requests "
             f"x {args.new_tokens} tokens, tracing off vs on, "
             f"{model_desc}")
        obs = _obs_leg(
            model, variables, n_requests=2 * args.concurrent,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens,
            slots=args.slots, prefill_len=args.prefill_len,
            vocab=args.vocab, repeats=args.repeats)
        record = {
            "metric": "online_serving_observability_overhead",
            "unit": "ratio (tracing on / off, paired runs)",
            "config": {
                "model": model_desc,
                "slots": args.slots,
                "prefill_len": args.prefill_len,
                "prompt_len": args.prompt_len,
                "observability": "per-request spans (obs/trace.py) -> "
                                 "JSONL sink (obs/export.py); per-tick "
                                 "telemetry ring always on "
                                 "(obs/ring.py)",
            },
            "provenance": provenance(args.repeats),
            "results": {"obs": obs},
            "device": jax.devices()[0].device_kind,
        }
        _log_obs_leg(obs)
        _maybe_write_trace(args, model, variables)
        _write_record(record, args.out)
        return

    if args.faults_only:
        _log(f"fault leg only: {2 * args.concurrent} requests x "
             f"{args.new_tokens} tokens at {args.fault_rate:.1%} "
             f"injected faults, {model_desc}")
        faults = _fault_leg(
            model, variables, n_requests=2 * args.concurrent,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens,
            slots=args.slots, prefill_len=args.prefill_len,
            fault_rate=args.fault_rate, vocab=args.vocab,
            repeats=args.repeats)
        record = {
            "metric": "online_serving_fault_tolerance",
            "unit": "ratio (faulted / clean, paired runs)",
            "config": {
                "model": model_desc,
                "slots": args.slots,
                "prefill_len": args.prefill_len,
                "prompt_len": args.prompt_len,
                "recovery": "retry (bounded exp backoff) + replay "
                            "(prompt re-prefill, tokens re-fed) + "
                            "degraded prefix cache on OOM",
            },
            "provenance": provenance(args.repeats),
            "results": {"faults": faults},
            "device": jax.devices()[0].device_kind,
        }
        _log_fault_leg(faults)
        _maybe_write_trace(args, model, variables)
        _write_record(record, args.out)
        return

    prompts = _make_requests(args.concurrent, args.prompt_len,
                             args.new_tokens, args.vocab)
    _log(f"head-to-head: {args.concurrent} requests x "
         f"{args.new_tokens} tokens, {model_desc}")
    seq_tps, seq_spread = _sequential_baseline(
        model, variables, prompts, args.new_tokens, repeats=args.repeats)
    eng_tps, eng_spread, eng = _engine_concurrent(
        model, variables, prompts, args.new_tokens, args.slots,
        args.prefill_len, repeats=args.repeats)
    counts = eng.compile_counts()
    speedup = eng_tps / seq_tps
    _log(f"sequential generate(): {seq_tps:,.0f} tok/s (spread "
         f"{seq_spread:.1f}%); engine ({args.slots} slots): "
         f"{eng_tps:,.0f} tok/s (spread {eng_spread:.1f}%, "
         f"{speedup:.2f}x); compile counts {counts}")

    # Offered loads relative to the measured closed-loop capacity:
    # comfortable, busy, oversaturated (the admission-control point).
    cap_rps = eng_tps / args.new_tokens
    record = {
        "metric": "online_serving_tokens_per_sec",
        "unit": "tokens/sec/chip",
        "config": {
            "model": model_desc,
            "slots": args.slots,
            "prefill_len": args.prefill_len,
            "prompt_len": args.prompt_len,
            "new_tokens": args.new_tokens,
            "concurrent": args.concurrent,
            "poisson_requests_per_load": args.poisson_requests,
            "max_queue_depth": args.max_queue_depth,
            "scheduler": "FCFS, prefill-token budget, typed QueueFull "
                         "shedding",
        },
        "provenance": provenance(args.repeats),
        "results": {
            "concurrent_sequential_tokens_per_s": round(seq_tps, 1),
            "concurrent_sequential_spread_pct": round(seq_spread, 2),
            "concurrent_engine_tokens_per_s": round(eng_tps, 1),
            "concurrent_engine_spread_pct": round(eng_spread, 2),
            "concurrent_speedup": round(speedup, 3),
            "engine_compile_counts_after_run": counts,
            # Tail latencies for the head-to-head engine run, not just
            # the throughput headline.
            "serve_metrics_snapshot": eng.metrics.snapshot(),
            "poisson": [],
        },
        "device": jax.devices()[0].device_kind,
    }

    prefix = _prefix_ttft_leg(
        model, variables, n_requests=args.prefix_requests,
        prompt_len=args.prefix_prompt_len,
        shared_frac=args.prefix_shared_frac,
        new_tokens=args.prefix_new_tokens, slots=args.prefix_requests,
        prefill_len=max(args.prefill_len, args.prefix_prompt_len),
        block_size=args.prefix_block_size, chunk=args.prefix_chunk,
        vocab=args.vocab, repeats=args.repeats)
    record["results"]["prefix"] = prefix
    _log(f"shared-prefix x{args.prefix_shared_frac}: mean TTFT "
         f"{prefix['mean_ttft_prefix_off_s']}s off -> "
         f"{prefix['mean_ttft_prefix_on_s']}s on "
         f"({prefix['ttft_reduction_x']}x, hit rate "
         f"{prefix['prefix_hit_rate']}, saved "
         f"{prefix['prefill_tokens_saved']} prefill tokens)")

    if args.fault_rate > 0:
        faults = _fault_leg(
            model, variables, n_requests=2 * args.concurrent,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens,
            slots=args.slots, prefill_len=args.prefill_len,
            fault_rate=args.fault_rate, vocab=args.vocab,
            repeats=args.repeats)
        record["results"]["faults"] = faults
        _log_fault_leg(faults)

    obs = _obs_leg(
        model, variables, n_requests=2 * args.concurrent,
        prompt_len=args.prompt_len, new_tokens=args.new_tokens,
        slots=args.slots, prefill_len=args.prefill_len,
        vocab=args.vocab, repeats=args.repeats)
    record["results"]["obs"] = obs
    _log_obs_leg(obs)
    _maybe_write_trace(args, model, variables)

    for frac in (() if args.skip_poisson else (0.3, 0.6, 1.2)):
        res = _poisson_load(
            model, variables, offered_rps=frac * cap_rps,
            n_requests=args.poisson_requests,
            prompt_len=args.prompt_len, new_tokens=args.new_tokens,
            vocab=args.vocab, slots=args.slots,
            prefill_len=args.prefill_len,
            max_queue_depth=args.max_queue_depth, seed=int(frac * 100))
        res["offered_fraction_of_capacity"] = frac
        record["results"]["poisson"].append(res)
        _log(f"poisson x{frac}: offered {res['offered_tokens_per_s']} "
             f"tok/s -> served {res['tokens_per_s']} tok/s, TTFT p50 "
             f"{res['ttft_p50_s']}s p99 {res['ttft_p99_s']}s, queue "
             f"{res['mean_queue_depth']}, occupancy "
             f"{res['mean_slot_occupancy']}, rejected "
             f"{res['requests_rejected_queue_full']}")

    _write_record(record, args.out)


if __name__ == "__main__":
    main()
