"""Speculative serving (ISSUE 12): per-slot draft/verify in the fused
tick, CPU.

The contracts under test:

- **Token-exactness**: a ``spec_k > 0`` engine's greedy streams are
  IDENTICAL to the one-shot ``generate()`` oracle — for GPT, Llama,
  int8, both engine modes, cold and prefix-hit admissions, and with a
  draft model riding the paged pool. Acceptance changes only speed.
- **Zero recompiles over mixed accept counts**: speculative + sampled
  + grammar-constrained + multi-adapter slots in ONE tick, accepted
  lengths all over the map, and the compiled set never grows — the
  accepted-length ``[S]`` array is runtime data like the masks and
  adapter ids before it.
- **Chaos** (`@pytest.mark.chaos`): seeded faults at the new
  draft/verify/draft_prefill sites (and everywhere else) leave every
  request terminal and every survivor token-exact; a draft fault is
  NEVER fatal (fallback drafts); replay re-feeds ride the verify
  window ``spec_k+1`` known tokens at a time.
- **Drain v5 / migration**: snapshots carry per-stream speculative
  accounting, restore token-exactly into speculative AND classic
  engines (v1–v4 still restore), and a mid-speculation stream
  live-migrates across a fleet kill token-exactly.
- **Budget contract**: token-budget accounting charges ACCEPTED, not
  drafted, tokens (`scheduler.admit`).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import generate, tiny_gpt
from pddl_tpu.models.llama import tiny_llama
from pddl_tpu.models.speculative import ngram_drafts
from pddl_tpu.obs import RequestTracer
from pddl_tpu.obs.export import parse_prometheus_text, serve_exposition
from pddl_tpu.serve import (
    FaultKind,
    FaultPlan,
    FaultSpec,
    FinishReason,
    KillPoint,
    Priority,
    RequestState,
    ServeEngine,
)
from pddl_tpu.serve import drain as drain_io
from pddl_tpu.serve.fleet import FleetRouter, LocalReplica
from pddl_tpu.serve.request import Request, RequestHandle, SamplingParams
from pddl_tpu.serve.tenant import AdapterRegistry, TenantConfig
from conftest import ref_greedy as _ref_greedy

pytestmark = pytest.mark.spec

_no_sleep = lambda s: None  # noqa: E731

VOCAB32 = (list("0123456789") + list('{}[]":,.-') + ["true", "false"]
           + list("abcdefghijk"))


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


@pytest.fixture(scope="module")
def llama_setup():
    model = tiny_llama(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(1), prompt, train=False)["params"]
    return model, {"params": params}


@pytest.fixture(scope="module")
def draft_setup():
    """A smaller, differently-seeded draft model over the same vocab —
    its guesses genuinely disagree with the target (acceptance is a
    property of the pair, exactness never is)."""
    model = tiny_gpt(vocab_size=32, max_len=64, depth=1)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(9), prompt, train=False)["params"]
    return model, {"params": params}


_WORKLOAD = [((np.arange(9) * 5 + 1) % 32, 9),
             ((np.arange(12) * 3 + 7) % 32, 6),
             ((np.arange(9) * 5 + 1) % 32, 5),   # shared prefix with #0
             ((np.arange(6) + 17) % 32, 8),
             ((np.arange(14) * 7 + 2) % 32, 4)]


@pytest.fixture(scope="module")
def workload_refs(gpt_setup):
    model, variables = gpt_setup
    return [_ref_greedy(model, variables, p, n) for p, n in _WORKLOAD]


def _spec_engine(model, variables, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_len", 16)
    kw.setdefault("spec_k", 3)
    return ServeEngine(model, variables, **kw)


# ------------------------------------------------------- shared drafter
def test_ngram_drafts_one_definition_and_equivalence():
    """Satellite: the serving drafter IS the one-shot drafter — one
    imported definition — and the per-row vector form reproduces the
    historical scalar form bit-for-bit on identical token histories."""
    import pddl_tpu.models.speculative as spec_mod
    import pddl_tpu.serve.engine as engine_mod

    assert engine_mod.ngram_drafts is spec_mod.ngram_drafts
    assert spec_mod._ngram_drafts is spec_mod.ngram_drafts
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 7, size=(3, 40)), jnp.int32)
    for cur_pos in (5, 17, 33):
        scalar = ngram_drafts(toks, jnp.int32(cur_pos), 3, 4)
        vector = ngram_drafts(
            toks, jnp.full((3,), cur_pos, jnp.int32), 3, 4)
        np.testing.assert_array_equal(np.asarray(scalar),
                                      np.asarray(vector))
    # Mixed per-row positions: each row matches its own scalar run.
    pos = jnp.asarray([5, 17, 33], jnp.int32)
    mixed = np.asarray(ngram_drafts(toks, pos, 3, 4))
    for r, p in enumerate((5, 17, 33)):
        solo = np.asarray(ngram_drafts(toks, jnp.int32(p), 3, 4))
        np.testing.assert_array_equal(mixed[r], solo[r])


# ----------------------------------------------------- token exactness
def test_spec_token_exact_gpt(gpt_setup, workload_refs,
                              pin_zero_recompiles):
    """Cold + shared-prefix admissions through the speculative engine:
    every greedy stream identical to generate(), more than one token
    per verify window actually accepted, zero recompiles over the
    mixed accept counts."""
    model, variables = gpt_setup
    eng = pin_zero_recompiles(
        _spec_engine(model, variables, max_slots=3))
    handles = [eng.submit(p, n) for p, n in _WORKLOAD]
    eng.run(max_steps=400)
    for h, ref in zip(handles, workload_refs):
        assert h.tokens == ref
    snap = eng.metrics.snapshot()
    assert snap["spec_ticks"] > 0
    assert snap["spec_drafted_tokens"] > 0
    total = sum(n for _, n in _WORKLOAD)
    # Speculation must have delivered: fewer verify windows than a
    # one-token tick would have needed is the whole point (loose bound
    # — acceptance on the untrained model is workload-dependent).
    assert snap["spec_accepted_tokens"] >= 1
    assert eng.metrics.tokens_emitted == total


def test_spec_token_exact_llama(llama_setup, pin_zero_recompiles):
    model, variables = llama_setup
    refs = [_ref_greedy(model, variables, p, n) for p, n in _WORKLOAD[:3]]
    eng = pin_zero_recompiles(
        _spec_engine(model, variables, max_slots=3))
    handles = [eng.submit(p, n) for p, n in _WORKLOAD[:3]]
    eng.run(max_steps=400)
    for h, ref in zip(handles, refs):
        assert h.tokens == ref


def test_spec_token_exact_int8(gpt_setup, pin_zero_recompiles):
    """int8 weight storage composes: the verify program dequantizes
    inside like every other compiled program."""
    from pddl_tpu.ops.quant import dequantize, quantize_int8

    model, variables = gpt_setup
    qparams = quantize_int8(variables["params"], min_elems=128)
    dense = {"params": dequantize(qparams)}
    p, n = _WORKLOAD[0]
    ref = _ref_greedy(model, dense, p, n)
    eng = pin_zero_recompiles(
        _spec_engine(model, {"params": qparams},
                     param_transform=dequantize))
    h = eng.submit(p, n)
    eng.run(max_steps=200)
    assert h.tokens == ref


def test_spec_draft_model_token_exact(gpt_setup, draft_setup,
                                      pin_zero_recompiles):
    """The draft model's KV rides the paged pool as a second cache tree
    (same blocks, same tables, same sharing): streams stay token-exact
    — including a repeat prompt whose blocks dedup-swap onto the stored
    chain — and the draft_prefill program compiles once."""
    model, variables = gpt_setup
    dmodel, dvars = draft_setup
    refs = [_ref_greedy(model, variables, p, n) for p, n in _WORKLOAD[:3]]
    eng = pin_zero_recompiles(
        _spec_engine(model, variables, max_slots=3,
                     spec_draft_model=dmodel, spec_draft_variables=dvars))
    assert eng.spec_draft_model_enabled
    handles = [eng.submit(p, n) for p, n in _WORKLOAD[:3]]
    eng.run(max_steps=400)
    for h, ref in zip(handles, refs):
        assert h.tokens == ref
    assert "draft_prefill" in eng.compile_counts()
    # A repeat of the shared prompt hits the radix chain (whose blocks
    # now hold BOTH trees' K/V) and still reproduces the oracle.
    again = eng.submit(_WORKLOAD[0][0], _WORKLOAD[0][1])
    eng.run(max_steps=200)
    assert again.tokens == refs[0]


def test_eos_mid_window_truncates_exactly(gpt_setup):
    """An eos accepted mid-window ends the stream exactly where the
    one-token engine would have: everything past it is discarded."""
    model, variables = gpt_setup
    p, n = _WORKLOAD[0][0], 12
    ref = _ref_greedy(model, variables, p, n)
    eos = ref[len(ref) // 2]  # a token the greedy stream really emits
    plain = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                        eos_token=eos)
    h0 = plain.submit(p, n)
    plain.run(max_steps=200)
    spec = _spec_engine(model, variables, eos_token=eos)
    h1 = spec.submit(p, n)
    spec.run(max_steps=200)
    assert h1.tokens == h0.tokens
    assert h1.finish_reason == h0.finish_reason == FinishReason.EOS


def test_sampled_constrained_stream_stays_mask_legal(gpt_setup):
    """A SAMPLED grammar-constrained stream on a speculative engine
    draws its one token per window under its FSM mask (review-found:
    an unmasked draw could emit an illegal token and crash the host
    FSM advance for every live stream). Every emitted token must be
    mask-legal and the stream must settle normally."""
    model, variables = gpt_setup
    from pddl_tpu.serve.tenant import compile_constraint

    tc = TenantConfig(registry=AdapterRegistry(model.embed_dim,
                                               model.vocab_size, rank=4),
                      token_strings=VOCAB32)
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      tenant=tc, spec_k=3)
    spec = {"kind": "regex", "pattern": r"-?\d+(\.\d+)?"}
    h = eng.submit(_WORKLOAD[0][0], 10, constraint=spec,
                   sampling=SamplingParams(temperature=1.0, top_k=8))
    greedy = eng.submit(_WORKLOAD[1][0], 10)  # a speculating neighbor
    eng.run(max_steps=300)
    assert h.done and greedy.done
    assert h.state == RequestState.FINISHED
    fsm = compile_constraint(spec, VOCAB32)
    state = fsm.start
    for tok in h.tokens:
        assert fsm.allow_row(state, None)[tok], \
            f"sampled constrained stream emitted illegal token {tok}"
        state = fsm.advance(state, tok)
        assert state >= 0


def test_sampled_rows_do_not_speculate(gpt_setup):
    """Sampled streams tick one exact token per window (cap 0): they
    finish, draw from the same batched sampler, and contribute nothing
    to the drafted/accepted series."""
    model, variables = gpt_setup
    eng = _spec_engine(model, variables, max_slots=2)
    hs = [eng.submit(p, n,
                     sampling=SamplingParams(temperature=1.0, top_k=8))
          for p, n in _WORKLOAD[:3]]
    eng.run(max_steps=400)
    assert all(h.done and len(h.tokens) == n
               for h, (_, n) in zip(hs, _WORKLOAD[:3]))
    assert eng.metrics.spec_drafted_tokens == 0
    assert eng.metrics.spec_ticks > 0


# -------------------------------------------- mixed batches, recompiles
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_mixed_batch_zero_recompiles(gpt_setup, llama_setup,
                                     pin_zero_recompiles, family):
    """The acceptance-criteria batch: speculative-greedy + sampled +
    grammar-constrained + two adapters live in ONE tick with mixed
    accept counts — zero recompiles in both engine modes for BOTH
    model families, and every deterministic stream equals its
    plain-engine twin."""
    model, variables = gpt_setup if family == "gpt" else llama_setup
    reg = AdapterRegistry(model.embed_dim, model.vocab_size, rank=4)
    reg.register_random("acme", seed=100, scale=0.1)
    reg.register_random("globex", seed=101, scale=0.1)
    constraint = {"kind": "regex", "pattern": r"-?\d+(\.\d+)?"}
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 32, size=ln).astype(np.int32)
               for ln in (5, 7, 6, 4)]

    def run(spec_k):
        tc = TenantConfig(registry=reg, token_strings=VOCAB32)
        eng = ServeEngine(model, variables, max_slots=4, prefill_len=16,
                          tenant=tc, spec_k=spec_k)
        eng.warmup()
        hs = [eng.submit(prompts[0], 10, constraint=constraint),
              eng.submit(prompts[1], 10, adapter="acme"),
              eng.submit(prompts[2], 10, adapter="globex",
                         constraint=constraint),
              eng.submit(prompts[3], 10,
                         sampling=SamplingParams(temperature=0.8,
                                                 top_k=4))]
        eng.run(max_steps=400)
        return hs, eng

    base, _ = run(0)
    spec, eng = run(3)
    pin_zero_recompiles(eng)  # counts already 1; pinned through teardown
    for i, (b, s) in enumerate(zip(base, spec)):
        assert s.done
        if i != 3:  # the sampled stream is distribution-, not bit-, pinned
            assert s.tokens == b.tokens, f"slot {i} diverged"
            assert s.finish_reason == b.finish_reason
    assert eng.metrics.spec_drafted_tokens > 0


# ----------------------------------------------------------- resilience
@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spec_chaos_matrix(gpt_setup, workload_refs, pin_zero_recompiles,
                           seed):
    """Seeded mixed chaos (transients, OOM, latency — the rate draws
    now also land on draft/verify/draft_prefill): no crash, every
    request terminal, survivors token-exact, zero recompiles across
    every recovery transition."""
    model, variables = gpt_setup
    plan = FaultPlan(seed=seed, sleep_fn=_no_sleep, transient_rate=0.05,
                     oom_rate=0.02, latency_rate=0.1, latency_s=1e-4,
                     max_random_injections=20)
    tracer = RequestTracer()
    eng = pin_zero_recompiles(
        _spec_engine(model, variables, fault_plan=plan,
                     backoff_sleep=_no_sleep, tracer=tracer))
    handles = [eng.submit(p, n) for p, n in _WORKLOAD]
    eng.run(max_steps=600)
    assert not eng.has_work, "engine failed to drain under chaos"
    for h, ref in zip(handles, workload_refs):
        assert h.done, f"request {h} never reached a terminal state"
        if h.state == RequestState.FINISHED:
            assert h.tokens == ref, \
                f"surviving stream diverged (seed {seed})"
    # Injections surfaced as trace events at matching coordinates.
    assert len(tracer.events_named("fault_injected")) \
        == plan.total_injected
    # Still serviceable after the storm.
    p, n = _WORKLOAD[0]
    again = eng.submit(p, n)
    eng.run(max_steps=100)
    assert again.tokens == workload_refs[0]


def test_verify_storm_replays_token_exact(gpt_setup,
                                          pin_zero_recompiles):
    """A transient burst at the VERIFY site past max_retries loses the
    live slots; replay rebuilds them token-exactly, re-feeding the
    emitted tokens through the verify window (multiple per tick)."""
    model, variables = gpt_setup
    p, n = _WORKLOAD[1]
    ref = _ref_greedy(model, variables, p, n)
    plan = FaultPlan(scheduled=[
        FaultSpec(step=3, site="verify", kind=FaultKind.TRANSIENT,
                  count=3)], sleep_fn=_no_sleep)
    eng = pin_zero_recompiles(
        _spec_engine(model, variables, fault_plan=plan, max_retries=1,
                     backoff_sleep=_no_sleep))
    h = eng.submit(p, n)
    eng.run(max_steps=300)
    assert h.tokens == ref
    assert eng.metrics.replays >= 1


def test_draft_fault_is_never_fatal(gpt_setup, pin_zero_recompiles):
    """A transient burst at the DRAFT site past max_retries falls back
    to repeat-last drafts: the stream neither replays nor diverges —
    drafting pays acceptance, never correctness."""
    model, variables = gpt_setup
    p, n = _WORKLOAD[0]
    ref = _ref_greedy(model, variables, p, n)
    plan = FaultPlan(scheduled=[
        FaultSpec(step=2, site="draft", kind=FaultKind.TRANSIENT,
                  count=4)], sleep_fn=_no_sleep)
    eng = pin_zero_recompiles(
        _spec_engine(model, variables, fault_plan=plan, max_retries=1,
                     backoff_sleep=_no_sleep))
    h = eng.submit(p, n)
    eng.run(max_steps=300)
    assert h.tokens == ref
    assert eng.metrics.replays == 0


def test_kill_mid_verify_drain_restore_token_exact(gpt_setup):
    """A hard kill-point at the verify site mid-stream, then
    drain/restore of the survivor state into a fresh speculative
    engine: streams resume token-exactly (the chaos matrix's
    preemption-mid-verify analogue at the hardest coordinate)."""
    model, variables = gpt_setup
    refs = [_ref_greedy(model, variables, p, n) for p, n in _WORKLOAD[:3]]
    plan = FaultPlan(scheduled=[
        FaultSpec(step=4, site="verify", kind=FaultKind.KILL)],
        sleep_fn=_no_sleep)
    eng = _spec_engine(model, variables, fault_plan=plan,
                       backoff_sleep=_no_sleep)
    handles = [eng.submit(p, n) for p, n in _WORKLOAD[:3]]
    with pytest.raises(KillPoint):
        eng.run(max_steps=300)
    snapshot = eng.drain()
    assert snapshot["version"] == 5
    eng2 = _spec_engine(model, variables)
    restored = eng2.restore(snapshot)
    eng2.run(max_steps=300)
    # Streams that FINISHED before the kill settled on the first
    # engine; everything else must finish token-exactly on the second.
    finished = {(tuple(h.request.prompt), h.request.max_new_tokens): h
                for h in [*handles, *restored] if h.done}
    for (p, n), ref in zip(_WORKLOAD[:3], refs):
        h = finished[(tuple(int(t) for t in p), n)]
        assert h.tokens == ref, "restored stream diverged"


def test_preempt_mid_speculation_token_exact(gpt_setup):
    """A best_effort stream parked mid-speculation for interactive
    work resumes token-exactly through the replay re-feed (spec_k+1
    known tokens per window)."""
    model, variables = gpt_setup
    p0, n0 = _WORKLOAD[1][0], 10
    p1, n1 = _WORKLOAD[3]
    ref0 = _ref_greedy(model, variables, p0, n0)
    ref1 = _ref_greedy(model, variables, p1, n1)
    eng = _spec_engine(model, variables, max_slots=1, preempt_cap=1)
    h0 = eng.submit(p0, n0, priority=Priority.BEST_EFFORT)
    for _ in range(2):
        eng.step()
    assert not h0.done
    h1 = eng.submit(p1, n1, priority=Priority.INTERACTIVE)
    eng.run(max_steps=300)
    assert eng.metrics.preemptions == 1
    assert h0.tokens == ref0 and h1.tokens == ref1


# ------------------------------------------------------ drain & compat
def test_drain_restore_v5_round_trip(gpt_setup):
    """Mid-flight drain: v5 snapshot carries the per-stream speculative
    accounting; restore is token-exact into a speculative engine of
    EITHER mode and into a classic (spec_k=0) engine."""
    model, variables = gpt_setup
    refs = [_ref_greedy(model, variables, p, n) for p, n in _WORKLOAD[:3]]
    eng = _spec_engine(model, variables)
    handles = [eng.submit(p, n) for p, n in _WORKLOAD[:3]]
    eng.step()  # one window each for the two slotted streams
    assert not any(h.done for h in handles)
    snapshot = eng.drain()
    assert snapshot["version"] == drain_io.SNAPSHOT_VERSION == 5
    assert snapshot["spec_k"] == 3
    entries = snapshot["requests"]
    assert len(entries) == 3
    assert all("spec" in e for e in entries)
    assert sum(e["spec"]["drafted"] for e in entries) \
        == eng.metrics.spec_drafted_tokens
    for spec_k in (3, 0):
        eng2 = ServeEngine(model, variables, max_slots=2,
                           prefill_len=16, spec_k=spec_k)
        restored = eng2.restore(snapshot)
        eng2.run(max_steps=300)
        done = {(tuple(h.request.prompt), h.request.max_new_tokens): h
                for h in restored}
        for (p, n), ref in zip(_WORKLOAD[:3], refs):
            h = done[(tuple(int(t) for t in p), n)]
            assert h.tokens == ref, f"diverged restoring into "\
                f"spec_k={spec_k}"
        if spec_k:
            # The migrated accounting continued, never reset.
            assert sum(h.spec_drafted for h in restored) \
                >= sum(e["spec"]["drafted"] for e in entries)


def test_v1_through_v4_snapshots_restore_into_spec_engine(gpt_setup,
                                                          tmp_path):
    """Back-compat both directions: pre-speculative snapshots (v1's
    bare entries through v4's tenant fields) restore token-exactly
    into a speculative engine — absent ``spec`` decodes to zeros — and
    future versions refuse loudly."""
    model, variables = gpt_setup
    p, n = _WORKLOAD[0]
    ref = _ref_greedy(model, variables, p, n)
    for version in (1, 4):
        entry = {"prompt": [int(t) for t in p], "max_new_tokens": n,
                 "tokens": ref[:2], "elapsed_s": 0.5}
        if version == 4:
            entry.update({"sampling": {"temperature": 0.0},
                          "priority": "interactive", "adapter": None,
                          "constraint": None, "ttft_s": 0.01,
                          "deadline_s": None})
        path = tmp_path / f"v{version}.json"
        path.write_text(json.dumps({"version": version,
                                    "requests": [entry]}))
        eng = _spec_engine(model, variables)
        restored = eng.restore(str(path))
        assert restored[0].spec_drafted == 0
        eng.run(max_steps=200)
        assert restored[0].tokens == ref, f"v{version} diverged"
    bad = tmp_path / "future.json"
    bad.write_text(json.dumps({"version": 99, "requests": []}))
    with pytest.raises(ValueError, match="unsupported"):
        drain_io.load_snapshot(str(bad))


@pytest.mark.chaos
@pytest.mark.fleet
def test_fleet_migration_mid_speculation_token_exact(gpt_setup,
                                                     pin_zero_recompiles):
    """Kill one of two SPECULATIVE replicas mid-stream (kill-point at
    its next verify): the dying replica's drain snapshot live-migrates
    its speculative streams onto the survivor, which resumes them
    token-exactly through the windowed replay re-feed."""
    model, variables = gpt_setup
    plans = [FaultPlan(sleep_fn=_no_sleep) for _ in range(2)]

    def factory(plan):
        def make():
            return _spec_engine(model, variables, fault_plan=plan,
                                backoff_sleep=_no_sleep)
        return make

    replicas = [LocalReplica(i, factory(plans[i])) for i in range(2)]
    fleet = pin_zero_recompiles(FleetRouter(
        replicas, affinity_block_size=8, affinity_blocks=1,
        respawn=False))
    reqs = [(p, n) for p, n in _WORKLOAD[:4]]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()
    victim = max(fleet.replicas, key=lambda s: s.load)
    assert victim.load > 0
    eng = victim.driver.engine
    plans[victim.replica_id]._sched[(eng._step_idx, "verify")] = \
        [FaultKind.KILL]
    fleet.run(max_steps=600)
    assert not fleet.has_work
    for h, ref in zip(handles, refs):
        assert h.done
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref, "migrated speculative stream diverged"
    assert fleet.metrics.requests_migrated >= 1


# ------------------------------------------------------ budget contract
def test_budget_charges_accepted_not_drafted(gpt_setup):
    """`scheduler.admit`'s speculative contract: a fresh admission
    costs EXACTLY what the classic engine charges (drafting never
    inflates the price or shrinks the admitted batch), and a replay's
    catch-up charge is its emitted token count — accepted, not the
    (spec_k+1)-wide drafted compute."""
    model, variables = gpt_setup
    budget = 14  # two of the 9/12-token prompts never fit in one step

    def admitted_first_step(spec_k):
        eng = ServeEngine(model, variables, max_slots=4, prefill_len=16,
                          prefill_token_budget=budget, spec_k=spec_k)
        eng.warmup()
        for p, n in _WORKLOAD[:4]:
            eng.submit(p, n)
        eng.step()
        return eng.live_slots

    assert admitted_first_step(3) == admitted_first_step(0)
    # Replay catch-up: charged at the emitted (accepted) token count.
    eng = _spec_engine(model, variables,
                       prefill_token_budget=budget)
    eng.warmup()
    handle = RequestHandle(
        Request(prompt=[1, 2, 3], max_new_tokens=8), arrival_s=0.0)
    fresh = eng._prefill_cost(handle)
    handle.tokens = [4, 5, 6, 7]
    assert eng._prefill_cost(handle) == fresh + len(handle.tokens)


# -------------------------------------------------------- observability
def test_spec_metrics_and_exposition(gpt_setup):
    """The acceptance-rate series surfaces in the snapshot and renders
    through the strict Prometheus referee; the engine gauges carry the
    draft config."""
    model, variables = gpt_setup
    eng = _spec_engine(model, variables)
    hs = [eng.submit(p, n) for p, n in _WORKLOAD[:2]]
    eng.run(max_steps=300)
    assert all(h.done for h in hs)
    snap = eng.metrics.snapshot()
    assert snap["spec_ticks"] > 0
    assert snap["spec_drafted_tokens"] > 0
    assert snap["spec_acceptance_rate"] == pytest.approx(
        snap["spec_accepted_tokens"] / snap["spec_drafted_tokens"])
    samples, types = parse_prometheus_text(
        serve_exposition(eng.metrics, eng))
    assert types["pddl_serve_spec_ticks_total"] == "counter"
    assert types["pddl_serve_spec_acceptance_rate"] == "gauge"
    assert samples[("pddl_serve_engine_spec_k", ())] == 3.0
    assert ("pddl_serve_engine_compile_counts",
            (("key", "verify"),)) in samples


def test_spec_validation(gpt_setup, draft_setup):
    model, variables = gpt_setup
    dmodel, dvars = draft_setup
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(model, variables, spec_k=-1)
    with pytest.raises(ValueError, match="spec_k >= 1"):
        ServeEngine(model, variables,
                    spec_draft_model=dmodel, spec_draft_variables=dvars)
    with pytest.raises(ValueError, match="spec_draft_variables"):
        ServeEngine(model, variables, spec_k=2,
                    spec_draft_model=dmodel)
    big = tiny_gpt(vocab_size=64, max_len=64)
    with pytest.raises(ValueError, match="vocab"):
        ServeEngine(model, variables, spec_k=2,
                    spec_draft_model=big, spec_draft_variables=dvars)
