"""Fault tolerance of the serving engine (`pddl_tpu/serve/faults.py`,
engine retry/replay/degraded/drain paths), CPU.

The contracts under test:

- **Chaos matrix** (seeds x fault kinds, `@pytest.mark.chaos`): under
  seeded injection of transient errors, RESOURCE_EXHAUSTED, and latency
  spikes, the engine never crashes, every admitted request reaches a
  terminal state, every SURVIVING (FINISHED) request's stream is
  token-identical to the fault-free run, and zero recompiles after
  warmup still holds across retry/replay/degraded transitions.
- **Retry**: a transient burst within the retry budget recovers in
  place — same tokens, no replay.
- **Replay**: a burst past the budget declares the slot KV lost; the
  request is rebuilt token-exactly from prompt + emitted tokens via the
  normal admission path plus re-fed ticks (no new compiled program).
- **Failure isolation**: a request that outlives ``max_replays`` ends
  FAILED/``FinishReason.ERROR``; the engine itself keeps serving.
- **Degraded mode**: an OOM flushes unpinned prefix blocks, turns
  donations off, keeps serving on the cold path, and re-arms after the
  cool-down — all token-exact.
- **Drain/restore**: SIGTERM (and even a hard kill-point mid-step)
  snapshots queued + running requests; a fresh engine restores and
  resumes each stream token-exactly.
- **Refcount hygiene**: storms of cancelled/faulted/deadline admissions
  leave the radix index at its refcount baseline (no pinned-chain
  leak).
"""

import signal
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import generate, tiny_gpt
from pddl_tpu.obs import RequestTracer
from pddl_tpu.serve import (
    FaultKind,
    FaultPlan,
    FaultSpec,
    FinishReason,
    KillPoint,
    Priority,
    QueueFull,
    RequestState,
    ServeEngine,
)
from pddl_tpu.serve.scheduler import FCFSScheduler
from pddl_tpu.serve.request import Request, RequestHandle
from conftest import (
    FakeClock as _FakeClock,
    assert_pool_idle,
    ref_greedy as _ref_greedy,
    refcount_baseline as _refcount_baseline,
)


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


_WORKLOAD = [((np.arange(9) * 5 + 1) % 32, 6),
             ((np.arange(12) * 3 + 7) % 32, 5),
             ((np.arange(9) * 5 + 1) % 32, 4),   # shared prefix with #0
             ((np.arange(6) + 17) % 32, 7),
             ((np.arange(14) * 7 + 2) % 32, 3)]


@pytest.fixture(scope="module")
def workload_refs(gpt_setup):
    model, variables = gpt_setup
    return [_ref_greedy(model, variables, p, n) for p, n in _WORKLOAD]


def _next_step(eng):
    """The (step, site) coordinate the engine's NEXT step() will use."""
    return eng._step_idx


# ------------------------------------------------------------ chaos matrix
_PROFILES = {
    "transient": dict(transient_rate=0.08, max_random_injections=12),
    "oom": dict(oom_rate=0.05, max_random_injections=6),
    "latency": dict(latency_rate=0.25, latency_s=1e-4,
                    max_random_injections=30),
    "mixed": dict(transient_rate=0.05, oom_rate=0.02, latency_rate=0.1,
                  latency_s=1e-4, max_random_injections=20),
}


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("profile", sorted(_PROFILES))
def test_chaos_matrix(gpt_setup, workload_refs, pin_zero_recompiles,
                      seed, profile):
    """Seeded chaos: no crash, every request terminal, survivors
    token-identical to the fault-free run, zero recompiles throughout —
    with per-request tracing ON across the whole matrix, and every
    injected fault (LATENCY included, which raises nothing) surfacing
    as a trace event whose (step, site) coordinates the retry events
    then match. (The seed-0 column doubles as the tier-1 smoke; the
    whole matrix is fast enough to stay un-`slow`.)"""
    model, variables = gpt_setup
    plan = FaultPlan(seed=seed, sleep_fn=_no_sleep, **_PROFILES[profile])
    tracer = RequestTracer()
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16,
        fault_plan=plan, backoff_sleep=_no_sleep, tracer=tracer))
    handles = [eng.submit(p, n) for p, n in _WORKLOAD]
    eng.run(max_steps=600)
    assert not eng.has_work, "engine failed to drain under chaos"
    for h, ref in zip(handles, workload_refs):
        assert h.done, f"request {h} never reached a terminal state"
        if h.state == RequestState.FINISHED:
            assert h.tokens == ref, \
                f"surviving stream diverged under {profile}/seed {seed}"
    # Observability contract under chaos: injections and recoveries
    # land in the trace with coordinates that line up.
    injected_evs = tracer.events_named("fault_injected")
    assert len(injected_evs) == plan.total_injected
    by_kind = {}
    for ev in injected_evs:
        by_kind[ev["kind"]] = by_kind.get(ev["kind"], 0) + 1
    assert by_kind == {k.value: v for k, v in plan.injected.items() if v}
    injected_coords = {(e["step"], e["site"]) for e in injected_evs}
    retry_evs = tracer.events_named("retry")
    assert len(retry_evs) == eng.metrics.retries
    for ev in retry_evs:
        assert (ev["step"], ev["site"]) in injected_coords, \
            f"retry at uninjected coordinate {(ev['step'], ev['site'])}"
    assert len(tracer.events_named("replay")) \
        == eng.metrics.replays + eng.metrics.requests_failed
    assert len(tracer.events_named("degraded_entry")) \
        == eng.metrics.degraded_entries
    # Every request's span settled with its terminal reason.
    assert tracer.spans_finished >= len(handles)
    assert not tracer.active
    # The engine is still serviceable after the storm (plan exhausted
    # its injection cap, so this completes clean).
    p, n = _WORKLOAD[0]
    again = eng.submit(p, n)
    eng.run(max_steps=100)
    assert again.tokens == workload_refs[0]


# -------------------------------------------------------- targeted faults
def test_transient_tick_retry_recovers_in_place(gpt_setup,
                                                pin_zero_recompiles):
    """A transient burst within max_retries recovers inside the retry
    loop: same stream, retries counted, no replay charged."""
    model, variables = gpt_setup
    p, n = (np.arange(7) * 4 + 3) % 32, 6
    ref = _ref_greedy(model, variables, p, n)
    plan = FaultPlan(scheduled=[FaultSpec(step=2, site="tick",
                                          kind=FaultKind.TRANSIENT,
                                          count=2)])
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16, fault_plan=plan,
        max_retries=3, backoff_sleep=_no_sleep))
    h = eng.submit(p, n)
    eng.run(max_steps=100)
    assert h.state == RequestState.FINISHED
    assert h.tokens == ref
    assert eng.metrics.retries == 2
    assert eng.metrics.retry_sites == {"tick": 2}
    assert eng.metrics.replays == 0


def test_scheduled_fault_surfaces_in_trace_at_exact_coordinates(
        gpt_setup):
    """A surgical FaultSpec at (step=2, site="tick", count=2): the
    trace must carry exactly two fault_injected and two retry events at
    that coordinate — the span-event/(step, site) contract the runbook's
    replay-storm diagnosis relies on — and the recovering request's
    span must record its replay-free finish."""
    model, variables = gpt_setup
    p, n = (np.arange(7) * 4 + 3) % 32, 6
    plan = FaultPlan(scheduled=[FaultSpec(step=2, site="tick",
                                          kind=FaultKind.TRANSIENT,
                                          count=2)])
    tracer = RequestTracer()
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      fault_plan=plan, max_retries=3,
                      backoff_sleep=_no_sleep, tracer=tracer)
    h = eng.submit(p, n)
    eng.run(max_steps=100)
    assert h.state == RequestState.FINISHED
    injected = tracer.events_named("fault_injected")
    assert [(e["step"], e["site"], e["kind"]) for e in injected] \
        == [(2, "tick", "transient")] * 2
    retries = tracer.events_named("retry")
    assert [(e["step"], e["site"]) for e in retries] == [(2, "tick")] * 2
    assert [e["attempt"] for e in retries] == [1, 2]
    (span,) = list(tracer.finished)
    assert span["finish_reason"] == "length"
    assert span["attrs"]["replays"] == 0
    # The ring saw the same step's retries (telemetry agreement).
    rec = next(r for r in eng.telemetry.snapshot() if r["step"] == 2)
    assert rec["retries"] == 2


def test_tick_retries_exhausted_replays_token_exact(gpt_setup,
                                                    pin_zero_recompiles):
    """Past the retry budget the live slots' KV is declared lost: both
    running requests replay (prompt re-prefilled, emitted tokens re-fed
    through the fused tick) and still finish token-exact."""
    model, variables = gpt_setup
    reqs = [((np.arange(8) * 3 + 1) % 32, 7), ((np.arange(5) + 9) % 32, 6)]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    plan = FaultPlan(scheduled=[FaultSpec(step=3, site="tick",
                                          kind=FaultKind.TRANSIENT,
                                          count=8)])
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16, fault_plan=plan,
        max_retries=2, backoff_sleep=_no_sleep))
    handles = [eng.submit(p, n) for p, n in reqs]
    eng.run(max_steps=100)
    for h, ref in zip(handles, refs):
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref
        assert h.replays == 1
    assert eng.metrics.replays == 2
    assert eng.metrics.retries == 2  # the budget's two actual retries


def test_replay_admission_queue_wait_counts_from_requeue(gpt_setup):
    """The replay 'admitted' event's queue_wait_s measures time since
    the REQUEUE, not since the original submit — otherwise the first
    service attempt reads as scheduler backlog in the timeline."""
    model, variables = gpt_setup
    clock = _FakeClock()
    plan = FaultPlan(scheduled=[FaultSpec(step=3, site="tick",
                                          kind=FaultKind.TRANSIENT,
                                          count=8)])
    tracer = RequestTracer(clock=clock)
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      clock=clock, fault_plan=plan, max_retries=2,
                      backoff_sleep=_no_sleep, tracer=tracer)
    h = eng.submit((np.arange(8) * 3 + 1) % 32, 7)
    for _ in range(100):
        if h.done:
            break
        eng.step()
        clock.now += 1.0
    assert h.state == RequestState.FINISHED
    assert h.replays == 1
    (span,) = list(tracer.finished)
    admits = [e for e in span["events"] if e["name"] == "admitted"]
    assert [a["replay"] for a in admits] == [False, True]
    # One fake-clock second passed between the requeue (mid-step 3)
    # and the replay admission (step 4); the original admission was
    # four seconds before that.
    assert admits[1]["queue_wait_s"] == 1.0
    assert span["duration_s"] > admits[1]["queue_wait_s"]


def test_replay_budget_exhausted_fails_request_not_engine(gpt_setup):
    """Every tick failing forever: requests settle FAILED/ERROR after
    max_replays instead of crash-looping; the engine survives and keeps
    answering."""
    model, variables = gpt_setup
    plan = FaultPlan(sites=("tick",), transient_rate=1.0)
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      fault_plan=plan, max_retries=1, max_replays=2,
                      backoff_sleep=_no_sleep)
    handles = [eng.submit((np.arange(4) + i) % 32, 5) for i in range(2)]
    eng.run(max_steps=60)
    assert not eng.has_work
    for h in handles:
        assert h.state == RequestState.FAILED
        assert h.finish_reason == FinishReason.ERROR
        assert h.replays == 3  # budget + the final straw
        assert len(h.tokens) == 1  # the admission-time first token
    snap = eng.metrics.snapshot()
    assert snap["requests_failed"] == 2
    assert snap["requests_finished"] == 0
    assert eng.step() == 0  # still alive, just idle


def test_oom_degrades_flushes_and_rearms(gpt_setup):
    """RESOURCE_EXHAUSTED in a prefix hit's suffix chunk: unpinned pool
    blocks are flushed (the chain the faulted admission had pinned in
    place is spared — its table referenced it), donations stop, serving
    continues cold and token-exact, and the prefix cache re-arms (hits
    resume) after the cool-down."""
    model, variables = gpt_setup
    clock = _FakeClock()
    p = (np.arange(12) * 5 + 1) % 32
    q = (np.arange(11) * 7 + 3) % 32
    ref = _ref_greedy(model, variables, p, 4)
    plan = FaultPlan()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      clock=clock, fault_plan=plan,
                      degraded_cooldown_s=5.0, backoff_sleep=_no_sleep)
    for prompt in (p, q):
        h0 = eng.submit(prompt, 4)
        eng.run(max_steps=50)
        assert h0.tokens == _ref_greedy(model, variables, prompt, 4)
    assert eng._prefix.blocks_live == 2  # p's block and q's
    assert eng.prefix_pool_nbytes > 0  # the pool's HBM gauge
    # The NEXT admission's chunk (the suffix of a hit on p's chain) OOMs.
    plan._sched[(_next_step(eng), "chunk_prefill")] = [FaultKind.OOM]
    h1 = eng.submit(p, 4)
    eng.run(max_steps=50)
    assert h1.state == RequestState.FINISHED
    assert h1.tokens == ref  # replayed cold, still exact
    assert h1.replays == 1
    assert eng.degraded
    # q's unpinned block was flushed; p's was pinned under h1 when the
    # OOM fired and survives, unpinned again by the unwind.
    assert eng._prefix.blocks_live == 1
    assert eng._prefix.match(p, max_blocks=1).n_blocks == 1
    assert eng._prefix.match(q, max_blocks=1).n_blocks == 0
    assert eng.metrics.degraded_entries == 1
    # While degraded: no lookups, no donations, still exact.
    lookups_during = eng.metrics.prefix_lookups
    h2 = eng.submit(q, 4)
    eng.run(max_steps=50)
    assert h2.tokens == _ref_greedy(model, variables, q, 4)
    assert eng.metrics.prefix_lookups == lookups_during
    assert eng._prefix.blocks_live == 1
    # Past the cool-down the cache re-arms: donation resumes, then hits.
    clock.now += 6.0
    h3 = eng.submit(q, 4)
    eng.run(max_steps=50)
    assert not eng.degraded
    assert eng.metrics.degraded_time_s > 0
    assert h3.tokens == _ref_greedy(model, variables, q, 4)
    assert eng._prefix.blocks_live == 2  # q donated again
    hits_before = eng.metrics.prefix_hits
    h4 = eng.submit(p, 4)
    eng.run(max_steps=50)
    assert h4.tokens == ref
    assert eng.metrics.prefix_hits == hits_before + 1  # cache is back


@pytest.mark.parametrize("message,kind", [
    ("INTERNAL: interconnect hiccup mid-dispatch", "transient"),
    ("UNAVAILABLE: device halted", "transient"),
    ("RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
     "allocate 1.00G. That was not possible.", "oom"),
    # The compiler's refusals (messages as libtpu words them for a
    # described v5e) share those status codes but must stay loud.
    ("INTERNAL: Mosaic failed to compile TPU kernel: infer-vector-layout: "
     "unsupported shape cast", None),
    ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
     "memory in memory space hbm. Used 32.00G of 15.75G hbm.", None),
    ("INVALID_ARGUMENT: shapes do not match", None),
])
def test_classify_real_runtime_errors(message, kind):
    """`classify` recognises the INSTALLED jax's runtime error class by
    isinstance (a name match on the retired `XlaRuntimeError` spelling
    classified nothing real), and never a compile-time refusal."""
    from pddl_tpu.utils.faults import classify

    assert classify(jax.errors.JaxRuntimeError(message)) == kind
    assert classify(RuntimeError(message)) is None


def test_real_error_on_donated_program_never_redispatches(gpt_setup):
    """A REAL device error (not an injected pre-dispatch fault) from a
    donated-buffer program may have consumed its input, so the engine
    must escalate immediately — rebuild the block pool and replay
    every live slot — instead of retrying into a deleted array.
    Simulated by raising the installed jax's own runtime error class
    from an admission's chunk program."""
    model, variables = gpt_setup
    reqs = [((np.arange(6) * 3 + 2) % 32, 6), ((np.arange(9) + 5) % 32, 5)]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      backoff_sleep=_no_sleep)
    eng.warmup()
    h0 = eng.submit(*reqs[0])
    eng.step()
    assert h0.state == RequestState.RUNNING
    real_chunk, calls = eng._chunk_p, {"n": 0}

    def flaky_chunk(*args):
        calls["n"] += 1
        if calls["n"] == 1:
            raise jax.errors.JaxRuntimeError(
                "INTERNAL: interconnect hiccup mid-dispatch")
        return real_chunk(*args)

    eng._chunk_p = flaky_chunk
    try:
        h1 = eng.submit(*reqs[1])
        eng.run(max_steps=100)
    finally:
        eng._chunk_p = real_chunk
    for h, ref in zip((h0, h1), refs):
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref
    # Escalated, not retried: the failing dispatch was never re-issued
    # (call 2 is a replay admission's fresh chunk), the mid-stream
    # neighbor was replayed off the rebuilt pool too.
    assert eng.metrics.retries == 0
    assert h0.replays == 1 and h1.replays == 1
    assert _refcount_baseline(eng._prefix)


# ---------------------------------------------- the matrix, site by site
# `test_chaos_matrix` draws its sites at random; this holds every
# surviving site to every recoverable fault kind by name. The workload
# reaches all four: a 14-token prompt takes the wide chunk, the others
# two narrow ones; every fresh admission samples a first token.
_SITE_KINDS = {
    "transient_past_retries": dict(transient_rate=1.0,
                                   max_random_injections=2),
    "oom": dict(oom_rate=1.0, max_random_injections=1),
    "latency": dict(latency_rate=1.0, latency_s=1e-4,
                    max_random_injections=2),
}


@pytest.mark.chaos
@pytest.mark.parametrize("kind", sorted(_SITE_KINDS))
@pytest.mark.parametrize("site", ["chunk_prefill", "chunk_prefill_wide",
                                  "tick", "sample_first"])
def test_fault_matrix_per_site(gpt_setup, workload_refs,
                               pin_zero_recompiles, site, kind):
    """One fault kind aimed at one site from the first dispatch on:
    a transient burst one past ``max_retries`` (the touched state is
    lost and replays), an OOM (DEGRADED, replay on the cold path,
    re-arm), a latency spike (nothing lost). Every stream ends
    token-exact against ``generate()``, nothing recompiles, and the
    pool ends with every pin released and every block cached or
    free."""
    model, variables = gpt_setup
    clock = _FakeClock()
    plan = FaultPlan(seed=0, sites=(site,), sleep_fn=_no_sleep,
                     **_SITE_KINDS[kind])
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16, clock=clock,
        fault_plan=plan, max_retries=1, degraded_cooldown_s=5.0,
        backoff_sleep=_no_sleep))
    handles = [eng.submit(p, n) for p, n in _WORKLOAD]
    eng.run(max_steps=300)
    assert not eng.has_work
    for h, ref in zip(handles, workload_refs):
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref
    assert plan.total_injected \
        == _SITE_KINDS[kind]["max_random_injections"]
    m = eng.metrics
    if kind == "transient_past_retries":
        # One retry, then the budget is spent: lost, replayed.
        assert m.retries == 1 and m.retry_sites == {site: 1}
        assert m.replays >= 1 and m.degraded_entries == 0
    elif kind == "oom":
        assert m.retries == 0  # never blind-retried
        assert m.replays >= 1 and m.degraded_entries == 1
        assert eng.degraded
        clock.now += 6.0
        again = eng.submit(*_WORKLOAD[0])
        eng.run(max_steps=100)
        assert again.tokens == workload_refs[0]
        assert not eng.degraded  # re-armed
    else:
        assert (m.retries, m.replays, m.degraded_entries) == (0, 0, 0)
    assert max(h.replays for h in handles) <= 1
    assert_pool_idle(eng)


# -------------------------------------------------------- drain / restore
def _drain_restore_roundtrip(model, variables, eng_a, snapshot_source):
    """Restore ``snapshot_source`` into a fresh engine and pin every
    stream token-exact against the fault-free reference."""
    eng_b = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    restored = eng_b.restore(snapshot_source)
    eng_b.run(max_steps=200)
    return eng_b, restored


def test_sigterm_drain_restore_roundtrip(gpt_setup, tmp_path):
    """The acceptance round-trip: SIGTERM → flag → drain at the next
    step boundary (snapshot on disk, admission stopped) → fresh engine
    restores → every in-flight request resumes token-exactly."""
    model, variables = gpt_setup
    reqs = [((np.arange(6) * 3 + 2) % 32, 8), ((np.arange(9) + 4) % 32, 7),
            ((np.arange(5) * 7 + 1) % 32, 6), ((np.arange(7) + 11) % 32, 5)]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    path = str(tmp_path / "serve_drain.json")
    eng_a = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    eng_a.install_drain_handler(path)
    try:
        handles_a = [eng_a.submit(p, n) for p, n in reqs]
        for _ in range(3):
            eng_a.step()
        # Two running mid-stream, two still queued.
        assert sum(h.state == RequestState.RUNNING for h in handles_a) == 2
        partial = [list(h.tokens) for h in handles_a]
        assert any(partial)
        signal.raise_signal(signal.SIGTERM)
        assert eng_a.step() == 0  # the drain step emits nothing
    finally:
        eng_a.uninstall_drain_handler()
    assert eng_a.drained and not eng_a.has_work
    with pytest.raises(RuntimeError, match="drained"):
        eng_a.submit(reqs[0][0], 2)
    eng_b, restored = _drain_restore_roundtrip(model, variables, eng_a, path)
    assert len(restored) == 4
    # Drain order is running-first; match each restored handle to its
    # original by prompt.
    by_prompt = {tuple(h.request.prompt): h for h in restored}
    for (p, n), ref, part in zip(reqs, refs, partial):
        h = by_prompt[tuple(int(t) for t in p)]
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref          # full stream, token-exact
        assert h.tokens[:len(part)] == part  # resumed, not re-sampled
    # Previously-running requests keep their measured TTFT.
    assert by_prompt[tuple(int(t) for t in reqs[0][0])].ttft_s is not None


def test_kill_point_mid_step_state_still_drainable(gpt_setup):
    """A hard kill-point (BaseException) aborts step() like a real
    SIGKILL would; the host-side request state survives, drains, and
    restores token-exactly — the harshest recovery path."""
    model, variables = gpt_setup
    reqs = [((np.arange(8) * 5 + 3) % 32, 7), ((np.arange(6) + 1) % 32, 6),
            ((np.arange(10) * 3 + 9) % 32, 5)]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    plan = FaultPlan(scheduled=[FaultSpec(step=2, site="tick",
                                          kind=FaultKind.KILL)])
    eng_a = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                        fault_plan=plan, backoff_sleep=_no_sleep)
    handles = [eng_a.submit(p, n) for p, n in reqs]
    with pytest.raises(KillPoint):
        eng_a.run(max_steps=100)
    assert any(h.tokens for h in handles)  # it died mid-flight
    snapshot = eng_a.drain()
    assert len(snapshot["requests"]) == 3
    eng_b, restored = _drain_restore_roundtrip(model, variables, eng_a,
                                               snapshot)
    by_prompt = {tuple(h.request.prompt): h for h in restored}
    for (p, n), ref in zip(reqs, refs):
        h = by_prompt[tuple(int(t) for t in p)]
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref


def test_drain_preserves_remaining_deadline_budget(gpt_setup):
    """Deadline semantics survive the round trip: wall budget consumed
    before the drain stays consumed in the restoring engine."""
    model, variables = gpt_setup
    clock_a = _FakeClock()
    eng_a = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                        clock=clock_a)
    h = eng_a.submit(np.arange(4) % 32, 30, deadline_s=10.0)
    eng_a.step()
    clock_a.now = 7.0  # 7s of the 10s budget burned
    snapshot = eng_a.drain()
    clock_b = _FakeClock()
    clock_b.now = 100.0  # a different epoch entirely
    eng_b = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                        clock=clock_b)
    (restored,) = eng_b.restore(snapshot)
    eng_b.step()
    assert restored.state == RequestState.RUNNING
    clock_b.now += 4.0  # 7 + 4 > 10: the budget is spent
    eng_b.step()
    assert restored.state == RequestState.TIMED_OUT


def test_cross_process_drain_restore_roundtrip(tmp_path):
    """The snapshot is a real WIRE format, not an in-process artifact:
    written by one interpreter (`tests/_serve_drain_child.py` — builds
    the deterministic fleet-worker engine, serves, drains on disk),
    restored token-exactly in THIS interpreter. Pins what the in-process
    round-trip cannot: JSON serialization fidelity, version checking,
    and param-derivation determinism across processes (the fleet's
    migration path crosses exactly this boundary)."""
    import json
    import os
    import subprocess

    from pddl_tpu.serve.fleet.worker import build_engine

    workload = [
        {"prompt": ((np.arange(10) * 5 + 1) % 64).tolist(),
         "max_new_tokens": 8},
        {"prompt": ((np.arange(13) * 3 + 7) % 64).tolist(),
         "max_new_tokens": 7},
        {"prompt": ((np.arange(7) + 17) % 64).tolist(),
         "max_new_tokens": 6},
        {"prompt": ((np.arange(11) * 7 + 2) % 64).tolist(),
         "max_new_tokens": 5},
    ]
    cfg = dict(vocab=64, max_len=128, embed_dim=64, depth=2, heads=2,
               slots=2, prefill_len=32, max_queue_depth=64, param_seed=3,
               steps_before_drain=3, workload=workload)
    child = os.path.join(os.path.dirname(__file__), "_serve_drain_child.py")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, child, str(tmp_path), json.dumps(cfg)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"drain child failed:\n{proc.stderr[-3000:]}"
    with open(tmp_path / "state.json") as f:
        child_state = json.load(f)
    assert any(child_state["partial_tokens"]), "child drained nothing live"
    assert "running" in child_state["states"]

    engine = build_engine(cfg)  # fresh engine, THIS interpreter
    restored = engine.restore(str(tmp_path / "snapshot.json"))
    assert len(restored) == len(workload)
    engine.run(max_steps=500)
    refs = [_ref_greedy(engine.model, {"params": engine._params},
                        req["prompt"], req["max_new_tokens"])
            for req in workload]
    by_prompt = {tuple(h.request.prompt): h for h in restored}
    for req, ref, part in zip(workload, refs,
                              child_state["partial_tokens"]):
        h = by_prompt[tuple(req["prompt"])]
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref                 # full stream, token-exact
        assert h.tokens[:len(part)] == part    # resumed, not re-sampled


# ---------------------------------------------------- backpressure hints
def test_retry_after_hint_monotone_nonnegative():
    """Property (seeded sweep): whatever admission history the engine
    has seen, ``estimate_retry_after_s`` is non-negative and monotone
    non-decreasing in queue depth — a deeper queue never promises a
    SHORTER wait (that inversion is what turns polite backoff into a
    retry storm)."""
    from pddl_tpu.serve.metrics import ServeMetrics

    rng = np.random.default_rng(0)
    warm_trials = 0
    for _ in range(25):
        m = ServeMetrics()
        t = 0.0
        for _ in range(int(rng.integers(0, 40))):
            t += float(rng.exponential(rng.uniform(0.01, 2.0)))
            m.record_admission(t, 0.0)
        depths = sorted(int(rng.integers(0, 64)) for _ in range(10))
        hints = [m.estimate_retry_after_s(d) for d in depths]
        if m.recent_admission_interval_s() is None:
            assert all(h is None for h in hints)  # honest cold answer
            continue
        warm_trials += 1
        assert all(h is not None and h >= 0.0 for h in hints)
        assert all(a <= b for a, b in zip(hints, hints[1:])), \
            f"hint not monotone over depths {depths}: {hints}"
    assert warm_trials >= 10  # the sweep exercised the warm estimator


@pytest.mark.parametrize("priority", list(Priority))
def test_polite_client_never_sees_consecutive_queue_fulls(gpt_setup,
                                                          priority):
    """Property (seeded runs, ALL THREE priority classes): a client
    that HONORS ``retry_after_s`` (waits the hinted interval while the
    engine keeps draining) never gets rejected twice in a row — the
    hint really does estimate when a queue slot frees, for
    ``best_effort`` (whose hint prices the whole queue ahead of it)
    just as for ``interactive``. Un-hinted rejections (cold estimator)
    are exempt: there was nothing to honor."""
    model, variables = gpt_setup
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        clock = _FakeClock()
        eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                          max_queue_depth=3, clock=clock)

        def pump(dt, *, eng=eng, clock=clock):
            # The draining engine: steps keep happening as time passes.
            for _ in range(max(1, int(dt / 0.25))):
                eng.step()
                clock.now += 0.25

        submitted, last_full_hinted = 0, False
        while submitted < 25:
            prompt = (np.arange(int(rng.integers(4, 10)))
                      + submitted) % 32
            try:
                eng.submit(prompt, int(rng.integers(2, 5)),
                           priority=priority)
                submitted += 1
                last_full_hinted = False
            except QueueFull as e:
                if e.retry_after_s is not None:
                    assert not last_full_hinted, \
                        (f"seed {seed}: consecutive QueueFulls for a "
                         f"client honoring retry_after_s")
                    assert e.retry_after_s >= 0.0
                    last_full_hinted = True
                    pump(e.retry_after_s + 0.25)
                else:
                    last_full_hinted = False
                    pump(0.25)
            if rng.random() < 0.5:
                pump(0.25)
        eng.run(max_steps=1000)


def test_queue_full_carries_retry_after_hint(gpt_setup):
    model, variables = gpt_setup
    clock = _FakeClock()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      max_queue_depth=2, clock=clock)
    # Cold engine: no admission history yet, the hint is honestly None.
    cold = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                       max_queue_depth=1)
    cold.submit(np.arange(4) % 32, 2)
    with pytest.raises(QueueFull) as exc:
        cold.submit(np.arange(4) % 32, 2)
    assert exc.value.retry_after_s is None
    # Build admission history at ~1 admission/s.
    for i in range(4):
        eng.submit((np.arange(4) + i) % 32, 2)
        eng.run(max_steps=10)
        clock.now += 1.0
    # Now saturate: one long request holds the slot, two fill the queue.
    eng.submit(np.arange(5) % 32, 30)
    eng.step()
    eng.submit((np.arange(5) + 1) % 32, 2)
    eng.submit((np.arange(5) + 2) % 32, 2)
    with pytest.raises(QueueFull) as exc:
        eng.submit((np.arange(5) + 3) % 32, 2)
    hint = exc.value.retry_after_s
    assert hint is not None
    # depth 2 x ~1s/admission: the hint scales with the queue ahead.
    assert 1.0 <= hint <= 4.0
    assert "retry after" in str(exc.value)


def test_deadline_shed_at_pop_time(gpt_setup):
    """Scheduler-level shedding: a queued handle whose deadline expired
    is failed at pop time with FinishReason.DEADLINE — before it can
    burn prefill budget or a slot."""
    sched = FCFSScheduler(max_queue_depth=8)
    fresh = RequestHandle(Request(prompt=[1, 2], max_new_tokens=2),
                          arrival_s=0.0)
    doomed = RequestHandle(Request(prompt=[3, 4], max_new_tokens=2,
                                   deadline_s=5.0), arrival_s=0.0)
    sched.submit(doomed)
    sched.submit(fresh)
    shed = []
    admitted = sched.admit(2, on_expired=shed.append, now_fn=lambda: 9.0)
    assert admitted == [fresh]
    assert shed == [doomed]
    assert doomed.state == RequestState.TIMED_OUT
    assert doomed.finish_reason == FinishReason.DEADLINE
    # Engine-level accounting: the shed lands in its own counter.
    model, variables = gpt_setup
    clock = _FakeClock()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      clock=clock)
    running = eng.submit(np.arange(4) % 32, 30)
    eng.step()  # admit `running` before the deadlined request exists
    #             (EDF pops real deadlines ahead of deadline-less work)
    dead = eng.submit(np.arange(5) % 32, 4, deadline_s=5.0)
    eng.step()
    clock.now = 6.0
    running.cancel()
    eng.run(max_steps=50)
    assert dead.state == RequestState.TIMED_OUT
    assert dead.finish_reason == FinishReason.DEADLINE
    assert dead.tokens == []
    snap = eng.metrics.snapshot()
    assert snap["requests_deadline_shed"] == 1
    assert snap["requests_timed_out"] == 0  # disjoint counters


# -------------------------------------------------------- refcount hygiene
@pytest.mark.chaos
def test_cancel_storm_refcounts_return_to_baseline(gpt_setup,
                                                   pin_zero_recompiles):
    """A seeded storm of shared-prefix admissions — half cancelled at
    random moments, deadlines expiring in the queue, faults injected
    throughout — must leave every radix refcount at zero and the block
    accounting exact once the engine drains: no unwind path may leak a
    pinned chain."""
    model, variables = gpt_setup
    rng = np.random.default_rng(42)
    clock = _FakeClock()
    plan = FaultPlan(seed=7, transient_rate=0.05, oom_rate=0.02,
                     max_random_injections=25, sleep_fn=_no_sleep)
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16, clock=clock,
        prefix_cache_blocks=17, max_queue_depth=64, fault_plan=plan,
        degraded_cooldown_s=3.0, backoff_sleep=_no_sleep))
    shared = (np.arange(8) * 3 + 2) % 32
    handles = []
    for round_i in range(6):
        for j in range(4):
            tail = rng.integers(0, 32, size=int(rng.integers(1, 7)))
            prompt = np.concatenate([shared, tail]).astype(np.int32)[:15]
            deadline = 4.0 if rng.random() < 0.3 else None
            handles.append(eng.submit(prompt, int(rng.integers(2, 6)),
                                      deadline_s=deadline))
        for _ in range(int(rng.integers(1, 4))):
            eng.step()
            clock.now += 0.5
            for h in handles:
                if not h.done and rng.random() < 0.25:
                    h.cancel()
    eng.run(max_steps=400)
    assert not eng.has_work
    assert all(h.done for h in handles)
    assert _refcount_baseline(eng._prefix), \
        "cancel/fault storm leaked a pinned prefix chain"
    # The engine is healthy: one more request completes exact.
    clock.now += 10.0  # clear any degraded window
    p = (np.arange(10) * 5 + 3) % 32
    h = eng.submit(p, 4)
    eng.run(max_steps=50)
    assert h.tokens == _ref_greedy(model, variables, p, 4)
    assert _refcount_baseline(eng._prefix)


# ------------------------------------------------------------- fault plan
def test_fault_plan_determinism_and_validation():
    """Same seed + same call sequence = same injections; bad configs
    are loud."""
    def drive(plan):
        fired = []
        plan.on_step(0)
        for i in range(200):
            try:
                plan.check("tick")
            except Exception as e:
                fired.append((i, type(e).__name__))
        return fired

    a = drive(FaultPlan(seed=3, transient_rate=0.1, oom_rate=0.05,
                        sleep_fn=_no_sleep))
    b = drive(FaultPlan(seed=3, transient_rate=0.1, oom_rate=0.05,
                        sleep_fn=_no_sleep))
    c = drive(FaultPlan(seed=4, transient_rate=0.1, oom_rate=0.05,
                        sleep_fn=_no_sleep))
    assert a and a == b
    assert a != c
    with pytest.raises(ValueError, match="sum to <= 1"):
        FaultPlan(transient_rate=0.8, oom_rate=0.4)
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan(sites=("warp_core",))
    with pytest.raises(ValueError, match="unknown scheduled site"):
        FaultPlan(scheduled=[FaultSpec(0, "nope", FaultKind.KILL)])
    plan = FaultPlan(seed=0, latency_rate=1.0, latency_s=2.5,
                     max_random_injections=3, sleep_fn=_no_sleep)
    slept = []
    plan._sleep = slept.append
    plan.on_step(0)
    for _ in range(10):
        plan.check("tick")
    assert slept == [2.5] * 3  # latency fires, then the cap holds
    assert plan.injected[FaultKind.LATENCY] == 3
