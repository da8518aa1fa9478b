"""SLO-aware scheduling + fleet admission control under overload
(ISSUE 7), CPU.

The contracts under test:

- **Priority pop order**: ``interactive`` > ``batch`` > ``best_effort``
  at the scheduler, EDF within a class, and the PRIORITY-AWARE
  ``retry_after_s`` hint (a lower class prices the deeper queue it
  actually waits behind).
- **Anti-starvation aging** (discriminative): a sustained interactive
  flood with one queued batch request still finishes the batch request
  within the aging bound — and the same schedule STARVES it with aging
  disabled, so plain EDF cannot pass by accident.
- **Chunked-prefill fairness**: with ``prefill_slice_tokens`` set, a
  long cold prompt's admission spreads over multiple steps with decode
  ticks in between (running streams keep emitting), token-exact, zero
  recompiles — and cancel/deadline land mid-slice without wedging the
  engine.
- **Versioned drain snapshots**: v2 round-trips priority + deadline; a
  pre-ISSUE-7 v1 snapshot (no priority field) restores with
  ``interactive`` defaults instead of raising.
- **Fleet admission control**: per-priority token buckets reject with
  the bucket's own refill hint; the brownout ladder escalates one rung
  per hold under pressure, sheds ``best_effort`` first with the
  longest honest hint, caps output tokens, rejects cold prompts, and
  recovers HYSTERETICALLY; per-priority metrics flow through the
  strict Prometheus referee.
- **Chaos under overload** (3 seeds, fault injection while 2x
  saturated): every request reaches a terminal state (finished /
  DEADLINE / shed-with-hint), every FINISHED stream is token-exact,
  zero recompiles throughout.
"""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.obs import fleet_exposition, parse_prometheus_text, serve_exposition
from pddl_tpu.serve import (
    AdmissionRejected,
    FaultPlan,
    FinishReason,
    Priority,
    QueueFull,
    RequestState,
    SLOScheduler,
    ServeEngine,
)
from pddl_tpu.serve import drain as drain_io
from pddl_tpu.serve.fleet import (
    AdmissionControl,
    BrownoutController,
    BrownoutRung,
    FleetRouter,
    LocalReplica,
    OverloadDetector,
    TokenBucket,
)
from pddl_tpu.serve.request import Request, RequestHandle
from conftest import ref_greedy as _ref_greedy, FakeClock as _FakeClock

pytestmark = pytest.mark.overload


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


def _handle(priority=Priority.INTERACTIVE, deadline_s=None, arrival_s=0.0,
            prompt=(1, 2, 3)):
    return RequestHandle(
        Request(prompt=list(prompt), max_new_tokens=2,
                deadline_s=deadline_s, priority=priority),
        arrival_s=arrival_s)


# ------------------------------------------------------------ pop order
def test_priority_classes_pop_before_lower_ones():
    sched = SLOScheduler(max_queue_depth=16)
    be = _handle(Priority.BEST_EFFORT)
    ba = _handle(Priority.BATCH)
    ia = _handle(Priority.INTERACTIVE)
    for h in (be, ba, ia):  # worst class submitted FIRST
        sched.submit(h)
    assert sched.admit(3, now_fn=lambda: 0.0) == [ia, ba, be]


def test_edf_within_class_and_deadline_less_last():
    sched = SLOScheduler(max_queue_depth=16)
    loose = _handle(deadline_s=10.0)
    tight = _handle(deadline_s=5.0)
    none = _handle()  # deadline-less: synthetic horizon, pops last
    for h in (none, loose, tight):
        sched.submit(h)
    assert sched.admit(3, now_fn=lambda: 0.0) == [tight, loose, none]


def test_depth_at_or_above_counts_the_queue_a_class_waits_behind():
    sched = SLOScheduler(max_queue_depth=16)
    for p in (Priority.INTERACTIVE, Priority.INTERACTIVE, Priority.BATCH,
              Priority.BEST_EFFORT):
        sched.submit(_handle(p))
    assert sched.depth_at_or_above(Priority.INTERACTIVE) == 2
    assert sched.depth_at_or_above(Priority.BATCH) == 3
    assert sched.depth_at_or_above(Priority.BEST_EFFORT) == 4


def test_aging_bound_is_discriminative_vs_plain_edf():
    """A sustained interactive flood (the queue never lacks fresh
    interactive work) with ONE queued batch request: with aging the
    batch request is admitted within the aging bound; the SAME
    schedule with aging disabled starves it indefinitely — so plain
    EDF without aging fails this test."""
    def flood_rounds(aging_s, rounds):
        clock = _FakeClock()
        sched = SLOScheduler(max_queue_depth=4096, aging_s=aging_s)
        batch = _handle(Priority.BATCH, arrival_s=0.0)
        sched.submit(batch)
        admitted_at = None
        for r in range(rounds):
            # Two fresh interactive arrivals, one admission slot per
            # round: interactive pressure never drains.
            for _ in range(2):
                sched.submit(_handle(Priority.INTERACTIVE,
                                     arrival_s=clock.now))
            for h in sched.admit(1, now_fn=clock):
                if h is batch and admitted_at is None:
                    admitted_at = clock.now
            clock.now += 1.0
        return admitted_at

    aging_s = 10.0
    admitted_at = flood_rounds(aging_s, rounds=40)
    assert admitted_at is not None, "batch request starved WITH aging"
    assert admitted_at <= aging_s + 1.0, \
        f"batch admitted at {admitted_at}s, past the {aging_s}s bound"
    assert flood_rounds(None, rounds=40) is None, \
        "plain EDF admitted the batch request — the test is not " \
        "discriminative"


def test_over_budget_head_stays_in_place_not_promoted():
    """Review-driven pin: a head blocked by the prefill budget must
    stay IN the queue at its own rank — parking it in the replay
    bypass lane would let a big best_effort prompt jump ahead of
    interactive work arriving the very next tick."""
    sched = SLOScheduler(max_queue_depth=8, prefill_token_budget=4)
    small = _handle(prompt=(1, 2))
    big = _handle(Priority.BEST_EFFORT, prompt=tuple(range(10)))
    sched.submit(small)
    sched.submit(big)
    assert sched.admit(2, now_fn=lambda: 0.0) == [small]  # big: over budget
    late_ia = _handle(Priority.INTERACTIVE, arrival_s=1.0)
    sched.submit(late_ia)
    assert sched.admit(1, now_fn=lambda: 1.0) == [late_ia], \
        "budget-parked best_effort outranked a later interactive"
    assert sched.admit(1, now_fn=lambda: 1.0) == [big]


def test_router_chains_caller_brownout_callback(gpt_setup):
    """Review-driven pin: FleetRouter's metrics observer must CHAIN
    the on_transition hook the caller gave AdmissionControl, not
    clobber it — a user's paging hook keeps firing."""
    model, variables = gpt_setup
    seen = []
    admission = AdmissionControl(
        on_transition=lambda a, b: seen.append((a, b)),
        brownout_kw=dict(high=0.2, low=0.05, escalate_hold_s=0.0,
                         recover_hold_s=0.2))
    clock = _FakeClock(10.0)
    fleet = _slo_fleet(model, variables, 1, clock=clock,
                       admission=admission, max_queue_depth=2)
    for i in range(12):
        try:
            fleet.submit([(i + j) % 32 for j in range(1, 6)], 3)
        except QueueFull:
            pass
        clock.now += 0.01
    assert seen, "caller's brownout hook never fired"
    assert fleet.metrics.brownout_escalations == \
        sum(1 for a, b in seen if b > a)
    fleet.run(max_steps=500)


def test_requeue_front_outranks_every_class():
    """Replayed handles bypass the SLO order entirely: a best_effort
    replay pops before a fresh interactive submit (it was admitted
    once already — shedding or demoting it would turn a device fault
    into visible starvation)."""
    sched = SLOScheduler(max_queue_depth=16)
    replayed = _handle(Priority.BEST_EFFORT)
    sched.submit(_handle(Priority.INTERACTIVE))
    sched.requeue_front([replayed])
    out = sched.admit(1, now_fn=lambda: 0.0)
    assert out == [replayed]


# -------------------------------------------------- priority-aware hints
def test_queue_full_hint_is_rank_monotone(gpt_setup):
    """At one queue state, the retry_after_s hint never SHRINKS as the
    class gets less urgent: best_effort >= batch >= interactive — the
    lower class really does wait behind more work."""
    model, variables = gpt_setup
    clock = _FakeClock()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      max_queue_depth=4, clock=clock)
    # Warm the admission-interval estimator at ~1 admission/s.
    for i in range(4):
        eng.submit((np.arange(4) + i) % 32, 2)
        eng.run(max_steps=10)
        clock.now += 1.0
    # Saturate with a mixed-class queue: 1 running + 4 queued.
    eng.submit(np.arange(5) % 32, 30)
    eng.step()
    eng.submit((np.arange(5) + 1) % 32, 2, priority=Priority.INTERACTIVE)
    eng.submit((np.arange(5) + 2) % 32, 2, priority=Priority.INTERACTIVE)
    eng.submit((np.arange(5) + 3) % 32, 2, priority=Priority.BATCH)
    eng.submit((np.arange(5) + 4) % 32, 2, priority=Priority.BEST_EFFORT)
    hints = {}
    for p in Priority:
        with pytest.raises(QueueFull) as exc:
            eng.submit((np.arange(5) + 5) % 32, 2, priority=p)
        assert exc.value.priority is p
        hints[p] = exc.value.retry_after_s
        assert hints[p] is not None and hints[p] >= 0.0
    assert hints[Priority.INTERACTIVE] <= hints[Priority.BATCH] \
        <= hints[Priority.BEST_EFFORT]
    assert hints[Priority.INTERACTIVE] < hints[Priority.BEST_EFFORT]


# --------------------------------------------------- versioned snapshots
def test_drain_snapshot_roundtrips_priority_and_deadline(gpt_setup):
    """Priority + deadline fields (the v2 additions) survive the
    drain→restore round trip at the CURRENT snapshot version (v3 since
    the paged engine — the fleet migration path inherits this for free:
    `serve/drain.py` IS its wire format)."""
    model, variables = gpt_setup
    clock_a = _FakeClock()
    eng_a = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                        clock=clock_a)
    h_batch = eng_a.submit(np.arange(6) % 32, 4, priority=Priority.BATCH,
                           deadline_s=30.0)
    h_be = eng_a.submit((np.arange(7) + 2) % 32, 3,
                        priority=Priority.BEST_EFFORT)
    eng_a.step()
    clock_a.now = 4.0
    snapshot = eng_a.drain()
    assert snapshot["version"] == drain_io.SNAPSHOT_VERSION == 5
    by_len = {len(e["prompt"]): e for e in snapshot["requests"]}
    assert by_len[6]["priority"] == "batch"
    assert by_len[6]["deadline_s"] == 30.0
    assert by_len[7]["priority"] == "best_effort"
    eng_b = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    restored = eng_b.restore(snapshot)
    by_prompt = {tuple(h.request.prompt): h for h in restored}
    assert by_prompt[tuple(h_batch.request.prompt)].request.priority \
        is Priority.BATCH
    assert by_prompt[tuple(h_be.request.prompt)].request.priority \
        is Priority.BEST_EFFORT
    eng_b.run(max_steps=100)
    assert all(h.state == RequestState.FINISHED for h in restored)


def test_pre_issue7_v1_snapshot_restores_with_interactive_default(
        gpt_setup, tmp_path):
    """A version-1 snapshot — written by a pre-priority engine, no
    ``priority`` key anywhere — must restore (NOT raise) with every
    request defaulting to ``interactive``, and still resume
    token-exactly. Pinned next to the cross-process drain child: this
    is the compatibility face of the same wire format."""
    model, variables = gpt_setup
    p, n = ((np.arange(9) * 5 + 1) % 32).tolist(), 6
    ref = _ref_greedy(model, variables, p, n)
    v1 = {
        "version": 1,
        "drained_unix_s": 0.0,
        "requests": [{
            "prompt": p, "max_new_tokens": n,
            "sampling": {"temperature": 0.0, "top_k": None, "top_p": None},
            "deadline_s": None, "elapsed_s": 1.5,
            "tokens": ref[:2],  # mid-stream: exercises replay too
            "ttft_s": 0.1,
        }],
    }
    path = tmp_path / "v1_snapshot.json"
    path.write_text(json.dumps(v1))
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    (restored,) = eng.restore(str(path))
    assert restored.request.priority is Priority.INTERACTIVE
    eng.run(max_steps=100)
    assert restored.state == RequestState.FINISHED
    assert restored.tokens == ref  # resumed, not re-sampled
    # Unknown future versions still refuse loudly.
    bad = tmp_path / "v99.json"
    bad.write_text(json.dumps({"version": 99, "requests": []}))
    with pytest.raises(ValueError, match="version"):
        drain_io.load_snapshot(str(bad))


# ------------------------------------------------ chunked-prefill slices
def test_sliced_prefill_interleaves_decode_ticks_token_exact(
        gpt_setup, pin_zero_recompiles):
    """The fairness mechanism itself: with ``prefill_slice_tokens``, a
    long cold prompt's admission spans multiple steps and the RUNNING
    stream keeps emitting between slices (without slicing it gets
    exactly one tick's token while the whole prefill lands in one
    step). Both requests finish token-exact; zero recompiles."""
    model, variables = gpt_setup

    def run(slice_tokens):
        eng = ServeEngine(model, variables, max_slots=2, prefill_len=32,
                          prefix_chunk=8,
                          prefill_slice_tokens=slice_tokens)
        eng.warmup()
        short_p, long_p = (np.arange(6) + 1) % 32, (np.arange(31) * 3) % 32
        a = eng.submit(short_p, 12)
        eng.step()  # A is running
        b = eng.submit(long_p, 3)
        a_before = len(a.tokens)
        steps_until_b = 0
        while not b.tokens:
            eng.step()
            steps_until_b += 1
            assert steps_until_b < 50
        a_during = len(a.tokens) - a_before
        eng.run(max_steps=200)
        return a, b, short_p, long_p, a_during, steps_until_b, eng

    a, b, short_p, long_p, a_during, steps, eng = run(8)
    pin_zero_recompiles(eng)
    # 31 cold tokens at 8 tokens/step: the admission spans >= 4 steps
    # and A emitted a token in each — the discriminative fairness claim.
    assert steps >= 4
    assert a_during >= 3
    assert a.tokens == _ref_greedy(model, variables, short_p, 12)
    assert b.tokens == _ref_greedy(model, variables, long_p, 3)
    # The whole-prompt engine admits B in ONE step: same outcome,
    # no interleaving (what slicing exists to fix).
    a2, b2, _, _, a2_during, steps2, _ = run(None)
    assert steps2 == 1 and a2_during <= 1
    assert b2.tokens == b.tokens


def test_cancel_and_deadline_land_mid_slice(gpt_setup,
                                            pin_zero_recompiles):
    """A parked slice must honor cancel() and deadline expiry between
    its steps — the request settles terminally, the engine keeps
    serving, nothing recompiles."""
    model, variables = gpt_setup
    clock = _FakeClock()
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=1, prefill_len=32, prefix_chunk=8,
        prefill_slice_tokens=8, clock=clock))
    long_p = (np.arange(31) * 5 + 2) % 32
    # Cancel mid-slice.
    h = eng.submit(long_p, 4)
    eng.step()  # slice started, not finished
    assert not h.done and not h.tokens
    h.cancel()
    eng.step()
    assert h.state == RequestState.CANCELLED
    # Deadline mid-slice.
    h2 = eng.submit(long_p, 4, deadline_s=1.0)
    eng.step()
    clock.now += 5.0
    eng.step()
    assert h2.state == RequestState.TIMED_OUT
    assert h2.finish_reason == FinishReason.TIMED_OUT
    # The engine is healthy: the same prompt now completes exact.
    h3 = eng.submit(long_p, 4)
    eng.run(max_steps=100)
    assert h3.tokens == _ref_greedy(model, variables, long_p, 4)
    snap = eng.metrics.snapshot()
    assert snap["requests_cancelled"] == 1
    assert snap["requests_timed_out"] == 1


# -------------------------------------------------------- preemption
def test_interactive_preempts_best_effort_token_exact(
        gpt_setup, pin_zero_recompiles):
    """Every slot busy with long best_effort streams, an interactive
    request arrives: one victim is PARKED (slot freed, requeued), the
    interactive request serves promptly, and the paused stream later
    resumes token-exactly through the replay machinery — the
    fault-recovery path doing scheduling duty, zero recompiles."""
    model, variables = gpt_setup
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16,
        preempt_cap=2))
    be_p = [(np.arange(7) + i) % 32 for i in range(2)]
    be = [eng.submit(p, 20, priority=Priority.BEST_EFFORT) for p in be_p]
    eng.step()
    assert eng.live_slots == 2
    ia_p = (np.arange(8) * 3 + 1) % 32
    ia = eng.submit(ia_p, 4, priority=Priority.INTERACTIVE)
    eng.step()  # preempts one best_effort, admits the interactive
    assert eng.metrics.preemptions == 1
    assert sum(1 for h in be if h.state == RequestState.QUEUED) == 1
    assert ia.state in (RequestState.RUNNING, RequestState.FINISHED)
    eng.run(max_steps=200)
    assert ia.tokens == _ref_greedy(model, variables, ia_p.tolist(), 4)
    for p, h in zip(be_p, be):
        assert h.state == RequestState.FINISHED
        assert h.tokens == _ref_greedy(model, variables, p.tolist(), 20)
    assert max(h.preemptions for h in be) == 1


def test_preempt_cap_zero_disables_preemption(gpt_setup):
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      preempt_cap=0)
    be = eng.submit(np.arange(6) % 32, 10, priority=Priority.BEST_EFFORT)
    eng.step()
    eng.submit((np.arange(5) + 2) % 32, 2,
               priority=Priority.INTERACTIVE)
    eng.step()
    assert be.state == RequestState.RUNNING  # never parked
    assert eng.metrics.preemptions == 0
    eng.run(max_steps=100)


# --------------------------------------------- per-priority observability
def test_per_priority_metrics_and_exposition_referee(gpt_setup):
    """ServeMetrics splits TTFT/finish/shed by class and the splits
    ride the Prometheus exposition as labeled series, verified through
    the strict parse_prometheus_text referee."""
    model, variables = gpt_setup
    clock = _FakeClock()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      clock=clock)
    hi = eng.submit(np.arange(5) % 32, 2, priority=Priority.INTERACTIVE)
    hb = eng.submit((np.arange(6) + 1) % 32, 2, priority=Priority.BATCH)
    doomed = eng.submit((np.arange(7) + 2) % 32, 2,
                        priority=Priority.BEST_EFFORT, deadline_s=1.0)
    eng.step()
    clock.now = 5.0  # best_effort expires in the queue -> pop-time shed
    eng.run(max_steps=100)
    assert hi.state == hb.state == RequestState.FINISHED
    assert doomed.finish_reason == FinishReason.DEADLINE
    snap = eng.metrics.snapshot()
    assert snap["requests_finished_by_priority"] == {
        "interactive": 1, "batch": 1, "best_effort": 0}
    assert snap["requests_deadline_shed_by_priority"]["best_effort"] == 1
    assert snap["ttft_p99_s_by_priority"]["interactive"] is not None
    assert snap["ttft_p99_s_by_priority"]["best_effort"] is None
    samples, types = parse_prometheus_text(
        serve_exposition(eng.metrics, eng))
    key = "pddl_serve_requests_finished_by_priority"
    assert samples[(key, (("key", "interactive"),))] == 1.0
    assert samples[(key, (("key", "best_effort"),))] == 0.0
    assert types[key] == "gauge"
    shed_key = "pddl_serve_requests_deadline_shed_by_priority"
    assert samples[(shed_key, (("key", "best_effort"),))] == 1.0


# ------------------------------------------------- admission-control units
def test_token_bucket_rates_and_refill_hint():
    b = TokenBucket(2.0, burst=2)
    assert b.take(0.0) and b.take(0.0)
    assert not b.take(0.0)
    assert b.time_until_token(0.0) == pytest.approx(0.5)
    assert b.take(0.5)  # refilled at 2/s
    unlimited = TokenBucket(None, burst=1)
    assert all(unlimited.take(0.0) for _ in range(100))
    assert unlimited.time_until_token(0.0) == 0.0
    with pytest.raises(ValueError):
        TokenBucket(0.0, burst=1)


def test_overload_detector_pressure_and_degraded_floor():
    d = OverloadDetector(window_s=2.0, min_samples=4, degraded_floor=0.5)
    for i in range(2):
        d.observe(0.0, rejected=True)
    assert d.pressure(0.0) == 0.0  # below min_samples: not overloaded
    for i in range(2):
        d.observe(0.0, rejected=False)
    assert d.pressure(0.0) == pytest.approx(0.5)
    assert d.pressure(3.0) == 0.0  # the window slid past everything
    d.set_degraded(1)  # r08 OOM state: pressure floor even when calm
    assert d.pressure(3.0) == pytest.approx(0.5)
    d.set_degraded(0)
    assert d.pressure(3.0) == 0.0


def test_brownout_ladder_escalates_and_recovers_hysteretically():
    moves = []
    c = BrownoutController(high=0.3, low=0.1, escalate_hold_s=1.0,
                           recover_hold_s=2.0, output_cap=8,
                           on_transition=lambda a, b: moves.append((a, b)))
    assert c.update(0.0, 0.9) is BrownoutRung.NORMAL  # hold not met yet
    assert c.update(1.0, 0.9) is BrownoutRung.SHED_BEST_EFFORT
    assert c.update(1.5, 0.9) is BrownoutRung.SHED_BEST_EFFORT
    assert c.update(2.0, 0.9) is BrownoutRung.CAP_OUTPUT  # one rung/hold
    assert c.update(3.0, 0.9) is BrownoutRung.REJECT_COLD
    assert c.update(4.0, 0.9) is BrownoutRung.REJECT_COLD  # ceiling
    # The dead band (low < p < high) neither escalates nor recovers.
    assert c.update(5.0, 0.2) is BrownoutRung.REJECT_COLD
    assert c.update(50.0, 0.2) is BrownoutRung.REJECT_COLD
    # Recovery: one rung per recover_hold_s of calm — never a jump.
    assert c.update(60.0, 0.0) is BrownoutRung.REJECT_COLD
    assert c.update(62.0, 0.0) is BrownoutRung.CAP_OUTPUT
    assert c.update(63.0, 0.0) is BrownoutRung.CAP_OUTPUT
    assert c.update(64.0, 0.0) is BrownoutRung.SHED_BEST_EFFORT
    assert c.update(66.0, 0.0) is BrownoutRung.NORMAL
    assert c.escalations == 3 and c.deescalations == 3
    assert len(moves) == 6
    # Decisions per rung: best_effort sheds with the LONGEST hint (the
    # whole ladder must unwind before it re-enters).
    c.rung = BrownoutRung.REJECT_COLD
    ok, reason, hint = c.decide(Priority.BEST_EFFORT, cold=False)
    assert not ok and reason == "brownout_shed"
    assert hint == pytest.approx(3 * 2.0)
    ok, reason, cold_hint = c.decide(Priority.INTERACTIVE, cold=True)
    assert not ok and reason == "brownout_cold"
    assert cold_hint < hint  # cold re-enters one rung down: shorter
    ok, _, _ = c.decide(Priority.INTERACTIVE, cold=False)
    assert ok
    assert c.cap_new_tokens(100) == 8
    c.rung = BrownoutRung.NORMAL
    assert c.cap_new_tokens(100) == 100


# ----------------------------------------------------- fleet integration
def _slo_fleet(model, variables, n, *, clock, admission,
               max_queue_depth=4, slots=2):
    def factory():
        return ServeEngine(model, variables, max_slots=slots,
                           prefill_len=16,
                           max_queue_depth=max_queue_depth,
                           backoff_sleep=_no_sleep)
    replicas = [LocalReplica(i, factory) for i in range(n)]
    return FleetRouter(replicas, affinity_block_size=8, affinity_blocks=1,
                       respawn=False, clock=clock, admission=admission)


def test_fleet_rate_limit_rejects_with_refill_hint(gpt_setup):
    model, variables = gpt_setup
    clock = _FakeClock(10.0)
    fleet = _slo_fleet(model, variables, 1, clock=clock,
                       admission=AdmissionControl(
                           rates={Priority.BEST_EFFORT: 1.0}, burst=1.0))
    p = (np.arange(6) + 1) % 32
    fleet.submit(p, 2, priority=Priority.BEST_EFFORT)  # takes the token
    with pytest.raises(AdmissionRejected) as exc:
        fleet.submit(p, 2, priority=Priority.BEST_EFFORT)
    assert exc.value.reason == "rate_limit"
    assert exc.value.retry_after_s == pytest.approx(1.0)
    assert exc.value.priority is Priority.BEST_EFFORT
    # Unlimited classes sail through; the limited class recovers after
    # its own refill interval — the hint was honest.
    fleet.submit(p, 2, priority=Priority.INTERACTIVE)
    clock.now += 1.0
    fleet.submit(p, 2, priority=Priority.BEST_EFFORT)
    assert fleet.metrics.admission_rate_limited == 1
    assert fleet.metrics.rejected_by_priority["best_effort"] == 1
    fleet.run(max_steps=300)


def test_fleet_brownout_sheds_best_effort_first_and_recovers(gpt_setup):
    """The acceptance shape in miniature: flood a small fleet far past
    capacity with a mixed-class workload. The ladder must escalate,
    the rejections must land overwhelmingly on best_effort, every
    accepted request must finish, and after the storm the ladder must
    unwind to NORMAL (hysteresis, not flapping)."""
    model, variables = gpt_setup
    clock = _FakeClock(100.0)
    admission = AdmissionControl(
        detector_kw=dict(window_s=5.0, min_samples=4),
        brownout_kw=dict(high=0.3, low=0.05, escalate_hold_s=0.0,
                         recover_hold_s=2.0, output_cap=2))
    fleet = _slo_fleet(model, variables, 2, clock=clock,
                       admission=admission, max_queue_depth=3)
    rng = np.random.default_rng(0)
    classes = [Priority.INTERACTIVE, Priority.BATCH, Priority.BEST_EFFORT]
    handles, rejects = [], {p.value: 0 for p in Priority}
    for i in range(60):  # ~4x what the queues can hold: a real storm
        p = classes[i % 3]
        prompt = rng.integers(0, 32, size=6)
        try:
            handles.append(fleet.submit(prompt, 2, priority=p))
        except (AdmissionRejected, QueueFull):
            rejects[p.value] += 1
        if i % 6 == 5:
            fleet.step()  # a little service between bursts
        clock.now += 0.01
    assert admission.rung > BrownoutRung.NORMAL
    assert fleet.metrics.brownout_escalations >= 1
    total = sum(rejects.values())
    assert total > 0
    # best_effort absorbs the bulk of the shedding: once the ladder is
    # up, EVERY best_effort submit is front-door shed, while
    # interactive is only ever queue-limited.
    assert rejects["best_effort"] >= max(rejects["interactive"],
                                         rejects["batch"])
    assert fleet.metrics.brownout_shed_best_effort >= 1
    # Output capping engaged at rung >= 2 for admitted requests.
    if admission.rung >= BrownoutRung.CAP_OUTPUT:
        assert fleet.metrics.brownout_capped_output >= 0
    # Drain the accepted work: everything terminal.
    while fleet.has_work:
        fleet.step()
        clock.now += 0.05
    assert all(h.done for h in handles)
    # Hysteretic recovery: calm steps unwind the ladder one rung per
    # recover_hold_s — and it reaches NORMAL, not a stuck brownout.
    for _ in range(200):
        fleet.step()
        clock.now += 0.1
        if admission.rung is BrownoutRung.NORMAL:
            break
    assert admission.rung is BrownoutRung.NORMAL
    assert fleet.metrics.brownout_deescalations \
        == fleet.metrics.brownout_escalations
    # The per-class rejects and the rung ride the fleet exposition.
    samples, types = parse_prometheus_text(fleet_exposition(fleet))
    assert samples[("pddl_fleet_brownout_rung", ())] == 0.0
    assert samples[("pddl_fleet_admission_rejected_best_effort_total",
                    ())] == float(rejects["best_effort"])
    assert types["pddl_fleet_admission_rejected_best_effort_total"] \
        == "counter"
    assert ("pddl_fleet_brownout_shed_best_effort_total", ()) in samples


def test_degraded_replica_raises_brownout_pressure(gpt_setup):
    """r08 composition: a replica in OOM-degraded mode feeds the
    overload detector's pressure floor, so sustained degradation
    browns the fleet out even when the queues look calm."""
    model, variables = gpt_setup
    clock = _FakeClock(50.0)
    admission = AdmissionControl(
        brownout_kw=dict(high=0.3, low=0.05, escalate_hold_s=0.0,
                         recover_hold_s=1.0))
    fleet = _slo_fleet(model, variables, 1, clock=clock,
                       admission=admission)
    slot = fleet.replicas[0]
    slot.driver.engine._degraded = True  # as an OOM would leave it
    fleet.step()
    clock.now += 0.1
    fleet.step()
    assert admission.rung > BrownoutRung.NORMAL
    with pytest.raises(AdmissionRejected):
        fleet.submit((np.arange(6) + 1) % 32, 2,
                     priority=Priority.BEST_EFFORT)


# --------------------------------------------------- chaos under overload
@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_under_overload_token_exact(gpt_setup, pin_zero_recompiles,
                                          seed):
    """Fault injection WHILE 2x saturated: a mixed-priority flood well
    past slot capacity, prefill slicing on, transient+OOM injection
    throughout. Every request must reach a terminal state (finished,
    DEADLINE, or shed-with-hint), every FINISHED stream must be
    token-identical to the fault-free oracle, zero recompiles across
    every retry/replay/degraded/sliced-admission transition."""
    model, variables = gpt_setup
    plan = FaultPlan(seed=seed, transient_rate=0.04, oom_rate=0.01,
                     max_random_injections=15, sleep_fn=_no_sleep)
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16,
        max_queue_depth=6, fault_plan=plan, backoff_sleep=_no_sleep,
        prefill_slice_tokens=8, aging_s=0.5))
    rng = np.random.default_rng(seed)
    classes = [Priority.INTERACTIVE, Priority.BATCH, Priority.BEST_EFFORT]
    handles, refs, rejected = [], [], 0
    deadline = time.monotonic() + 120.0
    for i in range(24):  # ~2x what queue+slots hold at any moment
        plen = int(rng.integers(5, 15))
        prompt = rng.integers(0, 32, size=plen).astype(np.int32)
        n = int(rng.integers(2, 6))
        try:
            h = eng.submit(prompt, n, priority=classes[i % 3],
                           deadline_s=60.0 if i % 5 == 0 else None)
        except QueueFull as e:
            rejected += 1
            assert e.retry_after_s is None or e.retry_after_s >= 0.0
            continue
        handles.append(h)
        refs.append(_ref_greedy(model, variables, prompt.tolist(), n))
        if i % 3 == 2:
            eng.step()
        assert time.monotonic() < deadline
    eng.run(max_steps=800)
    assert not eng.has_work, "engine failed to drain the overload"
    finished = 0
    for h, ref in zip(handles, refs):
        assert h.done, f"request {h} never reached a terminal state"
        if h.state == RequestState.FINISHED:
            finished += 1
            assert h.tokens == ref, \
                f"surviving stream diverged under overload (seed {seed})"
    assert finished >= 1
    snap = eng.metrics.snapshot()
    # Nothing simply vanished: accepted = finished + terminal-others.
    assert (snap["requests_finished"] + snap["requests_timed_out"]
            + snap["requests_deadline_shed"] + snap["requests_cancelled"]
            + snap["requests_failed"]) == len(handles)
