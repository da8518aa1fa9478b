"""Elastic autoscaling fleet (`pddl_tpu/serve/fleet/autoscaler.py`), CPU.

The contracts under test:

- **Flapping-load chaos matrix** (3 seeds, ``@pytest.mark.autoscale`` +
  ``chaos``): load storms and calms while the autoscaler runs; the
  fleet scales up under pressure and scales down by LIVE-MIGRATING the
  victim's streams — and a DIFFERENT replica is killed while that
  scale-down migration is in flight. Every request reaches FINISHED,
  every stream is token-identical to the unkilled oracle, zero
  recompiles hold on every surviving replica.
- **Control-loop policy**: scale-up engages at pressure BELOW the
  brownout ladder's high-water mark (capacity ahead of shedding); a
  wedged spawn raises the typed ``ReplicaSpawnTimeout`` and is retried
  behind a doubling backoff; the scale-down projection guard vetoes a
  shrink the survivors could not absorb.
- **Router mechanics**: ``scale_up`` joins a ready replica (and
  revives parked orphans); ``scale_down`` migrates via the drain
  snapshot, refuses to orphan work when no survivor exists.
- **Trace generator** (`fleet/tracegen.py`): seeded determinism, the
  diurnal peak:trough shape, the heavy-tail output mix, priority
  split, Zipf adapter popularity.
- **Replay client** (`fleet/replay.py`): rejected events re-enter at
  ``now + retry_after_s`` (the satellite fix — the r12 harness dropped
  them), and replica-hours are metered for goodput-per-replica-hour.
- **Observability**: autoscale counters/gauges render through
  ``fleet_exposition`` and re-parse through the strict referee.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.obs import RequestTracer, fleet_exposition, parse_prometheus_text
from pddl_tpu.serve import QueueFull, ServeEngine
from pddl_tpu.serve.fleet import (
    AdmissionControl,
    FleetAutoscaler,
    FleetRouter,
    LocalReplica,
    ProcessReplica,
    ReplicaDied,
    ReplicaSpawnTimeout,
    ScaleDecision,
    diurnal_trace,
    replay_trace,
)
from pddl_tpu.serve.request import Priority, RequestState
from conftest import ref_greedy as _ref_greedy, FakeClock as _FakeClock

pytestmark = pytest.mark.autoscale


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


def _engine_factory(model, variables, *, max_queue_depth=3):
    def make():
        return ServeEngine(model, variables, max_slots=2, prefill_len=16,
                           max_queue_depth=max_queue_depth,
                           backoff_sleep=_no_sleep)
    return make


# ---------------------------------------------------------- chaos matrix
@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_autoscale_flap_chaos_matrix(gpt_setup, pin_zero_recompiles, seed):
    """Flapping load with a kill mid-scale-down: storm -> scale-up,
    calm-with-live-streams -> migration scale-down, and the FIRST
    migration target dies while the scale-down restore is in flight
    (cascade onto the remaining survivors), then a second storm flaps
    capacity back up. Every admitted request FINISHES token-exact vs
    the oracle; zero recompiles on every surviving replica."""
    model, variables = gpt_setup
    clock = _FakeClock(50.0)
    tracer = RequestTracer()
    armed = {}
    factory = _engine_factory(model, variables)

    class DiesMidRestore(LocalReplica):
        def restore(self, pairs):
            if armed.pop("on", None):
                raise ReplicaDied(self.replica_id,
                                  "killed during someone else's "
                                  "scale-down migration")
            super().restore(pairs)

    def make_replica(rid):
        return DiesMidRestore(rid, factory)

    fleet = FleetRouter(
        [make_replica(0), make_replica(1)],
        affinity_block_size=8, affinity_blocks=1, respawn=False,
        clock=clock, tracer=tracer,
        admission=AdmissionControl(
            detector_kw=dict(window_s=1.0, min_samples=4),
            # The ladder armed but parked far above the autoscaler's
            # band: rung 2 would CAP max_new_tokens and break the
            # oracle comparison this matrix pins.
            brownout_kw=dict(high=0.9, low=0.05)))
    # up_load high enough that the projection guard does not veto the
    # calm-phase shrink (the survivors CAN absorb ~8 requests here);
    # the guard has its own discriminative test below.
    FleetAutoscaler(fleet, make_replica, min_replicas=2, max_replicas=4,
                    up_pressure=0.15, down_pressure=0.02,
                    up_load=8.0, down_load=6.0,
                    up_hold_s=0.1, down_hold_s=0.3, cooldown_s=0.2)
    fleet = pin_zero_recompiles(fleet)
    rng = np.random.default_rng(seed)
    handles = []

    def submit_burst(n, lo, hi):
        for _ in range(n):
            p = rng.integers(0, 32,
                             size=int(rng.integers(6, 14))).astype(np.int32)
            n_new = int(rng.integers(lo, hi))
            try:
                h = fleet.submit(p, n_new)
            except QueueFull:
                continue
            handles.append((h, _ref_greedy(model, variables, p, n_new)))

    # Phase 1 — storm: 16 submits against 2x(2 slots + 3 queue): the
    # overflow sheds feed the detector, and capacity scales up.
    submit_burst(16, 3, 7)
    for _ in range(60):
        fleet.step()
        clock.now += 0.05
        if not fleet.has_work:
            break
    assert fleet.metrics.scale_up_events >= 1
    assert not fleet.has_work
    n_after_storm = len(fleet.replicas)
    assert n_after_storm >= 3

    # Phase 2 — calm with LIVE streams. First age the storm out of the
    # detector's 1 s window in one jump (a single tick arms the
    # down-hold but cannot satisfy it), so no late scale-up can seat an
    # EMPTY replica as the future scale-down victim; then load every
    # replica with long decodes. The down-hold expires a few ticks in,
    # mid-stream, and the scale-down live-migrates running work — and
    # the armed death takes out the first migration TARGET while that
    # migration is in flight.
    # Streams of 40+ tokens: long enough to outlive the worst-case
    # scale-down arming (a load-up shed can hold pressure in the dead
    # band for a full detector window before the down-hold even starts).
    clock.now += 1.2
    fleet.step()
    for _ in range(40):
        if all(s.load >= 2 for s in fleet.replicas if s.available):
            break
        submit_burst(1, 40, 48)
    assert all(s.load >= 2 for s in fleet.replicas if s.available)
    armed["on"] = True
    for _ in range(500):
        fleet.step()
        clock.now += 0.05
        if not fleet.has_work:
            break
    # A post-kill scale-up is legitimate (the cascade concentrates load
    # on the survivor and the load trigger replaces the loss); what the
    # matrix pins is that the scale-down MIGRATED live work.
    assert fleet.metrics.scale_down_events >= 1, \
        "the calm phase never scaled down"
    assert fleet.metrics.scale_down_migrated >= 2
    assert fleet.metrics.migrated_via_drain >= 1  # live migration path
    assert not armed, "the mid-migration kill never fired"
    assert fleet.metrics.replica_down_events >= 1  # the killed target
    assert not fleet.has_work

    # Phase 3 — the flap: storm again on the shrunken fleet.
    submit_burst(16, 3, 7)
    for _ in range(120):
        fleet.step()
        clock.now += 0.05
        if not fleet.has_work:
            break
    assert not fleet.has_work
    assert fleet.metrics.scale_up_events >= 2  # both storms grew it

    finished = 0
    for h, ref in handles:
        assert h.done, f"request {h} never reached a terminal state"
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref, \
            f"stream diverged (seed {seed}): {h}"
        finished += 1
    assert finished == len(handles)
    assert fleet.metrics.requests_failed == 0
    assert fleet.metrics.requests_orphaned == 0
    # The whole episode is visible: scale events traced, exposition
    # (autoscale series included) re-parses through the strict referee.
    assert tracer.events_named("scale_up")
    assert tracer.events_named("scale_down")
    assert tracer.events_named("replica_down")
    samples, types = parse_prometheus_text(fleet_exposition(fleet))
    assert samples[("pddl_fleet_scale_up_events_total", ())] >= 2.0
    assert samples[("pddl_fleet_scale_down_events_total", ())] >= 1.0
    assert types["pddl_fleet_scale_down_migrated_total"] == "counter"
    assert samples[("pddl_fleet_autoscale_scale_up_completed_total",
                    ())] >= 2.0
    assert ("pddl_fleet_autoscale_replicas", ()) in samples


# ------------------------------------------------------- control policy
def test_scale_up_engages_before_brownout_ladder(gpt_setup):
    """The capacity-first contract: at pressure between the
    autoscaler's up_pressure and the ladder's high mark, a replica is
    spawned while the rung stays NORMAL — brownout is the last resort,
    not the first response."""
    model, variables = gpt_setup
    clock = _FakeClock(10.0)
    factory = _engine_factory(model, variables)
    admission = AdmissionControl(
        detector_kw=dict(window_s=10.0, min_samples=4),
        brownout_kw=dict(high=0.5, low=0.05, escalate_hold_s=0.0))
    fleet = FleetRouter([LocalReplica(0, factory)], respawn=False,
                        clock=clock, admission=admission)
    scaler = FleetAutoscaler(fleet, lambda rid: LocalReplica(rid, factory),
                             min_replicas=1, max_replicas=2,
                             up_pressure=0.2, down_pressure=0.02,
                             up_hold_s=0.2, down_hold_s=5.0,
                             cooldown_s=0.1)
    # One third rejected: pressure ~0.33 — above up_pressure (0.2),
    # below the ladder's high (0.5).
    for i in range(12):
        admission.observe(clock.now, rejected=(i % 3 == 0))
    assert scaler.step(clock.now) is ScaleDecision.HOLD  # hold arming
    clock.now += 0.25
    assert scaler.step(clock.now) is ScaleDecision.SCALE_UP
    assert len(fleet.replicas) == 2
    assert int(admission.rung) == 0  # ladder never engaged
    assert scaler.metrics.scale_up_completed == 1


def test_spawn_timeout_fails_fast_with_breaker_backoff(gpt_setup):
    """A wedged spawn raises the typed ReplicaSpawnTimeout out of the
    poll; the attempt fails WITHOUT blocking the loop, and retries are
    gated by a doubling backoff that resets on success."""
    model, variables = gpt_setup
    clock = _FakeClock(0.0)
    factory = _engine_factory(model, variables)

    class WedgedDriver:
        def __init__(self, rid):
            self.replica_id = rid

        def poll_ready(self):
            raise ReplicaSpawnTimeout(self.replica_id, 1.0)

    spawned = []

    def make(rid):
        spawned.append(rid)
        if len(spawned) < 3:
            return WedgedDriver(rid)
        return LocalReplica(rid, factory)

    fleet = FleetRouter([LocalReplica(0, factory)], respawn=False,
                        clock=clock)
    scaler = FleetAutoscaler(fleet, make, min_replicas=1, max_replicas=2,
                             up_pressure=0.9, down_pressure=0.02,
                             up_load=1.0, down_load=0.0,
                             up_hold_s=0.0, down_hold_s=99.0,
                             cooldown_s=0.0,
                             spawn_backoff_base_s=1.0,
                             spawn_backoff_max_s=8.0)
    fleet.submit(list(range(1, 9)), 4)  # load >= up_load arms want_up
    scaler.step(clock.now)  # attempt 1: wedged -> typed failure
    assert scaler.metrics.spawn_timeouts == 1
    assert scaler.metrics.scale_up_failed == 1
    assert len(spawned) == 1
    # Inside the backoff window: no new spawn, however hot the signal.
    clock.now += 0.5
    for _ in range(3):
        scaler.step(clock.now)
    assert len(spawned) == 1
    # Past the first backoff (1 s): attempt 2 fails too, backoff
    # doubles; attempt 3 only fires after ~2 s more.
    clock.now += 1.0
    scaler.step(clock.now)       # re-arm the hold at the new now
    scaler.step(clock.now)       # attempt 2 (hold 0): wedged again
    assert len(spawned) == 2
    clock.now += 1.0
    scaler.step(clock.now)
    assert len(spawned) == 2     # doubled backoff still gating
    clock.now += 1.5
    scaler.step(clock.now)
    assert len(spawned) == 3     # attempt 3: a real replica joins
    assert scaler.metrics.scale_up_completed == 1
    assert len(fleet.replicas) == 2
    # Success reset the backoff for the NEXT incident.
    assert scaler.gauges()["spawn_backoff_s"] == 1.0
    fleet.close()


def test_scale_down_projection_guard_vetoes_unabsorbable_shrink(
        gpt_setup):
    """The survivors-must-absorb rule: with total load that would push
    the remaining replicas back over the scale-up band, the controller
    refuses to shrink (a scale-down that causes the next scale-up is
    flapping with extra steps)."""
    model, variables = gpt_setup
    clock = _FakeClock(0.0)
    factory = _engine_factory(model, variables, max_queue_depth=16)
    fleet = FleetRouter([LocalReplica(0, factory),
                         LocalReplica(1, factory)],
                        respawn=False, clock=clock)
    scaler = FleetAutoscaler(fleet, lambda rid: LocalReplica(rid, factory),
                             min_replicas=1, max_replicas=2,
                             up_pressure=0.9, down_pressure=0.5,
                             up_load=4.0, down_load=4.0,
                             up_hold_s=0.0, down_hold_s=0.1,
                             cooldown_s=0.0)
    # 7 requests over 2 replicas: mean 3.5 <= down_load arms the
    # shrink, but 7 / 1 survivor = 7 >= up_load vetoes it.
    for i in range(7):
        fleet.submit(list(range(1, 8)), 3)
    clock.now += 0.2
    scaler.step(clock.now)
    clock.now += 0.2
    assert scaler.step(clock.now) is ScaleDecision.HOLD
    assert scaler.metrics.scale_down_vetoed >= 1
    assert len(fleet.replicas) == 2
    fleet.run(max_steps=400)
    fleet.close()


# ------------------------------------------------------ router mechanics
def test_router_scale_down_live_migrates_token_exact(gpt_setup):
    """The mechanism alone: scale_down drains the victim and restores
    its queued+running streams on the survivor, token-exact, counted
    as drain-path migration; the last replica refuses to retire."""
    model, variables = gpt_setup
    factory = _engine_factory(model, variables, max_queue_depth=16)
    fleet = FleetRouter([LocalReplica(0, factory),
                         LocalReplica(1, factory)],
                        affinity_block_size=8, affinity_blocks=1,
                        respawn=False)
    reqs = [(list(range(1, 9)), 6), (list(range(3, 10)), 5),
            ((np.arange(8) * 3 + 1) % 32, 7)]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()
    victim = max(fleet.replicas, key=lambda s: s.load)
    moved = fleet.scale_down(victim.replica_id)
    assert moved == victim.load or moved >= 1
    assert len(fleet.replicas) == 1
    assert fleet.metrics.scale_down_events == 1
    assert fleet.metrics.migrated_via_drain >= 1
    assert fleet.metrics.migrated_via_replay == 0
    fleet.run(max_steps=400)
    for h, ref in zip(handles, refs):
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref, "stream diverged across scale-down"
    with pytest.raises(ValueError, match="no other available"):
        fleet.scale_down(fleet.replicas[0].replica_id)
    fleet.close()


def test_router_scale_up_revives_orphans(gpt_setup):
    """A scale-up during a total outage is also a recovery: parked
    orphans re-enter on the new replica and finish token-exact."""
    from pddl_tpu.serve import FaultKind, FaultPlan

    model, variables = gpt_setup
    clock = _FakeClock()
    plan = FaultPlan(sleep_fn=_no_sleep)

    def make():
        return ServeEngine(model, variables, max_slots=2, prefill_len=16,
                           max_queue_depth=8,
                           fault_plan=plan, backoff_sleep=_no_sleep)

    fleet = FleetRouter([LocalReplica(0, make)], respawn=True,
                        clock=clock)
    p, n = list(range(1, 9)), 6
    ref = _ref_greedy(model, variables, p, n)
    h = fleet.submit(p, n)
    plan._sched[(2, "tick")] = [FaultKind.KILL]
    fleet.run(max_steps=20)
    assert fleet.metrics.requests_orphaned == 1
    assert not h.done
    factory = _engine_factory(model, variables)
    fleet.scale_up(LocalReplica(7, factory))
    assert fleet.metrics.scale_up_events == 1
    fleet.run(max_steps=200)
    assert h.state == RequestState.FINISHED
    assert h.tokens == ref
    assert h.replica_id == 7
    fleet.close()


def test_process_replica_wait_ready_timeout_is_typed():
    """A worker that never acks ready: wait_ready(timeout_s=...) and
    poll_ready() both raise the typed ReplicaSpawnTimeout (a
    ReplicaDied subclass, so every existing handler still catches it)
    and put the wedged process down."""

    class SleeperReplica(ProcessReplica):
        def _worker_argv(self):
            return [sys.executable, "-c", "import time; time.sleep(60)"]

    rep = SleeperReplica(0, {}, wait_ready=False, ready_timeout_s=0.2)
    try:
        with pytest.raises(ReplicaSpawnTimeout) as exc:
            rep.wait_ready(timeout_s=0.2)
        assert isinstance(exc.value, ReplicaDied)
        assert exc.value.waited_s >= 0.2
        deadline = time.monotonic() + 10
        while rep._proc.poll() is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert rep._proc.poll() is not None  # wedged spawn put down
    finally:
        if rep._proc.poll() is None:
            rep._proc.kill()
    rep2 = SleeperReplica(1, {}, wait_ready=False, ready_timeout_s=0.15)
    try:
        assert rep2.poll_ready() is False  # non-blocking while in budget
        deadline = time.monotonic() + 10
        with pytest.raises(ReplicaSpawnTimeout):
            while time.monotonic() < deadline:
                rep2.poll_ready()
                time.sleep(0.02)
    finally:
        if rep2._proc.poll() is None:
            rep2._proc.kill()


# ------------------------------------------------------- trace generator
def test_tracegen_is_seeded_and_diurnal():
    adapters = [f"a{i}" for i in range(6)]
    ev1, mean1 = diurnal_trace(3000, 64, seed=5, duration_s=100.0,
                               periods=1.0, peak_to_trough=6.0,
                               adapters=adapters)
    ev2, mean2 = diurnal_trace(3000, 64, seed=5, duration_s=100.0,
                               periods=1.0, peak_to_trough=6.0,
                               adapters=adapters)
    assert mean1 == mean2
    assert [(e["t"], e["session"], tuple(e["prompt"])) for e in ev1] \
        == [(e["t"], e["session"], tuple(e["prompt"])) for e in ev2]
    assert len(ev1) == 3000
    ts = np.array([e["t"] for e in ev1])
    # Sessions STARTING near the end spill their later turns past the
    # nominal day (think time is real time); the spill is bounded.
    assert (np.diff(ts) >= 0).all() and ts[0] >= 0 and ts[-1] <= 110.0
    # Diurnal shape (phase starts at the trough, peaks mid-trace): the
    # peak decile carries several times the trough deciles' arrivals.
    peak = ((ts >= 45) & (ts <= 55)).sum()
    trough = ((ts <= 5).sum() + (ts >= 95).sum())
    assert peak / max(trough, 1) > 2.5
    # Priority mix ~ 35/15/50 (sessions weight it by their turns).
    fracs = {p: np.mean([e["priority"] is p for e in ev1])
             for p in Priority}
    assert 0.2 < fracs[Priority.INTERACTIVE] < 0.5
    assert 0.05 < fracs[Priority.BATCH] < 0.3
    assert 0.35 < fracs[Priority.BEST_EFFORT] < 0.65
    for e in ev1:
        if e["priority"] is Priority.INTERACTIVE:
            assert e["deadline_s"] is not None
        else:
            assert e["deadline_s"] is None
    # Heavy-tail outputs: most replies short, a real tail, hard cap.
    news = np.array([e["new_tokens"] for e in ev1])
    assert np.percentile(news, 50) <= 12
    assert news.max() <= 48 and (news > 24).sum() >= 10
    # Zipf adapter popularity: the head adapter dominates, a no-adapter
    # slice survives, sessions keep their tenant across turns.
    counts = {}
    for e in ev1:
        counts[e["adapter"]] = counts.get(e["adapter"], 0) + 1
    named = {a: n for a, n in counts.items() if a is not None}
    assert max(named, key=named.get) == "a0"
    assert named["a0"] > 1.5 * named[min(named, key=named.get)]
    assert counts.get(None, 0) > 0
    by_session = {}
    for e in ev1:
        by_session.setdefault(e["session"], set()).add(e["adapter"])
    assert all(len(a) == 1 for a in by_session.values())


# --------------------------------------------------------- replay client
def test_replay_client_honors_retry_after_hints(gpt_setup):
    """The satellite fix: a rate-limited submit re-enters at
    ``now + retry_after_s`` and eventually lands — with hints off, the
    same events are terminally shed. Replica-hours are metered."""
    model, variables = gpt_setup
    factory = _engine_factory(model, variables, max_queue_depth=16)

    def fresh_fleet():
        fleet = FleetRouter(
            [LocalReplica(0, factory)], respawn=False,
            admission=AdmissionControl(
                rates={Priority.INTERACTIVE: 4.0}, burst=1.0))
        fleet.warmup()  # compile outside the replay's real-time window
        return fleet

    schedule = [dict(t=0.01 * i, session=f"s{i}",
                     prompt=list(range(1, 7)), new_tokens=2,
                     priority=Priority.INTERACTIVE, deadline_s=None,
                     adapter=None) for i in range(3)]
    fleet = fresh_fleet()
    rep = replay_trace(fleet, schedule, honor_hints=True, hang_s=30.0)
    fleet.close()
    assert rep.all_terminal
    assert len(rep.handles) == 3          # every event landed...
    assert rep.retried_after_hint >= 2    # ...two after their hints
    assert rep.hinted_rejects >= 2
    assert sum(rep.rejects.values()) == 0
    assert rep.wall_s >= 0.3              # the hints were real waits
    # One replica the whole run: replica-hours ~ wall clock.
    assert rep.replica_seconds == pytest.approx(rep.wall_s, rel=0.2)
    assert rep.goodput_tokens == 6
    assert rep.goodput_per_replica_hour > 0
    fleet = fresh_fleet()
    rep_blind = replay_trace(fleet, schedule, honor_hints=False,
                             hang_s=30.0)
    fleet.close()
    assert sum(rep_blind.rejects.values()) == 2  # dropped, the old way


def test_replay_meters_rung_time_and_scaled_fleet(gpt_setup):
    """An autoscaled fleet under a compressed diurnal burst: the
    replay meters replica-seconds through the scale events and the
    report's handles all settle; scale events show up in the
    exposition-facing counters."""
    model, variables = gpt_setup
    factory = _engine_factory(model, variables)
    fleet = FleetRouter(
        [LocalReplica(0, factory)], respawn=False,
        admission=AdmissionControl(
            detector_kw=dict(window_s=1.0, min_samples=4),
            brownout_kw=dict(high=0.6, low=0.05)))
    FleetAutoscaler(fleet, lambda rid: LocalReplica(rid, factory),
                    min_replicas=1, max_replicas=3,
                    up_pressure=0.1, down_pressure=0.02,
                    up_load=4.0, down_load=1.0,
                    up_hold_s=0.02, down_hold_s=0.4, cooldown_s=0.05)
    # prompt_cap must fit the engines' prefill_len (16): an oversize
    # prompt is a ValueError out of submit, and the replay client
    # deliberately lets that CRASH rather than count it as a shed.
    events, _ = diurnal_trace(60, 32, seed=3, duration_s=2.0,
                              periods=1.0, peak_to_trough=8.0,
                              prompt_base=6, prompt_cap=14,
                              max_turns=2, think_time_s=0.05,
                              new_tokens_base=2, new_tokens_scale=2.0,
                              new_tokens_cap=8)
    rep = replay_trace(fleet, events, honor_hints=True, hang_s=60.0)
    snap = fleet.metrics.snapshot()
    fleet.close()
    assert rep.all_terminal
    assert rep.replica_seconds > 0
    assert snap["scale_up_events"] >= 1
    assert len(rep.handles) + sum(rep.rejects.values()) == len(events)
