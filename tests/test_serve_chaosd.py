"""Storage-fault tolerance + multi-plane chaos campaigns (ISSUE 18),
CPU.

The contracts under test:

- **StorageFaultPlan**: seeded EIO/ENOSPC/torn-write/slow-fsync
  injection at exact ``(op, seq)`` coordinates through the journal's
  VFS shim — schedule validation, per-op call counters, the rate
  cascade, the injection cap, observer coordinates, ``quiesce()``.
- **WAL degradation**: transient storage errors retry with bounded
  backoff and never surface; persistent failure degrades the journal
  to NON_DURABLE (acks keep flowing, backlog retained in memory,
  alarmed through metrics/exposition/tracer) with rate-limited re-arm
  probes; ENOSPC skips the blind retry and forces an emergency
  checkpoint+rotate; a mid-checkpoint failure aborts with the
  checkpoint/prev pair still readable (the r10 newest-VERIFIED rule);
  a torn write's tail is repaired before any retry so replay stays
  exact; ``wal_bytes`` reports the last KNOWN size on fstat failure
  instead of lying "empty".
- **Seeded respawn jitter**: a same-instant mass-kill no longer
  schedules every breaker probe (or autoscaler spawn retry) at the
  same instant — subtractive jitter, so no probe ever fires LATER
  than the deterministic schedule.
- **3-seed storage-chaos matrix**: EIO storm over live token-delta
  fsyncs / ENOSPC at the checkpoint rotate / replica kill while
  NON_DURABLE — each followed by a router crash and
  ``FleetRouter.recover``, every stream token-exact vs the greedy
  oracle, zero recompiles on the recovered fleet, ``read_state``
  bit-stable across reads.
- **ChaosConductor campaigns** (marker ``chaosd``): seeded randomized
  multi-plane schedules (storage storm + hard kill + router crash)
  against unified and disaggregated+tiered fleets, judged by the
  invariant referee.
"""

import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.chaos import ChaosConductor, ReplicaChaos, local_kill
from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.obs import RequestTracer, fleet_exposition, parse_prometheus_text
from pddl_tpu.serve import FaultPlan, ServeEngine
from pddl_tpu.serve.fleet import (
    BreakerState,
    CircuitBreaker,
    FleetAutoscaler,
    FleetRouter,
    LocalReplica,
    ReplicaSpawnTimeout,
    RouterJournal,
)
from pddl_tpu.serve.fleet import journal as journal_io
from pddl_tpu.serve.request import RequestState
from pddl_tpu.utils.faults import (
    StorageFaultKind,
    StorageFaultPlan,
    StorageFaultSpec,
)
from conftest import ref_greedy as _ref_greedy, FakeClock

pytestmark = pytest.mark.storage

BS = 8


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


def _engine_factory(model, variables, plan=None):
    def make():
        return ServeEngine(model, variables, max_slots=2, prefill_len=16,
                           fault_plan=plan, max_queue_depth=64,
                           backoff_sleep=_no_sleep)
    return make


def _workload(seed, n_requests=4, *, min_len=8, max_len=13, n_new=8):
    """Unique seeded prompts (uniqueness keys the token-exact check
    across a crash) with one fixed continuation length, so the oracle
    compiles a handful of shapes, not one per stream."""
    rng = np.random.default_rng(seed)
    reqs, seen = [], set()
    while len(reqs) < n_requests:
        plen = int(rng.integers(min_len, max_len))
        p = rng.integers(0, 32, size=plen).astype(np.int32)
        key = tuple(int(t) for t in p)
        if key in seen:
            continue
        seen.add(key)
        reqs.append((p, n_new))
    return reqs


# ------------------------------------------------------ StorageFaultPlan
def test_storage_plan_validation():
    with pytest.raises(ValueError, match="eio_rate"):
        StorageFaultPlan(eio_rate=1.2)
    with pytest.raises(ValueError, match="sum"):
        StorageFaultPlan(eio_rate=0.6, torn_rate=0.6)
    with pytest.raises(ValueError, match="unknown storage op"):
        StorageFaultPlan(ops=("scribble",))
    with pytest.raises(ValueError, match="unknown scheduled op"):
        StorageFaultPlan(scheduled=(
            StorageFaultSpec("scribble", 0, StorageFaultKind.EIO),))
    with pytest.raises(ValueError, match="seq"):
        StorageFaultPlan(scheduled=(
            StorageFaultSpec("write", -1, StorageFaultKind.EIO),))
    with pytest.raises(ValueError, match="count"):
        StorageFaultPlan(scheduled=(
            StorageFaultSpec("write", 0, StorageFaultKind.EIO, count=0),))
    with pytest.raises(ValueError, match="unknown storage op"):
        StorageFaultPlan().check("scribble")


def test_storage_plan_scheduled_coordinates_fire_exactly():
    plan = StorageFaultPlan(scheduled=(
        StorageFaultSpec("write", 1, StorageFaultKind.EIO, count=2),))
    coords = []
    plan.on_inject = lambda seq, op, kind: coords.append((seq, op, kind))
    assert plan.check("write") is None            # seq 0: clean
    for _ in range(2):                            # seqs 1-2: the spec
        with pytest.raises(OSError):
            plan.check("write")
    assert plan.check("write") is None            # seq 3: spent
    assert plan.check("fsync") is None            # other ops untouched
    assert coords == [(1, "write", "eio"), (2, "write", "eio")]
    assert plan.calls["write"] == 4 and plan.calls["fsync"] == 1
    assert plan.injected[StorageFaultKind.EIO] == 2
    assert plan.total_injected == 2


def test_storage_plan_rate_cascade_cap_and_quiesce():
    plan = StorageFaultPlan(seed=3, eio_rate=1.0,
                            max_random_injections=2)
    for _ in range(2):
        with pytest.raises(OSError):
            plan.check("fsync")
    assert plan.check("fsync") is None  # cap: chaos runs terminate
    assert plan.total_injected == 2

    slept = []
    slow = StorageFaultPlan(seed=0, slow_rate=1.0, slow_s=0.123,
                            sleep_fn=slept.append)
    assert slow.check("fsync") is None  # SLOW returns normally...
    assert slept == [0.123]             # ...after the injected stall
    slow.quiesce()
    slept.clear()
    assert slow.check("fsync") is None
    assert slept == []                  # repaired disk: rates cleared


# ------------------------------------------------- journal degradation
def _journal(d, sp=None, **kw):
    kw.setdefault("retry_backoff_s", 0.0)
    kw.setdefault("sleep_fn", _no_sleep)
    return RouterJournal(str(d), storage_plan=sp, **kw)


def test_transient_write_error_retries_without_degrading(tmp_path):
    sp = StorageFaultPlan(scheduled=(
        StorageFaultSpec("write", 0, StorageFaultKind.EIO),))
    j = _journal(tmp_path / "wal", sp)
    j.append({"k": 1}, durable=True)
    assert not j.non_durable
    assert j.storage_errors == 1       # counted, then retried past
    assert j.degraded_events == 0
    assert len(list(journal_io.iter_wal_records(j.wal_path))) == 1
    j.close()


def test_persistent_fsync_failure_degrades_then_rearms(tmp_path):
    clock = FakeClock(0.0)
    sp = StorageFaultPlan(eio_rate=1.0, ops=("fsync",))
    events = []
    j = _journal(tmp_path / "wal", sp, retry_limit=2,
                 rearm_interval_s=1.0, clock=clock)
    j.on_storage_event = lambda ev, detail: events.append(ev)
    j.append({"k": 1}, durable=True)   # NEVER raises: degrades instead
    assert j.non_durable and j.degraded_events == 1
    assert j.storage_errors == 3       # retry_limit + 1 attempts
    assert "journal_degraded" in events
    j.append({"k": 2})                 # acks keep flowing
    j.append({"k": 3})
    # Probes are rate-limited: ticks inside the interval do not hammer
    # the dead disk.
    errs = j.storage_errors
    for _ in range(5):
        j.tick()
    assert j.storage_errors == errs
    clock.now = 1.5
    j.tick()                           # due probe, disk still dead
    assert j.storage_errors == errs + 1 and j.non_durable
    sp.quiesce()                       # the disk comes back
    clock.now = 3.0
    j.tick()                           # due probe -> full flush+fsync
    assert not j.non_durable and j.rearms == 1
    assert "journal_rearmed" in events
    # The retained backlog became durable at re-arm: nothing was lost.
    assert len(list(journal_io.iter_wal_records(j.wal_path))) == 3
    j.close()


def test_enospc_forces_emergency_checkpoint_that_reclaims(tmp_path):
    sp = StorageFaultPlan(scheduled=(
        StorageFaultSpec("write", 1, StorageFaultKind.ENOSPC),))
    j = _journal(tmp_path / "wal", sp)
    j.append({"k": 1}, durable=True)
    j.append({"k": 2}, durable=True)   # write seq 1: disk full
    assert j.emergency_checkpoint_due  # no blind retry on a full disk
    assert j.non_durable
    assert j.storage_errors == 1       # ENOSPC broke out of the retries
    assert j.checkpoint([(1, {"prompt": [1], "tokens": []})], next_rid=2)
    assert not j.emergency_checkpoint_due
    assert not j.non_durable and j.rearms == 1
    assert os.path.exists(j.wal_prev_path)  # the rotate reclaimed space
    cp = journal_io.load_checkpoint(str(tmp_path / "wal"))
    assert cp is not None and cp["next_rid"] == 2
    assert j.records_since_checkpoint == 0
    j.close()


def test_checkpoint_failure_keeps_newest_verified_pair(tmp_path):
    # Replace seqs: cp1 consumes 0 (promote) + 1 (rotate); cp2 demotes
    # at 2, then EIO at 3 kills the promotion — the worst interleaving.
    sp = StorageFaultPlan(scheduled=(
        StorageFaultSpec("replace", 3, StorageFaultKind.EIO),))
    j = _journal(tmp_path / "wal", sp)
    d = str(tmp_path / "wal")
    j.append({"k": 1}, durable=True)
    assert j.checkpoint([(1, {"a": 1})], next_rid=2)
    j.append({"k": 2}, durable=True)
    events = []
    j.on_storage_event = lambda ev, detail: events.append(ev)
    assert not j.checkpoint([(1, {"a": 1}), (2, {"b": 2})], next_rid=3)
    assert "journal_checkpoint_failed" in events
    assert j.non_durable
    # The r10 rule: the pair still holds a VERIFIED checkpoint (cp1,
    # demoted to the prev slot) and the WAL records since it — the
    # failed cycle lost nothing.
    cp = journal_io.load_checkpoint(d)
    assert cp is not None and cp["next_rid"] == 2
    assert [rec["k"] for _, rec in
            journal_io.iter_wal_records(j.wal_path)] == [2]
    # The disk recovers: the next cycle completes and re-arms.
    assert j.checkpoint([(1, {"a": 1}), (2, {"b": 2})], next_rid=3)
    assert not j.non_durable and j.rearms == 1
    assert journal_io.load_checkpoint(d)["next_rid"] == 3
    j.close()


def test_torn_write_tail_repaired_before_retry(tmp_path):
    sp = StorageFaultPlan(scheduled=(
        StorageFaultSpec("write", 0, StorageFaultKind.TORN),))
    j = _journal(tmp_path / "wal", sp)
    j.append({"k": 1}, durable=True)
    assert not j.non_durable
    assert sp.injected[StorageFaultKind.TORN] == 1
    # The half-written garbage was truncated before the retry: the
    # file holds exactly one readable frame, no buried tail.
    assert [rec["k"] for _, rec in
            journal_io.iter_wal_records(j.wal_path)] == [1]
    assert os.path.getsize(j.wal_path) == j.wal_bytes
    j.close()


def test_wal_bytes_returns_last_known_on_fstat_failure(tmp_path):
    sp = StorageFaultPlan(scheduled=(
        StorageFaultSpec("fstat", 1, StorageFaultKind.EIO),))
    j = _journal(tmp_path / "wal", sp)
    j.append({"k": 1}, durable=True)
    wb = j.wal_bytes
    assert wb > 0
    errs = j.storage_errors
    assert j.wal_bytes == wb           # last KNOWN size, not 0
    assert j.storage_errors == errs + 1  # ...and the error is counted
    assert j.wal_bytes == wb           # fstat healthy again
    assert j.storage_errors == errs + 1
    j.close()


# ------------------------------------------------- router integration
def test_router_surfaces_degradation_and_rearm(gpt_setup, tmp_path):
    model, variables = gpt_setup
    sp = StorageFaultPlan(eio_rate=1.0, ops=("fsync",))
    j = _journal(tmp_path / "wal", sp, retry_limit=1,
                 rearm_interval_s=0.0)
    tracer = RequestTracer()
    fleet = FleetRouter(
        [LocalReplica(i, _engine_factory(model, variables))
         for i in range(2)],
        journal=j, tracer=tracer, affinity_block_size=BS,
        affinity_blocks=1, respawn=False)
    reqs = _workload(11, n_requests=2, n_new=4)
    refs = {tuple(int(t) for t in p): _ref_greedy(model, variables, p, n)
            for p, n in reqs}
    handles = [fleet.submit(p, n) for p, n in reqs]
    fleet.step()
    m = fleet.metrics
    assert j.non_durable
    assert m.journal_degraded_events == 1
    assert m.journal_storage_errors >= 1
    assert tracer.events_named("journal_degraded")
    samples, types = parse_prometheus_text(fleet_exposition(fleet))
    assert samples[("pddl_fleet_journal_non_durable", ())] == 1.0
    assert types["pddl_fleet_journal_non_durable"] == "gauge"
    for key in ("journal_storage_errors", "journal_degraded_events",
                "journal_rearms"):
        name = f"pddl_fleet_{key}_total"
        assert types[name] == "counter"
        assert samples[(name, ())] == float(getattr(m, key))
    # The disk comes back: the next tick's probe re-arms, and the
    # degraded window cost the streams nothing.
    sp.quiesce()
    fleet.run(max_steps=500)
    assert not j.non_durable
    assert m.journal_rearms >= 1
    assert tracer.events_named("journal_rearmed")
    samples, _ = parse_prometheus_text(fleet_exposition(fleet))
    assert samples[("pddl_fleet_journal_non_durable", ())] == 0.0
    for h in handles:
        assert h.state == RequestState.FINISHED
        assert h.tokens == refs[tuple(int(t) for t in h.request.prompt)]
    fleet.close()
    # Unarmed fleet: present-but-unobserved, NaN.
    bare = FleetRouter(
        [LocalReplica(0, _engine_factory(model, variables))],
        respawn=False)
    samples, _ = parse_prometheus_text(fleet_exposition(bare))
    assert math.isnan(samples[("pddl_fleet_journal_non_durable", ())])
    bare.close()


# --------------------------------------------- seeded respawn jitter
def test_breaker_jitter_is_subtractive_seeded_and_validated():
    def opened(seed, frac):
        b = CircuitBreaker(failure_threshold=1, backoff_base_s=2.0,
                           backoff_max_s=30.0, jitter_frac=frac,
                           seed=seed)
        b.record_failure(100.0)
        assert b.state is BreakerState.OPEN
        return b.open_until_s
    # Subtractive: never LATER than the deterministic schedule.
    assert opened(None, 0.0) == 102.0
    a, b = opened(0, 0.25), opened(1, 0.25)
    assert 100.0 < a <= 102.0 and 100.0 < b <= 102.0
    assert a != b                      # per-seed desynchronization
    assert opened(7, 0.25) == opened(7, 0.25)  # deterministic per seed
    with pytest.raises(ValueError, match="jitter_frac"):
        CircuitBreaker(jitter_frac=1.0)


def test_same_instant_double_kill_respawn_probes_diverge(gpt_setup):
    """The respawn-herd pin: both replicas die in the SAME router step
    (same clock instant), yet their HALF_OPEN probes land at different
    instants — the router arms per-replica seeded jitter fleet-wide.
    The orphaned streams still revive token-exact."""
    model, variables = gpt_setup
    clock = FakeClock(0.0)
    plans = [FaultPlan(sleep_fn=_no_sleep) for _ in range(2)]
    fleet = FleetRouter(
        [LocalReplica(i, _engine_factory(model, variables, plans[i]))
         for i in range(2)],
        affinity_block_size=BS, affinity_blocks=1, respawn=True,
        clock=clock)
    reqs = _workload(21, n_requests=2, n_new=6)
    refs = {tuple(int(t) for t in p): _ref_greedy(model, variables, p, n)
            for p, n in reqs}
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()
    for plan in plans:
        local_kill(plan)
    fleet.step()                       # both die at the same instant
    slots = list(fleet.replicas)
    assert all(s.breaker.state is BreakerState.OPEN for s in slots)
    assert all(s.breaker.jitter_frac > 0.0 for s in slots)
    opens = [s.breaker.open_until_s for s in slots]
    assert opens[0] != opens[1]        # the herd is desynchronized
    assert all(clock.now < o <= clock.now + 0.5 for o in opens)
    clock.now += 1.0                   # past both (jittered) probes
    fleet.run(max_steps=800)
    for h in handles:
        assert h.state == RequestState.FINISHED
        assert h.tokens == refs[tuple(int(t) for t in h.request.prompt)]
    fleet.close()


def test_autoscaler_spawn_retry_jitter_diverges(gpt_setup):
    model, variables = gpt_setup
    fleet = FleetRouter(
        [LocalReplica(0, _engine_factory(model, variables))],
        respawn=False)
    mk = lambda rid: LocalReplica(rid, _engine_factory(model, variables))

    def failed_retry_at(seed, frac):
        s = FleetAutoscaler(fleet, mk, min_replicas=1, max_replicas=2,
                            spawn_backoff_base_s=4.0,
                            spawn_backoff_max_s=16.0,
                            spawn_jitter_frac=frac,
                            spawn_jitter_seed=seed)
        s._spawn_failed(100.0, 9, ReplicaSpawnTimeout(9, 1.0))
        return s._spawn_retry_at

    assert failed_retry_at(None, 0.0) == 104.0  # exact schedule default
    a, b = failed_retry_at(0, 0.5), failed_retry_at(1, 0.5)
    assert 100.0 < a <= 104.0 and 100.0 < b <= 104.0
    assert a != b
    with pytest.raises(ValueError, match="spawn_jitter_frac"):
        FleetAutoscaler(fleet, mk, min_replicas=1, max_replicas=2,
                        spawn_jitter_frac=1.0)
    fleet.close()


# ------------------------------------- 3-seed storage-chaos matrix
def _chaos_fleet(model, variables, d, sp, **journal_kw):
    journal_kw.setdefault("fsync_batch_records", 2)
    plans = [FaultPlan(sleep_fn=_no_sleep) for _ in range(2)]
    j = _journal(d, sp, retry_limit=1, rearm_interval_s=0.0,
                 **journal_kw)
    fleet = FleetRouter(
        [LocalReplica(i, _engine_factory(model, variables, plans[i]))
         for i in range(2)],
        journal=j, affinity_block_size=BS, affinity_blocks=1,
        respawn=False)
    return fleet, plans, j


@pytest.mark.parametrize("seed,scenario", [
    (0, "eio_storm"),          # every disk op EIOs while tokens flow
    (1, "enospc_rotate"),      # disk full exactly at the WAL rotate
    (2, "kill_non_durable"),   # replica hard-death inside the window
])
def test_storage_chaos_recovery_token_exact(gpt_setup, tmp_path, seed,
                                            scenario):
    model, variables = gpt_setup
    d = tmp_path / "wal"
    if scenario == "enospc_rotate":
        sp = StorageFaultPlan(seed=seed, scheduled=(
            StorageFaultSpec("replace", 1, StorageFaultKind.ENOSPC),))
        fleet, plans, j = _chaos_fleet(model, variables, d, sp,
                                       checkpoint_every_records=6)
    else:
        sp = StorageFaultPlan(seed=seed)
        fleet, plans, j = _chaos_fleet(model, variables, d, sp)
    reqs = _workload(seed)
    refs = {tuple(int(t) for t in p): _ref_greedy(model, variables, p, n)
            for p, n in reqs}
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()                   # admissions are durable
    if scenario == "eio_storm":
        sp._rates = (1.0, 0.0, 0.0, 0.0)
        for _ in range(4):
            fleet.step()
        assert j.non_durable
        assert fleet.metrics.journal_degraded_events >= 1
    elif scenario == "enospc_rotate":
        for _ in range(6):
            fleet.step()               # checkpoint_due fires in here
        assert sp.injected[StorageFaultKind.ENOSPC] == 1
        assert not j.non_durable       # rotate failure is non-fatal
        assert journal_io.load_checkpoint(str(d)) is not None
    else:                              # kill while NON_DURABLE
        sp._rates = (1.0, 0.0, 0.0, 0.0)
        for _ in range(3):
            fleet.step()
        assert j.non_durable
        local_kill(plans[1])
        for _ in range(2):
            fleet.step()               # replica 1 dies mid-degradation
    finished_pre = [(tuple(int(t) for t in p), list(h.tokens))
                    for (p, _), h in zip(reqs, handles)
                    if h.done and h.state == RequestState.FINISHED]
    # The router crash: abandon it un-closed (what SIGKILL leaves) and
    # recover over the same WAL directory with FRESH replicas. The fold
    # must be bit-stable across reads first (pure function of the dir).
    sp.quiesce()
    assert journal_io.read_state(str(d)) == journal_io.read_state(str(d))
    recovered, revived = FleetRouter.recover(
        str(d),
        [LocalReplica(10 + i, _engine_factory(model, variables))
         for i in range(2)],
        affinity_block_size=BS, affinity_blocks=1, respawn=False)
    for _ in range(600):
        recovered.step()
        if all(fh.done for fh in revived.values()):
            break
    # Token-exact: revived streams continue from the durable mirror and
    # land on the oracle; the NON_DURABLE loss window (fsync-batched
    # token deltas) only shortens the mirror, never corrupts it.
    for fh in revived.values():
        assert fh.state == RequestState.FINISHED
        assert fh.tokens == refs[tuple(int(t) for t in fh.request.prompt)]
    for key, toks in finished_pre:
        assert toks == refs[key]
    counts = recovered.compile_counts()
    assert counts and all(v == 1 for v in counts.values())
    recovered.close()


# --------------------------------------------- conductor campaigns
@pytest.mark.chaosd
@pytest.mark.parametrize("seed", [0, 1])
def test_conductor_campaign_unified_fleet(gpt_setup, tmp_path, seed):
    """Composed planes over a unified 2-replica fleet: a storage storm
    + a seeded hard kill + a router crash in one campaign, all seven
    referee invariants green."""
    model, variables = gpt_setup
    plans = {}
    state = {"base": 0}

    def make_replicas():
        base, state["base"] = state["base"], state["base"] + 10
        reps = []
        for k in range(2):
            plan = FaultPlan(sleep_fn=_no_sleep)
            plans[base + k] = plan
            reps.append(LocalReplica(
                base + k, _engine_factory(model, variables, plan)))
        return reps

    def make_chaos(fleet):
        return [ReplicaChaos(
                    replica_id=int(s.replica_id),
                    plan=plans[int(s.replica_id)],
                    kill_fn=(lambda p=plans[int(s.replica_id)]:
                             local_kill(p)))
                for s in fleet.replicas]

    sp = StorageFaultPlan(seed=seed)
    cond = ChaosConductor(
        make_replicas, make_chaos,
        lambda p, n: _ref_greedy(model, variables, p, n),
        journal_dir=str(tmp_path / "wal"), storage_plan=sp,
        router_kw=dict(affinity_block_size=BS, affinity_blocks=1,
                       respawn=False),
        journal_kw=dict(fsync_batch_records=2, retry_limit=1,
                        retry_backoff_s=0.0, rearm_interval_s=0.0,
                        sleep_fn=_no_sleep),
        recovery_bound_s=30.0, seed=seed)
    report = cond.run(_workload(100 + seed, n_requests=5),
                      planes=("device", "storage", "kill", "router"),
                      horizon=30, kills=1, max_wall_s=90.0)
    assert report.ok, report.violations
    kinds = [a.kind for a in report.actions]
    assert {"storm_on", "kill", "router_crash"} <= set(kinds)
    assert report.recovery_s is not None and report.recovery_s <= 30.0
    assert report.injected.get("storage", 0) >= 1  # the storm landed


@pytest.mark.chaosd
def test_conductor_campaign_disagg_tier_fleet(gpt_setup, tmp_path):
    """The campaign over a role-split fleet with the host tier armed:
    a storage storm degrades the WAL while prefill->decode hand-offs
    run, then the router crashes — recovery re-admits through the
    prefill pool and every invariant (pins balanced across the radix
    trees included) holds."""
    model, variables = gpt_setup
    state = {"base": 0}

    def _factory(host):
        def make():
            return ServeEngine(model, variables, max_slots=2,
                               prefill_len=32, prefix_cache_blocks=24,
                               prefix_block_size=BS, prefix_chunk=BS,
                               host_tier=host, max_queue_depth=64,
                               backoff_sleep=_no_sleep)
        return make

    def make_replicas():
        base, state["base"] = state["base"], state["base"] + 10
        return [LocalReplica(base, _factory(1 << 24), role="prefill"),
                LocalReplica(base + 1, _factory(1 << 24), role="decode")]

    def make_chaos(fleet):
        # No per-replica kill plane: killing the only replica of a
        # role starves its pool. The router-crash plane abandons the
        # whole fleet instead — the mass-failure this fleet shape
        # actually fears.
        return [ReplicaChaos(replica_id=int(s.replica_id))
                for s in fleet.replicas]

    sp = StorageFaultPlan(seed=5)
    cond = ChaosConductor(
        make_replicas, make_chaos,
        lambda p, n: _ref_greedy(model, variables, p, n),
        journal_dir=str(tmp_path / "wal"), storage_plan=sp,
        router_kw=dict(affinity_block_size=BS, affinity_blocks=1,
                       respawn=False),
        journal_kw=dict(fsync_batch_records=2, retry_limit=1,
                        retry_backoff_s=0.0, rearm_interval_s=0.0,
                        sleep_fn=_no_sleep),
        recovery_bound_s=30.0, seed=5)
    report = cond.run(
        _workload(7, n_requests=4, min_len=12, max_len=20, n_new=5),
        planes=("storage", "router"), horizon=30, kills=0,
        max_wall_s=90.0)
    assert report.ok, report.violations
    assert report.invariants["pins_balanced"]
    assert "router_crash" in [a.kind for a in report.actions]
    assert report.recovery_s is not None


# The worker-subprocess model config (mirrors the ctrlplane process
# fleet): the oracle is the worker's OWN engine built from the same
# cfg, so parent and child provably share params.
_WORKER_CFG = dict(vocab=32, max_len=64, embed_dim=32, depth=1, heads=2,
                   slots=4, prefill_len=16, max_queue_depth=64,
                   param_seed=0)


@pytest.mark.chaosd
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_conductor_campaign_seven_planes_process_fleet(tmp_path, seed):
    """ISSUE 20 acceptance: 3-seed campaigns drawing all SEVEN planes
    — device, wire, storage, gray, kill, router, partition — over a
    fleet of REAL worker processes, every referee invariant green
    including ``single_writer`` (no two routers' commands accepted in
    the same epoch interval: 100% of the deposed primary's
    post-partition probes come back typed EpochFenced rejects).

    The partition plane fires strictly before the router-crash window,
    so the promoted standby is the router the crash plane then
    SIGKILLs — hot failover and cold recovery compose in one campaign.
    The device plane rides along declared-but-inert: its injection
    surface is an in-process engine FaultPlan, which does not exist
    behind the worker pipe (the unified local-fleet campaign above
    owns that coverage)."""
    import subprocess
    import sys

    from pddl_tpu.serve.fleet import ProcessReplica, WireFaultPlan
    from pddl_tpu.serve.fleet.worker import build_engine

    wire_plans = {}
    state = {"base": 0}

    def make_replicas():
        base, state["base"] = state["base"], state["base"] + 10
        reps = []
        for k in range(2):
            rid = base + k
            wp = WireFaultPlan(1000 * seed + rid, corrupt_rate=0.01,
                               duplicate_rate=0.01, drop_rate=0.005)
            wire_plans[rid] = wp
            reps.append(ProcessReplica(
                rid, {**_WORKER_CFG, "replica_id": rid},
                python=sys.executable, stderr=subprocess.DEVNULL,
                wire_fault_plan=wp))
        return reps

    def make_chaos(fleet):
        return [ReplicaChaos(replica_id=int(s.replica_id),
                             wire_plan=wire_plans.get(int(s.replica_id)),
                             slow_fn=s.driver.set_tick_delay,
                             kill_fn=s.driver.kill)
                for s in fleet.replicas]

    eng = build_engine(_WORKER_CFG)
    sp = StorageFaultPlan(seed=seed)
    cond = ChaosConductor(
        make_replicas, make_chaos,
        lambda p, n: _ref_greedy(eng.model, {"params": eng._params},
                                 p, n),
        journal_dir=str(tmp_path / "wal"), storage_plan=sp,
        router_kw=dict(affinity_block_size=BS, affinity_blocks=1,
                       respawn=False),
        journal_kw=dict(fsync_batch_records=2, retry_limit=1,
                        retry_backoff_s=0.0, rearm_interval_s=0.0,
                        sleep_fn=_no_sleep),
        recovery_bound_s=60.0, seed=seed)
    report = cond.run(
        _workload(300 + seed, n_requests=4),
        planes=("device", "wire", "storage", "gray", "kill", "router",
                "partition"),
        horizon=30, kills=1, pace_s=0.01, max_wall_s=240.0)
    assert report.ok, report.violations
    assert report.invariants["single_writer"]
    assert "single_writer" not in " ".join(report.skipped)
    kinds = [a.kind for a in report.actions]
    assert {"partition", "router_crash", "kill", "storm_on",
            "slow_on"} <= set(kinds)
    assert report.failover_s is not None and report.failover_s < 10.0
    assert report.recovery_s is not None
    assert report.injected.get("wire", 0) >= 1    # the storm was real
