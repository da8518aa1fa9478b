"""Multi-replica serving fleet (`pddl_tpu/serve/fleet/`), CPU.

The contracts under test:

- **Chaos matrix** (3 seeds x N in {2, 4}, ``@pytest.mark.fleet`` +
  ``chaos``): a seeded kill-point takes one replica down mid-stream;
  every in-flight request reaches a terminal state, every FINISHED
  stream is token-identical to an unkilled oracle run (live migration
  via the drain wire format), and zero recompiles hold on every
  surviving replica (the per-replica ``pin_zero_recompiles``).
- **Routing**: prefix affinity lands shared-prefix prompts on the
  replica whose (shadow) radix cache holds them; sticky sessions keep
  multi-turn traffic in place; rendezvous hashing is deterministic;
  QueueFull sheds to the least-loaded healthy replica and only a
  fleet-wide full rejects, with the smallest retry_after hint.
- **Circuit breaker**: CLOSED→OPEN on consecutive failures, HALF_OPEN
  probe after bounded exponential backoff, probe success respawns the
  replica and returns orphaned requests to service.
- **Hard-kill fallback**: a replica that cannot drain (SIGKILL'd
  worker process) migrates via the router's prompt+token mirrors and
  still finishes token-exact.
- **Observability**: fleet events (replica_down, migration, circuit)
  flow through the tracer; ``fleet_exposition`` renders and re-parses
  through the strict Prometheus referee.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import generate, tiny_gpt
from pddl_tpu.obs import RequestTracer, fleet_exposition, parse_prometheus_text
from pddl_tpu.serve import FaultKind, FaultPlan, QueueFull, ServeEngine
from pddl_tpu.serve.fleet import (
    BreakerState,
    CircuitBreaker,
    FleetRouter,
    LocalReplica,
    NoHealthyReplica,
    ReplicaDied,
)
from pddl_tpu.serve.request import Priority, RequestState
from conftest import ref_greedy as _ref_greedy, FakeClock as _FakeClock

pytestmark = pytest.mark.fleet


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


def _local_fleet(model, variables, n, *, with_plans=False, clock=None,
                 respawn=True, tracer=None, max_queue_depth=64,
                 breaker=None, **router_kw):
    """N LocalReplica fleet over one shared tiny model; each replica
    gets its own (initially empty) fault plan so tests can schedule
    surgical kills after routing settles."""
    plans = [FaultPlan(sleep_fn=_no_sleep) if with_plans else None
             for _ in range(n)]

    def factory(plan):
        def make():
            # Engine prefix cache OFF: routing affinity lives in the
            # ROUTER's shadow index, and migration replay is prefix-
            # agnostic — the 4-program engine keeps the matrix fast
            # while the zero-recompile pin still covers every replica.
            return ServeEngine(model, variables, max_slots=2,
                               prefill_len=16, fault_plan=plan,
                               max_queue_depth=max_queue_depth,
                               backoff_sleep=_no_sleep)
        return make

    replicas = [LocalReplica(i, factory(plans[i])) for i in range(n)]
    fleet = FleetRouter(replicas, affinity_block_size=8, affinity_blocks=1,
                        respawn=respawn, tracer=tracer,
                        breaker=breaker,
                        clock=clock if clock is not None else time.monotonic,
                        **router_kw)
    return fleet, plans


def _workload(n_requests, seed=0):
    """Distinct prompt heads (spread over the hash ring) plus a shared-
    prefix pair (the affinity case); greedy, so streams are oracle-
    comparable."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n_requests):
        if i % 4 == 3 and reqs:  # every 4th shares the previous prompt
            p, _ = reqs[-1]
            reqs.append((p, int(rng.integers(3, 7))))
        else:
            plen = int(rng.integers(6, 15))
            reqs.append((rng.integers(0, 32, size=plen).astype(np.int32),
                         int(rng.integers(3, 8))))
    return reqs


# ---------------------------------------------------------- chaos matrix
@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_replicas", [2, 4])
def test_fleet_kill_matrix(gpt_setup, pin_zero_recompiles, seed,
                           n_replicas):
    """Kill one of N replicas mid-stream (seeded kill-point at its next
    tick): every request terminal, survivors token-exact vs the
    unkilled oracle, zero recompiles on every surviving replica, and
    the death/migration visible in the fleet trace."""
    model, variables = gpt_setup
    tracer = RequestTracer()
    fleet, plans = _local_fleet(model, variables, n_replicas,
                                with_plans=True, respawn=False,
                                tracer=tracer)
    fleet = pin_zero_recompiles(fleet)
    reqs = _workload(3 * n_replicas, seed=seed)
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    handles = [fleet.submit(p, n) for p, n in reqs]
    # Let streams start, then schedule a kill on the busiest replica's
    # NEXT tick — guaranteed mid-stream, whatever the routing chose.
    for _ in range(2):
        fleet.step()
    victim = max((s for s in fleet.replicas), key=lambda s: s.load)
    assert victim.load > 0
    eng = victim.driver.engine
    plans[victim.replica_id]._sched[(eng._step_idx + seed % 2, "tick")] = \
        [FaultKind.KILL]
    fleet.run(max_steps=600)
    assert not fleet.has_work, "fleet failed to drain after the kill"
    finished = 0
    for h, ref in zip(handles, refs):
        assert h.done, f"request {h} never reached a terminal state"
        if h.state == RequestState.FINISHED:
            finished += 1
            assert h.tokens == ref, \
                f"stream diverged (seed {seed}, N={n_replicas}): {h}"
    assert finished == len(handles)  # kills lose no requests at all
    assert fleet.metrics.replica_down_events == 1
    assert fleet.metrics.requests_migrated >= 1
    assert fleet.metrics.migrated_via_drain >= 1  # live migration path
    downs = tracer.events_named("replica_down")
    assert len(downs) == 1 and downs[0]["replica"] == victim.replica_id
    assert tracer.events_named("migration")
    # The fleet still serves after the loss.
    p, n = reqs[0]
    again = fleet.submit(p, n)
    fleet.run(max_steps=200)
    assert again.tokens == refs[0]


def test_cascading_death_mid_restore_stays_token_exact(gpt_setup):
    """The restore TARGET dies mid-migration, after streaming one more
    token for a request it partially restored. The retry pass must
    rebuild wire entries from the router's freshened mirrors — reusing
    the original snapshot would re-emit that token and break stream
    exactness."""
    model, variables = gpt_setup
    armed = {}

    def factory():
        return ServeEngine(model, variables, max_slots=2, prefill_len=16,
                           max_queue_depth=64,
                           backoff_sleep=_no_sleep)

    class DiesMidRestore(LocalReplica):
        def __init__(self, rid):
            super().__init__(rid, factory)
            self.die_on_step = False
            self._late = []

        def step(self):
            if self.die_on_step:
                self.die_on_step = False
                raise ReplicaDied(self.replica_id, "injected death")
            return super().step()

        def restore(self, pairs):
            if armed.pop("on", None):
                rid, entry = pairs[0]
                sofar = [int(t) for t in entry["tokens"]]
                nxt = _ref_greedy(model, variables, entry["prompt"],
                                  len(sofar) + 1)[-1]
                self._late.append({"ev": "tokens", "toks": [(rid, [nxt])]})
                raise ReplicaDied(self.replica_id, "died mid-restore")
            super().restore(pairs)

        def take_pending(self):
            events = super().take_pending()
            events += self._late
            self._late = []
            return events

    fleet = FleetRouter([DiesMidRestore(i) for i in range(3)],
                        affinity_block_size=8, affinity_blocks=1,
                        respawn=False)
    reqs = _workload(9, seed=5)
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()
    victim = max(fleet.replicas, key=lambda s: s.load)
    assert victim.load > 0
    victim.driver.die_on_step = True
    armed["on"] = True  # first restore target dies mid-restore
    fleet.run(max_steps=600)
    assert fleet.metrics.replica_down_events == 2
    for h, ref in zip(handles, refs):
        assert h.done
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref, "stream diverged across cascaded deaths"


# -------------------------------------------------------------- routing
def test_prefix_affinity_routes_to_cache_holder(gpt_setup):
    model, variables = gpt_setup
    fleet, _ = _local_fleet(model, variables, 2)
    shared = ((np.arange(12) * 3 + 5) % 32).astype(np.int32)
    h0 = fleet.submit(shared, 3)
    first_replica = h0.replica_id
    fleet.run(max_steps=100)
    # Same leading blocks, different tail: must land where the cache is.
    tail = np.concatenate([shared[:8], (np.arange(5) + 2) % 32]) \
        .astype(np.int32)
    h1 = fleet.submit(tail, 3)
    assert h1.replica_id == first_replica
    assert fleet.metrics.routed_affinity >= 1
    fleet.run(max_steps=100)
    assert h1.tokens == _ref_greedy(model, variables, tail, 3)


def test_priority_aware_routing_sheds_interactive_off_hot_affinity(
        gpt_setup):
    """ROADMAP item 5's unclaimed follow-on, made discriminative: with
    the affinity replica under load-pressure, an INTERACTIVE request
    abandons the warm cache for the least-loaded healthy replica
    (labeled ``load``), while a BATCH request with the SAME warm
    prefix keeps pure affinity — the cache is worth a queue wait only
    to traffic without an interactive SLO."""
    model, variables = gpt_setup
    fleet, _ = _local_fleet(model, variables, 2,
                            interactive_reroute_load=2)
    shared = ((np.arange(12) * 3 + 5) % 32).astype(np.int32)
    h0 = fleet.submit(shared, 3)
    hot = h0.replica_id
    fleet.run(max_steps=100)

    def _variant(t):
        return np.concatenate([shared[:8], [t]]).astype(np.int32)

    # Pile un-stepped load onto the warm replica (affinity routes the
    # shared head straight back to it).
    pressure = [fleet.submit(_variant(2 + i), 4) for i in range(2)]
    assert all(h.replica_id == hot for h in pressure)
    # Batch priority, same warm prefix, same pressure: stays put.
    hb = fleet.submit(_variant(20), 3, priority=Priority.BATCH)
    assert hb.replica_id == hot
    assert fleet.metrics.routed_load_balanced == 0
    # Interactive under the same pressure: least-loaded replica wins.
    hi = fleet.submit(_variant(21), 3)
    assert hi.replica_id != hot
    assert fleet.metrics.routed_load_balanced == 1
    fleet.run(max_steps=300)
    for h, t in [(hb, 20), (hi, 21)]:
        assert h.tokens == _ref_greedy(model, variables, _variant(t), 3)


def test_sticky_sessions_and_rendezvous_determinism(gpt_setup):
    model, variables = gpt_setup
    fleet, _ = _local_fleet(model, variables, 4)
    p = (np.arange(9) * 5 + 1) % 32
    a = fleet.submit(p, 2, session="alice")
    b = fleet.submit((np.arange(7) + 3) % 32, 2, session="alice")
    assert b.replica_id == a.replica_id  # sticky beats hash
    assert fleet.metrics.routed_sticky >= 1
    fleet.run(max_steps=100)
    # Rendezvous: identical cold prompt heads route identically (fresh
    # fleet — no shadow state).
    fleet2, _ = _local_fleet(model, variables, 4)
    q = (np.arange(10) * 7 + 2) % 32
    picks = {fleet2.submit(np.concatenate([q[:8], [i]]).astype(np.int32),
                           2).replica_id
             for i in range(3)}
    # Hmm-free determinism: the 8-token head dominates affinity_blocks=1
    # (one 8-token block), so all three share a hash key.
    assert len(picks) == 1
    fleet2.run(max_steps=100)


def test_queue_full_sheds_to_least_loaded_then_rejects(gpt_setup):
    model, variables = gpt_setup
    fleet, _ = _local_fleet(model, variables, 2, max_queue_depth=2)
    # Fill replica chosen by the hash for this head, then keep going:
    # overflow must shed to the sibling, and only a fleet-wide full
    # queue rejects the caller.
    p = (np.arange(9) * 5 + 1) % 32
    handles = []
    shed_before = fleet.metrics.shed_rerouted
    with pytest.raises(QueueFull) as exc:
        for i in range(12):
            handles.append(fleet.submit(p, 30))
    assert fleet.metrics.shed_rerouted > shed_before
    assert fleet.metrics.shed_rejected == 1
    assert exc.value.queue_depth > 0
    by_replica = {}
    for h in handles:
        by_replica[h.replica_id] = by_replica.get(h.replica_id, 0) + 1
    assert len(by_replica) == 2  # both replicas took load
    for h in handles:
        h.cancel()
    fleet.run(max_steps=300)


def test_no_healthy_replica_raises(gpt_setup):
    model, variables = gpt_setup
    clock = _FakeClock()
    fleet, plans = _local_fleet(model, variables, 1, with_plans=True,
                                clock=clock, respawn=False)
    h = fleet.submit((np.arange(6) + 1) % 32, 8)
    plans[0]._sched[(2, "tick")] = [FaultKind.KILL]
    fleet.run(max_steps=50)
    assert fleet.healthy_replicas == 0
    with pytest.raises(NoHealthyReplica):
        fleet.submit((np.arange(6) + 1) % 32, 2)
    # With no possible recovery the in-flight request failed terminally
    # rather than hanging forever.
    assert h.done


# ------------------------------------------------------ circuit breaker
def test_circuit_breaker_transitions_and_backoff():
    transitions = {}

    def count(old, new):
        key = f"{old.value}->{new.value}"
        transitions[key] = transitions.get(key, 0) + 1

    br = CircuitBreaker(failure_threshold=2, backoff_base_s=1.0,
                        backoff_max_s=4.0, on_transition=count)
    assert br.state is BreakerState.CLOSED and br.allows_traffic
    br.record_failure(0.0)
    assert br.state is BreakerState.CLOSED  # below threshold
    br.record_failure(0.0)
    assert br.state is BreakerState.OPEN and not br.allows_traffic
    assert not br.probe_due(0.5) and br.probe_due(1.0)
    br.begin_probe(1.0)
    assert br.state is BreakerState.HALF_OPEN
    br.record_failure(1.0)  # probe failed: re-open, backoff doubled
    assert br.state is BreakerState.OPEN
    assert not br.probe_due(2.9) and br.probe_due(3.0)
    br.begin_probe(3.0)
    br.record_failure(3.0)  # doubled again (4.0, at the cap)
    br.begin_probe(7.0)
    br.record_success(7.0)  # recovery: CLOSED, backoff reset
    assert br.state is BreakerState.CLOSED
    br.record_failure(8.0)
    br.record_failure(8.0)
    assert br.probe_due(9.0)  # back at the base interval
    assert transitions["closed->open"] == 2
    assert transitions["half_open->open"] == 2
    with pytest.raises(RuntimeError, match="must be open"):
        CircuitBreaker().begin_probe(0.0)  # probing a closed circuit


def test_replica_respawn_revives_orphans_token_exact(gpt_setup):
    """Single-replica fleet: the kill orphans the in-flight requests;
    past the breaker backoff a HALF_OPEN probe respawns the engine and
    the orphans replay to token-exact completion."""
    model, variables = gpt_setup
    clock = _FakeClock()
    tracer = RequestTracer()
    fleet, plans = _local_fleet(
        model, variables, 1, with_plans=True, clock=clock, respawn=True,
        tracer=tracer, breaker={"backoff_base_s": 2.0})
    reqs = [((np.arange(8) * 3 + 1) % 32, 6), ((np.arange(5) + 9) % 32, 5)]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    handles = [fleet.submit(p, n) for p, n in reqs]
    plans[0]._sched[(2, "tick")] = [FaultKind.KILL]
    fleet.run(max_steps=20)
    assert fleet.healthy_replicas == 0
    assert fleet.metrics.requests_orphaned == 2
    assert all(not h.done for h in handles)  # parked, not failed
    clock.now += 5.0  # past the backoff: the next step probes
    fleet.run(max_steps=300)
    for h, ref in zip(handles, refs):
        assert h.state == RequestState.FINISHED
        assert h.tokens == ref
        assert h.migrations >= 1
    assert fleet.metrics.replica_up_events == 1
    assert fleet.metrics.probes == 1
    assert tracer.events_named("replica_up")
    assert any(e["transition"] == "open->half_open"
               for e in tracer.events_named("circuit"))


# --------------------------------------------------------- process fleet
def test_process_fleet_sigkill_migration_token_exact():
    """Two real worker processes; SIGKILL one mid-stream. The router
    cannot drain a SIGKILL'd worker, so migration runs off its own
    prompt+token mirrors — and every stream still finishes token-exact
    vs an oracle engine with the same param seed."""
    from pddl_tpu.serve.fleet import ProcessReplica
    from pddl_tpu.serve.fleet.worker import build_engine

    cfg = dict(vocab=64, max_len=128, embed_dim=64, depth=2, heads=2,
               slots=4, prefill_len=32, max_queue_depth=64, param_seed=0)
    reps = [ProcessReplica(i, {**cfg, "replica_id": i},
                           python=sys.executable) for i in range(2)]
    fleet = FleetRouter(reps, affinity_block_size=8, affinity_blocks=1,
                        respawn=False)
    try:
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, size=12).tolist()
                   for _ in range(8)]
        handles = [fleet.submit(p, 16) for p in prompts]
        assert len({h.replica_id for h in handles}) == 2
        deadline = time.monotonic() + 60
        while sum(len(h.tokens) for h in handles) < 20 \
                and time.monotonic() < deadline:
            fleet.step()
        victim_id = handles[0].replica_id
        victim = next(s for s in fleet.replicas
                      if s.replica_id == victim_id)
        assert victim.load > 0
        victim.driver.kill()  # SIGKILL: no drain possible
        fleet.run(max_steps=400000, idle_sleep_s=0.002)
        assert all(h.done for h in handles)
        eng = build_engine(cfg)
        for p, h in zip(prompts, handles):
            assert h.state == RequestState.FINISHED
            assert h.tokens == _ref_greedy(eng.model,
                                           {"params": eng._params}, p, 16)
        assert fleet.metrics.replica_down_events == 1
        assert fleet.metrics.migrated_via_replay >= 1
        assert fleet.metrics.migrated_via_drain == 0
        # Zero recompiles on the surviving worker.
        counts = fleet.compile_counts()
        survivor = 1 - victim_id
        assert counts and all(
            v == 1 for k, v in counts.items()
            if k.startswith(f"r{survivor}/"))
    finally:
        fleet.close()


def test_sigkill_after_finish_settles_from_pipe_buffer():
    """A SIGKILL'd worker's stdout stays readable until EOF: finish
    events it wrote before dying must settle their handles from the
    residual OS pipe buffer, not replay-migrate (here: fail, no
    survivors) an already-complete stream."""
    import select

    from pddl_tpu.serve.fleet import ProcessReplica
    from pddl_tpu.serve.fleet.worker import build_engine

    cfg = dict(vocab=32, max_len=64, embed_dim=32, depth=1, heads=2,
               slots=2, prefill_len=16, max_queue_depth=8, param_seed=0,
               replica_id=0)
    rep = ProcessReplica(0, cfg, python=sys.executable)
    fleet = FleetRouter([rep], affinity_block_size=8, affinity_blocks=1,
                        respawn=False)
    try:
        prompt = [3, 1, 4, 1, 5]
        h = fleet.submit(prompt, 4)
        # Let the worker finish and write its events WITHOUT the router
        # reading the pipe; first readable byte, then a settle window
        # for the rest of the batch (4 tokens on a warm engine: ~ms).
        fd = rep._proc.stdout.fileno()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            readable, _, _ = select.select([fd], [], [], 0.1)
            if readable:
                time.sleep(0.5)
                break
        rep.kill()
        rep._proc.wait(timeout=10)
        fleet.run(max_steps=1000)  # death surfaces; capture runs
        assert h.state == RequestState.FINISHED
        eng = build_engine(cfg)
        assert h.tokens == _ref_greedy(eng.model,
                                       {"params": eng._params}, prompt, 4)
        assert fleet.metrics.requests_failed == 0
        assert fleet.metrics.requests_migrated == 0
        assert fleet.metrics.requests_orphaned == 0
    finally:
        fleet.close()


def test_worker_rejects_bad_restore_entry_and_stays_alive():
    """One corrupt migrated entry (undecodable wire dict) must fail
    THAT request terminally — never crash the healthy survivor it was
    being restored onto (which would cascade one bad mirror into a
    second replica loss)."""
    from pddl_tpu.serve.fleet import ProcessReplica
    from pddl_tpu.serve.request import SamplingParams

    cfg = dict(vocab=32, max_len=64, embed_dim=32, depth=1, heads=2,
               slots=2, prefill_len=16, max_queue_depth=8, param_seed=0,
               replica_id=0)
    rep = ProcessReplica(0, cfg, python=sys.executable)
    try:
        rep.restore([(7, {"tokens": [1, 2]})])  # no prompt: undecodable
        deadline = time.monotonic() + 30
        finish = None
        while finish is None and time.monotonic() < deadline:
            for ev in rep.step():
                if ev.get("ev") == "finish" and ev.get("rid") == 7:
                    finish = ev
        assert finish is not None, "bad entry never settled"
        assert finish["state"] == RequestState.FAILED.value
        # The worker survived: a fresh request still serves end-to-end.
        rep.submit(8, list(range(1, 7)), 3, SamplingParams(), None)
        deadline = time.monotonic() + 30
        ok = False
        while not ok and time.monotonic() < deadline:
            for ev in rep.step():
                if ev.get("ev") == "finish" and ev.get("rid") == 8:
                    assert ev["state"] == RequestState.FINISHED.value
                    ok = True
        assert ok, "worker did not serve after rejecting the bad entry"
    finally:
        rep.close()


def test_cancelled_orphans_settle_during_total_outage(gpt_setup):
    """cancel() must lead to a terminal state even for ORPHANS — parked
    requests no live replica holds. Without the step()-time sweep, an
    unbounded run() spins on has_work through an outage whose probes
    never succeed."""
    model, variables = gpt_setup
    clock = _FakeClock()
    fleet, plans = _local_fleet(model, variables, 1, with_plans=True,
                                clock=clock, respawn=True)
    handles = [fleet.submit((np.arange(6) + i) % 32, 6) for i in range(2)]
    plans[0]._sched[(2, "tick")] = [FaultKind.KILL]
    fleet.run(max_steps=20)
    assert fleet.metrics.requests_orphaned == 2
    for h in handles:
        h.cancel()
    fleet.run(max_steps=10)  # clock frozen: no probe fires
    for h in handles:
        assert h.state == RequestState.CANCELLED
    assert not fleet.has_work
    assert not fleet._by_rid


def test_router_idle_gap_is_not_heartbeat_silence():
    """beat_age_s is the age of the oldest UNANSWERED ping, never time
    since the last read: a router that idles between bursts must not
    wake up, see a stale read-timestamp on every healthy worker, and
    breaker-kill them before a single pong could round-trip."""
    from pddl_tpu.serve.fleet import ProcessReplica

    cfg = dict(vocab=32, max_len=64, embed_dim=32, depth=1, heads=2,
               slots=2, prefill_len=16, max_queue_depth=8, param_seed=0,
               replica_id=0)
    clock = _FakeClock(1000.0)
    rep = ProcessReplica(0, cfg, python=sys.executable, clock=clock)
    try:
        deadline = time.monotonic() + 30
        rep.step()  # sends a ping: outstanding until the pong reads
        # Frozen fake clock: an outstanding ping also reads age 0, so
        # wait on the marker itself for the pong to actually land.
        while rep._unanswered_ping_s is not None \
                and time.monotonic() < deadline:
            time.sleep(0.01)
            rep.step()
        assert rep._unanswered_ping_s is None
        assert rep.beat_age_s() == 0.0
        clock.now += 100.0  # long idle gap, nothing in flight
        assert rep.beat_age_s() == 0.0  # the gap is OUR silence, not its
        rep.step()  # fresh ping: age anchors to this send, not the gap
        assert rep.beat_age_s() <= 1.0
    finally:
        rep.close()


def test_fleet_drain_includes_snapshot_absent_assigned(gpt_setup):
    """A request assigned to a replica but missing from its drain
    snapshot (e.g. a migration restore still buffered unread in a
    worker's stdin pipe) must enter the fleet-wide drain from the
    router's mirrors — the leftovers rule death handling applies — and
    restore token-exactly, never vanish from a drain that reported
    success."""
    model, variables = gpt_setup

    class Forgetful(LocalReplica):
        def drain_entries(self, now_s):
            return super().drain_entries(now_s)[1:]  # "unread" request

    def factory():
        return ServeEngine(model, variables, max_slots=2, prefill_len=16)

    fleet = FleetRouter([Forgetful(0, factory)], respawn=False)
    reqs = [(list(range(1, 9)), 5), (list(range(3, 10)), 4)]
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()
    snapshot = fleet.drain()
    assert len(snapshot["requests"]) == 2  # nothing vanished
    fresh = factory()
    restored = fresh.restore(snapshot)
    while any(not h.done for h in restored):
        fresh.step()
    by_prompt = {tuple(h.request.prompt): h.tokens for h in restored}
    for (p, _n), ref, fh in zip(reqs, refs, handles):
        assert by_prompt[tuple(p)] == ref
        del fh  # fleet handles stay QUEUED/RUNNING post-drain by design


def test_local_drain_entries_encode_on_engine_clock(gpt_setup):
    """``elapsed_s`` (consumed deadline budget) is a same-epoch
    difference: the capture must encode against the ENGINE's clock the
    handles' ``arrival_s`` was stamped on, not the router's — a chaos
    router driving a fake clock over real-clock engines would
    otherwise snapshot a zero (or garbage) budget."""
    from pddl_tpu.serve import ServeEngine

    eng_clock = _FakeClock(100.0)
    rep = LocalReplica(0, lambda: ServeEngine(
        gpt_setup[0], gpt_setup[1], max_slots=2, prefill_len=16,
        clock=eng_clock))
    rep.submit(3, list(range(1, 9)), 4, None, None)
    eng_clock.now = 103.0
    (rid, entry), = rep.drain_entries(5.0)  # router epoch: meaningless
    assert rid == 3
    assert entry["elapsed_s"] == pytest.approx(3.0)


# -------------------------------------------------------- observability
def test_fleet_exposition_renders_and_reparses(gpt_setup):
    model, variables = gpt_setup
    clock = _FakeClock()
    fleet, plans = _local_fleet(model, variables, 2, with_plans=True,
                                clock=clock, respawn=False)
    reqs = _workload(4, seed=7)
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()
    victim = max(fleet.replicas, key=lambda s: s.load)
    plans[victim.replica_id]._sched[
        (victim.driver.engine._step_idx, "tick")] = [FaultKind.KILL]
    fleet.run(max_steps=300)
    assert all(h.done for h in handles)
    text = fleet_exposition(fleet)
    samples, types = parse_prometheus_text(text)  # the strict referee
    assert samples[("pddl_fleet_replicas", ())] == 2.0
    assert samples[("pddl_fleet_replicas_healthy", ())] == 1.0
    assert samples[("pddl_fleet_replica_down_events_total", ())] == 1.0
    assert samples[("pddl_fleet_requests_migrated_total", ())] >= 1.0
    assert types["pddl_fleet_requests_migrated_total"] == "counter"
    dead = (("key", f"r{victim.replica_id}"),)
    assert samples[("pddl_fleet_replica_state", dead)] == 0.0
    assert samples[("pddl_fleet_replica_breaker_open", dead)] == 1.0
    # Circuit transitions surfaced as flattened counters.
    assert any(name.startswith("pddl_fleet_circuit_")
               for name, _ in samples)
