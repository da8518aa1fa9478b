"""Router high availability (`fleet/standby.py` + the fencing-epoch
plumbing through journal/router/transport/worker/replica), CPU.

The contracts under test (ISSUE 20):

- **Lease = single-writer token**: the file-backed lease's epoch
  increments exactly on holder change; acquisition against a live
  foreign lease is a typed :class:`LeaseHeld`; renewal by a deposed
  holder reports False. The keeper's renewal jitter is SUBTRACTIVE
  and seeded (the r21 breaker/spawn discipline) — a jittered renewal
  can only land EARLY, so jitter can never push a renewal past the
  lease's safety margin.
- **WAL shipping + tail fold**: every journal append (NON_DURABLE
  backlog included) ships as one CRC-framed line; the standby's fold
  matches ``journal.read_state`` exactly, dedups by journal seq, and
  heals wire gaps with a disk catch-up (counted).
- **Fenced hot takeover**: promotion fences every worker at the new
  epoch FIRST, then rebuilds a router over the SAME live drivers and
  mirror-replays (r11 contract) — token-exact, zero recompiles. The
  deposed-but-alive primary's every subsequent command is a typed
  :class:`EpochFenced` reject on every worker — and the negative
  control shows an UNFENCED (epoch-free) command still passes, so the
  refusal is provably the epoch's doing.
- **Loss window under r21 storage faults**: promoting off a
  NON_DURABLE primary with the wire also dead loses exactly the
  fsync-batched token deltas — whose replay regenerates identical
  tokens.
- **Observability**: ``takeovers`` / ``fenced_commands_refused`` /
  ``standby_catchups`` counters and ``router_epoch`` / ``lease_age_s``
  / ``standby_lag_records`` gauges round-trip through the strict
  Prometheus referee in both directions, NaN when unarmed.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.obs import fleet_exposition, parse_prometheus_text
from pddl_tpu.serve import ServeEngine
from pddl_tpu.serve.fleet import (
    EpochFenced,
    FleetRouter,
    HotStandby,
    Lease,
    LeaseHeld,
    LeaseKeeper,
    LocalReplica,
    RouterJournal,
    WalShipper,
    WalTail,
)
from pddl_tpu.serve.fleet import journal as journal_io
from pddl_tpu.serve.request import Request, RequestState, SamplingParams
from pddl_tpu.utils.faults import StorageFaultPlan
from conftest import FakeClock, ref_greedy as _ref_greedy

pytestmark = pytest.mark.ha


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


def _local_fleet(model, variables, n, **router_kw):
    def factory():
        return ServeEngine(model, variables, max_slots=2,
                           prefill_len=16, max_queue_depth=64,
                           backoff_sleep=_no_sleep)
    replicas = [LocalReplica(i, factory) for i in range(n)]
    return FleetRouter(replicas, affinity_block_size=8,
                       affinity_blocks=1, respawn=False, **router_kw)


def _workload(n_requests, seed=0, vocab=32):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.integers(6, 15))
        reqs.append((rng.integers(0, vocab, size=plen).astype(np.int32),
                     int(rng.integers(3, 8))))
    return reqs


_ROUTER_KW = dict(affinity_block_size=8, affinity_blocks=1,
                  respawn=False)


def _armed_pair(tmp_path, fleet, journal, *, ttl_s=1.0, clock=None):
    """The deployment shape the runbook documents: a lease-armed
    primary (``set_epoch(keeper.acquire())`` — without this the
    primary's commands are epoch-free and fencing has nothing to
    refuse) plus a hot standby attached to its WAL shipper."""
    clock = clock or FakeClock(0.0)
    lease = Lease(str(tmp_path / "ha_lease.json"), ttl_s=ttl_s,
                  clock=clock)
    keeper = LeaseKeeper(lease, "primary", seed=0)
    fleet.set_epoch(keeper.acquire())
    fleet.ha = keeper
    standby = HotStandby(str(tmp_path / "wal"),
                         [s.driver for s in fleet.replicas],
                         lease=lease, holder="standby", seed=1,
                         router_kw=dict(_ROUTER_KW))
    shipper = WalShipper(journal, standby.feed)
    standby.attach(shipper)
    return clock, lease, keeper, standby, shipper


# ----------------------------------------------------------- the lease
def test_lease_single_writer_epoch_semantics(tmp_path):
    clock = FakeClock(0.0)
    lease = Lease(str(tmp_path / "lease.json"), ttl_s=1.0, clock=clock)
    assert lease.read() is None and lease.age_s() is None
    assert lease.expired()                    # never held = expired
    assert lease.acquire("a") == 1            # first holder arms epoch 1
    assert lease.acquire("a") == 1            # re-acquire: same holder,
    assert lease.renew("a")                   # same epoch; renew extends
    with pytest.raises(LeaseHeld) as ei:      # a live foreign lease is
        lease.acquire("b")                    # a typed refusal
    assert ei.value.other == "a" and ei.value.remaining_s > 0
    clock.now = 0.5
    assert lease.age_s() == pytest.approx(0.5)
    assert lease.acquire("b", steal=True) == 2  # forced failover bumps
    assert not lease.renew("a")               # deposed: must stop
    clock.now = 2.0                           # b's lease lapses
    assert lease.expired()
    assert lease.acquire("a") == 3            # every holder change bumps
    with pytest.raises(ValueError, match="ttl_s"):
        Lease(str(tmp_path / "x.json"), ttl_s=0.0)


def test_lease_keeper_validation_and_subtractive_jitter(tmp_path):
    clock = FakeClock(0.0)
    lease = Lease(str(tmp_path / "lease.json"), ttl_s=0.9, clock=clock)
    with pytest.raises(ValueError, match="jitter_frac"):
        LeaseKeeper(lease, "a", jitter_frac=1.0)
    with pytest.raises(ValueError, match="jitter_frac"):
        LeaseKeeper(lease, "a", jitter_frac=-0.1)
    with pytest.raises(ValueError, match="renew_every_s"):
        LeaseKeeper(lease, "a", renew_every_s=0.9)   # == ttl: no margin
    with pytest.raises(ValueError, match="renew_every_s"):
        LeaseKeeper(lease, "a", renew_every_s=0.0)
    # The jitter property: every drawn interval sits in
    # ((1 - frac) * renew_every_s, renew_every_s] — SUBTRACTIVE, so a
    # jittered renewal always lands no later than the unjittered one
    # and can never eat the (ttl - renew_every_s) safety margin.
    k = LeaseKeeper(lease, "a", renew_every_s=0.3, jitter_frac=0.9,
                    seed=42)
    draws = [k._interval_s() for _ in range(500)]
    assert all(0.3 * (1.0 - 0.9) < d <= 0.3 for d in draws)
    assert len(set(draws)) > 400              # it actually jitters
    twin = LeaseKeeper(lease, "a", renew_every_s=0.3, jitter_frac=0.9,
                       seed=42)
    assert draws == [twin._interval_s() for _ in range(500)]  # seeded
    other = LeaseKeeper(lease, "a", renew_every_s=0.3,
                        jitter_frac=0.9, seed=43)
    assert draws != [other._interval_s() for _ in range(500)]


def test_lease_keeper_never_expires_while_stepped_then_deposes(tmp_path):
    # Drive a keeper with maximal jitter across many renewals under a
    # fake clock: as long as step() runs at all, the lease NEVER
    # expires — the operational meaning of "jitter cannot delay
    # renewal past the safety margin".
    clock = FakeClock(0.0)
    lease = Lease(str(tmp_path / "lease.json"), ttl_s=0.9, clock=clock)
    keeper = LeaseKeeper(lease, "primary", renew_every_s=0.3,
                         jitter_frac=0.9, seed=7)
    keeper.acquire()
    for _ in range(2000):
        clock.now += 0.05
        assert not lease.expired(), "renewal landed past the margin"
        assert keeper.step()
    assert keeper.renewals >= 300
    # Depose it: a standby steals; the keeper's next due renewal
    # reports False and latches.
    assert lease.acquire("standby", steal=True) == 2
    clock.now += 0.9
    assert keeper.step() is False and keeper.deposed
    assert keeper.step() is False             # latched
    assert keeper.lag_records() is None       # a primary has no lag


# ------------------------------------------------- shipper + tail fold
def test_wal_shipper_tail_fold_matches_read_state(tmp_path):
    d = str(tmp_path / "wal")
    j = RouterJournal(d, fsync_batch_records=2)
    tail = WalTail(d)
    shipper = WalShipper(j, tail.feed)
    r0 = Request(prompt=[1, 2, 3], max_new_tokens=5,
                 sampling=SamplingParams())
    r1 = Request(prompt=[4, 5], max_new_tokens=3,
                 sampling=SamplingParams())
    j.append(journal_io.encode_admit(0, r0, "sess-a"), durable=True)
    j.append(journal_io.encode_route(0, 1, "hash"))
    j.append(journal_io.encode_fence_epoch(7), durable=True)
    j.append(journal_io.encode_tokens(0, [9, 8]))
    j.append(journal_io.encode_admit(1, r1, None), durable=True)
    j.append(journal_io.encode_tokens(1, [4]))
    j.append(journal_io.encode_finish(1, "finished", "stop"))
    assert shipper.shipped == 7 and shipper.ship_errors == 0
    assert tail.records_folded == 7 and tail.lag_records() == 0
    assert sorted(tail.entries) == [0]        # rid 1 finished
    assert tail.entries[0]["prompt"] == [1, 2, 3]
    assert tail.entries[0]["tokens"] == [9, 8]
    assert tail.entries[0]["session"] == "sess-a"
    assert tail.bindings == {0: 1}
    assert tail.primary_epoch == 7
    assert tail.next_rid == 2
    # The live fold IS the recovery fold: commit and compare against
    # read_state (tokens/session/prompt of the one open stream).
    j.commit()
    entries, next_rid = journal_io.read_state(d)
    assert next_rid == tail.next_rid
    assert sorted(entries) == sorted(tail.entries)
    assert entries[0]["tokens"] == tail.entries[0]["tokens"]
    j.close()


def test_wal_tail_wire_gap_heals_via_disk_catchup(tmp_path):
    d = str(tmp_path / "wal")
    j = RouterJournal(d, fsync_batch_records=1)
    tail = WalTail(d, gap_feeds=3)
    dropped = {"n": 0}

    def lossy_sink(line):
        dropped["n"] += 1
        if dropped["n"] == 3:
            return                            # one frame lost forever
        tail.feed(line)

    shipper = WalShipper(j, lossy_sink)
    r = Request(prompt=[1, 2, 3], max_new_tokens=9,
                sampling=SamplingParams())
    j.append(journal_io.encode_admit(0, r, None), durable=True)
    j.append(journal_io.encode_route(0, 0, "hash"))
    j.append(journal_io.encode_tokens(0, [5]))          # the lost frame
    assert tail.covered_seq == 2
    # Three more feeds arrive behind the unhealable gap; the third
    # trips the catch-up, which refolds from disk and then drains the
    # frames the gap left buffered — nothing is lost, nothing doubled.
    j.append(journal_io.encode_tokens(0, [6]))
    j.append(journal_io.encode_tokens(0, [7]))
    assert tail.covered_seq == 2 and tail.lag_records() == 3
    j.append(journal_io.encode_tokens(0, [8]))
    assert tail.catchups == 1
    assert tail.covered_seq == 6 and tail.lag_records() == 0
    assert tail.entries[0]["tokens"] == [5, 6, 7, 8]
    assert shipper.shipped == 6
    j.close()


def test_standby_join_and_midstream_attach(tmp_path):
    d = str(tmp_path / "wal")
    j = RouterJournal(d, fsync_batch_records=1)
    shipper = WalShipper(j, lambda line: None)   # nobody listening yet
    r = Request(prompt=[2, 2], max_new_tokens=4,
                sampling=SamplingParams())
    j.append(journal_io.encode_admit(0, r, None), durable=True)
    j.append(journal_io.encode_tokens(0, [3]))
    lease = Lease(str(tmp_path / "lease.json"), ttl_s=1.0,
                  clock=FakeClock(0.0))
    standby = HotStandby(d, [], lease=lease)
    # Join = the constructor's disk catch-up: history folded without
    # ever having seen a frame.
    assert standby.tail.catchups == 1
    assert standby.tail.entries[0]["tokens"] == [3]
    standby.attach(shipper)                      # mid-stream: frame seq
    j.append(journal_io.encode_tokens(0, [9]))   # space re-aligned
    assert standby.lag_records() == 0
    assert standby.tail.entries[0]["tokens"] == [3, 9]
    assert standby.tail.catchups == 1            # no gap, no catch-up
    j.close()


# ----------------------------------------------------- fenced takeover
def test_hot_takeover_token_exact_zero_recompiles(
        gpt_setup, pin_zero_recompiles, tmp_path):
    """The tentpole path: primary serves halfway, its lease lapses,
    the standby promotes over the SAME live replicas — every stream
    finishes token-identical to the unkilled oracle with zero
    recompiles, under a bumped fencing epoch."""
    model, variables = gpt_setup
    d = str(tmp_path / "wal")
    journal = RouterJournal(d, fsync_batch_records=4)
    fleet = _local_fleet(model, variables, 2, journal=journal)
    clock, lease, keeper, standby, shipper = _armed_pair(
        tmp_path, fleet, journal)
    assert fleet.epoch == 1
    reqs = _workload(6, seed=3)
    refs = {tuple(int(t) for t in p): _ref_greedy(model, variables, p, n)
            for p, n in reqs}
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(4):
        fleet.step()                  # partial progress, then the
        keeper.step()                 # primary silently dies
    fleet = pin_zero_recompiles(fleet)  # same engines survive takeover
    acked = {tuple(int(t) for t in h.request.prompt): list(h.tokens)
             for h in handles}
    clock.now = 5.0                   # the lease lapses un-renewed
    out = standby.step()
    assert out is not None and standby.promoted
    router, revived = out
    assert standby.step() is None     # the pair is returned exactly once
    assert router.epoch == 2          # holder change bumped the epoch
    assert lease.read()["holder"] == "standby"
    assert keeper.step() is False     # the deposed primary learns it
    router.run(max_steps=4000)
    assert router.metrics.takeovers == 1
    assert router.metrics.standby_catchups >= 1
    # Every acked-unfinished stream revived and landed on the oracle;
    # already-finished ones keep their (also oracle-exact) tokens.
    open_keys = {k for k, t in acked.items()
                 if len(t) < len(refs[k])}
    revived_keys = set()
    for old_rid, fh in revived.items():
        key = tuple(int(t) for t in fh.request.prompt)
        revived_keys.add(key)
        assert fh.state == RequestState.FINISHED, f"rid {old_rid}: {fh}"
        assert fh.tokens == refs[key], "stream diverged over takeover"
    assert open_keys <= revived_keys, "an acked open stream was lost"
    # Takeover's first act after replay was a fresh verified checkpoint.
    assert journal_io.load_checkpoint(d) is not None
    router.close()


def test_deposed_primary_fenced_on_every_worker(gpt_setup, tmp_path):
    """The split-brain discriminant. A partitioned-but-alive primary
    keeps commanding after the standby promoted: 100% of its commands
    are typed :class:`EpochFenced` rejects, counted, on EVERY worker.
    The negative control — an epoch-FREE command still passes — proves
    the refusal is the fencing epoch's doing: this test fails against
    an unfenced router."""
    model, variables = gpt_setup
    d = str(tmp_path / "wal")
    journal = RouterJournal(d, fsync_batch_records=4)
    fleet = _local_fleet(model, variables, 2, journal=journal)
    clock, lease, keeper, standby, shipper = _armed_pair(
        tmp_path, fleet, journal)
    handles = [fleet.submit(p, n) for p, n in _workload(4, seed=1)]
    for _ in range(3):
        fleet.step()
    # Full bidirectional silence: the primary neither renews nor hears
    # the standby; it stays alive and keeps trying to command.
    clock.now = 5.0
    out = standby.step()
    assert out is not None and standby.promoted
    router, revived = out
    assert router.epoch == 2
    # The deposed primary's next commands: refused, typed, counted.
    probes = [([1 + (k % 30)] * (6 + k), 4) for k in range(3)]
    refused_before = fleet.metrics.fenced_commands_refused
    for p, n in probes:
        with pytest.raises(EpochFenced) as ei:
            fleet.submit(p, n)
        assert ei.value.epoch == 1 and ei.value.highest == 2
    assert fleet.metrics.fenced_commands_refused - refused_before == 3
    # ...and not just whichever replica routing picked: EVERY worker
    # holds the fence floor against the stale epoch.
    for slot in fleet.replicas:
        with pytest.raises(EpochFenced):
            slot.driver.cancel(0, epoch=1)
    # Negative control (the unfenced-router shape): an epoch-free
    # command sails through on every worker — exactly why arming the
    # primary's epoch is mandatory, and what this discriminant would
    # MISS if the router under test never stamped epochs.
    for slot in fleet.replicas:
        slot.driver.cancel(424242)            # no raise: accepted
    router.run(max_steps=4000)
    for fh in revived.values():
        assert fh.state == RequestState.FINISHED
    router.close()


def test_takeover_off_non_durable_primary_loss_window(
        gpt_setup, tmp_path):
    """Takeover x r21 storage faults, wire ALSO dead (the partition
    case): the standby inherits the in-memory backlog semantics — the
    loss window is exactly the fsync-batched token deltas — and the
    r11 replay regenerates identical tokens, so every stream still
    lands on the oracle."""
    model, variables = gpt_setup
    d = str(tmp_path / "wal")
    sp = StorageFaultPlan(seed=0)
    journal = RouterJournal(d, storage_plan=sp, fsync_batch_records=2,
                            retry_limit=1, retry_backoff_s=0.0,
                            rearm_interval_s=1e9, sleep_fn=_no_sleep)
    fleet = _local_fleet(model, variables, 2, journal=journal)
    clock = FakeClock(0.0)
    lease = Lease(str(tmp_path / "ha_lease.json"), ttl_s=1.0,
                  clock=clock)
    keeper = LeaseKeeper(lease, "primary", seed=0)
    fleet.set_epoch(keeper.acquire())
    # The standby joined from disk but the replication wire is DOWN —
    # the shipper's frames go nowhere (its sink predates the standby).
    standby = HotStandby(d, [s.driver for s in fleet.replicas],
                         lease=lease, holder="standby", seed=1,
                         router_kw=dict(_ROUTER_KW),
                         journal_kw=dict(fsync_batch_records=2))
    WalShipper(journal, lambda line: None)
    reqs = _workload(5, seed=9)
    refs = {tuple(int(t) for t in p): _ref_greedy(model, variables, p, n)
            for p, n in reqs}
    handles = [fleet.submit(p, n) for p, n in reqs]
    for _ in range(2):
        fleet.step()                       # admissions durable on disk
    sp._rates = (1.0, 0.0, 0.0, 0.0)       # then the disk dies
    for _ in range(4):
        fleet.step()
    assert journal.non_durable
    assert fleet.metrics.journal_degraded_events >= 1
    acked = {tuple(int(t) for t in h.request.prompt): list(h.tokens)
             for h in handles}
    # The primary dies partitioned; the standby's disk catch-up sees
    # only the durable prefix: the backlog token deltas are the loss
    # window (strictly behind at least one acked stream).
    sp.quiesce()                           # the standby's own I/O path
    clock.now = 5.0
    out = standby.step()
    assert out is not None
    router, revived = out
    behind = [
        rid for rid, fh in revived.items()
        if len(standby.tail.entries.get(rid, {}).get("tokens", []))
        < len(acked.get(tuple(int(t) for t in fh.request.prompt), []))]
    assert behind, "no loss window: the NON_DURABLE backlog leaked " \
                   "to disk, or the primary never streamed"
    router.run(max_steps=4000)
    for fh in revived.values():
        key = tuple(int(t) for t in fh.request.prompt)
        assert fh.state == RequestState.FINISHED
        assert fh.tokens == refs[key], \
            "replayed loss-window deltas diverged from the oracle"
    assert router.metrics.takeovers == 1
    router.close()


# -------------------------------------------------------- observability
def test_ha_exposition_series_both_directions(gpt_setup, tmp_path):
    model, variables = gpt_setup
    d = str(tmp_path / "wal")
    journal = RouterJournal(d, fsync_batch_records=4)
    fleet = _local_fleet(model, variables, 2, journal=journal)
    clock, lease, keeper, standby, shipper = _armed_pair(
        tmp_path, fleet, journal)
    handles = [fleet.submit(p, n) for p, n in _workload(3, seed=5)]
    for _ in range(3):
        fleet.step()
    # Primary-side gauges: epoch armed, lease fresh, no lag (a primary
    # has none: NaN).
    clock.now = 0.25
    samples, types = parse_prometheus_text(fleet_exposition(fleet))
    assert samples[("pddl_fleet_router_epoch", ())] == 1.0
    assert samples[("pddl_fleet_lease_age_s", ())] \
        == pytest.approx(0.25)
    assert math.isnan(samples[("pddl_fleet_standby_lag_records", ())])
    assert types["pddl_fleet_router_epoch"] == "gauge"
    # Promote; probe the deposed primary once so the refusal counter
    # moves; then scrape the PROMOTED router.
    clock.now = 5.0
    router, _ = standby.step()
    with pytest.raises(EpochFenced):
        fleet.submit([3, 3, 3, 3, 3, 3], 4)
    router.run(max_steps=4000)
    samples, types = parse_prometheus_text(fleet_exposition(router))
    m = router.metrics
    for key, want in [("takeovers", m.takeovers),
                      ("fenced_commands_refused",
                       m.fenced_commands_refused),
                      ("standby_catchups", m.standby_catchups)]:
        name = f"pddl_fleet_{key}_total"
        assert types[name] == "counter"
        assert samples[(name, ())] == float(want)
    assert m.takeovers == 1 and m.standby_catchups >= 1
    assert samples[("pddl_fleet_router_epoch", ())] == 2.0
    assert samples[("pddl_fleet_standby_lag_records", ())] == 0.0
    assert samples[("pddl_fleet_lease_age_s", ())] >= 0.0
    # The deposed primary's own scrape shows ITS refusal count.
    psamples, _ = parse_prometheus_text(fleet_exposition(fleet))
    assert psamples[(("pddl_fleet_fenced_commands_refused_total"),
                     ())] == float(fleet.metrics.fenced_commands_refused)
    router.close()
    # Unarmed fleet: all three gauges present, NaN — "HA off" is
    # distinguishable from "metric vanished"; counters render 0.
    bare = _local_fleet(model, variables, 1)
    samples, _ = parse_prometheus_text(fleet_exposition(bare))
    assert math.isnan(samples[("pddl_fleet_router_epoch", ())])
    assert math.isnan(samples[("pddl_fleet_lease_age_s", ())])
    assert math.isnan(samples[("pddl_fleet_standby_lag_records", ())])
    assert samples[("pddl_fleet_takeovers_total", ())] == 0.0
    bare.close()
