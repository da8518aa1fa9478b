"""Control-plane durability (`fleet/journal.py`, `fleet/transport.py`,
gray-failure machinery in `fleet/health.py`/`fleet/router.py`), CPU.

The contracts under test (ISSUE 14):

- **Router WAL + crash-exact recovery**: a 3-seed matrix of router
  "SIGKILLs" at seeded WAL-record coordinates (mid-admission,
  mid-migration, mid-stream, mid-chain-pull) — every acked in-flight
  stream revives through ``FleetRouter.recover`` and finishes
  token-identical to the unkilled oracle, with zero recompiles on the
  recovered replicas. Torn WAL tails and corrupted checkpoints restore
  from the newest VERIFIED state (the r10 discipline).
- **Framed transport**: length+CRC+seq framing rejects every corrupt/
  truncated frame (zero corrupt frames accepted is a codec property),
  dedups duplicates, heals gaps through bounded resend — and a seeded
  :class:`WireFaultPlan` storm over real worker processes leaves every
  stream terminal and token-exact. Oversized frames are TYPED rejects
  on both pipe ends, never a crash or an unbounded buffer.
- **Gray failure**: the latency-quantile detector suspects a replica
  whose per-tick p95 drifts from its own baseline; interactive
  submissions hedge to a healthy sibling with first-result-wins
  cancellation, and ``gray_drain`` retires the suspect through the
  r16 ``scale_down`` live-migration path before it hard-fails.
- **Observability**: the new counters/gauges render through
  ``fleet_exposition`` and re-parse through the strict Prometheus
  referee, in both armed and unarmed fleets.
"""

import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.obs import RequestTracer, fleet_exposition, parse_prometheus_text
from pddl_tpu.serve import FaultKind, FaultPlan, ServeEngine
from pddl_tpu.serve.fleet import (
    FleetRouter,
    FrameReceiver,
    FrameSender,
    GrayDetector,
    LocalReplica,
    RouterJournal,
    WireFaultKind,
    WireFaultPlan,
    WireFaultSpec,
)
from pddl_tpu.serve.fleet import journal as journal_io
from pddl_tpu.serve.fleet.transport import (
    FrameError,
    decode_frame,
    encode_frame,
)
from pddl_tpu.serve.request import Priority, RequestState
from pddl_tpu.utils.faults import KillPoint
from conftest import ref_greedy as _ref_greedy

pytestmark = pytest.mark.ctrlplane


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


class _StepWallClock:
    """The router's ``gray_timer`` and, once `_make_gray` arms it, the
    victim's injected-LATENCY sleep: this clock moves only when an
    injected fault "sleeps", so the step wall the gray detector samples
    is the schedule's (calls x ``latency_s`` on the victim, zero on its
    sibling) whatever else the machine is running. On the wall clock a
    2 ms excess sat inside a loaded worker's own jitter, which could
    mask the victim or suspect the sibling (PERF.md, PR 34)."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


def _local_fleet(model, variables, n, *, with_plans=False,
                 max_queue_depth=64, **router_kw):
    plans = [FaultPlan(sleep_fn=_no_sleep) if with_plans else None
             for _ in range(n)]
    if "gray" in router_kw:
        router_kw.setdefault("gray_timer", _StepWallClock())

    def factory(plan):
        def make():
            return ServeEngine(model, variables, max_slots=2,
                               prefill_len=16, fault_plan=plan,
                               max_queue_depth=max_queue_depth,
                               backoff_sleep=_no_sleep)
        return make

    replicas = [LocalReplica(i, factory(plans[i])) for i in range(n)]
    fleet = FleetRouter(replicas, affinity_block_size=8,
                        affinity_blocks=1, respawn=False, **router_kw)
    return fleet, plans


def _fresh_replicas(model, variables, n):
    def factory():
        return ServeEngine(model, variables, max_slots=2,
                           prefill_len=16, max_queue_depth=64,
                           backoff_sleep=_no_sleep)
    return [LocalReplica(i, factory) for i in range(n)]


def _workload(n_requests, seed=0, vocab=32):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.integers(6, 15))
        reqs.append((rng.integers(0, vocab, size=plen).astype(np.int32),
                     int(rng.integers(3, 8))))
    return reqs


# ------------------------------------------------------ framed transport
def test_frame_codec_roundtrip_and_typed_rejects():
    payload = json.dumps({"ev": "tokens", "toks": [[3, [1, 2]]]}).encode()
    frame = encode_frame(7, payload)
    assert frame.endswith(b"\n")
    seq, got = decode_frame(frame.rstrip(b"\n"))
    assert (seq, got) == (7, payload)
    # Corruption anywhere fails validation — never a mis-parse.
    for idx in (1, 10, len(frame) - 3):
        mangled = bytearray(frame.rstrip(b"\n"))
        mangled[idx] ^= 0x40
        with pytest.raises(FrameError):
            decode_frame(bytes(mangled))
    with pytest.raises(FrameError):
        decode_frame(frame.rstrip(b"\n")[: len(frame) // 2])  # truncated
    with pytest.raises(FrameError):
        decode_frame(b'{"ev": "raw json line"}')  # unframed


def test_receiver_orders_dedups_and_reports_gaps():
    sender = FrameSender()
    frames = [sender.encode(json.dumps({"n": i}).encode())
              for i in range(1, 6)]
    rx = FrameReceiver()
    assert [json.loads(p)["n"] for p in rx.feed(frames[0].rstrip(b"\n"))] \
        == [1]
    # A duplicate of a delivered frame drops silently.
    assert rx.feed(frames[0].rstrip(b"\n")) == []
    assert rx.stats["dups"] == 1
    # Out-of-order arrival buffers until the gap fills, then releases
    # everything in order.
    assert rx.feed(frames[2].rstrip(b"\n")) == []
    assert rx.has_gap and rx.expected_seq == 2
    out = rx.feed(frames[1].rstrip(b"\n"))
    assert [json.loads(p)["n"] for p in out] == [2, 3]
    assert not rx.has_gap
    # A corrupt frame is refused (CRC) and the sender's replay buffer
    # can answer the resend request for it.
    bad = bytearray(frames[3].rstrip(b"\n"))
    bad[-2] ^= 0x5A
    assert rx.feed(bytes(bad)) == []
    assert rx.stats["crc_rejects"] == 1
    resent = sender.resend_from(rx.expected_seq)
    assert len(resent) == 2  # frames 4 and 5 still buffered
    for f in resent:
        rx.feed(f.rstrip(b"\n"))
    assert rx.expected_seq == 6 and not rx.has_gap


def test_receiver_oversize_is_typed_and_consumes_the_seq_slot():
    sender = FrameSender()
    small = sender.encode(b'{"n": 1}')
    big = sender.encode(b'{"blob": "' + b"x" * 4096 + b'"}')
    after = sender.encode(b'{"n": 3}')
    rx = FrameReceiver(max_frame_bytes=1024)
    assert len(rx.feed(small.rstrip(b"\n"))) == 1
    # The oversized frame is REFUSED by policy but its sequence slot
    # is consumed — resending the same bytes could never heal it, so
    # it must not wedge the gap machinery.
    assert rx.feed(big.rstrip(b"\n")) == []
    assert rx.stats["too_large"] == 1
    assert not rx.has_gap
    assert len(rx.feed(after.rstrip(b"\n"))) == 1
    assert rx.expected_seq == 4


def test_wire_fault_plan_seeded_and_scheduled():
    def run(seed):
        plan = WireFaultPlan(seed, corrupt_rate=0.2, drop_rate=0.1,
                             duplicate_rate=0.1, sleep_fn=_no_sleep)
        out = []
        for i in range(1, 41):
            frame = encode_frame(i, b'{"n": %d}' % i)
            out.append(tuple(plan.apply("ev", i, frame)))
        return out, dict(plan.injected)

    a, inj_a = run(3)
    b, inj_b = run(3)
    c, _ = run(4)
    assert a == b, "same seed must mangle the same frames"
    assert a != c
    assert sum(inj_a.values()) > 0
    # Scheduled coordinates fire exactly once at (step, site).
    plan = WireFaultPlan(0, scheduled=[
        WireFaultSpec(2, "cmd", WireFaultKind.DROP)])
    f1, f2 = encode_frame(1, b"{}"), encode_frame(2, b"{}")
    assert plan.apply("cmd", 1, f1) == [f1]
    assert plan.apply("ev", 2, f2) == [f2]  # wrong site: no fire
    assert plan.apply("cmd", 2, f2) == []   # dropped
    assert plan.injected[WireFaultKind.DROP] == 1
    with pytest.raises(ValueError, match="unknown scheduled wire site"):
        WireFaultPlan(0, scheduled=[
            WireFaultSpec(1, "typo", WireFaultKind.DROP)])


# ------------------------------------------------------------ router WAL
class _Handle:
    """Minimal handle for journal encoder tests."""

    def __init__(self, prompt, n):
        from pddl_tpu.serve.request import Request, SamplingParams

        self.request = Request(prompt=list(prompt), max_new_tokens=n,
                               sampling=SamplingParams())
        self.tokens = []
        self.arrival_s = 0.0
        self.ttft_s = None


def test_journal_append_read_and_state_fold(tmp_path):
    d = str(tmp_path / "j")
    j = RouterJournal(d, fsync_batch_records=2)
    h = _Handle([1, 2, 3], 5)
    j.append(journal_io.encode_admit(0, h.request, "sess-a"),
             durable=True)
    j.append(journal_io.encode_route(0, 1, "hash"))
    j.append(journal_io.encode_admit(1, _Handle([4, 5], 3).request,
                                     None), durable=True)
    j.append(journal_io.encode_tokens(0, [9, 8]))
    j.append(journal_io.encode_tokens(0, [7]))
    j.append(journal_io.encode_finish(1, "finished", "stop"))
    j.commit()
    entries, next_rid = journal_io.read_state(d)
    assert next_rid == 2
    assert sorted(entries) == [0]  # rid 1 finished
    assert entries[0]["prompt"] == [1, 2, 3]
    assert entries[0]["tokens"] == [9, 8, 7]  # deltas folded in order
    assert entries[0]["session"] == "sess-a"
    j.close()


def test_journal_torn_tail_recovers_readable_prefix(tmp_path):
    d = str(tmp_path / "j")
    j = RouterJournal(d)
    for rid in range(4):
        j.append(journal_io.encode_admit(
            rid, _Handle([rid + 1], 2).request, None), durable=True)
    j.close()
    wal = os.path.join(d, "wal.log")
    size = os.path.getsize(wal)
    # A SIGKILL mid-write tears the last record: cut it mid-payload.
    with open(wal, "r+b") as f:
        f.truncate(size - 7)
    entries, next_rid = journal_io.read_state(d)
    assert sorted(entries) == [0, 1, 2]  # exactly the readable prefix
    assert next_rid == 3
    # Bit-rot mid-file: everything from the corrupt record on is
    # untrusted, the prefix before it still reads. Find the third
    # record's payload via the frame headers and flip bytes in it.
    header = journal_io._HEADER
    with open(wal, "rb") as f:
        data = f.read()
    offsets, off = [], 0
    while off + header.size <= len(data):
        _, _, length, _ = header.unpack_from(data, off)
        offsets.append(off)
        off += header.size + length
    with open(wal, "r+b") as f:
        f.seek(offsets[2] + header.size + 2)
        f.write(b"\xff\xff")
    entries, _ = journal_io.read_state(d)
    assert sorted(entries) == [0, 1]
    # A fresh journal over the same dir (the recovery path) scans the
    # same readable prefix, TRUNCATES the torn tail, and continues the
    # seq line past it — appends after unreadable bytes would put
    # every later durable record beyond what recovery can read.
    j2 = RouterJournal(d)
    assert j2._next_seq == 3
    j2.append(journal_io.encode_admit(
        9, _Handle([7], 2).request, None), durable=True)
    j2.close()
    entries, next_rid = journal_io.read_state(d)
    assert sorted(entries) == [0, 1, 9]
    assert next_rid == 10


def test_checkpoint_cycle_and_corrupt_checkpoint_fallback(tmp_path):
    d = str(tmp_path / "j")
    j = RouterJournal(d, checkpoint_every_records=4)
    for rid in range(3):
        j.append(journal_io.encode_admit(
            rid, _Handle([rid + 1, rid + 2], 3).request, None),
            durable=True)
    # First checkpoint: rid 0 finished, 1..2 in flight.
    j.append(journal_io.encode_finish(0, "finished", "stop"))
    assert j.checkpoint_due
    entries, _ = journal_io.read_state(d)
    cp1 = [(rid, e) for rid, e in sorted(entries.items()) if rid != 0]
    j.checkpoint(cp1, next_rid=3)
    assert not j.checkpoint_due
    assert j.records_since_checkpoint == 0
    # Post-checkpoint traffic, then a second cycle.
    j.append(journal_io.encode_admit(
        3, _Handle([9, 9], 2).request, None), durable=True)
    j.append(journal_io.encode_tokens(1, [5]))
    j.commit()
    entries, next_rid = journal_io.read_state(d)
    assert sorted(entries) == [1, 2, 3]
    assert entries[1]["tokens"] == [5]
    assert next_rid == 4
    cp2 = [(rid, e) for rid, e in sorted(entries.items())]
    j.checkpoint(cp2, next_rid=4)
    j.append(journal_io.encode_tokens(2, [6]))
    j.commit()
    # The current checkpoint fails its CRC (torn/bit-rotted): recovery
    # falls back to the PREVIOUS verified checkpoint plus the rotated
    # WAL segment — nothing acked is lost (r10: newest VERIFIED).
    cp_path = os.path.join(d, "checkpoint.json")
    with open(cp_path) as f:
        wrapped = json.load(f)
    wrapped["crc"] ^= 0xDEAD
    with open(cp_path, "w") as f:
        json.dump(wrapped, f)
    entries, next_rid = journal_io.read_state(d)
    assert sorted(entries) == [1, 2, 3]
    assert entries[1]["tokens"] == [5]
    assert entries[2]["tokens"] == [6]
    assert next_rid == 4
    j.close()


class CrashingJournal(RouterJournal):
    """The router-SIGKILL injector at WAL-record granularity: raises
    :class:`KillPoint` INSTEAD of appending the first record matching
    ``kill_when`` — the crash coordinate is "this control-plane event
    was about to be journaled", which is exactly where a real SIGKILL
    lands mid-admission / mid-migration / mid-stream."""

    def __init__(self, *args, **kwargs):
        self.kill_when = None
        super().__init__(*args, **kwargs)

    def append(self, record, *, durable=False):
        if self.kill_when is not None and self.kill_when(record):
            self.kill_when = None
            raise KillPoint("journal", self.records_appended)
        return super().append(record, durable=durable)


def _drive_until_crash(fleet, reqs):
    """Submit + pump, letting a KillPoint unwind like a real SIGKILL
    (the router object is then abandoned). Returns acked handles."""
    handles = []
    try:
        for p, n in reqs:
            handles.append(fleet.submit(p, n))
        for _ in range(600):
            fleet.step()
            if not fleet.has_work:
                break
    except KillPoint:
        pass
    return handles


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("coord", ["mid_admission", "mid_stream",
                                   "mid_migration"])
def test_router_sigkill_matrix_recovers_token_exact(
        gpt_setup, pin_zero_recompiles, tmp_path, seed, coord):
    """The 3-seed x 3-coordinate crash matrix: kill the router at a
    seeded WAL-record coordinate, recover into a FRESH fleet, and
    every acked stream that had not durably finished revives and
    completes token-identical to the unkilled oracle — with zero
    recompiles on the recovered replicas."""
    model, variables = gpt_setup
    d = str(tmp_path / "wal")
    journal = CrashingJournal(d, fsync_batch_records=4)
    fleet, plans = _local_fleet(model, variables, 2,
                                with_plans=(coord == "mid_migration"),
                                journal=journal)
    reqs = _workload(8, seed=seed)
    refs = {tuple(int(t) for t in p): _ref_greedy(model, variables, p, n)
            for p, n in reqs}
    counters = {"admit": 0, "tokens": 0}

    if coord == "mid_admission":
        k = 3 + seed

        def kill_when(rec):
            if rec.get("rec") == "admit":
                counters["admit"] += 1
                return counters["admit"] == k
            return False
    elif coord == "mid_stream":
        k = 4 + 2 * seed

        def kill_when(rec):
            if rec.get("rec") == "tokens":
                counters["tokens"] += 1
                return counters["tokens"] == k
            return False
    else:  # mid_migration: a replica dies, the router crashes while
        #    journaling the migration re-binds.
        def kill_when(rec):
            return rec.get("rec") == "route" \
                and rec.get("via") == "migration"

    journal.kill_when = kill_when
    if coord == "mid_migration":
        # Arm the replica death that forces the migration: submit
        # first so a victim has load, then kill its next tick.
        handles = []
        try:
            for p, n in reqs:
                handles.append(fleet.submit(p, n))
            for _ in range(2):
                fleet.step()
            victim = max(fleet.replicas, key=lambda s: s.load)
            assert victim.load > 0
            eng = victim.driver.engine
            plans[victim.replica_id]._sched[
                (eng._step_idx + 1, "tick")] = [FaultKind.KILL]
            for _ in range(600):
                fleet.step()
                if not fleet.has_work:
                    break
        except KillPoint:
            pass
        assert journal.kill_when is None, \
            "the migration coordinate never fired"
    else:
        handles = _drive_until_crash(fleet, reqs)
        assert journal.kill_when is None, \
            f"the {coord} coordinate never fired"

    # --- the router process is gone; recover from the WAL alone.
    recovered, revived = FleetRouter.recover(
        d, _fresh_replicas(model, variables, 2),
        affinity_block_size=8, affinity_blocks=1, respawn=False)
    recovered = pin_zero_recompiles(recovered)
    assert revived, "nothing revived"
    recovered.run(max_steps=2000)
    for rid, fh in revived.items():
        assert fh.state == RequestState.FINISHED, f"rid {rid}: {fh}"
        key = tuple(int(t) for t in fh.request.prompt)
        assert fh.tokens == refs[key], \
            f"stream diverged after {coord} crash (seed {seed})"
    # Every acked request that had NOT settled at crash time must be
    # among the revived (its finish record cannot have been durable).
    revived_prompts = {tuple(int(t) for t in fh.request.prompt)
                      for fh in revived.values()}
    for h in handles:
        if not h.done:
            assert tuple(int(t) for t in h.request.prompt) \
                in revived_prompts
    # Recovery is the snapshot path's second normal case: the first
    # act of the recovered router was a fresh verified checkpoint.
    assert journal_io.load_checkpoint(d) is not None
    recovered.close()


def test_recover_mid_chain_pull(gpt_setup, tmp_path):
    """The chain-pull coordinate: the router dies INSIDE a
    replica-to-replica prefix transfer (import side, the r18 load-
    escape recipe). Acked in-flight streams still recover token-exact
    — the half-pulled chain is cache contents, never request state, so
    nothing depends on it — and the un-acked puller was never
    journaled, so it is (correctly) not revived."""
    model, variables = gpt_setup
    armed = {}

    def factory():
        return ServeEngine(model, variables, max_slots=2,
                           prefill_len=32, prefix_cache_blocks=24,
                           prefix_block_size=8, prefix_chunk=8,
                           host_tier=1 << 24, backoff_sleep=_no_sleep)

    class DiesMidImport(LocalReplica):
        def import_chain(self, entry):
            if armed.pop("on", None):
                raise KillPoint("import_chain", 0)
            return super().import_chain(entry)

    d = str(tmp_path / "wal")
    fleet = FleetRouter(
        [DiesMidImport(i, factory) for i in range(2)],
        affinity_block_size=8, respawn=False,
        interactive_reroute_load=1,
        shadow_host_capacity_blocks=1024, chain_pull_blocks=2,
        journal=RouterJournal(d))
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 32, size=24).astype(np.int32)
    probe = np.concatenate([shared[:16],
                            rng.integers(0, 32, 8).astype(np.int32)])
    h1 = fleet.submit(list(shared), 4, priority=Priority.BATCH)
    fleet.run(max_steps=400)
    assert h1.state == RequestState.FINISHED
    # Two busy batch streams keep the warm replica loaded: the
    # interactive probe load-escapes to the cold sibling, which pulls
    # the chain — and the router dies inside the import.
    busy = [fleet.submit(list(shared), 24, priority=Priority.BATCH)
            for _ in range(2)]
    fleet.step()
    armed["on"] = True
    with pytest.raises(KillPoint):
        fleet.submit(list(probe), 4, priority=Priority.INTERACTIVE)
    ref_busy = _ref_greedy(model, variables, list(shared), 24)

    def plain_factory():
        # Recovery replicas need no tier and no prefix pool — replay
        # rebuilds KV — but DO need a prefill window that admits the
        # 24-token prompts.
        return ServeEngine(model, variables, max_slots=2,
                           prefill_len=32, max_queue_depth=64,
                           backoff_sleep=_no_sleep)

    recovered, revived = FleetRouter.recover(
        d, [LocalReplica(i, plain_factory) for i in range(2)],
        affinity_block_size=8, affinity_blocks=1, respawn=False)
    recovered.run(max_steps=2000)
    prompts = [tuple(int(t) for t in fh.request.prompt)
               for fh in revived.values()]
    assert tuple(int(t) for t in probe) not in prompts  # never acked
    live = [fh for fh in revived.values()
            if fh.request.max_new_tokens == 24]
    assert len(live) == 2  # both busy streams revived
    for fh in live:
        assert fh.state == RequestState.FINISHED
        assert fh.tokens == ref_busy
    recovered.close()


def test_recover_unjournaled_router_is_empty(gpt_setup, tmp_path):
    model, variables = gpt_setup
    recovered, revived = FleetRouter.recover(
        str(tmp_path / "empty"), _fresh_replicas(model, variables, 1),
        respawn=False)
    assert revived == {}
    # The recovered (empty) router serves normally.
    h = recovered.submit(list(range(1, 8)), 3)
    recovered.run(max_steps=200)
    assert h.tokens == _ref_greedy(model, variables,
                                   list(range(1, 8)), 3)
    recovered.close()


# ---------------------------------------------------------- gray failure
def test_gray_detector_suspects_drift_and_recovers():
    det = GrayDetector(window=4, baseline=8, z_threshold=4.0,
                       min_excess_s=0.001, consecutive=2)
    rng = np.random.default_rng(0)
    for _ in range(12):
        det.observe(0, 0.001 + 1e-5 * rng.random())
        det.observe(1, 0.001 + 1e-5 * rng.random())
    assert det.suspected == set()
    # Replica 0 drifts; replica 1 stays in band.
    for _ in range(6):
        det.observe(0, 0.030)
        det.observe(1, 0.001 + 1e-5 * rng.random())
    assert det.suspected == {0}
    assert det.is_suspected(0) and not det.is_suspected(1)
    # While suspected, the baseline is FROZEN: staying slow does not
    # launder the drift away.
    for _ in range(20):
        det.observe(0, 0.030)
    assert det.suspected == {0}
    # Returning to the old band `consecutive` times clears it.
    det.observe(0, 0.001)
    det.observe(0, 0.001)
    assert det.suspected == set()
    det.forget(1)
    assert det.suspected == set()


def _make_gray(fleet, plans, victim_id, *, latency_s):
    """Drive the fleet until the detector suspects ``victim_id``: a
    long-running stream keeps each engine ticking; after a clean
    baseline window, the victim's every device call gains a latency
    injection on the fleet's `_StepWallClock`, which the router's
    per-step wall sampling sees."""
    det = fleet.gray
    # The median-of-``smooth`` prefilter (ISSUE 18 de-flake) consumes
    # ``smooth`` raw samples per window entry — scale the drive counts
    # so the baseline actually fills.
    need = (det.window + det.baseline) * det.smooth
    for _ in range(need + 2):
        fleet.step()
    plans[victim_id]._rates = (0.0, 0.0, 1.0)  # latency on every call
    plans[victim_id].latency_s = latency_s
    plans[victim_id]._sleep = fleet._gray_timer.sleep
    for _ in range(200 * det.smooth):
        fleet.step()
        # A gray_drain fleet acts on the suspicion INSIDE the same
        # step (and forgets the retired replica) — the executed drain
        # is the observable then, not the transient suspicion.
        if victim_id in det.suspected or fleet.metrics.gray_drains:
            return
    raise AssertionError(
        f"detector never suspected replica {victim_id}")


def test_gray_hedge_first_result_wins_token_exact(gpt_setup, tmp_path):
    model, variables = gpt_setup
    tracer = RequestTracer()
    fleet, plans = _local_fleet(
        model, variables, 2, with_plans=True, tracer=tracer,
        journal=RouterJournal(str(tmp_path / "wal")),
        # smooth=3 (ISSUE 18 de-flake): median-of-3 prefilter kills
        # single-sample wall outliers; baseline=4 medians keeps the
        # same 12 RAW samples of baseline coverage as before.
        gray=GrayDetector(window=4, baseline=4, z_threshold=4.0,
                          min_excess_s=0.002, consecutive=2, smooth=3),
        gray_hedge=True, gray_drain=False)
    # Pin a session to replica 0, and keep BOTH of its engine slots
    # busy so a later hedged request must queue there — which is what
    # lets the healthy sibling win by rounds, deterministically.
    pin = fleet.submit(list(range(1, 9)), 56, session="s0")
    victim_id = pin.replica_id
    busy = fleet.submit(list(range(2, 10)), 56, session="s0")
    assert busy.replica_id == victim_id
    _make_gray(fleet, plans, victim_id, latency_s=0.002)
    assert fleet.gray.suspected == {victim_id}
    # An INTERACTIVE submission stuck to the suspect hedges to the
    # healthy sibling...
    prompt = ((np.arange(7) * 5 + 3) % 32).astype(np.int32)
    ref = _ref_greedy(model, variables, prompt, 4)
    h = fleet.submit(prompt, 4, session="s0")
    assert fleet.metrics.hedges_launched == 1
    assert tracer.events_named("hedge")
    # ...a BATCH submission with the same routing does not.
    hb = fleet.submit(((np.arange(6) + 11) % 32).astype(np.int32), 3,
                      session="s0", priority=Priority.BATCH)
    assert fleet.metrics.hedges_launched == 1
    fleet.run(max_steps=3000)
    assert h.state == RequestState.FINISHED
    assert h.tokens == ref  # greedy determinism: either copy, one stream
    assert hb.state == RequestState.FINISHED
    # The pair settled exactly once: the healthy sibling won (the
    # suspect's copy was queued behind two busy slots), the loser was
    # cancelled.
    assert fleet.metrics.hedge_wins == 1
    assert fleet.metrics.hedge_cancelled == 1
    assert h.replica_id != victim_id
    assert not fleet._hedge_peer and not fleet._hedge_rids
    fleet.close()
    # The journal filed the WON hedge's tokens/finish under the
    # PRIMARY rid its admit used: every finished stream folds away —
    # a mismatch would leave the hedged stream resurrectable.
    entries, _ = journal_io.read_state(str(tmp_path / "wal"))
    assert entries == {}


def test_hedge_copy_failure_does_not_kill_the_stream(gpt_setup):
    """A hedge copy that fails with nothing emitted must be quietly
    abandoned — the healthy (if slow) primary keeps the stream, so
    hedging can never turn one admission into a failure it would not
    otherwise have."""
    model, variables = gpt_setup

    class FailsWhenArmed(LocalReplica):
        def __init__(self, rid, factory):
            super().__init__(rid, factory)
            self.fail_next = False
            self._fake = []

        def submit(self, rid, *a, **kw):
            if self.fail_next:
                self.fail_next = False
                self._fake.append({"ev": "finish", "rid": rid,
                                   "state": "failed", "reason": "error",
                                   "ttft_s": None, "n_tokens": 0})
                return
            super().submit(rid, *a, **kw)

        def step(self):
            events = super().step() + self._fake
            self._fake = []
            return events

    plans = [FaultPlan(sleep_fn=_no_sleep) for _ in range(2)]

    def factory(plan):
        def make():
            return ServeEngine(model, variables, max_slots=2,
                               prefill_len=16, fault_plan=plan,
                               backoff_sleep=_no_sleep)
        return make

    fleet = FleetRouter(
        [FailsWhenArmed(i, factory(plans[i])) for i in range(2)],
        affinity_block_size=8, affinity_blocks=1, respawn=False,
        gray_timer=_StepWallClock(),
        # smooth=3 (ISSUE 18 de-flake): median-of-3 prefilter kills
        # single-sample wall outliers; baseline=4 medians keeps the
        # same 12 RAW samples of baseline coverage as before.
        gray=GrayDetector(window=4, baseline=4, z_threshold=4.0,
                          min_excess_s=0.002, consecutive=2, smooth=3),
        gray_hedge=True, gray_drain=False)
    pin = fleet.submit(list(range(1, 9)), 56, session="s0")
    victim_id = pin.replica_id
    fleet.submit(list(range(2, 10)), 56, session="s0")
    _make_gray(fleet, plans, victim_id, latency_s=0.002)
    sibling = next(s for s in fleet.replicas
                   if s.replica_id != victim_id)
    sibling.driver.fail_next = True  # the hedge copy dies on arrival
    prompt = ((np.arange(7) * 5 + 3) % 32).astype(np.int32)
    ref = _ref_greedy(model, variables, prompt, 4)
    h = fleet.submit(prompt, 4, session="s0")
    assert fleet.metrics.hedges_launched == 1
    fleet.run(max_steps=3000)
    assert h.state == RequestState.FINISHED  # the primary carried it
    assert h.tokens == ref
    assert fleet.metrics.hedge_wins == 0
    assert fleet.metrics.hedge_cancelled == 0
    assert fleet.metrics.requests_failed == 0
    assert not fleet._hedge_peer and not fleet._hedge_rids
    fleet.close()


def test_gray_drain_retires_suspect_via_live_migration(gpt_setup):
    model, variables = gpt_setup
    tracer = RequestTracer()
    fleet, plans = _local_fleet(
        model, variables, 2, with_plans=True, tracer=tracer,
        # smooth=3 (ISSUE 18 de-flake): median-of-3 prefilter kills
        # single-sample wall outliers; baseline=4 medians keeps the
        # same 12 RAW samples of baseline coverage as before.
        gray=GrayDetector(window=4, baseline=4, z_threshold=4.0,
                          min_excess_s=0.002, consecutive=2, smooth=3),
        gray_hedge=False, gray_drain=True)
    pin = fleet.submit(list(range(1, 9)), 56, session="s0")
    victim_id = pin.replica_id
    busy = fleet.submit(list(range(2, 10)), 56, session="s0")
    assert busy.replica_id == victim_id
    refs = {tuple(range(1, 9)): _ref_greedy(model, variables,
                                            list(range(1, 9)), 56),
            tuple(range(2, 10)): _ref_greedy(model, variables,
                                             list(range(2, 10)), 56)}
    _make_gray(fleet, plans, victim_id, latency_s=0.002)
    # The suspect was retired through scale_down (live migration): its
    # in-flight streams moved and still finish token-exact.
    assert fleet.metrics.gray_drains == 1
    assert len(fleet.replicas) == 1
    assert fleet.replicas[0].replica_id != victim_id
    assert tracer.events_named("gray_drain")
    assert fleet.metrics.scale_down_events == 1
    fleet.run(max_steps=3000)
    for h in (pin, busy):
        assert h.state == RequestState.FINISHED
        assert h.tokens == refs[tuple(int(t) for t in h.request.prompt)]
        assert h.migrations >= 1
    fleet.close()


# --------------------------------------------------------- process fleet
_WORKER_CFG = dict(vocab=32, max_len=64, embed_dim=32, depth=1, heads=2,
                   slots=4, prefill_len=16, max_queue_depth=64,
                   param_seed=0)


@pytest.mark.chaos
def test_process_fleet_wire_storm_token_exact(pin_zero_recompiles):
    """Seeded transport-fault storm over two REAL worker processes:
    corrupt/truncate/duplicate/reorder/drop frames in both directions.
    Every stream terminal and token-exact, every corrupt frame refused
    (counted, never parsed), retries healed the gaps, zero recompiles
    on both replicas."""
    import subprocess

    from pddl_tpu.serve.fleet import ProcessReplica
    from pddl_tpu.serve.fleet.worker import build_engine

    plans = [WireFaultPlan(
        seed=100 + i, corrupt_rate=0.01, truncate_rate=0.005,
        duplicate_rate=0.01, reorder_rate=0.005, drop_rate=0.005,
        scheduled=[WireFaultSpec(5, "ev", WireFaultKind.CORRUPT),
                   WireFaultSpec(4, "cmd", WireFaultKind.DROP)])
        for i in range(2)]
    reps = [ProcessReplica(i, {**_WORKER_CFG, "replica_id": i},
                           python=sys.executable,
                           stderr=subprocess.DEVNULL,
                           wire_fault_plan=plans[i]) for i in range(2)]
    fleet = FleetRouter(reps, affinity_block_size=8, affinity_blocks=1,
                        respawn=False)
    fleet = pin_zero_recompiles(fleet)
    try:
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 32, size=10).tolist()
                   for _ in range(8)]
        handles = [fleet.submit(p, 12) for p in prompts]
        deadline = time.monotonic() + 120
        while any(not h.done for h in handles) \
                and time.monotonic() < deadline:
            fleet.step()
        eng = build_engine(_WORKER_CFG)
        for p, h in zip(prompts, handles):
            assert h.state == RequestState.FINISHED, f"stranded: {h}"
            assert h.tokens == _ref_greedy(
                eng.model, {"params": eng._params}, p, 12), \
                "stream diverged under the wire storm"
        # The storm actually fired, every corrupt frame was refused
        # (CRC), and the resend machinery healed the gaps.
        assert sum(p.total_injected for p in plans) > 0
        assert fleet.metrics.wire_crc_rejects > 0
        assert fleet.metrics.wire_retries > 0
        assert fleet.metrics.replica_down_events == 0
        assert fleet.metrics.requests_failed == 0
    finally:
        fleet.close()


def test_worker_self_reports_tick_walls_and_delay_knob():
    """Gray detection across a pipe rests on the worker self-reporting
    its engine-tick wall on pongs (the parent's pump wall cannot see a
    slow self-driving worker): samples flow through
    ``take_latency_samples``, and the ``set_tick_delay`` chaos knob
    visibly shifts them."""
    import subprocess

    from pddl_tpu.serve.fleet import ProcessReplica
    from pddl_tpu.serve.request import SamplingParams

    cfg = {**_WORKER_CFG, "replica_id": 0}
    rep = ProcessReplica(0, cfg, python=sys.executable,
                         stderr=subprocess.DEVNULL,
                         ping_interval_s=0.02)
    try:
        rep.submit(1, list(range(1, 9)), 50, SamplingParams(), None)
        deadline = time.monotonic() + 30
        clean: list = []
        while len(clean) < 5 and time.monotonic() < deadline:
            rep.step()
            clean.extend(s for s in rep.take_latency_samples()
                         if s is not None)
        assert clean, "no self-reported tick walls arrived"
        # The knob only shows on ticks, and ticks only happen with
        # work: slow the worker, then give it a second stream.
        rep.set_tick_delay(0.05)
        rep.submit(2, list(range(2, 10)), 50, SamplingParams(), None)
        slow: list = []
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            rep.step()
            slow.extend(s for s in rep.take_latency_samples()
                        if s >= 0.05)
            if len(slow) >= 3:
                break
        assert len(slow) >= 3, "delay knob never surfaced in samples"
        # The clean stream finishes in ~100ms, after which every pong
        # RE-REPORTS the final tick's wall — so one noise-inflated
        # last tick can dominate the clean median on a loaded host
        # (duplicated samples are not independent evidence). The
        # fastest clean tick is the only sample the duplication
        # artifact cannot poison: the knob's typical tick must clear
        # it by most of the injected 50ms.
        assert float(np.median(slow)) > float(min(clean)) + 0.04
    finally:
        rep.close()


def test_worker_oversized_frame_typed_reject_stays_alive():
    """The unbounded single-line pipe read, closed: a frame past the
    worker's max_frame_bytes is a TYPED reject (wire_error event, seq
    slot consumed) — the worker neither crashes nor wedges, and serves
    the next request normally."""
    import subprocess

    from pddl_tpu.serve.fleet import ProcessReplica
    from pddl_tpu.serve.request import SamplingParams

    cfg = {**_WORKER_CFG, "replica_id": 0, "slots": 2,
           "max_frame_bytes": 4096}
    rep = ProcessReplica(0, cfg, python=sys.executable,
                         stderr=subprocess.DEVNULL)
    try:
        rep._send({"cmd": "restore",
                   "requests": [[99, {"prompt": [1] * 6000,
                                      "max_new_tokens": 1}]]})
        deadline = time.monotonic() + 30
        rejected = False
        while not rejected and time.monotonic() < deadline:
            for ev in rep.step():
                if ev.get("ev") == "wire_error" \
                        and ev.get("kind") == "frame_too_large":
                    rejected = True
        assert rejected, "no typed oversize reject"
        # The worker survived AND its receive stream did not wedge: a
        # fresh request serves end-to-end.
        rep.submit(1, list(range(1, 7)), 3, SamplingParams(), None)
        deadline = time.monotonic() + 30
        ok = False
        while not ok and time.monotonic() < deadline:
            for ev in rep.step():
                if ev.get("ev") == "finish" and ev.get("rid") == 1:
                    assert ev["state"] == RequestState.FINISHED.value
                    ok = True
        assert ok, "worker did not serve after the oversize reject"
    finally:
        rep.close()


@pytest.mark.chaos
def test_process_fleet_router_crash_under_storm_recovers(tmp_path):
    """Router SIGKILL x transport-fault storm, process replicas: the
    journaled router dies mid-service under an injected wire storm;
    recovery spawns FRESH workers and every acked stream finishes
    token-exact, with zero recompiles on the recovered workers."""
    import subprocess

    from pddl_tpu.serve.fleet import ProcessReplica
    from pddl_tpu.serve.fleet.worker import build_engine

    d = str(tmp_path / "wal")

    def spawn(i, seed):
        return ProcessReplica(
            i, {**_WORKER_CFG, "replica_id": i}, python=sys.executable,
            stderr=subprocess.DEVNULL,
            wire_fault_plan=WireFaultPlan(seed, corrupt_rate=0.01,
                                          duplicate_rate=0.01,
                                          drop_rate=0.005))

    journal = CrashingJournal(d, fsync_batch_records=4)
    counters = {"tokens": 0}

    def kill_when(rec):
        if rec.get("rec") == "tokens":
            counters["tokens"] += 1
            return counters["tokens"] == 6
        return False

    journal.kill_when = kill_when
    reps = [spawn(i, 7 + i) for i in range(2)]
    fleet = FleetRouter(reps, affinity_block_size=8, affinity_blocks=1,
                        respawn=False, journal=journal)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 32, size=10).tolist() for _ in range(6)]
    handles = []
    try:
        for p in prompts:
            handles.append(fleet.submit(p, 10))
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            fleet.step()
    except KillPoint:
        pass
    assert journal.kill_when is None, "the crash coordinate never fired"
    # The dead router's workers are orphans; the machine reaps them.
    for rep in reps:
        rep.kill()
    recovered, revived = FleetRouter.recover(
        d, [spawn(10 + i, 70 + i) for i in range(2)],
        affinity_block_size=8, affinity_blocks=1, respawn=False)
    try:
        assert revived
        deadline = time.monotonic() + 120
        while any(not fh.done for fh in revived.values()) \
                and time.monotonic() < deadline:
            recovered.step()
        eng = build_engine(_WORKER_CFG)
        by_prompt = {tuple(p): _ref_greedy(
            eng.model, {"params": eng._params}, p, 10) for p in prompts}
        for fh in revived.values():
            assert fh.state == RequestState.FINISHED
            assert fh.tokens == by_prompt[
                tuple(int(t) for t in fh.request.prompt)]
        counts = recovered.compile_counts()
        assert counts and all(v == 1 for v in counts.values()), \
            f"recovered workers recompiled: {counts}"
    finally:
        recovered.close()


# -------------------------------------------------------- observability
def test_exposition_ctrlplane_series_both_directions(gpt_setup,
                                                     tmp_path):
    model, variables = gpt_setup
    fleet, plans = _local_fleet(
        model, variables, 2, with_plans=True,
        journal=RouterJournal(str(tmp_path / "wal")),
        gray=GrayDetector(window=4, baseline=4, min_excess_s=0.002,
                          consecutive=2, smooth=3))
    h = fleet.submit(list(range(1, 9)), 4, session="s0")
    victim_id = h.replica_id
    fleet.submit(list(range(2, 10)), 56, session="s0")
    _make_gray(fleet, plans, victim_id, latency_s=0.002)
    fleet.submit(list(range(3, 9)), 3, session="s0")  # hedges
    fleet.run(max_steps=2000)
    text = fleet_exposition(fleet)
    samples, types = parse_prometheus_text(text)  # strict referee in
    m = fleet.metrics                             # the render direction
    # ...and the parse direction: values round-trip exactly.
    for key, want in [("hedges_launched", m.hedges_launched),
                      ("hedge_wins", m.hedge_wins),
                      ("hedge_cancelled", m.hedge_cancelled),
                      ("gray_drains", m.gray_drains),
                      ("wire_retries", m.wire_retries),
                      ("wire_crc_rejects", m.wire_crc_rejects)]:
        name = f"pddl_fleet_{key}_total"
        assert types[name] == "counter"
        assert samples[(name, ())] == float(want)
    assert m.hedges_launched >= 1
    assert samples[("pddl_fleet_journal_bytes", ())] \
        == float(fleet.journal.wal_bytes)
    assert samples[("pddl_fleet_journal_lag_records", ())] \
        == float(fleet.journal.records_since_checkpoint)
    assert samples[("pddl_fleet_replicas_suspected_gray", ())] \
        == float(len(fleet.gray.suspected))
    assert types["pddl_fleet_journal_bytes"] == "gauge"
    fleet.close()
    # Unarmed fleet: the gauges still export, as NaN (present but
    # unobserved — a scrape can tell "off" from "vanished").
    bare, _ = _local_fleet(model, variables, 1)
    samples, _ = parse_prometheus_text(fleet_exposition(bare))
    assert math.isnan(samples[("pddl_fleet_journal_bytes", ())])
    assert math.isnan(
        samples[("pddl_fleet_replicas_suspected_gray", ())])
    bare.close()
