"""Fleet-wide distributed tracing (ISSUE 19), CPU.

The contracts under test:

- **Clock alignment** (`obs/propagate.py`): NTP-style offset samples
  off scripted ping/pong times; the minimal-RTT sample wins (its
  asymmetry error is bounded by the RTT), negative-RTT samples are
  discarded.
- **Span shipping**: the worker-side buffer is bounded, drops are
  counted (never silent), drain is FIFO and batch-limited.
- **Collector identity**: hedge aliases and the r20 hand-off rebind
  fold every secondary rid into the PRIMARY trace; ``context_for`` is
  pure (a failed routing attempt opens no phantom trace); the record
  ledger is bounded with terminal records evicted first.
- **Stitch across the hand-off** (`obs/assemble.py`): a split-fleet
  request's trace spans the prefill replica, the chain-wire transfer,
  and the decode replica with ZERO gaps — streams token-exact vs the
  greedy oracle, TTFT critical path resolvable with segments summing
  to TTFT.
- **Flight recorder** (`obs/flightrec.py`): CRC-framed rotation +
  prune round-trips through ``harvest``; a torn tail yields the
  readable prefix (the WAL's discipline); an injected storage storm
  degrades it to counted drops — appends never raise.
- **SIGKILL postmortem**: a hard-killed ProcessReplica's flight
  segments reassemble its final ticks (per-rid token prefixes of the
  canonical streams), the router writes the postmortem bundle, and
  every migrated stream's trace still stitches gap-free.
- **Chaos campaigns**: 3 seeded multi-plane campaigns with tracing
  armed hold the conductor's ``trace_complete`` invariant green.
"""

import json
import os
import struct
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.chaos import ChaosConductor, ReplicaChaos, local_kill
from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.obs import assemble as assemble_mod
from pddl_tpu.obs import flightrec as flightrec_mod
from pddl_tpu.obs.assemble import TRACE_SEGMENTS, aggregate, stitch
from pddl_tpu.obs.propagate import (
    ClockAligner,
    SpanShipper,
    TraceCollector,
    estimate_offset,
    trace_id_for_rid,
)
from pddl_tpu.serve import FaultPlan, ServeEngine
from pddl_tpu.serve.fleet import FleetRouter, LocalReplica
from pddl_tpu.utils.faults import StorageFaultPlan
from conftest import ref_greedy as _ref_greedy, FakeClock

pytestmark = pytest.mark.dtrace

BS = 8


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def _no_sleep(_):
    pass


def _engine_factory(model, variables, *, host=1 << 24, plan=None):
    """Hand-off-capable engine (prefix cache + host tier) — the same
    shape test_serve_disagg pins token-exact."""
    def make():
        return ServeEngine(model, variables, max_slots=2, prefill_len=32,
                           prefix_cache_blocks=24, prefix_block_size=BS,
                           prefix_chunk=BS, host_tier=host,
                           fault_plan=plan, max_queue_depth=64,
                           backoff_sleep=_no_sleep)
    return make


def _split_fleet(model, variables, n_prefill, n_decode, **router_kw):
    pf = _engine_factory(model, variables)
    df = _engine_factory(model, variables)
    replicas = [LocalReplica(i, pf, role="prefill")
                for i in range(n_prefill)]
    replicas += [LocalReplica(n_prefill + i, df, role="decode")
                 for i in range(n_decode)]
    return FleetRouter(replicas, affinity_block_size=BS,
                       affinity_blocks=1, respawn=False, **router_kw)


def _workload(n_requests, seed=0):
    """Cold prompts >= 1 full block (the exportable chain) with short
    greedy continuations."""
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n_requests):
        plen = int(rng.integers(12, 25))
        reqs.append((rng.integers(0, 32, size=plen).astype(np.int32),
                     int(rng.integers(3, 8))))
    return reqs


# ------------------------------------------------------- clock alignment
def test_estimate_offset_scripted_skew():
    """A remote clock running local+5s: symmetric samples recover the
    skew exactly; the midpoint assumption bounds the error of an
    asymmetric sample by half its RTT."""
    skew = 5.0
    # Remote reads its clock exactly mid-flight: offset is exact.
    off, rtt = estimate_offset(10.0, 10.2, 10.1 + skew)
    assert off == pytest.approx(skew)
    assert rtt == pytest.approx(0.2)
    # Fully asymmetric sample (remote read at the START of the round
    # trip): the error is rtt/2, never more.
    off_bad, rtt_bad = estimate_offset(30.0, 30.5, 30.0 + skew)
    assert abs(off_bad - skew) == pytest.approx(rtt_bad / 2.0)


def test_clock_aligner_min_rtt_wins():
    aligner = ClockAligner()
    skew = 5.0
    # High-RTT asymmetric sample first (offset error 0.25s)...
    aligner.observe(30.0, 30.5, 30.0 + skew)
    first = aligner.offset_s
    assert first is not None and abs(first - skew) > 0.2
    # ...then a tight sample: smaller RTT replaces it outright.
    aligner.observe(40.0, 40.01, 40.005 + skew)
    assert aligner.offset_s == pytest.approx(skew, abs=1e-9)
    assert aligner.best_rtt_s == pytest.approx(0.01)
    # A worse-RTT sample never overwrites the best one.
    aligner.observe(50.0, 50.3, 50.0 + skew)
    assert aligner.best_rtt_s == pytest.approx(0.01)
    # Negative RTT (clock stepped backwards mid-sample): discarded.
    aligner.observe(60.0, 59.9, 60.0 + skew)
    assert aligner.samples == 3
    assert aligner.best_rtt_s == pytest.approx(0.01)


# --------------------------------------------------------- span shipping
def test_span_shipper_bounds_and_drop_counting():
    shipper = SpanShipper(capacity=4)
    assert all(shipper.add({"i": i}) for i in range(4))
    assert not shipper.add({"i": 4})  # full: counted drop, no raise
    assert not shipper.add({"i": 5})
    assert shipper.dropped == 2
    assert len(shipper) == 4
    batch = shipper.drain(3)
    assert [r["i"] for r in batch] == [0, 1, 2]  # FIFO, batch-limited
    assert [r["i"] for r in shipper.drain(None)] == [3]
    assert shipper.shipped == 4
    assert len(shipper) == 0


# ---------------------------------------------------- collector identity
def test_collector_alias_rebind_and_purity():
    clock = FakeClock(100.0)
    col = TraceCollector(clock=clock)
    # context_for is PURE: probing a rid opens no phantom record.
    assert col.context_for(7) == (trace_id_for_rid(7), "router")
    assert col.records() == []
    col.on_submit(7, prompt_len=12, priority="batch")
    col.on_route(7, 0, how="affinity")
    # Hedge copy 8 and the hand-off's fresh rid 9 both alias to 7.
    col.on_hedge(8, 7, replica_id=1)
    col.rebind(8, 9)  # rebind chains THROUGH an alias to the primary
    assert col.primary_rid(9) == 7
    assert col.context_for(9)[0] == trace_id_for_rid(7)
    col.on_finish(9, "finished", "length", 5)
    recs = [r for r in col.records() if r["kind"] == "fleet_span"]
    assert len(recs) == 1  # one trace, not three
    assert recs[0]["trace_id"] == trace_id_for_rid(7)
    assert recs[0]["state"] == "finished"
    assert recs[0]["n_tokens"] == 5
    names = [e["name"] for e in recs[0]["events"]]
    assert names == ["submit", "route", "hedge", "finish"]


def test_collector_eviction_prefers_terminal_records():
    col = TraceCollector(clock=FakeClock(0.0), max_traces=2)
    col.on_submit(1, prompt_len=4, priority="batch")
    col.on_finish(1, "finished", "length", 3)
    col.on_submit(2, prompt_len=4, priority="batch")  # live
    col.on_submit(3, prompt_len=4, priority="batch")  # overflows
    assert col.records_dropped == 1
    kept = {r["rid"] for r in col.records()
            if r["kind"] == "fleet_span"}
    assert kept == {2, 3}  # the TERMINAL record retired first


# ------------------------------------------- stitch across the hand-off
def test_stitch_across_handoff_token_exact(gpt_setup):
    """One prefill + one decode replica: every stream token-exact vs
    the oracle, every trace gap-free spanning BOTH replicas with the
    chain-wire transfer spans and the hand-off on the router record."""
    model, variables = gpt_setup
    fleet = _split_fleet(model, variables, 1, 1, dtrace=True)
    assert fleet.disagg_armed and fleet.dtrace is not None
    reqs = _workload(6, seed=1)
    refs = [_ref_greedy(model, variables, p, n) for p, n in reqs]
    handles = [fleet.submit(p, n) for p, n in reqs]
    fleet.run(max_steps=1200)
    for _ in range(3):  # let the last finish's spans ship
        fleet.step()
    for h, ref in zip(handles, refs):
        assert list(h.tokens) == ref
    traces = stitch(fleet.dtrace.records())
    assert len(traces) == len(reqs)
    handed_off = 0
    for trace in traces.values():
        assert trace.gaps() == []
        events = [e["name"] for e in trace.router["events"]]
        if "handoff" in events:
            handed_off += 1
            # The trace spans prefill replica -> wire -> decode replica.
            assert set(trace.replicas()) == {0, 1}
            assert {s["name"] for s in trace.chain_spans()} == {
                "chain_export", "chain_import"}
            assert "handoff_export" in events
            assert "handoff_import" in events
        cp = trace.critical_path()
        assert cp is not None
        # Segments sum exactly to TTFT (first_tick is the residual).
        total = sum(cp[name] for name in TRACE_SEGMENTS)
        assert total == pytest.approx(cp["ttft_s"], abs=1e-9)
    assert handed_off == fleet.metrics.handoffs_completed > 0
    fleet.close()


def test_aggregate_and_cli_report(gpt_setup, tmp_path, capsys):
    """The fleet-level attribution surface: aggregate() percentiles
    over a traced unified fleet, the collector dump, and the
    ``python -m pddl_tpu.obs.assemble`` CLI over it."""
    model, variables = gpt_setup
    factory = _engine_factory(model, variables)
    fleet = FleetRouter(
        [LocalReplica(0, factory), LocalReplica(1, factory)],
        affinity_block_size=BS, affinity_blocks=1, respawn=False,
        dtrace=True)
    reqs = _workload(5, seed=2)
    handles = [fleet.submit(p, n) for p, n in reqs]
    fleet.run(max_steps=600)
    for _ in range(3):
        fleet.step()
    assert all(h.done for h in handles)
    traces = stitch(fleet.dtrace.records())
    agg = aggregate(traces.values())
    assert agg["traces"] == len(reqs)
    assert agg["attributed"] == len(reqs)
    assert agg["gappy"] == 0
    assert agg["segments"]["ttft_s"]["p50_s"] > 0.0
    assert "prefill" in agg["segments"]
    dump = tmp_path / "trace.jsonl"
    n = fleet.dtrace.dump(str(dump))
    assert n == len(fleet.dtrace.records())
    fleet.close()
    assert assemble_mod.main([str(dump)]) == 0
    report = capsys.readouterr().out
    assert f"traces={len(reqs)} attributed={len(reqs)} gappy=0" in report
    assert "first_tick" in report
    assert assemble_mod.main([str(dump), "--json"]) == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["gappy"] == 0


# -------------------------------------------------------- flight recorder
def test_flightrec_rotation_prune_and_harvest(tmp_path):
    d = str(tmp_path / "frec")
    rec = flightrec_mod.FlightRecorder(d, max_segment_bytes=256,
                                       max_segments=2)
    for i in range(40):
        assert rec.append({"kind": "flight_tick", "i": i})
    rec.close()
    assert rec.rotations > 2  # rotation happened, prune engaged
    segs = [n for n in os.listdir(d) if n.startswith("seg-")]
    assert 0 < len(segs) <= 2
    got = flightrec_mod.harvest(d)
    # Oldest segments were pruned: harvest returns a contiguous TAIL
    # of the append stream, in order, ending at the last record.
    idx = [r["i"] for r in got]
    assert idx == list(range(idx[0], 40))
    assert rec.counts()["records_written"] == 40


def test_flightrec_torn_tail_yields_readable_prefix(tmp_path):
    d = str(tmp_path / "frec")
    rec = flightrec_mod.FlightRecorder(d, max_segment_bytes=1 << 20)
    for i in range(5):
        rec.append({"i": i})
    rec.close()
    path = os.path.join(d, flightrec_mod.CURRENT_NAME)
    with open(path, "rb") as f:
        data = f.read()
    # A SIGKILL mid-write: append half a frame, then garbage.
    payload = json.dumps({"i": 99}).encode()
    frame = struct.pack(">4sII", b"PFR1", len(payload),
                        zlib.crc32(payload)) + payload
    with open(path, "ab") as f:
        f.write(frame[:len(frame) // 2])
    assert [r["i"] for r in flightrec_mod.readable_records(
        data + frame[:len(frame) // 2])] == list(range(5))
    # CRC mismatch stops the read at the corrupt frame too.
    bad = bytearray(data)
    bad[-1] ^= 0xFF
    assert len(flightrec_mod.readable_records(bytes(bad))) == 4
    # harvest() over the directory applies the same prefix rule.
    assert [r["i"] for r in flightrec_mod.harvest(d)] == list(range(5))


def test_flightrec_storage_faults_degrade_counted(tmp_path):
    """A dying disk degrades the recorder to counted no-export —
    appends keep returning (False), nothing raises, serving notices
    nothing."""
    plan = StorageFaultPlan(seed=3, eio_rate=1.0)
    rec = flightrec_mod.FlightRecorder(str(tmp_path / "frec"),
                                       storage_plan=plan,
                                       error_limit=3)
    results = [rec.append({"i": i}) for i in range(10)]
    assert not any(results)
    assert rec.disabled
    assert rec.records_dropped == 10
    assert rec.errors >= 1
    rec.close()


# --------------------------------------------------- SIGKILL postmortem
_WORKER_CFG = dict(vocab=32, max_len=64, embed_dim=32, depth=1, heads=2,
                   slots=4, prefill_len=16, max_queue_depth=64,
                   param_seed=0)


def test_sigkill_flight_harvest_and_postmortem(tmp_path):
    """Hard-kill a traced ProcessReplica mid-stream: the router
    harvests its flight segments (final ticks reassembled as per-rid
    token prefixes of the canonical streams), writes the postmortem
    bundle, and every migrated stream finishes with a gap-free trace."""
    import subprocess
    import sys
    import time

    from pddl_tpu.serve.fleet import ProcessReplica

    frdirs = [str(tmp_path / f"frec-{i}") for i in range(2)]
    reps = [ProcessReplica(
        i, {**_WORKER_CFG, "replica_id": i, "dtrace": True,
            "flightrec_dir": frdirs[i]},
        python=sys.executable, stderr=subprocess.DEVNULL,
        ping_interval_s=0.01, wait_ready=False) for i in range(2)]
    for r in reps:
        r.wait_ready()
    fleet = FleetRouter(reps, respawn=False, dtrace=True)
    try:
        rng = np.random.default_rng(4)
        prompts = [rng.integers(0, 32, size=10).tolist()
                   for _ in range(6)]
        handles = [fleet.submit(p, 24) for p in prompts]
        rids = dict(fleet._by_rid)  # rid -> handle, before migration
        deadline = time.monotonic() + 60.0
        while (any(len(h.tokens) < 2 for h in handles)
               and time.monotonic() < deadline):
            fleet.step()
        assert all(len(h.tokens) >= 2 for h in handles)
        victim = fleet.replicas[0]
        served = list(victim.assigned)  # rids on the doomed replica
        assert served  # the kill must actually orphan streams
        victim.driver.kill()
        deadline = time.monotonic() + 120.0
        while (any(not h.done for h in handles)
               and time.monotonic() < deadline):
            fleet.step()
        assert all(h.state.value == "finished" for h in handles)
        drain = time.monotonic() + 1.0
        while time.monotonic() < drain:
            fleet.step()
            time.sleep(0.01)
        # The postmortem bundle landed next to the dead worker's
        # segments, quoting what the harvest recovered.
        bundles = [n for n in os.listdir(frdirs[0])
                   if n.startswith("postmortem-")]
        assert len(bundles) == 1
        with open(os.path.join(frdirs[0], bundles[0])) as f:
            bundle = json.load(f)
        assert bundle["replica"] == 0
        assert bundle["harvested_records"] > 0
        assert {int(rid) for rid, _ in bundle["mirrors"]} == set(served)
        # The flight segments reassemble the dead worker's final
        # ticks: concatenated per-rid tokens are prefixes of the
        # canonical streams the router finished elsewhere.
        flight = flightrec_mod.harvest(frdirs[0])
        assert any(r.get("kind") == "flight_tick" for r in flight)
        flight_toks = {}
        for r in flight:
            if r.get("kind") == "flight_tokens":
                for rid, toks in r["toks"]:
                    flight_toks.setdefault(int(rid), []).extend(
                        int(t) for t in toks)
        assert flight_toks  # the final ticks ARE in the file
        for rid, toks in flight_toks.items():
            full = list(rids[rid].tokens)
            assert toks == full[:len(toks)]
        # Every stream's trace still stitches gap-free ACROSS the
        # migration, and both replicas shipped pipe spans.
        traces = stitch(fleet.dtrace.records())
        assert len(traces) == len(handles)
        for trace in traces.values():
            assert trace.gaps() == []
        shipped = {r.get("replica") for r in fleet.dtrace.records()
                   if r.get("kind") == "span"
                   and r.get("source") == "pipe"}
        assert 1 in shipped  # the survivor kept shipping
    finally:
        fleet.close()


# ------------------------------------------------------ chaos campaigns
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_conductor_campaign_trace_complete(gpt_setup, tmp_path, seed):
    """The composed-plane campaign with tracing armed: the referee's
    ``trace_complete`` invariant (every stitched trace gap-free after
    storms, kills and a router crash) holds across 3 seeds — and is
    CHECKED, not auto-skipped."""
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
                base + k,
                _engine_factory(model, variables, host=None, plan=plan)))
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
                       respawn=False, dtrace=True),
        journal_kw=dict(fsync_batch_records=2, retry_limit=1,
                        retry_backoff_s=0.0, rearm_interval_s=0.0,
                        sleep_fn=_no_sleep),
        recovery_bound_s=30.0, seed=seed)
    report = cond.run(
        [(p, n) for p, n in _workload(5, seed=200 + seed)],
        planes=("device", "storage", "kill", "router"),
        horizon=30, kills=1, max_wall_s=90.0)
    assert report.ok, report.violations
    assert report.invariants["trace_complete"] is True
    assert not any(s.startswith("trace_complete")
                   for s in report.skipped)
    kinds = [a.kind for a in report.actions]
    assert {"kill", "router_crash"} <= set(kinds)
