"""Observability layer (`pddl_tpu/obs/`), CPU.

The contracts under test:

- **Zero-cost disabled**: with the default no-op tracer, a full engine
  run allocates NOTHING attributable to `obs/trace.py` (tracemalloc
  pin) — tracing off must be indistinguishable from the pre-obs
  engine.
- **Span timelines**: a traced request's span reconstructs the whole
  lifecycle — queued → admitted (queue wait) → prefix match → prefill
  chunks → first token → per-tick decode events → finish — with
  monotone timestamps, and the JSONL sink round-trips it.
- **Ring buffer**: capacity is respected under arbitrary load (oldest
  overwritten, newest kept), records carry per-site dispatch wall
  time, and the summary aggregates the window.
- **Exporters**: the Prometheus text exposition round-trips through a
  STRICT parser; every `ServeMetrics.snapshot()` key appears in both
  the snapshot and the exposition (the drift guard — a new counter
  cannot silently skip export); the stdlib `/metrics` endpoint serves
  the same body over HTTP.
- **Reservoirs**: `ServeMetrics` memory is bounded under sustained
  load while snapshot percentiles stay stable (capped uniform
  sampling), and zero-recompile holds with tracing enabled.
- **Phase spans**: ``step()``'s span tree (`serve/metrics.PHASES`)
  reaches its three sinks — the always-on ``ServeMetrics`` counters
  (exact against an injected clock), the ring record (handed to the
  tracer's ``on_tick`` as it is), and ``pddl.serve.*`` spans in a
  ``jax.profiler`` trace.
- **Set-up stages and compiles**: the engine's own set-up
  (`serve/metrics.SETUP_STAGES`) reaches ``setup_wall_s`` and
  ``pddl.serve.setup.*`` spans and sums to the wall timed around it from
  outside; a backend compile is counted under the phase or stage open
  on its thread (and in the ring record of its step), a persistent-cache
  load as a hit; the benchmark's readers of those counters.
"""

import json
import time
import tracemalloc
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import generate, tiny_gpt
from pddl_tpu.obs import (
    SERVE_COUNTER_KEYS,
    JsonlEventLog,
    MetricsHTTPServer,
    NullTracer,
    RequestTracer,
    TelemetryRing,
    engine_gauges,
    parse_prometheus_text,
    read_jsonl,
    render_prometheus,
    serve_exposition,
)
from pddl_tpu.serve import (
    FaultKind,
    FaultPlan,
    FaultSpec,
    FinishReason,
    ServeEngine,
    TenantConfig,
)
from pddl_tpu.serve.metrics import (
    COMPILE_PLACES,
    PHASES,
    SETUP_STAGES,
    Reservoir,
    ServeMetrics,
)
from pddl_tpu.utils.profiling import StepTimer
from conftest import ref_greedy as _ref_greedy

pytestmark = pytest.mark.obs


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


# ---------------------------------------------------------------- tracer
def test_disabled_tracer_allocates_nothing(gpt_setup):
    """The zero-cost-when-disabled pin: run a real workload through an
    engine with the default no-op tracer and assert tracemalloc saw
    ZERO net allocations attributed to obs/trace.py."""
    from pddl_tpu.obs import trace as trace_mod

    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    eng.warmup()
    assert eng.tracer is trace_mod.NULL_TRACER
    handles = [eng.submit((np.arange(5) + i) % 32, 4) for i in range(3)]
    eng.run(max_steps=5)  # warm every code path before measuring
    tracemalloc.start()
    try:
        snap_before = tracemalloc.take_snapshot()
        eng.run(max_steps=200)
        snap_after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    assert all(h.done for h in handles)
    trace_file = trace_mod.__file__
    diff = snap_after.filter_traces(
        [tracemalloc.Filter(True, trace_file)]).compare_to(
        snap_before.filter_traces(
            [tracemalloc.Filter(True, trace_file)]), "lineno")
    grew = [d for d in diff if d.size_diff > 0]
    assert not grew, f"disabled tracer allocated: {grew}"


def test_disabled_tracer_dtrace_hooks_allocate_nothing():
    """The ISSUE 19 extension of the zero-cost pin: the distributed-
    tracing hook surface (trace context stamping, restore, chain
    transfer, span shipping, flight-recorder rotation) must be no-op
    AND allocation-free on the NullTracer — these hooks sit on the
    fleet hot paths of every UNtraced fleet too."""
    from pddl_tpu.obs import trace as trace_mod

    tracer = trace_mod.NULL_TRACER

    def drive():
        for i in range(200):
            tracer.on_trace_context(i, "0" * 16, "router")
            tracer.on_restored(None, i)
            tracer.on_chain_export(3, 0.001)
            tracer.on_chain_import(3, 0.001)
            tracer.on_span_shipped(4, 0)
            tracer.on_flight_rotate(2, 4096)

    drive()  # warm the code paths before measuring
    tracemalloc.start()
    try:
        snap_before = tracemalloc.take_snapshot()
        drive()
        snap_after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    trace_file = trace_mod.__file__
    diff = snap_after.filter_traces(
        [tracemalloc.Filter(True, trace_file)]).compare_to(
        snap_before.filter_traces(
            [tracemalloc.Filter(True, trace_file)]), "lineno")
    grew = [d for d in diff if d.size_diff > 0]
    assert not grew, f"disabled dtrace hooks allocated: {grew}"


def test_span_timeline_reconstructs_request(gpt_setup, tmp_path,
                                            pin_zero_recompiles):
    """One traced request: the span carries the full queue → admission
    → prefix match → prefill chunks → first token → decode → finish
    timeline with monotone timestamps, and the JSONL sink holds the
    identical record. Zero recompiles with tracing ON."""
    model, variables = gpt_setup
    path = str(tmp_path / "trace.jsonl")
    log = JsonlEventLog(path)
    tracer = RequestTracer(sink=log)
    eng = pin_zero_recompiles(ServeEngine(
        model, variables, max_slots=2, prefill_len=16, tracer=tracer))
    p, n = (np.arange(10) * 3 + 1) % 32, 5
    h = eng.submit(p, n)
    eng.run(max_steps=50)
    log.close()
    assert h.tokens == _ref_greedy(model, variables, p, n)
    assert tracer.spans_finished == 1
    (record,) = list(tracer.finished)
    assert record["kind"] == "span"
    assert record["schema"] == 1
    assert record["finish_reason"] == "length"
    assert record["attrs"]["prompt_len"] == 10
    assert record["attrs"]["tokens_emitted"] == n
    assert record["attrs"]["ttft_s"] >= 0
    names = [e["name"] for e in record["events"]]
    assert names[0] == "queued"
    assert "admitted" in names
    assert "prefix_match" in names  # prefix cache is on by default
    assert "prefill_chunk" in names
    assert "first_token" in names
    assert names.count("decode") == n - 1  # first token isn't a tick
    ts = [e["t_s"] for e in record["events"]]
    assert ts == sorted(ts), "span events out of order"
    assert record["end_s"] >= record["start_s"]
    admitted = next(e for e in record["events"] if e["name"] == "admitted")
    assert admitted["queue_wait_s"] >= 0
    chunks = [e for e in record["events"] if e["name"] == "prefill_chunk"]
    assert all(c["wall_s"] > 0 for c in chunks)
    # The sink's line is the same record, schema-stamped.
    (from_disk,) = [r for r in read_jsonl(path) if r["kind"] == "span"]
    assert from_disk == json.loads(json.dumps(record))


def test_broken_sink_never_crashes_the_engine(gpt_setup, tmp_path):
    """Observability must never be a fault source: a sink that closes
    (or throws) mid-run degrades to counted no-export — the engine
    keeps serving, drains cleanly, and the in-process deques still
    hold the records."""
    model, variables = gpt_setup
    log = JsonlEventLog(str(tmp_path / "t.jsonl"))
    tracer = RequestTracer(sink=log)
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      tracer=tracer)
    h1 = eng.submit(np.arange(5) % 32, 3)
    eng.run(max_steps=30)
    assert h1.done
    log.close()  # the sink dies under the engine
    h2 = eng.submit((np.arange(6) + 1) % 32, 3)
    eng.run(max_steps=30)
    assert h2.done
    assert eng.drain()["telemetry"]["ticks"] > 0  # drain event eats it
    assert tracer.sink_errors > 0
    assert tracer.spans_finished == 2  # records survive in-process


def test_drain_flushes_inflight_spans(gpt_setup, tmp_path):
    """SIGTERM-drain is exactly when a postmortem needs the spans:
    every in-flight request's span must be flushed to the sink with
    finish_reason 'drained' (the requests resume in a FRESH engine —
    these records would otherwise never land)."""
    model, variables = gpt_setup
    path = str(tmp_path / "drain_trace.jsonl")
    log = JsonlEventLog(path)
    tracer = RequestTracer(sink=log)
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      tracer=tracer)
    running = eng.submit(np.arange(5) % 32, 20)
    queued = eng.submit((np.arange(6) + 1) % 32, 4)
    for _ in range(3):
        eng.step()
    assert not running.done and not queued.done
    eng.drain()
    log.close()
    assert not tracer.active
    spans = [r for r in read_jsonl(path) if r["kind"] == "span"]
    assert len(spans) == 2
    assert all(s["finish_reason"] == "drained" for s in spans)
    assert all(s["attrs"]["drained"] for s in spans)
    # The running request's history survived into the flushed span.
    by_id = {s["request_id"]: s for s in spans}
    run_span = by_id[running.request.request_id]
    names = [e["name"] for e in run_span["events"]]
    assert "admitted" in names and "decode" in names


def test_span_event_cap_drops_and_counts(gpt_setup):
    model, variables = gpt_setup
    tracer = RequestTracer(max_events_per_span=4)
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      tracer=tracer)
    h = eng.submit(np.arange(6) % 32, 10)
    eng.run(max_steps=50)
    assert h.done
    (record,) = list(tracer.finished)
    assert len(record["events"]) == 4
    assert record["events_dropped"] > 0


def test_decode_events_have_their_own_budget(gpt_setup):
    """A long stream must not crowd rare lifecycle events out of the
    span: decode events stop at their own cap while later non-decode
    events still land."""
    model, variables = gpt_setup
    tracer = RequestTracer(max_decode_events_per_span=2)
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      tracer=tracer)
    h = eng.submit(np.arange(6) % 32, 10)
    eng.run(max_steps=50)
    assert h.done
    (record,) = list(tracer.finished)
    names = [e["name"] for e in record["events"]]
    assert names.count("decode") == 2
    assert record["events_dropped"] == 10 - 1 - 2  # the overflow
    assert record["finish_reason"] == "length"  # finish still settled


# ------------------------------------------------------------------ ring
def test_ring_respects_capacity_and_order():
    ring = TelemetryRing(capacity=4)
    assert len(ring) == 0 and ring.last() is None
    for i in range(11):
        ring.append({"step": i, "tick_wall_s": 0.001 * (i + 1),
                     "queue_depth": i, "live_slots": 1, "tokens": 2,
                     "retries": 0, "degraded": False,
                     "site_wall_s": {"tick": 0.001}})
    assert len(ring) == 4
    assert ring.total_appended == 11
    steps = [r["step"] for r in ring.snapshot()]
    assert steps == [7, 8, 9, 10]  # oldest evicted, order kept
    assert ring.last()["step"] == 10
    summary = ring.summary()
    assert summary["ticks"] == 4
    assert summary["tokens_emitted"] == 8
    assert summary["site_wall_s"] == {"tick": 0.004}
    with pytest.raises(ValueError, match="capacity"):
        TelemetryRing(capacity=0)


def test_engine_ring_records_per_site_wall(gpt_setup):
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      telemetry_capacity=8)
    handles = [eng.submit((np.arange(6) + i) % 32, 3) for i in range(3)]
    eng.run(max_steps=50)
    assert all(h.done for h in handles)
    assert len(eng.telemetry) <= 8
    window = eng.telemetry.snapshot()
    assert [r["step"] for r in window] == sorted(r["step"] for r in window)
    # An admission step saw admission sites; every live step saw a tick.
    sites = set()
    for r in window:
        sites.update(r["site_wall_s"])
        assert r["tick_wall_s"] >= 0
    assert "tick" in sites
    total_tokens = sum(r["tokens"] for r in eng.telemetry.snapshot())
    assert total_tokens <= 9  # window may have dropped early steps


# ------------------------------------------------------------- exporters
def test_jsonl_log_appends_whole_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with JsonlEventLog(path) as log:
        log.write({"kind": "tick", "step": 0, "np": np.int32(3)})
        log.write({"kind": "tick", "step": 1, "schema": 99})
    # Reopening appends, never truncates.
    with JsonlEventLog(path) as log:
        log.write({"kind": "span", "step": 2})
    records = read_jsonl(path)
    assert [r["kind"] for r in records] == ["tick", "tick", "span"]
    assert records[0]["schema"] == 1   # stamped
    assert records[0]["np"] == 3       # numpy scalars serialize
    assert records[1]["schema"] == 99  # caller's schema respected
    with pytest.raises(ValueError, match="closed"):
        log.write({"kind": "tick"})


def test_prometheus_render_parses_strict():
    snap = {"requests_finished": 3, "ttft_p50_s": 0.125,
            "maybe_none": None, "flag": True,
            "compile_counts": {"tick": 1, "sample_first": 1}}
    text = render_prometheus(snap, prefix="pddl_serve",
                             counters=frozenset({"requests_finished"}))
    samples, types = parse_prometheus_text(text)
    assert types["pddl_serve_requests_finished_total"] == "counter"
    assert types["pddl_serve_ttft_p50_s"] == "gauge"
    assert samples[("pddl_serve_requests_finished_total", ())] == 3.0
    assert samples[("pddl_serve_ttft_p50_s", ())] == 0.125
    assert np.isnan(samples[("pddl_serve_maybe_none", ())])
    assert samples[("pddl_serve_flag", ())] == 1.0
    assert samples[("pddl_serve_compile_counts",
                    (("key", "tick"),))] == 1.0
    # The parser is a real referee: malformed input is loud.
    for bad in ("pddl metric 1", "name{unclosed 1", "name 1 2 3",
                "# TYPE name bogus"):
        with pytest.raises(ValueError):
            parse_prometheus_text(bad)
    with pytest.raises(ValueError, match="not exposition-legal"):
        render_prometheus({"bad-key": 1})


def test_snapshot_drift_guard_every_metric_exported(gpt_setup):
    """THE drift guard: every counter/gauge in `ServeMetrics.snapshot()`
    must appear in the Prometheus exposition (and every declared
    counter key must still exist in the snapshot), so a new metric
    cannot ship half-exported."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    h = eng.submit(np.arange(6) % 32, 3)
    eng.run(max_steps=30)
    assert h.done
    snap = eng.metrics.snapshot()
    text = serve_exposition(eng.metrics, eng)
    samples, types = parse_prometheus_text(text)
    exported = {name for name, _ in samples}
    for key in snap:
        name = f"pddl_serve_{key}"
        if key in SERVE_COUNTER_KEYS:
            name += "_total"
        assert name in exported, \
            f"snapshot key {key!r} missing from the exposition"
        expect = "counter" if key in SERVE_COUNTER_KEYS else "gauge"
        assert types[name] == expect
    # Stale declarations are drift too: every declared counter must
    # still be a snapshot key.
    assert SERVE_COUNTER_KEYS <= set(snap), \
        "SERVE_COUNTER_KEYS declares a metric snapshot() no longer has"
    # Engine gauges ride along (the ISSUE's dashboard set).
    for gauge in ("pddl_serve_engine_live_slots",
                  "pddl_serve_engine_degraded",
                  "pddl_serve_engine_prefix_pool_nbytes",
                  "pddl_serve_engine_compile_counts",
                  "pddl_serve_ring_tick_wall_p50_s"):
        assert any(name == gauge for name, _ in samples), gauge
    for key in engine_gauges(eng):
        assert f"pddl_serve_engine_{key}" in {n for n, _ in samples}


def test_latency_histograms_round_trip_strict():
    """The ISSUE 19 exposition satellite: TTFT and token-latency
    render as conventional CUMULATIVE ``_bucket`` histograms —
    ascending ``le``, ``le="+Inf"`` equal to ``_count``, ``_sum``
    over the same samples — and the whole body round-trips through
    the strict parser in both directions (each histogram verified
    sample-exact from the parsed side)."""
    from pddl_tpu.obs import (TOKEN_LATENCY_BUCKETS_S, TTFT_BUCKETS_S,
                              reservoir_histogram)

    metrics = ServeMetrics()
    ttfts = [0.004, 0.03, 0.03, 0.2, 3.0, 30.0]  # incl. one > max edge
    toklats = [0.0005, 0.002, 0.02, 0.02, 0.3]
    for v in ttfts:
        metrics.ttft_s.append(v)
    metrics.token_latency_s.extend(toklats)
    text = serve_exposition(metrics)
    samples, types = parse_prometheus_text(text)
    for name, buckets, values in (
            ("pddl_serve_ttft_seconds", TTFT_BUCKETS_S, ttfts),
            ("pddl_serve_token_latency_seconds",
             TOKEN_LATENCY_BUCKETS_S, toklats)):
        assert types[name] == "histogram"
        # Cumulative and ascending, each bucket counting v <= le.
        prev = 0
        for edge in sorted(buckets):
            got = samples[(f"{name}_bucket",
                           (("le", format(edge, "g")),))]
            assert got == sum(1 for v in values if v <= edge)
            assert got >= prev
            prev = got
        inf = samples[(f"{name}_bucket", (("le", "+Inf"),))]
        assert inf == len(values) == samples[(f"{name}_count", ())]
        assert samples[(f"{name}_sum", ())] == pytest.approx(
            sum(values))
    # The other direction: a hand-built spec renders, parses, and
    # reproduces itself bucket-for-bucket.
    spec = reservoir_histogram([0.01, 0.5], (0.1, 1.0))
    assert spec["buckets"] == {"0.1": 1, "1": 2, "+Inf": 2}
    body = render_prometheus({}, prefix="pddl_x",
                             histograms={"lat_seconds": spec})
    parsed, ptypes = parse_prometheus_text(body)
    assert ptypes["pddl_x_lat_seconds"] == "histogram"
    assert {le: parsed[("pddl_x_lat_seconds_bucket", (("le", le),))]
            for le in spec["buckets"]} == {
                le: float(c) for le, c in spec["buckets"].items()}
    assert parsed[("pddl_x_lat_seconds_count", ())] == 2.0
    # An empty reservoir still exports the full (all-zero) ladder.
    empty = reservoir_histogram(Reservoir(4), TTFT_BUCKETS_S)
    assert empty["count"] == 0 and empty["sum"] == 0.0
    assert set(empty["buckets"].values()) == {0}
    parse_prometheus_text(render_prometheus(
        {}, prefix="pddl_y", histograms={"e_seconds": empty}))


def test_metrics_http_endpoint_scrapes(gpt_setup):
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    h = eng.submit(np.arange(4) % 32, 2)
    eng.run(max_steps=20)
    assert h.done
    with MetricsHTTPServer(lambda: serve_exposition(eng.metrics, eng)) \
            as server:
        with urllib.request.urlopen(server.url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        samples, _ = parse_prometheus_text(body)
        assert samples[("pddl_serve_requests_finished_total", ())] == 1.0
        # Anything but /metrics is a 404, and a scrape survives it.
        bad = urllib.request.Request(
            f"http://{server.host}:{server.port}/other")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(bad, timeout=10)
        assert exc.value.code == 404
        with urllib.request.urlopen(server.url, timeout=10) as resp:
            assert resp.status == 200


def test_step_timer_routes_through_renderer():
    """The training-side satellite: StepTimer emits the ServeMetrics
    snapshot-dict shape (stable keys, None before data, p99 included)
    and renders through the same Prometheus path."""
    timer = StepTimer(global_batch_size=8, verbose=0)
    cold = timer.snapshot()
    assert cold["step_time_p99_s"] is None
    assert cold["steps_timed"] == 0.0
    timer.step_times = [0.01 * (i + 1) for i in range(100)]
    snap = timer.snapshot()
    assert snap["step_time_p99_s"] >= snap["step_time_p90_s"] \
        >= snap["step_time_p50_s"]
    assert snap["steps_timed"] == 100.0
    assert snap["images_per_sec"] > 0
    text = render_prometheus(snap, prefix="pddl_train_step")
    samples, _ = parse_prometheus_text(text)
    assert samples[("pddl_train_step_step_time_p99_s", ())] == \
        pytest.approx(snap["step_time_p99_s"])
    assert samples[("pddl_train_step_steps_timed", ())] == 100.0


# ------------------------------------------------------------ reservoirs
def test_reservoir_caps_memory_keeps_percentiles():
    """The unbounded-growth fix: 200k samples through an 8k reservoir
    hold 8k floats, and p50/p99 stay within a tight tolerance of the
    true stream percentiles (uniform reservoir sampling)."""
    rng = np.random.default_rng(0)
    stream = rng.lognormal(mean=-3.0, sigma=0.5, size=200_000)
    res = Reservoir(cap=8192, seed=1)
    res.extend(stream.tolist())
    assert len(res) == 8192
    assert res.count == 200_000
    sampled_p50 = np.percentile(list(res), 50)
    sampled_p99 = np.percentile(list(res), 99)
    true_p50 = np.percentile(stream, 50)
    true_p99 = np.percentile(stream, 99)
    assert abs(sampled_p50 - true_p50) / true_p50 < 0.05
    assert abs(sampled_p99 - true_p99) / true_p99 < 0.05
    with pytest.raises(ValueError, match="cap"):
        Reservoir(cap=0)


def test_serve_metrics_bounded_under_sustained_load():
    """Drive ServeMetrics far past its cap straight through the real
    recording paths: every reservoir stays at cap, counters stay exact,
    and snapshot() still answers with sane percentiles."""
    m = ServeMetrics(reservoir_cap=64)
    for i in range(10_000):
        m.record_tick(float(i), queue_depth=i % 7, live_slots=i % 4,
                      total_slots=4, new_tokens=2, tick_seconds=0.001)
        m.record_first_token(0.05)
    assert len(m.ttft_s) == 64 and m.ttft_s.count == 10_000
    assert len(m.token_latency_s) == 64
    assert len(m.queue_depth) == 64
    assert len(m.occupancy) == 64
    snap = m.snapshot()
    assert snap["tokens_emitted"] == 30_000  # counters stay exact
    assert snap["ttft_p50_s"] == pytest.approx(0.05)
    assert snap["token_latency_p99_s"] == pytest.approx(0.001)
    assert 0.0 <= snap["mean_slot_occupancy"] <= 1.0


def test_tracer_hook_surface_matches_null():
    """RequestTracer must override only methods NullTracer declares —
    the engine calls exactly the NullTracer surface, so a hook added on
    the real tracer alone would never fire."""
    null_hooks = {n for n in vars(NullTracer)
                  if n.startswith("on_")}
    real_hooks = {n for n in vars(RequestTracer)
                  if n.startswith("on_")}
    assert real_hooks <= null_hooks, \
        f"RequestTracer hooks unknown to the engine: " \
        f"{real_hooks - null_hooks}"


# ----------------------------------------------------------- phase spans
# The direct children of ``pddl.serve.step`` (``first_token_wait`` nests
# in ``admit``, under the span-only ``admit_request``).
TOP_LEVEL_PHASES = tuple(p for p in PHASES if p != "first_token_wait")


class _Clock:
    """The injected engine clock: moves only when told to."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class _SlowAdmission(NullTracer):
    """Stands in for the prefill's duration on the injected clock (every
    admission takes ``dt`` between its pop and its first token), and
    keeps what ``on_tick`` was handed."""

    def __init__(self, clock, dt):
        self.clock, self.dt, self.ticks = clock, dt, []

    def on_admit(self, handle, slot, replay):
        self.clock.t += self.dt

    def on_tick(self, record):
        self.ticks.append(record)


def test_phase_counters_read_what_the_schedule_implies(gpt_setup):
    """Two requests through one slot on an injected clock: the
    scheduler's wait, the admission wall and the step/tick counts are
    exactly what the submits and steps imply; a replayed stream adds
    nothing to the pop and admission counters; the
    top-level phases sum to no more than the steps' wall."""
    model, variables = gpt_setup
    clock = _Clock()
    tracer = _SlowAdmission(clock, 2.0)
    # Step 3's tick fails past its (zero) retry budget: A's slot state
    # is lost and A replays.
    plan = FaultPlan(scheduled=[FaultSpec(step=3, site="tick",
                                          kind=FaultKind.TRANSIENT)])
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      clock=clock, tracer=tracer, fault_plan=plan,
                      max_retries=0)
    eng.warmup()
    a = eng.submit((np.arange(6) * 3 + 1) % 32, 6)   # t = 100
    clock.t = 101.0
    b = eng.submit((np.arange(5) + 7) % 32, 2)       # t = 101
    clock.t = 103.0
    eng.step()                  # pops A: waited 3 s; admission takes 2 s
    m = eng.metrics.snapshot()
    assert (m["queue_pops"], m["queue_wait_s"]) == (1, 3.0)
    assert (m["admissions"], m["admit_wall_s"]) == (1, 2.0)
    assert (m["engine_steps"], m["decode_ticks"]) == (1, 1)
    clock.t = 110.0
    steps = 1
    while not a.done:
        eng.step()
        steps += 1
    assert a.replays == 1 and a.tokens == _ref_greedy(
        model, variables, a.request.prompt, 6)
    m = eng.metrics.snapshot()
    # The replay popped and re-admitted A: neither counts (fresh
    # requests only).
    assert (m["queue_pops"], m["queue_wait_s"]) == (1, 3.0)
    assert (m["admissions"], m["admit_wall_s"]) == (1, 2.0)
    clock.t = 120.0
    while not b.done:
        eng.step()
        steps += 1
    m = eng.metrics.snapshot()
    # B was popped at 120 (submitted at 101) and took its 2 s.
    assert (m["queue_pops"], m["queue_wait_s"]) == (2, 3.0 + 19.0)
    assert (m["admissions"], m["admit_wall_s"]) == (2, 4.0)
    assert m["engine_steps"] == steps
    # Every step had a live slot; the failed tick was never dispatched.
    assert m["decode_ticks"] == steps - 1
    assert set(m["phase_wall_s"]) == set(PHASES)
    assert all(w > 0 for w in m["phase_wall_s"].values())
    top = sum(m["phase_wall_s"][p] for p in TOP_LEVEL_PHASES)
    assert 0 < top <= m["step_wall_s"]
    assert m["phase_wall_s"]["first_token_wait"] \
        <= m["phase_wall_s"]["admit"]
    # (d) the tracer was handed the ring's own records, one per step.
    assert len(tracer.ticks) == steps
    assert tracer.ticks[-1] == eng.telemetry.last()
    assert [r["step"] for r in tracer.ticks] == list(range(steps))


def test_ring_record_carries_phase_wall(gpt_setup):
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      telemetry_capacity=64)
    handles = [eng.submit((np.arange(6) + i) % 32, 3) for i in range(3)]
    eng.run(max_steps=50)
    assert all(h.done for h in handles)
    window = eng.telemetry.snapshot()
    for r in window:
        assert set(r["phase_wall_s"]) == set(PHASES)
        assert sum(r["phase_wall_s"][p] for p in TOP_LEVEL_PHASES) > 0
    # last() and snapshot() hand out copies: mutating them leaves the
    # live ring as it was.
    last = eng.telemetry.last()
    kept = dict(last["phase_wall_s"])
    last["phase_wall_s"]["admit"] = -1.0
    window[-1]["phase_wall_s"].clear()
    assert eng.telemetry.last()["phase_wall_s"] == kept
    # The ring's window and the lifetime counters agree while the ring
    # still holds every step (the window's summary leaves the split to
    # the counters: one series on /metrics, not two).
    assert "phase_wall_s" not in eng.telemetry.summary()
    window = eng.telemetry.snapshot()
    assert len(window) == eng.metrics.engine_steps
    for phase in PHASES:
        assert eng.metrics.phase_wall_s[phase] == pytest.approx(
            sum(r["phase_wall_s"][phase] for r in window))


def test_emit_ticks_writes_the_ring_record(gpt_setup, tmp_path):
    """`RequestTracer(emit_ticks=True)`: the sink's ``kind="tick"``
    lines are the ring's records — one shape for a step, not two."""
    model, variables = gpt_setup
    path = str(tmp_path / "ticks.jsonl")
    with JsonlEventLog(path) as log:
        eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                          telemetry_capacity=64,
                          tracer=RequestTracer(sink=log, emit_ticks=True))
        h = eng.submit(np.arange(5) % 32, 4)
        eng.run(max_steps=30)
        assert h.done
    ticks = [r for r in read_jsonl(path) if r["kind"] == "tick"]
    ring = eng.telemetry.snapshot()
    assert len(ticks) == len(ring) > 0
    for line, rec in zip(ticks, ring):
        assert line == json.loads(json.dumps(
            {"schema": 1, "kind": "tick", **rec}))


def _host_spans(trace_dir):
    """[(name, start_ns, end_ns, {stat: value})] of every
    ``pddl.serve.*`` span a profiler session left in its host planes."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pddl.serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def test_profiler_trace_holds_the_step_span_tree(gpt_setup, tmp_path):
    """A few steps under ``jax.profiler.start_trace`` (TraceMe spans
    only, as the benchmark's traced runs): one ``pddl.serve.step`` per
    engine step, numbered, with ``tick_wait`` and ``admit_request``
    nested inside and ``request_id`` on the latter."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    eng.warmup()
    handles = [eng.submit((np.arange(6) + i) % 32, 4) for i in range(2)]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        first = eng.metrics.engine_steps
        eng.run(max_steps=20)
        n_steps = eng.metrics.engine_steps - first
    finally:
        jax.profiler.stop_trace()
    assert all(h.done for h in handles) and n_steps >= 3
    spans = _host_spans(tmp_path)
    steps = sorted(s for s in spans if s[0] == "pddl.serve.step")
    assert [s[3]["step_num"] for s in steps] \
        == list(range(first, first + n_steps))

    def inside(child, parents):
        return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)

    by_name = {}
    for s in spans:
        by_name.setdefault(s[0].removeprefix("pddl.serve."), []).append(s)
    assert set(by_name) - {"step", "admit_request"} <= set(PHASES)
    for phase in TOP_LEVEL_PHASES:
        assert by_name[phase], phase
        assert all(inside(s, steps) for s in by_name[phase]), phase
    assert len(by_name["tick_wait"]) == n_steps
    requests = by_name["admit_request"]
    assert sorted(s[3]["request_id"] for s in requests) == sorted(
        h.request.request_id for h in handles)
    assert all(s[3]["prompt_len"] == 6 and not s[3]["replay"]
               for s in requests)
    assert all(inside(s, by_name["admit"]) for s in requests)
    assert len(by_name["first_token_wait"]) == 2
    assert all(inside(s, requests) for s in by_name["first_token_wait"])


# ----------------------------------------------- set-up stages and compiles
# Engines with each kind of resident program: the plain tick and both
# chunk widths; the speculative draft/verify pair; the tenant's adapter
# load; the host tier's promotion and demotion programs.
_ENGINE_KINDS = {
    "plain": {},
    "speculative": {"spec_k": 2},
    "tenant": {"tenant": TenantConfig()},
    "host_tier": {"host_tier": 1 << 20},
}


def _engine_stages(eng):
    """The set-up stages an engine has: ``build``, one ``warmup:<site>``
    per resident program (`compile_counts()` keys, and the host tier's
    demotion gather beside its promotion), ``warmup_wait``."""
    sites = set(eng.compile_counts())
    if "host_promote" in sites:
        sites.add("host_demote")
    return {"build", "warmup_wait"} | {f"warmup:{s}" for s in sites}


@pytest.mark.parametrize("kind", sorted(_ENGINE_KINDS))
def test_setup_stages_sum_to_the_wall_around_them(gpt_setup, kind):
    """Each stage of an engine's own set-up is timed, above 0, the stages
    it does not have at 0, every label present; together they are the
    wall timed from outside around ``ServeEngine(...)`` and ``warmup()``,
    within 5 %."""
    model, variables = gpt_setup
    t = time.perf_counter()
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      **_ENGINE_KINDS[kind])
    eng.warmup()
    outside = time.perf_counter() - t
    wall = eng.metrics.snapshot()["setup_wall_s"]
    assert tuple(wall) == SETUP_STAGES
    mine = _engine_stages(eng)
    assert {s for s, w in wall.items() if w > 0} == mine, wall
    assert sum(wall.values()) == pytest.approx(outside, rel=0.05)
    # Warmup runs once: a second call adds nothing.
    eng.warmup()
    assert eng.metrics.snapshot()["setup_wall_s"] == wall


@pytest.fixture
def persistent_cache_off():
    """The persistent compile cache off for one test, so that a program
    compiled again is a compile and not a load."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def test_a_compile_inside_a_step_lands_in_its_phase(gpt_setup,
                                                    persistent_cache_off):
    """``jax.clear_caches()`` between two steps makes the tick compile
    again: the compile is counted under ``tick_dispatch`` and nowhere
    else, and that step's ring record carries it; a step that compiles
    nothing counts 0 there and moves no counter."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      telemetry_capacity=64)
    h = eng.submit(np.arange(6) % 32, 12)
    for _ in range(3):
        eng.step()
    assert h.tokens and not h.done
    before = eng.metrics.snapshot()
    eng.step()
    quiet = eng.metrics.snapshot()
    assert eng.telemetry.last()["compiles"] == 0
    for key in ("compiles", "compile_s", "compile_cache_hits"):
        assert quiet[key] == before[key], key
    jax.clear_caches()
    eng.step()
    after = eng.metrics.snapshot()
    grew = {p: after["compiles"][p] - quiet["compiles"][p]
            for p in COMPILE_PLACES
            if after["compiles"][p] != quiet["compiles"][p]}
    assert set(grew) == {"tick_dispatch"}, grew
    assert eng.telemetry.last()["compiles"] == grew["tick_dispatch"] >= 1
    assert after["compile_s"]["tick_dispatch"] \
        > quiet["compile_s"]["tick_dispatch"]
    assert after["compile_cache_hits"] == quiet["compile_cache_hits"]
    eng.run(max_steps=30)
    assert h.done and h.tokens == _ref_greedy(
        model, variables, h.request.prompt, 12)


def test_a_warm_cache_loads_what_a_cold_one_compiled(gpt_setup, tmp_path):
    """Two engines of one shape through a persistent compile cache that
    starts empty: the first compiles every resident program in its own
    warmup stage, and the cache takes each in (a miss); the second loads
    each from the cache (a hit) in the same stage and compiles nothing."""
    from jax.experimental.compilation_cache import compilation_cache

    model, variables = gpt_setup
    keep = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    try:
        snaps = []
        for _ in range(2):
            eng = ServeEngine(model, variables, max_slots=2,
                              prefill_len=16)
            eng.warmup()
            snaps.append(eng.metrics.snapshot())
    finally:
        for name, value in zip(("jax_enable_compilation_cache",
                                "jax_compilation_cache_dir",
                                "jax_persistent_cache_min_compile_time_secs"),
                               keep):
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    programs = {f"warmup:{s}" for s in eng.compile_counts()}
    cold, warm = snaps
    for stage in programs:
        assert cold["compiles"][stage] >= 1, stage
        assert cold["compile_cache_misses"][stage] >= 1, stage
        assert cold["compile_s"][stage] > 0, stage
        assert warm["compile_cache_hits"][stage] >= 1, stage
    assert sum(warm["compiles"].values()) == 0, warm["compiles"]
    assert sum(warm["compile_cache_misses"].values()) == 0
    assert sum(cold["compile_cache_hits"].values()) == 0


def test_compiles_with_no_engine_phase_open_are_not_counted(gpt_setup,
                                                            persistent_cache_off):
    """A compile on the engine's thread between its steps (the caller's
    own work) is not the engine's."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    eng.warmup()
    before = eng.metrics.snapshot()["compiles"]
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    eng.tick_lowering().compile()
    assert eng.metrics.snapshot()["compiles"] == before


def test_profiler_trace_holds_the_setup_spans(gpt_setup, tmp_path):
    """An engine built and warmed under ``jax.profiler.start_trace``: one
    ``pddl.serve.setup.<stage>`` span per stage the engine has, one after
    another, each as long as its ``setup_wall_s``."""
    model, variables = gpt_setup
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
        eng.warmup()
    finally:
        jax.profiler.stop_trace()
    spans = sorted((s for s in _host_spans(tmp_path)
                    if s[0].startswith("pddl.serve.setup.")),
                   key=lambda s: s[1])
    names = [s[0].removeprefix("pddl.serve.setup.") for s in spans]
    assert sorted(names) == sorted(_engine_stages(eng))
    assert names[0] == "build" and names[-1] == "warmup_wait"
    assert all(a[2] <= b[1] for a, b in zip(spans, spans[1:]))
    wall = eng.metrics.setup_wall_s
    for name, (_, start, end, _) in zip(names, spans):
        assert (end - start) / 1e9 == pytest.approx(wall[name], rel=0.05,
                                                    abs=2e-4), name


# The benchmark's readers of the set-up counters (`chipbench/metrics/`),
# on a synthetic run: set-up 30 s, of it 12.5 s in the engine's stages and
# 2.5 s in the steps before the open.
_SETUP_OBS = {
    "setup_s": 30.0,
    "facts": {"counters_open": {
        "step_wall_s": 2.5,
        "setup_wall_s": {
            "build": 1.5, "warmup:chunk_prefill": 4.0,
            "warmup:sample_first": 1.0, "warmup:tick": 5.5,
            "warmup_wait": 0.5, "warmup:verify": 0.0},
        "compile_cache_misses": {"warmup:tick": 1, "warmup:chunk_prefill": 1,
                                 "first_token_wait": 0, "admit": 1},
    }},
}


@pytest.mark.parametrize("name,expected", [
    ("setup_engine_s", 15.0), ("setup_warmup_s", 11.0),
    ("setup_other_s", 15.0), ("setup_compiles", 3)])
def test_setup_readers_on_a_synthetic_obs(name, expected):
    from chipbench.run import metric_reader

    read = metric_reader(name)
    assert read(_SETUP_OBS) == pytest.approx(expected)
    # A program without the counters (the parent of this change) has
    # nothing to read, set-up and steps or not.
    older = {"setup_s": 30.0, "facts": {"counters_open": {
        "step_wall_s": 2.5, "phase_wall_s": {"admit": 1.0}}}}
    assert read(older) is None
    assert read({"setup_s": 30.0, "facts": {}}) is None


def test_setup_engine_and_other_sum_to_setup_s():
    from chipbench.run import metric_reader

    engine, other = (metric_reader(n)(_SETUP_OBS)
                     for n in ("setup_engine_s", "setup_other_s"))
    assert engine + other == _SETUP_OBS["setup_s"]


def test_cancelled_mid_admission_is_a_pop_and_no_admission(gpt_setup):
    """A sliced prefill cancelled between its scheduler pop and its
    slot: the pop and its wait count, and neither side of
    ``admit_wall_s / admissions`` moves — both are taken at install."""
    model, variables = gpt_setup
    clock = _Clock()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=32,
                      prefix_chunk=8, prefill_slice_tokens=8,
                      clock=clock)
    eng.warmup()
    h = eng.submit((np.arange(31) * 3) % 32, 3)      # t = 100
    clock.t = 101.5
    eng.step()               # popped; the first slice of four prefilled
    assert not h.tokens and not h.done
    h.cancel()
    clock.t = 105.0
    eng.step()
    assert h.finish_reason is FinishReason.CANCELLED
    m = eng.metrics.snapshot()
    assert (m["queue_pops"], m["queue_wait_s"]) == (1, 1.5)
    assert (m["admissions"], m["admit_wall_s"]) == (0, 0.0)
    # The next request through is one admission, its wall its own.
    clock.t = 106.0
    b = eng.submit((np.arange(5) + 7) % 32, 2)
    eng.run(max_steps=20)
    assert b.done
    m = eng.metrics.snapshot()
    assert (m["queue_pops"], m["admissions"], m["admit_wall_s"]) \
        == (2, 1, 0.0)


# ------------------------------------------- the block index under pressure
@pytest.fixture(scope="module")
def pressed_engine(gpt_setup):
    """An engine whose pool sits at its floor (16 blocks + scratch) after
    24 distinct full blocks went through it: the free list is empty and
    every further block is a reclaim. Left mid-stream: one request
    admitted, its answer still to decode across block boundaries."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      prefix_cache_blocks=17)
    for i in range(12):
        eng.submit((np.arange(16) * 7 + 11 * i + i // 3) % 32, 4)
        eng.run(max_steps=100)
    h = eng.submit((np.arange(16) * 5 + 3) % 32, 40)
    while not h.tokens:
        eng.step()
    return eng, h


@pytest.mark.parametrize("key", ["prefix_reclaims",
                                 "prefix_reclaim_visited"])
def test_reclaim_counters_in_snapshot_and_exposition(pressed_engine, key):
    eng, _ = pressed_engine
    snap = eng.metrics.snapshot()
    own = {"prefix_reclaims": eng._prefix.reclaims,
           "prefix_reclaim_visited": eng._prefix.reclaim_visited}
    assert snap[key] == own[key] > 0
    assert key in SERVE_COUNTER_KEYS
    samples, types = parse_prometheus_text(
        serve_exposition(eng.metrics, eng))
    assert types[f"pddl_serve_{key}_total"] == "counter"
    assert samples[(f"pddl_serve_{key}_total", ())] == float(snap[key])


def test_reclaims_cost_what_they_free_and_decode_feeds_them(pressed_engine):
    """Visited per eviction is the witness (the walk this replaced read
    the index's size there), and the counters move on a step that
    admits nothing: a live stream crossing a block boundary."""
    eng, h = pressed_engine
    before = eng.metrics.snapshot()
    eng.run(max_steps=100)
    assert h.done and len(h.tokens) == 40
    snap = eng.metrics.snapshot()
    assert snap["prefix_lookups"] == before["prefix_lookups"]
    assert snap["prefix_reclaims"] > before["prefix_reclaims"]
    assert snap["prefix_evictions"] > before["prefix_evictions"]
    assert snap["prefix_evictions"] == eng._prefix.evictions
    assert (snap["prefix_evictions"] <= snap["prefix_reclaim_visited"]
            <= 3 * snap["prefix_evictions"])


def test_reclaim_counters_start_at_zero_and_take_the_index_totals():
    m = ServeMetrics()
    snap = m.snapshot()
    assert snap["prefix_reclaims"] == snap["prefix_reclaim_visited"] == 0
    m.record_prefix_reclaims(evictions=5, reclaims=3, visited=7)
    snap = m.snapshot()
    assert (snap["prefix_evictions"], snap["prefix_reclaims"],
            snap["prefix_reclaim_visited"]) == (5, 3, 7)
    # A lookup restamps evictions and leaves the reclaim totals alone.
    m.record_prefix_lookup(0, blocks_live=4, evictions=6)
    snap = m.snapshot()
    assert (snap["prefix_evictions"], snap["prefix_reclaims"]) == (6, 3)
