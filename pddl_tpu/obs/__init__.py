"""Unified observability for the serving (and training) stack.

`trace.py` — Dapper-style per-request spans (queue → admission →
prefix match → prefill chunks → decode ticks → retries/replays →
finish), zero-cost when disabled via the no-op :class:`NullTracer`.
`ring.py` — the engine's fixed-capacity per-tick telemetry ring.
`export.py` — dependency-free exporters: atomic-append JSONL event
log, Prometheus text exposition over ``ServeMetrics`` + engine
gauges + the ring summary, and an optional stdlib ``/metrics`` HTTP
endpoint. See docs/OPERATIONS.md § "Observability (serving)".

Fleet-wide distributed tracing (ISSUE 19): `propagate.py` — wire
trace contexts, the worker span shipper, and the router-side
:class:`TraceCollector`; `assemble.py` — stitching, gap checking, and
TTFT critical-path attribution (CLI: ``python -m
pddl_tpu.obs.assemble``); `flightrec.py` — the SIGKILL-surviving
per-worker flight recorder (imported directly, not re-exported here:
it depends on the fleet journal's VFS shim and `obs` must stay
importable without the serving stack).
"""

from pddl_tpu.obs.export import (
    FLEET_COUNTER_KEYS,
    SERVE_COUNTER_KEYS,
    TOKEN_LATENCY_BUCKETS_S,
    TRAIN_COUNTER_KEYS,
    TTFT_BUCKETS_S,
    JsonlEventLog,
    MetricsHTTPServer,
    engine_gauges,
    fleet_exposition,
    parse_prometheus_text,
    read_jsonl,
    render_prometheus,
    reservoir_histogram,
    serve_exposition,
    train_exposition,
)
from pddl_tpu.obs.assemble import TRACE_EVENTS, TRACE_SEGMENTS, Trace, stitch
from pddl_tpu.obs.propagate import (
    ClockAligner,
    SpanShipper,
    TraceCollector,
    estimate_offset,
)
from pddl_tpu.obs.ring import TelemetryRing
from pddl_tpu.obs.trace import (
    NULL_TRACER,
    NullTracer,
    RequestTracer,
    Span,
)

__all__ = [
    "ClockAligner",
    "FLEET_COUNTER_KEYS",
    "JsonlEventLog",
    "MetricsHTTPServer",
    "NULL_TRACER",
    "NullTracer",
    "RequestTracer",
    "SERVE_COUNTER_KEYS",
    "Span",
    "SpanShipper",
    "TRACE_EVENTS",
    "TRACE_SEGMENTS",
    "Trace",
    "TraceCollector",
    "estimate_offset",
    "stitch",
    "TelemetryRing",
    "engine_gauges",
    "fleet_exposition",
    "parse_prometheus_text",
    "read_jsonl",
    "render_prometheus",
    "reservoir_histogram",
    "serve_exposition",
    "TOKEN_LATENCY_BUCKETS_S",
    "TTFT_BUCKETS_S",
    "train_exposition",
    "TRAIN_COUNTER_KEYS",
]
