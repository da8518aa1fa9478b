"""Deterministic fault injection for the serving engine.

The machinery (seeded schedule + rate draws, the fault taxonomy, the
injection-before-dispatch discipline) lives in
:mod:`pddl_tpu.utils.faults` and is shared with the training loop's
:mod:`pddl_tpu.train.faults`; this module pins the SERVING site
vocabulary: a :class:`FaultPlan` hooks every device-call boundary of
:class:`~pddl_tpu.serve.engine.ServeEngine` (the sites are exactly the
engine's ``compile_counts()`` keys) and fires transient errors,
allocation failures, latency spikes, or hard kill-points at chosen or
randomly drawn ``(step, site)`` coordinates — reproducible by
construction, so every recovery path is testable in tier-1 on CPU
(``tests/test_serve_faults.py``) and measurable in
``benchmarks/serve_bench.py``'s fault leg.

The engine's contract per fault kind (details in ``engine._device_call``
and docs/OPERATIONS.md § "Failure modes & recovery (serving)"):

- **TRANSIENT**: bounded-backoff retry; past ``max_retries`` the slot
  KV is declared lost and the request(s) REPLAY token-exactly.
- **OOM**: never blind-retried — DEGRADED mode (prefix-cache donations
  off, unpinned pool blocks flushed), re-arm after a cool-down.
- **LATENCY**: the call is delayed; deadlines and drain keep working.
- **KILL**: unwinds through ``step()`` like a real crash; the test then
  exercises drain/restore on the survivor state.
"""

from __future__ import annotations

from pddl_tpu.utils.faults import (  # noqa: F401 - the serve-layer surface
    FaultKind,
    FaultSpec,
    InjectedResourceExhausted,
    InjectedTransientError,
    KillPoint,
    classify,
)
from pddl_tpu.utils.faults import FaultPlan as _BaseFaultPlan


class FaultPlan(_BaseFaultPlan):
    """Seeded fault schedule over the engine's device-call sites
    (== ``ServeEngine.compile_counts()`` keys).

    The speculative sites (ISSUE 12): ``draft`` (the n-gram or
    draft-model proposal program — a lost draft call degrades to
    fallback drafts, never to a KV rebuild, unless a real error
    consumed the donated draft tree), ``verify`` (the wide-window
    program that replaces ``tick`` on a ``spec_k > 0`` engine — same
    donated-tree recovery: full live-slot replay), and
    ``draft_prefill`` (the draft model's admission chunk).

    The tiered-KV site (ISSUE 13): ``host_promote`` — the H2D scatter
    that promotes a host-tier chain into the pool on a ``host_tier``
    engine. Transients retry against the intact host copy (the tier
    pin holds across retries); exhausted retries unwind the promotion
    (ids + pins released exactly) and charge the admission a replay; a
    REAL error may have consumed the donated pool tree and recovers
    like a chunk's (pool rebuild + full live-slot replay).
    Demotion is deliberately NOT a site: it is an eager opportunistic
    read whose failure degrades to the old free-and-recompute path."""

    SITES = ("chunk_prefill", "chunk_prefill_wide", "tick",
             "sample_first", "adapter_load", "draft", "verify",
             "draft_prefill", "host_promote")
