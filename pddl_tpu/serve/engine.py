"""Continuous-batching online serving engine over the decode path.

`docs/SERVING.md` measured a strong SINGLE-request path (decode scan,
speculative decoding, int8); the ROADMAP's north star is heavy traffic
from many users. The gap between those is this engine: Orca-style
iteration-level scheduling (OSDI '22) — requests join and leave the
running batch at TOKEN granularity instead of waiting for the slowest
member of a fixed batch, which is worth roughly an order of magnitude
of aggregate tokens/s at realistic request mixes (vLLM, SOSP '23).

The slot model, under JAX's fixed-shape discipline:

- ONE resident compiled decode program with a fixed pool of ``S``
  batch slots over ONE block pool that IS the KV cache
  (`pddl_tpu/serve/kvcache/`, vLLM PagedAttention / SGLang
  RadixAttention composed): per attention layer a fused leaf
  ``[N, H_kv, block, 2*D]``
  (:func:`~pddl_tpu.serve.kvcache.paged_decode_cache`), read through
  a per-slot ``[S, T]`` block table with PER-SLOT position counters
  (``[S]`` int32 — the vector-index decode path in `ops/attention.py`
  / the model families), so every slot advances at its own depth
  inside one fused tick
  (:func:`~pddl_tpu.ops.attention.paged_decode_attention`; the Pallas
  kernel on TPU, the chunked jnp oracle elsewhere).
- Each ``step()``: (a) ADMIT queued requests into free slots — the
  prompt is matched against a host-side radix index over token ids
  (`kvcache/radix.py`), the matched chain's blocks are PINNED and the
  slot's table row points at them in place, private blocks are
  allocated for the uncached suffix, and fixed-width chunk programs
  (:func:`~pddl_tpu.models.gpt.prefill_row_from`) write the suffix's
  K/V straight into those pool blocks; the first token is sampled
  immediately (that's TTFT); (b) every live slot about to cross a
  block boundary gets a fresh private block appended to its table
  row, then one fused DECODE TICK runs for all live slots with
  per-slot sampling params as batched runtime arrays
  (:func:`~pddl_tpu.models.gpt.sample_logits_batched`); (c) EVICT
  finished slots (eos / length / cancel / deadline) host-side — the
  table row goes all-scratch, the slot's private blocks return to the
  free list, and the prompt's full blocks stay cached under the radix
  index, so stale K/V is unreachable by construction.
- A CLOSED set of compiled programs (the narrow chunk prefill, a
  ``prefill_len``-wide one where it pays, the tick, the first-token
  sample; speculation, tenancy and the host tier each add their own),
  each traced once at ``warmup()`` and never again: prompt lengths
  enter as a traced ``length`` over fixed chunk widths,
  tables/positions/sampling params are runtime arrays, and the pool
  tree is DONATED through every program that touches it so the
  resident buffers are reused in place. ``compile_counts()`` exposes
  the executable counts; the suite pins them at 1 after a mixed
  workload.

Dead slots tick too (fixed shapes — their table rows are all scratch,
so their writes land in block 0, a sink the radix index never
references); the cost is one batch row of compute, which is what buys
zero recompiles.

Prefix-aware KV reuse: production traffic is dominated by shared
prompt prefixes (system prompts, few-shot templates — the vLLM/SGLang
observation), so compute and the admission budget both scale with the
uncached SUFFIX, not the prompt. A hit copies nothing: the matched
blocks are referenced in place under a refcount pin, donation after
prefill is a pure ownership hand-off of blocks the chunks already
wrote, and a shared prefix's KV exists ONCE in HBM no matter how many
live slots reference it. A pinned chain is never evicted, so LRU
reclaim never reaches under a decoding request; a slot only ever
WRITES blocks it owns privately. Token-exactness is structural: both
families' caches are position-absolute (GPT adds position embeddings
before the blocks; Llama caches post-RoPE keys), so a shared-prefix
block is bit-valid for every request with those prompt tokens.

int8 serving composes exactly like ``generate()``: pass
``param_transform=pddl_tpu.ops.quant.dequantize`` and the int8 tensors
are what lives in HBM, dequantized inside the compiled programs (what
the pool stores is K/V, which int8 weight storage never touches).

Sliding-window layers live in the same full-length pool (masked and
block-skipped to their band), so window, NoPE-global and full-attention
models are all eligible; the reference for every one of them is
``generate()``.

Fault tolerance (`serve/faults.py`, `serve/drain.py`,
`docs/OPERATIONS.md` § "Failure modes & recovery"): every device
dispatch goes through one guarded boundary. Transient device errors
retry with bounded exponential backoff; when retries run out (or a
real error may have consumed a donated buffer) the affected slots'
KV is declared LOST and the requests REPLAY — the prompt re-prefills
through the normal admission path and the already-emitted tokens are
re-fed one per fused tick (known token in, sampled output discarded)
until the stream's live edge is rebuilt, which is token-exact because
the caches are position-absolute and costs no new compiled program.
RESOURCE_EXHAUSTED flips the engine DEGRADED:
prefix-cache donations stop, unpinned pool blocks flush, serving
continues on the cold path, and the cache re-arms after a cool-down.
A request whose replays exceed ``max_replays`` fails terminally
(``FinishReason.ERROR``) instead of crash-looping the engine. SIGTERM
(via ``install_drain_handler``) stops admission and snapshots every
queued + running request's host state to disk; a fresh engine
``restore()``s the snapshot and resumes each stream token-exactly
through the same replay machinery.

Speculative serving (``spec_k > 0``; ISSUE 12 / ROADMAP item 3):
the fused tick becomes a per-slot DRAFT/VERIFY window — Leviathan et
al.'s speculative decoding lifted into Orca-style iteration-level
scheduling. Each step a ``draft`` program proposes up to ``spec_k``
tokens per slot (the shared n-gram drafter from
`models/speculative.py` by default — zero extra weights — or a small
draft model whose KV rides the same block pool as a second
cache tree), and ONE batched ``verify`` dispatch runs the target
model over the ``[S, spec_k+1]`` block at per-slot positions through
the same multi-token machinery chunked prefill uses. Greedy slots
accept the longest matching draft prefix — up to ``spec_k+1`` tokens
from one tick, each the argmax of the true model given the true
prefix, so the stream is token-exact vs non-speculative greedy —
while sampled slots accept zero drafts and tick one token exactly as
before. Accepted length comes back as a runtime ``[S]`` int32 array:
mixed accept counts across the batch are DATA, never a recompile,
exactly the invariant the grammar masks and LoRA ids already hold.
Rejected draft suffixes roll back by stamping the host-side position
counters (and by the table discipline): the stale K/V sits
beyond the counter where the prefix-bounded sweep never reads it and
the next window overwrites it — a rewind is a counter stamp, never a
KV copy. Grammar-constrained slots speculate under the same FSM
tables (per-position masks over the draft path; the per-slot FSM
advances by the ACCEPTED length only), and replaying slots re-feed
known tokens ``spec_k+1`` per window, so fault recovery and
drain/restore/migration of speculative streams stay token-exact AND
speed up by the same factor.

Tiered KV cache (``host_tier=...``; ISSUE 13 / ROADMAP item 4,
`serve/kvcache/hosttier.py`): millions of users means the warm prefix
working set exceeds HBM by orders of magnitude, and the radix index's
LRU reclaim used to answer that by freeing — the fleet re-prefilled
any prefix that fell out of the pool. With a host tier armed, eviction
becomes a POLICY DECISION: reuse-worthy victims (scored by chain
length; recency rides the LRU order itself) spill their K/V D2H into a
byte-budgeted pinned-host pool under a second token-keyed index, and
an admission that misses HBM but hits the host tier PROMOTES the chain
back — one ``host_promote`` H2D scatter (riding
``ops.attention.cache_blocks_scatter`` over the donated pool, fixed
padded shapes, zero recompiles) charged against the prefill-token
budget through the scheduler's tenancy-aware ``cost_fn`` exactly like
a cold adapter load, with fault/cancel/preempt unwind releasing the
host-tier pins through the same discipline device chains use. The
demotion D2H rides an eager ``cache_blocks_gather`` of the one dying
block; degraded (post-OOM) mode bypasses the tier in BOTH directions
(spilling during an OOM response would defeat the shedding). A
disabled tier (``host_tier=None`` or byte budget 0) leaves the engine
bit-identical to the untiered one — same programs, same tokens.
"""

from __future__ import annotations

import functools
import signal
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from pddl_tpu.models.gpt import (
    is_paged_pool_path,
    lm_head_logits,
    prefill_row_features,
    prefill_row_from,
    sample_logits_batched,
    set_cache_block_tables,
    set_cache_positions,
    set_cache_state_slot,
)
from pddl_tpu.models.speculative import ngram_drafts
from pddl_tpu.obs.ring import TelemetryRing
from pddl_tpu.ops.attention import cache_blocks_gather, cache_blocks_scatter
from pddl_tpu.ops import moe
from pddl_tpu.ops.lora import adapter_pool_load, batched_lora_delta
from pddl_tpu.obs.trace import NULL_TRACER
from pddl_tpu.serve import drain as drain_io
from pddl_tpu.serve.faults import (
    InjectedResourceExhausted,
    InjectedTransientError,
    classify,
)
from pddl_tpu.serve.kvcache import (
    HostTierCache,
    HostTierConfig,
    RadixPrefixCache,
    paged_decode_cache,
    pool_nbytes,
    slot_state_nbytes,
)
from pddl_tpu.serve.metrics import PHASES, ServeMetrics
from pddl_tpu.serve.request import (
    FinishReason,
    Priority,
    QueueFull,
    Request,
    RequestHandle,
    RequestState,
    SamplingParams,
)
from pddl_tpu.serve.scheduler import SLOScheduler
from pddl_tpu.serve.tenant import (
    AdapterPool,
    AdapterPoolExhausted,
    AdapterRegistry,
    compile_constraint,
    constraint_key,
)


_WIDE_PROGRAM_BELOW_CHUNK = 1024  # see ServeEngine._wide_program_pays


class _SlotStateLost(RuntimeError):
    """Internal escalation: a device call outlasted its retry budget
    (or failed in a way that may have consumed a donated buffer), so
    whatever slot state it touched must be rebuilt, not reused.
    Never escapes the engine — admission turns it into a request
    replay/failure, the tick into a full live-slot replay.
    ``consumed`` names the resident resource (``pool`` — the one tree
    every program donates) a REAL mid-dispatch error may have eaten
    through donation; ``None`` for injected faults, which fire before
    the program runs and consume nothing."""

    def __init__(self, site: str, cause: BaseException,
                 consumed: Optional[str] = None):
        self.site = site
        self.consumed = consumed
        super().__init__(f"device call {site!r} lost after retries: {cause}")


# Which resident donated tree each site's program consumes on dispatch
# (sample_first donates nothing). A REAL error from one of these can
# leave the donated input deleted, so it is never re-dispatched — the
# escalation path rebuilds the resource instead. The pool IS the cache,
# and the tick and both chunk widths donate it — a real mid-dispatch
# error from any of them may have consumed the one tree holding every
# live stream's KV, so recovery is always the full pool rebuild + live
# -slot replay.
#
# Speculative engines (`spec_k > 0`): the ``verify`` program replaces
# ``tick`` and donates the same resident tree; the draft-MODEL program
# and its admission chunk donate the draft cache tree, which lives in
# the same block-id space as the pool — a consumed draft tree
# therefore recovers exactly like a consumed pool (full rebuild +
# live-slot replay). The n-gram ``draft`` program donates nothing and
# is deliberately absent here — a lost draft call degrades to fallback
# drafts, never to a KV rebuild — so the ``draft`` entry is stamped PER
# ENGINE (only when a draft model is drafting). Each engine keeps the
# entries of the sites it compiled.
_DONATED_BY_SITE = {
    "tick": "pool", "chunk_prefill": "pool", "chunk_prefill_wide": "pool",
    "verify": "pool", "draft_prefill": "pool",
}

# The step's span tree in the profiler's trace: ``pddl.serve.step`` (a
# StepTraceAnnotation carrying ``step_num``), one ``pddl.serve.<phase>``
# child per entry of a phase of `serve/metrics.PHASES`, and inside
# ``admit`` one ``pddl.serve.admit_request`` per admission (a span
# only: it carries the request's metadata, and its time is ``admit``'s).
_SPAN_PREFIX = "pddl.serve."


class _OpenPhase(threading.local):
    """The engine phase or set-up stage open on this thread, where a
    backend compile on it, and a routed expert layer traced on it, is
    counted (``None``: not the engine's)."""

    phase = None


_OPEN = _OpenPhase()
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"  # on a write
_listening = False


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == _COMPILE_EVENT:
        phase = _OPEN.phase
        if phase is not None:
            phase.metrics.record_backend_compile(phase.name, seconds)


def _on_event(event: str, **_) -> None:
    phase = _OPEN.phase
    if phase is None:
        return
    if event == _CACHE_HIT_EVENT:
        phase.metrics.record_compile_cache_hit(phase.name)
    elif event == _CACHE_MISS_EVENT:
        phase.metrics.record_compile_cache_miss(phase.name)
    elif event in (moe.KERNEL_LAYER_EVENT, moe.LOOP_LAYER_EVENT):
        phase.metrics.record_moe_layer(
            phase.name, kernel=event == moe.KERNEL_LAYER_EVENT)


def _listen_for_compiles() -> None:
    """Register the compile listeners with ``jax.monitoring``, once a
    process (jax keeps a listener for the process's life)."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True


class _Phase:
    """One phase boundary of ``step()``, or one stage of the engine's
    set-up: entering opens a ``jax.profiler.TraceAnnotation`` (a
    TraceMe — a flag test while no profiler session runs, a host span on
    the device trace's own clock while one does) and marks the phase
    open on this thread (a backend compile meanwhile counts under its
    name, `ServeMetrics.compiles`); leaving adds the
    ``time.perf_counter`` duration to ``wall`` (the engine's per-step
    ``phase_wall_s``, or ``ServeMetrics.setup_wall_s``) and hands the
    thread back to ``outer``, the phase it nests in. One preallocated
    object per step phase; a phase never nests inside itself."""

    __slots__ = ("name", "metrics", "_label", "_wall", "_outer", "_span",
                 "_t0")

    def __init__(self, name: str, wall: Dict[str, float],
                 metrics: ServeMetrics, *, label: Optional[str] = None,
                 outer: Optional["_Phase"] = None):
        self.name = name
        self.metrics = metrics
        self._label = label or _SPAN_PREFIX + name
        self._wall = wall
        self._outer = outer
        self._span = None
        self._t0 = 0.0

    def __enter__(self) -> None:
        # The TraceMe's span starts at construction.
        self._span = jax.profiler.TraceAnnotation(self._label)
        _OPEN.phase = self
        self._t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        self._wall[self.name] += time.perf_counter() - self._t0
        _OPEN.phase = self._outer
        self._span.__exit__(*exc)


def _setup_stage(name: str, metrics: ServeMetrics) -> _Phase:
    """One stage of the engine's set-up (`metrics.SETUP_STAGES`): span
    ``pddl.serve.setup.<name>``, wall into ``setup_wall_s``."""
    return _Phase(name, metrics.setup_wall_s, metrics,
                  label=_SPAN_PREFIX + "setup." + name)


def _build_stage(init):
    """``ServeEngine.__init__`` as the ``build`` set-up stage: the
    engine's ``metrics`` is made first, so the stage counts its wall and
    every compile of the body into it."""

    @functools.wraps(init)
    def __init__(self, *args, **kwargs) -> None:
        _listen_for_compiles()
        self.metrics = ServeMetrics()
        with _setup_stage("build", self.metrics):
            init(self, *args, **kwargs)

    return __init__


def _shared_block_count(slots, tables) -> int:
    """Pool blocks referenced by MORE THAN ONE live slot's block table
    (``ServeEngine.blocks_shared``)."""
    live = [sid for sid, h in enumerate(slots) if h is not None]
    if len(live) < 2:
        return 0
    # One vectorized pass: count ids that appear in more than one row.
    # Within a row ids are unique by construction (each table entry is a
    # distinct block or scratch), so a >1 total count means >1 slot.
    rows = tables[live]
    ids, counts = np.unique(rows[rows != 0], return_counts=True)
    return int((counts > 1).sum())


class ServeEngine:
    """Online multiplexer of generate requests onto one decode program.

    Args:
      model: a non-decode GPT/Llama (anything ``generate()``-
        compatible); the decode twin is cloned here.
      variables: ``{"params": ...}`` — kept on device, always a jit
        ARGUMENT (new same-shape checkpoints never recompile).
      max_slots: the batch-slot pool size ``S`` — the max concurrent
        requests in one fused tick.
      prefill_len: the longest admissible prompt (and the wide chunk
        program's width). Defaults to ``model.max_len // 2``.
      max_queue_depth / prefill_token_budget / aging_s: admission
        knobs, see `scheduler.py` — the scheduler pops priority-first
        (interactive > batch > best_effort), EDF within a class, with
        ``aging_s`` of queue wait promoting a request one class (the
        anti-starvation bound).
      prefill_slice_tokens: chunked-prefill FAIRNESS — when set, an
        admission prefills at most this many prompt tokens per
        ``step()`` (narrow chunks only; the wide program is skipped)
        and the fused decode tick runs between slices, so one 32k cold
        prompt is time-sliced against the running streams instead of
        stalling every next token behind its whole prefill. ``None``
        (default) keeps whole-prompt admission.
      eos_token: optional stop token (included in the stream when hit).
      param_transform: the ``generate()`` int8 hook — applied INSIDE the
        compiled programs (:mod:`pddl_tpu.ops.quant`).
      rng: sampling key, split once per tick and per admission (the
        fused tick draws for every row and greedy rows discard the
        draw — fixed work, no recompile — so the key stream advances
        even for an all-greedy workload).
      clock: injectable monotonic clock (tests drive deadlines with a
        fake one).
      prefix_cache_blocks: the block pool's size — every stream's K/V
        lives here, live and cached alike (block 0 is a reserved
        scratch sink). ``None`` (default) auto-sizes to hold every
        slot at ``max_len`` plus shared-cache headroom of about two
        full prompts per slot; an explicit size must cover
        ``max_slots * ceil(max_len/block_size) + 1`` so a live stream
        can never starve for a writable block. The config must be
        workable: ``prefix_block_size < prefill_len`` and
        ``prefill_len + prefix_chunk <= max_len`` (chunk positions
        must never clamp) — violations raise.
      prefix_block_size: tokens per KV block — the paging, reuse (and
        radix-tree) granularity. Smaller blocks match more of a prefix
        but cost more pool rows per prompt.
      prefix_chunk: suffix-prefill chunk width (one compiled program;
        admission prefills ``ceil(suffix/chunk)`` chunks, so prefill
        work scales with the UNCACHED suffix). Default
        ``max(prefix_block_size, prefill_len // 4)``.
      paged: accepts only ``True`` (the module docstring's engine is
        the only one; callers written against the two-engine
        constructor still pass it). ``False`` raises: the resident-row
        engine was removed in PR 34.
      host_tier: TIERED KV CACHE (module docstring, ISSUE 13): a
        :class:`~pddl_tpu.serve.kvcache.HostTierConfig` (or a plain
        int byte budget) arming the host-RAM spill tier under the
        radix index — LRU eviction demotes reuse-worthy chains D2H
        instead of freeing them, and admission promotes host-tier hits
        back through the ``host_promote`` program, charged against the
        prefill budget at ``promote_tokens_per_block`` per block.
        Refused (for now) alongside ``spec_draft_model`` — a promoted
        block carries target K/V only, and the draft tree's twin block
        would be junk. ``None``
        (default) or byte budget 0 disables the tier with a
        bit-identical engine (same compiled-program set, same tokens —
        the cold-path contract `tests/test_kv_tier.py` pins).
      fault_plan: optional :class:`~pddl_tpu.serve.faults.FaultPlan`
        consulted before every device dispatch (chaos tests, fault
        benches). ``None`` in production — real device errors take the
        same recovery paths, the plan only makes them injectable.
      max_retries: transient-error retries per device call before the
        touched slot state is declared lost and requests replay.
      retry_backoff_s: base of the bounded exponential backoff
        (``base * 2**attempt``) between retries.
      backoff_sleep: how the backoff waits (default ``time.sleep``;
        tests pass a no-op or a fake-clock advancer).
      max_replays: slot-state rebuilds per request before it fails
        terminally with ``FinishReason.ERROR``.
      degraded_cooldown_s: how long an OOM keeps the prefix cache
        degraded (donations off) before re-arming; a repeat OOM inside
        the window pushes the re-arm out again.
      preempt_cap: times one BEST_EFFORT stream may be parked (slot
        evicted, requeued, later resumed token-exactly via replay
        admission) to free a slot for queued ``interactive`` work;
        ``0`` disables preemption. The cap is what keeps a paused
        stream from thrashing forever under sustained pressure.
      tenant: optional :class:`~pddl_tpu.serve.tenant.TenantConfig` —
        MULTI-TENANT serving (ISSUE 9, `serve/tenant/`): per-request
        LoRA adapters from a paged device pool (per-slot int32 adapter
        ids gathered inside the fused tick — one compiled program for
        every tenant mix; admission pins the adapter row like a prefix
        chain and charges a cold load against the prefill budget) and
        grammar/JSON-schema-constrained decoding (a host-side token
        FSM per request whose per-state allow mask is stamped as a
        runtime ``[S, V]`` array ahead of the batched sampler; FSM
        state re-derives from emitted tokens, so replay/drain/
        migration stay token-exact). The v1 adaptation target is the
        LM HEAD, which keeps KV adapter-invariant — prefix KV
        sharing stays valid ACROSS tenants. ``None`` (default) compiles
        the plain programs: a non-tenant engine pays nothing.
      spec_k: SPECULATIVE SERVING (module docstring, ISSUE 12): draft
        up to ``spec_k`` tokens per engaged slot per step and verify
        them in one batched ``[S, spec_k+1]`` wide-logits dispatch —
        greedy slots emit up to ``spec_k + 1`` tokens per tick,
        token-exact vs the non-speculative greedy stream; sampled
        slots keep ticking one token. ``0`` (default) compiles the
        classic one-token tick — a non-speculative engine pays
        nothing. Accepted lengths are runtime ``[S]`` data, so mixed
        accept counts never recompile. Replays/restores re-feed known
        tokens ``spec_k + 1`` per window through the same machinery.
      spec_ngram: the n-gram drafter's lookup key length (the shared
        :func:`~pddl_tpu.models.speculative.ngram_drafts` definition —
        one drafter for the one-shot and serving paths).
      spec_draft_model / spec_draft_variables: optional DRAFT MODEL:
        a small ``generate()``-compatible model
        whose per-slot KV rides the same block pool as a second cache
        tree — same block ids, same tables, same radix sharing/dedup
        (draft K/V is position-absolute and token-pure exactly like
        the target's, so shared-prefix blocks stay bit-valid for both
        trees). Admission chunk-prefills the prompt through it
        (``draft_prefill`` site, narrow chunks); each step it drafts
        ``spec_k`` tokens autoregressively (known replay tokens are
        teacher-forced so its cache stays exact through recovery).
        ``None`` keeps the zero-weight n-gram drafter.
      tracer: optional per-request tracer
        (:class:`~pddl_tpu.obs.trace.RequestTracer`); ``None`` installs
        the no-op :data:`~pddl_tpu.obs.trace.NULL_TRACER` — tracing
        disabled costs nothing (no per-tick allocation, no device
        sync, pinned by `tests/test_obs.py`). Swap at runtime with
        :meth:`set_tracer`.
      telemetry_capacity: per-tick telemetry ring size
        (:class:`~pddl_tpu.obs.ring.TelemetryRing` on
        ``self.telemetry``): one record per ``step()`` with occupancy,
        queue depth, tokens, retries, and per-site dispatch wall time;
        the oldest record is overwritten, so memory is bounded forever.
    """

    @_build_stage
    def __init__(self, model, variables, *, max_slots: int = 8,
                 prefill_len: Optional[int] = None,
                 max_queue_depth: int = 64,
                 prefill_token_budget: Optional[int] = None,
                 aging_s: Optional[float] = 30.0,
                 prefill_slice_tokens: Optional[int] = None,
                 eos_token: Optional[int] = None,
                 param_transform=None, rng=None,
                 clock=time.monotonic,
                 prefix_cache_blocks: Optional[int] = None,
                 prefix_block_size: int = 8,
                 prefix_chunk: Optional[int] = None,
                 paged: bool = True,
                 host_tier=None,
                 fault_plan=None, max_retries: int = 3,
                 retry_backoff_s: float = 0.02,
                 backoff_sleep=time.sleep,
                 max_replays: int = 3,
                 degraded_cooldown_s: float = 5.0,
                 preempt_cap: int = 2,
                 tenant=None,
                 spec_k: int = 0, spec_ngram: int = 3,
                 spec_draft_model=None, spec_draft_variables=None,
                 tracer=None, telemetry_capacity: int = 512):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if paged is not True:
            raise ValueError(
                f"paged={paged!r}: the resident-row engine was removed "
                "in PR 34; ServeEngine is the paged engine (the block "
                "pool is the KV cache) and accepts only paged=True")
        self.model = model
        self.max_slots = int(max_slots)
        self.prefill_len = int(prefill_len if prefill_len is not None
                               else model.max_len // 2)
        if not 1 <= self.prefill_len <= model.max_len:
            raise ValueError(
                f"prefill_len {self.prefill_len} outside [1, "
                f"{model.max_len}]")
        self.eos_token = eos_token
        self._clock = clock
        self._params = variables["params"]
        self._dec = model.clone(decode=True)
        self._rng = rng if rng is not None else jax.random.key(0)
        self.scheduler = SLOScheduler(
            max_queue_depth=max_queue_depth,
            prefill_token_budget=prefill_token_budget,
            aging_s=aging_s)
        # ``self.metrics`` (ServeMetrics) is made by `_build_stage`,
        # before this body, which it times as the ``build`` stage.

        # Observability (`pddl_tpu/obs/`): the tracer defaults to the
        # shared no-op object, so a disabled engine pays one method
        # call per hook and allocates nothing; the telemetry ring is
        # always on (a dict of scalars per tick, bounded capacity).
        self._tracer = NULL_TRACER
        self.telemetry = TelemetryRing(telemetry_capacity)
        self._site_wall: Dict[str, float] = {}
        self._phase_wall: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        self._phase: Dict[str, _Phase] = {}
        for name in PHASES:
            # ``first_token_wait`` is the one phase that nests (in
            # ``admit``): leaving it hands the thread back to ``admit``.
            self._phase[name] = _Phase(
                name, self._phase_wall, self.metrics,
                outer=(self._phase["admit"] if name == "first_token_wait"
                       else None))
        self._last_wall_s = 0.0
        self._cur_step = 0

        # Resilience state (`serve/faults.py` taxonomy; docs/OPERATIONS
        # § "Failure modes & recovery").
        if max_retries < 0:
            raise ValueError(f"max_retries must be >= 0, got {max_retries}")
        if max_replays < 0:
            raise ValueError(f"max_replays must be >= 0, got {max_replays}")
        self._faults = fault_plan
        self._max_retries = int(max_retries)
        self._retry_backoff_s = float(retry_backoff_s)
        self._backoff_sleep = backoff_sleep
        self._max_replays = int(max_replays)
        self._degraded_cooldown_s = float(degraded_cooldown_s)
        self._degraded = False
        self._degraded_entered_s = 0.0
        self._degraded_until_s = 0.0
        self._step_idx = 0
        # Handles popped from the queue but not yet slotted: a kill
        # mid-admission must not lose them from the drain snapshot.
        self._admitting: Deque[RequestHandle] = deque()
        self._drain_flag = False
        self._drained = False
        self._drain_path: Optional[str] = None
        self._snapshot: Optional[Dict[str, object]] = None
        self._prev_handlers: Dict[int, object] = {}

        # Block-pool configuration (static — the compiled programs'
        # shapes derive from these).
        bs = int(prefix_block_size)
        if bs < 1:
            raise ValueError(
                f"prefix_block_size must be >= 1, got {bs}")
        # A prefix hit must leave >= 1 suffix token to produce the
        # sampled-from logits, so the longest matchable chain is
        # (prefill_len - 1) tokens, floor-blocked.
        self._match_cap = (self.prefill_len - 1) // bs
        self._donate_cap = self.prefill_len // bs
        chunk = (int(prefix_chunk) if prefix_chunk is not None
                 else max(bs, self.prefill_len // 4))
        # T table entries cover every position a stream can reach; the
        # pool must hold at least one writable block per live
        # position-block plus the scratch sink, or a decode tick could
        # starve mid-stream.
        self._table_width = -(-model.max_len // bs)
        pool_floor = self.max_slots * self._table_width + 1
        if prefix_cache_blocks is None:
            # Live worst case + shared-cache headroom (two prompts per
            # slot).
            pool_blocks = (pool_floor
                           + 2 * self.max_slots * max(self._donate_cap, 1))
        else:
            pool_blocks = int(prefix_cache_blocks)
        if pool_blocks <= 0:
            raise ValueError(
                "the block pool IS the KV cache; prefix_cache_blocks="
                f"{pool_blocks} would leave the engine none")
        if pool_blocks < pool_floor:
            raise ValueError(
                f"the engine needs prefix_cache_blocks >= "
                f"{pool_floor} (max_slots * ceil(max_len/"
                f"block_size) + scratch) so live streams can never "
                f"starve for a writable block; got {pool_blocks}")
        if self._match_cap < 1:
            raise ValueError(
                f"prefix_block_size {bs} leaves no cacheable block "
                f"under prefill_len {self.prefill_len} (need "
                f"block_size < prefill_len)")
        if not 1 <= chunk or self.prefill_len + chunk > model.max_len:
            raise ValueError(
                f"prefix_chunk {chunk} needs 1 <= chunk and "
                f"prefill_len + chunk <= max_len "
                f"({self.prefill_len} + {chunk} > {model.max_len}): "
                "a chunk starting at the deepest cached offset would "
                "clamp its positions")
        self.prefix_block_size = bs
        self._chunk = chunk
        # A model with latent attention layers re-expands, in every
        # chunk program, the cached entries the chunk attends over
        # (`metrics.latent_expanded_tokens`).
        self._reexpands = bool(getattr(model, "latent_layers", 0))
        # Slot state (module docstring): a model with layers that keep a
        # fixed state a SLOT and no per-token entry (`llama.ShortConv`).
        # Derived from the model's own declaration; it turns prefix
        # reuse off and refuses what cannot carry such state.
        self._stateful = bool(getattr(model, "slot_state_layers", 0))

        # Chunked-prefill fairness: at most `prefill_slice_tokens` of
        # prompt prefill per step(), the decode tick interleaved
        # between slices. One slice in flight at a time; `_slice` holds
        # its resumable state across steps.
        if prefill_slice_tokens is not None and prefill_slice_tokens < 1:
            raise ValueError(
                f"prefill_slice_tokens must be >= 1, got "
                f"{prefill_slice_tokens}")
        self._slice_tokens = (int(prefill_slice_tokens)
                              if prefill_slice_tokens is not None else None)
        self._slice: Optional[Dict[str, object]] = None
        self._slice_budget_left = 0
        if preempt_cap < 0:
            raise ValueError(f"preempt_cap must be >= 0, got {preempt_cap}")
        self._preempt_cap = int(preempt_cap)

        # Speculative serving (module docstring): static draft config —
        # the verify width spec_k+1 is a compiled shape, everything
        # per-slot (drafts, accepted lengths, caps, forced re-feeds)
        # is runtime data.
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self._spec_k = int(spec_k)
        self._spec_on = self._spec_k > 0
        if self._spec_on and self._stateful:
            raise NotImplementedError(
                "speculative serving needs a cache a counter stamp can "
                "rewind; this model's per-slot state (short-convolution "
                "layers) has moved on past a rejected draft and cannot "
                "be")
        self._spec_ngram = int(spec_ngram)
        self._draft_on = spec_draft_model is not None
        if self._spec_on:
            if self._spec_ngram < 1:
                raise ValueError(
                    f"spec_ngram must be >= 1, got {spec_ngram}")
            # The host-side token history every drafter reads: prompt +
            # emitted tokens per slot, the serving twin of the one-shot
            # path's token buffer (positions past the live edge hold
            # junk, which verification rejects by construction).
            self._hist = np.zeros((self.max_slots, model.max_len),
                                  np.int32)
        if self._draft_on:
            if not self._spec_on:
                raise ValueError(
                    "spec_draft_model needs spec_k >= 1 (the draft "
                    "model only exists to fill the verify window)")
            if spec_draft_variables is None:
                raise ValueError(
                    "spec_draft_model needs spec_draft_variables "
                    "({'params': ...})")
            if spec_draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft model vocab {spec_draft_model.vocab_size} "
                    f"!= target vocab {model.vocab_size}")
            if spec_draft_model.max_len < model.max_len:
                raise ValueError(
                    f"draft model max_len {spec_draft_model.max_len} < "
                    f"target max_len {model.max_len}: the draft cache "
                    "must cover every position a stream can reach")
            if getattr(spec_draft_model, "unrewindable_cache", False):
                raise NotImplementedError(
                    "draft models with sliding-window layers are not "
                    "supported (the draft tree gets no window masking), "
                    "nor ones with per-slot state: a counter stamp "
                    "rewinds neither cache")
            self._ddec = spec_draft_model.clone(decode=True)
            self._dparams = spec_draft_variables["params"]
        elif spec_draft_variables is not None:
            raise ValueError(
                "spec_draft_variables without spec_draft_model")

        # Multi-tenant state (`serve/tenant/`): the host-side adapter
        # pool bookkeeping, the device factor pools, per-slot adapter
        # rows, per-slot grammar masks, and the FSM cache. All absent
        # (None) on a plain engine — tenancy is opt-in per engine, so
        # existing deployments compile the exact same programs.
        self._tenant = tenant
        self._tenant_on = tenant is not None
        if self._tenant_on:
            registry = tenant.registry
            if registry is None:
                registry = AdapterRegistry(model.embed_dim,
                                           model.vocab_size)
                tenant.registry = registry
            if (registry.embed_dim != model.embed_dim
                    or registry.vocab_size != model.vocab_size):
                raise ValueError(
                    f"adapter registry shape ({registry.embed_dim}, "
                    f"{registry.vocab_size}) does not match the model "
                    f"({model.embed_dim}, {model.vocab_size})")
            if tenant.token_strings is not None \
                    and len(tenant.token_strings) != model.vocab_size:
                raise ValueError(
                    f"token_strings has {len(tenant.token_strings)} "
                    f"entries; the grammar vocabulary must cover every "
                    f"token id (vocab_size {model.vocab_size})")
            pool_rows = (int(tenant.adapter_pool_slots)
                         if tenant.adapter_pool_slots is not None
                         else self.max_slots + 4)
            if pool_rows < self.max_slots + 1:
                raise ValueError(
                    f"adapter_pool_slots {pool_rows} is below the live-"
                    f"mix floor max_slots + 1 = {self.max_slots + 1} "
                    "(every slot on a distinct adapter plus the "
                    "identity row 0); see docs/OPERATIONS.md 'Adapter "
                    "pool sizing'")
            self._registry = registry
            self._apool = AdapterPool(pool_rows)
            self._apool_a = jnp.zeros(
                (pool_rows, model.embed_dim, registry.rank), jnp.float32)
            self._apool_b = jnp.zeros(
                (pool_rows, registry.rank, model.vocab_size), jnp.float32)
            self._arow = np.zeros(self.max_slots, np.int32)
            self._masks = np.ones((self.max_slots, model.vocab_size),
                                  np.bool_)
            # The tick's mask arg stays DEVICE-resident and restages
            # only when a host-side row changed (`_masks_dirty`): an
            # adapters-only tenant mix (or idle constraints) then pays
            # zero per-tick mask transfer — at a real vocab the [S, V]
            # bool array is hundreds of KB per step otherwise.
            self._masks_dev = None
            self._masks_dirty = True
            # Speculative engines additionally carry PER-POSITION masks
            # [S, spec_k+1, V] for the verify block (the FSM states
            # along each slot's draft path, stamped by the host walk
            # each tick); same device-staging discipline as `_masks`.
            self._masks_w = (np.ones(
                (self.max_slots, self._spec_k + 1, model.vocab_size),
                np.bool_) if self._spec_on else None)
            self._masks_w_dev = None
            self._masks_w_dirty = True
            self._fsms: List[Optional[tuple]] = [None] * self.max_slots
            self._fsm_cache: Dict[str, object] = {}
        else:
            self._registry = None
            self._apool = None

        # One handle per occupied slot; all other per-slot state lives
        # in the arrays below (positions) or is derivable from the
        # handle (tokens emitted = len(handle.tokens)) — no duplicated
        # bookkeeping to keep in lockstep.
        self._slots: List[Optional[RequestHandle]] = [None] * self.max_slots
        # The radix node each occupied slot pinned at admission
        # (refcount released at evict).
        self._slot_nodes: List[Optional[object]] = [None] * self.max_slots
        # Engine-owned per-slot state, stamped into the programs each
        # tick (positions are authoritative HERE, not in the cache —
        # the tick program overwrites the cache's counters on entry).
        self._positions = np.zeros(self.max_slots, np.int32)
        self._tokens = np.zeros(self.max_slots, np.int32)
        self._temps = np.zeros(self.max_slots, np.float32)
        self._top_ks = np.zeros(self.max_slots, np.int32)
        self._top_ps = np.full(self.max_slots, 2.0, np.float32)

        dec, pt = self._dec, param_transform

        def _sample_first(logits, temp, top_k, top_p, rng):
            rng, sub = jax.random.split(rng)
            tok = sample_logits_batched(sub, logits, temperature=temp,
                                        top_k=top_k, top_p=top_p)
            return tok, rng

        # Every program stamps the engine-owned positions/tables on
        # entry and restores CANONICAL placeholders (scalar counter,
        # [1,1] table) on exit, so the donated resident tree keeps one
        # structure across the fused tick and the batch-1 chunk widths
        # — shape-stable donation is what keeps the set at zero
        # recompiles.
        def _canon_paged(cache):
            cache = set_cache_positions(cache, jnp.zeros((), jnp.int32))
            cache = set_cache_state_slot(cache, jnp.zeros((), jnp.int32))
            return set_cache_block_tables(cache,
                                          jnp.zeros((1, 1), jnp.int32))

        # A batch-1 chunk does not know which slot it fills except by
        # the stamped ``slot``: a model with per-slot state reads and
        # leaves its state row there; any other model has no such leaf
        # and the argument is pruned from the compiled program.
        def _stamp_chunk(cache, table, slot):
            cache = set_cache_block_tables(cache, table)
            return set_cache_state_slot(cache, slot)

        def _tick_paged(params, cache, positions, tables, tokens, temps,
                        top_ks, top_ps, rng):
            rng, sub = jax.random.split(rng)
            cache = set_cache_positions(cache, positions)
            cache = set_cache_block_tables(cache, tables)
            logits, mutated = dec.apply(
                {"params": (pt(params) if pt is not None else params),
                 "cache": cache},
                tokens[:, None], train=False, mutable=["cache"])
            nxt = sample_logits_batched(
                sub, logits[:, -1], temperature=temps, top_k=top_ks,
                top_p=top_ps)
            return _canon_paged(mutated["cache"]), nxt, rng

        def _chunk_paged(params, cache, tokens, length, start, table,
                         slot):
            cache = _stamp_chunk(cache, table, slot)
            cache, logits = prefill_row_from(dec, params, tokens, length,
                                             cache, start,
                                             param_transform=pt)
            return _canon_paged(cache), logits

        def _chunk_paged_wide(params, cache, tokens, length, start, table,
                              slot):
            # The same computation at the wide width — a DISTINCT
            # function object, so its jit cache (and compile_counts
            # entry) never shares entries with the narrow program's.
            cache = _stamp_chunk(cache, table, slot)
            cache, logits = prefill_row_from(dec, params, tokens, length,
                                             cache, start,
                                             param_transform=pt)
            return _canon_paged(cache), logits

        # --- tenant program bodies (the `tenant` arg docs) ---
        # Same SITES, swapped bodies: the model runs ``features_only``,
        # the LM head applies outside the module (`gpt.lm_head_logits`
        # — op-for-op identical, so a no-adapter slot is bit-exact vs
        # the base model), per-slot LoRA deltas gather from the device
        # factor pools by runtime int32 row ids, and grammar masks land
        # as a runtime [B, V] bool array right before the batched
        # sampler (all-True rows pass logits through bitwise). Nothing
        # here varies compiled-program shape — the zero-recompile pin
        # holds over every tenant mix.
        if self._tenant_on:
            def _sample_first_t(logits, mask, temp, top_k, top_p, rng):
                rng, sub = jax.random.split(rng)
                tok = sample_logits_batched(
                    sub, jnp.where(mask, logits, -jnp.inf),
                    temperature=temp, top_k=top_k, top_p=top_p)
                return tok, rng

            def _adapter_load(pool_a, pool_b, row, a, b):
                # A per-engine closure (not the bare module-level
                # function): jax.jit keyed on the same function object
                # would SHARE its tracing cache across engines, making
                # compile_counts() report other instances' pool shapes.
                return adapter_pool_load(pool_a, pool_b, row, a, b)

            def _tick_body(params, cache, tokens, temps, top_ks, top_ps,
                           masks, pool_a, pool_b, arows, sub):
                p2 = pt(params) if pt is not None else params
                feats, mutated = dec.apply(
                    {"params": p2, "cache": cache},
                    tokens[:, None], train=False, mutable=["cache"],
                    features_only=True)
                logits = lm_head_logits(dec, p2, feats)[:, -1]
                logits = logits + batched_lora_delta(
                    feats[:, -1], pool_a, pool_b, arows)
                nxt = sample_logits_batched(
                    sub, jnp.where(masks, logits, -jnp.inf),
                    temperature=temps, top_k=top_ks, top_p=top_ps)
                return mutated["cache"], nxt

            def _tick_paged_t(params, cache, positions, tables, tokens,
                              temps, top_ks, top_ps, masks, pool_a,
                              pool_b, arows, rng):
                rng, sub = jax.random.split(rng)
                cache = set_cache_positions(cache, positions)
                cache = set_cache_block_tables(cache, tables)
                cache, nxt = _tick_body(params, cache, tokens, temps,
                                        top_ks, top_ps, masks, pool_a,
                                        pool_b, arows, sub)
                return _canon_paged(cache), nxt, rng

            def _lora1(last, last_feats, pool_a, pool_b, aid):
                return last + batched_lora_delta(
                    last_feats, pool_a, pool_b,
                    jnp.full((1,), aid, jnp.int32))

            def _chunk_paged_t(params, cache, tokens, length, start,
                               table, slot, aid, pool_a, pool_b):
                cache = _stamp_chunk(cache, table, slot)
                cache, last, lf = prefill_row_features(
                    dec, params, tokens, length, cache, start,
                    param_transform=pt)
                return _canon_paged(cache), _lora1(last, lf, pool_a,
                                                   pool_b, aid)

            def _chunk_paged_wide_t(params, cache, tokens, length, start,
                                    table, slot, aid, pool_a, pool_b):
                # Distinct function object (wide-program discipline).
                cache = _stamp_chunk(cache, table, slot)
                cache, last, lf = prefill_row_features(
                    dec, params, tokens, length, cache, start,
                    param_transform=pt)
                return _canon_paged(cache), _lora1(last, lf, pool_a,
                                                   pool_b, aid)

        # --- speculative program bodies (the `spec_k` arg docs) ---
        # The VERIFY program replaces the fused tick: one apply over the
        # [S, spec_k+1] block at per-slot positions (the multi-token
        # vector-index write the model families grew for this), greedy
        # acceptance as cumprod-of-matches against the block's own draft
        # suffix, and the position-0 token through the SAME batched
        # sampler the plain tick used — a sampled row (cap 0) is the
        # old tick bit-for-bit in behavior. `caps` bounds acceptance per
        # row (spec_k for plain greedy, the grammar walk's legal-prefix
        # length for constrained rows, 0 for sampled rows); `forced >=
        # 0` pins the accepted length outright (replay re-feeds: tokens
        # known, model output discarded). Every one of them is [S]
        # runtime data — mixed accept counts never vary program shape.
        if self._spec_on:
            def _verify_core(logits, block, temps, top_ks, top_ps, caps,
                             forced, sub):
                y = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                match = (block[:, 1:] == y[:, :-1]).astype(jnp.int32)
                acc_model = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                acc = jnp.where(forced >= 0, forced,
                                jnp.minimum(acc_model, caps))
                first = sample_logits_batched(
                    sub, logits[:, 0], temperature=temps, top_k=top_ks,
                    top_p=top_ps)
                return y.at[:, 0].set(first), acc

            def _verify_paged(params, cache, positions, tables, block,
                              temps, top_ks, top_ps, caps, forced, rng):
                rng, sub = jax.random.split(rng)
                cache = set_cache_positions(cache, positions)
                cache = set_cache_block_tables(cache, tables)
                logits, mutated = dec.apply(
                    {"params": (pt(params) if pt is not None else params),
                     "cache": cache},
                    block, train=False, mutable=["cache"])
                w, acc = _verify_core(logits, block, temps, top_ks,
                                      top_ps, caps, forced, sub)
                return _canon_paged(mutated["cache"]), w, acc, rng

            if self._tenant_on:
                # Tenant verify: per-slot LoRA deltas over EVERY block
                # position (verification must judge drafts under the
                # ADAPTED model) and per-POSITION grammar masks
                # [S, W, V] — the draft path's FSM states, stamped by
                # the host walk each tick.
                def _verify_body_t(params, cache, block, temps, top_ks,
                                   top_ps, masks, pool_a, pool_b, arows,
                                   caps, forced, sub):
                    p2 = pt(params) if pt is not None else params
                    feats, mutated = dec.apply(
                        {"params": p2, "cache": cache},
                        block, train=False, mutable=["cache"],
                        features_only=True)
                    logits = lm_head_logits(dec, p2, feats)  # [S, W, V]
                    s_, w_, v_ = logits.shape
                    delta = batched_lora_delta(
                        feats.reshape(s_ * w_, -1), pool_a, pool_b,
                        jnp.repeat(arows, w_)).reshape(s_, w_, v_)
                    logits = jnp.where(masks, logits + delta, -jnp.inf)
                    w, acc = _verify_core(logits, block, temps, top_ks,
                                          top_ps, caps, forced, sub)
                    return mutated["cache"], w, acc

                def _verify_paged_t(params, cache, positions, tables,
                                    block, temps, top_ks, top_ps, masks,
                                    pool_a, pool_b, arows, caps, forced,
                                    rng):
                    rng, sub = jax.random.split(rng)
                    cache = set_cache_positions(cache, positions)
                    cache = set_cache_block_tables(cache, tables)
                    cache, w, acc = _verify_body_t(
                        params, cache, block, temps, top_ks, top_ps,
                        masks, pool_a, pool_b, arows, caps, forced, sub)
                    return _canon_paged(cache), w, acc, rng

            spec_kk, spec_ng = self._spec_k, self._spec_ngram

            def _draft_ngram(toks, positions):
                # THE shared drafter definition (`models/speculative.py`
                # — the one-shot loop compiles the same function with a
                # scalar position; equivalence is pinned by test).
                return ngram_drafts(toks, positions, spec_ng, spec_kk)

            if self._draft_on:
                ddec = self._ddec

                def _draft_model_fn(dparams, dcache, positions, tables,
                                    cur, forced, n_forced):
                    dcache = set_cache_positions(dcache, positions)
                    dcache = set_cache_block_tables(dcache, tables)
                    tok = cur
                    outs = []
                    for j in range(spec_kk):
                        logits, mutated = ddec.apply(
                            {"params": dparams, "cache": dcache},
                            tok[:, None], train=False, mutable=["cache"])
                        dcache = mutated["cache"]
                        nxt = jnp.argmax(logits[:, -1],
                                         axis=-1).astype(jnp.int32)
                        # Teacher-force known replay tokens: the draft
                        # cache must hold the TRUE stream's K/V (not the
                        # draft model's own guesses) through recovery.
                        nxt = jnp.where(j < n_forced, forced[:, j], nxt)
                        outs.append(nxt)
                        tok = nxt
                    # One extra apply writes the FINAL draft's K/V (its
                    # logits are discarded): a fully-accepted window
                    # would otherwise leave a one-position hole in the
                    # draft cache and degrade every later draft.
                    _, mutated = ddec.apply(
                        {"params": dparams, "cache": dcache},
                        tok[:, None], train=False, mutable=["cache"])
                    return (_canon_paged(mutated["cache"]),
                            jnp.stack(outs, axis=1))

                def _draft_chunk(dparams, dcache, tokens, length, start,
                                 table):
                    dcache = set_cache_block_tables(dcache, table)
                    dcache, _ = prefill_row_from(ddec, dparams, tokens,
                                                 length, dcache, start)
                    return _canon_paged(dcache)

        # The resident programs: tick + chunk widths + sample_first —
        # the prefill writes K/V in place and sharing is pure host
        # bookkeeping. Donation discipline: the pool tree is donated
        # through every program that touches it — the engine always
        # adopts the returned trees, so the resident HBM buffers are
        # reused in place and a stale reference can never be used by
        # mistake.
        self._donated_by_site = dict(_DONATED_BY_SITE)
        if self._draft_on:
            # The draft-MODEL program donates the draft tree (the
            # n-gram program donates nothing, so this entry exists
            # only with a draft model): a REAL mid-dispatch error
            # must never re-dispatch the consumed dcache — it
            # escalates straight to the pool-class rebuild, which
            # reconstructs both trees.
            self._donated_by_site["draft"] = "pool"
        ten = self._tenant_on
        self._sample_first_p = jax.jit(_sample_first_t if ten
                                       else _sample_first)
        # The adapter-load program copies (never donates — see
        # ops/lora.adapter_pool_load), so a faulted load retries
        # against the intact pool like any transient site.
        self._adapter_load_p = jax.jit(_adapter_load) if ten else None
        self._tick_p = jax.jit(_tick_paged_t if ten else _tick_paged,
                               donate_argnums=(1,))
        self._chunk_p = jax.jit(_chunk_paged_t if ten else _chunk_paged,
                                donate_argnums=(1,))
        # A second, WIDE chunk program (full prefill_len) for cold /
        # barely-cached prompts: one fixed per-apply cost instead of
        # ceil(plen/chunk) of them. Two separate jits (not two shapes
        # through one jit) keep the one-executable-per-program pin
        # meaningful. The wide program can start as deep as
        # prefill_len/4 (the width policy's threshold), so it also
        # needs its positions to stay in range at that offset.
        self._has_wide = self._wide_program_pays(model.max_len)
        self._chunk_wide_p = (jax.jit(_chunk_paged_wide_t if ten
                                      else _chunk_paged_wide,
                                      donate_argnums=(1,))
                              if self._has_wide else None)
        self._prefix = RadixPrefixCache(bs, pool_blocks)
        self._cache = paged_decode_cache(dec, pool_blocks, bs,
                                         self.max_slots)
        self.metrics.state_bytes_resident = slot_state_nbytes(self._cache)
        # Host-authoritative per-slot block tables (scratch-filled
        # for parked slots) and the private (not-yet-shared) block
        # ids each slot owns.
        self._tables = np.zeros(
            (self.max_slots, self._table_width), np.int32)
        # The sharing gauge is counted when read, off the step's path.
        self.metrics.bind_blocks_shared(functools.partial(
            _shared_block_count, self._slots, self._tables))
        self._private: List[List[int]] = [
            [] for _ in range(self.max_slots)]
        # KV bytes one token occupies across every leaf — what one
        # avoided gather copy is worth (`copy_bytes_avoided`).
        self._kv_token_bytes = pool_nbytes(self._cache) // (pool_blocks * bs)
        self._verify_p = self._draft_p = self._dchunk_p = None
        self._draft_model_p = None
        self._dcache = None
        if self._spec_on:
            self._verify_p = jax.jit(
                _verify_paged_t if ten else _verify_paged,
                donate_argnums=(1,))
            if self._draft_on:
                # A DISTINCT attribute from the (non-donating)
                # n-gram program: this one donates the draft tree.
                self._draft_model_p = jax.jit(_draft_model_fn,
                                              donate_argnums=(1,))
                self._dchunk_p = jax.jit(_draft_chunk,
                                         donate_argnums=(1,))
                # The second cache tree riding the same pool: one
                # block-id space, one table, two KV trees (target +
                # draft) — sharing, dedup, flush, and reset all act
                # on both through the same ids.
                self._dcache = paged_decode_cache(
                    self._ddec, pool_blocks, bs, self.max_slots)
            else:
                self._draft_p = jax.jit(_draft_ngram)
        self._init_host_tier(host_tier)
        # Only the sites THIS engine compiled stay in the map (a
        # speculative engine has no tick, an n-gram one no
        # draft_prefill): its keys are `compile_counts()` keys.
        compiled = self.compile_counts()
        self._donated_by_site = {
            site: tree for site, tree in self._donated_by_site.items()
            if site in compiled}
        self._warm = False
        if tracer is not None:
            self.set_tracer(tracer)

    def _wide_program_pays(self, max_len: int) -> bool:
        """Whether to build the second, ``prefill_len``-wide chunk
        program. It exists to spare a cold prompt the fixed cost of
        ``ceil(plen / chunk)`` applies; a chunk of
        ``_WIDE_PROGRAM_BELOW_CHUNK`` tokens or more amortises that cost
        by itself (a dispatch is about a millisecond, such a chunk tens
        of them), while a wide program's temporaries and its padding
        grow with its width: at ``prefill_len`` 12,288 it held 2.7 GB
        of them, more than the chip had left (PERF.md, PR 30). The
        other two conditions are the old ones: something narrower to
        be wider than, and positions that stay in range from the
        deepest offset the width policy starts it at."""
        return (self._chunk < self.prefill_len
                and self._chunk < _WIDE_PROGRAM_BELOW_CHUNK
                and self.prefill_len + self.prefill_len // 4 <= max_len)

    def _init_host_tier(self, host_tier) -> None:
        """Arm the host-RAM spill tier (the ``host_tier`` arg docs):
        build the byte-budgeted :class:`HostTierCache` with this
        engine's per-leaf block spec, compile the ONE promotion program
        (``host_promote`` — a :func:`cache_blocks_scatter` per KV leaf
        over the donated pool tree, fixed padded shapes), and install
        the demotion hook on the radix index's eviction path. A
        ``None``/zero-budget config installs NOTHING: the engine stays
        bit-identical to an untiered one."""
        self._host = None
        self._promote_p = None
        self._demote_p = None
        self._host_promote_tokens = 0
        if host_tier is None:
            return
        cfg = (host_tier if isinstance(host_tier, HostTierConfig)
               else HostTierConfig(byte_budget=int(host_tier)))
        if cfg.byte_budget == 0:
            return
        if self._stateful:
            raise NotImplementedError(
                "host_tier with a model that keeps per-slot state "
                "(short-convolution layers) is not supported: a promoted "
                "block restores the attention layers' K/V and not the "
                "state, and this engine matches no prefix for such a "
                "model (ROADMAP M5)")
        if self._draft_on:
            raise NotImplementedError(
                "host_tier with spec_draft_model is not supported yet: "
                "a promoted block carries target K/V only, and the "
                "draft tree's twin block would be junk — mirroring the "
                "second cache tree through the tier is follow-on work")
        spec = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(self._cache):
            if not is_paged_pool_path(path):
                continue
            spec[jax.tree_util.keystr(path)] = (
                (1,) + tuple(leaf.shape[1:-2])
                + (self.prefix_block_size, leaf.shape[-1]),
                np.dtype(leaf.dtype))
        self._host = HostTierCache(
            self.prefix_block_size, cfg.byte_budget,
            min_chain_blocks=cfg.min_chain_blocks, leaf_spec=spec)
        self._host_promote_tokens = int(cfg.promote_tokens_per_block)

        def _host_promote(pool, rows, ids):
            # The H2D rides the SAME primitive donation rides
            # (`ops.attention.cache_blocks_scatter`): one scatter per
            # KV leaf over the donated pool tree, no model compute;
            # padded ids land their junk in the scratch sink, and
            # non-pool leaves (counters, tables — told by KEY, not by
            # rank) pass through untouched so the tree keeps its
            # canonical placeholders.
            def _s(path, pool_leaf, row_leaf):
                if not is_paged_pool_path(path):
                    return pool_leaf
                return cache_blocks_scatter(pool_leaf, row_leaf, ids, 0)
            return jax.tree_util.tree_map_with_path(_s, pool, rows)

        self._promote_p = jax.jit(_host_promote, donate_argnums=(0,))
        # A REAL mid-dispatch promotion error may have consumed the
        # donated pool tree — recovery is the pool rebuild (the full
        # live-slot replay), like a chunk's.
        self._donated_by_site["host_promote"] = "pool"

        def _host_demote(pool, ids):
            # The D2H read (`ops.attention.cache_blocks_gather`),
            # jitted over
            # the whole tree at a FIXED scratch-padded id width: the
            # reclaim batch becomes ONE dispatch that traces once
            # (per-leaf eager gathers re-specialize per batch width
            # — mid-run backend compiles — and their dispatch
            # overhead dominated the admission path). Read-only: no
            # donation, no fault site — a failed read degrades to
            # the old free-and-recompute path in `_demote_blocks`.
            out = {}
            for path, leaf in jax.tree_util.tree_leaves_with_path(pool):
                if not is_paged_pool_path(path):
                    continue
                out[jax.tree_util.keystr(path)] = cache_blocks_gather(
                    leaf, ids)
            return out

        self._demote_p = jax.jit(_host_demote)
        self._prefix.on_evict = self._demote_blocks

    # ----------------------------------------------------- observability
    @property
    def tracer(self):
        """The installed tracer (the shared no-op object when tracing
        is disabled — check ``tracer.enabled``)."""
        return self._tracer

    def set_tracer(self, tracer) -> None:
        """Install (or, with ``None``, remove) a per-request tracer.

        Also wires the fault plan's injection observer so every
        injected fault surfaces as an engine event with the same
        ``(step, site)`` coordinates the plan fired at — including
        LATENCY faults, which raise nothing and would otherwise be
        invisible to the engine."""
        self._tracer = NULL_TRACER if tracer is None else tracer
        if self._faults is not None:
            self._faults.on_inject = (
                self._tracer.on_fault_injected if self._tracer.enabled
                else None)

    # -------------------------------------------------------- submission
    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               sampling: Optional[SamplingParams] = None,
               deadline_s: Optional[float] = None,
               priority: Priority = Priority.INTERACTIVE,
               adapter: Optional[str] = None,
               constraint: Optional[dict] = None) -> RequestHandle:
        """Queue one request; returns its streaming handle.

        Raises :class:`~pddl_tpu.serve.request.QueueFull` when the
        admission-control queue is at depth (the metrics count the
        rejection either way); the raised instance carries a
        ``retry_after_s`` hint — the queue this PRIORITY would wait
        behind (its own and every more urgent class) x the recent
        per-admission interval — once the engine has admitted enough
        traffic to estimate one, so a ``best_effort`` reject honestly
        hints a longer wait than an ``interactive`` one. After
        :meth:`drain` the engine accepts nothing (the process is on
        its way out).

        Tenant fields (need ``tenant=TenantConfig(...)``): ``adapter``
        names a registered LoRA adapter (``None`` = base model);
        ``constraint`` is a grammar/schema spec dict
        (``{"kind": "regex", "pattern": ...}`` or ``{"kind":
        "json_schema", "schema": {...}}``) compiled HERE — a malformed
        spec rejects the request loudly, never faults a tick."""
        if self._drained:
            raise RuntimeError(
                "engine is drained (snapshot taken, admission stopped); "
                "restore the snapshot into a fresh engine")
        priority = Priority(priority)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must have at least one token")
        if prompt.size > self.prefill_len:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the engine's "
                f"prefill_len {self.prefill_len}")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if prompt.size + max_new_tokens > self.model.max_len:
            raise ValueError(
                f"prompt + new tokens {prompt.size + max_new_tokens} "
                f"exceed max_len {self.model.max_len}")
        if (adapter is not None or constraint is not None) \
                and not self._tenant_on:
            raise ValueError(
                "adapter/constraint need a tenant-enabled engine "
                "(ServeEngine(..., tenant=TenantConfig(...)))")
        if adapter is not None and adapter not in self._registry:
            raise ValueError(
                f"adapter {adapter!r} is not registered "
                f"(known: {self._registry.names})")
        if constraint is not None:
            self._compiled_fsm(constraint)  # validate + warm the cache
        req = Request(prompt=prompt.tolist(),
                      max_new_tokens=int(max_new_tokens),
                      sampling=sampling or SamplingParams(),
                      deadline_s=deadline_s, priority=priority,
                      adapter=adapter, constraint=constraint)
        handle = RequestHandle(req, arrival_s=self._clock())
        try:
            self.scheduler.submit(handle)
        except QueueFull as e:
            self.metrics.record_rejected(priority.value)
            # Re-raise with the polite-backpressure hint the scheduler
            # cannot compute (it has no latency telemetry). The depth
            # priced in is what THIS class waits behind — its own and
            # every more urgent class — so lower classes get longer,
            # honest hints.
            raise QueueFull(
                e.queue_depth, e.max_queue_depth,
                retry_after_s=self.metrics.estimate_retry_after_s(
                    self.scheduler.depth_at_or_above(priority)),
                priority=priority) from None
        except Exception:
            self.metrics.record_rejected(priority.value)
            raise
        if constraint is not None:
            self.metrics.record_constrained()
        self._tracer.on_submit(handle, self.scheduler.depth)
        return handle

    # ---------------------------------------------------------- plumbing
    def warmup(self) -> None:
        """Trace/compile every resident program before traffic (one
        dummy chunk per width + one all-dead tick through all-scratch
        tables: every warmup write lands in the junk sink, the radix
        index stays empty, and every program traces once with its
        serving shapes). Implicit on the first ``step()`` if not
        called. Each program's first call is a set-up stage of its own
        (``warmup:<site>``), the closing wait another
        (``warmup_wait``)."""
        if self._warm:
            return
        stage = functools.partial(_setup_stage, metrics=self.metrics)
        first_mask = self._first_mask_args(None)  # () on a plain engine
        if self._tenant_on:
            # Warm the adapter-load program by writing zeros into the
            # identity row — content unchanged (row 0 IS the zero
            # adapter), program traced once.
            with stage("warmup:adapter_load"):
                self._apool_a, self._apool_b = self._adapter_load_p(
                    self._apool_a, self._apool_b, np.int32(0),
                    np.zeros((self.model.embed_dim, self._registry.rank),
                             np.float32),
                    np.zeros((self._registry.rank, self.model.vocab_size),
                             np.float32))
        with stage("warmup:chunk_prefill"):
            self._cache, logits = self._chunk_p(
                *self._chunk_args_paged(self._chunk))
        if self._has_wide:
            with stage("warmup:chunk_prefill_wide"):
                self._cache, logits = self._chunk_wide_p(
                    *self._chunk_args_paged(self.prefill_len))
        with stage("warmup:sample_first"):
            tok, self._rng = self._sample_first_p(
                logits, *first_mask, np.float32(0.0), np.int32(0),
                np.float32(2.0), self._rng)
        if self._host is not None:
            # All-scratch promote: junk lands in the sink, the host
            # tier stays empty, the program traces once — and the
            # demote gather's one program likewise.
            with stage("warmup:host_promote"):
                self._cache = self._promote_p(
                    self._cache, self._assemble_promote_rows([]),
                    np.zeros(self._match_cap, np.int32))
            with stage("warmup:host_demote"):
                self._demote_p(self._cache,
                               np.zeros(self._match_cap, np.int32))
        if self._spec_on:
            nxt = self._warm_spec(stage)
        else:
            with stage("warmup:tick"):
                self._cache, nxt, self._rng = self._tick_p(
                    *self._tick_args())
        with stage("warmup_wait"):
            jax.block_until_ready((tok, nxt))
        self._warm = True

    def _tick_args(self) -> tuple:
        """The one-token tick's arguments as they stand now. Warmup,
        ``step()`` and :meth:`tick_lowering` all build them here, so the
        three can never disagree on the program's signature."""
        return (self._params, self._cache, self._positions, self._tables,
                self._tokens, self._temps, self._top_ks, self._top_ps,
                *self._tick_extra(), self._rng)

    def _chunk_args_paged(self, width: int) -> tuple:
        """A chunk program's arguments for one real token at
        offset 0 through an all-scratch table: what warmup dispatches
        and :meth:`program_lowerings` lowers."""
        t1 = np.zeros((1, self._table_width), np.int32)
        return (self._params, self._cache, np.zeros((1, width), np.int32),
                np.int32(1), np.int32(0), t1, *self._chunk_extra(0, 0))

    def tick_lowering(self):
        """The one-token tick LOWERED at its serving shapes, for
        inspection (``jax.stages.Lowered``): ``.as_text()`` shows
        whether the paged Mosaic kernel is in the program
        (``tpu_custom_call``) or a jnp/interpreted stand-in is,
        ``.compile().memory_analysis()`` what it needs. Lowering runs
        nothing and adds no executable to :meth:`compile_counts`."""
        if self._spec_on:
            raise ValueError("a speculative engine has no one-token tick "
                             "(draft/verify replace it)")
        return self._tick_p.lower(*self._tick_args())

    def program_lowerings(self) -> Dict[str, object]:
        """The tick and the chunk programs LOWERED at their serving
        shapes, by site name. ``.compile().as_text()``
        names every instruction with the scope it came from
        (``metadata={op_name=...}``: the flax module path and the
        ``jax.named_scope`` names — ``moe_router``, ``moe_dispatch``,
        ``moe_ffn``, ``moe_combine``, ``attn_window``, ``attn_global``),
        which is how a device trace's op names, which carry no scope,
        are put down to a scope."""
        out = {"tick": self.tick_lowering()}
        out["chunk_prefill"] = self._chunk_p.lower(
            *self._chunk_args_paged(self._chunk))
        if self._has_wide:
            out["chunk_prefill_wide"] = self._chunk_wide_p.lower(
                *self._chunk_args_paged(self.prefill_len))
        return out

    def expert_load(self) -> Dict[str, np.ndarray]:
        """Routed pairs of PROMPT tokens per expert, by routed layer
        (``"block3/moe"`` -> int ``[experts]``), accumulated on the
        device by the chunk programs since the pool was built; empty
        for a model without routed layers. One
        device read: call it at a window's edges, not in the loop."""
        from pddl_tpu.ops.moe import EXPERT_LOAD_KEY

        found = {
            "/".join(str(getattr(k, "key", k)) for k in path[:-1]): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                self._cache)
            if str(getattr(path[-1], "key", path[-1])) == EXPERT_LOAD_KEY}
        return {k: np.asarray(v) for k, v in jax.device_get(found).items()}

    def _warm_spec(self, stage):
        """Trace the draft/verify pair (and the draft model's admission
        chunk) with all-dead inputs: caps 0 + forced -1 accept nothing,
        junk writes land in the scratch sink (all-scratch tables), so
        warmup leaves no trace in any live state. Each first call runs
        in its set-up stage (``stage(name)``, as `warmup`'s). Returns
        the verify window for the caller's block_until_ready."""
        s, k = self.max_slots, self._spec_k
        forced_tok = np.zeros((s, k), np.int32)
        forced_n = np.full(s, -1, np.int32)
        if self._draft_on:
            t1 = np.zeros((1, self._table_width), np.int32)
            with stage("warmup:draft_prefill"):
                self._dcache = self._dchunk_p(
                    self._dparams, self._dcache,
                    np.zeros((1, self._chunk), np.int32), np.int32(1),
                    np.int32(0), t1)
            with stage("warmup:draft"):
                self._dcache, drafts = self._draft_model_p(
                    self._dparams, self._dcache, self._positions,
                    self._tables, self._tokens, forced_tok, forced_n)
        else:
            with stage("warmup:draft"):
                drafts = self._draft_p(self._hist, self._positions)
        block = np.zeros((s, k + 1), np.int32)
        caps = np.zeros(s, np.int32)
        with stage("warmup:verify"):
            self._cache, w, acc, self._rng = self._verify_p(
                self._params, self._cache, self._positions, self._tables,
                block, self._temps, self._top_ks, self._top_ps,
                *self._verify_extra(), caps, forced_n, self._rng)
        with stage("warmup_wait"):
            jax.block_until_ready(drafts)
        return w

    def compile_counts(self) -> Dict[str, int]:
        """Compiled-executable count per resident program (the
        zero-recompiles-after-warmup contract: every entry stays at 1).
        Chunk widths, table shapes, and every offset/length are fixed
        shapes or runtime values, so the program set stays closed."""
        counts = {
            "sample_first": self._sample_first_p._cache_size(),
            "chunk_prefill": self._chunk_p._cache_size(),
        }
        if self._spec_on:
            # Speculative engines swap the one-token tick for the
            # draft/verify pair (+ the draft model's admission
            # chunk) — the site vocabulary graftlint keeps in
            # lockstep with FaultPlan.SITES.
            counts["verify"] = self._verify_p._cache_size()
            counts["draft"] = (self._draft_model_p if self._draft_on
                               else self._draft_p)._cache_size()
            if self._draft_on:
                counts["draft_prefill"] = self._dchunk_p._cache_size()
        else:
            counts["tick"] = self._tick_p._cache_size()
        if self._has_wide:
            counts["chunk_prefill_wide"] = \
                self._chunk_wide_p._cache_size()
        if self._tenant_on:
            counts["adapter_load"] = self._adapter_load_p._cache_size()
        if self._host is not None:
            counts["host_promote"] = self._promote_p._cache_size()
        return counts

    @property
    def paged(self) -> bool:
        """Always True: decode reads K/V straight from the block pool
        through per-slot block tables (kept for the exposition's
        ``paged`` series and callers of the two-engine era)."""
        return True

    @property
    def host_tier_enabled(self) -> bool:
        """True when the host-RAM spill tier is armed (module
        docstring; ``host_tier=`` with a nonzero byte budget)."""
        return self._host is not None

    @property
    def host_tier_bytes_resident(self) -> int:
        """Host bytes the spill tier currently holds (0 with the tier
        off) — the gauge the sizing runbook watches against the byte
        budget (docs/OPERATIONS.md § "Host tier sizing")."""
        return self._host.bytes_resident if self._host is not None else 0

    @property
    def host_tier_blocks_resident(self) -> int:
        """Demoted blocks currently resident in the host tier."""
        return (self._host.blocks_resident if self._host is not None
                else 0)

    @property
    def spec_enabled(self) -> bool:
        """True when this engine compiled the speculative draft/verify
        program pair (``spec_k > 0``; module docstring)."""
        return self._spec_on

    @property
    def spec_k(self) -> int:
        """Drafted tokens per slot per step (0 = classic tick)."""
        return self._spec_k

    @property
    def spec_draft_model_enabled(self) -> bool:
        """True when a draft model (second paged cache tree) drafts;
        False means the zero-weight n-gram drafter (or spec off)."""
        return self._draft_on

    # ----------------------------------------------------------- tenancy
    @property
    def tenant_enabled(self) -> bool:
        """True when this engine compiled the multi-tenant program set
        (per-slot LoRA adapters + grammar masks; `serve/tenant/`)."""
        return self._tenant_on

    @property
    def adapter_registry(self):
        """The engine's :class:`~pddl_tpu.serve.tenant.AdapterRegistry`
        (``None`` on a plain engine). Adapters registered here become
        submittable immediately — residency is handled at admission."""
        return self._registry

    @property
    def adapter_pool_resident(self) -> int:
        """Adapters currently device-resident (0 on a plain engine)."""
        return self._apool.resident if self._tenant_on else 0

    def _compiled_fsm(self, spec):
        """Compile (or fetch) the token FSM for a constraint spec dict.
        Cached by canonical spec key — N requests under one schema
        share one automaton and one mask table."""
        key = constraint_key(spec)
        fsm = self._fsm_cache.get(key)
        if fsm is None:
            if self._tenant.token_strings is None:
                raise ValueError(
                    "constrained decoding needs TenantConfig."
                    "token_strings (the token-id -> string vocabulary "
                    "grammar compilation maps masks through)")
            fsm = compile_constraint(spec, self._tenant.token_strings)
            # Bounded like the process-wide cache it fronts
            # (`grammar._FSM_CACHE`): client-supplied specs (e.g. a
            # per-request ID baked into a pattern) must not grow host
            # memory forever in a long-lived engine.
            if len(self._fsm_cache) >= 256:
                self._fsm_cache.pop(next(iter(self._fsm_cache)))
            self._fsm_cache[key] = fsm
        # Engine-specific (eos-dependent) viability, checked per call
        # because the FSM cache is engine-agnostic: a constraint whose
        # START state allows no token and has no eos escape (it matches
        # only the empty string — e.g. "x*" over a vocabulary with no
        # 'x') could never sample a first token; rejecting HERE fails
        # the request at submit (or via the replay budget at restore)
        # instead of crashing the step for everyone.
        if fsm.is_dead_end(fsm.start, self.eos_token):
            raise ValueError(
                "constraint admits no first token over this engine's "
                "vocabulary (it matches only the empty string, and the "
                "engine has no eos token to emit)")
        return fsm

    def _acquire_adapter(self, name: str, fresh: bool = True) -> int:
        """Resolve an adapter name to a PINNED device pool row, loading
        the factors on a cold miss (LRU-evicting an unpinned row under
        pressure — the prefix-chain discipline applied to weights).
        ``fresh=False`` marks a replay/resume re-admission (pool
        traffic counted, per-tenant request volume not). Escalates
        unresolvable shortfalls as :class:`_SlotStateLost` so admission
        charges a replay instead of crashing the step."""
        row = self._apool.lookup(name)
        if row is not None:
            self.metrics.record_adapter_hit(name, self._apool.resident,
                                            fresh=fresh)
            self._apool.pin(row)
            return row
        try:
            adapter = self._registry.get(name)
        except KeyError as e:
            # Permanently unserveable here (e.g. a migrated stream
            # whose adapter this deployment never registered): the
            # replay budget turns it into a terminal ERROR.
            raise _SlotStateLost("adapter_admit", e) from e
        try:
            row = self._apool.assign(name)
        except AdapterPoolExhausted as e:
            raise _SlotStateLost("adapter_admit", e) from e
        try:
            self._apool_a, self._apool_b = self._device_call(
                "adapter_load", self._adapter_load_p,
                self._apool_a, self._apool_b, np.int32(row),
                adapter.a, adapter.b)
        except _SlotStateLost:
            self._apool.unassign(row)
            raise
        self.metrics.record_adapter_load(name, self._apool.resident,
                                         self._apool.evictions,
                                         fresh=fresh)
        self._apool.pin(row)
        return row

    def _release_adapter(self, row) -> None:
        """Unpin a slot's (or a failed admission's) adapter row; row 0
        (identity / no adapter) is a no-op."""
        if self._tenant_on and int(row) != 0:
            self._apool.unpin(int(row))

    def _tenant_admit(self, handle):
        """The tenant half of one admission: ``(pinned_adapter_row,
        compiled_fsm_or_None)``. Raises :class:`_SlotStateLost` (self-
        unwound — nothing left pinned) on unresolvable specs/pools."""
        if not self._tenant_on:
            return 0, None
        req = handle.request
        fsm = None
        if req.constraint is not None:
            try:
                fsm = self._compiled_fsm(req.constraint)
            except ValueError as e:
                # submit() validates, so this is the restore/migration
                # path seeing a spec this engine cannot compile: fail
                # the REQUEST (via replay budget), not the engine.
                raise _SlotStateLost("constraint_admit", e) from e
        # "Fresh" means this request's FIRST service, not merely
        # zero tokens: a pre-first-token replay (prefill faulted past
        # the retry budget) has empty tokens but a replay charge, and
        # must not double-count the capacity-planning series.
        fresh = not handle.tokens and not handle.replays
        arow = (self._acquire_adapter(req.adapter, fresh=fresh)
                if req.adapter is not None else 0)
        return arow, fsm

    def _chunk_extra(self, aid, sid):
        """A chunk program's trailing args: the slot the chunk fills
        (where a model with per-slot state keeps its row) and, in
        tenant mode, adapter id + factor pools."""
        return ((np.int32(sid), np.int32(aid), self._apool_a, self._apool_b)
                if self._tenant_on else (np.int32(sid),))

    def _tick_extra(self):
        """Extra fused-tick args in tenant mode (grammar masks + factor
        pools + per-slot adapter rows); empty on a plain engine. The
        mask ships as one device-resident array restaged only on
        change."""
        if not self._tenant_on:
            return ()
        if self._masks_dev is None or self._masks_dirty:
            self._masks_dev = jnp.asarray(self._masks)
            self._masks_dirty = False
        return (self._masks_dev, self._apool_a, self._apool_b,
                self._arow)

    def _verify_extra(self):
        """Extra verify-program args in tenant mode (per-POSITION
        grammar masks ``[S, spec_k+1, V]`` + factor pools + per-slot
        adapter rows); empty on a plain engine. Same restage-on-change
        staging as the tick masks."""
        if not self._tenant_on:
            return ()
        if self._masks_w_dev is None or self._masks_w_dirty:
            self._masks_w_dev = jnp.asarray(self._masks_w)
            self._masks_w_dirty = False
        return (self._masks_w_dev, self._apool_a, self._apool_b,
                self._arow)

    def _first_mask_args(self, fsm):
        """The sample-first mask arg (``[1, V]``) in tenant mode: the
        FSM's start-state allow row for constrained requests, all-True
        (a bitwise logits pass-through) otherwise."""
        if not self._tenant_on:
            return ()
        if fsm is None:
            return (np.ones((1, self.model.vocab_size), np.bool_),)
        return (fsm.allow_row(fsm.start, self.eos_token)[None],)

    @property
    def blocks_shared(self) -> int:
        """Pool blocks referenced by MORE THAN ONE live slot's block
        table right now — each is one block of KV a private-copy
        design would have duplicated per referencing slot."""
        return _shared_block_count(self._slots, self._tables)

    @property
    def block_table_fill(self) -> float:
        """Mean fraction of live slots' table entries pointing at real
        (non-scratch) blocks — how much of the paged address space the
        current streams occupy. 0.0 with no live slots."""
        live = [sid for sid, h in enumerate(self._slots) if h is not None]
        if not live:
            return 0.0
        rows = self._tables[live]
        return float((rows != 0).mean())

    @property
    def degraded(self) -> bool:
        """True while an OOM has the prefix cache shed and donations
        off (serving continues on the cold path); re-arms after
        ``degraded_cooldown_s`` without another OOM."""
        return self._degraded

    @property
    def prefix_pool_nbytes(self) -> int:
        """Device bytes the resident KV block pool holds: the whole
        serving KV (live streams included), so only its unpinned
        cached fraction is sheddable by degraded mode
        (docs/OPERATIONS.md § "Failure modes & recovery")."""
        return pool_nbytes(self._cache)

    @property
    def drained(self) -> bool:
        """True once :meth:`drain` snapshotted the engine: admission is
        stopped and ``step()`` is a no-op — restore into a fresh
        engine."""
        return self._drained

    @property
    def live_slots(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def has_work(self) -> bool:
        if self._drained:
            return False
        return (self.live_slots > 0 or self.scheduler.depth > 0
                or bool(self._admitting))

    def _free_slot_ids(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _evict(self, slot_id: int, state: RequestState,
               reason: FinishReason) -> None:
        handle = self._slots[slot_id]
        assert handle is not None
        handle.state = state
        handle.finish_reason = reason
        handle.finish_s = self._clock()
        self.metrics.record_finish(reason.value,
                                   handle.request.priority.value)
        self._tracer.on_finish(handle, reason.value)
        self._park_slot(slot_id)

    # --------------------------------------------------- fault handling
    def _device_call(self, site: str, fn, *args):
        """The ONE guarded device-dispatch boundary: consult the fault
        plan, classify failures, retry transients with bounded
        exponential backoff, flip degraded on OOM (no blind retry — an
        allocation failure won't pass until memory is shed, and the
        degraded flush plus the caller's rebuild IS the shedding), and
        escalate to :class:`_SlotStateLost` when the budget runs out.
        ``KillPoint`` is a BaseException — it passes through everything
        here, like the SIGKILL it stands for. Injected faults fire
        BEFORE ``fn`` runs, so retrying never touches a half-consumed
        donated buffer; a REAL error from a donated-buffer program is
        never re-dispatched (its donated input may already be deleted)
        — it escalates immediately, tagged with the consumed resource
        so the recovery path rebuilds it."""
        attempt = 0
        while True:
            try:
                if self._faults is not None:
                    self._faults.check(site)
                t0 = time.perf_counter()
                out = fn(*args)
                # Dispatch wall time (the programs are async — this is
                # host-side dispatch + any implicit transfer wait, never
                # an added device sync), accumulated per site for the
                # telemetry ring and handed to the tracer's
                # prefill-chunk events via `_last_wall_s`.
                dt = time.perf_counter() - t0
                self._site_wall[site] = self._site_wall.get(site, 0.0) + dt
                self._last_wall_s = dt
                return out
            except Exception as e:
                kind = classify(e)
                if kind is None:
                    raise  # not a device fault: bugs stay loud
                injected = isinstance(e, (InjectedTransientError,
                                          InjectedResourceExhausted))
                consumed = (None if injected
                            else self._donated_by_site.get(site))
                if kind == "oom":
                    self._enter_degraded()
                    raise _SlotStateLost(site, e, consumed) from e
                if consumed is not None:
                    raise _SlotStateLost(site, e, consumed) from e
                attempt += 1
                if attempt > self._max_retries:
                    raise _SlotStateLost(site, e) from e
                self.metrics.record_retry(site)
                self._tracer.on_retry(self._cur_step, site, attempt)
                self._backoff_sleep(
                    self._retry_backoff_s * (2 ** (attempt - 1)))

    def _enter_degraded(self) -> None:
        """OOM response: flush every unpinned prefix block (the one
        large sheddable HBM consumer), stop donations, keep serving on
        the cold path. Live slots' pinned chains stay — their tables
        reference those blocks in place. A repeat OOM pushes the
        re-arm time out."""
        now = self._clock()
        if not self._degraded:
            self._degraded = True
            self._degraded_entered_s = now
            self.metrics.record_degraded_entry()
            self._tracer.on_degraded_entry(self._cur_step)
            self._prefix.flush_unpinned()
        self._degraded_until_s = now + self._degraded_cooldown_s

    def _maybe_rearm_degraded(self) -> None:
        now = self._clock()
        if self._degraded and now >= self._degraded_until_s:
            self._degraded = False
            self.metrics.record_degraded_exit(now - self._degraded_entered_s)
            self._tracer.on_degraded_exit(
                self._cur_step, now - self._degraded_entered_s)

    def _reset_paged_pool(self) -> None:
        """Rebuild the paged world after its one donated tree may have
        been consumed (or live KV presumed lost): fresh pool tree (same
        shapes — nothing recompiles), fresh index (every stored chain
        pointed into the dead storage), all tables back to scratch,
        all private ownership dropped. Callers park/replay the live
        slots FIRST — their KV lived here."""
        self._cache = paged_decode_cache(self._dec, self._prefix.num_blocks,
                                         self.prefix_block_size,
                                         self.max_slots)
        if self._draft_on:
            # The draft tree shares the block-id space: a pool reset
            # retires its storage too (replay rebuilds both trees).
            self._dcache = paged_decode_cache(self._ddec,
                                              self._prefix.num_blocks,
                                              self.prefix_block_size,
                                              self.max_slots)
        self._prefix = RadixPrefixCache(self.prefix_block_size,
                                        self._prefix.num_blocks)
        self._tables[:] = 0
        self._private = [[] for _ in range(self.max_slots)]
        self._slot_nodes = [None] * self.max_slots
        if self._host is not None:
            # The old index died wholesale WITHOUT demotion (its
            # storage may be consumed); the fresh one demotes again.
            # Host-tier contents are independent host copies and
            # survive the rebuild — still promotable.
            self._prefix.on_evict = self._demote_blocks

    def _recover_consumed(self, lost: _SlotStateLost) -> None:
        """Rebuild the resident donated tree a real mid-dispatch
        error may have eaten (`_SlotStateLost.consumed`). Every
        consuming site donates the ONE pool tree holding all live KV,
        so recovery is always the full live-slot replay
        (`_lose_live_slots` parks, resets the paged world, and
        requeues)."""
        if lost.consumed == "pool":
            self._lose_live_slots()

    def _park_slot(self, slot_id: int) -> None:
        """Park a vacated row: position 0, greedy params. The table
        row goes all-scratch, so its future junk writes land in the
        sink, and the slot's PRIVATE blocks — tail + generated tokens,
        never shared — return to the free list; donated prompt blocks
        stay cached under the radix index, unpinned below."""
        self._slots[slot_id] = None
        if self._tenant_on:
            # Release the slot's adapter pin (the weights stay resident
            # — that's the point — but become LRU-evictable once no
            # live slot needs them) and reset the grammar state: an
            # all-True mask is a bitwise logits pass-through, so the
            # parked row's junk tick behaves exactly as before.
            self._release_adapter(self._arow[slot_id])
            self._arow[slot_id] = 0
            if not self._masks[slot_id].all():
                self._masks[slot_id, :] = True
                self._masks_dirty = True
            if self._masks_w is not None \
                    and not self._masks_w[slot_id].all():
                self._masks_w[slot_id, :, :] = True
                self._masks_w_dirty = True
            self._fsms[slot_id] = None
        if self._private[slot_id]:
            self._prefix.release(self._private[slot_id])
            self._private[slot_id] = []
        self._tables[slot_id, :] = 0
        if self._slot_nodes[slot_id] is not None:
            # Release the request's pin on its prefix chain: the blocks
            # stay cached (that's the point) but become LRU-evictable
            # once no live slot or deeper chain needs them.
            self._prefix.unpin(self._slot_nodes[slot_id])
            self._slot_nodes[slot_id] = None
        self._positions[slot_id] = 0
        self._tokens[slot_id] = 0
        self._temps[slot_id] = 0.0
        self._top_ks[slot_id] = 0
        self._top_ps[slot_id] = 2.0

    def _mark_replay(self, handle: RequestHandle) -> bool:
        """Charge one replay against ``handle``; True = requeue it for
        a slot-state rebuild, False = replay budget exhausted, request
        settled FAILED/ERROR (the engine keeps serving everyone
        else)."""
        handle.replays += 1
        handle.replay_pending = []
        if handle.replays > self._max_replays:
            handle.state = RequestState.FAILED
            handle.finish_reason = FinishReason.ERROR
            handle.finish_s = self._clock()
            self.metrics.record_finish(FinishReason.ERROR.value,
                                       handle.request.priority.value)
            self._tracer.on_replay(handle, self._cur_step, False)
            self._tracer.on_finish(handle, FinishReason.ERROR.value)
            return False
        self.metrics.record_replay()
        self._tracer.on_replay(handle, self._cur_step, True)
        return True

    def _lose_live_slots(self) -> None:
        """The fused tick's retry budget ran out: every live slot's KV
        must be presumed gone (the pool tree is donated through the
        tick). Reallocate the pool (same shapes — nothing
        recompiles), release every pin, and requeue the live requests
        FCFS-front for replay; each rebuilds token-exactly from prompt
        + emitted tokens at its re-admission."""
        lost = [(sid, h) for sid, h in enumerate(self._slots)
                if h is not None]
        requeue: List[RequestHandle] = []
        for sid, handle in lost:
            self._park_slot(sid)  # releases pins/private into the OLD index
            if self._mark_replay(handle):
                requeue.append(handle)
        # A parked mid-prefill slice holds private ids and a pinned
        # node of the index about to be retired: DROP it without
        # releasing (the whole old index dies with the reset — a
        # release would double-own the ids in the fresh free list).
        # Its handle is still at the head of `_admitting`, so the
        # next step re-admits it from scratch against the fresh
        # pool, token-exactly. Its ADAPTER pin is different: the
        # adapter pool does NOT die with the paged reset, so the
        # pin unwinds normally (re-admission re-acquires).
        if self._slice is not None:
            self._release_adapter(self._slice.get("arow", 0))
        self._slice = None
        # The pool held every live stream's KV (and the cached
        # chains): rebuild the whole paged world — same shapes,
        # nothing recompiles.
        self._reset_paged_pool()
        self.scheduler.requeue_front(requeue)

    def _expired(self, handle: RequestHandle, now: float) -> bool:
        return (handle.request.deadline_s is not None
                and now - handle.arrival_s > handle.request.deadline_s)

    def _reap(self) -> None:
        """Cancellations and deadlines, checked at tick granularity."""
        now = self._clock()
        for sid, handle in enumerate(self._slots):
            if handle is None:
                continue
            if handle.cancelled:
                self._evict(sid, RequestState.CANCELLED,
                            FinishReason.CANCELLED)
            elif self._expired(handle, now):
                self._evict(sid, RequestState.TIMED_OUT,
                            FinishReason.TIMED_OUT)

    @property
    def _prefix_off(self) -> bool:
        """True when admissions neither match nor donate prefixes: the
        post-OOM cool-down, and always for a model with per-slot state
        (a shared block restores K/V, not a convolution's state —
        snapshots of it at radix nodes are ROADMAP M5's)."""
        return self._degraded or self._stateful

    def _record_state_start(self, off: int) -> None:
        """One chunk at ``off`` was dispatched: at offset 0 a model with
        per-slot state started its row from zeros."""
        if self._stateful and off == 0:
            self.metrics.state_rows_started += 1

    def _match_blocks(self, prompt) -> int:
        """Cap on the matchable chain for one prompt (blocks): leave at
        least one suffix token, never exceed ``match_cap``."""
        return min(self._match_cap, (len(prompt) - 1) // self.prefix_block_size)

    def _prefill_cost(self, handle) -> int:
        """Admission-budget charge: the UNCACHED suffix length (a cached
        prefix costs no prefill work). A pop-time estimate — the match
        also refreshes the chain's LRU stamp, so a same-tick eviction
        stealing it needs a fully-pinned pool; if that happens the
        request simply re-prefills more than charged (see
        ``FCFSScheduler.admit``). Degraded mode charges the full prompt
        (the cache is not consulted on the cold path)."""
        prompt = handle.request.prompt
        if self._prefix_off:
            cost = len(prompt)
        else:
            match = self._prefix.match(
                prompt, max_blocks=self._match_blocks(prompt))
            cost = len(prompt) - match.n_blocks * self.prefix_block_size
            # Tiered KV cache (ISSUE 13): blocks the host tier will
            # promote cost an H2D transfer, not a prefill — charge them
            # at promote_tokens_per_block instead of block_size tokens
            # (the adapter_load_tokens precedent: real admission-path
            # work, priced at what it actually is). Same pop-time-
            # estimate caveat as the prefix charge.
            if self._host is not None:
                h = self._host.match_depth(
                    prompt, match.n_blocks,
                    self._match_blocks(prompt) - match.n_blocks)
                if h > 0:
                    cost -= h * self.prefix_block_size
                    cost += h * self._host_promote_tokens
        # Tenancy-aware budget (ISSUE 9): a COLD adapter load is real
        # admission-path work (a host->device factor transfer), so it
        # charges like an uncached suffix; a resident adapter — like a
        # cached prefix — charges nothing. Pop-time estimate with the
        # same caveat as the prefix charge: a same-tick eviction can
        # make the real work exceed it, which costs latency, never
        # correctness.
        if (self._tenant_on and handle.request.adapter is not None
                and self._apool.row_of(handle.request.adapter) is None):
            cost += int(self._tenant.adapter_load_tokens)
        # Speculative engines charge a replay's catch-up re-feed against
        # the budget at the ACCEPTED token count — the emitted tokens
        # that really must re-enter the cache — never the drafted
        # (spec_k+1)-wide compute the verify window spends reaching
        # them (`scheduler.admit`'s accepted-not-drafted contract).
        if self._spec_on and handle.tokens:
            cost += len(handle.tokens)
        return cost

    # ---------------------------------------------------- tiered KV cache
    def _demote_blocks(self, victims) -> None:
        """``radix.on_evict`` hook — eviction becomes demotion (module
        docstring): spill the dying blocks' K/V D2H into the host tier
        when their chains are reuse-worthy. The whole reclaim pass
        moves through the jitted whole-tree gather (``_demote_p``,
        one dispatch + one device sync; a read — the pool is never
        copied, the one program traces at warmup, and demotion sits
        on the admission path, where per-block eager dispatches
        measured ~10x slower). Opportunistic by design: a refused or
        failed spill
        degrades to the old free-and-recompute path, never to an
        error, and degraded mode spills nothing (the OOM flush
        additionally bypasses this hook wholesale)."""
        if self._degraded:
            return
        keep: List[tuple] = []
        for node in victims:
            if not self._host.spill_worthy(self._prefix.chain_depth(node)):
                continue
            tokens = self._prefix.chain_tokens(node)
            if self._host.has_block(tokens):
                continue  # kept across a promotion: nothing to move
            keep.append((tokens, node.block_id))
        if not keep:
            return
        try:
            blocks = self._gather_blocks_host(
                [bid for _, bid in keep])
        except Exception as e:  # noqa: BLE001 - device faults only
            if classify(e) is None:
                raise  # not a device fault: bugs stay loud
            return
        for (tokens, _), data in zip(keep, blocks):
            if self._host.store(tokens, data):
                self.metrics.record_host_spill(self._host.bytes_resident)

    def _gather_blocks_host(self, block_ids) -> List[Dict[str, np.ndarray]]:
        """Pool blocks ``block_ids`` as per-block host payload dicts
        keyed by leaf path — the demotion (and chain-export) D2H read:
        the jitted whole-tree gather (``_demote_p``, fixed
        ``match_cap`` width, scratch-padded — one dispatch per chunk,
        traced once), one ``device_get`` for everything, then
        host-side splits (copies, so an evicted sibling cannot pin
        the batch buffer alive). Padded tail slices read scratch junk
        and are simply not taken."""
        bs = self.prefix_block_size
        w = self._match_cap
        n = len(block_ids)
        staged = []
        for c in range(0, n, w):
            ids = np.zeros(w, np.int32)
            chunk = block_ids[c:c + w]
            ids[:len(chunk)] = chunk
            staged.append(self._demote_p(self._cache, ids))
        pulled = jax.device_get(staged)
        out: List[Dict[str, np.ndarray]] = []
        for c, st in zip(range(0, n, w), pulled):
            out.extend({key: arr[..., j * bs:(j + 1) * bs, :].copy()
                        for key, arr in st.items()}
                       for j in range(min(w, n - c)))
        return out

    def _assemble_promote_rows(self, blocks: List[Dict[str, np.ndarray]]):
        """The ``host_promote`` scatter's source tree: per KV leaf a
        host row ``[1, ..., match_cap*bs, D]`` with the promoted
        payloads at ``[0, k*bs)`` and ZEROS beyond — those positions
        scatter into padded scratch ids, and the paged scratch block
        must stay zero (an ``np.empty`` tail measurably corrupted
        paged streams whose tables park on the sink). Non-KV leaves
        are scalar placeholders. Fixed width, so the program traces
        once."""
        bs = self.prefix_block_size

        def _leaf(path, leaf):
            if not is_paged_pool_path(path):
                return np.zeros((), np.int32)
            row = np.zeros((1,) + tuple(leaf.shape[1:-2])
                           + (self._match_cap * bs, leaf.shape[-1]),
                           leaf.dtype)
            key = jax.tree_util.keystr(path)
            for j, b in enumerate(blocks):
                row[..., j * bs:(j + 1) * bs, :] = b[key]
            return row

        return jax.tree_util.tree_map_with_path(_leaf, self._cache)

    def _promote_host_chain(self, prompt: np.ndarray, handle=None) -> int:
        """Promotion (module docstring): extend the device match with
        host-tier blocks — allocate device ids under the ANCHOR's pin,
        scatter the payloads H2D through the ``host_promote`` program,
        and attach the ids to the radix index, so the admission that
        follows simply matches a deeper chain. Self-unwinding: every
        exit (allocator shortfall, injected fault, real consumed-pool
        error) releases its ids and both pins exactly — the host-tier
        pin through the same discipline device chains use. Returns the
        promoted block count."""
        if self._degraded:
            return 0
        cap = self._match_blocks(prompt)
        match = self._prefix.match(prompt, max_blocks=cap)
        if match.n_blocks >= cap:
            return 0
        tip = self._host.pin_chain(prompt, match.n_blocks,
                                   cap - match.n_blocks)
        promoted = 0
        if tip is not None:
            try:
                promoted = self._promote_pinned(prompt, match, tip,
                                                handle)
            finally:
                self._host.unpin(tip)
        return promoted

    def _promote_pinned(self, prompt: np.ndarray, match, tip,
                        handle) -> int:
        """The H2D half of a promotion, under the caller's host-tier
        pin: allocate device ids beneath the ANCHOR's pin (eviction
        must not steal the chain the ids extend from), dispatch the
        scatter, attach the ids. Every failure path releases ids and
        the anchor pin exactly."""
        bs = self.prefix_block_size
        m = match.n_blocks
        anchor = match.node
        self._prefix.pin(anchor)
        try:
            ids = self._prefix.allocate(tip.depth - m)
            k = len(ids)
            if k == 0:
                self._prefix.release(ids)
                return 0
            node = tip
            while node.depth > m + k:  # allocator came up short:
                node = node.parent     # promote the prefix that fits
            rows = self._assemble_promote_rows(
                self._host.chain_data(node, k))
            dids = np.zeros(self._match_cap, np.int32)
            dids[:k] = ids
            try:
                self._cache = self._device_call(
                    "host_promote", self._promote_p, self._cache, rows,
                    dids)
            except _SlotStateLost:
                self._prefix.release(ids)
                raise
            self._prefix.extend(anchor, prompt[m * bs:(m + k) * bs], ids)
            self.metrics.record_host_promotion(
                k, k * self._host_promote_tokens,
                self._host.bytes_resident)
            self._tracer.on_prefill_chunk(handle, "host_promote", m * bs,
                                          k * bs, self._last_wall_s)
            return k
        finally:
            self._prefix.unpin(anchor)

    def _chunk_loop(self, prompt: np.ndarray, off: int, handle,
                    dispatch):
        """The whole-prompt suffix chunk loop and its width policy
        (coarse cost model — each apply pays a fixed dispatch cost plus
        per-token compute): a long remainder (>= 3/4 of the wide width)
        takes the WIDE program in one apply, so a cold prompt costs
        one apply, not ``ceil(plen / chunk)`` of them; short
        suffixes — the prefix-hit case — take narrow chunks and pay
        only for the uncached tail. ``dispatch(site, prog, chunk_toks,
        w, off)`` runs the program, adopts whatever resident tree it
        donated, and returns the logits."""
        plen = int(prompt.size)
        logits = None
        while off < plen:
            rem = plen - off
            if self._has_wide and 4 * rem >= 3 * self.prefill_len:
                width, prog = self.prefill_len, self._chunk_wide_p
                site = "chunk_prefill_wide"
            else:
                width, prog = self._chunk, self._chunk_p
                site = "chunk_prefill"
            w = min(width, rem)
            chunk_toks = np.zeros((1, width), np.int32)
            chunk_toks[0, :w] = prompt[off:off + w]
            logits = dispatch(site, prog, chunk_toks, w, off)
            self.metrics.record_prefill_chunk(
                width, off if self._reexpands else 0)
            self._tracer.on_prefill_chunk(handle, site, off, w,
                                          self._last_wall_s)
            off += w
        return logits

    def _draft_prefill_loop(self, prompt: np.ndarray, off: int,
                            table) -> None:
        """Chunk-prefill the prompt's uncached suffix through the DRAFT
        model into its pool tree (narrow chunks only — the draft model
        is small by design, so a wide twin would double the program set
        for marginal gain). Same offsets and blocks as the target's
        chunks: a donated shared-prefix block carries valid draft K/V
        for every future hit, exactly like the target K/V it sits
        beside."""
        plen = int(prompt.size)
        off = int(off)
        while off < plen:
            w = min(self._chunk, plen - off)
            chunk_toks = np.zeros((1, self._chunk), np.int32)
            chunk_toks[0, :w] = prompt[off:off + w]
            self._dcache = self._device_call(
                "draft_prefill", self._dchunk_p, self._dparams,
                self._dcache, chunk_toks, np.int32(w), np.int32(off),
                table)
            off += w

    # ------------------------------------------------------- admission
    def _paged_match_and_allocate(self, prompt: np.ndarray, handle=None):
        """The shared front half of every admission (whole-prompt
        AND sliced): match → pin → allocate private suffix blocks →
        stamp the table row. ONE definition because the ordering is
        safety-critical — the pin must land BEFORE any allocation (with
        no private copy, an eviction stealing a matched block
        mid-admission would reach under this very request) and a
        shortfall must unwind pin + ids exactly. Degraded mode skips
        the index entirely (all blocks private), and so does every
        admission of a model with per-slot state (``_prefix_off``: a
        hit would restore its attention layers and not its state).
        Returns
        ``(pinned_node_or_None, n_matched_blocks, table_row [T],
        private_ids)``; raises :class:`_SlotStateLost` unwound on
        shortfall."""
        plen = int(prompt.size)
        bs = self.prefix_block_size
        table_row = np.zeros(self._table_width, np.int32)
        node, m = None, 0
        if self._host is not None:
            # Tiered admission: host-tier blocks promote into the pool
            # FIRST, so the match below pins the deeper chain in place
            # (self-unwinding; a promotion fault escalates exactly like
            # any admission dispatch).
            self._promote_host_chain(prompt, handle)
        if self._stateful:
            self.metrics.prefix_skipped_stateful += 1
        elif not self._degraded:
            match = self._prefix.match(
                prompt, max_blocks=self._match_blocks(prompt))
            m = match.n_blocks
            if m > 0:
                node = match.node
                self._prefix.pin(node)
                table_row[:m] = match.block_ids
            self._tracer.on_prefix_match(handle, m, m * bs)
        need = -(-plen // bs) - m
        private = list(self._prefix.allocate(need)) if need > 0 else []
        if len(private) < need:
            # Everything unpinned is already gone and it still doesn't
            # fit — undo and escalate; the unwind charges a replay.
            self._prefix.release(private)
            if node is not None:
                self._prefix.unpin(node)
            raise _SlotStateLost(
                "paged_alloc",
                RuntimeError(
                    f"block pool exhausted ({need} blocks needed, "
                    f"{len(private)} free/evictable)"))
        table_row[m:m + len(private)] = private
        return node, m, table_row, private

    def _prefill_paged(self, prompt: np.ndarray, handle=None, aid=0,
                       sid=0):
        """Prefill one prompt, reusing any cached prefix: a prefix hit
        PINS the matched chain and points the slot's block table at it
        in place (no gather copy), private blocks are allocated for the
        suffix, and the chunk programs write K/V straight into those
        pool blocks; then the prompt's full blocks are donated.
        ``handle`` is the admission's request (tracing only — each
        dispatch lands on its span); ``aid`` the tenant adapter pool
        row (0 = base model, ignored on a plain engine); ``sid`` the
        slot being filled (where per-slot state lands). Degraded mode
        (post-OOM cool-down) neither consults nor grows the cache — a
        pure cold chunked prefill, so serving continues while the pool
        stays shed. Returns
        ``(last_logits, pinned_node_or_None, table_row [T] np.int32,
        private_ids)``; raises :class:`_SlotStateLost` with its own
        resources unwound."""
        node, m, table_row, private = self._paged_match_and_allocate(
            prompt, handle)
        n_cached = m * self.prefix_block_size
        use_prefix = not self._prefix_off
        t1 = table_row[None]  # [1, T] — the chunk programs' view

        def _dispatch(site, prog, chunk_toks, w, off):
            self._cache, lg = self._device_call(
                site, prog, self._params, self._cache, chunk_toks,
                np.int32(w), np.int32(off), t1,
                *self._chunk_extra(aid, sid))
            self._record_state_start(off)
            return lg

        try:
            logits = self._chunk_loop(prompt, n_cached, handle, _dispatch)
            if self._draft_on:
                self._draft_prefill_loop(prompt, n_cached, t1)
        except _SlotStateLost:
            # Injected faults consumed nothing: hand the resources
            # back. A REAL consumed-pool error resets the whole paged
            # world right after (the unwind's _recover_consumed), which
            # retires this index anyway — releasing first is harmless.
            self._prefix.release(private)
            if node is not None:
                self._prefix.unpin(node)
            raise
        if n_cached > 0:
            self.metrics.record_copy_avoided(
                n_cached * self._kv_token_bytes)
        if use_prefix:
            node = self._donate_tail_paged(prompt, node, table_row,
                                           private, m)
            self.metrics.record_prefix_lookup(
                n_cached, blocks_live=self._prefix.blocks_live,
                evictions=self._prefix.evictions)
            self._record_prefix_reclaims()
        return logits, node, table_row, private

    def _donate_tail_paged(self, prompt: np.ndarray, node, table_row,
                           private: List[int], m: int):
        """Donation with ZERO copies: the prompt's full blocks are
        already written in the pool — hand their ownership to the radix
        index (they become the stored chain) and keep the slot's pin.
        When a chain segment is ALREADY stored (the block-aligned-tail
        case), the slot's table is SWAPPED onto the stored blocks —
        token-identity implies bit-identical KV under the
        position-absolute cache contract —
        and the duplicate private blocks go back to the free list, so a
        repeat prompt holds the pool at its deduplicated size. Returns
        the pinned chain tip (or ``node`` unchanged when the prompt has
        no full blocks)."""
        bs = self.prefix_block_size
        plen = len(prompt)
        full = plen // bs
        anchor = node if node is not None else self._prefix.match(
            prompt, max_blocks=0).node
        deeper, stored = self._prefix.descend(anchor, prompt, m)
        if stored > m:
            chain = self._prefix.chain_ids(deeper)
            for j in range(m, stored):
                mine = int(table_row[j])
                table_row[j] = chain[j]
                private.remove(mine)
                self._prefix.release([mine])
        if deeper is not anchor or node is None:
            if node is not None:
                self._prefix.unpin(node)
            self._prefix.pin(deeper)
        node = deeper
        if full > stored:
            ids = [int(table_row[j]) for j in range(stored, full)]
            tip = self._prefix.extend(
                node, prompt[stored * bs:full * bs], ids)
            chain = self._prefix.chain_ids(tip)
            for j in range(stored, full):
                # extend normally attaches our block; on a (defensive)
                # dedup it freed ours — swap the table either way.
                private.remove(int(table_row[j]))
                table_row[j] = chain[j]
            self._prefix.unpin(node)
            self._prefix.pin(tip)
            node = tip
        return node

    def _admit(self) -> None:
        if self._slice_tokens is not None:
            # The per-STEP prefill allowance: every chunk dispatched on
            # behalf of admissions this step draws from it, so the
            # decode tick below is never more than one allowance away.
            self._slice_budget_left = self._slice_tokens
        if self._slice is not None:
            # A prefill is mid-flight from an earlier step — advance it
            # first; only if it finishes (or settles) may new
            # admissions start.
            with self._admit_request_span(self._slice["handle"],
                                          self._slice["sid"]):
                settled = self._continue_slice()
            if not settled:
                return
        free = self._free_slot_ids()
        if not free:
            free = self._preempt_for_interactive()
            if not free:
                return

        def _queued_cancel(handle):
            handle.finish_s = self._clock()
            self.metrics.record_finish(FinishReason.CANCELLED.value,
                                       handle.request.priority.value)
            self._tracer.on_finish(handle, FinishReason.CANCELLED.value)

        def _queued_expired(handle):
            # Died in the queue, shed by the scheduler at pop time:
            # never pay its prefill (the most expensive dispatch) nor
            # emit a post-deadline token — under sustained overload
            # this is exactly where deadlines earn their keep. The
            # slot stays free for the next admission.
            handle.finish_s = self._clock()
            self.metrics.record_finish(FinishReason.DEADLINE.value,
                                       handle.request.priority.value)
            self._tracer.on_deadline_shed(handle)
            self._tracer.on_finish(handle, FinishReason.DEADLINE.value)

        # The suffix-priced (and adapter-load-priced, and spec-replay-
        # priced) cost_fn walks the radix tree per pop; only pay that
        # when a budget actually consumes the result.
        use_cost = self.scheduler.prefill_token_budget is not None
        # A kill mid-admission can leave a handle parked in
        # `_admitting`; it owns the first free slot before anything new
        # is popped.
        popped = self.scheduler.admit(
            len(free) - len(self._admitting), on_cancelled=_queued_cancel,
            on_expired=_queued_expired, now_fn=self._clock,
            cost_fn=self._prefill_cost if use_cost else None)
        if popped:
            now = self._clock()
            for handle in popped:
                # The scheduler's own wait, of FRESH requests only (a
                # replay's requeue is no queue progress).
                if handle.admit_s is None and not handle.tokens:
                    handle.admit_s = now
                    self.metrics.record_queue_pop(now - handle.arrival_s)
            self._admitting.extend(popped)
        while self._admitting and free:
            if (self._slice_tokens is not None
                    and self._slice_budget_left <= 0):
                break  # this step's prefill allowance is spent
            handle = self._admitting[0]
            sid = free.pop(0)
            try:
                with self._admit_request_span(handle, sid):
                    if self._slice_tokens is None:
                        self._admit_one(sid, handle)
                    elif not self._start_slice(sid, handle):
                        return  # pending: handle stays in _admitting
            except _SlotStateLost as lost:
                free.insert(0, sid)
                self._unwind_admission(lost, handle)
            self._admitting.popleft()

    def _admit_request_span(self, handle: RequestHandle, sid: int):
        """The ``pddl.serve.admit_request`` span of one admission (or
        of one slice of a time-sliced one)."""
        return jax.profiler.TraceAnnotation(
            _SPAN_PREFIX + "admit_request",
            request_id=handle.request.request_id,
            prompt_len=len(handle.request.prompt), slot=sid,
            replay=bool(handle.tokens))

    def _paged_append_blocks(self) -> None:
        """Before a tick: every live slot about to write at a
        block boundary gets a fresh PRIVATE block appended to its
        table (block-table growth is a runtime-array update, never a
        KV copy). Allocation LRU-evicts unpinned cached chains under
        pressure; with the pool at its validated floor it cannot fail
        for a live stream, but if a mis-sized explicit pool ever does,
        the slot is parked and REPLAYED rather than writing into a
        shared block."""
        # A speculative tick writes the whole verify window, positions
        # pos .. pos+spec_k: every block that extent touches must be
        # writable before the dispatch (writes past the table deflect
        # to scratch, so the extent clamps at the table edge).
        span = self._spec_k if self._spec_on else 0
        bs = self.prefix_block_size
        for sid, handle in enumerate(self._slots):
            if handle is None:
                continue
            lo = int(self._positions[sid]) // bs
            hi = min((int(self._positions[sid]) + span) // bs,
                     self._table_width - 1)
            need = [blk for blk in range(lo, hi + 1)
                    if blk >= 0 and self._tables[sid, blk] == 0]
            if not need:
                continue
            ids = self._prefix.allocate(len(need))
            if len(ids) < len(need):
                self._prefix.release(ids)
                self._park_slot(sid)
                if self._mark_replay(handle):
                    self.scheduler.requeue_front([handle])
                continue
            for blk, bid in zip(need, ids):
                self._tables[sid, blk] = bid
                self._private[sid].append(bid)
        self._record_prefix_reclaims()

    def _record_prefix_reclaims(self) -> None:
        self.metrics.record_prefix_reclaims(
            evictions=self._prefix.evictions,
            reclaims=self._prefix.reclaims,
            visited=self._prefix.reclaim_visited)

    def _preempt_for_interactive(self) -> List[int]:
        """Every slot is busy and ``interactive`` work is queued: park
        running BEST_EFFORT streams (fewest tokens first — the
        cheapest replay) and requeue them through the normal lane.
        The paused stream resumes token-exactly later via the replay
        admission (prompt re-prefilled, emitted tokens re-fed) — the
        fault-recovery machinery doing scheduling duty. A handle is
        preempted at most ``preempt_cap`` times, so a best_effort
        stream can stall under pressure but never thrash forever; only
        ACTUAL interactive submissions trigger this (aging promotions
        and replay-lane entries don't), so preemption cannot cascade.
        Returns the freed slot ids."""
        if self._preempt_cap < 1:
            return []
        want = self.scheduler.queued_of_class(Priority.INTERACTIVE)
        if want < 1:
            return []
        victims = sorted(
            ((sid, h) for sid, h in enumerate(self._slots)
             if h is not None
             and h.request.priority is Priority.BEST_EFFORT
             and h.preemptions < self._preempt_cap
             and not h.replay_pending),
            key=lambda p: len(p[1].tokens))
        freed: List[int] = []
        for sid, victim in victims[:want]:
            victim.preemptions += 1
            self.metrics.record_preemption()
            self._tracer.on_preempt(victim, self._cur_step)
            self._park_slot(sid)
            self.scheduler.requeue(victim)
            freed.append(sid)
        return freed

    def _unwind_admission(self, lost: _SlotStateLost,
                          handle: RequestHandle) -> None:
        """A dispatch died during this handle's admission. The
        per-request unwind already released any pin; the slot never
        became live. Rebuild what the failed dispatch consumed (the
        pool → fresh pool + index + live-slot replay; same shapes,
        nothing recompiles) and charge the request a replay."""
        sl, self._slice = self._slice, None
        if sl is not None:
            # A parked slice owns its adapter pin, its chain pin and its
            # private blocks (the whole-prompt path releases its own
            # before raising, and then self._slice was never set). The
            # adapter pool does NOT die with any KV rebuild, so that
            # pin must unwind exactly.
            self._release_adapter(sl.get("arow", 0))
            if sl.get("private"):
                self._prefix.release(sl["private"])
            if sl.get("node") is not None:
                self._prefix.unpin(sl["node"])
        self._recover_consumed(lost)
        if self._mark_replay(handle):
            self.scheduler.requeue_front([handle])

    def _admit_one(self, sid: int, handle: RequestHandle) -> None:
        """Admit one popped handle into slot ``sid`` (the whole-prompt
        path; the sliced path is :meth:`_start_slice`). Tenant order:
        the adapter pin + FSM compile land FIRST (so a cold load or an
        unresolvable spec unwinds before any prefill work), and a
        prefill failure releases the pin before escalating — the
        install step owns the pin from there (its own failure path
        releases, its success hands ownership to the slot)."""
        replay = bool(handle.tokens)
        self._tracer.on_admit(handle, sid, replay)
        prompt = np.asarray(handle.request.prompt, np.int32)
        arow, fsm = self._tenant_admit(handle)
        try:
            logits, node, table_row, private = self._prefill_paged(
                prompt, handle, arow, sid)
        except _SlotStateLost:
            self._release_adapter(arow)
            raise
        self._install_slot(sid, handle, logits, node, table_row, private,
                           arow=arow, fsm=fsm)

    # ------------------------------------------------ sliced admission
    def _start_slice(self, sid: int, handle: RequestHandle) -> bool:
        """Begin a time-sliced admission: match + pin + allocate now
        (host-only), then chunk-prefill under the per-step allowance.
        Returns True when the admission completed within this step's
        budget; False parks it in ``self._slice`` to resume next step
        — the decode tick runs in between, which is the whole point."""
        prompt = np.asarray(handle.request.prompt, np.int32)
        replay = bool(handle.tokens)
        self._tracer.on_admit(handle, sid, replay)
        arow, fsm = self._tenant_admit(handle)
        # Pin-ownership tracking: a failure BEFORE the slice dict
        # exists leaves the adapter pin with nobody else to unwind it;
        # once created, the slice (via `_unwind_admission`) or —
        # should the slice finish and the INSTALL fault — the install's
        # own failure path owns the release. `self._slice is None`
        # cannot distinguish "never created" from "created, finished,
        # install faulted" (both are None here), so track creation
        # explicitly or a refcount would underflow.
        created = False
        try:
            # Pin + allocate now (host-only, no gather dispatch — the
            # matched blocks are referenced in place); the pin is what
            # keeps the chain under this admission across the decode
            # ticks that run between slices.
            node, m, table_row, private = \
                self._paged_match_and_allocate(prompt, handle)
            n_cached = m * self.prefix_block_size
            self._slice = {"handle": handle, "sid": sid,
                           "prompt": prompt, "off": n_cached,
                           "n_cached": n_cached, "logits": None,
                           "node": node, "table": table_row,
                           "private": private, "arow": arow,
                           "fsm": fsm}
            created = True
            return self._advance_slice(self._slice)
        except _SlotStateLost:
            if not created:
                self._release_adapter(arow)
            raise

    def _continue_slice(self) -> bool:
        """Resume the parked prefill. Returns True when ``self._slice``
        settled (installed, expired, cancelled, or unwound) — admission
        may continue — and False while it still has chunks to go."""
        sl = self._slice
        handle = sl["handle"]
        now = self._clock()
        if handle.cancelled or self._expired(handle, now):
            # Not in a slot yet, so _reap cannot see it: settle here.
            # The partially-prefilled private blocks return to the free
            # list, where their junk is unreachable until reallocated
            # and fully rewritten.
            if sl.get("private"):
                self._prefix.release(sl["private"])
            if sl.get("node") is not None:
                self._prefix.unpin(sl["node"])
            self._release_adapter(sl.get("arow", 0))
            self._slice = None
            if handle.cancelled:
                handle.state = RequestState.CANCELLED
                handle.finish_reason = FinishReason.CANCELLED
            else:
                handle.state = RequestState.TIMED_OUT
                handle.finish_reason = FinishReason.TIMED_OUT
            handle.finish_s = now
            self.metrics.record_finish(handle.finish_reason.value,
                                       handle.request.priority.value)
            self._tracer.on_finish(handle, handle.finish_reason.value)
            self._admitting.popleft()
            return True
        try:
            done = self._advance_slice(sl)
        except _SlotStateLost as lost:
            self._unwind_admission(lost, handle)
            self._admitting.popleft()
            return True
        if done:
            self._admitting.popleft()
        return done

    def _advance_slice(self, sl: Dict[str, object]) -> bool:
        """Dispatch narrow suffix chunks until the prompt is fully
        prefilled or the step's allowance runs out (always at least one
        chunk — progress is guaranteed). The wide program is never used
        here: one huge dispatch is exactly the head-of-line block
        slicing exists to break up."""
        handle, prompt = sl["handle"], sl["prompt"]
        plen = int(prompt.size)
        spent = 0
        while sl["off"] < plen:
            if spent and self._slice_budget_left <= 0:
                return False
            off = int(sl["off"])
            w = min(self._chunk, plen - off)
            chunk_toks = np.zeros((1, self._chunk), np.int32)
            chunk_toks[0, :w] = prompt[off:off + w]
            extra = self._chunk_extra(sl.get("arow", 0), sl["sid"])
            self._cache, sl["logits"] = self._device_call(
                "chunk_prefill", self._chunk_p, self._params,
                self._cache, chunk_toks, np.int32(w), np.int32(off),
                sl["table"][None], *extra)
            self._record_state_start(off)
            if self._draft_on:
                # The draft tree advances in lockstep with the
                # slices (same chunk, same blocks), so fairness and
                # the budget charge stay one number per slice.
                self._dcache = self._device_call(
                    "draft_prefill", self._dchunk_p, self._dparams,
                    self._dcache, chunk_toks, np.int32(w),
                    np.int32(off), sl["table"][None])
            self.metrics.record_prefill_chunk(
                self._chunk, off if self._reexpands else 0)
            self._tracer.on_prefill_chunk(handle, "chunk_prefill", off, w,
                                          self._last_wall_s)
            sl["off"] = off + w
            spent += w
            self._slice_budget_left -= w
        self._finish_slice(sl)
        return True

    def _finish_slice(self, sl: Dict[str, object]) -> None:
        """The prompt is fully in the pool: donate/pin, then install
        the slot exactly like the whole-prompt path."""
        handle, sid = sl["handle"], sl["sid"]
        prompt = sl["prompt"]
        node = sl["node"]
        if int(sl["n_cached"]) > 0:
            # Recorded at FINISH like the whole-prompt path, so a
            # mid-slice unwind + replay can never double-count.
            self.metrics.record_copy_avoided(
                int(sl["n_cached"]) * self._kv_token_bytes)
        if not self._prefix_off:
            # The start-time pin survived the interleaved ticks
            # (flush_unpinned spares pinned chains), so donation
            # descends from it directly. While degraded, the
            # matched blocks stay pinned-but-undonated: the table
            # references them in place, so the pin must outlive
            # the slot either way.
            node = self._donate_tail_paged(
                prompt, node, sl["table"], sl["private"],
                int(sl["n_cached"]) // self.prefix_block_size)
            self.metrics.record_prefix_lookup(
                int(sl["n_cached"]),
                blocks_live=self._prefix.blocks_live,
                evictions=self._prefix.evictions)
            self._record_prefix_reclaims()
        self._slice = None
        self._install_slot(sid, handle, sl["logits"], node, sl["table"],
                           sl["private"], arow=sl.get("arow", 0),
                           fsm=sl.get("fsm"))

    def _install_slot(self, sid: int, handle: RequestHandle, logits,
                      node, table_row, private, arow=0,
                      fsm=None) -> None:
        """Make a fully-prefilled prompt live in slot ``sid``. Two shapes:
        a FRESH request samples its first token from the prefill logits
        (that's TTFT); a REPLAYED one (``handle.tokens`` non-empty —
        fault recovery or drain/restore) rebuilt its KV from the
        prompt and re-feeds the emitted tokens through the coming
        ticks, so no token is ever re-sampled or double-streamed.

        The KV is already where it lives (the pool blocks
        ``table_row`` names, ``private`` the ones this slot owns), so
        there is no insert dispatch at all — installation is the
        host-side table stamp.

        Tenant mode passes ``arow`` (the admission's pinned adapter
        pool row — ownership transfers to the slot here, or is
        released on this method's own failure) and ``fsm`` (the
        compiled constraint automaton): a fresh request samples its
        first token under the FSM's start-state mask and advances; a
        replayed one RE-DERIVES its FSM state from the emitted tokens
        (state, like KV, is a pure function of the stream)."""
        req = handle.request
        plen = len(req.prompt)
        replay = bool(handle.tokens)
        t, k, p = req.sampling.as_arrays()
        fsm_state = None
        if fsm is not None and replay:
            try:
                fsm_state = fsm.advance_many(handle.tokens,
                                             eos_token=self.eos_token)
            except ValueError as e:
                # A replayed stream the automaton rejects (corrupted
                # migration mirror): fail the REQUEST via the replay
                # budget, never the engine.
                self._release_adapter(arow)
                if private:
                    self._prefix.release(private)
                if node is not None:
                    self._prefix.unpin(node)
                raise _SlotStateLost("constraint_admit", e) from e
        try:
            if replay:
                first = handle.tokens[0]
                handle.replay_pending = list(handle.tokens[1:])
            else:
                tok, self._rng = self._device_call(
                    "sample_first", self._sample_first_p, logits,
                    *self._first_mask_args(fsm),
                    np.float32(t), np.int32(k), np.float32(p), self._rng)
                self.metrics.record_sampled(t, k, p, tick=False)
                with self._phase["first_token_wait"]:
                    first = int(tok[0])
        except _SlotStateLost:
            if private:
                self._prefix.release(private)
            if node is not None:
                self._prefix.unpin(node)
            self._release_adapter(arow)
            raise
        self._tables[sid] = table_row
        self._private[sid] = list(private)
        self._slot_nodes[sid] = node
        if not replay:
            now = self._clock()
            handle.tokens.append(first)
            handle.ttft_s = now - handle.arrival_s
            self.metrics.record_first_token(
                handle.ttft_s, handle.request.priority.value)
            self.metrics.record_admission(now, now - handle.admit_s,
                                          prompt_tokens=plen)
            self._tracer.on_first_token(handle, handle.ttft_s)
        self._slots[sid] = handle
        self._positions[sid] = plen
        self._tokens[sid] = first
        self._temps[sid] = t
        self._top_ks[sid] = k
        self._top_ps[sid] = p
        if self._spec_on:
            # The drafter's token history: prompt + every emitted token
            # (one for a fresh admission, the full stream for a replay
            # — whose re-feed then drafts from complete history). The
            # row is zeroed first so a previous tenant's tail can never
            # leak into an n-gram match.
            self._hist[sid, :] = 0
            self._hist[sid, :plen] = np.asarray(req.prompt, np.int32)
            n = min(len(handle.tokens), self.model.max_len - plen)
            if n > 0:
                self._hist[sid, plen:plen + n] = handle.tokens[:n]
        if self._tenant_on:
            # The slot now owns the adapter pin (released at park) and
            # the grammar state/mask row the coming ticks read.
            self._arow[sid] = arow
            if fsm is not None:
                if not replay:
                    if self.eos_token is not None \
                            and first == self.eos_token:
                        fsm_state = fsm.start  # evicted as EOS below
                    else:
                        fsm_state = fsm.advance(fsm.start, first)
                        if fsm_state < 0:  # masked sample: impossible
                            raise RuntimeError(
                                "constrained first token escaped its "
                                "start-state mask (engine bug)")
                self._fsms[sid] = (fsm, fsm_state)
                self._masks[sid] = fsm.allow_row(fsm_state,
                                                 self.eos_token)
                self._masks_dirty = True
            else:
                self._fsms[sid] = None
                if not self._masks[sid].all():
                    self._masks[sid, :] = True
                    self._masks_dirty = True
        if replay:
            # Finish conditions were already evaluated for every
            # re-fed token before the fault — except possibly the LAST:
            # a fleet-migrated mirror can carry a token its dying
            # replica emitted without living to evict on, so re-check
            # the live edge alone (an in-engine replay can never be
            # complete — eviction beat it to the snapshot) or the first
            # post-replay tick samples one token past the stream's end.
            # Constrained streams add the grammar edge: a migrated
            # stream whose automaton has no continuation is COMPLETE.
            if (self.eos_token is not None
                    and handle.tokens[-1] == self.eos_token):
                self._evict(sid, RequestState.FINISHED, FinishReason.EOS)
            elif fsm is not None and fsm_state is not None \
                    and fsm.is_dead_end(fsm_state, self.eos_token):
                self._evict(sid, RequestState.FINISHED,
                            FinishReason.GRAMMAR)
            elif len(handle.tokens) >= req.max_new_tokens:
                self._evict(sid, RequestState.FINISHED,
                            FinishReason.LENGTH)
            return
        # A one-token request (or an immediate eos / an immediately
        # complete grammar) finishes at admission without ever joining
        # a tick.
        if self.eos_token is not None and first == self.eos_token:
            self._evict(sid, RequestState.FINISHED, FinishReason.EOS)
        elif fsm is not None and fsm.is_dead_end(fsm_state,
                                                 self.eos_token):
            self._evict(sid, RequestState.FINISHED, FinishReason.GRAMMAR)
        elif req.max_new_tokens == 1:
            self._evict(sid, RequestState.FINISHED, FinishReason.LENGTH)

    # ------------------------------------------------- speculative tick
    def _dispatch_draft(self, forced_tok, forced_n):
        """Dispatch the draft program; returns its ``[S, spec_k]``
        drafts, still on the device (the caller reads them inside its
        ``tick_wait`` phase).

        A draft failure is NEVER fatal to the streams: when the retry
        budget runs out without a consumed buffer (injected faults, or
        the weightless n-gram program, which donates nothing), the tick
        falls back to repeat-last-token drafts — the n-gram drafter's
        own no-match fallback — and pays acceptance, not correctness
        (verification is the oracle either way). Only a REAL error that
        may have consumed the donated draft tree escalates, and then it
        recovers exactly like a consumed pool: full live-slot replay."""
        try:
            if self._draft_on:
                self._dcache, drafts = self._device_call(
                    "draft", self._draft_model_p, self._dparams,
                    self._dcache, self._positions, self._tables,
                    self._tokens, forced_tok, forced_n)
            else:
                drafts = self._device_call(
                    "draft", self._draft_p, self._hist, self._positions)
            return drafts
        except _SlotStateLost as lost:
            if lost.consumed is not None:
                raise
            return np.repeat(self._tokens[:, None], self._spec_k, axis=1)

    def _grammar_draft_walk(self, sid: int, fsm_entry, drafts_row):
        """Walk one constrained slot's FSM along its draft path: stamp
        the per-position allow masks the verify program samples under,
        and return the accept cap — the longest draft prefix that is a
        legal continuation (an allowed eos draft is itself acceptable
        and ends the walk; everything past an illegal draft is
        discarded by the cap, so its masks stay pass-through). The
        slot's LIVE FSM state is untouched here: it advances by the
        ACCEPTED length only, token by token, in the window loop."""
        fsm, state = fsm_entry
        mw = self._masks_w
        mw[sid, 0] = self._masks[sid]
        cap = 0
        for j in range(1, self._spec_k + 1):
            d = int(drafts_row[j - 1])
            if not mw[sid, j - 1][d]:
                mw[sid, j:, :] = True
                break
            if self.eos_token is not None and d == self.eos_token:
                cap = j  # an accepted eos finishes the stream in-window
                mw[sid, j:, :] = True
                break
            state = fsm.advance(state, d)
            mw[sid, j] = fsm.allow_row(state, self.eos_token)
            cap = j
        self._masks_w_dirty = True
        return cap

    def _spec_tick(self, cur: int, live) -> int:
        """One speculative fused step: draft → ONE batched verify over
        the ``[S, spec_k+1]`` window → host-side accept/evict. Returns
        tokens emitted (replay re-feeds emit nothing but advance up to
        ``spec_k+1`` known tokens per window). Raises
        :class:`_SlotStateLost` to the caller exactly like the plain
        tick — the caller's recovery is identical."""
        ph = self._phase
        with ph["tick_dispatch"]:
            forced_tok, forced_n = self._spec_forced(live)
            drafts = self._dispatch_draft(forced_tok, forced_n)
        with ph["tick_wait"]:
            drafts = np.asarray(drafts)
        with ph["tick_dispatch"]:
            win, acc, caps, drafted_tick = self._spec_verify(
                live, drafts, forced_tok, forced_n)
        with ph["tick_wait"]:
            win = np.asarray(win)  # per-tick host sync (streaming)
            acc = np.asarray(acc)
        with ph["emit"]:
            new_tokens, accepted_tick = self._spec_emit(
                cur, live, win, acc, caps, forced_n)
        self.metrics.record_spec_tick(drafted_tick, accepted_tick)
        return new_tokens

    def _spec_forced(self, live):
        """The window's forced re-feeds: per replaying slot, the known
        tokens to push through it (``forced_tok``) and how many
        (``forced_n``; -1 = not replaying)."""
        s, k = self.max_slots, self._spec_k
        forced_tok = np.zeros((s, k), np.int32)
        forced_n = np.full(s, -1, np.int32)
        for sid in live:
            pend = self._slots[sid].replay_pending
            if pend:
                # Re-feed known tokens through the window, leaving the
                # LAST one as next tick's cur (the non-spec invariant:
                # the live edge's K/V is written by the tick that
                # samples past it).
                j = min(k, len(pend) - 1)
                if j > 0:
                    forced_tok[sid, :j] = pend[:j]
                forced_n[sid] = j
        return forced_tok, forced_n

    def _spec_verify(self, live, drafts, forced_tok, forced_n):
        """Build the ``[S, spec_k+1]`` window and the accept caps from
        the drafts, and dispatch the ONE batched verify. Returns the
        window and accept counts (still on the device), the caps and
        the draft tokens offered."""
        s, k = self.max_slots, self._spec_k
        block = np.zeros((s, k + 1), np.int32)
        block[:, 0] = self._tokens
        block[:, 1:] = drafts
        caps = np.zeros(s, np.int32)
        drafted_tick = 0
        for sid in live:
            if forced_n[sid] >= 0:
                block[sid, 1:] = 0
                if forced_n[sid] > 0:
                    block[sid, 1:1 + int(forced_n[sid])] = \
                        forced_tok[sid, :int(forced_n[sid])]
                continue
            if self._temps[sid] > 0:
                # Sampled streams tick one exact token (cap 0): the
                # rejection-sampling verifier stays on the one-shot
                # path; serving exactness comes first. A CONSTRAINED
                # sampled row still draws that token under its FSM mask
                # — the plain tick's pre-masking, lifted to position 0
                # of the window (an unmasked draw could emit an illegal
                # token and crash the host FSM advance for everyone).
                if self._tenant_on and self._fsms[sid] is not None:
                    self._masks_w[sid, 0] = self._masks[sid]
                    self._masks_w[sid, 1:, :] = True
                    self._masks_w_dirty = True
                continue
            fsm_entry = self._fsms[sid] if self._tenant_on else None
            if fsm_entry is None:
                caps[sid] = k
            else:
                caps[sid] = self._grammar_draft_walk(sid, fsm_entry,
                                                     block[sid, 1:])
            drafted_tick += int(caps[sid])
        self._cache, win, acc, self._rng = self._device_call(
            "verify", self._verify_p, self._params, self._cache,
            self._positions, self._tables, block, self._temps,
            self._top_ks, self._top_ps, *self._verify_extra(),
            caps, forced_n, self._rng)
        self.metrics.record_decode_tick()
        self.metrics.record_sampled(self._temps, self._top_ks, self._top_ps,
                                    tick=True)
        return win, acc, caps, drafted_tick

    def _spec_emit(self, cur: int, live, win, acc, caps, forced_n):
        """The host half of a verify window: per live slot, append
        the accepted tokens, advance FSMs and positions, evict the
        finished. Returns ``(tokens emitted, draft tokens
        accepted)``."""
        w_width = self._spec_k + 1
        new_tokens = 0
        accepted_tick = 0
        for sid in live:
            handle = self._slots[sid]
            if forced_n[sid] >= 0:
                j = int(forced_n[sid])
                del handle.replay_pending[:j]
                self._positions[sid] += j + 1
                self._tokens[sid] = handle.replay_pending.pop(0)
                continue
            n_emit = int(acc[sid]) + 1
            if caps[sid] > 0:
                accepted_tick += int(acc[sid])
                handle.spec_drafted += int(caps[sid])
                handle.spec_accepted += int(acc[sid])
            pos0 = int(self._positions[sid])
            # Write the WHOLE window into the drafter's history — the
            # rejected tail beyond the accepted length included, exactly
            # like the one-shot loop's token buffer. The tail is the
            # model's own next-token predictions: an n-gram continuation
            # that crosses the live edge then reads informed guesses
            # instead of zeros (zeros collapsed acceptance on looping
            # streams, found here), and the next window's write covers
            # the whole stale extent before the edge can reach it —
            # junk beyond the edge stays junk-safe, verification is
            # still the only oracle.
            end = min(pos0 + 1 + w_width, self._hist.shape[1])
            if end > pos0 + 1:
                self._hist[sid, pos0 + 1:end] = win[sid, :end - pos0 - 1]
            evicted = False
            for j in range(n_emit):
                tok = int(win[sid, j])
                handle.tokens.append(tok)
                new_tokens += 1
                self._tracer.on_token(handle, cur)
                fsm_entry = (self._fsms[sid] if self._tenant_on
                             else None)
                if self.eos_token is not None and tok == self.eos_token:
                    # Tokens past an in-window eos were never emitted —
                    # the loop stops here, exactly where the
                    # non-speculative stream would have stopped.
                    self._evict(sid, RequestState.FINISHED,
                                FinishReason.EOS)
                    evicted = True
                    break
                if fsm_entry is not None:
                    fsm, state = fsm_entry
                    state = fsm.advance(state, tok)
                    if state < 0:  # masked sample: impossible
                        raise RuntimeError(
                            "constrained token escaped its state mask "
                            "(engine bug)")
                    self._fsms[sid] = (fsm, state)
                    if fsm.is_dead_end(state, self.eos_token):
                        self._evict(sid, RequestState.FINISHED,
                                    FinishReason.GRAMMAR)
                        evicted = True
                        break
                    if len(handle.tokens) >= \
                            handle.request.max_new_tokens:
                        self._evict(sid, RequestState.FINISHED,
                                    FinishReason.LENGTH)
                        evicted = True
                        break
                    self._masks[sid] = fsm.allow_row(state,
                                                     self.eos_token)
                    self._masks_dirty = True
                elif len(handle.tokens) >= \
                        handle.request.max_new_tokens:
                    self._evict(sid, RequestState.FINISHED,
                                FinishReason.LENGTH)
                    evicted = True
                    break
            if not evicted:
                self._positions[sid] += n_emit
                self._tokens[sid] = int(win[sid, n_emit - 1])
        return new_tokens, accepted_tick

    def step(self) -> int:
        """One engine tick: (drain check) → reap → admit → one fused
        decode tick for all live slots → evict finished. Returns tokens
        emitted this step (admission first-tokens included; replay
        re-feeds emit nothing — those tokens were already streamed).
        With ``spec_k > 0`` the decode tick is the speculative
        draft/verify window (:meth:`_spec_tick`) and may emit up to
        ``spec_k + 1`` tokens per slot. After a drain this is a no-op
        returning 0."""
        if not self._warm:
            self.warmup()
        if self._drain_flag and not self._drained:
            # SIGTERM arrived (flag set by the async-signal-safe
            # handler): snapshot and stop at this step boundary — the
            # serving analog of PreemptionCheckpoint's batch-boundary
            # save.
            self.drain(self._drain_path)
        if self._drained:
            return 0
        # The current step coordinate: the fault plan, the trace
        # events, and the telemetry-ring record all stamp this value,
        # so an injected fault and its observed recovery line up on
        # identical (step, site) coordinates.
        cur = self._step_idx
        self._cur_step = cur
        if self._faults is not None:
            self._faults.on_step(cur)
        self._step_idx = cur + 1
        self._site_wall = {}
        wall = self._phase_wall
        for name in wall:
            wall[name] = 0.0
        t_step = time.perf_counter()
        with jax.profiler.StepTraceAnnotation(_SPAN_PREFIX + "step",
                                              step_num=cur):
            return self._step_phases(cur, t_step)

    def _step_phases(self, cur: int, t_step: float) -> int:
        """The body of :meth:`step`, phase by phase (`metrics.PHASES`).
        Every host<-device read of the step sits in a ``*_wait``
        phase."""
        ph = self._phase
        retries_before = self.metrics.retries
        compiles_before = self.metrics.compiles_counted
        t0 = self._clock()
        emitted_before = self.metrics.tokens_emitted
        with ph["reap"]:
            self._maybe_rearm_degraded()
            self._reap()
        with ph["admit"]:
            self._admit()
        with ph["append_blocks"]:
            self._paged_append_blocks()
        live = [i for i, s in enumerate(self._slots) if s is not None]
        new_tokens = 0
        if live and self._spec_on:
            try:
                new_tokens = self._spec_tick(cur, live)
            except _SlotStateLost:
                # The verify window donates the resident tree exactly
                # like the tick did (and a consumed draft tree shares
                # the pool's fate): every live slot replays.
                self._lose_live_slots()
        elif live:
            nxt = None
            try:
                with ph["tick_dispatch"]:
                    self._cache, nxt, self._rng = self._device_call(
                        "tick", self._tick_p, *self._tick_args())
                self.metrics.record_decode_tick()
                self.metrics.record_sampled(self._temps, self._top_ks,
                                            self._top_ps, tick=True)
            except _SlotStateLost:
                self._lose_live_slots()
            if nxt is not None:
                with ph["tick_wait"]:
                    nxt = np.asarray(nxt)  # per-tick host sync (streaming)
                with ph["emit"]:
                    new_tokens = self._emit_tick(cur, live, nxt)
        with ph["account"]:
            now = self._clock()
            self.metrics.record_tick(
                now, self.scheduler.depth, len(live), self.max_slots,
                new_tokens, now - t0)
            self.metrics.record_paged_gauges(self.block_table_fill)
            emitted = self.metrics.tokens_emitted - emitted_before
            # The one per-step record: the ring keeps it, the tracer is
            # handed the same object (`RequestTracer(emit_ticks=True)`
            # writes it to its sink).
            record = {
                "step": cur, "t_s": now,
                "queue_depth": self.scheduler.depth,
                "live_slots": len(live), "tokens": emitted,
                "tick_wall_s": now - t0,
                "retries": self.metrics.retries - retries_before,
                "compiles": self.metrics.compiles_counted - compiles_before,
                "degraded": self._degraded,
                "site_wall_s": self._site_wall,
            }
        # The step's wall ends with ``account``, whose time the record
        # carries: handing the record on is the step's last act.
        phase_wall = dict(self._phase_wall)
        self.metrics.record_step(time.perf_counter() - t_step, phase_wall)
        record["phase_wall_s"] = phase_wall
        self.telemetry.append(record)
        self._tracer.on_tick(record)
        return emitted

    def _emit_tick(self, cur: int, live, nxt) -> int:
        """The host half of a decode tick: per live slot, append the
        sampled token (or re-feed a replay's next known one), advance
        FSMs and positions, evict the finished. Returns tokens emitted."""
        new_tokens = 0
        for sid in live:
            handle = self._slots[sid]
            if handle.replay_pending:
                # Rebuilding lost KV: the tick just re-wrote
                # this row's next known token — feed the
                # following one, discard the sampled output
                # (the caller already has these tokens).
                self._tokens[sid] = handle.replay_pending.pop(0)
                self._positions[sid] += 1
                continue
            tok = int(nxt[sid])
            handle.tokens.append(tok)
            new_tokens += 1
            self._positions[sid] += 1
            self._tokens[sid] = tok
            self._tracer.on_token(handle, cur)
            fsm_entry = (self._fsms[sid] if self._tenant_on
                         else None)
            if self.eos_token is not None and tok == self.eos_token:
                # For a constrained slot the mask only ever
                # allows eos in an ACCEPTING state, so this is
                # simultaneously grammar acceptance.
                self._evict(sid, RequestState.FINISHED,
                            FinishReason.EOS)
            elif fsm_entry is not None:
                fsm, state = fsm_entry
                state = fsm.advance(state, tok)
                if state < 0:  # masked sample: impossible
                    raise RuntimeError(
                        "constrained token escaped its state "
                        "mask (engine bug)")
                self._fsms[sid] = (fsm, state)
                if fsm.is_dead_end(state, self.eos_token):
                    # No legal continuation: the output is a
                    # complete document (see FinishReason).
                    self._evict(sid, RequestState.FINISHED,
                                FinishReason.GRAMMAR)
                elif len(handle.tokens) >= \
                        handle.request.max_new_tokens:
                    self._evict(sid, RequestState.FINISHED,
                                FinishReason.LENGTH)
                else:
                    self._masks[sid] = fsm.allow_row(
                        state, self.eos_token)
                    self._masks_dirty = True
            elif len(handle.tokens) >= handle.request.max_new_tokens:
                self._evict(sid, RequestState.FINISHED,
                            FinishReason.LENGTH)
        return new_tokens

    def run(self, max_steps: Optional[int] = None) -> None:
        """Drive ``step()`` until queue and slots drain (or the step
        budget runs out) — the synchronous serving loop."""
        steps = 0
        while self.has_work:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break

    # ----------------------------------------------------- drain/restore
    def install_drain_handler(self, path: Optional[str] = None,
                              signals=(signal.SIGTERM,)) -> None:
        """Arm checkpoint-on-SIGTERM for the serving side (the analog of
        `utils/preemption.PreemptionCheckpoint`): the handler only sets
        a flag (async-signal-safe); the actual :meth:`drain` — snapshot
        to ``path``, stop admission — happens at the next ``step()``
        boundary on the serving thread, so the snapshot is a consistent
        request set, never a torn mid-dispatch capture."""
        self._drain_path = path

        def _on_signal(signum, frame):  # flag only: async-signal-safe
            self._drain_flag = True

        for sig in signals:
            self._prev_handlers[sig] = signal.signal(sig, _on_signal)

    def uninstall_drain_handler(self) -> None:
        """Put the previous signal handlers back (tests; in production
        the process exits after the drain)."""
        for sig, old in self._prev_handlers.items():
            signal.signal(sig, old)
        self._prev_handlers.clear()

    def drain(self, path: Optional[str] = None) -> Dict[str, object]:
        """Snapshot every in-flight request's host state and stop.

        Running slots first (FCFS owes them the earliest re-admission),
        then any handle caught mid-admission, then the queue — each as
        (prompt, tokens generated so far, sampling params, remaining
        deadline budget). No device state is saved: KV is a pure
        function of (params, tokens) and the restore path recomputes it
        token-exactly via the replay machinery. Idempotent; with
        ``path`` the snapshot is also written atomically
        (`serve/drain.py`). After the drain the engine admits nothing
        and ``step()`` is a no-op."""
        if self._drained:
            return self._snapshot
        now = self._clock()
        # Slot index is reuse order, not arrival order — sort so the
        # restore really does re-admit the oldest stream first.
        handles = sorted((h for h in self._slots if h is not None),
                         key=lambda h: h.arrival_s)
        handles.extend(self._admitting)
        handles.extend(self.scheduler.drain())
        # Each running slot's block table goes into the v3+
        # snapshot — postmortem context (which pool blocks the
        # stream occupied, how much was shared), never a restore input:
        # pool storage dies with the process and the restore path
        # rebuilds KV via replay exactly like a v2 snapshot.
        tables = {}
        for sid, h in enumerate(self._slots):
            if h is not None:
                row = self._tables[sid]
                tables[id(h)] = [int(b) for b in row[row != 0]]
        self._snapshot = {
            "version": drain_io.SNAPSHOT_VERSION,
            "drained_unix_s": time.time(),
            "paged": True,
            # v5: the drafting config the streams ran under — postmortem
            # context (restore replays token-exactly into ANY engine,
            # speculative or not; KV and FSM state are pure functions of
            # the tokens, and so is every drafter).
            "spec_k": self._spec_k,
            "requests": [drain_io.encode_handle(h, now,
                                                block_table=tables.get(id(h)))
                         for h in handles],
            # Last-moments telemetry (`obs/ring.py` summary): what the
            # engine looked like going down — postmortem context the
            # restore path ignores (`serve/drain.py`).
            "telemetry": self.telemetry.summary(),
        }
        self._tracer.on_drain(self._cur_step, len(handles))
        self._drained = True
        self._drain_flag = True
        if path is not None:
            drain_io.save_snapshot(self._snapshot, path)
        return self._snapshot

    def restore(self, source) -> List[RequestHandle]:
        """Resubmit a drain snapshot (dict or path) into THIS engine —
        call on a fresh engine with the same model/config. Requests
        that were running resume token-exactly: their handles re-enter
        the queue with tokens-so-far attached, and replay admission
        rebuilds each one's KV from prompt + tokens before the stream
        continues (``handle.tokens`` of the returned handles already
        contains the pre-drain tokens, so a completed restore holds
        each request's FULL stream). Depth limits don't apply — every
        one of these was already admitted once. Returns the new
        handles in service order."""
        if isinstance(source, str):
            source = drain_io.load_snapshot(source)
        handles = drain_io.restored_handles(source, self._clock())
        if not self._tenant_on:
            # A tenant stream restored onto a plain engine would
            # silently serve the BASE model (wrong weights, no mask) —
            # refuse loudly instead. v1-v3 snapshots carry neither
            # field, so every pre-tenant snapshot restores here
            # unchanged.
            bad = [h for h in handles
                   if h.request.adapter is not None
                   or h.request.constraint is not None]
            if bad:
                raise ValueError(
                    f"snapshot carries {len(bad)} tenant request(s) "
                    "(adapter/constraint) but this engine has no "
                    "tenant=TenantConfig(...)")
        self.scheduler.restore(handles)
        for h in handles:
            # Open a span for each resumed stream — without this, a
            # migrated/hand-off stream's decode side traces nothing.
            self._tracer.on_restored(h, len(h.tokens))
        return handles

    # ------------------------------------------- cross-replica transfer
    def export_prefix_chain(self, tokens,
                            max_blocks: Optional[int] = None):
        """The replica-to-replica prefix-transfer EXPORT (ISSUE 13):
        the longest cached chain for ``tokens`` — device radix match
        first (read D2H in one batched eager gather, the same read
        demotion uses), host-tier blocks extending it (already host
        arrays, no transfer) — as a `serve/drain.py` chain wire entry
        (:func:`~pddl_tpu.serve.drain.kv_chain_to_wire`). ``None``
        when nothing is cached, the HOST
        TIER is off (the D2H read rides the tier's jitted gather, and
        a tier-less replica could not receive a peer's chain either —
        exporting is a tiered-fleet feature), or the engine is
        degraded (exporting from a shed cache would race the flush).
        The matched chain is pinned for exactly the read."""
        self._refuse_chain_transfer()
        if self._host is None or self._degraded or self._drained:
            return None
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        cap = self._match_blocks(tokens)
        if max_blocks is not None:
            cap = min(cap, int(max_blocks))
        if cap < 1:
            return None
        match = self._prefix.match(tokens, max_blocks=cap)
        m = match.n_blocks
        blocks: List[Dict[str, np.ndarray]] = []
        if m > 0:
            self._prefix.pin(match.node)
            try:
                blocks = self._gather_blocks_host(match.block_ids)
            except Exception as e:  # noqa: BLE001 - device faults only
                if classify(e) is None:
                    raise
                blocks = []  # failed D2H: export nothing
            finally:
                self._prefix.unpin(match.node)
            if len(blocks) < m:
                return None
        if m < cap:  # the top guard ensured the tier is armed
            tip = self._host.pin_chain(tokens, m, cap - m)
            if tip is not None:
                try:
                    blocks.extend(
                        self._host.chain_data(tip, tip.depth - m))
                finally:
                    self._host.unpin(tip)
        if not blocks:
            return None
        bs = self.prefix_block_size
        return drain_io.kv_chain_to_wire(
            [int(t) for t in tokens[:len(blocks) * bs]], blocks)

    def _refuse_chain_transfer(self) -> None:
        if self._stateful:
            raise NotImplementedError(
                "a prefix chain carries K/V blocks only: a model that "
                "keeps per-slot state (short-convolution layers) cannot "
                "be resumed from one, and this engine caches no prefix "
                "for it (ROADMAP M5)")

    def import_prefix_chain(self, entry) -> int:
        """The transfer IMPORT: decoded chain blocks enter the HOST
        TIER (no device work on the routing path — the next admission
        for the prefix promotes them H2D through the normal
        budget-charged ``host_promote`` path, so a pulled chain pays
        admission exactly what a locally-spilled one pays). Payloads
        failing this engine's leaf spec are refused block-by-block
        (`HostTierCache.store` validates). Returns blocks stored;
        0 with the tier disabled."""
        self._refuse_chain_transfer()
        if self._host is None:
            return 0
        tokens, blocks = drain_io.kv_chain_from_wire(entry)
        bs = self.prefix_block_size
        stored = 0
        for j, data in enumerate(blocks):
            chain = tokens[:(j + 1) * bs]
            if len(chain) < (j + 1) * bs:
                break
            if self._host.has_block(chain):
                continue
            if not self._host.store(chain, data):
                break  # refused (spec mismatch / budget): a hole here
                #        would end every deeper block's promotability
            stored += 1
            self.metrics.record_host_spill(self._host.bytes_resident)
        return stored
