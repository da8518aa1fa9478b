"""Request lifecycle for the online serving engine.

The reference's endpoint is "save the model, then serve it"
(`/root/reference/imagenet-resnet50.py:72`); the batch serving story
(`docs/SERVING.md`) measured the single-request path. This module is
the per-request half of the ONLINE layer: what a caller submits, the
states a request moves through, and the handle it streams tokens from.

Design constraints, inherited from the engine:

- The engine is single-threaded and caller-driven (``engine.step()``),
  so handles need no locking — cancellation is a flag the engine
  honors at its next tick, not a cross-thread interrupt.
- Sampling parameters are PER-REQUEST runtime values (batched into
  ``[slots]`` arrays each tick), never compiled statics — hence the
  array sentinels on :class:`SamplingParams`.
"""

from __future__ import annotations

import dataclasses
import enum
import itertools
from typing import List, Optional, Sequence


class Priority(enum.Enum):
    """SLO class of a request — the scheduler's pop order and the
    fleet's admission/brownout ladder both key off it.

    - ``INTERACTIVE``: a human is waiting; protected under overload.
    - ``BATCH``: latency-tolerant but must eventually run (the
      scheduler's anti-starvation aging guarantees it).
    - ``BEST_EFFORT``: sheddable; the first thing a brownout drops.
    """

    INTERACTIVE = "interactive"
    BATCH = "batch"
    BEST_EFFORT = "best_effort"

    @property
    def rank(self) -> int:
        """0 = most urgent. The scheduler sorts ascending on this."""
        return _PRIORITY_RANK[self]


_PRIORITY_RANK = {Priority.INTERACTIVE: 0, Priority.BATCH: 1,
                  Priority.BEST_EFFORT: 2}


class QueueFull(RuntimeError):
    """Typed admission-control rejection: the engine's queue is at its
    ``max_queue_depth``. Carries the depth — and, when the engine has
    seen enough traffic to estimate one, a ``retry_after_s`` hint
    (queue depth x the recent per-admission interval from
    ``ServeMetrics``) — so upstream backpressure can be polite
    (honor the hint) instead of blind hammering, without parsing
    strings. ``retry_after_s`` is ``None`` before the estimator warms
    up (fewer than two admissions observed). The hint is
    PRIORITY-AWARE: a lower class waits behind every queued request of
    its own and all higher classes, so its hint counts that deeper
    effective queue — longer, and honest."""

    def __init__(self, queue_depth: int, max_queue_depth: int,
                 retry_after_s: Optional[float] = None,
                 priority: Optional["Priority"] = None):
        self.queue_depth = queue_depth
        self.max_queue_depth = max_queue_depth
        self.retry_after_s = retry_after_s
        self.priority = priority
        hint = (f"; retry after ~{retry_after_s:.3f}s"
                if retry_after_s is not None else "")
        super().__init__(
            f"serving queue full ({queue_depth}/{max_queue_depth}); "
            f"shed load upstream or raise max_queue_depth{hint}")


class AdmissionRejected(QueueFull):
    """Router-level admission-control rejection (a :class:`QueueFull`
    subclass so every existing backpressure path handles it): the fleet
    refused the request BEFORE any engine queue was consulted — a
    per-priority token bucket ran dry, or the brownout ladder is
    shedding this class (``reason`` says which). Carries the same
    honest ``retry_after_s`` contract; under brownout the hint covers
    the hysteretic recovery horizon, so a ``best_effort`` reject waits
    out the whole ladder unwind instead of hammering a browned-out
    fleet."""

    def __init__(self, reason: str, retry_after_s: Optional[float] = None,
                 priority: Optional["Priority"] = None,
                 queue_depth: int = 0, max_queue_depth: int = 0):
        super().__init__(queue_depth, max_queue_depth,
                         retry_after_s=retry_after_s, priority=priority)
        self.reason = reason
        hint = (f"; retry after ~{retry_after_s:.3f}s"
                if retry_after_s is not None else "")
        # Replace the queue-full message: no engine queue was involved.
        self.args = (
            f"fleet admission rejected ({reason}"
            f"{', ' + priority.value if priority is not None else ''})"
            f"{hint}",)


class RequestState(enum.Enum):
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    CANCELLED = "cancelled"
    TIMED_OUT = "timed_out"
    FAILED = "failed"        # replay budget exhausted (see FinishReason.ERROR)


class FinishReason(enum.Enum):
    LENGTH = "length"        # emitted max_new_tokens
    EOS = "eos"              # hit the engine's eos token (included)
    GRAMMAR = "grammar"      # a constrained stream's FSM reached a state
    #                          with no legal continuation: the output is
    #                          COMPLETE per its grammar (a success, like
    #                          eos — e.g. a JSON document's closing brace)
    CANCELLED = "cancelled"  # handle.cancel()
    TIMED_OUT = "timed_out"  # deadline_s exceeded while running
    DEADLINE = "deadline"    # deadline already expired at pop time (shed
    #                          by the scheduler before any prefill work)
    ERROR = "error"          # device faults outlasted the retry + replay
    #                          budget: the request fails, the engine lives


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration (the ``generate()`` surface).

    ``temperature <= 0`` is greedy; ``top_k``/``top_p`` then must be
    unset (mirroring ``generate()``'s loud error — greedy would
    silently ignore them)."""

    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None

    def __post_init__(self):
        if self.top_k is not None and int(self.top_k) < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if self.top_p is not None and not 0.0 < float(self.top_p) <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.temperature <= 0 and (self.top_k is not None
                                      or self.top_p is not None):
            raise ValueError(
                "top_k/top_p require temperature > 0 (greedy decoding "
                "would silently ignore them)")

    # Array-side sentinels (arrays can't carry None): see
    # gpt.batched_filtered_logits.
    def as_arrays(self) -> tuple:
        return (float(self.temperature),
                int(self.top_k) if self.top_k is not None else 0,
                float(self.top_p) if self.top_p is not None else 2.0)


_ids = itertools.count()


@dataclasses.dataclass
class Request:
    """One generate request as the scheduler sees it.

    ``adapter``/``constraint`` are the multi-tenant fields (ISSUE 9;
    `serve/tenant/`): the NAME of a registered LoRA adapter (``None`` =
    base model) and a JSON-able constraint spec dict
    (``{"kind": "regex"|"json_schema", ...}`` —
    :func:`pddl_tpu.serve.tenant.compile_constraint`'s input; ``None``
    = unconstrained). Both are plain wire-serializable values, so the
    drain snapshot (v4) and the fleet's submit/migration protocol carry
    them without new encode/decode pairs, and a replayed or migrated
    stream resumes under the identical adapter + automaton."""

    prompt: Sequence[int]
    max_new_tokens: int
    sampling: SamplingParams = SamplingParams()
    deadline_s: Optional[float] = None  # wall budget from submit()
    priority: Priority = Priority.INTERACTIVE
    adapter: Optional[str] = None       # registered LoRA adapter name
    constraint: Optional[dict] = None   # grammar/schema spec dict
    request_id: int = dataclasses.field(default_factory=lambda: next(_ids))


class RequestHandle:
    """The caller's view of a submitted request.

    ``tokens`` grows as the engine streams (generated tokens only, eos
    included when hit); ``state``/``finish_reason`` settle when the
    request leaves its slot. ``cancel()`` is honored at the engine's
    next step — a queued request never runs, a running one is evicted
    mid-decode with the tokens emitted so far intact.

    ``replays`` counts how many times the engine rebuilt this request's
    slot state after a device fault (each rebuild re-prefills the
    prompt and re-feeds ``tokens`` through the tick — the stream the
    caller sees never repeats or loses a token); past the engine's
    ``max_replays`` the request settles FAILED/ERROR instead of
    crash-looping. ``replay_pending`` is engine-internal: the
    already-emitted tokens still to re-feed during a replay.
    ``preemptions`` counts slot evictions in favor of more urgent
    queued work (the stream pauses and later resumes token-exactly
    through the same replay machinery); the engine stops preempting a
    handle past its preemption cap, so a stream can stall briefly but
    never thrash forever.
    """

    def __init__(self, request: Request, arrival_s: float):
        self.request = request
        self.arrival_s = arrival_s
        self.tokens: List[int] = []
        self.state = RequestState.QUEUED
        self.finish_reason: Optional[FinishReason] = None
        self.ttft_s: Optional[float] = None  # submit → first token
        # Engine-stamped at the scheduler's FIRST pop of a fresh request
        # (its clock): `ServeMetrics.admit_wall_s` runs from here.
        self.admit_s: Optional[float] = None
        self.finish_s: Optional[float] = None
        self.replays = 0
        self.replay_pending: List[int] = []
        self.preemptions = 0
        # Speculative-serving telemetry (engine ``spec_k > 0``): how
        # many draft tokens this stream was offered and how many the
        # verifier accepted — carried through drain/migration (snapshot
        # v5) so a stream's lifetime acceptance accounting survives a
        # replica move. Zero on non-speculative engines.
        self.spec_drafted = 0
        self.spec_accepted = 0
        self._cancel = False

    def cancel(self) -> None:
        self._cancel = True

    @property
    def cancelled(self) -> bool:
        return self._cancel

    @property
    def done(self) -> bool:
        return self.state in (RequestState.FINISHED, RequestState.CANCELLED,
                              RequestState.TIMED_OUT, RequestState.FAILED)

    def __repr__(self) -> str:  # debugging aid, not an API
        return (f"RequestHandle(id={self.request.request_id}, "
                f"state={self.state.value}, tokens={len(self.tokens)})")
