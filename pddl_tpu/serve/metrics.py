"""Serving telemetry: the numbers an online engine is judged by.

Single-request serving is judged by tokens/s; ONLINE serving is judged
by the latency/throughput trade under load — so the engine records, per
tick and per request:

- **TTFT** (time to first token, queue wait included) — the user-felt
  responsiveness number; p50/p99 because the tail IS the product.
- **per-token latency** — inter-token gap once streaming.
- **queue depth / slot occupancy** — the load signals the admission
  knobs (`scheduler.py`) act on.
- **tokens/s** — aggregate decoded throughput over the engine's active
  window.

Exposed through the existing :mod:`pddl_tpu.utils.summary` plumbing
(:func:`~pddl_tpu.utils.summary.format_table`) for humans, and as a
plain dict (:meth:`ServeMetrics.snapshot`) for benches/dashboards —
`benchmarks/serve_bench.py` writes the snapshot into the repo's
standard JSON-artifact shape.
"""

from __future__ import annotations

import random
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional

import numpy as np

from pddl_tpu.serve.request import Priority
from pddl_tpu.utils.summary import format_table

# Stable label vocabulary for the per-priority splits: every class is
# always present (zeros included) so the Prometheus exposition's label
# sets never appear/vanish with traffic.
PRIORITY_CLASSES = tuple(p.value for p in Priority)

# The timed phases of one ``ServeEngine.step()`` (the engine's span
# tree below ``pddl.serve.step``; docs/OPERATIONS.md § "Observability
# (serving)" has the table). The engine opens a ``pddl.serve.<phase>``
# profiler span per entry and sums each phase's wall time per step;
# this is the stable label set of ``phase_wall_s``, here and in the
# telemetry ring's record — every phase always present, like the
# priority classes. ``first_token_wait`` nests inside ``admit`` (under
# the per-request ``admit_request`` span, which only the profiler's
# trace carries: summed it would repeat ``admit``); the rest are direct
# children of the step. The two ``*_wait`` phases are the step's only
# host<-device reads. ``account`` is the step's own bookkeeping after
# ``emit`` (these counters, the gauges, the ring's record), so the
# direct children sum to the step's wall.
PHASES = ("reap", "admit", "first_token_wait", "append_blocks",
          "tick_dispatch", "tick_wait", "emit", "account")

# The engine's own set-up, stage by stage (``pddl.serve.setup.<stage>``
# spans; the stable label set of ``setup_wall_s``): ``build`` is the
# body of ``ServeEngine.__init__`` (the pool allocated, the programs
# wrapped); ``warmup:<site>`` the first call of one resident program
# from call to return — trace, lower, compile or persistent-cache load,
# dispatch — under its site name (`engine.compile_counts()`; the host
# tier's demotion gather is ``host_demote``); ``warmup_wait`` the closing
# wait on the device, the programs' first execution. A stage the engine
# does not have reads 0.
SETUP_STAGES = ("build", "warmup:adapter_load", "warmup:chunk_prefill",
                "warmup:chunk_prefill_wide", "warmup:sample_first",
                "warmup:host_promote", "warmup:host_demote",
                "warmup:draft_prefill", "warmup:draft", "warmup:verify",
                "warmup:tick", "warmup_wait")

# Where a compile can be counted: a step phase or a set-up stage (the
# stable label set of ``compiles``, ``compile_s``,
# ``compile_cache_misses`` and ``compile_cache_hits``). A compile while
# none of them is open on the
# compiling thread is not the engine's and is not counted.
COMPILE_PLACES = PHASES + SETUP_STAGES


def _pct(values, q: float) -> Optional[float]:
    vals = list(values)
    if not vals:
        return None
    return float(np.percentile(np.asarray(vals, np.float64), q))


class Reservoir:
    """Fixed-capacity uniform sample of an unbounded stream (Vitter's
    algorithm R): after ``n`` observations every observation has
    ``cap/n`` probability of being in the buffer, so percentiles and
    means over the buffer estimate the WHOLE stream — which is what
    keeps ``ServeMetrics.snapshot()`` stable while memory stays capped
    under sustained load (the plain lists it replaces grew forever).

    List-enough for the recording paths (``append``/``extend``/
    ``len``/iteration/truthiness); seeded, so the same workload
    snapshots the same numbers.
    """

    __slots__ = ("cap", "count", "_buf", "_rng")

    def __init__(self, cap: int = 8192, seed: int = 0):
        if cap < 1:
            raise ValueError(f"reservoir cap must be >= 1, got {cap}")
        self.cap = int(cap)
        self.count = 0  # total observed (>= len once capped)
        self._buf: List[float] = []
        self._rng = random.Random(seed)

    def append(self, value) -> None:
        self.count += 1
        if len(self._buf) < self.cap:
            self._buf.append(value)
        else:
            j = self._rng.randrange(self.count)
            if j < self.cap:
                self._buf[j] = value

    def extend(self, values: Iterable) -> None:
        for v in values:
            self.append(v)

    def __len__(self) -> int:
        return len(self._buf)

    def __iter__(self):
        return iter(self._buf)

    def __bool__(self) -> bool:
        return bool(self._buf)


class ServeMetrics:
    """Accumulates engine telemetry; cheap enough to leave always-on
    (a few floats per tick — never a device sync of its own). The
    per-sample series (TTFT, token latency, queue depth, occupancy)
    live in capped :class:`Reservoir`\\ s — ``reservoir_cap`` samples
    each, default ~8k — so a week of sustained load holds the same
    memory as a minute while ``snapshot()`` percentiles keep estimating
    the full stream."""

    def __init__(self, reservoir_cap: int = 8192) -> None:
        self.reservoir_cap = int(reservoir_cap)
        self.ttft_s = Reservoir(self.reservoir_cap, seed=0)
        self.token_latency_s = Reservoir(self.reservoir_cap, seed=1)
        self.queue_depth = Reservoir(self.reservoir_cap, seed=2)
        self.occupancy = Reservoir(self.reservoir_cap, seed=3)
        # Per-priority splits (the SLO dashboard: is `interactive`
        # actually protected, is `best_effort` actually absorbing the
        # shedding?). TTFT reservoirs per class plus finish/shed/reject
        # counters; exported as labeled Prometheus series.
        self.ttft_by_priority: Dict[str, Reservoir] = {
            cls: Reservoir(self.reservoir_cap, seed=10 + i)
            for i, cls in enumerate(PRIORITY_CLASSES)}
        self.finished_by_priority: Dict[str, int] = dict.fromkeys(
            PRIORITY_CLASSES, 0)
        self.deadline_shed_by_priority: Dict[str, int] = dict.fromkeys(
            PRIORITY_CLASSES, 0)
        self.rejected_by_priority: Dict[str, int] = dict.fromkeys(
            PRIORITY_CLASSES, 0)
        self.tokens_emitted = 0
        self.requests_finished = 0
        self.requests_rejected = 0
        self.requests_timed_out = 0
        self.requests_cancelled = 0
        # Prefix-cache telemetry (all zero when the cache is disabled):
        # one lookup per admission, hits counted at block granularity —
        # `prefill_tokens_saved` is the cached-token total the engine
        # did NOT re-prefill, the cache's whole value in one number.
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefill_tokens_saved = 0
        self.prefix_evictions = 0
        # Allocations that met a short free list, and the index nodes
        # those reclaims examined: visited per eviction is what a block
        # costs under pressure (about 1-2; it was the index's size).
        self.prefix_reclaims = 0
        self.prefix_reclaim_visited = 0
        # The sampler's searches, counted on the host from the arrays
        # each tick and first-token draw ships (no device cost): the
        # sampled rows that search a nucleus cut (`top_p < 1`), and the
        # ticks in which some row asked for top-k (any other tick skips
        # that search on the device).
        self.nucleus_rows_searched = 0
        self.topk_search_ticks = 0
        self.prefix_blocks_live = 0  # gauge, engine-stamped per admission
        # Block-pool telemetry: `copy_bytes_avoided` counts the bytes
        # a prefix hit references in place (matched tokens x per-token
        # KV bytes — what a pool->slot gather would have copied; the
        # path it measures against went in PR 34, ROADMAP names the
        # counter a debt); `blocks_shared` is the live gauge of pool
        # blocks referenced by >1 slot (each one a block a
        # private-copy design would hold once PER slot — the
        # capacity-doubling number), counted when it is read from the
        # source the engine binds (`bind_blocks_shared`);
        # `block_table_fill` is the mean occupied fraction of live
        # slots' block tables.
        self.copy_bytes_avoided = 0
        self._blocks_shared_fn = None
        self.block_table_fill = 0.0  # gauge, engine-stamped per tick
        # Tiered-KV-cache telemetry (`serve/kvcache/hosttier.py`; all
        # zero without a host tier): blocks demoted into the tier
        # (chain imports from replica pulls included), admissions whose
        # host match promoted >= 1 block, blocks promoted back H2D,
        # prefill-budget tokens those promotions were charged (the
        # adapter_load_tokens precedent), and the resident-byte gauge
        # the sizing runbook watches against the byte budget.
        self.host_tier_spills = 0
        self.host_tier_hits = 0
        self.host_tier_promotions = 0
        self.host_tier_promote_tokens_charged = 0
        self.host_tier_bytes_resident = 0  # gauge, engine-stamped
        # Multi-tenant telemetry (`serve/tenant/`; all zero on a plain
        # engine): adapter pool hits vs cold loads (the hit RATE is the
        # runbook's pool-sizing signal), LRU evictions under pressure,
        # a live residency gauge, per-adapter admission counts as a
        # labeled series, and the constrained-decoding counters.
        self.adapter_hits = 0        # admission found the adapter resident
        self.adapter_loads = 0       # cold host->device factor loads
        self.adapter_evictions = 0   # LRU evictions of unpinned rows
        self.adapter_pool_resident = 0  # gauge, engine-stamped
        self.requests_by_adapter: Dict[str, int] = {}
        self.constrained_requests = 0    # submissions carrying a spec
        self.requests_grammar_complete = 0  # FinishReason.GRAMMAR settles
        # Speculative-serving telemetry (engine ``spec_k > 0``; all
        # zero on a classic engine): verify windows dispatched, draft
        # tokens offered for acceptance (per-slot caps summed — sampled
        # rows and replay re-feeds offer none), and draft tokens the
        # verifier accepted. The acceptance RATE (accepted/drafted) is
        # the runbook's k-tuning signal: it falls as k grows past the
        # workload's self-similarity, and the throughput win follows it.
        self.spec_ticks = 0
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        # Resilience telemetry (`serve/faults.py`, engine retry/replay/
        # degraded paths): all zero on a fault-free engine.
        self.retries = 0             # failed device calls retried
        self.retry_sites: Dict[str, int] = {}
        self.replays = 0             # slot-state rebuilds (KV recomputed)
        self.preemptions = 0         # best_effort slots parked for
        #                              queued interactive work
        self.requests_failed = 0     # terminal FinishReason.ERROR
        self.requests_deadline_shed = 0  # FinishReason.DEADLINE at pop
        self.degraded_entries = 0    # times the engine flipped degraded
        self.degraded_time_s = 0.0   # wall time spent degraded (closed
        #                              intervals; re-arm stamps them)
        # Where a step's time goes (the engine's phase spans, always
        # on): steps that got past the drain check and their summed
        # wall, the same wall split by phase (``PHASES`` — the two
        # ``*_wait`` phases are the host blocked on the device), decode
        # ticks dispatched, and of FRESH requests (replays excluded):
        # per scheduler pop the scheduler's own wait (submit → pop, on
        # the engine's clock), per slot installed (`record_admission`)
        # pop → first token sampled. A request cancelled or expired
        # between the two is a pop and no admission.
        # rate(phase_wall_s{admit}) / rate(step_wall_s) is the share of
        # the loop in which admissions held every live stream.
        self.engine_steps = 0
        self.step_wall_s = 0.0
        self.phase_wall_s: Dict[str, float] = dict.fromkeys(PHASES, 0.0)
        # Where the engine's set-up goes (``SETUP_STAGES``, the same
        # `_Phase` boundary as the step's), and every backend compile the
        # engine saw, by the phase or stage open on the compiling thread
        # (``COMPILE_PLACES``; jax's monitoring events): executables
        # the backend compiled and their seconds; of those, the ones the
        # persistent compile cache took in (compiled for longer than its
        # floor, ``jax_persistent_cache_min_compile_time_secs``: what a
        # warm cache spares the next process — the rest, small eager
        # ops, compile in every process); and executables loaded from
        # that cache instead (not in ``compiles``).
        # sum(setup_wall_s) + step_wall_s before the first request is
        # the engine's share of a process's start.
        self.setup_wall_s: Dict[str, float] = dict.fromkeys(
            SETUP_STAGES, 0.0)
        self.compiles: Dict[str, int] = dict.fromkeys(COMPILE_PLACES, 0)
        self.compile_s: Dict[str, float] = dict.fromkeys(
            COMPILE_PLACES, 0.0)
        self.compile_cache_misses: Dict[str, int] = dict.fromkeys(
            COMPILE_PLACES, 0)
        self.compile_cache_hits: Dict[str, int] = dict.fromkeys(
            COMPILE_PLACES, 0)
        self._compiles_counted = 0
        self._cache_hit_pending = False
        # Routed expert layers traced, by the path their FFN took
        # (`ops/moe.grouped_expert_ffn`: the Pallas kernel, or the loop
        # of one tile a step) and by the phase or stage open on the
        # tracing thread (``COMPILE_PLACES``): counted once a layer each
        # time a program is traced, never a step.
        self.moe_kernel_layers: Dict[str, int] = dict.fromkeys(
            COMPILE_PLACES, 0)
        self.moe_loop_layers: Dict[str, int] = dict.fromkeys(
            COMPILE_PLACES, 0)
        self.decode_ticks = 0
        self.queue_pops = 0
        self.queue_wait_s = 0.0
        self.admissions = 0
        self.admit_wall_s = 0.0
        # What prefill cost in tokens: prompt tokens of the FRESH
        # requests installed (counted at the install, beside
        # `admissions`), and the chunk programs dispatched, by their
        # compiled width (counted at the dispatch, replays and sliced
        # admissions included). sum(width * chunks) / prefill_tokens is
        # the padding the fixed widths cost. Of a model with latent
        # attention layers, whose chunk programs re-expand every cached
        # entry they attend over: the cached tokens so re-expanded (a
        # chunk's offset, counted at its dispatch, once a chunk whatever
        # the number of such layers); over prefill_tokens, how many
        # times a prompt token is expanded again after its own chunk.
        self.prefill_tokens = 0
        self.prefill_chunks: Dict[str, int] = {}
        self.latent_expanded_tokens = 0
        # Slot state (a model with layers that keep a fixed state a
        # slot, `llama.ShortConv`): admissions whose first chunk started
        # a state row from zeros, admissions that skipped the prefix
        # index because a hit would not restore that state, and the
        # device bytes the state leaves hold (a gauge, set once: the
        # leaves are sized by slots, not by load).
        self.state_rows_started = 0
        self.prefix_skipped_stateful = 0
        self.state_bytes_resident = 0
        # Recent admission timestamps: the QueueFull retry_after_s
        # estimator (a short window so the hint tracks CURRENT service
        # rate, not the all-time average).
        self._admission_times: Deque[float] = deque(maxlen=32)
        self._first_activity_s: Optional[float] = None
        self._last_activity_s: Optional[float] = None

    # ------------------------------------------------------ recording
    def record_tick(self, now_s: float, queue_depth: int, live_slots: int,
                    total_slots: int, new_tokens: int,
                    tick_seconds: float) -> None:
        self.queue_depth.append(queue_depth)
        self.occupancy.append(live_slots / max(total_slots, 1))
        self.tokens_emitted += new_tokens
        if new_tokens:
            # One fused tick serves every live slot, so the inter-token
            # gap each STREAM sees is the whole tick's wall time — one
            # sample per token emitted this tick.
            self.token_latency_s.extend([tick_seconds] * new_tokens)
        if self._first_activity_s is None:
            self._first_activity_s = now_s
        self._last_activity_s = now_s

    def record_step(self, wall_s: float,
                    phase_wall_s: Dict[str, float]) -> None:
        """One ``step()`` ended: its wall time and the per-phase split
        (both ``time.perf_counter`` durations, so their ratio holds
        whatever clock the engine was given)."""
        self.engine_steps += 1
        self.step_wall_s += wall_s
        mine = self.phase_wall_s
        for phase, w in phase_wall_s.items():
            mine[phase] += w

    def record_compile_cache_hit(self, where: str) -> None:
        """The persistent compile cache held the executable the backend
        was asked for under ``where``; the backend's own event for it
        follows (`record_backend_compile`) and is not a compile."""
        self.compile_cache_hits[where] += 1
        self._cache_hit_pending = True

    def record_compile_cache_miss(self, where: str) -> None:
        """A compile under ``where`` went into the persistent compile
        cache (it is also one of ``compiles``)."""
        self.compile_cache_misses[where] += 1

    def record_backend_compile(self, where: str, seconds: float) -> None:
        """The backend produced one executable under ``where`` in
        ``seconds``: compiled, or loaded from the persistent cache when
        `record_compile_cache_hit` came first."""
        if self._cache_hit_pending:
            self._cache_hit_pending = False
            return
        self.compiles[where] += 1
        self.compile_s[where] += seconds
        self._compiles_counted += 1

    def record_moe_layer(self, where: str, kernel: bool) -> None:
        """A routed expert layer was traced under ``where``; its FFN
        runs as the expert kernel (``kernel``) or as the loop."""
        (self.moe_kernel_layers if kernel else self.moe_loop_layers)[
            where] += 1

    @property
    def compiles_counted(self) -> int:
        """Every compile counted so far, wherever (the ring record's
        ``compiles`` is its difference across one step)."""
        return self._compiles_counted

    def record_decode_tick(self) -> None:
        """One decode tick (or speculative verify window) dispatched."""
        self.decode_ticks += 1

    def record_sampled(self, temps, top_ks, top_ps, *,
                       tick: bool) -> None:
        """One sampler dispatch's per-row parameters as shipped (the
        engine's sentinels: ``top_p >= 1`` no nucleus, ``top_k <= 0`` no
        top-k); ``tick`` for a tick or verify window, not a first-token
        draw."""
        self.nucleus_rows_searched += int(np.count_nonzero(
            (np.asarray(temps) > 0) & (np.asarray(top_ps) < 1.0)))
        if tick and np.any(np.asarray(top_ks) > 0):
            self.topk_search_ticks += 1

    def record_queue_pop(self, queue_wait_s: float) -> None:
        """One FRESH request popped by the scheduler, ``queue_wait_s``
        after its submit."""
        self.queue_pops += 1
        self.queue_wait_s += queue_wait_s

    def record_first_token(self, ttft_s: float,
                           priority: Optional[str] = None) -> None:
        self.ttft_s.append(ttft_s)
        if priority in self.ttft_by_priority:
            self.ttft_by_priority[priority].append(ttft_s)
        self.tokens_emitted += 1

    def record_finish(self, reason_value: str,
                      priority: Optional[str] = None) -> None:
        """One request departed. ``requests_finished`` counts ONLY
        successful completions (length/eos); cancellations, timeouts,
        pop-time deadline sheds, and fault failures each go to their
        own counter — all disjoint, so a success rate is finished /
        (finished + cancelled + timed_out + deadline_shed + failed +
        rejected) with no hidden convention. ``priority`` (a
        :class:`~pddl_tpu.serve.request.Priority` value string) feeds
        the per-class finish/shed splits."""
        if reason_value == "timed_out":
            self.requests_timed_out += 1
        elif reason_value == "deadline":
            self.requests_deadline_shed += 1
            if priority in self.deadline_shed_by_priority:
                self.deadline_shed_by_priority[priority] += 1
        elif reason_value == "cancelled":
            self.requests_cancelled += 1
        elif reason_value == "error":
            self.requests_failed += 1
        else:
            self.requests_finished += 1
            if reason_value == "grammar":
                # A grammar-complete stream is a SUCCESS (the FSM ran
                # out of legal continuations because the output is a
                # complete document) — counted inside finished, plus
                # its own counter so the tenant dashboard can tell
                # grammar closure from eos/length.
                self.requests_grammar_complete += 1
            if priority in self.finished_by_priority:
                self.finished_by_priority[priority] += 1

    def record_rejected(self, priority: Optional[str] = None) -> None:
        self.requests_rejected += 1
        if priority in self.rejected_by_priority:
            self.rejected_by_priority[priority] += 1

    # ------------------------------------------------------- resilience
    def record_retry(self, site: str) -> None:
        self.retries += 1
        self.retry_sites[site] = self.retry_sites.get(site, 0) + 1

    def record_replay(self) -> None:
        self.replays += 1

    def record_preemption(self) -> None:
        self.preemptions += 1

    def record_degraded_entry(self) -> None:
        self.degraded_entries += 1

    def record_degraded_exit(self, seconds: float) -> None:
        self.degraded_time_s += max(0.0, float(seconds))

    def record_admission(self, now_s: float, admit_wall_s: float,
                         prompt_tokens: int = 0) -> None:
        """One FRESH request admitted — its slot installed, its first
        token sampled ``admit_wall_s`` after its scheduler pop, its
        ``prompt_tokens`` prefilled (replays excluded — they consume
        admission work but represent no new queue progress, and the
        retry_after hint estimates how fast the queue drains)."""
        self._admission_times.append(float(now_s))
        self.admissions += 1
        self.admit_wall_s += admit_wall_s
        self.prefill_tokens += int(prompt_tokens)

    def record_prefill_chunk(self, width: int,
                             latent_expanded: int = 0) -> None:
        """One chunk-prefill program of compiled ``width`` dispatched,
        re-expanding ``latent_expanded`` cached tokens (0 for a model
        without latent layers)."""
        key = str(int(width))
        self.prefill_chunks[key] = self.prefill_chunks.get(key, 0) + 1
        self.latent_expanded_tokens += int(latent_expanded)

    def recent_admission_interval_s(self) -> Optional[float]:
        """Mean gap between recent admissions, or ``None`` before two
        were observed."""
        times = self._admission_times
        if len(times) < 2:
            return None
        return (times[-1] - times[0]) / (len(times) - 1)

    def estimate_retry_after_s(self, queue_depth: int) -> Optional[float]:
        """The QueueFull backpressure hint: the queue ahead of a new
        arrival times the recent per-admission interval — roughly when
        a queue slot frees up. An estimate from a sliding window, not a
        promise; ``None`` until the engine has admitted twice."""
        interval = self.recent_admission_interval_s()
        if interval is None:
            return None
        return max(interval, 0.0) * max(int(queue_depth), 1)

    def record_prefix_lookup(self, tokens_saved: int, *, blocks_live: int,
                             evictions: int) -> None:
        """One admission-time prefix-cache lookup: ``tokens_saved`` is
        the matched (not re-prefilled) token count, 0 for a miss;
        ``blocks_live``/``evictions`` snapshot the pool state so the
        gauges need no separate plumbing."""
        self.prefix_lookups += 1
        if tokens_saved > 0:
            self.prefix_hits += 1
            self.prefill_tokens_saved += int(tokens_saved)
        self.prefix_blocks_live = int(blocks_live)
        self.prefix_evictions = int(evictions)

    def record_prefix_reclaims(self, *, evictions: int, reclaims: int,
                               visited: int) -> None:
        """The index's own running totals of allocation under pressure
        (`RadixPrefixCache.evictions` / ``reclaims`` /
        ``reclaim_visited``), stamped after every admission and once a
        step after the block tables grow."""
        self.prefix_evictions = int(evictions)
        self.prefix_reclaims = int(reclaims)
        self.prefix_reclaim_visited = int(visited)

    def record_copy_avoided(self, nbytes: int) -> None:
        """One paged prefix hit referenced ``nbytes`` of matched KV in
        place instead of gathering it into a slot row."""
        self.copy_bytes_avoided += int(nbytes)

    def record_paged_gauges(self, block_table_fill: float) -> None:
        """The per-tick table occupancy gauge (engine-stamped)."""
        self.block_table_fill = float(block_table_fill)

    def bind_blocks_shared(self, count) -> None:
        """Where ``blocks_shared`` is counted when read: ``count()``
        returns the blocks more than one live slot references now."""
        self._blocks_shared_fn = count

    @property
    def blocks_shared(self) -> int:
        """Pool blocks referenced by more than one live slot, counted
        now (0 with no engine bound)."""
        count = self._blocks_shared_fn
        return 0 if count is None else int(count())

    # ----------------------------------------------------- tiered cache
    def record_host_spill(self, bytes_resident: int) -> None:
        """One block entered the host tier — a demotion of an LRU
        victim, or a replica-to-replica chain import; ``bytes_resident``
        stamps the residency gauge in passing."""
        self.host_tier_spills += 1
        self.host_tier_bytes_resident = int(bytes_resident)

    def record_host_promotion(self, blocks: int, tokens_charged: int,
                              bytes_resident: int) -> None:
        """One admission promoted ``blocks`` host-tier blocks back into
        the device pool, charged ``tokens_charged`` against the prefill
        budget."""
        self.host_tier_hits += 1
        self.host_tier_promotions += int(blocks)
        self.host_tier_promote_tokens_charged += int(tokens_charged)
        self.host_tier_bytes_resident = int(bytes_resident)

    # ---------------------------------------------------------- tenancy
    def record_adapter_hit(self, name: str, resident: int, *,
                           fresh: bool = True) -> None:
        """One admission found its adapter already device-resident;
        ``resident`` stamps the pool-residency gauge in passing.
        ``fresh=False`` (a replay / preemption-resume re-admission)
        still counts pool traffic but NOT per-tenant request volume —
        ``requests_by_adapter`` is the capacity-planning series and
        must count each request once, however many times faults
        re-admit it."""
        self.adapter_hits += 1
        if fresh:
            self.requests_by_adapter[name] = \
                self.requests_by_adapter.get(name, 0) + 1
        self.adapter_pool_resident = int(resident)

    def record_adapter_load(self, name: str, resident: int,
                            evictions: int, *,
                            fresh: bool = True) -> None:
        """One COLD adapter load (host→device factor transfer on the
        admission path); ``evictions`` is the pool's cumulative LRU
        eviction count (stamped, like the prefix cache's). ``fresh``
        as in :meth:`record_adapter_hit` — a replay's reload is real
        pool traffic (it keeps the hit rate honest about thrash) but
        not new request volume."""
        self.adapter_loads += 1
        if fresh:
            self.requests_by_adapter[name] = \
                self.requests_by_adapter.get(name, 0) + 1
        self.adapter_pool_resident = int(resident)
        self.adapter_evictions = int(evictions)

    def record_constrained(self) -> None:
        """One submission carried a grammar/schema constraint."""
        self.constrained_requests += 1

    # ------------------------------------------------------ speculation
    def record_spec_tick(self, drafted: int, accepted: int) -> None:
        """One speculative verify window: ``drafted`` tokens offered
        for acceptance across the batch (sampled rows and forced replay
        re-feeds offer none), ``accepted`` of them taken."""
        self.spec_ticks += 1
        self.spec_drafted_tokens += int(drafted)
        self.spec_accepted_tokens += int(accepted)

    # ------------------------------------------------------ reporting
    def snapshot(self) -> Dict[str, object]:
        """The dashboard dict: counters plus latency percentiles (None
        where nothing was recorded yet)."""
        window = None
        if (self._first_activity_s is not None
                and self._last_activity_s is not None):
            window = self._last_activity_s - self._first_activity_s
        return {
            "requests_finished": self.requests_finished,
            "requests_rejected": self.requests_rejected,
            "requests_timed_out": self.requests_timed_out,
            "requests_cancelled": self.requests_cancelled,
            "tokens_emitted": self.tokens_emitted,
            "tokens_per_s": (self.tokens_emitted / window
                             if window else None),
            "ttft_p50_s": _pct(self.ttft_s, 50),
            "ttft_p99_s": _pct(self.ttft_s, 99),
            "token_latency_p50_s": _pct(self.token_latency_s, 50),
            "token_latency_p99_s": _pct(self.token_latency_s, 99),
            "mean_queue_depth": (float(np.mean(list(self.queue_depth)))
                                 if self.queue_depth else None),
            "mean_slot_occupancy": (float(np.mean(list(self.occupancy)))
                                    if self.occupancy else None),
            "prefix_lookups": self.prefix_lookups,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": (self.prefix_hits / self.prefix_lookups
                                if self.prefix_lookups else None),
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefix_blocks_live": self.prefix_blocks_live,
            "prefix_evictions": self.prefix_evictions,
            "prefix_reclaims": self.prefix_reclaims,
            "prefix_reclaim_visited": self.prefix_reclaim_visited,
            "nucleus_rows_searched": self.nucleus_rows_searched,
            "topk_search_ticks": self.topk_search_ticks,
            "copy_bytes_avoided": self.copy_bytes_avoided,
            "blocks_shared": self.blocks_shared,
            "block_table_fill": round(self.block_table_fill, 6),
            "host_tier_spills": self.host_tier_spills,
            "host_tier_hits": self.host_tier_hits,
            "host_tier_promotions": self.host_tier_promotions,
            "host_tier_promote_tokens_charged":
                self.host_tier_promote_tokens_charged,
            "host_tier_bytes_resident": self.host_tier_bytes_resident,
            "adapter_hits": self.adapter_hits,
            "adapter_loads": self.adapter_loads,
            "adapter_evictions": self.adapter_evictions,
            "adapter_hit_rate": (
                self.adapter_hits / (self.adapter_hits + self.adapter_loads)
                if (self.adapter_hits + self.adapter_loads) else None),
            "adapter_pool_resident": self.adapter_pool_resident,
            "constrained_requests": self.constrained_requests,
            "requests_grammar_complete": self.requests_grammar_complete,
            "spec_ticks": self.spec_ticks,
            "spec_drafted_tokens": self.spec_drafted_tokens,
            "spec_accepted_tokens": self.spec_accepted_tokens,
            "spec_acceptance_rate": (
                self.spec_accepted_tokens / self.spec_drafted_tokens
                if self.spec_drafted_tokens else None),
            # Labeled series: one sample per adapter NAME seen (unlike
            # the priority splits the label set is open — a tenant
            # appears on first admission and never vanishes).
            "requests_by_adapter": dict(self.requests_by_adapter),
            "retries": self.retries,
            # Per-site retry attribution (open label set, like
            # requests_by_adapter): WHERE the transient faults land —
            # recorded since r08 but only exported since the graftlint
            # exposition-parity rule caught it missing here.
            "retry_sites": dict(self.retry_sites),
            "replays": self.replays,
            "preemptions": self.preemptions,
            "requests_failed": self.requests_failed,
            "requests_deadline_shed": self.requests_deadline_shed,
            "degraded_entries": self.degraded_entries,
            "degraded_time_s": round(self.degraded_time_s, 6),
            "engine_steps": self.engine_steps,
            "step_wall_s": self.step_wall_s,
            # Labeled series, one sample per phase (closed label set).
            "phase_wall_s": dict(self.phase_wall_s),
            # Labeled series: one sample per set-up stage, and per phase
            # or stage for the compiles (closed label sets).
            "setup_wall_s": dict(self.setup_wall_s),
            "compiles": dict(self.compiles),
            "compile_s": dict(self.compile_s),
            "compile_cache_misses": dict(self.compile_cache_misses),
            "compile_cache_hits": dict(self.compile_cache_hits),
            # Labeled series: routed layers traced by their FFN's path,
            # per phase or stage (closed label set).
            "moe_kernel_layers": dict(self.moe_kernel_layers),
            "moe_loop_layers": dict(self.moe_loop_layers),
            "decode_ticks": self.decode_ticks,
            "queue_pops": self.queue_pops,
            "queue_wait_s": self.queue_wait_s,
            "admissions": self.admissions,
            "admit_wall_s": self.admit_wall_s,
            "prefill_tokens": self.prefill_tokens,
            # Labeled series, one sample per compiled chunk width.
            "prefill_chunks": dict(self.prefill_chunks),
            "latent_expanded_tokens": self.latent_expanded_tokens,
            "state_rows_started": self.state_rows_started,
            "prefix_skipped_stateful": self.prefix_skipped_stateful,
            "state_bytes_resident": self.state_bytes_resident,
            # Per-priority splits: mappings render as labeled series
            # (one sample per class) through `obs/export.py`, so the
            # SLO runbook reads shed/finish/TTFT per class off one
            # scrape. Every class is always present — a silent zero is
            # a zero, not a vanished label.
            "requests_finished_by_priority": dict(
                self.finished_by_priority),
            "requests_deadline_shed_by_priority": dict(
                self.deadline_shed_by_priority),
            "requests_rejected_by_priority": dict(
                self.rejected_by_priority),
            "ttft_p50_s_by_priority": {
                cls: _pct(r, 50)
                for cls, r in self.ttft_by_priority.items()},
            "ttft_p99_s_by_priority": {
                cls: _pct(r, 99)
                for cls, r in self.ttft_by_priority.items()},
        }

    def summary(self) -> str:
        """Human-readable table via the shared summary plumbing (the
        per-priority mappings flatten to one ``key[class]`` row each)."""
        rows = {}
        for k, v in self.snapshot().items():
            if isinstance(v, dict):
                for cls, cv in v.items():
                    rows[f"{k}[{cls}]"] = "-" if cv is None else cv
            else:
                rows[k] = "-" if v is None else v
        return format_table("Serving metrics:", rows)
