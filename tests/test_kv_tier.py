"""Tiered KV cache (ISSUE 13): host-RAM spill tier + cross-replica
prefix transfer (`serve/kvcache/hosttier.py`, `ServeEngine(host_tier=)`,
the fleet's chain pull).

The contracts under test:

- **Tier mechanics**: byte-budgeted store/match/pin/evict with
  radix-style refcounts; structural holes end promotable chains; the
  leaf spec refuses malformed payloads.
- **Eviction is demotion**: the radix LRU reclaim offers victims to the
  host tier; ``flush_unpinned`` (the OOM response) BYPASSES demotion —
  pinned discriminatively, at the radix hook level and through a real
  injected OOM.
- **Token-exactness**: a chain that round-trips the host tier (or
  crosses replicas over the chain wire format) yields bit-identical
  streams to ``generate()`` — GPT and Llama.
- **Cold path unchanged**: byte budget 0 compiles the exact untiered
  program set and emits identical tokens.
- **Budget charge**: promotions price ``promote_tokens_per_block`` per
  block through the cost_fn (the adapter_load_tokens precedent).
- **Resilience**: a 3-seed chaos matrix with faults at the
  ``host_promote`` site, a kill mid-promotion with drain/restore while
  the tier is populated — every survivor token-exact, zero recompiles,
  no leaked host pins.
- **Fleet**: second-tier shadow routing (``routed_host_tier``) and the
  replica-to-replica chain pull eliminating duplicate prefill.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ref_greedy
from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.models.llama import tiny_llama
from pddl_tpu.obs.export import (
    fleet_exposition,
    parse_prometheus_text,
    serve_exposition,
)
from pddl_tpu.serve import ServeEngine
from pddl_tpu.serve.drain import kv_chain_from_wire, kv_chain_to_wire
from pddl_tpu.serve.faults import FaultKind, FaultPlan, FaultSpec, KillPoint
from pddl_tpu.serve.fleet.replica import LocalReplica
from pddl_tpu.serve.fleet.router import FleetRouter, _ShadowIndex
from pddl_tpu.serve.kvcache import (
    HostTierCache,
    HostTierConfig,
    RadixPrefixCache,
)
from pddl_tpu.serve.request import (
    Priority,
    Request,
    RequestHandle,
    RequestState,
)

pytestmark = pytest.mark.kvtier

_no_sleep = lambda s: None  # noqa: E731

BS = 8  # prefix block size every engine below uses


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


@pytest.fixture(scope="module")
def llama_setup():
    model = tiny_llama(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(1), prompt, train=False)["params"]
    return model, {"params": params}


def _prompts(n=6, length=24, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 32, size=length).astype(np.int32)
            for _ in range(n)]


def _engine(model, variables, *, host=1 << 24, **kw):
    """A tier-testable engine: the device pool is deliberately TINY
    (the engine's floor + 1) so cycling a few 3-block prompts forces
    LRU eviction — the demotion trigger."""
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_len", 32)
    kw.setdefault("prefix_block_size", BS)
    kw.setdefault("prefix_chunk", BS)
    kw.setdefault("prefix_cache_blocks", 2 * (64 // BS) + 1 + 1)
    return ServeEngine(model, variables, host_tier=host,
                       **kw)


def _serve_all(eng, prompts, n_new=4):
    outs = []
    for p in prompts:
        h = eng.submit(p, n_new)
        eng.run(max_steps=5000)
        assert h.done, h.state
        outs.append(list(h.tokens))
    return outs


# ------------------------------------------------------------ tier unit
def _payload(val=1.0, shape=(1, 2, BS, 4)):
    return {"k": np.full(shape, val, np.float32),
            "v": np.full(shape, -val, np.float32)}


def _spec():
    return {"k": ((1, 2, BS, 4), np.dtype(np.float32)),
            "v": ((1, 2, BS, 4), np.dtype(np.float32))}


def test_hosttier_store_match_pin_evict():
    block_bytes = sum(a.nbytes for a in _payload().values())
    tier = HostTierCache(BS, 3 * block_bytes, leaf_spec=_spec())
    toks = list(range(4 * BS))
    # Store blocks 1..3 tip-first-ish: depth 3 first (structural 1-2),
    # then backfill — the device-evicts-leaf-first arrival order.
    assert tier.store(toks[:3 * BS], _payload(3.0))
    assert tier.store(toks[:2 * BS], _payload(2.0))
    assert tier.store(toks[:1 * BS], _payload(1.0))
    assert tier.blocks_resident == 3
    assert tier.bytes_resident == 3 * block_bytes
    # Re-store of a populated node is refused (no double accounting).
    assert not tier.store(toks[:2 * BS], _payload(9.0))
    # Full-chain match from depth 0; payloads come back root-first.
    tip = tier.match_from(toks, 0, 4)
    assert tip is not None and tip.depth == 3
    data = tier.chain_data(tip, 3)
    assert [d["k"][0, 0, 0, 0] for d in data] == [1.0, 2.0, 3.0]
    # A match from a device depth only needs structural coverage there.
    assert tier.match_depth(toks, 1, 4) == 2
    # Pin the chain, then overflow the budget: everything resident is
    # pinned, so the newcomer is REFUSED (never evict under a pin).
    tip = tier.pin_chain(toks, 0, 3)
    assert tip is not None and tier.pins_outstanding == 1
    other = [100 + t for t in range(BS)]
    assert not tier.store(other, _payload(7.0))
    assert tier.match_depth(toks, 0, 3) == 3
    tier.unpin(tip)
    assert tier.pins_outstanding == 0
    # Unpinned now: the same store evicts the LRU victim and lands.
    assert tier.store(other, _payload(7.0))
    assert tier.blocks_resident == 3
    assert tier.evictions >= 1
    # Spec validation refuses malformed payloads.
    bad = {"k": np.zeros((1, 2, BS, 4), np.float32)}  # missing "v"
    assert not tier.store([300 + t for t in range(BS)], bad)
    wrong = _payload()
    wrong["k"] = wrong["k"].astype(np.float64)
    assert not tier.store([300 + t for t in range(BS)], wrong)


def test_hosttier_full_budget_backfill_stays_reachable():
    """Discriminative for the detached-node leak: at a FULL budget,
    storing a chain's parent block evicts that chain's own deeper
    block (leaf-first demotion order, oldest LRU stamp) — the evictor's
    prune walk must not delete the store's target node out of the tree
    before the payload attaches. On the unfixed cache the backfilled
    block is tracked but unreachable: match misses it and the budget
    bytes can never be evicted again."""
    block_bytes = sum(a.nbytes for a in _payload().values())
    tier = HostTierCache(BS, block_bytes, leaf_spec=_spec())
    toks = list(range(2 * BS))
    assert tier.store(toks[:2 * BS], _payload(2.0))  # leaf first
    assert tier.store(toks[:1 * BS], _payload(1.0))  # backfill evicts it
    assert tier.bytes_resident == block_bytes
    assert tier.blocks_resident == 1
    # The backfilled block is REACHABLE: matchable from the root...
    tip = tier.match_from(toks, 0, 2)
    assert tip is not None and tip.depth == 1
    assert tip.data["k"][0, 0, 0, 0] == 1.0
    # ...and evictable: an unrelated store can reclaim its bytes (the
    # leaked node was invisible to the eviction DFS, so this store was
    # refused and the accounting stuck at a phantom block forever).
    other = [100 + t for t in range(BS)]
    assert tier.store(other, _payload(7.0))
    assert tier.bytes_resident == block_bytes
    assert tier.blocks_resident == 1
    assert tier.match_depth(toks, 0, 2) == 0


def test_hosttier_hole_ends_promotable_chain():
    tier = HostTierCache(BS, 1 << 20, leaf_spec=_spec())
    toks = list(range(3 * BS))
    assert tier.store(toks[:1 * BS], _payload(1.0))
    assert tier.store(toks[:3 * BS], _payload(3.0))  # depth 2 is a hole
    tip = tier.match_from(toks, 0, 3)
    assert tip is not None and tip.depth == 1  # stops at the hole


def test_radix_flush_bypasses_demotion_hook():
    """Discriminative at the radix level: allocation-pressure eviction
    calls ``on_evict``; the degraded flush (``flush_unpinned``) must
    NOT — spilling during an OOM response defeats the shedding."""
    idx = RadixPrefixCache(BS, 4)  # 3 allocatable
    seen = []
    idx.on_evict = lambda victims: seen.extend(
        idx.chain_tokens(v) for v in victims)
    toks = list(range(3 * BS))
    ids = idx.allocate(3)
    idx.extend(idx.match(toks).node, toks, ids)
    # Allocation pressure: the LRU victim is offered to the hook.
    idx.allocate(1)
    assert len(seen) == 1
    # The flush frees BOTH stored unpinned blocks WITHOUT offering
    # anything — a partial flush or a demoting flush both fail here.
    # (blocks_live is 3: the id allocate() just handed out is live but
    # caller-held, not the index's to free.)
    seen.clear()
    freed = idx.flush_unpinned()
    assert freed == 2 and idx.blocks_live == 1
    assert seen == []


# ------------------------------------------------- engine token-exact
def test_demote_promote_token_exact(gpt_setup,
                                    pin_zero_recompiles):
    """Cycling more chains than the device pool holds forces demotion;
    revisiting them forces promotion — and every stream, cold or
    promoted, matches the one-shot ``generate()`` oracle exactly."""
    model, variables = gpt_setup
    eng = pin_zero_recompiles(_engine(model, variables))
    # 6 distinct 3-block chains: more than the pool can keep (floor
    # 17 minus live usage).
    prompts = _prompts()
    refs = [ref_greedy(model, variables, p, 4) for p in prompts]
    for _ in range(3):
        outs = _serve_all(eng, prompts)
        assert outs == refs
    snap = eng.metrics.snapshot()
    assert snap["host_tier_spills"] > 0, "pool never demoted — tighten it"
    assert snap["host_tier_hits"] > 0
    assert snap["host_tier_promotions"] > 0
    assert snap["host_tier_promote_tokens_charged"] > 0
    assert eng.host_tier_bytes_resident > 0
    assert eng._host.pins_outstanding == 0
    assert eng.compile_counts()["host_promote"] == 1


def test_llama_promotion_token_exact(llama_setup, pin_zero_recompiles):
    model, variables = llama_setup
    eng = pin_zero_recompiles(_engine(model, variables))
    prompts = _prompts(seed=5)
    refs = [ref_greedy(model, variables, p, 4) for p in prompts]
    for _ in range(2):
        assert _serve_all(eng, prompts) == refs
    assert eng.metrics.host_tier_promotions > 0


def test_budget_zero_is_bit_identical_to_untiered(gpt_setup):
    """The cold-path contract: byte budget 0 (or host_tier=None) is
    the untiered engine — same compiled-program SET (no host_promote
    key), same tokens."""
    model, variables = gpt_setup
    prompts = _prompts()
    plain = _engine(model, variables, host=None)
    zero = _engine(model, variables, host=HostTierConfig(byte_budget=0))
    plain.warmup(), zero.warmup()
    assert plain.compile_counts() == zero.compile_counts()
    assert "host_promote" not in zero.compile_counts()
    assert not zero.host_tier_enabled
    outs_p = [_serve_all(plain, prompts) for _ in range(2)]
    outs_z = [_serve_all(zero, prompts) for _ in range(2)]
    assert outs_p == outs_z


def test_host_tier_requires_prefix_machinery(gpt_setup):
    model, variables = gpt_setup
    with pytest.raises(ValueError, match="IS the KV cache"):
        ServeEngine(model, variables, max_slots=2, prefill_len=32,
                    prefix_cache_blocks=0, host_tier=1 << 20)


def test_degraded_mode_touches_the_tier_in_neither_direction(gpt_setup):
    """A real injected OOM flips the engine degraded: the flush must
    hard-free (no spills), and admissions during the cool-down must
    not promote — the discriminative ISSUE 13 satellite pin."""
    model, variables = gpt_setup
    clock = __import__("conftest").FakeClock()
    eng = _engine(model, variables, clock=clock,
                  backoff_sleep=_no_sleep, degraded_cooldown_s=100.0)
    eng.warmup()
    prompts = _prompts()
    _serve_all(eng, prompts)          # populate pool + host tier
    _serve_all(eng, prompts)          # revisit: spills + promotions
    spills_before = eng.metrics.host_tier_spills
    bytes_before = eng.host_tier_bytes_resident
    assert spills_before > 0
    # Inject a REAL OOM on the very next tick: the live stream dies
    # into replay, degraded flushes every unpinned block — hard-frees.
    eng._faults = FaultPlan(scheduled=[
        FaultSpec(step=eng._step_idx, site="tick", kind=FaultKind.OOM)])
    h = eng.submit(prompts[0], 4)
    for _ in range(5):
        eng.step()
        if eng.degraded:
            break
    assert eng.degraded
    assert eng.metrics.host_tier_spills == spills_before, \
        "degraded flush demoted into the host tier"
    # Admissions while degraded promote nothing (cold path).
    hits_before = eng.metrics.host_tier_hits
    h2 = eng.submit(prompts[1], 4)
    eng.run(max_steps=2000)
    assert h.done and h2.done
    assert eng.metrics.host_tier_hits == hits_before
    assert eng.host_tier_bytes_resident == bytes_before


def test_promotion_budget_charge(gpt_setup):
    """The scheduler-facing price: a host-tier chain charges
    promote_tokens_per_block per block instead of block_size prefill
    tokens, and the charge lands on the counter."""
    model, variables = gpt_setup
    eng = _engine(model, variables, host=HostTierConfig(
        byte_budget=1 << 24, promote_tokens_per_block=3),
        prefill_token_budget=64)
    eng.warmup()
    prompts = _prompts()
    _serve_all(eng, prompts)   # A's chain ends up demoted by the cycle
    target = prompts[0]

    def cost_of(p):
        h = RequestHandle(Request(prompt=list(p), max_new_tokens=4),
                          arrival_s=0.0)
        return eng._prefill_cost(h)

    cold = cost_of(np.asarray(
        np.random.default_rng(9).integers(0, 32, 24), np.int32))
    assert cold == 24  # never seen: full prompt
    # `target`'s chain is split across tiers (LRU evicts leaf-first):
    # the cost composes the device match m with the host extension h —
    # promoted blocks price 3 tokens each instead of 8 prefill tokens.
    cap = (24 - 1) // BS
    m = eng._prefix.match(target, max_blocks=cap).n_blocks
    h = eng._host.match_depth(target, m, cap - m)
    assert h > 0, "the cycle never demoted target's chain"
    assert cost_of(target) == 24 - (m + h) * BS + h * 3
    charged_before = eng.metrics.host_tier_promote_tokens_charged
    handle = eng.submit(target, 4)
    eng.run(max_steps=2000)
    assert handle.done
    assert eng.metrics.host_tier_promote_tokens_charged \
        == charged_before + h * 3


def test_min_chain_blocks_policy(gpt_setup):
    """Spill-worthiness: chains shorter than min_chain_blocks are
    freed, not demoted."""
    model, variables = gpt_setup
    eng = _engine(model, variables, host=HostTierConfig(
        byte_budget=1 << 24, min_chain_blocks=3))
    eng.warmup()
    # 2-block prompts (16 tokens): every chain is below the floor.
    prompts = _prompts(12, length=16, seed=3)
    for _ in range(3):
        _serve_all(eng, prompts)
    assert eng.metrics.prefix_evictions > 0
    assert eng.metrics.host_tier_spills == 0


# ------------------------------------------------------------ resilience
def test_fault_storm_at_host_promote_replays_token_exact(
        gpt_setup, pin_zero_recompiles):
    model, variables = gpt_setup
    plan = FaultPlan(seed=7, transient_rate=1.0, sites=["host_promote"],
                     max_random_injections=4, sleep_fn=_no_sleep)
    eng = pin_zero_recompiles(_engine(model, variables, fault_plan=plan,
                                      backoff_sleep=_no_sleep))
    prompts = _prompts()
    refs = [ref_greedy(model, variables, p, 4) for p in prompts]
    for _ in range(3):
        assert _serve_all(eng, prompts) == refs
    assert eng.metrics.retries + eng.metrics.replays > 0
    assert eng._host.pins_outstanding == 0, "fault-unwind leaked a host pin"


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_chaos_matrix_faults_at_host_promote(gpt_setup, seed,
                                             pin_zero_recompiles):
    """The ISSUE 13 chaos matrix: seeded transient storms aimed at the
    promotion site while chains cycle through the tier — every request
    terminal, every stream token-exact, zero recompiles, zero leaked
    host pins."""
    model, variables = gpt_setup
    plan = FaultPlan(seed=seed, transient_rate=0.5,
                     sites=["host_promote"], max_random_injections=6,
                     sleep_fn=_no_sleep)
    eng = pin_zero_recompiles(_engine(model, variables,
                                      fault_plan=plan,
                                      backoff_sleep=_no_sleep))
    prompts = _prompts(seed=seed)
    refs = [ref_greedy(model, variables, p, 4) for p in prompts]
    for _ in range(3):
        assert _serve_all(eng, prompts) == refs
    assert eng._host.pins_outstanding == 0


@pytest.mark.chaos
def test_kill_mid_promotion_drain_restores_token_exact(gpt_setup):
    """A KILL at the host_promote site unwinds out of step() like a
    real crash while the tier is populated; the drain snapshot (taken
    on the dying engine) restores into a FRESH tiered engine
    token-exactly — the tier's contents die with the process and that
    must not matter."""
    model, variables = gpt_setup
    prompts = _prompts()  # enough chains to overflow either pool
    refs = [ref_greedy(model, variables, p, 6) for p in prompts]
    plan = FaultPlan(scheduled=[
        FaultSpec(step=s, site="host_promote", kind=FaultKind.KILL)
        for s in range(200)])
    eng = _engine(model, variables, fault_plan=plan,
                  backoff_sleep=_no_sleep)
    eng.warmup()
    _serve_all(eng, prompts, n_new=6)  # cold pass: no promotions yet
    assert eng.metrics.host_tier_spills > 0
    handles = [eng.submit(p, 6) for p in prompts]  # hits → promotion
    killed = False
    for _ in range(2000):
        if all(h.done for h in handles):
            break
        try:
            eng.step()
        except KillPoint:
            killed = True
            break
    assert killed, "no promotion happened — the kill never fired"
    snapshot = eng.drain()
    fresh = _engine(model, variables)
    fresh.warmup()
    restored = fresh.restore(snapshot)
    fresh.run(max_steps=5000)
    assert all(h.done for h in restored)
    by_prompt = {tuple(h.request.prompt): list(h.tokens)
                 for h in restored}
    for p, ref in zip(prompts, refs):
        assert by_prompt[tuple(int(t) for t in p)] == ref


def test_drain_restore_with_tier_populated(gpt_setup):
    """A graceful drain while the tier holds chains restores into a
    fresh tiered engine token-exactly (KV is a pure function of the
    tokens; the tier is an optimization, never restore state)."""
    model, variables = gpt_setup
    prompts = _prompts()
    refs = [ref_greedy(model, variables, p, 8) for p in prompts]
    eng = _engine(model, variables)
    eng.warmup()
    _serve_all(eng, prompts)
    assert eng.metrics.host_tier_spills > 0
    handles = [eng.submit(p, 8) for p in prompts]
    for _ in range(3):
        eng.step()
    snapshot = eng.drain()
    fresh = _engine(model, variables)
    fresh.warmup()
    restored = fresh.restore(snapshot)
    fresh.run(max_steps=5000)
    assert [list(h.tokens) for h in restored] \
        == [refs[[tuple(int(t) for t in p) for p in prompts].index(
            tuple(h.request.prompt))] for h in restored]
    assert all(h.state is RequestState.FINISHED for h in restored)


# ---------------------------------------------------------- exposition
def test_exposition_round_trips_host_tier_series(gpt_setup):
    model, variables = gpt_setup
    eng = _engine(model, variables)
    eng.warmup()
    prompts = _prompts()
    _serve_all(eng, prompts)
    _serve_all(eng, prompts)
    text = serve_exposition(eng.metrics, eng)
    samples, types = parse_prometheus_text(text)
    for name in ("pddl_serve_host_tier_spills_total",
                 "pddl_serve_host_tier_hits_total",
                 "pddl_serve_host_tier_promotions_total",
                 "pddl_serve_host_tier_promote_tokens_charged_total"):
        assert (name, ()) in samples, name
        assert types[name] == "counter"
    assert samples[("pddl_serve_host_tier_bytes_resident", ())] \
        == eng.metrics.host_tier_bytes_resident
    assert types["pddl_serve_host_tier_bytes_resident"] == "gauge"
    assert samples[("pddl_serve_engine_host_tier", ())] == 1.0
    assert samples[("pddl_serve_engine_host_tier_bytes_resident", ())] \
        == eng.host_tier_bytes_resident
    assert ("pddl_serve_engine_compile_counts",
            (("key", "host_promote"),)) in samples


# ------------------------------------------------------------- transfer
def test_chain_wire_roundtrip_and_cross_engine_import(gpt_setup):
    """export → JSON → import on a sibling engine: the pulled chain
    promotes there and the stream stays token-exact (token identity is
    bit identity under the position-absolute cache contract)."""
    model, variables = gpt_setup
    prompts = _prompts(2)
    ref = ref_greedy(model, variables, prompts[0], 4)
    src = _engine(model, variables)
    src.warmup()
    _serve_all(src, prompts)
    entry = src.export_prefix_chain(prompts[0])
    assert entry is not None
    entry = json.loads(json.dumps(entry))  # the pipe's JSON round trip
    toks, blocks = kv_chain_from_wire(entry)
    assert toks == [int(t) for t in prompts[0][:len(blocks) * BS]]
    assert kv_chain_from_wire(kv_chain_to_wire(toks, blocks))[0] == toks
    dst = _engine(model, variables)
    dst.warmup()
    assert dst.import_prefix_chain(entry) == len(blocks)
    h = dst.submit(prompts[0], 4)
    dst.run(max_steps=2000)
    assert list(h.tokens) == ref
    assert dst.metrics.host_tier_hits == 1
    assert dst.metrics.prefill_tokens_saved >= len(blocks) * BS
    # An untiered sibling refuses gracefully — BOTH directions: import
    # is a counted no-op, and export answers None instead of reaching
    # for the tier's jitted gather (a TypeError here used to kill the
    # whole worker process when a pull-armed router met a tier-less
    # replica).
    plain = _engine(model, variables, host=None)
    plain.warmup()
    assert plain.import_prefix_chain(entry) == 0
    _serve_all(plain, prompts)
    assert plain.export_prefix_chain(prompts[0]) is None


def test_shadow_models_the_second_tier():
    shadow = _ShadowIndex(BS, capacity_blocks=3, host_capacity_blocks=64)
    p1 = list(range(4 * BS))
    shadow.observe(p1, max_blocks=4)      # capacity 3: stores 3 blocks
    assert shadow.match_blocks(p1, 4) == 3
    p2 = [500 + t for t in range(4 * BS)]
    shadow.observe(p2, max_blocks=4)      # evicts p1 into the host shadow
    assert shadow.match_blocks_host(p1, 4) > 0
    blind = _ShadowIndex(BS, capacity_blocks=3)
    blind.observe(p1, max_blocks=4)
    blind.observe(p2, max_blocks=4)
    assert blind.match_blocks_host(p1, 4) == 0


def _fleet_factory(model, variables):
    def factory():
        return ServeEngine(model, variables, max_slots=2, prefill_len=32,
                           prefix_cache_blocks=24, prefix_block_size=BS,
                           prefix_chunk=BS, host_tier=1 << 24)
    return factory


def test_fleet_chain_pull_eliminates_duplicate_prefill(gpt_setup):
    """The 2-replica leg: replica A holds the warm shared prefix, load
    pressure escapes an interactive request to cold replica B. Shadow-
    blind, B re-prefills the prefix (duplicate work); with the pull, B
    imports A's chain and PROMOTES instead — and the stream is
    identical either way."""
    model, variables = gpt_setup
    rng = np.random.default_rng(11)
    shared = rng.integers(0, 32, size=24).astype(np.int32)
    probe = np.concatenate([shared[:16],
                            rng.integers(0, 32, 8).astype(np.int32)])

    def run(pull):
        fleet = FleetRouter(
            [LocalReplica(0, _fleet_factory(model, variables)),
             LocalReplica(1, _fleet_factory(model, variables))],
            affinity_block_size=BS, interactive_reroute_load=1,
            shadow_host_capacity_blocks=1024,
            chain_pull_blocks=(2 if pull else None))
        fleet.warmup()
        h1 = fleet.submit(list(shared), 4, priority=Priority.BATCH)
        while not h1.done:
            fleet.step()
        warm = h1.replica_id
        busy = [fleet.submit(list(shared), 24, priority=Priority.BATCH)
                for _ in range(2)]
        h2 = fleet.submit(list(probe), 4,
                          priority=Priority.INTERACTIVE)
        while not (h2.done and all(b.done for b in busy)):
            fleet.step()
        cold_slot = next(s for s in fleet.replicas
                         if s.replica_id != warm)
        assert h2.replica_id == cold_slot.replica_id  # load escape fired
        saved = cold_slot.driver.engine.metrics.prefill_tokens_saved
        pulls = fleet.metrics.chain_pulls
        fleet.close()
        return list(h2.tokens), saved, pulls

    t_blind, saved_blind, pulls_blind = run(False)
    t_pull, saved_pull, pulls_pull = run(True)
    assert t_blind == t_pull
    assert pulls_blind == 0 and pulls_pull >= 1
    assert saved_blind == 0          # duplicate prefill paid in full
    assert saved_pull >= 2 * BS      # the pulled chain was promoted


def test_fleet_exposition_carries_tier_counters(gpt_setup):
    model, variables = gpt_setup
    fleet = FleetRouter(
        [LocalReplica(0, _fleet_factory(model, variables))],
        affinity_block_size=BS, shadow_host_capacity_blocks=64,
        chain_pull_blocks=2)
    samples, types = parse_prometheus_text(fleet_exposition(fleet))
    for name in ("pddl_fleet_routed_host_tier_total",
                 "pddl_fleet_chain_pulls_total",
                 "pddl_fleet_chain_pull_tokens_total"):
        assert (name, ()) in samples, name
        assert types[name] == "counter"
    fleet.close()


def test_router_routes_to_host_tier_holder(gpt_setup):
    """No replica holds the prefix in HBM, one holds it in host RAM:
    the route label is host_tier and the counter moves."""
    model, variables = gpt_setup
    fleet = FleetRouter(
        [LocalReplica(0, _fleet_factory(model, variables)),
         LocalReplica(1, _fleet_factory(model, variables))],
        affinity_block_size=BS, shadow_host_capacity_blocks=1024)
    fleet.warmup()
    prompt = list(range(24))
    # White-box shadow state: replica 1 once held the chain, its
    # device shadow evicted it to the host shadow.
    fleet.replicas[1].shadow.observe_host(prompt, max_blocks=3)
    slot, how, _, _ = fleet._route(
        prompt, None, [s for s in fleet.replicas if s.available])
    assert how == "host_tier" and slot.replica_id == 1
    h = fleet.submit(prompt, 2)
    while not h.done:
        fleet.step()
    assert h.replica_id == 1
    assert fleet.metrics.routed_host_tier == 1
    fleet.close()
