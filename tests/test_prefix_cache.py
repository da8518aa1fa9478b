"""Prefix-aware KV reuse (`pddl_tpu/serve/kvcache/`), CPU.

The contracts under test:

- **Token-exactness** of prefix-HIT admissions lives in
  `tests/test_serve_paged.py` (``test_paged_token_exact_*``: GPT, Llama,
  int8, each at the default pool and at the pool's floor).
- **Suffix-priced admission**: the prefill-token budget charges the
  UNCACHED suffix, so shared-prefix requests co-admit where cold ones
  serialize.
- **Refcount/eviction invariants**: property-tested over randomized op
  sequences on the radix index — block accounting exact, pinned chains
  never evicted, interior nodes outlive children, LRU order respected.
- **Fixed-shape discipline**: the engine (tick, first-token sample,
  narrow+wide chunk-prefill) compiles nothing new after warmup across a
  hit/miss/evict workload (`pin_zero_recompiles` fixture from conftest).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import generate, tiny_gpt
from pddl_tpu.models.llama import tiny_llama
from pddl_tpu.ops.attention import cache_blocks_gather, cache_blocks_scatter
from pddl_tpu.serve import RadixPrefixCache, ServeEngine
from pddl_tpu.serve.kvcache.radix import SCRATCH_BLOCK
from conftest import ref_greedy as _ref_greedy


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def test_zero_recompiles_across_hit_miss_evict(gpt_setup,
                                               pin_zero_recompiles):
    """Every resident program stays at one executable through cold
    admissions, full and partial hits, and pool-pressure evictions (a
    pool at its floor, too small for the workload's distinct
    prefixes)."""
    model, variables = gpt_setup
    eng = pin_zero_recompiles(
        ServeEngine(model, variables, max_slots=2, prefill_len=16,
                    prefix_cache_blocks=17))  # the floor: 16 + scratch
    for i in range(12):  # 24 distinct full blocks: eviction churn
        p = (np.arange(16) * 7 + 11 * i + i // 3) % 32
        h = eng.submit(p, 4)
        eng.run(max_steps=100)
        assert h.tokens == _ref_greedy(model, variables, p, 4)
    h = eng.submit(p, 4)  # and one full-chain hit
    eng.run(max_steps=100)
    assert h.tokens == _ref_greedy(model, variables, p, 4)
    assert eng.metrics.prefix_lookups == 13
    assert eng.metrics.prefix_hits == 1
    assert eng.metrics.prefix_evictions > 0  # pressure actually happened


def test_suffix_priced_admission_budget(gpt_setup):
    """The budget charges the uncached suffix: two shared-prefix
    requests co-admit under a budget that would serialize them cold
    (the cold-index control engine proves the discrimination)."""
    model, variables = gpt_setup
    shared = (np.arange(8) * 3 + 2) % 32

    def prompts():
        return (np.concatenate([shared, [5, 9]]),
                np.concatenate([shared, [21, 4]]))

    # Prefix engine: seed the cache, then both suffix-2 requests fit a
    # 6-token budget in ONE admission burst.
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      prefill_token_budget=6)
    seed = eng.submit(np.concatenate([shared, [1, 2]]), 2)
    eng.run(max_steps=50)
    assert seed.done
    a, b = (eng.submit(p, 4) for p in prompts())
    eng.step()
    assert len(a.tokens) >= 1 and len(b.tokens) >= 1  # both admitted

    # Control: identical budget, nothing cached yet — the second
    # request's full 10-token prompt exceeds the burst budget and waits.
    ctl = ServeEngine(model, variables, max_slots=2, prefill_len=16,
                      prefill_token_budget=6)
    c, d = (ctl.submit(p, 4) for p in prompts())
    ctl.step()
    assert len(c.tokens) >= 1
    assert d.tokens == []  # still queued behind the budget


# ------------------------------------------------------------- primitives
def test_gather_scatter_roundtrip():
    """cache_blocks_scatter then cache_blocks_gather reproduces the row
    tokens bit-exactly at block granularity (the device copy contract
    both directions of the host tier rest on)."""
    rng = np.random.default_rng(0)
    pool = jnp.zeros((6, 2, 4, 3), jnp.float32)  # [N, H, bs, D]
    row = jnp.asarray(rng.normal(size=(1, 2, 32, 3)), jnp.float32)
    ids = jnp.asarray([2, 5, 1], jnp.int32)
    pool = cache_blocks_scatter(pool, row, ids, 1)  # tokens [4, 16)
    got = cache_blocks_gather(pool, ids)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(row[:, :, 4:16]))
    # Scratch-padded scatter must not disturb real blocks.
    pool2 = cache_blocks_scatter(pool, row,
                                 jnp.asarray([0, 0, 0], jnp.int32), 0)
    np.testing.assert_array_equal(
        np.asarray(cache_blocks_gather(pool2, ids)),
        np.asarray(row[:, :, 4:16]))


def test_gather_scatter_validation():
    pool = jnp.zeros((4, 2, 4, 3))
    with pytest.raises(ValueError, match="block_ids"):
        cache_blocks_gather(pool, jnp.zeros((2, 2), jnp.int32))
    with pytest.raises(ValueError, match="batch-1"):
        cache_blocks_scatter(pool, jnp.zeros((2, 2, 8, 3)),
                             jnp.zeros(1, jnp.int32), 0)


# ------------------------------------------------------------ radix index
def _chain_tokens(rng, n_blocks, bs):
    return rng.integers(0, 8, size=n_blocks * bs).tolist()


def test_radix_refcount_eviction_invariants_property():
    """Randomized op sequences (match / extend / pin / unpin /
    allocate-with-eviction) against the invariants the engine relies
    on. Seeded — failures reproduce."""
    rng = np.random.default_rng(1234)
    bs, num_blocks = 4, 12
    idx = RadixPrefixCache(bs, num_blocks)
    pinned = []      # nodes we hold pins on

    def protected_ids():
        """Block ids on any pinned chain's root path — never evictable."""
        out = set()
        for node in pinned:
            walk = node
            while walk is not idx._root:
                out.add(walk.block_id)
                walk = walk.parent
        return out

    def live_ids():
        out, stack = [], [idx._root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n is not idx._root:
                out.append(n.block_id)
        return out

    prompts = [_chain_tokens(rng, rng.integers(1, 4), bs)
               for _ in range(8)]
    for _ in range(300):
        op = rng.integers(0, 4)
        if op == 0:  # match + maybe extend with fresh blocks
            toks = prompts[rng.integers(len(prompts))]
            m = idx.match(toks)
            want = len(toks) // bs - m.n_blocks
            if want > 0:
                ids = idx.allocate(want)
                for bid in ids:
                    assert bid != SCRATCH_BLOCK
                    assert bid not in live_ids(), "double-issued block"
                if ids:
                    idx.extend(m.node, toks[m.n_blocks * bs:
                                            (m.n_blocks + len(ids)) * bs],
                               ids)
        elif op == 1:  # pin a matched chain
            toks = prompts[rng.integers(len(prompts))]
            m = idx.match(toks)
            if m.node is not idx._root:
                idx.pin(m.node)
                pinned.append(m.node)
        elif op == 2 and pinned:  # unpin
            idx.unpin(pinned.pop(rng.integers(len(pinned))))
        else:  # allocation pressure → forced LRU eviction of unpinned
            before = set(live_ids())
            safe = protected_ids()
            ids = idx.allocate(rng.integers(1, 4))
            idx._free.extend(ids)  # give them straight back
            evicted = before - set(live_ids())
            # eviction must never reach a pinned chain's blocks
            assert not (evicted & safe), (evicted, safe)
        # -------- invariants, after every op --------
        ids_now = live_ids()
        assert len(ids_now) == len(set(ids_now)), "block owned twice"
        assert SCRATCH_BLOCK not in ids_now
        assert idx.blocks_live + idx.blocks_free == num_blocks - 1
        assert idx.blocks_live == len(ids_now)
        # pinned chains fully alive: every pinned node's root path holds
        # ref > 0 and is still attached
        for node in pinned:
            walk = node
            while walk is not idx._root:
                assert walk.ref > 0
                assert walk.parent.children[walk.key] is walk
                walk = walk.parent
    # draining every pin leaves the whole tree evictable: allocation
    # pressure empties it without losing a single block
    while pinned:
        idx.unpin(pinned.pop())
    freed = idx.allocate(num_blocks - 1)
    assert len(freed) == num_blocks - 1  # everything evicted, none lost
    assert not idx._root.children  # tree fully drained


def test_radix_lru_order_and_pin_protection():
    bs = 2
    idx = RadixPrefixCache(bs, 4)  # 3 usable blocks
    a = idx.match([1, 1]); ids_a = idx.allocate(1)
    na = idx.extend(a.node, [1, 1], ids_a)
    b = idx.match([2, 2]); ids_b = idx.allocate(1)
    idx.extend(b.node, [2, 2], ids_b)
    c = idx.match([3, 3]); ids_c = idx.allocate(1)
    idx.extend(c.node, [3, 3], ids_c)
    idx.pin(na)
    idx.match([2, 2])  # refresh b — chain [1,1] is pinned, [3,3] is LRU
    got = idx.allocate(1)
    assert got == ids_c  # LRU unpinned leaf evicted first
    assert idx.match([1, 1]).n_blocks == 1  # pinned chain survived
    assert idx.match([3, 3]).n_blocks == 0
    # with every surviving chain pinned, allocation degrades gracefully
    # to empty (the engine then donates nothing) instead of failing
    idx.pin(idx.match([2, 2]).node)
    assert idx.allocate(3) == []
    with pytest.raises(RuntimeError, match="underflow"):
        idx.unpin(na); idx.unpin(na)


def test_radix_validation():
    with pytest.raises(ValueError, match="num_blocks"):
        RadixPrefixCache(4, 1)
    idx = RadixPrefixCache(4, 4)
    with pytest.raises(ValueError, match="scratch"):
        idx.extend(idx._root, [1, 2, 3, 4], [SCRATCH_BLOCK])
    with pytest.raises(ValueError, match="full"):
        idx.extend(idx._root, [1, 2], idx.allocate(1))


def test_engine_validation():
    """Loud config errors: unusable block size, chunk/positions clash."""
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    variables = {"params": model.init(jax.random.key(2), prompt,
                                      train=False)["params"]}
    with pytest.raises(ValueError, match="cacheable block"):
        ServeEngine(model, variables, max_slots=1, prefill_len=8,
                    prefix_block_size=8)
    with pytest.raises(ValueError, match="prefix_chunk"):
        ServeEngine(model, variables, max_slots=1, prefill_len=32,
                    prefix_block_size=8, prefix_chunk=48)


# ------------------------------------------- reclaim: the LRU heap vs the walk
class _WalkOracle(RadixPrefixCache):
    """The index as it reclaimed before it kept its evictable leaves in
    LRU order: ``_reclaim`` below is that version's body, copied — a
    depth-first walk of the whole index per pass, a sort of the
    evictable leaves — and lives on only here, as the reference the
    heap is held to. (The heap bookkeeping of the base class still runs
    underneath and is never read.)"""

    def _reclaim(self, need, demote=True):
        call_hook = demote and self.on_evict is not None
        all_taken = []
        marked = set()
        while need > 0:
            victims = []
            stack = [self._root]
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                if (node is not self._root and node.ref == 0
                        and id(node) not in marked
                        and all(id(c) in marked
                                for c in node.children.values())):
                    victims.append(node)
            if not victims:
                break
            victims.sort(key=lambda v: v.last_access)
            taken = victims[:need]
            all_taken.extend(taken)
            marked.update(id(v) for v in taken)
            need -= min(need, len(victims))
        if not all_taken:
            return
        if call_hook:
            self.on_evict(all_taken)
        for victim in all_taken:
            del victim.parent.children[victim.key]
            self._free.append(victim.block_id)
            self.evictions += 1


def _nodes(idx):
    out, stack = [], [idx._root]
    while stack:
        n = stack.pop()
        stack.extend(n.children.values())
        if n is not idx._root:
            out.append(n)
    return out


def _recording_hook(idx, log):
    """An ``on_evict`` that writes down what it is given — each victim's
    chain and block id, one list a call — and checks the contract: every
    victim still attached all the way to the root, its id not yet on
    the free list."""
    def hook(victims):
        free = set(idx._free)
        for v in victims:
            walk = v
            while walk is not idx._root:
                assert walk.parent.children[walk.key] is walk
                walk = walk.parent
            assert v.block_id not in free
        log.append([(tuple(idx.chain_tokens(v)), v.block_id)
                    for v in victims])
    return hook


def _check_heap(idx):
    """What the heap has to hold for a reclaim to find every victim:
    every evictable leaf queued, no node queued twice, no entry later
    than its node's stamp, never more entries than nodes — and no two
    evictable leaves under one stamp, so LRU order needs no tie-break."""
    nodes = _nodes(idx)
    evictable = [n for n in nodes if n.ref == 0 and not n.children]
    stamps = [n.last_access for n in evictable]
    assert len(stamps) == len(set(stamps)), "two evictable leaves share a stamp"
    queued = [node for _, _, node in idx._lru]
    assert len(queued) == len({id(n) for n in queued}), "node queued twice"
    assert all(n.queued for n in queued)
    assert {id(n) for n in evictable} <= {id(n) for n in queued}
    assert {id(n) for n in queued} <= {id(n) for n in nodes}
    assert all(stamp <= node.last_access for stamp, _, node in idx._lru)
    assert len(idx._lru) <= idx.blocks_live


@pytest.mark.parametrize("num_blocks,bs,vocab,steps",
                         [(24, 2, 3, 400), (161, 4, 2, 900)],
                         ids=["23blocks", "160blocks"])
@pytest.mark.parametrize("seed", range(8))
def test_reclaim_evicts_what_the_walk_evicted(seed, num_blocks, bs, vocab,
                                              steps):
    """Seeded random histories of all eight public operations, driven
    through the index and through the walk it replaced side by side:
    equal victim lists in order (all passes of a reclaim, one hook call),
    equal free lists, equal ``evictions``, after every operation."""
    rng = np.random.default_rng(10_000 * num_blocks + seed)
    new, old = RadixPrefixCache(bs, num_blocks), _WalkOracle(bs, num_blocks)
    logs = ([], [])
    hooks = tuple(_recording_hook(idx, log)
                  for idx, log in zip((new, old), logs))
    for idx, hook in zip((new, old), hooks):
        idx.on_evict = hook
    pins = []       # (node in new, node in old), pinned once each
    held = []       # ids allocated and not yet attached or released

    def prompt():
        return rng.integers(0, vocab,
                            size=bs * int(rng.integers(1, 9))).tolist()

    def both(fn):
        return fn(new), fn(old)

    def same_place(a, b):
        assert new.chain_tokens(a) == old.chain_tokens(b)

    for _ in range(steps):
        op = int(rng.integers(0, 13))
        if op <= 1:     # match, capped or not (restamps a root path)
            toks = prompt()
            cap = [None, 0, 1, 3][int(rng.integers(0, 4))]
            a, b = both(lambda i: i.match(toks, max_blocks=cap))
            assert a.block_ids == b.block_ids
            same_place(a.node, b.node)
        elif op in (2, 10, 11, 12):   # admit: match, pin, allocate, descend, extend
            toks = prompt()
            a, b = both(lambda i: i.match(toks, max_blocks=2))
            m = a.n_blocks
            new.pin(a.node), old.pin(b.node)
            want = len(toks) // bs - m
            ids_a, ids_b = both(lambda i: i.allocate(want))
            assert ids_a == ids_b
            (da, sa), (db, sb) = (new.descend(a.node, toks, m),
                                  old.descend(b.node, toks, m))
            assert sa == sb
            same_place(da, db)
            dup = min(sa - m, len(ids_a))       # already stored: hand back
            new.release(ids_a[:dup]), old.release(ids_b[:dup])
            rest = ids_a[dup:]
            if rng.integers(0, 4) == 0 and len(rest) > 1:
                cut = int(rng.integers(1, len(rest)))   # partial donation
                new.release(rest[cut:]), old.release(rest[cut:])
                rest = rest[:cut]
            chunk = toks[sa * bs:(sa + len(rest)) * bs]
            ta, tb = new.extend(da, chunk, rest), old.extend(db, chunk, rest)
            same_place(ta, tb)
            new.unpin(a.node), old.unpin(b.node)
            if rng.integers(0, 2):              # a live slot keeps the tip
                new.pin(ta), old.pin(tb)
                pins.append((ta, tb))
        elif op == 3:   # the same chunk stored twice: extend's dedup
            toks = prompt()[:bs]
            ids_a, ids_b = both(lambda i: i.allocate(1))
            assert ids_a == ids_b
            ta = new.extend(new._root, toks, ids_a)
            tb = old.extend(old._root, toks, ids_b)
            same_place(ta, tb)
        elif op == 4 and pins:   # a slot ends
            a, b = pins.pop(int(rng.integers(len(pins))))
            new.unpin(a), old.unpin(b)
        elif op == 5:   # pin wherever a match ends, leaf or not
            toks = prompt()
            a, b = both(lambda i: i.match(toks))
            if a.node is not new._root:
                new.pin(a.node), old.pin(b.node)
                pins.append((a.node, b.node))
        elif op in (6, 7):  # pressure: 1 .. more than is evictable
            top = 4 if op == 6 else 16
            if rng.integers(0, num_blocks // 4) == 0:
                top = num_blocks + 4
            n = int(rng.integers(1, top))
            ids_a, ids_b = both(lambda i: i.allocate(n))
            assert ids_a == ids_b
            if rng.integers(0, 2):
                held.extend(ids_a)              # a slot's private blocks
            else:
                new.release(ids_a), old.release(ids_b)
        elif op == 8 and held:   # private blocks come back
            k = int(rng.integers(1, len(held) + 1))
            new.release(held[:k]), old.release(held[:k])
            del held[:k]
        elif op == 9 and rng.integers(0, num_blocks // 3) == 0:   # degraded flush
            calls = len(logs[0])
            assert new.flush_unpinned() == old.flush_unpinned()
            assert len(logs[0]) == calls, "the flush called the hook"
        assert logs[0] == logs[1]
        assert list(new._free) == list(old._free)
        assert new.evictions == old.evictions
        assert new.blocks_live == len(_nodes(new)) + len(held)
        _check_heap(new)
    # The history met pressure: reclaims that evicted, some of them in
    # more than one pass (a victim's parent among the same call's).
    multi_pass = sum(any(c[:-bs] in {d for d, _ in call} for c, _ in call)
                     for call in logs[0])
    assert len(logs[0]) >= 15 and multi_pass >= 2, (len(logs[0]), multi_pass)
    assert new.reclaims >= len(logs[0])
    # Every pin released, everything is evictable: both drain alike.
    for a, b in pins:
        new.unpin(a), old.unpin(b)
    new.release(held), old.release(held)
    assert new.allocate(num_blocks) == old.allocate(num_blocks)
    assert logs[0] == logs[1]
    assert not new._root.children and not new._lru
    assert new.blocks_free == 0


def _filled(n_chains, depth=4, bs=2):
    """An index whose every block sits in one of ``n_chains`` distinct
    unpinned chains of ``depth``; the free list empty."""
    idx = RadixPrefixCache(bs, n_chains * depth + 1)
    for c in range(n_chains):
        toks = [c] * bs + [j for j in range(1, depth) for _ in range(bs)]
        idx.extend(idx._root, toks, idx.allocate(depth))
    assert idx.blocks_free == 0 and idx.reclaims == 0
    return idx


def test_reclaim_cost_does_not_grow_with_the_index():
    """No clock: ``reclaim_visited`` counts what a reclaim examined. A
    block handed out at a shortfall costs the same small constant in an
    index of 200 blocks and of 20,000; ``k`` blocks cost O(k + parents
    exposed); a leaf matched since it was queued costs one visit more,
    once."""
    seen = {}
    for n_chains in (50, 5_000):
        idx = _filled(n_chains)
        one = []
        for _ in range(3):      # a chain's tip, its parent exposed; ...
            before = idx.reclaim_visited
            idx.release(idx.allocate(1))
            idx.allocate(1)     # (take the freed one back: short again)
            one.append(idx.reclaim_visited - before)
        before = idx.reclaim_visited
        got = idx.allocate(40)
        many = idx.reclaim_visited - before
        assert len(got) == 40
        idx.match([7] * 2 + [1] * 2)    # restamps chain 7's path
        before = idx.reclaim_visited
        idx.allocate(4)
        after_match = idx.reclaim_visited - before
        seen[n_chains] = (one, many, after_match, idx.reclaims,
                          idx.evictions)
        assert idx.evictions == 3 + 40 + 4
    assert seen[50] == seen[5_000]
    one, many, after_match, reclaims, _ = seen[50]
    assert max(one) <= 2 and many <= 2 * 40 and after_match <= 2 * 4 + 1
    assert reclaims == 3 + 1 + 1


def test_evicting_hook_that_raises_leaves_no_half_taken_victim():
    idx = _filled(3)

    def boom(victims):
        raise RuntimeError("spill failed")
    idx.on_evict = boom
    with pytest.raises(RuntimeError, match="spill failed"):
        idx.allocate(2)
    idx.on_evict = None
    _check_heap(idx)
    assert idx.blocks_live == len(_nodes(idx)) == 10
    assert len(idx.allocate(12)) == 12 and not idx._root.children
