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
