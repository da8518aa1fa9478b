"""Paged attention (`ops/attention.paged_*`) and the engine's block pool.

The contracts under test:

- **Op-level numerics**: `paged_decode_attention`'s jnp reference path
  equals dense `decode_attention` over the equivalent contiguous cache
  (GQA, per-row depths, sliding window, multi-token chunks), and the
  Pallas kernel (interpret mode on CPU) equals the reference — the
  tier-1 oracle chain the TPU hot path hangs off.
- **Write discipline**: `paged_cache_insert` lands each token in its
  table-mapped block; padding junk beyond the table deflects to the
  scratch sink and can never corrupt a real block.
- **Engine token-exactness**: the engine — prefix hits PINNED in
  place, suffix blocks appended in place, donation a pure refcount
  hand-off — emits exactly what one-shot ``generate()`` emits, across
  GPT/Llama/int8, at the default pool and at the pool's floor, and
  across cold, prefix-hit, preempted, and replayed streams. Every
  exactness test also asserts the hit actually happened
  (``prefix_hits``/``prefill_tokens_saved``), so a silently-dead cache
  cannot pass vacuously.
- **Sharing with zero copies**: concurrent shared-prefix streams
  reference the SAME pool blocks (``blocks_shared`` > 0), admission
  records the gather bytes it no longer pays (``copy_bytes_avoided``),
  and a block-aligned repeat dedups onto the stored chain instead of
  growing the pool.
- **Resilience parity**: the 3-seed chaos matrix, drain/restore (v3
  snapshots carry block tables; v2 snapshots restore through the same
  replay path), and the zero-recompile pin all hold.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ref_greedy as _ref_greedy
from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.models.llama import tiny_llama
from pddl_tpu.obs.export import parse_prometheus_text, serve_exposition
from pddl_tpu.ops.attention import (
    attention_reference,
    decode_attention,
    paged_cache_insert,
    paged_decode_attention,
    paged_decode_attention_kernel,
    paged_kv_fuse,
)
from pddl_tpu.serve import ServeEngine
from pddl_tpu.serve.faults import FaultPlan
from pddl_tpu.serve.request import Priority, RequestState

pytestmark = pytest.mark.paged

_no_sleep = lambda s: None  # noqa: E731


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


# ------------------------------------------------------------- op level
def _random_paged(rng, b, hkv, bs, t, d):
    """A fused pool ``[N, Hkv, bs, 2D]`` + disjoint per-row linear
    tables + the DENSE K and V caches they spell (the oracle's view)."""
    n = 1 + b * t
    pool = jnp.asarray(rng.randn(n, hkv, bs, 2 * d), jnp.float32)
    table = np.zeros((b, t), np.int32)
    for i in range(b):
        table[i] = 1 + i * t + np.arange(t)
    dense = np.asarray(pool)[table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, t * bs, 2 * d)
    return pool, table, jnp.asarray(dense[..., :d]), jnp.asarray(dense[..., d:])


def test_paged_kv_fuse_lays_k_then_v():
    """K in lanes [0, D), V in [D, 2D): the one definition of a K/V pool
    leaf's last dimension."""
    rng = np.random.RandomState(9)
    k = jnp.asarray(rng.randn(2, 3, 4, 8), jnp.float32)
    v = jnp.asarray(rng.randn(2, 3, 4, 8), jnp.float32)
    kv = paged_kv_fuse(k, v)
    assert kv.shape == (2, 3, 4, 16)
    np.testing.assert_array_equal(np.asarray(kv[..., :8]), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(kv[..., 8:]), np.asarray(v))
    with pytest.raises(ValueError, match="differ"):
        paged_kv_fuse(k, v[:, :2])
    with pytest.raises(ValueError, match="value in"):
        paged_decode_attention(k[:, :, :1], kv[..., :8],
                               np.zeros((2, 1), np.int32), np.int32(0))


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_paged_reference_matches_dense_decode(hq, hkv):
    """Per-row depths (the serving tick's shape), MHA and GQA: the
    paged jnp path == decode_attention over the equivalent contiguous
    cache."""
    rng = np.random.RandomState(0)
    b, bs, t, d = 3, 4, 6, 8
    pool, table, kc, vc = _random_paged(rng, b, hkv, bs, t, d)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    index = np.array([5, 17, 0], np.int32)
    ref = decode_attention(q, kc, vc, index)
    got = paged_decode_attention(q, pool, table, index, kernel=False,
                                 blocks_per_chunk=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_paged_reference_multi_token_and_window():
    """The chunk-prefill shape (batch-1, s>1 at a scalar offset) and
    sliding-window masking both match the dense oracle."""
    rng = np.random.RandomState(1)
    b, hkv, bs, t, d, s = 1, 2, 4, 6, 8, 5
    pool, table, kc, vc = _random_paged(rng, b, hkv, bs, t, d)
    q = jnp.asarray(rng.randn(b, 4, s, d), jnp.float32)
    ref = decode_attention(q, kc, vc, np.int32(7))
    got = paged_decode_attention(q, pool, table, np.int32(7),
                                 kernel=False, blocks_per_chunk=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    q1 = jnp.asarray(rng.randn(b, 4, 1, d), jnp.float32)
    ref_w = decode_attention(q1, kc, vc, np.int32(13), window=6)
    got_w = paged_decode_attention(q1, pool, table, np.int32(13),
                                   window=6, kernel=False)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(ref_w),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_paged_kernel_matches_reference(hq, hkv):
    """The Pallas kernel (scalar-prefetched block table driving the
    pool index map), interpret mode on CPU, == the jnp oracle — per-row
    depths including a zero-depth (freshly admitted) row."""
    rng = np.random.RandomState(2)
    b, bs, t, d = 3, 4, 6, 8
    pool, table, kc, vc = _random_paged(rng, b, hkv, bs, t, d)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    index = np.array([23, 0, 8], np.int32)
    ref = paged_decode_attention(q, pool, table, index, kernel=False)
    got = paged_decode_attention_kernel(q, pool, table, index,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_paged_paths_match_attention_reference(path, hq, hkv, window):
    """Both readers of the fused leaf — the jnp sweep every prefill
    chunk takes and the kernel (interpret mode) — against the plain
    ``attention_reference`` over each row's virtual cache cut to its
    depth, MHA and GQA, with and without a sliding window."""
    rng = np.random.RandomState(4)
    b, bs, t, d = 3, 4, 6, 8
    pool, table, kc, vc = _random_paged(rng, b, hkv, bs, t, d)
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    index = np.array([23, 0, 9], np.int32)
    if path == "kernel":
        got = paged_decode_attention_kernel(q, pool, table, index,
                                            window=window, interpret=True)
    else:
        got = paged_decode_attention(q, pool, table, index, window=window,
                                     kernel=False, blocks_per_chunk=2)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_row_oracle(q, kc, vc, index, window)),
        rtol=1e-5, atol=1e-5)


def _row_oracle(q, kc, vc, index, window):
    """Each row through the plain ``attention_reference`` over its
    virtual cache cut to its depth (and its window's band)."""
    rows = []
    for i, depth in enumerate(np.asarray(index)):
        # The query sits at position `depth`, the last of depth + 1 keys:
        # k_offset 0 with one query row means causal is "all keys".
        lo = 0 if window is None else max(0, depth + 1 - window)
        rows.append(attention_reference(
            q[i:i + 1], kc[i:i + 1, :, lo:depth + 1],
            vc[i:i + 1, :, lo:depth + 1], causal=False))
    return jnp.concatenate(rows, axis=0)


# The group loop of the kernel, K blocks a step. At these sizes a block
# weighs hkv * 4 * 16 * 4 bytes, so the test names K by the bytes it sets
# `PAGED_GROUP_BYTES` to (K is computed from shapes; nothing takes it as
# an argument). bs = 4 throughout: K = 4 is a 16-key group.
_GROUP_CASES = {
    # K does not divide the table: T = 6, K = 4, the second group's tail
    # reads the table clamped at its last entry.
    "k_does_not_divide_table": dict(k=4, t=6, index=[23, 15, 16]),
    # Table narrower than K: the whole table is one group (K clamps to T).
    "table_smaller_than_k": dict(k=8, t=3, index=[11, 0, 5], want_k=3),
    # Depth ON a group's last key (15, 31) and on a group's first (16, 32).
    "depth_on_group_edges": dict(k=4, t=12, index=[15, 16, 31, 32]),
    # Band starts inside a group: depth 29, window 6 -> keys 24..29, first
    # block 6, groups of two blocks from there.
    "window_starts_inside_group": dict(k=2, t=12, index=[29, 26, 7],
                                       window=6),
    # Band skips whole groups: depth 47, window 5 -> blocks 10 and 11
    # only; ten blocks under the band are never fetched.
    "window_skips_whole_groups": dict(k=2, t=12, index=[47, 40, 3],
                                      window=5),
    # A window wider than some rows' depth beside rows it cuts.
    "window_wider_than_depth": dict(k=4, t=12, index=[5, 44, 20], window=24),
    # Parked rows (depth 0, every table entry scratch) between live ones,
    # first and last rows of the grid among them.
    "parked_rows_between_live": dict(k=2, t=8, index=[0, 30, 0, 0, 17, 0],
                                     parked=[0, 2, 3, 5]),
    # One block a group: the loop's smallest step.
    "one_block_groups": dict(k=1, t=6, index=[23, 0, 9]),
}


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (6, 1)])
@pytest.mark.parametrize("case", sorted(_GROUP_CASES))
def test_paged_kernel_group_loop(case, hq, hkv, monkeypatch):
    """The kernel's loop over groups of K blocks (interpret mode)
    against the jnp oracle and the plain reference, at the edges the
    one-block kernel never had: see `_GROUP_CASES`. rep 1, 2 and 6."""
    from pddl_tpu.ops import attention as attn

    spec = _GROUP_CASES[case]
    bs, d, t, window = 4, 8, spec["t"], spec.get("window")
    index = np.asarray(spec["index"], np.int32)
    b = len(index)
    rng = np.random.RandomState(len(case) + hq + hkv)
    pool, table, _, _ = _random_paged(rng, b, hkv, bs, t, d)
    for row in spec.get("parked", ()):
        table[row] = 0                       # all scratch, as the engine parks
    for row, depth in enumerate(index):      # entries past the depth: scratch
        table[row, depth // bs + 1:] = 0
    dense = np.asarray(pool)[table].transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, t * bs, 2 * d)
    kc, vc = jnp.asarray(dense[..., :d]), jnp.asarray(dense[..., d:])
    q = jnp.asarray(rng.randn(b, hq, 1, d), jnp.float32)
    monkeypatch.setattr(attn, "PAGED_GROUP_BYTES",
                        spec["k"] * hkv * bs * 2 * d * 4)
    assert attn.paged_blocks_per_group(pool.shape, 4, t) \
        == spec.get("want_k", spec["k"])
    got = paged_decode_attention_kernel(q, pool, table, index,
                                        window=window, interpret=True)
    ref = paged_decode_attention(q, pool, table, index, window=window,
                                 kernel=False, blocks_per_chunk=3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_row_oracle(q, kc, vc, index, window)),
        rtol=1e-5, atol=1e-5)


def test_paged_kernel_never_reads_past_a_rows_reach(monkeypatch):
    """A table entry the row cannot see — past its depth, or under its
    window's band — is neither fetched nor attended: poison (NaN) in
    every such block, and in scratch, leaves the output finite and equal
    to the clean pool's."""
    from pddl_tpu.ops import attention as attn

    rng = np.random.RandomState(11)
    b, hkv, bs, t, d, window = 3, 2, 4, 12, 8, 6
    pool, table, _, _ = _random_paged(rng, b, hkv, bs, t, d)
    q = jnp.asarray(rng.randn(b, 4, 1, d), jnp.float32)
    index = np.array([29, 47, 9], np.int32)
    seen = np.zeros(pool.shape[0], bool)
    for row, depth in enumerate(index):
        first = max(0, depth + 1 - window) // bs
        seen[table[row, first:depth // bs + 1]] = True
    poisoned = jnp.where(seen[:, None, None, None], pool, jnp.nan)
    monkeypatch.setattr(attn, "PAGED_GROUP_BYTES", 2 * hkv * bs * 2 * d * 4)
    clean = paged_decode_attention_kernel(q, pool, table, index,
                                          window=window, interpret=True)
    got = paged_decode_attention_kernel(q, poisoned, table, index,
                                        window=window, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


@pytest.mark.parametrize("window", [None, 6])
def test_paged_kernel_row_past_its_table_keeps_the_chain(window, monkeypatch):
    """A row whose counter lies past its table (outside the contract:
    the engine never sends one) still takes one group, so the rows
    after it find their first group's copies started: they come out
    right and the call returns."""
    from pddl_tpu.ops import attention as attn

    rng = np.random.RandomState(12)
    b, hkv, bs, t, d = 4, 2, 4, 6, 8
    pool, table, kc, vc = _random_paged(rng, b, hkv, bs, t, d)
    q = jnp.asarray(rng.randn(b, 4, 1, d), jnp.float32)
    index = np.array([9, t * bs + 40, 23, 0], np.int32)
    monkeypatch.setattr(attn, "PAGED_GROUP_BYTES", 2 * hkv * bs * 2 * d * 4)
    got = paged_decode_attention_kernel(q, pool, table, index,
                                        window=window, interpret=True)
    want = _row_oracle(q, kc, vc, index, window)
    keep = np.array([0, 2, 3])
    np.testing.assert_allclose(np.asarray(got)[keep], np.asarray(want)[keep],
                               rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("shape,itemsize,t,want", [
    ((3073, 20, 16, 128), 2, 64, 16),     # GPT-2-large's leaf: 80 KB a block
    ((8193, 4, 16, 256), 2, 1024, 32),    # SmallThinker's: 32 KB a block
    ((97, 12, 8, 128), 2, 128, 32),       # GPT-small, block 8: 24 KB
    ((19, 2, 4, 16), 4, 6, 6),            # a test's toy pool: the whole table
    ((9, 64, 64, 256), 2, 8, 1),          # a block over the target: one
])
def test_paged_blocks_per_group_follows_the_leaf(shape, itemsize, t, want):
    """K is a function of the leaf's shape and the table's width alone:
    the power of two nearest `PAGED_GROUP_BYTES`, at most the table."""
    from pddl_tpu.ops.attention import paged_blocks_per_group

    assert paged_blocks_per_group(shape, itemsize, t) == want


def _scatter_insert(pool, k, v, table, index):
    """The per-token two-dimension scatter the tick used to write with
    (``pool.at[bid, :, off].set``), over the fused leaf: the oracle of
    the block-granular write."""
    b, hkv, s, d = k.shape
    bs, t = pool.shape[2], table.shape[1]
    pos = np.broadcast_to(np.asarray(index).reshape(-1, 1)
                          + np.arange(s), (b, s))
    blk, off = pos // bs, pos % bs
    bid = np.where(blk < t,
                   np.take_along_axis(table, np.minimum(blk, t - 1), 1), 0)
    upd = jnp.moveaxis(jnp.concatenate([k, v], -1), 2, 1).reshape(
        b * s, hkv, 2 * d)
    return pool.at[bid.reshape(-1), :, off.reshape(-1)].set(upd)


@pytest.mark.parametrize("s", [1, 3, 6])
def test_paged_insert_matches_per_token_scatter(s):
    """The block-granular write against the old scatter's result on a
    random table: parked slots (all-scratch rows), a position on a block
    boundary, the last position of a block, and a position out of the
    table. ``s == 1`` is the tick, ``s > 1`` with per-row depths the
    speculative verify (a window that crosses into the next block and
    one that runs off the table). Everything but the scratch block —
    junk by contract, and where duplicate writes race — is equal."""
    rng = np.random.RandomState(5)
    b, hkv, bs, t, d = 6, 2, 4, 6, 8
    n = 1 + b * t
    pool = jnp.asarray(rng.randn(n, hkv, bs, 2 * d), jnp.float32)
    table = rng.permutation(np.arange(1, n)).reshape(b, t).astype(np.int32)
    table[1] = 0
    table[4] = 0                                   # parked slots
    index = np.array([4, 9, 7, t * bs - 1, 2, t * bs], np.int32)
    k = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, hkv, s, d), jnp.float32)
    got = paged_cache_insert(pool, paged_kv_fuse(k, v), table, index)
    want = _scatter_insert(pool, k, v, table, index)
    np.testing.assert_array_equal(np.asarray(got[1:]), np.asarray(want[1:]))
    # Row 0's first token really is where the table says.
    np.testing.assert_array_equal(
        np.asarray(got[table[0, 1], :, 0]),
        np.asarray(jnp.concatenate([k, v], -1)[0, :, 0]))


def test_paged_cache_insert_and_scratch_deflection():
    """Each slot's token lands at (table[pos//bs], pos%bs); positions
    past the table land in the scratch sink, and no real block outside
    the write set changes."""
    rng = np.random.RandomState(3)
    b, hkv, bs, t, d = 3, 2, 4, 6, 8
    pool, table, _, _ = _random_paged(rng, b, hkv, bs, t, d)
    index = np.array([5, 17, 0], np.int32)
    k = jnp.asarray(rng.randn(b, hkv, 1, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, hkv, 1, d), jnp.float32)
    out = paged_cache_insert(pool, paged_kv_fuse(k, v), table, index)
    for i in range(b):
        got = np.asarray(out[table[i, index[i] // bs], :, index[i] % bs])
        np.testing.assert_array_equal(got[:, :d], np.asarray(k[i, :, 0]))
        np.testing.assert_array_equal(got[:, d:], np.asarray(v[i, :, 0]))
    # Batch-1 multi-token chunk write (the block-granular RMW path):
    # tokens land contiguously at their (block, offset) homes...
    k2 = jnp.asarray(rng.randn(1, hkv, 10, d), jnp.float32)
    v2 = jnp.asarray(rng.randn(1, hkv, 10, d), jnp.float32)
    kv2 = paged_kv_fuse(k2, v2)
    start = 9  # mid-block start, spans blocks 2..4
    out2 = paged_cache_insert(pool, paged_kv_fuse(k2, v2), table[:1],
                              np.int32(start))
    for j in range(10):
        pos = start + j
        got = np.asarray(out2[table[0, pos // bs], :, pos % bs])
        np.testing.assert_array_equal(got, np.asarray(kv2[0, :, j]))
    # ...earlier tokens in the first span block survive the RMW...
    np.testing.assert_array_equal(
        np.asarray(out2[table[0, start // bs], :, : start % bs]),
        np.asarray(pool[table[0, start // bs], :, : start % bs]))
    # ...and a write running off the table's end deflects to scratch:
    # no real block outside row 0's own table changes.
    out3 = paged_cache_insert(pool, paged_kv_fuse(k2, v2), table[:1],
                              np.int32(t * bs - 3))
    np.testing.assert_array_equal(np.asarray(out3[1 + t:]),
                                  np.asarray(pool[1 + t:]))
    # The in-table tail tokens still landed.
    for j in range(3):
        pos = t * bs - 3 + j
        got = np.asarray(out3[table[0, pos // bs], :, pos % bs])
        np.testing.assert_array_equal(got, np.asarray(kv2[0, :, j]))


# --------------------------------------------------------- engine level
def _paged_engine(model, variables, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_len", 16)
    return ServeEngine(model, variables, **kw)


# The exactness workload's pool: the constructor's default (live worst
# case + two prompts a slot of cache) and the least the engine takes
# (2 slots x ceil(64 / 8) + scratch), where cached chains compete with
# live streams for every block.
_POOLS = {"auto": None, "floor": 2 * (64 // 8) + 1}
POOLS = pytest.mark.parametrize("pool", sorted(_POOLS))


def _exactness_workload(model, variables, ref_variables=None, **engine_kw):
    """Cold admit, full-chain re-hit, partial hit — all pinned
    token-exact against generate(); returns the engine so the caller
    can inspect telemetry."""
    ref_variables = ref_variables or variables
    eng = _paged_engine(model, variables, **engine_kw)
    base = (np.arange(12) * 5 + 1) % 32
    sibling = np.concatenate([base[:8], (np.arange(6) + 17) % 32])
    h_cold = eng.submit(base, 6)
    eng.run(max_steps=100)
    h_hit = eng.submit(base, 6)
    h_part = eng.submit(sibling, 6)
    eng.run(max_steps=100)
    assert h_cold.tokens == _ref_greedy(model, ref_variables, base, 6)
    assert h_hit.tokens == _ref_greedy(model, ref_variables, base, 6)
    assert h_part.tokens == _ref_greedy(model, ref_variables, sibling, 6)
    # Not vacuous: the hits referenced cached blocks in place.
    assert eng.metrics.prefix_hits >= 2
    assert eng.metrics.prefill_tokens_saved >= 2 * eng.prefix_block_size
    assert eng.metrics.copy_bytes_avoided > 0
    return eng


@pytest.fixture(scope="module")
def exact_gpt(gpt_setup):
    """One warmed paged GPT engine, driven through the exactness
    workload — shared by the pins that only READ its end state
    (program set, metrics exposition), so the suite compiles one
    engine for the three of them."""
    model, variables = gpt_setup
    return _exactness_workload(model, variables)


@POOLS
def test_paged_token_exact_gpt(gpt_setup, exact_gpt, pin_zero_recompiles,
                               pool):
    eng = pin_zero_recompiles(
        exact_gpt if pool == "auto" else _exactness_workload(
            *gpt_setup, prefix_cache_blocks=_POOLS[pool]))
    assert eng.paged
    # The program set: no gather, no insert, no donate scatter.
    assert set(eng.compile_counts()) == {
        "tick", "sample_first", "chunk_prefill", "chunk_prefill_wide"}


@POOLS
def test_paged_token_exact_llama(llama_setup, pool):
    """GQA + RoPE: post-RoPE keys are position-absolute, so a SHARED
    pool block read through two different slots' tables is bit-valid
    for both."""
    model, variables = llama_setup
    _exactness_workload(model, variables,
                        prefix_cache_blocks=_POOLS[pool])


@POOLS
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_paged_int8_token_exact(family, pool, gpt_setup, llama_setup):
    """int8 param_transform composes: what the pool stores is K/V,
    which int8 weight storage never touches; dequant runs inside the
    paged chunk/tick programs."""
    from pddl_tpu.ops.quant import dequantize, quantize_int8

    model, variables = gpt_setup if family == "gpt" else llama_setup
    qparams = quantize_int8(variables["params"], min_elems=128)
    dense = {"params": dequantize(qparams)}
    _exactness_workload(model, {"params": qparams}, ref_variables=dense,
                        param_transform=dequantize,
                        prefix_cache_blocks=_POOLS[pool])


def test_concurrent_shared_prefix_blocks_shared_in_place(gpt_setup):
    """Many live slots on one warm prefix: the matched blocks exist
    ONCE (blocks_shared counts them), table occupancy is reported, and
    every stream is token-exact — the capacity story of paging as
    an observable, not a slogan."""
    model, variables = gpt_setup
    eng = _paged_engine(model, variables, max_slots=4)
    base = (np.arange(12) * 5 + 1) % 32
    warm = eng.submit(base, 3)
    eng.run(max_steps=60)
    assert warm.tokens == _ref_greedy(model, variables, base, 3)
    variants = [np.concatenate([base[:8], [(i * 7 + 3) % 32]])
                for i in range(4)]
    hs = [eng.submit(v, 6) for v in variants]
    shared_seen, fill_seen = 0, 0.0
    while eng.has_work:
        eng.step()
        shared_seen = max(shared_seen, eng.blocks_shared)
        fill_seen = max(fill_seen, eng.block_table_fill)
    for h, v in zip(hs, variants):
        assert h.tokens == _ref_greedy(model, variables, v, 6)
    assert shared_seen >= 1          # the warm block was referenced >1x
    assert 0.0 < fill_seen <= 1.0
    assert eng.metrics.blocks_shared >= 0  # gauge stamped per tick
    assert eng.metrics.copy_bytes_avoided > 0


@pytest.mark.parametrize("pool", [None, 64 // 8 + 1], ids=["auto", "floor"])
def test_block_aligned_repeat_never_grows_a_paged_pool(gpt_setup, pool):
    """Donation dedup: a block-aligned prompt's tail block can never be
    MATCHED (the match cap leaves one suffix token) but it IS stored —
    re-admitting the same prompt swaps the slot's table onto the stored
    chain and RELEASES the duplicate private blocks, so repeats hold
    the pool at its deduplicated size (no eviction churn, live == 2),
    at the default pool and at the floor, where a leaked duplicate
    would LRU-evict a useful block."""
    model, variables = gpt_setup
    eng = _paged_engine(model, variables, max_slots=1,
                        prefix_cache_blocks=pool)
    p = (np.arange(16) * 3 + 5) % 32  # 2 full blocks at bs=8
    for _ in range(3):
        h = eng.submit(p, 3)
        eng.run(max_steps=50)
        assert h.tokens == _ref_greedy(model, variables, p, 3)
    assert eng.metrics.prefix_evictions == 0
    assert eng.metrics.prefix_blocks_live == 2
    assert eng.metrics.prefix_hits == 2


def test_paged_preemption_resumes_token_exact(gpt_setup):
    """A parked (preempted) best_effort stream resumes token-exactly
    through replay admission — its freed private blocks went back to
    the pool and were fully rewritten on re-admission."""
    model, variables = gpt_setup
    eng = _paged_engine(model, variables, max_slots=1)
    pb = (np.arange(8) * 5 + 4) % 32
    hbe = eng.submit(pb, 10, priority=Priority.BEST_EFFORT)
    for _ in range(3):
        eng.step()
    pi = (np.arange(8) * 11 + 6) % 32
    hint = eng.submit(pi, 4, priority=Priority.INTERACTIVE)
    eng.run(max_steps=300)
    assert eng.metrics.preemptions >= 1
    assert hbe.tokens == _ref_greedy(model, variables, pb, 10)
    assert hint.tokens == _ref_greedy(model, variables, pi, 4)


def test_paged_sliced_admission_token_exact(gpt_setup, pin_zero_recompiles):
    """Chunked-prefill fairness composes: slices write straight into
    the slot's pool blocks across interleaved ticks, pin held from
    slice start (flush spares pinned chains)."""
    model, variables = gpt_setup
    eng = pin_zero_recompiles(_paged_engine(
        model, variables, prefill_slice_tokens=4, prefix_chunk=4))
    p = (np.arange(15) * 3 + 1) % 32
    ha = eng.submit(p, 6)
    hb = eng.submit(p, 6)
    eng.run(max_steps=300)
    assert ha.tokens == _ref_greedy(model, variables, p, 6)
    assert hb.tokens == _ref_greedy(model, variables, p, 6)


def test_paged_pool_size_validation(gpt_setup):
    """No pool at all, or a pool the live streams could starve, fails
    LOUDLY at construction."""
    model, variables = gpt_setup
    with pytest.raises(ValueError, match="IS the KV cache"):
        ServeEngine(model, variables, max_slots=2, prefill_len=16,
                    prefix_cache_blocks=0)
    with pytest.raises(ValueError, match="starve"):
        ServeEngine(model, variables, max_slots=2, prefill_len=16,
                    prefix_cache_blocks=4)


# ----------------------------------------------------------- resilience
@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_paged_chaos_matrix(gpt_setup, pin_zero_recompiles, seed):
    """The mixed chaos profile in paged mode: every request terminal,
    survivors token-exact, zero recompiles across retry / replay /
    degraded / pool-rebuild transitions."""
    model, variables = gpt_setup
    plan = FaultPlan(seed=seed, sleep_fn=_no_sleep, transient_rate=0.05,
                     oom_rate=0.02, latency_rate=0.1, latency_s=1e-4,
                     max_random_injections=20)
    eng = pin_zero_recompiles(_paged_engine(
        model, variables, fault_plan=plan, backoff_sleep=_no_sleep))
    jobs = []
    for i in range(5):
        p = (np.arange(10) * 3 + i * 7 + 1) % 32
        jobs.append((p, eng.submit(p, 5)))
    eng.run(max_steps=600)
    assert not eng.has_work, "engine failed to drain under chaos"
    for p, h in jobs:
        assert h.done, f"request {h} never reached a terminal state"
        if h.state == RequestState.FINISHED:
            assert h.tokens == _ref_greedy(model, variables, p, 5)


def test_parked_slice_survives_paged_pool_reset(gpt_setup,
                                                pin_zero_recompiles):
    """A mid-prefill slice parked across steps must NOT leak its
    (retired) block ids or radix node into the rebuilt paged world
    when a tick fault forces the full pool reset: the slice is
    dropped pre-reset and its handle re-admits from scratch against
    the fresh pool — every stream still terminal and token-exact, no
    double-owned blocks (the refcount invariants would trip on a
    re-allocated duplicate)."""
    from pddl_tpu.serve.faults import FaultKind

    model, variables = gpt_setup
    plan = FaultPlan(sleep_fn=_no_sleep)
    eng = pin_zero_recompiles(_paged_engine(
        model, variables, prefill_slice_tokens=4, prefix_chunk=4,
        fault_plan=plan, backoff_sleep=_no_sleep, max_retries=0))
    p_live = (np.arange(8) * 5 + 4) % 32
    p_sliced = (np.arange(15) * 3 + 1) % 32
    h_live = eng.submit(p_live, 8)
    while eng.live_slots < 1:  # h_live fully admitted, now decoding
        eng.step()
    h_sliced = eng.submit(p_sliced, 4)
    eng.step()
    # White-box arm: the second admission must be PARKED mid-prefill
    # (15 tokens at 4/step), holding private ids + a table row; now a
    # single un-retryable transient at the NEXT tick forces the
    # live-slot replay and the full paged-world rebuild underneath it.
    assert eng._slice is not None
    plan._sched[(eng._step_idx, "tick")] = [FaultKind.TRANSIENT]
    eng.run(max_steps=400)
    assert h_live.done and h_sliced.done
    assert h_live.tokens == _ref_greedy(model, variables, p_live, 8)
    assert h_sliced.tokens == _ref_greedy(model, variables, p_sliced, 4)
    assert eng.metrics.replays >= 1  # the reset really happened


def test_paged_drain_restore_round_trip(gpt_setup):
    """v3 snapshot: carries ``paged`` + each running slot's block
    table (postmortem context); restore into a fresh paged engine
    resumes token-exactly via replay. A v2-shaped snapshot (no
    tables — the copy engine's format) restores through the SAME
    path."""
    model, variables = gpt_setup
    eng1 = _paged_engine(model, variables)
    p1 = (np.arange(11) * 5 + 2) % 32
    p2 = (np.arange(9) * 7 + 3) % 32
    eng1.submit(p1, 8)
    eng1.submit(p2, 8)
    for _ in range(3):
        eng1.step()
    snap = eng1.drain()
    assert snap["version"] == 5  # spec accounting rides v5; tables still here
    assert snap["paged"] is True
    running = [e for e in snap["requests"] if e.get("tokens")]
    assert running and all("block_table" in e for e in running)
    assert all(0 not in e["block_table"] for e in running)

    eng2 = _paged_engine(model, variables)
    rh = eng2.restore(snap)
    eng2.run(max_steps=300)
    assert rh[0].tokens == _ref_greedy(model, variables, p1, 8)
    assert rh[1].tokens == _ref_greedy(model, variables, p2, 8)

    # v2 copy-path snapshot into a paged engine: same replay restore.
    snap_v2 = dict(snap)
    snap_v2["version"] = 2
    snap_v2.pop("paged")
    snap_v2["requests"] = [
        {k: v for k, v in e.items() if k != "block_table"}
        for e in snap["requests"]]
    eng3 = _paged_engine(model, variables)
    rh3 = eng3.restore(snap_v2)
    eng3.run(max_steps=300)
    assert rh3[0].tokens == _ref_greedy(model, variables, p1, 8)
    assert rh3[1].tokens == _ref_greedy(model, variables, p2, 8)


# -------------------------------------------------------- observability
def test_paged_metrics_reach_the_exposition(exact_gpt):
    """blocks_shared / copy_bytes_avoided / block_table_fill flow
    through ServeMetrics AND the engine gauges into the Prometheus
    body, round-tripped through the strict referee parser (over the
    shared exactness engine's end state — its workload recorded hits
    and sharing)."""
    eng = exact_gpt
    text = serve_exposition(eng.metrics, eng)
    samples, types = parse_prometheus_text(text)
    flat = {name: v for (name, labels), v in samples.items() if not labels}
    assert flat["pddl_serve_copy_bytes_avoided_total"] > 0
    assert types["pddl_serve_copy_bytes_avoided_total"] == "counter"
    assert "pddl_serve_blocks_shared" in flat
    assert "pddl_serve_block_table_fill" in flat
    assert flat["pddl_serve_engine_paged"] == 1
    assert "pddl_serve_engine_blocks_shared" in flat
    assert "pddl_serve_engine_block_table_fill" in flat


def test_cache_tree_walkers_know_a_pool_by_its_key(gpt_setup, monkeypatch):
    """A cache tree that holds, beside an attention layer's pool, a
    3-dimensional leaf that is NOT a pool (what a layer with per-slot
    state keeps, `vit.SLOT_STATE_KEY`): `_kv_token_bytes`, the host
    tier's leaf spec, demotion (the whole-tree gather) and promotion (the
    whole-tree scatter) take the pool by its KEY and leave the other leaf
    alone — by rank they would have read a `[slots, 2, E]` state as a
    pool of `slots` blocks."""
    import pddl_tpu.serve.engine as engine_module
    from pddl_tpu.models.vit import PAGED_KV_KEY, SLOT_STATE_KEY

    model, variables = gpt_setup
    build = engine_module.paged_decode_cache

    def with_a_state_leaf(*args):
        cache = build(*args)

        def plant(tree):
            if PAGED_KV_KEY in tree:
                tree[SLOT_STATE_KEY] = jnp.full((2, 2, 8), 7.0)
                return True
            return any(plant(v) for v in tree.values()
                       if hasattr(v, "items"))

        assert plant(cache)
        return cache

    monkeypatch.setattr(engine_module, "paged_decode_cache",
                        with_a_state_leaf)
    bs = 8
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=32,
                      prefix_block_size=bs, prefix_chunk=bs,
                      prefix_cache_blocks=2 * (64 // bs) + 2,
                      host_tier=1 << 24)
    pools = [leaf for path, leaf in
             jax.tree_util.tree_leaves_with_path(eng._cache)
             if path[-1].key == PAGED_KV_KEY]
    per_token = sum(p.nbytes for p in pools) // (pools[0].shape[0] * bs)
    assert eng._kv_token_bytes == per_token
    assert eng.prefix_pool_nbytes == sum(p.nbytes for p in pools)
    assert eng.metrics.snapshot()["state_bytes_resident"] == 2 * 2 * 8 * 4
    assert all(PAGED_KV_KEY in key for key in eng._host.leaf_spec)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 32, size=24).astype(np.int32)
               for _ in range(6)]
    refs = [_ref_greedy(model, variables, p, 4) for p in prompts]
    for _ in range(3):   # more chains than the pool keeps: demote, promote
        for p, ref in zip(prompts, refs):
            h = eng.submit(p, 4)
            eng.run(max_steps=5000)
            assert h.tokens == ref
    snap = eng.metrics.snapshot()
    assert snap["host_tier_spills"] > 0 and snap["host_tier_promotions"] > 0
    planted = [leaf for path, leaf in
               jax.tree_util.tree_leaves_with_path(eng._cache)
               if path[-1].key == SLOT_STATE_KEY]
    assert planted and all(np.all(np.asarray(x) == 7.0) for x in planted)
