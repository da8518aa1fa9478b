"""Mosaic-compiled kernel numerics vs oracles, on real TPU hardware.

The CPU suite proves the same assertions in interpret mode; these runs
close the interpret-vs-Mosaic gap for the Pallas flash kernel (fwd and
fused bwd), the chunked-CE custom VJP, and on-device augment
determinism. Tolerances are bf16/f32-mixed: the kernel accumulates in
f32 but inputs/outputs are bf16 (the TPU training configuration).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.models.gpt import greedy_gap
from pddl_tpu.ops.attention import (
    attention_reference,
    decode_attention,
    flash_attention,
    paged_blocks_per_group,
    paged_cache_insert,
    paged_decode_attention,
    paged_decode_attention_kernel,
)
from pddl_tpu.ops.augment import standard_augment
from pddl_tpu.ops.large_vocab import chunked_cross_entropy


# Greedy-consistency bound at vocab 64 (`models/gpt.greedy_gap`): generous
# for bf16 ulp noise yet far below any real logit margin there — a wrong
# (non-tie) token would blow it up.
_TIE = 0.1


def _qkv(b=2, h=4, s=1024, d=64, dtype=jnp.bfloat16, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, h, s, d)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference_on_chip(causal):
    q, k, v = _qkv()
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        interpret=False)
    )(q, k, v)
    ref = jax.jit(
        lambda q, k, v: attention_reference(q, k, v, causal=causal)
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,  # bf16 outputs; f32 accumulation inside
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_backward_matches_reference_on_chip(causal):
    """The custom-VJP two-sweep backward (dq then dk/dv) vs AD through
    the O(S^2) reference — Mosaic-compiled, not interpreted."""
    q, k, v = _qkv(s=512)
    cot = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=False)
        return jnp.sum(o.astype(jnp.float32) * cot)

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=causal)
        return jnp.sum(o.astype(jnp.float32) * cot)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-2, rtol=5e-2,
            err_msg=f"d{name} mismatch (causal={causal})",
        )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_reference_on_chip(causal):
    """Mosaic-compiled GQA path (kv-head-aware index maps, K/V consumed
    unexpanded) fwd + fused bwd vs the expanded oracle — the llama-family
    training configuration (12 q-heads / 4 kv-heads at D=64)."""
    b, h, hkv, s, d = 2, 12, 4, 1024, 64
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, hkv, s, d), jnp.bfloat16)

    def expand(t):
        return jnp.repeat(t, h // hkv, axis=1)

    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=False))(q, k, v)
    ref = jax.jit(lambda q, k, v: attention_reference(
        q, expand(k), expand(v), causal=causal))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)

    cot = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=False)
        return jnp.sum(o.astype(jnp.float32) * cot)

    def loss_ref(q, k, v):
        o = attention_reference(q, expand(k), expand(v), causal=causal)
        return jnp.sum(o.astype(jnp.float32) * cot)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        assert a.shape == b_.shape  # dk/dv at kv-head shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b_, np.float32),
            atol=5e-2, rtol=5e-2,
            err_msg=f"d{name} mismatch (causal={causal})")


@pytest.mark.parametrize("block_size", [8, 16])
@pytest.mark.parametrize("heads,kv_heads,d", [
    (12, 12, 64), (12, 4, 64), (20, 20, 64), (16, 16, 128)])
def test_paged_decode_kernel_matches_oracle_on_chip(heads, kv_heads, d,
                                                    block_size):
    """The serving tick's kernel, Mosaic-compiled over the fused pool
    leaf at GPT-small (12x64), Llama-small (12/4x64), GPT-2-large
    (20x64) and 128-wide head shapes, vs the jnp path of
    ``paged_decode_attention`` and vs the dense oracle over the virtual
    cache: eight slots over a 1024-token context, depths from a freshly
    admitted slot (0) to the last position, block ids scattered over
    the pool."""
    slots = 8
    t = 1024 // block_size
    n = slots * t + 1  # block 0 is the scratch sink
    ks = jax.random.split(jax.random.key(heads + kv_heads + block_size), 2)
    q = jax.random.normal(ks[0], (slots, heads, 1, d), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (n, kv_heads, block_size, 2 * d),
                             jnp.bfloat16)
    table = jnp.asarray(np.random.RandomState(0).permutation(
        np.arange(1, n)).reshape(slots, t), jnp.int32)
    index = jnp.asarray([0, 1, 7, 8, 100, 511, 1000, 1023], jnp.int32)
    got = jax.jit(lambda *a: paged_decode_attention_kernel(
        *a, interpret=False))(q, pool, table, index)
    want = jax.jit(lambda *a: paged_decode_attention(
        *a, kernel=False))(q, pool, table, index)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2)
    # The virtual cache the table spells, attended densely.
    kv = jnp.moveaxis(pool[table], 1, 2).reshape(slots, kv_heads, 1024,
                                                 2 * d)
    dense = jax.jit(decode_attention)(q, kv[..., :d], kv[..., d:], index)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(dense, np.float32),
        atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("window", [None, 4096])
def test_paged_window_kernel_at_smallthinker_shapes_on_chip(window):
    """The tick's kernel as the SmallThinker cell calls it: 28 q heads
    over 4 kv heads of 128 (7 q heads a kv head), eight slots over a
    16,384-token context (table width 1,024), with and without the 4,096
    window (blocks wholly under the band are skipped), against the jnp
    path — and the jnp path's multi-token sweep, 2,048 rows continuing
    at 9,000 tokens of history, against the same rows one at a time."""
    slots, heads, kv_heads, d, bs = 8, 28, 4, 128, 16
    t = 16384 // bs
    n = slots * t + 1
    ks = jax.random.split(jax.random.key(28), 3)
    q = jax.random.normal(ks[0], (slots, heads, 1, d), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (n, kv_heads, bs, 2 * d), jnp.bfloat16)
    table = jnp.asarray(np.random.RandomState(1).permutation(
        np.arange(1, n)).reshape(slots, t), jnp.int32)
    index = jnp.asarray([0, 15, 4095, 4096, 5000, 9000, 12288, 16383],
                        jnp.int32)
    got = jax.jit(lambda *a: paged_decode_attention_kernel(
        *a, window=window, interpret=False))(q, pool, table, index)
    want = jax.jit(lambda *a: paged_decode_attention(
        *a, window=window, kernel=False))(q, pool, table, index)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2)
    rows = jax.random.normal(ks[2], (1, heads, 2048, d), jnp.bfloat16)
    sweep = jax.jit(lambda q_, i: paged_decode_attention(
        q_, pool, table[:1], i, window=window, kernel=False))
    chunk = sweep(rows, jnp.int32(9000))
    for r in (0, 1000, 2047):
        one = sweep(rows[:, :, r:r + 1], jnp.int32(9000 + r))
        np.testing.assert_allclose(
            np.asarray(chunk[:, :, r:r + 1], np.float32),
            np.asarray(one, np.float32), atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("slots,heads,kv_heads,d,context,window,lo,hi", [
    (48, 20, 20, 64, 1024, None, 0, 1023),       # gpt2l_chat_*
    (8, 28, 4, 128, 16384, None, 1024, 12288),   # st21b_longdoc_steady,
    (8, 28, 4, 128, 16384, 4096, 1024, 12288),   # NoPE-global and window
])
def test_paged_decode_kernel_at_the_cells_shapes_on_chip(
        slots, heads, kv_heads, d, context, window, lo, hi):
    """The kernel as the benchmark's cells call it — 48 rows of 20 x 64
    at mixed depths 0-1,023 (a fresh row, a group's last and first key
    and the context's last position among them, parked rows between),
    and 8 rows of 28 q / 4 kv heads of 128 at 1k-12k of context with the
    4,096 window and without — against the jnp path and against dense
    ``decode_attention`` over the virtual cache the table spells. Table
    entries past a row's depth are scratch, as the engine leaves them:
    the kernel's group loop copies none of them."""
    bs, t = 16, context // 16
    n = slots * t + 1
    ks = jax.random.split(jax.random.key(slots + heads), 2)
    q = jax.random.normal(ks[0], (slots, heads, 1, d), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (n, kv_heads, bs, 2 * d), jnp.bfloat16)
    rng = np.random.RandomState(slots)
    index = rng.randint(lo, hi + 1, size=slots).astype(np.int32)
    span = paged_blocks_per_group(pool.shape, 2, t) * bs
    a = lo // span + 1               # depths ON a group's last and first key
    index[:6] = [lo, hi, a * span - 1, a * span,
                 (a + 1) * span - 1, (a + 1) * span]
    table = rng.permutation(np.arange(1, n)).reshape(slots, t).astype(
        np.int32)
    if lo == 0:
        index[8::5] = 0              # parked rows: depth 0, all scratch
        table[8::5] = 0
    table[np.arange(t) > index[:, None] // bs] = 0
    got = jax.jit(lambda *a: paged_decode_attention_kernel(
        *a, window=window, interpret=False))(q, pool, table, index)
    want = jax.jit(lambda *a: paged_decode_attention(
        *a, window=window, kernel=False))(q, pool, table, index)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2)
    kv = jnp.moveaxis(pool[table], 1, 2).reshape(slots, kv_heads, context,
                                                 2 * d)
    dense = jax.jit(functools.partial(decode_attention, window=window))(
        q, kv[..., :d], kv[..., d:], index)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(dense, np.float32),
        atol=2e-2, rtol=2e-2)


def test_paged_kernel_on_a_latent_leaf_at_the_glm_cells_shape_on_chip():
    """The same kernel as the GLM-4.7-Flash cell calls it: 20 absorbed q
    heads of 576 over ONE cache head whose entries are key in their first
    576 lanes and value in their first 512, stored in 640; 48 rows over a
    20,480-token context (table width 1,280, K 64) at depths from a fresh
    row to 18k, parked rows between; against the jnp path and against
    dense ``decode_attention`` over the virtual cache the table spells."""
    slots, heads, dk, dv, lanes, bs, context = 48, 20, 576, 512, 640, 16, 20480
    t = context // bs
    n = slots * t + 1
    ks = jax.random.split(jax.random.key(576), 2)
    q = jax.random.normal(ks[0], (slots, heads, 1, dk), jnp.bfloat16)
    pool = jax.random.normal(ks[1], (n, 1, bs, lanes), jnp.bfloat16)
    pool = pool.at[..., dk:].set(0)       # as the layer stores an entry
    rng = np.random.RandomState(7)
    index = rng.randint(1024, 18432, size=slots).astype(np.int32)
    span = paged_blocks_per_group(pool.shape, 2, t) * bs
    assert span == 1024
    index[:6] = [0, 18431, 2 * span - 1, 2 * span, 3 * span - 1, 20479]
    table = rng.permutation(np.arange(1, n)).reshape(slots, t).astype(
        np.int32)
    index[8::5] = 0                       # parked rows: all scratch
    table[8::5] = 0
    table[np.arange(t) > index[:, None] // bs] = 0
    kw = dict(scale=256 ** -0.5, value_lanes=(0, dv))
    got = jax.jit(lambda *a: paged_decode_attention_kernel(
        *a, interpret=False, **kw))(q, pool, table, index)
    want = jax.jit(lambda *a: paged_decode_attention(
        *a, kernel=False, **kw))(q, pool, table, index)
    assert got.shape == (slots, heads, 1, dv)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=2e-2, rtol=2e-2)
    virtual = jnp.moveaxis(pool[table], 1, 2).reshape(slots, 1, context,
                                                      lanes)
    dense = jax.jit(functools.partial(decode_attention, scale=256 ** -0.5))(
        q, virtual[..., :dk], virtual[..., :dv], index)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(dense, np.float32),
        atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("tokens", [8, 2048])
def test_grouped_expert_ffn_matches_dense_oracle_on_chip(tokens):
    """The serving expert path at the SmallThinker cell's shapes (64
    ReGLU experts of 2560-768-2560, top-6, bf16) at decode size and at a
    prefill chunk's, against every token through every expert, masked."""
    from pddl_tpu.ops.moe import grouped_expert_ffn

    n, k, d, h = 64, 6, 2560, 768
    ks = jax.random.split(jax.random.key(tokens), 6)
    x = jax.random.normal(ks[0], (tokens, d), jnp.bfloat16)
    w1, w3 = (0.02 * jax.random.normal(key, (n, d, h), jnp.bfloat16)
              for key in ks[1:3])
    w2 = 0.02 * jax.random.normal(ks[3], (n, h, d), jnp.bfloat16)
    gates, index = jax.lax.top_k(
        jax.nn.softmax(jax.random.normal(ks[4], (tokens, n)), -1), k)
    got = jax.jit(lambda *a: grouped_expert_ffn(
        a[0], a[1], a[2], a[4], a[5], act="reglu", w_gate=a[3]))(
            x, index, gates, w1, w3, w2)

    @jax.jit
    def dense(x, index, gates, w1, w3, w2):
        def one(y, w):
            g = jnp.sum(jnp.where(index == w["e"], gates, 0.0), -1)
            hid = jax.nn.relu(x @ w["w1"]) * (x @ w["w3"])
            return y + g[:, None] * (hid @ w["w2"]).astype(jnp.float32), 0
        y, _ = jax.lax.scan(one, jnp.zeros((tokens, d), jnp.float32),
                            {"w1": w1, "w3": w3, "w2": w2,
                             "e": jnp.arange(n)})
        return y

    want = dense(x, index, gates, w1, w3, w2)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=2e-2, rtol=5e-2)


def test_paged_tick_write_in_place_on_chip():
    """The tick's block-granular token write, compiled with the pool
    donated: every slot's K/V row lands at (table[pos // bs], pos % bs)
    of the fused leaf, parked slots (all-scratch rows) and a position
    past the table touch only the scratch block, and nothing else in
    the pool changes."""
    slots, hkv, bs, d, t = 8, 20, 16, 64, 64
    n = slots * t + 1
    ks = jax.random.split(jax.random.key(29), 3)
    pool = jax.random.normal(ks[0], (n, hkv, bs, 2 * d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (slots, hkv, 1, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (slots, hkv, 1, d), jnp.bfloat16)
    table = np.random.RandomState(1).permutation(
        np.arange(1, n)).reshape(slots, t).astype(np.int32)
    table[2] = 0
    table[5] = 0                                  # parked slots
    index = np.asarray([0, 15, 3, 16, 511, 9, 1023, 1024], np.int32)
    before = np.asarray(pool, np.float32)
    out = jax.jit(paged_cache_insert, donate_argnums=(0,))(
        pool, jnp.concatenate([k, v], -1), table, index)
    out = np.asarray(out, np.float32)
    want = before.copy()
    new = np.asarray(jnp.concatenate([k, v], -1)[:, :, 0], np.float32)
    for i in (0, 1, 3, 4, 6):
        want[table[i, index[i] // bs], :, index[i] % bs] = new[i]
    np.testing.assert_array_equal(out[1:], want[1:])


def test_decode_attention_on_chip():
    """The serving sweep compiled on hardware: bf16 cache, grouped heads,
    ring buffer — vs the windowed oracle over the true history."""
    from pddl_tpu.ops.attention import decode_attention

    B, Hkv, rep, D = 1, 4, 3, 64
    H = Hkv * rep
    ring, window, T = 256, 200, 600
    ks = jax.random.split(jax.random.key(5), 3)
    keys = jax.random.normal(ks[0], (B, Hkv, T, D), jnp.bfloat16)
    vals = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.bfloat16)
    q = jax.random.normal(ks[2], (B, H, 1, D), jnp.bfloat16)

    ref = attention_reference(q, keys, vals, causal=True, window=window,
                              k_offset=-(T - 1))
    slots = jnp.arange(T) % ring
    k_ring = jnp.zeros((B, Hkv, ring, D), jnp.bfloat16).at[:, :, slots].set(keys)
    v_ring = jnp.zeros((B, Hkv, ring, D), jnp.bfloat16).at[:, :, slots].set(vals)
    out = jax.jit(lambda q, k, v: decode_attention(
        q, k, v, jnp.int32(T - 1), window=window, rolling=True))(
            q, k_ring, v_ring)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_chunked_ce_matches_materialized_logits_on_chip():
    """Loss AND grads of the never-materialize-logits head vs the full
    [T, V] logits path, at a vocab that actually chunks (3 scan steps)."""
    t, e, vocab, chunk = 256, 64, 1000, 384
    kf, kk, kl = jax.random.split(jax.random.key(1), 3)
    feats = jax.random.normal(kf, (t, e), jnp.float32)
    kernel = jax.random.normal(kk, (e, vocab), jnp.float32) * 0.02
    labels = jax.random.randint(kl, (t,), 0, vocab)

    def loss_chunked(feats, kernel):
        return chunked_cross_entropy(feats, kernel, labels,
                                     chunk_size=chunk)

    def loss_full(feats, kernel):
        logits = feats @ kernel
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, labels[:, None], axis=-1))

    lc, gc = jax.jit(jax.value_and_grad(loss_chunked, argnums=(0, 1)))(
        feats, kernel)
    lf, gf = jax.jit(jax.value_and_grad(loss_full, argnums=(0, 1)))(
        feats, kernel)
    np.testing.assert_allclose(float(lc), float(lf), atol=1e-5, rtol=1e-5)
    for a, b, name in zip(gc, gf, ("features", "kernel")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name} mismatch",
        )


def test_augment_deterministic_on_chip():
    """Same rng -> bitwise-identical augmented batch on hardware (the
    race-detection stand-in: functional purity holds on the chip, not
    just under the CPU interpreter)."""
    aug = jax.jit(standard_augment(crop=224, flip=True))
    x = jax.random.uniform(jax.random.key(3), (8, 256, 256, 3)) * 255.0
    rng = jax.random.key(11)
    a = np.asarray(aug(rng, x))
    b = np.asarray(aug(rng, x))
    np.testing.assert_array_equal(a, b)
    # ...and a different key actually changes something (flip/crop live).
    c = np.asarray(aug(jax.random.key(12), x))
    assert (a != c).any()


def test_flash_sliding_window_matches_reference_on_chip():
    """Mosaic-compiled SWA (band block-skip + band mask) fwd+bwd vs the
    windowed O(S^2) reference, at an S/window where whole k-blocks skip."""
    q, k, v = _qkv(s=1024)
    cot = jax.random.normal(jax.random.key(7), q.shape, jnp.float32)
    w = 200  # unaligned to the 512x1024 default blocks

    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=w, block_q=256, block_k=256,
        interpret=False))(q, k, v)
    ref = jax.jit(lambda q, k, v: attention_reference(
        q, k, v, causal=True, window=w))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, window=w,
                            block_q=256, block_k=256, interpret=False)
        return jnp.sum(o.astype(jnp.float32) * cot)

    def loss_ref(q, k, v):
        o = attention_reference(q, k, v, causal=True, window=w)
        return jnp.sum(o.astype(jnp.float32) * cot)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=5e-2, rtol=5e-2, err_msg=f"d{name} (window={w})")


def test_speculative_greedy_consistent_on_chip():
    """The serving path, compiled on hardware. The CPU suite proves
    bit-exactness vs generate(); on the chip, the k+1-wide verify block
    and the one-token decode tick are DIFFERENT compiled programs whose
    bf16 logits legitimately differ by ulps — on an untrained model
    (near-uniform logits, ties everywhere) that can flip an argmax, so
    token strings may diverge while both remain valid greedy decodes.
    The hardware-honest invariant is GREEDY CONSISTENCY: every token the
    speculative path emitted must be an argmax-or-numerical-tie of the
    model's own conditional along the speculative output's OWN prefix
    (the trained-model chip benches additionally observe bit-equality,
    because trained logits have margins ulps can't cross)."""
    from pddl_tpu.models.llama import tiny_llama
    from pddl_tpu.models.speculative import generate_speculative

    model = tiny_llama(vocab_size=64, max_len=256,
                       dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    prompt = (jnp.tile(jnp.arange(9, dtype=jnp.int32), (2, 6))[:, :48]
              % 64)
    variables = {"params": model.init(jax.random.key(0), prompt,
                                      train=False)["params"]}
    out, stats = generate_speculative(model, variables, prompt, 64,
                                      return_stats=True)
    assert stats["emitted"] == 64 and out.shape == (2, 112)
    gap = greedy_gap(model, variables, out, prompt.shape[1])
    assert np.all(gap < _TIE), float(gap.max())


def test_int8_serving_hook_on_chip():
    """Weight-only int8 through the compiled decode programs: the
    param_transform hook must decode the dequantized model greedily
    (same weights, same math; only the jit boundary and the HBM
    representation move)."""
    from pddl_tpu.models.gpt import generate, tiny_gpt
    from pddl_tpu.models.speculative import generate_speculative
    from pddl_tpu.ops.quant import dequantize, quantize_int8

    model = tiny_gpt(vocab_size=64, max_len=256,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    prompt = jnp.tile(jnp.arange(7, dtype=jnp.int32), (1, 6))[:, :40]
    params = model.init(jax.random.key(1), prompt, train=False)["params"]
    qparams = quantize_int8(params, min_elems=128)
    # Dequantizing inside the jit instead of before it is a DIFFERENT
    # compiled program (the compiler may fuse the int8 -> bf16 scaling
    # into the matmuls), and so is the k+1-wide speculative verify
    # block: bf16 logits can differ by ulps and flip an argmax at a
    # genuine tie of this untrained model (see
    # test_speculative_greedy_consistent_on_chip). So both legs are held
    # to GREEDY CONSISTENCY against the dequantized model's own
    # conditional, not to bit-equality with another program's output.
    dequantized = {"params": dequantize(qparams)}
    out = generate(model, {"params": qparams}, prompt, max_new_tokens=48,
                   param_transform=dequantize)
    out_spec = generate_speculative(model, {"params": qparams}, prompt,
                                    48, param_transform=dequantize)
    for tokens in (out, out_spec):
        assert tokens.shape == (1, 88)
        gap = greedy_gap(model, dequantized, tokens, prompt.shape[1])
        assert np.all(gap < _TIE), float(gap.max())


def test_lfm2_tick_and_chunk_against_the_reference_on_chip():
    """LFM2-24B-A2B at its published widths (2048, 32 q / 8 kv heads of 64
    with q/k norm, 3 taps, 64 experts of 1,536 top-4, a dense MLP of
    11,776, vocabulary 65,536), two layers — a dense short-convolution
    layer and a routed attention layer — through a paged ``ServeEngine``
    in bf16: a 2,050-token prompt in two 2,048-wide chunks (the
    convolution's state carried into a chunk of two real tokens), a
    1-token prompt into the slot the long stream left, then the tick.
    Every served greedy token against the benchmark's plain float32
    reference over the whole sequence: bf16 costs a mean gap of a few
    hundredths of a logit (the cell reads 0.01-0.03, PERF.md); a lost
    state or a wrong tap moves the first tokens by whole logits."""
    import sys
    import pathlib

    root = str(pathlib.Path(__file__).parent.parent)
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench.reference import lfm2 as reference
    from chipbench.weights_lfm2 import make_weights
    from pddl_tpu.models.llama import LFM2_24B_A2B
    from pddl_tpu.serve import ServeEngine

    types = ["conv", "full_attention"]
    cfg = {"num_hidden_layers": 2, "layer_types": types,
           "num_dense_layers": 1, "hidden_size": 2048,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "conv_L_cache": 3, "intermediate_size": 11776,
           "num_experts": 64, "num_experts_per_tok": 4,
           "moe_intermediate_size": 1536, "routed_scaling_factor": 1,
           "vocab_size": 65536, "norm_eps": 1e-5,
           "rope_parameters": {"rope_theta": 1000000}}
    model = LFM2_24B_A2B(depth=2, max_len=6144, layer_types=tuple(types),
                         moe_layout=(0, 1), dtype=jnp.bfloat16,
                         param_dtype=jnp.bfloat16)
    variables = make_weights(cfg, 5)
    engine = ServeEngine(model, variables, max_slots=1, prefill_len=4096,
                         prefix_block_size=16, prefix_chunk=2048)
    rng = np.random.RandomState(0)
    gaps = []
    for plen in (2050, 1):
        prompt = rng.randint(0, 65536, size=plen).astype(np.int32)
        handle = engine.submit(prompt, 8)
        engine.run()
        assert len(handle.tokens) == 8
        gaps.append(reference.served_gaps(variables["params"], cfg, prompt,
                                          handle.tokens, 8)["gaps"])
    gaps = np.concatenate(gaps)
    assert gaps.max() < 1.0 and gaps.mean() < 0.1, gaps
    assert set(engine.compile_counts().values()) == {1}
    assert "tpu_custom_call" in engine.tick_lowering().as_text()
