"""Attention numerics: flash kernel and ring attention vs the reference
oracle, plus ViT end-to-end training (the long-context stack, SURVEY.md §5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pddl_tpu.ops.attention import attention_reference, flash_attention
from pddl_tpu.ops.ring_attention import (
    ring_attention,
    sequence_parallel_attention,
)


def _qkv(b=2, h=2, s=256, d=64, dtype=jnp.float32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (b, h, s, d), dtype),
            jax.random.normal(kk, (b, h, s, d), dtype),
            jax.random.normal(kv, (b, h, s, d), dtype))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv(s=256, d=64)
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_small_blocks():
    q, k, v = _qkv(s=64, d=32)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_reference(causal):
    """The fused Pallas backward (dq/dk/dv kernels) vs AD of the oracle."""
    q, k, v = _qkv(s=64, d=32)

    def loss_flash(q, k, v):
        # Non-uniform cotangent so dq/dk/dv all get exercised non-trivially.
        out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).sum()

    def loss_ref(q, k, v):
        out = attention_reference(q, k, v, causal=causal)
        return (out * jnp.cos(jnp.arange(out.size).reshape(out.shape))).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_gradients_rectangular_and_multiblock():
    """sq != sk and several blocks per sweep (accumulator reuse paths)."""
    kq, kk, kv = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(kq, (1, 2, 96, 32))
    k = jax.random.normal(kk, (1, 2, 160, 32))
    v = jax.random.normal(kv, (1, 2, 160, 32))

    gf = jax.grad(lambda *a: flash_attention(
        *a, block_q=32, block_k=32).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: attention_reference(*a).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_second_order_via_reference_fallback():
    """Hessian-vector products: the fused Pallas backward is first-order
    only; fused_backward=False routes through the any-order reference path."""
    q, k, v = _qkv(b=1, h=1, s=32, d=16)

    def inner(q):
        return flash_attention(q, k, v, fused_backward=False).sum()

    hvp = jax.grad(lambda q_: jax.grad(inner)(q_).sum())(q)
    ref_hvp = jax.grad(
        lambda q_: jax.grad(
            lambda q2: attention_reference(q2, k, v).sum())(q_).sum())(q)
    np.testing.assert_allclose(np.asarray(hvp), np.asarray(ref_hvp),
                               atol=1e-5, rtol=1e-5)


def test_flash_gradients_bf16():
    q, k, v = _qkv(s=128, d=64, dtype=jnp.bfloat16)

    gf = jax.grad(lambda *a: flash_attention(
        *a, causal=True, block_q=64, block_k=64).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: attention_reference(
        *a, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_flash_bf16_close_to_f32():
    q, k, v = _qkv(s=128, d=64, dtype=jnp.bfloat16)
    ref = attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32))
    out = flash_attention(q, k, v, block_q=64, block_k=64)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(mesh8, causal):
    """8-way sequence-sharded ring attention == full attention, exactly the
    long-context guarantee: no device ever holds the whole sequence."""
    q, k, v = _qkv(b=1, h=2, s=128, d=16)
    ref = attention_reference(q, k, v, causal=causal)

    # Rebuild the mesh with all 8 devices on the seq axis.
    from pddl_tpu.core.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=1, seq=8))
    out = sequence_parallel_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients_match_full(causal):
    """AD through the ring (ppermute transpose + fori_loop) must equal the
    full-attention gradients — the backward pass of sequence parallelism."""
    from pddl_tpu.core.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=1, seq=8))
    q, k, v = _qkv(b=1, h=2, s=128, d=16)

    def loss_ring(q, k, v):
        out = sequence_parallel_attention(q, k, v, mesh, causal=causal)
        return (out * jnp.sin(jnp.arange(out.size).reshape(out.shape))).sum()

    def loss_full(q, k, v):
        out = attention_reference(q, k, v, causal=causal)
        return (out * jnp.sin(jnp.arange(out.size).reshape(out.shape))).sum()

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gf = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)


def test_ring_attention_single_shard_degenerates_to_full():
    from jax.sharding import PartitionSpec as P
    from pddl_tpu.core.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=8, seq=1))
    q, k, v = _qkv(b=1, h=1, s=32, d=8)
    spec = P(None, None, "seq", None)
    out = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_vit_trains_on_synthetic():
    from pddl_tpu.data.synthetic import SyntheticImageClassification
    from pddl_tpu.models.vit import tiny_vit
    from pddl_tpu.parallel.mirrored import MirroredStrategy
    from pddl_tpu.train.loop import Trainer

    tr = Trainer(tiny_vit(num_classes=8), optimizer="adamw",
                 learning_rate=1e-3, strategy=MirroredStrategy())
    ds = SyntheticImageClassification(batch_size=16, image_size=32,
                                      num_classes=8, seed=5)
    hist = tr.fit(ds, epochs=2, steps_per_epoch=4, verbose=0)
    assert hist.history["loss"][-1] < hist.history["loss"][0]


def test_vit_registry_and_config_path():
    from pddl_tpu.config import ExperimentConfig
    from pddl_tpu.run import run_experiment

    cfg = ExperimentConfig(
        model="tiny_vit", num_classes=8, image_size=32, crop=32,
        per_replica_batch=2, epochs=1, strategy="mirrored",
        compute_dtype="float32", verbose=0,
        reduce_lr_on_plateau=False, early_stopping=False,
    )
    hist = run_experiment(cfg, steps_per_epoch=2, validation_steps=1)
    assert np.isfinite(hist.history["loss"][-1])


def test_remat_policies_numerics_and_grads():
    """Remat must change memory, never numbers: forward and gradients
    identical across none/dots/full for ViT and GPT."""
    import jax
    import jax.numpy as jnp

    from pddl_tpu.models.gpt import tiny_gpt
    from pddl_tpu.models.vit import ViT

    x_img = jnp.linspace(0, 1, 2 * 16 * 16 * 3).reshape(2, 16, 16, 3)
    tokens = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % 32

    def check(make, inp):
        base = make("none")
        variables = base.init(jax.random.key(0), inp, train=False)

        def loss(m):
            def f(params):
                out = m.apply({"params": params}, inp, train=True)
                return jnp.sum(out.astype(jnp.float32) ** 2)
            return f

        ref_val, ref_grad = jax.value_and_grad(loss(base))(variables["params"])
        for policy in ("dots", "full"):
            m = make(policy)
            val, grad = jax.value_and_grad(loss(m))(variables["params"])
            np.testing.assert_allclose(float(val), float(ref_val),
                                       rtol=1e-5)
            for a, b in zip(jax.tree.leaves(grad),
                            jax.tree.leaves(ref_grad)):
                # atol covers XLA-version rematerialization reassociation
                # (older CPU backends land ~1e-5 off on isolated elements).
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=5e-5)

    check(lambda r: ViT(patch_size=4, embed_dim=32, depth=2, num_heads=4,
                        num_classes=8, attention="reference", remat=r),
          x_img)
    check(lambda r: tiny_gpt(vocab_size=32, max_len=32, remat=r), tokens)

    import pytest

    with pytest.raises(ValueError, match="remat"):
        from pddl_tpu.models.vit import remat_block, TransformerBlock
        remat_block(TransformerBlock, "bogus")


def test_flash_attention_lse_matches_reference():
    from pddl_tpu.ops.attention import (
        _attention_reference_lse,
        flash_attention_lse,
    )

    B, H, S, D = 2, 2, 64, 16
    q, k, v = (jax.random.normal(jax.random.key(i), (B, H, S, D))
               for i in range(3))
    for causal in (False, True):
        o1, l1 = flash_attention_lse(q, k, v, causal=causal)
        o2, l2 = _attention_reference_lse(q, k, v, causal, D ** -0.5)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   atol=2e-5, rtol=2e-5)

        # Gradients INCLUDING through the lse output (dlse folds into the
        # fused backward's row term).
        def loss(fn, qq):
            o, l = fn(qq)
            return (o.sum() + 0.3 * l.sum()).astype(jnp.float32)

        g1 = jax.grad(lambda qq: loss(
            lambda x: flash_attention_lse(x, k, v, causal=causal), qq))(q)
        g2 = jax.grad(lambda qq: loss(
            lambda x: _attention_reference_lse(x, k, v, causal, D ** -0.5),
            qq))(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=3e-5, rtol=3e-5)


@pytest.mark.slow  # multi-hop pallas-interpret loop: tier-2 wall-clock
def test_flash_ring_matches_reference_and_xla_ring(mesh8):
    """Flash-per-rotation ring == XLA-einsum ring == full attention,
    forward AND gradients, causal and not."""
    from pddl_tpu.core.mesh import MeshConfig, build_mesh
    from pddl_tpu.ops.attention import attention_reference
    from pddl_tpu.ops.ring_attention import sequence_parallel_attention

    mesh = build_mesh(MeshConfig(data=1, seq=8))
    B, H, S, D = 1, 2, 64, 16
    q, k, v = (jax.random.normal(jax.random.key(10 + i), (B, H, S, D))
               for i in range(3))
    for causal in (False, True):
        ref = attention_reference(q, k, v, causal=causal)
        flash_ring = jax.jit(lambda a, b, c: sequence_parallel_attention(
            a, b, c, mesh, causal=causal, use_flash=True))(q, k, v)
        np.testing.assert_allclose(np.asarray(flash_ring), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

        xla_ring = jax.jit(lambda a, b, c: sequence_parallel_attention(
            a, b, c, mesh, causal=causal, use_flash=False))(q, k, v)
        np.testing.assert_allclose(np.asarray(flash_ring),
                                   np.asarray(xla_ring),
                                   atol=2e-4, rtol=2e-4)

        # Gradients w.r.t. ALL inputs (dk/dv cross the ppermute transpose
        # and carry the dlse fold through the dkv kernel too).
        g_ref = jax.grad(lambda a, b, c: attention_reference(
            a, b, c, causal=causal).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)
        g_ring = jax.grad(lambda a, b, c: sequence_parallel_attention(
            a, b, c, mesh, causal=causal, use_flash=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)
        for gr, gf in zip(g_ref, g_ring):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                       atol=3e-4, rtol=3e-4)


def test_flash_ring_check_vma_limitation():
    """Pin WHY the flash ring runs with check_vma=False (VERDICT r1 weak #5).

    The ring itself is branch-free (the pallas call sits in straight-line
    shard_map code), but jax's varying-axes checker cannot propagate
    through the pallas kernel: its internal dynamic_slices combine varying
    ref data with invariant grid indices, and the checker raises the
    upstream 'varying manual axes to match' ValueError whose own message
    prescribes check_vma=False. When a jax upgrade makes this test FAIL
    (the checked call succeeds), flip use_flash to run checked in
    sequence_parallel_attention and delete this test."""
    import functools

    from jax.sharding import PartitionSpec as P

    from pddl_tpu.core.mesh import MeshConfig, build_mesh
    from pddl_tpu.ops.ring_attention import ring_attention_flash

    mesh = build_mesh(MeshConfig(data=1, seq=8))
    B, H, S, D = 1, 2, 64, 16
    q, k, v = (jax.random.normal(jax.random.key(20 + i), (B, H, S, D))
               for i in range(3))
    spec = P(None, None, "seq", None)
    checked = jax.shard_map(
        functools.partial(ring_attention_flash, axis_name="seq", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=True,
    )
    with pytest.raises(ValueError, match="varying manual axes"):
        jax.jit(checked)(q, k, v)


def test_tuned_blocks_resolution():
    """Defaults resolve per device generation; explicit args still win."""
    from pddl_tpu.ops.attention import TUNED_BLOCKS, tuned_blocks

    bq, bk = tuned_blocks()
    assert bq >= 8 and bk >= 8
    # Unknown generations (this CPU test backend included) fall back to
    # the measured v5e pair rather than failing.
    assert (bq, bk) == TUNED_BLOCKS.get(
        jax.devices()[0].device_kind, (512, 1024))

    # None-defaulted call == explicit tuned call, bitwise.
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 2, 256, 16))
               for i in range(3))
    auto = flash_attention(q, k, v, causal=True)
    explicit = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


# ------------------------------------------------------- sliding window
def test_flash_sliding_window_matches_reference():
    """Flash SWA vs the windowed reference oracle, with blocks small
    enough that whole k-blocks are skipped below the band (the O(S*W)
    path), windows aligned and unaligned to the block size."""
    q, k, v = (jax.random.normal(jax.random.key(i), (2, 2, 256, 32))
               for i in range(3))
    for w in (1, 37, 64, 200):
        ref = attention_reference(q, k, v, causal=True, window=w)
        got = flash_attention(q, k, v, causal=True, window=w,
                              block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"w={w}")


def test_flash_sliding_window_grads_match_reference():
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 2, 256, 32))
               for i in range(3))

    for w in (37, 128):
        def loss_flash(q, k, v, w=w):
            return flash_attention(q, k, v, causal=True, window=w,
                                   block_q=64, block_k=64).sum()

        def loss_ref(q, k, v, w=w):
            return attention_reference(q, k, v, causal=True, window=w).sum()

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4,
                                       err_msg=f"w={w}")


def test_window_geq_seq_degrades_to_plain_causal():
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 2, 64, 32))
               for i in range(3))
    plain = flash_attention(q, k, v, causal=True)
    wide = flash_attention(q, k, v, causal=True, window=64)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(wide))


# -------------------------------------------------- grouped-query (GQA)
def _tiled(t, rep):
    """Oracle-side expansion: repeat each kv head rep times (what the
    kernels must now match WITHOUT materializing)."""
    return jnp.repeat(t, rep, axis=1)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("rep", [2, 4])
def test_flash_gqa_matches_expanded_reference(causal, rep):
    """Flash with unexpanded [B, H_kv, S, D] K/V == MHA flash on the
    jnp.repeat-expanded K/V — the no-copy GQA path's core guarantee."""
    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    b, h, s, d = 2, 4, 256, 64
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, h // rep, s, d))
    v = jax.random.normal(kv, (b, h // rep, s, d))
    ref = attention_reference(q, _tiled(k, rep), _tiled(v, rep),
                              causal=causal)
    grouped_ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(grouped_ref), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_gradients_match_expanded_reference(causal):
    """dq at query-head shape; dk/dv at KV-head shape must equal the
    group-sum of the expanded oracle's per-head gradients (the kernel
    accumulates the query group in its dkv sweep)."""
    rep = 2
    kq, kk, kv = jax.random.split(jax.random.key(5), 3)
    b, h, s, d = 1, 4, 128, 32
    q = jax.random.normal(kq, (b, h, s, d))
    k = jax.random.normal(kk, (b, h // rep, s, d))
    v = jax.random.normal(kv, (b, h // rep, s, d))
    cot = jnp.cos(jnp.arange(b * h * s * d, dtype=jnp.float32)
                  ).reshape(b, h, s, d)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        return (o * cot).sum()

    def loss_ref(q, k, v):
        o = attention_reference(q, _tiled(k, rep), _tiled(v, rep),
                                causal=causal)
        return (o * cot).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    # Differentiating through jnp.repeat group-sums dk/dv automatically
    # (repeat's transpose), so oracle grads land at kv-head shape too.
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(gf, gr, "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


def test_flash_gqa_sliding_window():
    """GQA × SWA through the flash kernel (band skip composes with the
    kv-head index maps)."""
    kq, kk, kv = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(kq, (1, 6, 256, 32))
    k = jax.random.normal(kk, (1, 2, 256, 32))
    v = jax.random.normal(kv, (1, 2, 256, 32))
    for w in (37, 128):
        ref = attention_reference(q, _tiled(k, 3), _tiled(v, 3),
                                  causal=True, window=w)
        got = flash_attention(q, k, v, causal=True, window=w,
                              block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"w={w}")


def test_flash_lse_gqa_matches_reference():
    from pddl_tpu.ops.attention import (
        _attention_reference_lse,
        flash_attention_lse,
    )

    kq, kk, kv = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(kq, (1, 4, 64, 16))
    k = jax.random.normal(kk, (1, 2, 64, 16))
    v = jax.random.normal(kv, (1, 2, 64, 16))
    for causal in (False, True):
        o1, l1 = flash_attention_lse(q, k, v, causal=causal,
                                     block_q=32, block_k=32)
        o2, l2 = _attention_reference_lse(q, _tiled(k, 2), _tiled(v, 2),
                                          causal, 16 ** -0.5)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # multi-hop pallas-interpret loop: tier-2 wall-clock
@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_gqa_rotates_unexpanded_kv(mesh8, use_flash):
    """Ring attention with kv-head-sized shards (the ppermute payload is
    H/H_kv-times smaller) == full expanded attention, fwd and grads."""
    from pddl_tpu.core.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=1, seq=8))
    kq, kk, kv = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(kq, (1, 4, 128, 16))
    k = jax.random.normal(kk, (1, 2, 128, 16))
    v = jax.random.normal(kv, (1, 2, 128, 16))
    for causal in (False, True):
        ref = attention_reference(q, _tiled(k, 2), _tiled(v, 2),
                                  causal=causal)
        out = jax.jit(lambda a, b, c: sequence_parallel_attention(
            a, b, c, mesh, causal=causal, use_flash=use_flash))(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    # Oracle grads: differentiating THROUGH jnp.repeat already reduces
    # dk/dv over each query group (repeat's transpose is a group-sum), so
    # shapes match the ring's kv-head-sized grads directly.
    g_ref = jax.grad(lambda a, b, c: attention_reference(
        a, _tiled(b, 2), _tiled(c, 2), causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(lambda a, b, c: sequence_parallel_attention(
        a, b, c, mesh, causal=True, use_flash=use_flash).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for got, want in zip(g_ring, g_ref):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-4, rtol=3e-4)


@pytest.mark.parametrize("gqa", [False, True])
def test_fused_and_twosweep_backwards_agree(monkeypatch, gqa):
    """The single-sweep fused backward (default) and the two-sweep
    fallback (forced via a zero dq-scratch budget) must produce the same
    gradients — the fallback exists only for sequences whose dq
    accumulator exceeds VMEM."""
    import pddl_tpu.ops.attention as A

    kq, kk, kv = jax.random.split(jax.random.key(31), 3)
    hkv = 2 if gqa else 4
    q = jax.random.normal(kq, (1, 4, 128, 32))
    k = jax.random.normal(kk, (1, hkv, 128, 32))
    v = jax.random.normal(kv, (1, hkv, 128, 32))

    def grads():
        return jax.grad(lambda *a: flash_attention(
            *a, causal=True, window=50, block_q=32, block_k=32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    fused = grads()
    monkeypatch.setattr(A, "_FUSED_BWD_DQ_BYTES", 0)
    twosweep = grads()
    for a, b, name in zip(fused, twosweep, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"d{name}")


def test_decode_attention_linear_and_rolling_match_oracle():
    """The serving sweep (bf16-style storage reads, grouped heads,
    prefix-bounded fori_loop, ring-buffer slot mapping) vs plain windowed
    attention over the true key history."""
    from pddl_tpu.ops.attention import decode_attention

    B, Hkv, rep, D = 1, 2, 3, 16
    H = Hkv * rep
    ring, window, T = 128, 100, 300  # cache wrapped twice
    kk, kv, kq = jax.random.split(jax.random.key(21), 3)
    keys = jax.random.normal(kk, (B, Hkv, T, D))
    vals = jax.random.normal(kv, (B, Hkv, T, D))
    q = jax.random.normal(kq, (B, H, 1, D))

    # Oracle: the current token (position T-1) attends over the real
    # history under the window.
    ref = attention_reference(q, keys, vals, causal=True, window=window,
                              k_offset=-(T - 1))

    # Linear cache: history at slots 0..T-1, padded tail beyond.
    k_lin = jnp.zeros((B, Hkv, 512, D)).at[:, :, :T].set(keys)
    v_lin = jnp.zeros((B, Hkv, 512, D)).at[:, :, :T].set(vals)
    out_lin = decode_attention(q, k_lin, v_lin, jnp.int32(T - 1),
                               window=window, chunk=128)
    np.testing.assert_allclose(np.asarray(out_lin), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    # Ring cache: slot j holds the newest position ≡ j (mod ring).
    slots = jnp.arange(T) % ring
    k_ring = jnp.zeros((B, Hkv, ring, D)).at[:, :, slots].set(keys)
    v_ring = jnp.zeros((B, Hkv, ring, D)).at[:, :, slots].set(vals)
    out_ring = decode_attention(q, k_ring, v_ring, jnp.int32(T - 1),
                                window=window, rolling=True, chunk=64)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_chunk_not_dividing_cache():
    """A cache length the chunk doesn't divide (prime-ish max_decode_len)
    must stay exact: the tail chunk clamps its slice start and masks the
    re-read overlap — never degrading to a chunk=1 sweep."""
    from pddl_tpu.ops.attention import decode_attention

    B, H, D, L, T = 1, 2, 16, 331, 331  # prime cache length, fully live
    kk, kv, kq = jax.random.split(jax.random.key(6), 3)
    keys = jax.random.normal(kk, (B, H, T, D))
    vals = jax.random.normal(kv, (B, H, T, D))
    q = jax.random.normal(kq, (B, H, 1, D))
    ref = attention_reference(q, keys, vals, causal=True,
                              k_offset=-(T - 1))
    out = decode_attention(q, keys, vals, jnp.int32(T - 1), chunk=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_decode_attention_empty_history_returns_zero_weight():
    """history_only at index 0 (nothing attended yet) must yield zero
    output and ~-inf lse on BOTH the single-shot and chunked paths — a
    fully-masked fused pass would otherwise average the raw cache (the
    masked-softmax exp(0) pitfall)."""
    from pddl_tpu.ops.attention import decode_attention

    kq = jax.random.key(2)
    q = jax.random.normal(kq, (1, 2, 1, 8))
    cache = jnp.full((1, 2, 64, 8), 7.0)  # garbage that must not leak
    for chunk in (64, 16):  # single-shot and chunked
        out, lse = decode_attention(q, cache, cache, jnp.int32(0),
                                    history_only=True, return_lse=True,
                                    chunk=chunk)
        np.testing.assert_array_equal(np.asarray(out), 0.0)
        assert float(lse.max()) < -1e29


def test_decode_attention_prefix_bound_ignores_cache_garbage():
    """Slots beyond the valid prefix must never influence the output —
    the fori_loop stops at the last live chunk and masking covers the
    partial one (huge garbage planted past the prefix stays inert)."""
    from pddl_tpu.ops.attention import decode_attention

    B, H, D, L, T = 1, 2, 8, 256, 70
    kk, kv, kq = jax.random.split(jax.random.key(4), 3)
    keys = jax.random.normal(kk, (B, H, T, D))
    vals = jax.random.normal(kv, (B, H, T, D))
    q = jax.random.normal(kq, (B, H, 1, D))
    k_cache = jnp.full((B, H, L, D), 1e30).at[:, :, :T].set(keys)
    v_cache = jnp.full((B, H, L, D), 1e30).at[:, :, :T].set(vals)
    out = decode_attention(q, k_cache, v_cache, jnp.int32(T - 1), chunk=64)
    ref = attention_reference(q, keys, vals, causal=True,
                              k_offset=-(T - 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.slow  # multi-hop pallas-interpret loop: tier-2 wall-clock
@pytest.mark.parametrize("use_flash", [False, True])
def test_ring_swa_gqa_matches_windowed_reference(mesh8, use_flash):
    """Ring × SWA × GQA (VERDICT r3 task 4): the full composition —
    sequence-sharded ring rotating unexpanded kv-head shards with a
    sliding window that skips out-of-band rotations — fwd and grads vs
    the windowed grouped oracle. Windows aligned and unaligned to the
    16-position shard size, including one so narrow (w=5) that most
    rotations are skipped outright."""
    from pddl_tpu.core.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(data=1, seq=8))
    kq, kk, kv = jax.random.split(jax.random.key(14), 3)
    q = jax.random.normal(kq, (1, 4, 128, 16))   # s_local = 16
    k = jax.random.normal(kk, (1, 2, 128, 16))
    v = jax.random.normal(kv, (1, 2, 128, 16))
    for w in (5, 16, 37, 100):
        ref = attention_reference(q, k, v, causal=True, window=w)
        got = jax.jit(lambda a, b, c, w=w: sequence_parallel_attention(
            a, b, c, mesh, causal=True, window=w,
            use_flash=use_flash))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4, err_msg=f"w={w}")

    w = 37
    g_ref = jax.grad(lambda a, b, c: attention_reference(
        a, b, c, causal=True, window=w).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(lambda a, b, c: sequence_parallel_attention(
        a, b, c, mesh, causal=True, window=w, use_flash=use_flash).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_ring, g_ref, "qkv"):
        assert got.shape == want.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=3e-4, rtol=3e-4,
                                   err_msg=f"d{name} (w={w})")


def test_flash_k_offset_matches_reference():
    """The static k_offset (ring rotations' shifted key positions) in
    the Pallas kernel vs the reference's k_offset masking, fwd + grads."""
    kq, kk, kv = jax.random.split(jax.random.key(15), 3)
    q = jax.random.normal(kq, (1, 2, 64, 16))
    k = jax.random.normal(kk, (1, 2, 64, 16))
    v = jax.random.normal(kv, (1, 2, 64, 16))
    from pddl_tpu.ops.attention import flash_attention_lse

    for off, w in ((-64, 100), (-32, 40), (-64, None)):
        ref = attention_reference(q, k, v, causal=True, window=w,
                                  k_offset=off)
        got, _ = flash_attention_lse(q, k, v, causal=True, window=w,
                                     k_offset=off, block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"off={off} w={w}")


def test_gqa_head_divisibility_validated():
    q = jnp.zeros((1, 4, 16, 8))
    k = jnp.zeros((1, 3, 16, 8))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, k)
    with pytest.raises(ValueError, match="divisible"):
        attention_reference(q, k, k)


def test_window_requires_causal():
    q = jnp.zeros((1, 1, 16, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, window=4)
    with pytest.raises(ValueError, match="causal"):
        attention_reference(q, q, q, window=4)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, q, q, causal=True, window=0)
