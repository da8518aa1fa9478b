"""The latent-attention, sigmoid-routed, shared-expert block
(GLM-4.7-Flash's, the DeepSeek-V3 form) on the one Llama block, held to
the benchmark's plain reference (``chipbench/reference/glm47_flash.py``:
float32, per-head attention under a full mask, a loop over every expert,
imports nothing of the program) at a size the CPU holds: a dense layer
then two routed ones, 8 experts top-2 beside a shared one, 4 latent heads
of 12 + 4 rope dims a key and 16 a value over a 24 + 4-value cache entry.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import glm47_flash as reference
from chipbench.weights_glm47_flash import make_weights
from pddl_tpu.models import llama
from pddl_tpu.models.llama import tiny_glm_flash, tiny_llama
from pddl_tpu.ops import moe
from pddl_tpu.ops.attention import (
    paged_cache_insert,
    paged_decode_attention,
    paged_kv_fuse,
)
from pddl_tpu.serve import SamplingParams, ServeEngine

CFG = {"num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 32,
       "num_attention_heads": 4, "q_lora_rank": 20, "kv_lora_rank": 24,
       "qk_nope_head_dim": 12, "qk_rope_head_dim": 4, "v_head_dim": 16,
       "intermediate_size": 48, "n_routed_experts": 8, "n_shared_experts": 1,
       "num_experts_per_tok": 2, "moe_intermediate_size": 16,
       "routed_scaling_factor": 1.8, "vocab_size": 64, "rope_theta": 1e6,
       "rms_norm_eps": 1e-5}


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    for name, size in (("Q_BLOCK", 8), ("PAD_TO", 8), ("ROW_BLOCK", 8),
                       ("HEAD_ROWS", 8)):
        monkeypatch.setattr(reference, name, size)


@pytest.fixture(scope="module")
def weights():
    """The benchmark's draw, every matrix (and the selection bias) four
    times as large, norm scales as drawn: at 32 wide the N(0, 0.02) of
    the real size makes a near-linear model that hardly notices its
    positions or its experts."""
    drawn = make_weights(CFG, 7, dtype=jnp.float32)
    return {"params": jax.tree_util.tree_map_with_path(
        lambda path, a: a if "scale" in str(path[-1]) else 4.0 * a,
        drawn["params"])}


def reference_logits(weights, tokens):
    logits, sets = reference.forward(weights["params"], CFG, tokens,
                                     np.arange(len(tokens)))
    return np.asarray(logits), np.asarray(sets)


def tokens_of(seed, n):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0, 64))


# ------------------------------------------------------------ the model
def _bias_in_the_gates(monkeypatch):
    """The selection bias leaks into the gates: the gates are taken from
    the biased scores the experts were chosen by."""
    serve = moe.SwitchFFN._serve
    monkeypatch.setattr(
        moe.SwitchFFN, "_serve",
        lambda self, x, probs, select, *w: serve(self, x, select, select, *w))


def _rope_over_the_whole_head(monkeypatch):
    """The rope dims rotated with the frequencies of a 16-dim head
    (theta^(-2i/16)) where they are a 4-dim rotation of their own."""
    rope = llama.apply_rope_qk
    monkeypatch.setattr(
        llama, "apply_rope_qk",
        lambda q, k, pos, *, theta: rope(q, k, pos, theta=theta ** (4 / 16)))


def _routed_first_layer(params):
    return dict(params, block0=dict(params["block0"],
                                    moe=params["block1"]["moe"]))


def _softmax_router(params):
    """The softmax router of the other lineage has a logit bias."""
    out = dict(params)
    for i in (1, 2):
        m = dict(params[f"block{i}"]["moe"])
        m["router"] = dict(m["router"], bias=jnp.zeros(8))
        out[f"block{i}"] = dict(params[f"block{i}"], moe=m)
    return out


VARIANTS = {
    # name: (model options, patch, params edit, must differ)
    "as_published": ({}, None, None, False),
    "bias_added_to_the_gates": ({}, _bias_in_the_gates, None, True),
    "scale_1_8_dropped": ({"moe_gate_scale": 1.0}, None, None, True),
    "shared_expert_dropped": ({"moe_shared_experts": 0}, None, None, True),
    "rope_over_the_whole_head": ({}, _rope_over_the_whole_head, None, True),
    "softmax_in_place_of_sigmoid":
        ({"moe_router_score": "softmax"}, None, _softmax_router, True),
    "dense_layer_routed":
        ({"moe_layout": (1, 1, 1)}, None, _routed_first_layer, True),
    "selection_bias_dropped": ({"moe_select_bias": False}, None, None, True),
}


@pytest.mark.parametrize("variant", VARIANTS)
def test_forward_matches_the_reference_and_only_as_published(
        weights, variant, monkeypatch):
    """The model's full forward (per-head form) against the reference,
    40 tokens. Each departure from the published layer must read as
    wrong."""
    options, patch, edit, must_differ = VARIANTS[variant]
    if patch is not None:
        patch(monkeypatch)
    params = weights["params"] if edit is None else edit(weights["params"])
    tokens = tokens_of(1, 40)
    got = np.asarray(tiny_glm_flash(**options).apply(
        {"params": params}, tokens[None], train=False)[0])
    want, _ = reference_logits(weights, tokens)
    err = np.abs(got - want).max()
    if must_differ:
        assert err > 1e-2, err
    else:
        assert err < 2e-4, err


def test_program_and_reference_route_alike(weights):
    model = tiny_glm_flash()
    tokens = tokens_of(2, 33)
    _, state = model.apply({"params": weights["params"]}, tokens[None],
                           train=False, mutable=["intermediates"])
    _, want = reference_logits(weights, tokens)
    assert "moe" not in state["intermediates"].get("block0", {})
    for layer, i in enumerate((1, 2)):
        got = np.asarray(state["intermediates"][f"block{i}"]["moe"][
            "expert_index"][0][0])
        assert (np.sort(got, -1) == np.sort(want[layer], -1)).all()


def test_training_gates_are_the_serving_gates(weights):
    """With room for every token (no expert over its capacity) the
    one-hot training path computes what the dropless serving path does:
    sigmoid scores, the bias in the choice only, the scale, the shared
    expert."""
    layer = moe.SwitchFFN(
        num_experts=8, hidden_dim=16, top_k=2, capacity_factor=8.0,
        expert_act="swiglu", router_score="sigmoid", select_bias=True,
        gate_scale=1.8, shared_experts=1)
    x = jax.random.normal(jax.random.key(3), (2, 12, 32))
    params = {"params": weights["params"]["block1"]["moe"]}
    served = layer.apply(params, x, False)
    trained, _ = layer.apply(params, x, True, mutable=["losses", "metrics"])
    np.testing.assert_allclose(np.asarray(trained), np.asarray(served),
                               rtol=1e-5, atol=1e-5)


def test_absorbed_decode_is_the_per_head_form(weights):
    """The row cache of ``generate()`` runs every step in the latent
    space (a 20-token block, then single tokens): its logits are the full
    per-head forward's."""
    model = tiny_glm_flash()
    dec = model.clone(decode=True)
    tokens = tokens_of(4, 28)
    cache = jax.tree.map(
        jnp.zeros_like,
        dec.init(jax.random.key(0), tokens[None, :1], train=False)["cache"])
    step = jax.jit(lambda cache, block: dec.apply(
        {"params": weights["params"], "cache": cache}, block, train=False,
        mutable=["cache"]))
    got = []
    for lo, hi in [(0, 20)] + [(i, i + 1) for i in range(20, 28)]:
        logits, state = step(cache, tokens[None, lo:hi])
        cache = state["cache"]
        got.append(np.asarray(logits[0]))
    want = np.asarray(model.apply({"params": weights["params"]},
                                  tokens[None], train=False)[0])
    np.testing.assert_allclose(np.concatenate(got), want, atol=2e-4)


# ------------------------------------------------------------ the kernel
@pytest.mark.parametrize("heads,cache_heads,dk,lanes,value_lanes", [
    (20, 20, 64, 128, None),        # GPT-2-large's fused leaf
    (28, 4, 128, 256, None),        # SmallThinker's: 7 q heads a kv head
    (20, 1, 576, 640, (0, 512)),    # the latent leaf: key all 576 values,
                                    # value the first 512, stored in 640
])
def test_one_paged_kernel_serves_every_declared_leaf(heads, cache_heads, dk,
                                                     lanes, value_lanes):
    """``_paged_decode_kernel`` in interpret mode against the jnp sweep:
    the leaf's lanes, its key lanes and its value lanes are what the
    caller declares, nothing else differs. Depths from one block to the
    whole table, a parked row among them."""
    slots, bs, table_w = 5, 16, 12
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(slots * table_w + 1, cache_heads, bs,
                                 lanes), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(slots * table_w).reshape(
        slots, table_w), jnp.int32).at[3].set(0)     # a parked row
    index = jnp.asarray([5, 16 * 12 - 1, 100, 0, 47], jnp.int32)
    q = jnp.asarray(rng.randn(slots, heads, 1, dk), jnp.float32)
    kw = dict(scale=dk ** -0.5, value_lanes=value_lanes)
    want = paged_decode_attention(q, pool, table, index, kernel=False, **kw)
    got = paged_decode_attention(q, pool, table, index, kernel=True,
                                 interpret=True, **kw)
    width = dk if value_lanes is None else value_lanes[1] - value_lanes[0]
    assert got.shape == (slots, heads, 1, width)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_cache_insert_takes_the_entry_as_declared():
    """One entry a token whatever its lanes: K and V side by side, or a
    latent entry; a leaf of other lanes is refused."""
    pool = jnp.zeros((9, 1, 4, 28))
    entry = jnp.arange(2 * 3 * 28, dtype=jnp.float32).reshape(2, 1, 3, 28)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    out = paged_cache_insert(pool, entry, table, jnp.asarray([2, 5]))
    np.testing.assert_array_equal(out[1, 0, 2:], entry[0, 0, :2])
    np.testing.assert_array_equal(out[2, 0, 0], entry[0, 0, 2])
    np.testing.assert_array_equal(out[6, 0, 1:], entry[1, 0])
    with pytest.raises(ValueError, match="do not fit"):
        paged_cache_insert(pool, paged_kv_fuse(entry, entry), table, 0)


# ------------------------------------------------------------ the engine
def engine_for(weights, **kw):
    kw.setdefault("max_slots", 3)
    return ServeEngine(tiny_glm_flash(), weights, prefill_len=64,
                       prefix_block_size=4, prefix_chunk=16, **kw)


def test_paged_engine_serves_what_the_reference_computes(weights):
    """Prefill in several chunks (prompts of 50, 37, 20 and 9 tokens
    through 16-wide chunks: cached entries re-expanded a sweep step at a
    time), then decode in the absorbed form through the paged cache:
    every greedy token is the reference's best to float32 rounding, and
    every sampled token lies inside the reference's nucleus. The
    reference sees the whole sequence at once and no cache."""
    engine = engine_for(weights, rng=jax.random.key(5))
    pools = [leaf for leaf in jax.tree.leaves(engine._cache)
             if leaf.ndim == 4]
    # One entry a token for all heads, in whole 128-lane tiles.
    assert [p.shape[1:] for p in pools] == [(1, 4, 128)] * 3
    prompts = [tokens_of(10 + i, n) for i, n in enumerate((50, 20, 37, 9))]
    sampled = SamplingParams(temperature=0.7, top_p=0.9)
    handles = [engine.submit(p, 12, sampling=sampled if i % 2 else None)
               for i, p in enumerate(prompts)]
    engine.run()
    for i, (p, h) in enumerate(zip(prompts, handles)):
        assert len(h.tokens) == 12
        g = reference.served_gaps(
            weights["params"], CFG, p, h.tokens, 12,
            temperature=0.7 if i % 2 else 0.0, top_p=0.9 if i % 2 else None)
        if i % 2:
            assert g["nucleus_excess"].max() < 1e-3
        else:
            assert g["gaps"].max() < 1e-4
    assert set(engine.compile_counts().values()) == {1}


def test_engine_counts_what_chunks_re_expand(weights):
    """``latent_expanded_tokens``: a chunk program re-expands the cached
    entries before its own, so a chunk dispatched at offset ``off`` adds
    ``off`` (50 tokens in 16-wide slices: 0 + 16 + 32 + 48; 9 tokens: 0).
    A model without latent layers counts nothing."""
    engine = engine_for(weights, prefill_slice_tokens=16)
    engine.warmup()
    before = engine.metrics.snapshot()["latent_expanded_tokens"]
    load_before = engine.expert_load()
    assert sorted(load_before) == ["block1/moe", "block2/moe"]
    for i, n in enumerate((50, 9)):
        engine.submit(tokens_of(20 + i, n), 3)
    engine.run()
    snap = engine.metrics.snapshot()
    assert snap["prefill_tokens"] == 59
    assert snap["latent_expanded_tokens"] - before == 16 + 32 + 48
    for name, load in engine.expert_load().items():
        assert (load - load_before[name]).sum() == 2 * 59, name

    plain = ServeEngine(tiny_llama(), {"params": tiny_llama().init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32),
        train=False)["params"]}, max_slots=2, prefill_len=64,
        prefix_block_size=4, prefix_chunk=16)
    plain.submit(tokens_of(30, 50), 3)
    plain.run()
    assert plain.metrics.snapshot()["latent_expanded_tokens"] == 0
