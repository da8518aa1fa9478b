"""The window/NoPE-global, pre-attention-routed, ReGLU-expert block
(SmallThinker's) on the one Llama block, held to the benchmark's plain
reference (``chipbench/reference/smallthinker.py``: float32, full mask, a
loop over every expert, imports nothing of the program) at a size the CPU
holds: one period ``[global+NoPE, window+RoPE x 3]``, window 8 with
contexts past three windows so that blocks are skipped, 8 experts top-2,
``head_dim`` 8 where ``embed / heads`` is not a whole number, 7:1 GQA.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import smallthinker as reference
from chipbench.weights_smallthinker import make_weights
from pddl_tpu.models.llama import tiny_smallthinker
from pddl_tpu.ops import moe
from pddl_tpu.serve import SamplingParams, ServeEngine

CFG = {"num_hidden_layers": 4, "hidden_size": 40, "num_attention_heads": 7,
       "num_key_value_heads": 1, "head_dim": 8, "moe_ffn_hidden_size": 16,
       "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2,
       "sliding_window_size": 8, "vocab_size": 64, "rope_theta": 1.5e6,
       "rms_norm_eps": 1e-6, "sliding_window_layout": [0, 1, 1, 1],
       "rope_layout": [0, 1, 1, 1]}


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    monkeypatch.setattr(reference, "Q_BLOCK", 8)
    monkeypatch.setattr(reference, "PAD_TO", 8)


@pytest.fixture(scope="module")
def weights():
    """The benchmark's draw, every matrix four times as large (norm
    scales as drawn): at 40 wide the N(0, 0.02) of the real size makes a
    near-linear model that hardly notices its positions or its experts."""
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
def as_mlp_routed(params):
    """The same weights in the layout of a block that routes from the
    MLP's own normed input (router inside the expert layer, zero bias)."""
    out = dict(params)
    for i in range(CFG["num_hidden_layers"]):
        block = dict(params[f"block{i}"])
        kernel = block.pop("router")["kernel"]
        block["moe"] = dict(block["moe"], router={
            "kernel": kernel, "bias": jnp.zeros(kernel.shape[1])})
        out[f"block{i}"] = block
    return out


VARIANTS = {
    "as_published": ({}, False),
    "router_reads_the_mlps_input": ({"moe_router_input": "mlp"}, True),
    "nope_layer_rotates": ({"rope_layout": (1, 1, 1, 1)}, True),
    "window_layer_attends_past_its_band":
        ({"sliding_window_layout": (0, 0, 0, 0)}, True),
    "global_layer_is_windowed":
        ({"sliding_window_layout": (1, 1, 1, 1)}, True),
}


@pytest.mark.parametrize("variant", VARIANTS)
def test_forward_matches_the_reference_and_only_as_published(weights,
                                                             variant):
    """The model's full forward against the reference, 40 tokens (five
    windows). Each departure from the published layer — the router on
    the MLP's input, a rotated NoPE layer, a window layer that sees past
    its band, a windowed global layer — must read as wrong."""
    options, must_differ = VARIANTS[variant]
    model = tiny_smallthinker(**options)
    params = weights["params"]
    if options.get("moe_router_input") == "mlp":
        params = as_mlp_routed(params)
    tokens = tokens_of(1, 40)
    got = np.asarray(model.apply({"params": params}, tokens[None],
                                 train=False)[0])
    want, _ = reference_logits(weights, tokens)
    err = np.abs(got - want).max()
    if must_differ:
        assert err > 1e-2, err
    else:
        assert err < 2e-4, err


def test_program_and_reference_route_alike(weights):
    model = tiny_smallthinker()
    tokens = tokens_of(2, 33)
    _, state = model.apply({"params": weights["params"]}, tokens[None],
                           train=False, mutable=["intermediates"])
    _, want = reference_logits(weights, tokens)
    for i in range(4):
        got = np.asarray(state["intermediates"][f"block{i}"]["moe"][
            "expert_index"][0][0])
        assert (np.sort(got, -1) == np.sort(want[i], -1)).all()


# ------------------------------------------------------- the expert path
def per_token_loop(x, index, gates, w1, w3, w2, act="reglu"):
    gate = (lambda v: np.maximum(v, 0.0)) if act == "reglu" \
        else (lambda v: v / (1.0 + np.exp(-v)))
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for j in range(index.shape[1]):
            e = index[t, j]
            hid = gate(x[t] @ w1[e]) * (x[t] @ w3[e])
            out[t] += gates[t, j] * (hid @ w2[e])
    return out


def expert_case(routing, tokens, n=8, k=2, d=24, h=16):
    rng = np.random.RandomState(0)
    x = rng.randn(tokens, d).astype(np.float32)
    w1, w3 = rng.randn(2, n, d, h).astype(np.float32) * 0.2
    w2 = rng.randn(n, h, d).astype(np.float32) * 0.2
    if routing == "even":
        index = np.stack([np.arange(tokens) % n,
                          (np.arange(tokens) + 3) % n], 1)
    elif routing == "all_to_one_expert":
        index = np.stack([np.full(tokens, 5), np.full(tokens, 2)], 1)
    else:
        index = np.stack([rng.permutation(n)[:k] for _ in range(tokens)])
    gates = rng.rand(tokens, k).astype(np.float32)
    return x, index, gates, w1, w3, w2


@pytest.mark.parametrize("path", ["loop", "kernel"])
@pytest.mark.parametrize("routing", ["even", "all_to_one_expert", "drawn"])
@pytest.mark.parametrize("tokens,tile", [(37, None), (37, 16), (8, None)])
def test_grouped_expert_ffn_is_dropless(routing, tokens, tile, path):
    """The token-major serving path, as the loop and as the Pallas
    kernel (interpret mode), against a loop over tokens and their
    choices: under even routing, with EVERY token sent to one expert (a
    capacity would drop all but a few; six experts get no row), and
    drawn at random (with SwiGLU experts); at the default tile, at a
    tile smaller than an expert's run (several tiles an expert), and at
    decode size (two rows an expert: eight runs share one 16-row piece
    of the kernel's copies)."""
    x, index, gates, w1, w3, w2 = expert_case(routing, tokens)
    act = "swiglu" if routing == "drawn" else "reglu"
    got = moe.grouped_expert_ffn(
        jnp.asarray(x), jnp.asarray(index, jnp.int32), jnp.asarray(gates),
        jnp.asarray(w3), jnp.asarray(w2), act=act,
        w_gate=jnp.asarray(w1), tile=tile, kernel=path == "kernel",
        interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), per_token_loop(x, index, gates, w1, w3, w2, act),
        rtol=2e-5, atol=2e-5)


def test_expert_kernel_matches_the_loop_in_bfloat16(monkeypatch):
    """bf16 rows and weights, the hidden width streamed in three blocks
    (the down projection's float32 sums carried across them): the
    kernel agrees with the loop to bf16 rounding (the loop rounds the
    SiLU and its product where the kernel keeps float32, so a row may
    differ by an ulp or two), far under what a dropped or misplaced
    block would move."""
    monkeypatch.setattr(moe, "_h_block", lambda d, h, itemsize: 128)
    x, index, gates, w1, w3, w2 = expert_case("drawn", 40, h=384)
    args = [jnp.asarray(a, jnp.bfloat16) for a in (x, w3, w2, w1)]
    run = lambda path: np.asarray(moe.grouped_expert_ffn(
        args[0], jnp.asarray(index, jnp.int32), jnp.asarray(gates),
        args[1], args[2], act="swiglu", w_gate=args[3],
        kernel=path == "kernel", interpret=True), np.float32)
    loop, kernel = run("loop"), run("kernel")
    scale = np.abs(loop).max()
    assert np.abs(kernel - loop).max() < 0.02 * scale
    assert np.linalg.norm(kernel - loop) < 0.01 * np.linalg.norm(loop)


def test_expert_tile_bounds_the_padding():
    """Rows computed are at most pairs + experts x (tile - 1): under 3 x
    the pairs from 16 pairs an expert up, and a decode step's 48 pairs
    pad to 16-row tiles, not to hundreds."""
    assert moe.expert_tile(48, 64) == 16
    assert moe.expert_tile(2048 * 6, 64) == 256
    assert moe.expert_tile(12288 * 6, 64) == 512
    for pairs in (1024, 12288, 73728):
        tile = moe.expert_tile(pairs, 64)
        assert pairs + 64 * (tile - 1) < 3 * pairs


# ------------------------------------------------------------ the engine
def engine_for(weights, **kw):
    kw.setdefault("max_slots", 3)
    return ServeEngine(tiny_smallthinker(), weights,
                       prefill_len=64, prefix_block_size=4, prefix_chunk=16,
                       **kw)


def test_paged_engine_serves_what_the_reference_computes(weights):
    """Prefill in several chunks (prompts of 50, 37, 20 and 9 tokens
    through 16-wide chunks, window 8: history blocks under the band are
    skipped), then decode through the paged cache: every greedy token is
    the reference's best to float32 rounding, and every sampled token lies
    inside the reference's nucleus. The reference sees the whole
    sequence at once and no cache."""
    engine = engine_for(weights, rng=jax.random.key(5))
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


def test_engine_counts_prefill_and_expert_load(weights):
    engine = engine_for(weights)
    engine.warmup()   # its two one-token chunks count too
    before = engine.expert_load()
    assert sorted(before) == [f"block{i}/moe" for i in range(4)]
    prompts = [tokens_of(20 + i, n) for i, n in enumerate((50, 9))]
    for p in prompts:
        engine.submit(p, 3)
    engine.run()
    snap = engine.metrics.snapshot()
    assert snap["prefill_tokens"] == 59
    # 50 tokens: the wide program (64); 9: one 16-wide chunk.
    assert snap["prefill_chunks"] == {"64": 1, "16": 1}
    for name, load in engine.expert_load().items():
        # two choices a prompt token, pads and decode rows not counted
        assert (load - before[name]).sum() == 2 * 59, name
    assert sorted(engine.program_lowerings()) == [
        "chunk_prefill", "chunk_prefill_wide", "tick"]


def test_window_model_is_paged_not_row_cached(weights):
    """``ring_len(8, 256)`` is 128: ``generate()`` would allocate a
    rolling ring; the engine gives window layers the full-length pool,
    one ``[N, Hkv, bs, 2D]`` leaf a layer like any other."""
    model = tiny_smallthinker(max_len=256)
    assert model.uses_ring_cache
    eng = ServeEngine(model, weights, max_slots=2, prefill_len=64,
                      prefix_block_size=4)
    assert eng.paged
    pools = [leaf for leaf in jax.tree.leaves(eng._cache) if leaf.ndim == 4]
    assert len(pools) == model.depth
    assert {leaf.shape[0] for leaf in pools} == {eng._prefix.num_blocks}


def test_no_wide_program_beside_a_wide_chunk(weights):
    """A chunk of 1,024 tokens amortises an apply's fixed cost by itself:
    no ``prefill_len``-wide second program is built (at 12,288 wide its
    temporaries did not fit the chip)."""
    model = tiny_smallthinker(max_len=4096)
    wide = ServeEngine(model, weights, max_slots=1,
                       prefill_len=2048, prefix_block_size=16,
                       prefix_chunk=512)
    none = ServeEngine(model, weights, max_slots=1,
                       prefill_len=2048, prefix_block_size=16,
                       prefix_chunk=1024)
    assert wide._has_wide and not none._has_wide
    assert "chunk_prefill_wide" not in none.program_lowerings()
