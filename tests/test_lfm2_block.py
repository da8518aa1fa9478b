"""The gated short convolution beside grouped-query attention with q/k
norm, two kinds of operator in one model (LFM2-24B-A2B's block), held to
the benchmark's plain reference (``chipbench/reference/lfm2.py``: float32,
the whole sequence at once, no cache and no state, imports nothing of the
program) at a size the CPU holds: ``tiny_lfm2`` — a dense convolution
layer, then one period [attention, 3 x convolution] routed over 8 experts
top-4; 4 q heads over 2 kv heads of 8; 3 taps.

Through the paged ``ServeEngine`` the convolution's state has the slot's
life: zeroed by the first chunk, carried chunk to chunk and slice to slice,
handed to the tick, rebuilt on replay, never shared and never leaked.

Tolerances, all float32 on the CPU: 2e-4 on logits of order one (the
reference multiplies at ``highest``, the program at the default; 40-token
sums), 1e-4 on a served greedy token's gap below the reference's best
logit (a wrong state moves logits by tenths), 1e-5 between two chunkings
of the program itself (the same sums in another order).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import lfm2 as reference
from chipbench.tools import faults_lfm2
from chipbench.weights_lfm2 import make_weights
from pddl_tpu.models.llama import LFM2_24B_A2B, ShortConv, tiny_lfm2
from pddl_tpu.models.speculative import generate_speculative
from pddl_tpu.serve import Priority, SamplingParams, ServeEngine

TYPES = ("conv", "full_attention", "conv", "conv", "conv")
CFG = {"num_hidden_layers": 5, "layer_types": list(TYPES),
       "num_dense_layers": 1, "hidden_size": 32, "num_attention_heads": 4,
       "num_key_value_heads": 2, "conv_L_cache": 3, "intermediate_size": 48,
       "num_experts": 8, "num_experts_per_tok": 4,
       "moe_intermediate_size": 16, "routed_scaling_factor": 1,
       "vocab_size": 64, "rope_parameters": {"rope_theta": 1e6},
       "norm_eps": 1e-5, "initializer_range": 0.08,
       # At 8 experts the scores lie 0.1 apart, not 0.02: a spread that
       # re-orders them here is wider than the cell's.
       "select_bias_std": 0.1}


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    for name, size in (("Q_BLOCK", 8), ("PAD_TO", 8), ("ROW_BLOCK", 8),
                       ("HEAD_ROWS", 8)):
        monkeypatch.setattr(reference, name, size)


@pytest.fixture(scope="module")
def weights():
    """The benchmark's draw at four times its standard deviation (norm
    scales, taps and the bias as drawn): at 32 wide the N(0, 0.02) of the
    real size makes a near-linear model that hardly notices its experts."""
    return make_weights(CFG, 7, dtype=jnp.float32)


def reference_logits(weights, tokens):
    logits, _ = reference.forward(weights["params"], CFG, tokens,
                                  np.arange(len(tokens)))
    return np.asarray(logits)


def tokens_of(seed, n):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0, 64))


# ------------------------------------------------------------ the model
def test_constructor_gives_the_published_layout_and_the_cut():
    whole, cut = LFM2_24B_A2B(), LFM2_24B_A2B(depth=9)
    assert whole.layer_types.count("full_attention") == 10
    assert whole.layer_types[:3] == ("conv", "conv", "full_attention")
    assert whole.moe_layout == (0, 0) + (1,) * 38
    assert cut.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv", "full_attention", "conv", "conv",
                               "conv")
    assert cut.moe_layout == (0,) + (1,) * 8
    assert (cut.slot_state_layers, cut.latent_layers) == (7, 0)
    assert cut.unrewindable_cache and not cut.uses_ring_cache
    with pytest.raises(ValueError, match="per-layer layout"):
        tiny_lfm2(layer_types=("conv",)).init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32), train=False)
    with pytest.raises(ValueError, match="layer_types"):
        tiny_lfm2(layer_types=("conv", "ssm", "conv", "conv",
                               "conv")).slot_state_layers


def test_short_conv_forward_is_the_references(weights):
    """(i) The operator alone over a whole sequence: the module's causal
    depthwise convolution against the reference's three shifted
    products."""
    p = weights["params"]["block0"]
    x = jax.random.normal(jax.random.key(3), (40, 32), jnp.float32)
    want = reference.short_conv(x, p["conv"], p["ln1"]["scale"], eps=1e-5)
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) \
        * p["ln1"]["scale"]
    got = x + ShortConv().apply({"params": p["conv"]}, u[None])[0]
    assert np.abs(np.asarray(got - want)).max() < 2e-5


# The faults the chip tool plants in the timed path, here under the
# model's own forward: each must read as wrong (ix).
VARIANTS = [None, "selection_bias_dropped", "bias_in_the_gates",
            "b_c_exchanged", "taps_reversed", "qk_norm_left_out"]


@pytest.mark.parametrize("fault", VARIANTS,
                         ids=lambda f: f or "as_published")
def test_forward_matches_the_reference_and_only_as_published(weights, fault):
    """(ii), (ix) The model's full forward against the reference, 40
    tokens; each departure from the layer must read as wrong."""
    undo = faults_lfm2.FAULTS[fault]() if fault else (lambda: None)
    try:
        tokens = tokens_of(1, 40)
        got = np.asarray(tiny_lfm2().apply(
            {"params": weights["params"]}, tokens[None], train=False)[0])
    finally:
        undo()
    err = np.abs(got - reference_logits(weights, tokens)).max()
    if fault:
        assert err > 1e-2, err
    else:
        assert err < 2e-4, err


# ------------------------------------------------------------ the engine
def engine_for(weights, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("prefix_chunk", 16)
    return ServeEngine(tiny_lfm2(), weights, prefill_len=64,
                       prefix_block_size=4, **kw)


def record_first_logits(engine):
    """The logits each admission samples its first token from, in
    admission order (the prefill's last real row)."""
    engine.warmup()  # its own first-token call is not an admission's
    seen, real = [], engine._sample_first_p

    def wrapped(logits, *rest):
        seen.append(np.asarray(logits[0]))
        return real(logits, *rest)

    wrapped._cache_size = real._cache_size
    engine._sample_first_p = wrapped
    return seen


def state_rows(engine, sid):
    """Slot ``sid``'s convolution state, every layer: ``[4, 2, 32]``."""
    return np.stack([np.asarray(engine._cache[f"block{i}"]["conv"][
        "slot_state"][sid]) for i, kind in enumerate(TYPES)
        if kind == "conv"])


def assert_served(weights, prompt, handle, n):
    """Every served greedy token is the reference's best, by its own
    logits, over the whole stream."""
    assert len(handle.tokens) == n
    g = reference.served_gaps(weights["params"], CFG, prompt,
                              handle.tokens, n)
    assert g["gaps"].max() < 1e-4, g["gaps"]


@pytest.mark.parametrize("plen", [22, 24, 17, 15, 1, 2])
def test_paged_engine_serves_what_the_reference_computes(weights, plen):
    """(iii) Prefill through 16-wide chunks of 4-token blocks, then the
    tick: a prompt that ends mid-block (22), at a block boundary (24), one
    token past a chunk boundary (17: the last chunk holds one real token
    and fifteen of padding) and one short of it (15), and prompts of 1 and
    2 tokens (shorter than the state). The first token's logits are the
    reference's at the prompt's last row; every decoded token is the
    reference's best."""
    engine = engine_for(weights)
    firsts = record_first_logits(engine)
    prompt = tokens_of(40 + plen, plen)
    handle = engine.submit(prompt, 8)
    engine.run()
    want = reference_logits(weights, prompt)[-1]
    assert np.abs(firsts[0] - want).max() < 2e-4
    assert_served(weights, prompt, handle, 8)
    assert set(engine.compile_counts().values()) == {1}
    snap = engine.metrics.snapshot()
    assert snap["state_rows_started"] == 1
    assert snap["state_bytes_resident"] == 4 * 3 * 2 * 32 * 4


def test_cache_tree_holds_pools_by_blocks_and_state_by_slots(weights):
    engine = engine_for(weights)
    conv, attn = engine._cache["block0"]["conv"], \
        engine._cache["block1"]["attn"]
    assert sorted(conv) == ["cache_index", "slot_state", "state_slot",
                            "valid_len"]
    assert conv["slot_state"].shape == (3, 2, 32)
    assert sorted(attn) == ["block_table", "cache_index", "cached_kv"]
    assert attn["cached_kv"].shape[1:] == (2, 4, 16)  # K | V of 8 + 8
    assert engine.prefix_pool_nbytes == attn["cached_kv"].nbytes
    assert engine._kv_token_bytes == 2 * 16 * 4


def test_chunked_is_unchunked_and_sliced_is_whole(weights):
    """(iv) One 45-token prompt four ways — three 16-wide chunks (short of
    the three quarters of ``prefill_len`` that would take the one wide
    program), one 48-wide chunk, 16-token slices with another stream's ticks in between
    (the sliced slot is parked meanwhile: the tick must leave its row
    alone), and the slices alone: the same state after prefill, the same
    first logits, the same tokens."""
    prompt = tokens_of(5, 45)

    def serve(sliced_beside_a_live_stream=False, **kw):
        engine = engine_for(weights, **kw)
        firsts = record_first_logits(engine)
        if sliced_beside_a_live_stream:
            other = engine.submit(tokens_of(6, 9), 30)
            engine.step()
            assert other.tokens
        handle = engine.submit(prompt, 1)
        engine.run()
        sid = 1 if sliced_beside_a_live_stream else 0
        return state_rows(engine, sid), firsts[-1], handle.tokens

    chunked = serve()
    for other, atol in ((serve(prefix_chunk=48), 1e-5),
                        (serve(prefill_slice_tokens=16), 0.0),
                        (serve(True, prefill_slice_tokens=16), 0.0)):
        for a, b in zip(chunked[:2], other[:2]):
            np.testing.assert_allclose(a, b, rtol=0, atol=atol)
        assert chunked[2] == other[2]
    want = reference_logits(weights, prompt)[-1]
    assert np.abs(chunked[1] - want).max() < 2e-4


def test_a_slot_forgets_its_previous_stream(weights):
    """(v) One slot, a long stream and then a short one: the short one
    reads the reference's logits, not the long one's leftover state."""
    engine = engine_for(weights, max_slots=1)
    firsts = record_first_logits(engine)
    long_, short = tokens_of(8, 45), tokens_of(9, 2)
    first = engine.submit(long_, 12)
    second = engine.submit(short, 10)
    engine.run()
    assert_served(weights, long_, first, 12)
    assert_served(weights, short, second, 10)
    assert np.abs(firsts[1] - reference_logits(weights, short)[-1]).max() \
        < 2e-4
    assert engine.metrics.snapshot()["state_rows_started"] == 2


def test_a_shared_prefix_is_recomputed_and_counted(weights):
    """(vi) Two prompts that share three blocks: a prefix hit would
    restore the attention layer's K/V and not the convolutions' state, so
    the engine neither matches nor donates; both streams read right."""
    engine = engine_for(weights)
    shared = tokens_of(10, 12)
    prompts = [np.concatenate([shared, tokens_of(11 + i, 7)])
               for i in range(2)]
    first = engine.submit(prompts[0], 6)
    engine.run()
    second = engine.submit(prompts[1], 6)
    engine.run()
    for p, h in zip(prompts, (first, second)):
        assert_served(weights, p, h, 6)
    snap = engine.metrics.snapshot()
    assert snap["prefix_skipped_stateful"] == 2
    assert snap["prefix_hits"] == 0 and engine._prefix.blocks_live == 0
    assert engine.blocks_shared == 0


def test_replay_rebuilds_the_state(weights):
    """(vii) A preempted stream and a drained one come back through the
    replay admission — the prompt prefilled again (which rebuilds the
    state, as it rebuilds K/V), the emitted tokens fed back through the
    tick — and go on to the reference's tokens."""
    engine = engine_for(weights, max_slots=1)
    slow, fast = tokens_of(12, 21), tokens_of(13, 9)
    held = engine.submit(slow, 14, priority=Priority.BEST_EFFORT)
    for _ in range(4):
        engine.step()
    urgent = engine.submit(fast, 5, priority=Priority.INTERACTIVE)
    engine.run(max_steps=300)
    assert engine.metrics.preemptions >= 1
    assert_served(weights, slow, held, 14)
    assert_served(weights, fast, urgent, 5)

    first = engine_for(weights)
    prompts = [tokens_of(14, 19), tokens_of(15, 33)]
    for p in prompts:
        first.submit(p, 12)
    for _ in range(5):
        first.step()
    snapshot = first.drain()
    second = engine_for(weights)
    handles = second.restore(snapshot)
    assert all(0 < len(h.tokens) < 12 for h in handles)
    second.run(max_steps=300)
    for p, h in zip(prompts, handles):
        assert_served(weights, p, h, 12)


def test_sampled_streams_stay_inside_the_nucleus(weights):
    engine = engine_for(weights, rng=jax.random.key(5))
    prompt = tokens_of(16, 37)
    handle = engine.submit(prompt, 12, sampling=SamplingParams(
        temperature=0.7, top_p=0.9))
    engine.run()
    g = reference.served_gaps(weights["params"], CFG, prompt, handle.tokens,
                              12, temperature=0.7, top_p=0.9)
    assert g["nucleus_excess"].max() < 1e-3


@pytest.mark.parametrize("what", ["speculative_engine", "draft_model",
                                  "generate_speculative", "host_tier",
                                  "export_prefix_chain",
                                  "import_prefix_chain"])
def test_what_cannot_carry_slot_state_says_so(weights, what):
    """(viii) Speculation would have to take rejected tokens back out of
    the state; the host tier and a prefix chain carry K/V blocks only."""
    if what == "speculative_engine":
        with pytest.raises(NotImplementedError, match="per-slot state"):
            engine_for(weights, spec_k=2)
    elif what == "draft_model":
        from pddl_tpu.models.llama import tiny_llama

        target = tiny_llama(max_len=128)
        variables = {"params": target.init(
            jax.random.key(0), jnp.ones((1, 8), jnp.int32),
            train=False)["params"]}
        with pytest.raises(NotImplementedError, match="per-slot state"):
            ServeEngine(target, variables, prefill_len=64,
                        prefix_block_size=4, spec_k=2,
                        spec_draft_model=tiny_lfm2(),
                        spec_draft_variables=weights)
    elif what == "generate_speculative":
        with pytest.raises(NotImplementedError, match="per-slot state"):
            generate_speculative(tiny_lfm2(), weights,
                                 jnp.zeros((1, 4), jnp.int32), 4)
    elif what == "host_tier":
        with pytest.raises(NotImplementedError, match="per-slot state"):
            engine_for(weights, host_tier=1 << 20)
    else:
        engine = engine_for(weights)
        call = (lambda: engine.export_prefix_chain(tokens_of(1, 16))) \
            if what == "export_prefix_chain" \
            else (lambda: engine.import_prefix_chain({}))
        with pytest.raises(NotImplementedError, match="per-slot state"):
            call()
