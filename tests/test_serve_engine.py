"""Continuous-batching serving engine (`pddl_tpu/serve/`), CPU.

The contracts under test:

- **Exactness**: a greedy request served through the slot-pooled engine
  emits exactly what single-request ``generate()`` emits — admit order,
  slot reuse, and neighbors in the batch must not change anyone's
  tokens (both families: GPT scalar-MHA cache, Llama GQA + RoPE).
- **Isolation**: per-slot sampling parameters are runtime arrays; one
  tick serves a greedy request next to a hot-temperature one without
  either leaking into the other.
- **Lifecycle**: admit → stream → evict for length/eos; cancellation
  and deadlines evict mid-decode with tokens-so-far intact; a full
  queue sheds load with the typed ``QueueFull``.
- **Fixed-shape discipline**: after ``warmup()`` a mixed workload
  (different prompt lengths, sampling params, request sizes) compiles
  NOTHING new — every resident program stays at exactly one
  executable.
- **One engine**: the default constructor IS the paged engine; the
  site vocabulary, the donation map and ``compile_counts()`` agree for
  every engine kind; whatever ends a stream hands its blocks back.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import (
    FakeClock as _FakeClock,
    assert_pool_idle as _assert_pool_idle,
    ref_greedy as _ref_greedy,
)
from pddl_tpu.models.gpt import (
    batched_filtered_logits,
    filtered_logits,
    generate,
    sample_logits_batched,
    tiny_gpt,
)
from pddl_tpu.models.llama import tiny_llama
from pddl_tpu.serve import (
    FaultPlan,
    FinishReason,
    Priority,
    QueueFull,
    RequestState,
    SamplingParams,
    ServeEngine,
)
from pddl_tpu.serve.tenant import AdapterRegistry, TenantConfig


@pytest.fixture(scope="module")
def gpt_setup():
    model = tiny_gpt(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.key(0), prompt, train=False)["params"]
    return model, {"params": params}


def test_admit_evict_slot_reuse_matches_generate(gpt_setup):
    """More requests than slots: every slot is reused, every request's
    greedy stream equals its single-request generate() — the whole
    point of iteration-level scheduling is that batching is invisible
    to each stream."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    eng.warmup()
    prompts = [np.arange(1 + 2 * i, dtype=np.int32)[:9] % 32
               for i in range(5)]
    lengths = [4, 7, 3, 6, 5]
    handles = [eng.submit(p, n) for p, n in zip(prompts, lengths)]
    eng.run(max_steps=100)
    for h, p, n in zip(handles, prompts, lengths):
        assert h.state == RequestState.FINISHED
        assert h.finish_reason == FinishReason.LENGTH
        assert h.tokens == _ref_greedy(model, variables, p, n)
    # 5 requests through 2 slots: reuse is structural, and occupancy
    # telemetry saw the pool actually multiplexed.
    snap = eng.metrics.snapshot()
    assert snap["requests_finished"] == 5
    assert snap["tokens_emitted"] == sum(lengths)
    assert snap["mean_slot_occupancy"] > 0.5


def test_llama_family_through_engine():
    """The GQA + RoPE family (per-row rotary positions, grouped cache)
    through the same engine, exact vs generate()."""
    model = tiny_llama(vocab_size=32, max_len=64)
    prompt = jnp.ones((1, 8), jnp.int32)
    variables = {"params": model.init(jax.random.key(1), prompt,
                                      train=False)["params"]}
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    prompts = [(np.arange(6) * 5 + i) % 32 for i in range(3)]
    handles = [eng.submit(p, 5) for p in prompts]
    eng.run(max_steps=100)
    for h, p in zip(handles, prompts):
        assert h.tokens == _ref_greedy(model, variables, p, 5)


def test_per_slot_sampling_isolation(gpt_setup):
    """Three requests in one tick with different sampling params. The
    discriminative pair: greedy and (temperature=1, top_k=1) must BOTH
    reproduce their solo greedy streams (top-1 sampling is argmax), so
    a hot-temperature neighbor in the same fused tick proves per-slot
    parameters don't leak across rows."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=3, prefill_len=16,
                      rng=jax.random.key(7))
    pa = (np.arange(5) * 3) % 32
    pb = (np.arange(7) * 2 + 1) % 32
    pc = (np.arange(4) + 11) % 32
    ha = eng.submit(pa, 6)  # greedy
    hb = eng.submit(pb, 6, sampling=SamplingParams(temperature=1.0, top_k=1))
    hc = eng.submit(pc, 6, sampling=SamplingParams(temperature=8.0))
    eng.run(max_steps=50)
    assert ha.tokens == _ref_greedy(model, variables, pa, 6)
    assert hb.tokens == _ref_greedy(model, variables, pb, 6)
    assert all(0 <= t < 32 for t in hc.tokens) and len(hc.tokens) == 6


def _oracle_filtered_row(row, t, k, p):
    """The filter rule in plain NumPy, one row: warp, top-k by the k-th
    sorted value (boundary ties kept), then the smallest set of the
    stable descending order whose mass reaches ``p`` (ties to the lower
    id). Returns the float32 filtered row and how far the CDF stays from
    ``p`` (a case whose CDF grazes ``p`` would test rounding, not the
    rule)."""
    x = row.astype(np.float32) / np.float32(t if t > 0 else 1.0)
    v = x.size
    order = np.argsort(-x, kind="stable")
    keep = np.ones(v, bool)
    if k > 0:
        keep &= x >= x[order[min(k, v) - 1]]
    margin = np.inf
    if p < 1.0:
        xs = np.where(keep[order], x[order], -np.inf).astype(np.float64)
        probs = np.exp(xs - xs.max())
        cdf = np.cumsum(probs / probs.sum())
        margin = np.abs(cdf - p).min()
        n_keep = 1 + int(np.sum(cdf[:-1] < p))
        nucleus = np.zeros(v, bool)
        nucleus[order[:n_keep]] = True
        keep &= nucleus
    return np.where(keep, x, -np.inf).astype(np.float32), margin


def _filter_case(name):
    """``(logits [B, V], temperature, top_k, top_p, dtype)`` of one named
    case; sentinels as the engine passes them (0 = no top-k, 2.0 = no
    nucleus)."""
    rng = np.random.default_rng(sum(map(ord, name)))
    f32 = jnp.float32

    def normal(b, v, scale=3.0):
        return (rng.normal(size=(b, v)) * scale).astype(np.float32)

    if name == "random_mixed":
        return (normal(4, 33), [1.0, 0.7, 2.0, 0.5], [5, 0, 1, 0],
                [0.9, 2.0, 2.0, 0.3], f32)
    if name == "ties_at_top_k_boundary":
        # Integer logits: the k-th value is shared by several tokens.
        return (np.round(normal(4, 96, 1.5)), [1.0, 0.7, 1.3, 1.0],
                [3, 10, 25, 60], [2.0, 2.0, 2.0, 2.0], f32)
    if name == "ties_at_nucleus_boundary":
        return (np.round(normal(4, 96, 1.5)), [1.0, 0.7, 1.3, 1.0],
                [0, 0, 0, 0], [0.9, 0.5, 0.7, 0.97], f32)
    if name == "ties_at_both_boundaries":
        return (np.round(normal(4, 200, 1.2)), [1.0, 0.8, 1.0, 1.5],
                [40, 25, 90, 12], [0.9, 0.8, 0.95, 0.6], f32)
    if name == "whole_row_tied":
        return (np.full((4, 33), 1.5, np.float32), [1.0, 0.7, 1.0, 2.0],
                [0, 5, 0, 40], [0.9, 2.0, 0.3, 0.7], f32)
    if name == "top_k_0_1_and_above_v":
        return (normal(4, 50), [1.0, 1.0, 0.7, 1.0], [0, 1, 51, 5000],
                [2.0, 2.0, 0.9, 2.0], f32)
    if name == "top_p_1_0p9_and_1e-6":
        x = np.round(normal(4, 50), 1)
        x[3, [7, 11]] = x[3].max() + 1.0      # 1e-6 keeps ONE of a tie
        return (x, [1.0, 1.0, 0.5, 1.0], [0, 0, 0, 0],
                [1.0, 0.9, 1e-6, 1e-6], f32)
    if name == "temperature_zero_rows":
        return (normal(4, 64), [0.0, 0.7, 0.0, -1.0], [0, 4, 3, 0],
                [2.0, 0.9, 0.8, 0.5], f32)
    if name == "rows_holding_neg_inf":
        # Grammar masks: most of the vocabulary disallowed; row 3 keeps
        # fewer legal tokens than its top_k.
        x = np.round(normal(4, 130), 1)
        x[0, rng.random(130) < 0.8] = -np.inf
        x[1, 5:] = -np.inf
        x[2, ::2] = -np.inf
        x[3, 3:] = -np.inf
        return (x, [1.0, 0.7, 1.0, 1.0], [0, 3, 20, 8],
                [0.9, 0.9, 2.0, 0.95], f32)
    if name == "vocab_not_a_multiple_of_128":
        return (np.round(normal(3, 1001), 1), [1.0, 0.7, 1.2],
                [0, 300, 129], [0.9, 0.95, 0.5], f32)
    if name == "bf16_input":
        # bf16 rounding makes ties of its own among 257 normal draws.
        return (normal(4, 257, 2.0), [1.0, 0.7, 0.0, 1.1], [0, 20, 0, 129],
                [0.9, 0.8, 2.0, 0.6], jnp.bfloat16)
    raise KeyError(name)


@pytest.mark.parametrize("case", [
    "random_mixed", "ties_at_top_k_boundary", "ties_at_nucleus_boundary",
    "ties_at_both_boundaries", "whole_row_tied", "top_k_0_1_and_above_v",
    "top_p_1_0p9_and_1e-6", "temperature_zero_rows", "rows_holding_neg_inf",
    "vocab_not_a_multiple_of_128", "bf16_input"])
def test_batched_filter_matches_static_per_row(case):
    """The per-slot sampler's filter pipeline against an independent
    NumPy oracle of the rule (sort, CDF, smallest set, ties to the lower
    id), and against the compiled single-request pipeline row by row —
    the engine's sampling is generate()'s, just batched. The kept set is
    exact; the kept values are the warped logits to float32 rounding."""
    x, t, k, p, dtype = _filter_case(case)
    logits = jnp.asarray(x).astype(dtype)
    x = np.asarray(logits.astype(jnp.float32))   # what the filter sees
    params = dict(temperature=jnp.array(t, jnp.float32),
                  top_k=jnp.array(k, jnp.int32),
                  top_p=jnp.array(p, jnp.float32))
    batched = np.asarray(jax.jit(batched_filtered_logits)(logits, **params))
    assert batched.dtype == np.float32 and batched.shape == x.shape
    for i, (ti, ki, pi) in enumerate(zip(t, k, p)):
        want, margin = _oracle_filtered_row(x[i], ti, ki, pi)
        assert margin > 1e-5, f"{case} row {i}: the CDF grazes top_p"
        msg = f"{case} row {i} (t={ti}, k={ki}, p={pi})"
        np.testing.assert_array_equal(np.isfinite(batched[i]),
                                      np.isfinite(want), err_msg=msg)
        np.testing.assert_allclose(batched[i], want, rtol=1e-6, err_msg=msg)
        if ti > 0:
            ref = filtered_logits(logits[i:i + 1], temperature=ti,
                                  top_k=min(ki, x.shape[1]) or None,
                                  top_p=pi if pi <= 1.0 else None)
            np.testing.assert_array_equal(batched[i:i + 1], np.asarray(ref),
                                          err_msg=msg)
    # Greedy rows take the argmax of the RAW logits, whatever the filter
    # made of their row.
    drawn = np.asarray(sample_logits_batched(jax.random.key(0), logits,
                                             **params))
    for i, ti in enumerate(t):
        if ti <= 0:
            assert drawn[i] == int(np.argmax(x[i]))
        else:
            assert np.isfinite(batched[i, drawn[i]])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seeded_draw_returns_the_parents_tokens(dtype):
    """``sample_logits_batched`` draws the SAME tokens for one fixed key
    as it did before the filter lost its gathers (PR 31): the expected
    values were recorded from the parent commit's sampler on this batch
    — ties, a tied row, a grammar-masked row, greedy rows, every filter
    on and off — so "same draw" is held, not only "same distribution"."""
    rng = np.random.default_rng(31)
    x = np.round(rng.normal(size=(12, 257)) * 2.0, 1).astype(np.float32)
    x[3] = 0.5
    x[4, rng.random(257) < 0.8] = -np.inf
    t = np.array([0.7, 1.0, 0.0, 1.0, 0.9, 2.0, 0.7, 0.0, 1.3, 1.0, 0.5,
                  1.0], np.float32)
    k = np.array([0, 5, 0, 40, 0, 1, 300, 0, 50, 2, 0, 7], np.int32)
    p = np.array([0.9, 2.0, 2.0, 0.5, 0.9, 2.0, 0.3, 2.0, 0.95, 1.0, 1e-6,
                  0.99], np.float32)
    got = sample_logits_batched(jax.random.key(31),
                                jnp.asarray(x).astype(dtype),
                                temperature=t, top_k=k, top_p=p)
    assert got.dtype == jnp.int32
    assert np.asarray(got).tolist() == [
        127, 99, 50, 65, 245, 21, 212, 89, 18, 195, 0, 191]


def test_cancellation_mid_decode_frees_the_slot(gpt_setup):
    """Cancel a running request: evicted at the next step with its
    tokens-so-far intact, and a queued request takes over the slot."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    long_h = eng.submit(np.arange(4) % 32, 40)
    queued_p = (np.arange(5) + 2) % 32
    queued_h = eng.submit(queued_p, 4)
    for _ in range(3):
        eng.step()
    assert long_h.state == RequestState.RUNNING
    assert queued_h.state == RequestState.QUEUED
    emitted_at_cancel = len(long_h.tokens)
    assert emitted_at_cancel >= 1
    long_h.cancel()
    eng.run(max_steps=50)
    assert long_h.state == RequestState.CANCELLED
    assert long_h.finish_reason == FinishReason.CANCELLED
    assert len(long_h.tokens) == emitted_at_cancel  # stream stopped
    assert queued_h.state == RequestState.FINISHED
    assert queued_h.tokens == _ref_greedy(model, variables, queued_p, 4)


def test_cancelling_a_queued_request_never_runs(gpt_setup):
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    running = eng.submit(np.arange(4) % 32, 3)
    queued = eng.submit(np.arange(5) % 32, 3)
    queued.cancel()
    eng.run(max_steps=50)
    assert running.state == RequestState.FINISHED
    assert queued.state == RequestState.CANCELLED
    assert queued.tokens == []


def test_deadline_timeout_evicts(gpt_setup):
    """An injectable clock drives the deadline: the request times out
    mid-decode, keeps its partial stream, and is counted."""
    model, variables = gpt_setup
    clock = _FakeClock()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      clock=clock)
    h = eng.submit(np.arange(4) % 32, 40, deadline_s=10.0)
    eng.step()
    assert h.state == RequestState.RUNNING
    partial = len(h.tokens)
    assert partial >= 1
    clock.now = 11.0  # past the deadline
    eng.step()
    assert h.state == RequestState.TIMED_OUT
    assert h.finish_reason == FinishReason.TIMED_OUT
    assert len(h.tokens) == partial
    snap = eng.metrics.snapshot()
    assert snap["requests_timed_out"] == 1
    assert snap["requests_finished"] == 0  # counters are disjoint


def test_deadline_expired_in_queue_never_pays_prefill(gpt_setup):
    """A request whose deadline passes while QUEUED is timed out at
    admission — no prefill dispatch, no post-deadline token, the slot
    goes to the next admissible request."""
    model, variables = gpt_setup
    clock = _FakeClock()
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      clock=clock)
    running = eng.submit(np.arange(4) % 32, 30)
    eng.step()  # admit `running` first: EDF would otherwise pop the
    #             deadlined request ahead of the deadline-less one
    doomed = eng.submit(np.arange(5) % 32, 4, deadline_s=5.0)
    fine = eng.submit((np.arange(6) + 1) % 32, 3)
    for _ in range(3):
        eng.step()
    clock.now = 6.0  # doomed expires in the queue; running keeps going
    running.cancel()
    eng.run(max_steps=100)
    assert doomed.state == RequestState.TIMED_OUT
    assert doomed.tokens == []  # never ran
    assert fine.state == RequestState.FINISHED
    assert fine.tokens == _ref_greedy(model, variables,
                                      (np.arange(6) + 1) % 32, 3)


def test_queue_full_sheds_load_typed(gpt_setup):
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      max_queue_depth=2)
    for _ in range(2):
        eng.submit(np.arange(4) % 32, 2)
    with pytest.raises(QueueFull) as exc:
        eng.submit(np.arange(4) % 32, 2)
    assert exc.value.queue_depth == 2
    assert exc.value.max_queue_depth == 2
    assert eng.metrics.snapshot()["requests_rejected"] == 1
    eng.run(max_steps=50)  # the accepted two still complete
    assert eng.metrics.snapshot()["requests_finished"] == 2


def test_zero_recompiles_after_warmup(gpt_setup, pin_zero_recompiles):
    """THE fixed-shape contract: one warmup, then a deliberately mixed
    workload — different prompt lengths, temperatures, top-k/top-p,
    request sizes, slot churn — and every resident program still has
    exactly ONE compiled executable (the `pin_zero_recompiles` fixture
    asserts the counts at warmup and again at teardown)."""
    model, variables = gpt_setup
    eng = pin_zero_recompiles(
        ServeEngine(model, variables, max_slots=2, prefill_len=16,
                    rng=jax.random.key(9)))
    mixed = [
        (np.arange(3) % 32, 2, SamplingParams()),
        (np.arange(9) % 32, 7, SamplingParams(temperature=0.8, top_k=4)),
        (np.arange(14) % 32, 1, SamplingParams(temperature=1.5, top_p=0.7)),
        (np.arange(5) % 32, 9,
         SamplingParams(temperature=0.3, top_k=2, top_p=0.95)),
        (np.arange(16) % 32, 3, SamplingParams()),
    ]
    handles = [eng.submit(p, n, sampling=s) for p, n, s in mixed]
    eng.run(max_steps=200)
    assert all(h.state == RequestState.FINISHED for h in handles)


def test_int8_serving_composes_through_engine(gpt_setup):
    """The generate() int8 hook through the engine: int8 params +
    param_transform reproduce the dequantized model's greedy streams
    exactly (same weights, same math — only the HBM representation and
    the jit boundary move)."""
    from pddl_tpu.ops.quant import dequantize, quantize_int8

    model, variables = gpt_setup
    qparams = quantize_int8(variables["params"], min_elems=128)
    dense = {"params": dequantize(qparams)}
    eng = ServeEngine(model, {"params": qparams}, max_slots=2,
                      prefill_len=16, param_transform=dequantize)
    prompts = [(np.arange(6) + i) % 32 for i in range(3)]
    handles = [eng.submit(p, 5) for p in prompts]
    eng.run(max_steps=50)
    for h, p in zip(handles, prompts):
        assert h.tokens == _ref_greedy(model, dense, p, 5)


def test_eos_finishes_early(gpt_setup):
    """Whatever greedy emits 2 tokens in, declaring that token eos must
    stop the stream right there with reason EOS (token included)."""
    model, variables = gpt_setup
    p = np.arange(6) % 32
    ref = _ref_greedy(model, variables, p, 3)
    eos = ref[1]
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                      eos_token=eos)
    h = eng.submit(p, 20)
    eng.run(max_steps=50)
    assert h.state == RequestState.FINISHED
    assert h.finish_reason == FinishReason.EOS
    # The stream stops at the FIRST occurrence of the eos token (which
    # may be earlier than index 1 if greedy repeats it), eos included.
    assert h.tokens == ref[:ref.index(eos) + 1]


def test_submit_validation(gpt_setup):
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=1, prefill_len=8,
                      prefix_block_size=4)
    with pytest.raises(ValueError, match="prefill_len"):
        eng.submit(np.zeros(9, np.int32), 4)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(np.zeros(8, np.int32), 64)
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.zeros(0, np.int32), 4)
    with pytest.raises(ValueError, match="top_k/top_p"):
        SamplingParams(top_k=4)


# ------------------------------------------------------------ one engine
_PAGED_SITES = {"sample_first", "chunk_prefill", "chunk_prefill_wide",
                "tick"}


def test_default_constructor_is_the_paged_engine(gpt_setup):
    """No option selects an engine any more: ``ServeEngine(model,
    variables)`` decodes from the block pool through block tables, and
    its program set holds none of the resident-row engines' sites."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, max_slots=2, prefill_len=16)
    eng.warmup()
    assert eng.paged
    assert set(eng.compile_counts()) == _PAGED_SITES
    assert not {"insert", "gather", "donate", "prefill"} \
        & (set(eng.compile_counts()) | set(FaultPlan.SITES))
    assert sorted(eng.program_lowerings()) == [
        "chunk_prefill", "chunk_prefill_wide", "tick"]
    # The tick takes the [S, T] block tables: the pool is the cache.
    assert eng._tables.shape == (2, 64 // 8)
    assert "paged" in eng.tick_lowering().as_text()
    assert eng.prefix_pool_nbytes > 0


def test_paged_keyword_accepts_only_true(gpt_setup):
    """``paged`` is what is left of the switch: ``True`` (what the
    benchmark's system modules pass, with every keyword they pass
    beside it) builds the same engine as leaving it out; ``False``
    names an engine that is gone and raises, never serves as something
    else."""
    model, variables = gpt_setup
    eng = ServeEngine(model, variables, paged=True, max_slots=2,
                      prefill_len=16, prefix_block_size=8,
                      prefix_cache_blocks=2 * 8 + 1, prefix_chunk=8,
                      prefill_slice_tokens=None, max_queue_depth=8,
                      aging_s=30.0, rng=jax.random.key(1),
                      telemetry_capacity=16, param_transform=None)
    eng.warmup()
    assert set(eng.compile_counts()) == _PAGED_SITES
    for bad in (False, None, 0):
        with pytest.raises(ValueError, match="removed in PR 34"):
            ServeEngine(model, variables, paged=bad)


def test_worker_refuses_paged_false_before_the_engine_builds(
        monkeypatch, capsys):
    """A fleet worker's config comes from outside the process:
    ``paged: false`` exits 2 with a typed message, like a bad role,
    before any engine is built."""
    from pddl_tpu.serve.fleet import worker

    def _never(config):
        raise AssertionError("the engine was built")

    monkeypatch.setattr(worker, "build_engine", _never)
    cfg = dict(vocab=32, max_len=64, embed_dim=32, depth=1, heads=2,
               slots=2, prefill_len=16, param_seed=0, paged=False)
    assert worker.main(["--config-json", json.dumps(cfg)]) == 2
    err = capsys.readouterr().err
    assert "paged=False" in err and "removed in PR 34" in err
    # `paged: true` and the key absent both pass the gate (and reach
    # the engine build, which this test has stubbed out).
    for ok in ({**cfg, "paged": True},
               {k: v for k, v in cfg.items() if k != "paged"}):
        with pytest.raises(AssertionError, match="engine was built"):
            worker.main(["--config-json", json.dumps(ok)])


def _engine_of_kind(kind, model, variables):
    kw = dict(max_slots=2, prefill_len=16)
    if kind == "narrow_only":       # chunk == prefill_len: no wide twin
        kw.update(prefix_chunk=16)
    elif kind == "spec_ngram":
        kw.update(spec_k=3)
    elif kind == "spec_draft_model":
        draft = tiny_gpt(vocab_size=32, max_len=64, depth=1)
        dvars = {"params": draft.init(
            jax.random.key(9), jnp.ones((1, 8), jnp.int32),
            train=False)["params"]}
        kw.update(spec_k=2, spec_draft_model=draft,
                  spec_draft_variables=dvars)
    elif kind in ("tenant", "tenant_spec"):
        reg = AdapterRegistry(model.embed_dim, model.vocab_size, rank=4)
        reg.register_random("acme", seed=100, scale=0.1)
        kw.update(tenant=TenantConfig(registry=reg))
        if kind == "tenant_spec":
            kw.update(spec_k=2)
    elif kind == "host_tier":
        kw.update(host_tier=1 << 20)
    elif kind == "sliced":
        kw.update(prefill_slice_tokens=4, prefix_chunk=4)
    else:
        assert kind == "plain"
    return ServeEngine(model, variables, **kw)


@pytest.mark.parametrize("kind", [
    "plain", "narrow_only", "spec_ngram", "spec_draft_model", "tenant",
    "tenant_spec", "host_tier", "sliced"])
def test_sites_counts_and_donation_map_agree(gpt_setup, kind):
    """One vocabulary, three readers: after ``warmup()`` every
    ``compile_counts()`` key is a ``FaultPlan`` site and stands at one
    executable, and every site the engine's donation map names is a
    program this engine compiled (a speculative engine has no ``tick``
    to lose, an n-gram one no ``draft_prefill``) that really does
    donate the pool."""
    model, variables = gpt_setup
    eng = _engine_of_kind(kind, model, variables)
    eng.warmup()
    counts = eng.compile_counts()
    assert set(counts) <= set(FaultPlan.SITES)
    assert all(v == 1 for v in counts.values()), counts
    assert set(eng._donated_by_site) <= set(counts)
    assert set(eng._donated_by_site.values()) == {"pool"}
    assert ("tick" in counts) != ("verify" in counts)
    assert ("chunk_prefill_wide" in counts) == (kind != "narrow_only")
    # Whatever donates nothing is not in the map; the rest all are.
    quiet = {"sample_first", "adapter_load"}
    if kind in ("spec_ngram", "tenant_spec"):
        quiet.add("draft")      # the n-gram drafter reads, never owns
    assert set(counts) - set(eng._donated_by_site) == quiet & set(counts)
    # Warmup left no trace: nothing live, nothing cached, tables scratch.
    _assert_pool_idle(eng, cached_blocks=0)


@pytest.mark.parametrize("ending", [
    "cancelled", "deadline", "eos", "length", "queue_full_shed",
    "preempted_and_resumed", "cancelled_mid_slice"])
def test_every_ending_hands_its_blocks_back(gpt_setup, ending):
    """However a stream ends, its slot's table row goes all-scratch,
    the private blocks it owned (its prompt's tail and everything it
    generated) are back on the free list, and only its prompt's full
    blocks stay, cached and unpinned."""
    model, variables = gpt_setup
    clock = _FakeClock()
    p = (np.arange(12) * 5 + 1) % 32      # 1 full block + 4 tokens
    ref = _ref_greedy(model, variables, p, 12)
    kw = dict(max_slots=1, prefill_len=16, clock=clock, max_queue_depth=1)
    if ending == "eos":
        kw.update(eos_token=ref[9])
    if ending == "cancelled_mid_slice":
        kw.update(prefill_slice_tokens=4, prefix_chunk=4)
    eng = ServeEngine(model, variables, **kw)
    eng.warmup()
    _assert_pool_idle(eng, cached_blocks=0)
    prio = (Priority.BEST_EFFORT if ending == "preempted_and_resumed"
            else Priority.INTERACTIVE)
    h = eng.submit(p, 12, priority=prio,
                   deadline_s=10.0 if ending == "deadline" else None)
    eng.step()
    if ending == "cancelled_mid_slice":
        # One slice of three is in: blocks allocated, no slot yet.
        assert not h.tokens and eng._slice is not None
        held = list(eng._slice["private"])
        assert held and not set(held) & set(eng._prefix._free)
        h.cancel()
        eng.step()
        assert h.finish_reason is FinishReason.CANCELLED
        assert set(held) <= set(eng._prefix._free)
        _assert_pool_idle(eng, cached_blocks=0)
        return
    for _ in range(5):
        eng.step()
    assert h.state == RequestState.RUNNING
    # Live: 18 positions written = 3 table entries, one of them the
    # donated (cached, pinned) prompt block, two private.
    held = list(eng._private[0])
    assert len(held) == 2 and (eng._tables[0] != 0).sum() == 3
    assert eng.block_table_fill == 3 / 8
    assert not set(held) & set(eng._prefix._free)
    if ending == "cancelled":
        h.cancel()
    elif ending == "deadline":
        clock.now = 11.0
    elif ending == "queue_full_shed":
        eng.submit((np.arange(5) + 2) % 32, 2)        # fills the queue
        with pytest.raises(QueueFull):
            eng.submit((np.arange(6) + 3) % 32, 2)    # shed: holds nothing
    elif ending == "preempted_and_resumed":
        ia = eng.submit((np.arange(7) * 3 + 2) % 32, 3)
        eng.step()  # parks h, admits the interactive request
        assert h.state == RequestState.QUEUED and h.preemptions == 1
        assert set(held) <= set(eng._prefix._free) | set(eng._private[0])
    eng.run(max_steps=200)
    want = {"cancelled": FinishReason.CANCELLED,
            "deadline": FinishReason.TIMED_OUT,
            "eos": FinishReason.EOS}.get(ending, FinishReason.LENGTH)
    assert h.finish_reason is want
    if want is FinishReason.LENGTH:
        assert h.tokens == ref          # resumed or undisturbed: exact
    elif ending == "eos":
        assert h.tokens == ref[:ref.index(ref[9]) + 1]
    else:
        assert h.tokens == ref[:len(h.tokens)] and len(h.tokens) < 12
    if ending == "preempted_and_resumed":
        assert ia.tokens == _ref_greedy(
            model, variables, (np.arange(7) * 3 + 2) % 32, 3)
    assert set(held) <= set(eng._prefix._free)
    # Only full prompt blocks stay cached: p's one (the shed and the
    # interactive prompts are shorter than a block).
    _assert_pool_idle(eng, cached_blocks=1)
