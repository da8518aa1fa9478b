"""Multi-tenant serving (`serve/tenant/` + ``ServeEngine(tenant=...)``).

The contracts under test (ISSUE 9 acceptance criteria):

- **Grammar machinery**: regex subset → Brzozowski-derivative DFA →
  token FSM — acceptance semantics, class/quantifier parsing, the
  token-level trim (a mask can never steer a stream into a state no
  token tiling can complete from), JSON-schema lowering.
- **Adapter machinery**: registry shape/rank validation, pool LRU
  eviction under pin protection, exhaustion escalation.
- **Correctness oracles**: an adapter-off slot is token-exact vs the
  base model; a single-tenant batched LoRA apply is token-exact vs an
  unbatched MERGED-WEIGHTS ``generate()`` reference; every constrained
  stream's output is accepted by its grammar/schema.
- **Zero recompiles over a mixed batch**: ≥3 distinct adapters +
  constrained + unconstrained + no-adapter slots in ONE tick, in both
  GPT and Llama, int8 composing.
- **Resilience parity**: 3-seed chaos matrix with tenant requests
  (token-exact survivors, zero recompiles), preemption resume,
  drain/restore v4 + v1-v3 back-compat ("no adapter, unconstrained"
  defaults in both engine modes), future versions refused, plain
  engines refusing tenant snapshots, fleet migration of tenant streams.
- **Observability**: adapter/constraint counters and labeled series
  through ``serve_exposition`` and the strict referee parser.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import ref_greedy as _ref_greedy
from pddl_tpu.models.gpt import tiny_gpt
from pddl_tpu.models.llama import tiny_llama
from pddl_tpu.obs.export import parse_prometheus_text, serve_exposition
from pddl_tpu.ops.lora import merge_lora_into_head
from pddl_tpu.serve import ServeEngine
from pddl_tpu.serve.faults import FaultPlan
from pddl_tpu.serve.request import Priority, RequestState
from pddl_tpu.serve.tenant import (
    AdapterPool,
    AdapterPoolExhausted,
    AdapterRegistry,
    TenantConfig,
    compile_constraint,
    encode_text,
    json_schema_to_regex,
    token_fsm_from_regex,
)
from pddl_tpu.serve.tenant.grammar import RegexError

pytestmark = pytest.mark.tenant

_no_sleep = lambda s: None  # noqa: E731

# Token-id → string vocabulary for the 32-token test models: ids 0-9
# are the digit characters, then JSON punctuation and a few letters —
# enough to tile the schemas below; the rest are unmatched filler.
VOCAB32 = (list("0123456789") + list('{}[]":,.-') + ["true", "false"]
           + list("abcdefghijk"))
assert len(VOCAB32) == 32


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


def _registry(model, names=("acme", "globex", "initech"), scale=0.1):
    reg = AdapterRegistry(model.embed_dim, model.vocab_size, rank=4)
    for i, name in enumerate(names):
        reg.register_random(name, seed=100 + i, scale=scale)
    return reg


def _tenant_engine(model, variables, reg=None, **kw):
    reg = reg if reg is not None else _registry(model)
    kw.setdefault("max_slots", 2)
    kw.setdefault("prefill_len", 16)
    tc = TenantConfig(registry=reg, token_strings=VOCAB32,
                      adapter_pool_slots=kw.pop("adapter_pool_slots",
                                                None))
    return ServeEngine(model, variables, tenant=tc, **kw)


def _merged(model, variables, reg, name):
    ad = reg.get(name)
    return {"params": merge_lora_into_head(variables["params"], ad.a,
                                           ad.b)}


# ----------------------------------------------------------- grammar
def test_regex_token_fsm_basics():
    vocab = list("abc")
    fsm = token_fsm_from_regex("(ab|a)c*", vocab)
    a, b, c = 0, 1, 2
    assert fsm.accepts([a])
    assert fsm.accepts([a, b])
    assert fsm.accepts([a, b, c, c])
    assert fsm.accepts([a, c])
    assert not fsm.accepts([b])
    assert not fsm.accepts([a, b, a])
    # Start-state mask: only 'a' can begin a match.
    row = fsm.allow_row(fsm.start)
    assert row[a] and not row[b] and not row[c]


def test_regex_classes_escapes_quantifiers():
    vocab = list("0123456789ab\"\\-x.")
    fsm = token_fsm_from_regex(r"-?\d+(\.\d+)?", vocab)
    enc = lambda s: encode_text(s, vocab)  # noqa: E731
    assert fsm.accepts(enc("42"))
    assert fsm.accepts(enc("-7.25"))
    assert not fsm.accepts(enc("4."))
    assert not fsm.accepts(enc("x"))
    neg = token_fsm_from_regex(r'"[^"\\]*"', vocab)
    assert neg.accepts(enc('"ab0"'))
    assert not neg.accepts(enc('"a"b"'))
    rng = token_fsm_from_regex("[a-b]+", vocab)
    assert rng.accepts(enc("abba")) and not rng.accepts(enc("0"))
    with pytest.raises(RegexError):
        token_fsm_from_regex("*a", vocab)
    with pytest.raises(RegexError):
        token_fsm_from_regex("(a", vocab)


def test_multichar_tokens_and_token_level_trim():
    """Token lift handles multi-character tokens, and the TOKEN-level
    trim erases transitions into states no token tiling can complete —
    so a dead-end (grammar-complete) state is always ACCEPTING, the
    structural half of the "constrained output always validates"
    contract."""
    fsm = token_fsm_from_regex("abc+", ["ab", "c", "abc"])
    assert fsm.accepts([0, 1]) and fsm.accepts([2]) and fsm.accepts([2, 1])
    assert not fsm.accepts([1])
    # 'abx' needs an 'x' no token supplies: the trap branch is erased
    # from the masks, only 'ac' survives.
    vocab = list("abc")
    fsm2 = token_fsm_from_regex("(abx|ac)", vocab)
    s = fsm2.advance(fsm2.start, 0)
    assert not fsm2.allow_row(s)[1]  # 'b' would enter the dead branch
    assert fsm2.allow_row(s)[2]
    with pytest.raises(RegexError, match="tile"):
        token_fsm_from_regex("[ab]x[ab]", vocab)


def test_json_schema_lowering():
    # Property names drawn from the test vocabulary's letters (a-k):
    # the token-level trim LOUDLY rejects schemas the vocabulary
    # cannot tile (pinned at the end), so the happy path must tile.
    schema = {"type": "object", "properties": {
        "id": {"type": "integer"},
        "ab": {"type": "string"},
        "ed": {"type": "boolean"},
    }}
    pattern = json_schema_to_regex(schema)
    vocab = VOCAB32
    fsm = token_fsm_from_regex(pattern, vocab)
    enc = lambda s: encode_text(s, vocab)  # noqa: E731
    assert fsm.accepts(enc('{"id":42,"ab":"cig","ed":true}'))
    assert fsm.accepts(enc('{"id":-7,"ab":"","ed":false}'))
    # Property order is canonical (declared order), all required.
    assert not fsm.accepts(enc('{"ab":"cig","id":42,"ed":true}'))
    assert not fsm.accepts(enc('{"id":42,"ab":"cig"}'))
    # A schema the vocabulary cannot spell is refused loudly.
    with pytest.raises(RegexError, match="tile"):
        token_fsm_from_regex(json_schema_to_regex(
            {"type": "object",
             "properties": {"zz": {"type": "integer"}}}), vocab)
    arr = json_schema_to_regex({"type": "array",
                                "items": {"type": "integer"}})
    afsm = token_fsm_from_regex(arr, vocab)
    assert afsm.accepts(enc("[1,2,30]")) and afsm.accepts(enc("[]"))
    assert not afsm.accepts(enc("[1,]"))
    efsm = token_fsm_from_regex(
        json_schema_to_regex({"enum": ["ab", 7]}), vocab)
    assert efsm.accepts(enc('"ab"')) and efsm.accepts(enc("7"))
    with pytest.raises(ValueError, match="unsupported"):
        json_schema_to_regex({"type": "null"})
    with pytest.raises(ValueError):
        compile_constraint({"kind": "wat"}, vocab)
    with pytest.raises(ValueError):
        compile_constraint({"kind": "regex", "pattern": ""}, vocab)


# ----------------------------------------------------------- adapters
def test_registry_validation_and_rank_padding(gpt_setup):
    model, variables = gpt_setup
    reg = AdapterRegistry(model.embed_dim, model.vocab_size, rank=4)
    with pytest.raises(ValueError, match="must be"):
        reg.register("bad", np.zeros((7, 2)), np.zeros((2, 32)))
    with pytest.raises(ValueError, match="exceeds"):
        reg.register("big", np.zeros((model.embed_dim, 8)),
                     np.zeros((8, 32)))
    # A rank-2 adapter zero-pads to the pool rank — mathematically a
    # no-op: the padded merged head equals the unpadded one.
    rng = np.random.RandomState(0)
    a = rng.randn(model.embed_dim, 2).astype(np.float32)
    b = rng.randn(2, 32).astype(np.float32)
    ad = reg.register("small", a, b, scale=0.5)
    assert ad.a.shape == (model.embed_dim, 4)
    np.testing.assert_allclose(ad.a @ ad.b, 0.5 * (a @ b), rtol=1e-6)


def test_adapter_pool_lru_pins_and_exhaustion():
    pool = AdapterPool(3)  # identity + 2 usable rows
    r1 = pool.assign("a1")
    r2 = pool.assign("a2")
    assert {r1, r2} == {1, 2} and pool.resident == 2
    pool.pin(r1)
    # Full pool, a1 pinned: a3 must evict a2 (the only unpinned row).
    r3 = pool.assign("a3")
    assert r3 == r2 and pool.lookup("a2") is None
    assert pool.evictions == 1
    pool.pin(r3)
    with pytest.raises(AdapterPoolExhausted):
        pool.assign("a4")
    pool.unpin(r3)
    assert pool.assign("a4") == r3
    with pytest.raises(RuntimeError, match="underflow"):
        pool.unpin(r1) or pool.unpin(r1)
    # Identity row is never assignable/pinnable state.
    pool.pin(0), pool.unpin(0)  # no-ops
    with pytest.raises(ValueError, match="rows"):
        AdapterPool(1)


# ------------------------------------------------- correctness oracles
def test_mixed_batch_token_exact_zero_recompiles_gpt(
        gpt_setup, pin_zero_recompiles):
    """THE acceptance pin: one engine, ≥3 distinct adapters +
    constrained + unconstrained + no-adapter slots mixed through the
    same fused ticks — every stream token-exact against its own oracle
    (base model / merged weights / grammar referee), zero recompiles,
    both engine modes."""
    model, variables = gpt_setup
    reg = _registry(model)
    eng = pin_zero_recompiles(_tenant_engine(
        model, variables, reg=reg, max_slots=6))
    base = (np.arange(12) * 5 + 1) % 32
    spec = {"kind": "regex", "pattern": "[0-9][0-9][0-9][0-9]"}
    hs = {
        "plain": eng.submit(base, 6),
        "acme": eng.submit(base, 6, adapter="acme"),
        "globex": eng.submit((base + 3) % 32, 6, adapter="globex"),
        "initech": eng.submit((base + 7) % 32, 6, adapter="initech"),
        "constrained": eng.submit(base, 8, constraint=spec),
        "both": eng.submit(base, 8, adapter="acme", constraint=spec),
    }
    eng.step()
    # Not vacuous: all six flavors really do share ONE fused tick.
    assert eng.live_slots == 6
    eng.run(max_steps=400)
    assert hs["plain"].tokens == _ref_greedy(model, variables, base, 6)
    for name, prompt in (("acme", base), ("globex", (base + 3) % 32),
                         ("initech", (base + 7) % 32)):
        merged = _merged(model, variables, reg, name)
        assert hs[name].tokens == _ref_greedy(model, merged, prompt, 6), \
            f"adapter {name} diverged from the merged-weights reference"
    fsm = compile_constraint(spec, VOCAB32)
    for key in ("constrained", "both"):
        h = hs[key]
        assert h.finish_reason.value == "grammar"
        assert fsm.accepts(h.tokens), f"{key} output escaped its grammar"
    assert eng.metrics.adapter_loads == 3
    assert eng.metrics.constrained_requests == 2
    assert eng.metrics.requests_grammar_complete == 2


def test_mixed_batch_token_exact_llama(llama_setup, pin_zero_recompiles):
    """GQA + RoPE + bias-free head: the external-head tenant programs
    are token-exact on the Llama family too, both modes."""
    model, variables = llama_setup
    reg = _registry(model)
    eng = pin_zero_recompiles(_tenant_engine(
        model, variables, reg=reg, max_slots=3))
    base = (np.arange(11) * 3 + 2) % 32
    spec = {"kind": "regex", "pattern": "[0-9][0-9][0-9]"}
    h0 = eng.submit(base, 5)
    h1 = eng.submit(base, 5, adapter="acme")
    h2 = eng.submit(base, 6, adapter="globex", constraint=spec)
    eng.run(max_steps=300)
    assert h0.tokens == _ref_greedy(model, variables, base, 5)
    assert h1.tokens == _ref_greedy(
        model, _merged(model, variables, reg, "acme"), base, 5)
    assert compile_constraint(spec, VOCAB32).accepts(h2.tokens)


def test_int8_composes_with_adapters(gpt_setup):
    """int8 param_transform: dequant runs inside the tenant programs
    BEFORE the external head + LoRA delta, so the adapted stream
    matches a merged-weights reference over the dequantized params."""
    from pddl_tpu.ops.quant import dequantize, quantize_int8

    model, variables = gpt_setup
    qparams = quantize_int8(variables["params"], min_elems=128)
    dense = {"params": dequantize(qparams)}
    reg = _registry(model)
    eng = _tenant_engine(model, {"params": qparams}, reg=reg,
                         param_transform=dequantize)
    base = (np.arange(12) * 5 + 1) % 32
    h0 = eng.submit(base, 5)
    h1 = eng.submit(base, 5, adapter="acme")
    eng.run(max_steps=200)
    assert h0.tokens == _ref_greedy(model, dense, base, 5)
    assert h1.tokens == _ref_greedy(
        model, _merged(model, dense, reg, "acme"), base, 5)


def test_json_schema_constrained_stream_validates(gpt_setup):
    """A schema-constrained stream emits a parseable JSON document
    matching the schema — checked by json.loads, not just the FSM."""
    model, variables = gpt_setup
    eng = _tenant_engine(model, variables)
    schema = {"type": "object", "properties": {"id": {"type": "integer"}}}
    spec = {"kind": "json_schema", "schema": schema}
    base = (np.arange(10) * 7 + 3) % 32
    h = eng.submit(base, 20, constraint=spec)
    eng.run(max_steps=400)
    assert h.finish_reason.value == "grammar"
    text = "".join(VOCAB32[t] for t in h.tokens)
    doc = json.loads(text)
    assert isinstance(doc["id"], int)


def test_adapter_pool_churn_evicts_and_stays_exact(gpt_setup):
    """More adapters than pool rows: sequential single-slot traffic
    LRU-evicts cold factors and reloads on return — every stream still
    merged-exact, hit/load/eviction counters live."""
    model, variables = gpt_setup
    names = ["t0", "t1", "t2", "t3"]
    reg = AdapterRegistry(model.embed_dim, model.vocab_size, rank=4)
    for i, n in enumerate(names):
        reg.register_random(n, seed=40 + i, scale=0.1)
    eng = _tenant_engine(model, variables, reg=reg, max_slots=1,
                         adapter_pool_slots=3)  # identity + 2 rows
    base = (np.arange(10) * 3 + 1) % 32
    for name in names + [names[0]]:  # t0 returns after eviction
        h = eng.submit(base, 4, adapter=name)
        eng.run(max_steps=100)
        assert h.tokens == _ref_greedy(
            model, _merged(model, variables, reg, name), base, 4), name
    assert eng.metrics.adapter_evictions >= 3
    assert eng.metrics.adapter_loads == 5  # 4 cold + t0's reload
    snap = eng.metrics.snapshot()
    assert snap["requests_by_adapter"]["t0"] == 2


def test_cold_adapter_load_charges_the_budget(gpt_setup):
    """Tenancy-aware admission budget: a COLD adapter charges
    ``adapter_load_tokens`` on top of the (suffix-priced) prompt; a
    RESIDENT one charges nothing extra — the cached-prefix economics
    applied to weights."""
    model, variables = gpt_setup
    reg = _registry(model)
    eng = _tenant_engine(model, variables, reg=reg,
                         prefill_token_budget=64)
    base = (np.arange(12) * 5 + 1) % 32
    h = eng.submit(base, 3, adapter="acme")
    cold = eng._prefill_cost(h)
    plain = eng._prefill_cost(eng.submit(base, 3))
    assert cold == plain + eng._tenant.adapter_load_tokens
    eng.run(max_steps=100)  # acme now resident
    h2 = eng.submit(base, 3, adapter="acme")
    assert eng._prefill_cost(h2) <= plain  # warm adapter + warm prefix
    eng.run(max_steps=100)
    assert eng.metrics.adapter_hits >= 1


def test_submit_validation(gpt_setup):
    model, variables = gpt_setup
    plain = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    with pytest.raises(ValueError, match="tenant"):
        plain.submit([1, 2, 3], 2, adapter="acme")
    with pytest.raises(ValueError, match="tenant"):
        plain.submit([1, 2, 3], 2,
                     constraint={"kind": "regex", "pattern": "a"})
    eng = _tenant_engine(model, variables)
    with pytest.raises(ValueError, match="not registered"):
        eng.submit([1, 2, 3], 2, adapter="nobody")
    with pytest.raises(ValueError, match="kind"):
        eng.submit([1, 2, 3], 2, constraint={"kind": "wat"})
    # Constraints need a grammar vocabulary.
    bare = ServeEngine(model, variables, max_slots=1, prefill_len=16,
                       tenant=TenantConfig(registry=_registry(model)))
    with pytest.raises(ValueError, match="token_strings"):
        bare.submit([1, 2, 3], 2,
                    constraint={"kind": "regex", "pattern": "[0-9]"})
    # Pool floor validation.
    with pytest.raises(ValueError, match="floor"):
        ServeEngine(model, variables, max_slots=4, prefill_len=16,
                    tenant=TenantConfig(registry=_registry(model),
                                        adapter_pool_slots=3))
    # An empty-language constraint over this vocabulary ("x*" with no
    # 'x' token: start state allows no token, no eos to escape) must
    # reject the REQUEST at submit — on the unfixed engine it sampled
    # an all--inf row and the FSM advance crashed the step for every
    # live stream.
    with pytest.raises(ValueError, match="no first token"):
        eng.submit([1, 2, 3], 2,
                   constraint={"kind": "regex", "pattern": "x*"})


def test_preempted_tenant_stream_resumes_exact(gpt_setup):
    """A preempted best_effort ADAPTED + CONSTRAINED stream resumes
    token-exactly through replay admission: the adapter re-acquires
    (pin released at park) and the FSM state re-derives from the
    emitted tokens."""
    model, variables = gpt_setup
    reg = _registry(model)
    eng = _tenant_engine(model, variables, reg=reg, max_slots=1)
    spec = {"kind": "regex", "pattern": "[0-9]" * 10}
    pb = (np.arange(8) * 5 + 4) % 32
    hbe = eng.submit(pb, 10, priority=Priority.BEST_EFFORT,
                     adapter="acme", constraint=spec)
    for _ in range(3):
        eng.step()
    pi = (np.arange(8) * 11 + 6) % 32
    hint = eng.submit(pi, 4, priority=Priority.INTERACTIVE)
    eng.run(max_steps=400)
    assert eng.metrics.preemptions >= 1
    assert hint.tokens == _ref_greedy(model, variables, pi, 4)
    assert hbe.done
    fsm = compile_constraint(spec, VOCAB32)
    assert fsm.accepts(hbe.tokens) or len(hbe.tokens) == 10


def test_install_fault_after_single_step_slice_releases_pin_once(
        gpt_setup):
    """A sliced admission that COMPLETES within its first step and then
    faults at install (sample_first): the install's failure path owns
    the adapter-pin release — the slice machinery must not release it
    a second time (refcount underflow crashed the step on the unfixed
    engine). The request replays and finishes merged-exact with every
    pin balanced."""
    from pddl_tpu.serve.faults import FaultKind

    model, variables = gpt_setup
    reg = _registry(model)
    eng = _tenant_engine(model, variables, reg=reg, max_slots=1,
                         prefill_slice_tokens=16, prefix_chunk=4,
                         fault_plan=FaultPlan(sleep_fn=_no_sleep),
                         backoff_sleep=_no_sleep, max_retries=0)
    p = (np.arange(8) * 5 + 1) % 32
    h = eng.submit(p, 4, adapter="acme")
    eng._faults._sched[(eng._step_idx, "sample_first")] = \
        [FaultKind.TRANSIENT]
    eng.run(max_steps=100)
    assert h.state == RequestState.FINISHED
    assert h.tokens == _ref_greedy(
        model, _merged(model, variables, reg, "acme"), p, 4)
    assert eng.metrics.replays >= 1
    assert eng._apool.pinned_rows() == []  # every pin balanced


# ----------------------------------------------------------- resilience
@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tenant_chaos_matrix(gpt_setup, pin_zero_recompiles, seed):
    """The mixed chaos profile with tenant requests:
    every request terminal, finished streams token-exact against their
    own oracles (merged weights / grammar referee), zero recompiles
    across retry / replay / degraded / pool-rebuild transitions — the
    adapter pins and FSM states unwind exactly through every recovery
    path."""
    model, variables = gpt_setup
    reg = _registry(model)
    plan = FaultPlan(seed=seed, sleep_fn=_no_sleep, transient_rate=0.05,
                     oom_rate=0.02, latency_rate=0.1, latency_s=1e-4,
                     max_random_injections=20)
    eng = pin_zero_recompiles(_tenant_engine(
        model, variables, reg=reg, max_slots=2,
        fault_plan=plan, backoff_sleep=_no_sleep))
    spec = {"kind": "regex", "pattern": "[0-9][0-9][0-9][0-9]"}
    fsm = compile_constraint(spec, VOCAB32)
    jobs = []
    for i in range(6):
        p = (np.arange(10) * 3 + i * 7 + 1) % 32
        adapter = [None, "acme", "globex"][i % 3]
        constraint = spec if i % 2 else None
        jobs.append((p, adapter, constraint,
                     eng.submit(p, 5, adapter=adapter,
                                constraint=constraint)))
    eng.run(max_steps=800)
    assert not eng.has_work, "engine failed to drain under chaos"
    for p, adapter, constraint, h in jobs:
        assert h.done, f"request {h} never reached a terminal state"
        if h.state != RequestState.FINISHED:
            continue
        if constraint is not None:
            assert fsm.accepts(h.tokens) or len(h.tokens) == 5
        elif adapter is None:
            assert h.tokens == _ref_greedy(model, variables, p, 5)
        else:
            assert h.tokens == _ref_greedy(
                model, _merged(model, variables, reg, adapter), p, 5)


def test_drain_restore_v4_round_trip(gpt_setup):
    """v4 snapshot carries adapter + constraint; restore into a fresh
    tenant engine (same registry config) resumes adapted streams on the
    right weights and constrained streams under the same automaton,
    token-exactly."""
    model, variables = gpt_setup
    reg = _registry(model)
    spec = {"kind": "regex", "pattern": "[0-9]" * 8}
    eng1 = _tenant_engine(model, variables, reg=reg, max_slots=2)
    p1 = (np.arange(11) * 5 + 2) % 32
    p2 = (np.arange(9) * 7 + 3) % 32
    eng1.submit(p1, 8, adapter="acme")
    eng1.submit(p2, 8, constraint=spec)
    for _ in range(3):
        eng1.step()
    snap = eng1.drain()
    assert snap["version"] == 5
    entries = {len(e["prompt"]): e for e in snap["requests"]}
    assert entries[11]["adapter"] == "acme"
    assert entries[9]["constraint"] == spec

    eng2 = _tenant_engine(model, variables, reg=reg, max_slots=2)
    rh = eng2.restore(snap)
    eng2.run(max_steps=400)
    assert rh[0].tokens == _ref_greedy(
        model, _merged(model, variables, reg, "acme"), p1, 8)
    fsm = compile_constraint(spec, VOCAB32)
    assert fsm.accepts(rh[1].tokens) or len(rh[1].tokens) == 8


def test_old_snapshots_restore_with_tenant_defaults(gpt_setup, tmp_path):
    """The back-compat pin: v1/v2/v3 snapshots — no adapter/constraint
    keys anywhere — restore into a tenant-capable engine
    with "no adapter, unconstrained" defaults, token-exactly; future
    versions still refuse."""
    import pddl_tpu.serve.drain as drain_io

    model, variables = gpt_setup
    p, n = ((np.arange(9) * 5 + 1) % 32).tolist(), 6
    ref = _ref_greedy(model, variables, p, n)
    for version in (1, 2, 3):
        entry = {
            "prompt": p, "max_new_tokens": n,
            "sampling": {"temperature": 0.0, "top_k": None,
                         "top_p": None},
            "deadline_s": None, "elapsed_s": 1.5,
            "tokens": ref[:2],  # mid-stream: exercises replay
            "ttft_s": 0.1,
        }
        if version >= 2:
            entry["priority"] = "interactive"
        snap = {"version": version, "drained_unix_s": 0.0,
                "requests": [entry]}
        if version >= 3:
            snap["paged"] = False
        path = tmp_path / f"v{version}.json"
        path.write_text(json.dumps(snap))
        eng = _tenant_engine(model, variables, max_slots=1)
        (restored,) = eng.restore(str(path))
        assert restored.request.adapter is None
        assert restored.request.constraint is None
        eng.run(max_steps=200)
        assert restored.tokens == ref, version
    bad = tmp_path / "v99.json"
    bad.write_text(json.dumps({"version": 99, "requests": []}))
    with pytest.raises(ValueError, match="version"):
        drain_io.load_snapshot(str(bad))


def test_plain_engine_refuses_tenant_snapshot(gpt_setup):
    """A tenant stream restored onto a plain engine would silently
    serve the BASE model — the restore refuses loudly instead."""
    model, variables = gpt_setup
    eng1 = _tenant_engine(model, variables, max_slots=1)
    eng1.submit((np.arange(8) * 3 + 1) % 32, 6, adapter="acme")
    eng1.step()
    snap = eng1.drain()
    plain = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    with pytest.raises(ValueError, match="tenant"):
        plain.restore(snap)


@pytest.mark.fleet
@pytest.mark.chaos
def test_fleet_migrates_tenant_streams_token_exact(gpt_setup):
    """Fleet leg of the chaos matrix: a killed replica's ADAPTED +
    CONSTRAINED streams migrate to survivors and finish token-exactly
    (worker-config parity: every replica builds the same registry), and
    adapter-affinity routing re-homes after the death."""
    from conftest import FakeClock
    from pddl_tpu.serve.fleet.replica import LocalReplica
    from pddl_tpu.serve.fleet.router import FleetRouter
    from pddl_tpu.utils.faults import KillPoint

    model, variables = gpt_setup
    spec = {"kind": "regex", "pattern": "[0-9]" * 8}

    def factory():
        return _tenant_engine(model, variables, reg=_registry(model),
                              max_slots=2)

    clock = FakeClock()
    fleet = FleetRouter([LocalReplica(i, factory) for i in range(2)],
                        respawn=False, clock=clock)
    fleet.warmup()
    p1 = (np.arange(10) * 3 + 1) % 32
    p2 = (np.arange(10) * 7 + 2) % 32
    h1 = fleet.submit(p1, 8, adapter="acme")
    h2 = fleet.submit(p2, 8, constraint=spec)
    for _ in range(3):
        fleet.step()
    # Kill whichever replica holds h1 (mid-stream), hard.
    victim = next(s for s in fleet.replicas
                  if s.replica_id == h1.replica_id)
    original_step = victim.driver.engine.step
    victim.driver.engine.step = lambda: (_ for _ in ()).throw(
        KillPoint("chaos"))
    del original_step
    while fleet.has_work:
        fleet.step()
        clock.now += 0.05
    reg = _registry(model)
    assert h1.done and h2.done
    assert h1.tokens == _ref_greedy(
        model, _merged(model, variables, reg, "acme"), p1, 8)
    fsm = compile_constraint(spec, VOCAB32)
    assert fsm.accepts(h2.tokens) or len(h2.tokens) == 8
    assert fleet.metrics.requests_migrated >= 1
    # Affinity re-homes: the next acme submission lands on a survivor.
    h3 = fleet.submit(p1, 3, adapter="acme")
    assert h3.replica_id != victim.replica_id
    while fleet.has_work:
        fleet.step()
        clock.now += 0.05
    assert h3.tokens == _ref_greedy(
        model, _merged(model, variables, reg, "acme"), p1, 3)


@pytest.mark.fleet
def test_adapter_affinity_yields_to_interactive_load(gpt_setup):
    """The interactive pressure escape applies to ADAPTER affinity like
    prefix affinity: a popular adapter must not funnel interactive
    traffic onto its loaded home replica while a sibling idles (the
    unfixed router returned the home before the load check). The same
    pressure keeps BATCH traffic on the warm home."""
    from pddl_tpu.serve.fleet.replica import LocalReplica
    from pddl_tpu.serve.fleet.router import FleetRouter

    model, variables = gpt_setup

    def factory():
        return _tenant_engine(model, variables, reg=_registry(model),
                              max_slots=4, max_queue_depth=32)

    fleet = FleetRouter([LocalReplica(i, factory) for i in range(2)],
                        interactive_reroute_load=2)
    fleet.warmup()
    p = (np.arange(10) * 3 + 1) % 32
    h0 = fleet.submit(p, 32, adapter="acme")
    home = h0.replica_id
    # Load the home past the threshold (these stay assigned — long
    # streams, no stepping yet).
    fleet.submit((p + 1) % 32, 32, adapter="acme")
    assert fleet.submit((p + 2) % 32, 32, adapter="acme",
                        priority=Priority.BATCH).replica_id == home
    h_int = fleet.submit((p + 3) % 32, 32, adapter="acme",
                         priority=Priority.INTERACTIVE)
    assert h_int.replica_id != home
    assert fleet.metrics.routed_load_balanced >= 1
    while fleet.has_work:
        fleet.step()
    fleet.close()


# -------------------------------------------------------- observability
def test_tenant_metrics_reach_the_exposition(gpt_setup):
    """Adapter/constraint counters, the per-adapter labeled series and
    the engine tenant gauges flow through serve_exposition and the
    strict referee parser."""
    model, variables = gpt_setup
    eng = _tenant_engine(model, variables)
    base = (np.arange(10) * 5 + 1) % 32
    eng.submit(base, 4, adapter="acme")
    eng.submit(base, 4, adapter="acme")
    eng.submit(base, 5,
               constraint={"kind": "regex", "pattern": "[0-9][0-9]"})
    eng.run(max_steps=200)
    text = serve_exposition(eng.metrics, eng)
    samples, types = parse_prometheus_text(text)
    flat = {name: v for (name, labels), v in samples.items() if not labels}
    assert flat["pddl_serve_adapter_loads_total"] == 1
    assert flat["pddl_serve_adapter_hits_total"] == 1
    assert types["pddl_serve_adapter_loads_total"] == "counter"
    assert flat["pddl_serve_adapter_hit_rate"] == 0.5
    assert flat["pddl_serve_constrained_requests_total"] == 1
    assert flat["pddl_serve_requests_grammar_complete_total"] == 1
    assert flat["pddl_serve_engine_tenant"] == 1
    assert flat["pddl_serve_engine_adapter_pool_resident"] == 1
    labeled = {(n, dict(l).get("key")): v for (n, l), v in samples.items()
               if l}
    assert labeled[("pddl_serve_requests_by_adapter", "acme")] == 2
    # The empty-label placeholder convention on a PLAIN engine: the
    # open series still exports (NaN under key="").
    plain = ServeEngine(model, variables, max_slots=1, prefill_len=16)
    s2, _ = parse_prometheus_text(serve_exposition(plain.metrics, plain))
    assert ("pddl_serve_requests_by_adapter", (("key", ""),)) in s2
