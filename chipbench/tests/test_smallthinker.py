"""The SmallThinker configuration's yardstick, held to its own rules at a
size a CPU test can hold (4 layers of 40, 7/1 heads of 8, window 8, 8
experts top-2, float32): the reference against a second, slower
formulation; ``correct`` false for the fp8 control in the program's place
and for the timed path broken underneath
(``tools/faults_smallthinker.py``); the new readers on a synthetic ``obs``.

The chip's own readings, at the cell's size, are in PERF.md; the limits
here are this size's (stated below), not the chip's.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.reference import smallthinker as reference
from chipbench.tools import faults_smallthinker
from chipbench.weights_smallthinker import make_weights

TINY = {"num_hidden_layers": 4, "hidden_size": 40, "num_attention_heads": 7,
        "num_key_value_heads": 1, "head_dim": 8, "moe_ffn_hidden_size": 16,
        "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2,
        "sliding_window_size": 8, "vocab_size": 512,
        "max_position_embeddings": 256, "precision": "float32",
        "initializer_range": 0.08,
        "engine": {"max_slots": 3, "block_size": 4, "pool_blocks": 193,
                   "prefill_len": 128, "prefill_chunk": 32,
                   "prefill_slice_tokens": None, "max_queue_depth": 64}}
# Readings at this size on the CPU (weights N(0, 0.08), or the 40-wide
# model is all but linear): the program, in float32, reads greedy_gap_mean
# and _max 0 and no expert set apart (seeds 1, 2, 2**31+5); the fp8
# control 0.0027 / 0.060 and 0.0035 / 0.079 (seeds 2 and 2**31+5; on seed
# 1 its 36 tokens all agree); the window dropped 0.26-0.44 / 1.3-1.5 and
# a fifth of the expert sets apart; top-2 cut to top-1 0.018-0.040 /
# 0.15-0.30 and an eighth apart (seeds 3, 4).
SMALL = {"gap_mean_limit": 5e-4, "gap_max_limit": 0.01,
         "nucleus_excess_limit": 0.03, "expert_mismatch_limit": 0.02}


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    monkeypatch.setattr(reference, "Q_BLOCK", 16)
    monkeypatch.setattr(reference, "PAD_TO", 32)


def small(cfg, spec):
    cfg.update(TINY)
    cfg["check"] = dict(cfg["check"], requests=4, sampled_requests=2,
                        max_rows=16, min_tokens=8, **SMALL)
    spec["prompt_len"] = {"median": 40, "sigma": 0.6, "min": 10, "max": 120}
    spec["output_len"] = {"median": 6, "sigma": 0.5, "min": 3, "max": 12}
    spec["rate_per_s"] = 3.0
    spec["ramp_live"] = 1


def cell(seed, **kw):
    return run.run_cell("st21b_longdoc_steady", seed, 4.0, False,
                        allow_cpu=True, overrides=small, **kw)


# ------------------------------------------- the reference against itself
def slow_forward(params, cfg, tokens):
    """A second formulation, a token at a time in numpy float64: for
    token t, its own query against keys 0..t one by one, and its experts
    one by one."""
    s = reference.shape_of(cfg)
    f = lambda a: np.asarray(a, np.float64)
    rms = lambda x, w: x / np.sqrt(np.mean(x * x) + s["eps"]) * f(w)
    d, rep = s["head_dim"], s["heads"] // s["kv_heads"]
    inv = 1.0 / s["theta"] ** (np.arange(0, d, 2) / d)

    def rope(v, t):
        a, b = v[..., :d // 2], v[..., d // 2:]
        c, sn = np.cos(t * inv), np.sin(t * inv)
        return np.concatenate([a * c - b * sn, b * c + a * sn], -1)

    x = f(params["embed"]["embedding"])[np.asarray(tokens)]
    for i in range(s["layers"]):
        p = params[f"block{i}"]
        h = np.stack([rms(row, p["ln1"]["scale"]) for row in x])
        r = h @ f(p["router"]["kernel"])
        q = np.einsum("se,ehd->shd", h, f(p["attn"]["query"]["kernel"]))
        k = np.einsum("se,ehd->shd", h, f(p["attn"]["key"]["kernel"]))
        v = np.einsum("se,ehd->shd", h, f(p["attn"]["value"]["kernel"]))
        if s["rope_layout"][i]:
            q = np.stack([rope(q[t], t) for t in range(len(x))])
            k = np.stack([rope(k[t], t) for t in range(len(x))])
        out = np.zeros((len(x), s["heads"], d))
        for t in range(len(x)):
            lo = max(0, t - s["window"] + 1) if s["window_layout"][i] else 0
            for head in range(s["heads"]):
                g = head // rep
                sc = np.array([q[t, head] @ k[u, g]
                               for u in range(lo, t + 1)]) / np.sqrt(d)
                w = np.exp(sc - sc.max())
                out[t, head] = (w / w.sum()) @ v[lo:t + 1, g]
        x = x + out.reshape(len(x), -1) @ f(p["attn"]["out"]["kernel"])
        for t in range(len(x)):
            u = rms(x[t], p["ln2"]["scale"])
            top = np.argsort(-r[t], kind="stable")[:s["top_k"]]
            g = np.exp(r[t, top] - r[t, top].max())
            for e, ge in zip(top, g / g.sum()):
                hid = np.maximum(u @ f(p["moe"]["w1"][e]), 0) \
                    * (u @ f(p["moe"]["w3"][e]))
                x[t] = x[t] + ge * (hid @ f(p["moe"]["w2"][e]))
    h = np.stack([rms(row, params["ln_final"]["scale"]) for row in x])
    return h @ f(params["lm_head"]["kernel"])


def test_reference_agrees_with_a_token_at_a_time_formulation():
    cfg = dict(TINY, rope_theta=1.5e6, rms_norm_eps=1e-6,
               sliding_window_layout=[0, 1, 1, 1], rope_layout=[0, 1, 1, 1])
    params = make_weights(cfg, 11, dtype=jnp.float32)["params"]
    tokens = np.random.RandomState(0).randint(0, 512, size=27)
    got, _ = reference.forward(params, cfg, tokens, np.arange(27))
    want = slow_forward(params, cfg, tokens)
    assert np.abs(np.asarray(got) - want).max() < 2e-4 * np.abs(want).max()


# ------------------------------------------------- what decides `correct`
@pytest.mark.parametrize("seed", [2, 2 ** 31 + 5])
def test_control_in_the_programs_place_comes_out_not_correct(seed):
    out = cell(seed, control="fp8")
    assert out["correct"], out["checks"]
    assert out["checks"]["expert_set_mismatch_share"]["value"] \
        <= SMALL["expert_mismatch_limit"]
    control = out["control"]["fp8"]
    assert control["correct"] is False, control["checks"]


def broken(fault, seed):
    undo = faults_smallthinker.plant(fault)
    try:
        return cell(seed)
    finally:
        undo()


@pytest.mark.parametrize("fault", ["no_window", "top5"])
def test_a_dropped_mechanism_comes_out_not_correct(fault):
    out = broken(fault, 3)
    assert not out["correct"], out["checks"]
    assert out["checks"]["greedy_gap_max"]["value"] > SMALL["gap_max_limit"]
    assert out["checks"]["expert_set_mismatch_share"]["value"] \
        > SMALL["expert_mismatch_limit"]


def test_unfinished_requests_come_out_not_correct():
    out = broken("short_answers", 4)
    assert not out["correct"]
    assert out["failed"] > 0   # every answer longer than three tokens


# ------------------------------------------------------------ the readers
def test_readers_on_a_synthetic_obs():
    """Every new reader on an ``obs`` written by hand: a number where the
    program's spans and counters are there, ``None`` (never 0) where an
    older program has none."""
    bench = run.load_benchmark()
    _, cfg = run.find_cell(bench, "st21b_longdoc_steady")
    names = [m["name"] for m in bench["per_layer"]
             if m["name"].endswith(".st_longdoc")]
    assert len(names) == 12
    judged = [{"due_s": 0.0, "first_s": 0.4, "last_s": 1.0, "n": 8,
               "ok": True, "admit_step_s": 0.1}]
    scopes = {"total_s": 8.0, "chunk_attn_s": 1.0,
              "scope_s": {"moe_router": 0.2, "moe_dispatch": 0.3,
                          "moe_ffn": 2.0, "moe_combine": 0.3,
                          "attn_window": 1.5, "attn_global": 1.0,
                          "sampler": 2.0, "model_other": 0.7},
              "kernel_s": {"attn_window": 0.6, "attn_global": 0.4}}
    obs = {"cfg": cfg, "judged": judged, "seconds": 50.0,
           "drain_limit_s": 90.0, "peaks": {"bf16_flops_per_s": 197e12,
                                            "hbm_bytes_per_s": 819e9},
           "trace": {"window_s": 10.0, "chips": 1, "idle_pct": 20.0,
                     "programs": {"jit__chunk_paged": {"seconds": 3.0,
                                                       "count": 30}}},
           "scopes": scopes, "expert_load": {"block0/moe": [10, 12, 8, 10]},
           "work": {"prefills": [4096, 9000], "decodes": [5000.0] * 40,
                    "chunk_calls": [2048, 2048, 2048, 2048, 2048, 808],
                    "tick_rows": [4] * 10}}
    got = {n: run.metric_reader(n)(obs) for n in names}
    assert all(v is not None for v in got.values()), got
    for n in names:
        if n.split(".")[0].endswith(("_roofline_pct", "_mfu_pct",
                                     "_share_pct")):
            assert 0 < got[n] <= 100, (n, got[n])
    assert got["moe_load_max_over_mean.st_longdoc"] == pytest.approx(1.2)
    assert got["moe_share_pct.st_longdoc"] == pytest.approx(35.0)
    assert got["sampler_share_pct.st_longdoc"] == pytest.approx(25.0)
    assert got["prefill_ms_per_ktok.st_longdoc"] == pytest.approx(
        3000.0 / 13.096)
    # An older program: no scopes, no histogram, no trace.
    old = dict(obs, scopes=None, expert_load={}, work={}, trace=None)
    none = {n: run.metric_reader(n)(old) for n in names}
    assert {n for n, v in none.items() if v is not None} == {
        "ttft_p90_ms.st_longdoc", "queue_wait_p90_ms.st_longdoc"}


def test_trace_ops_are_put_down_to_scopes():
    """A traced op's name carries no scope; the compiled text's
    ``op_name`` does. Device self-time by scope, the kernel's time, the
    chunk programs' attention time and the expert loop's tile count, on
    events written by hand."""
    from chipbench.systems import serve_paged_moe_lm as system

    table = {
        "jit__tick_paged": {
            "attn_window.1": "jit(_tick_paged)/Llama/block1/attn/"
                             "attn._decode_step/attn_window/pallas_call",
            "fusion.7": "jit(_tick_paged)/Llama/block1/moe/moe._serve/"
                        "moe_ffn/while/body/dot_general",
            "fusion.9": "jit(_tick_paged)/jit(_where)/select_n"},
        "jit__chunk_paged": {
            "while.3": "jit(_chunk_paged)/Llama/block0/attn/"
                       "attn._decode_step/attn_global/while",
            "fusion.4": "jit(_chunk_paged)/Llama/block0/attn/"
                        "attn._decode_step/attn_global/while/body/mul",
            "fusion.5": "jit(_chunk_paged)/Llama/block0/moe/moe_router/"
                        "reduce_max",
            "fusion.6": "jit(_chunk_paged)/Llama/block0/ln1/mul",
            "fusion.8": "jit(_chunk_paged)/Llama/block0/moe/moe._serve/"
                        "moe_ffn/while/body/dot_general"}}
    op = lambda name, t, d, target="": [name, t, d, "", "fusion", target]
    events = {"host": [], "devices": [{
        "modules": [["jit__tick_paged(1)", 0.0, 1.0],
                    ["jit__chunk_paged(2)", 1.0, 2.0]],
        "ops": [op("attn_window.1", 0.0, 0.4, "tpu_custom_call"),
                op("fusion.7", 0.4, 0.1), op("fusion.9", 0.5, 0.3),
                op("copy.1", 0.8, 0.1),
                op("while.3", 1.0, 1.0), op("fusion.4", 1.1, 0.5),
                op("fusion.5", 2.0, 0.2), op("fusion.6", 2.2, 0.2),
                op("fusion.8", 2.4, 0.1), op("fusion.8", 2.5, 0.1),
                op("fusion.8", 2.6, 0.1)]}]}
    got = system.reduce_scopes(events, table, 0.0, 3.0)
    want = {"attn_window": 0.4, "moe_ffn": 0.4, "sampler": 0.4,
            "attn_global": 1.0, "moe_router": 0.2, "model_other": 0.2}
    assert got["scope_s"] == pytest.approx(want)
    assert got["kernel_s"] == pytest.approx({"attn_window": 0.4})
    assert got["chunk_attn_s"] == pytest.approx(1.0)
    assert got["total_s"] == pytest.approx(2.6)
    assert (got["moe_tiles_chunk"], got["moe_tiles_tick"]) == (3, 1)
