"""The LFM2-24B-A2B configuration's yardstick, held to its own rules at a
size a CPU test can hold (a dense convolution layer, then [attention, 3 x
convolution] routed over 8 experts top-4, 32 wide, 4 q heads over 2 kv
heads of 8, 3 taps, float32): the configuration file against the catalog's
row and the program's own tree; the reference against a second, slower
formulation; ``correct`` false for the fp8 control in the program's place
and for the timed path broken underneath (``tools/faults_lfm2.py``); the
new readers on a synthetic ``obs`` and on a recorded trace's op names; the
traffic file's multiset.

The chip's own readings, at the cell's size, are in PERF.md; the limits
here are this size's (stated below), not the chip's.
"""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run, traffic
from chipbench.reference import lfm2 as reference
from chipbench.tools import faults_lfm2
from chipbench.weights_lfm2 import make_weights, model_shapes

CELL = "lfm2_extract_saturated"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
TINY = {"num_hidden_layers": 5, "layer_types": TYPES, "num_dense_layers": 1,
        "hidden_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "intermediate_size": 48,
        "num_experts": 8, "num_experts_per_tok": 4,
        "moe_intermediate_size": 16, "vocab_size": 512,
        "precision": "float32", "initializer_range": 0.25,
        # At 8 experts the scores lie 0.1 apart, not 0.02.
        "select_bias_std": 0.1,
        "engine": {"max_slots": 3, "block_size": 4, "pool_blocks": 193,
                   "prefill_len": 128, "prefill_chunk": 32, "max_len": 256,
                   "max_queue_depth": 16}}
# Readings at this size on the CPU (weights N(0, 0.25), or the 32-wide
# model is all but linear and a lost state moves no token): the program, in float32, reads greedy_gap_mean
# and _max 0 and no expert set apart; the limits below sit between that
# and the faults' and the control's readings, which the tests print when
# they fail.
SMALL = {"gap_mean_limit": 5e-4, "gap_max_limit": 0.01,
         "nucleus_outside_limit": 0.02, "expert_mismatch_limit": 0.02,
         "state_probe_gap_limit": 0.01}


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    for name, size in (("Q_BLOCK", 16), ("PAD_TO", 32), ("ROW_BLOCK", 32),
                       ("HEAD_ROWS", 16)):
        monkeypatch.setattr(reference, name, size)


def small(cfg, spec):
    cfg.update(TINY)
    cfg["check"] = dict(cfg["check"], requests=4, sampled_requests=2,
                        max_rows=16, min_tokens=8, **SMALL)
    spec["prompt_len"] = {"median": 40, "sigma": 0.6, "min": 10, "max": 120}
    spec["output_len"] = {"median": 6, "sigma": 0.5, "min": 3, "max": 12}
    spec["queue_target"] = 2


def cell(seed, **kw):
    return run.run_cell(CELL, seed, 4.0, False, allow_cpu=True,
                        overrides=small, **kw)


def the_cfg():
    return run.find_cell(run.load_benchmark(), CELL)[1]


# -------------------------------------------------- the configuration file
def test_configuration_holds_the_catalogs_keys_but_the_three_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    cfg = the_cfg()
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types",
                              "num_dense_layers"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 9
    # The cut: the leading dense layers once, then the pattern from the
    # first layer after them, two whole periods.
    published = row["config"]["layer_types"]
    assert cfg["layer_types"] == published[:1] + published[2:10]
    assert cfg["num_dense_layers"] == 1
    eng = cfg["engine"]
    assert eng["pool_blocks"] == eng["max_slots"] * (
        eng["max_len"] // eng["block_size"]) + 1
    assert cfg["n_positions"] == eng["max_len"]


def test_reckoned_parameters_are_the_programs_tree():
    """The count in ``reckoned_bytes.weights`` against the program's own
    model at the cell's size (shapes only, nothing allocated), and
    against the tree the benchmark's weights are made in."""
    from chipbench.systems.serve_paged_lfm2 import build_model

    cfg = the_cfg()
    model = build_model(cfg)
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False))[
            "params"]
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert f"{count:,}" in cfg["reckoned_bytes"]["weights"]
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    made = jax.tree.map(lambda x: x[0], model_shapes(cfg), is_leaf=is_leaf)
    assert jax.tree.map(lambda x: tuple(x.shape), tree) == made
    assert (model.slot_state_layers, model.depth) == (7, 9)


# ------------------------------------------- the reference against itself
def slow_forward(params, cfg, tokens):
    """A second formulation, a token at a time in numpy float64: the
    convolution from a two-row state carried token to token (the
    reference has no state), attention of token t against keys 0..t one
    by one, the experts one by one."""
    s = reference.shape_of(cfg)
    f = lambda a: np.asarray(a, np.float64)
    rms = lambda x, w: x / np.sqrt(np.mean(x * x) + s["eps"]) * f(w)
    silu = lambda x: x / (1.0 + np.exp(-x))
    d, e = s["head_dim"], s["embed"]
    inv = 1.0 / s["theta"] ** (np.arange(0, d, 2) / d)

    def rotate(v, t):
        a, b = v[..., :d // 2], v[..., d // 2:]
        c, sn = np.cos(t * inv), np.sin(t * inv)
        return np.concatenate([a * c - b * sn, b * c + a * sn], -1)

    mlp = lambda u, g, up, dn: (silu(u @ f(g)) * (u @ f(up))) @ f(dn)
    x = f(params["embed"]["embedding"])[np.asarray(tokens)]
    n = len(x)
    for i in range(s["layers"]):
        p = params[f"block{i}"]
        if s["layer_types"][i] == "conv":
            c = p["conv"]
            taps, state = f(c["taps"]), np.zeros((s["taps"] - 1, e))
            for t in range(n):
                u = rms(x[t], p["ln1"]["scale"])
                b_, c_, x_ = np.split(u @ f(c["in_proj"]["kernel"]), 3)
                window = np.vstack([state, b_ * x_])
                x[t] = x[t] + (c_ * (taps * window).sum(0)) \
                    @ f(c["out_proj"]["kernel"])
                state = window[1:]
        else:
            a = p["attn"]
            keys, values = [], []
            group = s["heads"] // s["kv_heads"]
            for t in range(n):
                u = rms(x[t], p["ln1"]["scale"])
                q = np.einsum("e,ehd->hd", u, f(a["query"]["kernel"]))
                k = np.einsum("e,ehd->hd", u, f(a["key"]["kernel"]))
                v = np.einsum("e,ehd->hd", u, f(a["value"]["kernel"]))
                q = np.stack([rotate(rms(h, a["q_norm"]["scale"]), t)
                              for h in q])
                keys.append(np.stack([rotate(rms(h, a["k_norm"]["scale"]),
                                             t) for h in k]))
                values.append(v)
                out = np.zeros((s["heads"], d))
                for head in range(s["heads"]):
                    sc = np.array([q[head] @ keys[j][head // group]
                                   for j in range(t + 1)]) / np.sqrt(d)
                    w = np.exp(sc - sc.max())
                    out[head] = (w / w.sum()) @ np.array(
                        [values[j][head // group] for j in range(t + 1)])
                x[t] = x[t] + out.reshape(-1) @ f(a["out"]["kernel"])
        for t in range(n):
            u = rms(x[t], p["ln2"]["scale"])
            if i < s["dense_layers"]:
                x[t] = x[t] + mlp(u, p["mlp_gate"]["kernel"],
                                  p["mlp_up"]["kernel"],
                                  p["mlp_down"]["kernel"])
                continue
            m = p["moe"]
            score = 1.0 / (1.0 + np.exp(-(u @ f(m["router"]["kernel"]))))
            top = np.argsort(-(score + f(m["select_bias"])),
                             kind="stable")[:s["top_k"]]
            for j in top:
                g = s["gate_scale"] * score[j] / (score[top].sum()
                                                  + reference.GATE_EPS)
                x[t] = x[t] + g * mlp(u, m["w1"][j], m["w3"][j], m["w2"][j])
    h = np.stack([rms(row, params["ln_final"]["scale"]) for row in x])
    return h @ f(params["lm_head"]["kernel"])


def tiny_cfg():
    return dict(TINY, conv_L_cache=3, norm_eps=1e-5,
                routed_scaling_factor=1,
                rope_parameters={"rope_theta": 1e6})


def test_reference_agrees_with_a_token_at_a_time_formulation():
    cfg = tiny_cfg()
    params = make_weights(cfg, 11, dtype=jnp.float32)["params"]
    tokens = np.random.RandomState(0).randint(0, 512, size=27)
    got, _ = reference.forward(params, cfg, tokens, np.arange(27))
    want = slow_forward(params, cfg, tokens)
    assert np.abs(np.asarray(got) - want).max() < 2e-4 * np.abs(want).max()


# ------------------------------------------------- what decides `correct`
@pytest.mark.parametrize("seed", [2, 2 ** 31 + 5])
def test_control_in_the_programs_place_comes_out_not_correct(seed):
    out = cell(seed, control="fp8,bf16")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["checks"]["expert_set_mismatch_share"]["value"] \
        <= SMALL["expert_mismatch_limit"]
    control = out["control"]["fp8"]
    assert control["correct"] is False, control["checks"]
    gap = lambda c: c["checks"]["greedy_gap_mean"]["value"]
    assert gap(out) <= gap(out["control"]["bf16"]) < gap(control)


STATE_FAULTS = ("state_not_carried", "state_from_padded_tail",
                "previous_stream_kept")


def broken(fault, seed):
    undo = faults_lfm2.plant(fault)
    try:
        return cell(seed)
    finally:
        undo()


@pytest.mark.parametrize("fault", [
    "state_not_carried", "state_from_padded_tail", "previous_stream_kept",
    "b_c_exchanged", "taps_reversed", "qk_norm_left_out",
    "selection_bias_dropped", "bias_in_the_gates", "altered_token"])
def test_a_fault_in_the_timed_path_comes_out_not_correct(fault):
    """The convolution's state not carried from a chunk to the next, taken
    from a padded chunk's tail, or kept from the slot's previous stream;
    B and C exchanged, the taps reversed; q/k norm left out; the
    selection bias dropped or leaked into the gates; a greedy token
    altered in the sampler."""
    out = broken(fault, 3)
    assert not out["correct"], out["checks"]
    value = lambda name: out["checks"][name]["value"]
    if fault == "selection_bias_dropped":
        assert value("expert_set_mismatch_share") \
            > SMALL["expert_mismatch_limit"]
    elif fault in STATE_FAULTS:
        # A short convolution reaches three tokens back: a lost state
        # moves the few positions behind it, which is where the state
        # probes put their sampled rows; a long prompt's answer may not
        # notice.
        assert value("state_probe_gap_max") > SMALL["state_probe_gap_limit"]
    else:
        assert value("greedy_gap_max") > SMALL["gap_max_limit"]


def test_a_dropped_nucleus_filter_comes_out_not_correct():
    assert cell(3)["checks"]["nucleus_outside_share"]["value"] == 0
    out = broken("no_top_p", 3)
    assert not out["correct"], out["checks"]
    assert out["checks"]["nucleus_outside_share"]["value"] \
        > SMALL["nucleus_outside_limit"]


# ------------------------------------------------------------ the traffic
def test_traffic_multiset_is_the_files_whatever_the_seed():
    spec = traffic.load_traffic("extract_backlog")
    assert (spec["kind"], spec["queue_target"], spec["multiset_size"],
            spec["strata"], spec["order_seed"]) == ("backlog", 8, 32, 16, 0)
    plans = [traffic.generate(spec, seed, 50.0, 65536, 16384, 18432)
             for seed in (1, 2 ** 31 + 9)]
    lengths = [[(r.prompt.size, r.max_new_tokens) for r in plan]
               for plan in plans]
    assert lengths[0] == lengths[1]
    assert len(plans[0]) == 13 * 32   # traffic.BACKLOG_PLAN_RATE_PER_S
    cycle = lengths[0][:32]
    assert sorted(cycle) == sorted(lengths[0][32:64])
    assert all(1024 <= p <= 16384 and 32 <= o <= 768 for p, o in cycle)
    assert len(set(cycle)) == 32
    assert np.median([p for p, _ in cycle]) == pytest.approx(4096, rel=0.05)
    assert np.median([o for _, o in cycle]) == pytest.approx(192, rel=0.05)
    assert sum(r.greedy for r in plans[0][:32]) == 16
    assert not np.array_equal(plans[0][0].prompt, plans[1][0].prompt)


# ------------------------------------------------------------ the readers
def new_metrics(bench):
    return [m["name"] for m in bench["per_layer"]
            if m["name"].endswith(".lfm2_extract")]


def test_readers_on_a_synthetic_obs():
    """Every reader of the cell on an ``obs`` written by hand: a number
    where the program's spans and counters are there, ``None`` (never 0)
    where an older program has none."""
    bench = run.load_benchmark()
    cfg = the_cfg()
    names = [m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())]
    assert len(new_metrics(bench)) == 10
    assert set(new_metrics(bench)) <= set(names)
    for m in bench["per_layer"]:
        if m["name"] in new_metrics(bench):
            assert (m["workloads"], m["moves"]) == ([CELL], "out_tok_per_s")
    # The backlog's shared gauges are whichever lists hold the cell: a
    # later benchmark PR may add one without an edit here.
    shared = set(names) - set(new_metrics(bench))
    assert {"slot_occupancy_pct", "kv_pool_used_pct",
            "device_idle_pct.saturated"} <= shared
    scopes = {"total_s": 8.0, "chunk_s": 3.2, "tick_s": 4.6,
              "scope_s": {"moe_router": 0.2, "moe_dispatch": 0.3,
                          "moe_ffn": 3.0, "moe_combine": 0.3,
                          "shortconv": 0.8, "attn_global": 0.6,
                          "sampler": 0.8, "model_other": 2.0},
              "chunk_scope_s": {"shortconv": 0.6, "attn_global": 0.3},
              "tick_scope_s": {"shortconv": 0.2, "attn_global": 0.3},
              "kernel_s": {"attn_global": 0.25}}
    snap = lambda k: {"engine_steps": 100 * k, "step_wall_s": 5.0 * k,
                      "phase_wall_s": {"tick_wait": 2.0 * k,
                                       "tick_dispatch": 0.3 * k,
                                       "first_token_wait": 0.5 * k,
                                       "append_blocks": 0.1 * k},
                      "decode_ticks": 100 * k, "prefill_tokens": 70000 * k,
                      "state_rows_started": 14 * k,
                      "prefix_skipped_stateful": 14 * k}
    steps = [{"live": 48, "fill": 0.25, "tokens": 48, "t0": 0.03 * i,
              "t1": 0.03 * i + 0.028, "sites": ["tick"]}
             for i in range(10)]
    obs = {"cfg": cfg, "seconds": 50.0, "drain_limit_s": 90.0,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "trace": {"window_s": 6.0, "chips": 1, "idle_pct": 30.0,
                     "steps": [{"t0": 0.0, "t1": 0.03, "busy_s": 0.025}]},
           "steps": steps,
           "facts": {"steps_in_window": 10, "window_s": 50.0,
                     "counters_open": snap(1), "counters_close": snap(2)},
           "scopes": scopes, "expert_load": {"block1/moe": [10, 12, 8, 10]},
           "work": {"prefills": [4096, 9000], "decodes": [5000.0] * 4000,
                    "chunk_calls": [2048] * 6 + [808],
                    "tick_rows": [48] * 100}}
    got = {n: run.metric_reader(n)(obs) for n in names}
    assert all(v is not None for v in got.values()), got
    for n in names:
        if n.split(".")[0].endswith(("_roofline_pct", "_mfu_pct",
                                     "_share_pct")):
            assert 0 < got[n] <= 100, (n, got[n])
    assert got["moe_load_max_over_mean.lfm2_extract"] == pytest.approx(1.2)
    assert got["moe_share_pct.lfm2_extract"] == pytest.approx(47.5)
    assert got["shortconv_share_pct.lfm2_extract"] == pytest.approx(10.0)
    assert got["attn_share_pct.lfm2_extract"] == pytest.approx(7.5)
    assert got["sampler_share_pct.lfm2_extract"] == pytest.approx(10.0)
    assert got["prefill_busy_share_pct.lfm2_extract"] == pytest.approx(40.0)
    assert got["kv_pool_used_pct"] == pytest.approx(
        100 * 0.25 * 48 * 1152 / 55297)
    # The decode kernel's least time: 4,000 tokens x 5,000 keys x 2
    # attention layers x 2,048 B over 819 GB/s (the bytes bound it).
    assert got["paged_attn_roofline_pct.lfm2_extract"] == pytest.approx(
        100 * 4000 * 5000 * 2 * 2048 / 819e9 / 0.25)
    # The convolution's: a 2,048-token chunk is bound by its FLOPs (68.7
    # GFLOP a layer), an 808-token one too, a 48-row tick by the weights'
    # bytes (33.6 MB a layer, and 48 states read and written).
    per_tok = 2 * 4 * 2048 * 2048 + 7 * 2048
    chunk = sum(max(rows * per_tok / 197e12,
                    (4 * 2048 * 2048 * 2 + 3 * 2048 * 2
                     + (2 * rows + 4) * 2048 * 2) / 819e9)
                for rows in [2048] * 6 + [808])
    tick = 100 * (4 * 2048 * 2048 * 2 + 3 * 2048 * 2
                  + (2 * 48 + 4 * 48) * 2048 * 2) / 819e9
    assert got["shortconv_roofline_pct.lfm2_extract"] == pytest.approx(
        100 * 7 * (chunk + tick) / 0.8)
    # An older program (the parent of the PR that added the cell, could
    # it run it): no scopes, no histogram, no counter, no trace.
    facts = dict(obs["facts"], counters_open={}, counters_close={})
    old = dict(obs, scopes=None, expert_load={}, work={}, trace=None,
               facts=facts)
    none = {n: run.metric_reader(n)(old) for n in new_metrics(bench)}
    assert all(v is None for v in none.values()), none


def test_trace_ops_are_put_down_to_scopes():
    """Device self-time by the innermost scope, the tick kernel's time,
    the chunk programs' share, on events written by hand."""
    from chipbench.systems import serve_paged_lfm2 as system

    table = {
        "jit__tick_paged": {
            "attn_global.1": "jit(_tick_paged)/Llama/block1/attn/"
                             "attn_global/pallas_call",
            "fusion.2": "jit(_tick_paged)/Llama/block0/conv/shortconv/"
                        "conv._apply/in_proj/dot_general",
            "fusion.7": "jit(_tick_paged)/Llama/block1/moe/moe_ffn/"
                        "dot_general",
            "fusion.9": "jit(_tick_paged)/jit(_where)/select_n"},
        "jit__chunk_paged": {
            "while.3": "jit(_chunk_paged)/Llama/block1/attn/attn_global/"
                       "while",
            "fusion.4": "jit(_chunk_paged)/Llama/block2/conv/shortconv/"
                        "conv._apply/mul",
            "fusion.6": "jit(_chunk_paged)/Llama/block0/mlp_gate/dot"}}
    op = lambda name, t, d, target="": [name, t, d, "", "fusion", target]
    events = {"host": [], "devices": [{
        "modules": [["jit__tick_paged(1)", 0.0, 1.0],
                    ["jit__chunk_paged(2)", 1.0, 2.0]],
        "ops": [op("attn_global.1", 0.0, 0.4, "tpu_custom_call"),
                op("fusion.2", 0.4, 0.1), op("fusion.7", 0.5, 0.2),
                op("fusion.9", 0.7, 0.3),
                op("while.3", 1.0, 1.0), op("fusion.4", 2.0, 0.5),
                op("fusion.6", 2.5, 0.4)]}]}
    got = system.reduce_scopes(events, table, 0.0, 3.0)
    assert got["scope_s"] == pytest.approx({
        "attn_global": 1.4, "shortconv": 0.6, "moe_ffn": 0.2,
        "sampler": 0.3, "model_other": 0.4})
    assert got["kernel_s"] == pytest.approx({"attn_global": 0.4})
    assert got["chunk_scope_s"] == pytest.approx({
        "attn_global": 1.0, "shortconv": 0.5, "model_other": 0.4})
    assert got["tick_scope_s"] == pytest.approx({
        "attn_global": 0.4, "shortconv": 0.1, "moe_ffn": 0.2,
        "sampler": 0.3})
    assert (got["chunk_s"], got["tick_s"]) == pytest.approx((1.9, 1.0))


RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "recorded", "lfm2_extract_ops.json.gz")


def test_recorded_trace_ops_reach_every_scope_the_readers_read():
    """A stretch of the cell's own device trace and compiled op names,
    recorded on the chip (``tools/record_lfm2_ops.py``): ``trace_reduce``'s
    events through this configuration's scope table give time under
    ``shortconv`` in the ticks and in the chunk programs, under the
    expert scopes, and a Mosaic kernel under ``attn_global`` in the ticks
    — a scope renamed in the program, or named so that the compiled text
    does not carry it, fails here and not on the chip."""
    from chipbench.systems import serve_paged_lfm2 as system

    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    got = system.reduce_scopes(rec["events"], rec["table"], *rec["edges"])
    assert got["unmatched_s"] < 0.02 * got["total_s"]
    for where in ("chunk_scope_s", "tick_scope_s"):
        for scope in ("shortconv", "attn_global", "moe_ffn", "moe_router"):
            assert got[where].get(scope, 0.0) > 0, (where, scope)
    assert got["kernel_s"].get("attn_global", 0.0) > 0
    assert got["scope_s"]["sampler"] > 0
    sc = dict(got, scope_s=got["scope_s"])
    obs = {"scopes": sc}
    for name in ("shortconv_share_pct.lfm2_extract",
                 "attn_share_pct.lfm2_extract", "moe_share_pct.lfm2_extract",
                 "sampler_share_pct.lfm2_extract",
                 "prefill_busy_share_pct.lfm2_extract"):
        assert 0 < run.metric_reader(name)(obs) < 100, name
