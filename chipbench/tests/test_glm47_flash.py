"""The GLM-4.7-Flash configuration's yardstick, held to its own rules at a
size a CPU test can hold (a dense layer and two routed ones of 32, 4
latent heads over a 24 + 4-value entry, 8 experts top-2 beside a shared
one, float32): the reference against a second, slower formulation;
``correct`` false for the fp8 control in the program's place and for the
timed path broken underneath (``tools/faults_glm47_flash.py``); the new
readers on a synthetic ``obs``.

The chip's own readings, at the cell's size, are in PERF.md; the limits
here are this size's (stated below), not the chip's.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import run
from chipbench.reference import glm47_flash as reference
from chipbench.tools import faults_glm47_flash
from chipbench.weights_glm47_flash import make_weights

CELL = "glm47f_agent_saturated"
TINY = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
        "hidden_size": 32, "num_attention_heads": 4, "q_lora_rank": 20,
        "kv_lora_rank": 24, "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
        "v_head_dim": 16, "intermediate_size": 48, "n_routed_experts": 8,
        "n_shared_experts": 1, "num_experts_per_tok": 2,
        "moe_intermediate_size": 16, "vocab_size": 512,
        "precision": "float32", "initializer_range": 0.08,
        "engine": {"max_slots": 3, "block_size": 4, "pool_blocks": 193,
                   "prefill_len": 128, "prefill_chunk": 32, "max_len": 256,
                   "max_queue_depth": 16}}
# Readings at this size on the CPU (weights N(0, 0.08), or the 32-wide
# model is all but linear), seeds 2, 3, 4 and 2**31+5: the program, in
# float32, reads greedy_gap_mean and _max 0 and no expert set apart; the
# limits below sit between that and the faults' and the control's
# readings, which the tests print when they fail.
SMALL = {"gap_mean_limit": 5e-4, "gap_max_limit": 0.01,
         "nucleus_outside_limit": 0.02, "expert_mismatch_limit": 0.02}


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


# ------------------------------------------- the reference against itself
def slow_forward(params, cfg, tokens):
    """A second formulation, a token at a time in numpy float64: for
    token t, its own query against keys 0..t one by one (each key put
    together from the cached latent as it is needed), and its experts one
    by one."""
    s = reference.shape_of(cfg)
    f = lambda a: np.asarray(a, np.float64)
    rms = lambda x, w: x / np.sqrt(np.mean(x * x) + s["eps"]) * f(w)
    silu = lambda x: x / (1.0 + np.exp(-x))
    nope, rope, rank = s["nope"], s["rope"], s["kv_rank"]
    inv = 1.0 / s["theta"] ** (np.arange(0, rope, 2) / rope)

    def rotate(v, t):
        a, b = v[..., :rope // 2], v[..., rope // 2:]
        c, sn = np.cos(t * inv), np.sin(t * inv)
        return np.concatenate([a * c - b * sn, b * c + a * sn], -1)

    mlp = lambda u, g, up, d: (silu(u @ f(g)) * (u @ f(up))) @ f(d)
    x = f(params["embed"]["embedding"])[np.asarray(tokens)]
    n = len(x)
    for i in range(s["layers"]):
        p = params[f"block{i}"]
        a = p["attn"]
        w_ukv = f(a["kv_up"])                        # [rank, H, nope + v]
        latents, ropes, out = [], [], np.zeros((n, s["heads"], s["v_dim"]))
        for t in range(n):
            h = rms(x[t], p["ln1"]["scale"])
            c_q = rms(h @ f(a["q_down"]["kernel"]), a["q_norm"]["scale"])
            q = np.einsum("c,chd->hd", c_q, f(a["q_up"]["kernel"]))
            entry = h @ f(a["kv_down"]["kernel"])
            latents.append(rms(entry[:rank], a["kv_norm"]["scale"]))
            ropes.append(rotate(entry[rank:], t))
            for head in range(s["heads"]):
                qh = np.concatenate([q[head, :nope],
                                     rotate(q[head, nope:], t)])
                sc, vs = [], []
                for u in range(t + 1):
                    kv = latents[u] @ w_ukv[:, head]
                    sc.append(qh @ np.concatenate([kv[:nope], ropes[u]]))
                    vs.append(kv[nope:])
                sc = np.array(sc) / np.sqrt(nope + rope)
                w = np.exp(sc - sc.max())
                out[t, head] = (w / w.sum()) @ np.array(vs)
        x = x + out.reshape(n, -1) @ f(a["out"]["kernel"])
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
            y = mlp(u, m["shared_gate"]["kernel"], m["shared_up"]["kernel"],
                    m["shared_down"]["kernel"])
            for e in top:
                g = s["gate_scale"] * score[e] / score[top].sum()
                y = y + g * mlp(u, m["w1"][e], m["w3"][e], m["w2"][e])
            x[t] = x[t] + y
    h = np.stack([rms(row, params["ln_final"]["scale"]) for row in x])
    return h @ f(params["lm_head"]["kernel"])


def test_reference_agrees_with_a_token_at_a_time_formulation():
    cfg = dict(TINY, rope_theta=1e6, rms_norm_eps=1e-5,
               routed_scaling_factor=1.8)
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
    assert out["checks"]["expert_set_mismatch_share"]["value"] \
        <= SMALL["expert_mismatch_limit"]
    control = out["control"]["fp8"]
    assert control["correct"] is False, control["checks"]
    # The bf16 witness (the reference at the precision the configuration
    # states) reads between the float32 program of this test and fp8.
    gap = lambda c: c["checks"]["greedy_gap_mean"]["value"]
    assert gap(out) <= gap(out["control"]["bf16"]) < gap(control)


def broken(fault, seed):
    undo = faults_glm47_flash.plant(fault)
    try:
        return cell(seed)
    finally:
        undo()


@pytest.mark.parametrize("fault", ["top3", "no_shared", "altered_token"])
def test_a_fault_in_the_timed_path_comes_out_not_correct(fault):
    """Top-2 cut to top-1 (this size's top-3 of the cell's top-4), the
    shared expert dropped, a greedy token altered in the sampler."""
    out = broken(fault, 3)
    assert not out["correct"], out["checks"]
    assert out["checks"]["greedy_gap_max"]["value"] > SMALL["gap_max_limit"]
    assert "nucleus_excess_max" not in out["checks"]


def test_a_dropped_nucleus_filter_comes_out_not_correct():
    """Sampled rows drawn from the whole vocabulary: a tenth of them lie
    outside the reference's nucleus, where the program's own are all
    inside it."""
    assert cell(3)["checks"]["nucleus_outside_share"]["value"] == 0
    out = broken("no_top_p", 3)
    assert not out["correct"], out["checks"]
    assert out["checks"]["nucleus_outside_share"]["value"] \
        > SMALL["nucleus_outside_limit"]
    assert out["checks"]["greedy_gap_max"]["value"] <= SMALL["gap_max_limit"]


# ------------------------------------------------------------ the readers
def test_readers_on_a_synthetic_obs():
    """Every new reader on an ``obs`` written by hand: a number where the
    program's spans and counters are there, ``None`` (never 0) where an
    older program has none."""
    bench = run.load_benchmark()
    _, cfg = run.find_cell(bench, CELL)
    names = [m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())]
    assert sum(n.endswith(".glm_agent") for n in names) == 10
    # The backlog's gauges under the names the ledger already tracks.
    assert {n for n in names if not n.endswith(".glm_agent")} == {
        "slot_occupancy_pct", "engine_host_ms_per_step",
        "decode_tick_ms_p50", "kv_pool_used_pct",
        "device_idle_pct.saturated", "step_host_self_ms",
        "tick_dispatch_ms_per_tick", "tick_wait_ms_per_tick"}
    scopes = {"total_s": 8.0, "chunk_s": 2.4, "tick_s": 5.4,
              "scope_s": {"moe_router": 0.2, "moe_dispatch": 0.3,
                          "moe_ffn": 2.0, "moe_combine": 0.3,
                          "moe_shared": 0.2, "attn_latent": 1.5,
                          "mla_absorb": 0.2, "mla_expand": 0.3,
                          "sampler": 2.0, "model_other": 1.0},
              "chunk_scope_s": {"attn_latent": 0.5, "mla_expand": 0.3},
              "kernel_s": {"attn_latent": 0.8}}
    snap = lambda k: {"engine_steps": 100 * k, "step_wall_s": 5.0 * k,
                      "phase_wall_s": {"tick_wait": 2.0 * k,
                                       "tick_dispatch": 0.3 * k,
                                       "first_token_wait": 0.5 * k},
                      "decode_ticks": 100 * k, "prefill_tokens": 70000 * k,
                      "latent_expanded_tokens": 140000 * k}
    steps = [{"live": 40, "fill": 0.4, "tokens": 40, "t0": 0.03 * i,
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
           "work": {"prefills": [6144, 9000], "decodes": [8000.0] * 4000,
                    "chunk_calls": [2048] * 7 + [808],
                    "tick_rows": [40] * 100}}
    got = {n: run.metric_reader(n)(obs) for n in names}
    assert all(v is not None for v in got.values()), got
    for n in names:
        if n.split(".")[0].endswith(("_roofline_pct", "_mfu_pct",
                                     "_share_pct")):
            assert 0 < got[n] <= 100, (n, got[n])
    assert got["moe_load_max_over_mean.glm_agent"] == pytest.approx(1.2)
    assert got["moe_share_pct.glm_agent"] == pytest.approx(37.5)
    assert got["attn_share_pct.glm_agent"] == pytest.approx(25.0)
    assert got["prefill_busy_share_pct.glm_agent"] == pytest.approx(30.0)
    assert got["latent_reexpand_ratio.glm_agent"] == pytest.approx(2.0)
    assert got["tick_wait_ms_per_tick"] == pytest.approx(20.0)
    assert got["tick_dispatch_ms_per_tick"] == pytest.approx(3.0)
    assert got["step_host_self_ms"] == pytest.approx(25.0)
    assert got["slot_occupancy_pct"] == pytest.approx(100.0)
    assert got["decode_tick_ms_p50"] == pytest.approx(28.0)
    assert got["engine_host_ms_per_step"] == pytest.approx(5.0)
    assert got["device_idle_pct.saturated"] == pytest.approx(30.0)
    assert got["kv_pool_used_pct"] == pytest.approx(
        100 * 0.4 * 40 * 1280 / 51201)
    # The decode kernel's least time: 4,000 tokens x 8,000 keys x 6
    # layers x 1,152 B over 819 GB/s (the bytes bound it) over 0.8 s.
    assert got["mla_decode_roofline_pct.glm_agent"] == pytest.approx(
        100 * 4000 * 8000 * 6 * 1152 / 819e9 / 0.8)
    # An older program (the parent of the PR that added the cell, could
    # it run it): no scopes, no histogram, no counter, no trace.
    facts = dict(obs["facts"], counters_open={}, counters_close={})
    old = dict(obs, scopes=None, expert_load={}, work={}, trace=None,
               facts=facts)
    none = {n: run.metric_reader(n)(old) for n in names}
    assert {n for n, v in none.items() if v is not None} == {
        "kv_pool_used_pct", "slot_occupancy_pct", "decode_tick_ms_p50"}


def test_trace_ops_are_put_down_to_scopes():
    """Device self-time by the innermost scope, the kernel's time, the
    chunk programs' share, on events written by hand."""
    from chipbench.systems import serve_paged_glm47_flash as system

    table = {
        "jit__tick_paged": {
            "attn_latent.1": "jit(_tick_paged)/Llama/block1/attn/"
                             "attn_latent/pallas_call",
            "fusion.2": "jit(_tick_paged)/Llama/block1/attn/attn_latent/"
                        "mla_absorb/dot_general",
            "fusion.7": "jit(_tick_paged)/Llama/block1/moe/moe_shared/"
                        "dot_general",
            "fusion.9": "jit(_tick_paged)/jit(_where)/select_n"},
        "jit__chunk_paged": {
            "while.3": "jit(_chunk_paged)/Llama/block0/attn/attn_latent/"
                       "while",
            "fusion.4": "jit(_chunk_paged)/Llama/block0/attn/attn_latent/"
                        "while/body/mla_expand/dot_general",
            "fusion.6": "jit(_chunk_paged)/Llama/block0/mlp_gate/dot"}}
    op = lambda name, t, d, target="": [name, t, d, "", "fusion", target]
    events = {"host": [], "devices": [{
        "modules": [["jit__tick_paged(1)", 0.0, 1.0],
                    ["jit__chunk_paged(2)", 1.0, 2.0]],
        "ops": [op("attn_latent.1", 0.0, 0.4, "tpu_custom_call"),
                op("fusion.2", 0.4, 0.1), op("fusion.7", 0.5, 0.2),
                op("fusion.9", 0.7, 0.3),
                op("while.3", 1.0, 1.0), op("fusion.4", 1.1, 0.5),
                op("fusion.6", 2.0, 0.4)]}]}
    got = system.reduce_scopes(events, table, 0.0, 3.0)
    assert got["scope_s"] == pytest.approx({
        "attn_latent": 0.9, "mla_absorb": 0.1, "moe_shared": 0.2,
        "sampler": 0.3, "mla_expand": 0.5, "model_other": 0.4})
    assert got["kernel_s"] == pytest.approx({"attn_latent": 0.4})
    assert got["chunk_scope_s"] == pytest.approx({
        "attn_latent": 0.5, "mla_expand": 0.5, "model_other": 0.4})
    assert (got["chunk_s"], got["tick_s"]) == pytest.approx((1.4, 1.0))
    assert got["total_s"] == pytest.approx(2.4)
