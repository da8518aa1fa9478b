"""Plain reference for GLM-4.7-Flash (``glm4_moe_lite``): the forward
pass in straightforward ``jax.numpy``, float32, matmuls at ``highest``
precision, the per-head form of latent attention under a full mask, a loop
over all 64 experts, no kernels, no cache, no batching, no sorting of
tokens. Imports nothing of the program.

The layer, for tokens ``x [S, E]`` (configuration keys in backticks; every
item marked ASSUMED is one the public ``config.json`` does not settle and
is listed under ``assumed`` in the configuration file). It is the
DeepSeek-V3 form the keys name:

    h = RMSNorm(x; rms_norm_eps)
    c_q = RMSNorm(h W_dq)              `q_lora_rank` 768; ASSUMED: the norm
                                       has rms_norm_eps, no biases anywhere
    [q_nope | q_rope] = c_q W_uq       20 heads of `qk_nope_head_dim` 192 +
                                       `qk_rope_head_dim` 64
    [c_kv | k_rope] = h W_dkv          `kv_lora_rank` 512 + 64, ONE for all
                                       heads; c_kv = RMSNorm(c_kv)
    q_rope, k_rope = RoPE(.; rope_theta 1e6, half-split over the 64 rope
                     dims, absolute positions)        ASSUMED convention
    [k_nope | v]_h = c_kv W_ukv,h      192 + `v_head_dim` 256 a head
    k_h = [k_nope,h | k_rope]          (k_rope shared by the heads)
    a_h = causal softmax(q_h . k_h / sqrt(192 + 64)) v_h   ASSUMED scale
    x' = x + concat(a_h) W_o           5,120 -> 2,048
    u = RMSNorm(x'; rms_norm_eps)
    layer 0 (`first_k_dense_replace` 1):
      y = W_down(silu(u W_gate) * (u W_up)), `intermediate_size` 10,240
    layers 1..:
      s = sigmoid(u W_r)               64 scores, float32, no router bias
      I = the 4 largest of s + b       b: the per-expert correction bias
                                       (`topk_method` noaux_tc), SELECTION
                                       ONLY; `n_group` = `topk_group` = 1,
                                       so no group is cut before the choice
      g_i = 1.8 * s_i / sum_{j in I} s_j   (`norm_topk_prob`,
                                       `routed_scaling_factor`)
      y = sum_{i in I} g_i E_i(u) + E_shared(u), every E
          W_down(silu(u W_gate) * (u W_up)), `moe_intermediate_size` 1,536
    x'' = x' + y

then a final RMSNorm and an untied head. OMITTED: the multi-token
prediction block (`num_nextn_predict_layers` 1), which these layers'
logits do not depend on. Weights arrive in the type they are served in
(bf16) and are upcast as they are used, a layer and an expert at a time;
attention goes a stretch of query rows at a time and the MLPs a stretch of
tokens at a time, so that an 18k-token sequence fits beside the weights on
the chip; the experts are a loop over all 64, each multiplying EVERY token
and keeping the rows routed to it (16 x the routed multiplies: plain, not
fast).

``control="fp8"`` computes the same pass one precision below bf16: every
matmul's inputs and weights rounded to fp8 e4m3 (absmax scales per row /
per output channel), as ``reference/smallthinker.py`` does. It must come
out as not correct. ``control="bf16"`` rounds the same inputs to bf16, the
precision the configuration states: a witness that owes nothing to the
program, which must read about what the program reads and come out
correct (``tools/limits.py --control fp8,bf16``).

The tree read is the layout the benchmark's weights are made in
(``weights_glm47_flash.py``): ``embed/embedding``, ``block<i>/{ln1/scale,
attn/{q_down/kernel, q_norm/scale, q_up/kernel, kv_down/kernel,
kv_norm/scale, kv_up, out/kernel}, ln2/scale}`` and in layer 0
``mlp_{gate,up,down}/kernel``, in the others ``moe/{router/kernel,
select_bias, w1 (gate), w3 (up), w2 (down), shared_{gate,up,down}/kernel}``;
``ln_final/scale``, ``lm_head/kernel``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256     # query rows scored at once: [20, 256, S] float32
ROW_BLOCK = 2048  # tokens an MLP takes at once
PAD_TO = 2048     # a sequence is padded to a multiple: ten compiled
                  # lengths serve every sequence up to 20,480
HEAD_ROWS = 256   # logit rows made at once: [256, 154,880] float32


def shape_of(cfg: dict) -> dict:
    return {"layers": int(cfg["num_hidden_layers"]),
            "dense_layers": int(cfg["first_k_dense_replace"]),
            "embed": int(cfg["hidden_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "q_rank": int(cfg["q_lora_rank"]),
            "kv_rank": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "v_dim": int(cfg["v_head_dim"]),
            "dense_width": int(cfg["intermediate_size"]),
            "experts": int(cfg["n_routed_experts"]),
            "shared": int(cfg["n_shared_experts"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "expert_width": int(cfg["moe_intermediate_size"]),
            "gate_scale": float(cfg["routed_scaling_factor"]),
            "vocab": int(cfg["vocab_size"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"])}


def _fake_quant(x, axis, control):
    """Round ``x`` to the control's grid, one scale per slice across
    ``axis`` (the contraction axis)."""
    if control is None:
        return x
    if control == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if control != "fp8":
        raise ValueError(f"unknown control {control!r}")
    scale = (jnp.max(jnp.abs(x), axis=axis, keepdims=True) + 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control):
    """x [..., in] @ w [in, out], both on the control's grid."""
    return jnp.matmul(_fake_quant(x, -1, control),
                      _fake_quant(w, 0, control), precision=_HI)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x [H, S, D] rotated at positions 0..S-1, half-split: the head is
    [x1 | x2], the result [x1 cos - x2 sin | x2 cos + x1 sin], with
    frequencies theta^(-2i/D), i < D/2."""
    _, s, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(a):
    return a.astype(jnp.float32)


def _by_rows(fn, x):
    """``fn`` over ``x [S, E]`` a stretch of ``ROW_BLOCK`` tokens at a
    time (S a multiple of it, or under it)."""
    s, e = x.shape
    if s <= ROW_BLOCK:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(s // ROW_BLOCK, ROW_BLOCK, e))
    return out.reshape(s, -1)


def _gated_mlp(u, gate, up, down, control):
    return _by_rows(lambda r: _mm(
        jax.nn.silu(_mm(r, _f32(gate), control)) * _mm(r, _f32(up), control),
        _f32(down), control), u)


@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rope", "theta", "eps", "control"))
def attention(x, p, ln_scale, *, heads, rank, nope, rope, theta, eps,
              control=None):
    """``x + attention(RMSNorm(x))`` over one sequence ``x [S, E]`` (S a
    multiple of ``Q_BLOCK``), per-head form, full causal mask."""
    s, _ = x.shape
    h = _rms_norm(x, _f32(ln_scale), eps)
    c_q = _rms_norm(_mm(h, _f32(p["q_down"]["kernel"]), control),
                    _f32(p["q_norm"]["scale"]), eps)
    q = _mm(c_q, _f32(p["q_up"]["kernel"]).reshape(c_q.shape[-1], -1),
            control).reshape(s, heads, nope + rope).transpose(1, 0, 2)
    entry = _mm(h, _f32(p["kv_down"]["kernel"]), control)     # [S, rank+rope]
    c_kv = _rms_norm(entry[:, :rank], _f32(p["kv_norm"]["scale"]), eps)
    k_rope = _rope(entry[None, :, rank:], theta)              # [1, S, rope]
    kv = _mm(c_kv, _f32(p["kv_up"]).reshape(rank, -1), control) \
        .reshape(s, heads, -1).transpose(1, 0, 2)             # [H, S, nope+v]
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (heads, s, rope))], -1)
    v = kv[..., nope:]
    pos = jnp.arange(s)

    def rows(i):  # a stretch of Q_BLOCK query rows against every key
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        scores = jnp.einsum("hqd,hkd->hqk", qi, k, precision=_HI) \
            / np.sqrt(nope + rope)
        weights = jax.nn.softmax(
            jnp.where(pos[None, :] <= t[:, None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", weights, v, precision=_HI)

    a = jax.lax.map(rows, jnp.arange(s // Q_BLOCK))    # [S/Q, H, Q, v]
    a = a.transpose(0, 2, 1, 3).reshape(s, -1)
    return x + _mm(a, _f32(p["out"]["kernel"]), control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def dense_mlp(x, p, *, eps, control=None):
    """``x + MLP(RMSNorm(x))``: the leading dense layer."""
    u = _rms_norm(x, _f32(p["ln2"]["scale"]), eps)
    return x + _gated_mlp(u, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                          p["mlp_down"]["kernel"], control)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "gate_scale", "eps", "control"))
def routed_mlp(x, ln_scale, moe, *, top_k, gate_scale, eps, control=None):
    """``x + sum_i g_i E_i(u) + E_shared(u)`` and the experts chosen
    ``I [S, top_k]``."""
    u = _rms_norm(x, _f32(ln_scale), eps)
    s = jax.nn.sigmoid(_mm(u, _f32(moe["router"]["kernel"]), control))
    _, index = jax.lax.top_k(s + _f32(moe["select_bias"]), top_k)
    chosen = jnp.take_along_axis(s, index, axis=-1)
    gates = gate_scale * chosen / jnp.sum(chosen, -1, keepdims=True)

    def expert(y, w):  # every token through expert w, kept where routed
        gate_e = jnp.sum(jnp.where(index == w["e"], gates, 0.0), axis=-1)
        hid = jax.nn.silu(_mm(u, _f32(w["w1"]), control)) \
            * _mm(u, _f32(w["w3"]), control)
        return y + gate_e[:, None] * _mm(hid, _f32(w["w2"]), control), None

    n = moe["w1"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), {
        "w1": moe["w1"], "w3": moe["w3"], "w2": moe["w2"],
        "e": jnp.arange(n)})
    y = y + _gated_mlp(u, moe["shared_gate"]["kernel"],
                       moe["shared_up"]["kernel"],
                       moe["shared_down"]["kernel"], control)
    return x + y, index


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x_rows, ln_scale, kernel, *, eps, control=None):
    return _mm(_rms_norm(x_rows, _f32(ln_scale), eps), _f32(kernel), control)


def hidden(params, cfg: dict, tokens, control=None):
    """The last layer's output ``[S_padded, E]`` of one sequence
    ``tokens [S]`` and every routed layer's expert sets ``[L_routed, S,
    k]``. The sequence is padded to a multiple of ``PAD_TO``; padding
    after it changes nothing before it (causal)."""
    s = shape_of(cfg)
    tokens = np.asarray(tokens, np.int32)
    padded = -(-tokens.size // PAD_TO) * PAD_TO
    seq = np.zeros(padded, np.int32)
    seq[:tokens.size] = tokens
    x = _f32(params["embed"]["embedding"][jnp.asarray(seq)])
    sets = []
    for i in range(s["layers"]):
        p = params[f"block{i}"]
        x = attention(x, p["attn"], p["ln1"]["scale"], heads=s["heads"],
                      rank=s["kv_rank"], nope=s["nope"], rope=s["rope"],
                      theta=s["theta"], eps=s["eps"], control=control)
        if i < s["dense_layers"]:
            x = dense_mlp(x, p, eps=s["eps"], control=control)
        else:
            x, index = routed_mlp(
                x, p["ln2"]["scale"], p["moe"], top_k=s["top_k"],
                gate_scale=s["gate_scale"], eps=s["eps"], control=control)
            sets.append(index[:tokens.size])
    return x, jnp.stack(sets)


def forward(params, cfg: dict, tokens, rows, control=None):
    """Logits ``[len(rows), V]`` (float32) of one sequence ``tokens [S]``
    at positions ``rows``, and the routed layers' expert sets."""
    x, sets = hidden(params, cfg, tokens, control)
    logits = head(x[jnp.asarray(rows)], params["ln_final"]["scale"],
                  params["lm_head"]["kernel"], eps=shape_of(cfg)["eps"],
                  control=control)
    return logits, sets


@functools.partial(jax.jit, static_argnames=("eps", "control", "nucleus"))
def _row_stats(x_rows, ln_scale, kernel, served, temperature, *, eps,
               control=None, nucleus=False):
    """Of ``HEAD_ROWS`` rows: the best logit, the served token's, the
    token put first, and (``nucleus``) the probability mass of the tokens
    likelier than the served one at ``temperature``."""
    logits = head(x_rows, ln_scale, kernel, eps=eps, control=control)
    at = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    out = {"best": logits.max(-1), "at_served": at,
           "first": logits.argmax(-1)}
    if nucleus:
        warped = logits / temperature
        p = jax.nn.softmax(warped, axis=-1)
        out["likelier_mass"] = jnp.sum(
            jnp.where(warped > (at / temperature)[:, None], p, 0.0), -1)
    return out


def _stats(params, cfg, x, rows, served, temperature, control, nucleus):
    """``_row_stats`` over every row, ``HEAD_ROWS`` at a time (a request
    answers with up to 2,048 tokens: their logits at once would be 1.3 GB
    and as much again on the host)."""
    n = rows.size
    padded = -(-n // HEAD_ROWS) * HEAD_ROWS
    rows = np.concatenate([rows, np.full(padded - n, rows[-1], np.int32)])
    served = np.concatenate([served, np.zeros(padded - n, np.int32)])
    parts = [_row_stats(
        x[jnp.asarray(rows[i:i + HEAD_ROWS])], params["ln_final"]["scale"],
        params["lm_head"]["kernel"], jnp.asarray(served[i:i + HEAD_ROWS]),
        jnp.float32(temperature or 1.0), eps=shape_of(cfg)["eps"],
        control=control, nucleus=nucleus)
        for i in range(0, padded, HEAD_ROWS)]
    return {k: np.concatenate([np.asarray(p[k]) for p in parts])[:n]
            for k in parts[0]}


def served_gaps(params, cfg: dict, prompt, served, max_rows: int,
                controls=(), temperature: float = 0.0, top_p=None) -> dict:
    """One reference pass over ``prompt + served``: what
    ``reference/smallthinker.served_gaps`` returns (``gaps``,
    ``control_gaps``, ``nucleus_excess``, ``tokens``, ``expert_sets
    [L_routed, S, k]``, ``control_sets``), over the first ``max_rows``
    served tokens."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    served = served[:max_rows]
    n, plen = served.size, prompt.size
    rows = (plen - 1 + np.arange(n)).astype(np.int32)
    nucleus = temperature > 0 and top_p is not None
    x, sets = hidden(params, cfg, seq)
    ref = _stats(params, cfg, x, rows, served, temperature, None, nucleus)
    out = {"gaps": ref["best"] - ref["at_served"], "tokens": int(n),
           "control_gaps": {}, "nucleus_excess": None,
           "expert_sets": np.asarray(sets), "control_sets": {}}
    if nucleus:
        out["nucleus_excess"] = ref["likelier_mass"] - float(top_p)
    for control in controls:
        low_x, low_sets = hidden(params, cfg, seq, control=control)
        low = _stats(params, cfg, low_x, rows, served, temperature,
                     control, False)
        # The control's first token, judged by the reference's logits.
        again = _stats(params, cfg, x, rows, low["first"].astype(np.int32),
                       temperature, None, False)
        out["control_gaps"][control] = ref["best"] - again["at_served"]
        out["control_sets"][control] = np.asarray(low_sets)
    return out
