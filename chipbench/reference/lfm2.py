"""Plain reference for LFM2-24B-A2B (``lfm2_moe``): the forward pass in
straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision,
the short convolution as three shifted products over the whole sequence,
grouped-query attention under a full causal mask, a loop over all 64
experts, no kernels, no cache, no state, no batching, no sorting of
tokens. Imports nothing of the program.

The layer, for tokens ``x [S, E]`` (configuration keys in backticks; every
item marked ASSUMED is one the catalog's ``config`` does not settle and is
listed under ``assumed`` in the configuration file). With ``N_w(x) = x *
rsqrt(mean(x^2) + norm_eps) * w``:

    u = N_operator(x)
    `layer_types[l]` == "conv" (gated short convolution):
      [B | C | X] = u W_in             2048 -> 3 x 2048, no bias (`conv_bias`
                                       false); ASSUMED split order B, C, X
      z = B * X
      c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t   depthwise, `conv_L_cache`
                                       3 taps a channel, causal (z_t = 0
                                       for t < 0); ASSUMED: k_2 on the
                                       current token
      x' = x + (C * c) W_out
    `layer_types[l]` == "full_attention":
      q = u W_q (32 x 64), k = u W_k, v = u W_v (8 x 64), no biases
      q, k = N_qnorm(q), N_knorm(k)    over each head's 64 dimensions, one
                                       64-scale each shared by the heads,
                                       BEFORE RoPE                  ASSUMED
      q, k = RoPE(.; theta 1e6, half-split over all 64 dimensions,
                  absolute positions, no scaling)                  ASSUMED
      a_h = causal softmax(q_h . k_g(h) / 8) v_g(h), g(h) = h // 4
      x' = x + concat(a_h) W_o
    y = N_ffn(x')
    l < `num_dense_layers`:
      f = W_2(silu(y W_1) * (y W_3)), `intermediate_size` 11,776
    else:
      s = sigmoid(y W_r)               64 scores, float32, no router bias
      I = the 4 largest of s + b       b: the per-expert bias
                                       (`use_expert_bias`), SELECTION ONLY
      g_i = `routed_scaling_factor` 1 * s_i / (sum_{j in I} s_j + 1e-9)
                                       (`norm_topk_prob`; ASSUMED epsilon:
                                       the program's, see the file)
      f = sum_{i in I} g_i E_i(y), E = W_down(silu(y W_gate) * (y W_up)) at
          `moe_intermediate_size` 1,536; no shared expert
    x'' = x' + f

then a final RMSNorm and an untied head (ASSUMED). Weights arrive in the
type they are served in (bf16) and are upcast as they are used, a layer and
an expert at a time; attention goes a stretch of query rows at a time and
the MLPs a stretch of tokens at a time, so that an 18k-token sequence fits
beside the weights on the chip; the experts are a loop over all 64, each
multiplying EVERY token and keeping the rows routed to it (16 x the routed
multiplies: plain, not fast).

``control="fp8"`` computes the same pass one precision below bf16: every
matmul's inputs and weights rounded to fp8 e4m3 (absmax scales per row /
per output channel), as ``reference/glm47_flash.py`` does. It must come
out as not correct. ``control="bf16"`` rounds the same inputs to bf16, the
precision the configuration states: a witness that owes nothing to the
program.

The tree read is the layout the benchmark's weights are made in
(``weights_lfm2.py``): ``embed/embedding``, ``block<i>/{ln1/scale,
ln2/scale}``, in a convolution layer ``conv/{in_proj/kernel, taps,
out_proj/kernel}``, in an attention layer ``attn/{query,key,value}/kernel,
attn/{q_norm,k_norm}/scale, attn/out/kernel``; in the leading dense layers
``mlp_{gate,up,down}/kernel``, in the others ``moe/{router/kernel,
select_bias, w1 (gate), w3 (up), w2 (down)}``; ``ln_final/scale``,
``lm_head/kernel``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256     # query rows scored at once: [32, 256, S] float32
ROW_BLOCK = 2048  # tokens an MLP takes at once
PAD_TO = 2048     # a sequence is padded to a multiple: nine compiled
                  # lengths serve every sequence up to 18,432
HEAD_ROWS = 256   # logit rows made at once: [256, 65,536] float32
GATE_EPS = 1e-9   # the renormalised gates' epsilon (assumed.gate_epsilon)


def shape_of(cfg: dict) -> dict:
    heads = int(cfg["num_attention_heads"])
    return {"layers": int(cfg["num_hidden_layers"]),
            "layer_types": tuple(cfg["layer_types"]),
            "dense_layers": int(cfg["num_dense_layers"]),
            "embed": int(cfg["hidden_size"]),
            "heads": heads,
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["hidden_size"]) // heads,
            "taps": int(cfg["conv_L_cache"]),
            "dense_width": int(cfg["intermediate_size"]),
            "experts": int(cfg["num_experts"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "expert_width": int(cfg["moe_intermediate_size"]),
            "gate_scale": float(cfg["routed_scaling_factor"]),
            "vocab": int(cfg["vocab_size"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"]),
            "eps": float(cfg["norm_eps"])}


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


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def short_conv(x, p, ln_scale, *, eps, control=None):
    """``x + ShortConv(RMSNorm(x))`` over one sequence ``x [S, E]``: the
    whole sequence at once, ``z`` shifted down a row a tap."""
    u = _rms_norm(x, _f32(ln_scale), eps)
    b, c, v = jnp.split(_mm(u, _f32(p["in_proj"]["kernel"]), control), 3, -1)
    z = b * v
    taps = _f32(p["taps"])                     # [K, E]; taps[K-1] on z_t
    k = taps.shape[0]
    conv = sum(taps[j] * jnp.pad(z, ((k - 1 - j, 0), (0, 0)))[:z.shape[0]]
               for j in range(k))
    return x + _mm(c * conv, _f32(p["out_proj"]["kernel"]), control)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "control"))
def attention(x, p, ln_scale, *, heads, kv_heads, theta, eps, control=None):
    """``x + attention(RMSNorm(x))`` over one sequence ``x [S, E]`` (S a
    multiple of ``Q_BLOCK``): q/k norm a head, RoPE, full causal mask, one
    kv head for ``heads / kv_heads`` q heads."""
    s, e = x.shape
    u = _rms_norm(x, _f32(ln_scale), eps)

    def proj(name, n):
        w = _f32(p[name]["kernel"]).reshape(e, -1)
        return _mm(u, w, control).reshape(s, n, -1).transpose(1, 0, 2)

    q, k, v = proj("query", heads), proj("key", kv_heads), \
        proj("value", kv_heads)
    d = q.shape[-1]
    q = _rope(_rms_norm(q, _f32(p["q_norm"]["scale"]), eps), theta)
    k = _rope(_rms_norm(k, _f32(p["k_norm"]["scale"]), eps), theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=0)
    v = jnp.repeat(v, group, axis=0)
    pos = jnp.arange(s)

    def rows(i):  # a stretch of Q_BLOCK query rows against every key
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        scores = jnp.einsum("hqd,hkd->hqk", qi, k, precision=_HI) \
            / np.sqrt(d)
        weights = jax.nn.softmax(
            jnp.where(pos[None, :] <= t[:, None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", weights, v, precision=_HI)

    a = jax.lax.map(rows, jnp.arange(s // Q_BLOCK))    # [S/Q, H, Q, D]
    a = a.transpose(0, 2, 1, 3).reshape(s, -1)
    return x + _mm(a, _f32(p["out"]["kernel"]), control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def dense_mlp(x, p, *, eps, control=None):
    """``x + MLP(RMSNorm(x))``: a leading dense layer."""
    u = _rms_norm(x, _f32(p["ln2"]["scale"]), eps)
    gate, up, down = (_f32(p[n]["kernel"])
                      for n in ("mlp_gate", "mlp_up", "mlp_down"))
    return x + _by_rows(lambda r: _mm(
        jax.nn.silu(_mm(r, gate, control)) * _mm(r, up, control), down,
        control), u)


@functools.partial(jax.jit, static_argnames=(
    "top_k", "gate_scale", "eps", "control"))
def routed_mlp(x, ln_scale, moe, *, top_k, gate_scale, eps, control=None):
    """``x + sum_i g_i E_i(u)`` and the experts chosen ``I [S, top_k]``."""
    u = _rms_norm(x, _f32(ln_scale), eps)
    s = jax.nn.sigmoid(_mm(u, _f32(moe["router"]["kernel"]), control))
    _, index = jax.lax.top_k(s + _f32(moe["select_bias"]), top_k)
    chosen = jnp.take_along_axis(s, index, axis=-1)
    gates = gate_scale * chosen / (jnp.sum(chosen, -1, keepdims=True)
                                   + GATE_EPS)

    def expert(y, w):  # every token through expert w, kept where routed
        gate_e = jnp.sum(jnp.where(index == w["e"], gates, 0.0), axis=-1)
        hid = jax.nn.silu(_mm(u, _f32(w["w1"]), control)) \
            * _mm(u, _f32(w["w3"]), control)
        return y + gate_e[:, None] * _mm(hid, _f32(w["w2"]), control), None

    n = moe["w1"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x), {
        "w1": moe["w1"], "w3": moe["w3"], "w2": moe["w2"],
        "e": jnp.arange(n)})
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
        if s["layer_types"][i] == "conv":
            x = short_conv(x, p["conv"], p["ln1"]["scale"], eps=s["eps"],
                           control=control)
        else:
            x = attention(x, p["attn"], p["ln1"]["scale"], heads=s["heads"],
                          kv_heads=s["kv_heads"], theta=s["theta"],
                          eps=s["eps"], control=control)
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
    """``_row_stats`` over every row, ``HEAD_ROWS`` at a time."""
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
    ``reference/glm47_flash.served_gaps`` returns (``gaps``,
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
