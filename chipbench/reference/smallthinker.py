"""Plain reference for SmallThinker-21B-A3B: the forward pass in
straightforward ``jax.numpy``, float32, matmuls at ``highest`` precision,
a full mask, no kernels, no cache, no batching, no sorting of tokens.
Imports nothing of the program.

The layer, for tokens ``x [S, E]`` (configuration keys in backticks; every
item marked ASSUMED is one the public ``config.json`` does not settle and
is listed under ``assumed`` in the configuration file):

    h = RMSNorm(x; rms_norm_eps)
    r = h W_r                          W_r [E, 64], no bias. ASSUMED: the
                                       router reads the ATTENTION's normed
                                       input (described as "router placed
                                       before attention")
    q, k, v = h W_q, h W_k, h W_v      28 / 4 / 4 heads of head_dim 128;
                                       ASSUMED: no biases, no q/k norm
    rope_layout[l] == 1: q, k = RoPE(q, k; rope_theta, half-split over all
                         128 dims, absolute positions)   ASSUMED convention
                   == 0: nothing (no position encoding)
    a = causal attention, scale head_dim^-0.5, kv head g serves q heads
        7g..7g+6; sliding_window_layout[l] == 1: keys in (t - window, t]
    x' = x + a W_o
    u = RMSNorm(x'; rms_norm_eps)
    I = the 6 largest of r; g = softmax(r[I])
        (moe_primary_router_apply_softmax; norm_topk_prob is then the
        identity)
    y = sum_{e in I} g_e (relu(u W_gate,e) * (u W_up,e)) W_down,e
        ASSUMED: ReLU gate ("sparse ReGLU"), no biases, secondary experts off
    x'' = x' + y

then a final RMSNorm and an untied head. Weights arrive in the type they
are served in (bf16) and are upcast as they are used, a layer and an
expert at a time, so that the pass fits beside them on the chip; the
experts are a loop over all 64, each multiplying EVERY token and keeping
the rows routed to it (10.7 x the routed multiplies: plain, not fast).

``control=`` computes the same pass one precision below bf16: every
matmul's inputs and weights rounded to fp8 e4m3 (absmax scales per row /
per output channel), as ``reference/gpt2.py`` does. It must come out as
not correct.

The tree read is the layout the benchmark's weights are made in
(``weights_smallthinker.py``): ``embed/embedding``, ``block<i>/{ln1/scale,
router/kernel, attn/{query,key,value,out}/kernel, ln2/scale,
moe/{w1 (gate), w3 (up), w2 (down)}}``, ``ln_final/scale``,
``lm_head/kernel``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512     # query rows scored at once: [28, 512, S] float32
PAD_TO = 1024     # a sequence is padded to a multiple: a dozen compiled
                  # lengths serve every prompt up to 12,288


def shape_of(cfg: dict) -> dict:
    return {"layers": int(cfg["num_hidden_layers"]),
            "embed": int(cfg["hidden_size"]),
            "heads": int(cfg["num_attention_heads"]),
            "kv_heads": int(cfg["num_key_value_heads"]),
            "head_dim": int(cfg["head_dim"]),
            "experts": int(cfg["moe_num_primary_experts"]),
            "top_k": int(cfg["moe_num_active_primary_experts"]),
            "expert_width": int(cfg["moe_ffn_hidden_size"]),
            "window": int(cfg["sliding_window_size"]),
            "vocab": int(cfg["vocab_size"]),
            "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            # The file keeps the published 52-entry layouts whole; the
            # layers built read their first entries.
            "window_layout": tuple(
                cfg["sliding_window_layout"])[:int(cfg["num_hidden_layers"])],
            "rope_layout": tuple(
                cfg["rope_layout"])[:int(cfg["num_hidden_layers"])]}


def _fake_quant(x, axis, control):
    """Round ``x`` to the control's grid, one scale per slice across
    ``axis`` (the contraction axis)."""
    if control is None:
        return x
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


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "top_k", "theta", "eps", "control"))
def layer(x, p, window, rotate, *, heads, kv_heads, head_dim, top_k, theta,
          eps, control=None):
    """One layer over one sequence ``x [S, E]`` (S a multiple of
    ``Q_BLOCK``). ``window`` is the layer's window in tokens (a number
    past S for a full-attention layer), ``rotate`` whether it applies
    RoPE: both traced, so one compiled function serves every layer.
    Returns ``(x'', I [S, top_k])``."""
    f32 = lambda a: a.astype(jnp.float32)
    s, e = x.shape
    h = _rms_norm(x, f32(p["ln1"]["scale"]), eps)
    r = _mm(h, f32(p["router"]["kernel"]), control)               # [S, N]
    proj = lambda name, n: _mm(
        h, f32(p["attn"][name]["kernel"]).reshape(e, n * head_dim),
        control).reshape(s, n, head_dim).transpose(1, 0, 2)
    q, k, v = proj("query", heads), proj("key", kv_heads), \
        proj("value", kv_heads)
    q = jnp.where(rotate, _rope(q, theta), q)
    k = jnp.where(rotate, _rope(k, theta), k)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    pos = jnp.arange(s)

    def rows(i):  # a stretch of Q_BLOCK query rows against every key
        qi = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        t = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        scores = jnp.einsum("hqd,hkd->hqk", qi, k, precision=_HI) \
            / np.sqrt(head_dim)
        seen = (pos[None, :] <= t[:, None]) \
            & (pos[None, :] > t[:, None] - window)
        weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("hqk,hkd->hqd", weights, v, precision=_HI)

    a = jax.lax.map(rows, jnp.arange(s // Q_BLOCK))   # [S/Q, H, Q, D]
    a = a.transpose(0, 2, 1, 3).reshape(s, heads * head_dim)
    x = x + _mm(a, f32(p["attn"]["out"]["kernel"]), control)
    u = _rms_norm(x, f32(p["ln2"]["scale"]), eps)
    top, index = jax.lax.top_k(r, top_k)
    gates = jax.nn.softmax(top, axis=-1)

    def expert(y, w):  # every token through expert w, kept where routed
        gate_e = jnp.sum(jnp.where(index == w["e"], gates, 0.0), axis=-1)
        hid = jax.nn.relu(_mm(u, f32(w["w1"]), control)) \
            * _mm(u, f32(w["w3"]), control)
        return y + gate_e[:, None] * _mm(hid, f32(w["w2"]), control), None

    n = p["moe"]["w1"].shape[0]
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        dict(p["moe"], e=jnp.arange(n)))
    return x + y, index


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def head(x_rows, ln_scale, kernel, *, eps, control=None):
    f32 = lambda a: a.astype(jnp.float32)
    return _mm(_rms_norm(x_rows, f32(ln_scale), eps), f32(kernel), control)


def forward(params, cfg: dict, tokens, rows, control=None):
    """Logits ``[len(rows), V]`` (float32) of one sequence ``tokens [S]``
    at positions ``rows``, and every layer's expert sets ``[L, S, k]``.
    The sequence is padded to a multiple of ``PAD_TO``; padding after it
    changes nothing before it (causal)."""
    s = shape_of(cfg)
    tokens = np.asarray(tokens, np.int32)
    padded = -(-tokens.size // PAD_TO) * PAD_TO
    seq = np.zeros(padded, np.int32)
    seq[:tokens.size] = tokens
    x = params["embed"]["embedding"][jnp.asarray(seq)].astype(jnp.float32)
    sets = []
    for i in range(s["layers"]):
        window = s["window"] if s["window_layout"][i] else padded + 1
        x, index = layer(
            x, params[f"block{i}"], jnp.int32(window),
            jnp.bool_(s["rope_layout"][i]), heads=s["heads"],
            kv_heads=s["kv_heads"], head_dim=s["head_dim"],
            top_k=s["top_k"], theta=s["theta"], eps=s["eps"],
            control=control)
        sets.append(index[:tokens.size])
    logits = head(x[jnp.asarray(rows)], params["ln_final"]["scale"],
                  params["lm_head"]["kernel"], eps=s["eps"],
                  control=control)
    return logits, jnp.stack(sets)


def served_gaps(params, cfg: dict, prompt, served, max_rows: int,
                controls=(), temperature: float = 0.0, top_p=None) -> dict:
    """One reference pass over ``prompt + served``: what
    ``reference/gpt2.served_gaps`` returns (``gaps``, ``control_gaps``,
    ``nucleus_excess``, ``tokens``), and ``expert_sets [L, S, k]``, the
    reference's own routing of every position of the sequence (and
    ``control_sets[name]``, each control's)."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, plen = served.size, prompt.size
    seq = np.concatenate([prompt, served[:-1]])
    rows = np.full(max_rows, plen - 1, np.int32)
    rows[:n] = plen - 1 + np.arange(n)
    logits, sets = forward(params, cfg, seq, rows)
    ref = np.asarray(logits)[:n]
    best = ref.max(axis=-1)
    at_served = ref[np.arange(n), served]
    out = {"gaps": best - at_served, "tokens": int(n), "control_gaps": {},
           "nucleus_excess": None, "expert_sets": np.asarray(sets)}
    if temperature > 0 and top_p is not None:
        warped = ref.astype(np.float64) / float(temperature)
        p = np.exp(warped - warped.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        likelier = warped > (at_served.astype(np.float64)
                             / float(temperature))[:, None]
        out["nucleus_excess"] = (p * likelier).sum(axis=-1) - float(top_p)
    out["control_sets"] = {}
    for control in controls:
        low, low_sets = forward(params, cfg, seq, rows, control=control)
        first = np.asarray(low)[:n].argmax(axis=-1)
        out["control_gaps"][control] = best - ref[np.arange(n), first]
        out["control_sets"][control] = np.asarray(low_sets)
    return out
