"""Plain reference for the GPT-2 family: the forward pass in straightforward
``jax.numpy``, float32, matmuls at ``highest`` precision, no kernels, no
cache, no batching. Imports nothing of the program.

Follows Radford et al. 2019 (pre-LayerNorm blocks, learned positions,
``gelu_new``). Departure, stated in the configuration file: the LM head
is a matrix of its own with a bias (the program's ``GPT`` has no tied
head).

``control=`` computes the same pass in the nearest precision below bf16
(the step that would tempt a later PR): every matmul's weights and inputs
rounded to int8 (per output channel / per row, symmetric, absmax) or to
fp8 e4m3 (same scales); ``int8w`` rounds the weights alone. It is the
comparison's control and must come out as not correct.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST


def _fake_quant(x, axis, control):
    """Round ``x`` to the control's grid along ``axis`` (the contraction
    axis keeps one scale per slice across it)."""
    if control is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True) + 1e-30
    if control == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if control == "fp8":
        scale = amax / 448.0  # e4m3 finite max
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(f"unknown control {control!r}")


def _dense(x, w, b, control):
    """x [S, in] @ w [in, out] + b, both operands on the control's grid
    (``int8w``: the weights alone, as the program's own weight-only int8
    path stores them)."""
    if control == "int8w":
        w = _fake_quant(w, 0, "int8")
    else:
        x = _fake_quant(x, -1, control)
        w = _fake_quant(w, 0, control)
    return jnp.matmul(x, w, precision=_HI) + b


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _block(x, p, *, heads, eps, control):
    s, e = x.shape
    d = e // heads
    h = _layer_norm(x, p["ln1"], eps)
    qkv = [_dense(h, p["attn"][n]["kernel"].reshape(e, e),
                  p["attn"][n]["bias"].reshape(e), control)
           .reshape(s, heads, d).transpose(1, 0, 2)
           for n in ("query", "key", "value")]
    q, k, v = qkv
    scores = jnp.einsum("hqd,hkd->hqk", q, k, precision=_HI) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v,
                   precision=_HI)
    o = o.transpose(1, 0, 2).reshape(s, e)
    x = x + _dense(o, p["attn"]["out"]["kernel"], p["attn"]["out"]["bias"],
                   control)
    h = _layer_norm(x, p["ln2"], eps)
    h = _dense(h, p["mlp1"]["kernel"], p["mlp1"]["bias"], control)
    h = jax.nn.gelu(h, approximate=True)  # gelu_new
    return x + _dense(h, p["mlp2"]["kernel"], p["mlp2"]["bias"], control)


@functools.partial(jax.jit, static_argnames=("layers", "heads", "eps",
                                             "control"))
def forward_logits(params, tokens, rows, *, layers, heads, eps,
                   control=None):
    """Logits [len(rows), V] (float32) of one sequence ``tokens`` [S] at
    positions ``rows``. Padding after the sequence changes nothing before
    it (causal)."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = (f32(params["token_embed"]["embedding"])[tokens]
         + f32(params["pos_embed"])[0, :tokens.shape[0]])
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[params[f"block{i}"] for i in range(layers)])

    def body(x, p):
        return _block(x, f32(p), heads=heads, eps=eps,
                      control=control), None

    x, _ = jax.lax.scan(body, x, stacked)
    h = _layer_norm(x[rows], f32(params["ln_final"]), eps)
    return _dense(h, f32(params["lm_head"]["kernel"]),
                  f32(params["lm_head"]["bias"]), control)


def served_gaps(params, cfg: dict, prompt, served, width: int,
                max_rows: int, controls=(), temperature: float = 0.0,
                top_p=None) -> dict:
    """One reference pass over ``prompt + served``. Returns, per served
    token, the gap by which its logit lies below the reference's best
    (``gaps`` [n]) and — for each of ``controls`` — the gap, in the
    REFERENCE's logits, of the token that control puts first
    (``control_gaps[name]`` [n]). For a sampled stream (``temperature``
    above 0 with a ``top_p``) also ``nucleus_excess`` [n]: the reference's
    probability mass, at that temperature, of the tokens strictly more
    likely than the served one, less ``top_p`` — a token the nucleus
    filter let through reads below 0, to rounding."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, plen = served.size, prompt.size
    seq = np.zeros(width, np.int32)
    seq[:plen] = prompt
    seq[plen:plen + n - 1] = served[:-1]
    rows = np.full(max_rows, plen - 1, np.int32)
    rows[:n] = plen - 1 + np.arange(n)
    kw = dict(layers=int(cfg["n_layer"]), heads=int(cfg["n_head"]),
              eps=float(cfg["layer_norm_epsilon"]))
    ref = np.asarray(forward_logits(params, jnp.asarray(seq),
                                    jnp.asarray(rows), **kw))[:n]
    best = ref.max(axis=-1)
    at_served = ref[np.arange(n), served]
    out = {"gaps": best - at_served, "tokens": int(n), "control_gaps": {},
           "nucleus_excess": None}
    if temperature > 0 and top_p is not None:
        warped = ref.astype(np.float64) / float(temperature)
        p = np.exp(warped - warped.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
        likelier = warped > (at_served.astype(np.float64)
                             / float(temperature))[:, None]
        out["nucleus_excess"] = (p * likelier).sum(axis=-1) - float(top_p)
    for control in controls:
        low = np.asarray(forward_logits(params, jnp.asarray(seq),
                                        jnp.asarray(rows), control=control,
                                        **kw))[:n]
        first = low.argmax(axis=-1)
        out["control_gaps"][control] = best - ref[np.arange(n), first]
    return out
