"""LFM2-24B-A2B weights from the seed, made by the benchmark (not by the
program) on the device, in the type they are served in (bf16; the
selection bias float32). The same tree goes to the system under test and
to the plain reference.

The tree has the layout ``pddl_tpu.models.llama.Llama`` reads with
``layer_types`` (the one thing of the program's this module knows):
``embed/embedding``, ``block<i>/{ln1/scale, ln2/scale}``, in a convolution
layer ``conv/{in_proj/kernel, taps, out_proj/kernel}``, in an attention
layer ``attn/{query,key,value}/kernel, attn/{q_norm,k_norm}/scale,
attn/out/kernel``; in the leading dense layers ``mlp_{gate,up,down}/
kernel``, in the others ``moe/{router/kernel, select_bias, w1 (gate), w3
(up), w2 (down)}``; ``ln_final/scale``, ``lm_head/kernel``.

Initialisation (``assumed.weights`` in the configuration file): N(0, 0.02)
everywhere, residual projections (``conv/out_proj``, ``attn/out``,
``mlp_down``, ``moe/w2``) scaled by 1/sqrt(2 layers), norm scales (the
q/k norms' too) 1 + N(0, 0.1). The convolution's taps are N(1/3, 0.3): a
trained kernel is of order one, and the three taps must differ a channel
or reversing them would change nothing. The router's N(0, 0.02) over a
unit-RMS input of width 2048 gives logits of standard deviation about
0.9, so the sigmoid scores spread over 0.1 to 0.9, as in
``weights_glm47_flash.py``.

The selection bias (``assumed.selection_bias``): ``SELECT_BIAS_MEAN +
N(0, SELECT_BIAS_STD)``. The SPREAD decides which experts are chosen
(scores near the fourth place lie about 0.02 apart, so 0.015 re-orders
the fourth and fifth of two tokens in five: a program that drops the bias
routes wrong) and with them the load: small, so that no expert is much
busier than the rest. The common OFFSET changes no choice (the top four
of ``s + b`` do not move when every ``b`` does) and is what a checkpoint
leaves undetermined; it is set well away from zero so that a bias that
leaks into the gates changes them (``(s_i - 0.5) / sum (s_j - 0.5)`` is
not ``s_i / sum s_j``), which a spread of 0.015 alone would hide under
bf16's own rounding.

One layer is drawn per jitted call (one compiled function per kind of
layer): a whole-model draw would hold the random bits of 5.3 billion
weights at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key

SELECT_BIAS_MEAN = -0.5
SELECT_BIAS_STD = 0.015


def _std(cfg: dict) -> float:
    """0.02 at the published widths; a test at toy widths states a larger
    one (``initializer_range``), or its 32-wide model is all but linear
    and no fault moves it."""
    return float(cfg.get("initializer_range", 0.02))


def _res(cfg: dict) -> float:
    return _std(cfg) / math.sqrt(2 * cfg["num_hidden_layers"])


def operator_shapes(cfg: dict, kind: str) -> dict:
    e, std, res = cfg["hidden_size"], _std(cfg), _res(cfg)
    if kind == "conv":
        return {"conv": {
            "in_proj": {"kernel": ((e, 3 * e), std, 0.0)},
            "taps": ((int(cfg["conv_L_cache"]), e), 0.3, 1.0 / 3),
            "out_proj": {"kernel": ((e, e), res, 0.0)}}}
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = e // h
    return {"attn": {
        "query": {"kernel": ((e, h, d), std, 0.0)},
        "key": {"kernel": ((e, hkv, d), std, 0.0)},
        "value": {"kernel": ((e, hkv, d), std, 0.0)},
        "q_norm": {"scale": ((d,), 0.1, 1.0)},
        "k_norm": {"scale": ((d,), 0.1, 1.0)},
        "out": {"kernel": ((h * d, e), res, 0.0)}}}


def layer_shapes(cfg: dict, kind: str, routed: bool) -> dict:
    e, std, res = cfg["hidden_size"], _std(cfg), _res(cfg)
    out = {"ln1": {"scale": ((e,), 0.1, 1.0)},
           "ln2": {"scale": ((e,), 0.1, 1.0)},
           **operator_shapes(cfg, kind)}
    if not routed:
        w = cfg["intermediate_size"]
        out.update(mlp_gate={"kernel": ((e, w), std, 0.0)},
                   mlp_up={"kernel": ((e, w), std, 0.0)},
                   mlp_down={"kernel": ((w, e), res, 0.0)})
        return out
    n, w = cfg["num_experts"], cfg["moe_intermediate_size"]
    out["moe"] = {
        "router": {"kernel": ((e, n), std, 0.0)},
        "select_bias": ((n,), float(cfg.get("select_bias_std",
                                            SELECT_BIAS_STD)),
                        float(cfg.get("select_bias_mean",
                                      SELECT_BIAS_MEAN)), jnp.float32),
        "w1": ((n, e, w), std, 0.0), "w3": ((n, e, w), std, 0.0),
        "w2": ((n, w, e), res, 0.0)}
    return out


def top_shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    std = _std(cfg)
    return {"embed": {"embedding": ((v, e), std, 0.0)},
            "ln_final": {"scale": ((e,), 0.1, 1.0)},
            "lm_head": {"kernel": ((e, v), std, 0.0)}}


def model_shapes(cfg: dict) -> dict:
    """The whole tree as ``(shape, std, mean[, dtype])`` leaves."""
    dense = int(cfg["num_dense_layers"])
    tree = top_shapes(cfg)
    for i, kind in enumerate(cfg["layer_types"]):
        tree[f"block{i}"] = layer_shapes(cfg, kind, i >= dense)
    return tree


def _builder(spec, dtype):
    """A leaf is ``(shape, std, mean)`` or ``(shape, std, mean, dtype)``."""
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)

    @jax.jit
    def build(key):
        return jax.tree.unflatten(treedef, [
            (leaf[2] + leaf[1] * jax.random.normal(
                jax.random.fold_in(key, i), leaf[0], jnp.float32)
             ).astype(leaf[3] if len(leaf) > 3 else dtype)
            for i, leaf in enumerate(leaves)])

    return build


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """{"params": tree} on the default device."""
    key = seed_key(seed)
    tree = _builder(top_shapes(cfg), dtype)(jax.random.fold_in(key, 1 << 20))
    dense = int(cfg["num_dense_layers"])
    builders = {}
    for i, kind in enumerate(cfg["layer_types"]):
        which = (kind, i >= dense)
        if which not in builders:
            builders[which] = _builder(layer_shapes(cfg, *which), dtype)
        tree[f"block{i}"] = builders[which](jax.random.fold_in(key, i))
    return {"params": tree}
