"""GLM-4.7-Flash weights from the seed, made by the benchmark (not by the
program) on the device, in the type they are served in (bf16; the
selection bias float32). The same tree goes to the system under test and
to the plain reference.

The tree has the layout ``pddl_tpu.models.llama.Llama`` reads with latent
attention and the DeepSeek-V3 expert layer (the one thing of the program's
this module knows): ``embed/embedding``, ``block<i>/{ln1/scale,
attn/{q_down/kernel, q_norm/scale, q_up/kernel, kv_down/kernel,
kv_norm/scale, kv_up, out/kernel}, ln2/scale}`` and in the leading dense
layers ``mlp_{gate,up,down}/kernel``, in the others ``moe/{router/kernel,
select_bias, w1 (gate), w3 (up), w2 (down), shared_{gate,up,down}/kernel}``;
``ln_final/scale``, ``lm_head/kernel``.

Initialisation (``assumed.weights`` in the configuration file): N(0, 0.02)
everywhere, residual projections (``attn/out``, ``mlp_down``, ``moe/w2``,
``shared_down``) scaled by 1/sqrt(2 layers), norm scales 1 + N(0, 0.1).
The router's N(0, 0.02) over a unit-RMS input of width 2048 gives logits
of standard deviation about 0.9, so the sigmoid scores spread over 0.1 to
0.9. The selection bias is N(0, 0.1), wide enough beside those scores to
change which experts are chosen: a program that left it out, or added it
to the gates, reads wrong (a bias of zero would let either pass).

One layer is drawn per jitted call (one compiled function for the routed
layers): a whole-model draw would hold the random bits of 3.9 billion
weights at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key


def _std(cfg: dict) -> float:
    """0.02 at the published widths; a test at toy widths states a larger
    one (``initializer_range``), or its 32-wide model is all but linear
    and no fault moves it."""
    return float(cfg.get("initializer_range", 0.02))


def _res(cfg: dict) -> float:
    return _std(cfg) / math.sqrt(2 * cfg["num_hidden_layers"])


def attention_shapes(cfg: dict) -> dict:
    e, h = cfg["hidden_size"], cfg["num_attention_heads"]
    q_rank, rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    std = _std(cfg)
    return {"q_down": {"kernel": ((e, q_rank), std, 0.0)},
            "q_norm": {"scale": ((q_rank,), 0.1, 1.0)},
            "q_up": {"kernel": ((q_rank, h, nope + rope), std, 0.0)},
            "kv_down": {"kernel": ((e, rank + rope), std, 0.0)},
            "kv_norm": {"scale": ((rank,), 0.1, 1.0)},
            "kv_up": ((rank, h, nope + vd), std, 0.0),
            "out": {"kernel": ((h * vd, e), _res(cfg), 0.0)}}


def layer_shapes(cfg: dict, routed: bool) -> dict:
    e, std, res = cfg["hidden_size"], _std(cfg), _res(cfg)
    out = {"ln1": {"scale": ((e,), 0.1, 1.0)},
           "ln2": {"scale": ((e,), 0.1, 1.0)},
           "attn": attention_shapes(cfg)}
    if not routed:
        w = cfg["intermediate_size"]
        out.update(mlp_gate={"kernel": ((e, w), std, 0.0)},
                   mlp_up={"kernel": ((e, w), std, 0.0)},
                   mlp_down={"kernel": ((w, e), res, 0.0)})
        return out
    n, w = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    ws = cfg["n_shared_experts"] * w
    out["moe"] = {
        "router": {"kernel": ((e, n), std, 0.0)},
        "select_bias": ((n,), 0.1, 0.0, jnp.float32),
        "w1": ((n, e, w), std, 0.0), "w3": ((n, e, w), std, 0.0),
        "w2": ((n, w, e), res, 0.0),
        "shared_gate": {"kernel": ((e, ws), std, 0.0)},
        "shared_up": {"kernel": ((e, ws), std, 0.0)},
        "shared_down": {"kernel": ((ws, e), res, 0.0)}}
    return out


def top_shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    std = _std(cfg)
    return {"embed": {"embedding": ((v, e), std, 0.0)},
            "ln_final": {"scale": ((e,), 0.1, 1.0)},
            "lm_head": {"kernel": ((e, v), std, 0.0)}}


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
    dense = int(cfg["first_k_dense_replace"])
    builders = {False: _builder(layer_shapes(cfg, False), dtype),
                True: _builder(layer_shapes(cfg, True), dtype)}
    for i in range(int(cfg["num_hidden_layers"])):
        tree[f"block{i}"] = builders[i >= dense](jax.random.fold_in(key, i))
    return {"params": tree}
