"""Weights from the seed, made by the benchmark (not by the program) on
the device in ONE jitted call, in the type they are served in. The same
tree goes to the system under test and to the plain reference, so the
reference takes nothing the program has made.

The tree has the layout ``pddl_tpu.models.gpt.GPT`` reads (that layout is
the one thing of the program's this module knows): ``token_embed``,
``pos_embed``, ``block<i>/{ln1,attn/{query,key,value,out},ln2,mlp1,mlp2}``,
``ln_final``, ``lm_head``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number up to a little over 2**31 (more
    than 32 signed bits hold): low 31 bits seed it, the rest fold in."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def gpt_weight_shapes(cfg: dict) -> dict:
    e, h, v = cfg["n_embd"], cfg["n_head"], cfg["vocab_size"]
    d, inner = e // h, cfg.get("n_inner") or 4 * e
    # GPT-2's init: N(0, 0.02); residual projections scaled by
    # 1/sqrt(2 n_layer). Biases and LayerNorm parameters get a little
    # noise too (a trained model's are not 0 and 1), so that a path which
    # dropped one of them would read wrong.
    res = 0.02 / math.sqrt(2 * cfg["n_layer"])
    block = {
        "ln1": {"scale": ((e,), 0.1, 1.0), "bias": ((e,), 0.02, 0.0)},
        "ln2": {"scale": ((e,), 0.1, 1.0), "bias": ((e,), 0.02, 0.0)},
        "attn": {
            "query": {"kernel": ((e, h, d), 0.02, 0.0),
                      "bias": ((h, d), 0.02, 0.0)},
            "key": {"kernel": ((e, h, d), 0.02, 0.0),
                    "bias": ((h, d), 0.02, 0.0)},
            "value": {"kernel": ((e, h, d), 0.02, 0.0),
                      "bias": ((h, d), 0.02, 0.0)},
            "out": {"kernel": ((e, e), res, 0.0), "bias": ((e,), 0.02, 0.0)},
        },
        "mlp1": {"kernel": ((e, inner), 0.02, 0.0),
                 "bias": ((inner,), 0.02, 0.0)},
        "mlp2": {"kernel": ((inner, e), res, 0.0), "bias": ((e,), 0.02, 0.0)},
    }
    tree = {f"block{i}": block for i in range(cfg["n_layer"])}
    tree["token_embed"] = {"embedding": ((v, e), 0.02, 0.0)}
    tree["pos_embed"] = ((1, cfg["n_positions"], e), 0.01, 0.0)
    tree["ln_final"] = {"scale": ((e,), 0.1, 1.0), "bias": ((e,), 0.02, 0.0)}
    # Untied head (see configs/gpt2-large.json "assumed").
    tree["lm_head"] = {"kernel": ((e, v), 0.02, 0.0),
                       "bias": ((v,), 0.02, 0.0)}
    return tree


def make_gpt_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """{"params": tree} on the default device, one jitted call. Each kind
    of block leaf is drawn once for all layers and sliced (one random op
    per kind, not per layer: the per-layer version took minutes to
    compile)."""
    spec = gpt_weight_shapes(cfg)
    layers = int(cfg["n_layer"])
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    block_leaves, block_def = jax.tree.flatten(spec["block0"],
                                               is_leaf=is_leaf)
    rest = {k: v for k, v in spec.items() if not k.startswith("block")}
    rest_leaves, rest_def = jax.tree.flatten(rest, is_leaf=is_leaf)

    def draw(key, i, shape, std, mean):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        return (mean + std * x).astype(dtype)

    @jax.jit
    def build(key):
        stacked = [draw(key, i, (layers, *shape), std, mean)
                   for i, (shape, std, mean) in enumerate(block_leaves)]
        tree = jax.tree.unflatten(rest_def, [
            draw(key, 1000 + i, shape, std, mean)
            for i, (shape, std, mean) in enumerate(rest_leaves)])
        for layer in range(layers):
            tree[f"block{layer}"] = jax.tree.unflatten(
                block_def, [x[layer] for x in stacked])
        return tree

    return {"params": build(seed_key(seed))}
