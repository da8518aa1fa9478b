"""SmallThinker weights from the seed, made by the benchmark (not by the
program) on the device, in the type they are served in (bf16). The same
tree goes to the system under test and to the plain reference.

The tree has the layout ``pddl_tpu.models.llama.Llama`` reads with
``moe_router_input="attn"`` (the one thing of the program's this module
knows): ``embed/embedding``, ``block<i>/{ln1/scale, router/kernel,
attn/{query,key,value,out}/kernel, ln2/scale, moe/{w1,w3,w2}}``,
``ln_final/scale``, ``lm_head/kernel``.

Initialisation (``assumed.weights`` in the configuration file): N(0, 0.02)
everywhere, residual projections (``attn/out``, ``moe/w2``) scaled by
1/sqrt(2 layers), norm scales 1 + N(0, 0.1). The router's N(0, 0.02) over
a unit-RMS input of width 2560 gives logits of standard deviation about 1:
the six largest of 64 are then spread (gates 0.08-0.4, none near 1) and
every expert is drawn about equally often, so top-6 is not degenerate.

One layer is drawn per jitted call (the same compiled function twelve
times): a whole-model draw would hold the random bits of 4.7 billion
expert weights at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import seed_key


def _std(cfg: dict) -> float:
    """0.02 at the published widths; a test at toy widths states a larger
    one (``initializer_range``), or its 40-wide model is all but linear
    and no fault moves it."""
    return float(cfg.get("initializer_range", 0.02))


def layer_shapes(cfg: dict) -> dict:
    e, d = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, w = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    std = _std(cfg)
    res = std / math.sqrt(2 * cfg["num_hidden_layers"])
    return {
        "ln1": {"scale": ((e,), 0.1, 1.0)},
        "ln2": {"scale": ((e,), 0.1, 1.0)},
        "router": {"kernel": ((e, n), std, 0.0)},
        "attn": {"query": {"kernel": ((e, h, d), std, 0.0)},
                 "key": {"kernel": ((e, hkv, d), std, 0.0)},
                 "value": {"kernel": ((e, hkv, d), std, 0.0)},
                 "out": {"kernel": ((h * d, e), res, 0.0)}},
        "moe": {"w1": ((n, e, w), std, 0.0), "w3": ((n, e, w), std, 0.0),
                "w2": ((n, w, e), res, 0.0)},
    }


def top_shapes(cfg: dict) -> dict:
    e, v = cfg["hidden_size"], cfg["vocab_size"]
    std = _std(cfg)
    return {"embed": {"embedding": ((v, e), std, 0.0)},
            "ln_final": {"scale": ((e,), 0.1, 1.0)},
            "lm_head": {"kernel": ((e, v), std, 0.0)}}


def _builder(spec, dtype):
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], tuple)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)

    @jax.jit
    def build(key):
        return jax.tree.unflatten(treedef, [
            (mean + std * jax.random.normal(jax.random.fold_in(key, i),
                                            shape, jnp.float32)
             ).astype(dtype)
            for i, (shape, std, mean) in enumerate(leaves)])

    return build


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """{"params": tree} on the default device."""
    key = seed_key(seed)
    tree = _builder(top_shapes(cfg), dtype)(jax.random.fold_in(key, 1 << 20))
    one_layer = _builder(layer_shapes(cfg), dtype)
    for i in range(int(cfg["num_hidden_layers"])):
        tree[f"block{i}"] = one_layer(jax.random.fold_in(key, i))
    return {"params": tree}
