"""Operations and bytes the ALGORITHM needs for a routed-expert,
window/full-attention decoder, from the configuration's shapes alone — the
same whatever implements the work. Used by the ``*.st_longdoc`` readers of
``model_mfu_pct``, ``moe_ffn_roofline_pct``, ``prefill_attn_roofline_pct``
and ``paged_attn_roofline_pct``. Peaks come from ``peaks.py``.

2 FLOPs a multiply-add. A token at position ``t`` (0-based) attends
``t + 1`` keys in a full-attention layer and ``min(t + 1, window)`` in a
window layer.
"""

from __future__ import annotations

from chipbench.reference.smallthinker import shape_of


def layer_kinds(cfg: dict):
    """(window layers, full-attention layers)."""
    s = shape_of(cfg)
    windowed = sum(1 for x in s["window_layout"] if x)
    return windowed, s["layers"] - windowed


def dense_flops_per_token(cfg: dict) -> float:
    """q, k, v, out projections and the router, every layer."""
    s = shape_of(cfg)
    qkvo = s["embed"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])
    return 2.0 * s["layers"] * (qkvo + s["embed"] * s["experts"])


def expert_flops_per_pair(cfg: dict) -> float:
    """One token through one gated expert: gate, up and down."""
    s = shape_of(cfg)
    return 2.0 * 3 * s["embed"] * s["expert_width"]


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    s = shape_of(cfg)
    return 3.0 * s["embed"] * s["expert_width"] * itemsize


def head_flops_per_row(cfg: dict) -> float:
    s = shape_of(cfg)
    return 2.0 * s["embed"] * s["vocab"]


def keys_seen(cfg: dict, first: int, count: int) -> float:
    """Keys attended, summed over layers, by ``count`` consecutive tokens
    from position ``first``."""
    s = shape_of(cfg)
    windowed, full = layer_kinds(cfg)
    last = first + count                     # positions first .. last-1
    tri = lambda a, b: (b * (b + 1) - a * (a + 1)) / 2.0   # sum (t+1)
    full_keys = tri(first, last)
    w = s["window"]
    below = min(max(w - first, 0), count)    # tokens still under the window
    window_keys = tri(first, first + below) + (count - below) * w
    return full * full_keys + windowed * window_keys


def attn_flops(cfg: dict, keys: float) -> float:
    """q.k and p.v over ``keys`` (already summed over layers): every q
    head."""
    s = shape_of(cfg)
    return 4.0 * s["heads"] * s["head_dim"] * keys


def tokens_flops(cfg: dict, prefills, decodes) -> float:
    """Model FLOPs of a stretch of serving. ``prefills``: prompt lengths
    prefilled (each from position 0; one sampled row each); ``decodes``:
    contexts (tokens already cached) of every decoded token."""
    s = shape_of(cfg)
    per_tok = dense_flops_per_token(cfg) \
        + s["layers"] * s["top_k"] * expert_flops_per_pair(cfg)
    toks = sum(prefills) + len(decodes)
    keys = sum(keys_seen(cfg, 0, p) for p in prefills) \
        + sum(keys_seen(cfg, c, 1) for c in decodes)
    return (per_tok * toks + attn_flops(cfg, keys)
            + head_flops_per_row(cfg) * (len(prefills) + len(decodes)))


def expected_experts_hit(cfg: dict, rows: float) -> float:
    """Distinct experts ``rows`` tokens reach in one layer when each draws
    its ``top_k`` of ``experts`` evenly (the weights are random)."""
    s = shape_of(cfg)
    n, k = s["experts"], s["top_k"]
    return n * (1.0 - (1.0 - k / n) ** rows)


def moe_least_seconds(cfg: dict, calls, peaks: dict) -> float:
    """Least time for the expert layers of program calls of ``calls``
    real tokens each: per call and layer the larger of the routed pairs'
    FLOPs over the peak and the bytes of the experts hit over the
    bandwidth."""
    s = shape_of(cfg)
    total = 0.0
    for rows in calls:
        flops = rows * s["top_k"] * expert_flops_per_pair(cfg)
        nbytes = expected_experts_hit(cfg, rows) * expert_bytes(cfg)
        total += s["layers"] * max(flops / peaks["bf16_flops_per_s"],
                                   nbytes / peaks["hbm_bytes_per_s"])
    return total


def paged_attn_least_seconds(cfg: dict, contexts, peaks: dict,
                             itemsize: int = 2) -> float:
    """Least time for the decode attention of ticks whose live slots held
    ``contexts`` tokens (one entry a slot and tick): K and V of every key
    in reach read once per layer, window layers counted at ``min(context,
    window)``, and the q.k / p.v FLOPs over them."""
    s = shape_of(cfg)
    keys = sum(keys_seen(cfg, c, 1) for c in contexts)
    nbytes = keys * 2.0 * s["kv_heads"] * s["head_dim"] * itemsize
    return max(nbytes / peaks["hbm_bytes_per_s"],
               attn_flops(cfg, keys) / peaks["bf16_flops_per_s"])
