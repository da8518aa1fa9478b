"""Operations and bytes the ALGORITHM needs for a latent-attention decoder
with a leading dense layer and sigmoid-routed experts beside a shared one
(GLM-4.7-Flash), from the configuration's shapes alone — the same whatever
implements the work. Used by the ``*.glm_agent`` readers of
``model_mfu_pct``, ``mla_decode_roofline_pct``, ``mla_prefill_roofline_pct``
and ``moe_ffn_roofline_pct``. Peaks come from ``peaks.py``.

2 FLOPs a multiply-add. A token at position ``t`` (0-based) attends
``t + 1`` keys in every layer. Attention is counted in the form the
mathematics is cheapest in: a chunk of prefill per head (keys of ``nope +
rope``, values of ``v`` dimensions, expanded once a token), a decoded
token in the latent space (every head's score over the ``rank + rope``
values of a cached entry, its value over the first ``rank``). What a
chunk program re-expands of earlier chunks counts as time, not as work.
"""

from __future__ import annotations

from chipbench.reference.glm47_flash import shape_of


def routed_layers(cfg: dict) -> int:
    s = shape_of(cfg)
    return s["layers"] - s["dense_layers"]


def projection_flops_per_token(cfg: dict) -> float:
    """A layer's attention projections for one token: q down and up, the
    entry, the output, and ``W_ukv`` once — the per-head expansion of the
    token's own entry (prefill) or the two absorbed products of a decode
    step (``q_nope W_uk^T`` and ``o~ W_uv``), which cost the same."""
    s = shape_of(cfg)
    e, h = s["embed"], s["heads"]
    return 2.0 * (e * s["q_rank"] + s["q_rank"] * h * (s["nope"] + s["rope"])
                  + e * (s["kv_rank"] + s["rope"]) + h * s["v_dim"] * e
                  + s["kv_rank"] * h * (s["nope"] + s["v_dim"]))


def mlp_flops(cfg: dict, width: int) -> float:
    """One token through one gated MLP of ``width``: gate, up and down."""
    return 2.0 * 3 * shape_of(cfg)["embed"] * width


def mlp_bytes(cfg: dict, width: int, itemsize: int = 2) -> float:
    return 3.0 * shape_of(cfg)["embed"] * width * itemsize


def ffn_flops_per_token(cfg: dict) -> float:
    """Every layer's feed-forward work for one token: the dense layers'
    MLP; in a routed layer the router, ``top_k`` experts and the shared
    ones."""
    s = shape_of(cfg)
    routed = 2.0 * s["embed"] * s["experts"] \
        + (s["top_k"] + s["shared"]) * mlp_flops(cfg, s["expert_width"])
    return s["dense_layers"] * mlp_flops(cfg, s["dense_width"]) \
        + routed_layers(cfg) * routed


def head_flops_per_row(cfg: dict) -> float:
    s = shape_of(cfg)
    return 2.0 * s["embed"] * s["vocab"]


def causal_keys(first: int, count: int) -> float:
    """Keys attended by ``count`` consecutive tokens from position
    ``first``, in one layer."""
    last = first + count
    return (last * (last + 1) - first * (first + 1)) / 2.0


def prefill_attn_flops(cfg: dict, keys: float) -> float:
    """Per-head ``q . k`` and ``p . v`` over ``keys`` (one layer)."""
    s = shape_of(cfg)
    return 2.0 * s["heads"] * (s["nope"] + s["rope"] + s["v_dim"]) * keys


def absorbed_attn_flops(cfg: dict, keys: float) -> float:
    """Every head's score over an entry's ``rank + rope`` values and its
    value over the first ``rank`` (one layer): 43,520 a key at the
    published widths."""
    s = shape_of(cfg)
    return 2.0 * s["heads"] * (2 * s["kv_rank"] + s["rope"]) * keys


def entry_bytes(cfg: dict, itemsize: int = 2) -> float:
    """What one cached token weighs in one layer: ``rank + rope`` values
    (1,152 B), whatever the program pads it to."""
    s = shape_of(cfg)
    return (s["kv_rank"] + s["rope"]) * float(itemsize)


def tokens_flops(cfg: dict, prefills, decodes) -> float:
    """Model FLOPs of a stretch of serving. ``prefills``: prompt lengths
    prefilled (each from position 0; one sampled row each); ``decodes``:
    the keys every decoded token attended (its context, itself
    included, as the harness's ``decode_ctx`` counts them)."""
    s = shape_of(cfg)
    toks = sum(prefills) + len(decodes)
    per_tok = s["layers"] * projection_flops_per_token(cfg) \
        + ffn_flops_per_token(cfg)
    attn = s["layers"] * (
        prefill_attn_flops(cfg, sum(causal_keys(0, p) for p in prefills))
        + absorbed_attn_flops(cfg, sum(decodes)))
    return per_tok * toks + attn \
        + head_flops_per_row(cfg) * (len(prefills) + len(decodes))


def mla_decode_least_seconds(cfg: dict, contexts, peaks: dict) -> float:
    """Least time for the decode attention of ticks whose live slots held
    ``contexts`` keys in reach (one entry a slot and tick), every layer: the
    larger of the live entries' bytes over the bandwidth and the absorbed
    FLOPs over the peak."""
    s = shape_of(cfg)
    keys = s["layers"] * sum(contexts)
    return max(keys * entry_bytes(cfg) / peaks["hbm_bytes_per_s"],
               absorbed_attn_flops(cfg, keys) / peaks["bf16_flops_per_s"])


def expected_experts_hit(cfg: dict, rows: float, shares=None) -> float:
    """Distinct experts ``rows`` tokens reach in one layer. ``shares``:
    each expert's share of the layer's routed pairs as the engine counted
    them (a token then draws expert ``e`` with probability ``top_k x
    share_e``); ``None``: every expert alike. Random weights with a
    selection bias route unevenly (the busiest expert six times the
    mean), and an even draw would count weights that no token reads."""
    s = shape_of(cfg)
    n, k = s["experts"], s["top_k"]
    if shares is None:
        return n * (1.0 - (1.0 - k / n) ** rows)
    return float(sum(1.0 - (1.0 - min(1.0, k * p)) ** rows for p in shares))


def moe_least_seconds(cfg: dict, calls, peaks: dict, shares=None) -> float:
    """Least time for the routed layers' expert work (routed and shared)
    of program calls of ``calls`` real tokens each: per call and layer
    the larger of the pairs' FLOPs over the peak and the bytes of the
    experts hit, the shared ones among them, over the bandwidth.
    ``shares``: one list of per-expert shares a routed layer
    (:func:`expected_experts_hit`), or ``None``."""
    s = shape_of(cfg)
    layers = shares if shares else [None] * routed_layers(cfg)
    total = 0.0
    for rows in calls:
        flops = rows * (s["top_k"] + s["shared"]) \
            * mlp_flops(cfg, s["expert_width"])
        for layer in layers:
            nbytes = (expected_experts_hit(cfg, rows, layer) + s["shared"]) \
                * mlp_bytes(cfg, s["expert_width"])
            total += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return total
