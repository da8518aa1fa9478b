"""Operations and bytes the ALGORITHM needs for a decoder that alternates
gated short convolutions with grouped-query attention, a leading dense MLP
and sigmoid-routed experts (LFM2-24B-A2B), from the configuration's shapes
alone — the same whatever implements the work. Used by the
``*.lfm2_extract`` readers of ``model_mfu_pct``, ``shortconv_roofline_pct``,
``paged_attn_roofline_pct`` and ``moe_ffn_roofline_pct``. Peaks come from
``peaks.py``.

2 FLOPs a multiply-add. A token at position ``t`` (0-based) attends
``t + 1`` keys in every ATTENTION layer and none in a convolution layer,
whose reach is its ``conv_L_cache`` taps whatever the context.
"""

from __future__ import annotations

from chipbench.reference.lfm2 import shape_of


def layer_counts(cfg: dict) -> dict:
    s = shape_of(cfg)
    conv = sum(1 for t in s["layer_types"] if t == "conv")
    return {"conv": conv, "attn": s["layers"] - conv,
            "dense": s["dense_layers"],
            "routed": s["layers"] - s["dense_layers"]}


# ---------------------------------------------------- the short convolution
def shortconv_flops_per_token(cfg: dict) -> float:
    """One token through one convolution operator: the in projection
    (E -> 3E), the out projection (E -> E), and per channel the gate
    ``B * X``, ``K`` taps multiplied and summed, the gate ``C * c``."""
    s = shape_of(cfg)
    e, k = s["embed"], s["taps"]
    return 2.0 * (3 * e * e + e * e) + (2.0 * k + 1) * e


def shortconv_weight_bytes(cfg: dict, itemsize: int = 2) -> float:
    s = shape_of(cfg)
    e = s["embed"]
    return (4.0 * e * e + s["taps"] * e) * itemsize


def shortconv_bytes(cfg: dict, rows: int, states: int,
                    itemsize: int = 2) -> float:
    """One call of one convolution operator over ``rows`` tokens: its
    weights once, its input and output rows, and ``states`` states of
    ``K - 1`` rows read and written."""
    s = shape_of(cfg)
    e = s["embed"]
    return shortconv_weight_bytes(cfg, itemsize) \
        + (2.0 * rows * e + 2.0 * states * (s["taps"] - 1) * e) * itemsize


def shortconv_least_seconds(cfg: dict, chunk_calls, tick_rows,
                            peaks: dict) -> float:
    """Least time for the convolution operators of chunk programs of
    ``chunk_calls`` real tokens each (one state a call) and of ticks of
    ``tick_rows`` live rows each (a state a row): per call and layer the
    larger of the FLOPs over the peak and the bytes over the bandwidth.
    A 48-row tick is bound by the weights' bytes (33.6 MB a layer), a
    2,048-row chunk by its FLOPs (68.7 GFLOP a layer)."""
    def least(rows, states):
        return max(rows * shortconv_flops_per_token(cfg)
                   / peaks["bf16_flops_per_s"],
                   shortconv_bytes(cfg, rows, states)
                   / peaks["hbm_bytes_per_s"])

    return layer_counts(cfg)["conv"] * (
        sum(least(rows, 1) for rows in chunk_calls)
        + sum(least(rows, rows) for rows in tick_rows))


# ------------------------------------------------------------ attention
def attn_projection_flops_per_token(cfg: dict) -> float:
    """q, k, v and the out projection of one attention layer."""
    s = shape_of(cfg)
    return 2.0 * s["embed"] * s["head_dim"] * (2 * s["heads"]
                                               + 2 * s["kv_heads"])


def causal_keys(first: int, count: int) -> float:
    """Keys attended by ``count`` consecutive tokens from position
    ``first``, in one attention layer."""
    last = first + count
    return (last * (last + 1) - first * (first + 1)) / 2.0


def attn_flops(cfg: dict, keys: float) -> float:
    """q.k and p.v over ``keys`` (one layer): every q head."""
    s = shape_of(cfg)
    return 4.0 * s["heads"] * s["head_dim"] * keys


def kv_bytes_per_key(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one cached token in one attention layer: 2,048 B."""
    s = shape_of(cfg)
    return 2.0 * s["kv_heads"] * s["head_dim"] * itemsize


def paged_attn_least_seconds(cfg: dict, contexts, peaks: dict) -> float:
    """Least time for the decode attention of ticks whose live slots held
    ``contexts`` keys in reach (one entry a slot and tick), every
    attention layer: K and V of every key read once a layer, and the
    q.k / p.v FLOPs over them."""
    keys = layer_counts(cfg)["attn"] * sum(contexts)
    return max(keys * kv_bytes_per_key(cfg) / peaks["hbm_bytes_per_s"],
               attn_flops(cfg, keys) / peaks["bf16_flops_per_s"])


# ------------------------------------------------------------ the MLPs
def mlp_flops(cfg: dict, width: int) -> float:
    """One token through one gated MLP of ``width``: gate, up and down."""
    return 2.0 * 3 * shape_of(cfg)["embed"] * width


def mlp_bytes(cfg: dict, width: int, itemsize: int = 2) -> float:
    return 3.0 * shape_of(cfg)["embed"] * width * itemsize


def ffn_flops_per_token(cfg: dict) -> float:
    """Every layer's feed-forward work for one token: the dense layers'
    MLP; in a routed layer the router and ``top_k`` experts."""
    s = shape_of(cfg)
    n = layer_counts(cfg)
    routed = 2.0 * s["embed"] * s["experts"] \
        + s["top_k"] * mlp_flops(cfg, s["expert_width"])
    return n["dense"] * mlp_flops(cfg, s["dense_width"]) + n["routed"] * routed


def expected_experts_hit(cfg: dict, rows: float, shares=None) -> float:
    """Distinct experts ``rows`` tokens reach in one layer. ``shares``:
    each expert's share of the layer's routed pairs as the engine counted
    them (a token then draws expert ``e`` with probability ``top_k x
    share_e``); ``None``: every expert alike."""
    s = shape_of(cfg)
    n, k = s["experts"], s["top_k"]
    if shares is None:
        return n * (1.0 - (1.0 - k / n) ** rows)
    return float(sum(1.0 - (1.0 - min(1.0, k * p)) ** rows for p in shares))


def moe_least_seconds(cfg: dict, calls, peaks: dict, shares=None) -> float:
    """Least time for the routed layers' expert work of program calls of
    ``calls`` real tokens each: per call and layer the larger of the
    pairs' FLOPs over the peak and the bytes of the experts hit over the
    bandwidth. ``shares``: one list of per-expert shares a routed layer
    (:func:`expected_experts_hit`), or ``None``."""
    s = shape_of(cfg)
    layers = shares if shares else [None] * layer_counts(cfg)["routed"]
    total = 0.0
    for rows in calls:
        flops = rows * s["top_k"] * mlp_flops(cfg, s["expert_width"])
        for layer in layers:
            nbytes = expected_experts_hit(cfg, rows, layer) \
                * mlp_bytes(cfg, s["expert_width"])
            total += max(flops / peaks["bf16_flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"])
    return total


# --------------------------------------------------- the head, the sampler
def head_flops_per_row(cfg: dict) -> float:
    s = shape_of(cfg)
    return 2.0 * s["embed"] * s["vocab"]


def sampler_bytes_per_row(cfg: dict) -> float:
    """A sampled row's logits read once in float32 (the sort's passes
    over them are the implementation's, not the algorithm's)."""
    return 4.0 * shape_of(cfg)["vocab"]


# ----------------------------------------------------------- the whole step
def tokens_flops(cfg: dict, prefills, decodes) -> float:
    """Model FLOPs of a stretch of serving. ``prefills``: prompt lengths
    prefilled (each from position 0; one sampled row each); ``decodes``:
    the keys every decoded token attended (its context, itself included,
    as the harness's ``decode_ctx`` counts them)."""
    n = layer_counts(cfg)
    toks = sum(prefills) + len(decodes)
    per_tok = n["conv"] * shortconv_flops_per_token(cfg) \
        + n["attn"] * attn_projection_flops_per_token(cfg) \
        + ffn_flops_per_token(cfg)
    keys = sum(causal_keys(0, p) for p in prefills) + sum(decodes)
    return per_tok * toks + n["attn"] * attn_flops(cfg, keys) \
        + head_flops_per_row(cfg) * (len(prefills) + len(decodes))
