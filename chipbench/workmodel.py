"""Operations and bytes the ALGORITHM needs, as functions of a
configuration's shapes — the same whatever implements the work. Used by
the ``*_mfu_pct`` and ``*_roofline_pct`` readers. (The arithmetic follows
``benchmarks/gpt_train_bench.py`` and ``benchmarks/decode_attribution.py``,
which keep peak constants of their own — ROADMAP D8; peaks here come from
``peaks.py`` alone.)
"""

from __future__ import annotations


def gpt_shape(cfg: dict) -> dict:
    e, h = int(cfg["n_embd"]), int(cfg["n_head"])
    inner = int(cfg.get("n_inner") or 4 * e)
    return {"layers": int(cfg["n_layer"]), "embed": e, "heads": h,
            "head_dim": e // h, "inner": inner,
            "vocab": int(cfg["vocab_size"])}


def gpt_matmul_flops_per_token(cfg: dict) -> float:
    """Dense matmul FLOPs one token needs in a forward pass: q, k, v and
    out projections (4 e^2), the MLP (2 e inner), per layer, plus the LM
    head (e vocab). 2 FLOPs per multiply-add."""
    s = gpt_shape(cfg)
    per_layer = 4 * s["embed"] ** 2 + 2 * s["embed"] * s["inner"]
    return 2.0 * (s["layers"] * per_layer + s["embed"] * s["vocab"])


def gpt_attn_flops(cfg: dict, context: float) -> float:
    """Attention FLOPs for ONE query token attending to ``context`` keys:
    q.k and p.v, every head, every layer."""
    s = gpt_shape(cfg)
    return 4.0 * s["layers"] * s["heads"] * s["head_dim"] * context


def gpt_kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> float:
    """K and V of one token across every layer, in the cache's dtype."""
    s = gpt_shape(cfg)
    return 2.0 * s["layers"] * s["heads"] * s["head_dim"] * itemsize


def gpt_tokens_flops(cfg: dict, decode_contexts_sum: float,
                     decode_tokens: int, prefill_tokens: int,
                     prefill_context_sum: float) -> float:
    """Model FLOPs of a stretch of serving: every decoded token (one
    forward at its context) and every prefilled prompt token (one forward
    at its causal position). ``*_context*_sum`` are the summed contexts
    the tokens attended to."""
    per_tok = gpt_matmul_flops_per_token(cfg)
    return (per_tok * (decode_tokens + prefill_tokens)
            + gpt_attn_flops(cfg, 1.0)
            * (decode_contexts_sum + prefill_context_sum))


def paged_attn_least_seconds(cfg: dict, contexts_sum: float, peaks: dict,
                             itemsize: int = 2) -> dict:
    """Least time the chip could take for the decode-attention work of
    ticks whose live slots held ``contexts_sum`` context tokens, summed
    over ticks (not layers; the kernel runs once per layer per tick):
    it must read each live token's K and V once per layer and do the
    q.k / p.v FLOPs. Returns both legs and which one bounds."""
    s = gpt_shape(cfg)
    per_layer_kv = 2.0 * s["heads"] * s["head_dim"] * itemsize
    bytes_needed = contexts_sum * per_layer_kv * s["layers"]
    flops_needed = gpt_attn_flops(cfg, 1.0) * contexts_sum
    t_bytes = bytes_needed / peaks["hbm_bytes_per_s"]
    t_flops = flops_needed / peaks["bf16_flops_per_s"]
    return {"seconds": max(t_bytes, t_flops),
            "bound": "bytes" if t_bytes >= t_flops else "flops",
            "bytes": bytes_needed, "flops": flops_needed}
