"""Attention ops: reference softmax attention + a Pallas TPU flash kernel.

The reference repo has no attention at all (fixed 224x224 CNN inputs,
SURVEY.md §5 "Long-context": absent) — this module exists because
long-context support is first-class in the TPU build, not an afterthought.
It provides the single-device kernels; cross-device sequence parallelism
lives in :mod:`pddl_tpu.ops.ring_attention`.

Design:

- :func:`attention_reference` — straight jnp (materializes the [Sq, Sk]
  score matrix); numerics oracle for tests and the fallback path.
- :func:`flash_attention` — blockwise online-softmax Pallas kernel: scores
  never leave VMEM, HBM traffic is O(S·d) instead of O(S²), q/k/v blocks
  are MXU-tiled matmuls. Grid is (batch·heads, q_blocks, k_blocks) with the
  k dimension innermost: TPU grids execute sequentially, so running max /
  normalizer / accumulator persist in VMEM scratch across the k sweep.
- Backward: fully fused Pallas kernels as well. The forward additionally
  emits the log-sum-exp rows (lane-replicated, the standard TPU layout);
  the backward recomputes each score block from q/k + LSE in VMEM — never
  materializing the [S, S] probability matrix. Default: a SINGLE fused
  sweep producing dq/dk/dv together (5 MXU passes per block pair, dq
  accumulated across the k sweep in a sequence-sized VMEM scratch); when
  that scratch would not fit (very long sequences), two sweeps — a dq
  kernel (k innermost) and a dk/dv kernel (q innermost) — at 7 passes
  and a second operand read.

All shapes are ``[batch, heads, seq, head_dim]``; dtypes bf16/f32 in, f32
accumulation inside (MXU-native mixed precision).
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free


def _gqa_rep(q: jnp.ndarray, k: jnp.ndarray) -> int:
    """Query-heads-per-kv-head ratio; 1 for plain MHA.

    Grouped-query attention passes K/V with ``H_kv <= H`` heads; every
    kernel in this module consumes them UNEXPANDED (the q-head → kv-head
    mapping happens in index maps / reshapes), so GQA's bandwidth saving
    holds in training, not just in the decode cache.
    """
    hq, hkv = q.shape[-3], k.shape[-3]
    if hq == hkv:
        return 1
    if hq % hkv:
        raise ValueError(
            f"query heads {hq} not divisible by kv heads {hkv}")
    return hq // hkv


def attention_reference(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = False, scale: Optional[float] = None,
    k_offset: int = 0, window: Optional[int] = None,
) -> jnp.ndarray:
    """Plain softmax attention (the numerics oracle).

    ``k_offset`` shifts key/value global positions for causal masking —
    used by ring attention where each shard sees a rotated K/V slice.
    ``window`` (requires ``causal``): sliding-window attention — query t
    sees keys ``[t-window+1, t]`` (Mistral's SWA; window=1 is self-only).
    K/V may carry fewer heads than q (grouped-query attention): each kv
    head serves ``H/H_kv`` consecutive query heads, unexpanded.
    """
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (sliding-window "
                             "attention is a causal-LM construct)")
        if window < 1:
            # An empty band would make every row's scores equal (-1e30,
            # not -inf) and softmax silently uniform — raise like the
            # flash path instead.
            raise ValueError(f"window must be >= 1, got {window}")
    rep = _gqa_rep(q, k)
    if rep > 1:
        hkv = k.shape[-3]
        qg = q.reshape(*q.shape[:-3], hkv, rep, sq, d)
        s = jnp.einsum("...grqd,...gkd->...grqk", qg.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
    else:
        s = jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
    if causal:
        q_pos = jnp.arange(sq)[:, None]
        k_pos = jnp.arange(sk)[None, :] + k_offset
        mask = q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if rep > 1:
        o = jnp.einsum("...grqk,...gkd->...grqd", p, v.astype(jnp.float32))
        return o.reshape(q.shape).astype(q.dtype)
    return jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32)).astype(q.dtype)


# LSE/di rows are stored lane-replicated — shape [..., seq, LANES] — the
# standard Mosaic-friendly layout for per-row scalars (a bare [seq] column
# would fight the (sublane, lane) tiling).
LANES = 128


def _sequential_grid():
    """CompilerParams pinning sequential ('arbitrary') semantics on every
    grid dim. All four flash pallas_calls depend on sequential grid order
    for correctness: output blocks revisited along the innermost axis
    receive transient garbage writebacks that only the final visit's
    writes (later in grid order) overwrite, and the VMEM accumulators
    init on the first inner step / finalize on the last. Pinned
    explicitly so the assumption survives any change to the backend's
    default dimension semantics."""
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))


def _masked_scores(q_ref, k_ref, qi, ki, *, scale, causal, block_q, block_k,
                   window=None, k_offset=0):
    """Recompute one (bq, bk) score block: s = scale·q·kᵀ, causal-masked.

    Shared by the forward and both backward kernels so the mask/scale
    semantics can never drift between the p used forward and the p
    recomputed backward. ``k_offset`` (static) shifts every key's global
    position — ring attention's off-diagonal rotations see keys that are
    ``i·s_local`` positions earlier than their local index.
    """
    # Operands stay in their storage dtype (bf16 in training) with f32
    # accumulation: bf16xbf16 products are exact in f32, so this matches
    # an f32 matmul of the same (already-rounded) values while running on
    # the MXU's native bf16 path — the f32 path is ~4x slower per pass.
    s = jax.lax.dot_general(                              # (bq, bk) on MXU
        q_ref[0], k_ref[0],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if scale != 1.0:  # elided when the wrapper folded the scale into q
        s = scale * s
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = ki * block_k + k_offset + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
    return s


def _block_in_band(qi, ki, *, causal, block_q, block_k, window, k_offset=0):
    """Static-shape predicate: does block (qi, ki) intersect the causal
    (and, with ``window``, sliding-window) band? Shared by the forward
    and both backward sweeps so skip logic can never drift from the mask
    in :func:`_masked_scores` (same ``k_offset`` shift)."""
    run = True
    if causal:
        run = ki * block_k + k_offset <= qi * block_q + block_q - 1
        if window is not None:
            # block's max k_pos >= block's min q_pos - window + 1
            run &= (ki * block_k + block_k - 1 + k_offset
                    >= qi * block_q - window + 1)
    return run


# --------------------------------------------------------------- flash fwd
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int,
                  num_k: int, window=None, k_offset=0):
    """Forward kernel; ``lse_ref is None`` in the inference (no-vjp) variant,
    which then skips the LSE write entirely."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: skip blocks strictly above the diagonal; with a sliding
    # window, also blocks entirely below the band (compute drops from
    # O(S^2) to O(S*window) as S grows).
    run = _block_in_band(qi, ki, causal=causal, block_q=block_q,
                         block_k=block_k, window=window, k_offset=k_offset)

    @pl.when(run)
    def _compute():
        s = _masked_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                           block_q=block_q, block_k=block_k, window=window,
                           k_offset=k_offset)
        m_prev = m_ref[:, :1]                             # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                   # rescale old stats
        p = jnp.exp(s - m_new)                            # (bq, bk)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(                         # (bq, d) on MXU
            p.astype(v_ref.dtype), v_ref[0],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = m_ref[:] + jnp.log(jnp.maximum(l_ref[:], 1e-30))


# Measured (block_q, block_k) per TPU generation, keyed on
# jax device_kind. Only v5e has been benchmarked on hardware (see the
# flash_attention docstring); other generations inherit those values —
# safe everywhere (the f32 score block 512x1024x4B = 2 MB plus q/k/v/acc
# tiles sits well inside the ~16 MB/core VMEM on every generation) but
# not re-tuned. To tune a new chip: run benchmarks/attention_bench.py
# (it sweeps block pairs) and add the winner here.
# Head-dim note (round 5): the pair was originally tuned at D=64; a
# 7-pair fwd+bwd re-sweep at D=128 (B8 H16 S2048; taken on an earlier
# stack, its log is no longer kept, not re-measured since) found
# 512x1024 still best there (15.9 ms vs 16.6 for the 1024x1024
# runner-up), so the table needs no head_dim key.
TUNED_BLOCKS: dict[str, tuple[int, int]] = {
    "TPU v5 lite": (512, 1024),  # measured
    "TPU v5e": (512, 1024),      # measured (alternate kind string)
}
_DEFAULT_BLOCKS = (512, 1024)


def tuned_blocks(device=None) -> tuple[int, int]:
    """(block_q, block_k) for the local (or given) device's generation."""
    if device is None:
        device = jax.devices()[0]
    return TUNED_BLOCKS.get(getattr(device, "device_kind", ""),
                            _DEFAULT_BLOCKS)


def _resolve_blocks(block_q: Optional[int],
                    block_k: Optional[int]) -> tuple[int, int]:
    """Fill None block sizes from the local device's tuned pair."""
    if block_q is None or block_k is None:
        tq, tk = tuned_blocks()
        block_q = block_q if block_q is not None else tq
        block_k = block_k if block_k is not None else tk
    return block_q, block_k


def flash_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = False, scale: Optional[float] = None,
    window: Optional[int] = None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    fused_backward: bool = True,
) -> jnp.ndarray:
    """Flash attention, fused Pallas forward AND backward (see module docs).

    ``window`` (requires ``causal``) enables sliding-window attention
    (Mistral's SWA): query t attends to keys ``[t-window+1, t]``. Blocks
    entirely outside the band are skipped in the forward and both
    backward sweeps, so compute scales O(S*window) instead of O(S^2);
    ``window >= S`` degrades gracefully to plain causal.
    :func:`flash_attention_lse` accepts ``window`` too; only the
    ring/sequence-parallel wrapper rejects it.

    ``block_q``/``block_k`` default to the local device generation's tuned
    pair (:func:`tuned_blocks`; re-tune a new chip with
    ``benchmarks/attention_bench.py``). The v5e entry (512, 1024) was
    measured (B4 H16 D64 bf16 causal): fwd+bwd 12.5 ms at S=2048 vs
    17.8 ms for the fused-XLA reference and 5x faster than 128x128 blocks
    at S=8192 — where the reference's O(S²) scores no longer fit HBM at
    all. Shorter sequences clamp the blocks (``_largest_dividing_block``)
    and keep tiling down to S >= 8; below that (single-token decode, tiny
    test shapes) the reference fallback described above applies.

    Under ``jax.grad`` the forward additionally saves per-row LSE and the
    backward recomputes score blocks in VMEM (two fused kernels for dq and
    dk/dv) — the [S, S] matrices never reach HBM in either direction.
    Falls back to :func:`attention_reference` when shapes don't block-tile
    (tiny test shapes) — call sites never need to special-case.

    The fused backward is first-order only (a ``pallas_call`` has no AD
    rule): for higher-order differentiation — Hessian-vector products,
    gradient penalties — pass ``fused_backward=False`` to use the exact
    O(S²)-memory reference path, differentiable at any order.

    K/V may carry fewer heads than q (grouped-query attention). They are
    consumed UNEXPANDED: the kernels map each query head to its kv head
    in the block index maps, so no ``H/H_kv``-times K/V copy is ever
    materialized in HBM, forward or backward — dk/dv come back at kv-head
    shape, accumulated over the query group inside the kernel.
    """
    *_, sq, d = q.shape
    sk = k.shape[-2]
    _gqa_rep(q, k)  # validate head grouping before any dispatch
    scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
    window = _normalize_window(window, causal, sk)
    if not fused_backward:
        return attention_reference(q, k, v, causal=causal, scale=scale_v,
                                   window=window)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k = _resolve_blocks(block_q, block_k)
    bq = _largest_dividing_block(sq, block_q)
    bk = _largest_dividing_block(sk, block_k)
    if bq < 8 or bk < 8:
        # Degenerate tiling (e.g. prime-ish lengths): the kernel would run
        # sub-VPU-width blocks slower than one fused XLA softmax.
        return attention_reference(q, k, v, causal=causal, scale=scale_v,
                                   window=window)
    q, scale_v = _fold_scale(q, scale_v)
    return _flash(q, k, v, causal, scale_v, bq, bk, bool(interpret), window)


def _normalize_window(window: Optional[int], causal: bool, sk: int,
                      k_offset: int = 0) -> Optional[int]:
    """Validate a sliding-window width and clamp the trivial case.

    One definition shared by :func:`flash_attention` and
    :func:`flash_attention_lse` so the two entry points can never drift:
    window needs ``causal``, must be ``>= 1``, and ``window >= sk``
    degrades to plain causal (returned as None) — but only for aligned
    keys (``k_offset == 0``); offset keys sit further below the
    diagonal, where the band can still cut."""
    if window is None:
        return None
    if not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is a causal-LM construct)")
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    window = int(window)
    return None if (window >= sk and k_offset == 0) else window


def _fold_scale(q: jnp.ndarray, scale: float) -> tuple[jnp.ndarray, float]:
    """Fold a power-of-two softmax scale into q (bitwise-exact).

    Multiplying by 2^n is exponent arithmetic — no mantissa rounding in
    any binary float format — and scaling q before the dot distributes
    exactly over the f32 accumulation, so ``dot(q*scale, k)`` equals
    ``scale*dot(q, k)`` bit for bit. The win: the kernels skip one full
    VPU pass over every [block_q, block_k] score block in the forward and
    both backward sweeps (the ``scale != 1.0`` branches). The common
    ``1/sqrt(head_dim)`` is a power of two whenever head_dim is a power
    of four (64 -> 1/8, 256 -> 1/16); other scales stay in-kernel.
    """
    m, _ = math.frexp(scale)
    if m == 0.5:
        return q * jnp.asarray(scale, q.dtype), 1.0
    return q, scale


def _largest_dividing_block(n: int, want: int) -> int:
    """Largest block <= ``want`` that tiles ``n`` evenly AND that the
    TPU lowering accepts: a multiple of 8 (the sublane tile), or the
    whole dimension.

    Sequences shorter than the (large, v5e-tuned) defaults clamp to the
    full length and run as a single block — e.g. ViT's 196 tokens become
    one 196-wide block under want=512. Longer ones take their largest
    divisor that is a multiple of 8; where there is none (odd lengths
    like 543 = 3 x 181: interpret mode runs a 181-row block, the chip's
    compiler refuses it) this returns 1, and the ``bq < 8`` reference
    fallback at the call site fires — as it does for sequences shorter
    than 8 (decode steps, tiny test shapes)."""
    if n <= want:
        return n
    for b in range(want - want % 8, 7, -8):
        if n % b == 0:
            return b
    return 1


def _flash_kernel_nolse(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                        **kw):
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, None, m_ref, l_ref, acc_ref,
                  **kw)


def _sds_like(ref_value):
    """ShapeDtypeStruct factory that propagates the varying-manual-axes set
    of ``ref_value`` — inside shard_map (GPipe stages, seq-sharded regions)
    pallas outputs must declare how they vary across mesh axes."""
    vma = jax.typeof(ref_value).vma
    if vma:
        return functools.partial(jax.ShapeDtypeStruct, vma=vma)
    return jax.ShapeDtypeStruct


def _kv_index_map(h: int, hkv: int):
    """K/V BlockSpec index map over the flat ``b*h``-major grid axis.

    For GQA the K/V operands stay at ``[b*hkv, S, D]``; each q head's
    grid slot reads its group's kv head: flat kv index
    ``(batch)*hkv + (q_head)//rep``. MHA keeps the identity map (no
    scalar-core arithmetic on the hot path)."""
    if h == hkv:
        return lambda bh, qi, ki: (bh, ki, 0)
    rep = h // hkv
    return lambda bh, qi, ki: ((bh // h) * hkv + (bh % h) // rep, ki, 0)


def _flash_forward_call(q, k, v, causal, scale, block_q, block_k, interpret,
                        want_lse, window=None, k_offset=0):
    """Run the forward kernel; returns flat (out [bh,sq,d], lse or None).

    ``want_lse=False`` (inference / non-differentiated calls) uses a variant
    with no LSE output at all — a pallas_call output can't be DCE'd by XLA,
    so the [bh, sq, LANES] write must not exist rather than be unused.
    K/V may be grouped (``hkv < h``); they are consumed unexpanded via
    :func:`_kv_index_map`.
    """
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    sk = k.shape[-2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    num_q = pl.cdiv(sq, block_q)
    num_k = pl.cdiv(sk, block_k)
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    kernel = functools.partial(
        _flash_kernel if want_lse else _flash_kernel_nolse,
        scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k, window=window,
        k_offset=k_offset,
    )
    sds = _sds_like(qf)
    kv_map = _kv_index_map(h, hkv)

    o_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    lse_spec = pl.BlockSpec((1, block_q, LANES), lambda bh, qi, ki: (bh, qi, 0))
    result = pl.pallas_call(
        kernel,
        grid=(b * h, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[o_spec] + ([lse_spec] if want_lse else []),
        out_shape=[sds((b * h, sq, d), q.dtype)]
        + ([sds((b * h, sq, LANES), jnp.float32)] if want_lse else []),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running max m
            pltpu.VMEM((block_q, LANES), jnp.float32),  # running denom l
            pltpu.VMEM((block_q, d), jnp.float32),      # output accumulator
        ],
        compiler_params=_sequential_grid(),
        interpret=interpret,
    )(qf, kf, vf)
    if want_lse:
        return result[0], result[1]
    return result[0], None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, window=None,
           k_offset=0):
    b, h, sq, d = q.shape
    out, _ = _flash_forward_call(q, k, v, causal, scale, block_q, block_k,
                                 interpret, want_lse=False, window=window,
                                 k_offset=k_offset)
    return out.reshape(b, h, sq, d)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window=None, k_offset=0):
    b, h, sq, d = q.shape
    out, lse = _flash_forward_call(q, k, v, causal, scale, block_q, block_k,
                                   interpret, want_lse=True, window=window,
                                   k_offset=k_offset)
    # Residuals live from forward to backward — across every later layer's
    # forward. Keep LSE packed [bh, sq] for that window; the transient
    # lane-replicated buffer the kernel wrote is freed here.
    return out.reshape(b, h, sq, d), (q, k, v, out, lse[..., 0])


# --------------------------------------------------------------- flash bwd
#
# Standard two-sweep recomputation backward. With
#   p  = exp(scale·qkᵀ − lse),  dp = do·vᵀ,  di = Σ_d(do ⊙ o),
#   ds = p ⊙ (dp − di):
#   dq = scale · ds·k   dk = scale · dsᵀ·q   dv = pᵀ·do
# Each kernel recomputes its p block in VMEM from q/k + saved LSE; the [S,S]
# matrices never touch HBM.

def _block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, qi, ki,
                *, scale, causal, block_q, block_k, window, k_offset=0):
    """Recompute one block's (p, ds) — the shared first half of every
    backward kernel (masked scores → p from saved LSE → dp → ds). One
    definition so the fused single-sweep kernel and both two-sweep
    fallback kernels can never drift."""
    s = _masked_scores(q_ref, k_ref, qi, ki, scale=scale, causal=causal,
                       block_q=block_q, block_k=block_k, window=window,
                       k_offset=k_offset)
    p = jnp.exp(s - lse_ref[0][:, :1])                    # masked -> exactly 0
    dp = jax.lax.dot_general(                             # (bq, bk)
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    ds = p * (dp - di_ref[0][:, :1])
    return p, ds


def _scaled(x, scale):
    """Apply the softmax scale unless it was folded into q (== 1.0)."""
    return (scale * x) if scale != 1.0 else x


def _dq_contrib(ds, k_ref, scale):
    """ds·k → this block's dq rows (bq, d)."""
    return _scaled(jax.lax.dot_general(
        ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32), scale)


def _dk_contrib(ds, q_ref, scale):
    """dsᵀ·q → this block's dk rows (bk, d)."""
    return _scaled(jax.lax.dot_general(
        ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32), scale)


def _dv_contrib(p, do_ref):
    """pᵀ·do → this block's dv rows (bk, d)."""
    return jax.lax.dot_general(
        p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                         dq_ref, acc_ref,
                         *, scale: float, causal: bool, block_q: int,
                         block_k: int, num_k: int, window=None, k_offset=0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    run = _block_in_band(qi, ki, causal=causal, block_q=block_q,
                         block_k=block_k, window=window, k_offset=k_offset)

    @pl.when(run)
    def _compute():
        _, ds = _block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                            qi, ki, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k, window=window,
                            k_offset=k_offset)
        acc_ref[:] += _dq_contrib(ds, k_ref, scale)

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                          dk_ref, dv_ref, dk_acc_ref, dv_acc_ref,
                          *, scale: float, causal: bool, block_q: int,
                          block_k: int, num_q: int, inner_steps: int,
                          window=None, k_offset=0):
    """dk/dv sweep. The inner grid axis covers ``rep * num_q`` steps under
    GQA — all query heads of the kv head's group, q blocks innermost — so
    dk/dv accumulate the WHOLE group in scratch and each K/V block is
    fetched once per group instead of once per query head. ``qi`` is the
    per-head q-block index decoded from the flat inner step."""
    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % num_q  # per-q-head block index (t == qi for MHA)

    @pl.when(t == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    # Same band predicate as the forward, from the dkv grid's viewpoint:
    # above-diagonal OR fully-below-window blocks contribute nothing.
    run = _block_in_band(qi, ki, causal=causal, block_q=block_q,
                         block_k=block_k, window=window, k_offset=k_offset)

    @pl.when(run)
    def _compute():
        p, ds = _block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                            qi, ki, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k, window=window,
                            k_offset=k_offset)
        dv_acc_ref[:] += _dv_contrib(p, do_ref)
        dk_acc_ref[:] += _dk_contrib(ds, q_ref, scale)

    @pl.when(t == inner_steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                            dq_ref, dk_ref, dv_ref,
                            dq_acc_ref, dk_acc_ref, dv_acc_ref,
                            *, scale: float, causal: bool, block_q: int,
                            block_k: int, num_q: int, num_k: int,
                            inner_steps: int, window=None, k_offset=0):
    """Single-sweep fused backward: dq, dk, dv from ONE pass over the
    (k_block, q_block) grid.

    The two-sweep backward reads q/k/v/do twice and recomputes the score
    and dp matmuls in both kernels (7 MXU passes per block pair); here
    each block pair is visited once (5 passes) and the operands are read
    once per sweep. The price is a dq accumulator covering the WHOLE
    local sequence (``rep·S_q × D`` f32) living in VMEM scratch across
    the k sweep — the caller falls back to the two-sweep kernels when
    that does not fit (very long sequences).

    Grid: ``(b·hkv, num_k, rep·num_q)`` — same shape as the dkv sweep;
    dk/dv accumulate per (kv-head, k-block) across the inner axis, dq
    rows accumulate at ``t·block_q`` offsets across the OUTER k sweep
    and are emitted on its last iteration. dq output blocks mapped at
    earlier k iterations receive transient garbage writebacks that the
    final iteration's writes (later in sequential grid order)
    overwrite."""
    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % num_q

    @pl.when(jnp.logical_and(ki == 0, t == 0))
    def _init_dq():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    @pl.when(t == 0)
    def _init_dkv():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    run = _block_in_band(qi, ki, causal=causal, block_q=block_q,
                         block_k=block_k, window=window, k_offset=k_offset)

    @pl.when(run)
    def _compute():
        p, ds = _block_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                            qi, ki, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k, window=window,
                            k_offset=k_offset)
        dv_acc_ref[:] += _dv_contrib(p, do_ref)
        dk_acc_ref[:] += _dk_contrib(ds, q_ref, scale)
        rows = pl.ds(t * block_q, block_q)
        dq_acc_ref[rows, :] += _dq_contrib(ds, k_ref, scale)

    @pl.when(t == inner_steps - 1)
    def _finalize_dkv():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)

    @pl.when(ki == num_k - 1)
    def _finalize_dq():
        dq_ref[0] = dq_acc_ref[pl.ds(t * block_q, block_q), :].astype(
            dq_ref.dtype)


# dq accumulator budget for the fused single-sweep backward: rep·S_q·D
# f32 must sit in VMEM alongside the operand blocks (~16 MB/core total).
_FUSED_BWD_DQ_BYTES = 6 * 1024 * 1024


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, k_offset,
               res, g):
    return _flash_bwd_impl(causal, scale, block_q, block_k, interpret, res,
                           g, dlse=None, window=window, k_offset=k_offset)


def _flash_bwd_impl(causal, scale, block_q, block_k, interpret, res, g,
                    dlse=None, window=None, k_offset=0):
    """Shared fused backward. ``dlse`` (``[b, h, sq]`` or None) is the LSE
    output's cotangent for the (o, lse) variant: since
    d(lse)/d(s) = p, it enters every kernel as ``ds = p·(dp − di + dlse)``
    — folded here as ``di − dlse`` so the kernels stay untouched. dv has
    no lse term (lse is a function of q/k only)."""
    q, k, v, out, lse_packed = res
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    rep = h // hkv
    sk = k.shape[-2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, d)
    dof = g.reshape(b * h, sq, d)
    num_q = pl.cdiv(sq, block_q)
    num_k = pl.cdiv(sk, block_k)
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    # Re-expand packed LSE and compute di = rowsum(do ⊙ o), both
    # lane-replicated for the kernels (transient buffers, freed after the
    # two pallas calls; everything O(S²) stays inside the kernels).
    lse = jnp.broadcast_to(lse_packed[..., None], (b * h, sq, LANES))
    di_rows = jnp.sum(dof.astype(jnp.float32) * out.astype(jnp.float32),
                      axis=-1, keepdims=True)
    if dlse is not None:
        di_rows = di_rows - dlse.reshape(b * h, sq, 1).astype(jnp.float32)
    di = jnp.broadcast_to(di_rows, (b * h, sq, LANES))

    sds = _sds_like(qf)

    # Specs shared by the fused single-sweep backward and the dkv sweep
    # of the two-sweep fallback (grid (b·hkv, k_blocks, rep·q_blocks)).
    def _q_flat(bkv, t):
        if rep == 1:
            return bkv
        return (bkv // hkv) * h + (bkv % hkv) * rep + t // num_q

    qT_spec = pl.BlockSpec(
        (1, block_q, d), lambda bkv, j, t: (_q_flat(bkv, t), t % num_q, 0))
    rowT_spec = pl.BlockSpec(
        (1, block_q, LANES), lambda bkv, j, t: (_q_flat(bkv, t), t % num_q, 0))
    kT_spec = pl.BlockSpec((1, block_k, d), lambda bkv, j, t: (bkv, j, 0))

    if rep * sq * d * 4 <= _FUSED_BWD_DQ_BYTES:
        # Single fused sweep: 5 MXU passes per block pair instead of 7,
        # operands read once. See _flash_bwd_fused_kernel.
        dq, dk, dv = pl.pallas_call(
            functools.partial(
                _flash_bwd_fused_kernel, scale=scale, causal=causal,
                block_q=block_q, block_k=block_k, num_q=num_q,
                num_k=num_k, inner_steps=rep * num_q, window=window,
                k_offset=k_offset,
            ),
            grid=(b * hkv, num_k, rep * num_q),
            in_specs=[qT_spec, kT_spec, kT_spec, qT_spec, rowT_spec,
                      rowT_spec],
            out_specs=[qT_spec, kT_spec, kT_spec],
            out_shape=[
                sds((b * h, sq, d), q.dtype),
                sds((b * hkv, sk, d), k.dtype),
                sds((b * hkv, sk, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((rep * num_q * block_q, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            compiler_params=_sequential_grid(),
            interpret=interpret,
        )(qf, kf, vf, dof, lse, di)
        return (dq.reshape(b, h, sq, d), dk.reshape(b, hkv, sk, d),
                dv.reshape(b, hkv, sk, d))

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    row_spec = pl.BlockSpec((1, block_q, LANES), lambda bh, i, j: (bh, i, 0))
    kv_map = _kv_index_map(h, hkv)
    k_spec = pl.BlockSpec((1, block_k, d), lambda bh, i, j: kv_map(bh, i, j))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_k=num_k, window=window,
            k_offset=k_offset,
        ),
        grid=(b * h, num_q, num_k),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=sds((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_sequential_grid(),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, di)

    # dk/dv sweep: grid (b*hkv, k_blocks, rep*q_blocks) — the inner axis
    # runs q blocks innermost within each query head of the kv head's
    # group, so the k/v accumulators persist in scratch across the whole
    # group (dk/dv are SUMS over the group's query heads) and each K/V
    # block is read once per group, not once per query head.
    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q=num_q,
            inner_steps=rep * num_q, window=window, k_offset=k_offset,
        ),
        grid=(b * hkv, num_k, rep * num_q),
        in_specs=[qT_spec, kT_spec, kT_spec, qT_spec, rowT_spec, rowT_spec],
        out_specs=[kT_spec, kT_spec],
        out_shape=[
            sds((b * hkv, sk, d), k.dtype),
            sds((b * hkv, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_sequential_grid(),
        interpret=interpret,
    )(qf, kf, vf, dof, lse, di)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, hkv, sk, d),
            dv.reshape(b, hkv, sk, d))


_flash.defvjp(_flash_fwd, _flash_bwd)


# ------------------------------------------------------- (o, lse) variant
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
               window=None, k_offset=0):
    (o, lse), _ = _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k,
                                 interpret, window, k_offset)
    return o, lse


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                   window=None, k_offset=0):
    b, h, sq, d = q.shape
    out, lse = _flash_forward_call(q, k, v, causal, scale, block_q, block_k,
                                   interpret, want_lse=True, window=window,
                                   k_offset=k_offset)
    lse_rows = lse[..., 0]
    return ((out.reshape(b, h, sq, d), lse_rows.reshape(b, h, sq)),
            (q, k, v, out, lse_rows))


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, window,
                   k_offset, res, g):
    do, dlse = g
    return _flash_bwd_impl(causal, scale, block_q, block_k, interpret, res,
                           do, dlse=dlse, window=window, k_offset=k_offset)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _attention_reference_lse(q, k, v, causal, scale, window=None,
                             k_offset=0):
    """O(S²) (o, lse) fallback with the reference's exact masking.
    Supports grouped K/V like every other kernel in this module."""
    rep = _gqa_rep(q, k)
    if rep > 1:
        hkv = k.shape[-3]
        sq, d = q.shape[-2:]
        qg = q.reshape(*q.shape[:-3], hkv, rep, sq, d)
        s = scale * jnp.einsum("...grqd,...gkd->...grqk",
                               qg.astype(jnp.float32), k.astype(jnp.float32))
    else:
        s = scale * jnp.einsum("...qd,...kd->...qk", q.astype(jnp.float32),
                               k.astype(jnp.float32))
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        q_pos = jnp.arange(sq)[:, None]
        k_pos = jnp.arange(sk)[None, :] + k_offset
        mask = q_pos >= k_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    if rep > 1:
        o = jnp.einsum("...grqk,...gkd->...grqd", p, v.astype(jnp.float32))
        return (o.reshape(q.shape).astype(q.dtype),
                lse.reshape(*q.shape[:-1]))
    o = jnp.einsum("...qk,...kd->...qd", p, v.astype(jnp.float32))
    return o.astype(q.dtype), lse


def flash_attention_lse(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = False, scale: Optional[float] = None,
    window: Optional[int] = None, k_offset: int = 0,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """:func:`flash_attention` that ALSO returns per-row logsumexp.

    ``(o [B,H,S,D], lse [B,H,S])`` — the pair needed to merge partial
    attention over key/value blocks held elsewhere (ring attention's
    flash path): normalized partials combine as
    ``o = Σᵢ oᵢ·exp(lseᵢ − m) / Σᵢ exp(lseᵢ − m)``. Fully differentiable
    including through ``lse`` (the cotangent folds into the fused
    backward's row term). Falls back to an O(S²) reference when shapes
    don't tile, exactly like :func:`flash_attention`. Grouped K/V
    (``H_kv < H``) is supported unexpanded like everywhere else — this
    is what lets ring attention rotate kv-head-sized shards.

    ``k_offset`` (static) shifts the keys' global positions for the
    causal/window mask — ring attention's rotation ``i`` passes
    ``-i·s_local`` so each visiting shard masks at its true positions.
    """
    *_, sq, d = q.shape
    sk = k.shape[-2]
    _gqa_rep(q, k)  # validate head grouping before any dispatch
    scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
    window = _normalize_window(window, causal, sk, k_offset)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k = _resolve_blocks(block_q, block_k)
    bq = _largest_dividing_block(sq, block_q)
    bk = _largest_dividing_block(sk, block_k)
    if bq < 8 or bk < 8:
        return _attention_reference_lse(q, k, v, causal, scale_v, window,
                                        k_offset)
    q, scale_v = _fold_scale(q, scale_v)
    return _flash_lse(q, k, v, causal, scale_v, bq, bk, bool(interpret),
                      window, k_offset)


# ----------------------------------------------------------- decode sweep
# K-cache size (bytes, PER ARRAY — v doubles it) up to which a
# single-token decode step reads the WHOLE cache in one fused pass
# instead of the chunked loop. The loop's while/dynamic-slice machinery
# is a fixed per-layer cost; the extra read scales with batch x cache,
# so the gate is bytes-based. Measured in round 5 (an earlier stack;
# not re-measured since): at 0.5 MB/layer (llama-small GQA) single-shot
# wins ~8%; at 1.5 MB (GPT-small MHA) the prefix-bounded sweep wins ~7%
# at B1 and ~9% at B8 (benchmarks/decode_attribution.py). The crossover
# sits between, so the gate is 1 MB.
_SINGLE_SHOT_MAX_KC_BYTES = 1024 * 1024


def cache_blocks_gather(pool: jnp.ndarray, block_ids) -> jnp.ndarray:
    """Gather KV blocks ``block_ids [M]`` from a block-pool leaf
    ``[N, ..., block_size, D]`` into one contiguous batch-1 cache prefix
    ``[1, ..., M*block_size, D]`` (block ``j``'s tokens land at positions
    ``[j*block_size, (j+1)*block_size)``).

    The host tier's D2H read (`serve/engine.py` demotion and chain
    export): ``block_ids`` is a runtime int32 vector of FIXED length,
    so one compiled program serves every chain depth — callers pad
    short chains with the reserved scratch block (id 0), whose junk
    lands in tail slices they do not take. The gather COPIES, so the
    host payload never aliases pool storage.
    """
    block_ids = jnp.asarray(block_ids, jnp.int32)
    if block_ids.ndim != 1:
        raise ValueError(f"block_ids must be [M], got {block_ids.shape}")
    if pool.ndim < 3:
        raise ValueError(
            f"pool leaf must be [N, ..., block_size, D], got {pool.shape}")
    m = block_ids.shape[0]
    bs, d = pool.shape[-2], pool.shape[-1]
    g = jnp.take(pool, block_ids, axis=0)      # [M, ..., bs, D]
    g = jnp.moveaxis(g, 0, -3)                 # [..., M, bs, D]
    return g.reshape(g.shape[:-3] + (m * bs, d))[None]


def cache_blocks_scatter(pool: jnp.ndarray, row: jnp.ndarray, block_ids,
                         start_block) -> jnp.ndarray:
    """Write a batch-1 cache row's tokens
    ``[start_block*block_size, (start_block+M)*block_size)`` into pool
    blocks ``block_ids [M]`` of a ``[N, ..., block_size, D]`` leaf — the
    host tier's H2D promotion (`serve/engine.py` ``host_promote``: a
    demoted chain's K/V becomes shared, immutable pool blocks again).

    ``start_block`` is a traced int32 block index; ``block_ids`` is a
    fixed-length runtime vector (pad with the scratch block 0 — its
    content is junk by contract and never reachable through the radix
    index). Out-of-range source positions are clamped per token rather
    than shifting the whole slice, so padded tail blocks read junk
    without corrupting the real blocks' mapping.
    """
    block_ids = jnp.asarray(block_ids, jnp.int32)
    if block_ids.ndim != 1:
        raise ValueError(f"block_ids must be [M], got {block_ids.shape}")
    if row.shape[0] != 1 or row.ndim != pool.ndim:
        raise ValueError(
            f"row {row.shape} is not a batch-1 cache leaf matching pool "
            f"{pool.shape}")
    m = block_ids.shape[0]
    bs, d = pool.shape[-2], pool.shape[-1]
    pos = jnp.asarray(start_block, jnp.int32) * bs + jnp.arange(m * bs)
    window = jnp.take(row[0], jnp.minimum(pos, row.shape[-2] - 1), axis=-2)
    blocks = window.reshape(window.shape[:-2] + (m, bs, d))
    blocks = jnp.moveaxis(blocks, -3, 0)       # [M, ..., bs, D]
    return pool.at[block_ids].set(blocks.astype(pool.dtype))


# ------------------------------------------------------ paged decode
# True paged attention (vLLM PagedAttention, SOSP '23): decode reads
# K/V straight out of the serving engine's block POOL through a
# per-slot block-table indirection, so a shared prompt prefix exists
# ONCE in HBM no matter how many live requests reference it and
# admission never copies pool blocks into a resident row. Three ops:
#
# - :func:`paged_cache_insert` — write the current token(s) of every
#   slot into its table-mapped pool block (the paged twin of the
#   row-cache dynamic_update_slice writes in the decode modules).
# - :func:`paged_decode_attention` — attention over the pool through
#   the table. The jnp path (chunked gather + online softmax, HBM
#   traffic bounded by the deepest live slot exactly like
#   :func:`decode_attention`) is the CPU/tier-1 numerics ORACLE; the
#   Pallas path (:func:`paged_decode_attention_kernel`) is the TPU
#   hot-path kernel — the block table and the depths ride in SMEM via
#   scalar prefetch, the pool stays in HBM, and each slot's grid step
#   walks the table entries the slot's depth (and window) reaches, K
#   pool blocks a loop step: K block copies into one of two VMEM
#   buffers, the next group in flight under the current one's online
#   softmax. A table entry no key of which the slot can see is neither
#   visited nor fetched.
#
# THE POOL LEAF. One leaf per layer, ``[num_blocks, cache_heads,
# block_size, lanes]``: one ENTRY of ``lanes`` values a token and a cache
# head, as the layer declares it. The ops here know two things of an
# entry: its KEY is lanes ``[0, Dk)``, ``Dk`` the width of the query it
# is scored against, and its VALUE is the lanes the caller names
# (``value_lanes``). A K/V layer stores K and V side by side, ``lanes =
# 2 * head_dim``, K in ``[0, D)`` and V in ``[D, 2D)``
# (:func:`paged_kv_fuse`, the default). A latent-attention layer stores
# ONE entry for all its heads, ``[c_kv | k_rope]``: the whole entry is
# the key of the absorbed query and its first ``kv_lora_rank`` lanes are
# the value (`models/llama.LatentAttention`). Side by side, and not two
# leaves, because of the layout the leaf has AT
# REST, between programs, which nothing in the engine chooses: a jitted
# program receives and hands back every argument in the chip's default
# layout for its shape. For separate 64-wide leaves
# (``bf16[3073,20,16,64]``, GPT-2-large's) that default is
# ``{0,3,2,1:T(8,128)(2,1)}`` — the block index minor-most, since a
# 64-wide minor dimension would waste half of every 128-lane tile —
# while the Mosaic kernel demands row-major ``{3,2,1,0}`` and so do the
# whole-block gather and scatter; every program therefore relayouted
# every leaf on the way in and again on the way out (three pool-sized
# copies a leaf in the tick: 75 % of the chip's time, PERF.md §6 PR
# 29). Pinning the 64-wide leaf row-major is no way out: its 64 lanes
# pad to 128, 251.7 MB a leaf for 125.9 MB of data, 72 leaves = 18.1 GB
# on a 16 GB chip. Fused, the minor dimension is 128 (or 256) lanes,
# the default layout IS row-major and unpadded (36 leaves x 251.7 MB =
# 9.06 GB, the same bytes as the 72 before), and the kernel, the chunk
# path and the writes all work on it in place.
# ``tests/test_tpu_aot_compile.py`` holds the engine's compiled tick
# and chunk programs to that: no pool-sized copy, every leaf aliased.
#
# Every WRITE is block-granular for the same reason: a scatter that
# indexes (block, offset) with heads and lanes as its window makes XLA
# give the operand a third layout (``{3,1,2,0}``) and copy the pool in
# and out around it; a gather of whole blocks, a splice, and a scatter
# of whole blocks on dimension 0 alone keep the leaf where it is.
#
# Safety contract shared with `serve/kvcache/block_pool.py`: block 0
# is the reserved scratch sink — parked slots' table rows are all
# scratch, junk writes land there, and masked reads never reach past
# a slot's position counter, so scratch content is junk by
# construction and harmless by masking.


# Rows of a multi-token chunk the jnp sweep takes at once (see
# `paged_decode_attention`): every chunk width the engines had before
# the 12k-wide one is under it, so their programs are unchanged.
PAGED_SWEEP_MAX_ROWS = 2048


def paged_kv_fuse(k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """K and V ``[..., D]`` side by side as the pool leaf stores them:
    ``[..., 2D]``, K in lanes ``[0, D)``, V in ``[D, 2D)``."""
    if k.shape != v.shape:
        raise ValueError(f"k {k.shape} and v {v.shape} differ")
    return jnp.concatenate([k, v], axis=-1)


def _entry_lanes(pool: jnp.ndarray, dk: int, value_lanes):
    """``(v0, v1)``, the value lanes of a pool leaf's entries whose key
    is lanes ``[0, dk)``; ``None`` is the K/V leaf's ``[dk, 2 dk)``."""
    v0, v1 = (dk, 2 * dk) if value_lanes is None else map(int, value_lanes)
    lanes = pool.shape[-1]
    if pool.ndim != 4 or not (dk <= lanes and 0 <= v0 < v1 <= lanes):
        raise ValueError(
            f"pool leaf must be [N, H_c, block_size, lanes] with the key "
            f"in lanes [0, {dk}) and the value in [{v0}, {v1}), got "
            f"{pool.shape}")
    return v0, v1


def paged_cache_insert(pool: jnp.ndarray, entry: jnp.ndarray,
                       block_table, index) -> jnp.ndarray:
    """Write the entries ``entry [B, H_c, s, lanes]`` (a K/V layer's
    :func:`paged_kv_fuse`, a latent layer's ``[c_kv | k_rope]``) at
    global positions ``index (+ arange(s))`` into pool blocks resolved
    through ``block_table [B, T]`` (``pool [N, H_c, block_size, lanes]``).

    ``index`` is a scalar (batch-1 chunk prefill at a traced offset) or
    a per-row ``[B]`` vector (the serving tick: every slot writes one
    token at its own depth; the speculative verify: ``s`` tokens).
    Positions whose block index falls outside the table are deflected
    to the scratch block — padded prefill junk beyond a prompt's
    allocated blocks can never reach a real block. Distinct valid
    positions map to distinct (block, offset) pairs and a slot writes
    only blocks private to it (the engine's table discipline), so the
    scatter has no write conflicts except on scratch, whose content is
    junk by contract.

    Every shape works at BLOCK granularity (the section header says
    why): read the blocks each row's span touches, splice the new
    tokens in, scatter whole blocks back on dimension 0. A block of the
    span the tokens do not reach is written back as it was read.
    """
    if pool.ndim != 4 or entry.ndim != 4 \
            or entry.shape[1::2] != pool.shape[1::2]:
        raise ValueError(
            f"entries {entry.shape} do not fit the pool leaf {pool.shape} "
            "([N, H_c, block_size, lanes])")
    n, hkv, bs, d2 = pool.shape
    b, _, s, _ = entry.shape
    block_table = jnp.asarray(block_table, jnp.int32)
    if block_table.ndim != 2 or block_table.shape[0] != b:
        raise ValueError(
            f"block_table must be [B={b}, T], got {block_table.shape}")
    t = block_table.shape[1]
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    kv = entry.astype(pool.dtype)
    n_span = (bs + s - 2) // bs + 1     # most blocks s tokens can touch
    span = (index // bs)[:, None] + jnp.arange(n_span)         # [B, n_span]
    ids = jnp.where(
        span < t,
        jnp.take_along_axis(block_table, jnp.minimum(span, t - 1), axis=1),
        0).reshape(-1)                                # off-table -> scratch
    blocks = jnp.take(pool, ids, axis=0)              # [B*n_span, Hkv, bs, 2D]
    flat = jnp.moveaxis(blocks.reshape(b, n_span, hkv, bs, d2), 1, 2) \
        .reshape(b, hkv, n_span * bs, d2)
    off = index % bs
    if b == 1:
        flat = jax.lax.dynamic_update_slice(flat, kv, (0, 0, off[0], 0))
    else:
        rel = jnp.arange(n_span * bs) - off[:, None]              # [B, P]
        new = kv if s == 1 else jnp.take_along_axis(
            kv, jnp.clip(rel, 0, s - 1)[:, None, :, None], axis=2)
        flat = jnp.where(((rel >= 0) & (rel < s))[:, None, :, None],
                         new, flat)
    blocks = jnp.moveaxis(flat.reshape(b, hkv, n_span, bs, d2), 2, 1) \
        .reshape(b * n_span, hkv, bs, d2)
    return pool.at[ids].set(blocks)


def paged_decode_attention(
    q: jnp.ndarray, kv_pool: jnp.ndarray,
    block_table, index, *, window: Optional[int] = None,
    scale: Optional[float] = None, blocks_per_chunk: Optional[int] = None,
    kernel: Optional[bool] = None, interpret: Optional[bool] = None,
    value_lanes: Optional[tuple] = None, expand=None,
):
    """Attention over a paged KV pool through a per-slot block table.

    Semantically :func:`decode_attention` over the VIRTUAL cache
    ``cache[b, :, j*bs + o] == pool[block_table[b, j], :, o]`` — same
    masking, same online softmax, same prefix-bounded sweep — but the
    per-request cache rows never exist contiguously: the pool IS the
    storage and the table is the only per-slot state.

    Args:
      q: ``[B, H, s, Dk]`` post-RoPE queries (``s == 1`` on the decode
        tick; ``s > 1`` for chunked prefill continuing at ``index``).
      kv_pool: the ``[N, H_c, block_size, lanes]`` pool leaf (an
        entry's key in lanes ``[0, Dk)``); the current tokens must
        already be written (:func:`paged_cache_insert` runs first, like
        the row path).
      value_lanes: ``(start, stop)``, the lanes of an entry that are its
        value; ``None`` is the K/V leaf's ``[Dk, 2 Dk)``. The result is
        ``stop - start`` wide.
      expand: jnp path only. ``expand(entries [B, H_c, keys, lanes]) ->
        (k [B, H, keys, Dk], v [B, H, keys, Dv])`` turns a gathered
        stretch of entries into per-head keys and values inside the
        sweep (a latent layer's chunk prefill: ``q`` is then the
        per-head query and ``value_lanes`` is not read).
      block_table: ``[B, T]`` int32 pool block ids; entries beyond a
        slot's depth are scratch (never read — masked).
      index: tokens in the (virtual) cache before this call; scalar or
        per-row ``[B]``.
      window: sliding-window mask (non-rolling only — ring caches are
        not paged).
      blocks_per_chunk: table entries visited per sweep iteration on
        the jnp path. Default (``None``): ~512 cache tokens per
        iteration for single-token steps and ~256 for multi-token
        chunks — the same sweep widths :func:`decode_attention` uses,
        measured to amortize the gather/loop overhead on CPU without
        blowing up the per-iteration score block.
      kernel: ``True`` forces the Pallas kernel (decode steps only,
        ``s == 1``), ``False`` the jnp reference, ``None`` (default)
        picks the kernel on TPU and the reference elsewhere.
      interpret: Pallas interpret mode (defaults to non-TPU backends).

    Returns ``[B, H, s, Dv]`` in q's dtype.
    """
    b, h, s, d = q.shape
    if kv_pool.ndim != 4:
        raise ValueError(
            f"pool leaf must be [N, H_c, block_size, lanes], got "
            f"{kv_pool.shape}")
    n, hkv, bs, d2 = kv_pool.shape
    if expand is None:
        v0, v1 = _entry_lanes(kv_pool, d, value_lanes)
        groups, rep, dv = hkv, _gqa_rep(q, kv_pool), v1 - v0
    else:  # expanded keys and values are per q head: no grouping is left
        groups, rep, dv = h, 1, jax.eval_shape(
            expand, jax.ShapeDtypeStruct((b, hkv, bs, d2), kv_pool.dtype)
        )[1].shape[-1]
    block_table = jnp.asarray(block_table, jnp.int32)
    if block_table.ndim != 2 or block_table.shape[0] != b:
        raise ValueError(
            f"block_table must be [B={b}, T], got {block_table.shape}")
    t = block_table.shape[1]
    index = jnp.asarray(index, jnp.int32)
    if index.ndim > 1 or (index.ndim == 1 and index.shape[0] != b):
        raise ValueError(
            f"index must be a scalar or [B]={b} vector, got {index.shape}")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
    if kernel is None:
        kernel = jax.default_backend() == "tpu" and s == 1
    if kernel:
        if s != 1:
            raise ValueError(
                "the paged Pallas kernel serves single-token decode "
                f"steps only (got a {s}-token block); multi-token "
                "prefill takes the jnp path (kernel=False)")
        if expand is not None:
            raise ValueError("the paged Pallas kernel reads entries as "
                             "they are stored (expand= is the jnp path's)")
        return paged_decode_attention_kernel(
            q, kv_pool, block_table, index, scale=scale_v,
            window=window, interpret=interpret, value_lanes=value_lanes)

    # ---- jnp reference path (the tier-1 oracle) ----
    if blocks_per_chunk is None:
        blocks_per_chunk = max(1, (512 if s == 1 else 256) // bs)
    cb = min(int(blocks_per_chunk), t)
    chunk = cb * bs
    n_chunks = -(-t // cb)

    def _bcast(mask):
        return mask if mask.ndim == 2 else mask[:, None, None]

    def sweep(qg, index):
        """``qg [B, Hkv, rep, sq, D]`` at positions ``index (+
        arange(sq))`` over the chunks its rows can see."""
        sq = qg.shape[3]
        total = index + sq
        q_pos = index[..., None] + jnp.arange(sq)

        def body(c, carry):
            m, l, acc = carry
            start_blk = jnp.minimum(c * cb, t - cb)       # clamped tail
            ids = jax.lax.dynamic_slice(block_table, (0, start_blk),
                                        (b, cb))          # [B, cb]
            kvc = jnp.take(kv_pool, ids.reshape(-1), axis=0)
            # [B*cb, Hkv, bs, 2D] -> [B, Hkv, cb*bs, 2D]
            kvc = jnp.moveaxis(kvc.reshape(b, cb, hkv, bs, d2), 1, 2) \
                .reshape(b, hkv, chunk, d2)
            kc, vc = (kvc[..., :d], kvc[..., v0:v1]) if expand is None \
                else expand(kvc)
            sb = jnp.einsum("bgrqd,bgkd->bgrqk", qg.astype(kv_pool.dtype),
                            kc, preferred_element_type=jnp.float32) * scale_v
            pos = start_blk * bs + jnp.arange(chunk)
            dedup = pos >= c * chunk  # drop the clamped tail's re-read
            mask = pos[..., None, :] <= q_pos[..., :, None]
            if window is not None:
                mask &= pos[..., None, :] > q_pos[..., :, None] - window
            mask &= dedup[None, :]
            sb = jnp.where(_bcast(mask), sb, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sb, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sb - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "bgrqk,bgkd->bgrqd", p.astype(kv_pool.dtype), vc,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        live = jnp.minimum((jnp.max(total) + chunk - 1) // chunk, n_chunks)
        # A window layer's sweep starts at the first chunk any of these
        # rows' bands reaches: history wholly under the band is neither
        # gathered nor multiplied (at 12k of history and a 4k window,
        # two thirds of it).
        first = 0 if window is None else \
            jnp.maximum(jnp.min(index) - (window - 1), 0) // chunk
        m0 = jnp.full((b, groups, rep, sq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, groups, rep, sq, 1), jnp.float32)
        acc0 = jnp.zeros((b, groups, rep, sq, dv), jnp.float32)
        if n_chunks == 1:
            m, l, acc = body(0, (m0, l0, acc0))
        else:
            m, l, acc = jax.lax.fori_loop(first, live, body, (m0, l0, acc0))
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    qg = q.reshape(b, groups, rep, s, d)
    sq = _largest_dividing_block(s, PAGED_SWEEP_MAX_ROWS)
    if sq == s:
        return sweep(qg, index).reshape(b, h, s, dv)
    # A chunk wider than PAGED_SWEEP_MAX_ROWS goes a stretch of rows at a
    # time: each stretch sweeps to ITS causal edge and from ITS band's
    # start (half the multiplies of the whole square, less for a window
    # layer), and the score block stays the stretch's size.
    tiles = jnp.moveaxis(qg.reshape(b, groups, rep, s // sq, sq, d), 3, 0)
    out = jax.lax.map(
        lambda a: sweep(a[0], index + a[1] * sq),
        (tiles, jnp.arange(s // sq, dtype=jnp.int32)))
    return jnp.moveaxis(out, 0, 3).reshape(b, h, s, dv)


# Bytes of pool blocks one group of the paged decode kernel fetches (and
# one of its two VMEM buffers holds). The kernel pays its per-step fixed
# cost — the loop's bookkeeping, the copies' descriptors, the matmuls'
# set-up — once a group, so a group wants to be large; two buffers of it
# want to sit well inside VMEM and a short row wants few dead keys in
# its last group, so not too large. Chosen from readings on the chip at
# both benchmark configurations' shapes (PERF.md §6, PR 35).
PAGED_GROUP_BYTES = 1 << 20


def paged_blocks_per_group(pool_shape, itemsize: int, table_width: int) -> int:
    """K, the pool blocks the paged decode kernel takes a loop step:
    the power of two whose bytes come nearest :data:`PAGED_GROUP_BYTES`
    (a power of two so that ``K * block_size`` keys fill whole 128-lane
    tiles of the score block), at most the whole table."""
    _, hkv, bs, d2 = pool_shape
    k = 2 ** round(math.log2(max(
        1.0, PAGED_GROUP_BYTES / (hkv * bs * d2 * itemsize))))
    return max(1, min(int(k), int(table_width)))


def _paged_decode_kernel(table_ref, index_ref, q_ref, pool_ref, o_ref,
                         buf_ref, sem_ref, side_ref, m_ref, l_ref, acc_ref,
                         *, scale: float, window: Optional[int]):
    """One slot's grid step of paged decode attention: the whole sweep
    over the slot's live pool blocks, ``K`` of them a loop step.

    ``table_ref``/``index_ref`` are scalar-prefetched (SMEM); the pool
    leaf ``pool_ref`` stays in HBM. The slot's depth (and, on a window
    layer, its band) says which table entries hold keys it can see:
    ``first .. last``. The loop walks them in groups of ``K``,
    each group ``K`` block copies (K and V in one transfer) through the
    table into one of two VMEM buffers ``[H_kv, K * bs, 2D]``; the NEXT
    group's copies — at a slot's last group, the next slot's first —
    are started before the current one is waited for, so the fetch runs
    under the compute. A table entry past ``last`` is neither
    copied nor waited for: its stretch of the buffer keeps what an
    earlier group left there (finite pool data; zeros before the first)
    and the mask drops it, so a parked row costs one block and a block
    under a window's band nothing. Running max / denominator /
    accumulator live in VMEM scratch across the loop.

    Everything in here is one entry (``lanes``) wide and nothing is
    sliced or reshaped (Mosaic cannot re-tile a 64-lane minor dim):
    ``q_ref`` carries zeros in every lane past the key's ``[0, Dk)``, so
    ``q . entry`` is ``q . key`` exactly (the other lanes add exact
    zeros to the f32 sum), and ``p . entry`` accumulates ``p . value``
    in the value's lanes, which the wrapper slices off in HBM. A K/V
    leaf's entry is ``[k | v]``; a latent leaf's is key throughout and
    value in its leading lanes, which is the absorbed form of latent
    attention with no line of this body changed.
    """
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    num_t = table_ref.shape[1]
    _, _, span, _ = buf_ref.shape
    bs = pool_ref.shape[2]
    k_blocks = span // bs
    bq = pl.program_id(0)
    last_row = pl.num_programs(0) - 1

    def reach(depth):
        """(first, last) table entry holding a key a row at ``depth``
        attends to; ``first <= last`` inside the table whatever the
        depth, so every row has a group and the chain of prefetches
        from row to row never breaks."""
        last = jnp.clip(depth // bs, 0, num_t - 1)
        if window is None:
            return 0, last
        return jnp.clip((depth - (window - 1)) // bs, 0, last), last

    def group_copies(row, j0, last, side, start: bool):
        """Start, or wait for, the copies of ``row``'s group that opens
        at table entry ``j0`` into buffer ``side``: one a live entry."""
        def one(i, carry):
            copy = pltpu.make_async_copy(
                pool_ref.at[table_ref[row, j0 + i]],
                buf_ref.at[side, :, pl.ds(pl.multiple_of(i * bs, bs), bs)],
                sem_ref.at[side])
            copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, jnp.clip(last - j0 + 1, 0, k_blocks), one, None)

    @pl.when(bq == 0)
    def _first_slot():
        buf_ref[...] = jnp.zeros_like(buf_ref)
        side_ref[0] = 0
        first0, last0 = reach(index_ref[0])
        group_copies(0, first0, last0, 0, start=True)

    depth = index_ref[bq]  # tokens in the virtual cache before this step
    first, last = reach(depth)
    groups = (last - first) // k_blocks + 1
    # The row whose first group follows this row's last one.
    next_row = jnp.minimum(bq + 1, last_row)
    next_first, next_last = reach(index_ref[next_row])
    side0 = side_ref[0]
    m_ref[:] = jnp.full_like(m_ref, NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def body(g, carry):
        side = (side0 + g) % 2
        j0 = first + g * k_blocks
        # The next group of this slot or, at its last, the next slot's
        # first: in flight while this group is computed on.
        more = g + 1 < groups

        @pl.when(jnp.logical_or(more, bq < last_row))
        def _prefetch():
            group_copies(jnp.where(more, bq, next_row),
                         jnp.where(more, j0 + k_blocks, next_first),
                         jnp.where(more, last, next_last),
                         1 - side, start=True)

        group_copies(bq, j0, last, side, start=False)
        kv = buf_ref[side]
        # [Hkv, rep, 2D] x [Hkv, K*bs, 2D] -> [Hkv, rep, K*bs], batched on
        # the kv-head dim, f32 accumulation on the MXU. q, the output and
        # the scratches all carry the [Hkv, rep, ...] grouping (the wrapper
        # reshapes in HBM, where it is free).
        sb = jax.lax.dot_general(
            q_ref[0], kv, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        pos = j0 * bs + jax.lax.broadcasted_iota(jnp.int32, sb.shape, 2)
        mask = pos <= depth
        if window is not None:
            mask = jnp.logical_and(mask, pos > depth - window)
        sb = jnp.where(mask, sb, NEG_INF)
        m_prev = m_ref[:, :, :1]
        l_prev = l_ref[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(sb, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sb - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(  # [Hkv, rep, K*bs] x [Hkv, K*bs, 2D]
            p.astype(kv.dtype), kv, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        return carry

    jax.lax.fori_loop(0, groups, body, None)
    side_ref[0] = (side0 + groups) % 2
    l = jnp.maximum(l_ref[:, :, :1], 1e-30)
    o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def paged_decode_attention_kernel(
    q: jnp.ndarray, kv_pool: jnp.ndarray,
    block_table, index, *, scale: Optional[float] = None,
    window: Optional[int] = None, interpret: Optional[bool] = None,
    value_lanes: Optional[tuple] = None,
) -> jnp.ndarray:
    """The Pallas paged decode kernel (single-token steps).

    Grid ``(B,)``, one step a slot, the pool leaf left in HBM: inside
    its step a slot loops over the groups of ``K`` pool blocks that its
    depth (and its window's band) reaches and copies each through the
    scalar-prefetched block table into one of two VMEM buffers, the
    next group's copies in flight under the current group's online
    softmax (:func:`_paged_decode_kernel`) — indirection happens in the
    copies' source addresses, never as a gathered copy in HBM. A table
    entry that holds no key the slot can see has no loop step and no
    copy: a live slot costs its prefix (its band, on a window layer), a
    parked one a single block. ``K`` follows from the leaf's shape
    (:func:`paged_blocks_per_group`). Numerics match the jnp reference
    path of :func:`paged_decode_attention` (same masking and online
    softmax; pinned by `tests/test_serve_paged.py`).
    """
    b, h, s, d = q.shape
    if s != 1:
        raise ValueError(f"decode kernel takes single-token steps, got s={s}")
    v0, v1 = _entry_lanes(kv_pool, d, value_lanes)
    hkv, lanes = kv_pool.shape[1], kv_pool.shape[3]
    rep = _gqa_rep(q, kv_pool)
    block_table = jnp.asarray(block_table, jnp.int32)
    k_blocks = paged_blocks_per_group(
        kv_pool.shape, kv_pool.dtype.itemsize, block_table.shape[1])
    scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    # Cache-head grouping and q's zeros past the key, both done in HBM.
    qg = jnp.pad(q.reshape(b, hkv, rep, d),
                 ((0, 0),) * 3 + ((0, lanes - d),))
    out = _paged_decode_call(
        block_table, index, qg, kv_pool, k_blocks=k_blocks,
        scale=float(scale_v), window=window, interpret=bool(interpret))
    return out[..., v0:v1].reshape(b, h, 1, v1 - v0)


@functools.partial(jax.jit, inline=True, static_argnames=(
    "k_blocks", "scale", "window", "interpret"))
def _paged_decode_call(block_table, index, qg, kv_pool, *, k_blocks: int,
                       scale: float, window: Optional[int], interpret: bool):
    """The ``pallas_call`` of :func:`paged_decode_attention_kernel`. An
    inlined ``jit`` only so that jax keeps the traced kernel: a tick
    calls this once a layer with the same shapes, and without it every
    layer traces the kernel's body anew in every run's set-up."""
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    b, hkv, rep, d2 = qg.shape
    bs = kv_pool.shape[2]
    q_spec = pl.BlockSpec((1, hkv, rep, d2),
                          lambda bq, tbl, idx: (bq, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, hkv, k_blocks * bs, d2), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),           # one a buffer
            pltpu.SMEM((1,), jnp.int32),  # buffer of a slot's first group
            pltpu.VMEM((hkv, rep, LANES), jnp.float32),  # running max
            pltpu.VMEM((hkv, rep, LANES), jnp.float32),  # running denom
            pltpu.VMEM((hkv, rep, d2), jnp.float32),     # [junk | output]
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=scale, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_table, index, qg, kv_pool)


def decode_attention(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    index, *, window: Optional[int] = None, rolling: bool = False,
    chunk: int = 512, scale: Optional[float] = None,
    history_only: bool = False, return_lse: bool = False,
):
    """Serving-path attention over a KV cache, at the bandwidth roofline.

    The naive decode step (what this replaced) expanded the cache to
    query-head count, cast it to f32, and scored every padded position —
    ~6x the necessary HBM traffic for a GQA model plus dead-position
    work. Here instead:

    - the cache is read in its STORAGE dtype (bf16 in serving); the
      score matmul accumulates in f32 on the MXU
      (``preferred_element_type``), like the training kernel;
    - K/V stay at kv-head granularity — q is grouped ``[B, H_kv, rep,
      S, D]`` against the unexpanded cache;
    - the sweep visits only ``ceil((index+S)/chunk)`` cache chunks via a
      dynamic-trip-count ``fori_loop`` with online softmax, so HBM
      traffic and compute are bounded by the VALID PREFIX, not the
      padded cache length.

    Args:
      q: ``[B, H, S, D]`` post-RoPE queries (``S`` tokens being decoded).
      k_cache/v_cache: ``[B, H_kv, L, D]`` cache, current tokens already
        written at their slots.
      index: tokens in the cache BEFORE this call (query global
        positions are ``index .. index+S-1``). Scalar int32, or a
        PER-ROW ``[B]`` int32 vector — the continuous-batching serving
        engine's path, where each batch row is an independent request
        slot at its own depth; masking is then per row and the chunk
        sweep is bounded by the DEEPEST row.
      window: sliding-window width (Mistral SWA); masks keys below
        ``q_pos - window + 1``.
      rolling: the cache is a RING buffer of size ``L`` (requires
        ``L >= window``): slot ``j`` holds the newest global position
        ``p ≡ j (mod L)`` with ``p <= index+S-1``. Slot→position is
        reconstructed arithmetically for masking; never-written slots
        (``p < 0``) are masked out.
      chunk: cache positions per loop iteration (clamped to ``L``; need
        not divide it — the tail chunk clamps its start and masks the
        overlap).
      history_only: the cache holds ONLY the ``index`` tokens BEFORE this
        call (the current block is NOT written): queries attend strictly
        to ``pos < index``. The chunked-prefill building block — merge
        the result with the block's own (windowed, causal) attention in
        logsumexp space.
      return_lse: also return per-row logsumexp ``[B, H, S]`` (for
        merging partials, as in ring attention).

    Returns ``[B, H, S, D]`` in q's dtype (plus lse under ``return_lse``).

    Single-token steps over SMALL caches (``_SINGLE_SHOT_MAX_KC_BYTES``,
    batch included) skip the loop entirely and run ONE fused masked pass
    over the whole cache: the loop's while/dynamic-slice machinery is a
    fixed per-layer cost that dwarfs the few extra megabytes of read at
    single-stream sizes, while large-batch/long-cache steps keep the
    prefix-bounded sweep (their extra read would scale with B·L).
    """
    b, h, s, d = q.shape
    hkv, cache_len = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]  # a latent cache's value is narrower than its key
    rep = _gqa_rep(q, k_cache)
    index = jnp.asarray(index, jnp.int32)
    if index.ndim > 1 or (index.ndim == 1 and index.shape[0] != b):
        raise ValueError(
            f"index must be a scalar or [B]={b} vector, got {index.shape}")
    if rolling:
        # Both invariants are static; violating either silently loses
        # in-window history, so fail loudly here instead.
        if window is None:
            raise ValueError("rolling=True needs a sliding window (the "
                             "ring holds only the newest position per "
                             "slot — unwindowed attention would silently "
                             "miss overwritten history)")
        if cache_len < window:
            raise ValueError(
                f"rolling cache length {cache_len} < window {window}: "
                "in-window keys would be overwritten before leaving the "
                "band")
    scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
    # Short-cache single-token steps: one fused pass, no loop (see
    # docstring). The chunked loop remains for big-batch/long caches
    # (bounded HBM traffic) and multi-token prefill (bounded score
    # memory).
    kc_bytes = b * hkv * cache_len * d * jnp.dtype(k_cache.dtype).itemsize
    if s == 1 and kc_bytes <= _SINGLE_SHOT_MAX_KC_BYTES:
        chunk = cache_len
    # Chunks need NOT divide the cache: the final chunk's slice start is
    # clamped and the overlap with the previous chunk masked out (the
    # dedup term below), so a non-round cache length costs one partially
    # re-read chunk — never a degenerate chunk=1 sweep.
    chunk = min(chunk, cache_len)
    n_chunks = -(-cache_len // chunk)

    qg = q.reshape(b, hkv, rep, s, d)
    # Tokens the cache holds: through this block (written before the
    # call) unless history_only, where the block is attended separately.
    total = index if history_only else index + s
    # Global positions of the queries: [s] for a shared scalar index,
    # [B, s] for the per-row vector path ([..., None] makes the same
    # expression produce both ranks; every mask term below follows the
    # same pattern, so the two paths share one masking definition).
    q_pos = index[..., None] + jnp.arange(s)

    def _bcast(mask):
        """Lift a mask to broadcast against sb [b, g, r, s, chunk]:
        shared masks enter as [s, chunk] (or [1, chunk]); per-row masks
        as [B, s, chunk] (or [B, 1, chunk]) and gain the (g, r) axes."""
        return mask if mask.ndim == 2 else mask[:, None, None]

    def body(c, carry):
        m, l, acc = carry
        start = jnp.minimum(c * chunk, cache_len - chunk)  # clamped tail
        kc = jax.lax.dynamic_slice(
            k_cache, (0, 0, start, 0), (b, hkv, chunk, d))
        vc = jax.lax.dynamic_slice(
            v_cache, (0, 0, start, 0), (b, hkv, chunk, dv))
        sb = jnp.einsum("bgrqd,bgkd->bgrqk", qg.astype(k_cache.dtype), kc,
                        preferred_element_type=jnp.float32) * scale_v
        slot = start + jnp.arange(chunk)
        dedup = slot >= c * chunk  # drop the clamped tail's re-read overlap
        if rolling:
            # Newest global position congruent to the slot index; jnp's
            # mod is non-negative, so unwritten slots land at p < 0.
            # Vector total: [B, 1] against slot [chunk] → per-row [B,
            # chunk] positions.
            t1 = total[..., None] - 1
            pos = t1 - (t1 - slot) % cache_len
            valid = pos >= 0
        else:
            pos = slot
            valid = None
        if history_only:
            # strictly pre-block keys; broadcasts against the per-query
            # window term below
            mask = pos[..., None, :] < index[..., None, None]
        else:
            mask = pos[..., None, :] <= q_pos[..., :, None]
        if window is not None:
            mask &= pos[..., None, :] > q_pos[..., :, None] - window
        if valid is not None:
            mask &= valid[..., None, :]
        mask &= dedup[None, :]
        sb = jnp.where(_bcast(mask), sb, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sb, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sb - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bgrqk,bgkd->bgrqd", p.astype(v_cache.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    # Bound the sweep to chunks overlapping the valid prefix — the
    # DEEPEST row's prefix on the vector path (shallower rows mask the
    # excess). A rolling cache is dense once wrapped, so every chunk is
    # live after that; the min() still trims the pre-wrap phase.
    live = jnp.minimum((jnp.max(total) + chunk - 1) // chunk, n_chunks)
    m0 = jnp.full((b, hkv, rep, s, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, rep, s, 1), jnp.float32)
    acc0 = jnp.zeros((b, hkv, rep, s, dv), jnp.float32)
    if n_chunks == 1:
        # Whole cache in one pass — no while loop in the program at all.
        m, l, acc = body(0, (m0, l0, acc0))
    else:
        m, l, acc = jax.lax.fori_loop(0, live, body, (m0, l0, acc0))
    if history_only:
        # Rows with an empty valid prefix (index 0 — or a zero-depth row
        # on the vector path) must still produce the zero-iteration
        # result: a fully-masked pass makes every row's p uniform
        # (exp(NEG_INF - NEG_INF) == 1), so mask such rows back to the
        # inits instead of running on trust. Only history_only can be
        # empty — the regular path always sees at least the current
        # token (total = index + s >= 1) — so the decode hot path never
        # pays these wheres.
        keep = total[..., None, None, None, None] > 0
        m = jnp.where(keep, m, m0)
        l = jnp.where(keep, l, l0)
        acc = jnp.where(keep, acc, acc0)
    out = (acc / jnp.maximum(l, 1e-30)).reshape(b, h, s, dv).astype(q.dtype)
    if return_lse:
        # Rows with nothing attended (empty history) keep lse ~ -inf so
        # a logsumexp-space merge gives them zero weight.
        lse = (m + jnp.log(jnp.maximum(l, 1e-30))).reshape(b, h, s)
        return out, lse
    return out
