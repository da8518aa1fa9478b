"""Llama family: RoPE + RMSNorm + SwiGLU + grouped-query attention.

The reference repo trains one CNN family end to end
(`/root/reference/imagenet-resnet50.py:52`); its TPU rebuild carries a
transformer LM line (:mod:`pddl_tpu.models.gpt`) as the long-context
workload. This module adds the *modern* decoder architecture — the
Llama/Mistral/Qwen lineage — on the same substrate:

- **RoPE** (:mod:`pddl_tpu.ops.rope`) instead of GPT-2's learned
  position table: no ``max_len``-sized parameter, positions enter
  through q/k rotation, HF half-split convention so
  :func:`pddl_tpu.ckpt.hf_import.load_hf_llama` checkpoints reproduce
  transformers' logits to f32 tolerance.
- **RMSNorm** (f32 compute, like the family's LayerNorms) pre-attention,
  pre-MLP, and final.
- **SwiGLU** MLP (``silu(gate)·up → down``), no biases anywhere
  (except Qwen2's q/k/v projection biases, ``qkv_bias=True``).
- **Grouped-query attention**: ``num_kv_heads <= num_heads`` K/V heads,
  consumed UNEXPANDED by every kernel (flash, ring, decode — the
  q-head → kv-head mapping lives inside them), so GQA's
  ``num_heads/num_kv_heads`` memory/bandwidth saving holds in
  training, prefill, sequence-parallel rotation, AND the decode cache.
- **Sliding-window attention** (Mistral): band-skipped in the flash
  kernel, composed with the ring/sequence-parallel path (out-of-band
  rotations skipped — O(window) compute and ICI), and a
  ``window``-sized rolling ring-buffer decode cache.
- **Routed experts** (Mixtral): ``moe_experts`` switches each
  ``moe_every``-th block's MLP to top-``moe_top_k`` SwiGLU experts
  (:class:`pddl_tpu.ops.moe.SwitchFFN`, ``expert_act="swiglu"``);
  import/export via :func:`pddl_tpu.ckpt.hf_import.load_hf_mixtral` /
  ``export_hf_llama``; shard with ``LLAMA_EP_RULES``.
- **Per-layer declarations** on the one block, for the hybrids of the
  lineage: an explicit ``head_dim`` (q width ``num_heads * head_dim``
  need not equal the embedding), ``sliding_window_layout`` /
  ``rope_layout`` (a 0/1 tuple a layer: which layers attend through the
  window, which rotate; a layer with neither is full attention with NO
  position encoding and caches its keys as projected), ReLU-gated
  experts (``moe_act="reglu"``), and ``moe_router_input="attn"``: the
  router reads the ATTENTION's normed input, so a runtime can fetch the
  chosen experts' weights while attention runs.
- **Latent attention** (the DeepSeek-V2/V3 lineage's MLA;
  :class:`LatentAttention`, ``kv_lora_rank > 0``, declared on the block
  like a window or a RoPE flag): queries and keys/values through low-rank
  projections, RoPE on ``qk_rope_head_dim`` of a head's dimensions, and
  ONE cache entry a token for all heads, ``[c_kv | k_rope]``. Beside it
  the lineage's expert layer: ``moe_layout`` (which layers route; a
  leading dense layer), ``moe_intermediate_dim`` (an expert width apart
  from the dense MLP's), sigmoid scores with a selection-only bias, a
  gate scale and shared experts (:class:`pddl_tpu.ops.moe.SwitchFFN`).
- **Gated short convolution** (the LFM2 lineage; :class:`ShortConv`,
  ``layer_types[i] == "conv"``): an operator with NO per-token cache
  entry, whose whole memory is the last ``conv_kernel - 1`` gated inputs
  — a fixed state a serving slot, beside the paged K/V of the attention
  layers it alternates with. ``layer_types`` (the published key) is the
  one per-layer declaration of which operator a block has.

Everything else — flash/ring attention, Megatron TP (use
``LLAMA_TP_RULES`` from :mod:`pddl_tpu.parallel.tensor_parallel`),
fused-CE training loss, KV-cache generation — is shared with the GPT
family: :func:`pddl_tpu.models.gpt.generate` and
:func:`pddl_tpu.models.gpt.fused_lm_loss` are duck-typed over both.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from pddl_tpu.models.gpipe import GPipeModel
from pddl_tpu.models.vit import (
    BLOCK_TABLE_KEY,
    SLOT_STATE_KEY,
    STATE_SLOT_KEY,
    paged_decode_step,
    remat_block,
)
from pddl_tpu.ops.attention import (
    LANES,
    attention_reference,
    decode_attention,
    flash_attention,
    paged_kv_fuse,
)
from pddl_tpu.ops.rope import apply_rope_qk


def _default_intermediate_dim(embed_dim: int) -> int:
    """The SwiGLU convention: 2/3 of the 4E classic MLP width, rounded up
    to a multiple of 128 (lane-friendly). One definition shared by
    :class:`Llama` and :class:`GPipeLlama`."""
    return -(-(8 * embed_dim // 3) // 128) * 128


def ring_len(sliding_window: Optional[int],
             max_decode_len: int) -> Optional[int]:
    """Rolling-cache length for SWA decode: the window rounded up to a
    lane-friendly multiple of 128 (``>= window`` so the slot being
    overwritten each step is always already outside the band), or None
    when a full-length cache is smaller anyway.

    THE single definition of the ring decision — the attention module
    sizes its cache with it and ``Llama.uses_ring_cache`` (which
    speculative decoding consults to refuse unrewindable caches) answers
    from it, so the two can never diverge.
    """
    if sliding_window is None:
        return None
    ring = -(-sliding_window // 128) * 128
    return ring if ring < max_decode_len else None


def _rms_norm(eps: float, param_dtype, name: str):
    """Family-standard RMSNorm: f32 compute (stable under bf16), learned
    scale in ``param_dtype``."""
    return nn.RMSNorm(epsilon=eps, dtype=jnp.float32,
                      param_dtype=param_dtype, name=name)


class LlamaAttention(nn.Module):
    """Causal GQA with RoPE over the repo's attention kernels.

    Layout mirrors :class:`pddl_tpu.models.vit.MultiHeadAttention`
    (``query``/``key``/``value`` DenseGeneral, flattened ``out``) so the
    Megatron TP path rules apply unchanged. K/V carry ``num_kv_heads``
    and are consumed at that size by every kernel (flash, reference,
    ring): the q-head → kv-head mapping lives inside the kernels, so no
    expanded copy is materialized anywhere in training or prefill.
    """

    num_heads: int
    num_kv_heads: int
    head_dim: Optional[int] = None  # None: embed // num_heads
    rope: bool = True  # False: no position encoding (NoPE layer)
    rope_theta: float = 10000.0
    attention: str = "flash"  # "flash" | "reference" | "ring" | "ring_flash"
    sliding_window: Optional[int] = None  # Mistral-style SWA width
    qkv_bias: bool = False  # Qwen2-style q/k/v projection biases
    # RMSNorm of every q and k head over its own dimensions, one learned
    # scale each shared by the heads, BEFORE RoPE (the LFM2 lineage).
    qk_norm: bool = False
    rms_eps: float = 1e-5
    mesh: Optional[Any] = None
    decode: bool = False
    max_decode_len: int = 1024
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def _rotate(self, q, k, positions):
        if not self.rope:
            return q, k
        return apply_rope_qk(q, k, positions, theta=self.rope_theta)

    @nn.compact
    def __call__(self, x):
        b, s, e = x.shape
        if self.head_dim is None and e % self.num_heads:
            raise ValueError(f"embed dim {e} not divisible by {self.num_heads} heads")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads {self.num_heads} not divisible by "
                f"num_kv_heads {self.num_kv_heads}")
        if self.sliding_window is not None and self.sliding_window < 1:
            # Validate here so the decode path (which builds its own mask)
            # rejects it too, not just the flash/reference kernels.
            raise ValueError(
                f"sliding_window must be >= 1, got {self.sliding_window}")
        head_dim = self.head_dim or e // self.num_heads
        dense = functools.partial(
            nn.DenseGeneral, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype,
        )
        # Qwen2 puts biases on q/k/v (only); the out projection is always
        # bias-free across the lineage.
        qkv = functools.partial(dense, use_bias=self.qkv_bias)
        q = qkv(features=(self.num_heads, head_dim), name="query")(x)
        k = qkv(features=(self.num_kv_heads, head_dim), name="key")(x)
        v = qkv(features=(self.num_kv_heads, head_dim), name="value")(x)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, H, S, D]
        if self.qk_norm:
            q = _rms_norm(self.rms_eps, self.param_dtype, "q_norm")(
                q).astype(self.dtype)
            k = _rms_norm(self.rms_eps, self.param_dtype, "k_norm")(
                k).astype(self.dtype)

        if self.decode:
            return self._decode_step(q, k, v, b, s, e, head_dim, dense)

        q, k = self._rotate(q, k, jnp.arange(s))

        # K/V stay at kv-head shape [B, H_kv, S, D] through every kernel:
        # the attention ops consume grouped K/V natively (q-head → kv-head
        # mapping in kernel index maps), so training/prefill get GQA's
        # full HBM-bandwidth and activation-memory saving — no
        # H/H_kv-times expansion is ever materialized.
        if self.attention == "flash":
            o = flash_attention(q, k, v, causal=True,
                                window=self.sliding_window)
        elif self.attention == "reference":
            o = attention_reference(q, k, v, causal=True,
                                    window=self.sliding_window)
        elif self.attention in ("ring", "ring_flash"):
            from pddl_tpu.ops.ring_attention import sequence_parallel_attention

            if self.mesh is None:
                raise ValueError(f"attention={self.attention!r} needs the mesh")
            # SWA composes with the ring: out-of-band rotations (and
            # their ppermute hops) are skipped, so long-context Mistral
            # under sequence parallelism pays O(window) per device.
            o = sequence_parallel_attention(
                q, k, v, self.mesh, causal=True,
                window=self.sliding_window,
                use_flash=self.attention == "ring_flash")
        else:
            raise ValueError(f"unknown attention {self.attention!r}")

        o = o.transpose(0, 2, 1, 3).reshape(b, s, self.num_heads * head_dim)
        return dense(features=e, name="out")(o)

    def _ring_len(self) -> Optional[int]:
        """Rolling-cache length for SWA decode (see :func:`ring_len`)."""
        return ring_len(self.sliding_window, self.max_decode_len)

    def _decode_step(self, q, k, v, b, s, e, head_dim, dense):
        """KV-cache decoding at the bandwidth roofline.

        The cache holds POST-RoPE keys at KV-head granularity in the
        model's compute dtype (bf16 in serving — never cast up), and:

        - single-token steps sweep it with
          :func:`~pddl_tpu.ops.attention.decode_attention` — grouped
          (unexpanded) K/V, online softmax over chunks, HBM traffic
          bounded by the valid prefix;
        - with ``sliding_window`` the cache is a ``window``-sized RING
          buffer (:meth:`_ring_len`) instead of ``max_decode_len`` —
          Mistral's rolling cache — so decode memory and traffic are
          O(window), not O(max_len);
        - multi-token PREFILL (including chunked prefill at any starting
          index) runs the flash kernel on the block itself merged with a
          pre-write history sweep in logsumexp space — O(block) score
          memory, never ``[B,H,S,max_len]`` f32.

        Cache-content contract the serving layer builds on: the cache
        stores POST-RoPE keys rotated at their ABSOLUTE positions, so an
        entry depends only on (prompt tokens, position, params) — never
        on which request computed it. This is what makes the prefix
        cache's shared KV blocks (`pddl_tpu/serve/kvcache/`) bit-valid
        across requests, and what `gpt.prefill_row_from` relies on when
        it continues a cache whose table points at a pinned chain: a
        suffix chunk at starting index ``i`` reproduces exactly the K/V
        a full prefill would have written there. (The caller keeps
        ``i + s <= max_decode_len`` — the cache write's dynamic slice
        CLAMPS out-of-range starts rather than failing.)
        """
        hkv = self.num_kv_heads
        paged = self.has_variable("cache", BLOCK_TABLE_KEY)
        # A paged cache is full length for every layer: a window layer
        # lives in the same pool as a global one, masked (and its dead
        # blocks skipped) to its band, so no ring exists there.
        ring = None if paged else self._ring_len()
        cache_len = ring or self.max_decode_len
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))

        i = index.value
        if i.ndim and s != 1 and ring is not None:
            # Per-row [B] positions over a RING cache cannot take
            # multi-token blocks: a partially-rejected speculative
            # window would have overwritten in-window slots per row.
            # Full-length caches (the only kind the serving engine
            # admits) handle the vector multi-token write below.
            raise ValueError(
                "per-row cache_index over a rolling ring cache supports "
                f"single-token steps only (got a {s}-token block)")
        # [..., None] keeps one expression for both index ranks: scalar
        # i → positions [s]; per-row i → [B, s] (rope broadcasts a head
        # axis for the 2-D form).
        q, k = self._rotate(q, k, i[..., None] + jnp.arange(s))
        k = k.astype(self.dtype)
        v = v.astype(self.dtype)
        if paged:
            # PAGED serving (see the vit MHA twin): one fused pool leaf
            # + an engine-stamped per-slot block table replace the
            # contiguous row cache. Post-RoPE keys are cached at their
            # ABSOLUTE positions like the row path, so a shared pool
            # block stays bit-valid for every referencing slot — the
            # same contract the prefix cache's copies relied on, now
            # without the copies (a NoPE layer's keys are cached as
            # projected, which is position-pure a fortiori).
            with jax.named_scope("attn_window" if self.sliding_window
                                 else "attn_global"):
                o = paged_decode_step(self, index, q, paged_kv_fuse(k, v),
                                      window=self.sliding_window)
            o = o.transpose(0, 2, 1, 3).reshape(
                b, s, self.num_heads * head_dim)
            return dense(features=e, name="out")(o)
        initialized = self.has_variable("cache", "cached_key")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (b, hkv, cache_len, head_dim), self.dtype)
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (b, hkv, cache_len, head_dim), self.dtype)
        # Pre-write ring state: the multi-token ring path attends history
        # from here (the block's own writes below may overwrite in-window
        # history slots that this block's EARLY queries still need).
        hist_k, hist_v = cached_k.value, cached_v.value
        if initialized:
            if i.ndim:
                # Per-row scatter at i[b] + arange(s) (ring rows wrap
                # their slot; s > 1 is full-length-cache only — gated
                # above). Multi-token blocks are the speculative verify
                # write: out-of-range positions drop (jit scatter OOB),
                # so draft lookahead past the cache edge never lands.
                rows = jnp.arange(b)[:, None]          # [B, 1]
                pos = i[:, None] + jnp.arange(s)       # [B, s]
                slot = pos % ring if ring is not None else pos
                cached_k.value = cached_k.value.at[rows, :, slot].set(
                    jnp.moveaxis(k, 1, 2))
                cached_v.value = cached_v.value.at[rows, :, slot].set(
                    jnp.moveaxis(v, 1, 2))
            elif ring is None:
                cached_k.value = jax.lax.dynamic_update_slice(
                    cached_k.value, k, (0, 0, i, 0))
                cached_v.value = jax.lax.dynamic_update_slice(
                    cached_v.value, v, (0, 0, i, 0))
            elif s == 1:
                slot = i % ring
                cached_k.value = jax.lax.dynamic_update_slice(
                    cached_k.value, k, (0, 0, slot, 0))
                cached_v.value = jax.lax.dynamic_update_slice(
                    cached_v.value, v, (0, 0, slot, 0))
            else:
                # Prefill into the ring: only the last `ring` tokens can
                # survive; scatter them at their slots (consecutive
                # positions → distinct slots).
                keep = min(s, ring)
                slots = (i + jnp.arange(s)[s - keep:]) % ring
                cached_k.value = cached_k.value.at[:, :, slots].set(
                    k[:, :, s - keep:])
                cached_v.value = cached_v.value.at[:, :, slots].set(
                    v[:, :, s - keep:])
            index.value = i + s

        if s > 1:
            # Prefill / chunked prefill, exact for ANY starting index i.
            if ring is not None:
                # Ring path: the block attends within itself through the
                # flash kernel (O(block) memory) and strictly-pre-block
                # history through a sweep of the PRE-WRITE ring; the two
                # normalized partials merge in logsumexp space. At i == 0
                # the history term has -inf lse and zero weight.
                from pddl_tpu.ops.attention import flash_attention_lse

                o_blk, lse_blk = flash_attention_lse(
                    q, k, v, causal=True, window=self.sliding_window)
                o_hist, lse_hist = decode_attention(
                    q, hist_k, hist_v, i, window=self.sliding_window,
                    rolling=True, history_only=True, return_lse=True,
                    chunk=128)
                m = jnp.maximum(lse_blk, lse_hist)
                w_blk = jnp.exp(lse_blk - m)[..., None]
                w_hist = jnp.exp(lse_hist - m)[..., None]
                o = ((o_blk.astype(jnp.float32) * w_blk
                      + o_hist.astype(jnp.float32) * w_hist)
                     / (w_blk + w_hist)).astype(q.dtype)
            else:
                o = decode_attention(
                    q, cached_k.value, cached_v.value, i,
                    window=self.sliding_window, chunk=128)
        else:
            o = decode_attention(
                q, cached_k.value, cached_v.value, i,
                window=self.sliding_window, rolling=ring is not None)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, self.num_heads * head_dim)
        return dense(features=e, name="out")(o)


class LatentAttention(nn.Module):
    """Causal multi-head LATENT attention (MLA, DeepSeek-V2/V3 lineage).

    With ``x`` the block's normed input at position ``t``:

    - queries: ``c_q = RMSNorm(x W_dq)`` (``q_lora_rank``); ``[q_nope |
      q_rope] = c_q W_uq``, ``num_heads`` heads of ``qk_nope_head_dim +
      qk_rope_head_dim``; ``q_rope`` rotated at ``t``;
    - the cache entry, one a token, shared by all heads: ``[c_kv |
      k_rope] = x W_dkv`` (``kv_lora_rank + qk_rope_head_dim``); ``c_kv
      = RMSNorm(c_kv)``; ``k_rope`` rotated at ``t``;
    - keys and values: ``[k_nope | v]_h = c_kv W_ukv,h``; ``k_h = [k_nope,h
      | k_rope]``; scores ``q_h . k_h`` scaled by ``(qk_nope_head_dim +
      qk_rope_head_dim)^-0.5``; output ``concat(o_h) W_o``.

    Training, eval and a chunk of prefill use that PER-HEAD form. A decode
    step uses the same mathematics in the latent space (the ABSORBED
    form): ``q~_h = q_nope,h W_uk,h^T``, score ``[q~_h | q_rope,h] .
    [c_kv | k_rope]``, ``o~_h = sum p c_kv``, ``o_h = o~_h W_uv,h`` — the
    cached entry is the key of every head as it lies and its first
    ``kv_lora_rank`` lanes are the value, so the paged kernel reads each
    entry once for all heads and nothing a head wide is ever cached.
    An entry is STORED in whole 128-lane tiles, zeros after ``k_rope``
    (GLM-4.7-Flash's 576 values in 640 lanes): the chip lays a 576-wide
    minor dimension out in 640 lanes at rest whatever the shape says,
    and Mosaic copies whole tiles only (it refused the 576-wide leaf,
    `tests/test_tpu_aot_compile.py`), so the leaf says what is there.

    A chunk of prefill through the paged cache (``s > 1``) writes its
    entries first and then attends over the pool in the per-head form,
    the gathered entries expanded to ``k_nope`` / ``v`` a sweep step at a
    time (scope ``mla_expand``): per cached token that costs
    ``2 kv_lora_rank H (nope + v)`` FLOPs again in every later chunk,
    where the absorbed form would widen every score from ``nope + rope``
    to ``kv_lora_rank + rope`` lanes and every value from ``v`` to
    ``kv_lora_rank`` — at GLM-4.7-Flash's widths 2.1 x the attention
    FLOPs of a chunk against a re-expansion a fifth of them.

    The flash kernel takes keys and values of one width: a model whose
    ``v_head_dim`` differs from ``qk_nope_head_dim + qk_rope_head_dim``
    trains with ``attention="reference"``.
    """

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    attention: str = "flash"  # "flash" | "reference"
    rms_eps: float = 1e-5
    decode: bool = False
    max_decode_len: int = 1024
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, e = x.shape
        h, rank = self.num_heads, self.kv_lora_rank
        nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        dense = functools.partial(
            nn.DenseGeneral, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype)
        norm = lambda name, t: _rms_norm(
            self.rms_eps, self.param_dtype, name)(t).astype(self.dtype)
        c_q = norm("q_norm", dense(features=self.q_lora_rank,
                                   name="q_down")(x))
        q = dense(features=(h, nope + rope), name="q_up")(c_q)
        q = q.transpose(0, 2, 1, 3)                      # [B, H, S, nope+rope]
        down = dense(features=rank + rope, name="kv_down")(x)
        c_kv = norm("kv_norm", down[..., :rank])         # [B, S, rank]
        k_rope = down[..., None, :, rank:]               # [B, 1, S, rope]
        # W_ukv [rank, H, nope + v]: a head's W_uk beside its W_uv.
        w_ukv = self.param(
            "kv_up", nn.initializers.lecun_normal(), (rank, h, nope + vd),
            self.param_dtype).astype(self.dtype)
        out = dense(features=e, name="out")
        lanes = -(-(rank + rope) // LANES) * LANES   # an entry as stored
        index = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((), jnp.int32)) if self.decode else None
        positions = jnp.arange(s) if index is None \
            else index.value[..., None] + jnp.arange(s)
        q_rope, k_rope = apply_rope_qk(q[..., nope:], k_rope, positions,
                                       theta=self.rope_theta)
        q_nope = q[..., :nope]
        scale = (nope + rope) ** -0.5

        def expand(entries):
            """Cache entries ``[B, 1, K, lanes]`` as every head's key and
            value, ``[B, H, K, nope + rope]`` and ``[B, H, K, v]``."""
            c, kr = entries[:, 0, :, :rank], entries[:, :, :, rank:rank + rope]
            with jax.named_scope("mla_expand"):
                kv = jnp.einsum("bkc,chd->bhkd", c, w_ukv)
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(kr, kv.shape[:3] + (rope,))], axis=-1)
            return k, kv[..., nope:]

        if index is None:
            k, v = expand(jnp.concatenate([c_kv[:, None], k_rope], axis=-1))
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            if self.attention == "flash":
                o = flash_attention(q, k, v, causal=True, scale=scale)
            elif self.attention == "reference":
                o = attention_reference(q, k, v, causal=True, scale=scale)
            else:
                raise ValueError(
                    f"latent attention runs attention='flash' or "
                    f"'reference', got {self.attention!r}")
            return out(o.transpose(0, 2, 1, 3).reshape(b, s, h * vd))

        entry = jnp.concatenate(
            [c_kv[:, None], k_rope,
             jnp.zeros((b, 1, s, lanes - rank - rope), self.dtype)], axis=-1)

        def absorbed(attend):
            """A step in the latent space: ``attend(q~)`` over entries
            that are key and (their first ``rank`` lanes) value at once."""
            with jax.named_scope("mla_absorb"):
                q_lat = jnp.concatenate(
                    [jnp.einsum("bhsd,chd->bhsc", q_nope,
                                w_ukv[..., :nope]), q_rope], axis=-1)
            o_lat = attend(q_lat)                        # [B, H, s, rank]
            with jax.named_scope("mla_absorb"):
                return jnp.einsum("bhsc,chd->bhsd", o_lat, w_ukv[..., nope:])

        if self.has_variable("cache", BLOCK_TABLE_KEY):
            # PAGED serving: the pool leaf is [N, 1, block, lanes], the
            # entry as it is; the tick goes through the paged kernel
            # in the absorbed form, a chunk in the per-head form over
            # entries expanded inside the sweep.
            with jax.named_scope("attn_latent"):
                if s == 1:
                    o = absorbed(lambda q_lat: paged_decode_step(
                        self, index, q_lat, entry, scale=scale,
                        value_lanes=(0, rank)))
                else:
                    o = paged_decode_step(
                        self, index, jnp.concatenate([q_nope, q_rope], -1),
                        entry, scale=scale, expand=expand)
            return out(o.transpose(0, 2, 1, 3).reshape(b, s, h * vd))

        # The row cache of `generate()`: full length, absorbed throughout.
        initialized = self.has_variable("cache", "cached_latent")
        cached = self.variable(
            "cache", "cached_latent", jnp.zeros,
            (b, 1, self.max_decode_len, lanes), self.dtype)
        i = index.value
        if initialized:
            if i.ndim:  # per-row positions: scatter, out of range dropped
                cached.value = cached.value.at[
                    jnp.arange(b)[:, None], :, i[:, None] + jnp.arange(s)
                ].set(jnp.moveaxis(entry, 1, 2))
            else:
                cached.value = jax.lax.dynamic_update_slice(
                    cached.value, entry, (0, 0, i, 0))
            index.value = i + s
        o = absorbed(lambda q_lat: decode_attention(
            q_lat, cached.value[..., :rank + rope],
            cached.value[..., :rank], i, scale=scale,
            chunk=128 if s > 1 else 512))
        return out(o.transpose(0, 2, 1, 3).reshape(b, s, h * vd))


# What a block mixes tokens with (`LlamaBlock.operator`); `Llama` derives
# it a layer from `layer_types` and the latent widths.
OPERATORS = ("attention", "latent", "conv")
# The published `layer_types` entries, by the operator they name.
LAYER_TYPES = {"full_attention": "attention", "conv": "conv"}


class ShortConv(nn.Module):
    """Gated short convolution (the LFM2 lineage's ``conv`` operator).

    With ``u`` the block's normed input at position ``t``: ``[B | C | X] =
    u W_in`` (three ``E``-wide parts, in that order, no bias); ``z = B *
    X``; ``c_t = sum_j taps[j] * z_{t - (K-1) + j}`` — a causal depthwise
    convolution, one ``K``-tap kernel a channel, ``taps[K-1]`` on the
    current token, ``z_t = 0`` before the sequence; the operator is ``(C *
    c) W_out``. Nothing is cached per token: after token ``t`` the
    operator's whole memory is ``(z_{t-K+2} .. z_t)``, ``K - 1`` rows of
    ``E`` values whatever the context.

    ``decode=True`` keeps that state in the ``cache`` collection under
    ``SLOT_STATE_KEY``, ``[rows, K-1, E]`` in the compute dtype, beside a
    position counter (``cache_index``, stamped like every attention's):

    - a block whose position is 0 starts from zeros whatever the row
      held (a slot's previous stream never leaks into the next);
    - per-row positions ``[B]`` (the serving tick): batch row ``i`` IS
      state row ``i``, one token a row; a row at position 0 is a parked
      slot, and leaves its state row as it was (a prompt being prefilled
      in slices into that row is not overwritten by the ticks between);
    - a scalar position: one block of ``s`` tokens for every row, of
      which the first ``valid_len`` are real (the engine's right-padded
      chunk; all of them outside a paged engine) — the state left is
      that of the last ``K - 1`` REAL positions, the state handed in
      shifted along where the block holds fewer. In a paged engine the
      batch-1 chunk reaches its row through the stamped slot index
      (``STATE_SLOT_KEY``; `kvcache.paged_decode_cache` puts it and the
      ``valid_len`` leaf beside the state).

    Three taps over ``E`` channels are an elementwise fusion beside two
    matmuls: no kernel of its own (``shortconv_roofline_pct`` in the
    benchmark is the witness)."""

    kernel_size: int = 3  # conv_L_cache
    decode: bool = False
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("shortconv"):
            return self._apply(x)

    @staticmethod
    def _split_in(projected):
        """``[B | C | X]`` of the in projection, in that order."""
        return jnp.split(projected, 3, axis=-1)

    @staticmethod
    def _history(held, position):
        """The rows a block starts behind: what the state held, or zeros
        where the block starts a sequence (position 0)."""
        fresh = (position == 0)[..., None, None]
        return jnp.where(fresh, jnp.zeros_like(held), held)

    def _state_after(self, zp, valid):
        """The state ``valid`` real tokens leave: ``zp [B, K-1+s, E]`` is
        the block behind its history, so row ``valid + j`` holds ``z`` of
        block position ``valid - (K-1) + j``."""
        return jax.lax.dynamic_slice_in_dim(zp, valid, self.kernel_size - 1,
                                            axis=1)

    def _apply(self, x):
        from pddl_tpu.ops.moe import VALID_LEN_KEY

        b, s, e = x.shape
        k = self.kernel_size
        if k < 2:
            raise ValueError(f"a short convolution needs >= 2 taps, got {k}")
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  param_dtype=self.param_dtype)
        gate_in, gate_out, val = self._split_in(
            dense(3 * e, name="in_proj")(x))
        z = (gate_in * val).astype(self.dtype)
        taps = self.param("taps", nn.initializers.lecun_normal(), (k, e),
                          self.param_dtype)
        out = dense(e, name="out_proj")

        def convolve(zp):
            """Float32 sums, like the family's norms."""
            c = sum(taps[j].astype(jnp.float32)
                    * zp[:, j:j + s].astype(jnp.float32) for j in range(k))
            return out(gate_out * c.astype(self.dtype))

        if not self.decode:
            return convolve(jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0))))

        initialized = self.has_variable("cache", SLOT_STATE_KEY)
        state = self.variable("cache", SLOT_STATE_KEY, jnp.zeros,
                              (b, k - 1, e), self.dtype)
        index = self.variable("cache", "cache_index",
                              lambda: jnp.zeros((), jnp.int32))
        i = index.value
        if i.ndim and s != 1:
            # The speculative verify block: a rejected draft would have
            # to be taken back out of the state, which no counter stamp
            # does (`Llama.unrewindable_cache`).
            raise ValueError(
                "per-row positions over a short-convolution state support "
                f"single-token steps only (got a {s}-token block)")
        # A paged engine's batch-1 chunk: its row and its real length
        # are stamped beside the state.
        paged = self.has_variable("cache", STATE_SLOT_KEY)
        chunk = paged and not i.ndim
        valid = s
        if chunk:
            slot = self.variable("cache", STATE_SLOT_KEY, lambda: None).value
            valid = self.variable("cache", VALID_LEN_KEY, lambda: None).value
            held = jax.lax.dynamic_slice_in_dim(state.value, slot, b, axis=0)
        else:
            held = state.value
        zp = jnp.concatenate([self._history(held, i), z], axis=1)
        if initialized:
            left = self._state_after(zp, valid)
            if chunk:
                state.value = jax.lax.dynamic_update_slice_in_dim(
                    state.value, left, slot, axis=0)
            elif paged:
                # The tick: a row at position 0 is a parked slot, and
                # its state row may be a prompt's, mid-way through a
                # sliced prefill.
                state.value = jnp.where((i == 0)[:, None, None], held, left)
            else:
                state.value = left
            index.value = i + s
        return convolve(zp)


class LlamaBlock(nn.Module):
    """Pre-RMSNorm residual block: attention then a SwiGLU MLP — dense,
    or routed over ``moe_experts`` gated experts (the Mixtral block:
    ``block_sparse_moe`` with top-``moe_top_k`` routing).
    ``moe_router_input="attn"`` moves the router in front of the
    attention: logits from the attention's normed input (a bias-free
    ``router`` of the block's own), experts applied to the MLP's.
    ``operator`` says what the block mixes tokens with — ``"attention"``
    (:class:`LlamaAttention`), ``"latent"`` (:class:`LatentAttention`, at
    the five latent widths) or ``"conv"`` (:class:`ShortConv`);
    ``moe_intermediate_dim`` gives the experts a width of their own."""

    num_heads: int
    num_kv_heads: int
    intermediate_dim: int
    head_dim: Optional[int] = None
    rope: bool = True
    rope_theta: float = 10000.0
    attention: str = "flash"
    sliding_window: Optional[int] = None
    qkv_bias: bool = False
    mesh: Optional[Any] = None
    decode: bool = False
    max_decode_len: int = 1024
    moe_experts: int = 0  # >0: Mixtral-style routed SwiGLU experts
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_eval_dropless: bool = True  # eval/serving is dropless
    moe_act: str = "swiglu"  # "swiglu" | "reglu"
    moe_router_input: str = "mlp"  # "mlp" | "attn"
    moe_intermediate_dim: Optional[int] = None  # None: intermediate_dim
    moe_router_score: str = "softmax"  # "softmax" | "sigmoid"
    moe_select_bias: bool = False
    moe_gate_scale: float = 1.0
    moe_shared_experts: int = 0
    operator: str = "attention"  # one of OPERATORS
    kv_lora_rank: int = 0  # the five widths of operator="latent"
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    qk_norm: bool = False
    conv_kernel: int = 3  # operator="conv": taps (conv_L_cache)
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True, /):
        # train is positional-only for remat static_argnums — see
        # vit.TransformerBlock. (SwiGLU has no dropout; train gates the
        # MoE capacity rule: routed blocks drop over-capacity tokens in
        # training but run DROPLESS at eval/serving.)
        e = x.shape[-1]
        if self.moe_router_input not in ("mlp", "attn"):
            raise ValueError(
                f"unknown moe_router_input {self.moe_router_input!r}")
        h = _rms_norm(self.rms_eps, self.param_dtype, "ln1")(x)
        router_logits = None
        if self.moe_experts and self.moe_router_input == "attn":
            with jax.named_scope("moe_router"):
                router_logits = nn.Dense(
                    self.moe_experts, use_bias=False, dtype=jnp.float32,
                    param_dtype=self.param_dtype, name="router")(h)
        if self.operator not in OPERATORS:
            raise ValueError(f"unknown block operator {self.operator!r} "
                             f"(one of {OPERATORS})")
        if self.operator == "conv":
            attn = ShortConv(
                kernel_size=self.conv_kernel, decode=self.decode,
                dtype=self.dtype, param_dtype=self.param_dtype, name="conv")
        elif self.operator == "latent":
            attn = LatentAttention(
                num_heads=self.num_heads, q_lora_rank=self.q_lora_rank,
                kv_lora_rank=self.kv_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, rope_theta=self.rope_theta,
                attention=self.attention, rms_eps=self.rms_eps,
                decode=self.decode, max_decode_len=self.max_decode_len,
                dtype=self.dtype, param_dtype=self.param_dtype, name="attn")
        else:
            attn = LlamaAttention(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, rope=self.rope,
                rope_theta=self.rope_theta, attention=self.attention,
                sliding_window=self.sliding_window, qkv_bias=self.qkv_bias,
                qk_norm=self.qk_norm, rms_eps=self.rms_eps,
                mesh=self.mesh, decode=self.decode,
                max_decode_len=self.max_decode_len, dtype=self.dtype,
                param_dtype=self.param_dtype, name="attn")
        x = x + attn(h.astype(self.dtype))

        h = _rms_norm(self.rms_eps, self.param_dtype, "ln2")(x)
        h = h.astype(self.dtype)
        if self.moe_experts:
            from pddl_tpu.ops.moe import SwitchFFN

            h = SwitchFFN(
                num_experts=self.moe_experts,
                hidden_dim=self.moe_intermediate_dim
                or self.intermediate_dim, top_k=self.moe_top_k,
                capacity_factor=self.moe_capacity_factor,
                eval_dropless=self.moe_eval_dropless,
                expert_act=self.moe_act,
                router_score=self.moe_router_score,
                select_bias=self.moe_select_bias,
                gate_scale=self.moe_gate_scale,
                shared_experts=self.moe_shared_experts, dtype=self.dtype,
                param_dtype=self.param_dtype, name="moe",
            )(h, train, router_logits=router_logits)
            return x + h
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  param_dtype=self.param_dtype)
        gate = dense(self.intermediate_dim, name="mlp_gate")(h)
        up = dense(self.intermediate_dim, name="mlp_up")(h)
        h = dense(e, name="mlp_down")(nn.silu(gate) * up)
        return x + h


class Llama(nn.Module):
    """Decoder-only Llama-architecture LM: tokens ``[B, S]`` → logits.

    Interface-compatible with :class:`pddl_tpu.models.gpt.GPT` where it
    matters — ``max_len``/``decode``/``vocab_size``/``vocab_multiple``/
    ``dtype`` attributes, ``features_only`` apply mode, ``lm_head``
    param naming — so :func:`pddl_tpu.models.gpt.generate` and
    :func:`pddl_tpu.models.gpt.fused_lm_loss` work on it unchanged.
    The same contract is the MULTI-TENANT serving hook
    (`serve/tenant/`): :func:`pddl_tpu.models.gpt.lm_head_logits` and
    :func:`~pddl_tpu.models.gpt.prefill_row_features` reproduce
    :class:`_LlamaHead` op-for-op from the ``features_only`` output
    (bias-free ``lm_head``, padded-vocab slice, f32 cast — keep the
    three in sync), which is what lets per-slot LoRA deltas and
    grammar masks compose onto Llama logits token-exactly.
    """

    vocab_size: int
    max_len: int = 2048
    embed_dim: int = 512
    depth: int = 4
    num_heads: int = 8
    num_kv_heads: Optional[int] = None  # None → MHA (= num_heads)
    intermediate_dim: Optional[int] = None  # None → SwiGLU-standard ~8E/3
    rope_theta: float = 10000.0
    attention: str = "flash"
    head_dim: Optional[int] = None  # None: embed_dim // num_heads
    sliding_window: Optional[int] = None  # Mistral-style SWA width
    # Per-layer layouts (a 0/1 entry a layer, the published
    # `sliding_window_layout` / `rope_layout` of the window/NoPE
    # hybrids). None: every layer attends through `sliding_window` (if
    # set) and every layer rotates.
    sliding_window_layout: Optional[tuple] = None
    rope_layout: Optional[tuple] = None
    qkv_bias: bool = False  # Qwen2-style q/k/v biases
    mesh: Optional[Any] = None
    remat: str = "none"
    vocab_multiple: int = 1  # pad V for vocab-parallel TP (see gpt.GPT)
    decode: bool = False
    moe_experts: int = 0  # >0: Mixtral — routed SwiGLU experts
    moe_top_k: int = 2  # Mixtral's num_experts_per_tok
    moe_every: int = 1  # Mixtral puts MoE in EVERY layer
    moe_capacity_factor: float = 2.0
    moe_eval_dropless: bool = True  # eval/serving is dropless
    moe_act: str = "swiglu"  # "swiglu" | "reglu" (ReLU-gated experts)
    moe_router_input: str = "mlp"  # "attn": router before attention
    # A 0/1 entry a layer: which layers route (None: `moe_every`'s
    # rule). The DeepSeek lineage's `first_k_dense_replace` is zeros
    # first; `intermediate_dim` is then the dense layers' width and
    # `moe_intermediate_dim` an expert's (None: the same).
    moe_layout: Optional[tuple] = None
    moe_intermediate_dim: Optional[int] = None
    moe_router_score: str = "softmax"  # "sigmoid": per-expert scores
    moe_select_bias: bool = False  # a bias on the choice of experts only
    moe_gate_scale: float = 1.0  # routed_scaling_factor
    moe_shared_experts: int = 0  # always-on experts beside the routed
    # The operator a layer has, by the published key: "conv"
    # (`ShortConv`) or "full_attention" (None: attention everywhere).
    # An attention layer is `LatentAttention` when `kv_lora_rank > 0`,
    # else `LlamaAttention`; `layer_operator` is the ONE reading of both.
    layer_types: Optional[tuple] = None
    conv_kernel: int = 3  # conv_L_cache: the short convolution's taps
    qk_norm: bool = False  # per-head RMSNorm of q and k before RoPE
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    def layer_operator(self, i: int) -> str:
        """Layer ``i``'s operator, one of :data:`OPERATORS`."""
        if self.layer_types is not None:
            kind = self.layer_types[i]
            if kind not in LAYER_TYPES:
                raise ValueError(
                    f"layer_types[{i}] = {kind!r}: not one of "
                    f"{sorted(LAYER_TYPES)}")
            if LAYER_TYPES[kind] == "conv":
                return "conv"
        return "latent" if self.kv_lora_rank else "attention"

    def _layers_of(self, operator: str) -> int:
        return sum(self.layer_operator(i) == operator
                   for i in range(self.depth))

    @property
    def latent_layers(self) -> int:
        """How many layers a paged chunk program re-expands cached
        entries for (`serve/metrics.py` ``latent_expanded_tokens``)."""
        return self._layers_of("latent")

    @property
    def slot_state_layers(self) -> int:
        """How many layers keep a fixed state a serving slot and no
        per-token cache entry (the short convolutions). A prefix hit
        restores an attention layer and not these, so a paged engine
        neither matches nor donates prefixes for such a model
        (`serve/engine.py`, "slot state")."""
        return self._layers_of("conv")

    @property
    def unrewindable_cache(self) -> bool:
        """True when stamping a position counter back does NOT take a
        rejected block out of the cache: a rolling ring cache has
        recycled the slots (:attr:`uses_ring_cache`), a short
        convolution's state has moved on. THE property speculative
        decoding and the serving engine read before they draft."""
        return self.uses_ring_cache or self.slot_state_layers > 0

    def layer_window(self, i: int) -> Optional[int]:
        """Layer ``i``'s attention window (None: full attention)."""
        if self.sliding_window_layout is None \
                or self.sliding_window_layout[i]:
            return self.sliding_window
        return None

    def layer_rope(self, i: int) -> bool:
        return self.rope_layout is None or bool(self.rope_layout[i])

    def moe_layer(self, i: int) -> bool:
        """Whether layer ``i`` routes: ``moe_layout``'s entry, else every
        ``moe_every``-th block counted from the back like ViT (Mixtral's
        ``moe_every=1``: every block)."""
        if not self.moe_experts:
            return False
        if self.moe_layout is not None:
            return bool(self.moe_layout[i])
        return (self.depth - 1 - i) % self.moe_every == 0

    @property
    def uses_ring_cache(self) -> bool:
        """True when SWA decode (outside a paged engine) allocates a
        rolling ring cache (slots recycle — cannot be rewound;
        speculative decoding and the serving engine's draft-model
        check read this). Same decision, same code as the cache allocation:
        :func:`ring_len` over the blocks' ``max_decode_len`` (=
        ``max_len``, line where the blocks are built), for any layer."""
        return any(ring_len(self.layer_window(i), self.max_len) is not None
                   for i in range(self.depth)
                   if self.layer_operator(i) == "attention")

    def paged_cache_extras(self) -> dict:
        """Cache leaves a PAGED serving engine adds beside each
        attention's pool (`kvcache.paged_decode_cache` merges them in):
        per routed block, the expert-load counters the MoE layer
        accumulates over prompt tokens and the valid-length scalar the
        chunk programs stamp (`ops/moe.py` ``EXPERT_LOAD_KEY``)."""
        from pddl_tpu.ops.moe import EXPERT_LOAD_KEY, VALID_LEN_KEY

        return {f"block{i}": {"moe": {
            EXPERT_LOAD_KEY: jnp.zeros((self.moe_experts,), jnp.int32),
            VALID_LEN_KEY: jnp.zeros((), jnp.int32)}}
            for i in range(self.depth) if self.moe_layer(i)}

    @nn.compact
    def __call__(self, tokens, *, train: bool = True,
                 features_only: bool = False):
        kv = self.num_kv_heads or self.num_heads
        inter = self.intermediate_dim
        if inter is None:
            inter = _default_intermediate_dim(self.embed_dim)
        # Stem/head shared with GPipeLlama; share_scope keeps the param
        # names (embed/ln_final/lm_head) at this module's top level.
        embed = _LlamaEmbed(vocab_size=self.vocab_size,
                            embed_dim=self.embed_dim,
                            vocab_multiple=self.vocab_multiple,
                            dtype=self.dtype, param_dtype=self.param_dtype)
        nn.share_scope(self, embed)
        x = embed(tokens)

        block_cls = (LlamaBlock if self.decode
                     else remat_block(LlamaBlock, self.remat))
        for layout in (self.sliding_window_layout, self.rope_layout,
                       self.moe_layout, self.layer_types):
            if layout is not None and len(layout) != self.depth:
                raise ValueError(
                    f"a per-layer layout needs {self.depth} entries, got "
                    f"{len(layout)}")
        for i in range(self.depth):
            x = block_cls(
                num_heads=self.num_heads, num_kv_heads=kv,
                intermediate_dim=inter, head_dim=self.head_dim,
                rope=self.layer_rope(i), rope_theta=self.rope_theta,
                attention=self.attention,
                sliding_window=self.layer_window(i),
                qkv_bias=self.qkv_bias, mesh=self.mesh,
                decode=self.decode, max_decode_len=self.max_len,
                moe_experts=self.moe_experts if self.moe_layer(i) else 0,
                moe_top_k=self.moe_top_k,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_eval_dropless=self.moe_eval_dropless,
                moe_act=self.moe_act,
                moe_router_input=self.moe_router_input,
                moe_intermediate_dim=self.moe_intermediate_dim,
                moe_router_score=self.moe_router_score,
                moe_select_bias=self.moe_select_bias,
                moe_gate_scale=self.moe_gate_scale,
                moe_shared_experts=self.moe_shared_experts,
                operator=self.layer_operator(i),
                kv_lora_rank=self.kv_lora_rank,
                q_lora_rank=self.q_lora_rank,
                qk_nope_head_dim=self.qk_nope_head_dim,
                qk_rope_head_dim=self.qk_rope_head_dim,
                v_head_dim=self.v_head_dim, qk_norm=self.qk_norm,
                conv_kernel=self.conv_kernel,
                rms_eps=self.rms_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block{i}",
            )(x, train)

        head = _LlamaHead(vocab_size=self.vocab_size,
                          vocab_multiple=self.vocab_multiple,
                          rms_eps=self.rms_eps, dtype=self.dtype,
                          param_dtype=self.param_dtype,
                          features_only=features_only)
        nn.share_scope(self, head)
        return head(x)


def tiny_llama(vocab_size: int = 64, **kwargs) -> Llama:
    """Miniature Llama for tests/dry-runs (GQA exercised: 4 q / 2 kv)."""
    kwargs.setdefault("max_len", 128)
    kwargs.setdefault("embed_dim", 32)
    kwargs.setdefault("depth", 2)
    kwargs.setdefault("num_heads", 4)
    kwargs.setdefault("num_kv_heads", 2)
    kwargs.setdefault("attention", "reference")
    return Llama(vocab_size=vocab_size, **kwargs)


# GPT-2-small-comparable shape (12x768, GQA 12/4) — the benchmark
# configuration (`benchmarks/gpt_train_bench.py --family llama`,
# `benchmarks/decode_bench.py`).
Llama_Small = functools.partial(
    Llama, embed_dim=768, depth=12, num_heads=12, num_kv_heads=4)

# ~300M-parameter mid-size shape (GQA 16/4): big enough that bf16
# parameter/optimizer storage meaningfully matters, small enough to train
# f32 on one chip with no remat — the f32-vs-bf16 convergence comparison
# shape (docs/CONVERGENCE.md).
Llama_300M = functools.partial(
    Llama, embed_dim=1280, depth=16, num_heads=20, num_kv_heads=4,
    intermediate_dim=3456)

# Llama-3.2-1B-shaped config (RoPE theta 500k, GQA 32/8). Fits one v5e
# chip in bf16 for training at moderate batch; the multi-chip strategies
# apply as with every family.
Llama_1B = functools.partial(
    Llama, embed_dim=2048, depth=16, num_heads=32, num_kv_heads=8,
    intermediate_dim=8192, rope_theta=500000.0, max_len=4096)


# SmallThinker-21B-A3B-Instruct (PowerInfer, 2025-07; HF config.json):
# 52 layers x 2560, 28 q / 4 kv heads of 128 (q width 3584), every layer
# 64 ReLU-gated experts of 768 top-6 behind a bias-free router that reads
# the attention's normed input; period of four layers [full attention
# with NO position encoding, then three with a 4096 window and RoPE at
# theta 1.5e6]; RMSNorm eps 1e-6; untied head over 151,936 tokens; 16,384
# positions. `depth` is the caller's (a chip holds 12 of the 52 in bf16):
# the layouts repeat the published period. Served at random weights
# only — no checkpoint import maps the published tensor names yet.
SMALLTHINKER_PERIOD = (0, 1, 1, 1)


def _smallthinker_layout(depth: int) -> tuple:
    return tuple(SMALLTHINKER_PERIOD[i % 4] for i in range(depth))


def SmallThinker_21B_A3B(depth: int = 52, **kwargs) -> Llama:
    layout = _smallthinker_layout(depth)
    kwargs.setdefault("max_len", 16384)
    return Llama(
        vocab_size=151936, embed_dim=2560, depth=depth, num_heads=28,
        num_kv_heads=4, head_dim=128, intermediate_dim=768,
        rope_theta=1.5e6, sliding_window=4096,
        sliding_window_layout=layout, rope_layout=layout,
        moe_experts=64, moe_top_k=6, moe_act="reglu",
        moe_router_input="attn", rms_eps=1e-6, **kwargs)


def tiny_smallthinker(vocab_size: int = 64, **kwargs) -> Llama:
    """The same block at test size: one period [global+NoPE, 3 x
    window+RoPE], window 8, 7:1 GQA with ``head_dim != embed/heads``,
    8 ReLU-gated experts top-2 routed from the attention's input."""
    layout = _smallthinker_layout(kwargs.get("depth", 4))
    defaults = dict(
        depth=4, max_len=128, embed_dim=40, num_heads=7, num_kv_heads=1,
        head_dim=8, intermediate_dim=16, rope_theta=1.5e6,
        sliding_window=8, sliding_window_layout=layout, rope_layout=layout,
        moe_experts=8, moe_top_k=2, moe_act="reglu",
        moe_router_input="attn", rms_eps=1e-6, attention="reference")
    return Llama(vocab_size=vocab_size, **{**defaults, **kwargs})


# GLM-4.7-Flash (zai-org, HF config.json, `glm4_moe_lite`): 47 layers x
# 2048; 20 heads of latent attention (q rank 768, kv rank 512, 192 + 64
# rope dims a key, 256 a value, theta 1e6); layer 0 a dense SwiGLU MLP of
# 10,240, layers 1-46 64 SwiGLU experts of 1,536 top-4 chosen by sigmoid
# scores plus a selection-only bias, gates renormalised and scaled by
# 1.8, beside one shared expert; RMSNorm 1e-5; untied head over 154,880
# tokens. Its multi-token-prediction block is not built. `depth` and
# `max_len` are the caller's (202,752 positions published). Served at
# random weights only.
_GLM_FLASH = dict(
    num_heads=20, kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, rope_theta=1e6, moe_experts=64,
    moe_top_k=4, moe_router_score="sigmoid", moe_select_bias=True,
    moe_gate_scale=1.8, moe_shared_experts=1, rms_eps=1e-5)


def GLM_4_7_Flash(depth: int = 47, **kwargs) -> Llama:
    defaults = dict(
        _GLM_FLASH, vocab_size=154880, embed_dim=2048,
        intermediate_dim=10240, moe_intermediate_dim=1536,
        moe_layout=(0,) + (1,) * (depth - 1))
    return Llama(depth=depth, **{**defaults, **kwargs})


def tiny_glm_flash(vocab_size: int = 64, **kwargs) -> Llama:
    """The same block at test size: a dense layer then routed ones, 8
    experts top-2 beside a shared one, 4 latent heads of 12 + 4 rope
    dims a key and 16 a value over a cache entry of 24 + 4 values."""
    depth = kwargs.get("depth", 3)
    defaults = dict(
        _GLM_FLASH, depth=depth, max_len=128, embed_dim=32, num_heads=4,
        kv_lora_rank=24, q_lora_rank=20, qk_nope_head_dim=12,
        qk_rope_head_dim=4, v_head_dim=16, intermediate_dim=48,
        moe_intermediate_dim=16, moe_layout=(0,) + (1,) * (depth - 1),
        moe_experts=8, moe_top_k=2, attention="reference")
    return Llama(vocab_size=vocab_size, **{**defaults, **kwargs})


# LFM2-24B-A2B (LiquidAI, HF config.json, `lfm2_moe`): 40 layers x 2048;
# `layer_types` conv, conv, then [full_attention, conv, conv, conv] ten
# times less the last two: 30 gated short convolutions (3 taps, no bias)
# and 10 attention layers (32 q / 8 kv heads of 64, q and k RMS-normed a
# head before RoPE at theta 1e6); layers 0-1 a dense SwiGLU MLP of 11,776,
# the other 38 64 SwiGLU experts of 1,536 top-4 by sigmoid scores plus a
# selection-only bias, gates renormalised, scale 1, no shared expert;
# RMSNorm 1e-5; head over 65,536 tokens (untied here like the family's
# other two); 128,000 positions. A `depth` under 40 is a cut for one
# chip: the leading dense layers counted once, then the pattern from
# layer 2 on. Served at random weights only.
LFM2_DENSE_LAYERS = 2


def lfm2_layer_types(depth: int = 40) -> tuple:
    return tuple("full_attention" if i % 4 == 2 else "conv"
                 for i in range(depth))


_LFM2 = dict(
    qk_norm=True, conv_kernel=3, rope_theta=1e6, moe_top_k=4,
    moe_router_score="sigmoid", moe_select_bias=True, moe_gate_scale=1.0,
    rms_eps=1e-5)


def _lfm2_layouts(depth: int, published: int = 40) -> dict:
    types, dense = lfm2_layer_types(published), LFM2_DENSE_LAYERS
    if depth < published:
        types, dense = types[:1] + types[dense:dense + depth - 1], 1
    return dict(layer_types=types,
                moe_layout=(0,) * dense + (1,) * (depth - dense))


def LFM2_24B_A2B(depth: int = 40, **kwargs) -> Llama:
    defaults = dict(
        _LFM2, vocab_size=65536, max_len=128000, embed_dim=2048,
        num_heads=32, num_kv_heads=8, head_dim=64, intermediate_dim=11776,
        moe_intermediate_dim=1536, moe_experts=64, **_lfm2_layouts(depth))
    return Llama(depth=depth, **{**defaults, **kwargs})


def tiny_lfm2(vocab_size: int = 64, **kwargs) -> Llama:
    """The same block at test size: a dense convolution layer, then one
    whole period [attention, 3 x convolution] routed over 8 experts
    top-4; 4 q heads over 2 kv heads of 8, q/k norm, 3 taps."""
    depth = kwargs.get("depth", 5)
    defaults = dict(
        _LFM2, depth=depth, max_len=128, embed_dim=32, num_heads=4,
        num_kv_heads=2, head_dim=8, intermediate_dim=48,
        moe_intermediate_dim=16, moe_experts=8, attention="reference",
        **_lfm2_layouts(depth))
    return Llama(vocab_size=vocab_size, **{**defaults, **kwargs})


class _LlamaEmbed(nn.Module):
    """Token embedding (the pre-pipeline Llama stem; RoPE needs no
    positional parameters — positions enter inside each block)."""

    vocab_size: int
    embed_dim: int
    vocab_multiple: int = 1
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        padded_v = -(-self.vocab_size // self.vocab_multiple) * self.vocab_multiple
        return nn.Embed(padded_v, self.embed_dim, dtype=self.dtype,
                        param_dtype=self.param_dtype, name="embed")(tokens)


class _LlamaStage(nn.Module):
    """One pipeline stage: a run of Llama blocks.

    PP splits LAYERS, never the sequence, so each block's internal
    ``arange(S)`` RoPE positions stay correct on every stage."""

    num_heads: int
    num_kv_heads: int
    intermediate_dim: int
    blocks: int
    rope_theta: float = 10000.0
    attention: str = "reference"
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        for i in range(self.blocks):
            x = LlamaBlock(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                intermediate_dim=self.intermediate_dim,
                rope_theta=self.rope_theta, attention=self.attention,
                rms_eps=self.rms_eps, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block{i}",
            )(x, False)
        return x


class _LlamaHead(nn.Module):
    """Final RMSNorm + bias-free LM head (shared by :class:`Llama` via
    ``share_scope`` and by :class:`GPipeLlama` as the post-pipeline
    projection)."""

    vocab_size: int
    vocab_multiple: int = 1
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    features_only: bool = False  # stop after ln_final (fused-CE path)

    @nn.compact
    def __call__(self, x):
        x = _rms_norm(self.rms_eps, self.param_dtype, "ln_final")(x)
        if self.features_only and not self.is_initializing():
            # Pre-head features for fused CE. init() falls through to the
            # Dense regardless (like gpt._GPTHead), so lm_head params
            # exist even when the first trace goes through fused_lm_loss.
            return x.astype(self.dtype)
        padded_v = -(-self.vocab_size // self.vocab_multiple) * self.vocab_multiple
        logits = nn.Dense(padded_v, use_bias=False, dtype=self.dtype,
                          param_dtype=self.param_dtype, name="lm_head")(
                              x.astype(self.dtype))
        return logits[..., :self.vocab_size].astype(jnp.float32)


class GPipeLlama(GPipeModel):
    """Pipeline-parallel modern-decoder LM: PP x the Llama architecture —
    token embed (replicated) → ``n_stages`` stacked RoPE/RMSNorm/SwiGLU
    stages through the GPipe schedule → bias-free head (replicated).
    Completes the PP row of the parallelism x family matrix alongside
    :class:`pddl_tpu.models.vit.GPipeViT` and
    :class:`pddl_tpu.models.gpt.GPipeGPT`."""

    def __init__(self, *, vocab_size: int, n_stages: int,
                 blocks_per_stage: int, n_microbatches: int, mesh,
                 embed_dim: int = 256, num_heads: int = 4,
                 num_kv_heads: Optional[int] = None,
                 intermediate_dim: Optional[int] = None,
                 rope_theta: float = 10000.0,
                 attention: str = "reference", rms_eps: float = 1e-5,
                 remat_stages: bool = False, layer_types=None,
                 dtype: Any = jnp.float32, param_dtype: Any = jnp.float32):
        if layer_types is not None:
            raise NotImplementedError(
                "GPipeLlama stacks identical attention blocks: a model "
                "that mixes operators by layer (layer_types) has no "
                "pipeline stage yet")
        kv = num_kv_heads or num_heads
        if intermediate_dim is None:
            intermediate_dim = _default_intermediate_dim(embed_dim)
        super().__init__(
            embed=_LlamaEmbed(vocab_size=vocab_size, embed_dim=embed_dim,
                              dtype=dtype, param_dtype=param_dtype),
            stage=_LlamaStage(num_heads=num_heads, num_kv_heads=kv,
                              intermediate_dim=intermediate_dim,
                              blocks=blocks_per_stage,
                              rope_theta=rope_theta, attention=attention,
                              rms_eps=rms_eps, dtype=dtype,
                              param_dtype=param_dtype),
            head=_LlamaHead(vocab_size=vocab_size, rms_eps=rms_eps,
                            dtype=dtype, param_dtype=param_dtype),
            n_stages=n_stages, n_microbatches=n_microbatches, mesh=mesh,
            remat_stages=remat_stages,
        )
