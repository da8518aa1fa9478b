"""Vision Transformer family: the framework's attention-bearing model line.

The reference repo is ResNet-only (``tf.keras.applications.ResNet50``,
``/root/reference/imagenet-resnet50.py:56``); the ViT family exists because
the TPU build treats long-context/attention workloads as first-class
(SURVEY.md §5 "Long-context") — it is the model that exercises
:mod:`pddl_tpu.ops.attention` (flash kernel) and
:mod:`pddl_tpu.ops.ring_attention` (sequence parallelism), and it trains
under every distribution strategy exactly like the ResNets (same Trainer,
same data pipeline, same ``{"image", "label"}`` batches).

TPU-first choices:

- token count = (image/patch)² stays MXU-friendly (multiples of 128 for
  standard configs: 224/16 → 196 tokens + padding-free mean-pool head).
- bf16 compute / f32 params, f32 LayerNorm and softmax (numerics).
- ``attention="flash"`` routes through the Pallas kernel on TPU and the
  reference path elsewhere; ``attention="ring"`` shard-maps over the
  ``seq`` mesh axis for sequence-parallel long-context runs
  (``"ring_flash"``: same, with the flash kernel per rotation).
- no data-dependent control flow; everything jits to one XLA program.
"""

from __future__ import annotations

import functools
from typing import Any, Optional


import jax
import jax.numpy as jnp
from flax import linen as nn

from pddl_tpu.models.gpipe import GPipeModel
from pddl_tpu.ops.attention import (
    attention_reference,
    decode_attention,
    flash_attention,
    paged_cache_insert,
    paged_decode_attention,
    paged_kv_fuse,
)

# The paged-serving cache leaf names (`ops/attention.paged_*`,
# `serve/kvcache/block_pool.paged_decode_cache`). The block table's
# PRESENCE in a cache collection is what flips the attention modules
# (this file's MHA and llama's) onto the paged path, so the names are
# registry constants like `gpt.CACHE_INDEX_KEYS` — the modules, the
# engine's stamp helper and the pool builder all match by them, never
# by shape duck typing.
BLOCK_TABLE_KEY = "block_table"
PAGED_KV_KEY = "cached_kv"
# A layer that keeps a FIXED state a serving slot and no per-token entry
# (`llama.ShortConv`) declares it under SLOT_STATE_KEY, ``[rows, ...]``:
# rows by slots, never by blocks — the pool builder sizes it by the slot
# count and builds no pool and no table for such a layer. STATE_SLOT_KEY
# is the slot index a batch-1 chunk program reaches its row through
# (stamped like the tables, canonical placeholder scalar 0). Every walker
# of a cache tree tells a pool from a state leaf by these keys.
SLOT_STATE_KEY = "slot_state"
STATE_SLOT_KEY = "state_slot"


def paged_decode_step(module: nn.Module, index, q, entry, **attend):
    """The paged branch of an attention module's decode step, shared by
    the MHA below, `llama.LlamaAttention` and `llama.LatentAttention`:
    write this call's cache entries ``[B, H_c, s, lanes]`` (cache dtype,
    post-RoPE: a K/V layer's ``paged_kv_fuse(k, v)``, a latent layer's
    ``[c_kv | k_rope]``) into the pool through the slot's block table,
    advance the counter ``index`` (the module's own ``cache_index``
    variable), attend over the pool. ``attend`` is what the layer
    declares of its entries and its mask to
    :func:`~pddl_tpu.ops.attention.paged_decode_attention`
    (``window``, ``scale``, ``value_lanes``, ``expand``). The paged
    cache collection holds exactly three leaves per module — the pool
    ``[N, H_c, block_size, lanes]``, the counter and the table —
    DECLARED (not just read) so the mutated cache keeps them and the
    donated tree's structure stays stable. Returns ``[B, H, s, Dv]``."""
    pool = module.variable("cache", PAGED_KV_KEY, lambda: None)
    table = module.variable(
        "cache", BLOCK_TABLE_KEY,
        lambda: jnp.zeros((1, 1), jnp.int32)).value
    i = index.value
    pool.value = paged_cache_insert(pool.value, entry, table, i)
    index.value = i + q.shape[2]
    return paged_decode_attention(q, pool.value, table, i, **attend)


class MultiHeadAttention(nn.Module):
    """MHA over our attention ops (``[B, S, E]`` in/out).

    ``decode=True`` enables single-token autoregressive decoding with a KV
    cache (``"cache"`` variable collection): each call consumes one token
    (``S == 1``), appends its K/V at the running index, and attends over
    the cached prefix — the generation path of the GPT family.
    """

    num_heads: int
    attention: str = "flash"  # "flash" | "reference" | "ring" | "ring_flash"
    mesh: Optional[Any] = None  # required for "ring"
    causal: bool = False  # decoder-style masking (the GPT family)
    decode: bool = False  # KV-cache single-token decoding
    max_decode_len: int = 1024
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, s, e = x.shape
        if e % self.num_heads:
            raise ValueError(f"embed dim {e} not divisible by {self.num_heads} heads")
        head_dim = e // self.num_heads
        dense = functools.partial(
            nn.DenseGeneral, dtype=self.dtype, param_dtype=self.param_dtype,
        )
        # [B, S, H, D] then transpose to the kernel layout [B, H, S, D].
        q = dense(features=(self.num_heads, head_dim), name="query")(x)
        k = dense(features=(self.num_heads, head_dim), name="key")(x)
        v = dense(features=(self.num_heads, head_dim), name="value")(x)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))

        if self.decode:
            return self._decode_step(q, k, v, b, s, head_dim, dense)

        if self.attention == "flash":
            o = flash_attention(q, k, v, causal=self.causal)
        elif self.attention == "reference":
            o = attention_reference(q, k, v, causal=self.causal)
        elif self.attention in ("ring", "ring_flash"):
            from pddl_tpu.ops.ring_attention import sequence_parallel_attention

            if self.mesh is None:
                raise ValueError(f'attention={self.attention!r} needs the mesh')
            o = sequence_parallel_attention(
                q, k, v, self.mesh, causal=self.causal,
                use_flash=self.attention == "ring_flash")
        else:
            raise ValueError(f"unknown attention {self.attention!r}")

        o = o.transpose(0, 2, 1, 3).reshape(b, s, e)
        return dense(features=e, name="out")(o)

    def _decode_step(self, q, k, v, b, s, head_dim, dense):
        """Autoregressive decoding with a KV cache.

        Handles both the batched prefill (``s`` prompt tokens in one call,
        causal within the block) and single-token steps (``s == 1``): the
        block's K/V land at the running index, then
        :func:`~pddl_tpu.ops.attention.decode_attention` sweeps the cache
        in its STORAGE dtype with online softmax, traffic and compute
        bounded by the valid prefix — never an f32 copy of the cache nor
        an ``[s, max_decode_len]`` f32 score materialization.

        ``cache_index`` may be a PER-ROW ``[B]`` vector instead of the
        scalar the cache initializes with — the continuous-batching
        serving engine's slot model, where each batch row is an
        independent request at its own depth. Each row's K/V then lands
        at its own position(s) and the masking in ``decode_attention``
        is per row. Multi-token blocks compose with the vector index
        (the speculative verify step: every slot writes ``s`` tokens at
        ``i[b] .. i[b]+s-1``, causal within the block); positions
        beyond ``max_decode_len`` are DROPPED by the scatter — padding
        or rejected-draft junk past the cache edge never lands.
        """
        h = self.num_heads
        index = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        if self.has_variable("cache", BLOCK_TABLE_KEY):
            # PAGED serving: the one K/V leaf is the engine's shared
            # block POOL ``[N, H, block_size, 2D]`` (K and V side by
            # side, `ops/attention.py`'s paged section) and the
            # per-slot block table (engine-stamped, like the position
            # counter) resolves every read/write — K/V of a shared
            # prefix exists once regardless of how many slots reference
            # it. Writes land in the slot's private tail block (or the
            # scratch sink for parked slots / padding junk) by the
            # engine's table discipline; reads sweep the table with the
            # same masking as the row path below.
            o = paged_decode_step(
                self, index, q,
                paged_kv_fuse(k.astype(self.dtype), v.astype(self.dtype)))
            o = o.transpose(0, 2, 1, 3).reshape(b, s, h * head_dim)
            return dense(features=h * head_dim, name="out")(o)
        # During init() the cache variables don't exist yet: create them
        # but DON'T mutate, so init returns a pristine cache (index 0).
        initialized = self.has_variable("cache", "cached_key")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (b, h, self.max_decode_len, head_dim), self.dtype)
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (b, h, self.max_decode_len, head_dim), self.dtype)

        i = index.value
        if initialized:
            if i.ndim:
                # Per-row scatter at i[b] + arange(s): single-token
                # decode ticks and multi-token speculative verify blocks
                # share one write (out-of-range positions drop — the
                # scatter's jit OOB rule — so draft lookahead past the
                # cache edge is junk-safe by construction).
                rows = jnp.arange(b)[:, None]          # [B, 1]
                pos = i[:, None] + jnp.arange(s)       # [B, s]
                cached_k.value = cached_k.value.at[rows, :, pos].set(
                    jnp.moveaxis(k, 1, 2).astype(self.dtype))
                cached_v.value = cached_v.value.at[rows, :, pos].set(
                    jnp.moveaxis(v, 1, 2).astype(self.dtype))
            else:
                cached_k.value = jax.lax.dynamic_update_slice(
                    cached_k.value, k.astype(self.dtype), (0, 0, i, 0))
                cached_v.value = jax.lax.dynamic_update_slice(
                    cached_v.value, v.astype(self.dtype), (0, 0, i, 0))
            index.value = i + s

        o = decode_attention(q, cached_k.value, cached_v.value, i,
                             chunk=512 if s == 1 else 128)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, h * head_dim)
        # Same `dense` partial as the training path: one definition of the
        # 'out' projection, so the two can never diverge.
        return dense(features=h * head_dim, name="out")(o)


class TransformerBlock(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    attention: str = "flash"
    mesh: Optional[Any] = None
    causal: bool = False
    decode: bool = False  # KV-cache decoding (see MultiHeadAttention)
    max_decode_len: int = 1024
    dropout: float = 0.0
    moe_experts: int = 0  # >0: Switch-MoE FFN instead of the dense MLP
    moe_top_k: int = 1  # experts per token (1=Switch, 2=GShard/Mixtral)
    ln_eps: float = 1e-6  # flax default; HF GPT-2 checkpoints use 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True, /):
        # train is positional-ONLY: under nn.remat, static_argnums points
        # at position 2, and a keyword `train=` would silently shift past
        # it — better a loud TypeError at every call site.
        e = x.shape[-1]
        # Pre-LN (f32 for stability even under bf16 compute).
        h = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="ln1")(x)
        h = MultiHeadAttention(
            num_heads=self.num_heads, attention=self.attention,
            mesh=self.mesh, causal=self.causal, decode=self.decode,
            max_decode_len=self.max_decode_len, dtype=self.dtype,
            param_dtype=self.param_dtype, name="attn",
        )(h.astype(self.dtype))
        if self.dropout:
            h = nn.Dropout(self.dropout, deterministic=not train)(h)
        x = x + h

        h = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="ln2")(x)
        if self.moe_experts:
            from pddl_tpu.ops.moe import SwitchFFN

            h = SwitchFFN(
                num_experts=self.moe_experts, mlp_ratio=self.mlp_ratio,
                top_k=self.moe_top_k,
                dtype=self.dtype, param_dtype=self.param_dtype, name="moe",
            )(h.astype(self.dtype), train)
        else:
            h = nn.Dense(e * self.mlp_ratio, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="mlp1")(h.astype(self.dtype))
            h = nn.gelu(h)
            h = nn.Dense(e, dtype=self.dtype, param_dtype=self.param_dtype,
                         name="mlp2")(h)
        if self.dropout:
            h = nn.Dropout(self.dropout, deterministic=not train)(h)
        return x + h


# Rematerialization policies for the transformer families: trade FLOPs
# for HBM so longer sequences / deeper stacks fit (SURVEY has no analogue;
# this is the jax.checkpoint lever the TPU build exposes).
#   none  — store all activations (fastest, most memory)
#   dots  — save matmul outputs, recompute elementwise (the usual sweet
#           spot: most of the win, little recompute)
#   full  — save only block boundaries, recompute everything inside
REMAT_POLICIES = {
    "none": "none",  # sentinel: no wrapping at all
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "full": None,  # jax.checkpoint default: save nothing inside the block
}


def remat_block(block_cls, remat: str):
    """Wrap a transformer block class per the named remat policy.

    Call wrapped blocks with ``train`` POSITIONAL (``block(x, train)``):
    ``static_argnums`` counts positional args, and flax's lifted remat
    appends keywords after them, so a ``train=`` keyword fails at init
    with jax's static_argnums ValueError (loudly, but cryptically — the
    unwrapped block's positional-only signature gives the clear
    TypeError).
    """
    if remat not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat policy {remat!r}; known: {sorted(REMAT_POLICIES)}"
        )
    if remat == "none":
        return block_cls
    # train (arg index 2, after self/x) is a Python bool — keep it static.
    # prevent_cse stays at its True default: the blocks run Python-unrolled
    # under jit (not scan), where XLA CSE would otherwise eliminate the
    # recompute and silently restore the saved activations.
    return nn.remat(block_cls, policy=REMAT_POLICIES[remat],
                    static_argnums=(2,))


class ViT(nn.Module):
    """Vision Transformer (patch embed → blocks → mean-pool → head).

    Mean-pool head instead of a CLS token: one fewer ragged token keeps the
    sequence length a clean multiple for flash blocks and seq sharding.
    """

    patch_size: int = 16
    embed_dim: int = 384
    depth: int = 12
    num_heads: int = 6
    num_classes: int = 1000
    mlp_ratio: int = 4
    attention: str = "flash"
    mesh: Optional[Any] = None
    dropout: float = 0.0
    moe_experts: int = 0  # >0: every `moe_every`-th block uses Switch-MoE
    moe_top_k: int = 1
    moe_every: int = 2
    remat: str = "none"  # "none" | "dots" | "full" (REMAT_POLICIES)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, *, train: bool = True):
        # Stem shared with GPipeViT; share_scope keeps the historical param
        # names (patch_embed/pos_embed) at this module's top level.
        embed = _ViTEmbed(patch_size=self.patch_size,
                          embed_dim=self.embed_dim, dtype=self.dtype,
                          param_dtype=self.param_dtype)
        nn.share_scope(self, embed)
        x = embed(x)

        block_cls = remat_block(TransformerBlock, self.remat)
        for i in range(self.depth):
            # Interleave MoE FFN blocks (every moe_every-th, from the back
            # so depth=1 test models still get one) with dense MLP blocks —
            # the standard Switch/GShard placement.
            moe = (self.moe_experts
                   if (self.depth - 1 - i) % self.moe_every == 0 else 0)
            x = block_cls(
                num_heads=self.num_heads, mlp_ratio=self.mlp_ratio,
                attention=self.attention, mesh=self.mesh,
                dropout=self.dropout, moe_experts=moe,
                moe_top_k=self.moe_top_k, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block{i}",
            )(x, train)  # positional: remat keeps arg 2 static

        # Head shared with GPipeViT (ln_final/head names preserved).
        head = _ViTHead(num_classes=self.num_classes, dtype=self.dtype,
                        param_dtype=self.param_dtype)
        nn.share_scope(self, head)
        return head(x)


class _ViTEmbed(nn.Module):
    """Patch embed + positional embedding (ViT stem; also the pre-pipeline
    stem of :class:`GPipeViT`). Single source of truth — ``ViT.__call__``
    delegates here via ``nn.share_scope`` so param names are identical."""

    patch_size: int
    embed_dim: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        p = self.patch_size
        if x.shape[1] % p or x.shape[2] % p:
            raise ValueError(f"image {x.shape[1]}x{x.shape[2]} not divisible "
                             f"by patch {p}")
        x = x.astype(self.dtype)
        x = nn.Conv(self.embed_dim, (p, p), strides=(p, p), padding="VALID",
                    dtype=self.dtype, param_dtype=self.param_dtype,
                    name="patch_embed")(x)
        b, gh, gw, e = x.shape
        x = x.reshape(b, gh * gw, e)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, gh * gw, e), self.param_dtype)
        return x + pos.astype(self.dtype)


class _ViTStage(nn.Module):
    """One pipeline stage: a run of transformer blocks (identical across
    stages so their params stack on a leading ``[n_stages, ...]`` dim)."""

    num_heads: int
    blocks: int
    mlp_ratio: int = 4
    attention: str = "reference"  # "flash" uses the Pallas kernel per stage
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        for i in range(self.blocks):
            x = TransformerBlock(
                num_heads=self.num_heads, mlp_ratio=self.mlp_ratio,
                attention=self.attention, dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block{i}",
            )(x, False)
        return x


class _ViTHead(nn.Module):
    """Final LN + mean pool + classifier (the post-pipeline head)."""

    num_classes: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(dtype=jnp.float32, param_dtype=self.param_dtype,
                         name="ln_final")(x)
        x = jnp.mean(x, axis=1)
        if self.num_classes:
            x = nn.Dense(self.num_classes, dtype=self.dtype,
                         param_dtype=self.param_dtype, name="head")(x)
        return x.astype(jnp.float32)


class GPipeViT(GPipeModel):
    """Pipeline-parallel ViT: patch embed (replicated) → ``n_stages``
    stacked transformer stages through the GPipe schedule → head
    (replicated). See :class:`pddl_tpu.models.gpipe.GPipeModel`."""

    def __init__(self, *, n_stages: int, blocks_per_stage: int,
                 n_microbatches: int, mesh,
                 patch_size: int = 16, embed_dim: int = 384,
                 num_heads: int = 6, num_classes: int = 1000,
                 mlp_ratio: int = 4, attention: str = "reference",
                 dtype: Any = jnp.float32, param_dtype: Any = jnp.float32):
        super().__init__(
            embed=_ViTEmbed(patch_size=patch_size, embed_dim=embed_dim,
                            dtype=dtype, param_dtype=param_dtype),
            stage=_ViTStage(num_heads=num_heads, blocks=blocks_per_stage,
                            mlp_ratio=mlp_ratio, attention=attention,
                            dtype=dtype, param_dtype=param_dtype),
            head=_ViTHead(num_classes=num_classes, dtype=dtype,
                          param_dtype=param_dtype),
            n_stages=n_stages, n_microbatches=n_microbatches, mesh=mesh,
        )


ViT_S16 = functools.partial(ViT, patch_size=16, embed_dim=384, depth=12,
                            num_heads=6)
ViT_B16 = functools.partial(ViT, patch_size=16, embed_dim=768, depth=12,
                            num_heads=12)
ViT_L16 = functools.partial(ViT, patch_size=16, embed_dim=1024, depth=24,
                            num_heads=16)


def tiny_vit(num_classes: int = 10, **kwargs) -> ViT:
    """Miniature ViT for tests/dry-runs (8x8 patches on 32px inputs)."""
    kwargs.setdefault("patch_size", 8)
    kwargs.setdefault("embed_dim", 32)
    kwargs.setdefault("depth", 2)
    kwargs.setdefault("num_heads", 4)
    kwargs.setdefault("attention", "reference")
    return ViT(num_classes=num_classes, **kwargs)
