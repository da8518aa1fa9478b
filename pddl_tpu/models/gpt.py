"""GPT family: decoder-only causal transformer (the long-context workload).

The reference is a vision-only repo (fixed 224x224 CNN,
``/root/reference/imagenet-resnet50.py:52`` — SURVEY.md §5 "Long-context:
absent"); this family exists because long-context training is first-class
in the TPU build. It is the model line that exercises *causal* flash
attention (:mod:`pddl_tpu.ops.attention`) and causal ring attention
(:mod:`pddl_tpu.ops.ring_attention`) on the training path, and it reuses
:class:`pddl_tpu.models.vit.TransformerBlock` — so Megatron TP
(``/attn/``-path rules), Switch-MoE and every distribution strategy apply
unchanged.

Batches are ``{"tokens": int32 [B, S], "targets": int32 [B, S]}`` (the
Trainer's ``input_key``/``target_key``); loss/metrics are the standard
sparse CE / accuracy, which broadcast over the sequence dim as-is.
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
    PAGED_KV_KEY,
    SLOT_STATE_KEY,
    STATE_SLOT_KEY,
    TransformerBlock,
    remat_block,
)
from pddl_tpu.ops.large_vocab import chunked_cross_entropy


class GPT(nn.Module):
    """Decoder-only transformer LM: tokens ``[B, S]`` → logits ``[B, S, V]``."""

    vocab_size: int
    max_len: int = 1024
    embed_dim: int = 256
    depth: int = 4
    num_heads: int = 4
    mlp_ratio: int = 4
    attention: str = "flash"  # "flash" | "reference" | "ring" | "ring_flash"
    mesh: Optional[Any] = None  # required for "ring"/"ring_flash"
    dropout: float = 0.0
    moe_experts: int = 0
    moe_top_k: int = 1  # experts per token (1=Switch, 2=GShard/Mixtral)
    moe_every: int = 2
    remat: str = "none"  # "none" | "dots" | "full" (vit.REMAT_POLICIES)
    # Pad the embedding/head vocab dim up to a multiple (Megatron's
    # convention, typically 128): vocab-parallel TP needs V divisible by
    # the model axis, and real vocabs (GPT-2's 50257) divide nothing.
    # Logits are sliced back to vocab_size — numerics are unchanged.
    vocab_multiple: int = 1
    decode: bool = False  # KV-cache generation mode (see generate())
    ln_eps: float = 1e-6  # HF GPT-2 checkpoints: pass 1e-5 (ckpt/hf_import)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, *, train: bool = True,
                 features_only: bool = False):
        # Stem shared with GPipeGPT; share_scope keeps the param names
        # (token_embed/pos_embed) at this module's top level.
        embed = _GPTEmbed(vocab_size=self.vocab_size, max_len=self.max_len,
                          embed_dim=self.embed_dim, decode=self.decode,
                          vocab_multiple=self.vocab_multiple,
                          dtype=self.dtype, param_dtype=self.param_dtype)
        nn.share_scope(self, embed)
        x = embed(tokens)

        # Decode mutates the KV cache — remat would replay the mutation;
        # generation steps are tiny anyway, so remat only applies to the
        # training/full-forward path.
        block_cls = (TransformerBlock if self.decode
                     else remat_block(TransformerBlock, self.remat))
        for i in range(self.depth):
            moe = (self.moe_experts
                   if (self.depth - 1 - i) % self.moe_every == 0 else 0)
            x = block_cls(
                num_heads=self.num_heads, mlp_ratio=self.mlp_ratio,
                attention=self.attention, mesh=self.mesh, causal=True,
                decode=self.decode, max_decode_len=self.max_len,
                dropout=self.dropout, moe_experts=moe,
                moe_top_k=self.moe_top_k, ln_eps=self.ln_eps,
                dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block{i}",
            )(x, train)  # positional: remat keeps arg 2 static

        # Head shared with GPipeGPT (ln_final/lm_head names preserved).
        head = _GPTHead(vocab_size=self.vocab_size,
                        vocab_multiple=self.vocab_multiple,
                        ln_eps=self.ln_eps,
                        dtype=self.dtype, param_dtype=self.param_dtype,
                        features_only=features_only)
        nn.share_scope(self, head)
        return head(x)


class _GPTEmbed(nn.Module):
    """Token + positional embedding (the pre-pipeline LM stem).

    ``decode=True``: one token per call, positioned at a running index
    kept in the ``"cache"`` collection (generation mode)."""

    vocab_size: int
    max_len: int
    embed_dim: int
    decode: bool = False
    vocab_multiple: int = 1
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens):
        b, s = tokens.shape
        if s > self.max_len:
            raise ValueError(f"sequence {s} exceeds max_len {self.max_len}")
        padded_v = -(-self.vocab_size // self.vocab_multiple) * self.vocab_multiple
        x = nn.Embed(padded_v, self.embed_dim,
                     dtype=self.dtype, param_dtype=self.param_dtype,
                     name="token_embed")(tokens)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (1, self.max_len, self.embed_dim), self.param_dtype)
        if self.decode:
            initialized = self.has_variable("cache", "pos_index")
            idx = self.variable("cache", "pos_index",
                                lambda: jnp.zeros((), jnp.int32))
            if idx.value.ndim:
                # Per-row [B] position vector (the serving engine's slot
                # model): each row reads its own position embedding.
                step_pos = jnp.take(
                    pos[0], idx.value[:, None] + jnp.arange(s), axis=0)
            else:
                step_pos = jax.lax.dynamic_slice_in_dim(
                    pos, idx.value, s, axis=1)
            if initialized:  # init() must return a pristine cache
                idx.value = idx.value + s
            return x + step_pos.astype(self.dtype)
        return x + pos[:, :s].astype(self.dtype)


class _GPTStage(nn.Module):
    """One pipeline stage: a run of causal transformer blocks."""

    num_heads: int
    blocks: int
    mlp_ratio: int = 4
    attention: str = "reference"
    ln_eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        for i in range(self.blocks):
            x = TransformerBlock(
                num_heads=self.num_heads, mlp_ratio=self.mlp_ratio,
                attention=self.attention, causal=True, ln_eps=self.ln_eps,
                dtype=self.dtype,
                param_dtype=self.param_dtype, name=f"block{i}",
            )(x, False)
        return x


class _GPTHead(nn.Module):
    """Final LN + LM head (the post-pipeline projection to vocab)."""

    vocab_size: int
    vocab_multiple: int = 1
    ln_eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    features_only: bool = False  # stop after ln_final (fused-CE path)

    @nn.compact
    def __call__(self, x):
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         param_dtype=self.param_dtype, name="ln_final")(x)
        if self.features_only and not self.is_initializing():
            # Pre-head features for chunked/fused cross-entropy
            # (ops/large_vocab.py). init() falls through to the dense
            # below regardless, so lm_head params always exist.
            return x.astype(self.dtype)
        padded_v = -(-self.vocab_size // self.vocab_multiple) * self.vocab_multiple
        logits = nn.Dense(padded_v, dtype=self.dtype,
                          param_dtype=self.param_dtype, name="lm_head")(x)
        # Slice the padding classes away: the function computed is exactly
        # the unpadded head's (padded kernel columns never reach the loss
        # or sampling).
        return logits[..., :self.vocab_size].astype(jnp.float32)


class GPipeGPT(GPipeModel):
    """Pipeline-parallel causal LM: PP x long-context — token/pos embed
    (replicated) → ``n_stages`` stacked causal-transformer stages through
    the GPipe schedule → LM head (replicated). See
    :class:`pddl_tpu.models.gpipe.GPipeModel`."""

    def __init__(self, *, vocab_size: int, n_stages: int,
                 blocks_per_stage: int, n_microbatches: int, mesh,
                 max_len: int = 1024, embed_dim: int = 256,
                 num_heads: int = 4, mlp_ratio: int = 4,
                 attention: str = "reference", ln_eps: float = 1e-6,
                 dtype: Any = jnp.float32, param_dtype: Any = jnp.float32):
        super().__init__(
            embed=_GPTEmbed(vocab_size=vocab_size, max_len=max_len,
                            embed_dim=embed_dim, dtype=dtype,
                            param_dtype=param_dtype),
            stage=_GPTStage(num_heads=num_heads, blocks=blocks_per_stage,
                            mlp_ratio=mlp_ratio, attention=attention,
                            ln_eps=ln_eps,
                            dtype=dtype, param_dtype=param_dtype),
            head=_GPTHead(vocab_size=vocab_size, ln_eps=ln_eps, dtype=dtype,
                          param_dtype=param_dtype),
            n_stages=n_stages, n_microbatches=n_microbatches, mesh=mesh,
        )


def sample_logits(rng, logits, *, temperature: float = 1.0,
                  top_k: Optional[int] = None, top_p: Optional[float] = None):
    """One sampling step over ``[B, V]`` logits (compiled-friendly).

    Filters compose the standard way (matching common reference
    implementations): temperature warps the distribution FIRST, then
    top-k truncates, then nucleus (top-p) keeps the smallest set reaching
    ``top_p`` of the *warped* mass, then one categorical draw. Static
    shapes throughout — ``top_k`` uses ``lax.top_k``'s threshold,
    ``top_p`` masks on the sorted CDF — so the whole step stays jittable.
    """
    # Validate every CONCRETE value (Python, NumPy, or device scalar); a
    # TRACED top_p under jit stays dynamic and skips the check rather
    # than breaking the trace. (top_k is necessarily static: lax.top_k
    # needs a concrete k.)
    if top_k is not None and int(top_k) < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if (top_p is not None and not isinstance(top_p, jax.core.Tracer)
            and not 0.0 < float(top_p) <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    logits = logits.astype(jnp.float32)
    if temperature <= 0:
        # Greedy limit (filters never change the argmax); avoids the /0.
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(
        rng, filtered_logits(logits, temperature=temperature,
                             top_k=top_k, top_p=top_p), axis=-1)


def _sort_descending(logits):
    """One stable descending sort over the last axis that carries its
    values: ``(sorted values, their vocabulary ids)``. Equal values keep
    ascending-id order, exactly as ``jnp.argsort(-logits)`` orders them,
    and no gather is needed to fetch the sorted values afterwards."""
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    neg, ids = jax.lax.sort((-logits, ids), dimension=logits.ndim - 1,
                            is_stable=True, num_keys=1)
    return -neg, ids


def _nucleus_keep(logits, sorted_logits, sorted_ids, top_p):
    """The nucleus (top-p) rule both filter pipelines share: a boolean
    mask in VOCABULARY order of the smallest set of ``logits`` whose
    softmax mass reaches ``top_p``.

    ``sorted_logits`` / ``sorted_ids`` are ``logits`` in stable
    descending order (:func:`_sort_descending`); ``top_p`` broadcasts
    against ``[..., 1]``. An entry is kept while the CDF *before* it is
    ``< top_p`` (the first always is), and the CDF is monotone, so the
    kept entries are a prefix of the sorted row and its length names
    them. A bare value threshold would keep EVERY token tied with the
    boundary logit and exceed the nucleus; the stable sort put ties in
    ascending-id order, so the prefix ends at one (value, id) pair and,
    back in vocabulary order, holds exactly what is larger than that
    value, or equal to it with an id no higher: an elementwise test, no
    inverse permutation and no gather over the vocabulary."""
    cdf = jnp.cumsum(jax.nn.softmax(sorted_logits, axis=-1), axis=-1)
    last = jnp.sum(cdf[..., :-1] < top_p, axis=-1, keepdims=True,
                   dtype=jnp.int32)              # n_keep - 1
    v_last = jnp.take_along_axis(sorted_logits, last, axis=-1)
    id_last = jnp.take_along_axis(sorted_ids, last, axis=-1)
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return (logits > v_last) | ((logits == v_last) & (ids <= id_last))


def filtered_logits(logits, *, temperature: float,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None):
    """The warp+filter pipeline of :func:`sample_logits` WITHOUT the
    draw: f32 logits whose softmax is the exact sampling distribution.
    Shared with speculative decoding's verifier, whose accept/residual
    probabilities must be computed from the same filtered distribution
    a plain sampler would draw from."""
    logits = logits.astype(jnp.float32) / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None:
        keep = _nucleus_keep(logits, *_sort_descending(logits), top_p)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def batched_filtered_logits(logits, *, temperature, top_k, top_p):
    """Per-ROW warp+filter: the :func:`filtered_logits` pipeline with the
    sampling parameters as ``[B]`` RUNTIME arrays instead of statics —
    the serving engine's per-slot path, where every tick carries a mixed
    bag of requests and none of their parameters may enter the compiled
    program as constants.

    Disabled-filter sentinels (arrays can't carry None): ``top_k <= 0``
    disables top-k for that row, ``top_p >= 1`` disables nucleus.
    ``temperature <= 0`` rows are warped at 1.0 to stay finite — greedy
    selection for them happens in :func:`sample_logits_batched`, which
    ignores the filtered row entirely.

    Row-by-row this matches ``filtered_logits`` exactly for enabled
    filters: the top-k threshold is the k-th sorted value (ties at the
    boundary kept, like ``lax.top_k``'s), and the nucleus keep-set is
    the same rule (:func:`_nucleus_keep`). With k as data, the top-k
    threshold comes from ONE full sort that carries its values and that
    the nucleus pass shares; every other step is elementwise or a
    reduction over the row, so nothing gathers over ``[B, V]``.
    """
    logits = logits.astype(jnp.float32)
    b, v = logits.shape
    t = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    kk = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    pp = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    warped = logits / jnp.where(t > 0, t, 1.0)[:, None]
    sorted_l, sorted_ids = _sort_descending(warped)
    # Top-k: per-row k-th sorted value as the threshold (same
    # keep-boundary-ties rule as lax.top_k in filtered_logits). The
    # masked entries are exactly the tail of the descending order, so
    # the same comparison masks the sorted row and the one sort stays
    # valid after masking — no re-sort.
    kth = jnp.take_along_axis(
        sorted_l, (jnp.clip(kk, 1, v) - 1)[:, None], axis=-1)
    no_topk = kk[:, None] <= 0
    warped = jnp.where(no_topk | (warped >= kth), warped, -jnp.inf)
    sorted_l = jnp.where(no_topk | (sorted_l >= kth), sorted_l, -jnp.inf)
    keep = (_nucleus_keep(warped, sorted_l, sorted_ids, pp[:, None])
            | (pp[:, None] >= 1.0))
    return jnp.where(keep, warped, -jnp.inf)


def sample_logits_batched(rng, logits, *, temperature, top_k, top_p):
    """One sampling step over ``[B, V]`` logits with PER-ROW parameters
    (``[B]`` arrays; sentinels as in :func:`batched_filtered_logits`).
    Rows with ``temperature <= 0`` take the greedy argmax of the RAW
    logits (filters never change an argmax); the rest draw one
    categorical sample from their filtered distribution. Returns int32
    ``[B]``."""
    logits = logits.astype(jnp.float32)
    t = jnp.broadcast_to(
        jnp.asarray(temperature, jnp.float32), (logits.shape[0],))
    sampled = jax.random.categorical(
        rng, batched_filtered_logits(logits, temperature=temperature,
                                     top_k=top_k, top_p=top_p), axis=-1)
    return jnp.where(t > 0, sampled,
                     jnp.argmax(logits, axis=-1)).astype(jnp.int32)


def generate(model: GPT, variables, prompt, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: Optional[int] = None,
             top_p: Optional[float] = None, rng=None, strategy=None,
             param_transform=None):
    """Autoregressive sampling with a KV cache.

    Args:
      model: the (trained) non-decode GPT; a decode twin sharing its params
        is constructed internally via ``model.clone(decode=True)``.
      variables: ``{"params": ...}`` from training.
      prompt: int32 ``[B, P]`` prompt tokens (``P >= 1``).
      max_new_tokens: tokens to append.
      temperature: 0 → greedy argmax; >0 → temperature sampling (``rng``
        required), optionally filtered by ``top_k`` and/or nucleus
        ``top_p`` (:func:`sample_logits`).
      strategy: optional :class:`~pddl_tpu.parallel.tensor_parallel.
        TensorParallelStrategy` (mesh already set up) for SHARDED
        inference: weights lay out Megatron-style over the ``model``
        axis, the KV cache splits by head alongside its q/k/v shards,
        and each decode step compiles with the two per-block
        all-reduces on ICI — models too big for one chip generate
        without any model change.
      param_transform: optional module-level function mapping
        ``variables["params"]`` to apply-ready weights inside the jitted
        programs — the int8 weight-only serving hook
        (:func:`pddl_tpu.ops.quant.dequantize`); see `ops/quant.py`.
        Unsharded path only.

    Returns int32 ``[B, P + max_new_tokens]`` (prompt + continuation).
    Execution model: one jitted batched prefill over the whole prompt,
    then the ENTIRE decode as a single on-device ``lax.scan`` dispatch
    (sampling included); parameters are jit arguments, so new checkpoints
    of the same shape reuse the compiled program.
    """
    b, p = prompt.shape
    total = p + max_new_tokens
    if p < 1:
        raise ValueError("generate() needs a non-empty prompt (P >= 1)")
    if total > model.max_len:
        raise ValueError(f"prompt+new tokens {total} exceed max_len {model.max_len}")
    if temperature > 0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if temperature <= 0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p require temperature > 0 (greedy decoding would "
            "silently ignore them)"
        )
    dec = model.clone(decode=True)
    params = variables["params"]
    cache_shapes = _decode_cache_shapes(dec, b)

    def fresh_cache():
        return jax.tree.map(lambda sd: jnp.zeros(sd.shape, sd.dtype),
                            cache_shapes)

    # The prefill step runs ONCE (decode then scans on device) — no
    # donation: donating the just-created zero cache is never usable.
    if strategy is None:
        cache = fresh_cache()
        step, run = _decode_programs(dec, temperature, top_k, top_p,
                                     max_new_tokens, param_transform)
    else:
        if param_transform is not None:
            raise NotImplementedError(
                "param_transform (int8 serving) is unsharded-only: the "
                "sharding trees below describe the DENSE params layout")
        # One batched transfer for the whole tree; the same sharding tree
        # feeds the jits' in_shardings.
        param_sh = strategy.tree_sharding(params)
        params = jax.device_put(params, param_sh)
        cache_sh = strategy.decode_cache_sharding(cache_shapes)
        p_leaves, p_def = jax.tree_util.tree_flatten(param_sh)
        c_leaves, c_def = jax.tree_util.tree_flatten(cache_sh)
        step, run = _sharded_decode_programs(
            dec, temperature, top_k, top_p, max_new_tokens,
            p_def, tuple(p_leaves), c_def, tuple(c_leaves))
        cache = jax.jit(fresh_cache, out_shardings=cache_sh)()

    # Batched prefill: the whole prompt in ONE call (causal within the
    # block); then the ENTIRE decode runs as one compiled lax.scan — a
    # single dispatch for all max_new_tokens steps. A host-side
    # token-at-a-time loop costs one (or more) host→device round trips
    # per token, which dominates wall-clock wherever dispatch has
    # latency (a busy host); on-device scan makes generation latency
    # the compute itself.
    cache, logits = step(params, cache, prompt)
    if rng is None:
        rng = jax.random.key(0)  # unused under greedy; scan needs a value
    return jnp.concatenate([prompt, run(params, cache, logits, rng)], axis=1)


@functools.partial(jax.jit, static_argnums=0)
def _teacher_forced_logits(model, variables, tokens):
    return model.apply(variables, tokens, train=False)


def greedy_gap(model, variables, tokens, prompt_len: int):
    """How far below the argmax each GENERATED token of ``tokens`` sits
    under the model's own teacher-forced conditional along ``tokens``'
    own prefix: float32 ``[B, T - prompt_len]``, 0 where the token is
    the argmax.

    The hardware-honest test of a greedy decode. Two compiled programs
    for the same math (the one-token tick and a k+1-wide verify block,
    a paged kernel and a dense prefill, an engine stream and one-shot
    :func:`generate`) produce bf16 logits that legitimately differ by
    ulps, and on near-uniform logits (untrained weights: ties
    everywhere) an ulp flips an argmax — so token strings may diverge
    while both are valid greedy decodes. What must hold is that every
    emitted token is an argmax or a numerical tie of it: a small gap. A
    WRONG token (stale cache, wrong position, wrong block) lands at a
    typical logit, a gap of the logits' whole spread. ``tokens`` is
    int32 ``[B, T]`` (prompt + continuation, ``T <= model.max_len``);
    the forward runs at exactly that ``T``, so a caller picks the shape
    the attention kernel tiles.
    """
    tokens = jnp.asarray(tokens, jnp.int32)
    logits = _teacher_forced_logits(model, variables, tokens)
    logits = logits[:, prompt_len - 1:-1].astype(jnp.float32)
    chosen = jnp.take_along_axis(
        logits, tokens[:, prompt_len:, None], axis=-1)[..., 0]
    return jax.device_get(logits.max(axis=-1) - chosen)


def _decode_fns(dec, temperature, top_k, top_p, max_new_tokens,
                param_transform=None):
    """(step_fn, decode_all) python callables for a decode-mode model.

    params is an ARGUMENT of both functions, never a closure: closed-over
    arrays become program CONSTANTS, which bakes the full parameter set
    into the executable — gigabyte compile payloads (remote-compile
    transports reject them outright) and a recompile for every new
    checkpoint.

    ``param_transform`` (e.g. :func:`pddl_tpu.ops.quant.dequantize`)
    maps the passed params tree to apply-ready weights INSIDE the jitted
    programs — so what lives in HBM (and streams per tick) is the
    transformed-FROM representation, int8 for the quant case, with the
    convert fused into the consuming matmuls.
    """
    pt = param_transform or (lambda p: p)

    def step_fn(params, cache, tok):
        logits, mutated = dec.apply(
            {"params": pt(params), "cache": cache}, tok,
            train=False, mutable=["cache"],
        )
        return mutated["cache"], logits[:, -1]

    def sample_next(logits, rng):
        if temperature > 0:
            rng, sub = jax.random.split(rng)
            nxt = sample_logits(sub, logits, temperature=temperature,
                                top_k=top_k, top_p=top_p)
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return nxt.astype(jnp.int32), rng

    def decode_all(params, cache, logits, rng):
        def body(carry, _):
            cache, logits, rng = carry
            nxt, rng = sample_next(logits, rng)
            tok = nxt[:, None]
            cache, logits = step_fn(params, cache, tok)
            return (cache, logits, rng), tok

        # The final iteration's step_fn is one token of dead compute (its
        # logits are never sampled) — the price of a uniform scan body.
        _, toks = jax.lax.scan(
            body, (cache, logits, rng), None, length=max_new_tokens)
        return jnp.moveaxis(toks[..., 0], 0, 1)  # [T, B, 1] -> [B, T]

    return step_fn, decode_all


# The cache collections' position-counter leaf names, across every
# family: GPT's embed keeps `pos_index`, the attention modules (vit MHA
# and llama GQA) keep `cache_index`. THE single registry — speculative
# decoding's rewind and the serving engine's slot machinery both match
# counters by these names (never by scalar-int32 duck typing, which
# would silently capture any future non-position scalar cache state).
CACHE_INDEX_KEYS = frozenset({"pos_index", "cache_index"})


def _leaf_key(path) -> str:
    return str(getattr(path[-1], "key", path[-1])) if path else ""


def _stamp(cache, is_leaf, value):
    """``cache`` with ``value`` in place of every leaf whose key path
    ``is_leaf`` names; a tree without such leaves is returned as it is."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: value if is_leaf(path) else leaf, cache)


def is_cache_index_path(path) -> bool:
    """True when a cache-tree key path names a position counter leaf."""
    return _leaf_key(path) in CACHE_INDEX_KEYS


def is_block_table_path(path) -> bool:
    """True when a cache-tree key path names a paged block-table leaf."""
    return _leaf_key(path) == BLOCK_TABLE_KEY


def is_paged_pool_path(path) -> bool:
    """True when a cache-tree key path names a block pool leaf
    (``[N, H_c, block_size, lanes]``): the leaves whose blocks the host
    tier moves, a prefix chain exports and ``kv_token_bytes`` weighs. By
    key, never by rank: a per-slot state leaf has three dimensions too."""
    return _leaf_key(path) == PAGED_KV_KEY


def is_slot_state_path(path) -> bool:
    """True when a cache-tree key path names a per-slot state leaf
    (``[slots, ...]``, `vit.SLOT_STATE_KEY`)."""
    return _leaf_key(path) == SLOT_STATE_KEY


def set_cache_state_slot(cache, slot):
    """Stamp every ``state_slot`` leaf of a PAGED cache with the slot a
    batch-1 chunk program fills (a scalar; the canonical placeholder is
    0). A tree without per-slot state is returned as it is."""
    return _stamp(cache, lambda path: _leaf_key(path) == STATE_SLOT_KEY,
                  slot)


def set_cache_positions(cache, positions):
    """Overwrite every position counter of a cache tree with
    ``positions`` (``[slots]`` for the fused tick, a scalar for a
    batch-1 chunk; the engine owns the authoritative per-slot
    positions and every program stamps them in before its apply)."""
    return _stamp(cache, is_cache_index_path, positions)


def set_cache_block_tables(cache, tables):
    """Overwrite every ``block_table`` leaf of a PAGED cache with
    ``tables`` (``[slots, T]`` for the fused tick, ``[1, T]`` for a
    batch-1 chunk prefill). The engine owns the authoritative host-side
    tables exactly like the position counters — every paged program
    stamps them in before the apply and re-stamps a canonical
    placeholder on exit, so the resident donated tree keeps ONE
    structure across the whole program set (shape-stable donation =
    zero recompiles)."""
    return _stamp(cache, is_block_table_path, tables)


def set_cache_valid_len(cache, length):
    """Stamp every ``valid_len`` leaf of a cache (the routed layers'
    serving counters, `ops/moe.py`) with the number of real tokens in
    the chunk about to run; a tree without such leaves is returned as
    it is."""
    from pddl_tpu.ops.moe import VALID_LEN_KEY

    return _stamp(cache, lambda path: _leaf_key(path) == VALID_LEN_KEY,
                  length)


def prefill_row_from(dec, params, prompt, length, row_cache, start, *,
                     param_transform=None):
    """Chunked prefill CONTINUING an existing batch-1 cache: the
    serving engine's admission building block (family-generic —
    duck-typed over GPT/Llama like :func:`generate`; GPT's decode embed
    and the Llama/vit decode attention both run multi-token blocks at
    any starting index).

    ``row_cache`` already holds ``start`` valid tokens of K/V (e.g. a
    pinned shared-prefix chain behind the cache's block table);
    ``prompt`` is int32 ``[1, C]`` RIGHT-padded, ``length <= C`` its
    true token count, both traced — one compiled program per chunk
    width. The chunk's tokens take global positions
    ``start .. start+C-1``, so the caller must keep
    ``start + C <= dec.max_len`` (the embed/cache dynamic slices CLAMP
    out-of-range starts, which would silently mis-position the block).
    Padding is harmless by the same invariant speculative decoding
    relies on: causal masking hides the junk suffix from positions
    ``< start + length``, the returned logits row is taken at
    ``length - 1``, and the junk K/V lands beyond the position counter
    the caller keeps for the slot, where the prefix-bounded sweep
    never reads it (decode overwrites it position by position as the
    request generates).

    Returns ``(row_cache, last_logits [1, V])`` with the logits row
    taken at ``length - 1`` (only the FINAL chunk's logits are
    meaningful to sample from).
    """
    pt = param_transform or (lambda p: p)
    p2 = pt(params)
    cache = set_cache_positions(row_cache, jnp.asarray(start, jnp.int32))
    cache = set_cache_valid_len(cache, jnp.asarray(length, jnp.int32))
    # The head runs on the one row that is sampled from, not on the
    # chunk: at a 152k vocabulary the chunk's logits would be gigabytes.
    feats, mutated = dec.apply(
        {"params": p2, "cache": cache}, prompt,
        train=False, mutable=["cache"], features_only=True)
    last = jax.lax.dynamic_slice(
        feats, (0, length - 1, 0), (1, 1, feats.shape[-1]))
    return mutated["cache"], lm_head_logits(dec, p2, last)[:, 0]


def lm_head_logits(model, params, feats):
    """The LM head applied OUTSIDE the module: pre-head features
    (``features_only=True`` apply output — post-final-norm, already in
    the model's compute dtype) → vocab logits, mirroring
    ``_GPTHead``/``_LlamaHead`` operation-for-operation (same
    ``dot_general`` contraction, same bias/padding-slice/f32-cast
    order), so the computed logits match the in-module head exactly.

    This is the multi-tenant serving hook point (`serve/tenant/`): the
    tenant engine's compiled programs run the model ``features_only``,
    apply the head here, and then ADD per-slot LoRA deltas
    (:func:`pddl_tpu.ops.lora.batched_lora_delta`) and grammar masks
    before sampling — all runtime data, no program-shape variation.
    ``params`` must already be transform-applied (the int8
    ``param_transform`` runs BEFORE this, like everywhere else).
    Bias-free heads (the Llama family) simply have no ``bias`` key.
    """
    head = params["lm_head"]
    x = feats.astype(model.dtype)
    kernel = head["kernel"].astype(model.dtype)
    logits = jax.lax.dot_general(
        x, kernel, (((x.ndim - 1,), (0,)), ((), ())))
    if "bias" in head:
        logits = logits + head["bias"].astype(model.dtype)
    return logits[..., :model.vocab_size].astype(jnp.float32)


def prefill_row_features(dec, params, prompt, length, row_cache, start, *,
                         param_transform=None):
    """The tenant twin of :func:`prefill_row_from`: one prefill chunk
    that ALSO returns the last position's pre-head features, so the
    caller can compose LoRA deltas into the sampled logits. The chunk
    continues the given cache at global offset ``start``
    (``prefill_row_from`` semantics, same clamping caveats).

    Returns ``(row_cache, last_logits [1, V], last_feats [1, d])``.
    The logits are computed through :func:`lm_head_logits` over the
    full chunk and sliced at ``length - 1`` — the identical op shapes
    the in-module head produces, so a no-adapter tenant admission is
    bit-identical to the plain prefill path.
    """
    pt = param_transform or (lambda p: p)
    p2 = pt(params)
    cache = set_cache_positions(row_cache, jnp.asarray(start, jnp.int32))
    cache = set_cache_valid_len(cache, jnp.asarray(length, jnp.int32))
    feats, mutated = dec.apply(
        {"params": p2, "cache": cache}, prompt,
        train=False, mutable=["cache"], features_only=True)
    logits = lm_head_logits(dec, p2, feats)
    last = jax.lax.dynamic_slice(
        logits, (0, length - 1, 0), (1, 1, logits.shape[-1]))[:, 0]
    last_feats = jax.lax.dynamic_slice(
        feats, (0, length - 1, 0), (1, 1, feats.shape[-1]))[:, 0]
    return mutated["cache"], last, last_feats


@functools.lru_cache(maxsize=16)
def _decode_cache_shapes(dec, batch: int):
    """KV-cache ShapeDtypeStructs for a decode module at a batch size.

    The fresh cache is all zeros by construction; eval_shape over init
    gets its structure without materializing (and discarding) a full
    random parameter set. Cached: the abstract trace of init walks every
    block and is pure per-(dec, batch) overhead on the serving hot path.
    """
    dummy = jnp.zeros((batch, 1), jnp.int32)
    return jax.eval_shape(
        lambda: dec.init(jax.random.key(0), dummy, train=False)
    )["cache"]


@functools.lru_cache(maxsize=16)
def _decode_programs(dec, temperature, top_k, top_p, max_new_tokens,
                     param_transform=None):
    """Jitted (prefill_step, decode_scan) for the unsharded path, CACHED
    on the (hashable, frozen) decode module + sampling statics.

    Without this cache every generate() call would build fresh closures
    and re-trace/re-compile the whole decode scan — tens of seconds per
    request in a serving loop. With it, repeated calls (and new
    checkpoints of the same shape, which are just new jit arguments) hit
    the compiled programs. Entries keep the module and executables alive
    until LRU eviction (maxsize=16) or process exit — deliberate serving
    behavior, not a leak. ``param_transform`` participates in the key by
    identity — pass a module-level function (not a lambda) to hit.
    """
    step_fn, decode_all = _decode_fns(dec, temperature, top_k, top_p,
                                      max_new_tokens, param_transform)
    return jax.jit(step_fn), jax.jit(decode_all, donate_argnums=(1,))


@functools.lru_cache(maxsize=16)
def _sharded_decode_programs(dec, temperature, top_k, top_p, max_new_tokens,
                             param_sh_def, param_sh_leaves,
                             cache_sh_def, cache_sh_leaves):
    """(step, run) for tensor-parallel decoding, cached like
    :func:`_decode_programs` so sharded serving doesn't re-compile per
    request.

    Keys are VALUES, not identities: the flattened parameter and cache
    sharding trees (NamedShardings and treedefs hash by value, and the
    mesh is embedded in every leaf), so a strategy object rebuilt per
    request still hits; a different mesh, checkpoint structure, or
    sampling config misses. One lru_cache mechanism shared with the
    unsharded path — same true-LRU eviction.

    Retention: like :func:`_decode_programs`, cached entries hold strong
    references to the module, the NamedShardings (hence meshes and
    device handles) and the compiled executables until LRU-evicted or
    the process exits — the deliberate cost of not re-compiling per
    serving request (same caveat as ``core/sharding.py``'s lru_cache).
    """
    from jax.sharding import NamedSharding, PartitionSpec

    if not param_sh_leaves:
        raise ValueError(
            "sharded decode needs a non-empty params tree (got zero "
            "parameter leaves — was the model initialized?)")
    param_sh = jax.tree_util.tree_unflatten(param_sh_def, param_sh_leaves)
    cache_sh = jax.tree_util.tree_unflatten(cache_sh_def, cache_sh_leaves)
    repl = NamedSharding(param_sh_leaves[0].mesh, PartitionSpec())
    step_fn, decode_all = _decode_fns(dec, temperature, top_k, top_p,
                                      max_new_tokens)
    step = jax.jit(step_fn,
                   in_shardings=(param_sh, cache_sh, repl),
                   out_shardings=(cache_sh, repl))
    run = jax.jit(decode_all, donate_argnums=(1,),
                  in_shardings=(param_sh, cache_sh, repl, repl),
                  out_shardings=repl)
    return step, run


GPT_Small = functools.partial(GPT, embed_dim=768, depth=12, num_heads=12)


def tiny_gpt(vocab_size: int = 64, **kwargs) -> GPT:
    """Miniature GPT for tests/dry-runs."""
    kwargs.setdefault("max_len", 128)
    kwargs.setdefault("embed_dim", 32)
    kwargs.setdefault("depth", 2)
    kwargs.setdefault("num_heads", 4)
    kwargs.setdefault("attention", "reference")
    return GPT(vocab_size=vocab_size, **kwargs)


def fused_lm_loss(model: GPT, variables, tokens, targets, *,
                  train: bool = True, rngs=None,
                  chunk_size: Optional[int] = None) -> jnp.ndarray:
    """Mean token cross-entropy without materializing the ``[B, S, V]`` logits.

    The standard LM loss writes ~``B*S*V`` logits to HBM, saves them (and
    softmax residuals) for the backward, writes d-logits, and reads them
    again in the head-matmul backward. The fused head
    (:func:`pddl_tpu.ops.large_vocab.chunked_cross_entropy`, custom VJP)
    saves only per-token logsumexp rows and recomputes chunk logits in
    the backward: measured 33.7 vs 39.7 ms for head+CE fwd+bwd on one
    v5e at GPT-2-small shapes (B8 S2048 V50257 bf16).

    Memory: the default (``chunk_size=None`` → whole vocab, one fused
    step) optimizes for SPEED — its forward still builds one transient
    ``[tokens, V]`` f32 chunk (~3.3 GB at the shapes above), though
    nothing logits-sized is saved across fwd/bwd. Pass ``chunk_size``
    below the vocab for the long-context/large-vocab memory valve: peak
    extra memory drops to ``tokens x chunk_size``.

    Gradients match the materialized path — to float tolerance in f32
    and to bf16 tolerance in bf16, where both paths run the head matmul
    from bf16 operands with f32 accumulation (``tests/test_gpt.py``).
    For metrics that need logits (accuracy, sampling), use the regular
    ``model.apply`` — this is the training-loss fast path.

    Args:
      model: the :class:`GPT` (its ``vocab_size``/``vocab_multiple``
        locate the real columns of a padded head).
      variables: ``{"params": ...}``.
      tokens: ``[B, S]`` int32 inputs.
      targets: ``[B, S]`` int32 next-token labels.
      train: forwarded to the model (dropout etc.).
      rngs: forwarded to ``model.apply`` (needed when dropout > 0).
      chunk_size: vocab slab per scan step; None = the whole (unpadded)
        vocab in one fused step — fastest when the logits would fit.
    """
    kwargs = {"rngs": rngs} if rngs is not None else {}
    feats = model.apply(variables, tokens, train=train,
                        features_only=True, **kwargs)
    head = variables["params"]["lm_head"]
    # Compute dtype like the materialized Dense(dtype=model.dtype) would:
    # the chunked matmuls run on these operands with f32 accumulation.
    kernel = head["kernel"][:, :model.vocab_size].astype(model.dtype)
    # Bias-free heads (the Llama family) simply skip the bias term.
    bias = head["bias"][:model.vocab_size].astype(jnp.float32) \
        if "bias" in head else None
    return chunked_cross_entropy(
        feats, kernel, targets, bias,
        chunk_size=chunk_size if chunk_size is not None else model.vocab_size,
    )
