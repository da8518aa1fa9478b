"""Speculative decoding: multi-token ticks via prompt-lookup drafting.

Why this exists (the measured motivation): ARCHITECTURE.md §7e attributes
single-stream decode to a **0.289 ms per-tick FIXED serial-latency cost**
(scan tick machinery + the dependency-chain latency of ~130 small GEMV
ops) that is batch- and width-INDEPENDENT — the same tick that computes
one token's logits can compute eight tokens' logits for nearly the same
wall-clock, because the weight reads and the serial op chain are shared.
Single-token decode therefore pays the whole fixed cost per token; the
only lever left standing is fewer, wider ticks. This module is that
lever.

Scheme (prompt-lookup / n-gram self-drafting — no draft model):

1. DRAFT: find the most recent earlier occurrence of the last ``ngram``
   tokens in the sequence so far and propose the ``draft_len`` tokens
   that followed it. On repetitive text (code, logs — e.g. the byte-level
   Python corpus the convergence tracks train on) this guesses long runs
   correctly; on text with no self-similarity it simply proposes junk.
2. VERIFY: run ONE forward over the ``draft_len + 1`` block
   ``[current, d_1..d_k]`` through the ordinary KV-cache decode module —
   the same chunked-prefill path :func:`~pddl_tpu.models.gpt.generate`
   uses for prompts (causal within the block, K/V written at the running
   index, RoPE/positions from the index) — and greedy-decode every
   position: ``y_j = argmax(logits_j)``.
3. ACCEPT the longest prefix with ``d_{j+1} == y_j`` (``m`` drafts), emit
   ``y_0..y_m`` — ``m + 1`` tokens from one tick — and REWIND the cache
   index to the position after the last accepted token. Rejected
   positions hold stale K/V beyond the index; the prefix-bounded cache
   sweep (`ops/attention.py decode_attention`) never reads past the
   index, and the next tick's ``draft_len + 1``-wide write overwrites
   them before the index crosses.

Every emitted token is the argmax of the true model given the true
prefix, so the output is **bit-identical to greedy** ``generate()`` —
acceptance rate changes only the speed. Worst case (nothing ever
matches) each tick still emits one token, i.e. plain greedy decode at
one verify-width forward per tick. One hardware nuance, pinned by
`tests_tpu/`: the k+1-wide verify block and the one-token tick are
different COMPILED programs, so their bf16 logits can differ by ulps —
at a genuine numerical tie (untrained models; never trained margins)
the two argmaxes may break differently, and both outputs are then
valid greedy decodes. The trained-model chip benches assert
bit-equality every run.

Batching: acceptance is ``min`` over the batch (the KV caches share one
scalar index), which stays exact for every row — a row whose drafts
matched further simply re-derives those tokens next tick. The win is
largest at B=1, which is exactly where the fixed per-tick cost dominates
(§7e).

Tensor parallelism composes (``strategy=``): the verify forward runs
Megatron-sharded with its ICI all-reduces while the draft/accept/rewind
machinery stays on the replicated token buffer — acceptance depends
only on logits, which TP reproduces exactly.

Temperature sampling composes too: ``temperature > 0`` switches the
verifier to SPECULATIVE SAMPLING (rejection scheme — accept draft ``d``
with probability ``p(d)``, sample the masked residual on rejection, a
bonus draw when everything survives; see ``_spec_fns``), which draws
every token from exactly the filtered distribution
``gpt.sample_logits`` uses — unbiased, just fewer ticks.

Exclusions, all validated loudly: no sliding-window RING cache (a
partially rejected block has already overwritten ring slots that rolled
out of the window but are still inside it for the rewound position —
unsound to rewind; models whose ``sliding_window`` rounds up to
``>= max_len`` use a full cache and remain eligible); int8
``param_transform`` is unsharded-only.

Reference stake: the reference's endpoint is ``model.save`` then serve
(`/root/reference/imagenet-resnet50.py:72`); this is the serving path's
throughput story for the LM families.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from pddl_tpu.models.gpt import _decode_cache_shapes

__all__ = ["generate_speculative", "ngram_drafts"]


def ngram_drafts(toks, cur_pos, ngram: int, draft_len: int):
    """Prompt-lookup draft: ``[B, draft_len]`` continuations of the most
    recent earlier occurrence of the trailing ``ngram``.

    ``toks`` is the full token buffer ``[B, L]`` (prompt + emitted so
    far; positions > ``cur_pos`` hold junk), ``cur_pos`` the position of
    the last known token — a SCALAR (the one-shot loop below, whose
    rows share one cache index) or a per-row ``[B]`` int32 vector (the
    serving engine's slot model, where every row is an independent
    request at its own depth). THE one drafter definition: the one-shot
    ``generate_speculative`` loop and ``ServeEngine``'s per-slot draft
    program both compile exactly this function, so the two paths cannot
    drift (pinned by an equivalence test). All shapes static;
    `dynamic_slice` clamping makes out-of-range starts harmless (they
    yield junk drafts, which verification rejects — exactness never
    depends on the draft).
    """
    b, length = toks.shape
    cur_pos = jnp.asarray(cur_pos, jnp.int32)
    pos_b = jnp.broadcast_to(cur_pos, (b,))  # [B] either way
    # Trailing n-gram ending at each row's cur_pos (clamped left at the
    # buffer edge). Per-row dynamic_slice via vmap — identical to the
    # historical shared-scalar slice when every row carries one value.
    query = jax.vmap(
        lambda row, p: jax.lax.dynamic_slice(
            row, (p - (ngram - 1),), (ngram,)))(toks, pos_b)  # [B, ngram]
    # All length-n windows: wins[i, :, w] = toks[:, w + i].
    n_win = length - ngram + 1
    wins = jnp.stack([toks[:, i:i + n_win] for i in range(ngram)], axis=0)
    hit = jnp.all(wins == query.T[:, :, None], axis=0)  # [B, n_win]
    # A usable window ends strictly before the row's cur_pos (the
    # window ending AT cur_pos is the query itself).
    starts = jnp.arange(n_win)[None, :]
    usable = hit & (starts <= pos_b[:, None] - ngram)
    best = jnp.max(jnp.where(usable, starts, -1), axis=1)  # [B]
    found = best >= 0

    def take(row, start):  # per-row continuation after the matched window
        return jax.lax.dynamic_slice(row, (start,), (draft_len,))

    drafts = jax.vmap(take)(toks, jnp.where(found, best + ngram, 0))
    # No match → propose the last token repeated: free (the tick runs
    # anyway) and occasionally right on run-length text.
    fallback = jnp.broadcast_to(query[:, -1:], (b, draft_len))
    return jnp.where(found[:, None], drafts, fallback)


# The historical private name (kept so long-lived call sites and tests
# keep working; the public name above is the API).
_ngram_drafts = ngram_drafts


def _rewind_index(cache, new_index):
    """Set every cache position counter to ``new_index``.

    Counters are matched BY NAME (``pos_index``/``cache_index`` —
    :data:`pddl_tpu.models.gpt.CACHE_INDEX_KEYS`, the same registry the
    serving engine's slot machinery uses), never by scalar-int32 duck
    typing: a future scalar int32 cache leaf that is NOT a position (a
    step counter, say) must not be silently rewound.
    ``tests/test_speculative.py`` enumerates the scalar int32 cache
    leaves of every family, so adding one forces a decision here. Stale
    K/V beyond the index is unreachable (prefix-bounded sweep) until
    overwritten by the next block write.
    """
    from pddl_tpu.models.gpt import is_cache_index_path

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: (jnp.full_like(leaf, new_index)
                            if is_cache_index_path(path) else leaf),
        cache)


def _spec_fns(dec, draft_len: int, ngram: int, param_transform=None,
              temperature: float = 0.0, top_k=None, top_p=None):
    """(prefill, loop) python callables — the speculative twin of
    ``gpt._decode_fns``; the jit wrappers below (unsharded and
    tensor-parallel) compile exactly these.

    ``temperature > 0`` switches the verifier from exact-greedy
    acceptance to SPECULATIVE SAMPLING (the standard rejection scheme
    for a point-mass draft): draft ``d`` under target distribution
    ``p`` is accepted with probability ``p(d)``; on the first rejection
    the correction token samples the residual ``norm(max(p - 1_d, 0))``
    — i.e. ``p`` with ``d`` masked out — and when every draft survives,
    a bonus token samples ``p`` directly. Every emitted token is an
    exact draw from the model's (temperature/top-k/top-p filtered)
    conditional, the same distribution ``gpt.sample_logits`` draws from
    (the filter pipeline is literally shared: ``gpt.filtered_logits``),
    so speculation changes the speed, never the distribution. Min-over-
    batch truncation stays unbiased: a truncated row's later tokens are
    re-drawn next tick from the correct conditionals with fresh
    randomness, and its kept tokens used only coins at their own
    positions.
    """
    width = draft_len + 1
    buf_len = dec.max_len + width
    pt = param_transform or (lambda p: p)
    sampling = temperature > 0

    def _warp(logits):  # [..., V] -> f32 filtered sampling logits
        from pddl_tpu.models.gpt import filtered_logits

        return filtered_logits(logits, temperature=temperature,
                               top_k=top_k, top_p=top_p)

    def prefill(params, prompt):
        b, p = prompt.shape
        cache = jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype),
            _decode_cache_shapes(dec, b))
        # pt applies PER USE SITE (here and in the loop body), never
        # once up front: a pre-loop transform would be loop-invariant,
        # and XLA would hoist the dequantized dense weights out of the
        # while loop — materializing exactly the copy int8 storage is
        # meant to avoid.
        logits, mutated = dec.apply(
            {"params": pt(params), "cache": cache}, prompt,
            train=False, mutable=["cache"])
        toks = jnp.zeros((b, buf_len), jnp.int32)
        toks = jax.lax.dynamic_update_slice(toks, prompt, (0, 0))
        return mutated["cache"], toks, logits[:, -1]

    def loop(params, cache, toks, last_logits, prompt_len, max_new, rng):
        b = toks.shape[0]
        if sampling:
            rng, sub = jax.random.split(rng)
            first = jax.random.categorical(sub, _warp(last_logits), axis=-1)
        else:
            first = jnp.argmax(last_logits, axis=-1)
        toks = jax.lax.dynamic_update_slice(
            toks, first.astype(jnp.int32)[:, None], (0, prompt_len))

        def cond(state):
            _, n_out, _, _, _ = state
            return n_out < max_new

        def body(state):
            toks, n_out, cache, ticks, rng = state
            cur_pos = prompt_len + n_out - 1  # position of the last token
            drafts = ngram_drafts(toks, cur_pos, ngram, draft_len)
            cur = jax.lax.dynamic_slice(toks, (0, cur_pos), (b, 1))
            block = jnp.concatenate([cur, drafts], axis=1)  # [B, width]
            logits, mutated = dec.apply(
                {"params": pt(params), "cache": cache}, block,
                train=False, mutable=["cache"])
            cache = mutated["cache"]
            if not sampling:
                y = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # Longest accepted draft prefix, min over the batch
                # (shared cache index): cumprod turns the first mismatch
                # into zeros.
                match = (block[:, 1:] == y[:, :-1]).astype(jnp.int32)
                accepted = jnp.min(
                    jnp.sum(jnp.cumprod(match, axis=1), axis=1))
                window = y
            else:
                flog = _warp(logits)  # [B, width, V]
                probs = jax.nn.softmax(flog, axis=-1)
                rng, k_coin, k_fix = jax.random.split(rng, 3)
                # Coin j tests draft d_{j+1} against p_j: accept w.p.
                # p_j(d_{j+1}) (point-mass draft => the accept ratio is
                # just the target probability).
                p_draft = jnp.take_along_axis(
                    probs[:, :-1], drafts[..., None], axis=-1)[..., 0]
                ok = (jax.random.uniform(k_coin, p_draft.shape)
                      < p_draft).astype(jnp.int32)
                m_row = jnp.sum(jnp.cumprod(ok, axis=1), axis=1)
                accepted = jnp.min(m_row)
                # Token for slot `accepted`: rows whose own coin
                # rejected exactly there (m_row == accepted <
                # draft_len) draw the RESIDUAL (p with the rejected
                # draft masked); when every draft of every row survived
                # (accepted == draft_len, so m_row == accepted for all
                # rows), it's the bonus draw from p_k. Rows truncated
                # by the batch min (m_row > accepted) KEEP their
                # accepted draft — the write below is masked per row,
                # so an already-paid acceptance is never re-drawn.
                flog_last = jax.lax.dynamic_slice(
                    flog, (0, accepted, 0), (b, 1, flog.shape[-1]))[:, 0]
                d_next = jax.lax.dynamic_slice(
                    block, (0, jnp.minimum(accepted + 1, draft_len)),
                    (b, 1))[:, 0]
                rejected_here = (m_row == accepted) & (accepted < draft_len)
                vocab = flog.shape[-1]
                mask = (rejected_here[:, None]
                        & (jax.nn.one_hot(d_next, vocab, dtype=bool)))
                masked = jnp.where(mask, -jnp.inf, flog_last)
                # Degenerate residual (the draft carried ~all the mass,
                # e.g. top_k=1): fall back to the unmasked distribution
                # rather than sampling from all -inf.
                has_mass = jnp.any(masked > -jnp.inf, axis=-1,
                                   keepdims=True)
                masked = jnp.where(has_mass, masked, flog_last)
                fix = jax.random.categorical(k_fix, masked, axis=-1)
                # Write window: accepted drafts verbatim, the correction/
                # bonus at slot `accepted` ONLY for rows that need one
                # (m_row == accepted); truncated rows keep the draft
                # token already sitting in that slot. The stale tail
                # beyond it is overwritten before the frontier reaches
                # it (width >= tail), same invariant as the greedy path.
                window = jnp.concatenate(
                    [drafts, drafts[:, -1:]], axis=1).astype(jnp.int32)
                kept = jax.lax.dynamic_slice(
                    window, (0, accepted), (b, 1))[:, 0]
                slot_tok = jnp.where(m_row == accepted,
                                     fix.astype(jnp.int32), kept)
                window = jax.lax.dynamic_update_slice(
                    window, slot_tok[:, None], (0, accepted))
            toks = jax.lax.dynamic_update_slice(
                toks, window, (0, prompt_len + n_out))
            cache = _rewind_index(cache, cur_pos + accepted + 1)
            return toks, n_out + accepted + 1, cache, ticks + 1, rng

        toks, n_out, _, ticks, _ = jax.lax.while_loop(
            cond, body, (toks, jnp.int32(1), cache, jnp.int32(0), rng))
        return toks, n_out, ticks

    return prefill, loop


@functools.lru_cache(maxsize=16)
def _spec_programs(dec, draft_len: int, ngram: int, param_transform=None,
                   temperature: float = 0.0, top_k=None, top_p=None):
    """Jitted (prefill, loop) pair, cached on the frozen decode module +
    draft statics — like ``gpt._decode_programs``, params stay jit
    ARGUMENTS (never baked-in constants).

    The split mirrors ``generate()``: prefill re-traces per prompt
    SHAPE (it has to — the prompt is an array), while the speculative
    loop compiles ONCE per (module, batch, draft config) — the token
    buffer is fixed at ``max_len + width`` and prompt length / token
    budget enter as int32 runtime values, so varied-length serving
    traffic neither recompiles the loop nor thrashes the LRU. Each
    request is two dispatches (prefill, loop).

    ``param_transform`` (keyed by identity — pass a module-level
    function) maps the passed params to apply-ready weights inside the
    programs: int8 weight storage composes with speculation this way.
    """
    prefill, loop = _spec_fns(dec, draft_len, ngram, param_transform,
                              temperature, top_k, top_p)
    return jax.jit(prefill), jax.jit(loop, donate_argnums=(1, 2))


@functools.lru_cache(maxsize=16)
def _sharded_spec_programs(dec, draft_len: int, ngram: int,
                           param_sh_def, param_sh_leaves,
                           cache_sh_def, cache_sh_leaves,
                           temperature: float = 0.0, top_k=None,
                           top_p=None):
    """Tensor-parallel twin of :func:`_spec_programs` — same body
    functions, compiled with the strategy's parameter/cache shardings
    (the SPMD partitioner inserts the per-block all-reduces on ICI,
    exactly as in ``gpt._sharded_decode_programs``); the token buffer,
    logits, and scalars stay replicated. Keys are sharding VALUES
    (NamedShardings hash by value), so a strategy rebuilt per request
    still hits the cache.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    if not param_sh_leaves:
        raise ValueError(
            "sharded speculative decode needs a non-empty params tree "
            "(got zero parameter leaves — was the model initialized?)")
    param_sh = jax.tree_util.tree_unflatten(param_sh_def, param_sh_leaves)
    cache_sh = jax.tree_util.tree_unflatten(cache_sh_def, cache_sh_leaves)
    repl = NamedSharding(param_sh_leaves[0].mesh, PartitionSpec())
    prefill, loop = _spec_fns(dec, draft_len, ngram, None,
                              temperature, top_k, top_p)
    prefill_j = jax.jit(prefill,
                        in_shardings=(param_sh, repl),
                        out_shardings=(cache_sh, repl, repl))
    loop_j = jax.jit(loop, donate_argnums=(1, 2),
                     in_shardings=(param_sh, cache_sh, repl, repl,
                                   repl, repl, repl),
                     out_shardings=(repl, repl, repl))
    return prefill_j, loop_j


def generate_speculative(
        model, variables, prompt, max_new_tokens: int, *,
        temperature: float = 0.0, top_k=None, top_p=None, rng=None,
        draft_len: int = 7, ngram: int = 3,
        return_stats: bool = False, param_transform=None,
        strategy=None):
    """Speculative generation: bit-identical to ``generate()`` under
    greedy, distribution-identical under sampling, in (often far) fewer
    decode ticks. See the module docstring.

    Args:
      model: a non-decode :class:`~pddl_tpu.models.gpt.GPT` or
        :class:`~pddl_tpu.models.llama.Llama` (anything
        ``generate()``-compatible with a full-length KV cache).
      variables: ``{"params": ...}`` from training / checkpoint import.
      prompt: int32 ``[B, P]``, ``P >= 1``.
      max_new_tokens: tokens to append (exact — same contract as
        ``generate``).
      temperature / top_k / top_p / rng: the ``generate()`` sampling
        surface. 0 → greedy (bit-exact vs ``generate``); > 0 →
        speculative SAMPLING (rejection scheme, ``_spec_fns`` docstring)
        — every token is an exact draw from the same filtered
        conditional ``sample_logits`` uses, but the draw SEQUENCE
        differs from ``generate``'s (different rng consumption), so
        compare distributions, not token strings.
      draft_len: drafted tokens per tick; the verify block is
        ``draft_len + 1`` wide. 7 keeps the block at 8 (MXU-lane
        friendly) and caps the stale-cache tail at one block.
      ngram: lookup key length. 3 balances precision (fewer spurious
        matches) against recall on byte-level corpora.
      return_stats: also return ``{"ticks", "emitted", "tokens_per_tick"}``
        — the acceptance telemetry a serving stack wants on its dash.
      param_transform: optional module-level function mapping
        ``variables["params"]`` to apply-ready weights inside the jitted
        program (int8 weight-only serving,
        :func:`pddl_tpu.ops.quant.dequantize`) — same hook as
        ``generate()``. Unsharded path only.
      strategy: optional tensor-parallel strategy (mesh already set up),
        same contract as ``generate()``: weights and KV cache shard
        Megatron-style over the ``model`` axis, the verify forward runs
        with the per-block all-reduces on ICI, and the draft/accept/
        rewind machinery operates on the replicated token buffer —
        speculation and TP compose because acceptance depends only on
        logits, which TP reproduces exactly.

    Returns ``[B, P + max_new_tokens]`` int32, or ``(tokens, stats)``
    with ``return_stats=True``.
    """
    b, p = prompt.shape
    total = p + max_new_tokens
    if p < 1:
        raise ValueError("generate_speculative() needs a non-empty prompt")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if draft_len < 1:
        raise ValueError(f"draft_len must be >= 1, got {draft_len}")
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")
    if temperature > 0 and rng is None:
        raise ValueError("temperature sampling needs an rng key")
    if temperature <= 0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p require temperature > 0 (greedy decoding would "
            "silently ignore them)")
    # Cache writes reach index draft_len past the last emitted position.
    if total + draft_len > model.max_len:
        raise ValueError(
            f"prompt + new tokens + draft_len {total + draft_len} exceed "
            f"max_len {model.max_len} (speculative blocks write "
            f"draft_len={draft_len} positions of lookahead)")
    if getattr(model, "unrewindable_cache", False):
        # A cache `_rewind_index`'s counter stamp cannot rewind. Ring
        # cache: block writes reuse slots of positions that rolled out
        # of the window — after a partial rejection those slots are
        # back INSIDE the rewound position's window, and their history
        # is gone. Per-slot state (a short convolution's): it has moved
        # on past the rejected tokens. Not recoverable; refuse rather
        # than silently corrupt. (The decision comes from the model —
        # `Llama.unrewindable_cache`, which reads llama.ring_len, the
        # function that sizes the cache, and the layers' operators — so
        # this gate cannot drift.)
        raise NotImplementedError(
            "speculative decoding needs a cache that a position-counter "
            "stamp rewinds: a full-length KV cache in every layer. "
            f"This model (sliding_window={model.sliding_window}, "
            f"{getattr(model, 'slot_state_layers', 0)} layers with "
            "per-slot state) uses a ring cache whose slots cannot be "
            "rewound, or a state that cannot")

    dec = model.clone(decode=True)
    params = variables["params"]
    sampling = (float(temperature), top_k, top_p)
    if strategy is None:
        prefill, loop = _spec_programs(dec, int(draft_len), int(ngram),
                                       param_transform, *sampling)
    else:
        if param_transform is not None:
            raise NotImplementedError(
                "param_transform (int8 serving) is unsharded-only: the "
                "strategy's sharding trees describe the DENSE params "
                "layout")
        param_sh = strategy.tree_sharding(params)
        params = jax.device_put(params, param_sh)
        cache_sh = strategy.decode_cache_sharding(
            _decode_cache_shapes(dec, b))
        p_leaves, p_def = jax.tree_util.tree_flatten(param_sh)
        c_leaves, c_def = jax.tree_util.tree_flatten(cache_sh)
        prefill, loop = _sharded_spec_programs(
            dec, int(draft_len), int(ngram),
            p_def, tuple(p_leaves), c_def, tuple(c_leaves), *sampling)
    if rng is None:
        rng = jax.random.key(0)  # unused under greedy; loop needs a value
    cache, toks, last_logits = prefill(params, prompt)
    toks, n_out, ticks = loop(params, cache, toks, last_logits,
                              jnp.int32(p), jnp.int32(max_new_tokens), rng)
    out = toks[:, :total]
    if not return_stats:
        return out
    # The final tick may overshoot the budget by up to draft_len tokens
    # that the slice above discards — report only DELIVERED tokens, so
    # tokens_per_tick is the serving-visible rate, not the raw
    # acceptance rate.
    emitted = min(int(n_out), int(max_new_tokens))
    ticks = int(ticks)
    return out, {
        "ticks": ticks,
        "emitted": emitted,
        "tokens_per_tick": emitted / max(ticks, 1),
    }
