"""Device-resident KV block pool: the serving engine's KV cache.

The engine has no per-request cache rows: its cache tree IS a pool
(:func:`paged_decode_cache`), one leaf per attention layer (a layer that
keeps a fixed state a SLOT instead — `llama.ShortConv` — gets a
``[max_slots, ...]`` state leaf and no pool; see the builder)
``[N, cache_heads, block_size, lanes]`` whose entries are what the layer
declares it caches of a token — K and V side by side (``2*D`` lanes), or
a latent layer's one ``[c_kv | k_rope]`` — the shape whose layout at
rest every program uses in place (`ops/attention.py`, paged section).
Live streams and cached prompt prefixes alike live
here at fixed-size token-block granularity, reached through per-slot
block tables.

Block ``j`` of a prompt stores the K/V of tokens
``[j*block_size, (j+1)*block_size)`` at their ABSOLUTE positions — both
families' caches are position-absolute (GPT adds the learned position
embedding before the block stack; Llama caches post-RoPE keys rotated
at their global positions), so a prefix block computed by one request
is bit-valid for every later request sharing those prompt tokens, and
a hit references it in place under a refcount pin.

Block id 0 is reserved as a WRITE SINK ("scratch"): parked slots'
table rows and the padding of every fixed-shape id vector point at it,
so one compiled program serves every depth while the radix index
(`radix.py`) never hands out or references block 0. A slot only ever
WRITES blocks it owns privately; a shared block is immutable and
pinned while any table references it, so eviction can never reach
under a decoding request.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pddl_tpu.models.gpt import (
    BLOCK_TABLE_KEY,
    CACHE_INDEX_KEYS,
    _decode_cache_shapes,
    is_paged_pool_path,
    is_slot_state_path,
)
from pddl_tpu.models.vit import (
    PAGED_KV_KEY,
    SLOT_STATE_KEY,
    STATE_SLOT_KEY,
)
from pddl_tpu.ops.moe import VALID_LEN_KEY

# The reserved write-sink block id (see module docstring).
SCRATCH_BLOCK = 0


def paged_decode_cache(dec, num_blocks: int, block_size: int,
                       max_slots: int = 1):
    """The serving cache tree: the pool IS the cache.

    Builds the cache tree the engine hands straight to
    ``dec.apply``: what an attention module declares as its row cache
    (``[1, cache_heads, max_len, width]`` leaves: ``cached_key`` and
    ``cached_value``, or a latent layer's one ``cached_latent``) becomes
    ONE block pool ``[num_blocks, cache_heads, block_size, lanes]``,
    ``lanes`` the widths side by side (K in lanes ``[0, D)``, V in
    ``[D, 2D)``; a latent entry as it is) — the one leaf shape whose
    layout at rest every paged program reads and writes in place;
    `ops/attention.py`'s paged section says why. Which lanes are key
    and which are value is the module's to say where it attends
    (`models/vit.paged_decode_step`). Position counters
    and per-slot block tables are CANONICAL PLACEHOLDERS (scalar 0 /
    ``[1, 1]``) that every paged program re-stamps from engine-owned
    host state on entry and restores on exit — one tree structure
    across the fused tick ([S] counters, [S, T] tables) and the
    batch-1 chunk prefill (scalar counter, [1, T] table), which is
    what keeps the donated resident buffers shape-stable and the
    program set at zero recompiles.

    A layer declares what it keeps by its leaves' KEYS: a per-token
    entry (the row-cache leaves above), or — ``SLOT_STATE_KEY``, ``[1,
    ...]`` a row — a fixed state a slot. The second kind becomes a
    ``[max_slots, ...]`` leaf, sized by slots and not by blocks, with no
    pool and no table: the tick sees it row for row (batch row ``i`` IS
    slot ``i``), a batch-1 chunk reaches its row through the scalar
    ``STATE_SLOT_KEY`` placeholder beside it, stamped and restored like
    the tables, and reads how many of its tokens are real from a
    ``valid_len`` leaf (`gpt.set_cache_valid_len`).

    Block 0 stays the reserved scratch sink: parked slots' table rows
    are all scratch, so their fixed-shape tick writes land on junk the
    radix index never references.
    """
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is the reserved scratch "
            f"sink), got {num_blocks}")
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    if max_slots < 1:
        raise ValueError(f"max_slots must be >= 1, got {max_slots}")
    row = _decode_cache_shapes(dec, 1)

    def _build(tree):
        out = {}
        kv = {}
        for key, val in tree.items():
            name = str(key)
            if hasattr(val, "items"):
                out[name] = _build(val)
            elif name in CACHE_INDEX_KEYS:
                out[name] = jnp.zeros((), jnp.int32)
            elif name == SLOT_STATE_KEY:
                out[name] = jnp.zeros((max_slots,) + val.shape[1:],
                                      val.dtype)
                out[STATE_SLOT_KEY] = jnp.zeros((), jnp.int32)
                out[VALID_LEN_KEY] = jnp.zeros((), jnp.int32)
            else:
                kv[name] = val
        if kv:
            rows = list(kv.values())
            if any(r.ndim != 4 or r.shape[:3] != rows[0].shape[:3]
                   or r.dtype != rows[0].dtype for r in rows):
                raise ValueError(
                    "cannot lay the row-cache leaves "
                    f"{ {k: v.shape for k, v in kv.items()} } side by "
                    "side in one paged pool leaf")
            out[PAGED_KV_KEY] = jnp.zeros(
                (num_blocks, rows[0].shape[1], block_size,
                 sum(r.shape[3] for r in rows)), rows[0].dtype)
            out[BLOCK_TABLE_KEY] = jnp.zeros((1, 1), jnp.int32)
        return out

    cache = _build(row)
    extras = getattr(dec, "paged_cache_extras", None)
    if extras is not None:
        # Leaves the model keeps beside its pools in a paged engine
        # (the Llama family's expert-load counters): merged by module
        # path, carried and donated with the tree like any other leaf.
        def _merge(into, extra):
            for key, val in extra.items():
                if hasattr(val, "items"):
                    _merge(into.setdefault(key, {}), val)
                else:
                    into[key] = val

        _merge(cache, extras())
    return cache


def _nbytes(cache, is_kind) -> int:
    return sum(int(leaf.size) * leaf.dtype.itemsize
               for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
               if is_kind(path))


def pool_nbytes(cache) -> int:
    """Device bytes a cache tree's block POOL leaves occupy — the HBM
    the engine's degraded mode can shed (the number the failure-modes
    runbook in `docs/OPERATIONS.md` reasons about when sizing pools
    against OOM headroom)."""
    return _nbytes(cache, is_paged_pool_path)


def slot_state_nbytes(cache) -> int:
    """Device bytes a cache tree's per-slot state leaves occupy (0 for a
    model whose every layer caches per token)."""
    return _nbytes(cache, is_slot_state_path)
