"""Device-resident KV block pool: the storage half of the prefix cache.

The serving engine's pooled slot cache (`gpt.slot_decode_cache`) holds
each LIVE request's K/V at full request granularity; this module holds
SHARED prompt-prefix K/V at fixed-size token-block granularity, one
pool per K/V cache leaf:

    slot cache leaf   [S,  ..., max_len,     D]   (one row per request)
    block pool leaf   [N,  ..., block_size,  D]   (one row per block)

Block ``j`` of a cached prefix stores the K/V of tokens
``[j*block_size, (j+1)*block_size)`` at their ABSOLUTE positions — both
families' caches are position-absolute (GPT adds the learned position
embedding before the block stack; Llama caches post-RoPE keys rotated
at their global positions), so a prefix block computed by one request
is bit-valid for every later request sharing those prompt tokens.

The PAGED engine has no slot cache and no side pool: its cache tree IS
a pool (:func:`paged_decode_cache`), one fused leaf per attention layer
``[N, kv_heads, block_size, 2*D]`` with K and V side by side — the shape
whose layout at rest every paged program uses in place
(`ops/attention.py`, paged section).

Block id 0 is reserved as a WRITE SINK ("scratch"): fixed-shape gather
and scatter programs pad their runtime id vectors with 0, so one
compiled program serves every hit depth and donation width while the
radix index (`radix.py`) never hands out or references block 0. Data
flow is copy-only in both directions (gather copies pool → slot,
donation copies slot → pool), which is the copy-on-write guarantee: a
concurrent hit can never alias a live slot's storage, and eviction of
a pool block can never reach under a decoding request.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from pddl_tpu.models.gpt import (
    BLOCK_TABLE_KEY,
    CACHE_INDEX_KEYS,
    _decode_cache_shapes,
    is_cache_index_path,
)
from pddl_tpu.models.vit import PAGED_KV_KEY
from pddl_tpu.ops.attention import cache_blocks_gather, cache_blocks_scatter

# The reserved write-sink block id (see module docstring).
SCRATCH_BLOCK = 0


def kv_block_pool(dec, num_blocks: int, block_size: int):
    """A zeros-initialized block pool tree for a decode module.

    Mirrors the row-cache structure (`gpt._decode_cache_shapes`) so the
    gather/donate tree maps below can walk pool and row together; K/V
    leaves become ``[num_blocks, ..., block_size, D]``, position
    counters become scalar placeholders (never read — the pool stores
    token K/V only, positions are implicit in the block index).
    """
    if num_blocks < 2:
        raise ValueError(
            f"num_blocks must be >= 2 (block 0 is the reserved scratch "
            f"sink), got {num_blocks}")
    if block_size < 1:
        raise ValueError(f"block_size must be >= 1, got {block_size}")
    row = _decode_cache_shapes(dec, 1)

    def _leaf(path, sd):
        if is_cache_index_path(path):
            return jnp.zeros((), jnp.int32)
        return jnp.zeros(
            (num_blocks,) + sd.shape[1:-2] + (block_size, sd.shape[-1]),
            sd.dtype)

    return jax.tree_util.tree_map_with_path(_leaf, row)


def paged_decode_cache(dec, num_blocks: int, block_size: int):
    """The PAGED serving cache tree: the pool IS the cache.

    Where :func:`kv_block_pool` builds a pool that sits BESIDE the
    engine's resident slot cache (the copy-in/copy-out prefix cache),
    this builds the cache tree the paged engine hands straight to
    ``dec.apply``: each attention module's K and V become ONE fused
    block pool ``[num_blocks, kv_heads, block_size, 2 * head_dim]``
    (K in lanes ``[0, D)``, V in ``[D, 2D)`` — the one leaf shape whose
    layout at rest every paged program reads and writes in place;
    `ops/attention.py`'s paged section says why), position counters
    and per-slot block tables are CANONICAL PLACEHOLDERS (scalar 0 /
    ``[1, 1]``) that every paged program re-stamps from engine-owned
    host state on entry and restores on exit — one tree structure
    across the fused tick ([S] counters, [S, T] tables) and the
    batch-1 chunk prefill (scalar counter, [1, T] table), which is
    what keeps the donated resident buffers shape-stable and the
    program set at zero recompiles.

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
            else:
                kv[name] = val
        if kv:
            k, v = kv.pop("cached_key"), kv.pop("cached_value")
            if kv or k.shape != v.shape or k.dtype != v.dtype:
                raise ValueError(
                    f"cannot fuse K/V leaves {k.shape} {v.shape} "
                    f"{sorted(kv)} into one paged pool leaf")
            _, hkv, _, d = k.shape
            out[PAGED_KV_KEY] = jnp.zeros(
                (num_blocks, hkv, block_size, 2 * d), k.dtype)
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


def pool_nbytes(pool) -> int:
    """Device bytes a block pool's leaves occupy — the HBM the engine's
    degraded mode can shed (the number the failure-modes runbook in
    `docs/OPERATIONS.md` reasons about when sizing pools against OOM
    headroom)."""
    return sum(int(leaf.size) * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(pool))


def gather_prefix_into_row(pool, row_cache, block_ids):
    """Copy pool blocks ``block_ids [M]`` into positions
    ``[0, M*block_size)`` of every K/V leaf of a batch-1 row cache
    (counters untouched — the caller stamps them with the true cached
    length; junk from scratch-padded ids beyond it is overwritten by
    the suffix prefill or parked past the position counter)."""

    def _g(path, pool_leaf, row_leaf):
        if is_cache_index_path(path):
            return row_leaf
        pre = cache_blocks_gather(pool_leaf, block_ids)
        return jax.lax.dynamic_update_slice(
            row_leaf, pre.astype(row_leaf.dtype), (0,) * row_leaf.ndim)

    return jax.tree_util.tree_map_with_path(_g, pool, row_cache)


def donate_prefix_blocks(pool, row_cache, block_ids, start_block):
    """Write row-cache tokens ``[start_block*bs, (start_block+M)*bs)``
    into pool blocks ``block_ids [M]`` on every K/V leaf — a finished
    prefill donating its prompt's uncached full blocks. ``start_block``
    is traced; padded ids point at the scratch sink."""

    def _s(path, pool_leaf, row_leaf):
        if is_cache_index_path(path):
            return pool_leaf
        return cache_blocks_scatter(pool_leaf, row_leaf, block_ids,
                                    start_block)

    return jax.tree_util.tree_map_with_path(_s, pool, row_cache)
