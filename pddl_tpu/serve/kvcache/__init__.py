"""Prefix-aware KV reuse under the serving engine.

`block_pool.py` is the DEVICE half: the resident pool of fixed-size
token blocks that IS the engine's KV cache (one fused K/V leaf per
attention layer, read and written in place through block tables).
`radix.py` is the HOST half: a refcounted, LRU-evicted radix tree over
token ids mapping prompt prefixes to stored block chains.
`hosttier.py` is the SECOND tier under both (ISSUE 13): a
byte-budgeted pinned-host-memory pool where the radix index's LRU
victims spill instead of dying, and from which admission promotes
matched chains back H2D — see `docs/SERVING.md` § "Tiered KV cache".

See `docs/SERVING.md` § "Prefix caching" for the design and the
engine integration (`pddl_tpu/serve/engine.py`).
"""

from pddl_tpu.serve.kvcache.block_pool import (
    paged_decode_cache,
    pool_nbytes,
    slot_state_nbytes,
)
from pddl_tpu.serve.kvcache.hosttier import HostTierCache, HostTierConfig
from pddl_tpu.serve.kvcache.radix import RadixPrefixCache

__all__ = [
    "HostTierCache",
    "HostTierConfig",
    "RadixPrefixCache",
    "paged_decode_cache",
    "pool_nbytes",
    "slot_state_nbytes",
]
