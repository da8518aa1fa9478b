"""Ring attention: sequence/context parallelism over the ``seq`` mesh axis.

First-class long-context support (absent from the reference, which is a
fixed-224x224 CNN repo — SURVEY.md §5 "Long-context": this is a designed-in
capability of the TPU build, not parity). The sequence dimension is sharded
across devices; each device holds its local Q block permanently and the
K/V blocks *rotate around the ICI ring* via ``lax.ppermute`` — after
``seq``-axis-size steps every Q has attended to every K/V without any
device ever materializing the full sequence (memory O(S/n), comms
bandwidth-optimal on the torus).

Math: blockwise online softmax (same running max/denominator update as the
flash kernel in :mod:`pddl_tpu.ops.attention`) accumulated across ring
steps — numerically exact, not an approximation. Causal masking uses
*global* positions reconstructed from each shard's ring offset, so shards
that lie entirely in the future contribute nothing (their p == 0).

Usage (inside ``jax.shard_map`` over a mesh with a ``seq`` axis)::

    out = ring_attention(q, k, v, axis_name="seq", causal=True)

or at the array level via :func:`sequence_parallel_attention`, which wraps
the shard_map.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pddl_tpu.core.collectives import axis_size, pcast_varying
from pddl_tpu.ops.attention import NEG_INF


def _band_hops(n: int, s_local: int, window: Optional[int]) -> int:
    """Ring rotations that can carry in-band keys (incl. the diagonal).

    The sliding-window band is translation-invariant along the ring, so
    rotation ``i`` contributes iff the shard ``i`` hops back overlaps
    some query's ``(q-window, q]`` — a STATIC property of ``i``:
    ``i·s_local <= window + s_local - 2``. Rotations (and their
    ``ppermute`` hops) beyond that are skipped entirely: compute and ICI
    traffic scale O(window), not O(S)."""
    if window is None:
        return n
    return min(n, (window + s_local - 2) // s_local + 1)


def ring_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    axis_name: str = "seq", *, causal: bool = False,
    scale: Optional[float] = None, window: Optional[int] = None,
) -> jnp.ndarray:
    """Per-shard ring attention; call inside ``shard_map``.

    Args are local shards ``[batch, heads, seq_local, head_dim]``; returns
    the local output shard of exact global attention. K/V may be grouped
    (``H_kv < H``, GQA): the *unexpanded* kv-head-sized shards rotate
    around the ring, so per-hop ``ppermute`` ICI traffic is
    ``H/H_kv``-times smaller than rotating expanded K/V would be.
    ``window`` (requires ``causal``): Mistral-style sliding-window
    attention — the loop stops after :func:`_band_hops` rotations, so a
    long-context SWA model pays O(window) ring compute and comms.
    """
    from pddl_tpu.ops.attention import _gqa_rep

    b, h, s_local, d = q.shape
    hkv = k.shape[1]
    # Shape-static, so the check is free — direct shard_map callers get
    # the descriptive error instead of an opaque reshape failure.
    rep = _gqa_rep(q, k)
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    hops = _band_hops(n, s_local, window)

    # Grouped layout [B, H_kv, rep, S, D] for q and the accumulators; the
    # per-rotation einsums contract each kv head against its whole query
    # group in one pass. rep == 1 (MHA) makes the group axis size-1.
    qf = (q.astype(jnp.float32) * scale_v).reshape(b, hkv, rep, s_local, d)
    q_pos = my * s_local + jnp.arange(s_local)  # global positions of local Q

    def step(i, carry):
        m, l, acc, kc, vc = carry
        # kc/vc originated on shard (my - i) mod n after i rotations.
        src = (my - i) % n
        s = jnp.einsum("bgrqd,bgkd->bgrqk", qf, kc.astype(jnp.float32))
        if causal:
            k_pos = src * s_local + jnp.arange(s_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bgrqk,bgkd->bgrqd", p, vc.astype(jnp.float32))
        # Rotate K/V one hop around the ring (neighbor exchange on ICI).
        perm = [(j, (j + 1) % n) for j in range(n)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return m_new, l, acc, kc, vc

    # pcast-to-varying: the accumulators are logically per-shard
    # (device-varying along the ring axis) even though their initial values
    # are constants.
    def _vary(x):
        return pcast_varying(x, axis_name)

    m0 = _vary(jnp.full((b, hkv, rep, s_local, 1), NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros((b, hkv, rep, s_local, 1), jnp.float32))
    acc0 = _vary(jnp.zeros((b, hkv, rep, s_local, d), jnp.float32))
    m, l, acc, _, _ = lax.fori_loop(0, hops, step, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(b, h, s_local, d).astype(q.dtype)


def ring_attention_flash(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    axis_name: str = "seq", *, causal: bool = False,
    scale: Optional[float] = None, window: Optional[int] = None,
) -> jnp.ndarray:
    """Ring attention whose per-rotation compute is the FLASH kernel.

    The XLA path (:func:`ring_attention`) materializes an
    ``[s_local, s_local]`` score block per rotation; here each rotation
    runs :func:`~pddl_tpu.ops.attention.flash_attention_lse` on the
    local Q against the visiting K/V shard (scores stay in VMEM) and
    the normalized partials merge in logsumexp space:
    ``o = Σᵢ oᵢ·exp(lseᵢ − m) / Σᵢ exp(lseᵢ − m)``. Under ``causal``,
    the diagonal rotation (``src == my``) runs the causal kernel,
    earlier shards (``src < my``) run unmasked, later shards contribute
    nothing (lse = −inf) — block-level causality over the ring, exact
    row-level causality inside the kernel.

    ``window`` (requires ``causal``): the rotation loop UNROLLS to the
    :func:`_band_hops` in-band rotations, each running the kernel with a
    static ``k_offset = -i·s_local`` so its causal+window mask sits at
    the visiting shard's true positions; out-of-band rotations (and
    their ppermute hops) never execute.
    """
    from pddl_tpu.ops.attention import flash_attention_lse

    b, h, s_local, d = q.shape
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale_v = (1.0 / math.sqrt(d)) if scale is None else scale
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    perm = [(j, (j + 1) % n) for j in range(n)]

    def merge(m, s, acc, o_i, lse_i):
        m_new = jnp.maximum(m, lse_i)
        alpha = jnp.exp(m - m_new)
        w = jnp.exp(lse_i - m_new)
        s = s * alpha + w
        acc = acc * alpha[..., None] + o_i.astype(jnp.float32) * w[..., None]
        return m_new, s, acc

    def step(i, carry):
        m, s, acc, kc, vc = carry
        # The visiting shard originated on src = my - i (mod n); for
        # i >= 1 it is never the diagonal: strictly past iff my >= i.
        o_i, lse_i = flash_attention_lse(q, kc, vc, causal=False,
                                         scale=scale_v)
        if causal:
            keep = (my - i) % n < my
            # Future shards contribute nothing: -inf lse makes their merge
            # weight w == 0, which also zeroes o_i. The kernel still runs
            # on those devices — the per-rotation ppermute barrier means
            # the busiest device sets each rotation's wall-clock, so the
            # wasted flops cost no time. Masking instead of lax.cond also
            # removes one of the two check_vma blockers; the kernel's own
            # internals remain the other (see sequence_parallel_attention).
            lse_i = jnp.where(keep, lse_i, NEG_INF)
        m, s, acc = merge(m, s, acc, o_i, lse_i)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return m, s, acc, kc, vc

    def _vary(x):
        return pcast_varying(x, axis_name)

    # Rotation 0 always sees the device's own K/V shard (src == my). Under
    # causal that is the diagonal block, which needs row-level masking
    # INSIDE the kernel — selecting the causal kernel statically here
    # removes the data-dependent branch entirely.
    o0, lse0 = flash_attention_lse(q, k, v, causal=causal, scale=scale_v,
                                   window=window)
    m0 = _vary(jnp.full((b, h, s_local), NEG_INF, jnp.float32))
    s0 = _vary(jnp.zeros((b, h, s_local), jnp.float32))
    acc0 = _vary(jnp.zeros((b, h, s_local, d), jnp.float32))
    m, s, acc = merge(m0, s0, acc0, o0, lse0)

    if window is not None:
        # Unrolled in-band rotations: i is a Python int, so the kernel's
        # k_offset (and the band-skip predicates inside it) are static.
        hops = _band_hops(n, s_local, window)
        kc, vc = k, v
        for i in range(1, hops):
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            o_i, lse_i = flash_attention_lse(
                q, kc, vc, causal=True, window=window,
                k_offset=-i * s_local, scale=scale_v)
            # Wrapped sources are future shards: zero their weight.
            lse_i = jnp.where((my - i) % n < my, lse_i, NEG_INF)
            m, s, acc = merge(m, s, acc, o_i, lse_i)
        return (acc / jnp.maximum(s, 1e-30)[..., None]).astype(q.dtype)

    kc = lax.ppermute(k, axis_name, perm)
    vc = lax.ppermute(v, axis_name, perm)
    m, s, acc, _, _ = lax.fori_loop(1, n, step, (m, s, acc, kc, vc))
    return (acc / jnp.maximum(s, 1e-30)[..., None]).astype(q.dtype)


def sequence_parallel_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    mesh: Mesh, *, axis_name: str = "seq", causal: bool = False,
    scale: Optional[float] = None, use_flash: bool = False,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Array-level wrapper: global ``[B, H, S, D]`` inputs sharded on S.

    Installs the shard_map over ``mesh``'s sequence axis; XLA lowers the
    per-step ``ppermute`` to ICI neighbor exchange. ``use_flash`` routes
    each rotation through the Pallas kernel (:func:`ring_attention_flash`)
    instead of the XLA einsum path — same math (in f32 bit-comparable;
    bf16 inputs see one extra per-rotation rounding where the XLA path
    keeps a single f32 accumulator), with O(block) instead of
    O(s_local²) score memory per rotation.

    ``window`` (requires ``causal``): sliding-window attention composed
    with the ring — rotations whose shard lies wholly outside the band
    are skipped (no kernel launch, no ppermute hop), so long-context SWA
    costs O(window) per device instead of O(S).
    """
    from pddl_tpu.ops.attention import _gqa_rep, _normalize_window

    _gqa_rep(q, k)  # validate head grouping before entering the shard_map
    window = _normalize_window(window, causal, k.shape[-2])
    spec = P(None, None, axis_name, None)
    inner = ring_attention_flash if use_flash else ring_attention
    fn = functools.partial(inner, axis_name=axis_name,
                           causal=causal, scale=scale, window=window)
    # check_vma: the flash ring is branch-free (the former lax.cond around
    # the pallas call is gone), but the varying-axes checker still cannot
    # see through the pallas kernel itself: its internal dynamic_slices mix
    # varying ref data with invariant grid indices, and jax's own error
    # says to "pass the check_vma=False argument" until that propagation
    # exists. tests/test_attention.py::test_flash_ring_check_vma_limitation
    # pins the exact failure so a jax upgrade that fixes it flips this
    # flag. The XLA ring path runs fully checked.
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=not use_flash,
    )(q, k, v)
