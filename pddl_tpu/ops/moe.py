"""Mixture-of-Experts: top-k routed FFN (Switch top-1 / GShard top-2),
expert-parallel ready.

Beyond-parity capability (the reference has no MoE — SURVEY.md §2c lists
expert parallelism as absent; the mesh reserves an ``expert`` axis for it,
``pddl_tpu/core/mesh.py``). TPU-first formulation:

- **Dense one-hot dispatch** (the Mesh-TF/Switch-Transformer pattern):
  routing becomes two einsums against a ``[tokens, experts, capacity]``
  dispatch tensor — all FLOPs are MXU contractions with static shapes; no
  gather/scatter, no dynamic shapes, nothing XLA can't tile.
- **Expert-major weights**: expert FFN kernels are ``[n_experts, ...]`` so
  sharding dim 0 over the ``expert`` mesh axis places one expert group per
  device; XLA lowers the dispatch/combine einsums to the all-to-alls.
- **Capacity factor**: batch rows are the dispatch groups; each expert
  processes at most ``capacity_factor * top_k * seq / n_experts`` tokens
  per group (dispatch tensors are ``[B, S, N, C]`` — linear in batch;
  top-2 routes twice the token-slots, so capacity scales with ``top_k``).
  Overflow tokens pass through the residual (standard Switch behavior),
  keeping per-expert work static-shaped.
- **Load-balancing aux loss** (Switch loss: ``n·Σ fᵢ·Pᵢ``) is exported via
  ``self.sow("losses", ...)``; the Trainer adds every sown loss to the
  task loss.

That is the TRAINING path. At eval and serving (``train=False``, the
default ``eval_dropless``) the layer runs TOKEN-MAJOR and dropless
(:func:`grouped_expert_ffn`): the token–expert pairs are sorted by
expert, each expert's run of rows goes through its own weights a tile
at a time, and every token gathers its ``top_k`` results back. It
computes the routed pairs and at most ``num_experts`` tiles of padding
over them, builds nothing ``[B, S, N, S]``-shaped (a 12k-token prefill
chunk is a 74k-row sort, not a 9.6-billion-entry dispatch tensor), and a
decode step reads the weights of the experts its rows hit and no others.
On a TPU the gated experts' tiles run as one Pallas kernel a layer
(:func:`_expert_ffn_body`), each hit expert's weights streamed through
VMEM once while the MXU works on the blocks before them.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental import pallas as pl

# Cache-collection leaves a serving MoE layer accumulates into when the
# paged engine put them there (`Llama.paged_cache_extras`): per-expert
# counts of the routed pairs of prompt tokens, and the number of rows of
# the chunk that hold real tokens (stamped by `gpt.prefill_row_from`).
EXPERT_LOAD_KEY = "expert_load"
VALID_LEN_KEY = "valid_len"

EXPERT_ACTS = {"gelu": nn.gelu, "swiglu": nn.silu, "reglu": nn.relu}

# What `grouped_expert_ffn` reports at trace time, one event a routed
# layer, by the path its FFN took (`jax.monitoring`; the serving engine
# counts them under the set-up stage or step phase open on the thread,
# `ServeMetrics.moe_kernel_layers` / `moe_loop_layers`).
KERNEL_LAYER_EVENT = "/pddl_tpu/moe/kernel_layer"
LOOP_LAYER_EVENT = "/pddl_tpu/moe/loop_layer"

# The expert kernel moves rows between HBM and VMEM in pieces of this
# many, the height of a bf16 tile of the HBM layout: a copy must start
# at a multiple of it.
ROW_ALIGN = 16


def expert_tile(pairs: int, num_experts: int) -> int:
    """Rows per tile of :func:`grouped_expert_ffn`'s loop: about one
    expert's even share of the pairs, a power of two in [16, 512]. One
    tile reads its expert's weights once, so a tile should hold the
    expert's whole run where it can (a prefill chunk is bound by those
    reads until a run is some 256 rows long); 16 rows is the narrowest
    bf16 tile, which is what a decode step's one or two rows per expert
    pad to."""
    share = -(-pairs // num_experts)
    return min(512, max(16, 1 << (share - 1).bit_length()))


def _kernel_rows(pairs: int, num_experts: int, d: int,
                 itemsize: int) -> tuple:
    """``(cap, sub)`` of the expert kernel: a tile holds up to ``cap``
    rows of one expert's run (eight times :func:`expert_tile`, so a run
    of several times the even share still reads its expert's weights
    once; at most 1,024 rows and what 40 MiB of VMEM holds of the rows'
    three buffers and float32 sums), computed ``sub`` rows at a time
    (the MXU's 128, or the tile where it is narrower: 16 in a decode
    step, whose runs are a few rows)."""
    tile = expert_tile(pairs, num_experts)
    sub = min(128, tile)
    fits = (40 << 20) // (d * (3 * itemsize + 4)) // sub * sub
    return max(sub, min(1024, 8 * tile, fits)), sub


def _h_block(d: int, h: int, itemsize: int) -> int:
    """Hidden columns a grid step of the expert kernel streams: the
    widest multiple of 128 that divides ``h`` with the three weight
    blocks (``d x hb`` gate and up, ``hb x d`` down) within 8 MiB, so
    two steps' blocks sit double-buffered in VMEM; ``h`` itself where it
    is no multiple of 128."""
    if h % 128:
        return h
    fits = [hb for hb in range(128, h + 1, 128)
            if h % hb == 0 and 3 * d * hb * itemsize <= 8 << 20]
    return max(fits, default=128)


def grouped_expert_ffn(x, expert_index, gates, w_in, w_out, *,
                       act: str, w_gate=None, b_in=None, b_out=None,
                       tile: Optional[int] = None,
                       kernel: Optional[bool] = None,
                       interpret: Optional[bool] = None):
    """Dropless routed FFN, token-major: ``y[t] = sum_j gates[t, j] *
    FFN_{expert_index[t, j]}(x[t])``.

    ``x [T, d]``; ``expert_index``/``gates`` ``[T, k]``; expert-major
    weights ``w_in [N, d, h]``, ``w_out [N, h, d]``, for gated experts
    ``w_gate [N, d, h]`` (``act(x w_gate) * (x w_in)``), for the biased
    GELU expert ``b_in [N, h]`` / ``b_out [N, d]``.

    The ``P = T k`` pairs are sorted by expert (stable, so a token's
    pairs keep their order), each expert's run of rows is cut into
    tiles and goes through its expert's weights, and every token
    gathers its ``k`` rows back. On a TPU a gated, bias-free expert runs
    as one Pallas kernel (:func:`_expert_kernel`; ``kernel=None``
    selects it there, ``interpret`` as the backend says); anywhere
    else, and for the biased GELU expert, as a loop of one tile a step
    (:func:`_expert_loop`). Either way every pair is computed, whatever
    the routing (every token to one expert included), and an expert
    with no pair costs nothing, not even a read of its weights.
    ``tile`` overrides the rows of a tile (:func:`expert_tile` for the
    loop, :func:`_kernel_rows`' cap for the kernel, where it is a
    multiple of 16).
    """
    # Weights may arrive as host arrays (an imported checkpoint): the
    # loop indexes them with a traced expert.
    w_in, w_out, w_gate, b_in, b_out = (
        None if w is None else jnp.asarray(w)
        for w in (w_in, w_out, w_gate, b_in, b_out))
    t, d = x.shape
    k = expert_index.shape[1]
    n = w_in.shape[0]
    pairs = t * k
    if kernel is None:
        kernel = (jax.default_backend() == "tpu" and w_gate is not None
                  and b_in is None and b_out is None)
    jax.monitoring.record_event(
        KERNEL_LAYER_EVENT if kernel else LOOP_LAYER_EVENT)
    if kernel:
        itemsize = max(x.dtype.itemsize, w_in.dtype.itemsize)
        cap, sub = _kernel_rows(pairs, n, d, itemsize)
        if tile is not None:
            cap = sub = int(tile)
            if cap % ROW_ALIGN:
                raise ValueError(f"the expert kernel's tile must be a "
                                 f"multiple of {ROW_ALIGN}, got {cap}")
    else:
        cap = expert_tile(pairs, n) if tile is None else int(tile)
    with jax.named_scope("moe_dispatch"):
        order, tiles = _sort_pairs(expert_index, n, cap)
        tok_sorted = order // k   # pair p is token p // k's
    if kernel:
        y_sorted = _expert_kernel(
            x, tok_sorted, tiles, w_gate, w_in, w_out, act=act, cap=cap,
            sub=sub, interpret=(jax.default_backend() != "tpu"
                                if interpret is None else interpret))
    else:
        y_sorted = _expert_loop(x, tok_sorted, tiles, w_in, w_out, w_gate,
                                b_in, b_out, act=act, tile=cap)
    with jax.named_scope("moe_combine"):
        # Where pair p landed in the sorted order: the inverse of `order`.
        _, where = jax.lax.sort_key_val(
            order, jnp.arange(pairs, dtype=jnp.int32))
        where = where.reshape(t, k)
        gates = gates.astype(jnp.float32)
        # One choice at a time: a [T, k, d] float32 gather would be the
        # layer's largest temporary.
        out = sum(y_sorted[where[:, j]].astype(jnp.float32)
                  * gates[:, j:j + 1] for j in range(k))
        return out.astype(x.dtype)


def _sort_pairs(expert_index, n: int, tile: int):
    """The ``(token, choice)`` pairs sorted by expert, ``order``, and
    each expert's run of ``c_e`` rows cut into ``ceil(c_e / tile)``
    tiles: ``(expert, first row, rows)`` of each tile in row order and
    the number of tiles. The tables have room for the most tiles any
    routing makes (``c`` rows make at most ``(c + tile - 1) / tile``
    tiles, over at most ``min(N, P)`` hit experts); their entries past
    the last real tile repeat it."""
    pairs = expert_index.size
    flat = expert_index.reshape(pairs).astype(jnp.int32)
    sorted_e, order = jax.lax.sort_key_val(
        flat, jnp.arange(pairs, dtype=jnp.int32))
    bounds = jnp.searchsorted(
        sorted_e, jnp.arange(n + 1, dtype=jnp.int32),
        method="compare_all").astype(jnp.int32)
    starts, counts = bounds[:-1], bounds[1:] - bounds[:-1]
    tiles_per = (counts + tile - 1) // tile
    tile_cum = jnp.cumsum(tiles_per)
    ntiles = tile_cum[-1].astype(jnp.int32)
    room = (pairs + min(n, pairs) * (tile - 1)) // tile
    ids = jnp.minimum(jnp.arange(room, dtype=jnp.int32), ntiles - 1)
    tile_e = jnp.searchsorted(tile_cum, ids, side="right",
                              method="compare_all").astype(jnp.int32)
    first = (ids - (tile_cum[tile_e] - tiles_per[tile_e])) * tile
    return order, (tile_e, (starts[tile_e] + first).astype(jnp.int32),
                   jnp.minimum(tile, counts[tile_e] - first).astype(
                       jnp.int32), ntiles)


def _expert_loop(x, tok_sorted, tiles, w_in, w_out, w_gate, b_in, b_out, *,
                 act: str, tile: int):
    """The sorted pairs' FFN rows ``[P + tile, d]`` as a loop of one tile
    a step: an iteration gathers a tile's rows of ``x``, multiplies them
    through its expert's weights and writes the result at the tile's
    first row. A run's last tile reaches into the next expert's rows;
    that expert's own tile comes later in the loop and overwrites them,
    so no mask and no padded copy of ``x`` is needed. Rows computed:
    ``sum_e ceil(c_e / tile) * tile <= P + N * (tile - 1)``."""
    tile_e, tile_row, _, ntiles = tiles
    fn = EXPERT_ACTS[act]
    with jax.named_scope("moe_dispatch"):
        # `tile` rows of padding so a run's last tile may read past the
        # end.
        tok_sorted = jnp.concatenate(
            [tok_sorted, jnp.zeros((tile,), jnp.int32)])

    def body(i, y_sorted):
        e, r0 = tile_e[i], tile_row[i]
        xs = x[jax.lax.dynamic_slice(tok_sorted, (r0,), (tile,))]
        up = xs @ w_in[e]
        if b_in is not None:
            up = up + b_in[e]
        hid = fn(xs @ w_gate[e]) * up if w_gate is not None else fn(up)
        y = hid @ w_out[e]
        if b_out is not None:
            y = y + b_out[e]
        return jax.lax.dynamic_update_slice(y_sorted, y.astype(x.dtype),
                                            (r0, 0))

    with jax.named_scope("moe_ffn"):
        return jax.lax.fori_loop(
            0, ntiles, body,
            jnp.zeros((tok_sorted.shape[0], x.shape[1]), x.dtype))


def _expert_kernel(x, tok_sorted, tiles, w_gate, w_in, w_out, *, act: str,
                   cap: int, sub: int, interpret: bool):
    """The sorted pairs' FFN rows ``[P16, d]`` (``P`` rounded up to a
    multiple of ``ROW_ALIGN``) through :func:`_expert_ffn_call`: the rows
    of ``x`` gathered once, in sorted order, then one kernel call."""
    d = x.shape[1]
    n, _, h = w_in.shape
    rows = -(-tok_sorted.shape[0] // ROW_ALIGN) * ROW_ALIGN
    with jax.named_scope("moe_dispatch"):
        x_rows = x[jnp.concatenate(
            [tok_sorted,
             jnp.zeros((rows - tok_sorted.shape[0],), jnp.int32)])]
    tile_e, tile_row, tile_rows, ntiles = tiles
    with jax.named_scope("moe_ffn"):
        y = _expert_ffn_call(
            tile_e, tile_row, tile_rows, ntiles.reshape(1),
            x_rows.reshape(rows // ROW_ALIGN, ROW_ALIGN, d), w_gate, w_in,
            w_out, act=act, cap=cap, sub=sub,
            hb=_h_block(d, h, max(x.dtype.itemsize, w_in.dtype.itemsize)),
            interpret=interpret)
    return y.reshape(rows, d)


def _expert_ffn_body(tile_e_ref, tile_row_ref, tile_rows_ref, ntiles_ref,
                     x_hbm, wg_ref, wi_ref, wo_ref, y_hbm,
                     xbuf, obuf, acc_ref, carry_ref, x_sem, y_sem,
                     pending_ref, *, act: str, sub: int, mid):
    """One grid step ``(i, j)``: tile ``i``'s rows through hidden block
    ``j`` of its expert's weights (the pipeline's blocks ``wg_ref``,
    ``wi_ref``, ``wo_ref``, the next step's in flight meanwhile).

    Rows move by hand, in pieces of ``ROW_ALIGN`` (the HBM tiling): a
    tile's rows ``[s, s + c)`` arrive as the pieces that cover them, into
    ``xbuf[i % 2]`` while tile ``i - 1`` computes (started at its first
    step, waited for at ``i``'s), and the rows before ``s`` in the first
    piece, the previous tile's, are computed too and thrown away. The
    finished pieces leave from ``obuf`` while the next tile computes
    (waited for before ``obuf`` is written again, and at the grid's last
    step). A piece two tiles share is written by the later one: the
    earlier keeps its rows in ``carry_ref``, the later takes them from
    there. ``acc_ref`` keeps the down projection's float32 sums over the
    hidden blocks; the gate and up products are rounded to ``mid``, the
    dtype the loop's matmuls give, and the rows once, at the last
    block."""
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    i, j = pl.program_id(0), pl.program_id(1)
    last_i, last_j = pl.num_programs(0) - 1, pl.num_programs(1) - 1
    ntiles = ntiles_ref[0]
    live = i < ntiles
    side = i % 2

    def span(tile):
        """(first piece, pieces) of a tile's rows."""
        s = tile_row_ref[tile]
        first = s // ROW_ALIGN
        return first, pl.cdiv(s + tile_rows_ref[tile], ROW_ALIGN) - first

    def copies(first, pieces, make, start):
        def one(p, carry):
            copy = make(first + p, pl.ds(pl.multiple_of(p * ROW_ALIGN,
                                                        ROW_ALIGN), ROW_ALIGN))
            copy.start() if start else copy.wait()
            return carry

        jax.lax.fori_loop(0, pieces, one, None)

    def rows_in(tile, to, start):
        copies(*span(tile),
               lambda src, dst: pltpu.make_async_copy(
                   x_hbm.at[src], xbuf.at[to, dst], x_sem.at[to]), start)

    def rows_out(first, pieces, start):
        copies(first, pieces,
               lambda dst, src: pltpu.make_async_copy(
                   obuf.at[src], y_hbm.at[dst], y_sem.at[0]), start)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _first():
        pending_ref[0] = 0
        rows_in(0, 0, start=True)

    @pl.when(jnp.logical_and(live, j == 0))
    def _rows_arrive():
        rows_in(i, side, start=False)

        @pl.when(i + 1 < ntiles)
        def _next():
            rows_in(i + 1, 1 - side, start=True)

    @pl.when(jnp.logical_and(live, j == last_j))
    def _obuf_free():
        rows_out(0, pending_ref[0], start=False)

    lead = tile_row_ref[i] % ROW_ALIGN   # the previous tile's rows

    @pl.when(live)
    def _compute():
        f32 = jnp.float32

        def block(s, carry):
            r = pl.ds(pl.multiple_of(s * sub, sub), sub)
            xs = xbuf[side, r, :]
            gate = jnp.dot(xs, wg_ref[...], preferred_element_type=f32)
            up = jnp.dot(xs, wi_ref[...], preferred_element_type=f32)
            gate = EXPERT_ACTS[act](gate.astype(mid).astype(f32))
            hid = (gate * up.astype(mid).astype(f32)).astype(mid)
            part = jnp.dot(hid, wo_ref[...], preferred_element_type=f32)

            @pl.when(j == 0)
            def _open():
                acc_ref[r, :] = part

            @pl.when(jnp.logical_and(j > 0, j < last_j))
            def _add():
                acc_ref[r, :] += part

            @pl.when(j == last_j)
            def _close():
                total = part if last_j == 0 else acc_ref[r, :] + part
                obuf[r, :] = total.astype(obuf.dtype)

            return carry

        jax.lax.fori_loop(
            0, pl.cdiv(lead + tile_rows_ref[i], sub), block, None)

    @pl.when(jnp.logical_and(live, j == last_j))
    def _rows_leave():
        first, pieces = span(i)
        head = pl.ds(0, ROW_ALIGN)

        @pl.when(lead > 0)
        def _take_carry():
            row = jax.lax.broadcasted_iota(jnp.int32, carry_ref.shape, 0)
            obuf[head, :] = jnp.where(
                row < lead, carry_ref[...].astype(jnp.float32),
                obuf[head, :].astype(jnp.float32)).astype(obuf.dtype)

        # The last piece, where the next tile starts inside it, waits
        # for that tile; the grid's last tile writes it.
        ends_inside = jnp.logical_and(
            (tile_row_ref[i] + tile_rows_ref[i]) % ROW_ALIGN > 0,
            i < ntiles - 1)
        whole = pieces - ends_inside.astype(jnp.int32)

        @pl.when(ends_inside)
        def _keep_carry():
            carry_ref[...] = obuf[pl.ds(pl.multiple_of(
                whole * ROW_ALIGN, ROW_ALIGN), ROW_ALIGN), :]

        rows_out(first, whole, start=True)
        pending_ref[0] = whole

    @pl.when(jnp.logical_and(i == last_i, j == last_j))
    def _drain():
        rows_out(0, pending_ref[0], start=False)


@functools.partial(jax.jit, static_argnames=(
    "act", "cap", "sub", "hb", "interpret"))
def _expert_ffn_call(tile_e, tile_row, tile_rows, ntiles, x_rows, w_gate,
                     w_in, w_out, *, act: str, cap: int, sub: int, hb: int,
                     interpret: bool):
    """The ``pallas_call`` of the expert kernel: grid ``(tiles, h //
    hb)``, the weight blocks picked by the scalar-prefetched ``tile_e``
    (a step past the last real tile maps to that tile's last block, so
    nothing is fetched for it), ``x_rows`` and the result ``[P16 / 16,
    16, d]`` left in HBM and moved by :func:`_expert_ffn_body`. A ``jit``
    of its own so that jax traces and lowers the kernel once a shape, not
    once a layer."""
    from jax.experimental.pallas import tpu as pltpu  # noqa: PLC0415

    n, d, h = w_in.shape
    nj = h // hb
    mid = jnp.result_type(x_rows.dtype, w_in.dtype)

    def gate_up(i, j, tile_e, tile_row, tile_rows, ntiles):
        return tile_e[i], 0, jnp.where(i < ntiles[0], j, nj - 1)

    def down(i, j, tile_e, tile_row, tile_rows, ntiles):
        return tile_e[i], jnp.where(i < ntiles[0], j, nj - 1), 0

    # A tile's rows and the previous tile's in its first piece, rounded
    # up to whole blocks of `sub`.
    span = cap + sub
    wsize, xsize = w_in.dtype.itemsize, x_rows.dtype.itemsize
    vmem = (2 * 3 * d * hb * wsize + 3 * span * d * xsize + span * d * 4
            + sub * (2 * hb + d) * 4)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tile_e.shape[0], nj),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((None, d, hb), gate_up),
                  pl.BlockSpec((None, d, hb), gate_up),
                  pl.BlockSpec((None, hb, d), down)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, span, d), x_rows.dtype),  # rows in, two tiles
            pltpu.VMEM((span, d), x_rows.dtype),     # rows out
            pltpu.VMEM((span, d), jnp.float32),      # sums over blocks
            pltpu.VMEM((ROW_ALIGN, d), x_rows.dtype),  # a shared piece
            pltpu.SemaphoreType.DMA((2,)),           # one a rows-in buffer
            pltpu.SemaphoreType.DMA((1,)),
            pltpu.SMEM((1,), jnp.int32),             # row pieces in flight
        ],
    )
    return pl.pallas_call(
        functools.partial(_expert_ffn_body, act=act, sub=sub, mid=mid),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x_rows.shape, x_rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(vmem * 1.25) + (8 << 20)),
        interpret=interpret,
    )(tile_e, tile_row, tile_rows, ntiles, x_rows, w_gate, w_in, w_out)


class SwitchFFN(nn.Module):
    """Top-k routed expert FFN (drop-in for a transformer MLP block).

    Input/output ``[batch, seq, embed]``. ``top_k=1`` is the Switch
    Transformer; ``top_k=2`` is GShard/Mixtral-style routing where every
    token is processed by its two highest-probability experts with the
    two gates renormalized to sum to one (``normalize_gates`` — exactly
    transformers' Mixtral routing: softmax over all experts, top-k,
    renormalize by the kept sum), second choices queueing behind the
    group's first choices for capacity.

    Expert architecture (``expert_act``):

    - ``"gelu"`` — two-layer GELU FFN with biases, hidden
      ``mlp_ratio·embed`` (the Switch classic; the ViT family's MoE).
    - ``"swiglu"`` — ``w2·(silu(x·w1) ⊙ (x·w3))``, bias-free, hidden
      ``hidden_dim`` (the Mixtral expert; parameter names w1/w3/w2
      follow the HF checkpoint layout so
      :func:`pddl_tpu.ckpt.hf_import.load_hf_llama` maps them 1:1).
    - ``"reglu"`` — the same with ``relu`` for the gate (sparse ReGLU
      experts: a zero gate zeroes the row of ``w2`` it would read).

    ``router_logits`` (call argument): logits ``[B, S, N]`` computed
    outside the layer — a block that routes from its attention's normed
    input, so that expert weights can be fetched while attention runs,
    hands them in and the layer declares no router of its own.

    Routing of the DeepSeek-V3 lineage (``noaux_tc``), every piece its
    own attribute: ``router_score="sigmoid"`` scores each expert by
    ``sigmoid(logit)`` (a bias-free router: this lineage's only bias is
    the next one); ``select_bias`` adds a learned per-expert ``[N]``
    float32 bias to the scores FOR THE CHOICE OF EXPERTS ONLY — the
    gates are the unbiased scores of the chosen ones; ``gate_scale``
    multiplies the (renormalised) gates; ``shared_experts`` adds, after
    the combine, a plain gated MLP of ``shared_experts * hidden`` that
    every token goes through (scope ``moe_shared``).
    """

    num_experts: int
    mlp_ratio: int = 4
    hidden_dim: int | None = None  # overrides mlp_ratio * embed when set
    top_k: int = 1
    capacity_factor: float = 1.25
    expert_act: str = "gelu"  # "gelu" | "swiglu" (Mixtral) | "reglu"
    normalize_gates: bool = True  # top_k >= 2: g_j / sum_j g_j
    router_score: str = "softmax"  # "softmax" | "sigmoid"
    select_bias: bool = False  # a bias on the choice of experts only
    gate_scale: float = 1.0  # routed_scaling_factor
    shared_experts: int = 0  # always-on experts beside the routed ones
    aux_loss_weight: float = 0.01
    # Eval/serving (train=False) uses capacity == seq — enough for the
    # worst case: the k choices per token are DISTINCT experts (each
    # choice zeroes its expert from `remaining`), so one expert can
    # receive at most S tokens per batch row. Inference is therefore
    # DROPLESS regardless of capacity_factor. Real Mixtral checkpoints
    # assume dropless routing; without this, an imbalanced prompt
    # silently diverges from the reference logits. The dropless path
    # is token-major (`grouped_expert_ffn`), not a capacity-S dispatch.
    eval_dropless: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = True, /, router_logits=None):
        # train is positional-only to match the transformer blocks'
        # remat static_argnums convention (vit.TransformerBlock).
        b, s, d = x.shape
        n = self.num_experts
        if not 1 <= self.top_k <= n:
            raise ValueError(
                f"top_k={self.top_k} must be in [1, num_experts={n}]")
        if self.expert_act not in EXPERT_ACTS:
            raise ValueError(f"unknown expert_act {self.expert_act!r}")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown router_score {self.router_score!r}")
        gated = self.expert_act != "gelu"
        if self.shared_experts and not gated:
            raise ValueError("shared_experts are gated MLPs: expert_act "
                             f"{self.expert_act!r} has no gate")
        # Batch rows are the dispatch groups (the Switch/Mesh-TF "group"
        # dim): capacity is per group, so dispatch/combine are
        # [B, S, N, C] — linear in batch, never quadratic in total tokens.
        # top-2 doubles routed token-slots, so capacity scales with k.
        capacity = max(1, int(self.capacity_factor * self.top_k * s / n))
        hidden = self.hidden_dim if self.hidden_dim is not None \
            else d * self.mlp_ratio

        # Router (f32 for a stable softmax regardless of compute dtype).
        sigmoid = self.router_score == "sigmoid"
        with jax.named_scope("moe_router"):
            if router_logits is None:
                router_logits = nn.Dense(
                    n, use_bias=not sigmoid, dtype=jnp.float32,
                    param_dtype=self.param_dtype, name="router"
                )(x.astype(jnp.float32))
            router_logits = router_logits.astype(jnp.float32)
            probs = nn.sigmoid(router_logits) if sigmoid \
                else nn.softmax(router_logits, axis=-1)
            # What the experts are CHOSEN by; the gates stay `probs`.
            select = probs + self.param(
                "select_bias", nn.initializers.zeros, (n,), jnp.float32
            ) if self.select_bias else probs

        # Expert-major parameters: dim 0 shards over the `expert` mesh axis.
        # batch_axis=(0,): the expert dim must not count toward fan-in, or
        # every expert initializes sqrt(n) too small.
        he = nn.initializers.he_normal(batch_axis=(0,))
        param = lambda name, init, *shape: self.param(
            name, init, shape, self.param_dtype).astype(self.dtype)
        w1 = param("w1", he, n, d, hidden)       # gate (gated) / in (gelu)
        w3 = param("w3", he, n, d, hidden) if gated else None       # up
        b1 = None if gated else param("b1", nn.initializers.zeros, n, hidden)
        w2 = param("w2", he, n, hidden, d)
        b2 = None if gated else param("b2", nn.initializers.zeros, n, d)

        if not train and self.eval_dropless:
            return self._plus_shared(
                self._serve(x, probs, select, w1, w3, b1, w2, b2), x, hidden)

        # k sequential choices (k is tiny and static — an unrolled Python
        # loop of MXU-friendly one-hot ops, no sorting network needed).
        # Choice j's queue positions start after the KEPT tokens of
        # choices < j (mesh-tf top-2 convention), so second choices never
        # displace first choices from an expert's capacity.
        remaining = select
        offset = jnp.zeros((b, n), probs.dtype)     # kept tokens per expert
        gates, dispatches = [], []
        first_choice_onehot = None
        for _ in range(self.top_k):
            raw_onehot = nn.one_hot(
                jnp.argmax(remaining, axis=-1), n)            # (B, S, N)
            gate = jnp.sum(probs * raw_onehot, axis=-1)       # (B, S)
            if first_choice_onehot is None:
                first_choice_onehot = raw_onehot
            # A biased score may be negative: a chosen expert leaves
            # the race at -inf, not at 0.
            remaining = jnp.where(raw_onehot > 0, -jnp.inf, remaining)
            position = (jnp.cumsum(raw_onehot, axis=1)
                        + offset[:, None, :]) * raw_onehot    # 1-based
            onehot = raw_onehot * (position <= capacity)
            offset = offset + jnp.sum(onehot, axis=1)
            pos_in_expert = (position - 1.0) * onehot         # 0-based
            dispatches.append(onehot[..., None] * nn.one_hot(
                pos_in_expert.sum(axis=-1).astype(jnp.int32), capacity
            )[..., None, :])                                  # (B, S, N, C)
            gates.append(gate)

        if self.top_k > 1 and self.normalize_gates:
            denom = sum(gates) + 1e-9
            gates = [g / denom for g in gates]
        if self.gate_scale != 1.0:
            gates = [g * self.gate_scale for g in gates]

        dispatch = sum(dispatches)
        # Dropped tokens have an all-zero dispatch row, so gating needs no
        # explicit kept mask.
        combine = sum(dsp * g[..., None, None]
                      for dsp, g in zip(dispatches, gates))

        # Load-balancing loss BEFORE capacity drop (Switch eq. 4-6; for
        # top-k the token fraction counts FIRST choices, per GShard):
        # n * sum_i( fraction_of_tokens_i * mean_router_prob_i ).
        frac = jnp.mean(first_choice_onehot, axis=(0, 1))
        mean_prob = jnp.mean(probs, axis=(0, 1))
        aux = self.aux_loss_weight * n * jnp.sum(frac * mean_prob)
        self.sow("losses", "moe_aux_loss", aux)
        # Measured capacity-drop observable: the fraction of routed
        # token-slots (top_k per token) whose expert queue was already
        # full, i.e. tokens this layer silently skipped. `offset` is the
        # kept count per (batch row, expert) after all k choices. Sown
        # into "metrics" (surfaced into the training logs by the
        # Trainer); exactly 0.0 on the dropless eval path.
        kept = jnp.sum(offset)
        drop_rate = 1.0 - kept / (b * s * self.top_k)
        self.sow("metrics", "moe_drop_rate", drop_rate)

        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)
        xc = x.astype(self.dtype)

        # Dispatch -> expert FFN -> combine: all MXU einsums, static shapes.
        expert_in = jnp.einsum("bsnc,bsd->bncd", dispatch, xc)
        if gated:
            gate_h = jnp.einsum("bncd,ndh->bnch", expert_in, w1)
            up_h = jnp.einsum("bncd,ndh->bnch", expert_in, w3)
            expert_out = jnp.einsum(
                "bnch,nhd->bncd",
                EXPERT_ACTS[self.expert_act](gate_h) * up_h, w2)
        else:
            h = nn.gelu(jnp.einsum("bncd,ndh->bnch", expert_in, w1)
                        + b1[:, None, :])
            expert_out = jnp.einsum("bnch,nhd->bncd", h, w2) + b2[:, None, :]
        return self._plus_shared(
            jnp.einsum("bsnc,bncd->bsd", combine, expert_out), xc, hidden)

    def _plus_shared(self, y, x, hidden: int):
        """``y`` plus what the shared experts make of ``x``: one gated
        MLP of ``shared_experts * hidden``, bias-free, no routing."""
        if not self.shared_experts:
            return y
        dense = lambda width, name: nn.Dense(
            width, use_bias=False, dtype=self.dtype,
            param_dtype=self.param_dtype, name=name)
        width = self.shared_experts * hidden
        with jax.named_scope("moe_shared"):
            x = x.astype(self.dtype)
            hid = EXPERT_ACTS[self.expert_act](
                dense(width, "shared_gate")(x)) * dense(width, "shared_up")(x)
            return y + dense(x.shape[-1], "shared_down")(hid)

    def _serve(self, x, probs, select, w1, w3, b1, w2, b2):
        """Eval and serving: dropless, token-major. The k choices and
        their gates are the training path's (the k largest of ``select``,
        ties to the lower expert; gates the chosen experts' ``probs``,
        renormalised over the kept ones — for softmax scores a softmax
        over the selected logits — then scaled); what differs is that no
        expert has a capacity."""
        b, s, d = x.shape
        n, k = self.num_experts, self.top_k
        with jax.named_scope("moe_router"):
            if select is probs:
                gates, index = jax.lax.top_k(probs.reshape(b * s, n), k)
            else:
                _, index = jax.lax.top_k(select.reshape(b * s, n), k)
                gates = jnp.take_along_axis(probs.reshape(b * s, n), index,
                                            axis=-1)
            if k > 1 and self.normalize_gates:
                gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-9)
            if self.gate_scale != 1.0:
                gates = gates * self.gate_scale
            if s > 1 and self.has_variable("cache", EXPERT_LOAD_KEY):
                # Serving statistics (module docstring of the keys):
                # pairs of the chunk's real tokens only. The tick's rows
                # are not counted: a parked slot's junk row would be.
                load = self.variable("cache", EXPERT_LOAD_KEY, lambda: None)
                valid = self.variable("cache", VALID_LEN_KEY,
                                      lambda: None).value
                real = (jnp.arange(b * s) % s < valid)[:, None, None]
                load.value = load.value + jnp.sum(
                    real & (index[:, :, None] == jnp.arange(n)),
                    axis=(0, 1), dtype=load.value.dtype)
        self.sow("intermediates", "expert_index", index.reshape(b, s, k))
        # What the training path sows, for validation logs: the Switch
        # loss over first choices, and a drop rate that is 0 by
        # construction.
        frac = jnp.mean(nn.one_hot(index[:, 0], n), axis=0)
        self.sow("losses", "moe_aux_loss", self.aux_loss_weight * n
                 * jnp.sum(frac * jnp.mean(probs, axis=(0, 1))))
        self.sow("metrics", "moe_drop_rate", jnp.zeros((), jnp.float32))
        gated = w3 is not None
        y = grouped_expert_ffn(
            x.reshape(b * s, d).astype(self.dtype), index, gates,
            w3 if gated else w1, w2, act=self.expert_act,
            w_gate=w1 if gated else None, b_in=b1, b_out=b2)
        return y.reshape(b, s, d)
