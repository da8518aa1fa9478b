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
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

# Cache-collection leaves a serving MoE layer accumulates into when the
# paged engine put them there (`Llama.paged_cache_extras`): per-expert
# counts of the routed pairs of prompt tokens, and the number of rows of
# the chunk that hold real tokens (stamped by `gpt.prefill_row_from`).
EXPERT_LOAD_KEY = "expert_load"
VALID_LEN_KEY = "valid_len"

EXPERT_ACTS = {"gelu": nn.gelu, "swiglu": nn.silu, "reglu": nn.relu}


def expert_tile(pairs: int, num_experts: int) -> int:
    """Rows per tile of :func:`grouped_expert_ffn`: about one expert's
    even share of the pairs, a power of two in [16, 512]. One tile reads
    its expert's weights once, so a tile should hold the expert's whole
    run where it can (a prefill chunk is bound by those reads until a
    run is some 256 rows long); 16 rows is the narrowest bf16 tile, which
    is what a decode step's one or two rows per expert pad to."""
    share = -(-pairs // num_experts)
    return min(512, max(16, 1 << (share - 1).bit_length()))


def grouped_expert_ffn(x, expert_index, gates, w_in, w_out, *,
                       act: str, w_gate=None, b_in=None, b_out=None,
                       tile: Optional[int] = None):
    """Dropless routed FFN, token-major: ``y[t] = sum_j gates[t, j] *
    FFN_{expert_index[t, j]}(x[t])``.

    ``x [T, d]``; ``expert_index``/``gates`` ``[T, k]``; expert-major
    weights ``w_in [N, d, h]``, ``w_out [N, h, d]``, for gated experts
    ``w_gate [N, d, h]`` (``act(x w_gate) * (x w_in)``), for the biased
    GELU expert ``b_in [N, h]`` / ``b_out [N, d]``.

    The ``P = T k`` pairs are sorted by expert (stable, so a token's
    pairs keep their order). Expert ``e``'s run of ``c_e`` rows is cut
    into ``ceil(c_e / tile)`` tiles; one loop iteration gathers a tile's
    rows of ``x``, multiplies them through expert ``e``'s weights and
    writes the result at the run's offset. A run's last tile reaches
    into the next expert's rows; that expert's own tile comes later in
    the loop and overwrites them, so no mask and no padded copy of ``x``
    is needed. Rows computed: ``sum_e ceil(c_e / tile) * tile <= P + N *
    (tile - 1)``, whatever the routing (every token to one expert
    included); experts with no pair cost nothing, not even a read of
    their weights.
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
    tile = expert_tile(pairs, n) if tile is None else int(tile)
    fn = EXPERT_ACTS[act]
    with jax.named_scope("moe_dispatch"):
        flat = expert_index.reshape(pairs).astype(jnp.int32)
        sorted_e, order = jax.lax.sort_key_val(
            flat, jnp.arange(pairs, dtype=jnp.int32))
        # Pair p of token p // k; `tile` rows of padding so a run's last
        # tile may read past the end.
        tok_sorted = jnp.concatenate(
            [order // k, jnp.zeros((tile,), jnp.int32)])
        bounds = jnp.searchsorted(
            sorted_e, jnp.arange(n + 1, dtype=jnp.int32),
            method="compare_all").astype(jnp.int32)
        starts, counts = bounds[:-1], bounds[1:] - bounds[:-1]
        tiles_per = (counts + tile - 1) // tile
        tile_cum = jnp.cumsum(tiles_per)
        max_tiles = -(-pairs // tile) + n
        ids = jnp.arange(max_tiles, dtype=jnp.int32)
        tile_e = jnp.minimum(
            jnp.searchsorted(tile_cum, ids, side="right",
                             method="compare_all"), n - 1
        ).astype(jnp.int32)
        tile_row0 = starts[tile_e] + (
            ids - (tile_cum[tile_e] - tiles_per[tile_e])) * tile

    def body(i, y_sorted):
        e, r0 = tile_e[i], tile_row0[i]
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
        y_sorted = jax.lax.fori_loop(
            0, tile_cum[-1], body, jnp.zeros((pairs + tile, d), x.dtype))
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
