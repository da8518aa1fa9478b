"""GPipe pipeline parallelism over the ``stage`` mesh axis.

Beyond-parity capability (the reference has no pipeline parallelism —
SURVEY.md §2c). TPU-native formulation: instead of an RPC/stream scheduler
(the GPU-framework shape of PP), the whole pipeline is ONE compiled SPMD
program —

- stage parameters are stacked on a leading ``[n_stages, ...]`` dim and
  sharded over the ``stage`` mesh axis (one stage per mesh position);
- the batch is split into microbatches; a ``lax.scan`` over
  ``n_micro + n_stages - 1`` ticks runs every stage every tick (SPMD), and
  activations hop to the next stage via ``lax.ppermute`` — neighbor
  exchange on the ICI ring;
- stage 0 injects a fresh microbatch each tick, the last stage collects
  finished microbatches; the classic GPipe bubble is the
  ``(n_stages - 1) / (n_micro + n_stages - 1)`` idle fraction.

Because the schedule is ``scan`` + ``ppermute`` (both differentiable), the
backward pass IS the reverse pipeline — ``jax.grad`` derives it; no
hand-written 1F1B schedule, no framework scheduler thread.

Composes with data parallelism: the batch dim stays sharded over ``data``
inside the same ``shard_map``.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from pddl_tpu.core.collectives import pcast_varying
from pddl_tpu.core.mesh import DATA_AXIS, STAGE_AXIS

PyTree = Any


def gpipe_apply(
    stage_params: PyTree,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    stage_fn: Callable[[PyTree, jnp.ndarray], jnp.ndarray],
    n_microbatches: int,
    stage_axis: str = STAGE_AXIS,
    data_axis: str = DATA_AXIS,
    check_vma: bool = True,
    remat_stages: bool = False,
) -> jnp.ndarray:
    """Run ``x`` through the stage pipeline; returns same-shape activations.

    Args:
      stage_params: pytree whose leaves have leading dim ``n_stages``
        (sharded over ``stage_axis`` by the strategy).
      x: ``[batch, ...]`` activations (sharded over ``data_axis``).
      stage_fn: pure ``(params_slice, microbatch) -> microbatch`` for ONE
        stage (e.g. a flax ``module.apply`` closure). Applied under vmap-
        free SPMD — one call per device per tick.
      n_microbatches: microbatch count M; ``batch % M == 0``. Larger M
        shrinks the pipeline bubble (``(S-1)/(M+S-1)``) but each microbatch
        must stay big enough to keep the MXU busy.
      remat_stages: rematerialize each stage call in the backward. The
        AD-derived backward saves one stage-internal activation set per
        tick: ``M + S - 1`` ticks of ``B/M``-row microbatches, i.e.
        ``temp ≈ c·B·(M+S-1)/M`` at fixed global batch (measured law —
        larger M SHRINKS the envelope toward the ``c·B`` floor while
        also shrinking the bubble). What caps model size is the floor's
        constant ``c`` — every block-internal activation of the global
        batch — and remat cuts it ~5-10x by keeping only tick-boundary
        microbatches and recomputing stage internals in the backward
        (measured: benchmarks/gpipe_memory_bench.py,
        docs/ARCHITECTURE.md §7d; exactness: tests/test_pipeline.py).
    """
    if remat_stages:
        stage_fn = jax.checkpoint(stage_fn)
    n_stages = mesh.shape[stage_axis]
    batch = x.shape[0]
    dp = mesh.shape[data_axis]
    if batch % dp:
        raise ValueError(
            f"batch {batch} not divisible by the {data_axis} axis size {dp}")
    if (batch // dp) % n_microbatches:
        raise ValueError(
            f"per-data-shard batch {batch // dp} not divisible by "
            f"{n_microbatches} microbatches"
        )
    if n_stages == 1:  # degenerate: no pipeline, just apply the one stage
        return stage_fn(jax.tree.map(lambda p: p[0], stage_params), x)

    def pipelined(params, xs):
        # params leaves: [1, ...] (this device's stage); xs: local batch shard.
        params = jax.tree.map(lambda p: jnp.squeeze(p, 0), params)
        sid = lax.axis_index(stage_axis)
        last = n_stages - 1
        xs_mb = xs.reshape((n_microbatches, -1) + xs.shape[1:])  # (M, mb/dp, ...)

        def probe(h):
            return stage_fn(params, h)

        zero = jnp.zeros_like(xs_mb[0])
        out_shape = jax.eval_shape(probe, zero)
        outs0 = jnp.zeros((n_microbatches,) + out_shape.shape, out_shape.dtype)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry
            # Stage 0 injects microbatch t (clamped once the feed runs dry).
            inj = lax.dynamic_index_in_dim(
                xs_mb, jnp.minimum(t, n_microbatches - 1), 0, keepdims=False
            ).astype(buf.dtype)
            buf = jnp.where(sid == 0, inj, buf)
            y = stage_fn(params, buf)
            # Last stage collects microbatch t-(S-1) once it exists.
            idx = t - last
            updated = lax.dynamic_update_index_in_dim(
                outs, y.astype(outs.dtype), jnp.maximum(idx, 0), 0
            )
            outs = jnp.where((sid == last) & (idx >= 0), updated, outs)
            # Activations hop one stage forward around the ICI ring.
            buf = lax.ppermute(y, stage_axis, perm)
            return (buf, outs), None

        # The carries are logically per-device (stage-varying) even though
        # their initial values are constants — cast them to varying so the
        # scan carry type is stable (see also ring_attention).
        buf_init = pcast_varying(zero, (stage_axis,))
        outs_init = pcast_varying(outs0, (data_axis, stage_axis))
        (_, outs), _ = lax.scan(
            tick, (buf_init, outs_init), jnp.arange(n_microbatches + n_stages - 1)
        )
        # Only the last stage holds real outputs; psum broadcasts them to
        # every stage position (making the result stage-invariant).
        outs = lax.psum(jnp.where(sid == last, outs, 0.0), stage_axis)
        return outs.reshape((-1,) + outs.shape[2:])

    param_specs = jax.tree.map(
        lambda p: P(stage_axis, *([None] * (p.ndim - 1))), stage_params
    )
    return jax.shard_map(
        pipelined,
        mesh=mesh,
        in_specs=(param_specs, P(data_axis, *([None] * (x.ndim - 1)))),
        out_specs=P(data_axis, *([None] * (x.ndim - 1))),
        # check_vma=False only for stage_fns whose pallas interpret mode
        # can't declare varying axes (CPU test path); Mosaic on TPU can.
        check_vma=check_vma,
    )(stage_params, x)
