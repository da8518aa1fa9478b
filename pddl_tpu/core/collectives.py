"""Named-axis collectives: the NCCL/MPI/Horovod-core replacement.

Every cross-device primitive the reference obtains from native libraries —
NCCL all-reduce inside ``MirroredStrategy`` / ``MultiWorkerMirroredStrategy``
(``/root/reference/imagenet-resnet50-mirror.py:21``,
``imagenet-resnet50-multiworkers.py:19-21``), Horovod's ring all-reduce and
broadcast (``imagenet-resnet50-hvd.py:101,111``) — maps here to an XLA
collective compiled over ICI/DCN. These helpers are usable in two regimes:

1. **inside ``jax.shard_map``** (per-shard view): the functions below call
   ``lax.psum`` etc. with a mesh axis name.
2. **implicit, under ``jit`` with shardings** (global view): you usually do
   not need explicit collectives at all — a mean over a ``data``-sharded
   batch dimension *is* the gradient all-reduce; XLA inserts the transfer.
   The trainer (``pddl_tpu.train.loop``) uses this regime.

Regime 2 is the idiomatic TPU path; regime 1 exists for the Horovod-compat
shim, ring attention, and anywhere explicit per-replica code is clearer.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any


def psum(tree: PyTree, axis_name: str | Sequence[str]) -> PyTree:
    """All-reduce-sum a pytree over a named mesh axis (NCCL allreduce)."""
    return jax.tree.map(lambda x: lax.psum(x, axis_name), tree)


def pmean(tree: PyTree, axis_name: str | Sequence[str]) -> PyTree:
    """All-reduce-mean — gradient averaging (``hvd.DistributedOptimizer``,
    ``/root/reference/imagenet-resnet50-hvd.py:101``) and metric averaging
    (``MetricAverageCallback``, ``:112-113``)."""
    return jax.tree.map(lambda x: lax.pmean(x, axis_name), tree)


def pmax(tree: PyTree, axis_name: str | Sequence[str]) -> PyTree:
    return jax.tree.map(lambda x: lax.pmax(x, axis_name), tree)


def pmin(tree: PyTree, axis_name: str | Sequence[str]) -> PyTree:
    return jax.tree.map(lambda x: lax.pmin(x, axis_name), tree)


def broadcast(tree: PyTree, axis_name: str, root: int = 0) -> PyTree:
    """Broadcast ``root``'s values to every member of the axis.

    The ``hvd.callbacks.BroadcastGlobalVariablesCallback(0)`` analogue
    (``/root/reference/imagenet-resnet50-hvd.py:111``): used to force
    bitwise-identical initial weights across replicas. Under SPMD with
    replicated params this is a no-op by construction; the helper exists for
    per-replica (shard_map) code paths and for restoring from per-host state.
    """

    def _bcast(x: jnp.ndarray) -> jnp.ndarray:
        # Select root's shard on every member: gather along the axis, index.
        gathered = lax.all_gather(x, axis_name)
        return gathered[root]

    return jax.tree.map(_bcast, tree)


def all_gather(tree: PyTree, axis_name: str, *, axis: int = 0, tiled: bool = False) -> PyTree:
    """Gather per-replica values along a new (or tiled) leading axis."""
    return jax.tree.map(
        lambda x: lax.all_gather(x, axis_name, axis=axis, tiled=tiled), tree
    )


def reduce_scatter(tree: PyTree, axis_name: str, *, scatter_axis: int = 0) -> PyTree:
    """Sum-reduce across the axis, scattering shards — ZeRO-style gradient
    sharding; rides ICI at half the cost of allreduce when state is sharded."""
    return jax.tree.map(
        lambda x: lax.psum_scatter(x, axis_name, scatter_dimension=scatter_axis, tiled=True),
        tree,
    )


def ppermute_ring(x: jnp.ndarray, axis_name: str, *, shift: int = 1) -> jnp.ndarray:
    """Rotate shards around the ring: member i sends to (i+shift) % n.

    The building block for ring attention (:mod:`pddl_tpu.ops.ring_attention`)
    — neighbor exchange rides ICI at full bisection bandwidth.
    """
    n = axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str) -> jnp.ndarray:
    """This member's coordinate along the axis (``hvd.rank()`` analogue in
    traced code)."""
    return lax.axis_index(axis_name)


def axis_size(axis_name: str) -> int:
    """Static size of a named axis (``hvd.size()`` analogue in traced code)."""
    return lax.axis_size(axis_name)


def pcast_varying(x: jnp.ndarray, axis_name) -> jnp.ndarray:
    """Mark ``x`` device-varying along ``axis_name`` for the
    varying-manual-axes checker."""
    return lax.pcast(x, axis_name, to="varying")
