"""Device-mesh construction.

TPU-native replacement for the reference's cluster/strategy device handling:

- ``tf.distribute.MirroredStrategy`` device enumeration
  (``/root/reference/imagenet-resnet50-mirror.py:21``) → a single-host mesh
  over ``jax.local_devices()``.
- ``SlurmClusterResolver`` + ``MultiWorkerMirroredStrategy``
  (``/root/reference/imagenet-resnet50-multiworkers.py:16-25``) → a global
  mesh over ``jax.devices()`` after ``jax.distributed.initialize`` (see
  :mod:`pddl_tpu.core.dist`).
- Horovod's rank/size world (``/root/reference/imagenet-resnet50-hvd.py:16``)
  → the same mesh; ranks are positions along the ``data`` axis.

Axis conventions (all optional except ``data``):

========  =============================================================
``data``  data parallelism (batch sharding, gradient all-reduce via ICI)
``model`` tensor parallelism (Megatron weight sharding,
          :mod:`pddl_tpu.parallel.tensor_parallel`)
``seq``   sequence/context parallelism (ring attention, long context)
``expert`` expert parallelism for MoE layers (:mod:`pddl_tpu.ops.moe`)
``stage`` pipeline parallelism (GPipe microbatch pipeline,
          :mod:`pddl_tpu.ops.pipeline`)
========  =============================================================

The mesh is the *only* place device topology appears; everything above it
(strategies, trainer, models) speaks named axes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh


# Canonical axis names, in canonical order.
DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
EXPERT_AXIS = "expert"
STAGE_AXIS = "stage"  # pipeline parallelism (GPipe microbatch pipeline)
CANONICAL_AXES = (DATA_AXIS, MODEL_AXIS, SEQ_AXIS, EXPERT_AXIS, STAGE_AXIS)


def local_device_count() -> int:
    """Number of accelerator devices attached to this process."""
    return jax.local_device_count()


def global_device_count() -> int:
    """Number of devices across all processes (the "world size" analogue)."""
    return jax.device_count()


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape.

    Any axis may be ``-1`` meaning "all remaining devices". Axes of size 1
    are kept in the mesh (they cost nothing and keep sharding rules uniform
    across strategies).

    Example::

        MeshConfig(data=-1)                  # pure data parallel
        MeshConfig(data=-1, model=2)         # DP x TP
        MeshConfig(data=2, seq=4)            # DP x sequence parallel
    """

    data: int = -1
    model: int = 1
    seq: int = 1
    expert: int = 1
    stage: int = 1
    # Restrict to this process's local devices (mirrored strategy) instead of
    # the global device set (multi-worker).
    local_only: bool = False

    def axis_sizes(self, n_devices: int) -> dict[str, int]:
        sizes = {
            DATA_AXIS: self.data,
            MODEL_AXIS: self.model,
            SEQ_AXIS: self.seq,
            EXPERT_AXIS: self.expert,
            STAGE_AXIS: self.stage,
        }
        for name, s in sizes.items():
            if s == 0 or s < -1:
                raise ValueError(f"mesh axis {name!r} size must be >= 1 or -1, got {s}")
        wildcard = [name for name, s in sizes.items() if s == -1]
        if len(wildcard) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wildcard}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wildcard:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wildcard[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh shape {sizes} needs {fixed} devices, have {n_devices}"
            )
        return sizes


def build_mesh(
    config: MeshConfig | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
    **axis_sizes: int,
) -> Mesh:
    """Build a :class:`jax.sharding.Mesh` from a :class:`MeshConfig`.

    ``build_mesh()`` with no arguments gives the canonical data-parallel mesh
    over all devices — the TPU-native analogue of constructing a
    ``MirroredStrategy``/``MultiWorkerMirroredStrategy`` in the reference.

    Axis sizes can also be passed directly: ``build_mesh(data=4, model=2)``.
    """
    if config is None:
        config = MeshConfig(**axis_sizes) if axis_sizes else MeshConfig()
    elif axis_sizes:
        raise ValueError("pass either a MeshConfig or axis sizes, not both")

    if devices is None:
        devices = jax.local_devices() if config.local_only else jax.devices()
    devices = list(devices)
    sizes = config.axis_sizes(len(devices))
    shape = tuple(sizes[a] for a in CANONICAL_AXES)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, CANONICAL_AXES)


def slice_groups(
    devices: Sequence[jax.Device],
    num_slices: int | None = None,
) -> list[list[jax.Device]]:
    """Group devices by TPU slice (the ICI domain).

    Slice membership comes from ``device.slice_index`` (multi-slice TPU
    jobs); if absent, from ``process_index`` (multi-host CPU/GPU jobs);
    if neither distinguishes anything, ``num_slices`` splits the device
    list evenly (how tests fake a multi-slice topology on one host).
    """
    devices = list(devices)
    keys = {getattr(d, "slice_index", None) for d in devices}
    if keys != {None}:
        # Heterogeneous sets can expose slice_index on only some devices
        # (int and None mixed); -1 keeps the group keys sortable instead
        # of sorted() raising TypeError on None < int.
        def key(d):
            si = getattr(d, "slice_index", None)
            return -1 if si is None else si
    elif len({d.process_index for d in devices}) > 1:
        key = lambda d: d.process_index  # noqa: E731
    else:
        if not num_slices:
            return [devices]
        if len(devices) % num_slices != 0:
            raise ValueError(
                f"{len(devices)} devices not divisible into {num_slices} slices"
            )
        per = len(devices) // num_slices
        return [devices[i * per:(i + 1) * per] for i in range(num_slices)]
    groups: dict = {}
    for d in devices:
        groups.setdefault(key(d), []).append(d)
    out = [groups[k] for k in sorted(groups)]
    if num_slices and len(out) != num_slices:
        raise ValueError(
            f"detected {len(out)} slices but num_slices={num_slices}"
        )
    if len({len(g) for g in out}) != 1:
        raise ValueError(
            f"uneven slices: {[len(g) for g in out]} devices per slice"
        )
    return out


def build_hybrid_mesh(
    config: MeshConfig | None = None,
    *,
    dcn_axis: str = DATA_AXIS,
    devices: Sequence[jax.Device] | None = None,
    num_slices: int | None = None,
    **axis_sizes: int,
) -> Mesh:
    """Build a multi-slice mesh: one axis spans slices over DCN, the rest
    stay inside a slice on ICI.

    The returned object is an ordinary :class:`Mesh` — only the device
    *placement* differs from :func:`build_mesh`: positions along
    ``dcn_axis`` are slice-major (all of slice 0, then slice 1, …), and
    every other axis is laid out within a single slice, so its
    collectives (tensor-parallel all-reduces, ring-attention ppermutes,
    pipeline hops) never cross the slow DCN link. The ``dcn_axis``
    gradient all-reduce lowers to the standard hierarchical pattern:
    reduce over ICI inside each slice, then once over DCN between
    slices. This is the TPU analogue of the reference's NCCL
    intra-node ring + cross-host collective split
    (``imagenet-resnet50-multiworkers.py:19-25``).

    ``num_slices`` is only needed when the devices carry no slice/process
    identity (e.g. the fake CPU mesh in tests).
    """
    if config is None:
        config = MeshConfig(**axis_sizes) if axis_sizes else MeshConfig()
    elif axis_sizes:
        raise ValueError("pass either a MeshConfig or axis sizes, not both")
    if dcn_axis not in CANONICAL_AXES:
        raise ValueError(f"unknown dcn_axis {dcn_axis!r}")

    if devices is None:
        devices = jax.devices()
    groups = slice_groups(devices, num_slices)
    n_slices = len(groups)
    if n_slices == 1:
        return build_mesh(config, devices=devices)

    sizes = config.axis_sizes(len(list(devices)))
    if sizes[dcn_axis] % n_slices != 0:
        raise ValueError(
            f"{dcn_axis}-axis size {sizes[dcn_axis]} not divisible by "
            f"{n_slices} slices"
        )
    per_slice = dict(sizes)
    per_slice[dcn_axis] = sizes[dcn_axis] // n_slices
    per_slice_devices = math.prod(per_slice.values())
    if per_slice_devices != len(groups[0]):
        raise ValueError(
            f"per-slice mesh {per_slice} needs {per_slice_devices} devices "
            f"but each slice has {len(groups[0])} — non-DCN axes must fit "
            "inside one slice"
        )

    # Each slice reshapes to the canonical order with its share of the DCN
    # axis; stacking slice-major along that axis makes position//per_slice
    # the slice id.
    shape = tuple(per_slice[a] for a in CANONICAL_AXES)
    dcn_pos = CANONICAL_AXES.index(dcn_axis)
    blocks = [np.asarray(g).reshape(shape) for g in groups]
    dev_array = np.concatenate(blocks, axis=dcn_pos)
    return Mesh(dev_array, CANONICAL_AXES)


def mesh_num_replicas(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    """Replica count along a mesh axis — the ``strategy.num_replicas_in_sync``
    analogue (reference scales batch by it: ``imagenet-resnet50-mirror.py:54``).
    """
    return mesh.shape[axis]


def validate_divisible(batch_size: int, mesh: Mesh, axis: str = DATA_AXIS) -> None:
    n = mesh_num_replicas(mesh, axis)
    if batch_size % n != 0:
        raise ValueError(
            f"global batch {batch_size} not divisible by {axis}-axis size {n}"
        )


def describe(mesh: Mesh) -> str:
    """Human-readable one-liner for logs."""
    axes = ", ".join(f"{a}={s}" for a, s in mesh.shape.items() if s > 1) or "1 device"
    plat = mesh.devices.flat[0].platform
    return f"Mesh({axes}) on {mesh.devices.size} {plat} device(s)"
