"""Mesh construction + collectives on the fake 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pddl_tpu.core import collectives
from pddl_tpu.core.mesh import (
    MeshConfig,
    build_mesh,
    mesh_num_replicas,
    validate_divisible,
)


def test_mesh_default_all_data(eight_devices):
    mesh = build_mesh()
    assert mesh.shape["data"] == 8
    assert mesh.shape["model"] == 1
    assert mesh_num_replicas(mesh) == 8


def test_mesh_wildcard_and_fixed(eight_devices):
    mesh = build_mesh(MeshConfig(data=-1, model=2))
    assert mesh.shape["data"] == 4 and mesh.shape["model"] == 2


def test_mesh_bad_shapes(eight_devices):
    with pytest.raises(ValueError):
        build_mesh(MeshConfig(data=3))  # 8 % 3 != 0
    with pytest.raises(ValueError):
        MeshConfig(data=-1, model=-1).axis_sizes(8)


def test_validate_divisible(mesh8):
    validate_divisible(32, mesh8)
    with pytest.raises(ValueError):
        validate_divisible(31, mesh8)


def test_psum_pmean_over_mesh(mesh8):
    def f(x):
        return collectives.psum(x, "data"), collectives.pmean(x, "data")

    g = jax.shard_map(f, mesh=mesh8, in_specs=P("data"), out_specs=P())
    s, m = g(jnp.arange(8.0))
    assert s[0] == 28.0
    assert m[0] == 3.5


def test_broadcast_from_root(mesh8):
    def f(x):
        return collectives.broadcast(x, "data", root=3)

    g = jax.shard_map(f, mesh=mesh8, in_specs=P("data"), out_specs=P("data"))
    out = g(jnp.arange(8.0))
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 3.0))


def test_ppermute_ring(mesh8):
    def f(x):
        return collectives.ppermute_ring(x, "data", shift=1)

    g = jax.shard_map(f, mesh=mesh8, in_specs=P("data"), out_specs=P("data"))
    out = np.asarray(g(jnp.arange(8.0)))
    # member i sends to i+1: position j holds value j-1 (mod 8)
    np.testing.assert_array_equal(out, np.roll(np.arange(8.0), 1))


def test_reduce_scatter(mesh8):
    def f(x):
        return collectives.reduce_scatter(x, "data")

    # Each member holds a length-8 vector of ones; psum_scatter sums across
    # members then scatters: each member ends with 8/8=1 element == 8.0.
    g = jax.shard_map(f, mesh=mesh8, in_specs=P(None), out_specs=P("data"))
    out = np.asarray(g(jnp.ones(8)))
    np.testing.assert_array_equal(out, np.full(8, 8.0))
