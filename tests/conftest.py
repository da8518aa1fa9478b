"""Test harness: fake 8-device CPU mesh.

The reference "tests" multi-node topologies with an in-process gRPC cluster
(`/root/reference/imagenet-resnet50-ps.py:31-65`) and CUDA-hiding env vars
(`:29`). The JAX equivalent (SURVEY.md §4): force the host platform and split
it into 8 virtual devices so every sharding/collective path compiles and runs
on one CPU.

Must run before any JAX backend initialization: ``JAX_PLATFORMS`` and
``XLA_FLAGS`` are read when the backend starts.
"""

import os
import sys

# The scripts at the repo root (chip_smoke.py, bench.py) are importable
# from the tests whatever directory pytest was started in.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# The suite's wall-clock is dominated by XLA:CPU compiles of the sharded
# train steps. Persist them (shared with the driver's multichip gate):
# a warm cache cuts a full run by minutes. Where the cache goes is
# `pddl_tpu/utils/compile_cache.py`'s rule.
from pddl_tpu.utils.compile_cache import enable_persistent_compile_cache  # noqa: E402

enable_persistent_compile_cache()

import pytest  # noqa: E402


def native_build_error(tfrecord: bool = False) -> str:
    """Build the native library if missing; '' on success, else the error.

    Shared by the native-loader and TFRecord test modules so a missing
    toolchain produces one self-explanatory skip reason. Only TFRecord
    tests (``tfrecord=True``) additionally require the ``pddl_tfr_*``
    symbols, so a prebuilt pre-TFRecord library still runs the
    packed-loader tests.
    """
    try:
        from pddl_tpu.data.native_loader import build_native

        build_native()  # no-op when the .so is already fresh
        if tfrecord:
            from pddl_tpu.data.tfrecord import _tfr_lib

            _tfr_lib()  # raises if a stale pre-TFRecord .so got loaded
        return ""
    except Exception as e:  # noqa: BLE001 - any failure means "skip"
        return str(e)


def ref_greedy(model, variables, prompt, n_new):
    """The serving test suite's oracle: one-shot batch-1 ``generate()``
    over the same params. Every engine/fleet path (cold admit, prefix
    hit, replay, migration) is pinned token-exact against THIS — one
    copy, so every serving test file pins the same reference."""
    import jax.numpy as jnp
    import numpy as np

    from pddl_tpu.models.gpt import generate

    out = generate(model, variables,
                   jnp.asarray(prompt, jnp.int32)[None], n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


class FakeClock:
    """Deterministic ``clock=`` stand-in: time advances only when a
    test sets ``.now`` (deadlines, backoff, breaker windows)."""

    def __init__(self, now=0.0):
        self.now = now

    def __call__(self):
        return self.now


def refcount_baseline(prefix) -> bool:
    """(all refs zero, accounting exact) over the whole radix tree."""
    stack = [prefix._root]
    while stack:
        node = stack.pop()
        stack.extend(node.children.values())
        if node is not prefix._root and node.ref != 0:
            return False
    return (prefix.blocks_live + prefix.blocks_free
            == prefix.num_blocks - 1)


def assert_pool_idle(eng, cached_blocks=None):
    """No live stream of a ``ServeEngine`` holds anything: every table
    row all-scratch, no private block owned, the sharing gauges at
    their idle values, every radix refcount zero, and every block
    either cached or (once) on the free list."""
    assert eng.live_slots == 0 and eng._slice is None
    assert not eng._tables.any()
    assert all(not ids for ids in eng._private)
    assert all(node is None for node in eng._slot_nodes)
    assert eng.blocks_shared == 0 and eng.block_table_fill == 0.0
    idx = eng._prefix
    assert refcount_baseline(idx), "leaked pin or lost block"
    assert len(set(idx._free)) == idx.blocks_free, "block freed twice"
    if cached_blocks is not None:
        assert idx.blocks_live == cached_blocks


@pytest.fixture()
def pin_zero_recompiles():
    """THE fixed-shape contract as a reusable fixture: every resident
    compiled program of a registered object has exactly ONE executable
    at registration AND still exactly one when the test ends — whatever
    mixed workload (or fault-recovery path) ran in between compiled
    nothing new.

    Works for anything exposing ``compile_counts()``: a ``ServeEngine``
    (warmed first — it exposes ``warmup()``), a ``Trainer`` (register
    it after its first fit, when both programs exist), or a
    ``FleetRouter``, whose aggregated counts are keyed
    ``r<replica>/<site>`` — registering a fleet pins zero recompiles
    PER REPLICA, which is how the fleet chaos matrix asserts that no
    surviving replica recompiled anything across a migration::

        eng = pin_zero_recompiles(ServeEngine(model, variables, ...))
        trainer.fit(...); pin_zero_recompiles(trainer)
        fleet = pin_zero_recompiles(FleetRouter([...]))

    Every serve-layer test that builds an engine through it gets the
    zero-recompile pin for free (`test_serve_engine.py`,
    `test_prefix_cache.py`); the training chaos matrix pins recovery
    transitions the same way (`test_train_faults.py`), the fleet
    matrix per surviving replica (`test_serve_fleet.py`).
    """
    engines = []

    def register(engine):
        if hasattr(engine, "warmup"):
            engine.warmup()
        counts = engine.compile_counts()
        assert counts and all(v == 1 for v in counts.values()), \
            f"program(s) compiled more than once at registration: {counts}"
        engines.append(engine)
        return engine

    yield register
    for engine in engines:
        counts = engine.compile_counts()
        assert all(v == 1 for v in counts.values()), \
            f"workload recompiled resident program(s): {counts}"


@pytest.fixture(scope="session")
def eight_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8():
    from pddl_tpu.core.mesh import build_mesh, MeshConfig

    return build_mesh(MeshConfig(data=8))


@pytest.fixture()
def mesh4x2():
    from pddl_tpu.core.mesh import build_mesh, MeshConfig

    return build_mesh(MeshConfig(data=4, model=2))
