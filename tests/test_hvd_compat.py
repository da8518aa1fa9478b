"""Horovod-shim tests: API parity with the reference's hvd usage
(`/root/reference/imagenet-resnet50-hvd.py`) on the fake 8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import pddl_tpu.compat.hvd as hvd
from pddl_tpu.data.synthetic import SyntheticImageClassification
from pddl_tpu.models.resnet import tiny_resnet
from pddl_tpu.parallel.mirrored import MirroredStrategy
from pddl_tpu.train.loop import Trainer
from pddl_tpu.train.state import get_learning_rate


@pytest.fixture(autouse=True)
def _init():
    hvd.init()


def test_world_shape(eight_devices):
    assert hvd.size() == 8           # replicas = devices (LR/batch parity)
    assert hvd.rank() == 0
    assert hvd.local_rank() == 0
    assert hvd.local_size() == 8
    assert hvd.num_data_shards() == 1   # single process feeds all replicas
    assert hvd.data_shard_index() == 0


def test_lr_scaling_matches_reference_rule():
    """`0.1 * hvd.size()` (imagenet-resnet50-hvd.py:99) on 8 replicas."""
    assert 0.1 * hvd.size() == pytest.approx(0.8)


def test_allreduce_and_broadcast_single_process_identity():
    x = {"a": np.arange(4.0), "b": 3.0}
    out = hvd.allreduce(x)
    np.testing.assert_array_equal(out["a"], x["a"])
    out = hvd.broadcast(x)
    np.testing.assert_array_equal(out["a"], x["a"])
    # allgather: single process concatenates to itself; scalars become
    # a [size]-vector (hvd semantics).
    out = hvd.allgather(x)
    np.testing.assert_array_equal(out["a"], x["a"])
    np.testing.assert_array_equal(out["b"], np.asarray([3.0]))
    # Any in-range root is accepted (real cross-process check lives in
    # tests/_multiworker_child.py); out-of-range raises.
    import pytest

    with pytest.raises(ValueError, match="root_rank"):
        hvd.broadcast(x, root_rank=1)  # only 1 process here


def test_distributed_optimizer_pmeans_gradients_in_shard_map(mesh8):
    """Explicit regime: per-replica different grads → identical (averaged)
    updates, the literal hvd ring-allreduce semantics."""
    tx = hvd.DistributedOptimizer("sgd", learning_rate=1.0, axis_name="data")
    params = {"w": jnp.zeros((8, 4))}  # leading dim sharded over data

    from jax.sharding import PartitionSpec as P

    @jax.jit
    def step(params, grads):
        def _inner(p, g):
            opt_state = tx.init(p)
            updates, _ = tx.update(g, opt_state, p)
            return optax.apply_updates(p, updates)

        return jax.shard_map(
            _inner, mesh=mesh8,
            in_specs=(P("data"), P("data")),
            out_specs=P("data"),
        )(params, grads)

    # grads: replica i sees constant value i → pmean = 3.5 everywhere
    grads = {"w": jnp.repeat(jnp.arange(8.0)[:, None], 4, axis=1)}
    new = step(params, grads)
    np.testing.assert_allclose(np.asarray(new["w"]), -3.5, rtol=1e-6)


def test_distributed_optimizer_default_regime_is_plain_optimizer():
    tx = hvd.DistributedOptimizer("adam", learning_rate=2e-3)
    params = {"w": jnp.ones(3)}
    state = tx.init(params)
    updates, _ = tx.update({"w": jnp.ones(3)}, state, params)
    assert jax.tree.leaves(updates)[0].shape == (3,)


def test_reference_hvd_script_workflow_end_to_end():
    """The hvd script's shape, recomposed: scaled LR, DistributedOptimizer,
    warmup + broadcast + metric-average callbacks, rank-0 gating."""
    base_lr = 0.01
    scaled = base_lr * hvd.size() / 8  # keep it small for the tiny task
    trainer = Trainer(
        tiny_resnet(num_classes=10),
        optimizer=hvd.DistributedOptimizer("adam", learning_rate=scaled),
        strategy=MirroredStrategy(),
        seed=11,
    )
    cbs = [
        hvd.callbacks.BroadcastGlobalVariablesCallback(0),
        hvd.callbacks.MetricAverageCallback(),
        hvd.callbacks.LearningRateWarmupCallback(warmup_epochs=2),
    ]
    ds = SyntheticImageClassification(batch_size=16, image_size=32,
                                      num_classes=10, seed=4)
    hist = trainer.fit(ds, epochs=3, steps_per_epoch=4, callbacks=cbs,
                       verbose=0)
    assert hist.history["loss"][-1] < hist.history["loss"][0]
    # warmup has reached the target LR
    assert get_learning_rate(trainer.state) == pytest.approx(scaled, rel=1e-5)
    # rank-0 gating helper used for save/logging (:117-129)
    assert hvd.rank() == 0
