"""Ask the chip's compiler, without the chip.

libtpu is installed here and compiles for a device that is DESCRIBED and
not attached (``jax.experimental.topologies``), so the Mosaic kernels of
the main path are compiled at real widths for a TPU v5e on every tier-1
run. Interpret mode cannot see what this sees: ``paged_decode_attention_
kernel`` passed every interpret-mode test while the v5e compiler refused
it at every 64-wide head shape the repo ships (an in-kernel reshape of a
64-lane minor dim). A compile that passes is not a chip run — results and
times come from ``chip_smoke.py`` and ``tests_tpu/`` — but a compile that
FAILS here would fail there, and costs no chip time.

Nothing runs, so arguments are shapes; and the kernels are called with
``interpret=False`` directly, because code that asks
``jax.default_backend()`` still sees the CPU here.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from pddl_tpu.ops.attention import (  # noqa: E402
    flash_attention,
    paged_decode_attention_kernel,
)


@pytest.fixture(scope="module")
def v5e_chip():
    """One described v5e device as a sharding, with the persistent compile
    cache off around the module: an entry written for a described device
    cannot be read back without one, and warns on every later run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it cannot describe v5e
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _compile_with_kernel(fn, *shapes, kernel=True):
    """Compile for the described chip; the Mosaic kernel must be in it
    (or, ``kernel=False``, the documented jnp fallback must be)."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert ("tpu_custom_call" in text) == kernel


@pytest.mark.parametrize("heads,kv_heads,head_dim,block_size", [
    (12, 12, 64, 8),    # GPT-small, the engine's default block size
    (12, 12, 64, 16),
    (12, 4, 64, 8),     # Llama-small (GQA)
    (12, 4, 64, 16),
    (16, 16, 128, 8),   # the width that always compiled
])
def test_paged_decode_kernel_compiles_for_v5e(v5e_chip, heads, kv_heads,
                                              head_dim, block_size):
    """Eight slots over a 1024-token context, bf16, as the engine's paged
    tick calls it."""
    slots, table = 8, 1024 // block_size

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    pool = sds((slots * table + 1, kv_heads, block_size, head_dim),
               jnp.bfloat16)
    _compile_with_kernel(
        lambda q, k, v, t, i: paged_decode_attention_kernel(
            q, k, v, t, i, interpret=False),
        sds((slots, heads, 1, head_dim), jnp.bfloat16), pool, pool,
        sds((slots, table), jnp.int32), sds((slots,), jnp.int32))


@pytest.mark.parametrize("heads,kv_heads", [(12, 12), (12, 4)])
def test_flash_forward_and_fused_backward_compile_for_v5e(v5e_chip, heads,
                                                          kv_heads):
    """B8 S2048 D64 causal, bf16: the GPT-small / Llama-small training
    shape, forward and the fused single-sweep backward together."""
    def sds(h):
        return jax.ShapeDtypeStruct((8, h, 2048, 64), jnp.bfloat16,
                                    sharding=v5e_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    _compile_with_kernel(jax.grad(loss, argnums=(0, 1, 2)),
                         sds(heads), sds(kv_heads), sds(kv_heads))


@pytest.mark.parametrize("seq,kernel", [
    (1000, True),   # largest divisor <= 512 is 500: not a sublane multiple
    (543, False),   # 3 x 181: no legal tiling, the reference serves it
])
def test_flash_lengths_off_the_block_grid_compile_for_v5e(v5e_chip, seq,
                                                          kernel):
    """A block the interpreter runs happily (500 or 181 rows) is refused
    by the TPU lowering unless it is a multiple of 8 or the whole
    dimension; the block choice must know that, and fall back to the
    reference only where no legal block exists."""
    shape = jax.ShapeDtypeStruct((1, 12, seq, 64), jnp.bfloat16,
                                 sharding=v5e_chip)
    _compile_with_kernel(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False),
        shape, shape, shape, kernel=kernel)
