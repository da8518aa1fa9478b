"""Rehearsal of ``chip_smoke.py`` without the chip.

The script itself refuses everything but a TPU and has no option to run
elsewhere; what can break unnoticed between chip runs is its PHASES — an
API they call gets renamed, a check stops matching what the engine
returns. So the phases run here at tiny sizes on the suite's virtual CPU
devices (the four-chip phase on four of them). All the steering — sizes,
devices, skipping the platform check — lives in this file.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import chip_smoke  # at the repo root; conftest.py puts that on sys.path

_ROOT = os.path.dirname(os.path.abspath(chip_smoke.__file__))


def test_script_refuses_the_cpu():
    """Run as the driver runs it, but here: non-zero exit, no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for argv in ([], ["--four-chips"]):
        proc = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), *argv],
            env=env, cwd=_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert "needs a TPU" in proc.stderr


def test_memory_report_fails_where_the_backend_reports_nothing():
    """The CPU backend has no memory stats: the smoke raises, where
    ``device_memory_stats`` would log -1."""
    with pytest.raises(RuntimeError, match="bytes_limit"):
        chip_smoke.memory_report(jax.devices()[:1])


def test_train_phase_rehearsal():
    facts = chip_smoke.train_phase(
        steps=3, cli=("--model", "tiny_resnet", "--image-size", "32",
                      "--batch", "4", "--num-classes", "10"))
    assert facts["steps"] == 3 and facts["model"] == "tiny_resnet"
    assert facts["compile_counts"] == {"train_step": 1, "eval_step": 1}


def test_serve_phase_rehearsal():
    """The engine, all checks; on CPU the tick must NOT claim the
    Mosaic kernel (the phase asserts kernel-present == platform-is-tpu)."""
    from pddl_tpu.models.gpt import tiny_gpt

    facts = chip_smoke.serve_phase(
        model=tiny_gpt(vocab_size=64, max_len=64), prompt_lens=(5, 9, 17, 9),
        new_tokens=6, gap_tol=1e-3)  # f32 on CPU: ties only
    assert facts["tick_has_mosaic_kernel"] is False
    assert facts["paged"]["requests"] == 4
    assert facts["paged"]["streams_equal_generate"] == 4


def test_kernels_phase_rehearsal():
    facts = chip_smoke.kernels_phase(
        head_shapes=((4, 4, 8), (4, 2, 8)), seq=32, block_sizes=(4,),
        context=32, cell_shapes=((6, 4, 4, 8, 64, None, 0, 63),
                                 (3, 6, 2, 8, 128, 40, 30, 127)))
    assert facts["interpret"] is True
    assert len(facts["cell_shapes"]) == 2


def test_data_parallel_phase_rehearsal(eight_devices):
    from pddl_tpu.models.resnet import tiny_resnet

    facts = chip_smoke.data_parallel_phase(
        devices=eight_devices[:4],
        model_fn=lambda: tiny_resnet(num_classes=10, dtype=jnp.float32),
        image_size=32, global_batch=8, num_classes=10, steps=3,
        min_shard_bytes=1 << 10)
    assert facts["mirrored"]["param_bytes_sharded"] == 0
    assert facts["ps"]["param_bytes_sharded"] > 0
