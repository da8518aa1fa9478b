"""Two rules the chip bring-up set, pinned: where the compile cache goes,
and that ``bench.py`` names no device it did not find."""

import os
import types

import jax
import pytest

from pddl_tpu.utils.compile_cache import (
    CACHE_DIR_ENV,
    REPO_CACHE_DIR,
    enable_persistent_compile_cache,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_dir_config():
    """A sentinel in ``jax_compilation_cache_dir``, restored afterwards
    (jax reads the value when the cache initialises, once per process, so
    flipping it here moves no test's cache)."""
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_value,returned,configured", [
    # Placed from outside: jax reads the variable itself, code sets nothing.
    ("/placed/from/outside", "/placed/from/outside", "sentinel"),
    # Unset (or empty): ONE fixed path inside the checkout.
    (None, REPO_CACHE_DIR, REPO_CACHE_DIR),
    ("", REPO_CACHE_DIR, REPO_CACHE_DIR),
])
def test_compile_cache_placement_rule(monkeypatch, cache_dir_config,
                                      env_value, returned, configured):
    if env_value is None:
        monkeypatch.delenv(CACHE_DIR_ENV, raising=False)
    else:
        monkeypatch.setenv(CACHE_DIR_ENV, env_value)
    assert enable_persistent_compile_cache() == returned
    assert jax.config.jax_compilation_cache_dir == configured


def test_repo_cache_dir_is_fixed_and_inside_the_checkout():
    assert REPO_CACHE_DIR == os.path.join(_ROOT, ".jax_cache")
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("device,override,message", [
    (_device("cpu", "cpu"), None, "refusing to run on platform 'cpu'"),
    (_device("cpu", "cpu"), "819", "refusing to run on platform 'cpu'"),
    (_device("tpu", "TPU v9 imaginary"), None, "unknown device_kind"),
])
def test_bench_refuses_what_it_cannot_rate(monkeypatch, device, override,
                                           message):
    """No CPU grind, and no borrowed v5e bandwidth for a chip the table
    does not know — the explicit override is the only way past that."""
    import bench  # at the repo root; conftest.py puts that on sys.path

    if override is None:
        monkeypatch.delenv("PDDL_BENCH_HBM_GBPS", raising=False)
    else:
        monkeypatch.setenv("PDDL_BENCH_HBM_GBPS", override)
    with pytest.raises(SystemExit, match=message):
        bench.hbm_bytes_per_sec(device)


@pytest.mark.parametrize("kind,override,expected", [
    ("TPU v5 lite", None, 819e9),
    ("TPU v9 imaginary", "1000", 1000e9),
])
def test_bench_rates_known_or_stated_chips(monkeypatch, kind, override,
                                           expected):
    import bench

    if override is None:
        monkeypatch.delenv("PDDL_BENCH_HBM_GBPS", raising=False)
    else:
        monkeypatch.setenv("PDDL_BENCH_HBM_GBPS", override)
    assert bench.hbm_bytes_per_sec(_device("tpu", kind)) == expected
