"""Test bootstrap: force an 8-device virtual CPU mesh before jax imports.

Mirrors the reference's envtest strategy (SURVEY.md §4: multi-node
behavior is tested against fakes, never real hardware): all sharding /
collective paths compile and run on 8 virtual CPU devices.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# Tests get NO persistent compilation cache — not this process, not a
# child it starts (jax reads the variable at import). Trainer and
# DecodeEngine would otherwise join the checkout's .jax_compile_cache
# (utils.compile_cache.install_process_cache), and a test run must not
# read what an earlier run wrote there, nor pay disk writes tier-1 has
# no time for. tests/test_warmup.py checks the directory rule itself,
# which needs no live cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402
import jax  # noqa: E402

# Numerical-equivalence tests (merge-vs-adapter, sharded-vs-single) need
# true float32 matmuls; the default precision emulates TPU bf16 passes.
jax.config.update("jax_default_matmul_precision", "highest")


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, devs
    return devs


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests"
    )
