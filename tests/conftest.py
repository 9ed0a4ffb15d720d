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


@pytest.fixture(scope="session")
def final_part_against_whole():
    """One prompt of two parts of 16 and ``rem`` more tokens, admitted
    twice, each time by an engine of its own with ``max_tokens=1`` (the
    prefill's own token ends the request, so no decode step follows
    it): in parts under buckets (4, 8, 16), where the final part must
    run at ``bucket`` positions, and whole in a bucket of 64. The first
    tokens must agree; returns the prompt's length and, for each
    admission, the slot's row of every stack of the cache on the host,
    ``{name: [layers, ...]}``, for the family's test to compare."""
    import jax.numpy as jnp
    import numpy as np

    from odh_kubeflow_tpu.models.engine import DecodeEngine
    from odh_kubeflow_tpu.models.llama import stack_kind

    def admit(params, cfg, prompt, max_len, **engine_kw):
        engine = DecodeEngine(
            params, cfg, n_slots=2, max_len=max_len, chunk=4,
            cache_dtype=jnp.float32, **engine_kw,
        )
        try:
            req = engine.submit(prompt, max_tokens=1)
            (first,) = req.result(timeout=300)
        finally:
            engine.stop()
        return req, first, {
            name: np.asarray(leaf[:, req.slot])
            for name, leaf in engine._state["cache"].items()
            if stack_kind(name)
        }

    def both(params, cfg, rem, bucket, max_len):
        prompt = np.random.default_rng(rem).integers(1, 256, size=32 + rem).tolist()
        req, first, parts = admit(
            params, cfg, prompt, max_len, prompt_buckets=(4, 8, 16),
            prefill_chunk=16,
        )
        assert req.bucket == bucket
        _, want, whole = admit(params, cfg, prompt, max_len, prompt_buckets=(64,))
        assert first == want
        return len(prompt), parts, whole

    return both
