"""The chip path has no fallback that hides the device (PR 21).

CPU checks of what only matters on a machine with a TPU: the launcher
refuses to run without one, an unknown device has no peak, the control
plane leaves the accelerator to the process that needs it, and the
chip half does not import the control plane.
(The AOT contract is in tests/test_trainer.py, the dead-engine one in
tests/test_serve.py, the cache-directory rule in tests/test_warmup.py.)
"""

import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args],
        cwd=REPO,
        env=dict(os.environ, **env),
        capture_output=True,
        text=True,
        timeout=300,
    )


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """With the environment pinning the CPU (as this sandbox does) it
    exits non-zero, says that no chip was found, prints no result."""
    out = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert out.returncode == 2
    assert "no chip found" in out.stderr
    assert "JAX_PLATFORMS='cpu'" in out.stderr
    assert out.stdout.strip() == ""


def test_peak_flops_raises_on_unknown_device_kind():
    from odh_kubeflow_tpu.utils.tpu import peak_flops_per_chip

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    assert peak_flops_per_chip(Dev()) == 197e12
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        peak_flops_per_chip(jax.devices()[0])  # a CPU is not a slow TPU
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        peak_flops_per_chip(Dev())


def test_control_plane_initialises_no_jax_backend():
    """A chip belongs to one process. The platform process — build
    ``Platform(sim=True)``, spawn a notebook, suspend it to a session
    checkpoint, resume it; plus a bare store save + load — must leave
    every JAX backend uninitialised, so the child that runs the user's
    first step finds the chip free."""
    probe = (
        "import sys, tempfile\n"
        "from odh_kubeflow_tpu.sessions.checkpoint import "
        "SessionCheckpointStore\n"
        "store = SessionCheckpointStore(tempfile.mkdtemp())\n"
        "state = {'cells': [1, 'two'], 'n': 3}\n"
        "receipt = store.save('uid-a', state)\n"
        "loaded, digest = store.load('uid-a')\n"
        "assert loaded == state and digest == receipt['digest']\n"
        "from loadtest.spawn_latency import measure_spawn_to_ready\n"
        "out = measure_spawn_to_ready(with_suspend_resume=True)\n"
        "assert out['state_restored'] is True, out\n"
        "if 'jax' in sys.modules:\n"
        "    from jax._src import xla_bridge\n"
        "    assert not xla_bridge.backends_are_initialized()\n"
        "print('clean')\n"
    )
    out = _run(["-c", probe])
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("clean")


_CONTROL_PLANE = tuple(
    f"odh_kubeflow_tpu.{name}."
    for name in (
        "machinery", "analysis", "controllers", "sessions", "scheduling",
        "web", "webhooks", "warmup",
    )
)


@pytest.mark.parametrize(
    "module",
    ["odh_kubeflow_tpu.models.engine", "odh_kubeflow_tpu.train.trainer"],
)
def test_chip_half_imports_no_control_plane(module):
    """The other direction: a serving or training process joins the
    compile cache through a leaf (``utils/compile_cache.py``) and loads
    neither the store, the WAL's neighbours nor the linter."""
    probe = (
        "import importlib, sys\n"
        f"importlib.import_module({module!r})\n"
        "loaded = sorted(m for m in sys.modules\n"
        f"                if (m + '.').startswith({_CONTROL_PLANE!r}))\n"
        "assert not loaded, loaded\n"
        "print('clean')\n"
    )
    out = _run(["-c", probe], JAX_PLATFORMS="cpu")
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("clean")
