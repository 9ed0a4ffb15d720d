"""XLA/TPU profiler integration (BASELINE config #3's client half).

The platform story: a user captures traces from their notebook with
:func:`capture_trace` (or serves live with :func:`start_server` for
on-demand capture), writes them to a PVC or ``gs://`` bucket, and the
tensorboard-controller serves them (``controllers/tensorboard.py``
treats ``gs://`` as primary — that's where XLA traces land on TPU
pods). The layout produced here is exactly TensorBoard's profile
plugin contract: ``<logdir>/plugins/profile/<session>/<host>.xplane.pb``
plus ``.trace.json.gz``.

The program's own hot path (the decode engine's loop, the trainer's
step) marks its phases with :func:`hot_span`: one name, recorded in the
process's span ring (``utils/tracing.py``, what ``/debug/traces``
renders) and, while a profile is being captured, on the profiler's host
plane beside the device's ``XLA Ops``.

``jupyter-jax-tpu`` images auto-start the profiler server in every
IPython kernel (images/jupyter/start-jupyter.sh seeds the startup
file), so TensorBoard's "capture profile" button works against a
running notebook with zero user code.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from contextlib import contextmanager
from typing import Any, Iterator, Optional

from odh_kubeflow_tpu.utils import tracing

DEFAULT_PORT = int(os.environ.get("JAX_PROFILER_PORT", "9999"))


def start_server(port: Optional[int] = None):
    """Start the in-process profiler gRPC server TensorBoard's
    profile plugin captures from. Idempotent-ish: a second call in the
    same process raises inside jax; callers (the kernel-startup hook)
    guard with :func:`server_started`."""
    import jax

    port = port or DEFAULT_PORT
    server = jax.profiler.start_server(port)
    _STATE["server"] = server
    _STATE["port"] = port
    return server


def server_started() -> bool:
    return _STATE.get("server") is not None


_STATE: dict[str, Any] = {}


@contextmanager
def capture_trace(logdir: str):
    """Capture one profiling session into TensorBoard layout."""
    import jax

    os.makedirs(logdir, exist_ok=True)
    with jax.profiler.trace(logdir):
        yield
    # jax writes plugins/profile/<ts>/ under logdir


# The hot path's ROOT spans, and the latency over which the collector
# keeps one (with its children) as ``slow``; under it the span ages out
# of the ring as healthy. A turn of the decode engine is one decode
# chunk (0.3 s at 7B on one v5e) plus the prefills it admitted, so 2 s
# is a stall (or a compile inside the loop). A request lasts seconds to
# minutes by design (512 tokens at 40 ms are 20 s): only one that
# outlives a full-length generation is kept, or the kept store (128
# traces) would fill with healthy traffic. Waiting for work is never
# slow. A training step's host time converges on the device's step time
# whatever the model: only one that waits out a long compile is kept.
HOT_ROOT_SLOW_S = {
    "engine.turn": 2.0,
    "engine.request": 120.0,
    "engine.idle": float("inf"),
    "trainer.step": 60.0,
}
tracing.ROOT_THRESHOLDS.update(HOT_ROOT_SLOW_S)


@contextmanager
def hot_span(name: str, **attrs: Any) -> Iterator[tracing.SpanContext]:
    """A span of the hot path: ``tracing.span(name, **attrs)`` and a
    ``jax.profiler.TraceAnnotation(name)`` entered together. The span
    always lands in the process's ring (bounded, tail-kept like every
    other trace); the annotation costs a ``TraceMe`` check unless a
    profile is being captured (:func:`capture_trace`, the profiler
    server, a benchmark's ``--trace 1``), and then the same name lies on
    the capture's host plane, on one timeline with ``/device:TPU:0``.

    Granularity is the caller's contract: one per loop turn, per phase
    of a turn, per training step — never per token, slot or layer."""
    from jax.profiler import TraceAnnotation

    with tracing.span(name, **attrs) as ctx, TraceAnnotation(name):
        yield ctx


def trace_sessions(logdir: str) -> list[str]:
    """Session directories in TensorBoard profile-plugin layout,
    newest last."""
    return sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*")))


def latest_trace_events(logdir: str) -> list[dict]:
    """Parse the newest session's ``.trace.json.gz`` (the Chrome
    trace-event format TensorBoard's trace viewer renders) — the
    cheap validity check that what we captured is servable."""
    sessions = trace_sessions(logdir)
    if not sessions:
        return []
    files = glob.glob(os.path.join(sessions[-1], "*.trace.json.gz"))
    if not files:
        return []
    with gzip.open(files[0], "rt") as f:
        doc = json.load(f)
    return doc.get("traceEvents", [])


def kernel_startup_snippet() -> str:
    """The IPython-startup hook baked into TPU notebook images
    (images/jupyter/start-jupyter.sh seeds it into
    ``~/.ipython/profile_default/startup/``)."""
    return (
        "# auto-start the JAX profiler server so TensorBoard's\n"
        "# 'capture profile' works against this kernel (set\n"
        "# TPU_PROFILER_AUTOSTART=false to disable)\n"
        "import os as _os\n"
        "if _os.environ.get('TPU_PROFILER_AUTOSTART', 'true') == 'true':\n"
        "    try:\n"
        "        from odh_kubeflow_tpu.utils import profiling as _prof\n"
        "        if not _prof.server_started():\n"
        "            _prof.start_server()\n"
        "    except Exception:\n"
        "        pass\n"
    )
