"""North-star latency: notebook spawn → first JAX train step.

BASELINE.md's headline latency metric. Two measured segments:

1. **spawn→ready** — POST a TPU Notebook through the JWA REST API (the
   exact request the spawner UI sends) against the all-in-one platform
   and poll the same list endpoint the UI polls until the row reports
   ready. The kubelet is the simulator, so this segment measures the
   *platform* (admission → reconcile → schedule → status-mirror →
   BFF row shaping) and excludes image pull + container boot, which
   depend on cluster/network, not on this codebase.
2. **ready→first-step** — on the attached real TPU chip, do what the
   user's first cell does: import the runtime, build the Llama-1B LoRA
   trainer, and run one train step to a fetched loss. Cold-compile
   time is the dominant term and is measured for real — twice, in
   subprocesses routed through the compile-cache *service* (warmup/
   subsystem): the cold run fills the process cache directory, which
   is ingested as content-addressed ``CompileCacheEntry`` artifacts;
   the directory is emptied and materialized back from the service
   for the warm run — the exact path a warm-pool standby's
   pre-compiled cache mount takes. The directory is the one every
   process of this checkout uses (``JAX_COMPILATION_CACHE_DIR`` if
   set, else the checkout's own): the cold leg EMPTIES it.

This process initialises no JAX backend: on a machine with a chip the
chip belongs to the child that runs the first step.

``--warm-only`` (``make warmbench``) needs no accelerator: it races a
cold spawn against a warm-pool claim in ONE sim run (the cold spawn
pays the simulated image pull, the claim lands on the standby's
pre-imaged slice) and runs a cold/warm compile probe pair through the
cache service, gating warm-compile < 1s and warm < cold on both axes.

Prints one JSON line; ``--record`` rewrites the table row(s) in
BASELINE.md.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request


def measure_spawn_to_ready(with_suspend_resume: bool = False) -> dict:
    from odh_kubeflow_tpu.platform import Platform
    from odh_kubeflow_tpu.utils import tracing

    platform = Platform(sim=True)
    platform.cluster.add_node("cpu-0")
    platform.cluster.add_tpu_node_pool(
        "v5e", "tpu-v5-lite-podslice", "2x2", num_hosts=1, chips_per_host=4
    )
    platform.api.create(
        {
            "apiVersion": "kubeflow.org/v1",
            "kind": "Profile",
            "metadata": {"name": "bench-team"},
            "spec": {"owner": {"kind": "User", "name": "bench@example.com"}},
        }
    )
    api_port, web_port = platform.start(api_port=0, web_port=0)
    base = f"http://127.0.0.1:{web_port}"
    api_base = f"http://127.0.0.1:{api_port}"

    # the spawn is ONE trace: the POST carries this traceparent, the
    # store stamps the trace id on the Notebook, the controller fans it
    # to Workload/pods, and scheduler/kubelet/session spans join it —
    # the breakdown below is derived from the assembled tree and
    # cross-checked against the legacy polled-annotation path
    trace_id = tracing.new_trace_id()
    traceparent = f"00-{trace_id}-{tracing.new_span_id()}-01"

    def call(path, method="GET", body=None):
        headers = {
            "kubeflow-userid": "bench@example.com",
            "Content-Type": "application/json",
        }
        if method != "GET":
            headers["Cookie"] = "XSRF-TOKEN=t"
            headers["x-xsrf-token"] = "t"
            headers["traceparent"] = traceparent
        req = urllib.request.Request(
            base + path,
            data=json.dumps(body).encode() if body is not None else None,
            method=method,
            headers=headers,
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read().decode())

    t0 = time.monotonic()
    t0_wall = time.time()
    call(
        "/jupyter/api/namespaces/bench-team/notebooks",
        method="POST",
        body={
            "name": "latency-nb",
            "image": "odh-kubeflow-tpu/jupyter-jax-tpu:v0.1.0",
            "cpu": "4",
            "memory": "8Gi",
            "shm": True,
            "configurations": [],
            "tpus": {"accelerator": "tpu-v5-lite-podslice", "topology": "2x2"},
        },
    )
    # breakdown milestones, polled from the same details feed the UI
    # renders: queue wait (POST → workload Admitted), scheduling
    # (Admitted → every gang pod bound to a node), container start
    # (bound → row reports ready)
    ready_s = admitted_s = bound_s = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        details = call(
            "/jupyter/api/namespaces/bench-team/notebooks/latency-nb/details"
        )["details"]
        now = time.monotonic() - t0
        workload = details.get("workload") or {}
        if admitted_s is None and workload.get("state") == "Admitted":
            admitted_s = now
        pods = details.get("pods") or []
        if bound_s is None and pods and all(p.get("node") for p in pods):
            bound_s = now
        if details["status"]["phase"] == "ready":
            ready_s = now
            break
        time.sleep(0.05)
    if ready_s is None:
        platform.stop()
        raise RuntimeError("notebook never became ready")
    out = {"spawn_to_ready_s": round(ready_s, 3), "kubelet": "simulated"}
    if admitted_s is not None:
        bound_s = bound_s if bound_s is not None else ready_s
        out.update(
            {
                "queue_wait_s": round(admitted_s, 3),
                "scheduling_s": round(max(bound_s - admitted_s, 0.0), 3),
                "container_start_s": round(max(ready_s - bound_s, 0.0), 3),
            }
        )
    try:
        out.update(_trace_breakdown(api_base, trace_id, t0_wall, out))
        if with_suspend_resume:
            out.update(_measure_suspend_resume(platform, call))
            _assert_restore_traced(api_base, trace_id)
    finally:
        platform.stop()
    return out


# the two breakdowns measure through different clocks (trace spans end
# when the write lands; the legacy path polls the UI feed at 50ms and
# the sim steps at 500ms), so agreement is bounded, not exact
TRACE_TOLERANCE_S = 1.5


def _fetch_trace(api_base: str, trace_id: str) -> list[dict]:
    req = urllib.request.Request(
        f"{api_base}/debug/traces?trace={trace_id}&format=json"
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        body = json.loads(r.read().decode())
    traces = body.get("traces") or []
    return traces[0]["spans"] if traces else []


def _trace_breakdown(
    api_base: str,
    trace_id: str,
    t0_wall: float,
    legacy: dict,
) -> dict:
    """Derive the queue/schedule/start breakdown from the assembled
    spawn trace (served by the apiserver's /debug/traces zpage) and
    assert it agrees with the legacy polled-annotation path within
    tolerance. Raises on a missing milestone span or a disagreement —
    this IS the gate that the trace pipeline tells the truth."""
    spans = _fetch_trace(api_base, trace_id)
    ends: dict[str, float] = {}
    for s in spans:
        end = float(s["start"]) + float(s["duration"])
        ends[s["name"]] = max(ends.get(s["name"], 0.0), end)
    required = (
        "scheduler.admit",
        "kubelet.gang_bind",
        "kubelet.container_start",
    )
    missing = [n for n in required if n not in ends]
    if missing:
        raise RuntimeError(
            f"spawn trace {trace_id} is missing span(s) {missing}; "
            f"got {sorted(ends)}"
        )
    admit_end = ends["scheduler.admit"] - t0_wall
    bind_end = ends["kubelet.gang_bind"] - t0_wall
    start_end = ends["kubelet.container_start"] - t0_wall
    if not admit_end <= bind_end <= start_end:
        raise RuntimeError(
            "spawn trace milestones out of order: "
            f"admit={admit_end:.3f}s bind={bind_end:.3f}s "
            f"start={start_end:.3f}s"
        )
    derived = {
        "queue_wait_trace_s": round(max(admit_end, 0.0), 3),
        "scheduling_trace_s": round(max(bind_end - admit_end, 0.0), 3),
        "container_start_trace_s": round(max(start_end - bind_end, 0.0), 3),
        "trace_id": trace_id,
        "trace_spans": len(spans),
    }
    for trace_key, legacy_key in (
        ("queue_wait_trace_s", "queue_wait_s"),
        ("scheduling_trace_s", "scheduling_s"),
        ("container_start_trace_s", "container_start_s"),
    ):
        if legacy_key not in legacy:
            continue
        delta = abs(derived[trace_key] - legacy[legacy_key])
        if delta > TRACE_TOLERANCE_S:
            raise RuntimeError(
                f"trace-derived {trace_key}={derived[trace_key]}s "
                f"disagrees with legacy {legacy_key}="
                f"{legacy[legacy_key]}s by {delta:.3f}s "
                f"(tolerance {TRACE_TOLERANCE_S}s)"
            )
    return derived


def _assert_restore_traced(api_base: str, trace_id: str) -> None:
    """After a suspend/resume cycle the SAME spawn trace must contain
    the session.restore span (the notebook keeps its trace annotation,
    so the resume's restore lands in the original tree)."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        spans = _fetch_trace(api_base, trace_id)
        if any(s["name"] == "session.restore" for s in spans):
            return
        time.sleep(0.2)
    raise RuntimeError(
        f"resume finished but trace {trace_id} has no session.restore "
        "span"
    )


def _measure_suspend_resume(platform, call) -> dict:
    """The warm-resume half (sessions/ subsystem): suspend the ready
    notebook to a checkpoint (slice reservation freed), reopen it, and
    time suspend → durable and reopen → ready-with-state-restored. The
    kernel state planted before the suspend proves the resume is warm —
    it must come back bit-identical in the fresh pod."""
    state = {"bench": "kernel-state", "cells": list(range(32))}
    platform.cluster.set_session_state("bench-team", "latency-nb", state)

    def details():
        return call(
            "/jupyter/api/namespaces/bench-team/notebooks/latency-nb/details"
        )["details"]

    t0 = time.monotonic()
    call(
        "/jupyter/api/namespaces/bench-team/notebooks/latency-nb",
        method="PATCH",
        body={"stopped": True, "suspend": True},
    )
    suspend_s = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        d = details()
        if d["status"]["phase"] == "suspended" and d.get("workload") is None:
            suspend_s = time.monotonic() - t0
            break
        time.sleep(0.05)
    if suspend_s is None:
        raise RuntimeError("notebook never suspended (workload not freed)")

    t1 = time.monotonic()
    call(
        "/jupyter/api/namespaces/bench-team/notebooks/latency-nb/resume",
        method="POST",
        body={},
    )
    readmitted_s = warm_resume_s = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        d = details()
        now = time.monotonic() - t1
        workload = d.get("workload") or {}
        if readmitted_s is None and workload.get("state") == "Admitted":
            readmitted_s = now
        if d["status"]["phase"] == "ready":
            warm_resume_s = now
            break
        time.sleep(0.05)
    if warm_resume_s is None:
        raise RuntimeError("suspended notebook never resumed to ready")
    restored = (
        platform.cluster.get_session_state("bench-team", "latency-nb")
        == state
    )
    readmitted_s = readmitted_s if readmitted_s is not None else warm_resume_s
    return {
        "suspend_s": round(suspend_s, 3),
        "warm_resume_s": round(warm_resume_s, 3),
        "resume_queue_wait_s": round(readmitted_s, 3),
        "resume_restore_s": round(max(warm_resume_s - readmitted_s, 0.0), 3),
        "state_restored": restored,
    }


def measure_first_jax_step() -> dict:
    """The user's first cell, timed from a cold process state: build
    the sharded trainer and fetch the first loss."""
    t_import = time.monotonic()
    import jax
    import jax.numpy as jnp

    from odh_kubeflow_tpu.models import LlamaConfig, LoraConfig
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer

    devices = jax.devices()
    import_s = time.monotonic() - t_import

    t_build = time.monotonic()
    B, S = max(8, len(devices)), 1024
    trainer = Trainer(
        LlamaConfig.llama3_1b(dtype=jnp.bfloat16),
        TrainConfig(warmup_steps=2, total_steps=100),
        lora_cfg=LoraConfig(rank=16),
        mesh=build_mesh(MeshConfig(fsdp=len(devices)), devices),
        # the step compile (the biggest cold term) starts on a
        # background thread from abstract shapes while the inits run —
        # the notebook images' example first cell does the same
        precompile_batch=(B, S),
    )
    build_s = time.monotonic() - t_build
    batch = {
        "tokens": jnp.zeros((B, S), jnp.int32),
        "targets": jnp.zeros((B, S), jnp.int32),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    t_step = time.monotonic()
    metrics = trainer.train_step(batch)
    loss = float(metrics["loss"])  # host fetch: waits for the step
    first_step_s = time.monotonic() - t_step
    return {
        "device": getattr(devices[0], "device_kind", "cpu"),
        "import_s": round(import_s, 2),
        "trainer_build_s": round(build_s, 2),
        "first_step_compile_s": round(first_step_s, 2),
        "loss": round(loss, 3),
    }


def record(result: dict) -> None:
    import pathlib
    import re

    path = pathlib.Path(__file__).resolve().parent.parent / "BASELINE.md"
    text = path.read_text()
    warm = result.get("first_step_warm")
    warm_part = (
        f"; **warm re-spawn {result['total_warm_s']:.1f}s** (persistent "
        f"compile cache on the workspace PVC: build "
        f"{warm['trainer_build_s']}s + step {warm['first_step_compile_s']}s)"
        if warm
        else ""
    )
    breakdown = (
        (
            f" [queue {result['queue_wait_s']}s / schedule "
            f"{result['scheduling_s']}s / start {result['container_start_s']}s]"
        )
        if "queue_wait_s" in result
        else ""
    )
    resume_part = (
        (
            f"; **suspended-session warm resume "
            f"{result['warm_resume_s']}s** (suspend-to-checkpoint "
            f"{result['suspend_s']}s, resume re-queue "
            f"{result['resume_queue_wait_s']}s + state restore "
            f"{result['resume_restore_s']}s; restored kernel keeps its "
            "jitted state — no rebuild, no recompile)"
        )
        if "warm_resume_s" in result
        else ""
    )
    line = (
        f"| Spawn → first JAX step latency | "
        f"**{result['total_s']:.1f}s** cold (spawn→ready "
        f"{result['spawn_to_ready_s']}s{breakdown} platform path on sim kubelet, + "
        f"trainer build {result['first_step']['trainer_build_s']}s + "
        f"first-step compile {result['first_step']['first_step_compile_s']}s "
        f"on real {result['first_step']['device']}; excludes image pull)"
        f"{warm_part}{resume_part} "
        f"| v5e-1 (single chip) and v5p-8 | loadtest/spawn_latency.py |"
    )
    pattern = r"\| Spawn → first JAX step latency \|[^\n]*"
    if re.search(pattern, text):
        text = re.sub(pattern, line, text, count=1)
    else:
        text += "\n" + line + "\n"
    path.write_text(text)


def _child(flag: str) -> dict:
    """Run one ``--first-step-only`` / ``--compile-probe`` leg in a
    fresh interpreter — the only way to measure a cold/warm pair (an
    in-process rerun would hit jax's in-memory jit cache and measure
    nothing). The child inherits the environment and finds its cache
    directory by the one rule (``compilecache.process_cache_dir``);
    nothing here re-points it."""
    import subprocess

    out = subprocess.run(
        [sys.executable, "-m", "loadtest.spawn_latency", flag],
        capture_output=True,
        text=True,
        timeout=580,
    )
    if out.returncode:
        raise RuntimeError(
            f"spawn_latency {flag} exited {out.returncode}:\n"
            f"{out.stderr[-4000:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def _cache_service(root: str):
    """A standalone compile-cache service over a throwaway apiserver —
    the same CompileCacheService the platform embeds, so the bench
    exercises the real ingest/materialize/GC path, not a lookalike."""
    from odh_kubeflow_tpu.machinery.store import APIServer
    from odh_kubeflow_tpu.warmup import register_warmup
    from odh_kubeflow_tpu.warmup.compilecache import (
        CompileCacheConfig,
        CompileCacheService,
    )

    api = APIServer()
    register_warmup(api)
    return CompileCacheService(api, CompileCacheConfig(cache_dir=root))


def _empty_dir(path: str) -> None:
    """Leave ``path`` existing and empty (the path itself must not
    change: it is part of jax's cache key)."""
    import os
    import shutil

    os.makedirs(path, exist_ok=True)
    for name in os.listdir(path):
        full = os.path.join(path, name)
        if os.path.isdir(full) and not os.path.islink(full):
            shutil.rmtree(full)
        else:
            os.unlink(full)


def measure_compile_cache_roundtrip(probe: bool = False) -> dict:
    """Cold subprocess → ingest into the service → materialize → warm
    subprocess. ``probe=True`` swaps the Llama trainer for a small
    compile-heavy jitted probe so the roundtrip runs on CPU in CI."""
    import tempfile

    from odh_kubeflow_tpu.warmup.compilecache import process_cache_dir

    flag = "--compile-probe" if probe else "--first-step-only"
    topo = "bench"
    # XLA folds the cache-dir path into the compile-env key, so a hit
    # requires the SAME path cold and warm — which is the production
    # contract anyway: COMPILE_CACHE_MOUNT pins one stable path into
    # every pod. Here it is the directory the children resolve
    # themselves; a cold leg empties it, it does not invent another.
    mount = process_cache_dir()
    # the service's artifact store is not a process's cache directory
    with tempfile.TemporaryDirectory(prefix="warmcc-svc-") as svc_root:
        svc = _cache_service(svc_root)
        _empty_dir(mount)
        cold = _child(flag)  # cold: fills the mount
        ingested = svc.ingest_dir(mount, topology=topo)
        _empty_dir(mount)  # fresh pod: the mount starts empty ...
        materialized = svc.materialize_dir(mount, topology=topo)
        warm = _child(flag)  # ... holding only what the service served
        stats = svc.stats()
    return {
        "first_step": cold,
        "first_step_warm": warm,
        "compile_cache": {
            "dir": mount,
            "ingested": ingested,
            "materialized": materialized,
            **stats,
        },
    }


def _compile_probe() -> dict:
    """A deliberately compile-heavy jitted function (~1s cold on CPU)
    whose warm cost is a persistent-cache deserialization — the CI
    stand-in for the Llama first-step compile."""
    from odh_kubeflow_tpu.warmup.compilecache import install_process_cache

    cache_dir = install_process_cache()
    import jax
    import jax.numpy as jnp

    @jax.jit
    def step(x):
        for i in range(48):
            x = jnp.tanh(x @ (x * (1.0 + i / 37.0)).T @ x) / (2.0 + i)
        return x.sum()

    x = jnp.ones((192, 192), jnp.float32)
    t0 = time.monotonic()
    step(x).block_until_ready()
    return {
        "first_step_compile_s": round(time.monotonic() - t0, 3),
        "cache_dir": cache_dir,
    }


def measure_warm_spawn() -> dict:
    """Cold spawn vs warm-pool claim in ONE sim run. The simulated
    image pull is the cold tax; the warm spawn claims a standby whose
    slice already pulled the image and whose template kernel state
    restores through the ordinary resume machinery."""
    from odh_kubeflow_tpu.platform import Platform
    from odh_kubeflow_tpu.warmup import WARM_FROM_ANNOTATION
    from odh_kubeflow_tpu.warmup.pool import new_warm_pool

    image = "odh-kubeflow-tpu/jupyter-jax-tpu:v0.1.0"
    platform = Platform(sim=True)
    platform.cluster.add_node("cpu-0")
    for i in range(2):
        platform.cluster.add_tpu_node_pool(
            f"v5e-{i}", "tpu-v5-lite-podslice", "2x2",
            num_hosts=1, chips_per_host=4,
        )
    # every first placement on a pool pays this pull; the standby
    # pre-pays it off the user's clock
    platform.cluster.image_pull_seconds = 1.5
    platform.api.create(
        {
            "apiVersion": "kubeflow.org/v1",
            "kind": "Profile",
            "metadata": {"name": "bench-team"},
            "spec": {"owner": {"kind": "User", "name": "bench@example.com"}},
        }
    )
    _, web_port = platform.start(api_port=0, web_port=0)
    base = f"http://127.0.0.1:{web_port}"

    def call(path, method="GET", body=None):
        headers = {
            "kubeflow-userid": "bench@example.com",
            "Content-Type": "application/json",
        }
        if method != "GET":
            headers["Cookie"] = "XSRF-TOKEN=t"
            headers["x-xsrf-token"] = "t"
        req = urllib.request.Request(
            base + path,
            data=json.dumps(body).encode() if body is not None else None,
            method=method,
            headers=headers,
        )
        with urllib.request.urlopen(req, timeout=10) as r:
            return json.loads(r.read().decode())

    def spawn(name):
        t0 = time.monotonic()
        call(
            "/jupyter/api/namespaces/bench-team/notebooks",
            method="POST",
            body={
                "name": name,
                "image": image,
                "cpu": "1",
                "memory": "2Gi",
                "workspaceVolume": None,
                "dataVolumes": [],
                "tpus": {
                    "accelerator": "tpu-v5-lite-podslice",
                    "topology": "2x2",
                },
            },
        )
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            details = call(
                f"/jupyter/api/namespaces/bench-team/notebooks/{name}/details"
            )["details"]
            if details["status"]["phase"] == "ready":
                return time.monotonic() - t0, details
            time.sleep(0.05)
        raise RuntimeError(f"{name} never became ready")

    try:
        cold_s, _ = spawn("cold-nb")
        # stand up the pool and let the standby pre-pull + pre-admit
        platform.api.create(
            new_warm_pool(
                "bench-pool", "bench-team", size=1,
                accelerator="tpu-v5-lite-podslice", topology="2x2",
                image=image,
            )
        )
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            pool = platform.api.get("WarmPool", "bench-pool", "bench-team")
            if (pool.get("status") or {}).get("readyStandbys") == 1:
                break
            time.sleep(0.05)
        else:
            raise RuntimeError("warm pool never reached readyStandbys=1")
        warm_s, details = spawn("warm-nb")
        warm_from = (details.get("warm") or {}).get("pool")
        nb = platform.api.get("Notebook", "warm-nb", "bench-team")
        ann = (nb["metadata"].get("annotations") or {})
        handout = ann.get(WARM_FROM_ANNOTATION) == "bench-pool"
    finally:
        platform.stop()
    return {
        "cold_spawn_s": round(cold_s, 3),
        "warm_spawn_s": round(warm_s, 3),
        "image_pull_s": platform.cluster.image_pull_seconds,
        "warm_handout": handout,
        "warm_pool": warm_from or "",
        "kubelet": "simulated",
    }


def record_warm(result: dict) -> None:
    import pathlib
    import re

    path = pathlib.Path(__file__).resolve().parent.parent / "BASELINE.md"
    text = path.read_text()
    line = (
        f"| Warm-start (pool claim + compile cache) | "
        f"**spawn {result['warm_spawn_s']}s warm vs "
        f"{result['cold_spawn_s']}s cold** (standby claim skips the "
        f"{result['image_pull_s']}s image pull, sim kubelet); **compile "
        f"{result['first_step_warm']['first_step_compile_s']}s warm vs "
        f"{result['first_step']['first_step_compile_s']}s cold** "
        f"(cache-service ingest → materialize roundtrip, CPU probe; "
        f"gate warm < 1s) "
        f"| sim + CPU probe | loadtest/spawn_latency.py --warm-only |"
    )
    pattern = r"\| Warm-start \(pool claim \+ compile cache\) \|[^\n]*"
    anchor = r"(\| Spawn → first JAX step latency \|[^\n]*\n)"
    if re.search(pattern, text):
        text = re.sub(pattern, line, text, count=1)
    elif re.search(anchor, text):
        text = re.sub(anchor, r"\1" + line.replace("\\", r"\\") + "\n", text, count=1)
    else:
        text += line + "\n"
    path.write_text(text)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--record", action="store_true", help="update BASELINE.md")
    parser.add_argument(
        "--first-step-only",
        action="store_true",
        help="just the ready→first-step half (the user's first cell), "
        "in this process",
    )
    parser.add_argument(
        "--compile-probe",
        action="store_true",
        help="internal: the compile-heavy CPU probe",
    )
    parser.add_argument(
        "--warm-only",
        action="store_true",
        help="`make warmbench`: cold-vs-warm spawn in one sim run plus "
        "the cache-service compile roundtrip, gated (no accelerator "
        "needed)",
    )
    parser.add_argument(
        "--suspend-only",
        action="store_true",
        help="`make suspend-bench`: the platform-path cold spawn plus "
        "suspend → reopen → ready warm resume, gated (no accelerator "
        "needed)",
    )
    args = parser.parse_args()

    if args.first_step_only:
        print(json.dumps(measure_first_jax_step()))
        return

    if args.compile_probe:
        print(json.dumps(_compile_probe()))
        return

    if args.warm_only:
        import os

        result = measure_warm_spawn()
        if os.environ.get("WARM_POOL_ENABLED", "true").lower() == "true":
            # gate 1: the claim actually came from the pool, and the
            # warm spawn beat the cold one inside the SAME sim run
            if not result["warm_handout"]:
                raise SystemExit(
                    "GATE FAILED: spawn did not claim the warm standby"
                )
            if result["warm_spawn_s"] >= result["cold_spawn_s"]:
                raise SystemExit(
                    f"GATE FAILED: warm spawn {result['warm_spawn_s']}s "
                    f"not faster than cold {result['cold_spawn_s']}s"
                )
        result.update(measure_compile_cache_roundtrip(probe=True))
        cold_c = result["first_step"]["first_step_compile_s"]
        warm_c = result["first_step_warm"]["first_step_compile_s"]
        # gate 2: a materialized cache turns the compile into a
        # deserialization — sub-second, and strictly under cold
        if warm_c >= 1.0:
            raise SystemExit(
                f"GATE FAILED: warm compile {warm_c}s breaches the 1s bound"
            )
        if warm_c >= cold_c:
            raise SystemExit(
                f"GATE FAILED: warm compile {warm_c}s not faster than "
                f"cold {cold_c}s"
            )
        result["gate"] = "passed"
        print(json.dumps(result))
        if args.record:
            record_warm(result)
        return

    if args.suspend_only:
        import os

        if (
            os.environ.get("ENABLE_SESSION_SUSPEND", "true").lower()
            != "true"
        ):
            print(
                json.dumps(
                    {
                        "skipped": "sessions subsystem disabled "
                        "(ENABLE_SESSION_SUSPEND=false); nothing to gate"
                    }
                )
            )
            return
        result = measure_spawn_to_ready(with_suspend_resume=True)
        # the gate: suspend actually freed the reservation, the resume
        # came back with bit-identical kernel state, and the warm
        # reopen is not pathologically slower than a cold spawn (it
        # skips PVC/create but re-queues through admission)
        if not result["state_restored"]:
            raise SystemExit("GATE FAILED: resumed state not bit-identical")
        bound = max(2.0 * result["spawn_to_ready_s"], result["spawn_to_ready_s"] + 2.0)
        if result["warm_resume_s"] > bound:
            raise SystemExit(
                f"GATE FAILED: warm resume {result['warm_resume_s']}s "
                f"exceeds {bound:.1f}s bound (cold spawn "
                f"{result['spawn_to_ready_s']}s)"
            )
        result["gate"] = "passed"
        print(json.dumps(result))
        return

    import os

    # the suspend/resume half needs the sessions subsystem; honor the
    # documented opt-out instead of timing out against a platform that
    # will never reach phase "suspended"
    sessions_on = (
        os.environ.get("ENABLE_SESSION_SUSPEND", "true").lower() == "true"
    )
    spawn = measure_spawn_to_ready(with_suspend_resume=sessions_on)
    # the cold run stages into the cache service, the warm run reads a
    # dir the service materialized — the standby's pre-compiled mount
    roundtrip = measure_compile_cache_roundtrip()
    first = roundtrip["first_step"]
    warm = roundtrip["first_step_warm"]
    if warm["first_step_compile_s"] >= 1.0:
        raise SystemExit(
            f"GATE FAILED: warm first-step compile "
            f"{warm['first_step_compile_s']}s breaches the 1s bound "
            f"(cold {first['first_step_compile_s']}s)"
        )
    result = {
        **spawn,
        **roundtrip,
        "total_s": round(
            spawn["spawn_to_ready_s"]
            + first["trainer_build_s"]
            + first["first_step_compile_s"],
            3,
        ),
        "total_warm_s": round(
            spawn["spawn_to_ready_s"]
            + warm["trainer_build_s"]
            + warm["first_step_compile_s"],
            3,
        ),
    }
    if "warm_resume_s" in spawn:
        # a resumed session needs NO trainer rebuild or step compile —
        # the restored kernel still holds the jitted state. That is the
        # recorded cold-vs-warm gate: resume must beat the cold total.
        result["total_warm_resume_s"] = spawn["warm_resume_s"]
        if spawn["warm_resume_s"] >= result["total_s"]:
            raise SystemExit(
                f"GATE FAILED: warm resume {spawn['warm_resume_s']}s is "
                f"not faster than cold spawn {result['total_s']}s"
            )
    print(json.dumps(result))
    if args.record:
        record(result)


if __name__ == "__main__":
    sys.exit(main())
