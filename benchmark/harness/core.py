"""The harness: finds a cell's files by name, refuses to run without the
chips, hands the run to the mix's driver, reduces the trace, reads the
per-layer metrics through their readers and prints the result line.

Nothing here knows a cell, a configuration, a mix or a metric by name:
``BENCHMARK.json`` names the cell, ``cells/<cell>.json`` names the
configuration, the mix and the per-layer metrics it reports,
``traffic/<mix>.json`` names its driver, ``configs/<config>.json`` names
its model family, and each metric's own file names its reader. ``roots``
is the search path for all of them; the tests put a directory of their
own in front of the benchmark's."""

from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def find_file(roots, kind: str, name: str, ext: str) -> str:
    for root in roots:
        path = os.path.join(root, kind, name + ext)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {list(roots)}")


def load_json(roots, kind: str, name: str) -> dict:
    with open(find_file(roots, kind, name, ".json")) as f:
        return json.load(f)


def load_module(roots, kind: str, name: str):
    path = find_file(roots, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(path: Optional[str] = None) -> dict:
    with open(path or os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    """What a driver and the readers see of one run."""

    workload: str
    seed: int
    seconds: float
    trace: bool
    t0: float
    roots: tuple
    cell: dict
    config: dict
    mix: dict
    family: Any
    devices: list
    runtime_start_s: float
    rehearsal: bool
    counters: Any
    trace_dir: str
    peaks: Optional[dict] = None
    values: dict = dataclasses.field(default_factory=dict)
    checks: list = dataclasses.field(default_factory=list)
    reduced: Optional[dict] = None

    def check(self, name: str, value: float, limit: float) -> None:
        """One number compared, printed beside its limit. NaN fails."""
        ok = bool(value <= limit)
        self.checks.append({"name": name, "value": value, "limit": limit, "ok": ok})
        # on standard output, before the result line: every run shows
        # each number compared beside its limit
        print(
            f"check {name}: {value!r} (limit {limit!r}) {'ok' if ok else 'FAILED'}",
            flush=True,
        )

    def ready(self) -> None:
        """The driver calls this when every shape is warm and the window
        can open: ``setup_s`` is process start to now, less the start of
        the TPU runtime."""
        now = time.monotonic()
        self.values["runtime_start_s"] = self.runtime_start_s
        self.values["setup_s"] = now - self.t0 - self.runtime_start_s

    @property
    def device_prefix(self) -> str:
        return "/host:CPU" if self.rehearsal else "/device:TPU:"


def device_report(run: Run, memory_peak: Optional[int]) -> dict:
    d = run.devices[0]
    out = {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(run.devices),
        "memory_peak_bytes": memory_peak,
    }
    if run.reduced is not None:
        out["busy_s"] = run.reduced["busy_s"]
        out["window_s"] = run.reduced["window_s"]
    return out


def memory_peak_bytes(devices) -> Optional[int]:
    """The peak on the fullest chip. The TPU runtime counts a program's
    temporaries as ``reserved``, outside ``in_use``: around the training
    step ``peak_bytes_in_use`` read 7.61 GB (the step's arguments are
    7.55) and ``peak_bytes_reserved`` 5.77 GB, and the compiled step's
    own ``memory_analysis()`` gives a peak of 13.31 GB = 7.55 of
    arguments + 5.76 of live temporaries (PERF.md, PR 23). So the peak
    is the sum of the two peaks; where they fall at different moments it
    reads high by the difference (0.03 GB there)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"memory_stats {d}: {stats}")
        if "peak_bytes_in_use" in stats:
            peaks.append(
                stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)
            )
    return max(peaks) if peaks else None


def prepare(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t0: float,
    roots=(BENCH_DIR,),
    manifest: Optional[dict] = None,
    rehearsal: bool = False,
) -> dict:
    """Find the cell's files and the chips: the ``Run`` a driver gets."""
    import jax

    from benchmark.harness import counts
    from benchmark.harness.compile_listener import CompileCounters

    manifest = manifest or load_manifest()
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == workload), None
    )
    if entry is None:
        raise SystemExit(f"no workload {workload!r} in the manifest")
    cell = load_json(roots, "cells", workload)
    if (cell["config"], cell["traffic"]) != (entry["config"], entry["traffic"]):
        raise SystemExit(f"cells/{workload}.json disagrees with the manifest")
    config = load_json(roots, "configs", cell["config"])
    mix = load_json(roots, "traffic", cell["traffic"])

    # the first call that touches the backend starts the TPU runtime:
    # 7 to 22 s from one process to the next on the same code (PERF.md,
    # PR 23), which nothing in the repository can move. It is timed on
    # its own and is not part of ``setup_s``
    t_runtime = time.monotonic()
    devices = jax.devices()
    runtime_start_s = time.monotonic() - t_runtime
    if devices[0].platform == "cpu" and not rehearsal:
        raise SystemExit(
            "no accelerator: jax found only the CPU, and a CPU number is "
            "never written under a device metric's name"
        )
    if len(devices) < entry["chips"]:
        raise SystemExit(
            f"the cell asks for {entry['chips']} chips, jax found {len(devices)}"
        )
    devices = devices[: entry["chips"]]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    run = Run(
        workload=workload, seed=seed, seconds=seconds, trace=trace, t0=t0,
        roots=tuple(roots), cell=cell, config=config, mix=mix,
        family=load_module(roots, "families", config["family"]),
        devices=devices, runtime_start_s=runtime_start_s,
        rehearsal=rehearsal, counters=CompileCounters(),
        trace_dir=tempfile.mkdtemp(prefix="trace-"),  # under TMPDIR
        peaks=None if rehearsal else counts.peaks(devices[0].device_kind),
    )
    return run, manifest


def run_cell(workload, seed, seconds, trace, **kw) -> dict:
    """Run one cell and return the result object (the last line)."""
    from benchmark.harness import trace as trace_lib

    run, manifest = prepare(workload, seed, seconds, trace, **kw)
    roots, cell = run.roots, run.cell
    driver = load_module(roots, "drivers", run.mix["driver"])
    outcome = driver.run(run)  # {"attempted", "failed", "memory_peak_bytes"}

    if trace:
        run.reduced = trace_lib.reduce_logdir(run.trace_dir, run.device_prefix)
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    run.values["compile_s"] = run.counters.backend_compile_s
    run.values["programs_compiled"] = run.counters.compiled

    if trace:
        names = cell["per_layer"]
        declared = {m["name"]: m for m in manifest["per_layer"]}
    else:
        declared = {
            m["name"]: m
            for m in manifest["end_to_end"]
            if "workloads" not in m or workload in m["workloads"]
        }
        names = list(declared)
    metrics = {}
    for name in names:
        if trace:
            spec = load_json(roots, "metrics", name)
            reader = load_module(roots, "metrics", spec["reader"])
            value = reader.read(run, spec.get("params", {}))
            unit = spec["unit"]
        else:
            value, unit = run.values.get(name), declared[name]["unit"]
        if value is None:
            continue  # a reader that finds nothing to read returns nothing
        if not math.isfinite(value):
            raise ValueError(f"metric {name} is {value}")
        metrics[name] = {"value": value, "unit": unit}

    correct = all(c["ok"] for c in run.checks) and bool(run.checks)
    result = {
        "correct": correct,
        "attempted": outcome["attempted"],
        "failed": outcome["failed"],
        "metrics": metrics,
        "device": device_report(run, outcome["memory_peak_bytes"]),
    }
    if trace:
        result["breakdown"] = trace_lib.breakdown(run.reduced)
    result["checks"] = run.checks
    return result


def main(argv, *, t0: float) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), t0=t0
    )
    log(f"run took {time.monotonic() - t0:.1f} s in all")
    del result["checks"]  # printed above; the last line holds the contract's keys
    print(json.dumps(result), flush=True)
    return 0
