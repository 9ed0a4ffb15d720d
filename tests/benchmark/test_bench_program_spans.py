"""The readers of what the program records about itself
(``benchmark/metrics/program_spans.py``, ``program_share.py``) away from
a whole rehearsal: the shared clock (a profile captured on the CPU holds
the engine's and the trainer's spans as host events, inside the driver's
window), a program that records nothing, a ring that may have wrapped,
and the share of the device that named programs took."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import run_program_spans
from benchmark.harness import core, trace as trace_lib
from odh_kubeflow_tpu.utils import tracing

ROOTS = (core.BENCH_DIR,)


@pytest.fixture
def collector():
    c = tracing.SpanCollector()
    old = tracing.set_collector(c)
    yield c
    tracing.set_collector(old)


def _reader(name):
    return core.load_module(ROOTS, "metrics", name)


def _run(**values):
    """As much of a ``core.Run`` as the readers touch: set-up ended at
    t0 + 1 + 2 = 3.0 on the monotonic clock, the window lasts 10 s."""
    return types.SimpleNamespace(
        t0=0.0, seconds=10.0, reduced=None,
        values={"setup_s": 1.0, "runtime_start_s": 2.0, **values},
    )


def _record(name, start, duration, trace_id="t", span_id=None, parent=""):
    tracing.record_span(tracing.SpanRecord(
        trace_id=trace_id, span_id=span_id or tracing.new_span_id(),
        parent_span_id=parent, name=name, start=start, duration=duration,
        start_mono=start,
    ))


def test_a_cpu_profile_holds_the_programs_spans_inside_the_window(
    tmp_path, collector
):
    from jax.profiler import ProfileData, TraceAnnotation

    from odh_kubeflow_tpu.models import LlamaConfig, LoraConfig, init_params
    from odh_kubeflow_tpu.models.engine import DecodeEngine
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
    from odh_kubeflow_tpu.train import TrainConfig, Trainer

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg=cfg, dtype=jnp.float32)
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=64, chunk=4, prompt_buckets=(16,),
        cache_dtype=jnp.float32,
    )
    trainer = Trainer(
        cfg, TrainConfig(warmup_steps=1, total_steps=20),
        lora_cfg=LoraConfig(rank=4),
        mesh=build_mesh(MeshConfig(), jax.devices()[:1]),
    )
    batch = trainer.make_fake_batch(2, 16)
    try:
        engine.submit([5, 9, 13], max_tokens=3).result(timeout=120)  # warm
        trainer.train_step(batch)
        before = collector.recorded_total
        jax.profiler.start_trace(str(tmp_path))
        with TraceAnnotation("bench.window"):
            engine.submit([5, 9, 13], max_tokens=9).result(timeout=120)
            trainer.train_step(batch)
        jax.profiler.stop_trace()
    finally:
        engine.stop()
    profile = ProfileData.from_file(trace_lib.find_xplane(str(tmp_path)))
    events = {}
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("bench.", "engine.", "trainer.")):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    (window,) = events["bench.window"]
    inside = lambda iv: window[0] <= iv[0] and iv[1] <= window[1]  # noqa: E731
    for name in ("engine.turn", "engine.admit", "engine.dispatch",
                 "engine.fetch", "engine.emit", "trainer.step",
                 "trainer.dispatch"):
        assert any(inside(iv) for iv in events.get(name, [])), name
    # one clock: every fetch on the profiler's host plane lies inside a
    # turn there, and is the span the ring holds (same length, to the
    # cost of entering the two)
    turns = events["engine.turn"]
    for fs, fe in events["engine.fetch"]:
        assert any(ts <= fs and fe <= te for ts, te in turns)
    ring = [
        s for s in collector.spans_named("engine.fetch")
    ][-len(events["engine.fetch"]):]
    assert collector.recorded_total > before
    for (fs, fe), span in zip(sorted(events["engine.fetch"]), ring):
        assert abs((fe - fs) / 1e9 - span.duration) < 2e-3


def test_span_readers_return_nothing_for_a_program_without_spans(collector):
    spans = _reader("program_spans")
    run = _run(steps=4, ttft_ms=[], late_ms=[])
    for params in (
        {"what": "request_phase", "span": "engine.request.queued", "stat": 50},
        {"what": "request_phase", "span": "engine.request.first_token",
         "stat": 50, "ttft_tolerance_ms": 20},
        {"what": "turn_max", "tiling_tolerance_ms": 20},
        {"what": "host_share"},
        {"what": "step_host", "stat": 50},
    ):
        assert spans.read(run, params) is None, params
    # the parent of the PR that added the spans: no such read at all
    old = tracing.set_collector(object())
    try:
        assert spans.read(run, {"what": "host_share"}) is None
    finally:
        tracing.set_collector(old)


def test_span_readers_bound_themselves_to_the_window(collector):
    spans = _reader("program_spans")
    # the driver's own stamps for the two requests of the window: due to
    # first token 503 and 906 ms, sent 2 and 4 ms after they were due
    run = _run(steps=2, ttft_ms=[503.0, 906.0], late_ms=[2.0, 4.0])
    # window [3, 13]: a turn of 2 s that straddles its opening with 1 s
    # of fetch inside, one whole turn of 1 s with 0.75 s of fetch, idle
    _record("engine.fetch", 2.5, 1.5, "a", parent="turn-a")
    _record("engine.turn", 2.0, 2.0, "a", span_id="turn-a")
    _record("engine.idle", 4.0, 1.0, "i")
    _record("engine.admit", 5.0, 0.25, "b", parent="turn-b")
    _record("engine.fetch", 5.25, 0.75, "b", parent="turn-b")
    _record("engine.turn", 5.0, 1.0, "b", span_id="turn-b")
    # in turns 1 + 1 = 2 s of the window, of which fetch 1 + 0.75
    assert spans.read(run, {"what": "host_share"}) == pytest.approx(2.5)
    # the longest turn BEGUN in the window
    assert spans.read(
        run, {"what": "turn_max", "tiling_tolerance_ms": 20}
    ) == pytest.approx(1000.0)
    # requests: one submitted before the window (the warm-up), two in it
    for tid, t0, q, f in (("w", 1.0, 0.1, 0.2), ("x", 4.0, 0.1, 0.4),
                          ("y", 6.0, 0.3, 0.6)):
        _record("engine.request.queued", t0, q, tid, parent="r" + tid)
        _record("engine.request.first_token", t0 + q, f, tid, parent="r" + tid)
        _record("engine.request.decode", t0 + q + f, 1.0, tid, parent="r" + tid)
        _record("engine.request", t0, q + f + 1.0, tid, span_id="r" + tid)
    # one that never reached a token is left out of both percentiles
    _record("engine.request.queued", 7.0, 9.0, "z", parent="rz")
    _record("engine.request", 7.0, 9.0, "z", span_id="rz")
    read = lambda span, stat: spans.read(  # noqa: E731
        run, {"what": "request_phase", "span": span, "stat": stat,
              "ttft_tolerance_ms": 20}
    )
    assert read("engine.request.queued", 50) == pytest.approx(200.0)
    assert read("engine.request.queued", 100) == pytest.approx(300.0)
    assert read("engine.request.first_token", 50) == pytest.approx(500.0)
    # the last `steps` trainer.step spans
    for i, d in enumerate((9.0, 0.002, 0.004)):
        _record("trainer.step", 20.0 + i, d, f"s{i}")
    assert spans.read(run, {"what": "step_host", "stat": 50}) == pytest.approx(3.0)


def test_span_readers_refuse_a_ring_that_may_have_wrapped():
    c = tracing.SpanCollector(capacity=8)
    old = tracing.set_collector(c)
    try:
        spans = _reader("program_spans")
        run = _run(steps=2)
        for i in range(7):
            _record("engine.turn", 4.0 + i, 0.5, f"t{i}")
        turn_max = {"what": "turn_max", "tiling_tolerance_ms": 1000}
        assert spans.read(run, turn_max) == pytest.approx(500.0)
        _record("engine.turn", 12.0, 0.5, "t7")  # 8 since the window opened
        with pytest.raises(RuntimeError, match="may have wrapped"):
            spans.read(run, turn_max)
    finally:
        tracing.set_collector(old)


def _two_requests():
    """Submitted at 4.0 and 6.0 in the window [3, 13]: queued + first
    token 500 and 900 ms."""
    for tid, t0, q, f in (("x", 4.0, 0.1, 0.4), ("y", 6.0, 0.3, 0.6)):
        _record("engine.request.queued", t0, q, tid, parent="r" + tid)
        _record("engine.request.first_token", t0 + q, f, tid, parent="r" + tid)
        _record("engine.request.decode", t0 + q + f, 1.0, tid, parent="r" + tid)
        _record("engine.request", t0, q + f + 1.0, tid, span_id="r" + tid)


@pytest.mark.parametrize("ttft_ms, late_ms, error", [
    # the client stamps a few milliseconds after the engine: read
    ([503.0, 906.0], [2.0, 4.0], None),
    # the driver saw three requests due in the window, the ring two
    ([503.0, 906.0, 700.0], [2.0, 4.0, 1.0], "not the same requests"),
    # as many, but other requests: a turn apart, not milliseconds
    ([803.0, 906.0], [2.0, 4.0], "are not the driver's"),
])
def test_first_token_split_is_held_to_the_drivers_own_stamps(
    collector, ttft_ms, late_ms, error
):
    spans = _reader("program_spans")
    spec = core.load_json(ROOTS, "metrics", "first_token_wait_ms_p50")
    assert spec["params"]["ttft_tolerance_ms"] == 20
    run = _run(ttft_ms=ttft_ms, late_ms=late_ms)
    _two_requests()
    if error is None:
        assert spans.read(run, spec["params"]) == pytest.approx(500.0)
    else:
        with pytest.raises(RuntimeError, match=error):
            spans.read(run, spec["params"])


@pytest.mark.parametrize("traced, error", [
    ((7.05, 13.0015), None),    # the chip: the tracer wakes as the window closes
    ((13.6, 14.1), None),       # the CPU rehearsal: start_trace outlasted the
                                # traced seconds, the tracer slept its least
    ((11.0, 17.0), "closes"),   # the driver worked 4 s between ready and open
    ((6.0, 12.0), "closes"),    # the rebuilt window ends after the run's
    ((11.5, 12.0), "closes"),
])
def test_span_window_is_held_to_where_the_tracer_saw_it_close(
    collector, traced, error
):
    spans = _reader("program_spans")
    run = _run(traced=traced)
    _record("engine.fetch", 5.25, 0.75, "b", parent="turn-b")
    _record("engine.turn", 5.0, 1.0, "b", span_id="turn-b")
    if error is None:
        assert spans.read(run, {"what": "host_share"}) == pytest.approx(2.5)
    else:
        with pytest.raises(RuntimeError, match=error):
            spans.read(run, {"what": "host_share"})


def test_turn_max_refuses_phases_that_do_not_tile_the_turn(collector):
    spans = _reader("program_spans")
    spec = core.load_json(ROOTS, "metrics", "engine_turn_ms_max")
    assert spec["params"]["tiling_tolerance_ms"] == 20
    _record("engine.admit", 5.0, 0.25, "b", parent="turn-b")
    _record("engine.fetch", 5.25, 0.74, "b", parent="turn-b")
    _record("engine.turn", 5.0, 1.0, "b", span_id="turn-b")  # 10 ms bare
    assert spans.read(_run(), spec["params"]) == pytest.approx(1000.0)
    _record("engine.fetch", 7.0, 0.5, "c", parent="turn-c")
    _record("engine.turn", 7.0, 0.53, "c", span_id="turn-c")  # 30 ms bare
    with pytest.raises(RuntimeError, match="no longer tile"):
        spans.read(_run(), spec["params"])


@pytest.mark.parametrize("modules, want", [
    # two prefill programs and the decode chunk in a window of 6 s
    ({"jit__prefill_1024(1)": (0.9, 7.0), "jit__prefill_64(2)": (0.3, 2.0),
      "jit__decode_chunk(3)": (4.5, 16.0)}, 20.0),
    # every program named and no prefill among them: a share of zero
    ({"jit__decode_chunk(3)": (4.5, 16.0)}, 0.0),
    # the engine before its programs were named: nothing can be told
    ({"jit__unknown(1)": (0.9, 7.0), "jit__decode_chunk(3)": (4.5, 16.0)}, None),
    # the CPU rehearsal: no XLA Modules line
    ({}, None),
])
def test_prefill_device_share_reads_named_programs(modules, want):
    run = _run()
    run.reduced = {"modules": modules, "window_s": 6.0}
    spec = core.load_json(ROOTS, "metrics", "prefill_device_share.steady")
    got = _reader(spec["reader"]).read(run, spec["params"])
    assert got == (want if want is None else pytest.approx(want))


def test_the_prefill_pattern_is_the_engines_documented_tag():
    from odh_kubeflow_tpu.models import engine

    for cell in ("steady", "saturated"):
        spec = core.load_json(ROOTS, "metrics", f"prefill_device_share.{cell}")
        assert spec["params"]["patterns"] == [engine.PREFILL_PROGRAM_TAG]
    decode = core.load_json(ROOTS, "metrics", "decode_hbm_roofline.steady")
    assert decode["params"]["patterns"] == [engine.DECODE_PROGRAM]


# ---- the readers inside a whole run, through an overlay of the cells -------
#
# A cell's file is not this PR's to edit, so no cell lists these metrics
# yet: ``benchmark/run_program_spans.py`` lays copies of the cells' files
# with the names of ``benchmark/program_spans.json`` appended in front of
# the harness's search path. The tiny cells rehearse that on the CPU.

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
TINY_EXTRA = {
    "tiny-train": ["train_host_ms_p50"],
    "tiny-serve": [
        "queue_wait_ms_p50", "queue_wait_ms_p95", "first_token_wait_ms_p50",
        "engine_turn_ms_max", "engine_host_share.steady",
    ],
    "tiny-serve-saturated": ["engine_host_share.saturated"],
}


@pytest.mark.parametrize("workload", sorted(TINY_EXTRA))
def test_rehearsal_through_the_overlay_reads_the_programs_spans(
    workload, tmp_path, collector
):
    roots = (TINY, core.BENCH_DIR)
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    over = run_program_spans.overlay(roots, TINY_EXTRA, str(tmp_path))
    result = core.run_cell(
        workload, 2**31 + 13, 1.0, True, t0=time.monotonic(),
        roots=(over,) + roots, manifest=manifest, rehearsal=True,
    )
    assert result["correct"] is True, result["checks"]
    cell = core.load_json(roots, "cells", workload)
    # every metric the cell listed, and the program's own after them
    assert list(result["metrics"]) == cell["per_layer"] + TINY_EXTRA[workload]
    for name in TINY_EXTRA[workload]:
        assert result["metrics"][name]["value"] >= 0
    # the file the benchmark has is as it was
    assert core.load_json(roots, "cells", workload) == cell


def test_the_overlays_list_names_cells_and_metrics_that_exist():
    manifest = core.load_manifest()
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    with open(os.path.join(core.BENCH_DIR, "program_spans.json")) as f:
        extra = json.load(f)
    assert set(extra) <= {w["name"] for w in manifest["workloads"]}
    declared = {m["name"] for m in manifest["per_layer"]}
    for cell_name, names in extra.items():
        cell = core.load_json(ROOTS, "cells", cell_name)
        for name in names:
            assert name not in cell["per_layer"] and name not in declared
            spec = core.load_json(ROOTS, "metrics", name)
            assert spec["name"] == name
            assert set(spec) == {"name", "unit", "better", "layer", "moves",
                                 "source", "reader", "params"}
            assert hasattr(core.load_module(ROOTS, "metrics", spec["reader"]), "read")
            moved = e2e[spec["moves"]]
            assert cell_name in moved.get("workloads", [cell_name])
    assert sorted(n for names in extra.values() for n in names) == sorted(
        f[:-5] for f in os.listdir(os.path.join(core.BENCH_DIR, "metrics"))
        if f.endswith(".json") and f[:-5] not in declared
    )
