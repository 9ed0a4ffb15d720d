"""The reader of the engine's turn totals
(``benchmark/metrics/turn_totals.py``) and the eight metric files that
use it (``benchmark/turn_totals/``): each file is what a ``BENCHMARK.json``
entry will be; a window's difference is what the engine's own counters
say at both ends; a ring that may have wrapped and a ledger whose states
do not add up are refused; a quotient over nothing returns nothing; and
two tiny cells rehearse the whole path through
``benchmark/run_turn_totals.py``'s overlay."""

import json
import os
import time
import types

import jax
import jax.numpy as jnp
import pytest

from benchmark import run_program_spans, run_turn_totals
from benchmark.harness import core
from odh_kubeflow_tpu.utils import tracing

ROOTS = (run_turn_totals.TOTALS_DIR, core.BENCH_DIR)
TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
SATURATED = [
    "mistral7b-chat-saturated", "commandaplus-ep8-longmix-saturated",
    "granite4h-stage-longmix-saturated",
    "qwen3next-ep2-stage-longmix-saturated",
]
STEPS = [
    f"slot_steps_{s}"
    for s in ("live", "ended", "admitting", "free_lane", "free_no_work")
]
# name: (better, the totals above the line, below it, 100 less the quotient)
METRICS = {
    "slots_ended_share.saturated": ("lower", ["slot_steps_ended"], STEPS, False),
    "slots_admitting_share.saturated": (
        "lower", ["slot_steps_admitting"], STEPS, False),
    "slots_free_lane_share.saturated": (
        "lower", ["slot_steps_free_lane"], STEPS, False),
    "slots_free_no_work_share.saturated": (
        "lower", ["slot_steps_free_no_work"], STEPS, False),
    "lane_busy_share.saturated": ("lower", ["parts"], ["turn"], False),
    "wait_lane_share.saturated": (
        "lower", ["wait_lane_s"], ["wait_lane_s", "wait_slot_s"], False),
    "parts_ahead_share.saturated": ("higher", ["parts_ahead"], ["parts"], False),
    "prefill_pad_share.saturated": (
        "lower", ["prefill_tokens"], ["prefill_positions"], True),
}


@pytest.fixture
def collector():
    c = tracing.SpanCollector()
    old = tracing.set_collector(c)
    yield c
    tracing.set_collector(old)


def _reader():
    return core.load_module(ROOTS, "metrics", "turn_totals")


def _params(name):
    return core.load_json(ROOTS, "metrics", name)["params"]


def _run(lo=3.0, seconds=10.0, **values):
    """As much of a ``core.Run`` as the reader touches: set-up ended at
    ``lo`` on the monotonic clock."""
    return types.SimpleNamespace(
        t0=0.0, seconds=seconds, roots=ROOTS,
        values={"setup_s": lo, "runtime_start_s": 0.0, "n_slots": 4, **values},
    )


def _turn(end, totals, chunks=1, duration=0.5):
    """An ``engine.turn`` that closed at ``end`` with ``totals`` on it,
    over ``chunks`` ``engine.dispatch`` children."""
    span_id = tracing.new_span_id()
    for _ in range(chunks):
        tracing.record_span(tracing.SpanRecord(
            trace_id=span_id, span_id=tracing.new_span_id(),
            parent_span_id=span_id, name="engine.dispatch",
            start=end - duration, duration=0.001, start_mono=end - duration,
        ))
    tracing.record_span(tracing.SpanRecord(
        trace_id=span_id, span_id=span_id, parent_span_id="",
        name="engine.turn", start=end - duration, duration=duration,
        start_mono=end - duration, attrs=dict(totals),
    ))


def _totals(turn, live, ended=0, admitting=0, free_lane=0, free_no_work=0,
            **more):
    return {
        "turn": turn, "slot_steps_live": live, "slot_steps_ended": ended,
        "slot_steps_admitting": admitting, "slot_steps_free_lane": free_lane,
        "slot_steps_free_no_work": free_no_work, "wait_lane_s": 0.0,
        "wait_slot_s": 0.0, "held": 0, "parts": 0, "parts_ahead": 0,
        "prefill_tokens": 0, "prefill_positions": 0, **more,
    }


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_metrics_file_is_the_manifest_entry_it_will_be(name):
    better, num, den, complement = METRICS[name]
    spec = core.load_json(ROOTS, "metrics", name)
    assert spec == {
        "name": name, "unit": "%", "better": better,
        "layer": "Serving (models/engine.py)", "moves": "serve_tokens_per_s",
        "source": "program_span", "reader": "turn_totals",
        "params": {"num": num, "den": den, **(
            {"complement": True} if complement else {}
        )},
    }
    # the layer's name is the accepted one, letter for letter, and the
    # metric it moves is the four saturated cells' own
    manifest = core.load_manifest()
    assert spec["layer"] in {m["layer"] for m in manifest["per_layer"]}
    moved = next(m for m in manifest["end_to_end"] if m["name"] == spec["moves"])
    assert set(SATURATED) == set(moved["workloads"])
    # in front of the benchmark's own directory it shadows no file there
    assert not os.path.exists(
        os.path.join(core.BENCH_DIR, "metrics", name + ".json")
    )
    assert hasattr(_reader(), "read")


def test_the_cells_list_names_the_saturated_cells_and_files_that_exist():
    with open(os.path.join(run_turn_totals.TOTALS_DIR, "cells.json")) as f:
        cells = json.load(f)
    assert sorted(cells) == sorted(SATURATED)
    manifest = core.load_manifest()
    declared = {m["name"] for m in manifest["per_layer"]}
    for cell, names in cells.items():
        assert sorted(names) == sorted(METRICS)
        listed = core.load_json((core.BENCH_DIR,), "cells", cell)["per_layer"]
        assert not set(names) & (set(listed) | declared)
    assert sorted(
        f[:-5] for f in os.listdir(
            os.path.join(run_turn_totals.TOTALS_DIR, "metrics"))
    ) == sorted(METRICS)
    # the entry point reads PR 24's list too, and keeps both
    with open(os.path.join(core.BENCH_DIR, "program_spans.json")) as f:
        spans = json.load(f)
    extra = run_turn_totals.extra_metrics()
    for cell in SATURATED:
        assert extra[cell] == spans.get(cell, []) + cells[cell]
    assert extra["mistral7b-chat-steady"] == spans["mistral7b-chat-steady"]


def test_a_windows_difference_is_the_engines_own_counters(collector):
    """A tiny engine on the CPU, idle at both ends of a window: the
    reader's difference of the turns' totals is the difference of the
    counters read from the engine there."""
    from odh_kubeflow_tpu.models import LlamaConfig, init_params
    from odh_kubeflow_tpu.models.engine import DecodeEngine

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg=cfg, dtype=jnp.float32)
    engine = DecodeEngine(
        params, cfg, n_slots=3, max_len=160, chunk=4, prompt_buckets=(8,),
        prefill_chunk=8, cache_dtype=jnp.float32,
    )

    def counters():
        totals = {**engine._turn_totals(), "turn": engine.turns}
        del totals["held"]  # a depth, not a count
        return totals

    try:
        # the warm-up: every program, and turns from before the window
        engine.submit(list(range(1, 21)), max_tokens=2).result(timeout=300)
        engine.submit([5, 9, 13], max_tokens=6).result(timeout=300)
        time.sleep(0.2)  # the loop idles: its last turn has closed
        c0, lo = counters(), time.monotonic()
        running = engine.submit([3, 5, 8], max_tokens=90)
        longs = [
            engine.submit(list(range(2, 2 + n)), max_tokens=3) for n in (50, 40)
        ]
        shorts = [engine.submit([7, 7, 2 + i], max_tokens=5) for i in range(4)]
        for r in longs + shorts + [running]:
            r.result(timeout=300)
        time.sleep(0.2)
        c1, hi = counters(), time.monotonic()
    finally:
        engine.stop()
    run = _run(lo=lo, seconds=hi - lo, n_slots=3, decode_chunk=4,
               slot_occupancy=50.0)
    reader = _reader()
    d = {k: c1[k] - c0[k] for k in c0}
    assert d["parts"] > 8 and d["slot_steps_free_lane"] > 0
    assert d["wait_lane_s"] > 0 and d["wait_slot_s"] > 0
    for name, (_, num, den, complement) in METRICS.items():
        want = 100.0 * sum(d[k] for k in num) / sum(d[k] for k in den)
        got = reader.read(run, _params(name))
        assert got == pytest.approx(100.0 - want if complement else want), name
    # the window's difference itself, total by total
    assert {k: run.values["turn_totals.window"][k] for k in d} == d
    shares = [reader.read(run, _params(n)) for n in sorted(METRICS)[3:7]]
    live = 100.0 * d["slot_steps_live"] / sum(d[k] for k in STEPS)
    assert sum(shares) + live == pytest.approx(100.0)


def test_a_window_with_no_turn_before_it_leaves_its_first_turn_out(collector):
    reader = _reader()
    _turn(4.0, _totals(1, live=10, free_no_work=6))
    _turn(5.0, _totals(2, live=20, ended=2, free_no_work=10))
    _turn(6.0, _totals(3, live=34, ended=2, free_no_work=12))
    # the window [3, 13] holds all three: the first is the base
    run = _run()
    assert reader.read(
        run, _params("slots_free_no_work_share.saturated")
    ) == pytest.approx(100.0 * 6 / 32)
    assert reader.read(
        run, _params("slots_ended_share.saturated")
    ) == pytest.approx(100.0 * 2 / 32)
    # with a turn from before the window, that one is
    _turn(2.5, _totals(0, live=0))
    run = _run()
    assert reader.read(
        run, _params("slots_free_no_work_share.saturated")
    ) == pytest.approx(100.0 * 12 / 48)


@pytest.mark.parametrize("name, totals, want", [
    # no part at all: the share that went ahead has nothing under the line
    ("parts_ahead_share.saturated", {}, None),
    ("lane_busy_share.saturated", {}, 0.0),
    # nobody waited
    ("wait_lane_share.saturated", {}, None),
    ("wait_lane_share.saturated", {"wait_slot_s": 3.0}, 0.0),
    ("wait_lane_share.saturated", {"wait_lane_s": 1.0, "wait_slot_s": 3.0}, 25.0),
    # no prefill ran
    ("prefill_pad_share.saturated", {}, None),
    ("prefill_pad_share.saturated",
     {"prefill_tokens": 60, "prefill_positions": 80}, 25.0),
    ("parts_ahead_share.saturated", {"parts": 4, "parts_ahead": 3}, 75.0),
    ("lane_busy_share.saturated", {"parts": 1}, 50.0),
])
def test_a_quotient_over_nothing_returns_nothing(collector, name, totals, want):
    _turn(2.5, _totals(0, live=0))
    _turn(5.0, _totals(1, live=16))
    _turn(6.0, _totals(2, live=32, **totals))
    got = _reader().read(_run(), _params(name))
    assert got == (want if want is None else pytest.approx(want))


def test_turns_without_totals_give_nothing_to_read(collector):
    """The parent commit under these files, or an engine beside a draft."""
    tracing.record_span(tracing.SpanRecord(
        trace_id="t", span_id="t", parent_span_id="", name="engine.turn",
        start=5.0, duration=0.5, start_mono=5.0, attrs={"turn": 1},
    ))
    reader = _reader()
    for name in METRICS:
        assert reader.read(_run(), _params(name)) is None
    old = tracing.set_collector(object())  # a collector with no such read
    try:
        assert reader.read(_run(), _params(name)) is None
    finally:
        tracing.set_collector(old)


@pytest.mark.parametrize("broken, values", [
    # 31 slot-steps over one chunk of four slots
    ({"live": 31}, {}),
    # whole chunks, but not of the driver's 8 steps
    ({"live": 16}, {"decode_chunk": 8}),
    # two chunks dispatched, one chunk's slot-steps counted
    ({"live": 32, "chunks": 2}, {"decode_chunk": 8}),
])
def test_a_ledger_whose_states_do_not_add_up_is_refused(collector, broken, values):
    chunks = broken.pop("chunks", 1)
    _turn(2.5, _totals(0, live=0))
    _turn(5.0, _totals(1, **broken), chunks=chunks)
    with pytest.raises(RuntimeError, match="not a whole chunk"):
        _reader().read(_run(**values), _params("slots_ended_share.saturated"))


def test_a_ring_that_may_have_wrapped_is_refused():
    c = tracing.SpanCollector(capacity=8)
    old = tracing.set_collector(c)
    try:
        reader = _reader()
        _turn(2.5, _totals(0, live=0))
        for i in range(2):  # a turn is two spans: four since the window opened
            _turn(5.0 + i, _totals(1 + i, live=16 * (1 + i)))
        assert reader.read(
            _run(), _params("slots_ended_share.saturated")
        ) == pytest.approx(0.0)
        for i in range(2, 4):  # eight: the ring's whole room
            _turn(5.0 + i, _totals(1 + i, live=16 * (1 + i)))
        with pytest.raises(RuntimeError, match="may have wrapped"):
            reader.read(_run(), _params("slots_ended_share.saturated"))
    finally:
        tracing.set_collector(old)


def test_a_window_out_of_place_is_refused(collector):
    _turn(2.5, _totals(0, live=0))
    _turn(5.0, _totals(1, live=16))
    # the driver worked 4 s between ready and open
    with pytest.raises(RuntimeError, match="closes"):
        _reader().read(
            _run(traced=(11.0, 17.0)), _params("slots_ended_share.saturated")
        )


# ---- two tiny cells through the entry point's overlay -----------------------

TINY_CELLS = {
    # (the manifest beside the cell, the metrics that find nothing there)
    "tiny-serve-saturated": ("BENCHMARK.json", {"parts_ahead_share.saturated"}),
    "tiny-share-saturated": ("BENCHMARK.share.json", set()),
}


@pytest.mark.parametrize("workload", sorted(TINY_CELLS))
def test_rehearsal_through_the_overlay_reads_the_turn_totals(
    workload, tmp_path, collector
):
    roots = (TINY, core.BENCH_DIR)
    manifest_file, silent = TINY_CELLS[workload]
    with open(os.path.join(TINY, manifest_file)) as f:
        manifest = json.load(f)
    over = run_program_spans.overlay(
        roots, {workload: sorted(METRICS)}, str(tmp_path)
    )
    result = core.run_cell(
        workload, 2**31 + 17, 1.0, True, t0=time.monotonic(),
        roots=(over, run_turn_totals.TOTALS_DIR) + roots, manifest=manifest,
        rehearsal=True,
    )
    assert result["correct"] is True, result["checks"]
    cell = core.load_json(roots, "cells", workload)
    got = {n: m["value"] for n, m in result["metrics"].items() if n in METRICS}
    # a window above capacity fills its slots: nobody waits for the lane
    # with a slot free for long, somebody waits for a slot
    silent = silent | ({"wait_lane_share.saturated"} - set(got))
    assert set(got) == set(METRICS) - silent
    # what the cell lists comes first, in its order (a roofline share finds
    # nothing in the CPU's trace), and these after it
    listed = [n for n in cell["per_layer"] if n in result["metrics"]]
    assert list(result["metrics"]) == listed + [n for n in sorted(METRICS) if n in got]
    assert all(0.0 <= v <= 100.0 for v in got.values()), got
    states = sum(got[n] for n in sorted(METRICS)[3:7])
    assert 0.0 < states < 100.0  # the rest of the slot-steps were live
    if workload == "tiny-serve-saturated":
        # no admission in parts: nothing admitting, no lane to wait for
        assert got["slots_admitting_share.saturated"] == 0.0
        assert got["slots_free_lane_share.saturated"] == 0.0
        assert got["lane_busy_share.saturated"] == 0.0
    else:
        assert got["slots_admitting_share.saturated"] > 0.0
        assert got["lane_busy_share.saturated"] > 0.0
        assert got["parts_ahead_share.saturated"] > 0.0
        assert 0.0 < got["prefill_pad_share.saturated"] < 100.0
    # the file the benchmark has is as it was
    assert core.load_json(roots, "cells", workload) == cell
