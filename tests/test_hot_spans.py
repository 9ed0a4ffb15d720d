"""Spans of the hot path (models/engine.py, train/trainer.py) in the
process's span ring: one ``engine.request`` trace per request whose
phases abut, ``engine.turn`` traces whose phases tile the turn, a
bounded number of spans (none per token), tail-based keeping of a slow
or failed turn, the trainer's ``aot`` / ``lazy`` record, and the names
of the jitted programs."""

import threading
import time

import jax
import jax.numpy as jnp
import pytest

from odh_kubeflow_tpu.models import LlamaConfig, LoraConfig, init_params
from odh_kubeflow_tpu.models import engine as engine_lib
from odh_kubeflow_tpu.models.engine import DecodeEngine
from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh
from odh_kubeflow_tpu.train import TrainConfig, Trainer
from odh_kubeflow_tpu.utils import tracing
from odh_kubeflow_tpu.utils.profiling import hot_span

EPS = 1e-9
PHASES = ("engine.admit", "engine.dispatch", "engine.fetch", "engine.emit")
KINDS = (
    "plain", "chunked", "prefix_hit", "max_tokens_1", "cancelled_queued",
    "parts_beside_decode",
)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    return cfg, init_params(jax.random.key(0), cfg=cfg, dtype=jnp.float32)


@pytest.fixture
def collector():
    c = tracing.SpanCollector()
    old = tracing.set_collector(c)
    yield c
    tracing.set_collector(old)


def _engine(model, **kw):
    cfg, params = model
    kw = {
        "n_slots": 2, "max_len": 128, "chunk": 4,
        "prompt_buckets": (16, 64), "cache_dtype": jnp.float32, **kw,
    }
    return DecodeEngine(params, cfg, **kw)


@pytest.fixture(scope="module")
def served(model):
    """One tiny engine serves one request of each kind into a collector
    of its own; the engine is stopped before anything is read."""
    c = tracing.SpanCollector()
    old = tracing.set_collector(c)
    engine = _engine(
        model, prefill_chunk=32, prefix_cache_entries=4, prefix_buckets=(8,),
    )
    # the first turn of each program compiles inside the loop: on a busy
    # machine that alone can pass the engine's 2 s; this case is about
    # healthy turns
    c.set_threshold("engine.turn", 60.0)
    reqs = {}
    try:
        shared = list(range(20, 30))
        reqs["plain"] = engine.submit([5, 9, 13], max_tokens=6)
        reqs["plain"].result(timeout=120)
        reqs["chunked"] = engine.submit(list(range(3, 48)), max_tokens=5)
        reqs["chunked"].result(timeout=120)
        engine.submit(shared + [1, 2], max_tokens=3).result(timeout=120)
        reqs["prefix_hit"] = engine.submit(shared + [3, 4, 5], max_tokens=4)
        reqs["prefix_hit"].result(timeout=120)
        reqs["max_tokens_1"] = engine.submit([7, 7, 7, 7], max_tokens=1)
        reqs["max_tokens_1"].result(timeout=120)
        # both slots busy: the next request waits in the queue, and is
        # cancelled there
        busy = [engine.submit([11 + i] * 5, max_tokens=40) for i in range(2)]
        while sum(r is not None for r in engine._slot_req) < 2:
            time.sleep(0.005)
        reqs["cancelled_queued"] = engine.submit([2, 4, 6], max_tokens=8)
        reqs["cancelled_queued"].cancel()
        for r in busy:
            r.result(timeout=120)
        assert reqs["cancelled_queued"].done.wait(timeout=120)
        # a prompt admitted in three parts (32 + 32 + 6) while another
        # stream decodes (26 chunks): each part goes out behind a chunk
        running = engine.submit([9, 8, 7], max_tokens=100)
        reqs["parts_beside_decode"] = engine.submit(
            list(range(40, 110)), max_tokens=5
        )
        reqs["parts_beside_decode"].result(timeout=120)
        running.result(timeout=120)
        n_requests = 10
    finally:
        engine.stop()
        tracing.set_collector(old)
    return c, engine, reqs, n_requests


def _by_name(spans):
    out = {}
    for s in spans:
        assert s.name not in out, f"two {s.name} spans in one trace"
        out[s.name] = s
    return out


@pytest.mark.parametrize("kind", KINDS)
def test_request_trace_has_abutting_phases(served, kind):
    c, _engine_, reqs, _n = served
    req = reqs[kind]
    spans = _by_name(c.trace(req.request_id))
    root = spans.pop("engine.request")
    assert root.parent_span_id == "" and root.trace_id == req.request_id
    assert all(s.parent_span_id == root.span_id for s in spans.values())
    assert root.start_mono == req.submit_t
    assert abs(root.duration - (req.finish_t - req.submit_t)) < EPS
    assert root.attrs["prompt_len"] == len(req.prompt)
    assert root.attrs["max_tokens"] == req.max_tokens
    queued = spans["engine.request.queued"]
    assert queued.start_mono == req.submit_t
    if kind == "cancelled_queued":
        # it never left the queue: one phase, cut at the end
        assert set(spans) == {"engine.request.queued"}
        assert root.attrs["outcome"] == "cancelled" and root.status == "ok"
        assert abs(queued.duration - root.duration) < EPS
        assert not req.tokens and "slot" not in root.attrs
        return
    first, decode = (
        spans["engine.request.first_token"], spans["engine.request.decode"]
    )
    assert len(spans) == 3
    mono_end = lambda s: s.start_mono + s.duration  # noqa: E731
    assert abs(mono_end(queued) - first.start_mono) < EPS
    assert abs(mono_end(first) - decode.start_mono) < EPS
    assert abs(mono_end(decode) - mono_end(root)) < EPS
    assert first.start_mono == req.admit_t
    assert abs(
        queued.duration + first.duration - (req.times[0] - req.submit_t)
    ) < EPS
    assert root.attrs["outcome"] == "finished"
    assert root.attrs["tokens"] == len(req.tokens) == req.max_tokens
    assert root.attrs["prefix_hit"] is (kind == "prefix_hit")
    assert root.attrs["slot"] in (0, 1)
    # the last program that ran it: a final part of 13 (of 6) tokens
    # runs in the bucket of 16, not at a part's 32
    assert root.attrs["bucket"] == 16
    # the wall-clock start is the monotonic one, shifted
    assert abs(
        (first.start - root.start) - (first.start_mono - root.start_mono)
    ) < 1e-6


def _turns(c):
    turns = [s for s in c.spans_named("engine.turn")]
    children = {t.span_id: [] for t in turns}
    for s in c.spans_named("engine."):
        if s.parent_span_id in children:
            children[s.parent_span_id].append(s)
    return [(t, sorted(children[t.span_id], key=lambda s: s.start_mono))
            for t in turns]


# what the reader of these spans allows a turn to leave uncovered
# (benchmark/metrics/program_spans.py, ``tiling_tolerance_ms``)
TILING_TOLERANCE_S = 0.020


def _decoded(admitted=0, published=False, ahead=False):
    """The phases of a turn that decoded, in the loop's order: admit,
    the chunk's dispatch, then under the chunk the last chunk's tokens
    published (where one was settled) and the admission's next part
    dispatched ahead (where one is under way); for each request that
    has its first token coming, that token's fetch and emit; the
    chunk's fetch, and its tokens settled."""
    return [
        *PHASES[:2], *PHASES[3:] * published, *PHASES[:1] * ahead,
        *PHASES[2:] * admitted, *PHASES[2:],
    ]


def _parts_of(span):
    """The parts of an admission in parts that an admit span dispatched."""
    return [
        ev[2]["part"] for ev in span.events
        if ev[1] == "prefill" and ev[2]["part"] != "whole"
    ]


@pytest.mark.parametrize("case", ["part_ahead", "no_part", "first_tokens"])
def test_phases_of_a_turn_tile_it(served, case):
    """Every turn's phases come in the loop's order, do not overlap and
    cover the turn; by case, the turns that sent a part out behind
    their chunk, the decoding turns that did not, and the turns that
    fetched a first token ahead of their chunk."""
    c, engine, _reqs, _n = served
    turns = _turns(c)
    assert len(turns) == engine.turns > 10
    decoded = early = ahead_turns = seen = 0
    final_ahead = False  # the turn before sent a FINAL part out ahead
    for turn, kids in turns:
        assert turn.parent_span_id == "" and turn.status == "ok"
        names = [k.name for k in kids]
        first = [k for k in kids if k.attrs.get("first_tokens")]
        published = [k for k in kids if k.attrs.get("deferred")]
        ahead = [k for k in kids if k.attrs.get("ahead")]
        assert len(published) <= 1 and len(ahead) <= 1
        shape = _decoded(len(first) // 2, bool(published), bool(ahead))
        # a turn with nothing to decode: its admit phase, and what the
        # last chunk left to publish
        assert names in (shape, ["engine.admit"] + ["engine.emit"] * len(published))
        # the first tokens of the requests this turn's admit phase
        # brought to a slot (a part that is not the final one, or a
        # single token, brings none), and of a final part sent out
        # behind the last turn's chunk
        assert len(first) // 2 <= len(kids[0].events) + final_ahead
        at_first = 2 + len(published) + len(ahead)
        assert first == kids[at_first:at_first + len(first)]
        assert all(k.attrs == {"first_tokens": 1} for k in first)
        assert all(k.attrs == {"deferred": 1} for k in published)
        if ahead:
            # one part, and the top of the turn after it runs none
            (part,) = _parts_of(ahead[0])
            assert ahead[0].attrs == {"ahead": 1, "parts": 3}
            final_ahead = part == "final"
        else:
            final_ahead = False
        mine = {
            "part_ahead": bool(ahead),
            "no_part": len(names) > 2 and not ahead and not first,
            "first_tokens": bool(first),
        }[case]
        decoded += "engine.dispatch" in names
        early += len(first) // 2
        ahead_turns += bool(ahead)
        if not mine:
            continue
        seen += 1
        at = turn.start_mono
        for k in kids:
            assert k.trace_id == turn.trace_id
            assert k.start_mono >= at - EPS  # no overlap, in order
            at = k.start_mono + k.duration
        assert at <= turn.start_mono + turn.duration + EPS
        covered = sum(k.duration for k in kids)
        assert turn.duration - TILING_TOLERANCE_S <= covered <= (
            turn.duration + EPS
        )
    assert seen >= 3
    assert decoded * engine.chunk == engine.decode_steps
    # plain, chunked, the two that share a prefix, the two that keep the
    # slots busy, the two of the parts beside a decode: not the one of a
    # single token, not the one cancelled
    assert early == engine.first_tokens_early == 8
    # the three parts beside a decoding stream, and not the two parts of
    # the prompt that was admitted alone
    assert ahead_turns == engine.parts_ahead == 3 and engine.parts == 5


def test_span_count_is_bounded_by_turns_and_requests(served):
    c, engine, reqs, n_requests = served
    spans = c.spans_named("engine.")
    idle = [s for s in spans if s.name == "engine.idle"]
    assert all(s.parent_span_id == "" for s in idle)
    assert len(idle) <= n_requests + 1
    # a turn and its six phases (the last chunk's tokens published and a
    # part sent out ahead among them), a request and its three, and two
    # more phases in the turn that fetches the request's first token early
    assert len(spans) <= 7 * engine.turns + 6 * n_requests + len(idle)
    assert len(spans) == c.recorded_total  # nothing else wrote here
    # per token there is nothing: far more tokens than turns
    assert engine.tokens_emitted > 3 * engine.turns
    assert engine.prefill_calls == sum(
        ev[1] == "prefill"
        for s in spans if s.name == "engine.admit" for ev in s.events
    )
    assert [k for k, r in reqs.items() if r.prefix_hit] == ["prefix_hit"]
    assert engine.prefill_calls >= n_requests - 1
    # healthy traffic is not kept: no turn or request was slow, no error
    assert c.kept_traces() == []


def test_admit_events_carry_bucket_prefix_hit_and_part(served):
    c, _engine_, reqs, _n = served
    events = [
        ev[2] for s in c.spans_named("engine.admit") for ev in s.events
    ]
    mine = lambda r: [  # noqa: E731
        e for e in events if e["request"] == r.request_id
    ]
    assert [e["part"] for e in mine(reqs["plain"])] == ["whole"]
    assert [e["part"] for e in mine(reqs["chunked"])] == ["part@0", "final"]
    hit = mine(reqs["prefix_hit"])
    assert [(e["part"], e["prefix_hit"], e["bucket"]) for e in hit] == [
        ("whole", True, 16)
    ]
    assert mine(reqs["cancelled_queued"]) == []


def test_queue_wait_series_is_observed_at_admission(model):
    from odh_kubeflow_tpu.utils import prometheus

    reg = prometheus.Registry()
    engine = _engine(model, metrics_registry=reg)
    try:
        for _ in range(3):
            engine.submit([5, 9, 13], max_tokens=2).result(timeout=120)
    finally:
        engine.stop()
    assert engine.m_queue_wait.value() == 3
    assert "serving_queue_wait_seconds_count 3" in reg.exposition()


def test_a_slow_turn_is_kept_with_its_phases(model, collector):
    engine = _engine(model)
    try:
        engine.submit([5, 9, 13], max_tokens=4).result(timeout=120)  # warm
        assert collector.threshold_for("engine.turn") == 2.0
        assert collector.threshold_for("engine.request") == 120.0
        collector.set_threshold("engine.turn", 0.2)
        fast = engine._decode_greedy_fn

        def slow(*a):
            time.sleep(0.3)
            return fast(*a)

        engine._decode_greedy_fn = slow
        engine.submit([5, 9, 13], max_tokens=3).result(timeout=120)
        engine._decode_greedy_fn = fast
    finally:
        engine.stop()
    kept = [
        (reason, sorted(spans, key=lambda s: s.start_mono))
        for _tid, reason, spans in collector.kept_traces()
    ]
    assert kept and all(reason == "slow" for reason, _ in kept)
    # the oldest kept: the first slow turn, the one that admitted the
    # request (its first token fetched and emitted ahead of the chunk)
    reason, spans = kept[-1]
    assert [s.name for s in spans] == ["engine.turn"] + _decoded(admitted=1)
    turn, dispatch = spans[0], spans[2]
    assert dispatch.duration >= 0.3
    assert turn.duration >= dispatch.duration
    assert dispatch.attrs["program"] == "greedy"
    # the idle wait before it was far longer than a turn and is not kept
    assert not any(s.name == "engine.idle" for _r, ss in kept for s in ss)


def test_a_failing_dispatch_leaves_an_error_span(model, collector):
    engine = _engine(model)
    try:
        engine.submit([5, 9, 13], max_tokens=4).result(timeout=120)  # warm

        def refuses(*a):
            raise RuntimeError("device lost")

        engine._decode_greedy_fn = refuses
        req = engine.submit([5, 9, 13], max_tokens=3)
        with pytest.raises(RuntimeError, match="device lost"):
            req.result(timeout=120)
        assert isinstance(engine.failure, RuntimeError)
    finally:
        engine.stop()
    (bad,) = [
        s for s in collector.spans_named("engine.dispatch")
        if s.status == "error"
    ]
    assert "device lost" in bad.error
    assert collector.keep_reason(bad.trace_id) == "error"
    turn = _by_name(collector.trace(bad.trace_id))
    assert set(turn) == {"engine.turn", "engine.admit", "engine.dispatch"}
    # the request did not raise, the engine did: it is no error trace of
    # its own, it names the turn that is one
    spans = _by_name(collector.trace(req.request_id))
    root = spans["engine.request"]
    assert root.status == "ok" and "device lost" in root.error
    assert root.attrs["outcome"] == "failed"
    assert root.attrs["failed_by"] == bad.trace_id
    assert collector.keep_reason(req.request_id) is None
    # admitted, never saw a token: the phase it was in is cut at the end
    assert set(spans) == {
        "engine.request", "engine.request.queued", "engine.request.first_token",
    }


def test_a_failing_admission_is_the_requests_own_error(model, collector):
    engine = _engine(model)
    try:
        engine.submit([5, 9, 13], max_tokens=4).result(timeout=120)  # warm

        def refuses(*a):
            raise RuntimeError("prefill lost")

        engine._prefill_fns[16] = refuses
        req = engine.submit([5, 9, 13], max_tokens=3)
        with pytest.raises(RuntimeError, match="prefill lost"):
            req.result(timeout=120)
    finally:
        engine.stop()
    root = _by_name(collector.trace(req.request_id))["engine.request"]
    assert root.status == "error" and root.attrs["outcome"] == "failed"
    assert "failed_by" not in root.attrs
    assert collector.keep_reason(req.request_id) == "error"
    (admit,) = [
        s for s in collector.spans_named("engine.admit") if s.status == "error"
    ]
    assert collector.keep_reason(admit.trace_id) == "error"


def test_a_failure_under_a_long_queue_does_not_fill_the_kept_store(
    model, collector
):
    """One device failure with more requests queued than the kept store
    holds traces: the failing turn is kept, the requests it took down
    are not, and what the store held before is still there."""
    with pytest.raises(ValueError):
        with tracing.span("controlplane.spawn"):
            raise ValueError("an error trace from before")
    (before,) = [tid for tid, _why, _spans in collector.kept_traces()]
    # the warm-up's turn compiles: on a busy machine that alone can pass
    # the engine's 2 s and be kept as slow; this case is about errors
    collector.set_threshold("engine.turn", 60.0)
    engine = _engine(model)
    try:
        engine.submit([5, 9, 13], max_tokens=4).result(timeout=120)  # warm
        fast = engine._decode_greedy_fn
        release = threading.Event()

        def held_then_lost(*a):
            release.wait(timeout=120)
            raise RuntimeError("device lost")

        engine._decode_greedy_fn = held_then_lost
        n = collector.max_kept + 40
        reqs = [engine.submit([5, 9, 13], max_tokens=3) for _ in range(n)]
        release.set()
        for r in reqs:
            with pytest.raises(RuntimeError, match="device lost"):
                r.result(timeout=120)
        engine._decode_greedy_fn = fast
    finally:
        engine.stop()
    kept = {tid: why for tid, why, _spans in collector.kept_traces(limit=1000)}
    (bad,) = [
        s for s in collector.spans_named("engine.dispatch")
        if s.status == "error"
    ]
    assert kept == {before: "error", bad.trace_id: "error"}
    roots = [
        _by_name(collector.trace(r.request_id))["engine.request"] for r in reqs
    ]
    assert all(
        s.status == "ok" and s.attrs["outcome"] == "failed"
        and s.attrs["failed_by"] == bad.trace_id for s in roots
    )


def test_hot_thresholds_hold_for_a_collector_installed_later(model):
    """The thresholds are declared with the names, not set on whichever
    collector was current when an engine or a trainer was built."""
    engine = _engine(model)
    engine.submit([5, 9, 13], max_tokens=4).result(timeout=120)  # compiles
    # a result is set before its turn's span closes: the compiling turn
    # (seconds on a busy machine) must not close into the late collector,
    # so a healthy request's turn is the one that may
    engine.submit([5, 9, 13], max_tokens=4).result(timeout=120)
    late = tracing.SpanCollector()
    old = tracing.set_collector(late)
    try:
        assert late.threshold_for("engine.turn") == 2.0
        assert late.threshold_for("engine.request") == 120.0
        assert late.threshold_for("trainer.step") == 60.0
        assert late.threshold_for("engine.idle") == float("inf")
        assert late.threshold_for("anything.else") == late.default_threshold_s
        late.default_threshold_s = 0.0  # every other root would be kept
        engine.submit([5, 9, 13], max_tokens=4).result(timeout=120)
    finally:
        engine.stop()
        tracing.set_collector(old)
    assert late.spans_named("engine.request") and not late.kept_traces()


@pytest.mark.parametrize("executable", ["aot", "lazy"])
def test_trainer_step_records_which_executable_ran(collector, executable):
    keys = ("targets", "tokens")
    trainer = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32),
        TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=20),
        lora_cfg=LoraConfig(rank=4),
        mesh=build_mesh(MeshConfig(), jax.devices()[:1]),
        precompile_batch=(8, 32, keys) if executable == "aot" else None,
    )
    batch = trainer.make_fake_batch(8, 32)
    for _ in range(3):
        trainer.train_step(batch)
    steps = collector.spans_named("trainer.step")
    assert [s.attrs["executable"] for s in steps] == [executable] * 3
    assert [s.attrs["step"] for s in steps] == [0, 1, 2]
    assert (trainer.aot_steps, trainer.lazy_steps) == (
        (3, 0) if executable == "aot" else (0, 3)
    )
    compiled = trainer.compiled_step(8, 32, keys)
    if executable == "aot":
        assert isinstance(compiled, jax.stages.Compiled)
    else:
        assert compiled is None
    for step in steps:
        kids = [
            s for s in collector.trace(step.trace_id)
            if s.parent_span_id == step.span_id
        ]
        names = [s.name for s in sorted(kids, key=lambda s: s.start_mono)]
        want = ["trainer.h2d", "trainer.dispatch"] if executable == "aot" else [
            "trainer.dispatch"
        ]
        # the join on the compile thread: the first step of a shape only
        if executable == "aot" and step is steps[0]:
            want = ["trainer.aot_wait"] + want
        assert names == want
        assert sum(s.duration for s in kids) <= step.duration + EPS


def test_programs_carry_their_documented_names(model):
    assert engine_lib.DECODE_PROGRAM == "_decode_chunk"
    cfg, params = model
    engine = _engine(
        model, prefill_chunk=32, prefix_cache_entries=4, prefix_buckets=(8,),
        draft_params=params, draft_cfg=cfg,
    )
    try:
        shared = list(range(20, 30))
        engine.submit(shared + [1, 2], max_tokens=3).result(timeout=120)
        engine.submit(shared + [3, 4], max_tokens=3).result(timeout=120)
    finally:
        engine.stop()
    plain = _engine(model, prefill_chunk=32, prefix_cache_entries=4,
                    prefix_buckets=(8,))
    try:
        shared = list(range(20, 30))
        plain.submit(list(range(3, 48)), max_tokens=3).result(timeout=120)
        plain.submit(shared * 4, max_tokens=3).result(timeout=120)
        plain.submit(shared * 4 + [1], max_tokens=3).result(timeout=120)
    finally:
        plain.stop()

    def module(fn, *args):
        return fn.lower(*args).as_text().split("module @", 1)[1].split()[0]

    args = ((plain.params, plain.lora), plain._state)
    assert module(plain._decode_fn, *args) == "jit__decode_chunk"
    assert module(plain._decode_greedy_fn, *args) == "jit__decode_chunk_greedy"
    programs = {
        **plain._prefill_fns, **engine._prefill_fns,
        **{("draft", k): f for k, f in engine._draft_prefill_fns.items()},
    }
    kinds = {k if isinstance(k, int) else k[0] for k in programs}
    # a bucket's own (a whole prompt and a final part alike), cached
    # prefix (8, bucket), interior part, prefix seeding, the draft's
    # prefill
    assert kinds == {16, 8, "part", "seed", "draft"}, kinds
    names = {f.__name__ for f in programs.values()}
    assert len(names) == len(programs)  # one name per program
    assert all(engine_lib.PREFILL_PROGRAM_TAG in n for n in names), names
    assert all(engine_lib.DECODE_PROGRAM not in n for n in names)
    # the name given is the name jax gives the program (a bare
    # functools.partial would make it jit__unknown)
    named = engine_lib._program(lambda x, *, k: x + k, "_prefill_7", k=1)
    assert module(jax.jit(named), jnp.ones(3)) == "jit__prefill_7"


def test_span_record_round_trips_the_monotonic_start(collector):
    before = time.monotonic()
    with hot_span("trainer.unit", shape="8x32") as ctx:
        time.sleep(0.01)
    (rec,) = collector.trace(ctx.trace_id)
    assert before <= rec.start_mono <= time.monotonic()
    assert rec.duration >= 0.01 and rec.attrs == {"shape": "8x32"}
    again = tracing.SpanRecord.from_dict(rec.to_dict())
    assert again == rec and again.start_mono == rec.start_mono
    # a sender from before the field existed
    old = {k: v for k, v in rec.to_dict().items() if k != "startMono"}
    assert tracing.SpanRecord.from_dict(old).start_mono == 0.0


def test_spans_named_reads_ring_and_kept_store_once(collector):
    collector.set_threshold("engine.unit", 0.0)  # every root is kept
    with tracing.span("engine.unit"):
        with tracing.span("engine.unit.child"):
            pass
    with tracing.span("other.root"):
        pass
    assert collector.kept_traces()  # promoted: in the ring and kept
    got = collector.spans_named("engine.")
    assert [s.name for s in got] == ["engine.unit", "engine.unit.child"]
    assert collector.spans_named("nothing.") == []
    assert collector.recorded_total == 3


def test_trainer_counts_the_blocks_flash_walked(collector):
    """On packed rows under flash the step returns the live and the
    causal tile counts beside the loss; the trainer folds them in once
    a step has ended and puts the share on the next ``trainer.step``."""
    import numpy as np

    from odh_kubeflow_tpu.ops import pallas_attention as pa

    S = 2 * pa.SEGMENT_TILE
    trainer = Trainer(
        LlamaConfig.tiny(dtype=jnp.float32, attention_impl="flash"),
        TrainConfig(learning_rate=1e-2, warmup_steps=1, total_steps=20),
        lora_cfg=LoraConfig(rank=4),
        mesh=build_mesh(MeshConfig(), jax.devices()[:1]),
    )
    batch = trainer.make_fake_batch(2, S)
    plain = trainer.train_step(batch)
    assert "flash_blocks" not in plain
    assert (trainer.flash_blocks_live, trainer.flash_blocks_walked) == (0, 0)
    # row 0 one document, row 1 two with the wall on the tile's edge
    seg = np.ones((2, S), np.int32)
    seg[1, pa.SEGMENT_TILE:] = 2
    packed = dict(batch, segment_ids=seg, loss_mask=np.ones((2, S), np.float32))
    for _ in range(2):
        metrics = trainer.train_step(packed)
        float(metrics["loss"])  # the step has ended
    assert metrics["flash_blocks"].tolist() == [5, 6]
    assert (trainer.flash_blocks_live, trainer.flash_blocks_walked) == (10, 12)
    steps = collector.spans_named("trainer.step")
    assert [s.attrs.get("flash_live_share") for s in steps] == [None, None, 0.8333]


# ---- what the loop accounts for: the lane's wait and the turn's totals ------


@pytest.fixture(scope="module")
def lane(model):
    """A stream decodes; three long prompts come together: the first
    takes the part-by-part lane, the second is held for it, the third
    is held too and cancelled there."""
    c = tracing.SpanCollector()
    engine = _engine(model, n_slots=3, prompt_buckets=(8,), prefill_chunk=8)
    reqs = {}
    try:
        engine.submit(list(range(1, 21)), max_tokens=2).result(timeout=300)
        engine.submit([5, 9, 13], max_tokens=6).result(timeout=300)
        old = tracing.set_collector(c)
        try:
            running = engine.submit([3, 5, 8], max_tokens=110)
            while not running.tokens:
                time.sleep(0.002)
            for name, n in (("lane", 70), ("held", 60), ("held_cancelled", 50)):
                reqs[name] = engine.submit(list(range(2, 2 + n)), max_tokens=3)
            while len(engine._held) < 2:
                time.sleep(0.002)
            reqs["held_cancelled"].cancel()
            for r in reqs.values():
                assert r.done.wait(timeout=300)
            running.result(timeout=300)
        finally:
            engine.stop()
            tracing.set_collector(old)
    finally:
        engine.stop()
    return c, reqs


@pytest.mark.parametrize("kind", ["lane", "held", "held_cancelled"])
def test_queued_span_says_how_long_the_request_was_held_for_the_lane(lane, kind):
    c, reqs = lane
    req = reqs[kind]
    queued = _by_name(c.trace(req.request_id))["engine.request.queued"]
    # the span itself is where it was: submit to the loop taking it
    taken = req.finish_t if kind == "held_cancelled" else req.admit_t
    assert queued.start_mono == req.submit_t
    assert abs(queued.duration - (taken - req.submit_t)) < EPS
    if kind == "lane":
        assert req.held_t is None and queued.attrs == {"held_s": 0.0}
    else:
        assert req.submit_t < req.held_t < taken
        assert queued.attrs == {"held_s": taken - req.held_t}
        assert 0 < queued.attrs["held_s"] < queued.duration
    assert (req.admit_t is None) == (kind == "held_cancelled")


class _Spy:
    """A registry metric that also lists the calls made on it."""

    def __init__(self, metric):
        self.metric, self.calls = metric, []

    def inc(self, labels=None, by=1.0):
        self.calls.append((labels, by))
        self.metric.inc(labels, by)

    def set(self, value, labels=None):
        self.calls.append(value)
        self.metric.set(value, labels)


def test_the_slots_and_the_lane_reach_the_registry_once_a_turn(model, collector):
    from odh_kubeflow_tpu.utils import prometheus

    reg = prometheus.Registry()
    engine = _engine(
        model, n_slots=3, prompt_buckets=(8,), prefill_chunk=8,
        metrics_registry=reg,
    )
    steps = engine.m_slot_steps = _Spy(engine.m_slot_steps)
    held = engine.m_lane_held = _Spy(engine.m_lane_held)
    try:
        running = engine.submit([3, 5, 8], max_tokens=60)
        longs = [
            engine.submit(list(range(2, 2 + n)), max_tokens=3) for n in (40, 30)
        ]
        for r in longs + [running]:
            r.result(timeout=300)
    finally:
        engine.stop()
    turns = collector.spans_named("engine.turn")
    chunks = collector.spans_named("engine.dispatch")
    assert engine.turns == len(turns) == len(held.calls)
    assert max(held.calls) == 1 and held.calls[-1] == 0
    # five states a chunk, moved in the turn that ran it: never a token's
    assert len(steps.calls) == len(engine_lib.SLOT_STATES) * len(chunks)
    assert len(chunks) == engine.decode_calls < engine.tokens_emitted
    for state, n in engine.slot_steps.items():
        assert steps.metric.value({"state": state}) == n
    assert steps.metric.sum_matching() == engine.decode_calls * 4 * 3
    text = reg.exposition()
    assert 'serving_slot_steps_total{state="free_lane"}' in text
    assert "serving_lane_held 0" in text
    # the queue's depth stays the sum of the two, as documented
    assert engine.m_queue_depth.value() == 0
