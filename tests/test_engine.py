"""Continuous-batching decode engine (models/engine.py).

Greedy output must equal the one-shot ``generate()`` path token for
token (same model, same cache semantics, different batching), mixed
sampling params must coexist in one decode program, and staggered
arrivals must share decode steps (the rate that buys is a cell's to
measure, not a CPU test's).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import LlamaConfig, init_params
from odh_kubeflow_tpu.models.engine import DecodeEngine
from odh_kubeflow_tpu.models.generate import GenerateConfig, generate
from odh_kubeflow_tpu.utils import tracing


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg=cfg, dtype=jnp.float32)
    return cfg, params


def _reference_greedy(params, cfg, prompt, max_tokens, eos_id=None):
    out = generate(
        params,
        jnp.asarray([prompt], jnp.int32),
        cfg,
        GenerateConfig(max_new_tokens=max_tokens, eos_id=eos_id),
    )
    n = int(out["lengths"][0])
    return [int(t) for t in out["tokens"][0][:n]]


def test_greedy_matches_generate(model):
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
    )
    try:
        prompts = [[5, 9, 13], list(range(3, 40)), [7] * 10]
        for prompt in prompts:
            want = _reference_greedy(params, cfg, prompt, 12)
            got = engine.submit(prompt, max_tokens=12).result(timeout=120)
            assert got == want, (got, want)
    finally:
        engine.stop()


def test_concurrent_streams_greedy_exact(model):
    """Several streams in flight at once — each must still match its
    solo greedy decode exactly (slot isolation: kv_mask / per-row
    offsets keep streams from attending into each other)."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=4, max_len=128, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        prompts = [[2 + i, 11, 3 * i + 1] for i in range(6)]
        want = [_reference_greedy(params, cfg, p, 10) for p in prompts]
        handles = [engine.submit(p, max_tokens=10) for p in prompts]
        got = [h.result(timeout=180) for h in handles]
        assert got == want
    finally:
        engine.stop()


def test_mixed_sampling_params_and_eos(model):
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=4, max_len=128, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        greedy = engine.submit([5, 6, 7], max_tokens=8)
        sampled = engine.submit(
            [5, 6, 7], max_tokens=8, temperature=1.3, top_k=20
        )
        nucleus = engine.submit(
            [9, 2], max_tokens=8, temperature=0.9, top_p=0.8
        )
        g, s, n = (
            greedy.result(120), sampled.result(120), nucleus.result(120)
        )
        assert len(g) == 8 and len(s) == 8 and len(n) == 8
        assert g == _reference_greedy(params, cfg, [5, 6, 7], 8)
        assert all(0 <= t < cfg.vocab_size for t in s + n)

        # eos honored exactly: force eos = first greedy token → length 1
        eos = g[0]
        h = engine.submit([5, 6, 7], max_tokens=8, eos_id=eos)
        assert h.result(120) == [eos]
    finally:
        engine.stop()


def test_per_request_max_tokens(model):
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=128, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        for n in (1, 3, 9):
            assert len(engine.submit([4, 5], max_tokens=n).result(120)) == n
    finally:
        engine.stop()


def test_staggered_arrivals_share_decode_steps(model):
    """The structural half of the VERDICT r2 item-10 criterion, CPU-
    provable: with staggered overlapping arrivals, the engine must
    spend far fewer decode steps than serial handling (which pays
    max_tokens steps PER request) — ≥2 tokens per decode step here.
    The wall-clock half is decode-cost-model dependent (weight-
    streaming-bound on TPU, compute-bound on this CPU tiny model): on
    the chip the cell ``mistral7b-chat-saturated`` measures it
    (``serve_tokens_per_s``, ``slot_occupancy.saturated``)."""
    cfg, params = model
    N_REQ, MAX_TOK = 6, 32
    prompts = [[3 + i, 8, 2] for i in range(N_REQ)]

    engine = DecodeEngine(
        params, cfg, n_slots=4, max_len=128, chunk=8,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        # warm the compiles (prefill + chunk) outside the counted window
        engine.submit(prompts[0], max_tokens=2).result(300)
        engine.decode_steps = engine.tokens_emitted = 0
        handles = []
        for i, p in enumerate(prompts):
            handles.append(engine.submit(p, max_tokens=MAX_TOK))
            time.sleep(0.01 * i)  # staggered, overlapping arrivals
        engine_tokens = sum(len(h.result(300)) for h in handles)
        steps = engine.decode_steps
    finally:
        engine.stop()

    serial_steps = N_REQ * MAX_TOK  # generate() decodes per request
    assert engine_tokens == N_REQ * MAX_TOK
    # the bound is deliberately loose: how many requests land before
    # each chunk starts depends on CPU thread timing (measured 96-176
    # steps across runs for the 192-step serial equivalent; a 0.8×
    # steps ceiling — and a 1.2 tokens/step floor — both flaked under
    # full-suite load at the 176-step worst case). Any tokens/step > 1
    # proves the slots share decode steps; how many tokens a step
    # carries on the chip is the cell mistral7b-chat-saturated's
    # slot_occupancy.saturated (PERF_LEDGER.jsonl).
    assert engine.tokens_emitted / steps > 1.0, (
        engine.tokens_emitted, steps, serial_steps
    )


def test_engine_serves_moe_family():
    """The engine's cache path routes through family_forward: a MoE
    config decodes through the same slot machinery. Structural checks
    + determinism only — token-for-token equality with generate() is
    not guaranteed for MoE (different cache/bucket extents change XLA
    reduction order by ulps, and the router's top-k discretizes those
    ulps into different expert choices under random weights; the
    CAPACITY semantics of padded prefill, which caused real
    divergence, are pinned exactly by
    test_moe.test_padded_routing_matches_unpadded)."""
    from odh_kubeflow_tpu.models import moe as moe_lib

    cfg = moe_lib.MoeConfig.mixtral_tiny()
    import dataclasses

    cfg = dataclasses.replace(
        cfg, base=dataclasses.replace(cfg.base, dtype=jnp.float32)
    )
    params = jax.jit(
        lambda k: moe_lib.init_params(k, cfg, dtype=jnp.float32)
    )(jax.random.key(2))

    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=64, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        a = engine.submit([5, 6, 7], max_tokens=8).result(timeout=180)
        b = engine.submit([5, 6, 7], max_tokens=8).result(timeout=180)
        assert len(a) == 8
        assert all(0 <= t < cfg.vocab_size for t in a)
        assert a == b  # greedy MoE decode is deterministic per config
    finally:
        engine.stop()


def test_prefix_cache_exact_and_hits(model):
    """Requests sharing a bucketed prompt prefix reuse its KV: outputs
    stay token-exact vs the cold path and the second request records a
    cache hit (its prefill covers only the remainder)."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        prefix_cache_entries=2, prefix_buckets=(16,),
    )
    try:
        system = [3 + (i % 11) for i in range(16)]  # 16 = prefix bucket
        p1 = system + [7, 9, 2]
        p2 = system + [5, 1]
        want1 = _reference_greedy(params, cfg, p1, 10)
        want2 = _reference_greedy(params, cfg, p2, 10)
        first = engine.submit(p1, max_tokens=10)
        got1 = first.result(timeout=120)
        second = engine.submit(p2, max_tokens=10)
        got2 = second.result(timeout=120)
        assert not first.prefix_hit and second.prefix_hit
        assert got1 == want1, (got1, want1)
        assert got2 == want2, (got2, want2)
    finally:
        engine.stop()


def test_greedy_fast_path_matches_sampling_program(model):
    """The greedy chunk program (argmax alone) must produce
    the same tokens as the general sampling program for temperature=0
    requests — program-to-program, since the two must be
    interchangeable chunk by chunk as the request mix changes."""
    cfg, params = model
    prompt = [5, 9, 13, 2]
    results = {}
    for force_general in (True, False):
        engine = DecodeEngine(
            params, cfg, n_slots=2, max_len=256, chunk=4,
            prompt_buckets=(16,), cache_dtype=jnp.float32,
        )
        try:
            if force_general:
                engine._decode_greedy_fn = engine._decode_fn
            results[force_general] = engine.submit(
                prompt, max_tokens=12
            ).result(timeout=120)
            # a sampled request in the mix switches programs mid-flight
            h_s = engine.submit(
                [4, 4, 4], max_tokens=8, temperature=0.9, top_k=5
            )
            assert len(h_s.result(timeout=120)) == 8
        finally:
            engine.stop()
    assert results[True] == results[False], results


def test_spec_decode_engine_greedy_exact(model):
    """Draft-attached engine: continuous batching × speculative
    decoding must stay token-exact vs the engine's own plain greedy
    decode (acceptance only keeps proposals the target would have
    emitted anyway), across concurrent in-flight streams — and reject
    sampled requests (verify is exact only under argmax)."""
    cfg, params = model
    # the target doubles as a perfect draft: acceptance ≈ 1, so the
    # exactness check also covers the all-accepted cap path
    plain = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
    )
    spec = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        draft_params=params, draft_cfg=cfg, spec_k=3,
    )
    try:
        prompts = [[5, 9, 13], list(range(3, 40)), [7] * 10]
        want = {}
        for i, p in enumerate(prompts):
            want[i] = plain.submit(p, max_tokens=11).result(timeout=120)
        handles = [
            spec.submit(p, max_tokens=11) for p in prompts
        ]
        for i, h in enumerate(handles):
            got = h.result(timeout=120)
            assert got == want[i], (i, got, want[i])
        # beside a draft a decode step is a round
        assert spec.decode_steps > 0
        # a perfect draft should average well over 1 token per round
        assert spec.tokens_emitted / spec.decode_steps > 1.5, (
            spec.tokens_emitted, spec.decode_steps
        )
        with pytest.raises(ValueError):
            spec.submit([1, 2, 3], max_tokens=4, temperature=0.8)
    finally:
        plain.stop()
        spec.stop()


def test_spec_engine_composes_with_prefix_cache(model):
    """All three serving levers in one engine: a shared prompt prefix
    is reused (target-side), the draft re-prefills from scratch, and
    outputs remain exact vs the plain engine."""
    cfg, params = model
    plain = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
    )
    spec = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        draft_params=params, draft_cfg=cfg, spec_k=3,
        prefix_cache_entries=2, prefix_buckets=(16,),
    )
    try:
        system = [3 + (i % 11) for i in range(16)]
        p1 = system + [7, 9, 2]
        p2 = system + [5, 1]
        hits = []
        for p in (p1, p2):
            want = plain.submit(p, max_tokens=10).result(timeout=120)
            req = spec.submit(p, max_tokens=10)
            got = req.result(timeout=120)
            assert got == want, (got, want)
            hits.append(req.prefix_hit)
        assert hits == [False, True]
    finally:
        plain.stop()
        spec.stop()


def test_chunked_prefill_greedy_exact(model):
    """Long prompts admitted part-by-part (prefill_chunk) must decode
    token-for-token identically to whole-prompt admission — the KV a
    chunked prefill writes is positionally identical."""
    cfg, params = model
    prompt = list(range(3, 3 + 50))
    want = _reference_greedy(params, cfg, prompt, 10)
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        prefill_chunk=16,
    )
    try:
        got = engine.submit(prompt, max_tokens=10).result(timeout=120)
        assert got == want, (got, want)
        # short prompts skip the state machine entirely
        short = [5, 9, 13]
        want_s = _reference_greedy(params, cfg, short, 8)
        got_s = engine.submit(short, max_tokens=8).result(timeout=120)
        assert got_s == want_s, (got_s, want_s)
    finally:
        engine.stop()


def test_chunked_prefill_allows_prompts_past_buckets(model):
    """With chunked prefill the max prompt is bounded by max_len, not
    the bucket table: a prompt longer than every bucket admits in
    parts (the final ≤chunk remainder is its own compile width)."""
    cfg, params = model
    prompt = list(range(2, 2 + 100))  # > largest bucket (64)
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        prefill_chunk=32,
    )
    try:
        want = _reference_greedy(params, cfg, prompt, 8)
        got = engine.submit(prompt, max_tokens=8).result(timeout=180)
        assert got == want, (got, want)
    finally:
        engine.stop()


# (prompt buckets, prefill chunk) -> remainder of the final part -> the
# width it runs at: every bucket, a part filled exactly, and a chunk
# that is no bucket (its own width where no bucket under it holds the
# remainder)
_FINAL_PARTS = [
    ((4, 16, 32), 32, 3, 4),
    ((4, 16, 32), 32, 10, 16),
    ((4, 16, 32), 32, 20, 32),
    ((4, 16, 32), 32, 32, 32),
    ((4, 64), 16, 3, 4),
    ((4, 64), 16, 10, 16),
]


@pytest.fixture(scope="module")
def engines_by_shape(model):
    """Engines of this module's tiny model by (buckets, prefill chunk),
    built on first use and stopped together."""
    cfg, params = model
    built = {}

    def get(buckets, prefill_chunk):
        key = (buckets, prefill_chunk)
        if key not in built:
            built[key] = DecodeEngine(
                params, cfg, n_slots=2, max_len=256, chunk=4,
                prompt_buckets=buckets, cache_dtype=jnp.float32,
                prefill_chunk=prefill_chunk,
            )
        return built[key]

    yield get
    for engine in built.values():
        engine.stop()


@pytest.mark.parametrize(
    "buckets, prefill_chunk, rem, width", _FINAL_PARTS,
    ids=[f"chunk{c}-rem{r}-runs{w}" for _, c, r, w in _FINAL_PARTS],
)
def test_a_final_part_runs_at_the_smallest_bucket_that_holds_it(
    model, engines_by_shape, buckets, prefill_chunk, rem, width
):
    """The last part of a prompt admitted in parts runs through the
    program of the smallest bucket that holds it (``prefill_chunk``'s
    own width where no bucket under it does), at its offset, and the
    request decodes to the tokens of the same prompt admitted whole;
    the counters and the request say the width that ran."""
    prompt = (
        np.random.default_rng(rem).integers(1, 200, size=2 * prefill_chunk + rem)
    ).tolist()
    whole = engines_by_shape((128,), None)
    in_parts = engines_by_shape(buckets, prefill_chunk)
    want = whole.submit(prompt, max_tokens=10).result(timeout=300)
    tokens, positions = in_parts.prefill_tokens, in_parts.prefill_positions
    parts = in_parts.parts
    req = in_parts.submit(prompt, max_tokens=10)
    assert req.result(timeout=300) == want
    assert req.bucket == width
    assert in_parts.prefill_tokens - tokens == len(prompt)
    assert in_parts.prefill_positions - positions == 2 * prefill_chunk + width
    assert in_parts.parts - parts == 3
    assert set(in_parts._prefill_fns) <= (
        set(buckets) | {prefill_chunk, ("part", prefill_chunk)}
    )


def test_the_benchmarks_warm_up_calls_every_program_a_final_part_can(model):
    """What the benchmark's drivers call before their window opens (one
    whole prompt a bucket, then one prompt of two parts and 5 tokens)
    has run every prefill program the window can: a long prompt with a
    remainder in each bucket adds no program and no executable to one,
    and each program's name holds the tag and ends in the positions it
    runs (what ``benchmark/metrics/hybrid_roofline.py`` reckons a scan's
    work from)."""
    from benchmark.drivers.engine import warm_up
    from odh_kubeflow_tpu.models.engine import PREFILL_PROGRAM_TAG

    cfg, params = model
    C = 64
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=512, chunk=4,
        prompt_buckets=(8, 16, 32, C), cache_dtype=jnp.float32,
        prefill_chunk=C,
    )
    try:
        warm_up(engine, cfg.vocab_size, {"temperature": 0.7, "top_p": 0.95})
        rng = np.random.default_rng(1)
        engine.submit(
            rng.integers(1, cfg.vocab_size, size=2 * C + 5).tolist(),
            max_tokens=4,
        ).result(timeout=300)
        programs = dict(engine._prefill_fns)
        executables = {k: f._cache_size() for k, f in programs.items()}
        assert set(programs) == {8, 16, 32, C, ("part", C)}
        for rem in (5, 12, 20, 40, C):
            req = engine.submit(
                rng.integers(1, cfg.vocab_size, size=C + rem).tolist(),
                max_tokens=4,
            )
            req.result(timeout=300)
            assert req.bucket == next(b for b in (8, 16, 32, C) if rem <= b)
        assert engine._prefill_fns == programs
        assert {
            k: f._cache_size() for k, f in programs.items()
        } == executables
    finally:
        engine.stop()
    for key, fn in programs.items():
        width = key if isinstance(key, int) else key[1]
        assert PREFILL_PROGRAM_TAG in fn.__name__
        assert int(fn.__name__.rsplit("_", 1)[1]) == width, fn.__name__


def test_chunked_prefill_interleaves_decode(model):
    """The anti-head-of-line-blocking contract: while a long admission
    runs part-by-part, an already-active stream keeps emitting tokens
    BETWEEN parts instead of stalling for the whole prefill."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=512, chunk=2,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        prefill_chunk=16,
    )
    try:
        # warm every program OUTSIDE the observed window (compiles
        # would otherwise dominate the emit timeline)
        engine.submit(list(range(3, 53)), max_tokens=2).result(300)
        engine.submit([5, 9, 13], max_tokens=2).result(300)

        a = engine.submit([7] * 8, max_tokens=40, stream=True)
        # let a start decoding, then push a long admission behind it
        first = next(a.iter_tokens())
        b = engine.submit(list(range(3, 3 + 60)), max_tokens=4)
        b.result(timeout=300)
        a_tokens = list(a.iter_tokens())
        # b's admission spans ≥3 parts (60 tokens / 16-chunk); a must
        # have kept emitting during that window — check that a's emit
        # timeline overlaps b's admission window rather than pausing
        # until after b's first token
        b_first_t = b.times[0]
        emitted_during = sum(
            1 for t in a.times if a.times[0] < t < b_first_t
        )
        assert emitted_during >= 2, (
            emitted_during, len(a.times), first
        )
        assert len([first] + a_tokens) == 40
    finally:
        engine.stop()


def test_ttft_itl_metrics_recorded(model):
    """SLO observability: every request carries submit→first-token
    latency and the per-token emit timeline the loadtests aggregate
    into p50/p95."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=128, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        req = engine.submit([3, 5, 8], max_tokens=10)
        toks = req.result(timeout=120)
        assert len(toks) == 10
        assert req.ttft() > 0
        itls = req.itls()
        assert len(itls) == 9
        assert all(g >= 0 for g in itls)
    finally:
        engine.stop()


def test_engine_under_mesh_greedy_exact(model, devices8):
    """Multi-chip serving (VERDICT r4 item 6): the engine's persistent
    cache shards over the mesh (slots on data/fsdp, KV heads on
    tensor), every program compiles under it, and greedy decode stays
    token-exact vs the single-device engine — continuous batching is
    no longer a single-chip-only feature."""
    from odh_kubeflow_tpu.models.llama import param_specs
    from odh_kubeflow_tpu.parallel.mesh import (
        MeshConfig, build_mesh, shard_tree,
    )

    cfg, params = model
    prompts = [[5, 9, 13], list(range(3, 40)), [7] * 10, [11, 2]]
    want = [_reference_greedy(params, cfg, p, 10) for p in prompts]

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2), jax.devices())
    with jax.set_mesh(mesh):
        sharded = shard_tree(params, mesh, param_specs(cfg))
    engine = DecodeEngine(
        sharded, cfg, n_slots=4, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        mesh=mesh, prefill_chunk=16,
    )
    try:
        handles = [engine.submit(p, max_tokens=10) for p in prompts]
        got = [h.result(timeout=300) for h in handles]
        assert got == want, (got, want)
    finally:
        engine.stop()


@pytest.mark.parametrize("greedy", [False, True], ids=["sampling", "greedy"])
def test_decode_chunk_updates_the_donated_cache_in_place(model, greedy):
    """The decode chunk's executable aliases the donated cache to the
    cache it returns: what comes back that is NOT an alias of an input
    is smaller than one of the cache's two arrays."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=4, max_len=64, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        fn = engine._decode_greedy_fn if greedy else engine._decode_fn
        mem = fn.lower(
            (engine.params, engine.lora), engine._state
        ).compile().memory_analysis()
        cache_k = engine._state["cache"]["k"]
        assert mem.alias_size_in_bytes >= 2 * cache_k.nbytes
        assert (
            mem.output_size_in_bytes - mem.alias_size_in_bytes < cache_k.nbytes
        )
    finally:
        engine.stop()


def test_short_prompts_pass_a_long_one_waiting_for_the_lane(model):
    """One prompt at a time is admitted in parts. A second long prompt
    waits for that lane without holding a slot, and the short prompts
    queued behind it go on to the free slots meanwhile; every request
    still gets the tokens it would have got alone."""
    cfg, params = model
    rng = np.random.default_rng(5)
    long_a, long_b = (rng.integers(1, 200, size=n).tolist() for n in (100, 90))
    shorts = [rng.integers(1, 200, size=6).tolist() for _ in range(3)]
    # slow the lane down: a part of 8, so long_a takes 13 loop turns
    engine = DecodeEngine(
        params, cfg, n_slots=4, max_len=128, chunk=2, prompt_buckets=(8,),
        prefill_chunk=8, cache_dtype=jnp.float32,
    )
    try:
        # every program compiled before the order of first tokens is read
        engine.submit(long_a[:20], max_tokens=2).result(timeout=300)
        engine.submit(shorts[0], max_tokens=2).result(timeout=300)
        reqs = [engine.submit(p, max_tokens=4) for p in [long_a, long_b] + shorts]
        got = [r.result(timeout=300) for r in reqs]
        first = [r.times[0] for r in reqs]
        # the shorts got their first token while long_b was still waiting
        # for long_a's parts, and long_b was not starved: it follows long_a
        assert max(first[2:]) < first[1]
        assert first[0] < first[1]
        assert not engine._held
        for prompt, toks in zip([long_a, long_b] + shorts, got):
            alone = engine.submit(prompt, max_tokens=4).result(timeout=300)
            assert toks == alone
    finally:
        engine.stop()


class _Late:
    """A device value that reaches the host ``delay`` seconds after it
    is asked for: what ``jax.device_get`` sees of a slow decode chunk."""

    def __init__(self, value, delay):
        self.value, self.delay = value, delay

    def __array__(self, *args, **kwargs):
        time.sleep(self.delay)
        return np.asarray(self.value)


def test_first_token_is_streamed_before_its_turns_chunk_is_fetched(model):
    """A prefill's first token exists when the prefill ends. With the
    chunk dispatched behind it made slow on purpose, the token is
    stamped before the host starts waiting for that chunk, and the
    chunk's tokens follow a whole chunk later."""
    cfg, params = model
    delay = 0.4
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=128, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    ring = tracing.SpanCollector()
    try:
        engine.submit([5, 9, 13], max_tokens=6).result(timeout=300)  # compiles
        fast = engine._decode_greedy_fn

        def slow_chunk(*a):
            state, (toks, mask) = fast(*a)
            return state, (_Late(toks, delay), mask)

        engine._decode_greedy_fn = slow_chunk
        old = tracing.set_collector(ring)
        try:
            req = engine.submit([5, 9, 13], max_tokens=6, stream=True)
            stream = req.iter_tokens(timeout=300)
            first = next(stream)
            got_first_at = time.monotonic()
            rest = list(stream)
        finally:
            tracing.set_collector(old)
            engine._decode_greedy_fn = fast
    finally:
        engine.stop()
    assert [first] + rest == _reference_greedy(params, cfg, [5, 9, 13], 6)
    # the turn that admitted it: admit, dispatch, then the first tokens'
    # fetch and emit, then the chunk's fetch, and its tokens settled
    early, chunk = sorted(
        ring.spans_named("engine.fetch"), key=lambda s: s.start_mono
    )[:2]
    assert early.attrs == {"first_tokens": 1} and not chunk.attrs
    assert early.trace_id == chunk.trace_id
    assert early.start_mono <= req.times[0] <= chunk.start_mono
    assert chunk.duration >= delay
    # the client had the token while the chunk was still on its way
    assert got_first_at < chunk.start_mono + delay
    assert req.times[1] - req.times[0] >= delay
    assert engine.first_tokens_early == 2
    # the chunk's tokens are published by the NEXT turn, once it has
    # dispatched its own chunk: under it, not before it
    end = lambda s: s.start_mono + s.duration  # noqa: E731
    phases = sorted(
        (
            s for s in ring.spans_named("engine.")
            if s.name.split(".")[1] in ("admit", "dispatch", "fetch", "emit")
        ),
        key=lambda s: s.start_mono,
    )
    after = [s for s in phases if s.trace_id != chunk.trace_id]
    assert [s.name for s in after[:3]] == [
        "engine.admit", "engine.dispatch", "engine.emit",
    ]
    assert after[2].attrs == {"deferred": 1}
    assert end(after[1]) <= after[2].start_mono <= req.times[1] <= end(after[2])
    settle = [s for s in phases if s.trace_id == chunk.trace_id][-1]
    assert settle.name == "engine.emit" and not settle.attrs
    assert end(settle) <= after[0].start_mono


def test_first_token_does_not_wait_for_a_later_prefill_of_its_turn(model):
    """Two requests admitted in one turn: the first one's token is
    streamed when ITS prefill ends, not when the prefill dispatched
    after it (here made slow on purpose) does."""
    cfg, params = model
    delay = 0.4
    engine = DecodeEngine(
        params, cfg, n_slots=3, max_len=128, chunk=4,
        prompt_buckets=(16,), cache_dtype=jnp.float32,
    )
    try:
        engine.submit([5, 9, 13], max_tokens=6).result(timeout=300)  # compiles
        chunk_fn, prefill_fn = engine._decode_greedy_fn, engine._prefill_fns[16]
        chunks, prefills = [], []

        def held_chunk(*a):
            # the loop stays in this dispatch while both requests queue up
            chunks.append(None)
            time.sleep(0.3 if len(chunks) == 1 else 0.0)
            return chunk_fn(*a)

        def third_prefill_is_slow(*a):  # running's, a's, then b's
            state, first = prefill_fn(*a)
            prefills.append(None)
            return state, (_Late(first, delay) if len(prefills) == 3 else first)

        engine._decode_greedy_fn = held_chunk
        engine._prefill_fns[16] = third_prefill_is_slow
        running = engine.submit([7, 7, 7], max_tokens=30)
        while not chunks:
            time.sleep(0.002)
        a = engine.submit([5, 9, 13], max_tokens=5)
        b = engine.submit([4, 4, 4], max_tokens=5)
        got = [r.result(timeout=300) for r in (running, a, b)]
    finally:
        engine.stop()
    assert got[1] == _reference_greedy(params, cfg, [5, 9, 13], 5)
    assert got[2] == _reference_greedy(params, cfg, [4, 4, 4], 5)
    # one turn admitted both (their prefills follow each other) ...
    assert a.admit_t < b.admit_t < a.times[0]
    # ... and a's token did not wait for b's
    assert b.times[0] - a.times[0] >= delay
    assert engine.first_tokens_early == 4


@pytest.mark.parametrize(
    "kind", ["whole", "prefix_hit", "in_parts", "draft", "eos_first"]
)
def test_streamed_tokens_are_generates_for_every_admission(model, kind):
    """Whatever way a request reaches its slot, the tokens its client
    streams are ``generate()``'s, in order, the first among them; a
    first token that is the request's eos ends the stream there and
    frees the slot."""
    cfg, params = model
    system = [3 + (i % 11) for i in range(16)]
    prompts = {
        "whole": [[5, 9, 13], list(range(3, 40))],
        "prefix_hit": [system + [7, 9, 2], system + [5, 1]],
        "in_parts": [list(range(3, 53)), [5, 9, 13]],
        "draft": [list(range(3, 40)), [7] * 10],
        "eos_first": [[5, 6, 7], [5, 6, 7, 8]],
    }[kind]
    n = 10
    want = [_reference_greedy(params, cfg, p, n) for p in prompts]
    eos = [None, None]
    if kind == "eos_first":
        eos[0] = want[0][0]
        want[0] = want[0][:1]
        want[1] = _reference_greedy(params, cfg, prompts[1], n, eos_id=eos[0])
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32,
        **{
            "prefix_hit": dict(prefix_cache_entries=2, prefix_buckets=(16,)),
            "in_parts": dict(prefill_chunk=16),
            "draft": dict(draft_params=params, draft_cfg=cfg, spec_k=3),
        }.get(kind, {}),
    )
    try:
        if kind == "prefix_hit":
            # one after the other: the second finds the first's prefix
            got, reqs = [], []
            for p in prompts:
                reqs.append(engine.submit(p, max_tokens=n, stream=True))
                got.append(list(reqs[-1].iter_tokens(timeout=300)))
            assert [r.prefix_hit for r in reqs] == [False, True]
        else:
            reqs = [
                engine.submit(p, max_tokens=n, eos_id=e, stream=True)
                for p, e in zip(prompts, eos)
            ]
            got = [list(r.iter_tokens(timeout=300)) for r in reqs]
        assert got == want, (got, want)
        for r, toks in zip(reqs, got):
            assert r.tokens == toks and len(r.times) == len(toks)
            assert r.times == sorted(r.times)
        assert engine.first_tokens_early == len(prompts)
        if kind == "eos_first":
            # nothing after the eos, and the slot serves the next request
            assert reqs[0].done.wait(timeout=300)
            assert reqs[0].token_q.empty() and reqs[0].finish_t is not None
            deadline = time.monotonic() + 300
            while any(engine._slot_req) and time.monotonic() < deadline:
                time.sleep(0.005)
            assert engine._slot_req == [None, None]
            for _ in range(2):  # both slots, the freed one among them
                again = engine.submit(prompts[1], max_tokens=n)
                assert again.result(timeout=300) == _reference_greedy(
                    params, cfg, prompts[1], n
                )
        if kind == "in_parts":
            assert engine.prefill_calls == 4 + 1  # 16+16+16+2, and the short
    finally:
        engine.stop()


def test_first_tokens_early_counts_the_requests_that_reached_a_slot(model):
    """``first_tokens_early`` is the requests admitted to a slot with
    more than one token to make: not one that asks for a single token
    (fetched at its admission), not one cancelled in the queue."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=128, chunk=4,
        prompt_buckets=(16, 64), cache_dtype=jnp.float32, prefill_chunk=32,
    )
    try:
        asked = [1, 2, 5, 1, 9, 3]
        reqs = [
            engine.submit([4 + i, 5, 6], max_tokens=n)
            for i, n in enumerate(asked)
        ]
        reqs.append(engine.submit(list(range(3, 48)), max_tokens=1))
        reqs.append(engine.submit(list(range(3, 48)), max_tokens=4))
        for r in reqs:
            r.result(timeout=300)
        # both slots busy: the next request waits in the queue, and is
        # cancelled there
        busy = [engine.submit([11 + i] * 5, max_tokens=30) for i in range(2)]
        while sum(r is not None for r in engine._slot_req) < 2:
            time.sleep(0.005)
        gone = engine.submit([2, 4, 6], max_tokens=8)
        gone.cancel()
        for r in busy:
            r.result(timeout=300)
        assert gone.done.wait(timeout=300) and not gone.tokens
        early = sum(n > 1 for n in asked) + 1 + len(busy)
        assert engine.first_tokens_early == early
        assert [len(r.tokens) for r in reqs] == asked + [1, 4]
        assert engine.tokens_emitted == sum(
            len(r.tokens) for r in reqs + busy if r.max_tokens > 1
        )
    finally:
        engine.stop()


# ---- what goes out behind a chunk, and what is published under the next


def _device_order(ring, req):
    """What the loop handed the device, in order, as its spans say:
    ``P`` for a part of ``req``'s admission, ``D`` for a decode chunk."""
    marks = []
    for s in ring.spans_named("engine."):
        if s.name == "engine.dispatch":
            marks.append((s.start_mono, "D"))
        elif s.name == "engine.admit":
            marks += [
                (s.start_mono, "P") for ev in s.events
                if ev[2]["request"] == req.request_id
            ]
    return "".join(m for _, m in sorted(marks))


@pytest.mark.parametrize("begun", ["beside_decode", "alone"])
def test_parts_go_out_behind_the_chunk_while_other_slots_decode(model, begun):
    """A prompt admitted in four parts while two streams decode: every
    request gets the tokens it would have got alone, each part that
    follows a chunk was dispatched behind it before its fetch
    (``parts_ahead``: all four where the admission begins beside a
    decode, all but the first where it begins alone and its first part
    runs at the top of a turn), and on the device parts and chunks
    alternate as before: part, chunk, part, chunk."""
    cfg, params = model
    long = list(range(3, 3 + 53))  # 16 + 16 + 16 + 5
    # (prompts whose greedy streams hold no near-tie over 60 tokens: the
    # engine's batch of three and generate's of one round differently)
    shorts = [[3, 5, 8], [9, 8, 7]]
    engine = DecodeEngine(
        params, cfg, n_slots=3, max_len=128, chunk=2, prompt_buckets=(16,),
        cache_dtype=jnp.float32, prefill_chunk=16,
    )
    ring = tracing.SpanCollector()
    try:
        # every program compiled before anything is counted
        engine.submit(long, max_tokens=2).result(timeout=300)
        engine.submit(shorts[0], max_tokens=3).result(timeout=300)
        parts, ahead = engine.parts, engine.parts_ahead
        assert (parts, ahead) == (4, 0)  # alone: each at the top of a turn
        old = tracing.set_collector(ring)
        try:
            if begun == "beside_decode":
                reqs = [engine.submit(p, max_tokens=60) for p in shorts]
                while sum(r is not None for r in engine._slot_req) < 2:
                    time.sleep(0.002)
                reqs.append(engine.submit(long, max_tokens=7))
            else:
                # the loop is held inside the first part's dispatch, at
                # the top of a turn, until the two short prompts queue up
                part_fn = engine._prefill_fns[("part", 16)]
                gate = threading.Event()

                def held(*a):
                    gate.wait(timeout=300)
                    return part_fn(*a)

                engine._prefill_fns[("part", 16)] = held
                reqs = [engine.submit(long, max_tokens=7)]
                while engine.parts == parts:
                    time.sleep(0.002)
                reqs = [engine.submit(p, max_tokens=60) for p in shorts] + reqs
                gate.set()
            got = [r.result(timeout=300) for r in reqs]
        finally:
            tracing.set_collector(old)
    finally:
        engine.stop()
    for prompt, toks in zip(shorts + [long], got):
        assert toks == _reference_greedy(params, cfg, prompt, len(toks))
    assert engine.parts - parts == 4
    assert engine.parts_ahead - ahead == (4 if begun == "beside_decode" else 3)
    ahead_spans = [
        s for s in ring.spans_named("engine.admit") if s.attrs.get("ahead")
    ]
    assert len(ahead_spans) == engine.parts_ahead - ahead
    assert all(s.attrs == {"ahead": 1, "parts": 4} for s in ahead_spans)
    # one part, then a chunk for the slots that decode, then the next
    order = _device_order(ring, reqs[-1])
    assert "PP" not in order and order.count("P") == 4
    assert "PDPDPDP" in order
    # a part that went out ahead is dispatched between its turn's chunk
    # and that chunk's fetch
    for s in ahead_spans:
        turn = sorted(
            (k for k in ring.trace(s.trace_id) if k.parent_span_id),
            key=lambda k: k.start_mono,
        )
        names = [k.name for k in turn]
        assert names.index("engine.dispatch") < turn.index(s) < (
            len(names) - 1 - names[::-1].index("engine.fetch")
        )


@pytest.fixture(scope="module")
def hybrid():
    """A tiny stack with a recurrent state beside its cache and a
    mixture's counters in it (Granite 4.0-H's family)."""
    from odh_kubeflow_tpu.models import granite_hybrid as gh

    cfg = gh.GraniteHybridConfig.tiny(dtype=jnp.float32)
    return cfg, gh.init_params(jax.random.key(0), cfg)


def _state_after(cfg, params, tokens, max_len):
    """The recurrent state the family's own cached forward leaves after
    ``tokens``, one call on a fresh cache: ``{name: [layers, ...]}``."""
    from odh_kubeflow_tpu.models.generate import family_forward, init_cache
    from odh_kubeflow_tpu.models.llama import STATE, stack_kind

    cache_cfg, fwd = family_forward(cfg)
    n = len(tokens)
    _, cache = fwd(
        params, jnp.asarray([tokens], jnp.int32), cfg,
        init_cache(cache_cfg, 1, max_len, jnp.float32), jnp.int32(0),
        positions=jnp.arange(n, dtype=jnp.int32)[None],
        kv_mask=(jnp.arange(max_len) < n)[None],
        token_mask=jnp.ones((1, n), jnp.bool_),
    )
    return {
        name: np.asarray(leaf[:, 0]) for name, leaf in cache.items()
        if stack_kind(name) == STATE
    }


class _Lost:
    """A chunk's tokens that never reach the host."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device lost")


@pytest.mark.parametrize("how", ["stop", "part_ahead_fails", "fetch_fails"])
def test_nothing_settled_is_left_unpublished_when_the_loop_ends(hybrid, how):
    """The engine stops, or a program it dispatched fails (a part sent
    out behind a chunk; a chunk whose tokens never arrive), while one
    stream decodes beside an admission in parts: every reader's stream
    ends, ``result()`` raises, and each reader has read exactly the
    tokens its request holds (a chunk's tokens settled and not yet
    published are published first). Stopped, the slot of the request
    still decoding holds the state of exactly those tokens."""
    cfg, params = hybrid
    rng = np.random.default_rng(36)
    engine = DecodeEngine(
        params, cfg, n_slots=3, max_len=128, chunk=4, prompt_buckets=(8, 16),
        prefill_chunk=16, cache_dtype=jnp.float32,
    )
    long = rng.integers(1, 256, size=70).tolist()  # five parts
    read = {}

    def reader(name, req):
        toks = read.setdefault(name, [])
        try:
            for tok in req.iter_tokens(timeout=300):
                toks.append(tok)
        except RuntimeError as e:
            read[name + ".error"] = e

    try:
        # every program compiled
        engine.submit(long[:37], max_tokens=2).result(timeout=300)
        engine.submit(long[:5], max_tokens=5).result(timeout=300)
        prompt = rng.integers(1, 256, size=11).tolist()
        decoding = engine.submit(prompt, max_tokens=100, stream=True)
        threads = [threading.Thread(target=reader, args=("decoding", decoding))]
        threads[0].start()
        while len(decoding.tokens) < 9:
            time.sleep(0.002)
        if how == "part_ahead_fails":
            part_fn = engine._prefill_fns[("part", 16)]
            calls = []

            def third_part_is_lost(*a):
                calls.append(None)
                if len(calls) == 3:
                    raise RuntimeError("device lost")
                return part_fn(*a)

            engine._prefill_fns[("part", 16)] = third_part_is_lost
        elif how == "fetch_fails":
            chunk_fn = engine._decode_greedy_fn
            chunks = []

            def third_chunk_is_lost(*a):
                state, (toks, mask) = chunk_fn(*a)
                chunks.append(None)
                return state, (_Lost() if len(chunks) == 3 else toks, mask)

            engine._decode_greedy_fn = third_chunk_is_lost
        admitted = engine.submit(long, max_tokens=30, stream=True)
        threads.append(threading.Thread(target=reader, args=("admitted", admitted)))
        threads[1].start()
        if how == "stop":
            while engine.parts_ahead < 2:
                time.sleep(0.002)
        else:
            assert decoding.done.wait(timeout=300)
    finally:
        engine.stop()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive()
    lost = "stopped" if how == "stop" else "device lost"
    for name, req in (("decoding", decoding), ("admitted", admitted)):
        assert read[name] == req.tokens and len(req.times) == len(req.tokens)
        assert lost in str(read[name + ".error"])
        with pytest.raises(RuntimeError, match=lost):
            req.result(timeout=300)
    assert engine._settled is None
    assert 9 <= len(decoding.tokens) < 100 and not decoding.complete
    if how != "stop":
        assert lost in str(engine.failure)
        assert engine.parts_ahead >= 2
        return
    state = engine.slot_state(decoding.slot)
    want = _state_after(cfg, params, prompt + decoding.tokens[:-1], 128)
    assert set(state) == set(want) and state
    for name in state:
        np.testing.assert_allclose(
            state[name], want[name], rtol=2e-4, atol=2e-5, err_msg=name
        )


def test_a_request_cancelled_after_its_part_went_ahead_frees_the_lane(model):
    """An admission in parts is cancelled once one of its parts has gone
    out behind a chunk: it is dropped at its next step, its slot is
    free again, and the long prompt held behind it for the lane is
    admitted and served as it would have been alone."""
    cfg, params = model
    rng = np.random.default_rng(7)
    long_a, long_b = (rng.integers(1, 200, size=n).tolist() for n in (100, 40))
    engine = DecodeEngine(
        params, cfg, n_slots=3, max_len=160, chunk=2, prompt_buckets=(8,),
        prefill_chunk=8, cache_dtype=jnp.float32,
    )
    try:
        engine.submit(long_a[:20], max_tokens=2).result(timeout=300)  # compiles
        running = engine.submit([3, 5, 8], max_tokens=120)
        while not running.tokens:
            time.sleep(0.002)
        a = engine.submit(long_a, max_tokens=4)  # thirteen parts
        b = engine.submit(long_b, max_tokens=4)
        while engine.parts_ahead < 2:
            time.sleep(0.002)
        assert list(engine._held) == [b] or b.admit_t is None
        a.cancel()
        assert a.done.wait(timeout=300) and not a.tokens
        got = b.result(timeout=300)
        assert b.slot == a.slot and not engine._held
        assert got == _reference_greedy(params, cfg, long_b, 4)
        assert running.result(timeout=300) == _reference_greedy(
            params, cfg, [3, 5, 8], 120
        )
        # a's parts stopped where it was dropped
        assert engine.parts < 3 + 13 + 5
    finally:
        engine.stop()


def test_a_slot_freed_by_a_chunk_is_taken_in_the_very_next_turn(model):
    """A request that ends in a chunk gives up its slot when that chunk
    is settled, before its last tokens are published: the request
    waiting for a slot is admitted by the turn that follows, not a
    chunk later."""
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=128, chunk=4, prompt_buckets=(16,),
        cache_dtype=jnp.float32,
    )
    ring = tracing.SpanCollector()
    try:
        engine.submit([5, 9, 13], max_tokens=6).result(timeout=300)  # compiles
        old = tracing.set_collector(ring)
        try:
            # 1 + 8 tokens: ends with its second chunk; the other decodes on
            ending = engine.submit([7, 7, 7], max_tokens=9)
            other = engine.submit([4, 4, 4], max_tokens=40)
            while sum(r is not None for r in engine._slot_req) < 2:
                time.sleep(0.002)
            waiting = engine.submit([5, 9, 13], max_tokens=5)
            for r in (ending, other, waiting):
                r.result(timeout=300)
        finally:
            tracing.set_collector(old)
    finally:
        engine.stop()
    assert waiting.tokens == _reference_greedy(params, cfg, [5, 9, 13], 5)
    assert waiting.slot == ending.slot
    end = lambda s: s.start_mono + s.duration  # noqa: E731
    # the chunk that ended it was settled by the last ``engine.emit`` that
    # is neither a first token's nor a publishing one before its last
    # token's stamp ...
    settled_at = max(
        end(s) for s in ring.spans_named("engine.emit")
        if not s.attrs and end(s) <= ending.times[-1]
    )
    # ... and the request waiting was admitted after that, with no chunk
    # dispatched in between, and before those last tokens were published
    assert settled_at < waiting.admit_t < ending.times[-4] <= ending.finish_t
    assert not [
        s for s in ring.spans_named("engine.dispatch")
        if settled_at <= s.start_mono <= waiting.admit_t
    ]


# ---- the ledger of slot-steps and of what waiting requests wait for ---------


def _turns_with_chunks(ring):
    """Each ``engine.turn`` span's attributes with the number of decode
    chunks it dispatched, in the loop's order."""
    turns = sorted(ring.spans_named("engine.turn"), key=lambda s: s.attrs["turn"])
    chunks = {t.span_id: 0 for t in turns}
    for s in ring.spans_named("engine.dispatch"):
        chunks[s.parent_span_id] += 1
    return [(t.attrs, chunks[t.span_id]) for t in turns]


def _steps(attrs):
    from odh_kubeflow_tpu.models.engine import SLOT_STATES

    return {s: attrs[f"slot_steps_{s}"] for s in SLOT_STATES}


def _serve_whole_prompts(engine, model):
    """Five short prompts on three slots: two wait for a slot."""
    reqs = [engine.submit([3 + i, 7, 11], max_tokens=9 + i) for i in range(5)]
    for r in reqs:
        r.result(timeout=300)
    return {"slots": 3}


def _serve_parts_with_one_held(engine, model):
    """A stream decodes, a long prompt is admitted in parts beside it,
    a second long prompt is held behind the first for the lane; once
    both are served, whole prompts alone."""
    rng = np.random.default_rng(5)
    long_a, long_b = (rng.integers(1, 200, size=n).tolist() for n in (100, 90))
    running = engine.submit([3, 5, 8], max_tokens=150)
    while not running.tokens:
        time.sleep(0.002)
    a = engine.submit(long_a, max_tokens=4)
    b = engine.submit(long_b, max_tokens=4)
    a.result(timeout=300), b.result(timeout=300)
    assert b.held_t is not None and a.held_t is None
    after = engine.turns
    for r in [engine.submit([9, 9, 2 + i], max_tokens=6) for i in range(2)]:
        r.result(timeout=300)
    running.result(timeout=300)
    return {"held": b, "lane_free_from": after + 1}


def _serve_one_that_ends_on_its_eos(engine, model):
    """Alone on the engine, a request whose eos is the token the first
    chunk's second step emits (its third token: the prefill emits the
    first)."""
    cfg, params = model
    prompt = next(
        p for p in ([5, 9, 13 + i] for i in range(50))
        if (ref := _reference_greedy(params, cfg, p, 3))[2] not in ref[:2]
    )
    ref = _reference_greedy(params, cfg, prompt, 3)
    got = engine.submit(prompt, max_tokens=40, eos_id=ref[2]).result(timeout=300)
    assert got == ref
    return {}


def _serve_a_cancel_mid_admission(engine, model):
    rng = np.random.default_rng(7)
    long_a = rng.integers(1, 200, size=100).tolist()
    running = engine.submit([3, 5, 8], max_tokens=60)
    while not running.tokens:
        time.sleep(0.002)
    a = engine.submit(long_a, max_tokens=4)  # thirteen parts
    while engine.parts_ahead < 2:
        time.sleep(0.002)
    a.cancel()
    assert a.done.wait(timeout=300) and not a.tokens
    running.result(timeout=300)
    return {}


def _serve_one_alone(engine, model):
    engine.submit([5, 9, 13], max_tokens=13).result(timeout=300)
    return {}


LEDGER_CASES = {
    "whole_prompts": _serve_whole_prompts,
    "parts_with_one_held": _serve_parts_with_one_held,
    "eos_in_second_step": _serve_one_that_ends_on_its_eos,
    "cancel_mid_admission": _serve_a_cancel_mid_admission,
    "nothing_queued": _serve_one_alone,
}


@pytest.mark.parametrize("case", sorted(LEDGER_CASES))
def test_every_slot_step_of_every_chunk_is_counted_once(model, case):
    """Each decode chunk's ``chunk x n_slots`` slot-steps fall under
    exactly one state, turn by turn on the ``engine.turn`` spans'
    running totals, which never fall; and by case, the states that the
    traffic must and must not have produced."""
    cfg, params = model
    k, n_slots = 4, 3
    engine = DecodeEngine(
        params, cfg, n_slots=n_slots, max_len=256, chunk=k,
        prompt_buckets=(8,), prefill_chunk=8, cache_dtype=jnp.float32,
    )
    ring = tracing.SpanCollector()
    try:
        # every program compiled before anything is counted on
        engine.submit(list(range(1, 21)), max_tokens=2).result(timeout=300)
        engine.submit([5, 9, 13], max_tokens=6).result(timeout=300)
        # the idle engine's totals: what the first turn's are held against
        base = engine._turn_totals()
        old = tracing.set_collector(ring)
        try:
            said = LEDGER_CASES[case](engine, model)
        finally:
            engine.stop()
            tracing.set_collector(old)
    finally:
        engine.stop()
    turns = [(base, 0)] + _turns_with_chunks(ring)
    assert sum(n for _, n in turns) >= 1
    deltas = []
    for (before, _), (attrs, chunks) in zip(turns, turns[1:]):
        assert chunks in (0, 1)
        was, now = _steps(before), _steps(attrs)
        d = {s: now[s] - was[s] for s in now}
        assert all(v >= 0 for v in d.values()), d
        assert sum(d.values()) == chunks * k * n_slots, (attrs["turn"], d)
        for key in ("wait_lane_s", "wait_slot_s", "parts", "parts_ahead",
                    "prefill_tokens", "prefill_positions"):
            assert attrs[key] >= before[key], key
        deltas.append((attrs, d, {
            key: attrs[key] - before[key]
            for key in ("wait_lane_s", "wait_slot_s")
        }))
    last = turns[-1][0]
    assert _steps(last) == engine.slot_steps
    assert sum(engine.slot_steps.values()) == engine.decode_calls * k * n_slots
    assert last["parts"] == engine.parts
    assert last["parts_ahead"] == engine.parts_ahead
    assert last["prefill_tokens"] == engine.prefill_tokens
    assert last["prefill_positions"] == engine.prefill_positions
    assert last["wait_lane_s"] == engine.wait_lane_s
    assert last["wait_slot_s"] == engine.wait_slot_s
    total = {
        s: engine.slot_steps[s] - _steps(turns[0][0])[s]
        for s in engine.slot_steps
    }
    if case == "whole_prompts":
        assert total["admitting"] == total["free_lane"] == 0
        assert last["wait_lane_s"] == turns[0][0]["wait_lane_s"]
        # two of five waited for a slot, and nothing else
        assert last["wait_slot_s"] > turns[0][0]["wait_slot_s"]
    elif case == "parts_with_one_held":
        assert total["free_lane"] > 0 and total["admitting"] > 0
        for attrs, d, waits in deltas:
            if attrs["held"]:
                # a slot stood free for the lane, and for nothing else;
                # the held request's seconds went to the lane
                assert d["free_no_work"] == 0 and attrs["held"] == 1
                assert waits["wait_lane_s"] > 0 and waits["wait_slot_s"] == 0
            else:
                assert d["free_lane"] == 0 and waits["wait_lane_s"] == 0
            if attrs["turn"] >= said["lane_free_from"]:
                assert d["free_lane"] == d["admitting"] == 0
        assert any(attrs["held"] and d["free_lane"] for attrs, d, _ in deltas)
    elif case == "eos_in_second_step":
        assert total == {
            "live": 2, "ended": k - 2, "admitting": 0, "free_lane": 0,
            "free_no_work": k * (n_slots - 1),
        }
    elif case == "cancel_mid_admission":
        assert total["admitting"] > 0 and total["free_lane"] == 0
        # the slot it held is free again once it has been dropped
        last_chunk = [d for _, d, _ in deltas if sum(d.values())][-1]
        assert last_chunk["admitting"] == 0
        assert last_chunk["free_no_work"] == k * (n_slots - 1)
    elif case == "nothing_queued":
        # 1 + 12 tokens: three chunks, two slots empty for want of work
        assert total == {
            "live": 12, "ended": 0, "admitting": 0, "free_lane": 0,
            "free_no_work": 3 * k * (n_slots - 1),
        }
        assert last["wait_slot_s"] == turns[0][0]["wait_slot_s"]


def test_no_ledger_is_kept_beside_a_draft(model):
    cfg, params = model
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=256, chunk=4, prompt_buckets=(16,),
        cache_dtype=jnp.float32, draft_params=params, draft_cfg=cfg, spec_k=3,
    )
    ring = tracing.SpanCollector()
    old = tracing.set_collector(ring)
    try:
        got = engine.submit([5, 9, 13], max_tokens=11).result(timeout=300)
    finally:
        engine.stop()
        tracing.set_collector(old)
    assert got == _reference_greedy(params, cfg, [5, 9, 13], 11)
    assert engine.decode_steps > 0 and not any(engine.slot_steps.values())
    turns = ring.spans_named("engine.turn")
    assert turns and all(set(t.attrs) == {"turn"} for t in turns)
