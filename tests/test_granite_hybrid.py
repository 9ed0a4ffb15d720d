"""The ``granitemoehybrid`` family (nine Mamba-2 layers to one attention
layer, a softmax-over-top-k mixture beside a shared MLP) at a tiny size
on the CPU: the two recurrence kernels in interpret mode against the
token recurrence and the plain reference, the cached forward against the
reference's full forward, and the engine's handling of state beside the
cache: buckets, parts, reused and idle slots, the refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import granite_hybrid as gh
from odh_kubeflow_tpu.models import llama
from odh_kubeflow_tpu.models.engine import DecodeEngine, _splice_slot
from odh_kubeflow_tpu.models.generate import (
    cache_bytes,
    cache_specs,
    family_forward,
    init_cache,
)
from odh_kubeflow_tpu.ops import pallas_ssm as ps
from odh_kubeflow_tpu.reference import granitemoehybrid as ref

F32 = jnp.float32


def scan_inputs(B, S, H, P, N, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) - 2)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0, maxval=2.7))
    Bm, Cm = jax.random.normal(k[3], (B, S, N)), jax.random.normal(k[4], (B, S, N))
    init = ps.to_state(jax.random.normal(k[5], (B, H, P, N)))
    return x, dt, A, Bm, Cm, init


def reference_recurrence(x, dt, A, Bm, Cm, init_hpn):
    """The reference's own step, row by row, in numpy-like jnp."""
    ys, S = [], init_hpn
    for t in range(x.shape[0]):
        S = jnp.exp(dt[t] * A)[:, None, None] * S + (
            (dt[t][:, None] * x[t])[:, :, None] * Bm[t][None, None, :]
        )
        ys.append(jnp.einsum("hpn,n->hp", S, Cm[t]))
    return jnp.stack(ys), S


# ---- the kernels -----------------------------------------------------------


@pytest.mark.parametrize(
    "S,chunk,P,N", [(37, 16, 8, 16), (300, 256, 64, 128), (7, 8, 16, 16)],
    ids=["tiny-37", "published-widths-300", "shorter-than-a-chunk"],
)
def test_chunked_scan_is_the_token_recurrence(S, chunk, P, N):
    x, dt, A, Bm, Cm, init = scan_inputs(2, S, 4, P, N)
    y0, f0 = ps.ssm_scan_plain(x, dt, A, Bm, Cm, init)
    y1, f1 = ps.ssd_chunk_scan(x, dt, A, Bm, Cm, init, chunk=chunk, interpret=True)
    scale = float(jnp.abs(y0).max())
    np.testing.assert_allclose(y1, y0, atol=2e-5 * scale)
    np.testing.assert_allclose(f1, f0, atol=1e-5)
    # and the plain form is the reference's recurrence, from the same state
    yr, fr = reference_recurrence(
        x[0], dt[0], A, Bm[0], Cm[0], ps.from_state(init[0], P)
    )
    np.testing.assert_allclose(y0[0], yr, atol=2e-5 * scale)
    np.testing.assert_allclose(ps.from_state(f0[0], P), fr, atol=1e-5)


def test_a_padded_tail_leaves_the_state_of_the_true_last_token():
    """Two rows of different length in one call: a masked position has
    dt = 0, so each row's final state is its unpadded run's."""
    S, lengths = 40, (40, 23)
    x, dt, A, Bm, Cm, init = scan_inputs(2, S, 4, 8, 16, seed=3)
    mask = jnp.arange(S)[None] < jnp.asarray(lengths)[:, None]
    y, fin = ps.ssd_chunk_scan(
        x, dt * mask[..., None], A, Bm, Cm, init, chunk=16, interpret=True
    )
    for row, n in enumerate(lengths):
        sl = slice(row, row + 1)
        y1, f1 = ps.ssd_chunk_scan(
            x[sl, :n], dt[sl, :n], A, Bm[sl, :n], Cm[sl, :n], init[sl],
            chunk=16, interpret=True,
        )
        np.testing.assert_allclose(fin[sl], f1, atol=1e-5)
        np.testing.assert_allclose(y[sl, :n], y1, atol=1e-4)


def test_the_scan_hands_its_state_on():
    """A row in two calls, the first's final state the second's initial
    one, is the row in one call."""
    x, dt, A, Bm, Cm, init = scan_inputs(1, 48, 4, 8, 16, seed=5)
    y, fin = ps.ssd_chunk_scan(x, dt, A, Bm, Cm, init, chunk=16, interpret=True)
    cut = 29
    ya, mid = ps.ssd_chunk_scan(
        x[:, :cut], dt[:, :cut], A, Bm[:, :cut], Cm[:, :cut], init,
        chunk=16, interpret=True,
    )
    yb, fin2 = ps.ssd_chunk_scan(
        x[:, cut:], dt[:, cut:], A, Bm[:, cut:], Cm[:, cut:], mid,
        chunk=16, interpret=True,
    )
    np.testing.assert_allclose(jnp.concatenate([ya, yb], 1), y, atol=1e-4)
    np.testing.assert_allclose(fin2, fin, atol=1e-5)


@pytest.mark.parametrize("P,N", [(8, 16), (64, 128)], ids=["tiny", "published"])
def test_decode_update_is_one_recurrence_step_in_place(P, N):
    x, dt, A, Bm, Cm, init = scan_inputs(3, 1, 4, P, N, seed=7)
    stack = jnp.stack([init * 0 + 1, init, init * 2])
    # row 2 decodes nothing: dt = 0 must leave its state as it is
    dt = dt.at[2].set(0.0)
    args = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], stack, 1)
    y_plain, s_plain = ps.ssm_step_plain(*args)
    y, s = ps.ssm_decode_update(*args, interpret=True)
    np.testing.assert_allclose(y, y_plain, atol=1e-5)
    np.testing.assert_allclose(s, s_plain, atol=1e-6)
    y_scan, f_scan = ps.ssm_scan_plain(x, dt, A, Bm, Cm, init)
    np.testing.assert_allclose(y, y_scan[:, 0], atol=1e-5)
    np.testing.assert_allclose(s[1], f_scan, atol=1e-6)
    # only the addressed layer moved, and the idle row not at all
    np.testing.assert_array_equal(s[0], stack[0])
    np.testing.assert_array_equal(s[2], stack[2])
    np.testing.assert_array_equal(s[1, 2], stack[1, 2])


def test_decode_update_aliases_the_stacked_state():
    """The kernel's state operand is its state result (operand 0 is the
    prefetched layer index): a donated stack is updated where it lies.
    ``tests/test_tpu_compile.py`` reads the same off the compiled step."""
    x, dt, A, Bm, Cm, init = scan_inputs(2, 1, 4, 8, 16)
    stack = jnp.stack([init, init])
    jaxpr = jax.make_jaxpr(lambda *a: ps.ssm_decode_update(*a, interpret=True))(
        x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], stack, 1
    )
    text = str(jaxpr)
    assert "name=ssm_decode_update" in text
    assert "input_output_aliases=((1, 0),)" in text


# ---- the model -------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = gh.GraniteHybridConfig.tiny(dtype=F32)
    return cfg, gh.init_params(jax.random.key(0), cfg)


def file_config(cfg):
    """The tiny config as a configuration FILE, for the reference."""
    kinds = ["mamba" if k == llama.STATE else "attention" for k in cfg.layer_kinds]
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "layer_types": kinds * (cfg.num_layers // len(kinds)),
        "mamba_n_heads": cfg.mamba_heads, "mamba_d_head": cfg.mamba_head_dim,
        "mamba_d_state": cfg.mamba_d_state, "mamba_d_conv": cfg.mamba_d_conv,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "rms_norm_eps": cfg.rms_norm_eps,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "deployment": {"experts_held": {"first": cfg.experts_held[0],
                                        "count": cfg.experts_held[1]}},
    }


@functools.lru_cache(maxsize=None)
def jitted_reference(cfg):
    """Compiled once a length: called eagerly, the reference's scans are
    traced and compiled anew at every call."""
    config = file_config(cfg)
    return jax.jit(lambda params, seq: ref.logits(params, seq, config))


def reference_logits(tiny, tokens, pad_to=32):
    cfg, params = tiny
    seq = np.zeros(pad_to, np.int32)
    seq[: len(tokens)] = tokens
    lg, top = jitted_reference(cfg)(params, jnp.asarray(seq))
    return lg[: len(tokens)], top


def test_the_cache_has_state_stacks_and_keys_for_attention_layers_only(tiny):
    cfg, _ = tiny
    cache = init_cache(cfg, 3, 32, jnp.bfloat16)
    g = ps.heads_per_group(cfg.mamba_heads, cfg.mamba_head_dim)
    assert cache["k"].shape == (2, 3, 32, cfg.kv_dim)  # 2 of 8 layers
    assert cache["ssm"].shape == (
        6, 3, cfg.mamba_heads // g, cfg.mamba_d_state, g * cfg.mamba_head_dim
    )
    assert cache["ssm"].dtype == F32 and cache["conv"].dtype == jnp.bfloat16
    assert cache["conv"].shape == (6, 3, cfg.mamba_d_conv - 1, cfg.conv_dim)
    assert cache_bytes(cache) == {
        "full": 2 * 2 * 3 * 32 * cfg.kv_dim * 2,
        "window": 0,
        "indexed": 0,
        "state": cache["ssm"].size * 4 + cache["conv"].size * 2,
    }
    specs = cache_specs(cfg)
    assert set(specs) == set(cache)
    assert len(specs["ssm"]) == 2 and len(specs["k"]) == 4
    assert specs["moe_stats"] == jax.sharding.PartitionSpec()


def test_cache_layers_number_each_kind_down_its_own_stack():
    layers = llama.cache_layers((llama.STATE, llama.STATE, None, 8, llama.STATE), 2)
    assert [c.names for c in layers] == [
        llama.STATE_STACKS, llama.STATE_STACKS, llama.FULL_STACKS,
        llama.WINDOW_STACKS, llama.STATE_STACKS,
    ]
    assert [int(c.index) for c in layers] == [6, 7, 2, 2, 8]
    assert [int(c.depth) for c in layers] == [10, 11, 12, 13, 14]
    assert [c.window for c in layers] == [None, None, None, 8, None]


def test_the_router_takes_the_top_logits_then_a_softmax_over_them():
    logits = jnp.asarray([[0.0, 2.0, -1.0, 1.0, 3.0]])
    w, idx = gh.route_topk_softmax(logits, 3)
    assert idx.tolist() == [[4, 1, 3]]
    np.testing.assert_allclose(w, jax.nn.softmax(jnp.asarray([[3.0, 2.0, 1.0]])))


def test_uncached_forward_is_the_reference(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(1).integers(1, 256, size=21)
    want, _ = reference_logits(tiny, tokens)
    got = gh.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("padded", [False, True], ids=["whole", "right-padded"])
def test_prefill_then_decode_through_state_and_cache_is_the_reference(tiny, padded):
    cfg, params = tiny
    tokens = np.random.default_rng(2).integers(1, 256, size=21)
    want, _ = reference_logits(tiny, tokens)
    n, width = 13, 16 if padded else 13
    cache = init_cache(cfg, 1, 32, F32)
    prompt = np.zeros(width, np.int32)
    prompt[:n] = tokens[:n]
    lg, cache = gh.forward_with_cache(
        params, jnp.asarray(prompt)[None], cfg, cache, jnp.int32(0),
        positions=jnp.arange(width)[None], kv_mask=jnp.arange(32)[None] < n,
        token_mask=jnp.arange(width)[None] < n,
    )
    np.testing.assert_allclose(lg[0, :n], want[:n], atol=2e-5)
    for t in range(n, len(tokens)):
        lg, cache = gh.forward_with_cache(
            params, jnp.asarray(tokens[t:t + 1])[None], cfg, cache,
            jnp.full((1,), t, jnp.int32), positions=jnp.full((1, 1), t),
            kv_mask=jnp.arange(32)[None] <= t, token_mask=jnp.ones((1, 1), bool),
        )
        np.testing.assert_allclose(lg[0, 0], want[t], atol=2e-5)


def test_the_cached_forward_reports_the_references_routing(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(4).integers(1, 256, size=16)
    _, top = reference_logits(tiny, tokens, pad_to=16)
    cache = init_cache(cfg, 1, 16, F32)
    cache["moe_topk"] = jnp.zeros(
        (cfg.num_layers, 1, 16, cfg.num_experts_per_tok), jnp.int32
    )
    _, cache = gh.forward_with_cache(
        params, jnp.asarray(tokens)[None], cfg, cache, jnp.int32(0),
        positions=jnp.arange(16)[None], kv_mask=jnp.ones((1, 16), bool),
    )
    np.testing.assert_array_equal(
        jnp.sort(cache["moe_topk"][:, 0], -1), jnp.sort(top, -1)
    )
    # every token chose k experts, all of them held: all assignments local
    assert cache["moe_stats"].tolist()[0] == cfg.num_layers * 16 * 3
    assert cache["moe_stats"].tolist()[2] == 0


def test_an_idle_row_keeps_its_state_and_its_conv_tail(tiny):
    cfg, params = tiny
    cache = init_cache(cfg, 2, 16, F32)
    cache = {
        **cache,
        "ssm": cache["ssm"] + 0.5, "conv": cache["conv"] + 0.25,
    }
    _, new = gh.forward_with_cache(
        params, jnp.asarray([[5], [7]]), cfg, cache, jnp.asarray([3, 3]),
        positions=jnp.asarray([[3], [3]]), kv_mask=jnp.ones((2, 16), bool),
        token_mask=jnp.asarray([[True], [False]]),
    )
    for name in llama.STATE_STACKS:
        np.testing.assert_array_equal(new[name][:, 1], cache[name][:, 1])
        assert not np.array_equal(new[name][:, 0], cache[name][:, 0])


def test_several_tokens_a_row_at_per_row_offsets_are_refused(tiny):
    cfg, params = tiny
    cache = init_cache(cfg, 2, 16, F32)
    with pytest.raises(NotImplementedError, match="state after each"):
        gh.forward_with_cache(
            params, jnp.ones((2, 3), jnp.int32), cfg, cache, jnp.asarray([1, 2]),
            positions=jnp.ones((2, 3), jnp.int32),
        )


def test_splice_replaces_every_stack_of_the_slot_and_nothing_else(tiny):
    cfg, _ = tiny
    slots = {
        k: v + 1 if v.dtype != jnp.int32 else v + 9
        for k, v in init_cache(cfg, 3, 16, F32).items()
    }
    sub = init_cache(cfg, 1, 16, F32)
    out = _splice_slot(slots, sub, 1)
    for name, leaf in out.items():
        if llama.stack_kind(name) is None:
            np.testing.assert_array_equal(leaf, slots[name])
            continue
        assert not leaf[:, 1].any(), name
        np.testing.assert_array_equal(leaf[:, 0], slots[name][:, 0])
        np.testing.assert_array_equal(leaf[:, 2], slots[name][:, 2])


# ---- through the engine ----------------------------------------------------


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, params = tiny
    eng = DecodeEngine(
        params, cfg, n_slots=3, max_len=96, chunk=4, prompt_buckets=(8, 16),
        prefill_chunk=16, cache_dtype=F32,
    )
    yield eng
    eng.stop()


def greedy_by_reference(tiny, prompt, n):
    """The reference's own greedy continuation, and the margin by which
    each token led."""
    toks, margins = list(prompt), []
    for _ in range(n):
        lg, _ = reference_logits(tiny, toks, pad_to=96)
        best = jnp.sort(lg[-1])[-2:]
        margins.append(float(best[1] - best[0]))
        toks.append(int(jnp.argmax(lg[-1])))
    return toks[len(prompt):], margins


def assert_served_is_the_reference(tiny, prompt, served):
    want, margins = greedy_by_reference(tiny, prompt, len(served))
    for i, (a, b, m) in enumerate(zip(served, want, margins)):
        if m < 1e-4:
            return  # a near tie: what follows may differ legitimately
        assert a == b, (i, served, want)


@pytest.mark.parametrize(
    "length", [5, 13, 40, 33], ids=["bucket-8", "bucket-16", "parts", "parts-final-1"]
)
def test_engine_serves_the_references_greedy_tokens(tiny, engine, length):
    """A prompt in one bucket (right-padded) and prompts admitted in
    parts (two whole parts and a final one: the state is handed on)."""
    prompt = np.random.default_rng(length).integers(1, 256, size=length).tolist()
    calls = engine.prefill_calls
    served = engine.submit(prompt, max_tokens=6).result(timeout=300)
    assert_served_is_the_reference(tiny, prompt, served)
    assert engine.prefill_calls - calls == (1 if length <= 16 else -(-length // 16))
    assert engine.moe_dropped == 0


def test_a_reused_slot_keeps_nothing_of_the_last_request(tiny, engine):
    """Every slot has served a long request; a short one then reads the
    reference's tokens, whichever slot it lands in."""
    rng = np.random.default_rng(11)
    long = [rng.integers(1, 256, size=30).tolist() for _ in range(3)]
    for r in [engine.submit(p, max_tokens=8) for p in long]:
        r.result(timeout=300)
    prompt = rng.integers(1, 256, size=4).tolist()
    served = engine.submit(prompt, max_tokens=6).result(timeout=300)
    assert_served_is_the_reference(tiny, prompt, served)


def test_an_idle_slot_beside_a_busy_one(tiny, engine):
    """Two requests of different lengths share the chunks: the one that
    ends first idles beside the other, which a later one then joins."""
    rng = np.random.default_rng(12)
    a, b, c = (rng.integers(1, 256, size=n).tolist() for n in (6, 9, 12))
    ra = engine.submit(a, max_tokens=3)
    rb = engine.submit(b, max_tokens=14)
    ra.result(timeout=300)
    rc = engine.submit(c, max_tokens=5)
    for prompt, r in ((a, ra), (b, rb), (c, rc)):
        assert_served_is_the_reference(tiny, prompt, r.result(timeout=300))


def test_engine_counts_state_tokens_and_positions(tiny, engine):
    cfg, _ = tiny
    assert engine.cache_bytes["state"] == 6 * 3 * (
        cfg.mamba_heads * cfg.mamba_head_dim * cfg.mamba_d_state * 4
        + (cfg.mamba_d_conv - 1) * cfg.conv_dim * 4
    )
    assert engine.cache_bytes["window"] == 0 and engine.cache_bytes["full"] > 0
    tokens, positions = engine.prefill_tokens, engine.prefill_positions
    engine.submit([3] * 21, max_tokens=2).result(timeout=300)
    # one whole part of 16 and a final one of 5 in the bucket of 8
    assert engine.prefill_tokens - tokens == 21
    assert engine.prefill_positions - positions == 24
    assert engine.decode_calls > 0
    assert engine.decode_steps == engine.decode_calls * engine.chunk
    # the turns that ran its parts say how many it takes in all
    from odh_kubeflow_tpu.utils import tracing

    admits = tracing.collector().spans_named("engine.admit")
    assert any(s.attrs.get("parts") == 2 for s in admits)


@pytest.mark.parametrize("rem, bucket", [(3, 4), (7, 8)])
def test_a_narrow_final_part_leaves_what_the_whole_prompt_leaves(
    tiny, final_part_against_whole, rem, bucket
):
    """Two parts of 16 and a final part of ``rem`` tokens run at
    ``bucket`` positions, its scan starting from the state and the
    convolution's tail that the parts carried, against the same prompt
    admitted whole: the same first token, the same SSM state and
    tail, the same keys and values."""
    cfg, params = tiny
    n, parts, whole = final_part_against_whole(params, cfg, rem, bucket, 96)
    assert set(parts) == set(llama.STATE_STACKS) | {"k", "v"}
    for name in parts:
        got, want = (
            c[name] if name in llama.STATE_STACKS else c[name][:, :n]
            for c in (parts, whole)
        )
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5, err_msg=name)


def test_a_stopped_engines_slot_holds_the_state_of_its_stream(tiny):
    """Stopped with a request still decoding: the slot's row of the SSM
    state is the reference's after the prompt and every token served
    but the last (prefill in parts, the splice, then decode steps beside
    an idle and a finished slot). A running engine refuses the read."""
    cfg, params = tiny
    eng = DecodeEngine(
        params, cfg, n_slots=3, max_len=96, chunk=4, prompt_buckets=(8, 16),
        prefill_chunk=16, cache_dtype=F32,
    )
    try:
        rng = np.random.default_rng(14)
        eng.submit(rng.integers(1, 256, size=5).tolist(), max_tokens=2).result(timeout=300)
        prompt = rng.integers(1, 256, size=21).tolist()
        req = eng.submit(prompt, max_tokens=60, stream=True)
        stream = req.iter_tokens()
        for _ in range(9):
            next(stream)
        with pytest.raises(AssertionError, match="stop"):
            eng.slot_state(req.slot)
    finally:
        eng.stop()
    assert not req.complete and len(req.tokens) >= 9
    state = eng.slot_state(req.slot)
    assert set(state) == set(llama.STATE_STACKS)
    assert state["ssm"].shape == (6,) + ps.state_shape(
        cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_d_state
    )
    taken = prompt + list(req.tokens)[:-1]
    seq = np.zeros(96, np.int32)
    seq[: len(taken)] = taken
    want = ref.logits_and_states(
        params, jnp.asarray(seq), file_config(cfg), stop=len(taken)
    )[2]
    got = ps.from_state(state["ssm"], cfg.mamba_head_dim)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-4, atol=2e-5)


def test_a_prefix_cache_with_state_is_refused_as_a_ring_is(tiny):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="ring"):
        DecodeEngine(
            params, cfg, n_slots=2, max_len=64, prompt_buckets=(16,),
            prefix_cache_entries=2, prefix_buckets=(8,),
        )


def test_a_draft_beside_state_is_refused(tiny):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="take back"):
        DecodeEngine(
            params, cfg, n_slots=2, max_len=64, prompt_buckets=(16,),
            draft_params=params, draft_cfg=cfg,
        )


def test_generate_runs_the_family_with_right_padded_prompts(tiny):
    from odh_kubeflow_tpu.models.generate import GenerateConfig, generate

    cfg, params = tiny
    rng = np.random.default_rng(13)
    prompts = np.zeros((2, 12), np.int32)
    lengths = (12, 7)
    for i, n in enumerate(lengths):
        prompts[i, :n] = rng.integers(1, 256, size=n)
    out = generate(
        params, jnp.asarray(prompts), cfg, GenerateConfig(max_new_tokens=4),
        prompt_lengths=jnp.asarray(lengths),
    )
    assert family_forward(cfg)[1] is gh.forward_with_cache
    for i, n in enumerate(lengths):
        assert_served_is_the_reference(
            tiny, prompts[i, :n].tolist(), out["tokens"][i].tolist()
        )
