"""The ``brumby`` family (every layer's attention replaced by gated power
retention of degree 2: a state and a normaliser a slot and no keys and
values at all) at a tiny size on the CPU: the two retention kernels in
interpret mode against their plain forms, the token recurrence and the
reference's attention form (the identity between the two forms is the
mechanism), the cached forward against the reference's full forward, and
the engine's handling of a stack WITHOUT keys and values: buckets,
parts, reused and idle slots, the refusals."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families.brumby import to_symmetric_half
from odh_kubeflow_tpu.models import brumby as bm
from odh_kubeflow_tpu.models import llama
from odh_kubeflow_tpu.models.engine import DecodeEngine
from odh_kubeflow_tpu.models.generate import cache_bytes, family_forward, init_cache
from odh_kubeflow_tpu.ops import pallas_retention as pr
from odh_kubeflow_tpu.reference import brumby as ref

F32 = jnp.float32


def scan_inputs(B, S, Hq, Hkv, d, seed=0, zero_state=False):
    k = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(k[0], (B, S, Hq, d))
    kk = jax.random.normal(k[1], (B, S, Hkv, d))
    v = jax.random.normal(k[2], (B, S, Hkv, d))
    log_g = jax.nn.log_sigmoid(jax.random.normal(k[3], (B, S, Hkv)) + 3.0)
    R = pr.phi_rows(d)
    init = jax.random.normal(k[4], (B, Hkv, R, d, d))
    norm = jnp.abs(jax.random.normal(k[5], (B, Hkv, R, d))) + 1.0
    if zero_state:
        init, norm = jnp.zeros_like(init), jnp.zeros_like(norm)
    return q, kk, v, log_g, init, norm


# ---- the kernels -----------------------------------------------------------


@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_of_a_dot_phi_of_b_is_the_square_of_a_dot_b(d):
    a, b = jax.random.normal(jax.random.key(d), (2, 5, d))
    want = np.square(np.asarray(jnp.sum(a * b, -1)))
    got = jnp.sum(pr.phi(a) * pr.phi(b), axis=(-1, -2))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    # the reference's own feature map (the distinct pairs) says the same,
    # and a state in the program's layout reads out as one in its order
    np.testing.assert_allclose(jnp.sum(ref.phi(a) * ref.phi(b), -1), want, rtol=2e-5, atol=1e-5)
    state = jnp.einsum("tri,tv->rvi", pr.phi(a), b)
    np.testing.assert_allclose(
        to_symmetric_half(state), jnp.einsum("tp,tv->pv", ref.phi(a), b),
        rtol=1e-5, atol=1e-5,
    )
    assert to_symmetric_half(state).shape == (d * (d + 1) // 2, d)


@pytest.mark.parametrize(
    "S,chunk,d", [(37, 16, 16), (19, 8, 8), (150, 128, 128), (7, 8, 16)],
    ids=["tiny-37", "tiny-19", "published-head-150", "shorter-than-a-chunk"],
)
def test_the_state_form_is_the_attention_form(S, chunk, d):
    """Chunked scan = plain scan = the reference's attention form, from
    a zero state; ``S`` is no multiple of the chunk."""
    q, k, v, log_g, init, norm = scan_inputs(1, S, 4, 2, d, zero_state=True)
    y0, f0, z0 = pr.retention_scan_plain(q, k, v, log_g, init, norm)
    y1, f1, z1 = pr.retention_chunk_scan(
        q, k, v, log_g, init, norm, chunk=chunk, interpret=True
    )
    scale = float(jnp.abs(f0).max())
    # a row whose weights sum to little divides by little: rtol for those
    np.testing.assert_allclose(y1, y0, rtol=1e-3, atol=2e-4)
    np.testing.assert_allclose(f1, f0, atol=2e-5 * scale)
    np.testing.assert_allclose(z1, z0, rtol=2e-5, atol=2e-5)
    pad = -S % min(chunk, 128)
    padded = lambda a: jnp.pad(a[0], ((0, pad),) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want = ref.power_retention(
            padded(q), padded(k), padded(v), jnp.cumsum(padded(log_g), 0), pr.EPS,
            block=min(chunk, 128),
        )
    np.testing.assert_allclose(y0[0], want[:S], rtol=1e-3, atol=2e-4)


def test_the_scan_continues_a_state_as_the_token_recurrence_does():
    q, k, v, log_g, init, norm = scan_inputs(2, 21, 4, 2, 16, seed=3)
    y0, f0, z0 = pr.retention_scan_plain(q, k, v, log_g, init, norm)
    y1, f1, z1 = pr.retention_chunk_scan(
        q, k, v, log_g, init, norm, chunk=8, interpret=True
    )
    # and token by token through the decode update on a stack of one layer
    state, stack_norm, ys = init[None], norm[None], []
    step = jax.jit(pr.retention_step_plain)
    for t in range(21):
        y, state, stack_norm = step(
            q[:, t], k[:, t], v[:, t], log_g[:, t], state, stack_norm, 0
        )
        ys.append(y)
    scale = float(jnp.abs(f0).max())
    for got in (y1, jnp.stack(ys, 1)):
        np.testing.assert_allclose(got, y0, atol=2e-4)
    for got in (f1, state[0]):
        np.testing.assert_allclose(got, f0, atol=2e-5 * scale)
    for got in (z1, stack_norm[0]):
        np.testing.assert_allclose(got, z0, rtol=2e-5, atol=2e-5)


def test_a_padded_tail_leaves_the_state_of_the_true_last_token():
    q, k, v, log_g, init, norm = scan_inputs(2, 24, 4, 2, 16, seed=4)
    real = jnp.arange(24) < 17
    k_m = jnp.where(real[None, :, None, None], k, 0.0)
    g_m = jnp.where(real[None, :, None], log_g, 0.0)
    short = tuple(a[:, :17] for a in (q, k, v, log_g))
    _, f0, z0 = pr.retention_scan_plain(*short, init, norm)
    for scan in (
        pr.retention_scan_plain,
        functools.partial(pr.retention_chunk_scan, chunk=8, interpret=True),
    ):
        _, f, z = scan(q, k_m, v, g_m, init, norm)
        np.testing.assert_allclose(f, f0, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(z, z0, rtol=1e-5, atol=1e-5)


def test_the_scan_hands_its_state_on():
    q, k, v, log_g, init, norm = scan_inputs(1, 40, 4, 2, 16, seed=5)
    scan = functools.partial(pr.retention_chunk_scan, chunk=16, interpret=True)
    y, f, z = scan(q, k, v, log_g, init, norm)
    cut = 24
    ya, fa, za = scan(q[:, :cut], k[:, :cut], v[:, :cut], log_g[:, :cut], init, norm)
    yb, fb, zb = scan(q[:, cut:], k[:, cut:], v[:, cut:], log_g[:, cut:], fa, za)
    np.testing.assert_allclose(jnp.concatenate([ya, yb], 1), y, atol=1e-4)
    np.testing.assert_allclose(fb, f, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(zb, z, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("form", ["kernel", "plain"])
def test_no_decay_and_a_zero_key_are_the_identity_on_state_and_normaliser(form):
    q, k, v, _, init, norm = scan_inputs(2, 12, 4, 2, 16, seed=6)
    scan = (
        functools.partial(pr.retention_chunk_scan, chunk=8, interpret=True)
        if form == "kernel" else pr.retention_scan_plain
    )
    _, f, z = scan(q, jnp.zeros_like(k), v, jnp.zeros((2, 12, 2)), init, norm)
    np.testing.assert_array_equal(f, init)
    np.testing.assert_array_equal(z, norm)


@pytest.mark.parametrize("d", [16, 128], ids=["tiny", "published"])
def test_decode_update_is_one_recurrence_step_in_place(d):
    """One token a row on a stack of three layers, five query heads a
    state at the published head: the step written out, only the named
    layer moved, a masked row not at all."""
    G = 5 if d == 128 else 2
    q, k, v, log_g, init, norm = scan_inputs(2, 1, 2 * G, 2, d, seed=7)
    state = jnp.stack([init * 0 + 1.0, init, init * 0 - 1.0])
    norms = jnp.stack([norm * 0 + 2.0, norm, norm * 0 + 3.0])
    args = (
        q[:, 0], k[:, 0].at[1].set(0.0), v[:, 0], log_g[:, 0].at[1].set(0.0),
    )
    y0, s0, z0 = pr.retention_step_plain(*args, state, norms, 1)
    y1, s1, z1 = pr.retention_decode_update(*args, state, norms, 1, interpret=True)
    np.testing.assert_allclose(y1, y0, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s0, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z1, z0, rtol=1e-5, atol=1e-5)
    for got, was in ((s1, state), (z1, norms)):
        np.testing.assert_array_equal(got[0], was[0])
        np.testing.assert_array_equal(got[2], was[2])
        np.testing.assert_array_equal(got[1, 1], was[1, 1])
        assert not np.array_equal(got[1, 0], was[1, 0])
    # the step is the recurrence's: one token of the scan from that state
    ys, fs, zs = pr.retention_scan_plain(
        q, args[1][:, None], v, args[3][:, None], init, norm
    )
    np.testing.assert_allclose(y0, ys[:, 0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s0[1], fs, rtol=1e-6, atol=1e-6)


def test_decode_update_aliases_the_stacked_state_and_normaliser():
    """The kernel's state and normaliser operands are its first two
    results (operand 0 is the prefetched layer index): a donated stack is
    updated where it lies."""
    q, k, v, log_g, init, norm = scan_inputs(2, 1, 4, 2, 16, seed=8)
    state, norms = jnp.stack([init, init]), jnp.stack([norm, norm])
    text = str(jax.make_jaxpr(
        lambda *a: pr.retention_decode_update(*a, interpret=True)
    )(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], state, norms, 1))
    assert "name=retention_decode_update" in text
    assert "input_output_aliases=((1, 0), (2, 1))" in text


# ---- the model -------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = bm.BrumbyConfig.tiny(dtype=F32)
    return cfg, bm.init_params(jax.random.key(0), cfg)


def file_config(cfg):
    """The tiny config as a configuration FILE, for the reference."""
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "retention_eps": cfg.retention_eps,
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
    return jitted_reference(cfg)(params, jnp.asarray(seq))[: len(tokens)]


def reference_state(tiny, tokens, layer, pad_to=96):
    cfg, params = tiny
    seq = np.zeros(pad_to, np.int32)
    seq[: len(tokens)] = tokens
    return ref.state_at(
        params, jnp.asarray(seq), file_config(cfg), layer=layer, stop=len(tokens),
        block=32,
    )


def assert_logits_close(got, want):
    """The state form computes a weight ``(q . k)^2`` as a sum of d (d +
    1) / 2 products of mixed sign, the attention form as one square: at
    a stream's first positions, where a head's normaliser can be as
    small as ``retention_eps``, float32 cancellation shows (1e-3 of a
    logit at the worst seeds, 1e-5 at most); a fault in the recurrence
    shows as 0.1 and more."""
    np.testing.assert_allclose(got, want, atol=3e-3)


def test_the_cache_has_the_kinds_table_at_this_familys_shapes(tiny):
    """Nothing new in ``llama.CACHE_KINDS``: the state and its
    normaliser are the STATE kind's two stacks, float32 whatever the
    cache's dtype, and there is NO other stack: ``max_len`` costs
    nothing."""
    cfg, _ = tiny
    cache = init_cache(cfg, 3, 32, jnp.bfloat16, widest_part=16)
    state, norm = llama.STATE_STACKS
    assert set(cache) == {state, norm}
    assert cache[state].shape == (4, 3, 2, 9, 16, 16) and cache[state].dtype == F32
    assert cache[norm].shape == (4, 3, 2, 9, 16) and cache[norm].dtype == F32
    assert cache_bytes(cache) == {
        "full": 0, "window": 0, "indexed": 0,
        "state": (cache[state].size + cache[norm].size) * 4,
    }
    longer = init_cache(cfg, 3, 4096, jnp.bfloat16)
    assert cache_bytes(longer) == cache_bytes(cache)
    assert cfg.layer_kinds == (llama.STATE,)
    # the published head: 65 rows of phi, 34 MB a layer a stream
    full = bm.BrumbyConfig().state_leaves(jnp.bfloat16)
    assert full[state] == ((8, 65, 128, 128), F32) and full[norm] == ((8, 65, 128), F32)


def test_uncached_forward_is_the_reference(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(1).integers(1, 256, size=21)
    want = reference_logits(tiny, tokens)
    got = bm.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    assert_logits_close(got, want)


@pytest.mark.parametrize("padded", [False, True], ids=["whole", "right-padded"])
def test_prefill_in_parts_then_decode_through_the_state_is_the_reference(tiny, padded):
    cfg, params = tiny
    tokens = np.random.default_rng(2).integers(1, 256, size=29)
    want = reference_logits(tiny, tokens)
    cache = init_cache(cfg, 1, 32, F32)
    # a whole part of 8, then a final part of 5 tokens (run at 8 where padded)
    n, start = 13, 0
    for width, real in ((8, 8), (8 if padded else 5, 5)):
        part = np.zeros(width, np.int32)
        part[:real] = tokens[start:start + real]
        lg, cache = bm.forward_with_cache(
            params, jnp.asarray(part)[None], cfg, cache, jnp.int32(start),
            positions=start + jnp.arange(width)[None],
            token_mask=jnp.arange(width)[None] < real,
        )
        assert_logits_close(lg[0, :real], want[start:start + real])
        start += real
    for t in range(n, len(tokens)):
        lg, cache = bm.forward_with_cache(
            params, jnp.asarray(tokens[t:t + 1])[None], cfg, cache,
            jnp.full((1,), t, jnp.int32), positions=jnp.full((1, 1), t),
            token_mask=jnp.ones((1, 1), bool),
        )
        assert_logits_close(lg[0, 0], want[t])
    # what the cache now holds is the reference's direct sum, every layer
    for layer in (0, cfg.num_layers - 1):
        want_state = reference_state(tiny, tokens, layer)
        got = to_symmetric_half(cache[llama.STATE_STACKS[0]][layer, 0])
        np.testing.assert_allclose(
            got, want_state, atol=2e-5 * float(jnp.abs(want_state).max())
        )


def test_the_reference_in_a_lower_precision_is_not_the_reference(tiny):
    cfg, params = tiny
    config = file_config(cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(1, 256, size=32))
    sound = ref.logits(params, tokens, config)
    low = ref.logits(params, tokens, config, ref.Precision(act="int8"))
    assert float(jnp.abs(low - sound).max()) > 1e-3
    state = ref.state_at(params, tokens, config, layer=1, block=16)
    carried = ref.state_at(
        params, tokens, config, layer=1, prec=ref.Precision(state="bf16")
    )
    rel = float(jnp.abs(carried - state).max() / jnp.abs(state).max())
    assert 1e-4 < rel < 5e-2


def test_an_idle_row_keeps_its_state_and_normaliser(tiny):
    cfg, params = tiny
    cache = init_cache(cfg, 2, 16, F32)
    cache = {name: leaf + 0.5 for name, leaf in cache.items()}
    _, new = bm.forward_with_cache(
        params, jnp.asarray([[5], [7]]), cfg, cache, jnp.asarray([3, 3]),
        positions=jnp.asarray([[3], [3]]),
        token_mask=jnp.asarray([[True], [False]]),
    )
    for name in llama.STATE_STACKS:
        np.testing.assert_array_equal(new[name][:, 1], cache[name][:, 1])
        assert not np.array_equal(new[name][:, 0], cache[name][:, 0])


def test_several_tokens_a_row_at_per_row_offsets_are_refused(tiny):
    cfg, params = tiny
    cache = init_cache(cfg, 2, 16, F32)
    with pytest.raises(NotImplementedError, match="state after each"):
        bm.forward_with_cache(
            params, jnp.ones((2, 3), jnp.int32), cfg, cache, jnp.asarray([1, 2]),
            positions=jnp.ones((2, 3), jnp.int32),
        )


# ---- through the engine ----------------------------------------------------

ENGINE = dict(
    n_slots=3, max_len=96, chunk=4, prompt_buckets=(8, 16), prefill_chunk=16,
    cache_dtype=F32,
)


@pytest.fixture(scope="module")
def engine(tiny):
    cfg, params = tiny
    eng = DecodeEngine(params, cfg, **ENGINE)
    yield eng
    eng.stop()


def greedy_by_reference(tiny, prompt, n):
    """The reference's own greedy continuation, and the margin by which
    each token led."""
    toks, margins = list(prompt), []
    for _ in range(n):
        lg = reference_logits(tiny, toks, pad_to=96)
        best = jnp.sort(lg[-1])[-2:]
        margins.append(float(best[1] - best[0]))
        toks.append(int(jnp.argmax(lg[-1])))
    return toks[len(prompt):], margins


def assert_served_is_the_reference(tiny, prompt, served):
    want, margins = greedy_by_reference(tiny, prompt, len(served))
    for i, (a, b, m) in enumerate(zip(served, want, margins)):
        if m < 1e-2:
            return  # a near tie: what follows may differ legitimately
        assert a == b, (i, served, want)


def test_a_stack_without_keys_and_values_costs_the_engine_nothing_a_position(tiny, engine):
    assert engine.cache_bytes["full"] == engine.cache_bytes["window"] == 0
    assert engine.cache_bytes["state"] == 3 * 4 * 2 * 9 * 16 * (16 + 1) * 4


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


@pytest.mark.parametrize("rem, bucket", [(3, 4), (7, 8)])
def test_a_narrow_final_part_leaves_what_the_whole_prompt_leaves(
    tiny, final_part_against_whole, rem, bucket
):
    cfg, params = tiny
    _, parts, whole = final_part_against_whole(params, cfg, rem, bucket, 96)
    assert set(parts) == set(llama.STATE_STACKS)
    for name in parts:
        scale = float(np.abs(whole[name]).max())
        assert scale > 0
        np.testing.assert_allclose(
            parts[name], whole[name], atol=1e-4 * scale, err_msg=name
        )


def test_a_stopped_engines_slot_holds_the_state_of_its_stream(tiny):
    """Stopped with a request still decoding: the slot's row of the
    state is the reference's direct sum over the prompt and every token
    served but the last (prefill in parts, the splice, then decode steps
    beside an idle and a finished slot)."""
    cfg, params = tiny
    eng = DecodeEngine(params, cfg, **ENGINE)
    try:
        rng = np.random.default_rng(14)
        eng.submit(rng.integers(1, 256, size=5).tolist(), max_tokens=2).result(timeout=300)
        prompt = rng.integers(1, 256, size=21).tolist()
        req = eng.submit(prompt, max_tokens=60, stream=True)
        stream = req.iter_tokens()
        for _ in range(9):
            next(stream)
    finally:
        eng.stop()
    assert not req.complete and len(req.tokens) >= 9
    held = eng.slot_state(req.slot)
    state, norm = llama.STATE_STACKS
    assert set(held) == {state, norm}
    assert held[state].shape == (4, 2, 9, 16, 16) and held[norm].shape == (4, 2, 9, 16)
    taken = prompt + list(req.tokens)[:-1]
    for layer in (0, 3):
        want = reference_state(tiny, taken, layer)
        np.testing.assert_allclose(
            to_symmetric_half(jnp.asarray(held[state][layer])), want,
            atol=5e-5 * float(jnp.abs(want).max()),
        )


def test_a_prefix_cache_and_a_draft_are_refused_beside_state(tiny):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="prefix entry"):
        DecodeEngine(params, cfg, prefix_cache_entries=4, **ENGINE)
    with pytest.raises(NotImplementedError, match="no position to take back"):
        DecodeEngine(params, cfg, draft_params=params, draft_cfg=cfg, **ENGINE)


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
    assert family_forward(cfg)[1] is bm.forward_with_cache
    for i, n in enumerate(lengths):
        assert_served_is_the_reference(
            tiny, prompts[i, :n].tolist(), out["tokens"][i].tolist()
        )
