"""The ``keye_vl`` family (every layer's queries attend only the keys a
learned indexer picks: a third stack in the cache, a threshold found
with no sort, a gather over the stacked keys and values) at a tiny size
on the CPU: the pieces of ``ops/sparse_attention.py`` against their plain
forms and ``lax.top_k``, the three-stream rotation, the cached forward
against the reference's full forward on LOGITS and on the selection
itself, the shares of a layer against the uncut layer, and the engine's
handling of the third stack: parts, reused slots, a stopped engine's
slot, the refusals."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import engine as engine_mod
from odh_kubeflow_tpu.models import keye_vl as kv
from odh_kubeflow_tpu.models import llama
from odh_kubeflow_tpu.models.engine import DecodeEngine
from odh_kubeflow_tpu.models.generate import cache_bytes, family_forward, init_cache
from odh_kubeflow_tpu.ops import select
from odh_kubeflow_tpu.ops import sparse_attention as sa
from odh_kubeflow_tpu.ops.pallas_decode_attention import decode_attend
from odh_kubeflow_tpu.ops.rope import apply_rope, rope_angles, stream_angles
from odh_kubeflow_tpu.reference import keye_vl as ref

F32 = jnp.float32


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


# ---- three position streams ------------------------------------------------


def test_three_equal_streams_are_plain_rope():
    pos = jax.random.randint(jax.random.key(0), (2, 9), 0, 5000)
    plain = rope_angles(pos, 16, 1e4)
    three = stream_angles(jnp.stack([pos] * 3), 16, 1e4, (2, 3, 3))
    for a, b in zip(plain, three):
        np.testing.assert_array_equal(a, b)


def test_three_different_streams_are_the_references():
    pos = jax.random.randint(jax.random.key(1), (3, 1, 11), 0, 300)
    x = jax.random.normal(jax.random.key(2), (1, 11, 4, 16))
    got = apply_rope(x, *stream_angles(pos, 16, 1e4, (2, 3, 3)))
    want = ref.rotate(x[0], ref.stream_angles(pos[:, 0], 8, 1e4, (2, 3, 3)))
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    # ... and a frequency of the second section turns by the height alone
    moved = pos.at[1].add(7)
    other = apply_rope(x, *stream_angles(moved, 16, 1e4, (2, 3, 3)))
    same = np.isclose(got, other, atol=1e-6).all(axis=(0, 1, 2))
    assert same.tolist() == ([True] * 2 + [False] * 3 + [True] * 3) * 2


# ---- the k-th largest with no sort ----------------------------------------


def _rows(kind):
    rng = np.random.default_rng(5)
    x = rng.normal(size=(6, 40)).astype(np.float32)
    valid = np.ones_like(x, bool)
    if kind == "ties":
        x = np.round(x * 2) / 2
    elif kind == "negative-zero":
        x = np.where(rng.random(x.shape) < 0.5, np.float32(-0.0), np.float32(0.0))
        x[:, :5] = rng.normal(size=(6, 5))
    elif kind == "masked-tails":
        valid = np.arange(40)[None, :] < np.array([40, 31, 17, 9, 8, 3])[:, None]
    return x, valid


@pytest.mark.parametrize("k", [1, 8, 40, 64], ids=lambda k: f"k{k}")
@pytest.mark.parametrize("kind", ["plain", "ties", "negative-zero", "masked-tails"])
def test_kth_largest_is_top_ks_kth_value(kind, k):
    x, valid = _rows(kind)
    got = np.asarray(select.kth_largest(jnp.asarray(x), k, jnp.asarray(valid)))
    for r in range(x.shape[0]):
        row = x[r][valid[r]]
        if k > row.size:
            assert got[r] == -np.inf  # k past the row: everything is kept
        else:
            want = jax.lax.top_k(jnp.asarray(row), k)[0][-1]
            assert got[r] == float(want), (r, got[r], want)


def test_the_samplers_survivors_are_unchanged_by_the_move():
    assert engine_mod._largest_key is select._largest_key
    assert engine_mod._ordered_keys is select._ordered_keys
    logits = jax.random.normal(jax.random.key(3), (4, 300)) * 3
    top_k = jnp.asarray([0, 5, 40, 300], jnp.int32)
    got = engine_mod.mask_logits_rowwise(
        logits, jnp.ones((4,)), top_k, jnp.zeros((4,))
    )
    for r, k in enumerate(top_k.tolist()):
        kth = -jnp.inf if k == 0 else jnp.sort(logits[r])[-k]
        np.testing.assert_array_equal(jnp.isfinite(got[r]), logits[r] >= kth)


# ---- the selection ----------------------------------------------------------


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("t", [3, 5, 6, 30], ids=["under", "at", "one-over", "over"])
def test_the_selection_is_lax_top_ks(t, ties):
    """``min(topk, t + 1)`` positions, a tie at the edge to the lower
    position: for a query with fewer, exactly as many, and more causal
    keys than ``topk`` = 6."""
    scores = jax.random.normal(jax.random.key(t), (1, 48))
    if ties:
        scores = sa._no_negative_zero(jnp.round(scores))
    q_pos = jnp.asarray([t])
    ids, ok = ref.select(scores, q_pos, 6)
    want = np.zeros(48, bool)
    want[np.asarray(ids[0])[np.asarray(ok[0])]] = True
    valid = sa.visible(q_pos[None], None, 48)[0]
    thr, cut = sa.select_threshold(scores, valid, 6)
    got = sa.selected(scores, valid, thr, cut)
    np.testing.assert_array_equal(got[0], want)
    assert int(got.sum()) == min(6, t + 1)
    listed, count = sa.compact_positions(got, 6)
    assert int(count[0]) == min(6, t + 1)
    np.testing.assert_array_equal(
        listed[0, : int(count[0])], np.flatnonzero(want)
    )
    assert (np.asarray(listed[0, int(count[0]):]) == 48).all()


@pytest.mark.parametrize("N,k", [(256, 40), (24, 6), (384, 384)])
def test_compaction_lists_the_kept_positions_in_order(N, k):
    keep = jax.random.uniform(jax.random.key(N), (128, N)) < 0.2
    ids, count = sa.compact_positions(keep, k)
    for r in (0, 17, 127):
        want = np.flatnonzero(np.asarray(keep[r]))[:k]
        assert int(count[r]) == int(keep[r].sum())
        np.testing.assert_array_equal(ids[r, : len(want)], want)
        assert (np.asarray(ids[r, len(want):]) == N).all()


# ---- the kernels, interpreted ------------------------------------------------


@pytest.mark.parametrize(
    "S,offset", [(1, (37, 200)), (40, 130), (16, 0)], ids=["decode", "part", "first"]
)
def test_index_scores_is_the_plain_einsum_at_a_layer_of_the_stack(S, offset):
    k = jax.random.split(jax.random.key(7), 3)
    ik = jax.random.normal(k[0], (3, 2, 16, 256))
    qi = jax.random.normal(k[1], (2, S, 4, 16))
    w = jax.random.normal(k[2], (2, S, 4))
    off = jnp.asarray(offset, jnp.int32)
    got = sa.index_scores(qi, w, ik, 1, off, block_k=128, interpret=True)
    want = sa.index_scores_plain(qi, w, ik, 1)
    q_pos = jnp.broadcast_to(off, (2,))[:, None] + jnp.arange(S)
    seen = sa.visible(q_pos, None, 256)
    np.testing.assert_allclose(
        jnp.where(seen, got, 0), jnp.where(seen, want, 0), atol=1e-5
    )
    # a block no query of the row can see is not computed
    if S == 16:
        assert bool(jnp.all(jnp.isneginf(got[:, :, 128:])))
    assert not bool(jnp.any(jnp.signbit(got) & (got == 0)))


def test_a_key_is_written_in_place_as_a_column():
    ik = jax.random.normal(jax.random.key(8), (3, 2, 16, 256))
    new = jax.random.normal(jax.random.key(9), (2, 16))
    pos = jnp.asarray([5, 200])
    want = ik.at[2, jnp.arange(2), :, pos].set(new)
    got = sa.write_index_keys(ik + 0, new, 2, pos, interpret=True)
    np.testing.assert_array_equal(got, want)


def _attend_operands(S, seed=10):
    k = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(k[0], (2, S, 4, 64))
    ck = jax.random.normal(k[1], (3, 2, 256, 128))
    cv = jax.random.normal(k[2], (3, 2, 256, 128))
    scores = sa._no_negative_zero(jnp.round(jax.random.normal(k[3], (2, S, 256)) * 2) / 2)
    return q, ck, cv, scores


def test_attention_under_a_selection_is_the_masked_dense_one():
    S, off = 24, jnp.int32(100)
    q, ck, cv, scores = _attend_operands(S)
    kv_mask = jnp.arange(256)[None] < off + S
    q_pos = off + jnp.broadcast_to(jnp.arange(S), (2, S))
    seen = sa.visible(q_pos, jnp.broadcast_to(kv_mask, (2, 256)), 256)
    thr, cut = sa.select_threshold(scores, seen, 20)
    keep = sa.selected(scores, seen, thr, cut)
    assert int((cut < 256).sum()) > 0  # some edge is a tie with a surplus
    got = decode_attend(
        q, ck, cv, 1, off, jnp.broadcast_to(kv_mask, (2, 256)),
        select=(scores, thr, cut), block_k=128, interpret=True,
    )
    heads = lambda c: c[1].reshape(2, 256, 2, 64)  # noqa: E731
    want = sa.masked_attention(q, heads(ck), heads(cv), keep)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_predicate_off_leaves_decode_attend_as_it_was():
    from odh_kubeflow_tpu.ops.attention import dense_attention

    S, off = 24, jnp.int32(100)
    q, ck, cv, scores = _attend_operands(S, seed=11)
    kv_mask = jnp.broadcast_to(jnp.arange(256)[None] < off + S, (2, 256))
    plain = decode_attend(q, ck, cv, 1, off, kv_mask, block_k=128, interpret=True)
    heads = lambda c: c[1].reshape(2, 256, 2, 64)  # noqa: E731
    want = dense_attention(
        q, heads(ck), heads(cv), causal=True, q_offset=off, kv_mask=kv_mask
    )
    np.testing.assert_allclose(plain, want, atol=2e-5)
    # a selection that keeps everything is no selection
    every = (
        scores, jnp.full((2, S), -jnp.inf), jnp.full((2, S), 256, jnp.int32),
    )
    kept = decode_attend(
        q, ck, cv, 1, off, kv_mask, select=every, block_k=128, interpret=True
    )
    np.testing.assert_allclose(kept, plain, atol=2e-6)
    # and without the operand the program has no trace of it
    lowered = lambda **kw: jax.jit(functools.partial(  # noqa: E731
        decode_attend, block_k=128, interpret=True, **kw
    )).lower(q, ck, cv, 1, off, kv_mask).as_text()
    assert "2x24x256xf32" in lowered(select=every)
    assert "2x24x256xf32" not in lowered()


# ---- the model ---------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = kv.KeyeVLConfig.tiny(dtype=F32)
    return cfg, kv.init_params(jax.random.key(0), cfg)


def file_config(cfg, held=None):
    """The tiny config as a configuration FILE, for the reference."""
    first, count = held or cfg.experts_held
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
        "rope_scaling": {"mrope_section": list(cfg.mrope_section)},
        "sa_config": {
            "indexer_num_heads": cfg.index_heads,
            "indexer_head_dim": cfg.index_dim, "topk": cfg.index_topk,
        },
        "num_experts_per_tok": cfg.num_experts_per_tok, "norm_topk_prob": True,
        "deployment": {"experts_held": {"first": first, "count": count}},
    }


@functools.lru_cache(maxsize=None)
def jitted_reference(cfg):
    config = file_config(cfg)
    return jax.jit(lambda params, seq: ref.logits(params, seq, config)[0])


def reference_logits(tiny, tokens, pad_to=96):
    cfg, params = tiny
    seq = np.zeros(pad_to, np.int32)
    seq[: len(tokens)] = tokens
    return jitted_reference(cfg)(params, jnp.asarray(seq))[: len(tokens)]


def test_the_cache_has_the_new_kinds_three_stacks(tiny):
    cfg, _ = tiny
    assert llama.CACHE_KINDS[llama.INDEXED] == ("sk", "sv", "ik")
    assert llama.kind_of(llama.INDEXED) == llama.INDEXED
    cache = init_cache(cfg, 5, 64, jnp.bfloat16, widest_part=16)
    assert cache["sk"].shape == cache["sv"].shape == (3, 5, 64, 32)
    # one narrow head, positions along the lanes
    assert cache["ik"].shape == (3, 5, 8, 64)
    assert cache["sel_stats"].shape == (2,) and cache["moe_stats"].shape == (4,)
    assert [llama.stack_kind(n) for n in ("sk", "sv", "ik", "sel_stats")] == [
        llama.INDEXED
    ] * 3 + [None]
    by_kind = cache_bytes(cache)
    assert by_kind[llama.INDEXED] == 3 * 5 * 64 * (32 + 32 + 8) * 2
    assert by_kind["full"] == by_kind["window"] == by_kind[llama.STATE] == 0


def test_uncached_forward_is_the_reference(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 1, 256)
    got = kv.forward(params, tokens, cfg)
    for b in range(2):
        want = ref.logits(params, tokens[b], file_config(cfg))[0]
        np.testing.assert_allclose(got[b], want, atol=2e-5)


def test_three_streams_reach_the_program_as_they_reach_the_reference(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(2), (1, 24), 1, 256)
    t = jnp.arange(24)
    pos = jnp.stack([t, t // 4, t % 4])[:, None, :]  # a grid after the text
    got = kv.forward(params, tokens, cfg, positions=pos)
    want = ref.logits(params, tokens[0], file_config(cfg), positions=pos[:, 0])[0]
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    text = kv.forward(params, tokens, cfg)
    assert float(jnp.abs(got - text).max()) > 1e-3


def _through_the_cache(tiny, tokens, lengths, part, extra=None):
    """Rows right-padded to one width: prefill in parts of ``part`` at a
    scalar offset, then decode steps at per-row offsets. Returns each
    row's logits at its own positions and the final cache."""
    cfg, params = tiny
    B, width = tokens.shape
    lengths = np.asarray(lengths)
    prompt = -(-int(lengths.min()) // part) * part - part  # whole parts all rows have
    prompt = max(prompt, part)
    max_len = 96
    cache = init_cache(cfg, B, max_len, F32, widest_part=part)
    cache.update(extra(cfg, B, max_len) if extra else {})
    slots = jnp.arange(max_len)[None]
    out = [[] for _ in range(B)]
    for start in range(0, prompt, part):
        pos = jnp.broadcast_to(start + jnp.arange(part), (B, part))
        lg, cache = kv.forward_with_cache(
            params, tokens[:, start:start + part], cfg, cache, jnp.int32(start),
            positions=pos, kv_mask=jnp.broadcast_to(slots < start + part, (B, max_len)),
            token_mask=jnp.ones((B, part), bool),
        )
        for b in range(B):
            out[b].append(lg[b])
    at = np.full(B, prompt)
    while (at < lengths).any():
        live = at < lengths
        idx = jnp.asarray(np.minimum(at, lengths - 1), jnp.int32)
        tok = jnp.take_along_axis(tokens, idx[:, None], axis=1)
        lg, cache = kv.forward_with_cache(
            params, tok, cfg, cache, idx, positions=idx[:, None],
            kv_mask=slots <= idx[:, None], token_mask=jnp.asarray(live)[:, None],
        )
        for b in np.flatnonzero(live):
            out[b].append(lg[b])
        at = at + live
    return [jnp.concatenate(o) for o in out], cache


@pytest.mark.parametrize("padded", [False, True], ids=["whole", "right-padded"])
def test_prefill_in_parts_then_decode_through_the_cache_is_the_reference(tiny, padded):
    """On LOGITS, with ``topk`` = 6 of up to 40 positions: nearly every
    query selects, in the second part under the mask and in every decode
    step over gathered rows."""
    lengths = (40, 27) if padded else (40, 40)
    tokens = jax.random.randint(jax.random.key(3), (2, 40), 1, 256)
    got, cache = _through_the_cache(tiny, tokens, lengths, part=8)
    for b, n in enumerate(lengths):
        want = reference_logits(tiny, np.asarray(tokens[b, :n]).tolist())
        np.testing.assert_allclose(got[b], want, atol=3e-5)
    # what the queries could see and what they attended, the three layers over
    seen = sum(n * (n + 1) // 2 for n in lengths)
    attended = sum(sum(min(6, t + 1) for t in range(n)) for n in lengths)
    assert cache["sel_stats"].tolist() == [3 * seen, 3 * attended]


def test_the_programs_selection_is_the_references(tiny):
    """The leaf ``index_topk``, filled by parts and by decode steps, is
    ``S_t`` of the reference at every layer: the tie rule included (the
    seeded scores have none; ``test_the_selection_is_lax_top_ks`` has)."""
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(4), (1, 40), 1, 256)
    leaf = lambda cfg, B, n: {  # noqa: E731
        "index_topk": jnp.full((cfg.num_layers, B, n, cfg.index_topk), -2, jnp.int32),
        "index_inputs": jnp.zeros(
            (cfg.num_layers, B, n, cfg.index_heads * (cfg.index_dim + 1))
        ),
    }
    _, cache = _through_the_cache(tiny, tokens, (40,), part=8, extra=leaf)
    got = np.asarray(cache["index_topk"][:, 0, :40])
    # each query's q^I and w as the scores took them, and nothing past them
    assert bool(jnp.all(jnp.any(cache["index_inputs"][:, 0, :40] != 0, axis=-1)))
    assert not bool(jnp.any(cache["index_inputs"][:, 0, 40:]))
    for layer in range(cfg.num_layers):
        _, ids, ok, _ = ref.index_and_selection(
            params, jnp.pad(tokens[0], (0, 8)), file_config(cfg), layer=layer
        )
        for t in range(40):
            want = np.sort(np.asarray(ids[t])[np.asarray(ok[t])])
            n = len(want)
            np.testing.assert_array_equal(got[layer, t, :n], want, err_msg=(layer, t))
            assert (got[layer, t, n:] == -1).all()


def test_the_gathered_decode_step_is_the_masked_dense_one(tiny):
    """One decode step over a filled cache: attention over the gathered
    rows against dense attention over the whole layer under the mask."""
    cfg, _ = tiny
    k = jax.random.split(jax.random.key(12), 8)
    B, S_max = 3, 64
    cache = {
        "sk": jax.random.normal(k[0], (2, B, S_max, 32)),
        "sv": jax.random.normal(k[1], (2, B, S_max, 32)),
        "ik": jax.random.normal(k[2], (2, B, 8, S_max)),
        "sel_stats": jnp.zeros((2,), jnp.int32),
    }
    q = jax.random.normal(k[3], (B, 1, 4, 16))
    kk, vv = jax.random.normal(k[4], (2, B, 1, 2, 16))
    qi = jax.random.normal(k[5], (B, 1, 2, 8))
    ki = jax.random.normal(k[6], (B, 1, 8))
    wi = jax.random.normal(k[7], (B, 1, 2))
    index = jnp.asarray([3, 40, 63], jnp.int32)
    kv_mask = jnp.arange(S_max)[None] <= index[:, None]
    layer = llama.CacheLayer(jnp.int32(1), llama.INDEXED_STACKS, None, jnp.int32(1))
    got, new = llama.indexed_write_and_attend(
        q, kk, vv, qi, ki, wi, cache, layer, index, kv_mask, 6
    )
    rows = jnp.arange(B)
    np.testing.assert_array_equal(new["ik"][1, rows, :, index], ki[:, 0])
    np.testing.assert_array_equal(new["sk"][1, rows, index], kk.reshape(B, 32))
    scores = sa.index_scores_plain(qi, wi, new["ik"], 1)
    seen = sa.visible(index[:, None], kv_mask, S_max)
    keep = sa.selected(scores, seen, *sa.select_threshold(scores, seen, 6))
    assert keep.sum(-1)[:, 0].tolist() == [4, 6, 6]
    heads = lambda c: c[1].reshape(B, S_max, 2, 16)  # noqa: E731
    want = sa.masked_attention(q, heads(new["sk"]), heads(new["sv"]), keep)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert new["sel_stats"].tolist() == [4 + 41 + 64, 4 + 6 + 6]
    # the other layer's rows are untouched
    for name in ("sk", "sv", "ik"):
        np.testing.assert_array_equal(new[name][0], cache[name][0])


def test_several_tokens_a_row_at_per_row_offsets_are_refused(tiny):
    cfg, params = tiny
    cache = init_cache(cfg, 2, 32, F32)
    with pytest.raises(NotImplementedError, match="one selection a token"):
        kv.forward_with_cache(
            params, jnp.ones((2, 3), jnp.int32), cfg, cache,
            jnp.asarray([4, 9], jnp.int32), positions=jnp.zeros((2, 3), jnp.int32),
        )


def test_the_reference_in_a_lower_precision_is_not_the_reference(tiny):
    cfg, params = tiny
    q8 = lambda a: {  # noqa: E731 — an int8 leaf, so that the control has a matmul to round into
        "q": jnp.round(a / (jnp.abs(a).max(-2, keepdims=True) / 127)).astype(jnp.int8),
        "scale": jnp.abs(a).max(-2, keepdims=True) / 127,
    }
    params = {**params, "layers": {
        n: q8(v) if n in ("wq", "wk", "wv", "wo", "wq_idx", "wk_idx") else v
        for n, v in params["layers"].items()
    }}
    tokens = jax.random.randint(jax.random.key(5), (64,), 1, 256)
    config = file_config(cfg)
    sound, _, ids, ok = ref.logits_and_selection(
        params, tokens, config, keep=jnp.arange(8, 64)
    )
    for prec in (ref.Precision(act="int8"), ref.Precision(index="bf16")):
        low, _, low_ids, _ = ref.logits_and_selection(
            params, tokens, config, prec, keep=jnp.arange(8, 64)
        )
        assert float(jnp.abs(low - sound).max()) > 1e-3, prec
        # ... and it is WHICH keys are read that moves
        assert bool(jnp.any(jnp.sort(low_ids, -1) != jnp.sort(ids, -1))), prec


# ---- the shares add up --------------------------------------------------------


def test_the_shares_routed_parts_sum_to_the_uncut_layer(tiny):
    """Four chips hold two experts each of the router's eight: the parts
    of a layer's mixture they give, each with its OWN experts, sum to
    what the reference gives holding all eight; a chip's sliced head is
    rows of the whole head's logits."""
    cfg = kv.KeyeVLConfig.tiny(dtype=F32, experts_held=(0, 8))
    whole = kv.init_params(jax.random.key(6), cfg)
    tokens = jax.random.randint(jax.random.key(7), (16,), 1, 256)
    config = file_config(cfg)
    x = whole["embed"][tokens].astype(F32) * 3
    small = {n: v[1] for n, v in whole["layers"].items() if n not in ref.BANKS}
    banks = {n: whole["layers"][n] for n in ref.BANKS}
    uncut, top = ref.ffn(x, small, banks, 1, config, ref.SOUND, held=(0, 8))
    total = jnp.zeros_like(uncut)
    for first in (0, 2, 4, 6):
        share_cfg = kv.KeyeVLConfig.tiny(dtype=F32, experts_held=(first, 2))
        share_banks = {n: v[:, first:first + 2] for n, v in banks.items()}
        part, top_s = ref.ffn(
            x, small, share_banks, 1, config, ref.SOUND, held=(first, 2)
        )
        np.testing.assert_array_equal(top_s, top)  # the router is every chip's
        # the program's share: its own experts' part and nothing else
        prog, _, _ = kv._ffn(share_cfg, x[None], small, share_banks, 1, None)
        np.testing.assert_allclose(prog[0] - x, part, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, uncut, atol=2e-5)
    assert float(jnp.abs(uncut).max()) > 0.1


def test_the_sliced_head_is_rows_of_the_whole_heads_logits(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.key(8), (16,), 1, 64)
    config = file_config(cfg)
    whole = ref.logits(params, tokens, config)[0]
    sliced = {**params, "lm_head": params["lm_head"][:, :64]}
    np.testing.assert_allclose(
        ref.logits(sliced, tokens, config)[0], whole[:, :64], atol=1e-5
    )
    got = kv.forward(sliced, tokens[None], dataclasses.replace(cfg, vocab_size=64))
    np.testing.assert_allclose(got[0], whole[:, :64], atol=2e-5)


# ---- the engine ---------------------------------------------------------------

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
    toks, margins = list(prompt), []
    for _ in range(n):
        lg = reference_logits(tiny, toks)
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


@pytest.mark.parametrize(
    "length", [3, 5, 13, 40, 33],
    ids=["under-topk", "bucket-8", "bucket-16", "parts", "parts-final-1"],
)
def test_engine_serves_the_references_greedy_tokens(tiny, engine, length):
    """Prompts shorter than ``topk`` = 6 (every key attended), in one
    bucket, and admitted in parts (the later parts under the mask); every
    decode step past six positions over gathered rows."""
    prompt = np.random.default_rng(length).integers(1, 256, size=length).tolist()
    calls, causal = engine.prefill_calls, engine.sel_causal_rows
    served = engine.submit(prompt, max_tokens=6).result(timeout=300)
    assert_served_is_the_reference(tiny, prompt, served)
    assert engine.prefill_calls - calls == (1 if length <= 16 else -(-length // 16))
    assert engine.sel_causal_rows > causal


def test_the_engine_counts_what_its_decode_steps_saw_and_attended(tiny):
    cfg, params = tiny
    eng = DecodeEngine(params, cfg, **ENGINE)
    try:
        prompt = np.random.default_rng(21).integers(1, 256, size=10).tolist()
        served = eng.submit(prompt, max_tokens=9).result(timeout=300)
        eng.submit(prompt[:4], max_tokens=1).result(timeout=300)  # one more turn
    finally:
        eng.stop()
    # the prefill emits the first token; each later one is a decode step
    # whose query sits at position 10, 11, ...
    steps = len(served) - 1
    seen = sum(10 + i + 1 for i in range(steps))
    assert eng.sel_causal_rows == 3 * seen
    assert eng.sel_attended_rows == 3 * 6 * steps
    assert eng.prefill_pairs == 10 * 11 // 2 + 4 * 5 // 2
    totals = eng._turn_totals()
    assert totals["sel_causal_rows"] == eng.sel_causal_rows
    assert totals["sel_attended_rows"] == eng.sel_attended_rows


def test_a_reused_slot_keeps_nothing_of_the_last_requests_index_keys(tiny, engine):
    rng = np.random.default_rng(11)
    long = [rng.integers(1, 256, size=30).tolist() for _ in range(3)]
    for r in [engine.submit(p, max_tokens=8) for p in long]:
        r.result(timeout=300)
    prompt = rng.integers(1, 256, size=4).tolist()
    served = engine.submit(prompt, max_tokens=6).result(timeout=300)
    assert_served_is_the_reference(tiny, prompt, served)


def test_a_stopped_engines_slot_holds_its_streams_index_keys(tiny):
    """Stopped with a request still decoding: the slot's rows of the
    third stack are the reference's ``k^I`` over the prompt (admitted in
    parts and spliced) and every token served but the last (written a
    column a step), and nothing of the request the slot served before."""
    cfg, params = tiny
    eng = DecodeEngine(params, cfg, **{**ENGINE, "n_slots": 1})
    try:
        rng = np.random.default_rng(14)
        eng.submit(rng.integers(1, 256, size=60).tolist(), max_tokens=2).result(timeout=300)
        prompt = rng.integers(1, 256, size=21).tolist()
        req = eng.submit(prompt, max_tokens=60, stream=True)
        stream = req.iter_tokens()
        for _ in range(9):
            next(stream)
    finally:
        eng.stop()
    assert not req.complete and len(req.tokens) >= 9
    held = eng.slot_state(req.slot, llama.INDEXED)
    assert set(held) == set(llama.INDEXED_STACKS)
    assert held["ik"].shape == (3, 8, 96)
    taken = prompt + list(req.tokens)[:-1]
    seq = np.zeros(96, np.int32)
    seq[: len(taken)] = taken
    want = ref.index_keys(params, jnp.asarray(seq), file_config(cfg))[: len(taken)]
    np.testing.assert_allclose(held["ik"][0][:, : len(taken)].T, want, atol=2e-5)
    # past the stream's own positions the splice left the fresh cache's zeros
    assert not held["ik"][:, :, len(taken) + 1:].any()
    assert eng.slot_state(req.slot) == {}  # no recurrent state here


def test_a_prefix_cache_and_a_draft_are_refused_beside_index_keys(tiny):
    cfg, params = tiny
    with pytest.raises(NotImplementedError, match="indexer keys are zeros"):
        DecodeEngine(params, cfg, prefix_cache_entries=4, **ENGINE)
    with pytest.raises(NotImplementedError, match="one selection each"):
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
        params, jnp.asarray(prompts), cfg,
        GenerateConfig(max_new_tokens=4, cache_dtype=F32),
        prompt_lengths=jnp.asarray(lengths),
    )
    assert family_forward(cfg)[1] is kv.forward_with_cache
    # a padded row's positions are not its slots: the reference sees the
    # row without its padding
    for i, n in enumerate(lengths):
        assert_served_is_the_reference(
            tiny, prompts[i, :n].tolist(), out["tokens"][i].tolist()
        )
