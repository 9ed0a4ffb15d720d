"""The ``qwen3_next`` family (three Gated DeltaNet layers to one gated
attention layer with partial rotation, a softmax-over-all mixture of
many small experts beside a sigmoid-gated shared expert) at a tiny size
on the CPU: the two delta-rule kernels in interpret mode against their
plain forms and the token recurrence, the pieces the family brought
(partial rotation, the zero-centred norm, the router, the row tile), the
cached forward against the reference's full forward, the shares of a
layer adding up, and the engine's handling of the state: buckets, parts,
reused and idle slots."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import llama, moe
from odh_kubeflow_tpu.models import qwen3_next as qn
from odh_kubeflow_tpu.models.engine import DecodeEngine
from odh_kubeflow_tpu.models.generate import cache_bytes, family_forward, init_cache
from odh_kubeflow_tpu.ops import pallas_gdn as pg
from odh_kubeflow_tpu.ops import pallas_moe_local as pml
from odh_kubeflow_tpu.ops.norms import rms_norm
from odh_kubeflow_tpu.ops.rope import apply_rope, rope_angles
from odh_kubeflow_tpu.reference import qwen3_next as ref

F32 = jnp.float32


def scan_inputs(B, S, Hk, H, dk, dv, seed=0):
    k = jax.random.split(jax.random.key(seed), 7)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(k[0], (B, S, Hk, dk))) * dk**-0.5
    kk = unit(jax.random.normal(k[1], (B, S, Hk, dk)))
    v = jax.random.normal(k[2], (B, S, H, dv))
    A = jnp.exp(jax.random.uniform(k[3], (H,), minval=0, maxval=2.7))
    g = -A * jax.nn.softplus(jax.random.normal(k[4], (B, S, H)) - 2)
    beta = jax.nn.sigmoid(jax.random.normal(k[5], (B, S, H)))
    init = jax.random.normal(k[6], (B, H, dk, dv))
    return q, kk, v, g, beta, init


def reference_recurrence(q, k, v, g, beta, S):
    """The reference's own step, one row, token by token in a Python
    loop: ``q``/``k`` [T, Hk, dk], ``v`` [T, H, dv], ``S`` [H, dk, dv]."""
    rep = v.shape[1] // k.shape[1]
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)
    out = []
    for t in range(v.shape[0]):
        S = jnp.exp(g[t])[:, None, None] * S
        u = jnp.einsum("hkv,hk->hv", S, k[t])
        S = S + k[t][:, :, None] * (beta[t][:, None] * (v[t] - u))[:, None, :]
        out.append(jnp.einsum("hkv,hk->hv", S, q[t]))
    return jnp.stack(out), S


# ---- the kernels -----------------------------------------------------------


@pytest.mark.parametrize(
    "S,chunk,Hk,H,dk,dv,lengths",
    [
        (37, 16, 2, 4, 8, 16, None), (150, 64, 2, 4, 128, 128, None),
        (7, 8, 2, 4, 16, 16, None),
        # what solving many systems a grid step can break: a padded batch
        # of chunks (two systems a chunk, so four chunks a step: six
        # chunks run as eight), one chunk with more heads than a step
        # takes, a key head of one and of four value heads (a pack of two
        # key heads; four systems a step and two chunks beside them),
        # rows of different true length behind one grid
        (64 * 5 + 9, 64, 1, 2, 16, 16, None), (64, 64, 16, 32, 8, 8, None),
        (100, 16, 4, 4, 8, 16, None), (100, 16, 1, 4, 8, 16, None),
        (64 * 3, 64, 1, 2, 16, 16, (64 * 3, 70)),
        # three systems a step: the odd one has no neighbour along the lanes
        (16, 16, 1, 3, 8, 16, None),
    ],
    ids=["tiny-37", "published-widths-150", "shorter-than-a-chunk",
         "chunks-not-a-multiple-of-the-batch", "one-chunk-many-heads",
         "rep-1", "rep-4", "two-true-lengths", "an-odd-system-out"],
)
def test_chunked_scan_is_its_plain_form_and_the_token_recurrence(
    S, chunk, Hk, H, dk, dv, lengths
):
    a = scan_inputs(2, S, Hk, H, dk, dv)
    if lengths is not None:
        real = (jnp.arange(S)[None] < jnp.asarray(lengths)[:, None])[..., None]
        a = (*a[:3], a[3] * real, a[4] * real, a[5])
    o0, f0 = pg.gdn_scan_plain(*a)
    o1, f1 = pg.gdn_chunk_scan(*a, chunk=chunk, interpret=True)
    np.testing.assert_allclose(o1, o0, atol=2e-5)
    np.testing.assert_allclose(f1, f0, atol=2e-5)
    # and the plain form is the reference's recurrence, from the same state
    orf, frf = reference_recurrence(*(x[0] for x in a))
    np.testing.assert_allclose(o0[0], orf, atol=2e-5)
    np.testing.assert_allclose(f0[0], frf, atol=2e-5)


@pytest.mark.parametrize(
    "beta,g,run",
    [(0.5, -0.05, None), (0.9, -0.001, None), (0.99, 0.0, None),
     (0.9, -0.001, (100, 290)), (0.99, 0.0, (250, 262))],
    ids=["0.5", "0.9", "0.99", "0.9-across-a-batch", "0.99-over-the-boundary"],
)
def test_a_run_of_one_token_does_not_break_the_in_chunk_solve(beta, g, run):
    """Keys that repeat (a prompt's run of one token) make ``A`` ``beta``
    times a matrix of ones: ``(I + A)^-1`` as a product of squarings over
    the whole chunk then cancels powers with entries of 1e17 and reads
    NaN. The blockwise solve is the recurrence at float32's own error,
    also where the run lies across the chunks that one grid step solves
    together and the next's (four value heads: two chunks a step, so a
    step ends at 128 and at 256)."""
    S, Hk, H, dk, dv = 200 if run is None else 320, 2, 4, 128, 128
    ks = jax.random.split(jax.random.key(11), 3)
    one = jax.random.normal(ks[0], (1, 1, Hk, dk))
    k = jnp.broadcast_to(one, (1, S, Hk, dk))
    if run is not None:
        inside = ((jnp.arange(S) >= run[0]) & (jnp.arange(S) < run[1]))[None, :, None, None]
        k = jnp.where(inside, k, jax.random.normal(ks[2], (1, S, Hk, dk)))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[1], (1, S, H, dv))
    a = (k * dk**-0.5, k, v, jnp.full((1, S, H), g), jnp.full((1, S, H), beta),
         jnp.zeros((1, H, dk, dv)))
    o0, f0 = pg.gdn_scan_plain(*a)
    o1, f1 = pg.gdn_chunk_scan(*a, chunk=64, interpret=True)
    np.testing.assert_allclose(o1, o0, atol=1e-4 * float(jnp.abs(o0).max()))
    np.testing.assert_allclose(f1, f0, atol=1e-4 * float(jnp.abs(f0).max()))


def test_a_padded_tail_leaves_the_state_of_the_true_last_token():
    """Two rows of different length in one call: a masked position has
    g = 0 and beta = 0, so each row's final state is its unpadded run's."""
    S, lengths = 40, (40, 23)
    q, k, v, g, beta, init = scan_inputs(2, S, 2, 4, 8, 16, seed=3)
    mask = (jnp.arange(S)[None] < jnp.asarray(lengths)[:, None])[..., None]
    o, fin = pg.gdn_chunk_scan(
        q, k, v, g * mask, beta * mask, init, chunk=16, interpret=True
    )
    for row, n in enumerate(lengths):
        sl = slice(row, row + 1)
        o1, f1 = pg.gdn_chunk_scan(
            q[sl, :n], k[sl, :n], v[sl, :n], g[sl, :n], beta[sl, :n], init[sl],
            chunk=16, interpret=True,
        )
        np.testing.assert_allclose(fin[sl], f1, atol=2e-5)
        np.testing.assert_allclose(o[sl, :n], o1, atol=2e-5)


def test_the_scan_hands_its_state_on():
    """A row in two calls, the first's final state the second's initial
    one, is the row in one call."""
    a = scan_inputs(1, 48, 2, 4, 8, 16, seed=5)
    o, fin = pg.gdn_chunk_scan(*a, chunk=16, interpret=True)
    cut = 29
    head = [x[:, :cut] for x in a[:5]]
    tail = [x[:, cut:] for x in a[:5]]
    oa, mid = pg.gdn_chunk_scan(*head, a[5], chunk=16, interpret=True)
    ob, fin2 = pg.gdn_chunk_scan(*tail, mid, chunk=16, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([oa, ob], 1), o, atol=2e-5)
    np.testing.assert_allclose(fin2, fin, atol=2e-5)


@pytest.mark.parametrize("form", ["kernel", "plain"])
def test_no_decay_and_no_write_is_the_identity_on_the_state(form):
    q, k, v, g, beta, init = scan_inputs(1, 24, 2, 4, 8, 16, seed=6)
    zero = jnp.zeros_like(g)
    if form == "kernel":
        _, fin = pg.gdn_chunk_scan(q, k, v, zero, zero, init, chunk=8, interpret=True)
    else:
        _, fin = pg.gdn_scan_plain(q, k, v, zero, zero, init)
    np.testing.assert_array_equal(fin, init)


@pytest.mark.parametrize("dk,dv", [(8, 16), (128, 128)], ids=["tiny", "published"])
def test_decode_update_is_one_recurrence_step_in_place(dk, dv):
    q, k, v, g, beta, init = scan_inputs(3, 1, 2, 4, dk, dv, seed=7)
    stack = jnp.stack([init * 0 + 1, init, init * 2])
    # row 2 decodes nothing: g = 0 and beta = 0 must leave its state as it is
    g, beta = g.at[2].set(0.0), beta.at[2].set(0.0)
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], stack, 1)
    o_plain, s_plain = pg.gdn_step_plain(*args)
    o, s = pg.gdn_decode_update(*args, interpret=True)
    np.testing.assert_allclose(o, o_plain, atol=1e-5)
    np.testing.assert_allclose(s, s_plain, atol=1e-6)
    o_scan, f_scan = pg.gdn_scan_plain(q, k, v, g, beta, init)
    np.testing.assert_allclose(o, o_scan[:, 0], atol=1e-5)
    np.testing.assert_allclose(s[1], f_scan, atol=1e-6)
    # only the addressed layer moved, and the idle row not at all
    np.testing.assert_array_equal(s[0], stack[0])
    np.testing.assert_array_equal(s[2], stack[2])
    np.testing.assert_array_equal(s[1, 2], stack[1, 2])


def test_decode_update_aliases_the_stacked_state():
    """The kernel's state operand is its state result (operand 0 is the
    prefetched layer index): a donated stack is updated where it lies."""
    q, k, v, g, beta, init = scan_inputs(2, 1, 2, 4, 8, 16)
    stack = jnp.stack([init, init])
    text = str(jax.make_jaxpr(
        lambda *a: pg.gdn_decode_update(*a, interpret=True)
    )(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], stack, 1))
    assert "name=gdn_decode_update" in text
    assert "input_output_aliases=((1, 0),)" in text


# ---- what the family brought to ops/ and moe.py ----------------------------


def test_partial_rotation_leaves_the_trailing_dims_untouched():
    x = jax.random.normal(jax.random.key(0), (2, 5, 3, 256))
    positions = jnp.broadcast_to(jnp.arange(5)[None] + 7, (2, 5))
    sin, cos = rope_angles(positions, 64, 1e7)
    out = apply_rope(x, sin, cos)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])
    # the leading slice is the whole-head rotation of a head of 64
    np.testing.assert_allclose(out[..., :64], apply_rope(x[..., :64], sin, cos))
    assert not np.allclose(out[..., :64], x[..., :64])


def test_partial_rotation_is_the_references():
    x = jax.random.normal(jax.random.key(1), (6, 3, 32))
    sin, cos = rope_angles(jnp.arange(6)[None], 8, 1e4)
    np.testing.assert_allclose(
        apply_rope(x[None], sin, cos)[0], ref.rotate_leading(x, 8, 1e4), atol=1e-6
    )


def test_the_zero_centred_norm_is_one_plus_w():
    x = jax.random.normal(jax.random.key(2), (4, 64))
    w = 0.3 * jax.random.normal(jax.random.key(3), (64,))
    got = qn.norm(x, w, 1e-6)
    np.testing.assert_allclose(got, ref.norm(x, w, 1e-6), rtol=1e-6)
    np.testing.assert_allclose(got, rms_norm(x, jnp.ones(64), 1e-6) * (1 + w), rtol=1e-6)
    assert not np.allclose(got, rms_norm(x, w, 1e-6))


def test_the_router_is_the_training_paths_top_k():
    """One function: a softmax over all, the k largest, renormalised;
    ``_routing_stats`` (the training path) calls it."""
    logits = jax.random.normal(jax.random.key(4), (2, 9, 16))
    w, idx, probs = moe.route_softmax_topk(logits, 3)
    cfg = moe.MoeConfig.mixtral_tiny(num_experts=16, num_experts_per_tok=3)
    top_p, top_idx, _, _ = moe._routing_stats(logits, cfg)
    np.testing.assert_array_equal(idx, top_idx)
    np.testing.assert_array_equal(w, top_p)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(probs, jax.nn.softmax(logits, -1))
    # and the reference's weights over all experts are these
    combine, top_i = ref.routing(
        logits[0], jnp.eye(16), {"num_experts_per_tok": 3, "norm_topk_prob": True}
    )
    np.testing.assert_array_equal(top_i, idx[0])
    np.testing.assert_allclose(
        jnp.take_along_axis(combine, idx[0], -1), w[0], rtol=1e-6
    )


@pytest.mark.parametrize(
    "tokens,tile",
    [(16, 16), (32, 16), (64, 16), (256, 128), (1024, 128), (2048, 128)],
)
def test_command_a_plus_and_granite_keep_their_row_tiles(tokens, tile):
    """Their calls say nothing of the router's width (decode steps of 16
    and 32 slots, the cells' four buckets and the part of 2048)."""
    assert pml.block_m_for(tokens) == tile


@pytest.mark.parametrize(
    "tokens,tile", [(32, 16), (64, 16), (256, 16), (1024, 32), (2048, 64)]
)
def test_many_small_experts_get_the_tile_their_rows_fill(tokens, tile):
    """512 experts, ten a token: a part of 2048 gives an expert 40 rows."""
    assert pml.block_m_for(tokens, tokens * 10 / 512) == tile
    # experts that can expect a whole tile keep the whole tile
    assert pml.block_m_for(2048, 2048 * 8 / 128) == 128
    assert pml.block_m_for(2048, 2048 * 10 / 72) == 128


def test_the_fourth_counter_is_the_rows_the_kernel_computes():
    T, D, F, k = 100, 16, 8, 3
    keys = jax.random.split(jax.random.key(5), 5)
    banks = {
        "moe_gate": jax.random.normal(keys[0], (1, 8, D, F)),
        "moe_up": jax.random.normal(keys[1], (1, 8, D, F)),
        "moe_down": jax.random.normal(keys[2], (1, 8, F, D)),
    }
    h = jax.random.normal(keys[3], (T, D))
    idx = jax.random.randint(keys[4], (T, k), 0, 16)
    w = jnp.full((T, k), 1 / k)
    _, stats = moe.local_expert_ffn(h, w, idx, banks, 0, (0, 8), num_experts=16)
    tile = pml.block_m_for(T, T * k / 16)
    assert tile == 32
    sizes = np.bincount(np.asarray(idx).ravel(), minlength=16)[:8]
    assert stats.tolist() == [
        int(sizes.sum()), int((sizes > 0).sum()), 0,
        int((-(-sizes // tile) * tile).sum()),
    ]
    # the kernel's result (interpret mode) at that tile is the plain one
    out_k, _ = moe.local_expert_ffn(
        h, w, idx, _int8(banks), 0, (0, 8), in_place=True, interpret=True,
        num_experts=16,
    )
    out_p, _ = moe.local_expert_ffn(
        h, w, idx, _int8(banks), 0, (0, 8), in_place=False, num_experts=16
    )
    np.testing.assert_allclose(out_k, out_p, atol=1e-3, rtol=1e-3)


def _int8(banks):
    from odh_kubeflow_tpu.models.quant import quantize_tensor

    return {n: quantize_tensor(b) for n, b in banks.items()}


# ---- the model -------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = qn.Qwen3NextConfig.tiny(dtype=F32)
    return cfg, qn.init_params(jax.random.key(0), cfg)


def file_config(cfg, held=None):
    """The tiny config as a configuration FILE, for the reference."""
    first, count = held or cfg.experts_held
    return {
        "hidden_size": cfg.hidden_size, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "partial_rotary_factor": cfg.partial_rotary_factor,
        "rope_theta": cfg.rope_theta,
        "full_attention_interval": len(cfg.layer_kinds),
        "linear_num_key_heads": cfg.gdn_key_heads,
        "linear_num_value_heads": cfg.gdn_value_heads,
        "linear_key_head_dim": cfg.gdn_key_dim,
        "linear_value_head_dim": cfg.gdn_value_dim,
        "linear_conv_kernel_dim": cfg.gdn_conv,
        "num_experts_per_tok": cfg.num_experts_per_tok, "norm_topk_prob": True,
        "rms_norm_eps": cfg.rms_norm_eps,
        "deployment": {"experts_held": {"first": first, "count": count}},
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


def test_the_cache_has_the_kinds_table_at_this_familys_shapes(tiny):
    """Nothing new in ``llama.CACHE_KINDS``: the delta-rule state and the
    convolution's tail are the STATE kind's two stacks."""
    cfg, _ = tiny
    cache = init_cache(cfg, 3, 32, jnp.bfloat16)
    assert cache["k"].shape == (2, 3, 32, cfg.kv_dim)  # 2 of 8 layers
    assert cache["ssm"].shape == (6, 3, 4, 8, 16) and cache["ssm"].dtype == F32
    assert cache["conv"].shape == (6, 3, cfg.gdn_conv - 1, cfg.conv_dim)
    assert cache["conv"].dtype == jnp.bfloat16
    assert cache["moe_stats"].shape == (4,)
    assert cache_bytes(cache) == {
        "full": 2 * 2 * 3 * 32 * cfg.kv_dim * 2, "window": 0, "indexed": 0,
        "state": cache["ssm"].size * 4 + cache["conv"].size * 2,
    }
    assert cfg.layer_kinds == (llama.STATE,) * 3 + (None,)
    assert cfg.rotary_dim == 4 and cfg.conv_dim == 2 * 16 + 64


def test_uncached_forward_is_the_reference(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(1).integers(1, 256, size=21)
    want, _ = reference_logits(tiny, tokens)
    got = qn.forward(params, jnp.asarray(tokens)[None], cfg)[0]
    np.testing.assert_allclose(got, want, atol=2e-4)


@pytest.mark.parametrize("padded", [False, True], ids=["whole", "right-padded"])
def test_prefill_then_decode_through_state_and_cache_is_the_reference(tiny, padded):
    cfg, params = tiny
    tokens = np.random.default_rng(2).integers(1, 256, size=21)
    want, _ = reference_logits(tiny, tokens)
    n, width = 13, 16 if padded else 13
    cache = init_cache(cfg, 1, 32, F32)
    prompt = np.zeros(width, np.int32)
    prompt[:n] = tokens[:n]
    lg, cache = qn.forward_with_cache(
        params, jnp.asarray(prompt)[None], cfg, cache, jnp.int32(0),
        positions=jnp.arange(width)[None], kv_mask=jnp.arange(32)[None] < n,
        token_mask=jnp.arange(width)[None] < n,
    )
    np.testing.assert_allclose(lg[0, :n], want[:n], atol=2e-4)
    for t in range(n, len(tokens)):
        lg, cache = qn.forward_with_cache(
            params, jnp.asarray(tokens[t:t + 1])[None], cfg, cache,
            jnp.full((1,), t, jnp.int32), positions=jnp.full((1, 1), t),
            kv_mask=jnp.arange(32)[None] <= t, token_mask=jnp.ones((1, 1), bool),
        )
        np.testing.assert_allclose(lg[0, 0], want[t], atol=2e-4)


def test_the_cached_forward_reports_the_references_routing(tiny):
    cfg, params = tiny
    tokens = np.random.default_rng(4).integers(1, 256, size=16)
    _, top = reference_logits(tiny, tokens, pad_to=16)
    cache = init_cache(cfg, 1, 16, F32)
    cache["moe_topk"] = jnp.zeros(
        (cfg.num_layers, 1, 16, cfg.num_experts_per_tok), jnp.int32
    )
    _, cache = qn.forward_with_cache(
        params, jnp.asarray(tokens)[None], cfg, cache, jnp.int32(0),
        positions=jnp.arange(16)[None], kv_mask=jnp.ones((1, 16), bool),
    )
    np.testing.assert_array_equal(
        jnp.sort(cache["moe_topk"][:, 0], -1), jnp.sort(top, -1)
    )
    # the held half of the router's choices, none dropped, and the rows
    # the kernel would compute cover them
    local = int(jnp.sum(top < 8))
    assert cache["moe_stats"].tolist()[0] == local
    assert cache["moe_stats"].tolist()[2] == 0
    assert cache["moe_stats"].tolist()[3] >= local


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(tiny):
    """Experts 0..7 on one chip and 8..15 on the other, the shared expert
    (what both compute alike) counted once: the uncut layer."""
    cfg, params = tiny
    keys = jax.random.split(jax.random.key(9), 4)
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.expert_width
    other = {
        "moe_gate": jax.random.normal(keys[0], (L, 8, D, F)) * D**-0.5,
        "moe_up": jax.random.normal(keys[1], (L, 8, D, F)) * D**-0.5,
        "moe_down": jax.random.normal(keys[2], (L, 8, F, D)) * F**-0.5,
    }
    x = jax.random.normal(keys[3], (1, 24, D))
    depth = 1
    layer = llama.take_layer(
        {n: v for n, v in params["layers"].items() if n not in other}, depth
    )
    mine = {n: params["layers"][n] for n in other}
    both = {n: jnp.concatenate([mine[n], other[n]], axis=1) for n in other}
    parts = []
    for held, banks in (((0, 8), mine), ((8, 8), other)):
        share = dataclasses.replace(cfg, experts_held=held)
        y, stats, _ = qn._ffn(share, x, layer, banks, depth, None)
        parts.append(y - x)
        assert stats.tolist()[2] == 0
    config = file_config(cfg, held=(0, 16))
    with jax.default_matmul_precision("highest"):
        uncut, top = ref.ffn(x[0], layer, both, depth, config, ref.SOUND)
        shared = ref.shared_expert(
            ref.norm(x[0], layer["norm2"], cfg.rms_norm_eps), layer, ref.SOUND
        )
    assert int(jnp.sum(top < 8)) and int(jnp.sum(top >= 8))  # both shares work
    np.testing.assert_allclose(
        parts[0][0] + parts[1][0] - shared, uncut, atol=2e-5
    )


def test_an_idle_row_keeps_its_state_and_its_conv_tail(tiny):
    cfg, params = tiny
    cache = init_cache(cfg, 2, 16, F32)
    cache = {**cache, "ssm": cache["ssm"] + 0.5, "conv": cache["conv"] + 0.25}
    _, new = qn.forward_with_cache(
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
        qn.forward_with_cache(
            params, jnp.ones((2, 3), jnp.int32), cfg, cache, jnp.asarray([1, 2]),
            positions=jnp.ones((2, 3), jnp.int32),
        )


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
        if m < 1e-3:
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
    assert engine.moe_rows_computed >= engine.moe_local_assignments > 0


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
    """Two parts of 16 and a final part of ``rem`` tokens run at
    ``bucket`` positions, its scan starting from the state and the
    convolution's tail that the parts carried, against the same prompt
    admitted whole: the same first token, the same delta-rule state and
    tail, the same keys and values."""
    cfg, params = tiny
    n, parts, whole = final_part_against_whole(params, cfg, rem, bucket, 96)
    assert set(parts) == set(llama.STATE_STACKS) | {"k", "v"}
    for name in parts:
        got, want = (
            c[name] if name in llama.STATE_STACKS else c[name][:, :n]
            for c in (parts, whole)
        )
        # float32 sums in another order, as for a scan fed in two parts
        # above (a final part at a whole part's width is as far off)
        scale = float(np.abs(want).max())
        assert scale > 0
        np.testing.assert_allclose(got, want, atol=1e-4 * scale, err_msg=name)


def test_a_stopped_engines_slot_holds_the_state_of_its_stream(tiny):
    """Stopped with a request still decoding: the slot's row of the
    delta-rule state is the reference's after the prompt and every token
    served but the last (prefill in parts, the splice, then decode steps
    beside an idle and a finished slot)."""
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
    finally:
        eng.stop()
    assert not req.complete and len(req.tokens) >= 9
    state = eng.slot_state(req.slot)
    assert set(state) == set(llama.STATE_STACKS)
    assert state["ssm"].shape == (6, 4, 8, 16)
    taken = prompt + list(req.tokens)[:-1]
    seq = np.zeros(96, np.int32)
    seq[: len(taken)] = taken
    config = file_config(cfg)
    want = ref.logits_and_states(params, jnp.asarray(seq), config, stop=len(taken))[2]
    np.testing.assert_allclose(state["ssm"], np.asarray(want), rtol=2e-4, atol=2e-5)
    # and the first layer's alone is what ``first_state`` gives
    np.testing.assert_allclose(
        ref.first_state(params, jnp.asarray(seq), config, stop=len(taken)),
        want[0], rtol=1e-5, atol=1e-7,
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
    assert family_forward(cfg)[1] is qn.forward_with_cache
    for i, n in enumerate(lengths):
        assert_served_is_the_reference(
            tiny, prompts[i, :n].tolist(), out["tokens"][i].tolist()
        )
