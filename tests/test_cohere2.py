"""The ``cohere2_moe`` family on the CPU at a tiny size, seeded weights:
the program against the plain reference (``odh_kubeflow_tpu/reference/
cohere2_moe.py``), the window ring against a uniform cache, the parts
of the block one by one, and the SHARE test: what the eight shares of a
layer compute adds up to the uncut layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import cohere2, moe
from odh_kubeflow_tpu.models.engine import DecodeEngine
from odh_kubeflow_tpu.models.generate import cache_bytes, family_forward, init_cache
from odh_kubeflow_tpu.ops.attention import dense_attention
from odh_kubeflow_tpu.ops.pallas_decode_attention import (
    decode_attend,
    live_range,
    slot_positions,
)
from odh_kubeflow_tpu.ops.rope import (
    apply_rope,
    apply_rope_interleaved,
    rope_angles,
)
from odh_kubeflow_tpu.reference import cohere2_moe as ref

WINDOW = 8
TYPES = (["sliding_attention"] * 3 + ["full_attention"]) * 2


def file_config(cfg: cohere2.Cohere2MoeConfig) -> dict:
    """The configuration-file form the reference reads."""
    return dict(
        num_attention_heads=cfg.num_heads, num_key_value_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, layer_norm_eps=cfg.layer_norm_eps,
        rope_theta=cfg.rope_theta, sliding_window=WINDOW, layer_types=TYPES,
        num_experts_per_tok=cfg.num_experts_per_tok, norm_topk_prob=True,
        num_shared_experts=cfg.num_shared_experts,
        intermediate_size=cfg.expert_width, logit_scale=cfg.logit_scale,
        deployment={"experts_held": dict(zip(("first", "count"), cfg.experts_held))},
    )


@pytest.fixture(scope="module")
def share():
    cfg = cohere2.Cohere2MoeConfig.tiny(dtype=jnp.float32, experts_held=(4, 8))
    return cfg, cohere2.init_params(jax.random.key(0), cfg)


def _decode_through(cfg, params, tokens, max_len, widest_part, parts):
    """Prefill in ``parts``, then one token at a time: logits [B, T, V]."""
    _, fwd = family_forward(cfg)
    B, T = tokens.shape
    cache = init_cache(cfg, B, max_len, jnp.float32, widest_part=widest_part)
    out, pos = [], 0
    for n in parts:
        lg, cache = fwd(
            params, tokens[:, pos:pos + n], cfg, cache, jnp.int32(pos),
            positions=jnp.broadcast_to(jnp.arange(pos, pos + n), (B, n)),
            kv_mask=jnp.broadcast_to(jnp.arange(max_len) < pos + n, (B, max_len)),
        )
        out.append(lg)
        pos += n
    for t in range(pos, T):
        lg, cache = fwd(
            params, tokens[:, t:t + 1], cfg, cache, jnp.full((B,), t, jnp.int32),
            positions=jnp.full((B, 1), t),
            kv_mask=jnp.broadcast_to(jnp.arange(max_len) < t + 1, (B, max_len)),
        )
        out.append(lg)
    return jnp.concatenate(out, axis=1), cache


def test_forward_is_the_references_full_forward(share):
    cfg, params = share
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, cfg.vocab_size)
    got = cohere2.forward(params, tokens, cfg)
    for b in range(2):
        want, _ = ref.logits(params, tokens[b], file_config(cfg))
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want), atol=2e-5)


def test_parts_then_decode_through_the_ring_is_the_full_forward(share):
    """Prefill in parts of 8 and decoding to 40 positions through rings
    of 16 (window 8: wrapped twice and a half) against the reference's
    one full forward, on logits; the ring holds a quarter of what a
    uniform cache would, and gives what one gives."""
    cfg, params = share
    tokens = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab_size)
    ring, cache = _decode_through(cfg, params, tokens, 48, 8, (8, 8, 8))
    assert cache["wk"].shape == (6, 2, 16, cfg.kv_dim)
    assert cache["k"].shape == (2, 2, 48, cfg.kv_dim)
    assert cache_bytes(cache) == {
        "full": 2 * 2 * 2 * 48 * cfg.kv_dim * 4, "window": 2 * 6 * 2 * 16 * cfg.kv_dim * 4,
        "state": 0, "indexed": 0,
    }
    for b in range(2):
        want, _ = ref.logits(params, tokens[b], file_config(cfg))
        np.testing.assert_allclose(np.asarray(ring[b]), np.asarray(want), atol=2e-5)
    uniform, cache = _decode_through(cfg, params, tokens, 48, None, (8, 8, 8))
    assert cache["wk"].shape == (6, 2, 48, cfg.kv_dim)
    # the same keys in another order of slots: float32 sums in another
    # order, so equal to rounding and not bit for bit
    np.testing.assert_allclose(np.asarray(ring), np.asarray(uniform), atol=2e-5)
    assert int(cache["moe_stats"][2]) == 0


def test_engine_serves_what_the_reference_computes(share):
    """The engine's own path (admission in parts, decode chunks, two
    slots of unequal length, contexts past the window) against the
    reference's full forward: at every served position the reference's
    best logit is the served token's."""
    cfg, params = share
    engine = DecodeEngine(
        params, cfg, n_slots=2, max_len=64, chunk=4, prompt_buckets=(8,),
        prefill_chunk=8, cache_dtype=jnp.float32,
    )
    try:
        assert engine._state["cache"]["wk"].shape[2] == 16
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 256, size=n).tolist() for n in (30, 5)]
        reqs = [
            engine.submit(p, max_tokens=m) for p, m in zip(prompts, (20, 33))
        ]
        served = [r.result(timeout=300) for r in reqs]
    finally:
        engine.stop()
    for prompt, toks in zip(prompts, served):
        lg, _ = ref.logits(params, jnp.asarray(prompt + toks), file_config(cfg))
        lg = lg[len(prompt) - 1 + np.arange(len(toks))]
        picked = jnp.take_along_axis(lg, jnp.asarray(toks)[:, None], -1)[:, 0]
        assert float((lg.max(-1) - picked).max()) < 1e-4
    assert engine.moe_dropped == 0 and engine.moe_experts_hit > 0
    assert engine.moe_local_assignments >= engine.moe_experts_hit
    assert engine.window_blocks_skipped > 0
    assert engine.cache_bytes["window"] < engine.cache_bytes["full"] * 3


@pytest.mark.parametrize("rem, bucket", [(3, 4), (7, 8)])
def test_a_narrow_final_part_leaves_what_the_whole_prompt_leaves(
    share, final_part_against_whole, rem, bucket
):
    """Two parts of 16 and a final part of ``rem`` tokens run at
    ``bucket`` positions, written where the ring of 32 has wrapped,
    against the same prompt admitted whole into a cache that never
    wraps: the same first token, the same keys and values at every
    position a query can still see."""
    cfg, params = share
    n, parts, whole = final_part_against_whole(params, cfg, rem, bucket, 64)
    assert parts["wk"].shape[1] == 32 and whole["wk"].shape[1] == 64
    for name in parts:
        at = np.arange(n - WINDOW if name.startswith("w") else 0, n)
        got, want = (c[name][:, at % c[name].shape[1]] for c in (parts, whole))
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=name)


def test_a_windowed_cache_refuses_what_rings_cannot_do(share):
    cfg, params = share
    with pytest.raises(NotImplementedError, match="ring"):
        DecodeEngine(params, cfg, n_slots=1, max_len=64, prompt_buckets=(8,),
                     prefix_cache_entries=2, prefix_buckets=(4,))
    # a ring of 20 (window 8 + a bucket of 10, in tens): the part that
    # starts at 16 would straddle its end
    with pytest.raises(NotImplementedError, match="ring"):
        DecodeEngine(params, cfg, n_slots=1, max_len=64, prompt_buckets=(10,),
                     prefill_chunk=8)


def test_sigmoid_topk_with_normalisation():
    logits = jnp.asarray([[2.0, -1.0, 0.5, 3.0, 0.0], [0.0, 0.0, 5.0, -5.0, 1.0]])
    w, idx = moe.route_sigmoid_topk(logits, 2)
    s = 1 / (1 + np.exp(-np.asarray(logits)))
    np.testing.assert_array_equal(np.asarray(idx), [[3, 0], [2, 4]])
    np.testing.assert_allclose(
        np.asarray(w),
        [[s[0, 3], s[0, 0]] / (s[0, 3] + s[0, 0]), [s[1, 2], s[1, 4]] / (s[1, 2] + s[1, 4])],
        rtol=1e-6,
    )
    raw, _ = moe.route_sigmoid_topk(logits, 2, normalise=False)
    np.testing.assert_allclose(np.asarray(raw), [[s[0, 3], s[0, 0]], [s[1, 2], s[1, 4]]], rtol=1e-6)
    # a softmax would choose the same ids and weigh them otherwise
    assert not np.allclose(np.asarray(w), np.asarray(jax.nn.softmax(logits)[[0, 1], [3, 2]])[:, None])


def test_the_router_reads_the_norms_float32_result():
    """bf16 activations, float32 router: in the FIRST layer the norm's
    input is a token's embedding alone (exact in bf16 on both sides), so
    every token of the vocabulary chooses the reference's experts. A
    router fed the norm's bf16 copy tips a few per cent of the choices
    (8th and 9th of 128 sigmoids lie ~0.06 apart), and tips them at
    every occurrence of that token: what a greedy request that settled
    into a loop of three tokens showed on the chip (PERF.md, PR 26)."""
    cfg = cohere2.Cohere2MoeConfig.tiny(
        vocab_size=4096, num_experts=128, num_experts_per_tok=8,
        experts_held=(32, 16), num_layers=4, dtype=jnp.bfloat16,
    )
    params = cohere2.init_params(jax.random.key(7), cfg)
    params["embed"] = params["embed"].astype(jnp.bfloat16)
    tokens = jnp.arange(cfg.vocab_size, dtype=jnp.int32)
    _, fwd = family_forward(cfg)
    cache = init_cache(cfg, 1, cfg.vocab_size, widest_part=cfg.vocab_size)
    cache["moe_topk"] = jnp.zeros(
        (cfg.num_layers, 1, cfg.vocab_size, cfg.num_experts_per_tok), jnp.int32
    )
    _, cache = fwd(params, tokens[None], cfg, cache, jnp.int32(0), positions=tokens[None])
    config = {**file_config(cfg), "layer_types": TYPES[:4]}
    _, want = ref.logits(params, tokens, config)
    got = np.sort(np.asarray(cache["moe_topk"][0, 0]), -1)
    np.testing.assert_array_equal(got, np.sort(np.asarray(want[0]), -1))


def test_interleaved_rope_rotates_adjacent_pairs():
    x = jax.random.normal(jax.random.key(3), (2, 5, 3, 8))
    pos = jnp.broadcast_to(jnp.arange(5), (2, 5))
    sin, cos = rope_angles(pos, 8, 50_000.0)
    got = apply_rope_interleaved(x, sin, cos)
    # the half-split rotation of the de-interleaved vector, interleaved back
    perm = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    want = apply_rope(x[..., perm], sin, cos)[..., np.argsort(perm)]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(got[:, 0]), np.asarray(x[:, 0]), atol=1e-6
    )  # position 0 is not rotated
    want = ref.rope_interleaved(x[0], jnp.arange(5), 50_000.0)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=1e-6)


def test_window_layers_rotate_and_global_layers_do_not(share):
    """By kind of layer: shifting every position by a constant leaves a
    window layer's output alone (rotation is relative) and a global
    layer has no positions at all, so the logits of a row do not move;
    a stack that rotated its global layers by ABSOLUTE position would."""
    cfg, params = share
    _, fwd = family_forward(cfg)
    tokens = jax.random.randint(jax.random.key(4), (1, 12), 0, cfg.vocab_size)

    def run(offset):
        cache = init_cache(cfg, 1, 16, jnp.float32)
        return fwd(
            params, tokens, cfg, cache, jnp.int32(0),
            positions=offset + jnp.arange(12)[None],
        )[0]

    np.testing.assert_allclose(np.asarray(run(0)), np.asarray(run(1000)), atol=5e-4)


def test_the_shares_add_up_to_the_whole_layer():
    """THE SHARE TEST. One layer, 16 experts over 8 shares of 2: each
    share routes over all 16 and computes its own experts' part. What
    the shares compute, with attention and the shared experts (which
    every chip computes alike) counted once, adds up to the uncut
    reference's layer."""
    whole = cohere2.Cohere2MoeConfig.tiny(
        dtype=jnp.float32, num_layers=4, layer_windows=(WINDOW,) * 3 + (None,),
    )
    params = cohere2.init_params(jax.random.key(5), whole)
    layer = jax.tree_util.tree_map(lambda a: a[:1], params["layers"])
    x = jax.random.normal(jax.random.key(6), (1, 24, whole.hidden_size))
    fcfg = file_config(whole)
    lw = jax.tree_util.tree_map(lambda a: a[0], layer)
    want, _ = ref.layer(x[0], lw, True, fcfg, ref.SOUND)
    # what every chip computes alike: the layer with no routed expert
    none = dict(lw, **{n: lw[n][:0] for n in cohere2.BANKS})
    alike, _ = ref.layer(x[0], none, True, dict(
        fcfg, deployment={"experts_held": {"first": 0, "count": 0}}
    ), ref.SOUND)

    sin, cos = rope_angles(jnp.arange(24)[None], whole.head_dim, whole.rope_theta)
    total = jnp.zeros_like(want)
    for s in range(8):
        cfg = dataclasses.replace(whole, experts_held=(2 * s, 2))
        scanned, banks = cohere2._split_banks(layer)
        banks = {n: b[:, 2 * s:2 * s + 2] for n, b in banks.items()}

        def attend(q, kk, vv):
            return dense_attention(q, kk, vv, causal=True, window=WINDOW), None

        y, _, stats, _ = cohere2._block(
            cfg, x, jax.tree_util.tree_map(lambda a: a[0], scanned), banks, 0,
            True, attend, sin, cos, None,
        )
        total = total + (y[0] - alike)
        assert int(stats[2]) == 0
    np.testing.assert_allclose(
        np.asarray(alike + total), np.asarray(want), atol=2e-5
    )
    assert float(jnp.abs(total).max()) > 1e-2  # the routed part is not nothing


def _int8(key, shape, fan_in):
    from odh_kubeflow_tpu.models.quant import quantize_tensor

    return quantize_tensor(jax.random.normal(key, shape) * fan_in**-0.5)


@pytest.mark.parametrize("T", [16, 200], ids=["decode", "part"])
@pytest.mark.parametrize("held", [(4, 4), (12, 4)], ids=["mid", "last"])
def test_moe_local_ffn_kernel_is_the_plain_expert_sum(T, held):
    """``ops/pallas_moe_local.py`` in interpret mode on stacked int8
    banks against plain dots on the dequantised layer: sorted groups,
    padding, dead tiles, masked tokens."""
    L, E, D, F = 2, 4, 128, 256
    k = jax.random.split(jax.random.key(7), 6)
    banks = {
        "moe_gate": _int8(k[0], (L, E, D, F), D), "moe_up": _int8(k[1], (L, E, D, F), D),
        "moe_down": _int8(k[2], (L, E, F, D), F),
    }
    h = jax.random.normal(k[3], (T, D)).astype(jnp.bfloat16)
    w, idx = moe.route_sigmoid_topk(jax.random.normal(k[4], (T, 16)), 4)
    mask = jnp.arange(T) % 5 != 0
    plain, s0 = moe.local_expert_ffn(
        h, w, idx, banks, jnp.int32(1), held, mask, in_place=False
    )
    kernel, s1 = moe.local_expert_ffn(
        h, w, idx, banks, jnp.int32(1), held, mask, in_place=True, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    local = (idx >= held[0]) & (idx < held[0] + held[1]) & mask[:, None]
    assert int(s0[0]) == int(local.sum()) and int(s0[2]) == 0
    np.testing.assert_allclose(
        np.asarray(kernel, np.float32), np.asarray(plain, np.float32), atol=2e-2
    )
    assert float(jnp.abs(plain.astype(jnp.float32)).max()) > 0.1


RING_CASES = {
    # name: B, S, index, ring, window
    "decode-wrapped": (3, 1, [5, 300, 1000], 256, 128),
    "decode-not-yet": (2, 1, [17, 100], 256, 128),
    "part-wrapped": (1, 128, None, 384, 256),
    "verify-rows": (2, 3, [254, 600], 256, 200),
}


@pytest.mark.parametrize("name", list(RING_CASES))
def test_decode_attend_reads_a_ring_as_dense_reads_the_positions(name):
    """The kernel (interpret mode) over a RING against dense attention
    over the same keys laid out by position with the window as a mask."""
    B, S, index, ring, window = RING_CASES[name]
    L, Hq, Hkv, hd = 2, 8, 2, 128
    index = jnp.asarray(index, jnp.int32) if index is not None else jnp.int32(640)
    last = int(jnp.max(index)) + S
    kq, kk, kv = jax.random.split(jax.random.key(len(name)), 3)
    q = jax.random.normal(kq, (B, S, Hq, hd))
    by_pos = {
        n: jax.random.normal(key, (L, B, last, Hkv * hd))
        for n, key in (("k", kk), ("v", kv))
    }
    q_off = jnp.broadcast_to(index, (B,))
    held = slot_positions(q_off, S, ring)  # [B, ring]
    rows = jnp.arange(B)[:, None]
    stack = {
        n: jnp.where(
            (held >= 0)[None, :, :, None],
            a[:, rows, jnp.clip(held, 0, last - 1)], 1e4,  # never written: filler
        )
        for n, a in by_pos.items()
    }
    got = decode_attend(
        q, stack["k"], stack["v"], jnp.int32(1), index, None, window=window,
        interpret=True, block_k=128,
    )
    want = dense_attention(
        q, by_pos["k"][1].reshape(B, last, Hkv, hd),
        by_pos["v"][1].reshape(B, last, Hkv, hd),
        causal=True, q_offset=index, window=window,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_live_range_skips_blocks_before_the_window():
    geometry = dict(window=4096, block_k=512, num_k=10)
    assert [int(v) for v in live_range(100, 100, **geometry)] == [0, 1]
    assert [int(v) for v in live_range(4095, 4095, **geometry)] == [0, 8]
    # position 9000 sees 4905..9000: blocks 9..17 of the positions, nine
    # of the ring's ten
    assert [int(v) for v in live_range(9000, 9000, **geometry)] == [9, 9]
    # a part of 1024 queries from 8192 on: 4097..9215
    assert [int(v) for v in live_range(8192, 9215, **geometry)] == [8, 10]
    # no window: from the first block on
    assert [int(v) for v in live_range(9000, 9000, window=1 << 30, block_k=512, num_k=26)] == [0, 18]
