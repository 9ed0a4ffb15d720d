"""Flash attention vs the dense XLA baseline.

Mirrors the reference's fake-backend strategy (SURVEY.md §4): kernels
run in pallas interpret mode on CPU, exercising the exact grid/masking
logic that compiles on TPU.
"""

import jax
import jax.numpy as jnp
import pytest

from odh_kubeflow_tpu.ops.attention import dense_attention
from odh_kubeflow_tpu.ops.pallas_attention import flash_attention


def _qkv(key, B, Sq, Sk, Hq, Hkv, hd, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, Sq, Hq, hd), dtype)
    k = jax.random.normal(kk, (B, Sk, Hkv, hd), dtype)
    v = jax.random.normal(kv, (B, Sk, Hkv, hd), dtype)
    return q, k, v


@pytest.mark.parametrize(
    "B,S,Hq,Hkv,hd",
    [
        (1, 256, 4, 4, 64),   # MHA, two blocks
        (2, 128, 8, 2, 64),   # GQA group=4, single block
        (1, 384, 4, 1, 128),  # MQA, three blocks, wide head
    ],
)
def test_forward_matches_dense_causal(B, S, Hq, Hkv, hd):
    q, k, v = _qkv(jax.random.key(0), B, S, S, Hq, Hkv, hd)
    ref = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    assert got.shape == ref.shape
    assert jnp.allclose(got, ref, atol=2e-5, rtol=2e-5), (
        float(jnp.abs(got - ref).max())
    )


def test_forward_non_causal():
    q, k, v = _qkv(jax.random.key(1), 2, 256, 256, 4, 4, 64)
    ref = dense_attention(q, k, v, causal=False)
    got = flash_attention(q, k, v, causal=False)
    assert jnp.allclose(got, ref, atol=2e-5, rtol=2e-5)


def test_forward_ragged_seq_len():
    # 200 is not a multiple of the 128 block: exercises padding + masks.
    q, k, v = _qkv(jax.random.key(2), 1, 200, 200, 4, 2, 64)
    ref = dense_attention(q, k, v, causal=True)
    got = flash_attention(q, k, v, causal=True)
    assert jnp.allclose(got, ref, atol=2e-5, rtol=2e-5)


def test_forward_segment_ids():
    B, S = 2, 256
    q, k, v = _qkv(jax.random.key(3), B, S, S, 4, 4, 64)
    # two packed documents per row
    seg = jnp.concatenate(
        [jnp.zeros((B, S // 2), jnp.int32), jnp.ones((B, S - S // 2), jnp.int32)],
        axis=1,
    )
    ref = dense_attention(q, k, v, causal=True, segment_ids=seg)
    got = flash_attention(q, k, v, causal=True, segment_ids=seg)
    assert jnp.allclose(got, ref, atol=2e-5, rtol=2e-5)


def test_grads_match_dense():
    B, S, Hq, Hkv, hd = 1, 256, 4, 2, 64
    q, k, v = _qkv(jax.random.key(4), B, S, S, Hq, Hkv, hd)
    tangent = jax.random.normal(jax.random.key(5), (B, S, Hq, hd))

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True) * tangent)

    ref_grads = jax.grad(lambda *a: loss(dense_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    got_grads = jax.grad(lambda *a: loss(flash_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    for name, r, g in zip("qkv", ref_grads, got_grads):
        err = float(jnp.abs(r - g).max())
        assert jnp.allclose(r, g, atol=5e-4, rtol=1e-3), (name, err)


def test_grads_match_dense_hd128():
    """The production llama3 head_dim (128) takes the NON-augmented
    backward path — lse/delta as row operands, VPU subtract —
    while hd=64 tests cover the augmented-operand path; both branches
    need gradient coverage (pallas_attention._bwd ``aug``)."""
    B, S, Hq, Hkv, hd = 1, 256, 4, 2, 128
    q, k, v = _qkv(jax.random.key(40), B, S, S, Hq, Hkv, hd)
    tangent = jax.random.normal(jax.random.key(41), (B, S, Hq, hd))

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True) * tangent)

    ref_grads = jax.grad(lambda *a: loss(dense_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    got_grads = jax.grad(lambda *a: loss(flash_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    for name, r, g in zip("qkv", ref_grads, got_grads):
        err = float(jnp.abs(r - g).max())
        assert jnp.allclose(r, g, atol=5e-4, rtol=1e-3), (name, err)


def test_grads_segment_ids():
    B, S = 1, 256
    q, k, v = _qkv(jax.random.key(6), B, S, S, 4, 4, 64)
    seg = (jnp.arange(S)[None, :] >= S // 2).astype(jnp.int32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True, segment_ids=seg) ** 2)

    ref = jax.grad(lambda *a: loss(dense_attention, *a), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(lambda *a: loss(flash_attention, *a), argnums=(0, 1, 2))(q, k, v)
    for r, g in zip(ref, got):
        assert jnp.allclose(r, g, atol=5e-4, rtol=1e-3)


def test_model_forward_with_flash_impl():
    """The llama forward dispatches to the pallas path via config."""
    from odh_kubeflow_tpu.models import LlamaConfig, forward, init_params

    import dataclasses

    cfg_d = LlamaConfig.tiny(dtype=jnp.float32)
    cfg_f = dataclasses.replace(cfg_d, attention_impl="flash")
    params = init_params(jax.random.key(0), cfg=cfg_d, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (2, 128), 0, cfg_d.vocab_size)
    ref = forward(params, tokens, cfg_d)
    got = forward(params, tokens, cfg_f)
    assert jnp.allclose(ref, got, atol=3e-4, rtol=3e-4), (
        float(jnp.abs(ref - got).max())
    )


def test_multiblock_causal_exercises_full_block_fast_path():
    """S=512 with explicit 128-blocks: the causal grid has interior
    blocks that take the mask-free full-block fast path in all three
    kernels (fwd/dq/dkv) plus diagonal edge blocks — both paths must
    agree with dense, forward and grads. (The default-block tests run
    every causal case as a single diagonal block, which would let a
    broken `full` predicate pass green.)"""
    B, S, Hq, Hkv, hd = 1, 512, 4, 2, 64
    q, k, v = _qkv(jax.random.key(11), B, S, S, Hq, Hkv, hd)
    tangent = jax.random.normal(jax.random.key(12), (B, S, Hq, hd))

    def flash128(q, k, v, causal=True):
        return flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)

    ref = dense_attention(q, k, v, causal=True)
    got = flash128(q, k, v)
    assert jnp.allclose(got, ref, atol=2e-5, rtol=2e-5), (
        float(jnp.abs(got - ref).max())
    )

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True) * tangent)

    ref_grads = jax.grad(lambda *a: loss(dense_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    got_grads = jax.grad(lambda *a: loss(flash128, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    for name, r, g in zip("qkv", ref_grads, got_grads):
        err = float(jnp.abs(r - g).max())
        assert jnp.allclose(r, g, atol=5e-4, rtol=1e-3), (name, err)


def test_bwd_blocks_differ_from_fwd():
    """Backward kernels tiled independently of the forward — including
    a ragged seq where fwd/bwd pad to different multiples, exercising
    the residual re-pad in _flash_bwd."""
    B, S, Hq, Hkv, hd = 1, 300, 4, 2, 64  # fwd pads to 384, bwd to 512
    q, k, v = _qkv(jax.random.key(20), B, S, S, Hq, Hkv, hd)
    tangent = jax.random.normal(jax.random.key(21), (B, S, Hq, hd))

    def flash_mixed(q, k, v, causal=True):
        return flash_attention(
            q, k, v, causal=causal, block_q=128, block_k=128,
            bwd_block_q=256, bwd_block_k=256,
        )

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True) * tangent)

    ref = jax.grad(lambda *a: loss(dense_attention, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    got = jax.grad(lambda *a: loss(flash_mixed, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    for name, r, g in zip("qkv", ref, got):
        err = float(jnp.abs(r - g).max())
        assert jnp.allclose(r, g, atol=5e-4, rtol=1e-3), (name, err)


def test_attn_remat_policy_skips_flash_forward_recompute():
    """remat_policy="attn" pins the flash kernel's named residuals
    ("flash_out"/"flash_lse"): the backward must not re-execute the
    forward kernel. Counted structurally — a remat'd layer lowers 4
    pallas_calls (fwd, recomputed fwd, dq, dkv) under the "none"
    policy but exactly 3 under "attn"; grads must match no-remat."""
    import dataclasses

    from odh_kubeflow_tpu.models import LlamaConfig, forward, init_params

    cfg0 = LlamaConfig.tiny(dtype=jnp.float32, attention_impl="flash")
    params = init_params(jax.random.key(0), cfg=cfg0, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (1, 128), 0, cfg0.vocab_size)

    def loss_fn(cfg):
        return lambda p: jnp.sum(forward(p, tokens, cfg) ** 2) / tokens.size

    cfg_attn = dataclasses.replace(cfg0, remat=True, remat_policy="attn")
    cfg_none = dataclasses.replace(cfg0, remat=True, remat_policy="none")

    n_attn = str(jax.make_jaxpr(jax.grad(loss_fn(cfg_attn)))(params)).count(
        "pallas_call"
    )
    n_none = str(jax.make_jaxpr(jax.grad(loss_fn(cfg_none)))(params)).count(
        "pallas_call"
    )
    assert n_none == 4, n_none
    assert n_attn == 3, n_attn

    # under a mesh the kernels run per shard inside a shard_map
    # (llama._flash_per_shard); the policy must still see the named
    # residuals through it
    from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh

    with jax.set_mesh(build_mesh(MeshConfig(fsdp=4, tensor=2), jax.devices())):
        sharded = str(jax.make_jaxpr(jax.grad(loss_fn(cfg_attn)))(params))
    assert "shard_map" in sharded
    assert sharded.count("pallas_call") == 3, sharded.count("pallas_call")

    g_ref = jax.grad(loss_fn(cfg0))(params)
    g_attn = jax.grad(loss_fn(cfg_attn))(params)
    flat_r, _ = jax.tree_util.tree_flatten(g_ref)
    flat_a, _ = jax.tree_util.tree_flatten(g_attn)
    for r, a in zip(flat_r, flat_a):
        assert jnp.allclose(r, a, atol=1e-5, rtol=1e-5), (
            float(jnp.abs(r - a).max())
        )


def test_multiblock_non_causal_full_blocks():
    """Non-causal multi-block: every block is full (no mask at all);
    padding via ragged seq keeps one edge block alive too."""
    B, S, Hq, Hkv, hd = 1, 320, 4, 4, 64  # pads to 384 at block 128
    q, k, v = _qkv(jax.random.key(13), B, S, S, Hq, Hkv, hd)
    ref = dense_attention(q, k, v, causal=False)
    got = flash_attention(q, k, v, causal=False, block_q=128, block_k=128)
    assert jnp.allclose(got, ref, atol=2e-5, rtol=2e-5), (
        float(jnp.abs(got - ref).max())
    )

