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



# ---------------------------------------------------------------------------
# segmented calls: live-pair tables built on the device from the ids
# ---------------------------------------------------------------------------

import numpy as np

from odh_kubeflow_tpu.ops import pallas_attention as pa


def _ids(*lengths, start=1):
    """Ascending ids as ``pack_documents`` gives them: one id a piece."""
    return np.concatenate(
        [np.full(n, start + i, np.int32) for i, n in enumerate(lengths)]
    )


S_SEG = 512
SEGMENT_CASES = {
    # name: ([B, S] ids, Hq, Hkv)
    "wall_on_block_edge": (_ids(256, 256)[None], 2, 2),
    "document_over_blocks": (_ids(40, 400, 72)[None], 2, 2),
    "one_token_documents": (
        np.concatenate([_ids(200), _ids(*[1] * 112, start=2), _ids(200, start=200)])[None],
        2, 2,
    ),
    "padded_tail": (np.concatenate([_ids(130, 190), np.zeros(192, np.int32)])[None], 2, 2),
    "two_layouts": (np.stack([_ids(512), _ids(100, 28, 256, 128)]), 2, 2),
    "gqa_group4": (np.stack([_ids(300, 212), _ids(128, 384)]), 4, 1),
    # ids that fall and repeat: the id ranges overlap where no pair
    # matches, so the table is a superset and the in-tile mask decides
    "non_ascending": (
        np.concatenate([_ids(64, start=9), _ids(64), _ids(128, start=5), _ids(256, start=20)])[None],
        2, 2,
    ),
}
SEGMENT_BLOCKS = {"block128": (128, 128), "block256_tile128": (256, 128)}


def _brute_tables(seg, *, block, tile, order, group):
    """The walk ``_segment_tables`` should give, enumerated pair by
    pair in numpy, and the tiles in which some pair is really alive."""
    B, S = seg.shape
    nb, sub = S // block, block // tile
    rows, exact_tiles, walked_tiles = [], 0, 0
    for b in range(B):
        run = np.zeros((nb, nb), np.int64)
        full = np.zeros((nb, nb), np.int64)
        for qt in range(S // tile):
            for kt in range(S // tile):
                q = seg[b, qt * tile:(qt + 1) * tile]
                k = seg[b, kt * tile:(kt + 1) * tile]
                causal = (
                    np.arange(tile)[:, None] + qt * tile
                    >= np.arange(tile)[None, :] + kt * tile
                )
                same = q[:, None] == k[None, :]
                overlap = q.min() <= k.max() and k.min() <= q.max()
                assert not ((causal & same).any() and not overlap)
                exact_tiles += int((causal & same).any())
                if not (causal.any() and overlap):
                    continue
                walked_tiles += 1
                bit = 1 << ((qt % sub) * sub + kt % sub)
                run[qt // sub, kt // sub] |= bit
                if causal.all() and same.all():
                    full[qt // sub, kt // sub] |= bit
        entries = []
        if order == "row":
            for qi in range(nb):
                ks = [ki for ki in range(nb) if run[qi, ki]] or [0]
                for j, ki in enumerate(ks):
                    entries.append(
                        (qi, ki, 0, j == 0, j == len(ks) - 1, run[qi, ki], full[qi, ki])
                    )
        else:
            for ki in range(nb):
                qs = [qj for qj in range(nb) if run[qj, ki]]
                items = [(qj, g) for g in range(group) for qj in qs] or [(0, 0)]
                for j, (qj, g) in enumerate(items):
                    entries.append(
                        (qj, ki, g, j == 0, j == len(items) - 1, run[qj, ki], full[qj, ki])
                    )
        rows.append(entries)
    return rows, walked_tiles, exact_tiles


@pytest.mark.parametrize("order", ["row", "col"])
@pytest.mark.parametrize("blocks", SEGMENT_BLOCKS)
@pytest.mark.parametrize("case", SEGMENT_CASES)
def test_segment_tables_match_brute_force(case, blocks, order):
    seg, Hq, Hkv = SEGMENT_CASES[case]
    block, tile = SEGMENT_BLOCKS[blocks]
    group = Hq // Hkv
    qseg, kseg = pa._prep_segments(jnp.asarray(seg), S_SEG, S_SEG, S_SEG, S_SEG)
    geom = dict(causal=True, q_offset=0, sk=S_SEG, block_q=block, block_k=block)
    tabs, live, causal = pa._segment_tables(
        qseg, kseg, tile_q=tile, tile_k=tile, order=order, group=group, **geom
    )
    tabs = [np.asarray(t) for t in tabs]
    static = pa._pair_tables(
        num_q=S_SEG // block, num_k=S_SEG // block, order=order, group=group,
        **geom,
    )
    assert all(t.shape == (seg.shape[0], static[0].shape[0]) for t in tabs)

    want, walked, exact = _brute_tables(
        seg, block=block, tile=tile, order=order, group=group
    )
    assert int(live) == walked
    n_tiles = S_SEG // tile
    assert causal == seg.shape[0] * n_tiles * (n_tiles + 1) // 2
    if case in ("non_ascending", "padded_tail"):
        # the ids fall (padding is id 0, after the last document): a
        # superset of the tiles that hold a live pair, and still a skip
        assert exact < walked < causal
    else:
        assert exact == walked  # ascending ids: the range test is exact
    for b, entries in enumerate(want):
        n = len(entries)
        got = list(zip(*(t[b, :n].tolist() for t in tabs)))
        assert got == [tuple(int(x) for x in e) for e in entries], (b, got)
        # past the live count: the last live pair again, every flag 0
        for t, last in zip(tabs[:3], entries[-1][:3]):
            assert (t[b, n:] == int(last)).all()
        for t in tabs[3:]:
            assert (t[b, n:] == 0).all()


@pytest.mark.parametrize("blocks", SEGMENT_BLOCKS)
@pytest.mark.parametrize("case", SEGMENT_CASES)
def test_segmented_forward_and_grads_match_dense(case, blocks):
    seg, Hq, Hkv = SEGMENT_CASES[case]
    seg = jnp.asarray(seg)
    block, tile = SEGMENT_BLOCKS[blocks]
    B = seg.shape[0]
    q, k, v = _qkv(jax.random.key(30), B, S_SEG, S_SEG, Hq, Hkv, 64)
    tangent = jax.random.normal(jax.random.key(31), (B, S_SEG, Hq, 64))

    def flash(q, k, v, **kw):
        return flash_attention(q, k, v, block_q=block, block_k=block, tile=tile, **kw)

    def out_and_grads(fn):
        def loss(q, k, v):
            out = fn(q, k, v, causal=True, segment_ids=seg)
            return jnp.sum(out * tangent), out

        (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return out, grads

    ref_out, ref_grads = out_and_grads(dense_attention)
    got_out, got_grads = jax.jit(lambda: out_and_grads(flash))()
    assert jnp.allclose(got_out, ref_out, atol=2e-5, rtol=2e-5), (
        float(jnp.abs(got_out - ref_out).max())
    )
    for name, r, g in zip("qkv", ref_grads, got_grads):
        assert jnp.allclose(r, g, atol=5e-4, rtol=1e-3), (
            name, float(jnp.abs(r - g).max())
        )


def test_segment_tables_keep_an_owner_with_no_live_partner():
    """Keys that hold no id of the queries: every Q block (and every K
    block) still walks one pair, with no tile to run, and the output is
    zero as the dense semantics have it."""
    S = 256
    seg_q = jnp.asarray(_ids(S)[None])
    qseg, _ = pa._prep_segments(seg_q, S, S, S, S)
    _, kseg = pa._prep_segments(seg_q + 7, S, S, S, S)
    geom = dict(causal=True, q_offset=0, sk=S, block_q=128, block_k=128,
                tile_q=128, tile_k=128)
    for order, owners in (("row", 0), ("col", 1)):
        tabs, live, _ = pa._segment_tables(qseg, kseg, order=order, **geom)
        tabs = [np.asarray(t)[0] for t in tabs]
        assert int(live) == 0
        assert tabs[owners][:2].tolist() == [0, 1]
        assert tabs[3][:2].tolist() == [1, 1] and tabs[4][:2].tolist() == [1, 1]
        assert not tabs[5].any() and not tabs[6].any()
        assert not tabs[3][2:].any() and not tabs[4][2:].any()


def _brute_pair_tables(num_q, num_k, *, causal, q_offset, sk, block, order, group):
    def live(qb, kb):
        return kb * block < sk and (
            not causal or kb * block <= qb * block + block - 1 + q_offset
        )

    out = []
    if order == "row":
        for qi in range(num_q):
            ks = [kb for kb in range(num_k) if live(qi, kb)] or [0]
            out += [(qi, kb, 0, j == 0, j == len(ks) - 1) for j, kb in enumerate(ks)]
    else:
        for kb in range(num_k):
            items = [
                (qj, kb, g) for g in range(group)
                for qj in range(num_q) if live(qj, kb)
            ] or [(0, kb, 0)]
            out += [(*it, j == 0, j == len(items) - 1) for j, it in enumerate(items)]
    return [tuple(int(x) for x in e) for e in out]


@pytest.mark.parametrize(
    "num_q,num_k,causal,q_offset,sk,order,group",
    [
        (4, 4, True, 0, 4096, "row", 1),     # the training shape, forward/dq
        (4, 4, True, 0, 4096, "col", 4),     # and its dk/dv walk, GQA 4
        (16, 16, True, 0, 16384, "row", 1),  # the 16k geometry
        (2, 4, True, 2048, 4000, "col", 2),  # an offset shard, padded keys
        (3, 3, False, 0, 3072, "row", 1),
    ],
)
def test_unsegmented_tables_are_the_static_walk(
    num_q, num_k, causal, q_offset, sk, order, group, monkeypatch
):
    """Without segment ids nothing changed: the tables are the host's
    enumeration of the causal geometry at the 1024 blocks, and a call
    asks for exactly those."""
    geom = dict(causal=causal, q_offset=q_offset, sk=sk, block_q=1024, block_k=1024)
    tabs = pa._pair_tables(num_q=num_q, num_k=num_k, order=order, group=group, **geom)
    got = list(zip(*(np.asarray(t).tolist() for t in tabs)))
    assert got == _brute_pair_tables(
        num_q, num_k, causal=causal, q_offset=q_offset, sk=sk, block=1024,
        order=order, group=group,
    )


def test_unsegmented_call_asks_for_the_static_tables(monkeypatch):
    asked = []
    real = pa._pair_tables

    def spy(**kw):
        asked.append(kw)
        return real(**kw)

    monkeypatch.setattr(pa, "_pair_tables", spy)
    monkeypatch.setattr(
        pa, "_segment_tables", lambda *a, **kw: pytest.fail("segment tables built")
    )
    q, k, v = _qkv(jax.random.key(50), 1, 2048, 2048, 2, 1, 64)
    jax.eval_shape(jax.grad(lambda q: flash_attention(q, k, v).sum()), q)
    assert [a["order"] for a in asked] == ["row", "row", "col"]
    assert asked[2]["group"] == 2
    for a in asked:
        assert (a["block_q"], a["block_k"], a["num_q"], a["num_k"]) == (1024, 1024, 2, 2)
        assert (a["causal"], a["q_offset"], a["sk"]) == (True, 0, 2048)


def test_live_block_counts_of_packed_rows():
    """The counter the trainer reports: tiles run against tiles the
    causal walk alone would run, at the default blocks."""
    S, tile = 2048, pa.SEGMENT_TILE
    one = jnp.asarray(_ids(S)[None])
    live, causal = pa.live_block_counts(one)
    n = S // tile
    assert (int(live), causal) == (n * (n + 1) // 2, n * (n + 1) // 2)
    walls = jnp.asarray(np.stack([_ids(*[tile] * n), _ids(S)]))
    live, causal = pa.live_block_counts(walls)
    assert (int(live), causal) == (n + n * (n + 1) // 2, n * (n + 1))
