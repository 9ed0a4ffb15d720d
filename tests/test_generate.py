"""KV-cache generation: correctness vs the full forward, ragged
prompts, sampling semantics, and sharded decode on the virtual mesh.

The reference has no inference path at all (SURVEY.md §2.4); the test
model here is the training path itself — greedy cached decode must
reproduce exactly what repeated full forwards would.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.models import (
    GenerateConfig,
    LlamaConfig,
    LoraConfig,
    cache_specs,
    forward,
    generate,
    init_cache,
    init_lora_params,
    init_params,
    lora_specs,
    param_specs,
    sample_logits,
)
from odh_kubeflow_tpu.parallel.mesh import MeshConfig, build_mesh, shard_tree


@pytest.fixture(scope="module")
def tiny():
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    params = init_params(jax.random.key(0), cfg)
    return cfg, params


def _greedy_reference(params, cfg, prompt, n_new, lora=None):
    """Uncached greedy decode: full forward over the growing sequence."""
    tokens = prompt
    out = []
    for _ in range(n_new):
        logits = forward(params, tokens, cfg, lora=lora)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        out.append(nxt)
        tokens = jnp.concatenate([tokens, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


def test_greedy_matches_full_forward(tiny):
    cfg, params = tiny
    prompt = jax.random.randint(jax.random.key(1), (2, 7), 0, cfg.vocab_size)
    gen_cfg = GenerateConfig(max_new_tokens=6, cache_dtype=jnp.float32)
    got = generate(params, prompt, cfg, gen_cfg)
    want = _greedy_reference(params, cfg, prompt, 6)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), np.asarray(want))
    assert got["lengths"].tolist() == [6, 6] or (got["tokens"] != 0).all()


def test_greedy_with_lora_adapter(tiny):
    cfg, params = tiny
    lora_cfg = LoraConfig(rank=4)
    lora = init_lora_params(jax.random.key(5), cfg, lora_cfg)
    # break b==0 symmetry so the adapter actually changes logits
    lora = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.key(6), x.shape, x.dtype) * 0.1
        if x.ndim >= 2
        else x,
        lora,
    )
    prompt = jax.random.randint(jax.random.key(2), (2, 5), 0, cfg.vocab_size)
    gen_cfg = GenerateConfig(max_new_tokens=4, cache_dtype=jnp.float32)
    got = generate(params, prompt, cfg, gen_cfg, lora=lora)
    want = _greedy_reference(params, cfg, prompt, 4, lora=lora)
    np.testing.assert_array_equal(np.asarray(got["tokens"]), np.asarray(want))
    base = generate(params, prompt, cfg, gen_cfg)
    assert not np.array_equal(
        np.asarray(got["tokens"]), np.asarray(base["tokens"])
    ), "adapter had no effect on generation"


def test_ragged_prompts_match_per_row(tiny):
    cfg, params = tiny
    k = jax.random.key(3)
    row0 = jax.random.randint(k, (1, 4), 1, cfg.vocab_size)
    row1 = jax.random.randint(jax.random.key(4), (1, 7), 1, cfg.vocab_size)
    # batch them right-padded to 7
    batch = jnp.zeros((2, 7), jnp.int32)
    batch = batch.at[0, :4].set(row0[0])
    batch = batch.at[1, :].set(row1[0])
    lengths = jnp.array([4, 7], jnp.int32)
    gen_cfg = GenerateConfig(max_new_tokens=5, cache_dtype=jnp.float32)
    got = generate(params, batch, cfg, gen_cfg, prompt_lengths=lengths)
    want0 = _greedy_reference(params, cfg, row0, 5)
    want1 = _greedy_reference(params, cfg, row1, 5)
    np.testing.assert_array_equal(np.asarray(got["tokens"][0]), np.asarray(want0[0]))
    np.testing.assert_array_equal(np.asarray(got["tokens"][1]), np.asarray(want1[0]))


def test_eos_stops_and_pads(tiny):
    cfg, params = tiny
    prompt = jax.random.randint(jax.random.key(7), (1, 5), 0, cfg.vocab_size)
    # find what greedy emits, then declare its 2nd token to be eos
    ref = _greedy_reference(params, cfg, prompt, 4)
    eos = int(ref[0, 1])
    gen_cfg = GenerateConfig(
        max_new_tokens=4, eos_id=eos, pad_id=-1, cache_dtype=jnp.float32
    )
    got = generate(params, prompt, cfg, gen_cfg)
    toks = got["tokens"][0].tolist()
    assert toks[0] == int(ref[0, 0])
    assert toks[1] == eos
    assert toks[2:] == [-1, -1]
    assert int(got["lengths"][0]) == 2


def test_sampling_semantics(tiny):
    cfg, params = tiny
    prompt = jax.random.randint(jax.random.key(8), (2, 6), 0, cfg.vocab_size)
    greedy = generate(
        params, prompt, cfg, GenerateConfig(max_new_tokens=4, cache_dtype=jnp.float32)
    )
    # top_k=1 sampling degenerates to greedy regardless of temperature
    topk1 = generate(
        params,
        prompt,
        cfg,
        GenerateConfig(
            max_new_tokens=4, temperature=5.0, top_k=1, cache_dtype=jnp.float32
        ),
        key=jax.random.key(9),
    )
    np.testing.assert_array_equal(
        np.asarray(greedy["tokens"]), np.asarray(topk1["tokens"])
    )
    # tiny top_p keeps only the argmax token
    topp = generate(
        params,
        prompt,
        cfg,
        GenerateConfig(
            max_new_tokens=4, temperature=2.0, top_p=1e-6, cache_dtype=jnp.float32
        ),
        key=jax.random.key(10),
    )
    np.testing.assert_array_equal(
        np.asarray(greedy["tokens"]), np.asarray(topp["tokens"])
    )


def test_sample_logits_distribution():
    logits = jnp.log(jnp.array([[0.05, 0.15, 0.8]], jnp.float32))
    # greedy
    assert int(sample_logits(logits, jax.random.key(0))[0]) == 2
    # top_p=0.5: only token 2 (0.8 mass) survives the nucleus
    draws = [
        int(
            sample_logits(
                logits, jax.random.key(i), temperature=1.0, top_p=0.5
            )[0]
        )
        for i in range(20)
    ]
    assert set(draws) == {2}
    # top_k=2 never draws token 0
    draws = [
        int(
            sample_logits(
                logits, jax.random.key(i), temperature=1.0, top_k=2
            )[0]
        )
        for i in range(50)
    ]
    assert 0 not in draws and 2 in draws


def test_sharded_decode_matches_single_device(tiny, devices8):
    cfg, params = tiny
    prompt = jax.random.randint(jax.random.key(11), (4, 6), 0, cfg.vocab_size)
    gen_cfg = GenerateConfig(max_new_tokens=5, cache_dtype=jnp.float32)
    want = generate(params, prompt, cfg, gen_cfg)

    mesh = build_mesh(MeshConfig(data=2, fsdp=2, tensor=2), devices8)
    with jax.set_mesh(mesh):
        sharded_params = shard_tree(params, mesh, param_specs(cfg))
        got = jax.jit(
            lambda p, t: generate(p, t, cfg, gen_cfg)
        )(sharded_params, prompt)
    np.testing.assert_array_equal(
        np.asarray(got["tokens"]), np.asarray(want["tokens"])
    )


def test_cache_specs_shape(tiny):
    cfg, _ = tiny
    specs = cache_specs(cfg)
    assert set(specs) == {"k", "v"}
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 16))
    assert len(specs["k"]) == cache["k"].ndim == 4


# --- the cached forward: the stacked cache is the layer scan's carry -------

_T = 16  # tokens a row, and the cache's length: the window case clips


def _cached_family(family: str):
    """(cfg, params, plain logits of the whole sequence, cached forward)
    in float32, with ample expert capacity so that a token routed alone
    and one routed in its sequence pick the same experts."""
    from odh_kubeflow_tpu.models import moe as moe_lib

    tokens = jax.random.randint(jax.random.key(11), (3, _T), 1, 256)
    if family == "dense":
        cfg = LlamaConfig.tiny(dtype=jnp.float32)
        params = init_params(jax.random.key(0), cfg)
        return cfg, cfg, params, tokens, forward(params, tokens, cfg)
    cfg = moe_lib.MoeConfig.mixtral_tiny(
        base=LlamaConfig.tiny(dtype=jnp.float32), capacity_factor=8.0
    )
    params = moe_lib.init_params(jax.random.key(0), cfg, dtype=jnp.float32)
    return cfg, cfg.base, params, tokens, moe_lib.forward(params, tokens, cfg)[0]


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("family", ["dense", "moe"])
@pytest.mark.parametrize("case", ["prefill", "step", "rows", "window"])
def test_cached_forward_matches_plain_forward(case, family, cache_dtype):
    """Logits and the final cache of one cached call against a plain
    forward over the whole sequence, for every way a caller indexes the
    cache: scalar index with a prompt (prefill) and with one token
    (generate's step), a [B] index with one token at ragged depths and
    a row that is not active (the engine's decode), a [B] index with a
    window of three clipped at the cache's last position (speculative
    verify). The whole-sequence prefill, held to the plain forward by
    its logits, is what the other cases' caches are compared with."""
    from odh_kubeflow_tpu.models.generate import family_forward

    cfg, cache_cfg, params, tokens, plain = _cached_family(family)
    _, fwd = family_forward(cfg)
    B = tokens.shape[0]
    slots = jnp.arange(_T, dtype=jnp.int32)[None, :]
    tol = dict(atol=2e-4, rtol=2e-4) if cache_dtype == jnp.float32 else dict(
        atol=6e-2, rtol=6e-2
    )

    def prefill(n):
        return fwd(
            params, tokens[:, :n], cfg, init_cache(cache_cfg, B, _T, cache_dtype),
            jnp.int32(0),
            positions=jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32), (B, n)),
            kv_mask=jnp.broadcast_to(slots < n, (B, _T)),
        )

    def upto(cache, depth):  # what a row holds below its depth; zeros above
        keep = (slots < jnp.asarray(depth)[:, None])[None, :, :, None]
        return jax.tree.map(lambda a: jnp.where(keep, a, 0), cache)

    def assert_cache(got, want):
        for kv in ("k", "v"):
            assert got[kv].dtype == cache_dtype and got[kv].shape == want[kv].shape
            np.testing.assert_allclose(
                np.asarray(got[kv], np.float32), np.asarray(want[kv], np.float32),
                **tol,
            )

    ref_logits, ref_cache = prefill(_T)
    if case == "prefill":
        np.testing.assert_allclose(np.asarray(ref_logits), np.asarray(plain), **tol)
        _, part = prefill(_T - 5)
        assert_cache(part, upto(ref_cache, [_T - 5] * B))
        assert float(jnp.abs(ref_cache["k"].astype(jnp.float32)).min()) > 0
    elif case == "step":
        _, cache = prefill(_T - 1)
        logits, cache = fwd(
            params, tokens[:, _T - 1:], cfg, cache, jnp.int32(_T - 1),
            positions=jnp.full((B, 1), _T - 1, jnp.int32),
            kv_mask=jnp.ones((B, _T), bool),
        )
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(plain[:, _T - 1]), **tol
        )
        assert_cache(cache, ref_cache)
    elif case == "rows":
        depth = jnp.asarray([5, 9, 3], jnp.int32)
        active = jnp.asarray([True, True, False])
        logits, cache = fwd(
            params, jnp.take_along_axis(tokens, depth[:, None], axis=1), cfg,
            upto(ref_cache, depth), depth, positions=depth[:, None],
            # only an active row's valid region grows (engine._decode_chunk)
            kv_mask=(slots < depth[:, None]) | (active[:, None] & (slots == depth[:, None])),
        )
        for b in (0, 1):
            np.testing.assert_allclose(
                np.asarray(logits[b, 0]), np.asarray(plain[b, int(depth[b])]), **tol
            )
        assert bool(jnp.isfinite(logits).all())
        live = depth + active.astype(jnp.int32)
        assert_cache(upto(cache, live), upto(ref_cache, live))
        # every row writes; what the idle row wrote is its own token's
        # keys in the first layer, and unattended filler above it
        np.testing.assert_allclose(
            np.asarray(cache["k"][0, 2, 3], np.float32),
            np.asarray(ref_cache["k"][0, 2, 3], np.float32), **tol,
        )
        assert float(jnp.abs(cache["k"][1:, 2, 3].astype(jnp.float32)).max()) > 0
        assert float(jnp.abs(cache["k"][:, :, -1].astype(jnp.float32)).max()) == 0
    else:
        S = 3
        depth = jnp.asarray([4, _T - 2, 8], jnp.int32)  # row 1: 14, 15, 15
        real = jnp.asarray([3, 1, 3])  # window tokens that exist in the row
        cols = jnp.clip(depth[:, None] + jnp.arange(S)[None, :], 0, _T - 1)
        logits, cache = fwd(
            params, jnp.take_along_axis(tokens, cols, axis=1), cfg,
            upto(ref_cache, depth), depth,
            positions=depth[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :],
            kv_mask=slots < (depth + real)[:, None],
        )
        for b in range(B):
            n = int(real[b])
            np.testing.assert_allclose(
                np.asarray(logits[b, :n]),
                np.asarray(plain[b, int(depth[b]):int(depth[b]) + n]), **tol,
            )
        # the clipped position holds one of the two tokens sent to it
        got, want = upto(cache, depth + real), upto(ref_cache, depth + real)
        assert_cache(got, want)
        assert cache["k"].shape == ref_cache["k"].shape


@pytest.mark.parametrize("index", ["scalar", "rows"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_cache_is_the_layer_scans_carry(family, index):
    """The cached forward has ONE scan over layers, and the stacked
    cache is in its carry: no scanned input and no scanned output has
    the cache's shape (either would make XLA slice every layer's whole
    cache out of the stack, or write it back, each layer of each step)."""
    from odh_kubeflow_tpu.models.generate import family_forward

    cfg, cache_cfg, params, tokens, _ = _cached_family(family)
    _, fwd = family_forward(cfg)
    B = tokens.shape[0]
    cache = init_cache(cache_cfg, B, _T)
    at = jnp.int32(3) if index == "scalar" else jnp.asarray([3, 5, 2], jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda params, cache: fwd(
            params, tokens[:, :1], cfg, cache, at,
            positions=jnp.full((B, 1), 3, jnp.int32),
            kv_mask=jnp.ones((B, _T), bool),
        )
    )(params, cache).jaxpr
    layer_scans = [
        e for e in jaxpr.eqns
        if e.primitive.name == "scan"
        and e.params["length"] == cache_cfg.num_layers
    ]
    assert len(layer_scans) == 1
    (scan,) = layer_scans
    n_consts, n_carry = scan.params["num_consts"], scan.params["num_carry"]
    shape = cache["k"].shape
    shapes = lambda vs: [tuple(v.aval.shape) for v in vs]  # noqa: E731
    carried_in = shapes(scan.invars[n_consts:n_consts + n_carry])
    carried_out = shapes(scan.outvars[:n_carry])
    assert carried_in.count(shape) == carried_out.count(shape) == 2  # k, v
    for other in (
        shapes(scan.invars[:n_consts]),  # closed over
        shapes(scan.invars[n_consts + n_carry:]),  # scanned inputs
        shapes(scan.outvars[n_carry:]),  # scanned outputs
    ):
        assert shape not in other and shape[1:] not in other
