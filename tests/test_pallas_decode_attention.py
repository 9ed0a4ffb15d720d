"""``decode_attend`` (ops/pallas_decode_attention.py) in interpret mode
against ``dense_attention`` on the layer sliced out of the stack: every
way the cached forward indexes the cache, block skipping included."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from odh_kubeflow_tpu.ops.attention import dense_attention
from odh_kubeflow_tpu.ops.pallas_decode_attention import (
    decode_attend,
    supported,
)

L, S_MAX = 3, 256

CASES = {
    # name: B, S, Hq, Hkv, hd, index (None = scalar), dtype, masked, blocks
    "decode": (3, 1, 8, 2, 128, [0, 130, 255], jnp.float32, True, {}),
    "decode-bf16": (3, 1, 8, 2, 128, [5, 127, 128], jnp.bfloat16, True, {}),
    "decode-hd64": (2, 1, 8, 4, 64, [40, 200], jnp.float32, True, {}),
    "decode-no-mask": (2, 1, 4, 4, 128, [17, 250], jnp.float32, False, {}),
    "window-clipped": (3, 3, 8, 2, 128, [4, 126, 254], jnp.float32, True, {}),
    "scalar-step": (2, 1, 4, 2, 128, None, jnp.float32, True, {}),
    "prefill-row-blocks": (1, 40, 4, 2, 128, None, jnp.float32, True,
                           {"block_rows": 32}),
    "prefill-one-kv-block": (2, 24, 4, 2, 128, None, jnp.bfloat16, True,
                             {"block_k": 256}),
}


@pytest.mark.parametrize("name", list(CASES))
def test_decode_attend_matches_dense_attention(name):
    B, S, Hq, Hkv, hd, index, dtype, masked, blocks = CASES[name]
    kq, kk, kv, km = jax.random.split(jax.random.key(len(name)), 4)
    q = jax.random.normal(kq, (B, S, Hq, hd), dtype)
    cache_k = jax.random.normal(kk, (L, B, S_MAX, Hkv * hd), dtype)
    cache_v = jax.random.normal(kv, (L, B, S_MAX, Hkv * hd), dtype)
    index = jnp.int32(S_MAX // 3) if index is None else jnp.asarray(index, jnp.int32)
    kv_mask = None
    if masked:  # holes, as ragged prompts leave them; position 0 is real
        kv_mask = (jax.random.uniform(km, (B, S_MAX)) < 0.8).at[:, 0].set(True)
    assert supported(cache_k, hd)
    for layer in (0, L - 1):
        got = decode_attend(
            q, cache_k, cache_v, jnp.int32(layer), index, kv_mask,
            interpret=True, **{"block_k": 128, **blocks},
        )
        want = dense_attention(
            q, cache_k[layer].reshape(B, S_MAX, Hkv, hd),
            cache_v[layer].reshape(B, S_MAX, Hkv, hd),
            causal=True, q_offset=index, kv_mask=kv_mask,
        )
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2 if dtype == jnp.bfloat16 else 2e-5, rtol=0,
        )


def test_decode_attend_never_reads_past_a_rows_position():
    """Blocks wholly past a row's last query are skipped, not masked:
    non-finite values there cannot reach the output."""
    B, Hq, Hkv, hd = 2, 4, 2, 128
    kq, kk, kv = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(kq, (B, 1, Hq, hd), jnp.float32)
    cache_k = jax.random.normal(kk, (L, B, S_MAX, Hkv * hd), jnp.float32)
    cache_v = jax.random.normal(kv, (L, B, S_MAX, Hkv * hd), jnp.float32)
    index = jnp.asarray([100, 127], jnp.int32)  # both inside the first block
    want = decode_attend(q, cache_k, cache_v, jnp.int32(1), index,
                         interpret=True, block_k=128)
    poisoned = decode_attend(
        q, cache_k.at[:, :, 128:].set(jnp.nan), cache_v.at[:, :, 128:].set(jnp.nan),
        jnp.int32(1), index, interpret=True, block_k=128,
    )
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(want))


@pytest.mark.parametrize(
    "shape,hd,ok",
    [((2, 4, 2048, 1024), 128, True), ((2, 4, 2048, 512), 64, True),
     ((2, 4, 13, 32), 16, False), ((2, 4, 2048, 32), 16, False)],
)
def test_supported_asks_for_whole_tiles(shape, hd, ok):
    assert supported(jax.ShapeDtypeStruct(shape, jnp.bfloat16), hd) is ok
