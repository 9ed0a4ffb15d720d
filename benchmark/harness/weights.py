"""Seeded weights, made by the benchmark on the device in ONE jitted
call, in the type they are served in: int8 values drawn directly with a
float32 scale per output channel (no bf16 tree is ever built, nothing is
made on the host), embeddings and norms in bf16. The tree has the layout
the program is handed (leaves stacked over layers, a matmul weight as
``{"q", "scale"}``), and the same tree goes to the plain reference, so
neither reads anything the other made.

As dequantised, a matmul weight has standard deviation fan_in ** -0.5:
values are round(normal * 127 / 4) clipped to +-127 and the scale is
4 * fan_in ** -0.5 / 127 within +-25 % per channel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key for any whole-number seed, including those past 2**31 that
    a 32-bit key constructor refuses."""
    key = jax.random.key(int(seed) & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, int(seed) >> 31)


def int8_leaf(key, shape, fan_in):
    kq, ks = jax.random.split(key)
    q = jnp.clip(
        jnp.round(jax.random.normal(kq, shape, jnp.float32) * (127 / 4)),
        -127, 127,
    ).astype(jnp.int8)
    scale_shape = shape[:-2] + (1, shape[-1])
    jitter = jax.random.uniform(ks, scale_shape, jnp.float32, 0.75, 1.25)
    return {"q": q, "scale": 4 * fan_in**-0.5 / 127 * jitter}


def norm_leaf(key, shape):
    return (1 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16
    )


def make_params(cfg: dict, seed: int, family):
    """The frozen base, on the default device. ``family`` is the module
    of the configuration's model family: its ``layer`` makes one layer."""
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]

    def build(key):
        ke, kl, kn, kh = jax.random.split(key, 4)
        return {
            "embed": jax.random.normal(ke, (V, D), jnp.bfloat16),
            # a layer at a time, so that the float32 draws of one layer
            # are the only transients beside the int8 tree
            "layers": jax.lax.map(
                lambda kk: family.layer(kk, cfg), jax.random.split(kl, L)
            ),
            "final_norm": norm_leaf(kn, (D,)),
            "lm_head": int8_leaf(kh, (D, V), D),
        }

    return jax.jit(build)(seed_key(seed))


def make_lora(cfg: dict, lora: dict, seed: int):
    """Adapters in mid-training state (both factors non-zero, so that
    every leaf has a gradient from the first step), float32, on the
    attention projections, in the program's layout."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    r = lora["rank"]
    dims = {"wq": (D, q_dim), "wk": (D, kv_dim), "wv": (D, kv_dim), "wo": (q_dim, D)}

    def build(key):
        out = {}
        for i, (name, (fi, fo)) in enumerate(dims.items()):
            ka, kb = jax.random.split(jax.random.fold_in(key, i))
            out[name] = {
                "a": jax.random.normal(ka, (L, fi, r), jnp.float32) * fi**-0.5,
                "b": jax.random.normal(kb, (L, r, fo), jnp.float32) * 0.02,
                "scale": jnp.full((L,), lora["alpha"] / r, jnp.float32),
            }
        return {"layers": out}

    return jax.jit(build)(jax.random.fold_in(seed_key(seed), 0x10FA))
