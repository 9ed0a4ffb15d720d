"""The dense family (pre-norm RMSNorm, grouped-query attention, rotary
embeddings, SwiGLU, no biases): what the harness needs of ONE model
family, found by the ``family`` a configuration file names. A new family
is a new file here: the program's config object for a configuration
file, one layer of the seeded frozen base in the program's layout, and
the counts of matmul weights from the file's shapes."""

from __future__ import annotations

import jax

from benchmark.harness.weights import int8_leaf, norm_leaf


def program_config(config: dict):
    """The program's config object for a configuration file."""
    import jax.numpy as jnp

    from odh_kubeflow_tpu.models import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        dtype=jnp.bfloat16,
        **config["deployment"].get("program", {}),
    )


def attention_weights(keys, cfg: dict) -> dict:
    """The norms and the four attention projections of one layer."""
    D = cfg["hidden_size"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {
        "attn_norm": norm_leaf(next(keys), (D,)),
        "wq": int8_leaf(next(keys), (D, q_dim), D),
        "wk": int8_leaf(next(keys), (D, kv_dim), D),
        "wv": int8_leaf(next(keys), (D, kv_dim), D),
        "wo": int8_leaf(next(keys), (q_dim, D), q_dim),
        "mlp_norm": norm_leaf(next(keys), (D,)),
    }


def layer(key, cfg: dict) -> dict:
    """One layer of the frozen base."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    keys = iter(jax.random.split(key, 12))
    out = attention_weights(keys, cfg)
    out["w_gate"] = int8_leaf(next(keys), (D, F), D)
    out["w_up"] = int8_leaf(next(keys), (D, F), D)
    out["w_down"] = int8_leaf(next(keys), (F, D), F)
    return out


def attention_matmul_weights(cfg: dict) -> int:
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return D * H * hd + 2 * D * Hkv * hd + H * hd * D


def token_weights_per_layer(cfg: dict) -> int:
    """Matmul weights one token passes through in one layer."""
    return attention_matmul_weights(cfg) + 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def step_weights_per_layer(cfg: dict) -> int:
    """Matmul weights one decode step must read in one layer: in a dense
    layer, all that a token passes through."""
    return token_weights_per_layer(cfg)
