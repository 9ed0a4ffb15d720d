"""The ``cohere2_moe`` family (Command A+) as one chip's SHARE of a
deployment: what the harness needs of it, found by the ``family`` a
configuration file names. Parallel block from one LayerNorm, window
layers (interleaved RoPE) and global ones (none) in the source's
``layer_types`` order, a sigmoid router over all the source's experts
with only ``num_experts`` of them held here, shared experts stored side
by side, tied head over a slice of the vocabulary.

Unlike ``dense``, it brings its own builder of the seeded frozen base
(``make_params``: ``harness/weights.make_params`` always draws an untied
int8 head) and its own byte counts (``harness/counts.py`` reads a dense
layer's keys). Everything reads the configuration FILE."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights import int8_leaf, norm_leaf, seed_key

WINDOW = "sliding_attention"


def held(config: dict) -> tuple:
    h = config["deployment"]["experts_held"]
    assert h["count"] == config["num_experts"], (h, config["num_experts"])
    return h["first"], h["count"]


def layer_windows(config: dict) -> tuple:
    """The period of kinds, from the source's ``layer_types``."""
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    period = kinds[: config["layer_switch"]]
    assert kinds == period * (len(kinds) // len(period)), kinds
    return tuple(
        config["sliding_window"] if k == WINDOW else None for k in period
    )


def program_config(config: dict):
    """The program's config object for a configuration file. A program
    without the family fails here, before any weight is drawn."""
    from odh_kubeflow_tpu.models.cohere2 import Cohere2MoeConfig

    return Cohere2MoeConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        expert_width=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        layer_norm_eps=config["layer_norm_eps"],
        layer_windows=layer_windows(config),
        num_experts=config["reduced_from"]["num_experts"],
        experts_held=held(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["num_shared_experts"],
        norm_topk_prob=config["norm_topk_prob"],
        logit_scale=float(config["logit_scale"]),
        dtype=jnp.bfloat16,
    )


def layer(key, cfg: dict) -> dict:
    """One layer of the frozen base, in the program's layout."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    q_dim = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_key_value_heads"] * cfg["head_dim"]
    E, Fs = cfg["num_experts"], cfg["num_shared_experts"] * F
    keys = iter(jax.random.split(key, 12))

    def bank(key, shape, fan_in):
        # an expert at a time: the float32 draws of one matrix are the
        # only transients beside the int8 bank
        return jax.lax.map(
            lambda kk: int8_leaf(kk, shape, fan_in), jax.random.split(key, E)
        )

    return {
        "norm": norm_leaf(next(keys), (D,)),
        "wq": int8_leaf(next(keys), (D, q_dim), D),
        "wk": int8_leaf(next(keys), (D, kv_dim), D),
        "wv": int8_leaf(next(keys), (D, kv_dim), D),
        "wo": int8_leaf(next(keys), (q_dim, D), q_dim),
        # the router stays float32: its eight largest of 128 sigmoids
        # are the selection, and deployments do not quantise it
        "router": jax.random.normal(
            next(keys), (D, cfg["reduced_from"]["num_experts"]), jnp.float32
        ) * D**-0.5,
        "moe_gate": bank(next(keys), (D, F), D),
        "moe_up": bank(next(keys), (D, F), D),
        "moe_down": bank(next(keys), (F, D), F),
        "sh_gate": int8_leaf(next(keys), (D, Fs), D),
        "sh_up": int8_leaf(next(keys), (D, Fs), D),
        "sh_down": int8_leaf(next(keys), (Fs, D), F),
    }


def make_params(cfg: dict, seed: int):
    """The frozen base on the default device, in ONE jitted call: int8
    matmul weights with a float32 scale per output channel, bf16
    embedding (the tied head) and norms, float32 router."""
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]

    def build(key):
        ke, kl, kn = jax.random.split(key, 3)
        return {
            # the head's logits have the spread of Mistral's untied one
            "embed": jax.random.normal(ke, (V, D), jnp.bfloat16) * D**-0.5,
            "layers": jax.lax.map(
                lambda kk: layer(kk, cfg), jax.random.split(kl, L)
            ),
            "final_norm": norm_leaf(kn, (D,)),
        }

    return jax.jit(build)(seed_key(seed))


# ---- counts: matmul weights, all int8 (one byte a weight) ------------------


def attention_matmul_weights(cfg: dict) -> int:
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return D * H * hd + 2 * D * Hkv * hd + H * hd * D


def shared_expert_weights(cfg: dict) -> int:
    return cfg["num_shared_experts"] * expert_weights(cfg)


def expert_weights(cfg: dict) -> int:
    """One expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def kv_bytes_per_token_layer(cfg: dict) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2  # k, v in bf16


def kinds(cfg: dict) -> tuple:
    """(window layers, full layers) held here."""
    types = cfg["layer_types"][: cfg["num_hidden_layers"]]
    n_window = sum(t == WINDOW for t in types)
    return n_window, len(types) - n_window


def decode_step_bytes(cfg: dict, experts_hit: float, live_full: float,
                      live_window: float) -> dict:
    """Bytes one decode step must read, by part: attention and shared
    experts' int8 weights and the float32 router of every layer, the
    routed experts HIT (``experts_hit``: distinct (layer, expert) banks
    a step read, from the program's counter), the bf16 tied head, and
    the live keys and values: ``live_full`` positions in each full layer
    and ``live_window`` (each row's ``min(context, window)``) in each
    window layer."""
    L = cfg["num_hidden_layers"]
    n_window, n_full = kinds(cfg)
    router = cfg["hidden_size"] * cfg["reduced_from"]["num_experts"] * 4
    kv = kv_bytes_per_token_layer(cfg)
    return {
        "attention": L * attention_matmul_weights(cfg),
        "shared": L * shared_expert_weights(cfg),
        "router": L * router,
        "routed": experts_hit * expert_weights(cfg),
        "head": cfg["vocab_size"] * cfg["hidden_size"] * 2,
        "kv": kv * (n_full * live_full + n_window * live_window),
    }
