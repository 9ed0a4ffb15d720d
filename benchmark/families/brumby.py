"""The ``brumby`` family (Brumby-14B-Base) as ONE PIPELINE STAGE: what
the harness needs of it, found by the ``family`` a configuration file
names. A dense stack of Qwen3-14B's shape in which every layer's
attention is gated power retention of degree 2: a layer keeps NO keys
and values but, a slot, a float32 state ``[Hkv, R, d, d]`` and its
normaliser ``[Hkv, R, d]`` (``R = d / 2 + 1`` rows of the quadratic
feature map as the program lays it: 65 x 128 = 8320 for the 8256
distinct pairs of a head of 128), whatever the stream's length.

It brings its own builder of the seeded frozen base (``make_params``)
and its own counts: the bytes a decode step must move, by part, with the
state counted read AND written at the size the program allocates, and
the operations and bytes of one call of the prefill scan from its
shapes. Everything reads the configuration FILE."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness.weights import int8_leaf, seed_key

# the gates a seeded layer draws: half-lives of 35 to 1400 tokens
GATE_RANGE = (0.98, 0.9995)


def heads(cfg: dict) -> tuple:
    """(query heads, key/value heads = states a layer, head size)."""
    return cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]


def phi_rows(cfg: dict) -> int:
    """Rows of the feature map as the program lays it (``ops/
    pallas_retention.py``: the cyclic diagonals 0..d/2, each ``d``
    wide)."""
    return cfg["head_dim"] // 2 + 1


def to_symmetric_half(state) -> np.ndarray:
    """A state as the program lays it, ``[..., R, d_v, d]`` (row ``r``,
    lane ``i``: ``c_r x_i x_{(i + r) mod d}``), as what a reference that
    never heard of diagonals computes: ``[..., d (d + 1) / 2, d_v]``,
    ``sum_j decay_j * x_a x_b sqrt(2 - [a = b]) * v_j`` for the pairs
    ``a <= b`` in ``numpy.triu_indices``' order (the last diagonal holds
    each of its pairs twice: one copy is read)."""
    state = np.asarray(state)
    d = state.shape[-1]
    a, b = np.triu_indices(d)
    r = b - a
    # the pair lies on diagonal min(r, d - r), at the end it starts from
    far = r > d // 2
    row, lane = np.where(far, d - r, r), np.where(far, b, a)
    scale = np.where(2 * r == d, math.sqrt(2.0), 1.0).astype(np.float32)
    return np.swapaxes(state, -1, -2)[..., row, lane, :] * scale[:, None]


def program_config(config: dict):
    """The program's config object for a configuration file. A program
    without the family fails here, before any weight is drawn."""
    from odh_kubeflow_tpu.models.brumby import BrumbyConfig

    assert config["retention_degree"] == 2, config["retention_degree"]
    assert not config["tie_word_embeddings"] and not config["attention_bias"]
    H, Hkv, d = heads(config)
    return BrumbyConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=H, num_kv_heads=Hkv, head_dim=d,
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        retention_chunk=config["retention_chunk"],
        retention_eps=config["retention_eps"],
        # the file states it (the tests' tiny file states float32, to
        # hold the controls to limits that the program's own rounding
        # does not reach)
        dtype=jnp.dtype(config["activation_dtype"]),
    )


def _off_one(key, shape):
    """A plain norm's weight, drawn off 1 so that it is tested."""
    return 1 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def layer(key, cfg: dict) -> dict:
    """One layer of the frozen base. The gate's bias is drawn so that
    ``g = sigmoid(b_g)`` lies in ``GATE_RANGE``: a normal draw puts half
    the gates under 0.5, a stream's state then holds its last two
    tokens and a check of the state tests nothing."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, d = heads(cfg)
    keys = iter(jax.random.split(key, 13))
    g = jax.random.uniform(next(keys), (Hkv,), jnp.float32, *GATE_RANGE)
    return {
        "attn_norm": _off_one(next(keys), (D,)),
        "wq": int8_leaf(next(keys), (D, H * d), D),
        "wk": int8_leaf(next(keys), (D, Hkv * d), D),
        "wv": int8_leaf(next(keys), (D, Hkv * d), D),
        "wo": int8_leaf(next(keys), (H * d, D), H * d),
        "q_norm": _off_one(next(keys), (d,)),
        "k_norm": _off_one(next(keys), (d,)),
        "gate_w": jax.random.normal(next(keys), (D, Hkv), jnp.float32) * D**-0.5,
        "gate_b": jnp.log(g) - jnp.log1p(-g),  # sigmoid^-1
        "mlp_norm": _off_one(next(keys), (D,)),
        "w_gate": int8_leaf(next(keys), (D, F), D),
        "w_up": int8_leaf(next(keys), (D, F), D),
        "w_down": int8_leaf(next(keys), (F, D), F),
    }


def make_params(cfg: dict, seed: int):
    """The frozen base on the default device, in ONE jitted call, in the
    program's layout (everything stacked under ``layers``). int8 matmul
    weights with a float32 scale per output channel; ``W_g``, ``b_g``
    and norms float32; the embedding and the untied head bfloat16,
    standard deviation ``hidden_size ** -0.5`` (a row of norm 1, as the
    other staged configurations')."""
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]

    def build(key):
        ke, kh, kl, kn = jax.random.split(key, 4)
        return {
            "embed": jax.random.normal(ke, (V, D), jnp.bfloat16) * D**-0.5,
            "lm_head": jax.random.normal(kh, (D, V), jnp.bfloat16) * D**-0.5,
            # a layer at a time, so that the float32 draws of one layer
            # are the only transients beside the int8 tree
            "layers": jax.lax.map(lambda kk: layer(kk, cfg), jax.random.split(kl, L)),
            "final_norm": _off_one(kn, (D,)),
        }

    return jax.jit(build)(seed_key(seed))


# ---- counts: matmul weights are int8 (one byte a weight) -------------------


def matmul_weights_per_layer(cfg: dict) -> int:
    """``W_q``, ``W_k``, ``W_v``, ``W_o`` and the SwiGLU's three, a byte
    a weight."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, Hkv, d = heads(cfg)
    return D * H * d + 2 * D * Hkv * d + H * d * D + 3 * D * F


def state_bytes_per_slot_layer(cfg: dict) -> int:
    """One slot's state in one layer AS THE PROGRAM ALLOCATES IT: the
    float32 state ``[Hkv, R, d, d]`` and normaliser ``[Hkv, R, d]``."""
    _, Hkv, d = heads(cfg)
    return Hkv * phi_rows(cfg) * d * (d + 1) * 4


def decode_step_bytes(cfg: dict, live_slots: float) -> dict:
    """Bytes one decode step must move, by part: READ AND WRITTEN, the
    state of the ``live_slots`` decoding slots in every layer; every
    layer's int8 matmul weights and float32 gate projection; the bf16
    head."""
    L, D = cfg["num_hidden_layers"], cfg["hidden_size"]
    return {
        "state": 2 * state_bytes_per_slot_layer(cfg) * L * live_slots,
        "weights": L * (
            matmul_weights_per_layer(cfg) + 4 * D * cfg["num_key_value_heads"]
        ),
        "head": cfg["vocab_size"] * D * 2,
    }


def retention_scan_work(cfg: dict, positions: int) -> dict:
    """Operations and bytes of ONE call of the prefill scan (one layer
    over ``positions`` positions of one row), as chunked power retention
    of degree 2 needs them, with ``P = d (d + 1) / 2`` the DISTINCT
    products of a head (what the kernel spends on the doubled last
    diagonal is its own). A position: every query head reads a state and
    its normaliser (``2 P (d + 1)``), every key/value head adds to them
    (``2 P (d + 1)``), and in its chunk of C every query head takes ``Q
    K^T`` and the weights' product with ``V`` (``4 C d``). Bytes: ``q``,
    ``k``, ``v`` in and ``y`` out in bf16, the gates' logs in float32,
    state and normaliser in and out once a part, as allocated."""
    H, Hkv, d = heads(cfg)
    P = d * (d + 1) // 2
    C = min(cfg["retention_chunk"], positions)
    flops = positions * (
        H * 2 * P * (d + 1) + Hkv * 2 * P * (d + 1) + H * 4 * C * d
    )
    byts = (
        2 * positions * H * d * 2 + 2 * positions * Hkv * d * 2
        + positions * Hkv * 4 + 2 * state_bytes_per_slot_layer(cfg)
    )
    return {"flops": flops, "bytes": byts}
