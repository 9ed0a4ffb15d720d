"""The ``granitemoehybrid`` family (Granite 4.0-H) as ONE PIPELINE STAGE
of a deployment: what the harness needs of it, found by the ``family`` a
configuration file names. Nine Mamba-2 layers to one NoPE attention
layer in the source's ``layer_types`` order, every layer followed by a
top-k-then-softmax mixture of ``num_local_experts`` experts (all held
here) beside one shared MLP, the source's four multipliers, tied head
over the whole vocabulary.

It brings its own builder of the seeded frozen base (``make_params``)
and its own counts: the bytes a decode step must move, by part, with a
recurrent layer's state counted read AND written, and the operations
and bytes of one call of the prefill scan from its shapes. Everything
reads the configuration FILE."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights import int8_leaf, seed_key

MAMBA = "mamba"


def held(config: dict) -> tuple:
    h = config["deployment"]["experts_held"]
    return h["first"], h["count"]


def layer_kinds(config: dict) -> tuple:
    """The period of kinds, from the source's ``layer_types``: the
    shortest prefix that the held layers repeat."""
    kinds = config["layer_types"][: config["num_hidden_layers"]]
    period = next(
        kinds[:p] for p in range(1, len(kinds) + 1)
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p)
    )
    return tuple("state" if k == MAMBA else None for k in period)


def mamba_dims(cfg: dict) -> tuple:
    """(heads, head width, state size, d_inner, conv channels, in_proj
    columns)."""
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    di = H * P
    assert di == cfg["mamba_expand"] * cfg["hidden_size"], (di, cfg["mamba_expand"])
    assert cfg["mamba_n_groups"] == 1, "one group of B and C"
    return H, P, N, di, di + 2 * N, 2 * di + 2 * N + H


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def program_config(config: dict):
    """The program's config object for a configuration file. A program
    without the family fails here, before any weight is drawn."""
    from odh_kubeflow_tpu.models.granite_hybrid import GraniteHybridConfig

    H, P, N, *_ = mamba_dims(config)
    return GraniteHybridConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        expert_width=config["intermediate_size"],
        shared_width=config["shared_intermediate_size"],
        num_layers=config["num_hidden_layers"],
        layer_kinds=layer_kinds(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=head_dim(config),
        mamba_heads=H, mamba_head_dim=P, mamba_d_state=N,
        mamba_d_conv=config["mamba_d_conv"],
        mamba_chunk=config["mamba_chunk_size"],
        num_experts=config["num_local_experts"],
        experts_held=held(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        rms_norm_eps=config["rms_norm_eps"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        attention_multiplier=float(config["attention_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        # the file states it (the tests' tiny file states float32, to
        # hold the controls to limits that the program's own rounding
        # does not reach)
        dtype=jnp.dtype(config["activation_dtype"]),
    )


def _norm(key, shape):
    return 1 + 0.1 * jax.random.normal(key, shape, jnp.float32)


def common_layer(key, cfg: dict) -> dict:
    """What every layer has: two norms, the router, the held experts'
    banks and the shared MLP."""
    D, F, Fs = cfg["hidden_size"], cfg["intermediate_size"], cfg["shared_intermediate_size"]
    E = held(cfg)[1]
    keys = iter(jax.random.split(key, 9))

    def bank(key, shape, fan_in):
        return jax.lax.map(
            lambda kk: int8_leaf(kk, shape, fan_in), jax.random.split(key, E)
        )

    return {
        "norm1": _norm(next(keys), (D,)),
        "norm2": _norm(next(keys), (D,)),
        # float32: its ten largest of 72 logits are the selection
        "router": jax.random.normal(
            next(keys), (D, cfg["num_local_experts"]), jnp.float32
        ) * D**-0.5,
        "moe_gate": bank(next(keys), (D, F), D),
        "moe_up": bank(next(keys), (D, F), D),
        "moe_down": bank(next(keys), (F, D), F),
        "sh_gate": int8_leaf(next(keys), (D, Fs), D),
        "sh_up": int8_leaf(next(keys), (D, Fs), D),
        "sh_down": int8_leaf(next(keys), (Fs, D), Fs),
    }


def mamba_layer(key, cfg: dict) -> dict:
    """One Mamba-2 mixer. The recurrence's own parameters follow the
    Mamba-2 reference initialisation (``A`` uniform in 1..16, ``dt``
    log-uniform in 1e-3..1e-1 through the inverse softplus, ``D`` 1): a
    normal draw gives a state that dies at once or never decays, and a
    check that tests nothing."""
    D, K = cfg["hidden_size"], cfg["mamba_d_conv"]
    H, _, _, di, channels, columns = mamba_dims(cfg)
    keys = iter(jax.random.split(key, 7))
    dt = jnp.exp(jax.random.uniform(
        next(keys), (H,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return {
        "in_proj": int8_leaf(next(keys), (D, columns), D),
        "conv_w": jax.random.normal(next(keys), (K, channels), jnp.float32) * K**-0.5,
        "conv_b": 0.1 * jax.random.normal(next(keys), (channels,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(next(keys), (H,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((H,), jnp.float32),
        "norm": _norm(next(keys), (di,)),
        "out_proj": int8_leaf(next(keys), (di, D), di),
    }


def attention_layer(key, cfg: dict) -> dict:
    D, hd = cfg["hidden_size"], head_dim(cfg)
    q_dim, kv_dim = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": int8_leaf(kq, (D, q_dim), D),
        "wk": int8_leaf(kk, (D, kv_dim), D),
        "wv": int8_leaf(kv, (D, kv_dim), D),
        "wo": int8_leaf(ko, (q_dim, D), q_dim),
    }


def kinds(cfg: dict) -> tuple:
    """(Mamba-2 layers, attention layers) held here."""
    types = cfg["layer_types"][: cfg["num_hidden_layers"]]
    n_mamba = sum(t == MAMBA for t in types)
    return n_mamba, len(types) - n_mamba


def make_params(cfg: dict, seed: int):
    """The frozen base on the default device, in ONE jitted call, in the
    program's layout: what every layer has under ``layers``, the mixers
    by kind under ``mamba`` and ``attn`` in depth order. int8 matmul
    weights with a float32 scale per output channel; the convolution,
    ``A_log``, ``D``, ``dt_bias``, norms and router float32; the
    embedding (the tied head) bfloat16, of standard deviation
    ``hidden_size ** -0.5 / embedding_multiplier``."""
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    n_mamba, n_attn = kinds(cfg)

    def build(key):
        ke, kl, km, ka, kn = jax.random.split(key, 5)
        stack = lambda fn, k, n: jax.lax.map(  # noqa: E731
            lambda kk: fn(kk, cfg), jax.random.split(k, n)
        )
        return {
            # x0 = embedding_multiplier * E[tok] has Command A+'s scale
            # (a row of norm 1). At D ** -0.5 the tied head's logit of
            # the token just read stands 12 sigma above every other and
            # a stream repeats its last token for ever (PERF.md, PR 31)
            "embed": jax.random.normal(ke, (V, D), jnp.bfloat16)
            * (D**-0.5 / cfg["embedding_multiplier"]),
            "layers": stack(common_layer, kl, L),
            "mamba": stack(mamba_layer, km, n_mamba),
            "attn": stack(attention_layer, ka, n_attn),
            "final_norm": _norm(kn, (D,)),
        }

    return jax.jit(build)(seed_key(seed))


# ---- counts: matmul weights are int8 (one byte a weight) -------------------


def mamba_matmul_weights(cfg: dict) -> int:
    _, _, _, di, _, columns = mamba_dims(cfg)
    return cfg["hidden_size"] * columns + di * cfg["hidden_size"]


def attention_matmul_weights(cfg: dict) -> int:
    D, hd = cfg["hidden_size"], head_dim(cfg)
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return D * H * hd + 2 * D * Hkv * hd + H * hd * D


def expert_weights(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def shared_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def kv_bytes_per_token_layer(cfg: dict) -> int:
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * 2  # k, v in bf16


def state_bytes_per_slot_layer(cfg: dict) -> int:
    """One slot's state in one Mamba-2 layer: the float32 SSM state and
    the bf16 tail of the convolution's inputs."""
    H, P, N, _, channels, _ = mamba_dims(cfg)
    return H * P * N * 4 + (cfg["mamba_d_conv"] - 1) * channels * 2


def decode_step_bytes(cfg: dict, experts_hit: float, live_full: float,
                      live_slots: float) -> dict:
    """Bytes one decode step must move, by part: the mixers' and the
    shared MLP's int8 weights and the float32 router of every layer, the
    routed experts HIT (``experts_hit``: distinct (layer, expert) banks
    a step read, from the program's counter), the bf16 tied head, the
    live keys and values of the attention layers (``live_full``
    positions each) and, READ AND WRITTEN, the state of the
    ``live_slots`` decoding slots in every Mamba-2 layer."""
    L = cfg["num_hidden_layers"]
    n_mamba, n_attn = kinds(cfg)
    return {
        "mamba": n_mamba * mamba_matmul_weights(cfg),
        "attention": n_attn * attention_matmul_weights(cfg),
        "shared": L * shared_weights(cfg),
        "router": L * cfg["hidden_size"] * cfg["num_local_experts"] * 4,
        "routed": experts_hit * expert_weights(cfg),
        "head": cfg["vocab_size"] * cfg["hidden_size"] * 2,
        "kv": kv_bytes_per_token_layer(cfg) * n_attn * live_full,
        "state": 2 * state_bytes_per_slot_layer(cfg) * n_mamba * live_slots,
    }


def ssd_scan_work(cfg: dict, positions: int) -> dict:
    """Operations and bytes of ONE call of the prefill scan (one Mamba-2
    layer over ``positions`` positions of one row, in chunks of
    ``mamba_chunk_size``), as the algorithm needs them. Per chunk of Q
    positions: ``C B^T`` once (one group: 2 Q^2 N) and per head the
    masked product on ``x`` (2 Q^2 P), the read of the carried state
    (2 Q N P) and its update (2 Q N P). Bytes: ``x`` in and ``y`` out in
    bf16, ``B`` and ``C`` in bf16, ``dt`` in float32, the state in and
    out in float32."""
    H, P, N, di, _, _ = mamba_dims(cfg)
    Q = min(cfg["mamba_chunk_size"], positions)
    chunks = -(-positions // Q)
    flops = chunks * (2 * Q * Q * N + H * (2 * Q * Q * P + 4 * Q * N * P))
    byts = (
        2 * positions * di * 2 + 2 * positions * N * 2 + positions * H * 4
        + 2 * H * P * N * 4
    )
    return {"flops": flops, "bytes": byts}
