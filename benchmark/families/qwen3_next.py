"""The ``qwen3_next`` family (Qwen3-Next-80B-A3B) as ONE CHIP'S SHARE of
a layer group in a pipeline: what the harness needs of it, found by the
``family`` a configuration file names. Three Gated DeltaNet layers to
one gated-attention layer (``full_attention_interval``), every layer
followed by a softmax-over-all mixture of which ``num_experts`` experts
are held here (the router keeps the published width) beside a
sigmoid-gated shared expert, untied head over this chip's rows of the
vocabulary.

It brings its own builder of the seeded frozen base (``make_params``)
and its own counts: the bytes a decode step must move, by part, with a
DeltaNet layer's state counted read AND written, and the operations and
bytes of one call of the prefill scan from its shapes. Everything reads
the configuration FILE."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness.weights import int8_leaf, seed_key


def held(config: dict) -> tuple:
    h = config["deployment"]["experts_held"]
    assert h["count"] == config["num_experts"], (h, config["num_experts"])
    return h["first"], h["count"]


def router_width(config: dict) -> int:
    """The experts the ROUTER chooses among: the published count."""
    return config["reduced_from"]["num_experts"]


def layer_kinds(config: dict) -> tuple:
    """The period of kinds: layer ``i`` is full attention when ``(i + 1)
    % full_attention_interval == 0``."""
    p = config["full_attention_interval"]
    assert config["num_hidden_layers"] % p == 0, (config["num_hidden_layers"], p)
    return ("state",) * (p - 1) + (None,)


def kinds(cfg: dict) -> tuple:
    """(DeltaNet layers, attention layers) held here."""
    n_attn = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return cfg["num_hidden_layers"] - n_attn, n_attn


def gdn_dims(cfg: dict) -> tuple:
    """(key heads, value heads, d_k, d_v, key_dim, value_dim, conv
    channels)."""
    Hk, H = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return Hk, H, dk, dv, Hk * dk, H * dv, 2 * Hk * dk + H * dv


def program_config(config: dict):
    """The program's config object for a configuration file. A program
    without the family fails here, before any weight is drawn."""
    from odh_kubeflow_tpu.models.qwen3_next import Qwen3NextConfig

    Hk, H, dk, dv, *_ = gdn_dims(config)
    assert not config["mlp_only_layers"] and config["decoder_sparse_step"] == 1
    assert config["norm_topk_prob"] and not config["tie_word_embeddings"]
    return Qwen3NextConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        layer_kinds=layer_kinds(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(config["rope_theta"]),
        gdn_key_heads=Hk, gdn_value_heads=H, gdn_key_dim=dk, gdn_value_dim=dv,
        gdn_conv=config["linear_conv_kernel_dim"],
        num_experts=router_width(config),
        experts_held=held(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        rms_norm_eps=config["rms_norm_eps"],
        # the file states it (the tests' tiny file states float32, to
        # hold the controls to limits that the program's own rounding
        # does not reach)
        dtype=jnp.dtype(config["activation_dtype"]),
    )


def _small(key, shape):
    """A zero-centred norm's weight ``w``: small and NOT zero, so that
    ``1 + w`` shows."""
    return 0.1 * jax.random.normal(key, shape, jnp.float32)


def common_layer(key, cfg: dict) -> dict:
    """What every layer has: two norms, the router over ALL the
    published experts, the held experts' banks, the shared expert and
    its gate."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    Fs, E = cfg["shared_expert_intermediate_size"], held(cfg)[1]
    keys = iter(jax.random.split(key, 10))

    def bank(key, shape, fan_in):
        return jax.lax.map(
            lambda kk: int8_leaf(kk, shape, fan_in), jax.random.split(key, E)
        )

    return {
        "norm1": _small(next(keys), (D,)),
        "norm2": _small(next(keys), (D,)),
        # float32: its ten largest of 512 probabilities are the selection
        "router": jax.random.normal(
            next(keys), (D, router_width(cfg)), jnp.float32
        ) * D**-0.5,
        "moe_gate": bank(next(keys), (D, F), D),
        "moe_up": bank(next(keys), (D, F), D),
        "moe_down": bank(next(keys), (F, D), F),
        "sh_gate": int8_leaf(next(keys), (D, Fs), D),
        "sh_up": int8_leaf(next(keys), (D, Fs), D),
        "sh_down": int8_leaf(next(keys), (Fs, D), Fs),
        "sh_scale": jax.random.normal(next(keys), (D,), jnp.float32) * D**-0.5,
    }


def gdn_layer(key, cfg: dict) -> dict:
    """One Gated DeltaNet mixer. The recurrence's own parameters follow
    the reference implementation's initialisation (``A`` uniform in
    1..16, ``dt_bias`` through the inverse softplus of a log-uniform
    1e-3..1e-1): a normal draw gives a state that dies at once or never
    decays, and a check that tests nothing."""
    D, K = cfg["hidden_size"], cfg["linear_conv_kernel_dim"]
    _, H, _, dv, _, value_dim, channels = gdn_dims(cfg)
    keys = iter(jax.random.split(key, 7))
    dt = jnp.exp(jax.random.uniform(
        next(keys), (H,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return {
        "in_qkvz": int8_leaf(next(keys), (D, channels + value_dim), D),
        "in_ba": jax.random.normal(next(keys), (D, 2 * H), jnp.float32) * D**-0.5,
        "conv_w": jax.random.normal(next(keys), (K, channels), jnp.float32) * K**-0.5,
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "A_log": jnp.log(jax.random.uniform(next(keys), (H,), jnp.float32, 1.0, 16.0)),
        "norm": 1 + _small(next(keys), (dv,)),  # a plain weight
        "out_proj": int8_leaf(next(keys), (value_dim, D), value_dim),
    }


def attention_layer(key, cfg: dict) -> dict:
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    q_dim, kv_dim = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    kq, kk, kv, ko, kn, km = jax.random.split(key, 6)
    return {
        "wq": int8_leaf(kq, (D, 2 * q_dim), D),  # per head [query | gate]
        "wk": int8_leaf(kk, (D, kv_dim), D),
        "wv": int8_leaf(kv, (D, kv_dim), D),
        "wo": int8_leaf(ko, (q_dim, D), q_dim),
        "q_norm": _small(kn, (hd,)),
        "k_norm": _small(km, (hd,)),
    }


def make_params(cfg: dict, seed: int):
    """The frozen base on the default device, in ONE jitted call, in the
    program's layout: what every layer has under ``layers``, the mixers
    by kind under ``gdn`` and ``attn`` in depth order. int8 matmul
    weights with a float32 scale per output channel; ``W_ba``, the
    convolution, ``A_log``, ``dt_bias``, norms, router and the shared
    expert's gate float32; the embedding and the untied head bfloat16,
    standard deviation ``hidden_size ** -0.5`` (a row of norm 1, as
    Command A+'s; the head's logits then have the standard deviation of
    the final norm's ``1 + w``)."""
    D, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    n_gdn, n_attn = kinds(cfg)

    def build(key):
        ke, kh, kl, kg, ka, kn = jax.random.split(key, 6)
        stack = lambda fn, k, n: jax.lax.map(  # noqa: E731
            lambda kk: fn(kk, cfg), jax.random.split(k, n)
        )
        return {
            "embed": jax.random.normal(ke, (V, D), jnp.bfloat16) * D**-0.5,
            "lm_head": jax.random.normal(kh, (D, V), jnp.bfloat16) * D**-0.5,
            "layers": stack(common_layer, kl, L),
            "gdn": stack(gdn_layer, kg, n_gdn),
            "attn": stack(attention_layer, ka, n_attn),
            "final_norm": _small(kn, (D,)),
        }

    return jax.jit(build)(seed_key(seed))


# ---- counts: matmul weights are int8 (one byte a weight) -------------------


def gdn_matmul_weights(cfg: dict) -> int:
    """``W_qkvz`` and ``W_out``, a byte a weight."""
    *_, value_dim, channels = gdn_dims(cfg)
    return cfg["hidden_size"] * (channels + value_dim) + value_dim * cfg["hidden_size"]


def gdn_float32_bytes(cfg: dict) -> int:
    """``W_ba`` and the convolution's taps, float32."""
    _, H, *_, channels = gdn_dims(cfg)
    return 4 * (cfg["hidden_size"] * 2 * H + cfg["linear_conv_kernel_dim"] * channels)


def attention_matmul_weights(cfg: dict) -> int:
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return D * 2 * H * hd + 2 * D * Hkv * hd + H * hd * D


def expert_weights(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def kv_bytes_per_token_layer(cfg: dict) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2  # k, v in bf16


def state_bytes_per_slot_layer(cfg: dict) -> int:
    """One slot's state in one DeltaNet layer: the float32 delta-rule
    state and the bf16 tail of the convolution's inputs."""
    _, H, dk, dv, _, _, channels = gdn_dims(cfg)
    return H * dk * dv * 4 + (cfg["linear_conv_kernel_dim"] - 1) * channels * 2


def decode_step_bytes(cfg: dict, experts_hit: float, live_full: float,
                      live_slots: float) -> dict:
    """Bytes one decode step must move, by part: the mixers' and the
    shared expert's int8 weights, the float32 ``W_ba``, convolution and
    router (all its published outputs) of every layer, the routed
    experts HIT (``experts_hit``: distinct (layer, expert) banks a step
    read, from the program's counter), the bf16 head over this chip's
    rows, the live keys and values of the attention layers
    (``live_full`` positions each) and, READ AND WRITTEN, the state of
    the ``live_slots`` decoding slots in every DeltaNet layer."""
    L = cfg["num_hidden_layers"]
    n_gdn, n_attn = kinds(cfg)
    return {
        "gdn": n_gdn * (gdn_matmul_weights(cfg) + gdn_float32_bytes(cfg)),
        "attention": n_attn * attention_matmul_weights(cfg),
        "shared": L * shared_weights(cfg),
        "router": L * cfg["hidden_size"] * router_width(cfg) * 4,
        "routed": experts_hit * expert_weights(cfg),
        "head": cfg["vocab_size"] * cfg["hidden_size"] * 2,
        "kv": kv_bytes_per_token_layer(cfg) * n_attn * live_full,
        "state": 2 * state_bytes_per_slot_layer(cfg) * n_gdn * live_slots,
    }


GDN_CHUNK = 64  # ``ops/pallas_gdn.DEFAULT_CHUNK``: the source states none


def gdn_scan_work(cfg: dict, positions: int) -> dict:
    """Operations and bytes of ONE call of the prefill scan (one
    DeltaNet layer over ``positions`` positions of one row, in chunks of
    64), as the chunked delta rule needs them. Per chunk of C positions:
    per key head ``K K^T`` and ``Q K^T`` (2 C^2 d_k each); per value
    head the unit-triangular solve for ``W`` and ``U`` by substitution
    (C^2 (d_k + d_v): what the kernel spends on its blockwise inverse beyond that
    is its own), the state read by ``W`` and by ``Q`` and its update (2 C
    d_k d_v each) and the in-chunk product on ``V'`` (2 C^2 d_v). Bytes:
    ``q``, ``k``, ``v`` in and ``o`` out in bf16, ``g`` and ``beta`` in
    float32, the state in and out in float32."""
    Hk, H, dk, dv, key_dim, value_dim, _ = gdn_dims(cfg)
    C = min(GDN_CHUNK, positions)
    chunks = -(-positions // C)
    flops = chunks * (
        Hk * 4 * C * C * dk
        + H * (C * C * (dk + dv) + 6 * C * dk * dv + 2 * C * C * dv)
    )
    byts = (
        2 * positions * key_dim * 2 + 2 * positions * value_dim * 2
        + 2 * positions * H * 4 + 2 * H * dk * dv * 4
    )
    return {"flops": flops, "bytes": byts}


# the name the accepted reader (``metrics/hybrid_roofline.py``) asks a
# family for its prefill scan's work by
ssd_scan_work = gdn_scan_work
