"""The plain reference: the forward pass of the two architectures the
benchmark runs, in straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``.

Dense (Mistral-7B): pre-norm RMSNorm, grouped-query attention with
rotary embeddings (the half-split rotation of the published
implementation), SwiGLU, no biases, untied head. Mixture of experts
(Mixtral-8x7B): the same backbone with eight SwiGLU experts, softmax
over all experts, the two largest renormalised to sum to one, no shared
expert.

It imports nothing of the program. It reads the weights the HARNESS
made from the seed (``harness/weights.py``), in the layout the program
is handed: per-layer leaves stacked on a leading axis, a matmul weight
as ``{"q": int8, "scale": float32}`` and dequantised here as
``q * scale``, which is exact in float32.

Departures from the published description, each because the program
under test defines the quantity so and the comparison needs one
definition:
- the auxiliary load-balancing loss is the Switch form (share of tokens
  whose FIRST choice is the expert, times the mean router probability,
  summed over layers), not the top-k form of the source implementation;
- a LoRA adapter's ``scale`` is a leaf of the trained tree.

``Precision`` computes the same mathematics in a lower precision. It is
what the controls run: the check has to tell them from a sound run.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Precision:
    act: str = "f32"  # "f32" | "bf16" | "int8" (per-token, into frozen matmuls)
    weights: str = "int8"  # "int8" (as stored) | "int4" (group-wise, 128)


SOUND = Precision()


def static_cfg(cfg: dict) -> tuple:
    """The configuration's plain values as a hashable, for a jitted
    function's static argument."""
    return tuple(
        (k, v) for k, v in sorted(cfg.items())
        if isinstance(v, (int, float, str, bool))
    )


def _fake_int8_rows(x):
    """Per-row symmetric int8, straight through in the backward pass (a
    rounding has no gradient of its own)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def _fake_int4_groups(w, group=128):
    *lead, K, N = w.shape
    g = group if K % group == 0 else K
    wg = w.reshape(*lead, K // g, g, N)
    s = jnp.maximum(jnp.max(jnp.abs(wg), axis=-2, keepdims=True), 1e-12) / 7.0
    return (jnp.clip(jnp.round(wg / s), -7, 7) * s).reshape(w.shape)


def weight(leaf, prec: Precision = SOUND):
    """A stored leaf as float32; int8 leaves dequantise exactly."""
    if isinstance(leaf, dict):
        w = leaf["q"].astype(F32) * leaf["scale"].astype(F32)
        if prec.weights == "int4":
            w = _fake_int4_groups(w)
        return w
    return leaf.astype(F32)


def matmul(x, leaf, prec: Precision = SOUND):
    """x @ W for a frozen weight, in the stated precision."""
    w = weight(leaf, prec)
    if prec.act == "int8":
        x = _fake_int8_rows(x)
    if prec.act == "bf16":
        return jnp.matmul(
            x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        ).astype(F32)
    return jnp.matmul(x, w)


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope(x, positions, theta):
    """x [S, H, hd]; rotate pairs (i, i + hd/2) by position * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, segment_ids, block=1024):
    """Causal attention of one row, q [S, H, hd], k/v [S, Hkv, hd];
    a query sees the keys at or before it that carry its own segment id.
    Computed a block of queries at a time so that the scores fit."""
    S, H, hd = q.shape
    Hkv = k.shape[1]
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    block = math.gcd(S, block)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        qpos = start + jnp.arange(block)
        qseg = jax.lax.dynamic_slice_in_dim(segment_ids, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * hd**-0.5
        mask = (kpos[None, :] <= qpos[:, None]) & (
            segment_ids[None, :] == qseg[:, None]
        )
        scores = jnp.where(mask[None], scores, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, jnp.arange(0, S, block))
    return out.reshape(S, H, hd)


def _lora(name, x, y, lora_layer):
    if lora_layer is None or name not in lora_layer:
        return y
    ad = lora_layer[name]
    return y + ((x @ ad["a"].astype(F32)) @ ad["b"].astype(F32)) * ad["scale"]


def _attention_block(x, lw, lora_layer, cfg, segment_ids, positions, prec):
    H, Hkv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    S = x.shape[0]
    h = rms_norm(x, lw["attn_norm"], cfg["rms_norm_eps"])
    q = _lora("wq", h, matmul(h, lw["wq"], prec), lora_layer).reshape(S, H, hd)
    k = _lora("wk", h, matmul(h, lw["wk"], prec), lora_layer).reshape(S, Hkv, hd)
    v = _lora("wv", h, matmul(h, lw["wv"], prec), lora_layer).reshape(S, Hkv, hd)
    q = rope(q, positions, cfg["rope_theta"])
    k = rope(k, positions, cfg["rope_theta"])
    a = attention(q, k, v, segment_ids).reshape(S, H * hd)
    return x + _lora("wo", a, matmul(a, lw["wo"], prec), lora_layer)


def _swiglu(h, gate, up, down, prec):
    return matmul(
        jax.nn.silu(matmul(h, gate, prec)) * matmul(h, up, prec), down, prec
    )


def _dense_mlp(h, lw, lora_layer, prec):
    g = _lora("w_gate", h, matmul(h, lw["w_gate"], prec), lora_layer)
    u = _lora("w_up", h, matmul(h, lw["w_up"], prec), lora_layer)
    a = jax.nn.silu(g) * u
    return _lora("w_down", a, matmul(a, lw["w_down"], prec), lora_layer)


def moe_mlp(h, lw, cfg, token_mask, prec):
    """h [T, D] -> (out [T, D], aux). Softmax over all experts, the
    top-k renormalised; every expert is applied to every token and
    weighted by its (mostly zero) routing weight, one expert at a time."""
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(matmul(h, lw["router"], prec), axis=-1)  # [T, E]
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    m = token_mask.astype(F32)[:, None]
    route = (jax.nn.one_hot(top_i, E, dtype=F32) * top_p[..., None]).sum(1) * m

    @jax.checkpoint
    def one_expert(acc, xs):
        gate, up, down, w_e = xs
        return acc + w_e[:, None] * _swiglu(h, gate, up, down, prec), None

    out, _ = jax.lax.scan(
        one_expert,
        jnp.zeros_like(h),
        (lw["moe_gate"], lw["moe_up"], lw["moe_down"], route.T),
    )
    n = jnp.maximum(m.sum(), 1.0)
    f = (jax.nn.one_hot(top_i[:, 0], E, dtype=F32) * m).sum(0) / n
    p = (probs * m).sum(0) / n
    return out, E * jnp.sum(f * p) * cfg["router_aux_loss_coef"]


def layer(x, lw, lora_layer, cfg, segment_ids, prec):
    """One decoder layer on a batch x [B, S, D] -> (x, aux)."""
    B, S, D = x.shape
    positions = jnp.arange(S)
    x = jax.lax.map(
        lambda xs: _attention_block(
            xs[0], lw, lora_layer, cfg, xs[1], positions, prec
        ),
        (x, segment_ids),
    )
    h = rms_norm(x, lw["mlp_norm"], cfg["rms_norm_eps"]).reshape(B * S, D)
    if cfg.get("family") == "moe":
        out, aux = moe_mlp(h, lw, cfg, segment_ids.reshape(B * S) > 0, prec)
    else:
        out, aux = _dense_mlp(h, lw, lora_layer, prec), jnp.zeros((), F32)
    return x + out.reshape(B, S, D), aux


def hidden_states(params, lora, tokens, segment_ids, cfg, prec=SOUND):
    """tokens, segment_ids [B, S] -> (final-norm hidden [B, S, D], aux)."""
    x = params["embed"][tokens].astype(F32)
    lora_layers = None if lora is None else lora["layers"]

    @jax.checkpoint
    def body(carry, xs):
        x, aux = carry
        lw, ll = xs
        x, a = layer(x, lw, ll, cfg, segment_ids, prec)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(
        body, (x, jnp.zeros((), F32)), (params["layers"], lora_layers)
    )
    return rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]), aux


def logits(params, lora, tokens, segment_ids, cfg, prec=SOUND):
    with jax.default_matmul_precision("highest"):
        h, _ = hidden_states(params, lora, tokens, segment_ids, cfg, prec)
        return matmul(h, params["lm_head"], prec)


def nll_sum(params, lora, batch, cfg, prec=SOUND, block=2048):
    """(sum over unmasked positions of -log p(target), aux) for a
    packed batch; the head and the softmax run a block of positions at
    a time."""
    with jax.default_matmul_precision("highest"):
        h, aux = hidden_states(
            params, lora, batch["tokens"], batch["segment_ids"], cfg, prec
        )
        T = h.shape[0] * h.shape[1]
        block = min(block, T)
        h = h.reshape(T // block, block, -1)
        tg = batch["targets"].reshape(T // block, block)
        mk = batch["loss_mask"].astype(F32).reshape(T // block, block)

        @jax.checkpoint
        def one(xs):
            hb, tb, mb = xs
            lg = matmul(hb, params["lm_head"], prec)
            nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
                lg, tb[:, None], -1
            )[:, 0]
            return jnp.sum(nll * mb)

        return jnp.sum(jax.lax.map(one, (h, tg, mk))), aux
