"""The plain reference for IBM's ``granitemoehybrid`` (Granite 4.0-H):
one row's forward pass in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks, no batching, and nothing of the program is imported.
``benchmark/reference/granitemoehybrid.py`` is this file's copy, byte
for byte (a test holds them together), so that the benchmark's yardstick
does not move with the program.

The layer, with every key the SOURCE's (``config.json`` of
ibm-granite/granite-4.0-h-small), T tokens, D = ``hidden_size``, r =
``residual_multiplier``:

- ``x0 = embedding_multiplier * E[tok]``;
- ``h = RMSNorm(x)`` (``rms_norm_eps``), ``x = x + r * mixer(h)``, where
  ``layer_types[l]`` names the mixer:
  - ``"attention"``: ``q = h Wq`` [T, H, hd], ``k = h Wk``, ``v = h Wv``
    [T, Hkv, hd], no bias, NO rotation (``position_embedding_type:
    nope``), ``a = softmax(q k^T * attention_multiplier) v`` causal,
    ``a Wo``;
  - ``"mamba"`` (Mamba-2, ``mamba_n_groups`` 1): ``[z | xBC | dt] = h
    W_in`` (``d_inner | d_inner + 2 d_state | n_heads`` columns, no
    bias); ``xBC_t = silu(sum_j w_j * xBC_{t - (K - 1) + j} + b)``, a
    causal depthwise convolution of ``mamba_d_conv`` = K taps over the
    row (zeros before it); ``[x | B | C] = xBC``; ``dt = softplus(dt +
    dt_bias)`` (no clamp: the config has no ``time_step_limit``), ``A =
    -exp(A_log)``; per head, with ``S`` [d_head, d_state] from zero,
    TOKEN BY TOKEN: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``,
    ``y_t = S_t C_t + D x_t``; ``y = RMSNorm(y * silu(z)) * w`` over
    ``d_inner``; ``y W_out``;
- ``h = RMSNorm(x)``; ``l = h Wr`` (``num_local_experts`` logits), the
  ``num_experts_per_tok`` largest, gates = softmax over THOSE;
  ``routed = sum_e g_e (silu(h G_e) * (h U_e)) D_e``; the shared MLP the
  same at ``shared_intermediate_size``; ``x = x + r * (routed + shared)``;
- after the last layer ``RMSNorm`` and ``logits = (h E^T) /
  logits_scaling`` with the tied embedding.

Readings of the source that are inferences, each stated in the
configuration file too: ``intermediate_size`` is ONE routed expert's
width; ``head_dim = hidden_size / num_attention_heads``; the shared MLP
is one SwiGLU added to the routed sum; in the file only
``num_hidden_layers`` is cut (a pipeline stage holds the first of them).

The weights are the harness's seeded leaves in the program's layout:
what every layer has stacked under ``layers``, the mixers under
``mamba`` and ``attn`` in depth order; int8 leaves ``{"q", "scale"}``
dequantise as ``q * scale``, exact in float32.

``Precision`` computes the same mathematics in a lower precision: what
the controls run, and what the check has to tell from a sound run.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
BANKS = ("moe_gate", "moe_up", "moe_down")


class Precision(NamedTuple):
    act: str = "f32"  # "f32" | "int8" (per token, into frozen matmuls)
    state: str = "f32"  # "f32" | "bf16": the SSM state as it is carried


SOUND = Precision()


def _fake_int8_rows(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def weight(leaf):
    """A stored leaf as float32; int8 leaves dequantise exactly."""
    if isinstance(leaf, dict):
        return leaf["q"].astype(F32) * leaf["scale"].astype(F32)
    return leaf.astype(F32)


def matmul(x, leaf, prec: Precision = SOUND):
    """x @ W for a frozen weight, in the stated precision."""
    if prec.act == "int8":
        x = _fake_int8_rows(x)
    return jnp.matmul(x, weight(leaf))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w.astype(F32)


def attention(q, k, v, scale, block=128):
    """q [T, H, hd], k/v [T, Hkv, hd], causal, scores times ``scale``. A
    block of queries at a time, so that the float32 scores fit."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    block = min(block, T)
    assert T % block == 0, (T, block)
    qg = q.reshape(T // block, block, Hkv, H // Hkv, hd)
    kpos = jnp.arange(T)

    def one(args):
        qb, start = args
        qpos = start + jnp.arange(block)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) * scale
        scores = jnp.where(
            (kpos[None, :] <= qpos[:, None])[None, None], scores, -1e30
        )
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, (qg, jnp.arange(0, T, block)))
    return out.reshape(T, H * hd)


def mamba(h, mw, cfg, prec, stop=None):
    """One Mamba-2 mixer on a row h [T, D], the recurrence token by
    token. Returns ``(out [T, D], S [H, d_head, d_state])``: the state
    after ``stop`` tokens (after all of them where ``stop`` is None)."""
    T = h.shape[0]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    K, di = cfg["mamba_d_conv"], cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    zxbcdt = matmul(h, mw["in_proj"], prec)
    z, xBC, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), F32), xBC])
    conv_w = mw["conv_w"].astype(F32)  # [K, channels]; tap K - 1 is "now"
    xBC = jax.nn.silu(
        sum(padded[j:j + T] * conv_w[j] for j in range(K)) + mw["conv_b"]
    )
    x, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    x = x.reshape(T, H, P)
    dt = jax.nn.softplus(dt + mw["dt_bias"])  # [T, H]
    A = -jnp.exp(mw["A_log"].astype(F32))  # [H]

    last = T - 1 if stop is None else stop - 1

    def step(carry, xs):
        S, kept = carry
        x_t, dt_t, b_t, c_t, t = xs
        S = jnp.exp(dt_t * A)[:, None, None] * S + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        )
        if prec.state == "bf16":
            # not a cast there and back: XLA takes such a pair out
            # (the TPU's compiler allows excess precision)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return (S, jnp.where(t == last, S, kept)), jnp.einsum("hpn,n->hp", S, c_t)

    zero = jnp.zeros((H, P, N), F32)
    (_, kept), y = jax.lax.scan(
        step, (zero, zero), (x, dt, Bm, Cm, jnp.arange(T))
    )
    y = y + mw["D"].astype(F32)[None, :, None] * x
    y = y.reshape(T, di) * jax.nn.silu(z)
    out = matmul(rms_norm(y, mw["norm"], cfg["rms_norm_eps"]), mw["out_proj"], prec)
    return out, kept


def attention_mixer(h, aw, cfg, prec):
    T = h.shape[0]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // H
    q = matmul(h, aw["wq"], prec).reshape(T, H, hd)
    k = matmul(h, aw["wk"], prec).reshape(T, Hkv, hd)
    v = matmul(h, aw["wv"], prec).reshape(T, Hkv, hd)
    return matmul(
        attention(q, k, v, cfg["attention_multiplier"]), aw["wo"], prec
    )


def swiglu(h, gate, up, down, prec):
    return matmul(
        jax.nn.silu(matmul(h, gate, prec)) * matmul(h, up, prec), down, prec
    )


def routing(h, router, cfg):
    """(gates [T, E] over ALL experts, top ids [T, k]): the k largest
    logits, a softmax over those."""
    E, k = router.shape[-1], cfg["num_experts_per_tok"]
    top_l, top_i = jax.lax.top_k(jnp.matmul(h, router.astype(F32)), k)
    gates = jax.nn.softmax(top_l, axis=-1)
    return (jax.nn.one_hot(top_i, E, dtype=F32) * gates[..., None]).sum(1), top_i


def ffn(x, lw, banks, depth, cfg, prec):
    """``r * (routed + shared)(RMSNorm(x))`` and the chosen ids. The
    experts' banks stay stacked ``[L, E, ...]`` and an expert's matrices
    are taken from them one at a time (a layer's three banks sliced out
    whole are 0.65 GB, and ten layers of them do not fit beside the
    weights)."""
    h = rms_norm(x, lw["norm2"], cfg["rms_norm_eps"])
    combine, top_i = routing(h, lw["router"], cfg)
    first = cfg["deployment"]["experts_held"]["first"]
    count = jax.tree_util.tree_leaves(banks["moe_gate"])[0].shape[1]
    held = jax.lax.dynamic_slice_in_dim(combine, first, count, axis=1)

    def one_expert(acc, xs):
        e, g_e = xs
        gate, up, down = (
            jax.tree_util.tree_map(lambda a: a[depth, e], banks[name])
            for name in BANKS
        )
        return acc + g_e[:, None] * swiglu(h, gate, up, down, prec), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (jnp.arange(count), held.T)
    )
    shared = swiglu(h, lw["sh_gate"], lw["sh_up"], lw["sh_down"], prec)
    return cfg["residual_multiplier"] * (routed + shared), top_i


def hidden_states(params, tokens, cfg, prec=SOUND, stop=None):
    """tokens [T] -> (final-norm hidden [T, D], top ids [L, T, k], the
    Mamba-2 layers' states after ``stop`` tokens [L_m, H, d_head,
    d_state]). Consecutive layers of one kind go through one
    ``lax.scan`` (the same layer function, a layer's weights taken from
    the stacks by index): ten layers written out one after another take
    the compiler three times as long."""
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    take = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    banks = {n: params["layers"][n] for n in BANKS}
    small = {n: v for n, v in params["layers"].items() if n not in BANKS}
    kinds = cfg["layer_types"][:L]
    x = cfg["embedding_multiplier"] * params["embed"][tokens].astype(F32)
    seen = {"mamba": 0, "attention": 0}
    chosen, states = [], []
    depth = 0
    while depth < L:
        kind = kinds[depth]
        count = next(
            (j for j in range(depth, L) if kinds[j] != kind), L
        ) - depth

        def layer(x, i, kind=kind, depth0=depth, first=seen[kind]):
            lw = take(small, depth0 + i)
            h = rms_norm(x, lw["norm1"], cfg["rms_norm_eps"])
            if kind == "mamba":
                mixed, S = mamba(h, take(params["mamba"], first + i), cfg, prec, stop)
            else:
                mixed = attention_mixer(h, take(params["attn"], first + i), cfg, prec)
                S = jnp.zeros((0,), F32)
            x = x + cfg["residual_multiplier"] * mixed
            y, top_i = ffn(x, lw, banks, depth0 + i, cfg, prec)
            return x + y, (top_i, S)

        x, (top_i, S) = jax.lax.scan(layer, x, jnp.arange(count))
        chosen.append(top_i)
        if kind == "mamba":
            states.append(S)
        seen[kind] += count
        depth += count
    return (
        rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
        jnp.concatenate(chosen), jnp.concatenate(states),
    )


def logits_and_states(params, tokens, cfg, prec=SOUND, at=None, stop=None):
    """tokens [T] -> (logits [T or len(at), V], top ids [L, T, k], the
    Mamba-2 layers' states after ``stop`` tokens); ``at`` picks
    positions before the head runs."""
    with jax.default_matmul_precision("highest"):
        h, top_i, states = hidden_states(params, tokens, cfg, prec, stop)
        if at is not None:
            h = h[at]
        return (
            jnp.matmul(h, params["embed"].astype(F32).T) / cfg["logits_scaling"],
            top_i, states,
        )


def logits(params, tokens, cfg, prec=SOUND, at=None):
    """tokens [T] -> (logits [T or len(at), V], top ids [L, T, k])."""
    return logits_and_states(params, tokens, cfg, prec, at)[:2]
