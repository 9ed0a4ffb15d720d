"""The plain reference for Manifest AI's ``brumby`` (Brumby-14B-Base):
one row's forward pass in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``, power retention in its
ATTENTION form. No feature map, no state, no chunks, no kernels, no
cache, no batching, and nothing of the program is imported: that the
program's state form gives the same numbers is the mechanism, and this
file is what it is held to. ``benchmark/reference/brumby.py`` is this
file's copy, byte for byte (a test holds them together), so that the
benchmark's yardstick does not move with the program.

The layer, T tokens, D = ``hidden_size``, ``N(x) = x * rsqrt(mean(x^2) +
rms_norm_eps) * w`` (a PLAIN weight), ``H = num_attention_heads`` query
heads on ``Hkv = num_key_value_heads`` key/value heads of ``d =
head_dim`` (query head ``a`` reads key/value head ``a // (H / Hkv)``):

- ``x0 = E[tok]``; ``h = N(x)``; ``q = h W_q``, ``k = h W_k``, ``v = h
  W_v``, no bias; per head ``q = N(q)``, ``k = N(k)`` over ``d``; RoPE on
  both (``rope_theta``, all ``d`` dims, half-split pairs ``(i, i +
  d/2)``);
- the gate, one a key/value head: ``log g_t = log_sigmoid(h_t W_g +
  b_g)``, ``G_t = sum_{l <= t} log g_l``;
- power retention of degree 2: for ``j <= t``, ``w_tj = exp(G_t - G_j) *
  (q_t . k_j / sqrt(d))^2``, ``y_t = sum_j w_tj v_j / (sum_j w_tj +
  retention_eps)``;
- ``x = x + concat(y) W_o``; ``x = x + (silu(h G) * (h U)) D`` with ``h =
  N(x)``; after the last layer ``N`` and ``logits = h W_head`` (untied).

``config.json`` has the Qwen3 keys and none for the retention layer;
what the form above rests on is listed under ``assumed`` in the
configuration's file. Left out, as in the program: the ``retention``
package's switch-over (it attends from keys and values until a stream
passes a set length and only then builds the state: the same
mathematics).

``state_at`` is the state that form never builds: ``S_t = sum_{j <= t}
exp(G_t - G_j) phi(k_j) v_j^T`` by a direct sum, with ``phi(x) = (x_a x_b
sqrt(2 - [a = b]))`` over the pairs ``a <= b`` in
``numpy.triu_indices``' order (``phi(a) . phi(b) == (a . b)^2``): what a
program's state is held against.

The weights are the harness's seeded leaves in the program's layout,
stacked over layers under ``layers``; int8 leaves ``{"q", "scale"}``
dequantise as ``q * scale``, exact in float32.

``Precision`` computes the same mathematics in a lower precision: what
the controls run, and what the check has to tell from a sound run.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


class Precision(NamedTuple):
    act: str = "f32"  # "f32" | "int8" (per token, into frozen matmuls)
    # "f32" | "bf16": ``state_at``'s state as it is carried; bf16 takes
    # the recurrence token by token, rounded where it is written
    state: str = "f32"


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


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (
        w.astype(F32)
    )


def rotate(x, theta):
    """x [T, heads, d] rotated by position, half-split pairs."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(d // 2, dtype=F32) / (d // 2))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mixer_inputs(h, lw, cfg, prec):
    """``(q [T, H, d], k, v [T, Hkv, d], G [T, Hkv])`` of one layer on a
    row h [T, D]: norms and rotation done, the gates' logs summed."""
    T = h.shape[0]
    H, Hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = norm(matmul(h, lw["wq"], prec).reshape(T, H, d), lw["q_norm"], eps)
    k = norm(matmul(h, lw["wk"], prec).reshape(T, Hkv, d), lw["k_norm"], eps)
    v = matmul(h, lw["wv"], prec).reshape(T, Hkv, d)
    log_g = jax.nn.log_sigmoid(
        jnp.matmul(h, lw["gate_w"].astype(F32)) + lw["gate_b"].astype(F32)
    )
    return (
        rotate(q, cfg["rope_theta"]), rotate(k, cfg["rope_theta"]), v,
        jnp.cumsum(log_g, axis=0),
    )


def power_retention(q, k, v, G, eps, block=128):
    """The attention form: q [T, H, d], k/v [T, Hkv, d], G [T, Hkv]. A
    block of queries at a time, so that the float32 weights fit."""
    T, H, d = q.shape
    Hkv = k.shape[1]
    block = min(block, T)
    assert T % block == 0, (T, block)
    qg = q.reshape(T // block, block, Hkv, H // Hkv, d)
    Gb = G.reshape(T // block, block, Hkv)
    kpos = jnp.arange(T)

    def one(args):
        qb, Gq, start = args
        qpos = start + jnp.arange(block)
        power = jnp.square(jnp.einsum("qhgd,khd->hgqk", qb, k) * d**-0.5)
        decay = Gq.T[:, :, None] - G.T[:, None, :]  # [Hkv, block, T]
        decay = jnp.where(kpos[None, :] <= qpos[:, None], decay, -jnp.inf)
        w = power * jnp.exp(decay)[:, None]
        y = jnp.einsum("hgqk,khd->qhgd", w, v)
        return y / (jnp.moveaxis(w.sum(-1), 2, 0)[..., None] + eps)

    out = jax.lax.map(one, (qg, Gb, jnp.arange(0, T, block)))
    return out.reshape(T, H, d)


def swiglu(h, lw, prec):
    return matmul(
        jax.nn.silu(matmul(h, lw["w_gate"], prec)) * matmul(h, lw["w_up"], prec),
        lw["w_down"], prec,
    )


def layer(x, lw, cfg, prec):
    T = x.shape[0]
    h = norm(x, lw["attn_norm"], cfg["rms_norm_eps"])
    q, k, v, G = mixer_inputs(h, lw, cfg, prec)
    y = power_retention(q, k, v, G, cfg["retention_eps"])
    x = x + matmul(y.reshape(T, -1), lw["wo"], prec)
    return x + swiglu(norm(x, lw["mlp_norm"], cfg["rms_norm_eps"]), lw, prec)


def hidden_states(params, tokens, cfg, prec=SOUND, layers=None):
    """tokens [T] -> the residual stream [T, D] after ``layers`` layers
    (all of them where None), before the final norm."""
    stack = params["layers"]
    if layers is not None:
        stack = jax.tree_util.tree_map(lambda a: a[:layers], stack)
    x = params["embed"][tokens].astype(F32)
    if layers == 0:
        return x
    x, _ = jax.lax.scan(lambda x, lw: (layer(x, lw, cfg, prec), None), x, stack)
    return x


def logits(params, tokens, cfg, prec=SOUND, at=None):
    """tokens [T] -> logits [T or len(at), V]; ``at`` picks positions
    before the head runs."""
    with jax.default_matmul_precision("highest"):
        h = norm(
            hidden_states(params, tokens, cfg, prec), params["final_norm"],
            cfg["rms_norm_eps"],
        )
        if at is not None:
            h = h[at]
        return jnp.matmul(h, params["lm_head"].astype(F32))


def phi(x):
    """``[..., d] -> [..., d (d + 1) / 2]``: the distinct products ``x_a
    x_b``, ``a <= b``, the mixed ones times ``sqrt 2``."""
    a, b = np.triu_indices(x.shape[-1])
    return x[..., a] * x[..., b] * np.sqrt(2.0 - (a == b)).astype(np.float32)


def state_at(params, tokens, cfg, layer=0, stop=None, prec=SOUND, block=1024):
    """The state ``[Hkv, d (d + 1) / 2, d]`` of layer ``layer`` after
    ``stop`` of ``tokens`` [T] (after all of them where None): the
    layers below it whole, then ``sum_{j < stop} exp(G_last - G_j)
    phi(k_j) v_j^T`` by a direct sum, ``block`` positions at a time."""
    with jax.default_matmul_precision("highest"):
        T = tokens.shape[0]
        stop = T if stop is None else stop
        x = hidden_states(params, tokens, cfg, prec, layers=layer)
        lw = jax.tree_util.tree_map(lambda a: a[layer], params["layers"])
        h = norm(x, lw["attn_norm"], cfg["rms_norm_eps"])
        _, k, v, G = mixer_inputs(h, lw, cfg, prec)
        live = jnp.arange(T) < stop
        if prec.state == "bf16":
            return _state_carried_in_bf16(k, v, G, live)
        # what position j still weighs at the last one
        w = jnp.where(live[:, None], jnp.exp(G[stop - 1] - G), 0.0)  # [T, Hkv]
        block = min(block, T)
        assert T % block == 0, (T, block)
        split = lambda a: a.reshape((T // block, block) + a.shape[1:])  # noqa: E731

        def one(S, xs):
            kb, vb, wb = xs
            return S + jnp.einsum("jhp,jhd->hpd", phi(kb) * wb[..., None], vb), None

        Hkv, d = k.shape[1:]
        S, _ = jax.lax.scan(
            one, jnp.zeros((Hkv, d * (d + 1) // 2, d), F32),
            (split(k), split(v), split(w)),
        )
        return S


def _state_carried_in_bf16(k, v, G, live):
    """The same state by its recurrence, rounded to bfloat16 at every
    token (the lower precision a check of the state must fail)."""
    log_g = jnp.diff(G, axis=0, prepend=jnp.zeros_like(G[:1]))

    def step(S, xs):
        k_t, v_t, lg_t, on = xs
        new = jnp.exp(lg_t)[:, None, None] * S + phi(k_t)[:, :, None] * v_t[:, None, :]
        # not a cast there and back: XLA takes such a pair out
        new = jax.lax.reduce_precision(new, exponent_bits=8, mantissa_bits=7)
        return jnp.where(on, new, S), None

    Hkv, d = k.shape[1:]
    S, _ = jax.lax.scan(
        step, jnp.zeros((Hkv, d * (d + 1) // 2, d), F32), (k, v, log_g, live)
    )
    return S
