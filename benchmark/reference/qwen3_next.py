"""The plain reference for Qwen's ``qwen3_next`` (Qwen3-Next-80B-A3B):
one row's forward pass in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
chunks, no batching, and nothing of the program is imported.
``benchmark/reference/qwen3_next.py`` is this file's copy, byte for byte
(a test holds them together), so that the benchmark's yardstick does not
move with the program.

The layer, with every key the SOURCE's (``config.json`` of
Qwen/Qwen3-Next-80B-A3B-Instruct; HF ``modeling_qwen3_next.py``), T
tokens, D = ``hidden_size``, ``N(x) = x * rsqrt(mean(x^2) +
rms_norm_eps) * (1 + w)`` the family's ZERO-CENTRED RMSNorm:

- ``x0 = E[tok]``; layer ``i`` is full attention when ``(i + 1) %
  full_attention_interval == 0``, else linear attention; ``x = x +
  mixer(N(x))``, then ``x = x + moe(N(x))``;
- linear attention (Gated DeltaNet; ``Hk = linear_num_key_heads``, ``H =
  linear_num_value_heads``, ``dk``, ``dv`` the two head dims): ``[q | k
  | v | z] = h W_qkvz`` (``Hk dk | Hk dk | H dv | H dv`` columns), ``[b
  | a] = h W_ba`` (``H | H``); ``[q | k | v]_t = silu(sum_j w_j [q | k |
  v]_{t - (K - 1) + j})``, a causal depthwise convolution of
  ``linear_conv_kernel_dim`` = K taps (zeros before the row, no bias);
  per value head ``j`` with key head ``j // (H / Hk)``: ``q = l2norm(q)
  / sqrt(dk)``, ``k = l2norm(k)`` (``x * rsqrt(sum(x^2) + 1e-6)``),
  ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``;
  with ``S`` [dk, dv] from zero, TOKEN BY TOKEN: ``S' = exp(g_t)
  S_{t-1}``, ``u = S'^T k_t``, ``S_t = S' + k_t (outer) (beta_t (v_t -
  u))``, ``o_t = S_t^T q_t``; ``y = RMSNorm(o) * w * silu(z)`` per head
  over ``dv`` (a plain weight; the norm first, then the gate); ``y
  W_out``;
- full attention: per head ``[query | gate] = h W_q`` (``head_dim |
  head_dim``), ``k = h W_k``, ``v = h W_v``, no bias; ``query =
  N(query)``, ``k = N(k)`` per head over ``head_dim``; the first
  ``partial_rotary_factor * head_dim`` dims of query and k rotated
  (half-split pairs, ``rope_theta``), the rest pass; causal softmax of
  ``query k^T / sqrt(head_dim)``; ``(attn * sigmoid(gate)) W_o``;
- ``h = N(x)``; ``p = softmax(h W_r)`` over all ``num_experts`` of the
  ROUTER (its width is the published one, whatever is held here), the
  ``num_experts_per_tok`` largest, divided by their sum
  (``norm_topk_prob``); ``routed = sum_e p_e (silu(h G_e) * (h U_e))
  D_e`` over the chosen experts HELD here (``deployment.experts_held``:
  what the others would add is their chips' and is left out); ``shared =
  sigmoid(h w_sg) * (silu(h G_s) * (h U_s)) D_s``;
- after the last layer ``N`` and ``logits = h W_head`` (untied, this
  chip's rows of the vocabulary).

Left out, as in the program: the published multi-token-prediction
module (no key of ``config.json`` describes it).

The weights are the harness's seeded leaves in the program's layout:
what every layer has stacked under ``layers``, the mixers under ``gdn``
and ``attn`` in depth order; int8 leaves ``{"q", "scale"}`` dequantise
as ``q * scale``, exact in float32.

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
    state: str = "f32"  # "f32" | "bf16": the delta-rule state as it is carried


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
    """The zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (
        1 + w.astype(F32)
    )


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def layer_is_full(cfg, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


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
    return out.reshape(T, H, hd)


def rotate_leading(x, rd, theta):
    """x [T, heads, hd]: dims 0..rd-1 rotated by position (half-split
    pairs ``(i, i + rd/2)``), dims rd.. as they are."""
    T = x.shape[0]
    inv = theta ** (-jnp.arange(rd // 2, dtype=F32) / (rd // 2))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2, rest = x[..., : rd // 2], x[..., rd // 2:rd], x[..., rd:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


def delta_net(h, gw, cfg, prec, stop=None):
    """One Gated DeltaNet mixer on a row h [T, D], the recurrence token
    by token. Returns ``(out [T, D], S [H, dk, dv])``: the state after
    ``stop`` tokens (after all of them where ``stop`` is None)."""
    T = h.shape[0]
    Hk, H = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K, rep = cfg["linear_conv_kernel_dim"], H // Hk
    qkvz = matmul(h, gw["in_qkvz"], prec)
    ba = jnp.matmul(h, gw["in_ba"].astype(F32))  # a float32 weight
    qkv, z = jnp.split(qkvz, [2 * Hk * dk + H * dv], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), F32), qkv])
    conv_w = gw["conv_w"].astype(F32)  # [K, channels]; tap K - 1 is "now"
    qkv = jax.nn.silu(sum(padded[j:j + T] * conv_w[j] for j in range(K)))
    q, k, v = jnp.split(qkv, [Hk * dk, 2 * Hk * dk], axis=-1)
    q = jnp.repeat(l2norm(q.reshape(T, Hk, dk)) * dk**-0.5, rep, axis=1)
    k = jnp.repeat(l2norm(k.reshape(T, Hk, dk)), rep, axis=1)
    v = v.reshape(T, H, dv)
    b, a = jnp.split(ba, 2, axis=-1)
    beta = jax.nn.sigmoid(b)  # [T, H]
    g = -jnp.exp(gw["A_log"].astype(F32)) * jax.nn.softplus(a + gw["dt_bias"])

    last = T - 1 if stop is None else stop - 1

    def step(carry, xs):
        S, kept = carry
        q_t, k_t, v_t, g_t, beta_t, t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        u = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + k_t[:, :, None] * (beta_t[:, None] * (v_t - u))[:, None, :]
        if prec.state == "bf16":
            # not a cast there and back: XLA takes such a pair out
            # (the TPU's compiler allows excess precision)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return (S, jnp.where(t == last, S, kept)), jnp.einsum("hkv,hk->hv", S, q_t)

    zero = jnp.zeros((H, dk, dv), F32)
    (_, kept), o = jax.lax.scan(
        step, (zero, zero), (q, k, v, g, beta, jnp.arange(T))
    )
    # the norm per head, a plain weight, THEN the gate
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + cfg["rms_norm_eps"])
    y = o * gw["norm"].astype(F32) * jax.nn.silu(z.reshape(T, H, dv))
    return matmul(y.reshape(T, H * dv), gw["out_proj"], prec), kept


def attention_mixer(h, aw, cfg, prec):
    T = h.shape[0]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q, gate = jnp.split(matmul(h, aw["wq"], prec).reshape(T, H, 2 * hd), 2, axis=-1)
    k = matmul(h, aw["wk"], prec).reshape(T, Hkv, hd)
    v = matmul(h, aw["wv"], prec).reshape(T, Hkv, hd)
    rd = int(hd * cfg["partial_rotary_factor"])
    q = rotate_leading(norm(q, aw["q_norm"], eps), rd, cfg["rope_theta"])
    k = rotate_leading(norm(k, aw["k_norm"], eps), rd, cfg["rope_theta"])
    out = attention(q, k, v, hd**-0.5) * jax.nn.sigmoid(gate)
    return matmul(out.reshape(T, H * hd), aw["wo"], prec)


def swiglu(h, gate, up, down, prec):
    return matmul(
        jax.nn.silu(matmul(h, gate, prec)) * matmul(h, up, prec), down, prec
    )


def routing(h, router, cfg):
    """(weights [T, E] over ALL experts, top ids [T, k]): a softmax over
    all, the k largest, divided by their sum."""
    E, k = router.shape[-1], cfg["num_experts_per_tok"]
    top_p, top_i = jax.lax.top_k(
        jax.nn.softmax(jnp.matmul(h, router.astype(F32)), axis=-1), k
    )
    if cfg["norm_topk_prob"]:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    return (jax.nn.one_hot(top_i, E, dtype=F32) * top_p[..., None]).sum(1), top_i


def shared_expert(h, lw, prec):
    """``sigmoid(h w_sg) * shared(h)``: what every chip that shares the
    layer computes alike."""
    gate = jax.nn.sigmoid(jnp.matmul(h, lw["sh_scale"].astype(F32)))
    return gate[:, None] * swiglu(h, lw["sh_gate"], lw["sh_up"], lw["sh_down"], prec)


def ffn(x, lw, banks, depth, cfg, prec):
    """``(routed + shared)(N(x))`` and the chosen ids. The experts held
    are those of the banks, ``deployment.experts_held.first`` on; every
    one of them by a plain loop, its matrices taken from the stacked
    banks one at a time."""
    h = norm(x, lw["norm2"], cfg["rms_norm_eps"])
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
    return routed + shared_expert(h, lw, prec), top_i


def hidden_states(params, tokens, cfg, prec=SOUND, stop=None):
    """tokens [T] -> (final-norm hidden [T, D], top ids [L, T, k], the
    DeltaNet layers' states after ``stop`` tokens [L_g, H, dk, dv]).
    Consecutive layers of one kind go through one ``lax.scan``."""
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    take = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)  # noqa: E731
    banks = {n: params["layers"][n] for n in BANKS}
    small = {n: v for n, v in params["layers"].items() if n not in BANKS}
    full = [layer_is_full(cfg, i) for i in range(L)]
    x = params["embed"][tokens].astype(F32)
    seen = {True: 0, False: 0}
    chosen, states = [], []
    depth = 0
    while depth < L:
        kind = full[depth]
        count = next((j for j in range(depth, L) if full[j] != kind), L) - depth

        def layer(x, i, kind=kind, depth0=depth, first=seen[kind]):
            lw = take(small, depth0 + i)
            h = norm(x, lw["norm1"], cfg["rms_norm_eps"])
            if kind:
                mixed = attention_mixer(h, take(params["attn"], first + i), cfg, prec)
                S = jnp.zeros((0,), F32)
            else:
                mixed, S = delta_net(h, take(params["gdn"], first + i), cfg, prec, stop)
            x = x + mixed
            y, top_i = ffn(x, lw, banks, depth0 + i, cfg, prec)
            return x + y, (top_i, S)

        x, (top_i, S) = jax.lax.scan(layer, x, jnp.arange(count))
        chosen.append(top_i)
        if not kind:
            states.append(S)
        seen[kind] += count
        depth += count
    return (
        norm(x, params["final_norm"], cfg["rms_norm_eps"]),
        jnp.concatenate(chosen), jnp.concatenate(states),
    )


def logits_and_states(params, tokens, cfg, prec=SOUND, at=None, stop=None):
    """tokens [T] -> (logits [T or len(at), V], top ids [L, T, k], the
    DeltaNet layers' states after ``stop`` tokens); ``at`` picks
    positions before the head runs."""
    with jax.default_matmul_precision("highest"):
        h, top_i, states = hidden_states(params, tokens, cfg, prec, stop)
        if at is not None:
            h = h[at]
        return jnp.matmul(h, params["lm_head"].astype(F32)), top_i, states


def logits(params, tokens, cfg, prec=SOUND, at=None):
    """tokens [T] -> (logits [T or len(at), V], top ids [L, T, k])."""
    return logits_and_states(params, tokens, cfg, prec, at)[:2]


def first_state(params, tokens, cfg, prec=SOUND, stop=None):
    """The first DeltaNet layer's state [H, dk, dv] after ``stop`` of
    ``tokens`` [T] (after all of them where None): layer 0 alone, which
    nothing below it moves."""
    with jax.default_matmul_precision("highest"):
        w = params["layers"]["norm1"][0]
        h = norm(params["embed"][tokens].astype(F32), w, cfg["rms_norm_eps"])
        gw = jax.tree_util.tree_map(lambda a: a[0], params["gdn"])
        return delta_net(h, gw, cfg, prec, stop)[1]
