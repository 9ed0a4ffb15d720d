"""The plain reference for Cohere's ``cohere2_moe`` (Command A+): one
row's forward pass in straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``. No kernels, no cache, no
batching, and nothing of the program is imported. ``benchmark/reference/
cohere2_moe.py`` is this file's copy, byte for byte (a test holds them
together), so that the benchmark's yardstick does not move with the
program.

The layer, with every key the SOURCE's (``config.json`` of
CohereLabs/command-a-plus-05-2026), T tokens, D = ``hidden_size``:

- ``h = LayerNorm(x)``: mean-subtracting, scale only, ``layer_norm_eps``;
  one norm feeds both branches (``use_parallel_block``);
- ``q = h Wq`` [T, H, hd], ``k = h Wk``, ``v = h Wv`` [T, Hkv, hd], no
  bias, no q/k norm. ``layer_types[l] == "sliding_attention"``: q and k
  rotated on INTERLEAVED pairs (2i, 2i+1) (``rope_gptj``, ``rotary_pct``
  1, ``rope_theta``) and key j visible to query i iff ``i -
  sliding_window < j <= i``; ``"full_attention"``: no rotation, causal.
  ``a = softmax(q k^T / sqrt(hd)) v``, ``attn = a Wo``;
- ``s = sigmoid(h Wr)`` [T, E], the ``num_experts_per_tok`` largest,
  ``w = s_top / sum(s_top)`` (``norm_topk_prob``); ``routed = sum_e w_e
  (silu(h G_e) * (h U_e)) D_e``;
- ``shared = (1 / n) sum_j (silu(h G'_j) * (h U'_j)) D'_j`` over the
  ``num_shared_experts`` (``shared_expert_combination_strategy:
  average``), ADDED to the routed sum;
- ``y = x + attn + routed + shared``; after the last layer a LayerNorm
  and ``logits = logit_scale * h E^T`` with the tied embedding.

Departures from the source, each stated in the configuration file too:
- it is given one chip's SHARE of a deployment: the routed experts
  ``deployment.experts_held`` (first, count) of the router's E, and a
  slice of the vocabulary. The router scores all E; what the experts
  held elsewhere would add is left out, as in the program;
- two readings of the source are inferences: full-attention layers
  carry no positional rotation (the catalog's "global NoPE"; the Cohere2
  family's convention), and ``average`` is the mean over the shared
  experts' outputs, added to the routed sum;
- ``intermediate_size`` is read as ONE expert's width (routed or shared);
- the shared experts are stored side by side (``sh_gate`` [D, n * F],
  ``sh_down`` [n * F, D]); they are computed one at a time here;
- weights are the harness's seeded int8 leaves ``{"q", "scale"}``,
  dequantised as ``q * scale``, which is exact in float32.

``Precision`` computes the same mathematics in a lower precision: what
the controls run, and what the check has to tell from a sound run.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


class Precision(NamedTuple):
    act: str = "f32"  # "f32" | "bf16" | "int8" (per-token, into frozen matmuls)


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
    w = weight(leaf)
    if prec.act == "int8":
        x = _fake_int8_rows(x)
    if prec.act == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(F32)
    return jnp.matmul(x, w)


def layer_norm(x, w, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rope_interleaved(x, positions, theta):
    """x [T, H, hd]; rotate pairs (2i, 2i+1) by position * theta^(-2i/hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def attention(q, k, v, window, block=128):
    """q [T, H, hd], k/v [T, Hkv, hd]; query i sees keys j with ``i -
    window < j <= i``. A block of queries at a time, so that the float32
    scores of every head fit (128 heads x 128 queries x 13312 keys are
    0.9 GB)."""
    T, H, hd = q.shape
    Hkv = k.shape[1]
    block = min(block, T)
    assert T % block == 0, (T, block)
    qg = q.reshape(T // block, block, Hkv, H // Hkv, hd)
    kpos = jnp.arange(T)

    def one(args):
        qb, start = args
        qpos = start + jnp.arange(block)
        scores = jnp.einsum("qhgd,khd->hgqk", qb, k) * hd**-0.5
        seen = (kpos[None, :] <= qpos[:, None]) & (
            kpos[None, :] > qpos[:, None] - window
        )
        scores = jnp.where(seen[None, None], scores, -1e30)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, (qg, jnp.arange(0, T, block)))
    return out.reshape(T, H * hd)


def swiglu(h, gate, up, down, prec):
    return matmul(
        jax.nn.silu(matmul(h, gate, prec)) * matmul(h, up, prec), down, prec
    )


def routing(h, router, cfg):
    """(combine weights [T, E] over ALL experts, top ids [T, k])."""
    E, k = router.shape[-1], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(h, router.astype(F32)))
    top_s, top_i = jax.lax.top_k(scores, k)
    if cfg["norm_topk_prob"]:
        top_s = top_s / top_s.sum(-1, keepdims=True)
    combine = (jax.nn.one_hot(top_i, E, dtype=F32) * top_s[..., None]).sum(1)
    return combine, top_i


def layer(x, lw, windowed, cfg, prec):
    """One parallel block on a row x [T, D] -> (y, top ids [T, k])."""
    T = x.shape[0]
    H, Hkv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    h = layer_norm(x, lw["norm"], cfg["layer_norm_eps"])
    q = matmul(h, lw["wq"], prec).reshape(T, H, hd)
    k = matmul(h, lw["wk"], prec).reshape(T, Hkv, hd)
    v = matmul(h, lw["wv"], prec).reshape(T, Hkv, hd)
    pos = jnp.arange(T)
    q = jnp.where(windowed, rope_interleaved(q, pos, cfg["rope_theta"]), q)
    k = jnp.where(windowed, rope_interleaved(k, pos, cfg["rope_theta"]), k)
    window = jnp.where(windowed, cfg["sliding_window"], T + 1)
    attn = matmul(attention(q, k, v, window), lw["wo"], prec)

    combine, top_i = routing(h, lw["router"], cfg)
    first = cfg["deployment"]["experts_held"]["first"]
    count = lw["moe_gate"]["q"].shape[0] if isinstance(
        lw["moe_gate"], dict) else lw["moe_gate"].shape[0]
    held = jax.lax.dynamic_slice_in_dim(combine, first, count, axis=1)

    def one_expert(acc, xs):
        gate, up, down, w_e = xs
        return acc + w_e[:, None] * swiglu(h, gate, up, down, prec), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lw["moe_gate"], lw["moe_up"], lw["moe_down"], held.T),
    )

    n, F = cfg["num_shared_experts"], cfg["intermediate_size"]
    shared = jnp.zeros_like(x)
    for j in range(n):
        cols = lambda leaf: jax.tree_util.tree_map(  # noqa: E731
            lambda a: a[..., j * F:(j + 1) * F], leaf
        )
        rows = (
            {"q": lw["sh_down"]["q"][j * F:(j + 1) * F], "scale": lw["sh_down"]["scale"]}
            if isinstance(lw["sh_down"], dict) else lw["sh_down"][j * F:(j + 1) * F]
        )
        shared = shared + swiglu(h, cols(lw["sh_gate"]), cols(lw["sh_up"]), rows, prec)
    return x + attn + routed + shared / n, top_i


def hidden_states(params, tokens, cfg, prec=SOUND):
    """tokens [T] -> (final-norm hidden [T, D], top ids [L, T, k])."""
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    windowed = jnp.asarray(
        [t == "sliding_attention" for t in cfg["layer_types"][:L]]
    )
    x = params["embed"][tokens].astype(F32)

    @jax.checkpoint
    def body(x, xs):
        lw, w = xs
        return layer(x, lw, w, cfg, prec)

    x, top_i = jax.lax.scan(body, x, (params["layers"], windowed))
    return layer_norm(x, params["final_norm"], cfg["layer_norm_eps"]), top_i


def logits(params, tokens, cfg, prec=SOUND, at=None):
    """tokens [T] -> (logits [T or len(at), V] over the held slice of
    the vocabulary, top ids [L, T, k]); ``at`` picks positions before
    the head runs."""
    with jax.default_matmul_precision("highest"):
        h, top_i = hidden_states(params, tokens, cfg, prec)
        if at is not None:
            h = h[at]
        return cfg["logit_scale"] * jnp.matmul(h, params["embed"].astype(F32).T), top_i
