"""The plain reference for Kwai-Keye's ``KeyeVL2`` language model
(Keye-VL-2.0-30B-A3B): one row's forward pass in straightforward
``jax.numpy``, float32 under ``jax.default_matmul_precision("highest")``.
No kernels, no cache, no parts, no batching, and nothing of the program
is imported. ``benchmark/reference/keye_vl.py`` is this file's copy, byte
for byte (a test holds them together), so that the benchmark's yardstick
does not move with the program.

Every layer is of ONE kind (``config.json`` of
Kwai-Keye/Keye-VL-2.0-30B-A3B: ``decoder_sparse_step`` 1,
``mlp_only_layers`` []): GQA whose queries attend only the ``topk`` keys
a learned indexer picks (``sa_config``), then a softmax-routed mixture
with no shared expert. T tokens, ``N(x) = x * rsqrt(mean(x^2) +
rms_norm_eps) * w`` (a plain weight), ``h = N(x)``:

- ``q = h W_q`` (``num_attention_heads`` x ``head_dim``), ``k = h W_k``,
  ``v = h W_v`` (``num_key_value_heads`` x ``head_dim``), no bias; per
  head ``q <- N(q) w_qn``, ``k <- N(k) w_kn`` over ``head_dim``; rotation
  by THREE position streams ``p = (p_t, p_h, p_w)``: with ``f_i =
  rope_theta^(-i / (head_dim / 2))`` the half-split pair ``(i, i +
  head_dim / 2)`` turns by ``p_t f_i`` for ``i`` in the first of
  ``rope_scaling.mrope_section``, by ``p_h f_i`` in the second, by
  ``p_w f_i`` in the third (contiguous ranges 16 | 24 | 24); a text
  token has three equal positions and this is plain RoPE;
- the indexer: ``q^I_t = h_t W_qI`` (``indexer_num_heads`` x
  ``indexer_head_dim``), ``k^I_s = LayerNorm(h_s W_kI)`` (ONE head;
  weight and bias, eps ``rms_norm_eps``), ``w_t = h_t W_w`` (a weight a
  head); ``q^I`` and ``k^I`` rotated over ALL their dims (half-split,
  ``rope_theta^(-i / (indexer_head_dim / 2))``) by the temporal stream;
  ``I_ts = sum_j w_tj ReLU(q^I_tj . k^I_s)``. A positive scale on ``I``
  cannot change a ranking and is left out;
- the selection ``S_t``: the ``min(topk, t + 1)`` positions ``s <= t`` of
  largest ``I_ts`` (``lax.top_k`` over the causal scores: a tie at the
  edge goes to the LOWER position). One set a query, for every head;
- ``y_ta = sum_{s in S_t} softmax_{s in S_t}(q_ta . k_s,c(a) /
  sqrt(head_dim)) v_s,c(a)``; ``x <- x + concat(y) W_o``;
- ``h2 = N(x)``; ``p = softmax(h2 W_r)`` over all the ROUTER's experts
  (its width is the published one, whatever is held here), the
  ``num_experts_per_tok`` largest, divided by their sum
  (``norm_topk_prob``); ``x <- x + sum_{e chosen and HELD} p_e
  (silu(h2 G_e) * (h2 U_e)) D_e`` (``held``: which experts, first and
  count; what the others would add is their chips' and is left out);
- after the last layer ``N`` and ``logits = h W_head`` (untied; the rows
  of the vocabulary that the head's matrix holds).

Left out, as in the program: the vision tower (no key of the language
model's ``config.json`` describes it, and its positions would be the
three streams' only use beyond text), the indexer's Hadamard rotation
(an orthogonal map on both sides of a dot product) and its FP8 storage.

The weights are the harness's seeded leaves in the program's layout,
every layer's stacked under ``layers``; int8 leaves ``{"q", "scale"}``
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
NEG = -jnp.inf


class Precision(NamedTuple):
    act: str = "f32"  # "f32" | "int8" (per token, into frozen matmuls)
    index: str = "f32"  # "f32" | "bf16": the accumulation of ``I``


SOUND = Precision()


def _fake_int8_rows(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _bf16(x):
    # not a cast there and back: XLA takes such a pair out
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def weight(leaf):
    """A stored leaf as float32; int8 leaves dequantise exactly."""
    if isinstance(leaf, dict):
        return leaf["q"].astype(F32) * leaf["scale"].astype(F32)
    return leaf.astype(F32)


def matmul(x, leaf, prec: Precision = SOUND):
    """x @ W for a frozen weight, in the stated precision."""
    if prec.act == "int8" and isinstance(leaf, dict):
        x = _fake_int8_rows(x)
    return jnp.matmul(x, weight(leaf))


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * (
        w.astype(F32)
    )


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * w.astype(F32) + b.astype(F32)


def text_positions(T: int):
    """A text row's three streams: all the token's index."""
    return jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (3, T))


def stream_angles(positions, half: int, theta, sections=None):
    """positions [3, T] -> angles [T, half]: frequency ``i`` turns by the
    stream its section names (all by the temporal one without)."""
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    if sections is None:
        return positions[0].astype(F32)[:, None] * inv
    assert sum(sections) == half, (sections, half)
    stream = jnp.repeat(jnp.arange(3), jnp.asarray(sections), total_repeat_length=half)
    return positions.astype(F32).T[:, stream] * inv


def rotate(x, angles):
    """x [T, heads, d], half-split pairs ``(i, i + d/2)`` by angles [T, d/2]."""
    sin, cos = jnp.sin(angles)[:, None, :], jnp.cos(angles)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def index_parts(h, lw, cfg, positions, prec=SOUND):
    """(q^I [T, Hi, di], k^I [T, di], w [T, Hi]), rotated."""
    sa = cfg["sa_config"]
    Hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    T = h.shape[0]
    ang = stream_angles(positions, di // 2, cfg["rope_theta"])
    qi = matmul(h, lw["wq_idx"], prec).reshape(T, Hi, di)
    ki = layer_norm(
        matmul(h, lw["wk_idx"], prec), lw["ik_norm_w"], lw["ik_norm_b"],
        cfg["rms_norm_eps"],
    )
    w = jnp.matmul(h, lw["w_idx"].astype(F32))  # a float32 weight
    return rotate(qi, ang), rotate(ki[:, None, :], ang)[:, 0], w


def index_scores(qi, ki, w, prec=SOUND):
    """``I`` [Q, T] for queries qi [Q, Hi, di], w [Q, Hi] against every
    key ki [T, di]; not yet causal. A score of ``-0.0`` is written
    ``+0.0``: ``lax.top_k`` orders the two, and a ranking must not hang
    on the sign of a zero."""
    s = jnp.einsum("qhd,kd->hqk", qi, ki)
    no_negative_zero = lambda x: jnp.where(x == 0.0, 0.0, x)  # noqa: E731
    if prec.index == "bf16":
        # every partial sum a bfloat16: the dot, the product, the heads
        def add(acc, xs):
            s_h, w_h = xs
            return _bf16(acc + _bf16(jax.nn.relu(_bf16(s_h)) * w_h[:, None])), None

        return no_negative_zero(
            jax.lax.scan(add, jnp.zeros(s.shape[1:], F32), (s, w.T))[0]
        )
    return no_negative_zero(jnp.einsum("hqk,qh->qk", jax.nn.relu(s), w))


def select(scores, q_pos, topk: int):
    """The selection of each query: scores [Q, T] (any), q_pos [Q] the
    queries' indices among the keys. Returns ``(ids [Q, k] the chosen
    positions, largest score first, valid [Q, k])`` with ``k = min(topk,
    T)``; a query at ``t`` has ``min(k, t + 1)`` valid ones."""
    T = scores.shape[1]
    causal = jnp.arange(T)[None, :] <= q_pos[:, None]
    _, ids = jax.lax.top_k(jnp.where(causal, scores, NEG), min(topk, T))
    return ids, jnp.arange(ids.shape[1])[None, :] <= q_pos[:, None]


def attention_mixer(h, lw, cfg, positions, prec, block=128, keep=None):
    """The layer's attention on a row h [T, D], a block of queries at a
    time so that the float32 scores fit. ``keep`` [n] are query positions
    whose selection is returned: ``(out [T, D], ids [n, k], valid [n, k])``."""
    T = h.shape[0]
    H, Hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, topk = cfg["rms_norm_eps"], cfg["sa_config"]["topk"]
    q = norm(matmul(h, lw["wq"], prec).reshape(T, H, hd), lw["q_norm"], eps)
    k = norm(matmul(h, lw["wk"], prec).reshape(T, Hkv, hd), lw["k_norm"], eps)
    v = matmul(h, lw["wv"], prec).reshape(T, Hkv, hd)
    ang = stream_angles(
        positions, hd // 2, cfg["rope_theta"], cfg["rope_scaling"]["mrope_section"]
    )
    q, k = rotate(q, ang), rotate(k, ang)
    qi, ki, w = index_parts(h, lw, cfg, positions, prec)
    block = min(block, T)
    assert T % block == 0, (T, block)

    def one(start):
        qpos = start + jnp.arange(block)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, start, block)  # noqa: E731
        ids, valid = select(index_scores(cut(qi), ki, cut(w), prec), qpos, topk)
        chosen = jnp.zeros((block, T), bool).at[
            jnp.arange(block)[:, None], ids
        ].max(valid)
        qb = cut(q).reshape(block, Hkv, H // Hkv, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) * hd**-0.5
        s = jnp.where(chosen[None, None], s, -1e30)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(one, jnp.arange(0, T, block)).reshape(T, H * hd)
    ids = valid = None
    if keep is not None:
        ids, valid = select(index_scores(qi[keep], ki, w[keep], prec), keep, topk)
    return matmul(out, lw["wo"], prec), ids, valid


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


def ffn(x, lw, banks, depth, cfg, prec, held=None):
    """The held experts' part of the mixture on ``N(x)`` and the chosen
    ids. ``held = (first, count)``: which of the router's experts the
    banks hold (``deployment.experts_held`` where None); every one of
    them by a plain loop, its matrices taken from the stacked banks one
    at a time."""
    h = norm(x, lw["norm2"], cfg["rms_norm_eps"])
    combine, top_i = routing(h, lw["router"], cfg)
    if held is None:
        e = cfg["deployment"]["experts_held"]
        held = (e["first"], e["count"])
    first, count = held
    assert count == jax.tree_util.tree_leaves(banks["moe_gate"])[0].shape[1]
    mine = jax.lax.dynamic_slice_in_dim(combine, first, count, axis=1)

    def one_expert(acc, xs):
        e, g_e = xs
        gate, up, down = (
            jax.tree_util.tree_map(lambda a: a[depth, e], banks[name])
            for name in BANKS
        )
        return acc + g_e[:, None] * swiglu(h, gate, up, down, prec), None

    routed, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (jnp.arange(count), mine.T)
    )
    return routed, top_i


def split_layers(params):
    """(every layer's small weights [L, ...], the expert banks [L, E, ...])."""
    layers = params["layers"]
    return (
        {n: v for n, v in layers.items() if n not in BANKS},
        {n: layers[n] for n in BANKS},
    )


def hidden_states(params, tokens, cfg, prec=SOUND, positions=None, held=None,
                  keep=None):
    """tokens [T] -> (final-norm hidden [T, D], the router's top ids [L,
    T, k], the selections of the queries ``keep`` [L, n, topk] and their
    validity, or None twice). One ``lax.scan`` over the layers."""
    T = tokens.shape[0]
    if positions is None:
        positions = text_positions(T)
    L = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    small, banks = split_layers(params)

    def layer(x, i):
        lw = jax.tree_util.tree_map(lambda a: a[i], small)
        h = norm(x, lw["norm1"], cfg["rms_norm_eps"])
        mixed, ids, valid = attention_mixer(h, lw, cfg, positions, prec, keep=keep)
        x = x + mixed
        routed, top_i = ffn(x, lw, banks, i, cfg, prec, held)
        return x + routed, (top_i, ids, valid)

    x = params["embed"][tokens].astype(F32)
    x, (top_i, ids, valid) = jax.lax.scan(layer, x, jnp.arange(L))
    return norm(x, params["final_norm"], cfg["rms_norm_eps"]), top_i, ids, valid


def logits_and_selection(params, tokens, cfg, prec=SOUND, at=None, positions=None,
                         held=None, keep=None):
    """tokens [T] -> (logits [T or len(at), V], top ids [L, T, k], the
    selections of the queries ``keep`` [L, n, topk], their validity);
    ``at`` picks positions before the head runs."""
    with jax.default_matmul_precision("highest"):
        h, top_i, ids, valid = hidden_states(
            params, tokens, cfg, prec, positions, held, keep
        )
        if at is not None:
            h = h[at]
        return jnp.matmul(h, params["lm_head"].astype(F32)), top_i, ids, valid


def logits(params, tokens, cfg, prec=SOUND, at=None, positions=None, held=None):
    """tokens [T] -> (logits [T or len(at), V], top ids [L, T, k])."""
    return logits_and_selection(params, tokens, cfg, prec, at, positions, held)[:2]


def layer_input(params, tokens, cfg, layer: int, prec=SOUND, positions=None,
                held=None):
    """The normed hidden state ``h`` [T, D] that layer ``layer``'s
    attention and indexer read, and that layer's small weights."""
    T = tokens.shape[0]
    if positions is None:
        positions = text_positions(T)
    small, banks = split_layers(params)
    x = params["embed"][tokens].astype(F32)
    for i in range(layer):
        lw = jax.tree_util.tree_map(lambda a: a[i], small)
        h = norm(x, lw["norm1"], cfg["rms_norm_eps"])
        x = x + attention_mixer(h, lw, cfg, positions, prec)[0]
        x = x + ffn(x, lw, banks, i, cfg, prec, held)[0]
    lw = jax.tree_util.tree_map(lambda a: a[layer], small)
    return norm(x, lw["norm1"], cfg["rms_norm_eps"]), lw, positions


def index_and_selection(params, tokens, cfg, layer: int = 0, prec=SOUND,
                        positions=None, held=None):
    """Layer ``layer``'s ``I`` [T, T] (above the diagonal -inf), ``S_t``
    as ``(ids [T, k], valid [T, k])`` and the indexer's keys ``k^I`` [T,
    di]."""
    with jax.default_matmul_precision("highest"):
        h, lw, positions = layer_input(
            params, tokens, cfg, layer, prec, positions, held
        )
        T = tokens.shape[0]
        qi, ki, w = index_parts(h, lw, cfg, positions, prec)
        scores = index_scores(qi, ki, w, prec)
        qpos = jnp.arange(T)
        ids, valid = select(scores, qpos, cfg["sa_config"]["topk"])
        causal = qpos[None, :] <= qpos[:, None]
        return jnp.where(causal, scores, NEG), ids, valid, ki


def index_keys(params, tokens, cfg, prec=SOUND, positions=None):
    """The FIRST layer's indexer keys ``k^I`` [T, di]: what nothing
    below moves."""
    with jax.default_matmul_precision("highest"):
        h, lw, positions = layer_input(params, tokens, cfg, 0, prec, positions)
        return index_parts(h, lw, cfg, positions, prec)[1]
