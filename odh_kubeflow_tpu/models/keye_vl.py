"""Kwai-Keye's ``KeyeVL2`` language model (Keye-VL-2.0-30B-A3B), served.

What the block is (the plain reference, ``reference/keye_vl.py``, writes
the equations out): a pre-norm stack of ONE kind of layer.
``layer_kinds`` is ``(llama.INDEXED,)``: every layer keeps keys and
values ``max_len`` long AND, beside them, the keys of a learned INDEXER
(``generate.init_cache``: the ``("sk", "sv", "ik")`` stacks), and its
queries attend only the ``index_topk`` keys the indexer picks for them.

- ``q = h W_q``, ``k = h W_k``, ``v = h W_v`` (GQA); per head
  ``rms_norm(q) * w_qn``, ``rms_norm(k) * w_kn`` (a PLAIN weight, as
  ``models/brumby.py``); rotation by THREE position streams (temporal,
  height, width) over the frequency sections ``mrope_section``
  (``ops/rope.stream_angles``). ``positions`` is ``[B, S]`` (text, the
  engine's: three equal streams, which is plain RoPE) or ``[3, B, S]``;
- the indexer: ``q^I = h W_qI`` (``index_heads`` x ``index_dim``), ``k^I
  = LayerNorm(h W_kI)`` (ONE head), ``w = h W_w`` from the float32 norm;
  ``q^I``, ``k^I`` rotated over all their dims by the temporal stream;
  ``I_ts = sum_j w_tj ReLU(q^I_tj . k^I_s)`` and a query's selection the
  ``index_topk`` largest of its causal scores, a tie at the edge to the
  lower position (``ops/sparse_attention.py``;
  ``llama.indexed_write_and_attend`` over the cache);
- the experts: a float32 softmax over ALL ``num_experts``, the
  ``num_experts_per_tok`` largest, renormalised
  (``moe.route_softmax_topk``); ``moe.local_expert_ffn`` on the experts
  held here (``experts_held``). NO shared expert;
- ``x0 = E[tok]``, an untied head over this chip's rows of the
  vocabulary. The published vision tower is not here (no key of the
  language model's ``config.json`` describes it): the traffic is text.

The cached forward is the serving path (``llama.scan_layers_with_cache``
over the one kind: the stack is scanned as it lies). ``forward`` is the
uncached form the tests hold it against; the family has no training
path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from odh_kubeflow_tpu.models import llama, moe
from odh_kubeflow_tpu.models.granite_hybrid import _split_banks
from odh_kubeflow_tpu.models.llama import INDEXED
from odh_kubeflow_tpu.ops import sparse_attention
from odh_kubeflow_tpu.ops.norms import rms_norm
from odh_kubeflow_tpu.ops.rope import apply_rope, rope_angles, stream_angles

Params = dict[str, Any]
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class KeyeVLConfig:
    vocab_size: int = 151_936  # the rows of the vocabulary held here
    hidden_size: int = 2048
    num_layers: int = 48
    # every layer attends the keys its indexer picks
    layer_kinds: tuple = (INDEXED,)
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10_000_000.0
    mrope_section: tuple = (16, 24, 24)
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    num_experts: int = 128  # the router's width
    experts_held: tuple = (0, 128)  # (first, count) held here
    num_experts_per_tok: int = 8
    expert_width: int = 768
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    # ``generate.family_forward`` finds the cached forward here
    family_module = "odh_kubeflow_tpu.models.keye_vl"

    @staticmethod
    def tiny(**kw) -> "KeyeVLConfig":
        """Unit-test shape: three layers, 4 query heads onto 2 key/value
        heads of 16, an indexer of 2 heads of 8 that keeps 6 keys, 8
        experts of which the first 4 are held."""
        d = dict(
            vocab_size=256, hidden_size=64, num_layers=3, num_heads=4,
            num_kv_heads=2, head_dim=16, rope_theta=10_000.0,
            mrope_section=(2, 3, 3), index_heads=2, index_dim=8, index_topk=6,
            num_experts=8, experts_held=(0, 4), num_experts_per_tok=2,
            expert_width=32,
        )
        d.update(kw)
        return KeyeVLConfig(**d)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


def init_params(key: jax.Array, cfg: KeyeVLConfig, dtype=F32) -> Params:
    """Seeded weights in the served layout, every layer's stacked under
    ``layers`` [L, ...]. Norm weights are drawn off 1 so that they show."""
    D, F, L, E = cfg.hidden_size, cfg.expert_width, cfg.num_layers, cfg.experts_held[1]
    Hi, di = cfg.index_heads, cfg.index_dim
    k = iter(jax.random.split(key, 32))

    def dense(shape, fan_in, dt=dtype):
        return (jax.random.normal(next(k), shape, F32) * fan_in**-0.5).astype(dt)

    def off_one(shape):
        return (1 + 0.1 * jax.random.normal(next(k), shape, F32)).astype(dtype)

    return {
        "embed": dense((cfg.vocab_size, D), D),
        "lm_head": dense((D, cfg.vocab_size), D),
        "layers": {
            "norm1": off_one((L, D)),
            "norm2": off_one((L, D)),
            "wq": dense((L, D, cfg.q_dim), D),
            "wk": dense((L, D, cfg.kv_dim), D),
            "wv": dense((L, D, cfg.kv_dim), D),
            "wo": dense((L, cfg.q_dim, D), cfg.q_dim),
            "q_norm": off_one((L, cfg.head_dim)),
            "k_norm": off_one((L, cfg.head_dim)),
            "wq_idx": dense((L, D, Hi * di), D),
            "wk_idx": dense((L, D, di), D),
            # a weight a head with a spread of ~1 over the heads' sum
            "w_idx": dense((L, D, Hi), D * Hi, F32),
            "ik_norm_w": off_one((L, di)),
            "ik_norm_b": (0.1 * jax.random.normal(next(k), (L, di), F32)).astype(dtype),
            "router": dense((L, D, cfg.num_experts), D, F32),
            "moe_gate": dense((L, E, D, F), D),
            "moe_up": dense((L, E, D, F), D),
            "moe_down": dense((L, E, F, D), F),
        },
        "final_norm": off_one((D,)),
    }


def _layer_norm(x, w, b, eps):
    x = x.astype(F32)
    x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x * w.astype(F32) + b.astype(F32)


def _angles(cfg, positions):
    """((sin, cos) of the heads' rotation, (sin, cos) of the indexer's):
    three streams over the sections for the heads, the temporal stream
    over all of the indexer's dims."""
    if positions.ndim == 3:
        heads = stream_angles(
            positions, cfg.head_dim, cfg.rope_theta, cfg.mrope_section
        )
        positions = positions[0]
    else:
        # three equal streams: plain RoPE
        heads = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    return heads, rope_angles(positions, cfg.index_dim, cfg.rope_theta)


def _mixer_inputs(cfg, x, layer, angles):
    """What a layer's attention and indexer compute of ``N(x)`` before
    anything reads a cache: ``(q, k, v, q^I, k^I, w)``, rotated.
    ``layer`` is dequantised."""
    B, S, _ = x.shape
    (sin, cos), (isin, icos) = angles
    h32 = rms_norm(x.astype(F32), layer["norm1"], cfg.rms_norm_eps)
    h = h32.astype(x.dtype)
    # plain [B, S, width] matrices up to the barrier: without it XLA
    # carries the splits into heads onto the weights (PERF.md, PR 26)
    q, kk, vv, qi, ki = jax.lax.optimization_barrier(tuple(
        h @ layer[n].astype(h.dtype) for n in ("wq", "wk", "wv", "wq_idx", "wk_idx")
    ))
    q = rms_norm(
        q.reshape(B, S, cfg.num_heads, cfg.head_dim), layer["q_norm"],
        cfg.rms_norm_eps,
    )
    kk = rms_norm(
        kk.reshape(B, S, cfg.num_kv_heads, cfg.head_dim), layer["k_norm"],
        cfg.rms_norm_eps,
    )
    vv = vv.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    with jax.named_scope("indexer_proj"):
        qi = apply_rope(
            qi.reshape(B, S, cfg.index_heads, cfg.index_dim), isin, icos
        )
        ki = _layer_norm(
            ki, layer["ik_norm_w"], layer["ik_norm_b"], cfg.rms_norm_eps
        )
        ki = apply_rope(ki[:, :, None, :], isin, icos)[:, :, 0].astype(h.dtype)
        # a head's weight from the float32 norm, as the router's logits
        wi = jnp.einsum(
            "bsd,dh->bsh", h32, layer["w_idx"].astype(F32),
            precision=jax.lax.Precision.HIGHEST,
        )
    return apply_rope(q, sin, cos), apply_rope(kk, sin, cos), vv, qi, ki, wi


def _ffn(cfg, x, layer, banks, depth, token_mask):
    """``x + routed(N(x))``: the held experts' part of the mixture and no
    shared expert. Returns ``(x, expert stats, the router's chosen ids
    [B, S, k])``."""
    B, S, D = x.shape
    # the norm's float32 result feeds the router as it is (cohere2)
    h32 = rms_norm(x.astype(F32), layer["norm2"], cfg.rms_norm_eps)
    h = h32.astype(x.dtype)
    k = cfg.num_experts_per_tok
    with jax.named_scope("router"):
        logits = jnp.einsum(
            "bsd,de->bse", h32, layer["router"].astype(F32),
            precision=jax.lax.Precision.HIGHEST,
        )
        top_w, top_idx, _ = moe.route_softmax_topk(logits, k)
    routed, stats = moe.local_expert_ffn(
        h.reshape(B * S, D), top_w.reshape(B * S, k), top_idx.reshape(B * S, k),
        banks, depth, cfg.experts_held,
        None if token_mask is None else token_mask.reshape(B * S),
        num_experts=cfg.num_experts,
    )
    return x + routed.reshape(B, S, D).astype(x.dtype), stats, top_idx


def _embed(params, cfg, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)


def _head(params, cfg, x):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=F32,
    )


def forward_with_cache(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: KeyeVLConfig,
    cache: Params,  # ``generate.init_cache(cfg, ...)``
    cache_index,  # scalar int32, or [B] int32: write offset
    *,
    positions: jnp.ndarray,  # [B, S], or [3, B, S]: temporal, height, width
    kv_mask: Optional[jnp.ndarray] = None,
    lora: Optional[Params] = None,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = no token
) -> tuple[jnp.ndarray, Params]:
    """Cached forward (prefill parts and decode steps alike): returns
    (logits [B, S, V] float32, new cache). ``cache["moe_stats"]`` gains
    the call's expert counters and ``cache["sel_stats"]`` its indexers'
    (positions seen, positions attended); leaves ``"moe_topk"`` [L, B,
    positions, k] and ``"index_topk"`` [L, B, positions, topk] are filled
    if there (by the temporal stream's positions)."""
    if lora is not None:
        raise NotImplementedError("keye_vl has no adapter path yet")
    angles = _angles(cfg, positions)
    where = positions if positions.ndim == 2 else positions[0]
    x = _embed(params, cfg, tokens)
    scanned, banks = _split_banks(params["layers"])

    def layer_fn(x, layer, _lora_layer, cache, cache_layer):
        layer = llama._maybe_dequant(layer, cfg.dtype)
        mixed, cache = llama.indexed_write_and_attend(
            *_mixer_inputs(cfg, x, layer, angles), cache, cache_layer,
            cache_index, kv_mask, cfg.index_topk, positions=where,
            token_mask=token_mask,
        )
        B, S = tokens.shape
        x = x + (mixed.reshape(B, S, cfg.q_dim) @ layer["wo"].astype(x.dtype))
        x, stats, top_idx = _ffn(cfg, x, layer, banks, cache_layer.depth, token_mask)
        cache = {**cache, "moe_stats": cache["moe_stats"] + stats}
        if "moe_topk" in cache:
            cache["moe_topk"] = cache["moe_topk"].at[
                cache_layer.depth, jnp.arange(B)[:, None], where
            ].set(top_idx.astype(jnp.int32))
        return x, cache

    x, cache = llama.scan_layers_with_cache(
        layer_fn, x, scanned, None, cache, cfg.layer_kinds
    )
    return _head(params, cfg, x), cache


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: KeyeVLConfig,
    token_mask: Optional[jnp.ndarray] = None,
    positions: Optional[jnp.ndarray] = None,  # [B, S] or [3, B, S]
) -> jnp.ndarray:
    """Uncached forward over whole rows: logits [B, S, V] float32. Every
    query's selection from the scores of all its row (``lax.top_k``'s
    threshold by ``ops/select.py``'s search), attention under it as a
    mask."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    angles = _angles(cfg, positions)
    x = _embed(params, cfg, tokens)
    scanned, banks = _split_banks(params["layers"])
    k = min(cfg.index_topk, S)
    causal = sparse_attention.visible(
        jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)), None, S
    )
    for depth in range(cfg.num_layers):
        layer = llama._maybe_dequant(llama.take_layer(scanned, depth), cfg.dtype)
        q, kk, vv, qi, ki, wi = _mixer_inputs(cfg, x, layer, angles)
        scores = sparse_attention.index_scores_plain(
            qi, wi, ki.transpose(0, 2, 1)[None], 0
        )
        keep = sparse_attention.selected(
            scores, causal, *sparse_attention.select_threshold(scores, causal, k)
        )
        mixed = sparse_attention.masked_attention(q, kk, vv, keep)
        x = x + (mixed.reshape(B, S, cfg.q_dim) @ layer["wo"].astype(x.dtype))
        x, _, _ = _ffn(cfg, x, layer, banks, depth, token_mask)
    return _head(params, cfg, x)
