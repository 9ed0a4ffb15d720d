"""Qwen's ``qwen3_next`` family (Qwen3-Next-80B-A3B), served.

What the block is, by the source's own keys (the plain reference,
``benchmark/reference/qwen3_next.py``, writes the equations out):

- ``full_attention_interval`` 4: three Gated DeltaNet layers to one
  gated-attention layer. ``layer_kinds`` is that period for the cache
  (``llama.STATE`` or ``None``): a DeltaNet layer keeps NO keys and
  values but a state a slot, the float32 delta-rule state ``[H, d_k,
  d_v]`` and the last ``conv_kernel - 1`` inputs of its convolution
  (``generate.init_cache``, the ``("ssm", "conv")`` stacks of
  ``llama.CACHE_KINDS`` at this family's shapes), and the attention
  layer of the period keeps keys and values ``max_len`` long;
- every layer is sequential and pre-norm with the family's ZERO-CENTRED
  RMSNorm, ``x * rsqrt(mean(x^2) + eps) * (1 + w)``: ``rms_norm`` with
  ``1 + w`` passed as the weight;
- the DeltaNet mixer: ``[q | k | v | z] = h W_qkvz``, ``[b | a] = h
  W_ba`` (float32), a causal depthwise convolution and SiLU over ``[q |
  k | v]`` (no bias), l2-normalised ``q`` (scaled by ``d_k ** -0.5``)
  and ``k``, ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a +
  dt_bias)``, the recurrence of ``ops/pallas_gdn.py`` per value head
  (two to a key head), then the norm and THEN the gate (``rms_norm(o) *
  w * silu(z)`` per head, a plain weight) and the output projection. A
  part of a prompt goes through ``gdn_chunk_scan`` with the slot's
  state in and out; a decode step through ``gdn_decode_update`` on the
  stacked state;
- the attention layer: ``q_proj`` twice as wide (per head ``[query |
  gate]``), per-head zero-centred norms on query and key, rotation of
  the first ``partial_rotary_factor`` of the head's dims (half-split
  pairs), GQA, ``sigmoid(gate) * attn`` before ``o_proj``;
- the experts: a float32 softmax over ALL ``num_experts``, the
  ``num_experts_per_tok`` largest, renormalised
  (``moe.route_softmax_topk``); ``moe.local_expert_ffn`` on the experts
  held here (``experts_held``), its row tile sized by the router's
  width; beside them ``sigmoid(h w_sg) * shared(h)``, every chip's alike;
- ``x0 = E[tok]``, an untied head over this chip's rows of the
  vocabulary. The published multi-token-prediction module is not here.

What a token that is not one (``token_mask`` False: a bucket's padding,
a slot that decodes nothing) must not do, beyond not being routed: move
a state. Its ``g`` and ``beta`` are zeroed, which makes the delta rule
the identity, and the convolution's tail is taken at the row's TRUE
length (``granite_hybrid``'s rule).

The cached forward is the serving path (``llama.scan_layers_with_cache``
over the period of kinds). ``forward`` is the uncached form the tests
hold it against; the family has no training path (neither scan has a
backward).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from odh_kubeflow_tpu.models import llama, moe
from odh_kubeflow_tpu.models.granite_hybrid import _split_banks, _uses_kernels
from odh_kubeflow_tpu.models.llama import STATE, STATE_STACKS
from odh_kubeflow_tpu.ops import pallas_gdn
from odh_kubeflow_tpu.ops.norms import rms_norm
from odh_kubeflow_tpu.ops.rope import apply_rope, rope_angles

Params = dict[str, Any]
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151_936  # the rows of the vocabulary held here
    hidden_size: int = 2048
    num_layers: int = 48
    # the period of kinds: ``llama.STATE`` a DeltaNet layer, None attention
    layer_kinds: tuple = (STATE, STATE, STATE, None)
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    gdn_key_heads: int = 16
    gdn_value_heads: int = 32
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4
    gdn_chunk: int = pallas_gdn.DEFAULT_CHUNK
    num_experts: int = 512  # the router's width
    experts_held: tuple = (0, 512)  # (first, count) held here
    num_experts_per_tok: int = 10
    expert_width: int = 512  # one routed expert's SwiGLU width
    shared_width: int = 512  # the shared expert's
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    # ``generate.family_forward`` finds the cached forward here
    family_module = "odh_kubeflow_tpu.models.qwen3_next"

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """Unit-test shape: two periods of (linear, linear, linear,
        full), 2 key heads onto 4 value heads, 16 experts of which the
        first 8 are held."""
        d = dict(
            vocab_size=256, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16, rope_theta=10_000.0,
            gdn_key_heads=2, gdn_value_heads=4, gdn_key_dim=8, gdn_value_dim=16,
            gdn_chunk=8, num_experts=16, experts_held=(0, 8),
            num_experts_per_tok=3, expert_width=32, shared_width=48,
        )
        d.update(kw)
        return Qwen3NextConfig(**d)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.gdn_key_heads * self.gdn_key_dim

    @property
    def value_dim(self) -> int:
        return self.gdn_value_heads * self.gdn_value_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    def layers_of(self, kind: str) -> int:
        per = sum(llama.kind_of(k) == kind for k in self.layer_kinds)
        return per * (self.num_layers // len(self.layer_kinds))

    def state_leaves(self, dtype) -> dict:
        """A DeltaNet layer's state, one row of one layer: name ->
        (shape, dtype) (``generate.init_cache`` puts ``[layers, batch]``
        in front). The delta-rule state is float32 whatever the cache's
        dtype: every token of a stream reads and corrects it."""
        state, conv = STATE_STACKS
        return {
            # as ``ops/pallas_gdn.py`` lays it: d_v along the lanes
            state: (
                (self.gdn_value_heads, self.gdn_key_dim, self.gdn_value_dim), F32
            ),
            conv: ((self.gdn_conv - 1, self.conv_dim), dtype),
        }


def init_params(key: jax.Array, cfg: Qwen3NextConfig, dtype=F32) -> Params:
    """Seeded weights in the served layout: what every layer has under
    ``layers`` [L, ...], the mixers by kind under ``gdn`` [L_g, ...] and
    ``attn`` [L_a, ...], each in depth order. Norm weights ``w`` are
    drawn small and NOT zero, so that ``1 + w`` shows; the recurrence's
    own parameters as the reference implementation initialises them."""
    D, F, Fs, L = cfg.hidden_size, cfg.expert_width, cfg.shared_width, cfg.num_layers
    E, H = cfg.experts_held[1], cfg.gdn_value_heads
    Lg, La = cfg.layers_of(STATE), cfg.layers_of("full")
    k = iter(jax.random.split(key, 32))

    def dense(shape, fan_in, dt=dtype):
        return (jax.random.normal(next(k), shape, F32) * fan_in**-0.5).astype(dt)

    def small(shape):
        return (0.1 * jax.random.normal(next(k), shape, F32)).astype(dtype)

    dt0 = jnp.exp(jax.random.uniform(
        next(k), (Lg, H), F32, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return {
        "embed": dense((cfg.vocab_size, D), D),
        "lm_head": dense((D, cfg.vocab_size), D),
        "layers": {
            "norm1": small((L, D)),
            "norm2": small((L, D)),
            "router": dense((L, D, cfg.num_experts), D, F32),
            "moe_gate": dense((L, E, D, F), D),
            "moe_up": dense((L, E, D, F), D),
            "moe_down": dense((L, E, F, D), F),
            "sh_gate": dense((L, D, Fs), D),
            "sh_up": dense((L, D, Fs), D),
            "sh_down": dense((L, Fs, D), Fs),
            "sh_scale": dense((L, D), D, F32),  # w_sg: the shared expert's gate
        },
        "gdn": {
            "in_qkvz": dense((Lg, D, cfg.conv_dim + cfg.value_dim), D),
            "in_ba": dense((Lg, D, 2 * H), D, F32),
            "conv_w": dense((Lg, cfg.gdn_conv, cfg.conv_dim), cfg.gdn_conv, F32),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1
            "A_log": jnp.log(jax.random.uniform(next(k), (Lg, H), F32, 1.0, 16.0)),
            "norm": 1 + small((Lg, cfg.gdn_value_dim)),
            "out_proj": dense((Lg, cfg.value_dim, D), cfg.value_dim),
        },
        "attn": {
            "wq": dense((La, D, 2 * cfg.q_dim), D),
            "wk": dense((La, D, cfg.kv_dim), D),
            "wv": dense((La, D, cfg.kv_dim), D),
            "wo": dense((La, cfg.q_dim, D), cfg.q_dim),
            "q_norm": small((La, cfg.head_dim)),
            "k_norm": small((La, cfg.head_dim)),
        },
        "final_norm": small((D,)),
    }


def norm(x, w, eps):
    """The family's zero-centred RMSNorm."""
    return rms_norm(x, 1 + w.astype(F32), eps)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def _gdn_mixer(cfg, h, gw, tail_in, token_mask):
    """A DeltaNet mixer up to its recurrence, on ``h`` [B, S, D] with
    ``tail_in`` the row's last ``K - 1`` convolution inputs [B, K - 1,
    conv_dim]: returns ``(z [B, S, H, dv], q, k [B, S, Hk, dk], v [B, S,
    H, dv], g, beta [B, S, H] float32 with masked positions zeroed, the
    new convolution tail)``. ``gw`` is dequantised."""
    B, S, _ = h.shape
    Hk, H, dk, dv = (
        cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
    )
    K = cfg.gdn_conv
    # one plain [B, S, width] matrix up to the barrier: without it XLA
    # carries the split below onto the weight (PERF.md, PR 26)
    qkvz = jax.lax.optimization_barrier(h @ gw["in_qkvz"].astype(h.dtype))
    ba = jnp.dot(h.astype(F32), gw["in_ba"], precision=jax.lax.Precision.HIGHEST)
    qkv, z = jnp.split(qkvz, [cfg.conv_dim], axis=-1)
    with jax.named_scope("gdn_conv"):
        # the slot's last K - 1 inputs, then this call's
        cat = jnp.concatenate([tail_in.astype(qkv.dtype), qkv], axis=1)
        conv = sum(cat[:, j:j + S].astype(F32) * gw["conv_w"][j] for j in range(K))
        qkv = jax.nn.silu(conv)
        # the tail at each row's TRUE length: padding is not an input
        n_real = (
            jnp.full((B,), S, jnp.int32) if token_mask is None
            else jnp.sum(token_mask, axis=1, dtype=jnp.int32)
        )
        tail = jnp.take_along_axis(
            cat, (n_real[:, None] + jnp.arange(K - 1))[:, :, None], axis=1
        )
    q, k, v = jnp.split(qkv, [cfg.key_dim, 2 * cfg.key_dim], axis=-1)
    q = (_l2norm(q.reshape(B, S, Hk, dk)) * dk**-0.5).astype(h.dtype)
    k = _l2norm(k.reshape(B, S, Hk, dk)).astype(h.dtype)
    v = v.reshape(B, S, H, dv).astype(h.dtype)
    b, a = jnp.split(ba, 2, axis=-1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(gw["A_log"]) * jax.nn.softplus(a + gw["dt_bias"])
    if token_mask is not None:
        g = jnp.where(token_mask[..., None], g, 0.0)
        beta = jnp.where(token_mask[..., None], beta, 0.0)
    return z.reshape(B, S, H, dv), q, k, v, g, beta, tail


def _gdn_out(cfg, o, z, gw):
    """The norm per head (float32 statistics), THEN the gate, and the
    output projection. ``o``, ``z`` [B, S, H, dv]."""
    B, S, H, dv = o.shape
    y = rms_norm(o.astype(F32), gw["norm"], cfg.rms_norm_eps)
    y = (y * jax.nn.silu(z.astype(F32))).astype(cfg.dtype)
    return y.reshape(B, S, H * dv) @ gw["out_proj"].astype(y.dtype)


def _gdn_cached(cfg, h, gw, cache, cache_layer, token_mask):
    """A DeltaNet layer's mixer through the cache's state stacks."""
    state_name, conv_name = cache_layer.names
    at = cache_layer.index
    state, conv = cache[state_name], cache[conv_name]
    gw = llama._maybe_dequant(gw, cfg.dtype)
    z, q, k, v, g, beta, tail = _gdn_mixer(
        cfg, h, gw, jax.lax.dynamic_index_in_dim(conv, at, 0, False), token_mask
    )
    if h.shape[1] == 1:
        step = (
            pallas_gdn.gdn_decode_update if _uses_kernels()
            else pallas_gdn.gdn_step_plain
        )
        o, state = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state, at)
        o = o[:, None]
    else:
        init = jax.lax.dynamic_index_in_dim(state, at, 0, False)
        if _uses_kernels():
            o, fin = pallas_gdn.gdn_chunk_scan(
                q, k, v, g, beta, init, chunk=cfg.gdn_chunk
            )
        else:
            o, fin = pallas_gdn.gdn_scan_plain(q, k, v, g, beta, init)
        state = jax.lax.dynamic_update_index_in_dim(state, fin, at, 0)
    conv = jax.lax.dynamic_update_index_in_dim(
        conv, tail.astype(conv.dtype), at, 0
    )
    return _gdn_out(cfg, o, z, gw), {**cache, state_name: state, conv_name: conv}


def _attention(cfg, h, aw, sin, cos, attend):
    """Gated GQA with partial rotation: ``attend(q, k, v)`` is the
    caller's attention (scores scaled by ``head_dim ** -0.5``)."""
    B, S, _ = h.shape
    aw = llama._maybe_dequant(aw, cfg.dtype)
    qg, kk, vv = jax.lax.optimization_barrier((
        h @ aw["wq"].astype(h.dtype), h @ aw["wk"].astype(h.dtype),
        h @ aw["wv"].astype(h.dtype),
    ))
    # per head: [query | gate]
    q, gate = jnp.split(qg.reshape(B, S, cfg.num_heads, 2 * cfg.head_dim), 2, -1)
    q = norm(q, aw["q_norm"], cfg.rms_norm_eps)
    kk = norm(
        kk.reshape(B, S, cfg.num_kv_heads, cfg.head_dim), aw["k_norm"],
        cfg.rms_norm_eps,
    )
    attn, carried = attend(
        apply_rope(q, sin, cos), apply_rope(kk, sin, cos),
        vv.reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
    )
    attn = (attn.astype(F32) * jax.nn.sigmoid(gate.astype(F32))).astype(h.dtype)
    return attn.reshape(B, S, cfg.q_dim) @ aw["wo"].astype(h.dtype), carried


def _ffn(cfg, x, layer, banks, depth, token_mask):
    """``x + (routed + sigmoid(h w_sg) * shared)(N(x))``. Returns ``(x,
    expert stats, the router's chosen ids [B, S, k])``."""
    B, S, D = x.shape
    layer = llama._maybe_dequant(layer, cfg.dtype)
    # the norm's float32 result feeds the router as it is (cohere2)
    h32 = norm(x.astype(F32), layer["norm2"], cfg.rms_norm_eps)
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
    with jax.named_scope("shared_expert"):
        act = jax.nn.silu(h @ layer["sh_gate"].astype(h.dtype)) * (
            h @ layer["sh_up"].astype(h.dtype)
        )
        shared = (act @ layer["sh_down"].astype(h.dtype)).astype(F32)
        shared = shared * jax.nn.sigmoid(jnp.einsum(
            "bsd,d->bs", h32, layer["sh_scale"].astype(F32),
            precision=jax.lax.Precision.HIGHEST,
        ))[..., None]
    y = routed.reshape(B, S, D).astype(F32) + shared
    return x + y.astype(x.dtype), stats, top_idx


def _embed(params, cfg, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)


def _head(params, cfg, x):
    x = norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum(
        "bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype),
        preferred_element_type=F32,
    )


def forward_with_cache(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: Qwen3NextConfig,
    cache: Params,  # ``generate.init_cache(cfg, ...)``
    cache_index,  # scalar int32, or [B] int32: write offset
    *,
    positions: jnp.ndarray,  # [B, S]
    kv_mask: Optional[jnp.ndarray] = None,
    lora: Optional[Params] = None,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = no token
) -> tuple[jnp.ndarray, Params]:
    """Cached forward (prefill parts and decode steps alike): returns
    (logits [B, S, V] float32, new cache). As ``granite_hybrid``'s: a
    row's tokens CONTINUE the state the cache holds for it, a
    ``token_mask`` row must be a run of True then False,
    ``cache["moe_stats"]`` gains the call's expert counters, and a leaf
    ``"moe_topk"`` [L, B, positions, k] is filled if there."""
    if lora is not None:
        raise NotImplementedError("qwen3_next has no adapter path yet")
    if tokens.shape[1] > 1 and getattr(cache_index, "ndim", 0) == 1:
        raise NotImplementedError(
            "several tokens a row at per-row offsets (speculative verify) "
            "would need the state after each of them"
        )
    sin, cos = rope_angles(positions, cfg.rotary_dim, cfg.rope_theta)
    x = _embed(params, cfg, tokens)
    scanned, banks = _split_banks(params["layers"])

    def layer_fn(x, layer, _lora_layer, cache, cache_layer):
        h = norm(x, layer["norm1"], cfg.rms_norm_eps)
        if cache_layer.names == STATE_STACKS:
            mixed, cache = _gdn_cached(
                cfg, h, llama.take_layer(params["gdn"], cache_layer.index), cache,
                cache_layer, token_mask,
            )
        else:
            def attend(q, kk, vv):
                return llama.cache_write_and_attend(
                    q, kk, vv, cache, cache_layer, cache_index, kv_mask
                )

            mixed, cache = _attention(
                cfg, h, llama.take_layer(params["attn"], cache_layer.index),
                sin, cos, attend,
            )
        x = x + mixed.astype(x.dtype)
        x, stats, top_idx = _ffn(cfg, x, layer, banks, cache_layer.depth, token_mask)
        cache = {**cache, "moe_stats": cache["moe_stats"] + stats}
        if "moe_topk" in cache:
            rows = jnp.arange(x.shape[0])[:, None]
            cache["moe_topk"] = cache["moe_topk"].at[
                cache_layer.depth, rows, positions
            ].set(top_idx.astype(jnp.int32))
        return x, cache

    x, cache = llama.scan_layers_with_cache(
        layer_fn, x, scanned, None, cache, cfg.layer_kinds
    )
    return _head(params, cfg, x), cache


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: Qwen3NextConfig,
    token_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Uncached forward over whole rows, the recurrence token by token:
    logits [B, S, V] float32."""
    from odh_kubeflow_tpu.ops.attention import dense_attention

    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sin, cos = rope_angles(positions, cfg.rotary_dim, cfg.rope_theta)
    x = _embed(params, cfg, tokens)
    scanned, banks = _split_banks(params["layers"])
    leaves = cfg.state_leaves(cfg.dtype)
    zeros = [jnp.zeros((B,) + shape, dt) for shape, dt in leaves.values()]
    seen = {STATE: 0, "full": 0}
    for depth in range(cfg.num_layers):
        kind = llama.kind_of(cfg.layer_kinds[depth % len(cfg.layer_kinds)])
        layer = llama.take_layer(scanned, depth)
        h = norm(x, layer["norm1"], cfg.rms_norm_eps)
        if kind == STATE:
            gw = llama._maybe_dequant(
                llama.take_layer(params["gdn"], seen[kind]), cfg.dtype
            )
            z, q, k, v, g, beta, _ = _gdn_mixer(cfg, h, gw, zeros[1], token_mask)
            o, _ = pallas_gdn.gdn_scan_plain(q, k, v, g, beta, zeros[0])
            mixed = _gdn_out(cfg, o, z, gw)
        else:
            mixed, _ = _attention(
                cfg, h, llama.take_layer(params["attn"], seen[kind]), sin, cos,
                lambda q, kk, vv: (dense_attention(q, kk, vv, causal=True), None),
            )
        seen[kind] += 1
        x = x + mixed.astype(x.dtype)
        x, _, _ = _ffn(cfg, x, layer, banks, depth, token_mask)
    return _head(params, cfg, x)
