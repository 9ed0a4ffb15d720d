"""IBM's ``granitemoehybrid`` family (Granite 4.0-H), served.

What the block is, by the source's own keys (the plain reference,
``benchmark/reference/granitemoehybrid.py``, writes the equations out):

- ``layer_types``: nine ``mamba`` layers to one ``attention`` layer.
  ``layer_kinds`` is that period for the cache (``llama.STATE`` or
  ``None``): a Mamba-2 layer keeps NO keys and values but a state a
  slot, the float32 SSM state and the last ``mamba_d_conv - 1`` inputs
  of its convolution (``generate.init_cache``), and the one attention
  layer of the period keeps keys and values ``max_len`` long;
- every layer is SEQUENTIAL: ``x += residual_multiplier * mixer(
  RMSNorm(x))``, then ``x += residual_multiplier * (routed experts +
  shared MLP)(RMSNorm(x))``;
- the Mamba-2 mixer: one projection to ``[z | xBC | dt]``, a causal
  depthwise convolution and SiLU on ``xBC = [x | B | C]`` (one group),
  ``dt = softplus(dt + dt_bias)``, the recurrence of
  ``ops/pallas_ssm.py`` per head, ``+ D x``, a gated RMSNorm
  (``y * silu(z)``, then the norm) and the output projection. A part of
  a prompt goes through ``ssd_chunk_scan`` with the slot's state in and
  out; a decode step through ``ssm_decode_update`` on the stacked state;
- the attention layer: GQA without any positional rotation
  (``position_embedding_type: nope``), scores scaled by
  ``attention_multiplier`` (not ``head_dim ** -0.5``);
- the router is float32 and takes the ``num_experts_per_tok`` largest
  LOGITS, then a softmax over those (``route_topk_softmax``); the
  experts are ``moe.local_expert_ffn``'s, all of them held
  (``experts_held = (0, num_experts)`` unless a deployment shares them
  out);
- ``x0 = embedding_multiplier * E[tok]``, tied head, ``logits /
  logits_scaling``.

What a token that is not one (``token_mask`` False: a bucket's padding,
a slot that decodes nothing) must not do here, beyond not being routed:
move a state. Its ``dt`` is zeroed, which makes the recurrence the
identity, and the convolution's tail is taken at the row's TRUE length.
So a right-padded bucket leaves the state of the prompt's last token,
parts hand their state on, and an idle slot's state stands still.

The cached forward is the serving path (``llama.scan_layers_with_cache``
over the period of kinds). ``forward`` is the uncached form the tests
hold it against; this family has no training path (the scan has no
backward yet).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from odh_kubeflow_tpu.models import llama, moe
from odh_kubeflow_tpu.models.llama import STATE, STATE_STACKS
from odh_kubeflow_tpu.ops import pallas_ssm
from odh_kubeflow_tpu.ops.norms import rms_norm

Params = dict[str, Any]
BANKS = ("moe_gate", "moe_up", "moe_down")
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100_352
    hidden_size: int = 4096
    expert_width: int = 768  # one routed expert's SwiGLU width
    shared_width: int = 1536  # the shared MLP's
    num_layers: int = 40
    # the period of kinds: ``llama.STATE`` a Mamba-2 layer, None attention
    layer_kinds: tuple = (STATE,) * 5 + (None,) + (STATE,) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk: int = 256
    num_experts: int = 72  # the router's width
    experts_held: tuple = (0, 72)  # (first, count) held here
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.0078125
    logits_scaling: float = 16.0
    dtype: Any = jnp.bfloat16

    # ``generate.family_forward`` finds the cached forward here
    family_module = "odh_kubeflow_tpu.models.granite_hybrid"

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """Unit-test shape: two periods of (mamba, mamba, attention,
        mamba), every multiplier away from 1."""
        d = dict(
            vocab_size=256, hidden_size=64, expert_width=32, shared_width=48,
            num_layers=8, layer_kinds=(STATE, STATE, None, STATE),
            num_heads=4, num_kv_heads=2, head_dim=16, mamba_heads=8,
            mamba_head_dim=16, mamba_d_state=16, mamba_chunk=8,
            num_experts=8, experts_held=(0, 8), num_experts_per_tok=3,
            embedding_multiplier=3.0, residual_multiplier=0.5,
            attention_multiplier=0.2, logits_scaling=2.0,
        )
        d.update(kw)
        return GraniteHybridConfig(**d)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state

    def layers_of(self, kind: str) -> int:
        per = sum(llama.kind_of(k) == kind for k in self.layer_kinds)
        return per * (self.num_layers // len(self.layer_kinds))

    def state_leaves(self, dtype) -> dict:
        """A recurrent layer's state, one row of one layer: name ->
        (shape, dtype) (``generate.init_cache`` puts ``[layers, batch]``
        in front). The SSM state is float32 whatever the cache's dtype:
        it is summed into at every token of a stream."""
        ssm, conv = STATE_STACKS
        return {
            ssm: (pallas_ssm.state_shape(
                self.mamba_heads, self.mamba_head_dim, self.mamba_d_state
            ), F32),
            conv: ((self.mamba_d_conv - 1, self.conv_dim), dtype),
        }


def init_params(key: jax.Array, cfg: GraniteHybridConfig, dtype=F32) -> Params:
    """Seeded weights in the served layout: what every layer has under
    ``layers`` [L, ...], the mixers by kind under ``mamba`` [L_m, ...]
    and ``attn`` [L_a, ...], each in depth order. The recurrence's own
    parameters are drawn as the Mamba-2 reference initialises them (a
    normal draw gives a state that dies at once or never decays)."""
    D, F, Fs, L = cfg.hidden_size, cfg.expert_width, cfg.shared_width, cfg.num_layers
    E, H = cfg.experts_held[1], cfg.mamba_heads
    Lm, La = cfg.layers_of(STATE), cfg.layers_of("full")
    di, cd = cfg.d_inner, cfg.conv_dim
    k = iter(jax.random.split(key, 24))

    def dense(shape, fan_in, dt=dtype):
        return (jax.random.normal(next(k), shape, F32) * fan_in**-0.5).astype(dt)

    dt0 = jnp.exp(jax.random.uniform(
        next(k), (Lm, H), F32, jnp.log(1e-3), jnp.log(1e-1)
    ))
    return {
        "embed": dense((cfg.vocab_size, D), D),
        "layers": {
            "norm1": jnp.ones((L, D), dtype),
            "norm2": jnp.ones((L, D), dtype),
            "router": dense((L, D, cfg.num_experts), D, F32),
            "moe_gate": dense((L, E, D, F), D),
            "moe_up": dense((L, E, D, F), D),
            "moe_down": dense((L, E, F, D), F),
            "sh_gate": dense((L, D, Fs), D),
            "sh_up": dense((L, D, Fs), D),
            "sh_down": dense((L, Fs, D), Fs),
        },
        "mamba": {
            "in_proj": dense((Lm, D, 2 * di + 2 * cfg.mamba_d_state + H), D),
            "conv_w": dense((Lm, cfg.mamba_d_conv, cd), cfg.mamba_d_conv, F32),
            "conv_b": jnp.zeros((Lm, cd), F32),
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),  # softplus^-1
            "A_log": jnp.log(jax.random.uniform(next(k), (Lm, H), F32, 1.0, 16.0)),
            "D": jnp.ones((Lm, H), F32),
            "norm": jnp.ones((Lm, di), dtype),
            "out_proj": dense((Lm, di, D), di),
        },
        "attn": {
            "wq": dense((La, D, cfg.q_dim), D),
            "wk": dense((La, D, cfg.kv_dim), D),
            "wv": dense((La, D, cfg.kv_dim), D),
            "wo": dense((La, cfg.q_dim, D), cfg.q_dim),
        },
        "final_norm": jnp.ones((D,), dtype),
    }


def route_topk_softmax(router_logits: jnp.ndarray, k: int):
    """The ``k`` largest LOGITS and a softmax over those alone. Returns
    ``(weights [..., k] float32, ids [..., k])``."""
    top_l, top_idx = jax.lax.top_k(router_logits, k)
    return jax.nn.softmax(top_l.astype(F32), axis=-1), top_idx


def _uses_kernels() -> bool:
    """Whether the recurrence goes through the Pallas kernels (one TPU
    chip) or their plain forms, as the cache's read does
    (``llama._reads_cache_in_place``)."""
    am = jax.sharding.get_abstract_mesh()
    return jax.default_backend() == "tpu" and (am.empty or am.size == 1)


def _mamba_mixer(cfg, h, mw, state, token_mask):
    """A Mamba-2 mixer up to its recurrence, on ``h`` [B, S, D] with
    ``state`` the row's last ``K - 1`` convolution inputs [B, K - 1,
    conv_dim]: returns ``(z, x [B, S, H, P], dt [B, S, H] float32 with
    masked positions zeroed, A [H], B, C [B, S, N], the new
    convolution tail)``. The recurrence itself is the caller's: a
    part's scan or a decode step's update. ``mw`` is dequantised."""
    B, S, _ = h.shape
    di, N, H, P = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_heads, cfg.mamba_head_dim
    K = cfg.mamba_d_conv
    # one plain [B, S, width] matrix up to the barrier: without it XLA
    # carries the split below onto the weight (PERF.md, PR 26)
    zxbcdt = jax.lax.optimization_barrier(h @ mw["in_proj"].astype(h.dtype))
    z, xBC, dt = jnp.split(zxbcdt, [di, di + cfg.conv_dim], axis=-1)
    with jax.named_scope("mamba_conv"):
        # the slot's last K - 1 inputs, then this call's
        cat = jnp.concatenate([state.astype(xBC.dtype), xBC], axis=1)
        conv = sum(
            cat[:, j:j + S].astype(F32) * mw["conv_w"][j] for j in range(K)
        ) + mw["conv_b"]
        xBC = jax.nn.silu(conv).astype(h.dtype)
        # the tail at each row's TRUE length: padding is not an input
        n_real = (
            jnp.full((B,), S, jnp.int32) if token_mask is None
            else jnp.sum(token_mask, axis=1, dtype=jnp.int32)
        )
        tail = jnp.take_along_axis(
            cat, (n_real[:, None] + jnp.arange(K - 1))[:, :, None], axis=1
        )
    x, Bm, Cm = jnp.split(xBC, [di, di + N], axis=-1)
    dt = jax.nn.softplus(dt.astype(F32) + mw["dt_bias"])
    if token_mask is not None:
        dt = jnp.where(token_mask[..., None], dt, 0.0)
    return z, x.reshape(B, S, H, P), dt, -jnp.exp(mw["A_log"]), Bm, Cm, tail


def _mamba_out(cfg, y, x, z, mw):
    """``+ D x``, the gated norm (float32 statistics) and the output
    projection. ``y``, ``x`` [B, S, H, P]."""
    B, S, H, P = x.shape
    y = y.astype(F32) + mw["D"][:, None] * x.astype(F32)
    y = y.reshape(B, S, H * P) * jax.nn.silu(z.astype(F32))
    y = rms_norm(y, mw["norm"], cfg.rms_norm_eps).astype(cfg.dtype)
    return y @ mw["out_proj"].astype(y.dtype)


def _mamba_cached(cfg, h, mw, cache, cache_layer, token_mask):
    """A Mamba-2 layer's mixer through the cache's state stacks."""
    ssm_name, conv_name = cache_layer.names
    at = cache_layer.index
    ssm, conv = cache[ssm_name], cache[conv_name]
    mw = llama._maybe_dequant(mw, cfg.dtype)
    z, x, dt, A, Bm, Cm, tail = _mamba_mixer(
        cfg, h, mw, jax.lax.dynamic_index_in_dim(conv, at, 0, False), token_mask
    )
    if h.shape[1] == 1:
        step = (
            pallas_ssm.ssm_decode_update if _uses_kernels()
            else pallas_ssm.ssm_step_plain
        )
        y, ssm = step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], ssm, at)
        y = y[:, None]
    else:
        init = jax.lax.dynamic_index_in_dim(ssm, at, 0, False)
        if _uses_kernels():
            y, fin = pallas_ssm.ssd_chunk_scan(
                x, dt, A, Bm, Cm, init, chunk=cfg.mamba_chunk
            )
        else:
            y, fin = pallas_ssm.ssm_scan_plain(x, dt, A, Bm, Cm, init)
        ssm = jax.lax.dynamic_update_index_in_dim(ssm, fin, at, 0)
    conv = jax.lax.dynamic_update_index_in_dim(
        conv, tail.astype(conv.dtype), at, 0
    )
    return _mamba_out(cfg, y, x, z, mw), {**cache, ssm_name: ssm, conv_name: conv}


def _attention(cfg, h, aw, attend):
    """NoPE GQA: ``attend(q, k, v)`` is the caller's attention, which
    scales by ``head_dim ** -0.5``; the source's ``attention_multiplier``
    goes onto q in float32, before its one rounding."""
    B, S, _ = h.shape
    aw = llama._maybe_dequant(aw, cfg.dtype)
    q, kk, vv = jax.lax.optimization_barrier((
        jnp.dot(h, aw["wq"].astype(h.dtype), preferred_element_type=F32),
        h @ aw["wk"].astype(h.dtype), h @ aw["wv"].astype(h.dtype),
    ))
    q = (q * (cfg.attention_multiplier * cfg.head_dim**0.5)).astype(h.dtype)
    attn, carried = attend(
        q.reshape(B, S, cfg.num_heads, cfg.head_dim),
        kk.reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
        vv.reshape(B, S, cfg.num_kv_heads, cfg.head_dim),
    )
    return attn.reshape(B, S, cfg.q_dim) @ aw["wo"].astype(h.dtype), carried


def _ffn(cfg, x, layer, banks, depth, token_mask):
    """``x + residual_multiplier * (routed + shared)(RMSNorm(x))``.
    Returns ``(x, expert stats, the router's chosen ids [B, S, k])``."""
    B, S, D = x.shape
    layer = llama._maybe_dequant(layer, cfg.dtype)
    # the norm's float32 result feeds the router as it is (cohere2)
    h32 = rms_norm(x.astype(F32), layer["norm2"], cfg.rms_norm_eps)
    h = h32.astype(x.dtype)
    with jax.named_scope("router"):
        logits = jnp.einsum(
            "bsd,de->bse", h32, layer["router"].astype(F32),
            precision=jax.lax.Precision.HIGHEST,
        )
        top_w, top_idx = route_topk_softmax(logits, cfg.num_experts_per_tok)
    k = cfg.num_experts_per_tok
    routed, stats = moe.local_expert_ffn(
        h.reshape(B * S, D), top_w.reshape(B * S, k), top_idx.reshape(B * S, k),
        banks, depth, cfg.experts_held,
        None if token_mask is None else token_mask.reshape(B * S),
    )
    with jax.named_scope("shared_mlp"):
        act = jax.nn.silu(h @ layer["sh_gate"].astype(h.dtype)) * (
            h @ layer["sh_up"].astype(h.dtype)
        )
        shared = act @ layer["sh_down"].astype(h.dtype)
    y = routed.reshape(B, S, D).astype(F32) + shared.astype(F32)
    return (
        x + (cfg.residual_multiplier * y).astype(x.dtype), stats, top_idx,
    )


def _split_banks(layers: Params):
    banks = {n: layers[n] for n in BANKS}
    return {n: v for n, v in layers.items() if n not in BANKS}, banks


def _embed(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    return (cfg.embedding_multiplier * x).astype(cfg.dtype)


def _head(params, cfg, x):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return jnp.einsum(
        "bsd,vd->bsv", x, params["embed"].astype(cfg.dtype),
        preferred_element_type=F32,
    ) / cfg.logits_scaling


def forward_with_cache(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: GraniteHybridConfig,
    cache: Params,  # ``generate.init_cache(cfg, ...)``
    cache_index,  # scalar int32, or [B] int32: write offset
    *,
    positions: jnp.ndarray,  # [B, S]
    kv_mask: Optional[jnp.ndarray] = None,
    lora: Optional[Params] = None,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = no token
) -> tuple[jnp.ndarray, Params]:
    """Cached forward (prefill parts and decode steps alike): returns
    (logits [B, S, V] float32, new cache). A row's tokens CONTINUE the
    state the cache holds for it, whatever ``cache_index`` says: a fresh
    stream starts from a zeroed row (the engine splices one in). A
    ``token_mask`` row must be a run of True then False. As in
    ``cohere2``: ``cache["moe_stats"]`` gains the call's expert counters,
    and a leaf ``"moe_topk"`` [L, B, positions, k] is filled if there."""
    if lora is not None:
        raise NotImplementedError("granitemoehybrid has no adapter path yet")
    if tokens.shape[1] > 1 and getattr(cache_index, "ndim", 0) == 1:
        raise NotImplementedError(
            "several tokens a row at per-row offsets (speculative verify) "
            "would need the state after each of them"
        )
    x = _embed(params, cfg, tokens)
    scanned, banks = _split_banks(params["layers"])

    def layer_fn(x, layer, _lora_layer, cache, cache_layer):
        h = rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
        if cache_layer.names == STATE_STACKS:
            mixed, cache = _mamba_cached(
                cfg, h, llama.take_layer(params["mamba"], cache_layer.index), cache,
                cache_layer, token_mask,
            )
        else:
            def attend(q, kk, vv):
                return llama.cache_write_and_attend(
                    q, kk, vv, cache, cache_layer, cache_index, kv_mask
                )

            mixed, cache = _attention(
                cfg, h, llama.take_layer(params["attn"], cache_layer.index), attend
            )
        x = x + (cfg.residual_multiplier * mixed.astype(F32)).astype(x.dtype)
        x, stats, top_idx = _ffn(
            cfg, x, layer, banks, cache_layer.depth, token_mask
        )
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
    cfg: GraniteHybridConfig,
    token_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Uncached forward over whole rows, the recurrence token by token:
    logits [B, S, V] float32."""
    from odh_kubeflow_tpu.ops.attention import dense_attention

    B, S = tokens.shape
    x = _embed(params, cfg, tokens)
    scanned, banks = _split_banks(params["layers"])
    leaves = cfg.state_leaves(cfg.dtype)
    zeros = [jnp.zeros((B,) + shape, dt) for shape, dt in leaves.values()]
    seen = {STATE: 0, "full": 0}
    for depth in range(cfg.num_layers):
        kind = llama.kind_of(cfg.layer_kinds[depth % len(cfg.layer_kinds)])
        layer = llama.take_layer(scanned, depth)
        h = rms_norm(x, layer["norm1"], cfg.rms_norm_eps)
        if kind == STATE:
            mw = llama._maybe_dequant(
                llama.take_layer(params["mamba"], seen[kind]), cfg.dtype
            )
            z, xs, dt, A, Bm, Cm, _ = _mamba_mixer(
                cfg, h, mw, zeros[1], token_mask
            )
            y, _ = pallas_ssm.ssm_scan_plain(xs, dt, A, Bm, Cm, zeros[0])
            mixed = _mamba_out(cfg, y, xs, z, mw)
        else:
            mixed, _ = _attention(
                cfg, h, llama.take_layer(params["attn"], seen[kind]),
                lambda q, kk, vv: (dense_attention(q, kk, vv, causal=True), None),
            )
        seen[kind] += 1
        x = x + (cfg.residual_multiplier * mixed.astype(F32)).astype(x.dtype)
        x, _, _ = _ffn(cfg, x, layer, banks, depth, token_mask)
    return _head(params, cfg, x)
