"""Cohere's ``cohere2_moe`` family (Command A+), served.

What the block is, by the source's own keys (the plain reference,
``benchmark/reference/cohere2_moe.py``, writes the equations out):

- ``use_parallel_block``: ONE LayerNorm (mean-subtracting, no bias) a
  layer feeds attention and the experts side by side, and the layer adds
  ``attention + routed experts + shared experts`` to its input;
- ``layer_types``: three ``sliding_attention`` layers (keys rotated on
  INTERLEAVED pairs, ``rope_gptj``; a query sees the ``sliding_window``
  positions that end at its own) to one ``full_attention`` layer (NO
  rotation, every position): ``layer_windows`` is that period, and it is
  what gives the cache two kinds of stack (``generate.init_cache``);
- ``expert_selection_fn: sigmoid`` with ``norm_topk_prob``: the router's
  matmul and scores run in float32 (``moe.route_sigmoid_topk``);
- ``num_shared_experts`` beside the routed ones, their outputs averaged
  (``shared_expert_combination_strategy: average``) and ADDED; stored
  side by side as one wide SwiGLU, which is the same sum;
- ``experts_held = (first, count)``: the routed experts THIS chip holds
  of the layer's ``num_experts``. The router keeps its published width;
  the layer computes its own experts' part (``moe.local_expert_ffn``),
  on one chip without the exchange that would bring the other chips'
  tokens and take away its own. Nothing stands in for absent chips;
- tied head (``tie_word_embeddings``), ``logit_scale``.

The cached forward is the serving path: ``llama.scan_layers_with_cache``
over the stack's periods with both kinds of cache stack as its carry,
``llama.cache_write_and_attend`` for every layer. The expert banks do
not ride the scan as sliced inputs: they stay stacked and the kernel
takes the layer's index (``ops/pallas_moe_local.py``). ``forward`` is
the uncached form the tests hold it against; this family has no
training path yet (ROADMAP M2/M3).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from odh_kubeflow_tpu.models import llama, moe
from odh_kubeflow_tpu.ops.attention import dense_attention
from odh_kubeflow_tpu.ops.norms import layer_norm
from odh_kubeflow_tpu.ops.rope import apply_rope_interleaved, rope_angles

Params = dict[str, Any]
BANKS = ("moe_gate", "moe_up", "moe_down")


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262_144
    hidden_size: int = 4096
    expert_width: int = 4096  # one expert's (routed or shared) SwiGLU width
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 50_000.0
    layer_norm_eps: float = 1e-5
    # the period of kinds: an int is a window layer (rotated keys), None
    # a global one (no rotation)
    layer_windows: tuple = (4096, 4096, 4096, None)
    num_experts: int = 128  # the router's width
    # (first, count): the routed experts held here
    experts_held: tuple = (0, 128)
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    norm_topk_prob: bool = True
    logit_scale: float = 1.0
    dtype: Any = jnp.bfloat16

    # ``generate.family_forward`` finds the cached forward here
    family_module = "odh_kubeflow_tpu.models.cohere2"

    @staticmethod
    def tiny(**kw) -> "Cohere2MoeConfig":
        """Unit-test shape: two periods, a window of 8."""
        d = dict(
            vocab_size=256, hidden_size=64, expert_width=32, num_layers=8,
            num_heads=8, num_kv_heads=2, head_dim=16,
            layer_windows=(8, 8, 8, None), num_experts=16,
            experts_held=(0, 16), num_experts_per_tok=4, num_shared_experts=2,
        )
        d.update(kw)
        return Cohere2MoeConfig(**d)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim


def init_params(key: jax.Array, cfg: Cohere2MoeConfig, dtype=jnp.float32) -> Params:
    D, F, L = cfg.hidden_size, cfg.expert_width, cfg.num_layers
    E, Fs = cfg.experts_held[1], cfg.num_shared_experts * cfg.expert_width
    k = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, dt=dtype):
        return (
            jax.random.normal(next(k), shape, jnp.float32) * fan_in**-0.5
        ).astype(dt)

    return {
        "embed": dense((cfg.vocab_size, D), D),
        "layers": {
            "norm": jnp.ones((L, D), dtype),
            "wq": dense((L, D, cfg.q_dim), D),
            "wk": dense((L, D, cfg.kv_dim), D),
            "wv": dense((L, D, cfg.kv_dim), D),
            "wo": dense((L, cfg.q_dim, D), cfg.q_dim),
            "router": dense((L, D, cfg.num_experts), D, jnp.float32),
            "moe_gate": dense((L, E, D, F), D),
            "moe_up": dense((L, E, D, F), D),
            "moe_down": dense((L, E, F, D), F),
            "sh_gate": dense((L, D, Fs), D),
            "sh_up": dense((L, D, Fs), D),
            "sh_down": dense((L, Fs, D), F),
        },
        "final_norm": jnp.ones((D,), dtype),
    }


def _block(cfg, x, layer, banks, depth, rotate, attend, sin, cos, token_mask):
    """One parallel block: ``x + attention + routed + shared`` from one
    norm. ``attend(q, k, v)`` is the caller's attention (cached or
    not). Returns ``(x, attend's second result, expert stats, the
    router's chosen ids [B, S, k])``."""
    B, S, D = x.shape
    layer = llama._maybe_dequant(layer, cfg.dtype)
    # the norm's float32 result feeds the router as it is; only the
    # copy the matmuls take is rounded to the activations' dtype
    h32 = layer_norm(x.astype(jnp.float32), layer["norm"], cfg.layer_norm_eps)
    h = h32.astype(x.dtype)
    # the projections' outputs stay plain [B, S, width] matrices up to
    # this barrier: without it XLA folds the split into heads (and the
    # rotation's lane shuffles) into the projection, whose weight it
    # then wants transposed and dequantised in HBM, 134 MB a layer a
    # step for wq at 128 heads (PERF.md, PR 26)
    q, kk, vv = jax.lax.optimization_barrier((
        h @ layer["wq"].astype(h.dtype), h @ layer["wk"].astype(h.dtype),
        h @ layer["wv"].astype(h.dtype),
    ))
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    kk = kk.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    vv = vv.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if rotate:
        q = apply_rope_interleaved(q, sin, cos)
        kk = apply_rope_interleaved(kk, sin, cos)
    attn, carried = attend(q, kk, vv)
    attn = attn.reshape(B, S, cfg.q_dim) @ layer["wo"].astype(h.dtype)

    with jax.named_scope("router"):
        # float32 throughout, from the norm's unrounded result: the
        # eight largest of 128 sigmoids are close together, and a bf16
        # product, or a bf16 copy of the norm, would choose other experts
        # than the reference does. In the first layer the norm's input is
        # the token's embedding alone, so a choice that the rounding tips
        # is tipped at EVERY occurrence of that token (PERF.md, PR 26)
        logits = jnp.einsum(
            "bsd,de->bse", h32,
            layer["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        top_w, top_idx = moe.route_sigmoid_topk(
            logits, cfg.num_experts_per_tok, cfg.norm_topk_prob
        )
    k = cfg.num_experts_per_tok
    routed, stats = moe.local_expert_ffn(
        h.reshape(B * S, D), top_w.reshape(B * S, k), top_idx.reshape(B * S, k),
        banks, depth, cfg.experts_held,
        None if token_mask is None else token_mask.reshape(B * S),
    )
    with jax.named_scope("shared_experts"):
        act = jax.nn.silu(h @ layer["sh_gate"].astype(h.dtype)) * (
            h @ layer["sh_up"].astype(h.dtype)
        )
        shared = (act @ layer["sh_down"].astype(h.dtype)) * (
            1.0 / cfg.num_shared_experts
        )
    y = x + attn + routed.reshape(B, S, D) + shared.astype(x.dtype)
    return y, carried, stats, top_idx


def _split_banks(layers: Params):
    banks = {n: layers[n] for n in BANKS}
    return {n: v for n, v in layers.items() if n not in BANKS}, banks


def _head(params, cfg, x):
    x = layer_norm(x, params["final_norm"], cfg.layer_norm_eps)
    return cfg.logit_scale * jnp.einsum(
        "bsd,vd->bsv", x, params["embed"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )


def forward_with_cache(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: Cohere2MoeConfig,
    cache: Params,  # ``generate.init_cache(cfg, ...)``
    cache_index,  # scalar int32, or [B] int32: write offset
    *,
    positions: jnp.ndarray,  # [B, S]
    kv_mask: Optional[jnp.ndarray] = None,
    lora: Optional[Params] = None,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = no token
) -> tuple[jnp.ndarray, Params]:
    """KV-cached forward (prefill parts and decode steps alike): returns
    (logits [B, S, V] float32, new cache). ``cache["moe_stats"]`` gains
    this call's expert counters (``moe.local_expert_ffn``). A caller
    that wants to see the routing adds a leaf ``"moe_topk"`` [L, B,
    positions, k] int32 to the cache: each layer then writes the ids it
    chose at ``positions`` (the benchmark's check reads them; the
    engine's cache has no such leaf)."""
    if lora is not None:
        raise NotImplementedError("cohere2_moe has no adapter path yet")
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    scanned, banks = _split_banks(params["layers"])

    def layer_fn(x, layer, _lora_layer, cache, cache_layer):
        def attend(q, kk, vv):
            return llama.cache_write_and_attend(
                q, kk, vv, cache, cache_layer, cache_index, kv_mask
            )

        x, cache, stats, top_idx = _block(
            cfg, x, layer, banks, cache_layer.depth,
            cache_layer.window is not None, attend, sin, cos, token_mask,
        )
        cache = {**cache, "moe_stats": cache["moe_stats"] + stats}
        if "moe_topk" in cache:
            rows = jnp.arange(x.shape[0])[:, None]
            cache["moe_topk"] = cache["moe_topk"].at[
                cache_layer.depth, rows, positions
            ].set(top_idx.astype(jnp.int32))
        return x, cache

    x, cache = llama.scan_layers_with_cache(
        layer_fn, x, scanned, None, cache, cfg.layer_windows
    )
    return _head(params, cfg, x), cache


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: Cohere2MoeConfig,
    token_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Uncached forward over whole rows: logits [B, S, V] float32."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    scanned, banks = _split_banks(params["layers"])
    p = len(cfg.layer_windows)
    for depth in range(cfg.num_layers):
        window = cfg.layer_windows[depth % p]
        layer = jax.tree_util.tree_map(lambda a: a[depth], scanned)

        def attend(q, kk, vv, window=window):
            return dense_attention(q, kk, vv, causal=True, window=window), None

        x, _, _, _ = _block(
            cfg, x, layer, banks, depth, window is not None, attend, sin, cos,
            token_mask,
        )
    return _head(params, cfg, x)
