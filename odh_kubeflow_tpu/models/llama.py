"""Llama-family transformer, TPU-first.

Design choices (deliberately *not* a torch translation):

- **Pure functional**: params are a pytree of jnp arrays; the forward is
  a jittable function of (params, tokens). No modules, no state.
- **Stacked layers + ``lax.scan``**: every per-layer weight carries a
  leading ``[L, ...]`` axis and the decoder runs as one scanned body.
  XLA compiles the layer once (compile time O(1) in depth), and the
  stacked layout is what pipeline parallelism shards later.
- **Sharding by annotation**: ``param_specs`` returns a PartitionSpec
  tree mirroring the params; activations get
  ``with_sharding_constraint`` at layer boundaries. XLA inserts the
  collectives (all-gather for fsdp, reduce-scatter on grads, all-reduce
  for tensor) — nothing here issues a collective by hand.
- **bfloat16 activations / float32 master weights** are both supported;
  ``config.dtype`` controls the compute dtype, params keep their own.

This model is the flagship workload for the platform's north star
(BASELINE.json: Llama-3-8B LoRA >= 50% MFU on a v5p-8 notebook slice).
"""

from __future__ import annotations

import collections
import dataclasses
import math
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from odh_kubeflow_tpu.ops.attention import dense_attention
from odh_kubeflow_tpu.ops.norms import rms_norm
from odh_kubeflow_tpu.ops.rope import apply_rope, rope_angles
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from odh_kubeflow_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_FSDP,
    AXIS_PIPE,
    AXIS_TENSOR,
    constrain,
)

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_size: int = 4096
    intermediate_size: int = 14_336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 500_000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    # "auto" (flash on a TPU backend, dense elsewhere), "dense" (XLA
    # einsum), "flash" (pallas kernel), "ring" (context-parallel ring
    # attention over the `context` mesh axis).
    attention_impl: str = "auto"
    # rematerialise each decoder layer in the backward pass
    remat: bool = True
    # "dots": save weight-matmul outputs (fast backward, ~25k floats
    # per token per layer of residency — fine to ~4k context);
    # "attn": pin the attention output — on the flash path its padded
    # kernel output + logsumexp (~D+Hq floats per token per layer),
    # on dense/ring the "attn_out" tensor (~D floats) — so the
    # backward never re-executes the quadratic attention forward, at
    # a fraction of "dots" residency; the long-context sweet spot;
    # "attn_mlp": "attn" plus the roped q/k/v (the flash backward's
    # inputs) and the MLP gate activation — the recompute shrinks to
    # norms, the up matmul, and elementwise ops, at ~(S·F + S·D)·2B
    # extra per layer (the 16k single-chip winner when it fits);
    # "attn_offload": "attn" with residuals parked in pinned host
    # memory; "none": save only layer boundaries and recompute
    # everything (minimum residency, maximum recompute).
    remat_policy: str = "dots"
    # Memory-budgeted partial pinning: apply ``remat_policy`` to only
    # the LAST n layers and full recompute ("none") to the rest.
    # The 8B/16k QLoRA config is the motivating case: all-32 "attn"
    # pinning needs ~4GB of flash residuals that don't fit beside the
    # int8 base, but a suffix of layers does — each pinned layer's
    # backward skips one O(S²) attention recompute. Pinning the
    # suffix (not prefix) frees residuals earliest in the backward
    # sweep. None = all layers.
    remat_pin_layers: Optional[int] = None
    # Policy for the NON-pinned prefix when remat_pin_layers is set:
    # "none" (historical default — full recompute) or any remat_policy
    # value cheaper than the suffix's, e.g. suffix "attn_mlp" over a
    # prefix "attn" keeps the flash residuals pinned everywhere while
    # budgeting the bigger q/k/v+gate pins to the suffix only.
    remat_prefix_policy: str = "none"
    # Decode-path W8A8: keep int8 weights AS int8 through the matmul
    # (per-token symmetric activation quant, s8×s8→s32 on the MXU)
    # instead of dequantizing to bf16 first. Weight-only int8 decode is
    # CONVERT-bound on the VPU (~8B weight elements widen per step —
    # measured ~2× the HBM roofline on 8B batch-4); the int8 MXU path
    # removes the widening entirely. Opt-in: activation quantization
    # perturbs logits (rare greedy tie flips).
    w8a8_decode: bool = False
    # The stack's PERIOD of attention kinds, one entry a layer and
    # repeated down the depth: an int is a layer that sees only that
    # many positions (its own included) and keeps a ring of them in the
    # cache, None one that sees them all. ``(None,)`` is no window
    # anywhere; ``(4096, 4096, 4096, None)`` three window layers to a
    # global one. The cached forward honours it (``init_cache``,
    # ``scan_layers_with_cache``); training attends in full.
    layer_windows: tuple = (None,)

    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_1b(**kw) -> "LlamaConfig":
        """Llama-3.2-1B shape — fits a single v5e chip for training."""
        d = dict(
            hidden_size=2048,
            intermediate_size=8192,
            num_layers=16,
            num_heads=32,
            num_kv_heads=8,
            head_dim=64,
            tie_embeddings=True,
        )
        d.update(kw)
        return LlamaConfig(**d)

    @staticmethod
    def tiny(**kw) -> "LlamaConfig":
        """Unit-test shape: runs in milliseconds on CPU."""
        d = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            remat=False,
        )
        d.update(kw)
        return LlamaConfig(**d)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def num_params(self) -> int:
        D, F, V, L = (
            self.hidden_size,
            self.intermediate_size,
            self.vocab_size,
            self.num_layers,
        )
        per_layer = (
            D * self.q_dim  # wq
            + 2 * D * self.kv_dim  # wk, wv
            + self.q_dim * D  # wo
            + 3 * D * F  # gate, up, down
            + 2 * D  # norms
        )
        head = 0 if self.tie_embeddings else D * V
        return V * D + L * per_layer + D + head

    def flops_per_token(self, seq_len: int) -> float:
        """Forward-pass matmul FLOPs per token (2*params-style estimate
        plus the quadratic attention term), for MFU accounting.

        The attention term counts only the *causally required* pairs
        (seq_len/2 keys per query on average): a causal-block-skipping
        kernel (``ops/pallas_attention.py``) computes exactly these, so
        crediting the full S^2 would inflate MFU for the flash path and
        understate how much work the dense path wastes on masked pairs.
        """
        D, F, L = self.hidden_size, self.intermediate_size, self.num_layers
        proj = 2 * (D * self.q_dim + 2 * D * self.kv_dim + self.q_dim * D + 3 * D * F)
        attn = 2 * 2 * self.num_heads * self.head_dim * (seq_len / 2)  # qk^T + av
        head = 2 * D * self.vocab_size
        embed = 0  # lookup, not a matmul
        return L * (proj + attn) + head + embed


# ---------------------------------------------------------------------------
# init


def init_params(key: jax.Array, cfg: LlamaConfig, dtype=jnp.float32) -> Params:
    D, F, V, L = (
        cfg.hidden_size,
        cfg.intermediate_size,
        cfg.vocab_size,
        cfg.num_layers,
    )
    k = iter(jax.random.split(key, 16))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in**-0.5).astype(
            dtype
        )

    params: Params = {
        "embed": dense(next(k), (V, D), D),
        "layers": {
            "attn_norm": jnp.ones((L, D), dtype),
            "wq": dense(next(k), (L, D, cfg.q_dim), D),
            "wk": dense(next(k), (L, D, cfg.kv_dim), D),
            "wv": dense(next(k), (L, D, cfg.kv_dim), D),
            "wo": dense(next(k), (L, cfg.q_dim, D), cfg.q_dim),
            "mlp_norm": jnp.ones((L, D), dtype),
            "w_gate": dense(next(k), (L, D, F), D),
            "w_up": dense(next(k), (L, D, F), D),
            "w_down": dense(next(k), (L, F, D), F),
        },
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (D, V), D)
    return params


def param_specs(cfg: LlamaConfig) -> Params:
    """PartitionSpec tree mirroring ``init_params`` output.

    2D sharding: model dims split across (fsdp, tensor); the leading
    ``L`` (layer-stack) axis is always replicated — it is consumed by
    the scan, one slice per step.
    """
    specs: Params = {
        # vocab-sharded (V over tensor+fsdp, D replicated): V ≫ D so the
        # memory split is the same as a D-shard, but the token gather
        # and its scatter-add transpose both accept batch-sharded
        # activations — a D-over-fsdp table forces a batch→d reshard of
        # the embedding cotangent that GSPMD can only do by full
        # rematerialization (r2 multichip dryrun warnings).
        "embed": P((AXIS_TENSOR, AXIS_FSDP), None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, AXIS_FSDP, AXIS_TENSOR),
            "wk": P(None, AXIS_FSDP, AXIS_TENSOR),
            "wv": P(None, AXIS_FSDP, AXIS_TENSOR),
            "wo": P(None, AXIS_TENSOR, AXIS_FSDP),
            "mlp_norm": P(None, None),
            "w_gate": P(None, AXIS_FSDP, AXIS_TENSOR),
            "w_up": P(None, AXIS_FSDP, AXIS_TENSOR),
            "w_down": P(None, AXIS_TENSOR, AXIS_FSDP),
        },
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(AXIS_FSDP, AXIS_TENSOR)
    return specs


# ---------------------------------------------------------------------------
# forward


def _quant_act(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token symmetric int8 activation quant → (xq, scale).

    Split out of ``_int8_matmul`` so projections sharing one input
    (wq/wk/wv on h; w_gate/w_up on the MLP input) quantize it ONCE: the
    per-matmul absmax + round/clip fusions were 7 tiny launch-bound
    kernels per decode layer where 4 suffice — together ~2.6 ms of the
    measured 11.9 ms 8B batch-4 decode step."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sx = jnp.maximum(amax.astype(jnp.float32), 1e-8) / 127.0
    xq = jnp.clip(
        jnp.round(x.astype(jnp.float32) / sx), -127, 127
    ).astype(jnp.int8)
    return xq, sx


def _int8_matmul_pre(
    xq: jnp.ndarray, sx: jnp.ndarray, w: dict, out_dtype
) -> jnp.ndarray:
    """s8×s8 MXU dot on a pre-quantized activation → rescale by
    (activation scale × per-channel weight scale)."""
    acc = jax.lax.dot_general(
        xq, w["q"],
        (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc.astype(jnp.float32) * sx * w["scale"][0][None, :]).astype(
        out_dtype
    )


def _int8_matmul(x: jnp.ndarray, w: dict, out_dtype=None) -> jnp.ndarray:
    """W8A8: per-token symmetric activation quant → s8×s8 MXU dot →
    rescale by (activation scale × per-channel weight scale)."""
    xq, sx = _quant_act(x)
    return _int8_matmul_pre(xq, sx, w, out_dtype or x.dtype)


def _maybe_lora(name: str, x: jnp.ndarray, w, lora_layer,
                xq_sx=None) -> jnp.ndarray:
    """x @ w, plus the low-rank LoRA delta when an adapter is attached.
    ``w`` may be an un-dequantized int8 leaf (the W8A8 decode path);
    ``xq_sx`` optionally carries x already activation-quantized (shared
    across projections reading the same input)."""
    # the scope names in this file (frozen_matmul, dequant, attention,
    # kv_cache_write, kv_cache_read) are the blocks PERF.md section 5
    # names as where the time goes; they reach a profile as the
    # operations' op_name, never as an event's name
    with jax.named_scope("frozen_matmul"):
        if isinstance(w, dict):
            if xq_sx is not None:
                y = _int8_matmul_pre(xq_sx[0], xq_sx[1], w, x.dtype)
            else:
                y = _int8_matmul(x, w)
        else:
            y = x @ w.astype(x.dtype)
    if lora_layer is not None and name in lora_layer:
        a = lora_layer[name]["a"].astype(x.dtype)  # [D, r]
        b = lora_layer[name]["b"].astype(x.dtype)  # [r, out]
        scale = lora_layer[name]["scale"].astype(x.dtype)
        y = y + ((x @ a) @ b) * scale
    return y


def _activation_spec() -> P:
    # expert doubles as a batch axis for dense compute (mesh.batch_spec)
    return P((AXIS_DATA, AXIS_FSDP, AXIS_EXPERT), AXIS_CONTEXT, None)


def _decoder_layer(
    cfg: LlamaConfig,
    attention_fn: Callable,
    x: jnp.ndarray,  # [B, S, D]
    layer: Params,  # leaves sliced to this layer (no leading L)
    lora_layer,  # matching slice of lora params, or None
    sin: jnp.ndarray,
    cos: jnp.ndarray,
    segment_ids,
    cache=None,  # ``init_cache``'s stacks [L_kind, B, S_kind, Hkv * hd], or None
    layer_index=None,  # CacheLayer: where this layer lies in ``cache``
    cache_index=None,  # scalar or [B]: write offset into the cache
    kv_mask=None,  # [B, S_max] bool: which cache slots are valid
):
    """Returns ``(x, updated_cache)``.

    ``updated_cache`` is None on the training path; on the KV-cache
    decode path (``models/generate.py``) it is the whole stacked
    ``{"k","v"}`` with this step's keys/values written at
    ``[layer_index, :, cache_index]`` (``cache_write_and_attend``: the
    layer is never sliced out). The cache path attends with
    ``dense_attention``'s semantics (``decode_attend`` on the TPU) —
    decode attention is a bandwidth-bound gather over the cache where a
    traced ``cache_index``/``q_offset`` is required (the flash kernel
    needs it static and ring attention has no cache semantics);
    ``attention_fn`` only selects the *training* (no-cache)
    implementation.
    """
    B, S, D = x.shape
    x = constrain(x, _activation_spec())

    # int8-quantized frozen weights (models/quant.py) dequantize HERE,
    # inside the (possibly rematerialised) layer body: only the current
    # layer's bf16 copy ever materialises, and the backward pass
    # recomputes the dequant from int8 instead of holding 2× weights.
    # This is what lets an 8B QLoRA fine-tune fit a single 16GiB v5e.
    # Under w8a8_decode (cache path only), int8 matmul weights skip
    # dequant entirely — _maybe_lora runs them on the int8 MXU.
    keep = cache is not None and cfg.w8a8_decode
    with jax.named_scope("dequant"):
        layer = _maybe_dequant(layer, cfg.dtype, keep_int8_matmuls=keep)

    h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    # W8A8: wq/wk/wv read the same input — quantize it once
    hq = _quant_act(h) if keep and isinstance(layer["wq"], dict) else None
    q = _maybe_lora("wq", h, layer["wq"], lora_layer, hq)
    kk = _maybe_lora("wk", h, layer["wk"], lora_layer, hq)
    vv = _maybe_lora("wv", h, layer["wv"], lora_layer, hq)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    kk = kk.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    vv = vv.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    q = apply_rope(q, sin, cos)
    kk = apply_rope(kk, sin, cos)
    # named for the "attn_mlp" remat policy: the flash backward kernels
    # consume q/k/v — pinning the roped values removes the qkv
    # projection + rope from the recompute entirely
    q = _checkpoint_name(q, "q_rope")
    kk = _checkpoint_name(kk, "k_rope")
    vv = _checkpoint_name(vv, "v_proj")
    if cache is not None:
        attn, cache = cache_write_and_attend(
            q, kk, vv, cache, layer_index, cache_index, kv_mask
        )
    else:
        with jax.named_scope("attention"):
            attn = attention_fn(q, kk, vv, segment_ids=segment_ids)
    # named so the "attn" remat policy can pin exactly this tensor
    attn = _checkpoint_name(attn, "attn_out")
    attn = attn.reshape(B, S, cfg.q_dim)
    x = x + _maybe_lora("wo", attn, layer["wo"], lora_layer)

    h = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    # W8A8: gate/up share the MLP input — one quantization
    hq = (
        _quant_act(h)
        if keep and isinstance(layer["w_gate"], dict)
        else None
    )
    gate = _maybe_lora("w_gate", h, layer["w_gate"], lora_layer, hq)
    up = _maybe_lora("w_up", h, layer["w_up"], lora_layer, hq)
    # named for "attn_mlp": gate is pinned, up is NOT — silu' needs
    # both, so the backward recomputes exactly one D→F matmul (up);
    # pinning u as well (another S·F·2B/layer) OOMs the 16k configs
    # the policy exists for (see _make_layer_fn)
    gate = _checkpoint_name(gate, "mlp_g")
    x = x + _maybe_lora("w_down", jax.nn.silu(gate) * up, layer["w_down"], lora_layer)
    return x, cache


class CacheLayer(NamedTuple):
    """Where one layer's part of the cache lies: the names of its stacks
    (``init_cache``: a kind of layer has stacks of its own; keys and
    values, or a recurrent layer's state), its index in them, how far
    back it sees, and its depth in the whole stack of layers."""

    index: Any  # scalar int32
    names: tuple = ("k", "v")
    window: Optional[int] = None
    depth: Any = None  # scalar int32


def cache_write_and_attend(
    q,  # [B, S, Hq, hd]
    kk,  # [B, S, Hkv, hd] this step's keys
    vv,
    cache,  # ``init_cache``: stacks [L_kind, B, S_kind, Hkv * hd]
    layer,  # CacheLayer, or a scalar int32 index into {"k","v"}
    cache_index,  # scalar int32, or [B] int32 (per-row offsets)
    kv_mask,  # [B, S_max] bool or None
):
    """Append this step's K/V at ``[layer, :, cache_index]`` of the
    layer's stacks and attend over that layer with absolute positions
    (``kv_mask``/``q_offset`` mask the unwritten tail, ``layer.window``
    what lies too far back). Shared by every cached layer.

    The stacks are the layer scan's CARRY (``scan_layers_with_cache``):
    the write touches S rows in place and the read takes the layer
    where it lies, so no step copies a layer's cache. Returns
    ``(attn, cache)``.

    Position ``p`` is written to slot ``p % S_kind``. A stack as long
    as ``max_len`` never wraps; a window layer's is a RING of ``window
    + the widest part written at once`` slots, which is all that its
    queries can see. What that asks of the caller: a part of S > 1
    positions written at a scalar ``cache_index`` must not straddle the
    ring's end (parts whose width divides the ring and that start at
    multiples of it never do; the engine checks its own).

    A scalar ``cache_index`` is the classic generate() layout: every
    row writes at the same physical offset (ragged prompts pad to a
    shared index). A **[B] vector** is the continuous-batching engine's
    layout (``models/engine.py``): each batch slot sits at its own
    depth, so writes scatter per-row.
    """
    if not isinstance(layer, CacheLayer):
        layer = CacheLayer(layer)
    cache = _cache_write(kk, vv, cache, layer, cache_index)
    return _cache_attend(q, cache, layer, cache_index, kv_mask), cache


def _ring_slot(positions, window, S_kind):
    # a window layer's stack is a ring; a full one holds every position
    # the caller may ask for, and a window of tokens that runs past its
    # end (ragged speculative verify) is clamped to its last slot, which
    # the engine's kv_mask excludes
    if window is None:
        return jnp.clip(positions, 0, S_kind - 1)
    return positions % S_kind


def _cache_write(kk, vv, cache, layer: CacheLayer, cache_index):
    """``cache_write_and_attend``'s write: this step's keys and values
    at ``[layer, :, cache_index]`` of the layer's first two stacks."""
    B, S, Hkv, hd = kk.shape
    layer_index, window = layer.index, layer.window
    stacks = dict(zip(("k", "v"), (cache[n] for n in layer.names)))
    S_kind = stacks["k"].shape[2]
    slot = partial(_ring_slot, window=window, S_kind=S_kind)
    with jax.named_scope("kv_cache_write"):
        new = {"k": kk.reshape(B, S, Hkv * hd), "v": vv.reshape(B, S, Hkv * hd)}
        if getattr(cache_index, "ndim", 0) == 1:
            rows = jnp.arange(B)
            if S == 1:
                at = (layer_index, rows, slot(cache_index))
                new = {kv: x[:, 0] for kv, x in new.items()}
            else:
                # per-row offsets with a multi-token window — the engine's
                # speculative verify (k+1 tokens per slot, each slot at its
                # own depth)
                cols = slot(cache_index[:, None] + jnp.arange(S)[None, :])
                at = (layer_index, rows[:, None], cols)
            stacks = {
                kv: stacks[kv].at[at].set(new[kv].astype(stacks[kv].dtype))
                for kv in ("k", "v")
            }
        else:
            stacks = {
                kv: jax.lax.dynamic_update_slice(
                    stacks[kv],
                    new[kv][None].astype(stacks[kv].dtype),
                    (layer_index, 0, slot(cache_index), 0),
                )
                for kv in ("k", "v")
            }
    return {**cache, layer.names[0]: stacks["k"], layer.names[1]: stacks["v"]}


def _cache_attend(q, cache, layer: CacheLayer, cache_index, kv_mask,
                  select=None):
    """``cache_write_and_attend``'s read: attention over the layer's
    keys and values where they lie; ``select`` is ``decode_attend``'s."""
    B, S, _, hd = q.shape
    layer_index, window = layer.index, layer.window
    stacks = dict(zip(("k", "v"), (cache[n] for n in layer.names)))
    S_kind = stacks["k"].shape[2]
    Hkv = stacks["k"].shape[3] // hd
    with jax.named_scope("kv_cache_read"):
        from odh_kubeflow_tpu.ops import pallas_decode_attention as pda

        slot_mask, held = kv_mask, None
        if window is not None:
            # which position each slot of the ring holds; the mask is by
            # position, the read by slot
            held = pda.slot_positions(
                jnp.broadcast_to(cache_index, (B,)), S, S_kind
            )
            if kv_mask is not None and kv_mask.shape[1] != S_kind:
                slot_mask = jnp.take_along_axis(
                    kv_mask, jnp.clip(held, 0, kv_mask.shape[1] - 1), axis=1
                )
        if _reads_cache_in_place(stacks["k"], hd):
            attn = pda.decode_attend(
                q, stacks["k"], stacks["v"], layer_index, cache_index,
                slot_mask, window=window, select=select,
            )
        else:
            ck, cv = (
                jax.lax.dynamic_index_in_dim(
                    stacks[kv], layer_index, 0, keepdims=False
                ).reshape(B, -1, Hkv, hd)
                for kv in ("k", "v")
            )
            if select is not None:
                from odh_kubeflow_tpu.ops import sparse_attention

                q_pos = jnp.broadcast_to(cache_index, (B,))[:, None] + jnp.arange(S)
                keep = sparse_attention.selected(
                    select[0], sparse_attention.visible(q_pos, slot_mask, S_kind),
                    *select[1:],
                )
                return sparse_attention.masked_attention(q, ck, cv, keep)
            attn = dense_attention(
                q, ck, cv, causal=True, q_offset=cache_index,
                kv_mask=slot_mask, k_positions=held, window=window,
            )
    return attn


def indexed_write_and_attend(
    q,  # [B, S, Hq, hd]
    kk,  # [B, S, Hkv, hd] this step's keys
    vv,
    qi,  # [B, S, Hi, di] the indexer's queries, rotated
    ki,  # [B, S, di] the indexer's keys (one head), rotated
    wi,  # [B, S, Hi] float32: the indexer's weight a head
    cache,
    layer: CacheLayer,  # names ``INDEXED_STACKS``
    cache_index,  # scalar int32, or [B] int32 with S == 1
    kv_mask,  # [B, S_max] bool or None
    topk: int,
    positions=None,  # [B, S]: where a leaf ``index_topk`` is filled
    token_mask=None,  # [B, S] bool: the rows ``sel_stats`` counts
):
    """``cache_write_and_attend`` for a layer whose queries attend only
    the ``topk`` keys its indexer picks (``ops/sparse_attention.py``):
    append keys, values and the indexer's keys at ``[layer, :,
    cache_index]`` of the layer's three stacks, score every visible
    position with the indexer, and attend over each query's ``topk``
    largest (all of them while there are no more).

    One token a row (a decode step): the scores over the row's ``ik``
    read in place, the threshold by search, the kept positions compacted
    in position order, the kept rows of the key and value stacks taken by
    one gather each and attention over the ``[B, topk]`` gathered rows:
    what the step reads of keys and values does not grow with the
    context, only the indexer's 2 x ``index_dim`` bytes a position do.
    Several tokens a row at one offset (a part of a prompt): while
    ``cache_index + S <= topk`` plain causal attention; else the scores
    ``[S, cache_index + S]``, a threshold a query and attention over the
    stacks under the selection as a mask. ``cache["sel_stats"]`` gains
    the positions the call's queries could see and those they attended,
    and a leaf ``"index_topk"`` [L, B, positions, topk] is filled if
    there (-1 past a query's count), as is a leaf ``"index_inputs"`` [L,
    B, positions, Hi * (di + 1)] float32: each query's ``q^I`` and ``w``
    as the scores took them (a check computes the selection again from
    them and the cached keys)."""
    from odh_kubeflow_tpu.ops import sparse_attention as sa

    B, S, _, _ = q.shape
    sk, sv, ik_name = layer.names
    kv_layer = layer._replace(names=(sk, sv))
    cache = _cache_write(kk, vv, cache, kv_layer, cache_index)
    ik = cache[ik_name]
    S_max = ik.shape[3]
    k = min(topk, S_max)
    per_row = getattr(cache_index, "ndim", 0) == 1
    in_place = _reads_cache_in_place(cache[sk], q.shape[3]) and sa.supported(ik)
    with jax.named_scope("kv_cache_write"):
        ki = ki.astype(ik.dtype)
        if per_row:
            if S != 1:
                raise NotImplementedError(
                    "several tokens a row at per-row offsets (speculative "
                    "verify) would need one selection a token"
                )
            if in_place:
                ik = sa.write_index_keys(ik, ki[:, 0], layer.index, cache_index)
            else:
                at = (layer.index, jnp.arange(B), slice(None),
                      jnp.clip(cache_index, 0, S_max - 1))
                ik = ik.at[at].set(ki[:, 0])
        else:
            ik = jax.lax.dynamic_update_slice(
                ik, ki.transpose(0, 2, 1)[None],
                (layer.index, 0, 0, jnp.clip(cache_index, 0, S_max - 1)),
            )
    cache = {**cache, ik_name: ik}

    q_off = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (B,))
    q_pos = q_off[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    score = sa.index_scores if in_place else sa.index_scores_plain

    def seen():
        return sa.visible(q_pos, kv_mask, S_max)

    def choose():
        """(scores, thr, cut) of every query of the call."""
        scores = score(qi, wi, ik, layer.index, cache_index)
        with jax.named_scope("index_select"):
            return scores, *sa.select_threshold(scores, seen(), k)

    picked = None
    if S == 1:
        scores, thr, cut = choose()
        with jax.named_scope("index_select"):
            keep = sa.selected(scores, seen(), thr, cut)
            ids, count = sa.compact_positions(keep[:, 0], k)
        with jax.named_scope("sparse_gather"):
            rows_k = sa.gather_rows(cache[sk], layer.index, ids)[None]
            rows_v = sa.gather_rows(cache[sv], layer.index, ids)[None]
        # a one-layer stack, every row live up to its count
        gathered = CacheLayer(jnp.int32(0), ("k", "v"))
        attn = _cache_attend(
            q, {"k": rows_k, "v": rows_v}, gathered, count - 1, None
        )
        picked = jnp.where(ids < S_max, ids, -1)[:, None]
    elif S_max <= topk:
        attn = _cache_attend(q, cache, kv_layer, cache_index, kv_mask)
    else:
        attn = jax.lax.cond(
            cache_index + S <= topk,
            lambda: _cache_attend(q, cache, kv_layer, cache_index, kv_mask),
            lambda: _cache_attend(
                q, cache, kv_layer, cache_index, kv_mask, select=choose()
            ),
        )
    if "index_topk" in cache:
        if picked is None:
            scores, thr, cut = choose()
            keep = sa.selected(scores, seen(), thr, cut)
            ids, _ = sa.compact_positions(keep.reshape(B * S, S_max), k)
            picked = jnp.where(ids < S_max, ids, -1).reshape(B, S, k)
        cache["index_topk"] = cache["index_topk"].at[
            layer.depth, jnp.arange(B)[:, None], positions, :k
        ].set(picked.astype(jnp.int32))
    if "index_inputs" in cache:
        cache["index_inputs"] = cache["index_inputs"].at[
            layer.depth, jnp.arange(B)[:, None], positions
        ].set(jnp.concatenate(
            [qi.astype(ik.dtype).reshape(B, S, -1), wi], axis=-1
        ).astype(jnp.float32))
    with jax.named_scope("index_select"):
        # how many positions a query can see: those the mask holds up to its own
        n_seen = q_pos + 1 if kv_mask is None else jnp.take_along_axis(
            jnp.cumsum(kv_mask, axis=1, dtype=jnp.int32),
            jnp.clip(q_pos, 0, S_max - 1), axis=1,
        )
        if token_mask is not None:
            n_seen = jnp.where(token_mask, n_seen, 0)
        cache["sel_stats"] = cache["sel_stats"] + jnp.stack(
            [jnp.sum(n_seen), jnp.sum(jnp.minimum(n_seen, k))]
        ).astype(jnp.int32)
    return attn, cache


def _reads_cache_in_place(cache_leaf, head_dim: int) -> bool:
    """Whether attention reads the layer from the stack through the
    Pallas kernel (``ops/pallas_decode_attention.py``) or through
    ``dense_attention`` on a slice of it.

    The kernel is the TPU's read: XLA cannot hand a layer of the carried
    stack to the attention dots without first copying it out (a
    ``dynamic-slice`` of the layer's whole K and V; PERF.md, PR 25).
    The dense read stays where the kernel cannot go, as for flash
    (``resolved_attention_impl``): off the TPU (interpret mode is slow
    ordinary ops), under a multi-device mesh (GSPMD cannot partition a
    Mosaic call, ``_flash_per_shard``; the slice there is a shard's), and
    for a cache whose shape has no whole tiles."""
    from odh_kubeflow_tpu.ops import pallas_decode_attention

    am = jax.sharding.get_abstract_mesh()
    return (
        jax.default_backend() == "tpu"
        and (am.empty or am.size == 1)
        and pallas_decode_attention.supported(cache_leaf, head_dim)
    )


# The ONE table of the cache's kinds (``generate.init_cache``,
# ``cache_bytes``, ``cache_specs``, the engine's splice and this file's
# scan all read it): a kind of layer has stacks of its own, each with a
# row per layer of the kind and, behind it, a row per slot. An entry of
# a config's period of kinds is ``None`` (every position: keys and
# values ``max_len`` long), an int (a window: a ring of keys and
# values), ``STATE`` (a recurrent layer: no keys and values, a state
# of the shapes ``cfg.state_leaves`` gives) or ``INDEXED`` (a layer whose
# queries attend only the keys an indexer picks: keys and values
# ``max_len`` long and, beside them, the indexer's own keys, one head of
# ``cfg.index_dim`` a position, laid ``[index_dim, max_len]`` a row so
# that positions lie along the lanes; ``ops/sparse_attention.py``).
FULL_STACKS = ("k", "v")
WINDOW_STACKS = ("wk", "wv")
STATE_STACKS = ("ssm", "conv")
INDEXED_STACKS = ("sk", "sv", "ik")
STATE = "state"
INDEXED = "indexed"
CACHE_KINDS = {
    "full": FULL_STACKS, "window": WINDOW_STACKS, STATE: STATE_STACKS,
    INDEXED: INDEXED_STACKS,
}


def kind_of(entry) -> str:
    """The kind of cache an entry of a period of kinds asks for."""
    if entry is None:
        return "full"
    return entry if entry in (STATE, INDEXED) else "window"


def layer_kinds(cfg) -> tuple:
    """A config's period of kinds: ``layer_kinds`` where it has layers
    that keep no keys and values, else its ``layer_windows``."""
    return getattr(cfg, "layer_kinds", None) or getattr(
        cfg, "layer_windows", (None,)
    )


def stack_kind(name: str) -> Optional[str]:
    """The kind whose stack a cache leaf is; None for what is not a
    stack (a call's counters)."""
    return next((k for k, names in CACHE_KINDS.items() if name in names), None)


def cache_layers(kinds: tuple, period_index) -> list:
    """The ``CacheLayer`` of each layer of period ``period_index`` of a
    stack whose period of kinds is ``kinds``: a kind's layers are
    numbered down the depth within their own stacks."""
    per_period = collections.Counter(kind_of(k) for k in kinds)
    out, seen = [], collections.Counter()
    for j, entry in enumerate(kinds):
        kind = kind_of(entry)
        out.append(CacheLayer(
            period_index * per_period[kind] + seen[kind], CACHE_KINDS[kind],
            entry if kind == "window" else None,
            period_index * len(kinds) + j,
        ))
        seen[kind] += 1
    return out


def take_layer(tree, layer_index):
    """One layer of a stack of layers ``[L, ...]`` (None stays None). A
    period's layers are taken from the stacks one by one, each slice
    with the one matmul that consumes it: scanned as a ``[periods, p,
    ...]`` input, XLA copies the whole period's weights out of the stack
    on every turn of the scan (PERF.md, PR 26)."""
    if tree is None:
        return None
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer_index, 0, False), tree
    )


def scan_layers_with_cache(layer_fn, x, layers, lora_layers, cache,
                           kinds: tuple = (None,)):
    """The one scan over layers that has a cache (every family).

    ``layer_fn(x, layer, lora_layer, cache, cache_layer) -> (x, cache)``
    runs one layer; it hands ``cache`` and ``cache_layer`` (a
    ``CacheLayer``) to ``cache_write_and_attend``, or, for a recurrent
    layer (``cache_layer.names == STATE_STACKS``), reads and writes the
    layer's state at ``cache_layer.index`` itself. The scan runs over
    the stack's PERIODS (``kinds``: ``layer_kinds(cfg)``):
    its body holds one layer of each kind in the period's order, so a
    stack of one kind is a scan over its layers. The cache's stacks,
    a few a kind, ride the scan as its carry beside ``x``, and only
    the weights, adapters and the period's index are scanned: as a
    scanned input and output XLA would slice every layer's whole cache
    out of the stack and write it back, each layer of each step
    (PERF.md, PR 25). With the caller's buffer donated the stacks are
    updated in place."""
    p = len(kinds)
    depth = jax.tree_util.tree_leaves(layers)[0].shape[0]
    assert depth % p == 0, (depth, kinds)

    def body(carry, scanned):
        x, cache = carry
        period_index, layer, lora_layer = scanned
        for cache_layer in cache_layers(kinds, period_index):
            if p > 1:
                layer = take_layer(layers, cache_layer.depth)
                lora_layer = take_layer(lora_layers, cache_layer.depth)
            x, cache = layer_fn(x, layer, lora_layer, cache, cache_layer)
        return (x, cache), None

    # a stack of one kind is scanned as it lies
    one_kind = p == 1
    (x, cache), _ = jax.lax.scan(
        body, (x, cache),
        (
            jnp.arange(depth // p, dtype=jnp.int32),
            layers if one_kind else None,
            lora_layers if one_kind else None,
        ),
    )
    return x, cache


def resolved_attention_impl(cfg: LlamaConfig) -> str:
    """'auto' resolution, in priority order:

    1. ring — when the active mesh shards the ``context`` axis >1,
       attention must be context-parallel (any other impl would
       silently compute block-diagonal attention over the shards);
    2. flash — pallas kernel on a TPU backend (the regime it was
       written for);
    3. dense — everywhere else (CPU tests would only ever run flash in
       slow interpret mode).
    """
    if cfg.attention_impl != "auto":
        return cfg.attention_impl
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and am.shape.get(AXIS_CONTEXT, 1) > 1:
        return "ring"
    return "flash" if jax.default_backend() == "tpu" else "dense"


def _select_attention(cfg: LlamaConfig) -> Callable:
    impl = resolved_attention_impl(cfg)
    cfg = dataclasses.replace(cfg, attention_impl=impl)
    if cfg.attention_impl == "dense":
        return partial(dense_attention, causal=True)
    if cfg.attention_impl == "flash":
        return _flash_per_shard
    if cfg.attention_impl == "ring":
        from odh_kubeflow_tpu.parallel.ring_attention import ring_attention

        return partial(ring_attention, causal=True)
    raise ValueError(
        f"unknown attention_impl {cfg.attention_impl!r}; "
        "expected 'dense', 'flash', or 'ring'"
    )


def _flash_per_shard(q, k, v, *, segment_ids=None):
    """Causal flash attention that also runs under a multi-device mesh.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map" — and Mosaic wants EVERY mesh axis manual, trivial ones
    included), so under a mesh the kernel runs per shard inside a
    shard_map over the whole mesh: batch rows over (data, fsdp, expert)
    — the layout ``batch_spec`` already gives the batch — and heads
    over ``tensor``, each when it divides (else that dimension
    replicates and XLA gathers it at the boundary). Attention is
    independent across rows and heads: no collective. With no mesh, or
    one device, the kernel is called directly.

    Inside a pipeline stage ``pipe`` is already Manual. A nested map
    over the remaining axes computes the right thing, but Mosaic's
    check reads only the innermost map's axes and still refuses; naming
    ``pipe`` again passes the check and makes shard_map psum the
    cotangents over it — wrong gradients (both seen in PR 21: four-chip
    run, CPU cross-lowering). So on the TPU flash inside a pipeline
    stage is an error (ROADMAP S7); in interpret mode the kernel is
    ordinary ops that GSPMD partitions itself."""
    from odh_kubeflow_tpu.ops.pallas_attention import flash_attention

    am = jax.sharding.get_abstract_mesh()
    if am.empty or am.size == 1:
        return flash_attention(q, k, v, causal=True, segment_ids=segment_ids)
    if any(t != jax.sharding.AxisType.Auto for t in am.axis_types):
        if jax.default_backend() == "tpu":
            raise NotImplementedError(
                "flash attention inside a partly-manual shard_map (a "
                "pipeline stage) cannot be lowered by Mosaic; set "
                "attention_impl='dense' for pipelined meshes (ROADMAP S7)"
            )
        return flash_attention(q, k, v, causal=True, segment_ids=segment_ids)
    batch_ax = tuple(
        a for a in (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT) if a in am.axis_names
    )
    if not batch_ax or q.shape[0] % math.prod(am.shape[a] for a in batch_ax):
        batch_ax = None
    t = am.shape.get(AXIS_TENSOR, 1)
    head_ax = AXIS_TENSOR if t > 1 and k.shape[2] % t == 0 else None
    qkv_spec = P(batch_ax, None, head_ax, None)
    args, in_specs = (q, k, v), (qkv_spec, qkv_spec, qkv_spec)
    if segment_ids is not None:
        args, in_specs = (*args, segment_ids), (*in_specs, P(batch_ax, None))

    def local(q_, k_, v_, seg_=None):
        return flash_attention(q_, k_, v_, causal=True, segment_ids=seg_)

    return jax.shard_map(
        local, mesh=am, in_specs=in_specs, out_specs=qkv_spec, check_vma=False
    )(*args)


def _make_layer_fn(cfg: LlamaConfig, attention_fn: Callable,
                   gather_from=None) -> Callable:
    """``gather_from`` = (stacked_layers, stacked_lora_or_None): the
    returned fn takes a layer INDEX instead of layer trees and gathers
    inside the rematted region — gathering outside would make every
    per-layer parameter slice a saved residual (a full extra copy of
    the model across the scan; the 8B-int8 16k OOM)."""
    raw_fn = partial(_decoder_layer, cfg, attention_fn)
    if gather_from is None:
        layer_fn = raw_fn
    else:
        stacked_layers, stacked_lora = gather_from

        def layer_fn(x, i, _unused_lora, sin, cos, segment_ids):
            layer = jax.tree.map(lambda a: a[i], stacked_layers)
            lora_l = (
                None
                if stacked_lora is None
                else jax.tree.map(lambda a: a[i], stacked_lora)
            )
            return raw_fn(x, layer, lora_l, sin, cos, segment_ids)

    if cfg.remat:
        if cfg.remat_policy == "dots":
            # dots_with_no_batch_dims does NOT cover pallas_call, so on
            # the flash path the kernel's named residuals ride along —
            # otherwise the O(S²) forward would re-run in the backward
            # even under the "save matmuls" policy.
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            if resolved_attention_impl(cfg) == "flash":
                policy = jax.checkpoint_policies.save_from_both_policies(
                    policy,
                    jax.checkpoint_policies.save_only_these_names(
                        "flash_out", "flash_lse"
                    ),
                )
            layer_fn = jax.checkpoint(layer_fn, policy=policy)
        elif cfg.remat_policy in ("attn", "attn_offload", "attn_mlp"):
            # "flash_out"/"flash_lse" are the flash kernel's custom-vjp
            # residuals (ops/pallas_attention.py _flash_fwd): with them
            # saved, remat's recompute is projections-only — the O(S²)
            # forward kernel runs exactly once per layer, and the
            # un-padded "attn_out" view is re-derived from "flash_out"
            # by a free moveaxis/slice (saving both would double the
            # residency). Dense/ring impls have no flash residuals, so
            # there "attn_out" itself is pinned. "attn_offload" parks
            # the residuals in pinned host memory instead of HBM —
            # the 8B/16k config, whose ~4GB of residuals don't fit
            # beside the int8 base, trades PCIe round-trips for the
            # O(S²) recompute.
            names = (
                ("flash_out", "flash_lse")
                if resolved_attention_impl(cfg) == "flash"
                else ("attn_out",)
            )
            if cfg.remat_policy == "attn_mlp":
                # "attn" + the roped q/k/v (the flash backward's other
                # inputs) + the MLP gate activation: silu' needs g AND
                # u, so one matmul (up) is still recomputed — pinning u
                # as well (another S·F·2B/layer) OOMs the 16k configs
                # this policy exists for (the models/moe.py
                # pin_expert_acts trade, same reasoning). Residency
                # ~(S·F + S·(D+2·Hkv·hd))·2B per layer (1B @ 16k:
                # ~0.35GB/layer); budget with remat_pin_layers
                names = names + ("q_rope", "k_rope", "v_proj", "mlp_g")
            if cfg.remat_policy == "attn_offload":
                policy = (
                    jax.checkpoint_policies
                    .save_and_offload_only_these_names(
                        names_which_can_be_saved=[],
                        names_which_can_be_offloaded=list(names),
                        offload_src="device",
                        offload_dst="pinned_host",
                    )
                )
            else:
                policy = jax.checkpoint_policies.save_only_these_names(
                    *names
                )
            layer_fn = jax.checkpoint(layer_fn, policy=policy)
        elif cfg.remat_policy == "none":
            # full recompute, minimum residency
            layer_fn = jax.checkpoint(layer_fn)
        else:
            # a typo'd policy silently falling through to full
            # recompute would be a ~2× slower backward with no signal
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r}; expected "
                "'dots', 'attn', 'attn_mlp', 'attn_offload', or 'none'"
            )
    return layer_fn


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: LlamaConfig,
    lora: Optional[Params] = None,
    positions: Optional[jnp.ndarray] = None,
    segment_ids: Optional[jnp.ndarray] = None,
    return_hidden: bool = False,
    pipeline_microbatches: int = 8,
) -> jnp.ndarray:
    """Returns logits [B, S, V] in float32 — or, with
    ``return_hidden=True``, the final-norm hidden states [B, S, D] so
    the caller can run the LM head chunk-wise (long-context training:
    a full [S, V] logits tensor at S=16k and V=128k is 8GB+ and is the
    thing that OOMs, not attention — see
    ``train.trainer.chunked_cross_entropy``).

    When the active mesh shards the ``pipe`` axis, the layer stack runs
    through the GPipe combinator (``parallel/pipeline.py``) with
    ``pipeline_microbatches`` microbatches; embeddings, final norm, and
    the LM head stay outside the pipeline (replicated compute)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    # Resolve the attention impl exactly once: _select_attention and
    # _make_layer_fn's remat-policy choice must agree on it (both
    # consult ambient backend/mesh state under "auto").
    cfg = dataclasses.replace(cfg, attention_impl=resolved_attention_impl(cfg))
    attention_fn = _select_attention(cfg)
    layer_fn = _make_layer_fn(cfg, attention_fn)
    lora_layers = lora["layers"] if lora is not None else None

    am = jax.sharding.get_abstract_mesh()
    pipe = 0 if am.empty else am.shape.get(AXIS_PIPE, 1)
    if pipe > 1:
        x = _apply_layers_pipelined(
            cfg,
            layer_fn,
            params["layers"],
            lora_layers,
            x,
            positions,
            segment_ids,
            pipeline_microbatches,
        )
    else:
        def body_with(fn):
            def body(x, scanned):
                layer, lora_layer = scanned
                x, _ = fn(x, layer, lora_layer, sin, cos, segment_ids)
                return x, None

            return body

        pin = cfg.remat_pin_layers
        if (
            cfg.remat
            and cfg.remat_policy != "none"
            and pin is not None
            and 0 < pin < cfg.num_layers
        ):
            # two scans: a cheap-policy prefix and a pinned suffix —
            # per-layer policies can't vary inside one scan. The scans
            # iterate over layer INDICES and gather each layer from the
            # stacked params in-body: slicing the stacked trees into
            # prefix/suffix copies would double the (8GB at 8B-int8)
            # base-weight residency and OOM exactly the configs this
            # knob exists for.
            n_first = cfg.num_layers - pin
            gf = (params["layers"], lora_layers)
            fn_none_g = _make_layer_fn(
                dataclasses.replace(
                    cfg, remat_policy=cfg.remat_prefix_policy
                ),
                attention_fn, gather_from=gf,
            )
            fn_pin_g = _make_layer_fn(cfg, attention_fn, gather_from=gf)

            def body_gather(fn):
                def body(x, i):
                    x, _ = fn(x, i, None, sin, cos, segment_ids)
                    return x, None

                return body

            x, _ = jax.lax.scan(
                body_gather(fn_none_g),
                x,
                jnp.arange(n_first, dtype=jnp.int32),
            )
            x, _ = jax.lax.scan(
                body_gather(fn_pin_g),
                x,
                jnp.arange(n_first, cfg.num_layers, dtype=jnp.int32),
            )
        else:
            x, _ = jax.lax.scan(
                body_with(layer_fn), x, (params["layers"], lora_layers)
            )

    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    if return_hidden:
        return x
    head = lm_head_weight(params, cfg)
    logits = jnp.einsum(
        "bsd,dv->bsv", x, head.astype(cfg.dtype), preferred_element_type=jnp.float32
    )
    return logits


def _apply_layers_pipelined(
    cfg,  # LlamaConfig or any config with head_dim/rope_theta
    layer_fn: Callable,
    layers: Params,
    lora_layers: Optional[Params],
    x: jnp.ndarray,  # [B, S, D]
    positions: jnp.ndarray,  # [B, S]
    segment_ids: Optional[jnp.ndarray],
    num_microbatches: int,
    accumulate_aux: bool = False,
):
    """Decoder stack over the pipe axis — shared by the dense and MoE
    families. Rope angles and segment ids are per-microbatch constants
    riding the pipeline's ``aux`` channel, so every stage sees the
    slice belonging to the microbatch it is currently processing.

    ``layer_fn(x, layer, lora_layer, sin, cos, seg)`` returns
    ``(x, extra)``; with ``accumulate_aux`` the extra (the MoE router
    aux loss) is summed over layers and (stage, microbatch) pairs and
    this returns ``(y, aux_sum / M)`` at full-batch scale — otherwise
    the extra (the dense family's unused cache slot) is discarded and
    only ``y`` returns."""
    from odh_kubeflow_tpu.parallel.pipeline import pipeline_apply

    B, S, D = x.shape
    M = num_microbatches
    mb = B // M if B % M == 0 else 0
    if mb == 0:
        raise ValueError(
            f"batch {B} not divisible by pipeline_microbatches={M}"
        )
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)

    def split(a):
        return None if a is None else a.reshape(M, mb, *a.shape[1:])

    aux = {"sin": split(jnp.broadcast_to(sin, (B, *sin.shape[1:]))),
           "cos": split(jnp.broadcast_to(cos, (B, *cos.shape[1:])))}
    if segment_ids is not None:
        aux["segment_ids"] = split(segment_ids)

    stage_params = {"layers": layers}
    if lora_layers is not None:
        stage_params["lora"] = lora_layers

    def stage_fn(stage, x_flat, aux_t):
        xx = x_flat.reshape(x_flat.shape[0], S, D)
        seg = aux_t.get("segment_ids")

        def body(carry, scanned_idx):
            xx, acc = carry
            layer = jax.tree_util.tree_map(
                lambda l: l[scanned_idx], stage["layers"]
            )
            lora_layer = (
                jax.tree_util.tree_map(
                    lambda l: l[scanned_idx], stage["lora"]
                )
                if "lora" in stage
                else None
            )
            xx, extra = layer_fn(
                xx, layer, lora_layer, aux_t["sin"], aux_t["cos"], seg
            )
            if accumulate_aux:
                acc = acc + extra
            return (xx, acc), None

        n_local = jax.tree_util.tree_leaves(stage["layers"])[0].shape[0]
        (xx, acc), _ = jax.lax.scan(
            body, (xx, jnp.zeros((), jnp.float32)), jnp.arange(n_local)
        )
        xx = xx.reshape(x_flat.shape[0], S * D)
        return (xx, acc) if accumulate_aux else xx

    out = pipeline_apply(
        stage_fn,
        stage_params,
        x.reshape(B, S * D),
        num_microbatches=M,
        aux=aux,
        with_aux_out=accumulate_aux,
    )
    if accumulate_aux:
        y, aux_sum = out
        return y.reshape(B, S, D), aux_sum / M
    return out.reshape(B, S, D)


def lm_head_weight(params: Params, cfg: LlamaConfig) -> jnp.ndarray:
    """[D, V] head matrix (shared with the embedding when tied),
    dequantized if the tree carries an int8 lm_head."""
    if cfg.tie_embeddings:
        return params["embed"].T
    head = params["lm_head"]
    if isinstance(head, dict):  # int8 {"q","scale"} leaf
        head = _maybe_dequant({"lm_head": head}, cfg.dtype)["lm_head"]
    return head


def forward_with_cache(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32 (S=prompt len for prefill, 1 for decode)
    cfg: LlamaConfig,
    cache: Params,  # {"k","v"}: [L, B, S_max, Hkv * hd]
    cache_index,  # scalar int32, or [B] int32: write offset into the cache
    *,
    positions: jnp.ndarray,  # [B, S] absolute positions (rope)
    kv_mask: Optional[jnp.ndarray] = None,  # [B, S_max] valid cache slots
    lora: Optional[Params] = None,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S]; accepted for
    # family-generic callers (the MoE twin routes on it; the dense
    # stack has no router, pads are inert through masked attention)
) -> tuple[jnp.ndarray, Params]:
    """KV-cached forward: returns (logits [B, S, V] float32, new cache).

    This is the decode path ``models/generate.py`` drives — both
    prefill (S = prompt length, cache_index = 0) and autoregressive
    steps (S = 1) go through here, so the layer stack compiles exactly
    twice per shape. No remat (there is no backward pass to trade
    FLOPs against) and always attention over the cache with a traced
    offset (``cache_write_and_attend``). The cache is the layer scan's
    carry (``scan_layers_with_cache``): donate it and it is updated in
    place.
    """
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    lora_layers = lora["layers"] if lora is not None else None

    def layer_fn(x, layer, lora_layer, cache, layer_index):
        # int8-quantized weights (models/quant.py) dequantize inside
        # _decoder_layer: only the current layer's bf16 copy ever
        # materialises, so an 8B model serves from ~8GB of int8 on one
        # v5e instead of 16GB of bf16 that wouldn't fit.
        return _decoder_layer(
            cfg,
            None,  # attention_fn unused: the cache path has its own read
            x,
            layer,
            lora_layer,
            sin,
            cos,
            None,
            cache=cache,
            layer_index=layer_index,
            cache_index=cache_index,
            kv_mask=kv_mask,
        )

    x, new_cache = scan_layers_with_cache(
        layer_fn, x, params["layers"], lora_layers, cache, cfg.layer_windows
    )

    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head_leaf = params.get("lm_head")
    if (
        cfg.w8a8_decode
        and isinstance(head_leaf, dict)
        and set(head_leaf) == {"q", "scale"}
    ):
        # the single biggest decode matmul (D×V): int8 MXU, f32 logits
        logits = _int8_matmul(x, head_leaf, out_dtype=jnp.float32)
    else:
        head = lm_head_weight(params, cfg)
        logits = jnp.einsum(
            "bsd,dv->bsv", x, head.astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    return logits, new_cache


def _maybe_dequant(tree: Params, dtype, keep_int8_matmuls: bool = False) -> Params:
    """Dequantize any {"q","scale"} (int8) or {"q4","scale4"} (int4)
    leaves one level down (the shape a per-layer slice of a quantized
    param tree has). ``keep_int8_matmuls`` leaves int8 leaves packed
    for the W8A8 decode path (int4 always dequantizes — no 4-bit MXU)."""
    from odh_kubeflow_tpu.models.quant import dequantize_tensor

    out = {}
    for k, v in tree.items():
        if isinstance(v, dict) and set(v) == {"q", "scale"}:
            out[k] = v if keep_int8_matmuls else dequantize_tensor(v, dtype)
        elif isinstance(v, dict) and set(v) == {"q4", "scale4"}:
            out[k] = dequantize_tensor(v, dtype)
        else:
            out[k] = v
    return out
