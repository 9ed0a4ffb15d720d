"""Manifest AI's ``brumby`` family (Brumby-14B-Base), served.

What the block is (the plain reference, ``reference/brumby.py``, writes
the equations out): a dense pre-norm stack of Qwen3-14B's shape in which
EVERY layer's attention is gated power retention of degree 2.
``layer_kinds`` is ``(llama.STATE,)``: no layer keeps keys and values.
What a layer keeps a slot is the float32 state ``[Hkv, R, d_v, d]`` and
its normaliser ``[Hkv, R, d]`` (``ops/pallas_retention.py``: ``R = d / 2
+ 1`` rows of the quadratic feature map, 34 MB a layer a stream at a
head of 128), whatever the stream's length: ``max_len`` costs nothing
and the state IS the cache. They ride the cache's two state stacks
(``llama.STATE_STACKS``, named for a state-space layer: the state under
the first name, the normaliser under the second).

- ``q = h W_q``, ``k = h W_k``, ``v = h W_v`` (GQA: ``num_heads`` query
  heads read ``num_kv_heads`` states); per head ``rms_norm(q) * w_qn``,
  ``rms_norm(k) * w_kn`` (Qwen3's: a PLAIN weight), then RoPE on both
  (half-split pairs, all of the head's dims);
- the gate, one a key/value head, float32: ``log g = log_sigmoid(h W_g +
  b_g)``;
- the recurrence of ``ops/pallas_retention.py``. A part of a prompt goes
  through ``retention_chunk_scan`` with the slot's state in and out; a
  decode step through ``retention_decode_update`` on the stacked state;
- ``x + concat(y) W_o``, then ``x + SwiGLU(RMSNorm(x))``; untied head.

A token that is not one (``token_mask`` False: a bucket's padding, a
slot that decodes nothing) must not move a state: its ``log g`` is 0 and
its key zero, the identity on state and normaliser.

The cached forward is the serving path (``llama.scan_layers_with_cache``
over the one kind: the stack is scanned as it lies). ``forward`` is the
uncached form the tests hold it against; the family has no training path
(the scan has no backward).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from odh_kubeflow_tpu.models import llama
from odh_kubeflow_tpu.models.granite_hybrid import _uses_kernels
from odh_kubeflow_tpu.models.llama import STATE, STATE_STACKS
from odh_kubeflow_tpu.ops import pallas_retention
from odh_kubeflow_tpu.ops.norms import rms_norm
from odh_kubeflow_tpu.ops.rope import apply_rope, rope_angles

Params = dict[str, Any]
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151_936
    hidden_size: int = 5120
    intermediate_size: int = 17_408
    num_layers: int = 40
    # every layer is a retention layer: none keeps keys and values
    layer_kinds: tuple = (STATE,)
    num_heads: int = 40
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1_000_000.0
    rms_norm_eps: float = 1e-6
    retention_chunk: int = pallas_retention.DEFAULT_CHUNK
    retention_eps: float = pallas_retention.EPS
    dtype: Any = jnp.bfloat16

    # ``generate.family_forward`` finds the cached forward here
    family_module = "odh_kubeflow_tpu.models.brumby"

    @staticmethod
    def tiny(**kw) -> "BrumbyConfig":
        """Unit-test shape: four layers, 4 query heads onto 2 states of
        a head of 16 (nine rows of ``phi``)."""
        d = dict(
            vocab_size=256, hidden_size=64, intermediate_size=96, num_layers=4,
            num_heads=4, num_kv_heads=2, head_dim=16, rope_theta=10_000.0,
            retention_chunk=8,
        )
        d.update(kw)
        return BrumbyConfig(**d)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def state_leaves(self, dtype) -> dict:
        """A retention layer's state, one row of one layer: name ->
        (shape, dtype) (``generate.init_cache`` puts ``[layers, batch]``
        in front). Both float32 whatever the cache's dtype: every token
        of a stream decays and adds to them."""
        state, norm = STATE_STACKS
        d, R = self.head_dim, pallas_retention.phi_rows(self.head_dim)
        return {
            # as ``ops/pallas_retention.py`` lays it: phi's row, d_v
            # along the sublanes, phi's index along the lanes
            state: ((self.num_kv_heads, R, d, d), F32),
            norm: ((self.num_kv_heads, R, d), F32),
        }


def init_params(key: jax.Array, cfg: BrumbyConfig, dtype=F32) -> Params:
    """Seeded weights in the served layout, everything under ``layers``
    [L, ...]. Norm weights are drawn off 1 so that they show; the gate's
    bias so that ``g`` lies in 0.98-0.9995 (half-lives of 35 to 1400
    tokens: a normal draw puts half the gates under 0.5 and a stream's
    state then holds its last two tokens)."""
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    k = iter(jax.random.split(key, 16))

    def dense(shape, fan_in, dt=dtype):
        return (jax.random.normal(next(k), shape, F32) * fan_in**-0.5).astype(dt)

    def off_one(shape):
        return (1 + 0.1 * jax.random.normal(next(k), shape, F32)).astype(dtype)

    g = jax.random.uniform(next(k), (L, cfg.num_kv_heads), F32, 0.98, 0.9995)
    return {
        "embed": dense((cfg.vocab_size, D), D),
        "lm_head": dense((D, cfg.vocab_size), D),
        "layers": {
            "attn_norm": off_one((L, D)),
            "wq": dense((L, D, cfg.q_dim), D),
            "wk": dense((L, D, cfg.kv_dim), D),
            "wv": dense((L, D, cfg.kv_dim), D),
            "wo": dense((L, cfg.q_dim, D), cfg.q_dim),
            "q_norm": off_one((L, cfg.head_dim)),
            "k_norm": off_one((L, cfg.head_dim)),
            "gate_w": dense((L, D, cfg.num_kv_heads), D, F32),
            "gate_b": jnp.log(g) - jnp.log1p(-g),  # sigmoid^-1
            "mlp_norm": off_one((L, D)),
            "w_gate": dense((L, D, F), D),
            "w_up": dense((L, D, F), D),
            "w_down": dense((L, F, D), F),
        },
        "final_norm": off_one((D,)),
    }


def _mixer_inputs(cfg, h, lw, sin, cos, token_mask):
    """A retention mixer up to its recurrence, on ``h`` [B, S, D]:
    ``(q [B, S, Hq, d], k, v [B, S, Hkv, d], log_g [B, S, Hkv] float32)``
    with masked positions' ``log g`` and key zeroed. ``lw`` is
    dequantised."""
    B, S, _ = h.shape
    d = cfg.head_dim
    # plain [B, S, width] matrices up to the barrier: without it XLA
    # carries the split into heads onto the weight (PERF.md, PR 26)
    q, k, v = jax.lax.optimization_barrier((
        h @ lw["wq"].astype(h.dtype), h @ lw["wk"].astype(h.dtype),
        h @ lw["wv"].astype(h.dtype),
    ))
    with jax.named_scope("retention_gate"):
        log_g = jax.nn.log_sigmoid(jnp.dot(
            h.astype(F32), lw["gate_w"].astype(F32),
            precision=jax.lax.Precision.HIGHEST,
        ) + lw["gate_b"].astype(F32))
    with jax.named_scope("retention_norm_rope"):
        q = rms_norm(q.reshape(B, S, cfg.num_heads, d), lw["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k.reshape(B, S, cfg.num_kv_heads, d), lw["k_norm"], cfg.rms_norm_eps)
        q, k = apply_rope(q, sin, cos), apply_rope(k, sin, cos)
    if token_mask is not None:
        log_g = jnp.where(token_mask[..., None], log_g, 0.0)
        k = jnp.where(token_mask[..., None, None], k, jnp.zeros((), k.dtype))
    return q, k, v.reshape(B, S, cfg.num_kv_heads, d), log_g


def _retention_cached(cfg, h, lw, sin, cos, cache, cache_layer, token_mask):
    """A layer's mixer through the cache's state stacks."""
    state_name, norm_name = cache_layer.names
    at = cache_layer.index
    state, norm = cache[state_name], cache[norm_name]
    q, k, v, log_g = _mixer_inputs(cfg, h, lw, sin, cos, token_mask)
    if h.shape[1] == 1:
        step = (
            pallas_retention.retention_decode_update if _uses_kernels()
            else pallas_retention.retention_step_plain
        )
        y, state, norm = step(
            q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], state, norm, at,
            eps=cfg.retention_eps,
        )
        y = y[:, None]
    else:
        init = (
            jax.lax.dynamic_index_in_dim(state, at, 0, False),
            jax.lax.dynamic_index_in_dim(norm, at, 0, False),
        )
        if _uses_kernels():
            y, fin, zfin = pallas_retention.retention_chunk_scan(
                q, k, v, log_g, *init, chunk=cfg.retention_chunk,
                eps=cfg.retention_eps,
            )
        else:
            y, fin, zfin = pallas_retention.retention_scan_plain(
                q, k, v, log_g, *init, eps=cfg.retention_eps
            )
        state = jax.lax.dynamic_update_index_in_dim(state, fin, at, 0)
        norm = jax.lax.dynamic_update_index_in_dim(norm, zfin, at, 0)
    return y.astype(h.dtype), {**cache, state_name: state, norm_name: norm}


def _out_and_mlp(cfg, x, y, lw):
    """``x + concat(y) W_o``, then ``x + SwiGLU(RMSNorm(x))``."""
    B, S, _ = x.shape
    x = x + y.reshape(B, S, cfg.q_dim) @ lw["wo"].astype(x.dtype)
    h = rms_norm(x, lw["mlp_norm"], cfg.rms_norm_eps)
    act = jax.nn.silu(h @ lw["w_gate"].astype(h.dtype)) * (
        h @ lw["w_up"].astype(h.dtype)
    )
    return x + act @ lw["w_down"].astype(h.dtype)


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
    cfg: BrumbyConfig,
    cache: Params,  # ``generate.init_cache(cfg, ...)``
    cache_index,  # scalar int32, or [B] int32: unused but for its shape
    *,
    positions: jnp.ndarray,  # [B, S]
    kv_mask: Optional[jnp.ndarray] = None,  # there are no keys to mask
    lora: Optional[Params] = None,
    token_mask: Optional[jnp.ndarray] = None,  # [B, S] bool; False = no token
) -> tuple[jnp.ndarray, Params]:
    """Cached forward (prefill parts and decode steps alike): returns
    (logits [B, S, V] float32, new cache). A row's tokens CONTINUE the
    state the cache holds for it, wherever ``cache_index`` says they
    would be written (nothing is written at a position), and a
    ``token_mask`` row must be a run of True then False."""
    del kv_mask
    if lora is not None:
        raise NotImplementedError("brumby has no adapter path yet")
    if tokens.shape[1] > 1 and getattr(cache_index, "ndim", 0) == 1:
        raise NotImplementedError(
            "several tokens a row at per-row offsets (speculative verify) "
            "would need the state after each of them"
        )
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    x = _embed(params, cfg, tokens)

    def layer_fn(x, layer, _lora_layer, cache, cache_layer):
        lw = llama._maybe_dequant(layer, cfg.dtype)
        h = rms_norm(x, lw["attn_norm"], cfg.rms_norm_eps)
        y, cache = _retention_cached(
            cfg, h, lw, sin, cos, cache, cache_layer, token_mask
        )
        return _out_and_mlp(cfg, x, y, lw), cache

    x, cache = llama.scan_layers_with_cache(
        layer_fn, x, params["layers"], None, cache, cfg.layer_kinds
    )
    return _head(params, cfg, x), cache


def forward(
    params: Params,
    tokens: jnp.ndarray,  # [B, S] int32
    cfg: BrumbyConfig,
    token_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Uncached forward over whole rows, the recurrence token by token
    from a zero state: logits [B, S, V] float32."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sin, cos = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    x = _embed(params, cfg, tokens)
    zeros = [
        jnp.zeros((B,) + shape, dt)
        for shape, dt in cfg.state_leaves(cfg.dtype).values()
    ]
    for depth in range(cfg.num_layers):
        lw = llama._maybe_dequant(llama.take_layer(params["layers"], depth), cfg.dtype)
        h = rms_norm(x, lw["attn_norm"], cfg.rms_norm_eps)
        q, k, v, log_g = _mixer_inputs(cfg, h, lw, sin, cos, token_mask)
        y, _, _ = pallas_retention.retention_scan_plain(
            q, k, v, log_g, *zeros, eps=cfg.retention_eps
        )
        x = _out_and_mlp(cfg, x, y.astype(x.dtype), lw)
    return _head(params, cfg, x)
