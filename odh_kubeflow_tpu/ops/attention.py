"""Attention implementations.

``dense_attention`` is the XLA-fused baseline: one einsum → softmax →
einsum chain that XLA maps straight onto the MXU. GQA is handled by
reshaping queries to [B, S, Hkv, group, hd] rather than materialising
repeated KV heads (saves Hq/Hkv × KV HBM traffic).

Higher-performance paths plug in behind the same signature:
- pallas flash attention (``ops.pallas.flash_attention``) — tiled,
  never materialises the [S, S] score matrix;
- ring attention (``parallel.ring_attention``) — context-parallel over a
  mesh axis via ``ppermute``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dense_attention(
    q: jnp.ndarray,  # [B, Sq, Hq, hd]
    k: jnp.ndarray,  # [B, Sk, Hkv, hd]
    v: jnp.ndarray,  # [B, Sk, Hkv, hd]
    *,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    segment_ids: Optional[jnp.ndarray] = None,  # [B, S] same-id attends
    kv_mask: Optional[jnp.ndarray] = None,  # [B, Sk] bool, True = attend
    k_positions: Optional[jnp.ndarray] = None,  # [B, Sk] int32
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Returns [B, Sq, Hq, hd]. Scores accumulate in float32.

    ``q_offset`` is the absolute position of q[0] relative to k[0]
    (used by the KV-cache decode path and by ring attention blocks).
    ``kv_mask`` marks which cache slots hold real tokens (the KV-cache
    decode path with ragged right-padded prompts leaves invalid slots
    between each prompt's end and the shared write index).
    ``k_positions`` gives the absolute position each key holds where
    that is not its index (a ring: ``slot_positions`` of
    ``ops/pallas_decode_attention.py``; a negative one was never
    written), and ``window`` lets a query see only the ``window``
    positions that end at its own.
    """
    B, Sq, Hq, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv

    scale = hd**-0.5
    qg = q.reshape(B, Sq, Hkv, group, hd)
    # [B, Hkv, group, Sq, Sk]
    scores = jnp.einsum(
        "bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale

    mask = None
    if causal and (k_positions is not None or window is not None):
        q_pos = (
            jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))[:, None]
            + jnp.arange(Sq)[None, :]
        )[:, :, None]  # [B, Sq, 1]
        k_pos = (
            jnp.arange(Sk)[None, None, :] if k_positions is None
            else k_positions[:, None, :]
        )
        mask = (k_pos <= q_pos) & (k_pos >= 0)
        if window is not None:
            mask = mask & (k_pos > q_pos - window)
        mask = mask[:, None, None, :, :]
    elif causal:
        if getattr(q_offset, "ndim", 0) == 1:
            # per-row offsets ([B] vector — the continuous-batching
            # engine's slots each sit at their own position)
            q_pos = q_offset[:, None, None] + jnp.arange(Sq)[None, :, None]
            mask = (q_pos >= jnp.arange(Sk)[None, None, :])[
                :, None, None, :, :
            ]  # [B, 1, 1, Sq, Sk]
        else:
            q_pos = jnp.arange(Sq)[:, None] + q_offset
            k_pos = jnp.arange(Sk)[None, :]
            mask = q_pos >= k_pos  # [Sq, Sk]
            mask = mask[None, None, None, :, :]
    if segment_ids is not None:
        # [B, Sq, Sk] → [B, 1, 1, Sq, Sk]
        seg = (
            segment_ids[:, :, None] == segment_ids[:, None, :]
        )[:, None, None, :, :]
        mask = seg if mask is None else jnp.logical_and(mask, seg)
    if kv_mask is not None:
        kvm = kv_mask[:, None, None, None, :]  # [B, 1, 1, 1, Sk]
        mask = kvm if mask is None else jnp.logical_and(mask, kvm)
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.float32(-1e30))

    weights = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v)
    return out.reshape(B, Sq, Hq, hd)
