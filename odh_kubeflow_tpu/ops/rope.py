"""Rotary position embeddings (plain RoPE with configurable theta), in
the two pairings models are published with: the half-split one
(``apply_rope``: pairs ``(i, i + hd/2)``, Llama / Mistral; over a
leading slice of the head where the angles are made for one) and the
interleaved one (``apply_rope_interleaved``: pairs ``(2i, 2i + 1)``,
GPT-J's, which Cohere's ``rope_gptj`` names). Both take the same
``rope_angles``; ``stream_angles`` makes the same tables from THREE
position streams (temporal, height, width), each turning its own section
of the frequencies (Qwen2-VL's M-RoPE).

Angles are precomputed once per forward *outside* the layer scan so the
sin/cos tables are computed a single time and live in registers/VMEM
across all layers instead of being re-derived per layer.
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_angles(
    positions: jnp.ndarray,  # [B, S] int32 absolute positions
    head_dim: int,
    theta: float = 500_000.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (sin, cos), each [B, S, head_dim//2], float32."""
    freq_exponents = jnp.arange(0, head_dim // 2, dtype=jnp.float32) / (head_dim // 2)
    inv_freq = theta**-freq_exponents  # [hd/2]
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, S, hd/2]
    return jnp.sin(angles), jnp.cos(angles)


def stream_angles(
    positions: jnp.ndarray,  # [3, B, S] int32: temporal, height, width
    head_dim: int,
    theta: float,
    sections: tuple,  # frequencies a stream turns, in order; sums to hd/2
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``rope_angles`` with frequency ``i`` turned by the stream whose
    section holds it (contiguous ranges, e.g. 16 | 24 | 24 of a head of
    128's 64). Three equal streams give ``rope_angles`` of one."""
    half = head_dim // 2
    assert sum(sections) == half and len(sections) == 3, (sections, half)
    inv_freq = theta ** -(jnp.arange(0, half, dtype=jnp.float32) / half)
    stream = jnp.repeat(
        jnp.arange(3), jnp.asarray(sections), total_repeat_length=half
    )
    # [B, S, hd/2]: each frequency's own stream's position
    pos = jnp.moveaxis(positions.astype(jnp.float32), 0, -1)[..., stream]
    angles = pos * inv_freq
    return jnp.sin(angles), jnp.cos(angles)


def apply_rope(
    x: jnp.ndarray,  # [B, S, H, hd]
    sin: jnp.ndarray,  # [B, S, rd/2]: rd = hd, or a leading slice of it
    cos: jnp.ndarray,  # [B, S, rd/2]
) -> jnp.ndarray:
    """Half-split rotation of the head's first ``rd = 2 * sin.shape[-1]``
    dims; the dims from ``rd`` on pass as they are (a
    ``partial_rotary_factor`` below 1: the angles are made for ``rd``)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    rd = 2 * sin.shape[-1]
    rest = []
    if rd < x.shape[-1]:
        x, rest = x[..., :rd], [x[..., rd:]]
    x1, x2 = jnp.split(x, 2, axis=-1)
    sin = sin[:, :, None, :]
    cos = cos[:, :, None, :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, *rest], axis=-1
    )
    return out.astype(dtype)


def apply_rope_interleaved(
    x: jnp.ndarray,  # [B, S, H, hd]
    sin: jnp.ndarray,  # [B, S, hd/2]
    cos: jnp.ndarray,  # [B, S, hd/2]
) -> jnp.ndarray:
    """Rotate the ADJACENT pairs ``(x[2i], x[2i+1])`` by angle ``i``.

    Written as ``x * cos + partner(x) * sin`` with each lane's partner
    fetched by a rotation of the lanes, not as a reshape to ``[..., hd/2,
    2]``: XLA carries that reshape through the projection that made
    ``x`` and onto its WEIGHT, which it then dequantises, reshapes and
    writes out in full on every call (134 MB a layer at 128 heads;
    PERF.md, PR 26)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    even = (jnp.arange(x.shape[-1]) % 2 == 0)
    # lane 2i's partner is -x[2i+1], lane 2i+1's is x[2i]
    partner = jnp.where(
        even, -jnp.roll(x, -1, axis=-1), jnp.roll(x, 1, axis=-1)
    )
    sin = jnp.repeat(sin, 2, axis=-1)[:, :, None, :]
    cos = jnp.repeat(cos, 2, axis=-1)[:, :, None, :]
    return (x * cos + partner * sin).astype(dtype)
