"""Pallas TPU SwiGLU over the experts a chip HOLDS, read from the int8
banks where they lie (``name="moe_local_ffn"``).

An expert-parallel share of a mixture layer holds some of the layer's
experts (``models/moe.py`` ``local_expert_ffn``). A decode step of 16
rows hits about ten of sixteen held experts, one to three rows each; a
prefill part of 1024 rows gives each about 64. Either way the work is a
few row tiles against whole expert matrices, so the kernel is bound by
the weight bytes, and the two things it must not do are read an expert
nobody was routed to and copy a bank to get at it:

- the banks stay STACKED, ``[L, E_held, D, F]`` int8 with one float32
  scale per output channel, as the served tree holds them; the layer
  and each row tile's expert come by scalar prefetch and pick the block
  in HBM (a bank sliced out of the stack by XLA to feed a custom call
  is a copy of all of it, every layer of every step; PERF.md, PR 25);
- rows come sorted by expert, each expert's group padded to the row
  tile; only the ``n_live`` tiles that hold rows are computed. The grid
  still visits the rest: their index maps repeat the last live blocks
  (no new DMA) and their bodies are predicated off.

Per live tile the expert's width is walked in blocks of ``block_f``:
``h = silu(x G[:, f]) * (x U[:, f])`` and ``acc += h D[f, :]``, so the
gate, up and down matrices are each read once and nothing of width F
reaches HBM. int8 blocks are widened to the activations' dtype in VMEM
(exact) and the scales applied to the float32 products.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# rows of a tile: a decode step's groups are one to three rows (the
# bf16 sublane tile), a prefill part's about 64
DECODE_BLOCK_M = 16
PREFILL_BLOCK_M = 128
# expert width a grid step covers: three int8 blocks of [4096, 512] are
# 6 MB, 7 us of HBM time against ~0.35 us of step overhead
DEFAULT_BLOCK_F = 512
# double-buffered int8 blocks, their widened copies, the row tile and
# the float32 accumulator: ~30 MB at hidden 4096 (the default scoped
# limit is 16 of the chip's 128)
_VMEM_LIMIT = 64 << 20


def block_m_for(rows: int, expected: Optional[float] = None) -> int:
    """The row tile for a call that routes ``rows`` tokens. ``expected``
    is the rows ONE expert can expect of them (tokens x choices / the
    router's width), where the caller knows the router's width: the tile
    is then the power of two that holds them, between the two tiles, so
    that an expert's group is one tile and little of it is padding. A
    caller that does not say gets the tile its tokens alone give."""
    if rows <= 64:
        return DECODE_BLOCK_M
    if expected is None:
        return PREFILL_BLOCK_M
    tile = DECODE_BLOCK_M
    while tile < min(expected, PREFILL_BLOCK_M):
        tile *= 2
    return tile


def _kernel(layer_ref, expert_ref, live_ref, x_ref, g_ref, u_ref, d_ref,
            gs_ref, us_ref, ds_ref, o_ref, acc):
    del layer_ref, expert_ref
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < live_ref[0]

    @pl.when(live & (j == 0))
    def _init():
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(live)
    def _block():
        x = x_ref[...]
        # a bf16 operand has one pass to offer: a process-wide
        # jax_default_matmul_precision of "highest" must not reach
        # Mosaic with it (float32 operands follow the configuration)
        dot = functools.partial(
            jax.lax.dot_general,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.DEFAULT if x.dtype.itemsize < 4 else None,
        )
        g = dot(x, g_ref[...].astype(x.dtype)) * gs_ref[...]
        u = dot(x, u_ref[...].astype(x.dtype)) * us_ref[...]
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        acc[...] += dot(h, d_ref[...].astype(x.dtype))

    @pl.when(live & (j == pl.num_programs(1) - 1))
    def _finalize():
        o_ref[...] = (acc[...] * ds_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_f", "interpret")
)
def moe_local_ffn(
    x_sorted: jnp.ndarray,  # [M, D] rows sorted by expert, groups padded
    tile_expert: jnp.ndarray,  # [M // block_m] int32, held-expert index
    n_live: jnp.ndarray,  # [1] int32: tiles that hold rows
    layer,  # scalar int32: which layer of the stacked banks
    gate: dict,  # {"q": [L, E, D, F] int8, "scale": [L, E, 1, F] f32}
    up: dict,
    down: dict,  # {"q": [L, E, F, D] int8, "scale": [L, E, 1, D] f32}
    *,
    block_m: int,
    block_f: int = DEFAULT_BLOCK_F,
    interpret: bool = False,
) -> jnp.ndarray:
    """Each row through its tile's expert: ``(silu(x G) * (x U)) D``.
    Returns [M, D] in ``x_sorted``'s dtype; rows of tiles at or past
    ``n_live`` are NOT written (the caller never reads them)."""
    M, D = x_sorted.shape
    F = gate["q"].shape[-1]
    if F % min(block_f, F):
        # a width the block does not divide (768): the widest whole
        # number of lane tiles that does
        block_f = max(b for b in range(128, block_f, 128) if F % b == 0)
    block_f = min(block_f, F)
    assert M % block_m == 0 and F % block_f == 0, (M, F, block_m, block_f)
    num_f = F // block_f
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def tile(i, live):
        # past the last live tile every index stands still: no new DMA
        return jnp.maximum(jnp.minimum(i, live[0] - 1), 0)

    def f_block(i, j, live):
        return jnp.where(i < live[0], j, num_f - 1)

    def rows(i, j, layer, expert, live):
        return (tile(i, live), 0)

    def wide(i, j, layer, expert, live):  # a [D or 1, block_f] block
        return (layer[0], expert[tile(i, live)], 0, f_block(i, j, live))

    def tall(i, j, layer, expert, live):  # a [block_f, D] block
        return (layer[0], expert[tile(i, live)], f_block(i, j, live), 0)

    def whole(i, j, layer, expert, live):
        return (layer[0], expert[tile(i, live)], 0, 0)

    return pl.pallas_call(
        _kernel,
        name="moe_local_ffn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(M // block_m, num_f),
            in_specs=[
                pl.BlockSpec((block_m, D), rows),
                pl.BlockSpec((None, None, D, block_f), wide),
                pl.BlockSpec((None, None, D, block_f), wide),
                pl.BlockSpec((None, None, block_f, D), tall),
                pl.BlockSpec((None, None, 1, block_f), wide),
                pl.BlockSpec((None, None, 1, block_f), wide),
                pl.BlockSpec((None, None, 1, D), whole),
            ],
            out_specs=pl.BlockSpec((block_m, D), rows),
            scratch_shapes=[pltpu.VMEM((block_m, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, D), x_sorted.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        layer, tile_expert.astype(jnp.int32), n_live.astype(jnp.int32),
        x_sorted, gate["q"], up["q"], down["q"],
        gate["scale"], up["scale"], down["scale"],
    )
