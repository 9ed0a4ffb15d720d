"""Pallas TPU attention over one layer of the stacked KV cache, read
where it lies (``name="decode_attend"``).

The cached forward (``models/llama.py`` ``cache_write_and_attend``)
keeps the whole cache ``{"k","v"}: [L, B, S_max, Hkv*hd]`` as the layer
scan's carry. XLA cannot feed a layer of it to ``dense_attention``
without first copying that layer out of the stack (a ``dynamic-slice``
of 67 MB an operand at 16 slots x 2048 x 8 x 128, every layer of every
decode step; PERF.md, PR 25). This kernel takes the STACK as its
operand and the layer index by scalar prefetch: the K/V ``BlockSpec``
index maps pick ``[layer, row, kv-block]`` of the array in HBM, so the
only cache bytes moved are the blocks attended over.

Per row the bounds are known before the blocks are fetched (each row's
first query position is scalar-prefetched too), so kv-blocks wholly
past a row's last query, or wholly before the ``window`` positions its
first query can see, are neither fetched nor computed: their softmax
weights are exactly 0 in the dense form. The grid still visits them;
the index map repeats the last live block (no new DMA) and the body is
predicated off.

A layer whose ``S_max`` is shorter than the positions it is asked about
is a RING: position ``p`` lies in slot ``p % S_max`` (a window layer
keeps only ``window + the widest part written at once`` positions;
``models/generate.py`` ``init_cache``). The live positions of a row
block are contiguous, so their blocks are contiguous modulo the number
of blocks: the walk starts at the block of the oldest live position and
wraps. Which position a slot holds comes as an operand
(``slot_positions``), so the mask is one comparison whatever the layout;
"no window" is a window wider than any position.

Numerics are ``dense_attention``'s: float32 scores (``q @ k^T`` at
float32 accumulation, times ``hd ** -0.5``), masked with -1e30,
float32 softmax statistics, weights cast to the cache's dtype for the
``@ v`` matmul at float32 accumulation; the normalisation is applied to
the float32 accumulator (online softmax over kv-blocks).

A layer whose queries attend only the keys an indexer picked for them
(``ops/sparse_attention.py``) hands the selection in as it stands: the
indexer's float32 scores ``[B, S, S_max]`` and, a query, a threshold and
the position of the last tie that fits (``select``). The keys a query
keeps are those whose score lies above its threshold or equals it at a
position up to that edge; the kernel reads a ``[positions, block_k]``
tile of the scores beside each kv-block and the mask is two more
compares. Without ``select`` neither operand nor compare exists.

All ``Hkv`` heads of a kv-block are one grid step (a ``[block_k,
Hkv*hd]`` tile whose lane slices are stacked into one head-batched
matmul): at batch 16 a step per head would cost more in grid overhead
than the bytes it moves, and a Python loop over heads traced eight
times the operations for a slower kernel (PERF.md, PR 25).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# kv positions a grid step reads: 512 x 8 heads x 128 x bf16 = 1 MB an
# operand, ~2.5 us of HBM time against ~0.35 us of step overhead; finer
# blocks skip more of a short row's dead tail but pay more steps
DEFAULT_BLOCK_K = 512
# query rows (positions x group) a grid step holds; the float32
# accumulator and statistics for all heads scale with it
DEFAULT_BLOCK_ROWS = 256
_ROW_ALIGN = 16  # bf16 sublane tile
# what keeps a wider model (more KV heads, float32) inside scoped VMEM:
# a K or V tile of at most 1 MB, float32 state for at most 2048 rows
_MAX_TILE_BYTES = 1 << 20
_MAX_STATE_ROWS = 2048
# "no window": wider than any position, and far from int32's edge
NO_WINDOW = 1 << 30
_NEVER = 1 << 30  # the position of a slot that holds nothing to attend


def supported(cache_leaf, head_dim: int) -> bool:
    """Whether the kernel's tiles exist for this cache: kv positions
    and a position's row in whole lane tiles, heads that are half a
    lane tile or whole ones."""
    _, _, s_max, width = cache_leaf.shape
    return s_max % 128 == 0 and width % 128 == 0 and head_dim % 64 == 0


def slot_positions(q_off, steps: int, s_max: int):
    """[B, s_max] int32: the absolute position each slot of a row holds
    once ``steps`` positions from ``q_off`` on are written, position
    ``p`` in slot ``p % s_max``: the newest position that is congruent
    to the slot. Negative: the slot was never written. Where nothing
    wrapped (every position < s_max) a written slot holds its index."""
    last = (jnp.asarray(q_off, jnp.int32) + (steps - 1))[:, None]
    slot = jnp.arange(s_max, dtype=jnp.int32)[None, :]
    return last - jnp.mod(last - slot, s_max)


def live_range(first_pos, last_pos, *, window, block_k, num_k):
    """(first block, number of blocks) a query span ``first_pos ..
    last_pos`` can see: from the block of the oldest position inside
    the first query's window to the block of the last query's own."""
    lo = jnp.maximum(first_pos - window + 1, 0) // block_k
    return lo, jnp.clip(last_pos // block_k - lo + 1, 1, num_k)


def block_k_for(cache_leaf, block_k: int = DEFAULT_BLOCK_K) -> int:
    """kv positions a grid step reads of this stack: ``block_k``, cut to
    a tile of at most 1 MB, in whole lane tiles that divide the stack."""
    _, _, s_max, width = cache_leaf.shape
    tile_k = _MAX_TILE_BYTES // (width * cache_leaf.dtype.itemsize) // 128 * 128
    return math.gcd(s_max, min(block_k, max(tile_k, 128)))


def _kernel(layer_ref, qoff_ref, qpos_ref, q_ref, k_ref, v_ref, kpos_ref,
            *rest, heads, head_dim, window, live, scale, group=None):
    # ``group``: a selection is handed in (three more operands), and a
    # row block is ``group`` runs of the same positions, one a member
    *select, o_ref, acc, m, l = rest
    del layer_ref
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_rows = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m[...] = jnp.full(m.shape, _NEG_INF, m.dtype)
        l[...] = jnp.zeros(l.shape, l.dtype)
        acc[...] = jnp.zeros(acc.shape, acc.dtype)

    @pl.when(j < live(qoff_ref[b], i)[1])
    def _block():
        k_pos, q_pos = kpos_ref[...], qpos_ref[...]
        mask = (k_pos <= q_pos) & (k_pos > q_pos - window)
        # a bf16 operand has one pass to offer: a process-wide
        # jax_default_matmul_precision of "highest" must not reach
        # Mosaic with it (float32 operands follow the configuration)
        precision = (
            jax.lax.Precision.DEFAULT if k_ref.dtype.itemsize < 4 else None
        )

        def by_head(ref):  # [block_k, heads * hd] -> [heads, block_k, hd]
            return jnp.stack([
                ref[:, h * head_dim:(h + 1) * head_dim] for h in range(heads)
            ])

        s = jax.lax.dot_general(
            q_ref[...], by_head(k_ref), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=precision,
        ) * scale
        s = jnp.where(mask[None], s, _NEG_INF)
        if group is not None:
            score_ref, thr_ref, cut_ref = select
            sc, thr = score_ref[...], thr_ref[...]
            keep = (sc > thr) | ((sc == thr) & (k_pos <= cut_ref[...]))
            s = jnp.where(
                keep[None, None], s.reshape(heads, group, *keep.shape), _NEG_INF
            ).reshape(s.shape)
        m_prev = m[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l[...] = alpha * l[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = alpha * acc[...] + jax.lax.dot_general(
            p.astype(v_ref.dtype), by_head(v_ref),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32, precision=precision,
        )
        m[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc[...] / l[...]).astype(o_ref.dtype)


def _live_blocks(q_off, i, *, block_rows, rows_last, group, **geometry):
    """``live_range`` of row-block ``i`` of a row starting at ``q_off``."""
    last_row = jnp.minimum((i + 1) * block_rows - 1, rows_last)
    return live_range(
        q_off + (i * block_rows) // group, q_off + last_row // group,
        **geometry,
    )


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "block_rows", "interpret")
)
def decode_attend(
    q: jnp.ndarray,  # [B, S, Hq, hd]
    cache_k: jnp.ndarray,  # [L, B, S_max, Hkv*hd], this step already written
    cache_v: jnp.ndarray,
    layer_index,  # scalar int32
    q_offset,  # scalar or [B] int32: absolute position of q[:, 0]
    kv_mask=None,  # [B, S_max] bool, True = attend
    *,
    window=None,  # positions a query sees, its own included; None: all
    select=None,  # (scores [B, S, S_max] f32, thr [B, S] f32, cut [B, S] i32)
    block_k: int = DEFAULT_BLOCK_K,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """``dense_attention(q, k[layer], v[layer], causal=True,
    q_offset=q_offset, kv_mask=kv_mask, window=window)`` without taking
    the layer out of the stack. With a ``window`` the layer is a ring:
    position ``p`` is read from slot ``p % S_max`` (``kv_mask`` is by
    slot). With ``select`` a query sees, of those, only the keys whose
    score lies above its ``thr`` or equals it at a position up to its
    ``cut`` (``ops/sparse_attention.selected``). Returns [B, S, Hq, hd]."""
    B, S, Hq, hd = q.shape
    _, _, S_max, width = cache_k.shape
    heads = width // hd
    group = Hq // heads
    assert heads * hd == width and group * heads == Hq, (q.shape, cache_k.shape)
    block_k = block_k_for(cache_k, block_k)
    block_rows = min(
        block_rows,
        max(_MAX_STATE_ROWS // heads // _ROW_ALIGN * _ROW_ALIGN, _ROW_ALIGN),
    )

    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    if select is None:
        # rows of one kv head: (position, member of its group), padded to
        # whole sublane tiles / row blocks; a padded row's output is dropped
        rows = S * group
        rows_p = -(-rows // _ROW_ALIGN) * _ROW_ALIGN
        if rows_p > block_rows:
            rows_p = -(-rows // block_rows) * block_rows
        block_rows = min(block_rows, rows_p)
        qg = q.reshape(B, S, heads, group, hd).transpose(0, 2, 1, 3, 4)
        qg = qg.reshape(B, heads, rows, hd)
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_p - rows), (0, 0)))
        q_pos = q_off[:, None] + (jnp.arange(rows_p, dtype=jnp.int32) // group)
    else:
        # a row block holds ``per`` positions, ``group`` times over: rows
        # (member, position), so that the positions' one tile of scores
        # masks every member's run of rows as it lies
        per = min(
            max(block_rows // group // _ROW_ALIGN, 1) * _ROW_ALIGN,
            -(-S // _ROW_ALIGN) * _ROW_ALIGN,
        )
        S_p = -(-S // per) * per
        block_rows, rows, rows_p = per * group, S_p * group, S_p * group
        pad_s = ((0, 0), (0, S_p - S))
        qg = jnp.pad(q, pad_s + ((0, 0), (0, 0)))
        qg = qg.reshape(B, S_p // per, per, heads, group, hd)
        qg = qg.transpose(0, 3, 1, 4, 2, 5).reshape(B, heads, rows_p, hd)
        r = jnp.arange(rows_p, dtype=jnp.int32)
        q_pos = q_off[:, None] + (r // block_rows * per + r % per)
        scores, thr, cut = select
        if S_p != S:
            scores = jnp.pad(scores, pad_s + ((0, 0),))
            thr, cut = jnp.pad(thr, pad_s), jnp.pad(cut, pad_s)
        select = (
            scores.astype(jnp.float32), thr.astype(jnp.float32)[..., None],
            cut.astype(jnp.int32)[..., None],
        )
    if window is None:
        # a layer that sees everything holds position p in slot p
        k_pos = jnp.broadcast_to(jnp.arange(S_max, dtype=jnp.int32), (B, S_max))
        window = NO_WINDOW
    else:
        k_pos = slot_positions(q_off, S, S_max)
    held = k_pos >= 0 if kv_mask is None else (k_pos >= 0) & kv_mask
    k_pos = jnp.where(held, k_pos, _NEVER)[:, None, :]
    layer = jnp.asarray(layer_index, jnp.int32).reshape(1)
    num_q, num_k = rows_p // block_rows, S_max // block_k
    live = functools.partial(
        _live_blocks, block_rows=block_rows, rows_last=rows - 1, group=group,
        window=window, block_k=block_k, num_k=num_k,
    )

    def kv_block(b, i, j, q_off):
        # past the last live block the index stands still: no new DMA
        first, count = live(q_off[b], i)
        return (first + jnp.minimum(j, count - 1)) % num_k

    kv_spec = pl.BlockSpec(
        (None, None, block_k, width),
        lambda b, i, j, layer, q_off: (layer[0], b, kv_block(b, i, j, q_off), 0),
    )
    select_specs, geometry = [], {}
    if select is not None:
        geometry = {"group": group}
        per_query = pl.BlockSpec((None, per, 1), lambda b, i, j, *_: (b, i, 0))
        select_specs = [
            pl.BlockSpec(
                (None, per, block_k),
                lambda b, i, j, layer, q_off: (b, i, kv_block(b, i, j, q_off)),
            ),
            per_query,
            per_query,
        ]
    out = pl.pallas_call(
        functools.partial(
            _kernel, heads=heads, head_dim=hd, window=window, live=live,
            scale=hd**-0.5, **geometry,
        ),
        name="decode_attend",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, num_q, num_k),
            in_specs=[
                pl.BlockSpec(
                    (None, block_rows, 1), lambda b, i, j, *_: (b, i, 0)
                ),
                pl.BlockSpec(
                    (None, heads, block_rows, hd),
                    lambda b, i, j, *_: (b, 0, i, 0),
                ),
                kv_spec,
                kv_spec,
                pl.BlockSpec(
                    (None, 1, block_k),
                    lambda b, i, j, layer, q_off: (b, 0, kv_block(b, i, j, q_off)),
                ),
                *select_specs,
            ],
            out_specs=pl.BlockSpec(
                (None, heads, block_rows, hd), lambda b, i, j, *_: (b, 0, i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((heads, block_rows, hd), jnp.float32),
                pltpu.VMEM((heads, block_rows, 1), jnp.float32),
                pltpu.VMEM((heads, block_rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, heads, rows_p, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(layer, q_off, q_pos[:, :, None], qg, cache_k, cache_v, k_pos,
      *(select or ()))
    if select is not None:
        out = out.reshape(B, heads, S_p // per, group, per, hd)
        return out.transpose(0, 2, 4, 1, 3, 5).reshape(B, S_p, Hq, hd)[:, :S]
    out = out[:, :, :rows].reshape(B, heads, S, group, hd)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, S, Hq, hd)
