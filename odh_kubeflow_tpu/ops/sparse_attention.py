"""Attention that chooses which keys it reads: the pieces of a layer whose
queries attend only the ``topk`` keys a learned INDEXER picks
(DeepSeek-Sparse-Attention's lightning indexer, as Keye-VL-2.0's
``sa_config`` sizes it). ``models/llama.py`` ``indexed_write_and_attend``
puts them together over the cache; ``reference/keye_vl.py`` writes the
equations out.

- ``index_scores`` (Pallas, ``name="index_scores"``): ``I_ts = sum_j w_tj
  ReLU(q^I_tj . k^I_s)`` over one layer of the stacked indexer keys
  ``[L, B, d_i, S_max]``, read WHERE IT LIES: the layer by scalar
  prefetch, and of a row only the blocks up to its last query (XLA would
  copy the layer out of the carried stack first; PERF.md, PR 25). A key
  is one narrow head (``d_i`` = 64), so positions lie along the LANES: a
  block is a ``[d_i, block_k]`` tile with no padding, and ``q @ tile``
  puts a query's scores along the lanes too. bf16 operands, float32
  accumulation; ReLU, the heads' weights and their sum in float32; a
  score of ``-0.0`` is written ``+0.0``.
- ``select_threshold`` / ``selected``: which keys a query keeps, with no
  sort: the ``topk``-th largest score by ``ops/select.py``'s search, and
  a tie at that edge going to the LOWER position, as ``lax.top_k`` has
  it (the reference's rule).
- ``compact_positions``: the kept positions of each row in position
  order, with neither a sort nor a scatter: counts by chunks of 128
  lanes, then every output slot finds its chunk and its lane by
  compares and two small matmuls (integers below 256: exact in bf16).
- ``gather_rows``: the kept rows of the 4-D key and value stacks by ONE
  gather each, indexed ``[layer, row, position]`` (no layer is sliced
  out). A decode step then attends over ``[B, topk]`` gathered rows
  through ``decode_attend`` itself; a prefill part, whose eight query
  heads a key/value head would fill an MXU tile to a sixteenth over
  gathered rows, attends under the selection as a MASK
  (``decode_attend``'s ``select`` operands).

Everything but ``index_scores`` is plain XLA under a named scope."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from odh_kubeflow_tpu.ops.select import kth_largest

F32 = jnp.float32
NEG = -jnp.inf
# kv positions a grid step reads of the indexer's keys. One query a row
# (a decode step): 4096 x 64 x bf16 = 512 KB, 0.64 us of HBM time against
# ~0.35 us of step overhead. A part of a prompt: the float32 scores of a
# head for [block_q, block_k] are the tile that has to fit
DECODE_BLOCK_K = 4096
PREFILL_BLOCK_K = 1024
PREFILL_BLOCK_Q = 256
_CHUNK = 128  # lanes: the grain ``compact_positions`` counts by


def supported(ik_leaf) -> bool:
    """Whether the kernel's tiles exist for this stack of indexer keys:
    positions in whole lane tiles, the head's dims in whole bf16 sublane
    tiles."""
    _, _, d, s_max = ik_leaf.shape
    return s_max % 128 == 0 and d % 16 == 0


def _block_k(s_max: int, want: int) -> int:
    """The largest multiple of 128 that divides ``s_max`` and is no more
    than ``want``."""
    return max(b for b in range(128, min(want, s_max) + 1, 128) if s_max % b == 0)


def _live(q_off, i, *, block_q, S, block_k, num_k):
    """kv blocks that hold a position some query of block ``i`` of a row
    starting at ``q_off`` can see."""
    last = q_off + jnp.minimum((i + 1) * block_q, S) - 1
    return jnp.clip(last // block_k + 1, 1, num_k)


def _no_negative_zero(x):
    """``-0.0`` written ``+0.0``: ``lax.top_k`` orders the two, and a
    ranking must not hang on the sign of a zero."""
    return jnp.where(x == 0.0, 0.0, x)


def _precision(ref):
    # a bf16 operand has one pass to offer (``pallas_decode_attention``)
    return jax.lax.Precision.DEFAULT if ref.dtype.itemsize < 4 else None


def _kernel_one(layer_ref, qoff_ref, q_ref, w_ref, ik_ref, o_ref, *, live):
    """One query a row: q [H, d], w [H, 1], keys [d, block_k]."""
    del layer_ref
    b, j = pl.program_id(0), pl.program_id(2)
    alive = j < live(qoff_ref[b], 0)

    @pl.when(alive)
    def _block():
        s = jax.lax.dot_general(
            q_ref[...], ik_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=F32, precision=_precision(ik_ref),
        )
        o_ref[...] = _no_negative_zero(jnp.sum(
            jnp.maximum(s, 0.0) * w_ref[...], axis=0, keepdims=True
        ))

    @pl.when(jnp.logical_not(alive))
    def _dead():
        o_ref[...] = jnp.full(o_ref.shape, NEG, o_ref.dtype)


def _kernel_many(layer_ref, qoff_ref, q_ref, w_ref, ik_ref, o_ref, *, heads, live):
    """A block of queries: q [H, block_q, d], w [block_q, H], keys [d,
    block_k]; the heads one after the other into one float32 tile."""
    del layer_ref
    b, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    alive = j < live(qoff_ref[b], i)

    @pl.when(alive)
    def _block():
        keys = ik_ref[...]
        acc = jnp.zeros(o_ref.shape, F32)
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[h], keys, (((1,), (0,)), ((), ())),
                preferred_element_type=F32, precision=_precision(ik_ref),
            )
            acc = acc + jnp.maximum(s, 0.0) * w_ref[:, h:h + 1]
        o_ref[...] = _no_negative_zero(acc)

    @pl.when(jnp.logical_not(alive))
    def _dead():
        o_ref[...] = jnp.full(o_ref.shape, NEG, o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k", "interpret"))
def index_scores(
    qi: jnp.ndarray,  # [B, S, H, d] the indexer's queries, rotated
    w: jnp.ndarray,  # [B, S, H] float32: a weight a head
    ik: jnp.ndarray,  # [L, B, d, S_max] the stacked keys, this call's written
    layer_index,  # scalar int32
    q_offset,  # scalar or [B] int32: the position of qi[:, 0]
    *,
    block_q: int = PREFILL_BLOCK_Q,
    block_k=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """``I`` [B, S, S_max] float32 of every query against every position
    of layer ``layer_index``. NOT causal yet: a block that holds a
    position some query of its row block can see is computed whole, and
    every other block reads ``-inf``."""
    B, S, H, d = qi.shape
    _, _, _, S_max = ik.shape
    qi = qi.astype(ik.dtype)
    w = w.astype(F32)
    q_off = jnp.broadcast_to(jnp.asarray(q_offset, jnp.int32), (B,))
    layer = jnp.asarray(layer_index, jnp.int32).reshape(1)
    if S == 1:
        block_k = _block_k(S_max, block_k or DECODE_BLOCK_K)
        num_q, S_p, block_q = 1, 1, 1
        kernel = _kernel_one
        q_in, w_in = qi[:, 0], w[:, 0, :, None]  # [B, H, d], [B, H, 1]
        q_spec = pl.BlockSpec((None, H, d), lambda b, i, j, *_: (b, 0, 0))
        w_spec = pl.BlockSpec((None, H, 1), lambda b, i, j, *_: (b, 0, 0))
    else:
        block_k = _block_k(S_max, block_k or PREFILL_BLOCK_K)
        S_p = -(-S // 16) * 16  # bf16 sublane tiles
        block_q = min(block_q, S_p)
        S_p = -(-S_p // block_q) * block_q
        num_q = S_p // block_q
        kernel = functools.partial(_kernel_many, heads=H)
        pad = ((0, 0), (0, S_p - S), (0, 0), (0, 0))
        q_in = jnp.pad(qi, pad).transpose(0, 2, 1, 3)  # [B, H, S_p, d]
        w_in = jnp.pad(w, pad[:3])  # [B, S_p, H]
        q_spec = pl.BlockSpec(
            (None, H, block_q, d), lambda b, i, j, *_: (b, 0, i, 0)
        )
        w_spec = pl.BlockSpec((None, block_q, H), lambda b, i, j, *_: (b, i, 0))
    num_k = S_max // block_k
    live = functools.partial(
        _live, block_q=block_q, S=S, block_k=block_k, num_k=num_k
    )
    out = pl.pallas_call(
        functools.partial(kernel, live=live),
        name="index_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, num_q, num_k),
            in_specs=[
                q_spec,
                w_spec,
                pl.BlockSpec(
                    (None, None, d, block_k),
                    # past the last live block the index stands still
                    lambda b, i, j, layer, q_off: (
                        layer[0], b, 0, jnp.minimum(j, live(q_off[b], i) - 1)
                    ),
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, block_q, block_k), lambda b, i, j, *_: (b, i, j)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((B, S_p, S_max), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(layer, q_off, q_in, w_in, ik)
    return out[:, :S]


def _write_kernel(layer_ref, pos_ref, new_ref, ik_ref, o_ref):
    """One row's new key [d, 1] into the lane of its position in the
    128 positions' tile [d, 128] that holds it."""
    del layer_ref
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    at = pos_ref[pl.program_id(0)] % o_ref.shape[1]
    o_ref[...] = jnp.where(lane == at, new_ref[...], ik_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=0)
def write_index_keys(ik, new, layer_index, positions, *, interpret: bool = False):
    """``ik[layer, b, :, positions[b]] = new[b]`` for every row, IN PLACE
    (``name="index_key_write"``): ``ik`` [L, B, d, S_max], ``new`` [B, d].
    A position is a lane of the stack, so a token's key is a COLUMN of a
    tile: as a scatter XLA transposes the whole stack to make the column
    a row, scatters, and transposes it back, every layer of every step.
    Here each row's one tile of 128 positions is read, one lane of it
    replaced, and written where it lay."""
    L, B, d, S_max = ik.shape
    layer = jnp.asarray(layer_index, jnp.int32).reshape(1)
    pos = jnp.clip(jnp.asarray(positions, jnp.int32), 0, S_max - 1)
    tile = pl.BlockSpec(
        (None, None, d, 128),
        lambda b, layer, pos: (layer[0], b, 0, pos[b] // 128),
    )
    return pl.pallas_call(
        _write_kernel,
        name="index_key_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((None, d, 1), lambda b, *_: (b, 0, 0)),
                tile,
            ],
            out_specs=tile,
        ),
        out_shape=jax.ShapeDtypeStruct(ik.shape, ik.dtype),
        # operands 0 and 1 are the prefetched scalars: the stack is 3
        input_output_aliases={3: 0},
        interpret=interpret,
    )(layer, pos, new.astype(ik.dtype)[:, :, None], ik)


def index_scores_plain(qi, w, ik, layer_index, q_offset=None):
    """``index_scores`` as an einsum on the layer taken out of the stack
    (every block computed)."""
    del q_offset
    keys = jax.lax.dynamic_index_in_dim(ik, layer_index, 0, keepdims=False)
    s = jnp.einsum(
        "bqhd,bdk->bhqk", qi.astype(ik.dtype), keys, preferred_element_type=F32
    )
    return _no_negative_zero(
        jnp.einsum("bhqk,bqh->bqk", jnp.maximum(s, 0.0), w.astype(F32))
    )


def visible(q_pos, kv_mask, s_max: int):
    """[B, S, S_max] bool: the slots a query at ``q_pos`` [B, S] may see
    (its own and those before it that ``kv_mask`` [B, S_max] holds)."""
    seen = jnp.arange(s_max, dtype=jnp.int32)[None, None, :] <= q_pos[..., None]
    return seen if kv_mask is None else seen & kv_mask[:, None, :]


def select_threshold(scores, valid, k: int):
    """What decides a query's ``k`` largest of ``scores`` [..., N] among
    ``valid`` [..., N]: ``(thr [...], cut [...])``. A query keeps what
    lies above ``thr``, and of the scores EQUAL to it those at positions
    up to ``cut``: the lower positions, as many as still fit
    (``lax.top_k``'s order). With fewer than ``k`` valid, all of them."""
    N = scores.shape[-1]
    thr = kth_largest(scores, k, valid)
    above = jnp.sum(valid & (scores > thr[..., None]), axis=-1, dtype=jnp.int32)
    ties = valid & (scores == thr[..., None])
    room = k - above  # ties that still fit: at least one where thr is a score

    def edge(_):
        # the position of the last tie that fits; only where more tie
        # than fit is there an edge to find (a float32 sum of products
        # rarely ties: this branch seldom runs)
        reached = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) >= room[..., None]
        return jnp.argmax(reached, axis=-1).astype(jnp.int32)

    surplus = jnp.sum(ties, axis=-1, dtype=jnp.int32) > room
    cut = jax.lax.cond(
        jnp.any(surplus), edge, lambda _: jnp.zeros(thr.shape, jnp.int32), None
    )
    return thr, jnp.where(surplus, cut, jnp.int32(N))


def selected(scores, valid, thr, cut):
    """[..., N] bool: the positions ``select_threshold`` keeps."""
    pos = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    t, c = thr[..., None], cut[..., None]
    return valid & ((scores > t) | ((scores == t) & (pos <= c)))


def compact_positions(keep, k: int, rows_at_once: int = 64):
    """The positions ``keep`` [R, N] marks, in position order, the first
    ``k`` of each row: ``(ids [R, k] int32, count [R])``; ``ids`` past a
    row's count read ``N``."""
    R, N = keep.shape
    c = _CHUNK if N % _CHUNK == 0 else N
    C = N // c
    tri = (jnp.arange(c)[:, None] <= jnp.arange(c)[None, :]).astype(jnp.bfloat16)
    j = jnp.arange(k, dtype=jnp.int32)

    def some(keep):
        # within each chunk, how many kept up to and including a lane
        incl = jnp.einsum(
            "rcl,lm->rcm", keep.reshape(-1, C, c).astype(jnp.bfloat16), tri,
            preferred_element_type=F32,
        )
        counts = incl[..., -1].astype(jnp.int32)  # [r, C]
        ends = jnp.cumsum(counts, axis=-1)
        # output slot j lies in the first chunk whose end passes it, behind
        # what the chunks before it hold (a masked sum beside the count:
        # taken by index, the 16 x 2048 scalars were 4 of a decode step's
        # 19 ms; my chip run, PR 42)
        passed = ends[:, None, :] <= j[None, :, None]  # [r, k, C]
        at = jnp.minimum(jnp.sum(passed, axis=-1), C - 1)
        before = jnp.sum(jnp.where(passed, counts[:, None, :], 0), axis=-1)
        lanes = jnp.einsum(
            "rkc,rcl->rkl", jax.nn.one_hot(at, C, dtype=jnp.bfloat16),
            incl.astype(jnp.bfloat16), preferred_element_type=F32,
        )
        # ... at the first lane whose count passes its rank in the chunk
        lane = jnp.sum(lanes <= (j[None, :] - before)[..., None], axis=-1)
        count = ends[:, -1]
        ids = jnp.where(j[None, :] < count[:, None], at * c + lane, N)
        return ids.astype(jnp.int32), count

    if R <= rows_at_once:
        return some(keep)
    assert R % rows_at_once == 0, (R, rows_at_once)
    ids, count = jax.lax.map(some, keep.reshape(-1, rows_at_once, N))
    return ids.reshape(R, k), count.reshape(R)


def gather_rows(stack, layer_index, ids):
    """``stack[layer, b, ids[b]]`` [B, k, width] of a stack [L, B, S_max,
    width] by one gather over the whole stack; an id past the end reads
    the last position (the caller masks it)."""
    B = ids.shape[0]
    rows = jnp.arange(B, dtype=jnp.int32)[:, None]
    return stack[layer_index, rows, jnp.minimum(ids, stack.shape[2] - 1)]


def masked_attention(q, k, v, keep):
    """The plain form of attention under a selection: q [B, S, Hq, hd],
    k / v [B, Sk, Hkv, hd], ``keep`` [B, S, Sk] bool; ``dense_attention``'s
    numerics."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, S, Hkv, Hq // Hkv, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k, preferred_element_type=F32)
    s = jnp.where(keep[:, None, None], s * hd**-0.5, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", p, v).reshape(B, S, Hq, hd)
