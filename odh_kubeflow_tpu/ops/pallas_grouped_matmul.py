"""Pallas TPU grouped matmul (megablocks-style) for dropless MoE.

``gmm(lhs, rhs, group_offsets)`` multiplies row-groups of ``lhs [M, K]``
against per-group weight matrices ``rhs [E, K, N]``: rows in
``[group_offsets[e], group_offsets[e+1])`` use expert ``e``. Unlike the
one-hot (GShard) or capacity-table dispatch in ``models/moe.py``, there
is **no per-expert capacity**: the caller sorts token assignments by
expert (padding each group to a 128 multiple) and every assignment is
computed exactly once — zero drops, zero capacity over-compute. That
padding discipline is what lets the hot kernel skip all boundary
masking (see kernel A below).

The reference platform carries no kernels at all (SURVEY.md §2.4); this
is TPU-native capability on top of it, built for the v5e memory system:

- **Kernel A** (contraction dim K ≤ ~2048, e.g. the gate/up projection
  D→F): grid ``(n_tiles, m_tiles)`` with the *expert weight block
  resident in VMEM* across each group's row tiles (consecutive m tiles
  share a group, so Mosaic re-uses the fetched block) while 128-row lhs
  tiles stream through. K is not split, so there is no accumulator
  scratch. Requires every group boundary 128-aligned — then every lhs
  tile belongs to exactly one group and the kernel has no masks at all.
- **Kernel B** (K large, output dim N ≤ 4096, e.g. the down projection
  F→D and the backward dlhs of gate/up): K is split into ``bk`` blocks
  accumulated in a full-width ``(bm, N)`` f32 scratch. Row tiles are
  512 wide, so a tile may span several groups; the grid runs over
  (tile × group) *span pairs* with scalar-prefetched metadata, masking
  lhs rows outside the pair's group and writing the tile out once, on
  its last pair. Unwritten grid visits flush whatever the rotating
  VMEM buffer holds, so pad pairs target a dedicated dummy tile row
  (the output carries one extra ``bm`` row block the caller slices off).
- **tgmm** computes the weight gradient ``drhs[e] = lhsᵀ · doutᵀ`` per
  group with the same span-pair walk (k, n outer; pairs inner) and a
  per-group f32 accumulator; empty groups get a singleton pair that
  writes zeros (their block would otherwise be uninitialised HBM). With
  frozen expert banks (the QLoRA recipe) the whole tgmm is dead code —
  XLA removes it because ``grad`` never requests those cotangents.

``trans_rhs`` reads ``rhs`` stored as ``[E, N, K]`` (an expert weight
bank used "backwards", as in dlhs = dout · Wᵀ) without materialising a
256 MB transposed copy in HBM — the dot contracts the trailing axis of
both operands and Mosaic handles the in-VMEM layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# group boundaries are padded to this (kernel A's row tile); kernel B's
# row tile must be a multiple of it
ALIGN = 128

DEFAULT_BM_B = 512
DEFAULT_BK_B = 1024
DEFAULT_BN_B = 1024
DEFAULT_BK_T = 512
DEFAULT_BN_T = 512
# kernel A's contraction limit: (128, K) lhs + (K, bn) rhs blocks must
# double-buffer in ~16MB VMEM
MAX_K_A = 4096
# kernel B's scratch is (bm, N) f32
MAX_N_B = 4096


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pick_bn(n: int, budget: int) -> int:
    """Largest lane-aligned divisor of ``n`` within the VMEM column
    budget (trace-time loop, ≤ n/128 iterations); sub-ALIGN n (tiny
    test shapes) runs as one block."""
    bn = n
    for cand in range(ALIGN, min(n, budget) + 1, ALIGN):
        if n % cand == 0:
            bn = cand
    return bn


def _group_of_tile(m: int, group_offsets) -> jnp.ndarray:
    """Expert id of each ALIGN-row tile — ALIGN-aligned group
    boundaries guarantee each tile has exactly one."""
    tiles = jnp.arange(m // ALIGN, dtype=jnp.int32) * ALIGN
    return (
        jnp.searchsorted(group_offsets[1:-1], tiles, side="right")
        .astype(jnp.int32)
    )


# ---------------------------------------------------------------------------
# span-pair metadata (traced; E- and tile-count-sized arrays only)


def span_pairs(group_offsets: jnp.ndarray, m: int, bm: int,
               include_empty: bool) -> dict[str, jnp.ndarray]:
    """Tile×group span pairs for kernels that walk ``bm``-row tiles.

    ``group_offsets`` is [E+1] int32 with ``offsets[0]=0``,
    ``offsets[E]=m``, every entry ALIGN-aligned. A *pair* is a (row
    tile, group) intersection; listing pairs in offset order makes
    consecutive pairs of one tile adjacent (so output-buffer revisits
    are consecutive — a Mosaic requirement) and consecutive pairs of
    one group adjacent (so weight blocks stay resident).

    Static length: T + E pairs (T = m // bm), padded with inert pairs.
    Inert pads REUSE the last real pair's block indices and carry
    ``live = 0``: identical consecutive indices mean Mosaic's pipeliner
    issues no DMA for them, and the kernels' ``pl.when(live)`` guard
    skips their dots — before this, every pad burned a full fetch plus
    a masked dot, E/(T+E) ≈ 19% of the grid at the 8×1B kernel-B shape
    (a count from the shapes; no cell times this kernel: PERF.md
    section 7). With ``include_empty``,
    zero-size groups still get a live pair (tgmm must write zeros to
    their gradient block); without it they are skipped (kernel B
    writes rows, and empty groups own none).

    Returns int32 arrays of length L = T + E:
      ``tile``   lhs/out row-tile index (pads: the last real pair's)
      ``otile``  kernel B's out row tile (pads: the last real pair's —
                 revisits without a write are free; the dummy row T is
                 used only when there are no real pairs at all)
      ``group``  expert id (pads: the last real pair's, for the fetch)
      ``live``   1 on real pairs — the kernels' compute guard
      ``write``  1 on the last pair of each real tile (kernel B writes)
      ``gfirst``/``glast`` group-accumulation boundaries (tgmm; 0 on
                 pads so a pad can never re-write a real block)
    """
    E = group_offsets.shape[0] - 1
    T = m // bm
    L = T + E
    starts = group_offsets[:-1]
    ends = group_offsets[1:]
    sizes = ends - starts
    nonempty = sizes > 0
    # tiles spanned by each group (0 for empty groups unless included)
    first_tile = starts // bm
    last_tile = jnp.where(nonempty, (ends - 1) // bm, first_tile)
    ntiles = jnp.where(nonempty, last_tile - first_tile + 1, 0)
    if include_empty:
        ntiles = jnp.maximum(ntiles, 1)
    cum = jnp.cumsum(ntiles)  # pairs before group e+1
    total = cum[-1]
    i = jnp.arange(L, dtype=jnp.int32)
    # group of pair i: first g with cum[g] > i; pads get E
    group = jnp.searchsorted(cum, i, side="right").astype(jnp.int32)
    pad = i >= total
    group_c = jnp.minimum(group, E - 1)
    within = i - jnp.where(group_c > 0, cum[group_c - 1], 0)
    tile = jnp.clip(first_tile[group_c] + within, 0, T - 1)
    group = jnp.where(pad, E, group_c)
    # write: last pair of its tile — next pair has a different tile (or
    # is a pad). Pads never write. Empty-group pairs sit at their
    # offset's tile but their mask is empty; they must not steal the
    # write flag, so exclude them from tile ownership.
    owns = ~pad & (sizes[group_c] > 0)
    nxt_tile = jnp.concatenate([tile[1:], jnp.full((1,), -1, jnp.int32)])
    nxt_owns = jnp.concatenate([owns[1:], jnp.zeros((1,), bool)])
    write = (owns & ((nxt_tile != tile) | ~nxt_owns)).astype(jnp.int32)
    otile = jnp.where(owns, tile, T).astype(jnp.int32)
    # group accumulation boundaries (tgmm): compare neighbour groups
    # BEFORE the pad remap below (a pad's fetch-group aliases the last
    # real pair's, which must not clear that pair's glast)
    prv_group = jnp.concatenate([jnp.full((1,), -1, jnp.int32), group[:-1]])
    nxt_group = jnp.concatenate([group[1:], jnp.full((1,), -2, jnp.int32)])
    live = (~pad).astype(jnp.int32)
    gfirst = (group != prv_group).astype(jnp.int32) * live
    glast = (group != nxt_group).astype(jnp.int32) * live
    # pads alias the last real pair's indices: unchanged consecutive
    # block indices cost no DMA, and live=0 skips their compute
    last = jnp.maximum(total - 1, 0)

    def pad_alias(arr):
        return jnp.where(pad, arr[last], arr)

    return {
        "tile": pad_alias(tile).astype(jnp.int32),
        "otile": pad_alias(otile).astype(jnp.int32),
        "group": pad_alias(group).astype(jnp.int32),
        "live": live,
        "write": write,
        "gfirst": gfirst,
        "glast": glast,
    }


# ---------------------------------------------------------------------------
# kernel A: small K, rhs-resident, maskless


def _gmm_a_kernel(gid_ref, lhs_ref, rhs_ref, out_ref, *, trans_rhs):
    rhs = rhs_ref[0].astype(lhs_ref.dtype)
    dn = (((1,), (1,)), ((), ())) if trans_rhs else (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(
        lhs_ref[...], rhs, dn, preferred_element_type=jnp.float32
    )
    out_ref[...] = acc.astype(out_ref.dtype)


def _gmm_a_kernel_q(gid_ref, lhs_ref, rhs_ref, scale_ref, out_ref, *,
                    trans_rhs):
    """int8 bank variant: the per-output-channel scale (bank's last
    axis — ``models/quant.py``) factors out of the contraction, so the
    weight block is convert-only and one cheap vector multiply lands
    on the f32 accumulator (non-trans) or the streamed lhs tile
    (trans, where the scaled axis is the contraction)."""
    rhs = rhs_ref[0].astype(lhs_ref.dtype)
    dn = (((1,), (1,)), ((), ())) if trans_rhs else (((1,), (0,)), ((), ()))
    if trans_rhs:
        lhs = lhs_ref[...] * scale_ref[0, 0][None, :].astype(lhs_ref.dtype)
        acc = jax.lax.dot_general(
            lhs, rhs, dn, preferred_element_type=jnp.float32
        )
    else:
        acc = jax.lax.dot_general(
            lhs_ref[...], rhs, dn, preferred_element_type=jnp.float32
        )
        acc = acc * scale_ref[0, 0][None, :]
    out_ref[...] = acc.astype(out_ref.dtype)


def _gmm_a(lhs, rhs, group_of_tile, *, trans_rhs, interpret,
           scale=None, base=None):
    m, k = lhs.shape
    n = rhs.shape[1] if trans_rhs else rhs.shape[2]
    # resident weight block ≤4MB so it double-buffers beside the
    # streaming lhs tiles in ~16MB VMEM — int8 banks fit 2× the
    # columns. Largest lane-aligned divisor of n that fits the budget
    # (trace-time loop, ≤ n/128 iterations).
    budget = 4 * 1024 * 1024 // (k * rhs.dtype.itemsize)
    bn = _pick_bn(n, budget)
    assert n % bn == 0, f"N={n} has no legal block under K={k}"
    T = m // ALIGN
    rhs_block = (1, bn, k) if trans_rhs else (1, k, bn)
    # stacked-bank mode (``base``): rhs holds every layer's expert
    # banks [L·E, ...] and the fetch index offsets by the layer's
    # group base — the scan never dynamic-slices a 100+MB bank copy
    # per layer just to feed the custom call (see models/moe.py)
    pref = [group_of_tile] if base is None else [group_of_tile, base]

    def _g(p, t):
        g = p[0][t]
        return g if base is None else p[1][0] + g

    rhs_idx = (
        (lambda ni, t, *p: (_g(p, t), ni, 0))
        if trans_rhs
        else (lambda ni, t, *p: (_g(p, t), 0, ni))
    )
    grid = (n // bn, T)
    in_specs = [
        pl.BlockSpec((ALIGN, k), lambda ni, t, *p: (t, 0)),
        pl.BlockSpec(rhs_block, rhs_idx),
    ]
    operands = pref + [lhs, rhs]
    nker = len(pref)

    def strip(fn):
        # kernel positional args: prefetch refs first — drop them all
        # (bodies never read the ids; index maps consume them)
        def wrapped(*refs):
            return fn(refs[0], *refs[nker:])
        return wrapped

    if scale is None:
        kernel = strip(functools.partial(_gmm_a_kernel, trans_rhs=trans_rhs))
    else:
        kernel = strip(functools.partial(_gmm_a_kernel_q, trans_rhs=trans_rhs))
        scale_block = (1, 1, k) if trans_rhs else (1, 1, bn)
        scale_idx = (
            (lambda ni, t, *p: (_g(p, t), 0, 0))
            if trans_rhs
            else (lambda ni, t, *p: (_g(p, t), 0, ni))
        )
        in_specs.append(pl.BlockSpec(scale_block, scale_idx))
        operands.append(scale)
    return pl.pallas_call(
        kernel,
        name="gmm_a",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pref),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((ALIGN, bn), lambda ni, t, *p: (t, ni)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# kernel B: split K, span pairs, full-width scratch


def _gmm_b_kernel(
    tile_ref, otile_ref, group_ref, write_ref, live_ref, offs_ref,
    lhs_ref, rhs_ref, *rest, bm, bn, nk, trans_rhs,
):
    if len(rest) == 3:
        scale_ref, out_ref, acc_ref = rest
    else:
        (out_ref, acc_ref), scale_ref = rest, None
    """Grid is (pairs, n, k) with k innermost: for one (pair, n-tile)
    the k loop accumulates into scratch slice ``acc_ref[ni]`` and the
    out block index stays constant, so every output block's visits are
    consecutive and it is written exactly once (on its tile's last
    pair, final k step). The scratch's leading axis is the n-tile —
    indexing it is a major-dim dynamic slice (lane-dim dynamic slices
    are not a Mosaic-friendly pattern); all n slices persist across
    pairs so a boundary tile's earlier pairs survive until the
    tile-closing pair merges and writes. Pad pairs (live = 0) alias
    the last real pair's block indices, so they cost neither a DMA
    nor (guarded below) a dot."""
    i = pl.program_id(0)
    ni = pl.program_id(1)
    ki = pl.program_id(2)
    g = group_ref[i]
    t = tile_ref[i]
    live = live_ref[i] == 1
    start = offs_ref[g]
    end = offs_ref[g + 1]
    # most pairs cover their whole tile (boundary pairs are ≤E of
    # T+E); the full case skips the row mask select and the masked
    # accumulator merge — VPU work between the MXU dots
    full = jnp.logical_and(start <= t * bm, end >= (t + 1) * bm)

    def _dot(lhs):
        if scale_ref is not None and trans_rhs:
            # int8 bank used backwards: scaled axis is the contraction
            lhs = lhs * scale_ref[0, 0][None, :].astype(lhs.dtype)
        rhs = rhs_ref[0].astype(lhs_ref.dtype)
        dn = (
            (((1,), (1,)), ((), ()))
            if trans_rhs
            else (((1,), (0,)), ((), ()))
        )
        return jax.lax.dot_general(
            lhs, rhs, dn, preferred_element_type=jnp.float32
        )

    @pl.when(jnp.logical_and(live, full))
    def _full():
        d = _dot(lhs_ref[...])

        @pl.when(ki == 0)
        def _init():
            acc_ref[ni] = d

        @pl.when(ki > 0)
        def _accum():
            acc_ref[ni] = acc_ref[ni] + d

    @pl.when(jnp.logical_and(live, jnp.logical_not(full)))
    def _partial():
        rows = t * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mask = jnp.logical_and(rows >= start, rows < end)
        d = _dot(jnp.where(mask, lhs_ref[...], 0).astype(lhs_ref.dtype))

        @pl.when(ki == 0)
        def _init():
            # keep earlier pairs' rows of this tile; lhs is already
            # zeroed outside the mask so d carries no stale part
            acc_ref[ni] = jnp.where(mask, d, acc_ref[ni])

        @pl.when(ki > 0)
        def _accum():
            acc_ref[ni] = acc_ref[ni] + d

    @pl.when(jnp.logical_and(ki == nk - 1, write_ref[i] == 1))
    def _write():
        acc = acc_ref[ni]
        if scale_ref is not None and not trans_rhs:
            # int8 bank forwards: per-output-column scale on the f32
            # accumulator, once per written block
            acc = acc * scale_ref[0, 0][None, :]
        out_ref[...] = acc.astype(out_ref.dtype)


def _gmm_b(lhs, rhs, pairs, group_offsets, *, trans_rhs, bm, bk, bn,
           interpret, scale=None, base=None):
    m, k = lhs.shape
    E = group_offsets.shape[0] - 1  # layer-LOCAL group count
    n = rhs.shape[1] if trans_rhs else rhs.shape[2]
    bk = min(bk, k)
    bn = min(bn, n)
    assert k % bk == 0 and n % bn == 0, (k, bk, n, bn)
    nk = k // bk
    L = pairs["tile"].shape[0]
    rhs_block = (1, bn, bk) if trans_rhs else (1, bk, bn)

    # pad pairs alias a real pair's group (span_pairs) — the clamp
    # stays as belt-and-braces against an out-of-bounds fetch (a hard
    # TPU fault). Stacked-bank mode (``base``, models/moe.py): rhs is
    # [L·E, ...] and the fetch offsets into this layer's bank span.
    def _g(p, i):
        g = jnp.minimum(p[2][i], E - 1)
        return g if base is None else p[6][0] + g

    rhs_idx = (
        (lambda i, ni, ki, *p: (_g(p, i), ni, ki))
        if trans_rhs
        else (lambda i, ni, ki, *p: (_g(p, i), ki, ni))
    )
    # offsets extended so the dummy group E is empty: offs[E+1] = offs[E]
    offs = jnp.concatenate([group_offsets, group_offsets[-1:]])
    in_specs = [
        pl.BlockSpec(
            (bm, bk), lambda i, ni, ki, *p: (p[0][i], ki)
        ),
        pl.BlockSpec(rhs_block, rhs_idx),
    ]
    operands = [
        pairs["tile"], pairs["otile"], pairs["group"], pairs["write"],
        pairs["live"], offs,
    ] + ([] if base is None else [base]) + [lhs, rhs]
    npref = 6 if base is None else 7

    def strip(fn):
        # bodies read the first six prefetch refs; drop the base ref
        def wrapped(*refs):
            return fn(*refs[:6], *refs[npref:])
        return wrapped

    if scale is not None:
        # scaled axis is the bank's last: output columns (non-trans,
        # applied at write) or the contraction (trans, prescaled)
        scale_block = (1, 1, bk) if trans_rhs else (1, 1, bn)
        scale_idx = (
            (lambda i, ni, ki, *p: (_g(p, i), 0, ki))
            if trans_rhs
            else (lambda i, ni, ki, *p: (_g(p, i), 0, ni))
        )
        in_specs.append(pl.BlockSpec(scale_block, scale_idx))
        operands.append(scale)
    out = pl.pallas_call(
        strip(functools.partial(
            _gmm_b_kernel, bm=bm, bn=bn, nk=nk, trans_rhs=trans_rhs
        )),
        name="gmm_b",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=npref,
            grid=(L, n // bn, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (bm, bn), lambda i, ni, ki, *p: (p[1][i], ni)
            ),
            scratch_shapes=[pltpu.VMEM((n // bn, bm, bn), jnp.float32)],
        ),
        # one extra bm-row dummy block absorbs inert pairs' buffer flushes
        out_shape=jax.ShapeDtypeStruct((m + bm, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return out[:m]


# ---------------------------------------------------------------------------
# tgmm: per-group weight gradient


def _tgmm_kernel(
    tile_ref, group_ref, gfirst_ref, glast_ref, live_ref, offs_ref,
    lhs_ref, dout_ref, out_ref, acc_ref, *, bm,
):
    i = pl.program_id(2)
    g = group_ref[i]
    t = tile_ref[i]
    start = offs_ref[g]
    end = offs_ref[g + 1]

    # pad pairs (live = 0, aliased indices — no DMA) must not touch
    # the accumulator: their gfirst/glast are 0, so an unguarded body
    # would ACCUMULATE a stale dot into the last real group
    @pl.when(live_ref[i] == 1)
    def _compute():
        rows = t * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        mask = jnp.logical_and(rows >= start, rows < end)
        lhs = jnp.where(mask, lhs_ref[...], 0).astype(lhs_ref.dtype)
        # (bk, bn) = lhsᵀ · dout, contracting the bm rows
        d = jax.lax.dot_general(
            lhs, dout_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(gfirst_ref[i] == 1)
        def _init():
            acc_ref[...] = d

        @pl.when(gfirst_ref[i] == 0)
        def _accum():
            acc_ref[...] = acc_ref[...] + d

        @pl.when(glast_ref[i] == 1)
        def _write():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, dout, pairs, group_offsets, *, bm, bk, bn, interpret):
    m, k = lhs.shape
    n = dout.shape[1]
    bk = min(bk, k)
    bn = min(bn, n)
    assert k % bk == 0 and n % bn == 0, (k, bk, n, bn)
    E = group_offsets.shape[0] - 1
    L = pairs["tile"].shape[0]
    offs = jnp.concatenate([group_offsets, group_offsets[-1:]])
    out = pl.pallas_call(
        functools.partial(_tgmm_kernel, bm=bm),
        name="tgmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(k // bk, n // bn, L),
            in_specs=[
                pl.BlockSpec(
                    (bm, bk),
                    lambda ki, ni, i, t, g, gf, gl, lv, o: (t[i], ki),
                ),
                pl.BlockSpec(
                    (bm, bn),
                    lambda ki, ni, i, t, g, gf, gl, lv, o: (t[i], ni),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, bk, bn),
                lambda ki, ni, i, t, g, gf, gl, lv, o: (g[i], ki, ni),
            ),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        # dummy group E absorbs the no-real-pairs degenerate flush
        out_shape=jax.ShapeDtypeStruct((E + 1, k, n), dout.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(
        pairs["tile"], pairs["group"], pairs["gfirst"], pairs["glast"],
        pairs["live"], offs, lhs, dout,
    )
    return out[:E]


# ---------------------------------------------------------------------------
# public op


def _gmm_fwd_impl(lhs, rhs, group_offsets, *, trans_rhs, interpret,
                  scale=None, base=None):
    m, k = lhs.shape
    n = rhs.shape[1] if trans_rhs else rhs.shape[2]
    assert m % DEFAULT_BM_B == 0, f"M={m} must be a {DEFAULT_BM_B} multiple"
    # kernel A holds a (K, bn) weight block double-buffered in ~16MB
    # VMEM; scale the K limit down for wider dtypes (f32 tests) so a
    # legal-on-CPU shape can't oversubscribe VMEM on hardware
    max_k_a = MAX_K_A * 2 // max(lhs.dtype.itemsize, rhs.dtype.itemsize)
    if k <= max_k_a:
        return _gmm_a(
            lhs, rhs, _group_of_tile(m, group_offsets),
            trans_rhs=trans_rhs, interpret=interpret, scale=scale,
            base=base,
        )
    if n > MAX_N_B:
        raise NotImplementedError(
            f"gmm: K={k} > {MAX_K_A} and N={n} > {MAX_N_B} — no kernel "
            "shape fits VMEM; reshape the problem"
        )
    pairs = span_pairs(group_offsets, m, DEFAULT_BM_B, include_empty=False)
    return _gmm_b(
        lhs, rhs, pairs, group_offsets, trans_rhs=trans_rhs,
        bm=DEFAULT_BM_B, bk=DEFAULT_BK_B, bn=DEFAULT_BN_B,
        interpret=interpret, scale=scale, base=base,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gmm(lhs, rhs, group_offsets, trans_rhs=False,
        interpret: Optional[bool] = None, scale=None, group_base=None):
    """Grouped matmul: rows ``[offsets[e], offsets[e+1])`` of ``lhs``
    through ``rhs[e]``. Offsets must be ALIGN-aligned with
    ``offsets[0] = 0`` and ``offsets[E] = M`` (the caller's sort pads
    groups — ``models/moe.py`` ``route_sorted``). Returns [M, N] in
    ``lhs.dtype``; differentiable in ``lhs`` and ``rhs``.

    ``scale`` enables int8-native banks: ``rhs`` int8 with the
    per-output-channel scale [E, 1, bank-last-axis] from
    ``models/quant.py`` — the kernel reads half the weight bytes and
    never materialises a dequantized bank in HBM. Weight gradients are
    not defined through the quantized path (frozen banks — QLoRA).

    ``group_base`` (stacked-bank mode, int32 [1]): ``rhs``/``scale``
    hold EVERY layer's banks ([L·E, ...]) and fetch indices offset by
    this layer's first group — so a per-layer scan never materialises
    a bank copy just to feed the kernel. Frozen (``scale``) banks only:
    the weight-gradient tgmm has no stacked form."""
    if interpret is None:
        interpret = _interpret_default()
    if group_base is not None and scale is None:
        raise NotImplementedError(
            "gmm: group_base (stacked banks) requires int8 frozen "
            "banks (scale) — no stacked weight-gradient path"
        )
    return _gmm_fwd_impl(
        lhs, rhs, group_offsets, trans_rhs=trans_rhs, interpret=interpret,
        scale=scale, base=group_base,
    )


def _gmm_fwd(lhs, rhs, group_offsets, trans_rhs, interpret, scale,
             group_base):
    if interpret is None:
        interpret = _interpret_default()
    out = _gmm_fwd_impl(
        lhs, rhs, group_offsets, trans_rhs=trans_rhs, interpret=interpret,
        scale=scale, base=group_base,
    )
    return out, (lhs, rhs, group_offsets, scale, group_base)


def _gmm_bwd(trans_rhs, interpret, res, dout):
    lhs, rhs, group_offsets, scale, group_base = res
    if interpret is None:
        interpret = _interpret_default()
    # dlhs = dout · rhsᵀ — the same grouped matmul with rhs read
    # "the other way", so the two trans_rhs variants are each other's
    # backward and no transposed weight copy ever hits HBM
    dlhs = _gmm_fwd_impl(
        dout.astype(lhs.dtype), rhs, group_offsets,
        trans_rhs=not trans_rhs, interpret=interpret, scale=scale,
        base=group_base,
    )
    if scale is not None:
        # int8 banks are frozen (QLoRA): no weight cotangents
        return (dlhs, None, None, jnp.zeros_like(scale), None)
    E = rhs.shape[0]
    m = lhs.shape[0]
    pairs = span_pairs(group_offsets, m, DEFAULT_BM_B, include_empty=True)
    if trans_rhs:
        # rhs layout [E, N, K]: drhs[e] = doutᵀ · lhs
        drhs = _tgmm(
            dout.astype(lhs.dtype), lhs, pairs, group_offsets,
            bm=DEFAULT_BM_B, bk=DEFAULT_BK_T, bn=DEFAULT_BN_T,
            interpret=interpret,
        ).astype(rhs.dtype)
    else:
        drhs = _tgmm(
            lhs, dout.astype(lhs.dtype), pairs, group_offsets,
            bm=DEFAULT_BM_B, bk=DEFAULT_BK_T, bn=DEFAULT_BN_T,
            interpret=interpret,
        ).astype(rhs.dtype)
    return dlhs, drhs, None, None, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


# ---------------------------------------------------------------------------
# fused SwiGLU grouped matmul: h = silu(x·Wg) ⊙ (x·Wu) in one kernel
# ---------------------------------------------------------------------------


def _swiglu_fwd_kernel(gid_ref, *rest, has_base):
    """Kernel-A-shaped fused gate+up: both expert weight blocks stay
    resident across the group's 128-row lhs tiles, the silu·mul
    epilogue runs on the f32 accumulators in VMEM, and only h (plus g,
    which the QLoRA remat policy pins as "moe_g" — measured CHEAPER
    than recomputing g with an extra backward dot, despite the scan
    residual's stacking DUS) ever reach HBM; the separate u tensor and
    the standalone silu fusion's passes disappear."""
    if has_base:
        _base, lhs_ref, wg_ref, wu_ref, sg_ref, su_ref, h_ref, g_ref = rest
    else:
        lhs_ref, wg_ref, wu_ref, sg_ref, su_ref, h_ref, g_ref = rest
    lhs = lhs_ref[...]
    g = jax.lax.dot_general(
        lhs, wg_ref[0].astype(lhs.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sg_ref[0, 0][None, :]
    u = jax.lax.dot_general(
        lhs, wu_ref[0].astype(lhs.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * su_ref[0, 0][None, :]
    h = jax.nn.silu(g) * u
    h_ref[...] = h.astype(h_ref.dtype)
    g_ref[...] = g.astype(g_ref.dtype)


def _swiglu_bwd_kernel(gid_ref, *rest, has_base):
    """Backward fusion: recompute u (the one matmul the moe_g pin
    leaves — recomputing g too was measured slower than reading the
    pin), then the dsilu epilogue — dg = dh·u·silu'(g),
    du = dh·silu(g) — on the in-VMEM tiles. Replaces a standalone
    u-recompute kernel plus two [M, F] dsilu fusions."""
    if has_base:
        _base, lhs_ref, wu_ref, su_ref, g_ref, dh_ref, dg_ref, du_ref = rest
    else:
        lhs_ref, wu_ref, su_ref, g_ref, dh_ref, dg_ref, du_ref = rest
    lhs = lhs_ref[...]
    u = jax.lax.dot_general(
        lhs, wu_ref[0].astype(lhs.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * su_ref[0, 0][None, :]
    g = g_ref[...].astype(jnp.float32)
    dh = dh_ref[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    dg_ref[...] = (dh * u * (sig * (1.0 + g * (1.0 - sig)))).astype(
        dg_ref.dtype
    )
    du_ref[...] = (dh * (g * sig)).astype(du_ref.dtype)


def _swiglu_specs(m, k, n, group_of_tile, base):
    """Shared grid/spec plumbing for the two fused kernels: kernel-A
    walk (n-tiles outer, 128-row lhs tiles inner) with the column
    budget halved so the forward's TWO weight blocks double-buffer.
    int8 banks only (itemsize 1 in the budget — enforced by
    swiglu_gmm's signature taking q/scale pairs)."""
    budget = 4 * 1024 * 1024 // (k * 1) // 2  # two resident int8 blocks
    if k > MAX_K_A * 2 or budget < ALIGN:
        # mirror gmm's explicit failure instead of silently resident-
        # loading an oversized [K, N] bank (a Mosaic VMEM fault)
        raise NotImplementedError(
            f"swiglu_gmm: K={k} exceeds the fused kernel-A VMEM "
            "budget; use separate gmm calls (kernel B) for this shape"
        )
    bn = _pick_bn(n, budget)
    T = m // ALIGN
    pref = [group_of_tile] if base is None else [group_of_tile, base]

    def _g(p, t):
        g = p[0][t]
        return g if base is None else p[1][0] + g

    lhs_spec = pl.BlockSpec((ALIGN, k), lambda ni, t, *p: (t, 0))
    w_spec = pl.BlockSpec((1, k, bn), lambda ni, t, *p: (_g(p, t), 0, ni))
    s_spec = pl.BlockSpec((1, 1, bn), lambda ni, t, *p: (_g(p, t), 0, ni))
    row_spec = pl.BlockSpec((ALIGN, bn), lambda ni, t, *p: (t, ni))
    return pref, bn, T, lhs_spec, w_spec, s_spec, row_spec


def _swiglu_fwd_impl(lhs, wg, wu, sg, su, group_of_tile, base, interpret):
    m, k = lhs.shape
    n = wg.shape[2]
    pref, bn, T, lhs_spec, w_spec, s_spec, row_spec = _swiglu_specs(
        m, k, n, group_of_tile, base
    )
    return pl.pallas_call(
        functools.partial(_swiglu_fwd_kernel, has_base=base is not None),
        name="gmm_swiglu_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pref),
            grid=(n // bn, T),
            in_specs=[lhs_spec, w_spec, w_spec, s_spec, s_spec],
            out_specs=(row_spec, row_spec),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, n), lhs.dtype),
            jax.ShapeDtypeStruct((m, n), lhs.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*pref, lhs, wg, wu, sg, su)


def _swiglu_bwd_impl(lhs, wu, su, g, dh, group_of_tile, base, interpret):
    m, k = lhs.shape
    n = wu.shape[2]
    pref, bn, T, lhs_spec, w_spec, s_spec, row_spec = _swiglu_specs(
        m, k, n, group_of_tile, base
    )
    return pl.pallas_call(
        functools.partial(_swiglu_bwd_kernel, has_base=base is not None),
        name="gmm_swiglu_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pref),
            grid=(n // bn, T),
            in_specs=[lhs_spec, w_spec, s_spec, row_spec, row_spec],
            out_specs=(row_spec, row_spec),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, n), lhs.dtype),
            jax.ShapeDtypeStruct((m, n), lhs.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*pref, lhs, wu, su, g, dh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def swiglu_gmm(lhs, wg_q, wu_q, sg, su, group_offsets, group_base,
               interpret=None):
    """Fused grouped SwiGLU for int8 expert banks:
    ``h[r] = silu(x[r]·Wg[e]) ⊙ (x[r]·Wu[e])`` for rows in expert e's
    group, plus the gate pre-activation ``g`` as a second output. The
    vjp names its g residual "moe_g", so the QLoRA remat policy pins
    it and the backward recomputes ONLY u, fused with the dsilu
    epilogue (both measured: pinning g beats recomputing it, and the
    fused epilogue beats standalone [M, F] dsilu fusions). K ≤ the
    kernel-A budget only (the MoE D→F shape); frozen banks (no weight
    grads). Returns ``(h, g)``.
    """
    if interpret is None:
        interpret = _interpret_default()
    return _swiglu_fwd_fn(
        lhs, wg_q, wu_q, sg, su, group_offsets, group_base, interpret
    )


def _swiglu_fwd_fn(lhs, wg_q, wu_q, sg, su, group_offsets, group_base,
                   interpret):
    assert lhs.shape[0] % ALIGN == 0
    return _swiglu_fwd_impl(
        lhs, wg_q, wu_q, sg, su,
        _group_of_tile(lhs.shape[0], group_offsets), group_base,
        interpret,
    )


def _swiglu_vjp_fwd(lhs, wg_q, wu_q, sg, su, group_offsets, group_base,
                    interpret):
    if interpret is None:
        interpret = _interpret_default()
    h, g = _swiglu_fwd_fn(
        lhs, wg_q, wu_q, sg, su, group_offsets, group_base, interpret
    )
    # name the RESIDUAL itself: under save_only_these_names("moe_g")
    # the backward then reads the pinned value instead of re-running
    # the forward kernel (naming only the returned g would pin a value
    # the backward never consumes)
    g_saved = _checkpoint_name(g, "moe_g")
    return (h, g), (
        lhs, wg_q, wu_q, sg, su, group_offsets, group_base, g_saved
    )


def _swiglu_vjp_bwd(interpret, res, cts):
    lhs, wg_q, wu_q, sg, su, group_offsets, group_base, g = res
    dh, dg_out = cts
    if interpret is None:
        interpret = _interpret_default()
    dg, du = _swiglu_bwd_impl(
        lhs, wu_q, su, g, dh.astype(lhs.dtype),
        _group_of_tile(lhs.shape[0], group_offsets), group_base,
        interpret,
    )
    # g is also an OUTPUT (for the remat pin); fold any cotangent that
    # arrives on it into the pre-activation gradient (normally zero —
    # nothing consumes g downstream — and XLA DCEs the add)
    dg = dg + dg_out.astype(dg.dtype)
    # dlhs through both frozen banks, read "backwards" (trans) — the
    # same kernel-B/A machinery every gmm backward uses
    dlhs = _gmm_fwd_impl(
        dg, wg_q, group_offsets, trans_rhs=True, interpret=interpret,
        scale=sg, base=group_base,
    ) + _gmm_fwd_impl(
        du, wu_q, group_offsets, trans_rhs=True, interpret=interpret,
        scale=su, base=group_base,
    )
    return (dlhs, None, None, jnp.zeros_like(sg), jnp.zeros_like(su),
            None, None)


swiglu_gmm.defvjp(_swiglu_vjp_fwd, _swiglu_vjp_bwd)
