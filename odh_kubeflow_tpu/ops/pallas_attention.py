"""Pallas TPU flash attention (forward + backward).

Tiled attention that never materialises the [Sq, Sk] score matrix:
the kernel streams K/V blocks through VMEM and keeps an online-softmax
accumulator (running max ``m``, denominator ``l``, weighted sum ``acc``)
per Q block, so HBM traffic is O(S·d) instead of O(S²). The backward
pass recomputes scores from the saved logsumexp (flash-v2 style) in two
kernels: one accumulating dQ over K blocks, one accumulating dK/dV over
Q blocks.

Drop-in for ``ops.attention.dense_attention`` (same signature; the
reference platform has no attention code at all — SURVEY.md §2.4 — this
is a new TPU-native component). GQA is handled by mapping each Q head's
grid cell onto its KV head (``h // group``) in the K/V index maps, so
KV blocks are fetched once per group from HBM's point of view (Mosaic
caches the revisited block).

The grid's last axis walks a scalar-prefetched table of live
(Q-block, K-block) pairs, so a dead block is never dispatched. Without
``segment_ids`` liveness is the causal geometry alone and the tables
are built on the host (``_pair_tables``). With ``segment_ids`` they are
built on the device, per batch row, from the row's ids
(``_segment_tables``): a pair is live iff it is causally live AND the
id ranges of its Q rows and K columns overlap, so a packed row's
document walls kill blocks; the table also says which live tiles hold
one document wholly below the diagonal, and those take the mask-free
body. The MXU sees [tile_q, tile_k] @ [tile_k, hd] tiles — 128-aligned
by construction (inputs are padded).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# swept on v5e (fwd, S∈{1k,4k}): 1024×1024 beats 512×1024 by ~10%;
# both clamp to the sequence length for shorter inputs
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# With segment_ids the same blocks are walked as SEGMENT_TILE-square
# tiles, each skipped or run mask-free by a bit of the prefetched table.
# Swept on v5e at the training cell's shape (2 x 4096, 32 / 8 heads x
# 128, rows of pack_documents; fwd / dq / dkv ms a call; PERF.md, PR
# 29). The parent's masked 1024 walk: 3.56 / 3.84 / 4.96. 1024 blocks
# in 512 tiles: 2.52 / 3.26 / 4.22. The block as one tile: 3.01 / 3.59 /
# 4.50. 256 tiles: 6.49 / 4.41 / 8.23 (too few rows stream behind each
# weight tile the MXU loads). 512 x 1024 and 1024 x 512 tiles, 2048
# blocks: within 3 % of the choice, or worse. 512 blocks on the grid
# lost to 512 tiles in a 1024 block in all three kernels (4.08 / 3.70 /
# 4.86 against 3.91 / 3.41 / 4.46, the forward's statistics still in
# columns): a grid step costs more than a skipped bit.
SEGMENT_TILE = 512
_NEG_INF = -1e30
# The softmax runs in base-2 end to end: log2(e) folds into the q
# prescale (one [bq, hd] multiply), so the VPU evaluates raw exp2 on
# the [bq, bk] score blocks instead of exp = exp2(x·log2e) — one fewer
# full-block multiply per exponential, and the kernel is VPU-bound at
# small head_dim. The saved logsumexp residual is likewise base-2
# (lse2 = log2e·lse); it never leaves this file.
_LOG2E = 1.4426950408889634


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _pair_tables(*, num_q, num_k, causal, q_offset, sk, block_q, block_k,
                 order, group=1):
    """Static live-(Q-block, K-block) pair tables for the triangular
    grids (scalar-prefetched, like ``pallas_grouped_matmul.span_pairs``
    but fully host-side: liveness depends only on static geometry).

    The old grids ran the full num_q×num_k rectangle and predicated
    dead blocks off — at 16k/1024 causal that is ~half the programs
    dispatched for nothing. Here the grid's last axis walks live pairs
    only.

    order="row": pairs sorted by (qi, ki) — fwd/dq walk, accumulator
    keyed on the Q block. order="col": sorted by (ki, g, qj) with the
    GQA group folded in — the dkv walk, accumulator keyed on the K
    block. An owner with no live partner gets one synthetic masked
    pair so its output block is still initialised and finalised
    (l = 0 ⇒ zero output — the dense semantics fully-masked ring
    shards rely on).

    Returns int32 arrays of length L: ``qi``, ``ki``, ``g`` (0 unless
    order="col"), ``first``/``last`` (accumulator init/flush flags).
    """
    import numpy as np

    def live(qb, kb):
        if kb * block_k >= sk:
            return False
        if not causal:
            return True
        return kb * block_k <= qb * block_q + (block_q - 1) + q_offset

    qi_l, ki_l, g_l, first_l, last_l = [], [], [], [], []

    def emit(items):
        for j, (qi, kb, g) in enumerate(items):
            qi_l.append(qi)
            ki_l.append(kb)
            g_l.append(g)
            first_l.append(int(j == 0))
            last_l.append(int(j == len(items) - 1))

    if order == "row":
        for qi in range(num_q):
            kbs = [kb for kb in range(num_k) if live(qi, kb)]
            emit([(qi, kb, 0) for kb in (kbs or [0])])
    else:
        for kb in range(num_k):
            qjs = [qj for qj in range(num_q) if live(qj, kb)]
            items = [(qj, kb, g) for g in range(group) for qj in qjs]
            emit(items or [(0, kb, 0)])
    return tuple(
        jnp.asarray(np.asarray(a, np.int32))
        for a in (qi_l, ki_l, g_l, first_l, last_l)
    )


def _tile_geometry(nq, nk, *, causal, q_offset, sk, tile_q, tile_k):
    """Static [nq, nk] numpy masks of a (tile_q, tile_k) grid: ``live``
    (some pair of the tile is causally live and not a padded K column:
    the predicate ``_pair_tables`` walks) and ``full`` (every pair is,
    so the tile needs no mask)."""
    import numpy as np

    qb = np.arange(nq)[:, None]
    kb = np.arange(nk)[None, :]
    live = np.broadcast_to(kb * tile_k < sk, (nq, nk))
    full = np.broadcast_to((kb + 1) * tile_k <= sk, (nq, nk))
    if causal:
        live = live & (kb * tile_k <= qb * tile_q + (tile_q - 1) + q_offset)
        full = full & (qb * tile_q + q_offset >= kb * tile_k + (tile_k - 1))
    return live, full


def _segment_tables(qseg, kseg, *, causal, q_offset, sk, block_q, block_k,
                    tile_q, tile_k, order, group=1):
    """``_pair_tables`` from the data: the live-pair tables of a
    segmented call, built on the device per batch row from the row's
    ids (``qseg`` [B, Sq, 1], ``kseg`` [B, 1, Sk], padded as ``_prep``
    pads them).

    A (tile_q, tile_k) tile is live iff it is causally live and the id
    ranges (min..max) of its Q rows and K columns overlap: exact for
    ``pack_documents``' ascending ids, a superset for arbitrary ones,
    and right either way because the in-tile mask stays. It is full iff
    both sides hold the same single id and the geometry masks nothing.
    A (block_q, block_k) block is walked iff one of its tiles is live.

    Returns ``(tabs, live, causal)``. ``tabs`` are seven int32
    [B, L_max] arrays, L_max the static length of ``_pair_tables``'
    walk: ``qi, ki, g, first, last`` as there, then ``run`` and
    ``full``, bit ``qs * (block_k // tile_k) + ks`` for tile (qs, ks)
    of the block. Entries past a row's live count repeat its last live
    pair (the index maps start no new DMA) with every flag 0; an owner
    with no live partner keeps one entry with ``run`` 0, which
    initialises and flushes its output (l = 0, zero output). ``live``
    and ``causal`` count, over the batch, the tiles that run and the
    tiles the causal geometry alone would run."""
    B = qseg.shape[0]
    num_q, num_k = qseg.shape[1] // block_q, kseg.shape[2] // block_k
    sub_q, sub_k = block_q // tile_q, block_k // tile_k
    assert sub_q * sub_k < 32, (block_q, block_k, tile_q, tile_k)
    geom = dict(causal=causal, q_offset=q_offset, sk=sk)
    tile_live, tile_full = _tile_geometry(
        num_q * sub_q, num_k * sub_k, tile_q=tile_q, tile_k=tile_k, **geom
    )
    block_live, _ = _tile_geometry(
        num_q, num_k, tile_q=block_q, tile_k=block_k, **geom
    )

    qt = qseg.reshape(B, num_q * sub_q, tile_q)
    kt = kseg.reshape(B, num_k * sub_k, tile_k)
    q_lo, q_hi = qt.min(-1)[:, :, None], qt.max(-1)[:, :, None]
    k_lo, k_hi = kt.min(-1)[:, None, :], kt.max(-1)[:, None, :]
    run_t = (q_lo <= k_hi) & (k_lo <= q_hi) & tile_live
    full_t = (q_lo == q_hi) & (k_lo == k_hi) & (q_lo == k_lo) & tile_full

    def bits(t):  # [B, num_q, num_k]: a block's tiles, one bit each
        t = t.reshape(B, num_q, sub_q, num_k, sub_k).transpose(0, 1, 3, 2, 4)
        weights = 1 << jnp.arange(sub_q * sub_k, dtype=jnp.int32)
        return jnp.sum(
            t.reshape(B, num_q, num_k, -1).astype(jnp.int32) * weights, -1
        )

    run_b, full_b = bits(run_t), bits(full_t & run_t)
    walk = run_b != 0  # [B, num_q, num_k]
    if order == "row":
        # (qi, ki), owner qi; an owner left with no partner walks ki 0
        need = walk.at[:, :, 0].set(walk[:, :, 0] | ~walk.any(2))
        per_owner = num_k
        l_max = int(block_live.sum(1).clip(min=1).sum())
    else:
        # (ki, g, qj), owner ki; a lone owner walks (qj 0, g 0)
        need = jnp.broadcast_to(
            walk.transpose(0, 2, 1)[:, :, None, :], (B, num_k, group, num_q)
        )
        need = need.at[:, :, 0, 0].set(need[:, :, 0, 0] | ~walk.any(1))
        per_owner = group * num_q
        l_max = int((group * block_live.sum(0)).clip(min=1).sum())
    need = need.reshape(B, -1)
    n_all = need.shape[1]

    # compact each row's needed entries to the front, in walk order
    slot = jnp.cumsum(need, axis=1, dtype=jnp.int32) - 1  # [B, n_all]
    count = slot[:, -1:] + 1
    j = jnp.arange(l_max, dtype=jnp.int32)
    hit = need[:, None, :] & (slot[:, None, :] == j[None, :, None])
    n = jnp.arange(n_all, dtype=jnp.int32)
    idx = jnp.sum(jnp.where(hit, n, 0), -1)  # [B, l_max]
    valid = j[None, :] < count
    idx = jnp.where(valid, idx, jnp.max(jnp.where(need, n, 0), 1)[:, None])

    owner = idx // per_owner
    edge = jnp.ones((B, 1), bool)
    first = valid & jnp.concatenate([edge, owner[:, 1:] != owner[:, :-1]], 1)
    last = valid & (
        jnp.concatenate([owner[:, 1:] != owner[:, :-1], edge], 1)
        | (j[None, :] == count - 1)
    )
    if order == "row":
        qi, ki, g = idx // num_k, idx % num_k, jnp.zeros_like(idx)
    else:
        ki, g, qi = owner, idx % per_owner // num_q, idx % num_q
    at = qi * num_k + ki

    def entry_bits(b):
        return jnp.where(
            valid, jnp.take_along_axis(b.reshape(B, -1), at, axis=1), 0
        )

    tabs = (
        qi, ki, g, first.astype(jnp.int32), last.astype(jnp.int32),
        entry_bits(run_b), entry_bits(full_b),
    )
    live = jnp.sum(run_t, dtype=jnp.int32)
    return tabs, live, B * int(tile_live.sum())


def _lanes(x, n):
    """A row statistic for an [r, n] operand: a [r, 1] column as it is
    (it broadcasts), a [r, 128] one, the same value in every lane, cut
    or repeated to n lanes with no broadcast."""
    if x.shape[1] == 1:
        return x
    if n <= 128:
        return x[:, :n]
    if n % 128 == 0:
        return pltpu.repeat(x, n // 128, 1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _entry(tabs, per_row):
    """This grid step's entry of the pair tables, one scalar a table."""
    i = pl.program_id(2)
    at = (pl.program_id(0), i) if per_row else (i,)
    return [t[at] for t in tabs]


def _index_maps(per_row, group):
    """The index maps of the three kernels' operands, reading the
    scalar-prefetched tables ``t = (qi, ki, g, ...)`` at grid step
    (b, h, i): [L] tables of a static walk, [B, L] of a per-row one."""
    if per_row:
        def at(table, b, i):
            return table[b, i]
    else:
        def at(table, b, i):
            return table[i]

    return dict(
        # forward / dq: the grid's h is the Q head
        q=lambda b, h, i, *t: (b, h, at(t[0], b, i), 0),
        kv=lambda b, h, i, *t: (b, h // group, at(t[1], b, i), 0),
        # dk/dv: the grid's h is the KV head, the table's g the member
        q_of_kv=lambda b, h, i, *t: (
            b, h * group + at(t[2], b, i), at(t[0], b, i), 0
        ),
        kv_own=lambda b, h, i, *t: (b, h, at(t[1], b, i), 0),
        qseg=lambda b, h, i, *t: (b, at(t[0], b, i), 0),
        kseg=lambda b, h, i, *t: (b, 0, at(t[1], b, i)),
    )


def _for_tiles(entry, qseg_ref, kseg_ref, body, *, causal, q_offset, sk,
               block_q, block_k, tile_q, tile_k):
    """Run ``body(mask, rows, cols)`` on each tile of this step's block
    that holds a live pair: ``rows``/``cols`` slice the block's refs,
    ``mask`` is None on a tile where every pair is live (no
    iota/compare/select between the MXU dots) and a thunk of the
    [tile_q, tile_k] live-pair mask elsewhere.

    Unsegmented, the tile is the block (``_default_blocks``), always
    live (``_pair_tables`` walks live pairs only), and full by the
    geometry. Segmented, the
    table's ``run``/``full`` bits say both per tile."""
    qi, ki = entry[0], entry[1]
    segmented = qseg_ref is not None
    sub_q, sub_k = block_q // tile_q, block_k // tile_k

    def tile(t, rows, cols):
        q0 = qi * block_q + rows.start
        k0 = ki * block_k + cols.start

        def mask():
            q_pos = q0 + jax.lax.broadcasted_iota(
                jnp.int32, (tile_q, tile_k), 0
            )
            k_pos = k0 + jax.lax.broadcasted_iota(
                jnp.int32, (tile_q, tile_k), 1
            )
            m = k_pos < sk  # padded K columns never contribute
            if causal:
                m = jnp.logical_and(m, q_pos + q_offset >= k_pos)
            if segmented:
                m = jnp.logical_and(
                    m, qseg_ref[0, rows] == kseg_ref[0, :, cols]
                )
            return m

        if segmented:
            full = ((entry[6] >> t) & 1) == 1  # set only where run is
            edge = jnp.logical_and(
                ((entry[5] >> t) & 1) == 1, jnp.logical_not(full)
            )
        else:
            full = (k0 + tile_k) <= sk
            if causal:
                full = jnp.logical_and(
                    full, q0 + q_offset >= k0 + (tile_k - 1)
                )
            edge = jnp.logical_not(full)
        pl.when(full)(functools.partial(body, None, rows, cols))
        pl.when(edge)(functools.partial(body, mask, rows, cols))

    if sub_q * sub_k == 1:
        tile(0, pl.ds(0, tile_q), pl.ds(0, tile_k))
        return

    # one traced body a kind of tile, whatever their number: the loop
    # addresses the tile (aligned, so the slices stay whole vregs)
    def step(t, _):
        tile(
            t,
            pl.ds(pl.multiple_of(t // sub_k * tile_q, tile_q), tile_q),
            pl.ds(pl.multiple_of(t % sub_k * tile_k, tile_k), tile_k),
        )

    jax.lax.fori_loop(0, sub_q * sub_k, step, None)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    tabs,  # scalar-prefetch refs (see _pair_tables / _segment_tables)
    q_ref,  # [1, 1, block_q, hd]   (prescaled by scale·log2e in HBM)
    k_ref,  # [1, 1, block_k, hd]
    v_ref,  # [1, 1, block_k, hd+1] when aug (ones column), else hd
    qseg_ref,  # [1, block_q, 1] or None
    kseg_ref,  # [1, 1, block_k] or None
    o_ref,  # [1, 1, block_q, hd]
    lse_ref,  # [1, 1, block_q, 1]
    acc_scr,  # [block_q, hd+1] f32 when aug (last column = l), else hd
    m_scr,  # [block_q, 1 or 128] f32 (see the body)
    l_scr,  # as m_scr — used only when not aug
    *,
    hd: int,
    aug: bool,
    **geom,
):
    entry = _entry(tabs, qseg_ref is not None)

    @pl.when(entry[3] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if not aug:
            l_scr[...] = jnp.zeros_like(l_scr)

    def body(mask, rows, cols):
        # Dots take the native (bf16) operands — the MXU runs bf16
        # inputs at full rate — and accumulate in f32 via
        # preferred_element_type. Softmax statistics stay f32.
        q = q_ref[0, 0, rows]
        k = k_ref[0, 0, cols]
        v = v_ref[0, 0, cols]

        s = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        if mask is not None:
            mask = mask()
            s = jnp.where(mask, s, _NEG_INF)

        # m and l are [rows, 1], or, in a segmented call, [rows, 128]
        # with the value in every lane (_fwd): the maximum below
        # broadcasts the new column into them
        m_prev = m_scr[rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - _lanes(m_new, s.shape[1]))
        if mask is not None:
            # Re-mask after the exp: on a row with no live column yet,
            # m_new == _NEG_INF and exp(s - m_new) == 1 for masked
            # entries, which would poison l/acc with phantom mass.
            p = jnp.where(mask, p, 0.0)
        if not aug:
            l_scr[rows] = l_scr[rows] * alpha + jnp.sum(
                p, axis=1, keepdims=True
            )
        # when aug, v's appended ones column makes the pv dot carry the
        # softmax denominator through the same rescale recurrence as
        # the numerator (l_new = α·l + Σp rides in acc[:, hd]) — the
        # VPU row-sum pass moves onto MXU lanes that were pad at hd=64
        acc_scr[rows] = acc_scr[rows] * _lanes(
            alpha, acc_scr.shape[1]
        ) + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[rows] = m_new

    _for_tiles(entry, qseg_ref, kseg_ref, body, **geom)

    @pl.when(entry[4] == 1)
    def _finalize():
        acc = acc_scr[...]
        l = acc[:, hd:] if aug else l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
        o_ref[0, 0] = (acc[:, :hd] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[:, :1] + jnp.log2(l_safe)


def _tables(qseg, kseg, *, num_q, num_k, tile_q, tile_k, **walk):
    """The pair tables of a call: static from the geometry, or, with
    segment ids, per row from the data."""
    if qseg is None:
        return _pair_tables(num_q=num_q, num_k=num_k, **walk)
    return _segment_tables(
        qseg, kseg, tile_q=tile_q, tile_k=tile_k, **walk
    )[0]


def _fwd(
    q,  # [B, Hq, Sq, hd]  (padded, head-major)
    k,  # [B, Hkv, Sk, hd]
    v,
    qseg,  # [B, Sq, 1] int32 or None
    kseg,  # [B, 1, Sk] int32 or None
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    sk: int,
    block_q: int,
    block_k: int,
    tile_q: int,
    tile_k: int,
    interpret: bool,
):
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    # operand augmentation rides MXU lanes that are pad at hd=64 — but
    # at 128-aligned head dims it would push every block to the next
    # 128 multiple (hd=128 → 2× dot cost), so gate it
    aug = hd % 128 != 0

    geom = dict(
        causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k, tile_q=tile_q, tile_k=tile_k,
    )
    tabs = _tables(
        qseg, kseg, num_q=Sq // block_q, num_k=Sk // block_k, order="row",
        **geom,
    )
    n_tabs = len(tabs)
    # base-2 softmax fold rides the q prescale, done once in HBM (the
    # in-kernel variant redid the multiply on every (qi, ki) revisit);
    # python-float × bf16 rounds identically either way
    q = q * (scale * _LOG2E)
    if aug:
        # ones column: the pv dot computes numerator AND denominator
        v = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)
    hd_v = v.shape[-1]
    # A segmented call walks a block tile by tile, and every tile
    # rescales by the running max and denominator: a [rows, 1] column
    # is one used lane a vreg and a lane broadcast at every use, which
    # at 512-wide tiles was a third of the forward (3.67 -> 2.52 ms a
    # call at the training cell's shape; v5e, PR 29). So there the two
    # live in every lane of [rows, 128]. The static walk's one tile a
    # block reads the same either way (3.01 / 3.05 ms) and keeps its
    # columns.
    stat_lanes = 1 if qseg is None else 128

    at = _index_maps(qseg is not None, group)

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    in_specs = [
        spec((1, 1, block_q, hd), at["q"]),
        spec((1, 1, block_k, hd), at["kv"]),
        spec((1, 1, block_k, hd_v), at["kv"]),
    ]
    args = [q, k, v]
    if qseg is not None:
        # qseg rides as a [B, Sq, 1] column, kseg as a [B, 1, Sk] row:
        # both shapes satisfy Mosaic's (8, 128)-or-full tiling rule and
        # broadcast against each other inside the kernel.
        in_specs += [
            spec((1, block_q, 1), at["qseg"]),
            spec((1, 1, block_k), at["kseg"]),
        ]
        args += [qseg, kseg]

    def kernel(*refs):
        tabs_r, rest = refs[:n_tabs], list(refs[n_tabs:])
        if qseg is None:
            rest[3:3] = [None, None]
        _fwd_kernel(tabs_r, *rest, hd=hd, aug=aug, **geom)

    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_tabs,
            grid=(B, Hq, tabs[0].shape[-1]),
            in_specs=in_specs,
            out_specs=(
                spec((1, 1, block_q, hd), at["q"]),
                spec((1, 1, block_q, 1), at["q"]),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, hd_v), jnp.float32),
                pltpu.VMEM((block_q, stat_lanes), jnp.float32),
                pltpu.VMEM((block_q, stat_lanes), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*tabs, *args)
    return out, lse

# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    tabs,  # scalar-prefetch refs, order="row"
    q_ref,  # aug: [1,1,bq,hd+2] = [q·scale·log2e | lse_hi | lse_lo];
            # else [1,1,bq,hd] prescaled q
    k_ref,  # aug: [1,1,bk,hd+2] = [k | -1 | -1]; else [1,1,bk,hd]
    v_ref,  # aug: [1,1,bk,hd+2] = [v | -1 | -1]; else [1,1,bk,hd]
    do_ref,  # aug: [1,1,bq,hd+2] = [do | δ_hi | δ_lo]; else [1,1,bq,hd]
    lse_ref,  # [1,1,bq,1] f32 — only when not aug (else folded into q)
    delta_ref,  # [1,1,bq,1] f32 — only when not aug
    qseg_ref,
    kseg_ref,
    dq_ref,  # [1, 1, block_q, hd]
    dq_scr,  # [block_q, operand width] f32
    *,
    scale: float,
    hd: int,
    **geom,
):
    entry = _entry(tabs, qseg_ref is not None)

    @pl.when(entry[3] == 1)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def body(mask, rows, cols):
        # Augmented mode (hd not 128-aligned): the row constants ride
        # the contraction instead of the VPU — q's two appended columns
        # carry lse (hi/lo split; one bf16 column would cost ~3 decimal
        # digits on the exponent), k's carry -1, so the s dot lands
        # directly on s·log2e·scale − lse and exp2 applies with no
        # [bq, bk] subtract pass; same for delta via do/v. The extra
        # columns are free — at hd=64 the MXU lanes were pad anyway.
        # At hd % 128 == 0 the same trick would push blocks to the next
        # lane multiple (2× dot cost), so lse/delta arrive as row
        # operands and subtract on the VPU instead.
        q = q_ref[0, 0, rows]
        k = k_ref[0, 0, cols]
        v = v_ref[0, 0, cols]
        do = do_ref[0, 0, rows]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if lse_ref is not None:
            s = s - lse_ref[0, 0, rows]
        p = jnp.exp2(s)
        if mask is not None:
            p = jnp.where(mask(), p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if delta_ref is not None:
            dp = dp - delta_ref[0, 0, rows]
        ds = p * dp
        # aug: contracting against k_aug writes junk into dq_scr[:, hd:],
        # sliced off at the finalize
        dq_scr[rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _for_tiles(entry, qseg_ref, kseg_ref, body, **geom)

    @pl.when(entry[4] == 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[:, :hd] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    tabs,  # scalar-prefetch refs, order="col": sorted by K block, the
           # GQA group member of a pair in ``g``
    q_ref,  # same operand layouts as _dq_kernel (aug vs not)
    k_ref,
    v_ref,
    do_ref,
    lse_ref,  # [1,1,bq,1] f32 — only when not aug
    delta_ref,  # [1,1,bq,1] f32 — only when not aug
    qseg_ref,
    kseg_ref,
    dk_ref,  # [1, 1, block_k, hd]  per-KV-head
    dv_ref,
    dk_scr,  # [block_k, operand width] f32
    dv_scr,
    *,
    hd: int,
    **geom,
):
    entry = _entry(tabs, qseg_ref is not None)

    @pl.when(entry[3] == 1)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(mask, rows, cols):
        # Same operand folds as _dq_kernel (see the comment there).
        q = q_ref[0, 0, rows]
        k = k_ref[0, 0, cols]
        v = v_ref[0, 0, cols]
        do = do_ref[0, 0, rows]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if lse_ref is not None:
            s = s - lse_ref[0, 0, rows]
        p = jnp.exp2(s)
        if mask is not None:
            p = jnp.where(mask(), p, 0.0)
        # aug: do's δ columns write junk into dv_scr[:, hd:], sliced at
        # the finalize; likewise q's lse columns for dk_scr
        dv_scr[cols] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if delta_ref is not None:
            dp = dp - delta_ref[0, 0, rows]
        ds = p * dp
        dk_scr[cols] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _for_tiles(entry, qseg_ref, kseg_ref, body, **geom)

    @pl.when(entry[4] == 1)
    def _finalize():
        # the dk dot contracted against the PRE-SCALED q (·scale·log2e);
        # the raw-s gradient needs ·scale against raw q, so divide the
        # log2e back out
        dk_ref[0, 0] = (dk_scr[:, :hd] * (1.0 / _LOG2E)).astype(
            dk_ref.dtype
        )
        dv_ref[0, 0] = dv_scr[:, :hd].astype(dv_ref.dtype)


def _bwd(
    q,
    k,
    v,
    qseg,
    kseg,
    out,
    lse,
    do,
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    sk: int,
    block_q: int,
    block_k: int,
    tile_q: int,
    tile_k: int,
    interpret: bool,
):
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    # see _fwd: operand augmentation only where the lanes are pad anyway
    aug = hd % 128 != 0

    # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, XLA fuses it.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    q = q * (scale * _LOG2E)  # base-2 fold, once in HBM
    if aug:
        # Row constants fold into the dots via two appended operand
        # columns (hi/lo bf16 split keeps f32-grade precision; one bf16
        # column would cost ~2% on exp2).
        def _hi_lo(x):
            hi = x.astype(k.dtype)
            lo = (x - hi.astype(x.dtype)).astype(k.dtype)
            return hi, lo

        lse_hi, lse_lo = _hi_lo(lse)
        d_hi, d_lo = _hi_lo(delta)
        neg1 = -jnp.ones_like(k[..., :1])
        q = jnp.concatenate([q, lse_hi, lse_lo], -1)
        k = jnp.concatenate([k, neg1, neg1], -1)
        v = jnp.concatenate([v, neg1, neg1], -1)
        do = jnp.concatenate([do, d_hi, d_lo], -1)
    hd2 = q.shape[-1]

    geom = dict(
        causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k, tile_q=tile_q, tile_k=tile_k,
    )
    at = _index_maps(qseg is not None, group)

    def spec(shape, index_map):
        return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)

    def call(name, kernel, order, q_map, kv_map, heads, out_map, out_block,
             out_shape, **kernel_kw):
        """One backward kernel over the walk ``order`` gives: operands
        q, k, v, do (+ lse, delta when not aug; + the segment ids), its
        outputs and one f32 accumulator each."""
        tabs = _tables(
            qseg, kseg, num_q=Sq // block_q, num_k=Sk // block_k,
            order=order, group=group, **geom,
        )
        n_tabs = len(tabs)
        qblk = spec((1, 1, block_q, hd2), q_map)
        kvblk = spec((1, 1, block_k, hd2), kv_map)
        args, specs = [q, k, v, do], [qblk, kvblk, kvblk, qblk]
        if not aug:
            args += [lse, delta]
            specs += [spec((1, 1, block_q, 1), q_map)] * 2
        if qseg is not None:
            args += [qseg, kseg]
            specs += [
                spec((1, block_q, 1), at["qseg"]),
                spec((1, 1, block_k), at["kseg"]),
            ]

        def wrapped(*refs):
            tabs_r, rest = refs[:n_tabs], list(refs[n_tabs:])
            if aug:
                rest[4:4] = [None, None]
            if qseg is None:
                rest[6:6] = [None, None]
            kernel(tabs_r, *rest, hd=hd, **kernel_kw, **geom)

        return pl.pallas_call(
            wrapped,
            name=name,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=n_tabs,
                grid=(B, heads, tabs[0].shape[-1]),
                in_specs=specs,
                out_specs=[spec(out_block, out_map)] * len(out_shape),
                scratch_shapes=[
                    pltpu.VMEM((out_block[2], hd2), jnp.float32)
                ] * len(out_shape),
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(*tabs, *args)

    # --- dQ: grid (B, Hq, live pairs), accumulate over K blocks ------
    (dq,) = call(
        "flash_bwd_dq", _dq_kernel, "row", at["q"], at["kv"], Hq,
        at["q"], (1, 1, block_q, hd),
        [jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype)],
        scale=scale,
    )
    # --- dK/dV: grid (B, Hkv, live (ki, g, qj) triples). The GQA
    # group is folded into the pair walk, so dK/dV accumulate per KV
    # head in VMEM scratch and hit HBM exactly once, in k.dtype — no
    # per-Q-head f32 transients.
    dk, dv = call(
        "flash_bwd_dkv", _dkv_kernel, "col", at["q_of_kv"], at["kv_own"],
        Hkv, at["kv_own"], (1, 1, block_k, hd),
        [
            jax.ShapeDtypeStruct((B, Hkv, Sk, hd), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Sk, hd), v.dtype),
        ],
    )
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13)
)
def _flash(q, k, v, segment_ids, causal, q_offset, sq, sk,
           block_q, block_k, bwd_block_q, bwd_block_k, tile, interpret):
    out, _ = _flash_fwd(
        q, k, v, segment_ids, causal, q_offset, sq, sk,
        block_q, block_k, bwd_block_q, bwd_block_k, tile, interpret,
    )
    return out


def _prep(q, k, v, segment_ids, sq, sk, block_q, block_k):
    """[B,S,H,d] → padded head-major [B,H,S,d] plus padded segment ids."""
    sq_p, sk_p = _ceil_to(sq, block_q), _ceil_to(sk, block_k)
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    if sq_p != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
    qseg = kseg = None
    if segment_ids is not None:
        qseg, kseg = _prep_segments(segment_ids, sq, sk, sq_p, sk_p)
    return qt, kt, vt, qseg, kseg


def _prep_segments(segment_ids, sq, sk, sq_p, sk_p):
    """Padded rows/cols get sentinel ids that never match real ones.
    Shapes: qseg [B, Sq, 1] (column), kseg [B, 1, Sk] (row) — see the
    spec comment in _fwd."""
    seg = segment_ids.astype(jnp.int32)
    qseg = jnp.pad(seg, ((0, 0), (0, sq_p - sq)),
                   constant_values=-1)[:, :, None]
    kseg = jnp.pad(seg[:, :sk], ((0, 0), (0, sk_p - sk)),
                   constant_values=-2)[:, None, :]
    return qseg, kseg


def _tile_of(block: int, tile: Optional[int]) -> int:
    """The tile a block is walked in: ``tile`` where it divides the
    block, else the whole block."""
    return tile if tile and block % tile == 0 else block


def _flash_fwd(q, k, v, segment_ids, causal, q_offset, sq, sk,
               block_q, block_k, bwd_block_q, bwd_block_k, tile, interpret):
    hd = q.shape[-1]
    scale = hd**-0.5
    qt, kt, vt, qseg, kseg = _prep(
        q, k, v, segment_ids, sq, sk, block_q, block_k
    )
    out_p, lse = _fwd(
        qt, kt, vt, qseg, kseg,
        scale=scale, causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k,
        tile_q=_tile_of(block_q, tile), tile_k=_tile_of(block_k, tile),
        interpret=interpret,
    )
    # Named residuals: under ``jax.checkpoint`` a policy that saves
    # "flash_out"/"flash_lse" (models/llama.py remat_policy="attn")
    # keeps exactly these two tensors, so the backward pass never
    # re-executes the forward flash kernel — the recompute is reduced
    # to the (cheap) projections while attention runs fwd-once +
    # bwd-once. O(S·Hq·hd) extra residency per layer, vs the O(S²)
    # score matrix flash exists to avoid.
    out_p = _checkpoint_name(out_p, "flash_out")
    lse = _checkpoint_name(lse, "flash_lse")
    out = jnp.moveaxis(out_p[:, :, :sq], 2, 1)
    return out, (q, k, v, segment_ids, out_p, lse)


def _flash_bwd(causal, q_offset, sq, sk, block_q, block_k,
               bwd_block_q, bwd_block_k, tile, interpret, res, g):
    q, k, v, segment_ids, out_p, lse = res
    hd = q.shape[-1]
    scale = hd**-0.5
    # The dq/dkv kernels have different arithmetic (3 dots each, larger
    # VMEM working set) than the forward, so their optimal tiling
    # differs — they get their own block sizes. Residuals out_p/lse are
    # padded to the FORWARD block multiple; re-pad to the backward one
    # when they disagree (padded q rows are zero ⇒ s = 0 and do = 0
    # there, so any finite lse fill keeps the padded contributions 0).
    bq, bk = bwd_block_q or block_q, bwd_block_k or block_k
    qt, kt, vt, qseg, kseg = _prep(
        q, k, v, segment_ids, sq, sk, bq, bk
    )
    sq_p = qt.shape[2]
    if out_p.shape[2] != sq_p:
        out_p = out_p[:, :, :sq]
        lse = lse[:, :, :sq]
        if sq_p != sq:
            pad = ((0, 0), (0, 0), (0, sq_p - sq), (0, 0))
            out_p = jnp.pad(out_p, pad)
            lse = jnp.pad(lse, pad)
    do = jnp.moveaxis(g, 1, 2)
    if sq_p != sq:
        do = jnp.pad(do, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    dq, dk, dv = _bwd(
        qt, kt, vt, qseg, kseg, out_p, lse, do,
        scale=scale, causal=causal, q_offset=q_offset, sk=sk,
        block_q=bq, block_k=bk,
        tile_q=_tile_of(bq, tile), tile_k=_tile_of(bk, tile),
        interpret=interpret,
    )
    dq = jnp.moveaxis(dq[:, :, :sq], 2, 1)
    dk = jnp.moveaxis(dk[:, :, :sk], 2, 1)
    dv = jnp.moveaxis(dv[:, :, :sk], 2, 1)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def _default_blocks(segmented: bool, sq: int, sk: int, block_q, block_k, tile):
    """The blocks and the tile of a call from what it can see: its
    lengths and whether it carries segment ids."""
    block_q = min(block_q or DEFAULT_BLOCK_Q, _ceil_to(sq, 128))
    block_k = min(block_k or DEFAULT_BLOCK_K, _ceil_to(sk, 128))
    if not segmented:
        tile = None  # the geometry alone is walked block by block
    elif tile is None:
        tile = SEGMENT_TILE
    return block_q, block_k, tile


def live_block_counts(segment_ids: jnp.ndarray) -> tuple:
    """``(live, causal)``: over a batch of packed rows [B, S], the
    tiles the flash kernels run for causal self-attention with these
    ``segment_ids`` at the default blocks, and the tiles the causal
    geometry alone would make them run. The same for every head, layer
    and kernel of a step."""
    S = segment_ids.shape[1]
    block_q, block_k, tile = _default_blocks(True, S, S, None, None, None)
    sq_p, sk_p = _ceil_to(S, block_q), _ceil_to(S, block_k)
    qseg, kseg = _prep_segments(segment_ids, S, S, sq_p, sk_p)
    _, live, causal = _segment_tables(
        qseg, kseg, causal=True, q_offset=0, sk=S,
        block_q=block_q, block_k=block_k,
        tile_q=_tile_of(block_q, tile), tile_k=_tile_of(block_k, tile),
        order="row",
    )
    return live, causal


def flash_attention(
    q: jnp.ndarray,  # [B, Sq, Hq, hd]
    k: jnp.ndarray,  # [B, Sk, Hkv, hd]
    v: jnp.ndarray,  # [B, Sk, Hkv, hd]
    *,
    causal: bool = True,
    q_offset: int = 0,
    segment_ids: Optional[jnp.ndarray] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention; same contract as ``dense_attention``.

    ``q_offset`` must be a static python int on this path (the pallas
    grid's causal-skip predicate is specialised on it); the decode path
    with a traced offset should use ``dense_attention``.

    With ``segment_ids`` a block is walked in ``tile``-square tiles
    (``SEGMENT_TILE``; the whole block where the tile does not divide
    it), each skipped where the row's documents leave no pair alive.

    ``bwd_block_q``/``bwd_block_k`` tile the dq/dkv kernels
    independently of the forward (their 3-dot bodies have a different
    VMEM/VPU balance); None inherits the forward blocks.
    """
    if not isinstance(q_offset, int):
        raise TypeError(
            "flash_attention requires a static int q_offset; use "
            "dense_attention for traced offsets (KV-cache decode)."
        )
    B, sq, Hq, hd = q.shape
    _, sk, Hkv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    if interpret is None:
        interpret = _interpret_default()
    block_q, block_k, tile = _default_blocks(
        segment_ids is not None, sq, sk, block_q, block_k, tile
    )
    if bwd_block_q is not None:
        bwd_block_q = min(bwd_block_q, _ceil_to(sq, 128))
    if bwd_block_k is not None:
        bwd_block_k = min(bwd_block_k, _ceil_to(sk, 128))
    return _flash(
        q, k, v, segment_ids, causal, q_offset, sq, sk,
        block_q, block_k, bwd_block_q, bwd_block_k, tile, interpret,
    )
