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

Causal masking skips fully-masked K blocks via predication
(``pl.when``), and the MXU sees [block_q, block_k] @ [block_k, hd]
tiles — 128-aligned by construction (inputs are padded).
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




def _block_full(qb, ki, *, causal, q_offset, sk, block_q, block_k):
    """True iff EVERY (row, col) pair of the block is live — interior
    causal blocks with no padded K columns, the hot case at long
    context (S=16k, block 1024: 120 of 136 live blocks are full). Full
    blocks skip the iota/compare/select mask arithmetic, which is what
    the VPU otherwise burns time on between the MXU dots. (Liveness
    itself is static now — _pair_tables enumerates live pairs — so
    there is no 'run' predicate anymore.)"""
    full = (ki + 1) * block_k <= sk
    if causal:
        full = jnp.logical_and(
            full, qb * block_q + q_offset >= ki * block_k + (block_k - 1)
        )
    return full


def _dispatch_body(full, has_segments, body):
    """Full/edge split shared by the three kernels: segmented kernels
    always take the masked path (segment walls can cut any block);
    otherwise interior blocks run the mask-free fast path."""
    if has_segments:
        body(masked=True)
    else:

        @pl.when(full)
        def _full():
            body(masked=False)

        @pl.when(jnp.logical_not(full))
        def _edge():
            body(masked=True)


def _block_mask(qb, ki, qseg_ref, kseg_ref, *, causal, q_offset, sk,
                block_q, block_k):
    """[block_q, block_k] live-pair mask for an edge block."""
    q_pos = qb * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    mask = k_pos < sk  # padded K columns never contribute
    if causal:
        mask = jnp.logical_and(mask, q_pos + q_offset >= k_pos)
    if qseg_ref is not None:
        mask = jnp.logical_and(mask, qseg_ref[0] == kseg_ref[0])
    return mask


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    qi_ref,  # [L] scalar-prefetch: Q-block of pair i
    ki_ref,  # [L] K-block of pair i
    g_ref,  # [L] unused here (order="row")
    first_ref,  # [L] 1 on the first pair of each Q block
    last_ref,  # [L] 1 on the last pair of each Q block
    q_ref,  # [1, 1, block_q, hd]   (prescaled by scale·log2e in HBM)
    k_ref,  # [1, 1, block_k, hd]
    v_ref,  # [1, 1, block_k, hd+1] when aug (ones column), else hd
    qseg_ref,  # [1, block_q] or None
    kseg_ref,  # [1, block_k] or None
    o_ref,  # [1, 1, block_q, hd]
    lse_ref,  # [1, 1, block_q, 1]
    acc_scr,  # [block_q, hd+1] f32 when aug (last column = l), else hd
    m_scr,  # [block_q, 1] f32
    l_scr,  # [block_q, 1] f32 — used only when not aug
    *,
    causal: bool,
    q_offset: int,
    sk: int,
    block_q: int,
    block_k: int,
    hd: int,
    aug: bool,
):
    i = pl.program_id(2)
    qi = qi_ref[i]
    ki = ki_ref[i]

    @pl.when(first_ref[i] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if not aug:
            l_scr[...] = jnp.zeros_like(l_scr)

    geom = dict(
        causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k,
    )
    full = _block_full(qi, ki, **geom)

    def body(masked: bool):
        # Dots take the native (bf16) operands — the MXU runs bf16
        # inputs at full rate — and accumulate in f32 via
        # preferred_element_type. Softmax statistics stay f32.
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q,
            k,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        mask = None
        if masked:
            mask = _block_mask(qi, ki, qseg_ref, kseg_ref, **geom)
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp2(m_prev - m_new)
        p = jnp.exp2(s - m_new)
        if masked:
            # Re-mask after the exp: on a row with no live column yet,
            # m_new == _NEG_INF and exp(s - m_new) == 1 for masked
            # entries, which would poison l/acc with phantom mass.
            p = jnp.where(mask, p, 0.0)
        if not aug:
            l_scr[...] = l_scr[...] * alpha + jnp.sum(
                p, axis=1, keepdims=True
            )
        # when aug, v's appended ones column makes the pv dot carry the
        # softmax denominator through the same rescale recurrence as
        # the numerator (l_new = α·l + Σp rides in acc[:, hd]) — the
        # VPU row-sum pass moves onto MXU lanes that were pad at hd=64
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype),
            v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    _dispatch_body(full, qseg_ref is not None, body)

    @pl.when(last_ref[i] == 1)
    def _finalize():
        acc = acc_scr[...]
        l = acc[:, hd:] if aug else l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows
        o_ref[0, 0] = (acc[:, :hd] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log2(l_safe)


def _fwd(
    q,  # [B, Hq, Sq, hd]  (padded, head-major)
    k,  # [B, Hkv, Sk, hd]
    v,
    qseg,  # [B, Sq] int32 or None
    kseg,  # [B, Sk] int32 or None
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    sk: int,
    block_q: int,
    block_k: int,
    interpret: bool,
):
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    num_q, num_k = Sq // block_q, Sk // block_k
    # operand augmentation rides MXU lanes that are pad at hd=64 — but
    # at 128-aligned head dims it would push every block to the next
    # 128 multiple (hd=128 → 2× dot cost), so gate it
    aug = hd % 128 != 0

    tabs = _pair_tables(
        num_q=num_q, num_k=num_k, causal=causal, q_offset=q_offset,
        sk=sk, block_q=block_q, block_k=block_k, order="row",
    )
    L = tabs[0].shape[0]
    # base-2 softmax fold rides the q prescale, done once in HBM (the
    # in-kernel variant redid the multiply on every (qi, ki) revisit);
    # python-float × bf16 rounds identically either way
    q = q * (scale * _LOG2E)
    if aug:
        # ones column: the pv dot computes numerator AND denominator
        v = jnp.concatenate([v, jnp.ones_like(v[..., :1])], axis=-1)
    hd_v = v.shape[-1]

    qspec = pl.BlockSpec(
        (1, 1, block_q, hd),
        lambda b, h, i, qi, ki, g, fs, ls: (b, h, qi[i], 0),
        memory_space=pltpu.VMEM,
    )
    kspec = pl.BlockSpec(
        (1, 1, block_k, hd),
        lambda b, h, i, qi, ki, g, fs, ls: (b, h // group, ki[i], 0),
        memory_space=pltpu.VMEM,
    )
    vspec = pl.BlockSpec(
        (1, 1, block_k, hd_v),
        lambda b, h, i, qi, ki, g, fs, ls: (b, h // group, ki[i], 0),
        memory_space=pltpu.VMEM,
    )
    in_specs = [qspec, kspec, vspec]
    args = [q, k, v]
    if qseg is not None:
        # qseg rides as a [B, Sq, 1] column, kseg as a [B, 1, Sk] row:
        # both shapes satisfy Mosaic's (8, 128)-or-full tiling rule and
        # broadcast against each other inside the kernel.
        in_specs.append(
            pl.BlockSpec(
                (1, block_q, 1),
                lambda b, h, i, qi, ki, g, fs, ls: (b, qi[i], 0),
                memory_space=pltpu.VMEM,
            )
        )
        in_specs.append(
            pl.BlockSpec(
                (1, 1, block_k),
                lambda b, h, i, qi, ki, g, fs, ls: (b, 0, ki[i]),
                memory_space=pltpu.VMEM,
            )
        )
        args += [qseg, kseg]

    kernel = functools.partial(
        _fwd_kernel,
        causal=causal,
        q_offset=q_offset,
        sk=sk,
        block_q=block_q,
        block_k=block_k,
        hd=hd,
        aug=aug,
    )
    if qseg is None:
        base = kernel

        def kernel(qi_r, ki_r, g_r, fs_r, ls_r,
                   q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l):
            return base(qi_r, ki_r, g_r, fs_r, ls_r,
                        q_ref, k_ref, v_ref, None, None,
                        o_ref, lse_ref, acc, m, l)

    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, Hq, L),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec(
                    (1, 1, block_q, hd),
                    lambda b, h, i, qi, ki, g, fs, ls: (b, h, qi[i], 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, 1, block_q, 1),
                    lambda b, h, i, qi, ki, g, fs, ls: (b, h, qi[i], 0),
                    memory_space=pltpu.VMEM,
                ),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, hd_v), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
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
    qi_ref,  # [L] scalar-prefetch (see _pair_tables, order="row")
    ki_ref,
    g_ref,  # unused (order="row")
    first_ref,
    last_ref,
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
    causal: bool,
    q_offset: int,
    sk: int,
    block_q: int,
    block_k: int,
    hd: int,
):
    i = pl.program_id(2)
    qi = qi_ref[i]
    ki = ki_ref[i]

    @pl.when(first_ref[i] == 1)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    geom = dict(
        causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k,
    )
    full = _block_full(qi, ki, **geom)

    def body(masked: bool):
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
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if lse_ref is not None:
            s = s - lse_ref[0, 0]
        p = jnp.exp2(s)
        if masked:
            p = jnp.where(
                _block_mask(qi, ki, qseg_ref, kseg_ref, **geom), p, 0.0
            )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if delta_ref is not None:
            dp = dp - delta_ref[0, 0]
        ds = p * dp
        # aug: contracting against k_aug writes junk into dq_scr[:, hd:],
        # sliced off at the finalize
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _dispatch_body(full, qseg_ref is not None, body)

    @pl.when(last_ref[i] == 1)
    def _finalize():
        dq_ref[0, 0] = (dq_scr[:, :hd] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    qi_ref,  # [L] scalar-prefetch (order="col": sorted by K block)
    ki_ref,
    g_ref,  # GQA group member of pair i
    first_ref,
    last_ref,
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
    scale: float,
    causal: bool,
    q_offset: int,
    sk: int,
    block_q: int,
    block_k: int,
    hd: int,
):
    i = pl.program_id(2)
    qj = qi_ref[i]
    ki = ki_ref[i]

    @pl.when(first_ref[i] == 1)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    geom = dict(
        causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k,
    )
    # full is symmetric in (Q block, K block): same predicate as the
    # forward, evaluated at this pair's qj.
    full = _block_full(qj, ki, **geom)

    def body(masked: bool):
        # Same operand folds as _dq_kernel (see the comment there).
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if lse_ref is not None:
            s = s - lse_ref[0, 0]
        p = jnp.exp2(s)
        if masked:
            p = jnp.where(
                _block_mask(qj, ki, qseg_ref, kseg_ref, **geom), p, 0.0
            )
        # aug: do's δ columns write junk into dv_scr[:, hd:], sliced at
        # the finalize; likewise q's lse columns for dk_scr
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if delta_ref is not None:
            dp = dp - delta_ref[0, 0]
        ds = p * dp
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _dispatch_body(full, qseg_ref is not None, body)

    @pl.when(last_ref[i] == 1)
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
    interpret: bool,
):
    B, Hq, Sq, hd = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    num_q, num_k = Sq // block_q, Sk // block_k
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

    common = dict(
        scale=scale, causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k, hd=hd,
    )

    def row_spec(idx):
        return pl.BlockSpec(
            (1, 1, block_q, 1), idx, memory_space=pltpu.VMEM,
        )

    # --- dQ: grid (B, Hq, live pairs), accumulate over K blocks ------
    dq_tabs = _pair_tables(
        num_q=num_q, num_k=num_k, causal=causal, q_offset=q_offset,
        sk=sk, block_q=block_q, block_k=block_k, order="row",
    )
    qblk = pl.BlockSpec(
        (1, 1, block_q, hd2),
        lambda b, h, i, qi, ki, g, fs, ls: (b, h, qi[i], 0),
        memory_space=pltpu.VMEM,
    )
    kvblk = pl.BlockSpec(
        (1, 1, block_k, hd2),
        lambda b, h, i, qi, ki, g, fs, ls: (b, h // group, ki[i], 0),
        memory_space=pltpu.VMEM,
    )
    dq_args = [q, k, v, do]
    dq_specs = [qblk, kvblk, kvblk, qblk]
    if not aug:
        dq_args += [lse, delta]
        dq_specs += [
            row_spec(lambda b, h, i, qi, ki, g, fs, ls: (b, h, qi[i], 0)),
            row_spec(lambda b, h, i, qi, ki, g, fs, ls: (b, h, qi[i], 0)),
        ]
    if qseg is not None:
        dq_args += [qseg, kseg]
        dq_specs += [
            pl.BlockSpec(
                (1, block_q, 1),
                lambda b, h, i, qi, ki, g, fs, ls: (b, qi[i], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k),
                lambda b, h, i, qi, ki, g, fs, ls: (b, 0, ki[i]),
                memory_space=pltpu.VMEM,
            ),
        ]

    def dq_kernel(*refs):
        tabs, rest = refs[:5], list(refs[5:])
        q_r, k_r, v_r, do_r = rest[:4]
        rest = rest[4:]
        lse_r = delta_r = qs_r = ks_r = None
        if not aug:
            lse_r, delta_r = rest[:2]
            rest = rest[2:]
        if qseg is not None:
            qs_r, ks_r = rest[:2]
            rest = rest[2:]
        dq_r, scr = rest
        _dq_kernel(
            *tabs, q_r, k_r, v_r, do_r, lse_r, delta_r, qs_r, ks_r,
            dq_r, scr, **common,
        )

    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, Hq, dq_tabs[0].shape[0]),
            in_specs=dq_specs,
            out_specs=pl.BlockSpec(
                (1, 1, block_q, hd),
                lambda b, h, i, qi, ki, g, fs, ls: (b, h, qi[i], 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.VMEM((block_q, hd2), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dq_tabs, *dq_args)

    # --- dK/dV: grid (B, Hkv, live (ki, g, qj) triples). The GQA
    # group is folded into the pair walk, so dK/dV accumulate per KV
    # head in VMEM scratch and hit HBM exactly once, in k.dtype — no
    # per-Q-head f32 transients.
    dkv_tabs = _pair_tables(
        num_q=num_q, num_k=num_k, causal=causal, q_offset=q_offset,
        sk=sk, block_q=block_q, block_k=block_k, order="col",
        group=group,
    )
    qhblk = pl.BlockSpec(
        (1, 1, block_q, hd2),
        lambda b, h, i, qi, ki, g, fs, ls: (
            b, h * group + g[i], qi[i], 0
        ),
        memory_space=pltpu.VMEM,
    )
    kvhblk = pl.BlockSpec(
        (1, 1, block_k, hd2),
        lambda b, h, i, qi, ki, g, fs, ls: (b, h, ki[i], 0),
        memory_space=pltpu.VMEM,
    )
    dkv_args = [q, k, v, do]
    dkv_specs = [qhblk, kvhblk, kvhblk, qhblk]
    if not aug:
        dkv_args += [lse, delta]
        dkv_specs += [
            row_spec(lambda b, h, i, qi, ki, g, fs, ls: (
                b, h * group + g[i], qi[i], 0
            )),
            row_spec(lambda b, h, i, qi, ki, g, fs, ls: (
                b, h * group + g[i], qi[i], 0
            )),
        ]
    if qseg is not None:
        dkv_args += [qseg, kseg]
        dkv_specs += [
            pl.BlockSpec(
                (1, block_q, 1),
                lambda b, h, i, qi, ki, g, fs, ls: (b, qi[i], 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k),
                lambda b, h, i, qi, ki, g, fs, ls: (b, 0, ki[i]),
                memory_space=pltpu.VMEM,
            ),
        ]

    def dkv_kernel(*refs):
        tabs, rest = refs[:5], list(refs[5:])
        q_r, k_r, v_r, do_r = rest[:4]
        rest = rest[4:]
        lse_r = delta_r = qs_r = ks_r = None
        if not aug:
            lse_r, delta_r = rest[:2]
            rest = rest[2:]
        if qseg is not None:
            qs_r, ks_r = rest[:2]
            rest = rest[2:]
        dk_r, dv_r, kscr, vscr = rest
        _dkv_kernel(
            *tabs, q_r, k_r, v_r, do_r, lse_r, delta_r, qs_r, ks_r,
            dk_r, dv_r, kscr, vscr, **common,
        )

    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, Hkv, dkv_tabs[0].shape[0]),
            in_specs=dkv_specs,
            out_specs=(
                pl.BlockSpec(
                    (1, 1, block_k, hd),
                    lambda b, h, i, qi, ki, g, fs, ls: (b, h, ki[i], 0),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, 1, block_k, hd),
                    lambda b, h, i, qi, ki, g, fs, ls: (b, h, ki[i], 0),
                    memory_space=pltpu.VMEM,
                ),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_k, hd2), jnp.float32),
                pltpu.VMEM((block_k, hd2), jnp.float32),
            ],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, Hkv, Sk, hd), k.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Sk, hd), v.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*dkv_tabs, *dkv_args)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12)
)
def _flash(q, k, v, segment_ids, causal, q_offset, sq, sk,
           block_q, block_k, bwd_block_q, bwd_block_k, interpret):
    out, _ = _flash_fwd(
        q, k, v, segment_ids, causal, q_offset, sq, sk,
        block_q, block_k, bwd_block_q, bwd_block_k, interpret,
    )
    return out


def _prep(q, k, v, segment_ids, sq, sk, block_q, block_k):
    """[B,S,H,d] → padded head-major [B,H,S,d] plus padded segment ids."""
    B = q.shape[0]
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
        seg = segment_ids.astype(jnp.int32)
        # Padded rows/cols get sentinel ids that never match real ones.
        # Shapes: qseg [B, Sq, 1] (column), kseg [B, 1, Sk] (row) — see
        # the spec comment in _fwd.
        qseg = jnp.pad(seg, ((0, 0), (0, sq_p - sq)),
                       constant_values=-1)[:, :, None]
        kseg = jnp.pad(seg[:, :sk], ((0, 0), (0, sk_p - sk)),
                       constant_values=-2)[:, None, :]
    return qt, kt, vt, qseg, kseg


def _flash_fwd(q, k, v, segment_ids, causal, q_offset, sq, sk,
               block_q, block_k, bwd_block_q, bwd_block_k, interpret):
    hd = q.shape[-1]
    scale = hd**-0.5
    qt, kt, vt, qseg, kseg = _prep(
        q, k, v, segment_ids, sq, sk, block_q, block_k
    )
    out_p, lse = _fwd(
        qt, kt, vt, qseg, kseg,
        scale=scale, causal=causal, q_offset=q_offset, sk=sk,
        block_q=block_q, block_k=block_k, interpret=interpret,
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
               bwd_block_q, bwd_block_k, interpret, res, g):
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
        block_q=bq, block_k=bk, interpret=interpret,
    )
    dq = jnp.moveaxis(dq[:, :, :sq], 2, 1)
    dk = jnp.moveaxis(dk[:, :, :sk], 2, 1)
    dv = jnp.moveaxis(dv[:, :, :sk], 2, 1)
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,  # [B, Sq, Hq, hd]
    k: jnp.ndarray,  # [B, Sk, Hkv, hd]
    v: jnp.ndarray,  # [B, Sk, Hkv, hd]
    *,
    causal: bool = True,
    q_offset: int = 0,
    segment_ids: Optional[jnp.ndarray] = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Flash attention; same contract as ``dense_attention``.

    ``q_offset`` must be a static python int on this path (the pallas
    grid's causal-skip predicate is specialised on it); the decode path
    with a traced offset should use ``dense_attention``.

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
    block_q = min(block_q, _ceil_to(sq, 128))
    block_k = min(block_k, _ceil_to(sk, 128))
    if bwd_block_q is not None:
        bwd_block_q = min(bwd_block_q, _ceil_to(sq, 128))
    if bwd_block_k is not None:
        bwd_block_k = min(bwd_block_k, _ceil_to(sk, 128))
    return _flash(
        q, k, v, segment_ids, causal, q_offset, sq, sk,
        block_q, block_k, bwd_block_q, bwd_block_k, interpret,
    )
