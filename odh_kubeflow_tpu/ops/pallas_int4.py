"""Pallas int4→bf16 weight dequantization.

XLA lowers the int4 unpack chain (bit-ops + concat/reshape + group
scaling) into passes that cost ~5× the HBM roofline on the 8B/16k
config (+0.4s/step). This kernel is a pure streaming transform: read a
packed uint8 block, unpack the requested nibble half, apply the
group-wise scales in VMEM, write the bf16 block — one pass at memory
speed. The grid's leading dimension selects the nibble half, matching
``models/quant.py``'s split-halves packing (low nibbles = rows
[0, K/2), high = [K/2, K)), so each output block is contiguous.

Used by ``quant.dequantize_tensor4`` on TPU for shapes the blocking
divides; everything else (CPU tests, tiny shapes) takes the jnp path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BK = 1024  # output rows per block (scale block = 8 sublanes)
DEFAULT_BN = 512


def _dequant_kernel(packed_ref, scale_ref, out_ref, *, group, bk):
    h = pl.program_id(0)
    # i32 lanes: Mosaic has no u8 vector shift (arith.shrui fails to
    # legalize); the widen/narrow is VPU-local
    p = packed_ref[...].astype(jnp.int32)
    nib = jnp.where(h == 0, p & 0xF, (p >> 4) & 0xF)
    v = (nib - 8).astype(jnp.float32)
    rows = bk // group
    vg = v.reshape(rows, group, v.shape[-1])
    vg = vg * scale_ref[...][:, None, :]
    out_ref[...] = vg.reshape(bk, v.shape[-1]).astype(out_ref.dtype)


def int4_dequant(packed, scale, dtype=jnp.bfloat16, *, group=128,
                 bk=DEFAULT_BK, bn=DEFAULT_BN):
    """``packed`` [K//2, N] uint8 (split-halves), ``scale`` [K//group,
    N] f32 → [K, N] ``dtype``. 2-D only — callers vmap leading dims."""
    K2, N = packed.shape
    K = 2 * K2
    bk = min(bk, K2)
    bn = min(bn, N)
    if (
        K2 % bk
        or N % bn
        or bk % group
        or scale.shape != (K // group, N)
    ):
        raise ValueError(f"int4_dequant blocking mismatch: {packed.shape}")
    srows = bk // group
    return pl.pallas_call(
        functools.partial(_dequant_kernel, group=group, bk=bk),
        name="int4_dequant",
        grid=(2, K2 // bk, N // bn),
        in_specs=[
            pl.BlockSpec((bk, bn), lambda h, i, j: (i, j)),
            pl.BlockSpec(
                (srows, bn),
                lambda h, i, j: (h * (K2 // bk) + i, j),
            ),
        ],
        out_specs=pl.BlockSpec(
            (bk, bn), lambda h, i, j: (h * (K2 // bk) + i, j)
        ),
        out_shape=jax.ShapeDtypeStruct((K, N), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
    )(packed, scale)


# ---------------------------------------------------------------------------
# fused-consumer matmul: weights STAY packed int4 in HBM
# ---------------------------------------------------------------------------
#
# x[M, K] · W[K, N] where W lives as {q4 [K/2, N] uint8 (split-halves),
# scale4 [K/group, N] f32}. A pre-round finding (round 4, not re-measured):
# int4-with-in-graph-dequant frees 4GB of HBM but materialising the
# bf16 weight per consumer eats the win. Here the unpack + group scale
# happen on the accumulator in VMEM — weights cross HBM packed (0.5
# byte/weight, 2× less traffic than int8, 4× less than bf16) and no
# dequantized copy ever exists. The per-K-group scales are exactly why
# XLA cannot fuse this itself: they multiply neither operand of a
# single dot (folding them needs a [M, K/group, N] intermediate), but
# they CAN rescale each group's partial product on the f32 accumulator
# — one VPU multiply per (group, tile) step.
#
# Frozen-base training only (QLoRA): differentiable in x (the dlhs
# kernel reads the same packed bank "backwards"), never in the weights.

MM_BM = 512
MM_BN = 512
MM_BK = 1024  # K-chunk per grid step: 8 scale groups (one aligned
# sublane block), one MXU-wide dot



def _unpack_scaled(p_ref, s_ref, lo_half, q, dtype):
    """Shared nibble-select + group-scale dequant for the matmul
    kernels: unpack the requested half's nibbles, apply the q group
    scales row-blockwise, return the bf16 weight block — ONE copy, so
    the fwd and dlhs kernels can never desynchronize their rounding."""
    p = p_ref[...].astype(jnp.int32)
    nib = jnp.where(lo_half, p & 0xF, (p >> 4) & 0xF)
    kb, bn = nib.shape
    sc = s_ref[...]
    return (
        (nib - 8).astype(jnp.float32).reshape(q, kb // q, bn)
        * sc[:, None, :]
    ).reshape(kb, bn).astype(dtype)


def _int4_mm_kernel(x_ref, p_ref, s_ref, out_ref, acc_ref, *, nc, q):
    c = pl.program_id(2)  # k-chunk, innermost
    c2 = nc // 2
    # scale the unpacked weights IN VMEM (bf16, same rounding as the
    # dequantize path) — one wide dot per chunk keeps the MXU fed; the
    # first cut dotted per 128-group and ran at 49 TF/s vs 167 for the
    # dequant path
    w = _unpack_scaled(p_ref, s_ref, c < c2, q, x_ref.dtype)
    d = jax.lax.dot_general(
        x_ref[...], w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = d

    @pl.when(c > 0)
    def _accum():
        acc_ref[...] = acc_ref[...] + d

    @pl.when(c == nc - 1)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _int4_mm_impl(x, q4, scale4, *, group, interpret):
    M, K = x.shape
    K2, N = q4.shape
    ng = K // group
    bm = min(MM_BM, M)
    bn = min(MM_BN, N)
    kb = MM_BK
    q = kb // group  # 8 groups: the scale block is one aligned
    # sublane tile — Mosaic cannot prove smaller dynamic slices aligned
    if (
        K != 2 * K2
        or K % (2 * kb)
        or kb % group
        or group > kb
        or scale4.shape != (ng, N)
        or M % bm
        or N % bn
    ):
        raise NotImplementedError(
            f"int4_matmul blocking mismatch: x{x.shape} q4{q4.shape}"
        )
    nc = K // kb
    c2 = nc // 2

    # chunk c < c2 reads packed rows [c*kb, ...) as LOW nibbles;
    # c >= c2 reads rows [(c-c2)*kb, ...) as HIGH nibbles — the
    # split-halves layout of quantize_tensor4
    def p_idx(ni, mi, c):
        return (jnp.where(c < c2, c, c - c2), ni)

    return pl.pallas_call(
        functools.partial(_int4_mm_kernel, nc=nc, q=q),
        name="int4_matmul",
        grid=(N // bn, M // bm, nc),
        in_specs=[
            pl.BlockSpec((bm, kb), lambda ni, mi, c: (mi, c)),
            pl.BlockSpec((kb, bn), p_idx),
            pl.BlockSpec((q, bn), lambda ni, mi, c: (c, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda ni, mi, c: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x, q4, scale4)


def _int4_dlhs_kernel(d_ref, p_ref, s_ref, out_ref, acc_ref, *, nn, nc, q):
    ni = pl.program_id(2)  # n-split, innermost
    c = pl.program_id(0)
    c2 = nc // 2
    w = _unpack_scaled(p_ref, s_ref, c < c2, q, d_ref.dtype)
    # dx_c = dout · w_cᵀ (w already carries the group scales)
    acc = jax.lax.dot_general(
        d_ref[...], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ni == 0)
    def _init():
        acc_ref[...] = acc

    @pl.when(ni > 0)
    def _accum():
        acc_ref[...] = acc_ref[...] + acc

    @pl.when(ni == nn - 1)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _int4_dlhs_impl(dout, q4, scale4, *, group, interpret):
    M, N = dout.shape
    K2, N2 = q4.shape
    K = 2 * K2
    ng = K // group
    bm = min(MM_BM, M)
    bn = min(MM_BN, N)
    kb = MM_BK
    q = kb // group
    if (
        N != N2
        or K % (2 * kb)
        or kb % group
        or group > kb
        or M % bm
        or N % bn
        or scale4.shape != (ng, N)
    ):
        raise NotImplementedError(
            f"int4_matmul dlhs blocking mismatch: dout{dout.shape}"
        )
    nc = K // kb
    c2 = nc // 2

    def p_idx(c, mi, ni):
        return (jnp.where(c < c2, c, c - c2), ni)

    return pl.pallas_call(
        functools.partial(
            _int4_dlhs_kernel, nn=N // bn, nc=nc, q=q
        ),
        name="int4_matmul_dlhs",
        grid=(nc, M // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda c, mi, ni: (mi, ni)),
            pl.BlockSpec((kb, bn), p_idx),
            pl.BlockSpec((q, bn), lambda c, mi, ni: (c, ni)),
        ],
        out_specs=pl.BlockSpec(
            (bm, kb), lambda c, mi, ni: (mi, c)
        ),
        out_shape=jax.ShapeDtypeStruct((M, K), dout.dtype),
        scratch_shapes=[pltpu.VMEM((bm, kb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(dout, q4, scale4)
def _interpret_default():
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def int4_matmul(x, q4, scale4, group=128, interpret=None):
    """``x [M, K] @ dequant(q4, scale4) [K, N]`` with the weights
    staying packed: unpack + group-scale happen on the accumulator in
    VMEM. Differentiable in ``x`` only (frozen banks — QLoRA).
    Raises ``NotImplementedError`` on shapes the blocking doesn't
    divide; callers fall back to the dequantize path."""
    if interpret is None:
        interpret = _interpret_default()
    return _int4_mm_impl(x, q4, scale4, group=group, interpret=interpret)


def _int4_matmul_fwd(x, q4, scale4, group, interpret):
    if interpret is None:
        interpret = _interpret_default()
    out = _int4_mm_impl(x, q4, scale4, group=group, interpret=interpret)
    return out, (q4, scale4)


def _int4_matmul_bwd(group, interpret, res, dout):
    q4, scale4 = res
    if interpret is None:
        interpret = _interpret_default()
    dx = _int4_dlhs_impl(
        dout, q4, scale4, group=group, interpret=interpret
    )
    return dx, None, jnp.zeros_like(scale4)


int4_matmul.defvjp(_int4_matmul_fwd, _int4_matmul_bwd)
