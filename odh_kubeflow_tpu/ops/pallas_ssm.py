"""Pallas TPU kernels for a Mamba-2 (state-space duality) layer's
recurrence, and its plain forms.

Per head, with ``S`` a ``[P, N]`` state (``P`` the head's width, ``N``
the state size), ``a_t = dt_t * A`` (``A < 0``), one group of ``B``/``C``
shared by every head:

    S_t = exp(a_t) S_{t-1} + dt_t * x_t (outer) B_t        y_t = S_t C_t

(``D * x`` and the gate are the model's, ``models/granite_hybrid.py``).
A position with ``dt = 0`` is the identity on the state: that is how a
caller masks padding and idle rows.

**How the state lies.** ``[..., H // g, N, g * P]`` float32: ``g`` heads
side by side so that ``(head, p)`` runs along the 128 lanes (``g = 2``
at ``P = 64``) and ``n`` along the sublanes. Then everything that is per
``(head, p)`` is a ROW vector and everything per ``n`` (``B``, ``C``)
comes once a row of the batch: the decode step needs no transpose, the
prefill's read ``C S`` and its update ``B^T (w x)`` are plain matmuls.
``state_shape`` / ``to_state`` / ``from_state`` are the only places that
know it.

- ``ssm_decode_update`` (``name="ssm_decode_update"``): one token a
  row. ONE pass over the state: read, decay, add, contract with ``C``,
  write. The state is the STACKED cache ``[L, B, H // g, N, g * P]``,
  addressed by a prefetched layer index and aliased in and out, so no
  layer is copied out of the layer scan's carry (PERF.md, PR 25).
- ``ssd_chunk_scan`` (``name="ssd_chunk_scan"``): a whole part. Chunks
  of ``chunk`` positions; inside one, the quadratic dual form (a masked
  ``[Q, Q]`` product on the MXU), between chunks the state, carried in
  VMEM: an initial state in, the final state out.

The plain forms (``ssm_scan_plain``: the recurrence token by token;
``ssm_step_plain``) are what runs off the TPU and what the kernels are
held to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# (group of heads) blocks a grid step covers: the decode step moves
# 16 x [128, 128] float32 = 1 MB in and 1 MB out a step (2.5 us of HBM
# time against ~0.35 us of step overhead); the scan holds 8 groups'
# state (0.5 MB) beside a [Q, 1024] block of x
DECODE_GROUPS = 16
SCAN_GROUPS = 8
_VMEM_LIMIT = 64 << 20
_NEG = -1e30


def heads_per_group(heads: int, head_dim: int) -> int:
    g = max(1, LANES // head_dim)
    while heads % g:
        g -= 1
    return g


def state_shape(heads: int, head_dim: int, d_state: int) -> tuple:
    """The trailing dims of a state leaf: ``(H // g, N, g * P)``."""
    g = heads_per_group(heads, head_dim)
    return (heads // g, d_state, g * head_dim)


def to_state(s_hpn: jnp.ndarray) -> jnp.ndarray:
    """``[..., H, P, N]`` (the equations' layout) to the stored one."""
    *lead, H, P, N = s_hpn.shape
    g = heads_per_group(H, P)
    s = s_hpn.reshape(*lead, H // g, g * P, N)
    return jnp.swapaxes(s, -1, -2)


def from_state(s: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """The stored layout back to ``[..., H, P, N]``."""
    *lead, G, N, gP = s.shape
    g = gP // head_dim
    return jnp.swapaxes(s, -1, -2).reshape(*lead, G * g, head_dim, N)


# ---------------------------------------------------------------------------
# plain forms


def ssm_scan_plain(x, dt, A, Bm, Cm, init):
    """The recurrence token by token, float32. ``x`` [B, S, H, P], ``dt``
    [B, S, H] (0 = masked), ``A`` [H], ``Bm``/``Cm`` [B, S, N], ``init``
    the stored state [B, H // g, N, g * P]. Returns ``(y [B, S, H, P]
    float32, final state)``."""
    P = x.shape[-1]
    f32 = jnp.float32

    def step(S, xs):
        x_t, dt_t, b_t, c_t = xs  # [B,H,P], [B,H], [B,N], [B,N]
        decay = jnp.exp(dt_t * A)[..., None, None]
        S = decay * S + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return S, jnp.einsum("bhpn,bn->bhp", S, c_t)

    S, y = jax.lax.scan(
        step, from_state(init.astype(f32), P),
        tuple(
            jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, Bm, Cm)
        ),
    )
    return jnp.moveaxis(y, 0, 1), to_state(S)


def _step_rows(x, dt, A, Bm, Cm):
    """What one decode step's update takes beside the state, laid along
    the state's lanes: ``(decay, dt * x)`` [B, H // g, g * P] and
    ``(B, C)`` [B, N, g * P] (each ``n`` repeated along the lanes)."""
    B_, H, P = x.shape
    g = heads_per_group(H, P)
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.repeat(jnp.exp(dt * A.astype(f32)), P, axis=-1)
    xdt = (dt[..., None] * x.astype(f32)).reshape(B_, H // g, g * P)
    wide = lambda v: jnp.broadcast_to(  # noqa: E731
        v.astype(f32)[..., None], v.shape + (g * P,)
    )
    return decay.reshape(B_, H // g, g * P), xdt, wide(Bm), wide(Cm)


def ssm_step_plain(x, dt, A, Bm, Cm, state, layer):
    """One token a row on the stacked state, in plain ``jax.numpy``:
    ``x`` [B, H, P], ``dt`` [B, H], ``Bm``/``Cm`` [B, N], ``state`` [L,
    B, H // g, N, g * P]. Returns ``(y [B, H, P] float32, state)``."""
    decay, xdt, b, c = _step_rows(x, dt, A, Bm, Cm)
    with jax.named_scope("ssm_decode_update"):
        S = jax.lax.dynamic_index_in_dim(state, layer, 0, False)
        S = S * decay[:, :, None, :] + b[:, None] * xdt[:, :, None, :]
        y = jnp.sum(S * c[:, None], axis=2)
        state = jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)
    return y.reshape(x.shape), state


# ---------------------------------------------------------------------------
# decode: one token a row, the stacked state in place


def _decode_kernel(layer_ref, s_ref, dec_ref, xdt_ref, b_ref, c_ref,
                   o_ref, y_ref):
    del layer_ref
    b, c = b_ref[...], c_ref[...]
    for i in range(s_ref.shape[0]):
        S = s_ref[i] * dec_ref[i:i + 1, :] + b * xdt_ref[i:i + 1, :]
        o_ref[i] = S
        y_ref[i:i + 1, :] = jnp.sum(S * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_update(x, dt, A, Bm, Cm, state, layer, *, interpret=False):
    """``ssm_step_plain`` as one pass over the layer's state where it
    lies in the stack (aliased in and out)."""
    B_, H, P = x.shape
    L, _, G, N, gP = state.shape
    gb = min(DECODE_GROUPS, G)
    assert G % gb == 0, (G, gb)
    decay, xdt, b, c = _step_rows(x, dt, A, Bm, Cm)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    rows = pl.BlockSpec((None, gb, gP), lambda i, j, layer: (i, j, 0))
    per_row = pl.BlockSpec((None, N, gP), lambda i, j, layer: (i, 0, 0))
    block = pl.BlockSpec(
        (None, None, gb, N, gP), lambda i, j, layer: (layer[0], i, j, 0, 0)
    )
    state, y = pl.pallas_call(
        _decode_kernel,
        name="ssm_decode_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B_, G // gb),
            in_specs=[block, rows, rows, per_row, per_row],
            out_specs=[block, rows],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B_, G, gP), jnp.float32),
        ],
        # operand 0 is the prefetched layer index: the state is 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(layer, state, decay, xdt, b, c)
    return y.reshape(B_, H, P), state


# ---------------------------------------------------------------------------
# prefill: chunks of the dual form, the state carried between them


def _scan_kernel(x_ref, cum_ref, w_ref, cumT_ref, dtT_ref, bT_ref, c_ref,
                 init_ref, y_ref, fin_ref, st, *, g, head_dim):
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _load():
        st[...] = init_ref[...]

    Q, gP = x_ref.shape[0], g * head_dim
    dt_ = x_ref.dtype
    f32 = jnp.float32
    # a bf16 operand has one pass to offer (``pallas_moe_local``)
    dot = functools.partial(
        jnp.dot, preferred_element_type=f32,
        precision=jax.lax.Precision.DEFAULT if dt_.itemsize < 4 else None,
    )
    Cm, bT = c_ref[...], bT_ref[...]
    # G[i, j] = C_i . B_j: one group, so every head of the block shares it
    G = dot(Cm, bT)
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    )
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, gP), 1) // head_dim
    for i in range(st.shape[0]):
        xg = x_ref[:, i * gP:(i + 1) * gP]
        y = jnp.zeros((Q, gP), f32)
        e = jnp.zeros((Q, gP), f32)
        w = jnp.zeros((Q, gP), f32)
        decay = jnp.zeros((1, gP), f32)
        for h in range(g):
            hl = i * g + h
            ci, cj = cum_ref[:, hl:hl + 1], cumT_ref[hl:hl + 1, :]
            # position j reaches i through exp(cum_i - cum_j) <= 1
            M = G * jnp.exp(jnp.where(causal, ci - cj, _NEG)) * dtT_ref[hl:hl + 1, :]
            sel = lane_head == h
            y = jnp.where(sel, dot(M.astype(dt_), xg), y)
            e = jnp.where(sel, jnp.exp(ci), e)
            w = jnp.where(sel, w_ref[:, hl:hl + 1], w)
            decay = jnp.where(sel, jnp.exp(cumT_ref[hl:hl + 1, Q - 1:Q]), decay)
        S = st[i]
        y_ref[:, i * gP:(i + 1) * gP] = (
            y + e * dot(Cm, S.astype(dt_))
        ).astype(y_ref.dtype)
        st[i] = decay * S + dot(bT, (xg.astype(f32) * w).astype(dt_))

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _store():
        fin_ref[...] = st[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x, dt, A, Bm, Cm, init, *, chunk=256, interpret=False):
    """``ssm_scan_plain`` in chunks. ``x`` [B, S, H, P] (its dtype is the
    matmuls' operand dtype; accumulation, decays and the state are
    float32), ``dt`` [B, S, H] float32 (0 = masked), ``init`` [B, H // g,
    N, g * P] float32. ``S`` is padded to whole chunks with ``dt = 0``.
    Returns ``(y [B, S, H, P] in x's dtype, final state float32)``."""
    B_, S, H, P = x.shape
    N = Bm.shape[-1]
    g = heads_per_group(H, P)
    G, gP = H // g, g * P
    f32 = jnp.float32
    Q = min(chunk, -(-S // 8) * 8)
    Sp = -(-S // Q) * Q
    nc = Sp // Q
    pad = lambda a: jnp.pad(a, ((0, 0), (0, Sp - S)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    x2 = pad(x.reshape(B_, S, H * P))
    dt = pad(dt.astype(f32))
    Bm, Cm = pad(Bm.astype(x.dtype)), pad(Cm.astype(x.dtype))
    # the decays' exponents, summed inside each chunk, and each
    # position's weight in its chunk's closing state
    cum = jnp.cumsum((dt * A.astype(f32)).reshape(B_, nc, Q, H), axis=2)
    w = (jnp.exp(cum[:, :, -1:] - cum) * dt.reshape(B_, nc, Q, H))
    gb = min(SCAN_GROUPS, G)
    assert G % gb == 0, (G, gb)
    hb = gb * g  # heads a block covers
    cols = lambda a: jnp.moveaxis(  # noqa: E731 — [B, H // hb, Sp, hb]
        a.reshape(B_, Sp, H // hb, hb), 2, 1
    )
    rows = lambda a: jnp.swapaxes(a.reshape(B_, Sp, H), 1, 2)  # noqa: E731

    col = pl.BlockSpec((None, None, Q, hb), lambda b, j, c: (b, j, c, 0))
    row = pl.BlockSpec((None, hb, Q), lambda b, j, c: (b, j, c))
    xs = pl.BlockSpec((None, Q, gb * gP), lambda b, j, c: (b, c, j))
    bc = pl.BlockSpec((None, Q, N), lambda b, j, c: (b, c, 0))
    bcT = pl.BlockSpec((None, N, Q), lambda b, j, c: (b, 0, c))
    whole = pl.BlockSpec((None, gb, N, gP), lambda b, j, c: (b, j, 0, 0))
    y, fin = pl.pallas_call(
        functools.partial(_scan_kernel, g=g, head_dim=P),
        name="ssd_chunk_scan",
        grid=(B_, G // gb, nc),
        in_specs=[xs, col, col, row, row, bcT, bc, whole],
        out_specs=[xs, whole],
        out_shape=[
            jax.ShapeDtypeStruct((B_, Sp, H * P), x.dtype),
            jax.ShapeDtypeStruct((B_, G, N, gP), f32),
        ],
        scratch_shapes=[pltpu.VMEM((gb, N, gP), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        x2, cols(cum), cols(w), rows(cum), rows(dt),
        jnp.swapaxes(Bm, 1, 2), Cm, init.astype(f32),
    )
    return y[:, :S].reshape(B_, S, H, P), fin
