"""Pallas TPU kernels for a Gated DeltaNet layer's recurrence (the gated
delta rule), and its plain forms.

Per value head, with ``S`` a ``[d_k, d_v]`` state, ``g_t <= 0`` the log
of the step's decay and ``beta_t`` in (0, 1) the step's write strength
(``rep = H // Hk`` value heads share one key head's ``q`` and ``k``):

    S' = exp(g_t) S_{t-1}        u = S'^T k_t        (the state is READ by the key)
    S_t = S' + k_t (outer) (beta_t (v_t - u))        (and corrected, not added to)
    o_t = S_t^T q_t

(``q`` comes l2-normalised and scaled, ``k`` l2-normalised; the
convolution, the norm and the gate are the model's,
``models/qwen3_next.py``.) A position with ``g = 0`` and ``beta = 0`` is
the identity on the state: that is how a caller masks padding and idle
rows.

**How the state lies.** ``[..., H, d_k, d_v]`` float32: ``d_v`` along
the 128 lanes, ``d_k`` along the sublanes. Then ``v``, ``u``, the
correction and the output are ROW vectors, and ``k`` and ``q`` columns:
the decode step takes those two already transposed (``[d_k, heads]``,
64 bytes a head beside a 64 KB state) and nothing is transposed in
either kernel.

- ``gdn_decode_update`` (``name="gdn_decode_update"``): one token a row.
  ONE pass over the state: read, decay, read by the key, correct, read
  out, write. The state is the STACKED cache ``[L, B, H, d_k, d_v]``,
  addressed by a prefetched layer index and aliased in and out, as
  ``pallas_ssm.ssm_decode_update`` is.
- ``gdn_chunk_scan`` (``name="gdn_chunk_scan"``): a whole part, in
  chunks of ``chunk`` positions, the chunked WY form. With ``c_i`` the
  decays' exponents summed inside the chunk:

      A = strict_tril(diag(beta) (K K^T * exp(c_i - c_j)))
      T = (I + A)^-1                 (unit lower triangular)
      W = T diag(beta) (K * exp(c))  U = T diag(beta) V
      V' = U - W S_0
      O = (Q * exp(c)) S_0 + tril(Q K^T * exp(c_i - c_j)) V'
      S_C = exp(c_C) S_0 + (K * exp(c_C - c))^T V'

  ``A`` is nilpotent, so ``T = (I - A)(I + A^2)(I + A^4)...`` exactly,
  but only in exact arithmetic: keys that repeat (a run of one token)
  make ``A`` ``beta`` times a matrix of ones, whose 32nd power has
  entries of 1e17 that float32 cannot cancel again (NaN within a
  chunk). So the product form is taken only inside diagonal blocks of 8
  positions (powers up to the fourth, entries of at most 20), and two
  neighbouring blocks' inverses are joined as ``[[T1, 0], [-T2 A21 T1,
  T2]]``, every factor of which is as small as the inverse itself: 8 ->
  16 -> 32 -> 64, all of it float32 matmuls on the MXU, as many as the
  squarings were. Between chunks the state, carried in VMEM: an initial
  state in, the final state out.

The plain forms (``gdn_scan_plain``: the recurrence token by token;
``gdn_step_plain``) are what runs off the TPU and what the kernels are
held to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# value heads a decode grid step covers: 16 x [128, 128] float32 = 1 MB
# in and 1 MB out a step (``pallas_ssm.DECODE_GROUPS``)
DECODE_HEADS = 16
DEFAULT_CHUNK = 64
# positions of a diagonal block of the in-chunk solve (``_scan_kernel``)
_SOLVE_BLOCK = 8
_VMEM_LIMIT = 64 << 20
_NEG = -1e30
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# plain forms


def _delta_step(S, q, k, v, g, beta):
    """One token of every (row, value head): ``S`` [..., H, dk, dv],
    ``q``/``k`` [..., H, dk] (already repeated onto the value heads),
    ``v`` [..., H, dv], ``g``/``beta`` [..., H]."""
    S = jnp.exp(g)[..., None, None] * S
    u = jnp.sum(S * k[..., :, None], axis=-2)
    S = S + k[..., :, None] * (beta[..., None] * (v - u))[..., None, :]
    return S, jnp.sum(S * q[..., :, None], axis=-2)


def gdn_scan_plain(q, k, v, g, beta, init):
    """The recurrence token by token, float32. ``q``/``k`` [B, S, Hk,
    dk], ``v`` [B, S, H, dv], ``g``/``beta`` [B, S, H] (both 0 =
    masked), ``init`` [B, H, dk, dv]. Returns ``(o [B, S, H, dv]
    float32, final state)``."""
    rep = v.shape[2] // k.shape[2]
    q, k = (jnp.repeat(a.astype(F32), rep, axis=2) for a in (q, k))

    def step(S, xs):
        return _delta_step(S, *xs)

    S, o = jax.lax.scan(
        step, init.astype(F32),
        tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta)),
    )
    return jnp.moveaxis(o, 0, 1), S


def gdn_step_plain(q, k, v, g, beta, state, layer):
    """One token a row on the stacked state, in plain ``jax.numpy``:
    ``q``/``k`` [B, Hk, dk], ``v`` [B, H, dv], ``g``/``beta`` [B, H],
    ``state`` [L, B, H, dk, dv]. Returns ``(o [B, H, dv] float32,
    state)``."""
    rep = v.shape[1] // k.shape[1]
    q, k = (jnp.repeat(a.astype(F32), rep, axis=1) for a in (q, k))
    with jax.named_scope("gdn_decode_update"):
        S = jax.lax.dynamic_index_in_dim(state, layer, 0, False)
        S, o = _delta_step(
            S, q, k, v.astype(F32), g.astype(F32), beta.astype(F32)
        )
        state = jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)
    return o, state


# ---------------------------------------------------------------------------
# decode: one token a row, the stacked state in place


def _decode_kernel(layer_ref, s_ref, kT_ref, qT_ref, dec_ref, beta_ref,
                   bv_ref, o_ref, y_ref):
    del layer_ref
    for i in range(s_ref.shape[0]):
        k = kT_ref[:, i:i + 1]  # [dk, 1]: a column, broadcast along the lanes
        S = s_ref[i] * dec_ref[i:i + 1, :]
        u = jnp.sum(S * k, axis=0, keepdims=True)
        S = S + k * (bv_ref[i:i + 1, :] - beta_ref[i:i + 1, :] * u)
        o_ref[i] = S
        y_ref[i:i + 1, :] = jnp.sum(S * qT_ref[:, i:i + 1], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode_update(q, k, v, g, beta, state, layer, *, interpret=False):
    """``gdn_step_plain`` as one pass over the layer's state where it
    lies in the stack (aliased in and out)."""
    B_, H, dv = v.shape
    Hk, dk = k.shape[1:]
    L = state.shape[0]
    assert state.shape == (L, B_, H, dk, dv), (state.shape, v.shape, k.shape)
    hb = min(DECODE_HEADS, H)
    assert H % hb == 0, (H, hb)
    rep = H // Hk
    # q and k as COLUMNS of their block of value heads: [B, H // hb, dk, hb]
    cols = lambda a: jnp.swapaxes(  # noqa: E731
        jnp.repeat(a.astype(F32), rep, axis=1).reshape(B_, H // hb, hb, dk), 2, 3
    )
    wide = lambda a: jnp.broadcast_to(  # noqa: E731 — a head's scalar along its row
        a.astype(F32)[..., None], (B_, H, dv)
    )
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    rows = pl.BlockSpec((None, hb, dv), lambda i, j, layer: (i, j, 0))
    col = pl.BlockSpec((None, None, dk, hb), lambda i, j, layer: (i, j, 0, 0))
    block = pl.BlockSpec(
        (None, None, hb, dk, dv), lambda i, j, layer: (layer[0], i, j, 0, 0)
    )
    state, y = pl.pallas_call(
        _decode_kernel,
        name="gdn_decode_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B_, H // hb),
            in_specs=[block, col, col, rows, rows, rows],
            out_specs=[block, rows],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((B_, H, dv), F32),
        ],
        # operand 0 is the prefetched layer index: the state is 1
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(
        layer, state, cols(k), cols(q), wide(jnp.exp(g.astype(F32))),
        wide(beta), beta.astype(F32)[..., None] * v.astype(F32),
    )
    return y, state


# ---------------------------------------------------------------------------
# prefill: chunks of the WY form, the state carried between them


def _scan_kernel(q_ref, k_ref, kT_ref, v_ref, col_ref, row_ref, dec_ref,
                 init_ref, o_ref, fin_ref, st, *, rep, d_v):
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _load():
        st[...] = init_ref[...]

    C = q_ref.shape[0]
    dt_ = q_ref.dtype
    # a bf16 operand has one pass to offer (``pallas_moe_local``); the
    # in-chunk solve is float32 whatever the activations are
    dot = functools.partial(
        jnp.dot, preferred_element_type=F32,
        precision=jax.lax.Precision.DEFAULT if dt_.itemsize < 4 else _HIGHEST,
    )
    dot32 = functools.partial(
        jnp.dot, preferred_element_type=F32, precision=_HIGHEST
    )
    Q, K, KT = q_ref[...], k_ref[...], kT_ref[...]
    # one key head a grid step: its ``rep`` value heads share these
    KK, QK = dot(K, KT), dot(Q, KT)
    ri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (ri == ci).astype(F32)
    # the solve's blocks: the diagonal ones, then at each doubling the
    # block below the diagonal that joins two neighbours
    base = min(_SOLVE_BLOCK, C)
    diagonal = ri // base == ci // base
    joins, b = [], base
    while b < C:
        joins.append((ri // (2 * b) == ci // (2 * b)) & (ri // b != ci // b))
        b *= 2
    for i in range(rep):
        cum, beta = col_ref[:, i:i + 1], col_ref[:, rep + i:rep + i + 1]
        cumT = row_ref[i:i + 1, :]
        # position j reaches i through exp(c_i - c_j) <= 1
        D = jnp.exp(jnp.where(ri >= ci, cum - cumT, _NEG))
        A = jnp.where(ri > ci, beta * KK * D, 0.0)
        # (I + A)^-1. Inside a diagonal block (I - A)(I + A^2)(I +
        # A^4)...: the block's A is nilpotent at its size
        Ad = jnp.where(diagonal, A, 0.0)
        T, X = eye - Ad, dot32(Ad, Ad)
        n = 2
        while n < base:
            T = T + dot32(T, X)
            n *= 2
            if n < base:
                X = dot32(X, X)
        # two neighbours joined: [[T1, 0], [-T2 A21 T1, T2]]
        for join in joins:
            T = T - dot32(T, dot32(jnp.where(join, A, 0.0), T))
        e = jnp.exp(cum)
        V = v_ref[:, i * d_v:(i + 1) * d_v].astype(F32)
        W = dot32(T, K.astype(F32) * (beta * e))
        U = dot32(T, V * beta)
        S = st[i]
        Sd = S.astype(dt_)
        Vn = U - dot(W.astype(dt_), Sd)
        o = dot((Q.astype(F32) * e).astype(dt_), Sd) + dot(
            jnp.where(ri >= ci, QK * D, 0.0).astype(dt_), Vn.astype(dt_)
        )
        o_ref[:, i * d_v:(i + 1) * d_v] = o.astype(o_ref.dtype)
        last = row_ref[i:i + 1, C - 1:C]  # c_C: [1, 1]
        st[i] = dec_ref[i:i + 1, :] * S + dot(
            (KT.astype(F32) * jnp.exp(last - cumT)).astype(dt_), Vn.astype(dt_)
        )

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _store():
        fin_ref[...] = st[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def gdn_chunk_scan(q, k, v, g, beta, init, *, chunk=DEFAULT_CHUNK,
                   interpret=False):
    """``gdn_scan_plain`` in chunks. ``q``/``k`` [B, S, Hk, dk], ``v``
    [B, S, H, dv] (their dtype is the matmuls' operand dtype outside the
    in-chunk solve; accumulation, decays, the solve and the state are
    float32), ``g``/``beta`` [B, S, H] float32 (both 0 = masked),
    ``init`` [B, H, dk, dv] float32. ``S`` is padded to whole chunks
    with masked positions. Returns ``(o [B, S, H, dv] in v's dtype,
    final state float32)``."""
    B_, S, H, dv = v.shape
    Hk, dk = k.shape[2:]
    rep = H // Hk
    assert chunk & (chunk - 1) == 0, chunk
    C = chunk
    while C > 8 and C // 2 >= S:
        C //= 2  # a row shorter than a chunk
    Sp = -(-S // C) * C
    nc = Sp // C
    dt_ = v.dtype

    def chunks(a, width):  # [B, S, ...] -> [B, nc, C, width], padded
        a = a.reshape(B_, S, width)
        return jnp.pad(a, ((0, 0), (0, Sp - S), (0, 0))).reshape(B_, nc, C, width)

    q4, k4 = chunks(q.astype(dt_), Hk * dk), chunks(k.astype(dt_), Hk * dk)
    v4 = chunks(v, H * dv)
    g4, b4 = chunks(g.astype(F32), H), chunks(beta.astype(F32), H)
    # the decays' exponents summed inside each chunk
    cum = jnp.cumsum(g4, axis=2)
    # per key head: its value heads' (cum | beta) as columns, cum as rows
    by_head = lambda a: jnp.moveaxis(  # noqa: E731 — [B, nc, Hk, C, rep]
        a.reshape(B_, nc, C, Hk, rep), 3, 2
    )
    cols = jnp.concatenate([by_head(cum), by_head(b4)], axis=-1)
    rows = jnp.swapaxes(by_head(cum), -1, -2)
    # a chunk's whole decay exp(c_C), along a state's rows (Mosaic does
    # not broadcast a [1, 1] along both axes at once)
    dec = jnp.broadcast_to(jnp.exp(rows[..., -1:]), (B_, nc, Hk, rep, dv))

    at = lambda b, j, c: (b, c, 0, j)  # noqa: E731
    o, fin = pl.pallas_call(
        functools.partial(_scan_kernel, rep=rep, d_v=dv),
        name="gdn_chunk_scan",
        grid=(B_, Hk, nc),
        in_specs=[
            pl.BlockSpec((None, None, C, dk), at),
            pl.BlockSpec((None, None, C, dk), at),
            pl.BlockSpec((None, None, dk, C), lambda b, j, c: (b, c, j, 0)),
            pl.BlockSpec((None, None, C, rep * dv), at),
            pl.BlockSpec((None, None, None, C, 2 * rep), lambda b, j, c: (b, c, j, 0, 0)),
            pl.BlockSpec((None, None, None, rep, C), lambda b, j, c: (b, c, j, 0, 0)),
            pl.BlockSpec((None, None, None, rep, dv), lambda b, j, c: (b, c, j, 0, 0)),
            pl.BlockSpec((None, rep, dk, dv), lambda b, j, c: (b, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, C, rep * dv), at),
            pl.BlockSpec((None, rep, dk, dv), lambda b, j, c: (b, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_, nc, C, H * dv), dt_),
            jax.ShapeDtypeStruct((B_, H, dk, dv), F32),
        ],
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(q4, k4, jnp.swapaxes(k4, 2, 3), v4, cols, rows, dec, init.astype(F32))
    return o.reshape(B_, Sp, H, dv)[:, :S], fin
