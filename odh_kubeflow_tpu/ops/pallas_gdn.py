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
  16 -> 32 -> 64, all of it float32 matmuls on the MXU at
  ``Precision.HIGHEST``. Between chunks the state, carried in VMEM: an
  initial state in, the final state out.

  **The solve is batched** (PR 41). ``A``, ``T``, ``W`` and ``U`` are
  functions of the chunk's own ``K``, ``V``, ``beta`` and ``g``: only
  ``V'``, ``O`` and ``S_C`` go from chunk to chunk. The solve's
  products are a quarter of an MXU tile each and hang on each other,
  ten levels deep, and that DEPTH set the time, not their count: the
  same twelve products a system took 2.37 ms a call of 2048 positions
  as squarings over the whole chunk (seven levels) and 2.79 ms
  blockwise (ten), one key head's two value heads a grid step, one
  after the other (PR 34). Mosaic issues the MXU's work in the order it
  is written: a second head's chain written behind the first's does
  not start before that ends (one, two and four heads a step compile to
  2127, 4058 and 7750 instruction words), and the two heads' levels
  written side by side by hand ran in 1.83 ms for 2.74 (PR 41). So a
  grid step takes ``SCAN_SYSTEMS`` systems (a value head's chunk each:
  value heads first, so a bucket of one chunk has as many as a part of
  2048; chunks where the heads are too few) through the solve LEVEL BY
  LEVEL (``_solve``): the systems lie along a leading axis and every
  operation of the kernel is ONE over all of them, so a level's
  products stand next to each other and overlap (and set-up traces and
  lowers a kernel of the old one's size: written out system by system
  it was seven times that, half a second more a prefill program). What
  the batch then made cheap, every
  element still the same float32 sum of the same terms (what goes is
  zeros):

  - two systems side by side along the lanes (a chunk of 64 fills half
    of them) against the pair's block-diagonal right operand: a pair's
    product streams and pops the rows of one;
  - a left operand whose blocks of rows reach disjoint lanes goes
    through its product FOLDED, the blocks summed into the rows of one:
    the eight diagonal blocks of 8 are 8 rows, not 64, and a join
    multiplies the rows it moves (the lower half of each doubled block:
    ``T22 A21 T11``; the rest of ``T (A_join T)`` is zeros) as those of
    ONE doubled block;
  - ``T + T X`` and the next ``X^2`` are one product (they share ``X``),
    ``W`` and ``U`` one product against ``[K beta e | V beta]``.

  What is left is the MXU's own time for float32 operands (a pass of 8
  rows takes ~7 cycles, a push of 8 rows of the right operand 2, and
  ``HIGHEST`` is six passes, each with its own pushes): ``W | U`` alone
  is half of it. The state pass behind the solve (one bf16 pass a
  product, the state float32 in VMEM) runs the step's heads side by
  side too. Both halves are ONE ``pallas_call``: ``W`` and ``U`` never
  leave VMEM.

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
# systems (a value head's chunk each) a grid step of the prefill scan
# takes through the in-chunk solve together (``_solve``)
SCAN_SYSTEMS = 8
# positions of a diagonal block of the in-chunk solve (``_solve``)
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


def _solve(A, Bm, dot32):
    """``(I + A_s)^-1 B_s`` for every system ``s`` along the leading axis
    (``A`` [G, C, C] strictly lower triangular, ``Bm`` [G, C, n]), all
    float32. Every operation is ONE over all the systems, so each level
    of the chain's products stands together (Mosaic issues products in
    the order they are written and overlaps only neighbours that do not
    hang on each other). Two systems lie side by side along the lanes
    where a chunk fills half of them: ``[C, 2C]`` against the pair's
    block-diagonal ``[2C, 2C]``. A left operand made of blocks that
    reach disjoint lanes goes through its product FOLDED, the blocks
    summed into the rows of one: the diagonal blocks of 8 as 8 rows, a
    join's moving rows as those of one doubled block."""
    G, C, _ = A.shape
    pk = 2 if 2 * C <= 128 and G > 1 else 1
    P = -(-G // pk)  # packs: system p beside system p + P
    if P * pk != G:  # the odd one out beside itself
        A, Bm = (jnp.concatenate([x, x[-1:]], axis=0) for x in (A, Bm))
    if pk == 2:
        A = jnp.concatenate([A[:P], A[P:]], axis=2)
        Bm = jnp.concatenate([Bm[:P], Bm[P:]], axis=1)
    width = pk * C
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape, axis)  # noqa: E731
    # every size here is a power of two: a block's index is a shift (a
    # ``//`` traces to ten operations, and set-up lowers this kernel once
    # a prefill program)
    blk = lambda i, b: jnp.right_shift(i, b.bit_length() - 1)  # noqa: E731
    ri, ci = iota((C, width), 0), jnp.bitwise_and(iota((C, width), 1), C - 1)
    rw, cw = iota((width, width), 0), iota((width, width), 1)
    base = min(_SOLVE_BLOCK, C)
    own = {b: blk(rw, b) == blk(cw, b) for b in {base, C}}
    rows = lambda blocks: jnp.concatenate(blocks, axis=1)  # noqa: E731 — whole tiles
    fold = lambda x, starts, b: sum(x[:, r:r + b] for r in starts)  # noqa: E731

    def right(x, b):
        """``x`` [P, b, width] (folded) or [P, C, width] as the right
        operand of its systems' own products: block-diagonal in blocks
        of ``b`` (folded) or in whole systems."""
        if x.shape[1] == width:
            return x
        return jnp.where(own[b], rows([x] * (width // x.shape[1])), 0.0)

    # inside a diagonal block (I - A)(I + A^2)(I + A^4)...: the block's A
    # is nilpotent at its size. ``T + T X`` and the next ``X^2`` share
    # their right operand: one product of both, one under the other
    blocks = range(0, C, base)
    diagonal = blk(ri, base) == blk(ci, base)
    Ad = fold(jnp.where(diagonal, A, 0.0), blocks, base)
    T = (ri[:base] == jnp.bitwise_and(ci[:base], base - 1)).astype(F32) - Ad
    X = dot32(Ad, right(Ad, base))
    n = 4
    while n < base:  # another squaring follows
        TX = dot32(rows([T, X]), right(X, base))
        T, X = T + TX[:, :base], TX[:, base:]
        n *= 2
    if base > 2:
        T = T + dot32(T, right(X, base))
    T = jnp.where(diagonal, rows([T] * len(blocks)), 0.0)
    # two neighbours joined: [[T1, 0], [-T2 A21 T1, T2]]. Only the lower
    # half of a doubled block moves, and the doubled blocks reach disjoint
    # lanes: their lower halves go through both products folded
    b = base
    while b < C:
        lower = range(b, C, 2 * b)
        join = (blk(ri, 2 * b) == blk(ci, 2 * b)) & (blk(ri, b) != blk(ci, b))
        lane = blk(jnp.bitwise_and(iota((b, width), 1), C - 1), 2 * b)
        mine = [lane == r // (2 * b) for r in lower]  # unfolded: a block's own lanes
        zero = jnp.zeros((P, b, width), F32)
        J = dot32(fold(jnp.where(join, A, 0.0), lower, b), right(T, C))
        J = rows([blk_ for m in mine for blk_ in (zero, jnp.where(m, J, 0.0))])
        TJ = dot32(fold(T, lower, b), right(J, C))
        T = rows([
            blk_ for r, m in zip(lower, mine)
            for blk_ in (T[:, r - b:r], T[:, r:r + b] - jnp.where(m, TJ, 0.0))
        ])
        b *= 2
    X = dot32(right(T, C), Bm)
    if pk == 2:
        X = jnp.concatenate([X[:, :C], X[:, C:]], axis=0)
    return X[:G]


def _scan_kernel(q_ref, k_ref, kT_ref, v_ref, col_ref, row_ref, dec_ref,
                 init_ref, o_ref, fin_ref, st, *, rep, d_k, d_v):
    @pl.when(pl.program_id(2) == 0)
    def _load():
        st[...] = init_ref[...]

    cb, C = q_ref.shape[:2]
    hb = col_ref.shape[1]
    dt_ = q_ref.dtype
    # a bf16 operand has one pass to offer (``pallas_moe_local``); the
    # in-chunk solve is float32 whatever the activations are. Both a
    # product a system, the systems along the leading axis
    batched = functools.partial(
        jnp.einsum, "gik,gkj->gij", preferred_element_type=F32
    )
    dot = functools.partial(
        batched,
        precision=jax.lax.Precision.DEFAULT if dt_.itemsize < 4 else _HIGHEST,
    )
    dot32 = functools.partial(batched, precision=_HIGHEST)
    ri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # a system: one value head's chunk, [chunk, key head, value head] in
    # the order the state's heads lie. ALL of the step go through every
    # operation together: [G, ...]
    systems = [(c, j, i) for c in range(cb) for j in range(hb) for i in range(rep)]
    at = lambda j: slice(j * d_k, (j + 1) * d_k)  # noqa: E731
    of = lambda j, i: slice((j * rep + i) * d_v, (j * rep + i + 1) * d_v)  # noqa: E731
    Q = jnp.stack([q_ref[c, :, at(j)] for c, j, _ in systems])
    K = jnp.stack([k_ref[c, :, at(j)] for c, j, _ in systems])
    KT = jnp.stack([kT_ref[c, at(j), :] for c, j, _ in systems])
    V = jnp.stack([v_ref[c, :, of(j, i)] for c, j, i in systems]).astype(F32)
    cum = jnp.stack([col_ref[c, j, :, i:i + 1] for c, j, i in systems])
    beta = jnp.stack([col_ref[c, j, :, rep + i:rep + i + 1] for c, j, i in systems])
    cumT = jnp.stack([row_ref[c, j, i:i + 1, :] for c, j, i in systems])
    dec = jnp.stack([dec_ref[c, j, i:i + 1, :] for c, j, i in systems])
    KK, QK = dot(K, KT), dot(Q, KT)
    # position j reaches i through exp(c_i - c_j) <= 1
    D = jnp.exp(jnp.where(ri >= ci, cum - cumT, _NEG))
    e = jnp.exp(cum)
    # W | U = (I + A)^-1 [diag(beta) K exp(c) | diag(beta) V]: one solve
    WU = _solve(
        jnp.where(ri > ci, beta * KK * D, 0.0),
        jnp.concatenate([K.astype(F32) * (beta * e), V * beta], axis=2),
        dot32,
    )
    W, U = WU[:, :, :d_k].astype(dt_), WU[:, :, d_k:]
    # what else of the state pass needs no state
    Qe = (Q.astype(F32) * e).astype(dt_)
    Pm = jnp.where(ri >= ci, QK * D, 0.0).astype(dt_)
    # c_C - c_j: what is left of the chunk after position j
    Kd = (KT.astype(F32) * jnp.exp(cumT[:, :, C - 1:C] - cumT)).astype(dt_)

    # the state pass: chunk after chunk, the step's heads together
    heads = hb * rep
    S = st[...]
    for c in range(cb):
        now = slice(c * heads, (c + 1) * heads)
        Sd = S.astype(dt_)
        Vn = (U[now] - dot(W[now], Sd)).astype(dt_)
        o = (dot(Qe[now], Sd) + dot(Pm[now], Vn)).astype(o_ref.dtype)
        for h in range(heads):
            o_ref[c, :, h * d_v:(h + 1) * d_v] = o[h]
        S = dec[now] * S + dot(Kd[now], Vn)
    st[...] = S

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
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
    nc = -(-S // C)
    # the systems of a grid step: value heads first (a bucket of one
    # chunk has as many as a part), chunks where the heads are too few
    hb = max(1, min(Hk, SCAN_SYSTEMS // rep))
    while Hk % hb:
        hb -= 1
    cb = max(1, min(nc, SCAN_SYSTEMS // (hb * rep)))
    nc = -(-nc // cb) * cb
    Sp = nc * C
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
    by_key = lambda b, j, c: (b, c, j, 0, 0)  # noqa: E731
    state = pl.BlockSpec((None, hb * rep, dk, dv), lambda b, j, c: (b, j, 0, 0))
    o, fin = pl.pallas_call(
        functools.partial(_scan_kernel, rep=rep, d_k=dk, d_v=dv),
        name="gdn_chunk_scan",
        grid=(B_, Hk // hb, nc // cb),
        in_specs=[
            pl.BlockSpec((None, cb, C, hb * dk), at),
            pl.BlockSpec((None, cb, C, hb * dk), at),
            pl.BlockSpec((None, cb, hb * dk, C), lambda b, j, c: (b, c, j, 0)),
            pl.BlockSpec((None, cb, C, hb * rep * dv), at),
            pl.BlockSpec((None, cb, hb, C, 2 * rep), by_key),
            pl.BlockSpec((None, cb, hb, rep, C), by_key),
            pl.BlockSpec((None, cb, hb, rep, dv), by_key),
            state,
        ],
        out_specs=[pl.BlockSpec((None, cb, C, hb * rep * dv), at), state],
        out_shape=[
            jax.ShapeDtypeStruct((B_, nc, C, H * dv), dt_),
            jax.ShapeDtypeStruct((B_, H, dk, dv), F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb * rep, dk, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(q4, k4, jnp.swapaxes(k4, 2, 3), v4, cols, rows, dec, init.astype(F32))
    return o.reshape(B_, Sp, H, dv)[:, :S], fin
