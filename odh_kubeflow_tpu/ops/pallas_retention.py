"""Pallas TPU kernels for gated power retention of degree 2, and its
plain forms.

Per key/value head, with ``log g_t <= 0`` the log of the step's decay
and ``phi`` the quadratic feature map for which ``phi(a) . phi(b) == (a
. b)^2`` (``G = Hq // Hkv`` query heads read one head's state):

    S_t = g_t S_{t-1} + phi(k_t) (outer) v_t        (the state)
    z_t = g_t z_{t-1} + phi(k_t)                    (the normaliser)
    y_t = phi(q_t)^T S_t * s^2 / (phi(q_t) . z_t * s^2 + eps)

(``s`` the scale inside the power, ``head_dim ** -0.5``: ``phi`` is
quadratic, so it leaves as ``s^2``; the norms, RoPE and the gate's
projection are the model's, ``models/brumby.py``.) A position with
``log g = 0`` and a zero key is the identity on both: that is how a
caller masks padding and idle rows. There are no keys and values to
keep: the state IS the cache.

**How phi is laid, and the state with it.** The symmetric half of ``x
(outer) x`` by cyclic diagonals: with ``d`` the head's size (even) and
``R = d // 2 + 1``,

    phi(x)[r, i] = c_r x_i x_{(i + r) mod d},   r = 0..d/2

``c_0 = 1`` (the squares), ``c_r = sqrt 2`` (every unordered pair at
cyclic distance ``r`` once) and ``c_{d/2} = 1`` (each of those pairs
lies there twice). So ``phi`` is ``R`` ROWS of ``d`` lanes, each a lane
roll of ``x`` times ``x``: built in VMEM a row at a time and never
written to HBM. ``R * d`` = 8320 for a head of 128, against the
``d (d + 1) / 2`` = 8256 distinct pairs: the last diagonal is held
double (0.8 % of padding) so that every row is whole.

The state is ``[..., Hkv, R, d_v, d]`` float32: ``phi``'s index ``i``
along the 128 lanes and ``d_v`` along the sublanes, so that ``phi`` and
``q`` stay rows and only ``v`` is a column (``d_v`` floats beside a 4.3
MB state). The normaliser is ``[..., Hkv, R, d]`` float32.

- ``retention_decode_update`` (``name="retention_decode_update"``): one
  token a row. ONE pass over the state: read, decay, add ``phi(k) v^T``,
  read out by the group's ``G`` query heads, write. The state is the
  STACKED cache ``[L, B, Hkv, R, d_v, d]``, addressed by a prefetched
  layer index and aliased in and out (``pallas_gdn.gdn_decode_update``'s
  discipline), and so is the normaliser. All of it on the VPU in
  float32: eight sublanes of ``d_v`` at a time against every row of
  ``phi``, so that a query head's accumulator is one register.
- ``retention_chunk_scan`` (``name="retention_chunk_scan"``): a whole
  part, in chunks of ``chunk`` positions. Inside a chunk the attention
  form under the decay mask, ``(q k^T)^2 * exp(G_t - G_j)``; across
  chunks through the state carried in VMEM: an initial state in, the
  final state out. With ``c`` the decays' exponents summed inside the
  chunk:

      num = tril((Q K^T)^2 * exp(c_i - c_j)) V + exp(c) phi(Q) S_0
      den = rowsum(tril(...))               + exp(c) phi(Q) . z_0
      S_C = exp(c_C) S_0 + phi(K)^T (V * exp(c_C - c))

The plain forms (``retention_scan_plain``: the recurrence token by
token; ``retention_step_plain``) are what runs off the TPU and what the
kernels are held to.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 128
EPS = 1e-6
_SUBLANES = 8
_VMEM_LIMIT = 64 << 20
_NEG = -1e30
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def phi_rows(d: int) -> int:
    """Rows of ``phi`` for a head of ``d``: the cyclic distances 0..d/2."""
    assert d % 2 == 0, d
    return d // 2 + 1


def _coef(d: int) -> tuple:
    R = phi_rows(d)
    return (1.0,) + (math.sqrt(2.0),) * (R - 2) + (1.0,)


# ---------------------------------------------------------------------------
# plain forms


def phi(x):
    """``[..., d] -> [..., R, d]`` float32 with ``phi(a) . phi(b) == (a
    . b)^2`` (summed over both axes)."""
    x = x.astype(F32)
    d = x.shape[-1]
    rolled = jnp.stack(
        [jnp.roll(x, -r, axis=-1) for r in range(phi_rows(d))], axis=-2
    )
    return jnp.asarray(_coef(d), F32)[:, None] * x[..., None, :] * rolled


def _read(pq, S, z, scale, eps):
    """``pq`` [B, Hkv, G, R, d] against ``S`` [B, Hkv, R, dv, d] and
    ``z`` [B, Hkv, R, d]: ``y`` [B, Hkv, G, dv]."""
    s2 = scale * scale
    num = jnp.einsum("bhgri,bhrvi->bhgv", pq, S, precision=_HIGHEST)
    den = jnp.einsum("bhgri,bhri->bhg", pq, z, precision=_HIGHEST)
    return num * s2 / (den * s2 + eps)[..., None]


def _retention_step(S, z, q, k, v, log_g, scale, eps):
    """One token of every (row, head): ``S`` [B, Hkv, R, dv, d], ``z``
    [B, Hkv, R, d], ``q`` [B, Hq, d], ``k``/``v`` [B, Hkv, d], ``log_g``
    [B, Hkv]."""
    B, Hkv, d = k.shape
    g = jnp.exp(log_g.astype(F32))
    pk = phi(k)
    S = g[..., None, None, None] * S + (
        v.astype(F32)[..., None, :, None] * pk[..., :, None, :]
    )
    z = g[..., None, None] * z + pk
    pq = phi(q).reshape(B, Hkv, -1, phi_rows(d), d)
    return S, z, _read(pq, S, z, scale, eps).reshape(B, -1, v.shape[-1])


def retention_scan_plain(q, k, v, log_g, state, norm, *, scale=None, eps=EPS):
    """The recurrence token by token, float32. ``q`` [B, S, Hq, d],
    ``k``/``v`` [B, S, Hkv, d], ``log_g`` [B, S, Hkv] (0 with a zero key
    = masked), ``state`` [B, Hkv, R, dv, d], ``norm`` [B, Hkv, R, d].
    Returns ``(y [B, S, Hq, dv] float32, final state, final norm)``."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale

    def step(carry, xs):
        S, z, y = _retention_step(*carry, *xs, scale, eps)
        return (S, z), y

    (S, z), y = jax.lax.scan(
        step, (state.astype(F32), norm.astype(F32)),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_g)),
    )
    return jnp.moveaxis(y, 0, 1), S, z


def retention_step_plain(q, k, v, log_g, state, norm, layer, *, scale=None,
                         eps=EPS):
    """One token a row on the stacked state, in plain ``jax.numpy``:
    ``q`` [B, Hq, d], ``k``/``v`` [B, Hkv, d], ``log_g`` [B, Hkv],
    ``state`` [L, B, Hkv, R, dv, d], ``norm`` [L, B, Hkv, R, d]. Returns
    ``(y [B, Hq, dv] float32, state, norm)``."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    with jax.named_scope("retention_decode_update"):
        S = jax.lax.dynamic_index_in_dim(state, layer, 0, False)
        z = jax.lax.dynamic_index_in_dim(norm, layer, 0, False)
        S, z, y = _retention_step(S, z, q, k, v, log_g, scale, eps)
        state = jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)
        norm = jax.lax.dynamic_update_index_in_dim(norm, z, layer, 0)
    return y, state, norm


# ---------------------------------------------------------------------------
# decode: one token a row, the stacked state in place


def _roll_left(x, r: int):
    """``x[..., (i + r) mod d]`` at lane ``i``."""
    d = x.shape[-1]
    return x if r % d == 0 else pltpu.roll(x, (d - r) % d, x.ndim - 1)


def _decode_kernel(layer_ref, s_ref, z_ref, k_ref, q_ref, vT_ref, dec_ref,
                   so_ref, zo_ref, num_ref, den_ref, pkb, pqb, *, groups):
    del layer_ref
    R, dv, d = s_ref.shape
    coef = _coef(d)
    k, q, g = k_ref[...], q_ref[...], dec_ref[...]  # [8, d]: k, g repeated
    # phi's rows, each already along eight sublanes, and with them the
    # normaliser (R rows: a hundred-and-twenty-eighth of the state)
    dacc = jnp.zeros(q.shape, F32)
    for r in range(R):
        pk = coef[r] * k * _roll_left(k, r)
        pq = coef[r] * q * _roll_left(q, r)
        z = g[:1] * z_ref[r:r + 1, :] + pk[:1]
        zo_ref[r:r + 1, :] = z
        dacc = dacc + pq * z
        pkb[r] = pk
        for a in range(groups):
            pqb[a, r] = jnp.broadcast_to(pq[a:a + 1, :], pk.shape)
    den_ref[...] = jnp.sum(dacc, axis=1, keepdims=True)

    def sublanes(s, carry):
        rows = pl.ds(pl.multiple_of(s * _SUBLANES, _SUBLANES), _SUBLANES)
        vb = jnp.broadcast_to(vT_ref[rows, :], (_SUBLANES, d))
        acc = [jnp.zeros((_SUBLANES, d), F32)] * groups
        for r in range(R):
            S = g * s_ref[r, rows, :] + vb * pkb[r]
            so_ref[r, rows, :] = S
            for a in range(groups):
                acc[a] = acc[a] + S * pqb[a, r]
        for a in range(groups):
            num_ref[rows, a:a + 1] = jnp.sum(acc[a], axis=1, keepdims=True)
        return carry

    jax.lax.fori_loop(0, dv // _SUBLANES, sublanes, 0)


@functools.partial(jax.jit, static_argnames=("scale", "eps", "interpret"))
def retention_decode_update(q, k, v, log_g, state, norm, layer, *,
                            scale=None, eps=EPS, interpret=False):
    """``retention_step_plain`` as one pass over the layer's state where
    it lies in the stack (state and normaliser aliased in and out)."""
    B_, Hkv, d = k.shape
    dv = v.shape[-1]
    G = q.shape[1] // Hkv
    L, R = state.shape[0], phi_rows(d)
    assert state.shape == (L, B_, Hkv, R, dv, d), (state.shape, k.shape, v.shape)
    assert norm.shape == (L, B_, Hkv, R, d), (norm.shape, state.shape)
    assert dv % _SUBLANES == 0 and G <= _SUBLANES, (dv, G)
    scale = d**-0.5 if scale is None else scale
    eight = lambda a: jnp.broadcast_to(  # noqa: E731 — a row along the sublanes
        a.astype(F32)[:, :, None, :], (B_, Hkv, _SUBLANES, a.shape[-1])
    )
    q8 = jnp.pad(
        q.astype(F32).reshape(B_, Hkv, G, d),
        ((0, 0), (0, 0), (0, _SUBLANES - G), (0, 0)),
    )
    dec = jnp.broadcast_to(jnp.exp(log_g.astype(F32))[..., None], (B_, Hkv, d))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    row8 = pl.BlockSpec((None, None, _SUBLANES, d), lambda i, j, layer: (i, j, 0, 0))
    s_block = pl.BlockSpec(
        (None, None, None, R, dv, d), lambda i, j, layer: (layer[0], i, j, 0, 0, 0)
    )
    z_block = pl.BlockSpec(
        (None, None, None, R, d), lambda i, j, layer: (layer[0], i, j, 0, 0)
    )
    state, norm, num, den = pl.pallas_call(
        functools.partial(_decode_kernel, groups=G),
        name="retention_decode_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B_, Hkv),
            in_specs=[
                s_block, z_block, row8, row8,
                pl.BlockSpec((None, None, dv, 1), lambda i, j, layer: (i, j, 0, 0)),
                row8,
            ],
            out_specs=[
                s_block, z_block,
                pl.BlockSpec((None, None, dv, G), lambda i, j, layer: (i, j, 0, 0)),
                pl.BlockSpec(
                    (None, None, _SUBLANES, 1), lambda i, j, layer: (i, j, 0, 0)
                ),
            ],
            scratch_shapes=[
                pltpu.VMEM((R, _SUBLANES, d), F32),
                pltpu.VMEM((G, R, _SUBLANES, d), F32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct(norm.shape, norm.dtype),
            jax.ShapeDtypeStruct((B_, Hkv, dv, G), F32),
            jax.ShapeDtypeStruct((B_, Hkv, _SUBLANES, 1), F32),
        ],
        # operand 0 is the prefetched layer index: the state is 1, the
        # normaliser 2
        input_output_aliases={1: 0, 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(layer, state, norm, eight(k), q8, v.astype(F32)[..., None], eight(dec))
    s2 = scale * scale
    y = jnp.swapaxes(num, 2, 3) * s2 / (den[:, :, :G] * s2 + eps)
    return y.reshape(B_, Hkv * G, dv), state, norm


# ---------------------------------------------------------------------------
# prefill: chunks in attention form, the state carried between them


def _scan_kernel(q_ref, k_ref, v_ref, col_ref, row_ref, dec_ref, s0_ref, z0_ref,
                 o_ref, fin_ref, zfin_ref, st, zst, *, groups, s2, eps):
    chunk = pl.program_id(2)

    @pl.when(chunk == 0)
    def _load():
        st[...] = s0_ref[...]
        zst[...] = z0_ref[...]

    C, d = k_ref.shape
    dv = v_ref.shape[1]
    R = st.shape[0]
    coef = _coef(d)
    dt_ = k_ref.dtype
    # a bf16 operand has one pass to offer (``pallas_gdn``), so with
    # bf16 activations everything that meets the MXU is cast to bf16:
    # the state AS READ (``Sd``; the carried ``st`` stays float32),
    # ``phi(Q)``, ``phi(K)``, ``V * w`` and the in-chunk weights ``A``.
    # ``Q K^T`` as accumulated, its square, the decays, the denominator
    # (from ``A`` before its cast), the normaliser's path and every
    # accumulation are float32 whatever the activations are
    prec = jax.lax.Precision.DEFAULT if dt_.itemsize < 4 else _HIGHEST
    dot = functools.partial(jnp.dot, preferred_element_type=F32, precision=prec)
    dot_nt = lambda a, b: jax.lax.dot_general(  # noqa: E731 — a b^T
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=F32, precision=prec
    )
    K, V = k_ref[...], v_ref[...]
    K32 = K.astype(F32)
    cum, cumT = col_ref[...], row_ref[...]  # [C, 1], [1, C]
    ri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # position j reaches i through exp(c_i - c_j) <= 1
    D = jnp.exp(jnp.where(ri >= ci, cum - cumT, _NEG))
    e = jnp.exp(cum)  # the chunk's start reaches i through it
    w = jnp.exp(cumT[:, C - 1:C] - cum)  # j reaches the chunk's end through it
    VwT = (V.astype(F32) * w).T.astype(dt_)

    Q = [q_ref[:, a * d:(a + 1) * d] for a in range(groups)]
    num, den = [], []
    for a in range(groups):
        s = dot_nt(Q[a], K)
        A = s * s * D
        num.append(dot(A.astype(dt_), V))
        den.append(jnp.sum(A, axis=1, keepdims=True))
    Q32 = [x.astype(F32) for x in Q]
    from_state = [jnp.zeros((C, dv), F32)] * groups
    from_norm = [jnp.zeros((C, d), F32)] * groups
    dec = dec_ref[...]  # [1, d]: exp(c_C)
    for r in range(R):
        S, z = st[r], zst[r:r + 1, :]
        Sd = S.astype(dt_)
        for a in range(groups):
            pq = coef[r] * Q32[a] * _roll_left(Q32[a], r)
            from_state[a] = from_state[a] + dot_nt(pq.astype(dt_), Sd)
            from_norm[a] = from_norm[a] + pq * z
        pk = coef[r] * K32 * _roll_left(K32, r)
        st[r] = dec * S + dot(VwT, pk.astype(dt_))
        zst[r:r + 1, :] = dec * z + jnp.sum(pk * w, axis=0, keepdims=True)
    for a in range(groups):
        n = (num[a] + e * from_state[a]) * s2
        dn = (den[a] + e * jnp.sum(from_norm[a], axis=1, keepdims=True)) * s2
        o_ref[:, a * dv:(a + 1) * dv] = (n / (dn + eps)).astype(o_ref.dtype)

    @pl.when(chunk == pl.num_programs(2) - 1)
    def _store():
        fin_ref[...] = st[...]
        zfin_ref[...] = zst[...]


@functools.partial(
    jax.jit, static_argnames=("chunk", "scale", "eps", "interpret")
)
def retention_chunk_scan(q, k, v, log_g, state, norm, *, chunk=DEFAULT_CHUNK,
                         scale=None, eps=EPS, interpret=False):
    """``retention_scan_plain`` in chunks. ``q`` [B, S, Hq, d],
    ``k``/``v`` [B, S, Hkv, d] (their dtype is the matmuls' operand
    dtype, to which the state as read, ``phi`` and the weights are cast
    too; decays, squares, accumulation, the normaliser's path and the
    state as carried are float32), ``log_g`` [B, S, Hkv] float32 (0
    with a zero key = masked), ``state`` [B, Hkv, R, dv, d], ``norm`` [B, Hkv, R, d]
    float32. ``S`` is padded to whole chunks with masked positions.
    Returns ``(y [B, S, Hq, dv] in v's dtype, final state, final
    norm)``."""
    B_, S, Hkv, d = k.shape
    dv = v.shape[-1]
    G = q.shape[2] // Hkv
    R = phi_rows(d)
    assert state.shape == (B_, Hkv, R, dv, d), (state.shape, k.shape, v.shape)
    scale = d**-0.5 if scale is None else scale
    C = chunk
    while C > 8 and C // 2 >= S:
        C //= 2  # a row shorter than a chunk
    Sp = -(-S // C) * C
    nc = Sp // C
    dt_ = v.dtype

    def chunks(a, width):  # [B, S, ...] -> [B, nc, C, width], padded
        a = a.reshape(B_, S, width)
        return jnp.pad(a, ((0, 0), (0, Sp - S), (0, 0))).reshape(B_, nc, C, width)

    q4 = chunks(q.astype(dt_), Hkv * G * d)
    k4, v4 = chunks(k.astype(dt_), Hkv * d), chunks(v, Hkv * dv)
    # the decays' exponents summed inside each chunk, per head as a
    # column and as a row
    cum = jnp.moveaxis(jnp.cumsum(chunks(log_g.astype(F32), Hkv), axis=2), 3, 2)
    cols, rows = cum[..., None], cum[..., None, :]  # [B, nc, Hkv, C, 1] / [.., 1, C]
    # a chunk's whole decay exp(c_C), along a state's lanes (Mosaic does
    # not broadcast a [1, 1] along both axes at once)
    dec = jnp.broadcast_to(jnp.exp(cols[..., -1:, :]), (B_, nc, Hkv, 1, d))

    at = lambda b, j, c: (b, c, 0, j)  # noqa: E731
    per_head = lambda *blk: pl.BlockSpec(  # noqa: E731
        (None, None, None) + blk, lambda b, j, c: (b, c, j, 0, 0)
    )
    s_block = pl.BlockSpec((None, None, R, dv, d), lambda b, j, c: (b, j, 0, 0, 0))
    z_block = pl.BlockSpec((None, None, R, d), lambda b, j, c: (b, j, 0, 0))
    o, fin, zfin = pl.pallas_call(
        functools.partial(_scan_kernel, groups=G, s2=scale * scale, eps=eps),
        name="retention_chunk_scan",
        grid=(B_, Hkv, nc),
        in_specs=[
            pl.BlockSpec((None, None, C, G * d), at),
            pl.BlockSpec((None, None, C, d), at),
            pl.BlockSpec((None, None, C, dv), at),
            per_head(C, 1), per_head(1, C), per_head(1, d),
            s_block, z_block,
        ],
        out_specs=[pl.BlockSpec((None, None, C, G * dv), at), s_block, z_block],
        out_shape=[
            jax.ShapeDtypeStruct((B_, nc, C, Hkv * G * dv), dt_),
            jax.ShapeDtypeStruct(state.shape, F32),
            jax.ShapeDtypeStruct(norm.shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((R, dv, d), F32), pltpu.VMEM((R, d), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(q4, k4, v4, cols, rows, dec, state.astype(F32), norm.astype(F32))
    return o.reshape(B_, Sp, Hkv * G, dv)[:, :S], fin, zfin
