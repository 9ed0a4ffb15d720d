"""An exact k-th largest with no sort: a float32's bits, flipped so that
their integer order is the float order, make a threshold a 32-bit
integer that a search over compares and row reductions settles. The
engine's sampler (``models/engine.py`` ``mask_logits_rowwise``: top-k and
nucleus cut-offs over the vocabulary; PR 33) and the indexed attention's
selection (``ops/sparse_attention.py``: the ``topk`` largest of a query's
index scores) both reach it here."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


# bits of a key one pass of ``_largest_key`` settles: its 2**bits - 1
# candidates share one read of the row. On a v5e the sampler takes
# 0.80 / 0.51 / 0.45 ms a step at [32, 100352] with 1 / 2 / 4; with 4
# every program that holds it loads 0.2 s slower (PERF.md, PR 33)
_SEARCH_BITS = 2


def _ordered_keys(x: jnp.ndarray) -> jnp.ndarray:
    """uint32 keys whose integer order is the float32 order of ``x``
    (``-0.0`` one below ``+0.0``)."""
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _key_value(keys: jnp.ndarray) -> jnp.ndarray:
    """The float32 an ordered key stands for."""
    b = jnp.where(keys >> 31 == 1, keys ^ jnp.uint32(1 << 31), ~keys)
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _largest_key(holds, rows: int) -> jnp.ndarray:
    """Per row the largest uint32 ``t`` with ``holds(t)``, for a
    ``holds`` ([rows] keys -> [rows] bool) that is true at 0 and, once
    false, stays false as ``t`` grows. The key is settled from its top
    bits down, ``_SEARCH_BITS`` a pass: every candidate of a pass is one
    compare and one row reduction over the same operands, which XLA
    fuses into one read of them. The passes stay a ``while``: laid out
    one after the other they save 0.1 ms a step at [32, 100352], and
    every program that holds the sampler then loads 0.2-0.35 s slower
    from the compile cache (PERF.md, PR 33)."""

    def settle(i, t):
        shift = (32 - _SEARCH_BITS * (i + 1)).astype(jnp.uint32)
        digit = sum(
            holds(t | (jnp.uint32(d) << shift)).astype(jnp.uint32)
            for d in range(1, 1 << _SEARCH_BITS)
        )
        return t | (digit << shift)

    return jax.lax.fori_loop(
        0, 32 // _SEARCH_BITS, settle, jnp.zeros((rows,), jnp.uint32)
    )


def kth_largest(scores: jnp.ndarray, k, valid=None) -> jnp.ndarray:
    """Per row of ``scores`` [..., N] float32 the ``k``-th largest VALUE
    among the entries ``valid`` [..., N] marks (all where None), as
    ``lax.top_k(row, k)[0][-1]`` would give it: ties count one each.
    ``k`` is an int or an int array that broadcasts against the rows. A
    row with fewer than ``k`` valid entries gives ``-inf``: every one of
    its entries is among its ``k`` largest."""
    lead, N = scores.shape[:-1], scores.shape[-1]
    rows = math.prod(lead)
    keys = _ordered_keys(scores.astype(jnp.float32)).reshape(rows, N)
    k = jnp.broadcast_to(jnp.asarray(k, jnp.int32), lead).reshape(rows)
    if valid is None:
        held = jnp.full((rows,), N, jnp.int32)
    else:
        valid = valid.reshape(rows, N)
        # key 0 is below every float's (-nan aside): never counted first
        keys = jnp.where(valid, keys, jnp.uint32(0))
        held = jnp.sum(valid, axis=-1, dtype=jnp.int32)
    kth = _key_value(_largest_key(
        lambda c: jnp.sum(keys >= c[:, None], axis=-1, dtype=jnp.int32) >= k,
        rows,
    ))
    return jnp.where(held < k, -jnp.inf, kth).reshape(lead)
