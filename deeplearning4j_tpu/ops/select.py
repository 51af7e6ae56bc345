"""Exact selection without a sort: the k-th largest of each row of a
table by compare-and-count.

The floats are taken to an order-preserving ``uint32`` image
(``sortable_keys``), and the threshold is built from the top
``WALK_BITS`` bits a pass (``walk``): a pass counts, in one read of the
table, the keys that reach each of the ``2^WALK_BITS - 1`` candidates.
32 / ``WALK_BITS`` passes settle it, whatever ``k`` is, and ``k`` may
differ by row.  On the chip a sort of a row (``jnp.sort``,
``lax.top_k``) costs several times that.

Used by the sparse attention's selection (models/sparse_gqa.py, over a
table given in parts) and by the sampler's top-k filter
(ops/sampling.py, ``kth_largest``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

#: bits of the key the threshold walk settles a pass
WALK_BITS = 2


def sortable_keys(score: Array) -> Array:
    """float32 -> uint32 whose unsigned order is the floats' order; never
    0, which marks a column that is no candidate."""
    bits = jax.lax.bitcast_convert_type(score.astype(jnp.float32), jnp.uint32)
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))
    return jnp.maximum(u, jnp.uint32(1))


def keys_to_float(keys: Array) -> Array:
    """``sortable_keys`` undone: the float32 a key stands for."""
    bits = jnp.where(keys >> 31 == 1, keys ^ jnp.uint32(0x80000000), ~keys)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def walk(n_bits: int, start: Array, fits) -> Array:
    """Digit by digit from the top: the largest value ``v`` of
    ``n_bits`` bits for which ``fits(v)`` holds (it holds for 0 and
    fails from some value on), by row (``start`` [rows], zeros of the
    values' type; ``fits`` takes and gives [rows])."""
    passes = -(-n_bits // WALK_BITS)

    def digit(i, v):
        shift = ((passes - 1 - i) * WALK_BITS).astype(v.dtype)
        out = v
        for d in range(1, 1 << WALK_BITS):
            cand = v | (jnp.asarray(d, v.dtype) << shift)
            out = jnp.where(fits(cand), cand, out)
        return out
    return jax.lax.fori_loop(0, passes, digit, start)


def kth_key(reach, rows: int, k) -> Array:
    """uint32 [rows]: the largest value that at least ``k`` keys of the
    row reach, which is its ``k``-th largest key.  ``reach(c)`` counts,
    by row, the keys ``>= c[:, None]``; ``k`` is an int or an int32
    [rows] array.  A row with fewer than ``k`` keys gets 0."""
    return walk(32, jnp.zeros((rows,), jnp.uint32), lambda c: reach(c) >= k)


def kth_largest(x: Array, k) -> Array:
    """The ``k``-th largest entry of each row of ``x`` [rows, n] float32
    (``k`` an int or int32 [rows], 1 <= k <= n), the value
    ``jnp.sort(row)[::-1][k - 1]`` has: ``-inf`` entries count, ties
    count once each, and ``-0.0`` may come back for ``+0.0`` (or the
    other way), which no float comparison tells apart."""
    keys = sortable_keys(x)
    return keys_to_float(kth_key(
        lambda c: jnp.sum(keys >= c[:, None], axis=-1, dtype=jnp.int32),
        x.shape[0], k))
