"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

No reference analog (DL4J 0.9.2 handles sequence scale with TBPTT +
masking only — SURVEY.md §5 "Long-context"); designed TPU-first per SURVEY
§7-M5: the sequence axis is sharded across devices, each device keeps its
local Q block resident, and K/V blocks rotate around the ICI ring via
``jax.lax.ppermute`` while the blockwise streaming-softmax accumulator
(ops.attention.blockwise_update — the same update rule the pallas flash
kernel uses on-chip) folds in one block per hop.  Communication overlaps
compute; peak memory is O(T/n) per device.

Use inside ``jax.shard_map`` with q/k/v sharded on the sequence axis, or
through ``ring_self_attention`` which sets that up from a Mesh.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import _NEG_INF, blockwise_update, causal_bias
from .mesh import vary_over

Array = jax.Array


def ring_attention(q: Array, k: Array, v: Array, axis_name: str,
                   *, causal: bool = False,
                   scale: Optional[float] = None) -> Array:
    """Blockwise attention with K/V rotating around the ``axis_name`` ring.

    Call INSIDE shard_map/pjit with q/k/v [B,H,T_local,D] sharded on the
    sequence axis.  Each of the n hops computes the local Q against the
    visiting K/V block with an online-softmax accumulator, then ppermutes
    the block to the next device.  Causal masking uses global block offsets
    derived from ``lax.axis_index``.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, t, d = q.shape

    # flatten batch×heads so the accumulator matches blockwise_update's [T,D]
    qf = q.reshape(b * h, t, d)

    def local_block(carry, step):
        acc, m, l, kk, vv = carry
        src = (my - step) % n          # global block index currently held
        bias = causal_bias(t, t, my * t, src * t) if causal else None

        kf = kk.reshape(b * h, t, d)
        vf = vv.reshape(b * h, t, d)
        upd = jax.vmap(
            functools.partial(blockwise_update, scale=scale, bias=bias))
        acc, m, l = upd(acc, m, l, qf, kf, vf)

        # rotate K/V to the next device (last hop's permute is still issued
        # to keep the loop shape static; XLA overlaps it with the epilogue)
        perm = [(i, (i + 1) % n) for i in range(n)]
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (acc, m, l, kk, vv), None

    # mark the zero-init accumulators as device-varying over every axis the
    # inputs vary on (shard_map's vma typing: the scan carry must match the
    # loop body's type) — q may additionally vary over data/model/pipe when
    # ring attention runs inside a larger manual region
    vary = tuple(set(jax.typeof(q).vma) | {axis_name})
    acc0 = vary_over(jnp.zeros((b * h, t, d), jnp.float32), vary)
    m0 = vary_over(jnp.full((b * h, t, 1), _NEG_INF, jnp.float32), vary)
    l0 = vary_over(jnp.zeros((b * h, t, 1), jnp.float32), vary)
    (acc, m, l, _, _), _ = jax.lax.scan(
        local_block, (acc0, m0, l0, k, v), jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(b, h, t, d).astype(q.dtype)


def ring_self_attention(q: Array, k: Array, v: Array, mesh: Mesh,
                        *, seq_axis: str = "seq", causal: bool = False,
                        scale: Optional[float] = None) -> Array:
    """Convenience wrapper: shard [B,H,T,D] q/k/v on ``seq_axis`` of
    ``mesh`` and run ring attention.  T must divide by the axis size."""
    n = mesh.shape[seq_axis]
    if q.shape[2] % n:
        raise ValueError(f"seq len {q.shape[2]} not divisible by seq axis {n}")
    spec = P(None, None, seq_axis, None)

    fn = shard_map(
        functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
