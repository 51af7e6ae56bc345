"""Ulysses sequence parallelism — all-to-all head-scatter / seq-gather.

No reference analog (DL4J 0.9.2 handles sequence scale with TBPTT +
masking only — SURVEY.md §5 "Long-context"); designed TPU-first per SURVEY
§7-M5 as the LOW-COMMUNICATION alternative to ring attention:

  ring:    n hops × ppermute of the full local K/V block — traffic
           O(T·D·H) per device per layer, overlapped with compute.
  ulysses: TWO all-to-alls per attention — q/k/v head-scatter+seq-gather
           in, output seq-scatter+head-gather out.  Traffic O(T·D·H/P)
           per device: a P-fold reduction, at the cost of requiring
           n_heads % P == 0 (heads are the scattered resource).

After the first all-to-all each device holds the FULL sequence for
n_heads/P heads, so the local attention is just ``flash_mha`` — the
pallas kernel, causal masking and key-padding masks all work unchanged.
(DeepSpeed-Ulysses, Jacobs et al. 2023, is the published pattern.)
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import flash_mha

Array = jax.Array


def ulysses_attention(q: Array, k: Array, v: Array, axis_name: str,
                      *, causal: bool = False,
                      scale: Optional[float] = None,
                      kmask: Optional[Array] = None) -> Array:
    """All-to-all attention — call INSIDE shard_map/pjit.

    q/k/v: [B, H, T_local, D] with the sequence axis sharded on
    ``axis_name`` (T_global = T_local · P).  ``kmask`` [B, T_local] is the
    local slice of the key-padding mask.  H must divide by the axis size.
    Returns [B, H, T_local, D] sharded the same way.
    """
    p = jax.lax.axis_size(axis_name)
    h = q.shape[1]
    if h % p:
        raise ValueError(f"n_heads {h} not divisible by '{axis_name}' axis "
                         f"size {p} — Ulysses scatters heads; use ring "
                         "attention for head counts below the axis size")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def a2a_in(x):
        # [B, H, T/P, D] → [B, H/P, T, D]: scatter heads, gather sequence
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = a2a_in(q), a2a_in(k), a2a_in(v)
    mg = None
    if kmask is not None:
        # every device needs the FULL key mask for its heads
        mg = jax.lax.all_gather(kmask, axis_name, axis=1, tiled=True)
    o = flash_mha(qg, kg, vg, causal, scale, kmask=mg)
    # [B, H/P, T, D] → [B, H, T/P, D]: gather heads back, scatter sequence
    return jax.lax.all_to_all(o, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def ulysses_self_attention(q: Array, k: Array, v: Array, mesh: Mesh,
                           *, seq_axis: str = "seq", causal: bool = False,
                           scale: Optional[float] = None,
                           kmask: Optional[Array] = None) -> Array:
    """Convenience wrapper: shard [B,H,T,D] q/k/v on ``seq_axis`` of
    ``mesh`` and run Ulysses attention.  T and n_heads must divide by the
    axis size.  Mirrors ``ring_self_attention`` — the two are drop-in
    alternatives behind the same calling convention."""
    n = mesh.shape[seq_axis]
    if q.shape[2] % n:
        raise ValueError(f"seq len {q.shape[2]} not divisible by seq axis {n}")
    spec = P(None, None, seq_axis, None)
    mspec = P(None, seq_axis)
    if kmask is None:
        fn = shard_map(
            functools.partial(ulysses_attention, axis_name=seq_axis,
                              causal=causal, scale=scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
        return fn(q, k, v)

    def body(q, k, v, m):
        return ulysses_attention(q, k, v, seq_axis, causal=causal,
                                 scale=scale, kmask=m)

    fn = shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, spec, mspec), out_specs=spec)
    return fn(q, k, v, kmask)
