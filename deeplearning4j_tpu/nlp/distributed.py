"""Distributed Word2Vec — data-parallel embedding training over a mesh.

Parity target: reference dl4j-spark-nlp (SparkWord2Vec /
Word2VecVariables: corpus sharded across executors, parameter averaging
of the word vectors each iteration) — the "Spark NLP" row of SURVEY §2.4.

TPU inversion: instead of Spark executors averaging parameters through
the driver, the PAIR BATCH is sharded over the mesh's data axis inside
``shard_map``; every shard computes UNSCALED scatter-add deltas plus
occurrence counts against the replicated tables, a ``psum`` merges both,
and the global occurrence-average is applied — mathematically identical
to the single-device update at any mesh size (numerically equal to
~1e-5; fp summation order differs), strictly stronger than Spark's
periodic parameter averaging, with the collective on ICI instead of the
driver network.  Multi-host: call parallel.distributed.initialize()
first and feed each host its corpus shard; the same program then spans
hosts.

Cost model: collectives are ROW-SPARSE — each flush all_gathers the
per-pair gradient rows and indices, O(B·D·(2+K)) wire traffic per batch
independent of vocabulary size (the round-2 dense-[V,D]-psum cap is
gone; at B=4096, K=5, D=128 that's ~15MB/flush whether V is 10³ or
10⁷).  The scatter-add into the replicated tables happens identically
on every device from the gathered global pair set, preserving exact
single-device occurrence-averaging semantics.

``DistributedWord2Vec(mesh=...)`` is a drop-in Word2Vec whose jitted
update runs sharded; with a 1-device mesh it reproduces the
single-device step.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .sequencevectors import _sg_pair_grads
from .word2vec import Word2Vec


def make_dp_sg_step(mesh: Mesh, data_axis: str = "data"):
    """Build the sharded skip-gram step: pairs split over ``data_axis``,
    tables replicated — with ROW-SPARSE collectives.

    Instead of psum'ing dense [V,D] delta tables (O(V·D) wire traffic per
    flush, the round-2 vocab cap), each shard all_gathers only its
    per-pair gradient ROWS and indices — O(B·D·(2+K)) traffic,
    independent of vocabulary size — and every device applies the
    identical global scatter-add with occurrence averaging.  Numerically
    this is the same sum-then-divide as the dense formulation (the
    scatter temp is local HBM, never communicated), so single-device
    semantics hold at any mesh size."""

    def shard_fn(syn0, syn1, centers, contexts, negatives, valid, lr):
        dv, du_flat, flat_t, flat_tw = _sg_pair_grads(
            syn0, syn1, centers, contexts, negatives, valid, lr)
        gather = lambda x: jax.lax.all_gather(x, data_axis, tiled=True)
        # pair-level rows+indices cross the wire, not [V,D] tables
        g_c = gather(centers)                        # [B]
        g_w = gather(valid)                          # [B]
        g_dv = gather(dv * valid[:, None])           # [B, D]
        g_t = gather(flat_t)                         # [B·(1+K)]
        g_tw = gather(flat_tw)                       # [B·(1+K)]
        g_du = gather(du_flat * flat_tw[:, None])    # [B·(1+K), D]
        n0 = jnp.zeros((syn0.shape[0],), jnp.float32).at[g_c].add(g_w)
        d0 = jnp.zeros_like(syn0).at[g_c].add(g_dv)
        n1 = jnp.zeros((syn1.shape[0],), jnp.float32).at[g_t].add(g_tw)
        d1 = jnp.zeros_like(syn1).at[g_t].add(g_du)
        syn0 = syn0 + d0 / jnp.maximum(n0, 1.0)[:, None].astype(syn0.dtype)
        syn1 = syn1 + d1 / jnp.maximum(n1, 1.0)[:, None].astype(syn1.dtype)
        return syn0, syn1

    # check_vma=False: the gathered pair set is identical on every device
    # (tiled all_gather), so the scatter-added tables ARE replicated — the
    # static varying-across-mesh inference just can't prove it; the
    # exact-parity tests (test_nlp_distributed.py) pin the semantics.
    sharded = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(), P(data_axis), P(data_axis), P(data_axis),
                  P(data_axis), P()),
        out_specs=(P(), P()), check_vma=False)
    return jax.jit(sharded, donate_argnums=(0, 1))


class DistributedWord2Vec(Word2Vec):
    """Word2Vec with the skip-gram update sharded over a mesh's data axis
    (reference SparkWord2Vec's role).  CBOW / hierarchical softmax fall
    back to the single-device step (parity with the reference, which
    distributes the skip-gram path)."""

    def __init__(self, mesh: Optional[Mesh] = None, data_axis: str = "data",
                 **kwargs):
        if kwargs.get("cbow") or kwargs.get("hierarchic_softmax"):
            raise NotImplementedError(
                "DistributedWord2Vec shards the skip-gram/negative-sampling "
                "path; use Word2Vec for CBOW/HS")
        super().__init__(**kwargs)
        if mesh is None:
            from ..parallel.mesh import build_mesh

            mesh = build_mesh({data_axis: len(jax.devices())})
        if data_axis not in mesh.shape:
            raise ValueError(f"mesh has no '{data_axis}' axis: {dict(mesh.shape)}")
        dp = mesh.shape[data_axis]
        if self.batch_size % dp:
            raise ValueError(f"batch_size {self.batch_size} not divisible by "
                             f"data axis size {dp}")
        self.mesh = mesh
        self.data_axis = data_axis
        self._dp_step = make_dp_sg_step(mesh, data_axis)
        # the sharded step has no multi-batch scan — dispatch one batch at a
        # time (chunks stays 1; see _sg_step's loud failure for chunks>1)
        self._device_batches = 1

    # SequenceVectors' flush calls _sg_neg_step via the module global; the
    # narrowest seam is overriding fit_sequences' step through this hook:
    def _sg_step(self, syn0, syn1, centers, contexts, negatives, valid, lr,
                 chunks=1):
        if chunks > 1:
            # micro-chunk scanning (DBOW label semantics) has no sharded
            # formulation here — fail loudly rather than silently average
            # consecutive label pairs away
            raise NotImplementedError(
                "DistributedWord2Vec does not support chunked sequential "
                "updates (chunks>1, used by DBOW label training)")
        return self._dp_step(syn0, syn1, centers, contexts, negatives,
                             valid, lr)
