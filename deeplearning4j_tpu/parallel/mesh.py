"""Mesh + sharding utilities — the collectives layer (SURVEY.md §7 M0).

The reference's communication backends (libnd4j device copies +
`Nd4j.averageAndPropagate`, Aeron UDP VoidParameterServer — SURVEY.md §5
"Distributed communication backend") are replaced by a device mesh with
named axes; XLA GSPMD inserts the psum/all-gather/reduce-scatter collectives
that ride ICI intra-slice and DCN across slices.

Axis convention: ``data`` (DP), ``model`` (TP), ``seq`` (SP/CP),
``pipe`` (PP), ``dcn`` (cross-slice DP).  Build a mesh with the axes you
use; absent axes = size 1.

The two-tier interconnect is first-class: axes over devices WITHIN a TPU
slice ride the ICI (fast — dense collectives are free at that bandwidth),
while an outer ``dcn`` axis spans slices over the data-center network,
which is orders of magnitude slower — the tier where
``ShardedTrainer(grad_compression=...)`` swaps the dense psum for the
compressed exchange (ops/compression.py).  ``build_two_tier_mesh`` builds
the slice-major device layout so consecutive devices (ICI neighbors on
Cloud TPU) land in the same slice row.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
DCN_AXIS = "dcn"


def vary_over(x, axes):
    """Mark ``x`` as device-varying over the ``axes`` it isn't already
    varying on (shard_map vma typing for zero-init scan carries)."""
    need = tuple(a for a in axes if a not in jax.typeof(x).vma)
    return jax.lax.pcast(x, need, to="varying") if need else x


def build_mesh(axes: Optional[Dict[str, int]] = None,
               devices: Optional[Sequence] = None) -> Mesh:
    """Create a Mesh from {axis_name: size}.  Default: all local devices on
    the data axis (the ParallelWrapper-equivalent ceremony: one line).

    Sizes must multiply to the device count; use -1 for one inferred axis.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    axes = dict(axes) if axes else {DATA_AXIS: n}
    names = list(axes.keys())
    sizes = list(axes.values())
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = n // known
    if int(np.prod(sizes)) != n:
        raise ValueError(f"mesh axes {dict(zip(names, sizes))} != {n} devices")
    arr = np.asarray(devices).reshape(sizes)
    return Mesh(arr, names)


def build_two_tier_mesh(n_slices: int,
                        axes: Optional[Dict[str, int]] = None,
                        devices: Optional[Sequence] = None) -> Mesh:
    """Mesh with an OUTER ``dcn`` axis of ``n_slices`` plus inner ICI axes
    (default: all remaining devices on ``data``).

    The dcn axis is placed first so each slice's devices form one
    contiguous row — on Cloud TPU, ``jax.devices()`` orders devices
    slice-major, so the row boundary is the real ICI/DCN boundary.  Pair
    with ``ShardedTrainer(grad_compression=...)`` to compress the
    cross-slice gradient exchange; ``distributed.detect_num_slices()``
    reads the multislice runtime's slice count."""
    if n_slices < 1:
        raise ValueError(f"n_slices must be >= 1, got {n_slices}")
    inner = dict(axes) if axes else {DATA_AXIS: -1}
    if DCN_AXIS in inner:
        raise ValueError("pass the dcn size as n_slices, not in axes")
    return build_mesh({DCN_AXIS: n_slices, **inner}, devices)


def surviving_mesh(alive_slices: Sequence[int], n_slices: int,
                   axes: Optional[Dict[str, int]] = None,
                   devices: Optional[Sequence] = None) -> Mesh:
    """Two-tier mesh over the devices of the SURVIVING slices only —
    slice-granular recovery: when a host/slice leaves the membership, the
    job re-provisions a (possibly smaller ``dcn``) mesh over what's left
    instead of dying or waiting for the full pod to return.

    ``alive_slices`` are slice row indices into the ORIGINAL ``n_slices``
    slice-major device order (the layout ``build_two_tier_mesh`` assumes);
    inner axes default to ``data=-1`` over each slice's devices.  Restore
    the newest checkpoint after rebuilding — params placed for the old
    mesh don't transfer (``ElasticTrainer(rebuild_fn=...)`` wires both
    steps into one recovery)."""
    devs = list(devices if devices is not None else jax.devices())
    if n_slices < 1 or len(devs) % n_slices:
        raise ValueError(f"{len(devs)} devices not divisible into "
                         f"{n_slices} slices")
    alive = sorted(set(int(s) for s in alive_slices))
    if not alive:
        raise ValueError("no surviving slices — nothing to rebuild on")
    if alive[0] < 0 or alive[-1] >= n_slices:
        raise ValueError(f"alive slices {alive} out of range "
                         f"[0, {n_slices})")
    per = len(devs) // n_slices
    keep = [d for s in alive for d in devs[s * per:(s + 1) * per]]
    return build_two_tier_mesh(len(alive), axes, keep)


def put_global(arr, sharding: NamedSharding):
    """Place a host array onto a (possibly multi-process) sharding.

    Single-process: plain ``device_put``.  Multi-process: ``device_put``
    cannot target non-addressable devices, so the global array is built
    from per-shard callbacks — each process materializes only the rows its
    local devices own (replicated specs read the same full array
    everywhere).  Callers pass the GLOBAL array on every host; per-host
    disjoint loading composes via ``distributed.local_batch_slice``."""
    import numpy as np
    if jax.process_count() == 1:
        return jax.device_put(arr, sharding)
    a = np.asarray(arr)
    return jax.make_array_from_callback(a.shape, sharding, lambda idx: a[idx])


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(mesh: Mesh, batch_axis: str = DATA_AXIS):
    """Sharding for [batch, ...] arrays: batch split on the data axis."""
    return NamedSharding(mesh, P(batch_axis))


def _shard_leaf(mesh: Mesh, arr, model_axis: str, min_size: int = 2):
    """Tensor-parallel rule for one weight tensor: shard the largest axis
    divisible by the model-axis size; replicate if none divides.

    This is the generic Megatron-ish default — XLA GSPMD propagates the
    choice through the graph and inserts the all-gathers/reduce-scatters.
    Layer-specific overrides can refine it later without changing callers.
    """
    msize = mesh.shape.get(model_axis, 1)
    if msize <= 1 or arr.ndim == 0:
        return NamedSharding(mesh, P())
    # prefer trailing axes (output features) — weight layouts here are
    # [in, out] / HWIO, so the last axis is the output-feature axis
    for ax in reversed(range(arr.ndim)):
        if arr.shape[ax] % msize == 0 and arr.shape[ax] >= msize * min_size:
            spec = [None] * arr.ndim
            spec[ax] = model_axis
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def infer_param_shardings(params, mesh: Mesh, model_axis: str = MODEL_AXIS):
    """Pytree of NamedShardings for a params tree (TP rules, DP-replicated)."""
    return jax.tree_util.tree_map(lambda a: _shard_leaf(mesh, a, model_axis), params)
