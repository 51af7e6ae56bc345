"""Gradient compression kernels — the wire format of the DCN exchange tier.

Parity target: the reference's distributed trainer compresses gradients
before they touch the (slow) wire — ``EncodingHandler`` behind
``SharedTrainingMaster`` picks between ``thresholdEncode`` (sparse: one
signed int32 index per transmitted element, sign of the int = sign of the
update, magnitude = the threshold) and ``bitmapEncode`` (dense: 2 bits per
element) and keeps what it did NOT transmit in a residual accumulator that
is re-applied next step (error feedback — compression error never
disappears, it is deferred).

Here the slow wire is the DCN between TPU slices (ICI within a slice is
orders of magnitude faster — "Exploring the limits of Concurrency in ML
Training on Google TPUs"), so these kernels implement the cross-slice tier
of a two-tier exchange: dense psum over the ICI axis, then
``compressed_pmean`` over the ``dcn`` axis.  Everything is jit-able jnp
code; the exchange all_gathers the ENCODED buffers, so the collective
genuinely moves only the compressed bytes.

Two encodings, mirroring the reference's pair:

  threshold  — top-k-by-magnitude sparse encoding with a fixed capacity of
               ``n/16`` elements (the reference's threshold→bitmap
               switchover density).  Fixed ``threshold`` reproduces the
               reference exactly (transmit sign·threshold); the default
               adaptive mode (``threshold=None``) transmits sign·scale
               with scale = mean |selected| — a per-bucket, per-step
               live threshold that needs no tuning.
  bitmap     — 2 bits/element packed 16-to-a-uint32 ({0, +scale, -scale});
               adaptive scale = mean |g|.  Wire cost is shape-static
               (n/16 words), the right choice when gradients are dense.

Both are ~16x below f32 on the wire by construction, independent of the
gradient's actual sparsity — the property the bench gate asserts.
"""

from __future__ import annotations

import math
import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

#: opt-in one-pass fixed-threshold encode (sort-free select+pack; see
#: the "one-pass threshold encode" section).  Read once at import, like
#: ops/update_kernel.ENABLED — checked at TRACE time.
FUSED_ENCODE = os.environ.get("DL4J_TPU_FUSED_ENCODE", "0") == "1"
METHODS = ("threshold", "bitmap")
#: reference EncodingHandler default threshold (fixed-threshold mode)
DEFAULT_THRESHOLD = 1e-3
#: capacity of the threshold encoding: at most n/16 elements per message
#: (the reference switches to bitmapEncode above this density — beyond it
#: the sparse format is no longer smaller)
THRESHOLD_DENSITY_CAP = 1.0 / 16.0
#: 2-bit codes, 16 to a uint32 word
BITMAP_LANES = 16
#: bucket granularity of the exchange (see GradBucketer)
DEFAULT_BUCKET_BYTES = 4 << 20


def default_k_max(n: int) -> int:
    """Threshold-encoding message capacity for an n-element bucket."""
    # graftcheck: disable=GC101 (n is a STATIC bucket size known at trace time, not a traced value)
    return 0 if n == 0 else max(1, int(n * THRESHOLD_DENSITY_CAP))


# ---------------------------------------------------------------------------
# one-pass threshold encode (sort-free select + signed-index pack)
# ---------------------------------------------------------------------------
#
# Fixed-threshold mode does not need top_k's O(n log n) sort at all: the
# selection predicate (|g| >= t) is local, so each selected element's
# output slot is just the running count of selected elements before it —
# a cumsum — and the pack is one scatter.  The encoded SET is identical
# to the top_k path whenever at most k elements clear the threshold;
# entry ORDER differs (index-ascending vs magnitude-descending), which
# threshold_decode's scatter-add never observes — decode round-trips are
# bit-identical (every dense index receives the same +-scale entries,
# and partial sums of m·t are exact for integral m).  Overflow (> k
# selected) lax.cond's into the exact top_k path, keeping its
# largest-first selection.  Adaptive mode (threshold=None) genuinely
# needs the k-th order statistic and always uses top_k.

def _topk_pack(g, mag, k: int, threshold):
    """The reference-exact fixed-mode pack: top_k over the masked
    magnitudes (largest-first selection under overflow)."""
    vals, idx = jax.lax.top_k(jnp.where(mag >= threshold, mag, 0.0), k)
    valid = vals > 0.0
    sign = jnp.where(g[idx] >= 0, 1, -1).astype(jnp.int32)
    return jnp.where(valid, sign * (idx + 1), 0).astype(jnp.int32)


def _streaming_pack(g, mag, k: int, threshold: float, n: int):
    """One fused pass: slot = exclusive running count of selections.
    Precondition (caller's lax.cond): at most k elements clear t."""
    sel = mag >= threshold
    pos = jnp.cumsum(sel.astype(jnp.int32)) - 1
    payload = (jnp.where(g >= 0, 1, -1).astype(jnp.int32)
               * (jnp.arange(n, dtype=jnp.int32) + 1))
    slot = jnp.where(sel & (pos < k), pos, k)
    return jnp.zeros((k,), jnp.int32).at[slot].set(payload, mode="drop")


def _one_pass_threshold_encode(g, mag, k: int, threshold: float, n: int):
    """enc int32[k] via the sort-free path, falling back to the exact
    top_k pack inside lax.cond when more than k elements clear t."""
    count = jnp.sum((mag >= threshold).astype(jnp.int32))

    def fits(_):
        return _streaming_pack(g, mag, k, threshold, n)

    def overflow(_):
        return _topk_pack(g, mag, k, threshold)

    return jax.lax.cond(count <= k, fits, overflow, None)


# ---------------------------------------------------------------------------
# threshold encoding (reference thresholdEncode analog)
# ---------------------------------------------------------------------------

def threshold_encode(g, k_max: int, threshold: Optional[float] = None):
    """Encode a 1-D gradient into ``(enc int32[k], scale f32[])``.

    ``enc`` entries are ``sign(g)·(index+1)`` for the selected elements and
    0 for unused capacity — the reference's signed-index wire format, which
    carries sign and position in one int32.  The decoded value of every
    transmitted element is ``sign·scale``:

      threshold=None  (adaptive) — select the k_max largest |g|; scale =
        mean of the selected magnitudes (zero-magnitude elements are never
        selected, so an all-zero gradient encodes to an empty message)
      threshold=t     (reference-exact) — select only |g| >= t (capacity
        permitting, largest first); scale = t
    """
    n = 0 if g.ndim == 0 else g.shape[0]
    k = min(k_max, n)
    if n == 0 or k <= 0:
        return jnp.zeros((max(k_max, 0),), jnp.int32), jnp.zeros((), jnp.float32)
    g = g.astype(jnp.float32)
    mag = jnp.abs(g)
    if threshold is None:
        vals, idx = jax.lax.top_k(mag, k)
        valid = vals > 0.0
        scale = (jnp.sum(jnp.where(valid, vals, 0.0))
                 / jnp.maximum(jnp.sum(valid), 1))
    else:
        if threshold <= 0:
            raise ValueError(f"threshold must be > 0, got {threshold}")
        scale = jnp.asarray(threshold, jnp.float32)
        # the one-pass path takes a static threshold; a traced threshold
        # stays on the top_k path
        if FUSED_ENCODE and isinstance(threshold, (int, float)):
            # graftcheck: disable=GC101 (the isinstance guard above makes threshold a STATIC Python number here — a traced threshold takes the top_k branch)
            enc = _one_pass_threshold_encode(g, mag, k, float(threshold), n)
            return enc, scale
        return _topk_pack(g, mag, k, threshold), scale
    sign = jnp.where(g[idx] >= 0, 1, -1).astype(jnp.int32)
    enc = jnp.where(valid, sign * (idx + 1), 0).astype(jnp.int32)
    return enc, scale.astype(jnp.float32)


def threshold_decode(enc, scale, n: int):
    """Decode (and SUM) threshold messages back to a dense f32[n].

    Accepts one message (``enc [k]``, ``scale []``) or a stack of gathered
    messages (``enc [P, k]``, ``scale [P]``) — the scatter-add over all
    entries is exactly the sum-of-decodes the allreduce needs, with no
    [P, n] dense intermediate."""
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    enc = jnp.asarray(enc)
    scale_b = jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32)[..., None], enc.shape)
    # empty slots (enc == 0) map out of range and are dropped by the scatter
    idx = jnp.where(enc == 0, n, jnp.abs(enc) - 1).reshape(-1)
    val = (jnp.sign(enc).astype(jnp.float32) * scale_b).reshape(-1)
    return jnp.zeros((n,), jnp.float32).at[idx].add(val, mode="drop")


# ---------------------------------------------------------------------------
# bitmap encoding (reference bitmapEncode analog)
# ---------------------------------------------------------------------------

def bitmap_encode(g, threshold: Optional[float] = None):
    """Encode a 1-D gradient into ``(words uint32[ceil(n/16)], scale f32[])``.

    2-bit codes per element: 0 → not transmitted, 1 → +scale, 2 → -scale
    (code 3 reserved).  ``threshold=None`` uses the live scale mean |g|;
    a fixed threshold reproduces the reference's bitmapEncode."""
    n = 0 if g.ndim == 0 else g.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint32), jnp.zeros((), jnp.float32)
    g = g.astype(jnp.float32)
    mag = jnp.abs(g)
    if threshold is None:
        scale = jnp.mean(mag)
    else:
        if threshold <= 0:
            raise ValueError(f"threshold must be > 0, got {threshold}")
        scale = jnp.asarray(threshold, jnp.float32)
    sel = (mag >= scale) & (scale > 0)  # scale==0 ⇒ zero gradient ⇒ empty
    code = jnp.where(sel, jnp.where(g >= 0, 1, 2), 0).astype(jnp.uint32)
    pad = (-n) % BITMAP_LANES
    lanes = jnp.pad(code, (0, pad)).reshape(-1, BITMAP_LANES)
    shifts = (2 * jnp.arange(BITMAP_LANES, dtype=jnp.uint32))
    # codes occupy disjoint bit pairs, so the sum is a bitwise OR
    words = jnp.sum(lanes << shifts, axis=1, dtype=jnp.uint32)
    return words, scale.astype(jnp.float32)


def bitmap_decode(words, scale, n: int):
    """Decode (and SUM) bitmap messages back to a dense f32[n].

    Accepts ``words [W]`` / ``scale []`` or gathered ``words [P, W]`` /
    ``scale [P]``; leading axes are summed."""
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    words = jnp.asarray(words)
    shifts = (2 * jnp.arange(BITMAP_LANES, dtype=jnp.uint32))
    codes = (words[..., None] >> shifts) & jnp.uint32(3)          # [..., W, 16]
    codes = codes.reshape(codes.shape[:-2] + (-1,))[..., :n]      # [..., n]
    scale_b = jnp.asarray(scale, jnp.float32)[..., None]
    vals = jnp.where(codes == 1, 1.0,
                     jnp.where(codes == 2, -1.0, 0.0)) * scale_b
    if vals.ndim > 1:
        vals = jnp.sum(vals, axis=tuple(range(vals.ndim - 1)))
    return vals.astype(jnp.float32)


# ---------------------------------------------------------------------------
# the compressed collective
# ---------------------------------------------------------------------------

def compressed_pmean(g, axis_name: str, method: str = "threshold",
                     threshold: Optional[float] = None,
                     k_max: Optional[int] = None):
    """Compressed mean of a 1-D bucket over a mesh axis (use inside
    shard_map).  Encodes locally, ``all_gather``s the ENCODED buffers —
    the only bytes that cross the axis — then decode-sums.

    Returns ``(mean, local_decoded)``: the caller keeps
    ``g - local_decoded`` as its error-feedback residual (what this step
    failed to transmit, re-applied next step)."""
    if method not in METHODS:
        raise ValueError(f"method must be one of {METHODS}, got {method!r}")
    n = g.shape[0]
    p = jax.lax.axis_size(axis_name)
    if method == "threshold":
        k = k_max if k_max is not None else default_k_max(n)
        enc, scale = threshold_encode(g, k, threshold)
        decode = threshold_decode
    else:
        enc, scale = bitmap_encode(g, threshold)
        decode = bitmap_decode
    gathered = jax.lax.all_gather(enc, axis_name)      # [P, message]
    scales = jax.lax.all_gather(scale, axis_name)      # [P]
    local = decode(enc, scale, n)
    total = decode(gathered, scales, n)
    return total / p, local


# ---------------------------------------------------------------------------
# bucketing — the comm/compute overlap unit
# ---------------------------------------------------------------------------

class GradBucketer:
    """Partition a gradient pytree into fixed-size 1-D f32 buckets.

    Each bucket is encoded and exchanged as an independent collective, so
    XLA's latency-hiding scheduler can overlap bucket k's all_gather with
    bucket k+1's encode/decode and with the optimizer update — one fused
    whole-tree message would serialize the entire exchange behind the last
    gradient.  (The reference buckets the same way: EncodingHandler
    encodes per-parameter chunks into the Aeron send queue as they become
    ready.)  Boundaries are computed once from the params template; the
    same instance must flatten and unflatten, since bucket layout is part
    of the wire format."""

    def __init__(self, tree, bucket_bytes: int = DEFAULT_BUCKET_BYTES):
        leaves, self.treedef = jax.tree_util.tree_flatten(tree)
        self.shapes = [np.shape(l) for l in leaves]
        self.dtypes = [jnp.asarray(l).dtype for l in leaves]
        self.sizes = [int(np.prod(s)) for s in self.shapes]
        self.total = int(sum(self.sizes))
        per = max(1, int(bucket_bytes) // 4)
        self.bounds = [(s, min(s + per, self.total))
                       for s in range(0, self.total, per)]

    @property
    def n_buckets(self) -> int:
        return len(self.bounds)

    def bucket_sizes(self) -> List[int]:
        return [e - s for s, e in self.bounds]

    def flatten(self, tree) -> List:
        """tree (same structure as the template) → list of f32 buckets."""
        leaves = jax.tree_util.tree_leaves(tree)
        if not leaves:
            return []
        flat = jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves])
        return [flat[s:e] for s, e in self.bounds]

    def unflatten(self, buckets: List, cast: bool = True):
        """list of f32 buckets → tree.  ``cast=True`` restores each leaf's
        template dtype (gradients); ``cast=False`` keeps f32 (residuals
        must never round-trip through a lower-precision param dtype)."""
        if not buckets:
            return jax.tree_util.tree_unflatten(self.treedef, [])
        flat = jnp.concatenate(buckets)
        out, off = [], 0
        for shape, dtype, size in zip(self.shapes, self.dtypes, self.sizes):
            leaf = flat[off:off + size].reshape(shape)
            out.append(leaf.astype(dtype) if cast else leaf)
            off += size
        return jax.tree_util.tree_unflatten(self.treedef, out)


# ---------------------------------------------------------------------------
# analytic wire/overlap model (the pipeline_schedule_stats analog)
# ---------------------------------------------------------------------------

def encoded_message_bytes(n: int, method: str = "threshold",
                          k_max: Optional[int] = None) -> int:
    """Per-participant wire bytes of one bucket's encoded message
    (indices/words buffer + the f32 scale)."""
    if method not in METHODS:
        raise ValueError(f"method must be one of {METHODS}, got {method!r}")
    if n == 0:
        return 0
    if method == "threshold":
        k = k_max if k_max is not None else default_k_max(n)
        return 4 * min(k, n) + 4
    return 4 * math.ceil(n / BITMAP_LANES) + 4


def compression_stats(n_params: int, method: str = "threshold",
                      n_slices: int = 2,
                      bucket_bytes: int = DEFAULT_BUCKET_BYTES,
                      k_max: Optional[int] = None, itemsize: int = 4,
                      dcn_gbps: float = 25.0) -> dict:
    """Analytic DCN-tier accounting for an ``n_params`` model.

    Per-participant bytes on the wire per step:

      dense ring allreduce        ≈ 2 · itemsize · n      (reduce-scatter
                                    + all-gather phases)
      compressed ring all_gather  ≈ (P-1) · message_bytes (each rank's
                                    encoded message circulates to the
                                    other P-1 ranks)

    The ratio is ~16·2/(P-1) for both encodings — by construction, not by
    luck: threshold capacity is n/16 int32s, bitmap is n/16 uint32 words.
    ``*_exchange_ms`` divides by the DCN bandwidth for a per-step exposure
    estimate; with ``n_buckets`` independent collectives the scheduler can
    hide most of it behind remaining backward compute."""
    per = max(1, int(bucket_bytes) // 4)
    sizes = ([min(per, n_params - s) for s in range(0, n_params, per)]
             if n_params else [])
    dense = 2 * itemsize * n_params
    msg = sum(encoded_message_bytes(b, method, k_max) for b in sizes)
    compressed = max(1, n_slices - 1) * msg
    byte_rate = dcn_gbps * 1e9
    return {
        "method": method,
        "n_slices": n_slices,
        "n_buckets": len(sizes),
        "message_bytes_per_rank": msg,
        "dense_wire_bytes_per_step": dense,
        "compressed_wire_bytes_per_step": compressed,
        "wire_ratio": (dense / compressed) if compressed else float("inf"),
        "dense_exchange_ms": dense / byte_rate * 1e3,
        "compressed_exchange_ms": compressed / byte_rate * 1e3,
    }
