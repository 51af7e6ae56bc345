"""Multi-head attention — XLA reference path + fused flash (pallas) kernel.

The reference (DL4J 0.9.2) has NO attention layer at all (SURVEY.md §5
"Long-context": closest analogs are TBPTT + mask propagation).  Long-context
support is therefore designed TPU-first per SURVEY §7-M5:

  - ``mha``: plain XLA einsum-softmax-einsum attention (the semantics
    oracle; XLA fuses it well at moderate sequence lengths).
  - ``flash_mha``: blockwise streaming-softmax attention as three pallas
    TPU kernels (forward, dk/dv, dq) — O(T) memory instead of O(T²), f32
    accumulation.  Each kernel's grid runs over (slices of batch*heads,
    blocks of one sequence axis[, parts of the other axis when it
    outgrows VMEM]); a grid step keeps the other axis resident in VMEM
    and walks it in chunks inside its body, up to the causal diagonal.
    How much a step holds is chosen from the shapes by ``flash_tiles``
    and logged once per shape at trace time.  At the benchmark's training
    call (bf16, B·H 192, T 1024, D 64) a layer's three calls take about
    1.9 ms where the 128 x 128-tile-a-step kernels took 15.4 (my chip
    runs, PR 26; docs/KERNELS.md).  Falls back to ``mha`` when shapes
    don't tile (and logs that it did when the backend is a tpu).
  - ``ring_attention`` (parallel/ring.py) reuses the same blockwise update
    rule across devices over the ``seq`` mesh axis.

Layout convention: [batch, heads, seq, head_dim] (BHTD).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_support import engaged, fell_back, interpret

Array = jax.Array

_NEG_INF = -1e30  # large-finite: keeps padded/causal-masked rows NaN-free


# ---------------------------------------------------------------------------
# shared layout/masking helpers
# ---------------------------------------------------------------------------


def causal_bias(tq: int, tk: int, q_off=0, k_off=0) -> Array:
    """Additive causal bias [tq, tk]: 0 where global q index ≥ global k
    index, large-negative otherwise.  Offsets may be traced values (ring
    attention passes per-device block offsets).  The single source of the
    causal-mask convention for mha / flash kernel / flash bwd / ring."""
    qi = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0) + q_off
    ki = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1) + k_off
    return jnp.where(qi >= ki, 0.0, _NEG_INF).astype(jnp.float32)


def split_heads(x: Array, n_heads: int) -> Array:
    """[B, T, H*D] → [B, H, T, D] (the framework's head-layout convention)."""
    b, t, dm = x.shape
    return x.reshape(b, t, n_heads, dm // n_heads).transpose(0, 2, 1, 3)


def merge_heads(x: Array) -> Array:
    """[B, H, T, D] → [B, T, H*D]."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


# ---------------------------------------------------------------------------
# XLA reference path
# ---------------------------------------------------------------------------


def mha(q: Array, k: Array, v: Array, *, causal: bool = False,
        mask: Optional[Array] = None, scale: Optional[float] = None) -> Array:
    """Plain attention: softmax(q·kᵀ/√d (+mask)) · v.

    q [B,H,T,D], k/v [B,H,S,D]; mask broadcastable to [B,H,T,S] with 1 =
    attend, 0 = blocked (DL4J mask convention).  Returns [B,H,T,D].
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        scores = scores + causal_bias(scores.shape[-2], scores.shape[-1])
    if mask is not None:
        scores = jnp.where(mask.astype(bool), scores, _NEG_INF)
    # accumulate the softmax in ≥f32 (bf16 inputs promote; f64 stays f64
    # so the float64 gradient-check suite is meaningful)
    acc_dtype = jnp.promote_types(scores.dtype, jnp.float32)
    p = jax.nn.softmax(scores.astype(acc_dtype), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v)


# ---------------------------------------------------------------------------
# blockwise streaming-softmax update (shared by flash kernel + ring attention)
# ---------------------------------------------------------------------------


def blockwise_update(acc, m, l, q, k, v, scale, bias=None):
    """One online-softmax accumulation step (Milakov & Gimelshein / Flash).

    acc [T,D] f32 un-normalized output, m [T,1] running max, l [T,1] running
    denominator.  Processes the (q, k-block) score tile and returns updated
    (acc, m, l).  Used on-chip by the pallas kernel and across chips by ring
    attention — one math, two transports.

    Matmul operands stay in the INPUT dtype (bf16 inputs → native-rate MXU
    passes; f32 casts would triple every matmul's MXU time) while both
    matmuls accumulate in f32 via preferred_element_type and all softmax
    statistics are f32 — the standard flash precision contract.  ``p`` is
    cast to v's dtype for the second matmul (identity for f32 inputs, so
    the f32 parity/gradient-check suites see unchanged numerics).
    """
    if q.dtype == jnp.float64:
        # f64 callers (ring-attention grad checks) run the matmuls at f32
        # with f32 statistics — the historical semantics of this function
        # (the fused-kernel path excludes f64 entirely, _fallback_reason)
        q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    nt = (((1,), (1,)), ((), ()))  # contract head_dim of both, no transpose
    s = jax.lax.dot_general(q, k, nt,
                            preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                                     # [T, S_blk]
    correction = jnp.exp(m - m_new)
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * correction + jnp.dot(p.astype(v.dtype), v,
                                         preferred_element_type=jnp.float32)
    return acc_new, m_new, l_new


# ---------------------------------------------------------------------------
# flash attention pallas kernels: tile geometry
# ---------------------------------------------------------------------------

_LANES = 128                 # lane width of a vreg: statistics are kept this wide
_VMEM_BUDGET = 12 * 2 ** 20  # bytes flash_tiles may plan for, under Mosaic's
#                              16 MiB default scoped-VMEM limit on the v5e
_MAX_BLOCK = 1024            # rows of a block or chunk, at most
_STEP_ROWS = 1024            # rows x heads of a grid step, at most
_MAX_HEADS = 8               # (batch*head) slices per grid step, at most
_DIAGONAL = 256              # rows of a sub-block of a chunk on the diagonal


class FlashTiles(NamedTuple):
    """What one grid step of a flash kernel holds (see ``flash_tiles``)."""
    rows: int          # rows of the blocked axis per grid step
    chunk: int         # rows of the walked axis per inner-loop chunk
    span: int          # rows of the walked axis resident in VMEM per step
    heads: int         # (batch*head) slices per grid step
    grid: tuple        # (BH // heads, blocked // rows, walked // span)
    vmem_bytes: int    # VMEM reckoned for the step (blocks, scratch, temps)

    @property
    def grid_steps(self) -> int:
        return math.prod(self.grid)


def _axis_blocks(n: int, sublane: int) -> tuple:
    """Block sizes a sequence axis of length ``n`` admits, largest first.

    Mosaic wants the last two dims of every block aligned to the dtype's
    (sublane, 128) tile or equal to the array's: the [heads, block, D]
    q/k/v blocks put ``block`` on sublanes, the [heads, 1, block] lse/mask
    rows put it on LANES.  So a block is a multiple of 128 rows, or — for
    short sequences — the whole axis in one sublane-aligned tile."""
    if n % _LANES == 0:
        return tuple(b for b in range(_MAX_BLOCK, 0, -_LANES) if n % b == 0)
    if n <= 512 and n % sublane == 0:
        return (n,)
    return ()


def _vmem_bytes(rows, chunk, span, heads, d, itemsize) -> int:
    """VMEM one grid step needs, by the largest of the three kernels: the
    pipeline double-buffers every block; a [*, D] tile occupies whole
    128-lane rows whatever D is.  The last term stands for the compiler's
    own temporaries: it streams a score tile through in row groups and
    keeps far less than the tile (read off the compiler by bisecting the
    limit it accepts, PR 26; the sum reads 10 to 40% over what it took)."""
    lanes = -(-d // _LANES) * _LANES
    walked = 2 * 2 * heads * span * lanes * itemsize       # k, v (or q, do)
    blocked = 4 * 2 * heads * rows * lanes * itemsize      # k v dk dv (q do dq)
    stat_rows = 3 * 2 * heads * 8 * max(rows, span) * 4    # lse, delta, mask
    scratch = heads * rows * (2 * lanes + 2 * _LANES) * 4  # f32 accumulators
    return (walked + blocked + stat_rows + scratch
            + 2 * 2 ** 20 + rows * chunk)


@functools.lru_cache(maxsize=None)
def flash_tiles(T: int, S: int, D: int, itemsize: int,
                BH: int) -> Optional[FlashTiles]:
    """Work of one grid step, from the shapes alone; None = does not tile.

    Every kernel blocks one sequence axis over the grid and WALKS the
    other inside its body: the forward and dq block the T queries and walk
    the S keys, dk/dv blocks the keys and walks the queries (it calls
    ``flash_tiles(S, T, ...)``).  A step holds ``rows`` blocked rows of
    ``heads`` (batch*head) slices, keeps ``span`` walked rows of them
    resident in VMEM (the whole axis when it fits ``_VMEM_BUDGET`` — 128 KB
    a head for K or V at T=1024, d=64, bf16 — else the largest part that
    does, and the grid gets an outer walked-major dimension), and works
    through them ``chunk`` rows at a time.

    Why not 128 x 128 tiles one to a grid step (what ran until PR 26): a
    grid step costs about 0.4 us of pipeline bookkeeping whatever it
    computes, and a 128 x 128 x 64 tile is 0.02 us of MXU time; at
    (192, 1024, 64) that was 12,288 steps a call, 5.6 ms against 0.13 ms of
    arithmetic (ledger, PR 25).  What ``scripts/chip_probe.py tiles``
    timed on the v5e (my chip runs, PR 26; docs/KERNELS.md has the table):
    blocks and chunks as large as divide the axis, up to 1024 — every pass
    over a chunk costs each 8 query rows about as much again as 3 vregs of
    scores, so at T=1024 one 1024 x 1024 tile a head beats the four
    256 x 512 ones that skip the dead half (forward 1.19 against 1.66 ms);
    at T=2048 and 4096 sizes from 512 up time within 4% of each other.
    Several slices a step only while rows x heads <= ``_STEP_ROWS``: the
    body is unrolled over them, which lets one slice's products overlap
    another's softmax (T=128: 8 slices 0.73 ms against 1.19 for one) and
    buys nothing once a slice alone fills the step."""
    sublane = 8 * max(1, 4 // itemsize)
    rows_ok = _axis_blocks(T, sublane)
    chunk_ok = _axis_blocks(S, sublane)
    if not (rows_ok and chunk_ok):
        return None
    fits = lambda rows, chunk, span, heads: _vmem_bytes(
        rows, chunk, span, heads, D, itemsize) <= _VMEM_BUDGET
    # the walked axis whole if a block and chunk of at least half the
    # largest admit it (K and V are then fetched once a slice) ...
    whole = next(((r, c) for r in rows_ok for c in chunk_ok
                  if 2 * r >= rows_ok[0] and 2 * c >= chunk_ok[0]
                  and fits(r, c, S, 1)), None)
    if whole:
        (rows, chunk), span = whole, S
    else:   # ... else the largest tiles, and the largest part that fits
        rows, chunk = next(((r, c) for r in rows_ok for c in chunk_ok
                            if fits(r, c, c, 1)), (rows_ok[-1], chunk_ok[-1]))
        span = next((S // n for n in range(2, S // chunk + 1)
                     if S % n == 0 and (S // n) % chunk == 0
                     and fits(rows, chunk, S // n, 1)), chunk)
    heads = next(h for h in range(min(BH, _MAX_HEADS), 0, -1)
                 if h == 1 or (BH % h == 0 and rows * h <= _STEP_ROWS
                               and fits(rows, chunk, span, h)))
    return FlashTiles(rows, chunk, span, heads,
                      (BH // heads, T // rows, S // span),
                      _vmem_bytes(rows, chunk, span, heads, D, itemsize))


# ---------------------------------------------------------------------------
# flash attention pallas kernels: bodies
# ---------------------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))   # contract head_dim of both, no transpose


def _lanes(x: Array, n: int) -> Array:
    """A lane-replicated statistic [..., 128] at width ``n``: every lane
    of a row holds the same number, so widening is a copy of whole vregs
    and never a one-lane broadcast."""
    w = x.shape[-1]
    if n == w:
        return x
    if n < w:
        return x[..., :n]
    if n % w == 0:
        return jnp.tile(x, (1,) * (x.ndim - 1) + (n // w,))
    return jnp.broadcast_to(x[..., :1], x.shape[:-1] + (n,))


def _walk(body, carry, *segments):
    """Run ``body(c, carry, masked=...)`` over each ``(lo, hi, masked)``
    segment of chunk indices in turn; bounds may be traced (they follow
    the causal diagonal).  A segment known empty at trace time is left
    out of the program."""
    for lo, hi, masked in segments:
        static = isinstance(lo, int) and isinstance(hi, int)
        if static and hi - lo <= 1:   # no loop: the chunk's offset is static
            if hi > lo:
                carry = body(lo, carry, masked=masked)
            continue
        carry = jax.lax.fori_loop(
            lo, hi, functools.partial(body, masked=masked), carry)
    return carry


def _causal_diff(n_q: int, n_k: int, transposed: bool = False) -> Array:
    """Key index minus query index over a [n_q, n_k] score tile (or its
    [n_k, n_q] transpose), both counted from the tile's corner: the causal
    rule keeps an entry where this is at most how far the tile's first
    query lies after its first key."""
    shape, (qd, kd) = ((n_k, n_q), (1, 0)) if transposed else ((n_q, n_k), (0, 1))
    return (jax.lax.broadcasted_iota(jnp.int32, shape, kd)
            - jax.lax.broadcasted_iota(jnp.int32, shape, qd))


def _chunk_start(c, chunk: int):
    """Row offset of chunk ``c``, with its alignment said to Mosaic."""
    return c * chunk if isinstance(c, int) else pl.multiple_of(c * chunk, chunk)


def _static_walk(first, base, chunk, n_chunks) -> bool:
    """Whether a causal walk is known at trace time: one chunk, which
    starts where the blocked rows do (both offsets the int 0) or cannot be
    sliced at a traced offset (a whole axis that is no multiple of 128)."""
    return (n_chunks == 1 and isinstance(base, int)
            and (isinstance(first, int) or chunk % _LANES != 0))


def _key_walk_bounds(causal, q0, rows, k_base, chunk, n_chunks):
    """For ``rows`` queries from ``q0`` walking ``n_chunks`` key chunks
    from ``k_base``: (first masked chunk, end of the live chunks).  Chunks
    before the first lie wholly under the diagonal; chunks from the end on
    lie wholly above it and are never touched.  An offset is the int 0
    where its axis is one block."""
    if not causal:
        return n_chunks, n_chunks
    if _static_walk(q0, k_base, chunk, n_chunks):
        return 0, 1     # the one chunk holds key 0, which every query sees
    n_full = jnp.clip((q0 + 1 - k_base) // chunk, 0, n_chunks)
    n_live = jnp.clip((q0 + rows - k_base + chunk - 1) // chunk, 0, n_chunks)
    return n_full, n_live


def _diagonal_blocks(tiles) -> int:
    """Rows of the sub-blocks a chunk that the causal diagonal crosses
    corner to corner is worked in: sub-block r then needs only the first
    r+1 sub-blocks of the chunk's columns, a static extent, and the rest
    of the square is never computed (at T=1024 in one 1024 x 1024 tile a
    head: forward 0.97 against 1.11 ms, dk/dv 1.37 against 1.75, dq 1.06
    against 1.36; sub-blocks of 128 and 512 rows time 3% and 6% behind in
    the sum; my chip runs, PR 26).  Only where block and chunk are one
    size does every masked chunk lie so; 0 = work masked chunks whole."""
    if tiles.rows == tiles.chunk and tiles.rows % _DIAGONAL == 0 \
            and tiles.rows > _DIAGONAL:
        return _DIAGONAL
    return 0


def _carry_through(scratch, outer, heads, start, walk, finish):
    """The frame the three kernels share.  For each of the step's
    ``heads`` slices (unrolled: their work can overlap), ``walk(h, carry)``
    takes the carried values from ``start`` through this step's part of
    the walked axis and ``finish(h, carry)`` writes the results.  Where
    the walked axis comes in parts (``outer`` is the grid's index over
    them, ``scratch`` holds one [heads, rows, w] buffer per carried value)
    the values rest in scratch between parts and ``finish`` runs on the
    last; where it is whole there is no scratch and no round trip."""
    if not scratch:
        for h in range(heads):
            finish(h, walk(h, start))
        return

    @pl.when(outer == 0)
    def _init():
        for ref, x in zip(scratch, start):
            ref[...] = jnp.broadcast_to(x, ref.shape)

    for h in range(heads):
        for ref, x in zip(scratch, walk(h, tuple(ref[h] for ref in scratch))):
            ref[h] = x

    @pl.when(outer == pl.num_programs(2) - 1)
    def _finish():
        for h in range(heads):
            finish(h, tuple(ref[h] for ref in scratch))


def _flash_fwd_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref,
                      *scratch, scale, causal, tiles):
    """Grid (BH/heads, T/rows, S/span).  A step holds ``rows`` queries of
    ``heads`` slices and the ``span`` keys and values they may see (all of
    them unless S outgrows VMEM), and walks the keys ``chunk`` at a time up
    to the causal diagonal: no grid step, K/V copy or arithmetic is spent
    above it, only the chunks the diagonal passes through build a mask, and
    a chunk it crosses corner to corner is worked in row sub-blocks that
    stop at it (``_diagonal_blocks``).  The running (acc, m, l) are values
    carried through the walk (``_carry_through``); m and l are 128 lanes
    wide, every lane the same.  Also emits the log-sum-exp per query row
    (what the backward needs to rebuild p).  ``km_ref`` is the optional
    [heads, 1, span] key-padding mask (1 = attend)."""
    rows, chunk, span = tiles.rows, tiles.chunk, tiles.span
    d = q_ref.shape[-1]
    qi, kj = pl.program_id(1), pl.program_id(2)
    q0 = qi * rows if tiles.grid[1] > 1 else 0
    k_base = kj * span if tiles.grid[2] > 1 else 0
    n_full, n_live = _key_walk_bounds(causal, q0, rows, k_base, chunk,
                                      span // chunk)
    sub = _diagonal_blocks(tiles)
    if causal and not sub:
        diff = _causal_diff(rows, chunk)

    def walk(h, carry):
        q = q_ref[h]

        def update(carry, q, k0, width, keep):
            """The online-softmax step of blockwise_update on keys
            [k0, k0 + width), m and l 128 lanes wide."""
            acc, m, l = carry
            k = k_ref[h, pl.ds(k0, width), :]
            v = v_ref[h, pl.ds(k0, width), :]
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32) * scale
            if keep is not None:
                s = jnp.where(keep, s, _NEG_INF)
            if km_ref is not None:
                s = jnp.where(km_ref[h, :, pl.ds(k0, width)] != 0, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, width))
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * _lanes(alpha, d) + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            return acc_new, m_new, l_new

        def step(c, carry, masked):
            k0 = _chunk_start(c, chunk)
            if not masked:
                return update(carry, q, k0, chunk, None)
            if not sub:
                return update(carry, q, k0, chunk, diff <= q0 - k_base - k0)
            parts = [update(tuple(x[r:r + sub] for x in carry), q[r:r + sub],
                            k0, r + sub, _causal_diff(sub, r + sub) <= r)
                     for r in range(0, rows, sub)]   # rows r.. see keys ..r+sub
            return tuple(jnp.concatenate(xs, axis=0) for xs in zip(*parts))

        return _walk(step, carry, (0, n_full, False), (n_full, n_live, True))

    def finish(h, carry):
        acc, m, l = carry
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[h] = (acc / _lanes(l_safe, d)).astype(o_ref.dtype)
        # Rows that never saw a live key (m still at the −LARGE init; note
        # l is NOT 0 there — every masked score is exactly −LARGE, so p=1
        # per entry) take lse = +LARGE: the backward's p = exp(s − lse)
        # then reconstructs to 0, i.e. flash's convention is ZERO gradients
        # for fully-masked rows (see _xla_attention_bwd for the rationale
        # and the mha difference).
        lse = jnp.where(m > _NEG_INF / 2, m + jnp.log(l_safe), 1e30)
        lse_ref[h, 0] = lse[:, 0].astype(lse_ref.dtype)

    start = (jnp.zeros((rows, d), jnp.float32),
             jnp.full((rows, _LANES), _NEG_INF, jnp.float32),
             jnp.zeros((rows, _LANES), jnp.float32))
    _carry_through(scratch, kj, tiles.heads, start, walk, finish)


def _vma(x):
    """Varying-across-mesh-axes of ``x`` (frozenset; empty outside
    shard_map) — pallas out_shapes must carry it so the kernels trace
    under shard_map's check_vma (ulysses/pipelined attention)."""
    return jax.typeof(x).vma


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s vma."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=_vma(like))


def _fallback_reason(q, tiles: Optional[FlashTiles]) -> Optional[str]:
    """Why the XLA path runs instead of the kernel (None = kernel runs).
    The kernel targets the TPU memory spaces: compiled on tpu, interpreted
    on cpu (tests), plain XLA elsewhere (gpu).  f64 also falls back: the
    kernel accumulates in f32 VMEM scratch, which would silently degrade
    float64 gradient checks.

    CPU + varying-across-mesh operands (inside shard_map) also fall back:
    jax 0.9's pallas HLO *interpreter* emits invariant slice indices
    against the varying operand, which shard_map's check_vma rightly
    rejects — the compiled TPU kernel carries vma through its out_shapes
    and passes the check, so only the interpreter needs the escape."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        return f"backend {backend}"
    if backend == "cpu" and _vma(q):
        return "pallas interpreter under shard_map"
    if q.dtype == jnp.float64:
        return "float64 inputs"
    if tiles is None:
        return "sequence length does not tile (see flash_tiles)"
    return None


def _blocked_spec(tiles, d):
    """[heads, rows, D] block of the axis the grid blocks."""
    return pl.BlockSpec((tiles.heads, tiles.rows, d), lambda b, i, j: (b, i, 0))


def _blocked_row_spec(tiles):
    """[heads, 1, rows] statistic/mask row of the blocked axis."""
    return pl.BlockSpec((tiles.heads, 1, tiles.rows), lambda b, i, j: (b, 0, i))


def _walked_specs(tiles, d, live=None):
    """([heads, span, D], [heads, 1, span]) blocks of the walked axis.
    Under the causal mask ``live(i)`` gives the first and last outer
    walked index that blocked index ``i`` touches; a dead one is mapped to
    the nearest live one, whose block is already resident — the pipeline
    copies nothing for a repeated block index."""
    last = tiles.grid[2] - 1
    if live is None or last == 0:
        at = lambda i, j: j
    else:
        def at(i, j):
            lo, hi = live(i)
            return jnp.clip(j, jnp.minimum(lo, last), jnp.minimum(hi, last))
    return (pl.BlockSpec((tiles.heads, tiles.span, d),
                         lambda b, i, j: (b, at(i, j), 0)),
            pl.BlockSpec((tiles.heads, 1, tiles.span),
                         lambda b, i, j: (b, 0, at(i, j))))


def _carried(tiles, *widths):
    """Scratch for what a walk carries ([heads, rows, width] f32 each):
    needed only across an outer walked dimension."""
    if tiles.grid[2] == 1:
        return []
    return [pltpu.VMEM((tiles.heads, tiles.rows, w), jnp.float32)
            for w in widths]


def _keys_seen(tiles, causal):
    """``live`` of ``_walked_specs`` for a kernel that blocks the queries:
    a query block sees the key blocks up to the one its last row is in."""
    if not causal:
        return None
    return lambda i: (0, (i * tiles.rows + tiles.rows - 1) // tiles.span)


def _queries_seeing(tiles, causal):
    """``live`` of ``_walked_specs`` for the kernel that blocks the keys:
    a key block is seen from the query block its first row is in, on."""
    if not causal:
        return None
    return lambda i: ((i * tiles.rows) // tiles.span, tiles.grid[2] - 1)


def _without_mask(kernel, n_in: int):
    """``kernel`` for a call with no key-padding mask: its mask ref, which
    follows the first ``n_in`` refs, is None."""
    def masked_none(*refs):
        kernel(*refs[:n_in], None, *refs[n_in:])
    return masked_none


def _flat_kmask(kmask, H):
    """[B, S] key-padding mask → int32 [B*H, 1, S], one row a slice, so a
    grid step's ``heads`` slices need not share a batch entry."""
    return jnp.repeat(kmask.astype(jnp.int32), H, axis=0)[:, None, :]


def _flash_forward(q: Array, k: Array, v: Array, kmask, causal: bool,
                   scale: float):
    """→ (o [B,H,T,D], lse [B*H,1,T] or None-on-fallback)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    tiles = flash_tiles(T, S, D, q.dtype.itemsize, B * H)
    why = _fallback_reason(q, tiles)
    if why:
        fell_back(f"flash_mha[T={T},S={S},{q.dtype}]", why)
        m = None if kmask is None else kmask[:, None, None, :]
        return mha(q, k, v, causal=causal, mask=m, scale=scale), None
    engaged(f"flash_mha[BH={B * H},T={T},S={S},D={D},{q.dtype}]", str(tiles))

    kv_spec, km_spec = _walked_specs(tiles, D, _keys_seen(tiles, causal))
    kernel = functools.partial(_flash_fwd_kernel, scale=scale, causal=causal,
                               tiles=tiles)
    in_specs = [_blocked_spec(tiles, D), kv_spec, kv_spec]
    args = [q.reshape(B * H, T, D), k.reshape(B * H, S, D),
            v.reshape(B * H, S, D)]
    if kmask is not None:
        in_specs.append(km_spec)
        args.append(_flat_kmask(kmask, H))
    else:
        kernel = _without_mask(kernel, 3)

    out, lse = pl.pallas_call(
        kernel,
        grid=tiles.grid,
        in_specs=in_specs,
        out_specs=[_blocked_spec(tiles, D), _blocked_row_spec(tiles)],
        out_shape=[
            _out_struct((B * H, T, D), q.dtype, q),
            _out_struct((B * H, 1, T), jnp.float32, q),
        ],
        scratch_shapes=_carried(tiles, D, _LANES, _LANES),
        interpret=interpret(),
    )(*args)
    return out.reshape(B, H, T, D), lse


# ---------------------------------------------------------------------------
# fused backward kernels (FlashAttention-2 style, O(T) memory)
# ---------------------------------------------------------------------------


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         km_ref, dq_ref, *scratch, scale, causal, tiles):
    """Grid (BH/heads, T/rows, S/span): the forward's walk over the keys,
    with p rebuilt per chunk from the saved lse (no [T,S] materialization)
    and dq the value carried through it.  Masking is where()-style to
    match the XLA oracle: no gradient flows through blocked score entries
    (p, and ds with it, is exactly 0 there).  Fully masked rows carry the
    lse=+LARGE sentinel from the forward, so p — and with it every
    gradient — is exactly 0 for them.  The lse and delta rows are turned
    into 128-lane columns once per slice, outside the walk."""
    rows, chunk, span = tiles.rows, tiles.chunk, tiles.span
    qi, kj = pl.program_id(1), pl.program_id(2)
    q0 = qi * rows if tiles.grid[1] > 1 else 0
    k_base = kj * span if tiles.grid[2] > 1 else 0
    n_full, n_live = _key_walk_bounds(causal, q0, rows, k_base, chunk,
                                      span // chunk)
    sub = _diagonal_blocks(tiles)
    if causal and not sub:
        diff = _causal_diff(rows, chunk)

    def walk(h, carry):
        column = lambda ref: jnp.broadcast_to(ref[h, 0][:, None],
                                              (rows, _LANES))
        rows_of = (q_ref[h], g_ref[h], column(lse_ref), column(delta_ref))

        def update(dq, q, g, lse, delta, k0, width, keep):
            k = k_ref[h, pl.ds(k0, width), :]
            v = v_ref[h, pl.ds(k0, width), :]
            s = jax.lax.dot_general(
                q, k, _NT, preferred_element_type=jnp.float32) * scale
            if km_ref is not None:
                km = km_ref[h, :, pl.ds(k0, width)] != 0
                keep = km if keep is None else keep & km
            if keep is not None:
                s = jnp.where(keep, s, _NEG_INF)
            p = jnp.exp(s - _lanes(lse, width))
            dp = jax.lax.dot_general(
                g, v, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - _lanes(delta, width)) * scale   # 0 where p is
            return dq + jnp.dot(ds.astype(k.dtype), k,
                                preferred_element_type=jnp.float32)

        def step(c, dq, masked):
            k0 = _chunk_start(c, chunk)
            if not masked:
                return update(dq, *rows_of, k0, chunk, None)
            if not sub:
                return update(dq, *rows_of, k0, chunk,
                              diff <= q0 - k_base - k0)
            return jnp.concatenate([
                update(dq[r:r + sub], *(x[r:r + sub] for x in rows_of), k0,
                       r + sub, _causal_diff(sub, r + sub) <= r)
                for r in range(0, rows, sub)], axis=0)

        return (_walk(step, carry[0],
                      (0, n_full, False), (n_full, n_live, True)),)

    def finish(h, carry):
        dq_ref[h] = carry[0].astype(dq_ref.dtype)

    start = (jnp.zeros((rows, q_ref.shape[-1]), jnp.float32),)
    _carry_through(scratch, kj, tiles.heads, start, walk, finish)


def _flash_bwd_dkdv_kernel(k_ref, v_ref, q_ref, g_ref, lse_ref, delta_ref,
                           km_ref, dk_ref, dv_ref, *scratch,
                           scale, causal, tiles):
    """Grid (BH/heads, S/rows, T/span): a step holds ``rows`` keys and
    values and walks the queries that see them, from the causal diagonal
    down.  The score tile is built TRANSPOSED, sᵀ = k·qᵀ [rows, chunk]:
    the saved lse and delta rows then broadcast along sublanes as they
    lie in HBM, and dv = pᵀ·do and dk = dsᵀ·q are plain products — no
    operand is transposed in the walk.  dk and dv are the values carried
    through it.  A chunk the
    diagonal crosses corner to corner is worked in key sub-blocks, each
    from its own first query on (``_diagonal_blocks``)."""
    rows, chunk, span = tiles.rows, tiles.chunk, tiles.span
    ki, qj = pl.program_id(1), pl.program_id(2)
    k0 = ki * rows if tiles.grid[1] > 1 else 0
    q_base = qj * span if tiles.grid[2] > 1 else 0
    n_chunks = span // chunk
    c_live = c_full = 0
    if causal:
        # chunks before c_live lie wholly above the diagonal, chunks from
        # c_full on wholly under it
        if _static_walk(k0, q_base, chunk, n_chunks):
            c_full = 1  # the one chunk holds the last query, which sees all
        else:
            c_live = jnp.clip((k0 - q_base) // chunk, 0, n_chunks)
            c_full = jnp.clip((k0 + rows - 1 - q_base + chunk - 1) // chunk,
                              0, n_chunks)
    sub = _diagonal_blocks(tiles)
    if causal and not sub:
        diff = _causal_diff(chunk, rows, transposed=True)

    def walk(h, carry):
        k, v = k_ref[h], v_ref[h]
        km = None
        if km_ref is not None:   # this step's keys, down the sublanes
            km = jnp.broadcast_to(km_ref[h, 0][:, None], (rows, _LANES)) != 0

        def update(carry, k, v, km, c0, width, keep):
            """Queries [c0, c0 + width) against the keys ``k``."""
            dk, dv = carry
            q = q_ref[h, pl.ds(c0, width), :]
            g = g_ref[h, pl.ds(c0, width), :]
            s = jax.lax.dot_general(
                k, q, _NT, preferred_element_type=jnp.float32) * scale
            if km is not None:
                kmw = _lanes(km, width)
                keep = kmw if keep is None else keep & kmw
            if keep is not None:
                s = jnp.where(keep, s, _NEG_INF)
            p = jnp.exp(s - lse_ref[h, :, pl.ds(c0, width)])
            dv = dv + jnp.dot(p.astype(g.dtype), g,
                              preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(
                v, g, _NT, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[h, :, pl.ds(c0, width)]) * scale
            dk = dk + jnp.dot(ds.astype(q.dtype), q,
                              preferred_element_type=jnp.float32)
            return dk, dv

        def step(c, carry, masked):
            c0 = _chunk_start(c, chunk)
            if not masked:
                return update(carry, k, v, km, c0, chunk, None)
            if not sub:
                return update(carry, k, v, km, c0, chunk,
                              diff <= q_base + c0 - k0)
            parts = []
            for r in range(0, rows, sub):   # keys r.. are seen from query r on
                at = c0 + r if isinstance(c0, int) else pl.multiple_of(c0 + r, sub)
                parts.append(update(
                    tuple(x[r:r + sub] for x in carry), k[r:r + sub],
                    v[r:r + sub], None if km is None else km[r:r + sub], at,
                    rows - r, _causal_diff(rows - r, sub, transposed=True) <= 0))
            return tuple(jnp.concatenate(xs, axis=0) for xs in zip(*parts))

        return _walk(step, carry,
                     (c_live, c_full, True), (c_full, n_chunks, False))

    def finish(h, carry):
        dk, dv = carry
        dk_ref[h] = dk.astype(dk_ref.dtype)
        dv_ref[h] = dv.astype(dv_ref.dtype)

    zero = jnp.zeros((rows, k_ref.shape[-1]), jnp.float32)
    _carry_through(scratch, qj, tiles.heads, (zero, zero), walk, finish)


def _flash_backward(q, k, v, kmask, o, lse, g, causal, scale):
    """Fused O(T)-memory backward: rebuild p per chunk from lse.  Falls
    back to the XLA recompute path when the forward did (lse is None)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    if lse is None:   # the forward fell back (and said so on tpu)
        return _xla_attention_bwd(q, k, v, kmask, g, causal, scale)
    isz, BH = q.dtype.itemsize, B * H

    flat = lambda x: x.reshape(BH, *x.shape[2:])
    qf, kf, vf, gf = flat(q), flat(k), flat(v), flat(g)
    # delta_i = Σ_d g_i·o_i — the softmax-jacobian row term (Dao 2023 eq. 4)
    delta = jnp.sum(gf.astype(jnp.float32) * flat(o).astype(jnp.float32),
                    axis=-1)[:, None, :]                       # [BH, 1, T]
    kmi = None if kmask is None else _flat_kmask(kmask, H)
    interp = interpret()

    # dk/dv: blocks the keys, walks the queries
    tiles = flash_tiles(S, T, D, isz, BH)
    qg_spec, row_spec = _walked_specs(tiles, D, _queries_seeing(tiles, causal))
    base = functools.partial(_flash_bwd_dkdv_kernel, scale=scale,
                             causal=causal, tiles=tiles)
    kv_spec = _blocked_spec(tiles, D)
    in_specs = [kv_spec, kv_spec, qg_spec, qg_spec, row_spec, row_spec]
    args = [kf, vf, qf, gf, lse, delta]
    if kmi is not None:
        in_specs.append(_blocked_row_spec(tiles))
        args.append(kmi)
    dk, dv = pl.pallas_call(
        base if kmi is not None else _without_mask(base, 6),
        grid=tiles.grid,
        in_specs=in_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[_out_struct((BH, S, D), k.dtype, k),
                   _out_struct((BH, S, D), v.dtype, v)],
        scratch_shapes=_carried(tiles, D, D),
        interpret=interp,
    )(*args)

    # dq: blocks the queries, walks the keys (the forward's geometry)
    tiles = flash_tiles(T, S, D, isz, BH)
    kv_spec, km_spec = _walked_specs(tiles, D, _keys_seen(tiles, causal))
    base = functools.partial(_flash_bwd_dq_kernel, scale=scale,
                             causal=causal, tiles=tiles)
    q_spec, row_spec = _blocked_spec(tiles, D), _blocked_row_spec(tiles)
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    args = [qf, kf, vf, gf, lse, delta]
    if kmi is not None:
        in_specs.append(km_spec)
        args.append(kmi)
    dq = pl.pallas_call(
        base if kmi is not None else _without_mask(base, 6),
        grid=tiles.grid,
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=_out_struct((BH, T, D), q.dtype, q),
        scratch_shapes=_carried(tiles, D),
        interpret=interp,
    )(*args)

    unflat = lambda x: x.reshape(B, H, *x.shape[1:])
    return unflat(dq), unflat(dk), unflat(dv)


def _xla_attention_bwd(q, k, v, kmask, g, causal, scale):
    """XLA recompute backward (O(T²) memory) — the fallback for shapes the
    kernels don't tile and for f64 gradient checks."""
    # accumulate in f32 for low-precision inputs, but keep f64 at f64 so the
    # float64 gradient-check suite stays meaningful (matches mha's contract)
    acc = jnp.float64 if q.dtype == jnp.float64 else jnp.float32
    qf, kf, vf = (x.astype(acc) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    if causal:
        s = s + causal_bias(s.shape[-2], s.shape[-1])
    if kmask is not None:
        s = jnp.where(kmask[:, None, None, :].astype(bool), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if causal or kmask is not None:
        # zero-grad convention for rows with NO live key (matches the
        # kernel path's lse sentinel): their p degenerates to uniform,
        # which would leak a dv contribution from rows whose output is
        # garbage-by-convention.  (mha's autodiff leaks that dv; the
        # flash contract documents the difference.)
        p = p * jnp.any(s > _NEG_INF / 2, axis=-1, keepdims=True)
    gf = g.astype(acc)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
    ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
    if causal or kmask is not None:
        # where()-style masking: no score gradient through blocked entries
        ds = jnp.where(s > _NEG_INF / 2, ds, 0.0)
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * scale
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flash_mha_p(q: Array, k: Array, v: Array, kmask, causal: bool,
                 scale: float) -> Array:
    return _flash_forward(q, k, v, kmask, causal, scale)[0]


def _flash_fwd(q, k, v, kmask, causal, scale):
    o, lse = _flash_forward(q, k, v, kmask, causal, scale)
    return o, (q, k, v, kmask, o, lse)


def _flash_bwd(causal, scale, res, g):
    q, k, v, kmask, o, lse = res
    dq, dk, dv = _flash_backward(q, k, v, kmask, o, lse, g, causal, scale)
    return dq, dk, dv, None  # mask carries no gradient


_flash_mha_p.defvjp(_flash_fwd, _flash_bwd)


def flash_mha(q: Array, k: Array, v: Array, causal: bool = False,
              scale: Optional[float] = None,
              kmask: Optional[Array] = None) -> Array:
    """Fused blockwise attention — pallas TPU kernels, O(T) memory in BOTH
    directions (forward: online softmax; backward: per-tile p rebuilt from
    the saved log-sum-exp, FlashAttention-2 style).

    ``kmask`` [B, S] (1 = attend) supports DL4J-style variable-length
    padding without leaving the kernel.  Shapes that don't tile, f64, and
    non-TPU/CPU backends fall back to XLA with identical semantics — with
    one documented exception: query rows whose EVERY key is masked get
    ZERO gradients here (both paths), where ``mha``'s autodiff leaks a
    uniform-p dv contribution from them.  Such rows' outputs are
    garbage-by-convention in both (the attention layer zeroes them via the
    output mask, under which the two are gradient-identical — see
    tests/test_attention.py::test_fully_masked_rows_*).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_mha_p(q, k, v, kmask, causal, scale)
