"""The routed experts' gated feed-forward over rows SORTED by expert, as
ONE Mosaic call an expert layer: ``parallel/moe.moe_forward_held``'s
grouped product.

``xs`` [M, d] holds the picks that fell on the experts held here, sorted
by expert; ``group_sizes`` [held] says how many rows each expert took
(rows past their sum belong to nobody).  Three ``jax.lax.ragged_dot``\\ s
did this before (gate, up, down), and XLA's kernel for them read the
weights at 39% of the chip's bandwidth whether a group held 4 rows or 8
(PERF.md section 6, PR 44 and PR 46).  ``grouped_ffn`` is one kernel:

* the grid walks WORK ITEMS, then tiles of the experts' width ``f``.  A
  work item is one expert with at least one row, in one block of ``rows``
  rows (at a decode step the block is all of ``xs``, so an item is an
  expert that was hit; in a prefill chunk an expert whose rows straddle
  two blocks is two items).  The items, their experts and blocks, are
  computed from ``group_sizes`` outside the kernel and scalar-prefetched:
  the pipeline's index maps read them, so the next item's weight blocks
  ``[d, cols]``, ``[d, cols]``, ``[cols, d]`` are in flight while this
  one's are multiplied, and an expert nobody picked is no item at all:
  neither a copy nor a product.  The grid is as long as the items can
  get (blocks + experts - 1); the steps past the last item map to the
  blocks already held and are skipped;
* a block of ``xs`` and of the result stays in VMEM while its items pass.
  An item's rows are taken from it in windows of ``window`` rows that
  start on a sublane tile, masked to the rows that are the expert's: 4
  rows and 8 rows cost the same one pass;
* ``silu(gate) * up`` is cast to the weights' type and multiplied by the
  tile of ``w_down`` at once: the ``[M, f]`` intermediate never leaves
  VMEM, and the result block gathers the tiles' sums in float32.

Operands in the weights' type, float32 accumulation, float32 out: the
mathematics of the three ``ragged_dot``\\ s with the sum over ``f`` taken
a tile at a time (tests/test_grouped_ffn.py states the bound).

The tiles follow from the shapes (``ffn_tiles``); what keeps
``ragged_dot`` is decided from shapes and type alone (``kept_path``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _out_struct, _vma
from .kv_cache import LANES
from .pallas_support import engaged, interpret

Array = jax.Array

#: rows a window starts on: one sublane tile of bfloat16, two of float32
_ALIGN = 16
#: rows a window takes where groups are small (a decode step): a group of
#: up to 17 rows lies in one window wherever it starts
_WINDOW_MIN = 32
#: and the most it takes where they are large (a prefill chunk)
_WINDOW_MAX = 128
#: bytes one weight block may take (three of them, two buffers each)
_WEIGHT_BLOCK_BYTES = 2 * 1024 * 1024
#: elements a block of ``xs`` and of the result may take
_ROW_BLOCK_ELEMS = 2 * 1024 * 1024
#: VMEM asked of the compiler over what the buffers take
_VMEM_MARGIN = 16 * 1024 * 1024


class FfnTiles(NamedTuple):
    """How the rows and the experts' width are walked."""

    rows: int          # rows of xs and of the result a block holds
    blocks: int        # blocks of rows (M padded up to blocks * rows)
    window: int        # rows one product takes
    cols: int          # columns of f a grid step takes
    vmem_bytes: int    # the pipeline's buffers


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def ffn_tiles(m: int, d: int, f: int, held: int,
              itemsize: int) -> Optional[FfnTiles]:
    """Tiles from the shapes: a window of twice the rows an even split
    gives a group (a power of two within ``_WINDOW_MIN`` and
    ``_WINDOW_MAX``), the widest tile of ``f`` (whole lane tiles, a
    divisor of ``f``) whose weight block fits ``_WEIGHT_BLOCK_BYTES``,
    and as many rows a block as ``_ROW_BLOCK_ELEMS`` allows.  None where
    ``f`` has no such tile."""
    window = _WINDOW_MIN
    while window < min(_WINDOW_MAX, 2 * m // max(held, 1)):
        window *= 2
    fits = [c for c in range(LANES, f + 1, LANES)
            if f % c == 0 and d * c * itemsize <= _WEIGHT_BLOCK_BYTES]
    if not fits:
        return None
    cols = fits[-1]
    cap = max(window, _ROW_BLOCK_ELEMS // d // window * window)
    blocks = -(-_round_up(m, window) // cap)
    rows = _round_up(-(-m // blocks), window)
    vmem = 2 * (3 * d * cols * itemsize + rows * d * (itemsize + 4))
    return FfnTiles(rows, blocks, window, cols, vmem)


def kept_path(xs: Array, w_gate: Array) -> Optional[str]:
    """Why these rows go through ``jax.lax.ragged_dot`` instead of the
    kernel (None = the kernel runs): from shapes and type alone."""
    m, d = xs.shape
    held, _, f = w_gate.shape
    if w_gate.dtype not in (jnp.bfloat16, jnp.float32):
        return f"weights of type {w_gate.dtype}"
    if _vma(xs) and jax.default_backend() == "cpu":
        return "pallas interpreter under shard_map"
    if d % LANES or f % LANES:
        return f"widths {d} and {f} are no multiples of {LANES} lanes"
    if m == 0 or held == 0:
        return "nothing to multiply"
    if not ffn_tiles(m, d, f, held, w_gate.dtype.itemsize):
        return "no tile of the experts' width fits the buffers"
    return None


def work_items(group_sizes: Array, tiles: FfnTiles):
    """The grid's work items from the rows each expert took: ``(expert
    [W], block [W], start [held], end [held], items [1])``, all int32,
    with ``W = blocks + held - 1``.  Item ``w`` is expert ``expert[w]``'s
    rows (``start`` to ``end``) inside block ``block[w]``; experts
    ascend, and so do blocks.  Items past ``items`` repeat the last."""
    held = group_sizes.shape[0]
    n_w = tiles.blocks + held - 1
    sizes = group_sizes.astype(jnp.int32)
    end = jnp.cumsum(sizes)
    start = end - sizes
    first = start // tiles.rows
    spans = jnp.where(sizes > 0, (end - 1) // tiles.rows - first + 1, 0)
    item_end = jnp.cumsum(spans)
    items = item_end[-1]
    w = jnp.minimum(jnp.arange(n_w, dtype=jnp.int32),
                    jnp.maximum(items - 1, 0))
    expert = jnp.minimum(
        jnp.sum(item_end[None, :] <= w[:, None], axis=1, dtype=jnp.int32),
        held - 1)
    block = first[expert] + w - (item_end - spans)[expert]
    return (expert, jnp.clip(block, 0, tiles.blocks - 1), start, end,
            items[None])


def _grouped_ffn_kernel(expert_ref, block_ref, start_ref, end_ref, items_ref,
                        x_ref, wg_ref, wu_ref, wd_ref, o_ref, *, tiles):
    """One grid step a work item and tile of ``f``, items outermost: the
    result block is zeroed when its first item arrives and written back
    when the walk leaves it."""
    w, j = pl.program_id(0), pl.program_id(1)
    rows, window = tiles.rows, tiles.window
    blk = block_ref[w]
    f32 = jnp.float32

    @pl.when((j == 0) & ((w == 0) | (blk != block_ref[jnp.maximum(w - 1, 0)])))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(w < items_ref[0])
    def _():
        e = expert_ref[w]
        base = blk * rows
        lo = jnp.maximum(start_ref[e] - base, 0)
        hi = jnp.minimum(end_ref[e] - base, rows)
        first = lo // _ALIGN * _ALIGN

        def one(i, carry):
            at = first + i * window
            # the last window of a block is pulled back inside it; the
            # rows it shares with the one before are that one's
            a = pl.multiple_of(jnp.minimum(at, rows - window), _ALIGN)
            x = x_ref[pl.ds(a, window), :]
            g = jnp.dot(x, wg_ref[0], preferred_element_type=f32)
            u = jnp.dot(x, wu_ref[0], preferred_element_type=f32)
            act = (jax.nn.silu(g) * u).astype(wd_ref.dtype)
            res = jnp.dot(act, wd_ref[0], preferred_element_type=f32)
            r = a + jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            mine = (r >= jnp.maximum(lo, at)) & (r < hi)
            # the other rows of the window are other experts': a select,
            # so that nothing of theirs (a poisoned row's) gets in
            o_ref[pl.ds(a, window), :] += jnp.where(mine, res, 0.0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(hi - first, window), one, 0)


def grouped_ffn(xs: Array, w_gate: Array, w_up: Array, w_down: Array,
                group_sizes: Array) -> Array:
    """``xs`` [M, d] sorted by expert, ``w_gate`` / ``w_up`` [held, d, f],
    ``w_down`` [held, f, d], ``group_sizes`` [held] int32.  Row ``r`` of
    group ``e`` gives ``w_down[e](silu(w_gate[e] x_r) * (w_up[e] x_r))``,
    [M, d] float32; the rows past the last group hold zeros or were never
    written (the caller masks them).  For shapes ``kept_path`` gives no
    reason to keep from it."""
    why = kept_path(xs, w_gate)
    if why:
        raise ValueError(f"grouped_ffn cannot take these shapes: {why}")
    held, d, f = w_gate.shape
    tiles = ffn_tiles(xs.shape[0], d, f, held, w_gate.dtype.itemsize)
    engaged(call_name(xs, w_gate), str(tiles))
    return with_tiles(xs, w_gate, w_up, w_down, group_sizes, tiles)


def call_name(xs: Array, w_gate: Array) -> str:
    """The kernel's name with the shapes and type that choose its tiles:
    what ``pallas_support.engaged`` / ``fell_back`` say it by."""
    held, d, f = w_gate.shape
    return (f"grouped_ffn[M={xs.shape[0]},d={d},f={f},held={held},"
            f"{w_gate.dtype.name}]")


def with_tiles(xs: Array, w_gate: Array, w_up: Array, w_down: Array,
               group_sizes: Array, tiles: FfnTiles) -> Array:
    """``grouped_ffn`` walked by ``tiles`` (``ffn_tiles``' choice, or
    another geometry put in its place: ``scripts/chip_probe.py
    experts``)."""
    m = xs.shape[0]
    x = xs.astype(w_gate.dtype)
    if tiles.blocks * tiles.rows != m:
        x = jnp.pad(x, ((0, tiles.blocks * tiles.rows - m), (0, 0)))
    out = _items_call(*work_items(group_sizes, tiles), x, w_gate, w_up,
                      w_down, tiles=tiles, interpreted=interpret())
    return out[:m]


@functools.partial(jax.jit, static_argnames=("tiles", "interpreted"))
def _items_call(expert, block, start, end, items, x, w_gate, w_up, w_down, *,
                tiles, interpreted):
    """The Mosaic call, a function of its own under ``jit``: a program's
    calls, one an expert layer, are one traced and lowered body
    (``ops/paged_attention._held_pages_call``'s lesson)."""
    held, d, f = w_gate.shape
    n_f = f // tiles.cols
    kernel = functools.partial(_grouped_ffn_kernel, tiles=tiles)

    def rows_of(w, j, expert, block, *_):
        return block[w], 0

    def tile_of(w, j, items):
        # past the last item: the tile already held
        return jnp.where(w < items[0], j, n_f - 1)

    def w_in(w, j, expert, block, start, end, items):
        return expert[w], 0, tile_of(w, j, items)

    def w_out(w, j, expert, block, start, end, items):
        return expert[w], tile_of(w, j, items), 0

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(tiles.blocks + held - 1, n_f),
            in_specs=[pl.BlockSpec((tiles.rows, d), rows_of),
                      pl.BlockSpec((1, d, tiles.cols), w_in),
                      pl.BlockSpec((1, d, tiles.cols), w_in),
                      pl.BlockSpec((1, tiles.cols, d), w_out)],
            out_specs=pl.BlockSpec((tiles.rows, d), rows_of)),
        out_shape=_out_struct(x.shape, jnp.float32, x),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=tiles.vmem_bytes + _VMEM_MARGIN),
        interpret=interpreted, name="grouped_ffn",
    )(expert, block, start, end, items, x, w_gate, w_up, w_down)
