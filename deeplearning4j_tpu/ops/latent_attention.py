"""Attention of one ABSORBED latent query a slot over the cached latent
rows the slot HOLDS, read from the pool as stored: the decode step's
kernel of ``models/latent_moe.py``.

A latent row is ONE shared head: ``[c_kv | rotated k_pe | 0]`` in
``lanes`` lanes, key and (its first ``width`` values) value at once, for
all ``H`` query heads.  The step used to gather every slot's whole
window for it (``pool[layer, table]`` as ``[S, L, lanes]``: written in
every layer of every step and read back twice, though a slot holds a
third of it: PERF.md section 6, PR 42).  ``latent_attention`` is ONE
Mosaic call a layer instead:

* the pool stays whole in HBM (``[layers, pages, page, lanes]``); the
  layer, the flattened page table and the rows each slot holds are
  scalar-prefetch arguments, and the body copies in, through the table,
  only the ``ceil(held / page)`` pages of a slot, several pages a block
  and two blocks in flight (the next slot's first block is sent for
  while this slot's last is attended);
* the products are real MXU work, not selectors: ``[H, lanes] x [lanes,
  B]`` scores and ``[H, B] x [B, width]`` values a block of ``B`` rows,
  operands in the pool's type, float32 out;
* the softmax is float32 and carried from block to block (maximum, sum,
  weighted rows).  It STARTS from the slot's own new row, which is not
  in the pool yet (the step writes all layers' rows at its end): the row
  is one more key, so no slot's softmax is ever empty and a slot that
  holds nothing (idle, or its first token) reads nothing and attends to
  its own row alone.

A slot's result depends on its own rows and length only: the grid is the
slots, walked in order, the blocks in the order of the slot's own pages,
and nothing is reduced across slots, so whoever shares the batch a slot's
result is bitwise the same.  Against the gathered window
(``models/latent_moe.attend_window``) it is the same mathematics in
another order of summation: tokens equal, logits to rounding
(tests/test_latent_attention.py).

What keeps the gathered window, decided by the caller from what the code
sees (``kept_path``): a row that is no multiple of 128 lanes, a page that
is no whole number of the type's sublane tiles (a copy would land inside
a tile), the Pallas interpreter under ``shard_map`` on cpu.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _out_struct, _vma
from .kv_cache import LANES, NEG_INF
from .pallas_support import engaged, interpret

Array = jax.Array

#: bytes of one sublane tile's column: 8 rows of float32, 16 of bfloat16
_TILE_BYTES = 32
#: rows a block aims at.  On the chip, 16 slots holding 1,457 rows each
#: (my chip run, PR 42): 0.108 ms a call at 256, 0.094 at 512, 0.090 at
#: 1,024, where a short context pays for more rows than it holds
_BLOCK_ROWS = 512
#: VMEM the two blocks in flight may take
_BUFFER_BYTES = 4 * 1024 * 1024


class LatentTiles(NamedTuple):
    """How one slot's pages are walked."""

    pages: int          # pages a block copies in and attends over
    rows: int           # = pages * page_size
    vmem_bytes: int     # the two block buffers


def latent_tiles(page_size: int, pages_per_slot: int, lanes: int,
                 itemsize: int) -> Optional[LatentTiles]:
    """Pages a block holds, from the shapes: up to ``_BLOCK_ROWS`` rows,
    no more than the slot has, and two buffers of it within
    ``_BUFFER_BYTES``.  None where not even one page fits."""
    page_bytes = page_size * lanes * itemsize
    pages = min(max(1, _BLOCK_ROWS // page_size), pages_per_slot,
                _BUFFER_BYTES // (2 * page_bytes))
    if pages < 1:
        return None
    return LatentTiles(pages, pages * page_size, 2 * pages * page_bytes)


def kept_path(pool: Array, pages_per_slot: int, tp: int = 1) -> Optional[str]:
    """Why the step over this latent pool runs the gathered window
    instead of the kernel (None = the kernel runs, and a step reads only
    the pages held): the step asks it of the pool it is handed
    (``models/latent_moe.mla_attention``), the engine of the pool it
    allocates, for what its ``kv_pages_read`` counts
    (``ops/paged_attention.kept_path``'s signature; a latent pool is
    never split over ``tp`` devices)."""
    if (tp > 1 or _vma(pool)) and jax.default_backend() == "cpu":
        return "pallas interpreter under shard_map"
    _, _, page, lanes = pool.shape
    if lanes % LANES:
        return f"a row of {lanes} lanes is no multiple of {LANES}"
    sublanes = _TILE_BYTES // pool.dtype.itemsize
    if page % sublanes:
        return f"a page of {page} rows is no multiple of {sublanes} sublanes"
    if not latent_tiles(page, pages_per_slot, lanes, pool.dtype.itemsize):
        return "one page does not fit the buffers"
    return None


def _latent_attention_kernel(layer_ref, held_ref, table_ref, q_ref, new_ref,
                             pool_hbm, o_ref, buf, sem, turn_ref, *, tiles,
                             page, pps, scale):
    """One grid step a slot, in order (the grid is ``arbitrary``): a
    slot's first block is already on its way when its step starts, sent
    for by the step before (``turn_ref`` says into which of the two
    buffers)."""
    s = pl.program_id(0)
    n = held_ref[s]
    layer = layer_ref[0]
    rows = tiles.rows
    n_blocks = pl.cdiv(n, rows)
    f32 = jnp.float32

    def each_page(slot_, b, at, act):
        # the held pages of slot ``slot_``'s block ``b``, through its table
        # row: a loop, not an unrolled one (a block of 32 pages at four
        # sites is a kernel that takes seconds to lower)
        held = pl.cdiv(held_ref[slot_], page) - b * tiles.pages

        def one(j, carry):
            pid = table_ref[slot_ * pps + b * tiles.pages + j]
            act(pltpu.make_async_copy(
                pool_hbm.at[layer, pid],
                buf.at[at, pl.ds(pl.multiple_of(j * page, page), page)],
                sem.at[at]))
            return carry
        jax.lax.fori_loop(0, jnp.clip(held, 0, tiles.pages), one, 0)

    start = lambda slot_, b, at: each_page(slot_, b, at, lambda c: c.start())
    wait = lambda slot_, b, at: each_page(slot_, b, at, lambda c: c.wait())

    @pl.when(s == 0)
    def _():
        turn_ref[0] = 0

        @pl.when(n_blocks > 0)
        def _():
            start(0, 0, 0)

    turn = turn_ref[0]                       # the buffer of this slot's block 0
    nxt = jnp.minimum(s + 1, pl.num_programs(0) - 1)
    nxt_reads = (s + 1 < pl.num_programs(0)) & (held_ref[nxt] > 0)

    @pl.when((n_blocks == 0) & nxt_reads)
    def _():
        start(nxt, 0, turn)

    q = q_ref[0]                                             # [H, lanes]
    new = new_ref[0]                                         # [1, lanes]
    width = o_ref.shape[-1]

    def block(b, carry):
        m, l, acc = carry
        at = (turn + b) % 2

        @pl.when(b + 1 < n_blocks)
        def _():
            start(s, b + 1, 1 - at)

        @pl.when((b + 1 == n_blocks) & nxt_reads)
        def _():
            start(nxt, 0, 1 - at)

        wait(s, b, at)
        k = buf[at]                                          # [rows, lanes]
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32) * scale
        iota = jax.lax.broadcasted_iota
        sc = jnp.where(b * rows + iota(jnp.int32, (1, rows), 1) < n, sc,
                       NEG_INF)                              # [H, rows]
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)          # 0 past the length: m is finite
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        # rows past the slot's length are whatever the buffer held, another
        # slot's rows among them: a poisoned neighbour's must not get in
        v = jnp.where(b * rows + iota(jnp.int32, (rows, 1), 0) < n,
                      k[:, :width], jnp.zeros((), k.dtype))
        acc = alpha * acc + jax.lax.dot(p.astype(k.dtype), v,
                                        preferred_element_type=f32)
        return m_new, l, acc

    # the slot's own row is the first key: weight 1 at its own score
    s_new = jnp.sum(q.astype(f32) * new.astype(f32), axis=1,
                    keepdims=True) * scale                   # [H, 1]
    init = (s_new, jnp.ones_like(s_new),
            jnp.broadcast_to(new[:, :width].astype(f32),
                             (q.shape[0], width)))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, init)
    turn_ref[0] = (turn + n_blocks) % 2
    o_ref[0] = acc / l


def latent_attention(q_lat: Array, row_new: Array, pool: Array, layer: int,
                     page_table: Array, held: Array, width: int,
                     scale: float) -> Array:
    """``q_lat`` [S, H, lanes], the slots' absorbed queries, and
    ``row_new`` [S, lanes], their own new rows (not in the pool), both in
    the pool's type; ``page_table`` [S, pages_per_slot]; ``held`` [S]
    rows of earlier positions a slot holds in the pool (0: the slot reads
    nothing; past the window: the window).  Returns the softmax-weighted
    sum of the rows' first ``width`` values, [S, H, width] float32.  For a
    pool ``kept_path`` gives no reason to keep from it."""
    s_n, h, lanes = q_lat.shape
    page = pool.shape[2]
    pps = page_table.shape[1]
    why = kept_path(pool, pps)
    if why:
        raise ValueError(f"latent_attention cannot take this pool: {why}")
    held = jnp.clip(held.astype(jnp.int32), 0, pps * page)
    tiles = latent_tiles(page, pps, lanes, pool.dtype.itemsize)
    engaged(f"latent_attention[S={s_n},H={h},L={pps * page},lanes={lanes}]",
            str(tiles))
    # whole lane tiles of values out; the caller's ``width`` of them kept
    out = _held_rows_call(
        jnp.full((1,), layer, jnp.int32), held,
        page_table.reshape(-1).astype(jnp.int32),
        q_lat.astype(pool.dtype), row_new[:, None, :].astype(pool.dtype),
        pool, width=min(lanes, -(-width // LANES) * LANES), scale=scale,
        tiles=tiles, interpreted=interpret())
    return out[..., :width]


@functools.partial(jax.jit,
                   static_argnames=("width", "scale", "tiles", "interpreted"))
def _held_rows_call(layer, held, table, q_lat, row_new, pool, *, width, scale,
                    tiles, interpreted):
    """The Mosaic call.  A function of its own under ``jit`` with the
    layer an ARGUMENT, so that a program's calls, one a layer, are one
    traced and lowered body called that many times
    (``ops/paged_attention._held_pages_call``'s lesson)."""
    s_n, h, lanes = q_lat.shape
    page = pool.shape[2]
    pps = table.shape[0] // s_n
    kernel = functools.partial(_latent_attention_kernel, tiles=tiles,
                               page=page, pps=pps, scale=scale)
    per_slot = lambda *block: pl.BlockSpec((1,) + block,
                                           lambda s, *_: (s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(s_n,),
            in_specs=[per_slot(h, lanes), per_slot(1, lanes),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_slot(h, width),
            scratch_shapes=[
                pltpu.VMEM((2, tiles.rows, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=_out_struct((s_n, h, width), jnp.float32, q_lat),
        interpret=interpreted, name="latent_attention",
    )(layer, held, table, q_lat, row_new, pool)
