"""Attention of a few query rows a slot over the pages the slot HOLDS,
read from the pool as stored: the decode step's kernel.

``step`` / ``spec_step`` / ``step_multi`` ask for one to a few query rows
a slot.  Gathering every slot's whole window for them (``gather_layer``
+ ``det_attention``) reads, relayouts into ``[.., H, d]`` and multiplies
``L`` rows a slot in every layer though a slot holds a fraction of them:
half of GPT-2 large's decode step (PERF.md section 6, PR 30).
``paged_attention`` is ONE Mosaic call a layer instead:

* both pools stay whole in HBM (``[layers, pages, page, row_lanes]``);
  the layer, the flattened page table and each slot's length are
  scalar-prefetch arguments, and the body copies in, through the table,
  only the ``ceil(len / page)`` pages a slot holds, several pages a
  block and two blocks in flight;
* a row is read AS STORED, its heads side by side in the lane axis.  The
  per-head sum of ``q * k`` over a head's lanes, and the spreading of a
  head's weight back over its lanes, are products with a 0/1 selector in
  VMEM, exact to float32 rounding (``_by_selector``), so no ``[.., H,
  d]`` array with a 64-wide minor axis exists anywhere;
* products, the softmax (carried over the blocks in the order of the
  slot's own pages) and sums are float32: the mathematics of
  ``det_attention`` at its precision, in another order of summation.

A slot's result depends on its own rows and length only: the grid is the
slots, the blocks are walked in a fixed order and nothing is reduced
across slots, so one program compared with itself (co-batching, retry,
hand-off, fused against plain, ``spec_step`` against ``step``) stays
bitwise equal.  Against ``prefill`` / ``reencode`` (``det_attention``
over ``L``) tokens are equal and logits agree to rounding (ROADMAP C1,
tests/_decode_checks.py).  A slot of length 0 reads nothing and returns
zeros.

What keeps the gathered window, decided from what the code sees
(``kept_path``): an int8 pool, a row that is no multiple of 128 lanes (a
tensor-parallel shard of one), the Pallas interpreter under
``shard_map`` on cpu.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _out_struct, _vma
from .kv_cache import (
    LANES, NEG_INF, KVPool, QuantPages, det_attention, gather_layer,
)
from .pallas_support import engaged, fell_back, interpret

Array = jax.Array

_SUBLANES = 8
#: VMEM the four page buffers (two pools, two blocks in flight) may take;
#: the body's temporaries are about as much again, under the 16 MiB default
_BUFFER_BYTES = 4 * 1024 * 1024
#: rows a block aims at: one MXU pass of the selector products
_BLOCK_ROWS = 128


class PagedTiles(NamedTuple):
    """How one slot's pages are walked."""

    pages: int          # pages a block copies in and attends over
    rows: int           # = pages * page_size
    vmem_bytes: int     # the four page buffers


def paged_tiles(page_size: int, pages_per_slot: int, row_lanes: int,
                itemsize: int) -> Optional[PagedTiles]:
    """Pages a block holds, from the shapes: up to ``_BLOCK_ROWS`` rows,
    no more than the slot has, and four buffers of it within
    ``_BUFFER_BYTES``.  None where not even one page fits."""
    page_bytes = page_size * row_lanes * itemsize
    pages = min(max(1, _BLOCK_ROWS // page_size), pages_per_slot,
                _BUFFER_BYTES // (4 * page_bytes))
    if pages < 1:
        return None
    return PagedTiles(pages, pages * page_size, 4 * pages * page_bytes)


def _selector(lanes: int, head_lanes: int, hp: int, transposed: bool):
    """0/1 ``[lanes, hp]`` (or its transpose): lane l belongs to head
    ``l // head_lanes``.  Columns past the last head stay zero."""
    shape = (hp, lanes) if transposed else (lanes, hp)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1 if transposed else 0)
    lo = jax.lax.broadcasted_iota(
        jnp.int32, shape, 0 if transposed else 1) * head_lanes
    return ((lane >= lo) & (lane < lo + head_lanes)).astype(jnp.bfloat16)


def _by_selector(x, sel):
    """``x @ sel`` for a float32 ``x`` and a 0/1 bfloat16 ``sel``, to
    float32 rounding: ``x`` goes in as three bfloat16 terms that sum to
    it exactly, one MXU pass each, accumulated in float32.  ``highest``
    precision takes six passes for the same products because it may
    assume neither operand exact, and the selector products are what
    bounds the kernel (PERF.md section 6, PR 30)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    rest = x - hi.astype(f32)
    mid = rest.astype(bf16)
    lo = (rest - mid.astype(f32)).astype(bf16)
    dot = lambda a: jax.lax.dot(a, sel, preferred_element_type=f32)
    return dot(hi) + dot(mid) + dot(lo)


def _paged_attention_kernel(layer_ref, lens_ref, table_ref, q_ref, k_hbm,
                            v_hbm, o_ref, kbuf, vbuf, sem, sel_ref, sel_t_ref,
                            turn_ref, *, tiles, page, pps, head_lanes, hp,
                            scale):
    """One grid step a slot, in order (the grid is ``arbitrary``): the
    selectors are built in the first and kept, and a slot's first block
    is already on its way when its step starts, sent for by the step
    before (``turn_ref`` says into which of the two buffers)."""
    s = pl.program_id(0)
    # a speculative step near the window's end overshoots it by design:
    # the mask counts from the length as given, the reads stop at the
    # window's last row
    held_rows = lambda slot_: jnp.minimum(lens_ref[slot_], pps * page)
    n_mask, n = lens_ref[s], held_rows(s)
    layer = layer_ref[0]
    n_blocks = pl.cdiv(pl.cdiv(n, page), tiles.pages)
    t_q, lanes = q_ref.shape[1:]
    rows = tiles.rows

    def each_page(slot_, b, buf, act):
        # the held pages of slot ``slot_``'s block ``b``, through its
        # table row
        held = pl.cdiv(held_rows(slot_), page)
        for j in range(tiles.pages):
            pj = b * tiles.pages + j
            pid = table_ref[slot_ * pps + jnp.minimum(pj, pps - 1)]
            dst = pl.ds(j * page, page)

            @pl.when(pj < held)
            def _():
                act(pltpu.make_async_copy(
                    k_hbm.at[layer, pid], kbuf.at[buf, dst], sem.at[0, buf]))
                act(pltpu.make_async_copy(
                    v_hbm.at[layer, pid], vbuf.at[buf, dst], sem.at[1, buf]))

    start = lambda slot_, b, buf: each_page(slot_, b, buf,
                                            lambda c: c.start())
    wait = lambda slot_, b, buf: each_page(slot_, b, buf, lambda c: c.wait())

    @pl.when(s == 0)
    def _():
        sel_ref[...] = _selector(lanes, head_lanes, hp, False)
        sel_t_ref[...] = _selector(lanes, head_lanes, hp, True)
        turn_ref[0] = 0

        @pl.when(n_blocks > 0)
        def _():
            start(0, 0, 0)

    turn = turn_ref[0]                       # the buffer of this slot's block 0
    nxt = jnp.minimum(s + 1, pl.num_programs(0) - 1)
    nxt_reads = (s + 1 < pl.num_programs(0)) & (lens_ref[nxt] > 0)

    @pl.when((n_blocks == 0) & nxt_reads)
    def _():
        start(nxt, 0, turn)

    spread = lambda x: _by_selector(x, sel_t_ref[...])

    def block(b, carry):
        buf = (turn + b) % 2

        @pl.when(b + 1 < n_blocks)
        def _():
            start(s, b + 1, 1 - buf)

        @pl.when((b + 1 == n_blocks) & nxt_reads)
        def _():
            start(nxt, 0, 1 - buf)

        wait(s, b, buf)
        k = kbuf[buf].astype(jnp.float32)
        key = b * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        # rows past the slot's length are whatever the buffer held
        v = jnp.where(key < n, vbuf[buf].astype(jnp.float32), 0.0)
        out = []
        for t in range(t_q):
            m, l, acc = carry[3 * t:3 * t + 3]
            sc = _by_selector(k * q_ref[0, t:t + 1, :], sel_ref[...]) * scale
            # causal among the new rows, and nothing past what was read
            seen = key < jnp.minimum(n_mask - (t_q - 1 - t), n)
            sc = jnp.where(seen, sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen, jnp.exp(sc - m_new), 0.0)      # [rows, hp]
            l = alpha * l + jnp.sum(p, axis=0, keepdims=True)
            # one product spreads the block's weights and the carried
            # sum's rescaling over the heads' lanes
            w = spread(jnp.concatenate(
                [p, jnp.broadcast_to(alpha, (_SUBLANES, hp))], axis=0))
            acc = (acc * w[rows:rows + 1]
                   + jnp.sum(w[:rows] * v, axis=0, keepdims=True))
            out += [m_new, l, acc]
        return tuple(out)

    init = (jnp.full((1, hp), NEG_INF, jnp.float32),
            jnp.zeros((1, hp), jnp.float32),
            jnp.zeros((1, lanes), jnp.float32)) * t_q
    carry = jax.lax.fori_loop(0, n_blocks, block, init)
    turn_ref[0] = (turn + n_blocks) % 2
    for t in range(t_q):
        _, l, acc = carry[3 * t:3 * t + 3]
        inv = jnp.where(l > 0.0, 1.0 / jnp.where(l > 0.0, l, 1.0), 0.0)
        o_ref[0, t:t + 1, :] = (acc * spread(jnp.broadcast_to(
            inv, (_SUBLANES, hp)))[:1]).astype(o_ref.dtype)


def kept_path(pages: KVPool, pages_per_slot: int, tp: int = 1) -> Optional[str]:
    """Why attention over this pool runs the gathered window instead of
    the kernel (None = the kernel runs, and a step reads only the pages
    held): ``paged_attention`` asks it of the pool it is handed, the
    engine of the pool it allocates, for what its ``kv_pages_read``
    counts.  ``tp``: over how many devices of a ``shard_map`` a row is
    yet to be split (the engine's whole pool under a program of that
    ``tp``); inside the ``shard_map`` the pool says so itself."""
    if isinstance(pages, QuantPages):
        return "int8 pool"
    if (tp > 1 or _vma(pages)) and jax.default_backend() == "cpu":
        return "pallas interpreter under shard_map"
    _, _, page, lanes = pages.shape
    if lanes // tp % LANES:
        return f"a row of {lanes // tp} lanes is no multiple of {LANES}"
    if not paged_tiles(page, pages_per_slot, lanes // tp,
                       pages.dtype.itemsize):
        return "one page does not fit the buffers"
    return None


def window_attention(q: Array, k_pages: KVPool, v_pages: KVPool, layer: int,
                     page_table: Array, bias: Array,
                     heads: Tuple[int, int]) -> Array:
    """``det_attention`` of ``q`` [S, H, T, d] over every slot's whole
    gathered window under ``bias``: prefill's and re-encode's form."""
    k_all = gather_layer(k_pages, layer, page_table, heads).transpose(0, 2, 1, 3)
    v_all = gather_layer(v_pages, layer, page_table, heads).transpose(0, 2, 1, 3)
    return det_attention(q, k_all, v_all, bias)


def paged_attention(q: Array, k_pages: KVPool, v_pages: KVPool, layer: int,
                    page_table: Array, lens: Array,
                    heads: Tuple[int, int]) -> Array:
    """``q`` [S, H, T, d], the slots' last ``T`` rows (already written);
    ``page_table`` [S, pages_per_slot]; ``lens`` [S] rows a slot holds
    counting the new ones (0: the slot reads nothing and gets zeros).
    Query row ``t`` sees the keys before ``lens - (T - 1 - t)``; ``lens``
    may pass the window's end (a speculative step there overshoots by
    design: the rows past the end see the whole window and are never
    committed).  Returns [S, H, T, d]."""
    h, d = heads
    s_n, _, t_q, _ = q.shape
    values = k_pages.q if isinstance(k_pages, QuantPages) else k_pages
    _, _, page, lanes = values.shape
    pps = page_table.shape[1]
    lens = lens.astype(jnp.int32)
    name = f"paged_attention[S={s_n},T={t_q},L={pps * page},lanes={lanes}]"
    why = kept_path(k_pages, pps)
    if why:
        fell_back(name, why)
        last = lens[:, None] - (t_q - 1 - jnp.arange(t_q, dtype=jnp.int32))
        bias = jnp.where(
            jnp.arange(pps * page, dtype=jnp.int32)[None, None, :]
            < last[:, :, None], 0.0, NEG_INF)[:, None]
        return window_attention(q, k_pages, v_pages, layer, page_table, bias,
                                heads)
    tiles = paged_tiles(page, pps, lanes, values.dtype.itemsize)
    engaged(name, str(tiles))

    hl = lanes // h
    rows = q.transpose(0, 2, 1, 3)                     # [S, T, H, d]
    if hl != d:
        rows = jnp.pad(rows, [(0, 0)] * 3 + [(0, hl - d)])
    out = _held_pages_call(
        jnp.full((1,), layer, jnp.int32), lens,
        page_table.reshape(-1).astype(jnp.int32),
        rows.reshape(s_n, t_q, lanes).astype(jnp.float32), k_pages, v_pages,
        heads=heads, tiles=tiles, interpreted=interpret())
    return out.reshape(s_n, t_q, h, hl)[..., :d].transpose(0, 2, 1, 3) \
        .astype(q.dtype)


@functools.partial(jax.jit,
                   static_argnames=("heads", "tiles", "interpreted"))
def _held_pages_call(layer, lens, table, rows, k_pages, v_pages, *, heads,
                     tiles, interpreted):
    """The Mosaic call.  A function of its own under ``jit`` with the
    layer an ARGUMENT, so that a program's calls, one a layer, are one
    traced and lowered body called that many times: lowering the kernel
    anew for each of GPT-2 large's 36 layers took the engine's load from
    15 to 30 s (PERF.md section 6, PR 30)."""
    h, d = heads
    s_n, t_q, lanes = rows.shape
    page = k_pages.shape[2]
    pps = table.shape[0] // s_n
    hp = -(-h // LANES) * LANES           # the heads, as a lane-tiled axis
    kernel = functools.partial(
        _paged_attention_kernel, tiles=tiles, page=page, pps=pps,
        head_lanes=lanes // h, hp=hp, scale=1.0 / math.sqrt(d))
    row_spec = pl.BlockSpec((1, t_q, lanes), lambda s, *_: (s, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(s_n,),
            in_specs=[row_spec, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((2, tiles.rows, lanes), k_pages.dtype),
                pltpu.VMEM((2, tiles.rows, lanes), k_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((lanes, hp), jnp.bfloat16),
                pltpu.VMEM((hp, lanes), jnp.bfloat16),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=_out_struct((s_n, t_q, lanes), jnp.float32, rows),
        interpret=interpreted, name="paged_attention",
    )(layer, lens, table, rows, k_pages, v_pages)
