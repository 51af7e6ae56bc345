"""The grouped-query block over a learned sparse selection (the language
model of ``model_type: KeyeVL2``: Qwen3-MoE's block with the indexer of
DeepSeek's sparse attention at ``sa_config``'s sizes) and its decode
program.

A layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``;
every layer is an expert layer (``parallel/moe.moe_forward_held`` behind
the softmax router), there is no shared expert.

Attention, ``u = RMSNorm(x)``: ``q = u W_q`` per query head, ``k = u W_k``
and ``v = u W_v`` per key/value head (fewer: query head ``a`` reads KV
head ``a // (H / KV)``); RMSNorm over each head's ``q`` and ``k``; rotary
on all of both, rotate-half pairing, frequency ``i`` turned by the
position stream ``mrope_section`` gives it (text: all three streams are
the position, which is plain rotary).  The **indexer** beside it:
``qI = u W_iq`` (``index_n_heads`` heads of ``index_head_dim``), ONE key
a token ``kI = LayerNorm(u W_ik)``, both rotated by the temporal stream,
head weights ``w = u W_iw / sqrt(heads * dim)``; the score of an earlier
row ``s`` for the query at ``t`` is ``sum_j w[t, j] relu(qI[t, j] .
kI[s])`` in float32.  The query attends over the ``index_topk`` rows
``s <= t`` of largest score only (all of them while there are no more),
exactly: ties go to the lower position, nothing is approximate.

**What is cached**: three kinds of row a token a layer, in three pools
that live and die with the same pages: the rotated normed K heads side
by side (``n_kv_heads * head_dim`` lanes), the V heads likewise, and the
rotated index key in the next multiple of 128 lanes (the rest zero: a
minor axis that is no multiple of the chip's 128-lane tile is stored
pages-minor and transposed in and out of every call).

Two paths read them, the same mathematics:

* *a chunk* (prefill, the full forward): the indexer scores every held
  row a block of pages at a time into one ``[T, rows]`` table of sortable
  keys; the k-th largest key of each query is found by a walk over the
  key's 32 bits, two a pass, and the cut among equal keys by one over
  the position's (passes of compare-and-count, no sort: a sort of the
  table is 3.7 x as long on the chip); attention then runs
  block by block over the pages that hold context with the selection as
  a mask and the softmax carried from block to block.  It reads every
  held K and V row once a chunk.
* *the step*: one new row a slot.  The index rows of the pages held are
  scored block by block, ``lax.top_k`` names the rows (a sort over the
  window's scores, 0.08 ms a layer on the chip: the threshold walk plus
  a compaction to indices is slower there), and ONLY those K and V rows
  are gathered (by page and row through the table) and attended.  No K
  or V temporary of a slot's window exists.

The decode program is the expert family's one builder
(``models/latent_moe.expert_decode_program``); this module hands it the
attention above.  Beside the expert counts a call reports
``SPARSE_STATS`` and the rows each layer chose (``attn_rows``).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.layers.normalization import layer_norm
from ..ops.kv_cache import LANES
from ..ops.select import kth_key, sortable_keys, walk
from ..parallel.moe import init_held_experts
from .arch import LMArch
from .latent_moe import (NEG_INF, CachedAttention, _embed, _join_aux,
                         _logits, _mm, expert_decode_program, layer_finish,
                         rms_norm)

Array = jax.Array

#: what the attention counts a call, in this order (int32 [3]), summed
#: over the layers, each what the call DID: cached and new rows the
#: indexer scored (whole blocks, of every slot of a step), K or V rows
#: the attention read after the selection (a step's gather of
#: ``index_topk`` rows a slot; a chunk's blocks, which the mask covers
#: whole), rows the stepped slots (or the chunk's slot) held
SPARSE_STATS = ("index_rows_scored", "attn_rows_read", "rows_held")


def index_lanes(arch: LMArch) -> int:
    """Lanes an index row takes in its pool."""
    return -(-arch.index_head_dim // LANES) * LANES


# -- rotary ------------------------------------------------------------------

def rope_rows(arch: LMArch, positions: np.ndarray):
    """``(cos, sin, cosI, sinI)`` float32 for rows at ``positions`` [3, N]
    (temporal, height, width; on the host, float64): the heads' tables
    [N, head_dim], where frequency ``i`` takes the stream its
    ``mrope_section`` names, and the indexer's [N, index_head_dim] from
    the temporal stream.  Both halves repeat the frequencies."""
    positions = np.asarray(positions, np.float64)
    half = arch.head_dim // 2
    inv = arch.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
    stream = np.repeat(np.arange(len(arch.mrope_section)), arch.mrope_section)
    ang = positions[stream].T * inv                          # [N, half]
    half_i = arch.index_head_dim // 2
    inv_i = arch.rope_theta ** (-np.arange(half_i, dtype=np.float64) / half_i)
    ang_i = positions[0][:, None] * inv_i
    two = lambda a: np.concatenate([a, a], axis=-1)
    return tuple(jnp.asarray(f(two(a)), jnp.float32)
                 for a in (ang, ang_i) for f in (np.cos, np.sin))


def rotate_half(x: Array, cos: Array, sin: Array) -> Array:
    """Rotary on the whole last axis, pairing ``(i, i + d/2)``."""
    half = x.shape[-1] // 2
    x = x.astype(jnp.float32)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


# -- parameters ----------------------------------------------------------------

def init_layer(rng: Array, arch: LMArch, dtype=jnp.float32) -> Dict[str, Array]:
    """One layer's tree.  Every matrix N(0, init_std), unit gains."""
    d, D = arch.d_model, arch.head_dim
    H, KV = arch.n_heads, arch.n_kv_heads
    HI, DI = arch.index_n_heads, arch.index_head_dim
    ks = jax.random.split(rng, 8)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    p = {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
         "W_q": normal(ks[0], (d, H * D)), "W_k": normal(ks[1], (d, KV * D)),
         "W_v": normal(ks[2], (d, KV * D)), "W_o": normal(ks[3], (H * D, d)),
         "q_norm_g": jnp.ones((D,), dtype), "k_norm_g": jnp.ones((D,), dtype),
         "W_iq": normal(ks[4], (d, HI * DI)), "W_ik": normal(ks[5], (d, DI)),
         "ik_norm_g": jnp.ones((DI,), dtype),
         "ik_norm_b": jnp.zeros((DI,), dtype),
         "W_iw": normal(ks[6], (d, HI))}
    p.update(init_held_experts(
        ks[7], d, arch.moe_d_ff, arch.n_experts, arch.experts_held,
        n_shared=0, std=arch.init_std, dtype=dtype, router=arch.router))
    return p


def init_params(rng: Array, arch: LMArch, dtype=jnp.float32) -> dict:
    """The whole tree; ``blocks`` is a LIST of per-layer trees."""
    ke, kh, *kb = jax.random.split(rng, 2 + arch.n_layers)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    return {"embed": normal(ke, (arch.vocab_size, arch.d_model)),
            "blocks": [init_layer(k, arch, dtype) for k in kb],
            "lnf_g": jnp.ones((arch.d_model,), dtype),
            "head": normal(kh, (arch.d_model, arch.vocab_size))}


# -- the layer's first half ------------------------------------------------------

def project(p: Dict[str, Array], h: Array, rope, arch: LMArch):
    """First half of the layer for rows ``h`` [N, d] whose rotary rows
    are ``rope`` (``rope_rows``).  Returns ``((q [N, H, D], qI [N, HI,
    DI], w [N, HI]), (k row, v row, index row))``: the query side in
    float32, and what the three pools will hold of these positions, in
    the weights' type (so every reader sees the values the cache will),
    the index key zero-filled to ``index_lanes``."""
    cos, sin, cos_i, sin_i = rope
    n = h.shape[0]
    H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    HI, DI = arch.index_n_heads, arch.index_head_dim
    cd = p["W_k"].dtype
    u = rms_norm(h, p["ln1_g"], arch.rms_eps)
    q = rms_norm(_mm(u, p["W_q"]).reshape(n, H, D), p["q_norm_g"],
                 arch.rms_eps)
    k = rms_norm(_mm(u, p["W_k"]).reshape(n, KV, D), p["k_norm_g"],
                 arch.rms_eps)
    q = rotate_half(q, cos[:, None, :], sin[:, None, :])
    k = rotate_half(k, cos[:, None, :], sin[:, None, :])
    q_i = rotate_half(_mm(u, p["W_iq"]).reshape(n, HI, DI),
                      cos_i[:, None, :], sin_i[:, None, :])
    k_i = rotate_half(layer_norm(_mm(u, p["W_ik"]), p["ik_norm_g"],
                                 p["ik_norm_b"], arch.rms_eps), cos_i, sin_i)
    w = _mm(u, p["W_iw"]) * (HI ** -0.5 * DI ** -0.5)
    pad = jnp.zeros((n, index_lanes(arch) - DI))
    rows = (k.reshape(n, KV * D).astype(cd), _mm(u, p["W_v"]).astype(cd),
            jnp.concatenate([k_i, pad], axis=-1).astype(cd))
    return (q, q_i, w), rows


# -- the selection ----------------------------------------------------------------

def topk_threshold(keys, cols, k: int, n_pos_bits: int):
    """The exact top ``k`` of each row of a table of sortable keys given
    in parts (``keys[i]`` [T, n_i] uint32, 0 = no candidate; ``cols[i]``
    [n_i] the positions of its columns), as a pair ``(thr, cut)`` [T]:
    chosen are the keys above ``thr`` and, of those equal to it, the
    positions up to ``cut`` (``chosen``).  ``thr`` is the largest value
    that at least ``k`` keys reach, found by ``ops.select``'s walk
    (passes of compare-and-count over the table, no sort); ``cut``
    likewise over the position's ``n_pos_bits``.  A row with fewer than
    ``k`` candidates gets ``thr`` 0: all of them."""
    def count(pred):
        return sum(jnp.sum(pred(kk, cc[None, :]), axis=-1, dtype=jnp.int32)
                   for kk, cc in zip(keys, cols))

    rows = keys[0].shape[0]
    thr = kth_key(lambda c: count(lambda kk, cc: kk >= c[:, None]), rows, k)
    need = k - count(lambda kk, cc: kk > thr[:, None])
    cut = walk(n_pos_bits, jnp.zeros((rows,), jnp.int32),
               lambda c: count(lambda kk, cc: (kk == thr[:, None])
                               & (cc < c[:, None])) < need)
    return thr, cut


def chosen(kk: Array, cc: Array, thr: Array, cut: Array) -> Array:
    """The selection as a mask of one part of the table (``kk`` [T, n],
    ``cc`` [n] or [T, n] its columns' positions)."""
    t = thr[:, None]
    return (kk > t) | ((kk == t) & (t > 0) & (cc <= cut[:, None]))


def causal(t: int) -> Array:
    """[t, t] bool: row ``i`` may choose columns up to ``i``."""
    ar = jnp.arange(t)
    return ar[None, :] <= ar[:, None]


def index_scores(q_i: Array, w: Array, index_rows: Array, arch: LMArch
                 ) -> Array:
    """``sum_j w[t, j] relu(qI[t, j] . kI[s])`` float32 [T, rows]: the
    products' operands in the cached rows' type."""
    cd = index_rows.dtype
    s = jnp.einsum("thd,ld->thl", q_i.astype(cd),
                   index_rows[:, :arch.index_head_dim],
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1)


def slot_index_scores(q_i: Array, w: Array, index_rows: Array, arch: LMArch
                      ) -> Array:
    """``index_scores`` of ONE query a slot against that slot's own rows:
    ``q_i`` [S, HI, DI], ``w`` [S, HI], ``index_rows`` [S, rows, lanes]
    -> [S, rows]."""
    return jax.vmap(lambda q, w_, rows: index_scores(
        q[None], w_[None], rows, arch)[0])(q_i, w, index_rows)


def block_pages(pps: int, page_size: int, rows_wanted: int) -> int:
    """Pages a block of about ``rows_wanted`` rows: whole blocks tile a
    slot's ``pps`` pages."""
    return next(d for d in range(max(1, min(pps, rows_wanted // page_size)),
                                 0, -1) if pps % d == 0)


#: rows a block of index keys and a block of K and V rows hold
INDEX_BLOCK_ROWS, KV_BLOCK_ROWS = 2048, 1024


# -- attention ---------------------------------------------------------------------

def attend_blocks(q: Array, k_new: Array, v_new: Array, arch: LMArch,
                  mask_new, read_kv=None, mask_old=None, n_old=0,
                  kv_block: int = 0, scale: Optional[float] = None) -> Array:
    """Grouped-query attention of ``T`` new rows (``q`` [T, H, D] float32,
    ``k_new`` / ``v_new`` [T, KV * D] as cached) over themselves under
    ``mask_new()`` [T, T] and over the ``n_old`` cached rows before them,
    read a block at a time (``read_kv(j)`` gives the K and V rows ``j *
    kv_block ..``, ``mask_old(j)`` what each query may see of them,
    broadcastable to [T, kv_block]): a loop over the blocks that hold any
    carries the softmax's running maximum, sum and weighted values, so no
    score matrix wider than a block exists and the work follows the rows
    held.  The softmax's scale is ``head_dim^-0.5`` unless ``scale`` says
    otherwise.  Returns ``([T, H * D] float32, the mask mask_new gave)``."""
    T = q.shape[0]
    H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    scale = D ** -0.5 if scale is None else scale
    cd = k_new.dtype
    qg = q.reshape(T, KV, H // KV, D).astype(cd)

    def scored(k_rows, v_rows, mask):
        """Masked scores [KV, G, T, rows] and values [rows, KV, D]."""
        s = jnp.einsum("tgqd,lgd->gqtl", qg,
                       k_rows.reshape(-1, KV, D).astype(cd),
                       preferred_element_type=jnp.float32) * scale
        return (jnp.where(mask[None, None], s, NEG_INF),
                v_rows.reshape(-1, KV, D).astype(cd))

    new = mask_new()
    s, v = scored(k_new, v_new, new)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    z = jnp.sum(e, axis=-1, keepdims=True)
    acc = jnp.einsum("gqtl,lgd->gqtd", e.astype(cd), v,
                     preferred_element_type=jnp.float32)
    if read_kv is not None:
        def body(j, carry):
            m, z, acc = carry
            mask = mask_old(j)
            s, v = scored(*read_kv(j), mask)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            e, keep = jnp.exp(s - m2), jnp.exp(m - m2)
            return (m2, z * keep + jnp.sum(e, axis=-1, keepdims=True),
                    acc * keep + jnp.einsum(
                        "gqtl,lgd->gqtd", e.astype(cd), v,
                        preferred_element_type=jnp.float32))
        m, z, acc = jax.lax.fori_loop(
            0, (n_old + kv_block - 1) // kv_block, body, (m, z, acc))
    return jnp.moveaxis(acc / z, 2, 0).reshape(T, H * D), new


def attend_chunk(q_side, rows, arch: LMArch, offset=0, n_real=None,
                 read_index=None, read_kv=None, n_old: int = 0,
                 index_block: int = 0, kv_block: int = 0):
    """Causal attention of ``T`` new rows over the selection each makes
    among themselves and the ``offset`` rows of earlier positions the
    cache holds (columns ``0 .. n_old - 1`` of which the first
    ``offset`` are real), read a block at a time: ``read_index(j)``
    gives index rows ``j * index_block ..`` as [index_block, lanes],
    ``read_kv(j)`` the K and V rows ``j * kv_block ..``.

    Returns ``(att [T, H * D], picked)``: with ``n_real`` the positions
    row ``n_real - 1`` chose (int32 [min(topk, rows there can be)],
    ascending, -1 where it chose fewer), without it the whole mask over
    the new rows [T, T]."""
    (q, q_i, w), (k_new, v_new, i_new) = q_side, rows
    T = q.shape[0]
    key_new = jnp.where(causal(T),
                        sortable_keys(index_scores(q_i, w, i_new, arch)), 0)
    keys, cols = [key_new], [offset + jnp.arange(T)]
    if read_index is not None:
        def score_block(j, table):
            kk = sortable_keys(index_scores(q_i, w, read_index(j), arch))
            seen = j * index_block + jnp.arange(index_block) < offset
            return jax.lax.dynamic_update_slice(
                table, jnp.where(seen[None, :], kk, 0), (0, j * index_block))
        keys.append(jax.lax.fori_loop(
            0, (offset + index_block - 1) // index_block, score_block,
            jnp.zeros((T, n_old), jnp.uint32)))
        cols.append(jnp.arange(n_old))
    thr, cut = topk_threshold(keys, cols, arch.index_topk,
                              (n_old + T).bit_length())

    def old_mask(j):
        kb = jax.lax.dynamic_slice(keys[1], (0, j * kv_block), (T, kv_block))
        return chosen(kb, (j * kv_block + jnp.arange(kv_block))[None, :],
                      thr, cut)

    att, mask_new = attend_blocks(
        q, k_new, v_new, arch,
        lambda: chosen(key_new, cols[0][None, :], thr, cut), read_kv,
        old_mask, offset, kv_block)
    if n_real is None:
        return att, mask_new
    r = n_real - 1
    row = jnp.concatenate(
        [chosen(jax.lax.dynamic_slice_in_dim(kk, r, 1), cc[None, :],
                jax.lax.dynamic_slice_in_dim(thr, r, 1),
                jax.lax.dynamic_slice_in_dim(cut, r, 1))[0]
         for kk, cc in zip(keys[::-1], cols[::-1])])
    at = jnp.concatenate(cols[::-1])
    n_out = min(arch.index_topk, max(n_old, T))
    hit, = jnp.nonzero(row, size=n_out, fill_value=-1)
    return att, jnp.where(hit >= 0, at[hit], -1).astype(jnp.int32)


def step_top_k(table: Array, k: int):
    """The step's selection: the ``k`` largest of each row of scores and
    their columns.  A sort on this chip; exact, ties to the lower
    column."""
    return jax.lax.top_k(table, k)


def attend_step(q_side, rows, arch: LMArch, positions: Array, score_old,
                read_rows):
    """One new row a slot over what it selects of its cache.
    ``score_old(q_i, w) -> [S, L]`` float32 scores of the cached index
    rows (anything at columns from ``positions`` on); ``read_rows(at
    [S, K]) -> (K rows, V rows)`` [S, K, lanes] of those positions.  A
    slot's own new row is not in the pools yet: its score joins the
    table at its column and its K and V stand in where it is chosen.
    Returns ``(att [S, H * D], chosen positions [S, K] int32, -1 where
    a slot holds fewer)``."""
    (q, q_i, w), (k_new, v_new, i_new) = q_side, rows
    S = q.shape[0]
    H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    cd = k_new.dtype
    table = score_old(q_i, w)
    col = jnp.arange(table.shape[1])[None, :]
    own = slot_index_scores(q_i, w, i_new[:, None, :], arch)[:, 0]
    table = jnp.where(col < positions[:, None], table,
                      jnp.where(col == positions[:, None], own[:, None],
                                NEG_INF))
    k_out = min(arch.index_topk, table.shape[1])
    top, at = step_top_k(table, k_out)
    ok = top > NEG_INF / 2
    k_rows, v_rows = read_rows(at)
    is_own = (at == positions[:, None])[..., None]
    k_rows = jnp.where(is_own, k_new[:, None, :], k_rows)
    v_rows = jnp.where(is_own, v_new[:, None, :], v_rows)
    s = jnp.einsum("sgqd,slgd->sgql",
                   q.reshape(S, KV, H // KV, D).astype(cd),
                   k_rows.reshape(S, k_out, KV, D),
                   preferred_element_type=jnp.float32) * D ** -0.5
    a = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, NEG_INF), axis=-1)
    o = jnp.einsum("sgql,slgd->sgqd", a.astype(cd),
                   v_rows.reshape(S, k_out, KV, D),
                   preferred_element_type=jnp.float32)
    return o.reshape(S, H * D), jnp.where(ok, at, -1).astype(jnp.int32)


def read_pages(pool: Array, layer, pages: Array) -> Array:
    """The rows of ``pages`` [..., n] of one layer as [..., n * page,
    lanes].  The layer is an index beside the pages: ``pool[layer]
    [pages]`` makes XLA copy the layer's whole slice of the pool first."""
    g = pool[jnp.full_like(pages, layer), pages]       # [..., n, page, lanes]
    return g.reshape(g.shape[:-3] + (-1, g.shape[-1]))


def held_index_scores(i_pool: Array, layer, table: Array, q_i: Array,
                      w: Array, n_held, block_pages: int, arch: LMArch
                      ) -> Array:
    """The step's scores [S, L] float32 of the cached index rows, read
    a block of ``block_pages`` pages a slot at a time through ``table``
    [S, pages a slot] up to row ``n_held`` (the fullest slot's): the
    pages HELD, not the window.  Columns never reached stay ``NEG_INF``."""
    s_n, pps = table.shape
    block = block_pages * i_pool.shape[2]

    def body(j, out):
        pages = jax.lax.dynamic_slice(table, (0, j * block_pages),
                                      (s_n, block_pages))
        s = slot_index_scores(q_i, w, read_pages(i_pool, layer, pages), arch)
        return jax.lax.dynamic_update_slice(out, s, (0, j * block))

    return jax.lax.fori_loop(
        0, (n_held + block - 1) // block, body,
        jnp.full((s_n, pps * i_pool.shape[2]), NEG_INF, jnp.float32))


def read_chosen_rows(k_pool: Array, v_pool: Array, layer, table: Array,
                     at: Array):
    """The K and V rows at positions ``at`` [S, K] of each slot, [S, K,
    lanes] each: a gather by page and row through ``table``."""
    page_size = k_pool.shape[2]
    page = jnp.take_along_axis(table, at // page_size, axis=1)
    idx = (jnp.full_like(page, layer), page, at % page_size)
    return k_pool[idx], v_pool[idx]


def forward(params, tokens: Array, arch: LMArch, with_aux: bool = False,
            positions: Optional[np.ndarray] = None):
    """Full forward of ``tokens`` [B, T] by the chunk path with nothing
    cached: logits [B, T, V] float32.  ``positions`` [3, T] (host) are
    the three rotary streams (default: the position, three times).
    ``with_aux`` adds ``attn_mask`` [B, layers, T, T], what each row
    selected."""
    t = tokens.shape[1]
    if positions is None:
        positions = np.broadcast_to(np.arange(t), (3, t))
    rope = rope_rows(arch, positions)

    def one(seq):
        h = _embed(params, seq, arch)
        picks, stats, masks = [], [], []
        for p in params["blocks"]:
            q_side, rows = project(p, h, rope, arch)
            att, mask = attend_chunk(q_side, rows, arch)
            h, pk, st = layer_finish(p, h, att, arch)
            picks.append(pk)
            stats.append(st)
            masks.append(mask)
        out = _logits(params, h, arch)
        if with_aux:
            return out, {**_join_aux(picks, stats, arch, (seq.shape[0],)),
                         "attn_mask": jnp.stack(masks)}
        return out

    return jax.lax.map(one, tokens)


# -- the decode program -------------------------------------------------------------

def sparse_attention(arch: LMArch, page_size: int, pps: int) -> CachedAttention:
    """The attention above over three pools (K rows, V rows, index rows)
    ``[layers, pages, page, lanes]``."""
    L = pps * page_size
    kv_lanes = arch.n_kv_heads * arch.head_dim

    index_pages = block_pages(pps, page_size, INDEX_BLOCK_ROWS)
    kv_pages = block_pages(pps, page_size, KV_BLOCK_ROWS)

    def counts(scored, read, held):
        return jnp.stack([scored, read, held]).astype(jnp.int32)

    def chunk(p, pools, layer, page_table_row, q, rows, offset, n_real):
        k_pool, v_pool, i_pool = pools

        def pages(j, n):
            return jax.lax.dynamic_slice(page_table_row, (j * n,), (n,))

        att, picked = attend_chunk(
            q, rows, arch, offset, n_real,
            read_index=lambda j: read_pages(i_pool, layer,
                                            pages(j, index_pages)),
            read_kv=lambda j: (read_pages(k_pool, layer, pages(j, kv_pages)),
                               read_pages(v_pool, layer, pages(j, kv_pages))),
            n_old=L, index_block=index_pages * page_size,
            kv_block=kv_pages * page_size)
        # whole blocks up to the rows held, and the chunk's own (padded)
        # rows: the masked form reads every K and V block, whatever is
        # chosen
        new = rows[0].shape[0]
        blocks = lambda block: -(-offset // block) * block + new
        return att, {"sparse_stats": counts(blocks(index_pages * page_size),
                                            blocks(kv_pages * page_size),
                                            offset + n_real),
                     "attn_rows": picked}

    def step(p, pools, layer, table, q, rows, positions, active):
        k_pool, v_pool, i_pool = pools
        held = jnp.where(active, positions, 0)
        att, picked = attend_step(
            q, rows, arch, positions,
            lambda q_i, w: held_index_scores(
                i_pool, layer, table, q_i, w, jnp.max(held), index_pages,
                arch),
            lambda at: read_chosen_rows(k_pool, v_pool, layer, table, at))
        # every slot, stepped or not: index blocks up to the fullest
        # slot's row and the slot's own new row, then the gather's rows
        block = index_pages * page_size
        s_n = positions.shape[0]
        scored = s_n * (-(-jnp.max(held) // block) * block + 1)
        return att, {"sparse_stats": counts(
            scored, s_n * picked.shape[1],
            jnp.sum(jnp.where(active, positions + 1, 0))),
            "attn_rows": picked}

    return CachedAttention(
        pool_rows=((kv_lanes,), (kv_lanes,), (index_lanes(arch),)),
        tables=rope_rows(arch, np.broadcast_to(np.arange(L), (3, L))),
        project=lambda p, h, rope: project(p, h, rope, arch),
        attend_chunk=chunk, attend_step=step, d_head=arch.head_dim,
        stats=(("sparse_stats", SPARSE_STATS),), extras=("attn_rows",),
        held_pages=None)


def decode_program(arch: LMArch, page_size: int, max_len: Optional[int]):
    """``ops/kv_cache.DecodeProgram`` over the K, V and index pools."""
    return expert_decode_program(arch, page_size, max_len, sparse_attention)
