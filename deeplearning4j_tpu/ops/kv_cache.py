"""Paged KV-cache + deterministic attention for autoregressive decode.

The decode engine (serving/decode.py) keeps per-request K/V as explicit
JAX carry state in a **page pool**: one global buffer per layer holding
``n_pages`` fixed-size pages, with a per-slot **page table** of pool
indices.  A request's cache is the pages its table row points at —
allocation/free is host-side free-list bookkeeping (serving/decode.py),
never a device reshape.  A ring buffer is the degenerate case
(``pages_per_slot * page_size`` contiguous pages per slot, never freed
early); the paged layout additionally lets a pool smaller than
``max_slots * pages_per_slot`` oversubscribe slots when request lengths
vary, returning a finished request's pages to the free list the moment
it stops (EOS / max-tokens / deadline).

A pool is ``[n_layers, n_pages, page_size, row_lanes]``: the ``n_heads *
d_head`` cached values of one position laid head-major in ONE minor axis
(``row_lanes``; ``head_lanes`` says how wide a head sits in it).  On a
TPU an array's minor axis is tiled in 128 lanes, and where a pool's
minor axis is no multiple of 128 (a 64-wide ``d_head`` behind an
``n_heads`` axis, a 576-wide latent row) the chip's default layout puts
the PAGES minor-most instead: every program that takes the pool then
transposes all of it on the way in and again on the way out (four 758 MB
copies a decode step for GPT-2 large, half the step: PERF.md section 6,
PR 28).  With the row one lane-aligned axis the default layout is the
natural one and the per-layer scatters update the donated pool in place.

Page id 0 is the **scratch page** by convention: inactive slots' page-
table rows are all-zero, so the fixed-shape decode step can write every
slot unconditionally (no dynamic shapes, zero recompiles) while masked
slots' writes land in scratch and are never read unmasked.

Why a dedicated attention formulation instead of ops/attention.mha:
a served token's logits must not depend on how its row was batched.
On XLA, ``X @ W`` against a shared 2D weight is bitwise independent of
the number of rows — but dot-general attention scores are NOT: lowering
changes with the query count, so row k of a [T,L] score matrix differs
in final ulps from the same row computed alone.  ``det_attention``
therefore computes scores and the weighted sum as broadcast-multiply +
reduce over a trailing axis, whose per-element reduction is independent
of the leading (query) shape, and always attends over the same fixed
key length ``L`` (the slot capacity) with additive ``NEG_INF`` masking
— exp underflows to exact 0.0 for masked keys, and ``0.0 * v`` terms
cannot perturb the sum.  The price is an O(T·L·d) materialized product
instead of an MXU dot.

Who attends how (since PR 30).  ``prefill`` / ``prefill_at`` (a bucket
of query rows over one slot's window) and the ``reencode`` reference
use ``det_attention``, so a prompt's rows agree with the re-encode's
wherever XLA computes two row counts alike.  ``step`` / ``spec_step``
/ ``step_multi`` (one to a few rows a slot, every layer of every token)
do NOT: gathering, relayouting and multiplying every slot's whole
window for them was half the decode step on the chip (PERF.md section
6, PR 30).  They call ``ops/paged_attention.py``, one Mosaic kernel a
layer that reads the pool's rows as stored and only the pages a slot
holds, in float32, summing in the order of the slot's own pages.  Its
result depends on the slot's own rows and length only, so a decode
program compared with itself (co-batched, retried, handed off, fused)
stays bitwise equal; against ``reencode`` tokens are equal and logits
agree to rounding (ROADMAP C1; tests/_decode_checks.py holds the
limit).  An int8 pool, and the Pallas interpreter under ``shard_map``
on cpu, keep the gathered window there too.
"""

from __future__ import annotations

import math
from typing import Any, Callable, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .attention import _NEG_INF

Array = jax.Array

NEG_INF = _NEG_INF  # shared masking convention with ops/attention.py

SCRATCH_PAGE = 0    # pool page 0: write target for masked-out slots

LANES = 128         # the TPU's minor-axis tile


def head_lanes(n_heads: int, d_head: int) -> int:
    """Lanes one head takes in a cached row: ``d_head`` widened (zeros,
    never read) to the least width that makes the row, ``n_heads`` of
    them, a multiple of 128 lanes.  64-wide heads need none at 16 or 20
    heads (1024 and 1280 lanes); one 576-wide latent head sits in 640.
    The padding is per head and not at the row's end so that any split
    of the row by whole heads (tensor-parallel decode shards the lane
    axis) and any page transfer look the same whatever the split."""
    step = LANES // math.gcd(n_heads, LANES)
    return -(-d_head // step) * step


class QuantPages(NamedTuple):
    """Int8 page pool: symmetrically quantized values plus the f32
    scales that ride alongside (``kv_dtype="int8"``).

    ``q``: [n_layers, n_pages, page_size, row_lanes] int8 (the row as
    in an f32 pool: module docstring); ``scale``: [n_layers, n_pages,
    page_size] f32 — one scale per cached row.  Pages fill append-only
    (prefill writes a range, each decode step appends one row), so the
    symmetric scale is computed per ROW at write time: a page-wide amax
    would change as rows arrive and force requantizing rows already
    stored.  Row granularity is the
    page-aligned refinement of per-page quantization that append-only
    writes admit, and every scale lives in the page-indexed side arrays
    so pages still share/free/scrub as a unit.  Dequantization happens
    in ``gather_layer`` (feeding ``det_scores``/``det_weighted_sum``
    f32; an int8 pool keeps the gathered window in the decode step
    too), so attention math is unchanged — int8 trades bits for HBM and
    is gated behind an accuracy envelope (bench ``decode_speed_ab``).
    """

    q: Array
    scale: Array


KVPool = Union[Array, QuantPages]


class KVCache(NamedTuple):
    """Device carry state: the page pools for K and V.

    ``k_pages`` / ``v_pages``: [n_layers, n_pages, page_size, row_lanes]
    with ``row_lanes = n_heads * head_lanes(n_heads, d_head)``, a row's
    heads side by side in one lane-aligned axis (module docstring says
    why), or :class:`QuantPages` when ``kv_dtype="int8"``.  Page tables
    and sequence positions live host-side in the decode engine (tiny
    int arrays passed per call).
    """

    k_pages: KVPool
    v_pages: KVPool


def alloc_cache(n_layers: int, n_pages: int, page_size: int, n_heads: int,
                d_head: int, dtype=jnp.float32,
                kv_dtype: Optional[str] = None) -> KVCache:
    """Zero-filled pool.  ``n_pages`` INCLUDES the scratch page 0.
    ``kv_dtype="int8"`` allocates int8 value pools with f32 row scales
    (a zero scale dequantizes untouched rows to the same 0.0 an f32
    pool starts with)."""
    shape = (n_layers, n_pages, page_size,
             n_heads * head_lanes(n_heads, d_head))
    if kv_dtype in ("int8", "i8"):
        def pool():
            return QuantPages(jnp.zeros(shape, jnp.int8),
                              jnp.zeros(shape[:3], jnp.float32))
        return KVCache(pool(), pool())
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


class PoolsAndState(NamedTuple):
    """What rides where the engine's contract has ``v_pages`` for a
    program with per-slot state (``DecodeProgram.slot_state``): the
    pools after the first, and beside them the state that no page table
    reaches.  ``state`` holds, for each layer that keeps one, a tuple of
    arrays ``[slots, ...]`` (one an entry of ``slot_state``): an array a
    layer and not a layer axis, so that a step replaces each whole, in
    place, and a chunk updates one slot's part of it.  Donated, reset
    and handed on with the pools; a page scrub leaves it alone."""

    pools: tuple
    state: tuple


def alloc_pools(prog: "DecodeProgram", n_pages: int,
                kv_dtype: Optional[str] = None,
                slots: Optional[int] = None) -> KVCache:
    """The pools ``prog`` threads, zero-filled: shape and dtype are the
    program's own (``pool_rows`` / ``pool_dtype``).  A program that names
    its rows gets one pool a row kind: the first where the engine's
    contract has ``k_pages``, the others as a tuple where it has
    ``v_pages`` (empty for a single latent pool), so every pool lives,
    is donated, scrubbed and reset with the same pages.  The pools'
    layer axis is as long as the layers that have rows in them (the
    ``"pool"`` entries of ``kinds``).  A program with per-slot state gets
    it, for every ``"state"`` layer and ``slots`` slots, beside those
    pools (:class:`PoolsAndState`)."""
    if prog.pool_rows is None:
        return alloc_cache(prog.n_layers, n_pages, prog.page_size,
                           prog.n_heads, prog.d_head,
                           dtype=prog.pool_dtype or jnp.float32,
                           kv_dtype=kv_dtype)
    if kv_dtype in ("int8", "i8"):
        raise ValueError("int8 KV is not carried by this decode program")
    dtype = prog.pool_dtype or jnp.float32
    kinds = prog.kinds or ("pool",) * prog.n_layers
    layers = kinds.count("pool")
    first, *rest = (jnp.zeros((layers, n_pages, prog.page_size)
                              + tuple(row), dtype)
                    for row in prog.pool_rows)
    if not prog.slot_state:
        return KVCache(first, tuple(rest))
    if slots is None:
        raise ValueError("a program with per-slot state needs the number "
                         "of slots to allocate it for")
    state = tuple(tuple(jnp.zeros((slots,) + tuple(shape), dt)
                        for shape, dt in prog.slot_state)
                  for _ in range(kinds.count("state")))
    return KVCache(first, PoolsAndState(tuple(rest), state))


def state_nbytes(cache) -> int:
    """Resident bytes of the per-slot state a cache carries (0 without)."""
    rest = cache[1]
    return pool_nbytes(rest.state) if isinstance(rest, PoolsAndState) else 0


def pool_nbytes(cache) -> int:
    """Resident bytes of a cache (pool values + any quant scales) — the
    sessions-at-fixed-HBM arithmetic in bench ``decode_speed_ab``."""
    return int(sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(cache)))


def _quantize_rows(kv: Array) -> tuple:
    """Per-row symmetric int8: ``kv`` [..., H, d] → (int8 values,
    f32 scales [...]) with scale = amax/127 (ops/quantize.py scheme;
    zero rows get scale 1.0 so dequant stays exact-zero).  A non-finite
    row propagates through its SCALE, so poison isolation still sees
    NaN after dequantization."""
    amax = jnp.max(jnp.abs(kv), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(kv / scale[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages a request of ``n_tokens`` total (prompt + generated) needs."""
    return max(1, math.ceil(n_tokens / page_size))


# -- pool read/write (pure; all shapes static) ----------------------------


def _pool_values(pages: KVPool) -> Array:
    return pages.q if isinstance(pages, QuantPages) else pages


def _as_rows(kv: Array, row_lanes: int) -> Array:
    """[..., H, d] -> [..., row_lanes]: each head zero-filled to its
    ``head_lanes``, the heads side by side."""
    h, d = kv.shape[-2:]
    pad = row_lanes // h - d
    if pad:
        kv = jnp.pad(kv, [(0, 0)] * (kv.ndim - 1) + [(0, pad)])
    return kv.reshape(kv.shape[:-2] + (row_lanes,))


def _pool_set(pages: KVPool, layer, page_idx, slot_idx, kv: Array) -> KVPool:
    """Scatter f32 rows ``kv`` [..., H, d] into an f32 or int8 pool
    (quantizing on write)."""
    lanes = _pool_values(pages).shape[3]
    if isinstance(pages, QuantPages):
        q, sc = _quantize_rows(kv)
        return QuantPages(
            pages.q.at[layer, page_idx, slot_idx].set(_as_rows(q, lanes)),
            pages.scale.at[layer, page_idx, slot_idx].set(sc))
    return pages.at[layer, page_idx, slot_idx].set(_as_rows(kv, lanes))


def write_prefill(pages: KVPool, layer: int, page_table_row: Array,
                  kv: Array, offset=0) -> KVPool:
    """Scatter a prompt's projected rows into one slot's pages.

    ``page_table_row`` [pages_per_slot] int32, ``kv`` [T, H, d] written
    at positions ``offset``..``offset+T-1`` (``offset`` defaults to 0;
    a prefix-cache suffix prefill passes the matched token count, a
    page multiple).  Positions beyond the prompt's real length are
    garbage-but-finite and masked by the step bias until overwritten by
    the decode steps that reach them; positions past the slot's page
    capacity (an offset prefill's bucket padding can overshoot) are
    routed to the scratch page.
    """
    t = kv.shape[0]
    page_size = _pool_values(pages).shape[2]
    pps = page_table_row.shape[0]
    pos = offset + jnp.arange(t, dtype=jnp.int32)
    idx = pos // page_size
    page_idx = jnp.where(idx < pps,
                         page_table_row[jnp.clip(idx, 0, pps - 1)],
                         SCRATCH_PAGE)
    return _pool_set(pages, layer, page_idx, pos % page_size, kv)


def write_step(pages: KVPool, layer: int, page_table: Array, positions: Array,
               kv: Array) -> KVPool:
    """Scatter one token per slot: ``page_table`` [S, pages_per_slot],
    ``positions`` [S], ``kv`` [S, H, d].  Masked slots are routed to the
    scratch page by the caller (their table rows are zeroed)."""
    page_size = _pool_values(pages).shape[2]
    s = jnp.arange(page_table.shape[0], dtype=jnp.int32)
    page_idx = page_table[s, positions // page_size]
    return _pool_set(pages, layer, page_idx, positions % page_size, kv)


def write_tokens(pages: KVPool, layer: int, page_table: Array,
                 positions: Array, kv: Array) -> KVPool:
    """Scatter a RANGE of tokens per slot — the speculative-verify
    write.  ``page_table`` [S, pages_per_slot], ``positions`` [S] (the
    absolute position of each slot's first row), ``kv`` [S, T, H, d]
    written at positions ``positions[s]``..``positions[s]+T-1``.  Rows
    past the slot's page capacity are routed to the scratch page (a
    fixed-k speculative step near ``max_len`` overshoots by
    construction — those proposals are never committed)."""
    page_size = _pool_values(pages).shape[2]
    s_n, pps = page_table.shape
    t_n = kv.shape[1]
    pos = positions[:, None] + jnp.arange(t_n, dtype=jnp.int32)[None, :]
    idx = pos // page_size
    s_ix = jnp.arange(s_n, dtype=jnp.int32)[:, None]
    page_idx = jnp.where(idx < pps,
                         page_table[s_ix, jnp.clip(idx, 0, pps - 1)],
                         SCRATCH_PAGE)
    return _pool_set(pages, layer, page_idx, pos % page_size, kv)


def gather_layer(pages: KVPool, layer: int, page_table: Array,
                 heads: Tuple[int, int]) -> Array:
    """[S, pages_per_slot] table -> [S, L, H, d] contiguous f32 view of
    one layer's cached rows (L = pages_per_slot * page_size; ``heads`` =
    (H, d) of the rows this pool holds, which its lanes alone do not
    tell).  Int8 pools dequantize here — ``det_scores`` /
    ``det_weighted_sum`` always see f32, so the attention math is
    dtype-agnostic."""
    h, d = heads
    # the layer as an index of ONE gather: ``pages[layer][page_table]``
    # copies the layer's whole slice of the pool before it gathers
    at = (jnp.full_like(page_table, layer), page_table)
    if isinstance(pages, QuantPages):
        g = (pages.q[at].astype(jnp.float32)
             * pages.scale[at][..., None])
    else:
        g = pages[at]                     # [S, pps, page, row_lanes]
    s, pps, page, lanes = g.shape
    return g.reshape(s, pps * page, h, lanes // h)[..., :d]


def scrub_pool(pages: KVPool, ids: Array) -> KVPool:
    """Zero the given page ids — values AND scales for int8 pools (a
    stale scale would re-scale the next tenant's rows).  Per-slot state
    beside the pools has no pages and stays."""
    if isinstance(pages, PoolsAndState):
        return pages._replace(pools=scrub_pool(pages.pools, ids))
    return jax.tree_util.tree_map(lambda a: a.at[:, ids].set(0), pages)


# -- page transfer (disaggregated prefill/decode) --------------------------


def gather_pages(pages: KVPool, ids: Array) -> KVPool:
    """Extract page ids as a dense payload [n_layers, len(ids), ...] —
    the device half of a prefill→decode page transfer.  Tree-aware like
    ``scrub_pool`` (int8 pools carry values AND scales), so the payload
    is bit-exact: f32 rows copy verbatim, int8 rows copy q and scale
    verbatim (dequantization happens only at attention time on the
    receiving host, same as locally).  Duplicate ids are harmless — the
    fixed-shape extract executable pads with repeats."""
    return jax.tree_util.tree_map(lambda a: a[:, ids], pages)


def set_pages(pages: KVPool, ids: Array, payload: KVPool) -> KVPool:
    """Scatter a gathered payload back at (generally DIFFERENT) page
    ids — the attach half of a transfer after page-table remap.  Padding
    and prefix-deduped entries must point at the scratch page with
    all-zero payload rows: scratch is never read unmasked, so which
    duplicate scatter wins there is immaterial."""
    return jax.tree_util.tree_map(
        lambda a, p: a.at[:, ids].set(p), pages, payload)


class PageTransfer(NamedTuple):
    """One request's extracted KV pages as a host-side transfer unit.

    ``n_pages`` real pages (payload rows beyond it, if any, are
    padding); ``k`` / ``v`` are numpy payloads shaped
    [n_layers, n_pages, page_size, row_lanes], the pool's own rows (any
    lane padding rides along, zero) — plain f32 arrays,
    or :class:`QuantPages` of numpy arrays (int8 values + f32 row
    scales) when the pool is int8.  ``pack_transfer`` /
    ``unpack_transfer`` give the wire form; the round trip is bitwise
    for f32 and exact on (q, scale) for int8."""

    n_pages: int
    k: Any
    v: Any


_TRANSFER_MAGIC = b"KVPX1\n"


def _transfer_arrays(t: PageTransfer):
    out = []
    for name, side in (("k", t.k), ("v", t.v)):
        if isinstance(side, QuantPages):
            out.append((name + ".q", side.q))
            out.append((name + ".scale", side.scale))
        else:
            out.append((name, side))
    return out


def transfer_nbytes(t: PageTransfer) -> int:
    """Payload bytes a transfer puts on the wire (header excluded)."""
    return int(sum(np.asarray(a).nbytes for _, a in _transfer_arrays(t)))


def pack_transfer(t: PageTransfer) -> bytes:
    """Serialize a :class:`PageTransfer`: a json header (names, dtypes,
    shapes, page count) followed by the raw array bytes in header
    order.  No pickling — the wire form is self-describing and safe to
    unpack from an untrusted peer (``unpack_transfer`` validates)."""
    import json
    arrs = [(n, np.ascontiguousarray(np.asarray(a)))
            for n, a in _transfer_arrays(t)]
    header = json.dumps({
        "n_pages": int(t.n_pages),
        "arrays": [{"name": n, "dtype": a.dtype.name, "shape": a.shape}
                   for n, a in arrs],
    }).encode()
    body = b"".join(a.tobytes() for _, a in arrs)
    return (_TRANSFER_MAGIC + len(header).to_bytes(8, "big")
            + header + body)


def unpack_transfer(data: bytes) -> PageTransfer:
    """Inverse of :func:`pack_transfer`.  Raises ``ValueError`` on any
    truncated/corrupt input — the decode host fails the ONE request the
    bad bytes belong to, before any page allocation, so its free-list
    partition is untouched."""
    import json
    m = len(_TRANSFER_MAGIC)
    if len(data) < m + 8 or data[:m] != _TRANSFER_MAGIC:
        raise ValueError("not a KV page transfer (bad magic)")
    hlen = int.from_bytes(data[m:m + 8], "big")
    if len(data) < m + 8 + hlen:
        raise ValueError("truncated page transfer (header)")
    try:
        header = json.loads(data[m + 8:m + 8 + hlen])
        descs = header["arrays"]
        n_pages = int(header["n_pages"])
    except (ValueError, KeyError, TypeError) as e:
        raise ValueError(f"corrupt page transfer header: {e}") from e
    off = m + 8 + hlen
    parts: dict = {}
    for d in descs:
        dt = np.dtype(d["dtype"])
        shape = tuple(int(x) for x in d["shape"])
        nbytes = int(dt.itemsize * math.prod(shape)) if shape else dt.itemsize
        if len(data) < off + nbytes:
            raise ValueError(f"truncated page transfer (array {d['name']})")
        parts[d["name"]] = np.frombuffer(
            data[off:off + nbytes], dtype=dt).reshape(shape)
        off += nbytes

    def _side(name):
        if name in parts:
            return parts[name]
        if name + ".q" in parts and name + ".scale" in parts:
            return QuantPages(parts[name + ".q"], parts[name + ".scale"])
        raise ValueError(f"page transfer missing {name!r} payload")

    return PageTransfer(n_pages=n_pages, k=_side("k"), v=_side("v"))


# -- deterministic attention ----------------------------------------------


def det_scores(q: Array, k: Array) -> Array:
    """[B,H,Tq,d] x [B,H,L,d] -> [B,H,Tq,L] via broadcast-multiply +
    trailing-axis reduce: per-element bits independent of Tq (a
    dot-general's are not — see module docstring)."""
    return jnp.sum(q[:, :, :, None, :] * k[:, :, None, :, :], axis=-1)


def det_weighted_sum(p: Array, v: Array) -> Array:
    """[B,H,Tq,L] x [B,H,L,d] -> [B,H,Tq,d]; exact-zero weights (masked
    keys) contribute exact zeros regardless of the garbage in v."""
    return jnp.sum(p[:, :, :, :, None] * v[:, :, None, :, :], axis=-2)


def det_attention(q: Array, k: Array, v: Array, bias: Array) -> Array:
    """Row-bitwise-deterministic attention over a FIXED key length.

    ``q`` [B,H,Tq,d]; ``k``/``v`` [B,H,L,d]; ``bias`` broadcastable to
    [B,H,Tq,L] with 0 on visible keys and ``NEG_INF`` elsewhere.  Every
    caller (prefill / decode step / re-encode reference) must use the
    same L so the softmax reduces over identical row lengths.
    """
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = det_scores(q, k) * scale + bias
    p = jax.nn.softmax(s, axis=-1)
    return det_weighted_sum(p, v)


class DecodeProgram(NamedTuple):
    """The pure functions + static config a model hands the decode
    engine (``ShardedTransformerLM.decode_program()``).  All fns are
    shape-polymorphic; the engine fixes shapes at AOT-warmup time.

      prefill(params, k_pages, v_pages, page_table_row, tokens, n_real)
          -> (k_pages, v_pages, logits [V])   one slot, bucketed length
      step(params, k_pages, v_pages, page_table, tokens, positions,
           active) -> (k_pages, v_pages, logits [S, V])   all slots
      reencode(params, tokens [B, L]) -> logits [B, L, V]
          the full-forward reference the bit-identity gate compares to

    Optional decode-speed entry points (``None`` when the model does
    not provide them; the engine falls back to the plain paths):

      prefill_at(params, k_pages, v_pages, page_table_row, tokens,
                 n_real, offset) -> (k_pages, v_pages, logits [V])
          suffix prefill for a prefix-cache hit: rows land at absolute
          positions offset..offset+Tb-1 and attend over the shared
          prefix pages already in the pool
      spec_step(params, k_pages, v_pages, page_table, tokens [S, T],
                positions [S], active [S])
          -> (k_pages, v_pages, logits [S, T, V])
          speculative verify: score T tokens per slot in one call,
          writing their K/V rows (overflow rows route to scratch)
      step_multi(params, k_pages, v_pages, page_table, tokens,
                 positions, active, temps [S], top_ks [S], top_ps [S],
                 seeds [S], steps [S], budgets [S], eos_id, horizon [H])
          -> (k_pages, v_pages, tokens [H, S], finite [H, S],
              logits [H, S, V])
          fused multi-step decode: ``lax.scan`` of the step body over
          ``horizon`` (an int32 arange whose LENGTH is the fused
          horizon H), with sampling device-resident
          (``ops.sampling.sample_tokens`` keyed ``fold_in(seed,
          steps + j)``) so the host syncs once per H tokens.  Per-slot
          EOS (token == eos_id; pass -1 to disable) / token-budget /
          poison masking runs on device: a finished slot's page-table
          row zeroes, routing its remaining writes to the scratch page,
          so live slots' bits are untouched and fusion stays
          bit-identical to H plain steps.
    """

    prefill: Callable[..., Any]
    step: Callable[..., Any]
    reencode: Callable[..., Any]
    n_layers: int
    n_heads: int
    d_head: int
    vocab_size: int
    max_len: int            # L: fixed key length = pages_per_slot * page_size
    page_size: int
    pages_per_slot: int
    prefill_at: Any = None
    spec_step: Any = None
    step_multi: Any = None
    # tensor-parallel degree of the program's executables: >1 means the
    # fns are shard_map'd over the mesh's "data" axis (heads + page pool
    # sharded, logits replicated) — see parallel/transformer.py
    tp: int = 1
    # the row kinds of a program whose cache is not a K and a V pool of
    # n_heads heads of d_head (None): one entry a pool, the trailing dims
    # after [layers, pages, page]; and the pools' dtype (None = float32).
    # The engine threads the first pool as ``k_pages`` and a tuple of
    # the others as ``v_pages``: one latent pool and an empty tuple
    # (models/latent_moe.py), or K rows, then V rows and index rows
    # (models/sparse_gqa.py).
    pool_rows: Optional[tuple] = None
    pool_dtype: Any = None
    # True: prefill / prefill_at / step return a fourth value, a dict of
    # small arrays the engine reads back with the tokens
    # (``expert_picks`` [..., expert layers, k], and the int32 vectors
    # of counts that ``aux_stats`` names: ``(key, names)`` pairs, each
    # count read onto the step's or chunk's span and the engine's
    # counter of its name)
    aux: bool = False
    aux_stats: tuple = ()
    # what ``kv_pages_read`` says of step / spec_step / step_multi.
    # True: they attend through a kernel that reads only the pages a
    # slot holds wherever it takes the pool: ops/paged_attention.py, or
    # the kernel whose rule ``kept_path`` names (``(pool, pages_per_slot,
    # tp) -> why the gathered window runs instead, or None``:
    # ops/latent_attention.py for models/latent_moe.py); False: they read
    # every slot's whole window; None: they read rows, not pages, and
    # count them in ``aux_stats`` (models/sparse_gqa.py): the engine
    # reports no ``kv_pages_read``
    held_pages: Optional[bool] = False
    kept_path: Optional[Callable[..., Optional[str]]] = None
    # which layers have rows in the pools (``"pool"``) and which keep
    # state that is PER SLOT and not per token (``"state"``,
    # models/linear_gqa.py), one entry a layer (None: every layer has
    # rows); ``slot_state`` names a state layer's arrays, one ``(shape
    # after [slots], dtype)`` each.  ``alloc_pools`` makes them beside the
    # pools
    # (:class:`PoolsAndState`, where the contract has ``v_pages``); every
    # token replaces them, so ``prefill`` / ``prefill_at`` take the slot's
    # index as one argument more (a chunk at offset 0 starts from zero
    # state whatever the slot held, a later one from what the chunk
    # before left at its last REAL row), and ``step`` / ``step_multi``
    # leave the state of a slot that is not active as it was
    kinds: Optional[tuple] = None
    slot_state: tuple = ()
