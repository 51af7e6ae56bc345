"""The latent-attention / routed-expert block (DeepSeek-V3's modelling
code, which ``model_type: kimi_k2`` reuses) and its decode program.

A layer is ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``.

Attention (MLA), ``u = RMSNorm(x)``: ``c_q = RMSNorm(u W_qa)``,
``[q_nope | q_pe] = c_q W_qb`` per head; ``[c_kv | k_pe] = u W_kva``,
``c_kv = RMSNorm(c_kv)``, ``k_pe`` ONE rotary key shared by all heads;
``[k_nope | v] = c_kv W_kvb`` per head; ``q_pe``/``k_pe`` rotated by
position (YaRN-scaled frequencies); score of head i is
``(q_nope_i . k_nope_i + q_pe_i . k_pe) * softmax_scale``.

**What is cached** is the row ``[c_kv | rotated k_pe]``
(``kv_lora_rank + qk_rope_head_dim`` values a token a layer) — never a
per-head key or value.  Two paths read it, the same mathematics:

* *expanded* (prefill chunks, the full forward): ``k_nope`` and ``v``
  are expanded from the cached rows by ``W_kvb``, the earlier rows a
  block at a time with the softmax carried from block to block;
* *absorbed* (the decode step): ``W_kvb``'s key half is folded into the
  query (``q_nope_i W_kvb,k,i^T`` against ``c_kv``) and its value half
  into the output (the weighted sum of ``c_kv`` through ``W_kvb,v,i``).
  The absorbed query meets the cached rows AS STORED, one 576-wide head
  shared by all query heads: over the pages a slot holds, through the
  page table, by one Mosaic call a layer (``ops/latent_attention.py``);
  over every slot's whole gathered window (``attend_window``) only where
  that kernel cannot take the pool (its ``kept_path``).

Both are MXU products (bf16 operands where the weights are bf16,
float32 accumulation, float32 softmax): the bitwise decode-vs-reencode
contract of ``ops/kv_cache.det_attention`` (ROADMAP C1) does not hold
for this program; agreement with ``reencode`` and the plain reference
is by tolerance (tests/test_latent_moe.py; the benchmark's limits).

The feed-forward of the leading ``n_dense_layers`` is a gated SiLU
(``W_down(silu(W_gate x) * (W_up x))``); the other layers are
``parallel/moe.moe_forward_held``: a router over all experts, the
experts held here, the shared expert.

The decode program is the expert family's one builder
(``expert_decode_program``, which ``models/sparse_gqa.py``,
``models/linear_gqa.py``, ``models/ssm_gqa.py`` and
``models/conv_gqa.py`` share: the entry
points, the layer loop, the write of the new rows, ``layer_finish`` and
the aux read-back), around the attention a block hands it
(:class:`CachedAttention`).  The builder scales embedding, residual and
logits and ties the head where the architecture says so
(``LMArch.embedding_multiplier`` / ``residual_multiplier`` /
``logits_scaling`` / ``tie_embeddings``): only ``models/ssm_gqa.py``'s
family may state those, and ``models/conv_gqa.py``'s the tied head
(``LMArch.from_config`` refuses ``tie_word_embeddings: true`` for this
block, ``sparse_gqa`` and ``linear_gqa`` by name, and none of the three
reads a multiplier), so their programs trace as they did.  A leading
dense layer may be of either kind: one whose mixer keeps per-slot state
(``models/conv_gqa.py``'s) threads its state as an expert layer's does
and reports no picks.  This block's threads ONE latent pool
``[layers, pages, page, latent_lanes]`` through ``prefill`` /
``prefill_at`` / ``step``: a row is
the ``latent_width`` cached values in the next multiple of 128 lanes,
the rest zero.  (At a minor size that is no multiple of its 128-lane
tile the chip's default layout of the array puts the PAGES minor-most,
and every call then transposes the whole pool in and back out: two
whole-pool copies a call in the compiled program.)  A call
only READS the pool while its layers run (the rows of earlier
positions) and attends to its own new rows directly; all layers' new
rows are written by one scatter at the end, so the donated pool is
updated in place and never copied.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import latent_attention
from ..ops.pallas_support import fell_back
from ..parallel.moe import (EXPERT_STATS, gated_silu, init_held_experts,
                            moe_forward_held)
from .arch import LMArch, yarn_mscale

Array = jax.Array
NEG_INF = -1e30


# -- pieces ----------------------------------------------------------------

def rms_norm(x: Array, g: Array, eps: float) -> Array:
    """RMSNorm without bias, in float32; float32 out."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def yarn_inv_freq(arch: LMArch, dim: int = 0) -> np.ndarray:
    """The rotary inverse frequencies [dim / 2] (float64 on the host;
    ``dim`` 0: ``qk_rope_head_dim``): plain ``theta^(-2i/d)`` when
    ``rope_factor`` is 1, otherwise each blended between ``1/f`` and
    ``1/(factor f)`` by the published code's linear ramp between its two
    correction bounds (it widens the upper by 0.001 where they
    coincide)."""
    dim, base = dim or arch.qk_rope_head_dim, arch.rope_theta
    pos_freq = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extra = 1.0 / pos_freq
    if arch.rope_factor <= 1.0:
        return extra
    inter = 1.0 / (arch.rope_factor * pos_freq)

    def correction_dim(rotations):
        return (dim * math.log(arch.rope_original_max_len
                               / (rotations * 2 * math.pi))
                ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(arch.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(arch.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1.0 - mask) + extra * mask


def rope_tables(arch: LMArch, n_pos: int, dim: int = 0):
    """(cos, sin) float32 [n_pos, dim] (``dim`` 0: ``qk_rope_head_dim``):
    the two halves repeat the frequencies; both carry ``mscale /
    mscale_all_dim``."""
    inv = yarn_inv_freq(arch, dim)
    ang = np.outer(np.arange(n_pos, dtype=np.float64), inv)
    emb = np.concatenate([ang, ang], axis=-1)
    m = 1.0
    if arch.rope_factor > 1.0:
        m = (yarn_mscale(arch.rope_factor, arch.rope_mscale)
             / yarn_mscale(arch.rope_factor, arch.rope_mscale_all_dim))
    return (jnp.asarray(np.cos(emb) * m, jnp.float32),
            jnp.asarray(np.sin(emb) * m, jnp.float32))


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate the last axis of ``x`` [..., r] by per-row tables
    broadcastable to it.  As published: the interleaved pairs
    (2i, 2i+1) are first gathered into halves, then ``rotate_half``."""
    r = x.shape[-1]
    x = x.astype(jnp.float32)
    x = jnp.swapaxes(x.reshape(x.shape[:-1] + (r // 2, 2)), -1, -2)
    x = x.reshape(x.shape[:-2] + (r,))
    rot = jnp.concatenate([-x[..., r // 2:], x[..., : r // 2]], axis=-1)
    return x * cos + rot * sin


def _mm(x: Array, w: Array) -> Array:
    """Product with operands in the weight's type, float32 out."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


# -- parameters ------------------------------------------------------------

def init_layer(rng: Array, arch: LMArch, dense: bool,
               dtype=jnp.float32) -> Dict[str, Array]:
    """One layer's tree.  Every matrix N(0, init_std), unit norm gains."""
    d, h = arch.d_model, arch.n_heads
    ka, kb, kc, kd, ke, kf = jax.random.split(rng, 6)
    std = arch.init_std

    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    p = {
        "ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype),
        "W_qa": normal(ka, (d, arch.q_lora_rank)),
        "q_norm_g": jnp.ones((arch.q_lora_rank,), dtype),
        "W_qb": normal(kb, (arch.q_lora_rank, h * arch.qk_head_dim)),
        "W_kva": normal(kc, (d, arch.latent_width)),
        "kv_norm_g": jnp.ones((arch.kv_lora_rank,), dtype),
        "W_kvb": normal(kd, (arch.kv_lora_rank,
                             h * (arch.qk_nope_head_dim + arch.v_head_dim))),
        "W_o": normal(ke, (h * arch.v_head_dim, d)),
    }
    if dense:
        k1, k2, k3 = jax.random.split(kf, 3)
        p.update(W_gate=normal(k1, (d, arch.d_ff)),
                 W_up=normal(k2, (d, arch.d_ff)),
                 W_down=normal(k3, (arch.d_ff, d)))
    else:
        p.update(init_held_experts(
            kf, d, arch.moe_d_ff, arch.n_experts, arch.experts_held,
            arch.n_shared_experts, std=std, dtype=dtype))
    return p


def init_params(rng: Array, arch: LMArch, dtype=jnp.float32) -> dict:
    """The whole tree: ``blocks`` is a LIST of per-layer trees (a
    leading dense layer and expert layers have different leaves)."""
    ke, kh, *kb = jax.random.split(rng, 2 + arch.n_layers)
    std = arch.init_std

    def normal(key, shape):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    return {
        "embed": normal(ke, (arch.vocab_size, arch.d_model)),
        "blocks": [init_layer(k, arch, i < arch.n_dense_layers, dtype)
                   for i, k in enumerate(kb)],
        "lnf_g": jnp.ones((arch.d_model,), dtype),
        "head": normal(kh, (arch.d_model, arch.vocab_size)),
    }


# -- attention -----------------------------------------------------------------

def mla_project(p: Dict[str, Array], h: Array, cos: Array, sin: Array,
                arch: LMArch):
    """First half of the layer, for rows ``h`` [N, d] at the positions
    whose rotary rows are ``cos``/``sin`` [N, r].  Returns ``(q_nope
    [N, H, n], q_pe [N, H, r] rotated, row [N, c + r])`` where ``row``
    is what the cache holds: ``[RMSNorm(c_kv) | rotated k_pe]``, in the
    weights' type (so every reader sees the values the cache will),
    zero-filled from ``latent_width`` to ``latent_lanes``."""
    H, nd, rd = arch.n_heads, arch.qk_nope_head_dim, arch.qk_rope_head_dim
    u = rms_norm(h, p["ln1_g"], arch.rms_eps)
    c_q = rms_norm(_mm(u, p["W_qa"]), p["q_norm_g"], arch.rms_eps)
    q = _mm(c_q, p["W_qb"]).reshape(h.shape[0], H, nd + rd)
    q_pe = apply_rope(q[..., nd:], cos[:, None, :], sin[:, None, :])
    kva = _mm(u, p["W_kva"])
    c_kv = rms_norm(kva[:, : arch.kv_lora_rank], p["kv_norm_g"], arch.rms_eps)
    k_pe = apply_rope(kva[:, arch.kv_lora_rank:], cos, sin)
    pad = jnp.zeros((h.shape[0], arch.latent_lanes - arch.latent_width))
    row = jnp.concatenate([c_kv, k_pe, pad], axis=-1).astype(p["W_kva"].dtype)
    return q[..., :nd], q_pe, row


def _kvb(p, arch: LMArch):
    """``W_kvb`` as [c, H, n + v]."""
    return p["W_kvb"].reshape(arch.kv_lora_rank, arch.n_heads,
                              arch.qk_nope_head_dim + arch.v_head_dim)


def _softmax_pair(s_a: Array, s_b: Array):
    """One softmax over the keys of two score blocks (last axes)."""
    m = jnp.maximum(jnp.max(s_a, axis=-1, keepdims=True),
                    jnp.max(s_b, axis=-1, keepdims=True))
    e_a, e_b = jnp.exp(s_a - m), jnp.exp(s_b - m)
    z = jnp.sum(e_a, axis=-1, keepdims=True) \
        + jnp.sum(e_b, axis=-1, keepdims=True)
    return e_a / z, e_b / z


def attend_expanded(p, q_nope, q_pe, rows_new, arch: LMArch,
                    read_old=None, n_old: Any = 0,
                    block_rows: int = 0) -> Array:
    """Causal attention of ``T`` new rows over themselves and over the
    ``n_old`` rows of earlier positions the cache holds.  Keys and
    values are EXPANDED from the cached rows by ``W_kvb``.

    The earlier rows are read a block at a time: ``read_old(j)`` gives
    rows ``j * block_rows .. (j + 1) * block_rows - 1`` as [block_rows,
    lanes], and a loop over the ``ceil(n_old / block_rows)`` blocks that
    hold any carries the softmax's running maximum, sum and weighted
    values from block to block, so the work follows the context the
    slot really holds and no score matrix wider than a block exists.
    Returns [T, H * v]."""
    c, nd = arch.kv_lora_rank, arch.qk_nope_head_dim
    cd = p["W_kvb"].dtype
    wkvb = _kvb(p, arch)
    t = q_nope.shape[0]
    qn, qp = q_nope.astype(cd), q_pe.astype(cd)

    def scored(rows):
        """Scores [H, T, rows] and values [rows, H, v] of a block."""
        kv = jnp.einsum("lc,chx->lhx", rows[:, :c].astype(cd), wkvb,
                        preferred_element_type=jnp.float32).astype(cd)
        s = jnp.einsum("thn,lhn->htl", qn, kv[..., :nd],
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("thr,lr->htl", qp,
                           rows[:, c:arch.latent_width].astype(cd),
                           preferred_element_type=jnp.float32)
        return s * arch.softmax_scale, kv[..., nd:]

    s_new, v_new = scored(rows_new)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s_new = jnp.where(causal[None], s_new, NEG_INF)
    m = jnp.max(s_new, axis=-1, keepdims=True)               # [H, T, 1]
    e = jnp.exp(s_new - m)
    z = jnp.sum(e, axis=-1, keepdims=True)
    acc = jnp.einsum("htl,lhv->htv", e.astype(cd), v_new,
                     preferred_element_type=jnp.float32)
    if read_old is not None:
        def body(j, carry):
            m, z, acc = carry
            s, v = scored(read_old(j))
            seen = j * block_rows + jnp.arange(block_rows) < n_old
            s = jnp.where(seen[None, None, :], s, NEG_INF)
            m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            e, keep = jnp.exp(s - m2), jnp.exp(m - m2)
            return (m2, z * keep + jnp.sum(e, axis=-1, keepdims=True),
                    acc * keep + jnp.einsum(
                        "htl,lhv->htv", e.astype(cd), v,
                        preferred_element_type=jnp.float32))
        blocks = (n_old + block_rows - 1) // block_rows
        m, z, acc = jax.lax.fori_loop(0, blocks, body, (m, z, acc))
    return jnp.swapaxes(acc / z, 0, 1).reshape(t, -1)


def absorb_query(wkvb, q_nope, q_pe, arch: LMArch) -> Array:
    """The step's query against a cached row as stored: ``q_nope`` [S, H,
    n] through the key half of ``wkvb`` (``_kvb``), ``q_pe`` [S, H, r]
    beside it, zero in the padding lanes: [S, H, latent_lanes] in the
    weights' type."""
    q_abs = jnp.einsum("shn,chn->shc", q_nope.astype(wkvb.dtype),
                       wkvb[..., :arch.qk_nope_head_dim],
                       preferred_element_type=jnp.float32)
    pad = jnp.zeros(q_pe.shape[:-1] + (arch.latent_lanes
                                       - arch.latent_width,))
    return jnp.concatenate([q_abs, q_pe, pad], axis=-1).astype(wkvb.dtype)


def attend_window(q_lat, row_new, rows_old, n_old, arch: LMArch) -> Array:
    """``q_lat`` [S, H, lanes] over ``rows_old`` [S, L, lanes], of which
    the first ``n_old[s]`` are earlier positions, and over the slot's own
    ``row_new`` [S, lanes]: the weighted sum of ``c_kv`` a head, [S, H, c]
    float32."""
    c, cd = arch.kv_lora_rank, q_lat.dtype
    s_old = jnp.einsum("shx,slx->shl", q_lat, rows_old.astype(cd),
                       preferred_element_type=jnp.float32)
    seen = jnp.arange(rows_old.shape[1])[None, :] < n_old[:, None]
    s_old = jnp.where(seen[:, None, :], s_old * arch.softmax_scale, NEG_INF)
    s_new = jnp.einsum("shx,sx->sh", q_lat, row_new.astype(cd),
                       preferred_element_type=jnp.float32
                       )[..., None] * arch.softmax_scale
    a_old, a_new = _softmax_pair(s_old, s_new)
    return jnp.einsum("shl,slc->shc", a_old.astype(cd),
                      rows_old[..., :c].astype(cd),
                      preferred_element_type=jnp.float32) \
        + a_new * row_new[:, None, :c].astype(jnp.float32)


def expand_values(wkvb, o_lat, arch: LMArch) -> Array:
    """``o_lat`` [S, H, c] through the value half of ``wkvb``: [S, H * v]."""
    o = jnp.einsum("shc,chv->shv", o_lat.astype(wkvb.dtype),
                   wkvb[..., arch.qk_nope_head_dim:],
                   preferred_element_type=jnp.float32)
    return o.reshape(o.shape[0], -1)


def attend_absorbed(p, q_nope, q_pe, row_new, rows_old, n_old,
                    arch: LMArch) -> Array:
    """One new row per slot against the cache, ``W_kvb`` ABSORBED:
    ``q_nope`` [S, H, n], ``q_pe`` [S, H, r], ``row_new`` [S, c + r]
    (the slot's own row, not yet in the pool), ``rows_old`` [S, L,
    c + r] of which the first ``n_old[s]`` are earlier positions.  No
    per-head key or value exists anywhere.  Returns [S, H * v]."""
    wkvb = _kvb(p, arch)
    o_lat = attend_window(absorb_query(wkvb, q_nope, q_pe, arch), row_new,
                          rows_old, n_old, arch)
    return expand_values(wkvb, o_lat, arch)


def layer_finish(p, h: Array, att: Array, arch: LMArch,
                 valid: Optional[Array] = None):
    """Second half of the layer: output projection, residual, the
    feed-forward the tree holds (dense, or routed + shared experts).
    ``h`` [N, d] float32.  What the mixer and the feed-forward add to
    the stream is scaled by ``arch.residual_multiplier`` where the file
    states one (``models/ssm_gqa.py``'s family; the others state none and
    trace no product).  Returns ``(h, picks or None, stats or None)``
    (``parallel/moe.moe_forward_held``'s)."""
    r = arch.residual_multiplier
    scaled = (lambda y: y) if r == 1.0 else (lambda y: r * y)
    h = h + scaled(_mm(att, p["W_o"]))
    u = rms_norm(h, p["ln2_g"], arch.rms_eps)
    if "W_gate" in p:
        return h + scaled(gated_silu(u, p["W_gate"], p["W_up"],
                                     p["W_down"])), None, None
    y, picks, stats = moe_forward_held(
        p, u, first_expert=arch.first_expert, k=arch.experts_per_token,
        scaling=arch.routed_scaling_factor, valid=valid, router=arch.router,
        router_eps=arch.router_eps)
    return h + scaled(y), picks, stats


def _embed(params, tokens, arch: LMArch):
    """The tokens' rows of the embedding in float32, times
    ``arch.embedding_multiplier`` where the file states one."""
    h = params["embed"][tokens].astype(jnp.float32)
    return h if arch.embedding_multiplier == 1.0 \
        else arch.embedding_multiplier * h


def _logits(params, h, arch: LMArch):
    """``RMSNorm(h)`` times the head, or times the embedding transposed
    where the head is tied (contracted over the embedding's own minor
    axis: no transposed copy of the table), divided by
    ``arch.logits_scaling`` where the file states one."""
    u = rms_norm(h, params["lnf_g"], arch.rms_eps)
    if arch.tie_embeddings:
        e = params["embed"]
        out = jax.lax.dot_general(
            u.astype(e.dtype), e, (((u.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        out = _mm(u, params["head"])
    return out if arch.logits_scaling == 1.0 else out / arch.logits_scaling


def _join_aux(picks: List[Array], stats: List[Array], arch: LMArch,
              lead: tuple) -> Dict[str, Array]:
    """What a decode-program call reports beside its logits: the counts
    of ``EXPERT_STATS`` summed over the expert layers, and the chosen
    experts [*lead, expert layers, k] (ids ascending)."""
    if not picks:
        k = max(arch.experts_per_token, 1)
        return {"expert_stats": jnp.zeros((len(EXPERT_STATS),), jnp.int32),
                "expert_picks": jnp.zeros(lead + (0, k), jnp.int32)}
    return {"expert_stats": sum(stats[1:], stats[0]),
            "expert_picks": jnp.stack(picks, axis=-2)}


def forward(params, tokens: Array, arch: LMArch, with_aux: bool = False):
    """Full forward of ``tokens`` [B, T] by the expanded path: logits
    [B, T, V] float32 (row p = next-token logits after position p)."""
    cos, sin = rope_tables(arch, tokens.shape[1])

    def one(seq):
        h = _embed(params, seq, arch)
        picks, stats = [], []
        for p in params["blocks"]:
            qn, qp, rows = mla_project(p, h, cos, sin, arch)
            att = attend_expanded(p, qn, qp, rows, arch)
            h, pk, st = layer_finish(p, h, att, arch)
            if pk is not None:
                picks.append(pk)
                stats.append(st)
        out = _logits(params, h, arch)
        if with_aux:
            return out, _join_aux(picks, stats, arch, (seq.shape[0],))
        return out

    return jax.lax.map(one, tokens)


# -- the decode program -----------------------------------------------------------

class CachedAttention(NamedTuple):
    """What a block of the expert family hands ``expert_decode_program``:
    its attention over a paged cache, and nothing else of the program.

    ``pool_rows``: the trailing dims of each pool it threads, one entry
    a row kind (``ops/kv_cache.DecodeProgram.pool_rows``).
    ``tables``: a tree of per-position rotary tables ``[L, ...]``; the
    builder hands ``project`` their rows at the call's positions.
    ``project(p, h, rope) -> (query side, rows)``: first half of the
    layer for rows ``h`` [N, d]; ``rows`` is one ``[N, lanes]`` array a
    pool, what the cache will hold of these positions.
    ``attend_chunk(p, pools, layer, page_table_row, q, rows, offset,
    n_real) -> (att [T, .], extra)``: one slot's ``T`` new rows over
    themselves and the ``offset`` rows the pools hold.
    ``attend_step(p, pools, layer, table, q, rows, positions, active)
    -> (att [S, .], extra)``: one new row a slot over its cache.
    ``extra`` is None or a dict with the keys ``stats`` and ``extras``
    name: a ``stats`` entry (``(key, names of its counts)``) is summed
    over the layers (and a fused horizon's steps) and read onto the
    engine's spans and counters under those names, an ``extras`` entry
    is stacked on a layer axis before its last."""
    pool_rows: tuple
    tables: Any
    project: Callable
    attend_chunk: Callable
    attend_step: Callable
    d_head: int
    stats: tuple = ()
    extras: tuple = ()
    # what the step reads of a slot's window, and the rule of the kernel
    # it reads through (``ops/kv_cache.DecodeProgram.held_pages`` /
    # ``kept_path``)
    held_pages: Optional[bool] = False
    kept_path: Optional[Callable] = None
    # layers of two kinds (models/linear_gqa.py).  ``kinds`` says of each
    # layer whether it leaves rows in the pools ("pool": the callables
    # above, with its index among the pool layers) or keeps per-slot state
    # ("state"); None = every layer has rows in the pools.  ``slot_state``
    # names a state layer's arrays (``DecodeProgram.slot_state``).
    # ``state_chunk(p, h, state, offset, n_real) -> (att [T, .], state,
    # extra)``: one slot's ``T`` new rows from that slot's own ``state``
    # (from zero where ``offset`` is 0), the state returned as the last
    # REAL row leaves it.  ``state_step(p, h, state, active) -> (att
    # [S, .], state, extra)``: one row a slot over ``state`` [S, ...],
    # which a slot that is not active keeps as it was.
    kinds: Optional[tuple] = None
    slot_state: tuple = ()
    state_chunk: Optional[Callable] = None
    state_step: Optional[Callable] = None


def mla_attention(arch: LMArch, page_size: int, pps: int) -> CachedAttention:
    """Latent attention over ONE latent pool ``[layers, pages, page,
    latent_lanes]``: chunks by the expanded path a block of pages at a
    time, the step by the absorbed path over the pages a slot holds, read
    from the pool as stored (``ops/latent_attention.py``, one Mosaic call
    a layer), or over the gathered window where that kernel's
    ``kept_path`` says it cannot take the pool."""
    L = pps * page_size

    def gather(pool, layer, table):
        # the layer is an index beside the pages: ``pool[layer][table]``
        # makes XLA copy the layer's whole slice of the pool first
        g = pool[jnp.full_like(table, layer), table]  # [..., pps, page, w]
        return g.reshape(g.shape[:-3] + (L, g.shape[-1]))

    # a chunk reads the rows before it a block of pages at a time
    block_pages = next(d for d in range(max(1, pps // 8), 0, -1)
                       if pps % d == 0)          # whole blocks tile a slot
    block_rows = block_pages * page_size

    def project(p, h, rope):
        qn, qp, row = mla_project(p, h, *rope, arch)
        return (qn, qp), (row,)

    def attend_chunk(p, pools, layer, page_table_row, q, rows, offset,
                     n_real):
        (pool,), (row,) = pools, rows

        def read_old(j):
            pages = jax.lax.dynamic_slice(page_table_row,
                                          (j * block_pages,), (block_pages,))
            g = pool[jnp.full_like(pages, layer), pages]
            return g.reshape(block_rows, g.shape[-1])
        return attend_expanded(p, *q, row, arch, read_old, offset,
                               block_rows), None

    def attend_step(p, pools, layer, table, q, rows, positions, active):
        (pool,), (row,) = pools, rows
        why = latent_attention.kept_path(pool, pps)
        if why:
            fell_back(f"latent_attention[L={L},lanes={pool.shape[-1]}]", why)
            return attend_absorbed(p, *q, row, gather(pool, layer, table),
                                   positions, arch), None
        wkvb = _kvb(p, arch)
        # a slot that is not active holds nothing: its table is scratch
        o_lat = latent_attention.latent_attention(
            absorb_query(wkvb, *q, arch), row, pool, layer, table,
            jnp.where(active, positions, 0), arch.kv_lora_rank,
            arch.softmax_scale)
        return expand_values(wkvb, o_lat, arch), None

    return CachedAttention(
        pool_rows=((arch.latent_lanes,),), tables=rope_tables(arch, L),
        project=project, attend_chunk=attend_chunk, attend_step=attend_step,
        d_head=arch.qk_head_dim, held_pages=True,
        kept_path=latent_attention.kept_path)


def decode_program(arch: LMArch, page_size: int, max_len: Optional[int]):
    """``ops/kv_cache.DecodeProgram`` over one latent pool."""
    return expert_decode_program(arch, page_size, max_len, mla_attention)


def expert_decode_program(arch: LMArch, page_size: int,
                          max_len: Optional[int], attention):
    """The ONE decode program of the expert family: the entry points,
    the loop over the layers, the write of the new rows, ``layer_finish``
    and what is read back, around the attention ``attention(arch,
    page_size, pages_per_slot)`` gives (a :class:`CachedAttention`).

    ``prefill`` / ``prefill_at`` / ``step`` keep the signature the
    engine calls (``params, k_pages, v_pages, ...``): ``k_pages`` is the
    attention's first pool ``[layers, pages, page, lanes]`` in the
    weights' type, ``v_pages`` the tuple of its others (empty where
    there is one pool).  A call only READS the pools while its layers
    run (the rows of earlier positions) and attends to its own new rows
    directly; all layers' new rows are written at the end, one scatter a
    pool, so the donated pools are updated in place and never copied.
    Each returns one value more than the engine's contract names, the
    aux tree of ``_join_aux``; ``step_multi`` fuses steps and sampling.

    Where the attention names layers that keep per-slot state
    (``CachedAttention.kinds``), that rule holds for the pools only: the
    state rides beside the pools after the first
    (``ops/kv_cache.PoolsAndState``), a chunk takes its slot's part (the
    slot's index is ``prefill`` / ``prefill_at``'s last argument) and
    puts back what its last real row left, a step replaces every active
    slot's; both in place, the arrays being donated with the pools.
    """
    from ..ops.kv_cache import SCRATCH_PAGE, DecodeProgram, PoolsAndState

    if max_len is None:
        max_len = (arch.max_len // page_size) * page_size
    if max_len % page_size or not (0 < max_len <= arch.max_len):
        raise ValueError(
            f"max_len {max_len} must be a positive multiple of page_size "
            f"{page_size} and <= the rotary table ({arch.max_len})")
    L = int(max_len)
    pps = L // page_size
    att = attention(arch, page_size, pps)
    n_layers = arch.n_layers
    kinds = att.kinds or ("pool",) * n_layers
    # a layer's index among those of its kind
    nth = [kinds[:i].count(k) for i, k in enumerate(kinds)]

    def split(first, rest):
        """(the pools, the per-slot state) of what a call was handed."""
        if att.slot_state:
            return (first,) + tuple(rest.pools), rest.state
        return (first,) + tuple(rest), ()

    def joined(first, rest, state):
        """What a call hands back where it took ``first`` and ``rest``."""
        return first, (PoolsAndState(rest, tuple(state)) if att.slot_state
                       else rest)

    def write_rows(pools, page_idx, in_page, rows_all):
        """Every layer's new rows into the pools, after the last read of
        them, by ONE scatter of whole rows a pool with the layer an
        index like the page (a slice over the layers makes XLA transpose
        the whole pool and back; a scatter a layer makes it split the
        pool into its layers and copy each)."""
        out = []
        for j, pool in enumerate(pools):
            rows = jnp.stack([r[j] for r in rows_all]).astype(pool.dtype)
            layer = jnp.arange(rows.shape[0], dtype=jnp.int32)[:, None]
            out.append(pool.at[layer, page_idx[None, :],
                               in_page[None, :]].set(rows))
        return out[0], tuple(out[1:])

    def join(picks, stats, extras, lead):
        aux = _join_aux(picks, stats, arch, lead)
        for name, _ in att.stats:
            per_layer = [e[name] for e in extras]
            aux[name] = sum(per_layer[1:], per_layer[0])
        for name in att.extras:
            aux[name] = jnp.stack([e[name] for e in extras], axis=-2)
        return aux

    def prefill_at(params, first, rest, page_table_row, tokens, n_real,
                   offset, slot=None):
        """One slot's rows at positions offset..offset+Tb-1 (the first
        ``n_real`` real) attending over the ``offset`` rows the pools
        already hold and over themselves; their cache rows are written
        by one scatter a pool; the last real position's logits.
        ``slot``: whose per-slot state the chunk carries on (a program
        that keeps any)."""
        pools, state = split(first, rest)
        state = list(state)
        tb = tokens.shape[0]
        pos = offset + jnp.arange(tb, dtype=jnp.int32)
        at = jnp.clip(pos, 0, L - 1)
        rope = jax.tree_util.tree_map(lambda t: t[at], att.tables)
        valid = jnp.arange(tb) < n_real
        h = _embed(params, tokens, arch)
        rows_all, picks, stats, extras = [], [], [], []
        for i, p in enumerate(params["blocks"]):
            if kinds[i] == "state":
                a, new, extra = att.state_chunk(
                    p, h, tuple(s[slot] for s in state[nth[i]]), offset,
                    n_real)
                state[nth[i]] = tuple(s.at[slot].set(n) for s, n
                                      in zip(state[nth[i]], new))
            else:
                q, rows = att.project(p, h, rope)
                a, extra = att.attend_chunk(p, pools, nth[i], page_table_row,
                                            q, rows, offset, n_real)
                rows_all.append(rows)
            h, pk, st = layer_finish(p, h, a, arch, valid)
            extras.append(extra)
            if pk is not None:
                picks.append(pk[n_real - 1])
                stats.append(st)
        idx = pos // page_size
        page_idx = jnp.where(idx < pps,
                             page_table_row[jnp.clip(idx, 0, pps - 1)],
                             SCRATCH_PAGE)
        first, rest = write_rows(pools, page_idx, pos % page_size, rows_all)
        return *joined(first, rest, state), \
            _logits(params, h[n_real - 1], arch), \
            join(picks, stats, extras, ())

    def prefill(params, first, rest, page_table_row, tokens, n_real,
                slot=None):
        return prefill_at(params, first, rest, page_table_row, tokens,
                          n_real, jnp.int32(0), slot)

    def step(params, first, rest, page_table, tokens, positions, active):
        """One token for every slot.  Idle slots' rows go to the scratch
        page, their picks are not counted and their state stays."""
        pools, state = split(first, rest)
        state = list(state)
        s_n = tokens.shape[0]
        at = jnp.clip(positions, 0, L - 1)
        rope = jax.tree_util.tree_map(lambda t: t[at], att.tables)
        table = jnp.where(active[:, None], page_table, SCRATCH_PAGE)
        h = _embed(params, tokens, arch)
        rows_all, picks, stats, extras = [], [], [], []
        for i, p in enumerate(params["blocks"]):
            if kinds[i] == "state":
                a, state[nth[i]], extra = att.state_step(
                    p, h, state[nth[i]], active)
            else:
                q, rows = att.project(p, h, rope)
                a, extra = att.attend_step(p, pools, nth[i], table, q, rows,
                                           positions, active)
                rows_all.append(rows)
            h, pk, st = layer_finish(p, h, a, arch, active)
            extras.append(extra)
            if pk is not None:
                picks.append(pk)
                stats.append(st)
        page_idx = table[jnp.arange(s_n), at // page_size]
        first, rest = write_rows(pools, page_idx, at % page_size, rows_all)
        return *joined(first, rest, state), _logits(params, h, arch), \
            join(picks, stats, extras, (s_n,))

    def step_multi(params, first, rest, page_table, tokens, positions,
                   active, temps, top_ks, top_ps, seeds, steps, budgets,
                   eos_id, horizon):
        """``horizon.shape[0]`` decode steps in one program: a scan of
        ``step`` with the sampling on the device (the engine's own
        ``ops.sampling.sample_tokens``, keyed ``fold_in(seed, steps + j)``
        as its per-step sampler is, so fusion changes no token).  A slot
        that stops (EOS, budget, a non-finite row) leaves ``alive``: its
        later rows go to the scratch page and its picks are not
        counted.  The counts are summed over the steps; what was chosen
        comes back for every step."""
        from ..ops.sampling import sample_tokens

        summed = ("expert_stats",) + tuple(n for n, _ in att.stats)
        names = summed + ("expert_picks",) + att.extras

        def body(carry, j):
            first, rest, tok, alive = carry
            first, rest, lgs, aux = step(params, first, rest, page_table,
                                         tok, positions + j, alive)
            nxt, fin = sample_tokens(lgs, temps, top_ks, top_ps, seeds,
                                     steps + j)
            alive = alive & fin & (nxt != eos_id) & (j + 1 < budgets)
            return (first, rest, nxt, alive), (
                nxt, fin, lgs, *(aux[n] for n in names))

        (first, rest, _, _), (toks, fins, lgs, *outs) = jax.lax.scan(
            body, (first, rest, tokens, active), horizon)
        return first, rest, toks, fins, lgs, {
            n: jnp.sum(o, axis=0) if n in summed else o
            for n, o in zip(names, outs)}

    def reencode(params, tokens):
        return family_module(arch).forward(params, tokens, arch)

    return DecodeProgram(
        prefill=prefill, step=step, reencode=reencode, n_layers=n_layers,
        n_heads=arch.n_heads, d_head=att.d_head,
        vocab_size=arch.vocab_size, max_len=L, page_size=page_size,
        pages_per_slot=pps, prefill_at=prefill_at, step_multi=step_multi,
        pool_rows=att.pool_rows, pool_dtype=jnp.dtype(arch.param_dtype),
        aux=True, aux_stats=(("expert_stats", EXPERT_STATS),) + att.stats,
        held_pages=att.held_pages, kept_path=att.kept_path,
        kinds=att.kinds, slot_state=att.slot_state)


def family_module(arch: LMArch):
    """The module of ``arch.block`` (``init_params``, ``forward``,
    ``decode_program``)."""
    import importlib
    return importlib.import_module(f"{__package__}.{arch.block}")
