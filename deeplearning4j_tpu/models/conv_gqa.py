"""The block of gated short-convolution and rotary grouped-query layers
(``model_type: lfm2_moe``): a gated short convolution everywhere but at
the ``full_attention`` indices of ``layer_types``, a grouped-query
attention with RMSNorm on each head's query and key and plain rotary
positions there; the leading ``n_dense_layers`` with a dense gated
feed-forward, the others expert layers behind the ``noaux_tc`` router
with no shared expert; a tied head; and its decode program.

A layer is ``a = x + Mix(RMSNorm(x))``, ``y = a + FF(RMSNorm(a))``
(``latent_moe.layer_finish``: dense where the tree holds ``W_gate``, else
``parallel/moe.moe_forward_held`` with ``router_eps`` 1e-6 in the
weights' normalisation); logits ``RMSNorm(h_L) E^T`` with ``E`` the
embedding (``latent_moe._logits``).  No bias anywhere.  ``u =
RMSNorm(x)`` below.

**The grouped-query mixer**: ``q = u W_q`` per query head, ``k = u W_k``
and ``v = u W_v`` per key/value head (query head ``a`` reads KV head ``a
// (H / KV)``); RMSNorm over each head's ``q`` and ``k`` (one gain of
``head_dim`` each, shared by the heads); THEN rotary over the whole head,
the halves ``(i, i + head_dim / 2)`` paired, plain frequencies
``rope_theta^(-2i / head_dim)``; causal softmax at ``head_dim^-0.5`` over
every earlier row; ``W_o``.  What is cached: the rotated normed K heads
side by side and the V heads likewise, a row a token in two pools
``[gqa layers, pages, page, KV * head_dim]``, read through the page table
a block of pages at a time over the pages HELD
(``linear_gqa.gqa_over_pages``: the walk Solar's and Granite's layers
use, at this block's scale and without a gate).

**The gated short convolution**: ``[B | C | X] = u W_in`` (three thirds,
in that order); ``z = B * X``; ``c_t = sum_j w_j z_{t - (K - 1) + j}`` a
channel, ``K = conv_L_cache`` taps (causal, depthwise, the last tap on
the current row, rows before the sequence zero, no bias, no activation);
``Mix = (C * c) W_o``.  What a slot holds of such a layer is PER SLOT and
not per token, and it is ONLY a tail: the last ``K - 1`` rows of ``z``
(in the weights' type: every path convolves the values the tail will
hold).  Two forms compute it, the same mathematics:

* *a chunk* (``conv_chunk``: prefill chunks, the full forward): the
  carried tail before the chunk's rows, the taps as ``K`` shifted
  products.  Rows at and beyond ``n_real`` do not enter the tail: it is
  rows ``n_real - (K - 1) .. n_real - 1`` of the inputs, the old tail's
  where the chunk has fewer.
* *the one-row step* (``conv_step``: every slot at once): the tail and
  the new row are the ``K`` rows the taps meet; a slot that is not
  active keeps its tail.

Which family may state what: this block's file may state a leading dense
layer beside layers of two kinds (``linear_gqa`` and ``ssm_gqa`` refuse
``n_dense_layers``), a tied head (as ``ssm_gqa``), and the gate's own
epsilon; it refuses ``conv_bias``, a router without its bias or its
normalisation and any rotary scaling, by name (``LMArch.from_config``).

The decode program is the expert family's one builder
(``models/latent_moe.expert_decode_program``); this module hands it both
mixers.  Beside the expert counts a call reports ``STATE_STATS`` under
the names and meanings ``models/linear_gqa.py`` gives them.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..parallel.moe import init_held_experts
from .arch import LMArch
from .latent_moe import (CachedAttention, _embed, _join_aux, _logits, _mm,
                         expert_decode_program, layer_finish, rms_norm,
                         rope_tables)
from .linear_gqa import STATE_STATS, _counts, gqa_over_pages
from .sparse_gqa import attend_blocks, causal, rotate_half

Array = jax.Array


def slot_state(arch: LMArch, dtype) -> tuple:
    """What a slot holds of ONE convolution layer, ``(shape after
    [slots], dtype)`` each: the convolution's tail in the weights' type,
    and nothing else."""
    return (((arch.conv_L_cache - 1, arch.d_model), jnp.dtype(dtype)),)


# -- parameters ----------------------------------------------------------------

def init_layer(rng: Array, arch: LMArch, kind: str, dense: bool,
               dtype=jnp.float32) -> Dict[str, Array]:
    """One layer's tree.  Matrices N(0, init_std), unit gains; a
    convolution layer's taps U(-K^-0.5, K^-0.5) (a depthwise Conv1d's
    default at ``K`` taps)."""
    d = arch.d_model
    ks = jax.random.split(rng, 10)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    p = {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype)}
    if kind == "gqa":
        H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
        p.update(W_q=normal(ks[0], (d, H * D)), W_k=normal(ks[1], (d, KV * D)),
                 W_v=normal(ks[2], (d, KV * D)), W_o=normal(ks[3], (H * D, d)),
                 q_norm_g=jnp.ones((D,), dtype), k_norm_g=jnp.ones((D,), dtype))
    else:
        K = arch.conv_L_cache
        p.update(W_in=normal(ks[0], (d, 3 * d)), W_o=normal(ks[3], (d, d)),
                 conv_w=jax.random.uniform(ks[4], (K, d), jnp.float32,
                                           -K ** -0.5, K ** -0.5).astype(dtype))
    if dense:
        p.update(W_gate=normal(ks[5], (d, arch.d_ff)),
                 W_up=normal(ks[6], (d, arch.d_ff)),
                 W_down=normal(ks[7], (arch.d_ff, d)))
    else:
        p.update(init_held_experts(
            ks[9], d, arch.moe_d_ff, arch.n_experts, arch.experts_held,
            n_shared=0, std=arch.init_std, dtype=dtype, router=arch.router))
    return p


def init_params(rng: Array, arch: LMArch, dtype=jnp.float32) -> dict:
    """The whole tree; ``blocks`` is a LIST of per-layer trees (the two
    kinds, and a dense and an expert layer, have different leaves), each
    from its own key.  A tied head has no leaf of its own."""
    ke, kh, *kb = jax.random.split(rng, 2 + arch.n_layers)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    params = {"embed": normal(ke, (arch.vocab_size, arch.d_model)),
              "blocks": [init_layer(k, arch, kind, i < arch.n_dense_layers,
                                    dtype)
                         for i, (k, kind) in enumerate(
                             zip(kb, arch.layer_types))],
              "lnf_g": jnp.ones((arch.d_model,), dtype)}
    if not arch.tie_embeddings:
        params["head"] = normal(kh, (arch.d_model, arch.vocab_size))
    return params


# -- the grouped-query mixer ---------------------------------------------------

def gqa_project(p: Dict[str, Array], h: Array, rope, arch: LMArch):
    """First half of a grouped-query layer for rows ``h`` [N, d] whose
    rotary rows are ``rope`` (``(cos, sin)`` [N, head_dim]): ``((q [N, H,
    D] float32, None: no gate), (k row, v row))``, ``q`` and ``k`` normed
    a head and THEN rotated, the rows as the two pools will hold them
    (the weights' type)."""
    cos, sin = (t[:, None, :] for t in rope)
    n = h.shape[0]
    H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    cd = p["W_k"].dtype
    u = rms_norm(h, p["ln1_g"], arch.rms_eps)
    q = rms_norm(_mm(u, p["W_q"]).reshape(n, H, D), p["q_norm_g"],
                 arch.rms_eps)
    k = rms_norm(_mm(u, p["W_k"]).reshape(n, KV, D), p["k_norm_g"],
                 arch.rms_eps)
    q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
    return (q, None), (k.reshape(n, KV * D).astype(cd),
                       _mm(u, p["W_v"]).astype(cd))


# -- the gated short convolution -------------------------------------------------

def conv_inputs(p: Dict[str, Array], h: Array, arch: LMArch):
    """The projection of a convolution layer for rows ``h`` [N, d]: ``(C
    [N, d] float32 the output's gate, z = B * X [N, d] the convolution's
    input in the weights' type)``."""
    d = arch.d_model
    bcx = _mm(rms_norm(h, p["ln1_g"], arch.rms_eps), p["W_in"])
    z = bcx[:, :d] * bcx[:, 2 * d:]
    return bcx[:, d:2 * d], z.astype(p["W_in"].dtype)


def short_conv(p: Dict[str, Array], z_ext: Array, arch: LMArch) -> Array:
    """``c`` [..., N, d] float32 of the rows whose convolution inputs are
    ``z_ext`` [..., K - 1 + N, d] (the ``K - 1`` rows before them first):
    the causal depthwise convolution, the last tap on the row itself."""
    K = arch.conv_L_cache
    n = z_ext.shape[-2] - (K - 1)
    z, w = z_ext.astype(jnp.float32), p["conv_w"].astype(jnp.float32)
    return sum(w[j] * z[..., j:j + n, :] for j in range(K))


def conv_chunk(p: Dict[str, Array], h: Array, state, arch: LMArch,
               offset=0, n_real=None):
    """A convolution layer's mixer over ``T`` new rows ``h`` [T, d] of one
    sequence from ``state`` (``(tail,)`` of that slot; None or ``offset``
    0: from zero).  Only the first ``n_real`` rows are real (None: all).
    Returns ``(what W_o multiplies [T, d], (tail,) as row n_real - 1
    leaves it)``."""
    C, z = conv_inputs(p, h, arch)
    keep = arch.conv_L_cache - 1
    if state is None:
        tail = jnp.zeros((keep, z.shape[-1]), z.dtype)
    else:
        tail = jnp.where(offset == 0, jnp.zeros((), z.dtype), state[0])
    z_ext = jnp.concatenate([tail, z], axis=0)
    if n_real is None:
        n_real = h.shape[0]
    # rows n_real - keep .. n_real - 1 of the inputs, the old tail's where
    # the chunk has fewer
    tail = jax.lax.dynamic_slice_in_dim(z_ext, n_real, keep, axis=0)
    return C * short_conv(p, z_ext, arch), (tail,)


def conv_step(p: Dict[str, Array], h: Array, state, active: Array,
              arch: LMArch):
    """A convolution layer's mixer for one new row a slot (``h`` [S, d])
    over ``state`` = ``(tail [slots, K - 1, d],)``; a slot that is not
    ``active`` keeps its tail.  Returns ``(what W_o multiplies [S, d],
    the state)``."""
    tail, = state
    C, z = conv_inputs(p, h, arch)
    z_ext = jnp.concatenate([tail, z[:, None, :]], axis=1)
    c = short_conv(p, z_ext, arch)[:, 0]
    tail = jnp.where(active[:, None, None], z_ext[:, 1:], tail)
    return C * c, (tail,)


# -- the full forward ------------------------------------------------------------

def forward(params, tokens: Array, arch: LMArch, with_aux: bool = False):
    """Full forward of ``tokens`` [B, T] with nothing cached and zero
    tails: logits [B, T, V] float32."""
    rope = rope_tables(arch, tokens.shape[1], arch.head_dim)

    def one(seq):
        h = _embed(params, seq, arch)
        picks, stats = [], []
        for p, kind in zip(params["blocks"], arch.layer_types):
            if kind == "gqa":
                (q, _), (k, v) = gqa_project(p, h, rope, arch)
                att = attend_blocks(q, k, v, arch,
                                    lambda: causal(seq.shape[0]))[0]
            else:
                att, _ = conv_chunk(p, h, None, arch)
            h, pk, st = layer_finish(p, h, att, arch)
            if pk is not None:
                picks.append(pk)
                stats.append(st)
        out = _logits(params, h, arch)
        if with_aux:
            return out, _join_aux(picks, stats, arch, (seq.shape[0],))
        return out

    return jax.lax.map(one, tokens)


# -- the decode program ---------------------------------------------------------

def mixers(arch: LMArch, page_size: int, pps: int) -> CachedAttention:
    """Both mixers for the builder: the grouped-query layers over a K
    and a V pool ``[gqa layers, pages, page, KV * head_dim]`` with their
    rotary tables, the convolution layers over their per-slot tail."""
    kv_lanes = arch.n_kv_heads * arch.head_dim
    chunk, step = gqa_over_pages(arch, page_size, pps)

    def state_chunk(p, h, state, offset, n_real):
        att, state = conv_chunk(p, h, state, arch, offset, n_real)
        return att, state, {"state_stats": _counts(rows=n_real)}

    def state_step(p, h, state, active):
        att, state = conv_step(p, h, state, active, arch)
        return att, state, {"state_stats": _counts(slots=jnp.sum(active))}

    return CachedAttention(
        pool_rows=((kv_lanes,), (kv_lanes,)),
        tables=rope_tables(arch, pps * page_size, arch.head_dim),
        project=lambda p, h, rope: gqa_project(p, h, rope, arch),
        attend_chunk=chunk, attend_step=step, d_head=arch.head_dim,
        stats=(("state_stats", STATE_STATS),), held_pages=None,
        kinds=tuple("pool" if t == "gqa" else "state"
                    for t in arch.layer_types),
        slot_state=slot_state(arch, arch.param_dtype),
        state_chunk=state_chunk, state_step=state_step)


def decode_program(arch: LMArch, page_size: int, max_len: Optional[int]):
    """``ops/kv_cache.DecodeProgram`` over the K and V pools of the
    grouped-query layers and the per-slot tails of the convolution
    ones."""
    return expert_decode_program(arch, page_size, max_len, mixers)
