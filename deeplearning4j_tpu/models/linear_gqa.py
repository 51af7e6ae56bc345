"""The block whose layers are of two kinds (``model_type: solar_open2``):
a gated grouped-query attention WITHOUT positions at the ``gqa_layers``
indices, a gated delta-rule linear attention everywhere else, every layer
an expert layer with a shared expert; and its decode program.

A layer is ``h = x + Mix(RMSNorm(x))``, ``y = h + Experts(RMSNorm(h))``
(``parallel/moe.moe_forward_held`` behind the ``noaux_tc`` router,
``latent_moe.layer_finish``).  ``u = RMSNorm(x)`` below.

**The grouped-query mixer**: ``q = u W_q`` per query head, ``k = u W_k``
and ``v = u W_v`` per key/value head (query head ``a`` reads KV head ``a
// (H / KV)``), no rotary and no other position, causal softmax at scale
``head_dim^-0.5`` over every earlier row, ``W_o (sigmoid(u W_g) * att)``.
What is cached: the K heads side by side and the V heads likewise, a row
a token in two pools ``[gqa layers, pages, page, KV * head_dim]`` that
live and die with the slot's pages.  A chunk and the step both read them
through the page table a block of pages at a time over the pages HELD,
with the softmax carried from block to block
(``sparse_gqa.attend_blocks`` / ``read_pages``, without the selection).
The step walks its slots in groups of ``STEP_GROUP`` taken in the order
of the rows they hold, each group as far as ITS fullest slot: one walk
of every slot to the fullest slot's row reads a 1,024-row prompt's slot
as far as the 16,384-row prompt's beside it, several times the rows
held, and how many depends on which answer met which prompt.

**The linear mixer** (gated delta rule with a decay a key channel):
``q, k, v = SiLU(conv(u W_q)), SiLU(conv(u W_k)), SiLU(conv(u W_v))`` with
a causal depthwise convolution of ``conv_kernel`` taps a channel; ``q``
and ``k`` L2-normalised a head, ``q`` times ``dim^-0.5``; log-decay ``g_t
= -exp(A_h) softplus(u W_f1 W_f2 + b_dt)`` a key channel; ``beta_t = 2
sigmoid(u W_b)`` a head (the transition's eigenvalue along ``k`` is ``1 -
beta`` in (-1, 1)); a state ``S`` [dim, dim] a head in float32,

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

then ``W_o (RMSNorm_head(o_t) * sigmoid(u W_g1 W_g2))``.  What a slot
holds of such a layer is PER SLOT and not per token: ``S`` and the last
``conv_kernel - 1`` rows of the three convolutions' inputs (in the
weights' type: every path convolves the values the tail will hold).  Two
forms compute it, the same mathematics:

* *the chunked scan* (``delta_scan``: prefill chunks, the full forward):
  sub-chunks of ``SUB_CHUNK`` rows.  Inside one, with ``G`` the running
  sum of ``g``, the delta rule's corrections ``w_t = beta_t (v_t - (Diag
  (exp g_t) S_{t-1})^T k_t)`` solve ``(I + Diag(beta) A) W = Diag(beta)
  (V - Kbar S_0)``, ``A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])``
  for ``i < t``: a unit lower-triangular system, solved by forward
  substitution for all sub-chunks at once against ``[V | Kbar]`` (the
  part that needs ``S_0`` is a product).  The state is carried between
  sub-chunks: ``o = Qbar S_0 + B W``, ``S_C = Diag(exp G_C) S_0 + Ktil^T
  W``.  Decays are only ever combined as ``exp(G_t - G_i)`` for ``t >=
  i`` (at most 1): dividing by a cumulative product overflows float32
  within one sub-chunk at the decays the bias allows.  Rows at and beyond
  ``n_real`` are masked (``beta = 0``, ``g = 0``): they leave the state
  as it was.
* *the one-row update* (``delta_step``: the step, every slot at once):
  the recurrence as written, the state read twice and written once.

Every product with the state runs in float32 (``HIGHEST``): the state
is stated float32 and what rounds it is held against
(benchmarks/configs/solar-open2-250b.json ``limits_from``).

The decode program is the expert family's one builder
(``models/latent_moe.expert_decode_program``); this module hands it both
mixers.  Beside the expert counts a call reports ``STATE_STATS``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..parallel.moe import init_held_experts
from .arch import LMArch
from .latent_moe import (NEG_INF, CachedAttention, _embed, _join_aux,
                         _logits, _mm, expert_decode_program, layer_finish,
                         rms_norm)
from .sparse_gqa import (KV_BLOCK_ROWS, attend_blocks, block_pages, causal,
                         read_pages)

Array = jax.Array
HIGHEST = jax.lax.Precision.HIGHEST

#: what the mixers count a call, in this order (int32 [4]), summed over
#: the layers (and a fused horizon's steps): active slots stepped a linear
#: layer, real rows scanned a linear layer, K (and as many V) rows the
#: stepped slots or the chunk's slot hold a grouped-query layer, rows the
#: block walk read of them (whole blocks up to the fullest slot's of its
#: group, of every slot of a step; a chunk's own rows too)
STATE_STATS = ("state_slots_stepped", "state_rows_scanned", "kv_rows_held",
               "kv_rows_read")

#: rows of a sub-chunk of the scan
SUB_CHUNK = 64

#: slots the step's block walk takes together (all of them where the
#: slots are no multiple of it)
STEP_GROUP = 4


def slot_state(arch: LMArch, dtype) -> tuple:
    """What a slot holds of ONE linear layer, ``(shape after [slots],
    dtype)`` each: the state in float32, the convolutions' tail in the
    weights' type."""
    n, d = arch.linear_n_heads, arch.linear_head_dim
    return (((n, d, d), jnp.dtype(jnp.float32)),
            ((arch.conv_kernel - 1, 3 * n * d), jnp.dtype(dtype)))


# -- parameters ----------------------------------------------------------------

def init_layer(rng: Array, arch: LMArch, kind: str,
               dtype=jnp.float32) -> Dict[str, Array]:
    """One layer's tree.  Matrices N(0, init_std), unit gains; a linear
    layer's taps U(-0.5, 0.5) (a depthwise Conv1d's default at 4 taps),
    ``A_log = log U(1, 16)`` a head and ``dt_bias`` with ``softplus``
    log-uniform in [0.001, 0.1] a channel, both float32."""
    d = arch.d_model
    ks = jax.random.split(rng, 16)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    p = {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype)}
    if kind == "gqa":
        H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
        p.update(W_q=normal(ks[0], (d, H * D)), W_k=normal(ks[1], (d, KV * D)),
                 W_v=normal(ks[2], (d, KV * D)), W_g=normal(ks[3], (d, H * D)),
                 W_o=normal(ks[4], (H * D, d)))
    else:
        n, dl = arch.linear_n_heads, arch.linear_head_dim
        c = n * dl
        dt = jnp.exp(jax.random.uniform(
            ks[10], (c,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        p.update(
            W_q=normal(ks[0], (d, c)), W_k=normal(ks[1], (d, c)),
            W_v=normal(ks[2], (d, c)), W_o=normal(ks[4], (c, d)),
            conv_w=jax.random.uniform(ks[5], (arch.conv_kernel, 3 * c),
                                      jnp.float32, -0.5, 0.5).astype(dtype),
            W_f1=normal(ks[6], (d, dl)), W_f2=normal(ks[7], (dl, c)),
            W_g1=normal(ks[8], (d, dl)), W_g2=normal(ks[9], (dl, c)),
            W_b=normal(ks[11], (d, n)),
            A_log=jnp.log(jax.random.uniform(ks[12], (n,), jnp.float32,
                                             1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            o_norm_g=jnp.ones((dl,), dtype))
    p.update(init_held_experts(
        ks[15], d, arch.moe_d_ff, arch.n_experts, arch.experts_held,
        arch.n_shared_experts, std=arch.init_std, dtype=dtype,
        router=arch.router))
    return p


def init_params(rng: Array, arch: LMArch, dtype=jnp.float32) -> dict:
    """The whole tree; ``blocks`` is a LIST of per-layer trees (the two
    kinds have different leaves), each from its own key."""
    ke, kh, *kb = jax.random.split(rng, 2 + arch.n_layers)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    return {"embed": normal(ke, (arch.vocab_size, arch.d_model)),
            "blocks": [init_layer(k, arch, kind, dtype)
                       for k, kind in zip(kb, arch.layer_types)],
            "lnf_g": jnp.ones((arch.d_model,), dtype),
            "head": normal(kh, (arch.d_model, arch.vocab_size))}


# -- the grouped-query mixer ---------------------------------------------------

def gqa_project(p: Dict[str, Array], h: Array, arch: LMArch):
    """First half of a grouped-query layer for rows ``h`` [N, d]:
    ``((q [N, H, D] float32, gate [N, H * D]), (k row, v row))``, the
    rows as the two pools will hold them (the weights' type)."""
    n = h.shape[0]
    cd = p["W_k"].dtype
    u = rms_norm(h, p["ln1_g"], arch.rms_eps)
    q = _mm(u, p["W_q"]).reshape(n, arch.n_heads, arch.head_dim)
    gate = jax.nn.sigmoid(_mm(u, p["W_g"]))
    return (q, gate), (_mm(u, p["W_k"]).astype(cd), _mm(u, p["W_v"]).astype(cd))


def gqa_attend_step(q: Array, k_new: Array, v_new: Array, arch: LMArch,
                    positions: Array, read_block, n_held, block: int,
                    scale: Optional[float] = None) -> Array:
    """One new row a slot (``q`` [S, H, D], ``k_new`` / ``v_new`` [S, KV *
    D], not in the pools yet) over the ``positions[s]`` rows slot ``s``
    holds, read a block a slot at a time up to row ``n_held`` (the
    fullest slot's): ``read_block(j)`` gives the K and V rows ``j * block
    ..`` of every slot as [S, block, lanes].  The softmax starts from the
    slot's own row and is carried from block to block, at scale
    ``head_dim^-0.5`` unless ``scale`` says otherwise.  [S, H * D]."""
    S = q.shape[0]
    H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
    scale = D ** -0.5 if scale is None else scale
    cd = k_new.dtype
    qg = q.reshape(S, KV, H // KV, D).astype(cd)
    m = jnp.einsum("sgqd,sgd->sgq", qg, k_new.reshape(S, KV, D),
                   preferred_element_type=jnp.float32)[..., None] * scale
    z = jnp.ones_like(m)
    acc = jnp.broadcast_to(
        v_new.reshape(S, KV, 1, D).astype(jnp.float32), qg.shape)

    def body(j, carry):
        m, z, acc = carry
        k_rows, v_rows = read_block(j)
        s = jnp.einsum("sgqd,slgd->sgql", qg, k_rows.reshape(S, block, KV, D),
                       preferred_element_type=jnp.float32) * scale
        seen = j * block + jnp.arange(block)[None, :] < positions[:, None]
        s = jnp.where(seen[:, None, None, :], s, NEG_INF)
        m2 = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        e, keep = jnp.exp(s - m2), jnp.exp(m - m2)
        return (m2, z * keep + jnp.sum(e, axis=-1, keepdims=True),
                acc * keep + jnp.einsum(
                    "sgql,slgd->sgqd", e.astype(cd),
                    v_rows.reshape(S, block, KV, D),
                    preferred_element_type=jnp.float32))

    m, z, acc = jax.lax.fori_loop(0, (n_held + block - 1) // block, body,
                                  (m, z, acc))
    return (acc / z).reshape(S, H * D)


# -- the linear mixer ------------------------------------------------------------

def linear_inputs(p: Dict[str, Array], h: Array, arch: LMArch):
    """The projections of a linear layer for rows ``h`` [N, d]: ``(x [N,
    3 * C] the three convolutions' inputs side by side (q | k | v) in the
    weights' type, g [N, heads, dim] float32 log-decay (<= 0), beta [N,
    heads] in (0, 2), gate [N, C])``."""
    n = h.shape[0]
    nh, dl = arch.linear_n_heads, arch.linear_head_dim
    u = rms_norm(h, p["ln1_g"], arch.rms_eps)
    x = jnp.concatenate([_mm(u, p["W_q"]), _mm(u, p["W_k"]),
                         _mm(u, p["W_v"])], axis=-1).astype(p["W_q"].dtype)
    f = _mm(_mm(u, p["W_f1"]), p["W_f2"]) + p["dt_bias"]
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f.reshape(n, nh, dl))
    beta = 2.0 * jax.nn.sigmoid(_mm(u, p["W_b"]))
    gate = jax.nn.sigmoid(_mm(_mm(u, p["W_g1"]), p["W_g2"]))
    return x, g, beta, gate


def conv_qkv(p: Dict[str, Array], x_ext: Array, arch: LMArch):
    """``q, k, v`` [..., N, heads, dim] float32 of the rows whose
    convolution inputs are ``x_ext`` [..., K - 1 + N, 3 * C] (the ``K -
    1`` rows before them first): the causal depthwise convolution, SiLU,
    ``q`` and ``k`` L2-normalised a head, ``q`` scaled."""
    K = arch.conv_kernel
    nh, dl = arch.linear_n_heads, arch.linear_head_dim
    n = x_ext.shape[-2] - (K - 1)
    x, w = x_ext.astype(jnp.float32), p["conv_w"].astype(jnp.float32)
    y = sum(w[j] * x[..., j:j + n, :] for j in range(K))
    y = jax.nn.silu(y).reshape(y.shape[:-1] + (3, nh, dl))
    q, k, v = y[..., 0, :, :], y[..., 1, :, :], y[..., 2, :, :]

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * dl ** -0.5, unit(k), v


def linear_out(p: Dict[str, Array], o: Array, gate: Array, arch: LMArch
               ) -> Array:
    """``RMSNorm_head(o) * gate`` [..., C]: what ``W_o`` multiplies."""
    o = rms_norm(o, p["o_norm_g"], arch.rms_eps)
    return o.reshape(o.shape[:-2] + (-1,)) * gate


def delta_step(S: Array, q: Array, k: Array, v: Array, g: Array,
               beta: Array):
    """The one-row update of every slot and head: ``S`` [..., dk, dv]
    float32, ``q`` / ``k`` / ``g`` [..., dk], ``v`` [..., dv], ``beta``
    [...].  Returns ``(o [..., dv], S_t)``.  The decay is folded into the
    vectors (``(k * exp g)^T S`` is ``k^T Diag(exp g) S``) and ``o_t =
    S_t^T q_t`` is read beside ``S^T k`` from the state as it was, so the
    decayed state is never stored: one pass reads the state, one reads it
    again and writes its successor."""
    decay = jnp.exp(g)
    kq = jnp.stack([k, q], axis=-2) * decay[..., None, :]    # [..., 2, dk]
    ku, qu = jnp.moveaxis(
        jnp.sum(kq[..., :, :, None] * S[..., None, :, :], axis=-2), -2, 0)
    w = beta[..., None] * (v - ku)                           # the delta
    o = qu + jnp.sum(q * k, axis=-1, keepdims=True) * w
    return o, decay[..., :, None] * S + k[..., :, None] * w[..., None, :]


def delta_scan(q: Array, k: Array, v: Array, g: Array, beta: Array,
               S0: Array, sub: int = SUB_CHUNK):
    """The chunked form of ``delta_step`` over ``T`` rows of one
    sequence: ``q`` / ``k`` / ``g`` [T, H, dk], ``v`` [T, H, dv], ``beta``
    [T, H], ``S0`` [H, dk, dv]; ``T`` a multiple of ``sub``.  Returns ``(o
    [T, H, dv], S_T)`` (module docstring)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    n = T // sub
    # [n, H, C, .]
    q, k, v, g = (jnp.swapaxes(a.reshape(n, sub, H, a.shape[-1]), 1, 2)
                  for a in (q, k, v, g))
    beta = jnp.swapaxes(beta.reshape(n, sub, H), 1, 2)[..., None]
    G = jnp.cumsum(g, axis=2)
    t = jnp.arange(sub)

    def decayed_products(a):
        """``sum_c a_t[c] k_i[c] exp(G_t[c] - G_i[c])`` [n, H, C, C]: zero
        where ``t < i`` (the difference is masked BEFORE the exp)."""
        diff = G[:, :, :, None, :] - G[:, :, None, :, :]
        e = jnp.exp(jnp.where((t[:, None] >= t[None, :])[..., None],
                              diff, -jnp.inf))
        return jnp.sum(a[:, :, :, None, :] * k[:, :, None, :, :] * e, axis=-1)

    A = jnp.where(t[:, None] > t[None, :], decayed_products(k), 0.0)
    B = decayed_products(q)
    # (I + Diag(beta) A) X = Diag(beta) [V | Kbar], row by row
    rhs = beta * jnp.concatenate([v, k * jnp.exp(G)], axis=-1)
    N = beta * A

    def row(i, X):
        done = jnp.einsum("nhac,nhcx->nhax",
                          jax.lax.dynamic_slice_in_dim(N, i, 1, axis=2), X,
                          precision=HIGHEST)
        return jax.lax.dynamic_update_slice_in_dim(
            X, jax.lax.dynamic_slice_in_dim(rhs, i, 1, axis=2) - done, i,
            axis=2)

    X = jax.lax.fori_loop(0, sub, row, jnp.zeros_like(rhs))
    Xv, Xk = X[..., :dv], X[..., dv:]
    G_end = G[:, :, -1:, :]
    q_bar, k_til = q * jnp.exp(G), k * jnp.exp(G_end - G)

    def carry_on(S, part):
        Xv, Xk, q_bar, B, k_til, decay = part
        W = Xv - jnp.einsum("hck,hkv->hcv", Xk, S, precision=HIGHEST)
        o = jnp.einsum("hck,hkv->hcv", q_bar, S, precision=HIGHEST) \
            + jnp.einsum("hci,hiv->hcv", B, W, precision=HIGHEST)
        S = decay[..., None] * S \
            + jnp.einsum("hck,hcv->hkv", k_til, W, precision=HIGHEST)
        return S, o

    S, o = jax.lax.scan(carry_on, S0,
                        (Xv, Xk, q_bar, B, k_til, jnp.exp(G_end[:, :, 0])))
    return jnp.swapaxes(o, 1, 2).reshape(T, H, dv), S


def linear_chunk(p: Dict[str, Array], h: Array, state, arch: LMArch,
                 offset=0, n_real=None):
    """A linear layer's mixer over ``T`` new rows ``h`` [T, d] of one
    sequence from ``state`` (``(S, tail)`` of that slot; None or
    ``offset`` 0: from zero).  Only the first ``n_real`` rows are real
    (None: all).  Returns ``(what W_o multiplies [T, C], (S, tail) as row
    n_real - 1 leaves them)``."""
    T = h.shape[0]
    nh, dl = arch.linear_n_heads, arch.linear_head_dim
    x, g, beta, gate = linear_inputs(p, h, arch)
    keep = arch.conv_kernel - 1
    if state is None:
        S0 = jnp.zeros((nh, dl, dl), jnp.float32)
        tail = jnp.zeros((keep, x.shape[-1]), x.dtype)
    else:
        fresh = offset == 0
        S0 = jnp.where(fresh, 0.0, state[0])
        tail = jnp.where(fresh, jnp.zeros((), x.dtype), state[1])
    x_ext = jnp.concatenate([tail, x], axis=0)
    q, k, v = conv_qkv(p, x_ext, arch)
    if n_real is None:
        n_real = T
    else:
        real = jnp.arange(T) < n_real
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
    pad = -T % SUB_CHUNK                     # masked rows: state unchanged
    if pad:
        q, k, v, g, beta = (jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    o, S = delta_scan(q, k, v, g, beta, S0)
    # rows n_real - keep .. n_real - 1 of the inputs, the old tail's where
    # the chunk has fewer
    tail = jax.lax.dynamic_slice_in_dim(x_ext, n_real, keep, axis=0)
    return linear_out(p, o[:T], gate, arch), (S, tail)


def linear_step(p: Dict[str, Array], h: Array, state, active: Array,
                arch: LMArch):
    """A linear layer's mixer for one new row a slot (``h`` [S, d]) over
    ``state`` = ``(S [slots, H, dk, dv], tail [slots, K - 1, 3 * C])``;
    a slot that is not ``active`` keeps its state.  Returns ``(what W_o
    multiplies [S, C], the state)``."""
    S, tail = state
    x, g, beta, gate = linear_inputs(p, h, arch)
    x_ext = jnp.concatenate([tail, x[:, None, :]], axis=1)
    q, k, v = (a[:, 0] for a in conv_qkv(p, x_ext, arch))
    o, S_new = delta_step(S, q, k, v, g, beta)
    S = jnp.where(active[:, None, None, None], S_new, S)
    tail = jnp.where(active[:, None, None], x_ext[:, 1:], tail)
    return linear_out(p, o, gate, arch), (S, tail)


# -- the full forward ------------------------------------------------------------

def _counts(slots=0, rows=0, held=0, read=0) -> Array:
    return jnp.stack([jnp.asarray(c, jnp.int32)
                      for c in (slots, rows, held, read)])


def forward(params, tokens: Array, arch: LMArch, with_aux: bool = False):
    """Full forward of ``tokens`` [B, T] with nothing cached and zero
    state: logits [B, T, V] float32."""
    def one(seq):
        h = _embed(params, seq, arch)
        picks, stats = [], []
        for p, kind in zip(params["blocks"], arch.layer_types):
            if kind == "gqa":
                (q, gate), (k, v) = gqa_project(p, h, arch)
                att = attend_blocks(q, k, v, arch,
                                    lambda: causal(seq.shape[0]))[0] * gate
            else:
                att, _ = linear_chunk(p, h, None, arch)
            h, pk, st = layer_finish(p, h, att, arch)
            picks.append(pk)
            stats.append(st)
        out = _logits(params, h, arch)
        if with_aux:
            return out, _join_aux(picks, stats, arch, (seq.shape[0],))
        return out

    return jax.lax.map(one, tokens)


# -- the decode program ---------------------------------------------------------

def gqa_over_pages(arch: LMArch, page_size: int, pps: int,
                   scale: Optional[float] = None):
    """``(attend_chunk, attend_step)`` of ``CachedAttention`` for
    grouped-query layers over a K and a V pool ``[gqa layers, pages, page,
    KV * head_dim]``, for a ``project`` that gives ``((q, gate), (k row, v
    row))``: the output is multiplied by ``gate`` unless it is None (an
    ungated mixer's, ``models/ssm_gqa.py``).  Both count ``STATE_STATS``."""
    kv_pages = block_pages(pps, page_size, KV_BLOCK_ROWS)
    block = kv_pages * page_size

    def gated(att, gate):
        return att if gate is None else att * gate

    def chunk(p, pools, layer, page_table_row, q, rows, offset, n_real):
        k_pool, v_pool = pools
        (q, gate), (k_new, v_new) = q, rows
        T = q.shape[0]

        def read_kv(j):
            pages = jax.lax.dynamic_slice(page_table_row, (j * kv_pages,),
                                          (kv_pages,))
            return (read_pages(k_pool, layer, pages),
                    read_pages(v_pool, layer, pages))

        att, _ = attend_blocks(
            q, k_new, v_new, arch, lambda: causal(T), read_kv,
            lambda j: (j * block + jnp.arange(block) < offset)[None, :],
            offset, block, scale)
        # whole blocks up to the rows held, and the chunk's own rows
        return gated(att, gate), {"state_stats": _counts(
            held=offset + n_real, read=-(-offset // block) * block + T)}

    def step(p, pools, layer, table, q, rows, positions, active):
        k_pool, v_pool = pools
        (q, gate), (k_new, v_new) = q, rows
        s_n = positions.shape[0]
        size = STEP_GROUP if s_n % STEP_GROUP == 0 else s_n
        held = jnp.where(active, positions, 0)
        # slots that hold alike walk together: a group of slots not
        # stepped walks no block
        order = jnp.argsort(held)
        att, read = [], 0
        for g in range(s_n // size):
            at = order[g * size:(g + 1) * size]
            n_held = jnp.max(held[at])
            table_g = table[at]

            def read_block(j, table_g=table_g):
                pages = jax.lax.dynamic_slice(table_g, (0, j * kv_pages),
                                              (size, kv_pages))
                return (read_pages(k_pool, layer, pages),
                        read_pages(v_pool, layer, pages))

            att.append(gqa_attend_step(q[at], k_new[at], v_new[at], arch,
                                       positions[at], read_block, n_held,
                                       block, scale))
            read += size * (-(-n_held // block) * block + 1)
        att = jnp.concatenate(att)[jnp.argsort(order)]
        # every slot, stepped or not: blocks up to its group's fullest row
        return gated(att, gate), {"state_stats": _counts(
            held=jnp.sum(jnp.where(active, positions + 1, 0)), read=read)}

    return chunk, step


def mixers(arch: LMArch, page_size: int, pps: int) -> CachedAttention:
    """Both mixers for the builder: the grouped-query layers over a K
    and a V pool ``[gqa layers, pages, page, KV * head_dim]``, the linear
    layers over their per-slot state."""
    kv_lanes = arch.n_kv_heads * arch.head_dim
    chunk, step = gqa_over_pages(arch, page_size, pps)

    def state_chunk(p, h, state, offset, n_real):
        att, state = linear_chunk(p, h, state, arch, offset, n_real)
        return att, state, {"state_stats": _counts(rows=n_real)}

    def state_step(p, h, state, active):
        att, state = linear_step(p, h, state, active, arch)
        return att, state, {"state_stats": _counts(slots=jnp.sum(active))}

    return CachedAttention(
        pool_rows=((kv_lanes,), (kv_lanes,)), tables=(),
        project=lambda p, h, rope: gqa_project(p, h, arch),
        attend_chunk=chunk, attend_step=step, d_head=arch.head_dim,
        stats=(("state_stats", STATE_STATS),), held_pages=None,
        kinds=tuple("pool" if t == "gqa" else "state"
                    for t in arch.layer_types),
        slot_state=slot_state(arch, arch.param_dtype),
        state_chunk=state_chunk, state_step=state_step)


def decode_program(arch: LMArch, page_size: int, max_len: Optional[int]):
    """``ops/kv_cache.DecodeProgram`` over the K and V pools of the
    grouped-query layers and the per-slot state of the linear ones."""
    return expert_decode_program(arch, page_size, max_len, mixers)
