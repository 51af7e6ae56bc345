"""The block of state-space and grouped-query layers (``model_type:
granitemoehybrid``): Mamba-2 layers everywhere but at the ``attention``
indices of ``layer_types``, an UNGATED grouped-query attention without
positions there, every layer a softmax-routed expert layer with a shared
MLP beside the experts, scalars on the one residual stream; and its
decode program.

With ``r = residual_multiplier`` a layer is ``h = x + r Mix(RMSNorm(x))``,
``y = h + r (Experts(v) + Shared(v))``, ``v = RMSNorm(h)``
(``parallel/moe.moe_forward_held`` behind the ``softmax_topk`` router,
``latent_moe.layer_finish``); the embedding's rows are multiplied by
``embedding_multiplier``, the logits are ``RMSNorm(h_L) E^T /
logits_scaling`` with ``E`` the embedding itself where the head is tied
(``latent_moe._embed`` / ``_logits``).  ``u = RMSNorm(x)`` below.

**The grouped-query mixer**: ``q = u W_q`` per query head, ``k = u W_k``
and ``v = u W_v`` per key/value head, no position of any kind, no gate,
causal softmax at scale ``attention_multiplier`` (NOT ``head_dim^-0.5``)
over every earlier row, ``W_o``.  It is ``models/linear_gqa.py``'s mixer
over the same two pools and the same block walk
(``linear_gqa.gqa_over_pages``), without its gate and with the scale
from the file.

**The state-space mixer** (Mamba-2, SSD, one group): ``[z | xBC | dt] = u
W_in``; ``xBC = SiLU(conv(xBC) + b)``, a causal depthwise convolution of
``mamba_d_conv`` taps a channel; ``[x | B | C]`` with ``x`` as heads of
``mamba_d_head``, ``B`` and ``C`` of ``mamba_d_state`` shared by every
head; ``dt_t,h = softplus(dt_t,h + dt_bias_h)``, ``A_h = -exp(A_log_h)``;
a state ``S`` [d_head, d_state] a head in float32,

    S_t,h = exp(dt_t,h A_h) S_t-1,h + dt_t,h x_t,h B_t^T
    o_t,h = S_t,h C_t + D_h x_t,h

then ``W_o RMSNorm(o * SiLU(z))``, the norm over all inner channels at
once and AFTER the gate.  What a slot holds of such a layer is PER SLOT
and not per token: ``S`` and the last ``mamba_d_conv - 1`` rows of the
convolution's input (in the weights' type: every path convolves the
values the tail will hold).  Two forms compute it, the same mathematics:

* *the chunked scan* (``ssd_scan``: prefill chunks, the full forward):
  chunks of ``mamba_chunk_size`` rows (a bucket that is shorter is one
  chunk).  Inside one, with ``a`` the running sum of ``dt A`` (a head),
  ``G = C B^T`` once for all heads, a head's ``Y = (G * L)(dt x)`` with
  ``L[t, i] = exp(a_t - a_i)`` for ``i <= t``, plus ``exp(a_t) C_t`` times
  the carried state; the state goes on as ``exp(a_end) S + sum_i exp(a_end
  - a_i) dt_i x_i B_i^T``.  Decays are only ever combined as exponentials
  of differences of running sums that are ``<= 0`` (masked BEFORE the
  exponential), never as a quotient of cumulative products.  Rows at and
  beyond ``n_real`` get ``dt = 0``: they leave the state as it was, and
  they do not enter the tail.
* *the one-row update* (``ssd_step``: the step, every slot at once): the
  recurrence as written, one pass that reads the state, writes its
  successor and reads ``o`` off it.  A slot that is not active gets ``dt
  = 0`` too.

Every product with the state runs in float32 (``HIGHEST``): the state is
stated float32 and what rounds it is held against
(benchmarks/configs/granite-4.0-h-small.json ``limits_from``).

Which family may state what: this block's file may state
``tie_word_embeddings`` either way, the four multipliers, and an ungated
attention; ``latent_moe``, ``sparse_gqa`` and ``linear_gqa`` refuse a tied
head, and ``linear_gqa`` an ungated mixer (``use_gqa_gate``), by name
(``LMArch.from_config``).

The decode program is the expert family's one builder
(``models/latent_moe.expert_decode_program``); this module hands it both
mixers.  Beside the expert counts a call reports ``STATE_STATS`` under
the names and meanings ``models/linear_gqa.py`` gives them, and
``state_rows_computed``: the rows the scan computed (whole chunks of the
bucket), of which ``state_rows_scanned`` were real.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from ..parallel.moe import init_held_experts
from .arch import LMArch
from .latent_moe import (CachedAttention, _embed, _join_aux, _logits, _mm,
                         expert_decode_program, layer_finish, rms_norm)
from .linear_gqa import STATE_STATS, _counts, gqa_over_pages
from .sparse_gqa import attend_blocks, causal

Array = jax.Array
HIGHEST = jax.lax.Precision.HIGHEST

#: what the scan counts beside ``STATE_STATS`` (int32 [1]), summed over
#: the state-space layers: rows it computed, whole chunks of the bucket
SCAN_STATS = ("state_rows_computed",)


def slot_state(arch: LMArch, dtype) -> tuple:
    """What a slot holds of ONE state-space layer, ``(shape after
    [slots], dtype)`` each: the state in float32, the convolution's tail
    in the weights' type."""
    return (((arch.mamba_n_heads, arch.mamba_d_head, arch.mamba_d_state),
             jnp.dtype(jnp.float32)),
            ((arch.mamba_d_conv - 1, arch.mamba_conv_dim), jnp.dtype(dtype)))


# -- parameters ----------------------------------------------------------------

def init_layer(rng: Array, arch: LMArch, kind: str,
               dtype=jnp.float32) -> Dict[str, Array]:
    """One layer's tree.  Matrices N(0, init_std), unit gains; a
    state-space layer's taps and their bias U(-0.5, 0.5), ``A_log = log
    U(1, 16)`` and ``dt_bias`` with ``softplus`` log-uniform in [0.001,
    0.1] a head, ``D`` one (the published layer's initialisation), those
    three float32."""
    d = arch.d_model
    ks = jax.random.split(rng, 12)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    p = {"ln1_g": jnp.ones((d,), dtype), "ln2_g": jnp.ones((d,), dtype)}
    if kind == "gqa":
        H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
        p.update(W_q=normal(ks[0], (d, H * D)), W_k=normal(ks[1], (d, KV * D)),
                 W_v=normal(ks[2], (d, KV * D)), W_o=normal(ks[3], (H * D, d)))
    else:
        nh, di, cw = arch.mamba_n_heads, arch.mamba_d_inner, arch.mamba_conv_dim
        dt = jnp.exp(jax.random.uniform(
            ks[6], (nh,), jnp.float32, jnp.log(0.001), jnp.log(0.1)))
        p.update(
            W_in=normal(ks[0], (d, di + cw + nh)),
            W_o=normal(ks[3], (di, d)),
            conv_w=jax.random.uniform(ks[4], (arch.mamba_d_conv, cw),
                                      jnp.float32, -0.5, 0.5).astype(dtype),
            A_log=jnp.log(jax.random.uniform(ks[7], (nh,), jnp.float32,
                                             1.0, 16.0)),
            D=jnp.ones((nh,), jnp.float32),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
            norm_g=jnp.ones((di,), dtype))
        if arch.mamba_conv_bias:
            p["conv_b"] = jax.random.uniform(
                ks[5], (cw,), jnp.float32, -0.5, 0.5).astype(dtype)
    p.update(init_held_experts(
        ks[11], d, arch.moe_d_ff, arch.n_experts, arch.experts_held,
        arch.n_shared_experts, std=arch.init_std, dtype=dtype,
        router=arch.router))
    return p


def init_params(rng: Array, arch: LMArch, dtype=jnp.float32) -> dict:
    """The whole tree; ``blocks`` is a LIST of per-layer trees (the two
    kinds have different leaves), each from its own key.  A tied head has
    no leaf of its own."""
    ke, kh, *kb = jax.random.split(rng, 2 + arch.n_layers)

    def normal(key, shape):
        return (arch.init_std * jax.random.normal(key, shape, jnp.float32)
                ).astype(dtype)

    params = {"embed": normal(ke, (arch.vocab_size, arch.d_model)),
              "blocks": [init_layer(k, arch, kind, dtype)
                         for k, kind in zip(kb, arch.layer_types)],
              "lnf_g": jnp.ones((arch.d_model,), dtype)}
    if not arch.tie_embeddings:
        params["head"] = normal(kh, (arch.d_model, arch.vocab_size))
    return params


# -- the grouped-query mixer ---------------------------------------------------

def gqa_project(p: Dict[str, Array], h: Array, arch: LMArch):
    """First half of a grouped-query layer for rows ``h`` [N, d]: ``((q
    [N, H, D] float32, None: no gate), (k row, v row))``, the rows as the
    two pools will hold them (the weights' type)."""
    cd = p["W_k"].dtype
    u = rms_norm(h, p["ln1_g"], arch.rms_eps)
    q = _mm(u, p["W_q"]).reshape(h.shape[0], arch.n_heads, arch.head_dim)
    return (q, None), (_mm(u, p["W_k"]).astype(cd), _mm(u, p["W_v"]).astype(cd))


# -- the state-space mixer -----------------------------------------------------

def ssm_inputs(p: Dict[str, Array], h: Array, arch: LMArch):
    """The projection of a state-space layer for rows ``h`` [N, d]: ``(z
    [N, inner] float32 the gate's input, xBC [N, conv_dim] the
    convolution's input in the weights' type, dt [N, heads] float32 the
    step, > 0)``."""
    di, cw = arch.mamba_d_inner, arch.mamba_conv_dim
    zxd = _mm(rms_norm(h, p["ln1_g"], arch.rms_eps), p["W_in"])
    dt = jax.nn.softplus(zxd[:, di + cw:] + p["dt_bias"])
    return zxd[:, :di], zxd[:, di:di + cw].astype(p["W_in"].dtype), dt


def conv_xbc(p: Dict[str, Array], x_ext: Array, arch: LMArch):
    """``x [..., N, heads, d_head], B, C [..., N, d_state]`` float32 of
    the rows whose convolution inputs are ``x_ext`` [..., K - 1 + N,
    conv_dim] (the ``K - 1`` rows before them first): the causal
    depthwise convolution with its bias, SiLU, the split."""
    K, di, ds = arch.mamba_d_conv, arch.mamba_d_inner, arch.mamba_d_state
    n = x_ext.shape[-2] - (K - 1)
    x, w = x_ext.astype(jnp.float32), p["conv_w"].astype(jnp.float32)
    y = sum(w[j] * x[..., j:j + n, :] for j in range(K))
    if "conv_b" in p:
        y = y + p["conv_b"].astype(jnp.float32)
    y = jax.nn.silu(y)
    return (y[..., :di].reshape(y.shape[:-1] + (arch.mamba_n_heads,
                                                arch.mamba_d_head)),
            y[..., di:di + ds], y[..., di + ds:])


def ssm_out(p: Dict[str, Array], o: Array, z: Array, arch: LMArch) -> Array:
    """``RMSNorm(o * SiLU(z))`` [..., inner] over all inner channels, the
    gate BEFORE the norm: what ``W_o`` multiplies."""
    o = o.reshape(o.shape[:-2] + (-1,))
    return rms_norm(o * jax.nn.silu(z), p["norm_g"], arch.rms_eps)


def ssd_step(S: Array, x: Array, B: Array, C: Array, dt: Array, A: Array,
             D: Array):
    """The one-row update of every slot and head: ``S`` [..., H, P, N]
    float32, ``x`` [..., H, P], ``B`` / ``C`` [..., N], ``dt`` [..., H],
    ``A`` / ``D`` [H].  Returns ``(o [..., H, P], S_t)``: elementwise in
    float32, the state read once and written once, ``o`` read off what
    is written."""
    decay = jnp.exp(dt * A)[..., None, None]
    S = decay * S + (dt[..., None] * x)[..., None] * B[..., None, None, :]
    o = jnp.sum(S * C[..., None, None, :], axis=-1)
    return o + D[:, None] * x, S


def ssd_scan(x: Array, B: Array, C: Array, dt: Array, A: Array, D: Array,
             S0: Array, chunk: int):
    """The chunked form of ``ssd_step`` over ``T`` rows of one sequence:
    ``x`` [T, H, P], ``B`` / ``C`` [T, N], ``dt`` [T, H], ``S0`` [H, P,
    N]; ``T`` a multiple of ``chunk``.  Returns ``(o [T, H, P], S_T)``
    (module docstring)."""
    T, H, P = x.shape
    n = T // chunk
    # a head's rows side by side: [n, H, chunk, .]
    xd = jnp.swapaxes((x * dt[..., None]).reshape(n, chunk, H, P), 1, 2)
    a = jnp.cumsum(jnp.swapaxes((dt * A).reshape(n, chunk, H), 1, 2), axis=-1)
    B, C = (m.reshape(n, chunk, -1) for m in (B, C))
    t = jnp.arange(chunk)
    lower = t[:, None] >= t[None, :]

    def carry_on(S, part):
        xd, a, B, C = part
        G = jnp.einsum("tn,in->ti", C, B, precision=HIGHEST)
        L = jnp.exp(jnp.where(lower, a[:, :, None] - a[:, None, :], -jnp.inf))
        o = jnp.einsum("hti,hip->htp", G * L, xd, precision=HIGHEST) \
            + jnp.exp(a)[..., None] * jnp.einsum(
                "tn,hpn->htp", C, S, precision=HIGHEST)
        end = a[:, -1:]
        S = jnp.exp(end)[..., None] * S + jnp.einsum(
            "hip,in->hpn", xd * jnp.exp(end - a)[..., None], B,
            precision=HIGHEST)
        return S, o

    S, o = jax.lax.scan(carry_on, S0, (xd, a, B, C))
    return jnp.swapaxes(o, 1, 2).reshape(T, H, P) + D[:, None] * x, S


def scan_rows(arch: LMArch, T: int) -> tuple:
    """``(rows of a chunk of the scan, rows it computes)`` for ``T`` new
    rows: whole chunks of ``mamba_chunk_size``, one shorter chunk where
    the rows are fewer."""
    chunk = min(arch.mamba_chunk_size, T)
    return chunk, -(-T // chunk) * chunk


def ssm_chunk(p: Dict[str, Array], h: Array, state, arch: LMArch,
              offset=0, n_real=None):
    """A state-space layer's mixer over ``T`` new rows ``h`` [T, d] of one
    sequence from ``state`` (``(S, tail)`` of that slot; None or
    ``offset`` 0: from zero).  Only the first ``n_real`` rows are real
    (None: all).  Returns ``(what W_o multiplies [T, inner], (S, tail) as
    row n_real - 1 leaves them)``."""
    T = h.shape[0]
    z, xbc, dt = ssm_inputs(p, h, arch)
    keep = arch.mamba_d_conv - 1
    if state is None:
        S0 = jnp.zeros(slot_state(arch, xbc.dtype)[0][0], jnp.float32)
        tail = jnp.zeros((keep, xbc.shape[-1]), xbc.dtype)
    else:
        fresh = offset == 0
        S0 = jnp.where(fresh, 0.0, state[0])
        tail = jnp.where(fresh, jnp.zeros((), xbc.dtype), state[1])
    x_ext = jnp.concatenate([tail, xbc], axis=0)
    x, B, C = conv_xbc(p, x_ext, arch)
    if n_real is None:
        n_real = T
    else:
        dt = jnp.where((jnp.arange(T) < n_real)[:, None], dt, 0.0)
    chunk, computed = scan_rows(arch, T)
    if computed > T:                           # masked rows: state unchanged
        x, B, C, dt = (jnp.pad(m, [(0, computed - T)] + [(0, 0)] * (m.ndim - 1))
                       for m in (x, B, C, dt))
    o, S = ssd_scan(x, B, C, dt, -jnp.exp(p["A_log"]), p["D"], S0, chunk)
    # rows n_real - keep .. n_real - 1 of the inputs, the old tail's where
    # the chunk has fewer
    tail = jax.lax.dynamic_slice_in_dim(x_ext, n_real, keep, axis=0)
    return ssm_out(p, o[:T], z, arch), (S, tail)


def ssm_step(p: Dict[str, Array], h: Array, state, active: Array,
             arch: LMArch):
    """A state-space layer's mixer for one new row a slot (``h`` [S, d])
    over ``state`` = ``(S [slots, H, P, N], tail [slots, K - 1,
    conv_dim])``; a slot that is not ``active`` keeps its state.  Returns
    ``(what W_o multiplies [S, inner], the state)``."""
    S, tail = state
    z, xbc, dt = ssm_inputs(p, h, arch)
    x_ext = jnp.concatenate([tail, xbc[:, None, :]], axis=1)
    x, B, C = (m[:, 0] for m in conv_xbc(p, x_ext, arch))
    o, S = ssd_step(S, x, B, C, jnp.where(active[:, None], dt, 0.0),
                    -jnp.exp(p["A_log"]), p["D"])
    tail = jnp.where(active[:, None, None], x_ext[:, 1:], tail)
    return ssm_out(p, o, z, arch), (S, tail)


# -- the full forward ------------------------------------------------------------

def forward(params, tokens: Array, arch: LMArch, with_aux: bool = False):
    """Full forward of ``tokens`` [B, T] with nothing cached and zero
    state: logits [B, T, V] float32."""
    def one(seq):
        h = _embed(params, seq, arch)
        picks, stats = [], []
        for p, kind in zip(params["blocks"], arch.layer_types):
            if kind == "gqa":
                (q, _), (k, v) = gqa_project(p, h, arch)
                att = attend_blocks(q, k, v, arch,
                                    lambda: causal(seq.shape[0]),
                                    scale=arch.gqa_scale)[0]
            else:
                att, _ = ssm_chunk(p, h, None, arch)
            h, pk, st = layer_finish(p, h, att, arch)
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
    and a V pool ``[gqa layers, pages, page, KV * head_dim]``, the
    state-space layers over their per-slot state."""
    kv_lanes = arch.n_kv_heads * arch.head_dim
    gqa_chunk, gqa_step = gqa_over_pages(arch, page_size, pps, arch.gqa_scale)

    def counted(extra, computed=0):
        return {**extra, "scan_stats": jnp.asarray([computed], jnp.int32)}

    def pool_layer(attend):
        """A grouped-query layer scans nothing."""
        def attend_and_count(*args):
            att, extra = attend(*args)
            return att, counted(extra)
        return attend_and_count

    def state_chunk(p, h, state, offset, n_real):
        att, state = ssm_chunk(p, h, state, arch, offset, n_real)
        return att, state, counted(
            {"state_stats": _counts(rows=n_real)},
            scan_rows(arch, h.shape[0])[1])

    def state_step(p, h, state, active):
        att, state = ssm_step(p, h, state, active, arch)
        return att, state, counted(
            {"state_stats": _counts(slots=jnp.sum(active))})

    return CachedAttention(
        pool_rows=((kv_lanes,), (kv_lanes,)), tables=(),
        project=lambda p, h, rope: gqa_project(p, h, arch),
        attend_chunk=pool_layer(gqa_chunk), attend_step=pool_layer(gqa_step),
        d_head=arch.head_dim,
        stats=(("state_stats", STATE_STATS), ("scan_stats", SCAN_STATS)),
        held_pages=None,
        kinds=tuple("pool" if t == "gqa" else "state"
                    for t in arch.layer_types),
        slot_state=slot_state(arch, arch.param_dtype),
        state_chunk=state_chunk, state_step=state_step)


def decode_program(arch: LMArch, page_size: int, max_len: Optional[int]):
    """``ops/kv_cache.DecodeProgram`` over the K and V pools of the
    grouped-query layers and the per-slot state of the state-space ones."""
    return expert_decode_program(arch, page_size, max_len, mixers)
