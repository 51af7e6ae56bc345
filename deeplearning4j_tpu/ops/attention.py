"""Multi-head attention — XLA reference path + fused flash (pallas) kernel.

The reference (DL4J 0.9.2) has NO attention layer at all (SURVEY.md §5
"Long-context": closest analogs are TBPTT + mask propagation).  Long-context
support is therefore designed TPU-first per SURVEY §7-M5:

  - ``mha``: plain XLA einsum-softmax-einsum attention (the semantics
    oracle; XLA fuses it well at moderate sequence lengths).
  - ``flash_mha``: blockwise streaming-softmax attention as a pallas TPU
    kernel — O(T) memory instead of O(T²), tiles sized for the MXU, f32
    accumulation.  Falls back to ``mha`` when shapes don't tile (and
    logs that it did when the backend is a tpu).
  - ``ring_attention`` (parallel/ring.py) reuses the same blockwise update
    rule across devices over the ``seq`` mesh axis.

Layout convention: [batch, heads, seq, head_dim] (BHTD).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_support import fell_back, interpret

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
# flash attention pallas kernel
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, km_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref,
                  *, scale: float, causal: bool, block_q: int, block_k: int):
    """Grid (BH, nQ, nK), k innermost — TPU grids run sequentially, so the
    running (acc, m, l) stats live in VMEM scratch across k-steps.  Also
    emits the log-sum-exp per query row (the residual the fused backward
    kernels need to rebuild p without a second online-softmax pass).
    ``km_ref`` is the optional [1, block_k] key-padding mask (1 = attend)."""
    kb = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    qb = pl.program_id(1)
    bias = None
    if causal:
        bias = causal_bias(block_q, block_k, qb * block_q, kb * block_k)
    if km_ref is not None:
        kbias = jnp.where(km_ref[0, 0] != 0, 0.0, _NEG_INF).astype(jnp.float32)
        bias = kbias[None, :] if bias is None else bias + kbias[None, :]

    def _step():
        acc, m, l = blockwise_update(
            acc_ref[:], m_ref[:], l_ref[:],
            q_ref[0], k_ref[0], v_ref[0], scale, bias)
        acc_ref[:] = acc
        m_ref[:] = m
        l_ref[:] = l

    if causal:
        # whole tile above the diagonal → skip (saves ~half the FLOPs)
        @pl.when(qb * block_q + block_q - 1 >= kb * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(kb == n_k - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # Rows that never saw a live key (m still at the −LARGE init; note
        # l is NOT 0 there — every masked score is exactly −LARGE after
        # f32 absorption, so p=1 per entry and l=S) take lse = +LARGE: the
        # backward's p = exp(s − lse) then reconstructs to 0, i.e. flash's
        # convention is ZERO gradients for fully-masked rows (see
        # _xla_attention_bwd for the rationale and the mha difference).
        lse = jnp.where(m_ref[:] > _NEG_INF / 2,
                        m_ref[:] + jnp.log(l_safe), 1e30)
        lse_ref[0, 0] = lse[:, 0].astype(lse_ref.dtype)


def _pick_block(n: int, dtype) -> int:
    """Rows per tile along a sequence axis of length ``n``; 0 = no tiling.

    Mosaic wants the last two dims of every block aligned to the dtype's
    (sublane, 128) tile or equal to the array's: the [1, block, D] q/k/v
    blocks put ``block`` on sublanes, the [1, 1, block] lse/mask rows put
    it on LANES.  So a block is 128 rows, or — for short sequences — the
    whole axis in one sublane-aligned tile."""
    if n % 128 == 0:
        return 128
    sublane = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    if n <= 512 and n % sublane == 0:
        return n
    return 0


def _vma(x):
    """Varying-across-mesh-axes of ``x`` (frozenset; empty outside
    shard_map) — pallas out_shapes must carry it so the kernels trace
    under shard_map's check_vma (ulysses/pipelined attention)."""
    return jax.typeof(x).vma


def _out_struct(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s vma."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=_vma(like))


def _fallback_reason(q, block_q: int, block_k: int) -> Optional[str]:
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
    if not (block_q and block_k):
        return "sequence length does not tile (see _pick_block)"
    return None


def _flash_forward(q: Array, k: Array, v: Array, kmask, causal: bool,
                   scale: float):
    """→ (o [B,H,T,D], lse [B*H,T] or None-on-fallback)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    block_q = _pick_block(T, q.dtype)
    block_k = _pick_block(S, k.dtype)
    why = _fallback_reason(q, block_q, block_k)
    if why:
        fell_back(f"flash_mha[T={T},S={S},{q.dtype}]", why)
        m = None if kmask is None else kmask[:, None, None, :]
        return mha(q, k, v, causal=causal, mask=m, scale=scale), None

    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    grid = (B * H, T // block_q, S // block_k)
    base = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k)
    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
    ]
    args = [qf, kf, vf]
    if kmask is not None:
        # [B,1,S] row blocks (block_k on the lane axis); batch = flat_bh // H
        in_specs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda b, i, j, H=H: (b // H, 0, j)))
        args.append(kmask.astype(jnp.int32)[:, None, :])
        kernel = base
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s):
            base(q_ref, k_ref, v_ref, None, o_ref, lse_ref, acc, m_s, l_s)

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            _out_struct((B * H, T, D), q.dtype, q),
            _out_struct((B * H, 1, T), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret(),
    )(*args)
    return out.reshape(B, H, T, D), lse


# ---------------------------------------------------------------------------
# fused backward kernels (FlashAttention-2 style, O(T) memory)
# ---------------------------------------------------------------------------


def _bwd_tile(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, km_ref,
              qi, ki, *, scale, causal, block_q, block_k):
    """Shared tile math for both backward kernels: rebuild p from the saved
    lse and form ds — ONE definition so the masking/lse conventions cannot
    desynchronize between dq and dk/dv.

    Masking is where()-style to match the XLA oracle: no gradient flows
    through blocked score entries (ds hard-zeroed there).  Fully-masked
    rows carry the lse=+LARGE sentinel from the forward, so p — and with
    it every gradient — is exactly 0 for them.
    Returns (qb, kb, vb, gb, p, ds); operands keep the input dtype (native
    MXU rate for bf16 — see blockwise_update), p/ds are f32 stats."""
    nt = (((1,), (1,)), ((), ()))      # contract head_dim, no transposes
    qb = q_ref[0]                                   # [bq, D]
    kb = k_ref[0]                                   # [bk, D]
    vb = v_ref[0]
    gb = g_ref[0]
    s = jax.lax.dot_general(qb, kb, nt,
                            preferred_element_type=jnp.float32) * scale
    bias = jnp.zeros((block_q, block_k), jnp.float32)
    if causal:
        bias = bias + causal_bias(block_q, block_k,
                                  qi * block_q, ki * block_k)
    if km_ref is not None:
        bias = bias + jnp.where(km_ref[0, 0] != 0, 0.0,
                                _NEG_INF).astype(jnp.float32)[None, :]
    p = jnp.exp(s + bias - lse_ref[0, 0][:, None])  # [bq, bk]
    dp = jax.lax.dot_general(gb, vb, nt, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0][:, None]) * scale
    ds = ds * (bias > _NEG_INF / 2).astype(jnp.float32)
    return qb, kb, vb, gb, p, ds


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                           km_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                           *, scale, causal, block_q, block_k):
    """Grid (BH, nK, nQ), q innermost; dk/dv accumulate in VMEM scratch.
    p is rebuilt per tile from the saved lse — no [T,S] materialization."""
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _step():
        qb, _, _, gb, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, km_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        ct = (((0,), (0,)), ((), ()))  # contract the q-row dim of both
        dv_acc[:] += jax.lax.dot_general(
            p.astype(gb.dtype), gb, ct, preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, ct, preferred_element_type=jnp.float32)

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(qi == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
                         km_ref, dq_ref, dq_acc,
                         *, scale, causal, block_q, block_k):
    """Grid (BH, nQ, nK), k innermost; dq accumulates in VMEM scratch."""
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _step():
        _, kb, _, _, _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, km_ref, qi, ki,
            scale=scale, causal=causal, block_q=block_q, block_k=block_k)
        dq_acc[:] += jnp.dot(ds.astype(kb.dtype), kb,
                             preferred_element_type=jnp.float32)

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(ki == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_backward(q, k, v, kmask, o, lse, g, causal, scale):
    """Fused O(T)-memory backward: rebuild p per tile from lse.  Falls back
    to the XLA recompute path when the forward did (lse is None)."""
    B, H, T, D = q.shape
    S = k.shape[2]
    if lse is None:   # the forward fell back (and said so on tpu)
        return _xla_attention_bwd(q, k, v, kmask, g, causal, scale)
    block_q = _pick_block(T, q.dtype)
    block_k = _pick_block(S, k.dtype)

    flat = lambda x: x.reshape(B * H, *x.shape[2:])
    qf, kf, vf, gf = flat(q), flat(k), flat(v), flat(g)
    # delta_i = Σ_d g_i·o_i — the softmax-jacobian row term (Dao 2023 eq. 4)
    delta = jnp.sum(gf.astype(jnp.float32) * flat(o).astype(jnp.float32),
                    axis=-1)[:, None, :]                       # [BH, 1, T]
    interp = interpret()

    q_spec_i = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, j, 0))
    k_spec_o = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))
    row_spec_i = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, j))
    if kmask is not None:
        kmi = kmask.astype(jnp.int32)[:, None, :]

    # dk/dv: grid (BH, nK, nQ)
    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k)
    base_kv = functools.partial(_flash_bwd_dkdv_kernel, **kw)
    specs_kv = [q_spec_i, k_spec_o,
                pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, j, 0)),
                row_spec_i, row_spec_i]
    args_kv = [qf, kf, vf, gf, lse, delta]
    if kmask is not None:
        specs_kv.append(pl.BlockSpec((1, 1, block_k),
                                     lambda b, i, j, H=H: (b // H, 0, i)))
        args_kv.append(kmi)
        kernel_kv = base_kv
    else:
        def kernel_kv(q_r, k_r, v_r, g_r, l_r, d_r, dk_r, dv_r, dka, dva):
            base_kv(q_r, k_r, v_r, g_r, l_r, d_r, None, dk_r, dv_r, dka, dva)
    dk, dv = pl.pallas_call(
        kernel_kv,
        grid=(B * H, S // block_k, T // block_q),
        in_specs=specs_kv,
        out_specs=[pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, i, 0))],
        out_shape=[_out_struct((B * H, S, D), k.dtype, k),
                   _out_struct((B * H, S, D), v.dtype, v)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interp,
    )(*args_kv)

    # dq: grid (BH, nQ, nK)
    base_q = functools.partial(_flash_bwd_dq_kernel, **kw)
    specs_q = [pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
               pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
               pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
               pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
               pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
               pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))]
    args_q = [qf, kf, vf, gf, lse, delta]
    if kmask is not None:
        specs_q.append(pl.BlockSpec((1, 1, block_k),
                                    lambda b, i, j, H=H: (b // H, 0, j)))
        args_q.append(kmi)
        kernel_q = base_q
    else:
        def kernel_q(q_r, k_r, v_r, g_r, l_r, d_r, dq_r, dqa):
            base_q(q_r, k_r, v_r, g_r, l_r, d_r, None, dq_r, dqa)
    dq = pl.pallas_call(
        kernel_q,
        grid=(B * H, T // block_q, S // block_k),
        in_specs=specs_q,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((B * H, T, D), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interp,
    )(*args_q)

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
