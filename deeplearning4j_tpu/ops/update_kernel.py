"""Fused Adam/Nadam update — moment update + param step in ONE VMEM pass.

The plain path (nn/updaters.py + nn/multilayer._apply_updates) lowers one
Adam step to ~4 small elementwise HLOs PER PYTREE LEAF (m, v, step, the
param subtract), each reading and writing HBM separately; on models with
many small leaves the optimizer phase is launch- and bandwidth-bound, not
compute-bound.  This module flattens a layer's {params, grads, m, v} trees
into flat bucketed f32 buffers and applies the whole update — both moment
EMAs, bias corrections, the step, and the param subtract — in one pass:

  pallas    one VMEM-resident kernel over (rows, 128) tiles (TPU compiled,
            interpret-mode on CPU for tests)
  flat-jnp  the plain-jnp fallback over the same flat buffers (other
            backends, under one tile, or DL4J_TPU_FUSED_UPDATE_JNP=1
            — also the CPU A/B arm that isolates the flat-bucketing win
            from the kernel itself)

Seams mirror ops/lstm_kernel.py: opt-in env flag evaluated at TRACE time,
compiled/interpret/fallback split, and callers (nn/updaters.Adam.apply)
fall back to the per-leaf path whenever ``fused_apply`` returns None.

Bit-comparability contract (tests/test_update_kernel.py): the math is
the same f32 elementwise chain in the same per-element order — flatten/
concat/slice only change layout, and the pallas grid partitions the
flat buffer without reassociating anything.  The only permitted
divergence is XLA:CPU's layout-dependent FMA contraction of
``a*x + b*y`` terms (LLVM contracts or not depending on vector-lane
boundaries), which bit-identity over identical layouts confirms.  How
that jitter is bounded depends on the output: the moments see one
contractible FMA each, so they match the per-leaf path to <= 1 ulp;
the param step inherits a few-ulp RELATIVE wobble through the
sqrt/divide chain, which is a tiny ABSOLUTE error at lr scale (~1e-9
at lr=1e-3) but can read as hundreds of ulp of the subtracted output
wherever ``p - step`` cancels toward zero — so param parity is gated
on absolute difference, not ulp (scripts/fused_update_ab.py).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import trace as obs_trace
from .pallas_support import fell_back, interpret

#: opt-in, read once at import (the lstm_kernel.ENABLED pattern): set
#: BEFORE the first trace of a step — already-jitted executables keep
#: whichever path they were traced with.
ENABLED = os.environ.get("DL4J_TPU_FUSED_UPDATE", "0") == "1"
#: force the flat-jnp arm even where pallas is usable (A/B isolation).
FORCE_JNP = os.environ.get("DL4J_TPU_FUSED_UPDATE_JNP", "0") == "1"

_LANES = 128
#: rows per grid step: 256 x 128 f32 = 128 KiB per operand block
_BLOCK_ROWS = 256
#: below one (8, 128) f32 tile the flat-jnp path is already one fused HLO
_TILE = 8 * _LANES


def _update_math(kind: str, p, g, m, v, lr, bc1, bc2,
                 beta1: float, beta2: float, eps: float):
    """The single source of truth for the fused step (plain Adam/Nadam
    math from nn/updaters.py, plus the param subtract).  All operands
    f32; returns (p_new, m_new, v_new)."""
    m_new = beta1 * m + (1 - beta1) * g
    v_new = beta2 * v + (1 - beta2) * g * g
    if kind == "nadam":
        m_hat = beta1 * (m_new / bc1) + (1 - beta1) * g / bc1
        step = lr * m_hat / (jnp.sqrt(v_new / bc2) + eps)
    else:
        step = lr * (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return p - step, m_new, v_new


def _kernel(p_ref, g_ref, m_ref, v_ref, sc_ref, p_out, m_out, v_out, *,
            kind: str, beta1: float, beta2: float, eps: float):
    lr = sc_ref[0]
    bc1 = sc_ref[1]
    bc2 = sc_ref[2]
    p_new, m_new, v_new = _update_math(
        kind, p_ref[...], g_ref[...], m_ref[...], v_ref[...],
        lr, bc1, bc2, beta1, beta2, eps)
    p_out[...] = p_new
    m_out[...] = m_new
    v_out[...] = v_new


def _use_pallas(n: int) -> bool:
    """Caller has already excluded f64 trees (fused_apply)."""
    if FORCE_JNP:
        return False
    if jax.default_backend() not in ("tpu", "cpu"):
        return False
    if n < _TILE:
        fell_back("fused_update", f"{n} elements is under one tile")
        return False
    return True


def _pallas_flat(kind: str, flat_p, flat_g, flat_m, flat_v, scalars,
                 beta1: float, beta2: float, eps: float):
    """One kernel over the padded flat buffers; returns f32 flats
    (p_new, m_new, v_new) of the original length.  The buffers are padded
    to a whole number of row blocks, so every block is (8, 128)-aligned
    whatever ``n`` is (the pad lanes compute on zeros and are sliced off)."""
    n = flat_p.shape[0]
    rows = -(-n // _TILE) * 8
    bm = min(rows, _BLOCK_ROWS)
    rows = -(-rows // bm) * bm
    pad = rows * _LANES - n
    grid = (rows // bm,)

    def shape2(a):
        return jnp.pad(a, (0, pad)).reshape(rows, _LANES)

    spec = pl.BlockSpec((bm, _LANES), lambda b: (b, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, kind=kind, beta1=beta1, beta2=beta2,
                          eps=eps),
        grid=grid,
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] * 3,
        interpret=interpret(),
    )(shape2(flat_p), shape2(flat_g), shape2(flat_m), shape2(flat_v),
      scalars)
    return tuple(o.reshape(-1)[:n] for o in out)


def kind_of(updater) -> Optional[str]:
    """"adam"/"nadam" for EXACT Adam/Nadam configs (subclasses like
    AdaMax/AMSGrad carry different math), else None."""
    from ..nn.updaters import Adam, Nadam

    if type(updater) is Nadam:
        return "nadam"
    if type(updater) is Adam:
        return "adam"
    return None


def fused_apply(kind: str, updater, params, grads, state, it):
    """The fused one-pass update over a layer's flat bucketed buffers.

    Returns ``(new_params, new_state)`` matching ``Updater.apply``'s
    contract bit-for-bit, or None when the fused path is unavailable
    (disabled, f64 anywhere, or empty trees) — the caller then runs the
    per-leaf plain path."""
    if not ENABLED:
        return None
    p_leaves, treedef = jax.tree_util.tree_flatten(params)
    g_leaves = treedef.flatten_up_to(grads)
    m_leaves = treedef.flatten_up_to(state["m"])
    v_leaves = treedef.flatten_up_to(state["v"])
    if not p_leaves:
        return None
    every = p_leaves + g_leaves + m_leaves + v_leaves
    if any(jnp.asarray(l).dtype == jnp.float64 for l in every):
        return None   # exact-gradient-check configs stay on the plain path

    # same scalar prelude as the plain Adam.update (bit-comparable)
    lr = updater.lr_at(it)
    t = it.astype(jnp.float32) + 1.0
    bc1 = 1.0 - jnp.power(updater.beta1, t)
    bc2 = 1.0 - jnp.power(updater.beta2, t)

    def flat(leaves):
        return jnp.concatenate(
            [jnp.ravel(l).astype(jnp.float32) for l in leaves])

    flat_p, flat_g = flat(p_leaves), flat(g_leaves)
    flat_m, flat_v = flat(m_leaves), flat(v_leaves)
    n = flat_p.shape[0]

    if _use_pallas(n):
        scalars = jnp.stack([lr.astype(jnp.float32), bc1, bc2])
        out = _pallas_flat(kind, flat_p, flat_g, flat_m, flat_v, scalars,
                           updater.beta1, updater.beta2, updater.eps)
    else:   # flat-jnp: same math, one fused flat pass
        out = _update_math(kind, flat_p, flat_g, flat_m, flat_v,
                           lr, bc1, bc2,
                           updater.beta1, updater.beta2, updater.eps)
    new_p_flat, new_m_flat, new_v_flat = out

    def unflat(flat_buf, like_leaves):
        leaves, off = [], 0
        for l in like_leaves:
            size = l.size
            leaves.append(flat_buf[off:off + size]
                          .reshape(l.shape).astype(l.dtype))
            off += size
        return treedef.unflatten(leaves)

    new_params = unflat(new_p_flat, p_leaves)
    new_state = {"m": unflat(new_m_flat, m_leaves),
                 "v": unflat(new_v_flat, v_leaves)}
    return new_params, new_state


def jit_apply(updater):
    """Standalone jitted optimizer-update program: ``run(params, grads,
    state, it) -> (new_params, new_state)`` with each dispatch wrapped in
    the ``train/update`` span (docs/OBSERVABILITY.md taxonomy) — the
    dispatch-level harness the fused-update A/B
    (scripts/fused_update_ab.py) and scripts/step_breakdown.py time."""
    fn = jax.jit(lambda p, g, s, it: updater.apply(p, g, s, it))

    def run(params, grads, state, it) -> Tuple:
        with obs_trace.span("train/update", cat="train"):
            return fn(params, grads, state, it)

    run.jitted = fn
    return run
