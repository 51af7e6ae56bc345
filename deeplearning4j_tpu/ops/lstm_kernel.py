"""Fused LSTM cell — the SURVEY M0 pallas kernel.

The cell's matmuls (x·W + h·RW) stay in XLA where the MXU already runs
them optimally; the elementwise gate math (3 sigmoids, 2 tanhs,
muls/adds) is fused here into ONE pallas VMEM pass per direction via
custom VJP.

**Measured on the v5e chip (mb=64, T=128, n=512): XLA's own epilogue
fusion inside ``lax.scan`` is FASTER than this kernel (fwd 3.5 ms vs
5.7 ms; grad equal)** — XLA already fuses the cell's elementwise ops into
the matmul epilogue, and a separate pallas dispatch per scan step only
adds overhead.  The kernel therefore defaults OFF (``ENABLED=False`` /
``DL4J_TPU_FUSED_LSTM=1`` to opt in); it stays in-tree as the
custom-cell seam — the place a block-diagonal, quantized, or
multi-step-fused variant (where XLA genuinely can't fuse) drops in — and
is fully parity-tested on both the interpret and compiled paths.

Seams mirror ops/attention.py's flash kernel: compiled on TPU,
interpret-mode on CPU (tests), plain jax.numpy fallback for f64 (exact
gradient checks), other backends, or tile-unfriendly shapes.  Gate order
matches nn/layers/recurrent.py: [i, f, o, g].
"""

from __future__ import annotations

import functools
import os
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pallas_support import fell_back, interpret

#: opt-in: XLA's scan-epilogue fusion beats the kernel at common sizes
#: (see module docstring).  Set BEFORE the first trace of a model —
#: _use_pallas is evaluated at trace time, so already-jitted executables
#: keep whichever path they were traced with (clear jax caches to switch).
ENABLED = os.environ.get("DL4J_TPU_FUSED_LSTM", "0") == "1"

#: rows per grid step (a multiple of the (8, 128) f32 sublane tile)
_BLOCK_ROWS = 256


def _plain_cell(z: jax.Array, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    n = c.shape[-1]
    i = jax.nn.sigmoid(z[:, :n])
    f = jax.nn.sigmoid(z[:, n:2 * n])
    o = jax.nn.sigmoid(z[:, 2 * n:3 * n])
    g = jnp.tanh(z[:, 3 * n:])
    c_new = f * c + i * g
    return o * jnp.tanh(c_new), c_new


def _bwd_math(z: jax.Array, c: jax.Array, dh: jax.Array,
              dcn: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Closed-form cell backward (single source of truth — used by the
    pallas backward kernel AND the plain fallback): recomputes the gates
    from the (z, c) residuals, returns (dz, dc)."""
    n = c.shape[-1]
    i = jax.nn.sigmoid(z[:, :n])
    f = jax.nn.sigmoid(z[:, n:2 * n])
    o = jax.nn.sigmoid(z[:, 2 * n:3 * n])
    g = jnp.tanh(z[:, 3 * n:])
    c_new = f * c + i * g
    tc = jnp.tanh(c_new)
    do = dh * tc
    dct = dcn + dh * o * (1.0 - tc * tc)
    dz = jnp.concatenate([
        dct * g * i * (1.0 - i),
        dct * c * f * (1.0 - f),
        do * o * (1.0 - o),
        dct * i * (1.0 - g * g),
    ], axis=1)
    return dz, dct * f


def _fwd_kernel(z_ref, c_ref, h_out, c_out, *, n: int):
    h, c_new = _plain_cell(z_ref[...], c_ref[...])
    h_out[...] = h
    c_out[...] = c_new


def _bwd_kernel(z_ref, c_ref, dh_ref, dcn_ref, dz_out, dc_out, *, n: int):
    dz, dc = _bwd_math(z_ref[...], c_ref[...], dh_ref[...], dcn_ref[...])
    dz_out[...] = dz
    dc_out[...] = dc


def _use_pallas(z: jax.Array, n: int) -> bool:
    if not ENABLED or z.dtype == jnp.float64:
        return False
    if jax.default_backend() not in ("tpu", "cpu"):
        return False
    mb = z.shape[0]
    # the gate slices z[:, k*n:(k+1)*n] must start on a 128-lane boundary,
    # and a row block is the whole batch or an aligned 256-row tile
    if n % 128 or (mb > _BLOCK_ROWS and mb % _BLOCK_ROWS):
        fell_back("fused_lstm_cell",
                  f"width {n} / batch {mb} do not tile (need n % 128 == 0 "
                  f"and batch <= {_BLOCK_ROWS} or a multiple of it)")
        return False
    return True


def _pallas_call(kernel, z, *args, out_shapes, n):
    mb = z.shape[0]
    bm = min(mb, _BLOCK_ROWS)      # _use_pallas: bm divides mb
    grid = (mb // bm,)

    def spec(width):
        return pl.BlockSpec((bm, width), lambda b: (b, 0))

    widths = [a.shape[1] for a in (z,) + args]
    return pl.pallas_call(
        functools.partial(kernel, n=n),
        grid=grid,
        in_specs=[spec(w) for w in widths],
        out_specs=[spec(s[1]) for s in out_shapes],
        out_shape=[jax.ShapeDtypeStruct(s, z.dtype) for s in out_shapes],
        interpret=interpret(),
    )(z, *args)


@jax.custom_vjp
def fused_lstm_cell(z: jax.Array, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(h', c') from preactivations z [mb, 4n] (gate order [i|f|o|g]) and
    cell state c [mb, n].  One fused VMEM pass on TPU; exact fallbacks
    elsewhere."""
    n = c.shape[-1]
    if not _use_pallas(z, n):
        return _plain_cell(z, c)
    out = _pallas_call(_fwd_kernel, z, c,
                       out_shapes=[(z.shape[0], n), (z.shape[0], n)], n=n)
    return out[0], out[1]


def _cell_fwd(z, c):
    out = fused_lstm_cell(z, c)
    return out, (z, c)


def _cell_bwd(res, cts):
    z, c = res
    dh, dcn = cts
    n = c.shape[-1]
    # cotangents can arrive as zeros with a different weak type; normalize
    dh = jnp.asarray(dh, z.dtype)
    dcn = jnp.asarray(dcn, z.dtype)
    if not _use_pallas(z, n):
        return _bwd_math(z, c, dh, dcn)   # exact, f64-safe
    out = _pallas_call(_bwd_kernel, z, c, dh, dcn,
                       out_shapes=[z.shape, c.shape], n=n)
    return out[0], out[1]


fused_lstm_cell.defvjp(_cell_fwd, _cell_bwd)
