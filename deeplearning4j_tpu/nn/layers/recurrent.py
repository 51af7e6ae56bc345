"""Recurrent family — ``lax.scan`` replaces the reference's hand-written
per-timestep loop (nn/layers/recurrent/LSTMHelpers.java:68,392 shared
fwd/bwd for all LSTM variants; CudnnLSTMHelper on GPU).

Layout: [mb, time, features] (reference is [mb, features, time]).  Gate
order in the fused 4*n_out kernels: [i, f, o, g] (input, forget, output,
cell-candidate).  Param keys match LSTMParamInitializer.java:48-50:
"W" (input weights), "RW" (recurrent weights), "b".

GravesLSTM adds peephole connections (param "pW": [3*n_out] for i,f,o —
reference GravesLSTMParamInitializer packs them into RW's extra columns; we
keep a separate key for clarity).  GravesBidirectionalLSTM runs forward and
backward passes and SUMS their outputs
(reference GravesBidirectionalLSTM.java:219 "sum outputs").

Statefulness: ``rnnTimeStep``-style streaming inference (reference
MultiLayerNetwork.rnnTimeStep:2636) is provided by ``step()`` which takes and
returns the carry explicitly — functional, jit-friendly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.activations import get_activation
from ...ops.initializers import init_weight
from ...ops.losses import get_loss
from ..conf.inputs import InputType
from .base import ForwardOut, Layer, register_layer

Array = jax.Array


def _lstm_cell(cfg, params, carry, x_t, mask_t=None, suffix="", zx_t=None):
    """One LSTM step.  carry = (h, c); x_t [mb, n_in]; mask_t [mb] or None.
    ``zx_t`` is the precomputed input projection x_t @ W (see _scan_lstm —
    batching the projection over all timesteps is one big MXU matmul
    instead of T small ones, and enables integer-index inputs).

    The standard sigmoid/tanh non-peephole cell calls
    ops/lstm_kernel.fused_lstm_cell — which resolves to XLA's (faster,
    epilogue-fused) plain math by default and to the pallas kernel when
    opted in via DL4J_TPU_FUSED_LSTM=1; custom activations and peepholes
    use the general path."""
    h, c = carry
    if zx_t is None:
        zx_t = x_t @ params["W" + suffix].astype(x_t.dtype)
    RW = params["RW" + suffix].astype(zx_t.dtype)
    b = params["b" + suffix].astype(zx_t.dtype)
    z = zx_t + h @ RW + b  # [mb, 4*n_out]
    n = cfg.n_out
    if (not cfg.peephole and cfg.gate_activation == "sigmoid"
            and cfg.activation == "tanh"):
        from ...ops.lstm_kernel import fused_lstm_cell
        h_new, c_new = fused_lstm_cell(z, c)
        if mask_t is not None:
            m = mask_t[:, None].astype(h_new.dtype)
            h_new = m * h_new + (1 - m) * h
            c_new = m * c_new + (1 - m) * c
        return (h_new, c_new)
    zi, zf, zo, zg = z[:, :n], z[:, n:2 * n], z[:, 2 * n:3 * n], z[:, 3 * n:]
    gate = get_activation(cfg.gate_activation)
    act = get_activation(cfg.activation)
    if cfg.peephole:
        pW = params["pW" + suffix].astype(z.dtype)
        pi, pf, po = pW[:n], pW[n:2 * n], pW[2 * n:]
        i = gate(zi + c * pi)
        f = gate(zf + c * pf)
        c_new = f * c + i * act(zg)
        o = gate(zo + c_new * po)
    else:
        i, f, o = gate(zi), gate(zf), gate(zo)
        c_new = f * c + i * act(zg)
    h_new = o * act(c_new)
    if mask_t is not None:
        m = mask_t[:, None].astype(h_new.dtype)
        h_new = m * h_new + (1 - m) * h
        c_new = m * c_new + (1 - m) * c
    return (h_new, c_new)


def _scan_lstm(cfg, params, x, mask, h0, c0, reverse=False, suffix=""):
    """Scan the cell over time. x [mb,t,f] (or int indices [mb,t]) →
    outputs [mb,t,n_out] + final carry.

    The input projection x @ W is hoisted out of the scan: one [mb·t, f]
    × [f, 4n] MXU matmul instead of t small ones.  Integer inputs take the
    gather form W[x] — mathematically identical to one_hot(x) @ W with the
    same parameters, but the host ships 2-byte indices instead of f-float
    one-hots (a ~vocab× smaller host→device transfer)."""
    W = params["W" + suffix]
    if jnp.issubdtype(x.dtype, jnp.integer):
        # gather in the COMPUTE dtype (h0's dtype — the carry carries it):
        # W.dtype is the param dtype, which under mixed precision (f32
        # params, bf16 compute) would poison the scan carry dtype
        zx = W[x].astype(h0.dtype)      # [mb, t, 4n] embedding-style gather
    else:
        zx = x @ W.astype(x.dtype)      # [mb, t, 4n]
    zxT = jnp.swapaxes(zx, 0, 1)        # [t, mb, 4n]
    maskT = None if mask is None else jnp.swapaxes(mask, 0, 1)  # [t, mb]

    def body(carry, inp):
        zx_t, m_t = inp
        new = _lstm_cell(cfg, params, carry, None, m_t, suffix, zx_t=zx_t)
        return new, new[0]

    inputs = (zxT, maskT if maskT is not None else jnp.ones(zxT.shape[:2], zx.dtype))
    # unroll=4: XLA pipelines/fuses across unrolled cell iterations —
    # measured +40% char-RNN training throughput vs unroll=1 on the chip
    # (unroll=8 regresses: code bloat); semantics unchanged
    (hF, cF), hs = lax.scan(body, (h0, c0), inputs, reverse=reverse,
                            unroll=4)
    return jnp.swapaxes(hs, 0, 1), (hF, cF)


@register_layer
@dataclasses.dataclass
class LSTM(Layer):
    """Standard LSTM, no peepholes (reference nn/conf/layers/LSTM.java)."""

    wants = "rnn"
    recurrent = True

    n_in: int = 0
    n_out: int = 0
    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"
    activation: str = "tanh"
    peephole: bool = False

    def infer_nin(self, in_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = in_type.size

    def output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def _init_direction(self, rng, dtype, suffix="") -> Dict[str, Array]:
        k1, k2, k3 = jax.random.split(rng, 3)
        n = self.n_out
        b = jnp.zeros((4 * n,), dtype)
        b = b.at[n:2 * n].set(self.forget_gate_bias_init)  # forget-gate bias
        p = {
            "W" + suffix: init_weight(k1, (self.n_in, 4 * n), self._winit(), self.n_in, n, dtype),
            "RW" + suffix: init_weight(k2, (n, 4 * n), self._winit(), n, n, dtype),
            "b" + suffix: b,
        }
        if self.peephole:
            p["pW" + suffix] = init_weight(k3, (3 * n,), "uniform", n, n, dtype)
        return p

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return self._init_direction(rng, dtype)

    def zero_carry(self, mb: int, dtype=jnp.float32) -> Tuple[Array, Array]:
        return (jnp.zeros((mb, self.n_out), dtype), jnp.zeros((mb, self.n_out), dtype))

    def init_carry(self, mb: int, dtype=jnp.float32):
        return self.zero_carry(mb, dtype)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None,
                carry=None) -> ForwardOut:
        if not jnp.issubdtype(x.dtype, jnp.integer):
            x = self._maybe_dropout(x, train, rng)
            cdt = x.dtype
        else:
            # index inputs: dropout on raw ids is meaningless — skip; the
            # compute dtype comes from the container (set per trace by
            # _apply_layers), falling back to the param dtype
            cdt = jnp.dtype(getattr(self, "_compute_dtype", None)
                            or params["W"].dtype)
        h0, c0 = carry if carry is not None else self.zero_carry(x.shape[0], cdt)
        ys, final = _scan_lstm(self, params, x, mask, h0, c0)
        return ForwardOut(ys, state, mask, final)

    def step(self, params, carry, x_t):
        """Single streaming step (rnnTimeStep parity): x_t [mb, n_in]
        dense, or [mb] integer indices (same gather form as _scan_lstm)."""
        if jnp.issubdtype(x_t.dtype, jnp.integer):
            new = _lstm_cell(self, params, carry, None, zx_t=params["W"][x_t])
        else:
            new = _lstm_cell(self, params, carry, x_t)
        return new[0], new


@register_layer
@dataclasses.dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (reference GravesLSTM.java, per
    Graves 2013 'Generating Sequences with RNNs')."""

    peephole: bool = True


@register_layer
@dataclasses.dataclass
class GravesBidirectionalLSTM(LSTM):
    """Bidirectional peephole LSTM; fwd+bwd outputs are SUMMED
    (reference GravesBidirectionalLSTM.java:219).  Not streamable: the
    backward pass needs the whole sequence, so no carry support (matches
    the reference, which disallows rnnTimeStep on bidirectional layers)."""

    recurrent = False
    peephole: bool = True

    def init_carry(self, mb, dtype=jnp.float32):
        return None

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        kf, kb = jax.random.split(rng)
        p = self._init_direction(kf, dtype, suffix="F")
        p.update(self._init_direction(kb, dtype, suffix="B"))
        return p

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        if not jnp.issubdtype(x.dtype, jnp.integer):
            x = self._maybe_dropout(x, train, rng)
            cdt = x.dtype
        else:
            cdt = jnp.dtype(getattr(self, "_compute_dtype", None)
                            or params["WF"].dtype)
        h0, c0 = self.zero_carry(x.shape[0], cdt)
        fwd, _ = _scan_lstm(self, params, x, mask, h0, c0, reverse=False, suffix="F")
        bwd, _ = _scan_lstm(self, params, x, mask, h0, c0, reverse=True, suffix="B")
        return ForwardOut(fwd + bwd, state, mask)


@register_layer
@dataclasses.dataclass
class SimpleRnn(Layer):
    """Vanilla RNN: h_t = act(x_t·W + h_{t-1}·RW + b)."""

    wants = "rnn"
    recurrent = True

    n_in: int = 0
    n_out: int = 0
    activation: str = "tanh"

    def infer_nin(self, in_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = in_type.size

    def output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        k1, k2 = jax.random.split(rng)
        return {
            "W": init_weight(k1, (self.n_in, self.n_out), self._winit(), self.n_in, self.n_out, dtype),
            "RW": init_weight(k2, (self.n_out, self.n_out), self._winit(), self.n_out, self.n_out, dtype),
            "b": jnp.full((self.n_out,), self.bias_init, dtype),
        }

    def init_carry(self, mb: int, dtype=jnp.float32):
        return jnp.zeros((mb, self.n_out), dtype)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None,
                carry=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        act = get_activation(self.activation)
        W = params["W"].astype(x.dtype)
        RW = params["RW"].astype(x.dtype)
        b = params["b"].astype(x.dtype)
        xT = jnp.swapaxes(x, 0, 1)
        maskT = None if mask is None else jnp.swapaxes(mask, 0, 1)

        def body(h, inp):
            x_t, m_t = inp
            h_new = act(x_t @ W + h @ RW + b)
            if maskT is not None:
                m = m_t[:, None].astype(h_new.dtype)
                h_new = m * h_new + (1 - m) * h
            return h_new, h_new

        h0 = carry if carry is not None else self.init_carry(x.shape[0], x.dtype)
        inputs = (xT, maskT if maskT is not None else jnp.ones(xT.shape[:2], x.dtype))
        hF, hs = lax.scan(body, h0, inputs)
        return ForwardOut(jnp.swapaxes(hs, 0, 1), state, mask, hF)


@register_layer
@dataclasses.dataclass
class Bidirectional(Layer):
    """Wrapper running any recurrent layer fwd+bwd with a combine mode
    (CONCAT / ADD / MUL / AVERAGE) — generalizes the reference's
    Graves-only bidirectionality."""

    layer: Optional[Layer] = None
    mode: str = "concat"

    def infer_nin(self, in_type: InputType) -> None:
        self.layer.infer_nin(in_type)

    def output_type(self, in_type: InputType) -> InputType:
        inner = self.layer.output_type(in_type)
        if self.mode == "concat":
            return InputType.recurrent(inner.size * 2, inner.timesteps)
        return inner

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        kf, kb = jax.random.split(rng)
        return {
            "fwd": self.layer.init_params(kf, in_type, dtype),
            "bwd": self.layer.init_params(kb, in_type, dtype),
        }

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        rf = rb = None
        if rng is not None:
            rf, rb = jax.random.split(rng)
        fwd = self.layer.forward(params["fwd"], {}, x, train=train, rng=rf, mask=mask).y
        xrev = jnp.flip(x, axis=1)
        mrev = None if mask is None else jnp.flip(mask, axis=1)
        bwd = self.layer.forward(params["bwd"], {}, xrev, train=train, rng=rb, mask=mrev).y
        bwd = jnp.flip(bwd, axis=1)
        if self.mode == "concat":
            y = jnp.concatenate([fwd, bwd], axis=-1)
        elif self.mode == "add":
            y = fwd + bwd
        elif self.mode == "mul":
            y = fwd * bwd
        elif self.mode == "average":
            y = 0.5 * (fwd + bwd)
        else:
            raise ValueError(self.mode)
        return ForwardOut(y, state, mask)

    def regularization_score(self, params):
        return self.layer.regularization_score(params["fwd"]) + self.layer.regularization_score(params["bwd"])


@register_layer
@dataclasses.dataclass
class RnnOutputLayer(Layer):
    """Time-distributed dense + per-timestep loss (reference
    nn/conf/layers/RnnOutputLayer.java; masked loss averaging per
    LossFunction masking semantics)."""

    wants = "rnn"

    n_in: int = 0
    n_out: int = 0
    loss: str = "mcxent"
    has_bias: bool = True

    def infer_nin(self, in_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = in_type.size

    def output_type(self, in_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, in_type.timesteps)

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return self._dense_init(rng, self.n_in, self.n_out, dtype)

    def _pre(self, params, x):
        y = x @ params["W"].astype(x.dtype)
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return y

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        return ForwardOut(self._act(self._pre(params, x)), state, mask)

    def score(self, params, state, x, labels, *, mask: Optional[Array] = None) -> Array:
        pre = self._pre(params, x)  # [mb, t, n_out]
        return get_loss(self.loss)(labels, pre, self.activation or "identity", mask)

    def score_examples(self, params, state, x, labels, *,
                       mask: Optional[Array] = None) -> Array:
        """[mb] scores: per-timestep loss summed over the sequence
        (reference scoreExamples on RNN output layers)."""
        pre = self._pre(params, x)
        from ...ops.losses import summed_per_example
        return summed_per_example(self.loss, labels, pre, self.activation, mask)


@register_layer
@dataclasses.dataclass
class LastTimeStep(Layer):
    """Wrapper: inner recurrent layer, emit only the last (masked) timestep
    (reference conf/graph/rnn/LastTimeStepVertex.java as a layer wrapper)."""

    layer: Optional[Layer] = None

    def infer_nin(self, in_type: InputType) -> None:
        self.layer.infer_nin(in_type)

    def output_type(self, in_type: InputType) -> InputType:
        inner = self.layer.output_type(in_type)
        return InputType.feed_forward(inner.size)

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return self.layer.init_params(rng, in_type, dtype)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        out = self.layer.forward(params, state, x, train=train, rng=rng, mask=mask)
        ys = out.y  # [mb, t, f]
        if mask is not None:
            idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)  # [mb]
            y = ys[jnp.arange(ys.shape[0]), idx]
        else:
            y = ys[:, -1]
        return ForwardOut(y, out.state, None)

    def regularization_score(self, params):
        return self.layer.regularization_score(params)
