"""Normalization layers.

BatchNormalization — reference nn/layers/normalization/BatchNormalization.java
(+ CudnnBatchNormalizationHelper): per-feature affine with running mean/var
kept as non-trainable state ("global mean/var" updated with decay each fit
step).  LocalResponseNormalization — reference
nn/layers/normalization/LocalResponseNormalization.java (AlexNet-era LRN).
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..conf.inputs import InputType
from .base import ForwardOut, Layer, register_layer

Array = jax.Array


@register_layer
@dataclasses.dataclass
class BatchNormalization(Layer):
    """BN over the feature axis: CNN [mb,h,w,c] normalizes per-channel,
    FF [mb,f] per-feature (matching reference axis semantics on its NCHW).

    ``decay`` matches the reference's running-average decay (default 0.9);
    state keys "mean"/"var" correspond to GLOBAL_MEAN/GLOBAL_VAR params in
    BatchNormalizationParamInitializer (kept as state here since they are
    not gradient-trained).
    """

    n_features: int = 0
    eps: float = 1e-5
    decay: float = 0.9
    lock_gamma_beta: bool = False

    def infer_nin(self, in_type: InputType) -> None:
        if self.n_features == 0:
            self.n_features = in_type.channels if in_type.kind == "cnn" else in_type.size

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        if self.lock_gamma_beta:
            return {}
        return {
            "gamma": jnp.ones((self.n_features,), dtype),
            "beta": jnp.zeros((self.n_features,), dtype),
        }

    def init_state(self, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return {
            "mean": jnp.zeros((self.n_features,), dtype),
            "var": jnp.ones((self.n_features,), dtype),
        }

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        axes = tuple(range(x.ndim - 1))  # all but the trailing feature/channel axis
        if train:
            acc = jnp.promote_types(x.dtype, jnp.float32)
            if jnp.dtype(x.dtype).itemsize < 4:
                # bf16/f16 compute: E[x²]−E[x]² with f32-ACCUMULATING
                # reductions.  jnp.var would upcast the whole activation
                # and materialize (x−mean)² in f32 (and again in the
                # transpose), doubling HBM traffic — the dominant cost of
                # ResNet BN on TPU.
                # Caveat: this form loses the spread when |mean|/std ≳ 1e²
                # — but x itself carries an 8-bit mantissa here, so such
                # channels are already unresolvable in bf16; full-precision
                # robustness is what the f32 branch below is for.
                mean = jnp.mean(x, axis=axes, dtype=acc)
                mean2 = jnp.mean(lax.square(x), axis=axes, dtype=acc)
                var = jnp.maximum(mean2 - lax.square(mean), 0.0)
            else:
                # f32/f64 compute: two-pass jnp.var — numerically robust
                # (no cancellation for large-mean channels) and no dtype
                # upcast exists to cause extra traffic
                mean = jnp.mean(x, axis=axes)
                var = jnp.var(x, axis=axes)
            d = jnp.asarray(self.decay, state["mean"].dtype)
            new_state = {
                "mean": d * state["mean"] + (1 - d) * mean.astype(state["mean"].dtype),
                "var": d * state["var"] + (1 - d) * var.astype(state["var"].dtype),
            }
        else:
            mean, var = state["mean"].astype(x.dtype), state["var"].astype(x.dtype)
            new_state = state
        inv = lax.rsqrt(var.astype(x.dtype) + jnp.asarray(self.eps, x.dtype))
        y = (x - mean.astype(x.dtype)) * inv
        if not self.lock_gamma_beta:
            y = y * params["gamma"].astype(x.dtype) + params["beta"].astype(x.dtype)
        return ForwardOut(self._act(y), new_state, mask)

    def has_params(self) -> bool:
        return not self.lock_gamma_beta


@register_layer
@dataclasses.dataclass
class LocalResponseNormalization(Layer):
    """Cross-channel LRN: y = x / (k + α/n · Σ x²)^β over a sliding channel
    window (reference LocalResponseNormalization.java, defaults k=2, n=5,
    α=1e-4, β=0.75 per AlexNet)."""

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self) -> bool:
        return False

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        # channels last: sliding-window sum of squares over channel axis
        sq = x * x
        half = self.n // 2
        padded = jnp.pad(sq, ((0, 0), (0, 0), (0, 0), (half, half)))
        window = lax.reduce_window(
            padded, 0.0, lax.add,
            (1, 1, 1, self.n), (1, 1, 1, 1), "VALID")
        denom = (self.k + (self.alpha / self.n) * window) ** self.beta
        return ForwardOut(x / denom, state, mask)


def layer_norm(x: Array, gamma: Array, beta: Array, eps: float = 1e-5) -> Array:
    """Normalize the last axis; shared by LayerNorm and TransformerBlock."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * gamma.astype(acc) + beta.astype(acc)).astype(x.dtype)


@register_layer
@dataclasses.dataclass
class LayerNorm(Layer):
    """Per-token normalization over the feature axis (no reference analog —
    DL4J 0.9.2 predates LayerNorm; required by the transformer path)."""

    n_features: int = 0
    eps: float = 1e-5

    def infer_nin(self, in_type: InputType) -> None:
        if not self.n_features:
            self.n_features = in_type.size

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return {"gamma": jnp.ones((self.n_features,), dtype),
                "beta": jnp.zeros((self.n_features,), dtype)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        return ForwardOut(
            self._act(layer_norm(x, params["gamma"], params["beta"], self.eps)),
            state, mask)
