"""Loss functions — parity with ND4J ``ILossFunction`` implementations.

Reference: DL4J output layers hold an ``ILossFunction`` (LossFunctions enum:
MCXENT, XENT, MSE, L1, L2, NEGATIVELOGLIKELIHOOD, HINGE, SQUARED_HINGE,
KL_DIVERGENCE, POISSON, COSINE_PROXIMITY, MEAN_ABSOLUTE_PERCENTAGE_ERROR,
MEAN_SQUARED_LOGARITHMIC_ERROR) whose ``computeGradient`` is hand-written.
Here losses are pure functions of (labels, pre-activation output); gradients
come from autodiff.  Softmax+MCXENT and sigmoid+XENT are computed in fused,
numerically-stable log-space form — the reference relies on clipping
(LossUtil) instead.

Conventions (match the reference):
  - per-example score = sum of per-element loss over feature axes
  - network score = mean per-example score over the (masked) minibatch
  - binary losses expect labels in {0,1}; hinge expects {-1,+1} internally
    but accepts {0,1} and maps them (as LossHinge does).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .activations import get_activation

Array = jax.Array
_EPS = 1e-7


def _activated(preout: Array, activation) -> Array:
    return get_activation(activation)(preout)


class Loss:
    """A loss = per-element function + reduction, with optional fused paths.

    ``per_example(labels, preout, activation, mask)`` returns a [batch] (or
    [batch, time]) array of per-example scores; ``__call__`` reduces to the
    mean scalar the way MultiLayerNetwork.score() does (reference
    nn/multilayer/MultiLayerNetwork.java score accumulation).
    """

    def __init__(self, name: str, elementwise: Callable[[Array, Array], Array],
                 feature_mean: bool = False):
        self.name = name
        self._elementwise = elementwise
        # reference: LossMSE = LossL2 / nOut, LossMAE = LossL1 / nOut
        # (per-example score averaged, not summed, over output columns)
        self._feature_mean = feature_mean

    def per_element(self, labels: Array, preout: Array, activation="identity") -> Array:
        if (jnp.issubdtype(labels.dtype, jnp.integer)
                and labels.ndim == preout.ndim - 1):
            # sparse class-index labels (the TPU-native data path: the host
            # ships 4-byte ids, the device materializes the one-hot) —
            # numerically identical to dense one-hot labels
            labels = jax.nn.one_hot(labels, preout.shape[-1], dtype=preout.dtype)
        if self.name in ("mcxent", "negativeloglikelihood") and _act_name(activation) == "softmax":
            logp = jax.nn.log_softmax(preout, axis=-1)
            return -labels * logp
        if self.name == "xent" and _act_name(activation) == "sigmoid":
            # stable sigmoid BCE from logits
            z, y = preout, labels
            return jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        out = _activated(preout, activation)
        return self._elementwise(labels, out)

    def per_example(
        self,
        labels: Array,
        preout: Array,
        activation="identity",
        mask: Optional[Array] = None,
    ) -> Array:
        el = self.per_element(labels, preout, activation)
        if mask is not None:
            el = el * _broadcast_mask(mask, el.shape)
        s = jnp.sum(el, axis=-1)
        if self._feature_mean:
            s = s / el.shape[-1]
        return s

    def __call__(
        self,
        labels: Array,
        preout: Array,
        activation="identity",
        mask: Optional[Array] = None,
    ) -> Array:
        """Reduce to the network score.  Mask shapes supported (reference
        ILossFunction computeScore + MaskedReductionUtil semantics):
          - mask.shape == per-example shape ([mb] or [mb, t]): average over
            present entries only (per-timestep / per-example masking)
          - mask.shape == labels.shape: per-output weighting; average over
            entries with any unmasked output
        """
        pe = self.per_example(labels, preout, activation, mask)
        if mask is not None:
            if mask.shape == pe.shape:
                present = mask
            elif mask.shape == labels.shape:
                present = (jnp.max(mask, axis=-1) > 0).astype(pe.dtype)
            else:  # broadcastable per-example mask, e.g. [mb, 1]
                present = jnp.broadcast_to(mask.reshape(mask.shape[: pe.ndim]), pe.shape)
            return jnp.sum(pe) / jnp.maximum(jnp.sum(present), 1.0)
        return jnp.mean(pe)


def _act_name(activation) -> str:
    return activation if isinstance(activation, str) else getattr(activation, "__name__", "")


def _broadcast_mask(mask: Array, shape) -> Array:
    m = mask
    while m.ndim < len(shape):
        m = m[..., None]
    return jnp.broadcast_to(m, shape)


def _mse(y, out):
    d = out - y
    return d * d


def _l2(y, out):
    d = out - y
    return d * d


def _l1(y, out):
    return jnp.abs(out - y)


def _mae(y, out):
    return jnp.abs(out - y)


def _xent(y, out):
    out = jnp.clip(out, _EPS, 1.0 - _EPS)
    return -(y * jnp.log(out) + (1.0 - y) * jnp.log1p(-out))


def _mcxent(y, out):
    return -y * jnp.log(jnp.clip(out, _EPS, 1.0))


def _hinge(y, out):
    yy = jnp.where(y > 0.5, 1.0, -1.0)
    return jnp.maximum(0.0, 1.0 - yy * out)


def _squared_hinge(y, out):
    yy = jnp.where(y > 0.5, 1.0, -1.0)
    h = jnp.maximum(0.0, 1.0 - yy * out)
    return h * h


def _kld(y, out):
    yc = jnp.clip(y, _EPS, 1.0)
    oc = jnp.clip(out, _EPS, 1.0)
    return yc * (jnp.log(yc) - jnp.log(oc))


def _poisson(y, out):
    return out - y * jnp.log(jnp.clip(out, _EPS, None))


def _cosine_proximity(y, out):
    # summed over the feature axis downstream; spread the scalar across elements
    yn = y / jnp.clip(jnp.linalg.norm(y, axis=-1, keepdims=True), _EPS)
    on = out / jnp.clip(jnp.linalg.norm(out, axis=-1, keepdims=True), _EPS)
    return -(yn * on)


def _mape(y, out):
    return 100.0 * jnp.abs((y - out) / jnp.clip(jnp.abs(y), _EPS))


def _msle(y, out):
    d = jnp.log1p(jnp.clip(out, -1 + _EPS, None)) - jnp.log1p(jnp.clip(y, -1 + _EPS, None))
    return d * d


_REGISTRY = {
    "mse": Loss("mse", _mse, feature_mean=True),
    "l2": Loss("l2", _l2),
    "l1": Loss("l1", _l1),
    "mae": Loss("mae", _mae, feature_mean=True),
    "xent": Loss("xent", _xent),
    "mcxent": Loss("mcxent", _mcxent),
    "negativeloglikelihood": Loss("negativeloglikelihood", _mcxent),
    "hinge": Loss("hinge", _hinge),
    "squared_hinge": Loss("squared_hinge", _squared_hinge),
    "kl_divergence": Loss("kl_divergence", _kld),
    "poisson": Loss("poisson", _poisson),
    "cosine_proximity": Loss("cosine_proximity", _cosine_proximity),
    "mape": Loss("mape", _mape),
    "msle": Loss("msle", _msle),
}


def get_loss(name) -> Loss:
    if isinstance(name, Loss):
        return name
    key = name.lower()
    if key not in _REGISTRY:
        raise ValueError(f"Unknown loss '{name}'. Known: {sorted(_REGISTRY)}")
    return _REGISTRY[key]


def loss_names() -> list[str]:
    return sorted(_REGISTRY)


def summed_per_example(loss_name, labels, preout, activation="identity",
                       mask=None) -> Array:
    """[mb] per-example scores: elementwise loss summed over features AND
    any trailing time axis — the single reference-scoreExamples reduction
    the output layers' score_examples methods share."""
    pe = get_loss(loss_name).per_example(labels, preout,
                                         activation or "identity", mask)
    return pe.sum(axis=tuple(range(1, pe.ndim)))


# ---------------------------------------------------------------------------
# fused sparse softmax cross-entropy (large-vocab LM loss)
# ---------------------------------------------------------------------------


@jax.custom_vjp
def sparse_softmax_xent(logits: Array, targets: Array) -> Array:
    """Mean token NLL for integer targets WITHOUT materializing the f32
    log-softmax over the vocab.

    ``logits`` [..., V] (any float dtype, typically bf16), ``targets``
    [...] int.  A naive ``log_softmax(logits.astype(f32))`` writes an f32
    [..., V] tensor plus its gradient — at GPT-2 vocab (50K) that is the
    single largest HBM stream in the train step.  Here the forward keeps
    only per-row (max, log-sum-exp) f32 statistics (fused by XLA into
    streaming reductions over the bf16 logits) and the backward rebuilds
    ``softmax − onehot`` in the logits dtype from the saved lse — ~2.5×
    less loss-region traffic by byte count.  No reference analog (DL4J's LossMCXENT
    densifies labels; its vocab-scale path is sampled hierarchical
    softmax).
    """
    nll, _ = _sparse_xent_fwd(logits, targets)
    return nll


def _sparse_xent_fwd(logits, targets):
    lmax = jnp.max(logits, axis=-1)                       # [...] in dtype
    shifted = logits - lmax[..., None]
    sumexp = jnp.sum(jnp.exp(shifted.astype(jnp.float32)), axis=-1)
    lse = lmax.astype(jnp.float32) + jnp.log(sumexp)      # [..., ] f32
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jnp.mean(lse - tgt.astype(jnp.float32))
    return nll, (logits, targets, lse)


def _sparse_xent_bwd(res, g):
    logits, targets, lse = res
    n = lse.size  # mean over all token positions
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=jnp.float32)
    dlogits = ((p - onehot) * (g / n)).astype(logits.dtype)
    return dlogits, None


sparse_softmax_xent.defvjp(_sparse_xent_fwd, _sparse_xent_bwd)
