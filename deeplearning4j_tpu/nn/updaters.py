"""Updaters + LR schedules + gradient normalization.

Parity surface: ND4J ``IUpdater`` configs (org.nd4j.linalg.learning.config:
Sgd, Nesterovs, Adam, AdaMax, Nadam, AdaGrad, AdaDelta, RmsProp, NoOp) and
DL4J's updater machinery (nn/updater/BaseMultiLayerUpdater.java:38 —
``update():208-223`` applies per-block updater math, ``preApply():318``
applies gradient normalization/clipping).

Design: an Updater is a dataclass with ``init_state(params)`` and
``update(grads, state, iteration)`` → (updates, new_state); the train step
applies ``params -= updates`` (the reference's in-place
StepFunction.step equivalent).  The reference's flattened-view UpdaterBlock
machinery disappears: XLA fuses the per-leaf update ops as well as a flat
buffer would, without the aliasing hazards.

LR schedules follow LearningRatePolicy (nn/conf/LearningRatePolicy.java):
exponential / inverse / poly / sigmoid / step / map.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from .layers.base import register_config

Array = jax.Array


# ---------------------------------------------------------------------------
# learning-rate schedules
# ---------------------------------------------------------------------------


@register_config
@dataclasses.dataclass
class Schedule:
    """Fixed LR (base class doubles as the trivial schedule)."""

    lr: float = 1e-3

    def __call__(self, it: Array) -> Array:
        return jnp.asarray(self.lr, jnp.float32)


@register_config
@dataclasses.dataclass
class ExponentialSchedule(Schedule):
    decay: float = 0.99

    def __call__(self, it):
        return self.lr * jnp.power(self.decay, it.astype(jnp.float32))


@register_config
@dataclasses.dataclass
class InverseSchedule(Schedule):
    decay: float = 0.01
    power: float = 1.0

    def __call__(self, it):
        return self.lr / jnp.power(1.0 + self.decay * it.astype(jnp.float32), self.power)


@register_config
@dataclasses.dataclass
class PolySchedule(Schedule):
    power: float = 1.0
    max_iter: int = 10000

    def __call__(self, it):
        frac = jnp.clip(it.astype(jnp.float32) / self.max_iter, 0.0, 1.0)
        return self.lr * jnp.power(1.0 - frac, self.power)


@register_config
@dataclasses.dataclass
class SigmoidSchedule(Schedule):
    decay: float = 0.01
    steps: int = 1000

    def __call__(self, it):
        return self.lr / (1.0 + jnp.exp(-self.decay * (it.astype(jnp.float32) - self.steps)))


@register_config
@dataclasses.dataclass
class StepSchedule(Schedule):
    decay: float = 0.1
    steps: int = 1000

    def __call__(self, it):
        return self.lr * jnp.power(self.decay, jnp.floor(it.astype(jnp.float32) / self.steps))


def resolve_schedule(lr_or_schedule) -> Schedule:
    if isinstance(lr_or_schedule, Schedule):
        return lr_or_schedule
    return Schedule(lr=float(lr_or_schedule))


# ---------------------------------------------------------------------------
# updaters
# ---------------------------------------------------------------------------


def _zeros_like_tree(params):
    return jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params)


def _tree_update(fn, grads, *state_trees):
    """Apply ``fn(g, *state_leaves) -> (out1, out2, ...)`` leafwise over the
    gradient tree, returning one tree per output slot.  Replaces the
    flatten/zip/unflatten plumbing every updater needs."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_states = [treedef.flatten_up_to(s) for s in state_trees]
    outs = [fn(g, *(fs[i] for fs in flat_states)) for i, g in enumerate(flat_g)]
    if not isinstance(outs[0], tuple):
        return treedef.unflatten(outs)
    return tuple(treedef.unflatten([o[j] for o in outs]) for j in range(len(outs[0])))


@dataclasses.dataclass
class Updater:
    """Base updater config.  ``schedule`` may be a Schedule or raw float."""

    lr: Any = 1e-3

    def lr_at(self, it: Array) -> Array:
        return resolve_schedule(self.lr)(it)

    def init_state(self, params) -> Dict:
        return {}

    def update(self, grads, state, it: Array):
        raise NotImplementedError

    def apply(self, params, grads, state, it: Array):
        """One full optimizer application: updater math + the param step
        (``params -= updates`` in f32, cast back to each leaf's dtype) —
        what nn/multilayer._apply_updates runs per layer.  Subclasses
        with a fused one-pass kernel (ops/update_kernel.py) override
        this; the base implementation is the bit-exact reference."""
        updates, new_state = self.update(grads, state, it)
        new_params = jax.tree_util.tree_map(
            lambda pp, uu: (pp.astype(jnp.float32) - uu).astype(pp.dtype),
            params, updates)
        return new_params, new_state


@register_config
@dataclasses.dataclass
class Sgd(Updater):
    def update(self, grads, state, it):
        lr = self.lr_at(it)
        return jax.tree_util.tree_map(lambda g: lr * g.astype(jnp.float32), grads), state


@register_config
@dataclasses.dataclass
class Nesterovs(Updater):
    lr: Any = 0.1
    momentum: float = 0.9

    def init_state(self, params):
        return {"v": _zeros_like_tree(params)}

    def update(self, grads, state, it):
        lr, mu = self.lr_at(it), self.momentum

        def upd(g, v):
            # ND4J Nesterovs.java: vNew = mu*v - lr*g; update = mu*v - (1+mu)*vNew
            g = g.astype(jnp.float32)
            v_new = mu * v - lr * g
            return mu * v - (1.0 + mu) * v_new, v_new

        updates, new_v = _tree_update(upd, grads, state["v"])
        return updates, {"v": new_v}


@register_config
@dataclasses.dataclass
class Adam(Updater):
    """Adam (reference updater/AdamUpdater.java).

    ``moment_dtype`` (opt-in, e.g. "bfloat16") stores BOTH moments in a
    reduced dtype: the m/v read+write traffic is the dominant optimizer
    HBM cost on large models (~3.9 GB/step by byte count on the
    GPT-2-small TransformerLM), and bf16 keeps
    f32's exponent range so v's dynamic range survives — only mantissa
    precision drops, quantified by tests/test_updaters_bf16.py.  The
    update math always runs in f32; only the carried state narrows."""

    lr: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    moment_dtype: Any = None

    def _moments_like(self, params):
        z = _zeros_like_tree(params)
        if self.moment_dtype is None:
            return z
        dt = jnp.dtype(self.moment_dtype)
        return jax.tree_util.tree_map(lambda a: a.astype(dt), z)

    def init_state(self, params):
        return {"m": self._moments_like(params),
                "v": self._moments_like(params)}

    def update(self, grads, state, it):
        lr = self.lr_at(it)
        t = it.astype(jnp.float32) + 1.0
        bc1 = 1.0 - jnp.power(self.beta1, t)
        bc2 = 1.0 - jnp.power(self.beta2, t)

        def upd(g, m, v):
            g = g.astype(jnp.float32)
            m_new = self.beta1 * m.astype(jnp.float32) + (1 - self.beta1) * g
            v_new = self.beta2 * v.astype(jnp.float32) + (1 - self.beta2) * g * g
            step = lr * (m_new / bc1) / (jnp.sqrt(v_new / bc2) + self.eps)
            return step, m_new.astype(m.dtype), v_new.astype(v.dtype)

        updates, new_m, new_v = _tree_update(upd, grads, state["m"], state["v"])
        return updates, {"m": new_m, "v": new_v}

    def apply(self, params, grads, state, it):
        """Routes through the fused one-pass kernel (moment update +
        param step in one VMEM pass over flat bucketed buffers,
        ops/update_kernel.py) when it is enabled and applicable; the
        kernel's output is bit-identical to the per-leaf base path, which
        remains the fallback.  Exact Adam/Nadam only — AdaMax/AMSGrad
        subclasses carry different math and always take the base path."""
        from ..ops import update_kernel

        kind = update_kernel.kind_of(self)
        if kind is not None:
            fused = update_kernel.fused_apply(
                kind, self, params, grads, state, it)
            if fused is not None:
                return fused
        return super().apply(params, grads, state, it)


@register_config
@dataclasses.dataclass
class AdaMax(Adam):
    def update(self, grads, state, it):
        lr = self.lr_at(it)
        t = it.astype(jnp.float32) + 1.0
        bc1 = 1.0 - jnp.power(self.beta1, t)

        def upd(g, m, u):
            g = g.astype(jnp.float32)
            m_new = self.beta1 * m.astype(jnp.float32) + (1 - self.beta1) * g
            u_new = jnp.maximum(self.beta2 * u.astype(jnp.float32), jnp.abs(g))
            step = lr * (m_new / bc1) / (u_new + self.eps)
            return step, m_new.astype(m.dtype), u_new.astype(u.dtype)

        updates, new_m, new_v = _tree_update(upd, grads, state["m"], state["v"])
        return updates, {"m": new_m, "v": new_v}


@register_config
@dataclasses.dataclass
class Nadam(Adam):
    def update(self, grads, state, it):
        lr = self.lr_at(it)
        t = it.astype(jnp.float32) + 1.0
        bc1 = 1.0 - jnp.power(self.beta1, t)
        bc2 = 1.0 - jnp.power(self.beta2, t)

        def upd(g, m, v):
            g = g.astype(jnp.float32)
            m_new = self.beta1 * m.astype(jnp.float32) + (1 - self.beta1) * g
            v_new = self.beta2 * v.astype(jnp.float32) + (1 - self.beta2) * g * g
            m_hat = self.beta1 * (m_new / bc1) + (1 - self.beta1) * g / bc1
            step = lr * m_hat / (jnp.sqrt(v_new / bc2) + self.eps)
            return step, m_new.astype(m.dtype), v_new.astype(v.dtype)

        updates, new_m, new_v = _tree_update(upd, grads, state["m"], state["v"])
        return updates, {"m": new_m, "v": new_v}


@register_config
@dataclasses.dataclass
class AMSGrad(Adam):
    """AMSGrad (Reddi et al. 2018) — Adam with a monotone max on the
    second moment (upstream ND4J learning/config/AmsGrad.java; the
    reference's updater family resolves through nd4j).  State: m, v, and
    the running max v_hat."""

    def init_state(self, params):
        return {"m": self._moments_like(params),
                "v": self._moments_like(params),
                "vhat": self._moments_like(params)}

    def update(self, grads, state, it):
        lr = self.lr_at(it)
        t = it.astype(jnp.float32) + 1.0
        bc1 = 1.0 - jnp.power(self.beta1, t)
        bc2 = 1.0 - jnp.power(self.beta2, t)

        def upd(g, m, v, vh):
            g = g.astype(jnp.float32)
            m_new = self.beta1 * m.astype(jnp.float32) + (1 - self.beta1) * g
            v_new = self.beta2 * v.astype(jnp.float32) + (1 - self.beta2) * g * g
            vh_new = jnp.maximum(vh.astype(jnp.float32), v_new)
            step = lr * (m_new / bc1) / (jnp.sqrt(vh_new / bc2) + self.eps)
            return (step, m_new.astype(m.dtype), v_new.astype(v.dtype),
                    vh_new.astype(vh.dtype))

        updates, new_m, new_v, new_vh = _tree_update(
            upd, grads, state["m"], state["v"], state["vhat"])
        return updates, {"m": new_m, "v": new_v, "vhat": new_vh}


@register_config
@dataclasses.dataclass
class AdaGrad(Updater):
    lr: Any = 1e-1
    eps: float = 1e-6

    def init_state(self, params):
        return {"h": _zeros_like_tree(params)}

    def update(self, grads, state, it):
        lr = self.lr_at(it)

        def upd(g, h):
            g = g.astype(jnp.float32)
            h_new = h + g * g
            return lr * g / (jnp.sqrt(h_new) + self.eps), h_new

        updates, new_h = _tree_update(upd, grads, state["h"])
        return updates, {"h": new_h}


@register_config
@dataclasses.dataclass
class AdaDelta(Updater):
    rho: float = 0.95
    eps: float = 1e-6

    def init_state(self, params):
        return {"g2": _zeros_like_tree(params), "dx2": _zeros_like_tree(params)}

    def update(self, grads, state, it):
        def upd(g, g2, dx2):
            g = g.astype(jnp.float32)
            g2_new = self.rho * g2 + (1 - self.rho) * g * g
            step = jnp.sqrt(dx2 + self.eps) / jnp.sqrt(g2_new + self.eps) * g
            dx2_new = self.rho * dx2 + (1 - self.rho) * step * step
            return step, g2_new, dx2_new

        updates, new_g2, new_dx2 = _tree_update(upd, grads, state["g2"], state["dx2"])
        return updates, {"g2": new_g2, "dx2": new_dx2}


@register_config
@dataclasses.dataclass
class RmsProp(Updater):
    lr: Any = 1e-3
    rms_decay: float = 0.95
    eps: float = 1e-8

    def init_state(self, params):
        return {"g2": _zeros_like_tree(params)}

    def update(self, grads, state, it):
        lr = self.lr_at(it)

        def upd(g, g2):
            g = g.astype(jnp.float32)
            g2_new = self.rms_decay * g2 + (1 - self.rms_decay) * g * g
            return lr * g / (jnp.sqrt(g2_new) + self.eps), g2_new

        updates, new_g2 = _tree_update(upd, grads, state["g2"])
        return updates, {"g2": new_g2}


@register_config
@dataclasses.dataclass
class NoOp(Updater):
    def update(self, grads, state, it):
        return jax.tree_util.tree_map(lambda g: jnp.zeros_like(g, jnp.float32), grads), state


# ---------------------------------------------------------------------------
# gradient normalization (BaseMultiLayerUpdater.preApply parity)
# ---------------------------------------------------------------------------


class GradientNormalization:
    NONE = "none"
    RENORMALIZE_L2_PER_LAYER = "renormalize_l2_per_layer"
    RENORMALIZE_L2_PER_PARAM_TYPE = "renormalize_l2_per_param_type"
    CLIP_ELEMENTWISE_ABSOLUTE = "clip_elementwise_absolute_value"
    CLIP_L2_PER_LAYER = "clip_l2_per_layer"
    CLIP_L2_PER_PARAM_TYPE = "clip_l2_per_param_type"


def normalize_gradients(layer_grads: Dict[str, Array], mode: str, threshold: float) -> Dict[str, Array]:
    """Apply one layer's gradient normalization (reference preApply():318).

    ``layer_grads`` is the {param_name: grad} dict for a single layer.
    """
    if mode in (None, GradientNormalization.NONE):
        return layer_grads
    leaves, treedef = jax.tree_util.tree_flatten(layer_grads)
    if mode == GradientNormalization.RENORMALIZE_L2_PER_LAYER:
        norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
        scale = 1.0 / jnp.maximum(norm, 1e-8)
        return treedef.unflatten([g * scale.astype(g.dtype) for g in leaves])
    if mode == GradientNormalization.RENORMALIZE_L2_PER_PARAM_TYPE:
        return treedef.unflatten([
            g / jnp.maximum(jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2)), 1e-8).astype(g.dtype)
            for g in leaves])
    if mode == GradientNormalization.CLIP_ELEMENTWISE_ABSOLUTE:
        return treedef.unflatten([jnp.clip(g, -threshold, threshold) for g in leaves])
    if mode == GradientNormalization.CLIP_L2_PER_LAYER:
        norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in leaves))
        scale = jnp.where(norm > threshold, threshold / (norm + 1e-8), 1.0)
        return treedef.unflatten([g * scale.astype(g.dtype) for g in leaves])
    if mode == GradientNormalization.CLIP_L2_PER_PARAM_TYPE:
        out = []
        for g in leaves:
            norm = jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
            scale = jnp.where(norm > threshold, threshold / (norm + 1e-8), 1.0)
            out.append(g * scale.astype(g.dtype))
        return treedef.unflatten(out)
    raise ValueError(f"unknown gradient normalization mode {mode}")
