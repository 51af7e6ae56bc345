"""Mixture-of-Experts with expert parallelism (EP).

No reference analog (DL4J predates MoE); SURVEY §2.3 lists EP as the
remaining first-class TPU parallelism axis.  Design follows the
Shazeer/Switch lineage the TPU stack was built around:

  - router: tokens → top-k experts (softmax over the selected logits)
  - experts: per-expert FFN [d_model → d_ff → d_model], params stacked on
    a leading expert dim so ALL experts compute as one batched einsum
    (MXU-shaped, no ragged work)
  - EP sharding: experts split over a mesh axis inside ``shard_map``;
    tokens stay replicated on that axis, each shard computes only its
    local experts' capacity slots, and one ``psum`` merges expert
    contributions — collective traffic = activations once per layer,
    the standard replicated-token/sharded-expert formulation
  - capacity: fixed per-expert slots (ceil(k·N/E·capacity_factor));
    overflow tokens are dropped by the dispatch one-hot exactly as in
    Switch — keeps every shape static for XLA

``moe_forward_dense`` is the exact (every expert sees every token's
gate-weighted input) single-device path used for parity tests and the
``MoE`` layer; ``moe_forward_ep`` is the sharded production path.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def init_moe_params(rng: Array, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32) -> Dict[str, Array]:
    k1, k2, k3 = jax.random.split(rng, 3)
    s_in = (2.0 / d_model) ** 0.5
    s_ff = (2.0 / d_ff) ** 0.5
    return {
        "Wg": jax.random.normal(k1, (d_model, n_experts), dtype) * s_in,
        "W1": jax.random.normal(k2, (n_experts, d_model, d_ff), dtype) * s_in,
        "b1": jnp.zeros((n_experts, d_ff), dtype),
        "W2": jax.random.normal(k3, (n_experts, d_ff, d_model), dtype) * s_ff,
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def _router(params, x, k: int):
    """→ (gates [N,E] with nonzeros only on the top-k, aux load-balance
    loss).  Gates renormalize softmax over the selected logits (Shazeer
    2017); aux loss is the Switch E·Σ f_e·p_e balance term."""
    logits = x @ params["Wg"].astype(x.dtype)            # [N,E]
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(logits, k)                # [N,k]
    gate_v = jax.nn.softmax(topv, axis=-1)               # renormalized
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], topi].set(gate_v)
    # load balance: fraction routed vs mean prob per expert
    frac = jnp.mean((gates > 0).astype(x.dtype), axis=0)  # [E]
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    return gates, aux


def moe_forward_dense(params: Dict[str, Array], x: Array, k: int = 2
                      ) -> Tuple[Array, Array]:
    """Exact MoE: every expert processes every token, outputs combined by
    the (sparse) gates.  O(E·N·d·f) — the test/teaching path.
    x [N, d_model] → (y [N, d_model], aux_loss)."""
    gates, aux = _router(params, x, k)
    h = jnp.einsum("nd,edf->nef", x, params["W1"].astype(x.dtype))
    h = jax.nn.relu(h + params["b1"].astype(x.dtype)[None])
    y_e = jnp.einsum("nef,efd->ned", h, params["W2"].astype(x.dtype))
    y_e = y_e + params["b2"].astype(x.dtype)[None]
    y = jnp.einsum("ne,ned->nd", gates, y_e)
    return y, aux


def capacity(n_tokens: int, n_experts: int, k: int,
             capacity_factor: float = 1.25) -> int:
    """Per-expert token slots (Switch capacity), computed statically."""
    return max(1, int(np.ceil(k * n_tokens / n_experts * capacity_factor)))


def moe_forward_ep(params: Dict[str, Array], x: Array, mesh: Mesh,
                   expert_axis: str = "model", k: int = 2,
                   capacity_factor: float = 1.25,
                   data_axis: Optional[str] = "data") -> Tuple[Array, Array]:
    """Expert-parallel MoE over ``expert_axis``, DP-composable.

    Experts are sharded over ``expert_axis``; tokens are sharded over
    ``data_axis`` (when the mesh has one) and replicated over the expert
    axis.  Each shard builds dispatch/combine one-hots for its LOCAL
    experts on its LOCAL tokens, computes its capacity slots, and a psum
    over the expert axis merges the gate-weighted expert outputs.
    Capacity is per data shard (each shard routes its own tokens).
    Dropped (over-capacity) tokens contribute zero, exactly like Switch.
    """
    E = params["Wg"].shape[-1]
    M = mesh.shape[expert_axis]
    if E % M:
        raise ValueError(f"n_experts {E} not divisible by {expert_axis} "
                         f"axis size {M}")
    if data_axis is not None and data_axis not in mesh.shape:
        data_axis = None
    D = mesh.shape[data_axis] if data_axis else 1
    N = x.shape[0]
    if N % D:
        raise ValueError(f"token count {N} not divisible by {data_axis} "
                         f"axis size {D}")
    C = capacity(N // D, E, k, capacity_factor)
    e_loc = E // M

    expert_keys = ("W1", "b1", "W2", "b2")
    in_specs = (
        {kk: (P(expert_axis) if kk in expert_keys else P())
         for kk in params},
        P(data_axis),   # tokens sharded over data, replicated over experts
    )
    out_specs = (P(data_axis), P())

    def shard_fn(p, xs):
        idx = jax.lax.axis_index(expert_axis)
        gates, aux = _router(p, xs, k)          # identical across expert axis
        aux = aux / M                           # psum'd below → global value
        if data_axis:
            aux = jax.lax.pmean(aux, data_axis)  # average over token shards
        local_gates = jax.lax.dynamic_slice_in_dim(
            gates, idx * e_loc, e_loc, axis=1)  # [N, e_loc]
        # position of each token within its expert's capacity buffer:
        # cumulative count of prior routed tokens for that expert
        routed = (local_gates > 0).astype(jnp.int32)          # [N, e_loc]
        pos = jnp.cumsum(routed, axis=0) - routed             # [N, e_loc]
        keep = routed * (pos < C)
        # dispatch one-hot [N, e_loc, C]
        disp = keep[..., None] * jax.nn.one_hot(pos, C, dtype=xs.dtype)
        exp_in = jnp.einsum("nec,nd->ecd", disp, xs)          # [e_loc, C, d]
        # expert params cast to the token dtype — same mixed-precision
        # contract as moe_forward_dense
        W1, b1 = p["W1"].astype(xs.dtype), p["b1"].astype(xs.dtype)
        W2, b2 = p["W2"].astype(xs.dtype), p["b2"].astype(xs.dtype)
        h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", exp_in, W1)
                        + b1[:, None, :])
        out = jnp.einsum("ecf,efd->ecd", h, W2) + b2[:, None, :]
        combine = disp * local_gates[..., None]               # gate-weighted
        y_local = jnp.einsum("nec,ecd->nd", combine, out)
        y = jax.lax.psum(y_local, expert_axis)
        return y, jax.lax.psum(aux, expert_axis)

    fn = shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    return fn(params, x)


# ---------------------------------------------------------------------------
# dropless routed experts, told which experts live here (ROADMAP M4)
# ---------------------------------------------------------------------------
#
# The Switch path above (softmax router, biased ReLU experts, a fixed
# capacity that DROPS overflow) stays for its own users (the ``MoE``
# layer, ``moe_forward_ep``).  The layer below is what an LM block of the
# DeepSeek-V3 family needs and what expert parallelism asks of a chip:
# route over ALL ``n_experts``, compute the part of the result that the
# experts held here give, never drop a token.  On one chip it runs
# without its exchange; nothing stands in for the absent chips.

#: what ``moe_forward_held`` counts, in this order (int32 [4])
EXPERT_STATS = ("expert_picks", "expert_picks_held", "expert_load_max",
                "experts_hit")


def init_held_experts(rng: Array, d_model: int, d_ff: int, n_experts: int,
                      experts_held: int, n_shared: int = 1,
                      std: float = 0.02, bias_std: float = 0.1,
                      dtype=jnp.float32,
                      router: str = "noaux_tc") -> Dict[str, Array]:
    """Router over all ``n_experts`` (weights and, for ``noaux_tc``, the
    correction bias, drawn non-zero so the choice-only path is worked),
    gated-SiLU experts for the ``experts_held`` that live here, and the
    shared expert (``n_shared`` experts' width in one)."""
    kg, kb, k1, k2, k3, k4, k5, k6 = jax.random.split(rng, 8)

    def normal(key, shape, s=std):
        return (s * jax.random.normal(key, shape, jnp.float32)).astype(dtype)

    p = {
        "router_w": normal(kg, (d_model, n_experts)),
        "router_b": (bias_std * jax.random.normal(
            kb, (n_experts,), jnp.float32)),
        "e_gate": normal(k1, (experts_held, d_model, d_ff)),
        "e_up": normal(k2, (experts_held, d_model, d_ff)),
        "e_down": normal(k3, (experts_held, d_ff, d_model)),
    }
    if router != "noaux_tc":
        del p["router_b"]
    if n_shared:
        f = n_shared * d_ff
        p.update(s_gate=normal(k4, (d_model, f)), s_up=normal(k5, (d_model, f)),
                 s_down=normal(k6, (f, d_model)))
    return p


def route_noaux_tc(x: Array, router_w: Array, router_b: Array, k: int,
                   scaling: float, eps: float = 1e-20
                   ) -> Tuple[Array, Array]:
    """The ``noaux_tc`` gate with one group, in float32: scores are
    ``sigmoid(x W)``; the ``k`` experts are the top k of ``scores +
    bias``; their weights are the SCORES (without the bias) of those k,
    divided by their sum ``+ eps`` (1e-20 in DeepSeek-V3's gate, which
    Kimi's and Solar's files reuse; LFM2's adds 1e-6), times ``scaling``.
    ``x`` [N, d] -> (expert ids [N, k] int32, weights [N, k] f32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + router_b.astype(jnp.float32), k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scaling
    return idx.astype(jnp.int32), w


def route_softmax_topk(x: Array, router_w: Array, k: int
                       ) -> Tuple[Array, Array]:
    """The softmax gate of the Qwen3-MoE family with ``norm_topk_prob``,
    in float32: probabilities are ``softmax(x W)`` over all experts; the
    ``k`` experts are its top k; their weights are those probabilities
    divided by their sum.  No bias, no scaling.
    ``x`` [N, d] -> (expert ids [N, k] int32, weights [N, k] f32)."""
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return idx.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)


def gated_silu(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    """``W_down(silu(W_gate x) * (W_up x))`` with the products' operands
    in the weights' type and float32 accumulation; float32 out."""
    xc = x.astype(w_gate.dtype)
    g = jnp.dot(xc, w_gate, preferred_element_type=jnp.float32)
    u = jnp.dot(xc, w_up, preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(w_down.dtype)
    return jnp.dot(a, w_down, preferred_element_type=jnp.float32)


def moe_forward_held(p: Dict[str, Array], x: Array, *, first_expert: int,
                     k: int, scaling: float = 1.0,
                     valid: Optional[Array] = None, shared: bool = True,
                     router: str = "noaux_tc", router_eps: float = 1e-20):
    """The part of a routed-expert layer that THIS chip gives.

    ``x`` [N, d].  Routes every row over all experts (``p["router_w"]``
    is [d, n_experts]) by ``router``: ``"noaux_tc"`` (sigmoid scores, the
    correction bias ``p["router_b"]``, ``scaling``, ``router_eps`` in
    the weights' normalisation) or ``"softmax_topk"``
    (neither); everything after the router is one code path.  Keeps the picks that fall on the experts held
    here (``p["e_gate"]`` is [held, d, f]; they are experts
    ``first_expert .. first_expert + held - 1``), sorts those picks by
    expert and runs ONE grouped feed-forward over them
    (``held_experts_ffn``: work follows the rows really routed, an
    expert nobody picked is not read).  A pick on an expert that lives
    elsewhere contributes nothing.  No capacity: every held pick is
    computed whatever the skew, the shapes are static (N*k rows).  The
    shared expert, when the tree has one and ``shared`` is set, is added
    once.  ``valid`` [N] bool marks the rows that are real tokens: the
    others (padding, idle slots) are routed nowhere and not counted.

    Returns ``(y [N, d] float32, picks [N, k] int32 sorted by id,
    stats int32 [4])`` with stats in the order of ``EXPERT_STATS``:
    picks made by valid rows, those that fell on held experts, the
    fullest held expert's picks, held experts with at least one pick.

    What follows the router is a function of its own under ``jit``
    (``_held_picks``): a program's expert layers, all of one shape, are
    traced once and lowered once, and called a layer (XLA inlines the
    calls).
    """
    n = x.shape[0]
    if router == "softmax_topk":
        idx, w = route_softmax_topk(x, p["router_w"], k)
    else:
        idx, w = route_noaux_tc(x, p["router_w"], p["router_b"], k, scaling,
                                router_eps)
    if valid is None:
        valid = jnp.ones((n,), bool)
    mine = {name: p[name] for name in _HELD_PARAMS
            if name in p and (shared or not name.startswith("s_"))}
    y, stats = _held_picks(mine, x, idx, w, valid, first_expert=first_expert)
    return y, jnp.sort(idx, axis=-1), stats


#: what of a layer's tree ``moe_forward_held`` reads after the router
_HELD_PARAMS = ("e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")


def held_experts_ffn(xs: Array, w_gate: Array, w_up: Array, w_down: Array,
                     group_sizes: Array) -> Array:
    """The gated feed-forward of rows ``xs`` [M, d] sorted by expert
    (``group_sizes`` [held] rows each): operands in the weights' type,
    float32 accumulation, ``silu(gate) * up`` cast to the weights' type
    before the down projection; [M, d] float32, the rows past the last
    group unspecified.  One Mosaic call (``ops/grouped_ffn.py``) where
    the shapes and the type allow it, three ``jax.lax.ragged_dot``\\ s
    where they do not."""
    from ..ops import grouped_ffn, pallas_support

    why = grouped_ffn.kept_path(xs, w_gate)
    if why is None:
        return grouped_ffn.grouped_ffn(xs, w_gate, w_up, w_down, group_sizes)
    pallas_support.fell_back(grouped_ffn.call_name(xs, w_gate), why)
    rd = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                           preferred_element_type=jnp.float32)
    a = (jax.nn.silu(rd(xs, w_gate)) * rd(xs, w_up)).astype(w_down.dtype)
    return rd(a, w_down)


@functools.partial(jax.jit, static_argnames=("first_expert",))
def _held_picks(p, x, idx, w, valid, *, first_expert):
    """``moe_forward_held`` after its router: the picks ``idx`` [N, k]
    with weights ``w`` -> ``(y, stats)``."""
    n, d = x.shape
    k = idx.shape[1]
    held = p["e_gate"].shape[0]
    local = idx - first_expert
    on_held = (local >= 0) & (local < held) & valid[:, None]
    # picks elsewhere go to a last, empty-weighted group `held`
    flat_e = jnp.where(on_held, local, held).reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    tok = order // k
    load = jnp.zeros((held + 1,), jnp.int32).at[flat_e].add(1)[:held]
    n_held = jnp.sum(load)
    in_group = jnp.arange(n * k) < n_held
    xs = x.astype(p["e_gate"].dtype)[tok]
    o = held_experts_ffn(xs, p["e_gate"], p["e_up"], p["e_down"], load)
    # rows past the last group hold nothing of any expert's
    o = jnp.where(in_group[:, None], o * w.reshape(-1)[order][:, None], 0.0)
    # back to the picks' own order by a gather (a scatter-add of N*k rows
    # is the slow way on this chip), then each token's k picks summed
    back = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    y = jnp.sum(o[back].reshape(n, k, d), axis=1)
    if "s_gate" in p:
        y = y + gated_silu(x, p["s_gate"], p["s_up"], p["s_down"])
    stats = jnp.stack([jnp.sum(valid) * k, n_held, jnp.max(load),
                       jnp.sum(load > 0)]).astype(jnp.int32)
    return y, stats


# ---------------------------------------------------------------------------
# layer wrapper (single-device / GSPMD path)
# ---------------------------------------------------------------------------

from ..nn.conf.inputs import InputType          # noqa: E402
from ..nn.layers.base import (                  # noqa: E402
    AUX_LOSS_KEY, ForwardOut, Layer, register_layer,
)


@register_layer
@dataclasses.dataclass
class MoE(Layer):
    """Mixture-of-Experts FFN layer (exact dense combine; use
    ``moe_forward_ep`` / ShardedTransformerLM for the sharded path).
    Accepts [mb, d] or [mb, t, d] (applied per token).

    The Switch load-balance auxiliary loss rides the ``AUX_LOSS_KEY``
    state slot, which the containers add to the training objective —
    without it the router can collapse onto one expert."""

    n_in: int = 0
    d_ff: int = 0
    n_experts: int = 4
    top_k: int = 2
    aux_weight: float = 0.01

    def infer_nin(self, in_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = in_type.size
        if self.d_ff == 0:
            self.d_ff = 4 * self.n_in

    def output_type(self, in_type: InputType) -> InputType:
        return in_type

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return init_moe_params(rng, self.n_in, self.d_ff, self.n_experts, dtype)

    def init_state(self, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        return {AUX_LOSS_KEY: jnp.zeros((), jnp.float32)}

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        y, aux = moe_forward_dense(params, flat, self.top_k)
        new_state = dict(state)
        new_state[AUX_LOSS_KEY] = (self.aux_weight * aux).astype(jnp.float32)
        return ForwardOut(self._act(y.reshape(shape)), new_state, mask)
