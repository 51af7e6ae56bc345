"""Pipeline parallelism — microbatch schedules over the ``pipe`` mesh axis.

No reference analog (SURVEY.md §2.3: PP is ABSENT in DL4J; a first-class
TPU deliverable).  Design: a stack of homogeneous blocks (transformer /
LSTM layers) has its parameters stacked on a leading stage axis that is
sharded over ``pipe`` — each device holds ``n_stages // pipe`` block
params.  Two microbatch schedules:

``schedule="gpipe"`` (default) — all forwards, then all backwards.  A
single ``lax.scan`` inside ``shard_map``: at step s, the device holding
stage p processes microbatch ``s - p`` and hands its activation to stage
p+1 via ``lax.ppermute``; autodiff transposes the scan into the mirrored
backward.  Bubble (S-1)/(M+S-1); peak activation memory grows with M —
the scan checkpoints every step's block residuals, (M+S-1) sets per
device.

``schedule="1f1b"`` (opt-in) — interleaved one-forward-one-backward.
The forward value pass is the SAME program as gpipe (losses are
bit-identical); the backward is a hand-scheduled combined pass: warm-up
forwards, steady-state alternating one recomputed forward with one
backward, cool-down backwards.  A stage stashes only microbatch
*stage inputs*, at most ``min(M, 2S-1)+1`` live at once, and block
internals exist only transiently inside the one microbatch being
differentiated — so peak activation memory is bounded by the pipeline
DEPTH, not the microbatch count, and M can grow to amortize the bubble
without growing memory.  The price is recompute: 3 forward passes per
microbatch (value, wavefront, vjp linearization) vs gpipe's 1.  Pick
1f1b when activations at the gpipe M you need don't fit; pick gpipe when
they do (docs/PARALLELISM.md has the decision table and the derivations;
``pipeline_schedule_stats`` is the analytic model).

Both schedules compose with the other mesh axes: batch stays sharded on
``data``/``seq``, and block_fn may use collectives (ring attention on
``seq``, TP psums on ``model``).  The 1f1b backward takes ``jax.vjp`` OF
the shard_map'd stage step — never inside it — so the shard_map
transpose machinery inserts the data/seq/model grad collectives.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import vary_over

logger = logging.getLogger("deeplearning4j_tpu")

Array = jax.Array

SCHEDULES = ("gpipe", "1f1b")


def stack_stage_params(param_list):
    """Stack per-block param pytrees [p0, p1, ...] into one pytree with a
    leading stage axis (all blocks must be homogeneous)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_list)


def stage_sharding(mesh: Mesh, stacked_params, axis: str = "pipe"):
    """NamedShardings putting the leading stage axis on ``axis``."""
    def spec(a):
        return NamedSharding(mesh, P(axis, *([None] * (a.ndim - 1))))
    return jax.tree_util.tree_map(spec, stacked_params)


def pipeline_schedule_stats(schedule: str, n_microbatches: int,
                            n_stages: int, *, layers_per_stage: int = 1,
                            residual_factor: float = 1.0,
                            stage_input_bytes: int = 0) -> Dict[str, Any]:
    """Analytic bubble / peak-activation accounting for one schedule.

    Conventions (all derivations in docs/PARALLELISM.md):
      - ``bubble_fraction``: idle (garbage-compute) slots over total slots
        of the schedule grid the implementation actually executes.  gpipe
        runs two mirrored (M+S-1)-step scans → (S-1)/(M+S-1).  1f1b runs
        a value pass (M+S-1 slots) plus a combined pass of M+2(S-1) steps
        with a forward and a backward slot each → (5S-5)/(3M+5S-5).  At
        EQUAL M the 1f1b grid idles more (longer drain + recompute); its
        lever is ``peak_activation_units``, which is depth-bounded, so M
        can be raised at fixed memory — compare against
        ``gpipe_microbatches_at_same_memory`` for the like-for-like
        bubble.
      - ``peak_live_stage_inputs``: stage-input-sized activation buffers
        live per device at the worst moment.  gpipe's backward needs every
        scan step's saved state: M+S-1.  1f1b stashes at most
        min(M, 2S-1) stage inputs (+1 in transit).
      - ``peak_activation_units``: peak activation memory in stage-input
        units, including per-layer block residuals
        (``layers_per_stage * residual_factor`` per checkpointed
        microbatch).  gpipe checkpoints block internals for every step;
        1f1b only for the single microbatch inside the current vjp.
        Multiply by ``stage_input_bytes`` for bytes
        (``peak_activation_bytes``, 0 when no byte size is given).

    ``residual_factor``: saved residuals per layer per microbatch,
    measured in stage-input units (≈1-2 for a dense block; ≈10 + 2·d_ff/d
    for a transformer block — q/k/v/att/gelu/FFN intermediates).
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, got {schedule!r}")
    m, s = n_microbatches, n_stages
    if m < 1 or s < 1:
        raise ValueError(f"need n_microbatches>=1, n_stages>=1; got {m}, {s}")
    lr = layers_per_stage * residual_factor
    if schedule == "gpipe":
        out = {
            "schedule": "gpipe",
            "n_steps": 2 * (m + s - 1),
            "bubble_fraction": (s - 1) / (m + s - 1),
            "peak_live_stage_inputs": m + s - 1,
            "peak_activation_units": (m + s - 1) * max(lr, 1.0),
            "forward_passes_per_microbatch": 1,
        }
    else:
        live = min(m, 2 * s - 1) + 1
        out = {
            "schedule": "1f1b",
            "n_steps": (m + s - 1) + (m + 2 * (s - 1)),
            "bubble_fraction": (5 * s - 5) / (3 * m + 5 * s - 5),
            "peak_live_stage_inputs": live,
            "peak_activation_units": live + max(lr, 1.0),
            "forward_passes_per_microbatch": 3,
        }
        # the largest M a gpipe schedule could run inside THIS memory
        # footprint — the honest basis for a bubble comparison
        g_equiv = int(out["peak_activation_units"] // max(lr, 1.0)) - s + 1
        out["gpipe_microbatches_at_same_memory"] = max(g_equiv, 1)
    if stage_input_bytes:
        out["peak_activation_bytes"] = int(
            out["peak_activation_units"] * stage_input_bytes)
    return out


def _resolve_specs(mesh, stacked_params, x, axis, data_axis, x_spec,
                   param_specs, n_microbatches):
    """Shared spec/microbatch resolution for both schedules."""
    if x_spec is not None:
        batch_spec = x_spec
    elif data_axis and mesh.shape.get(data_axis, 1) > 1:
        batch_spec = P(data_axis)
    else:
        batch_spec = P()

    # microbatches split the PER-DEVICE batch; shrink to the largest feasible
    # count (a perf knob, not a semantics change — parity tests cover this)
    dim0 = batch_spec[0] if len(batch_spec) else None
    dim0 = dim0 if isinstance(dim0, tuple) else (dim0,) if dim0 else ()
    dp = 1
    for a in dim0:
        dp *= mesh.shape.get(a, 1)
    b_local = x.shape[0] // dp
    if x.shape[0] % dp:
        raise ValueError(f"batch {x.shape[0]} not divisible by {dim0} ({dp})")
    requested = n_microbatches
    while b_local % n_microbatches:
        n_microbatches -= 1
    if n_microbatches != requested:
        # GPipe bubble fraction is (stages-1)/(m+stages-1): shrinking m
        # degrades pipelining — at m=1 every stage but one idles.  Never
        # do this silently (a prime b_local collapses all the way to 1).
        # graftcheck: disable=GC102 (shape-static degradation warning: firing ONCE at trace time is exactly the intended behavior)
        logger.warning(
            "n_microbatches=%d does not divide local batch %d — degraded to "
            "%d%s; pad the batch or pick a divisor to keep the pipeline full",
            requested, b_local, n_microbatches,
            " (NO pipelining: full GPipe bubble)" if n_microbatches == 1 else "")
    param_spec = param_specs if param_specs is not None else \
        jax.tree_util.tree_map(
            lambda a: P(axis, *([None] * (a.ndim - 1))), stacked_params)
    return batch_spec, param_spec, n_microbatches


def _spec_axes(batch_spec):
    axes = set()
    for entry in batch_spec:
        if isinstance(entry, (tuple, list)):
            axes.update(entry)
        elif entry is not None:
            axes.add(entry)
    return axes


def _clear_extra_vma(out, batch_spec, axis):
    """Activations may be typed varying over axes block_fn reduced over
    (e.g. TP psums on "model" leave replicated-but-varying values);
    pmean over axes absent from the output spec clears the variance."""
    extra = tuple(n for n in jax.typeof(out).vma
                  if n != axis and n not in _spec_axes(batch_spec))
    if extra:
        out = jax.lax.pmean(out, extra)
    return out


def _gpipe_fn(block_fn, mesh, axis, n_pipe, m, batch_spec, param_spec):
    """The shard_map'd all-forward pipeline (the gpipe schedule's forward
    AND the 1f1b schedule's value pass — bit-identical by construction)."""
    def run(params_local, xs):  # per-device: params [n_stages/n_pipe, ...]
        my = jax.lax.axis_index(axis)
        mb = xs.shape[0] // m
        micro = xs.reshape((m, mb) + xs.shape[1:])

        def apply_local(h):
            def f(h, p):
                return block_fn(p, h), None
            h, _ = jax.lax.scan(f, h, params_local)
            return h

        perm_fwd = [(i, i + 1) for i in range(n_pipe - 1)]
        n_steps = m + n_pipe - 1
        # zero-init buffers must carry the same varying-axes type as the
        # loop body's outputs (shard_map vma typing): they vary over pipe
        # AND over any axis the batch is sharded on
        out0 = vary_over(jnp.zeros_like(micro), mesh.axis_names)
        buf0 = vary_over(jnp.zeros((mb,) + xs.shape[1:], xs.dtype),
                         mesh.axis_names)

        def step(carry, s):
            buf, out = carry
            # stage 0 injects microbatch s (clamped; inactive steps compute
            # on stale data and their results are never written back)
            inj = micro[jnp.clip(s, 0, m - 1)]
            h_in = jnp.where(my == 0, inj, buf)
            h_out = apply_local(h_in)
            # last stage banks microbatch s - (n_pipe - 1) when in range
            widx = s - (n_pipe - 1)
            write = jnp.logical_and(my == n_pipe - 1,
                                    jnp.logical_and(widx >= 0, widx < m))
            out = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(o, h_out, jnp.clip(widx, 0, m - 1), 0),
                lambda o: o, out)
            # hand activation to the next stage
            buf = jax.lax.ppermute(h_out, axis, perm_fwd)
            return (buf, out), None

        (_, out), _ = jax.lax.scan(step, (buf0, out0), jnp.arange(n_steps))
        # result lives on the last stage; broadcast over the pipe axis
        out = jax.lax.psum(
            jnp.where(my == n_pipe - 1, out, jnp.zeros_like(out)), axis)
        out = _clear_extra_vma(out, batch_spec, axis)
        return out.reshape(xs.shape)

    return shard_map(run, mesh=mesh, in_specs=(param_spec, batch_spec),
                     out_specs=batch_spec)


def _stage_step_fn(block_fn, mesh, axis, batch_spec, param_spec):
    """One pipeline tick as a shard_map'd function at GLOBAL level: every
    pipe device applies its local layer stack to its slot of the
    [n_pipe, microbatch, ...] activation stack.  The 1f1b backward takes
    ``jax.vjp`` of THIS function, so grad collectives (data/seq psums for
    params, TP transposes inside block_fn) are inserted by the shard_map
    transpose."""
    hspec = P(axis, *tuple(batch_spec))

    def tick(params_local, h_stk):   # h_stk [1, mb_local, ...] per device
        # the scan carry must enter with the varying-axes type block_fn
        # returns (same reason as _gpipe_fn's zero-init buffers)
        h = vary_over(h_stk[0], mesh.axis_names)

        def f(h, p):
            return block_fn(p, h), None
        h, _ = jax.lax.scan(f, h, params_local)
        h = _clear_extra_vma(h, batch_spec, axis)
        return h[None]

    return shard_map(tick, mesh=mesh, in_specs=(param_spec, hspec),
                     out_specs=hspec)


def _pipeline_1f1b(block_fn, stacked_params, x, mesh, axis, n_pipe, m,
                   batch_spec, param_spec):
    """Interleaved 1F1B: gpipe-identical value pass + a hand-scheduled
    combined backward (custom_vjp).

    Backward schedule, per pipe stage p of S at combined-pass step s
    (each step has one forward and one backward slot):
      forward slot:  recompute microbatch  f = s - p            (warm-up)
      backward slot: differentiate         b = s - 2(S-1) + p   (cool-down)
    Steady state alternates the two; stage inputs are stashed in a
    ``min(M, 2S-1)``-deep ring buffer between their forward and backward.
    """
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    l_local = n_stages // n_pipe
    value_fn = _gpipe_fn(block_fn, mesh, axis, n_pipe, m, batch_spec,
                         param_spec)
    tick_fn = _stage_step_fn(block_fn, mesh, axis, batch_spec, param_spec)
    S = n_pipe
    K = min(m, 2 * S - 1)

    def bwd_pass(params, xx, gy):
        mbs = xx.shape[0] // m
        micro = xx.reshape((m, mbs) + xx.shape[1:])
        gmicro = gy.reshape((m, mbs) + xx.shape[1:])
        stages = jnp.arange(S)
        n_steps = m + 2 * (S - 1)
        stk_shape = (S, mbs) + xx.shape[1:]

        hs = NamedSharding(mesh, P(axis, *tuple(batch_spec)))
        ss = NamedSharding(mesh, P(axis, None, *tuple(batch_spec)))
        fstk0 = jax.lax.with_sharding_constraint(
            jnp.zeros(stk_shape, xx.dtype), hs)
        gstk0 = jax.lax.with_sharding_constraint(
            jnp.zeros(stk_shape, gy.dtype), hs)
        # the 1f1b memory contract: the ONLY cross-step activation state is
        # this K-deep per-stage stash of stage inputs (+ the two in-transit
        # stacks) — block internals never outlive one vjp
        sstk0 = jax.lax.with_sharding_constraint(
            jnp.zeros((S, K, mbs) + xx.shape[1:], xx.dtype), ss)
        dx0 = jnp.zeros((m, mbs) + xx.shape[1:], xx.dtype)
        dp0 = jax.tree_util.tree_map(jnp.zeros_like, params)

        def put(col, i, v):
            return jax.lax.dynamic_update_index_in_dim(col, v, i, 0)

        def take(col, i):
            return jax.lax.dynamic_index_in_dim(col, i, 0, keepdims=False)

        def step(carry, s):
            sstk, fstk, gstk, dx, dp = carry
            # ---- forward slot: recompute the wavefront ----
            f_idx = s - stages                                      # [S]
            f_ok = jnp.logical_and(f_idx >= 0, f_idx < m)
            h_in = put(fstk, 0, micro[jnp.clip(s, 0, m - 1)])
            slot_w = jnp.where(f_ok, f_idx % K, 0)
            stored = jax.vmap(put)(sstk, slot_w, h_in)
            keep = f_ok.reshape((S,) + (1,) * (sstk.ndim - 1))
            sstk = jnp.where(keep, stored, sstk)
            fstk = jnp.roll(tick_fn(params, h_in), 1, axis=0)
            # ---- backward slot: vjp of the stage tick ----
            b_idx = s - 2 * (S - 1) + stages                        # [S]
            b_ok = jnp.logical_and(b_idx >= 0, b_idx < m)
            g_in = put(gstk, S - 1, gmicro[jnp.clip(s - (S - 1), 0, m - 1)])
            h_sav = jax.vmap(take)(sstk, jnp.where(b_ok, b_idx % K, 0))
            _, vjp_fn = jax.vjp(tick_fn, params, h_sav)
            dp_s, dh = vjp_fn(g_in)
            layer_ok = jnp.repeat(b_ok, l_local)                    # [n_stages]

            def acc(a, g):
                mask = layer_ok.reshape((n_stages,) + (1,) * (g.ndim - 1))
                return a + jnp.where(mask, g, jnp.zeros_like(g))

            dp = jax.tree_util.tree_map(acc, dp, dp_s)
            dx = jnp.where(
                b_ok[0],
                put(dx, jnp.clip(b_idx[0], 0, m - 1), dh[0]), dx)
            gstk = jnp.roll(dh, -1, axis=0)
            return (sstk, fstk, gstk, dx, dp), None

        (_, _, _, dx, dp), _ = jax.lax.scan(
            step, (sstk0, fstk0, gstk0, dx0, dp0), jnp.arange(n_steps))
        return dp, dx.reshape(xx.shape)

    @jax.custom_vjp
    def pp(params, xx):
        return value_fn(params, xx)

    def pp_fwd(params, xx):
        return value_fn(params, xx), (params, xx)

    def pp_bwd(res, gy):
        params, xx = res
        return bwd_pass(params, xx, gy)

    pp.defvjp(pp_fwd, pp_bwd)
    return pp(stacked_params, x)


def pipeline_apply(block_fn: Callable[[Any, Array], Array],
                   stacked_params, x: Array, mesh: Mesh, *,
                   axis: str = "pipe", n_microbatches: int = 4,
                   data_axis: str | None = "data",
                   schedule: str = "gpipe",
                   param_specs=None, x_spec=None) -> Array:
    """Run ``x`` through the pipelined block stack; returns same-shape y.

    ``block_fn(params_i, h) -> h`` is one block (activation shapes must be
    preserved — the homogeneous-pipeline contract).  ``stacked_params`` has
    leading axis n_stages (divisible by the pipe axis size), sharded via
    ``stage_sharding``.  ``x`` is [B, ...]; B must divide by
    n_microbatches.  Composes with other mesh axes: batch stays sharded on
    ``data_axis``, and block_fn may itself use collectives (e.g. ring
    attention on ``seq``, TP psums on ``model``).

    ``schedule``: ``"gpipe"`` or ``"1f1b"`` (module docstring has the
    trade-off; forward values and first-step losses are bit-identical
    between the two — only the backward's order and memory differ).
    ``param_specs``: optional PartitionSpec pytree for the stacked params
    (leading dim on ``axis``) to tensor-parallel individual weights on top
    of the stage sharding.  ``x_spec``: optional PartitionSpec for the
    activations (e.g. ``P('data', 'seq', None)`` for sequence-sharded LM
    inputs); microbatching always splits dim 0.
    """
    if schedule not in SCHEDULES:
        raise ValueError(f"schedule must be one of {SCHEDULES}, got {schedule!r}")
    n_pipe = mesh.shape[axis]
    n_stages = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n_stages % n_pipe:
        raise ValueError(f"{n_stages} stages not divisible by pipe={n_pipe}")

    batch_spec, param_spec, m = _resolve_specs(
        mesh, stacked_params, x, axis, data_axis, x_spec, param_specs,
        n_microbatches)

    if schedule == "1f1b":
        return _pipeline_1f1b(block_fn, stacked_params, x, mesh, axis,
                              n_pipe, m, batch_spec, param_spec)
    fn = _gpipe_fn(block_fn, mesh, axis, n_pipe, m, batch_spec, param_spec)
    return fn(stacked_params, x)
