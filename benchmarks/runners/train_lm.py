"""Runner ``train_lm``: a GPT-2-class LM trained through
``ShardedTransformerLM.fit_batch``, data-parallel over the cell's chips.

Set-up builds ONE object (the LM with its compiled step and state),
gives it the benchmark's seeded weights, drives it through the mix's
first steps by the window's own call and feed, and hands that same
object to the window.  ``correct`` compares those first steps with the
configuration's plain reference: each step's loss, the first gradient
as the optimizer got it (from Adam's first moment after one step) and
the parameters' change after the first steps, leaf by leaf.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from benchmarks import traffic
from benchmarks.harness import seeded_params
from benchmarks.stats import median, rate

BETA1 = 0.9


def sizes(cell) -> dict:
    out = {k: cell.config[k] for k in cell.reference.SIZE_KEYS}
    out.update(seq=cell.mix["seq_len"], chips=cell.chips,
               batch_per_chip=cell.mix["batch_per_chip"],
               batch=cell.mix["batch_per_chip"] * cell.chips)
    return out


# -- leaf-wise norms --------------------------------------------------------------

def leaf_sqnorms(tree) -> dict:
    """{"embed": x, "blocks.Wq": [one per layer], ...}: squared norms,
    each layer of the stacked blocks a leaf of its own (traceable)."""
    import jax.numpy as jnp

    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            for kk, a in v.items():
                a = a.astype(jnp.float32)
                out[f"{k}.{kk}"] = jnp.sum(a * a, axis=tuple(range(1, a.ndim)))
        else:
            out[k] = jnp.sum(v.astype(jnp.float32) ** 2)
    return out


def flat_norms(sq: dict) -> dict:
    """{"blocks.Wq[3]": norm, "embed": norm, ...} as Python floats."""
    out = {}
    for k, v in sq.items():
        v = np.sqrt(np.asarray(v, np.float64))
        if v.ndim:
            out.update({f"{k}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[k] = float(v)
    return out


def change_norms(after, before) -> dict:
    """Leaf-wise norms of ``after - before`` (two parameter trees)."""
    import jax

    sq = jax.jit(lambda a, b: leaf_sqnorms(jax.tree_util.tree_map(
        lambda x, y: x - y, a, b)))(after, before)
    return flat_norms(jax.device_get(sq))


def worst_gap(ours: dict, theirs: dict):
    """Worst leaf's |ours - theirs| against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    floor = float(np.median(list(theirs.values())))
    gaps = {k: abs(ours[k] - theirs[k]) / max(theirs[k], floor)
            for k in theirs}
    at = max(gaps, key=gaps.get)
    return gaps[at], at


# -- the run -------------------------------------------------------------------------

def setup(cell, split: dict) -> dict:
    split["t_enter"] = time.time()
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh

    cfg, prog = cell.config, cell.config["program"]
    t0 = time.time()
    lm = ShardedTransformerLM(
        vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], n_heads=cfg["n_head"], d_ff=cfg["n_inner"],
        mesh=build_mesh({"data": cell.chips}, devices=cell.devices),
        max_len=cfg["n_positions"], n_microbatches=prog["n_microbatches"],
        compute_dtype=jnp.dtype(prog["compute_dtype"]),
        attention_impl=prog["attention_impl"],
        updater=Adam(lr=prog["adam_lr"],
                     moment_dtype=prog["adam_moment_dtype"]))
    split["program_object"] = round(time.time() - t0, 3)

    # the benchmark's own weights, on the device in one jitted call
    t0 = time.time()
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, lm.params)
    lm.params = jax.block_until_ready(seeded_params(cell, shardings))
    split["weights"] = round(time.time() - t0, 3)

    # the first steps: the window's own call and feed
    t0 = time.time()
    batches = traffic.train_batches(cell.mix, cell.seed, cell.chips,
                                    cfg["vocab_size"])
    first, losses, m1 = [], [], None
    for i in range(int(cell.mix["first_steps"])):
        toks, tgts = next(batches)
        first.append((toks, tgts))
        losses.append(float(lm.fit_batch(toks, tgts)))
        if i == 0:
            split["first_step_compile_or_load"] = round(time.time() - t0, 3)
            m1 = jax.device_get(lm.opt_state["m"])
    delta = change_norms(lm.params, seeded_params(cell, shardings))
    split["first_steps"] = round(time.time() - t0, 3)
    return {"lm": lm, "batches": batches,
            "served": {"first": first, "losses": losses, "m1": m1,
                       "delta_norms": delta}}


def window(cell, state, tracer) -> dict:
    import jax

    lm, batches = state["lm"], state["batches"]
    per_step = cell.mix["batch_per_chip"] * cell.chips * cell.mix["seq_len"]
    step_s, losses = [], []
    t0 = last = time.perf_counter()
    while True:
        with tracer.annotate("bench/train_step"):
            toks, tgts = next(batches)
            loss = lm.fit_batch(toks, tgts).device_value()
        with tracer.annotate("train/device_sync"):
            jax.block_until_ready(loss)
        now = time.perf_counter()
        step_s.append(now - last)
        losses.append(loss)
        last = now
        tracer.tick(now - t0, cell.seconds)
        if now - t0 >= cell.seconds:
            break
    window_s = last - t0
    losses = np.asarray(jax.device_get(losses), np.float64)
    failed = int(np.sum(~np.isfinite(losses)))
    steps = len(step_s)
    return {
        "attempted": steps, "failed": failed,
        "end_to_end": {"train_tokens_per_s": rate(steps * per_step, window_s)},
        "step_s": step_s, "tokens_per_step": per_step,
        "summary": {"steps": steps, "tokens": steps * per_step,
                    "window_s": window_s, "loss_first": float(losses[0]),
                    "loss_last": float(losses[-1]),
                    "step_ms_median": 1e3 * median(step_s)}}


def release(cell, state) -> dict:
    import jax

    served = state.pop("served")
    lm = state.pop("lm")
    lm.params = lm.opt_state = lm._jit_step = None
    del lm
    state.clear()
    gc.collect()
    jax.clear_caches()      # the step's executable holds its temporaries
    return served


# -- the comparison ---------------------------------------------------------------------

def reference_run(cell, first, precision: str) -> dict:
    """The reference (or, at a lower ``precision``, its control) through
    the same first steps, in blocks of rows so that it fits: losses, the
    first gradient, and the norms of the parameters' change."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    cfg, ref = cell.config, cell.reference
    mesh = Mesh(np.asarray(cell.devices), ("data",))
    whole, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    block_rows = int(cell.mix.get("reference_rows_per_chip", 4)) * cell.chips
    grad = jax.jit(jax.value_and_grad(functools.partial(
        ref.loss_sum, n_heads=cfg["n_head"], precision=precision)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    scale = jax.jit(lambda a, s: jax.tree_util.tree_map(lambda x: x * s, a),
                    donate_argnums=(0,))
    adam = jax.jit(functools.partial(ref.adam_step,
                                     lr=cfg["program"]["adam_lr"]),
                   donate_argnums=(0, 2, 3))
    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))

    with ref.with_precision(precision):
        params = seeded_params(cell, whole)
        m, v = zeros(params), zeros(params)
        losses, g1 = [], None
        for it, (toks, tgts) in enumerate(first):
            n_tok, total, acc = toks.size, 0.0, None
            for r in range(0, toks.shape[0], block_rows):
                tb = jax.device_put(toks[r:r + block_rows], rows)
                yb = jax.device_put(tgts[r:r + block_rows], rows)
                ls, g = grad(params, tb, yb)
                total += float(ls)
                acc = g if acc is None else add(acc, g)
            g = scale(acc, 1.0 / n_tok)
            losses.append(total / n_tok)
            params, m, v = adam(params, g, m, v, float(it))
            if it == 0:
                g1 = g
        delta = change_norms(params, seeded_params(cell, whole))
    return {"losses": losses, "g1": g1, "delta_norms": delta}


def numbers_against(theirs: dict, losses, g1, delta_norms) -> dict:
    """The numbers compared, for the program's (or the control's) first
    steps against the reference run ``theirs``."""
    import jax

    sq = jax.jit(lambda a, b: (leaf_sqnorms(a), leaf_sqnorms(
        jax.tree_util.tree_map(lambda x, y: x.astype("float32") - y, a, b))))
    ours_sq, err_sq = jax.device_get(sq(g1, theirs["g1"]))
    ref_norms = flat_norms(jax.device_get(
        jax.jit(leaf_sqnorms)(theirs["g1"])))
    ours, err = flat_norms(ours_sq), flat_norms(err_sq)
    floor = float(np.median(list(ref_norms.values())))
    errors = {k: err[k] / max(ref_norms[k], floor) for k in ref_norms}
    g_gap, g_at = worst_gap(ours, ref_norms)
    d_gap, d_at = worst_gap(delta_norms, theirs["delta_norms"])
    worst_err = max(errors, key=errors.get)
    return {
        "numbers": {
            "loss_gap": float(max(abs(a - b) for a, b in
                                  zip(losses, theirs["losses"]))),
            "grad_norm_gap": g_gap,
            "grad_error": float(np.median(list(errors.values()))),
            "delta_norm_gap": d_gap},
        "detail": {"losses": [float(x) for x in losses],
                   "losses_reference": theirs["losses"],
                   "grad_norm_gap_at": g_at, "delta_norm_gap_at": d_at,
                   "grad_error_worst": errors[worst_err],
                   "grad_error_worst_at": worst_err, "leaves": len(errors)}}


def compare(cell, served, with_control: bool = False,
            control_precision: str = None) -> dict:
    import jax
    import jax.numpy as jnp

    theirs = reference_run(cell, served["first"], "float32")
    # the gradient as the optimizer got it: m1 = (1 - beta1) * g
    g1 = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a).astype(jnp.float32) / (1.0 - BETA1),
        served["m1"])
    out = numbers_against(theirs, served["losses"], g1, served["delta_norms"])
    del g1
    if with_control:
        ctl = reference_run(cell, served["first"], control_precision
                            or cell.reference.CONTROL_PRECISION)
        out["control"] = numbers_against(
            theirs, ctl["losses"], ctl["g1"], ctl["delta_norms"])["numbers"]
    return out
