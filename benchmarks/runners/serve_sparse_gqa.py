"""Runner ``serve_sparse_gqa``: a grouped-query / routed-expert LM whose
attention reads a learned selection of its cache (the chip's share of a
stated deployment), served by ``DecodeEngine`` (``load()``,
``generate_async``) on one chip.

Set-up, the load loop, the window and the release are
``serve_latent_moe``'s and ``serve_lm``'s: the same closed loop, the same
seeded weights made layer by layer from each layer's own key by the
configuration's reference, the same counting of what came back.  What
differs:

* a request that asks for its logits also gets back, for every token,
  the cached positions each layer's attention selected at the position
  the token was taken from (``GenerationResult.attn_rows``); the window
  keeps them beside the tokens, the echoed logits and the chosen
  experts, for the requests the comparison will draw and no others (a
  finished answer's logits are some hundreds of MB at this vocabulary);
* ``compare`` runs the reference **in blocks** as ``serve_latent_moe``
  does and reads its four numbers by that runner's own arithmetic
  (``router_flip_share``, then ``served_logit_mse``, ``served_logit_gap``
  and ``sampled_topk_gap`` over the flip-free positions), plus one of
  the selection: ``index_miss_share``, the share of the (served
  position, layer, chosen row) triples the program chose that the
  reference did not.  A row near the 2048th score flips under bf16
  rounding and moves the output by about one part in 2048, so it is
  counted and limited, not averaged into the logits; the reference is
  never forced onto the program's choice.
"""

from __future__ import annotations

# the program's new symbols first: a checkout without them fails here, at
# once, before anything is built
from deeplearning4j_tpu.models.sparse_gqa import SPARSE_STATS  # noqa: E402  isort:skip

import functools

import numpy as np

from benchmarks.runners import serve_latent_moe, serve_lm
from benchmarks.runners.serve_latent_moe import (  # noqa: F401  (the harness calls them)
    release, setup, sizes)


def window(cell, state, tracer) -> dict:
    """``serve_lm.window`` with the engine's answers also kept whole (the
    loop submits its requests one after another from one thread, so the
    n-th call is the stream's n-th request); of the requests the
    comparison will not draw only the tokens are kept, from the moment
    they come back."""
    eng = state["eng"]
    results = {}
    submit = eng.generate_async
    calls = iter(range(1 << 62))

    def keeping(prompt, **kw):
        n = next(calls)
        fut = submit(prompt, **kw)

        def keep(f, n=n):
            if not f.cancelled() and f.exception() is None:
                results[n] = (f.result().expert_picks, f.result().attn_rows)
        fut.add_done_callback(keep)
        return fut

    def may_be_drawn(finished):
        """``_sample`` of the answers back so far: one it leaves out now
        it leaves out at the close too (a fixed order, a growing set),
        so its logits and rows can go at once."""
        keep = [f for kind in _sample(cell, finished) for f in kind]
        for n in {f[0].index for f in finished} - {f[0].index for f in keep}:
            results.pop(n, None)
        return keep

    eng.generate_async = keeping
    try:
        out = serve_lm.window(cell, state, tracer, may_be_drawn)
    finally:
        del eng.generate_async
    finished = [(req, toks, lg, *results.get(req.index, (None, None)))
                for req, toks, lg in state["finished"]]
    drawn = {id(f[1]) for kind in _sample(cell, finished) for f in kind}
    state["finished"] = [f if id(f[1]) in drawn else (f[0], f[1], None,
                                                      None, None)
                         for f in finished]
    c = eng.metrics.counter_value
    out["summary"]["counters_of_the_process"] = {
        k: c(k) for k in serve_latent_moe.EXPERT_STATS + SPARSE_STATS}
    return out


# -- the comparison ---------------------------------------------------------------------

def _sample(cell, finished):
    """The requests compared, (greedy, sampled): of each kind the
    ``compare_requests`` finished answers with the most served tokens
    (the earlier request where two are as long).  Not ``serve_lm``'s
    draw of the longest prompt + answer: a compared request costs the
    reference its whole prompt, and the logit numbers are read over the
    served positions WITHOUT a router flip in any of the seven layers,
    a few in a hundred here (PERF.md section 6, PR 32), so the answers
    with the most positions are the ones that carry them."""
    n = int(cell.mix["compare_requests"])
    return [sorted((f for f in finished if f[0].greedy == greedy and f[1]),
                   key=lambda f: (-len(f[1]), f[0].index))[:n]
            for greedy in (True, False)]


def reference_pass(cell, seqs, positions, with_control=False, low=None):
    """The reference over ``seqs`` (one int32 row each, padded to one
    length) **in blocks**: per layer, the layer's weights are made from
    its key, applied to every sequence, freed.  Returns, per precision,
    at ``positions`` [R, P]: the logits [R, P, V], the chosen experts
    [R, P, layers, k] and the attention's selection [R, P, layers, T]
    bool."""
    import jax
    import jax.numpy as jnp

    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    key = ref.seed_key(cell.seed)
    precisions = ["float32"] + ([low or ref.CONTROL_PRECISION]
                                if with_control else [])
    make, ends = serve_latent_moe._makers(cell, jnp.bfloat16)
    ends = ends(key)
    seqs = jnp.asarray(seqs, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)

    @functools.partial(jax.jit, static_argnames=("precision",),
                       donate_argnums=(1,))
    def apply(p, h, precision):
        def one(row):
            x, at = row
            y, pk, chosen = ref.layer(p, x, sz, precision)
            return y, pk[at], chosen[at]
        return jax.lax.map(one, (h, positions))

    @functools.partial(jax.jit, static_argnames=("precision",))
    def read(ends, h, precision):
        at = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return ref.logits(ends, at, sz, precision)

    out = {}
    with ref.with_precision("float32"):
        hs = {p: jax.jit(ref.embed)(ends, seqs) for p in precisions}
        picks = {p: [] for p in precisions}
        chosen = {p: [] for p in precisions}
        for i in range(int(sz["num_hidden_layers"])):
            p_i = make[False](ref.layer_key(key, i))
            for prec in precisions:
                hs[prec], pk, ch = apply(p_i, hs[prec], precision=prec)
                picks[prec].append(pk)
                chosen[prec].append(np.asarray(ch))
            del p_i
        for prec in precisions:
            lg = read(ends, hs.pop(prec), precision=prec)
            out[prec] = (np.asarray(lg),
                         np.asarray(jnp.stack(picks[prec], axis=2)),
                         np.stack(chosen[prec], axis=2))
    return out


def _miss_share(their_rows, ref_chosen) -> tuple:
    """(rows chosen that the reference did not choose, rows chosen), a
    layer each [layers], over one request: ``their_rows`` [n, layers, k]
    positions (-1 = none), ``ref_chosen`` [n, layers, T] bool."""
    rows = np.asarray(their_rows)
    valid = rows >= 0
    hit = np.take_along_axis(ref_chosen, np.where(valid, rows, 0), axis=-1)
    return (valid & ~hit).sum(axis=(0, 2)), valid.sum(axis=(0, 2))


def compare(cell, served, with_control: bool = False,
            control_precision: str = None) -> dict:
    greedy, sampled = _sample(cell, served.get("finished", []))
    kept = {}

    def passing(cell, seqs, positions, with_control=False, low=None):
        kept.update(reference_pass(cell, seqs, positions, with_control, low))
        return {prec: (lg, pk) for prec, (lg, pk, _) in kept.items()}

    # serve_latent_moe's comparison, whole, over this runner's reference
    theirs = serve_latent_moe.reference_pass
    serve_latent_moe.reference_pass = passing
    try:
        out = serve_latent_moe.compare(
            cell, {"finished": [f[:4] for f in greedy + sampled]},
            with_control, control_precision)
    finally:
        serve_latent_moe.reference_pass = theirs
    if not out["numbers"]:
        return out
    # the reference's rows are in ITS order of the greedy requests
    in_order, _ = serve_latent_moe._sample(cell, [f[:4] for f in greedy])
    by_tokens = {id(f[1]): f for f in greedy}
    greedy = [by_tokens[id(f[1])] for f in in_order]
    if any(rows is None or len(rows) != len(t) for _, t, _, _, rows in greedy):
        return {"numbers": {}, "error": "a greedy request came back without "
                                        "the rows its layers selected for "
                                        "each of its tokens"}
    ref_chosen = kept["float32"][2]
    missed = total = 0
    for i, (_, toks, _, _, rows) in enumerate(greedy):
        n = min(len(toks), ref_chosen.shape[1])
        m, t = _miss_share(rows[:n], ref_chosen[i, :n])
        missed, total = missed + m, total + t
    out["numbers"]["index_miss_share"] = \
        float(missed.sum() / total.sum()) if total.sum() else None
    out["detail"].update(
        rows_chosen_compared=int(total.sum()),
        rows_chosen_missed=int(missed.sum()),
        index_miss_share_by_layer=[round(float(m / max(t, 1)), 4)
                                   for m, t in zip(missed, total)])
    if with_control:
        low = control_precision or cell.reference.CONTROL_PRECISION
        low_chosen = kept[low][2]
        missed = int((low_chosen & ~ref_chosen).sum())
        total = int(low_chosen.sum())
        out["control"]["index_miss_share"] = missed / total if total else None
        out["detail"].update(control_rows_chosen_missed=missed)
    return out
