"""Runner ``serve_linear_gqa``: an LM whose layers are of two kinds
(grouped-query layers over paged K and V, linear-attention layers over a
per-slot recurrent state) with routed experts, the chip's share of a
stated deployment, served by ``DecodeEngine`` (``load()``,
``generate_async``) on one chip.

Set-up, the load loop, the window, the release and the blocked
comparison are ``serve_latent_moe``'s and ``serve_lm``'s, taken as
``serve_sparse_gqa`` takes them: the same closed loop, the same counting
of what came back, the same four numbers (``router_flip_share``, then
``served_logit_mse``, ``served_logit_gap`` and ``sampled_topk_gap`` over
the flip-free positions).  What differs:

* the engine takes the chunks of its prefills in the order the
  configuration's ``program`` states (``prefill_order``);
* the seeded weights are made layer by layer from each layer's own key
  by the configuration's reference, each layer of ITS kind
  (``reference.layer_kind``), and the reference runs in blocks over
  layers of both kinds;
* the window is ``serve_sparse_gqa``'s: it keeps the echoed logits of
  the requests the comparison will draw and of no others (``_sample``:
  of each kind the finished answers with the most served tokens; an
  answer's logits are up to 300 MB at this vocabulary); it reports the
  program's ``STATE_STATS`` beside the expert counts;
* every request asks for its slot's recurrent state at its end
  (``GenerationResult.slot_state``), and ``compare`` reads two numbers
  more from the drawn answers' states against the reference's state at
  the same row: ``state_gap`` and ``state_rounding_lost``
  (``state_numbers``).  The second is what holds the float32 the
  configuration states for the state: no number read from logits can,
  because the stated bfloat16 operands move the logits further than a
  bfloat16 state does and rounding does not cancel between two
  implementations (the configuration's ``limits_from``).
"""

from __future__ import annotations

# the program's new symbols first: a checkout without them fails here, at
# once, before anything is built
from deeplearning4j_tpu.models.linear_gqa import STATE_STATS  # noqa: E402  isort:skip
from deeplearning4j_tpu.ops.kv_cache import PoolsAndState  # noqa: E402,F401  isort:skip

import functools

import numpy as np

from benchmarks.runners import serve_latent_moe, serve_sparse_gqa
from benchmarks.runners.serve_latent_moe import (  # noqa: F401  (the harness calls them)
    release, sizes)
from benchmarks.runners.serve_sparse_gqa import _sample


def _makers(cell, dtype):
    """Jitted makers of one layer's weights, by the layer's kind, and of
    the embedding, final gain and head, from a key."""
    import jax

    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    make = {kind: jax.jit(functools.partial(ref.init_layer, sizes=sz,
                                            kind=kind, dtype=dtype))
            for kind in ("gqa", "linear")}
    return make, jax.jit(functools.partial(ref.init_ends, sizes=sz,
                                           dtype=dtype))


def seeded_tree(cell, dtype=None) -> dict:
    """The program's parameter tree for ``cell.seed``: each layer from
    its own key by the reference's initialiser, one jitted call a
    layer (two programs: a grouped-query layer's and a linear one's)."""
    import jax.numpy as jnp

    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    key = ref.seed_key(cell.seed)
    make, ends = _makers(cell, dtype or jnp.bfloat16)
    ends = ends(key)
    blocks = [make[ref.layer_kind(sz, i)](ref.layer_key(key, i))
              for i in range(int(sz["num_hidden_layers"]))]
    return {"embed": ends["embed"], "blocks": blocks,
            "lnf_g": ends["lnf_g"], "head": ends["head"]}


def setup(cell, split: dict) -> dict:
    """``serve_latent_moe.setup`` over this runner's weights, the engine
    taking its prefill chunks in the order the configuration's
    ``program`` states (``prefill_order``), warm-up requests included."""
    from deeplearning4j_tpu import serving

    theirs, engine = serve_latent_moe.seeded_tree, serving.DecodeEngine
    serve_latent_moe.seeded_tree = seeded_tree
    serving.DecodeEngine = functools.partial(
        engine, prefill_order=cell.config["program"]["prefill_order"])
    try:
        return serve_latent_moe.setup(cell, split)
    finally:
        serve_latent_moe.seeded_tree, serving.DecodeEngine = theirs, engine


def window(cell, state, tracer) -> dict:
    """``serve_sparse_gqa.window`` (the chosen experts of every answer
    kept beside its tokens, the echoed logits of the answers the
    comparison may draw only) with the slot's recurrent state asked of
    every request in place of the selected rows, which this program has
    none of, and with this program's counters.  An answer's state stays
    on the device until the comparison reads it (12.6 MB an answer, of
    the drawn answers only)."""
    eng = state["eng"]
    n_drawn = int(cell.mix["compare_requests"])
    states, longest = {}, {True: [], False: []}
    submit = eng.generate_async
    calls = iter(range(1 << 62))

    def asking(prompt, **kw):
        n = next(calls)
        fut = submit(prompt, echo_state=True, **kw)

        def keep(f, n=n, greedy=bool(kw.get("echo_logits"))):
            if f.cancelled() or f.exception() is not None \
                    or not f.result().tokens:
                return
            # ``_sample``'s order over the answers back so far: one it
            # leaves out now it leaves out at the close too
            states[n] = f.result().slot_state
            kind = longest[greedy]
            kind.append((-len(f.result().tokens), n))
            kind.sort()
            for _, gone in kind[n_drawn:]:
                states.pop(gone, None)
            del kind[n_drawn:]
        fut.add_done_callback(keep)
        return fut

    eng.generate_async = asking
    try:
        out = serve_sparse_gqa.window(cell, state, tracer)
    finally:
        eng.__dict__.pop("generate_async", None)
    state["finished"] = [(*f[:4], states.get(f[0].index))
                         for f in state["finished"]]
    c = eng.metrics.counter_value
    out["summary"]["counters_of_the_process"] = {
        k: c(k) for k in serve_latent_moe.EXPERT_STATS + STATE_STATS
        + ("recurrent_state_resets",)}
    out["summary"]["recurrent_state_bytes"] = int(
        eng.metrics.recurrent_state_bytes.value())
    return out


# -- the comparison ---------------------------------------------------------------------

def reference_pass(cell, seqs, positions, stops, precisions):
    """The reference over ``seqs`` (one int32 row each, padded to one
    length) **in blocks**: per layer, the layer's weights are made from
    its key as its kind asks, applied to every sequence, freed.  Returns,
    per precision, ``(logits at positions [R, P] as [R, P, V], the chosen
    experts there [R, P, layers, k])`` and, beside them, the recurrent
    state of every linear layer as row ``stops[r]`` of sequence ``r``
    left it ``[R, linear layers, heads, 128, 128]``."""
    import jax
    import jax.numpy as jnp

    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    key = ref.seed_key(cell.seed)
    make, ends = _makers(cell, jnp.bfloat16)
    ends = ends(key)
    seqs = jnp.asarray(seqs, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    stops = jnp.asarray(stops, jnp.int32)

    @functools.partial(jax.jit, static_argnames=("precision",),
                       donate_argnums=(1,))
    def apply(p, h, precision):
        def one(row):
            x, at, stop = row
            y, pk, S = ref.layer(p, x, sz, precision, stop)
            return (y, pk[at]) + (() if S is None else (S,))
        return jax.lax.map(one, (h, positions, stops))

    @functools.partial(jax.jit, static_argnames=("precision",))
    def read(ends, h, precision):
        at = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return ref.logits(ends, at, sz, precision)

    out, states = {}, {}
    with ref.with_precision("float32"):
        hs = {p: jax.jit(ref.embed)(ends, seqs) for p in precisions}
        picks = {p: [] for p in precisions}
        held = {p: [] for p in precisions}
        for i in range(int(sz["num_hidden_layers"])):
            p_i = make[ref.layer_kind(sz, i)](ref.layer_key(key, i))
            for prec in precisions:
                hs[prec], pk, *S = apply(p_i, hs[prec], precision=prec)
                picks[prec].append(pk)
                held[prec] += [np.asarray(a) for a in S]
            del p_i
        for prec in precisions:
            lg = read(ends, hs.pop(prec), precision=prec)
            out[prec] = (np.asarray(lg),
                         np.asarray(jnp.stack(picks[prec], axis=2)))
            states[prec] = np.stack(held[prec], axis=1)
    return out, states


def _rounding(S) -> float:
    """How far ``S`` lies from its own rounding to bfloat16, as a share
    of its size: about 0.0016 of a float32 array, 0 of one kept in
    bfloat16."""
    import jax.numpy as jnp

    S = np.asarray(S, np.float32)
    off = S - S.astype(jnp.bfloat16).astype(np.float32)
    return float(np.sqrt(np.mean(off * off) / np.mean(S * S)))


def state_numbers(theirs, wanted) -> tuple:
    """The two numbers of the recurrent state, of one candidate (the
    program, or a control in its place) against the float32 reference:
    ``theirs[r]`` is None or the states ``[linear layers, heads, 128,
    128]`` request ``r``'s slot held when its last fed token was in,
    ``wanted[r]`` the reference's there.  ``state_gap``: the distance
    between the two as a share of the reference's size, a layer's
    median over the requests (an answer with a router flip some tokens
    before its end lies several times as far as the others, sound or
    not), the widest layer's.  ``state_rounding_lost``: the share of a
    float32 state's distance to its bfloat16 rounding that the
    candidate's state has NOT (0: it keeps what float32 keeps below
    bfloat16's seven bits; 1: every value is a bfloat16 value), the
    largest over requests and layers."""
    gaps, lost = [], []
    for mine, ref_s in zip(theirs, wanted):
        if mine is None:
            continue
        pairs = [(np.asarray(S, np.float32), W) for S, W in zip(mine, ref_s)]
        gaps.append([float(np.sqrt(np.mean((S - W) ** 2) / np.mean(W * W)))
                     for S, W in pairs])
        lost.append(max(1.0 - _rounding(S) / _rounding(W) for S, W in pairs))
    by_layer = np.median(gaps, axis=0).tolist() if gaps else []
    return ({"state_gap": max(by_layer) if gaps else None,
             "state_rounding_lost": max(lost) if lost else None},
            {"states_compared": len(gaps), "state_gap_by_layer": by_layer,
             "state_gap_widest": float(np.max(gaps)) if gaps else None})


def compare(cell, served, with_control: bool = False,
            control_precision: str = None) -> dict:
    """``serve_latent_moe.compare``, whole, over this runner's draw and
    this runner's reference, and the two numbers of the recurrent state
    (``state_numbers``) over the drawn answers that brought theirs (one
    cut at the close brings none)."""
    drawn = [f for kind in _sample(cell, served.get("finished", []))
             for f in kind]
    # in the order serve_latent_moe.compare will put them
    rows = [(r, t, lg, *rest) for kind in serve_latent_moe._sample(
        cell, [(f[0], f[1], f[2], (f[3], f[4])) for f in drawn])
        for r, t, lg, rest in kind]
    low = control_precision or cell.reference.CONTROL_PRECISION
    states = {}

    def passing(cell, seqs, positions, with_control=False, low=low):
        out, held = reference_pass(
            cell, seqs, positions,
            [len(r.prompt) + len(t) - 2 for r, t, *_ in rows],
            ["float32"] + ([low] if with_control else []))
        states.update(held)
        return out

    theirs = serve_latent_moe.reference_pass
    serve_latent_moe.reference_pass = passing
    try:
        out = serve_latent_moe.compare(
            cell, {"finished": [f[:4] for f in drawn]}, with_control, low)
    finally:
        serve_latent_moe.reference_pass = theirs
    if not states:
        return out
    served_states = [None if f[4] is None else [np.asarray(S) for S, *_ in f[4]]
                     for f in rows]
    nums, detail = state_numbers(served_states, states["float32"])
    out["numbers"].update(nums)
    out["detail"].update(detail)
    if with_control:
        c_nums, c_detail = state_numbers(states[low], states["float32"])
        out["control"].update(c_nums)
        out["detail"].update({"control_" + k: v for k, v in c_detail.items()})
    return out
