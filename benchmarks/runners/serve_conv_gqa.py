"""Runner ``serve_conv_gqa``: an LM of gated short-convolution layers (a
per-slot tail and nothing else) beside rotary grouped-query layers (paged
K and V) with a leading dense layer, routed experts and a tied head, one
pipeline stage of a stated deployment, served by ``DecodeEngine``
(``load()``, ``generate_async``) on one chip.

Everything is ``serve_linear_gqa``'s, taken as ``serve_ssm_gqa`` takes
it: the set-up with the engine's ``prefill_order`` from the
configuration's ``program``, the closed loop, the window that keeps the
echoed logits of the answers the comparison will draw and asks every
request for its slot's state, the four numbers of the logits over the
flip-free positions and ``state_gap`` over the tails a request's end
echoes.  What differs:

* a layer is of a mixer kind (``layer_types``: ``conv`` /
  ``full_attention``) AND dense or routed (``reference.is_dense``): the
  makers are keyed by both, the blocked reference pass keeps the chosen
  experts of the routed layers only (as ``serve_latent_moe`` does for
  its leading dense layer), and the tree has no ``head``: it is tied;
* a state layer's state is ONE array, its tail, which the configuration
  states in bfloat16: ``state_rounding_lost`` (what holds a float32
  state against its rounding) has nothing to hold here and is left out;
* the comparison draws among the answers that reached their own END
  (every token they asked for): an answer of this mix runs to 2,048
  tokens, longer than the window, so the answers with the most served
  tokens are those the close cut, and an answer cut at the close echoes
  no tail (the device has run on past its last committed token);
* the window reports ``admit_rounds_budget_bound`` beside the expert and
  state counts: the admission rounds that the token budget ended with
  slots free and requests waiting.
"""

from __future__ import annotations

# the program's new symbols first: a checkout without them fails here, at
# once, before anything is built
from deeplearning4j_tpu.models.conv_gqa import conv_step  # noqa: E402,F401  isort:skip

import contextlib
import functools

import numpy as np

from benchmarks.runners import (serve_latent_moe, serve_linear_gqa,
                                serve_sparse_gqa)
from benchmarks.runners.serve_linear_gqa import (  # noqa: F401  (the harness calls them)
    STATE_STATS, release, sizes, state_numbers)

KINDS = ("full_attention", "conv")
_longest_of_each_kind = serve_sparse_gqa._sample


def _sample(cell, finished):
    """``serve_sparse_gqa._sample`` (of each kind the answers with the
    most served tokens) among the answers that reached their own end."""
    return _longest_of_each_kind(
        cell, [f for f in finished if len(f[1]) == f[0].max_new_tokens])


def _makers(cell, dtype):
    """Makers of one layer's weights, by ``(mixer kind, dense)``, and of
    the embedding and final gain, from a key.  A layer is made by two
    jitted programs, its mixer's and its feed-forward's
    (``reference.init_layer`` is the two together), so the program that
    draws an expert layer's 604 M values is compiled once and not once a
    mixer kind."""
    import jax

    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    mixer = {kind: jax.jit(functools.partial(
                 ref.init_mixer, sizes=sz, kind=kind, dtype=dtype))
             for kind in KINDS}
    ff = {dense: jax.jit(functools.partial(
              ref.init_ff, sizes=sz, dense=dense, dtype=dtype))
          for dense in (True, False)}

    def both(m, f):
        return lambda key: {**m(key), **f(key)}

    make = {(kind, dense): both(mixer[kind], ff[dense])
            for kind in KINDS for dense in (True, False)}
    return make, jax.jit(functools.partial(ref.init_ends, sizes=sz,
                                           dtype=dtype))


def _layers(cell, make, key):
    """Layer ``i``'s weights, made when asked for, for each layer."""
    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    for i in range(int(sz["num_hidden_layers"])):
        yield make[ref.layer_kind(sz, i), ref.is_dense(sz, i)](
            ref.layer_key(key, i))


def seeded_tree(cell, dtype=None) -> dict:
    """The program's parameter tree for ``cell.seed``: each layer from
    its own key by the reference's initialiser (``_makers``: four
    programs, a mixer's of each kind, the dense feed-forward's and the
    expert layer's).  No ``head``: the embedding is the head."""
    import jax.numpy as jnp

    key = cell.reference.seed_key(cell.seed)
    make, ends = _makers(cell, dtype or jnp.bfloat16)
    ends = ends(key)
    return {"embed": ends["embed"], "blocks": list(_layers(cell, make, key)),
            "lnf_g": ends["lnf_g"]}


def reference_pass(cell, seqs, positions, stops, precisions):
    """``serve_linear_gqa.reference_pass`` over layers that may be dense:
    per precision ``(logits at positions [R, P, V], the chosen experts
    there [R, P, expert layers, k])`` and, beside them, the tail of every
    convolution layer as row ``stops[r]`` of sequence ``r`` left it ``[R,
    convolution layers, taps - 1, d]``."""
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
            y, pk, tail = ref.layer(p, x, sz, precision, stop)
            return y, (None if pk is None else pk[at]), tail
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
        for p_i in _layers(cell, make, key):
            for prec in precisions:
                hs[prec], pk, tail = apply(p_i, hs[prec], precision=prec)
                picks[prec] += [] if pk is None else [pk]
                held[prec] += [] if tail is None else [np.asarray(tail)]
            del p_i
        for prec in precisions:
            lg = read(ends, hs.pop(prec), precision=prec)
            out[prec] = (np.asarray(lg),
                         np.asarray(jnp.stack(picks[prec], axis=2)))
            states[prec] = np.stack(held[prec], axis=1)
    return out, states


@contextlib.contextmanager
def _as_this_runner():
    """``serve_linear_gqa``'s functions over this runner's weights and
    this runner's pass of the reference."""
    names = [(serve_linear_gqa, n) for n in (
        "_makers", "seeded_tree", "reference_pass", "_sample")] \
        + [(serve_sparse_gqa, "_sample")]
    theirs = [getattr(mod, n) for mod, n in names]
    for mod, n in names:
        setattr(mod, n, globals()[n])
    try:
        yield
    finally:
        for (mod, n), fn in zip(names, theirs):
            setattr(mod, n, fn)


def setup(cell, split: dict) -> dict:
    with _as_this_runner():
        return serve_linear_gqa.setup(cell, split)


def window(cell, state, tracer) -> dict:
    """``serve_linear_gqa.window`` (every request asked for its slot's
    state, the states of the answers the comparison may draw kept on the
    device) over this runner's draw: an answer that brought no state, one
    cut at the close, displaces none that did."""
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
                    or f.result().slot_state is None:
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
        with _as_this_runner():
            out = serve_sparse_gqa.window(cell, state, tracer)
    finally:
        eng.__dict__.pop("generate_async", None)
    state["finished"] = [(*f[:4], states.get(f[0].index))
                         for f in state["finished"]]
    c = eng.metrics.counter_value
    out["summary"]["counters_of_the_process"] = {
        k: c(k) for k in serve_latent_moe.EXPERT_STATS + STATE_STATS
        + ("recurrent_state_resets", "admit_rounds_budget_bound")}
    out["summary"]["recurrent_state_bytes"] = int(
        eng.metrics.recurrent_state_bytes.value())
    return out


def compare(cell, served, with_control: bool = False,
            control_precision: str = None) -> dict:
    """``serve_linear_gqa.compare``, whole, without the number that reads
    a float32 state's own values: the tails are stated bfloat16."""
    with _as_this_runner():
        out = serve_linear_gqa.compare(cell, served, with_control,
                                       control_precision)
    for part in ("numbers", "control"):
        out.get(part, {}).pop("state_rounding_lost", None)
    return out
