"""Runner ``serve_ssm_gqa``: an LM of Mamba-2 state-space layers (a
per-slot state) beside an ungated grouped-query layer (paged K and V)
with routed experts, a tied head and scalars on its one residual stream,
the chip's share of a stated deployment, served by ``DecodeEngine``
(``load()``, ``generate_async``) on one chip.

Everything is ``serve_linear_gqa``'s, taken as that runner takes
``serve_latent_moe``'s and ``serve_sparse_gqa``'s: the set-up with the
engine's ``prefill_order`` from the configuration's ``program``, the
closed loop, the window that keeps the echoed logits of the answers the
comparison will draw and asks every request for its slot's state, the
blocked reference over layers of both kinds, the four numbers of the
logits over the flip-free positions and the two of the state
(``state_gap``, ``state_rounding_lost``).  What differs:

* the layers' kinds are the reference's own names (``layer_types``:
  ``mamba`` / ``attention``), and the tree has no ``head``: it is tied;
* the reference's ``embed`` takes the sizes (the embedding's
  multiplier), which the blocked pass hands it;
* the window reports ``state_rows_computed`` beside ``STATE_STATS``: the
  rows the chunked scan computed for those it scanned.
"""

from __future__ import annotations

# the program's new symbols first: a checkout without them fails here, at
# once, before anything is built
from deeplearning4j_tpu.models.ssm_gqa import SCAN_STATS  # noqa: E402  isort:skip

import contextlib
import dataclasses
import functools
import types

from benchmarks.runners import serve_latent_moe, serve_linear_gqa
from benchmarks.runners.serve_linear_gqa import (  # noqa: F401  (the harness calls them)
    release, sizes, state_numbers)

KINDS = ("attention", "mamba")


def _makers(cell, dtype):
    """Jitted makers of one layer's weights, by the layer's kind, and of
    the embedding and final gain, from a key."""
    import jax

    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    make = {kind: jax.jit(functools.partial(ref.init_layer, sizes=sz,
                                            kind=kind, dtype=dtype))
            for kind in KINDS}
    return make, jax.jit(functools.partial(ref.init_ends, sizes=sz,
                                           dtype=dtype))


def seeded_tree(cell, dtype=None) -> dict:
    """The program's parameter tree for ``cell.seed``: each layer from
    its own key by the reference's initialiser, one jitted call a layer
    (two programs: an attention layer's and a state-space one's).  No
    ``head``: the embedding is the head."""
    import jax.numpy as jnp

    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    key = ref.seed_key(cell.seed)
    make, ends = _makers(cell, dtype or jnp.bfloat16)
    ends = ends(key)
    blocks = [make[ref.layer_kind(sz, i)](ref.layer_key(key, i))
              for i in range(int(sz["num_hidden_layers"]))]
    return {"embed": ends["embed"], "blocks": blocks, "lnf_g": ends["lnf_g"]}


@contextlib.contextmanager
def _as_this_runner():
    """``serve_linear_gqa``'s functions over this runner's weights."""
    theirs = serve_linear_gqa._makers, serve_linear_gqa.seeded_tree
    serve_linear_gqa._makers, serve_linear_gqa.seeded_tree = \
        _makers, seeded_tree
    try:
        yield
    finally:
        serve_linear_gqa._makers, serve_linear_gqa.seeded_tree = theirs


def setup(cell, split: dict) -> dict:
    with _as_this_runner():
        return serve_linear_gqa.setup(cell, split)


def window(cell, state, tracer) -> dict:
    out = serve_linear_gqa.window(cell, state, tracer)
    c = state["eng"].metrics.counter_value
    out["summary"]["counters_of_the_process"].update(
        {k: c(k) for k in SCAN_STATS})
    return out


def compare(cell, served, with_control: bool = False,
            control_precision: str = None) -> dict:
    """``serve_linear_gqa.compare``, whole, the reference's ``embed``
    handed the sizes it reads its multiplier from."""
    ref, sz = cell.reference, serve_latent_moe._ref_sizes(cell)
    shim = types.SimpleNamespace(**{k: getattr(ref, k) for k in dir(ref)
                                    if not k.startswith("__")})
    shim.embed = functools.partial(ref.embed, sizes=sz)
    with _as_this_runner():
        return serve_linear_gqa.compare(
            dataclasses.replace(cell, reference=shim), served, with_control,
            control_precision)
