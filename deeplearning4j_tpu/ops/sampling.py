"""Seeded counter-based token sampling — ONE source of truth.

The decode engine's host-driven samplers (serving/decode.py
``_make_samplers``), its speculative proposer and verifier
(``_make_spec_fns``) and the fused multi-step decode programs
(``DecodeProgram.step_multi`` — models/transformer.py,
models/latent_moe.py) must draw bitwise-identical tokens for the same
(logits, sampling spec, seed, token_index): the fused-decode A/B gate
(bench ``fused_step_ab``) compares them token for token, and the
crash-retry path regenerates sequences by replaying the same counters.
Keeping the math here makes that identity structural — every caller
traces the SAME functions, so there is no second implementation to
drift.

The key schedule is ``fold_in(PRNGKey(seed), step)`` with ``step`` the
absolute generated-token index (0 = the token sampled from the prefill
logits), which is what makes horizon fusion exact: step j of a fused
horizon uses the identical key the plain engine would have used j
dispatches later.

The functions take a BATCH of rows, because what the filter costs is
decided once for the batch (``needs_sort``): only top-p needs a row in
sorted order; the top-k threshold alone is one value, which an exact
selection finds without a sort (``ops.select.kth_largest``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .select import kth_largest


def needs_sort(ts, ps):
    """Whether a batch's filter takes the sorted path: some sampled row
    (temperature > 0) sets ``top_p < 1``.  Of numpy or jax arrays (or
    scalars): the engine counts by it on the host what the program
    branches on on the device (``sampler_sorted_steps``)."""
    return ((ps < 1.0) & (ts > 0.0)).any()


def _sorted_threshold(scaled, k, p):
    """One row's threshold from its sorted order: the larger of the
    ``k``-th largest entry and the smallest entry of the top-p prefix."""
    n = scaled.shape[0]
    srt = jnp.sort(scaled)[::-1]
    kk = jnp.clip(jnp.where(k > 0, k, n), 1, n)
    thr_k = srt[kk - 1]
    probs = jax.nn.softmax(srt)
    cum_excl = jnp.cumsum(probs) - probs   # mass BEFORE each entry
    keep = cum_excl < jnp.clip(p, 1e-6, 1.0)  # top-1 always kept
    thr_p = jnp.min(jnp.where(keep, srt, jnp.inf))
    return jnp.maximum(thr_k, thr_p)


def _selected_threshold(scaled, ks):
    """The rows' top-k thresholds with no top-p in the batch: the
    ``k``-th largest entry by selection, ``-inf`` where ``k == 0``."""
    n = scaled.shape[-1]
    thr_k = kth_largest(scaled, jnp.clip(ks, 1, n))
    return jnp.where(ks > 0, thr_k, -jnp.inf)


def filter_threshold(scaled, ts, ks, ps):
    """float32 [S]: what an entry of ``scaled`` [S, V] (the logits over
    the temperature) has to reach to stay a candidate under the rows'
    top-k (``ks``, 0 disables) and top-p (``ps``, >= 1 disables)
    filters; entries tied with it all stay.  ONE branch for the whole
    batch (``needs_sort``): a per-row ``cond`` under ``vmap`` would be a
    ``select`` that runs both."""
    return jax.lax.cond(
        needs_sort(ts, ps),
        lambda: jax.vmap(_sorted_threshold)(scaled, ks, ps),
        lambda: _selected_threshold(scaled, ks))


def scale_and_filter(lgs, ts, ks, ps):
    """``lgs`` [S, V] over the rows' temperatures, with ``-inf`` where
    the rows' filters drop an entry."""
    scaled = lgs / jnp.maximum(ts, 1e-6)[:, None]
    thr = filter_threshold(scaled, ts, ks, ps)
    return jnp.where(scaled >= thr[:, None], scaled, -jnp.inf)


def sample_tokens(lgs, ts, ks, ps, seeds, steps):
    """Sample one token from each logits row of ``lgs`` [S, V].

    temperature ``ts`` <= 0 is greedy; ``ks`` == 0 and ``ps`` >= 1
    disable the top-k / top-p filters.  Returns ``(tokens int32 [S],
    finite bool [S])`` — ``finite`` is the all-finite poison flag the
    engine's isolation path reads.  Deterministic: a row's PRNG key is
    ``fold_in(PRNGKey(seed), step)``, so the same (seed, step) always
    produces the same draw regardless of which executable traced it or
    which rows share its batch.
    """
    finite = jnp.all(jnp.isfinite(lgs), axis=-1)
    greedy = jnp.argmax(lgs, axis=-1).astype(jnp.int32)
    masked = scale_and_filter(lgs, ts, ks, ps)
    g = jax.vmap(lambda seed, step: jax.random.gumbel(
        jax.random.fold_in(jax.random.PRNGKey(seed), step),
        lgs.shape[1:]))(seeds, steps)
    sampled = jnp.argmax(masked + g, axis=-1).astype(jnp.int32)
    return jnp.where(ts <= 0.0, greedy, sampled), finite


def sample_token(lg, t, k, p, seed, step):
    """``sample_tokens`` of the one row ``lg`` [V]: ``(token, finite)``."""
    tok, finite = sample_tokens(*(jnp.asarray(a)[None]
                                  for a in (lg, t, k, p, seed, step)))
    return tok[0], finite[0]
