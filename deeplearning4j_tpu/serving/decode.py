"""Autoregressive decode engine: paged KV-cache + continuous batching.

A genuinely different execution mode from the one-shot ``Engine``:
stateful (the KV-cache carries across steps), multi-step (one request
spans many device dispatches), and shape-bucketed in TWO dimensions
(prompt length at prefill, slot count at decode).  The design:

  prefill/decode split
      A request's prompt runs ONCE through a bucketed prefill program
      (one AOT-compiled executable per prompt bucket) that writes K/V
      for every prompt position into the request's cache pages and
      samples the first token — so TTFT is one prefill dispatch, not
      ``n_prompt`` decode steps.  After that, every token costs one
      fixed-shape decode step.

  iteration-level continuous batching
      The decode step always runs over ALL ``max_slots`` slots with an
      active mask (masked slots write to the scratch page — see
      ops/kv_cache.py), so its compiled shape never changes and a new
      request can join the running batch at the NEXT step boundary
      (``ContinuousBatcher.admit``) instead of waiting for the batch to
      drain.  Zero serve-time compiles is therefore structural: the
      serve path only ever calls executables built at ``load()``
      (``compile_cache_size()`` is the witness, same contract as the
      one-shot engine).

  per-request stop conditions
      EOS / max-tokens / deadline are checked as every sampled token is
      recorded; a stopped request resolves immediately and its cache
      pages go back to the free list the same turn — the pool
      oversubscribes slots when request lengths vary.

  one turn of the loop (``_turn``)
      A turn admits (a whole prompt's prefill blocks on its first
      token), runs the turn's prefill chunks, and then, per live model
      version, QUEUES one decode dispatch and READS what is due
      (``_step_once``: one dispatch function, one read, one record
      loop).  Two predicates say how, each computed from what the engine
      observes and written once, with its reason:

      ``keep`` (``_step_once``): with a horizon of 1 and one live
      version the new dispatch stays unread until the next turn, which
      queues step n+1 and its sampler call and only then reads step n
      back: the device runs n+1 while the host reads, records, finishes,
      admits and builds n+2.  Step n+1's input tokens are the sampler's
      output of step n, still on the device; a slot that joined from a
      prefill since has its token on the host, and one small program
      joins the two (``("join",)``).  Positions, sampler counters and
      budget stops are known without the read; EOS, a passed deadline
      and non-finite logits are not, so such a request is stepped once
      too often: a write at its own next row, ordered on the device
      before anything a later tenant of its pages does, and a token that
      is dropped (``overrun_slot_steps``).  While two model versions are
      alive each step is read before the next is queued
      (``step_drains``); counters ``steps_ahead`` / ``decode_steps`` say
      how often the device had its next step waiting.

      ``_chunks_ride_behind``: under a fused horizon the turn's chunks
      are queued behind the dispatch and read after it; otherwise they
      run before it, each waited for (see "chunked prefill" below).

      Slot state on the host changes only at a read, so a crash retries
      from the last recorded token.

  resilience (the PR-7 supervisor patterns, decode-shaped)
      A crash anywhere in the decode loop fails or RETRIES every
      in-flight request (sampling is seeded + counter-based, so a retry
      regenerates the identical sequence), resets the pool, and keeps
      serving; a supervisor thread respawns the loop if it dies
      outright.  Poison isolation is per-slot: non-finite logits fail
      only that slot's request (its pages are scrubbed — a NaN left in
      a freed page would contaminate the next tenant), co-batched slots
      never notice.  Every future resolves on every path.

  hot-swap without version mixing
      ``swap_model`` flips the tag NEW admissions use; in-flight slots
      keep decoding under the version that prefilled them (the decode
      step runs once per distinct active tag — same executable,
      different params), so no request ever mixes versions and a swap
      never stalls the batch.  ``attach_registry`` wires this to
      ``ModelRegistry.set_alias``.

Sampling is greedy / temperature / top-k / top-p, seeded and
deterministic: the PRNG key is ``fold_in(PRNGKey(seed), token_index)``,
so a sequence is a pure function of (params, prompt, sampling spec) —
the property the retry path and the A/B bit-identity gate both lean on.

Three stacked decode-side optimizations, each independently gated
(docs/SERVING.md "Decode-side optimizations"):

  radix prefix cache (``prefix_cache=True``)
      A host-side trie keyed by page-sized token chunks maps
      fully-filled prompt pages to refcounted pool pages.  On admit,
      the longest matching PAGE-ALIGNED prefix is attached read-only to
      the new request's page table and only the unmatched suffix
      prefills (``prefill_at``), so a shared-prefix TTFT collapses
      toward one suffix dispatch.  Shared pages are copy-on-write by
      construction: a request only ever WRITES pages it privately owns
      (the first partial page is re-prefilled privately; generated
      tokens land past the insertable region), so sharing needs no page
      copies at all.  ``_finish`` decrefs instead of freeing; eviction
      is LRU over refcount-zero leaves under pool pressure.

  speculative decoding (``draft_model=..., speculate_k=k``)
      A draft program proposes k tokens per round (k cheap draft steps
      against a draft-sized pool indexed by the SAME page table); the
      target scores all k+1 rows in ONE fixed-shape ``spec_step``
      dispatch and seeded rejection sampling commits 1..k+1 tokens.
      At temperature 0 acceptance degenerates to exact greedy match,
      so output is BIT-identical to non-speculative decode; at
      temperature > 0 commits are exactly target-distributed but use
      dedicated RNG streams, so the sampled sequence differs from the
      non-speculative stream (documented, not gated).

  int8 KV storage (``kv_dtype="int8"``)
      Pages hold per-row symmetric int8 values + f32 scales
      (ops/kv_cache.QuantPages), quantized on write and dequantized in
      ``gather_layer`` — attention math stays f32.  ~4x sessions at
      fixed HBM; changes bits, so it is gated by a top1-agree accuracy
      envelope in ``decode_speed_ab``, never by the identity gates.

Two more host-overhead eliminations ride on top (docs/SERVING.md
"Host-overhead elimination"; both off by default, both bit-exact):

  fused multi-step decode (``decode_horizon=H``)
      H consecutive decode steps + device-resident sampling run inside
      ONE AOT executable (``DecodeProgram.step_multi`` — a ``lax.scan``
      of the step body) so the per-token Python round-trip is paid once
      per H tokens.  The ``fold_in(seed, token_index)`` keying makes
      the fused stream bit-identical to step-by-step; per-slot
      EOS/budget/poison masking on device routes a finished slot's
      remaining writes to the scratch page, and the host discards the
      ≤ H-1 token overrun at replay.  Mutually exclusive with
      speculative decoding (checked at construction).

  chunked prefill (``prefill_chunk=N``)
      Long prompts prefill in ≤ N-token chunks through ``prefill_at``
      at increasing offsets, so a long prompt never serializes the
      decode step loop.  The budget of a loop iteration is ONE CHUNK
      FOR EACH SLOT MID-PREFILL at its start (``_chunk_budget``; no
      option sets it): a prompt read at one chunk a turn whatever waits
      holds its slot for as many turns as it has chunks, and a turn's
      dispatch costs the same whether five slots decode or eight.  The
      stall bound: between two of a decoding slot's dispatches lie at
      most as many chunks as slots were mid-prefill, so at most
      ``max_slots - 1`` chunks of ``prefill_chunk`` tokens, and that
      many only when it is the one slot that decodes (nobody else is
      stalled then); counters ``chunk_turns`` / ``chunk_turns_multi``,
      the gauge ``chunk_turn_max`` and the ``chunks`` / ``mid_prefill``
      arguments of ``serve/decode_step`` say how often and how far.
      ADMISSION keeps that pace: the batcher's token-budget rule lets
      one round take in no more prompt tokens than one chunk FOR EACH
      FREE SLOT (the head request always), so short prompts fill every
      free slot at once and a wall of long ones enters at a chunk a
      slot a turn (``admit_rounds_budget_bound`` counts the rounds the
      budget ended).
      Per-row attention math is unchanged, so the final chunk's logits
      (and every sampled token) are bit-identical to an unchunked
      prefill, and to any other number of chunks a turn.  Under a fused
      horizon the turn's chunks are queued behind the step's dispatch,
      back to back, so the device runs them while the host reads back
      and records the step's tokens; they are read in the order of their
      dispatch as far as the last FINAL chunk among them (its first
      token joins the next step), the rest after the next turn's steps,
      when they are long done (``_turn``).  A fused dispatch's echoed
      logits are copied out one dispatch late (``_Flight.late``,
      ``_flush_echo``): between two programs the host then only reads
      tokens, records them and dispatches.
      Whose chunk goes next (``prefill_order``, asked once a chunk):
      ``"round_robin"`` over the slots mid-prefill, or
      ``"nearest_end"``, the prompt with the fewest tokens left first
      (``_nearest_end``; a turn may then hand several chunks to one
      prompt, each starting where the last one ends): under a closed
      loop of long prompts round-robin finishes every waiting prompt
      late and together.

TTFT and time-per-output-token are first-class (``DecodeMetrics``).
Everything the loop thread does is a live ``obs.trace`` span under
``serve/iteration`` (``serve/admit``, ``serve/prefill``, a span each
where the host builds and dispatches the step it queues,
``serve/decode_step`` with a child where it waits for and one where it
records the step it reads, ``serve/finish``), and the spans of one
request carry its ``request_id`` — docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import threading
import time
from collections import deque
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..obs import startup as obs_startup
from ..obs import trace as obs_trace
from .batcher import (ContinuousBatcher, DeadlineExceededError,
                      pow2_buckets)
from .engine import (ModelNotLoadedError, PoisonInputError,
                     ReplicaCrashError, _fail_safe, _set_safe)
from .metrics import DecodeMetrics

FINISH_REASONS = ("eos", "max_tokens", "deadline")

#: ``prefill_order="nearest_end"``: rounds of a full engine's round-robin
#: a prompt mid-prefill may be passed over before it goes first
PASSED_OVER_ROUNDS = 4


def _leaves(tree) -> list:
    import jax
    return jax.tree_util.tree_leaves(tree)


@dataclass
class GenerationResult:
    """One finished generation.  ``tokens`` are the GENERATED ids only
    (prompt excluded; a terminating EOS is included).  ``logits`` is
    [n_tokens, vocab] float32 when the request asked ``echo_logits``
    (the bit-identity gate's evidence), else None.  ``request_id`` is
    the integer the request's spans carry (``serve/admit``,
    ``serve/prefill``, ``serve/finish``, ``serve/request``)."""

    tokens: List[int]
    n_prompt: int
    finish_reason: str
    model_tag: str
    ttft_ms: float
    tpot_ms: Optional[float]
    logits: Optional[np.ndarray] = None
    request_id: int = 0
    # [n_tokens, expert layers, k] int32: the experts (ids ascending) the
    # router chose at the position each token was taken from, where the
    # decode program has routed experts (models/latent_moe.py); else None
    expert_picks: Optional[np.ndarray] = None
    # [n_tokens, layers, k] int32: the cached positions (-1 = none) each
    # layer's attention selected at the position each token was taken
    # from, for a request that asked ``echo_logits`` of a program with a
    # learned sparse selection (models/sparse_gqa.py); else None
    attn_rows: Optional[np.ndarray] = None
    # what the slot held of every layer that keeps per-slot state
    # (``DecodeProgram.slot_state``: one tuple of arrays a state layer)
    # when the answer stopped, for a request that asked ``echo_state``
    # and ran to ``max_tokens``: the state as the LAST token fed left it,
    # which is every token of prompt and answer but the answer's last
    # (served, and never fed back).  Jax arrays, copied out of the pools
    # on the device and still there: ``np.asarray`` them.  Else None
    slot_state: Optional[tuple] = None


@dataclass(frozen=True)
class _GenSpec:
    """Immutable request payload — a crash-retry re-runs exactly this."""

    prompt: np.ndarray
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    seed: int
    echo_logits: bool
    request_id: int = 0       # drawn at submit; joins the request's spans
    echo_state: bool = False


@dataclass
class PrefillHandoff:
    """The complete baton a ``role="prefill"`` host passes to a
    ``role="decode"`` host: the request spec, the first sampled token
    (TTFT is paid on the prefill side), and the prompt's KV pages packed
    with ``ops.kv_cache.pack_transfer`` — bit-exact f32 bytes or the
    int8+scale pair, so the decode host continues the EXACT sequence a
    unified engine would have produced.  ``logits0`` carries the prefill
    logits row only when the request asked ``echo_logits``."""

    prompt: np.ndarray
    max_new: int
    temperature: float
    top_k: int
    top_p: float
    seed: int
    echo_logits: bool
    first_token: int
    finite: bool
    n_pages: int
    pages: bytes
    logits0: Optional[np.ndarray]
    model_tag: str


@dataclass(frozen=True)
class _HandoffSpec(_GenSpec):
    """``_GenSpec`` + the inbound transfer — what a decode-role host's
    batcher queues for ``continue_async``."""

    handoff: Any = None


class _Slot:
    """Host-side state of one occupied decode slot.  ``page_ids`` are
    the slot's PRIVATE pages (freed at finish); ``shared_nodes`` are the
    prefix-trie nodes it holds a reference on — the first ``n_matched``
    are donor pages attached read-only at admit, the rest are pages this
    slot's own prefill inserted (decref'd, never freed directly)."""

    __slots__ = ("req", "spec", "tag", "page_ids", "n_prompt", "pos",
                 "last_token", "tokens", "n_out", "max_new", "deadline",
                 "t_first", "t_last", "logits", "shared_nodes", "n_matched",
                 "n_prefilled", "picks", "logit_buf", "rows_buf",
                 "rows_next", "passed_over")

    def __init__(self, req, tag: str, page_ids: List[int], max_new: int):
        self.req = req
        self.spec = req.payload
        self.tag = tag
        self.page_ids = page_ids
        self.n_prompt = int(self.spec.prompt.shape[0])
        self.pos = self.n_prompt      # where the NEXT input token lands
        self.last_token = 0
        self.tokens: List[int] = []
        self.n_out = 0
        self.max_new = max_new
        self.deadline = req.deadline
        self.t_first = 0.0
        self.t_last = 0.0
        self.logits: Optional[List[np.ndarray]] = \
            [] if self.spec.echo_logits else None
        self.logit_buf: Optional[np.ndarray] = None   # the rows' storage
        # the positions each layer's attention selected, a row a token
        # beside ``logit_buf`` (a program that reports ``attn_rows``)
        self.rows_buf: Optional[np.ndarray] = None
        # the row of the token about to be recorded, set by the caller
        # as ``picks`` is: ``_record_token`` keeps its five arguments
        self.rows_next: Optional[np.ndarray] = None
        # the experts each token's layers chose, where the program says
        self.picks: List[np.ndarray] = []
        self.shared_nodes: List["_PrefixNode"] = []
        self.n_matched = 0
        # chunked prefill progress: prompt tokens already resident in
        # the cache (None once prefill completes / for unchunked slots);
        # a slot with n_prefilled set is NOT steppable yet
        self.n_prefilled: Optional[int] = None
        # chunk picks since this prompt's last chunk that went to another
        self.passed_over = 0


class _Chunk:
    """One dispatch of a prompt program between its pick and its commit:
    the slot, the tokens, and what the dispatch left on the device.
    ``whole``: it is the prompt's whole unmatched part, prefilled at
    admission in one program, and not a turn's chunk of a chunked
    prefill."""

    __slots__ = ("i", "slot", "offset", "take", "bucket", "padded", "last",
                 "whole", "lg", "aux", "tok", "fin", "picks_h", "tok_h",
                 "fin_h", "lg_h", "rows_h", "t1")


class _StepInputs:
    """One decode dispatch's host-built inputs: the per-slot arrays the
    compiled step and samplers take, and what the group holds."""

    __slots__ = ("params", "tag", "group", "slots", "echo", "toks_in",
                 "on_host", "pos", "act", "temps", "tks", "tps", "seeds",
                 "steps", "budgets", "pages_reserved", "pages_filled")

    def __init__(self, n_slots: int, tag: str):
        self.params = None
        self.tag = tag
        self.group: List[int] = []      # slot indices stepped together
        # by slot, the request stepped there (None: not in the group):
        # what the step returns is its, whoever holds the slot at the read
        self.slots: List[Optional[_Slot]] = [None] * n_slots
        self.echo = False               # some slot wants its logits back
        self.toks_in = np.zeros((n_slots,), np.int32)
        # False where the slot's input token is still on the device, in
        # the sampler's output of the step in flight
        self.on_host = np.ones((n_slots,), bool)
        self.pos = np.zeros((n_slots,), np.int32)
        self.act = np.zeros((n_slots,), bool)
        self.temps = np.zeros((n_slots,), np.float32)
        self.tks = np.zeros((n_slots,), np.int32)
        self.tps = np.ones((n_slots,), np.float32)
        self.seeds = np.zeros((n_slots,), np.uint32)
        self.steps = np.zeros((n_slots,), np.int32)
        self.budgets = np.ones((n_slots,), np.int32)
        self.pages_reserved = 0         # pages the group's slots hold
        self.pages_filled = 0           # ... of which hold >= 1 token


class _Flight:
    """One decode dispatch on the device and not read back yet: its
    inputs, the decode ``steps`` it holds, what it left on the device
    (``[steps, slots, ...]``, a single step's without the leading axis;
    the transfers to the host started at the dispatch), whether the
    dispatch before it was unread when it went, and the dispatch's
    times.  ``late``: its echoed logits are the bulk of ``steps`` steps
    and are copied out a dispatch late (``_flush_echo``), not waited for
    at the read.  ``sample_ms`` is None where the step's program samples
    itself: the step's time is then the whole of dispatch to read."""

    __slots__ = ("inp", "steps", "toks", "fin", "lgs", "attn", "aux",
                 "late", "ahead", "t0", "step_ms", "sample_ms")


class _PrefixNode:
    """One fully-filled, immutable KV page in the radix prefix trie.
    ``key`` is the page's token tuple (length = page_size); ``refs``
    counts the slots currently holding the page in their page table
    (a holder of a node holds every ancestor, so refs are monotonically
    non-increasing root -> leaf and a refs-0 node's children are also
    refs-0).  ``last_used`` is an injectable-clock timestamp (GC201)
    driving LRU eviction; ``detached`` marks a node already pulled out
    of the trie (never match it again)."""

    __slots__ = ("key", "page_id", "refs", "children", "parent",
                 "last_used", "detached")

    def __init__(self, key: tuple, page_id: Optional[int], parent):
        self.key = key
        self.page_id = page_id
        self.refs = 0
        self.children: Dict[tuple, "_PrefixNode"] = {}
        self.parent = parent
        self.last_used = 0.0
        self.detached = False


def _make_samplers():
    """(sample_one, sample_batch) pure fns.  Deterministic: the key is
    ``fold_in(PRNGKey(seed), step)`` — same (seed, step) → same draw.
    temperature <= 0 is greedy; top_k == 0 and top_p >= 1 disable those
    filters.  Also returns the all-finite flag the poison check reads.

    The math lives in ``ops.sampling`` so the fused ``step_multi``
    programs trace the SAME function — that shared source is what makes
    horizon fusion bit-identical to step-by-step.
    """
    from ..ops.sampling import sample_token, sample_tokens

    # the names the executables carry in a trace (``jit_sample_batch``)
    def sample_one(lg, t, k, p, seed, step):
        return sample_token(lg, t, k, p, seed, step)

    def sample_batch(lgs, ts, ks, ps, seeds, steps):
        return sample_tokens(lgs, ts, ks, ps, seeds, steps)

    return sample_one, sample_batch


# speculative decoding draws from dedicated RNG streams so a request's
# (seed, token_index) space never collides across the draft proposal,
# the accept test, and the residual resample
_DRAFT_STREAM, _ACCEPT_STREAM, _RESID_STREAM = 1, 2, 3


def _make_spec_fns(vocab_size: int, n_spec: int):
    """(propose, accept) pure fns for speculative decoding with
    ``n_spec`` draft tokens per round.

    ``propose`` samples one draft token per slot from the WARPED draft
    distribution (same temperature/top-k/top-p filter as
    ``_make_samplers``; one-hot(argmax) at temperature <= 0) and
    returns the full distribution — the accept test needs p_draft(d).

    ``accept`` runs exact rejection sampling: draft token j is accepted
    iff u_j < p_target(d_j) / p_draft(d_j) with u_j a seeded uniform;
    the first rejected position resamples from the normalized residual
    max(p_target - p_draft, 0), and full acceptance earns the bonus
    token from the target's row k ("residual" against an all-zero draft
    row — pure target).  At temperature <= 0 both distributions are
    one-hot, the ratio is exactly 0 or 1, and the commit short-circuits
    to argmax of the target row — deterministic, RNG-free, and
    bit-identical to the non-speculative greedy path.
    """
    import jax
    import jax.numpy as jnp

    from ..ops.sampling import scale_and_filter

    def _key(seed, stream, step):
        return jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(seed), stream), step)

    def _warped(lgs, ts, ks, ps):
        # the samplers' filter (one source), expressed as distributions
        onehot = jax.nn.one_hot(jnp.argmax(lgs, axis=-1), vocab_size,
                                dtype=jnp.float32)
        return jnp.where((ts <= 0.0)[:, None], onehot,
                         jax.nn.softmax(scale_and_filter(lgs, ts, ks, ps)))

    def propose_one(lg, dist, t, seed, step):
        g = jax.random.gumbel(_key(seed, _DRAFT_STREAM, step), lg.shape)
        sampled = jnp.argmax(jnp.log(jnp.maximum(dist, 1e-30)) + g)
        tok = jnp.where(t <= 0.0, jnp.argmax(lg), sampled)
        return tok.astype(jnp.int32)

    def accept_one(tlgs, targ, dtoks, dprobs, t, seed, step0):
        # tlgs [n_spec+1, V] target logits, targ their warped
        # distributions; dtoks [n_spec] draft tokens; dprobs [n_spec, V]
        # warped draft distributions
        finite = jnp.all(jnp.isfinite(tlgs))
        j = jnp.arange(n_spec)
        p_t_d = targ[j, dtoks]
        p_d_d = dprobs[j, dtoks]
        u = jax.vmap(lambda jj: jax.random.uniform(
            _key(seed, _ACCEPT_STREAM, step0 + jj)))(j)
        acc = u < p_t_d / jnp.maximum(p_d_d, 1e-30)
        a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32)))   # leading accepts
        dp_full = jnp.concatenate(
            [dprobs, jnp.zeros((1, vocab_size), jnp.float32)], 0)
        resid = jnp.maximum(targ - dp_full, 0.0)
        rs = jnp.sum(resid, -1, keepdims=True)
        resid = jnp.where(rs > 0, resid / jnp.maximum(rs, 1e-30), targ)
        jr = jnp.arange(n_spec + 1)

        def draw_row(jj):
            g = jax.random.gumbel(_key(seed, _RESID_STREAM, step0 + jj),
                                  (vocab_size,))
            return jnp.argmax(jnp.log(jnp.maximum(resid[jj], 1e-30)) + g)

        draws = jax.vmap(draw_row)(jr).astype(jnp.int32)
        dt_full = jnp.concatenate([dtoks, jnp.zeros((1,), jnp.int32)])
        sampled = jnp.where(jr < a, dt_full,
                            jnp.where(jr == a, draws, 0))
        greedy = jnp.where(jr < a, dt_full,
                           jnp.where(jr == a,
                                     jnp.argmax(tlgs, -1).astype(jnp.int32),
                                     0))
        commit = jnp.where(t <= 0.0, greedy, sampled)
        return (a + 1).astype(jnp.int32), commit, finite

    def propose(lgs, ts, ks, ps, seeds, steps):
        dists = _warped(lgs, ts, ks, ps)
        return jax.vmap(propose_one)(lgs, dists, ts, seeds, steps), dists

    def accept(tlgs, dtoks, dprobs, ts, ks, ps, seeds, steps):
        # every row of a slot under the slot's spec, in ONE batch
        targ = _warped(
            tlgs.reshape(-1, vocab_size),
            *(jnp.repeat(a, n_spec + 1) for a in (ts, ks, ps))
        ).reshape(tlgs.shape)
        return jax.vmap(accept_one)(tlgs, targ, dtoks, dprobs, ts, seeds,
                                    steps)

    return propose, accept


class DecodeEngine:
    """``DecodeEngine(lm).load()`` then ``generate(prompt_ids, ...)``.

    ``model`` provides ``decode_program()`` (ShardedTransformerLM) — the
    pure prefill/step/re-encode functions of ops/kv_cache.DecodeProgram.
    ``clock`` is injectable (monotonic seconds) so deadline/TTFT logic
    is testable without sleeping.
    """

    def __init__(self, model, *, max_slots: int = 4, page_size: int = 16,
                 max_len: Optional[int] = None,
                 total_pages: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 eos_id: Optional[int] = None, slo_ms: float = 30_000.0,
                 max_queue: int = 256, admission: str = "block",
                 max_retries: int = 1, default_max_new: int = 32,
                 clock=time.monotonic, tag: str = "v0",
                 metrics: Optional[DecodeMetrics] = None,
                 prefix_cache: bool = False, draft_model=None,
                 speculate_k: int = 4, kv_dtype: Optional[str] = None,
                 role: str = "unified", tenants=None,
                 decode_horizon: int = 1,
                 prefill_chunk: Optional[int] = None,
                 prefill_order: str = "round_robin"):
        if max_slots < 1:
            raise ValueError("max_slots must be >= 1")
        if decode_horizon < 1:
            raise ValueError("decode_horizon must be >= 1")
        if decode_horizon > 1 and draft_model is not None:
            raise ValueError(
                "fused multi-step decode and speculative decoding are "
                "mutually exclusive — speculation keeps its own round "
                "structure (propose/verify/commit), so a fused horizon "
                "has nothing to amortize there")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if prefill_chunk is not None and role != "unified":
            raise ValueError(
                "chunked prefill is unified-role only: a decode-role "
                "host never prefills, and a prefill-role host has no "
                "step loop to interleave with")
        if prefill_chunk is not None and draft_model is not None:
            raise ValueError(
                "chunked prefill + speculative decoding is unsupported "
                "(the draft pool's mirror prefill is not chunked)")
        if prefill_order not in ("round_robin", "nearest_end"):
            raise ValueError(f"prefill_order {prefill_order!r} not supported "
                             "(round_robin or nearest_end)")
        if prefill_order != "round_robin" and prefill_chunk is None:
            raise ValueError("prefill_order orders the chunks of a chunked "
                             "prefill: it needs prefill_chunk")
        if kv_dtype not in (None, "f32", "float32", "int8", "i8"):
            raise ValueError(f"kv_dtype {kv_dtype!r} not supported "
                             "(float32 or int8)")
        if role not in ("unified", "prefill", "decode"):
            raise ValueError(f"role {role!r} not supported "
                             "(unified, prefill, or decode)")
        if role != "unified" and draft_model is not None:
            raise ValueError(
                "speculative decoding is unified-role only — the draft "
                "pool's state never crosses a page handoff")
        self.role = role
        self._mesh = getattr(model, "mesh", None)
        self.program = model.decode_program(page_size=page_size,
                                            max_len=max_len)
        prog = self.program
        if getattr(prog, "tp", 1) > 1 and kv_dtype in ("int8", "i8"):
            raise ValueError(
                "int8 KV + tensor-parallel decode is unsupported: the "
                "per-row quantization scale is an amax over ALL heads "
                "and cannot be computed inside one head shard")
        if (getattr(prog, "pool_rows", None) is not None
                or getattr(prog, "aux", False)):
            # a program with its own pools (one latent pool; K, V and
            # index rows) carries the plain path, chunked prefill and the
            # fused horizon; the rest is not ported
            asked = [name for name, on in (
                ("int8 KV (kv_dtype)", kv_dtype in ("int8", "i8")),
                ("page transfer between hosts (role)", role != "unified"),
                ("speculation (draft_model)", draft_model is not None),
                ("the prefix cache (prefix_cache)", bool(prefix_cache)),
                ("tensor-parallel decode", getattr(prog, "tp", 1) > 1),
            ) if on]
            if asked:
                # per-slot state has no pages to share, hand over or roll
                # back: a prefix hit, a transfer and a rejected proposal
                # would each need a snapshot of it
                own = ("pools of its own rows and a per-slot recurrent state"
                       if prog.slot_state else "pools of its own rows")
                raise ValueError(
                    f"this decode program keeps {own} and "
                    "does not carry " + ", ".join(asked) + " yet")
        # the program keeps state per slot beside its pools (``load``
        # allocates it with them; ``_slot_arg``)
        self._slot_state = bool(getattr(prog, "slot_state", ()))
        self._prefix_on = bool(prefix_cache)
        if self._prefix_on and prog.prefill_at is None:
            raise ValueError(
                "prefix_cache=True needs a decode program with a "
                "prefill_at entry point (suffix prefill)")
        self.decode_horizon = int(decode_horizon)
        if self.decode_horizon > 1 and prog.step_multi is None:
            raise ValueError(
                "decode_horizon > 1 needs a decode program with a "
                "step_multi entry point (fused multi-step decode)")
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        if self.prefill_chunk is not None and prog.prefill_at is None:
            raise ValueError(
                "prefill_chunk needs a decode program with a prefill_at "
                "entry point (offset prefill drives each chunk)")
        self.prefill_order = prefill_order
        self._kv_dtype = kv_dtype
        self.speculate_k = int(speculate_k)
        self._draft_program = None
        self._draft_params = None
        self._draft_cache = None
        if draft_model is not None:
            if self.speculate_k < 1:
                raise ValueError("speculate_k must be >= 1")
            if prog.spec_step is None:
                raise ValueError(
                    "speculative decoding needs a decode program with a "
                    "spec_step entry point (multi-token verify)")
            dprog = draft_model.decode_program(page_size=page_size,
                                               max_len=prog.max_len)
            if (dprog.vocab_size != prog.vocab_size
                    or dprog.max_len != prog.max_len
                    or dprog.page_size != prog.page_size):
                raise ValueError(
                    "draft/target program mismatch: vocab "
                    f"{dprog.vocab_size}/{prog.vocab_size}, max_len "
                    f"{dprog.max_len}/{prog.max_len}, page_size "
                    f"{dprog.page_size}/{prog.page_size} must all agree")
            self._draft_program = dprog
            self._draft_params = getattr(draft_model, "params", draft_model)
        self._prefix_root = _PrefixNode((), None, None)
        self._trie_pages = 0
        self.max_slots = int(max_slots)
        self.eos_id = eos_id
        self.max_retries = int(max_retries)
        self.default_max_new = int(default_max_new)
        self.clock = clock
        self.total_pages = int(
            total_pages if total_pages is not None
            else 1 + self.max_slots * prog.pages_per_slot)
        if self.total_pages < 1 + prog.pages_per_slot:
            raise ValueError(
                f"total_pages {self.total_pages} cannot hold even one "
                f"full-length request ({prog.pages_per_slot} pages) plus "
                "the scratch page")
        self.metrics = metrics or DecodeMetrics()
        self.tenants = tenants           # tenancy.TenantTable or None
        self.batcher = ContinuousBatcher(
            max_batch=self.max_slots, slo_ms=slo_ms, max_queue=max_queue,
            admission=admission, metrics=self.metrics, clock=clock,
            tenants=tenants)
        buckets = sorted(set(int(b) for b in (prompt_buckets
                                              or pow2_buckets(prog.max_len))))
        self.prompt_buckets = [b for b in buckets if 0 < b <= prog.max_len]
        if not self.prompt_buckets:
            raise ValueError("no prompt bucket <= max_len "
                             f"{prog.max_len}: {buckets}")
        self.max_prompt = min(self.prompt_buckets[-1], prog.max_len - 1)
        if (self.prefill_chunk is not None
                and self.prefill_chunk <= self.prompt_buckets[-1]):
            # chunks go through the buckets, not whole prompts: a prompt
            # may be as long as the slot holds
            self.max_prompt = prog.max_len - 1

        params = getattr(model, "params", model)
        self._versions: Dict[str, Any] = {tag: params}
        self._serve_tag = tag
        # NAMED models this engine also decodes: name -> serve tag in
        # _versions.  Param trees must be shape-compatible with the
        # loaded program (executables are shared across versions).
        self._model_tags: Dict[str, str] = {}
        self._model_last_used: Dict[str, float] = {}
        self._slots: List[Optional[_Slot]] = [None] * self.max_slots
        self._page_table = np.zeros(
            (self.max_slots, prog.pages_per_slot), np.int32)
        self._free_pages = deque(range(1, self.total_pages))
        self._cache = None
        self._reads_held_pages = False
        self._compiled: Dict[tuple, Any] = {}
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._loaded = False
        self._shutdown = False
        self._generation = 0
        self._chunk_cursor = 0     # round-robin over chunked prefills
        # echoed logits of the last dispatch that hands them over late
        # (``_Flight.late``), not copied out yet: the device arrays,
        # (buffer, first row, rows, slot) per request, and the finished
        # answers that wait for those rows
        self._echo_lgs = None
        self._rows_shape: Optional[tuple] = None   # of ``attn_rows``, a token
        self._echo_rows: List[tuple] = []
        self._echo_results: List[tuple] = []
        self._echo_defer = False   # True while such a dispatch is recorded
        # chunks queued behind a decode dispatch and not read back yet, in
        # the order of their dispatch
        self._chunk_inflight: List[_Chunk] = []
        # the decode dispatch a turn left unread for the next one
        self._flight: Optional[_Flight] = None
        self._step_read_at = 0.0   # clock at the end of the last step's read
        self._request_ids = itertools.count(1)
        self._crash_next = False   # test hook: raise inside the next step
        self._thread: Optional[threading.Thread] = None
        self._supervisor: Optional[threading.Thread] = None
        self._autoscaler = None             # see enable_autoscale()
        self._autoscale_cb = None
        self._autoscale_interval_s = 0.25
        self._last_autoscale_t: Optional[float] = None
        self._shed_seen = 0.0
        self._logical_replicas = 1

    # -- load / warmup -----------------------------------------------------

    def load(self, warm_bundle: Optional[str] = None) -> "DecodeEngine":
        """Allocate the pool and AOT-compile + run every serve-path
        executable: one prefill per prompt bucket, the decode step, the
        two samplers, the pool reset, and the page scrub.  After this,
        ``compile_cache_size()`` must not grow while serving — the
        zero-serve-time-compiles contract; what JAX itself compiles
        after this returns is counted too (``serve_time_compiles``).

        ``warm_bundle`` points at a bundle written by
        :meth:`save_warmup_bundle` (serving/warmcache.py): each
        executable deserializes instead of compiling, with per-key
        fallback to compile on any miss.  Bundle hits are still executed
        once below, so the donated pool state flows identically to a
        cold load.

        The whole of it is the phase ``serve/load`` of the start-up
        account (obs/startup.py), each executable a
        ``serve/load_executable`` with its ``serve/lower``,
        ``serve/compile`` and ``serve/first_run``."""
        obs_startup.watch_compiles()
        with obs_startup.phase("serve/load", cat="serve",
                               tag=self._serve_tag, role=self.role) as ph:
            misses = self._load(warm_bundle)
            n = len(self._compiled)
            ph.set(executables=n, bundle_hits=n - misses,
                   bundle_misses=misses)
        self._loaded = True
        obs_startup.on_unphased_compile(self._on_compile_after_load)
        self._start_loop()
        self._supervisor = threading.Thread(
            target=self._supervise, name="decode-supervisor", daemon=True)
        self._supervisor.start()
        return self

    def _on_compile_after_load(self, event: dict) -> None:
        """JAX compiled (or read its cache) outside every phase of the
        start-up account while this engine serves."""
        if self._shutdown:
            return
        self.metrics.inc("serve_time_compiles")
        obs_trace.instant("serve/compile_after_load", cat="serve",
                          fun_name=event["fun_name"], stage=event["stage"],
                          seconds=event["seconds"])

    def _load(self, warm_bundle: Optional[str]) -> int:
        """The body of ``load``; returns how many executables the bundle
        did not bring."""
        import jax

        from ..ops.kv_cache import alloc_pools, pool_nbytes, state_nbytes
        from ..ops.paged_attention import kept_path
        from .warmcache import load_bundle

        prog = self.program
        params = self._versions[self._serve_tag]
        s_n, pps = self.max_slots, prog.pages_per_slot
        kp, vp = alloc_pools(prog, self.total_pages, self._kv_dtype,
                             slots=s_n)
        slot0 = self._slot_arg(0)
        # what ``kv_pages_read`` counts: the program says it attends over
        # the pages held, its kernel's own rule whether it takes this pool
        self._reads_held_pages = bool(prog.held_pages) and (
            prog.kept_path or kept_path)(kp, pps, prog.tp) is None
        self.metrics.recurrent_state_bytes.set(state_nbytes((kp, vp)))
        self.metrics.kv_bytes_per_token.set(
            (pool_nbytes((kp, vp)) - state_nbytes((kp, vp)))
            / (self.total_pages * prog.page_size))
        bundle_mesh = self._mesh if getattr(prog, "tp", 1) > 1 else None
        if bundle_mesh is not None:
            # the pool is head-sharded from its first byte (the program's
            # pool spec), not parked whole on one device until a step
            # happens to reshard it
            from jax.sharding import NamedSharding, PartitionSpec
            kp, vp = jax.device_put((kp, vp), NamedSharding(
                bundle_mesh, PartitionSpec(None, None, None, "data")))
        # reset/scrub return the pool where they found it: left to itself
        # XLA parks their (input-independent) zeros on one device
        pool_sh = jax.tree_util.tree_map(lambda a: a.sharding, (kp, vp))
        # a one-device LM's executables are committed to ITS device,
        # which on a multi-chip host need not be the first local one
        bundle_devices = (None if self._mesh is None
                          else list(self._mesh.devices.flat))
        bundle = (load_bundle(warm_bundle, mesh=bundle_mesh,
                              devices=bundle_devices)
                  if warm_bundle else {})
        built = []      # executables the bundle did not bring

        def _warm(key: tuple, jitted, *args):
            """One executable of the serve path: out of the bundle, or
            lowered and compiled here; run once on ``args`` (the donated
            pools thread through), kept under ``key``.  Returns what the
            run returned, ready."""
            name = ":".join(str(p) for p in key)   # its name in a bundle
            with obs_startup.phase("serve/load_executable", cat="serve",
                                   key=name) as ph:
                exe = bundle.get(name)
                ph.set(source="built" if exe is None else "bundle")
                if exe is None:
                    built.append(name)
                    with obs_startup.phase("serve/lower", cat="serve",
                                           key=name):
                        lowered = jitted.lower(*args)
                    with obs_startup.phase("serve/compile", cat="serve",
                                           key=name):
                        exe = lowered.compile()
                with obs_startup.phase("serve/first_run", cat="serve",
                                       key=name):
                    out = jax.block_until_ready(exe(*args))
            self._compiled[key] = exe
            return out

        t0 = self.clock()
        with obs_trace.span("serve/warmup", cat="serve", kind="decode",
                            tag=self._serve_tag, role=self.role):
            lgs = None
            zs_i = np.zeros((s_n,), np.int32)
            table0 = np.zeros((s_n, pps), np.int32)
            ids0 = np.zeros((pps,), np.int32)
            if self.role != "prefill":
                # decode step + batch sampler — a prefill-role host never
                # steps, so its warmup (and bundle) skips them entirely
                kp, vp, lgs, *aux = _warm(
                    ("step",), jax.jit(prog.step, donate_argnums=(1, 2)),
                    params, kp, vp, table0, zs_i, zs_i,
                    np.zeros((s_n,), bool))
                if aux and "attn_rows" in aux[0]:
                    # [layers, k] a token: what an echoing request keeps
                    self._rows_shape = tuple(aux[0]["attn_rows"].shape[1:])

                if self.decode_horizon > 1:
                    # fused multi-step decode: H is a compile-time
                    # constant (the scan length = horizon arange), so
                    # the executable lives in the bundle like any other
                    H = self.decode_horizon
                    kp, vp = _warm(
                        ("step_multi", H),
                        jax.jit(prog.step_multi, donate_argnums=(1, 2)),
                        params, kp, vp, table0, zs_i, zs_i,
                        np.zeros((s_n,), bool),
                        np.zeros((s_n,), np.float32), zs_i,
                        np.ones((s_n,), np.float32),
                        np.zeros((s_n,), np.uint32), zs_i,
                        np.ones((s_n,), np.int32), np.int32(-1),
                        np.arange(H, dtype=np.int32))[:2]

            lg1 = None
            if self.role != "decode":
                prefill_jit = jax.jit(prog.prefill, donate_argnums=(1, 2))
                # chunked prefill sends every prompt through prefill_at:
                # the whole-prompt programs would never run
                for b in (self.prompt_buckets
                          if self.prefill_chunk is None else ()):
                    kp, vp, lg1 = _warm(
                        ("prefill", b), prefill_jit, params, kp, vp, ids0,
                        np.zeros((b,), np.int32), np.int32(1), *slot0)[:3]

                if self._prefix_on or self.prefill_chunk is not None:
                    # suffix prefill per bucket — prefix-cache HITS and
                    # chunked-prefill chunks drive these; the cold
                    # path's executables (and bits) are untouched when
                    # both features are off
                    pa_jit = jax.jit(prog.prefill_at, donate_argnums=(1, 2))
                    for b in self.prompt_buckets:
                        kp, vp, lg1 = _warm(
                            ("prefill_at", b), pa_jit, params, kp, vp, ids0,
                            np.zeros((b,), np.int32), np.int32(1),
                            np.int32(0), *slot0)[:3]

            one, batch = _make_samplers()
            if self.role != "decode":
                _warm(("sample1",), jax.jit(one), lg1, np.float32(0),
                      np.int32(0), np.float32(1), np.uint32(0), np.int32(0))
            if self.role != "prefill":
                toks, _ = _warm(
                    ("sample",), jax.jit(batch), lgs,
                    np.zeros((s_n,), np.float32), zs_i,
                    np.ones((s_n,), np.float32),
                    np.zeros((s_n,), np.uint32), zs_i)
                if self.decode_horizon == 1 and self._draft_program is None:
                    # a single step may stay in flight (``_step_once``): a
                    # slot that joins from a prefill has its token on the
                    # host, the slots already stepping theirs on the device
                    def _join_tokens(dev, host, on_host):
                        import jax.numpy as jnp
                        return jnp.where(on_host, host, dev)

                    _warm(("join",), jax.jit(_join_tokens), toks, zs_i,
                          np.zeros((s_n,), bool))
            if self._slot_state:
                # an answer that asked for its slot's state takes a copy
                # of it out of the pools at its finish
                def _state_of(state, i):
                    return jax.tree_util.tree_map(lambda a: a[i], state)

                _warm(("slot_state",), jax.jit(_state_of), vp.state,
                      np.int32(0))

            from ..ops.kv_cache import scrub_pool

            def _reset(k, v):
                import jax.numpy as jnp
                z = jax.tree_util.tree_map(jnp.zeros_like, (k, v))
                return z[0], z[1]

            def _scrub(k, v, ids):
                # zero the given pages (padded with repeats — idempotent;
                # int8 pools zero values AND scales)
                return scrub_pool(k, ids), scrub_pool(v, ids)

            # keep_unused: the zeros do not read the pools, and an argument
            # the program drops is not donated: the reset then builds a
            # second pair of pools beside the first (12 GB at 16 slots)
            kp, vp = _warm(("reset",), jax.jit(
                _reset, donate_argnums=(0, 1), keep_unused=True,
                out_shardings=pool_sh), kp, vp)
            kp, vp = _warm(("scrub",), jax.jit(
                _scrub, donate_argnums=(0, 1), out_shardings=pool_sh),
                kp, vp, ids0)

            if self.role == "prefill":
                # page export: gather one slot's pages out of the pool
                # (read-only — the pool stays donated to the serve path)
                from ..ops.kv_cache import gather_pages

                def _extract(k, v, ids):
                    return gather_pages(k, ids), gather_pages(v, ids)

                _warm(("extract",), jax.jit(_extract), kp, vp, ids0)
            if self.role == "decode":
                # page attach: scatter an inbound transfer's rows into
                # freshly-allocated pages in ONE donated dispatch
                from ..ops.kv_cache import set_pages

                def _attach(k, v, ids, kpay, vpay):
                    return set_pages(k, ids, kpay), set_pages(v, ids, vpay)

                kp, vp = _warm(
                    ("attach",), jax.jit(_attach, donate_argnums=(0, 1)),
                    kp, vp, ids0, self._zero_payload(kp),
                    self._zero_payload(vp))

            if self._draft_program is not None:
                kp, vp = self._load_spec(_warm, params, kp, vp)
        misses = len(built)
        hits = len(self._compiled) - misses
        self.metrics.inc("bundle_hits", hits)
        self.metrics.inc("bundle_misses", misses)
        self.metrics.inc("warmup_seconds_total", self.clock() - t0)

        self._cache = (kp, vp)
        with self._lock:
            self._refresh_pool_gauges_locked()
        return misses

    def _load_spec(self, _warm, params, kp, vp):
        """Warm the speculative-decoding executables: the draft pool's
        prefill/step/reset/scrub (draft dims, SAME page table), the
        target's fixed-[S, k+1] ``spec_step`` verify, and the
        propose/accept samplers — all AOT, all fixed-shape (k is frozen
        at construction), so speculation adds zero serve-time compiles.
        Returns the threaded target pool (spec_step donates it)."""
        import jax

        from ..ops.kv_cache import alloc_pools, scrub_pool

        prog, dprog = self.program, self._draft_program
        dparams = self._draft_params
        s_n, pps, v_n = self.max_slots, prog.pages_per_slot, prog.vocab_size
        k = self.speculate_k
        dkp, dvp = alloc_pools(dprog, self.total_pages, self._kv_dtype)
        table0 = np.zeros((s_n, pps), np.int32)
        ids0 = np.zeros((pps,), np.int32)
        zj = np.zeros((s_n,), np.int32)
        active0 = np.zeros((s_n,), bool)

        dp_jit = jax.jit(dprog.prefill, donate_argnums=(1, 2))
        for b in self.prompt_buckets:
            dkp, dvp, _ = _warm(
                ("draft_prefill", b), dp_jit, dparams, dkp, dvp, ids0,
                np.zeros((b,), np.int32), np.int32(1))
        if self._prefix_on:
            dpa_jit = jax.jit(dprog.prefill_at, donate_argnums=(1, 2))
            for b in self.prompt_buckets:
                dkp, dvp, _ = _warm(
                    ("draft_prefill_at", b), dpa_jit, dparams, dkp, dvp,
                    ids0, np.zeros((b,), np.int32), np.int32(1),
                    np.int32(0))

        dkp, dvp, dlgs = _warm(
            ("draft_step",), jax.jit(dprog.step, donate_argnums=(1, 2)),
            dparams, dkp, dvp, table0, zj, zj, active0)
        kp, vp, tlgs = _warm(
            ("spec_step",), jax.jit(prog.spec_step, donate_argnums=(1, 2)),
            params, kp, vp, table0, np.zeros((s_n, k + 1), np.int32), zj,
            active0)

        propose, accept = _make_spec_fns(v_n, k)
        zt = np.zeros((s_n,), np.float32)
        zp = np.ones((s_n,), np.float32)
        zs = np.zeros((s_n,), np.uint32)
        _warm(("propose",), jax.jit(propose), dlgs, zt, zj, zp, zs, zj)
        _warm(("spec_accept",), jax.jit(accept), tlgs,
              np.zeros((s_n, k), np.int32),
              np.zeros((s_n, k, v_n), np.float32), zt, zj, zp, zs, zj)

        def _dreset(dk, dv):
            import jax.numpy as jnp
            z = jax.tree_util.tree_map(jnp.zeros_like, (dk, dv))
            return z[0], z[1]

        def _dscrub(dk, dv, ids):
            return scrub_pool(dk, ids), scrub_pool(dv, ids)

        dkp, dvp = _warm(("draft_reset",), jax.jit(
            _dreset, donate_argnums=(0, 1), keep_unused=True), dkp, dvp)
        dkp, dvp = _warm(("draft_scrub",), jax.jit(
            _dscrub, donate_argnums=(0, 1)), dkp, dvp, ids0)

        self._draft_cache = (dkp, dvp)
        return kp, vp

    def _slot_arg(self, i: int) -> tuple:
        """What ``prefill`` / ``prefill_at`` take after the contract's
        arguments: the slot's index where the program keeps per-slot
        state (its chunk carries that slot's on), nothing otherwise."""
        return (np.int32(i),) if self._slot_state else ()

    def _zero_payload(self, pool):
        """A zero host-side payload with the shape
        ``gather_pages(pool, ids)`` produces for a full pages-per-slot id
        vector — the AOT lowering specimen for the attach executable
        (handles both the f32 pool and the int8 QuantPages pair)."""
        import jax
        pps = self.program.pages_per_slot
        return jax.tree_util.tree_map(
            lambda a: np.zeros((a.shape[0], pps) + tuple(a.shape[2:]),
                               a.dtype), pool)

    def save_warmup_bundle(self, path: str) -> str:
        """Export every serve-path executable as a warmup bundle
        (serving/warmcache.py) so a fresh process — a scaled-up decode
        host, a respawn — deserializes in milliseconds via
        ``load(warm_bundle=path)`` instead of paying the XLA compiles.
        Sharded (tp > 1) engines pin the mesh topology into the bundle
        fingerprint — a differently-meshed process recompiles."""
        from .warmcache import save_bundle
        if not self._loaded:
            raise RuntimeError("load() the engine before bundling")
        entries = {":".join(str(p) for p in key): exe
                   for key, exe in self._compiled.items()}
        mesh = self._mesh if getattr(self.program, "tp", 1) > 1 else None
        return save_bundle(path, self._serve_tag, entries, mesh=mesh)

    def compile_cache_size(self) -> int:
        """Executables backing the serve path.  Must not grow after
        ``load()`` while serving — watched by ``continuous_batching_ab``."""
        return len(self._compiled)

    @property
    def current_tag(self) -> str:
        with self._lock:
            return self._serve_tag

    # -- request path ------------------------------------------------------

    def generate_async(self, prompt_ids, *, max_new_tokens: Optional[int] = None,
                       temperature: float = 0.0, top_k: int = 0,
                       top_p: float = 1.0, seed: int = 0,
                       slo_ms: Optional[float] = None,
                       deadline: Optional[float] = None,
                       echo_logits: bool = False,
                       echo_state: bool = False,
                       model: Optional[str] = None,
                       tenant: Optional[str] = None) -> Future:
        """Enqueue one generation; the Future resolves to a
        ``GenerationResult`` (or a typed serving error).  Joins the
        running decode batch at the next step boundary.  ``echo_state``
        asks for the slot's per-slot state at the answer's end
        (``GenerationResult.slot_state``; a program that keeps none
        gives None).  ``model``
        routes to a placed named model (``add_model``; None = the
        default); ``tenant`` tags the request for fair-share scheduling
        and quota accounting."""
        if not self._loaded:
            raise RuntimeError("DecodeEngine.load() must run before generate")
        if model is not None:
            with self._lock:
                if model not in self._model_tags:
                    f: Future = Future()
                    f.set_exception(ModelNotLoadedError(
                        f"model {model!r} is not placed on this decode "
                        "host"))
                    return f
        if self.role == "decode":
            raise RuntimeError(
                "decode-role host accepts page handoffs (continue_async), "
                "not raw prompts — route prompts at a prefill or unified "
                "host")
        prog = self.program
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.shape[0] < 1 or prompt.shape[0] > self.max_prompt:
            raise ValueError(
                f"prompt length {prompt.shape[0]} outside [1, "
                f"{self.max_prompt}] (largest warmed bucket, < max_len "
                f"{prog.max_len})")
        if prompt.min() < 0 or prompt.max() >= prog.vocab_size:
            raise ValueError(f"prompt ids outside [0, {prog.vocab_size})")
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.default_max_new)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        max_new = min(max_new, prog.max_len - int(prompt.shape[0]))
        if temperature < 0:
            raise ValueError("temperature must be >= 0 (0 = greedy)")
        if not (0 <= top_k <= prog.vocab_size):
            raise ValueError(f"top_k outside [0, {prog.vocab_size}]")
        if not (0 < top_p <= 1.0):
            raise ValueError("top_p must be in (0, 1]")
        spec = _GenSpec(prompt=prompt, max_new=max_new,
                        temperature=float(temperature), top_k=int(top_k),
                        top_p=float(top_p), seed=int(seed),
                        echo_logits=bool(echo_logits),
                        request_id=next(self._request_ids),
                        echo_state=bool(echo_state))
        return self.batcher.submit_request(spec, slo_ms=slo_ms,
                                           deadline=deadline,
                                           tenant=tenant, model=model)

    def generate(self, prompt_ids, **kw) -> GenerationResult:
        """Blocking ``generate_async``."""
        return self.generate_async(prompt_ids, **kw).result()

    def continue_async(self, handoff: PrefillHandoff, *,
                       slo_ms: Optional[float] = None,
                       deadline: Optional[float] = None,
                       tenant: Optional[str] = None) -> Future:
        """Enqueue the DECODE stage of a disaggregated generation:
        attach the prefill host's exported KV pages, then stream tokens
        from the already-sampled first token.  Only valid on a
        ``role="decode"`` engine.  Resolves to the same
        ``GenerationResult`` a unified engine would produce — seeded
        counter-based sampling continues at step 1, so the token
        sequence is bit-identical."""
        if not self._loaded:
            raise RuntimeError("DecodeEngine.load() must run before "
                               "continue_async")
        if self.role != "decode":
            raise RuntimeError(
                "continue_async needs a role='decode' engine "
                f"(this one is {self.role!r})")
        prog = self.program
        prompt = np.asarray(handoff.prompt, np.int32).reshape(-1)
        n = int(prompt.shape[0])
        if n < 1 or n >= prog.max_len:
            raise ValueError(
                f"handoff prompt length {n} outside [1, {prog.max_len})")
        if prompt.min() < 0 or prompt.max() >= prog.vocab_size:
            raise ValueError(f"prompt ids outside [0, {prog.vocab_size})")
        if not 0 <= int(handoff.first_token) < prog.vocab_size:
            raise ValueError(
                f"handoff first_token {handoff.first_token} outside "
                f"[0, {prog.vocab_size})")
        max_new = max(1, min(int(handoff.max_new), prog.max_len - n))
        spec = _HandoffSpec(
            prompt=prompt, max_new=max_new,
            temperature=float(handoff.temperature),
            top_k=int(handoff.top_k), top_p=float(handoff.top_p),
            seed=int(handoff.seed),
            echo_logits=bool(handoff.echo_logits),
            request_id=next(self._request_ids), handoff=handoff)
        return self.batcher.submit_request(spec, slo_ms=slo_ms,
                                           deadline=deadline, tenant=tenant)

    # -- hot-swap ----------------------------------------------------------

    def _check_params_compat(self, params, tag: str) -> None:
        """Incoming params must match the loaded shapes/dtypes — the
        AOT executables are shared across every version and named
        model on this engine."""
        import jax

        ref = self._versions[self._serve_tag]
        try:
            mismatch = jax.tree_util.tree_map(
                lambda a, b: (np.shape(a) != np.shape(b)
                              or np.asarray(a).dtype != np.asarray(b).dtype),
                ref, params)
        except ValueError as e:
            raise ValueError(f"incoming model {tag!r} has a different "
                             f"parameter tree: {e}") from e
        if any(jax.tree_util.tree_leaves(mismatch)):
            raise ValueError(
                f"incoming model {tag!r} has mismatched parameter "
                "shapes/dtypes — decode versions must share the compiled "
                "executables")

    def swap_model(self, model, tag: str,
                   name: Optional[str] = None) -> None:
        """Flip the version NEW admissions decode under; in-flight slots
        finish under the version that prefilled them (the step runs per
        distinct active tag), so no request mixes versions and nothing
        drains.  ``name`` scopes the flip to one placed named model
        (swaps never cross models/tenants); None flips the default."""
        params = getattr(model, "params", model)
        self._check_params_compat(params, tag)
        with self._lock:
            if name is not None:
                if name not in self._model_tags:
                    raise ModelNotLoadedError(
                        f"model {name!r} is not placed on this decode host")
                self._versions[tag] = params
                self._model_tags[name] = tag
            else:
                self._versions[tag] = params
                self._serve_tag = tag
        self.metrics.inc("swaps")
        obs_trace.instant("serve/swap", cat="serve", incoming=tag,
                          kind="decode", model=name)

    # -- multi-model placement ---------------------------------------------

    def add_model(self, name: str, model,
                  tag: Optional[str] = None) -> "DecodeEngine":
        """Place a NAMED model alongside the default: its param tree
        must be shape/dtype-compatible with the loaded decode program
        (same vocab, max_len, page layout — the compiled step/prefill
        executables are shared, so placement costs a params residency,
        not a compile).  New generations route with
        ``generate_async(model=name)``."""
        if not name:
            raise ValueError("model name must be non-empty")
        params = getattr(model, "params", model)
        tag = tag or f"{name}:v0"
        self._check_params_compat(params, tag)
        with self._lock:
            if name in self._model_tags:
                raise ValueError(f"model {name!r} is already placed")
            self._versions[tag] = params
            self._model_tags[name] = tag
            self._model_last_used[name] = self.clock()
        self.metrics.inc("model_loads")
        obs_trace.instant("serve/model_load", cat="serve", model=name,
                          tag=tag, kind="decode")
        return self

    def add_model_from_registry(self, registry, name: str,
                                ref: str = "prod", *,
                                subscribe: bool = False) -> "DecodeEngine":
        """Registry-backed :meth:`add_model` (tag = ``name:vN``).
        ``subscribe=True`` follows alias moves with per-model swaps —
        leave False under a placement controller."""
        version, model = registry.resolve(name, ref)
        self.add_model(name, model, tag=f"{name}:v{version}")
        if subscribe:
            registry.subscribe(
                name, ref,
                lambda ver, m: self.swap_model(m, f"{name}:v{ver}",
                                               name=name))
        return self

    def remove_model(self, name: str) -> bool:
        """Evict a named model: unroute it (queued requests fail typed
        at admission → the fleet re-routes).  In-flight slots finish
        under their own tag — the params stay resident until the last
        such slot completes (version GC), so eviction never strands a
        generation or mixes versions.  Returns False if not placed."""
        with self._lock:
            tag = self._model_tags.pop(name, None)
            self._model_last_used.pop(name, None)
            if tag is None:
                return False
            live = {sl.tag for sl in self._slots if sl is not None}
            live.add(self._serve_tag)
            live.update(self._model_tags.values())
            if tag not in live:
                del self._versions[tag]
        self.metrics.inc("model_evictions")
        obs_trace.instant("serve/model_evict", cat="serve", model=name,
                          tag=tag, kind="decode")
        return True

    def has_model(self, name: Optional[str]) -> bool:
        """True when this engine currently decodes ``name`` (None — the
        default model — always)."""
        if name is None:
            return True
        with self._lock:
            return name in self._model_tags

    def placed_models(self) -> Dict[str, str]:
        """name → serve tag for every model this engine decodes (the
        default under "")."""
        with self._lock:
            out = {"": self._serve_tag}
            out.update(self._model_tags)
            return out

    def model_last_used(self, name: str) -> Optional[float]:
        """Engine-clock stamp of the last admission for a named model
        (None = never, or not placed) — the placement controller's
        idle-eviction signal."""
        with self._lock:
            return self._model_last_used.get(name)

    def attach_registry(self, registry, name: str,
                        alias: str = "prod") -> "DecodeEngine":
        """Serve (name, alias) from a ModelRegistry and follow every
        ``set_alias`` move with a no-drain ``swap_model``."""
        version, model = registry.resolve(name, alias)
        self.swap_model(model, f"{name}:v{version}")
        registry.subscribe(
            name, alias,
            lambda ver, mod: self.swap_model(mod, f"{name}:v{ver}"))
        return self

    # -- decode loop -------------------------------------------------------

    def _start_loop(self) -> None:
        with self._lock:
            self._generation += 1
            gen = self._generation
            self._thread = threading.Thread(
                target=self._loop, args=(gen,),
                name=f"decode-loop-{gen}", daemon=True)
            self._thread.start()

    def enable_autoscale(self, on_scale, autoscaler=None, *,
                         min_replicas: int = 1, max_replicas: int = 4,
                         interval_s: float = 0.25,
                         **knobs) -> "DecodeEngine":
        """Arm the load controller over the decode queue.  Unlike the
        predict engine, decode slot capacity is COMPILE-SHAPE-FIXED
        (the step executable is compiled for ``max_slots``), so the
        actuator is a callback, not an in-process replica birth: the
        fleet tier owns physical decode scaling (a new `serve` host
        warming from this engine's warmup bundle — docs/SERVING.md
        "Cold start & autoscaling").  ``on_scale(delta, replicas)`` is
        called with +1/-1 and the new logical replica count; spans and
        scale counters are emitted here either way."""
        from .autoscale import ReplicaAutoscaler
        if autoscaler is None:
            autoscaler = ReplicaAutoscaler(
                min_replicas=int(min_replicas),
                max_replicas=int(max_replicas),
                clock=self.clock, **knobs)
        self._autoscale_interval_s = float(interval_s)
        self._shed_seen = self.metrics.counter_value("shed")
        self._autoscale_cb = on_scale
        self._autoscaler = autoscaler
        return self

    def _autoscale_tick(self) -> None:
        a = self._autoscaler
        if a is None or not self._loaded or self._shutdown:
            return
        now = self.clock()
        if (self._last_autoscale_t is not None
                and now - self._last_autoscale_t < self._autoscale_interval_s):
            return
        self._last_autoscale_t = now
        shed = self.metrics.counter_value("shed")
        shed_delta = shed - self._shed_seen
        self._shed_seen = shed
        with self._lock:
            active = sum(1 for s in self._slots if s is not None)
        decision = a.observe(self.batcher.qsize(), active,
                             self._logical_replicas,
                             shed_delta=int(shed_delta))
        if decision == 0:
            return
        self._logical_replicas += decision
        if decision > 0:
            with obs_trace.span("serve/scale_up", cat="serve",
                                kind="decode",
                                replicas=self._logical_replicas):
                self._autoscale_cb(1, self._logical_replicas)
            self.metrics.inc("scale_ups")
        else:
            with obs_trace.span("serve/scale_down", cat="serve",
                                kind="decode",
                                replicas=self._logical_replicas):
                self._autoscale_cb(-1, self._logical_replicas)
            self.metrics.inc("scale_downs")

    def _supervise(self) -> None:
        """Respawn the decode loop if it dies outright (a crash its own
        handler could not absorb) — in-flight requests are retried or
        failed, never stranded."""
        while not self._stop.wait(0.05):
            with self._lock:
                if self._shutdown:
                    return
                t = self._thread
            self._autoscale_tick()
            if t is not None and not t.is_alive():
                obs_trace.instant("serve/replica_crash", cat="serve",
                                  kind="decode_loop_dead")
                self.metrics.inc("replica_crashes")
                self._drain_crashed(ReplicaCrashError(
                    "decode loop thread died"))
                with self._lock:
                    if self._shutdown:
                        return
                self.metrics.inc("replica_respawns")
                self._start_loop()

    def _loop(self, gen: int) -> None:
        if self.role == "prefill":
            # Prefill hosts are throughput-oriented: drop the loop
            # thread to lowest scheduling priority so a co-located
            # decode-role host keeps its inter-token latency through
            # prompt bursts (TTFT of queued prefills is the explicit
            # trade).  On a dedicated prefill machine there is no
            # competitor and this changes nothing; a thread may always
            # raise its own nice value on Linux.
            try:
                os.setpriority(os.PRIO_PROCESS,
                               threading.get_native_id(), 19)
            except (AttributeError, OSError):  # pragma: no cover
                pass
        while True:
            with self._lock:
                leave = self._shutdown or gen != self._generation
            if leave:
                self._chunk_inflight, self._flight = [], None
                self._flush_echo()          # answers already finished
                return
            # everything this thread does is under serve/iteration, so
            # no device gap is left without a span of the program's
            with obs_trace.span("serve/iteration", cat="serve") as it:
                try:
                    worked = self._turn(it)
                except Exception as e:
                    obs_trace.instant("serve/replica_crash", cat="serve",
                                      kind="decode_step",
                                      error=type(e).__name__)
                    self.metrics.inc("replica_crashes")
                    self._drain_crashed(e)
                    worked = True       # recovery was work: do not park
                it.set(worked=bool(worked))
                if not worked:
                    it.drop()   # an idle engine must not fill the ring
            if not worked:
                self.batcher.wait_for_work(0.05)

    def _turn(self, it) -> bool:
        """One turn of the loop: one admission round, the turn's prefill
        chunks (``_chunk_budget()``: one for each slot mid-prefill, so a
        decode dispatch never waits behind more than that many), and the
        decode dispatches with their reads (``_step_once``).  Where the
        chunks stand to the dispatch is ``_chunks_ride_behind``'s to
        say; False when the turn found nothing to do."""
        worked = self._admit_some(it)
        if self._draft_program is not None:
            return self._spec_step_once() or worked
        behind = self._chunks_ride_behind()
        if not behind:
            # each chunk waited for, and a final chunk's slot steps with
            # this turn's dispatch
            worked = self._turn_chunks() > 0 or worked
        old = len(self._chunk_inflight)
        stepped = self._step_once()
        if behind:
            # the chunks an earlier turn left running lie before this
            # turn's steps on the device: they are done, and reading them
            # costs no wait
            self._chunk_settle(old)
            new = self._chunk_inflight
            if new:
                # they are still running: this dispatch's rows land now
                self._flush_echo()
                # a final chunk's first token joins the next step: wait
                # as far as the last of them.  Nothing of the chunks
                # behind it is needed before the next dispatch, so that
                # is queued behind them with the device still busy
                self._chunk_settle(max(
                    (k + 1 for k, c in enumerate(new) if c.last), default=0))
            elif not stepped:
                # no dispatch to queue them behind, and nothing else will
                # cover the rows and results still owed
                self._flush_echo()
                worked = self._turn_chunks() > 0 or worked
        return stepped or worked

    def _chunks_ride_behind(self) -> bool:
        """The schedule's second predicate: whether a turn's chunks are
        queued BEHIND its decode dispatch, back to back, and read after
        it (as far as the last final chunk), or run before it, each
        waited for.  Behind ⇔ ``decode_horizon > 1``: a fused dispatch
        gives the host H tokens a slot to read back and record, and the
        device goes from the steps into the chunks meanwhile; a single
        step's read is short, and its successor is what the device has
        waiting (``_step_once``), so the chunks go first and a prompt
        whose final chunk ran decodes with this very turn's step."""
        return self.decode_horizon > 1

    # -- radix prefix cache (host-side trie; loop thread + _lock) ----------

    def _iter_trie(self):
        stack = list(self._prefix_root.children.values())
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            yield nd

    def _prefix_lookup(self, prompt: np.ndarray) -> List[_PrefixNode]:
        """Longest page-aligned prefix match, capped at (n-1)//page_size
        pages so the suffix prefill always has >= 1 real token (the
        last prompt token's logits seed the first sample)."""
        ps = self.program.page_size
        cap = (int(prompt.shape[0]) - 1) // ps
        node, nodes = self._prefix_root, []
        for j in range(cap):
            child = node.children.get(
                tuple(int(x) for x in prompt[j * ps:(j + 1) * ps]))
            if child is None:
                break
            nodes.append(child)
            node = child
        return nodes

    def _prefix_insert(self, s: _Slot, now: float) -> None:
        """Move the pages fully covered by ``s``'s prompt (beyond the
        matched prefix) from the slot's private list into the trie,
        refcount 1 (held by ``s`` until finish).  Runs on the loop
        thread right after a FINITE first sampled token, so the trie
        never holds rows from a poisoned prefill, and before any later
        admission — a same-prompt request in the same admit batch hits.
        Pages fully covered by the prompt are never written again (the
        first generated token lands at position n_prompt), so inserted
        pages are immutable from this point on."""
        ps = self.program.page_size
        prompt = s.spec.prompt
        node = s.shared_nodes[-1] if s.shared_nodes else self._prefix_root
        inserted = 0
        for j in range(s.n_matched, int(prompt.shape[0]) // ps):
            key = tuple(int(x) for x in prompt[j * ps:(j + 1) * ps])
            if key in node.children:
                # the match was suffix-capped below an existing node —
                # our duplicate page stays private, stop extending
                break
            child = _PrefixNode(key, s.page_ids.pop(0), node)
            child.refs = 1
            child.last_used = now
            node.children[key] = child
            s.shared_nodes.append(child)
            node = child
            inserted += 1
        if inserted:
            self._trie_pages += inserted
            self.metrics.inc("prefix_inserts", inserted)
            self.metrics.shared_pages.set(self._trie_pages)

    def _prefix_evict(self, need: int) -> int:
        """LRU eviction of refcount-zero LEAF nodes (a refs-0 node's
        children are refs-0 too, so leaves free first and parents become
        evictable as their subtree drains).  Evicted pages return to the
        free list WITHOUT a scrub: trie rows were validated finite at
        insert, and garbage-but-finite freed pages are the pool-wide
        convention.  ``last_used`` comes from the injectable engine
        clock (GC201)."""
        import heapq
        heap = [(nd.last_used, nd.page_id, nd) for nd in self._iter_trie()
                if nd.refs <= 0 and not nd.children]
        heapq.heapify(heap)
        freed = 0
        while heap and freed < need:
            _, _, nd = heapq.heappop(heap)
            if nd.children or nd.refs > 0 or nd.detached:
                continue
            nd.parent.children.pop(nd.key, None)
            nd.detached = True
            self._trie_pages -= 1
            self._free_pages.append(nd.page_id)
            freed += 1
            p = nd.parent
            if p is not self._prefix_root and p.refs <= 0 and not p.children:
                heapq.heappush(heap, (p.last_used, p.page_id, p))
        if freed:
            self.metrics.inc("prefix_evictions", freed)
            self.metrics.shared_pages.set(self._trie_pages)
        return freed

    def _debug_page_state(self) -> dict:
        """Diagnostic partition of page ids 1..total_pages-1: every page
        is exactly one of free / slot-private / trie-resident (the
        accounting invariant the hardening tests assert)."""
        with self._lock:
            return {
                "free": sorted(self._free_pages),
                "private": sorted(p for s in self._slots if s is not None
                                  for p in s.page_ids),
                "trie": sorted(nd.page_id for nd in self._iter_trie()),
            }

    def _admit_some(self, turn=None) -> bool:
        """Join queued requests to the running batch: allocate pages +
        a slot (attaching the longest matching prefix read-only when the
        prefix cache is on), prefill, sample the first token (TTFT).
        Stops at the first request the pool cannot hold yet (FIFO order
        preserved).  With a ``prefill_chunk`` a round's prompt tokens
        are budgeted: one chunk's worth for each free slot, none
        otherwise.  ``turn`` is the loop's ``serve/iteration`` span: a
        round that the token budget ended with slots free and requests
        waiting is counted there and in ``admit_rounds_budget_bound``."""
        from ..ops.kv_cache import pages_for

        with self._lock:
            free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return False
        # chunked prefill's batch-formation rule: a round takes in one
        # chunk's worth of prompt tokens for EACH FREE SLOT, the pace at
        # which the loop reads (``_chunk_budget``: a chunk a turn for
        # every slot mid-prefill), so short prompts fill every slot that
        # stands free in one round.  What it still ends is a round whose
        # long prompts would take more chunks than the slots they fill
        # give the loop: a 2,048-token head on one free slot is admitted
        # (the head always is), a second one behind it waits a turn
        budget = (None if self.prefill_chunk is None
                  else self.prefill_chunk * len(free))
        reqs = self.batcher.admit(len(free), token_budget=budget)
        if self.batcher.last_admit_budget_bound:
            # it counts; it changes no decision
            self.metrics.inc("admit_rounds_budget_bound")
            if turn is not None:
                turn.set(admit_budget_bound=True, free_slots=len(free),
                         admitted=len(reqs))
        if not reqs:
            return False
        prog = self.program
        leftovers: List[Any] = []
        worked = False
        for r in reqs:
            if leftovers:           # keep FIFO once one request stalls
                leftovers.append(r)
                continue
            spec = r.payload
            handoff = getattr(spec, "handoff", None)
            transfer = None
            with self._lock:
                if r.model is None:
                    slot_tag = self._serve_tag
                else:
                    slot_tag = self._model_tags.get(r.model)
                    if slot_tag is not None:
                        self._model_last_used[r.model] = self.clock()
            if slot_tag is None:
                # evicted between admission and slot assignment: typed
                # failure, retryable at fleet level (demand reload)
                self.metrics.inc("errors")
                _fail_safe(r.future, ModelNotLoadedError(
                    f"model {r.model!r} was evicted from this decode host"))
                continue
            if handoff is not None:
                try:
                    # validate BEFORE any allocation: a corrupt transfer
                    # fails typed with the free list untouched
                    transfer = self._check_handoff(spec, handoff)
                except ValueError as e:
                    self.metrics.inc("errors")
                    _fail_safe(r.future, e)
                    continue
            # one request's admission, from the page count to the slot's
            # assignment; the prefill that follows is its sibling
            with obs_trace.span("serve/admit", cat="serve",
                                request_id=spec.request_id) as sp:
                if self.role == "prefill":
                    # a prefill host never decodes — the slot only needs
                    # the prompt's pages, exported and freed at handoff
                    need_total = pages_for(int(spec.prompt.shape[0]),
                                           prog.page_size)
                else:
                    max_total = min(
                        int(spec.prompt.shape[0]) + spec.max_new,
                        prog.max_len)
                    need_total = pages_for(max_total, prog.page_size)
                with self._lock:
                    if not free:
                        leftovers.append(r)
                        sp.set(requeued=True)
                        continue
                    matched = (self._prefix_lookup(spec.prompt)
                               if self._prefix_on else [])
                    m = len(matched)
                    need = need_total - m
                    if len(self._free_pages) < need:
                        self._prefix_evict(need - len(self._free_pages))
                    if len(self._free_pages) < need:
                        # no incref has happened yet, so a requeued
                        # request holds nothing — re-admission matches
                        # afresh (the no-double-decref-by-construction
                        # invariant)
                        leftovers.append(r)
                        sp.set(requeued=True)
                        continue
                    i = free.pop(0)
                    now = self.clock()
                    for nd in matched:
                        nd.refs += 1
                        nd.last_used = now
                    ids = [self._free_pages.popleft() for _ in range(need)]
                    self._page_table[i] = 0
                    self._page_table[i, :m] = [nd.page_id for nd in matched]
                    self._page_table[i, m:m + need] = ids
                    slot = _Slot(r, slot_tag, ids, spec.max_new)
                    slot.shared_nodes = matched
                    slot.n_matched = m
                    self._slots[i] = slot
                    self._refresh_pool_gauges_locked()
                queue_wait_ms = (now - r.t_submit) * 1e3
                self.metrics.queue_wait.record(queue_wait_ms)
                if self._prefix_on:
                    if m:
                        self.metrics.inc("prefix_hits")
                        self.metrics.inc("prefix_hit_tokens",
                                         m * prog.page_size)
                    else:
                        self.metrics.inc("prefix_misses")
                sp.set(slot=i, queue_wait_ms=round(queue_wait_ms, 3),
                       pages_reserved=need_total, matched_pages=m,
                       matched_tokens=m * prog.page_size)
            self.metrics.inc("requests")
            if transfer is not None:
                self._attach_handoff(i, transfer)
            elif self.role == "prefill":
                self._prefill_export(i)
            elif self.prefill_chunk is not None:
                # defer to the chunk loop: the slot holds its pages but
                # is not steppable until the last chunk samples token 0
                slot.n_prefilled = m * prog.page_size
            else:
                self._prefill_slot(i)
            worked = True
        for r in reversed(leftovers):
            self.batcher.requeue_front(r)
        return worked

    def _check_handoff(self, spec, handoff):
        """Unpack + shape-check an inbound transfer against THIS pool's
        layout (layers / page dims / kv dtype).  Raises ``ValueError``
        on any mismatch or corruption — called before page allocation so
        failure leaves the free list and page table untouched."""
        import jax

        from ..ops.kv_cache import pages_for, unpack_transfer

        transfer = unpack_transfer(handoff.pages)
        want = pages_for(int(spec.prompt.shape[0]), self.program.page_size)
        if transfer.n_pages != want:
            raise ValueError(
                f"handoff carries {transfer.n_pages} pages; a prompt of "
                f"{int(spec.prompt.shape[0])} tokens needs {want}")
        kp, _ = self._cache
        ref = jax.tree_util.tree_leaves(kp)
        got = jax.tree_util.tree_leaves(transfer.k)
        if len(ref) != len(got) or any(
                tuple(g.shape[2:]) != tuple(a.shape[2:])
                or g.dtype != a.dtype or g.shape[0] != a.shape[0]
                or g.shape[1] != transfer.n_pages
                for g, a in zip(got, ref)):
            raise ValueError(
                "handoff page payload does not match this engine's pool "
                "layout (n_layers / page dims / kv_dtype)")
        return transfer

    def _bucket_for(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        return self.prompt_buckets[-1]

    def _prefill_slot(self, i: int) -> None:
        """Admission's prefill of a whole prompt: ONE chunk, the part of
        it no prefix match covers, dispatched, waited for and committed
        (first token, TTFT) before the next request is admitted."""
        self._chunk_run(self._chunk_of(i, self._slots[i], whole=True))

    def _chunk_budget(self) -> int:
        """How many prefill chunks this turn may take: as many as slots
        are mid-prefill at its start, one ``_chunk_pick`` each (so
        ``prefill_order`` alone says whose).  A prompt read at one chunk
        a turn whatever waits holds its slot for as many turns as it has
        chunks while every dispatch is paid in full; read at this pace
        the waiting prompts take one turn a chunk together.  What a
        decoding slot pays is bounded by the same count: the turn's
        dispatch and at most ``max_slots - 1`` chunks, and that many
        only when it is the one slot that decodes."""
        with self._lock:
            return len(self._mid_prefill())

    def _mid_prefill(self) -> List[int]:
        """The slots with a chunk still to pick (a prompt whose last
        chunk is on the device has none).  Caller holds ``_lock``."""
        return [i for i, s in enumerate(self._slots)
                if s is not None and s.n_prefilled is not None
                and s.n_prefilled < s.n_prompt]

    def _turn_chunks(self, behind=None) -> int:
        """Advance the chunked prefills by one turn: ``_chunk_budget()``
        chunks, one ``_chunk_pick`` each (at most ``prefill_chunk``
        prompt tokens through the ``prefill_at`` offset entry point),
        and count the turn.  Each is dispatched, waited for and
        committed before the next; or, given ``behind`` (the
        ``serve/decode_step`` span of the dispatch the device is busy
        with), they are queued behind it back to back, nothing read
        back, and left in ``_chunk_inflight`` for the turn to settle.
        Chunk rows attend over all earlier rows already in the pool
        (same per-row math as a cold prefill), so the final logits are
        bit-identical to an unchunked prefill of the whole prompt.
        Returns the chunks sent."""
        if self.prefill_chunk is None:
            return 0
        budget, k = self._chunk_budget(), 0
        for _ in range(budget):
            c = self._chunk_pick()
            if c is None:
                break
            if behind is None:
                self._chunk_run(c)
            else:
                with obs_trace.span("serve/prefill_dispatch", cat="serve",
                                    slot=c.i):
                    self._chunk_dispatch(c)
                self._chunk_inflight.append(c)
            k += 1
        if k:
            self.metrics.inc("chunk_turns")
            if k > 1:
                self.metrics.inc("chunk_turns_multi")
            if k > self.metrics.chunk_turn_max.value():
                self.metrics.chunk_turn_max.set(k)
        if behind is not None:
            # queued behind this dispatch, of the slots that were
            # mid-prefill
            behind.set(chunks=k, mid_prefill=budget)
        return k

    def _chunk_run(self, c: _Chunk) -> None:
        """One prompt program from dispatch to commit, inside its
        ``serve/prefill`` span."""
        with self._chunk_span(c) as sp:
            self._chunk_dispatch(c)
            self._chunk_wait(c, sp)
        self._chunk_commit(c)

    def _chunk_pick(self) -> Optional[_Chunk]:
        """The next chunk in ``prefill_order``, with its padded tokens.  A
        prompt whose deadline passed while it was being prefilled is
        given up first, as one still queued would be: it has no token to
        hand back, and its remaining chunks (up to seconds of the device
        for a long prompt) would serve nobody."""
        now = self.clock()
        with self._lock:
            pending = self._mid_prefill()
            late = [i for i in pending if now > self._slots[i].deadline]
        for i in late:
            s = self._slots[i]
            self._finish(i, now, error=DeadlineExceededError(
                f"deadline passed {s.n_prefilled} tokens into the prefill "
                f"of a {s.n_prompt}-token prompt"))
        with self._lock:
            pending = [i for i in pending if i not in late]
            if not pending:
                return None
            if self.prefill_order == "nearest_end":
                i = self._nearest_end(pending)
            else:
                start = self._chunk_cursor
                i = min(pending, key=lambda x: (x - start) % self.max_slots)
                self._chunk_cursor = (i + 1) % self.max_slots
            s = self._slots[i]
        return self._chunk_of(i, s)

    def _chunk_of(self, i: int, s: _Slot, whole: bool = False) -> _Chunk:
        """The slot's next dispatch of a prompt program, with its padded
        tokens: the next ``prefill_chunk`` tokens of a chunked prefill,
        or (``whole``) all of the prompt past its matched prefix."""
        c = _Chunk()
        c.i, c.slot, c.whole = i, s, whole
        if whole:
            c.offset = s.n_matched * self.program.page_size
            c.take = s.n_prompt - c.offset
        else:
            c.offset = s.n_prefilled
            c.take = min(self.prefill_chunk, s.n_prompt - c.offset)
        c.bucket = self._bucket_for(c.take)
        c.padded = np.zeros((c.bucket,), np.int32)
        c.padded[:c.take] = s.spec.prompt[c.offset:c.offset + c.take]
        c.last = c.offset + c.take >= s.n_prompt
        return c

    def _nearest_end(self, pending: List[int]) -> int:
        """Of the slots mid-prefill the one with the fewest prompt tokens
        left (the lowest slot among equals): its request decodes soonest,
        so fewer slots are held by half-read prompts.  A prompt passed
        over ``PASSED_OVER_ROUNDS * max_slots`` picks in a row goes
        first, the longest-waiting one: a full engine's round-robin gives
        every prompt a chunk each ``max_slots`` picks, this order at
        worst that many times later.  Caller holds ``_lock``."""
        slots = self._slots
        late = max(pending, key=lambda x: slots[x].passed_over)
        if slots[late].passed_over >= PASSED_OVER_ROUNDS * self.max_slots:
            i = late
        else:
            i = min(pending, key=lambda x: (
                slots[x].n_prompt - slots[x].n_prefilled, x))
        for x in pending:
            slots[x].passed_over = 0 if x == i else slots[x].passed_over + 1
        return i

    def _chunk_span(self, c: _Chunk):
        s = c.slot
        # a whole prompt's span carries its length; a chunk's, its own
        # tokens and where they start
        rows = ({"prompt_tokens": s.n_prompt} if c.whole
                else {"prompt_tokens": c.take, "offset": c.offset})
        return obs_trace.span("serve/prefill", cat="serve", slot=c.i,
                              bucket=c.bucket, model=s.tag,
                              request_id=s.spec.request_id, **rows)

    def _chunk_dispatch(self, c: _Chunk) -> None:
        """Queue the chunk's program (and, after a final chunk, the
        first token's sampler) on the device; nothing is read back.  A
        whole prompt from its row 0 runs ``prefill``; a chunk, and the
        suffix past a prefix-cache hit (the shared pages already hold
        the prefix rows and the suffix rows attend over them), runs
        ``prefill_at``: the same per-row math, so the logits are
        bit-identical.  A chunked slot's next chunk starts where this
        one ends, and may be picked and queued behind it before either
        is read: the device runs them in the order of their dispatch."""
        s, spec = c.slot, c.slot.spec
        key, at = (("prefill", ()) if c.whole and c.offset == 0
                   else ("prefill_at", (np.int32(c.offset),)))
        kp, vp = self._cache
        kp, vp, c.lg, *c.aux = self._compiled[(key, c.bucket)](
            self._versions[s.tag], kp, vp, self._page_table[c.i], c.padded,
            np.int32(c.take), *at, *self._slot_arg(c.i))
        self._cache = (kp, vp)
        if not c.whole:
            s.n_prefilled = c.offset + c.take
        if c.offset == 0 and self._slot_state:
            # the rows at offset 0 start the slot from zero state
            self.metrics.inc("recurrent_state_resets")
        if c.last:
            c.tok, c.fin = self._compiled[("sample1",)](
                c.lg, np.float32(spec.temperature), np.int32(spec.top_k),
                np.float32(spec.top_p), np.uint32(spec.seed), np.int32(0))
        if self._draft_program is not None:
            # mirror the prompt into the draft pool (same page ids, the
            # draft's dims) so proposals start from the right state
            dkp, dvp = self._draft_cache
            dkp, dvp, _ = self._compiled[("draft_" + key, c.bucket)](
                self._draft_params, dkp, dvp, self._page_table[c.i],
                c.padded, np.int32(c.take), *at)
            self._draft_cache = (dkp, dvp)

    def _chunk_wait(self, c: _Chunk, sp) -> None:
        """The blocking read-back of what the chunk left."""
        c.picks_h, c.rows_h = self._read_aux(
            sp, c.aux, c.last and c.slot.spec.echo_logits)
        if c.last:
            c.tok_h = int(np.asarray(c.tok))
            c.fin_h = bool(np.asarray(c.fin))
            c.lg_h = (np.asarray(c.lg) if c.slot.spec.echo_logits
                      else None)
        c.t1 = self.clock()

    def _chunk_commit(self, c: _Chunk) -> None:
        """Count the chunk; after a prompt's last one record its first
        token (TTFT): the slot becomes steppable."""
        s, i, t1 = c.slot, c.i, c.t1
        if not c.whole:
            self.metrics.inc("prefill_chunks")
        if not c.last:
            return
        if c.offset > s.n_matched * self.program.page_size:
            self.metrics.inc("chunked_prefills")   # took >= 2 chunks
        s.n_prefilled = None
        if c.picks_h is not None:
            s.picks.append(c.picks_h)
        s.rows_next = c.rows_h
        self._first_token(i, c.tok_h, c.fin_h, c.lg_h, t1)

    def _first_token(self, i: int, token: int, finite: bool,
                     logits_row: Optional[np.ndarray], t1: float) -> None:
        """A request's first token, from its prompt's last rows here or
        handed over by a prefill host: TTFT, and the slot decodes."""
        s = self._slots[i]
        self.metrics.inc("prefills")
        self.metrics.ttft.record((t1 - s.req.t_submit) * 1e3)
        s.t_first = t1
        if self._prefix_on and finite:
            # insert BEFORE recording the token so a same-prompt request
            # admitted next hits; gated on a finite first sample so a
            # poisoned prefill's rows never enter the trie
            with self._lock:
                self._prefix_insert(s, t1)
        self._record_token(i, token, finite, logits_row, t1)

    def _attach_handoff(self, i: int, transfer) -> None:
        """Decode-stage admission: scatter the prefill host's exported
        page payload into this slot's freshly-allocated private pages
        (rows below a local prefix match are deduped — they target the
        scratch page and the shared pages serve those rows), then record
        the already-sampled first token.  One AOT dispatch; position and
        sampling-step bookkeeping land exactly where a local prefill
        would have left them, so the continuation is bit-identical."""
        s = self._slots[i]
        h = s.spec.handoff
        pps = self.program.pages_per_slot
        m = s.n_matched
        p_pro = transfer.n_pages
        ids = np.zeros((pps,), np.int32)        # scratch: write discarded
        ids[m:p_pro] = self._page_table[i][m:p_pro]

        def _pad(side):
            import jax

            def one(a):
                full = np.zeros((a.shape[0], pps) + tuple(a.shape[2:]),
                                a.dtype)
                full[:, m:p_pro] = a[:, m:p_pro]
                return full
            return jax.tree_util.tree_map(one, side)

        with obs_trace.span("serve/prefill", cat="serve", slot=i, bucket=0,
                            prompt_tokens=s.n_prompt, model=s.tag,
                            attached_pages=p_pro - m,
                            request_id=s.spec.request_id):
            kp, vp = self._cache
            kp, vp = self._compiled[("attach",)](
                kp, vp, ids, _pad(transfer.k), _pad(transfer.v))
            self._cache = (kp, vp)
            t1 = self.clock()
        self.metrics.inc("handoffs_in")
        self.metrics.inc("pages_attached", p_pro - m)
        if m:
            self.metrics.inc("pages_deduped", m)
        lg_h = (np.asarray(h.logits0, np.float32)
                if s.spec.echo_logits and h.logits0 is not None else None)
        self._first_token(i, int(h.first_token), bool(h.finite), lg_h, t1)

    def _prefill_export(self, i: int) -> None:
        """Prefill-role terminal: run the standard prefill + first-token
        sample, then EXPORT the slot — gather the prompt's KV pages into
        a packed transfer, resolve the future with a
        ``PrefillHandoff``, and free the slot immediately (a prefill
        host never decodes).  A poisoned prefill is isolated HERE and
        never crosses the wire."""
        import jax

        from ..ops.kv_cache import PageTransfer, pack_transfer, pages_for

        s = self._slots[i]
        spec = s.spec
        n = s.n_prompt
        c = self._chunk_of(i, s, whole=True)
        with self._chunk_span(c) as sp:
            self._chunk_dispatch(c)
            self._chunk_wait(c, sp)
        tok_h, fin_h, t1 = c.tok_h, c.fin_h, c.t1
        self.metrics.inc("prefills")
        self.metrics.ttft.record((t1 - s.req.t_submit) * 1e3)
        s.t_first = t1
        if not fin_h:
            self.metrics.inc("poison_isolated")
            self._scrub_pages(s.page_ids)
            self._finish(i, t1, error=PoisonInputError(
                f"prefill produced non-finite logits (slot {i}) — "
                "handoff suppressed, request isolated"))
            return
        if self._prefix_on:
            with self._lock:
                self._prefix_insert(s, t1)
        p_pro = pages_for(n, self.program.page_size)
        k_pages, v_pages = self._compiled[("extract",)](
            *self._cache, self._page_table[i])
        k_np = jax.tree_util.tree_map(
            lambda a: np.asarray(a)[:, :p_pro].copy(), k_pages)
        v_np = jax.tree_util.tree_map(
            lambda a: np.asarray(a)[:, :p_pro].copy(), v_pages)
        payload = pack_transfer(PageTransfer(n_pages=p_pro, k=k_np, v=v_np))
        handoff = PrefillHandoff(
            prompt=spec.prompt, max_new=s.max_new,
            temperature=spec.temperature, top_k=spec.top_k,
            top_p=spec.top_p, seed=spec.seed,
            echo_logits=spec.echo_logits, first_token=tok_h, finite=True,
            n_pages=p_pro, pages=payload,
            logits0=c.lg_h.copy() if spec.echo_logits else None,
            model_tag=s.tag)
        self.metrics.inc("handoffs_out")
        self.metrics.inc("pages_exported", p_pro)
        now = self.clock()
        with self._lock:
            self._release_locked(i, s, now)
        _set_safe(s.req.future, handoff)
        obs_trace.complete_at("serve/request", s.req.t_submit, now,
                              cat="serve", kind="prefill_handoff",
                              tokens=1, finish="handoff",
                              request_id=spec.request_id)

    def _step_once(self) -> bool:
        """The decode part of a turn: per distinct live version tag ONE
        dispatch of that tag's slots (same executable, that tag's
        params: the no-version-mixing hot-swap invariant lives here),
        the turn's chunks behind the first of them where
        ``_chunks_ride_behind``, and the read of what is due.  False
        when there was nothing to dispatch or read.

        The schedule's first predicate, ``keep``: whether the new
        dispatch stays UNREAD until the next turn, which queues its
        successor behind it before reading it, so the device runs that
        while the host reads, records, finishes, admits and builds.
        Kept ⇔ ``decode_horizon == 1`` and one live tag: a single step's
        successor takes its input tokens from ONE sampler output on the
        device (``("join",)`` puts in the host's token of a slot that
        came from a prefill since), so with two tags alive each step is
        read before the next is queued (``step_drains``); and a fused
        dispatch is read in its own turn, with the turn's chunks to keep
        the device busy meanwhile.

        What the host knows without the read goes into a kept
        dispatch's successor (``_step_inputs``): a stepped slot's
        position and sampler counter are one further, and a slot whose
        budget ends with the step in flight is left out.  EOS, a passed
        deadline and non-finite logits are known only at the read: such
        a slot has then been stepped ONCE more.  That step's write lands
        at the request's own next row, inside the pages it reserved, and
        any later tenant's prefill, attach or scrub of those pages is
        queued behind it on the device; its token is dropped
        (``overrun_slot_steps``).  Host slot state changes only at a
        read, so a crash retries from the last recorded token.

        Spans: a kept dispatch's ``serve/step_build`` /
        ``serve/step_dispatch`` / ``serve/sample_dispatch`` lie under
        ``serve/iteration`` in the turn that queues it, and so do a
        drained step's; ``serve/decode_step`` is the dispatch that is
        READ, with its ``serve/step_wait`` and ``serve/step_record``.
        Where the chunks ride behind, the ``serve/decode_step`` of the
        dispatch opens before it is built and holds its dispatch spans
        and the chunks' ``serve/prefill_dispatch`` too, whose count it
        carries."""
        tags, crash = self._live_tags()
        behind = self._chunks_ride_behind()
        keep = self.decode_horizon == 1 and len(tags) == 1
        if crash and not (behind and tags):
            # the test hook: before a dispatch; behind a fused one, after
            # its read and before any commit (``_step_read``)
            raise ReplicaCrashError("injected decode-batch crash (test hook)")
        prev, self._flight = self._flight, None
        if prev is None and not tags:
            return False
        if keep:
            self._flight = self._step_dispatch(tags[0], prev)
        if prev is not None:
            self._step_read(prev)
        if keep:
            return True
        chunks_due = behind
        for tag in tags:
            with contextlib.ExitStack() as scope:
                sp = (scope.enter_context(self._step_span(tag))
                      if behind else None)
                f = self._step_dispatch(tag)
                if f is None:
                    continue
                if chunks_due:
                    chunks_due = False
                    self._turn_chunks(behind=sp)
                # the device is busy: the last dispatch's rows can land
                self._flush_echo()
                if not behind:
                    # a single step that two live versions keep from
                    # staying in flight
                    self.metrics.inc("step_drains")
                self._step_read(f, sp, crash)
        return True

    def _live_tags(self) -> tuple:
        """The version tags of the slots that can be stepped, in slot
        order, and the test hook's flag (``_crash_next``), taken."""
        with self._lock:
            tags: List[str] = []
            for s in self._slots:
                if (s is not None and s.n_prefilled is None
                        and s.tag not in tags):
                    tags.append(s.tag)
            crash, self._crash_next = self._crash_next, False
        return tags, crash

    def _step_span(self, tag: str):
        return obs_trace.span("serve/decode_step", cat="serve", model=tag)

    def _step_dispatch(self, tag: str, prev: Optional[_Flight] = None
                       ) -> Optional[_Flight]:
        """Queue one decode dispatch of ``tag``'s slots behind whatever
        the device holds (``prev``: the dispatch in flight, unread,
        whose tokens on the device are the inputs of the slots it
        steps), and start the transfers of what the read will want;
        nothing is waited for.  A horizon of 1 is the step, its sampler
        and, after ``prev``, the token join; a longer one is ONE
        ``("step_multi", H)`` executable, H steps and their sampling in
        a ``lax.scan`` of the step body: the ``fold_in(seed,
        token_index)`` keying makes its stream bit-identical to
        step-by-step, and a slot that ends mid-horizon (EOS, budget,
        poison) has its remaining writes routed to the scratch page on
        the device.  None where no slot is left to step."""
        inp = self._step_inputs(tag, prev)
        if inp is None:
            return None
        H = self.decode_horizon
        f = _Flight()
        f.inp, f.steps, f.ahead = inp, H, int(prev is not None)
        f.late = inp.echo and H > 1
        f.step_ms = f.sample_ms = None
        f.t0 = self.clock()
        kp, vp = self._cache
        with obs_trace.span("serve/step_dispatch", cat="serve"):
            if H > 1:
                eos = np.int32(self.eos_id if self.eos_id is not None else -1)
                kp, vp, f.toks, f.fin, lgs, *f.aux = \
                    self._compiled[("step_multi", H)](
                        inp.params, kp, vp, self._page_table, inp.toks_in,
                        inp.pos, inp.act, inp.temps, inp.tks, inp.tps,
                        inp.seeds, inp.steps, inp.budgets, eos,
                        np.arange(H, dtype=np.int32))
            else:
                toks_in = inp.toks_in
                if not inp.on_host[inp.group].all():
                    # a slot of the step in flight: its token is on the
                    # device
                    toks_in = self._compiled[("join",)](
                        prev.toks, toks_in, inp.on_host)
                kp, vp, lgs, *f.aux = self._compiled[("step",)](
                    inp.params, kp, vp, self._page_table, toks_in, inp.pos,
                    inp.act)
        if H == 1:
            t_step = self.clock()
            with obs_trace.span("serve/sample_dispatch", cat="serve"):
                f.toks, f.fin = self._compiled[("sample",)](
                    lgs, inp.temps, inp.tks, inp.tps, inp.seeds, inp.steps)
        self._cache = (kp, vp)
        # what the read waits for goes first, the bulk last
        f.lgs = lgs if inp.echo else None
        f.attn = f.aux[0].get("attn_rows") if f.aux else None
        for a in (f.toks, f.fin, *_leaves(f.aux)):
            if a is not f.attn:
                a.copy_to_host_async()
        if inp.echo:
            lgs.copy_to_host_async()
            if f.attn is not None:
                f.attn.copy_to_host_async()
        if H == 1:
            f.step_ms = (t_step - f.t0) * 1e3
            f.sample_ms = (self.clock() - t_step) * 1e3
        else:
            self.metrics.inc("fused_dispatches")
        self.metrics.inc("decode_steps")
        self._count_sorted(inp, steps=H)
        if prev is not None:
            self.metrics.inc("steps_ahead")
        return f

    def _step_read(self, f: _Flight, sp=None, crash: bool = False) -> None:
        """Read dispatch ``f`` back and record it, inside its
        ``serve/decode_step`` span (``sp`` where the turn opened it
        before the dispatch): the blocking read-back (the device works
        inside it, on ``f`` and on whatever was queued behind it) and
        the group's bookkeeping, every step's (token, finite) pair
        through ``_record_token`` exactly as so many single steps would
        have been.  Echoed logits are read here with the tokens, or
        (``f.late``) ride one dispatch behind: their transfer started at
        the dispatch, and ``_flush_echo`` copies the rows to the
        requests' buffers once the next dispatch keeps the device busy
        (or a chunk does, or the loop has nothing to step); an answer
        that echoes logits and finishes meanwhile is handed to its
        caller right after its last rows land."""
        inp = f.inp

        def by_step(a):     # [steps, slots, ...]: a single step has no axis
            return a if a is None or f.steps > 1 else a[None]

        with (self._step_span(inp.tag) if sp is None
              else contextlib.nullcontext(sp)) as sp:
            t_wait = self.clock()
            with obs_trace.span("serve/step_wait", cat="serve"):
                toks_h = by_step(np.asarray(f.toks))    # [steps, S]
                fin_h = by_step(np.asarray(f.fin))
                inline = inp.echo and not f.late
                lgs_h = by_step(np.asarray(f.lgs)) if inline else None
                picks_h, rows_h = self._read_aux(sp, f.aux, inline)
            t1 = self.clock()
            if crash:
                # "mid-horizon" from the host's view: the device has
                # advanced but NOTHING is committed — recovery must retry
                # from the last committed token
                raise ReplicaCrashError(
                    "injected decode-batch crash (test hook)")
            if f.sample_ms is None:
                step_ms, sample_ms = (t1 - f.t0) * 1e3, 0.0
            else:
                step_ms = f.step_ms
                sample_ms = f.sample_ms + (t1 - t_wait) * 1e3
            self._set_step_args(sp, inp, step_ms, sample_ms, f.steps)
            sp.set(tokens=f.steps, ahead=f.ahead)
            # the device was on the dispatch before until that one's read
            # ended, where this one was queued behind it
            self.metrics.step_time.record(
                (t1 - max(f.t0, self._step_read_at)) * 1e3)
            self._step_read_at = t1
            if f.late:
                self._echo_lgs = (f.lgs, f.attn)
            committed = self._record_steps(
                inp, toks_h, fin_h, t1, picks=by_step(picks_h), lgs=lgs_h,
                rows=by_step(rows_h), late=f.late)
            if f.steps > 1:
                self.metrics.inc("tokens_per_dispatch", committed)

    def _record_steps(self, inp: _StepInputs, toks: np.ndarray,
                      fins: np.ndarray, now: float, *, counts=None,
                      picks=None, lgs=None, rows=None,
                      late: bool = False) -> int:
        """The record loop of every decode dispatch: slot ``i`` of the
        group gets column ``i`` of ``toks`` / ``fins`` (``[steps,
        slots]``; ``picks``, ``lgs``, ``rows`` likewise where there are
        any), its first ``counts[i]`` steps where ``counts`` says, in
        order, and none once it is no longer the request the dispatch
        stepped: ended at the read before (EOS, deadline, poison: it was
        stepped for nothing, ``overrun_slot_steps``) or by a token of
        this very column (the device's overrun past it is dropped).
        ``late``: the echoed rows are not here yet; the requests' buffers
        keep their place (``_echo_rows``) for ``_flush_echo``.  Returns
        the tokens committed."""
        committed = 0
        self._echo_defer = late
        try:
            with obs_trace.span("serve/step_record", cat="serve"):
                for i in inp.group:
                    s = inp.slots[i]
                    with self._lock:
                        gone = self._slots[i] is not s
                    if gone:
                        self.metrics.inc("overrun_slot_steps")
                        continue
                    echo = lgs is not None and s.logits is not None
                    n0 = len(s.logits) if s.logits is not None else 0
                    for j in range(len(toks) if counts is None
                                   else counts[i]):
                        if self._slots[i] is not s:
                            break
                        s.pos += 1
                        fin = bool(fins[j, i])
                        if picks is not None:
                            s.picks.append(picks[j, i])
                        if echo and rows is not None:
                            s.rows_next = rows[j, i]
                        self._record_token(i, int(toks[j, i]), fin,
                                           lgs[j, i] if echo else None, now)
                        committed += fin
                    if late and s.logits is not None and len(s.logits) > n0:
                        # rows n0.. of its buffer are column i of this
                        # dispatch's logits
                        self._echo_rows.append(
                            (s.logit_buf, s.rows_buf, n0,
                             len(s.logits) - n0, i))
        finally:
            self._echo_defer = False
        return committed

    def _read_aux(self, sp, aux, rows: bool = False) -> tuple:
        """What a program with ``aux`` reports beside its logits, read
        back with the tokens: the expert counts (and a sparse
        selection's) go onto the span and the counters of the same
        names; returned for the requests' results are the chosen experts
        and, where ``rows`` asks and the program reports them, the
        positions each layer's attention selected (``attn_rows``, the
        bulk of the tree: left on the device otherwise).  ``(None,
        None)`` for any other program."""
        if not aux:
            return None, None
        import jax
        host = jax.device_get({k: v for k, v in aux[0].items()
                               if rows or k != "attn_rows"})   # one round trip
        counts = {name: v for key, names in self.program.aux_stats
                  for name, v in zip(names, host[key].tolist())}
        for name, v in counts.items():
            self.metrics.inc(name, v)
        sp.set(**counts)
        return host["expert_picks"], host.get("attn_rows")

    def _step_inputs(self, tag: str, ahead: Optional[_Flight] = None
                     ) -> Optional[_StepInputs]:
        """Assemble, under the lock, the arrays one dispatch takes for
        the steppable slots serving ``tag``; None when the version is
        gone or no slot is left to step.  ``ahead`` is the step in
        flight, not recorded yet: a slot it steps is taken one row and
        one token further than the host has recorded, its input token is
        the one that step leaves on the device, and it is left out where
        that token is the last of its budget."""
        inp = _StepInputs(self.max_slots, tag)
        with obs_trace.span("serve/step_build", cat="serve"), self._lock:
            inp.params = self._versions.get(tag)
            if inp.params is None:
                return None
            filled_all = 0
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                d = int(ahead is not None and ahead.inp.slots[i] is s)
                filled = self._pages_filled(s, d)
                filled_all += filled
                if (s.tag != tag or s.n_prefilled is not None
                        or (d and s.n_out + 1 >= s.max_new)):
                    continue
                inp.group.append(i)
                inp.slots[i] = s
                inp.toks_in[i] = s.last_token
                inp.on_host[i] = not d
                inp.pos[i] = s.pos + d
                inp.act[i] = True
                inp.temps[i] = s.spec.temperature
                inp.tks[i] = s.spec.top_k
                inp.tps[i] = s.spec.top_p
                inp.seeds[i] = s.spec.seed
                inp.steps[i] = s.n_out + d
                inp.budgets[i] = max(1, s.max_new - s.n_out)
                inp.echo = inp.echo or s.logits is not None
                inp.pages_reserved += len(s.page_ids) + len(s.shared_nodes)
                inp.pages_filled += filled
            self.metrics.pages_filled.set(filled_all)
        return inp if inp.group else None

    def _count_sorted(self, inp: _StepInputs, steps: int = 1) -> None:
        """Count ``steps`` decode steps under ``sampler_sorted_steps`` if
        their batch takes the sampler's sorted path: the predicate the
        program branches by on the device, read here of the host's own
        arrays."""
        from ..ops.sampling import needs_sort
        if needs_sort(inp.temps, inp.tps):
            self.metrics.inc("sampler_sorted_steps", steps)

    def _set_step_args(self, sp, inp: _StepInputs, step_ms: float,
                       sample_ms: float, steps: int = 1) -> None:
        """The arguments every ``serve/decode_step`` span carries
        (``steps``: decode steps in the dispatch)."""
        prog = self.program
        tp = int(getattr(prog, "tp", 1))
        more = {"shards": tp} if tp > 1 else {}
        if self._reads_held_pages:
            # each stepped slot's pages up to its new row, every step
            rows = (inp.pos[inp.group][:, None] + 1
                    + np.arange(steps, dtype=np.int64))
            more["kv_pages_read"] = int((-(-rows // prog.page_size)).sum())
        elif prog.held_pages is not None:
            more["kv_pages_read"] = (steps * self.max_slots
                                     * prog.pages_per_slot)
        # else the program reads rows and counts them itself (``_read_aux``)
        sp.set(n_active=len(inp.group), step_ms=round(step_ms, 3),
               sample_ms=round(sample_ms, 3), queued=self.batcher.qsize(),
               pages_reserved=inp.pages_reserved,
               pages_filled=inp.pages_filled, **more)

    def _chunk_settle(self, n: int) -> None:
        """Read back and commit, in the order of their dispatch, the
        first ``n`` chunks left on the device."""
        flight = self._chunk_inflight
        done, self._chunk_inflight = flight[:n], flight[n:]
        for c in done:
            with self._chunk_span(c) as sp:
                self._chunk_wait(c, sp)
            self._chunk_commit(c)

    def _flush_echo(self) -> None:
        """Copy the echoed logits of the last dispatch that left them on
        the device (``_Flight.late``) into the requests' buffers and hand over the answers that waited for
        them.  Loop thread only; a no-op when nothing is owed."""
        lgs, rows, results = self._echo_lgs, self._echo_rows, \
            self._echo_results
        if lgs is None and not results:
            return
        self._echo_lgs, self._echo_rows, self._echo_results = None, [], []
        try:
            if rows:
                lgs_h = np.asarray(lgs[0])              # [H, S, V]
                attn_h = None if lgs[1] is None else np.asarray(lgs[1])
                for buf, rows_buf, n0, n, i in rows:
                    buf[n0:n0 + n] = lgs_h[:n, i]
                    if rows_buf is not None:
                        rows_buf[n0:n0 + n] = attn_h[:n, i]
        except Exception as e:      # the device is gone: say so
            for fut, _ in results:
                _fail_safe(fut, e)
            raise
        for fut, res in results:
            _set_safe(fut, res)

    def _spec_step_once(self) -> bool:
        """One speculative round per distinct active version tag: k
        sequential draft steps propose tokens, the target verifies all
        k+1 rows in ONE fixed-shape ``spec_step`` dispatch
        (``serve/spec_verify``), and seeded rejection sampling commits
        1..k+1 tokens per slot.  Rejected rows' K/V garbage is always
        overwritten before it can be unmasked (the next round's writes
        start at the new position and cover the old speculative range).
        After a FULL acceptance the draft pool is one row behind, so a
        catch-up draft step writes the last proposal's row — without it
        every fully-accepted round would degrade later proposals."""
        s_n = self.max_slots
        k = self.speculate_k
        tags, crash = self._live_tags()
        if crash:
            raise ReplicaCrashError("injected decode-batch crash (test hook)")
        if not tags:
            return False
        for tag in tags:
            inp = self._step_inputs(tag)
            if inp is None:
                continue
            group = inp.group
            t0 = self.clock()
            dkp, dvp = self._draft_cache
            cur = inp.toks_in
            d_toks_dev, d_probs_dev = [], []
            for j in range(k):
                dkp, dvp, dlgs = self._compiled[("draft_step",)](
                    self._draft_params, dkp, dvp, self._page_table, cur,
                    inp.pos + j, inp.act)
                d_tok, d_prob = self._compiled[("propose",)](
                    dlgs, inp.temps, inp.tks, inp.tps, inp.seeds,
                    inp.steps + j)
                d_toks_dev.append(d_tok)
                d_probs_dev.append(d_prob)
                cur = d_tok
            self._draft_cache = (dkp, dvp)
            d_toks = np.stack([np.asarray(t) for t in d_toks_dev],
                              1).astype(np.int32)          # [S, k]
            spec_tokens = np.concatenate([inp.toks_in[:, None], d_toks], 1)
            with obs_trace.span("serve/spec_verify", cat="serve",
                                n_active=len(group), k=k, model=tag):
                kp, vp = self._cache
                kp, vp, lgs = self._compiled[("spec_step",)](
                    inp.params, kp, vp, self._page_table, spec_tokens,
                    inp.pos, inp.act)
                n_commit, commit, fin = self._compiled[("spec_accept",)](
                    lgs, d_toks,
                    np.stack([np.asarray(p) for p in d_probs_dev], 1),
                    inp.temps, inp.tks, inp.tps, inp.seeds, inp.steps)
                self._cache = (kp, vp)
                nc_h = np.asarray(n_commit)
                cm_h = np.asarray(commit)
                fin_h = np.asarray(fin)
                lgs_h = np.asarray(lgs) if inp.echo else None
                t1 = self.clock()
            self.metrics.inc("decode_steps")
            self._count_sorted(inp)
            self.metrics.step_time.record((t1 - t0) * 1e3)
            self.metrics.inc("spec_steps")
            self.metrics.inc("spec_proposed", k * len(group))
            self.metrics.inc("spec_accepted",
                             int(nc_h[group].sum()) - len(group))
            # a slot's column is its ``nc_h`` committed tokens, one
            # finite flag for all of them
            committed = self._record_steps(
                inp, cm_h.T, np.broadcast_to(fin_h, cm_h.T.shape), t1,
                counts=nc_h,
                lgs=None if lgs_h is None else lgs_h.transpose(1, 0, 2))
            catchup = np.zeros((s_n,), bool)
            cu_tok = np.zeros((s_n,), np.int32)
            with self._lock:
                for i in group:
                    if (self._slots[i] is inp.slots[i]
                            and nc_h[i] == k + 1):
                        catchup[i] = True
                        cu_tok[i] = d_toks[i, k - 1]
            self.metrics.inc("spec_committed", committed)
            if catchup.any():
                dkp, dvp = self._draft_cache
                dkp, dvp, _ = self._compiled[("draft_step",)](
                    self._draft_params, dkp, dvp, self._page_table, cu_tok,
                    inp.pos + k, catchup)
                self._draft_cache = (dkp, dvp)
        return True

    # -- per-token bookkeeping + stop conditions ---------------------------

    def _record_token(self, i: int, token: int, finite: bool,
                      logits_row: Optional[np.ndarray], now: float) -> None:
        s = self._slots[i]
        if s is None:
            return
        if not finite:
            self.metrics.inc("poison_isolated")
            self._scrub_pages(s.page_ids)
            self._finish(i, now, error=PoisonInputError(
                f"decode produced non-finite logits at token {s.n_out} "
                f"(slot {i}) — request isolated, co-batched slots "
                "unaffected"))
            return
        s.tokens.append(token)
        s.n_out += 1
        s.last_token = token
        s.t_last = now
        if s.logits is not None and (logits_row is not None
                                     or self._echo_defer):
            # rows land in one buffer sized for the whole answer, so that
            # finishing hands it over without stacking (tens of MB copied
            # with the device idle, once a request: PERF.md §5)
            if s.logit_buf is None:
                s.logit_buf = np.empty(
                    (s.max_new, self.program.vocab_size), np.float32)
                if self._rows_shape is not None:
                    s.rows_buf = np.empty((s.max_new,) + self._rows_shape,
                                          np.int32)
            n = len(s.logits)
            if n == len(s.logit_buf):       # more rows than the budget
                s.logit_buf = np.concatenate(
                    [s.logit_buf, np.empty_like(s.logit_buf)])
                if s.rows_buf is not None:
                    s.rows_buf = np.concatenate(
                        [s.rows_buf, np.empty_like(s.rows_buf)])
            if logits_row is not None:
                s.logit_buf[n] = logits_row
            # else a row that comes late, which ``_flush_echo`` copies in
            if s.rows_next is not None:
                s.rows_buf[n], s.rows_next = s.rows_next, None
            s.logits.append(s.logit_buf[n])
        self.metrics.inc("tokens_out")
        if self.eos_id is not None and token == self.eos_id:
            self._finish(i, now, reason="eos")
        elif s.n_out >= s.max_new:
            self._finish(i, now, reason="max_tokens")
        elif now > s.deadline:
            # mid-decode deadline is a STOP condition, not an error: the
            # caller gets the tokens produced inside the budget
            self._finish(i, now, reason="deadline")

    def _scrub_pages(self, page_ids: List[int]) -> None:
        """Zero freed pages that may hold non-finite rows — a NaN left
        behind would poison the page's next tenant (0 * NaN = NaN).
        Only ever called with a slot's PRIVATE pages: shared prefix
        pages are read-only to their holders and validated finite at
        insert, so a scrub can never hit a page another request still
        references — the no-scrub-while-shared discipline."""
        pps = self.program.pages_per_slot
        ids = np.full((pps,), page_ids[0], np.int32)
        ids[:len(page_ids)] = page_ids
        kp, vp = self._cache
        self._cache = self._compiled[("scrub",)](kp, vp, ids)
        if self._draft_program is not None:
            dkp, dvp = self._draft_cache
            self._draft_cache = self._compiled[("draft_scrub",)](
                dkp, dvp, ids)

    def _finish(self, i: int, now: float, reason: Optional[str] = None,
                error: Optional[BaseException] = None) -> None:
        if self._slots[i] is None:
            return
        with obs_trace.span("serve/finish", cat="serve",
                            reason=reason or "error") as sp:
            with self._lock:
                s = self._slots[i]
                if s is None:
                    return
                self._release_locked(i, s, now)
            request_id = s.spec.request_id
            # an error before the first token leaves no time to it
            ttft_ms = (round((s.t_first - s.req.t_submit) * 1e3, 3)
                       if s.t_first else None)
            if error is not None:
                self.metrics.inc("deadline_missed" if isinstance(
                    error, DeadlineExceededError) else "errors")
                _fail_safe(s.req.future, error)
            else:
                self.metrics.inc({"eos": "eos_stops",
                                  "max_tokens": "max_token_stops",
                                  "deadline": "deadline_stops"}[reason])
                tpot = ((s.t_last - s.t_first) * 1e3 / (s.n_out - 1)
                        if s.n_out > 1 else None)
                if tpot is not None:
                    self.metrics.tpot.record(tpot)
                # the device stopped the slot itself, so the state is
                # the last fed token's (an EOS is fed by the step ahead)
                held = (self._compiled[("slot_state",)](
                            self._cache[1].state, np.int32(i))
                        if s.spec.echo_state and self._slot_state
                        and reason == "max_tokens" else None)
                result = GenerationResult(
                    tokens=list(s.tokens), n_prompt=s.n_prompt,
                    finish_reason=reason, model_tag=s.tag, ttft_ms=ttft_ms,
                    tpot_ms=round(tpot, 3) if tpot is not None else None,
                    logits=s.logit_buf[:len(s.logits)] if s.logits
                    else None,
                    request_id=request_id,
                    expert_picks=np.stack(s.picks) if s.picks else None,
                    attn_rows=s.rows_buf[:len(s.logits)]
                    if s.logits and s.rows_buf is not None else None,
                    slot_state=held)
                if s.logits and self._echo_defer:
                    # its last rows are still on the device
                    self._echo_results.append((s.req.future, result))
                else:
                    _set_safe(s.req.future, result)
            sp.set(request_id=request_id, tokens=s.n_out,
                   request_ms=round((now - s.req.t_submit) * 1e3, 3))
            if ttft_ms is not None:
                sp.set(ttft_ms=ttft_ms)
        # submit -> result spans threads, so it cannot be a live scope
        obs_trace.complete_at("serve/request", s.req.t_submit, now,
                              cat="serve", kind="generate", tokens=s.n_out,
                              finish=reason or "error",
                              request_id=request_id)

    def _release_locked(self, i: int, s: _Slot, now: float) -> None:
        """Take ``s`` out of slot ``i``: its private pages go back to the
        free list, its shared ones lose a reference, and a version no
        slot, alias or placed model holds any more is dropped.  Caller
        holds ``_lock``."""
        self._slots[i] = None
        self._free_pages.extend(s.page_ids)
        for nd in reversed(s.shared_nodes):
            # decref, never free: trie pages stay resident for the next
            # shared-prefix request until LRU eviction
            nd.refs -= 1
            nd.last_used = now
        s.shared_nodes = []
        self._page_table[i] = 0
        live_tags = {sl.tag for sl in self._slots if sl is not None}
        live_tags.add(self._serve_tag)
        live_tags.update(self._model_tags.values())
        for t in [t for t in self._versions if t not in live_tags]:
            del self._versions[t]
        self._refresh_pool_gauges_locked()

    # -- crash recovery ----------------------------------------------------

    def _drain_crashed(self, exc: BaseException) -> None:
        """Fail or retry every in-flight request after a decode-batch
        crash, reset the pool, keep serving.  Retries regenerate the
        identical sequence (seeded counter-based sampling), so a retry
        is indistinguishable from a slow first attempt."""
        try:
            self._flush_echo()      # answers that had finished before it
        except Exception as e:      # their callers have been told
            obs_trace.instant("serve/replica_crash", cat="serve",
                              kind="echo_flush", error=type(e).__name__)
        # their slots are wiped with the rest, what they computed dropped
        self._chunk_inflight, self._flight = [], None
        with self._lock:
            in_flight = [s for s in self._slots if s is not None]
            self._slots = [None] * self.max_slots
            self._free_pages = deque(range(1, self.total_pages))
            self._page_table[:] = 0
            # the prefix trie dies with the pool: slots are wiped WITHOUT
            # decref and the trie is rebuilt empty, so a retried
            # prefix-hit request re-matches from scratch — a crash-retry
            # can never double-decref a shared page
            self._prefix_root = _PrefixNode((), None, None)
            self._trie_pages = 0
            self.metrics.shared_pages.set(0)
            self._refresh_pool_gauges_locked()
        # the crash may have left non-finite rows anywhere — zero the pool
        kp, vp = self._cache
        self._cache = self._compiled[("reset",)](kp, vp)
        if self._draft_program is not None:
            dkp, dvp = self._draft_cache
            self._draft_cache = self._compiled[("draft_reset",)](dkp, dvp)
        now = self.clock()
        for s in in_flight:
            r = s.req
            r.retries += 1
            if r.retries <= self.max_retries and r.deadline > now \
                    and not r.future.done():
                self.metrics.inc("retries")
                obs_trace.instant("serve/retry", cat="serve", kind="decode",
                                  retries=r.retries)
                self.batcher.requeue_front(r)
            else:
                self.metrics.inc("errors")
                _fail_safe(r.future, ReplicaCrashError(
                    f"decode batch crashed ({type(exc).__name__}: {exc}) "
                    f"after {s.n_out} tokens; retry budget exhausted"))

    # -- observability / shutdown ------------------------------------------

    def _refresh_pool_gauges_locked(self) -> None:
        """Keep the occupancy and free-capacity gauges live — the fleet
        router scores decode sinks by them (docs/SERVING.md
        "Disaggregated and sharded decode").  Caller holds
        ``self._lock``."""
        free = sum(1 for s in self._slots if s is None)
        self.metrics.active_slots.set(self.max_slots - free)
        self.metrics.free_slots.set(free)
        self.metrics.pages_in_use.set(
            self.total_pages - 1 - len(self._free_pages))
        self.metrics.free_pages.set(len(self._free_pages))
        self.metrics.pages_filled.set(
            sum(self._pages_filled(s) for s in self._slots if s is not None))

    def _pages_filled(self, s: _Slot, ahead: int = 0) -> int:
        """Pages of ``s`` that hold at least one token (``ahead``: rows
        a step in flight has written and the host not yet recorded)."""
        held = s.pos + ahead if s.n_prefilled is None else s.n_prefilled
        return -(-held // self.program.page_size)

    def metrics_snapshot(self) -> dict:
        snap = self.metrics.snapshot()
        with self._lock:
            snap["model"] = self._serve_tag
            snap["versions"] = sorted(self._versions)
            snap["models"] = {"": self._serve_tag, **self._model_tags}
        if self.tenants is not None:
            snap["tenants"] = self.tenants.snapshot()
        with self._lock:
            snap["queue_depth"] = self.batcher.qsize()
            snap["free_pages"] = len(self._free_pages)
            snap["free_slots"] = sum(1 for s in self._slots if s is None)
        snap["compile_cache_size"] = self.compile_cache_size()
        snap["prompt_buckets"] = list(self.prompt_buckets)
        snap["max_slots"] = self.max_slots
        snap["total_pages"] = self.total_pages
        snap["pages_per_slot"] = self.program.pages_per_slot
        snap["prefix_cache"] = self._prefix_on
        snap["speculate_k"] = (self.speculate_k
                               if self._draft_program is not None else 0)
        snap["kv_dtype"] = self._kv_dtype or "float32"
        snap["role"] = self.role
        snap["tp"] = int(getattr(self.program, "tp", 1))
        snap["decode_horizon"] = self.decode_horizon
        snap["prefill_chunk"] = self.prefill_chunk
        snap["prefill_order"] = self.prefill_order
        return snap

    def health_snapshot(self) -> dict:
        with self._lock:
            t = self._thread
            ready = (self._loaded and not self._shutdown
                     and t is not None and t.is_alive())
        return {"status": "ready" if ready else "unready", "ready": ready,
                "kind": "decode", "model": self.current_tag}

    def begin_drain(self) -> None:
        """Stop admission (new submissions shed → 429) while queued and
        in-flight generations complete — the decode half of the
        graceful SIGTERM drain (docs/SERVING.md)."""
        self.batcher.begin_drain()
        self.metrics.inc("drains")
        obs_trace.instant("serve/drain", cat="serve")

    def shutdown(self) -> None:
        """Idempotent; every queued AND in-flight future resolves."""
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
            self._generation += 1
            in_flight = [s for s in self._slots if s is not None]
            self._slots = [None] * self.max_slots
        self._stop.set()
        self.batcher.close(fail_pending=True)
        for s in in_flight:
            _fail_safe(s.req.future,
                       RuntimeError("serving engine is shut down"))
        for t in (self._thread, self._supervisor):
            if t is not None and t is not threading.current_thread():
                t.join(timeout=5)
