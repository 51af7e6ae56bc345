"""What the decode tests hold an engine's echoed logits to against
``reencode``, in one place (ROADMAP C1).

An engine's echoed logits come from programs of other row counts than
``reencode``'s (one row a step, a bucket a prefill, the whole window a
re-encode), and since PR 30 a decode step's attention sums over the
pages a slot holds (``ops/paged_attention.py``) where ``reencode`` sums
over all ``L`` keys at once: the same float32 mathematics in another
order, so the last bits differ by construction.  The contract: every
token equal (a greedy token is the argmax of the reference's row), and
every echoed logit within ``LOGIT_ATOL`` of the reference's.  Eight
comparisons hold it: test_decode.py
``TestBitIdentity::test_decode_logits_match_full_reencode`` and
``test_mln_output_bitwise_unchanged_by_decode_engine`` (the adapter's
echo), test_decode_speed.py ``test_hit_is_bitwise_identical_and_counts``
and ``test_temp0_bitwise_identical_to_plain`` (these four asserted equal
bits until PR 30, and failed in this sandbox on any tree: XLA:CPU's
own row-count differences), test_fused_decode.py
``test_greedy_bitwise_identical``, ``test_echo_logits_bitwise``,
``test_interacts_with_prefix_cache`` and test_disagg.py
``test_decode_bitwise_vs_sharded_reencode`` (under this limit since PR
29), whatever their names say.  test_decode.py::TestOneBuilder holds
``step_multi`` at a horizon of 1 to ``step`` under the same limit: a scan
and a plain program.

What stays ``np.array_equal``: a decode program against ITSELF
(co-batched against solo, crash-retry, hand-off, fused against plain
tokens, a slot alone against co-batched and ``spec_step``'s rows against
``step``'s in tests/test_paged_attention.py): the kernel reads a slot's
own rows in a fixed order.

Widest gap measured over the eight's 100 rows (PR 30, this sandbox's
XLA:CPU; a row's largest logit 0.19 to 2.85): 7.15e-07, three units in
the last place of a logit of 2.02 (1.79e-07 over the four of PR 29,
before the kernel).  The limit stays PR 29's: 2.8 x that gap and 500 x
under the 1e-3 a changed operation shows
(benchmarks/configs/gpt2-large.json's bf16 control reads 1e-2).
"""

import numpy as np
import pytest

LOGIT_ATOL = 2e-6


def assert_logits_close(got, ref, what=""):
    got, ref = np.asarray(got), np.asarray(ref)
    assert got.shape == ref.shape and got.dtype == ref.dtype, what
    np.testing.assert_allclose(got, ref, rtol=0, atol=LOGIT_ATOL,
                               err_msg=what)


def assert_greedy_echo(prompt, res, ref_rows):
    """A greedy request's echoed logits against ``reencode`` of prompt +
    tokens (``ref_rows`` [L, V]): row len(prompt) + j - 1 produced token
    j, so the token is its argmax and the echoed row lies within the
    limit of it."""
    n = len(prompt)
    assert len(res.logits) == len(res.tokens) > 0
    for j, tok in enumerate(res.tokens):
        row = ref_rows[n + j - 1]
        assert int(np.argmax(row)) == int(tok), f"token {j}"
        assert_logits_close(res.logits[j], row, f"token {j}")


def save_bundle_or_skip(engine, path):
    """``engine.save_warmup_bundle(path)``, or skip what needs the bundle
    where the backend cannot serialise an executable (XLA:CPU and the
    samplers' sort comparator; the TPU client can).  Decided from the
    error the bundle code raises, so the chip runs the whole test."""
    try:
        return engine.save_warmup_bundle(path)
    except RuntimeError as e:
        if "is not serializable on the" not in str(e):
            raise
        pytest.skip(str(e))


def queued_together(eng, requests, budget_of=None, timeout=300):
    """``requests`` (``(prompt, generate_async's keywords)`` each) queued
    TOGETHER (the loop waits for the engine's lock until all are in):
    their results in order, the admission rounds that took them in as
    ``(limit, token_budget, [prompt lengths])``, and how many rounds the
    engine counted as ended by the budget meanwhile.  ``budget_of(limit)``
    puts another budget in the engine's place (the rule before a round's
    budget followed the free slots: one chunk's worth whatever is free)."""
    rounds, admit = [], eng.batcher.admit

    def watched(limit, token_budget=None):
        if budget_of is not None:
            token_budget = budget_of(limit)
        out = admit(limit, token_budget=token_budget)
        if out:
            rounds.append((limit, token_budget,
                           [len(r.payload.prompt) for r in out]))
        return out

    def bound():
        return eng.metrics_snapshot()["counters"]["admit_rounds_budget_bound"]

    n0 = bound()
    try:
        with eng._lock:
            eng.batcher.admit = watched
            futs = [eng.generate_async(prompt, **kw)
                    for prompt, kw in requests]
        res = [f.result(timeout=timeout) for f in futs]
    finally:
        eng.batcher.admit = admit
    return res, rounds, bound() - n0
