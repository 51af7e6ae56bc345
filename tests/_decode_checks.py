"""What four decode tests hold an engine to on XLA:CPU, in one place.

An engine's echoed logits come from programs of other row counts than
``reencode``'s (one row a step, a bucket a prefill, the whole window a
re-encode).  The decode contract (ROADMAP C1) is equal bits, and the
tests that see equal bits on the driver's host still assert them
(``np.array_equal``: test_decode.py::TestBitIdentity, test_decode_speed.py
``_bits_match``).  Four comparisons do not get equal bits from XLA:CPU
under jax 0.9 there: every token agrees and a logit's last bits differ
(test_fused_decode.py ``test_greedy_bitwise_identical``,
``test_echo_logits_bitwise``, ``test_interacts_with_prefix_cache``;
test_disagg.py ``test_decode_bitwise_vs_sharded_reencode``).  Those four
hold: equal tokens, and logits within ``LOGIT_ATOL`` of the reference's.
Widest gap measured over their 49 rows (PR 29, this sandbox's XLA:CPU; a
row's largest logit 0.19 to 0.37): 1.79e-07, at most 11 units in the
last place of that largest logit.  The limit is 11 x that gap and 500 x
under the 1e-3 a changed operation shows
(benchmarks/configs/gpt2-large.json's bf16 control reads 1e-2).
test_decode.py::TestOneBuilder (new in PR 29) holds ``step_multi`` at a
horizon of 1 to ``step`` under the same limit: a scan and a plain program.
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
