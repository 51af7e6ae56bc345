"""One-pass (sort-free) fixed-threshold encode: selection-set parity with
the top_k path, bit-identical decode round-trips, and overflow fallback."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops import compression
from deeplearning4j_tpu.ops.compression import (threshold_decode,
                                                threshold_encode)


@pytest.fixture(autouse=True)
def enable_fused(monkeypatch):
    """The one-pass path is opt-in (DL4J_TPU_FUSED_ENCODE=1)."""
    monkeypatch.setattr(compression, "FUSED_ENCODE", True)


def plain_encode(g, k_max, threshold):
    """The top_k reference path (fused flag off)."""
    return compression._topk_pack(
        g.astype(jnp.float32), jnp.abs(g.astype(jnp.float32)),
        min(k_max, g.shape[0]), threshold)


def grad(n=4096, seed=0, sparse_frac=0.02, t=1e-3):
    """Gradient where ~sparse_frac of elements clear the threshold."""
    rng = np.random.default_rng(seed)
    g = rng.normal(size=n).astype(np.float32) * (t / 10)
    hot = rng.choice(n, max(1, int(n * sparse_frac)), replace=False)
    g[hot] = rng.normal(size=hot.size).astype(np.float32) * 10 * t
    return jnp.asarray(g)


class TestOnePassEncode:
    def test_selection_set_matches_topk(self):
        g, t, k = grad(), 1e-3, 256
        enc, scale = threshold_encode(g, k, threshold=t)
        ref = plain_encode(g, k, t)
        assert float(scale) == float(np.float32(t))
        # same SET of signed indices; order is index-ascending instead of
        # top_k's magnitude-descending (decode never observes order)
        assert (set(np.asarray(enc).tolist()) - {0}
                == set(np.asarray(ref).tolist()) - {0})

    def test_decode_roundtrip_bit_identical(self):
        g, t, k = grad(), 1e-3, 256
        enc, scale = threshold_encode(g, k, threshold=t)
        ref = plain_encode(g, k, t)
        d_fused = threshold_decode(enc, scale, g.shape[0])
        d_plain = threshold_decode(ref, jnp.float32(t), g.shape[0])
        np.testing.assert_array_equal(np.asarray(d_fused),
                                      np.asarray(d_plain))

    def test_overflow_falls_back_to_topk_exactly(self):
        # every element clears the threshold -> count > k -> the lax.cond
        # overflow branch must reproduce top_k's largest-first selection
        g = jnp.asarray(np.linspace(1.0, 2.0, 64, dtype=np.float32)
                        * np.resize([1, -1], 64))
        enc, scale = threshold_encode(g, 8, threshold=0.5)
        ref = plain_encode(g, 8, 0.5)
        np.testing.assert_array_equal(np.asarray(enc), np.asarray(ref))
        # largest magnitudes live at the END of linspace
        sent = {abs(int(e)) - 1 for e in np.asarray(enc) if e != 0}
        assert sent == set(range(56, 64))

    def test_nothing_selected(self):
        g = jnp.zeros((128,), jnp.float32)
        enc, scale = threshold_encode(g, 8, threshold=1e-3)
        assert not np.asarray(enc).any()
        d = threshold_decode(enc, scale, 128)
        assert not np.asarray(d).any()

    def test_under_jit(self):
        g, t, k = grad(n=2048, seed=1), 1e-3, 128
        f = jax.jit(lambda x: threshold_encode(x, k, threshold=t))
        enc, scale = f(g)
        ref = plain_encode(g, k, t)
        assert (set(np.asarray(enc).tolist()) - {0}
                == set(np.asarray(ref).tolist()) - {0})

    def test_sign_preserved(self):
        g = jnp.zeros((1024,), jnp.float32)
        g = g.at[3].set(0.5).at[700].set(-0.25)
        enc, scale = threshold_encode(g, 64, threshold=0.1)
        nz = sorted(int(e) for e in np.asarray(enc) if e != 0)
        assert nz == [-701, 4]

    def test_traced_threshold_stays_on_topk(self):
        # a traced (non-static) threshold cannot be baked into the
        # one-pass kernel; the encode must still work via top_k
        g = grad(n=512, seed=2)
        f = jax.jit(lambda x, t: threshold_encode(x, 32, threshold=t))
        with pytest.raises(Exception):
            # raw traced scalars hit the <=0 guard under tracing; the
            # supported contract is static thresholds
            f(g, jnp.float32(1e-3))
