"""Attention stack tests: flash kernel parity, layer gradients, masking.

DL4J 0.9.2 has no attention; these exercise the TPU-first long-context
path (SURVEY.md §5/§7-M5): ops.attention (XLA + pallas flash kernel) and
the SelfAttention / LearnedSelfAttention layers.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    LearnedSelfAttention, OutputLayer, RnnOutputLayer, SelfAttention,
    GlobalPooling,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.updaters import Adam, NoOp
from deeplearning4j_tpu.ops.attention import flash_mha, mha
from deeplearning4j_tpu.utils.gradient_check import check_gradients
from jax import enable_x64

RNG = np.random.default_rng(7)


def _qkv(b=2, h=4, t=128, d=64, seed=0):
    rng = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(r, (b, h, t, d)) for r in jax.random.split(rng, 3))


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla_single_block(self, causal):
        q, k, v = _qkv()
        np.testing.assert_allclose(
            np.asarray(flash_mha(q, k, v, causal)),
            np.asarray(mha(q, k, v, causal=causal)), rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_xla_multi_block(self, causal):
        q, k, v = _qkv(b=1, h=2, t=256, d=32, seed=1)
        np.testing.assert_allclose(
            np.asarray(flash_mha(q, k, v, causal)),
            np.asarray(mha(q, k, v, causal=causal)), rtol=2e-5, atol=2e-5)

    def test_cross_attention_lengths(self):
        q, k, v = _qkv(t=128, seed=2)
        k2, v2 = k[:, :, :64], v[:, :, :64]
        np.testing.assert_allclose(
            np.asarray(flash_mha(q, k2, v2)),
            np.asarray(mha(q, k2, v2)), rtol=2e-5, atol=2e-5)

    def test_odd_length_falls_back(self):
        q, k, v = _qkv(t=100, seed=3)  # 100 has no pow2 block divisor ≥ 8
        np.testing.assert_allclose(
            np.asarray(flash_mha(q, k, v)),
            np.asarray(mha(q, k, v)), rtol=2e-5, atol=2e-5)

    def test_gradients_match_xla(self):
        q, k, v = _qkv(b=1, h=2, t=64, d=16, seed=4)

        def loss(fn, causal):
            return lambda q, k, v: jnp.sum(fn(q, k, v, causal) ** 2)

        g_ref = jax.grad(lambda q, k, v: jnp.sum(mha(q, k, v, causal=True) ** 2),
                         argnums=(0, 1, 2))(q, k, v)
        g_fl = jax.grad(loss(flash_mha, True), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_fl):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_padding_mask_matches_xla(self, causal):
        """Variable-length batches (the DL4J-parity case) stay on the
        kernel: key-padding mask in both forward and fused backward."""
        q, k, v = _qkv(b=2, h=2, t=64, d=16, seed=5)
        mask = np.ones((2, 64), np.float32)
        mask[0, 41:] = 0.0
        mask[1, 13:] = 0.0
        mj = jnp.asarray(mask)
        ref = mha(q, k, v, causal=causal, mask=mj[:, None, None, :])
        out = flash_mha(q, k, v, causal, kmask=mj)
        w = mask[:, None, :, None]
        np.testing.assert_allclose(np.asarray(out) * w, np.asarray(ref) * w,
                                   rtol=2e-5, atol=2e-5)

        def loss_fl(q, k, v):
            o = flash_mha(q, k, v, causal, kmask=mj)
            return jnp.sum((o * mj[:, None, :, None]) ** 2)

        def loss_ref(q, k, v):
            o = mha(q, k, v, causal=causal, mask=mj[:, None, None, :])
            return jnp.sum((o * mj[:, None, :, None]) ** 2)

        g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_fully_masked_rows_finite_and_output_masked_equal(self):
        """All-keys-masked rows produce garbage-by-convention in BOTH paths
        (flash: the additive −LARGE bias is a constant row shift, softmax
        cancels it; mha: uniform over where()-replaced scores) — the DL4J
        contract is that such rows are zeroed DOWNSTREAM by the output
        mask, which is exactly what the attention layer does.  What must
        hold: finiteness, and output-masked loss gradients equal."""
        q, k, v = _qkv(b=2, h=2, t=32, d=16, seed=6)
        mask = np.ones((2, 32), np.float32)
        mask[0, :] = 0.0   # row 0: ALL keys masked
        mask[1, 20:] = 0.0
        mj = jnp.asarray(mask)
        w = mj[:, None, :, None]

        def loss_fl(q, k, v):
            return jnp.sum((flash_mha(q, k, v, False, kmask=mj) * w) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum((mha(q, k, v, mask=mj[:, None, None, :]) * w) ** 2)

        g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_fl, g_ref):
            assert np.all(np.isfinite(np.asarray(a)))
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


def _seq_data(n=4, t=8, f=6, c=3):
    x = RNG.normal(size=(n, t, f))
    y = np.eye(c)[RNG.integers(0, c, (n, t))]
    return DataSet(x, y)


def _net(layers, input_type):
    b = NeuralNetConfiguration.builder().seed(0).updater(NoOp()).dtype("float64", "float64")
    for l in layers:
        b.layer(l)
    b.set_input_type(input_type)
    net = MultiLayerNetwork(b.build())
    with enable_x64(True):
        net.init()
    return net


class TestSelfAttentionLayer:
    def test_gradient_check(self):
        net = _net([SelfAttention(n_out=8, n_heads=2, kernel="xla"),
                    RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                   InputType.recurrent(6, 8))
        with enable_x64(True):
            assert check_gradients(net, _seq_data(), epsilon=1e-6,
                                   max_rel_error=1e-4, verbose=True)

    def test_gradient_check_causal(self):
        net = _net([SelfAttention(n_out=8, n_heads=2, causal=True, kernel="xla"),
                    RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                   InputType.recurrent(6, 8))
        with enable_x64(True):
            assert check_gradients(net, _seq_data(), epsilon=1e-6,
                                   max_rel_error=1e-4, verbose=True)

    def test_trains(self):
        # learnable pattern: class = argmax over time-mean of features
        n, t, f = 64, 16, 3
        x = RNG.normal(size=(n, t, f)).astype(np.float32)
        y_cls = np.argmax(x.mean(axis=1), axis=-1)
        y = np.eye(f, dtype=np.float32)[y_cls][:, None, :].repeat(t, axis=1)
        conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(lr=5e-3))
                .layer(SelfAttention(n_out=16, n_heads=4))
                .layer(RnnOutputLayer(n_out=f, activation="softmax", loss="mcxent"))
                .set_input_type(InputType.recurrent(f, t)).build())
        net = MultiLayerNetwork(conf)
        net.init()
        losses = [net.fit_batch(DataSet(x, y)) for _ in range(60)]
        assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])

    def test_causal_is_causal(self):
        # causal attention: output at t must not depend on inputs after t
        net = _net([SelfAttention(n_out=8, n_heads=2, causal=True, kernel="xla"),
                    RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                   InputType.recurrent(6, 8))
        x = RNG.normal(size=(1, 8, 6))
        with enable_x64(True):
            out1 = np.asarray(net.output(x))
            x2 = x.copy()
            x2[:, 5:] = 99.0  # corrupt the future
            out2 = np.asarray(net.output(x2))
        np.testing.assert_allclose(out1[:, :5], out2[:, :5], rtol=1e-6)

    def test_mask_blocks_padded_steps(self):
        net = _net([SelfAttention(n_out=8, n_heads=2, kernel="xla"),
                    RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                   InputType.recurrent(6, 8))
        x = RNG.normal(size=(2, 8, 6))
        mask = np.ones((2, 8), np.float32)
        mask[:, 6:] = 0.0
        with enable_x64(True):
            out1 = np.asarray(net.output(x, mask=mask))
            x2 = x.copy()
            x2[:, 6:] = 123.0  # corrupt masked-out steps
            out2 = np.asarray(net.output(x2, mask=mask))
        np.testing.assert_allclose(out1[:, :6], out2[:, :6], rtol=1e-6)

    def test_serde_roundtrip(self):
        from deeplearning4j_tpu.nn.layers.base import layer_from_dict, layer_to_dict
        layer = SelfAttention(n_in=6, n_out=8, n_heads=2, causal=True)
        back = layer_from_dict(layer_to_dict(layer))
        assert back == layer


class TestLearnedSelfAttention:
    def test_fixed_length_summary(self):
        net = _net([LearnedSelfAttention(n_out=8, n_heads=2, n_queries=3, kernel="xla"),
                    RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.recurrent(6, 10))
        x = RNG.normal(size=(4, 10, 6))
        with enable_x64(True):
            out = np.asarray(net.output(x))
        assert out.shape == (4, 3, 2)

    def test_gradient_check(self):
        net = _net([LearnedSelfAttention(n_out=8, n_heads=2, n_queries=2, kernel="xla"),
                    RnnOutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                   InputType.recurrent(6, 8))
        x = RNG.normal(size=(4, 8, 6))
        y = np.eye(3)[RNG.integers(0, 3, (4, 2))]
        with enable_x64(True):
            assert check_gradients(net, DataSet(x, y), epsilon=1e-6,
                                   max_rel_error=1e-4, verbose=True)
