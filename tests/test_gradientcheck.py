"""Gradient checks: analytic (jax.grad) vs central differences.

The reference's correctness backbone (13 suites under
deeplearning4j-core/src/test/.../gradientcheck/, GradientCheckUtil.java:112).
Run in float64 (enable_x64) so 1e-3 relative tolerance is meaningful.
"""

import numpy as np
import pytest
import jax

from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    LSTM, BatchNormalization, Convolution2D, Dense, ElementWiseMultiplication,
    GravesLSTM, GravesBidirectionalLSTM, LocalResponseNormalization, OutputLayer,
    RnnOutputLayer, Subsampling2D, GlobalPooling, SimpleRnn,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork, NeuralNetConfiguration
from deeplearning4j_tpu.nn.updaters import NoOp
from deeplearning4j_tpu.utils.gradient_check import check_gradients
from jax import enable_x64

RNG = np.random.default_rng(12345)


def _net(layers, input_type):
    b = NeuralNetConfiguration.builder().seed(0).updater(NoOp()).dtype("float64", "float64")
    for l in layers:
        b.layer(l)
    b.set_input_type(input_type)
    net = MultiLayerNetwork(b.build())
    with enable_x64(True):
        net.init()
    return net


def _check(net, ds, **kw):
    with enable_x64(True):
        ok = check_gradients(net, ds, epsilon=1e-6, max_rel_error=1e-4,
                             verbose=True, **kw)
    assert ok


def _ff_data(n=4, f=6, c=3):
    x = RNG.normal(size=(n, f))
    y = np.eye(c)[RNG.integers(0, c, n)]
    return DataSet(x, y)


class TestGradientsDense:
    def test_mlp_mcxent(self):
        net = _net([Dense(n_out=8, activation="tanh"),
                    OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                   InputType.feed_forward(6))
        _check(net, _ff_data())

    def test_mlp_mse_sigmoid(self):
        net = _net([Dense(n_out=8, activation="sigmoid"),
                    OutputLayer(n_out=3, activation="sigmoid", loss="mse")],
                   InputType.feed_forward(6))
        _check(net, _ff_data())

    def test_mlp_l1_l2(self):
        net = _net([Dense(n_out=8, activation="elu", l1=0.01, l2=0.02),
                    OutputLayer(n_out=3, activation="softmax", loss="mcxent", l2=0.01)],
                   InputType.feed_forward(6))
        _check(net, _ff_data())

    def test_elementwise_mult(self):
        net = _net([ElementWiseMultiplication(activation="tanh"),
                    OutputLayer(n_out=3, activation="softmax", loss="mcxent")],
                   InputType.feed_forward(6))
        _check(net, _ff_data())

    @pytest.mark.parametrize("loss,act", [
        ("xent", "sigmoid"), ("l1", "tanh"), ("hinge", "identity"),
        ("squared_hinge", "identity"), ("poisson", "softplus"),
        ("kl_divergence", "sigmoid"), ("cosine_proximity", "identity"),
    ])
    def test_loss_functions(self, loss, act):
        n, f, c = 4, 6, 3
        x = RNG.normal(size=(n, f))
        if loss in ("xent", "kl_divergence"):
            y = RNG.uniform(0.1, 0.9, size=(n, c))
        elif loss == "poisson":
            y = RNG.uniform(0.5, 3.0, size=(n, c))
        else:
            y = np.eye(c)[RNG.integers(0, c, n)]
        net = _net([Dense(n_out=8, activation="tanh"),
                    OutputLayer(n_out=c, activation=act, loss=loss)],
                   InputType.feed_forward(f))
        _check(net, DataSet(x, y))


class TestGradientsCNN:
    def _img_data(self, n=3, h=8, w=8, c=1, classes=2):
        x = RNG.normal(size=(n, h, w, c))
        y = np.eye(classes)[RNG.integers(0, classes, n)]
        return DataSet(x, y)

    def test_conv_pool_dense(self):
        net = _net([Convolution2D(n_out=3, kernel=(3, 3), activation="tanh"),
                    Subsampling2D(pooling="max", kernel=(2, 2), stride=(2, 2)),
                    OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.convolutional(8, 8, 1))
        _check(net, self._img_data())

    def test_conv_avg_pool(self):
        net = _net([Convolution2D(n_out=3, kernel=(3, 3), activation="sigmoid",
                                  convolution_mode="same"),
                    Subsampling2D(pooling="avg", kernel=(2, 2), stride=(2, 2)),
                    OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.convolutional(8, 8, 1))
        _check(net, self._img_data())

    def test_batchnorm(self):
        # BN gradient check runs in inference mode (train=False uses running
        # stats — matches reference BNGradientCheckTest's use of fixed stats)
        net = _net([Convolution2D(n_out=3, kernel=(3, 3), activation="identity"),
                    BatchNormalization(),
                    GlobalPooling(pooling="avg"),
                    OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.convolutional(8, 8, 1))
        _check(net, self._img_data())

    def test_lrn(self):
        net = _net([Convolution2D(n_out=4, kernel=(3, 3), activation="relu"),
                    LocalResponseNormalization(),
                    OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.convolutional(8, 8, 1))
        _check(net, self._img_data())


class TestGradientsRNN:
    def _seq_data(self, n=3, t=5, f=4, c=2, per_step=False, mask=None):
        x = RNG.normal(size=(n, t, f))
        if per_step:
            y = np.eye(c)[RNG.integers(0, c, (n, t))]
        else:
            y = np.eye(c)[RNG.integers(0, c, n)]
        return DataSet(x, y, labels_mask=mask)

    def test_lstm(self):
        net = _net([LSTM(n_out=6),
                    RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.recurrent(4))
        _check(net, self._seq_data(per_step=True))

    def test_graves_lstm_peephole(self):
        net = _net([GravesLSTM(n_out=6),
                    RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.recurrent(4))
        _check(net, self._seq_data(per_step=True))

    def test_bidirectional_lstm(self):
        net = _net([GravesBidirectionalLSTM(n_out=5),
                    RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.recurrent(4))
        _check(net, self._seq_data(per_step=True))

    def test_simple_rnn(self):
        net = _net([SimpleRnn(n_out=6),
                    RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.recurrent(4))
        _check(net, self._seq_data(per_step=True))

    def test_masked_rnn(self):
        """Gradient check WITH per-timestep label masking (reference
        GradientCheckTestsMasking)."""
        n, t = 3, 5
        mask = np.ones((n, t))
        mask[0, 3:] = 0
        mask[2, 1:] = 0
        net = _net([LSTM(n_out=6),
                    RnnOutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.recurrent(4))
        ds = self._seq_data(per_step=True)
        ds.labels_mask = mask
        ds.features_mask = mask
        _check(net, ds)

    def test_lstm_global_pooling(self):
        net = _net([LSTM(n_out=6),
                    GlobalPooling(pooling="max"),
                    OutputLayer(n_out=2, activation="softmax", loss="mcxent")],
                   InputType.recurrent(4))
        _check(net, self._seq_data())
