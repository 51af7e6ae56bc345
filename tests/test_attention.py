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
from deeplearning4j_tpu.ops import attention
from deeplearning4j_tpu.ops.attention import FlashTiles, flash_mha, flash_tiles, mha
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


def _max_rel(a, b):
    """Largest absolute difference over the reference's largest entry."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def _parity(q, k, v, causal, kmask=None, out_w=None):
    """(worst forward gap, worst gradient gap) of flash_mha against mha,
    each over the reference's largest entry; ``out_w`` [B,1,T,1] weights
    the outputs the loss sees (the attention layer's output mask)."""
    xm = None if kmask is None else kmask[:, None, None, :]
    w = 1.0 if out_w is None else out_w
    fl = lambda q, k, v: flash_mha(q, k, v, causal, kmask=kmask)
    ref = lambda q, k, v: mha(q, k, v, causal=causal, mask=xm)
    loss = lambda f: (lambda q, k, v: jnp.sum(
        (f(q, k, v).astype(jnp.float32) * w) ** 2))
    fwd = _max_rel(fl(q, k, v) * w, ref(q, k, v) * w)
    g_fl = jax.grad(loss(fl), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g in g_fl:
        assert np.all(np.isfinite(np.asarray(g, np.float32)))
    return fwd, max(_max_rel(a, b) for a, b in zip(g_fl, g_ref))


# f32: the two paths differ by summation order only; bf16: both round p and
# the operands to 8 bits, in different places
_TOL = {"float32": 2e-5, "bfloat16": 3e-2}


class TestFlashTiledShapes:
    """The shapes where a grid step walks several chunks (T >= 512): what
    the benchmark's training cells run, beside masks and cross-attention."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("t", [512, 1024, 2048])
    def test_forward_and_grad_match_xla(self, t, dtype, causal):
        q, k, v = (x.astype(dtype) for x in _qkv(b=1, h=2, t=t, d=64, seed=t))
        tiles = flash_tiles(t, t, 64, q.dtype.itemsize, 2)
        assert tiles is not None and tiles.span == t
        fwd, grad = _parity(q, k, v, causal)
        assert fwd < _TOL[dtype] and grad < _TOL[dtype], (fwd, grad)

    @pytest.mark.parametrize("causal", [False, True])
    def test_padding_mask_multi_chunk(self, causal):
        q, k, v = _qkv(b=2, h=1, t=1024, d=64, seed=11)
        mask = np.ones((2, 1024), np.float32)
        mask[0, 700:] = 0.0      # ends inside a chunk
        mask[1, 512:] = 0.0      # ends on a chunk's edge
        mj = jnp.asarray(mask)
        fwd, grad = _parity(q, k, v, causal, kmask=mj,
                            out_w=mj[:, None, :, None])
        assert fwd < 2e-5 and grad < 2e-5, (fwd, grad)

    @pytest.mark.parametrize("t,s", [(512, 1024), (1024, 512), (192, 1024)])
    def test_cross_attention_multi_chunk(self, t, s):
        q, _, _ = _qkv(b=1, h=2, t=t, d=64, seed=12)
        _, k, v = _qkv(b=1, h=2, t=s, d=64, seed=13)
        mask = np.ones((1, s), np.float32)
        mask[0, s - 100:] = 0.0
        fwd, grad = _parity(q, k, v, False, kmask=jnp.asarray(mask))
        assert fwd < 2e-5 and grad < 2e-5, (fwd, grad)

    @pytest.mark.parametrize("t,s", [(256, 512), (512, 256), (1024, 384),
                                     (2048, 1024), (1024, 2048)])
    def test_causal_cross_attention(self, t, s):
        """Key blocks past the last query see no query at all (S > T), and
        query blocks past the last key see every key (T > S)."""
        q, _, _ = _qkv(b=1, h=2, t=t, d=64, seed=16)
        _, k, v = _qkv(b=1, h=2, t=s, d=64, seed=17)
        fwd, grad = _parity(q, k, v, True)
        assert fwd < 2e-5 and grad < 2e-5, (fwd, grad)

    @pytest.mark.parametrize("causal", [False, True])
    def test_fully_masked_rows_multi_chunk(self, causal):
        """Slice 0 has no live key at all; under the causal mask the first
        three queries of slice 1 have none either.  The zero-gradient
        convention holds across chunks: finite everywhere, and equal to
        mha under the output mask."""
        q, k, v = _qkv(b=2, h=1, t=1024, d=64, seed=14)
        mask = np.ones((2, 1024), np.float32)
        mask[0, :] = 0.0
        mask[1, :3] = 0.0
        mask[1, 900:] = 0.0
        w = mask.copy()
        fwd, grad = _parity(q, k, v, causal, kmask=jnp.asarray(mask),
                            out_w=jnp.asarray(w)[:, None, :, None])
        assert fwd < 2e-5 and grad < 2e-5, (fwd, grad)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("rows,chunk,span,heads", [
        (128, 128, 256, 2),      # four outer key blocks of two chunks
        (256, 128, 128, 1),      # span == chunk: one chunk a step
        (128, 256, 512, 2),
    ])
    def test_walked_axis_in_parts(self, monkeypatch, rows, chunk, span, heads,
                                  causal):
        """A walked axis too long for VMEM stays an outer grid dimension;
        reached here by putting a small ``span`` in place of the choice."""
        def small(T, S, D, itemsize, BH):
            c, sp = min(chunk, S), min(span, S)
            return FlashTiles(rows, c, sp, heads,
                              (BH // heads, T // rows, S // sp), 0)
        monkeypatch.setattr(attention, "flash_tiles", small)
        q, k, v = _qkv(b=1, h=2, t=512, d=32, seed=15)
        mask = np.ones((1, 512), np.float32)
        mask[0, 400:] = 0.0
        mj = jnp.asarray(mask)
        fwd, grad = _parity(q, k, v, causal, kmask=mj,
                            out_w=mj[:, None, :, None])
        assert fwd < 2e-5 and grad < 2e-5, (fwd, grad)
        # S != T under the causal mask: dead outer blocks on either side
        fwd, grad = _parity(q[:, :, :256], k, v, causal)
        assert fwd < 2e-5 and grad < 2e-5, (fwd, grad)
        fwd, grad = _parity(q, k[:, :, :256], v[:, :, :256], causal)
        assert fwd < 2e-5 and grad < 2e-5, (fwd, grad)


class TestFlashTiles:
    """The tile function alone."""

    def test_training_cell_shape(self):
        t = flash_tiles(1024, 1024, 64, 2, 192)       # gpt2-medium.train-1k
        assert t.grid_steps <= 1000, t
        assert t.vmem_bytes <= attention._VMEM_BUDGET, t
        assert t.span == 1024                          # K and V resident
        assert t.grid == (192 // t.heads, 1024 // t.rows, 1)
        assert 1024 % t.rows == 0 and 1024 % t.chunk == 0 and 192 % t.heads == 0

    @pytest.mark.parametrize("t,s,d,itemsize,bh", [
        (128, 128, 64, 2, 1536), (512, 512, 64, 4, 8), (2048, 2048, 64, 2, 16),
        (4096, 4096, 64, 2, 16), (1024, 1024, 128, 4, 6), (640, 384, 64, 2, 3),
        (192, 1024, 64, 4, 2), (16384, 16384, 128, 4, 4), (512, 65536, 128, 4, 1),
        (8192, 8192, 128, 2, 16), (1536, 768, 64, 2, 4), (64, 64, 16, 4, 8),
    ])
    def test_geometry_is_consistent_and_fits(self, t, s, d, itemsize, bh):
        g = flash_tiles(t, s, d, itemsize, bh)
        assert t % g.rows == 0 and s % g.span == 0 and g.span % g.chunk == 0
        assert bh % g.heads == 0 and 1 <= g.heads <= attention._MAX_HEADS
        assert g.heads == 1 or g.rows * g.heads <= attention._STEP_ROWS
        assert g.grid == (bh // g.heads, t // g.rows, s // g.span)
        assert g.vmem_bytes <= attention._VMEM_BUDGET, g
        for n, block in ((t, g.rows), (s, g.chunk)):   # Mosaic's alignment
            assert block % 128 == 0 or block == n

    def test_long_walked_axis_is_held_in_parts(self):
        g = flash_tiles(512, 65536, 128, 4, 1)
        assert g.span < 65536 and g.grid[2] == 65536 // g.span > 1

    @pytest.mark.parametrize("n,itemsize,block", [
        (64, 4, 64), (192, 2, 192), (384, 2, 128), (512, 4, 128),
        (100, 4, 0), (24, 2, 0), (520, 4, 0), (640, 4, 128), (1000, 2, 0),
    ])
    def test_lengths_that_do_not_tile_keep_their_answers(self, n, itemsize,
                                                         block):
        """What ``_pick_block`` answered before PR 26 for the blocked axis:
        0 = the XLA path, n = the whole axis in one tile, 128 = tiled (now
        in multiples of 128)."""
        g = flash_tiles(n, n, 64, itemsize, 4)
        if block == 0:
            assert g is None
        elif block == n:
            assert (g.rows, g.chunk, g.span) == (n, n, n)
        else:
            assert g.rows % 128 == 0 and g.chunk % 128 == 0

    def test_one_layer_holds_three_calls_with_the_shapes_the_benchmark_reads(self):
        """``benchmarks/kernels/flash_attention.classify`` tells the three
        Mosaic calls of a layer apart by their results: forward
        ([BH,T,D], f32[BH,1,T]), dk/dv ([BH,S,D] x 2), dq ([BH,T,D])."""
        b, h, t, s, d = 2, 3, 256, 512, 64
        q = jnp.zeros((b, h, t, d), jnp.bfloat16)
        k = v = jnp.zeros((b, h, s, d), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_mha(q, k, v).astype(jnp.float32)),
            argnums=(0, 1, 2)))(q, k, v)

        def calls(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    yield [(o.aval.shape, str(o.aval.dtype))
                           for o in eqn.outvars]
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from calls(sub)
        bh = b * h
        assert list(calls(jaxpr.jaxpr)) == [
            [((bh, t, d), "bfloat16"), ((bh, 1, t), "float32")],
            [((bh, s, d), "bfloat16"), ((bh, s, d), "bfloat16")],
            [((bh, t, d), "bfloat16")],
        ]


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
