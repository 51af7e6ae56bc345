"""1F1B pipeline schedule: parity, memory, accounting, wiring (round-5
verdict Next #6).

Parity gates on the virtual 8-device CPU mesh (conftest): 1F1B losses ==
GPipe losses bit-for-bit on the first step (the value pass is the same
program) across microbatch counts; gradients match the sequential stack.
Memory gate: the compiled 1F1B train step's temp footprint (where XLA
puts activation checkpoints) is strictly below GPipe's at
n_microbatches > n_stages.  Accounting gate: the analytic model matches
(S-1)/(M+S-1) for GPipe and 1F1B improves at memory parity.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.parallel import (
    ShardedTrainer, ShardedTransformerLM, build_mesh, pipeline_apply,
    stack_stage_params,
)
from deeplearning4j_tpu.parallel.pipeline import pipeline_schedule_stats

RNG = np.random.default_rng(11)


def _blocks(n, f, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return [{"W": jax.random.normal(k, (f, f)) * 0.2, "b": jnp.zeros((f,))}
            for k in keys]


def _block_fn(p, h):
    return jnp.tanh(h @ p["W"] + p["b"])


class TestScheduleParity:
    def _lm(self, mesh, schedule, m):
        return ShardedTransformerLM(vocab_size=64, n_layers=4, d_model=32,
                                    n_heads=4, mesh=mesh, max_len=16, seed=7,
                                    n_microbatches=m, schedule=schedule)

    @pytest.mark.parametrize("m", [2, 4])
    def test_loss_bitwise_equal_to_gpipe(self, m):
        """First-step loss bit-for-bit across >=2 microbatch counts (the
        ISSUE acceptance gate), later steps to tight tolerance (backward
        accumulation order differs between the schedules)."""
        mesh = build_mesh({"data": 2, "pipe": 4})
        toks = RNG.integers(0, 64, (8, 16))
        tgts = np.roll(toks, -1, axis=1)
        lm_g = self._lm(mesh, "gpipe", m)
        lm_f = self._lm(mesh, "1f1b", m)
        l_g = [float(lm_g.fit_batch(toks, tgts)) for _ in range(3)]
        l_f = [float(lm_f.fit_batch(toks, tgts)) for _ in range(3)]
        assert l_f[0] == l_g[0], (l_f[0], l_g[0])
        np.testing.assert_allclose(l_f, l_g, rtol=1e-5)

    def test_loss_parity_on_full_4d_mesh(self):
        """1F1B composes with TP psums + ring attention + DP: same loss
        trajectory as GPipe on a data x model x seq x pipe mesh."""
        mesh = build_mesh({"data": 1, "model": 2, "seq": 2, "pipe": 2})
        toks = RNG.integers(0, 64, (8, 16))
        tgts = np.roll(toks, -1, axis=1)
        kw = dict(vocab_size=64, n_layers=2, d_model=32, n_heads=4,
                  mesh=mesh, max_len=16, seed=7, n_microbatches=2)
        lm_g = ShardedTransformerLM(schedule="gpipe", **kw)
        lm_f = ShardedTransformerLM(schedule="1f1b", **kw)
        l_g = [float(lm_g.fit_batch(toks, tgts)) for _ in range(3)]
        l_f = [float(lm_f.fit_batch(toks, tgts)) for _ in range(3)]
        assert l_f[0] == l_g[0]
        np.testing.assert_allclose(l_f, l_g, rtol=1e-5)

    @pytest.mark.parametrize("m", [1, 4, 8])
    def test_gradient_parity_vs_sequential(self, m):
        """1F1B grads == unpipelined stack grads, including m=8 > 2S-1
        (the stage-input ring buffer's slot-reuse regime)."""
        mesh = build_mesh({"data": 2, "pipe": 4})
        params = _blocks(8, 16, seed=2)
        stacked = stack_stage_params(params)
        x = jax.random.normal(jax.random.PRNGKey(3), (16, 16))

        def loss_pp(sp, xx):
            return jnp.sum(pipeline_apply(
                _block_fn, sp, xx, mesh, n_microbatches=m,
                schedule="1f1b") ** 2)

        def loss_seq(plist, xx):
            h = xx
            for p in plist:
                h = _block_fn(p, h)
            return jnp.sum(h ** 2)

        g_pp, gx_pp = jax.grad(loss_pp, argnums=(0, 1))(stacked, x)
        g_seq = stack_stage_params(jax.grad(loss_seq)(params, x))
        gx_seq = jax.grad(loss_seq, argnums=1)(params, x)
        for a, b in zip(jax.tree_util.tree_leaves(g_pp),
                        jax.tree_util.tree_leaves(g_seq)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gx_pp), np.asarray(gx_seq),
                                   rtol=1e-4, atol=1e-5)


class TestPeakMemory:
    def test_compiled_temp_memory_lower_at_m_gt_s(self):
        """Measured gate: at M=8 microbatches > S=4 stages the compiled
        1F1B train step keeps strictly less temp memory (activation
        checkpoints) than GPipe."""
        mesh = build_mesh({"data": 2, "pipe": 4})
        toks = RNG.integers(0, 64, (16, 16))
        tgts = np.roll(toks, -1, axis=1)
        temp = {}
        for sched in ("gpipe", "1f1b"):
            lm = ShardedTransformerLM(vocab_size=64, n_layers=4, d_model=32,
                                      n_heads=4, mesh=mesh, max_len=16,
                                      seed=0, n_microbatches=8,
                                      schedule=sched)
            lm.fit_batch(toks, tgts)  # builds + compiles the jit step
            ma = lm._jit_step.lower(
                lm.params, lm.opt_state, jnp.asarray(0, jnp.int32),
                jnp.asarray(toks, jnp.int32), jnp.asarray(tgts, jnp.int32),
            ).compile().memory_analysis()
            temp[sched] = ma.temp_size_in_bytes
        assert temp["1f1b"] < temp["gpipe"], temp


class TestScheduleStats:
    @pytest.mark.parametrize("m,s", [(4, 2), (8, 4), (16, 4), (32, 8)])
    def test_gpipe_bubble_formula(self, m, s):
        stats = pipeline_schedule_stats("gpipe", m, s)
        assert stats["bubble_fraction"] == (s - 1) / (m + s - 1)

    @pytest.mark.parametrize("m,s", [(8, 2), (16, 4), (64, 8)])
    def test_1f1b_improves_bubble_at_memory_parity(self, m, s):
        """1F1B's lever: its peak activation memory is depth-bounded, so
        at a FIXED memory budget it affords far more microbatches than
        GPipe — and therefore a smaller bubble.  (At equal M its own grid
        idles more — the recompute and longer drain — which the stats
        report honestly.)"""
        lr = dict(layers_per_stage=2, residual_factor=12.0)
        f = pipeline_schedule_stats("1f1b", m, s, **lr)
        g = pipeline_schedule_stats("gpipe", m, s, **lr)
        assert f["peak_activation_units"] < g["peak_activation_units"]
        m_equiv = f["gpipe_microbatches_at_same_memory"]
        g_parity = pipeline_schedule_stats("gpipe", m_equiv, s, **lr)
        assert f["bubble_fraction"] < g_parity["bubble_fraction"]

    @pytest.mark.parametrize("m,s", [(8, 4), (16, 4), (16, 2)])
    def test_peak_live_stage_inputs_depth_bounded(self, m, s):
        f = pipeline_schedule_stats("1f1b", m, s)
        g = pipeline_schedule_stats("gpipe", m, s)
        assert f["peak_live_stage_inputs"] == min(m, 2 * s - 1) + 1
        assert g["peak_live_stage_inputs"] == m + s - 1
        assert f["peak_live_stage_inputs"] <= 2 * s  # depth, not M

    def test_rejects_unknown_schedule(self):
        with pytest.raises(ValueError, match="schedule"):
            pipeline_schedule_stats("pipedream", 4, 2)


class TestWiring:
    def test_pipeline_apply_rejects_unknown_schedule(self):
        mesh = build_mesh({"pipe": 2, "data": 4})
        stacked = stack_stage_params(_blocks(2, 8))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 8))
        with pytest.raises(ValueError, match="schedule"):
            pipeline_apply(_block_fn, stacked, x, mesh, schedule="zb-h1")

    def test_transformer_rejects_unknown_schedule(self):
        mesh = build_mesh({"data": 8})
        with pytest.raises(ValueError, match="schedule"):
            ShardedTransformerLM(vocab_size=64, n_layers=2, d_model=32,
                                 n_heads=4, mesh=mesh, max_len=16,
                                 schedule="interleaved")

    def test_trainer_forwards_schedule(self):
        from deeplearning4j_tpu.models import LeNet
        net = LeNet(height=8, width=8, channels=1, num_classes=4)
        trainer = ShardedTrainer(net, build_mesh({"data": 8}),
                                 pipeline_schedule="1f1b")
        assert trainer.pipeline_schedule == "1f1b"
        with pytest.raises(ValueError, match="pipeline_schedule"):
            ShardedTrainer(net, build_mesh({"data": 8}),
                           pipeline_schedule="nope")

    def test_cli_mesh_schedule_token(self):
        from deeplearning4j_tpu.cli import _parse_mesh
        axes, schedule, compress = _parse_mesh("data=2,pipe=4,schedule=1f1b")
        assert axes == {"data": 2, "pipe": 4}
        assert schedule == "1f1b"
        assert compress is None
        axes, schedule, compress = _parse_mesh("data=8")
        assert schedule == "gpipe"
        with pytest.raises(SystemExit, match="schedule"):
            _parse_mesh("data=8,schedule=fast")
        with pytest.raises(SystemExit, match="duplicate schedule"):
            _parse_mesh("data=8,schedule=gpipe,schedule=1f1b")


class TestSatellites:
    def test_child_xla_flags_preserved(self):
        """_run_in_subprocess must keep unrelated XLA_FLAGS and replace
        only the host-device-count token (satellite: the child previously
        lost e.g. memory-fraction or dump flags wholesale)."""
        import __graft_entry__ as ge
        out = ge._child_xla_flags(
            "--xla_dump_to=/tmp/d --xla_force_host_platform_device_count=8 "
            "--xla_cpu_enable_fast_math=false", 64)
        toks = out.split()
        assert "--xla_dump_to=/tmp/d" in toks
        assert "--xla_cpu_enable_fast_math=false" in toks
        assert "--xla_force_host_platform_device_count=64" in toks
        assert "--xla_force_host_platform_device_count=8" not in toks
        assert ge._child_xla_flags("", 16) == \
            "--xla_force_host_platform_device_count=16"

    def test_serializer_version_and_bf16_hint(self):
        from deeplearning4j_tpu.utils import serializer
        # v3 = v2 (bf16 uint16-view scheme) + optional grad_residual.npz
        # (compressed-exchange error feedback, tests/test_compression.py);
        # v4 adds per-entry integrity digests (tests/test_chaos.py)
        assert serializer.FORMAT_VERSION == 4
        assert 3 in serializer.SUPPORTED_VERSIONS
        with pytest.raises(KeyError, match="bfloat16"):
            serializer._unflatten_into({"a": jnp.zeros(2)}, {}, "")
