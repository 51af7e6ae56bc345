"""Zero-cold-start serving: warmup bundles, the persistent compile
cache seam, and load-driven replica autoscaling.

The bundle contract under test (serving/warmcache.py): a fresh engine
``load(warm_bundle=...)`` deserializes AOT executables instead of
compiling (bitwise-identical serving, zero bundle misses), and ANY
unusable bundle — corrupt, truncated, wrong device fingerprint, wrong
tag — falls back to compiling with exactly one warning, never an error.
A missing bundle is the normal first-run case and stays silent.

The autoscaler contract (serving/autoscale.py + Engine supervisor):
pure hysteresis controller (consecutive-tick streaks, cooldown, bounds,
injectable clock), actuated by the engine's replica birth/retire
machinery — births re-warm from the shared AOT set (zero new compiles)
and retirement strands nothing.
"""

import json
import os
import warnings
import zipfile

import numpy as np
import pytest
from _decode_checks import save_bundle_or_skip

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.multilayer import (
    MultiLayerNetwork, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.updaters import Sgd
from deeplearning4j_tpu.serving import (
    Engine, ModelRegistry, ReplicaAutoscaler,
)
from deeplearning4j_tpu.serving import warmcache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mlp(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr=0.05))
            .layer(Dense(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(12)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def _xs(rows=4, seed=0):
    return np.random.default_rng(seed).normal(size=(rows, 12)).astype(
        np.float32)


def _engine(net, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("replicas", 1)
    kw.setdefault("slo_ms", 60_000)
    return Engine(net, **kw)


# ---------------------------------------------------------------------------
# warmup bundles
# ---------------------------------------------------------------------------

class TestWarmBundle:
    def test_round_trip_bitwise_and_flat_cache(self, tmp_path):
        """Warm-from-bundle load compiles nothing (zero misses), serves
        bitwise-identically to the cold arm, and the compile-cache
        witness stays flat in both arms."""
        net = _mlp()
        bundle = str(tmp_path / "m.zip.warm")
        cold = _engine(net).load()
        try:
            c0 = cold.compile_cache_size()
            out_cold = np.asarray(cold.output(_xs()))
            assert cold.compile_cache_size() == c0
            assert cold.metrics.counter_value("bundle_misses") == len(
                cold.batcher.buckets)
            assert cold.metrics.counter_value("warmup_seconds_total") > 0
            cold.save_warmup_bundle(bundle)
        finally:
            cold.shutdown()

        warm = _engine(net).load(warm_bundle=bundle)
        try:
            assert warm.compile_cache_size() == c0
            assert warm.metrics.counter_value("bundle_misses") == 0
            assert warm.metrics.counter_value("bundle_hits") == len(
                warm.batcher.buckets)
            out_warm = np.asarray(warm.output(_xs()))
            assert warm.compile_cache_size() == c0
            np.testing.assert_array_equal(out_cold, out_warm)
        finally:
            warm.shutdown()

    def test_missing_bundle_is_silent(self, tmp_path):
        """An absent bundle is the normal cold-start case: no warning,
        plain compile."""
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert warmcache.load_bundle(str(tmp_path / "nope.warm")) == {}
            eng = _engine(_mlp()).load(
                warm_bundle=str(tmp_path / "still_nope.warm"))
            try:
                assert eng.compile_cache_size() == len(eng.batcher.buckets)
            finally:
                eng.shutdown()
        assert [x for x in w if issubclass(x.category, RuntimeWarning)] == []

    @pytest.mark.parametrize("spoil", ["corrupt", "truncate", "fingerprint"])
    def test_unusable_bundle_falls_back_with_one_warning(self, tmp_path,
                                                         spoil):
        """Corrupt blob / truncated zip / wrong device fingerprint: the
        load still succeeds (compiles instead), serves correctly, and
        logs exactly one warning."""
        net = _mlp()
        bundle = str(tmp_path / "m.zip.warm")
        cold = _engine(net).load()
        out_ref = np.asarray(cold.output(_xs()))
        cold.save_warmup_bundle(bundle)
        cold.shutdown()

        if spoil == "corrupt":
            with open(bundle, "r+b") as f:
                f.seek(os.path.getsize(bundle) // 2)
                f.write(b"\x00" * 32)
        elif spoil == "truncate":
            with open(bundle, "r+b") as f:
                f.truncate(100)
        else:  # wrong device fingerprint — another topology's bundle
            spoiled = str(tmp_path / "spoiled.warm")
            with zipfile.ZipFile(bundle) as zin, \
                    zipfile.ZipFile(spoiled, "w") as zout:
                for name in zin.namelist():
                    b = zin.read(name)
                    if name == "meta.json":
                        meta = json.loads(b)
                        meta["fingerprint"] = "tpu|TPU v9|8192|99.99"
                        b = json.dumps(meta).encode()
                    zout.writestr(name, b)
            bundle = spoiled

        with pytest.warns(RuntimeWarning, match="falling back to compile"):
            eng = _engine(net).load(warm_bundle=bundle)
        try:
            assert eng.metrics.counter_value("bundle_hits") == 0
            assert eng.compile_cache_size() == len(eng.batcher.buckets)
            np.testing.assert_array_equal(out_ref,
                                          np.asarray(eng.output(_xs())))
        finally:
            eng.shutdown()

    def test_wrong_tag_falls_back(self, tmp_path):
        net = _mlp()
        bundle = str(tmp_path / "m.zip.warm")
        eng = _engine(net).load()
        eng.save_warmup_bundle(bundle)
        eng.shutdown()
        with pytest.warns(RuntimeWarning, match="tag"):
            assert warmcache.load_bundle(bundle, tag="someone-else") == {}

    def test_save_without_aot_or_path_raises(self, tmp_path):
        class Duck:
            def output(self, x):
                return np.zeros((x.shape[0], 1), np.float32)

        eng = Engine(Duck(), max_batch=4, replicas=1, slo_ms=60_000)
        eng.load(input_shape=(3,))
        try:
            with pytest.raises(RuntimeError, match="no AOT executables"):
                eng.save_warmup_bundle(str(tmp_path / "x.warm"))
        finally:
            eng.shutdown()
        eng2 = _engine(_mlp()).load()
        try:
            with pytest.raises(ValueError, match="path"):
                eng2.save_warmup_bundle()  # no checkpoint provenance
        finally:
            eng2.shutdown()


# ---------------------------------------------------------------------------
# registry provenance: <checkpoint>.warm rides the load/swap seams
# ---------------------------------------------------------------------------

class TestRegistryBundleProvenance:
    @pytest.mark.parametrize("fmt", [1, 2, 3, 4])
    def test_checkpoint_round_trip_every_format_version(self, tmp_path, fmt):
        """save → registry.load (any serializer FORMAT_VERSION) → engine
        cold load → save_warmup_bundle() lands at <checkpoint>.warm by
        provenance → a SECOND engine over the same registry warms from
        it automatically, bitwise-identically."""
        net = _mlp(seed=fmt)
        p = str(tmp_path / "m_v4.zip")
        net.save(p)
        if fmt < 4:
            p_old = str(tmp_path / f"m_v{fmt}.zip")
            with zipfile.ZipFile(p) as zin, \
                    zipfile.ZipFile(p_old, "w") as zout:
                for name in zin.namelist():
                    b = zin.read(name)
                    if name == "meta.json":
                        meta = json.loads(b)
                        del meta["integrity"]  # v1-v3 carried no digests
                        meta["format_version"] = fmt
                        b = json.dumps(meta).encode()
                    zout.writestr(name, b)
            p = p_old
        reg = ModelRegistry()
        v = reg.load("m", p)
        assert reg.checkpoint_path("m", v) == p
        reg.set_alias("m", "prod", v)

        cold = Engine.from_registry(reg, "m", "prod", max_batch=4,
                                    slo_ms=60_000).load()
        out_ref = np.asarray(cold.output(_xs()))
        written = cold.save_warmup_bundle()  # path from provenance
        cold.shutdown()
        assert written == warmcache.bundle_path_for(p)
        assert os.path.exists(written)

        warm = Engine.from_registry(reg, "m", "prod", max_batch=4,
                                    slo_ms=60_000).load()
        try:
            assert warm.metrics.counter_value("bundle_misses") == 0
            assert warm.metrics.counter_value("bundle_hits") > 0
            np.testing.assert_array_equal(out_ref,
                                          np.asarray(warm.output(_xs())))
        finally:
            warm.shutdown()

    def test_in_memory_registration_has_no_provenance(self):
        reg = ModelRegistry()
        v = reg.register("m", _mlp())
        assert reg.checkpoint_path("m", v) is None
        assert reg.checkpoint_path("ghost") is None


# ---------------------------------------------------------------------------
# the load controller (pure; fake clock per GC201)
# ---------------------------------------------------------------------------

class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _controller(**kw):
    clock = kw.pop("clock", _FakeClock())
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("up_load", 2.0)
    kw.setdefault("down_load", 0.25)
    kw.setdefault("up_ticks", 2)
    kw.setdefault("down_ticks", 3)
    kw.setdefault("cooldown_s", 5.0)
    return ReplicaAutoscaler(clock=clock, **kw), clock


class TestReplicaAutoscaler:
    def test_hysteresis_needs_consecutive_high_ticks(self):
        a, _ = _controller(up_ticks=3)
        assert a.observe(10, 2, 1) == 0
        assert a.observe(10, 2, 1) == 0
        assert a.observe(10, 2, 1) == 1  # third consecutive high tick

    def test_streak_resets_on_a_calm_tick(self):
        a, _ = _controller(up_ticks=2)
        assert a.observe(10, 2, 1) == 0
        assert a.observe(0, 1, 1) == 0   # mid load: both streaks reset
        assert a.observe(10, 2, 1) == 0  # streak restarted
        assert a.observe(10, 2, 1) == 1

    def test_cooldown_blocks_back_to_back_actions(self):
        a, clock = _controller(up_ticks=1, cooldown_s=5.0)
        assert a.observe(10, 2, 1) == 1
        assert a.observe(10, 2, 2) == 0   # inside the cooldown window
        clock.t += 5.1
        assert a.observe(10, 2, 2) == 1

    def test_bounds_clamp_both_directions(self):
        a, clock = _controller(up_ticks=1, down_ticks=1, max_replicas=2)
        assert a.observe(10, 2, 2) == 0   # already at max: no up
        clock.t += 10
        assert a.observe(0, 0, 1) == 0    # already at min: no down

    def test_scale_down_after_sustained_idle(self):
        a, clock = _controller(down_ticks=3)
        for _ in range(2):
            assert a.observe(0, 0, 3) == 0
        assert a.observe(0, 0, 3) == -1
        clock.t += 10
        assert a.observe(0, 0, 3) == 0    # streak consumed by the action
        assert a.observe(0, 0, 3) == 0
        assert a.observe(0, 0, 3) == -1

    def test_shed_delta_counts_as_high_signal(self):
        """Sheds mean the queue bound is already saturating — the
        controller must react even when the sampled depth looks calm."""
        a, _ = _controller(up_ticks=2)
        assert a.observe(0, 0, 1, shed_delta=3) == 0
        assert a.observe(0, 0, 1, shed_delta=1) == 1

    def test_load_signal_is_per_replica(self):
        a, _ = _controller()
        assert a.load(6, 2, 4) == 2.0
        assert a.load(0, 0, 0) == 0.0  # replica floor guards div-zero

    def test_knob_validation(self):
        with pytest.raises(ValueError):
            ReplicaAutoscaler(min_replicas=0)
        with pytest.raises(ValueError):
            ReplicaAutoscaler(min_replicas=3, max_replicas=2)
        with pytest.raises(ValueError):
            ReplicaAutoscaler(up_load=1.0, down_load=1.5)


# ---------------------------------------------------------------------------
# engine integration: birth/retire actuation
# ---------------------------------------------------------------------------

class TestEngineAutoscale:
    def test_burst_scales_up_idle_scales_down_zero_compiles(self):
        """Sustained deep queue births a replica (re-warmed from the
        shared AOT set — the compile-cache witness must not move); after
        the burst drains, idle ticks retire it; every future resolves."""
        eng = _engine(_mlp(), max_queue=100_000, admission="block",
                      max_wait_ms=0.5).load()
        try:
            c0 = eng.compile_cache_size()
            eng.enable_autoscale(min_replicas=1, max_replicas=2,
                                 up_load=8.0, down_load=0.5, up_ticks=2,
                                 down_ticks=4, cooldown_s=0.3,
                                 interval_s=0.03)
            rng = np.random.default_rng(0)
            futs = []
            import time
            deadline = time.monotonic() + 20.0
            while (eng.metrics.counter_value("scale_ups") < 1
                   and time.monotonic() < deadline):
                for _ in range(200):
                    futs.append(eng.output_async(
                        rng.normal(size=(1, 12)).astype(np.float32),
                        slo_ms=600_000))
            for f in futs:
                f.result(timeout=120)
            assert eng.metrics.counter_value("scale_ups") >= 1
            assert len(eng._replicas) == 2
            # the only growth allowed is the birth warmup for the new
            # replica's device (executables are device-committed; on a
            # single-device host this is zero) — never per-request
            c_peak = eng.compile_cache_size()
            assert c_peak - c0 <= len(eng.batcher.buckets)
            deadline = time.monotonic() + 20.0
            while (eng.metrics.counter_value("scale_downs") < 1
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert eng.metrics.counter_value("scale_downs") >= 1
            assert len(eng._replicas) == 1
            assert eng.compile_cache_size() == c_peak
            assert all(f.done() for f in futs)
        finally:
            eng.shutdown()

    def test_disabled_by_default(self):
        eng = _engine(_mlp()).load()
        try:
            assert eng._autoscaler is None
            eng.output(_xs())
            assert eng.metrics.counter_value("scale_ups") == 0
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# decode engine: bundle seams + callback actuator
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_lm():
    import jax

    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:1])
    return ShardedTransformerLM(vocab_size=32, n_layers=1, d_model=16,
                                n_heads=2, max_len=16, mesh=mesh, seed=11)


class TestDecodeWarmBundle:
    def test_round_trip_identical_tokens_zero_misses(self, small_lm,
                                                     tmp_path):
        from deeplearning4j_tpu.serving import DecodeEngine

        bundle = str(tmp_path / "lm.zip.warm")
        cold = DecodeEngine(small_lm, max_slots=2, page_size=4,
                            default_max_new=4).load()
        try:
            ref = cold.generate([1, 2, 3], max_new_tokens=6).tokens
            n_exec = cold.compile_cache_size()
            assert len(ref) == 6 and n_exec > 0
            save_bundle_or_skip(cold, bundle)
        finally:
            cold.shutdown()

        warm = DecodeEngine(small_lm, max_slots=2, page_size=4,
                            default_max_new=4).load(warm_bundle=bundle)
        try:
            assert warm.metrics.counter_value("bundle_misses") == 0
            assert warm.metrics.counter_value("bundle_hits") == n_exec
            assert warm.compile_cache_size() == n_exec
            assert warm.generate([1, 2, 3], max_new_tokens=6).tokens == ref
        finally:
            warm.shutdown()

    def test_bundle_before_load_raises(self, small_lm, tmp_path):
        from deeplearning4j_tpu.serving import DecodeEngine

        eng = DecodeEngine(small_lm, max_slots=2, page_size=4)
        with pytest.raises(RuntimeError, match="load"):
            eng.save_warmup_bundle(str(tmp_path / "x.warm"))


class TestDecodeAutoscaleActuator:
    def test_scripted_decisions_drive_callback_and_counters(self, small_lm):
        """Decode capacity is compile-shape-fixed, so the actuator is a
        callback (the fleet tier owns physical scaling).  Script the
        controller so the test exercises actuation — callback args,
        logical replica tracking, scale counters — without burst
        timing."""
        import time

        from deeplearning4j_tpu.serving import DecodeEngine

        class Scripted:
            def __init__(self, decisions):
                self.decisions = list(decisions)

            def observe(self, queue_depth, inflight, replicas, shed_delta=0):
                return self.decisions.pop(0) if self.decisions else 0

        calls = []
        eng = DecodeEngine(small_lm, max_slots=2, page_size=4,
                           default_max_new=4).load()
        try:
            eng.enable_autoscale(lambda d, n: calls.append((d, n)),
                                 autoscaler=Scripted([1, 1, -1]),
                                 interval_s=0.0)
            deadline = time.monotonic() + 10.0
            while len(calls) < 3 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert calls == [(1, 2), (1, 3), (-1, 2)]
            assert eng.metrics.counter_value("scale_ups") == 2
            assert eng.metrics.counter_value("scale_downs") == 1
            # the engine keeps serving across scale events
            assert len(eng.generate([4, 5], max_new_tokens=3).tokens) == 3
        finally:
            eng.shutdown()


# ---------------------------------------------------------------------------
# persistent compile cache seam
# ---------------------------------------------------------------------------

_RESOLVE = r"""
import json, os, sys
import jax
sys.path.insert(0, sys.argv[1])
dir_updates = []
_update = jax.config.update
def spy(name, val):
    if name == "jax_compilation_cache_dir":
        dir_updates.append(val)
    _update(name, val)
jax.config.update = spy
from deeplearning4j_tpu.serving import warmcache
before = jax.config.jax_compilation_cache_dir
ret = warmcache.enable_compile_cache()
assert warmcache.enable_compile_cache() == ret          # idempotent
jax.jit(lambda x: x * 3.0 + 1.0)(jax.numpy.arange(8.0)).block_until_ready()
print(json.dumps({
    "before": before, "ret": ret, "dir_updates": dir_updates,
    "after": jax.config.jax_compilation_cache_dir,
    "env": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
    "default": warmcache.DEFAULT_CACHE_DIR,
    "entries": len([f for f in os.listdir(ret) if f.endswith("-cache")])}))
"""


class TestEnableCompileCache:
    """The cache directory is placed from OUTSIDE the program."""

    def _resolve(self, env_dir):
        import subprocess
        import sys
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_ENABLE_COMPILATION_CACHE="true")
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        p = subprocess.run([sys.executable, "-c", _RESOLVE, _REPO],
                           env=env, capture_output=True, text=True,
                           timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    def test_env_set_is_used_and_never_overridden(self, tmp_path):
        d = str(tmp_path / "x")
        out = self._resolve(d)
        # jax read the variable itself; our code set no directory at all
        assert out["before"] == out["after"] == out["ret"] == out["env"] == d
        assert out["dir_updates"] == []
        assert out["entries"] >= 1          # and entries land in it

    def test_env_unset_falls_back_to_checkout_cache(self):
        out = self._resolve(None)
        want = os.path.join(_REPO, ".cache", "jax-compile")
        assert out["default"] == want
        assert out["before"] is None
        assert out["after"] == out["ret"] == want
        assert out["dir_updates"] == [want]
        # exported so forked workers (launch) share it
        assert out["env"] == want
        assert out["entries"] >= 1

    def test_fingerprint_pins_backend_topology_and_version(self):
        import jax
        fp = warmcache.device_fingerprint()
        parts = fp.split("|")
        assert parts[0] == jax.default_backend()
        assert parts[2] == str(len(jax.devices()))
        assert parts[3] == jax.__version__
