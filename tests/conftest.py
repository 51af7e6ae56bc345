"""Test configuration: force CPU with 8 virtual devices.

This is the reference's `local[N]` Spark-test analog (SURVEY.md §4.5): all
multi-device/sharding tests run on a virtual 8-device CPU mesh, no TPU pod
required.  The tier-1 command already exports JAX_PLATFORMS=cpu; the
config update below makes a bare ``pytest`` behave the same, and the
XLA_FLAGS device-count flag is read when the CPU client is created.
"""

import os
import sys

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

_repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _repo_root)

import pytest  # noqa: E402

# Persistent compile cache, scoped to an allowlist of test modules.
#
# Compilation dominates the suite's wall clock: every DecodeEngine /
# fleet host load AOT-compiles ~10 executables, and the trainer tests
# re-jit the same tiny models across modules and runs.  Turning on the
# repo's own warmcache.enable_compile_cache (a gitignored dir under the
# repo unless JAX_COMPILATION_CACHE_DIR says otherwise) makes repeat runs
# (and the tier-1 verify) hit warm executables — measured ~3x faster on test_decode and
# test_pipeline_1f1b, 2.5x on test_parallelism_4d, bit-identical by
# construction (the cache stores serialized XLA executables keyed by
# HLO).  The serving executables are the same ones PR 15's warmup
# bundles serialize/deserialize in production, so their reload path is
# battle-tested.
#
# Allowlisted, not suite-wide, deliberately: on this jaxlib build SOME
# trainer-side executables (two-tier compression, the chaos-guarded
# train step) segfault nondeterministically at execution time when
# reloaded from the on-disk cache — reproduced with clean,
# fully-written entries.  Every module below was validated by a
# fresh-cache cold run followed by a fully-warm rerun; the unsafe
# modules run with the cache off.
# (enable_compile_cache also hardens jax's cache writes to temp+rename
# — this suite SIGKILLs workers mid-step, and a stranded half-written
# entry would otherwise deserialize as garbage.)
_CACHE_SAFE_MODULES = {
    "test_attention",
    "test_backend_parity",
    "test_data_records",
    "test_decode",
    "test_decode_speed",
    "test_disagg",
    "test_examples",
    "test_fit_batches",
    "test_fleet",
    "test_graph_recurrent",
    "test_lstm_kernel",
    "test_moe",
    "test_parallelism_4d",
    "test_pipeline_1f1b",
    "test_regularizers_solvers",
    "test_serving_resilience",
    "test_ulysses",
    "test_updaters_bf16",
    "test_zoo",
}
# test_warmcache is deliberately absent: it exercises the warmup-bundle
# machinery itself, and on this jaxlib serialize_executable on an
# executable that was RELOADED from the compile cache emits a payload
# with dangling fusion symbols ("Symbols not found" at deserialize) —
# bundles must be built from cold-compiled executables.
# test_multichip_scale is absent too: its subprocesses re-run the same
# program at DIFFERENT device counts (8 -> 16), and a warm reload
# across that boundary trained wrong (silent bad numerics, not a
# crash) on this jaxlib.


def _set_cache(on: bool) -> None:
    """Flip jax's OWN switch, in this process and (via the env var) in
    every child it spawns: the CLI's compile cache is on by default, and
    it must not reach the trainer-side workers (chaos / launcher / cli)
    the unsafe modules fork.  The directory is placed from outside
    (JAX_COMPILATION_CACHE_DIR, else <checkout>/.cache/jax-compile)."""
    from jax.experimental.compilation_cache import compilation_cache as _cc

    from deeplearning4j_tpu.serving.warmcache import enable_compile_cache
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "true" if on else "false"
    jax.config.update("jax_enable_compilation_cache", on)
    if on:
        enable_compile_cache()
        # plain-jax children (example scripts) persist small programs too
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    _cc.reset_cache()   # jax latches "cache used?" at its first compile


@pytest.fixture(autouse=True, scope="module")
def _scoped_compile_cache(request):
    name = request.module.__name__.rpartition(".")[2]
    _set_cache(name in _CACHE_SAFE_MODULES)
    yield
    _set_cache(False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running load/soak tests, deselected in tier-1 (-m 'not slow')")
