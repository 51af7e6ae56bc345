"""Bench-harness machinery: no fallback that hides the device (peak table
keyed by device_kind, a failed config fails the run, chip children only
before the parent holds a backend), the matmul ceiling probe, and the
measured collective microbench.

These test the MECHANISM on CPU (the numbers themselves are produced on
the chip).
"""

import importlib.util
import json
import os

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(_REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestNoFallbackThatHidesTheDevice:
    def test_unknown_device_kind_is_an_error_not_a_default(self, bench):
        # conftest pins CPU: its device_kind is in nobody's peak table
        assert jax.devices()[0].device_kind not in bench.PEAK_BF16_FLOPS
        with pytest.raises(KeyError, match="PEAK_BF16_FLOPS"):
            bench.peak_flops()

    def test_a_failed_config_makes_the_run_exit_nonzero(self, bench,
                                                        monkeypatch,
                                                        tmp_path, capsys):
        def boom():
            raise RuntimeError("config blew up")

        monkeypatch.setattr(bench, "_REPO", str(tmp_path))
        monkeypatch.setattr(bench, "_configs", lambda platform: [
            ("good", lambda: {"metric": "m", "value": 1.0, "unit": "u"}),
            ("bad", boom)])
        assert bench.main() == 1
        line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert line["failed"] == ["bad"]
        assert "value" not in line          # no made-up primary of 0.0
        assert line["device"]["platform"] == "cpu"
        assert [r.get("error") for r in line["results"]] == [
            None, "RuntimeError: config blew up"]
        monkeypatch.setattr(bench, "_configs", lambda platform: [
            ("good", lambda: {"metric": "m", "value": 1.0, "unit": "u"})])
        assert bench.main() == 0

    def test_chip_children_refused_once_the_parent_holds_a_backend(
            self, bench):
        jax.devices()                       # this process now has a backend
        with pytest.raises(RuntimeError, match="already initialised"):
            bench._kernel_ab("fused_update_ab.py")


class TestCeilingProbe:
    def test_probe_returns_positive_tfs(self, bench, monkeypatch):
        monkeypatch.setattr(bench, "QUICK", True)  # tiny shapes on CPU
        tfs = bench.probe_matmul_ceiling()
        assert tfs > 0


class TestCollectiveMicrobench:
    def test_multi_device_psum_shapes_and_rate(self, bench):
        # conftest pins 8 virtual CPU devices: the SAME code the chip
        # bench runs must produce correct collective results at n>1
        # (payload scaled to 1/10 — 8 emulated devices moving the full
        # 102 MB pytree costs ~2 min of tier-1 budget for no extra
        # shape coverage; the chip run keeps the default)
        assert len(jax.devices()) >= 2
        out = bench.bench_collective(n_params=2_560_000)
        assert out["metric"] == "psum_measured_gbps"
        assert out["value"] > 0 and out["ppermute_measured_gbps"] > 0
        assert out["n_devices"] == len(jax.devices())
        assert out["payload_mb"] == pytest.approx(10.24, rel=0.01)
