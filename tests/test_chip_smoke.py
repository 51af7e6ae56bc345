"""chip_smoke.py's contract, as far as a box without a chip can check it:
the explicit CPU rehearsal runs the whole train→serve script at tiny size
and can never read as a chip pass; without the rehearsal request the
script refuses — non-zero, no result — before it builds anything."""

import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one device: the one-chip layout
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *args],
        env=env, cwd=_REPO, capture_output=True, text=True, timeout=600)


def test_cpu_rehearsal_passes_and_is_not_a_chip_pass():
    p = _run("--rehearse-cpu")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    # last line: the verdict, exactly these keys, never "ok" off the chip
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["ok"] is False
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["kind"], str)
    assert verdict["device"]["count"] == 1
    # the line before it: the report
    out = json.loads(lines[-2])["report"]
    assert out["rehearsal_ok"] is True and out["failed"] == []
    assert "rehearsal" in out
    assert out["train"]["losses"][-1] < out["train"]["losses"][0]
    assert out["train"]["pallas_calls"] >= 3
    assert out["serve"]["requests"] >= 8
    assert out["serve"]["serve_time_compiles"] == 0
    assert out["serve"]["pages_in_use"] == 0
    # no rate from a CPU run under a device metric's name
    assert "tokens_per_s" not in out["train"]


def test_without_a_chip_it_refuses_before_building_anything():
    p = _run()
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "{" not in p.stdout and "train:" not in p.stdout
