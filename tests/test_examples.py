"""Every example script must run end-to-end on CPU (round-4 verdict
Next #6: the reference ships 8 runnable tutorials; these are the
equivalent user journeys, CI-tested).

Each runs in its own process under JAX_PLATFORMS=cpu (some pin device
counts) and must print the final "OK" its internal assertions guard."""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_EX = os.path.join(_REPO, "examples")

SCRIPTS = sorted(f for f in os.listdir(_EX)
                 if f.endswith(".py") and f[0].isdigit())


def test_all_tutorial_numbers_present():
    # the reference arc is 8 tutorials + the TPU flagship + decode serving
    nums = {s.split("_")[0] for s in SCRIPTS}
    assert nums == {"01", "02", "03", "04", "05", "06", "07", "08", "09",
                    "10"}


@pytest.mark.parametrize("script", SCRIPTS)
def test_example_runs(script):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # give example 09 a multi-device mesh to shard over
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    p = subprocess.run([sys.executable, os.path.join(_EX, script)],
                       env=env, cwd=_EX, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, (
        f"{script} failed:\nstdout:\n{p.stdout[-2000:]}\n"
        f"stderr:\n{p.stderr[-3000:]}")
    assert "OK" in p.stdout
