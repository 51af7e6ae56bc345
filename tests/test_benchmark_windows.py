"""How long a cell's window is, as its traffic file's data (PR 36): the
cases of ``benchmarks/tests/test_benchmark.py`` that guard ``windows``,
kept here too because tier-1 collects ``tests/`` and not
``benchmarks/tests`` (PERF.md section 7, row 28 (b); the precedent is
``tests/test_paged_attention_metrics.py``)."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness, traffic      # noqa: E402
from benchmarks.tests import rehearsal        # noqa: E402


@pytest.mark.parametrize("bad", [0, 1.5, "2", True, -1], ids=repr)
def test_a_mix_whose_windows_are_no_whole_number_from_1_up_is_refused(bad):
    with pytest.raises(ValueError) as e:
        traffic.check_mix({"kind": "train", "windows": bad},
                          "traffic/made-up.json")
    assert "traffic/made-up.json" in str(e.value) and "windows" in str(e.value)
    assert repr(bad) in str(e.value)


@pytest.mark.parametrize("kind", ["train", "requests"])
def test_a_mix_may_state_its_windows(kind):
    mix = {"kind": kind, "loop": "closed", "windows": 3}
    assert traffic.check_mix(mix, "x") is mix


MIXES = sorted(f[:-len(".json")] for f in
               os.listdir(os.path.join(ROOT, "benchmarks", "traffic")))


@pytest.mark.parametrize("name", MIXES)
def test_a_traffic_file_reads_as_it_is_written(name):
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) as f:
        written = json.load(f)
    assert traffic.load_mix(name) == written
    assert written.get("windows", 1) >= 1


def _opened(cell, seconds, **mix):
    reh = rehearsal.CELLS[cell]
    return harness.open_cell(cell, 4294967311, seconds, False,
                             {**reh, "mix": {**reh["mix"], **mix}})[1]


def test_open_cell_multiplies_the_commands_seconds_by_the_mixs_windows(capsys):
    cell = _opened("gpt2-large.chat-closed8", 51.0, windows=3)
    assert (cell.seconds, cell.windows) == (153.0, 3)
    assert "window 3 x 51.0s = 153.0s, trace 0" in capsys.readouterr().out
    cell = _opened("gpt2-medium.train-1k", 51.0)
    assert (cell.seconds, cell.windows) == (51.0, 1)
    assert "window 51.0s, trace 0" in capsys.readouterr().out
    for bad in (0, 1.5, "2", True):
        with pytest.raises(ValueError):
            _opened("gpt2-medium.train-1k", 51.0, windows=bad)


def test_a_longer_window_is_traced_for_the_same_three_seconds(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    tr = harness.Tracer(True, "d")
    for t in (0.0, 9.9, 10.0, 12.9, 13.0, 30.0):
        tr.tick(t, 102.0)
        assert tr.stopped == (t >= 13.0)
    tr.stop()
    assert calls == [("start", "d"), ("stop",)] and tr.started_at == 10.0
