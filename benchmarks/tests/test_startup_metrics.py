"""The five ``startup_*`` per-layer metrics (PR 39): against rehearsal
runs of a training and a serving cell, which fill the program's start-up
account, and against what a program without the account gives.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_startup_metrics.py -q

``tests/test_startup_metrics.py`` runs the same cases where tier-1
collects them.
"""

import json
import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness                # noqa: E402
from benchmarks.tests import rehearsal        # noqa: E402

MANIFEST = harness.load_manifest()
STARTUP = ["startup_program_s", "startup_trace_lower_s", "startup_compile_s",
           "startup_cache_read_s", "startup_first_run_s"]
# the Solar cell's own test pins the whole set of metrics it reports
# (test_solar_open2_250b.py), so the cell stays off these lists until a
# ``benchmark`` PR relaxes that pin (PERF.md section 7 row 15)
PINNED = "solar-open2-250b.longdoc-closed64"
CELLS = [w["name"] for w in MANIFEST["workloads"] if w["name"] != PINNED]
SERVED = [c for c in next(
    m for m in MANIFEST["end_to_end"]
    if m["name"] == "serve_tokens_per_s")["workloads"] if c != PINNED]


def _read(metric, observed):
    return harness.load_layer_metric(metric).read(observed)


def _observed(cell):
    return harness.Observed(cell=types.SimpleNamespace(name=cell, config={}),
                            window={}, counters={})


@pytest.mark.parametrize("metric", STARTUP)
def test_the_entry_is_a_start_up_metric_under_setup_s(metric):
    m = harness.find(MANIFEST["per_layer"], metric, "metric")
    assert (m["layer"], m["moves"], m["source"], m["unit"], m["better"]) == (
        "start-up", "setup_s", "program_counter", "s", "lower")
    assert m["workloads"] == (
        SERVED if metric == "startup_first_run_s" else CELLS)


@pytest.mark.parametrize("metric", STARTUP)
def test_a_reader_reads_nothing_without_a_cell(metric):
    """``check_per_layer_metric``'s empty ``Observed``: nothing, though
    the process may hold an account by now."""
    nothing = harness.Observed(cell=None, window={}, counters={})
    assert _read(metric, nothing) is None


@pytest.mark.parametrize("metric", STARTUP)
def test_a_program_without_the_account_reads_nothing(metric, monkeypatch):
    """The parent: the registry is there, the counters are not."""
    from deeplearning4j_tpu import obs
    from deeplearning4j_tpu.obs.metrics import MetricsRegistry

    bare = MetricsRegistry()
    monkeypatch.setattr(obs, "get_registry", lambda: bare)
    assert _read(metric, _observed("gpt2-large.chat-closed8")) is None


@pytest.fixture(scope="module")
def rehearsed():
    """One traced rehearsal of a training and of a serving cell: their
    result lines, and the process's account after both."""
    import contextlib
    import io

    lines = {}
    for cell in ("gpt2-medium.train-1k", "gpt2-large.chat-closed8"):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = harness.main(["--workload", cell, "--seed", "4294967311",
                               "--seconds", "3", "--trace", "1"],
                              rehearsal=rehearsal.CELLS[cell])
        assert rc == 0, out.getvalue()
        lines[cell] = out.getvalue().strip().splitlines()
    return lines


@pytest.mark.parametrize("cell", ["gpt2-medium.train-1k",
                                  "gpt2-large.chat-closed8"])
def test_a_traced_rehearsal_reports_the_start_up_metrics(rehearsed, cell):
    result = json.loads(rehearsed[cell][-1])
    assert result["correct"] is True
    want = {m["name"] for m in
            harness.metrics_of_cell(MANIFEST, "per_layer", cell)
            if m["name"] in STARTUP}
    assert want == set(STARTUP) - ({"startup_first_run_s"}
                                   if "train" in cell else set())
    assert want <= set(result["metrics"])
    # a rehearsal prints no number under a metric's name
    assert all(result["metrics"][m]["value"] is None for m in want)
    assert any("start-up account: compile cache hits" in ln
               for ln in rehearsed[cell])


def test_the_parts_do_not_exceed_the_whole(rehearsed):
    """After both rehearsals the account holds an LM's constructor (two),
    a first step and an engine's load: the compile seconds inside the
    phases are no more than the phases (nothing counted twice), and the
    first runs are part of the load."""
    obs = _observed("gpt2-large.chat-closed8")
    program = _read("startup_program_s", obs)
    parts = [_read(m, obs) for m in ("startup_trace_lower_s",
                                     "startup_compile_s",
                                     "startup_cache_read_s")]
    first_run = _read("startup_first_run_s", obs)
    assert program > 0 and all(p is not None and p >= 0 for p in parts)
    assert parts[0] > 0                       # something was traced
    assert parts[1] + parts[2] > 0            # and compiled, or read
    assert sum(parts) <= program
    acc = harness.load_layer_metric("startup_program_s").account(obs)
    assert {"lm/init", "train/first_step", "serve/load"} <= set(acc["phases"])
    assert 0 < first_run < acc["phases"]["serve/load"]
    covered = sum(acc["phases"][p] for p in
                  ("serve/lower", "serve/compile", "serve/first_run"))
    assert covered <= acc["phases"]["serve/load_executable"] \
        <= acc["phases"]["serve/load"]


def test_what_the_benchmark_compiles_itself_stays_out(rehearsed):
    """The seeded weights, the warm requests and the reference compile
    outside every phase (``phase=none``): counted by the program, left
    out by the readers."""
    pm = harness.load_layer_metric("startup_program_s")
    outside = sum(v for lb, v in pm.series("compile_seconds_total")
                  if lb["phase"] == pm.NO_PHASE)
    assert outside > 0
    acc = pm.account(_observed("gpt2-medium.train-1k"))
    inside = sum(acc["compile"].values())
    total = sum(v for _, v in pm.series("compile_seconds_total"))
    assert abs(inside + outside - total) < 1e-9
