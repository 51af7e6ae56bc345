"""The benchmark's own arithmetic and wiring, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import copy
import json
import os
import re
import statistics
import subprocess
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np      # noqa: E402
import pytest           # noqa: E402

from benchmarks import harness, opcounts, stats, tracing, traffic  # noqa: E402
from benchmarks.kernels import flash_attention as fa                # noqa: E402
from benchmarks.tests import rehearsal                              # noqa: E402

MANIFEST = harness.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- traffic ------------------------------------------------------------------

def _take(it, n):
    return [next(it) for _ in range(n)]


def test_requests_repeat_for_a_seed_and_differ_between_seeds():
    mix = traffic.load_mix("chat-closed8")
    a = _take(traffic.request_stream(mix, 4294967311, 50257), 200)
    b = _take(traffic.request_stream(mix, 4294967311, 50257), 200)
    c = _take(traffic.request_stream(mix, 7, 50257), 200)
    key = lambda r: (r.prompt.tolist(), r.max_new_tokens, r.temperature,
                     r.top_k, r.seed)
    assert [key(r) for r in a] == [key(r) for r in b]
    assert [key(r) for r in a] != [key(r) for r in c]


def test_every_seed_does_the_same_work_in_another_order():
    mix = traffic.load_mix("chat-closed8")
    n = mix["n_sizes"]
    rounds = [_take(traffic.request_stream(mix, s, 50257), n) for s in (1, 2)]
    prompts = [sorted(len(r.prompt) for r in rnd) for rnd in rounds]
    kinds = [sorted(r.temperature for r in rnd) for rnd in rounds]
    assert prompts[0] == prompts[1] and kinds[0] == kinds[1]
    assert [len(r.prompt) for r in rounds[0]] != [len(r.prompt) for r in rounds[1]]
    assert sum(r.greedy for r in rounds[0]) == n // 2
    for r in rounds[0]:
        assert 16 <= len(r.prompt) <= 512 and 1 <= r.max_new_tokens <= 256
        assert len(r.prompt) + r.max_new_tokens <= mix["max_total_tokens"]


def test_quantile_sizes_match_the_stated_distribution():
    sizes = traffic.quantile_sizes(
        {"dist": "lognormal", "median": 100, "sigma": 0.9, "min": 16,
         "max": 512}, 96)
    assert statistics.median(sizes) == pytest.approx(100, abs=2)
    assert min(sizes) == 16 and max(sizes) == 512
    with pytest.raises(ValueError):
        traffic.quantile_sizes({"dist": "uniform", "min": 0, "max": 10}, 5)


def test_a_mix_that_is_not_a_closed_loop_is_refused():
    closed = {"kind": "requests", "loop": "closed"}
    assert traffic.check_mix(closed, "x") is closed
    for loop in ("open", None):
        with pytest.raises(ValueError):
            traffic.check_mix({"kind": "requests", "loop": loop}, "x")


# -- how long a cell's window is, as the mix's data --------------------------------

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
    """``load_mix`` adds nothing: a file without the keys has none, and
    the harness then runs one window of ``--seconds``, as before there
    was the key."""
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) as f:
        written = json.load(f)
    assert traffic.load_mix(name) == written
    assert written.get("windows", 1) >= 1


def _rehearsal_with(cell, **mix):
    """The cell's tiny sizes with ``mix`` laid over its traffic."""
    reh = rehearsal.CELLS[cell]
    return {**reh, "mix": {**reh["mix"], **mix}}


def _opened(cell, seconds, **mix):
    return harness.open_cell(cell, 4294967311, seconds, False,
                             _rehearsal_with(cell, **mix))[1]


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


def test_train_batches_repeat_for_a_seed_and_rows_differ():
    mix = traffic.load_mix("train-1k")
    a = next(traffic.train_batches(mix, 3000000019, 1, 50257))
    b = next(traffic.train_batches(mix, 3000000019, 1, 50257))
    c = next(traffic.train_batches(mix, 3000000020, 1, 50257))
    assert np.array_equal(a[0], b[0]) and not np.array_equal(a[0], c[0])
    assert a[0].shape == (12, 1024) and a[0].dtype == np.int32
    assert np.array_equal(a[0][:, 1:], a[1][:, :-1])     # targets: shifted by one
    assert len({row.tobytes() for row in a[0]}) == 12
    four = next(traffic.train_batches(traffic.load_mix("train-1k-dp4"), 1, 4, 50257))
    assert four[0].shape == (48, 1024)


# -- percentiles, rates, spreads -------------------------------------------------

def test_percentiles_and_rates_on_known_inputs():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 50) == 30.0 == stats.median(xs)
    assert stats.percentile(xs, 0) == 10.0 and stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile([3.0, 1.0], 50) == 2.0
    assert stats.rate(1007616, 46.0) == pytest.approx(21904.7, abs=0.1)
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    runs = [92.1, 92.4, 92.7, 92.9, 93.1, 93.5]
    q1, _, q3 = statistics.quantiles(runs, n=4)
    assert stats.quartile_spread(runs) == pytest.approx((q3 - q1) / 92.8)


# -- operations and bytes, against hand-worked values for GPT-2 medium --------------

def test_op_counts_for_gpt2_medium():
    cfg = harness.load_config("gpt2-medium")
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 50257
    assert opcounts.matmul_params(cfg) == 353_453_056
    # 2 x 353,453,056 + 24 layers x 2 products x 2 x 1024 x 1025 / 2
    assert opcounts.forward_flops_per_token(cfg, 1024) == 757_286_912
    assert opcounts.train_flops_per_token(cfg, 1024) == 2_271_860_736
    # 21,800 tokens/s on one chip of 197 TFLOP/s: about a quarter of the peak
    peak = harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert 2_271_860_736 * 21_800 / peak == pytest.approx(0.2514, abs=1e-4)
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")


def test_flash_kernel_costs_for_gpt2_medium():
    fwd = fa.classify("(bf16[192,1024,64], f32[192,1,1024])")
    dkdv = fa.classify("(bf16[192,1024,64], bf16[192,1024,64])")
    dq = fa.classify("bf16[192,1024,64]")
    assert fwd == ("forward", 192, 1024, 64, 2)
    assert dkdv[0] == "backward_dkdv" and dq[0] == "backward_dq"
    assert fa.classify("f32[]") is None
    # one causal product: 2 x 192 x 1024^2 x 64 / 2 = 12,884,901,888
    assert fa.flops("forward", 192, 1024, 64) == 2 * 12_884_901_888
    assert fa.flops("backward_dkdv", 192, 1024, 64) == 4 * 12_884_901_888
    assert fa.flops("backward_dq", 192, 1024, 64) == 3 * 12_884_901_888
    # q k v o at 192 x 1024 x 64 x 2 bytes, lse at 192 x 1024 x 4
    assert fa.bytes_moved("forward", 192, 1024, 64, 2) == 4 * 25_165_824 + 786_432
    peaks = harness.load_peaks("TPU v5 lite")
    secs, bound = fa.least_seconds(*fwd, peaks)
    assert bound == "compute" and secs == pytest.approx(25_769_803_776 / 197e12)


# -- the trace reduction, on a trace recorded on the v5e -----------------------------

def test_trace_reduction_on_the_recorded_trace():
    here = os.path.join(ROOT, "benchmarks", "fixture")
    with open(os.path.join(here, "recorded.expect.json")) as f:
        want = json.load(f)
    got = tracing.reduce_xplane(os.path.join(here, "recorded.xplane.pb"))
    assert got.devices == want["devices"]
    assert got.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert got.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert got.op_seconds() == pytest.approx(want["op_seconds"], rel=1e-9)
    assert [list(r) for r in got.module_table()] == \
        [[n, c, pytest.approx(s)] for n, c, s in want["modules"]]
    assert got.idle_gaps() == pytest.approx(want["idle_gaps"], rel=1e-9)
    assert got.busy_s + sum(got.idle_gaps().values()) == pytest.approx(got.window_s)
    assert got.opcode_seconds({"fusion"}) == pytest.approx(1.3336e-05)
    assert got.opcode_seconds(tracing.COLLECTIVE_OPCODES) == 0.0
    assert len(got.module_runs(r"^jit_f$")) == 3
    assert got.mosaic_calls() == []
    assert got.clock_offset_ns == pytest.approx(want["clock_offset_ns"])
    top = got.breakdown()["device_ops"][0]
    assert top[0].startswith("convolution_tanh_fusion.2 fusion")


def test_gaps_go_to_the_innermost_annotation():
    tr = tracing.Trace(devices=1, gaps=[[(100e3, 200e3), (300e3, 305e3),
                                         (900e3, 990e3)]],
                       annotations=[("serve/request", 0.0, 500e3),
                                    ("serve/decode_step", 120e3, 100e3)])
    assert tr.idle_gaps() == pytest.approx({
        "serve/decode_step": 100e-6, tracing.SHORT_GAPS: 5e-6,
        tracing.UNANNOTATED: 90e-6})


def test_clock_offset_is_bracketed_by_enqueue_and_completion():
    runs = {"7": (1000.0, 5000.0), "8": (6000.0, 9000.0)}
    host = [("DoEnqueueProgram", "7", 1300.0), ("CompleteCallbacks", "7", 5900.0),
            ("DoEnqueueProgram", "8", 6500.0), ("CompleteCallbacks", "8", 9700.0),
            ("DoEnqueueProgram", "99", 1.0)]
    # not before 500 (run 8's enqueue), not after 700 (run 8's completion)
    assert tracing.clock_offset_ns(runs, host) == 600.0
    assert tracing.clock_offset_ns(runs, []) == 0.0


def test_mosaic_calls_are_told_by_their_target():
    ops = tracing._self_times([
        ('%c.1 = bf16[192,1024,64]{2,1,0} custom-call(bf16[192,1024,64]{2,1,0} %a), '
         'custom_call_target="tpu_custom_call"', 0.0, 10.0),
        ('%c.2 = bf16[24,1024,1024]{2,1,0} custom-call(bf16[6,1024,1024]{2,1,0} %b), '
         'custom_call_target="ConcatBitcast"', 20.0, 1.0)])
    tr = tracing.Trace(devices=1, ops=[ops])
    assert [op.name for op in tr.mosaic_calls()] == ["c.1"]


def test_hlo_event_names():
    assert tracing.parse_hlo_event(
        "%while = (s32[]{:T(128)}, bf16[512,512]{1,0:T(8,128)(2,1)S(1)}) "
        "while((s32[]{:T(128)}, bf16[512,512]{1,0}) %tuple), body=%b") == \
        ("while", "while", "(s32[], bf16[512,512])")
    assert tracing.parse_hlo_event("something else") == ("something", "", "")


# -- BENCHMARK.json: everything named is found as a file -------------------------------

def check_manifest(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks"] and 1 <= manifest["run_seconds"] <= 51
    names = [x["name"] for sec in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in manifest[sec]]
    assert all(NAME.match(n) for n in names)
    for sec in ("configs", "workloads"):
        ns = [x["name"] for x in manifest[sec]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert "setup_s" in [m["name"] for m in manifest["end_to_end"]]


def test_manifest_keeps_to_the_contract():
    check_manifest(MANIFEST)


def check_configuration(manifest, config, cfg):
    entry = harness.find(manifest["configs"], config, "config")
    assert entry["file"] == f"benchmarks/configs/{config}.json"
    assert cfg["name"] == config and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "runners",
                                       cfg["runner"] + ".py"))
    ref = harness.load_reference(cfg)       # the plain reference, beside it
    assert ref.CONTROL_PRECISION in ref.PRECISIONS
    if "n_inner" in cfg:      # the GPT-2 family's own key: no width is cut
        assert cfg["n_inner"] == 4 * cfg["n_embd"]
    assert cfg["limits"] and all(v > 0 for v in cfg["limits"].values())


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_configuration_files(config):
    check_configuration(MANIFEST, config, harness.load_config(config))


def check_cell(manifest, cell, mix):
    w = harness.find(manifest["workloads"], cell, "workload")
    harness.find(manifest["configs"], w["config"], "config")
    assert mix["kind"] in ("train", "requests")
    e2e = [m["name"] for m in harness.metrics_of_cell(manifest, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of_cell(manifest, "per_layer", cell)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_files_and_what_each_cell_reports(cell):
    w = harness.find(MANIFEST["workloads"], cell, "workload")
    check_cell(MANIFEST, cell, traffic.load_mix(w["traffic"]))


def check_per_layer_metric(manifest, metric, reader):
    m = harness.find(manifest["per_layer"], metric, "metric")
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) \
        == (m["name"], m["unit"], m["layer"], m["moves"], m["source"])
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    cells = m.get("workloads") or [w["name"] for w in manifest["workloads"]]
    for cell in cells:
        reported = [x["name"] for x in
                    harness.metrics_of_cell(manifest, "end_to_end", cell)]
        assert m["moves"] in reported, (metric, cell)
    # a reader that finds nothing to read returns nothing
    nothing = harness.Observed(cell=None, window={}, counters={})
    assert reader.read(nothing) is None


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_metric_files(metric):
    check_per_layer_metric(MANIFEST, metric, harness.load_layer_metric(metric))


def test_a_later_pr_can_append_a_configuration_a_cell_and_a_metric():
    """The guard against tests that pin the END of the manifest's lists
    (three of this directory failed by design for it, PRs 27 to 35): a
    made-up configuration, cell and per-layer metric are appended to a
    copy of the manifest, the cell also to every list that holds the
    newest served cell, and every assertion this directory makes about
    the manifest has to hold on the copy as it holds on the file."""
    from benchmarks.tests import test_keye_vl_2_30b_a3b as keye
    from benchmarks.tests import test_kimi_k2_instruct as kimi

    later = copy.deepcopy(MANIFEST)
    name, cell = "made-up-1b", "made-up-1b.notes-closed4"
    source = "https://example.org/made-up-1b/blob/main/config.json"
    later["configs"].append({
        "name": name, "source": source, "reduced": ["num_hidden_layers"],
        "file": f"benchmarks/configs/{name}.json",
        "why": "made up: the configuration a later PR appends"})
    later["workloads"].append({
        "name": cell, "config": name, "traffic": "notes-closed4", "chips": 1,
        "why": "made up: the cell a later PR appends"})
    for m in later["end_to_end"] + later["per_layer"]:
        if keye.CELL in m.get("workloads", []) or m["name"] in kimi.READERS:
            m["workloads"].append(cell)
    later["per_layer"].append({
        "name": "made_up_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "decode scheduler",
        "moves": "serve_tokens_per_s", "workloads": [cell]})

    check_manifest(later)
    keye.check_this_configurations_entries(later)
    kimi.check_this_configurations_entries(later)
    for entry in later["configs"] + later["workloads"]:
        keye.test_every_why_and_source_is_one_printable_ascii_line(entry)
    # the made-up configuration's file: the newest one's, renamed; it has
    # no n_inner, as no configuration outside the GPT-2 family has
    cfg = {**keye.CONFIG, "name": name, "source": source,
           "reduced": ["num_hidden_layers"]}
    assert "n_inner" not in cfg
    check_configuration(later, name, cfg)
    check_cell(later, cell, {"kind": "requests", "loop": "closed"})
    check_per_layer_metric(later, "made_up_share", types.SimpleNamespace(
        NAME="made_up_share", UNIT="%", LAYER="decode scheduler",
        MOVES="serve_tokens_per_s", SOURCE="program_counter",
        read=lambda observed: None))
    for metric in [m["name"] for m in MANIFEST["per_layer"]]:
        check_per_layer_metric(later, metric, harness.load_layer_metric(metric))


def test_nothing_under_benchmarks_imports_bench_or_scripts():
    for d, _, files in os.walk(os.path.join(ROOT, "benchmarks")):
        for f in files:
            if f.endswith(".py") and f != os.path.basename(__file__):
                src = open(os.path.join(d, f)).read()
                assert not re.search(r"^\s*(import|from) (bench|scripts)\b", src, re.M), f


# -- the harness itself, rehearsed at tiny sizes ------------------------------------------

def last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), out


@pytest.mark.parametrize("cell,trace", [("gpt2-medium.train-1k", 0),
                                        ("gpt2-medium.train-1k", 1),
                                        ("gpt2-large.chat-closed8", 0)])
def test_rehearsal_drives_a_run_and_prints_no_device_metric(cell, trace, capsys):
    rc = harness.main(["--workload", cell, "--seed", "4294967311",
                       "--seconds", "3", "--trace", str(trace)],
                      rehearsal=rehearsal.CELLS[cell])
    result, lines = last_line(capsys)
    assert rc == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["metrics"]
    assert all(m["value"] is None for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    if not trace:
        assert set(result["metrics"]) == {m["name"] for m in
            harness.metrics_of_cell(MANIFEST, "end_to_end", cell)}
    assert any("compilations inside the window: 0" in ln for ln in lines)
    assert sum(ln.startswith("bench: compared: ") for ln in lines) >= 2


@pytest.mark.parametrize("cell,seconds", [("gpt2-large.chat-closed8", "1.5"),
                                          ("gpt2-medium.train-1k", "1")])
def test_rehearsal_of_two_windows_measures_twice_the_seconds(cell, seconds,
                                                             capsys):
    rc = harness.main(["--workload", cell, "--seed", "4294967311",
                       "--seconds", seconds, "--trace", "0"],
                      rehearsal=_rehearsal_with(cell, windows=2))
    result, lines = last_line(capsys)
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    whole = 2 * float(seconds)
    said = next(ln for ln in lines if ln.startswith("bench: cell "))
    assert f"window 2 x {float(seconds)}s = {whole}s" in said
    summary = json.loads(next(ln for ln in lines if ln.startswith(
        "bench: window: "))[len("bench: window: "):])
    assert summary["window_s"] >= whole - 1e-6
    if "train" not in cell:
        at = summary["tokens_out_at_window"]
        assert len(at) == 2 and 0 < at[0] < at[1]
        assert at[1] == summary["tokens_out_by_engine"]


def test_the_command_refuses_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, *MANIFEST["command"][1].split("/")),
         "--workload", "gpt2-medium.train-1k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "never falls back" in p.stderr
