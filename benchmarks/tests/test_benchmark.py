"""The benchmark's own arithmetic and wiring, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import re
import statistics
import subprocess
import sys

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

def test_manifest_keeps_to_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmarks"] and 1 <= MANIFEST["run_seconds"] <= 51
    names = [x["name"] for sec in ("configs", "workloads", "end_to_end",
                                   "per_layer") for x in MANIFEST[sec]]
    assert all(NAME.match(n) for n in names)
    for sec in ("configs", "workloads"):
        ns = [x["name"] for x in MANIFEST[sec]]
        assert len(ns) == len(set(ns))
    metrics = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert "setup_s" in [m["name"] for m in MANIFEST["end_to_end"]]


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_configuration_files(config):
    entry = harness.find(MANIFEST["configs"], config, "config")
    assert entry["file"] == f"benchmarks/configs/{config}.json"
    cfg = harness.load_config(config)
    assert cfg["name"] == config and cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "runners",
                                       cfg["runner"] + ".py"))
    ref = harness.load_reference(cfg)       # the plain reference, beside it
    assert ref.CONTROL_PRECISION in ref.PRECISIONS
    assert cfg["n_inner"] == 4 * cfg["n_embd"]      # no width is cut
    assert cfg["limits"] and all(v > 0 for v in cfg["limits"].values())


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_files_and_what_each_cell_reports(cell):
    w = harness.find(MANIFEST["workloads"], cell, "workload")
    harness.find(MANIFEST["configs"], w["config"], "config")
    mix = traffic.load_mix(w["traffic"])
    assert mix["kind"] in ("train", "requests")
    e2e = [m["name"] for m in harness.metrics_of_cell(MANIFEST, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_of_cell(MANIFEST, "per_layer", cell)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_per_layer_metric_files(metric):
    m = harness.find(MANIFEST["per_layer"], metric, "metric")
    reader = harness.load_layer_metric(metric)
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) \
        == (m["name"], m["unit"], m["layer"], m["moves"], m["source"])
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    cells = m.get("workloads") or [w["name"] for w in MANIFEST["workloads"]]
    for cell in cells:
        reported = [x["name"] for x in
                    harness.metrics_of_cell(MANIFEST, "end_to_end", cell)]
        assert m["moves"] in reported, (metric, cell)
    # a reader that finds nothing to read returns nothing
    nothing = harness.Observed(cell=None, window={}, counters={})
    assert reader.read(nothing) is None


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


def test_the_command_refuses_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, *MANIFEST["command"][1].split("/")),
         "--workload", "gpt2-medium.train-1k", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "never falls back" in p.stderr
