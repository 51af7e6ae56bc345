"""The program's spans as the per-layer metrics read them, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np      # noqa: E402
import pytest           # noqa: E402

from benchmarks import harness, program_spans  # noqa: E402
from benchmarks.tests import rehearsal         # noqa: E402

MANIFEST = harness.load_manifest()
NEW = ("decode_host_ms.closed", "queue_wait_ms.closed",
       "kv_pages_filled_share.closed", "train_host_ms")
SERVE_CELL, TRAIN_CELL = "gpt2-large.chat-closed8", "gpt2-medium.train-1k"

MS = 1e6     # nanoseconds


def engine_thread():
    """Two turns of the engine's loop as a traced run records them: the
    benchmark's wrappers round the program's same-named spans."""
    step = lambda t0, filled: [
        ("serve/decode_step", t0, 40 * MS, {}),                  # wrapper
        ("serve/decode_step", t0 + 0.1 * MS, 39.8 * MS,          # program
         {"n_active": 4, "pages_reserved": 20, "pages_filled": filled}),
        ("serve/step_build", t0 + 0.2 * MS, 0.3 * MS, {}),
        ("serve/step_dispatch", t0 + 0.5 * MS, 1.0 * MS, {}),
        ("serve/sample_dispatch", t0 + 1.5 * MS, 0.2 * MS, {}),
        ("serve/step_wait", t0 + 1.7 * MS, 37.0 * MS, {}),
        ("serve/step_record", t0 + 38.7 * MS, 1.0 * MS, {})]
    return ([("serve/iteration", 0.0, 41 * MS, {"worked": 1})]
            + step(0.5 * MS, 8)
            # a turn that admits and prefills before it steps
            + [("serve/iteration", 50 * MS, 72 * MS, {"worked": 1}),
               ("serve/admit", 50.2 * MS, 0.3 * MS,
                {"request_id": 9, "queue_wait_ms": 5000.0, "slot": 2}),
               ("serve/admit", 50.6 * MS, 0.1 * MS,
                {"request_id": 10, "requeued": 1}),
               ("serve/prefill", 51 * MS, 30 * MS, {}),            # wrapper
               ("serve/prefill", 51.1 * MS, 29.5 * MS, {"request_id": 9})]
            + step(81.5 * MS, 12))


def client_thread():
    """A thread that holds the benchmark's annotation only."""
    return [("serve/request", 1 * MS, 0.2 * MS, {})]


def forest(*threads):
    return [s for t in threads
            for s in program_spans.keep_inner(program_spans.nest(t))]


def observed_of(monkeypatch, spans):
    """An ``Observed`` of a traced run whose profile holds ``spans``."""
    monkeypatch.setattr(program_spans, "of", lambda observed: spans)
    return harness.Observed(cell=None, window={}, counters={}, trace=object())


def test_the_inner_of_two_same_named_spans_is_kept_and_counted_once():
    spans = forest(engine_thread(), client_thread())
    turns = program_spans.named(spans, "serve/iteration")
    assert [t.name for t in spans] == ["serve/iteration", "serve/iteration",
                                       "serve/request"]
    steps = program_spans.named(spans, "serve/decode_step")
    assert len(steps) == 2 and all("n_active" in s.args for s in steps)
    assert [c.name for c in steps[0].children] == [
        "serve/step_build", "serve/step_dispatch", "serve/sample_dispatch",
        "serve/step_wait", "serve/step_record"]
    prefills = program_spans.named(spans, "serve/prefill")
    assert len(prefills) == 1 and prefills[0].args == {"request_id": 9}
    assert [c.name for c in turns[1].children] == [
        "serve/admit", "serve/admit", "serve/prefill", "serve/decode_step"]
    # a program older than the bridge: the wrappers stay, nothing is lost
    old = forest([("serve/decode_step", 0.0, 40 * MS, {}),
                  ("serve/prefill", 50 * MS, 30 * MS, {})])
    assert [s.name for s in old] == ["serve/decode_step", "serve/prefill"]


def test_readers_on_the_hand_built_threads(monkeypatch):
    obs = observed_of(monkeypatch, forest(engine_thread(), client_thread()))
    read = lambda name: harness.load_layer_metric(name).read(obs)
    # turn 1: 41 - 37 of waiting; turn 2: 72 - 37 - 29.5 of prefill
    assert read("decode_host_ms.closed") == pytest.approx((4.0 + 5.5) / 2)
    assert read("queue_wait_ms.closed") == 5000.0   # the requeued one: none
    assert read("kv_pages_filled_share.closed") == pytest.approx(50.0)
    assert read("train_host_ms") is None
    train = forest([("bench/train_step", 0.0, 9 * MS, {}),
                    ("train/step", 1 * MS, 3 * MS, {"iteration": 4}),
                    ("train/h2d", 1.1 * MS, 2 * MS, {}),
                    ("train/dispatch", 3.2 * MS, 0.7 * MS, {}),
                    ("train/step", 600 * MS, 5 * MS, {"iteration": 5})])
    obs = observed_of(monkeypatch, train)
    assert read("train_host_ms") == pytest.approx(4.0)
    assert read("decode_host_ms.closed") is None
    assert read("kv_pages_filled_share.closed") is None
    # a parent that emits no program span: its wrappers give no value
    obs = observed_of(monkeypatch, forest(
        [("serve/decode_step", 0.0, 40 * MS, {}), ("serve/prefill", 50 * MS,
                                                   30 * MS, {})]))
    assert [read(n) for n in NEW] == [None] * 4


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_returns_nothing_where_nothing_was_traced(metric):
    entry = harness.find(MANIFEST["per_layer"], metric, "metric")
    assert entry["source"] in ("program_span", "program_counter")
    untraced = harness.Observed(
        cell=types.SimpleNamespace(name=SERVE_CELL), window={}, counters={})
    assert harness.load_layer_metric(metric).read(untraced) is None


def test_readers_on_a_recorded_profile_of_a_tiny_engine(tmp_path, monkeypatch):
    """The real composition: a profiler session on the CPU, the
    benchmark's wrappers round a tiny engine, the program's own spans
    inside them, and a training step."""
    import jax

    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    from deeplearning4j_tpu.serving import DecodeEngine

    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), ".bench", "trace", SERVE_CELL)
    cfg = rehearsal.TINY_GPT2
    tiny_lm = lambda: ShardedTransformerLM(
        vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], n_heads=cfg["n_head"], d_ff=cfg["n_inner"],
        mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]),
        max_len=cfg["n_positions"])
    eng = DecodeEngine(tiny_lm(), max_slots=2, page_size=16, max_len=128,
                       prompt_buckets=[16, 32]).load()
    lm = tiny_lm()          # a step donates its parameters: not the served
    rng = np.random.default_rng(5)
    batch = rng.integers(0, cfg["vocab_size"], (2, 32))
    for _ in range(2):      # compiled before the session, both layouts
        lm.fit_batch(batch, batch)
    tracer = harness.Tracer(True, trace_dir)
    harness.load_runner("serve_lm")._annotate_engine(eng, tracer)
    steps_before = eng.metrics.counter_value("decode_steps")
    try:
        jax.profiler.start_trace(trace_dir)
    except Exception as e:                   # no profiler backend here
        eng.shutdown()
        pytest.skip(f"no profiler session can start here: {e}")
    try:
        futs = [eng.generate_async(rng.integers(0, cfg["vocab_size"], 20),
                                   max_new_tokens=6) for _ in range(4)]
        ids = {f.result(timeout=300).request_id for f in futs}
        # a span is recorded when it closes, after the future resolves:
        # a one-token request behind the last is admitted a turn later
        eng.generate(rng.integers(0, cfg["vocab_size"], 20),
                     max_new_tokens=1)
        with tracer.annotate("bench/train_step"):
            lm.fit_batch(batch, batch)
    finally:
        jax.profiler.stop_trace()
        steps = eng.metrics.counter_value("decode_steps") - steps_before
        eng.shutdown()
    observed = harness.Observed(cell=types.SimpleNamespace(name=SERVE_CELL),
                                window={}, counters={}, trace=object())
    spans = program_spans.of(observed)
    # wrapper and program both say serve/decode_step: counted once (a
    # wrapper round a turn that had nothing to step holds no span of
    # the program's and carries no argument)
    assert len([s for s in program_spans.named(spans, "serve/decode_step")
                if "n_active" in s.args]) == steps
    assert len(program_spans.named(spans, "serve/step_wait")) == steps
    admits = program_spans.named(spans, "serve/admit")
    assert ids <= {a.args["request_id"] for a in admits if "slot" in a.args}
    prefills = program_spans.named(spans, "serve/prefill")
    assert ids <= {p.args.get("request_id") for p in prefills}
    assert len(prefills) <= 5         # the wrapper's were not added to them
    got = {n: harness.load_layer_metric(n).read(observed) for n in NEW}
    assert got["decode_host_ms.closed"] > 0 and got["train_host_ms"] > 0
    assert got["queue_wait_ms.closed"] >= 0
    assert 0 < got["kv_pages_filled_share.closed"] <= 100


@pytest.mark.parametrize("cell", [SERVE_CELL, TRAIN_CELL])
def test_a_traced_rehearsal_still_ends_correct_with_the_new_entries(
        cell, capsys):
    assert {m["name"] for m in MANIFEST["per_layer"]} >= set(NEW)
    rc = harness.main(["--workload", cell, "--seed", "2147483659",
                       "--seconds", "3", "--trace", "1"],
                      rehearsal=rehearsal.CELLS[cell])
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True and result["failed"] == 0
    # a rehearsal traces nothing: the new readers find nothing to read
    assert not set(result["metrics"]) & set(NEW)
