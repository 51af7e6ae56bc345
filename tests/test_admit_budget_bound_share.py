"""The per-layer metric PR 44 brought, ``admit_budget_bound_share.closed``,
on hand-built spans: what it reads, that the parent's spans (which carry
``admit_budget_bound`` since PR 43) read the same way, and that a run
without the spans gives nothing and does not raise.  Kept here and not
under benchmarks/tests: the benchmark gained the metric's file and entry
only (ISSUE 44).
"""

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness, program_spans, tracing  # noqa: E402

NAME = "admit_budget_bound_share.closed"
CELLS = ["kimi-k2-instruct.agent-closed32", "keye-vl-2-30b-a3b.doc-closed16",
         "solar-open2-250b.longdoc-closed64",
         "granite-4.0-h-small.chat-closed128",
         "lfm2-24b-a2b.compose-closed256"]
MS = 1e6
BOUND = {"admit_budget_bound": 1, "free_slots": 30, "admitted": 1}


def turns_of(turns):
    """``(arguments of the turn, does it hold a dispatch)`` each as a
    ``serve/iteration`` of 200 ms, the stepping ones round a
    ``serve/decode_step`` with its ``serve/step_wait``."""
    events = []
    for i, (args, steps) in enumerate(turns):
        t0 = 250 * MS * i
        events.append(("serve/iteration", t0, 200 * MS,
                       {"worked": 1, **args}))
        if steps:
            events += [("serve/decode_step", t0 + 10 * MS, 150 * MS,
                        {"n_active": 71, "tokens": 4}),
                       ("serve/step_wait", t0 + 11 * MS, 120 * MS, {})]
        else:
            events.append(("serve/prefill", t0 + 10 * MS, 30 * MS,
                           {"offset": 0}))
    return events


def observed_of(monkeypatch, events):
    forest = program_spans.keep_inner(program_spans.nest(events))
    monkeypatch.setattr(program_spans, "of", lambda observed: forest)
    return harness.Observed(
        cell=types.SimpleNamespace(config={}, name=CELLS[-1]),
        window={"max_slots": 128}, counters={}, trace=tracing.Trace(),
        peaks={})


def test_the_share_of_the_stepping_turns_that_the_budget_ended(monkeypatch):
    read = harness.load_layer_metric(NAME).read
    # five turns hold a dispatch, three of them bound; two turns that only
    # read a chunk are left out, bound or not
    obs = observed_of(monkeypatch, turns_of([
        (BOUND, True), ({}, True), (BOUND, False), (BOUND, True),
        ({}, False), ({}, True), (BOUND, True)]))
    assert read(obs) == pytest.approx(60.0)
    # the profiler hands a flag back as text as readily as a number, and
    # the parent's spans carry the same three arguments: read alike
    as_text = {"admit_budget_bound": "True", "free_slots": 2, "admitted": 1}
    obs = observed_of(monkeypatch, turns_of([
        (as_text, True), ({}, True), ({}, True), ({}, True)]))
    assert read(obs) == pytest.approx(25.0)
    # a full engine whose rounds the SLOTS end: 0, which is a reading
    obs = observed_of(monkeypatch, turns_of([({}, True), ({}, True)]))
    assert read(obs) == 0.0


def test_the_benchmarks_wrapper_round_a_turn_is_not_counted_twice(monkeypatch):
    """A traced run's own annotation of the same name round the
    program's span: the inner one is kept (``program_spans.keep_inner``)."""
    read = harness.load_layer_metric(NAME).read
    inner = turns_of([(BOUND, True), ({}, True)])
    outer = [(name, t0 - MS, dur + 2 * MS, {})
             for name, t0, dur, _ in inner if name == "serve/iteration"]
    assert read(observed_of(monkeypatch, inner + outer)) == pytest.approx(50.0)


@pytest.mark.parametrize("events", [
    [],                                             # nothing traced
    turns_of([(BOUND, False), ({}, False)]),        # no turn holds a dispatch
    [("serve/decode_step", 0.0, 60 * MS, {"n_active": 5})],  # no turn at all
])
def test_nothing_to_read_gives_nothing_and_does_not_raise(monkeypatch, events):
    read = harness.load_layer_metric(NAME).read
    assert read(observed_of(monkeypatch, events)) is None


def test_an_untraced_run_gives_nothing():
    read = harness.load_layer_metric(NAME).read
    obs = harness.Observed(
        cell=types.SimpleNamespace(config={}, name=CELLS[0]), window={},
        counters={}, trace=None, peaks={})
    assert read(obs) is None


def test_on_a_recorded_profile_of_a_tiny_engine(tmp_path, monkeypatch):
    """The real composition: a profiler session on the CPU round a tiny
    engine that reads its prompts in chunks.  ``admit_budget_bound`` is
    set on the turn's span AFTER it opened; it has to reach the profile
    all the same."""
    import time

    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    from deeplearning4j_tpu.serving import DecodeEngine

    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    trace_dir = os.path.join(str(tmp_path), ".bench", "trace", CELLS[-1])
    lm = ShardedTransformerLM(
        vocab_size=64, n_layers=2, d_model=32, n_heads=2, max_len=128,
        mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]), seed=3)
    eng = DecodeEngine(lm, max_slots=3, page_size=8, max_len=128,
                       prompt_buckets=[16], prefill_chunk=16,
                       decode_horizon=4).load()
    rng = np.random.default_rng(5)
    prompt = lambda n: rng.integers(1, 64, n)       # noqa: E731
    counter = eng.metrics.counter_value
    try:
        jax.profiler.start_trace(trace_dir)
    except Exception as e:                   # no profiler backend here
        eng.shutdown()
        pytest.skip(f"no profiler session can start here: {e}")
    try:
        # one slot decodes, so the turns that follow hold a dispatch
        first = eng.generate_async(prompt(5), max_new_tokens=100)
        t_end = time.monotonic() + 120
        while counter("decode_steps") < 1 and time.monotonic() < t_end:
            time.sleep(0.005)
        # two slots free, a head of three chunks: the budget ends the round
        with eng._lock:
            futs = [eng.generate_async(prompt(n), max_new_tokens=4)
                    for n in (40, 6, 7)]
        for f in [first, *futs]:
            f.result(timeout=300)
        # a span is recorded when it closes: one more turn behind the last
        eng.generate(prompt(5), max_new_tokens=1)
    finally:
        jax.profiler.stop_trace()
        bound = counter("admit_rounds_budget_bound")
        eng.shutdown()
    assert bound == 1
    observed = harness.Observed(
        cell=types.SimpleNamespace(config={}, name=CELLS[-1]), window={},
        counters={}, trace=object(), peaks={})
    turns = [t for t in program_spans.named(program_spans.of(observed),
                                            "serve/iteration")
             if t.inside("serve/decode_step")]
    marked = [t for t in turns if "admit_budget_bound" in t.args]
    assert len(marked) == 1 and len(turns) > 1
    assert (int(marked[0].args["free_slots"]),
            int(marked[0].args["admitted"])) == (2, 1)
    got = harness.load_layer_metric(NAME).read(observed)
    assert got == pytest.approx(100.0 / len(turns))


def test_the_manifest_lists_it_last_for_the_five_cells_that_read_in_chunks():
    manifest = harness.load_manifest()
    entry = harness.find(manifest["per_layer"], NAME, "metric")
    module = harness.load_layer_metric(NAME)
    assert manifest["per_layer"][-1] == entry == {
        "name": NAME, "unit": module.UNIT, "better": "lower",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES, "workloads": CELLS}
    assert (module.UNIT, module.LAYER, module.MOVES, module.SOURCE) == (
        "%", "decode scheduler", "serve_tokens_per_s", "program_span")
    # the five whose program has a ``prefill_chunk``, and no other
    chunked = [w["name"] for w in manifest["workloads"]
               if harness.load_config(w["config"]).get("program", {})
               .get("prefill_chunk")]
    assert sorted(chunked) == sorted(CELLS)
    moved = harness.find(manifest["end_to_end"], module.MOVES, "metric")
    for cell in CELLS:
        assert cell in moved["workloads"]
        assert entry in harness.metrics_of_cell(manifest, "per_layer", cell)
