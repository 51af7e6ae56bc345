"""The per-layer metric PR 40 brought, ``slots_decoding_share.closed``, on
hand-built spans: what it reads, that the parent's spans (which carry
``n_active`` already) read the same way, and that a run without the
spans or the slot count gives nothing and does not raise.  Kept here and
not under benchmarks/tests: the benchmark gained the metric's file and
entry only (ISSUE 40).
"""

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness, program_spans, tracing  # noqa: E402

NAME = "slots_decoding_share.closed"
CELLS = ["kimi-k2-instruct.agent-closed32", "keye-vl-2-30b-a3b.doc-closed16",
         "solar-open2-250b.longdoc-closed64"]


def observed_of(monkeypatch, steps, window):
    forest = program_spans.nest([
        ("serve/decode_step", 80e6 * i, 60e6, dict(args))
        for i, args in enumerate(steps)])
    monkeypatch.setattr(program_spans, "of", lambda observed: forest)
    return harness.Observed(
        cell=types.SimpleNamespace(config={}, name=CELLS[1]), window=window,
        counters={}, trace=tracing.Trace(), peaks={})


def test_the_share_of_the_slots_that_the_traced_dispatches_step(monkeypatch):
    read = harness.load_layer_metric(NAME).read
    # 5, 6 and 8 of 8 slots decode: 19 of 24
    obs = observed_of(monkeypatch, [
        {"n_active": 5, "tokens": 4, "chunks": 2, "mid_prefill": 2},
        {"n_active": 6, "tokens": 4, "chunks": 1, "mid_prefill": 1},
        {"n_active": 8, "tokens": 4, "chunks": 0, "mid_prefill": 0}],
        {"max_slots": 8})
    assert read(obs) == pytest.approx(100.0 * 19 / 24)
    # the parent's spans carry ``n_active`` and no ``chunks``: read alike
    obs = observed_of(monkeypatch, [{"n_active": 21, "tokens": 4},
                                    {"n_active": 22, "tokens": 4}],
                      {"max_slots": 32})
    assert read(obs) == pytest.approx(100.0 * 43 / 64)
    assert 0 < read(obs) <= 100


@pytest.mark.parametrize("steps,window", [
    ([], {"max_slots": 8}),                         # no dispatch traced
    ([{"tokens": 4}], {"max_slots": 8}),            # a span without the count
    ([{"n_active": 5}], {}),                        # a runner without slots
])
def test_nothing_to_read_gives_nothing_and_does_not_raise(monkeypatch, steps,
                                                          window):
    read = harness.load_layer_metric(NAME).read
    assert read(observed_of(monkeypatch, steps, window)) is None


def test_the_manifest_lists_it_for_the_three_cells_of_the_fused_loop():
    manifest = harness.load_manifest()
    entry = harness.find(manifest["per_layer"], NAME, "metric")
    module = harness.load_layer_metric(NAME)
    # the three it was brought for, first and in order; a later cell of the
    # fused loop is appended behind them (PR 41's is)
    assert {**entry, "workloads": entry["workloads"][:len(CELLS)]} == {
        "name": NAME, "unit": module.UNIT, "better": "higher",
        "source": module.SOURCE, "layer": module.LAYER,
        "moves": module.MOVES, "workloads": CELLS}
    assert (module.UNIT, module.LAYER, module.MOVES, module.SOURCE) == (
        "%", "decode scheduler", "serve_tokens_per_s", "program_counter")
    for cell in CELLS:
        assert entry in harness.metrics_of_cell(manifest, "per_layer", cell)
