"""The two per-layer metrics PR 30 brought, on hand-built spans and a
hand-built trace: what they read, and that a program without the counter
or the kernel (the parent) gives nothing and does not raise.  Kept here
and not under benchmarks/tests: the benchmark gained the two metrics'
files and entries only (ISSUE 30).
"""

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness, program_spans, tracing  # noqa: E402
from benchmarks.kernels import paged_attention          # noqa: E402

CONFIG = harness.load_config("gpt2-large")
US = 1e3     # nanoseconds


def spans(*steps):
    return program_spans.nest([
        ("serve/decode_step", 20e6 * i, 15e6, dict(args))
        for i, args in enumerate(steps)])


def observed_of(monkeypatch, forest, trace=None):
    monkeypatch.setattr(program_spans, "of", lambda observed: forest)
    cell = types.SimpleNamespace(config=CONFIG, name="gpt2-large.chat-closed8")
    return harness.Observed(
        cell=cell, window={}, counters={},
        trace=trace if trace is not None else tracing.Trace(),
        peaks={"hbm_bytes_per_s": 819e9})


def step_trace(call_us, n_steps=2, cut_first=False):
    """``n_steps`` executions of ``jit_step`` with one Mosaic call a layer
    of ``call_us`` each, other operations between them, and a prefill."""
    ops, modules = [], [("jit_prefill", 0.0, 5e6)]
    for i in range(n_steps):
        start = 10e6 + 20e6 * i
        modules.append(("jit_step", start, 15e6))
        layers = CONFIG["n_layer"] - (1 if cut_first and i == 0 else 0)
        for j in range(layers):
            t = start + 100 * US * j
            ops.append(tracing.Op("paged_attention custom-call f32[4,1,1280]",
                                  "paged_attention", "custom-call",
                                  "f32[4,1,1280]", t, call_us * US,
                                  call_us * US, tracing.MOSAIC_TARGET))
            ops.append(tracing.Op("fusion.1 fusion f32[4,1280]", "fusion.1",
                                  "fusion", "f32[4,1280]", t + 50 * US,
                                  40 * US, 40 * US))
    return tracing.Trace(devices=1, ops=[ops], modules=[modules])


def test_bytes_of_a_step():
    # 1280 values x 4 bytes x 2 pools x 36 layers a cached position
    assert paged_attention.row_bytes(CONFIG) == 1280 * 4 * 2 * 36
    assert paged_attention.step_bytes(CONFIG, 200) == 200 * 368_640


def test_kv_pages_read_share(monkeypatch):
    read = harness.load_layer_metric("kv_pages_read_share.closed").read
    # 4 slots x 64 pages a step could be read: 52 and 76 of 256 were
    obs = observed_of(monkeypatch, spans(
        {"n_active": 4, "pages_filled": 48, "kv_pages_read": 52},
        {"n_active": 4, "pages_filled": 72, "kv_pages_read": 76}))
    assert read(obs) == pytest.approx(100.0 * 128 / 512)
    # a fused dispatch counts its steps
    obs = observed_of(monkeypatch, spans(
        {"n_active": 4, "tokens": 4, "kv_pages_read": 256}))
    assert read(obs) == pytest.approx(25.0)
    # the parent's spans carry no such counter
    obs = observed_of(monkeypatch, spans({"n_active": 4, "pages_filled": 48}))
    assert read(obs) is None
    assert read(observed_of(monkeypatch, [])) is None


def test_paged_attention_roofline(monkeypatch):
    read = harness.load_layer_metric("paged_attention_roofline").read
    steps = spans({"n_active": 4, "pages_filled": 56, "kv_pages_read": 58},
                  {"n_active": 4, "pages_filled": 60, "kv_pages_read": 62})
    # full pages: 52 and 56, 16 rows each, 368,640 bytes a row: 0.3185 GB,
    # 388.9 us a step at 819 GB/s; the 36 calls took 36 x 40 us
    least = (52 + 56) / 2 * 16 * 368_640 / 819e9
    obs = observed_of(monkeypatch, steps, step_trace(call_us=40.0))
    assert read(obs) == pytest.approx(100.0 * least / (36 * 40e-6))
    assert 0 < read(obs) < 100
    # an execution the trace cut is left out of the mean
    obs = observed_of(monkeypatch, steps, step_trace(40.0, cut_first=True))
    assert read(obs) == pytest.approx(100.0 * least / (36 * 40e-6))
    # the parent's step holds no Mosaic call; an untraced run has no trace
    bare = step_trace(40.0)
    bare.ops = [[op for op in bare.ops[0] if not op.target]]
    assert read(observed_of(monkeypatch, steps, bare)) is None
    assert read(observed_of(monkeypatch, steps)) is None
    obs = observed_of(monkeypatch, steps, step_trace(40.0))
    obs.trace = None
    assert read(obs) is None
