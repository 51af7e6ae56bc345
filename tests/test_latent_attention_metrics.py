"""The per-layer metric PR 42 brought, on hand-built spans and a
hand-built trace: what it reads, and that a program without the kernel
(the parent) gives nothing and does not raise; and that the engine asks
the LATENT kernel's own rule what its ``kv_pages_read`` counts.  Kept
here and not under benchmarks/tests: the benchmark gained the metric's
file and entry only (ISSUE 42).
"""

import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness, program_spans, tracing  # noqa: E402
from benchmarks.kernels import decode_bytes             # noqa: E402

CELL = "kimi-k2-instruct.agent-closed32"
CONFIG = harness.load_config("kimi-k2-instruct")
LAYERS, HORIZON = 8, 4
US = 1e3     # nanoseconds


def spans(*steps):
    return program_spans.nest([
        ("serve/decode_step", 50e6 * i, 45e6, dict(args))
        for i, args in enumerate(steps)])


def observed_of(monkeypatch, forest, trace=None, config=CONFIG):
    monkeypatch.setattr(program_spans, "of", lambda observed: forest)
    cell = types.SimpleNamespace(config=config, name=CELL)
    return harness.Observed(
        cell=cell, window={}, counters={},
        trace=trace if trace is not None else tracing.Trace(),
        peaks={"hbm_bytes_per_s": 819e9})


def dispatch_trace(call_us, n=2, cut_first=False, program="jit_step_multi",
                   calls=LAYERS * HORIZON):
    """``n`` executions of ``program`` with ``calls`` Mosaic calls of
    ``call_us`` each, other operations between them, and a chunk."""
    ops, modules = [], [("jit_prefill_at", 0.0, 5e6)]
    for i in range(n):
        start = 10e6 + 50e6 * i
        modules.append((program, start, 42e6))
        for j in range(calls - (1 if cut_first and i == 0 else 0)):
            t = start + 1000 * US * j
            ops.append(tracing.Op(
                f"latent_attention.{j % 8 + 1} custom-call f32[16,64,512]",
                f"latent_attention.{j % 8 + 1}", "custom-call",
                "f32[16,64,512]", t,
                call_us * US, call_us * US, tracing.MOSAIC_TARGET))
            ops.append(tracing.Op("fusion.1 fusion f32[16,7168]", "fusion.1",
                                  "fusion", "f32[16,7168]", t + 500 * US,
                                  400 * US, 400 * US))
            # XLA's grouped product is a Mosaic call too, by another name
            ops.append(tracing.Op(
                "ragged-dot-none.3 custom-call f32[128,7168]",
                "ragged-dot-none.3", "custom-call", "f32[128,7168]",
                t + 900 * US, 90 * US, 90 * US, tracing.MOSAIC_TARGET))
    return tracing.Trace(devices=1, ops=[ops], modules=[modules])


def test_the_manifest_names_the_metric_for_the_kimi_cell_only():
    entry = harness.find(harness.load_manifest()["per_layer"],
                         "latent_attention_roofline", "metric")
    assert entry == {
        "name": "latent_attention_roofline", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": "kernels",
        "moves": "serve_tokens_per_s", "workloads": [CELL]}
    reader = harness.load_layer_metric("latent_attention_roofline")
    assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES,
            reader.SOURCE) == (entry["name"], entry["unit"], entry["layer"],
                               entry["moves"], entry["source"])


def test_bytes_of_a_cached_row():
    # 512 + 64 values x 2 bytes x 8 layers a cached position
    assert decode_bytes.latent_row_bytes(CONFIG) == 576 * 2 * 8


def test_latent_attention_roofline(monkeypatch):
    read = harness.load_layer_metric("latent_attention_roofline").read
    steps = spans(
        {"n_active": 16, "tokens": 4, "pages_filled": 1400, "kv_pages_read": 5650},
        {"n_active": 15, "tokens": 4, "pages_filled": 1300, "kv_pages_read": 5250},
        # a plain step between fused dispatches is not this program's
        {"n_active": 16, "tokens": 1, "pages_filled": 9000})
    # full pages: 1384 and 1285, 16 rows each, 9,216 bytes a row, 4 steps
    least = (1384 + 1285) / 2 * 16 * 9216 * 4 / 819e9
    obs = observed_of(monkeypatch, steps, dispatch_trace(call_us=60.0))
    assert read(obs) == pytest.approx(100.0 * least / (32 * 60e-6))
    assert 0 < read(obs) < 100
    # every page read a page held, at the chip's whole bandwidth: 100%
    at_peak = 1e6 * least / 32
    obs = observed_of(monkeypatch, steps, dispatch_trace(at_peak))
    assert read(obs) == pytest.approx(100.0)
    # an execution the trace cut is left out of the mean
    obs = observed_of(monkeypatch, steps, dispatch_trace(60.0, cut_first=True))
    assert read(obs) == pytest.approx(100.0 * least / (32 * 60e-6))
    obs = observed_of(monkeypatch, steps,
                      dispatch_trace(60.0, n=1, cut_first=True))
    assert read(obs) is None
    # the parent's step holds no Mosaic call; an untraced run has no trace
    bare = dispatch_trace(60.0)
    bare.ops = [[op for op in bare.ops[0] if not op.target]]
    assert read(observed_of(monkeypatch, steps, bare)) is None
    assert read(observed_of(monkeypatch, steps)) is None
    obs = observed_of(monkeypatch, steps, dispatch_trace(60.0))
    obs.trace = None
    assert read(obs) is None
    # spans without the counters (or none at all) read nothing
    assert read(observed_of(monkeypatch, spans({"tokens": 4}),
                            dispatch_trace(60.0))) is None
    assert read(observed_of(monkeypatch, [], dispatch_trace(60.0))) is None


def test_a_horizon_of_one_reads_the_plain_step(monkeypatch):
    read = harness.load_layer_metric("latent_attention_roofline").read
    config = {**CONFIG, "program": {**CONFIG["program"], "decode_horizon": 1}}
    steps = spans({"n_active": 16, "tokens": 1, "pages_filled": 1400})
    least = 1384 * 16 * 9216 / 819e9
    trace = dispatch_trace(60.0, program="jit_step", calls=LAYERS)
    obs = observed_of(monkeypatch, steps, trace, config)
    assert read(obs) == pytest.approx(100.0 * least / (8 * 60e-6))
    # the fused program's executions are another program's
    fused = dispatch_trace(60.0)
    assert read(observed_of(monkeypatch, steps, fused, config)) is None


# -- the engine's counter asks the program's own kernel ------------------------

def _latent_lm():
    import jax

    from deeplearning4j_tpu.models.arch import LMArch
    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    from test_latent_moe import SIZES
    return ShardedTransformerLM(
        arch=LMArch.from_config(SIZES, max_len=128),
        mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]), seed=1)


@pytest.mark.parametrize("why, reads_held", [(None, True),
                                             ("the kernel cannot", False)])
def test_the_engine_asks_the_latent_kernels_rule(monkeypatch, why,
                                                 reads_held):
    from deeplearning4j_tpu.ops import latent_attention, paged_attention
    from deeplearning4j_tpu.serving import DecodeEngine

    asked = []

    def rule(pool, pages_per_slot, tp=1):
        asked.append((pool.shape, pages_per_slot, tp))
        return why

    def not_this_one(*a, **k):
        raise AssertionError("GPT-2's kept_path was asked of a latent pool")

    monkeypatch.setattr(latent_attention, "kept_path", rule)
    monkeypatch.setattr(paged_attention, "kept_path", not_this_one)
    lm = _latent_lm()
    prog = lm.decode_program(page_size=8, max_len=128)
    assert prog.held_pages is True and prog.kept_path is rule
    eng = DecodeEngine(lm, max_slots=2, page_size=8, max_len=128,
                       prompt_buckets=(16,))
    eng.load()
    try:
        assert eng._reads_held_pages is reads_held
        # the engine's own pool: 3 layers, 128-lane rows, 16 pages a slot
        assert (asked[0][0][0], asked[0][0][2:], asked[0][1:]) == (
            3, (8, 128), (16, 1))
    finally:
        eng.shutdown()
