"""Configuration ``lfm2-24b-a2b`` and its cell: the manifest's own limits,
the file against the catalog's published values, the cut against the
stated deployment, the bytes and operations of
``benchmarks/kernels/conv_decode_bytes.py`` on a worked example, the
readers on a worked example and on nothing, the two shares against the
uncut layer, a CPU rehearsal of the cell at tiny sizes, the controls
beside the program, and ``correct`` coming out false when the timed path
is broken underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_lfm2_24b_a2b.py -q
"""

import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness                     # noqa: E402
from benchmarks.kernels import conv_decode_bytes   # noqa: E402

NAME = "lfm2-24b-a2b"
CELL = NAME + ".compose-closed256"
CONFIG = harness.load_config(NAME)
MANIFEST = harness.load_manifest()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = ["conv_decode_bytes_roofline", "expert_bytes_share",
       "conv_chunk_flops_roofline"]
REDUCED = {"num_hidden_layers": (40, 9), "num_dense_layers": (2, 1)}
PERIOD = ["conv", "conv", "full_attention", "conv"]

#: the catalog row's ``config`` (LFM2-24B-A2B, config.json)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PERIOD * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}

#: a model of the same family a test run holds: 5 layers (layer 0 the
#: leading dense layer, layer 2 grouped-query, the others convolutions), 4
#: query heads over 2 KV heads of 16, 16 experts (all held) with 3 a token,
#: an expert bias of N(0, 0.1) so that weights taken from the biased scores
#: show.  The limits are this size's own.  On eight seeds the program (bf16
#: weights in a model 64 wide) reads flips 0.017 .. 0.037, mse 0.0007 ..
#: 0.0030, ``state_gap`` 0.0126 .. 0.0175 and the two widest-gap numbers 0
#: .. 0.24; on the seed the faults run on, the three mildest breaks read:
#: rotary before the norm 0.094, 0.0108, 0.090; weights from the biased
#: scores 0.066, 0.0065, 0.077; a tail not reset at admission 0.0625,
#: 0.0084, 0.031; the others lie further out (a k norm left out 0.19, 0.048,
#: 0.17).
TINY = {
    "vocab_size": 256, "num_hidden_layers": 5, "num_dense_layers": 1,
    "hidden_size": 64,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32, "num_experts": 16,
    "n_routed_experts": 16, "first_expert": 0, "num_experts_per_tok": 3,
    "max_position_embeddings": 256, "initializer_range": 0.2,
    "router_bias_std": 0.1,
    "limits": {"router_flip_share": 0.05, "served_logit_mse": 0.0045,
               "served_logit_gap": 0.6, "sampled_topk_gap": 0.6,
               "state_gap": 0.024}}
REHEARSAL = {
    "config": TINY,
    "program": {"max_slots": 4, "page_size": 8, "max_len": 128,
                "prompt_buckets": [8, 16, 32], "prefill_chunk": 32,
                "decode_horizon": 2},
    "mix": {"clients": 8, "n_sizes": 32,
            "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.7,
                           "min": 8, "max": 90},
            "answer_len": {"dist": "lognormal", "median": 10, "sigma": 0.6,
                           "min": 2, "max": 24},
            "max_total_tokens": 120, "compare_requests": 6, "windows": 1}}


# -- the manifest ------------------------------------------------------------------

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_this_configurations_entries_are_in_the_manifest():
    """What PR 43 added, found by name: where an entry stands in its list
    is not this configuration's to say, nor what a later PR lists the
    cell under."""
    manifest = MANIFEST
    entry = harness.find(manifest["configs"], NAME, "config")
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == list(REDUCED)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "compose-closed256", 1)
    assert [c["name"] for c in manifest["workloads"]
            if c["config"] == NAME] == [CELL]               # no second cell
    slots = CONFIG["program"]["max_slots"]
    assert f"256 clients on {slots} slots" in cell["why"]
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isascii() and text.isprintable()
    for name in [NAME, CELL, cell["traffic"], *OWN, *REDUCED]:
        assert NAME_RE.match(name), name
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] in OWN] == OWN                      # in this order
    for name in OWN:
        m = harness.find(manifest["per_layer"], name, "metric")
        module = harness.load_layer_metric(name)
        assert m["workloads"][0] == CELL
        assert (m["unit"], m["layer"], m["moves"], m["source"]) == \
            (module.UNIT, module.LAYER, module.MOVES, module.SOURCE)
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    reported = {m["name"] for section in ("end_to_end", "per_layer")
                for m in harness.metrics_of_cell(manifest, section, CELL)}
    assert reported == {
        "serve_tokens_per_s", "setup_s", "prefill_time_share",
        "slot_occupancy.closed", "device_idle_share.closed",
        "decode_host_ms.closed", "queue_wait_ms.closed",
        "kv_pages_filled_share.closed", "slots_decoding_share.closed",
        "expert_picks_held_share", "expert_load_max_over_mean",
        "prefill_rows_real_share", "startup_program_s",
        "startup_trace_lower_s", "startup_compile_s", "startup_cache_read_s",
        "startup_first_run_s", *OWN}


# -- the file ------------------------------------------------------------------

def test_every_published_value_is_carried_unchanged_but_the_two_reduced():
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert (CONFIG["published"][key], CONFIG[key]) == REDUCED[key]
        else:
            assert CONFIG[key] == value, key
    # all 64 experts held, once more under the key the experts' readers read
    assert (CONFIG["n_routed_experts"], CONFIG["first_expert"]) == (64, 0)
    # the widths, as published
    c = CONFIG
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["conv_L_cache"]) == (2048, 32, 8, 3)
    assert (c["intermediate_size"], c["moe_intermediate_size"],
            c["num_experts"], c["num_experts_per_tok"], c["vocab_size"]) == \
        (11776, 1536, 64, 4, 65536)
    assert c["rope_parameters"]["rope_theta"] == 1000000
    assert (c["router_eps"], c["router_bias_std"], c["tie_embedding"],
            c["initializer_range"]) == (1e-6, 0.003, True, 0.02)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_published_values_are_the_catalogs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    assert row["config"] == PUBLISHED
    assert row["source_url"] == CONFIG["source"]
    entry = harness.find(MANIFEST["configs"], NAME, "config")
    assert entry["source"] == row["source_url"]


def test_the_cut_is_the_stated_deployments_stage_and_inside_the_floors():
    assert "ONE chip shares a layer" in CONFIG["deployment"]
    assert "first of five pipeline stages" in CONFIG["deployment"]
    # layer 0 the leading dense layer, then two periods' worth in the
    # published 3 : 1, above the guide's floor of four
    held = CONFIG["layer_types"][:9]
    assert held == ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv"]
    assert (held[1:].count("conv"), held[1:].count("full_attention")) == (6, 2)
    assert CONFIG["num_experts"] == CONFIG["n_routed_experts"] >= 8
    for key in ("assumed", "departures", "limits", "limits_from", "program",
                "reduced_why", "deployment"):
        assert CONFIG[key], key
    for detail in ("tie_embedding", "router_eps", "conv_order",
                   "initializer_range", "router_bias_std", "conv_init",
                   "serving_dtype", "n_routed_experts", "max_len", "page_size",
                   "max_slots", "prefill_chunk", "decode_horizon",
                   "prefill_order"):
        assert CONFIG["assumed"][detail], detail
    # ISSUE 43's settings; buckets and the chunks' order are the builder's
    # and the file says so
    prog = CONFIG["program"]
    assert {k: prog[k] for k in ("page_size", "max_len", "prefill_chunk",
                                 "decode_horizon")} == {
        "page_size": 16, "max_len": 3072, "prefill_chunk": 512,
        "decode_horizon": 4}
    assert 96 <= prog["max_slots"] <= 128
    assert prog["prompt_buckets"][-1] == 512
    for key in ("prefill_chunk", "prefill_order"):
        assert "the builder's choice" in CONFIG["assumed"][key]
    # bfloat16 tails: nothing for state_rounding_lost to hold
    assert set(CONFIG["limits"]) == {
        "router_flip_share", "served_logit_mse", "served_logit_gap",
        "sampled_topk_gap", "state_gap"}
    assert set(CONFIG["limits"]) <= set(CONFIG["limits_from"])
    assert "state_rounding_lost" in CONFIG["limits_from"]["left_out"]
    from benchmarks import traffic
    m = traffic.load_mix("compose-closed256")
    assert (m["kind"], m["loop"], m["clients"], m["n_sizes"]) == \
        ("requests", "closed", 256, 96)
    assert m["prompt_len"] == {"dist": "lognormal", "median": 384,
                               "sigma": 0.8, "min": 32, "max": 2048}
    assert m["answer_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.6, "min": 64, "max": 2048}
    assert m["max_total_tokens"] == prog["max_len"] == 3072
    assert m["compare_requests"] == 4 and m["windows"] in (1, 2)
    assert [(s["share"], s["temperature"], s["top_k"])
            for s in m["sampling"]] == [(0.5, 0.0, 0), (0.5, 0.8, 40)]
    _, prompts, answers = traffic.size_sets(m)
    assert 32 <= min(prompts) < 64 and max(prompts) == 2048
    assert 64 <= min(answers) < 128 and max(answers) == 2048


def test_the_reference_states_its_precisions_and_imports_no_program():
    ref = harness.load_reference(CONFIG)
    assert ref.STATED_PRECISION == "bfloat16"
    assert ref.CONTROL_PRECISION == "fp8"
    assert {"float32", "bfloat16", "fp8", "bf16_stream"} == set(ref.PRECISIONS)
    with open(os.path.join(harness.HERE, "configs", CONFIG["reference"])) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "deeplearning4j_tpu" not in code and "benchmarks" not in code
    assert 'default_matmul_precision("highest")' in code
    # the convolution as the plain sum over a zero-padded sequence
    assert "sum(p[\"conv_w\"][j] * z[j:j + T] for j in range(taps))" in code
    assert "jnp.zeros((taps - 1, d))" in code and "conv_general" not in code
    assert "*assumed*" in src


def test_the_arithmetic_of_the_cut():
    """ISSUE 43's count of what this chip holds."""
    c, k = CONFIG, conv_decode_bytes
    assert k.layer_counts(c) == (2, 7) and k.ff_counts(c) == (1, 8)
    assert k.conv_matrix_params(c) + 3 * 2048 == 16_783_360
    assert k.gqa_matrix_params(c) + 2 * 64 == 10_485_888
    assert k.expert_params(c) == 9_437_184
    assert 64 * k.expert_params(c) == 603_979_776
    assert k.dense_ff_params(c) == 72_351_744
    assert sum(k.router_params(c)) == 131_136
    conv_expert = 16_783_360 + 4_096 + 131_136 + 603_979_776
    gqa_expert = 10_485_888 + 4_096 + 131_136 + 603_979_776
    dense = 16_783_360 + 4_096 + 72_351_744
    assert (conv_expert, gqa_expert, dense) == \
        (620_898_368, 614_600_896, 89_139_200)
    total = k.held_params(c)
    assert total == dense + 6 * conv_expert + 2 * gqa_expert \
        + 65536 * 2048 + 2048 == 5_177_950_976
    assert round(total * 2 / 1e9, 2) == 10.36
    # the whole model: the name's 24B
    whole = 2 * dense + 28 * conv_expert + 10 * gqa_expert \
        + 65536 * 2048 + 2048
    assert round(whole / 1e9, 2) == 23.84
    prog = c["program"]
    assert k.kv_row_bytes(c) == 2048 and k.tail_bytes(c) == 8192
    pools = prog["max_slots"] * prog["max_len"] * 2 * k.kv_row_bytes(c)
    tails = prog["max_slots"] * 7 * k.tail_bytes(c)
    if prog["max_slots"] == 128:
        assert round(pools / 1e9, 2) == 1.61 and round(tails / 1e6) == 7
        assert round((total * 2 + pools + tails) / 1e9, 2) == 11.97
    assert 11e9 < total * 2 + pools + tails < 16e9


# -- bytes and operations, on a worked example ----------------------------------

SMALL = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 4, "num_dense_layers": 1,
         "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
         "conv_L_cache": 3, "intermediate_size": 10,
         "moe_intermediate_size": 6, "num_experts": 5, "n_routed_experts": 3,
         "vocab_size": 10}


def test_step_bytes_on_a_worked_example():
    c, k = SMALL, conv_decode_bytes
    assert k.layer_counts(c) == (1, 3)         # read up to the depth held
    assert k.ff_counts(c) == (1, 3) and k.head_dim(c) == 2
    gqa = 2 * 8 * 8 + 2 * 8 * 4
    assert k.gqa_matrix_params(c) == gqa == 192
    conv = 8 * 24 + 8 * 8
    assert k.conv_matrix_params(c) == conv == 256
    mixers = gqa + 2 * 2 + 3 * (conv + 3 * 8)
    assert k.mixer_params(c) == mixers == 1036
    assert k.expert_params(c) == 3 * 8 * 6 == 144
    assert k.dense_ff_params(c) == 3 * 8 * 10 == 240
    assert k.router_params(c) == (40, 5)
    fixed = (mixers + 4 * 16 + 240 + 3 * 40 + 8 + 80) * 2 + 3 * 5 * 4
    assert k.fixed_bytes(c) == fixed == 3156
    assert k.held_params(c) == mixers + 64 + 240 + 3 * (45 + 3 * 144) + 88
    assert k.kv_row_bytes(c) == 2 * 2 * 2 * 2 == 16
    assert k.tail_bytes(c) == 2 * 8 * 2 == 32
    assert k.tail_step_bytes(c, 6) == 2 * 6 * 32
    assert k.expert_step_bytes(c, 5) == 5 * 144 * 2
    assert k.step_bytes(c, experts_hit=5, kv_rows_held=100,
                        state_slots_stepped=6) == \
        fixed + 5 * 144 * 2 + 100 * 16 + 2 * 6 * 32
    # the real configuration: 0.69 GB fixed a step, 18.9 MB an expert hit,
    # 2,048 B a K and V row, 8 KB of tail a slot a layer; a step of 128
    # slots that hits every expert moves 10.4 GB of weights, and with 128
    # slots at 1,500 rows 11.2 GB, 13.6 ms at the chip's bandwidth, 87% of
    # it experts (93% of the weights)
    assert round(k.fixed_bytes(CONFIG) / 1e9, 2) == 0.69
    assert k.expert_params(CONFIG) * 2 == 18_874_368
    whole = k.step_bytes(CONFIG, 8 * 64, 2 * 128 * 1500, 7 * 128)
    assert round(whole / 1e9, 1) == 11.2
    assert 0.86 < k.expert_step_bytes(CONFIG, 8 * 64) / whole < 0.88
    weights = k.fixed_bytes(CONFIG) + k.expert_step_bytes(CONFIG, 8 * 64)
    assert round(weights / 1e9, 2) == 10.36
    assert 0.93 < k.expert_step_bytes(CONFIG, 8 * 64) / weights < 0.94
    assert k.tail_step_bytes(CONFIG, 7 * 128) / whole < 0.002


def test_chunk_flops_on_a_worked_example():
    c, k = SMALL, conv_decode_bytes
    rows, offset, picks = 10, 20, 7
    products = 2 * rows * (192 + 3 * 256 + 240 + 3 * 40)
    pairs = rows * offset + rows * 11 / 2
    assert k.chunk_flops(c, rows, offset, picks) == (
        products + 2 * picks * 144 + 2 * 8 * 10 + pairs * 4 * 4 * 2)
    # the real configuration: a 512-row chunk with every pick held is 0.53
    # TFLOP, 2.7 ms at the peak: under the 12.6 ms its weights take to
    # stream, so a chunk is bound by bytes as a step is
    flops = k.chunk_flops(CONFIG, 512, 0, 512 * 4 * 8)
    assert 0.52e12 < flops < 0.54e12
    assert flops / 197e12 < 10.36e9 / 819e9


# -- the readers -------------------------------------------------------------------

def _observed(**kw):
    return harness.Observed(
        cell=harness.Cell(name=CELL, chips=1, seed=1, seconds=1.0,
                          trace=True, config=CONFIG, mix={}, reference=None,
                          devices=[]),
        window={}, counters={}, **kw)


def test_the_readers_return_nothing_when_given_nothing():
    for name in OWN:
        assert harness.load_layer_metric(name).read(_observed()) is None


def test_the_readers_return_nothing_for_a_program_without_the_counts(
        monkeypatch):
    """Another program's spans carry no such counts, and another
    configuration's file no such sizes: the metric is left out, nothing
    raises."""
    from benchmarks import program_spans
    spans = [program_spans.Span("serve/decode_step", 0.0, 1.0,
                                {"tokens": 4, "experts_hit": 3}),
             program_spans.Span("serve/prefill", 0.0, 1.0,
                                {"offset": 0, "expert_picks_held": 9})]
    monkeypatch.setattr(program_spans, "of", lambda observed: spans)

    class Trace:
        def module_runs(self, pattern):
            return [0.05]
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    observed = _observed(trace=Trace(), peaks=peaks)
    for name in OWN:
        assert harness.load_layer_metric(name).read(observed) is None
    full = [program_spans.Span("serve/decode_step", 0.0, 1.0, {
                "tokens": 4, "experts_hit": 3, "kv_rows_held": 5,
                "state_slots_stepped": 7}),
            program_spans.Span("serve/prefill", 0.0, 1.0, {
                "offset": 0, "expert_picks_held": 9, "prompt_tokens": 3,
                "state_rows_scanned": 4})]
    monkeypatch.setattr(program_spans, "of", lambda observed: full)
    other = _observed(trace=Trace(), peaks=peaks)
    other.cell.config = harness.load_config("granite-4.0-h-small")
    for name in OWN:
        assert harness.load_layer_metric(name).read(other) is None


def test_the_readers_on_a_worked_example(monkeypatch):
    """Two fused dispatches of 4 steps and two chunks with the counts the
    program puts on its spans, beside device times of 100 ms and 40 ms."""
    from benchmarks import program_spans

    def span(name, **args):
        return program_spans.Span(name, 0.0, 1.0, args)
    spans = [
        span("serve/decode_step", tokens=4, experts_hit=4 * 512,
             kv_rows_held=4 * 2 * 120_000, kv_rows_read=2_000_000,
             state_slots_stepped=4 * 7 * 128, state_rows_scanned=0),
        span("serve/decode_step", tokens=4, experts_hit=4 * 500,
             kv_rows_held=4 * 2 * 100_000, kv_rows_read=2_000_000,
             state_slots_stepped=4 * 7 * 120, state_rows_scanned=0),
        span("serve/decode_step", n_active=3),      # another program's
        span("serve/prefill", prompt_tokens=512, offset=512, bucket=512,
             state_rows_scanned=7 * 512, kv_rows_read=2 * 1536,
             kv_rows_held=2 * 1024, expert_picks_held=8 * 4 * 512,
             state_slots_stepped=0),
        span("serve/prefill", prompt_tokens=90, offset=0, bucket=128,
             state_rows_scanned=7 * 90, kv_rows_read=2 * 128,
             kv_rows_held=2 * 90, expert_picks_held=8 * 4 * 90,
             state_slots_stepped=0),
        span("serve/prefill", prompt_tokens=700, bucket=0)]    # an attach
    monkeypatch.setattr(program_spans, "of", lambda observed: spans)

    class Trace:
        def module_runs(self, pattern):
            if re.search(pattern, "jit_step_multi"):
                assert not re.search(pattern, "jit_prefill_at")
                return [0.10, 0.10]
            assert re.search(pattern, "jit_prefill_at")
            assert not re.search(pattern, "jit_step_multi")
            return [0.05, 0.03]
    observed = _observed(trace=Trace(), peaks={"hbm_bytes_per_s": 819e9,
                                               "bf16_flops_per_s": 197e12})
    k = conv_decode_bytes
    fixed, tail = k.fixed_bytes(CONFIG), k.tail_bytes(CONFIG)
    one = 4 * fixed + 2048 * 18_874_368 + 960_000 * 2048 + 2 * 3584 * tail
    two = 4 * fixed + 2000 * 18_874_368 + 800_000 * 2048 + 2 * 3360 * tail
    roofline = harness.load_layer_metric(OWN[0]).read(observed)
    assert roofline == pytest.approx(100 * (one + two) / 2 / 819e9 / 0.10)
    assert 50 < roofline < 60
    share = harness.load_layer_metric(OWN[1]).read(observed)
    assert share == pytest.approx(100 * 4048 * 18_874_368 / (one + two))
    assert 85 < share < 93
    flops = (k.chunk_flops(CONFIG, 512, 512, 8 * 4 * 512)
             + k.chunk_flops(CONFIG, 90, 0, 8 * 4 * 90)) / 2
    chunk = harness.load_layer_metric(OWN[2]).read(observed)
    assert chunk == pytest.approx(100 * flops / 197e12 / 0.04)
    assert 0 < chunk < 100


# -- the share ---------------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer():
    """The routed parts that the shares [0, 32) and [32, 64) of a
    64-expert layer give equal the uncut reference's expert layer; and the
    program's held layer is one such share, at this family's epsilon."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel.moe import moe_forward_held

    ref = harness.load_reference(CONFIG)
    sz = {k: {**CONFIG, **TINY}[k] for k in ref.SIZE_KEYS}
    whole_sizes = {**sz, "num_experts": 64, "n_routed_experts": 64,
                   "num_experts_per_tok": 4}
    whole = ref.init_layer(jax.random.PRNGKey(3), whole_sizes, "conv", False,
                           jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (33, 64))
    with ref.with_precision("float32"):
        want, want_picks = ref.experts(whole, x, whole_sizes)
        total = 0.0
        for lo in (0, 32):
            p = {**whole, **{k: whole[k][lo:lo + 32]
                             for k in ("e_gate", "e_up", "e_down")}}
            part, picks = ref.experts(p, x, whole_sizes, first_expert=lo)
            y, got_picks, stats = moe_forward_held(
                p, x, first_expert=lo, k=4, scaling=1.0, router="noaux_tc",
                router_eps=1e-6)
            np.testing.assert_array_equal(np.asarray(picks),
                                          np.asarray(want_picks))
            np.testing.assert_array_equal(np.asarray(got_picks),
                                          np.asarray(want_picks))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part),
                                       rtol=2e-4, atol=2e-5)
            assert int(stats[0]) == 33 * 4 and 0 < int(stats[1]) < 33 * 4
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("rows", [4, 11, 33, 128])
def test_an_expert_applied_to_its_own_rows_is_the_weighted_sum(rows,
                                                               monkeypatch):
    """``reference.experts`` applies an expert to the rows that chose it,
    ``EXPERT_ROWS`` at a time; whatever that number (one that divides the
    33 rows, one that does not, the rows themselves, more than them), the
    layer is the sum over EVERY expert applied to EVERY row, weighted by
    the router's weight where the row chose it and 0 elsewhere."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = harness.load_reference(CONFIG)
    sz = {k: {**CONFIG, **TINY}[k] for k in ref.SIZE_KEYS}
    p = ref.init_layer(jax.random.PRNGKey(5), sz, "conv", False, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(9), (33, sz["hidden_size"]))
    monkeypatch.setattr(ref, "EXPERT_ROWS", rows)
    with ref.with_precision("float32"):
        got, picks = ref.experts(p, x, sz)
        idx, w = ref.route(x, p["router_w"], p["router_b"],
                           sz["num_experts_per_tok"], 1.0, sz["router_eps"])
        want = sum(
            jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)[:, None]
            * ref.gated_silu(x, p["e_gate"][e], p["e_up"][e], p["e_down"][e],
                             jnp.matmul)
            for e in range(sz["n_routed_experts"]))
    np.testing.assert_array_equal(np.asarray(picks),
                                  np.sort(np.asarray(idx), axis=-1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


# -- the harness, rehearsed ---------------------------------------------------------------

def run(capsys, seed="4294967311"):
    rc = harness.main(["--workload", CELL, "--seed", seed, "--seconds", "3",
                       "--trace", "0"], rehearsal=REHEARSAL)
    out = capsys.readouterr().out.strip().splitlines()
    compared = {}
    for ln in out:
        if ln.startswith("bench: compared: {"):
            c = json.loads(ln[len("bench: compared: "):])
            compared[c["number"]] = c
    return rc, json.loads(out[-1]), compared, out


def test_rehearsal_prints_the_contracts_line(capsys):
    rc, result, compared, lines = run(capsys, "4294967311")
    assert rc == 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert all(m["value"] is None for m in result["metrics"].values())
    assert set(compared) == set(TINY["limits"])
    assert any("compilations inside the window: 0" in ln for ln in lines)
    counted = next(json.loads(ln[len("bench: window: "):]) for ln in lines
                   if ln.startswith("bench: window: "))
    counters = counted["counters_of_the_process"]
    assert counters["kv_rows_read"] >= counters["kv_rows_held"] > 0
    assert counters["state_slots_stepped"] > 0
    assert counters["state_rows_scanned"] > 0
    assert counters["expert_picks_held"] == counters["expert_picks"] > 0
    assert counters["recurrent_state_resets"] >= counted["prefills"] > 0
    assert counters["admit_rounds_budget_bound"] > 0
    # 4 slots x 4 convolution layers x a tail of 2 x 64 bfloat16
    assert counted["recurrent_state_bytes"] == 4 * 4 * 2 * 64 * 2


def test_a_traced_rehearsal_reports_every_metric_of_the_cell(capsys):
    rc = harness.main(["--workload", CELL, "--seed", "11", "--seconds", "2",
                       "--trace", "1"], rehearsal=REHEARSAL)
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True
    # nothing is traced on the CPU: the trace's readers find nothing and
    # leave their metric out; nothing raises
    assert set(result["metrics"]) <= {
        m["name"] for m in harness.metrics_of_cell(MANIFEST, "per_layer",
                                                   CELL)}


def _served(seed=2147483659):
    _, cell, _ = harness.open_cell(CELL, seed, 3.0, False, REHEARSAL)
    runner = harness.load_runner(cell.config["runner"])
    state = runner.setup(cell, {})
    runner.window(cell, state, harness.Tracer(False, ""))
    return cell, runner, runner.release(cell, state)


def test_the_fp8_control_fails_and_the_program_passes():
    """The fp8 control fails the router's number, the state's and the
    logits'.  The second control, the stated operands with a bfloat16
    router and residual stream, reads further out than the program and
    than the reference AT the stated precision, which reads as the
    program does; at this size (an expert bias of 0.1 chooses, five
    layers 64 wide) it stays inside the limits, and what it fails at the
    published widths is in the configuration's ``limits_from``."""
    cell, runner, served = _served()
    limits = cell.config["limits"]
    res = runner.compare(cell, served, with_control=True)
    assert set(res["numbers"]) == set(limits)       # no state_rounding_lost
    for name, value in res["numbers"].items():
        assert value <= limits[name], (name, value)
    failed = [n for n, v in res["control"].items()
              if v is None or v > limits[n]]
    assert {"router_flip_share", "state_gap", "served_logit_mse"} \
        <= set(failed)
    stream, stated = (runner.compare(cell, served, with_control=True,
                                     control_precision=low)
                      for low in ("bf16_stream", "bfloat16"))
    assert stream["numbers"] == stated["numbers"] == res["numbers"]
    assert set(stream["control"]) == set(limits)
    for name in ("router_flip_share", "state_gap"):
        assert stream["control"][name] > stated["control"][name] * 1.1
        assert stream["control"][name] > res["numbers"][name] * 1.1
        # the stated precision with nothing of the program in it reads as
        # the program does
        assert 0.7 < stated["control"][name] / res["numbers"][name] < 1.4


def _breaks():
    """Name -> [(module, attribute, replacement)]."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import conv_gqa, latent_moe
    from deeplearning4j_tpu.models.sparse_gqa import rotate_half
    from deeplearning4j_tpu.parallel import moe

    conv, inputs, chunk = (conv_gqa.short_conv, conv_gqa.conv_inputs,
                           conv_gqa.conv_chunk)
    rms_norm, _mm = latent_moe.rms_norm, latent_moe._mm

    def taps_reversed(p, z_ext, arch):
        return conv({**p, "conv_w": p["conv_w"][::-1]}, z_ext, arch)

    def c_before_the_convolution(p, h, arch):
        C, z = inputs(p, h, arch)
        return jnp.ones_like(C), (C * z.astype(jnp.float32)).astype(z.dtype)

    def project(k_norm=True, rotary_first=False):
        def gqa_project(p, h, rope, arch):
            cos, sin = (t[:, None, :] for t in rope)
            n = h.shape[0]
            H, KV, D = arch.n_heads, arch.n_kv_heads, arch.head_dim
            cd = p["W_k"].dtype
            u = rms_norm(h, p["ln1_g"], arch.rms_eps)
            q = _mm(u, p["W_q"]).reshape(n, H, D)
            k = _mm(u, p["W_k"]).reshape(n, KV, D)
            if rotary_first:
                q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
            q = rms_norm(q, p["q_norm_g"], arch.rms_eps)
            if k_norm:
                k = rms_norm(k, p["k_norm_g"], arch.rms_eps)
            if not rotary_first:
                q, k = rotate_half(q, cos, sin), rotate_half(k, cos, sin)
            return (q, None), (k.reshape(n, KV * D).astype(cd),
                               _mm(u, p["W_v"]).astype(cd))
        return gqa_project

    def route(biased_weights=False, normalised=True):
        def route_noaux_tc(x, router_w, router_b, k, scaling, eps=1e-20):
            logits = jnp.dot(x.astype(jnp.float32),
                             router_w.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            scores = jax.nn.sigmoid(logits)
            biased = scores + router_b.astype(jnp.float32)
            _, idx = jax.lax.top_k(biased, k)
            w = jnp.take_along_axis(biased if biased_weights else scores,
                                    idx, axis=-1)
            if normalised:
                w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
            return idx.astype(jnp.int32), w * scaling
        return route_noaux_tc

    def never_reset(p, h, state, arch, offset=0, n_real=None):
        # a chunk at offset 0 carries on from what the slot held
        return chunk(p, h, state, arch, offset + 1 if state is not None
                     else offset, n_real)

    def untied(params, h, arch):
        # a head of its own: the embedding's rows in another order
        u = rms_norm(h, params["lnf_g"], arch.rms_eps)
        return _mm(u, jnp.roll(params["embed"], 1, axis=0).T)

    return {
        "the taps reversed": [(conv_gqa, "short_conv", taps_reversed)],
        "C applied before the convolution": [
            (conv_gqa, "conv_inputs", c_before_the_convolution)],
        "the k norm left out": [(conv_gqa, "gqa_project",
                                 project(k_norm=False))],
        "rotary before the norm": [(conv_gqa, "gqa_project",
                                    project(rotary_first=True))],
        "weights taken from the biased scores": [
            (moe, "route_noaux_tc", route(biased_weights=True))],
        "the normalisation left out": [
            (moe, "route_noaux_tc", route(normalised=False))],
        "the tail not reset at admission": [(conv_gqa, "conv_chunk",
                                             never_reset)],
        "an untied head": [(latent_moe, "_logits", untied),
                           (conv_gqa, "_logits", untied)]}


FAULTS = ["the taps reversed", "C applied before the convolution",
          "the k norm left out", "rotary before the norm",
          "weights taken from the biased scores",
          "the normalisation left out", "the tail not reset at admission",
          "an untied head"]


@pytest.mark.parametrize("what", FAULTS)
def test_broken_underneath_is_not_correct(what, capsys, monkeypatch):
    for module, attr, fn in _breaks()[what]:
        monkeypatch.setattr(module, attr, fn)
    rc, result, compared, _ = run(capsys)
    assert rc == 0 and result["correct"] is False
    failed = [n for n, c in compared.items() if not c["ok"]]
    assert failed, compared
