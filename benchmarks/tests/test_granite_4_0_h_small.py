"""Configuration ``granite-4.0-h-small`` and its cell: the manifest's own
limits, the file against the catalog's published values, the cut against
the stated deployment, the bytes and operations of
``benchmarks/kernels/ssm_decode_bytes.py`` on a worked example, the
readers on a worked example and on nothing, the two shares against the
uncut layer, a CPU rehearsal of the cell at tiny sizes, the controls
beside the program, and ``correct`` coming out false when the timed path
is broken underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_granite_4_0_h_small.py -q
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
from benchmarks.kernels import ssm_decode_bytes    # noqa: E402

NAME = "granite-4.0-h-small"
CELL = NAME + ".chat-closed128"
CONFIG = harness.load_config(NAME)
MANIFEST = harness.load_manifest()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
OWN = ["ssm_decode_bytes_roofline", "ssm_state_bytes_share",
       "ssm_chunk_flops_roofline", "prefill_rows_real_share"]
REDUCED = {"num_hidden_layers": (40, 10), "num_local_experts": (72, 36),
           "vocab_size": (100352, 50176)}
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

#: the catalog row's ``config`` (granite-4.0-h-small, config.json)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 1536,
    "tie_word_embeddings": True, "vocab_size": 100352}

#: a model of the same family a test run holds: 4 layers (layer 1
#: grouped-query), 4 query heads over 2 KV heads of 16, 8 state-space heads
#: of 16 with a state of 16 scanned in chunks of 16, 16 experts (8 held)
#: with 3 a token and a shared MLP two experts wide.  The limits are this
#: size's own.  On six seeds the program (bf16 weights in a model 64 wide,
#: logits divided by 16) reads flips 0 .. 0.0035, mse 6.2e-08 .. 7.6e-08,
#: ``state_gap`` 0.0039 .. 0.0058; the fp8 control 0.053, 2.7e-05 and 0.16;
#: the mildest break (a slot's state not reset at admission) 0.009 .. 0.013,
#: 4.1e-07 .. 5.6e-07 and 0.037 .. 0.087, so it passes the router's limit
#: and fails the other two.  The two widest-gap numbers read 0 to 0.0004 of
#: the program and only bound a break.  ``state_rounding_lost`` reads 0.26
#: .. 0.51 of the program (a state of 8 x 16 x 16 values is too few for the
#: 0.005 the real size reads) and exactly 1 of a state kept in bfloat16.
TINY = {
    "vocab_size": 256, "num_hidden_layers": 4, "hidden_size": 64,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.125,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_chunk_size": 16, "intermediate_size": 32,
    "shared_intermediate_size": 64, "num_local_experts": 8,
    "num_local_experts_published": 16, "first_expert": 8,
    "num_experts_per_tok": 3, "max_position_embeddings": 256,
    "initializer_range": 0.2,
    "limits": {"router_flip_share": 0.02, "served_logit_mse": 2e-7,
               "served_logit_gap": 0.05, "sampled_topk_gap": 0.05,
               "state_gap": 0.015, "state_rounding_lost": 0.75}}
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


def _strings(node):
    if isinstance(node, str):
        yield node
    elif isinstance(node, dict):
        for v in node.values():
            yield from _strings(v)
    elif isinstance(node, list):
        for v in node:
            yield from _strings(v)


def test_every_string_of_the_manifest_is_one_printable_line_of_at_most_200():
    for text in _strings(MANIFEST):
        assert 1 <= len(text) <= 200 and text.isascii() \
            and text.isprintable(), text
    assert len(json.dumps(MANIFEST, indent=1)) <= 64 * 1024
    cell = harness.find(MANIFEST["workloads"], CELL, "workload")
    for name in [NAME, CELL, cell["traffic"], *OWN, *REDUCED]:
        assert NAME_RE.match(name), name


def test_this_configurations_entries_are_in_the_manifest():
    """What PR 41 added, found by name: where an entry stands in its list
    is not this configuration's to say, nor what a later PR lists the
    cell under."""
    manifest = MANIFEST
    entry = harness.find(manifest["configs"], NAME, "config")
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert entry["reduced"] == list(REDUCED)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "chat-closed128", 1)
    assert [c["name"] for c in manifest["workloads"]
            if c["config"] == NAME] == [CELL]               # no second cell
    # the experts see half their share: the cell's why says so
    slots = CONFIG["program"]["max_slots"]
    assert f"128 clients on {slots} slots" in cell["why"]
    assert (f"{slots * 10 / 72:.1f} picks a held expert a step for "
            f"{2 * slots * 10 / 72:.1f}") in cell["why"]
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] in OWN] == OWN                      # in this order
    for name in OWN:
        m = harness.find(manifest["per_layer"], name, "metric")
        assert m["workloads"][0] == CELL
        assert m["moves"] == "serve_tokens_per_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    reported = {m["name"] for section in ("end_to_end", "per_layer")
                for m in harness.metrics_of_cell(manifest, section, CELL)}
    assert reported >= {
        "serve_tokens_per_s", "setup_s", "prefill_time_share",
        "slot_occupancy.closed", "device_idle_share.closed",
        "decode_host_ms.closed", "queue_wait_ms.closed",
        "kv_pages_filled_share.closed", "slots_decoding_share.closed",
        "expert_picks_held_share", "expert_load_max_over_mean",
        "startup_program_s", "startup_trace_lower_s", "startup_compile_s",
        "startup_cache_read_s", "startup_first_run_s", *OWN}
    # another configuration's readers find nothing to read here
    assert not reported & {
        "recurrent_decode_bytes_roofline", "recurrent_state_bytes_share",
        "prefill_chunk_flops_roofline", "sparse_decode_bytes_roofline",
        "decode_bytes_roofline", "paged_attention_roofline"}


# -- the file ------------------------------------------------------------------

def test_every_published_value_is_carried_unchanged_but_the_three_reduced():
    assert CONFIG["reduced"] == list(REDUCED)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert (CONFIG["published"][key], CONFIG[key]) == REDUCED[key]
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["num_local_experts_published"] == 72
    assert CONFIG["first_expert"] in (0, 36)         # one of the 2 shares
    # the held count once more, under the key the experts' readers read
    assert CONFIG["n_routed_experts"] == CONFIG["num_local_experts"]
    # the widths, as published
    c = CONFIG
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"]) == (4096, 32, 8)
    assert (c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_d_conv"], c["mamba_chunk_size"]) == (128, 64, 128, 4, 256)
    assert (c["intermediate_size"], c["shared_intermediate_size"],
            c["num_experts_per_tok"]) == (768, 1536, 10)
    assert (c["embedding_multiplier"], c["residual_multiplier"],
            c["logits_scaling"], c["attention_multiplier"]) == \
        (12, 0.22, 16, 0.0078125)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_published_values_are_the_catalogs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == NAME)
    assert row["config"] == PUBLISHED
    assert row["source_url"] == CONFIG["source"]
    entry = harness.find(MANIFEST["configs"], NAME, "config")
    assert entry["source"] == row["source_url"]


def test_the_cut_is_the_stated_deployments_share_and_inside_the_floors():
    assert "2 chips share each layer" in CONFIG["deployment"]
    assert "[36, 72)" in CONFIG["deployment"]
    # one whole period of the pattern, above the guide's floor of four
    assert CONFIG["layer_types"][:10] == PERIOD
    assert CONFIG["num_local_experts"] >= 8 and 72 // 2 == 36
    assert CONFIG["vocab_size"] * 2 == 100352
    for key in ("assumed", "departures", "limits", "limits_from", "program",
                "reduced_why", "deployment"):
        assert CONFIG[key], key
    for detail in ("expert_width", "router", "mamba_init", "mamba_dt_limit",
                   "mamba_norm", "attention", "multipliers", "serving_dtype",
                   "initializer_range", "n_routed_experts", "max_len",
                   "page_size", "max_slots",
                   "prefill_chunk", "decode_horizon", "prefill_order"):
        assert CONFIG["assumed"][detail], detail
    # ISSUE 41's settings; buckets and the chunks' order are the builder's
    # and the file says so
    prog = CONFIG["program"]
    assert {k: prog[k] for k in ("page_size", "max_len", "prefill_chunk",
                                 "decode_horizon")} == {
        "page_size": 16, "max_len": 2560, "prefill_chunk": 512,
        "decode_horizon": 4}
    assert 48 <= prog["max_slots"] <= 64
    assert "stop_trace" in CONFIG["assumed"]["max_slots"]
    assert prog["prompt_buckets"][-1] == 512
    for key in ("prefill_chunk", "prefill_order"):
        assert "the builder's choice" in CONFIG["assumed"][key]
    assert set(CONFIG["limits"]) == {
        "router_flip_share", "served_logit_mse", "served_logit_gap",
        "sampled_topk_gap", "state_gap", "state_rounding_lost"}
    assert set(CONFIG["limits"]) <= set(CONFIG["limits_from"])
    from benchmarks import traffic
    m = traffic.load_mix("chat-closed128")
    assert (m["kind"], m["loop"], m["clients"], m["n_sizes"]) == \
        ("requests", "closed", 128, 96)
    assert m["prompt_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.8, "min": 32, "max": 2048}
    assert m["answer_len"] == {"dist": "lognormal", "median": 160,
                               "sigma": 0.6, "min": 16, "max": 512}
    assert m["max_total_tokens"] == prog["max_len"] == 2560
    assert m["prompt_len"]["max"] + m["answer_len"]["max"] \
        <= m["max_total_tokens"]
    assert m["compare_requests"] == 4 and m["windows"] in (1, 2)
    assert [(s["share"], s["temperature"], s["top_k"])
            for s in m["sampling"]] == [(0.5, 0.0, 0), (0.5, 0.8, 40)]
    _, prompts, answers = traffic.size_sets(m)
    assert 32 <= min(prompts) < 40 and 1900 < max(prompts) <= 2048
    assert 16 <= min(answers) < 40 and max(answers) == 512


def test_the_reference_states_its_precisions_and_imports_no_program():
    ref = harness.load_reference(CONFIG)
    assert ref.STATED_PRECISION == "bfloat16"
    assert ref.CONTROL_PRECISION == "fp8"
    assert {"float32", "bfloat16", "fp8", "bf16_state"} == set(ref.PRECISIONS)
    with open(os.path.join(harness.HERE, "configs", CONFIG["reference"])) as f:
        src = f.read()
    code = src.split('"""', 2)[2]
    assert "deeplearning4j_tpu" not in code and "benchmarks" not in code
    assert 'default_matmul_precision("highest")' in code
    # the recurrence as written, token by token: a scan whose carry is S
    assert "jax.lax.scan(token" in code and "cumsum" not in code
    assert "*Assumed*" in src


def test_the_arithmetic_of_the_cut():
    """ISSUE 41's count of what this chip holds."""
    c, k = CONFIG, ssm_decode_bytes
    assert k.layer_counts(c) == (1, 9)
    assert (k.inner(c), k.conv_dim(c), k.head_dim(c)) == (8192, 8448, 128)
    mamba = 4096 * 16768 + 8192 * 4096
    assert k.mamba_matrix_params(c) == mamba
    assert round((mamba + 5 * 8448 + 8192 + 3 * 128) / 1e6, 2) == 102.29
    assert k.gqa_matrix_params(c) == 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert round(k.gqa_matrix_params(c) / 1e6, 2) == 41.94
    assert k.expert_params(c) == 4096 * 1536 + 768 * 4096 == 9_437_184
    assert k.shared_params(c) == 4096 * 72 + 4096 * 3072 + 1536 * 4096
    assert round(36 * k.expert_params(c) / 1e6, 2) == 339.74
    assert round(50176 * 4096 / 1e6, 1) == 205.5
    total = k.held_params(c)
    assert round(total / 1e9, 2) == 4.76
    assert round(total * 2 / 1e9, 2) == 9.51
    prog = c["program"]
    assert k.kv_row_bytes(c) == 4096
    pools = (1 + prog["max_slots"] * prog["max_len"]) * k.kv_row_bytes(c)
    state = prog["max_slots"] * 9 * k.state_bytes(c)
    assert k.state_bytes(c) == 128 * 64 * 128 * 4 + 3 * 8448 * 2
    assert prog["max_slots"] == 48       # ISSUE 41's 64: 0.67 and 2.45 GB
    assert round(pools / 1e9, 2) == 0.50
    assert round(state / 1e9, 2) == 1.83
    held = total * 2 + pools + state
    assert 11e9 < held < 16e9


# -- bytes and operations, on a worked example ----------------------------------

SMALL = {"hidden_size": 8, "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 3, "layer_types": ["mamba", "attention",
                                                 "mamba", "mamba"],
         "mamba_n_heads": 2, "mamba_d_head": 8, "mamba_d_state": 4,
         "mamba_d_conv": 4, "mamba_chunk_size": 4, "mamba_conv_bias": True,
         "intermediate_size": 6, "shared_intermediate_size": 12,
         "num_local_experts": 3, "num_local_experts_published": 5,
         "vocab_size": 10}


def test_step_bytes_on_a_worked_example():
    c, k = SMALL, ssm_decode_bytes
    assert k.layer_counts(c) == (1, 2)         # read up to the depth held
    assert (k.head_dim(c), k.inner(c), k.conv_dim(c)) == (2, 16, 24)
    gqa = 2 * 8 * 8 + 2 * 8 * 4
    assert k.gqa_matrix_params(c) == gqa == 192
    mamba = 8 * (16 + 24 + 2) + 16 * 8
    assert k.mamba_matrix_params(c) == mamba == 464
    assert k.mamba_vector_bytes(c) == (5 * 24 + 16) * 2 + 3 * 2 * 4 == 296
    assert k.expert_params(c) == 3 * 8 * 6 == 144
    assert k.shared_params(c) == 8 * (5 + 36) == 328
    fixed = (gqa + 2 * mamba + 3 * (328 + 16) + 8 + 80) * 2 + 2 * 296
    assert k.fixed_bytes(c) == fixed == 5072
    assert k.kv_row_bytes(c) == 2 * 2 * 2 * 2 == 16
    assert k.state_bytes(c) == 2 * 8 * 4 * 4 + 3 * 24 * 2 == 400
    assert k.state_step_bytes(c, 6) == 2 * 6 * 400
    assert k.step_bytes(c, experts_hit=5, kv_rows_held=100,
                        state_slots_stepped=6) == \
        fixed + 5 * 144 * 2 + 100 * 16 + 2 * 6 * 400
    # the real configuration: 2.72 GB fixed a step, 18.9 MB an expert hit,
    # 4,096 B a K and V row, 4.24 MB of state and tail a slot a layer; a
    # step of 64 slots that hits every held expert moves 14.6 GB, 17.8 ms
    # at the chip's bandwidth, a third of it state
    assert round(k.fixed_bytes(CONFIG) / 1e9, 2) == 2.72
    assert k.expert_params(CONFIG) * 2 == 18_874_368
    whole = k.step_bytes(CONFIG, 360, 64 * 600, 64 * 9)
    assert round(whole / 1e9, 1) == 14.6
    assert 0.33 < k.state_step_bytes(CONFIG, 64 * 9) / whole < 0.34


def test_chunk_flops_on_a_worked_example():
    c, k = SMALL, ssm_decode_bytes
    # 10 real rows in chunks of 4: two whole chunks of 10 pairs, one of 2 rows
    assert k.scan_pairs(10, 4) == 2 * 10 + 3
    assert k.scan_pairs(3, 4) == 6 and k.scan_pairs(8, 8) == 36
    assert k.scan_flops(c, 10, 4) == 23 * 2 * (4 + 16) + 10 * 4 * 16 * 4
    rows, offset, picks = 10, 20, 7
    dense = 2 * rows * (192 + 2 * 464 + 3 * 328)
    pairs = rows * offset + rows * 11 / 2
    assert k.chunk_flops(c, rows, offset, picks, bucket=16) == (
        dense + 2 * picks * 144 + 2 * 8 * 10 + pairs * 4 * 4 * 2
        + 2 * k.scan_flops(c, rows, 4))
    # a bucket shorter than the scan's chunk is one chunk of the bucket's rows
    assert k.chunk_flops(c, 3, 0, 0, bucket=2) \
        - k.chunk_flops(c, 3, 0, 0, bucket=16) == 2 * 2 * (4 + 16) * (
            k.scan_pairs(3, 2) - k.scan_pairs(3, 4))
    # the real configuration: a 256-row chunk with even routing is 0.85
    # TFLOP, 4.3 ms at the peak; the scan is under a fiftieth of it
    flops = k.chunk_flops(CONFIG, 256, 0, 256 * 50, 256)
    assert 0.8e12 < flops < 0.9e12
    assert 9 * k.scan_flops(CONFIG, 256, 256) < flops / 50


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
    """Another program's spans carry no such counts: the metric is left
    out, nothing raises."""
    from benchmarks import program_spans
    spans = [program_spans.Span("serve/decode_step", 0.0, 1.0,
                                {"tokens": 4, "experts_hit": 3}),
             program_spans.Span("serve/prefill", 0.0, 1.0,
                                {"offset": 0, "expert_picks_held": 9})]
    monkeypatch.setattr(program_spans, "of", lambda observed: spans)

    class Trace:
        def module_runs(self, pattern):
            return [0.05]
    observed = _observed(trace=Trace(), peaks={"hbm_bytes_per_s": 819e9,
                                               "bf16_flops_per_s": 197e12})
    for name in OWN:
        assert harness.load_layer_metric(name).read(observed) is None


def test_the_readers_on_a_worked_example(monkeypatch):
    """Two fused dispatches of 4 steps and two chunks with the counts the
    program puts on its spans, beside device times of 140 ms and 20 ms."""
    from benchmarks import program_spans

    def span(name, **args):
        return program_spans.Span(name, 0.0, 1.0, args)
    spans = [
        span("serve/decode_step", tokens=4, experts_hit=4 * 360,
             kv_rows_held=150_000, kv_rows_read=260_000,
             state_slots_stepped=4 * 64 * 9, state_rows_scanned=0,
             state_rows_computed=0),
        span("serve/decode_step", tokens=4, experts_hit=4 * 350,
             kv_rows_held=100_000, kv_rows_read=260_000,
             state_slots_stepped=4 * 60 * 9, state_rows_scanned=0,
             state_rows_computed=0),
        span("serve/decode_step", n_active=3),      # another program's
        span("serve/prefill", prompt_tokens=512, offset=512, bucket=512,
             state_rows_scanned=9 * 512, state_rows_computed=9 * 512,
             kv_rows_read=1024 + 512, kv_rows_held=1024,
             expert_picks_held=25_000, state_slots_stepped=0),
        span("serve/prefill", prompt_tokens=90, offset=0, bucket=128,
             state_rows_scanned=9 * 90, state_rows_computed=9 * 128,
             kv_rows_read=128, kv_rows_held=90, expert_picks_held=4_400,
             state_slots_stepped=0),
        span("serve/prefill", prompt_tokens=700, bucket=0)]    # an attach
    monkeypatch.setattr(program_spans, "of", lambda observed: spans)

    class Trace:
        def module_runs(self, pattern):
            if re.search(pattern, "jit_step_multi"):
                assert not re.search(pattern, "jit_prefill_at")
                return [0.14, 0.14]
            assert re.search(pattern, "jit_prefill_at")
            assert not re.search(pattern, "jit_step_multi")
            return [0.03, 0.01]
    observed = _observed(trace=Trace(), peaks={"hbm_bytes_per_s": 819e9,
                                               "bf16_flops_per_s": 197e12})
    k = ssm_decode_bytes
    fixed, state = k.fixed_bytes(CONFIG), k.state_bytes(CONFIG)
    one = 4 * fixed + 1440 * 18_874_368 + 150_000 * 4096 + 2 * 2304 * state
    two = 4 * fixed + 1400 * 18_874_368 + 100_000 * 4096 + 2 * 2160 * state
    roofline = harness.load_layer_metric(OWN[0]).read(observed)
    assert roofline == pytest.approx(100 * (one + two) / 2 / 819e9 / 0.14)
    assert 0 < roofline < 100
    share = harness.load_layer_metric(OWN[1]).read(observed)
    assert share == pytest.approx(100 * 2 * (2304 + 2160) * state
                                  / (one + two))
    assert 25 < share < 40
    flops = (k.chunk_flops(CONFIG, 512, 512, 25_000, 512)
             + k.chunk_flops(CONFIG, 90, 0, 4_400, 128)) / 2
    chunk = harness.load_layer_metric(OWN[2]).read(observed)
    assert chunk == pytest.approx(100 * flops / 197e12 / 0.02)
    assert 0 < chunk < 100
    real = harness.load_layer_metric(OWN[3]).read(observed)
    assert real == pytest.approx(100 * (512 + 90) / (512 + 128))


# -- the share ---------------------------------------------------------------------

def test_the_two_shares_add_up_to_the_uncut_layer():
    """The routed parts that both shares of a 16-expert layer give (8
    experts each), plus the shared MLP ONCE, equal the uncut reference's
    expert layer; and the program's held layer is one such share."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.parallel.moe import moe_forward_held

    ref = harness.load_reference(CONFIG)
    sz = {k: {**CONFIG, **TINY}[k] for k in ref.SIZE_KEYS}
    whole_sizes = {**sz, "num_local_experts": 16, "first_expert": 0}
    whole = ref.init_layer(jax.random.PRNGKey(3), whole_sizes, "mamba",
                           jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (33, 64))
    with ref.with_precision("float32"):
        want, want_picks = ref.experts(whole, x, whole_sizes)
        total = 0.0
        for share in range(2):
            lo = 8 * share
            p = {**whole, **{k: whole[k][lo:lo + 8]
                             for k in ("e_gate", "e_up", "e_down")}}
            part, picks = ref.experts(p, x, whole_sizes, first_expert=lo,
                                      with_shared=share == 0)
            y, got_picks, _ = moe_forward_held(
                p, x, first_expert=lo, k=3, shared=share == 0,
                router="softmax_topk")
            np.testing.assert_array_equal(np.asarray(picks),
                                          np.asarray(want_picks))
            np.testing.assert_array_equal(np.asarray(got_picks),
                                          np.asarray(want_picks))
            np.testing.assert_allclose(np.asarray(y), np.asarray(part),
                                       rtol=2e-4, atol=2e-5)
            total = total + part
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# -- the harness, rehearsed ---------------------------------------------------------------

def run(capsys, seed="3000000019"):
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
    assert counters["state_rows_computed"] >= counters["state_rows_scanned"] \
        > 0
    assert counters["recurrent_state_resets"] >= counted["prefills"] > 0
    # 4 slots x 3 layers x (8 heads x 16 x 16 float32 + 3 x 160 bfloat16)
    assert counted["recurrent_state_bytes"] == 4 * 3 * (8192 + 3 * 160 * 2)


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


def test_both_controls_fail_and_the_program_passes():
    """The fp8 control fails the router's number and the state's gap.
    The second control, the stated precision with the state rounded to
    bfloat16 after every token, fails the one number that reads the
    state's own values: ``state_rounding_lost`` is exactly 1 of it."""
    cell, runner, served = _served()
    limits = cell.config["limits"]
    res = runner.compare(cell, served, with_control=True)
    assert set(res["numbers"]) == set(limits)
    for name, value in res["numbers"].items():
        assert value <= limits[name], (name, value)
    for name in ("router_flip_share", "state_gap"):
        assert res["control"][name] > limits[name], name
    state = runner.compare(cell, served, with_control=True,
                           control_precision="bf16_state")
    assert state["numbers"] == res["numbers"]
    assert state["control"]["state_rounding_lost"] == 1.0 \
        > limits["state_rounding_lost"]
    assert state["control"]["router_flip_share"] \
        < res["control"]["router_flip_share"] / 4


def _breaks():
    """Name -> (module, attribute, replacement)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import latent_moe, ssm_gqa
    from deeplearning4j_tpu.parallel import moe

    finish, step, chunk = (latent_moe.layer_finish, ssm_gqa.ssd_step,
                           ssm_gqa.ssm_chunk)
    scan, out, conv = ssm_gqa.ssd_scan, ssm_gqa.ssm_out, ssm_gqa.conv_xbc
    mixer_step = ssm_gqa.ssm_step

    def no_residual_multiplier(p, h, att, arch, valid=None):
        return finish(p, h, att, dataclasses.replace(
            arch, residual_multiplier=1.0), valid)

    def step_without_d(S, x, B, C, dt, A, D):
        return step(S, x, B, C, dt, A, jnp.zeros_like(D))

    def scan_without_d(x, B, C, dt, A, D, S0, q):
        return scan(x, B, C, dt, A, jnp.zeros_like(D), S0, q)

    def gate_after_norm(p, o, z, arch):
        o = o.reshape(o.shape[:-2] + (-1,))
        return latent_moe.rms_norm(o, p["norm_g"], arch.rms_eps) \
            * jax.nn.silu(z)

    def no_conv_bias(p, x_ext, arch):
        return conv({k: v for k, v in p.items() if k != "conv_b"}, x_ext,
                    arch)

    def softmax_over_all(x, router_w, k):
        logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return idx.astype(jnp.int32), w          # not normalised over the k

    def never_reset(p, h, state, arch, offset=0, n_real=None):
        # a chunk at offset 0 carries on from what the slot held
        return chunk(p, h, state, arch, offset + 1 if state is not None
                     else offset, n_real)

    def bf16_state(p, h, state, active, arch):
        att, (S, tail) = mixer_step(p, h, state, active, arch)
        return att, (jax.lax.reduce_precision(S, 8, 7), tail)

    return {
        "a multiplier left out": [(latent_moe, "layer_finish",
                                   no_residual_multiplier)],
        "D left out": [(ssm_gqa, "ssd_step", step_without_d),
                       (ssm_gqa, "ssd_scan", scan_without_d)],
        "the gate applied after the norm": [(ssm_gqa, "ssm_out",
                                             gate_after_norm)],
        "the convolution's bias dropped": [(ssm_gqa, "conv_xbc",
                                            no_conv_bias)],
        "softmax over all 72 in place of the 10": [
            (moe, "route_softmax_topk", softmax_over_all)],
        "the state not reset at admission": [(ssm_gqa, "ssm_chunk",
                                              never_reset)],
        "the state kept in bfloat16": [(ssm_gqa, "ssm_step", bf16_state)]}


FAULTS = ["a multiplier left out", "D left out",
          "the gate applied after the norm", "the convolution's bias dropped",
          "softmax over all 72 in place of the 10",
          "the state not reset at admission", "the state kept in bfloat16"]


@pytest.mark.parametrize("what", FAULTS)
def test_broken_underneath_is_not_correct(what, capsys, monkeypatch):
    for module, attr, fn in _breaks()[what]:
        monkeypatch.setattr(module, attr, fn)
    rc, result, compared, _ = run(capsys)
    assert rc == 0 and result["correct"] is False
    failed = [n for n, c in compared.items() if not c["ok"]]
    assert failed, compared
    if what == "the state kept in bfloat16":
        # every number of the logits passes: only the state's own does not
        assert failed == ["state_rounding_lost"]
        assert compared["state_rounding_lost"]["value"] == 1.0
