"""Configuration ``kimi-k2-instruct`` and its cell: the file against the
catalog's published values, the cut against the stated deployment, the
byte count of ``decode_bytes_roofline`` on a worked example, a CPU
rehearsal of the cell at tiny sizes, and ``correct`` coming out false
when the timed path is broken underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_kimi_k2_instruct.py -q
"""

import dataclasses
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest           # noqa: E402

from benchmarks import harness                        # noqa: E402
from benchmarks.kernels import decode_bytes           # noqa: E402

CELL = "kimi-k2-instruct.agent-closed32"
CONFIG = harness.load_config("kimi-k2-instruct")
MANIFEST = harness.load_manifest()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: the catalog row's ``config`` (Kimi-K2-Instruct, config.json)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "kimi_k2",
    "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 384, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 64,
    "num_nextn_predict_layers": 0, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_theta": 50000, "routed_scaling_factor": 2.827,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 163840}

#: a model of the same family a test run holds: 1 dense + 2 expert
#: layers, 16 experts of which 8 are held from expert 4 on, 3 a token.
#: The limits are this size's own: sound runs read flips 0.024..0.030 and
#: mse 4.4e-4..6.1e-4 on three seeds, the fp8 control 0.36..0.43 and
#: 0.15..0.17, every break below at least one number far over its limit.
TINY = {
    "vocab_size": 256, "num_hidden_layers": 3, "hidden_size": 64,
    "num_attention_heads": 4, "intermediate_size": 128,
    "moe_intermediate_size": 32, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "n_routed_experts": 8, "n_routed_experts_published": 16,
    "first_expert": 4, "num_experts_per_tok": 3,
    "max_position_embeddings": 256, "initializer_range": 0.2,
    "router_bias_std": 0.1,
    "rope_scaling": {**PUBLISHED["rope_scaling"],
                     "original_max_position_embeddings": 32},
    "limits": {"router_flip_share": 0.06, "served_logit_mse": 0.002,
               "served_logit_gap": 0.3, "sampled_topk_gap": 0.3}}
REHEARSAL = {
    "config": TINY,
    "program": {"max_slots": 4, "page_size": 8, "max_len": 128,
                "prompt_buckets": [8, 16, 32], "prefill_chunk": 32,
                "decode_horizon": 2},
    "mix": {"clients": 8,
            "prompt_len": {"dist": "lognormal", "median": 40, "sigma": 0.7,
                           "min": 8, "max": 90},
            "answer_len": {"dist": "lognormal", "median": 10, "sigma": 0.6,
                           "min": 2, "max": 24},
            "max_total_tokens": 120, "compare_requests": 6}}


# -- the file ------------------------------------------------------------------

def test_every_published_value_is_carried_unchanged_but_the_three_cut():
    reduced = set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert CONFIG["published"][key] == value, key
            assert CONFIG[key] != value, key
        else:
            assert CONFIG[key] == value, key
    entry = harness.find(MANIFEST["configs"], "kimi-k2-instruct", "config")
    assert set(entry["reduced"]) == reduced
    assert entry["source"] == CONFIG["source"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_published_values_are_the_catalogs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-K2-Instruct")
    assert row["config"] == PUBLISHED
    assert row["source_url"] == CONFIG["source"]


def test_the_cut_is_the_stated_deployments_share_and_inside_the_floors():
    pub = CONFIG["published"]
    chips = 32                       # that share each layer
    assert f"{chips} chips share each layer" in CONFIG["deployment"]
    assert CONFIG["n_routed_experts"] * chips == pub["n_routed_experts"]
    assert CONFIG["n_routed_experts_published"] == pub["n_routed_experts"]
    assert CONFIG["first_expert"] % CONFIG["n_routed_experts"] == 0
    assert (CONFIG["first_expert"] + CONFIG["n_routed_experts"]
            <= pub["n_routed_experts"])
    assert CONFIG["vocab_size"] * 8 == pub["vocab_size"]
    # floors: the dense layer and at least 4 expert layers, 8 experts, 1/8
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    for key in ("assumed", "departures", "limits", "limits_from", "program"):
        assert CONFIG[key], key
    assert set(CONFIG["limits"]) == {"router_flip_share", "served_logit_mse",
                                     "served_logit_gap", "sampled_topk_gap"}
    from benchmarks import traffic
    m = traffic.load_mix("agent-closed32")
    assert m["clients"] == 32 and m["n_sizes"] == 96
    assert m["max_total_tokens"] <= CONFIG["program"]["max_len"]
    assert m["prompt_len"]["max"] + m["answer_len"]["max"] \
        <= m["max_total_tokens"]
    cell = harness.find(MANIFEST["workloads"], CELL, "workload")
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert "more than its share" in cell["why"]


def test_the_reference_states_its_precisions_and_imports_no_program():
    ref = harness.load_reference(CONFIG)
    assert ref.STATED_PRECISION == "bfloat16"
    assert ref.CONTROL_PRECISION == "fp8" and "fp8" in ref.PRECISIONS
    with open(os.path.join(harness.HERE, "configs", CONFIG["reference"])) as f:
        src = f.read()
    assert "deeplearning4j_tpu" not in src.split('"""', 2)[2]
    assert "benchmarks" not in src.split('"""', 2)[2]


def test_the_arithmetic_of_the_cut():
    """ISSUE 27's count of what this chip holds."""
    c = CONFIG
    attn = decode_bytes.attention_params(c) - (2 * 7168 + 1536 + 512)
    assert attn == (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                    + 512 * 64 * 256 + 64 * 128 * 7168)
    assert round(attn / 1e6, 1) == 101.1
    assert decode_bytes.expert_params(c) == 3 * 7168 * 2048
    layer = attn + 44_040_192 + 7168 * 384 + 12 * 44_040_192
    total = (attn + 3 * 7168 * 18432) + 7 * layer + 2 * 20480 * 7168
    assert round(total / 1e9, 2) == 5.53


# -- the byte count of decode_bytes_roofline, on a worked example --------------------

def test_step_bytes_on_a_worked_example():
    c = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 4,
         "kv_lora_rank": 4, "qk_nope_head_dim": 2, "qk_rope_head_dim": 2,
         "v_head_dim": 2, "num_hidden_layers": 3, "first_k_dense_replace": 1,
         "intermediate_size": 16, "moe_intermediate_size": 4,
         "n_routed_experts": 2, "n_routed_experts_published": 8,
         "n_shared_experts": 1, "vocab_size": 10}
    attn = (8 * 4 + 4 * 2 * 4 + 8 * 6 + 4 * 2 * 4 + 2 * 2 * 8    # matrices
            + 2 * 8 + 4 + 4)                                      # gains
    assert decode_bytes.attention_params(c) == attn == 200
    expert = 3 * 8 * 4
    assert decode_bytes.expert_params(c) == expert == 96
    fixed = (3 * attn + 3 * 8 * 16 + 2 * (8 * 8 + expert) + 8 + 8 * 10)
    assert decode_bytes.fixed_bytes(c) == fixed * 2 + 2 * 8 * 4
    assert decode_bytes.latent_row_bytes(c) == 3 * 6 * 2
    assert decode_bytes.step_bytes(c, experts_hit=3, cached_rows=10) == \
        decode_bytes.fixed_bytes(c) + 3 * expert * 2 + 10 * 36
    # the real configuration: 3.36 GB a step whatever the routing
    assert round(decode_bytes.fixed_bytes(CONFIG) / 1e9, 2) == 3.36
    assert decode_bytes.latent_row_bytes(CONFIG) == 8 * 576 * 2


READERS = ("expert_picks_held_share", "expert_load_max_over_mean",
           "decode_bytes_roofline")


def test_the_readers_return_nothing_when_given_nothing():
    observed = harness.Observed(
        cell=harness.Cell(name=CELL, chips=1, seed=1, seconds=1.0,
                          trace=True, config=CONFIG, mix={}, reference=None,
                          devices=[]),
        window={}, counters={})
    for name in READERS:
        assert harness.load_layer_metric(name).read(observed) is None
    check_this_configurations_entries(MANIFEST)


def check_this_configurations_entries(manifest):
    """What PR 27 added, found by name: its cell is IN each of its
    metrics' lists, which a later configuration may join."""
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["chips"]) == (CONFIG["name"], 1)
    for name in READERS:
        entry = harness.find(manifest["per_layer"], name, "metric")
        assert CELL in entry["workloads"]
        assert entry["moves"] == "serve_tokens_per_s"


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


def test_the_fp8_control_fails_and_the_program_passes():
    _, cell, _ = harness.open_cell(CELL, 2147483659, 3.0, False, REHEARSAL)
    runner = harness.load_runner(cell.config["runner"])
    state = runner.setup(cell, {})
    runner.window(cell, state, harness.Tracer(False, ""))
    res = runner.compare(cell, runner.release(cell, state), with_control=True)
    limits = cell.config["limits"]
    assert set(res["numbers"]) == set(limits)
    for name, value in res["numbers"].items():
        assert value <= limits[name], (name, value)
    assert res["control"]["router_flip_share"] > limits["router_flip_share"]
    assert res["control"]["served_logit_mse"] > limits["served_logit_mse"]


def _breaks():
    """Name -> (attribute of models/latent_moe.py, replacement)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import latent_moe

    moe, absorbed = latent_moe.moe_forward_held, latent_moe.attend_absorbed

    def dropped(p, x, *, valid=None, **kw):       # every third token dropped
        keep = jnp.arange(x.shape[0]) % 3 != 0
        return moe(p, x, valid=keep if valid is None else valid & keep, **kw)

    def no_bias(p, x, **kw):                      # the choice without its bias
        return moe({**p, "router_b": jnp.zeros_like(p["router_b"])}, x, **kw)

    def no_shared(p, x, **kw):
        return moe(p, x, **{**kw, "shared": False})

    def bad_scale(p, qn, qp, row, rows, n, arch):  # decode's scale without m^2
        return absorbed(p, qn, qp, row, rows, n,
                        dataclasses.replace(arch, rope_mscale_all_dim=0.0))

    return {"a dropped token": ("moe_forward_held", dropped),
            "the correction bias left out": ("moe_forward_held", no_bias),
            "the shared expert left out": ("moe_forward_held", no_shared),
            "the absorbed path's scale wrong": ("attend_absorbed", bad_scale)}


@pytest.mark.parametrize("what", ["a dropped token",
                                  "the correction bias left out",
                                  "the shared expert left out",
                                  "the absorbed path's scale wrong"])
def test_broken_underneath_is_not_correct(what, capsys, monkeypatch):
    from deeplearning4j_tpu.models import latent_moe

    attr, fn = _breaks()[what]
    monkeypatch.setattr(latent_moe, attr, fn)
    rc, result, compared, _ = run(capsys)
    assert rc == 0 and result["correct"] is False
    assert [n for n, c in compared.items() if not c["ok"]], compared
