"""Configuration ``keye-vl-2-30b-a3b`` and its cell: the manifest's own
limits, the file against the catalog's published values, the cut against
the stated deployment, the byte count of ``sparse_decode_bytes_roofline``
on a worked example, a CPU rehearsal of the cell at tiny sizes, and
``correct`` coming out false when the timed path is broken underneath.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_keye_vl_2_30b_a3b.py -q
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

from benchmarks import harness                        # noqa: E402
from benchmarks.kernels import sparse_decode_bytes    # noqa: E402

NAME = "keye-vl-2-30b-a3b"
CELL = NAME + ".doc-closed16"
CONFIG = harness.load_config(NAME)
MANIFEST = harness.load_manifest()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: the catalog row's ``config`` (Keye-VL-2.0-30B-A3B, config.json)
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}

#: a model of the same family a test run holds: 2 layers, 4 query heads
#: over 2 KV heads of 16, 16 experts with 3 a token, an indexer of 4
#: heads of 16 that keeps 16 rows of contexts of 8 to 114.  The limits
#: are this size's own, where ONE row of 16 that flips moves the logits
#: as 128 of 2,048 would: sound runs read flips 0.07..0.11, mse
#: 0.033..0.053 and misses 0.015..0.032 on five seeds, the fp8 control
#: 0.48..0.61, 0.36..0.61 and 0.16..0.22; the two widest-gap numbers do
#: not tell them apart at this size and only bound a break.
TINY = {
    "vocab_size": 256, "num_hidden_layers": 2, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "moe_intermediate_size": 32, "num_experts": 16, "n_routed_experts": 16,
    "num_experts_per_tok": 3, "max_position_embeddings": 256,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {**PUBLISHED["sa_config"], "indexer_head_dim": 16,
                  "indexer_num_heads": 4, "topk": 16},
    "initializer_range": 0.2,
    "limits": {"router_flip_share": 0.3, "served_logit_mse": 0.2,
               "served_logit_gap": 4.0, "sampled_topk_gap": 1.0,
               "index_miss_share": 0.08}}
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


# -- the manifest's own limits (PR 31 was refused on one before anything ran) ----------

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _line(text) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and text.isascii() and text.isprintable())


@pytest.mark.parametrize("entry", MANIFEST["configs"] + MANIFEST["workloads"],
                         ids=lambda e: e["name"])
def test_every_why_and_source_is_one_printable_ascii_line(entry):
    assert _line(entry["why"]), entry["why"]
    if "source" in entry:
        assert _line(entry["source"]), entry["source"]


def test_every_name_of_the_manifest_is_a_name():
    names = []
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in MANIFEST[section]]
    for cfg in MANIFEST["configs"]:
        names += cfg["reduced"]
    for cell in MANIFEST["workloads"]:
        names += [cell["config"], cell["traffic"]]
    for name in names:
        assert NAME_RE.match(name), name
    for section in ("configs", "workloads"):
        listed = [e["name"] for e in MANIFEST[section]]
        assert len(set(listed)) == len(listed)
    metrics = [e["name"] for e in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in MANIFEST["per_layer"]:
        assert _line(m["layer"]) and set(m) <= {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}


@pytest.mark.parametrize("entry", MANIFEST["configs"], ids=lambda e: e["name"])
def test_a_configurations_file_says_what_its_entry_says(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert cfg["source"] == entry["source"]
    assert list(cfg.get("reduced", [])) == list(entry["reduced"])


def check_this_configurations_entries(manifest):
    """What PR 32 added, found by name.  Where an entry stands in its list
    is not this configuration's to say: a later PR appends to all four."""
    entry = harness.find(manifest["configs"], NAME, "config")
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "doc-closed16", 1)
    own = ["sparse_rows_read_share", "sparse_decode_bytes_roofline"]
    assert [m["name"] for m in manifest["per_layer"]
            if m["name"] in own] == own              # in this order
    for name in own:
        m = harness.find(manifest["per_layer"], name, "metric")
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
    reported = {m["name"] for section in ("end_to_end", "per_layer")
                for m in harness.metrics_of_cell(manifest, section, CELL)}
    assert reported >= {
        "serve_tokens_per_s", "setup_s", "prefill_time_share",
        "slot_occupancy.closed", "device_idle_share.closed",
        "decode_host_ms.closed", "queue_wait_ms.closed",
        "kv_pages_filled_share.closed", "expert_load_max_over_mean",
        "sparse_rows_read_share", "sparse_decode_bytes_roofline"}


def test_the_manifest_holds_one_configuration_one_cell_and_two_metrics():
    check_this_configurations_entries(MANIFEST)


def test_the_cells_why_states_the_window_its_traffic_file_gives():
    """The window is the traffic file's ``windows`` times ``run_seconds``
    (PR 36: one window's rate followed the order of the seed's sizes)."""
    from benchmarks import traffic
    windows = traffic.load_mix("doc-closed16")["windows"]
    cell = harness.find(MANIFEST["workloads"], CELL, "workload")
    assert windows > 1
    assert f"window {windows} x {MANIFEST['run_seconds']} s" in cell["why"]


# -- the file ------------------------------------------------------------------

def test_every_published_value_is_carried_unchanged_but_the_depth():
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert CONFIG["published"][key] == value and CONFIG[key] == 7
        else:
            assert CONFIG[key] == value, key
    # the count HELD, which ``expert_load_max_over_mean`` reads
    assert CONFIG["n_routed_experts"] == CONFIG["num_experts"] == 128
    assert CONFIG["first_expert"] == 0


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_published_values_are_the_catalogs():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert row["config"] == PUBLISHED
    assert row["source_url"] == CONFIG["source"]


def test_the_cut_is_the_stated_deployments_share_and_inside_the_floors():
    assert "ONE chip shares a layer" in CONFIG["deployment"]
    assert "7, 7, 7, 7, 7, 7 and 6" in CONFIG["deployment"]
    assert 6 * 7 + 6 == CONFIG["published"]["num_hidden_layers"]
    assert CONFIG["num_hidden_layers"] >= 4            # the guide's floor
    for key in ("assumed", "departures", "limits", "limits_from", "program"):
        assert CONFIG[key], key
    for detail in ("qk_norm", "indexer_query", "indexer_key",
                   "indexer_rotary", "indexer_head_weights", "chunk_sizes",
                   "serving_dtype", "initializer_range", "max_len",
                   "page_size", "max_slots", "prefill", "decode_horizon",
                   "pool_rows"):
        assert CONFIG["assumed"][detail], detail
    assert set(CONFIG["limits"]) == {
        "router_flip_share", "served_logit_mse", "served_logit_gap",
        "sampled_topk_gap", "index_miss_share"}
    assert set(CONFIG["limits"]) <= set(CONFIG["limits_from"])
    from benchmarks import traffic
    m = traffic.load_mix("doc-closed16")
    assert m["clients"] == 16 and m["n_sizes"] == 24
    assert m["clients"] == 2 * CONFIG["program"]["max_slots"]
    assert m["max_total_tokens"] <= CONFIG["program"]["max_len"]
    assert m["prompt_len"]["max"] + m["answer_len"]["max"] \
        <= m["max_total_tokens"]
    # every prompt is over the selection's size: no step runs with it idle
    assert m["prompt_len"]["min"] > CONFIG["sa_config"]["topk"]
    _, prompts, answers = traffic.size_sets(m)
    assert min(prompts) >= 4096 and max(prompts) <= 14336
    assert min(answers) >= 128 and max(answers) <= 1024


def test_the_reference_states_its_precisions_and_imports_no_program():
    ref = harness.load_reference(CONFIG)
    assert ref.STATED_PRECISION == "bfloat16"
    assert ref.CONTROL_PRECISION == "fp8" and "fp8" in ref.PRECISIONS
    with open(os.path.join(harness.HERE, "configs", CONFIG["reference"])) as f:
        src = f.read()
    assert "deeplearning4j_tpu" not in src.split('"""', 2)[2]
    assert "benchmarks" not in src.split('"""', 2)[2]
    assert "approx_max_k" not in src


def test_the_arithmetic_of_the_cut():
    """ISSUE 32's count of what this chip holds."""
    c = CONFIG
    attn = sparse_decode_bytes.attention_params(c) - (2 * 2048 + 2 * 128)
    assert attn == 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    assert round(attn / 1e6, 2) == 18.87
    indexer = sparse_decode_bytes.indexer_params(c) - 2 * 64
    assert indexer == 2048 * 1024 + 2048 * 64 + 2048 * 16
    assert round(indexer / 1e6, 2) == 2.26
    assert sparse_decode_bytes.expert_params(c) == 3 * 2048 * 768
    layer = attn + indexer + 2048 * 128 + 128 * 3 * 2048 * 768
    assert round(layer / 1e6, 1) == 625.4
    total = 7 * layer + 2 * 151936 * 2048
    assert round(total / 1e9, 2) == 5.0
    # a token leaves 512 + 512 + 128 lanes a layer in bf16
    prog = c["program"]
    row = (512 + 512 + 128) * 2
    pools = 7 * (1 + prog["max_slots"] * prog["max_len"]) * row
    assert row == 2304 and round(pools / 1e9, 2) == 2.11


# -- the byte count of sparse_decode_bytes_roofline, on a worked example ---------------

def test_step_bytes_on_a_worked_example():
    c = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 4,
         "num_key_value_heads": 2, "num_hidden_layers": 3,
         "moe_intermediate_size": 6, "num_experts": 5, "vocab_size": 10,
         "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 4}}
    attn = 8 * 16 + 2 * 8 * 8 + 16 * 8 + 2 * 8 + 2 * 4
    assert sparse_decode_bytes.attention_params(c) == attn == 408
    indexer = 8 * 8 + 8 * 4 + 8 * 2 + 2 * 4
    assert sparse_decode_bytes.indexer_params(c) == indexer == 120
    assert sparse_decode_bytes.expert_params(c) == 3 * 8 * 6
    fixed = 3 * (attn + indexer + 8 * 5) + 8 + 8 * 10
    assert sparse_decode_bytes.fixed_bytes(c) == 2 * fixed
    assert sparse_decode_bytes.index_row_bytes(c) == 8
    assert sparse_decode_bytes.kv_row_bytes(c) == 2 * 2 * 4 * 2
    assert sparse_decode_bytes.step_bytes(
        c, experts_hit=4, index_rows_scored=100, attn_rows_read=30) == \
        2 * fixed + 4 * 144 * 2 + 100 * 8 + 30 * 32
    # the real configuration: ISSUE 32's 299 MB + 311 MB fixed a step,
    # 9.44 MB an expert hit, 128 B an index key, 2,048 B a K and V row
    fixed = sparse_decode_bytes.fixed_bytes(CONFIG)
    head = (2048 + 2048 * 151936) * 2
    assert round((fixed - head) / 1e6) == 300 and round(head / 1e6) == 622
    assert sparse_decode_bytes.expert_params(CONFIG) * 2 == 9_437_184
    assert sparse_decode_bytes.index_row_bytes(CONFIG) == 128
    assert sparse_decode_bytes.kv_row_bytes(CONFIG) == 2048


def test_the_readers_return_nothing_when_given_nothing():
    observed = harness.Observed(
        cell=harness.Cell(name=CELL, chips=1, seed=1, seconds=1.0,
                          trace=True, config=CONFIG, mix={}, reference=None,
                          devices=[]),
        window={}, counters={})
    for name in ("sparse_rows_read_share", "sparse_decode_bytes_roofline",
                 "expert_load_max_over_mean"):
        assert harness.load_layer_metric(name).read(observed) is None


def test_the_readers_on_a_worked_example(monkeypatch):
    """Two fused dispatches of 4 steps with the counts the program puts
    on ``serve/decode_step``, beside a device time of 50 ms each."""
    from benchmarks import program_spans

    def step(**args):
        return program_spans.Span("serve/decode_step", 0.0, 1.0,
                                  {"tokens": 4, **args})
    spans = [step(experts_hit=1400, index_rows_scored=2_000_000,
                  attn_rows_read=458_752, rows_held=2_000_000),
             # whole blocks of every slot scored: counted up to the rows held
             step(experts_hit=1600, index_rows_scored=3_670_016,
                  attn_rows_read=458_752, rows_held=2_400_000),
             step(n_active=3)]                      # another program's: no counts
    monkeypatch.setattr(program_spans, "of", lambda observed: spans)

    class Trace:
        def module_runs(self, pattern):
            assert re.search(pattern, "jit_step_multi")
            assert not re.search(pattern, "jit_prefill_at")
            return [0.05, 0.05]
    observed = harness.Observed(
        cell=harness.Cell(name=CELL, chips=1, seed=1, seconds=1.0,
                          trace=True, config=CONFIG, mix={}, reference=None,
                          devices=[]),
        window={}, counters={}, trace=Trace(),
        peaks={"hbm_bytes_per_s": 819e9})
    share = harness.load_layer_metric("sparse_rows_read_share").read(observed)
    assert share == pytest.approx(100.0 * 917_504 / 4_400_000)
    fixed = sparse_decode_bytes.fixed_bytes(CONFIG)
    least = (4 * fixed + 1500 * 9_437_184 + 2_200_000 * 128
             + 458_752 * 2048) / 819e9
    roofline = harness.load_layer_metric(
        "sparse_decode_bytes_roofline").read(observed)
    assert roofline == pytest.approx(100.0 * least / 0.05)
    assert 0 < roofline < 100


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
    # what the calls did: whole blocks of index rows, of every slot
    assert counters["index_rows_scored"] > counters["rows_held"] > 0
    assert counters["attn_rows_read"] > 0


def test_the_fp8_control_fails_and_the_program_passes():
    _, cell, _ = harness.open_cell(CELL, 2147483659, 3.0, False, REHEARSAL)
    runner = harness.load_runner(cell.config["runner"])
    state = runner.setup(cell, {})
    runner.window(cell, state, harness.Tracer(False, ""))
    served = runner.release(cell, state)
    res = runner.compare(cell, served, with_control=True)
    limits = cell.config["limits"]
    assert set(res["numbers"]) == set(limits)
    for name, value in res["numbers"].items():
        assert value <= limits[name], (name, value)
    for name in ("router_flip_share", "served_logit_mse", "index_miss_share"):
        assert res["control"][name] > limits[name], name
    # the STATED precision in the reference's place (no control: rounding's
    # second witness, with nothing of the program in it) stays inside them
    stated = runner.compare(cell, served, with_control=True,
                            control_precision="bfloat16")
    for name in ("router_flip_share", "served_logit_mse", "index_miss_share"):
        assert 0 < stated["control"][name] <= limits[name], name


def test_the_window_keeps_the_logits_of_the_answers_it_may_draw_and_no_others(
        monkeypatch):
    _, cell, _ = harness.open_cell(CELL, 2147483659, 3.0, False, REHEARSAL)
    runner = harness.load_runner(cell.config["runner"])
    n, sample, held = cell.mix["compare_requests"], runner._sample, []

    def counting(cell, finished):
        held.append(sum(f[2] is not None for f in finished))
        return sample(cell, finished)

    monkeypatch.setattr(runner, "_sample", counting)
    state = runner.setup(cell, {})
    runner.window(cell, state, harness.Tracer(False, ""))
    finished = state["finished"]
    runner.release(cell, state)
    greedy = [f for f in finished if f[0].greedy and f[1]]
    assert len(greedy) > n                 # there was something to drop
    assert max(held) <= n + 1              # the drawable and the newcomer
    # and the draw is what it is with every answer kept: most tokens first
    most = sorted(greedy, key=lambda f: (-len(f[1]), f[0].index))[:n]
    assert [f[0].index for f in sample(cell, finished)[0]] == \
        [f[0].index for f in most]
    for f in greedy:
        whole = all(x is not None and len(x) == len(f[1]) for x in f[2:])
        assert whole == any(f is m for m in most)


def _breaks():
    """Name -> (module, attribute, replacement)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import latent_moe, sparse_gqa
    from deeplearning4j_tpu.parallel import moe

    held, project, route = (latent_moe.moe_forward_held, sparse_gqa.project,
                            moe.route_softmax_topk)

    def dropped(p, x, *, valid=None, **kw):       # every third token dropped
        keep = jnp.arange(x.shape[0]) % 3 != 0
        return held(p, x, valid=keep if valid is None else valid & keep, **kw)

    def everything(kk, cc, thr, cut):             # a chunk attends over all
        return kk > 0

    def recent(table, k):                         # the step's most recent k
        at = jnp.arange(table.shape[1], dtype=jnp.float32)[None, :]
        return jax.lax.top_k(jnp.where(table > -1e29, at, table), k)

    def no_head_norms(x, g, eps):
        return x if x.ndim == 3 else latent_moe.rms_norm(x, g, eps)

    def wrong_kv_head(p, h, rope, arch):          # a % KV for a // (H / KV)
        (q, q_i, w), rows = project(p, h, rope, arch)
        n, H, D = q.shape
        q = jnp.swapaxes(q.reshape(n, H // arch.n_kv_heads, arch.n_kv_heads,
                                   D), 1, 2).reshape(n, H, D)
        return (q, q_i, w), rows

    def not_renormalised(x, router_w, k):
        idx, w = route(x, router_w, k)
        return idx, w * 0.35

    return {
        "a dropped token": (latent_moe, "moe_forward_held", dropped),
        "the selection left out of the chunks": (sparse_gqa, "chosen",
                                                 everything),
        "the most recent k in the step": (sparse_gqa, "step_top_k", recent),
        "the causal bound on the selection dropped": (
            sparse_gqa, "causal", lambda t: jnp.ones((t, t), bool)),
        "another KV head": (sparse_gqa, "project", wrong_kv_head),
        "the q/k norms left out": (sparse_gqa, "rms_norm", no_head_norms),
        "the top-k weights not renormalised": (moe, "route_softmax_topk",
                                               not_renormalised)}


@pytest.mark.parametrize("what", [
    "a dropped token", "the selection left out of the chunks",
    "the most recent k in the step",
    "the causal bound on the selection dropped", "another KV head",
    "the q/k norms left out", "the top-k weights not renormalised"])
def test_broken_underneath_is_not_correct(what, capsys, monkeypatch):
    module, attr, fn = _breaks()[what]
    monkeypatch.setattr(module, attr, fn)
    rc, result, compared, _ = run(capsys)
    assert rc == 0 and result["correct"] is False
    assert [n for n, c in compared.items() if not c["ok"]], compared
