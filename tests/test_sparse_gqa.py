"""The grouped-query block over a learned sparse selection
(models/sparse_gqa.py) against the plain reference of the benchmark's
``keye-vl-2-30b-a3b`` configuration, at small sizes on the CPU with
seeded weights: the full forward, chunked prefill and prefill-then-decode
through the paged cache (plain and fused), across the ``topk`` boundary
scaled down (``topk`` 8, contexts of 5 to 40, pages of 4)."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import latent_moe, sparse_gqa
from deeplearning4j_tpu.models.arch import LMArch
from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
from deeplearning4j_tpu.parallel import moe
from deeplearning4j_tpu.serving import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_PATH = os.path.join(ROOT, "benchmarks", "configs",
                           "keye-vl-2-30b-a3b.json")


def _reference():
    path = os.path.join(ROOT, "benchmarks", "configs",
                        "keye-vl-2-30b-a3b_reference.py")
    spec = importlib.util.spec_from_file_location("keye_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
with open(CONFIG_PATH) as _f:
    CONFIG = json.load(_f)

#: a small model of the same family: 2 layers, 4 query heads over 2 KV
#: heads of 8, 8 experts with 2 a token, an indexer of 2 heads of 8 that
#: keeps 8 rows, three rotary streams over 1 + 2 + 1 frequencies
SIZES = {
    **{k: CONFIG[k] for k in ("attention_bias", "hidden_act", "norm_topk_prob",
                              "tie_word_embeddings", "decoder_sparse_step",
                              "mlp_only_layers", "rms_norm_eps")},
    "vocab_size": 64, "num_hidden_layers": 2, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "moe_intermediate_size": 16, "num_experts": 8, "n_routed_experts": 8,
    "first_expert": 0, "num_experts_per_tok": 2, "rope_theta": 10000.0,
    "rope_scaling": {"mrope_section": [1, 2, 1], "rope_type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 8},
    "initializer_range": 0.2, "max_position_embeddings": 64,
}
SEED = 4294967311
#: float32 weights: program and reference differ by the order of their
#: sums only (widest gap seen 2.2e-06)
LOGIT_ATOL = 2e-5
TOKENS = np.random.default_rng(5).integers(0, 64, 40).astype(np.int32)


def sizes_of(cfg=SIZES):
    return {k: cfg[k] for k in ref.SIZE_KEYS}


def arch_of(cfg=SIZES, **over):
    return LMArch.from_config(cfg, **over)


def tree_of(cfg=SIZES, seed=SEED, dtype=jnp.float32):
    """The program's tree holding the reference's seeded weights."""
    key, sz = ref.seed_key(seed), sizes_of(cfg)
    ends = ref.init_ends(key, sz, dtype)
    return {"embed": ends["embed"], "lnf_g": ends["lnf_g"],
            "head": ends["head"],
            "blocks": [ref.init_layer(ref.layer_key(key, i), sz, dtype=dtype)
                       for i in range(sz["num_hidden_layers"])]}


def ref_forward(tokens, precision="float32", positions=None, seed=SEED):
    with ref.with_precision("float32"):
        lg, picks, chosen = ref.forward(
            ref.seed_key(seed), jnp.asarray(tokens), sizes_of(), precision,
            dtype=jnp.float32, positions=positions)
    return np.asarray(lg), np.stack(picks, 1), np.stack(chosen)


def program_forward(tokens, positions=None):
    lg, aux = sparse_gqa.forward(tree_of(), jnp.asarray(tokens)[None],
                                 arch_of(), with_aux=True,
                                 positions=positions)
    return (np.asarray(lg[0]), np.asarray(aux["expert_picks"][0]),
            np.asarray(aux["attn_mask"][0]))


@pytest.fixture(scope="module")
def mesh():
    return build_mesh({"data": 1}, devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def lm(mesh):
    return ShardedTransformerLM(arch=arch_of(), params=tree_of(), mesh=mesh)


@pytest.fixture(scope="module")
def reference_run():
    return ref_forward(TOKENS)


# -- the description ------------------------------------------------------------

def test_the_benchmarks_file_is_this_family_at_its_published_widths():
    arch = LMArch.from_config(CONFIG, max_len=16384, param_dtype="bfloat16")
    assert arch.block == "sparse_gqa" and arch.router == "softmax_topk"
    assert (arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim) == \
        (2048, 32, 4, 128)
    assert arch.n_heads * arch.head_dim != arch.d_model      # a free head_dim
    assert (arch.index_n_heads, arch.index_head_dim, arch.index_topk) == \
        (16, 64, 2048)
    assert arch.mrope_section == (16, 24, 24) and arch.rope_theta == 1e7
    assert (arch.n_experts, arch.experts_held, arch.first_expert,
            arch.experts_per_token, arch.moe_d_ff) == (128, 128, 0, 8, 768)
    assert arch.n_layers == arch.n_moe_layers == 7
    assert arch.vocab_size == 151936 and arch.n_shared_experts == 0
    prog = sparse_gqa.decode_program(arch, 16, 16384)
    assert prog.pool_rows == ((512,), (512,), (128,))        # K, V, index
    assert prog.pool_dtype == jnp.bfloat16 and prog.pages_per_slot == 1024


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("hidden_act", "gelu"),
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("use_sliding_window", True), ("sliding_window", 4096)])
def test_a_key_the_block_cannot_express_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        LMArch.from_config({**SIZES, key: value})


def test_nested_keys_the_block_cannot_express_are_refused_by_name():
    with pytest.raises(ValueError, match="indexer_num_kv_heads"):
        LMArch.from_config({**SIZES, "sa_config": {
            **SIZES["sa_config"], "indexer_num_kv_heads": 2}})
    with pytest.raises(ValueError, match="rope_scaling"):
        LMArch.from_config({**SIZES, "rope_scaling": {
            "mrope_section": [1, 2, 1], "rope_type": "yarn"}})
    with pytest.raises(ValueError, match="mrope_section"):
        LMArch.from_config({**SIZES, "rope_scaling": {
            "mrope_section": [1, 1, 1], "rope_type": "default"}})
    with pytest.raises(ValueError, match="neither"):
        LMArch.from_config({"vocab_size": 8, "num_key_value_heads": 2})


def test_training_says_it_is_not_there(lm):
    with pytest.raises(NotImplementedError, match="sparse_gqa"):
        lm.fit_batch(TOKENS[None, :8], TOKENS[None, 1:9])


# -- the full forward ------------------------------------------------------------

def test_forward_agrees_with_the_reference(reference_run):
    want, want_picks, want_chosen = reference_run
    got, picks, chosen = program_forward(TOKENS)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    np.testing.assert_array_equal(picks, want_picks)
    # the chosen sets: every earlier row up to topk, then exactly topk
    np.testing.assert_array_equal(chosen, want_chosen)
    assert chosen.sum(-1).tolist() == [[min(t + 1, 8) for t in range(40)]] * 2


def test_three_unequal_rotary_streams_agree_with_the_reference():
    at = np.arange(40)
    streams = np.stack([at, at // 3, (at * 2) % 7])
    want, _, want_chosen = ref_forward(TOKENS, positions=streams)
    got, _, chosen = program_forward(TOKENS, positions=streams)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    np.testing.assert_array_equal(chosen, want_chosen)
    # and they are not the text's streams
    assert np.abs(want - ref_forward(TOKENS)[0]).max() > 1e-2


def test_ties_go_to_the_lower_position_in_both():
    """Equal scores everywhere (a zero indexer query): the reference and
    the program both keep the ``topk`` lowest positions."""
    scores = jnp.zeros((6, 20))
    seen = jnp.arange(20)[None, :] <= (jnp.arange(6) + 14)[:, None]
    want = np.asarray(ref.topk_mask(jnp.where(seen, scores, -jnp.inf), 8))
    keys = jnp.where(seen, sparse_gqa.sortable_keys(scores), 0)
    thr, cut = sparse_gqa.topk_threshold([keys], [jnp.arange(20)], 8, 5)
    got = np.asarray(sparse_gqa.chosen(keys, jnp.arange(20)[None], thr, cut))
    np.testing.assert_array_equal(got, want)
    assert got[0].nonzero()[0].tolist() == list(range(8))


@pytest.mark.parametrize("split", [1, 2, 3])
@pytest.mark.parametrize("n,k", [(40, 8), (40, 1), (5, 8), (33, 33), (64, 17)])
def test_the_threshold_form_is_the_exact_top_k(n, k, split):
    """Against a sort, on scores with repeats (ties at the threshold),
    negative values and rows with fewer candidates than ``k``; the table
    whole and cut in two at a half and at a third."""
    rng = np.random.default_rng(n * 100 + k)
    scores = jnp.asarray(rng.integers(-3, 4, (16, n)).astype(np.float32)
                         * 0.25)
    seen = jnp.asarray(rng.random((16, n)) < 0.7).at[:, 0].set(True)
    want = np.asarray(ref.topk_mask(jnp.where(seen, scores, -jnp.inf), k))
    keys = jnp.where(seen, sparse_gqa.sortable_keys(scores), 0)
    # the table in two parts, as a chunk's is (new rows, then cached rows)
    cut_at = n // split if split > 1 else 0
    thr, cut = sparse_gqa.topk_threshold(
        [keys[:, cut_at:], keys[:, :cut_at]],
        [jnp.arange(cut_at, n), jnp.arange(cut_at)], k, int(n).bit_length())
    got = np.asarray(sparse_gqa.chosen(keys, jnp.arange(n)[None], thr, cut))
    np.testing.assert_array_equal(got, want)


# -- through the cache -----------------------------------------------------------

def _served(lm, horizon, requests):
    eng = DecodeEngine(lm, max_slots=2, page_size=4, max_len=64,
                       prompt_buckets=[8, 16], prefill_chunk=16,
                       decode_horizon=horizon).load()
    try:
        n0 = eng.compile_cache_size()
        futs = [eng.generate_async(TOKENS[:n], max_new_tokens=new, **kw)
                for n, new, kw in requests]
        out = [f.result(timeout=300) for f in futs]
        assert eng.compile_cache_size() == n0
        return out, eng.metrics_snapshot()["counters"]
    finally:
        eng.shutdown()


REQUESTS = [(5, 6, {"echo_logits": True}),       # below topk, then across it
            (30, 8, {"echo_logits": True}),      # two chunks, far above it
            (21, 6, {"echo_logits": True}),
            (30, 8, {"temperature": 0.8, "top_k": 5, "seed": 3})]


@pytest.mark.parametrize("horizon", [1, 2])
def test_chunked_prefill_and_decode_agree_with_the_reference(lm, horizon):
    """Echoed logits and the rows each layer chose, position by
    position, against the reference's full forward of prompt + answer."""
    out, counters = _served(lm, horizon, REQUESTS)
    for (n, new, kw), res in zip(REQUESTS, out):
        assert len(res.tokens) == new
        assert res.expert_picks.shape == (new, 2, 2)
        if "echo_logits" not in kw:
            assert res.logits is None and res.attn_rows is None
            continue
        seq = np.concatenate([TOKENS[:n], res.tokens])
        want, want_picks, want_chosen = ref_forward(seq)
        at = n - 1 + np.arange(new)
        np.testing.assert_allclose(res.logits, want[at], atol=LOGIT_ATOL,
                                   rtol=0)
        assert res.tokens == want[at].argmax(-1).tolist()
        np.testing.assert_array_equal(res.expert_picks, want_picks[at])
        assert res.attn_rows.shape == (new, 2, 8)
        for j, t in enumerate(at):
            for layer in range(2):
                rows = sorted(r for r in res.attn_rows[j, layer].tolist()
                              if r >= 0)
                assert rows == want_chosen[layer, t].nonzero()[0].tolist()
    # whole blocks of index rows (the window, at this size) of every slot
    assert counters["index_rows_scored"] > counters["rows_held"] > 0
    assert counters["attn_rows_read"] > 0


def test_fused_and_plain_serve_the_same(lm):
    one, _ = _served(lm, 1, REQUESTS)
    two, _ = _served(lm, 2, REQUESTS)
    for a, b in zip(one, two):
        assert a.tokens == b.tokens
        np.testing.assert_array_equal(a.expert_picks, b.expert_picks)
        if a.logits is not None:
            np.testing.assert_allclose(a.logits, b.logits, atol=1e-6, rtol=0)
            np.testing.assert_array_equal(a.attn_rows, b.attn_rows)


def test_a_prompt_whose_deadline_passes_mid_prefill_is_given_up(lm):
    """As a request still queued would be: it has no token to hand back,
    and a long prompt's remaining chunks would hold the device for
    nobody (a 14k-token prompt is 28 chunks)."""
    from deeplearning4j_tpu.serving import DeadlineExceededError
    now = [0.0]
    eng = DecodeEngine(lm, max_slots=2, page_size=4, max_len=64,
                       prompt_buckets=[8], prefill_chunk=8,
                       decode_horizon=2, clock=lambda: now[0]).load()
    try:
        dispatch, chunks = eng._chunk_dispatch, []

        def counting(c):
            chunks.append(c.offset)
            dispatch(c)
            if len(chunks) == 2:
                now[0] = 100.0              # the deadline passes here
        eng._chunk_dispatch = counting
        late = eng.generate_async(TOKENS[:38], max_new_tokens=4,
                                  deadline=50.0)
        with pytest.raises(DeadlineExceededError, match="into the prefill"):
            late.result(timeout=120)
        assert chunks == [0, 8]             # of five
        assert eng.metrics.counter_value("deadline_missed") == 1
        assert eng.metrics.counter_value("errors") == 0
        # its pages are free again and the engine serves on
        res = eng.generate_async(TOKENS[:20], max_new_tokens=3,
                                 deadline=1e6).result(timeout=120)
        assert len(res.tokens) == 3
        assert eng.metrics_snapshot()["pages_in_use"] == 0
    finally:
        eng.shutdown()


def test_reencode_is_the_forward(lm, reference_run):
    prog = lm.decode_program(page_size=4, max_len=64)
    got = np.asarray(prog.reencode(lm.params, jnp.asarray(TOKENS)[None]))[0]
    np.testing.assert_allclose(got, reference_run[0], atol=LOGIT_ATOL, rtol=0)


def test_the_spans_and_counters_say_what_the_selection_did(lm):
    from deeplearning4j_tpu.obs import trace as obs_trace
    rec = obs_trace.enable_tracing(capacity=65536)
    try:
        _served(lm, 2, [(30, 5, {"echo_logits": True})])
        events = rec.events()
    finally:
        obs_trace.disable_tracing()
    steps = [e for e in events if e["name"] == "serve/decode_step"]
    chunks = [e for e in events if e["name"] == "serve/prefill"]
    assert steps and len(chunks) == 2
    # two layers; a chunk's slot held offset + tokens rows a layer
    assert [c["args"]["rows_held"] for c in chunks] == [2 * 16, 2 * 30]
    # what a chunk scored and read: whole blocks (64 rows here: the
    # window) up to the rows cached, and its own 16 padded rows
    for name in ("index_rows_scored", "attn_rows_read"):
        assert [c["args"][name] for c in chunks] == [2 * 16, 2 * (64 + 16)]
    first = steps[0]["args"]           # positions 30 and 31, two layers
    assert first["rows_held"] == 2 * (31 + 32)
    # both slots' block and new row scored, both slots' 8 rows gathered,
    # whether stepped or not
    assert first["index_rows_scored"] == 2 * 2 * 2 * (64 + 1)
    assert first["attn_rows_read"] == 2 * 2 * 2 * 8
    assert first["experts_hit"] > 0
    # the step reads rows, not pages: the program's counts say which
    assert "kv_pages_read" not in first


@pytest.mark.parametrize("what,kw", [
    ("prefix", {"prefix_cache": True}), ("int8", {"kv_dtype": "int8"}),
    ("page transfer", {"role": "prefill"})])
def test_the_engine_refuses_by_name_what_these_pools_do_not_carry(lm, what,
                                                                  kw):
    with pytest.raises(ValueError, match=what):
        DecodeEngine(lm, max_slots=2, page_size=4, max_len=64, **kw)


def test_the_pools_live_and_die_with_the_pages(lm):
    """Three pools behind one page table; a scrub and a reset zero all
    of them."""
    from deeplearning4j_tpu.ops.kv_cache import alloc_pools, scrub_pool
    prog = lm.decode_program(page_size=4, max_len=64)
    k, rest = alloc_pools(prog, 9)
    assert k.shape == (2, 9, 4, 16) and [a.shape for a in rest] == \
        [(2, 9, 4, 16), (2, 9, 4, 128)]
    ones = jax.tree_util.tree_map(jnp.ones_like, (k, rest))
    scrubbed = scrub_pool(ones, jnp.asarray([2, 5]))
    for a in jax.tree_util.tree_leaves(scrubbed):
        a = np.asarray(a)
        assert not a[:, [2, 5]].any() and a[:, [0, 1, 3, 4, 6, 7, 8]].all()


# -- broken underneath: each must leave the tolerance far behind ---------------------

def _breaks():
    def everything(kk, cc, thr, cut):            # the selection left out
        return kk > 0

    def recent(score):                           # the most recent k in its place
        n = score.shape[-1]
        return jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.uint32),
                                score.shape)

    def no_head_norms(x, g, eps):                # the q/k norms left out
        return x if x.ndim == 3 else latent_moe.rms_norm(x, g, eps)

    def wrong_kv_head(p, h, rope, arch):         # a % KV for a // (H / KV)
        (q, q_i, w), rows = sparse_gqa_project(p, h, rope, arch)
        n, H, D = q.shape
        q = jnp.swapaxes(q.reshape(n, H // arch.n_kv_heads, arch.n_kv_heads,
                                   D), 1, 2).reshape(n, H, D)
        return (q, q_i, w), rows

    def not_renormalised(x, router_w, k):        # the top-k weights as they are
        idx, w = route_softmax(x, router_w, k)
        pr = jax.nn.softmax(jnp.dot(x, router_w.astype(jnp.float32),
                                    precision="highest"), axis=-1)
        return idx, jnp.take_along_axis(pr, idx, axis=-1)

    return {
        "the selection left out": (sparse_gqa, "chosen", everything),
        "the most recent k in its place": (sparse_gqa, "sortable_keys",
                                           recent),
        "the causal bound on the selection dropped": (
            sparse_gqa, "causal", lambda t: jnp.ones((t, t), bool)),
        "another KV head": (sparse_gqa, "project", wrong_kv_head),
        "the q/k norms left out": (sparse_gqa, "rms_norm", no_head_norms),
        "the top-k weights not renormalised": (moe, "route_softmax_topk",
                                               not_renormalised)}


sparse_gqa_project = sparse_gqa.project
route_softmax = moe.route_softmax_topk


@pytest.mark.parametrize("what", [
    "the selection left out", "the most recent k in its place",
    "the causal bound on the selection dropped", "another KV head",
    "the q/k norms left out", "the top-k weights not renormalised"])
def test_broken_underneath_leaves_the_tolerance_behind(what, monkeypatch,
                                                       reference_run):
    module, attr, fn = _breaks()[what]
    monkeypatch.setattr(module, attr, fn)
    got, _, _ = program_forward(TOKENS)
    assert np.abs(got - reference_run[0]).max() > 100 * LOGIT_ATOL, what


def test_the_fp8_control_leaves_the_tolerance_behind(reference_run):
    low, _, low_chosen = ref_forward(TOKENS, precision="fp8")
    assert np.abs(low - reference_run[0]).max() > 100 * LOGIT_ATOL
    assert (low_chosen != reference_run[2]).any()


def test_the_stated_precision_lies_between_the_reference_and_its_control(
        reference_run):
    """``bfloat16`` operands in the reference (the precision the
    configuration states, with nothing of the program in it) move the
    logits, the routing and the selection, each less than the fp8
    control does (means: one flipped row of 8 moves a position's logits
    as far under either)."""
    def moved(precision):
        lg, picks, chosen = ref_forward(TOKENS, precision=precision)
        return (((lg - reference_run[0]) ** 2).mean(),
                (picks != reference_run[1]).any(-1).mean(),
                (chosen != reference_run[2]).mean())
    for stated, control in zip(moved("bfloat16"), moved("fp8")):
        assert 0 < stated < control / 2
