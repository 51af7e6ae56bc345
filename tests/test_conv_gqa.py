"""The block of gated short-convolution and rotary grouped-query layers
(models/conv_gqa.py: a mixer whose whole memory is a two-row tail, q/k
norm before plain rotary over paged K and V, a leading dense layer in a
block of two layer kinds, the ``noaux_tc`` router with the file's own
epsilon, a tied head) against the plain reference of the benchmark's
``lfm2-24b-a2b`` configuration and against its own other path, at small
sizes on the CPU with seeded weights: the chunk form against the one-row
step and the three-term sum, chunked prefill and prefill-then-decode
through the engine under a fused horizon, the tail's life in a slot, what
the description refuses, the router's epsilon, and the older blocks'
programs, which the shared code must not change."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import conv_gqa, sparse_gqa
from deeplearning4j_tpu.models.arch import LMArch
from deeplearning4j_tpu.models.latent_moe import rms_norm, rope_tables
from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
from deeplearning4j_tpu.parallel.moe import route_noaux_tc
from deeplearning4j_tpu.serving import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "lfm2-24b-a2b"


def _reference():
    path = os.path.join(ROOT, "benchmarks", "configs", f"{NAME}_reference.py")
    spec = importlib.util.spec_from_file_location("lfm2_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
with open(os.path.join(ROOT, "benchmarks", "configs", f"{NAME}.json")) as _f:
    CONFIG = json.load(_f)

#: a small model of the same family: 5 layers (layer 2 grouped-query, the
#: others convolutions; layer 0 the leading dense layer), 4 query heads
#: over 2 KV heads of 8, 3 taps, 8 experts with 3 a token
SIZES = {
    **{k: CONFIG[k] for k in (
        "model_type", "conv_L_cache", "conv_bias", "norm_eps",
        "norm_topk_prob", "use_expert_bias", "routed_scaling_factor",
        "rope_parameters", "router_bias_std", "router_eps", "tie_embedding")},
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv"],
    "vocab_size": 64, "num_hidden_layers": 5, "num_dense_layers": 1,
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 48, "moe_intermediate_size": 16, "num_experts": 8,
    "n_routed_experts": 8, "first_expert": 0, "num_experts_per_tok": 3,
    "initializer_range": 0.2, "max_position_embeddings": 128,
}
SEED = 4294967311
#: float32 weights: program and reference differ by the order of their
#: sums only (logits of deviation 1.5)
LOGIT_ATOL = 2e-5
TOKENS = np.random.default_rng(5).integers(0, 64, 100).astype(np.int32)


def sizes_of(cfg=SIZES):
    return {k: cfg[k] for k in ref.SIZE_KEYS}


def arch_of(cfg=SIZES, **over):
    return LMArch.from_config(cfg, **over)


def tree_of(cfg=SIZES, seed=SEED, dtype=jnp.float32):
    """The program's tree holding the reference's seeded weights."""
    return ref.init_params(ref.seed_key(seed), sizes_of(cfg), dtype)


def ref_forward(tokens, seed=SEED):
    with ref.with_precision("float32"):
        lg, picks = ref.forward(ref.seed_key(seed), jnp.asarray(tokens),
                                sizes_of(), dtype=jnp.float32)
    return np.asarray(lg), np.stack(picks, 1)


@pytest.fixture(scope="module")
def lm():
    mesh = build_mesh({"data": 1}, devices=jax.devices()[:1])
    return ShardedTransformerLM(arch=arch_of(), params=tree_of(), mesh=mesh)


# -- the description ------------------------------------------------------------

def test_the_benchmarks_file_is_this_family_at_its_published_widths():
    arch = LMArch.from_config(CONFIG, max_len=3072, param_dtype="bfloat16")
    assert arch.block == "conv_gqa" and arch.router == "noaux_tc"
    assert (arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim) == \
        (2048, 32, 8, 64)
    assert (arch.conv_L_cache, arch.conv_bias, arch.d_ff, arch.moe_d_ff) == \
        (3, False, 11776, 1536)
    # the published list, read up to the depth held; ONE leading dense layer
    assert arch.layer_types == ("conv", "conv", "gqa", "conv", "conv", "conv",
                                "gqa", "conv", "conv")
    assert (arch.n_layers, arch.n_dense_layers, arch.n_moe_layers) == (9, 1, 8)
    assert (arch.n_experts, arch.experts_held, arch.first_expert,
            arch.experts_per_token, arch.n_shared_experts) == (64, 64, 0, 4, 0)
    assert (arch.router_eps, arch.routed_scaling_factor, arch.rope_theta,
            arch.rms_eps) == (1e-6, 1.0, 1e6, 1e-5)
    assert arch.tie_embeddings and arch.vocab_size == 65536
    prog = conv_gqa.decode_program(arch, 16, 3072)
    assert prog.pool_rows == ((512,), (512,))                # K, V
    assert prog.kinds == ("state", "state", "pool", "state", "state", "state",
                          "pool", "state", "state")
    # a state that is ONLY a tail
    assert prog.slot_state == (((2, 2048), jnp.dtype("bfloat16")),)
    assert prog.pool_dtype == jnp.bfloat16 and prog.pages_per_slot == 192
    # Kimi's and Solar's files keep DeepSeek-V3's epsilon
    for other in ("kimi-k2-instruct", "solar-open2-250b"):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               f"{other}.json")) as f:
            assert LMArch.from_config(json.load(f)).router_eps == 1e-20


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("use_expert_bias", False),
    ("norm_topk_prob", False),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("layer_types", ["conv", "conv", "sliding_attention", "conv", "conv"])])
def test_a_key_the_block_cannot_express_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"{key}.*conv_gqa"):
        LMArch.from_config({**SIZES, key: value})


def test_what_the_description_cannot_say_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        arch_of(layer_types=("gqa", "mamba", "gqa", "conv", "conv"))
    with pytest.raises(ValueError, match="conv_L_cache"):
        arch_of(conv_L_cache=1)
    with pytest.raises(ValueError, match="n_dense_layers"):
        arch_of(n_dense_layers=6)
    with pytest.raises(ValueError,
                       match="short-convolution and grouped-query"):
        LMArch.from_config({"vocab_size": 8, "layer_types": ["conv"]})
    # an untied head is this family's to say
    loose = arch_of({**SIZES, "tie_embedding": False})
    tree = jax.eval_shape(lambda: conv_gqa.init_params(
        jax.random.PRNGKey(0), loose))
    assert "head" in tree and "W_gate" in tree["blocks"][0] \
        and "router_b" in tree["blocks"][1]


@pytest.mark.parametrize("family", ["linear_gqa", "ssm_gqa"])
def test_a_leading_dense_layer_stays_refused_for_the_other_two_kind_blocks(
        family):
    import tests.test_linear_gqa as tg
    import tests.test_ssm_gqa as tsm
    arch_fn = {"linear_gqa": tg.arch_of, "ssm_gqa": tsm.arch_of}[family]
    with pytest.raises(ValueError, match=f"every {family} layer"):
        arch_fn(n_dense_layers=1)


def test_training_says_it_is_not_there(lm):
    with pytest.raises(NotImplementedError, match="conv_gqa"):
        lm.fit_batch(TOKENS[None, :8], TOKENS[None, 1:9])


# -- the mathematics --------------------------------------------------------------

def test_forward_agrees_with_the_reference(lm):
    want, want_picks = ref_forward(TOKENS[:50])
    lg, aux = conv_gqa.forward(tree_of(), jnp.asarray(TOKENS[:50])[None],
                               arch_of(), with_aux=True)
    np.testing.assert_allclose(lg[0], want, atol=LOGIT_ATOL, rtol=0)
    # the four EXPERT layers' choices: the dense layer makes none
    assert aux["expert_picks"].shape == (1, 50, 4, 3)
    np.testing.assert_array_equal(aux["expert_picks"][0], want_picks)
    np.testing.assert_allclose(
        lm.decode_program(page_size=4, max_len=128).reencode(
            lm.params, jnp.asarray(TOKENS[:50])[None])[0], want,
        atol=LOGIT_ATOL, rtol=0)


def _conv_layer(seed=4):
    arch, p = arch_of(), tree_of()["blocks"][1]
    h = jnp.asarray(np.random.default_rng(seed).normal(size=(40, 32)),
                    jnp.float32)
    return arch, p, h


def _three_term_sum(p, h, arch):
    """``(C * c) `` of the whole sequence by the equation as written, in
    numpy float64 over a zero-padded sequence."""
    C, z = (np.asarray(a, np.float64) for a in conv_gqa.conv_inputs(p, h, arch))
    w = np.asarray(p["conv_w"], np.float64)
    zp = np.concatenate([np.zeros((2, z.shape[1])), z])
    c = np.stack([w[0] * zp[t] + w[1] * zp[t + 1] + w[2] * zp[t + 2]
                  for t in range(z.shape[0])])
    return C * c, zp


@pytest.mark.parametrize("cuts", [(40,), (16, 24), (1, 39), (39, 1),
                                  (13, 1, 1, 25), (2, 2, 36)])
def test_the_chunk_form_is_the_three_term_sum_whatever_the_chunks(cuts):
    """Chunk boundaries inside the taps' reach: a chunk of ONE row takes
    one of its three terms from each of the two rows the tail carries."""
    arch, p, h = _conv_layer()
    want, zp = _three_term_sum(p, h, arch)
    state, out, at = None, [], 0
    for n in cuts:
        att, state = conv_gqa.conv_chunk(p, h[at:at + n], state, arch,
                                         offset=at)
        out.append(att)
        at += n
        # the tail is the last two rows of z, zeros before the sequence
        np.testing.assert_allclose(state[0], zp[at:at + 2], atol=1e-6)
    np.testing.assert_allclose(jnp.concatenate(out), want, atol=1e-5)


def test_the_one_row_step_is_the_chunk_form_row_by_row():
    arch, p, h = _conv_layer()
    want, _ = conv_gqa.conv_chunk(p, h, None, arch)
    # two slots: one steps through the rows, the other stands idle
    tail = jnp.stack([jnp.zeros((2, 32)), jnp.full((2, 32), 7.0)])
    active = jnp.asarray([True, False])
    out = []
    for t in range(h.shape[0]):
        att, (tail,) = conv_gqa.conv_step(
            p, jnp.stack([h[t], h[t]]), (tail,), active, arch)
        out.append(att[0])
    np.testing.assert_allclose(jnp.stack(out), want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tail[1]), 7.0)    # kept
    _, (want_tail,) = conv_gqa.conv_chunk(p, h, None, arch)
    np.testing.assert_allclose(tail[0], want_tail, atol=1e-6)


@pytest.mark.parametrize("bucket,n_real", [(16, 11), (8, 1), (16, 16),
                                           (8, 0)])
def test_padded_rows_and_a_one_row_last_chunk_leave_the_tail_alone(bucket,
                                                                   n_real):
    """A bucket's rows at and beyond ``n_real`` do not enter the tail: the
    chunk over the real rows alone leaves the same one.  One real row
    shifts the old tail by one; none leaves it as it was."""
    arch, p, h = _conv_layer()
    h = h[:bucket]
    old = jnp.asarray(np.random.default_rng(9).normal(size=(2, 32)),
                      jnp.float32)
    att, (tail,) = conv_gqa.conv_chunk(p, h, (old,), arch, 5, n_real)
    want_att, (want,) = conv_gqa.conv_chunk(p, h[:n_real], (old,), arch, 5)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(want))
    np.testing.assert_allclose(att[:n_real], want_att, atol=1e-6)
    if n_real == 0:
        np.testing.assert_array_equal(np.asarray(tail), np.asarray(old))
    if n_real == 1:
        np.testing.assert_array_equal(np.asarray(tail[0]), np.asarray(old[1]))
    # a chunk at offset 0 starts from zero whatever the slot held
    _, (fresh,) = conv_gqa.conv_chunk(p, h, (old,), arch, 0, 1)
    np.testing.assert_array_equal(np.asarray(fresh[0]), 0.0)


def test_rotary_comes_after_the_norms_and_pairs_the_halves():
    arch, p = arch_of(), tree_of()["blocks"][2]
    h = jnp.asarray(np.random.default_rng(2).normal(size=(12, 32)),
                    jnp.float32)
    rope = rope_tables(arch, 12, arch.head_dim)
    (q, gate), (k_row, v_row) = conv_gqa.gqa_project(p, h, rope, arch)
    assert gate is None and q.shape == (12, 4, 8) and k_row.shape == (12, 16)
    # by hand, in float64: norm a head, THEN rotate (i, i + 4)
    u = np.asarray(rms_norm(h, p["ln1_g"], arch.rms_eps), np.float64)

    def normed(w, heads, gain):
        x = (u @ np.asarray(w, np.float64)).reshape(12, heads, 8)
        return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + 1e-5) \
            * np.asarray(gain, np.float64)

    inv = 1e6 ** (-np.arange(4) / 4.0)
    ang = np.arange(12)[:, None] * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]

    def rotated(x):
        a, b = x[..., :4], x[..., 4:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    np.testing.assert_allclose(q, rotated(normed(p["W_q"], 4, p["q_norm_g"])), atol=1e-5)
    np.testing.assert_allclose(k_row.reshape(12, 2, 8),
                               rotated(normed(p["W_k"], 2, p["k_norm_g"])), atol=1e-5)
    # rotary BEFORE the norm is another function of a head whose gain
    # differs by channel (the reference draws it so; all ones commute)
    g = p["q_norm_g"]
    assert float(jnp.max(g) - jnp.min(g)) > 0.3
    x = jnp.asarray(np.random.default_rng(3).normal(size=(12, 4, 8)),
                    jnp.float32)
    c, s = (t[:, None, :] for t in rope)
    after = sparse_gqa.rotate_half(rms_norm(x, g, 1e-5), c, s)
    before = rms_norm(sparse_gqa.rotate_half(x, c, s), g, 1e-5)
    assert float(jnp.max(jnp.abs(after - before))) > 0.05
    # position 0 is not rotated at all
    np.testing.assert_allclose(q[0], normed(p["W_q"], 4, p["q_norm_g"])[0], atol=1e-5)


def test_the_routers_default_epsilon_gives_the_parents_numbers_bit_for_bit():
    """``route_noaux_tc`` as Kimi's and Solar's programs call it (no
    epsilon given) is the parent's function, and this family's 1e-6 is
    the published gate's."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(33, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.normal(size=(16,)) * 0.003, jnp.float32)

    def parent(x, w, b, k, scaling, eps=1e-20):
        logits = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(scores + b, k)
        s = jnp.take_along_axis(scores, idx, axis=-1)
        return idx.astype(jnp.int32), \
            s / (jnp.sum(s, axis=-1, keepdims=True) + eps) * scaling

    for fn, args in ((route_noaux_tc, ()), (route_noaux_tc, (1e-20,))):
        idx, got = jax.jit(lambda *a: fn(*a, 4, 2.5, *args))(x, w, b)
        want_idx, want = jax.jit(lambda *a: parent(*a, 4, 2.5))(x, w, b)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_idx))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    idx, lfm = route_noaux_tc(x, w, b, 4, 1.0, 1e-6)
    _, want = parent(x, w, b, 4, 1.0, 1e-6)
    np.testing.assert_array_equal(np.asarray(lfm), np.asarray(want))
    # the weights of the chosen sum to just under one
    total = np.asarray(jnp.sum(lfm, axis=-1))
    assert np.all(total < 1.0) and np.all(total > 1.0 - 2e-6)


# -- the program through the engine ---------------------------------------------------

def _served(lm, horizon, requests, slots=2, chunk=16):
    eng = DecodeEngine(lm, max_slots=slots, page_size=4, max_len=128,
                       prompt_buckets=[8, 16], prefill_chunk=chunk,
                       decode_horizon=horizon).load()
    try:
        n0 = eng.compile_cache_size()
        out = []
        for group in requests:
            futs = [eng.generate_async(TOKENS[a:b], max_new_tokens=new, **kw)
                    for (a, b), new, kw in group]
            out.append([f.result(timeout=300) for f in futs])
        assert eng.compile_cache_size() == n0
        return out, eng.metrics_snapshot()
    finally:
        eng.shutdown()


_ECHO = {"echo_logits": True, "echo_state": True}
REQUESTS = [((0, 5), 6, _ECHO),                      # less than a chunk
            ((0, 37), 9, _ECHO),                     # three chunks, one padded
            ((10, 27), 7, _ECHO),                    # a ONE-row last chunk
            ((0, 37), 9, {"temperature": 0.8, "top_k": 5, "seed": 3})]


@pytest.fixture(scope="module")
def served(lm):
    return _served(lm, 4, [REQUESTS])


def ref_tails(tokens, state_at):
    """The reference's tail of every convolution layer as row
    ``state_at`` left it."""
    sz, key = sizes_of(), ref.seed_key(SEED)
    with ref.with_precision("float32"):
        h, out = ref.embed(ref.init_ends(key, sz, jnp.float32),
                           jnp.asarray(tokens)), []
        for i in range(5):
            p = ref.init_layer(ref.layer_key(key, i), sz,
                               ref.layer_kind(sz, i), ref.is_dense(sz, i),
                               jnp.float32)
            h, _, tail = ref.layer(p, h, sz, "float32", state_at)
            out += [] if tail is None else [np.asarray(tail)]
    return out


def test_chunked_prefill_and_decode_agree_with_the_reference(served):
    """Echoed logits, chosen experts and the slot's tails at the answer's
    end, against the reference's full forward of prompt + answer, under
    the fused horizon the cell runs: four requests on two slots, so each
    slot serves a second request after its first, and that second tenant
    agrees with the reference only if it started from a zero tail."""
    (out,), snap = served
    for ((a, b), new, kw), res in zip(REQUESTS, out):
        assert len(res.tokens) == new
        assert res.expert_picks.shape == (new, 4, 3)
        if "echo_logits" not in kw:
            assert res.logits is None and res.slot_state is None
            continue
        seq = np.concatenate([TOKENS[a:b], res.tokens])
        want, want_picks = ref_forward(seq)
        at = b - a - 1 + np.arange(new)
        np.testing.assert_allclose(res.logits, want[at], atol=LOGIT_ATOL,
                                   rtol=0)
        np.testing.assert_array_equal(res.expert_picks, want_picks[at])
        # the tails once the last FED token is in: all but the answer's last
        tails = ref_tails(seq, len(seq) - 2)
        assert len(res.slot_state) == 4
        for state, want_tail in zip(res.slot_state, tails):
            assert len(state) == 1 and state[0].shape == (2, 32)
            np.testing.assert_allclose(state[0], want_tail, atol=1e-5)
    c = snap["counters"]
    assert c["recurrent_state_resets"] == 4
    assert c["state_rows_scanned"] == 4 * (5 + 37 + 17 + 37)
    assert c["state_slots_stepped"] >= 4 * (5 + 8 + 6 + 8)
    assert c["kv_rows_read"] >= c["kv_rows_held"] > 0
    assert c["state_rows_computed"] == 0             # another family's scan
    # 2 slots x 4 convolution layers x a tail of 2 x 32 float32
    assert snap["recurrent_state_bytes"] == 2 * 4 * 2 * 32 * 4
    assert snap["kv_bytes_per_token"] == 2 * 16 * 4      # ONE layer's K and V


def test_the_spans_and_counters_say_what_the_tails_did(lm):
    from deeplearning4j_tpu.obs import trace as obs_trace
    rec = obs_trace.enable_tracing(capacity=65536)
    try:
        _served(lm, 4, [[((0, 37), 5, {})]])
        events = rec.events()
    finally:
        obs_trace.disable_tracing()
    steps = [e["args"] for e in events if e["name"] == "serve/decode_step"]
    chunks = [e["args"] for e in events if e["name"] == "serve/prefill"]
    assert steps and [c["offset"] for c in chunks] == [0, 16, 32]
    # four convolution layers scan the chunk's REAL rows; the one
    # grouped-query layer holds offset + tokens rows
    assert [c["state_rows_scanned"] for c in chunks] == [64, 64, 20]
    assert [c["bucket"] for c in chunks] == [16, 16, 8]
    assert [c["kv_rows_held"] for c in chunks] == [16, 32, 37]
    assert all(c["state_slots_stepped"] == 0 for c in chunks)
    # four expert layers route a chunk's real rows, 3 picks each
    assert [c["expert_picks"] for c in chunks] == [192, 192, 60]
    first = steps[0]                    # four fused steps of one active slot
    assert first["state_slots_stepped"] == 4 * 4
    assert first["kv_rows_held"] == 38 + 39 + 40 + 41
    assert first["state_rows_scanned"] == 0
    assert first["expert_picks"] == first["expert_picks_held"] == 4 * 4 * 3
    assert first["experts_hit"] > 0 and "kv_pages_read" not in first


def test_a_round_that_the_token_budget_ends_is_counted(lm):
    """Three requests wait for two free slots; a round takes in one
    chunk's worth of prompt tokens (16), so the first round admits the
    30-token head alone and leaves a slot empty with requests waiting."""
    from deeplearning4j_tpu.obs import trace as obs_trace
    eng = DecodeEngine(lm, max_slots=2, page_size=4, max_len=128,
                       prompt_buckets=[8, 16], prefill_chunk=16,
                       decode_horizon=4).load()
    rec = obs_trace.enable_tracing(capacity=65536)
    try:
        with eng._lock:         # the loop waits here: all three are queued
            futs = [eng.generate_async(TOKENS[:n], max_new_tokens=3)
                    for n in (30, 12, 12)]
        for f in futs:
            f.result(timeout=300)
        snap = eng.metrics_snapshot()
        events = rec.events()
    finally:
        obs_trace.disable_tracing()
        eng.shutdown()
    assert snap["counters"]["admit_rounds_budget_bound"] >= 1
    bound = [e["args"] for e in events if e["name"] == "serve/iteration"
             and e["args"].get("admit_budget_bound")]
    assert len(bound) == snap["counters"]["admit_rounds_budget_bound"]
    assert bound[0]["free_slots"] == 2 and bound[0]["admitted"] == 1
    # a round that the SLOTS end is not counted: one slot, nothing else
    (_,), alone = _served(lm, 4, [[((0, 5), 2, {}), ((0, 5), 2, {})]],
                          slots=1)
    assert alone["counters"]["admit_rounds_budget_bound"] == 0


@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.8, "top_k": 5}], ids=["greedy", "sampled"])
def test_slots_filled_in_one_round_say_what_they_say_filled_one_a_round(
        lm, sampling):
    """A round's budget is a chunk's worth of prompt tokens for each free
    slot: four short prompts fill four free slots at once, where one
    chunk's worth a round (the rule it replaces) takes them in one or two
    a turn.  The schedule decides WHEN a tail and its pages are written,
    and nothing a request says: tokens and chosen experts are equal."""
    from _decode_checks import queued_together
    requests = [(TOKENS[a:b], {"max_new_tokens": 7, "seed": j, **sampling})
                for j, (a, b) in enumerate([(0, 12), (20, 25), (30, 46),
                                            (50, 59)])]
    eng = DecodeEngine(lm, max_slots=4, page_size=4, max_len=128,
                       prompt_buckets=[8, 16], prefill_chunk=16,
                       decode_horizon=4).load()
    try:
        now, rounds, bound = queued_together(eng, requests)
        then, before, _ = queued_together(eng, requests,
                                          budget_of=lambda limit: 16)
    finally:
        eng.shutdown()
    assert rounds == [(4, 4 * 16, [12, 5, 16, 9])] and bound == 0
    assert before[0] == (4, 16, [12]) and len(before) > 1
    for a, b in zip(now, then):
        assert list(a.tokens) == list(b.tokens) and len(a.tokens) == 7
        np.testing.assert_array_equal(a.expert_picks, b.expert_picks)


@pytest.mark.parametrize("what,kw", [
    ("prefix", {"prefix_cache": True}), ("int8", {"kv_dtype": "int8"}),
    ("page transfer", {"role": "prefill"})])
def test_the_engine_refuses_by_name_what_a_tail_does_not_carry(lm, what, kw):
    with pytest.raises(ValueError,
                       match=f"per-slot recurrent state.*{what}"):
        DecodeEngine(lm, max_slots=2, page_size=4, max_len=128, **kw)


# -- the other blocks of the one builder --------------------------------------------

#: the digests of the parent's tree (PR 42's commit), on this repository's
#: one installation (jax 0.9.0): the first three blocks' are those
#: tests/test_ssm_gqa.py pins, the fourth's were taken from a ``git
#: archive`` of the parent before this block joined the builder
SSM_BEFORE = {"prefill_at": "f1ae8c387647600c", "prefill": "ca15d31f37485606",
              "step": "815d856367b84094", "step_multi": "5ced77e9e4285e9c",
              "reencode": "d47320ab4ba9d2df"}


#: this tree's, since PR 46 put the expert layer under a ``jit`` of its
#: own (tests/test_linear_gqa.py ``SINCE_PR46``)
SSM_SINCE_PR46 = {"prefill_at": "4371400915c7ee87",
                  "prefill": "2eeb2eb11fb852c7", "step": "da6fcda3567e94cd",
                  "step_multi": "ce1786b02e63b01f",
                  "reencode": "6282d1812de5dda7"}


@pytest.mark.parametrize("expert_layer", ["its-own-function", "traced-in-line"])
@pytest.mark.parametrize("block", ["latent_moe", "sparse_gqa", "linear_gqa",
                                   "ssm_gqa"])
def test_the_other_blocks_programs_lower_to_what_they_did(block, expert_layer,
                                                          monkeypatch):
    """One builder for five blocks, whose router now takes the epsilon of
    its normalisation from the architecture and whose rotary tables take
    a width: Kimi's, Keye's, Solar's and Granite's programs are the
    parent's, text for text, but for the expert layer's call."""
    import tests.test_linear_gqa as tg
    import tests.test_ssm_gqa as tsm
    tg.pinned_texts(
        tsm._texts, block, expert_layer, {**tsm.BEFORE, "ssm_gqa": SSM_BEFORE},
        {**tg.SINCE_PR46, **tsm.SINCE_PR46, "ssm_gqa": SSM_SINCE_PR46},
        monkeypatch)
