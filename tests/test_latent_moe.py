"""The latent-attention / routed-expert block (models/latent_moe.py,
parallel/moe.moe_forward_held) against the plain reference of the
benchmark's ``kimi-k2-instruct`` configuration, at small sizes on the
CPU with seeded weights; and ``ShardedTransformerLM`` built from an
``LMArch``."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import latent_moe
from deeplearning4j_tpu.models.arch import LMArch
from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
from deeplearning4j_tpu.parallel.moe import (
    EXPERT_STATS, moe_forward_held, route_noaux_tc)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(config="kimi-k2-instruct"):
    path = os.path.join(ROOT, "benchmarks", "configs",
                        f"{config}_reference.py")
    spec = importlib.util.spec_from_file_location(
        config.replace("-", "_") + "_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

#: a small model of the same family: 1 dense + 2 expert layers, 16 experts
#: of which 4 are held from expert 4 on, 3 a token
SIZES = {
    "vocab_size": 97, "num_hidden_layers": 3, "hidden_size": 64,
    "num_attention_heads": 4, "intermediate_size": 128,
    "moe_intermediate_size": 32, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "first_expert": 4, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.827,
    "rms_norm_eps": 1e-6, "rope_theta": 50000,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "initializer_range": 0.2, "router_bias_std": 0.1,
    "max_position_embeddings": 128,
}
SEED = 4294967311


def arch_of(sizes=SIZES, **over):
    return LMArch.from_config(sizes, **{"max_len": 128, **over})


def tree_of(sizes=SIZES, seed=SEED, dtype=jnp.float32):
    """The program's tree holding the reference's seeded weights."""
    key = ref.seed_key(seed)
    ends = ref.init_ends(key, sizes, dtype)
    blocks = [ref.init_layer(ref.layer_key(key, i), sizes,
                             ref.is_dense(sizes, i), dtype)
              for i in range(sizes["num_hidden_layers"])]
    return {"embed": ends["embed"], "blocks": blocks,
            "lnf_g": ends["lnf_g"], "head": ends["head"]}


def ref_logits(tokens, sizes=SIZES, seed=SEED, precision="float32"):
    with ref.with_precision("float32"):
        lg, picks = ref.forward(ref.seed_key(seed), jnp.asarray(tokens),
                                sizes, precision, dtype=jnp.float32)
    return np.asarray(lg), [np.asarray(p) for p in picks]


@pytest.fixture(scope="module")
def mesh():
    return build_mesh({"data": 1}, devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def lm(mesh):
    return ShardedTransformerLM(arch=arch_of(), params=tree_of(), mesh=mesh)


TOKENS = np.random.default_rng(5).integers(0, 97, (2, 60)).astype(np.int32)


# -- YaRN ---------------------------------------------------------------------

@pytest.mark.parametrize("sizes", [
    SIZES,
    {**SIZES, "qk_rope_head_dim": 64, "rope_scaling": {
        **SIZES["rope_scaling"], "original_max_position_embeddings": 4096}},
    {**SIZES, "rope_scaling": None}])
def test_yarn_tables_match_the_reference(sizes):
    arch = arch_of({**sizes, "rope_scaling": sizes["rope_scaling"] or {}})
    np.testing.assert_allclose(latent_moe.yarn_inv_freq(arch),
                               ref.yarn_inv_freq(sizes), rtol=1e-12)
    for a, b in zip(latent_moe.rope_tables(arch, 100),
                    ref.rope_tables(sizes, 100)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert arch.softmax_scale == pytest.approx(ref.softmax_scale(sizes))


def test_published_scale_of_kimi_k2():
    arch = arch_of({**SIZES, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64})
    m = 0.1 * np.log(32.0) + 1.0
    assert m == pytest.approx(1.3466, abs=1e-4)
    assert arch.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    # the ramp blends between the published code's two correction bounds
    inv = latent_moe.yarn_inv_freq(arch_of(
        {**SIZES, "qk_rope_head_dim": 64, "rope_scaling": {
            **SIZES["rope_scaling"],
            "original_max_position_embeddings": 4096}}))
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:20], plain[:20])          # fast: kept
    np.testing.assert_allclose(inv[20:], plain[20:] / 32.0)   # slow: / factor


# -- the router ----------------------------------------------------------------

def test_router_pieces_are_each_told_apart():
    """Scores chosen so that each piece of the gate changes the answer:
    the bias moves the CHOICE (expert 3 enters only through it) but not
    the weights; the weights are the chosen scores over their sum,
    times the scale."""
    logits = np.array([[2.0, 1.0, 0.0, -1.0, -3.0]], np.float32)
    w = np.eye(5, dtype=np.float32)
    x = logits                                   # x @ I = logits
    bias = np.array([0.0, 0.0, 0.0, 2.0, 0.0], np.float32)
    s = 1.0 / (1.0 + np.exp(-logits[0]))
    idx, wt = route_noaux_tc(jnp.asarray(x), jnp.asarray(w),
                             jnp.asarray(bias), 2, 2.5)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]   # not [0, 1]
    got = dict(zip(np.asarray(idx)[0].tolist(), np.asarray(wt)[0].tolist()))
    norm = s[0] + s[3]                           # scores WITHOUT the bias
    assert got[0] == pytest.approx(2.5 * s[0] / norm, rel=1e-6)
    assert got[3] == pytest.approx(2.5 * s[3] / norm, rel=1e-6)
    # without the bias the choice is the plain top 2
    idx0, _ = route_noaux_tc(jnp.asarray(x), jnp.asarray(w),
                             jnp.zeros(5), 2, 2.5)
    assert sorted(np.asarray(idx0)[0].tolist()) == [0, 1]
    # and the reference's gate says the same
    ridx, rw = ref.route(jnp.asarray(x), jnp.asarray(w), jnp.asarray(bias),
                         2, 2.5)
    assert sorted(np.asarray(ridx)[0].tolist()) == [0, 3]
    np.testing.assert_allclose(np.sort(np.asarray(rw)), np.sort(np.asarray(wt)),
                               rtol=1e-6)


def _expert_layer(sizes=SIZES, seed=3):
    return ref.init_layer(ref.layer_key(ref.seed_key(seed), 1), sizes,
                          False, jnp.float32)


def test_no_token_is_dropped_when_every_token_picks_the_same_expert():
    p = dict(_expert_layer())
    # a bias that sends every token to experts 4, 5, 6: all held here
    p["router_b"] = jnp.zeros(16).at[jnp.array([4, 5, 6])].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (50, 64))
    y, picks, stats = moe_forward_held(p, x, first_expert=4, k=3,
                                       scaling=2.827)
    assert np.asarray(picks).tolist() == [[4, 5, 6]] * 50
    stats = dict(zip(EXPERT_STATS, np.asarray(stats).tolist()))
    assert stats == {"expert_picks": 150, "expert_picks_held": 150,
                     "expert_load_max": 50, "experts_hit": 3}
    with ref.with_precision("float32"):
        want, _ = ref.experts(p, x, SIZES)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_rows_that_are_no_tokens_route_nowhere():
    p = _expert_layer()
    x = jax.random.normal(jax.random.PRNGKey(2), (20, 64))
    valid = jnp.arange(20) < 7
    _, _, stats = moe_forward_held(p, x, first_expert=4, k=3, scaling=1.0,
                                   valid=valid)
    _, _, first7 = moe_forward_held(p, x[:7], first_expert=4, k=3,
                                    scaling=1.0)
    assert np.asarray(stats).tolist() == np.asarray(first7).tolist()
    assert int(stats[0]) == 21


@pytest.mark.parametrize("router", ["noaux_tc", "softmax_topk"])
def test_the_shares_add_up_to_the_uncut_layer(router):
    """The routed parts that all four shares of a 16-expert layer give
    (4 experts each), plus the shared expert ONCE, equal the uncut
    reference's whole layer: behind the sigmoid gate with its bias
    (Kimi's reference) and behind the softmax gate without one
    (Keye's), one code path after the router."""
    whole_sizes = {**SIZES, "n_routed_experts": 16, "first_expert": 0}
    whole = _expert_layer(whole_sizes)
    x = jax.random.normal(jax.random.PRNGKey(7), (33, 64))
    if router == "softmax_topk":
        sparse = _reference("keye-vl-2-30b-a3b")
        whole = {k: v for k, v in whole.items()
                 if k != "router_b" and not k.startswith("s_")}
        with sparse.with_precision("float32"):
            want, want_picks = sparse.experts(whole, x, whole_sizes)
    else:
        with ref.with_precision("float32"):
            want, want_picks = ref.experts(whole, x, whole_sizes)
    total = 0.0
    for share in range(4):
        lo = 4 * share
        p = {**whole, **{k: whole[k][lo:lo + 4]
                         for k in ("e_gate", "e_up", "e_down")}}
        y, picks, _ = moe_forward_held(p, x, first_expert=lo, k=3,
                                       scaling=2.827, shared=share == 0,
                                       router=router)
        np.testing.assert_array_equal(np.asarray(picks),
                                      np.asarray(want_picks))
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_softmax_router_weights_are_the_top_probabilities_over_their_sum():
    from deeplearning4j_tpu.parallel.moe import route_softmax_topk
    logits = np.array([[2.0, 1.0, 0.0, -1.0, -3.0]], np.float32)
    idx, wt = route_softmax_topk(jnp.asarray(logits), jnp.eye(5), 2)
    assert np.asarray(idx)[0].tolist() == [0, 1]
    pr = np.exp(logits[0]) / np.exp(logits[0]).sum()
    np.testing.assert_allclose(np.asarray(wt)[0], pr[:2] / pr[:2].sum(),
                               rtol=1e-6)
    assert float(np.asarray(wt).sum()) == pytest.approx(1.0, rel=1e-6)


# -- attention: expanded, absorbed, reference ----------------------------------

def test_expanded_absorbed_and_reference_attention_agree():
    arch = arch_of()
    p = tree_of()["blocks"][1]
    T = 24
    h = jax.random.normal(jax.random.PRNGKey(4), (T, 64))
    cos, sin = latent_moe.rope_tables(arch, T)
    qn, qp, rows = latent_moe.mla_project(p, h, cos, sin, arch)
    expanded = latent_moe.attend_expanded(p, qn, qp, rows, arch)
    # absorbed: every position as one decode slot over the rows before it
    old = jnp.broadcast_to(rows[None], (T, T, rows.shape[-1]))
    absorbed = latent_moe.attend_absorbed(p, qn, qp, rows, old,
                                          jnp.arange(T), arch)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=1e-4, atol=1e-5)
    # chunked: the second half over the first half's cached rows
    half = T // 2
    old_rows = jnp.concatenate([rows[:half], jnp.zeros_like(rows)])
    chunk = latent_moe.attend_expanded(
        p, qn[half:], qp[half:], rows[half:], arch,
        read_old=lambda j: jax.lax.dynamic_slice_in_dim(old_rows, j * 8, 8),
        n_old=half, block_rows=8)
    np.testing.assert_allclose(np.asarray(chunk), np.asarray(expanded[half:]),
                               rtol=1e-4, atol=1e-5)
    with ref.with_precision("float32"):
        want = ref.attention(p, h, SIZES)
    got = latent_moe._mm(expanded, p["W_o"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_a_wrong_absorbed_scale_is_seen():
    arch = arch_of()
    wrong = arch_of(rope_factor=1.0)          # no m^2 in the scale
    assert wrong.softmax_scale != arch.softmax_scale


# -- the whole model through ShardedTransformerLM -------------------------------

def test_full_forward_matches_the_reference(lm):
    got = np.asarray(lm.logits(TOKENS))
    for b in range(2):
        want, _ = ref_logits(TOKENS[b])
        np.testing.assert_allclose(got[b], want, rtol=2e-3, atol=2e-4)


def test_fit_batch_refuses_with_a_clear_error(lm):
    with pytest.raises(NotImplementedError, match="latent_moe"):
        lm.fit_batch(TOKENS, TOKENS)


@pytest.mark.parametrize("chunk", [None, 16])
def test_prefill_then_decode_through_the_latent_pool(lm, chunk):
    """Chunked or not, prefill and then decoding through the latent pool
    agree with the reference's full forward on logits; the experts the
    engine reports are the reference's; nothing compiles after load."""
    from deeplearning4j_tpu.serving import DecodeEngine

    eng = DecodeEngine(lm, max_slots=3, page_size=8, max_len=128,
                       prompt_buckets=(16, 64), prefill_chunk=chunk)
    eng.load()
    try:
        n0 = eng.compile_cache_size()
        futs = [eng.generate_async(TOKENS[b, :n], max_new_tokens=9,
                                   echo_logits=True)
                for b, n in ((0, 41), (1, 16), (0, 5))]
        for f, (b, n) in zip(futs, ((0, 41), (1, 16), (0, 5))):
            r = f.result(timeout=300)
            seq = np.concatenate([TOKENS[b, :n], r.tokens]).astype(np.int32)
            want, picks = ref_logits(seq)
            at = slice(n - 1, n - 1 + len(r.tokens))
            np.testing.assert_allclose(r.logits, want[at],
                                       rtol=2e-3, atol=2e-4)
            assert r.tokens == np.argmax(r.logits, axis=-1).tolist()
            want_picks = np.stack([p[at] for p in picks], axis=1)
            assert r.expert_picks.shape == want_picks.shape == (9, 2, 3)
            np.testing.assert_array_equal(r.expert_picks, want_picks)
        assert eng.compile_cache_size() == n0
        snap = eng.metrics_snapshot()
        c = snap["counters"]
        assert c["expert_picks"] > 0
        assert 0 < c["expert_picks_held"] < c["expert_picks"]
        assert c["experts_hit"] > 0 and c["expert_load_max"] > 0
        # one latent row: 16 + 8 float32 values in 128 lanes, 3 layers
        assert snap["kv_bytes_per_token"] == 3 * 128 * 4
        assert snap["pages_in_use"] == 0
    finally:
        eng.shutdown()


def test_the_pool_is_one_latent_array_and_switches_refuse(lm):
    from deeplearning4j_tpu.ops.kv_cache import alloc_pools
    from deeplearning4j_tpu.serving import DecodeEngine

    prog = lm.decode_program(page_size=8, max_len=128)
    k, v = alloc_pools(prog, 5)
    assert k.shape == (3, 5, 8, 128) and v == ()
    for kw, word in (({"kv_dtype": "int8"}, "int8"),
                     ({"prefix_cache": True}, "prefix cache"),
                     ({"role": "prefill"}, "page transfer"),
                     ({"draft_model": lm}, "speculation")):
        with pytest.raises(ValueError, match=word):
            DecodeEngine(lm, max_slots=2, page_size=8, max_len=128, **kw)


def test_fused_horizon_serves_the_same_tokens_logits_and_experts(lm):
    """``decode_horizon=4`` (four steps and their sampling in one
    program) changes nothing a client sees, greedy or sampled, with
    chunked prefill interleaved; nothing compiles after load."""
    from deeplearning4j_tpu.serving import DecodeEngine

    out = {}
    for h in (1, 4):
        eng = DecodeEngine(lm, max_slots=3, page_size=8, max_len=128,
                           prompt_buckets=(16,), prefill_chunk=16,
                           decode_horizon=h)
        eng.load()
        try:
            n0 = eng.compile_cache_size()
            futs = [eng.generate_async(TOKENS[0, :37], max_new_tokens=10,
                                       echo_logits=True),
                    eng.generate_async(TOKENS[1, :9], max_new_tokens=7,
                                       temperature=0.8, top_k=5, seed=3,
                                       echo_logits=True)]
            out[h] = [f.result(timeout=300) for f in futs]
            assert eng.compile_cache_size() == n0
            assert eng.metrics_snapshot()["counters"]["expert_picks"] > 0
        finally:
            eng.shutdown()
    for one, four in zip(out[1], out[4]):
        assert one.tokens == four.tokens
        np.testing.assert_allclose(four.logits, one.logits,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(four.expert_picks, one.expert_picks)


def test_bf16_weights_are_served_as_bf16(mesh):
    arch = arch_of(param_dtype="bfloat16")
    lm16 = ShardedTransformerLM(arch=arch, mesh=mesh, seed=3)
    leaves = jax.tree_util.tree_leaves(lm16.params)
    assert all(a.dtype == jnp.bfloat16 for a in leaves
               if a.ndim >= 2), {a.dtype for a in leaves}
    prog = lm16.decode_program(page_size=8, max_len=128)
    assert prog.pool_dtype == jnp.bfloat16 and prog.pool_rows == ((128,),)
    assert arch_of({**SIZES, "kv_lora_rank": 512, "qk_rope_head_dim": 64}).latent_lanes == 640


# -- GPT-2 through the description ------------------------------------------------

def test_gpt2_through_the_description_is_bit_equal(mesh):
    old = ShardedTransformerLM(vocab_size=64, n_layers=2, d_model=32,
                               n_heads=4, mesh=mesh, max_len=32, seed=11)
    cfg = {"vocab_size": 64, "n_layer": 2, "n_embd": 32, "n_head": 4,
           "n_inner": None, "n_positions": 32}
    new = ShardedTransformerLM(arch=LMArch.from_config(cfg), mesh=mesh,
                               seed=11)
    assert new.arch == old.arch
    for a, b in zip(jax.tree_util.tree_leaves(old.params),
                    jax.tree_util.tree_leaves(new.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    toks = np.random.default_rng(0).integers(0, 64, (2, 16))
    np.testing.assert_array_equal(np.asarray(old.logits(toks)),
                                  np.asarray(new.logits(toks)))
    # a ready tree takes the place of the constructor's own draw
    given = ShardedTransformerLM(arch=old.arch, mesh=mesh, params=old.params)
    np.testing.assert_array_equal(np.asarray(given.logits(toks)),
                                  np.asarray(old.logits(toks)))
