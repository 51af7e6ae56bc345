"""The block of state-space and grouped-query layers (models/ssm_gqa.py:
Mamba-2 layers over a per-slot state, an ungated NoPE grouped-query layer
over paged K and V, scalars on the residual stream, a tied head) against
the plain reference of the benchmark's ``granite-4.0-h-small``
configuration and against its own other path, at small sizes on the CPU
with seeded weights: the full forward, the chunked scan against the
one-row update and the recurrence, chunked prefill and
prefill-then-decode through the engine under a fused horizon, the
state's life in a slot, what the description refuses, and the older blocks'
programs, which the shared builder must not change."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import (latent_moe, linear_gqa, sparse_gqa,
                                       ssm_gqa)
from deeplearning4j_tpu.models.arch import LMArch
from deeplearning4j_tpu.ops.kv_cache import PoolsAndState, alloc_pools
from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
from deeplearning4j_tpu.serving import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "granite-4.0-h-small"


def _reference():
    path = os.path.join(ROOT, "benchmarks", "configs", f"{NAME}_reference.py")
    spec = importlib.util.spec_from_file_location("granite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
with open(os.path.join(ROOT, "benchmarks", "configs", f"{NAME}.json")) as _f:
    CONFIG = json.load(_f)

#: a small model of the same family: 4 layers (layer 2 grouped-query, the
#: others state-space), 4 query heads over 2 KV heads of 8, 8 state-space
#: heads of 8 with a state of 16 and 4 taps, scanned in chunks of 16; 8
#: experts with 3 a token and a shared MLP two experts wide; the file's
#: own multipliers but the attention's (1/128 suits a head of 128)
SIZES = {
    **{k: CONFIG[k] for k in (
        "position_embedding_type", "mamba_n_groups", "attention_bias",
        "mamba_proj_bias", "hidden_act", "rope_scaling",
        "normalization_function", "tie_word_embeddings", "rms_norm_eps",
        "mamba_conv_bias", "mamba_d_conv", "mamba_expand",
        "embedding_multiplier", "residual_multiplier", "logits_scaling")},
    "attention_multiplier": 0.125,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "vocab_size": 64, "num_hidden_layers": 4, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_chunk_size": 16, "intermediate_size": 16,
    "shared_intermediate_size": 32, "num_local_experts": 8,
    "num_local_experts_published": 8, "first_expert": 0,
    "num_experts_per_tok": 3, "initializer_range": 0.2,
    "max_position_embeddings": 128,
}
SEED = 4294967311
#: float32 weights: program and reference differ by the order of their
#: sums only, and the logits are narrow (divided by 16): a state kept in
#: bfloat16 moves them by 4e-05
LOGIT_ATOL = 2e-6
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
    arch = LMArch.from_config(CONFIG, max_len=2560, param_dtype="bfloat16")
    assert arch.block == "ssm_gqa" and arch.router == "softmax_topk"
    assert (arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim) == \
        (4096, 32, 8, 128)
    assert (arch.mamba_n_heads, arch.mamba_d_head, arch.mamba_d_state,
            arch.mamba_d_conv, arch.mamba_chunk_size) == (128, 64, 128, 4, 256)
    assert (arch.mamba_d_inner, arch.mamba_conv_dim) == (8192, 8448)
    # the published list, read up to the depth held: one whole period
    assert arch.layer_types == ("mamba",) * 5 + ("gqa",) + ("mamba",) * 4
    assert (arch.n_experts, arch.experts_held, arch.first_expert,
            arch.experts_per_token, arch.moe_d_ff, arch.n_shared_experts) == \
        (72, 36, 36, 10, 768, 2)
    assert (arch.embedding_multiplier, arch.residual_multiplier,
            arch.logits_scaling, arch.gqa_scale) == (12, 0.22, 16, 1 / 128)
    assert arch.tie_embeddings and arch.vocab_size == 50176
    prog = ssm_gqa.decode_program(arch, 16, 2560)
    assert prog.pool_rows == ((1024,), (1024,))              # K, V
    assert prog.kinds == ("state",) * 5 + ("pool",) + ("state",) * 4
    assert prog.slot_state == (((128, 64, 128), jnp.dtype("float32")),
                               ((3, 8448), jnp.dtype("bfloat16")))
    assert prog.pool_dtype == jnp.bfloat16 and prog.pages_per_slot == 160


@pytest.mark.parametrize("key,value", [
    ("position_embedding_type", "rope"), ("mamba_n_groups", 8),
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("hidden_act", "gelu"), ("rope_scaling", {"factor": 2.0}),
    ("normalization_function", "layernorm"),
    ("layer_types", ["mamba", "mamba", "sliding", "mamba"]),
    ("shared_intermediate_size", 24)])
def test_a_key_the_block_cannot_express_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=f"{key}.*ssm_gqa"):
        LMArch.from_config({**SIZES, key: value})


def test_what_the_description_cannot_say_is_refused():
    with pytest.raises(ValueError, match="layer_types"):
        arch_of(layer_types=("gqa", "linear", "gqa", "mamba"))
    with pytest.raises(ValueError, match="mamba_expand"):
        arch_of(mamba_expand=4)
    with pytest.raises(ValueError, match="state-space and grouped-query"):
        LMArch.from_config({"vocab_size": 8, "layer_types": ["mamba"]})
    # an untied head and a convolution without bias are this family's to say
    loose = arch_of({**SIZES, "tie_word_embeddings": False,
                     "mamba_conv_bias": False})
    tree = jax.eval_shape(lambda: ssm_gqa.init_params(
        jax.random.PRNGKey(0), loose))
    assert "head" in tree and "conv_b" not in tree["blocks"][0]


def test_training_says_it_is_not_there(lm):
    with pytest.raises(NotImplementedError, match="ssm_gqa"):
        lm.fit_batch(TOKENS[None, :8], TOKENS[None, 1:9])


# -- the mathematics --------------------------------------------------------------

def test_forward_over_several_chunks_agrees_with_the_reference(lm):
    """50 rows are four chunks of 16, the last padded."""
    want, want_picks = ref_forward(TOKENS[:50])
    lg, aux = ssm_gqa.forward(tree_of(), jnp.asarray(TOKENS[:50])[None],
                              arch_of(), with_aux=True)
    np.testing.assert_allclose(lg[0], want, atol=LOGIT_ATOL, rtol=0)
    np.testing.assert_array_equal(aux["expert_picks"][0], want_picks)
    np.testing.assert_allclose(
        lm.decode_program(page_size=4, max_len=128).reencode(
            lm.params, jnp.asarray(TOKENS[:50])[None])[0], want,
        atol=LOGIT_ATOL, rtol=0)


def _inputs(rng, T, H, P, N, lo, hi):
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(lo), np.log(hi), (T, H))),
                     jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), jnp.float32)
    return f(T, H, P), f(T, N), f(T, N), dt, A, f(H)


def _by_steps(x, B, C, dt, A, D, S):
    out = []
    for t in range(x.shape[0]):
        o, S = ssm_gqa.ssd_step(S, x[t], B[t], C[t], dt[t], A, D)
        out.append(o)
    return jnp.stack(out), S


@pytest.mark.parametrize("decay", ["slowest", "fastest", "mixed"])
@pytest.mark.parametrize("from_zero", [True, False])
def test_the_chunked_scan_is_the_one_row_update_row_by_row(decay, from_zero):
    """At both ends of the steps the initialisation allows and beyond
    (``dt`` 0.001 at ``A`` -1: -0.001 a token; ``dt`` 4 at -16: -64 a
    token, whose running sum passes float32's exponent range inside one
    chunk of 64 rows)."""
    rng = np.random.default_rng(17)
    T, H, P, N = 192, 3, 4, 8
    lo, hi = {"slowest": (0.001, 0.001), "fastest": (4.0, 4.0),
              "mixed": (0.001, 4.0)}[decay]
    x, B, C, dt, A, D = _inputs(rng, T, H, P, N, lo, hi)
    S0 = jnp.zeros((H, P, N)) if from_zero else \
        jnp.asarray(rng.normal(size=(H, P, N)), jnp.float32)
    want_o, want_S = _by_steps(x, B, C, dt, A, D, S0)
    got_o, got_S = ssm_gqa.ssd_scan(x, B, C, dt, A, D, S0, 64)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(got_S, want_S, atol=5e-5, rtol=1e-4)


def test_the_one_row_update_is_the_equation_as_written():
    rng = np.random.default_rng(3)
    f = lambda *s: np.asarray(rng.normal(size=s), np.float64)
    S, x, B, C = f(2, 4, 8), f(2, 4), f(8), f(8)
    dt, A, D = np.abs(f(2)), -np.abs(f(2)), f(2)
    want_S = np.exp(dt * A)[:, None, None] * S \
        + (dt[:, None] * x)[:, :, None] * B[None, None, :]
    want_o = want_S @ C + D[:, None] * x
    o, got_S = ssm_gqa.ssd_step(*(jnp.asarray(a, jnp.float32)
                                  for a in (S, x, B, C, dt, A, D)))
    np.testing.assert_allclose(got_S, want_S, atol=1e-5)
    np.testing.assert_allclose(o, want_o, atol=1e-5)


@pytest.mark.parametrize("bucket,n_real", [(48, 37), (8, 5), (16, 16)])
def test_padded_rows_and_a_short_bucket_leave_state_and_tail_alone(bucket,
                                                                   n_real):
    """A bucket's rows at and beyond ``n_real`` change neither the state
    nor the tail, whether the bucket is several chunks of 16 or shorter
    than one: the chunk over the real rows alone leaves the same."""
    arch, p = arch_of(), tree_of()["blocks"][0]
    rng = np.random.default_rng(4)
    h = jnp.asarray(rng.normal(size=(bucket, 32)), jnp.float32)
    state = tuple(jnp.asarray(rng.normal(size=shape), dt)
                  for shape, dt in ssm_gqa.slot_state(arch, jnp.float32))
    att, (S, tail) = ssm_gqa.ssm_chunk(p, h, state, arch, 5, n_real)
    want_att, (want_S, want_tail) = ssm_gqa.ssm_chunk(p, h[:n_real], state,
                                                      arch, 5)
    np.testing.assert_allclose(S, want_S, atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(want_tail))
    np.testing.assert_allclose(att[:n_real], want_att, atol=1e-5, rtol=1e-5)
    assert ssm_gqa.scan_rows(arch, bucket) == (min(16, bucket), bucket)
    # no real row at all: the slot's state as it was
    _, (S, tail) = ssm_gqa.ssm_chunk(p, h, state, arch, 5, 0)
    np.testing.assert_array_equal(np.asarray(S), np.asarray(state[0]))
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(state[1]))


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
            ((10, 31), 7, _ECHO),
            ((0, 37), 9, {"temperature": 0.8, "top_k": 5, "seed": 3})]


@pytest.fixture(scope="module")
def served(lm):
    return _served(lm, 4, [REQUESTS])


def ref_states(tokens, state_at):
    """The reference's state of every state-space layer as row
    ``state_at`` left it."""
    sz, key = sizes_of(), ref.seed_key(SEED)
    with ref.with_precision("float32"):
        h, out = ref.embed(ref.init_ends(key, sz, jnp.float32),
                           jnp.asarray(tokens), sz), []
        for i in range(4):
            p = ref.init_layer(ref.layer_key(key, i), sz,
                               ref.layer_kind(sz, i), jnp.float32)
            h, _, S = ref.layer(p, h, sz, "float32", state_at)
            out += [] if S is None else [np.asarray(S)]
    return out


def test_chunked_prefill_and_decode_agree_with_the_reference(served):
    """Echoed logits, chosen experts and the slot's state at the answer's
    end, against the reference's full forward of prompt + answer, under
    the fused horizon the cell runs: four requests on two slots, so each
    slot serves a second request after its first, and that second tenant
    agrees with the reference only if it started from zero state."""
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
        # the state once the last FED token is in: all but the answer's last
        states = ref_states(seq, len(seq) - 2)
        assert len(res.slot_state) == 3
        for (S, tail), want_S in zip(res.slot_state, states):
            np.testing.assert_allclose(S, want_S, atol=1e-5, rtol=1e-4)
            assert tail.shape == (3, 96)
    c = snap["counters"]
    assert c["recurrent_state_resets"] == 4
    assert c["state_rows_scanned"] == 3 * (5 + 37 + 21 + 37)
    # whole buckets: 8; 16 + 16 + 8; 16 + 8; 16 + 16 + 8
    assert c["state_rows_computed"] == 3 * (8 + 40 + 24 + 40)
    assert c["state_slots_stepped"] >= 3 * (5 + 8 + 6 + 8)
    assert c["kv_rows_read"] >= c["kv_rows_held"] > 0
    assert snap["recurrent_state_bytes"] == 2 * 3 * (8 * 8 * 16 + 3 * 96) * 4
    assert snap["kv_bytes_per_token"] == 2 * 16 * 4     # ONE layer's K and V


def test_the_spans_and_counters_say_what_the_state_did(lm):
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
    # three state-space layers scan the chunk's REAL rows and compute its
    # bucket's; the one grouped-query layer holds offset + tokens rows
    assert [c["state_rows_scanned"] for c in chunks] == [48, 48, 15]
    assert [c["state_rows_computed"] for c in chunks] == [48, 48, 24]
    assert [c["bucket"] for c in chunks] == [16, 16, 8]
    assert [c["kv_rows_held"] for c in chunks] == [16, 32, 37]
    assert all(c["state_slots_stepped"] == 0 for c in chunks)
    first = steps[0]                    # four fused steps of one active slot
    assert first["state_slots_stepped"] == 4 * 3
    assert first["kv_rows_held"] == 38 + 39 + 40 + 41
    assert first["state_rows_scanned"] == first["state_rows_computed"] == 0
    assert first["experts_hit"] > 0 and "kv_pages_read" not in first


@pytest.mark.parametrize("what,kw", [
    ("prefix", {"prefix_cache": True}), ("int8", {"kv_dtype": "int8"}),
    ("page transfer", {"role": "prefill"})])
def test_the_engine_refuses_by_name_what_the_state_does_not_carry(lm, what,
                                                                  kw):
    with pytest.raises(ValueError,
                       match=f"per-slot recurrent state.*{what}"):
        DecodeEngine(lm, max_slots=2, page_size=4, max_len=128, **kw)


# -- the other blocks of the one builder --------------------------------------------

def _texts(block):
    """sha256 of the lowered text of each entry point of a small program
    of ``block``, from ``ShapeDtypeStruct``s (nothing runs)."""
    import tests.test_latent_moe as tl
    import tests.test_linear_gqa as tg
    import tests.test_sparse_gqa as ts
    mod, arch = {"latent_moe": (latent_moe, tl.arch_of),
                 "sparse_gqa": (sparse_gqa, ts.arch_of),
                 "linear_gqa": (linear_gqa, tg.arch_of),
                 "ssm_gqa": (ssm_gqa, arch_of)}[block]
    arch = arch()
    params = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0),
                                                    arch))
    prog = mod.decode_program(arch, 4, 32)
    s_n, pps = 2, prog.pages_per_slot
    first, rest = jax.eval_shape(lambda: alloc_pools(prog, 17, slots=s_n))
    assert isinstance(rest, PoolsAndState) == bool(prog.slot_state)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    slot = (i32(),) if prog.slot_state else ()
    step_args = (params, first, rest, i32(s_n, pps), i32(s_n), i32(s_n),
                 jax.ShapeDtypeStruct((s_n,), jnp.bool_))
    f32 = jax.ShapeDtypeStruct((s_n,), jnp.float32)
    lowered = {
        "prefill_at": jax.jit(prog.prefill_at, donate_argnums=(1, 2)).lower(
            params, first, rest, i32(pps), i32(8), i32(), i32(), *slot),
        "prefill": jax.jit(prog.prefill, donate_argnums=(1, 2)).lower(
            params, first, rest, i32(pps), i32(8), i32(), *slot),
        "step": jax.jit(prog.step, donate_argnums=(1, 2)).lower(*step_args),
        "step_multi": jax.jit(prog.step_multi, donate_argnums=(1, 2)).lower(
            *step_args, f32, i32(s_n), f32,
            jax.ShapeDtypeStruct((s_n,), jnp.uint32), i32(s_n), i32(s_n),
            i32(), i32(2)),
        "reencode": jax.jit(prog.reencode).lower(params, i32(1, 16))}
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
            for k, v in lowered.items()}


#: the digests of the parent's tree (PR 40's commit), on this
#: repository's one installation (jax 0.9.0): the first two blocks' are
#: those tests/test_linear_gqa.py pins, the third's were taken from a
#: ``git archive`` of the parent before this block joined the builder
BEFORE = {
    "latent_moe": {"prefill_at": "9e523187ef514400",
                   "prefill": "75f7e2286e3f0566", "step": "3b3858ebf0e144ec",
                   "step_multi": "eb46206caa61b7e9",
                   "reencode": "055df8d5d251b425"},
    "sparse_gqa": {"prefill_at": "99861492a1a1c19d",
                   "prefill": "32c32972e353bb69", "step": "bb4e166c8adad74e",
                   "step_multi": "3b3917727229b5cf",
                   "reencode": "b6327a003d8beba3"},
    "linear_gqa": {"prefill_at": "09342fd0fb56d294",
                   "prefill": "5165f368447d709b", "step": "1a2aecab45063b12",
                   "step_multi": "0105b0e597c5eeed",
                   "reencode": "b03bfb04abee49e6"},
}


#: this tree's, since PR 46 put the expert layer under a ``jit`` of its
#: own (tests/test_linear_gqa.py ``SINCE_PR46`` says what moved and what
#: did not)
SINCE_PR46 = {
    "linear_gqa": {"prefill_at": "db3343db441b92c5",
                   "prefill": "534bac04d2780e02", "step": "d8b1d04c807f334e",
                   "step_multi": "0f62cef1e25aea98",
                   "reencode": "be339e83bc62a034"},
}


@pytest.mark.parametrize("expert_layer", ["its-own-function", "traced-in-line"])
@pytest.mark.parametrize("block", ["latent_moe", "sparse_gqa", "linear_gqa"])
def test_the_other_blocks_programs_lower_to_what_they_did(block, expert_layer,
                                                          monkeypatch):
    """One builder for four blocks, which now scales the residual, the
    embedding and the logits and ties the head where the architecture
    says so: the three that say none of it get the programs they got on
    the parent, text for text, but for the expert layer's call."""
    import tests.test_linear_gqa as tg
    tg.pinned_texts(_texts, block, expert_layer, BEFORE,
                    {**tg.SINCE_PR46, **SINCE_PR46}, monkeypatch)
