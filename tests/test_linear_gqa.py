"""The block of two layer kinds (models/linear_gqa.py: gated NoPE
grouped-query layers over paged K and V, gated delta-rule linear layers
over a per-slot recurrent state) against the plain reference of the
benchmark's ``solar-open2-250b`` configuration and against its own other
path, at small sizes on the CPU with seeded weights: the full forward,
the chunked scan against the recurrence, chunked prefill and
prefill-then-decode through the engine (plain and fused), the state's
life in a slot, and the engine's refusals."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import latent_moe, linear_gqa, sparse_gqa
from deeplearning4j_tpu.models.arch import LMArch
from deeplearning4j_tpu.ops.kv_cache import (PoolsAndState, alloc_pools,
                                             scrub_pool, state_nbytes)
from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
from deeplearning4j_tpu.parallel.moe import moe_forward_held
from deeplearning4j_tpu.serving import DecodeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_PATH = os.path.join(ROOT, "benchmarks", "configs",
                           "solar-open2-250b.json")


def _reference(name="solar-open2-250b"):
    path = os.path.join(ROOT, "benchmarks", "configs", f"{name}_reference.py")
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_") + "_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
with open(CONFIG_PATH) as _f:
    CONFIG = json.load(_f)

#: a small model of the same family: one period of 4 layers (layer 0
#: grouped-query, 1 to 3 linear), 4 query heads over 2 KV heads of 8, 4
#: linear heads of 8 with 4 taps, 8 experts with 2 a token and a shared one
SIZES = {
    **{k: CONFIG[k] for k in ("use_rope", "use_gqa_gate", "kda_use_full_proj",
                              "kda_allow_neg_eigval", "first_k_dense_replace",
                              "norm_topk_prob", "tie_word_embeddings",
                              "rms_norm_eps", "gqa_layers",
                              "routed_scaling_factor", "router_bias_std")},
    "vocab_size": 64, "num_hidden_layers": 4, "hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 8,
                           "num_heads": 4, "num_kv_heads": None},
    "moe_intermediate_size": 16, "n_routed_experts": 8,
    "n_routed_experts_published": 8, "first_expert": 0,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "initializer_range": 0.2, "max_position_embeddings": 128,
}
SEED = 4294967311
#: float32 weights: program and reference differ by the order of their
#: sums only (widest gap seen 3.1e-05: the chunked scan sums a sub-chunk's
#: rows where the recurrence goes row by row)
LOGIT_ATOL = 2e-4
TOKENS = np.random.default_rng(5).integers(0, 64, 100).astype(np.int32)


def sizes_of(cfg=SIZES):
    return {k: cfg[k] for k in ref.SIZE_KEYS}


def arch_of(cfg=SIZES, **over):
    return LMArch.from_config(cfg, **over)


def tree_of(cfg=SIZES, seed=SEED, dtype=jnp.float32):
    """The program's tree holding the reference's seeded weights."""
    return ref.init_params(ref.seed_key(seed), sizes_of(cfg), dtype)


def ref_forward(tokens, precision="float32", seed=SEED):
    with ref.with_precision("float32"):
        lg, picks = ref.forward(ref.seed_key(seed), jnp.asarray(tokens),
                                sizes_of(), precision, dtype=jnp.float32)
    return np.asarray(lg), np.stack(picks, 1)


def program_forward(tokens):
    lg, aux = linear_gqa.forward(tree_of(), jnp.asarray(tokens)[None],
                                 arch_of(), with_aux=True)
    return np.asarray(lg[0]), np.asarray(aux["expert_picks"][0])


@pytest.fixture(scope="module")
def mesh():
    return build_mesh({"data": 1}, devices=jax.devices()[:1])


@pytest.fixture(scope="module")
def lm(mesh):
    return ShardedTransformerLM(arch=arch_of(), params=tree_of(), mesh=mesh)


@pytest.fixture(scope="module")
def reference_run():
    return ref_forward(TOKENS[:40])


# -- the description ------------------------------------------------------------

def test_the_benchmarks_file_is_this_family_at_its_published_widths():
    arch = LMArch.from_config(CONFIG, max_len=19456, param_dtype="bfloat16")
    assert arch.block == "linear_gqa" and arch.router == "noaux_tc"
    assert (arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim) == \
        (4096, 64, 8, 128)
    assert (arch.linear_n_heads, arch.linear_head_dim, arch.conv_kernel) == \
        (64, 128, 4)
    # the published list, read up to the depth held: one whole period
    assert arch.layer_types == ("gqa", "linear", "linear", "linear")
    assert (arch.n_experts, arch.experts_held, arch.first_expert,
            arch.experts_per_token, arch.moe_d_ff, arch.n_shared_experts) == \
        (320, 40, CONFIG["first_expert"], 8, 1280, 1)
    assert arch.n_layers == arch.n_moe_layers == 4
    assert arch.vocab_size == 24576 and arch.routed_scaling_factor == 1
    prog = linear_gqa.decode_program(arch, 16, 19456)
    assert prog.pool_rows == ((1024,), (1024,))              # K, V
    assert prog.kinds == ("pool", "state", "state", "state")
    assert prog.slot_state == (((64, 128, 128), jnp.dtype("float32")),
                               ((3, 3 * 8192), jnp.dtype("bfloat16")))
    assert prog.pool_dtype == jnp.bfloat16 and prog.pages_per_slot == 1216


@pytest.mark.parametrize("key,value", [
    ("use_rope", True), ("use_gqa_gate", False), ("kda_use_full_proj", True),
    ("kda_allow_neg_eigval", False), ("first_k_dense_replace", 1),
    ("norm_topk_prob", False), ("tie_word_embeddings", True)])
def test_a_key_the_block_cannot_express_is_refused_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        LMArch.from_config({**SIZES, key: value})


def test_what_the_description_cannot_say_is_refused():
    with pytest.raises(ValueError, match="num_kv_heads"):
        LMArch.from_config({**SIZES, "linear_attn_config": {
            **SIZES["linear_attn_config"], "num_kv_heads": 2}})
    with pytest.raises(ValueError, match="layer_types"):
        arch_of(layer_types=("gqa", "linear"))
    with pytest.raises(ValueError, match="neither"):
        LMArch.from_config({"vocab_size": 8, "gqa_layers": [0]})


def test_training_says_it_is_not_there(lm):
    with pytest.raises(NotImplementedError, match="linear_gqa"):
        lm.fit_batch(TOKENS[None, :8], TOKENS[None, 1:9])


# -- the full forward ------------------------------------------------------------

def test_forward_agrees_with_the_reference(reference_run):
    want, want_picks = reference_run
    got, picks = program_forward(TOKENS[:40])
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    np.testing.assert_array_equal(picks, want_picks)


def test_forward_over_more_than_one_sub_chunk_agrees_with_the_reference():
    """100 rows: a whole sub-chunk of 64 and a padded one; the state is
    carried between them."""
    want, _ = ref_forward(TOKENS)
    got, _ = program_forward(TOKENS)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)


def test_reencode_is_the_forward(lm, reference_run):
    prog = lm.decode_program(page_size=4, max_len=128)
    got = np.asarray(prog.reencode(lm.params,
                                   jnp.asarray(TOKENS[:40])[None]))[0]
    np.testing.assert_allclose(got, reference_run[0], atol=LOGIT_ATOL, rtol=0)


# -- the scan against the recurrence ---------------------------------------------

def _recurrence(q, k, v, g, beta, S):
    out = []
    for t in range(q.shape[0]):
        o, S = linear_gqa.delta_step(S, q[t], k[t], v[t], g[t], beta[t])
        out.append(o)
    return jnp.stack(out), S


@pytest.mark.parametrize("decay", [
    "slowest", "fastest", "mixed"])
@pytest.mark.parametrize("beta_range", [(0.05, 0.95), (1.05, 1.95)])
@pytest.mark.parametrize("from_zero", [True, False])
def test_the_chunked_scan_is_the_token_by_token_recurrence(decay, beta_range,
                                                           from_zero):
    """At both ends of the decays ``b_dt`` allows (``softplus`` 0.001 at
    ``exp(A)`` 1: -0.001 a token; 0.1 at 16 and an activation of a few
    more: -6 a token, whose running sum passes float32's exponent range
    inside one sub-chunk), ``beta`` below and above 1."""
    rng = np.random.default_rng(17)
    T, H, d = 192, 3, 8
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = f(T, H, d), f(T, H, d), f(T, H, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    lo, hi = {"slowest": (0.001, 0.001), "fastest": (6.0, 6.0),
              "mixed": (0.001, 6.0)}[decay]
    g = -jnp.asarray(np.exp(rng.uniform(np.log(lo), np.log(hi), (T, H, d))),
                     jnp.float32)
    beta = jnp.asarray(rng.uniform(*beta_range, (T, H)), jnp.float32)
    S0 = jnp.zeros((H, d, d)) if from_zero else f(H, d, d)
    want_o, want_S = _recurrence(q, k, v, g, beta, S0)
    got_o, got_S = linear_gqa.delta_scan(q, k, v, g, beta, S0)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=1e-4)
    np.testing.assert_allclose(got_S, want_S, atol=5e-5, rtol=1e-4)


def test_the_recurrence_is_the_equation_as_written():
    """``delta_step`` reads ``o`` from the decayed state; the equation
    builds ``S_t`` first."""
    rng = np.random.default_rng(3)
    f = lambda *s: np.asarray(rng.normal(size=s), np.float64)
    S, q, k, v, g = f(8, 8), f(8), f(8), f(8), -np.abs(f(8))
    k /= np.linalg.norm(k)
    beta = 1.7
    want_S = (np.eye(8) - beta * np.outer(k, k)) @ np.diag(np.exp(g)) @ S \
        + beta * np.outer(k, v)
    o, got_S = linear_gqa.delta_step(*(jnp.asarray(a, jnp.float32) for a in
                                       (S, q, k, v, g, beta)))
    np.testing.assert_allclose(got_S, want_S, atol=1e-5)
    np.testing.assert_allclose(o, want_S.T @ q, atol=1e-5)


def test_a_state_kept_in_bfloat16_leaves_the_tolerance_behind(monkeypatch,
                                                              reference_run):
    """What holds the state's stated float32: with float32 weights the
    program sits 3e-5 from the reference, and a state rounded to
    bfloat16 after every row does not.  (On the chip, behind bfloat16
    weights, that rounding moves the logits less than the weights' own
    does, so the benchmark's ``correct`` cannot hold it: PERF.md §2.)"""
    step, scan = linear_gqa.delta_step, linear_gqa.delta_scan
    bf16 = lambda S: jax.lax.reduce_precision(S, 8, 7)

    def rounded_step(S, *row):
        o, S = step(S, *row)
        return o, bf16(S)

    def rounded_scan(q, k, v, g, beta, S0, sub=1):
        out = []
        for t in range(q.shape[0]):
            o, S0 = rounded_step(S0, q[t], k[t], v[t], g[t], beta[t])
            out.append(o)
        return jnp.stack(out), S0

    monkeypatch.setattr(linear_gqa, "delta_scan", rounded_scan)
    got, _ = program_forward(TOKENS[:40])
    assert np.abs(got - reference_run[0]).max() > 10 * LOGIT_ATOL


def test_masked_rows_leave_the_state_as_it_was():
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v, S0 = f(64, 2, 8), f(64, 2, 8), f(64, 2, 8), f(2, 8, 8)
    _, S = linear_gqa.delta_scan(q, k, v, jnp.zeros((64, 2, 8)),
                                 jnp.zeros((64, 2)), S0)
    np.testing.assert_array_equal(np.asarray(S), np.asarray(S0))


# -- the program: chunks, steps, the state's life in a slot ----------------------

@pytest.fixture(scope="module")
def program(lm):
    prog = lm.decode_program(page_size=4, max_len=128)
    fns = {"prefill_at": jax.jit(prog.prefill_at),
           "step": jax.jit(prog.step)}
    return prog, fns


def _table(prog, slots):
    pps = prog.pages_per_slot
    return np.arange(1, 1 + slots * pps, dtype=np.int32).reshape(slots, pps)


def _prefill(program, lm, cache, table_row, tokens, slot, chunk, bucket):
    """``tokens`` into ``slot`` in chunks of ``chunk`` rows, each padded
    to ``bucket``; the cache and the last chunk's logits."""
    prog, fns = program
    first, rest = cache
    for off in range(0, len(tokens), chunk):
        part = tokens[off:off + chunk]
        padded = np.zeros((bucket,), np.int32)
        padded[:len(part)] = part
        first, rest, lg, _ = fns["prefill_at"](
            lm.params, first, rest, table_row, padded, np.int32(len(part)),
            np.int32(off), np.int32(slot))
    return (first, rest), np.asarray(lg)


def test_the_pools_hold_the_grouped_query_layers_and_the_state_rides_beside(
        program):
    prog, _ = program
    first, rest = alloc_pools(prog, 9, slots=3)
    assert isinstance(rest, PoolsAndState)
    assert first.shape == (1, 9, 4, 16)                 # ONE layer of four
    assert [a.shape for a in rest.pools] == [(1, 9, 4, 16)]
    assert [[a.shape for a in layer] for layer in rest.state] == \
        [[(3, 4, 8, 8), (3, 3, 96)]] * 3
    assert state_nbytes((first, rest)) == 3 * 3 * (4 * 8 * 8 + 3 * 96) * 4
    with pytest.raises(ValueError, match="slots"):
        alloc_pools(prog, 9)
    # a scrub zeroes pages of the pools and leaves the state alone
    ones = jax.tree_util.tree_map(jnp.ones_like, (first, rest))
    k, scrubbed = scrub_pool(ones[0], jnp.asarray([2])), \
        scrub_pool(ones[1], jnp.asarray([2]))
    assert not np.asarray(k)[:, 2].any()
    assert not np.asarray(scrubbed.pools[0])[:, 2].any()
    assert all(np.asarray(a).all()
               for a in jax.tree_util.tree_leaves(scrubbed.state))


@pytest.mark.parametrize("chunk,bucket", [(16, 16), (16, 32), (8, 8)])
def test_a_prompt_in_padded_chunks_is_the_prompt_in_one(program, lm, chunk,
                                                        bucket):
    """The state stops at ``n_real``: 37 rows in chunks whose last is
    padded (and, at bucket 32, all are) leave the logits, the state and
    the tail that one chunk of 37 rows leaves."""
    prog, _ = program
    table = _table(prog, 2)
    tokens = TOKENS[:37]
    whole, want = _prefill(program, lm, alloc_pools(prog, 65, slots=2),
                           table[1], tokens, 1, 64, 64)
    parts, got = _prefill(program, lm, alloc_pools(prog, 65, slots=2),
                          table[1], tokens, 1, chunk, bucket)
    np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    np.testing.assert_allclose(got, ref_forward(tokens)[0][-1],
                               atol=LOGIT_ATOL, rtol=0)
    for a, b in zip(jax.tree_util.tree_leaves(parts[1].state),
                    jax.tree_util.tree_leaves(whole[1].state)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a[1], b[1], atol=2e-5, rtol=0)
        assert not a[0].any()                       # the other slot: untouched


def test_an_idle_slot_next_to_busy_ones_does_not_change(program, lm):
    """Slots 0 and 2 step, slot 1 holds a prefilled request that is not
    active: its state and tail come back bit for bit, and garbage in an
    idle slot's state is not what a later admission starts from."""
    prog, fns = program
    table = _table(prog, 3)
    cache = alloc_pools(prog, 1 + 3 * prog.pages_per_slot, slots=3)
    for slot, n in ((0, 9), (1, 21), (2, 5)):
        cache, _ = _prefill(program, lm, cache, table[slot], TOKENS[:n], slot,
                            16, 16)
    before = jax.tree_util.tree_map(np.asarray, cache[1].state)
    first, rest, _, aux = fns["step"](
        lm.params, *cache, table, np.asarray([3, 4, 5], np.int32),
        np.asarray([9, 21, 5], np.int32), np.asarray([True, False, True]))
    for a, b in zip(jax.tree_util.tree_leaves(rest.state),
                    jax.tree_util.tree_leaves(before)):
        a = np.asarray(a)
        np.testing.assert_array_equal(a[1], b[1])
        assert (a[0] != b[0]).any() and (a[2] != b[2]).any()
    assert dict(zip(linear_gqa.STATE_STATS,
                    np.asarray(aux["state_stats"]).tolist())) == {
        "state_slots_stepped": 2 * 3, "state_rows_scanned": 0,
        "kv_rows_held": 10 + 6,
        # every slot reads blocks (the window, at this size) up to the
        # fullest ACTIVE slot's row, and its own new row
        "kv_rows_read": 3 * (128 + 1)}
    # a new request in slot 1 starts from zero whatever the slot held
    _, got = _prefill(program, lm, (first, rest), table[1], TOKENS[50:63], 1,
                      16, 16)
    _, want = _prefill(program, lm, alloc_pools(prog, 1 + 3 * prog.pages_per_slot,
                                                slots=3),
                       table[1], TOKENS[50:63], 1, 16, 16)
    np.testing.assert_array_equal(got, want)


def test_the_step_walks_its_slots_in_groups_by_the_rows_they_hold(
        monkeypatch):
    """Eight slots, blocks of 16 rows: the step takes the slots four at a
    time in the order of the rows they hold and walks each group as far
    as ITS fullest slot.  Every stepped slot's logits are the reference's
    full forward's at its row; what the walk read is counted by group,
    under what one walk of all eight to the fullest slot's row reads."""
    monkeypatch.setattr(linear_gqa, "KV_BLOCK_ROWS", 16)
    prog = linear_gqa.decode_program(arch_of(), 4, 128)
    fns = {"prefill_at": jax.jit(prog.prefill_at), "step": jax.jit(prog.step)}
    params = tree_of()
    lm_like = type("P", (), {"params": params})
    table = _table(prog, 8)
    held = [3, 40, 9, 7, 21, 5, 33, 12]
    active = np.asarray([True, True, True, False, True, True, True, True])
    cache = alloc_pools(prog, 1 + 8 * prog.pages_per_slot, slots=8)
    for slot, n in enumerate(held):
        cache, _ = _prefill((prog, fns), lm_like, cache, table[slot],
                            TOKENS[slot:slot + n], slot, 16, 16)
    new = np.asarray([TOKENS[slot + n] for slot, n in enumerate(held)])
    _, _, lg, aux = fns["step"](params, *cache, table, new.astype(np.int32),
                                np.asarray(held, np.int32), active)
    for slot, n in enumerate(held):
        if active[slot]:
            want, _ = ref_forward(TOKENS[slot:slot + n + 1])
            np.testing.assert_allclose(np.asarray(lg)[slot], want[n],
                                       atol=LOGIT_ATOL, rtol=0)
    stats = dict(zip(linear_gqa.STATE_STATS,
                     np.asarray(aux["state_stats"]).tolist()))
    assert stats["kv_rows_held"] == sum(n + 1 for n, a in zip(held, active)
                                        if a)
    # by the rows held (the idle slot counts none): 0, 3, 5, 9 walk to row
    # 9 (one block), 12, 21, 33, 40 to row 40 (three)
    assert stats["kv_rows_read"] == 4 * (16 + 1) + 4 * (48 + 1)
    assert stats["kv_rows_read"] < 8 * (48 + 1)


# -- through the engine -----------------------------------------------------------

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
    return {h: _served(lm, h, [REQUESTS]) for h in (1, 4)}


@pytest.mark.parametrize("horizon", [1, 4])
def test_chunked_prefill_and_decode_agree_with_the_reference(served, horizon):
    """Echoed logits and chosen experts, position by position, against
    the reference's full forward of prompt + answer: four requests on
    two slots, so each slot serves a second request after its first."""
    (out,), snap = served[horizon]
    for ((a, b), new, kw), res in zip(REQUESTS, out):
        assert len(res.tokens) == new
        assert res.expert_picks.shape == (new, 4, 2)
        if "echo_logits" not in kw:
            assert res.logits is None
            continue
        seq = np.concatenate([TOKENS[a:b], res.tokens])
        want, want_picks = ref_forward(seq)
        at = b - a - 1 + np.arange(new)
        np.testing.assert_allclose(res.logits, want[at], atol=LOGIT_ATOL,
                                   rtol=0)
        assert res.tokens == want[at].argmax(-1).tolist()
        np.testing.assert_array_equal(res.expert_picks, want_picks[at])
    c = snap["counters"]
    assert c["recurrent_state_resets"] == 4
    assert c["state_rows_scanned"] == 3 * (5 + 37 + 21 + 37)
    assert c["state_slots_stepped"] >= 3 * (5 + 8 + 6 + 8)
    assert c["kv_rows_read"] >= c["kv_rows_held"] > 0
    assert snap["recurrent_state_bytes"] == 2 * 3 * (4 * 8 * 8 + 3 * 96) * 4
    assert snap["kv_bytes_per_token"] == 2 * 16 * 4     # ONE layer's K and V


def ref_states(tokens, state_at, seed=SEED):
    """The reference's recurrent state of each linear layer as row
    ``state_at`` left it, float32 weights."""
    sz, key = sizes_of(), ref.seed_key(seed)
    with ref.with_precision("float32"):
        h = ref.embed(ref.init_ends(key, sz, jnp.float32), jnp.asarray(tokens))
        states = []
        for i in range(sz["num_hidden_layers"]):
            p = ref.init_layer(ref.layer_key(key, i), sz,
                               ref.layer_kind(sz, i), jnp.float32)
            h, _, S = ref.layer(p, h, sz, "float32", state_at)
            if S is not None:
                states.append(np.asarray(S))
    return states


@pytest.mark.parametrize("horizon", [1, 4])
def test_an_answer_brings_the_state_its_last_fed_token_left(served, horizon):
    """``echo_state``: the slot's state at the answer's end is the
    recurrence's after every token of prompt and answer but the
    answer's last, which was served and never fed back; a request that
    did not ask gets none."""
    (out,), _ = served[horizon]
    for ((a, b), new, kw), res in zip(REQUESTS, out):
        if "echo_state" not in kw:
            assert res.slot_state is None
            continue
        seq = np.concatenate([TOKENS[a:b], res.tokens])
        want = ref_states(seq, len(seq) - 2)
        a_row_early = ref_states(seq, len(seq) - 3)
        assert len(res.slot_state) == len(want) == 3
        for (S, tail), S_ref, S_early in zip(res.slot_state, want,
                                             a_row_early):
            assert tail.shape == (3, 96)
            np.testing.assert_allclose(np.asarray(S), S_ref, atol=2e-5,
                                       rtol=0)
            assert np.abs(S_ref - S_early).max() > 1e-3   # a row off is not


def test_a_fused_horizon_serves_what_single_steps_serve(served):
    (one,), _ = served[1]
    (four,), _ = served[4]
    for a, b in zip(one, four):
        assert a.tokens == b.tokens
        np.testing.assert_array_equal(a.expert_picks, b.expert_picks)
        if a.logits is not None:
            np.testing.assert_allclose(a.logits, b.logits, atol=1e-6, rtol=0)


@pytest.mark.parametrize("horizon", [1, 4])
def test_a_slot_admitted_a_second_request_serves_what_a_fresh_engine_serves(
        lm, horizon):
    """One slot: the second request inherits nothing of the first (its
    first chunk starts from zero state; the first's overrun steps are
    behind it)."""
    second = ((40, 77), 6, {"echo_logits": True})
    (_, (got,)), _ = _served(lm, horizon,
                             [[((0, 30), 5, {})], [second]], slots=1)
    ((want,),), _ = _served(lm, horizon, [[second]], slots=1)
    assert got.tokens == want.tokens
    np.testing.assert_array_equal(got.logits, want.logits)


def test_two_chunks_of_one_slot_in_one_turn_leave_the_state_of_one_a_turn(lm):
    """``nearest_end`` hands a turn's second chunk to the prompt it gave
    the first (the one nearest its end), queued behind it before either
    is read: the slot's state rides from one to the next on the device,
    and state, logits and tokens are those of one chunk a turn."""
    import time

    from deeplearning4j_tpu.obs import trace as obs_trace

    def serve(one_a_turn):
        eng = DecodeEngine(lm, max_slots=3, page_size=4, max_len=128,
                           prompt_buckets=[8, 16], prefill_chunk=16,
                           decode_horizon=4,
                           prefill_order="nearest_end").load()
        if one_a_turn:
            many = eng._chunk_budget
            eng._chunk_budget = lambda: min(1, many())
        rec = obs_trace.enable_tracing(capacity=65536)
        try:
            beside = eng.generate_async(TOKENS[90:95], max_new_tokens=60)
            while not eng.metrics.counter_value("tokens_out"):
                time.sleep(0.0005)
            with eng._lock:     # both queued before the loop's next turn
                futs = [eng.generate_async(TOKENS[a:b], max_new_tokens=6,
                                           **_ECHO)
                        for a, b in ((0, 53), (20, 90))]
            out = [f.result(timeout=300) for f in futs]
            beside.result(timeout=300)
            return out, rec.events(), eng.metrics_snapshot()["counters"]
        finally:
            obs_trace.disable_tracing()
            eng.shutdown()

    got, events, c = serve(False)
    want, _, _ = serve(True)
    steps = [e for e in events if e["name"] == "serve/decode_step"]
    queued = [e for e in events if e["name"] == "serve/prefill_dispatch"]
    twice = [s for s in steps if s["args"].get("chunks", 0) >= 2
             and len({q["args"]["slot"] for q in queued
                      if s["ts"] <= q["ts"] <= s["ts"] + s["dur"]}) == 1]
    assert twice, "no turn queued two chunks of one slot"
    assert c["recurrent_state_resets"] == 3 and c["chunk_turns_multi"] >= 1
    assert c["state_rows_scanned"] == 3 * (5 + 53 + 70)
    for g, w in zip(got, want):
        assert g.tokens == w.tokens
        np.testing.assert_array_equal(g.logits, w.logits)
        for (S, tail), (S_w, tail_w) in zip(g.slot_state, w.slot_state):
            np.testing.assert_array_equal(np.asarray(S), np.asarray(S_w))
            np.testing.assert_array_equal(np.asarray(tail),
                                          np.asarray(tail_w))


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
    # three linear layers scan the chunk's REAL rows; the one grouped-query
    # layer holds offset + tokens rows and reads whole blocks (128 rows
    # here: the window) up to the rows cached, and the chunk's own 16 or 8
    # padded rows
    assert [c["state_rows_scanned"] for c in chunks] == [48, 48, 15]
    assert [c["kv_rows_held"] for c in chunks] == [16, 32, 37]
    assert [c["kv_rows_read"] for c in chunks] == [16, 128 + 16, 128 + 8]
    assert all(c["state_slots_stepped"] == 0 for c in chunks)
    first = steps[0]                    # four fused steps of one active slot
    assert first["state_slots_stepped"] == 4 * 3
    assert first["kv_rows_held"] == 38 + 39 + 40 + 41
    assert first["kv_rows_read"] == 4 * 2 * (128 + 1)   # both slots' blocks
    assert first["state_rows_scanned"] == 0 and first["experts_hit"] > 0
    assert "kv_pages_read" not in first


@pytest.mark.parametrize("what,kw", [
    ("prefix", {"prefix_cache": True}), ("int8", {"kv_dtype": "int8"}),
    ("page transfer", {"role": "prefill"}),
    ("speculation", {"draft_model": "a draft"})])
def test_the_engine_refuses_by_name_what_the_state_does_not_carry(lm, what,
                                                                  kw):
    if "draft_model" in kw:
        kw = {"draft_model": lm}
    with pytest.raises(ValueError,
                       match=f"per-slot recurrent state.*{what}"):
        DecodeEngine(lm, max_slots=2, page_size=4, max_len=128, **kw)


def test_tensor_parallel_decode_is_refused_by_name():
    devices = jax.devices()
    if len(devices) < 2:
        pytest.skip("needs two devices")
    lm2 = ShardedTransformerLM(arch=arch_of(), params=tree_of(),
                               mesh=build_mesh({"data": 2},
                                               devices=devices[:2]))
    with pytest.raises(NotImplementedError, match="tensor-parallel decode"):
        lm2.decode_program(page_size=4, max_len=128)


# -- the share ---------------------------------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that all eight shares of a 16-expert layer give (2
    experts each), plus the shared expert ONCE, equal the uncut
    reference's expert layer."""
    whole_sizes = {**sizes_of(), "n_routed_experts": 16,
                   "n_routed_experts_published": 16, "first_expert": 0}
    whole = ref.init_layer(jax.random.PRNGKey(3), whole_sizes, "linear",
                           jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(7), (33, 32))
    with ref.with_precision("float32"):
        want, want_picks = ref.experts(whole, x, whole_sizes)
    total = 0.0
    for share in range(8):
        lo = 2 * share
        p = {**whole, **{k: whole[k][lo:lo + 2]
                         for k in ("e_gate", "e_up", "e_down")}}
        y, picks, _ = moe_forward_held(p, x, first_expert=lo, k=2,
                                       scaling=1.0, shared=share == 0)
        np.testing.assert_array_equal(np.asarray(picks),
                                      np.asarray(want_picks))
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


# -- the other blocks of the one builder --------------------------------------------

def _texts(block):
    """sha256 of the lowered text of each entry point of a small program
    of ``block``, from ``ShapeDtypeStruct``s (nothing runs)."""
    import tests.test_latent_moe as tl
    import tests.test_sparse_gqa as ts
    mod, arch = {"latent_moe": (latent_moe, tl.arch_of()),
                 "sparse_gqa": (sparse_gqa, ts.arch_of())}[block]
    params = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0),
                                                    arch))
    prog = mod.decode_program(arch, 4, 32)
    first, rest = jax.eval_shape(lambda: alloc_pools(prog, 17))
    assert not isinstance(rest, PoolsAndState)
    assert prog.kinds is None and prog.slot_state == ()
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    s_n, pps = 2, prog.pages_per_slot
    step_args = (params, first, rest, i32(s_n, pps), i32(s_n), i32(s_n),
                 jax.ShapeDtypeStruct((s_n,), jnp.bool_))
    f32 = jax.ShapeDtypeStruct((s_n,), jnp.float32)
    lowered = {
        "prefill_at": jax.jit(prog.prefill_at, donate_argnums=(1, 2)).lower(
            params, first, rest, i32(pps), i32(8), i32(), i32()),
        "prefill": jax.jit(prog.prefill, donate_argnums=(1, 2)).lower(
            params, first, rest, i32(pps), i32(8), i32()),
        "step": jax.jit(prog.step, donate_argnums=(1, 2)).lower(*step_args),
        "step_multi": jax.jit(prog.step_multi, donate_argnums=(1, 2)).lower(
            *step_args, f32, i32(s_n), f32,
            jax.ShapeDtypeStruct((s_n,), jnp.uint32), i32(s_n), i32(s_n),
            i32(), i32(2)),
        "reencode": jax.jit(prog.reencode).lower(params, i32(1, 16))}
    return {k: hashlib.sha256(v.as_text().encode()).hexdigest()[:16]
            for k, v in lowered.items()}


#: the digests of the tree before this block joined the builder (PR 36's
#: commit), on this repository's one installation (jax 0.9.0).  The two
#: ``step_multi`` digests are PR 38's: the scan's body holds the sampler,
#: which finds its top-k threshold by selection since (ops/sampling.py);
#: ``sparse_gqa``'s other four still pin the threshold walk's move to
#: ops/select.py as a pure one
BEFORE = {
    "latent_moe": {"prefill_at": "9e523187ef514400",
                   "prefill": "75f7e2286e3f0566", "step": "3b3858ebf0e144ec",
                   "step_multi": "eb46206caa61b7e9",
                   "reencode": "055df8d5d251b425"},
    "sparse_gqa": {"prefill_at": "99861492a1a1c19d",
                   "prefill": "32c32972e353bb69", "step": "bb4e166c8adad74e",
                   "step_multi": "3b3917727229b5cf",
                   "reencode": "b6327a003d8beba3"},
}


#: the digests of this tree since PR 46, which put what follows the
#: router of an expert layer (``parallel/moe._held_picks``) under a
#: ``jit`` of its own: every text now holds it ONCE, as a private
#: function called a layer.  At these widths the grouped feed-forward
#: keeps ``ragged_dot`` (64 and 32 are no multiples of 128 lanes), so
#: with that ``jit`` taken away each text is the one ``BEFORE`` pins: the
#: router's, the attention's and the shared expert's did not move
SINCE_PR46 = {
    "latent_moe": {"prefill_at": "939c625c4761c2b8",
                   "prefill": "f38eeb786a2385a7", "step": "b14eab4fb96a8523",
                   "step_multi": "0ebdb0c39eaddf67",
                   "reencode": "347727fb6049f224"},
    "sparse_gqa": {"prefill_at": "a972f00f1936fb0a",
                   "prefill": "3e4578b93abd6121", "step": "3df1885023f3b5b0",
                   "step_multi": "39a4755ab6232851",
                   "reencode": "7644d1318b26aab9"},
}


def pinned_texts(texts, block, expert_layer, before, since, monkeypatch):
    """``texts(block)`` against ``since`` as the tree lowers it, against
    ``before`` with the expert layer traced in line."""
    from deeplearning4j_tpu.parallel import moe
    if expert_layer == "traced-in-line":
        monkeypatch.setattr(moe, "_held_picks", moe._held_picks.__wrapped__)
        assert texts(block) == before[block]
    else:
        assert texts(block) == since[block]


@pytest.mark.parametrize("expert_layer", ["its-own-function", "traced-in-line"])
@pytest.mark.parametrize("block", ["latent_moe", "sparse_gqa"])
def test_the_other_blocks_programs_lower_to_what_they_did(block, expert_layer,
                                                          monkeypatch):
    """One builder for three blocks: the two that keep no per-slot state
    get the programs they got before, text for text, but for the expert
    layer's call."""
    pinned_texts(_texts, block, expert_layer, BEFORE, SINCE_PR46, monkeypatch)
