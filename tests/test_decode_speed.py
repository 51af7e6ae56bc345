"""Decode-side speed offensive: radix prefix cache, speculative
decoding, int8 KV storage (docs/SERVING.md "Decode-side optimizations").

The key contracts tested here:
  - prefix-hit requests produce the tokens of a cold decode and
    logits within ``_decode_checks.LOGIT_ATOL`` of the re-encode's —
    sharing pages is an allocation optimization, never an approximation
  - the page pool stays a clean partition (free / slot-private /
    trie-resident) through hits, eviction, crash-retry and poison: a
    crash-retry of a prefix-hit request never double-decrefs, and a
    poison scrub never touches a referenced shared page
  - temperature-0 speculative decoding gives the plain engine's tokens
    and logits within that limit of the re-encode's (a self-draft
    control accepts every proposal); seeded
    sampling stays deterministic; a crash mid-speculative-round strands
    nothing
  - int8 KV storage is gated by an accuracy envelope (top-1 agreement
    vs the f32 oracle), never the identity gates, and halves+ the pool
    bytes
  - all three features are zero-serve-time-compile and report their
    counters through DecodeMetrics (zero-keys when off)
"""

import json
import time
import urllib.request

import numpy as np
import pytest
from _decode_checks import assert_greedy_echo, assert_logits_close

from deeplearning4j_tpu.ops.kv_cache import (
    QuantPages, _quantize_rows, alloc_cache, alloc_pools, gather_layer,
    head_lanes,
    pool_nbytes, scrub_pool, write_prefill, write_step, write_tokens,
)
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM
from deeplearning4j_tpu.serving import DecodeEngine, PoisonInputError

VOCAB, MAXLEN, PAGE = 48, 64, 8
K = 3


@pytest.fixture(scope="module")
def lm():
    import jax

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:1])
    return ShardedTransformerLM(vocab_size=VOCAB, n_layers=2, d_model=32,
                                n_heads=2, max_len=MAXLEN, mesh=mesh,
                                seed=11)


@pytest.fixture(scope="module")
def draft_lm(lm):
    return ShardedTransformerLM(vocab_size=VOCAB, n_layers=1, d_model=16,
                                n_heads=2, max_len=MAXLEN, mesh=lm.mesh,
                                seed=9)


def _make(lm, **kw):
    return DecodeEngine(lm, max_slots=3, page_size=PAGE,
                        default_max_new=8, prompt_buckets=(16, 32),
                        **kw).load()


@pytest.fixture(scope="module")
def plain(lm):
    eng = _make(lm)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def pref(lm):
    eng = _make(lm, prefix_cache=True)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def spec(lm, draft_lm):
    eng = _make(lm, draft_model=draft_lm, speculate_k=K)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def i8(lm):
    eng = _make(lm, kv_dtype="int8")
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def oracle(lm, plain):
    """The reference: re-encode the full sequence, return per-row
    logits (the same contract the decode A/B gates on)."""
    import jax

    prog = plain.program
    re1 = jax.jit(prog.reencode).lower(
        lm.params, np.zeros((1, prog.max_len), np.int32)).compile()

    def rows(prompt, toks):
        seq = np.zeros((1, prog.max_len), np.int32)
        full = [int(x) for x in prompt] + [int(t) for t in toks]
        seq[0, :len(full)] = full
        return np.asarray(re1(lm.params, seq))[0]

    return rows


def _tokens(engine, prompt, **kw):
    return engine.generate(prompt, **kw).tokens


def _ctr(engine, key):
    return engine.metrics.snapshot()["counters"][key]


def _partition_ok(engine) -> bool:
    """free / slot-private / trie-resident must partition 1..N-1."""
    st = engine._debug_page_state()
    all_ids = st["free"] + st["private"] + st["trie"]
    return (len(all_ids) == len(set(all_ids))
            and sorted(all_ids) == list(range(1, engine.total_pages)))


PREFIX = list(range(1, 17))          # two full pages when PAGE == 8


class TestPrefixCache:
    def test_hit_is_bitwise_identical_and_counts(self, pref, plain,
                                                 oracle):
        h0, t0 = _ctr(pref, "prefix_hits"), _ctr(pref, "prefix_hit_tokens")
        _tokens(pref, PREFIX + [20, 21, 22])        # seeds the trie
        res = pref.generate(PREFIX + [30, 31], max_new_tokens=8,
                            echo_logits=True)
        assert _ctr(pref, "prefix_hits") == h0 + 1
        assert _ctr(pref, "prefix_hit_tokens") == t0 + len(PREFIX)
        assert res.tokens == _tokens(plain, PREFIX + [30, 31],
                                     max_new_tokens=8)
        assert_greedy_echo(PREFIX + [30, 31], res,
                           oracle(PREFIX + [30, 31], res.tokens))

    def test_identical_prompt_hits_its_own_insert(self, pref):
        p = PREFIX + [40]
        a = _tokens(pref, p, max_new_tokens=6)
        h0 = _ctr(pref, "prefix_hits")
        assert _tokens(pref, p, max_new_tokens=6) == a
        assert _ctr(pref, "prefix_hits") == h0 + 1

    def test_miss_counts_and_stays_correct(self, pref, plain):
        m0 = _ctr(pref, "prefix_misses")
        assert (_tokens(pref, [42, 43, 44], max_new_tokens=6)
                == _tokens(plain, [42, 43, 44], max_new_tokens=6))
        assert _ctr(pref, "prefix_misses") == m0 + 1

    def test_eviction_under_pool_pressure(self, pref, plain):
        e0 = _ctr(pref, "prefix_evictions")
        rng = np.random.default_rng(3)
        for _ in range(3 * pref.total_pages // 4):   # unique prefixes
            pref.generate(rng.integers(0, VOCAB, size=32).astype(np.int32),
                          max_new_tokens=1)
        assert _ctr(pref, "prefix_evictions") > e0
        assert _partition_ok(pref)
        # a post-eviction request is still exact
        assert (_tokens(pref, PREFIX + [45], max_new_tokens=6)
                == _tokens(plain, PREFIX + [45], max_new_tokens=6))

    def test_shared_pages_gauge_tracks_trie(self, pref):
        snap = pref.metrics_snapshot()
        assert snap["shared_pages"] == len(pref._debug_page_state()["trie"])
        assert snap["prefix_cache"] is True


class TestFreeListHardening:
    def test_crash_retry_of_prefix_hit_never_double_decrefs(self, pref,
                                                            plain):
        """A crash mid-decode resets pool + trie; the retried prefix-hit
        request must re-admit cleanly (no node decref'd twice, no page
        in two partitions) and reproduce the plain tokens."""
        _tokens(pref, PREFIX + [33], max_new_tokens=4)     # trie warm
        refs = [_tokens(plain, PREFIX + [34 + i], max_new_tokens=6)
                for i in range(3)]
        r0 = _ctr(pref, "retries")
        pref._crash_next = True
        futs = [pref.generate_async(PREFIX + [34 + i], max_new_tokens=6)
                for i in range(3)]
        got = [f.result(timeout=60) for f in futs]    # nothing stranded
        assert [r.tokens for r in got] == refs
        assert _ctr(pref, "retries") > r0
        assert _partition_ok(pref)

    def test_poison_scrub_never_touches_referenced_pages(self, pref, lm,
                                                         plain):
        """A poisoned co-tenant that attached shared prefix pages must
        scrub only its private suffix pages: the donor's trie rows stay
        bitwise intact for the next hit."""
        import jax

        ref = _tokens(pref, PREFIX + [18, 19], max_new_tokens=6)
        nan = jax.tree_util.tree_map(
            lambda a: np.full(np.shape(a), np.nan,
                              np.asarray(a).dtype), lm.params)
        p0 = _ctr(pref, "poison_isolated")
        try:
            pref.swap_model(nan, "vnan")
            with pytest.raises(PoisonInputError):
                pref.generate(PREFIX + [22, 23], max_new_tokens=6)
        finally:
            pref.swap_model(lm, "v0")
        assert _ctr(pref, "poison_isolated") > p0
        assert _partition_ok(pref)
        # the shared pages the poisoned request had attached still
        # serve a bitwise-identical hit
        assert _tokens(pref, PREFIX + [18, 19], max_new_tokens=6) == ref


class TestSpeculative:
    def test_self_draft_accepts_every_proposal(self, lm):
        eng = _make(lm, draft_model=lm, speculate_k=K)
        try:
            for p in ([1, 2, 3], [4, 5]):
                eng.generate(p, max_new_tokens=8)
            snap = eng.metrics_snapshot()
            assert snap["speculate_k"] == K
            assert snap["accepted_tokens_per_step"] >= K
        finally:
            eng.shutdown()

    def test_temp0_bitwise_identical_to_plain(self, spec, plain, oracle):
        for p in ([1, 2, 3], [7], list(range(4, 18))):
            res = spec.generate(p, max_new_tokens=8, echo_logits=True)
            assert res.tokens == _tokens(plain, p, max_new_tokens=8)
            assert_greedy_echo(p, res, oracle(p, res.tokens))

    @pytest.mark.parametrize("short", range(1, K + 1))
    def test_verify_at_the_windows_end_matches_reencode(self, lm, plain,
                                                        oracle, short):
        """A request that runs to the window's end is verified at
        positions L-k..L-1: the round's last rows lie past the window
        (written to scratch, never committed), and the rows before them
        must keep their own horizon, not one shifted back by the
        overshoot.  Slot 1 verifies mid-window beside it."""
        import jax

        prog = plain.program
        L, pps = prog.max_len, prog.pages_per_slot
        seqs = np.random.default_rng(short).integers(
            1, VOCAB, (2, L)).astype(np.int32)
        table = np.arange(1, 1 + 2 * pps, dtype=np.int32).reshape(2, pps)
        kp, vp = alloc_pools(prog, 1 + 2 * pps)
        prefill = jax.jit(prog.prefill)
        for i in range(2):
            kp, vp, _ = prefill(lm.params, kp, vp, table[i], seqs[i], L)
        pos = np.array([L - short, 20], np.int32)
        toks = np.zeros((2, K + 1), np.int32)
        for i in range(2):
            row = seqs[i, pos[i]:pos[i] + K + 1]
            toks[i, :len(row)] = row
        _, _, lgs = jax.jit(prog.spec_step)(
            lm.params, kp, vp, table, toks, pos, np.ones((2,), bool))
        lgs = np.asarray(lgs)
        assert np.isfinite(lgs).all()
        for i in range(2):
            ref = oracle(seqs[i], [])
            for t in range(min(K + 1, L - pos[i])):
                what = f"slot {i} row {t}"
                assert np.argmax(lgs[i, t]) == np.argmax(ref[pos[i] + t]), what
                assert_logits_close(lgs[i, t], ref[pos[i] + t], what)

    def test_runs_to_the_windows_end_as_plain_does(self, spec, plain, oracle):
        """prompt + max_new == max_len under speculation: the last
        rounds overshoot the window, and every committed token and
        echoed logit is still the plain engine's and the re-encode's."""
        p = list(range(1, 25))
        n = MAXLEN - len(p)
        res = spec.generate(p, max_new_tokens=n, echo_logits=True)
        assert len(res.tokens) == n
        assert res.tokens == _tokens(plain, p, max_new_tokens=n)
        assert_greedy_echo(p, res, oracle(p, res.tokens))

    def test_seeded_sampling_deterministic(self, spec):
        kw = dict(max_new_tokens=8, temperature=0.9, top_k=5, seed=13)
        assert _tokens(spec, [4, 5], **kw) == _tokens(spec, [4, 5], **kw)

    def test_seed_changes_sampled_text(self, spec):
        runs = {tuple(_tokens(spec, [7, 8], max_new_tokens=8,
                              temperature=1.5, seed=s)) for s in range(4)}
        assert len(runs) > 1

    def test_crash_mid_spec_round_strands_nothing(self, spec, plain):
        prompts = [[1, 2], [3, 4, 5], [6]]
        refs = [_tokens(plain, p, max_new_tokens=6) for p in prompts]
        r0 = _ctr(spec, "retries")
        spec._crash_next = True
        futs = [spec.generate_async(p, max_new_tokens=6) for p in prompts]
        got = [f.result(timeout=60) for f in futs]    # nothing stranded
        assert [r.tokens for r in got] == refs
        assert _ctr(spec, "retries") > r0

    def test_counters_advance(self, spec):
        s0 = _ctr(spec, "spec_steps")
        spec.generate([9, 10], max_new_tokens=6)
        assert _ctr(spec, "spec_steps") > s0
        assert _ctr(spec, "spec_committed") >= _ctr(spec, "spec_steps")
        assert _ctr(spec, "spec_proposed") >= _ctr(spec, "spec_accepted")


class TestInt8KV:
    def test_quantize_roundtrip(self):
        rows = np.array([[1.0, -2.0, 0.5], [0.0, 0.0, 0.0]], np.float32)
        q, sc = _quantize_rows(rows)
        assert np.asarray(q).dtype == np.int8
        deq = np.asarray(q, np.float32) * np.asarray(sc)[..., None]
        assert np.allclose(deq[0], rows[0], atol=2.0 / 127)
        assert np.all(deq[1] == 0.0)          # zero row, scale 1.0

    def test_generate_inside_accuracy_envelope(self, i8, oracle):
        agree = total = 0
        for p in ([1, 2, 3], [5, 6], list(range(7, 19))):
            res = i8.generate(p, max_new_tokens=8)
            ref = oracle(p, res.tokens)
            for j, t in enumerate(res.tokens):
                agree += int(int(np.argmax(ref[len(p) + j - 1])) == t)
                total += 1
        assert agree / total >= 0.80          # envelope, not identity

    def test_pool_bytes_at_least_halved(self, i8, plain):
        f32 = sum(pool_nbytes(a) for a in plain._cache)
        q = sum(pool_nbytes(a) for a in i8._cache)
        assert isinstance(i8._cache[0], QuantPages)
        assert f32 / q >= 2.0

    def test_scrub_zeroes_values_and_scales(self):
        kp, _ = alloc_cache(1, 4, PAGE, 2, 4, kv_dtype="int8")
        kv = np.full((PAGE, 2, 4), 3.0, np.float32)
        kp = write_prefill(kp, 0, np.array([2], np.int32), kv)
        assert np.asarray(kp.q[0, 2]).any() and np.asarray(kp.scale[0, 2]).all()
        kp = scrub_pool(kp, np.array([2], np.int32))
        assert not np.asarray(kp.q[0, 2]).any()
        assert not np.asarray(kp.scale[0, 2]).any()

    def test_write_tokens_overflow_routes_to_scratch(self):
        kp, _ = alloc_cache(1, 3, PAGE, 2, 4)
        assert kp.shape == (1, 3, PAGE, 128)           # one lane-aligned row
        table = np.array([[1, 2]], np.int32)          # 2 pages = 16 rows
        kv = np.ones((1, 4, 2, 4), np.float32)
        out = write_tokens(kp, 0, table, np.array([14], np.int32), kv)
        assert np.asarray(out[0, 2, 6]).any()          # row 14 lands
        assert np.asarray(out[0, 0]).any()             # 16.. -> scratch
        assert not np.asarray(out[0, 1, :6]).any()     # rows < 14 clean


# -- the cached row: [H, d] values in one lane-aligned axis -------------------
# (n_heads, d_head): 2 x 64 and 20 x 64 fill their 128 / 1280 lanes, the
# others are widened per head (2 x 16 -> 2 x 64, 3 x 8 -> 3 x 128, and one
# 576-wide head -> 640 as the latent pool's)
ROWS = [(2, 64), (20, 64), (2, 16), (3, 8), (1, 576)]


def _real_lanes(h, d):
    w = head_lanes(h, d)
    return (np.arange(h * w) % w) < d


class TestPoolRow:
    @pytest.mark.parametrize("h,d", ROWS)
    def test_row_is_a_multiple_of_128_lanes(self, h, d):
        w = head_lanes(h, d)
        assert w >= d and (h * w) % 128 == 0
        assert not any((h * n) % 128 == 0 for n in range(d, w))   # least
        kp, vp = alloc_cache(2, 3, PAGE, h, d)
        assert kp.shape == vp.shape == (2, 3, PAGE, h * w)
        q, _ = alloc_cache(2, 3, PAGE, h, d, kv_dtype="int8")
        assert q.q.shape == (2, 3, PAGE, h * w) and q.q.dtype == np.int8
        assert q.scale.shape == (2, 3, PAGE)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    @pytest.mark.parametrize("writer", ["prefill", "step", "tokens"])
    @pytest.mark.parametrize("h,d", ROWS[:4])
    def test_written_rows_come_back_bit_for_bit(self, h, d, writer,
                                                kv_dtype):
        """What ``write_prefill`` / ``write_step`` / ``write_tokens``
        store, ``gather_layer`` hands back as [S, L, H, d]: the f32
        rows themselves, or for int8 exactly ``q * scale`` of
        ``_quantize_rows``; the padding lanes stay zero, garbage put
        there is never read, and ``scrub_pool`` zeroes them too."""
        rng = np.random.default_rng([h, d])
        kp, _ = alloc_cache(2, 7, PAGE, h, d, kv_dtype=kv_dtype)
        table = np.array([[1, 2, 3], [4, 5, 6]], np.int32)   # 2 slots
        layer, L = 1, 3 * PAGE
        want = np.zeros((2, L, h, d), np.float32)
        if writer == "prefill":
            kv = rng.standard_normal((13, h, d)).astype(np.float32)
            kp = write_prefill(kp, layer, table[1], kv, offset=PAGE)
            want[1, PAGE:PAGE + 13] = kv
        elif writer == "step":
            kv = rng.standard_normal((2, h, d)).astype(np.float32)
            pos = np.array([5, 17], np.int32)
            kp = write_step(kp, layer, table, pos, kv)
            want[0, 5], want[1, 17] = kv
        else:
            kv = rng.standard_normal((2, 4, h, d)).astype(np.float32)
            pos = np.array([6, 11], np.int32)
            kp = write_tokens(kp, layer, table, pos, kv)
            want[0, 6:10], want[1, 11:15] = kv
        if kv_dtype:
            q, sc = _quantize_rows(want)
            want = (np.asarray(q, np.float32)
                    * np.asarray(sc)[..., None, None])
        got = np.asarray(gather_layer(kp, layer, table, (h, d)))
        assert got.shape == (2, L, h, d) and got.dtype == np.float32
        assert np.array_equal(got, want)
        other = np.asarray(gather_layer(kp, 0, table, (h, d)))
        assert not other.any()                        # the layer asked for
        # padding: zero as written, never read, scrubbed with the page
        real = _real_lanes(h, d)
        values = kp.q if kv_dtype else kp
        assert not np.asarray(values)[..., ~real].any()
        junk = values.at[..., ~real].set(7)
        dirty = (QuantPages(junk, kp.scale) if kv_dtype else junk)
        assert np.array_equal(
            np.asarray(gather_layer(dirty, layer, table, (h, d))), want)
        clean = scrub_pool(dirty, np.arange(7, dtype=np.int32))
        assert not any(np.asarray(a).any()
                       for a in (clean if kv_dtype else (clean,)))

    @pytest.mark.parametrize("which", ["plain", "i8"])
    def test_kv_bytes_per_token_is_resident_bytes(self, which, request):
        eng = request.getfixturevalue(which)
        rows = eng.total_pages * eng.program.page_size
        lanes = 2 * head_lanes(2, 16)                 # the toy LM: 128
        per_row = 2 * 2 * {"plain": lanes * 4,        # layers x (K, V)
                           "i8": lanes + 4}[which]
        assert pool_nbytes(eng._cache) == rows * per_row
        assert eng.metrics_snapshot()["kv_bytes_per_token"] == per_row


class TestMetricsAndFlags:
    def test_zero_keys_when_features_off(self, plain):
        snap = plain.metrics_snapshot()
        c = snap["counters"]
        for key in ("prefix_hits", "prefix_misses", "prefix_inserts",
                    "prefix_evictions", "prefix_hit_tokens", "spec_steps",
                    "spec_proposed", "spec_accepted", "spec_committed"):
            assert c[key] == 0
        assert snap["shared_pages"] == 0
        assert snap["accepted_tokens_per_step"] is None
        assert snap["prefix_cache"] is False
        assert snap["speculate_k"] == 0
        assert snap["kv_dtype"] == "float32"

    def test_snapshot_reflects_enabled_features(self, pref, spec, i8):
        assert pref.metrics_snapshot()["prefix_cache"] is True
        assert spec.metrics_snapshot()["speculate_k"] == K
        assert i8.metrics_snapshot()["kv_dtype"] == "int8"

    def test_http_metrics_zero_keys_when_off(self, plain):
        from deeplearning4j_tpu.ui.server import UIServer

        srv = UIServer(port=0).attach_decode_engine(plain).start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics") as r:
                m = json.loads(r.read())
            snap = next(s for s in m["serving"] if "counters" in s)
            assert snap["counters"]["prefix_hits"] == 0
            assert snap["counters"]["spec_steps"] == 0
            assert snap["kv_dtype"] == "float32"
        finally:
            srv.stop()

    def test_warm_bundles_cover_new_executables(self, pref, spec):
        assert any(k[0] == "prefill_at" for k in pref._compiled)
        for key in (("spec_step",), ("propose",), ("spec_accept",),
                    ("draft_step",), ("draft_reset",), ("draft_scrub",)):
            assert key in spec._compiled

    def test_zero_serve_time_compiles(self, pref, spec, i8):
        sizes = [(e, e.compile_cache_size()) for e in (pref, spec, i8)]
        for e, _ in sizes:
            e.generate(PREFIX + [2, 3], max_new_tokens=4)
            e.generate([1], max_new_tokens=3, temperature=0.8, seed=2)
        for e, n0 in sizes:
            assert e.compile_cache_size() == n0

    def test_cli_flags_parse(self):
        from deeplearning4j_tpu.cli import _parse_speculate, build_parser

        p = build_parser()
        a = p.parse_args(["serve", "--model", "m.npz", "--prefix-cache",
                          "--speculate", "d.npz,6", "--kv-dtype", "int8"])
        assert a.prefix_cache and a.kv_dtype == "int8"
        assert _parse_speculate(a.speculate) == ("d.npz", 6)
        a = p.parse_args(["generate", "--model", "m.npz", "--prompt",
                          "hi", "--speculate", "d.npz"])
        assert _parse_speculate(a.speculate) == ("d.npz", 4)
        assert not a.prefix_cache and a.kv_dtype == "float32"
        with pytest.raises(SystemExit):
            _parse_speculate("d.npz,zero")

    def test_draft_shape_mismatch_rejected(self, lm):
        import jax

        mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                          jax.devices()[:1])
        other = ShardedTransformerLM(vocab_size=VOCAB + 2, n_layers=1,
                                     d_model=16, n_heads=2,
                                     max_len=MAXLEN, mesh=mesh, seed=3)
        with pytest.raises(ValueError):
            DecodeEngine(lm, page_size=PAGE, draft_model=other)

    def test_bad_kv_dtype_rejected(self, lm):
        with pytest.raises(ValueError):
            DecodeEngine(lm, page_size=PAGE, kv_dtype="fp8")
