"""Host-overhead elimination: fused multi-step decode + chunked prefill
(docs/SERVING.md "Host-overhead elimination").

The key contracts tested here:
  - fused multi-step decode (``decode_horizon=H``) is BITWISE identical
    to the plain step loop: greedy tokens, seeded temp>0 tokens, echoed
    logits vs the re-encode oracle, EOS and budget stops — horizon
    fusion is an amortization, never an approximation (the counter-based
    fold_in(seed, token_index) key schedule makes this structural)
  - a crash injected mid-horizon strands nothing and the retry
    regenerates identical tokens (host state commits only AFTER the
    fused dispatch returns)
  - one ``serve/decode_step`` span per fused dispatch carrying
    ``tokens=H`` — H tokens never flood the 65536-entry trace ring with
    H spans — and the ring's eviction counter survives the change
  - chunked prefill (``prefill_chunk=N``) stays token-exact across
    chunk-boundary shapes (shorter/exact/non-multiple, non-page-aligned
    budgets) and composes with the radix prefix cache (resume offset =
    matched pages, NOT a chunk boundary) and tenant fair-share lanes
  - fused decode composes with a prefill/decode disaggregated sink
  - the new DecodeMetrics keys are zero-keyed in every snapshot with the
    features off (HTTP /metrics included) and advance when on; the fused
    executable is covered by the warmup bundle

Tokens are held exactly everywhere.  The three tests here that compare
ECHOED LOGITS with the re-encode oracle (``test_greedy_bitwise_identical``,
``test_echo_logits_bitwise``, ``test_interacts_with_prefix_cache``) hold
them to ``_decode_checks.LOGIT_ATOL``, not to equal bits, whatever
their names say: the oracle is a program of another row count (see there).
"""

import json
import time
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest
from _decode_checks import (assert_greedy_echo, queued_together,
                            save_bundle_or_skip)

from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM
from deeplearning4j_tpu.serving import DecodeEngine
from deeplearning4j_tpu.serving.batcher import ContinuousBatcher

VOCAB, MAXLEN, PAGE = 48, 64, 8
H = 4
CHUNK = 16


@pytest.fixture(scope="module")
def lm():
    import jax

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:1])
    return ShardedTransformerLM(vocab_size=VOCAB, n_layers=2, d_model=32,
                                n_heads=2, max_len=MAXLEN, mesh=mesh,
                                seed=11)


def _make(lm, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("default_max_new", 8)
    kw.setdefault("prompt_buckets", (16, 32))
    return DecodeEngine(lm, **kw).load()


@pytest.fixture(scope="module")
def plain(lm):
    eng = _make(lm)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def fused(lm):
    eng = _make(lm, decode_horizon=H)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def chunk(lm):
    eng = _make(lm, prefill_chunk=CHUNK)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def oracle(lm, plain):
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


def _partition_ok(engine) -> bool:
    st = engine._debug_page_state()
    all_ids = st["free"] + st["private"] + st["trie"]
    return (len(all_ids) == len(set(all_ids))
            and sorted(all_ids) == list(range(1, engine.total_pages)))


PROMPTS = ([3, 1, 4], [9, 8, 7, 6, 5], list(range(1, 13)),
           list(range(2, 24)))


# -- construction contracts ------------------------------------------------

class TestConstruction:
    def test_horizon_below_one_rejected(self, lm):
        with pytest.raises(ValueError):
            DecodeEngine(lm, max_slots=3, page_size=PAGE, decode_horizon=0)

    def test_horizon_and_speculation_mutually_exclusive(self, lm):
        with pytest.raises(ValueError):
            DecodeEngine(lm, max_slots=3, page_size=PAGE,
                         decode_horizon=H, draft_model=lm, speculate_k=2)

    def test_chunk_below_one_rejected(self, lm):
        with pytest.raises(ValueError):
            DecodeEngine(lm, max_slots=3, page_size=PAGE, prefill_chunk=0)

    @pytest.mark.parametrize("kw", [
        {"prefill_chunk": CHUNK, "prefill_order": "shortest"},
        {"prefill_order": "nearest_end"}])      # nothing is chunked
    def test_prefill_order_rejected(self, lm, kw):
        with pytest.raises(ValueError, match="prefill_order"):
            DecodeEngine(lm, max_slots=3, page_size=PAGE, **kw)

    def test_chunk_requires_unified_role(self, lm):
        with pytest.raises(ValueError):
            DecodeEngine(lm, max_slots=3, page_size=PAGE,
                         prefill_chunk=CHUNK, role="prefill")

    def test_chunk_and_speculation_mutually_exclusive(self, lm):
        with pytest.raises(ValueError):
            DecodeEngine(lm, max_slots=3, page_size=PAGE,
                         prefill_chunk=CHUNK, draft_model=lm,
                         speculate_k=2)


# -- fused multi-step decode ----------------------------------------------

class TestFusedIdentity:
    def test_greedy_bitwise_identical(self, fused, plain, oracle):
        """Tokens equal the plain loop's exactly; echoed logits within
        ``LOGIT_ATOL`` of the re-encode's, NOT bit for bit: a scan
        and a plain program differ in a logit's last bit on XLA:CPU."""
        for p in PROMPTS:
            ref = plain.generate(p, max_new_tokens=8)
            res = fused.generate(p, max_new_tokens=8, echo_logits=True)
            assert res.tokens == ref.tokens
            assert_greedy_echo(p, res, oracle(p, res.tokens))

    def test_seeded_sampling_identical(self, fused, plain):
        kw = dict(max_new_tokens=8, temperature=0.8, top_k=5, seed=123)
        for p in PROMPTS:
            assert (fused.generate(p, **kw).tokens
                    == plain.generate(p, **kw).tokens)

    @pytest.mark.parametrize("kw", [
        dict(temperature=0.8, top_k=5), dict(temperature=0.8, top_p=0.9),
        dict(temperature=1.2, top_k=7, top_p=0.85),
    ], ids=["top_k", "top_p", "top_k_and_top_p"])
    def test_both_sampler_paths_identical(self, fused, plain, kw):
        """The selection (no top-p in the batch) and the sort (a top-p
        row) each draw in a scan what they draw step by step."""
        for seed, p in enumerate(PROMPTS):
            spec = dict(max_new_tokens=8, seed=40 + seed, **kw)
            assert (fused.generate(p, **spec).tokens
                    == plain.generate(p, **spec).tokens)

    def test_a_top_p_row_cobatched_with_top_k_rows_identical(self, fused,
                                                             plain):
        """While the top-p request decodes its neighbours take the sorted
        path with it, and draw the tokens they draw alone."""
        specs = [dict(temperature=0.8, top_p=0.9, seed=1),
                 dict(temperature=0.8, top_k=5, seed=2), dict()]
        alone = [plain.generate(p, max_new_tokens=8, **kw).tokens
                 for p, kw in zip(PROMPTS, specs)]
        for eng in (fused, plain):
            futs = [eng.generate_async(p, max_new_tokens=8, **kw)
                    for p, kw in zip(PROMPTS, specs)]
            assert [f.result(timeout=120).tokens for f in futs] == alone

    def test_budget_not_a_horizon_multiple(self, fused, plain):
        # 6 = H + 2: the second dispatch must stop mid-horizon and the
        # device overrun (routed to the scratch page) is never recorded
        ref = plain.generate(PROMPTS[1], max_new_tokens=6)
        res = fused.generate(PROMPTS[1], max_new_tokens=6)
        assert res.tokens == ref.tokens and len(res.tokens) == 6
        assert res.finish_reason == ref.finish_reason

    def test_eos_stop_identical(self, lm):
        pl = _make(lm, eos_id=3)
        fu = _make(lm, eos_id=3, decode_horizon=H)
        try:
            for p in PROMPTS:
                ref = pl.generate(p, max_new_tokens=8, temperature=0.9,
                                  seed=7)
                got = fu.generate(p, max_new_tokens=8, temperature=0.9,
                                  seed=7)
                assert got.tokens == ref.tokens
                assert got.finish_reason == ref.finish_reason
        finally:
            pl.shutdown()
            fu.shutdown()

    def test_crash_mid_horizon_retry_identical(self, fused, plain):
        kw = dict(max_new_tokens=8, temperature=0.7, seed=42)
        ref = plain.generate(PROMPTS[2], **kw)
        crashes0 = fused.metrics_snapshot()["counters"]["replica_crashes"]
        fused._crash_next = True
        got = fused.generate(PROMPTS[2], **kw)
        snap = fused.metrics_snapshot()["counters"]
        assert snap["replica_crashes"] == crashes0 + 1
        assert got.tokens == ref.tokens
        # nothing stranded: the engine still serves
        assert len(fused.generate(PROMPTS[0], max_new_tokens=4).tokens) == 4

    def test_zero_serve_time_compiles(self, fused):
        n0 = fused.compile_cache_size()
        fused.generate(PROMPTS[0], max_new_tokens=8)
        assert fused.compile_cache_size() == n0
        assert ("step_multi", H) in fused._compiled

    def test_page_partition_clean_after_traffic(self, fused):
        assert _partition_ok(fused)


class TestStepAheadAgainstFused:
    def test_cobatched_mix_with_slots_refilling_matches_the_fused_loop(
            self, plain, fused):
        """The plain loop (a step in flight, its tokens fed on the
        device) against the fused horizon, a path of its own: greedy
        requests that echo their logits and top-k requests of different
        lengths, twice as many as slots, so that slots finish and
        refill mid-run beside slots that keep stepping."""
        from _decode_checks import assert_logits_close

        reqs = [(PROMPTS[i % 4], dict(max_new_tokens=5 + 3 * i,
                                      echo_logits=True) if i % 2 == 0 else
                 dict(max_new_tokens=4 + 2 * i, temperature=0.8, top_k=7,
                      seed=100 + i)) for i in range(7)]
        a0 = plain.metrics.counter_value("steps_ahead")
        got, ref = ([f.result(timeout=120) for f in
                     [e.generate_async(p, **kw) for p, kw in reqs]]
                    for e in (plain, fused))
        assert plain.metrics.counter_value("steps_ahead") > a0
        for (p, kw), g, r in zip(reqs, got, ref):
            assert g.tokens == r.tokens and len(g.tokens) == kw[
                "max_new_tokens"]
            assert g.finish_reason == r.finish_reason
            if kw.get("echo_logits"):
                assert_logits_close(g.logits, r.logits, f"prompt {p}")
        assert _partition_ok(plain)


class TestFusedSpans:
    def test_one_span_per_fused_dispatch_with_tokens_arg(self, fused):
        rec = obs_trace.TraceRecorder()
        old = obs_trace.set_recorder(rec)
        try:
            fused.generate(PROMPTS[0], max_new_tokens=8)
        finally:
            obs_trace.set_recorder(old)
        spans = [e for e in rec.export()["traceEvents"]
                 if e.get("name") == "serve/decode_step"]
        # token 1 comes from the prefill dispatch; the remaining 7 take
        # exactly two fused dispatches at H=4 — two spans, NOT seven
        assert len(spans) == 2
        assert all(e["args"]["tokens"] == H for e in spans)
        assert all(e["args"]["sample_ms"] == 0.0 for e in spans)

    def test_plain_span_carries_tokens_one(self, plain):
        rec = obs_trace.TraceRecorder()
        old = obs_trace.set_recorder(rec)
        try:
            plain.generate(PROMPTS[0], max_new_tokens=4)
        finally:
            obs_trace.set_recorder(old)
        spans = [e for e in rec.export()["traceEvents"]
                 if e.get("name") == "serve/decode_step"]
        # token 1 comes from the prefill dispatch: 3 steps for 4 tokens
        assert len(spans) == 3
        assert all(e["args"]["tokens"] == 1 for e in spans)

    def test_ring_eviction_counter_regression(self):
        # the 65536-entry default is the flooding headroom the fused
        # span consolidation protects; the dropped counter must count
        # every evicted event and survive export
        assert obs_trace.DEFAULT_CAPACITY == 65536
        rec = obs_trace.TraceRecorder(capacity=8)
        old = obs_trace.set_recorder(rec)
        try:
            for i in range(20):
                obs_trace.complete_at("serve/decode_step", 0.0, 1e-4,
                                      cat="serve", tokens=1, i=i)
        finally:
            obs_trace.set_recorder(old)
        assert rec.dropped == 12
        exp = rec.export()
        assert exp["metadata"]["dropped"] == 12
        assert exp["metadata"]["events"] == 8


# -- fused steps with chunked prefill and echoed logits -----------------------

class TestFusedChunkedEcho:
    """The fused step queues the iteration's chunk behind its own
    dispatch and copies echoed logits out one dispatch late: nothing a
    client sees may change, and no answer may come back before its rows."""

    CLOCK_OFFSET = [0.0]

    @classmethod
    def _clock(cls):
        return time.monotonic() + cls.CLOCK_OFFSET[0]

    @pytest.fixture(scope="class")
    def both(self, lm):
        engines = [_make(lm, prefill_chunk=CHUNK, decode_horizon=h,
                         clock=self._clock) for h in (1, H)]
        yield engines
        for e in engines:
            e.shutdown()

    @staticmethod
    def _requests():
        out = []
        for k, (n, new) in enumerate([(5, 9), (30, 6), (21, 1), (16, 11),
                                      (3, 2), (27, 7), (12, 5)]):
            p = [1 + (i * (3 + k)) % (VOCAB - 1) for i in range(n)]
            kw = {"max_new_tokens": new, "echo_logits": k % 3 != 2}
            if k % 2:
                kw.update(temperature=0.8, top_k=5, seed=k)
            out.append((p, kw))
        return out

    def test_concurrent_answers_identical_to_step_by_step(self, both):
        got = []
        for eng in both:
            n0 = eng.compile_cache_size()
            futs = [eng.generate_async(p, **kw) for p, kw in self._requests()]
            got.append([f.result(timeout=120) for f in futs])
            assert eng.compile_cache_size() == n0
        for (p, kw), one, four in zip(self._requests(), *got):
            assert four.tokens == one.tokens
            assert four.finish_reason == one.finish_reason
            if kw["echo_logits"]:
                assert four.logits.shape == (len(four.tokens), VOCAB)
                np.testing.assert_allclose(four.logits, one.logits,
                                           rtol=1e-5, atol=1e-6)
            else:
                assert four.logits is None
        assert _partition_ok(both[1])

    def test_chunk_is_queued_inside_the_step_and_waited_for_after_it(
            self, both):
        eng = both[1]
        c0 = eng.metrics_snapshot()["counters"]
        rec = obs_trace.TraceRecorder()
        old = obs_trace.set_recorder(rec)
        try:
            first = eng.generate_async([1, 2, 3], max_new_tokens=30)
            while (eng.metrics_snapshot()["counters"]["tokens_out"]
                   == c0["tokens_out"]):
                time.sleep(0.0005)
            eng.generate(list(range(1, 31)), max_new_tokens=2)  # 2 chunks
            first.result(timeout=120)
        finally:
            obs_trace.set_recorder(old)
        ev = [e for e in rec.export()["traceEvents"] if e.get("ph") == "X"]

        def within(inner, outer):
            return (outer["ts"] <= inner["ts"] and inner["ts"] + inner["dur"]
                    <= outer["ts"] + outer["dur"])
        steps = [e for e in ev if e["name"] == "serve/decode_step"]
        queued = [e for e in ev if e["name"] == "serve/prefill_dispatch"]
        assert queued, "no chunk was queued behind a fused dispatch"
        assert all(any(within(q, s) for s in steps) for q in queued)
        # every chunk is read back, after a step and never inside one
        waits = [e for e in ev if e["name"] == "serve/prefill"
                 and "offset" in e["args"]]
        assert len(waits) >= len(queued)
        assert not any(within(w, s) for w in waits for s in steps)
        assert eng._chunk_inflight == []
        c = eng.metrics_snapshot()["counters"]
        assert c["prefill_chunks"] - c0["prefill_chunks"] == len(waits) == 3

    def test_answers_cut_at_the_deadline_come_back_with_every_row(
            self, both):
        eng, full_eng = both[1], both[0]
        p = [2, 2, 7]
        whole = full_eng.generate(p, max_new_tokens=40, echo_logits=True)
        t0 = eng.metrics_snapshot()["counters"]["tokens_out"]
        fut = eng.generate_async(p, max_new_tokens=40, echo_logits=True,
                                 slo_ms=3_600_000.0)
        limit = time.monotonic() + 30
        while eng.metrics_snapshot()["counters"]["tokens_out"] <= t0:
            assert time.monotonic() < limit, "prefill never landed"
            time.sleep(0.0005)
        try:
            self.CLOCK_OFFSET[0] = 7200.0
            res = fut.result(timeout=60)
        finally:
            self.CLOCK_OFFSET[0] = 0.0
        assert res.finish_reason == "deadline"
        n = len(res.tokens)
        assert 1 <= n < 40 and res.logits.shape == (n, VOCAB)
        assert res.tokens == whole.tokens[:n]
        np.testing.assert_allclose(res.logits, whole.logits[:n],
                                   rtol=1e-5, atol=1e-6)

    def test_shutdown_hands_over_what_had_finished(self, lm):
        eng = _make(lm, prefill_chunk=CHUNK, decode_horizon=H)
        futs = [eng.generate_async([1 + k, 5], max_new_tokens=3 + k,
                                   echo_logits=True) for k in range(3)]
        done = [f.result(timeout=120) for f in futs]
        eng.shutdown()
        assert all(len(r.logits) == len(r.tokens) for r in done)
        assert eng._echo_lgs is None and not eng._echo_results


# -- a chunk a turn for each slot mid-prefill --------------------------------

class TestChunkBudget:
    """A fused turn queues a chunk for EACH slot mid-prefill behind its
    dispatch (``_chunk_budget``), where it used to queue one whatever
    waited: only the order of the programs on the device changes, so
    every answer keeps its bits, and no turn takes more chunks than
    slots were mid-prefill at its start."""

    SLOTS = 4
    CLOCK_OFFSET = [0.0]

    @classmethod
    def _clock(cls):
        return time.monotonic() + cls.CLOCK_OFFSET[0]

    @classmethod
    def _engine(cls, lm, order, horizon=H, one_a_turn=False):
        eng = _make(lm, max_slots=cls.SLOTS, prefill_chunk=CHUNK,
                    decode_horizon=horizon, prefill_order=order,
                    clock=cls._clock)
        if one_a_turn:          # the schedule before: one chunk whatever waits
            many = eng._chunk_budget
            eng._chunk_budget = lambda: min(1, many())
        return eng

    @staticmethod
    def _long_prompts():
        """Three prompts of three or four chunks each."""
        return [[1 + (i * k) % (VOCAB - 1) for i in range(n)]
                for k, n in ((3, 50), (5, 41), (7, 47))]

    @classmethod
    def _serve(cls, eng, before_prompts=None):
        """One request that decodes throughout and, once it does, three
        long prompts at once; the answers in that order."""
        t0 = eng.metrics.counter_value("tokens_out")
        first = eng.generate_async([2, 7, 1], max_new_tokens=56,
                                   echo_logits=True)
        limit = time.monotonic() + 60
        while eng.metrics.counter_value("tokens_out") == t0:
            assert time.monotonic() < limit, "the first prefill never landed"
            time.sleep(0.0005)
        if before_prompts is not None:
            before_prompts()
        with eng._lock:         # the loop's next turn finds all three queued
            futs = [eng.generate_async(p, slo_ms=3_600_000.0, **kw)
                    for p, kw in zip(cls._long_prompts(), (
                        dict(max_new_tokens=9, echo_logits=True),
                        dict(max_new_tokens=7, temperature=0.8, top_k=5,
                             seed=3),
                        dict(max_new_tokens=12, echo_logits=True)))]
        return [first] + futs

    @staticmethod
    def _turns(rec):
        """(the ``serve/decode_step`` span, the ``serve/prefill_dispatch``
        spans inside it) of every fused turn recorded."""
        ev = [e for e in rec.export()["traceEvents"] if e.get("ph") == "X"]
        steps = [e for e in ev if e["name"] == "serve/decode_step"]
        queued = [e for e in ev if e["name"] == "serve/prefill_dispatch"]
        inside = lambda q, s: (s["ts"] <= q["ts"] and q["ts"] + q["dur"]
                               <= s["ts"] + s["dur"])
        turns = [(s, [q for q in queued if inside(q, s)]) for s in steps]
        assert sum(len(qs) for _, qs in turns) == len(queued)
        return turns, ev

    @pytest.mark.parametrize("order", ["round_robin", "nearest_end"])
    def test_answers_keep_their_bits_and_no_turn_passes_its_bound(
            self, lm, order):
        got = {}
        for name, kw in (("many", {}), ("one", {"one_a_turn": True}),
                         ("steps", {"horizon": 1})):
            eng = self._engine(lm, order, **kw)
            rec = obs_trace.TraceRecorder()
            old = obs_trace.set_recorder(rec)
            try:
                n0 = eng.compile_cache_size()
                got[name] = [f.result(timeout=120) for f in self._serve(eng)]
                assert eng.compile_cache_size() == n0
                assert eng._chunk_inflight == [] and _partition_ok(eng)
                snap = eng.metrics_snapshot()
            finally:
                obs_trace.set_recorder(old)
                eng.shutdown()
            if name == "steps":
                continue
            turns, ev = self._turns(rec)
            c = snap["counters"]
            chunks = [s["args"].get("chunks", 0) for s, _ in turns]
            # (b) as many dispatches inside the turn's step as its span
            # says, each read back after a step and never inside one
            assert chunks == [len(qs) for _, qs in turns]
            waits = [e for e in ev if e["name"] == "serve/prefill"
                     and "offset" in e["args"]]
            assert len(waits) == c["prefill_chunks"] == 1 + 4 + 3 + 3
            for w in waits:
                assert not any(s["ts"] <= w["ts"] and w["ts"] + w["dur"]
                               <= s["ts"] + s["dur"] for s, _ in turns)
            # (c) never more chunks than slots were mid-prefill, and with
            # a slot decoding that is fewer than the engine has slots
            mid = [s["args"]["mid_prefill"] for s, _ in turns]
            assert all(k <= p <= self.SLOTS - 1 for k, p in zip(chunks, mid))
            assert snap["chunk_turn_max"] >= max(chunks)
            assert c["chunk_turns"] >= sum(k >= 1 for k in chunks)
            multi = sum(k > 1 for k in chunks)
            if name == "one":       # what the parent queued
                assert max(chunks) == 1 and c["chunk_turns_multi"] == 0
                assert snap["chunk_turn_max"] == 1
            else:
                # no deadline passes here, so every waiting prompt got
                # its chunk: three prompts wait beside the one that decodes
                assert chunks == mid and max(chunks) == 3
                assert c["chunk_turns_multi"] == multi >= 2
                assert snap["chunk_turn_max"] == 3
        # (a) the same bits as under one chunk a turn; the step-by-step
        # engine's tokens, and its logits as close as two horizons come
        for many, one, steps in zip(got["many"], got["one"], got["steps"]):
            assert many.tokens == one.tokens == steps.tokens
            assert many.finish_reason == one.finish_reason == "max_tokens"
            if one.logits is None:
                assert many.logits is None
                continue
            np.testing.assert_array_equal(many.logits, one.logits)
            np.testing.assert_allclose(many.logits, steps.logits,
                                       rtol=1e-5, atol=1e-6)

    def _at_pick(self, eng, n, then):
        """Call ``then`` on the loop thread right before chunk pick ``n``
        (the first pick of the third turn with prompts: two chunks of
        the turn before are still on the device then)."""
        picks, pick = [0], eng._chunk_pick

        def counted():
            picks[0] += 1
            if picks[0] == n:
                then()
            return pick()
        eng._chunk_pick = counted

    def test_a_deadline_that_passes_with_chunks_in_flight_gives_them_up(
            self, lm):
        from deeplearning4j_tpu.serving.batcher import DeadlineExceededError

        ref = self._engine(lm, "round_robin")
        try:
            whole = self._serve(ref)[0].result(timeout=120)
        finally:
            ref.shutdown()
        eng = self._engine(lm, "round_robin")
        try:
            seen = []

            def later():
                seen.append(len(eng._chunk_inflight))
                self.CLOCK_OFFSET[0] = 7200.0
            futs = self._serve(eng, lambda: self._at_pick(eng, 4, later))
            for f in futs[1:]:
                with pytest.raises(DeadlineExceededError):
                    f.result(timeout=120)
            # the request beside them states the engine's own deadline,
            # which the jump passed too: cut, with every row it had
            first = futs[0].result(timeout=120)
            assert seen == [2] and first.finish_reason == "deadline"
            n = len(first.tokens)
            assert 1 <= n < 56 and first.tokens == whole.tokens[:n]
            np.testing.assert_array_equal(first.logits, whole.logits[:n])
            assert eng._chunk_inflight == [] and _partition_ok(eng)
        finally:
            self.CLOCK_OFFSET[0] = 0.0
            eng.shutdown()

    def test_a_crash_with_chunks_in_flight_retries_to_the_same_bits(
            self, lm):
        ref = self._engine(lm, "nearest_end")
        try:
            want = [f.result(timeout=120) for f in self._serve(ref)]
        finally:
            ref.shutdown()
        eng = self._engine(lm, "nearest_end")
        try:
            seen = []

            def crash():
                seen.append(len(eng._chunk_inflight))
                eng._crash_next = True      # the next turn's dispatch
            futs = self._serve(eng, lambda: self._at_pick(eng, 4, crash))
            got = [f.result(timeout=120) for f in futs]
            assert seen == [2]
            assert eng.metrics.counter_value("replica_crashes") == 1
            for g, w in zip(got, want):
                assert g.tokens == w.tokens
                if w.logits is not None:
                    np.testing.assert_array_equal(g.logits, w.logits)
            assert eng._chunk_inflight == [] and _partition_ok(eng)
        finally:
            eng.shutdown()


# -- chunked prefill -------------------------------------------------------

class TestChunkedPrefill:
    @pytest.mark.parametrize("n", [5, CHUNK, 21, 30])
    def test_tokens_identical_across_chunk_shapes(self, chunk, plain, n):
        # below / exactly / just past / nearly twice the chunk budget
        p = [1 + (i * 7) % (VOCAB - 1) for i in range(n)]
        assert (chunk.generate(p, max_new_tokens=8).tokens
                == plain.generate(p, max_new_tokens=8).tokens)

    def test_echo_logits_bitwise(self, chunk, oracle):
        """Echoed logits within ``LOGIT_ATOL`` of the re-encode's
        and each token its row's argmax, NOT bit for bit: a 16-row chunk
        and the whole window differ in a logit's last bit on XLA:CPU."""
        p = list(range(1, 31))          # 2 chunks: 16 + 14
        res = chunk.generate(p, max_new_tokens=6, echo_logits=True)
        assert_greedy_echo(p, res, oracle(p, res.tokens))

    def test_counters_advance(self, chunk):
        c0 = chunk.metrics_snapshot()["counters"]
        chunk.generate(list(range(1, 31)), max_new_tokens=4)   # 2 chunks
        chunk.generate([4, 2], max_new_tokens=4)               # 1 chunk
        c1 = chunk.metrics_snapshot()["counters"]
        assert c1["chunked_prefills"] == c0["chunked_prefills"] + 1
        assert c1["prefill_chunks"] == c0["prefill_chunks"] + 3
        assert c1["prefills"] == c0["prefills"] + 2

    def test_non_page_aligned_chunk_budget(self, lm, plain):
        # 12 is not a multiple of page_size=8: chunk boundaries land
        # mid-page and the offsets must still be token-exact
        eng = _make(lm, prefill_chunk=12, prompt_buckets=(16, 32))
        try:
            for n in (11, 24, 30):
                p = [1 + (i * 5) % (VOCAB - 1) for i in range(n)]
                assert (eng.generate(p, max_new_tokens=8).tokens
                        == plain.generate(p, max_new_tokens=8).tokens)
        finally:
            eng.shutdown()

    def test_nearest_end_serves_what_round_robin_serves(self, lm, plain):
        """The order of the chunks is no part of an answer: three prompts
        of two, one and two chunks at once, each the plain engine's
        tokens."""
        eng = _make(lm, prefill_chunk=CHUNK, prefill_order="nearest_end")
        try:
            assert eng.metrics_snapshot()["prefill_order"] == "nearest_end"
            prompts = [[1 + (i * k) % (VOCAB - 1) for i in range(n)]
                       for k, n in ((3, 32), (5, 9), (7, 21))]
            futs = [eng.generate_async(p, max_new_tokens=6) for p in prompts]
            for p, f in zip(prompts, futs):
                assert (f.result(timeout=120).tokens
                        == plain.generate(p, max_new_tokens=6).tokens)
            assert _partition_ok(eng)
        finally:
            eng.shutdown()

    def test_nearest_end_picks_the_least_left_and_lets_none_wait_for_ever(
            self, chunk):
        """``_nearest_end`` over slots mid-prefill: the fewest prompt
        tokens left first (the lowest slot among equals); a prompt passed
        over ``PASSED_OVER_ROUNDS * max_slots`` picks in a row goes
        first, once, and waits again."""
        from types import SimpleNamespace

        from deeplearning4j_tpu.serving.decode import PASSED_OVER_ROUNDS

        slots = [SimpleNamespace(n_prompt=n, n_prefilled=0, passed_over=0)
                 for n in (500, 40, 40)]
        eng = SimpleNamespace(_slots=slots, max_slots=3)
        pick = lambda pending: DecodeEngine._nearest_end(eng, pending)
        assert pick([0, 1, 2]) == 1
        assert [s.passed_over for s in slots] == [1, 0, 1]
        slots[1].n_prefilled = 16
        assert pick([0, 1, 2]) == 1         # 24 left against 40 and 500
        slots[1].n_prefilled = None         # its last chunk: it decodes
        assert pick([0, 2]) == 2 and slots[0].passed_over == 3
        limit = PASSED_OVER_ROUNDS * 3
        for _ in range(limit - 3):          # new short prompts keep coming
            slots[2].n_prefilled = 0
            assert pick([0, 2]) == 2
        assert slots[0].passed_over == limit
        assert pick([0, 2]) == 0 and slots[0].passed_over == 0
        assert pick([0, 2]) == 2

    def test_interacts_with_prefix_cache(self, lm, plain, oracle):
        # a prefix hit resumes the chunk walk at matched-pages (24 =
        # 3 pages), which is NOT a chunk boundary (16) — the suffix
        # chunks must pick up exactly there: tokens equal, echoed logits
        # within LOGIT_ATOL of the re-encode's
        eng = _make(lm, prefill_chunk=CHUNK, prefix_cache=True,
                    max_slots=3)
        try:
            shared = [1 + (i * 3) % (VOCAB - 1) for i in range(24)]
            eng.generate(shared + [7, 8, 9], max_new_tokens=4)  # seeds trie
            hits0 = eng.metrics_snapshot()["counters"]["prefix_hits"]
            p = shared + [5, 6, 7, 8, 9, 10]
            res = eng.generate(p, max_new_tokens=6, echo_logits=True)
            assert eng.metrics_snapshot()["counters"]["prefix_hits"] \
                == hits0 + 1
            assert res.tokens == plain.generate(p,
                                                max_new_tokens=6).tokens
            assert_greedy_echo(p, res, oracle(p, res.tokens))
            assert _partition_ok(eng)
        finally:
            eng.shutdown()

    def test_interacts_with_tenant_lanes(self, lm, plain):
        # a wall of long prompts from one tenant must not starve the
        # other lane: token-budget admission still rotates lanes
        eng = _make(lm, prefill_chunk=CHUNK, max_slots=3)
        try:
            long_p = list(range(1, 33))
            short_p = [9, 4, 2]
            futs = ([eng.generate_async(long_p, max_new_tokens=4,
                                        tenant="waller")
                     for _ in range(3)]
                    + [eng.generate_async(short_p, max_new_tokens=4,
                                          tenant="reader")
                       for _ in range(3)])
            res = [f.result(timeout=120) for f in futs]
            assert all(len(r.tokens) == 4 for r in res)
            ref_long = plain.generate(long_p, max_new_tokens=4).tokens
            ref_short = plain.generate(short_p, max_new_tokens=4).tokens
            assert all(r.tokens == ref_long for r in res[:3])
            assert all(r.tokens == ref_short for r in res[3:])
        finally:
            eng.shutdown()

    def test_admit_token_budget_rule(self):
        # head always admitted; admission stops before the budget is
        # exceeded; fair-share lane rotation still interleaves tenants
        b = ContinuousBatcher(max_batch=8, slo_ms=1000, max_queue=100)
        for n, tenant in ((20, "a"), (6, "b"), (6, "b"), (20, "a")):
            b.submit_request(SimpleNamespace(prompt=list(range(n))),
                             tenant=tenant)
        rounds = [[len(r.payload.prompt)
                   for r in b.admit(8, token_budget=16)]
                  for _ in range(4)]
        # round 1: a's 20-token head exceeds the budget ALONE — admitted
        # anyway (an oversized prompt cannot be split at admission).
        # round 2: b's 6 fits, then rotation offers a's 20 — would blow
        # the budget, stop.  rounds 3/4 drain the rest the same way.
        assert rounds == [[20], [6], [20], [6]]
        # same-lane small prompts pack under one budget
        for n in (6, 6, 20):
            b.submit_request(SimpleNamespace(prompt=list(range(n))))
        packed = b.admit(8, token_budget=16)
        assert [len(r.payload.prompt) for r in packed] == [6, 6]
        b.close(fail_pending=True)

    def test_admit_unbudgeted_unchanged(self):
        b = ContinuousBatcher(max_batch=8, slo_ms=1000, max_queue=100)
        for n in (20, 20, 20):
            b.submit_request(SimpleNamespace(prompt=list(range(n))))
        out = b.admit(8)
        assert len(out) == 3
        for r in out:
            r.future.set_result(None)
        b.close()


# -- the token budget of an admission round --------------------------------

def _prompt(n, k=7):
    return [1 + (i * k) % (VOCAB - 1) for i in range(n)]


def _queued(eng, lengths, budget_of=None, **kw):
    """Seeded prompts of ``lengths`` through ``queued_together``."""
    return queued_together(
        eng, [(_prompt(n, 3 + 2 * j),
               {"max_new_tokens": 6, "seed": j, **kw})
              for j, n in enumerate(lengths)], budget_of, timeout=120)


@pytest.fixture(scope="module", params=[1, H], ids=["plain", "fused"])
def budgeted(lm, request):
    """Three slots read in chunks, under the plain loop and the fused."""
    eng = _make(lm, prefill_chunk=CHUNK, decode_horizon=request.param)
    yield eng
    eng.shutdown()


class TestAdmissionBudget:
    """A round takes in one chunk's worth of prompt tokens for EACH FREE
    SLOT: short prompts fill every free slot at once, a long head still
    ends the round, and with one slot free the round is what it was."""

    # (3, 30, 9): a two-chunk prompt between short ones fits as well,
    # 42 of 3 x 16 tokens
    @pytest.mark.parametrize("lengths", [(10, 16, 5), (16, 16, 16),
                                         (3, 30, 9)])
    def test_short_prompts_fill_every_free_slot_in_one_round(self, budgeted,
                                                             lengths):
        res, rounds, bound = _queued(budgeted, lengths)
        assert rounds == [(3, 3 * CHUNK, list(lengths))] and bound == 0
        assert all(len(r.tokens) == 6 for r in res)
        assert _partition_ok(budgeted)

    def test_one_free_slot_takes_the_head_whatever_its_length(self, lm):
        eng = _make(lm, prefill_chunk=CHUNK, decode_horizon=H, max_slots=1)
        try:
            for head in (5, 16, 40):
                _, rounds, bound = _queued(eng, (head, 7))
                assert rounds == [(1, CHUNK, [head]), (1, CHUNK, [7])]
                assert bound == 0       # the SLOT ended both rounds
        finally:
            eng.shutdown()

    @pytest.mark.parametrize("horizon", [1, H])
    def test_a_long_head_ends_the_round_and_the_next_waits_a_turn(
            self, lm, horizon):
        eng = _make(lm, prefill_chunk=CHUNK, decode_horizon=horizon,
                    max_slots=2)
        rec = obs_trace.enable_tracing(capacity=65536)
        try:
            # a head of three chunks on two free slots: alone, counted;
            # then one slot is free and its round is the slot's
            _, rounds, bound = _queued(eng, (40, 6, 7))
            events = rec.events()
        finally:
            obs_trace.disable_tracing()
            eng.shutdown()
        assert rounds == [(2, 2 * CHUNK, [40]), (1, CHUNK, [6]),
                          (1, CHUNK, [7])]                  # FIFO kept
        assert bound == 1
        turns = sorted((e for e in events if e["name"] == "serve/iteration"),
                       key=lambda e: e["ts"])
        assert [(t["args"]["free_slots"], t["args"]["admitted"])
                for t in turns if t["args"].get("admit_budget_bound")] \
            == [(2, 1)]
        assert turns[0]["args"].get("admit_budget_bound")
        # the request behind the head is taken in by the very next turn
        second = sorted(e["ts"] for e in events
                        if e["name"] == "serve/admit")[1]
        assert turns[1]["ts"] <= second <= turns[1]["ts"] + turns[1]["dur"]

    @pytest.mark.parametrize("sampling", [
        {}, {"temperature": 0.8, "top_k": 5}], ids=["greedy", "sampled"])
    def test_the_schedule_changes_when_a_request_runs_not_what_it_says(
            self, budgeted, plain, sampling):
        """Requests admitted together return the tokens they return
        admitted one chunk's worth a round, and the tokens the plain
        engine gives each alone."""
        lengths = (12, 5, 16, 21, 9, 3)
        now, rounds, _ = _queued(budgeted, lengths, **sampling)
        then, before, _ = _queued(budgeted, lengths,
                                  budget_of=lambda limit: CHUNK, **sampling)
        assert rounds[0] == (3, 3 * CHUNK, [12, 5, 16])
        assert before[0] == (3, CHUNK, [12])
        assert [r.tokens for r in now] == [r.tokens for r in then]
        for j, (n, r) in enumerate(zip(lengths, now)):
            alone = plain.generate(_prompt(n, 3 + 2 * j), max_new_tokens=6,
                                   seed=j, **sampling)
            assert r.tokens == alone.tokens

    @pytest.mark.parametrize("which", ["plain", "fused"])
    def test_an_engine_without_a_chunk_passes_no_budget(self, request,
                                                        which):
        _, rounds, bound = _queued(request.getfixturevalue(which),
                                   (30, 30, 30))
        assert rounds == [(3, None, [30, 30, 30])] and bound == 0


# -- composition with disaggregation --------------------------------------

class TestFusedDisagg:
    def test_fused_decode_role_sink_identical(self, lm, plain):
        pre = _make(lm, role="prefill")
        dec = _make(lm, role="decode", decode_horizon=H)
        try:
            for i, p in enumerate(PROMPTS[:3]):
                ref = plain.generate(p, max_new_tokens=8, seed=i)
                h = pre.generate(p, max_new_tokens=8, seed=i)
                got = dec.continue_async(h).result(timeout=120)
                assert got.tokens == ref.tokens
            kw = dict(max_new_tokens=8, temperature=0.8, top_k=5,
                      seed=123)
            ref = plain.generate(PROMPTS[2], **kw)
            h = pre.generate(PROMPTS[2], **kw)
            got = dec.continue_async(h).result(timeout=120)
            assert got.tokens == ref.tokens
            assert dec.metrics_snapshot()["counters"]["fused_dispatches"] \
                > 0
        finally:
            pre.shutdown()
            dec.shutdown()


# -- metrics + warmup bundle ----------------------------------------------

class TestMetricsAndBundle:
    def test_zero_keys_when_features_off(self, plain):
        snap = plain.metrics_snapshot()
        c = snap["counters"]
        for key in ("fused_dispatches", "tokens_per_dispatch",
                    "chunked_prefills", "prefill_chunks"):
            assert c[key] == 0
        assert snap["decode_horizon"] == 1
        assert snap["prefill_chunk"] is None

    def test_http_metrics_zero_keys_when_off(self, plain):
        from deeplearning4j_tpu.ui.server import UIServer

        srv = UIServer(port=0).attach_decode_engine(plain).start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/metrics") as r:
                m = json.loads(r.read())
            snap = next(s for s in m["serving"] if "counters" in s)
            for key in ("fused_dispatches", "tokens_per_dispatch",
                        "chunked_prefills", "prefill_chunks"):
                assert snap["counters"][key] == 0
            assert snap["decode_horizon"] == 1
            assert snap["prefill_chunk"] is None
        finally:
            srv.stop()

    def test_counters_advance_when_on(self, fused):
        c0 = fused.metrics_snapshot()["counters"]
        fused.generate(PROMPTS[0], max_new_tokens=8)
        snap = fused.metrics_snapshot()
        c1 = snap["counters"]
        assert c1["fused_dispatches"] == c0["fused_dispatches"] + 2
        # token 1 comes from prefill: the two fused dispatches commit 7
        assert c1["tokens_per_dispatch"] == c0["tokens_per_dispatch"] + 7
        assert snap["decode_horizon"] == H

    def test_sorted_steps_count_each_step_of_a_top_p_dispatch(self, fused):
        """``sampler_sorted_steps``: nothing for greedy and top-k
        requests; every step of every dispatch (H a dispatch) while a
        sampled top-p request decodes; nothing for a GREEDY request that
        names a top-p."""
        def moved(**kw):
            c0 = fused.metrics_snapshot()["counters"]
            fused.generate(PROMPTS[0], max_new_tokens=8, **kw)
            c1 = fused.metrics_snapshot()["counters"]
            return (c1["sampler_sorted_steps"] - c0["sampler_sorted_steps"],
                    c1["fused_dispatches"] - c0["fused_dispatches"])

        assert moved() == (0, 2)
        assert moved(temperature=0.8, top_k=5, seed=3) == (0, 2)
        assert moved(top_p=0.9) == (0, 2)
        assert moved(temperature=0.8, top_p=0.9, seed=3) == (2 * H, 2)

    def test_warm_bundle_covers_fused_executable(self, lm, fused,
                                                 tmp_path):
        path = str(tmp_path / "fused.warmup")
        assert ("step_multi", H) in fused._compiled
        save_bundle_or_skip(fused, path)
        warmed = DecodeEngine(lm, max_slots=3, page_size=PAGE,
                              default_max_new=8, prompt_buckets=(16, 32),
                              decode_horizon=H).load(warm_bundle=path)
        try:
            assert warmed.metrics_snapshot()["counters"]["bundle_misses"] \
                == 0
            ref = fused.generate(PROMPTS[0], max_new_tokens=8).tokens
            assert warmed.generate(PROMPTS[0],
                                   max_new_tokens=8).tokens == ref
        finally:
            warmed.shutdown()
