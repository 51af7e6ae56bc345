"""Autoregressive decode engine: paged KV-cache, prefill/decode split,
continuous batching (docs/SERVING.md "Autoregressive decode").

The key contracts tested here:
  - seeded sampling is deterministic: same (prompt, seed, knobs) ->
    same tokens, regardless of co-batched traffic or a crash-retry
  - greedy decode tokens equal, and echoed logits lie within
    ``_decode_checks.LOGIT_ATOL`` of, re-encoding the full sequence (the
    paged cache is exact, not approximate; the step sums over the pages
    a slot holds and the re-encode over the window: ROADMAP C1)
  - early EOS frees cache pages immediately and the recycled pages
    serve the next request uncorrupted
  - hot-swap mid-decode never mixes versions: in-flight requests
    finish on the version that prefilled them
  - zero XLA compiles at serve time after load() (AOT warmup)
  - every submitted future resolves (crash retry, poison isolation,
    deadline, shutdown) — never a hang
  - loading a decode engine does not perturb the wrapped network's
    one-shot output path (bitwise regression pin)
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
from _decode_checks import assert_greedy_echo

from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM
from deeplearning4j_tpu.serving import (
    ContinuousBatcher, DeadlineExceededError, DecodeEngine,
    OverloadedError, PoisonInputError,
)

VOCAB, MAXLEN = 48, 32
#: test-controlled clock shared by the module engine: bumping the
#: offset expires deadlines deterministically mid-decode
CLOCK_OFFSET = [0.0]


def _clock():
    return time.monotonic() + CLOCK_OFFSET[0]


@pytest.fixture(scope="module")
def lm():
    import jax

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:1])
    return ShardedTransformerLM(vocab_size=VOCAB, n_layers=2, d_model=32,
                                n_heads=2, max_len=MAXLEN, mesh=mesh, seed=11)


@pytest.fixture(scope="module")
def engine(lm):
    eng = DecodeEngine(lm, max_slots=3, page_size=8, default_max_new=8,
                       clock=_clock).load()
    yield eng
    eng.shutdown()


def _tokens(engine, prompt, **kw):
    return engine.generate(prompt, **kw).tokens


def _ctr(engine, key):
    return engine.metrics.snapshot()["counters"][key]


def _ctr_reaches(engine, key, want, timeout=30.0):
    """A counter the loop moves a turn AFTER a future resolved (the
    step in flight is read then)."""
    deadline = time.monotonic() + timeout
    while _ctr(engine, key) < want and time.monotonic() < deadline:
        time.sleep(0.001)
    return _ctr(engine, key) == want


class TestSamplingDeterminism:
    def test_greedy_repeatable(self, engine):
        a = _tokens(engine, [1, 2, 3], max_new_tokens=8)
        b = _tokens(engine, [1, 2, 3], max_new_tokens=8)
        assert a == b and len(a) == 8

    @pytest.mark.parametrize("temperature,top_k,top_p", [
        (0.7, 0, 1.0), (0.9, 5, 1.0), (0.8, 0, 0.9), (1.2, 7, 0.85),
    ])
    def test_seeded_sampling_repeatable(self, engine, temperature, top_k,
                                        top_p):
        kw = dict(max_new_tokens=8, temperature=temperature, top_k=top_k,
                  top_p=top_p, seed=13)
        assert _tokens(engine, [4, 5], **kw) == _tokens(engine, [4, 5], **kw)

    def test_sorted_steps_count_the_steps_a_top_p_request_decodes(
            self, engine):
        """``sampler_sorted_steps`` stays where it was over greedy and
        top-k requests (and a greedy one that names a top-p), and moves
        with ``decode_steps`` while a sampled top-p request decodes: the
        host counts by the predicate the sampler branches on."""
        def moved(**kw):
            c0 = engine.metrics_snapshot()["counters"]
            engine.generate([4, 5], max_new_tokens=8, **kw)
            c1 = engine.metrics_snapshot()["counters"]
            return (c1["sampler_sorted_steps"] - c0["sampler_sorted_steps"],
                    c1["decode_steps"] - c0["decode_steps"])

        assert moved() == (0, 7)        # token 1 comes from the prefill
        assert moved(temperature=0.9, top_k=5, seed=2) == (0, 7)
        assert moved(top_p=0.5) == (0, 7)
        assert moved(temperature=0.8, top_p=0.9, seed=2) == (7, 7)
        # co-batched, the top-k neighbour's steps sort with it
        c0 = engine.metrics_snapshot()["counters"]
        futs = [engine.generate_async([4, 5], max_new_tokens=8, seed=2,
                                      temperature=0.8, **kw)
                for kw in (dict(top_p=0.9), dict(top_k=5))]
        for f in futs:
            f.result(timeout=120)
        c1 = engine.metrics_snapshot()["counters"]
        assert 7 <= (c1["sampler_sorted_steps"]
                     - c0["sampler_sorted_steps"]) <= (
            c1["decode_steps"] - c0["decode_steps"])

    def test_seed_changes_sampled_text(self, engine):
        runs = {tuple(_tokens(engine, [7, 8, 9], max_new_tokens=8,
                              temperature=1.5, seed=s)) for s in range(4)}
        assert len(runs) > 1

    def test_greedy_token_is_argmax_of_echoed_logits(self, engine):
        res = engine.generate([2, 3, 4], max_new_tokens=6, echo_logits=True)
        assert res.logits.shape == (6, VOCAB)
        assert res.tokens == [int(np.argmax(r)) for r in res.logits]

    def test_validation(self, engine, lm):
        with pytest.raises(ValueError):
            engine.generate_async([])                        # empty prompt
        with pytest.raises(ValueError):
            engine.generate_async([VOCAB])                   # out of vocab
        with pytest.raises(ValueError):
            engine.generate_async(list(range(MAXLEN)))       # too long
        with pytest.raises(ValueError):
            engine.generate_async([1], temperature=-0.1)
        with pytest.raises(ValueError):
            engine.generate_async([1], top_p=0.0)
        with pytest.raises(ValueError):
            engine.generate_async([1], top_k=VOCAB + 1)
        with pytest.raises(RuntimeError):                    # before load()
            DecodeEngine(lm, max_slots=1, page_size=8).generate_async([1])


class TestBitIdentity:
    def test_decode_logits_match_full_reencode(self, engine, lm):
        import jax

        prog = engine.program
        res = engine.generate([3, 1, 4, 1, 5], max_new_tokens=10,
                              echo_logits=True)
        seq = np.zeros((1, prog.max_len), np.int32)
        seq[0, :5] = [3, 1, 4, 1, 5]
        seq[0, 5:5 + len(res.tokens)] = res.tokens
        ref = np.asarray(jax.jit(prog.reencode)(lm.params, seq))[0]
        assert_greedy_echo([3, 1, 4, 1, 5], res, ref)

    def test_cobatched_tokens_match_solo_runs(self, engine):
        prompts = [[1, 2], [9, 8, 7], [20, 21, 22, 23]]
        solo = [_tokens(engine, p, max_new_tokens=8) for p in prompts]
        futs = [engine.generate_async(p, max_new_tokens=8) for p in prompts]
        assert [f.result(timeout=60).tokens for f in futs] == solo


class TestPagedCache:
    def test_early_eos_frees_pages_for_reuse(self, engine, lm):
        # the greedy first token for this prompt becomes the small
        # engine's EOS id, forcing a 1-token generation
        eos = _tokens(engine, [3, 4], max_new_tokens=6)[0]
        small = DecodeEngine(lm, max_slots=1, page_size=8,
                             eos_id=eos).load()
        try:
            assert small.total_pages == 5    # scratch + 4: no slack at all
            a = small.generate([3, 4], max_new_tokens=20)
            assert a.finish_reason == "eos" and a.tokens == [eos]
            snap = small.metrics_snapshot()
            assert snap["pages_in_use"] == 0 and snap["active_slots"] == 0
            # a full-length request needs EVERY pool page -> it can only
            # run on the pages the EOS'd request just freed, and must
            # still match the (eos-free) engine's greedy prefix exactly
            ref = _tokens(engine, [5, 6, 7], max_new_tokens=29)
            b = small.generate([5, 6, 7], max_new_tokens=29)
            assert b.tokens == ref[:len(b.tokens)]
            assert b.finish_reason in ("eos", "max_tokens")
            assert small.metrics_snapshot()["pages_in_use"] == 0
            # shutdown resolves anything submitted afterwards
            small.shutdown()
            with pytest.raises(RuntimeError):
                small.generate_async([1]).result(timeout=10)
        finally:
            small.shutdown()

    @pytest.mark.parametrize("key", [("step",), ("prefill", 8), ("scrub",),
                                     ("reset",)])
    def test_every_program_gives_the_donated_pools_back_in_place(
            self, engine, key):
        """A program that takes the pools and does not alias them builds
        a second pair beside the first: ``reset`` did (its zeros read
        nothing, so the unused arguments were dropped and with them the
        donation), 12 GB of a 16 GB chip at GPT-2 large with 16 slots."""
        from deeplearning4j_tpu.ops.kv_cache import pool_nbytes

        mem = engine._compiled[key].memory_analysis()
        assert mem.alias_size_in_bytes >= pool_nbytes(engine._cache)

    def test_gauges_return_to_zero_when_idle(self, engine):
        engine.generate([1], max_new_tokens=2)
        snap = engine.metrics_snapshot()
        assert snap["active_slots"] == 0 and snap["pages_in_use"] == 0


class TestStopConditions:
    def test_max_tokens(self, engine):
        res = engine.generate([6, 7], max_new_tokens=5)
        assert res.finish_reason == "max_tokens" and len(res.tokens) == 5
        assert res.n_prompt == 2 and res.ttft_ms is not None

    def test_queued_deadline_expiry_raises(self, engine):
        fut = engine.generate_async([1, 2], deadline=_clock() - 1.0)
        with pytest.raises(DeadlineExceededError):
            fut.result(timeout=30)

    def test_mid_decode_deadline_is_a_stop_not_an_error(self, engine):
        t0 = _ctr(engine, "tokens_out")
        fut = engine.generate_async([2, 2], max_new_tokens=30,
                                    slo_ms=3_600_000.0)
        deadline = time.monotonic() + 30
        while _ctr(engine, "tokens_out") <= t0:
            assert time.monotonic() < deadline, "prefill never landed"
            time.sleep(0.0005)
        try:
            CLOCK_OFFSET[0] = 7200.0        # jump far past the deadline
            res = fut.result(timeout=60)
        finally:
            CLOCK_OFFSET[0] = 0.0
        assert res.finish_reason == "deadline"
        assert 1 <= len(res.tokens) < 30    # partial result, no exception


class TestAdmission:
    def test_shed_policy_raises_overloaded(self):
        b = ContinuousBatcher(max_batch=2, slo_ms=1000, max_queue=1,
                              admission="shed")
        b.submit_request("spec-a")
        with pytest.raises(OverloadedError):
            b.submit_request("spec-b")
        b.close(fail_pending=True)

    def test_concurrent_submit_sheds_boundedly_and_leaks_nothing(self):
        """16 threads race submit_request at a queue cap of 10 with no
        consumer: the admission lock must admit EXACTLY max_queue specs
        (never cap+1 from a check-then-act race), shed the rest with a
        typed error, keep each thread's admitted specs in its submit
        order, and close() must resolve every admitted future — the
        queue-cap contract the fleet soak leans on at millions of
        requests."""
        cap, n_threads, per_thread = 10, 16, 8
        b = ContinuousBatcher(max_batch=4, slo_ms=1000, max_queue=cap,
                              admission="shed")
        start = threading.Barrier(n_threads)
        admitted, shed = [], []
        lock = threading.Lock()

        def pump(tid):
            start.wait()
            for i in range(per_thread):
                spec = (tid, i)
                try:
                    fut = b.submit_request(spec)
                except OverloadedError:
                    with lock:
                        shed.append(spec)
                else:
                    with lock:
                        admitted.append((spec, fut))

        threads = [threading.Thread(target=pump, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(admitted) == cap == b.qsize()
        assert len(shed) == n_threads * per_thread - cap
        # FIFO per thread: admit() drains in arrival order, and a
        # thread's later spec never overtakes its earlier one
        drained = b.admit(cap)
        assert [r.payload for r in drained] == [s for s, _ in admitted]
        per_tid = {}
        for tid, i in (r.payload for r in drained):
            assert per_tid.get(tid, -1) < i
            per_tid[tid] = i
        # no leaked futures: close() resolves everything still admitted
        for r in drained:
            r.future.set_result("served")
        b.close(fail_pending=True)
        for (_, fut) in admitted:
            assert fut.done()
        assert all(fut.result(timeout=1) == "served"
                   for _, fut in admitted)

    def test_begin_drain_wakes_blocked_submitter_to_shed(self):
        """admission="block" parks submitters on the space condvar; a
        drain (serve SIGTERM) must wake them into a typed shed, not
        leave them blocked past the grace window."""
        b = ContinuousBatcher(max_batch=2, slo_ms=1000, max_queue=1,
                              admission="block")
        b.submit_request("occupies-the-queue")
        errs = []

        def blocked():
            try:
                b.submit_request("parked")
            except Exception as e:  # noqa: BLE001 - recorded, asserted
                errs.append(e)

        t = threading.Thread(target=blocked)
        t.start()
        time.sleep(0.1)
        assert t.is_alive() and not errs     # genuinely parked
        b.begin_drain()
        t.join(timeout=10)
        assert not t.is_alive()
        assert len(errs) == 1 and isinstance(errs[0], OverloadedError)
        assert b.qsize() == 1                # queued work kept for drain
        b.close(fail_pending=True)


class TestHotSwap:
    def test_swap_mid_decode_never_mixes_versions(self, engine, lm):
        import jax

        ref_v0 = _tokens(engine, [10, 11], max_new_tokens=24)
        v1 = jax.tree_util.tree_map(
            lambda a: (a * 1.37 + 0.05).astype(a.dtype), lm.params)
        pre = _ctr(engine, "prefills")
        fut_old = engine.generate_async([10, 11], max_new_tokens=24)
        deadline = time.monotonic() + 30
        while _ctr(engine, "prefills") <= pre:
            assert time.monotonic() < deadline, "prefill never landed"
            time.sleep(0.0005)
        try:
            engine.swap_model(v1, "v1")
            fut_new = engine.generate_async([10, 11], max_new_tokens=24)
            r_old = fut_old.result(timeout=60)
            r_new = fut_new.result(timeout=60)
            assert r_old.model_tag == "v0" and r_old.tokens == ref_v0
            assert r_new.model_tag == "v1"
            ref_v1 = _tokens(engine, [10, 11], max_new_tokens=24)  # pure v1
            assert r_new.tokens == ref_v1 and ref_v1 != ref_v0
        finally:
            engine.swap_model(lm, "v0")
        assert _tokens(engine, [10, 11], max_new_tokens=24) == ref_v0
        assert engine.metrics_snapshot()["versions"] == ["v0"]  # v1 GC'd

    def test_swap_rejects_mismatched_tree(self, engine, lm):
        import jax

        bad = jax.tree_util.tree_map(
            lambda a: np.zeros(np.shape(a) + (2,), np.float32), lm.params)
        with pytest.raises(ValueError):
            engine.swap_model(bad, "vbad")


class TestResilience:
    def test_crash_retries_regenerate_identical_tokens(self, engine):
        prompts = [[1, 2], [3, 4, 5], [6]]
        refs = [_tokens(engine, p, max_new_tokens=6) for p in prompts]
        c0 = {k: _ctr(engine, k)
              for k in ("replica_crashes", "retries", "errors")}
        engine._crash_next = True
        futs = [engine.generate_async(p, max_new_tokens=6) for p in prompts]
        got = [f.result(timeout=60) for f in futs]    # nothing stranded
        assert [r.tokens for r in got] == refs
        assert _ctr(engine, "replica_crashes") > c0["replica_crashes"]
        assert _ctr(engine, "retries") > c0["retries"]
        assert _ctr(engine, "errors") == c0["errors"]

    def test_supervisor_respawns_dead_loop(self, engine, monkeypatch):
        # the injected BaseException below is SUPPOSED to escape the
        # loop thread — keep pytest's thread excepthook quiet about it
        monkeypatch.setattr(threading, "excepthook", lambda args: None)
        r0 = _ctr(engine, "replica_respawns")
        orig = engine._step_once

        def die_once():
            engine._step_once = orig
            raise KeyboardInterrupt    # BaseException: kills the thread

        engine._step_once = die_once
        engine.generate_async([1]).result(timeout=60)   # wakes + recovers
        deadline = time.monotonic() + 30
        while _ctr(engine, "replica_respawns") <= r0:
            assert time.monotonic() < deadline, "supervisor never respawned"
            time.sleep(0.005)
        assert engine.health_snapshot()["ready"]
        assert _tokens(engine, [1], max_new_tokens=2)   # still serving

    def test_poison_isolated_and_pages_scrubbed(self, engine, lm):
        import jax

        ref = _tokens(engine, [12, 13], max_new_tokens=6)
        ref_long = _tokens(engine, [14, 15], max_new_tokens=30)
        nan = jax.tree_util.tree_map(
            lambda a: np.full(np.shape(a), np.nan,
                              np.asarray(a).dtype), lm.params)
        p0 = _ctr(engine, "poison_isolated")
        pre = _ctr(engine, "prefills")
        fut_good = engine.generate_async([14, 15], max_new_tokens=30)
        deadline = time.monotonic() + 30
        while _ctr(engine, "prefills") <= pre:
            assert time.monotonic() < deadline, "prefill never landed"
            time.sleep(0.0005)
        try:
            engine.swap_model(nan, "vnan")
            with pytest.raises(PoisonInputError):
                engine.generate([16, 17], max_new_tokens=6)
            # the co-batched in-flight request (old version) is unharmed
            assert fut_good.result(timeout=60).tokens == ref_long
        finally:
            engine.swap_model(lm, "v0")
        assert _ctr(engine, "poison_isolated") > p0
        # scrub proof: the poisoned slot's recycled pages serve clean
        # (a NaN row left in the pool would contaminate via 0 * NaN)
        assert _tokens(engine, [12, 13], max_new_tokens=6) == ref


class TestStepAhead:
    """The plain loop keeps one decode step in flight: step n+1 is
    queued, fed by the sampler's tokens on the device, before step n is
    read.  Same tokens and stops as reading each step first; what only
    the read can know (EOS, deadline, poison) costs the slot one step
    too many, counted and dropped."""

    @staticmethod
    def _small(lm, **kw):
        # scratch + 4 pages: one full-length request needs them all, so
        # the next tenant can only run on the pages the last one freed
        kw.setdefault("max_slots", 1)
        return DecodeEngine(lm, page_size=8, **kw).load()

    def test_eos_stops_at_the_same_token_and_counts_the_overrun(
            self, engine, lm):
        ref = _tokens(engine, [3, 4], max_new_tokens=12)
        # the first token not seen before it, past the prefill's: the
        # EOS is found at a decode step's read, with the next in flight
        k = next(j for j in range(1, len(ref)) if ref[j] not in ref[:j])
        ref_next = _tokens(engine, [5, 6, 7], max_new_tokens=29)
        small = self._small(lm, eos_id=ref[k])
        try:
            assert small.total_pages == 5
            res = small.generate([3, 4], max_new_tokens=12)
            assert res.finish_reason == "eos" and res.tokens == ref[:k + 1]
            assert _ctr_reaches(small, "overrun_slot_steps", 1)
            assert _ctr(small, "decode_steps") == k + 1     # one too many
            nxt = small.generate([5, 6, 7], max_new_tokens=29)
            assert nxt.tokens == ref_next[:len(nxt.tokens)]
            assert nxt.finish_reason == "eos" or nxt.tokens == ref_next
            assert small.metrics_snapshot()["pages_in_use"] == 0
        finally:
            small.shutdown()

    def test_deadline_stops_at_the_same_token_and_counts_the_overrun(
            self, engine, lm):
        ref = _tokens(engine, [2, 2], max_new_tokens=20)
        ref_next = _tokens(engine, [5, 6, 7], max_new_tokens=29)
        box = []
        # the clock jumps once five tokens are recorded: the read that
        # follows records the sixth and finds the deadline passed
        late = lambda: (1e6 if box and box[0].metrics.counter_value(
            "tokens_out") >= 5 else 0.0)
        small = self._small(lm, clock=late)
        box.append(small)
        try:
            res = small.generate_async([2, 2], max_new_tokens=20,
                                       deadline=100.0).result(timeout=60)
            assert res.finish_reason == "deadline" and res.tokens == ref[:6]
            assert _ctr_reaches(small, "overrun_slot_steps", 1)
            nxt = small.generate_async([5, 6, 7], max_new_tokens=29,
                                       deadline=2e6).result(timeout=60)
            assert nxt.tokens == ref_next
        finally:
            small.shutdown()

    def test_poison_stops_its_request_alone_and_counts_the_overrun(
            self, engine, lm):
        ref_b = _tokens(engine, [14, 15], max_new_tokens=14)
        ref_next = _tokens(engine, [5, 6, 7], max_new_tokens=12)
        small = self._small(lm, max_slots=2, total_pages=5)
        real, calls = small._compiled[("sample",)], []

        def poisoned(lgs, *a):
            toks, fin = real(lgs, *a)
            calls.append(1)
            if len(calls) == 3:         # slot 0's logits "were" non-finite
                fin = fin.at[0].set(False)
            return toks, fin

        small._compiled[("sample",)] = poisoned
        try:
            bad = small.generate_async([12, 13], max_new_tokens=14)
            good = small.generate_async([14, 15], max_new_tokens=14)
            with pytest.raises(PoisonInputError):
                bad.result(timeout=60)
            assert good.result(timeout=60).tokens == ref_b
            assert _ctr(small, "poison_isolated") == 1
            assert _ctr(small, "overrun_slot_steps") == 1   # good ran on
            # the freed (scrubbed) pages serve the next tenant clean
            assert _tokens(small, [5, 6, 7], max_new_tokens=12) == ref_next
            assert small.metrics_snapshot()["pages_in_use"] == 0
        finally:
            small.shutdown()

    def test_crash_with_a_step_in_flight_retries_to_the_same_tokens(
            self, engine):
        prompts = [[1, 2], [3, 4, 5], [6]]
        refs = [_tokens(engine, p, max_new_tokens=24) for p in prompts]
        c0 = {k: _ctr(engine, k) for k in ("retries", "errors")}
        in_flight, real = [], engine._drain_crashed

        def spy(exc):
            in_flight.append(engine._flight is not None)
            return real(exc)

        engine._drain_crashed = spy
        try:
            t0 = _ctr(engine, "tokens_out")
            futs = [engine.generate_async(p, max_new_tokens=24)
                    for p in prompts]
            deadline = time.monotonic() + 30
            while _ctr(engine, "tokens_out") < t0 + 6:
                assert time.monotonic() < deadline, "decode never started"
                time.sleep(0.0005)
            engine._crash_next = True
            got = [f.result(timeout=60) for f in futs]
        finally:
            engine._drain_crashed = real
        assert in_flight == [True]          # its tokens were never read
        assert [r.tokens for r in got] == refs
        assert _ctr(engine, "retries") > c0["retries"]
        assert _ctr(engine, "errors") == c0["errors"]

    def test_two_versions_alive_drain_and_mix_nothing(self, engine, lm):
        import jax

        ref_v0 = _tokens(engine, [10, 11], max_new_tokens=24)
        v1 = jax.tree_util.tree_map(
            lambda a: (a * 1.37 + 0.05).astype(a.dtype), lm.params)
        pre = _ctr(engine, "prefills")
        fut_old = engine.generate_async([10, 11], max_new_tokens=24)
        deadline = time.monotonic() + 30
        while _ctr(engine, "prefills") <= pre:
            assert time.monotonic() < deadline, "prefill never landed"
            time.sleep(0.0005)
        d0 = _ctr(engine, "step_drains")
        try:
            engine.swap_model(v1, "v1")
            fut_new = engine.generate_async([10, 11], max_new_tokens=24)
            r_old, r_new = fut_old.result(60), fut_new.result(60)
            drained = _ctr(engine, "step_drains") - d0
            a0 = _ctr(engine, "steps_ahead")
            ref_v1 = _tokens(engine, [10, 11], max_new_tokens=24)  # pure v1
        finally:
            engine.swap_model(lm, "v0")
        assert drained > 0                  # each tag's step read by itself
        assert r_old.model_tag == "v0" and r_old.tokens == ref_v0
        assert r_new.model_tag == "v1" and r_new.tokens == ref_v1 != ref_v0
        # one version alive again: no drain, a step in flight
        assert _ctr(engine, "step_drains") == d0 + drained
        assert _ctr(engine, "steps_ahead") > a0

    def test_a_saturated_engine_runs_ahead(self, lm):
        eng = DecodeEngine(lm, max_slots=4, page_size=8).load()
        try:
            futs = [eng.generate_async([1 + i, 2 + i], max_new_tokens=12 + i,
                                       temperature=0.8 * (i % 2), top_k=5,
                                       seed=i) for i in range(12)]
            assert [len(f.result(timeout=120).tokens)
                    for f in futs] == [12 + i for i in range(12)]
            c = eng.metrics_snapshot()["counters"]
            assert c["steps_ahead"] / c["decode_steps"] >= 0.8
            # every stop was a budget the host knew at the dispatch
            assert c["overrun_slot_steps"] == 0 == c["step_drains"]
        finally:
            eng.shutdown()

    def test_a_tensor_parallel_engine_steps_ahead_to_the_same_tokens(self):
        """The sampler's output on four devices feeds the next step (and
        the join) as compiled: no executable refuses its sharding."""
        import jax

        if len(jax.devices()) < 4:
            pytest.skip("needs >= 4 devices")

        def served(n):
            mesh = build_mesh({"data": n, "model": 1, "seq": 1, "pipe": 1},
                              jax.devices()[:n])
            eng = DecodeEngine(ShardedTransformerLM(
                vocab_size=VOCAB, n_layers=2, d_model=32, n_heads=4,
                max_len=MAXLEN, mesh=mesh, seed=11),
                max_slots=3, page_size=8).load()
            try:
                assert eng.program.tp == n
                n0 = eng.compile_cache_size()
                futs = [eng.generate_async(
                    [1 + i, 2 + i], max_new_tokens=10 + i,
                    temperature=0.8 * (i % 2), top_k=5, seed=i)
                    for i in range(6)]
                toks = [f.result(timeout=120).tokens for f in futs]
                assert eng.compile_cache_size() == n0
                return toks, eng.metrics_snapshot()["counters"]
            finally:
                eng.shutdown()

        one, _ = served(1)
        four, c = served(4)
        assert four == one
        # slots refill from prefills mid-run: joined with those in flight
        assert c["steps_ahead"] / c["decode_steps"] >= 0.8


class TestZeroServeTimeCompiles:
    def test_compile_cache_frozen_across_varied_traffic(self, engine):
        n0 = engine.compile_cache_size()
        for prompt in ([1], [1, 2, 3], list(range(1, 9)),
                       list(range(1, 18))):   # spans several buckets
            engine.generate(prompt, max_new_tokens=3)
        engine.generate([5, 6], max_new_tokens=4, temperature=0.9,
                        top_k=5, top_p=0.9, seed=3)
        engine.generate([5, 6], max_new_tokens=4, echo_logits=True)
        futs = [engine.generate_async([i + 1], max_new_tokens=4)
                for i in range(3)]
        [f.result(timeout=60) for f in futs]
        assert engine.compile_cache_size() == n0


class TestHttpGenerate:
    @pytest.fixture()
    def server(self, engine):
        from deeplearning4j_tpu.ui.server import UIServer
        srv = UIServer(port=0).attach_decode_engine(engine).start()
        yield srv
        srv.stop()

    def _post(self, srv, body):
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate", data=data,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def test_generate_ok_and_metrics(self, engine, server):
        code, out = self._post(server, {"prompt_ids": [1, 2, 3],
                                        "max_tokens": 4, "seed": 1})
        assert code == 200 and len(out["tokens"]) == 4
        assert out["finish_reason"] == "max_tokens" and out["n_prompt"] == 3
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics") as r:
            m = json.loads(r.read())
        snap = next(s for s in m["serving"] if "ttft_ms" in s)
        assert snap["ttft_ms"]["count"] >= 1 and "tpot_ms" in snap

    def test_error_mapping(self, server):
        assert self._post(server, {"max_tokens": 2})[0] == 400
        code, out = self._post(server, {"prompt_ids": [VOCAB + 5]})
        assert (code, out["error_class"]) == (400, "bad_request")
        assert self._post(server, b"{not json")[0] == 400
        code, out = self._post(server, {"prompt_ids": [1], "slo_ms": 0.0})
        assert (code, out["error_class"]) == (504, "deadline_exceeded")

    def test_no_engine_is_503(self):
        from deeplearning4j_tpu.ui.server import UIServer
        srv = UIServer(port=0).start()
        try:
            code, out = self._post(srv, {"prompt_ids": [1]})
            assert (code, out["error_class"]) == (503, "unavailable")
        finally:
            srv.stop()

    def test_healthz_covers_decode_engine(self, engine, server):
        """A decode-only host must answer readiness from ITS engine —
        not the blanket 503 the endpoint returned before decode health
        was wired in (a healthy box would have been pulled from every
        fleet rotation)."""
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/healthz") as r:
            assert r.status == 200
            h = json.loads(r.read())
        assert h["ready"] is True and h["status"] == "ready"
        assert h["kind"] == "decode"
        assert h["model"] == engine.current_tag

    def test_healthz_with_both_engines_is_per_engine(self, engine):
        from deeplearning4j_tpu.ui.server import UIServer

        class _DeadPredict:
            def health_snapshot(self):
                return {"status": "unready", "ready": False}

            def metrics_snapshot(self):
                return {"queue_depth": 0}

        srv = (UIServer(port=0).attach_engine(_DeadPredict())
               .attach_decode_engine(engine).start())
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/healthz")
            assert ei.value.code == 503      # one dead engine -> out of
            h = json.loads(ei.value.read())  # rotation, with evidence
            assert h["ready"] is False and h["status"] == "unready"
            assert h["engines"]["predict"]["ready"] is False
            assert h["engines"]["decode"]["ready"] is True
        finally:
            srv.stop()

    def test_decode_metrics_ride_the_global_registry(self, engine, server):
        """DecodeMetrics registers a process-global collector: one
        /metrics response carries TTFT/TPOT and decode counters under
        registry.collected, keyed by the engine's registered name."""
        name = engine.metrics.global_name
        assert name.startswith("decode")
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics") as r:
            m = json.loads(r.read())
        snap = m["registry"]["collected"][name]
        assert snap["counters"]["requests"] >= 1
        assert "ttft_ms" in snap and "tpot_ms" in snap


class TestOneBuilder:
    """models/transformer.paged_decode_program makes the program of all
    three families; the engine's executables (and the benchmark's
    readers: ``^jit_step$``, ``jit_prefill*``) are called after the
    functions it hands back."""

    ENTRY_POINTS = ("step", "prefill", "prefill_at", "spec_step",
                    "step_multi")

    @staticmethod
    def _model(family):
        import jax

        if family == "adapter":
            from deeplearning4j_tpu.models import TransformerLM
            from deeplearning4j_tpu.models.transformer import (
                TransformerDecodeAdapter,
            )
            return TransformerDecodeAdapter(TransformerLM(
                vocab_size=VOCAB, n_layers=2, d_model=32, n_heads=4,
                max_len=MAXLEN, seed=3, kernel="xla"))
        n = 4 if family == "sharded_tp4" else 1
        if len(jax.devices()) < n:
            pytest.skip(f"needs >= {n} devices")
        mesh = build_mesh({"data": n, "model": 1, "seq": 1, "pipe": 1},
                          jax.devices()[:n])
        return ShardedTransformerLM(vocab_size=VOCAB, n_layers=2, d_model=32,
                                    n_heads=4, max_len=MAXLEN, mesh=mesh,
                                    seed=11)

    @pytest.mark.parametrize("family", ["adapter", "sharded", "sharded_tp4"])
    def test_names_and_one_step_horizon(self, family):
        import jax
        from _decode_checks import assert_logits_close
        from jax.sharding import NamedSharding, PartitionSpec

        from deeplearning4j_tpu.ops.kv_cache import alloc_pools

        model = self._model(family)
        prog = model.decode_program(page_size=8)
        assert prog.tp == (4 if family == "sharded_tp4" else 1)
        for name in self.ENTRY_POINTS:
            assert getattr(prog, name).__name__ == name
        pps, s_n = prog.pages_per_slot, 2
        pools = alloc_pools(prog, 1 + s_n * pps)
        if prog.tp > 1:
            pools = jax.device_put(pools, NamedSharding(
                model.mesh, PartitionSpec(None, None, None, "data")))
        table = 1 + np.arange(s_n * pps, dtype=np.int32).reshape(s_n, pps)
        prompt = np.array([3, 1, 4, 1, 5, 0, 0, 0], np.int32)   # 5 real
        kp, vp, lg = jax.jit(prog.prefill)(
            model.params, *pools, table[0], prompt, np.int32(5))
        args = (model.params, kp, vp, table,
                np.array([int(np.argmax(lg)), 0], np.int32),
                np.array([5, 0], np.int32), np.array([True, False]))
        step = jax.jit(prog.step)
        assert step.lower(*args).as_text().startswith("module @jit_step ")
        lgs = np.asarray(step(*args)[2])
        zi = np.zeros((s_n,), np.int32)
        _, _, toks, fins, lgm = jax.jit(prog.step_multi)(
            *args, np.zeros((s_n,), np.float32), zi,
            np.ones((s_n,), np.float32), np.zeros((s_n,), np.uint32), zi,
            np.ones((s_n,), np.int32), np.int32(-1),
            np.arange(1, dtype=np.int32))
        assert toks.shape == (1, s_n) and bool(fins[0, 0])
        assert int(toks[0, 0]) == int(np.argmax(lgs[0]))
        # a scan and a plain program need not agree in the last bit on
        # XLA:CPU (test_fused_decode.py::test_greedy_bitwise_identical)
        assert_logits_close(np.asarray(lgm)[0, 0], lgs[0])


class TestOneShotPredictRegression:
    def test_mln_output_bitwise_unchanged_by_decode_engine(self):
        import jax

        from deeplearning4j_tpu.models import TransformerLM
        from deeplearning4j_tpu.models.transformer import (
            TransformerDecodeAdapter,
        )

        net = TransformerLM(vocab_size=32, n_layers=1, d_model=32,
                            n_heads=2, max_len=16, seed=0, kernel="xla")
        x = np.arange(24, dtype=np.int32).reshape(2, 12) % 32
        before = np.asarray(net.output(x))
        eng = DecodeEngine(TransformerDecodeAdapter(net), max_slots=1,
                           page_size=8).load()
        try:
            res = eng.generate([1, 2, 3], max_new_tokens=6,
                               echo_logits=True)
            assert len(res.tokens) == 6
            # the adapter's decode agrees with its own re-encode too
            seq = np.zeros((1, 16), np.int32)
            seq[0, :3] = [1, 2, 3]
            seq[0, 3:9] = res.tokens
            ref = np.asarray(jax.jit(eng.program.reencode)(
                eng._versions[eng.current_tag], seq))[0]
            assert_greedy_echo([1, 2, 3], res, ref)
        finally:
            eng.shutdown()
        after = np.asarray(net.output(x))
        assert before.dtype == after.dtype
        assert np.array_equal(before, after)


class TestCliGenerate:
    def test_transformer_checkpoint(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        from deeplearning4j_tpu.models import TransformerLM

        net = TransformerLM(vocab_size=48, n_layers=1, d_model=32,
                            n_heads=2, max_len=16, seed=0, kernel="xla")
        path = str(tmp_path / "tlm.zip")
        net.save(path)
        rc = main(["generate", "--model", path, "--prompt", "ab",
                   "--max-tokens", "4", "--seed", "1"])
        assert rc == 0
        assert len(capsys.readouterr().out) > 0

    def test_recurrent_checkpoint(self, tmp_path, capsys):
        from deeplearning4j_tpu.cli import main
        from deeplearning4j_tpu.models.textgen_lstm import TextGenerationLSTM

        net = TextGenerationLSTM(vocab_size=48, hidden=16, seed=0)
        path = str(tmp_path / "trnn.zip")
        net.save(path)
        rc = main(["generate", "--model", path, "--prompt", "ab",
                   "--max-tokens", "4"])
        assert rc == 0
        assert len(capsys.readouterr().out) > 0
