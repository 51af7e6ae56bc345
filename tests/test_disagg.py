"""Disaggregated prefill/decode + tensor-parallel sharded decode
(docs/SERVING.md "Disaggregated and sharded decode").

The key contracts tested here:
  - a KV page transfer survives serialize -> wire -> attach bitwise
    (f32) / envelope-exact (int8 q + scale); corrupt or truncated
    bytes raise ValueError BEFORE the decode host allocates anything
  - a prefill-host -> handoff -> decode-host pipeline produces
    BIT-IDENTICAL tokens and echoed logits to a unified engine, for
    greedy AND seeded temperature sampling
  - the prefix cache dedups handoff pages the decode host already
    holds (refcounted trie pages, not copies)
  - the fleet router runs the two-stage dispatch transparently and a
    prefill-host kill re-runs requests elsewhere with the SAME tokens,
    leaving the decode host's page accounting a clean partition
  - tensor-parallel decode (heads sharded over the mesh) is bitwise
    equal to single-device decode and each device holds 1/n of the
    KV pool bytes
  - warmup-bundle fingerprints include the mesh shape: a bundle AOT'd
    for one topology never silently loads on another
  - every new counter/gauge is present (zero) on a fresh engine with
    disaggregation off — dashboards can key on them unconditionally
"""

import dataclasses

import numpy as np
import pytest
from _decode_checks import assert_greedy_echo

from deeplearning4j_tpu.ops.kv_cache import (
    PageTransfer, QuantPages, pack_transfer, pages_for, transfer_nbytes,
    unpack_transfer,
)
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM
from deeplearning4j_tpu.serving import (
    DecodeEngine, FleetHost, FleetRouter, PrefillHandoff,
)

VOCAB, MAXLEN = 48, 32


def _lm(n_devices=1, seed=11):
    import jax

    mesh = build_mesh({"data": n_devices, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:n_devices])
    return ShardedTransformerLM(vocab_size=VOCAB, n_layers=2, d_model=32,
                                n_heads=2, max_len=MAXLEN, mesh=mesh,
                                seed=seed)


def _engine(lm, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("page_size", 8)
    kw.setdefault("default_max_new", 8)
    return DecodeEngine(lm, **kw).load()


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.fixture(scope="module")
def unified(lm):
    eng = _engine(lm)
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def pre(lm):
    eng = _engine(lm, role="prefill")
    yield eng
    eng.shutdown()


@pytest.fixture(scope="module")
def dec(lm):
    eng = _engine(lm, role="decode")
    yield eng
    eng.shutdown()


def _partition_ok(engine):
    st = engine._debug_page_state()
    total = engine.total_pages
    return sorted(st["free"] + st["private"] + st["trie"]) == \
        list(range(1, total))


# -- wire format ----------------------------------------------------------

class TestPageTransferWire:
    def _f32(self, n_pages=3):
        rng = np.random.default_rng(0)
        shape = (2, n_pages, 8, 128)       # [layers, pages, page, row]
        return PageTransfer(
            n_pages=n_pages,
            k=rng.standard_normal(shape).astype(np.float32),
            v=rng.standard_normal(shape).astype(np.float32))

    def test_f32_round_trip_bitwise(self):
        t = self._f32()
        back = unpack_transfer(pack_transfer(t))
        assert back.n_pages == t.n_pages
        for a, b in ((t.k, back.k), (t.v, back.v)):
            assert b.dtype == np.float32 and b.shape == a.shape
            assert np.array_equal(a, b)

    def test_int8_round_trip_exact(self):
        rng = np.random.default_rng(1)
        q = rng.integers(-128, 128, size=(2, 2, 8, 128), dtype=np.int8)
        scale = rng.random((2, 2, 8), dtype=np.float32)
        t = PageTransfer(n_pages=2, k=QuantPages(q, scale),
                         v=QuantPages(q[::-1].copy(), scale * 2))
        back = unpack_transfer(pack_transfer(t))
        for a, b in ((t.k, back.k), (t.v, back.v)):
            assert isinstance(b, QuantPages)
            assert b.q.dtype == np.int8 and np.array_equal(a.q, b.q)
            assert b.scale.dtype == np.float32
            assert np.array_equal(a.scale, b.scale)

    def test_nbytes_matches_payload(self):
        t = self._f32()
        assert transfer_nbytes(t) == t.k.nbytes + t.v.nbytes

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    @pytest.mark.parametrize("h,d", [(2, 64), (2, 16)])
    def test_pages_packed_by_one_pool_attach_to_another(self, h, d,
                                                        kv_dtype):
        """Rows one host's pool holds, extracted, sent over the wire and
        attached at OTHER page ids of another host's pool, read back
        there bit for bit (f32) or exactly as quantized (int8), with a
        row that fills its 128 lanes and one widened to them."""
        import jax

        from deeplearning4j_tpu.ops.kv_cache import (
            alloc_cache, gather_layer, gather_pages, set_pages,
            write_prefill,
        )

        rng = np.random.default_rng([h, d])
        src, _ = alloc_cache(2, 6, 8, h, d, kv_dtype=kv_dtype)
        dst, _ = alloc_cache(2, 9, 8, h, d, kv_dtype=kv_dtype)
        there, here = np.array([2, 5, 1], np.int32), np.array([7, 3, 4],
                                                               np.int32)
        for layer in range(2):
            src = write_prefill(src, layer, there, rng.standard_normal(
                (20, h, d)).astype(np.float32))
        sent = jax.tree_util.tree_map(np.asarray, gather_pages(src, there))
        back = unpack_transfer(pack_transfer(
            PageTransfer(n_pages=3, k=sent, v=sent)))
        dst = set_pages(dst, here, back.k)
        for layer in range(2):
            a = np.asarray(gather_layer(src, layer, there[None], (h, d)))
            b = np.asarray(gather_layer(dst, layer, here[None], (h, d)))
            assert a[0, :20].any() and np.array_equal(a, b)

    @pytest.mark.parametrize("cut", [0, 4, 10, 40, -1])
    def test_truncated_raises(self, cut):
        data = pack_transfer(self._f32())
        with pytest.raises(ValueError):
            unpack_transfer(data[:cut])

    def test_bad_magic_raises(self):
        data = pack_transfer(self._f32())
        with pytest.raises(ValueError):
            unpack_transfer(b"XX" + data[2:])

    def test_corrupt_header_raises(self):
        data = bytearray(pack_transfer(self._f32()))
        data[20] ^= 0xFF               # inside the json header
        with pytest.raises(ValueError):
            unpack_transfer(bytes(data))


# -- engine-level handoff -------------------------------------------------

class TestDisaggEngine:
    def test_greedy_handoff_identical(self, unified, pre, dec):
        for i, prompt in enumerate(([1, 2, 3], [7, 4], list(range(12)))):
            ref = unified.generate(prompt, max_new_tokens=6, seed=i)
            h = pre.generate(prompt, max_new_tokens=6, seed=i)
            assert isinstance(h, PrefillHandoff)
            assert h.n_pages == pages_for(len(prompt), 8)
            sent = unpack_transfer(h.pages)       # the pool's own rows
            assert sent.k.shape[0] == 2 and sent.k.shape[2:] == (8, 128)
            got = dec.continue_async(h).result(timeout=60)
            assert got.tokens == ref.tokens

    def test_seeded_sampling_identical(self, unified, pre, dec):
        kw = dict(max_new_tokens=8, temperature=0.8, top_k=5, seed=123)
        ref = unified.generate([3, 1, 4, 1, 5], **kw)
        h = pre.generate([3, 1, 4, 1, 5], **kw)
        got = dec.continue_async(h).result(timeout=60)
        assert got.tokens == ref.tokens

    def test_echo_logits_bitwise(self, unified, pre, dec):
        kw = dict(max_new_tokens=5, echo_logits=True, seed=0)
        ref = unified.generate([9, 8, 7, 6], **kw)
        h = pre.generate([9, 8, 7, 6], **kw)
        got = dec.continue_async(h).result(timeout=60)
        assert got.tokens == ref.tokens
        assert len(got.logits) == len(ref.logits)
        for a, b in zip(ref.logits, got.logits):
            assert np.array_equal(a, b)

    def test_handoff_counters(self, pre, dec):
        out0 = pre.metrics.snapshot()["counters"]["handoffs_out"]
        in0 = dec.metrics.snapshot()["counters"]["handoffs_in"]
        h = pre.generate([5, 6, 7, 8, 9, 10, 11, 12, 13], max_new_tokens=2)
        dec.continue_async(h).result(timeout=60)
        ps = pre.metrics.snapshot()["counters"]
        ds = dec.metrics.snapshot()["counters"]
        assert ps["handoffs_out"] == out0 + 1
        assert ps["pages_exported"] >= h.n_pages
        assert ds["handoffs_in"] == in0 + 1
        assert ds["pages_attached"] >= 1

    def test_decode_role_rejects_prompts(self, dec):
        with pytest.raises(RuntimeError):
            dec.generate_async([1, 2, 3])

    def test_corrupt_handoff_typed_error_pool_intact(self, pre, dec):
        h = pre.generate([1, 2, 3, 4, 5], max_new_tokens=3)
        bad = dataclasses.replace(
            h, pages=h.pages[:len(h.pages) // 2])
        with pytest.raises(ValueError):
            dec.continue_async(bad).result(timeout=60)
        assert _partition_ok(dec)
        good = dec.continue_async(h).result(timeout=60)
        assert len(good.tokens) == 3

    def test_partition_clean_after_traffic(self, pre, dec):
        assert _partition_ok(pre) and _partition_ok(dec)

    def test_prefix_shared_pages_dedup(self, lm):
        p2 = _engine(lm, role="prefill", prefix_cache=True,
                     prompt_buckets=(MAXLEN,))
        d2 = _engine(lm, role="decode", prefix_cache=True,
                     prompt_buckets=(MAXLEN,))
        try:
            prompt = list(range(17))   # 2 full pages + 1 partial
            a = d2.continue_async(
                p2.generate(prompt, max_new_tokens=4)).result(timeout=60)
            dd0 = d2.metrics.snapshot()["counters"]["pages_deduped"]
            b = d2.continue_async(
                p2.generate(prompt, max_new_tokens=4)).result(timeout=60)
            assert a.tokens == b.tokens
            assert d2.metrics.snapshot()["counters"]["pages_deduped"] \
                == dd0 + 2             # both full pages reused, refcounted
            assert _partition_ok(d2)
        finally:
            p2.shutdown()
            d2.shutdown()


# -- fleet router: two-stage dispatch + chaos -----------------------------

class TestFleetDisagg:
    def test_two_stage_dispatch(self, unified, pre, dec):
        router = FleetRouter([FleetHost("pre0", decode=pre),
                              FleetHost("dec0", decode=dec)],
                             max_retries=2)
        try:
            prompts = [[4, 4, 2], [1] * 9, [30, 20, 10, 0]]
            ref = [unified.generate(p, max_new_tokens=5, seed=i).tokens
                   for i, p in enumerate(prompts)]
            got = [router.generate(p, max_new_tokens=5, seed=i).tokens
                   for i, p in enumerate(prompts)]
            assert got == ref
            snap = router.metrics_snapshot()
            assert snap["counters"]["disagg_requests"] >= len(prompts)
            assert snap["counters"]["page_transfers"] >= len(prompts)
            assert snap["counters"]["transfer_bytes"] > 0
            hosts = snap["hosts"]
            assert hosts["pre0"]["role"] == "prefill"
            assert hosts["dec0"]["role"] == "decode"
            assert all("free_pages" in h for h in hosts.values())
        finally:
            router.shutdown()

    def test_prefill_host_kill_same_tokens(self, lm, unified, dec):
        prompts = [[int(x) for x in np.random.default_rng(i).integers(
            0, VOCAB, size=3 + i)] for i in range(6)]
        ref = [unified.generate(p, max_new_tokens=4, seed=i).tokens
               for i, p in enumerate(prompts)]
        pre0 = _engine(lm, role="prefill")
        pre1 = _engine(lm, role="prefill")
        router = FleetRouter([FleetHost("pre0", decode=pre0),
                              FleetHost("pre1", decode=pre1),
                              FleetHost("dec0", decode=dec)], max_retries=3)
        try:
            futs = [router.generate_async(p, max_new_tokens=4, seed=i)
                    for i, p in enumerate(prompts)]
            pre0.shutdown()
            router.mark_host_down("pre0", reason="test-kill")
            got = [f.result(timeout=60).tokens for f in futs]
            assert got == ref
            assert _partition_ok(dec)
        finally:
            router.shutdown()
            pre1.shutdown()

    def test_decode_pressure_scoring(self):
        class _Gauges:
            role = "decode"

            def __init__(self, snap):
                self.snap = snap

            def metrics_snapshot(self):
                return self.snap

        calm = FleetHost("a", decode=_Gauges(
            {"free_slots": 2, "free_pages": 9, "pages_per_slot": 4}))
        full = FleetHost("b", decode=_Gauges(
            {"free_slots": 0, "free_pages": 1, "pages_per_slot": 4}))
        legacy = FleetHost("c", decode=_Gauges({}))   # no gauges exported
        for h in (calm, full, legacy):
            h.read_decode_pressure()
        assert calm.decode_pressure() == 0
        assert full.decode_pressure() == 2
        assert legacy.decode_pressure() == 0          # back-compat: no bias


# -- tensor-parallel decode -----------------------------------------------

class TestTensorParallel:
    @pytest.fixture(scope="class")
    def lm2(self):
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        return _lm(n_devices=2)

    @pytest.fixture(scope="class")
    def tp_engine(self, lm2):
        eng = _engine(lm2)
        assert eng.program.tp == 2
        yield eng
        eng.shutdown()

    def test_tokens_match_single_device(self, unified, tp_engine):
        for i, p in enumerate(([1, 2, 3], [11] * 7, [0, 47])):
            assert tp_engine.generate(p, max_new_tokens=6, seed=i).tokens \
                == unified.generate(p, max_new_tokens=6, seed=i).tokens

    def test_kv_pool_sharded_per_device(self, tp_engine):
        kp, vp = tp_engine._cache
        for pool in (kp, vp):
            shard = pool.sharding.shard_shape(pool.shape)
            assert int(np.prod(shard)) * 2 == int(np.prod(pool.shape))

    def test_decode_bitwise_vs_sharded_reencode(self, lm2, tp_engine):
        """Each token the argmax of the sharded re-encode's row, echoed
        logits within ``LOGIT_ATOL`` of it, NOT bit for bit: a
        one-row step and the whole window differ in a logit's last bit
        on XLA:CPU."""
        import jax

        prompt = [3, 9, 27, 33]
        res = tp_engine.generate(prompt, max_new_tokens=5,
                                 echo_logits=True, seed=0)
        seq = np.array([prompt + res.tokens], dtype=np.int32)
        prog = tp_engine.program
        ref = np.asarray(jax.jit(prog.reencode)(lm2.params, seq))[0]
        assert_greedy_echo(prompt, res, ref)

    def test_single_chip_prefill_feeds_tp_sink(self, lm2, pre, unified):
        sink = _engine(lm2, role="decode")
        try:
            prompt = [3, 1, 4, 1, 5, 9, 2, 6]
            ref = unified.generate(prompt, max_new_tokens=6, seed=0)
            h = pre.generate(prompt, max_new_tokens=6, seed=0)
            got = sink.continue_async(h).result(timeout=60)
            assert got.tokens == ref.tokens
        finally:
            sink.shutdown()

    def test_int8_tp_rejected(self, lm2):
        with pytest.raises(ValueError, match="int8"):
            DecodeEngine(lm2, max_slots=2, page_size=8, kv_dtype="int8")


# -- warm bundles across topologies ---------------------------------------

class TestMeshFingerprint:
    def test_fingerprint_includes_mesh(self, lm):
        import jax

        from deeplearning4j_tpu.serving import device_fingerprint

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        mesh = build_mesh({"data": 2, "model": 1, "seq": 1, "pipe": 1},
                          jax.devices()[:2])
        fp0, fp2 = device_fingerprint(), device_fingerprint(mesh=mesh)
        assert fp0 != fp2
        assert "mesh(" in fp2 and "data=2" in fp2

    def test_mesh_mismatch_falls_back(self, tmp_path):
        import warnings

        import jax

        from deeplearning4j_tpu.serving import load_bundle, save_bundle

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        mesh = build_mesh({"data": 2, "model": 1, "seq": 1, "pipe": 1},
                          jax.devices()[:2])
        path = str(tmp_path / "warm.bundle")
        save_bundle(path, "v0", {})    # fingerprinted for mesh=None
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            out = load_bundle(path, tag="v0", mesh=mesh)
        assert out == {}
        assert sum(issubclass(x.category, RuntimeWarning) for x in w) == 1


# -- metrics contract: off means zero, not absent -------------------------

class TestMetricsZeroKeyed:
    def test_fresh_engine_zero_keys(self, lm):
        eng = _engine(lm, prompt_buckets=(MAXLEN,))
        try:
            snap = eng.metrics_snapshot()
            for key in ("handoffs_out", "handoffs_in", "pages_exported",
                        "pages_attached", "pages_deduped"):
                assert snap["counters"][key] == 0
            assert snap["role"] == "unified" and snap["tp"] == 1
            assert isinstance(snap["free_pages"], int)
            assert isinstance(snap["free_slots"], int)
            assert snap["free_slots"] == 3
        finally:
            eng.shutdown()

    def test_fresh_router_zero_keys(self, unified):
        router = FleetRouter([FleetHost("u0", decode=unified)])
        try:
            snap = router.metrics_snapshot()
            for key in ("disagg_requests", "page_transfers",
                        "transfer_bytes"):
                assert snap["counters"][key] == 0
        finally:
            router.shutdown()


# -- HTTP surface: a prefill-role host refuses /generate ------------------

class TestHttpPrefillRole:
    def test_generate_on_prefill_host_is_409(self, pre):
        """A PrefillHandoff is a page baton, not tokens — plain HTTP
        /generate on a prefill-role host must answer a STRUCTURED 409
        (never a raw AttributeError 500) pointing at the fleet router."""
        import json
        import urllib.error
        import urllib.request

        from deeplearning4j_tpu.ui.server import UIServer
        srv = UIServer(port=0).attach_decode_engine(pre).start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate",
                data=json.dumps({"prompt_ids": [1, 2, 3],
                                 "max_tokens": 4}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req)
            assert ei.value.code == 409
            body = json.loads(ei.value.read())
            assert body["error_class"] == "prefill_role"
        finally:
            srv.stop()
