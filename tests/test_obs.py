"""Unified telemetry (PR 8): span tracing correctness (golden span trees,
thread safety, ring-buffer bounds, Chrome-trace schema, pod merge), the
unified MetricsRegistry (typed instruments, collectors, snapshot merge,
serving back-compat), the /metrics + /trace HTTP surface, and the
satellite fixes (RemoteStatsRouter drop accounting, profiler degrade)."""

import json
import logging
import threading

import numpy as np
import pytest

from deeplearning4j_tpu.datasets import DataSet
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
from deeplearning4j_tpu.nn.multilayer import (
    MultiLayerNetwork, NeuralNetConfiguration,
)
from deeplearning4j_tpu.obs import metrics as obs_metrics
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.obs.metrics import (
    MetricsRegistry, get_registry, merge_snapshots,
)
from deeplearning4j_tpu.obs.trace import (
    TraceRecorder, find_spans, merge_traces, span_tree, validate_chrome_trace,
)


@pytest.fixture
def recorder():
    """Install a fresh global recorder; always disarm afterwards so no
    other test observes tracing enabled."""
    rec = obs_trace.enable_tracing(capacity=65536)
    try:
        yield rec
    finally:
        obs_trace.disable_tracing()


def small_net(seed=5):
    conf = (NeuralNetConfiguration.builder().seed(seed)
            .layer(Dense(n_out=8, activation="relu"))
            .layer(OutputLayer(n_out=2, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def data(n=32):
    rng = np.random.default_rng(0)
    return DataSet(rng.normal(size=(n, 4)).astype(np.float32),
                   np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)])


# ---------------------------------------------------------------------------
# trace recorder core
# ---------------------------------------------------------------------------

class TestTraceRecorder:
    def test_disabled_is_shared_noop(self):
        obs_trace.disable_tracing()
        assert obs_trace.get_recorder() is None
        assert not obs_trace.tracing_enabled()
        # the hot-path fast path allocates nothing: one shared object
        assert obs_trace.span("a") is obs_trace.span("b")
        obs_trace.instant("x", k=1)          # no-op, no error
        with obs_trace.span("c", cat="t") as sp:
            sp.set(extra=1)                  # .set works on the null span

    def test_span_nesting_and_args(self, recorder):
        with obs_trace.span("outer", cat="test", a=1) as sp:
            sp.set(b=2)
            with obs_trace.span("inner", cat="test"):
                pass
        tree = span_tree(recorder.export())
        outer = find_spans(tree, "outer")
        assert len(outer) == 1
        assert [c["name"] for c in outer[0]["children"]] == ["inner"]
        assert outer[0]["event"]["args"] == {"a": 1, "b": 2}

    def test_span_records_error_class_on_exception(self, recorder):
        with pytest.raises(ValueError):
            with obs_trace.span("boom"):
                raise ValueError("x")
        (ev,) = [e for e in recorder.events() if e["name"] == "boom"]
        assert ev["args"]["error"] == "ValueError"

    def test_instant_events(self, recorder):
        obs_trace.instant("fault", cat="chaos", kind="device_loss", step=3)
        (ev,) = recorder.events()
        assert ev["ph"] == "i" and ev["cat"] == "chaos"
        assert ev["args"] == {"kind": "device_loss", "step": 3}

    def test_traced_decorator(self, recorder):
        @obs_trace.traced("my/op")
        def f(x):
            return x + 1

        assert f(1) == 2
        assert [e["name"] for e in recorder.events()] == ["my/op"]

    def test_ring_buffer_eviction_bounds(self):
        rec = TraceRecorder(capacity=16)
        for i in range(100):
            rec.instant(f"e{i}")
        events = rec.events()
        assert len(events) == 16
        assert rec.dropped == 84
        # the SURVIVORS are the newest events, not the oldest
        assert events[-1]["name"] == "e99" and events[0]["name"] == "e84"
        assert rec.export()["metadata"]["dropped"] == 84

    def test_thread_safety_concurrent_spans(self):
        rec = TraceRecorder(capacity=100000)
        obs_trace.set_recorder(rec)
        try:
            n_threads, per_thread = 8, 200

            def work(tid):
                for i in range(per_thread):
                    with obs_trace.span(f"t{tid}", cat="mt", i=i):
                        pass

            threads = [threading.Thread(target=work, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            obs_trace.set_recorder(None)
        events = rec.events()
        assert len(events) == n_threads * per_thread
        assert rec.dropped == 0
        # no torn/interleaved records: every event fully formed, and each
        # thread's stream is complete on its own tid track
        assert not validate_chrome_trace({"traceEvents": events})
        by_name = {}
        for e in events:
            by_name.setdefault(e["name"], set()).add(e["args"]["i"])
        for t in range(n_threads):
            assert by_name[f"t{t}"] == set(range(per_thread))

    def test_export_validates_and_save_roundtrip(self, recorder, tmp_path):
        with obs_trace.span("a"):
            obs_trace.instant("i1")
        obj = recorder.export()
        assert validate_chrome_trace(obj) == []
        path = recorder.save(str(tmp_path / "t.trace.json"))
        with open(path) as f:
            assert validate_chrome_trace(json.load(f)) == []

    def test_validator_catches_malformed(self):
        assert validate_chrome_trace([]) != []
        assert validate_chrome_trace({"traceEvents": "x"}) != []
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "a", "ts": 1.0,
                              "pid": 0, "tid": 0}]})  # missing dur
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "Z", "name": "a"}]}) != []
        assert validate_chrome_trace(
            {"traceEvents": [{"ph": "X", "name": "a", "ts": "NaN",
                              "dur": 1.0, "pid": 0, "tid": 0}]}) != []

    def test_flush_without_path_is_none(self, recorder):
        assert obs_trace.flush() is None   # no configured path → no write


class TestMergeTraces:
    def _trace_file(self, tmp_path, name, pid, events):
        rec = TraceRecorder(capacity=64, process_id=pid,
                            process_name=name)
        for fn in events:
            fn(rec)
        path = str(tmp_path / f"{name}.trace.json")
        rec.save(path)
        return path

    def test_merges_two_workers_one_timeline(self, tmp_path):
        p0 = self._trace_file(tmp_path, "w0", 0,
                              [lambda r: r.instant("a", step=1)])
        p1 = self._trace_file(tmp_path, "w1", 1,
                              [lambda r: r.instant("b", step=2)])
        out = str(tmp_path / "pod.trace.json")
        merged = merge_traces([p0, p1], out)
        names = {e["name"] for e in merged["traceEvents"]
                 if e.get("ph") == "i"}
        assert names == {"a", "b"}
        pids = {e["pid"] for e in merged["traceEvents"]
                if e.get("ph") == "i"}
        assert pids == {0, 1}
        with open(out) as f:
            assert validate_chrome_trace(json.load(f)) == []

    def test_pid_collision_remapped_to_distinct_tracks(self, tmp_path):
        # two incarnations of worker 1 claim the same Chrome pid — the
        # merge must keep them on distinct tracks, not interleave them
        p0 = self._trace_file(tmp_path, "w1.inc0", 1,
                              [lambda r: r.instant("death")])
        p1 = self._trace_file(tmp_path, "w1.inc1", 1,
                              [lambda r: r.instant("resume")])
        merged = merge_traces([p0, p1])
        by_name = {e["name"]: e["pid"] for e in merged["traceEvents"]
                   if e.get("ph") == "i"}
        assert by_name["death"] != by_name["resume"]

    def test_merged_events_time_ordered(self, tmp_path):
        import time
        p0 = self._trace_file(tmp_path, "a", 0,
                              [lambda r: r.instant("first")])
        time.sleep(0.01)
        p1 = self._trace_file(tmp_path, "b", 1,
                              [lambda r: r.instant("second")])
        merged = merge_traces([p1, p0])   # deliberately out of order
        inst = [e for e in merged["traceEvents"] if e.get("ph") == "i"]
        assert [e["name"] for e in inst] == ["first", "second"]
        assert inst[0]["ts"] <= inst[1]["ts"]


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestMetricsRegistry:
    def test_counter_gauge_histogram_with_labels(self):
        reg = MetricsRegistry()
        c = reg.counter("reqs")
        c.inc()
        c.inc(2, replica=1)
        g = reg.gauge("depth")
        g.set(3, queue="a")
        h = reg.histogram("lat")
        h.record(1.5)
        h.record(300.0)
        snap = reg.snapshot()
        assert snap["counters"] == {"reqs": 1, "reqs{replica=1}": 2}
        assert snap["gauges"]["depth{queue=a}"] == 3.0
        assert snap["histograms"]["lat"]["count"] == 2
        assert snap["histograms"]["lat"]["max"] == 300.0
        assert c.value(replica=1) == 2 and c.value() == 1

    def test_counter_cannot_decrease(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("c").inc(-1)

    def test_instruments_idempotent_and_type_checked(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")
        with pytest.raises(ValueError):
            reg.histogram("lat", buckets=(1.0, 2.0)) \
                and reg.histogram("lat", buckets=(3.0,))
        reg.histogram("lat", buckets=(1.0, 2.0))
        with pytest.raises(ValueError):
            reg.histogram("lat", buckets=(3.0,))

    def test_gauge_callback(self):
        reg = MetricsRegistry()
        reg.gauge("live").set_fn(lambda: 7)
        assert reg.snapshot()["gauges"]["live"] == 7.0

    def test_collector_and_weakref_cleanup(self):
        reg = MetricsRegistry()

        class Owner:
            def snapshot(self):
                return {"hello": 1}

        o = Owner()
        name = reg.register_collector("owner", o.snapshot, unique=True)
        assert reg.snapshot()["collected"][name] == {"hello": 1}
        del o
        import gc
        gc.collect()
        assert name not in reg.snapshot()["collected"]

    def test_broken_collector_does_not_take_snapshot_down(self):
        reg = MetricsRegistry()
        reg.register_collector("bad", lambda: 1 / 0)
        snap = reg.snapshot()
        assert "error" in snap["collected"]["bad"]

    def test_merge_snapshots_pod_view(self):
        def worker(n):
            reg = MetricsRegistry()
            reg.counter("steps").inc(n)
            reg.gauge("depth").set(n)
            h = reg.histogram("lat", buckets=(1.0, 10.0))
            h.record(0.5)
            h.record(5.0 * n)
            return reg.snapshot()

        agg = merge_snapshots([worker(1), worker(3)])
        assert agg["sources"] == 2
        assert agg["counters"]["steps"] == 4
        assert agg["gauges"]["depth"] == {"min": 1.0, "max": 3.0,
                                          "mean": 2.0, "n": 2}
        assert agg["histograms"]["lat"]["count"] == 4
        assert agg["histograms"]["lat"]["counts"] == [2, 1, 1]

    def test_global_registry_is_shared(self):
        assert get_registry() is get_registry()
        assert isinstance(get_registry(), MetricsRegistry)


class TestServingMetricsBackCompat:
    """The PR-4 snapshot schema survives the migration onto the unified
    registry — the old tests/scripts read these exact keys."""

    def test_legacy_snapshot_schema(self):
        from deeplearning4j_tpu.serving import ServingMetrics

        m = ServingMetrics()
        m.inc("shed")
        m.inc("retries", 2)
        m.record_batch(3, 7, 1, device_ms=4.2)
        m.queue_wait.record(1.0)
        m.e2e.record(6.0)
        snap = m.snapshot()
        c = snap["counters"]
        assert c["shed"] == 1 and c["retries"] == 2
        assert c["batches"] == 1 and c["requests"] == 3
        assert c["rows"] == 7 and c["padded_rows"] == 1
        # every pre-migration counter key still reported (zeros included)
        for key in ("errors", "swaps", "unwarmed_serves", "replica_crashes",
                    "replica_hangs", "replica_respawns", "poison_isolated",
                    "circuit_opens", "canary_promotions", "canary_rollbacks",
                    "canary_mirrored_batches", "deadline_missed"):
            assert c[key] == 0
        assert snap["max_batch_rows"] == 7
        assert snap["batch_occupancy"] == round(7 / 8, 4)
        for hkey in ("queue_wait_ms", "device_time_ms", "e2e_ms"):
            h = snap[hkey]
            for field in ("count", "sum_ms", "max_ms", "mean_ms",
                          "buckets_ms", "counts", "p50_ms", "p90_ms",
                          "p99_ms"):
                assert field in h
        assert snap["device_time_ms"]["count"] == 1
        assert snap["device_time_ms"]["max_ms"] == 4.2

    def test_latency_histogram_legacy_attrs(self):
        from deeplearning4j_tpu.serving import LatencyHistogram

        h = LatencyHistogram()
        assert h.count == 0 and h.percentile(99) is None
        h.record(3.0)
        h.record(70.0)
        assert h.count == 2
        assert h.sum_ms == 73.0 and h.max_ms == 70.0
        assert 2.0 <= h.percentile(50) <= 5.0

    def test_serving_metrics_surface_in_global_registry(self):
        from deeplearning4j_tpu.serving import ServingMetrics

        m = ServingMetrics()
        m.inc("shed", 5)
        collected = get_registry().snapshot()["collected"]
        assert m.global_name in collected
        assert collected[m.global_name]["counters"]["shed"] == 5

    def test_per_engine_registry_typed_instruments(self):
        from deeplearning4j_tpu.serving import ServingMetrics

        m = ServingMetrics()
        m.record_batch(1, 4, 0, device_ms=2.0)
        snap = m.registry.snapshot()
        assert snap["counters"]["batches"] == 1
        assert snap["histograms"]["device_time_ms"]["count"] == 1


# ---------------------------------------------------------------------------
# golden span trees (the documented taxonomy, docs/OBSERVABILITY.md)
# ---------------------------------------------------------------------------

class TestGoldenSpanTrees:
    def test_training_step_span_tree(self, recorder):
        net = small_net()
        loss = net.fit_batch(data())
        float(loss)                       # forces train/device_sync
        tree = span_tree(recorder.export())
        steps = find_spans(tree, "train/step")
        assert len(steps) == 1
        children = {c["name"] for c in steps[0]["children"]}
        assert {"train/h2d", "train/dispatch"} <= children
        assert steps[0]["event"]["args"]["iteration"] == 1
        assert find_spans(tree, "train/device_sync")
        assert validate_chrome_trace(recorder.export()) == []

    def test_tracing_off_records_nothing_and_same_loss(self):
        obs_trace.disable_tracing()
        l_off = float(small_net().fit_batch(data()))
        rec = obs_trace.enable_tracing()
        try:
            l_on = float(small_net().fit_batch(data()))
            assert l_off == l_on          # spans never change math
            assert find_spans(span_tree(rec.export()), "train/step")
        finally:
            obs_trace.disable_tracing()

    def test_serving_request_span_tree(self, recorder):
        from deeplearning4j_tpu.serving import Engine

        eng = Engine(small_net(), max_batch=4, slo_ms=2000.0, replicas=1)
        eng.load(input_shape=(4,))
        out = eng.output(np.zeros((2, 4), np.float32))
        assert out.shape[0] == 2
        eng.shutdown()
        obj = recorder.export()
        tree = span_tree(obj)
        batches = find_spans(tree, "serve/batch")
        assert batches, "no serve/batch span recorded"
        assert any(c["name"] == "serve/forward"
                   for b in batches for c in b["children"])
        for name in ("serve/request", "serve/queue_wait",
                     "serve/batch_form"):
            assert find_spans(tree, name), f"missing {name}"
        assert validate_chrome_trace(obj) == []

    def test_elastic_fault_instant_and_recovery_span(self, recorder,
                                                     tmp_path):
        from deeplearning4j_tpu.parallel import (
            ChaosInjector, ElasticTrainer, FaultSchedule,
        )

        class Plain:
            def __init__(self, n):
                self.net = n

            def fit_batch(self, ds):
                return self.net.fit_batch(ds)

        net = small_net()
        sched = FaultSchedule.scripted({3: ["device_loss"]})
        inj = ChaosInjector(Plain(net), sched)
        et = ElasticTrainer(inj, str(tmp_path), checkpoint_every=1,
                            sync_every=1, max_restarts=2)
        before = get_registry().counter("elastic_restarts_total").value()
        for _ in range(4):
            et.fit_batch(data())
        assert et.total_restarts == 1
        tree = span_tree(recorder.export())
        faults = [e for e in recorder.events()
                  if e["name"] == "fault" and e.get("ph") == "i"]
        assert any(f["args"]["kind"] == "device_loss" for f in faults)
        assert find_spans(tree, "elastic/recovery")
        assert find_spans(tree, "ckpt/save")
        assert find_spans(tree, "ckpt/restore")
        # the unified registry counted it too
        reg = get_registry()
        assert reg.counter("elastic_restarts_total").value() == before + 1
        stats = [v for k, v in reg.snapshot()["collected"].items()
                 if k.startswith("elastic#") and v.get("total_restarts")]
        assert any(s["total_restarts"] == 1 for s in stats)

    def test_prefetch_data_wait_span_and_collector(self, recorder):
        from deeplearning4j_tpu.datasets import (
            DevicePrefetchIterator, ListDataSetIterator,
        )

        it = DevicePrefetchIterator(
            ListDataSetIterator([data(8), data(8)]), depth=1)
        net = small_net()
        while it.has_next():
            net.fit_batch(it.next())
        snap = get_registry().snapshot()["collected"]["input_pipeline"]
        assert any(s["batches"] == 2 for s in snap)
        it.close()
        assert find_spans(span_tree(recorder.export()), "input/data_wait")


# ---------------------------------------------------------------------------
# one span system, two sinks: the decode engine's and the sharded LM's
# live spans in the ring and in a profiler session
# ---------------------------------------------------------------------------

STEP_CHILDREN = ["serve/step_build", "serve/step_dispatch",
                 "serve/sample_dispatch", "serve/step_wait",
                 "serve/step_record"]
PROMPT = [3, 1, 4, 1, 5]


def tiny_lm():
    import jax

    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    return ShardedTransformerLM(
        vocab_size=64, n_layers=2, d_model=32, n_heads=2, d_ff=64,
        mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]),
        max_len=32, seed=11)


def lm_batch():
    t = np.random.default_rng(3).integers(0, 64, (2, 16))
    return t, np.roll(t, -1, axis=1)


@pytest.fixture(scope="module")
def tiny_engine():
    from deeplearning4j_tpu.serving import DecodeEngine
    eng = DecodeEngine(tiny_lm(), max_slots=2, page_size=8, max_len=32,
                       prompt_buckets=(8, 16)).load()
    try:
        yield eng
    finally:
        eng.shutdown()


def served(eng, **kw):
    """One request's result, with the engine's thread past the turn
    that finished it: a live span is recorded when it closes, which is
    after the future resolves, and the one-token request sent behind is
    admitted only in a later turn."""
    res = eng.generate(PROMPT, **kw)
    eng.generate(PROMPT, max_new_tokens=1)
    return res


@pytest.fixture
def profiler_session(tmp_path):
    """A JAX profiler session on the CPU, the ring OFF; yields a reader
    of the program's spans ``(name, stats)`` in the written profile.
    Skipped where no session can start."""
    import glob

    import jax

    obs_trace.disable_tracing()
    try:
        jax.profiler.start_trace(str(tmp_path))
    except Exception as e:
        pytest.skip(f"no profiler session can start here: {e}")
    stopped = []

    def spans():
        if not stopped:
            jax.profiler.stop_trace()
            stopped.append(True)
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[-1]
        out = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                out += [(e.name, dict(e.stats), e.start_ns, e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(("serve/", "train/"))]
        return sorted(out, key=lambda e: e[2])

    try:
        yield spans
    finally:
        if not stopped:
            jax.profiler.stop_trace()


class TestOneSpanSystemTwoSinks:
    def test_both_sinks_off_is_the_shared_null_span(self):
        import jax  # noqa: F401  (loaded: the profiler's flag is read)
        obs_trace.disable_tracing()
        s = obs_trace.span("serve/decode_step", cat="serve", n_active=1)
        assert s is obs_trace._NULL_SPAN
        assert s.set(x=1) is s and s.drop() is None

    def test_generate_golden_span_tree_in_the_ring(self, tiny_engine,
                                                   recorder):
        res = served(tiny_engine, max_new_tokens=3)
        tree = span_tree(recorder.export())
        turns = find_spans(tree, "serve/iteration")
        assert turns and all(t["event"]["args"]["worked"] for t in turns)
        steps = find_spans(tree, "serve/decode_step")
        assert len(steps) == 2            # token 1 comes from the prefill
        by_turn = [s for t in turns for s in t["children"]
                   if s["name"] == "serve/decode_step"]
        assert len(by_turn) == 2          # each directly under a turn
        # a step's span is the turn that READS it: the wait and the
        # record; its build and dispatch came a turn earlier
        for st in steps:
            assert [c["name"] for c in st["children"]] == STEP_CHILDREN[3:]
        assert [st["event"]["args"]["ahead"] for st in steps] == [0, 1]
        for st in steps:
            a = st["event"]["args"]
            assert a["tokens"] == 1 and a["n_active"] == 1
            assert {"model", "step_ms", "sample_ms", "queued"} <= set(a)
            assert 1 <= a["pages_filled"] <= a["pages_reserved"]
            # the step's attention read the pages held, the new row's too
            assert (a["pages_filled"] <= a["kv_pages_read"]
                    <= a["pages_filled"] + a["n_active"])
            assert "shards" not in a      # one device: not tensor-parallel
        # admit and prefill are siblings in the first turn, in that
        # order, then the first step is queued: nothing is in flight to
        # read.  Every later turn queues the next step, then reads.
        first = [c["name"] for c in turns[0]["children"]]
        assert first == ["serve/admit", "serve/prefill"] + STEP_CHILDREN[:3]
        second = [c["name"] for c in turns[1]["children"]]
        assert second == STEP_CHILDREN[:3] + ["serve/decode_step"]
        # the spans of one request share its identifier
        assert res.request_id > 0
        mine = lambda name: [
            f for f in find_spans(tree, name)
            if f["event"]["args"]["request_id"] == res.request_id]
        for name in ("serve/admit", "serve/prefill", "serve/finish",
                     "serve/request"):
            assert len(mine(name)) == 1, name
        admit = mine("serve/admit")[0]["event"]["args"]
        assert admit["queue_wait_ms"] >= 0 and admit["pages_reserved"] == 1
        assert admit["matched_pages"] == 0 and admit["slot"] in (0, 1)
        fin = mine("serve/finish")[0]
        assert fin["event"]["args"]["reason"] == "max_tokens"
        assert fin["event"]["args"]["tokens"] == 3
        assert {"ttft_ms", "request_ms"} <= set(fin["event"]["args"])
        # the last token's finish happens while the step is recorded
        assert fin in find_spans(steps[-1]["children"], "serve/finish")
        for gone in ("serve/shard_step", "serve/prefix_attach"):
            assert not find_spans(tree, gone)
        assert validate_chrome_trace(recorder.export()) == []

    def test_idle_turns_stay_out_of_the_ring(self, tiny_engine, recorder):
        import time
        served(tiny_engine, max_new_tokens=2)
        time.sleep(0.2)                   # the loop polls every 50 ms
        turns = find_spans(span_tree(recorder.export()), "serve/iteration")
        assert turns and all(t["event"]["args"]["worked"] for t in turns)

    def test_fit_batch_golden_span_tree_in_the_ring(self, recorder):
        lm = tiny_lm()
        t, y = lm_batch()
        lm.fit_batch(t, y)
        lm.fit_batches(np.stack([t, t]), np.stack([y, y]))
        steps = find_spans(span_tree(recorder.export()), "train/step")
        assert len(steps) == 2
        for st in steps:
            assert [c["name"] for c in st["children"]] == [
                "train/h2d", "train/dispatch"]
        one, many = (st["event"]["args"] for st in steps)
        assert one == {"iteration": 1, "tokens": 32}
        assert many == {"iteration": 2, "steps": 2, "tokens": 64}

    def test_same_names_and_arguments_in_a_profile_ring_off(
            self, tiny_engine, profiler_session):
        res = served(tiny_engine, max_new_tokens=3)
        lm = tiny_lm()
        lm.fit_batch(*lm_batch())
        assert not obs_trace.tracing_enabled()
        spans = profiler_session()
        names = [n for n, *_ in spans]
        for name in ["serve/iteration", "serve/admit", "serve/prefill",
                     "serve/decode_step", "serve/finish", "train/step",
                     "train/h2d", "train/dispatch"] + STEP_CHILDREN:
            assert name in names, name
        assert "serve/request" not in names      # post-hoc: ring only
        stats = lambda name: [s for n, s, *_ in spans if n == name]
        assert len(stats("serve/decode_step")) == 2
        for a in stats("serve/decode_step"):
            assert a["tokens"] == 1 and a["n_active"] == 1
            assert a["model"] == tiny_engine.current_tag
            assert 1 <= a["pages_filled"] <= a["pages_reserved"]
            assert {"step_ms", "sample_ms", "queued"} <= set(a)
        for name in ("serve/admit", "serve/prefill", "serve/finish"):
            assert [a["request_id"] for a in stats(name)].count(
                res.request_id) == 1, name
        assert stats("serve/finish")[0]["reason"] == "max_tokens"
        assert stats("train/step") == [{"iteration": 1, "tokens": 32}]
        # nesting by containment, as in the ring: the step's children
        step = next(e for e in spans if e[0] == "serve/decode_step")
        inside = [n for n, _, t0, d in spans
                  if n != "serve/decode_step" and step[2] <= t0
                  and t0 + d <= step[2] + step[3] and n in STEP_CHILDREN]
        assert inside == STEP_CHILDREN[3:]      # the step that is read

    def test_instrumented_paths_are_bit_identical_in_every_mode(
            self, tiny_engine, profiler_session):
        def run():
            r = tiny_engine.generate(PROMPT, max_new_tokens=4,
                                     echo_logits=True)
            s = tiny_engine.generate(PROMPT, max_new_tokens=4,
                                     temperature=0.8, top_k=8, seed=5)
            lm = tiny_lm()
            losses = [float(lm.fit_batch(*lm_batch())) for _ in range(2)]
            return r.tokens, r.logits.tobytes(), s.tokens, losses

        with_profiler = run()             # the session is on, the ring off
        profiler_session()                # stops it
        obs_trace.disable_tracing()
        off = run()
        obs_trace.enable_tracing()
        try:
            with_ring = run()
        finally:
            obs_trace.disable_tracing()
        assert off == with_ring == with_profiler

    def test_queue_wait_and_pages_filled_counters(self, tiny_engine,
                                                  recorder):
        before = tiny_engine.metrics.snapshot()["queue_wait_ms"]["count"]
        futs = [tiny_engine.generate_async(PROMPT, max_new_tokens=6)
                for _ in range(3)]        # three requests on two slots
        filled = []
        while not all(f.done() for f in futs):
            filled.append(tiny_engine.metrics.snapshot()["pages_filled"])
        for f in futs:
            f.result(timeout=60)
        snap = tiny_engine.metrics_snapshot()
        assert snap["queue_wait_ms"]["count"] == before + 3
        assert snap["queue_wait_ms"]["max_ms"] >= 0
        assert max(filled) >= 1           # the gauge moved while serving
        assert snap["pages_filled"] == 0 == snap["pages_in_use"]
        steps = find_spans(span_tree(recorder.export()), "serve/decode_step")
        assert len(steps) >= 5
        for st in steps:
            a = st["event"]["args"]
            assert 1 <= a["pages_filled"] <= a["pages_reserved"]
            assert a["pages_reserved"] == 2 * a["n_active"]   # 11 tokens
        assert any(st["event"]["args"]["queued"] == 1 for st in steps)


# ---------------------------------------------------------------------------
# HTTP surface: /metrics carries the registry, /trace dumps the ring
# ---------------------------------------------------------------------------

class TestHTTPSurface:
    def _get(self, port, path):
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, json.loads(r.read())

    def test_metrics_has_registry_and_trace_endpoint(self, recorder):
        from deeplearning4j_tpu.serving import ServingMetrics
        from deeplearning4j_tpu.ui import UIServer

        m = ServingMetrics()
        m.inc("shed", 3)
        obs_trace.instant("fault", cat="chaos", kind="hung_step")
        server = UIServer(port=0).start()
        try:
            code, body = self._get(server.port, "/metrics")
            assert code == 200
            reg = body["registry"]
            assert set(reg) >= {"counters", "gauges", "histograms",
                                "collected"}
            assert reg["collected"][m.global_name]["counters"]["shed"] == 3
            # legacy keys stay
            assert "serving" in body and "sessions" in body
            code, trace = self._get(server.port, "/trace")
            assert code == 200
            assert validate_chrome_trace(trace) == []
            assert any(e.get("name") == "fault"
                       for e in trace["traceEvents"])
        finally:
            server.stop()

    def test_trace_endpoint_when_disabled(self):
        from deeplearning4j_tpu.ui import UIServer

        obs_trace.disable_tracing()
        server = UIServer(port=0).start()
        try:
            code, trace = self._get(server.port, "/trace")
            assert code == 200
            assert trace["traceEvents"] == []
            assert "disabled" in trace["metadata"]["tracing"]
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# satellites
# ---------------------------------------------------------------------------

class TestRemoteRouterDropAccounting:
    """ui/remote.py satellite: dropped records are no longer silent."""

    def _router(self, **kw):
        from deeplearning4j_tpu.ui.remote import RemoteStatsRouter

        # 127.0.0.1:9 (discard port) refuses immediately — every POST fails
        kw.setdefault("max_retries", 1)
        kw.setdefault("backoff", 0.0)
        kw.setdefault("timeout", 0.2)
        return RemoteStatsRouter("http://127.0.0.1:9", **kw)

    def test_drops_counted_in_registry_and_attribute(self, caplog):
        before = get_registry().counter(
            "ui_remote_dropped_records_total").value()
        router = self._router(max_buffer=2)
        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            for i in range(5):
                router.put_update("s", {"iteration": i})
        assert router.dropped == 3
        # the registry counter moved by exactly the dropped count
        after = get_registry().counter(
            "ui_remote_dropped_records_total").value()
        assert after - before == 3
        # newest records kept, oldest dropped
        assert [r["record"]["iteration"] for r in router._pending] == [3, 4]

    def test_warning_fires_exactly_once(self, caplog):
        router = self._router(max_buffer=1)
        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            for i in range(6):
                router.put_update("s", {"iteration": i})
        drops = [r for r in caplog.records
                 if "DROPPING stats records" in r.getMessage()]
        assert len(drops) == 1
        assert router.dropped == 5

    def test_no_drop_no_warning_under_buffer(self, caplog):
        router = self._router(max_buffer=100)
        with caplog.at_level(logging.WARNING, logger="deeplearning4j_tpu"):
            for i in range(3):
                router.put_update("s", {"iteration": i})
        assert router.dropped == 0
        assert not [r for r in caplog.records
                    if "DROPPING stats records" in r.getMessage()]


class TestProfilerDegrade:
    """ui/profiler.py satellite: no raise when the XLA profiler backend
    is unavailable — a recorded instant event instead."""

    def test_unavailable_backend_noops_with_instant(self, recorder,
                                                    tmp_path, monkeypatch):
        import jax

        from deeplearning4j_tpu.ui.profiler import profile_trace

        def boom(*a, **kw):
            raise RuntimeError("profiler backend not available")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        ran = []
        with profile_trace(str(tmp_path / "prof")):
            ran.append(True)             # the region still runs
        assert ran
        evs = [e for e in recorder.events()
               if e["name"] == "profiler/unavailable"]
        assert len(evs) == 1
        assert "RuntimeError" in evs[0]["args"]["error"]
        # the region span is recorded either way, flagged un-backed
        spans = find_spans(span_tree(recorder.export()), "profiler/trace")
        assert spans and spans[0]["event"]["args"]["backend_started"] is False

    def test_available_backend_still_used(self, tmp_path, monkeypatch):
        import jax

        from deeplearning4j_tpu.ui.profiler import profile_trace

        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda *a, **kw: calls.append(("start", kw)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop", {})))
        with profile_trace(str(tmp_path / "prof"),
                           create_perfetto_link=True):
            pass
        assert [c[0] for c in calls] == ["start", "stop"]
        assert calls[0][1].get("create_perfetto_link") is True


# ---------------------------------------------------------------------------
# heartbeat metrics export + pod aggregation (launcher side, in-process)
# ---------------------------------------------------------------------------

class TestPodTimelineMerge:
    """Acceptance e2e: a 2-process ``launch --trace`` run with a
    scheduled proc_kill produces ONE merged pod timeline showing the
    proc_kill instant followed by the relaunched incarnation's
    resume/recovery spans (docs/OBSERVABILITY.md "Reading a pod
    timeline")."""

    def test_two_proc_launch_kill_rejoin_one_timeline(self, tmp_path,
                                                      monkeypatch):
        from deeplearning4j_tpu.cli import main

        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        for var in ("DL4J_TPU_RUN_DIR", "DL4J_TPU_CHAOS",
                    "DL4J_TPU_TRACE_DIR", "DL4J_TPU_PROCESS_ID"):
            monkeypatch.delenv(var, raising=False)
        conf = (NeuralNetConfiguration.builder().seed(3)
                .layer(Dense(n_out=8, activation="relu"))
                .layer(OutputLayer(n_out=2, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(4)).build())
        conf_path = tmp_path / "conf.json"
        conf_path.write_text(json.dumps(conf.to_dict()))
        ds = data(64)
        np.savez(tmp_path / "d.npz", x=ds.features,
                 y=np.argmax(ds.labels, axis=1))
        pod_path = tmp_path / "pod.trace.json"
        run_dir = tmp_path / "run"
        try:
            rc = main([
                "launch", "--nprocs", "2", "--run-dir", str(run_dir),
                "--deadline", "300", "--max-restarts", "2",
                "--trace", str(pod_path),
                "--chaos-worker", "1:proc_kill@2",
                "--", "train", "--config", str(conf_path),
                "--data", str(tmp_path / "d.npz"),
                "--epochs", "2", "--batch-size", "16",
                "--elastic-dir", str(tmp_path / "ck"),
                "--checkpoint-every", "1",
            ])
        finally:
            obs_trace.disable_tracing()   # cmd_launch armed the global
        assert rc == 0
        with open(pod_path) as f:
            merged = json.load(f)
        assert validate_chrome_trace(merged) == []
        events = merged["traceEvents"]
        # the worker-1 death is on the timeline...
        kills = [e for e in events if e.get("name") == "fault"
                 and e.get("args", {}).get("kind") == "proc_kill"]
        assert len(kills) == 1
        t_kill = kills[0]["ts"]
        # ...the launcher observed the leave and the rejoin around it...
        leaves = [e for e in events if e.get("name") == "launcher/leave"]
        joins = [e for e in events if e.get("name") == "launcher/join"]
        assert leaves and joins
        assert min(e["ts"] for e in joins) > t_kill
        # ...and the relaunched incarnation's recovery spans FOLLOW the
        # kill: its resume-from-checkpoint and its training steps
        resumes = [e for e in events if e.get("name") == "elastic/resume"]
        assert any(e["ts"] > t_kill for e in resumes)
        late_steps = [e for e in events if e.get("name") == "train/step"
                      and e["ts"] > t_kill]
        assert late_steps
        # the killed incarnation and the relaunched one sit on DISTINCT
        # tracks, both distinct from the surviving worker 0
        pids = {e["pid"] for e in events if e.get("name") == "train/step"}
        assert len(pids) >= 3
        # per-worker metrics snapshots aggregated into the pod view
        from deeplearning4j_tpu.obs.metrics import merge_snapshots  # noqa
        obs_dir = run_dir / "obs"
        worker_files = sorted(p.name for p in obs_dir.glob("metrics_w*.json"))
        assert worker_files == ["metrics_w0.json", "metrics_w1.json"]


class TestPodMetricsAggregation:
    def test_heartbeat_exports_and_launcher_aggregates(self, tmp_path):
        from deeplearning4j_tpu.parallel.launcher import (
            Heartbeat, Membership, PodLauncher,
        )

        run_dir = str(tmp_path / "run")
        mem = Membership(run_dir, heartbeat_timeout=5.0)
        get_registry().counter("elastic_restarts_total")  # ensure present
        hb = Heartbeat(mem, process_id=0, interval=60.0)
        hb.start()
        hb.stop()
        # the export landed where pod_metrics() looks
        launcher = PodLauncher(["true"], num_workers=1, run_dir=run_dir)
        pod = launcher.pod_metrics()
        assert "w0" in pod["workers"]
        assert pod["aggregate"]["sources"] == 1
        assert "counters" in pod["launcher"]
        # launcher registers itself as a collector
        collected = get_registry().snapshot()["collected"]
        assert any(k.startswith("launcher#") for k in collected)
