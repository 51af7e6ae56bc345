"""The start-up account and the compile watch (obs/startup.py, PR 39).

What is held here:
  - a phase is counted and logged with both sinks off, and is the same
    span in the ring when it is on; phases nest, and a compile event
    lands on the innermost
  - nothing is counted twice: a cold ``jit`` that jits inside counts one
    trace, one lowering, one backend compile; a compile served by the
    persistent cache is a ``hit`` and a ``cache_read``, not a ``backend``
  - a compile outside every phase is ``phase=none``; while a loaded
    engine is alive it is ``serve_time_compiles`` and the instant
    ``serve/compile_after_load``; a step program of an LM that compiles
    again is the instant ``train/recompile``
  - the program's own load-time sites: ``lm/init``, ``train/first_step``,
    ``serve/load`` and its children
  - a steady ``fit_batch`` step and a decode turn open no phase
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.obs import startup
from deeplearning4j_tpu.obs import trace as obs_trace
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM
from deeplearning4j_tpu.serving import DecodeEngine

VOCAB, MAXLEN = 48, 32
X = np.ones((4, 4), np.float32)


def _count(name, **labels):
    return obs.get_registry().counter(name).value(**labels)


def _phases(name):
    return [p for p in obs.startup_account()["phases"] if p["name"] == name]


def _compiles(phase):
    return [c for c in obs.startup_account()["compiles"]
            if c["phase"] == phase]


def _nested_jits(scale):
    """A fresh function (so a fresh trace) that jits inside: ``jnp.sin``
    and ``@`` are jitted functions of their own."""
    import jax.numpy as jnp

    def my_step(x):
        return jnp.sin(x * scale) @ x

    return my_step


@pytest.fixture
def ring():
    was = obs.get_recorder()      # another module may have left one on
    rec = obs.enable_tracing()
    yield rec
    obs_trace.set_recorder(was)


@pytest.fixture
def sinks_off():
    was = obs.get_recorder()      # another module may have left one on
    obs_trace.set_recorder(None)
    yield
    obs_trace.set_recorder(was)


@pytest.fixture(scope="module", autouse=True)
def watching():
    obs.watch_compiles()


# -- phases ------------------------------------------------------------------------

def test_a_phase_is_counted_and_logged_with_both_sinks_off(sinks_off):
    assert not obs.tracing_enabled()
    with obs.phase("t/off", cat="test", key="a") as ph:
        time.sleep(0.01)
        ph.set(source="built")
    assert _count("startup_phases_total", phase="t/off") == 1
    secs = _count("startup_seconds_total", phase="t/off")
    assert 0.01 <= secs < 1.0
    (rec,) = _phases("t/off")
    assert rec["seconds"] == secs
    assert rec["args"] == {"key": "a", "source": "built"}
    assert rec["t_start"] <= time.monotonic() - secs
    # the collector GET /metrics serves
    collected = obs.get_registry().snapshot()["collected"]["startup"]
    assert rec in collected["phases"] and collected["watching"] is True


def test_a_phase_is_the_same_span_in_the_ring(ring):
    with obs.phase("t/ring", cat="test", key="b") as ph:
        ph.set(n=2)
    (ev,) = [e for e in ring.events() if e["name"] == "t/ring"]
    (rec,) = _phases("t/ring")
    assert ev["ph"] == "X" and ev["cat"] == "test"
    assert ev["args"] == {"key": "b", "n": 2} == rec["args"]
    # the count lies inside the ring's span, two clock reads apart
    start_us = ring._abs_us(rec["t_start"])
    assert ev["ts"] <= start_us + 0.1
    assert start_us + rec["seconds"] * 1e6 <= ev["ts"] + ev["dur"] + 0.1
    assert ev["dur"] == pytest.approx(rec["seconds"] * 1e6, abs=200.0)
    assert _count("startup_phases_total", phase="t/ring") == 1


def test_a_phase_that_raises_is_counted_and_says_so():
    with pytest.raises(KeyError):
        with obs.phase("t/raises"):
            raise KeyError("x")
    (rec,) = _phases("t/raises")
    assert rec["args"] == {"error": "KeyError"}
    assert startup.current_phase() is None


def test_phases_nest_and_a_compile_lands_on_the_innermost():
    import jax

    with obs.phase("t/outer"):
        assert startup.current_phase() == "t/outer"
        with obs.phase("t/inner"):
            assert startup.current_phase() == "t/inner"
            jax.jit(_nested_jits(2.0))(X)
        assert startup.current_phase() == "t/outer"
    assert startup.current_phase() is None
    assert _count("compile_events_total", stage="backend", phase="t/inner") == 1
    assert _count("compile_events_total", stage="backend", phase="t/outer") == 0
    assert (_count("startup_seconds_total", phase="t/inner")
            <= _count("startup_seconds_total", phase="t/outer"))


def test_the_log_is_bounded_and_counts_what_it_drops():
    before = obs.startup_account()["phases_dropped"]
    for _ in range(startup.PHASE_LOG_CAPACITY + 3):
        with obs.phase("t/many"):
            pass
    acc = obs.startup_account()
    assert len(acc["phases"]) == startup.PHASE_LOG_CAPACITY
    assert acc["phases_dropped"] >= before + 3
    assert _count("startup_phases_total",
                  phase="t/many") == startup.PHASE_LOG_CAPACITY + 3


# -- the compile watch -------------------------------------------------------------

def test_watch_compiles_twice_registers_once():
    from jax._src import monitoring

    obs.watch_compiles()
    obs.watch_compiles()
    assert monitoring.get_event_duration_listeners().count(
        startup._on_duration) == 1
    assert monitoring.get_event_listeners().count(startup._on_event) == 1


def test_a_cold_jit_counts_each_stage_once_though_it_jits_inside():
    import jax

    with obs.phase("t/cold"):
        jax.jit(_nested_jits(3.0))(X)
    for stage in ("trace", "lower", "backend"):
        assert _count("compile_events_total", stage=stage,
                      phase="t/cold") == 1, stage
    assert _count("compile_events_total", stage="cache_read",
                  phase="t/cold") == 0
    names = {c["stage"]: c["fun_name"] for c in _compiles("t/cold")}
    assert names == {"trace": "my_step", "lower": "jit(my_step)",
                     "backend": "jit(my_step)"}
    # the parts are no more than the phase: nothing counted twice
    parts = sum(_count("compile_seconds_total", stage=s, phase="t/cold")
                for s in ("trace", "lower", "backend"))
    assert 0 < parts <= _count("startup_seconds_total", phase="t/cold")


def test_a_trivial_jit_still_counts_each_stage_once():
    """A program whose trace and lowering take a fraction of a
    millisecond: each stage follows the last within that, and none is
    taken for a part of the next."""
    import jax

    for i in range(5):
        with obs.phase(f"t/trivial{i}"):
            jax.jit(lambda x: x + 1)(X)
        for stage in ("trace", "lower", "backend"):
            assert _count("compile_events_total", stage=stage,
                          phase=f"t/trivial{i}") == 1, (i, stage)
        seconds = {c["stage"]: c["seconds"] for c in _compiles(f"t/trivial{i}")}
        assert all(v > 0 for v in seconds.values()), seconds


def test_a_threads_waiting_trace_is_its_own_to_book(ring):
    """A trace with no lowering after it waits on its thread for the next
    event there; a scrape from another thread leaves it alone (it would
    enter the ring under the scraper's thread), the thread's own books it."""
    import threading

    import jax

    def traced_only(x):
        return x * 3

    seen = {}

    def work():
        with obs.phase("t/thread"):
            jax.make_jaxpr(traced_only)(X)
            seen["before"] = _count("compile_events_total", stage="trace",
                                    phase="t/thread")
        seen["tid"] = threading.get_ident()

    obs.startup_account()
    t = threading.Thread(target=work)
    t.start()
    t.join()
    assert seen["before"] == 0          # waited while the phase was open
    assert _count("compile_events_total", stage="trace",
                  phase="t/thread") == 1    # booked at the phase's exit
    (ev,) = [e for e in ring.events() if e["name"] == "compile/trace"
             and e["args"]["phase"] == "t/thread"]
    assert ev["tid"] == seen["tid"]


def test_an_eager_compile_inside_a_trace_is_taken_off_the_trace():
    """An outer trace CONTAINS what compiled eagerly while it ran: the
    outer's seconds are booked less the inner events'."""
    import jax
    import jax.numpy as jnp

    def outer(x):
        # runs at trace time on a concrete array: a compile of its own
        with jax.ensure_compile_time_eval():
            jax.jit(_nested_jits(5.0))(X)
        return jnp.cos(x)

    with obs.phase("t/eager"):
        jax.jit(outer)(X)
    recs = _compiles("t/eager")
    assert [(c["stage"], c["fun_name"]) for c in recs].count(
        ("backend", "jit(my_step)")) == 1
    (trace,) = [c for c in recs if c["stage"] == "trace"
                and c["fun_name"] == "outer"]
    inner = sum(c["seconds"] for c in recs if "my_step" in c["fun_name"])
    assert inner > 0
    total = sum(c["seconds"] for c in recs)
    assert total <= _count("startup_seconds_total", phase="t/eager")
    assert trace["seconds"] + inner <= _count("startup_seconds_total",
                                              phase="t/eager")


def test_a_compile_the_persistent_cache_serves_is_a_hit_and_a_cache_read(
        tmp_path):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was_dir = jax.config.jax_compilation_cache_dir
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        f = _nested_jits(7.0)
        with obs.phase("t/miss"):
            jax.jit(f)(X)
        jax.clear_caches()          # the process forgets; the disk does not
        with obs.phase("t/hit"):
            jax.jit(f)(X)
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
        jax.config.update("jax_compilation_cache_dir", was_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was_min)
        cc.reset_cache()
    assert _count("compile_cache_total", result="miss", phase="t/miss") == 1
    assert _count("compile_events_total", stage="backend", phase="t/miss") == 1
    assert _count("compile_cache_total", result="hit", phase="t/hit") == 1
    assert _count("compile_events_total", stage="cache_read",
                  phase="t/hit") == 1
    assert _count("compile_events_total", stage="backend", phase="t/hit") == 0
    # the trace and the lowering are paid again: the cache cannot save them
    assert _count("compile_events_total", stage="trace", phase="t/hit") == 1
    assert _count("compile_events_total", stage="lower", phase="t/hit") == 1
    (read,) = [c for c in _compiles("t/hit") if c["stage"] == "cache_read"]
    # booked whole: the retrieval and the executable's load
    assert 0 < read["retrieval_seconds"] <= read["seconds"]


def test_a_compile_outside_every_phase_carries_phase_none(ring):
    import jax

    before = _count("compile_events_total", stage="backend", phase="none")
    jax.jit(_nested_jits(11.0))(X)
    assert _count("compile_events_total", stage="backend",
                  phase="none") == before + 1
    spans = [e for e in ring.events() if e["name"].startswith("compile/")]
    assert {e["name"] for e in spans} == {"compile/trace", "compile/lower",
                                          "compile/backend"}
    assert all(e["args"]["phase"] == "none" for e in spans)
    assert {e["args"]["fun_name"] for e in spans} == {"my_step",
                                                      "jit(my_step)"}


def test_the_hook_of_a_dead_owner_goes_with_it():
    import gc

    import jax

    class Owner:
        seen = 0

        def on(self, event):
            Owner.seen += 1

    o = Owner()
    obs.on_unphased_compile(o.on)
    jax.jit(_nested_jits(13.0))(X)
    assert Owner.seen == 1
    with obs.phase("t/phased"):
        jax.jit(_nested_jits(17.0))(X)      # inside a phase: not told
    assert Owner.seen == 1
    obs.on_unphased_compile(o.on)           # asking twice is asking once
    jax.jit(_nested_jits(29.0))(X)
    assert Owner.seen == 2
    del o
    gc.collect()
    jax.jit(_nested_jits(19.0))(X)
    assert Owner.seen == 2
    assert not any(isinstance(r, type(None)) for r in startup._hooks)


# -- the program's own sites ---------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    import jax

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:1])
    n = len(_phases("lm/init"))
    lm = ShardedTransformerLM(vocab_size=VOCAB, n_layers=2, d_model=32,
                              n_heads=2, max_len=MAXLEN, mesh=mesh, seed=11)
    lm.init_phases = _phases("lm/init")[n:]
    return lm


@pytest.fixture(scope="module")
def trained(lm):
    """The LM after its first ``fit_batch``, and the phases that opened."""
    toks = np.zeros((2, 16), np.int32)
    n = len(_phases("train/first_step"))
    lm.fit_batch(toks, toks)
    return lm, _phases("train/first_step")[n:]


@pytest.fixture(scope="module")
def engine(lm):
    # an LM of its own: ``fit_batch`` donates the trained one's weights
    lm = ShardedTransformerLM(vocab_size=VOCAB, n_layers=2, d_model=32,
                              n_heads=2, max_len=MAXLEN, mesh=lm.mesh, seed=3)
    before = {k: _count("startup_phases_total", phase=k)
              for k in ("serve/load", "serve/load_executable", "serve/lower",
                        "serve/compile", "serve/first_run")}
    eng = DecodeEngine(lm, max_slots=2, page_size=8, default_max_new=4).load()
    eng.opened = {k: _count("startup_phases_total", phase=k) - v
                  for k, v in before.items()}
    yield eng
    eng.shutdown()


def test_the_constructor_is_the_phase_lm_init(lm):
    (rec,) = lm.init_phases
    assert rec["args"] == {"drew_params": True, "leaves": 18}
    # it drew its weights leaf by leaf: compiles of its own, under its name
    assert _count("compile_events_total", stage="backend",
                  phase="lm/init") > 0
    again = ShardedTransformerLM(
        vocab_size=VOCAB, n_layers=2, d_model=32, n_heads=2, max_len=MAXLEN,
        mesh=lm.mesh, params=lm.params)
    assert _phases("lm/init")[-1]["args"]["drew_params"] is False
    assert again.params is not None


def test_the_first_fit_batch_is_the_phase_train_first_step(trained):
    lm, opened = trained
    (rec,) = opened
    assert rec["args"] == {"fun": "fit_batch"}
    assert lm.iteration == 1
    step = [c for c in _compiles("train/first_step")
            if c["fun_name"] == "jit(step)"]
    assert {c["stage"] for c in step} >= {"lower", "backend"}
    n = len(_phases("train/first_step"))
    toks = np.zeros((2, 2, 16), np.int32)
    lm.fit_batches(toks, toks)              # its own first call
    assert [p["args"] for p in _phases("train/first_step")[n:]] == [
        {"fun": "fit_batches"}]


def test_load_is_the_phase_serve_load_with_a_phase_an_executable(engine):
    n = engine.compile_cache_size()
    assert engine.opened == {"serve/load": 1, "serve/load_executable": n,
                             "serve/lower": n, "serve/compile": n,
                             "serve/first_run": n}
    load = _phases("serve/load")[-1]
    assert load["args"] == {"tag": engine.current_tag, "role": "unified",
                            "executables": n, "bundle_hits": 0,
                            "bundle_misses": n}
    counters = engine.metrics.snapshot()["counters"]
    assert (counters["bundle_hits"], counters["bundle_misses"]) == (0, n)
    mine = [p for p in obs.startup_account()["phases"]
            if p["t_start"] >= load["t_start"]
            and p["t_start"] + p["seconds"] <= load["t_start"]
            + load["seconds"] + 1e-6]
    by_name = {}
    for p in mine:
        by_name.setdefault(p["name"], []).append(p)
    keys = {p["args"]["key"] for p in by_name["serve/load_executable"]}
    assert {"step", "sample", "sample1", "reset", "scrub", "join"} <= keys
    assert all(p["args"]["source"] == "built"
               for p in by_name["serve/load_executable"])
    # the three children cover their executable, the executables the load
    for exe in by_name["serve/load_executable"]:
        parts = sum(p["seconds"] for name in ("serve/lower", "serve/compile",
                                              "serve/first_run")
                    for p in by_name[name]
                    if p["args"]["key"] == exe["args"]["key"])
        assert 0.9 * exe["seconds"] <= parts <= exe["seconds"]
    whole = sum(p["seconds"] for p in by_name["serve/load_executable"])
    assert whole <= load["seconds"]
    # the serve/warmup span still counts its seconds
    assert 0 < counters["warmup_seconds_total"] <= load["seconds"]


def test_a_compile_after_load_is_counted_with_what_compiled(engine, ring):
    import jax

    assert "serve_time_compiles" in engine.metrics.snapshot()["counters"]
    before = engine.metrics.counter_value("serve_time_compiles")

    def late_helper(x):
        return x * 2 + 1

    jax.jit(late_helper)(X)
    assert engine.metrics.counter_value("serve_time_compiles") == before + 1
    (ev,) = [e for e in ring.events()
             if e["name"] == "serve/compile_after_load"]
    assert ev["ph"] == "i"
    assert ev["args"]["fun_name"] == "jit(late_helper)"
    assert ev["args"]["stage"] == "backend" and ev["args"]["seconds"] > 0
    # a compile that a phase covers (another model loading) is none
    with obs.phase("t/other_load"):
        jax.jit(_nested_jits(23.0))(X)
    assert engine.metrics.counter_value("serve_time_compiles") == before + 1


def test_a_compile_inside_a_later_train_step_is_a_recompile(trained, ring):
    lm, _ = trained
    toks = np.zeros((3, 16), np.int32)       # a new batch shape: a new step
    it = lm.iteration + 1
    lm.fit_batch(toks, toks)
    recs = [e for e in ring.events() if e["name"] == "train/recompile"]
    assert recs, "the step recompiled and nothing said so"
    assert {(e["args"]["fun_name"], e["args"]["iteration"])
            for e in recs} == {("jit(step)", it)}
    assert all(e["args"]["seconds"] > 0 for e in recs)
    # the compile's own span lies inside the train/step it stalled
    (step,) = [e for e in ring.events() if e["name"] == "train/step"
               and e["args"]["iteration"] == it]
    (comp,) = [e for e in ring.events() if e["name"] == "compile/backend"
               and e["args"]["fun_name"] == "jit(step)"]
    assert step["ts"] <= comp["ts"]
    assert comp["ts"] + comp["dur"] <= step["ts"] + step["dur"] + 1.0


def test_a_steady_step_and_a_decode_turn_open_no_phase(trained, engine,
                                                       monkeypatch, sinks_off):
    """With both sinks off they run no code of the account: ``phase``
    itself is made to fail, and the listeners are not called."""
    lm, _ = trained
    toks = np.zeros((2, 16), np.int32)
    engine.generate([1, 2, 3], max_new_tokens=3)     # every path warm
    called = []

    def refuse(*a, **kw):
        raise AssertionError("a phase opened on a steady path")

    monkeypatch.setattr(startup, "phase", refuse)
    monkeypatch.setattr(startup._Phase, "__enter__", refuse)
    monkeypatch.setattr(startup, "_book",
                        lambda w, ev: called.append(ev["fun_name"]))
    assert not obs.tracing_enabled()
    opened = len(obs.startup_account()["phases"])
    for _ in range(2):
        float(lm.fit_batch(toks, toks))
    out = engine.generate([4, 5, 6, 7], max_new_tokens=4)
    assert len(out.tokens) == 4
    assert called == []
    assert len(obs.startup_account()["phases"]) == opened
    # and the disabled span is still the one shared null object
    assert obs.span("train/step") is obs_trace._NULL_SPAN

