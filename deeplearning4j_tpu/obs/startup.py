"""The start-up account and the compile watch.

Two things the program could not say about itself: where its start-up
goes, and that (and what) it compiled.  Both extend the one span system
(:mod:`.trace`) and the one registry (:mod:`.metrics`); neither runs on
a per-step or per-token path.

**Phases.**  ``phase(name)`` is a ``span()`` that is counted whatever
the sinks: while it is open it stands on the calling thread's stack of
open phases, and on exit its seconds go to
``startup_seconds_total{phase=<name>}`` (and 1 to
``startup_phases_total``) in the process-global registry and one record
to a bounded log.  With the ring on it is the same span there, with a
profiler session on the same annotation.  It is for LOAD-TIME sites
only (a constructor, ``load()``, a first step): each one takes a lock,
two clock reads and a dict, which a decode turn or a steady train step
must not pay (``tests/test_startup_account.py`` holds that they open
none).

**The compile watch.**  ``watch_compiles()`` listens, once a process, to
what JAX reports of its own compiles (``jax.monitoring``: a trace, a
lowering, a backend compile or a read of the persistent cache, each
with the function's name) and books every event under the innermost
phase open on the thread it fell on, or ``none``:
``compile_seconds_total{stage,phase}``, ``compile_events_total`` and
``compile_cache_total{result,phase}``, a record in a log of its own,
and with the ring on a ``compile/<stage>`` span where it happened.
Nothing is counted twice: an event's seconds are booked less what
events nested in it on the same thread already booked, and the traces
of the functions jitted INSIDE a traced function are dropped for the
outermost (a trace waits, unbooked, for the event that follows it, so
that an outer one can still absorb it).  ``backend_compile_duration``
after a ``cache_hits`` on its thread is the read of the cache and the
load of the executable, not a compile: it is booked whole as
``cache_read``.

A backend compile or a cache read outside every phase is what a loaded
engine or a model past its first step must never see:
``on_unphased_compile`` hands each one to whoever asked (``DecodeEngine``
counts ``serve_time_compiles``, ``ShardedTransformerLM`` records a
recompile of its step).

``GET /metrics`` carries both logs as the ``startup`` collector.
``obs`` stays free of JAX at import: ``watch_compiles`` imports it.
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque
from typing import Callable, List, Optional

from . import trace as _trace
from .metrics import get_registry

PHASE_LOG_CAPACITY = 256
COMPILE_LOG_CAPACITY = 512
#: the label of an event that fell outside every phase
NO_PHASE = "none"
#: an event lies inside another if it began no earlier and ended no
#: later, to this (a listener runs some microseconds after the interval
#: it is told of, and not always the same number)
_NEST_TOL_S = 5e-5

_DURATION_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}

_lock = threading.Lock()
_phase_log: deque = deque(maxlen=PHASE_LOG_CAPACITY)
_compile_log: deque = deque(maxlen=COMPILE_LOG_CAPACITY)
_dropped = {"phases": 0, "compiles": 0}
_watching = False
_hooks: List[object] = []           # weak (bound methods) or strong refs
#: of the calling thread: ``phases``, the open ones, outermost first, and
#: ``watch``, what the compile watch keeps between two events; both go
#: with their thread
_local = threading.local()


# -- phases --------------------------------------------------------------------

#: ``span()``, under a name the taxonomy check (GC401) does not read: it
#: wants a literal name at every call, and a phase's is its caller's,
#: checked at the call of ``phase()``
_span = _trace.span


def _open_phases() -> list:
    try:
        return _local.phases
    except AttributeError:
        _local.phases = []
        return _local.phases


class _Phase:
    """A ``span()`` and, whatever the sinks, a count (see :func:`phase`)."""

    __slots__ = ("name", "args", "_span", "_t0")

    def __init__(self, name: str, cat: str, args: dict):
        self.name = name
        self.args = args
        self._span = _span(name, cat, **args)

    def set(self, **args) -> "_Phase":
        self.args.update(args)
        self._span.set(**args)
        return self

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.monotonic()
        _open_phases().append(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        seconds = max(0.0, time.monotonic() - self._t0)
        self._span.__exit__(exc_type, exc, tb)
        stack = _open_phases()
        if self in stack:           # not one closed on another thread
            stack.remove(self)
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        reg = get_registry()
        reg.counter("startup_seconds_total").inc(seconds, phase=self.name)
        reg.counter("startup_phases_total").inc(1, phase=self.name)
        with _lock:
            _flush(_watch_of_thread())
            if len(_phase_log) == PHASE_LOG_CAPACITY:
                _dropped["phases"] += 1
            _phase_log.append({"name": self.name, "t_start": self._t0,
                               "seconds": seconds, "args": dict(self.args)})
        return False


def phase(name: str, cat: str = "", **args) -> _Phase:
    """``with phase("serve/load", tag=t) as ph: ...`` — a ``span()``
    (ring when enabled, profiler annotation when a session is on, same
    arguments, ``.set()`` works) that is ALSO counted with both sinks
    off: ``startup_seconds_total{phase=name}``, a record in the
    ``startup`` collector, and the label of every compile that falls
    inside it.  For load-time sites only: never open one per step or
    per token."""
    return _Phase(name, cat, args)


def current_phase() -> Optional[str]:
    """Name of the innermost phase open on the calling thread."""
    stack = _open_phases()
    return stack[-1].name if stack else None


# -- the compile watch -------------------------------------------------------

class _ThreadWatch:
    """What the watch keeps of one thread between two events."""

    __slots__ = ("pending", "booked", "cache", "retrieval", "saved")

    def __init__(self):
        self.pending: List[dict] = []     # traces not booked yet
        # (t_start, t_end, seconds as reported) of the last booked events:
        # what an event that CONTAINS them takes off its own seconds
        self.booked: deque = deque(maxlen=256)
        self.cache: Optional[str] = None  # "hit" / "miss" since the last compile
        self.retrieval: Optional[float] = None
        self.saved: Optional[float] = None


def _watch_of_thread() -> _ThreadWatch:
    try:
        return _local.watch
    except AttributeError:
        _local.watch = _ThreadWatch()
        return _local.watch


def _began(ev: dict) -> float:
    return ev["t_end"] - ev["seconds"]


def _inside(t_start: float, t_end: float, ev: dict) -> bool:
    """Whether the interval lies inside the event's."""
    return (t_start >= _began(ev) - _NEST_TOL_S
            and t_end <= ev["t_end"] + _NEST_TOL_S)


def _book(w: _ThreadWatch, ev: dict) -> None:
    """Count one event (``_lock`` held): its seconds less those of the
    events booked inside it, one record, one ring span."""
    t_start = _began(ev)
    inside = 0.0
    while w.booked and _inside(*w.booked[-1][:2], ev):
        inside += w.booked.pop()[2]
    w.booked.append((t_start, ev["t_end"], ev["seconds"]))
    own = max(0.0, ev["seconds"] - inside)
    stage, label = ev["stage"], ev["phase"] or NO_PHASE
    reg = get_registry()
    reg.counter("compile_seconds_total").inc(own, stage=stage, phase=label)
    reg.counter("compile_events_total").inc(1, stage=stage, phase=label)
    if len(_compile_log) == COMPILE_LOG_CAPACITY:
        _dropped["compiles"] += 1
    _compile_log.append({**ev, "seconds": own, "phase": label})
    _trace.complete_at(f"compile/{stage}", t_start, ev["t_end"],
                       cat="compile", fun_name=ev["fun_name"], phase=label)


def _flush(w: _ThreadWatch) -> None:
    """Book the traces that waited for an outer one (``_lock`` held)."""
    if w.pending:
        pending, w.pending = w.pending, []
        for ev in pending:
            _book(w, ev)


def _on_duration(event: str, seconds: float, **kw) -> None:
    stage = _DURATION_STAGES.get(event)
    if stage is None:
        if event == _CACHE_RETRIEVAL or event == _CACHE_SAVED:
            with _lock:
                w = _watch_of_thread()
                if event == _CACHE_RETRIEVAL:
                    w.retrieval = seconds
                else:
                    w.saved = seconds
        return
    t_end = time.monotonic()
    ev = {"stage": stage, "fun_name": str(kw.get("fun_name", "")),
          "seconds": max(0.0, float(seconds)), "t_end": t_end,
          "phase": current_phase()}
    with _lock:
        w = _watch_of_thread()
        # what was traced inside this event came first, and is part of it:
        # the functions jitted inside a traced one, a lowering rule's own
        while w.pending and _inside(_began(w.pending[-1]),
                                    w.pending[-1]["t_end"], ev):
            w.pending.pop()
        if stage == "trace":
            w.pending.append(ev)
            return
        _flush(w)
        if stage == "lower":
            _book(w, ev)
            return
        if w.cache == "hit":
            ev["stage"] = "cache_read"
            ev["retrieval_seconds"] = w.retrieval
            ev["saved_seconds"] = w.saved
        w.cache = w.retrieval = w.saved = None
        _book(w, ev)
        unphased = ev["phase"] is None
        hooks = list(_hooks) if unphased else ()
    if unphased:
        _tell(ev, hooks)


def _on_event(event: str, **_) -> None:
    result = _CACHE_EVENTS.get(event)
    if result is None:
        return
    label = current_phase() or NO_PHASE
    get_registry().counter("compile_cache_total").inc(
        1, result=result, phase=label)
    with _lock:
        _watch_of_thread().cache = result


def _tell(ev: dict, hooks) -> None:
    """Hand a compile that no phase covers to whoever asked."""
    dead = []
    for ref in hooks:
        fn = ref() if isinstance(ref, weakref.WeakMethod) else ref
        if fn is None:
            dead.append(ref)
            continue
        fn(ev)
    if dead:
        with _lock:
            for ref in dead:
                if ref in _hooks:
                    _hooks.remove(ref)


def on_unphased_compile(fn: Callable[[dict], None]) -> None:
    """Call ``fn(event)`` for every backend compile or cache read that
    falls outside every phase, on the thread that compiled: ``event``
    has ``stage`` (``backend`` / ``cache_read``), ``fun_name``,
    ``seconds`` and ``t_end``.  A bound method is held weakly: when its
    owner dies the hook goes with it.  Asking twice is asking once."""
    try:
        ref: object = weakref.WeakMethod(fn)
    except TypeError:
        ref = fn
    with _lock:
        if ref not in _hooks:
            _hooks.append(ref)


def watch_compiles() -> None:
    """Listen to JAX's own compile events (module docstring).
    Idempotent: the listeners are registered once a process."""
    global _watching
    with _lock:
        if _watching:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _watching = True


# -- the collector ---------------------------------------------------------------

def startup_account() -> dict:
    """Both logs, oldest first: what ``GET /metrics`` carries under
    ``registry.collected.startup``."""
    with _lock:
        _flush(_watch_of_thread())      # the caller's own waiting traces
        return {"watching": _watching,
                "phases": list(_phase_log),
                "phases_dropped": _dropped["phases"],
                "compiles": list(_compile_log),
                "compiles_dropped": _dropped["compiles"]}


get_registry().register_collector("startup", startup_account)
