"""The program's own spans, read from a traced run's profile.

While a profiler session is active, every ``obs.trace.span`` of the
program is also a profiler annotation (``deeplearning4j_tpu/obs/trace.py``),
so it lands in the ``/host:CPU`` plane of the ``.xplane.pb`` with its
arguments as the event's stats.  ``tracing.reduce_xplane`` keeps such an
event's name and interval only; the per-layer metrics that read the
program's spans need the arguments and the nesting too, so this opens
the same file once more and keeps, per host thread, the events under
``serve/`` and ``train/`` as a forest nested by containment.

A traced run also puts the benchmark's own annotation round some of the
program's calls (``runners/serve_lm.py::_annotate_engine``), under the
name the program now gives its own span inside: where a span's direct
child has its name, the inner one is kept and takes the outer's place,
so nothing is counted twice.  A program that emits no spans (one older
than ``obs``'s bridge to the profiler) leaves the wrappers alone, and
the readers find nothing under the names they look for.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks import harness, tracing

#: name prefixes of the program's span taxonomy (docs/OBSERVABILITY.md)
PREFIXES = ("serve/", "train/")

#: (name, start_ns, duration_ns, stats) of one host event
Event = Tuple[str, float, float, dict]


@dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float
    args: Dict[str, object]
    children: List["Span"] = field(default_factory=list)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def walk(self) -> Iterator["Span"]:
        """This span and everything beneath it."""
        yield self
        for c in self.children:
            yield from c.walk()

    def inside(self, name: str) -> List["Span"]:
        """The spans named ``name`` beneath this one."""
        return [s for c in self.children for s in c.walk() if s.name == name]


def nest(events: List[Event]) -> List[Span]:
    """One thread's events as a forest, nested by containment."""
    roots: List[Span] = []
    stack: List[Span] = []
    for name, start, dur, args in sorted(events, key=lambda e: (e[1], -e[2])):
        span = Span(name, start, dur, dict(args))
        while stack and start >= stack[-1].end_ns:
            stack.pop()
        (stack[-1].children if stack else roots).append(span)
        stack.append(span)
    return roots


def keep_inner(forest: List[Span]) -> List[Span]:
    """Where a span's direct child has its name (the benchmark's wrapper
    round the program's own span), the inner one takes its place."""
    out: List[Span] = []
    for span in forest:
        span.children = keep_inner(span.children)
        same = [c for c in span.children if c.name == span.name]
        out.extend(same if same else [span])
    return out


@functools.lru_cache(maxsize=1)
def load(path: str) -> List[Span]:
    """The program's spans in one ``.xplane.pb``: a forest per host
    thread, all threads in one list."""
    from jax.profiler import ProfileData

    forest: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns),
                       dict(e.stats))
                      for e in line.events if e.name.startswith(PREFIXES)]
            forest.extend(keep_inner(nest(events)))
    return forest


def of(observed) -> Optional[List[Span]]:
    """The program's spans of the run's traced part, or None where
    nothing was traced (a rehearsal, an untraced run)."""
    if observed.trace is None:
        return None
    trace_dir = os.path.join(harness.ROOT, ".bench", "trace",
                             observed.cell.name)
    return load(tracing.find_xplane(trace_dir))


def named(forest: Optional[List[Span]], name: str) -> List[Span]:
    """Every span named ``name`` anywhere in the forest."""
    return [s for root in forest or [] for s in root.walk() if s.name == name]
