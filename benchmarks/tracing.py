"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle time, time per operation, kernel and
collective time, executions of each compiled program, and the idle gaps
attributed to what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  A TPU device plane
(``/device:TPU:n``) has a line ``XLA Ops`` (what the core's instruction
stream ran, nested where a ``while`` holds a body) and a line ``XLA
Modules`` (one event per execution of a compiled program).  Host threads
are lines of the plane ``/host:CPU``; the benchmark's own
``jax.profiler.TraceAnnotation`` spans appear there under their names.
The two clocks differ by a millisecond or two.  The offset is bracketed by
the host events that carry a program execution's ``run_id``: the device
cannot start it before the host's ``DoEnqueueProgram`` nor end it after
the host's ``CompleteCallbacks``; the middle of the bracket is taken and
the annotations are moved onto the device's clock.  A gap goes to the
annotation that covers its middle, innermost first.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

#: opcodes during which the core waits on other chips
COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-reduce-start", "all-reduce-done", "all-gather",
    "all-gather-start", "all-gather-done", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-permute-start",
    "collective-permute-done"})
#: annotation name prefixes that are the benchmark's own
ANNOTATION_PREFIXES = ("bench/", "train/", "serve/")
SHORT_GAP_NS = 20_000.0
SHORT_GAPS = "(gaps under 20 us)"
UNANNOTATED = "(no annotation)"

_LAYOUT = re.compile(r"\{[^{}]*\}")
_HLO = re.compile(r"^%(?P<name>\S+) = (?P<shape>.+?) (?P<opcode>[a-z][\w\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: custom_call_target of a Pallas (Mosaic) kernel
MOSAIC_TARGET = "tpu_custom_call"


@dataclass(frozen=True)
class Op:
    """One executed device operation."""
    label: str        # "<name> <opcode> <shape>", as the breakdown prints it
    name: str
    opcode: str
    shape: str
    start_ns: float
    dur_ns: float
    self_ns: float    # duration minus the operations nested inside it
    target: str = ""  # custom_call_target of a custom call


@dataclass
class Trace:
    devices: int = 0
    window_s: float = 0.0
    busy_s: float = 0.0                       # mean over the devices
    ops: List[List[Op]] = field(default_factory=list)       # per device
    modules: List[List[Tuple[str, float, float]]] = field(default_factory=list)
    gaps: List[List[Tuple[float, float]]] = field(default_factory=list)
    annotations: List[Tuple[str, float, float]] = field(default_factory=list)
    clock_offset_ns: float = 0.0              # host clock minus device clock

    # -- reductions the per-layer metrics share -------------------------

    def op_seconds(self) -> Dict[str, float]:
        """Self time of each operation label, mean over the devices."""
        out: Dict[str, float] = defaultdict(float)
        for dev in self.ops:
            for op in dev:
                out[op.label] += op.self_ns
        return {k: v / 1e9 / self.devices for k, v in out.items()}

    def opcode_seconds(self, opcodes) -> float:
        """Self time under the given opcodes, mean over the devices."""
        total = sum(op.self_ns for dev in self.ops for op in dev
                    if op.opcode in opcodes)
        return total / 1e9 / self.devices

    def mosaic_calls(self) -> List[Op]:
        """Every executed Pallas (Mosaic) kernel call, all devices."""
        return [op for dev in self.ops for op in dev
                if op.target == MOSAIC_TARGET]

    def module_runs(self, pattern: str) -> List[float]:
        """Durations (s) of the executions, on the first device, of the
        compiled programs whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return [d / 1e9 for n, _, d in self.modules[0] if rx.search(n)]

    def module_seconds(self, pattern: str) -> float:
        """Device time inside programs matching ``pattern``, mean over
        the devices."""
        rx = re.compile(pattern)
        total = sum(d for dev in self.modules for n, _, d in dev
                    if rx.search(n))
        return total / 1e9 / self.devices

    def module_table(self) -> List[Tuple[str, int, float]]:
        """(program, executions, seconds) on the first device."""
        acc: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
        for n, _, d in self.modules[0] if self.modules else []:
            acc[n][0] += 1
            acc[n][1] += d / 1e9
        return sorted(((n, int(c), s) for n, (c, s) in acc.items()),
                      key=lambda r: -r[2])

    def idle_gaps(self) -> Dict[str, float]:
        """Idle seconds by what the host was doing, mean over devices."""
        spans = sorted(self.annotations, key=lambda a: a[1])
        out: Dict[str, float] = defaultdict(float)
        for dev in self.gaps:
            for g0, g1 in dev:
                if g1 - g0 < SHORT_GAP_NS:
                    out[SHORT_GAPS] += g1 - g0
                    continue
                mid, best = 0.5 * (g0 + g1), None
                for name, s, d in spans:
                    if s > mid:
                        break
                    if s + d >= mid and (best is None or s >= best[1]):
                        best = (name, s)
                out[best[0] if best else UNANNOTATED] += g1 - g0
        return {k: v / 1e9 / self.devices for k, v in out.items()}

    def idle_share(self) -> Optional[float]:
        """1 - busy / window, mean over the devices."""
        return 1.0 - self.busy_s / self.window_s if self.window_s else None

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k[:100], v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def parse_hlo_event(text: str) -> Tuple[str, str, str]:
    """(name, opcode, shape) of an ``XLA Ops`` event's HLO text."""
    m = _HLO.match(_LAYOUT.sub("", text))
    if not m:
        return text.lstrip("%").split(" ")[0], "", ""
    return m.group("name"), m.group("opcode"), m.group("shape")


def _self_times(events: List[Tuple[str, float, float]]) -> List[Op]:
    """Events of one line, nested by containment, with their self time:
    the duration less that of the events directly inside."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    child = [0.0] * len(events)
    stack: List[Tuple[float, int]] = []          # (end_ns, index)
    for i, (_, start, dur) in enumerate(events):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            child[stack[-1][1]] += dur
        stack.append((start + dur, i))
    out = []
    for (text, start, dur), inside in zip(events, child):
        name, opcode, shape = parse_hlo_event(text)
        label = " ".join(x for x in (name, opcode, shape) if x)
        target = _TARGET.search(text) if opcode == "custom-call" else None
        out.append(Op(label, name, opcode, shape, start, dur,
                      max(0.0, dur - inside), target.group(1) if target else ""))
    return out


def _union(intervals: List[Tuple[float, float]]):
    """(busy_ns, merged intervals) of possibly nested intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def clock_offset_ns(runs: Dict[str, Tuple[float, float]],
                    host_events: List[Tuple[str, str, float]]) -> float:
    """Host clock minus device clock, from (start, end) of device program
    executions by ``run_id`` and the host's (name, run_id, start) events."""
    lower, upper = float("-inf"), float("inf")
    for name, run_id, start in host_events:
        if run_id not in runs:
            continue
        if name == "DoEnqueueProgram":
            lower = max(lower, start - runs[run_id][0])
        elif name == "CompleteCallbacks":
            upper = min(upper, start - runs[run_id][1])
    inf = float("inf")
    if lower == -inf:
        return 0.0 if upper == inf else upper
    if upper == inf or lower > upper:
        return lower
    return 0.5 * (lower + upper)


def reduce_xplane(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    trace = Trace()
    lo, hi = float("inf"), float("-inf")
    busy = []
    runs: Dict[str, Tuple[float, float]] = {}
    host_events: List[Tuple[str, str, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops_ev, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops_ev = [(e.name, float(e.start_ns), float(e.duration_ns))
                              for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(re.sub(r"\(\d+\)$", "", e.name),
                             float(e.start_ns), float(e.duration_ns))
                            for e in line.events]
                    if not trace.ops:        # the first device's clock
                        for e in line.events:
                            rid = dict(e.stats).get("run_id")
                            runs[str(rid)] = (float(e.start_ns), float(
                                e.start_ns + e.duration_ns))
            if not ops_ev:
                continue
            ops = _self_times(ops_ev)
            busy_ns, merged = _union(
                [(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
            trace.ops.append(ops)
            trace.modules.append(mods)
            trace.gaps.append([(a[1], b[0]) for a, b in
                               zip(merged, merged[1:])])
            busy.append(busy_ns)
            lo, hi = min(lo, merged[0][0]), max(hi, merged[-1][1])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIXES):
                        trace.annotations.append(
                            (e.name, float(e.start_ns), float(e.duration_ns)))
                    elif e.name in ("DoEnqueueProgram", "CompleteCallbacks"):
                        host_events.append((e.name, str(dict(e.stats).get(
                            "run_id")), float(e.start_ns)))
    trace.devices = len(trace.ops)
    trace.clock_offset_ns = clock_offset_ns(runs, host_events)
    trace.annotations = [(n, s - trace.clock_offset_ns, d)
                         for n, s, d in trace.annotations]
    if trace.devices:
        trace.window_s = (hi - lo) / 1e9
        trace.busy_s = sum(busy) / 1e9 / trace.devices
    return trace
