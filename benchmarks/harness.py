"""The harness: everything a run does that is not particular to one
configuration, traffic mix or per-layer metric.

It finds the cell in ``BENCHMARK.json`` and, by the names there, the
configuration's file and plain reference (``configs/``), the mix's
parameters (``traffic/``), the runner the configuration names
(``runners/``) and each per-layer metric's reader (``layer_metrics/``).
A later PR adds a cell, a configuration, a mix or a metric by adding
files and entries; nothing here names one.

A run: refuse without the chips, point the compile cache at its fixed
directory, let the runner set the system up and warm it (``setup_s``
ends where the window starts), measure the window (with ``--trace 1``
a few seconds of it under the profiler), free the program's state,
compare with the reference, print the facts on earlier lines and the
contract's object on the last.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import resource
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: seconds of the window a traced run keeps under the profiler
TRACE_SECONDS = 3.0


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


# -- finding things by name ---------------------------------------------------

def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reference(config: dict):
    """The configuration's plain reference: the module beside its file."""
    cfg_dir = os.path.join(HERE, "configs")
    if cfg_dir not in sys.path:       # a reference may share mathematics
        sys.path.insert(0, cfg_dir)   # with a sibling file
    return _load_module(os.path.join(cfg_dir, config["reference"]),
                        "bench_reference_" + config["name"].replace("-", "_"))


def load_runner(name: str):
    return _load_module(os.path.join(HERE, "runners", f"{name}.py"),
                        f"bench_runner_{name}")


def load_layer_metric(name: str):
    return _load_module(os.path.join(HERE, "layer_metrics", f"{name}.py"),
                        "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def load_peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}: "
                       f"add it to benchmarks/peaks.json with its source")
    return table[device_kind]


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json names no {what} {name!r}")


def metrics_of_cell(manifest: dict, section: str, cell: str) -> List[dict]:
    """The metrics of ``section`` that ``cell`` reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


# -- what a run hands around -----------------------------------------------------

@dataclass
class Cell:
    name: str
    chips: int
    seed: int
    seconds: float                     # the whole window: windows x --seconds
    trace: bool
    config: dict
    mix: dict
    reference: Any
    devices: list
    rehearsal: bool = False
    windows: int = 1                   # the mix's ``windows``


@dataclass
class Observed:
    """What the per-layer readers may read."""
    cell: Cell
    window: dict                       # the runner's own account of the window
    counters: dict                     # the program's counters over the window
    trace: Any = None                  # tracing.Trace of the traced part
    peaks: Optional[dict] = None


@dataclass
class Compilations:
    """Counts requests to compile, and which fell inside the window."""
    total: int = 0
    in_window: int = 0
    hits: int = 0
    misses: int = 0
    window_open: bool = False

    def on_event(self, event: str, **_) -> None:
        if event.endswith("/compile_requests_use_cache"):
            self.total += 1
            self.in_window += self.window_open
        elif event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1


class Tracer:
    """Keeps ``TRACE_SECONDS`` of the window under the profiler."""

    def __init__(self, on: bool, directory: str):
        self.on, self.directory = on, directory
        self.started_at: Optional[float] = None
        self.stopped = False

    def annotate(self, name: str):
        import contextlib
        import jax
        return jax.profiler.TraceAnnotation(name) if self.on \
            else contextlib.nullcontext()

    def tick(self, elapsed_s: float, window_s: float) -> None:
        """Called by the runner between units of work."""
        import jax
        if not self.on or self.stopped:
            return
        if self.started_at is None:
            if elapsed_s >= min(window_s / 3.0, 10.0):
                jax.profiler.start_trace(self.directory)
                self.started_at = elapsed_s
        elif elapsed_s - self.started_at >= TRACE_SECONDS:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.on and self.started_at is not None and not self.stopped:
            jax.profiler.stop_trace()
        self.stopped = True


def seeded_params(cell: Cell, out_shardings=None):
    """The benchmark's own weights for ``cell.seed``, made on the device in
    one jitted call of the reference's initialiser."""
    import functools

    import jax

    ref = cell.reference
    sizes = {k: cell.config[k] for k in ref.SIZE_KEYS}
    make = jax.jit(functools.partial(ref.init_params, sizes=sizes),
                   out_shardings=out_shardings)
    return make(ref.seed_key(cell.seed))


def host_peak_bytes() -> int:
    """The most this process has held of the host's memory so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def memory_bytes(devices) -> int:
    """Bytes held on the fullest chip: live arrays plus what the loaded
    programs keep reserved for their temporaries."""
    worst = 0
    for d in devices:
        s = d.memory_stats() or {}
        worst = max(worst, int(s.get("bytes_in_use", 0))
                    + int(s.get("bytes_reserved", 0)),
                    int(s.get("peak_bytes_in_use", 0)))
    return worst


# -- one run -------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Refused(Exception):
    """The run cannot be made here; carries the exit code."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def open_cell(workload: str, seed: int, seconds: float, trace: bool,
              rehearsal: Optional[dict] = None):
    """(manifest, cell, compilation counter) of one run, or
    :class:`Refused` without the program or the chips the cell asks for.
    ``rehearsal`` is never set by the command: a test passes tiny sizes
    there (``{"config": {...}, "program": {...}, "mix": {...}}``) to
    drive the control flow on the CPU; such a run prints no device
    metric."""
    manifest = load_manifest()
    entry = find(manifest["workloads"], workload, "workload")
    if importlib.util.find_spec("deeplearning4j_tpu") is None:
        raise Refused(3, "the program (deeplearning4j_tpu) is not in this "
                         "checkout; nothing to measure")
    from benchmarks import traffic

    config = load_config(entry["config"])
    mix = traffic.load_mix(entry["traffic"])
    if rehearsal is not None:
        config = {**config, **rehearsal.get("config", {})}
        config["program"] = {**config.get("program", {}),
                             **rehearsal.get("program", {})}
        mix = traffic.check_mix({**mix, **rehearsal.get("mix", {})},
                                "the rehearsal's mix")

    import jax

    devices = jax.devices()
    dev = devices[0]
    if rehearsal is None and (dev.platform != "tpu"
                              or len(devices) != entry["chips"]):
        raise Refused(2, f"cell {entry['name']} needs {entry['chips']} TPU "
                         f"chip(s); JAX reports {len(devices)} x "
                         f"{dev.platform!r}. Refusing to run: this benchmark "
                         "never falls back.")
    devices = devices[: entry["chips"]]

    from deeplearning4j_tpu.serving.warmcache import enable_compile_cache

    comp = Compilations()
    jax.monitoring.register_event_listener(comp.on_event)
    cache_dir = enable_compile_cache()
    # the mix states how many times --seconds its cell's window lasts
    windows = mix.get("windows", 1)
    cell = Cell(name=entry["name"], chips=entry["chips"], seed=seed,
                seconds=seconds * windows, trace=trace, config=config,
                mix=mix, reference=load_reference(config), devices=devices,
                rehearsal=rehearsal is not None, windows=windows)
    span = f"{cell.seconds}s" if windows == 1 \
        else f"{windows} x {seconds}s = {cell.seconds}s"
    say(f"cell {cell.name}: config {config['name']}, traffic "
        f"{entry['traffic']}, {cell.chips} chip(s), seed {cell.seed}, window "
        f"{span}, trace {int(cell.trace)}"
        + (" [REHEARSAL on " + dev.platform + ": no device metric]"
           if cell.rehearsal else ""))
    say(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    return manifest, cell, comp


def main(argv, t_start: Optional[float] = None,
         rehearsal: Optional[dict] = None) -> int:
    """Run one cell and print its result line."""
    t_start = time.time() if t_start is None else t_start
    args = parse_args(argv)
    try:
        manifest, cell, comp = open_cell(args.workload, args.seed,
                                         args.seconds, bool(args.trace),
                                         rehearsal)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return e.code
    config, devices, dev = cell.config, cell.devices, cell.devices[0]

    runner = load_runner(config["runner"])
    trace_dir = os.path.join(ROOT, ".bench", "trace", cell.name)
    tracer = Tracer(cell.trace and not cell.rehearsal, trace_dir)
    if tracer.on:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)

    split: Dict[str, float] = {}          # where set-up's seconds went
    state = runner.setup(cell, split)
    setup_s = time.time() - t_start
    split["import_and_start"] = round(split.pop("t_enter") - t_start, 3)
    mem_setup, host_setup = memory_bytes(devices), host_peak_bytes()

    comp.window_open = True
    try:
        window = runner.window(cell, state, tracer)
    finally:
        tracer.stop()
        comp.window_open = False
    counters = window.pop("counters", {})
    memory_peak = max(mem_setup, memory_bytes(devices))
    host_window = host_peak_bytes()
    mem_detail = {str(d.id): d.memory_stats() for d in devices}

    served = runner.release(cell, state)       # frees the program's state
    del state
    t_ref = time.time()
    compared = runner.compare(cell, served)
    ref_s = time.time() - t_ref

    say(f"set-up {setup_s:.3f}s, split {json.dumps(split)} (cache hits "
        f"{comp.hits}, misses {comp.misses}); compilations inside the "
        f"window: {comp.in_window}; peak memory {memory_peak} bytes")
    say(f"memory: {json.dumps(mem_detail)}")
    say(f"host memory: peak {host_setup} bytes at set-up's end, {host_window} "
        f"at the window's, {host_peak_bytes()} after the reference")
    say(f"window: {json.dumps(window.get('summary', {}))}")
    say(f"sizes: {json.dumps(runner.sizes(cell))}")
    correct = comp.in_window == 0 and bool(compared["numbers"])
    for name, value in sorted(compared["numbers"].items()):
        limit = config["limits"][name]
        ok = value is not None and value <= limit
        correct = correct and ok
        say("compared: " + json.dumps(
            {"number": name, "value": value, "limit": limit, "ok": ok}))
    say(f"compared detail: {json.dumps(compared.get('detail', {}))} "
        f"(reference took {ref_s:.1f}s, outside set-up and window)")
    if compared.get("error"):
        say(f"compared: NOT CORRECT: {compared['error']}")
        correct = False
    if comp.in_window:
        say(f"NOT CORRECT: {comp.in_window} compilation(s) inside the window")

    observed = Observed(cell=cell, window=window, counters=counters,
                        peaks=None if cell.rehearsal
                        else load_peaks(dev.device_kind))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(window["attempted"]),
        "failed": int(window["failed"]), "metrics": {}, "device": device}

    if cell.trace:
        if tracer.on and tracer.started_at is not None:
            from benchmarks import tracing
            observed.trace = tracing.reduce_xplane(tracing.find_xplane(trace_dir))
            device["busy_s"] = observed.trace.busy_s
            device["window_s"] = observed.trace.window_s
            result["breakdown"] = observed.trace.breakdown()
            say("programs in the trace: " + json.dumps(
                observed.trace.module_table()[:12]))
        for m in metrics_of_cell(manifest, "per_layer", cell.name):
            reader = load_layer_metric(m["name"])
            value = reader.read(observed)
            if value is not None:
                result["metrics"][m["name"]] = {
                    "value": None if cell.rehearsal else value,
                    "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s, **window["end_to_end"]}
        for m in metrics_of_cell(manifest, "end_to_end", cell.name):
            result["metrics"][m["name"]] = {
                "value": None if cell.rehearsal else values[m["name"]],
                "unit": m["unit"]}
    print(json.dumps(result), flush=True)
    return 0
