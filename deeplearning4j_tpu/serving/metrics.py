"""Serving observability, on the unified registry (obs/metrics.py).

The reference stack exports serving metrics through its model-server's
/metrics-style endpoints; here a `ServingMetrics` instance is owned by one
`serving.Engine` and exported three ways: `snapshot()` (the legacy plain
dict — the test/API surface, schema unchanged since PR 4), the per-engine
``registry`` (typed instruments, one schema with every other subsystem),
and the process-global ``obs.metrics.get_registry()`` — each
ServingMetrics registers itself as a collector there, so one
``MetricsRegistry.snapshot()`` / one ``UIServer /metrics`` response
carries every live engine alongside the elastic / input-pipeline /
launcher stats (docs/OBSERVABILITY.md).

Histograms are FIXED-bucket (exponential ms boundaries), not reservoirs:
recording is O(#buckets) worst case, lock-held time is tiny, and snapshots
are mergeable across engines — the properties a hot serving path needs.
Percentiles are estimated by linear interpolation inside the winning
bucket, so p99 on a 17-bucket histogram is approximate by design; tests
that need exact latencies read `count`/`sum_ms` or time externally.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Sequence

from ..obs.metrics import (
    DEFAULT_LATENCY_BUCKETS_MS, Histogram, MetricsRegistry, get_registry,
)

# kept as the serving-local name; one source of truth in obs/metrics.py
DEFAULT_BUCKETS_MS: Sequence[float] = DEFAULT_LATENCY_BUCKETS_MS


class LatencyHistogram(Histogram):
    """The unified fixed-bucket histogram with the serving-legacy
    millisecond surface: ``record(ms)``, ``count``/``sum_ms``/``max_ms``
    attributes, and the ``*_ms``-keyed ``snapshot()`` schema the serving
    tests and A/B scripts read."""

    def __init__(self, buckets_ms: Sequence[float] = DEFAULT_BUCKETS_MS,
                 name: str = "latency_ms"):
        super().__init__(name, buckets_ms)

    def _unlabeled(self):
        with self._lock:
            return self._series.get(())

    @property
    def count(self) -> int:
        s = self._unlabeled()
        return s.count if s else 0

    @property
    def sum_ms(self) -> float:
        s = self._unlabeled()
        return s.total if s else 0.0

    @property
    def max_ms(self) -> float:
        s = self._unlabeled()
        return s.max_value if s else 0.0

    def snapshot(self) -> dict:
        with self._lock:
            s = self._series.get(())
            counts = list(s.counts) if s else [0] * (len(self.bounds) + 1)
            count = s.count if s else 0
            total = s.total if s else 0.0
            mx = s.max_value if s else 0.0
        out = {"count": count, "sum_ms": round(total, 3),
               "max_ms": round(mx, 3),
               "mean_ms": round(total / count, 3) if count else None,
               "buckets_ms": list(self.bounds), "counts": counts}
        for p in (50, 90, 99):
            v = self.percentile(p)
            out[f"p{p}_ms"] = round(v, 3) if v is not None else None
        return out


# every counter a fresh engine reports as zero (docs/SERVING.md: the
# batching/admission set, then the resilience + canary set, then the
# cold-start/autoscale set)
_COUNTER_KEYS = (
    "requests", "rows", "batches", "padded_rows",
    "shed", "deadline_missed", "errors", "swaps", "unwarmed_serves",
    "replica_crashes", "replica_hangs", "replica_respawns",
    "respawn_failures",
    "retries", "poison_isolated", "circuit_opens",
    "canary_promotions", "canary_rollbacks", "canary_mirrored_batches",
    "warmup_seconds_total", "bundle_hits", "bundle_misses",
    "scale_ups", "scale_downs",
    "model_loads", "model_evictions",
)


class ServingMetrics:
    """Per-engine metric set: three latency histograms (queue wait,
    device time, end-to-end) + batching/admission/resilience counters —
    all typed instruments in the per-engine ``registry``.

    Batch occupancy (padding waste) is the satellite-regression metric:
    ``padded_rows / (rows + padded_rows)`` should stay near zero when
    request sizes align with buckets — a drain that overshoots
    ``max_batch`` before bucketing (the old ``ParallelInference._run``
    bug) shows up here as waste and as ``max_batch_rows`` > max_batch."""

    def __init__(self, buckets_ms: Sequence[float] = DEFAULT_BUCKETS_MS,
                 registry: MetricsRegistry = None):
        self.registry = registry or MetricsRegistry()
        self.queue_wait = self.registry.register(
            LatencyHistogram(buckets_ms, name="queue_wait_ms"))
        self.device_time = self.registry.register(
            LatencyHistogram(buckets_ms, name="device_time_ms"))
        self.e2e = self.registry.register(
            LatencyHistogram(buckets_ms, name="e2e_ms"))
        self._counters = {k: self.registry.counter(k) for k in _COUNTER_KEYS}
        self._lock = threading.Lock()
        self._batch_rows_max = 0
        self._rows_max_gauge = self.registry.gauge("max_batch_rows")
        self._rows_max_gauge.set(0)
        self._t0 = time.monotonic()
        # one process-wide surface: every live engine's snapshot rides
        # the global registry (weakly — a dropped engine unregisters)
        self.global_name = get_registry().register_collector(
            "serving", self.snapshot, unique=True)

    def inc(self, key: str, n: int = 1, tenant: str = None) -> None:
        c = self._counters.get(key)
        if c is None:        # open key set, as before the migration
            with self._lock:
                c = self._counters.get(key)
                if c is None:
                    c = self._counters[key] = self.registry.counter(key)
        c.inc(n)
        if tenant:
            # a per-tenant label slice of the same instrument — shows up
            # in the registry snapshot as ``key{tenant=...}`` (the
            # unlabeled series above stays the all-tenants total)
            c.inc(n, tenant=tenant)

    def counter_value(self, key: str, tenant: str = None) -> float:
        """Current value of one counter (0.0 if never incremented) — the
        cheap read the autoscaler's shed-delta signal polls.  With
        ``tenant``, reads that tenant's label slice."""
        c = self._counters.get(key)
        if c is None:
            return 0.0
        return float(c.value(tenant=tenant) if tenant else c.value())

    def record_batch(self, n_requests: int, rows: int, padded_rows: int,
                     device_ms: float) -> None:
        self._counters["batches"].inc()
        self._counters["requests"].inc(n_requests)
        self._counters["rows"].inc(rows)
        self._counters["padded_rows"].inc(padded_rows)
        with self._lock:
            if rows > self._batch_rows_max:
                self._batch_rows_max = rows
                self._rows_max_gauge.set(rows)
        self.device_time.record(device_ms)

    def snapshot(self) -> dict:
        c: Dict[str, int] = {}
        for k, counter in list(self._counters.items()):
            v = counter.value()
            c[k] = int(v) if float(v).is_integer() else v
        with self._lock:
            rows_max = self._batch_rows_max
        elapsed = time.monotonic() - self._t0
        total = c["rows"] + c["padded_rows"]
        return {
            "counters": c,
            "max_batch_rows": rows_max,
            "batch_occupancy": round(c["rows"] / total, 4) if total else None,
            "requests_per_sec": round(c["requests"] / elapsed, 2)
            if elapsed > 0 else None,
            "uptime_sec": round(elapsed, 3),
            "queue_wait_ms": self.queue_wait.snapshot(),
            "device_time_ms": self.device_time.snapshot(),
            "e2e_ms": self.e2e.snapshot(),
        }


# every counter a fresh fleet router reports as zero (docs/SERVING.md
# fleet section: dispatch set, then failover, then swap/drain lifecycle)
_FLEET_COUNTER_KEYS = (
    "requests", "dispatched", "delivered", "retries", "shed", "failed",
    "timeouts", "late_discards", "affinity_routed",
    "host_failures", "host_down", "host_up",
    "drains", "preempt_drains", "rolling_swaps", "swap_hosts", "rollbacks",
    "disagg_requests", "page_transfers", "transfer_bytes",
    "placements", "placement_evictions", "demand_loads", "model_misses",
)


class FleetMetrics:
    """Per-router metric set for the fleet router (serving/fleet.py):
    fleet end-to-end latency (submit → delivered, across retries and
    failover) plus dispatch/failover/swap counters and host-population
    gauges.  Exported like ``ServingMetrics``: a plain ``snapshot()``
    dict, a typed per-router registry, and a collector named ``fleet``
    on the process-global registry so one ``/metrics`` response carries
    the router beside every per-host engine (docs/OBSERVABILITY.md)."""

    def __init__(self, buckets_ms: Sequence[float] = DEFAULT_BUCKETS_MS,
                 registry: MetricsRegistry = None):
        self.registry = registry or MetricsRegistry()
        self.e2e = self.registry.register(
            LatencyHistogram(buckets_ms, name="fleet_e2e_ms"))
        self._counters = {k: self.registry.counter(k)
                          for k in _FLEET_COUNTER_KEYS}
        self._lock = threading.Lock()
        self.hosts_up = self.registry.gauge("hosts_up")
        self.hosts_up.set(0)
        self.hosts_total = self.registry.gauge("hosts_total")
        self.hosts_total.set(0)
        self._t0 = time.monotonic()
        self.global_name = get_registry().register_collector(
            "fleet", self.snapshot, unique=True)

    def inc(self, key: str, n: int = 1, tenant: str = None) -> None:
        c = self._counters.get(key)
        if c is None:        # open key set, matching ServingMetrics
            with self._lock:
                c = self._counters.get(key)
                if c is None:
                    c = self._counters[key] = self.registry.counter(key)
        c.inc(n)
        if tenant:
            c.inc(n, tenant=tenant)

    def snapshot(self) -> dict:
        c: Dict[str, int] = {}
        for k, counter in list(self._counters.items()):
            v = counter.value()
            c[k] = int(v) if float(v).is_integer() else v
        elapsed = time.monotonic() - self._t0
        return {
            "counters": c,
            "hosts_up": int(self.hosts_up.value()),
            "hosts_total": int(self.hosts_total.value()),
            "requests_per_sec": round(c["requests"] / elapsed, 2)
            if elapsed > 0 else None,
            "uptime_sec": round(elapsed, 3),
            "fleet_e2e_ms": self.e2e.snapshot(),
        }


# every counter a fresh decode engine reports as zero (docs/SERVING.md
# decode section: throughput set, then stop conditions, then resilience,
# then the cold-start set, then the decode-speed set — prefix cache and
# speculation counters stay registered-at-zero when the features are off
# so dashboards never see a key appear mid-flight)
_DECODE_COUNTER_KEYS = (
    "requests", "tokens_out", "prefills", "decode_steps",
    "eos_stops", "max_token_stops", "deadline_stops",
    "shed", "deadline_missed", "errors", "retries",
    "poison_isolated", "replica_crashes", "replica_respawns", "swaps",
    "warmup_seconds_total", "bundle_hits", "bundle_misses",
    "scale_ups", "scale_downs",
    "prefix_hits", "prefix_misses", "prefix_inserts",
    "prefix_evictions", "prefix_hit_tokens",
    "spec_steps", "spec_proposed", "spec_accepted", "spec_committed",
    "handoffs_out", "handoffs_in",
    "pages_exported", "pages_attached", "pages_deduped",
    # host-overhead elimination (docs/SERVING.md): fused multi-step
    # decode dispatches, tokens committed by them (tokens_per_dispatch /
    # fused_dispatches = realized amortization), and chunked-prefill
    # prompt/chunk counts
    "fused_dispatches", "tokens_per_dispatch",
    "chunked_prefills", "prefill_chunks",
    # turns of the loop that sent at least one chunk to the device, and
    # those that sent more than one (a chunk for each slot mid-prefill:
    # prefill_chunks / fused_dispatches = chunks a turn)
    "chunk_turns", "chunk_turns_multi",
    # admission rounds that the token budget ended (one chunk's worth of
    # prompt tokens for each free slot) with slots still free and
    # requests waiting
    "admit_rounds_budget_bound",
    # the plain loop's step in flight: decode steps queued while the step
    # before was unread, steps read in the turn that queued them (two
    # version tags alive), slot-steps computed for a request that the
    # read before had stopped (EOS, deadline, poison)
    "steps_ahead", "step_drains", "overrun_slot_steps",
    # decode steps (each step of a fused dispatch) whose batch held a
    # sampled top-p row, so the sampler sorted every row's vocabulary
    # (ops/sampling.needs_sort); the others found the top-k threshold by
    # selection
    "sampler_sorted_steps",
    # backend compiles and compile-cache reads that JAX made outside every
    # phase of the start-up account (obs/startup.py) while this engine
    # was loaded: the zero-serve-time-compiles contract, watched from
    # inside JAX and not only through the engine's own dictionary
    "serve_time_compiles",
    # routed experts (parallel/moe.EXPERT_STATS; zero for a program
    # without them): picks made by real tokens, those that fell on
    # experts held here, the fullest held expert's picks (summed over
    # layers and calls), held experts with at least one pick
    "expert_picks", "expert_picks_held", "expert_load_max", "experts_hit",
    # a learned sparse selection (models/sparse_gqa.SPARSE_STATS; zero
    # for a program without one), summed over layers and calls: context
    # rows the indexer scored, K/V rows attention read after the
    # selection, rows the stepped slots / the chunk's slot held
    "index_rows_scored", "attn_rows_read", "rows_held",
    # layers that keep a per-slot recurrent state beside grouped-query
    # layers over paged K and V (models/linear_gqa.STATE_STATS, which
    # models/ssm_gqa.py reports under the same names; zero for a
    # program without them), summed over layers and calls: active slots
    # whose state a step replaced, real rows a chunk scanned into a
    # slot's state, K rows the stepped slots / the chunk's slot held,
    # rows the block walk read of them; and admissions that started a
    # slot from zero state
    "state_slots_stepped", "state_rows_scanned", "kv_rows_held",
    "kv_rows_read", "recurrent_state_resets",
    # rows a chunked scan computed for those it scanned (whole chunks of
    # the bucket: models/ssm_gqa.SCAN_STATS; zero for any other program)
    "state_rows_computed",
)


class DecodeMetrics:
    """Per-decode-engine metric set: TTFT and time-per-output-token are
    the first-class histograms (the serving numbers that matter for
    generative inference — PAPERS.md Gemma-on-TPU framing), plus
    per-step device time, throughput/stop/resilience counters, and
    pool-occupancy gauges.  Exported like ``ServingMetrics``: a legacy
    ``snapshot()`` dict, a typed per-engine registry, and a collector on
    the process-global registry (one ``/metrics`` response carries every
    live engine — docs/OBSERVABILITY.md)."""

    def __init__(self, buckets_ms: Sequence[float] = DEFAULT_BUCKETS_MS,
                 registry: MetricsRegistry = None):
        self.registry = registry or MetricsRegistry()
        self.ttft = self.registry.register(
            LatencyHistogram(buckets_ms, name="ttft_ms"))
        self.tpot = self.registry.register(
            LatencyHistogram(buckets_ms, name="tpot_ms"))
        self.step_time = self.registry.register(
            LatencyHistogram(buckets_ms, name="decode_step_ms"))
        # submit -> slot assignment, recorded at admission
        self.queue_wait = self.registry.register(
            LatencyHistogram(buckets_ms, name="queue_wait_ms"))
        self._counters = {k: self.registry.counter(k)
                          for k in _DECODE_COUNTER_KEYS}
        self._lock = threading.Lock()
        self.active_slots = self.registry.gauge("active_slots")
        self.active_slots.set(0)
        self.pages_in_use = self.registry.gauge("pages_in_use")
        self.pages_in_use.set(0)
        # pages of the active slots that hold at least one token
        # (pages_in_use counts pages RESERVED: total - 1 - free)
        self.pages_filled = self.registry.gauge("pages_filled")
        self.pages_filled.set(0)
        self.shared_pages = self.registry.gauge("shared_pages")
        self.shared_pages.set(0)
        self.free_pages = self.registry.gauge("free_pages")
        self.free_pages.set(0)
        self.free_slots = self.registry.gauge("free_slots")
        self.free_slots.set(0)
        # the most chunks one turn sent to the device (the stall a
        # decoding slot met at worst, in chunks; at most max_slots)
        self.chunk_turn_max = self.registry.gauge("chunk_turn_max")
        self.chunk_turn_max.set(0)
        # bytes one cached token holds in the pool, all layers (set at load)
        self.kv_bytes_per_token = self.registry.gauge("kv_bytes_per_token")
        self.kv_bytes_per_token.set(0)
        # bytes of the per-slot recurrent state beside the pools, all
        # slots and layers (set at load; 0 for a program without any)
        self.recurrent_state_bytes = self.registry.gauge(
            "recurrent_state_bytes")
        self.recurrent_state_bytes.set(0)
        self._t0 = time.monotonic()
        self.global_name = get_registry().register_collector(
            "decode", self.snapshot, unique=True)

    def inc(self, key: str, n: int = 1, tenant: str = None) -> None:
        c = self._counters.get(key)
        if c is None:        # open key set, matching ServingMetrics
            with self._lock:
                c = self._counters.get(key)
                if c is None:
                    c = self._counters[key] = self.registry.counter(key)
        c.inc(n)
        if tenant:
            c.inc(n, tenant=tenant)

    def counter_value(self, key: str, tenant: str = None) -> float:
        """Current value of one counter (0.0 if never incremented) — the
        cheap read the autoscaler's shed-delta signal polls.  With
        ``tenant``, reads that tenant's label slice."""
        c = self._counters.get(key)
        if c is None:
            return 0.0
        return float(c.value(tenant=tenant) if tenant else c.value())

    def snapshot(self) -> dict:
        c: Dict[str, int] = {}
        for k, counter in list(self._counters.items()):
            v = counter.value()
            c[k] = int(v) if float(v).is_integer() else v
        elapsed = time.monotonic() - self._t0
        return {
            "counters": c,
            "active_slots": int(self.active_slots.value()),
            "pages_in_use": int(self.pages_in_use.value()),
            "pages_filled": int(self.pages_filled.value()),
            "shared_pages": int(self.shared_pages.value()),
            "free_pages": int(self.free_pages.value()),
            "free_slots": int(self.free_slots.value()),
            "chunk_turn_max": int(self.chunk_turn_max.value()),
            "kv_bytes_per_token": int(self.kv_bytes_per_token.value()),
            "recurrent_state_bytes": int(self.recurrent_state_bytes.value()),
            "accepted_tokens_per_step": round(
                c["spec_committed"] / c["spec_steps"], 4)
            if c.get("spec_steps") else None,
            "tokens_per_sec": round(c["tokens_out"] / elapsed, 2)
            if elapsed > 0 else None,
            "uptime_sec": round(elapsed, 3),
            "ttft_ms": self.ttft.snapshot(),
            "tpot_ms": self.tpot.snapshot(),
            "decode_step_ms": self.step_time.snapshot(),
            "queue_wait_ms": self.queue_wait.snapshot(),
        }
