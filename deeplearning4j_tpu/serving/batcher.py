"""Deadline-aware dynamic micro-batcher with per-tenant fair-share lanes.

Replaces the fixed-poll drain of the old ``parallel/inference.py``
worker (``queue.get(timeout=queue_timeout_s)`` per item — a latency
floor under EVERY request, and a throughput stall whenever the queue
briefly empties) with an event-driven close: a batch closes the moment

  * queued rows reach ``max_batch`` (never overshooting it — the old
    drain bucketed on the TOTAL queued rows, so a 33-row drain at
    ``max_batch=32`` ran an unbucketed 33-row program; here drains are
    split at ``max_batch`` BEFORE bucketing), or
  * waiting any longer would eat into the oldest request's deadline:
    close time = earliest deadline − the EMA device time of the bucket
    the batch would run in (seeded by AOT warmup, see engine.load()).

Requests carry their own deadline (default: submit + SLO budget).  A
request whose deadline passes while still queued fails fast with
``DeadlineExceededError`` instead of returning a stale result.

Admission control: the queue is bounded (``max_queue`` requests) with a
configurable overload policy — ``"block"`` (backpressure the caller) or
``"shed"`` (raise ``OverloadedError`` immediately) — so overload
degrades predictably instead of growing an unbounded queue until OOM.

Multi-tenancy (serving/tenancy.py): requests are tenant-tagged and the
queue is a set of PER-TENANT LANES drained by stride scheduling — the
scheduler always pops from the non-empty lane with the smallest virtual
time ``served_rows / weight`` — so a bursting tenant's backlog queues
behind its own lane, never in front of a victim tenant's requests.
Per-tenant quotas (concurrent cap, QPS bucket) are checked-and-charged
atomically at submit; a tenant over quota sheds with the typed
``TenantOverloadedError`` carrying the tenant and its shed count.
Untagged traffic rides the anonymous lane (weight 1.0) and behaves
exactly as the pre-tenancy FIFO.  Requests also carry an optional
``model`` tag; a batch never mixes models (the engine executes one
model version per batch — the no-version-mixing contract extended to
the zoo).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from ..obs import trace as obs_trace

ADMISSION_POLICIES = ("block", "shed")

_ANY_MODEL = object()      # sentinel: lane selection unconstrained


class DeadlineExceededError(RuntimeError):
    """The request's deadline passed before a device slot freed up —
    the caller's SLO is already blown, so the result would be stale."""


class OverloadedError(RuntimeError):
    """The admission queue is full and the policy is ``shed`` — retry
    with backoff or route to another replica group."""


class _Request:
    __slots__ = ("x", "rows", "future", "t_submit", "deadline",
                 "retries", "tried", "payload", "tenant", "model")

    def __init__(self, x: np.ndarray, future: Future, t_submit: float,
                 deadline: float, tenant: str = "",
                 model: Optional[str] = None):
        self.x = x
        self.rows = int(x.shape[0])
        self.future = future
        self.t_submit = t_submit
        self.deadline = deadline
        self.retries = 0          # failure-isolation retries consumed
        self.tried = set()        # replica indices that failed this request
        self.payload = None       # decode-path request spec (ContinuousBatcher)
        self.tenant = tenant      # "" = the anonymous lane
        self.model = model        # None = the engine's default model


def pow2_buckets(max_batch: int) -> List[int]:
    """1, 2, 4, ... up to and including ``max_batch``."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return sorted(set(out))


class DynamicBatcher:
    """Bounded request queue + deadline-aware batch former.

    One or more worker/dispatcher threads call :meth:`next_batch`; any
    number of caller threads call :meth:`submit`.  ``clock`` is
    injectable (monotonic seconds) so deadline logic is testable
    without sleeping.  ``tenants`` (a ``tenancy.TenantTable``) arms
    per-tenant admission quotas and weighted-fair lane scheduling;
    without it every request rides the anonymous lane — byte-identical
    to the pre-tenancy behavior.
    """

    def __init__(self, max_batch: int = 32, slo_ms: float = 50.0,
                 bucket_sizes: Optional[Sequence[int]] = None,
                 max_queue: int = 1024, admission: str = "block",
                 max_wait_ms: Optional[float] = None,
                 metrics=None, clock=time.monotonic, tenants=None):
        if admission not in ADMISSION_POLICIES:
            raise ValueError(f"admission must be one of "
                             f"{ADMISSION_POLICIES}, got {admission!r}")
        if max_batch < 1 or max_queue < 1 or slo_ms <= 0:
            raise ValueError("max_batch/max_queue must be >=1, slo_ms > 0")
        self.max_batch = int(max_batch)
        self.slo_ms = float(slo_ms)
        # batch-forming window: at LOW load a batch must not sit waiting
        # for companions until its deadline-slack runs out (that would
        # make p50 == SLO); the oldest request waits at most this long
        # before the batch closes.  The deadline-slack close below stays
        # the binding constraint whenever it is tighter.
        self.max_wait_ms = (float(max_wait_ms) if max_wait_ms is not None
                            else min(5.0, self.slo_ms / 10.0))
        self.buckets = (sorted(set(int(b) for b in bucket_sizes))
                        if bucket_sizes else pow2_buckets(max_batch))
        self.max_queue = int(max_queue)
        self.admission = admission
        self.metrics = metrics
        self.clock = clock
        self.tenants = tenants
        # tenant -> FIFO lane; drained by stride scheduling over _pass
        # (virtual time = rows served / weight).  A new lane joins at
        # the minimum live pass so it neither starves nor is starved.
        self._lanes: Dict[str, Deque[_Request]] = {}
        self._pass: Dict[str, float] = {}
        self._n_pending = 0
        self._lock = threading.Lock()
        self._nonempty = threading.Condition(self._lock)
        self._space = threading.Condition(self._lock)
        self._closed = False
        self._draining = False
        # bucket -> EMA device ms; the exec budget subtracted from the
        # oldest deadline when deciding how long a batch may keep filling
        self._exec_ema_ms: Dict[int, float] = {}

    # -- shape buckets -----------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest configured bucket >= n; oversized requests (> the
        largest bucket) get the next power of two — they run, but pay
        their own compile (engine metrics count them as unwarmed)."""
        for b in self.buckets:
            if n <= b:
                return b
        b = self.buckets[-1]
        while b < n:
            b *= 2
        return b

    def observe_exec_ms(self, bucket: int, ms: float, alpha: float = 0.3) -> None:
        prev = self._exec_ema_ms.get(bucket)
        self._exec_ema_ms[bucket] = (ms if prev is None
                                     else alpha * ms + (1 - alpha) * prev)

    def _exec_budget_ms(self, rows: int) -> float:
        """Expected device time for a batch of ``rows`` — the slack we
        must keep in hand when deciding to wait for more requests.
        Unmeasured buckets assume a quarter of the SLO."""
        ema = self._exec_ema_ms.get(self.bucket_for(min(rows, self.max_batch)))
        return ema if ema is not None else self.slo_ms * 0.25

    # -- tenant lanes ------------------------------------------------------

    def _count_shed(self, tenant: str) -> None:
        if self.metrics:
            self.metrics.inc("shed", tenant=tenant or None)

    def _weight_of(self, tenant: str) -> float:
        if not tenant or self.tenants is None:
            return 1.0
        return self.tenants.weight(tenant)

    def _append_locked(self, r: _Request, front: bool = False) -> None:
        lane = self._lanes.get(r.tenant)
        if lane is None:
            lane = self._lanes[r.tenant] = deque()
            live = [p for t, p in self._pass.items() if self._lanes.get(t)]
            self._pass[r.tenant] = max(self._pass.get(r.tenant, 0.0),
                                       min(live, default=0.0))
        if front:
            lane.appendleft(r)
        else:
            lane.append(r)
        self._n_pending += 1

    def _iter_pending(self):
        for lane in self._lanes.values():
            yield from lane

    def _next_lane_locked(self, model=_ANY_MODEL) -> Optional[str]:
        """Stride scheduling: the non-empty lane with the smallest
        virtual time whose head matches ``model`` (batches never mix
        models)."""
        best = None
        best_pass = None
        for t, lane in self._lanes.items():
            if not lane:
                continue
            if model is not _ANY_MODEL and lane[0].model != model:
                continue
            p = self._pass.get(t, 0.0)
            if best_pass is None or p < best_pass:
                best, best_pass = t, p
        return best

    def _pop_one_locked(self, tenant: str) -> _Request:
        r = self._lanes[tenant].popleft()
        self._n_pending -= 1
        self._pass[tenant] = (self._pass.get(tenant, 0.0)
                              + r.rows / self._weight_of(tenant))
        return r

    # -- submission --------------------------------------------------------

    def _admission_locked(self, fut: Future, tenant: str,
                          model: Optional[str]) -> bool:
        """Every admission gate, under ``self._lock``: closed fails the
        future deterministically (returns False — do not enqueue);
        draining and quota exhaustion shed by RAISING; True means the
        caller must enqueue.  On True with a tenant, the concurrent
        slot is already charged and its release is chained to the
        future — the engine invariant (every future resolves) makes
        the release exactly-once."""
        if self._closed:
            fut.set_exception(RuntimeError("serving engine is shut down"))
            return False
        if self._draining:
            self._count_shed(tenant)
            raise OverloadedError(
                "admission stopped: engine is draining (preemption "
                "notice)")
        if self._n_pending >= self.max_queue:
            if self.admission == "shed":
                self._count_shed(tenant)
                raise OverloadedError(
                    f"admission queue full ({self.max_queue} requests); "
                    "policy=shed")
            while (self._n_pending >= self.max_queue
                   and not self._closed and not self._draining):
                self._space.wait(timeout=0.1)
            if self._closed:
                fut.set_exception(
                    RuntimeError("serving engine is shut down"))
                return False
            if self._draining:
                self._count_shed(tenant)
                raise OverloadedError(
                    "admission stopped: engine is draining (preemption "
                    "notice)")
        if self.tenants is not None and tenant:
            if not self.tenants.try_admit(tenant, model, now=self.clock()):
                if self.tenants.admission_for(tenant, model) == "block":
                    # poll-with-timeout: quota releases happen on other
                    # threads' done-callbacks, which cannot notify this
                    # condition — the 50ms cap bounds staleness
                    while (not self._closed and not self._draining
                           and not self.tenants.try_admit(
                               tenant, model, now=self.clock())):
                        self._space.wait(timeout=0.05)
                    if self._closed:
                        fut.set_exception(
                            RuntimeError("serving engine is shut down"))
                        return False
                    if self._draining:
                        self._count_shed(tenant)
                        raise OverloadedError(
                            "admission stopped: engine is draining "
                            "(preemption notice)")
                else:
                    self._count_shed(tenant)
                    raise self.tenants.shed(tenant, model)
            fut.add_done_callback(
                lambda f, t=tenant: self.tenants.release(t))
        return True

    def _resolve_deadline(self, now: float, slo_ms: Optional[float],
                          deadline: Optional[float], tenant: str,
                          model: Optional[str]) -> float:
        if deadline is not None:
            return deadline
        if slo_ms is None and tenant and self.tenants is not None:
            slo_ms = self.tenants.slo_ms_for(tenant, model)
        return now + (slo_ms if slo_ms is not None else self.slo_ms) / 1000.0

    def submit(self, x: np.ndarray, slo_ms: Optional[float] = None,
               deadline: Optional[float] = None,
               tenant: Optional[str] = None,
               model: Optional[str] = None) -> Future:
        """Enqueue one request; returns its Future.  Shedding raises
        ``OverloadedError`` (the tenant-quota flavor carries the
        tenant) synchronously; a closed batcher fails the future
        deterministically (never a silent hang)."""
        x = np.asarray(x)
        if x.ndim < 1 or x.shape[0] < 1:
            raise ValueError(f"request must have a leading batch axis, "
                             f"got shape {x.shape}")
        tenant = tenant or ""
        fut: Future = Future()
        now = self.clock()
        dl = self._resolve_deadline(now, slo_ms, deadline, tenant, model)
        with self._lock:
            if not self._admission_locked(fut, tenant, model):
                return fut
            self._append_locked(_Request(x, fut, now, dl, tenant, model))
            self._nonempty.notify()
        return fut

    def qsize(self) -> int:
        with self._lock:
            return self._n_pending

    def tenant_qsize(self, tenant: str) -> int:
        with self._lock:
            lane = self._lanes.get(tenant or "")
            return len(lane) if lane else 0

    # -- batch formation ---------------------------------------------------

    def _expire_locked(self, now: float) -> None:
        """Fail-fast every queued request whose deadline already passed."""
        if not self._n_pending:
            return
        expired = 0
        for t, lane in self._lanes.items():
            if not lane or all(r.deadline >= now for r in lane):
                continue
            keep: Deque[_Request] = deque()
            lane_expired = 0
            for r in lane:
                if r.deadline < now:
                    lane_expired += 1
                    if not r.future.done():
                        r.future.set_exception(DeadlineExceededError(
                            f"request waited "
                            f"{(now - r.t_submit) * 1e3:.1f}ms in queue, "
                            f"past its "
                            f"{(r.deadline - r.t_submit) * 1e3:.0f}"
                            "ms deadline"))
                else:
                    keep.append(r)
            if lane_expired:
                self._lanes[t] = keep
                expired += lane_expired
                if self.metrics:
                    self.metrics.inc("deadline_missed", lane_expired,
                                     tenant=t or None)
        if expired:
            self._n_pending -= expired
            self._space.notify_all()

    def _pop_batch_locked(self) -> List[_Request]:
        batch: List[_Request] = []
        rows = 0
        model = _ANY_MODEL
        while self._n_pending:
            t = self._next_lane_locked(model)
            if t is None:       # only other-model lanes remain
                break
            head = self._lanes[t][0]
            # split at max_batch BEFORE bucketing; a single oversized
            # request still goes alone (it cannot be split)
            if batch and rows + head.rows > self.max_batch:
                break
            r = self._pop_one_locked(t)
            batch.append(r)
            rows += r.rows
            model = r.model     # the batch never mixes models
            if rows >= self.max_batch:
                break
        self._space.notify_all()
        if batch:
            # post-hoc span: batch formation ran from the oldest member's
            # submit until this close decision
            obs_trace.complete_at(
                "serve/batch_form", min(r.t_submit for r in batch),
                self.clock(), cat="serve", rows=rows, n_requests=len(batch))
        return batch

    def next_batch(self) -> Optional[List[_Request]]:
        """Block until a batch closes; None once closed AND drained."""
        with self._lock:
            while True:
                now = self.clock()
                self._expire_locked(now)
                if not self._n_pending:
                    if self._closed:
                        return None
                    # pure event wait — the timeout only bounds how stale
                    # a missed notify can leave us (defensive, not a poll)
                    self._nonempty.wait(timeout=0.5)
                    continue
                total = sum(r.rows for r in self._iter_pending())
                if total >= self.max_batch or self._closed:
                    return self._pop_batch_locked()
                earliest = min(r.deadline for r in self._iter_pending())
                oldest = min(r.t_submit for r in self._iter_pending())
                t_close = min(
                    oldest + self.max_wait_ms / 1000.0,
                    earliest - self._exec_budget_ms(total) / 1000.0)
                if now >= t_close:
                    return self._pop_batch_locked()
                # cap the wait so deadline expiry scans keep running even
                # if no new request arrives to notify us
                self._nonempty.wait(timeout=min(t_close - now, 0.05))

    # -- shutdown ----------------------------------------------------------

    def begin_drain(self) -> None:
        """Stop admission without failing anything queued: every
        SUBSEQUENT submit sheds (``OverloadedError``, → HTTP 429)
        regardless of the admission policy — block-policy callers
        already waiting for space are woken and shed too — while queued
        requests keep draining through ``next_batch``/``admit``.  The
        graceful-preemption front half: shed new, finish in-flight,
        then ``close()``.  Idempotent."""
        with self._lock:
            self._draining = True
            self._space.notify_all()

    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def close(self, fail_pending: bool = True) -> None:
        """Idempotent.  With ``fail_pending`` every queued request —
        including one enqueued concurrently with shutdown — resolves
        deterministically (the old implementation could leave a future
        enqueued between shutdown-flag set and worker exit hanging
        forever under timing skew)."""
        with self._lock:
            self._closed = True
            if fail_pending:
                for lane in self._lanes.values():
                    while lane:
                        r = lane.popleft()
                        if not r.future.done():
                            r.future.set_exception(
                                RuntimeError("serving engine is shut down"))
                self._n_pending = 0
            self._nonempty.notify_all()
            self._space.notify_all()


class ContinuousBatcher(DynamicBatcher):
    """Iteration-level admission for the decode engine (serving/decode.py).

    The one-shot ``DynamicBatcher`` forms a batch and hands it over
    whole; a decode batch instead runs for many steps, and NEW requests
    must join it at the next step boundary rather than waiting for the
    running batch to drain.  So instead of ``next_batch()`` this front
    door exposes ``admit(limit)`` — a non-blocking pop of up to
    ``limit`` requests, called by the decode loop between steps —
    while keeping the parent's admission control (bounded queue,
    block/shed overload policy, per-tenant quotas + fair-share lanes),
    queued-deadline fail-fast, and injectable clock.  Requests carry an
    opaque ``payload`` (the generation spec) instead of an input array.
    """

    #: whether the token budget ended the last ``admit`` round
    last_admit_budget_bound = False

    def submit_request(self, payload, slo_ms: Optional[float] = None,
                       deadline: Optional[float] = None,
                       tenant: Optional[str] = None,
                       model: Optional[str] = None) -> Future:
        """Enqueue one decode request; same admission semantics as
        ``DynamicBatcher.submit`` (shed raises ``OverloadedError``
        synchronously, closed fails the future deterministically)."""
        tenant = tenant or ""
        fut: Future = Future()
        now = self.clock()
        dl = self._resolve_deadline(now, slo_ms, deadline, tenant, model)
        with self._lock:
            if not self._admission_locked(fut, tenant, model):
                return fut
            r = _Request(np.empty((1, 0), np.float32), fut, now, dl,
                         tenant, model)
            r.payload = payload
            self._append_locked(r)
            self._nonempty.notify()
        return fut

    def admit(self, limit: int,
              token_budget: Optional[int] = None) -> List[_Request]:
        """Pop up to ``limit`` queued requests (0 when idle) — called at
        every decode-step boundary, in fair-share lane order (decode
        slots each carry their own model tag, so one admit round MAY
        span models).  Expired requests fail fast first, exactly as in
        the one-shot path.

        ``token_budget`` is the chunked-prefill batch-formation rule
        (docs/SERVING.md "Host-overhead elimination"): stop admitting
        once the popped payloads' prompt tokens (``len(payload.prompt)``
        for payloads that carry one) would exceed the budget, so one
        admit round never enqueues more prefill work than the engine is
        willing to interleave per step — a wall of long prompts drains
        one chunk-budget's worth per round instead of all at once.  The
        head request is always admitted even when it alone exceeds the
        budget (an oversized prompt cannot be split at admission; the
        engine chunks its prefill instead), so the rule bounds pacing
        without ever starving.  ``last_admit_budget_bound`` says of the
        call just made whether the budget is what ended it: requests
        still waited and ``limit`` was not reached."""
        self.last_admit_budget_bound = False
        if limit <= 0:
            return []

        def _cost(r: _Request) -> int:
            p = getattr(r.payload, "prompt", None)
            return 0 if p is None else len(p)

        with self._lock:
            self._expire_locked(self.clock())
            out: List[_Request] = []
            spent = 0
            while self._n_pending and len(out) < limit:
                t = self._next_lane_locked()
                if t is None:
                    break
                if (token_budget is not None and out
                        and spent + _cost(self._lanes[t][0]) > token_budget):
                    self.last_admit_budget_bound = True
                    break
                r = self._pop_one_locked(t)
                spent += _cost(r)
                out.append(r)
            if out:
                self._space.notify_all()
            return out

    def requeue_front(self, r: _Request) -> None:
        """Put a request back at the head of its lane — admission
        raced ahead of capacity (no free pages/slot) or its replica
        crashed mid-decode and it has retry budget left.  The fair
        scheduler's charge for the pop is refunded so a requeue does
        not eat the tenant's share."""
        with self._lock:
            if self._closed:
                if not r.future.done():
                    r.future.set_exception(
                        RuntimeError("serving engine is shut down"))
                return
            self._pass[r.tenant] = (self._pass.get(r.tenant, 0.0)
                                    - r.rows / self._weight_of(r.tenant))
            self._append_locked(r, front=True)
            self._nonempty.notify()

    def wait_for_work(self, timeout: float = 0.05) -> bool:
        """Park the decode loop until a request is queued (or timeout /
        close).  Returns True when work is pending."""
        with self._lock:
            if self._n_pending or self._closed:
                return bool(self._n_pending)
            self._nonempty.wait(timeout=timeout)
            return bool(self._n_pending)
