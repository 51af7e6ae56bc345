"""Elastic / fault-tolerant training: checkpoint-resume + failure recovery.

Parity target: SURVEY §5 "Failure detection / elasticity" — the reference
covers this operationally via Spark task retry + TrainingMaster state
(dl4j-spark SharedTrainingMaster) and CheckpointListener.  The TPU-native
equivalent is checkpoint/restore elasticity: pods fail as units, so the
recovery loop is (1) detect a failed step, (2) re-provision a mesh over
the devices that are still healthy, (3) restore the last checkpoint,
(4) continue.  Orbax-style periodic checkpointing rides the existing zip
serializer (utils/serializer.py) so restored models are plain framework
checkpoints.

``ElasticTrainer`` wraps any trainer-like object exposing
``fit_batch(ds) -> float`` plus a wrapped ``net``; failures are surfaced
to a pluggable ``FailureDetector`` so tests (and health monitors) can
inject/observe them.
"""

from __future__ import annotations

import logging
import os
import re
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..obs import trace as obs_trace
from ..obs.metrics import get_registry
from .distributed import resolve_process_index

logger = logging.getLogger("deeplearning4j_tpu")


class RecoverableInfraError(RuntimeError):
    """Base class for failures the elastic stack treats as recoverable
    *by construction* (host lost, membership change, hung step) — the
    FailureDetector recognizes the type, not a message marker, so
    subclasses anywhere in the stack opt into recovery without touching
    the marker list."""


class _HostSnapshot:
    """Detached host-side copy of a model's persistent state — quacks like
    the net for utils/serializer.save_model, so serialization can run on a
    background thread after the training loop has moved on (and donated
    the device buffers the snapshot was taken from)."""

    def __init__(self, net):
        import numpy as _np

        def host(t):
            return jax.tree_util.tree_map(lambda a: _np.asarray(a), t)

        self.conf = net.conf
        self.params = host(net.params)
        self.state = host(net.state)
        self.opt_state = host(net.opt_state)
        # compressed-exchange error-feedback residual (serializer format
        # v3): losing it on restore would drop in-flight compression error
        residual = getattr(net, "grad_residual", None)
        self.grad_residual = None if residual is None else host(residual)
        self.iteration = net.iteration
        self.epoch = getattr(net, "epoch", 0)
        # serializer writes this into meta.json — the checkpoint must
        # record the REAL network class, not the snapshot wrapper
        self._model_class = type(net).__name__

    def save(self, path: str, save_updater: bool = True,
             compression: Optional[int] = None) -> None:
        import zipfile

        from ..utils.serializer import save_model
        save_model(self, path, save_updater=save_updater,
                   compression=(zipfile.ZIP_DEFLATED if compression is None
                                else compression))


class CheckpointManager:
    """Rolling checkpoint store (reference CheckpointListener semantics:
    keep-last-N, save-every-N-iterations; zip format from utils/serializer).

    ``save_async`` overlaps the expensive part (zip/deflate, ~1s for
    100MB of params) with training: the device→host snapshot happens on
    the caller's thread (it must — the next step donates those buffers),
    then a single background writer thread serializes and atomically
    renames.  The orbax-style pattern, stdlib-only.

    Multi-host: every host of a pod job shares one checkpoint directory,
    and params are replicated across hosts — N hosts writing the same
    ``checkpoint_X.zip.tmp`` race each other's rename.  ``role`` decides
    who writes:

    - ``"auto"`` (default): only the host with process index 0 writes
      (the index resolves from an explicit ``process_id``, the launcher's
      ``DL4J_TPU_PROCESS_ID`` env, or ``jax.process_index()``); every
      other host's ``save``/``save_async``/prune are no-ops, while
      restore/list stay available everywhere — a rejoining host restores
      the coordinator's checkpoints.
    - ``"writer"`` / ``"reader"``: force the role regardless of index.
    - ``"per_host"``: every host writes its OWN shard under a distinct
      name (``checkpoint_X.h<process>.zip``) and lists only its own —
      for host-local state that is NOT replicated."""

    _NAME_RE = re.compile(r"^checkpoint_(\d+)(?:\.h(\d+))?\.zip$")

    def __init__(self, directory: str, keep_last: int = 3,
                 role: str = "auto", process_id: Optional[int] = None):
        if role not in ("auto", "writer", "reader", "per_host"):
            raise ValueError(f"role must be auto/writer/reader/per_host, "
                             f"got {role!r}")
        self.directory = directory
        self.keep_last = keep_last
        self.role = role
        self.process_id = resolve_process_index(process_id)
        self._suffix = f".h{self.process_id}" if role == "per_host" else ""
        self._executor = None
        self._pending = None
        # checkpoint -> registry provenance: which (name, version) a
        # checkpoint was registered as (serving/lifecycle.py stamps this
        # at REGISTER time); persisted as a sidecar so the mapping
        # survives the controller, like the checkpoints themselves
        self.registered: Dict[str, Tuple[str, int]] = {}
        # wall clock of the most recent completed (deflate) write — the
        # preemption handler's estimate of whether another deflate pass
        # still fits the remaining grace budget (parallel/preemption.py)
        self.last_save_seconds: Optional[float] = None
        os.makedirs(directory, exist_ok=True)
        if self.is_writer:
            self._clean_stale_tmp()
        self._load_provenance()

    @property
    def is_writer(self) -> bool:
        if self.role == "reader":
            return False
        if self.role in ("writer", "per_host"):
            return True
        return self.process_id == 0

    def _clean_stale_tmp(self) -> None:
        """Remove ``checkpoint_*.zip.tmp`` left by a crash mid-(async-)write.
        The atomic-rename protocol means a .tmp is never the newest valid
        state — without this they leak forever, one per crash.  Only this
        manager's OWN temp names are touched (suffix-matched): a rejoining
        host must never delete the temp another host is actively writing."""
        for fn in os.listdir(self.directory):
            if not (fn.startswith("checkpoint_") and fn.endswith(".zip.tmp")):
                continue
            m = self._NAME_RE.match(fn[:-len(".tmp")])
            if m is None:
                continue   # foreign name — not ours to judge
            host = m.group(2)
            own = (host is not None and int(host) == self.process_id
                   if self.role == "per_host" else host is None)
            if own:
                try:
                    os.remove(os.path.join(self.directory, fn))
                    logger.info("removed stale checkpoint temp file %s", fn)
                except OSError:
                    pass

    def _path(self, step: int) -> str:
        return os.path.join(self.directory,
                            f"checkpoint_{step:010d}{self._suffix}.zip")

    # -- checkpoint -> registry provenance ---------------------------------

    _PROVENANCE_FILE = "registry_provenance.json"

    def _provenance_path(self) -> str:
        return os.path.join(self.directory, self._PROVENANCE_FILE)

    def _load_provenance(self) -> None:
        import json
        prov = self._provenance_path()
        if not os.path.exists(prov):
            return
        try:
            with open(prov) as f:
                raw = json.load(f)
            self.registered = {k: (str(v[0]), int(v[1]))
                               for k, v in raw.items()}
        except Exception as exc:  # an unreadable sidecar must not take
            # down checkpointing itself — provenance is advisory metadata
            logger.warning("unreadable %s (%s) — starting with empty "
                           "registry provenance", self._PROVENANCE_FILE, exc)
            self.registered = {}

    def note_registered(self, path: str, name: str, version: int) -> None:
        """Record that checkpoint ``path`` was registered as
        ``(name, version)`` in a model registry — the lifecycle
        controller's REGISTER stage calls this so "which checkpoint
        produced which serving version" is answerable from the
        checkpoint store itself.  Persisted as an atomic sidecar
        (``registry_provenance.json``) with the same crash discipline
        as the checkpoints."""
        import json
        self.registered[os.path.basename(str(path))] = (str(name),
                                                        int(version))
        prov = self._provenance_path()
        tmp = f"{prov}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({k: list(v) for k, v in self.registered.items()}, f,
                      indent=2, sort_keys=True)
        os.replace(tmp, prov)

    def registered_version(self, path: str) -> Optional[Tuple[str, int]]:
        """The ``(registry name, version)`` checkpoint ``path`` was
        registered as, or None if it never reached a registry."""
        return self.registered.get(os.path.basename(str(path)))

    def save(self, net, step: int) -> Optional[str]:
        if not self.is_writer:
            logger.debug("checkpoint save @%d skipped on non-writer host %d",
                         step, self.process_id)
            return None
        path = self._path(step)
        # temp-file + atomic rename: a crash mid-write must never leave a
        # truncated zip as the latest (restore would load garbage)
        tmp = path + ".tmp"
        t0 = time.monotonic()
        net.save(tmp)
        self.last_save_seconds = time.monotonic() - t0
        os.replace(tmp, path)
        self._prune()
        return path

    def save_snapshot(self, snap: "_HostSnapshot", step: int,
                      compressed: bool = True,
                      prune: bool = True) -> Optional[str]:
        """Write an already-captured :class:`_HostSnapshot` — the
        emergency-checkpoint entry point (parallel/preemption.py): the
        snapshot was taken the moment the preemption notice was
        processed, and ``compressed=False`` writes ZIP_STORED when the
        remaining grace budget won't fit a deflate pass.  Same atomic
        temp-file + rename protocol and writer-role guard as ``save``;
        ``prune=False`` skips the keep-last sweep (every millisecond of
        grace goes to the write itself)."""
        import zipfile
        if not self.is_writer:
            logger.debug("emergency checkpoint @%d skipped on non-writer "
                         "host %d", step, self.process_id)
            return None
        path = self._path(step)
        tmp = path + ".tmp"
        snap.save(tmp, compression=(zipfile.ZIP_DEFLATED if compressed
                                    else zipfile.ZIP_STORED))
        os.replace(tmp, path)
        if prune:
            self._prune()
        return path

    def save_async(self, net, step: int):
        """Snapshot now, write in the background; returns a Future of the
        final path (``None`` on non-writer hosts — no snapshot is taken).
        At most one write is in flight — a second call first waits for the
        previous write (backpressure beats unbounded host copies of the
        full model)."""
        from concurrent.futures import ThreadPoolExecutor
        if not self.is_writer:
            return None
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="ckpt-writer")
        if self._pending is not None:
            # Clear before result() (mirrors wait()): a failed background
            # write must raise once, not poison every later save_async.
            pending, self._pending = self._pending, None
            pending.result()
        snap = _HostSnapshot(net)

        def write():
            path = self._path(step)
            tmp = path + ".tmp"
            t0 = time.monotonic()
            snap.save(tmp)
            self.last_save_seconds = time.monotonic() - t0
            os.replace(tmp, path)
            self._prune()
            return path

        self._pending = self._executor.submit(write)
        return self._pending

    def wait(self) -> None:
        """Block until any in-flight async write has landed.  The pending
        slot is cleared even when the write failed — a stale exception
        must not re-raise forever — but the failure still propagates to
        THIS caller."""
        if self._pending is not None:
            pending, self._pending = self._pending, None
            pending.result()

    def _prune(self) -> None:
        if not self.is_writer:
            return
        ckpts = self.list_checkpoints()
        for path, _ in ckpts[:-self.keep_last]:
            try:
                os.remove(path)
            except OSError:
                pass

    def list_checkpoints(self) -> List:
        out = []
        for fn in sorted(os.listdir(self.directory)):
            if not (fn.startswith("checkpoint_") and fn.endswith(".zip")):
                continue
            m = self._NAME_RE.match(fn)
            if m is None:
                # a foreign/renamed file matching the glob must not
                # take down every list/prune/restore in the store
                logger.warning("skipping unparsable checkpoint filename "
                               "%s", fn)
                continue
            step, host = int(m.group(1)), m.group(2)
            if self.role == "per_host":
                if host is None or int(host) != self.process_id:
                    continue   # another host's shard — not ours to touch
            elif host is not None:
                continue       # per-host shard in a shared-writer store
            out.append((os.path.join(self.directory, fn), step))
        return out

    def latest(self) -> Optional[Any]:
        ckpts = self.list_checkpoints()
        return ckpts[-1] if ckpts else None

    def _quarantine(self, path: str) -> None:
        """Rename a checkpoint that failed to load to ``<path>.corrupt`` —
        keeps the evidence for post-mortem while taking it out of the
        rotation, so the next restore/prune doesn't re-try (or protect)
        a file that is known garbage."""
        try:
            os.replace(path, path + ".corrupt")
            logger.warning("quarantined corrupt checkpoint as %s.corrupt",
                           os.path.basename(path))
        except OSError:
            pass

    def restore_latest(self, loader: Callable[[str], Any]):
        """→ (model, step) from the newest INTACT checkpoint, or (None, -1).

        Waits for any in-flight async write first, so the newest state is
        always restorable; a FAILED async write is logged and skipped —
        recovery must proceed from the newest checkpoint that did land,
        not die on the write that didn't.  A checkpoint whose load fails
        (truncated/bit-flipped zip, integrity-digest mismatch — serializer
        format v4) is quarantined and restore falls through to the next
        older one: a corrupt LATEST must cost one checkpoint interval, not
        the whole job."""
        try:
            self.wait()
        except Exception as exc:
            logger.warning("in-flight async checkpoint write failed (%s) — "
                           "restoring from the newest on-disk checkpoint", exc)
        candidates = list(reversed(self.list_checkpoints()))
        for path, step in candidates:
            try:
                return loader(path), step
            except Exception as exc:
                logger.error("checkpoint %s failed to load (%s: %s) — "
                             "falling back to the next older checkpoint",
                             os.path.basename(path), type(exc).__name__, exc)
                self._quarantine(path)
        if candidates:
            logger.error("all %d checkpoints failed to load — restarting "
                         "from current in-memory params", len(candidates))
        return None, -1


class StepHangError(RecoverableInfraError):
    """The step watchdog fired: a dispatch exceeded ``step_timeout`` wall
    clock.  Message carries DEADLINE_EXCEEDED so the default
    FailureDetector classifies it as recoverable."""

    def __init__(self, elapsed: float, timeout: float):
        super().__init__(
            f"DEADLINE_EXCEEDED: step watchdog — dispatch took "
            f"{elapsed:.1f}s (> step_timeout={timeout:.1f}s); treating the "
            "step as hung and recovering from checkpoint")
        self.elapsed = elapsed
        self.timeout = timeout


class FailureDetector:
    """Decides whether an exception is a recoverable infrastructure failure
    (device loss, RPC deadline) vs a programming error that must propagate.
    Subclass / replace for custom health signals."""

    #: specific infrastructure signatures only — broad words like "device"
    #: or "internal" would misclassify deterministic bugs as recoverable
    #: and burn the restart budget re-hitting them
    RECOVERABLE_MARKERS = ("DEADLINE_EXCEEDED", "UNAVAILABLE", "DATA_LOSS",
                           "ABORTED", "device halted", "device lost",
                           "connection reset", "socket closed",
                           "non-finite gradient")

    def is_recoverable(self, exc: Exception) -> bool:
        if getattr(exc, "recoverable", None) is False:
            return False   # non-recoverable by construction: a preemption
            # notice (PreemptedError) means the HOST is going away —
            # retrying the step here would burn the grace budget
        if isinstance(exc, RecoverableInfraError):
            return True    # recoverable by construction (hang, host lost)
        if isinstance(exc, (ValueError, TypeError, KeyError)):
            return False   # programming errors propagate
        text = f"{type(exc).__name__}: {exc}"
        return any(m.lower() in text.lower() for m in self.RECOVERABLE_MARKERS)

    def on_failure(self, exc: Exception, attempt: int) -> None:
        logger.warning("step failure (attempt %d): %s", attempt, exc)


class ElasticTrainer:
    """Checkpoint-resume training loop with failure recovery.

    >>> et = ElasticTrainer(trainer, ckpt_dir, checkpoint_every=100)
    >>> et.fit(iterator, epochs=3)

    On a recoverable failure: rebuild (via ``rebuild_fn``, e.g. re-creating
    the mesh over surviving devices), restore the newest checkpoint, and
    continue from there.  ``max_restarts`` bounds the retry budget.

    Restart pacing: ``backoff_base > 0`` sleeps
    ``min(backoff_max, backoff_base * 2**(restarts-1))`` scaled by a seeded
    jitter factor between restore attempts — at pod scale, thousands of
    workers restarting in lockstep re-stampede the very storage/network
    that just failed; the jitter decorrelates them.  ``step_timeout``
    arms a wall-clock watchdog: a dispatch that neither completes nor
    raises (hung collective, lost device) is converted into a recoverable
    :class:`StepHangError` instead of blocking forever.  ``sleep_fn`` /
    ``clock`` are injectable so recovery timing is testable with a fake
    clock (tests/test_chaos.py).
    """

    def __init__(self, trainer, checkpoint_dir: str,
                 checkpoint_every: int = 100,
                 keep_last: int = 3,
                 max_restarts: int = 3,
                 failure_detector: Optional[FailureDetector] = None,
                 rebuild_fn: Optional[Callable[[], Any]] = None,
                 loader: Optional[Callable[[str], Any]] = None,
                 sync_every: int = 10,
                 restart_reset_after: Optional[int] = None,
                 async_checkpoints: bool = False,
                 backoff_base: float = 0.0,
                 backoff_max: float = 30.0,
                 backoff_jitter: float = 0.5,
                 jitter_seed: Optional[int] = None,
                 step_timeout: Optional[float] = None,
                 sleep_fn: Callable[[float], None] = time.sleep,
                 clock: Callable[[], float] = time.monotonic,
                 membership_check: Optional[Callable[[], None]] = None,
                 checkpoint_role: str = "auto",
                 preemption=None,
                 run_id: Optional[str] = None):
        import random
        import uuid

        self.trainer = trainer
        # stable identity of THIS training run, stamped into registry
        # lineage by the promotion pipeline (docs/LIFECYCLE.md) — pass
        # one explicitly to correlate relaunched workers of the same
        # logical run (the launcher's relaunch keeps the id; a fresh
        # controller generates one)
        self.run_id = run_id or uuid.uuid4().hex[:12]
        self.ckpt = CheckpointManager(checkpoint_dir, keep_last,
                                      role=checkpoint_role)
        self.checkpoint_every = max(1, checkpoint_every)
        self.max_restarts = max_restarts
        self.detector = failure_detector or FailureDetector()
        self.rebuild_fn = rebuild_fn
        self.loader = loader or self._default_loader
        self.sync_every = max(1, sync_every)
        self.async_checkpoints = async_checkpoints
        self.backoff_base = backoff_base
        self.backoff_max = backoff_max
        self.backoff_jitter = backoff_jitter
        self._jitter_rng = random.Random(jitter_seed)
        self.step_timeout = step_timeout
        self.sleep_fn = sleep_fn
        self.clock = clock
        # pod-scale membership: a callable polled before every step that
        # raises a RecoverableInfraError (e.g. launcher.HostLostError) on
        # host join/leave — the failure flows through the SAME backoff →
        # rebuild_fn → restore machinery as a device loss, so slice-
        # granular recovery (smaller dcn mesh over the survivors) is the
        # existing recovery path, not a parallel one
        self.membership_check = membership_check
        # announced failures (parallel/preemption.py): a PreemptionHandler
        # whose notice flag is checked at every STEP BOUNDARY — the
        # handler then captures an emergency checkpoint inside the grace
        # budget and raises PreemptedError, which is NOT recoverable (the
        # host is going away; the launcher relaunches the worker and
        # resume() picks the emergency checkpoint up)
        self.preemption = preemption
        # newest checkpoint step known DURABLE on disk (-1 = none yet):
        # sync saves record it inline, async saves when the background
        # write lands — surfaced through the heartbeat so the launcher's
        # pod-liveness report can answer "how much work would we lose"
        self.last_checkpoint_step = -1
        # ...and its path: the lifecycle pipeline reads
        # `final_checkpoint_path` after fit() to register the run's
        # durable artifact without parsing checkpoint filenames
        self.last_checkpoint_path: Optional[str] = None
        self.restarts = 0        # consecutive-failure budget (resets)
        self.total_restarts = 0  # lifetime count, for observability
        self.recovery_seconds = 0.0  # total wall clock spent in recovery
        self.backoff_sleeps: List[float] = []  # delays slept, observability
        # the watchdog arms only after one step has completed since the
        # last (re)start (it re-disarms on every recovery): the first step
        # jit-compiles (unbounded, legitimate wall clock) and a restore
        # re-places + recompiles — counting compile time as a hang would
        # turn every cold start into a spurious recovery loop
        self._watchdog_armed = False
        self.global_step = 0
        # max_restarts bounds CONSECUTIVE failures, not lifetime failures:
        # after this many successful steps the counter resets, so a
        # months-long job surviving occasional pre-emptions doesn't
        # eventually die with 'exceeded max_restarts' despite every
        # incident having recovered
        self.restart_reset_after = (restart_reset_after
                                    if restart_reset_after is not None
                                    else checkpoint_every)
        self._ok_steps = 0
        # unified registry (docs/OBSERVABILITY.md): process-wide recovery
        # counters plus this trainer's structured stats as a collector —
        # one /metrics response answers "how often is this job failing"
        reg = get_registry()
        self._m_restarts = reg.counter("elastic_restarts_total")
        self._m_recovery_s = reg.counter("elastic_recovery_seconds_total")
        self._m_backoff = reg.counter("elastic_backoff_sleeps_total")
        reg.register_collector("elastic", self.recovery_stats, unique=True)

    def recovery_stats(self) -> dict:
        """Structured recovery counters (the registry collector view)."""
        return {"run_id": self.run_id,
                "global_step": self.global_step,
                "restarts": self.restarts,
                "total_restarts": self.total_restarts,
                "recovery_seconds": round(self.recovery_seconds, 3),
                "backoff_sleeps": len(self.backoff_sleeps),
                "last_checkpoint_step": self.last_checkpoint_step}

    def _record_durable(self, step: int, path) -> None:
        """A checkpoint write for ``step`` landed (path None = this host
        is not the writer — the durable step is unknown here)."""
        if path is not None and step > self.last_checkpoint_step:
            self.last_checkpoint_step = step
            self.last_checkpoint_path = str(path)

    @property
    def final_checkpoint_path(self) -> Optional[str]:
        """The newest checkpoint known durable on disk for this run —
        after ``fit()`` returns, the run's final artifact (``fit``
        always lands a last checkpoint).  None before any write landed
        on this host (non-writer hosts never observe a path)."""
        return self.last_checkpoint_path

    @staticmethod
    def _default_loader(path: str):
        from ..utils.serializer import load_model
        return load_model(path)

    @property
    def net(self):
        return getattr(self.trainer, "net", self.trainer)

    def _restore(self) -> None:
        with obs_trace.span("ckpt/restore", cat="ckpt"):
            model, step = self.ckpt.restore_latest(self.loader)
        if model is None:
            logger.warning("no checkpoint to restore — restarting from "
                           "current params")
            return
        net = self.net
        net.params = model.params
        net.state = model.state
        net.opt_state = model.opt_state
        # None when the checkpoint predates compression (or it is off) —
        # _place_model re-inits zeros in that case
        net.grad_residual = getattr(model, "grad_residual", None)
        net.iteration = model.iteration
        self.global_step = step
        # the checkpoint just loaded is by definition durable on disk
        if step >= self.last_checkpoint_step:
            self.last_checkpoint_step = step
            self.last_checkpoint_path = self.ckpt._path(step)
        logger.info("restored checkpoint @ step %d", step)

    def resume(self) -> int:
        """Restore the newest intact checkpoint before training starts and
        return the restored global step (0 when the store is empty) — the
        host-(re)join entry point: a relaunched worker calls ``resume()``
        and continues the loop from wherever the cluster's checkpoints
        left off, instead of only recovering after a mid-training
        failure."""
        if self.ckpt.latest() is None:
            return 0   # fresh store — nothing to resume, no warning
        with obs_trace.span("elastic/resume", cat="elastic") as sp:
            self._restore()
            if self.global_step > 0 and hasattr(self.trainer, "_place_model"):
                self.trainer._place_model()
            sp.set(step=self.global_step)
        self._watchdog_armed = False
        return self.global_step

    def _materialize(self, loss) -> None:
        """Force the device barrier (``loss.value()``), under the watchdog
        when ``step_timeout`` is armed: a dispatch that never completes
        (hung collective, lost device) raises neither — the read
        just blocks.  Running the read on a worker thread bounds the wait;
        on timeout the worker is abandoned (it stays parked on the dead
        dispatch) and the step surfaces as a recoverable StepHangError."""
        if self.step_timeout is None or not self._watchdog_armed:
            loss.value()
            return
        import threading
        box: dict = {}

        def read():
            try:
                box["v"] = loss.value()
            except Exception as exc:  # surfaced below, on the caller
                box["e"] = exc

        # a bare DAEMON thread, not an executor worker: a genuinely hung
        # read parks this thread forever, and a non-daemon worker would
        # then block interpreter exit at the executor's atexit join
        t = threading.Thread(target=read, daemon=True, name="step-watchdog")
        t.start()
        t.join(self.step_timeout)
        if t.is_alive():
            raise StepHangError(self.step_timeout, self.step_timeout)
        if "e" in box:
            raise box["e"]

    def _backoff_delay(self) -> float:
        """Exponential backoff with seeded jitter for restart ``restarts``
        (1-based; call after incrementing).  0 when backoff is disabled."""
        if self.backoff_base <= 0:
            return 0.0
        base = min(self.backoff_max,
                   self.backoff_base * (2.0 ** (self.restarts - 1)))
        return base * (1.0 + self.backoff_jitter * self._jitter_rng.random())

    def fit_batch(self, ds) -> float:
        """One step with checkpoint + recovery semantics.

        The underlying fit_batch is async (device-resident LazyScore); a
        device failure would otherwise surface at some later read, outside
        this try block.  Materializing every ``sync_every`` steps keeps the
        failure inside the recovery loop while amortizing the host sync —
        at most sync_every steps are replayed from the last checkpoint.
        With ``step_timeout`` set, a step whose wall clock exceeds the
        budget — whether it blocked in the dispatch (caught by the
        watchdog thread) or crawled through a degraded link (caught by the
        elapsed check) — is treated as hung and recovered."""
        while True:
            # step boundary: the preemption flag is processed here, OUTSIDE
            # the recovery try — a notice is not a failure to retry, it is
            # an order to checkpoint and leave (PreemptedError propagates)
            if self.preemption is not None:
                self.preemption.check(self)
            t_start = self.clock()
            try:
                if self.membership_check is not None:
                    # inside the try: a HostLostError / membership change
                    # takes the normal recovery path (backoff → rebuild →
                    # restore), not an unhandled crash
                    self.membership_check()
                loss = self.trainer.fit_batch(ds)
                self.global_step += 1
                saving = self.global_step % self.checkpoint_every == 0
                if (saving or self.global_step % self.sync_every == 0) \
                        and hasattr(loss, "value"):
                    # device barrier: surfaces async failures — ALWAYS
                    # before a checkpoint write, so a latent failure can't
                    # first materialize mid-save and corrupt the newest
                    # checkpoint
                    self._materialize(loss)
                if self.step_timeout is not None:
                    elapsed = self.clock() - t_start
                    if self._watchdog_armed and elapsed > self.step_timeout:
                        raise StepHangError(elapsed, self.step_timeout)
                    self._watchdog_armed = True
                if saving:
                    with obs_trace.span("ckpt/save", cat="ckpt",
                                        step=self.global_step,
                                        is_async=self.async_checkpoints):
                        if self.async_checkpoints:
                            # zip/deflate overlaps the next training
                            # steps; the device→host snapshot happens
                            # here (the next step donates these buffers)
                            fut = self.ckpt.save_async(self.net,
                                                       self.global_step)
                            if fut is not None:
                                step_saved = self.global_step
                                fut.add_done_callback(
                                    lambda f, s=step_saved:
                                    self._record_durable(
                                        s, None if f.exception()
                                        else f.result()))
                        else:
                            self._record_durable(
                                self.global_step,
                                self.ckpt.save(self.net, self.global_step))
                self._ok_steps += 1
                if self._ok_steps >= self.restart_reset_after and self.restarts:
                    logger.info("%d successful steps since last failure — "
                                "resetting restart counter", self._ok_steps)
                    self.restarts = 0
                return loss
            except Exception as exc:
                if not self.detector.is_recoverable(exc):
                    raise
                t_fail = self.clock()
                self._ok_steps = 0
                self.restarts += 1
                self.total_restarts += 1
                obs_trace.instant("fault", cat="elastic",
                                  kind=type(exc).__name__,
                                  step=self.global_step,
                                  restart=self.restarts)
                self._m_restarts.inc()
                self.detector.on_failure(exc, self.restarts)
                if self.restarts > self.max_restarts:
                    raise RuntimeError(
                        f"exceeded max_restarts={self.max_restarts}") from exc
                with obs_trace.span("elastic/recovery", cat="elastic",
                                    kind=type(exc).__name__,
                                    step=self.global_step):
                    delay = self._backoff_delay()
                    if delay > 0:
                        logger.info("backing off %.2fs before restart %d "
                                    "(exponential + jitter)", delay,
                                    self.restarts)
                        self.backoff_sleeps.append(delay)
                        self._m_backoff.inc()
                        self.sleep_fn(delay)
                    if self.rebuild_fn is not None:
                        self.trainer = self.rebuild_fn()
                    self._restore()
                    # restored params are host arrays — a sharded trainer
                    # must re-place them on its (possibly rebuilt) mesh
                    # before the next step, or the jit step sees
                    # uncommitted inputs
                    if hasattr(self.trainer, "_place_model"):
                        self.trainer._place_model()
                # re-placement/rebuild recompiles: the next step gets the
                # cold-start compile grace again
                self._watchdog_armed = False
                spent = self.clock() - t_fail
                self.recovery_seconds += spent
                self._m_recovery_s.inc(max(0.0, spent))

    def fit(self, data, epochs: int = 1) -> List[float]:
        losses: List[float] = []
        net = self.net
        it = net._as_iterator(data) if hasattr(net, "_as_iterator") else data
        for _ in range(epochs):
            for ds in it:
                losses.append(self.fit_batch(ds))
        # final checkpoint so a clean shutdown is always resumable: FLUSH
        # any in-flight save_async first — without the wait() a clean exit
        # could return while the newest state is still half-written on the
        # background thread — then skip the re-serialization when the last
        # step already checkpointed durably
        self.ckpt.wait()
        latest = self.ckpt.latest()
        if latest is None or latest[1] != self.global_step:
            self._record_durable(self.global_step,
                                 self.ckpt.save(self.net, self.global_step))
        return losses
