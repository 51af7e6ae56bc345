"""LazyScore — a device-resident loss scalar with float semantics.

Why this exists: the reference's ``MultiLayerNetwork.fit`` returns ``score``
as a Java double, which on GPU forces a device→host readback every iteration
(reference nn/multilayer/MultiLayerNetwork.java:1165 → ``score()``).  On TPU
a per-step readback serializes dispatch — the host cannot enqueue step n+1
until step n's loss has crossed back — and caps training throughput below
what the chip can do.

So ``fit_batch`` returns the loss as a *future*: a 0-d ``jax.Array`` still
on device, wrapped so it behaves like a ``float`` the moment anyone actually
reads it (printing, comparing, ``round``-ing, numpy-converting).  A training
loop that just chains ``fit_batch`` calls never blocks; XLA keeps the device
busy while Python races ahead enqueueing the next steps.  The first numeric
use materializes (and caches) the host value.

This is the TPU-native analog of the reference's async gradient machinery
(``EncodedGradientsAccumulator``): don't make the host a per-step barrier.
"""

from __future__ import annotations

from typing import Optional


def materialize_scores(scores) -> None:
    """Batch-materialize every un-read LazyScore in ``scores`` with ONE
    device transfer (``jax.device_get`` of all pending 0-d buffers), then
    cache the floats.  Per-score ``float()`` would pay one host round trip
    each — on a remote TPU that's ~100ms × steps; this is one."""
    import jax

    from ..obs import trace as obs_trace
    lazy = [s for s in scores
            if isinstance(s, LazyScore) and not s.materialized]
    if not lazy:
        return
    # the batched device barrier (one transfer for the whole epoch) —
    # the other place step device time surfaces on the host timeline
    with obs_trace.span("train/device_sync", cat="train", n_scores=len(lazy)):
        vals = jax.device_get([s._dev for s in lazy])
    for s, v in zip(lazy, vals):
        s._val = float(v)
        s._dev = None


class LazyScore:
    """Float-like view of a device scalar; blocks only on first read.

    ``float(score)``, ``f"{score:.4f}"``, comparisons, arithmetic, ``round``
    and ``np.asarray`` all materialize the value (cached after the first
    read).  ``score.device_value()`` hands back the un-materialized
    ``jax.Array`` for callers that want to keep computation on device
    (e.g. accumulating an epoch-mean loss without syncing).
    """

    __slots__ = ("_dev", "_val")

    def __init__(self, device_scalar, value: Optional[float] = None):
        self._dev = device_scalar
        self._val = value

    # -- materialization ---------------------------------------------------

    def value(self) -> float:
        if self._val is None:
            from ..obs import trace as obs_trace
            # the host<->device barrier of the step — the only blocking
            # read in a chained fit_batch loop (docs/OBSERVABILITY.md)
            with obs_trace.span("train/device_sync", cat="train"):
                self._val = float(self._dev)
            self._dev = None  # drop the device buffer once read
        return self._val

    def device_value(self):
        """The underlying 0-d jax.Array (or the cached float if already
        materialized) — for device-side accumulation without a sync."""
        return self._dev if self._dev is not None else self._val

    @property
    def materialized(self) -> bool:
        return self._val is not None

    # -- float protocol ----------------------------------------------------

    def __float__(self) -> float:
        return self.value()

    def __int__(self) -> int:
        return int(self.value())

    def __bool__(self) -> bool:
        return bool(self.value())

    def __round__(self, ndigits=None):
        return round(self.value(), ndigits)

    def __format__(self, spec: str) -> str:
        return format(self.value(), spec)

    def __repr__(self) -> str:
        return repr(self.value())

    def __str__(self) -> str:
        return str(self.value())

    def __hash__(self) -> int:
        return hash(self.value())

    def __array__(self, dtype=None, copy=None):
        import numpy as np
        return np.asarray(self.value(), dtype=dtype)

    # -- comparisons -------------------------------------------------------

    @staticmethod
    def _coerce(other):
        return other.value() if isinstance(other, LazyScore) else other

    def __eq__(self, other):
        return self.value() == self._coerce(other)

    def __ne__(self, other):
        return self.value() != self._coerce(other)

    def __lt__(self, other):
        return self.value() < self._coerce(other)

    def __le__(self, other):
        return self.value() <= self._coerce(other)

    def __gt__(self, other):
        return self.value() > self._coerce(other)

    def __ge__(self, other):
        return self.value() >= self._coerce(other)

    # -- arithmetic (materializes; use device_value() to stay on device) ---

    def __add__(self, other):
        return self.value() + self._coerce(other)

    def __radd__(self, other):
        return self._coerce(other) + self.value()

    def __sub__(self, other):
        return self.value() - self._coerce(other)

    def __rsub__(self, other):
        return self._coerce(other) - self.value()

    def __mul__(self, other):
        return self.value() * self._coerce(other)

    def __rmul__(self, other):
        return self._coerce(other) * self.value()

    def __truediv__(self, other):
        return self.value() / self._coerce(other)

    def __rtruediv__(self, other):
        return self._coerce(other) / self.value()

    def __neg__(self):
        return -self.value()

    def __abs__(self):
        return abs(self.value())
