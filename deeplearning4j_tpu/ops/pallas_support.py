"""What the Pallas call sites in ops/ share: kernels run compiled on tpu
and interpreted on cpu (the tests), and a kernel that was asked for but is
not what runs on a tpu says so.
"""

from __future__ import annotations

import logging

import jax

logger = logging.getLogger("deeplearning4j_tpu")


def interpret() -> bool:
    """``pallas_call(interpret=...)``: the HLO interpreter on cpu only."""
    return jax.default_backend() == "cpu"


def fell_back(kernel: str, why: str) -> None:
    """Call at TRACE time where ``kernel`` was asked for and the XLA path
    runs instead.  Dispatch by shape or dtype is legitimate; losing the
    kernel on the chip without a word is not — a benchmark would then time
    a program its caller did not ask for."""
    if jax.default_backend() == "tpu":
        # graftcheck: disable=GC102 (shape-static dispatch notice: firing ONCE at trace time is the intended behavior)
        logger.warning("%s: pallas kernel not used on tpu — %s; running "
                       "the XLA path", kernel, why)


_ENGAGED: set = set()


def engaged(kernel: str, what: str) -> None:
    """Call at TRACE time where ``kernel`` is what runs: says once per
    distinct ``kernel`` (its name carries the shapes) what it engaged
    with — the opposite case of ``fell_back``."""
    if kernel not in _ENGAGED:
        _ENGAGED.add(kernel)
        # graftcheck: disable=GC102 (shape-static dispatch notice: firing ONCE at trace time is the intended behavior)
        logger.info("%s: pallas kernel engaged — %s", kernel, what)
