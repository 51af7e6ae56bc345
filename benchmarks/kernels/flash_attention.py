"""Operations and bytes of the flash-attention Mosaic kernels
(``ops/attention.py``), from the shapes of one call.

The train step makes three calls per layer, told apart by what they
return: the forward (``o``, ``lse``), the backward for keys and values
(``dk``, ``dv``) and the backward for queries (``dq``).  Operations are
what each call's own mathematics needs, causal (half the score matrix):
forward 2 products (scores, values); ``dk/dv`` 4 (scores again, dP, dV,
dK); ``dq`` 3 (scores again, dP, dQ).  Bytes are each operand read once
and each result written once.
"""

from __future__ import annotations

import re
from typing import Optional, Tuple

_DIMS = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}
PRODUCTS = {"forward": 2, "backward_dkdv": 4, "backward_dq": 3}


def classify(shape: str) -> Optional[Tuple[str, int, int, int, int]]:
    """(kind, batch*heads, seq, head_dim, itemsize) of a custom call from
    its result shape as the trace prints it, or None if it is no flash
    call."""
    parts = _DIMS.findall(shape)
    if not parts:
        return None
    dt, bh, t, d = parts[0]
    bh, t, d = int(bh), int(t), int(d)
    if len(parts) == 1:
        kind = "backward_dq"
    elif parts[1][0] == "f32" and int(parts[1][2]) == 1:
        kind = "forward"                       # (o, lse[bh, 1, t])
    else:
        kind = "backward_dkdv"
    return kind, bh, t, d, ITEMSIZE[dt]


def flops(kind: str, bh: int, t: int, d: int, causal: bool = True) -> float:
    per_product = 2.0 * bh * t * t * d * (0.5 if causal else 1.0)
    return PRODUCTS[kind] * per_product


def bytes_moved(kind: str, bh: int, t: int, d: int, itemsize: int) -> float:
    tensor, row_stat = bh * t * d * itemsize, bh * t * 4
    if kind == "forward":                      # q k v -> o, lse
        return 4 * tensor + row_stat
    if kind == "backward_dkdv":                # q k v do lse delta -> dk dv
        return 6 * tensor + 2 * row_stat
    return 5 * tensor + 2 * row_stat           # q k v do lse delta -> dq


def least_seconds(kind, bh, t, d, itemsize, peaks) -> Tuple[float, str]:
    """The least time the chip could take for the call, and what bounds it."""
    by_compute = flops(kind, bh, t, d) / peaks["bf16_flops_per_s"]
    by_memory = bytes_moved(kind, bh, t, d, itemsize) / peaks["hbm_bytes_per_s"]
    return (by_compute, "compute") if by_compute >= by_memory \
        else (by_memory, "memory")
