"""Bytes the attention of one decode step of a GPT-2-family LM must read
from device memory: the cached K and V rows of the live slots' earlier
positions, in every layer, at the precision the configuration states
for its pool (float32).

Only what ANY correct program must read is counted: a row's
``n_embd`` values (no lane padding), once a pool a layer.  Not counted,
though the kernel reads or writes them: the query and result rows, the
new row written in the step, the part of a slot's last page past its
length, the page table.  The caller counts a slot's FULL pages only (as
``decode_bytes_roofline`` does), so the bytes are a floor and
``paged_attention_roofline`` cannot pass 100%.
"""

from __future__ import annotations

CACHE_BYTES = 4        # float32, as the configuration states
POOLS = 2              # keys and values


def row_bytes(c: dict) -> int:
    """One cached position: both pools, all layers."""
    return c["n_embd"] * CACHE_BYTES * POOLS * c["n_layer"]


def step_bytes(c: dict, cached_rows: float) -> float:
    """``cached_rows``: earlier positions of the live slots, summed over
    the slots."""
    return cached_rows * row_bytes(c)
