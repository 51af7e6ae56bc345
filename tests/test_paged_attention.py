"""ops/paged_attention.py on cpu (the Pallas interpreter): the decode
step's kernel against ``det_attention`` over the gathered window.

What it must give: the window's result to rounding (it sums over the
pages a slot holds, in blocks, where ``det_attention`` sums over all L
keys at once) at every length, query count and head geometry; zeros and
no read at all for a slot of length 0; a slot's bits from its own rows
and length alone.  tests/test_flash_tpu_compile.py compiles it for the
described v5e; what it does to a whole engine is in tests/test_decode*.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.kv_cache import (
    NEG_INF, QuantPages, _quantize_rows, head_lanes,
)
from deeplearning4j_tpu.ops.paged_attention import (
    kept_path, paged_attention, paged_tiles, window_attention,
)

#: the widest gap these cases show is 6.0e-7 (outputs up to 3.7); the
#: limit is tests/_decode_checks.py's
ATOL = 2e-6
LAYERS, LAYER = 2, 1

# (H, d), page_size, pages_per_slot
GEOMETRIES = {
    "gpt2-large-20x64": ((20, 64), 16, 64),
    "toy-4x8": ((4, 8), 4, 8),
    "widened-3x40": ((3, 40), 8, 5),       # a head of 40 sits in 128 lanes
}
#: rows a slot holds before its T new ones, given (page, window, T)
HELD = {
    "new-rows-only": lambda page, L, t: t,
    "one-page-exactly": lambda page, L, t: max(page, t),
    "a-page-plus-one-row": lambda page, L, t: max(page, t) + 1,
    "full-window": lambda page, L, t: L,
    # a speculative step at the window's end: its last rows lie past it,
    # see the whole window and are never committed; the rows before them
    # keep their own horizon
    "past-the-windows-end": lambda page, L, t: L + max(1, t // 2),
}
SLOTS = 3


@functools.lru_cache(maxsize=None)
def _setup(geometry):
    """Random pools whose scratch page is NaN (a read of it shows), the
    same with a clean scratch page for the reference, and a table whose
    pages are out of order."""
    (h, d), page, pps = GEOMETRIES[geometry]
    rng = np.random.default_rng(sum(map(ord, geometry)))
    hl = head_lanes(h, d)
    n_pages = 1 + SLOTS * pps

    def pool():
        x = rng.standard_normal((LAYERS, n_pages, page, h, d))
        x = np.pad(x.astype(np.float32), [(0, 0)] * 4 + [(0, hl - d)])
        x = x.reshape(LAYERS, n_pages, page, h * hl)
        poisoned = x.copy()
        x[:, 0] = 0.0
        poisoned[:, 0] = np.nan
        return jnp.asarray(poisoned), jnp.asarray(x)

    (kp, kp_clean), (vp, vp_clean) = pool(), pool()
    table = rng.permutation(np.arange(1, n_pages)).reshape(SLOTS, pps)
    return (h, d), page, pps, kp, vp, kp_clean, vp_clean, table.astype(np.int32)


@functools.lru_cache(maxsize=None)
def _kernel(geometry, t_q):
    heads = GEOMETRIES[geometry][0]
    return jax.jit(lambda q, kp, vp, table, lens: paged_attention(
        q, kp, vp, LAYER, table, lens, heads))


def _queries(geometry, t_q, seed=0):
    (h, d), _, _ = GEOMETRIES[geometry]
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (SLOTS, h, t_q, d)).astype(np.float32))


def _held_table(table, lens, page):
    """The engine's table: entries past the pages a slot holds are 0."""
    held = -(-np.asarray(lens) // page)
    return jnp.asarray(np.where(
        np.arange(table.shape[1])[None, :] < held[:, None], table, 0))


def _window(q, kp, vp, table, lens, heads, page):
    t_q = q.shape[2]
    last = np.asarray(lens)[:, None] - (t_q - 1 - np.arange(t_q))
    bias = jnp.where(
        jnp.arange(table.shape[1] * page)[None, None, :] < last[:, :, None],
        0.0, NEG_INF)[:, None]
    return np.asarray(window_attention(q, kp, vp, LAYER, table, bias, heads))


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("t_q", [1, 4])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_matches_det_attention_over_the_window(geometry, t_q, held):
    """Slot 0 holds the case's rows, slot 1 is inactive (length 0, its
    table row zeroed) and slot 2 holds a few pages and a row: all three
    against ``det_attention`` over the gathered window, through a table
    out of order, with the scratch page NaN."""
    heads, page, pps, kp, vp, kp_clean, vp_clean, table = _setup(geometry)
    L = pps * page
    lens = np.array([HELD[held](page, L, t_q), 0,
                     min(L, 2 * page + 1 + t_q)], np.int32)
    pt = _held_table(table, lens, page)
    q = _queries(geometry, t_q)
    out = np.asarray(_kernel(geometry, t_q)(q, kp, vp, pt, jnp.asarray(lens)))
    assert out.shape == q.shape and out.dtype == np.float32
    assert np.isfinite(out).all()            # the scratch page was not read
    assert not out[1].any()                  # an inactive slot: zeros
    want = _window(q, kp_clean, vp_clean, pt, lens, heads, page)
    np.testing.assert_allclose(out[[0, 2]], want[[0, 2]], rtol=0, atol=ATOL)


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_slot_reads_its_own_rows_and_length_only(geometry):
    """Bitwise: a slot alone (the others inactive) and co-batched (the
    others live, the pool's other pages different), and each row of a
    four-row call against the one-row call at that row's length (what
    keeps ``spec_step`` and ``step`` equal bit for bit)."""
    heads, page, pps, kp, vp, _, _, table = _setup(geometry)
    L = pps * page
    n = min(L, 3 * page + 2)
    q = _queries(geometry, 1, seed=1)
    lens = np.array([n, 0, 0], np.int32)
    alone = np.asarray(_kernel(geometry, 1)(
        q, kp, vp, _held_table(table, lens, page), jnp.asarray(lens)))
    lens = np.array([n, L, page], np.int32)
    others = jnp.asarray(table)[1:].reshape(-1)
    both = np.asarray(_kernel(geometry, 1)(
        q.at[1:].add(1.0), kp.at[:, others].add(1.0), vp.at[:, others].add(1.0),
        _held_table(table, lens, page), jnp.asarray(lens)))
    assert np.array_equal(alone[0], both[0])

    q4 = _queries(geometry, 4, seed=2)
    lens = np.array([n, 4, L + 2], np.int32)      # slot 2 overshoots
    pt = _held_table(table, lens, page)
    four = np.asarray(_kernel(geometry, 4)(q4, kp, vp, pt, jnp.asarray(lens)))
    for t in range(4):
        one = np.asarray(_kernel(geometry, 1)(
            q4[:, :, t:t + 1], kp, vp, pt, jnp.asarray(lens - (3 - t))))
        assert np.array_equal(four[:, :, t], one[:, :, 0]), f"row {t}"


def test_an_int8_pool_keeps_the_gathered_window():
    """What the kernel does not take goes the present way, and says so to
    the engine's ``kv_pages_read`` (``kept_path``)."""
    heads, page, pps, _, _, kp, vp, table = _setup("toy-4x8")
    h, d = heads
    lens = np.array([5, 0, 9], np.int32)
    quant = lambda p: QuantPages(*_quantize_rows(
        p.reshape(p.shape[:3] + (h, -1))[..., :d]))
    qk, qv = quant(kp), quant(vp)
    qk = QuantPages(qk.q.reshape(kp.shape[:3] + (-1,)), qk.scale)
    qv = QuantPages(qv.q.reshape(vp.shape[:3] + (-1,)), qv.scale)
    assert kept_path(kp, pps) is None and kept_path(qk, pps) == "int8 pool"
    assert "shard_map" in kept_path(kp, pps, tp=4)       # the interpreter
    pt = _held_table(table, lens, page)
    q = _queries("toy-4x8", 1)
    out = np.asarray(paged_attention(q, qk, qv, LAYER, pt, jnp.asarray(lens),
                                     heads))
    want = _window(q, qk, qv, pt, lens, heads, page)
    assert np.array_equal(out[[0, 2]], want[[0, 2]])
    assert np.isfinite(out).all()


@pytest.mark.parametrize("page,pps,lanes,itemsize,pages", [
    (16, 64, 1280, 4, 8),        # gpt2-large: 128 rows a block
    (16, 64, 1024, 4, 8),        # gpt2-medium
    (4, 8, 128, 4, 8),           # a toy window is one block
    (16, 256, 8192, 4, 2),       # a wide row: what the buffers hold
    (16, 64, 640, 2, 8),         # bf16
    (64, 16, 32768, 4, None),    # one page is more than the buffers
])
def test_paged_tiles_chooses_a_block_from_the_shapes(page, pps, lanes,
                                                     itemsize, pages):
    tiles = paged_tiles(page, pps, lanes, itemsize)
    if pages is None:
        assert tiles is None
        return
    assert tiles.pages == pages and tiles.rows == pages * page
    assert tiles.vmem_bytes == 4 * tiles.rows * lanes * itemsize <= 4 << 20
