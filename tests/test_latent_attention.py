"""ops/latent_attention.py on cpu (the Pallas interpreter): the latent
decode step's kernel against the absorbed path over the gathered window.

What it must give: the window's result to rounding (it sums over the
pages a slot holds, in blocks, with the slot's own new row as the first
key, where ``window_attention`` takes one softmax over all L keys and
the new row) at every length; no read at all for a slot that holds
nothing; a slot's bits from its own rows and length alone; through the
decode program the gathered window's tokens, ``step_multi`` as four
``step``s, and the engine's ``kv_pages_read`` the pages held.
tests/test_flash_tpu_compile.py compiles the step for the described v5e.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import latent_moe
from deeplearning4j_tpu.ops import latent_attention as la
from test_latent_moe import SIZES, arch_of, tree_of

#: the widest gaps these cases show: float32 2.4e-7 (outputs up to 2.8),
#: bfloat16 7.9e-3 (up to 3.9: the weights go into the second product as
#: bfloat16, before they are divided by their sum in the kernel and after
#: it in the window)
ATOL = {"float32": 2e-6, "bfloat16": 2e-2}
LAYERS, LAYER, SLOTS = 2, 1, 3

# heads, lanes, width (values of a row), page_size, pages_per_slot, dtype
GEOMETRIES = {
    "kimi-row-bf16": (64, 640, 512, 16, 40, "bfloat16"),
    "toy-f32": (4, 128, 16, 8, 96, "float32"),
    "toy-f32-one-block": (4, 128, 24, 8, 16, "float32"),
}
#: rows of earlier positions slot 0 holds, given (page, rows a block, window)
HELD = {
    "nothing": lambda page, block, L: 0,
    "one-row": lambda page, block, L: 1,
    "a-row-short-of-a-page": lambda page, block, L: page - 1,
    "a-whole-block": lambda page, block, L: block,
    "a-block-and-a-row": lambda page, block, L: min(L, block + 1),
    "the-whole-window": lambda page, block, L: L,
}


@functools.lru_cache(maxsize=None)
def _setup(geometry):
    """A random pool whose scratch page is NaN (a read of it shows), the
    same with a clean scratch page for the window, queries, new rows and
    a table whose pages are out of order."""
    h, lanes, width, page, pps, dtype = GEOMETRIES[geometry]
    rng = np.random.default_rng(sum(map(ord, geometry)))
    n_pages = 1 + SLOTS * pps
    x = rng.standard_normal((LAYERS, n_pages, page, lanes)).astype(np.float32)
    poisoned = x.copy()
    x[:, 0] = 0.0
    poisoned[:, 0] = np.nan
    table = rng.permutation(np.arange(1, n_pages)).reshape(SLOTS, pps)
    q = 0.3 * rng.standard_normal((SLOTS, h, lanes))
    new = rng.standard_normal((SLOTS, lanes))
    cast = lambda a: jnp.asarray(a, dtype)
    return (cast(poisoned), cast(x), cast(q), cast(new),
            table.astype(np.int32))


@functools.lru_cache(maxsize=None)
def _kernel(geometry):
    width = GEOMETRIES[geometry][2]
    scale = _arch(width).softmax_scale
    return jax.jit(lambda q, new, pool, table, held: la.latent_attention(
        q, new, pool, LAYER, table, held, width, scale))


def _held_table(table, held, page):
    """The engine's table: entries past the pages a slot holds are 0."""
    pages = -(-np.asarray(held) // page)
    return jnp.asarray(np.where(
        np.arange(table.shape[1])[None, :] < pages[:, None], table, 0))


def _gather(pool, table):
    g = pool[LAYER, table]                         # [S, pps, page, lanes]
    return g.reshape(g.shape[0], -1, g.shape[-1])


@functools.lru_cache(maxsize=None)
def _arch(width):
    """An architecture whose cached row holds ``width`` values of c_kv:
    all ``attend_window`` reads of it, beside the softmax's scale."""
    arch = arch_of({**SIZES, "kv_lora_rank": width})
    assert arch.kv_lora_rank == width
    return arch


def _window(geometry, q, new, pool, table, held):
    """``models/latent_moe.attend_absorbed``'s attention over the
    gathered window."""
    arch = _arch(GEOMETRIES[geometry][2])
    return np.asarray(latent_moe.attend_window(
        q, new, _gather(pool, table), jnp.asarray(held), arch))


def _tiles(geometry):
    _, lanes, _, page, pps, dtype = GEOMETRIES[geometry]
    return la.latent_tiles(page, pps, lanes, jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_matches_the_absorbed_path_over_the_window(geometry, held):
    """Slot 0 holds the case's rows, slot 1 is not active (it holds
    nothing and its table row is scratch) and slot 2 holds a few pages
    and a row: all three against the gathered window, through a table out
    of order, with the scratch page NaN."""
    h, lanes, width, page, pps, dtype = GEOMETRIES[geometry]
    poisoned, clean, q, new, table = _setup(geometry)
    L = pps * page
    held = np.array([HELD[held](page, _tiles(geometry).rows, L), 0,
                     2 * page + 1], np.int32)
    pt = _held_table(table, held, page)
    out = np.asarray(_kernel(geometry)(q, new, poisoned, pt,
                                       jnp.asarray(held)))
    assert out.shape == (SLOTS, h, width) and out.dtype == np.float32
    assert np.isfinite(out).all()            # the scratch page was not read
    want = _window(geometry, q, new, clean, pt, held)
    np.testing.assert_allclose(out, want, rtol=0, atol=ATOL[dtype])
    # a slot that holds nothing attends to its own row alone
    own = np.asarray(new.astype(jnp.float32))[1, :width]
    np.testing.assert_array_equal(out[1], np.broadcast_to(own, (h, width)))


def test_rows_held_are_clamped_to_the_window():
    """A fused horizon's last steps may ask past the window's end: the
    kernel reads the window and no further (the window's own mask)."""
    geometry = "toy-f32"
    _, _, _, page, pps, _ = GEOMETRIES[geometry]
    poisoned, clean, q, new, table = _setup(geometry)
    L = pps * page
    over = np.array([L + 3, -2, L], np.int32)
    pt = jnp.asarray(table)
    out = np.asarray(_kernel(geometry)(q, new, poisoned, pt,
                                       jnp.asarray(over)))
    want = _window(geometry, q, new, clean, pt, np.array([L, 0, L]))
    np.testing.assert_allclose(out, want, rtol=0, atol=ATOL["float32"])


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_a_slot_reads_its_own_rows_and_length_only(geometry):
    """Bitwise: a slot alone (the others not active) and co-batched (the
    others live, their queries and rows different and the pool's other
    pages NaN: a poisoned neighbour's rows, which pass through the same
    two buffers, do not get into the slot's sums)."""
    _, _, _, page, pps, _ = GEOMETRIES[geometry]
    poisoned, _, q, new, table = _setup(geometry)
    L = pps * page
    n = min(L, _tiles(geometry).rows + 3 * page + 2)
    held = np.array([n, 0, 0], np.int32)
    alone = np.asarray(_kernel(geometry)(
        q, new, poisoned, _held_table(table, held, page), jnp.asarray(held)))
    held = np.array([n, L, page], np.int32)
    others = jnp.asarray(table)[1:].reshape(-1)
    both = np.asarray(_kernel(geometry)(
        q.at[1:].add(1.0), new.at[1:].add(1.0),
        poisoned.at[:, others].set(jnp.nan), _held_table(table, held, page),
        jnp.asarray(held)))
    assert np.isfinite(alone[0]).all() and not np.isfinite(both[1:]).any()
    assert np.array_equal(alone[0], both[0])
    # and it comes second in the batch as it came first
    order = jnp.asarray([2, 0, 1])
    held = np.array([page, n, L], np.int32)
    moved = np.asarray(_kernel(geometry)(
        q[order], new[order], poisoned,
        _held_table(table, held[[1, 2, 0]], page)[order], jnp.asarray(held)))
    assert np.array_equal(alone[0], moved[1])


@pytest.mark.parametrize("page,pps,lanes,dtype,why", [
    (16, 256, 640, "bfloat16", None),            # kimi-k2-instruct's pool
    (8, 16, 128, "float32", None),               # the tests' small models
    (16, 256, 576, "bfloat16", "576 lanes"),     # a row as published
    (8, 16, 128, "bfloat16", "16 sublanes"),     # half a tile a page
    (4, 16, 128, "float32", "8 sublanes"),
    (512, 8, 4096, "float32", "does not fit"),
])
def test_kept_path_decides_from_what_it_sees(page, pps, lanes, dtype, why):
    pool = jax.ShapeDtypeStruct((2, 9, page, lanes), jnp.dtype(dtype))
    got = la.kept_path(pool, pps)
    assert (got is None) if why is None else (why in got)
    assert "shard_map" in la.kept_path(pool, pps, tp=4)      # the interpreter


def test_a_pool_the_kernel_does_not_take_is_refused_here():
    """The caller asks ``kept_path`` first and keeps the gathered window
    (``mla_attention``); handed such a pool all the same, the kernel says
    why it is not for it."""
    pool = jnp.zeros((LAYERS, 7, 8, 128), jnp.bfloat16)
    q, new = jnp.zeros((2, 4, 128), jnp.bfloat16), jnp.zeros((2, 128),
                                                             jnp.bfloat16)
    assert la.kept_path(pool, 3) is not None
    with pytest.raises(ValueError, match="16 sublanes"):
        la.latent_attention(q, new, pool, LAYER, jnp.zeros((2, 3), jnp.int32),
                            jnp.zeros((2,), jnp.int32), 16, 0.2)


@pytest.mark.parametrize("page,pps,lanes,itemsize,pages", [
    (16, 256, 640, 2, 32),       # kimi-k2-instruct: 512 rows a block
    (8, 16, 128, 4, 16),         # a toy window is one block
    (8, 96, 128, 4, 64),
    (16, 256, 16384, 2, 4),      # a wide row: what the buffers hold
    (512, 8, 4096, 4, None),     # one page is more than the buffers
])
def test_latent_tiles_chooses_a_block_from_the_shapes(page, pps, lanes,
                                                      itemsize, pages):
    tiles = la.latent_tiles(page, pps, lanes, itemsize)
    if pages is None:
        assert tiles is None
        return
    assert tiles.pages == pages and tiles.rows == pages * page
    assert tiles.vmem_bytes == 2 * tiles.rows * lanes * itemsize <= 4 << 20


# -- through the decode program -------------------------------------------------

S_N, PAGE, MAX_LEN = 3, 8, 128


@functools.lru_cache(maxsize=None)
def _program():
    """The small model of tests/test_latent_moe.py, its pool filled with
    three prompts of different lengths by ``prefill``."""
    from deeplearning4j_tpu.ops.kv_cache import alloc_pools
    arch, params = arch_of(), tree_of()
    prog = latent_moe.decode_program(arch, PAGE, MAX_LEN)
    pps = prog.pages_per_slot
    pool, rest = alloc_pools(prog, 1 + S_N * pps)
    table = 1 + np.random.default_rng(0).permutation(S_N * pps) \
        .reshape(S_N, pps).astype(np.int32)
    lens = [41, 16, 5]
    prompts = np.random.default_rng(5).integers(0, 97, (S_N, 48))
    last = []
    for s, n in enumerate(lens):
        toks = jnp.asarray(prompts[s], jnp.int32)
        pool, rest, lg, _ = jax.jit(prog.prefill)(
            params, pool, rest, jnp.asarray(table[s]), toks, jnp.int32(n))
        last.append(int(jnp.argmax(lg)))
    return (prog, params, pool, rest, jnp.asarray(table),
            jnp.asarray(last, jnp.int32), jnp.asarray(lens, jnp.int32))


def _fresh(fn):
    # ``jax.jit`` of one function is cached across monkeypatched variants
    return jax.jit(lambda *a: fn(*a))


def test_the_step_gives_the_gathered_windows_tokens(monkeypatch):
    """One program, its step through the kernel and through the gathered
    window: logits to the written tolerance, tokens equal, the new rows
    written bit for bit the same; a slot that is not active changes
    nothing of the others."""
    prog, params, pool, rest, table, toks, lens = _program()
    active = jnp.asarray([True, True, True])
    assert la.kept_path(pool, prog.pages_per_slot) is None
    p1, _, lg, _ = _fresh(prog.step)(params, pool, rest, table, toks, lens,
                                     active)
    monkeypatch.setattr(la, "kept_path", lambda *a, **k: "asked to")
    p2, _, lg_w, _ = _fresh(prog.step)(params, pool, rest, table, toks, lens,
                                       active)
    lg, lg_w = np.asarray(lg), np.asarray(lg_w)
    # the widest gap seen is 3.6e-6 on logits up to 4.9
    np.testing.assert_allclose(lg, lg_w, rtol=0, atol=2e-5)
    assert np.argmax(lg, -1).tolist() == np.argmax(lg_w, -1).tolist()
    assert np.array_equal(np.asarray(p1)[0], np.asarray(p2)[0])
    monkeypatch.undo()
    _, _, lg_2, _ = _fresh(prog.step)(
        params, pool, rest, table, toks, lens,
        jnp.asarray([True, False, True]))
    assert np.array_equal(np.asarray(lg_2)[[0, 2]], lg[[0, 2]])


def test_step_multi_is_four_steps():
    """The fused horizon's scan calls the same kernel: tokens equal and
    logits to the fusion's own rounding, greedy and sampled."""
    from deeplearning4j_tpu.ops.sampling import sample_tokens
    prog, params, pool, rest, table, toks, lens = _program()
    active = jnp.asarray([True, True, True])
    temps = jnp.asarray([0.0, 0.8, 0.0], jnp.float32)
    top_ks = jnp.asarray([0, 5, 0], jnp.int32)
    top_ps = jnp.ones((S_N,), jnp.float32)
    seeds = jnp.asarray([1, 2, 3], jnp.uint32)
    steps = jnp.asarray([0, 0, 0], jnp.int32)
    budgets = jnp.full((S_N,), 100, jnp.int32)
    _, _, m_toks, m_fin, m_lgs, _ = jax.jit(prog.step_multi)(
        params, pool, rest, table, toks, lens, active, temps, top_ks, top_ps,
        seeds, steps, budgets, jnp.int32(-1), jnp.arange(4, dtype=jnp.int32))
    step = jax.jit(prog.step)
    tok = toks
    for j in range(4):
        pool, rest, lg, _ = step(params, pool, rest, table, tok, lens + j,
                                 active)
        tok, fin = sample_tokens(lg, temps, top_ks, top_ps, seeds, steps + j)
        assert np.asarray(tok).tolist() == np.asarray(m_toks)[j].tolist()
        assert np.asarray(fin).all() and np.asarray(m_fin)[j].all()
        np.testing.assert_allclose(np.asarray(m_lgs)[j], np.asarray(lg),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("entry", ["step", "step_multi"])
def test_the_lowered_step_holds_no_gathered_window(monkeypatch, entry):
    """No ``[slots, max_len, lanes]`` array in the step's lowered text
    (nor its ``[slots, pages, page, lanes]`` gather); the kept path, asked
    for, holds both: the check sees what it looks for."""
    prog, params, pool, rest, table, toks, lens = _program()
    args = (params, pool, rest, table, toks, lens,
            jnp.asarray([True, True, True]))
    if entry == "step_multi":
        args += (jnp.zeros((S_N,)), jnp.zeros((S_N,), jnp.int32),
                 jnp.ones((S_N,)), jnp.zeros((S_N,), jnp.uint32),
                 jnp.zeros((S_N,), jnp.int32), jnp.full((S_N,), 9, jnp.int32),
                 jnp.int32(-1), jnp.arange(4, dtype=jnp.int32))
    window = f"tensor<{S_N}x{MAX_LEN}x128xf32>"
    gathered = f"tensor<{S_N}x{MAX_LEN // PAGE}x{PAGE}x128xf32>"
    text = _fresh(getattr(prog, entry)).lower(*args).as_text()
    assert window not in text and gathered not in text
    monkeypatch.setattr(la, "kept_path", lambda *a, **k: "asked to")
    text = _fresh(getattr(prog, entry)).lower(*args).as_text()
    assert window in text and gathered in text


@pytest.mark.parametrize("horizon", [1, 4])
def test_the_engine_counts_the_pages_held(horizon):
    """``kv_pages_read`` on every ``serve/decode_step`` span is the pages
    the stepped slots hold up to their new rows, each step: not ``steps x
    max_slots x pages_per_slot``."""
    from deeplearning4j_tpu.obs import trace as obs_trace
    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    from deeplearning4j_tpu.serving import DecodeEngine

    lm = ShardedTransformerLM(
        arch=arch_of(), params=tree_of(),
        mesh=build_mesh({"data": 1}, devices=jax.devices()[:1]))
    prompts = np.random.default_rng(5).integers(0, 97, (2, 48))
    eng = DecodeEngine(lm, max_slots=3, page_size=PAGE, max_len=MAX_LEN,
                       prompt_buckets=(16, 64), decode_horizon=horizon)
    eng.load()
    rec = obs_trace.enable_tracing(capacity=65536)
    try:
        assert eng._reads_held_pages is True
        futs = [eng.generate_async(prompts[0, :41], max_new_tokens=9),
                eng.generate_async(prompts[1, :16], max_new_tokens=9)]
        for f in futs:
            assert len(f.result(timeout=300).tokens) == 9
        events = rec.events()
    finally:
        obs_trace.disable_tracing()
        eng.shutdown()
    steps = [e["args"] for e in events if e["name"] == "serve/decode_step"]
    assert steps
    whole = eng.max_slots * (MAX_LEN // PAGE)
    for a in steps:
        # every stepped slot's pages up to its new row, each of the steps
        assert (a["tokens"] * a["pages_filled"] <= a["kv_pages_read"]
                <= a["tokens"] * (a["pages_filled"] + a["n_active"]))
        assert a["kv_pages_read"] < a["tokens"] * whole / 2
    # prompts of 41 and 16 tokens: their first new rows lie in pages 6 and 3
    first = steps[0]
    assert (first["n_active"], first["pages_filled"]) == (2, 8)
    assert first["kv_pages_read"] == {1: 6 + 3, 4: 4 * (6 + 3)}[horizon]
