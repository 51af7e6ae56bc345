"""The expert layers' grouped feed-forward (ops/grouped_ffn.py, one Mosaic
call a layer; the Pallas interpreter here) against the three
``jax.lax.ragged_dot``s it replaced AND against a plain loop over the
experts, at reduced widths with the five expert cells' group structure;
then ``parallel/moe.moe_forward_held`` around it against the function it
replaced.

The bounds, written down.  The kernel computes gate and up as
``ragged_dot`` does (one float32 sum over ``d`` a row) and takes the
down projection's sum over ``f`` a tile at a time, so with float32
weights the two differ by float32 rounding in another order: 1e-5 of the
result's largest value.  With bfloat16 weights ``silu(gate) * up`` is
rounded to bfloat16 before the down projection, and a gate that differs
in its last float32 bit can round to the neighbouring bfloat16 value
(2**-8 of it): the bound is two such flips of the largest intermediate
through the largest down weight, on top of the float32 bound.  No cell's
``limits`` move.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import grouped_ffn as G
from deeplearning4j_tpu.ops import pallas_support
from deeplearning4j_tpu.parallel import moe
from deeplearning4j_tpu.parallel.moe import (gated_silu, init_held_experts,
                                             moe_forward_held,
                                             route_noaux_tc,
                                             route_softmax_topk)


def _draw(m, d, f, held, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    cast = lambda a: a.astype(dtype)
    return (cast(jax.random.normal(k[0], (m, d), jnp.float32)),
            cast(0.05 * jax.random.normal(k[1], (held, d, f))),
            cast(0.05 * jax.random.normal(k[2], (held, d, f))),
            cast(0.05 * jax.random.normal(k[3], (held, f, d))))


def _ragged(xs, wg, wu, wd, sizes):
    rd = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                           preferred_element_type=jnp.float32)
    return rd((jax.nn.silu(rd(xs, wg)) * rd(xs, wu)).astype(wd.dtype), wd)


def _loop(xs, wg, wu, wd, sizes):
    """Expert by expert over its own rows -> (result, the largest
    intermediate); rows past the last group are zeros."""
    out = np.zeros(xs.shape, np.float32)
    at, top = 0, 0.0
    for e, n in enumerate(np.asarray(sizes).tolist()):
        if n:
            x = xs[at:at + n]
            a = (jax.nn.silu(jnp.dot(x, wg[e],
                                     preferred_element_type=jnp.float32))
                 * jnp.dot(x, wu[e], preferred_element_type=jnp.float32)
                 ).astype(wd.dtype)
            out[at:at + n] = np.asarray(jnp.dot(
                a, wd[e], preferred_element_type=jnp.float32))
            top = max(top, float(jnp.max(jnp.abs(a.astype(jnp.float32)))))
        at += n
    return out, top


def _bound(want, top, wd):
    """The module's docstring's bound on |kernel - reference|."""
    b = 1e-5 * float(np.abs(want).max()) + 1e-7
    if wd.dtype == jnp.bfloat16:
        b += 2 * 2.0 ** -8 * top * float(jnp.max(jnp.abs(
            wd.astype(jnp.float32))))
    return b


def _spread(held, total, seed):
    """``total`` rows over ``held`` experts as routing spreads them."""
    return np.random.default_rng(seed).multinomial(
        total, np.ones(held) / held)


# name -> (M, d, f, sizes, rows a block may hold (None: the module's))
GROUPS = {
    # the cells' steps: 8 rows a group (LFM2), 7 (Granite), 4 to 8 mixed
    "8-rows-a-group": (128, 128, 256, [8] * 16, None),
    "4-7-8-rows": (96, 128, 256, [4, 7, 8, 7, 4, 8, 8, 7, 4, 4, 8, 7], None),
    "drawn-7-a-group-half-elsewhere": (240, 256, 128, _spread(16, 112, 1),
                                       None),
    # 0.3 rows a group: most experts empty (Kimi's, Keye's step)
    "most-experts-empty": (64, 128, 128,
                           [0, 1, 0, 0, 2, 0, 0, 0, 0, 0, 1, 0, 0, 0, 3, 0,
                            0, 0, 0, 1, 0, 0, 0, 0, 0, 0, 0, 0, 1, 0, 0, 0],
                           None),
    "every-pick-elsewhere": (64, 128, 128, [0] * 8, None),
    "one-expert-takes-every-row": (96, 128, 128, [0, 0, 96, 0], None),
    "first-and-last-expert-only": (64, 128, 128, [30, 0, 0, 0, 0, 34], None),
    # a group across two windows, and one across two blocks of rows
    "a-group-straddles-a-window": (96, 128, 128, [27, 10, 3, 40], None),
    "a-group-straddles-a-block": (192, 128, 128, [50, 30, 60, 20, 17], 64),
    "blocks-nobody-visits": (256, 128, 128, [5, 0, 9], 64),
    # M no multiple of the window: the rows are padded
    "M-50": (50, 128, 128, [11, 0, 20, 6], None),
    "M-77-in-blocks": (77, 128, 128, [40, 0, 30, 7], 32),
    # a prefill chunk: 30 to 140 rows a group
    "chunk-64-rows-a-group": (512, 128, 256, _spread(8, 512, 2), None),
    "chunk-140-rows-in-blocks": (1024, 128, 128, _spread(6, 840, 3), 256),
}


@pytest.fixture
def block_rows(monkeypatch):
    def set_to(rows, d):
        if rows is not None:
            monkeypatch.setattr(G, "_ROW_BLOCK_ELEMS", rows * d)
    return set_to


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_grouped_ffn_is_the_three_ragged_dots_and_the_loop_over_experts(
        case, dtype, block_rows):
    m, d, f, sizes, rows = GROUPS[case]
    block_rows(rows, d)
    held = len(sizes)
    xs, wg, wu, wd = _draw(m, d, f, held, jnp.dtype(dtype))
    sizes = jnp.asarray(sizes, jnp.int32)
    n = int(sizes.sum())
    assert G.kept_path(xs, wg) is None
    tiles = G.ffn_tiles(m, d, f, held, jnp.dtype(dtype).itemsize)
    if rows is not None:
        assert tiles.blocks > 1 and tiles.rows <= max(rows, tiles.window)
    got = np.asarray(G.grouped_ffn(xs, wg, wu, wd, sizes))
    assert got.shape == (m, d) and got.dtype == np.float32
    want, top = _loop(xs, wg, wu, wd, sizes)
    bound = _bound(want, top, wd)
    assert np.abs(got[:n] - want[:n]).max(initial=0.0) <= bound
    ragged = np.asarray(_ragged(xs, wg, wu, wd, sizes))
    assert np.abs(got[:n] - ragged[:n]).max(initial=0.0) <= bound
    # past the last group: zeros where a block was visited, unwritten
    # elsewhere, and the layer masks both
    visited = -(-n // tiles.rows) * tiles.rows if n else 0
    assert not got[n:min(visited, m)].any()


def test_a_poisoned_row_stays_in_its_own_rows():
    """A window holds other experts' rows too: theirs are selected away,
    not multiplied by zero."""
    xs, wg, wu, wd = _draw(64, 128, 128, 4, jnp.bfloat16)
    sizes = jnp.asarray([5, 9, 20, 11], jnp.int32)
    xs = xs.at[7].set(jnp.nan).at[50].set(jnp.inf)    # past the groups too
    got = np.asarray(G.grouped_ffn(xs, wg, wu, wd, sizes))
    bad = ~np.isfinite(got).all(axis=1)
    assert np.flatnonzero(bad).tolist() == [7]


def test_work_items_walk_the_hit_experts_block_by_block():
    tiles = G.FfnTiles(rows=64, blocks=3, window=32, cols=128, vmem_bytes=0)
    sizes = jnp.asarray([10, 0, 70, 0, 60, 0], jnp.int32)    # 140 of 192
    expert, block, start, end, items = map(np.asarray,
                                           G.work_items(sizes, tiles))
    assert items.tolist() == [5] and len(expert) == 3 + 6 - 1
    # expert 2 holds rows 10..79 (blocks 0, 1), expert 4 rows 80..139
    assert expert.tolist() == [0, 2, 2, 4, 4, 4, 4, 4]
    assert block.tolist() == [0, 0, 1, 1, 2, 2, 2, 2]
    assert start.tolist() == [0, 10, 10, 80, 80, 140]
    assert end.tolist() == [10, 10, 80, 80, 140, 140]
    # nothing held: no item, and indices that exist
    expert, block, _, _, items = map(np.asarray, G.work_items(
        jnp.zeros((6,), jnp.int32), tiles))
    assert items.tolist() == [0]
    assert set(expert.tolist()) <= set(range(6)) and not block.any()


@pytest.mark.parametrize("shape,why", [
    ((64, 64, 128, 4, "bfloat16"), "no multiples of 128 lanes"),
    ((64, 128, 192, 4, "bfloat16"), "no multiples of 128 lanes"),
    ((64, 128, 128, 4, "float16"), "weights of type float16"),
    ((0, 128, 128, 4, "bfloat16"), "nothing to multiply"),
])
def test_shapes_the_kernel_leaves_to_ragged_dot_and_says_so(
        shape, why, monkeypatch):
    m, d, f, held, dtype = shape
    xs, wg, wu, wd = _draw(m, d, f, held, jnp.dtype(dtype))
    assert why in G.kept_path(xs, wg)
    with pytest.raises(ValueError, match="cannot take these shapes"):
        G.grouped_ffn(xs, wg, wu, wd, jnp.zeros((held,), jnp.int32))
    said = []
    monkeypatch.setattr(pallas_support, "fell_back",
                        lambda kernel, reason: said.append((kernel, reason)))
    sizes = jnp.asarray([m] + [0] * (held - 1), jnp.int32)
    got = moe.held_experts_ffn(xs, wg, wu, wd, sizes)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_ragged(xs, wg, wu, wd, sizes)))
    assert len(said) == 1 and why in said[0][1]
    assert said[0][0] == "grouped_ffn[M=%d,d=%d,f=%d,held=%d,%s]" % shape


def test_the_kernel_says_once_a_shape_that_it_engaged(monkeypatch):
    monkeypatch.setattr(pallas_support, "_ENGAGED", set())
    said = []
    monkeypatch.setattr(pallas_support.logger, "info",
                        lambda fmt, *a: said.append(fmt % a))
    xs, wg, wu, wd = _draw(32, 128, 128, 2, jnp.bfloat16)
    sizes = jnp.asarray([3, 4], jnp.int32)
    for _ in range(3):
        moe.held_experts_ffn(xs, wg, wu, wd, sizes)
    moe.held_experts_ffn(xs[:16], wg, wu, wd, sizes)
    assert len(said) == 2
    assert "grouped_ffn[M=32,d=128,f=128,held=2,bfloat16]" in said[0]
    assert "grouped_ffn[M=16," in said[1] and "FfnTiles(" in said[1]


# -- the layer around it, against the function it replaced -------------------------

def _held_before(p, x, *, first_expert, k, scaling=1.0, valid=None,
                 shared=True, router="noaux_tc", router_eps=1e-20):
    """``moe_forward_held`` as it stood before the kernel (PR 44's tree),
    kept here as the plain reference of the layer."""
    n, d = x.shape
    held = p["e_gate"].shape[0]
    if router == "softmax_topk":
        idx, w = route_softmax_topk(x, p["router_w"], k)
    else:
        idx, w = route_noaux_tc(x, p["router_w"], p["router_b"], k, scaling,
                                router_eps)
    if valid is None:
        valid = jnp.ones((n,), bool)
    local = idx - first_expert
    on_held = (local >= 0) & (local < held) & valid[:, None]
    flat_e = jnp.where(on_held, local, held).reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    tok = order // k
    load = jnp.zeros((held + 1,), jnp.int32).at[flat_e].add(1)[:held]
    n_held = jnp.sum(load)
    in_group = jnp.arange(n * k) < n_held
    cd = p["e_gate"].dtype
    o = _ragged(x.astype(cd)[tok], p["e_gate"], p["e_up"], p["e_down"], load)
    o = jnp.where(in_group[:, None], o * w.reshape(-1)[order][:, None], 0.0)
    back = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    y = jnp.sum(o[back].reshape(n, k, d), axis=1)
    if shared and "s_gate" in p:
        y = y + gated_silu(x, p["s_gate"], p["s_up"], p["s_down"])
    stats = jnp.stack([jnp.sum(valid) * k, n_held, jnp.max(load),
                       jnp.sum(load > 0)]).astype(jnp.int32)
    return y, jnp.sort(idx, axis=-1), stats


def _layer(dtype, router="noaux_tc", n_experts=16, held=4, n_shared=1):
    return init_held_experts(jax.random.PRNGKey(3), 128, 128, n_experts,
                             held, n_shared=n_shared, std=0.05,
                             dtype=jnp.dtype(dtype), router=router)


# name -> (rows, keyword arguments, what is done to the layer's tree)
LAYER_CASES = {
    "a-quarter-of-the-experts-held": (50, dict(first_expert=4, k=3,
                                               scaling=2.827), None),
    "every-token-picks-the-same-experts": (
        50, dict(first_expert=4, k=3, scaling=2.827),
        lambda p: {**p, "router_b": jnp.zeros(16).at[
            jnp.array([4, 5, 6])].set(10.0)}),
    "rows-that-are-no-tokens": (20, dict(first_expert=4, k=3, scaling=1.0,
                                         valid=jnp.arange(20) < 7), None),
    "every-pick-elsewhere": (
        33, dict(first_expert=12, k=2, scaling=1.0),
        lambda p: {**p, "router_b": jnp.zeros(16).at[
            jnp.array([0, 1])].set(10.0)}),
    "the-share-without-the-shared-expert": (
        33, dict(first_expert=8, k=3, scaling=2.827, shared=False), None),
    "the-softmax-gate": (33, dict(first_expert=0, k=3, router="softmax_topk"),
                         None),
    "a-chunk-of-rows": (256, dict(first_expert=4, k=4, scaling=1.0,
                                  router_eps=1e-6), None),
}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_moe_forward_held_is_the_function_it_replaced(case, dtype):
    n, kw, edit = LAYER_CASES[case]
    p = _layer(dtype, kw.get("router", "noaux_tc"))
    if edit:
        p = edit(p)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, 128))
    y, picks, stats = moe_forward_held(p, x, **kw)
    want, want_picks, want_stats = _held_before(p, x, **kw)
    np.testing.assert_array_equal(np.asarray(picks), np.asarray(want_picks))
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))
    if case == "every-token-picks-the-same-experts":
        assert np.asarray(stats).tolist() == [150, 150, 50, 3]
    if case == "every-pick-elsewhere":
        assert np.asarray(stats).tolist() == [66, 0, 0, 0]
    # a token's result sums k picks: k times the kernel's own bound
    a = (jax.nn.silu(x @ p["e_gate"].astype(jnp.float32))
         * (x @ p["e_up"].astype(jnp.float32)))
    bound = kw["k"] * kw.get("scaling", 1.0) * _bound(
        np.asarray(want), float(jnp.max(jnp.abs(a))), p["e_down"])
    assert np.abs(np.asarray(y) - np.asarray(want)).max() <= bound


@pytest.mark.parametrize("router", ["noaux_tc", "softmax_topk"])
def test_the_shares_add_up_to_the_uncut_layer_through_the_kernel(router):
    """tests/test_latent_moe.py's and tests/test_linear_gqa.py's sum of
    shares at a width the kernel takes: four shares of 4 experts each,
    the shared expert once, against ALL experts applied to every row and
    combined by the gate."""
    whole = _layer("float32", router, held=16,
                   n_shared=0 if router == "softmax_topk" else 1)
    x = jax.random.normal(jax.random.PRNGKey(7), (33, 128))
    if router == "softmax_topk":
        idx, w = route_softmax_topk(x, whole["router_w"], 3)
    else:
        idx, w = route_noaux_tc(x, whole["router_w"], whole["router_b"], 3,
                                2.827)
    every = jnp.stack([gated_silu(x, whole["e_gate"][e], whole["e_up"][e],
                                  whole["e_down"][e]) for e in range(16)], 1)
    want = jnp.sum(jnp.take_along_axis(every, idx[..., None], axis=1)
                   * w[..., None], axis=1)
    if "s_gate" in whole:
        want = want + gated_silu(x, whole["s_gate"], whole["s_up"],
                                 whole["s_down"])
    total = 0.0
    for share in range(4):
        lo = 4 * share
        p = {**whole, **{k: whole[k][lo:lo + 4]
                         for k in ("e_gate", "e_up", "e_down")}}
        y, picks, _ = moe_forward_held(p, x, first_expert=lo, k=3,
                                       scaling=2.827, shared=share == 0,
                                       router=router)
        np.testing.assert_array_equal(np.asarray(picks),
                                      np.sort(np.asarray(idx), axis=-1))
        total = total + y
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
