"""The sampler (ops/sampling.py) and the selection it finds a top-k
threshold by (ops/select.py).

The contracts held here:
  - ``select.kth_largest`` is ``jnp.sort(row)[::-1][k - 1]`` bit for bit
    (a zero's sign apart, which no float comparison sees), at the
    vocabulary sizes the cells serve: ties, ``-inf`` entries and zeros of
    both signs included, ``k`` by row
  - ``sample_tokens`` over a batch draws the tokens and ``finite`` flags
    that the sampler it replaced drew a row at a time (kept below as the
    plain reference, ``_reference_sample_token``): with no top-p row by
    the selection, with one by the reference's own sorted path, for every
    row of the batch
  - a row with a non-finite logit says so and disturbs no neighbour
  - the program sorts ONLY inside the conditional's top-p branch
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import sampling, select

GPT2_VOCAB, KEYE_VOCAB = 50257, 151936


def _reference_sample_token(lg, t, k, p, seed, step, vocab_size):
    """The sampler as it was before the selection: one row, by a sort of
    the whole vocabulary whatever the row asks for."""
    finite = jnp.all(jnp.isfinite(lg))
    greedy = jnp.argmax(lg).astype(jnp.int32)
    scaled = lg / jnp.maximum(t, 1e-6)
    srt = jnp.sort(scaled)[::-1]
    kk = jnp.clip(jnp.where(k > 0, k, vocab_size), 1, vocab_size)
    thr_k = srt[kk - 1]
    probs = jax.nn.softmax(srt)
    cum_excl = jnp.cumsum(probs) - probs
    keep = cum_excl < jnp.clip(p, 1e-6, 1.0)
    thr_p = jnp.min(jnp.where(keep, srt, jnp.inf))
    thr = jnp.maximum(thr_k, thr_p)
    masked = jnp.where(scaled >= thr, scaled, -jnp.inf)
    g = jax.random.gumbel(
        jax.random.fold_in(jax.random.PRNGKey(seed), step), lg.shape)
    sampled = jnp.argmax(masked + g).astype(jnp.int32)
    return jnp.where(t <= 0.0, greedy, sampled), finite


@functools.lru_cache(maxsize=None)
def _reference_batch(vocab):
    return jax.jit(jax.vmap(functools.partial(_reference_sample_token,
                                              vocab_size=vocab)))


_sample_tokens = jax.jit(sampling.sample_tokens)
_kth_largest = jax.jit(select.kth_largest)


# -- (a) the selection against the sort ---------------------------------------------

def _row(kind, vocab, seed=0):
    rng = np.random.default_rng([seed, vocab])
    x = (rng.standard_normal(vocab) * 4).astype(np.float32)
    if kind == "ties":
        # quarter steps: dozens of entries share every value
        x = np.round(x * 4) / 4
    elif kind == "neg_inf":
        # a masked vocabulary tail
        x[vocab - vocab // 3:] = -np.inf
    elif kind == "zeros":
        # one entry in two hundred is not a zero; the zeros' signs
        # alternate
        zero = np.where(np.arange(vocab) % 2 == 0, 0.0, -0.0)
        x = np.where(rng.random(vocab) < 0.005, x, zero)
    return x.astype(np.float32)


def _bits(x):
    """The floats' bits, a zero's sign dropped."""
    return (np.asarray(x, np.float32) + np.float32(0.0)).view(np.uint32)


@pytest.mark.parametrize("kind", ["seeded", "ties", "neg_inf", "zeros"])
@pytest.mark.parametrize("k", [1, 2, 40, 1000, "V"])
@pytest.mark.parametrize("vocab", [GPT2_VOCAB, KEYE_VOCAB])
def test_kth_largest_is_the_sorted_rows_entry(vocab, k, kind):
    k = vocab if k == "V" else k
    row = _row(kind, vocab)
    want = np.sort(row)[::-1][k - 1]
    assert want == np.asarray(jnp.sort(jnp.asarray(row))[::-1][k - 1])
    got = np.asarray(_kth_largest(row[None], np.int32(k)))[0]
    assert _bits(got) == _bits(want), (got, want)
    # what the sampler does with it: the same candidates
    assert np.array_equal(row >= got, row >= want)
    assert (row >= got).sum() >= k
    if kind == "ties" and 1 < k < vocab:
        assert (row >= got).sum() > k       # the ties at the threshold stay
    if kind == "zeros" and k == 1000:
        assert got == 0.0                   # the threshold fell among them
    if kind == "neg_inf" and k == vocab:
        assert got == -np.inf


@pytest.mark.parametrize("vocab", [GPT2_VOCAB, KEYE_VOCAB])
def test_k_is_taken_by_row_and_zero_disables(vocab):
    rows = np.stack([_row(kind, vocab, seed=1) for kind in
                     ("seeded", "ties", "neg_inf", "zeros", "seeded",
                      "ties")])
    ks = np.array([40, 0, vocab, 1000, 0, 1], np.int32)
    want = np.array([np.sort(r)[::-1][k - 1] if k else -np.inf
                     for r, k in zip(rows, ks)], np.float32)
    got = np.asarray(jax.jit(sampling._selected_threshold)(rows, ks))
    assert np.array_equal(_bits(got), _bits(want))
    by_row = np.asarray(_kth_largest(rows, np.maximum(ks, 1)))
    assert np.array_equal(_bits(by_row)[ks > 0], _bits(want)[ks > 0])
    # a Python int serves every row
    assert np.array_equal(
        _bits(_kth_largest(rows, 40)),
        _bits([np.sort(r)[::-1][39] for r in rows]))


def test_keys_and_floats_go_back_and_forth():
    x = np.array([-np.inf, -3.5, -1e-38, -0.0, 0.0, 1e-45, 2.0, np.inf],
                 np.float32)
    keys = np.asarray(select.sortable_keys(jnp.asarray(x)))
    assert np.all(np.diff(keys.astype(np.int64)) > 0) and keys.min() > 0
    back = np.asarray(select.keys_to_float(jnp.asarray(keys)))
    assert np.array_equal(back.view(np.uint32), x.view(np.uint32))


# -- (b), (c) the batch against the plain reference ------------------------------------

def _logits(vocab, rows, seed):
    rng = np.random.default_rng([seed, vocab, rows])
    lgs = (rng.standard_normal((rows, vocab)) * 3).astype(np.float32)
    lgs[:, vocab - 300:] = -np.inf          # a masked tail, never drawn
    return lgs


#: greedy (whatever filter it names), top-k as the cells send it, top-1,
#: a wide top-k, no filter
MIXED = [(0.0, 0), (0.0, 40), (0.8, 40), (0.8, 40), (1.3, 1), (0.7, 1000),
         (1.0, 0), (0.8, 0)]


def _batch(vocab, seed, step, specs=MIXED, top_p=None):
    n = len(specs)
    ts = np.array([t for t, _ in specs], np.float32)
    ks = np.array([k for _, k in specs], np.int32)
    ps = np.ones((n,), np.float32)
    for i, p in (top_p or {}).items():
        ps[i] = p
    seeds = (np.arange(n) * 7919 + seed).astype(np.uint32)
    steps = (np.arange(n) * 3 + step).astype(np.int32)
    return _logits(vocab, n, seed), ts, ks, ps, seeds, steps


@pytest.mark.parametrize("step", [0, 1, 517])
@pytest.mark.parametrize("seed", [0, 1, 2, 2**31 + 5])
@pytest.mark.parametrize("vocab", [GPT2_VOCAB, KEYE_VOCAB])
def test_a_batch_draws_the_references_tokens(vocab, seed, step):
    args = _batch(vocab, seed, step)
    assert not sampling.needs_sort(args[1], args[3])
    toks, fin = _sample_tokens(*args)
    want, want_fin = _reference_batch(vocab)(*args)
    assert np.array_equal(toks, want)
    assert np.array_equal(fin, want_fin) and not np.asarray(fin).any()
    assert np.all(np.asarray(toks) < vocab - 300)
    # greedy rows: the argmax, whatever else the row says
    assert np.array_equal(np.asarray(toks)[:2], args[0][:2].argmax(-1))


@pytest.mark.parametrize("at", [0, 2, 6])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_one_top_p_row_and_every_row_draws_the_references_token(seed, at):
    """``at`` 0 is a GREEDY row that names a top-p: it sorts nothing."""
    args = _batch(GPT2_VOCAB, seed, 11, top_p={at: 0.9})
    assert bool(sampling.needs_sort(args[1], args[3])) == (at != 0)
    toks, fin = _sample_tokens(*args)
    want, want_fin = _reference_batch(GPT2_VOCAB)(*args)
    assert np.array_equal(toks, want) and np.array_equal(fin, want_fin)


@pytest.mark.parametrize("seed", [0, 9])
def test_a_row_alone_draws_what_it_draws_in_a_batch(seed):
    args = _batch(GPT2_VOCAB, seed, 5)
    toks, fin = _sample_tokens(*args)
    one = jax.jit(sampling.sample_token)
    for i in (1, 2, 6):
        tok, f = one(*(a[i] for a in args))
        assert tok.shape == () and int(tok) == int(toks[i])
        assert bool(f) == bool(fin[i])


# -- (d) a poisoned row -----------------------------------------------------------------

@pytest.mark.parametrize("top_p", [None, {3: 0.9}], ids=["top_k", "top_p"])
@pytest.mark.parametrize("bad", [np.nan, np.inf], ids=["nan", "inf"])
def test_a_non_finite_row_says_so_and_disturbs_no_neighbour(bad, top_p):
    lgs, *spec = _batch(GPT2_VOCAB, 6, 2, top_p=top_p)
    lgs[:, GPT2_VOCAB - 300:] = -30.0       # every row finite
    clean, clean_fin = _sample_tokens(lgs, *spec)
    assert np.asarray(clean_fin).all()
    poisoned = lgs.copy()
    poisoned[2, 1234] = bad
    toks, fin = _sample_tokens(poisoned, *spec)
    others = np.arange(len(lgs)) != 2
    assert np.array_equal(np.asarray(fin), others)
    assert np.array_equal(np.asarray(toks)[others],
                          np.asarray(clean)[others])
    want, _ = _reference_batch(GPT2_VOCAB)(poisoned, *spec)
    assert np.array_equal(np.asarray(toks)[others],
                          np.asarray(want)[others])


# -- the predicate on the host ----------------------------------------------------------

@pytest.mark.parametrize("ts,ps,want", [
    ([0.0, 0.8], [1.0, 1.0], False),
    ([0.0, 0.8], [0.9, 1.0], False),        # a greedy row's top-p
    ([0.0, 0.8], [1.0, 0.9], True),
    ([0.0, 0.0], [0.5, 0.5], False),
])
def test_needs_sort_reads_numpy_arrays_as_it_reads_traced_ones(ts, ps, want):
    ts, ps = np.array(ts, np.float32), np.array(ps, np.float32)
    assert bool(sampling.needs_sort(ts, ps)) is want
    assert bool(jax.jit(sampling.needs_sort)(ts, ps)) is want


# -- the lowering: a sort only inside the conditional -----------------------------------

def _computations(hlo_text):
    """``{name: body}`` of an HLO module's text, and its entry's name."""
    comps, entry, name = {}, None, None
    for line in hlo_text.splitlines():
        m = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{$", line)
        if m:
            name = m.group(2)
            comps[name] = []
            entry = name if m.group(1) else entry
        elif name is not None:
            comps[name].append(line)
    return {n: "\n".join(b) for n, b in comps.items()}, entry


_SORT = re.compile(r"=\s*\S+\s+sort\(")
_CALLED = re.compile(
    r"(?:to_apply|calls|body|condition|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _reached(comps, start, through_branches):
    seen, todo = set(), [start]
    while todo:
        n = todo.pop()
        if n in seen:
            continue
        seen.add(n)
        body = comps[n]
        if not through_branches:
            body = "\n".join(l for l in body.splitlines()
                             if " conditional(" not in l)
        todo += _CALLED.findall(body)
        for group in _BRANCHES.findall(body):
            todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compiler_ir(dialect="hlo").as_hlo_text()


def _spec_args(rows, vocab):
    return (jnp.zeros((rows, vocab)), jnp.zeros((rows,)),
            jnp.zeros((rows,), jnp.int32), jnp.ones((rows,)),
            jnp.zeros((rows,), jnp.uint32), jnp.zeros((rows,), jnp.int32))


def test_the_branch_without_top_p_lowers_to_no_sort():
    lgs, _, ks, *_ = _spec_args(4, GPT2_VOCAB)
    text = _hlo(sampling._selected_threshold, lgs, ks)
    assert not _SORT.search(text)
    assert " while(" in text                # the walk's passes


@pytest.mark.parametrize("fn", ["sample_tokens", "scale_and_filter"])
def test_the_sampler_sorts_only_inside_the_conditionals_branch(fn):
    args = _spec_args(4, GPT2_VOCAB)
    text = _hlo(getattr(sampling, fn),
                *(args if fn == "sample_tokens" else args[:4]))
    comps, entry = _computations(text)
    assert entry is not None and text.count(" conditional(") == 1
    sorting = {n for n, body in comps.items() if _SORT.search(body)}
    assert sorting                          # the top-p branch is there
    assert not sorting & _reached(comps, entry, through_branches=False)
    cond = next(l for l in text.splitlines() if " conditional(" in l)
    branches = [b.strip().lstrip("%")
                for b in _BRANCHES.search(cond).group(1).split(",")]
    with_sort = [bool(sorting & _reached(comps, b, through_branches=True))
                 for b in branches]
    assert with_sort == [False, True]       # index 1: the predicate held


def test_a_fused_horizon_sorts_only_inside_the_conditionals_branch():
    """``step_multi`` of the paged program: the scan's body holds the
    sampler, and its sort stays behind the conditional."""
    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      jax.devices()[:1])
    lm = ShardedTransformerLM(vocab_size=48, n_layers=1, d_model=32,
                              n_heads=2, max_len=32, mesh=mesh, seed=1)
    prog = lm.decode_program(8, 32)
    from deeplearning4j_tpu.ops.kv_cache import alloc_pools
    kp, vp = alloc_pools(prog, 1 + 2 * prog.pages_per_slot)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    text = _hlo(prog.step_multi, lm.params, kp, vp,
                i32(2, prog.pages_per_slot), i32(2), i32(2),
                jnp.ones((2,), bool), jnp.zeros((2,)), i32(2),
                jnp.ones((2,)), jnp.zeros((2,), jnp.uint32), i32(2),
                jnp.ones((2,), jnp.int32), jnp.int32(-1), i32(4))
    comps, entry = _computations(text)
    sorting = {n for n, body in comps.items() if _SORT.search(body)}
    assert sorting
    assert not sorting & _reached(comps, entry, through_branches=False)
    # of the program's conditionals (the interpreted kernels bring their
    # own) ONE leads to the sort, by its second branch
    leading = [
        [bool(sorting & _reached(comps, b.strip().lstrip("%"),
                                 through_branches=True))
         for b in _BRANCHES.search(l).group(1).split(",")]
        for l in text.splitlines() if " conditional(" in l]
    assert [w for w in leading if any(w)] == [[False, True]]
