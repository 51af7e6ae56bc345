"""Runner ``serve_latent_moe``: a latent-attention / routed-expert LM
(the chip's share of a stated deployment) served by ``DecodeEngine``
(``load()``, ``generate_async``) on one chip.

The load loop, the window and the release are ``serve_lm``'s: the same
closed loop, the same counting of what came back.  What differs:

* set-up builds ``ShardedTransformerLM`` from the configuration's file
  (``LMArch.from_config``) and hands it the seeded weights, which the
  configuration's reference makes layer by layer from each layer's own
  key, in bfloat16, on the device; nothing is ever held twice;
* the engine also returns, for every token, the experts its layers
  chose at the position the token was taken from
  (``GenerationResult.expert_picks``); the window keeps them beside the
  tokens and echoed logits;
* ``compare`` runs the reference **in blocks** (a layer's weights are
  made, applied to every compared sequence, and freed), and reads four
  numbers: the share of (position, expert layer) pairs where program
  and reference chose different experts (``router_flip_share``), and
  the three logit numbers of ``serve_lm`` over the positions WITHOUT
  such a difference, the count of those left out beside them.  A token
  whose 8th and 9th router scores lie closer than the program's
  rounding picks another expert than the reference, and its logits
  then differ by far more than rounding: that is counted, with a limit
  of its own, and not averaged into the logits' distance.
"""

from __future__ import annotations

# the program's new symbols first: a checkout without them fails here, at
# once, before anything is built
from deeplearning4j_tpu.models.arch import LMArch  # noqa: E402  isort:skip
from deeplearning4j_tpu.parallel.moe import EXPERT_STATS  # noqa: E402,F401  isort:skip

import functools
import time

import numpy as np

from benchmarks.runners import serve_lm
from benchmarks.runners.serve_lm import release  # noqa: F401  (the harness calls it)

#: served positions compared per request: the mix's longest answer fits
MAX_ANSWER = 768


def sizes(cell) -> dict:
    ref = cell.reference
    out = {k: cell.config[k] for k in ref.SIZE_KEYS if k != "rope_scaling"}
    out.update(cell.config["program"], chips=cell.chips)
    return out


def _ref_sizes(cell) -> dict:
    return {k: cell.config[k] for k in cell.reference.SIZE_KEYS}


def arch_of(cell) -> "LMArch":
    return LMArch.from_config(cell.config,
                              max_len=cell.config["program"]["max_len"],
                              param_dtype="bfloat16")


def seeded_tree(cell, dtype=None) -> dict:
    """The program's parameter tree for ``cell.seed``: each layer from
    its own key by the reference's initialiser, one jitted call per
    layer (two programs: the dense layer's and an expert layer's)."""
    import jax
    import jax.numpy as jnp

    ref, sz = cell.reference, _ref_sizes(cell)
    key = ref.seed_key(cell.seed)
    make, ends = _makers(cell, dtype or jnp.bfloat16)
    ends = ends(key)
    blocks = [make[ref.is_dense(sz, i)](ref.layer_key(key, i))
              for i in range(int(sz["num_hidden_layers"]))]
    return {"embed": ends["embed"], "blocks": blocks,
            "lnf_g": ends["lnf_g"], "head": ends["head"]}


def _makers(cell, dtype):
    """Jitted makers of one layer's weights (by kind: dense or not) and
    of the embedding, final gain and head, from a key."""
    import jax

    ref, sz = cell.reference, _ref_sizes(cell)
    make = {dense: jax.jit(functools.partial(ref.init_layer, sizes=sz,
                                             dense=dense, dtype=dtype))
            for dense in (True, False)}
    return make, jax.jit(functools.partial(ref.init_ends, sizes=sz,
                                           dtype=dtype))


def setup(cell, split: dict) -> dict:
    split["t_enter"] = time.time()
    import jax

    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    from deeplearning4j_tpu.serving import DecodeEngine

    cfg, prog = cell.config, cell.config["program"]
    t0 = time.time()
    params = jax.block_until_ready(seeded_tree(cell))
    split["weights"] = round(time.time() - t0, 3)

    t0 = time.time()
    lm = ShardedTransformerLM(
        arch=arch_of(cell), params=params,
        mesh=build_mesh({"data": cell.chips}, devices=cell.devices))
    del params
    split["program_object"] = round(time.time() - t0, 3)

    t0 = time.time()
    eng = DecodeEngine(lm, max_slots=prog["max_slots"],
                       page_size=prog["page_size"], max_len=prog["max_len"],
                       prompt_buckets=prog["prompt_buckets"],
                       prefill_chunk=prog["prefill_chunk"],
                       decode_horizon=prog.get("decode_horizon", 1))
    eng.load()
    split["engine_load"] = round(time.time() - t0, 3)

    # warm the request path: every bucket as a last chunk, a prompt of
    # several chunks, greedy and sampled
    t0 = time.time()
    rng = np.random.default_rng([cell.seed, 9])
    chunk = int(prog["prefill_chunk"])
    lengths = sorted({min(b, eng.max_prompt) for b in eng.prompt_buckets}
                     | {min(chunk + eng.prompt_buckets[0], eng.max_prompt)})
    futs = []
    for n in lengths:
        for kw in ({"echo_logits": True},
                   {"temperature": 0.8, "top_k": 40, "seed": 1}):
            futs.append(eng.generate_async(
                rng.integers(0, cfg["vocab_size"], n), max_new_tokens=2, **kw))
    for f in futs:
        f.result(timeout=900)
    split["warm_requests"] = round(time.time() - t0, 3)
    return {"eng": eng, "lm": lm, "executables": eng.compile_cache_size()}


def window(cell, state, tracer) -> dict:
    """``serve_lm.window`` with the engine's answers also kept whole:
    the loop submits its requests one after another from one thread, so
    the n-th call is the stream's n-th request."""
    eng = state["eng"]
    results = {}
    submit = eng.generate_async
    calls = iter(range(1 << 62))

    def keeping(prompt, **kw):
        n = next(calls)
        fut = submit(prompt, **kw)

        def keep(f, n=n):
            if not f.cancelled() and f.exception() is None:
                results[n] = f.result().expert_picks
        fut.add_done_callback(keep)
        return fut

    eng.generate_async = keeping
    try:
        out = serve_lm.window(cell, state, tracer)
    finally:
        del eng.generate_async
    state["finished"] = [(req, toks, lg, results.get(req.index))
                         for req, toks, lg in state["finished"]]
    c = eng.metrics.counter_value
    out["summary"]["expert_counters_of_the_process"] = {
        k: c(k) for k in EXPERT_STATS}
    return out


# -- the comparison ---------------------------------------------------------------------

def _sample(cell, finished):
    """As ``serve_lm._sample``: the longest of each kind, then a draw."""
    three = [(r, t, (lg, pk)) for r, t, lg, pk in finished]
    return [[(r, t, *rest) for r, t, rest in kind]
            for kind in serve_lm._sample(cell, three)]


def reference_pass(cell, seqs, positions, with_control=False, low=None):
    """The reference over ``seqs`` (one int32 row each, padded to one
    length) **in blocks**: per layer, the layer's weights are made from
    its key, applied to every sequence, freed.  Returns, per precision,
    the logits at ``positions`` [R, P] as [R, P, V] and the chosen
    experts there as [R, P, expert layers, k]."""
    import jax
    import jax.numpy as jnp

    ref, sz = cell.reference, _ref_sizes(cell)
    key = ref.seed_key(cell.seed)
    precisions = ["float32"] + ([low or ref.CONTROL_PRECISION]
                                if with_control else [])
    make, ends = _makers(cell, jnp.bfloat16)
    ends = ends(key)
    seqs = jnp.asarray(seqs, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)

    @functools.partial(jax.jit, static_argnames=("precision",),
                       donate_argnums=(1,))
    def apply(p, h, precision):
        def one(x):
            y, pk = ref.layer(p, x, sz, precision)
            return y, (jnp.zeros((), jnp.int32) if pk is None else pk)
        return jax.lax.map(one, h)

    @functools.partial(jax.jit, static_argnames=("precision",))
    def read(ends, h, precision):
        at = jnp.take_along_axis(h, positions[:, :, None], axis=1)
        return ref.logits(ends, at, sz, precision)

    out = {}
    with ref.with_precision("float32"):
        hs = {p: jax.jit(ref.embed)(ends, seqs) for p in precisions}
        picks = {p: [] for p in precisions}
        for i in range(int(sz["num_hidden_layers"])):
            dense = ref.is_dense(sz, i)
            p_i = make[dense](ref.layer_key(key, i))
            for prec in precisions:
                hs[prec], pk = apply(p_i, hs[prec], precision=prec)
                if not dense:
                    picks[prec].append(jnp.take_along_axis(
                        pk, positions[:, :, None], axis=1))
            del p_i
        for prec in precisions:
            lg = read(ends, hs.pop(prec), precision=prec)
            pk = (jnp.stack(picks[prec], axis=2) if picks[prec]
                  else jnp.zeros(positions.shape + (0, 1), jnp.int32))
            out[prec] = (np.asarray(lg), np.asarray(pk))
    return out


def compare(cell, served, with_control: bool = False,
            control_precision: str = None) -> dict:
    finished = served.get("finished", [])
    if not finished:
        return {"numbers": {}, "error": "the window finished no request"}
    greedy, sampled = _sample(cell, finished)
    if not greedy:
        return {"numbers": {}, "error": "the window finished no greedy request"}
    if any(lg is None or len(lg) != len(t) for _, t, lg, _ in greedy):
        return {"numbers": {}, "error": "a greedy request came back without "
                                        "the logits of each of its tokens"}
    rows = greedy + sampled
    if any(pk is None or len(pk) != len(t) for _, t, _, pk in rows):
        return {"numbers": {}, "error": "a request came back without the "
                                        "experts chosen for each of its tokens"}
    low = control_precision or cell.reference.CONTROL_PRECISION
    k = max([r.top_k for r, _, _, _ in sampled] + [1])
    longest = max(len(r.prompt) + len(t) for r, t, _, _ in rows)
    cap = int(cell.mix.get("max_total_tokens")
              or cell.config["program"]["max_len"])
    padded_len = min(-(-longest // 256) * 256, max(cap, longest))
    n_pos = min(MAX_ANSWER, max(len(t) for _, t, _, _ in rows))
    seqs = np.zeros((len(rows), padded_len), np.int32)
    positions = np.zeros((len(rows), n_pos), np.int32)
    due = np.zeros((len(rows), n_pos), bool)
    for i, (req, toks, _, _) in enumerate(rows):
        seq = np.concatenate([req.prompt, np.asarray(toks, np.int32)])
        seqs[i, : len(seq)] = seq
        n = min(len(toks), n_pos)
        positions[i, :n] = len(req.prompt) - 1 + np.arange(n)
        due[i, :n] = True
    passed = reference_pass(cell, seqs, positions, with_control, low)
    ref_lg, ref_pk = passed["float32"]

    def numbers(their_tokens, their_logits, their_picks):
        """The four numbers of one candidate (the program, or the
        control in its place) against the float32 reference: per row
        the tokens it served, the logits it echoed (greedy rows), the
        experts it chose."""
        flips = pairs = left_out = n_mse = n_all = 0
        sq = sq_all = 0.0
        g_gap, s_gap, gap_all = [], [], [0.0]
        for i, (req, _, _, _) in enumerate(rows):
            n = int(due[i].sum())
            tk = np.asarray(their_tokens[i][:n])
            differs = np.any(np.asarray(their_picks[i][:n])
                             != ref_pk[i, :n], axis=-1)     # [n, layers]
            flips += int(differs.sum())
            pairs += differs.size
            same = ~np.any(differs, axis=-1) if differs.size \
                else np.ones(n, bool)
            left_out += int((~same).sum())
            lg = ref_lg[i, :n]
            at = lg[np.arange(n), tk]
            if req.greedy:
                gap = lg.max(axis=1) - at
                g_gap.append(gap[same])
                d = np.sum((np.asarray(their_logits[i][:n], np.float32)
                            - lg) ** 2, axis=1)
                sq, sq_all = sq + float(d[same].sum()), sq_all + float(d.sum())
                n_mse, n_all = n_mse + int(same.sum()), n_all + n
            else:
                gap = np.maximum(np.sort(lg, axis=1)[:, -k] - at, 0.0)
                s_gap.append(gap[same])
            gap_all.append(float(gap.max()))
        g_gap, s_gap = np.concatenate(g_gap), (np.concatenate(s_gap)
                                               if s_gap else np.zeros(0))
        vocab = ref_lg.shape[-1]
        out = {"router_flip_share": flips / pairs if pairs else 0.0,
               "served_logit_mse": sq / (n_mse * vocab) if n_mse else None,
               "served_logit_gap": float(g_gap.max()) if len(g_gap) else None}
        if len(sampled):
            out["sampled_topk_gap"] = float(s_gap.max()) if len(s_gap) \
                else None
        return out, {"pairs_compared": pairs, "pairs_flipped": flips,
                     "positions_left_out_for_a_flip": left_out,
                     "greedy_positions_in_the_logit_numbers": n_mse,
                     "sampled_positions_in_the_gap": int(len(s_gap)),
                     # the same numbers with the flipped positions left in
                     "logit_mse_over_every_position": sq_all / (n_all * vocab),
                     "widest_gap_over_every_position": max(gap_all)}

    nums, detail = numbers([t for _, t, _, _ in rows],
                           [lg for _, _, lg, _ in rows],
                           [pk for _, _, _, pk in rows])
    out = {"numbers": nums, "detail": {
        **detail, "greedy_requests_compared": len(greedy),
        "sampled_requests_compared": len(sampled), "longest": longest,
        "served_logit_rms": (nums["served_logit_mse"] or 0.0) ** 0.5}}
    if with_control:
        low_lg, low_pk = passed[low]
        # what the control would have served: its best token (greedy
        # rows), its k-th (rows drawn with top-k)
        order = np.argsort(low_lg, axis=-1)
        toks = [order[i, :, -1] if rows[i][0].greedy else order[i, :, -k]
                for i in range(len(rows))]
        c_nums, c_detail = numbers(toks, low_lg, low_pk)
        out["control"] = c_nums
        out["detail"].update({"control_" + k_: v for k_, v in c_detail.items()})
    return out
