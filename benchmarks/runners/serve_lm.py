"""Runner ``serve_lm``: a GPT-2-class LM served by ``DecodeEngine``
(``load()``, ``generate_async``) on one chip, under a mix of requests.

The load comes from one thread of this process: a closed loop keeps
``clients`` requests in flight, each client sending its next request
when the last one returns.  Only what a client of ``generate_async`` can
see is counted: the tokens of the answers that came back.  Every request
carries the window's close as its ``deadline``, so an answer still being
decoded at the close comes back with the tokens it has (finish reason
``deadline``) and those count too: the rate is over all the work and all
the time of the window, not over whole answers only.  A request still
queued at the close fails fast (``DeadlineExceededError``); it did no
work and is neither counted nor a failure.  Greedy requests ask the
engine to echo the logits each token was taken from (one read-back of
the step's logits, no other program).

``correct`` takes a sample, drawn from the seed, of the requests the
window finished (the longest among them), runs the plain reference once
over each prompt with its served tokens, and reads the widest gap by
which a served token's logit lies below the reference's best (greedy
requests) or below the reference's k-th best (requests sampled with
top-k), and the mean squared difference between the logits the engine
echoed and the reference's at the same positions (greedy requests).  A
window that finishes no request is not correct.
"""

from __future__ import annotations

import functools
import gc
import time
from concurrent.futures import FIRST_COMPLETED, wait

import numpy as np

from benchmarks import traffic
from benchmarks.harness import seeded_params
from benchmarks.stats import rate

#: served positions compared per request: the mix's longest answer fits
MAX_ANSWER = 256
POLL_S = 0.25
#: after the close, how long the answers cut there may take to come back
DRAIN_S = 2.0


def sizes(cell) -> dict:
    out = {k: cell.config[k] for k in cell.reference.SIZE_KEYS}
    out.update(cell.config["program"], chips=cell.chips)
    return out


def setup(cell, split: dict) -> dict:
    split["t_enter"] = time.time()
    import jax

    from deeplearning4j_tpu.nn.updaters import Sgd
    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    from deeplearning4j_tpu.serving import DecodeEngine

    cfg, prog = cell.config, cell.config["program"]
    t0 = time.time()
    # an LM that will not train: Sgd keeps no optimizer state on the chip
    lm = ShardedTransformerLM(
        vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], n_heads=cfg["n_head"], d_ff=cfg["n_inner"],
        mesh=build_mesh({"data": cell.chips}, devices=cell.devices),
        max_len=cfg["n_positions"], updater=Sgd(lr=0.0))
    split["program_object"] = round(time.time() - t0, 3)

    t0 = time.time()
    shardings = jax.tree_util.tree_map(lambda a: a.sharding, lm.params)
    lm.params = jax.block_until_ready(seeded_params(cell, shardings))
    lm.opt_state = None
    split["weights"] = round(time.time() - t0, 3)

    t0 = time.time()
    eng = DecodeEngine(lm, max_slots=prog["max_slots"],
                       page_size=prog["page_size"], max_len=prog["max_len"],
                       prompt_buckets=prog["prompt_buckets"])
    eng.load()
    split["engine_load"] = round(time.time() - t0, 3)

    # warm the request path on every prompt bucket, greedy and sampled
    t0 = time.time()
    rng = np.random.default_rng([cell.seed, 9])
    futs = []
    for b in eng.prompt_buckets:
        n = min(b, eng.max_prompt)
        for kw in ({}, {"temperature": 0.8, "top_k": 40, "seed": 1}):
            futs.append(eng.generate_async(
                rng.integers(0, cfg["vocab_size"], n), max_new_tokens=2, **kw))
    for f in futs:
        f.result(timeout=600)
    split["warm_requests"] = round(time.time() - t0, 3)
    return {"eng": eng, "lm": lm, "executables": eng.compile_cache_size()}


def _submit(eng, tracer, req, close_at):
    with tracer.annotate("serve/request"):
        return eng.generate_async(
            req.prompt, max_new_tokens=req.max_new_tokens,
            temperature=req.temperature, top_k=req.top_k, seed=req.seed,
            echo_logits=req.greedy, deadline=close_at)


def _annotate_engine(eng, tracer) -> None:
    """A traced run puts the benchmark's annotations round the engine's
    own prefill and decode-step calls (the program's spans are not
    profiler annotations yet)."""
    for attr, name in (("_prefill_slot", "serve/prefill"),
                       ("_step_once", "serve/decode_step")):
        inner = getattr(eng, attr)

        def wrapped(*a, _inner=inner, _name=name, **kw):
            with tracer.annotate(_name):
                return _inner(*a, **kw)

        setattr(eng, attr, wrapped)


def window(cell, state, tracer, may_be_drawn=None) -> dict:
    """The measured window.  ``may_be_drawn(finished)``, where a runner
    gives it, names those of the answers back so far that its comparison
    may still draw: the echoed logits of the others are dropped as they
    come back, so the host holds a few answers' logits however long the
    window is.  Without it (a draw from the seed, which needs them all)
    every answer is kept whole."""
    from deeplearning4j_tpu.serving import DeadlineExceededError

    eng, mix = state["eng"], cell.mix
    if tracer.on:
        _annotate_engine(eng, tracer)
    stream = traffic.request_stream(mix, cell.seed, cell.config["vocab_size"])
    counter = eng.metrics.counter_value
    before = {k: counter(k) for k in
              ("decode_steps", "prefills", "tokens_out", "errors", "shed")}
    pending, finished, failed = {}, [], 0
    occupancy = []
    tokens_seen, stalled_since, longest_stall = before["tokens_out"], None, 0.0
    # what a window of 1, 2, .. times --seconds would have read: the
    # engine's count at each whole multiple inside the window
    each_s = cell.seconds / cell.windows
    tokens_at = []

    def collect(fut, req) -> int:
        """An answer that came back: 1 if it is a failure."""
        if fut.exception() is None:
            res = fut.result()
            finished.append((req, list(res.tokens), res.logits))
            if may_be_drawn is not None:
                keep = {id(f) for f in may_be_drawn(finished)}
                finished[:] = [f if id(f) in keep else (f[0], f[1], None)
                               for f in finished]
            return 0
        print(f"bench: request {req.index} failed: {fut.exception()!r}",
              flush=True)
        return 1

    t0 = time.perf_counter()
    marks = [t0 + each_s * k for k in range(1, cell.windows + 1)]
    deadline = marks[-1]
    close_at = eng.clock() + cell.seconds       # on the engine's own clock
    for _ in range(int(mix["clients"])):
        req = next(stream)
        pending[_submit(eng, tracer, req, close_at)] = req
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        stop = marks[len(tokens_at)]      # the next multiple, or the close
        done, _ = wait(list(pending), timeout=min(POLL_S, stop - now),
                       return_when=FIRST_COMPLETED)
        now = time.perf_counter()
        if now >= deadline:   # woken late: the engine may have cut or expired
            break             # requests already, which is the close's business
        for fut in done:
            failed += collect(fut, pending.pop(fut))
            nxt = next(stream)
            pending[_submit(eng, tracer, nxt, close_at)] = nxt
        if not done:          # a periodic wake-up: one at a completion would
            occupancy.append(eng.metrics.active_slots.value())  # see a freed slot
        seen = counter("tokens_out")
        if now >= stop:
            tokens_at.append(seen - before["tokens_out"])
        if seen != tokens_seen:
            tokens_seen, stalled_since = seen, None
        else:
            stalled_since = now if stalled_since is None else stalled_since
            longest_stall = max(longest_stall, now - stalled_since)
        tracer.tick(now - t0, cell.seconds)
    tracer.stop()
    # the close: answers being decoded come back cut, queued requests expire
    left, cut, queued, t_last = set(pending), len(finished), 0, deadline
    while left and now < deadline + DRAIN_S:
        done, left = wait(left, timeout=deadline + DRAIN_S - now,
                          return_when=FIRST_COMPLETED)
        now = time.perf_counter()
        for fut in done:
            if isinstance(fut.exception(), DeadlineExceededError):
                queued += 1
            else:
                failed += collect(fut, pending[fut])
                t_last = now
    cut = len(finished) - cut
    failed += len(left)                 # never came back
    window_s = t_last - t0              # to the last answer that came back

    after = {k: counter(k) for k in before}
    tokens_out = after["tokens_out"] - before["tokens_out"]
    state["finished"] = finished
    tokens = sum(len(f[1]) for f in finished)
    return {
        "attempted": len(finished) + failed, "failed": failed,
        "end_to_end": {"serve_tokens_per_s": rate(tokens, window_s)},
        "occupancy": occupancy, "window_s": window_s,
        "max_slots": eng.max_slots,
        "counters": {k: after[k] - before[k] for k in before},
        "summary": {
            "requests_completed": len(finished), "failed": failed,
            "tokens_of_completed_requests": tokens,
            "requests_per_s": rate(len(finished), window_s),
            "window_s": window_s, "answers_back_at_the_close": cut,
            "queued_at_the_close": queued,
            "unresolved_after_the_close": len(left),
            "decode_steps": after["decode_steps"] - before["decode_steps"],
            "prefills": after["prefills"] - before["prefills"],
            "tokens_out_by_engine": tokens_out,
            "tokens_out_at_window": tokens_at + [tokens_out],
            "longest_no_progress_s": longest_stall,
            "executables_before_and_after": [state["executables"],
                                             eng.compile_cache_size()],
            "mix": traffic.describe_requests(mix)}}


def release(cell, state) -> dict:
    import jax

    eng, lm = state.pop("eng"), state.pop("lm")
    eng.shutdown()
    lm.params = None
    eng._versions.clear()
    eng._cache = eng._compiled = None
    del eng, lm
    finished = state.pop("finished", [])
    state.clear()
    gc.collect()
    jax.clear_caches()
    return {"finished": finished}


# -- the comparison ---------------------------------------------------------------------

def _sample(cell, finished):
    """A sample of the finished requests drawn from the seed, the longest
    of each kind in it: (greedy, sampled)."""
    rng = np.random.default_rng([cell.seed, 7])
    n = int(cell.mix["compare_requests"])
    out = []
    for want_greedy in (True, False):
        pool = [f for f in finished if f[0].greedy == want_greedy and f[1]]
        if not pool:
            out.append([])
            continue
        longest = max(range(len(pool)),
                      key=lambda i: len(pool[i][0].prompt) + len(pool[i][1]))
        rest = [i for i in range(len(pool)) if i != longest]
        rng.shuffle(rest)
        out.append([pool[i] for i in [longest] + rest[: n - 1]])
    return out


def _gap_fns(cell):
    """Jitted readers at the served positions of one padded sequence: how
    far each row of tokens lies below the reference's best and k-th best
    logit, how far each set of logits lies from the reference's (summed
    squares over the first ``n`` positions), and what a lower precision
    gives at the same positions (its first and k-th token, its logits)."""
    import jax
    import jax.numpy as jnp

    ref, heads = cell.reference, cell.config["n_head"]

    def logits_at(params, tokens, positions, precision):
        h = ref.hidden(params, tokens[None], heads, precision)[0][positions]
        return jnp.matmul(h, params["head"]).astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames=("k",))
    def gaps(params, tokens, positions, tok_rows, others, n, k):
        lg = logits_at(params, tokens, positions, "float32")
        kth = jax.lax.top_k(lg, k)[0][:, k - 1]
        at = jax.vmap(lambda t: jnp.take_along_axis(
            lg, t[:, None], axis=1)[:, 0])(tok_rows)
        due = (jnp.arange(lg.shape[0]) < n)[None, :, None]
        squares = jnp.sum(jnp.where(due, (others - lg) ** 2, 0.0), axis=(1, 2))
        return jnp.max(lg, axis=1) - at, jnp.maximum(kth - at, 0.0), squares

    @functools.partial(jax.jit, static_argnames=("k", "precision"))
    def lower(params_low, tokens, positions, k, precision):
        lg = logits_at(params_low, tokens, positions, precision)
        top = jax.lax.top_k(lg, k)[1]
        return jnp.stack([top[:, 0], top[:, k - 1]]), lg

    return gaps, lower


def compare(cell, served, with_control: bool = False,
            control_precision: str = None) -> dict:
    import jax.numpy as jnp

    finished = served.get("finished", [])
    if not finished:
        return {"numbers": {}, "error": "the window finished no request"}
    cfg, ref = cell.config, cell.reference
    greedy, sampled = _sample(cell, finished)
    if not greedy:
        return {"numbers": {}, "error": "the window finished no greedy request"}
    if any(lg is None or len(lg) != len(t) for _, t, lg in greedy):
        return {"numbers": {}, "error": "a greedy request came back without "
                                        "the logits of each of its tokens"}
    gaps_fn, lower_fn = _gap_fns(cell)
    low = control_precision or ref.CONTROL_PRECISION
    k = max([r.top_k for r, _, _ in sampled] + [1])
    vocab = cfg["vocab_size"]
    # every sequence padded to one length: one program to compile
    longest = int(cell.mix.get("max_total_tokens") or cfg["n_positions"])
    padded_len = min(-(-longest // 128) * 128, cfg["n_positions"])
    params = seeded_params(cell)
    params_low = ref.cast_params(params, low) if with_control else None
    no_logits = jnp.zeros((1, MAX_ANSWER, vocab), jnp.float32)

    def read(req, toks, echoed):
        """Gaps and squared logit error of one request's served tokens,
        and of the control's."""
        n_p, n = len(req.prompt), len(toks)
        seq = np.concatenate([req.prompt, np.asarray(toks, np.int32)])
        padded = np.zeros(max(padded_len, len(seq)), np.int32)
        padded[: len(seq)] = seq
        pos = np.minimum(n_p - 1 + np.arange(MAX_ANSWER), len(padded) - 1)
        rows = np.zeros((1, MAX_ANSWER), np.int32)
        rows[0, :n] = toks
        others = no_logits
        if echoed is not None:
            others = np.zeros((1, MAX_ANSWER, vocab), np.float32)
            others[0, :n] = echoed
        if with_control:      # the lower precision's first and k-th token, logits
            with ref.with_precision(low):
                chosen, lg_low = lower_fn(params_low, padded, pos, k=k,
                                          precision=low)
            rows = np.concatenate([rows, np.asarray(chosen)])
            others = jnp.concatenate([jnp.asarray(others), lg_low[None]])
        with ref.with_precision("float32"):
            top1, topk, squares = (np.asarray(x) for x in gaps_fn(
                params, padded, pos, rows, others, n, k=k))
        top1, topk = top1[:, :n], topk[:, :n]
        # served tokens: both gaps; control: its first's top-1, its k-th's top-k
        return ([top1[0], topk[0], squares[0]]
                + ([top1[1], topk[2], squares[1]] if with_control else []))

    g = [read(*f) for f in greedy]
    s = [read(*f) for f in sampled]
    cat = lambda rows, i: np.concatenate([x[i] for x in rows]) if rows \
        else np.zeros(0)
    g_top1, s_topk = cat(g, 0), cat(s, 1)
    mse = lambda i: float(sum(x[i] for x in g)) / (len(g_top1) * vocab)
    numbers = {"served_logit_gap": float(g_top1.max()),
               "served_logit_mse": mse(2)}
    if len(s_topk):
        numbers["sampled_topk_gap"] = float(s_topk.max())
    out = {"numbers": numbers, "detail": {
        "greedy_requests_compared": len(greedy),
        "greedy_tokens_compared": int(len(g_top1)),
        "longest": max(len(r.prompt) + len(t) for r, t, _ in greedy + sampled),
        "greedy_tokens_off_the_reference_best": int(np.sum(g_top1 > 0)),
        "served_logit_gap_mean": float(g_top1.mean()),
        "served_logit_rms": mse(2) ** 0.5,
        "sampled_requests_compared": len(sampled),
        "sampled_tokens_compared": int(len(s_topk)),
        "sampled_tokens_outside_top_k": int(np.sum(s_topk > 0))}}
    if with_control:
        out["control"] = {"served_logit_gap": float(cat(g, 3).max()),
                          "served_logit_mse": mse(5)}
        if len(s_topk):
            out["control"]["sampled_topk_gap"] = float(cat(s, 4).max())
        out["detail"]["control_logit_gap_mean"] = float(cat(g, 3).mean())
        out["detail"]["control_logit_rms"] = mse(5) ** 0.5
    return out
