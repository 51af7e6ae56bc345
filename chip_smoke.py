#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a
user calls, at the full width of the GPT-2-small-class TransformerLM
(vocab 50304, 12 layers, d=768, 12 heads, T=1024, B=8 per device, bf16
compute, Adam with bf16 moments — bench.py's "transformer_lm"):

  train   a few ``ShardedTransformerLM.fit_batch`` steps on a repeated
          seeded batch over ``build_mesh({"data": n_devices})`` with the
          class-default attention; loss finite at every step and lower at
          the last than the first; the Pallas kernel is found in the
          LOWERED step, not read off a constructor argument
  serve   a ``DecodeEngine`` over an f32 LM of the same width holding the
          trained parameters: ``load()``, concurrent greedy and seeded
          requests across two prompt buckets, one ``POST /generate``
          through ``UIServer``; every request returns its tokens, echoed
          logits agree with the ``reencode`` reference, no compile at
          serve time, no page left in use, no stranded future

and checks that ``block_until_ready`` is a barrier here.  Exits non-zero
on any exception or failed check, and — without printing a result — when
JAX finds no TPU.  The last two stdout lines are JSON objects: first the
report (``{"report": {...}}`` — versions, compile-cache directory and
hits, the barrier answer, per-phase facts and wall times with compile and
run apart, the checks that failed), then the verdict, with exactly these
keys and the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse-cpu`` runs the same script at a tiny size on CPU, to debug
the script itself; it is never inferred from finding no chip, and its
verdict line says ``"ok": false`` on platform ``"cpu"`` (the report's
``"rehearsal_ok"`` carries its outcome) so it cannot be mistaken for a
chip pass.  Weights and tokens come from seeds;
nothing is read that git would not commit, and there are no child
processes: a TPU belongs to one process at a time.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import re
import statistics
import sys
import time
import traceback
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np

FULL = dict(vocab=50304, layers=12, d_model=768, heads=12, seq=1024,
            batch_per_device=8, steps=6, serve_max_len=256,
            buckets=(32, 128), slots=8, max_new=12,
            prompt_lens=(5, 20, 31, 40, 64, 100, 127, 12, 90))
TINY = dict(vocab=512, layers=2, d_model=64, heads=4, seq=128,
            batch_per_device=2, steps=6, serve_max_len=64,
            buckets=(8, 32), slots=4, max_new=6,
            prompt_lens=(3, 7, 8, 12, 20, 31, 5, 25, 16))

FAILED: list = []


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, what: str) -> bool:
    say(("ok   " if ok else "FAIL ") + what)
    if not ok:
        FAILED.append(what)
    return bool(ok)


def barrier_check() -> dict:
    """Is ``block_until_ready`` a barrier?  After it returns, reading a
    value back must cost ~nothing next to the compute it waited for."""
    n, chain = (4096, 64) if jax.default_backend() == "tpu" else (1024, 8)
    w = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def f(x, w):
        return jax.lax.fori_loop(0, chain,
                                 lambda _, y: (y @ w).astype(y.dtype), x)

    x = jnp.ones((n, n), jnp.bfloat16)
    float(f(x, w)[0, 0])                       # compile + warm
    t0 = time.perf_counter()
    y = jax.block_until_ready(f(x, w))
    t1 = time.perf_counter()
    float(y[0, 0])
    t2 = time.perf_counter()
    compute, readback = t1 - t0, t2 - t1
    out = {"compute_s": round(compute, 6),
           "readback_after_s": round(readback, 6),
           "is_barrier": readback < 0.2 * compute}
    if jax.default_backend() == "tpu":     # a rate is a chip number only
        out["bf16_matmul_tflops"] = round(
            2.0 * n ** 3 * chain / compute / 1e12, 1)
    check(out["is_barrier"], f"block_until_ready is a barrier: {out}")
    return out


def count_primitive(jaxpr, name: str) -> int:
    """Equations of primitive ``name`` in a jaxpr, sub-jaxprs included."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == name
        for v in eqn.params.values():
            for item in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(item, "jaxpr", item)
                if hasattr(sub, "eqns"):
                    total += count_primitive(sub, name)
    return total


def custom_call_shapes(hlo_text: str) -> list:
    """Result shapes of the Mosaic custom calls in a compiled HLO text."""
    return sorted(set(re.findall(
        r"= \(?([a-z0-9]+\[[0-9,]*\])[^\n]*custom_call_target=\"tpu_custom_call\"",
        hlo_text)))


def train_phase(cfg, devices, on_tpu) -> tuple:
    from jax.sharding import set_mesh

    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh

    n_dev = len(devices)
    mesh = build_mesh({"data": n_dev})
    lm = ShardedTransformerLM(
        vocab_size=cfg["vocab"], n_layers=cfg["layers"],
        d_model=cfg["d_model"], n_heads=cfg["heads"], mesh=mesh,
        max_len=cfg["seq"], n_microbatches=1, compute_dtype=jnp.bfloat16,
        updater=Adam(lr=3e-4, moment_dtype="bfloat16"))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(lm.params))
    B, T = cfg["batch_per_device"] * n_dev, cfg["seq"]
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab"], (B, T)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1)
    say(f"train: {n_params / 1e6:.1f}M params, batch {B}x{T}, "
        f"mesh {dict(mesh.shape)}, attention_impl={lm.attention_impl!r}")

    losses, times = [], []
    for i in range(cfg["steps"]):
        t0 = time.perf_counter()
        loss = float(lm.fit_batch(toks, tgts))      # float() = device sync
        times.append(time.perf_counter() - t0)
        losses.append(loss)
        say(f"train: step {i} loss {loss:.4f} ({times[-1]:.3f}s)")
    check(all(np.isfinite(losses)), "train: loss finite at every step")
    check(losses[-1] < losses[0],
          f"train: loss fell {losses[0]:.4f} -> {losses[-1]:.4f}")
    steady = statistics.median(times[1:])

    # the Pallas kernel must be IN the step: read the lowered program
    args = (lm.params, lm.opt_state, jnp.asarray(lm.iteration, jnp.int32),
            jax.device_put(toks, lm.token_sharding),
            jax.device_put(tgts, lm.token_sharding))
    with set_mesh(lm.mesh):
        traced = lm._jit_step.trace(*args)
        lowered = traced.lower()
    n_pallas = count_primitive(traced.jaxpr.jaxpr, "pallas_call")
    n_mosaic = lowered.as_text().count("tpu_custom_call")
    check(n_pallas >= 3 * cfg["layers"],
          f"train: {n_pallas} pallas_call(s) traced into the step")
    if on_tpu:
        check(n_mosaic >= 3 * cfg["layers"],
              f"train: {n_mosaic} Mosaic custom call(s) in the lowered step")

    out = {"params_m": round(n_params / 1e6, 1), "batch": B, "seq": T,
           "steps": len(losses), "losses": [round(x, 4) for x in losses],
           "first_step_s": round(times[0], 3),
           "steady_step_s": round(steady, 4),
           "compile_s": round(times[0] - steady, 3),
           "pallas_calls": n_pallas, "mosaic_custom_calls": n_mosaic}
    if on_tpu:                             # a rate is a chip number only
        out["tokens_per_s"] = round(B * T / steady, 1)
    if n_dev > 1:
        # parameters and batch really live on every device, the step
        # all-reduces its gradients, and each chip attends to ITS batch
        # shard (an opaque custom call GSPMD replicated would all-gather
        # the batch and compute every chip's attention on every chip)
        with set_mesh(lm.mesh):
            hlo = lowered.compile().as_text()
        emb = lm.params["embed"]
        check(len(emb.addressable_shards) == n_dev
              and len(args[3].addressable_shards) == n_dev,
              f"train: params and batch placed on {n_dev} devices")
        check(args[3].addressable_shards[0].data.shape[0] == B // n_dev,
              "train: batch is sharded, not replicated")
        check("all-reduce" in hlo, "train: compiled step has an all-reduce")
        out["all_reduces"] = hlo.count(" all-reduce(") + hlo.count(
            " all-reduce-start(")
        out["all_gathers"] = hlo.count(" all-gather(") + hlo.count(
            " all-gather-start(")
        if on_tpu:
            shapes = custom_call_shapes(hlo)
            out["custom_call_shapes"] = shapes
            bh_local = (B // n_dev) * cfg["heads"]
            bh_global = B * cfg["heads"]
            check(any(f"[{bh_local}," in s for s in shapes)
                  and not any(f"[{bh_global}," in s for s in shapes),
                  f"train: flash kernels run on the per-chip batch shard "
                  f"(B*H={bh_local}, not {bh_global}): {shapes}")
    return lm, out


def serve_phase(cfg, lm, devices, on_tpu) -> dict:
    from deeplearning4j_tpu.parallel import ShardedTransformerLM
    from deeplearning4j_tpu.serving import DecodeEngine
    from deeplearning4j_tpu.ui.server import UIServer

    n_dev = len(devices)
    # decode_program serves the f32 parameter path: an LM of the same width
    # without compute_dtype, holding the parameters just trained
    serve_lm = ShardedTransformerLM(
        vocab_size=cfg["vocab"], n_layers=cfg["layers"],
        d_model=cfg["d_model"], n_heads=cfg["heads"], mesh=lm.mesh,
        max_len=cfg["seq"])
    serve_lm.params, serve_lm.opt_state = lm.params, None
    lm.opt_state = None                         # free the optimizer state

    eng = DecodeEngine(serve_lm, max_slots=cfg["slots"], page_size=16,
                       max_len=cfg["serve_max_len"],
                       prompt_buckets=cfg["buckets"],
                       default_max_new=cfg["max_new"])
    srv = None
    out, futs = {}, []
    try:
        t0 = time.perf_counter()
        eng.load()
        out["load_s"] = round(time.perf_counter() - t0, 3)
        n_exec = eng.compile_cache_size()
        snap = eng.metrics_snapshot()
        say(f"serve: load {out['load_s']}s, {n_exec} executables, tp="
            f"{snap['tp']}, bundle hits/misses "
            f"{snap['counters'].get('bundle_hits', 0)}/"
            f"{snap['counters'].get('bundle_misses', 0)} (no bundle used)")
        if n_dev > 1:
            kp = eng._cache[0]
            check(snap["tp"] == n_dev and
                  len(jax.tree_util.tree_leaves(kp)[0].addressable_shards)
                  == n_dev, f"serve: TP={n_dev} decode, KV pool on "
                            f"{n_dev} devices")

        rng = np.random.default_rng(1)
        t0 = time.perf_counter()
        for i, n in enumerate(cfg["prompt_lens"]):
            prompt = rng.integers(0, cfg["vocab"], n)
            kw = ({} if i % 2 == 0 else
                  {"temperature": 0.8, "top_k": 40, "seed": 100 + i})
            futs.append((n, kw, eng.generate_async(
                prompt, max_new_tokens=cfg["max_new"], **kw)))
        results = [(n, kw, f.result(timeout=600)) for n, kw, f in futs]
        out["serve_s"] = round(time.perf_counter() - t0, 3)
        good = all(len(r.tokens) == cfg["max_new"]
                   and all(0 <= t < cfg["vocab"] for t in r.tokens)
                   for _, _, r in results)
        buckets_hit = {min(b for b in cfg["buckets"] if b >= n)
                       for n, _, _ in results}
        check(good and len(results) >= 8 and len(buckets_hit) >= 2,
              f"serve: {len(results)} concurrent requests (greedy + seeded) "
              f"over buckets {sorted(buckets_hit)} returned "
              f"{cfg['max_new']} tokens each in {out['serve_s']}s")
        out["requests"] = len(results)
        out["ttft_ms_median"] = round(statistics.median(
            r.ttft_ms for _, _, r in results), 2)
        out["tpot_ms_median"] = round(statistics.median(
            r.tpot_ms for _, _, r in results if r.tpot_ms), 3)

        # same seed, same prompt, different company -> same tokens
        p = rng.integers(0, cfg["vocab"], 9)
        kw = dict(max_new_tokens=cfg["max_new"], temperature=0.8, top_k=40,
                  seed=7)
        alone = eng.generate(p, **kw).tokens
        pair = [eng.generate_async(p, **kw), eng.generate_async(p[:4], **kw)]
        futs += [(len(p), kw, f) for f in pair]
        check(alone == pair[0].result(timeout=600).tokens
              and len(pair[1].result(timeout=600).tokens) == cfg["max_new"],
              "serve: seeded sampling is deterministic under co-batching")

        # the reference on a small input: echoed decode logits vs reencode
        prompt = [3, 1, 4, 1, 5]
        res = eng.generate(prompt, max_new_tokens=cfg["max_new"],
                           echo_logits=True)
        prog = eng.program
        seq = np.zeros((1, prog.max_len), np.int32)
        seq[0, :len(prompt)] = prompt
        seq[0, len(prompt):len(prompt) + len(res.tokens)] = res.tokens
        ref = np.asarray(jax.jit(prog.reencode)(serve_lm.params, seq))[0]
        ref = ref[len(prompt) - 1:len(prompt) - 1 + len(res.tokens)]
        diff = float(np.max(np.abs(res.logits - ref)))
        out["echo_vs_reencode_max_abs"] = diff
        out["echo_vs_reencode_bitwise"] = bool(np.array_equal(res.logits, ref))
        say(f"serve: echo logits vs reencode bitwise: "
            f"{out['echo_vs_reencode_bitwise']} (printed, not gated)")
        check(res.logits.shape == (cfg["max_new"], cfg["vocab"])
              and bool(np.all(np.isfinite(res.logits))) and diff < 1e-2,
              f"serve: echo logits finite, [{cfg['max_new']}, vocab], within "
              f"1e-2 of the reencode reference (max abs diff {diff:.3g})")
        check(res.tokens == [int(np.argmax(row)) for row in res.logits],
              "serve: greedy tokens are the argmax of the echoed logits")

        srv = UIServer(port=0).attach_decode_engine(eng).start()
        body = json.dumps({"prompt_ids": [1, 2, 3], "max_tokens": 4,
                           "seed": 1}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
        with opener.open(req, timeout=600) as r:
            code, reply = r.status, json.loads(r.read())
        check(code == 200 and len(reply["tokens"]) == 4,
              f"serve: POST /generate -> {code} {reply.get('tokens')}")

        check(eng.compile_cache_size() == n_exec,
              f"serve: zero serve-time compiles ({n_exec} executables "
              "after load and after serving)")
        snap = eng.metrics_snapshot()
        check(snap["pages_in_use"] == 0 and snap["active_slots"] == 0,
              "serve: pages_in_use back to 0 at idle")
        out.update(executables=n_exec, pages_in_use=snap["pages_in_use"],
                   serve_time_compiles=eng.compile_cache_size() - n_exec)
        if on_tpu:
            out["bytes_in_use_per_device"] = [
                int(d.memory_stats()["bytes_in_use"]) for d in devices]
            check(all(b > 0 for b in out["bytes_in_use_per_device"]),
                  f"memory in use on every device: "
                  f"{out['bytes_in_use_per_device']}")
    finally:
        if srv is not None:
            srv.stop()
        eng.shutdown()
    stranded = [f for _, _, f in futs if not f.done()]
    check(not stranded, "serve: shutdown left no stranded future")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run at a tiny size on CPU to debug this script; "
                    "never a chip pass")
    args = ap.parse_args()

    if args.rehearse_cpu:
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    dev = devices[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(f"chip_smoke: no TPU — jax.devices()[0].platform is "
              f"{dev.platform!r}; refusing to run (--rehearse-cpu debugs "
              "the script on CPU and is never a chip pass)", file=sys.stderr)
        return 2

    from deeplearning4j_tpu.serving.warmcache import enable_compile_cache

    def version(pkg):
        try:
            return importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            return None

    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    versions = {"jax": jax.__version__, "jaxlib": version("jaxlib"),
                "libtpu": version("libtpu"),
                "python": sys.version.split()[0]}
    cache_dir = enable_compile_cache()
    say(f"device {device}")
    say(f"versions {versions}")
    say(f"compile cache {cache_dir}")

    cfg = FULL if on_tpu else TINY
    t_start = time.perf_counter()
    report = {"versions": versions, "compile_cache_dir": cache_dir}
    if args.rehearse_cpu:
        report["rehearsal"] = "cpu, tiny size — not a chip pass"
    try:
        report["barrier"] = barrier_check()
        lm, report["train"] = train_phase(cfg, devices, on_tpu)
        report["serve"] = serve_phase(cfg, lm, devices, on_tpu)
    except Exception:
        traceback.print_exc()
        FAILED.append("exception (traceback above)")
    report["compile_cache"] = dict(cache)
    report["wall_s"] = round(time.perf_counter() - t_start, 2)
    report["failed"] = list(FAILED)
    passed = not FAILED
    if args.rehearse_cpu:
        report["rehearsal_ok"] = passed
    say(f"compile cache hits/misses {cache['hits']}/{cache['misses']}, "
        f"wall {report['wall_s']}s, "
        + ("PASS" if passed else f"FAILED: {FAILED}"))
    print(json.dumps({"report": report}), flush=True)
    # the verdict: exactly these keys; a rehearsal is never "ok"
    print(json.dumps({"ok": passed and on_tpu, "device": device}),
          flush=True)
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())
