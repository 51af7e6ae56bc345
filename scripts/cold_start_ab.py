"""Cold-start A/B: cold XLA compile vs warm-from-bundle load, plus an
autoscale burst soak.

Protocol (CPU; run with ``JAX_PLATFORMS=cpu``, as bench.py's subprocess
harness does):

  1. Build an MLP (the serving test fixture shape: 12 -> 16 -> 3) and a
     COLD arm: a fresh ``Engine.load()`` that compiles every shape
     bucket from nothing.  Time it, serve a fixed request set, then
     ``save_warmup_bundle()`` — serialized AOT executables keyed by
     (tag, bucket, dtype, device fingerprint, jax version).
  2. WARM arm: a second fresh engine over the same weights,
     ``load(warm_bundle=...)`` — every executable deserializes instead
     of compiling (``bundle_misses`` must be 0).  Serve the SAME
     requests and compare bitwise.
  3. While serving mixed sizes, ``compile_cache_size()`` must stay flat
     in BOTH arms (the zero-serve-time-compiles witness).
  4. Autoscale burst soak on the warm engine: blast a seeded open-loop
     burst through a 1-replica engine with the load controller armed —
     it must scale up during the burst, scale back down after idle,
     compile NOTHING new (the birth re-warms from the shared AOT set),
     and strand no future.
  5. Persistent-compile-cache wiring check (the cache is held OFF during
     the arms, so it can't shortcut the cold arm): ``enable_compile_cache()``
     + one fresh jit compile must leave that program's entry in the
     directory in effect (JAX_COMPILATION_CACHE_DIR, else
     <checkout>/.cache/jax-compile).

Gates (consumed by bench.py ``cold_start_ab``):
  - speedup_ok:   cold load wall >= 3x warm load wall
  - bitwise_ok:   warm-arm outputs bitwise-identical to cold-arm
  - bundle_ok:    warm arm loaded with zero bundle misses
  - cache_flat_ok: compile_cache_size() unchanged across serving, both arms
  - autoscale_ok: scale-up within the burst budget, scale-down after,
                  zero new compiles, every future resolved
  - compile_cache_ok: the persistent cache directory holds the probe's entry

Last stdout line is the JSON result (the bench subprocess contract).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mlp(seed=7):
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import (
        MultiLayerNetwork, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.updaters import Sgd

    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(lr=0.05))
            .layer(Dense(n_out=16, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(12)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    return net


def _serve_fixed(engine, xs) -> list:
    futs = [engine.output_async(x, slo_ms=120_000) for x in xs]
    return [np.asarray(f.result(timeout=120)) for f in futs]


def _run_arm(engine, xs, warm_bundle=None) -> dict:
    t0 = time.perf_counter()
    engine.load(warm_bundle=warm_bundle)
    load_s = time.perf_counter() - t0
    c0 = engine.compile_cache_size()
    outs = _serve_fixed(engine, xs)
    counters = engine.metrics.snapshot()["counters"]
    return {
        "load_s": round(load_s, 4),
        "cache_after_load": c0,
        "cache_after_serve": engine.compile_cache_size(),
        "bundle_hits": counters.get("bundle_hits", 0),
        "bundle_misses": counters.get("bundle_misses", 0),
        "warmup_s": round(counters.get("warmup_seconds_total", 0.0), 4),
        "outs": outs,
    }


def _burst_soak(engine, n_requests: int, budget_s: float) -> dict:
    """Seeded burst, closed-loop on the control signal: keep the queue
    deep until the controller births a replica (bounded by ``budget_s``),
    then stop submitting, drain, and wait for the idle ticks to retire
    it.  The burst engine shares the cold/warm engines' model."""
    c0 = engine.compile_cache_size()
    engine.enable_autoscale(min_replicas=1, max_replicas=2, up_load=8.0,
                            down_load=0.5, up_ticks=2, down_ticks=6,
                            cooldown_s=0.5, interval_s=0.05)
    rng = np.random.default_rng(42)
    xs = [rng.normal(size=(1 + i % 2, 12)).astype(np.float32)
          for i in range(256)]
    t0 = time.perf_counter()
    futs = []
    i = 0
    # sustain the burst until the controller reacts — never longer than
    # the budget, never more than n_requests in flight at once
    while (engine.metrics.counter_value("scale_ups") < 1
           and time.perf_counter() - t0 < budget_s):
        if len(futs) - sum(1 for f in futs if f.done()) < n_requests:
            for _ in range(200):
                futs.append(engine.output_async(xs[i % len(xs)],
                                                slo_ms=600_000))
                i += 1
        else:
            time.sleep(0.01)
    for f in futs:
        f.result(timeout=600)
    burst_s = time.perf_counter() - t0
    ups = engine.metrics.counter_value("scale_ups")
    peak = len(engine._replicas)
    # idle: 6 down-ticks at 0.05s interval + slack for the drain/join
    deadline = time.perf_counter() + max(5.0, budget_s)
    while (engine.metrics.counter_value("scale_downs") < ups
           and time.perf_counter() < deadline):
        time.sleep(0.05)
    downs = engine.metrics.counter_value("scale_downs")
    return {
        "burst_s": round(burst_s, 4),
        "scale_ups": int(ups),
        "scale_downs": int(downs),
        "peak_replicas": peak,
        "replicas_after_idle": len(engine._replicas),
        "cache_before": c0,
        "cache_after": engine.compile_cache_size(),
        "unresolved": sum(1 for f in futs if not f.done()),
        "scaled_within_budget": bool(ups >= 1 and burst_s <= budget_s),
    }


def _compile_cache_check() -> dict:
    """Separate from the A/B arms (main() holds the cache off until here)
    so the persistent cache can't shortcut the cold arm's compiles."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from deeplearning4j_tpu.serving.warmcache import enable_compile_cache

    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()            # jax latched "off" at the arms' compiles
    d = enable_compile_cache()

    @jax.jit
    def _distinct_probe(x):
        return jnp.tanh(x) * 3.0 + 1.0

    np.asarray(_distinct_probe(jnp.arange(8.0)))
    files = [f for f in os.listdir(d) if f.startswith("jit__distinct_probe")]
    return {"dir": d, "files": len(files), "populated": bool(files)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--burst-budget-s", type=float, default=30.0)
    args = ap.parse_args()

    import jax

    from deeplearning4j_tpu.serving import Engine
    from deeplearning4j_tpu.serving.warmcache import device_fingerprint

    # the A/B's cold arm must really compile, whatever the environment's
    # JAX_COMPILATION_CACHE_DIR holds
    jax.config.update("jax_enable_compilation_cache", False)

    n_serve = 64 if args.quick else 256
    n_burst = args.requests or (2000 if args.quick else 4000)
    rng = np.random.default_rng(0)
    serve_xs = [rng.normal(size=(1 + i % 4, 12)).astype(np.float32)
                for i in range(n_serve)]
    net = _mlp()
    print(f"cold_start_ab: serve={n_serve} burst={n_burst} "
          f"platform={jax.devices()[0].platform} "
          f"fingerprint={device_fingerprint()}", file=sys.stderr)

    def fresh_engine():
        # replicas=1 keeps the warm arm compile-free: every bucket routes
        # through the deserialized lead-device executables
        return Engine(net, max_batch=16, replicas=1, slo_ms=120_000,
                      max_queue=100_000, admission="block", max_wait_ms=0.5)

    bundle_dir = tempfile.mkdtemp(prefix="dl4j_tpu_cold_start_")
    bundle = os.path.join(bundle_dir, "model.zip.warm")

    cold_eng = fresh_engine()
    cold = _run_arm(cold_eng, serve_xs)
    cold_eng.save_warmup_bundle(bundle)
    cold["bundle_bytes"] = os.path.getsize(bundle)
    cold_eng.shutdown()

    warm_eng = fresh_engine()
    warm = _run_arm(warm_eng, serve_xs, warm_bundle=bundle)

    bitwise_ok = all(np.array_equal(a, b)
                     for a, b in zip(cold.pop("outs"), warm.pop("outs")))
    speedup = (cold["load_s"] / warm["load_s"]
               if warm["load_s"] > 0 else float("inf"))

    soak = _burst_soak(warm_eng, n_burst, args.burst_budget_s)
    warm_eng.shutdown()

    cache_check = _compile_cache_check()

    result = {
        "platform": jax.devices()[0].platform,
        "quick": args.quick,
        "n_serve": n_serve,
        "n_burst": n_burst,
        "cold": cold,
        "warm": warm,
        "soak": soak,
        "compile_cache": cache_check,
        "load_speedup_warm_vs_cold": round(speedup, 2),
        "speedup_ok": speedup >= 3.0,
        "bitwise_ok": bitwise_ok,
        "bundle_ok": (warm["bundle_misses"] == 0
                      and warm["bundle_hits"] > 0),
        "cache_flat_ok": (
            cold["cache_after_serve"] == cold["cache_after_load"]
            and warm["cache_after_serve"] == warm["cache_after_load"]
            and warm["cache_after_load"] == cold["cache_after_load"]),
        "autoscale_ok": (soak["scaled_within_budget"]
                         and soak["scale_downs"] >= 1
                         and soak["replicas_after_idle"] == 1
                         and soak["cache_after"] == soak["cache_before"]
                         and soak["unresolved"] == 0),
        "compile_cache_ok": cache_check["populated"],
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
