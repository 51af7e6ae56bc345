"""Where does the non-matmul time go?  Summarize a train/step span trace.

Usage:
    python step_breakdown.py <trace.json>          # summarize a trace file
    python step_breakdown.py --demo <trace.json>   # record one first (MLP+Adam)

Reads a Chrome trace written by ``obs.trace`` (``--trace`` on any CLI,
``enable_tracing()`` anywhere else) and breaks one training run's
``train/step`` time into its instrumented phases — the measurement the
MFU-gap kernel work (ROADMAP item 2) ranks its levers by:

    train/h2d           host→device batch staging      → input-pipeline lever
    train/dispatch      the fused XLA program dispatch  → everything on-device
                        (fwd+bwd+grad-exchange+optimizer update) plus dispatch
                        overhead; the per-phase device split needs the XLA
                        profiler, but the HOST-visible residual below bounds it
    train/device_sync   blocking loss readbacks         → sync-discipline lever
    train/update        standalone optimizer-update dispatch (the fused-update
                        A/B harness, ops/update_kernel.jit_apply) → optimizer
                        lever
    input/data_wait     consumer-side input stalls      → input-pipeline lever
    step residual       train/step minus its children   → host-side Python/
                        framework overhead between phases

Prints one JSON line: per-span totals/shares plus a ``levers`` ranking.
The ranking is what ISSUE-12 uses to order the kernel offensive: a lever
whose span share is already ~0 is not worth a kernel.

``--decode`` switches to the serving-side breakdown (docs/SERVING.md
"Host-overhead elimination"): it records a decode trace at each fusion
horizon H in {1, 2, 4, 8} on a tiny model and splits PER-TOKEN time
into the four buckets the fused-step work amortizes:

    device_step    step_ms arg of serve/decode_step / tokens — the
                   decode executable itself (H steps fused for H > 1)
    sampling       sample_ms arg / tokens — the separate sampling
                   dispatch (0 for fused: sampling runs in-program)
    host_dispatch  span dur minus step_ms+sample_ms, / tokens — the
                   host work inside the span round the dispatches: the
                   input arrays (serve/step_build) and the
                   _record_token replay (serve/step_record)
    bookkeeping    gap to the previous decode_step span / tokens — the
                   host Python between steps (the loop's own checks,
                   admission, prefill)

and prints the amortization ratio (per-token total at H=1 over H) for
each horizon — the measured host-overhead elimination.
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from deeplearning4j_tpu.obs import trace as obs_trace  # noqa: E402

#: span name -> the ROADMAP-item-2 lever it measures
LEVERS = {
    "train/h2d": "input_pipeline",
    "input/data_wait": "input_pipeline",
    "train/device_sync": "sync_discipline",
    "train/update": "optimizer_update",
    "train/dispatch": "device_program",
}


def _record_demo(path: str, steps: int = 30) -> None:
    """Record a small but real trace: MLP+Adam fit_batch steps plus the
    standalone optimizer-update dispatch (the train/update span)."""
    import numpy as np
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import (MultiLayerNetwork,
                                                  NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.ops import update_kernel

    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 784)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, 256)]
    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Adam(lr=1e-3))
            .layer(Dense(n_out=512, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    ds = DataSet(x, y)
    net.fit_batch(ds)          # compile outside the trace
    obs_trace.enable_tracing(path=path)
    for _ in range(steps):
        net.fit_batch(ds)
    # the standalone updater dispatch (train/update): same params/grads
    # shapes as the model; grads = params (content is irrelevant for timing)
    upd = Adam(lr=1e-3)
    params = net.params
    state = upd.init_state(params)
    run = update_kernel.jit_apply(upd)
    it = jnp.asarray(0.0, jnp.float32)
    p, s = run(params, params, state, it)    # compile
    for _ in range(steps):
        p, s = run(p, p, s, it)
    obs_trace.flush(path)
    obs_trace.disable_tracing()


DECODE_HORIZONS = (1, 2, 4, 8)


def _record_decode_demo(path: str, horizon: int, steps: int = 48) -> None:
    """Record a real decode trace: one tiny engine at fusion horizon
    ``horizon`` (1 = the plain step loop) generating ``steps`` tokens
    batch-1 — the workload whose host overhead the fused step targets."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.parallel.mesh import build_mesh
    from deeplearning4j_tpu.parallel.transformer import ShardedTransformerLM
    from deeplearning4j_tpu.serving import DecodeEngine

    mesh = build_mesh({"data": 1, "model": 1, "seq": 1, "pipe": 1},
                      devices=jax.devices()[:1])
    lm = ShardedTransformerLM(vocab_size=64, n_layers=2, d_model=64,
                              n_heads=4, max_len=128, mesh=mesh, seed=7)
    eng = DecodeEngine(lm, max_slots=4, page_size=8, default_max_new=steps,
                       max_queue=100, admission="block",
                       prompt_buckets=(16,),
                       decode_horizon=horizon).load()
    prompt = np.arange(1, 12, dtype=np.int32)
    eng.generate(prompt, max_new_tokens=steps)     # absorb first-dispatch
    obs_trace.enable_tracing(path=path)
    eng.generate(prompt, max_new_tokens=steps)
    obs_trace.flush(path)
    obs_trace.disable_tracing()
    eng.shutdown()


def summarize_decode(trace_path: str) -> dict:
    """Per-token {device_step, sampling, host_dispatch, bookkeeping}
    split of the ``serve/decode_step`` spans in one decode trace
    (module docstring)."""
    with open(trace_path) as f:
        obj = json.load(f)
    evs = sorted((e for e in obj.get("traceEvents", [])
                  if e.get("ph") == "X"
                  and e.get("name") == "serve/decode_step"),
                 key=lambda e: e["ts"])
    if not evs:
        return {"trace": os.path.basename(trace_path), "dispatches": 0}
    tokens = dev = smp = disp = book = 0
    for prev, e in zip([None] + evs[:-1], evs):
        a = e.get("args", {})
        n = int(a.get("tokens", 1))
        tokens += n
        dur = e.get("dur", 0.0) / 1e3
        dev += float(a.get("step_ms", 0.0))
        smp += float(a.get("sample_ms", 0.0))
        disp += max(0.0, dur - float(a.get("step_ms", 0.0))
                    - float(a.get("sample_ms", 0.0)))
        if prev is not None:
            book += max(0.0, (e["ts"] - (prev["ts"] + prev.get("dur", 0.0)))
                        / 1e3)
    per = {
        "device_step_ms": round(dev / tokens, 4),
        "sampling_ms": round(smp / tokens, 4),
        "host_dispatch_ms": round(disp / tokens, 4),
        "bookkeeping_ms": round(book / tokens, 4),
    }
    per["total_ms"] = round(sum(per.values()), 4)
    host = per["sampling_ms"] + per["host_dispatch_ms"] + per["bookkeeping_ms"]
    return {"trace": os.path.basename(trace_path),
            "dispatches": len(evs), "tokens": tokens,
            "tokens_per_dispatch": round(tokens / len(evs), 3),
            "per_token": per,
            "host_share": round(host / max(per["total_ms"], 1e-9), 4)}


def decode_breakdown(path: str) -> dict:
    """Record + summarize one trace per fusion horizon; the
    ``amortization`` ratios are H=1's per-token total over each H's."""
    runs = {}
    for h in DECODE_HORIZONS:
        p = f"{path}.h{h}.json"
        _record_decode_demo(p, h)
        runs[str(h)] = summarize_decode(p)
    base = runs["1"]["per_token"]["total_ms"]
    return {
        "mode": "decode", "horizons": list(DECODE_HORIZONS),
        "runs": runs,
        "amortization": {
            h: round(base / max(r["per_token"]["total_ms"], 1e-9), 4)
            for h, r in runs.items()},
    }


def summarize(trace_path: str) -> dict:
    with open(trace_path) as f:
        obj = json.load(f)
    spans = [e for e in obj.get("traceEvents", []) if e.get("ph") == "X"]
    by_name = {}
    for e in spans:
        d = by_name.setdefault(e["name"], [])
        d.append(e.get("dur", 0.0) / 1e3)     # us -> ms
    stats = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        stats[name] = {
            "count": len(durs),
            "total_ms": round(sum(durs), 3),
            "mean_ms": round(sum(durs) / len(durs), 4),
            "p50_ms": round(durs[len(durs) // 2], 4),
            "p95_ms": round(durs[int(len(durs) * 0.95)], 4),
        }
    step_total = stats.get("train/step", {}).get("total_ms", 0.0)
    # children of train/step per the documented taxonomy; the residual is
    # host-side framework time between the instrumented phases
    child_total = sum(stats.get(n, {}).get("total_ms", 0.0)
                      for n in ("train/h2d", "train/dispatch"))
    levers = {}
    for name, lever in LEVERS.items():
        t = stats.get(name, {}).get("total_ms", 0.0)
        if t:
            levers[lever] = round(levers.get(lever, 0.0) + t, 3)
    if step_total:
        levers["host_residual"] = round(max(0.0, step_total - child_total), 3)
        for k in list(levers):
            levers[k + "_share"] = round(levers[k] / step_total, 4)
    ranked = sorted((k for k in levers if not k.endswith("_share")),
                    key=lambda k: -levers[k])
    return {"trace": os.path.basename(trace_path),
            "train_step_total_ms": step_total,
            "spans": stats, "levers": levers, "ranked_levers": ranked}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("trace", help="Chrome trace JSON (obs.trace export)")
    ap.add_argument("--demo", action="store_true",
                    help="record a small MLP+Adam trace at TRACE first")
    ap.add_argument("--decode", action="store_true",
                    help="decode mode: record one tiny-engine trace per "
                    "fusion horizon H in {1,2,4,8} at TRACE.h<H>.json and "
                    "print the per-token host/device split + amortization")
    args = ap.parse_args()
    if args.decode:
        import jax  # noqa: F401  (imported late: --help must not need jax)
        print(json.dumps(decode_breakdown(args.trace)), flush=True)
    else:
        if args.demo:
            import jax  # noqa: F401
            _record_demo(args.trace)
        print(json.dumps(summarize(args.trace)), flush=True)
