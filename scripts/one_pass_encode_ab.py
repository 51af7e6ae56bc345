"""Interleaved A/B for the one-pass fixed-threshold encode
(ops/compression.py).

Arms (alternating windows, identical protocol):

  topk       the baseline fixed-mode pack: top_k over masked magnitudes
             (sort-backed selection)
  streaming  the sort-free one-pass pack: cumsum positions + one scatter

(A single-block pallas variant of the pack was removed: Mosaic has no
lowering for its in-kernel ``cumsum`` — CHANGES.md, PR 21.)

Workload: one DCN exchange bucket (encode + decode round-trip per
iteration, the compressed_pmean inner loop minus the collective), with
~2% of elements clearing the threshold — the sparse regime the format
targets.  Parity: the decode round-trip must be BIT-identical across
arms (entry order differs; the scatter-add never observes it).  Prints
one JSON line; --quick shrinks the bucket.
"""
import argparse
import json
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deeplearning4j_tpu.ops import compression  # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("--quick", action="store_true")
args = ap.parse_args()

QUICK = args.quick or os.environ.get("PROBE_QUICK", "0") == "1"
WARMUP, WINDOWS, PER = (3, 2, 8) if QUICK else (10, 3, 33)
N = (1 << 16) if QUICK else (1 << 20)
K = compression.default_k_max(N)
T = 1e-3

rng = np.random.default_rng(0)
g_host = rng.normal(size=N).astype(np.float32) * (T / 10)
hot = rng.choice(N, N // 50, replace=False)       # ~2% clear the threshold
g_host[hot] = rng.normal(size=hot.size).astype(np.float32) * 10 * T
g = jnp.asarray(g_host)


def make_arm(fused: bool):
    """Trace one arm's encode+decode round trip with the module flag
    set the way that arm needs it (the flag is read at trace time)."""
    compression.FUSED_ENCODE = fused

    @jax.jit
    def run(gg):
        enc, scale = compression.threshold_encode(gg, K, threshold=T)
        return compression.threshold_decode(enc, scale, N), enc
    dec, enc = run(g)   # trace NOW, while the flags are set
    return run, np.asarray(dec), np.asarray(enc)


arm_topk, dec_ref, enc_ref = make_arm(False)
arm_stream, dec_st, enc_st = make_arm(True)
ARMS = {"topk": arm_topk, "streaming": arm_stream}

parity = {
    "roundtrip_bitwise_streaming": bool(np.array_equal(dec_ref, dec_st)),
    "selection_set_equal": bool(
        set(enc_ref.tolist()) - {0} == set(enc_st.tolist()) - {0}),
}

best = {name: float("inf") for name in ARMS}
for name, fn in ARMS.items():
    for _ in range(WARMUP):
        dec, _ = fn(g)
    float(jnp.sum(dec))
for _ in range(WINDOWS):
    for name, fn in ARMS.items():        # interleaved
        t0 = time.perf_counter()
        for _ in range(PER):
            dec, _ = fn(g)
        float(jnp.sum(dec))
        best[name] = min(best[name], (time.perf_counter() - t0) / PER)

out = {"config": "one_pass_encode_ab", "n": N, "k": K,
       "topk_ms": round(best["topk"] * 1e3, 4),
       "streaming_ms": round(best["streaming"] * 1e3, 4),
       "speedup_streaming": round(best["topk"] / best["streaming"], 3),
       **parity,
       "platform": jax.devices()[0].platform, "t": round(time.time(), 1)}
print(json.dumps(out), flush=True)
