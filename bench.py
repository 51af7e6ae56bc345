"""Benchmark suite — the 5 BASELINE.md configs + TPU-first extensions.

Primary (driver) metric: ResNet-50 training images/sec on one chip,
printed as ONE JSON line on stdout (the driver's contract).  The 9-config
protocol (BASELINE.md: MLP/MNIST, LeNet/CIFAR, ResNet-50, Word2Vec +
LSTM char-RNN, sharded ResNet-50 with gradient allreduce; plus the
TPU-first flash-attention fwd+bwd, GPT-2-small TransformerLM, and
measured-collective configs) is measured post-compile as the best of
three-to-five ~20-33-step steady-state windows (see _steady_state) and
written to ``bench_results.json`` (generated, git-ignored) / echoed on
stderr, including:
  - mfu: model FLOPs utilization from XLA's compiled cost analysis vs the
    published peak of the chip's device_kind (PEAK_BF16_FLOPS; a kind
    missing from that table is an error, never a default)
  - matmul_ceiling_tfs / mfu_vs_ceiling: the sustained bf16 matmul rate
    probed in-run (probe_matmul_ceiling), and MFU against it
  - allreduce_traffic_gbps_est: per-step gradient bytes x step rate — the
    DP gradient traffic the ICI must carry (an estimate; the MEASURED
    psum/ppermute rates are bench_collective's psum_measured_gbps)
  - pipeline_1f1b_*: GPipe-vs-1F1B schedule A/B (bubble fraction, peak
    activation memory analytic+measured) on a virtual 4-device CPU mesh
    via scripts/pipeline_ab.py
  - compressed_wire_bytes_est + grad_compression_wire_ratio: the DCN-tier
    compressed gradient exchange — per-step wire bytes at the threshold
    default, and the dense/compressed A/B on a virtual 2-slice mesh via
    scripts/compression_ab.py, hard-gated at >=8x with loss parity
  - chaos_recovery_faults_recovered: the chaos-soak fault-recovery gate
    (scripts/chaos_soak.py) — a scripted >=5-kind fault schedule against
    a real ElasticTrainer loop, hard-gated on zero unrecovered failures,
    corrupt-latest checkpoint fallback, chaos-off bitwise identity, and
    loss parity with the fault-free run (docs/FAULT_TOLERANCE.md)
  - multihost_chaos_recovered: the PROCESS-scale chaos gate
    (scripts/chaos_soak.py --multiproc) — PodLauncher forks 2 workers x
    4 virtual devices sharing one checkpoint store, SIGKILLs one and
    SIGSTOPs the other mid-run; hard-gated on zero unrecovered workers,
    both proc-fault recoveries completing training, chaos-off 2-process
    bit-identity with the single-process baseline, bit-exact trajectory
    replay after resume, and zero orphan worker processes
  - preemption_recovery: the ANNOUNCED-failure gate (scripts/chaos_soak.py
    --preempt) — a scheduled preemption notice (SIGTERM) against the
    writer/coordinator worker plus a slow_worker straggler and a
    coordinator kill, hard-gated on the emergency checkpoint landing
    within the grace budget, a PREEMPTED exit relaunching WITHOUT
    consuming the restart budget, resume at exactly the preempted step
    (zero steps lost) with bit-exact trajectory replay, coordinator-kill
    recovery to completion, heartbeat-based straggler flagging, zero
    orphans, and chaos-off bit-identity with the pre-PR launcher
    configuration (docs/FAULT_TOLERANCE.md "Announced failures")
  - input_pipeline_overlap: the device-resident input-pipeline A/B gate
    (scripts/input_pipeline_ab.py) — sync host feeding vs
    DevicePrefetchIterator (async H2D ring, uint8 wire, on-device
    normalization), hard-gated on prefetched >= 1.0x sync throughput,
    bit-identical loss sequences, and a reported input-stall fraction
    (docs/INPUT_PIPELINE.md)
  - serving_throughput_rps: the production-serving A/B gate
    (scripts/serving_ab.py) — legacy fixed-poll ParallelInference vs the
    new serving.Engine on the same synthetic open-loop LeNet load,
    hard-gated on new >= 1.0x legacy throughput AND new p99 <= legacy
    at equal load, zero unwarmed serves (docs/SERVING.md)
  - serving_chaos_recovery: the serving-resilience gate
    (scripts/serving_chaos_soak.py) — replica_crash/replica_hang/
    poison_input/bad_version faults against a live 2-replica engine
    under open-loop load, hard-gated on zero stranded futures, zero
    cross-request poisoning, bounded p99 through replica loss, zero
    compiles across respawns, canary auto-rollback on exactly the
    regressed version, and chaos-off bit-identity with the pre-PR
    engine configuration (docs/SERVING.md "Failure model")
  - fleet_load_chaos: the fleet-router resilience gate
    (scripts/fleet_load_soak.py) — host_straggle/host_preempt/host_kill
    faults (the kill fired mid-rolling-swap) against a 3-host fleet
    under an open-loop diurnal+burst+heavy-tail trace, plus a clean
    rolling promote and a million-request scale arm, hard-gated on
    zero stranded futures, at-most-once delivery, zero version mixing
    after promote/rollback, bounded post-fault p99 and shed rate, and
    chaos-off bit-identity with a single-host engine
    (docs/SERVING.md "Fleet serving")
  - disagg_decode_ab: the disaggregated prefill/decode gate
    (scripts/fleet_load_soak.py --disagg) — unified vs prefill-host ->
    KV-page-handoff -> decode-host vs tensor-parallel decode arms,
    hard-gated on temp-0 bit-identity across all three, decode-host
    TPOT p99 <= 1.2x calm through a prompt burst that degrades the
    unified arm, zero serve-time compiles on the decode host, and
    exactly-once same-tokens delivery with clean page accounting
    through a prefill-host kill (docs/SERVING.md "Disaggregated and
    sharded decode")
  - train_promote_loop: the production-flywheel gate
    (scripts/train_promote_soak.py) — a PromotionPipeline drives six
    train -> eval -> register -> canary -> roll generations against a
    live 3-host fleet under open-loop traffic with chaos at every
    stage (device loss mid-train, NaN params, a regressed generation,
    a host kill mid-roll, a controller crash at the canary), hard-
    gated on three promotions with monotone eval, lineage-target
    rollback (never version-1), the eval/canary gates each catching
    their regression, crash-resume without retraining, zero dropped/
    stranded/version-mixed requests, and zero serve-time compiles
    (docs/LIFECYCLE.md)
  - multitenant_soak: the multi-tenant many-model serving gate
    (scripts/multitenant_soak.py) — 3 models x 3 tenants on a 3-host
    fleet (per-host TenantTables: weighted-fair lanes + atomic quotas;
    a PlacementController mapping (model, host) from live traffic)
    under open-loop mixed load where one tenant 10x-bursts, a host
    dies mid-burst, and the idle model is evicted then demand-
    reloaded; hard-gated on victim-tenant p99/error isolation, exact
    ledger==tables==metrics shed attribution to the bursting tenant,
    zero version/tenant mixing, nothing stranded or double-delivered,
    the placement loop actuating (widen/evict/demand-load), and zero
    serve-time compiles across every placement move
    (docs/SERVING.md "Multi-tenant serving")
  - decode_tokens_per_sec: the autoregressive-decode A/B gate
    (scripts/decode_ab.py) — static-batch full-re-encode decoding vs
    serving.DecodeEngine (paged KV-cache, bucketed prefill/decode split,
    iteration-level continuous batching) on the same open-loop prompt
    schedule; hard-gated everywhere on temperature-0 BITWISE logit
    identity with re-encode, greedy token parity, zero serve-time
    compiles, and zero stranded futures under a decode-batch crash;
    speed gates (tokens/sec >= baseline, p99 TTFT <= baseline) bind on
    TPU only (docs/SERVING.md "Autoregressive decode")
  - telemetry_overhead: the observability-layer gate
    (scripts/trace_overhead_ab.py) — span tracing OFF vs ON on
    adjacent-step pairs, hard-gated on median paired overhead <= 3%,
    tracing-off arm bit-identical losses (and a shared no-op fast
    path), the exported Chrome trace validating against the schema, and
    the documented span trees present for BOTH a training step and a
    served request (docs/OBSERVABILITY.md)

BASELINE.md: the reference publishes NO numbers; the driver target is
>=0.8x per-chip of H100+nd4j-cuda on ResNet-50 ≈ 2000 img/s.

Set BENCH_QUICK=1 for a fast smoke run (small windows, CPU-friendly).

A config that raises makes the run exit non-zero (its entry in the
results carries the error).  A TPU belongs to one process: every child
this file spawns is either pinned to CPU or (``_kernel_ab``) runs BEFORE
this process initialises a JAX backend.
"""

from __future__ import annotations

import json
import os
import sys
import time

from typing import Optional

import numpy as np

BASELINE_IMG_S = 2000.0  # 0.8 x H100 nd4j-cuda ResNet-50 (BASELINE.md target)
#: published bf16 peak per chip, keyed by ``jax.devices()[0].device_kind``
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s)
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}
QUICK = os.environ.get("BENCH_QUICK", "0") == "1"

WARMUP = 3 if QUICK else 10
STEPS = 10 if QUICK else 100


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


_REPO = os.path.dirname(os.path.abspath(__file__))


def peak_flops() -> float:
    """Published bf16 peak FLOP/s of the chip this process runs on; an
    unknown ``device_kind`` is an error, not a default."""
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"device_kind {kind!r} has no entry in bench.PEAK_BF16_FLOPS "
            f"(known: {sorted(PEAK_BF16_FLOPS)}) — add its published peak "
            "with the source before reporting an MFU on it")
    return PEAK_BF16_FLOPS[kind]


def probe_matmul_ceiling(chain: int = 24, n: int = 8192) -> float:
    """The chip's sustained bf16 matmul rate, TF/s (best of 3
    chained-matmul windows) — what a large matmul reaches in practice,
    reported beside the published peak: mfu_vs_ceiling = achieved FLOPs /
    THIS number."""
    import jax
    import jax.numpy as jnp

    if QUICK:
        chain, n = 4, 2048

    key = jax.random.PRNGKey(0)
    # w is an ARGUMENT, not a closure capture: closed-over arrays embed as
    # HLO constants, and a 128MB constant bloats the compile
    w = jax.random.normal(key, (n, n), jnp.bfloat16) * (1.0 / np.sqrt(n))

    @jax.jit
    def chained(x, w):
        def body(y, _):
            # astype: some backends emit f32 from bf16 matmuls; the carry
            # must keep its dtype for scan
            return (y @ w).astype(y.dtype), None
        y, _ = jax.lax.scan(body, x, None, length=chain)
        return y

    x = jax.random.normal(key, (n, n), jnp.bfloat16)
    chained(x, w)  # compile
    _sync(chained(x, w))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _sync(chained(x, w))
        best = min(best, time.perf_counter() - t0)
    return 2.0 * n * n * n * chain / best / 1e12


def _sync(state) -> None:
    """Wait for the device (``block_until_ready`` is a real barrier on the
    attached chip — chip_smoke.py checks it on every run)."""
    import jax

    jax.block_until_ready(state)


def _steady_state(step_fn, state, steps=STEPS, warmup=WARMUP, windows=3):
    """Post-compile steady-state timing: returns (state, sec_per_step).

    Takes the BEST of `windows` equal sub-windows (full runs only; QUICK
    keeps a single window — 5//3-step windows would just measure the sync
    round trip): the window least disturbed by host jitter.  Sub-10ms-step
    configs pass windows=5.  (ROADMAP A0 replaces best-of with median and
    quartiles.)"""
    for i in range(warmup):
        state = step_fn(state, i)
    _sync(state)
    windows = 1 if QUICK else windows
    per = max(1, steps // windows)
    best = float("inf")
    i = warmup
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(per):
            state = step_fn(state, i)
            i += 1
        _sync(state)
        best = min(best, (time.perf_counter() - t0) / per)
    return state, best


def _net_step(net, x, y):
    """Raw jitted step closure for an initialized MultiLayerNetwork/graph."""
    import jax.numpy as jnp
    import jax.random as jrandom

    if net._jit_step is None:
        net._jit_step = net._make_step()
    is_graph = isinstance(net.params, dict)
    if is_graph:
        inputs = {net.conf.network_inputs[0]: x}
        labels = {net.conf.network_outputs[0]: y}
        masks = {net.conf.network_inputs[0]: None}
        lmasks = {net.conf.network_outputs[0]: None}
    else:
        inputs, labels, masks, lmasks = x, y, None, None

    def step(state, i):
        params, st, opt = state
        params, st, opt, loss = net._jit_step(
            params, st, opt, jnp.asarray(i, jnp.int32), inputs, labels,
            jrandom.PRNGKey(i), masks, lmasks)
        return (params, st, opt)

    return step, (net.params, net.state, net.opt_state)


def _param_bytes(net) -> int:
    import jax

    return sum(l.size * l.dtype.itemsize
               for l in jax.tree_util.tree_leaves(net.params))


def _compressed_wire_bytes(net) -> int:
    """Per-step DCN wire bytes if the model's gradient crossed a 2-slice
    dcn axis threshold-compressed (ops/compression accounting)."""
    import jax

    from deeplearning4j_tpu.ops.compression import compression_stats

    n = sum(l.size for l in jax.tree_util.tree_leaves(net.params))
    return compression_stats(n, "threshold",
                             n_slices=2)["compressed_wire_bytes_per_step"]


def _flops_per_step(net, x, y):
    """XLA's own cost analysis of the compiled train step (None if the
    backend reports no flops)."""
    import jax.numpy as jnp
    import jax.random as jrandom

    is_graph = isinstance(net.params, dict)
    if is_graph:
        args = (net.params, net.state, net.opt_state, jnp.asarray(0, jnp.int32),
                {net.conf.network_inputs[0]: x},
                {net.conf.network_outputs[0]: y}, jrandom.PRNGKey(0),
                {net.conf.network_inputs[0]: None},
                {net.conf.network_outputs[0]: None})
    else:
        args = (net.params, net.state, net.opt_state, jnp.asarray(0, jnp.int32),
                x, y, jrandom.PRNGKey(0), None, None)
    ca = net._jit_step.lower(*args).compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca.get("flops", 0.0)) or None


def bench_mlp_mnist():
    """Config 1: MLP on MNIST-shaped data (MultiLayerNetwork fit loop)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import Dense, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import (
        MultiLayerNetwork, NeuralNetConfiguration,
    )
    from deeplearning4j_tpu.nn.updaters import Nesterovs

    batch = 512
    conf = (NeuralNetConfiguration.builder()
            .updater(Nesterovs(lr=0.1, momentum=0.9))
            .layer(Dense(n_out=512, activation="relu"))
            .layer(Dense(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(784)).build())
    net = MultiLayerNetwork(conf)
    net.init()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 784)).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    step, state = _net_step(net, x, y)
    _, sec = _steady_state(step, state, windows=5)
    return {"metric": "mlp_mnist_images_per_sec", "value": round(batch / sec, 2),
            "unit": "images/sec"}


def bench_lenet_cifar():
    """Config 2: LeNet on CIFAR-10-shaped data (conv path)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import LeNet
    from deeplearning4j_tpu.nn.updaters import Nesterovs

    batch = 256
    net = LeNet(height=32, width=32, channels=3, num_classes=10,
                updater=Nesterovs(lr=0.01, momentum=0.9))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, 32, 32, 3)).astype(np.float32))
    y = jnp.asarray(np.eye(10, dtype=np.float32)[rng.integers(0, 10, batch)])
    step, state = _net_step(net, x, y)
    _, sec = _steady_state(step, state, windows=5)
    return {"metric": "lenet_cifar10_images_per_sec",
            "value": round(batch / sec, 2), "unit": "images/sec"}


def bench_resnet50(platform: str):
    """Config 3 (primary): ResNet-50 training throughput + MFU."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.updaters import Nesterovs

    batch = 32 if QUICK else 128
    size = 64 if QUICK else 224
    net = ResNet50(height=size, width=size, channels=3, num_classes=1000,
                   updater=Nesterovs(lr=0.1, momentum=0.9))
    if platform != "cpu":
        net.conf.compute_dtype = "bfloat16"
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch, size, size, 3)).astype(np.float32))
    y = jnp.asarray(np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])
    step, state = _net_step(net, x, y)
    state, sec = _steady_state(step, state, steps=(10 if QUICK else 100))
    img_s = batch / sec
    out = {"metric": "resnet50_train_images_per_sec_per_chip",
           "value": round(img_s, 2), "unit": "images/sec",
           "vs_baseline": round(img_s / BASELINE_IMG_S, 4)}
    flops = _flops_per_step(net, x, y)
    if flops and platform == "tpu":
        out["mfu"] = round(flops / sec / peak_flops(), 4)
        ceiling = probe_matmul_ceiling()
        out["matmul_ceiling_tfs"] = round(ceiling, 1)
        out["mfu_vs_ceiling"] = round(flops / sec / (ceiling * 1e12), 4)
    # DP gradient traffic this step rate would put on the ICI (ring
    # allreduce moves ~2x param bytes per step per chip) — an ESTIMATE
    # derived from step rate, not a measured collective (see
    # bench_collective for the measured rate)
    out["allreduce_traffic_gbps_est"] = round(
        2 * _param_bytes(net) / sec / 1e9, 3)
    # ...and what the CROSS-SLICE tier of that exchange would put on the
    # DCN with threshold compression on (grad_compression="threshold",
    # 2-slice accounting; ops/compression.py) — the wire the compressed
    # exchange exists for
    out["compressed_wire_bytes_est"] = _compressed_wire_bytes(net)
    return out


def bench_word2vec_lstm():
    """Config 4: Word2Vec + LSTM char-RNN (embedding + recurrent paths)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nlp import Word2Vec
    from deeplearning4j_tpu.models import TextGenerationLSTM
    from deeplearning4j_tpu.nn.updaters import Adam

    from deeplearning4j_tpu.datasets import DataSet

    # word2vec: words/sec — first fit pays jit compilation, second fit on a
    # fresh model hits the jit cache (same batch shapes) = steady state.
    # Corpus large enough that fixed costs (vocab build, the final table
    # readback) amortize — the metric is steady-state training throughput
    rng = np.random.default_rng(0)
    vocab = [f"w{i}" for i in range(2000)]
    sentences = [" ".join(rng.choice(vocab, size=20))
                 for _ in range(100 if QUICK else 48000)]
    n_words = sum(len(s.split()) for s in sentences)

    def make_w2v():
        return Word2Vec(layer_size=128, window=5, min_word_frequency=1,
                        epochs=1, batch_size=4096, subsampling=0)

    warm = make_w2v()
    warm.fit(sentences)
    warm.word_vector("w0")  # drain the warmup's async queue before timing
    w2v_rate = 0.0
    for _ in range(1 if QUICK else 3):  # best-of-3, same policy as
        t0 = time.perf_counter()        # _steady_state
        m = make_w2v()
        m.fit(sentences)
        # fit() enqueues async and exports tables lazily (framework-wide
        # device-resident convention) — materialize a vector INSIDE the
        # window so the metric stays end-to-end (device drain + readback)
        m.word_vector("w0")
        w2v_rate = max(w2v_rate, n_words / (time.perf_counter() - t0))

    # char-LSTM: chars/sec through the REAL training path — fit_batch with
    # the model's configured TBPTT(50) chunking (all chunk steps fused into
    # one scanned dispatch).  Characters ship as int32 indices — the
    # TPU-native data layout (LSTM gathers its input-weight rows, the loss
    # one-hots on device; numerically identical to one-hot inputs, see
    # tests/test_recurrent.py) — and each step sees a different batch.
    batch, T, vocab_sz = 64, 100, 96
    net = TextGenerationLSTM(vocab_size=vocab_sz, updater=Adam(lr=1e-3))
    dss = [DataSet(rng.integers(0, vocab_sz, (batch, T)).astype(np.int32),
                   rng.integers(0, vocab_sz, (batch, T)).astype(np.int32))
           for _ in range(20)]
    # fit_batch returns a LazyScore (loss stays on device) — steps chain
    # without host round trips; _steady_state handles warmup + windows
    def rnn_step(_, i):
        net.fit_batch(dss[i % len(dss)])
        return net.params
    _, sec = _steady_state(rnn_step, net.params, steps=(5 if QUICK else 100),
                           windows=5)
    return [
        {"metric": "word2vec_words_per_sec", "value": round(w2v_rate, 1),
         "unit": "words/sec"},
        {"metric": "lstm_charrnn_chars_per_sec",
         "value": round(batch * T / sec, 1), "unit": "chars/sec",
         "tbptt_length": net.conf.tbptt_length},
    ]


def bench_sharded_resnet(platform: str):
    """Config 5: DP-sharded ResNet-50 over the local mesh + allreduce GB/s.

    On the 1-chip bench box this exercises the sharded path end-to-end
    (mesh build, sharding constraints, psum) with data=n_devices; the
    reported allreduce_gbps is the gradient traffic per chip."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.datasets import DataSet
    from deeplearning4j_tpu.models import ResNet50
    from deeplearning4j_tpu.nn.updaters import Nesterovs
    from deeplearning4j_tpu.parallel import ShardedTrainer, build_mesh

    n_dev = len(jax.devices())
    batch = (32 if QUICK else 128) * n_dev
    size = 64 if QUICK else 224
    net = ResNet50(height=size, width=size, channels=3, num_classes=1000,
                   updater=Nesterovs(lr=0.1, momentum=0.9))
    if platform != "cpu":
        net.conf.compute_dtype = "bfloat16"
    mesh = build_mesh({"data": n_dev})
    trainer = ShardedTrainer(net, mesh)
    rng = np.random.default_rng(0)
    ds = DataSet(rng.normal(size=(batch, size, size, 3)).astype(np.float32),
                 np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, batch)])
    # pre-place the batch on the mesh: measure compute+collectives, not the
    # per-step host→device upload of the same 77MB batch
    ds = trainer.shard_dataset(ds)
    # async fit path: losses stay device-resident, so the loop enqueues
    # steps back-to-back; _steady_state handles warmup + windows

    def sharded_step(_, i):
        trainer.fit_batch(ds)
        return net.params
    _, sec = _steady_state(sharded_step, net.params,
                           steps=(5 if QUICK else 100), warmup=3)
    grad_bytes = 2 * _param_bytes(net)
    return {"metric": "sharded_resnet50_images_per_sec",
            "value": round(batch / sec, 2), "unit": "images/sec",
            "n_devices": n_dev,
            "allreduce_traffic_gbps_est": round(grad_bytes / sec / 1e9, 3),
            "compressed_wire_bytes_est": _compressed_wire_bytes(net)}


def bench_collective(n_params: int = 25_600_000):
    """Config 8: MEASURED collective rates (round-4 verdict Next #7 — the
    derived allreduce_traffic_gbps_est is a traffic estimate, this is the
    measured thing).  psum of a ResNet-50-sized gradient pytree over the
    local mesh's data axis, plus a ppermute ring pass of the same bytes.
    On the 1-chip bench box the psum degenerates to identity and the
    ppermute to a device-local copy — so the reported rate is the chip's
    collective-dispatch + HBM floor, labeled with n_devices so nobody
    reads it as a multi-chip ICI figure; on a real slice the same code
    measures the ICI.  Shape-correctness on ≥2 devices is covered on the
    virtual 8-CPU mesh with a scaled-down ``n_params`` (pushing the full
    102 MB through 8 emulated devices costs minutes, not insight —
    tests/test_bench_harness.py)."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    from deeplearning4j_tpu.parallel import build_mesh

    n_dev = len(jax.devices())
    mesh = build_mesh({"data": n_dev})
    # ResNet-50-sized gradient pytree: 25.6M f32 params ≈ 102 MB, split
    # into realistic per-layer leaves (conv1, fc, 3x3 bottleneck convs)
    sizes = [7 * 7 * 3 * 64, 2048 * 1000, 2048]
    while sum(sizes) + 512 * 512 * 9 <= n_params:
        sizes.append(512 * 512 * 9)
    sizes.append(n_params - sum(sizes))
    key = jax.random.PRNGKey(0)
    tree = [jax.random.normal(key, (s,), jnp.float32) for s in sizes]
    nbytes = sum(4 * s for s in sizes)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
    def allreduce(t):
        return [jax.lax.psum(a, "data") for a in t]

    @functools.partial(shard_map, mesh=mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
    def ring_pass(t):
        perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
        return [jax.lax.ppermute(a, "data", perm) for a in t]

    def timeit(f, n=3 if QUICK else 10):
        jf = jax.jit(f)
        _sync(jf(tree))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            r = None
            for _ in range(n):
                r = jf(tree)
            _sync(r)
            best = min(best, (time.perf_counter() - t0) / n)
        return best

    sec_psum, sec_perm = timeit(allreduce), timeit(ring_pass)
    return {"metric": "psum_measured_gbps",
            "value": round(nbytes / sec_psum / 1e9, 2), "unit": "GB/s",
            "n_devices": n_dev, "payload_mb": round(nbytes / 1e6, 1),
            "ppermute_measured_gbps": round(nbytes / sec_perm / 1e9, 2)}


def bench_flash_attention(platform: str):
    """Config 6 (TPU-first extension; no DL4J analog): fused flash
    attention fwd+bwd at T=4096 vs the XLA O(T²) path — tokens/sec plus
    the backward's temp-memory footprint (the reason the kernel exists)."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops.attention import flash_mha, mha

    B, H, T, D = 2, 8, (512 if QUICK else 4096), 64
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, T, D))
                             .astype(np.float32)).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    mask = np.ones((B, T), np.float32)
    mask[0, int(T * 0.7):] = 0.0
    mj = jnp.asarray(mask)

    def loss_flash(q, k, v):
        return jnp.sum(flash_mha(q, k, v, True, kmask=mj).astype(jnp.float32) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True,
                           mask=mj[:, None, None, :]).astype(jnp.float32) ** 2)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
    gx = jax.jit(jax.grad(loss_xla, argnums=(0, 1, 2)))

    def timeit(f, n=(5 if QUICK else 30)):
        f(q, k, v)
        _sync(f(q, k, v))
        t0 = time.perf_counter()
        for _ in range(n):
            r = f(q, k, v)
        _sync(r)
        return (time.perf_counter() - t0) / n

    sec_f, sec_x = timeit(gf), timeit(gx)
    out = {"metric": "flash_attn_fwdbwd_tokens_per_sec",
           "value": round(B * T / sec_f, 1), "unit": "tokens/sec",
           "seq_len": T, "xla_tokens_per_sec": round(B * T / sec_x, 1),
           "speedup_vs_xla": round(sec_x / sec_f, 3)}
    mf = gf.lower(q, k, v).compile().memory_analysis()
    mx = gx.lower(q, k, v).compile().memory_analysis()
    out["bwd_temp_mb"] = round(mf.temp_size_in_bytes / 1e6, 1)
    out["xla_bwd_temp_mb"] = round(mx.temp_size_in_bytes / 1e6, 1)
    return out


def bench_transformer_lm(platform: str):
    """Config 7 (round-4 protocol extension; no DL4J analog — anchor is
    SURVEY §7-M5): GPT-2-small-class TransformerLM end-to-end training.

    ~163M params (124M non-embedding), L=12 d=768 H=12, T=1024, vocab
    50304 (128-aligned GPT-2 BPE), bf16 compute, Adam, fused sparse-xent
    loss — trained through ShardedTransformerLM.fit_batch (the real 4D-
    parallel train-step path on a 1-axis mesh).  Reports tokens/sec plus
    TWO MFU figures:
      - mfu: XLA cost-analysis FLOPs / time / peak (the ResNet protocol)
      - mfu_model_flops: analytic 6·N_matmul·tokens + 12·L·B·T²·d
        (the PaLM-convention model-FLOPs count; excludes the embedding
        gather that 6·N_total would overcount)
    Attention is the class default (what users get) on every platform.
    """
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh

    B = 2 if QUICK else 8
    T = 256 if QUICK else 1024
    V, L, D, H = 50304, 12, 768, 12
    if QUICK:
        L, D, H = 2, 256, 4
    from deeplearning4j_tpu.nn.updaters import Adam

    n_dev = len(jax.devices())
    mesh = build_mesh({"data": n_dev})
    lm = ShardedTransformerLM(
        vocab_size=V, n_layers=L, d_model=D, n_heads=H, mesh=mesh,
        max_len=T, n_microbatches=1, compute_dtype=jnp.bfloat16,
        # bf16 Adam moments halve the m/v HBM traffic; loss-curve parity
        # quantified in tests/test_updaters_bf16.py
        updater=Adam(lr=3e-4, moment_dtype="bfloat16"))
    rng = np.random.default_rng(0)
    toks = jax.device_put(jnp.asarray(rng.integers(0, V, (B * n_dev, T)),
                                      jnp.int32), lm.token_sharding)
    tgts = jax.device_put(jnp.asarray(np.roll(np.asarray(toks), -1, axis=1),
                                      jnp.int32), lm.token_sharding)

    def lm_step(_, i):
        lm.fit_batch(toks, tgts)
        return lm.params

    _, sec = _steady_state(lm_step, lm.params, steps=(5 if QUICK else 60),
                           warmup=3)
    tokens = B * n_dev * T
    out = {"metric": "transformer_lm_tokens_per_sec",
           "value": round(tokens / sec, 1), "unit": "tokens/sec",
           "params_m": round(sum(x.size for x in
                                 jax.tree_util.tree_leaves(lm.params)) / 1e6, 1),
           "seq_len": T, "batch": B * n_dev}
    # analytic model FLOPs: matmul-participating params only (blocks +
    # head + final LN; embedding/pos gathers do no matmul FLOPs)
    n_matmul = sum(x.size for k, v in lm.params.items()
                   if k not in ("embed", "pos")
                   for x in jax.tree_util.tree_leaves(v))
    flops_model = 6 * n_matmul * tokens + 12 * L * (B * n_dev) * T * T * D
    if platform == "tpu":
        # flops_model counts the GLOBAL batch → all chips' peak
        out["mfu_model_flops"] = round(
            flops_model / sec / (peak_flops() * n_dev), 4)
        ceiling = probe_matmul_ceiling()
        out["matmul_ceiling_tfs"] = round(ceiling, 1)
        out["mfu_model_vs_ceiling"] = round(
            flops_model / sec / (ceiling * 1e12 * n_dev), 4)
        args = (lm.params, lm.opt_state, jnp.asarray(0, jnp.int32),
                toks, tgts)
        with jax.sharding.set_mesh(lm.mesh):
            ca = lm._jit_step.lower(*args).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        # (the partitioned module's per-device count; a pallas_call is
        # opaque to it, so flash attention's FLOPs are NOT in this figure —
        # mfu_model_flops is the full count)
        xla_flops = float(ca.get("flops", 0.0))
        if xla_flops:
            out["mfu"] = round(xla_flops / sec / peak_flops(), 4)
            out["mfu_vs_ceiling"] = round(
                xla_flops / sec / (ceiling * 1e12), 4)
    return out


def bench_pipeline_schedules():
    """Config 9 (round-5 verdict Next #6): GPipe vs 1F1B pipeline
    schedule A/B at the transformer-LM shape.  A pipe axis needs >1
    device, so the A/B runs in a child process on a virtual 4-device CPU
    mesh (scripts/pipeline_ab.py; the dryrun-harness mechanism) — the
    schedule-vs-schedule ratios (step time, measured peak temp memory)
    and the analytic bubble/peak accounting are the deliverables; the
    absolute CPU tokens/sec is NOT a TPU figure and is labeled as such."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "pipeline_ab.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"pipeline_ab failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if not ab.get("loss_parity_bitwise"):
        raise RuntimeError(f"1F1B/GPipe loss parity FAILED in A/B: {ab}")
    g, f = ab["gpipe"], ab["1f1b"]
    return {"metric": "pipeline_1f1b_tokens_per_sec",
            "value": f["tokens_per_sec"], "unit": "tokens/sec (cpu-virtual)",
            "platform": ab["platform"], "n_stages": ab["n_stages"],
            "n_microbatches": ab["n_microbatches"],
            "gpipe_tokens_per_sec": g["tokens_per_sec"],
            "step_time_ratio_1f1b_vs_gpipe":
                ab["step_time_ratio_1f1b_vs_gpipe"],
            "loss_parity_bitwise": True,
            "bubble_fraction": {"gpipe": g["bubble_fraction"],
                                "1f1b": f["bubble_fraction"]},
            "peak_live_stage_inputs": {"gpipe": g["peak_live_stage_inputs"],
                                       "1f1b": f["peak_live_stage_inputs"]},
            "analytic_peak_activation_mb":
                {"gpipe": g["analytic_peak_activation_mb"],
                 "1f1b": f["analytic_peak_activation_mb"]},
            "measured_peak_temp_mb": {"gpipe": g["measured_peak_temp_mb"],
                                      "1f1b": f["measured_peak_temp_mb"]},
            "peak_temp_ratio_1f1b_vs_gpipe":
                ab.get("peak_temp_ratio_1f1b_vs_gpipe")}


def bench_grad_compression():
    """Config 10: dense vs threshold/bitmap DCN gradient exchange on a
    virtual 2-slice mesh (scripts/compression_ab.py; the dryrun-harness
    subprocess mechanism — a dcn axis needs >1 slice).  The deliverables
    are the wire-bytes ratio and loss-curve parity; the absolute CPU step
    time is NOT a TPU figure and is labeled as such.  HARD gates (the
    satellite's regression contract): the threshold arm's wire ratio must
    be >=8x, the error-feedback loss curves must stay within tolerance of
    dense, and grad_compression=None must be bit-identical to the
    unadorned trainer — a silent miss on any of these is a correctness
    regression, not a perf note."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "compression_ab.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"compression_ab failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if not ab.get("wire_ratio_ok") or ab["wire_ratio_threshold"] < 8.0:
        raise RuntimeError("compression wire-bytes ratio gate FAILED "
                           f"(need >=8x): {ab}")
    if not ab.get("loss_parity_ok") or not ab.get("compressed_learns"):
        raise RuntimeError(f"compression loss-parity gate FAILED: {ab}")
    if not ab.get("dense_bitwise_vs_today"):
        raise RuntimeError("grad_compression=None is no longer bit-identical "
                           f"to the default trainer: {ab}")
    return {"metric": "grad_compression_wire_ratio",
            "value": ab["wire_ratio_threshold"], "unit": "x (analytic)",
            "platform": ab["platform"], "mesh": ab["mesh"],
            "n_params": ab["n_params"],
            "wire_bytes_per_step": {
                "dense": ab["threshold"]["dense_wire_bytes_per_step"],
                "threshold": ab["threshold"]["wire_bytes_per_step"],
                "bitmap": ab["bitmap"]["wire_bytes_per_step"]},
            "bitmap_wire_ratio": ab["bitmap"]["wire_ratio"],
            "final_loss": {m: ab[m]["final_loss"]
                           for m in ("dense", "threshold", "bitmap")},
            "loss_parity_ok": True, "dense_bitwise_vs_today": True,
            "n_buckets": ab["threshold"]["n_buckets"]}


def bench_serving():
    """Config 12: production-serving A/B (scripts/serving_ab.py; the CPU
    subprocess mechanism — the batching logic under test is host-side).
    The legacy fixed-poll ParallelInference and the new serving.Engine
    each serve the SAME synthetic open-loop trickle on the LeNet model;
    HARD gates (the serving regression contract): new throughput >= 1.0x
    legacy AND new p99 <= legacy p99 at equal offered load, with zero
    unwarmed serves (AOT warmup really covered every bucket) and zero
    request errors.  The headline value is the new engine's requests/sec
    on this box — NOT a TPU figure; the deliverables are the ratios."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "serving_ab.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"serving_ab failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if not ab.get("throughput_ok"):
        raise RuntimeError("serving throughput gate FAILED (new engine must "
                           f"be >= 1.0x legacy ParallelInference): {ab}")
    if not ab.get("p99_ok"):
        raise RuntimeError("serving p99 gate FAILED (new engine p99 must be "
                           f"<= legacy at equal load): {ab}")
    if not ab.get("all_completed"):
        raise RuntimeError(f"serving A/B had request errors: {ab}")
    if ab["new"].get("unwarmed_serves"):
        raise RuntimeError("serving AOT warmup gate FAILED (a request paid "
                           f"a serve-time compile): {ab}")
    return {"metric": "serving_throughput_rps",
            "value": ab["new"]["throughput_rps"], "unit": "requests/sec (cpu)",
            "platform": ab["platform"], "n_requests": ab["n_requests"],
            "throughput_ratio_new_vs_legacy":
                ab["throughput_ratio_new_vs_legacy"],
            "p50_ms": {"legacy": ab["legacy"]["p50_ms"],
                       "new": ab["new"]["p50_ms"]},
            "p99_ms": {"legacy": ab["legacy"]["p99_ms"],
                       "new": ab["new"]["p99_ms"]},
            "batch_occupancy": ab["new"]["batch_occupancy"],
            "p99_ok": True, "throughput_ok": True}


def bench_input_pipeline():
    """Config 13: device-resident input pipeline A/B
    (scripts/input_pipeline_ab.py; CPU subprocess — the feeding logic
    under test is host-side).  Sync (host normalizer + per-step blocking
    H2D in fit_batch) vs DevicePrefetchIterator (uint8 wire, depth-2
    async H2D ring, jitted on-device normalization) on the same uint8
    image stream, arms interleaved epoch-for-epoch.  HARD gates (the
    input-pipeline regression contract): prefetched throughput >= 1.0x
    sync (median paired-epoch ratio), the loss sequence BIT-IDENTICAL to
    the sync path (on the gated model AND a full-LeNet leg — the
    pipeline moves work, never math; this also pins the sync fallback
    path bitwise), and a reported stall fraction from the prefetcher's
    request-vs-ready accounting (docs/INPUT_PIPELINE.md).  The headline
    value is the throughput ratio — a host-side figure, NOT a TPU
    number; the wire-byte and overlap wins are larger on a real chip."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "input_pipeline_ab.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"input_pipeline_ab failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if not ab.get("throughput_ok"):
        raise RuntimeError("input-pipeline throughput gate FAILED "
                           f"(prefetched must be >= 1.0x sync): {ab}")
    if not ab.get("loss_bitwise") or not ab.get("lenet_bitwise"):
        raise RuntimeError("input-pipeline bit-identity gate FAILED (the "
                           f"prefetched path changed the math): {ab}")
    if ab.get("stall_fraction") is None:
        raise RuntimeError(f"input-pipeline stall accounting MISSING: {ab}")
    return {"metric": "input_pipeline_overlap",
            "value": ab["throughput_ratio"],
            "unit": "x (prefetched/sync, cpu)",
            "platform": ab["platform"],
            "paired_epoch_ratios": ab["paired_epoch_ratios"],
            "images_per_sec": {"sync": ab["sync"]["images_per_sec"],
                               "prefetched":
                                   ab["prefetched"]["images_per_sec"]},
            "stall_fraction": ab["stall_fraction"],
            "stall_stats": ab["stall_stats"],
            "loss_bitwise": True, "lenet_bitwise": True,
            "throughput_ok": True}


def bench_telemetry_overhead():
    """Config 16: observability-layer A/B (scripts/trace_overhead_ab.py;
    CPU subprocess — the span recorder under test is host-side).  The
    OFF and ON arms run adjacent-step-paired on the same batches.  HARD
    gates (the telemetry contract): median paired overhead <= 1.03x,
    loss sequences BIT-IDENTICAL across arms (tracing may move clock
    reads, never math) with the disabled fast path a shared no-op
    object, the exported trace valid Chrome-trace JSON, and the
    documented span trees present: train/step ⊃ {train/h2d,
    train/dispatch} (+ train/device_sync) for training, serve/batch ⊃
    serve/forward (+ serve/request / serve/queue_wait /
    serve/batch_form) for serving (docs/OBSERVABILITY.md)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "trace_overhead_ab.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"trace_overhead_ab failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if not ab.get("overhead_ok"):
        raise RuntimeError("telemetry overhead gate FAILED (tracing-on "
                           f"must be <= 1.03x paired): {ab}")
    if not ab.get("loss_bitwise") or not ab.get("disabled_noop"):
        raise RuntimeError("telemetry off-arm identity gate FAILED "
                           f"(tracing changed behavior): {ab}")
    if not ab.get("trace_valid"):
        raise RuntimeError("exported trace failed Chrome-trace schema "
                           f"validation: {ab}")
    if not ab.get("train_span_tree_ok") or not ab.get("serve_span_tree_ok"):
        raise RuntimeError("documented span tree MISSING from the exported "
                           f"trace: {ab}")
    return {"metric": "telemetry_overhead",
            "value": ab["overhead_ratio"],
            "unit": "x (tracing on/off, cpu)",
            "platform": ab["platform"], "pairs": ab["pairs"],
            "pair_ratio_iqr": ab["pair_ratio_iqr"],
            "events": ab["events"],
            "dropped_events": ab["dropped_events"],
            "train_steps_traced": ab["train_steps_traced"],
            "loss_bitwise": True, "disabled_noop": True,
            "trace_valid": True, "train_span_tree_ok": True,
            "serve_span_tree_ok": True, "overhead_ok": True}


def bench_serving_chaos():
    """Config 15: serving chaos recovery (scripts/serving_chaos_soak.py;
    CPU subprocess — the resilience logic under test is host-side).  An
    open-loop trickle against a 2-replica engine while every serving
    fault kind fires: replica threads crashed and hung mid-batch
    (supervisor must retry/complete every future and respawn+re-warm),
    scripted all-NaN poison requests (bisection must isolate them so
    co-batched requests succeed), and a canary choreography (a healthy
    candidate must promote, a NaN-weight regressed candidate must
    auto-roll-back).  HARD gates (the serving-resilience contract): zero
    stranded futures, zero cross-request poisoning, p99 under the SLO
    bound overall AND inside the 1s windows after each replica loss,
    zero compiles across respawns (cache-hit re-warm), auto-rollback on
    exactly the regressed version, and a chaos-off arm whose outputs are
    BIT-IDENTICAL to the pre-PR engine configuration with every
    resilience counter at zero.  The reported value is the injected
    fault count — fixed by the deterministic schedule."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "serving_chaos_soak.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"serving_chaos_soak failed (rc={p.returncode}): "
                           f"{p.stdout[-500:]} {p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if soak.get("stranded") != 0:
        raise RuntimeError(f"serving soak STRANDED futures: {soak}")
    if (soak.get("poison_cross_contaminated") != 0
            or soak.get("non_poison_failures") != 0
            or not soak.get("poison_isolated_ok")):
        raise RuntimeError(f"poison isolation gate FAILED: {soak}")
    if not soak.get("p99_ok"):
        raise RuntimeError("p99 gate FAILED during replica loss: "
                           f"{soak}")
    if not soak.get("respawn_zero_compiles"):
        raise RuntimeError("replica respawn paid a serve-time compile: "
                           f"{soak}")
    if (not soak.get("canary_promoted_good")
            or not soak.get("canary_rollback_fired")):
        raise RuntimeError(f"canary promote/rollback gate FAILED: {soak}")
    if not soak.get("off_behavior_identical"):
        raise RuntimeError("chaos-off engine is no longer behavior-"
                           f"identical to the pre-PR configuration: {soak}")
    if not soak.get("soak_ok"):
        raise RuntimeError(f"serving chaos soak gate FAILED: {soak}")
    return {"metric": "serving_chaos_recovery",
            "value": soak["faults_injected"], "unit": "faults recovered",
            "platform": soak["platform"],
            "replica_crashes": soak["replica_crashes"],
            "replica_hangs": soak["replica_hangs"],
            "replica_respawns": soak["replica_respawns"],
            "retries": soak["retries"],
            "poison_isolated": soak["poison_isolated"],
            "p99_ms": soak["p99_ms"],
            "p99_loss_window_ms": soak["p99_loss_window_ms"],
            "canary_history_promoted": soak["canary_history_promoted"],
            "stranded": 0, "poison_cross_contaminated": 0,
            "off_behavior_identical": True,
            "wall_seconds": soak["wall_seconds"]}


def bench_fleet_load():
    """Config 18: fleet load + chaos (scripts/fleet_load_soak.py; CPU
    subprocess — the routing/failover logic under test is host-side).
    An open-loop seeded trace (diurnal rate, burst windows, heavy-tail
    sizes) against a 3-host fleet router while every fleet fault kind
    fires driver-side: a straggling host (dispatch must steer away), a
    preemption notice (drain + re-place, planned leave), and a host
    KILLED mid-rolling-swap (the already-swapped survivors must roll
    back; the aborted version never appears after the call returns).
    Plus a clean registry promote through the router and a memory-
    bounded million-request scale arm streamed through the router
    against instant synthetic hosts.  HARD gates: zero stranded
    futures, at-most-once delivery (zero double-delivered), zero
    version mixing after promote/rollback, p99 under the SLO bound
    overall AND inside the 1s post-fault windows, bounded shed rate,
    zero router in-flight after shutdown, and a chaos-off 2-host fleet
    arm whose outputs are BIT-IDENTICAL to a single-host engine with
    every resilience counter at zero.  The reported value is router
    throughput on the scale arm."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "fleet_load_soak.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"fleet_load_soak failed (rc={p.returncode}): "
                           f"{p.stdout[-500:]} {p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if soak.get("stranded") != 0 or soak.get("scale_stranded") != 0:
        raise RuntimeError(f"fleet soak STRANDED futures: {soak}")
    if soak.get("double_delivered") != 0:
        raise RuntimeError(f"at-most-once delivery gate FAILED: {soak}")
    if (soak.get("unmatched_versions") != 0
            or soak.get("v1_after_promote") != 0
            or soak.get("v3_after_rollback") != 0):
        raise RuntimeError(f"version-mixing gate FAILED: {soak}")
    if not soak.get("p99_ok"):
        raise RuntimeError(f"fleet p99 gate FAILED post-fault: {soak}")
    if not soak.get("promote_ok") or not soak.get("swap_rolled_back"):
        raise RuntimeError(f"rolling swap/rollback gate FAILED: {soak}")
    if not soak.get("off_behavior_identical"):
        raise RuntimeError("chaos-off fleet is no longer behavior-"
                           f"identical to a single host: {soak}")
    if not soak.get("soak_ok"):
        raise RuntimeError(f"fleet load soak gate FAILED: {soak}")
    return {"metric": "fleet_load_chaos",
            "value": soak["scale_rps"], "unit": "router req/sec",
            "platform": soak["platform"],
            "faults_injected": soak["faults_injected"],
            "retries": soak["retries"],
            "timeouts": soak["timeouts"],
            "late_discards": soak["late_discards"],
            "affinity_routed": soak["affinity_routed"],
            "shed_rate": soak["shed_rate"],
            "p99_ms": soak["p99_ms"],
            "p99_post_fault_ms": soak["p99_post_fault_ms"],
            "scale_requests": soak["scale_requests"],
            "scale_peak_outstanding": soak["scale_peak_outstanding"],
            "stranded": 0, "double_delivered": 0,
            "off_behavior_identical": True,
            "wall_seconds": soak["wall_seconds"]}


def bench_disagg_decode():
    """Config 24: disaggregated prefill/decode A/B
    (scripts/fleet_load_soak.py --disagg; CPU subprocess — the
    role-split routing and KV-page handoff under test are host-side).
    Three arms.  Identity: temp-0 outputs of a prefill-host -> KV-page
    handoff -> decode-host pipeline AND a tensor-parallel sharded
    decode engine are BIT-IDENTICAL to a unified single-host engine,
    with the TP arm's KV pool holding 1/n of the pages per device.
    Burst: a wall of long-prompt prefill requests degrades a unified
    host's inter-token latency beyond 1.2x calm (prefill and step
    share the loop) while the disaggregated decode host's TPOT p99
    stays within 1.2x of calm AND serves zero new compiles.  Chaos: a
    prefill host is killed mid-run; every future resolves exactly once
    with the SAME tokens (seeded re-prefill elsewhere) and the decode
    host's page accounting stays a clean free/private/trie partition.
    The reported value is the disagg decode host's burst-phase TPOT
    p99."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "fleet_load_soak.py")
    cmd = [sys.executable, script, "--disagg"] + \
        (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"disagg soak failed (rc={p.returncode}): "
                           f"{p.stdout[-500:]} {p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if not soak.get("identity_disagg_bitwise"):
        raise RuntimeError("disaggregated decode is no longer bit-"
                           f"identical to the unified engine: {soak}")
    if not soak.get("identity_tp_bitwise"):
        raise RuntimeError("tensor-parallel decode is no longer bit-"
                           f"identical to the unified engine: {soak}")
    if not soak.get("unified_degraded"):
        raise RuntimeError("burst arm no longer degrades the unified "
                           f"host (A/B baseline lost): {soak}")
    if not soak.get("disagg_tpot_ok"):
        raise RuntimeError("disagg decode TPOT p99 gate FAILED under "
                           f"the prefill burst: {soak}")
    if not soak.get("decode_zero_compiles"):
        raise RuntimeError("decode host compiled at serve time during "
                           f"the burst: {soak}")
    if (soak.get("chaos_disagg_stranded") != 0
            or soak.get("chaos_disagg_double_delivered") != 0):
        raise RuntimeError("prefill-host kill stranded/double-"
                           f"delivered futures: {soak}")
    if not soak.get("chaos_disagg_tokens_ok"):
        raise RuntimeError("prefill-host kill retries changed tokens "
                           f"(seeded determinism lost): {soak}")
    if not soak.get("chaos_disagg_partition_ok"):
        raise RuntimeError("decode host page accounting corrupt after "
                           f"prefill-host kill: {soak}")
    if not soak.get("disagg_ok"):
        raise RuntimeError(f"disagg A/B gate FAILED: {soak}")
    return {"metric": "disagg_decode_ab",
            "value": soak["disagg_tpot_burst_p99_ms"], "unit": "ms tpot p99",
            "platform": soak["platform"],
            "identity_requests": soak["identity_requests"],
            "identity_page_transfers": soak["identity_page_transfers"],
            "identity_tp_shard_frac": soak["identity_tp_shard_frac"],
            "unified_tpot_calm_p99_ms": soak["unified_tpot_calm_p99_ms"],
            "unified_tpot_burst_p99_ms": soak["unified_tpot_burst_p99_ms"],
            "disagg_tpot_calm_p99_ms": soak["disagg_tpot_calm_p99_ms"],
            "chaos_disagg_requests": soak["chaos_disagg_requests"],
            "chaos_disagg_retries": soak["chaos_disagg_retries"],
            "identity_bitwise": True, "stranded": 0,
            "double_delivered": 0, "decode_zero_compiles": True}


def bench_train_promote():
    """Config 25: the train→promote flywheel gate
    (scripts/train_promote_soak.py; CPU subprocess — the lifecycle
    control flow under test is host-side).  A PromotionPipeline drives
    six train → eval → register → canary → roll generations against a
    live 3-host fleet under concurrent open-loop traffic, with chaos at
    every stage boundary: device-loss faults mid-train (recovered), a
    NaN-params generation (the EVAL gate must catch it), a regressed
    generation (the CANARY must reject it on prediction divergence), a
    host killed mid-roll (survivors roll back, the pipeline re-aliases
    to the LINEAGE target — never version−1), and a controller crash at
    the canary stage (a fresh pipeline resumes from the journal without
    retraining).  HARD gates: exactly three promoted generations with
    monotone (non-increasing) eval losses, both rollbacks land on the
    lineage-selected ancestor, zero dropped/stranded/double-delivered
    requests, zero unmatched responses and zero version mixing inside
    steady windows, zero serve-time compiles (warm bundles cover fleet
    birth, canary warm, every roll and every rollback), and the
    crash-resume completes with exactly one training run for the
    interrupted generation.  The reported value is promoted generations
    per wall-minute."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "train_promote_soak.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"train_promote_soak failed (rc={p.returncode}): "
                           f"{p.stdout[-500:]} {p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if soak.get("promoted_generations") != [1, 2, 6]:
        raise RuntimeError("flywheel promoted the wrong generations "
                           f"(want [1, 2, 6]): {soak}")
    if not soak.get("monotone_eval"):
        raise RuntimeError(f"promoted eval losses are not monotone: {soak}")
    if not soak.get("nan_caught_by_eval"):
        raise RuntimeError("the EVAL gate missed the NaN-params "
                           f"generation: {soak}")
    if not soak.get("canary_rejected_regression"):
        raise RuntimeError("the canary promoted the regressed "
                           f"generation: {soak}")
    if not soak.get("midroll_kill_rolled_back"):
        raise RuntimeError("mid-roll host kill did not roll the "
                           f"generation back: {soak}")
    if not soak.get("rollbacks_hit_lineage_target") \
            or not soak.get("lineage_chain_ok"):
        raise RuntimeError("rollback missed the lineage target "
                           f"(or picked version-1): {soak}")
    if not soak.get("resume_ok"):
        raise RuntimeError("controller crash-resume gate FAILED "
                           f"(retrained or stalled): {soak}")
    if soak.get("stranded") != 0 or soak.get("double_delivered") != 0 \
            or soak.get("errors"):
        raise RuntimeError(f"flywheel dropped/duplicated traffic: {soak}")
    if soak.get("unmatched_versions") != 0 \
            or soak.get("window_violations") != 0 \
            or not soak.get("window_samples"):
        raise RuntimeError(f"version-mixing gate FAILED: {soak}")
    if soak.get("serve_time_bundle_misses") != 0 \
            or not soak.get("compile_cache_stable"):
        raise RuntimeError("serve-time compile gate FAILED (a fleet "
                           f"host missed its warm bundle): {soak}")
    if not soak.get("fleet_converged") or not soak.get("soak_ok"):
        raise RuntimeError(f"train_promote_loop gate FAILED: {soak}")
    n_promoted = len(soak["promoted_generations"])
    return {"metric": "train_promote_loop",
            "value": round(n_promoted / (soak["wall_seconds"] / 60.0), 2),
            "unit": "promotions/min",
            "platform": soak["platform"],
            "generations": len(soak["generations"]),
            "promoted": n_promoted,
            "promoted_losses": soak["promoted_losses"],
            "requests": soak["n_submitted"],
            "window_samples": soak["window_samples"],
            "p99_ms": soak["p99_ms"],
            "bundle_hits": soak["bundle_hits"],
            "stranded": 0, "double_delivered": 0,
            "serve_time_bundle_misses": 0,
            "wall_seconds": soak["wall_seconds"]}


def bench_multitenant():
    """Config 26: the multi-tenant many-model serving gate
    (scripts/multitenant_soak.py; CPU subprocess — admission/placement
    logic is host-side).  Three models on a 3-host fleet, three tenants
    under the same per-host TenantTable (weighted-fair lanes, atomic
    check-and-charge quotas), a PlacementController closing the
    (model, host) loop, open-loop mixed traffic.  Chaos: one tenant
    10x-bursts its model (shared with a victim tenant), an m2-holding
    host is killed mid-burst, the idle model is controller-evicted and
    then demand-reloaded by fresh traffic.  HARD gates: both victim
    tenants' burst-window p99 inside the calm envelope with ZERO victim
    sheds/errors (the burst tenant sheds only its own traffic), exact
    three-way shed attribution (request ledger == host TenantTables ==
    per-tenant metric label slices, every TenantOverloadedError naming
    the bursting tenant), zero version/tenant mixing on classified
    responses, nothing stranded or double-delivered through the kill,
    the placement loop observed widening the hot model and evicting +
    demand-reloading the cold one, and zero serve-time compiles — no
    warm-bundle miss and no compile-cache growth across eviction,
    reload, and widening.  The reported value is the victim tenants'
    burst-window p99."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "multitenant_soak.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode not in (0, 2) or not p.stdout.strip():
        raise RuntimeError(f"multitenant_soak failed (rc={p.returncode}): "
                           f"{p.stdout[-500:]} {p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if soak.get("stranded") != 0 or soak.get("double_delivered") != 0 \
            or not soak.get("all_done_before_timeout"):
        raise RuntimeError(f"multitenant soak stranded requests: {soak}")
    if not soak.get("victims_ok") or soak.get("victim_sheds") != 0 \
            or soak.get("victim_errors") != 0:
        raise RuntimeError("victim-tenant isolation gate FAILED (burst "
                           f"leaked into a victim's p99/errors): {soak}")
    if not soak.get("burst_sheds") or not soak.get("attribution_exact"):
        raise RuntimeError("exact shed-attribution gate FAILED (ledger, "
                           f"host tables and metric slices disagree): {soak}")
    if soak.get("mixed_responses") != 0:
        raise RuntimeError(f"version/tenant mixing detected: {soak}")
    if not soak.get("host_killed") \
            or soak.get("hosts_final", {}).get("h1") != "down":
        raise RuntimeError(f"mid-burst host kill did not land: {soak}")
    if not soak.get("m3_evicted") or not soak.get("m3_reloaded") \
            or not soak.get("m3_ok_responses"):
        raise RuntimeError("cold-model evict + demand-reload gate "
                           f"FAILED: {soak}")
    if not soak.get("placements") or not soak.get("placement_evictions") \
            or not soak.get("demand_loads") or not soak.get("model_misses"):
        raise RuntimeError(f"placement loop never actuated: {soak}")
    if soak.get("serve_time_bundle_misses") != 0 \
            or not soak.get("compile_caches_stable"):
        raise RuntimeError("serve-time compile gate FAILED (a placement "
                           f"move missed its warm bundle): {soak}")
    if not soak.get("soak_ok"):
        raise RuntimeError(f"multitenant_soak gate FAILED: {soak}")
    iso = soak["isolation"]
    p99 = max(iso[t]["burst_p99_ms"] for t in iso)
    return {"metric": "multitenant_soak", "value": p99,
            "unit": "ms victim burst p99",
            "platform": soak["platform"],
            "requests": soak["n_requests"],
            "burst_sheds": soak["burst_sheds"],
            "victim_sheds": 0, "victim_errors": 0,
            "attribution_exact": True, "mixed_responses": 0,
            "placements": soak["placements"],
            "placement_evictions": soak["placement_evictions"],
            "demand_loads": soak["demand_loads"],
            "stranded": 0, "double_delivered": 0,
            "serve_time_bundle_misses": 0,
            "wall_seconds": soak["wall_seconds"]}


def bench_chaos_recovery():
    """Config 11: chaos-tested fault recovery (scripts/chaos_soak.py; the
    subprocess mechanism, CPU — fault injection needs no accelerator).  A
    scripted schedule fires ≥5 distinct fault kinds (device loss, mid-zip
    checkpoint-write crash, truncated + bit-flipped latest checkpoint,
    hung step, NaN gradients) into a real ElasticTrainer loop.  HARD
    gates (the robustness contract, not perf): zero unrecovered failures,
    restore falls back to the newest INTACT checkpoint when the latest is
    corrupt, chaos machinery disabled is bit-identical to the plain
    trainer, and the chaos arm's final loss stays within tolerance of the
    fault-free run.  The reported value is the recovery count — fixed by
    the deterministic schedule, so any change means the schedule or the
    recovery behavior changed."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "chaos_soak.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"chaos_soak failed (rc={p.returncode}): "
                           f"{p.stdout[-500:]} {p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if soak.get("unrecovered") != 0:
        raise RuntimeError(f"chaos soak had UNRECOVERED failures: {soak}")
    if not soak.get("intact_fallback_ok"):
        raise RuntimeError("corrupt-latest checkpoint fallback FAILED "
                           f"in chaos soak: {soak}")
    if not soak.get("disabled_bitwise"):
        raise RuntimeError("chaos-disabled run is no longer bit-identical "
                           f"to the plain trainer: {soak}")
    if not soak.get("loss_parity_ok") or not soak.get("chaos_learns"):
        raise RuntimeError(f"chaos-arm loss parity gate FAILED: {soak}")
    if soak.get("n_fault_kinds", 0) < 5:
        raise RuntimeError(f"chaos soak exercised <5 fault kinds: {soak}")
    return {"metric": "chaos_recovery_faults_recovered",
            "value": soak["recoveries"], "unit": "recoveries",
            "platform": soak["platform"],
            "fault_kinds": soak["fault_kinds"],
            "faults_injected": soak["faults_injected"],
            "recovery_seconds": soak["recovery_seconds"],
            "corrupt_checkpoints_quarantined":
                soak["corrupt_checkpoints_quarantined"],
            "stale_tmp_cleaned": soak["stale_tmp_cleaned"],
            "disabled_bitwise": True, "loss_parity_ok": True,
            "final_loss": soak["final_loss"]}


def bench_multihost_chaos():
    """Config 14: process-scale chaos recovery (scripts/chaos_soak.py
    --multiproc; CPU subprocesses — process lifecycle needs no
    accelerator).  The PodLauncher forks 2 workers x 4 virtual devices
    (the tests/test_multiprocess.py topology) sharing one checkpoint
    store; worker 1 is SIGKILLed mid-run (proc_kill) and worker 0
    SIGSTOPped (proc_hang → heartbeat expiry).  HARD gates (the
    pod-elasticity contract): zero unrecovered workers, ≥1 proc_kill AND
    ≥1 proc_hang recovery each ending in training completion, the
    chaos-off 2-process run BIT-IDENTICAL to the single-process baseline
    loss sequence, every chaos-arm loss bit-equal to the baseline at its
    global step (restarted workers replay the exact trajectory from the
    shared checkpoints — only process 0 writes), and ZERO orphan worker
    processes surviving the run.  The reported value is the worker
    restart count — fixed by the deterministic self-injected schedule."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "chaos_soak.py")
    cmd = [sys.executable, script, "--multiproc"] + \
        (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"multiproc chaos_soak failed (rc={p.returncode})"
                           f": {p.stdout[-500:]} {p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if soak.get("unrecovered") != 0 or soak.get("deadline_hit"):
        raise RuntimeError(f"multiproc soak had UNRECOVERED workers: {soak}")
    if soak.get("proc_kill_recovered", 0) < 1 \
            or soak.get("proc_hang_recovered", 0) < 1:
        raise RuntimeError("multiproc soak missed a proc fault recovery "
                           f"(kill+hang both required): {soak}")
    if not soak.get("off_bitwise"):
        raise RuntimeError("chaos-off 2-process run is not bit-identical "
                           f"to the single-process baseline: {soak}")
    if not soak.get("chaos_loss_bitwise"):
        raise RuntimeError("chaos-arm losses diverged from the baseline "
                           f"trajectory: {soak}")
    if soak.get("leaked", 1) != 0 or soak.get("off_leaked", 1) != 0:
        raise RuntimeError(f"orphan worker process survived the soak: {soak}")
    if not soak.get("writer_guard_ok") or not soak.get("completion_steps_ok"):
        raise RuntimeError(f"multihost checkpoint/completion gate: {soak}")
    if not soak.get("soak_ok"):
        raise RuntimeError(f"multiproc soak gate FAILED: {soak}")
    return {"metric": "multihost_chaos_recovered",
            "value": soak["restarts"], "unit": "worker restarts",
            "platform": soak["platform"],
            "workers": soak["workers"],
            "devices_per_worker": soak["devices_per_worker"],
            "proc_kill_recovered": soak["proc_kill_recovered"],
            "proc_hang_recovered": soak["proc_hang_recovered"],
            "membership_epoch": soak["membership_epoch"],
            "resume_tail_steps": soak["resume_tail_steps"],
            "off_bitwise": True, "chaos_loss_bitwise": True,
            "leaked": 0, "wall_seconds": soak["wall_seconds"]}


def bench_preemption():
    """Config 17: announced-failure recovery (scripts/chaos_soak.py
    --preempt; CPU subprocesses — signal/process lifecycle needs no
    accelerator).  The PodLauncher forks 2 workers x 4 virtual devices;
    worker 0 (writer + coordinator) receives a scheduled preemption
    notice (SIGTERM self) and, in a separate arm, a coordinator kill;
    worker 1 is made a straggler.  HARD gates (the preemption-tolerance
    contract): the emergency checkpoint lands WITHIN the grace budget,
    the preempted worker exits with the distinct PREEMPTED code and
    relaunches WITHOUT consuming the restart budget, the relaunched
    incarnation resumes at EXACTLY the preempted step (zero steps lost)
    with a bit-exact trajectory replay, the coordinator kill recovers to
    training completion, the straggler is flagged from heartbeat step
    times within the beat budget, zero orphan processes, and the
    chaos-off arm (announced-failure machinery armed, no faults) stays
    BIT-IDENTICAL to the pre-PR single-process baseline with zero
    restarts/planned leaves/straggler flags.  The reported value is the
    planned-leave count — fixed by the deterministic schedule."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "chaos_soak.py")
    cmd = [sys.executable, script, "--preempt"] + \
        (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"preemption chaos_soak failed (rc="
                           f"{p.returncode}): {p.stdout[-500:]} "
                           f"{p.stderr[-1000:]}")
    soak = json.loads(p.stdout.strip().splitlines()[-1])
    if soak.get("unrecovered") != 0 or soak.get("coord_unrecovered") != 0:
        raise RuntimeError(f"preemption soak had UNRECOVERED workers: "
                           f"{soak}")
    if not soak.get("emergency_within_grace"):
        raise RuntimeError("emergency checkpoint missed the grace budget "
                           f"(or never landed): {soak}")
    if not soak.get("zero_steps_lost"):
        raise RuntimeError("steps were lost beyond the preempted step: "
                           f"{soak}")
    if not soak.get("budget_untouched"):
        raise RuntimeError("planned leave consumed the restart budget: "
                           f"{soak}")
    if not soak.get("preempt_loss_bitwise") \
            or not soak.get("coord_loss_bitwise"):
        raise RuntimeError("post-resume trajectory diverged from the "
                           f"baseline: {soak}")
    if not soak.get("coord_ok"):
        raise RuntimeError(f"coordinator-kill recovery gate FAILED: {soak}")
    if not soak.get("straggler_flagged"):
        raise RuntimeError(f"straggler was never flagged: {soak}")
    if not soak.get("off_bitwise") or not soak.get("off_ok"):
        raise RuntimeError("chaos-off arm is no longer bit-identical to "
                           f"the pre-PR launcher configuration: {soak}")
    if soak.get("preempt_leaked", 1) != 0 or soak.get("off_leaked", 1) != 0 \
            or soak.get("coord_leaked", 1) != 0:
        raise RuntimeError(f"orphan worker survived the soak: {soak}")
    if not soak.get("soak_ok"):
        raise RuntimeError(f"preemption soak gate FAILED: {soak}")
    return {"metric": "preemption_recovery",
            "value": soak["planned_leaves"], "unit": "planned leaves",
            "platform": soak["platform"],
            "workers": soak["workers"],
            "grace_s": soak["grace_s"],
            "emergency_seconds": soak["emergency"]["seconds"],
            "emergency_stored_fallback": soak["emergency"]["stored"],
            "preempted_at_step": soak["preempted_at_step"],
            "resume_start_steps": soak["resume_start_steps"],
            "restart_budget_used": soak["restart_budget_used"],
            "coord_restarts": soak["coord_restarts"],
            "stragglers_flagged": len(soak["straggler_events"]),
            "zero_steps_lost": True, "off_bitwise": True,
            "preempt_loss_bitwise": True, "coord_loss_bitwise": True,
            "leaked": 0, "wall_seconds": soak["wall_seconds"]}


def bench_static_analysis():
    """Config 18: graftcheck clean gate (scripts/graftcheck.py; no
    accelerator — pure AST analysis).  HARD gate: the analyzer runs
    over the whole package with >= 12 rules across the four families
    (jit purity / determinism / thread safety / contracts) and reports
    ZERO unsuppressed findings; every suppression carries a
    justification (a justification-less pragma or baseline entry is
    itself a finding, so it cannot pass).  The bench trail thereby
    records the zero-findings state per round — a future PR that trips
    a rule shows up here as well as in tier-1
    (tests/test_static_analysis.py).  The reported value is the number
    of enforced rules."""
    import subprocess
    import sys

    script = os.path.join(_REPO, "scripts", "graftcheck.py")
    p = subprocess.run([sys.executable, script, "--format", "json"],
                       capture_output=True, text=True, timeout=600,
                       cwd=_REPO)
    if p.returncode not in (0, 1):
        raise RuntimeError(f"graftcheck crashed (rc={p.returncode}): "
                           f"{p.stderr[-1000:]}")
    report = json.loads(p.stdout)
    if not report["ok"] or report["summary"]["unsuppressed"] != 0:
        heads = [f"{f['path']}:{f['line']} {f['rule']} {f['message']}"
                 for f in report["findings"][:10]]
        raise RuntimeError(
            f"graftcheck gate FAILED: {report['summary']['unsuppressed']} "
            f"unsuppressed finding(s): " + "; ".join(heads))
    n_rules = len(report["rules"])
    if n_rules < 12:
        raise RuntimeError(f"rule catalog shrank below 12 ({n_rules}) — "
                           "the analyzer lost coverage")
    return {"metric": "static_analysis_clean", "value": n_rules,
            "unit": "rules enforced", "files": report["files"],
            "unsuppressed": 0,
            "suppressed": report["summary"]["suppressed"]}


def _kernel_ab(script: str, probe_program: Optional[str] = None) -> dict:
    """Run one kernel A/B script (and optionally the structural HLO
    probe for its pallas program) and return the parsed JSON line(s).

    These children run on whatever accelerator JAX finds — on a TPU host
    they need the chip, so the parent must not hold it: main() runs them
    before it initialises a backend, and this refuses otherwise."""
    import subprocess

    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"{script}: this process already initialised a JAX backend; on "
            "a TPU host it holds the chip and the child would hang or fail")
    cmd = [sys.executable, os.path.join(_REPO, "scripts", script)]
    if QUICK:
        cmd.append("--quick")
    p = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                       cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"{script} failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if probe_program is not None:
        probe = os.path.join(_REPO, "scripts", "ab_hlo_probe.py")
        q = subprocess.run([sys.executable, probe, _REPO, "bench",
                            probe_program],
                           capture_output=True, text=True, timeout=600,
                           cwd=_REPO)
        if q.returncode != 0:
            raise RuntimeError(
                f"structural probe {probe_program} FAILED: "
                f"{q.stdout.strip().splitlines()[-1:] or q.stderr[-800:]}")
        ab["structure"] = json.loads(q.stdout.strip().splitlines()[-1])
    return ab


def bench_fused_update_ab():
    """Config 19: the fused-update kernel and one-pass-encode A/Bs
    (scripts/fused_update_ab.py + scripts/one_pass_encode_ab.py,
    interpret-mode pallas arm on CPU).  HARD gates on EVERY platform —
    the correctness contract the kernels ride on:

      * fused update parity vs the per-leaf plain path: moments within
        2 ulp (one contractible FMA each; measured 0), params within
        1e-8 ABSOLUTE (the step's few-ulp FMA jitter at lr scale —
        measured ~1e-9; a ulp gate on the subtracted param output would
        reject bit-equivalent math wherever p - step cancels);
      * one-pass encode decode round-trips BIT-identical to the top_k
        path, with the selection sets equal;
      * structural landing (ab_hlo_probe): exactly one pallas_call in
        the fused update, no stray transposes/convert pairs, no sort.

    The SPEED gate (>=1.05x on the gated metric) binds on TPU only —
    interpret-mode pallas and XLA:CPU's scatter/top_k costs make CPU
    arm times meaningless for the TPU decision, and both paths stay
    opt-in (DL4J_TPU_FUSED_UPDATE / DL4J_TPU_FUSED_ENCODE) until a TPU
    round accepts them; the CPU numbers are still recorded, honestly
    labeled, as the protocol artifact.  The platform is the CHILD's
    report — this process has not touched a backend yet."""
    upd = _kernel_ab("fused_update_ab.py", probe_program="fused_update")
    on_tpu = upd["platform"] == "tpu"
    for k in ("parity_moments_max_ulp_jnp", "parity_moments_max_ulp_pallas"):
        if upd[k] > 2:
            raise RuntimeError(f"fused update moment-parity gate FAILED: "
                               f"{k}={upd[k]} ulp (allow <= 2): {upd}")
    for k in ("parity_params_max_abs_jnp", "parity_params_max_abs_pallas"):
        if upd[k] > 1e-8:
            raise RuntimeError(f"fused update param-parity gate FAILED: "
                               f"{k}={upd[k]} (allow <= 1e-8): {upd}")
    if on_tpu and upd["speedup_fused_pallas"] < 1.05:
        raise RuntimeError("fused update TPU speed gate FAILED "
                           f"(need >=1.05x): {upd}")

    enc = _kernel_ab("one_pass_encode_ab.py")
    if not (enc["roundtrip_bitwise_streaming"]
            and enc["selection_set_equal"]):
        raise RuntimeError(f"one-pass encode round-trip gate FAILED: {enc}")
    if on_tpu and enc["speedup_streaming"] < 1.05:
        raise RuntimeError("one-pass encode TPU speed gate FAILED "
                           f"(need >=1.05x): {enc}")

    return [{"metric": "fused_update_speedup",
             "value": upd["speedup_fused_pallas"],
             "unit": "x vs per-leaf (CPU-interpret arm)" if not on_tpu
                     else "x vs per-leaf",
             "plain_ms": upd["plain_ms"], "fused_jnp_ms": upd["fused_jnp_ms"],
             "fused_pallas_ms": upd["fused_pallas_ms"],
             "speedup_fused_jnp": upd["speedup_fused_jnp"],
             "parity_moments_max_ulp": max(
                 upd["parity_moments_max_ulp_jnp"],
                 upd["parity_moments_max_ulp_pallas"]),
             "parity_params_max_abs": max(
                 upd["parity_params_max_abs_jnp"],
                 upd["parity_params_max_abs_pallas"]),
             "n_params": upd["n_params"], "structure_ok": True,
             "platform": upd["platform"]},
            {"metric": "one_pass_encode_speedup",
             "value": enc["speedup_streaming"],
             "unit": "x vs top_k (CPU arm)" if not on_tpu else "x vs top_k",
             "topk_ms": enc["topk_ms"], "streaming_ms": enc["streaming_ms"],
             "roundtrip_bitwise": True, "n": enc["n"], "k": enc["k"],
             "platform": enc["platform"]}]


def bench_quantized_serving_ab():
    """Config 20: int8 quantized serving A/B
    (scripts/quantized_serving_ab.py — the raw jitted forward, f32 vs
    calibrated int8, interleaved windows).  HARD gates on EVERY
    platform — the numerics envelope that makes the fast path safe to
    offer at all: top-1 agreement >= 0.98 and max relative logit
    divergence <= 0.05 between the arms on identical inputs.  The
    SPEED gate (int8 >= 1.2x f32) binds on TPU only: XLA:CPU has no
    int8 matmul fast path (it widens to i32 scalar loops), so the CPU
    ratio measures the wrong backend; the serving contract itself
    (zero serve-time compiles under Engine.load(quantize="int8")) is
    enforced in tier-1 (tests/test_quantize.py)."""
    ab = _kernel_ab("quantized_serving_ab.py")
    on_tpu = ab["platform"] == "tpu"
    if ab["top1_agree"] < 0.98:
        raise RuntimeError("int8 top-1 agreement gate FAILED "
                           f"(need >=0.98): {ab}")
    if ab["max_rel_logit_diff"] > 0.05:
        raise RuntimeError("int8 logit-divergence gate FAILED "
                           f"(need <=0.05): {ab}")
    if on_tpu and ab["speedup_int8"] < 1.2:
        raise RuntimeError("int8 TPU speed gate FAILED (need >=1.2x): "
                           f"{ab}")
    return {"metric": "quantized_serving_speedup",
            "value": ab["speedup_int8"],
            "unit": "x vs f32 (CPU arm)" if not on_tpu else "x vs f32",
            "f32_ms": ab["f32_ms"], "int8_ms": ab["int8_ms"],
            "f32_qps": ab["f32_qps"], "int8_qps": ab["int8_qps"],
            "top1_agree": ab["top1_agree"],
            "max_rel_logit_diff": ab["max_rel_logit_diff"],
            "batch": ab["batch"], "hidden": ab["hidden"],
            "platform": ab["platform"]}


def bench_continuous_batching():
    """Config 21: autoregressive decode A/B (scripts/decode_ab.py; CPU
    subprocess — the continuous-batching logic under test is host-side).
    Static-batch full-re-encode decoding vs serving.DecodeEngine (paged
    KV-cache + bucketed prefill + iteration-level joins) on the SAME
    open-loop prompt schedule.  HARD gates on EVERY platform — the
    correctness contract that makes the cache safe to offer at all:
    temperature-0 per-token logits BITWISE identical to re-encoding,
    greedy tokens identical across arms, zero serve-time compiles, and
    zero stranded futures when a mid-flight decode batch crashes.  The
    SPEED gates (tokens/sec >= baseline, p99 TTFT <= baseline) bind on
    TPU only, where device time dominates; they are reported here too."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "decode_ab.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"decode_ab failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if not ab.get("bit_identical"):
        raise RuntimeError("decode bit-identity gate FAILED (paged-cache "
                           f"logits must match re-encode bitwise): {ab}")
    if not ab.get("tokens_match"):
        raise RuntimeError("decode token-parity gate FAILED (greedy tokens "
                           f"must agree across arms): {ab}")
    if not ab.get("zero_compiles"):
        raise RuntimeError("decode AOT gate FAILED (a request paid a "
                           f"serve-time compile): {ab}")
    if ab.get("stranded"):
        raise RuntimeError("decode resilience gate FAILED (futures stranded "
                           f"after a decode-batch crash): {ab}")
    if ab.get("speed_gated"):
        if not ab.get("tokens_ok"):
            raise RuntimeError("decode throughput gate FAILED (engine must "
                               f"be >= 1.0x static baseline on TPU): {ab}")
        if not ab.get("ttft_ok"):
            raise RuntimeError("decode TTFT gate FAILED (engine p99 TTFT "
                               f"must be <= baseline on TPU): {ab}")
    return {"metric": "decode_tokens_per_sec",
            "value": ab["engine"]["tokens_per_sec"],
            "unit": "tokens/sec (cpu)" if ab["platform"] != "tpu"
            else "tokens/sec",
            "platform": ab["platform"], "n_requests": ab["n_requests"],
            "tokens_ratio_engine_vs_baseline":
                ab["tokens_ratio_engine_vs_baseline"],
            "ttft_p99_ms": {"baseline": ab["baseline"]["ttft_p99_ms"],
                            "engine": ab["engine"]["ttft_p99_ms"]},
            "bit_identical": True, "tokens_match": True,
            "zero_compiles": True, "stranded": 0,
            "crash_retries": ab["crash_retries"],
            "speed_gated": ab["speed_gated"]}


def bench_cold_start():
    """Config 22: zero-cold-start A/B (scripts/cold_start_ab.py; CPU
    subprocess — bundle serialization and the load controller are host-
    side).  Cold ``Engine.load()`` (XLA compiles every bucket) vs a
    fresh process-equivalent warm load from a warmup bundle
    (serialize_executable round-trip), plus an autoscale burst soak.
    HARD gates on EVERY platform: warm load >= 3x faster than cold,
    warm outputs BITWISE identical to cold, zero bundle misses, the
    compile-cache-size witness flat across serving in both arms, the
    burst soak scales up within budget / back down after idle with zero
    new compiles and zero stranded futures, and the persistent compile
    cache writes through (serving/warmcache.py)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "cold_start_ab.py")
    cmd = [sys.executable, script] + (["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"cold_start_ab failed (rc={p.returncode}): "
                           f"{p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    if not ab.get("speedup_ok"):
        raise RuntimeError("cold-start speedup gate FAILED (warm-from-"
                           f"bundle load must be >= 3x cold compile): {ab}")
    if not ab.get("bitwise_ok"):
        raise RuntimeError("cold-start bitwise gate FAILED (warm-arm "
                           f"outputs must match cold-arm bitwise): {ab}")
    if not ab.get("bundle_ok"):
        raise RuntimeError("cold-start bundle gate FAILED (warm arm must "
                           f"load with zero bundle misses): {ab}")
    if not ab.get("cache_flat_ok"):
        raise RuntimeError("cold-start AOT gate FAILED (compile_cache_size "
                           f"must stay flat while serving): {ab}")
    if not ab.get("autoscale_ok"):
        raise RuntimeError("autoscale soak gate FAILED (scale up in "
                           "budget, down after idle, zero compiles, zero "
                           f"stranded): {ab}")
    if not ab.get("compile_cache_ok"):
        raise RuntimeError("persistent compile cache gate FAILED (enabled "
                           f"cache dir must be populated): {ab}")
    return {"metric": "cold_start_load_speedup",
            "value": ab["load_speedup_warm_vs_cold"],
            "unit": "x (cpu)" if ab["platform"] != "tpu" else "x",
            "platform": ab["platform"],
            "cold_load_s": ab["cold"]["load_s"],
            "warm_load_s": ab["warm"]["load_s"],
            "bundle_bytes": ab["cold"]["bundle_bytes"],
            "scale_ups": ab["soak"]["scale_ups"],
            "scale_downs": ab["soak"]["scale_downs"],
            "burst_s": ab["soak"]["burst_s"],
            "bitwise_ok": True, "bundle_ok": True, "cache_flat_ok": True,
            "autoscale_ok": True, "compile_cache_ok": True}


def bench_decode_speed():
    """Config 23: decode-side speed offensive A/B (scripts/decode_ab.py
    --speed-suite; CPU subprocess — the sharing/acceptance/quantization
    logic under test is host-side + bitwise).  Three independently-gated
    arms, HARD gates on EVERY platform:
      prefix — shared-prefix p50 TTFT strictly below equal-length cold
        p50 (suffix-only prefill runs a smaller bucket, so the win is
        structural, not device-bound), prefix-hit logits BITWISE equal
        to the re-encode oracle, greedy tokens identical to the plain
        engine, hit counters advancing, zero serve-time compiles.
      spec — self-draft control accepts >= k tokens/step, an
        independent draft at temperature 0 is BITWISE identical to the
        plain engine with accepted tokens/step >= 1.0, and a crash
        injected mid-speculative-round strands nothing with retries
        reproducing the plain tokens.
      int8 — top-1 agreement vs the f32 oracle >= 0.80 (int8 changes
        bits by design, so it gets an accuracy envelope, never the
        identity gates) and f32/int8 pool bytes >= 2.0 (sessions at
        fixed HBM)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "decode_ab.py")
    cmd = [sys.executable, script, "--speed-suite"] + (
        ["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"decode_ab --speed-suite failed "
                           f"(rc={p.returncode}): {p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    pre, spc, i8 = ab["prefix"], ab["spec"], ab["int8"]
    if not pre.get("ok"):
        raise RuntimeError("prefix-cache gate FAILED (hit TTFT < cold, "
                           "bit-identity, token parity, hit counters, "
                           f"zero compiles): {pre}")
    if not spc.get("ok"):
        raise RuntimeError("speculative gate FAILED (temp-0 bit-identity, "
                           "accepted/step >= 1.0, self-draft >= k, crash "
                           f"strands nothing): {spc}")
    if not i8.get("ok"):
        raise RuntimeError("int8 KV gate FAILED (top1-agree >= 0.80 "
                           f"envelope, pool-bytes ratio >= 2.0): {i8}")
    if not ab.get("plain_zero_compiles"):
        raise RuntimeError("decode-speed AOT gate FAILED (plain control "
                           f"engine paid a serve-time compile): {ab}")
    return {"metric": "decode_ttft_hit_over_cold",
            "value": pre["ttft_hit_over_cold"],
            "unit": "ratio (cpu)" if ab["platform"] != "tpu" else "ratio",
            "platform": ab["platform"],
            "ttft_cold_p50_ms": pre["ttft_cold_p50_ms"],
            "ttft_hit_p50_ms": pre["ttft_hit_p50_ms"],
            "prefix_hits": pre["hits"],
            "prefix_hit_tokens": pre["hit_tokens"],
            "prefix_evictions": pre["evictions"],
            "spec_accept_per_step": spc["accept_per_step"],
            "spec_self_draft_accept_per_step":
                spc["self_draft_accept_per_step"],
            "spec_crash_retries": spc["crash_retries"],
            "int8_top1_agree": i8["top1_agree"],
            "int8_sessions_at_fixed_hbm": i8["sessions_at_fixed_hbm"],
            "bit_identical": True, "tokens_match": True,
            "zero_compiles": True, "stranded": 0}


def bench_fused_step():
    """Config 28: host-overhead elimination A/B (scripts/decode_ab.py
    --host-overhead; CPU subprocess — the horizon-fusion and chunking
    logic under test is host-side + bitwise).  HARD gates on EVERY
    platform:
      fused — at every H in {2, 4, 8}: temp-0 tokens identical to the
        plain engine with echoed logits BITWISE equal to the re-encode
        oracle, seeded temp>0 tokens identical (counter-based RNG keying
        is horizon-invariant), a crash injected mid-horizon strands
        nothing and retries reproduce identical bits, zero serve-time
        compiles with the fused executable round-tripping through the
        warmup bundle (bundle_misses == 0).
      speed — batch-1 closed-loop tokens/sec strictly above the
        plain-step engine (H-for-1 host dispatch amortization is
        platform-independent, so this gate holds everywhere).
      chunked — a long-prompt wall landing on a unified engine holds
        the in-flight streams' inter-step TPOT p99 <= 1.2x calm, while
        the same wall on monolithic prefill measurably degrades it.
    On failure the subprocess dumps its trace ring as a Chrome trace
    artifact (path surfaced in the error)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(_REPO, "scripts", "decode_ab.py")
    cmd = [sys.executable, script, "--host-overhead"] + (
        ["--quick"] if QUICK else [])
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1800, cwd=_REPO)
    if p.returncode != 0:
        raise RuntimeError(f"decode_ab --host-overhead failed "
                           f"(rc={p.returncode}): {p.stderr[-1500:]}")
    ab = json.loads(p.stdout.strip().splitlines()[-1])
    art = ab.get("trace_artifact")
    suffix = f" [trace artifact: {art}]" if art else ""
    for H, arm in ab["fused"].items():
        if not arm.get("ok"):
            raise RuntimeError(
                f"fused-decode gate FAILED at H={H} (temp-0 bit-identity, "
                "seeded identity, crash-mid-horizon retry, bundle "
                f"round-trip, zero compiles): {arm}{suffix}")
    spd = ab["speed"]
    if not spd.get("ok"):
        raise RuntimeError("fused-decode speed gate FAILED (batch-1 "
                           "tokens/sec must beat the plain-step engine "
                           f"on every platform): {spd}{suffix}")
    chk = ab["chunked"]
    if not chk.get("ok"):
        raise RuntimeError("chunked-prefill gate FAILED (wall TPOT p99 "
                           "<= 1.2x calm, plain degrades, token parity, "
                           f"chunk counters, zero compiles): {chk}{suffix}")
    return {"metric": "fused_step_speedup", "value": spd["speedup"],
            "unit": "ratio (cpu)" if ab["platform"] != "tpu" else "ratio",
            "platform": ab["platform"],
            "plain_tokens_per_sec": spd["plain_tokens_per_sec"],
            "fused_tokens_per_sec": spd["fused_tokens_per_sec"],
            "tokens_per_dispatch": spd["tokens_per_dispatch"],
            "chunk_tpot_wall_over_calm": chk["tpot_wall_over_calm"],
            "plain_tpot_wall_over_calm":
                chk["plain_tpot_wall_over_calm"],
            "prefill_chunks": chk["prefill_chunks"],
            "bit_identical": True, "tokens_match": True,
            "zero_compiles": True, "stranded": 0}


def _configs(platform: str):
    """[(name, fn)] in run order.  The first two spawn children that need
    the accelerator, so they come before anything here touches JAX."""
    return [("fused_update_ab", bench_fused_update_ab),
            ("quantized_serving_ab", bench_quantized_serving_ab),
            ("mlp_mnist", bench_mlp_mnist),
            ("lenet_cifar10", bench_lenet_cifar),
            ("resnet50", lambda: bench_resnet50(platform())),
            ("word2vec_lstm", bench_word2vec_lstm),
            ("sharded_resnet50", lambda: bench_sharded_resnet(platform())),
            ("flash_attention", lambda: bench_flash_attention(platform())),
            ("transformer_lm", lambda: bench_transformer_lm(platform())),
            ("collective", bench_collective),
            ("pipeline_schedules", bench_pipeline_schedules),
            ("grad_compression", bench_grad_compression),
            ("chaos_recovery", bench_chaos_recovery),
            ("multihost_chaos_recovery", bench_multihost_chaos),
            ("preemption_recovery", bench_preemption),
            ("serving_throughput", bench_serving),
            ("serving_chaos_recovery", bench_serving_chaos),
            ("fleet_load_chaos", bench_fleet_load),
            ("input_pipeline_overlap", bench_input_pipeline),
            ("telemetry_overhead", bench_telemetry_overhead),
            ("static_analysis_clean", bench_static_analysis),
            ("continuous_batching_ab", bench_continuous_batching),
            ("cold_start_ab", bench_cold_start),
            ("decode_speed_ab", bench_decode_speed),
            ("fused_step_ab", bench_fused_step),
            ("disagg_decode_ab", bench_disagg_decode),
            ("train_promote_loop", bench_train_promote),
            ("multitenant_soak", bench_multitenant)]


def main() -> int:
    import jax

    from deeplearning4j_tpu.serving.warmcache import enable_compile_cache

    cache_dir = enable_compile_cache()     # config only, no backend yet
    log(f"bench: quick={QUICK} window={STEPS} compile_cache={cache_dir}")

    def platform() -> str:   # first call initialises the backend
        return jax.devices()[0].platform

    results, failed = [], []
    primary = None
    for name, fn in _configs(platform):
        try:
            t0 = time.perf_counter()
            out = fn()
            outs = out if isinstance(out, list) else [out]
            results.extend(outs)
            if name == "resnet50":
                primary = outs[0]
            for o in outs:
                log(f"  {o['metric']}: {o['value']} {o['unit']} "
                    f"({time.perf_counter() - t0:.1f}s)")
        except Exception as e:  # the other configs still run; the exit
            # code below reports the failure
            log(f"  {name} FAILED: {type(e).__name__}: {e}")
            results.append({"metric": name, "error": f"{type(e).__name__}: {e}"})
            failed.append(name)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    log(f"bench: device={device}")
    with open(os.path.join(_REPO, "bench_results.json"), "w") as f:
        json.dump({"device": device, "quick": QUICK, "failed": failed,
                   "results": results}, f, indent=2)
    # one stdout JSON line: the primary metric (absent if its config
    # failed — never a made-up 0.0) carrying every config's result
    # (copies: primary is itself one of the results — a cycle otherwise)
    line = dict(primary) if primary is not None else {}
    line.update(device=device, failed=failed,
                results=[dict(r) for r in results])
    print(json.dumps(line))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
