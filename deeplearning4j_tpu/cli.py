"""Command-line interface: train / evaluate / predict / summary.

Parity target: the reference ecosystem's CLI umbrella
(deeplearning4j-cli-parent — train/eval entry points over serialized
configs).  Usage:

    python -m deeplearning4j_tpu train --zoo lenet --data mnist \\
        --epochs 2 --batch-size 128 --output model.zip --dashboard out.html
    python -m deeplearning4j_tpu train --zoo lenet --data mnist \\
        --mesh data=4,model=2 ...   # sharded (ParallelWrapperMain role)
    python -m deeplearning4j_tpu train --config conf.json --data data.npz ...
    python -m deeplearning4j_tpu evaluate --model model.zip --data mnist
    python -m deeplearning4j_tpu predict --model model.zip --input x.npz \\
        --output preds.npz
    python -m deeplearning4j_tpu serve --model model.zip --max-batch 32 \\
        --slo-ms 50 --replicas -1 --admission shed --port 9000
    python -m deeplearning4j_tpu generate --model lm.zip \\
        --prompt "the " --max-tokens 64 --temperature 0.8 --seed 7
    python -m deeplearning4j_tpu launch --nprocs 2 --devices-per-proc 4 \\
        -- train --zoo lenet --data mnist --elastic-dir ckpts
    python -m deeplearning4j_tpu summary --model model.zip
    python -m deeplearning4j_tpu flywheel --generations 3 \\
        --eval-threshold 3.0 --canary 1.0 --chaos nan,regression

``--data`` accepts a built-in name (mnist / cifar10 / iris / emnist /
svhn / uci) or a .npz file with arrays ``x`` and ``y`` (one-hot or class
indices).  Configs are the framework's JSON (MultiLayerConfiguration
to_dict format).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Tuple

import numpy as np


def _num_classes_of(net) -> Optional[int]:
    """Model's output width, so index labels one-hot to the RIGHT width
    even when a split doesn't contain the highest class."""
    layers = getattr(net.conf, "layers", None)
    if layers:
        return getattr(layers[-1], "n_out", None) or None
    specs = getattr(net.conf, "vertices", None)
    if specs:
        by_name = {s.name: s for s in specs}
        out = by_name.get(net.conf.network_outputs[0])
        layer = getattr(getattr(out, "vertex", None), "layer", None)
        return getattr(layer, "n_out", None) or None
    return None


def _load_data(spec: str, train: bool = True,
               num_classes: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    from .datasets import fetchers

    builtin = {
        "mnist": lambda: fetchers.load_mnist(train=train),
        "cifar10": lambda: fetchers.load_cifar10(train=train),
        "iris": lambda: fetchers.load_iris(),
        "emnist": lambda: fetchers.load_emnist(train=train),
        "svhn": lambda: fetchers.load_svhn(train=train),
        "uci": lambda: fetchers.load_uci_synthetic_control(train=train),
    }
    if spec in builtin:
        xs, ys = builtin[spec]()
    else:
        data = np.load(spec)
        if "x" not in data or "y" not in data:
            raise SystemExit(f"{spec}: .npz must contain arrays 'x' and 'y'")
        xs, ys = data["x"], data["y"]
    if ys.ndim == 1:  # class indices → one-hot
        width = num_classes or int(ys.max()) + 1
        if int(ys.max()) >= width:
            raise SystemExit(f"label {int(ys.max())} out of range for "
                             f"{width} classes")
        ys = np.eye(width, dtype=np.float32)[ys.astype(np.int64)]
    return np.asarray(xs, np.float32), np.asarray(ys, np.float32)


def _build_model(args):
    if args.zoo:
        from .models import ZOO

        name = args.zoo.lower()
        if name not in ZOO:
            raise SystemExit(f"unknown zoo model '{args.zoo}' — one of "
                             f"{sorted(ZOO)}")
        kw = json.loads(args.zoo_args) if args.zoo_args else {}
        net = ZOO[name](**kw)
        if not getattr(net, "params", None):
            net.init()
        return net
    if args.config:
        from .nn.multilayer import MultiLayerConfiguration, MultiLayerNetwork

        with open(args.config) as f:
            conf = MultiLayerConfiguration.from_dict(json.load(f))
        net = MultiLayerNetwork(conf)
        net.init()
        return net
    raise SystemExit("pass --zoo NAME or --config conf.json")


def _load_model(path: str):
    from .utils.serializer import load_model

    return load_model(path)


def _parse_mesh(spec: str) -> tuple:
    """'data=4,model=2[,schedule=1f1b][,compress=threshold]' →
    ({"data": 4, "model": 2}, schedule, compress) (-1 = infer; schedule
    defaults to "gpipe", compress to None).  Resolves -1 against the
    visible device count and guarantees a 'data' axis (ShardedTrainer's
    batch sharding names it), so every failure mode here is a clean
    one-line CLI error, not a jax traceback.  The ``schedule`` token
    picks the pipeline microbatch order for nets that pipeline over a
    ``pipe`` axis (parallel/pipeline.py); the ``compress`` token enables
    the DCN-tier compressed gradient exchange for meshes with a ``dcn``
    axis (ops/compression.py)."""
    from .ops.compression import METHODS
    from .parallel.pipeline import SCHEDULES

    axes = {}
    schedule = "gpipe"
    compress = None
    seen_schedule = False
    for part in spec.split(","):
        name, _, size = part.partition("=")
        name = name.strip()
        if name == "schedule":
            if seen_schedule:
                raise SystemExit(
                    f"bad --mesh {spec!r}: duplicate schedule token")
            if size.strip() not in SCHEDULES:
                raise SystemExit(
                    f"bad --mesh {spec!r}: schedule must be one of "
                    f"{'/'.join(SCHEDULES)}, got {size.strip()!r}")
            schedule = size.strip()
            seen_schedule = True
            continue
        if name == "compress":
            if compress is not None:
                raise SystemExit(
                    f"bad --mesh {spec!r}: duplicate compress token")
            if size.strip() not in METHODS:
                raise SystemExit(
                    f"bad --mesh {spec!r}: compress must be one of "
                    f"{'/'.join(METHODS)}, got {size.strip()!r}")
            compress = size.strip()
            continue
        if name in axes:
            raise SystemExit(f"bad --mesh {spec!r}: duplicate axis {name!r}")
        try:
            axes[name] = int(size)
        except ValueError:
            size = ""  # fall through to the shared message
        if not name or not size or axes.get(name) == 0 or (
                axes.get(name, 0) < -1):
            raise SystemExit(
                f"bad --mesh {spec!r}: expected name=size[,name=size...] "
                "with positive integer sizes (or one -1 to infer), "
                "e.g. 'data=8', 'data=4,model=2' or "
                "'data=2,pipe=4,schedule=1f1b'")
    axes.setdefault("data", 1)
    if list(axes.values()).count(-1) > 1:
        raise SystemExit(f"bad --mesh {spec!r}: at most one -1 (infer) axis")
    if -1 in axes.values():
        import jax

        known = 1
        for s in axes.values():
            if s != -1:
                known *= s
        n = jax.device_count()
        if known == 0 or n % known:
            raise SystemExit(f"bad --mesh {spec!r}: cannot infer -1 axis "
                             f"from {n} device(s)")
        axes = {k: (n // known if s == -1 else s) for k, s in axes.items()}
    if compress is not None and "dcn" not in axes:
        raise SystemExit(f"bad --mesh {spec!r}: compress={compress} needs a "
                         "dcn axis, e.g. 'dcn=2,data=4,compress=threshold'")
    return axes, schedule, compress


def _parse_prefetch(spec: str):
    """'DEPTH[,DEVICE]' → (depth, device_spec|None).  depth 0 = the
    synchronous path (bitwise-unchanged pre-prefetch behavior); DEVICE is
    'platform[:index]' or a bare device index.  Every parse failure is a
    one-line CLI error, not a traceback."""
    depth_s, _, dev = spec.partition(",")
    try:
        depth = int(depth_s)
        if depth < 0:
            raise ValueError
    except ValueError:
        raise SystemExit(f"bad --prefetch {spec!r}: expected "
                         "DEPTH[,DEVICE] with DEPTH >= 0, e.g. '2' or "
                         "'2,tpu:0' (0 = synchronous feeding)")
    dev = dev.strip() or None
    if depth == 0 and dev:
        raise SystemExit(f"bad --prefetch {spec!r}: a device makes no "
                         "sense with depth 0 (synchronous feeding)")
    return depth, dev


def _resolve_device(spec: str):
    """'tpu:0' / 'cpu' / '1' → a jax.Device (clean CLI errors)."""
    import jax

    try:
        if spec.isdigit():
            return jax.devices()[int(spec)]
        plat, _, idx = spec.partition(":")
        return jax.devices(plat)[int(idx) if idx else 0]
    except (RuntimeError, IndexError, ValueError) as e:
        raise SystemExit(f"bad --prefetch device {spec!r}: {e}")


def _parse_chaos(spec: str):
    """'kind@step[,kind@step...][,seed=S][,hang=SECONDS][,slow=SECONDS]' →
    (FaultSchedule, seed, hang_seconds, slow_seconds).  Fault kinds are
    the parallel/chaos.py FaultKind names (device_loss, ckpt_write_crash,
    ckpt_truncate, ckpt_bitflip, hung_step, nan_grads, proc_kill,
    proc_hang, preempt_notice, coord_kill, slow_worker); ``slow=`` is the
    per-step drag a scheduled slow_worker adds (default: the hang
    seconds); every parse failure is a one-line CLI error, not a
    traceback."""
    from .parallel.chaos import FaultKind, FaultSchedule

    faults: dict = {}
    seed, hang, slow = 0, 5.0, None
    for part in spec.split(","):
        part = part.strip()
        if "=" in part and "@" not in part:
            key, _, val = part.partition("=")
            try:
                if key == "seed":
                    seed = int(val)
                elif key == "hang":
                    hang = float(val)
                elif key == "slow":
                    slow = float(val)
                else:
                    raise SystemExit(f"bad --chaos {spec!r}: unknown option "
                                     f"{key!r} (seed=, hang=, slow=)")
            except ValueError:
                raise SystemExit(f"bad --chaos {spec!r}: {key}= needs a "
                                 "number")
            continue
        kind, _, step = part.partition("@")
        if kind not in FaultKind.ALL:
            raise SystemExit(f"bad --chaos {spec!r}: unknown fault kind "
                             f"{kind!r} — one of {'/'.join(FaultKind.ALL)}")
        try:
            step_i = int(step)
            if step_i < 1:
                raise ValueError
        except ValueError:
            raise SystemExit(f"bad --chaos {spec!r}: {kind} needs a positive "
                             f"step, e.g. '{kind}@5'")
        faults.setdefault(step_i, []).append(kind)
    if not faults:
        raise SystemExit(f"bad --chaos {spec!r}: no faults — expected "
                         "kind@step[,kind@step...], e.g. "
                         "'device_loss@5,nan_grads@9,seed=1'")
    return FaultSchedule(faults), seed, hang, slow


def _setup_trace(args):
    """Arm span tracing (docs/OBSERVABILITY.md) from ``--trace PATH`` or
    the launcher's ``DL4J_TPU_TRACE_DIR`` env contract (each worker
    incarnation writes its own ``worker{i}.inc{j}.trace.json``, which
    ``launch --trace`` merges into one pod timeline).  Returns the armed
    output path, or None when tracing stays off."""
    import os

    from .parallel.distributed import (
        ENV_INCARNATION, ENV_TRACE_DIR, resolve_process_index,
    )
    path = getattr(args, "trace", None)
    if path:
        path = path.replace("{process}", str(resolve_process_index()))
    else:
        trace_dir = os.environ.get(ENV_TRACE_DIR)
        if trace_dir:
            inc = os.environ.get(ENV_INCARNATION, "0")
            path = os.path.join(
                trace_dir,
                f"worker{resolve_process_index()}.inc{inc}.trace.json")
    if not path:
        return None
    from .obs import trace as obs_trace
    obs_trace.enable_tracing(path=path)
    return path


def _flush_trace(trace_path) -> None:
    if not trace_path:
        return
    from .obs import trace as obs_trace
    written = obs_trace.flush()
    if written:
        print(f"trace: {written} (chrome://tracing / ui.perfetto.dev)")


def cmd_train(args) -> int:
    from .datasets import DataSet, ListDataSetIterator
    from .optimize import ScoreIterationListener
    from .parallel.launcher import Heartbeat, maybe_bootstrap_from_env

    # under `launch`: join the jax.distributed cluster when the launcher
    # exported a coordinator (bounded timeout — a dead coordinator is a
    # CoordinatorUnreachableError, not a hang), and beat the shared
    # membership so the launcher can tell wedged from working
    if maybe_bootstrap_from_env():
        from .parallel import distributed
        print(f"distributed: process {distributed.process_index()}/"
              f"{distributed.process_count()}")
    heartbeat = Heartbeat.start_from_env()
    trace_path = _setup_trace(args)
    from .serving.warmcache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    net = _build_model(args)
    xs, ys = _load_data(args.data, train=True, num_classes=_num_classes_of(net))
    batches = DataSet(xs, ys).shuffle(args.seed).batch_by(args.batch_size)
    mesh_axes, schedule, compress = (_parse_mesh(args.mesh) if args.mesh
                                     else (None, "gpipe", None))
    if mesh_axes:
        # XLA needs static shapes divisible by the data axis — drop the
        # ragged tail batch instead of erroring mid-epoch
        dp = mesh_axes["data"] * mesh_axes.get("dcn", 1)
        if args.batch_size % dp:
            raise SystemExit(f"--batch-size {args.batch_size} not divisible "
                             f"by mesh data axis {dp}")
        full = [b for b in batches if len(b.features) == args.batch_size]
        dropped = len(xs) - len(full) * args.batch_size
        if not full:
            raise SystemExit(
                f"dataset ({len(xs)} samples) has no full batch of "
                f"{args.batch_size}; lower --batch-size for --mesh training")
        if dropped:
            print(f"mesh training drops the ragged tail: {dropped} of "
                  f"{len(xs)} samples not in a full batch of "
                  f"{args.batch_size}")
        batches = full
    it = ListDataSetIterator(batches)
    listeners = [ScoreIterationListener(args.print_every)]
    storage = None
    if args.dashboard:
        from .ui import InMemoryStatsStorage, StatsListener

        storage = InMemoryStatsStorage()
        listeners.append(StatsListener(storage, session_id="cli_train"))
    net.set_listeners(*listeners)
    # the launcher injects per-worker chaos via env (cleared on relaunch,
    # so a scheduled proc_kill fires once per run, not per incarnation)
    import os as _os

    from .parallel.distributed import ENV_CHAOS
    chaos_spec = args.chaos or _os.environ.get(ENV_CHAOS) or None
    if chaos_spec and not args.elastic_dir:
        raise SystemExit("--chaos needs --elastic-dir (faults are injected "
                         "into the ElasticTrainer recovery loop)")
    trainer = None
    if mesh_axes:
        # the reference's ParallelWrapperMain role (parallelism/main/
        # ParallelWrapperMain.java: CLI multi-device training): place the
        # model on a named mesh, train through the sharded step
        import jax

        from .parallel import ShardedTrainer, build_mesh

        total = 1
        for s in mesh_axes.values():
            total *= s
        if total > jax.device_count():
            raise SystemExit(f"--mesh {args.mesh!r} needs {total} device(s), "
                             f"found {jax.device_count()}")
        mesh = build_mesh(mesh_axes, devices=jax.devices()[:total])
        trainer = ShardedTrainer(net, mesh, pipeline_schedule=schedule,
                                 grad_compression=compress,
                                 nan_guard=args.nan_guard)
        print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} device(s)"
              + (f", pipeline schedule {schedule}" if schedule != "gpipe"
                 else "")
              + (f", grad compression {compress}" if compress else "")
              + (f", nan guard budget {args.nan_guard}" if args.nan_guard
                 else ""))
    elif args.nan_guard is not None:
        if not hasattr(net, "set_nan_guard"):
            raise SystemExit(f"--nan-guard is not supported for "
                             f"{type(net).__name__} yet")
        net.set_nan_guard(args.nan_guard)
        print(f"nan guard armed (budget {args.nan_guard})")
    prefetcher = None
    if args.prefetch:
        depth, dev_spec = _parse_prefetch(args.prefetch)
        if depth > 0:
            # device-resident input pipeline (docs/INPUT_PIPELINE.md):
            # batches cross host→device from a background thread, landing
            # pre-sharded on a mesh run (the trainer's batch placement
            # then passes them through untouched)
            from .datasets.device_prefetch import DevicePrefetchIterator

            if mesh_axes and dev_spec:
                raise SystemExit("--prefetch DEVICE does not combine with "
                                 "--mesh (batches land on the mesh's batch "
                                 "sharding)")
            sharding = trainer.batch_sharding if trainer is not None else None
            device = _resolve_device(dev_spec) if dev_spec else None
            prefetcher = it = DevicePrefetchIterator(
                it, depth=depth, sharding=sharding, device=device)
            where = ("mesh batch sharding" if sharding is not None else
                     str(device) if device is not None else "default device")
            print(f"prefetch: depth {depth} onto {where}")
    if args.elastic_dir:
        # checkpoint-restore recovery (the reference CheckpointListener +
        # Spark task-retry role; docs/FAULT_TOLERANCE.md) — with --chaos,
        # scripted faults are injected INSIDE the recovery loop, a
        # self-test that the stack rides out the scheduled failures
        from .parallel import ChaosInjector, ElasticTrainer

        class _Plain:
            def __init__(self, n):
                self.net = n

            def fit_batch(self, ds):
                return self.net.fit_batch(ds)

        inner = trainer if trainer is not None else _Plain(net)
        injector = None
        if chaos_spec:
            sched, seed, hang, slow = _parse_chaos(chaos_spec)
            injector = inner = ChaosInjector(inner, sched,
                                             hang_seconds=hang, seed=seed,
                                             slow_seconds=slow)
            print(f"chaos armed: {sched.pending()} fault(s) scheduled")
        # announced failures (docs/FAULT_TOLERANCE.md): SIGTERM/SIGUSR1 is
        # a preemption notice — grace-window emergency checkpoint at the
        # next step boundary, then a distinct PREEMPTED exit so the
        # launcher relaunches without burning the restart budget
        from .parallel.preemption import PreemptionHandler
        preemption = PreemptionHandler.install_from_env(grace_s=args.grace)
        trainer = ElasticTrainer(
            inner, args.elastic_dir, checkpoint_every=args.checkpoint_every,
            sync_every=min(10, args.checkpoint_every),
            step_timeout=args.step_timeout, backoff_base=0.5, jitter_seed=0,
            preemption=preemption)
        if injector is not None:
            injector.attach_checkpoints(trainer.ckpt)
        if heartbeat is not None:
            heartbeat.set_step_fn(lambda: trainer.global_step)
            heartbeat.set_ckpt_step_fn(lambda: trainer.last_checkpoint_step)
        # host (re)join: a relaunched worker resumes from the cluster's
        # newest checkpoint instead of step 0
        resumed = trainer.resume()
        if resumed:
            print(f"resumed from checkpoint @ step {resumed}")
    from .parallel.preemption import PreemptedError
    try:
        losses = (trainer.fit(it, epochs=args.epochs) if trainer
                  else net.fit(it, epochs=args.epochs))
    except PreemptedError as exc:
        print(f"preempted: {exc}")
        _flush_trace(trace_path)
        if heartbeat is not None:
            heartbeat.stop()
        return exc.exit_code
    if args.elastic_dir:
        et = trainer
        print(f"elastic: {et.total_restarts} recovery(ies), "
              f"{et.recovery_seconds:.1f}s in recovery, final checkpoint @ "
              f"step {et.global_step} in {args.elastic_dir}")
    print(f"trained {args.epochs} epoch(s), {len(losses)} iterations, "
          f"final loss {losses[-1]:.5f}")
    if prefetcher is not None:
        s = prefetcher.stall_stats()
        print(f"prefetch: stall fraction {s['stall_fraction']:.3f} "
              f"({s['stalls']} stall(s), avg {s['avg_stall_ms']:.1f}ms) over "
              f"{s['batches']} batches, depth {s['depth']}")
    if args.dashboard:
        from .ui import render_dashboard

        render_dashboard(storage, args.dashboard)
        print(f"dashboard: {args.dashboard}")
    if args.output:
        from .parallel.distributed import resolve_process_index
        out_path = args.output.replace("{process}",
                                       str(resolve_process_index()))
        net.save(out_path)
        print(f"saved: {out_path}")
    _flush_trace(trace_path)
    if heartbeat is not None:
        heartbeat.stop()
    return 0


def cmd_evaluate(args) -> int:
    net = _load_model(args.model)
    xs, ys = _load_data(args.data, train=False,
                        num_classes=_num_classes_of(net))
    ev = net.evaluate((xs, ys))
    print(ev.stats() if hasattr(ev, "stats") else
          f"accuracy: {ev.accuracy():.4f}")
    return 0


def cmd_predict(args) -> int:
    net = _load_model(args.model)
    data = np.load(args.input)
    x = data["x"] if "x" in data else data[data.files[0]]
    out = net.output(np.asarray(x, np.float32))
    out = out[0] if isinstance(out, list) else out
    np.savez(args.output, predictions=out)
    print(f"wrote {out.shape} predictions to {args.output}")
    return 0


def _parse_tenants(path):
    """tenants.json → serving.TenantTable (docs/SERVING.md "Multi-tenant
    serving").  The file is a JSON list of tenant rows (or an object
    with a "tenants" list), each row the TenantConfig dict shape:
    {"tenant": "acme", "model": null, "slo_ms": 50, "weight": 2.0,
    "quota_qps": 100, "quota_concurrent": 8, "admission": "shed"}.
    Every parse or validation failure is a one-line CLI error, not a
    traceback."""
    from .serving import TenantTable

    try:
        with open(path) as f:
            rows = json.load(f)
    except OSError as e:
        raise SystemExit(f"bad --tenants {path!r}: {e}")
    except json.JSONDecodeError as e:
        raise SystemExit(f"bad --tenants {path!r}: invalid JSON ({e})")
    if isinstance(rows, dict):
        rows = rows.get("tenants", rows)
    if not isinstance(rows, list) or not rows or not all(
            isinstance(r, dict) for r in rows):
        raise SystemExit(f"bad --tenants {path!r}: expected a non-empty "
                         "JSON list of tenant rows (or "
                         '{"tenants": [...]})')
    try:
        return TenantTable.from_specs(rows)
    except (TypeError, ValueError) as e:
        raise SystemExit(f"bad --tenants {path!r}: {e}")


def _parse_models(spec):
    """'NAME=PATH[,NAME=PATH...]' (or bare checkpoint paths — the name
    is the file stem) → [(name, path)] with clean CLI errors."""
    import os

    out, seen = [], set()
    for part in [p.strip() for p in spec.split(",") if p.strip()]:
        name, sep, path = part.partition("=")
        if not sep:
            name = os.path.splitext(os.path.basename(part))[0]
            path = part
        if not name or not path:
            raise SystemExit(f"bad --models {spec!r}: expected "
                             "NAME=PATH[,NAME=PATH...] or a comma-"
                             "separated list of checkpoint paths")
        if name in seen:
            raise SystemExit(f"bad --models {spec!r}: duplicate model "
                             f"name {name!r}")
        seen.add(name)
        out.append((name, path))
    if not out:
        raise SystemExit(f"bad --models {spec!r}: no models")
    return out


def _serve_queue_depth(engine) -> int:
    """Pending work still inside a serving engine (or fleet router) —
    the drain loop below waits for this to reach zero."""
    batcher = getattr(engine, "batcher", None)
    if batcher is not None:
        return batcher.qsize()
    return int(engine.metrics_snapshot().get("queue_depth", 0))


def cmd_serve(args) -> int:
    """Production serving (docs/SERVING.md): load a checkpoint into the
    versioned registry, AOT-warm every shape bucket, and serve — over
    HTTP (POST /predict + GET /metrics on the UI server), as a --fleet
    router fronting remote serve hosts, or as a --smoke self-test that
    pushes synthetic requests through the engine and prints the metrics
    snapshot.

    A SIGTERM/SIGUSR1 preemption notice (docs/FAULT_TOLERANCE.md env
    contract) triggers a graceful drain: admission stops (new requests
    shed with HTTP 429), in-flight requests finish within the grace
    budget, and the process exits with ``PREEMPTED_EXIT_CODE`` so the
    pod launcher relaunches it without burning restart budget."""
    import os
    import time

    from .parallel.distributed import ENV_SERVE_PORT, PREEMPTED_EXIT_CODE
    from .parallel.launcher import Heartbeat
    from .parallel.preemption import PreemptionHandler
    from .serving import Engine, FleetRouter, HttpHost, ModelRegistry
    from .serving.warmcache import enable_compile_cache

    trace_path = _setup_trace(args)
    print(f"compile cache: {enable_compile_cache()}")
    if not args.fleet and not args.model and not getattr(
            args, "models", None):
        raise SystemExit("serve needs --model/--models "
                         "(or --fleet HOST:PORT,...)")
    tenants = (_parse_tenants(args.tenants)
               if getattr(args, "tenants", None) else None)
    net = None
    is_lm = False
    if args.fleet:
        if args.smoke:
            raise SystemExit("serve --smoke is incompatible with --fleet")
        if tenants is not None:
            raise SystemExit("--tenants configures a serve HOST's "
                             "admission — pass it to each `serve --model` "
                             "worker, not the --fleet router")
        engine = FleetRouter(
            max_retries=args.max_retries,
            request_timeout_s=args.forward_timeout,
            breaker_threshold=args.breaker_threshold)
        for ep in [e.strip() for e in args.fleet.split(",") if e.strip()]:
            url = ep if ep.startswith("http") else f"http://{ep}"
            engine.add_host(ep, engine=HttpHost(
                url, timeout_s=args.forward_timeout or 5.0))
        print(f"fleet router over {sorted(engine.hosts())}: "
              f"max_retries={args.max_retries}, "
              f"request_timeout={args.forward_timeout}")
    else:
        from .models.transformer import TransformerBlock
        model_pairs = (_parse_models(args.models)
                       if getattr(args, "models", None) else [])
        if args.model:
            model_pairs = [(args.name, args.model)] + model_pairs
        name, default_path = model_pairs[0]
        net = _load_model(default_path)
        is_lm = any(isinstance(l, TransformerBlock) for l in net.conf.layers)
        if is_lm:
            # a transformer LM has no float /predict surface (the predict
            # engine's warmup batches are float feature rows) — serve it
            # decode-only: POST /generate below, /predict answers 503
            if len(model_pairs) > 1:
                raise SystemExit("--models needs predict checkpoints "
                                 "(float feature inputs) — a transformer "
                                 "LM serves decode-only via --model")
            engine = None
        else:
            reg = ModelRegistry()
            version = reg.load(name, default_path, version=args.version)
            reg.set_alias(name, "prod", version)
            engine = Engine.from_registry(
                reg, name, "prod", max_batch=args.max_batch,
                slo_ms=args.slo_ms,
                replicas=args.replicas, max_queue=args.queue_cap,
                admission=args.admission,
                forward_timeout_s=args.forward_timeout,
                max_retries=args.max_retries,
                breaker_threshold=args.breaker_threshold,
                tenants=tenants)
            # an explicit --warm-bundle wins; otherwise the registry's
            # checkpoint provenance finds `<checkpoint>.warm` automatically
            engine.load(warm_bundle=getattr(args, "warm_bundle", None))
            # --models extras: registered + AOT-warmed alongside the
            # default, addressable via the request's "model" field
            for extra_name, extra_path in model_pairs[1:]:
                v = reg.load(extra_name, extra_path)
                reg.set_alias(extra_name, "prod", v)
                engine.add_model_from_registry(reg, extra_name, "prod")
            print(f"serving {name} v{version} (alias 'prod'): "
                  f"max_batch={args.max_batch}, slo={args.slo_ms}ms, "
                  f"replicas={len(engine._replicas)}, "
                  f"admission={args.admission}, "
                  f"warmed buckets {engine.batcher.buckets}")
            if len(model_pairs) > 1:
                print(f"models placed: {engine.placed_models()}")
    if tenants is not None:
        print(f"tenants: {sorted(tenants.tenants())} from {args.tenants}")
    if args.smoke:
        if engine is None:
            raise SystemExit("serve --smoke needs a predict checkpoint "
                             "(float feature inputs), not a transformer "
                             "LM — use POST /generate instead")
        shape = engine._example_shape
        rng = np.random.default_rng(0)
        futs = [engine.output_async(
            rng.normal(size=(1 + i % 4,) + shape).astype(np.float32))
            for i in range(args.smoke)]
        for f in futs:
            f.result(timeout=120)
        print(json.dumps(engine.metrics_snapshot()))
        engine.shutdown()
        _flush_trace(trace_path)
        return 0
    from .ui import UIServer

    # under the pod launcher each serving worker gets a stable port
    # assignment via the env contract; an explicit --port wins
    port = args.port
    if port == 9000 and os.environ.get(ENV_SERVE_PORT):
        port = int(os.environ[ENV_SERVE_PORT])
    server = UIServer(port=port, host=args.host)
    if engine is not None:
        server.attach_engine(engine)
    decode_eng = None
    wants_decode = (is_lm
                    or getattr(args, "prefix_cache", False)
                    or getattr(args, "speculate", None)
                    or getattr(args, "decode_role", "unified")
                    not in (None, "unified")
                    or getattr(args, "kv_dtype", None)
                    not in (None, "float32"))
    if wants_decode:
        # decode-speed flags attach a DecodeEngine for POST /generate
        # next to the predict engine (docs/SERVING.md "Decode-side
        # optimizations")
        if args.fleet:
            raise SystemExit("--prefix-cache/--speculate/--kv-dtype need "
                             "a local --model, not --fleet")
        from .models.transformer import (TransformerBlock,
                                         TransformerDecodeAdapter)
        from .serving import DecodeEngine
        if net is None:
            net = _load_model(args.model)
        if not any(isinstance(l, TransformerBlock)
                   for l in net.conf.layers):
            raise SystemExit("--prefix-cache/--speculate/--kv-dtype need "
                             "a transformer LM checkpoint")
        opts = _decode_opts(args)
        decode_eng = DecodeEngine(TransformerDecodeAdapter(net),
                                  tenants=tenants, **opts).load()
        server.attach_decode_engine(decode_eng)
        print(f"decode engine on POST /generate: "
              f"role={opts['role']}, "
              f"prefix_cache={opts['prefix_cache']}, "
              f"speculate_k={opts['speculate_k'] if opts['draft_model'] is not None else 0}, "
              f"kv_dtype={opts['kv_dtype'] or 'float32'}")
    server.start()
    heartbeat = Heartbeat.start_from_env()
    handler = PreemptionHandler.install_from_env()
    print(f"listening on http://{args.host}:{server.port} — "
          "POST /predict, GET /metrics, GET /healthz, GET /trace",
          flush=True)
    preempted = False
    try:
        while not handler.requested:
            time.sleep(0.2)
        preempted = True
        # graceful drain: shed new admissions, let in-flight requests
        # finish inside the grace window, then hand the port back
        for e in (engine, decode_eng):
            if e is not None:
                e.begin_drain()
        print(f"serve: preemption notice — draining "
              f"({handler.remaining_s:.1f}s grace)", flush=True)
        drain_of = engine if engine is not None else decode_eng
        while _serve_queue_depth(drain_of) > 0 and handler.remaining_s > 0.5:
            time.sleep(0.05)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        if engine is not None:
            engine.shutdown()
        if decode_eng is not None:
            decode_eng.shutdown()
        if heartbeat is not None:
            heartbeat.stop()
        handler.uninstall()
        _flush_trace(trace_path)
    return PREEMPTED_EXIT_CODE if preempted else 0


def _sample_probs(probs: np.ndarray, temperature: float, top_k: int,
                  top_p: float, rng: np.random.Generator) -> int:
    """Host-side sampling from a probability row (the char-RNN path —
    its output layer already applied softmax).  Same knob semantics as
    the decode engine: temperature<=0 greedy, top_k==0 / top_p>=1 off."""
    if temperature <= 0.0:
        return int(np.argmax(probs))
    p = np.asarray(probs, np.float64) ** (1.0 / max(temperature, 1e-6))
    if top_k and top_k < p.shape[0]:
        p[np.argsort(p)[:-top_k]] = 0.0
    if top_p < 1.0:
        order = np.argsort(p)[::-1]
        cum = np.cumsum(p[order]) / max(p.sum(), 1e-30)
        p[order[1:][cum[:-1] >= top_p]] = 0.0   # keep top-1 always
    p /= p.sum()
    return int(rng.choice(p.shape[0], p=p))


def _parse_speculate(spec):
    """'DRAFT_CKPT[,k]' → (path, k) with a clean CLI error — the
    --speculate argument of generate/serve."""
    if spec is None:
        return None, 4
    path, sep, ks = spec.rpartition(",")
    if sep and path:
        try:
            k = int(ks)
            if k < 1:
                raise ValueError
        except ValueError:
            raise SystemExit(f"bad --speculate {spec!r}: expected "
                             "DRAFT_CKPT[,k] with k >= 1")
        return path, k
    return spec, 4


def _decode_opts(args) -> dict:
    """DecodeEngine kwargs for the decode-speed flags shared by
    generate/serve: --prefix-cache, --speculate DRAFT_CKPT[,k],
    --kv-dtype int8 (docs/SERVING.md "Decode-side optimizations")."""
    from .models.transformer import TransformerDecodeAdapter

    draft_path, k = _parse_speculate(getattr(args, "speculate", None))
    draft = None
    if draft_path:
        draft = TransformerDecodeAdapter(_load_model(draft_path))
    kv = getattr(args, "kv_dtype", None)
    return {
        "prefix_cache": bool(getattr(args, "prefix_cache", False)),
        "draft_model": draft,
        "speculate_k": k,
        "kv_dtype": None if kv in (None, "float32") else kv,
        "role": getattr(args, "decode_role", None) or "unified",
    }


def cmd_generate(args) -> int:
    """Autoregressive text generation (docs/SERVING.md "Autoregressive
    decode").  Two model families, one CLI:

      transformer LM  — served through serving.DecodeEngine (paged
                        KV-cache, bucketed prefill, continuous
                        batching), models.TransformerDecodeAdapter
      recurrent nets  — the reference rnnTimeStep() streaming loop
                        (stateful hidden carry, one step per token)

    Text <-> token ids is byte-valued (ord/chr clamped to the model's
    vocab) — the char-LM convention of examples/10_textgen_decode.py.
    """
    net = _load_model(args.model)
    from .models.transformer import TransformerBlock

    is_transformer = any(isinstance(l, TransformerBlock)
                         for l in net.conf.layers)
    if is_transformer:
        from .models.transformer import TransformerDecodeAdapter
        from .serving import DecodeEngine

        adapter = TransformerDecodeAdapter(net)
        vocab = adapter.vocab_size
        pos_rows = int(adapter.params["pos"]["P"].shape[0])
        page = args.page_size
        while page > 1 and page > pos_rows // 2:
            page //= 2
        prompt_ids = [min(ord(c), vocab - 1) for c in args.prompt]
        if not prompt_ids:
            raise SystemExit("--prompt must be non-empty")
        eng = DecodeEngine(adapter, max_slots=1, page_size=page,
                           default_max_new=args.max_tokens,
                           **_decode_opts(args)).load()
        try:
            if len(prompt_ids) > eng.max_prompt:
                raise SystemExit(f"prompt longer than the warmed buckets "
                                 f"(max {eng.max_prompt} tokens)")
            res = eng.generate(prompt_ids, max_new_tokens=args.max_tokens,
                               temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               seed=args.seed)
            text = "".join(chr(t) if t < 0x110000 else "?"
                           for t in res.tokens)
            print(f"[decode engine: {len(res.tokens)} tokens, "
                  f"finish={res.finish_reason}, ttft={res.ttft_ms}ms, "
                  f"tpot={res.tpot_ms}ms]", file=sys.stderr)
            print(args.prompt + text)
        finally:
            eng.shutdown()
        return 0

    # recurrent path: reference rnnTimeStep() streaming
    if (getattr(args, "prefix_cache", False)
            or getattr(args, "speculate", None)
            or getattr(args, "kv_dtype", None) not in (None, "float32")):
        raise SystemExit(
            "--prefix-cache/--speculate/--kv-dtype need a transformer LM "
            "checkpoint (they live in the paged decode engine)")
    out_layer = net.conf.layers[-1]
    vocab = int(getattr(out_layer, "n_out", 256) or 256)
    prompt_ids = [min(ord(c), vocab - 1) for c in args.prompt]
    if not prompt_ids:
        raise SystemExit("--prompt must be non-empty")
    rng = np.random.default_rng(args.seed)
    net.rnn_clear_previous_state()
    probs = net.rnn_time_step(np.asarray([prompt_ids], np.int32))
    dist = probs[0, -1] if probs.ndim == 3 else probs[0]
    toks = []
    for _ in range(args.max_tokens):
        tok = _sample_probs(dist, args.temperature, args.top_k, args.top_p,
                            rng)
        toks.append(tok)
        probs = net.rnn_time_step(np.asarray([tok], np.int32))
        dist = probs[0]
    net.rnn_clear_previous_state()
    print(f"[rnn_time_step: {len(toks)} tokens]", file=sys.stderr)
    print(args.prompt + "".join(chr(t) if t < 0x110000 else "?"
                                for t in toks))
    return 0


def _parse_chaos_worker(specs):
    """['1:proc_kill@10', ...] → {worker: chaos spec}, validating both the
    worker index syntax and the embedded chaos spec (clean CLI errors)."""
    out = {}
    for item in specs or []:
        worker_s, sep, spec = item.partition(":")
        try:
            worker = int(worker_s)
            if worker < 0 or not sep or not spec:
                raise ValueError
        except ValueError:
            raise SystemExit(f"bad --chaos-worker {item!r}: expected "
                             "WORKER:SPEC, e.g. '1:proc_kill@10'")
        if worker in out:
            raise SystemExit(f"bad --chaos-worker {item!r}: duplicate "
                             f"worker {worker}")
        _parse_chaos(spec)   # validate eagerly; workers re-parse from env
        out[worker] = spec
    return out


def cmd_launch(args) -> int:
    """Pod-scale launcher (docs/FAULT_TOLERANCE.md "Process-scale"): fork
    N worker processes running the command after ``--`` (or join an
    existing cluster with --join), monitor heartbeats, and relaunch
    workers that die or hang — host leave/join with membership epochs.
    """
    import os

    rest = list(args.worker_args or [])
    if rest and rest[0] == "--":
        rest = rest[1:]
    if not rest:
        raise SystemExit("launch needs a worker command after '--', e.g. "
                         "launch --nprocs 2 -- train --zoo lenet ...")
    if rest[0] not in ("train", "evaluate", "predict", "serve", "summary"):
        raise SystemExit(f"launch worker command must be a "
                         f"deeplearning4j_tpu subcommand, got {rest[0]!r}")
    # arm the shared compile cache BEFORE any worker exists: enable_
    # compile_cache exports JAX_COMPILATION_CACHE_DIR, which both forked
    # workers and the --join re-exec inherit (config only — this parent
    # must never initialise a backend: a TPU belongs to one process)
    from .serving.warmcache import enable_compile_cache
    print(f"launch: compile cache {enable_compile_cache()}")
    if args.join:
        # join mode: THIS process becomes worker --process-id of an
        # existing cluster (one `launch --join` per host on a real pod)
        from .parallel.distributed import (
            ENV_CONNECT_TIMEOUT, ENV_COORDINATOR, ENV_NUM_PROCESSES,
            ENV_PROCESS_ID, ENV_RUN_DIR,
        )
        if args.process_id is None:
            raise SystemExit("launch --join needs --process-id")
        if args.coordinator:
            os.environ[ENV_COORDINATOR] = args.coordinator
        os.environ[ENV_PROCESS_ID] = str(args.process_id)
        os.environ[ENV_NUM_PROCESSES] = str(args.nprocs)
        os.environ[ENV_CONNECT_TIMEOUT] = str(args.connect_timeout)
        if args.run_dir:
            os.environ[ENV_RUN_DIR] = args.run_dir
        return main(rest)
    import sys as _sys

    from .parallel.launcher import PodLauncher

    run_dir = args.run_dir
    if not run_dir:
        import tempfile
        run_dir = tempfile.mkdtemp(prefix="dl4j_tpu_launch_")
    trace_dir = None
    if args.trace:
        # pod tracing: every worker incarnation writes its own trace
        # file under run_dir/trace, the launcher records its
        # spawn/leave/join/membership instants on its own track, and the
        # merge below stitches everything into ONE pod timeline at
        # args.trace (docs/OBSERVABILITY.md "Reading a pod timeline")
        from .obs import trace as obs_trace
        trace_dir = os.path.join(run_dir, "trace")
        obs_trace.enable_tracing(
            path=os.path.join(trace_dir, "launcher.trace.json"),
            process_id=-1, process_name="launcher")
    chaos = _parse_chaos_worker(args.chaos_worker)
    try:
        launcher = PodLauncher(
            [_sys.executable, "-m", "deeplearning4j_tpu"] + rest,
            num_workers=args.nprocs, run_dir=run_dir,
            devices_per_worker=args.devices_per_proc,
            chaos=chaos or None,
            bootstrap=args.bootstrap,
            heartbeat_timeout=args.heartbeat_timeout,
            max_restarts=args.max_restarts,
            deadline_s=args.deadline,
            connect_timeout_s=args.connect_timeout,
            megascale_slices=args.megascale_slices,
            trace_dir=trace_dir,
            grace_s=args.grace,
            straggler_factor=args.straggler_factor,
            straggler_beats=args.straggler_beats,
            straggler_policy=args.straggler_policy,
            serve=args.serve)
    except ValueError as e:   # e.g. more workers x chips than the TPU host has
        raise SystemExit(f"launch: {e}")
    print(f"launch: {args.nprocs} worker(s) x "
          f"{args.devices_per_proc or 'default'} device(s), "
          f"bootstrap={args.bootstrap}, run dir {run_dir}"
          + (f", chaos {chaos}" if chaos else ""))
    if args.serve:
        print("launch: fleet endpoints "
              + ",".join(launcher.serve_endpoints()))
    report = launcher.run()
    print(f"launch: completed={report['completed']} "
          f"restarts={report['restarts']} "
          f"planned_leaves={report['planned_leaves']} "
          f"stragglers={len(report['stragglers'])} "
          f"epoch={report['epoch']} "
          f"last_ckpt_step={report['last_checkpoint_step']} "
          f"leaked={report['leaked_killed']} "
          f"wall={report['wall_seconds']}s")
    for e in report["events"]:
        print(f"  [{e['t']:8.2f}s] {e['kind']}"
              + (f" worker {e['worker']}" if 'worker' in e else "")
              + (f" ({e['cause']}, rc={e.get('rc')})"
                 if e['kind'] in ('leave', 'unrecovered') else ""))
    if args.trace:
        merged = launcher.merge_trace(args.trace)
        if merged is None:
            print(f"trace: no worker traces found under {trace_dir}")
        else:
            print(f"trace: pod timeline ({merged['metadata']['events']} "
                  f"events) -> {args.trace}")
    if report["unrecovered"]:
        print(f"launch: UNRECOVERED workers {report['unrecovered']} — "
              f"logs under {run_dir}/logs")
        return 1
    return 0


def cmd_summary(args) -> int:
    net = _load_model(args.model)
    from .nn.conf.memory import memory_report

    print(f"model: {type(net).__name__}, {net.num_params():,} params")
    print(memory_report(net, minibatch=args.batch_size))
    return 0


def cmd_check(args) -> int:
    """graftcheck static analysis (docs/STATIC_ANALYSIS.md)."""
    from .analysis import main as analysis_main

    argv = list(args.paths)
    argv += ["--format", args.format]
    if args.baseline != "<default>":
        argv += ["--baseline", args.baseline]
    if args.baseline_update:
        argv += ["--baseline-update", "--justification", args.justification]
    if args.show_suppressed:
        argv += ["--show-suppressed"]
    return analysis_main(argv)


def cmd_flywheel(args) -> int:
    """Headless train→eval→canary→fleet-promote flywheel on a synthetic
    task (docs/LIFECYCLE.md): a PromotionPipeline drives --generations
    lifecycle rounds against an in-process registry + fleet, with
    optional chaos kinds fired on successive generations after the
    bootstrap.  One JSON line per generation; the journal makes a
    killed run resumable (re-run with the same --journal)."""
    import os
    import tempfile
    import threading
    import time

    from .datasets import DataSet
    from .datasets.iterators import ListDataSetIterator
    from .earlystopping import DataSetLossCalculator
    from .nn.conf.inputs import InputType
    from .nn.layers import Dense, OutputLayer
    from .nn.multilayer import MultiLayerNetwork, NeuralNetConfiguration
    from .nn.updaters import Sgd
    from .parallel import (ChaosInjector, ElasticTrainer, FaultKind,
                           FaultSchedule)
    from .serving import (Engine, EvalGate, FleetRouter, ModelRegistry,
                          PromotionPipeline)
    from .utils.serializer import load_model

    chaos_plan = [c.strip() for c in (args.chaos or "").split(",")
                  if c.strip()]
    known = {"device_loss", "nan", "regression", "host_kill", "crash"}
    bad = set(chaos_plan) - known
    if bad:
        print(f"unknown --chaos kind(s): {sorted(bad)} "
              f"(known: {sorted(known)})", file=sys.stderr)
        return 2
    # chaos kinds fire one per generation, starting at gen 2: the
    # bootstrap generation always runs clean (there is nothing to roll
    # back to before the first promote)
    chaos_at = {i + 2: kind for i, kind in enumerate(chaos_plan)}

    rng = np.random.default_rng(args.seed)
    teacher = rng.standard_normal((12, 3)).astype(np.float32)

    def data(n, seed):
        r = np.random.default_rng(seed)
        x = r.standard_normal((n, 12)).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[np.argmax(x @ teacher, axis=1)]
        return DataSet(features=x, labels=y)

    def mlp(seed, lr=0.05):
        conf = (NeuralNetConfiguration.builder().seed(seed)
                .updater(Sgd(lr=lr))
                .layer(Dense(n_out=16, activation="tanh"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(12)).build())
        net = MultiLayerNetwork(conf)
        net.init()
        return net

    train_ds, eval_ds = data(96, args.seed + 1), data(48, args.seed + 2)
    workdir = args.workdir or tempfile.mkdtemp(prefix="flywheel_")
    os.makedirs(workdir, exist_ok=True)
    journal = args.journal or os.path.join(workdir, "flywheel.jsonl")
    reg = ModelRegistry()
    router = FleetRouter(max_retries=3)
    killable = {"host": None}

    def train_fn(gen):
        kind = chaos_at.get(gen)
        if kind == "nan":
            net = mlp(args.seed + gen)
            import jax
            net.params = jax.tree_util.tree_map(
                lambda a: np.full(np.shape(a), np.nan, np.float32),
                net.params)
            return {"model": net, "run_id": f"flywheel-g{gen}"}
        labels = train_ds.labels
        if kind == "regression":
            net = mlp(args.seed + gen, lr=0.1)
            labels = np.roll(labels, 1, axis=1)   # confidently wrong
        elif gen == 1:
            net = mlp(args.seed, lr=0.08)
        else:
            net = load_model(reg.checkpoint_path("flywheel", args.alias))
        trainee = net
        if kind == "device_loss":
            trainee = ChaosInjector(net, FaultSchedule.scripted(
                {3: FaultKind.DEVICE_LOSS}))
        tr = ElasticTrainer(trainee,
                            checkpoint_dir=os.path.join(workdir,
                                                        f"gen{gen}"),
                            checkpoint_every=2, sync_every=1,
                            run_id=f"flywheel-g{gen}")
        shuffled = train_ds.features, labels
        idx = np.random.default_rng(args.seed + 10 * gen).permutation(
            shuffled[0].shape[0])
        batches = ListDataSetIterator(
            [DataSet(features=shuffled[0][idx][i:i + 24],
                     labels=shuffled[1][idx][i:i + 24])
             for i in range(0, shuffled[0].shape[0], 24)])
        tr.fit(batches, epochs=1 if kind == "regression" else args.epochs)
        return tr

    class _Crash(Exception):
        pass

    crash_armed = {g for g, k in chaos_at.items() if k == "crash"}

    def stage_hook(stage, gen):
        if stage == "CANARY" and gen in crash_armed:
            crash_armed.discard(gen)
            raise _Crash(f"controller crash injected at gen {gen}")

    thresholds = {}
    if args.max_divergence is not None:
        thresholds["max_divergence"] = args.max_divergence

    def make_pipe():
        return PromotionPipeline(
            reg, router, "flywheel", train_fn,
            EvalGate(DataSetLossCalculator(eval_ds),
                     threshold=args.eval_threshold),
            alias=args.alias, journal_path=journal,
            canary_frac=args.canary, canary_window=args.canary_window,
            canary_timeout_s=args.canary_timeout_s,
            canary_thresholds=thresholds, stage_hook=stage_hook)

    pipe = make_pipe()
    resumed = pipe.resume()
    if resumed["completed"] or resumed["partial"] is not None:
        print(f"resumed from journal: completed={resumed['completed']} "
              f"partial={resumed['partial']}", file=sys.stderr)

    stop = threading.Event()
    traffic = None
    dropped = [0]
    try:
        while len(pipe.completed) < args.generations:
            gen_no = max(pipe.completed, default=0) + 1
            if chaos_at.get(gen_no) == "host_kill" \
                    and killable["host"] is not None:
                killable["host"].kill_on_swap = True
            try:
                rec = pipe.run_generation()
            except _Crash as exc:
                print(f"# {exc} — resuming from the journal",
                      file=sys.stderr)
                pipe = make_pipe()
                pipe.resume()
                rec = pipe.run_generation()
            print(json.dumps(rec))
            if args.hosts > 0 and not router.hosts():
                # fleet birth after the bootstrap promote: every host
                # loads straight from the registry's warm bundle
                kw = dict(max_batch=8, slo_ms=30_000.0, replicas=1,
                          admission="block")
                h0 = Engine.from_registry(reg, "flywheel", args.alias,
                                          **kw)
                h0.load()
                router.add_host("h0", engine=h0)
                v, model = reg.resolve("flywheel", args.alias)
                for i in range(1, args.hosts):
                    eng = Engine(model, **kw)
                    eng.swap_model(model, tag=f"flywheel:v{v}")
                    eng.load()
                    host = _KillableEngine(eng)
                    killable["host"] = host
                    router.add_host(f"h{i}", engine=host)

                def loop():   # canary mirror windows need live traffic
                    probes = [rng.standard_normal((r, 12)).astype(
                        np.float32) for r in (1, 2, 4)]
                    i = 0
                    while not stop.is_set():
                        try:
                            router.output(probes[i % 3], slo_ms=30_000.0)
                        except Exception:
                            dropped[0] += 1   # reported in final stats;
                            # expected inside chaos windows (host_kill)
                        i += 1
                        time.sleep(0.002)
                traffic = threading.Thread(target=loop, daemon=True)
                traffic.start()
    finally:
        stop.set()
        if traffic is not None:
            traffic.join(timeout=10)
        router.shutdown(shutdown_hosts=True)
    print(json.dumps({"stats": pipe.stats(),
                      "alias": reg.resolve("flywheel", args.alias)[0],
                      "traffic_dropped": dropped[0],
                      "journal": journal}))
    return 0


class _KillableEngine:
    """cmd_flywheel's --chaos host_kill seam: dies the moment a rolling
    swap touches it (scripts/train_promote_soak.py carries the full
    version)."""

    def __init__(self, inner):
        self.inner = inner
        self.kill_on_swap = False
        self.killed = False

    def output_async(self, x, slo_ms=None):
        from .serving import ServingUnavailableError
        if self.killed:
            raise ServingUnavailableError("host killed (chaos)")
        return self.inner.output_async(x, slo_ms=slo_ms)

    def swap_model(self, model, tag=None, warm_bundle=None):
        if self.kill_on_swap or self.killed:
            self.killed = True
            raise RuntimeError("host killed mid-roll (chaos)")
        return self.inner.swap_model(model, tag, warm_bundle=warm_bundle)

    @property
    def current_tag(self):
        return self.inner.current_tag

    def metrics_snapshot(self):
        return self.inner.metrics_snapshot()

    def health_snapshot(self):
        if self.killed:
            return {"status": "unready", "ready": False}
        return self.inner.health_snapshot()

    def shutdown(self, timeout: float = 5.0):
        self.inner.shutdown(timeout=timeout)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="deeplearning4j_tpu",
                                description=__doc__.split("\n")[0])
    sub = p.add_subparsers(dest="command", required=True)

    t = sub.add_parser("train", help="train a model")
    t.add_argument("--zoo", help="zoo model name (e.g. lenet)")
    t.add_argument("--zoo-args", help="JSON kwargs for the zoo constructor")
    t.add_argument("--config", help="MultiLayerConfiguration JSON file")
    t.add_argument("--data", required=True,
                   help="builtin name (mnist/cifar10/iris/emnist/svhn/uci) or .npz")
    t.add_argument("--epochs", type=int, default=1)
    t.add_argument("--batch-size", type=int, default=128)
    t.add_argument("--seed", type=int, default=12345)
    t.add_argument("--print-every", type=int, default=10)
    t.add_argument("--output", help="checkpoint zip to write")
    t.add_argument("--dashboard", help="HTML training report to write")
    t.add_argument("--mesh", help="train sharded over a named device mesh, "
                   "e.g. 'data=8' or 'data=4,model=2' (the reference's "
                   "ParallelWrapperMain role); an optional "
                   "'schedule=gpipe|1f1b' token picks the pipeline "
                   "microbatch order for pipe-axis nets, and "
                   "'compress=threshold|bitmap' enables the DCN-tier "
                   "compressed gradient exchange on dcn-axis meshes, "
                   "e.g. 'dcn=2,data=4,compress=threshold'")
    t.add_argument("--prefetch", metavar="DEPTH[,DEVICE]",
                   help="device-resident input pipeline "
                   "(docs/INPUT_PIPELINE.md): keep DEPTH batches already "
                   "transferred to device ahead of the step (async H2D from "
                   "a background thread; pre-sharded on --mesh runs); "
                   "'0' = synchronous feeding (bitwise-unchanged legacy "
                   "path); optional DEVICE pins placement, e.g. '2,tpu:0'")
    t.add_argument("--nan-guard", type=int, default=None, metavar="BUDGET",
                   help="arm the divergence guard: steps with non-finite "
                   "gradients apply no update; BUDGET consecutive bad steps "
                   "escalate (recoverable under --elastic-dir)")
    t.add_argument("--elastic-dir", metavar="DIR",
                   help="train under ElasticTrainer: rolling checkpoints in "
                   "DIR + automatic restore-and-continue on recoverable "
                   "failures (docs/FAULT_TOLERANCE.md)")
    t.add_argument("--checkpoint-every", type=int, default=100,
                   help="checkpoint interval in steps for --elastic-dir")
    t.add_argument("--step-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="step watchdog for --elastic-dir: a step exceeding "
                   "this wall clock is treated as hung and recovered")
    t.add_argument("--chaos", metavar="SPEC",
                   help="inject scripted faults (chaos drill; needs "
                   "--elastic-dir): 'kind@step[,kind@step...]"
                   "[,seed=S][,hang=SECONDS][,slow=SECONDS]', kinds: "
                   "device_loss/ckpt_write_crash/ckpt_truncate/"
                   "ckpt_bitflip/hung_step/nan_grads/proc_kill/proc_hang/"
                   "preempt_notice/coord_kill/slow_worker (the process "
                   "kinds take down THIS worker — only meaningful under "
                   "`launch`, which restarts it; preempt_notice is the "
                   "ANNOUNCED path: SIGTERM self, emergency checkpoint, "
                   "PREEMPTED exit)")
    t.add_argument("--trace", metavar="PATH",
                   help="record step/span tracing and write a Chrome-"
                   "trace JSON to PATH on exit (view in chrome://tracing "
                   "or ui.perfetto.dev; '{process}' expands to the worker "
                   "index; docs/OBSERVABILITY.md)")
    t.add_argument("--grace", type=float, default=None, metavar="SECONDS",
                   help="preemption grace budget for --elastic-dir runs: "
                   "on SIGTERM/SIGUSR1 (a preemption notice) the next "
                   "step boundary writes a deadline-bounded emergency "
                   "checkpoint (uncompressed fallback when deflate won't "
                   "fit the remaining budget) and exits with the "
                   "PREEMPTED code 75 (default: DL4J_TPU_GRACE_S env, "
                   "else 30)")
    t.set_defaults(fn=cmd_train)

    ln = sub.add_parser(
        "launch", help="multi-process pod launcher: fork N workers (or "
        "join a cluster) with heartbeat membership + host join/leave "
        "recovery (docs/FAULT_TOLERANCE.md)")
    ln.add_argument("--nprocs", type=int, default=2,
                    help="number of worker processes (cluster size)")
    ln.add_argument("--devices-per-proc", type=int, default=None,
                    metavar="K", help="per-process device visibility: each "
                    "worker sees K devices (CPU: K virtual devices via "
                    "XLA_FLAGS; TPU host: its own K chips, default 1 — "
                    "more workers x chips than the host has is refused)")
    ln.add_argument("--bootstrap", choices=("replica", "distributed"),
                    default="replica",
                    help="'distributed' = workers form a jax.distributed "
                    "cluster (global mesh; needs backend support — see "
                    "probe_multiprocess_support); 'replica' = independent "
                    "replicas per worker, no cross-process collectives "
                    "(default; the single-box CPU mode)")
    ln.add_argument("--run-dir", help="shared run directory for heartbeats/"
                    "membership/logs (default: a fresh temp dir)")
    ln.add_argument("--heartbeat-timeout", type=float, default=5.0,
                    metavar="S", help="a worker whose heartbeat is older "
                    "than this is declared hung, killed, and relaunched")
    ln.add_argument("--max-restarts", type=int, default=2,
                    help="per-worker relaunch budget (host rejoin)")
    ln.add_argument("--deadline", type=float, default=600.0, metavar="S",
                    help="overall run deadline; survivors are reaped "
                    "(no orphan worker outlives the launcher)")
    ln.add_argument("--connect-timeout", type=float, default=60.0,
                    metavar="S", help="coordinator bootstrap budget; a dead "
                    "coordinator raises CoordinatorUnreachableError instead "
                    "of hanging")
    ln.add_argument("--megascale-slices", type=int, default=None,
                    metavar="N", help="export MEGASCALE_NUM_SLICES=N to "
                    "workers (feeds detect_num_slices → "
                    "ShardedTrainer.two_tier / build_two_tier_mesh); "
                    "distributed bootstrap defaults it to --nprocs")
    ln.add_argument("--grace", type=float, default=30.0, metavar="S",
                    help="preemption grace budget exported to workers "
                    "(DL4J_TPU_GRACE_S) AND the launcher's escalation "
                    "deadline: a notified worker still alive ~1.5x past "
                    "it is SIGKILLed; workers exiting with the PREEMPTED "
                    "code are relaunched WITHOUT consuming the restart "
                    "budget")
    ln.add_argument("--straggler-factor", type=float, default=2.0,
                    metavar="K", help="flag a worker whose per-step wall "
                    "time exceeds K x the median of its peers' (from "
                    "heartbeats; default 2.0)")
    ln.add_argument("--straggler-beats", type=int, default=3, metavar="M",
                    help="consecutive over-threshold heartbeats before a "
                    "worker is flagged a straggler (default 3)")
    ln.add_argument("--straggler-policy",
                    choices=("off", "flag", "relaunch"), default="flag",
                    help="what to do with a flagged straggler: 'flag' = "
                    "counter + trace instant + run-report event (default), "
                    "'relaunch' = kill and relaunch it (consumes restart "
                    "budget), 'off' = no detection")
    ln.add_argument("--chaos-worker", action="append", metavar="I:SPEC",
                    help="arm worker I with a --chaos spec (repeatable), "
                    "e.g. '1:proc_kill@10' — injected only into the FIRST "
                    "incarnation, so the relaunched worker survives")
    ln.add_argument("--trace", metavar="PATH",
                    help="arm span tracing in every worker (per-"
                    "incarnation files under RUN_DIR/trace) and merge "
                    "them — plus the launcher's own membership/leave/join "
                    "events — into ONE pod-timeline Chrome trace at PATH")
    ln.add_argument("--serve", action="store_true",
                    help="serving-fleet mode: assign each worker a stable "
                    "serve port (exported as DL4J_TPU_SERVE_PORT, stable "
                    "across relaunch) and print the fleet endpoints — pair "
                    "with a 'serve' worker command and a `serve --fleet` "
                    "router (docs/SERVING.md 'Fleet serving')")
    ln.add_argument("--join", action="store_true",
                    help="join an existing cluster as one worker instead "
                    "of forking (one `launch --join` per host on a pod)")
    ln.add_argument("--process-id", type=int, default=None,
                    help="this host's index (with --join)")
    ln.add_argument("--coordinator", metavar="HOST:PORT",
                    help="coordinator address (with --join)")
    ln.add_argument("worker_args", nargs=argparse.REMAINDER,
                    help="-- followed by the worker subcommand, e.g. "
                    "-- train --zoo lenet --data mnist --elastic-dir ckpts")
    ln.set_defaults(fn=cmd_launch)

    e = sub.add_parser("evaluate", help="evaluate a saved model")
    e.add_argument("--model", required=True)
    e.add_argument("--data", required=True)
    e.set_defaults(fn=cmd_evaluate)

    r = sub.add_parser("predict", help="run inference")
    r.add_argument("--model", required=True)
    r.add_argument("--input", required=True, help=".npz with array 'x'")
    r.add_argument("--output", required=True, help=".npz to write")
    r.set_defaults(fn=cmd_predict)

    v = sub.add_parser("serve", help="serve a saved model (docs/SERVING.md)")
    v.add_argument("--model", default=None,
                   help="checkpoint zip to serve (required unless --fleet "
                   "or --models)")
    v.add_argument("--models", metavar="NAME=PATH,...",
                   help="boot a multi-model host: comma-separated "
                   "checkpoints (NAME=PATH, or bare paths — the name is "
                   "the file stem), all registered and AOT-warmed on one "
                   "engine; the first (or --model) is the default, the "
                   "rest are addressed by the request's 'model' field "
                   "(docs/SERVING.md 'Multi-tenant serving')")
    v.add_argument("--tenants", metavar="JSON",
                   help="per-tenant admission classes: a JSON list of "
                   "rows {tenant, model?, slo_ms?, weight?, quota_qps?, "
                   "quota_concurrent?, admission?} enforced by the "
                   "batcher's weighted-fair lanes — over-quota requests "
                   "shed typed, and the HTTP 429 carries the tenant "
                   "(docs/SERVING.md 'Multi-tenant serving')")
    v.add_argument("--fleet", metavar="HOST:PORT,...",
                   help="run a fleet router instead of a local engine: "
                   "front the comma-separated serve hosts with "
                   "least-loaded dispatch, session affinity, dead-host "
                   "failover, and rolling promote (docs/SERVING.md "
                   "'Fleet serving')")
    v.add_argument("--name", default="model",
                   help="registry name for the model (default: 'model')")
    v.add_argument("--version", type=int, default=None,
                   help="registry version number (default: auto-assign)")
    v.add_argument("--max-batch", type=int, default=32,
                   help="dynamic batcher fused-batch cap")
    v.add_argument("--slo-ms", type=float, default=50.0,
                   help="per-request deadline budget; queued requests past "
                   "it fail fast with DeadlineExceededError")
    v.add_argument("--replicas", type=int, default=-1,
                   help="engine replicas (-1 = one per local device)")
    v.add_argument("--admission", choices=("block", "shed"), default="shed",
                   help="overload policy: block callers or shed with "
                   "OverloadedError (HTTP 429)")
    v.add_argument("--queue-cap", type=int, default=256,
                   help="admission queue bound in requests")
    v.add_argument("--port", type=int, default=9000)
    v.add_argument("--host", default="127.0.0.1")
    v.add_argument("--forward-timeout", type=float, default=None,
                   metavar="SECONDS",
                   help="declare a replica HUNG (abandon + retry its batch "
                   "elsewhere + respawn it) when one forward exceeds this "
                   "(default: disabled)")
    v.add_argument("--max-retries", type=int, default=1,
                   help="per-request retry budget after a replica failure "
                   "(deadline-aware, different replica; default 1)")
    v.add_argument("--breaker-threshold", type=int, default=3,
                   help="consecutive replica failures that trip its circuit "
                   "breaker (dispatch routes around it; default 3)")
    v.add_argument("--warm-bundle", metavar="PATH",
                   help="warmup bundle of serialized AOT executables to "
                   "deserialize at load (default: <checkpoint>.warm next to "
                   "--model when present; docs/SERVING.md 'Cold start & "
                   "autoscaling')")
    v.add_argument("--smoke", type=int, default=0, metavar="N",
                   help="push N synthetic requests through the engine, "
                   "print the metrics snapshot, and exit (self-test)")
    v.add_argument("--trace", metavar="PATH",
                   help="record request/batch span tracing; the ring "
                   "buffer is served live on GET /trace and written to "
                   "PATH on shutdown (docs/OBSERVABILITY.md)")
    v.add_argument("--prefix-cache", action="store_true",
                   help="radix prefix cache over the paged KV pool: "
                   "shared-prompt requests attach matching pages "
                   "read-only and prefill only their suffix "
                   "(docs/SERVING.md 'Decode-side optimizations')")
    v.add_argument("--speculate", metavar="DRAFT_CKPT[,k]",
                   help="speculative decoding: DRAFT_CKPT proposes k "
                   "tokens per step (default k=4), the target "
                   "verifies in one dispatch — temp-0 output is "
                   "bit-identical to plain decode")
    v.add_argument("--kv-dtype", choices=("float32", "int8"),
                   default="float32",
                   help="KV page storage dtype: int8 stores "
                   "per-row-quantized pages + f32 scales (~4x "
                   "sessions at fixed HBM; changes bits — gated by "
                   "a top1-agree envelope, not the identity gates)")
    v.add_argument("--decode-role", choices=("unified", "prefill", "decode"),
                   default="unified",
                   help="disaggregated serving role for the decode "
                   "engine: 'prefill' hosts run prompt prefill and "
                   "export KV pages as handoffs, 'decode' hosts attach "
                   "handoffs and stream tokens; a FleetRouter routes "
                   "the two stages (docs/SERVING.md 'Disaggregated "
                   "and sharded decode')")
    v.set_defaults(fn=cmd_serve)

    g = sub.add_parser(
        "generate", help="autoregressive text generation (docs/SERVING.md "
        "\"Autoregressive decode\"): transformer LMs run through the "
        "paged-KV-cache decode engine, recurrent nets through "
        "rnnTimeStep streaming")
    g.add_argument("--model", required=True, help="checkpoint zip")
    g.add_argument("--prompt", required=True,
                   help="prompt text (byte-valued char vocab)")
    g.add_argument("--max-tokens", type=int, default=64,
                   help="tokens to generate (default 64)")
    g.add_argument("--temperature", type=float, default=0.0,
                   help="sampling temperature; 0 = greedy (default)")
    g.add_argument("--top-k", type=int, default=0,
                   help="keep only the k highest-probability tokens "
                   "(0 = off)")
    g.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus sampling mass (1.0 = off)")
    g.add_argument("--seed", type=int, default=0,
                   help="sampling seed — same seed, same text")
    g.add_argument("--page-size", type=int, default=16,
                   help="KV-cache page size in tokens (transformer path; "
                   "auto-shrunk for short position tables)")
    g.add_argument("--prefix-cache", action="store_true",
                   help="radix prefix cache over the paged KV pool "
                   "(transformer path; docs/SERVING.md)")
    g.add_argument("--speculate", metavar="DRAFT_CKPT[,k]",
                   help="speculative decoding: DRAFT_CKPT proposes k "
                   "tokens per step (default k=4); temp-0 output is "
                   "bit-identical to plain decode")
    g.add_argument("--kv-dtype", choices=("float32", "int8"),
                   default="float32",
                   help="KV page storage dtype; int8 quantizes pages "
                   "per row (~4x sessions at fixed HBM)")
    g.set_defaults(fn=cmd_generate)

    s = sub.add_parser("summary", help="model + memory summary")
    s.add_argument("--model", required=True)
    s.add_argument("--batch-size", type=int, default=32)
    s.set_defaults(fn=cmd_summary)

    c = sub.add_parser(
        "check", help="graftcheck: repo-native static analysis — jit "
        "purity, determinism, thread safety, telemetry contracts "
        "(docs/STATIC_ANALYSIS.md)")
    c.add_argument("paths", nargs="*",
                   help="specific .py files (default: whole package)")
    c.add_argument("--format", choices=("text", "json"), default="text")
    c.add_argument("--baseline", default="<default>",
                   help="baseline json ('none' disables)")
    c.add_argument("--baseline-update", action="store_true",
                   help="accept current findings into the baseline "
                   "(REQUIRES --justification)")
    c.add_argument("--justification", default="",
                   help="why the baselined findings are accepted")
    c.add_argument("--show-suppressed", action="store_true")
    c.set_defaults(fn=cmd_check)

    fw = sub.add_parser(
        "flywheel", help="continuous train→eval→canary→fleet-promote "
        "lifecycle on a synthetic task (docs/LIFECYCLE.md): repeated "
        "PromotionPipeline generations with lineage-aware rollback, a "
        "crash-resumable journal, and optional per-generation chaos")
    fw.add_argument("--generations", type=int, default=3, metavar="K",
                    help="lifecycle generations to complete (default 3)")
    fw.add_argument("--eval-threshold", type=float, default=3.0,
                    help="eval-gate loss ceiling; non-finite scores "
                    "always fail (default 3.0)")
    fw.add_argument("--canary", type=float, default=1.0, metavar="FRAC",
                    help="fraction of live batches mirrored to the "
                    "canary (default 1.0)")
    fw.add_argument("--canary-window", type=int, default=4,
                    help="mirrored batches per canary decision "
                    "(default 4)")
    fw.add_argument("--canary-timeout-s", type=float, default=60.0,
                    help="canary window deadline; an unfilled window "
                    "is a rejection (default 60)")
    fw.add_argument("--max-divergence", type=float, default=None,
                    help="canary prediction-divergence ceiling "
                    "(mean abs diff vs the incumbent; default off)")
    fw.add_argument("--hosts", type=int, default=2,
                    help="fleet hosts; host 0 is the subscribed canary "
                    "engine, the rest roll via rolling_swap; 0 = no "
                    "fleet, alias-only promotion (default 2)")
    fw.add_argument("--chaos", default="", metavar="KIND[,KIND...]",
                    help="chaos kinds fired one per generation starting "
                    "at gen 2: device_loss (mid-train, recovered), nan "
                    "(eval gate catches), regression (canary rejects), "
                    "host_kill (mid-roll, lineage rollback), crash "
                    "(controller dies at CANARY, journal resume)")
    fw.add_argument("--workdir",
                    help="checkpoint/journal directory (default: fresh "
                    "temp dir)")
    fw.add_argument("--journal",
                    help="journal path override — reuse one to resume "
                    "a killed run (default: <workdir>/flywheel.jsonl)")
    fw.add_argument("--alias", default="prod")
    fw.add_argument("--epochs", type=int, default=3,
                    help="training epochs per generation (default 3)")
    fw.add_argument("--seed", type=int, default=12345)
    fw.set_defaults(fn=cmd_flywheel)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
