"""Bring-up probes for the attached TPU: one process, facts only.

Run on the chip (it refuses anywhere else) to re-establish what CHANGES.md
records about the installation:

  kernels    every ``pl.pallas_call`` in ops/ compiled at its A/B script's
             shape and checked against its XLA reference — compiled /
             refused, with the compiler's message for a refusal; the flash
             rows name the tile geometry ``flash_tiles`` chose
  tiles      the three flash kernels alone at the benchmark's training
             shape over a handful of tile geometries (what the preferences
             in ``ops/attention.py`` were read from); not in the default run
  experts    the expert layers' grouped feed-forward (``ops/grouped_ffn``)
             alone against the three ``ragged_dot``s it replaced, at the
             five expert cells' step and chunk shapes, then over tile
             geometries put in place of ``ffn_tiles``' choice; not in the
             default run
  precision  what an f32 matmul is on the MXU at default precision
  flash_xla  one LM train step each with attention_impl "flash" and "xla"
             at the chip_smoke width (one run each — a finding, not a
             benchmark)
  bundle     whether ``serialize`` works for a decode warm bundle here, and
             the bundle round trip on the device count at hand
  gspmd      (>1 chip) what plain jit does with a batch-sharded flash call

    python scripts/chip_probe.py [section ...]      # default: all

Writes ``chiprun_out/chip_probe.json``; the last stdout line is the same
JSON.
"""
import functools
import glob
import json
import os
import sys
import time
import traceback

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _time(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def _attempt(table, name, shape, fn):
    """Run one kernel probe; a refusal is a row, not a crash."""
    row = {"kernel": name, "shape": shape}
    try:
        row.update(fn())
        # "compiled" means Mosaic compiled it, not that XLA ran instead
        row["status"] = ("compiled" if row.get("mosaic_calls", 1)
                         else "not used (the XLA path ran)")
    except Exception as e:  # the compiler's message IS the finding
        traceback.print_exc()
        row["status"] = "refused"
        row["message"] = f"{type(e).__name__}: {e}"[:1500]
    print(f"chip_probe: {row}", flush=True)
    table.append(row)


CELL_CALL = (12, 16, 1024, 64)   # B, H, T, D of gpt2-medium.train-1k's flash call


def _flash_geometry(T, S, D, dtype, BH) -> str:
    """The tiles the code chooses for a flash call, as the row prints it."""
    from deeplearning4j_tpu.ops import attention
    pick = getattr(attention, "flash_tiles", None)
    if pick is None:       # a tree from before PR 26
        return "128 x 128, one tile a grid step"
    t = pick(T, S, D, jnp.dtype(dtype).itemsize, BH)
    return "none (XLA path)" if t is None else f"{t} steps={t.grid_steps}"


def probe_kernels() -> list:
    from deeplearning4j_tpu.nn.updaters import Adam, Updater
    from deeplearning4j_tpu.ops import lstm_kernel, update_kernel
    from deeplearning4j_tpu.ops.attention import flash_mha, mha

    table = []
    rng = np.random.default_rng(0)

    def flash(B, H, T, D, dtype, masked):
        def run():
            mk = lambda: jnp.asarray(
                rng.normal(size=(B, H, T, D)).astype(np.float32)).astype(dtype)
            q, k, v = mk(), mk(), mk()
            mask = np.ones((B, T), np.float32)
            if masked:
                mask[0, int(T * 0.7):] = 0.0
            mj = jnp.asarray(mask)
            w = mj[:, None, :, None]
            km = mj if masked else None
            xm = mj[:, None, None, :] if masked else None

            def loss(attn):
                return lambda q, k, v: jnp.sum(
                    (attn(q, k, v).astype(jnp.float32) * w) ** 2)
            gf = jax.jit(jax.value_and_grad(loss(
                lambda q, k, v: flash_mha(q, k, v, True, kmask=km)),
                argnums=(0, 1, 2)))
            gx = jax.jit(jax.value_and_grad(loss(
                lambda q, k, v: mha(q, k, v, causal=True, mask=xm)),
                argnums=(0, 1, 2)))
            fwd = jax.jit(lambda q, k, v: flash_mha(q, k, v, True, kmask=km))
            hlo = gf.lower(q, k, v).as_text()
            (lf, grads_f), (lx, grads_x) = gf(q, k, v), gx(q, k, v)
            err = max(float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))))
                      for a, b in zip(grads_f, grads_x))
            scale = max(float(jnp.max(jnp.abs(b.astype(jnp.float32))))
                        for b in grads_x)
            return {"tiles": _flash_geometry(T, T, D, dtype, B * H),
                    "mosaic_calls": hlo.count("tpu_custom_call"),
                    "loss_rel_err": abs(float(lf) - float(lx)) / abs(float(lx)),
                    "grad_max_abs_err": err, "grad_max_abs": scale,
                    "fwd_ms": round(_time(fwd, q, k, v) * 1e3, 3),
                    "fwd_bwd_ms": round(_time(gf, q, k, v) * 1e3, 3),
                    "xla_fwd_bwd_ms": round(_time(gx, q, k, v) * 1e3, 3)}
        name = "flash fwd+bwd" + (" +kmask" if masked else "")
        _attempt(table, name,
                 f"B{B} H{H} T{T} D{D} {jnp.dtype(dtype).name}", run)

    flash(2, 8, 4096, 64, jnp.bfloat16, True)     # bench.py config 6
    flash(*CELL_CALL, jnp.bfloat16, False)        # gpt2-medium.train-1k's call
    flash(2, 8, 2048, 64, jnp.bfloat16, False)
    flash(8, 12, 1024, 64, jnp.bfloat16, False)   # chip_smoke's LM step
    flash(2, 4, 1024, 64, jnp.float32, False)     # f32 (8,128) tiles
    flash(2, 4, 192, 64, jnp.bfloat16, True)      # whole-axis block, T % 128 != 0
    flash(2, 4, 64, 16, jnp.float32, True)        # the unit tests' shape

    def fused_update():
        layers, dim = 48, 256                     # scripts/fused_update_ab.py
        params = {f"l{i}": {
            "W": jnp.asarray(rng.normal(size=(dim, dim)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(dim,)), jnp.float32)}
            for i in range(layers)}
        grads = jax.tree_util.tree_map(lambda p: p * 0.01, params)
        upd = Adam(lr=1e-3)
        state = {"m": jax.tree_util.tree_map(lambda p: p * 0.03, params),
                 "v": jax.tree_util.tree_map(lambda p: p * p * 0.01, params)}
        it = jnp.asarray(3.0, jnp.float32)
        plain = jax.jit(lambda p, g, s, i: Updater.apply(upd, p, g, s, i))
        update_kernel.ENABLED, update_kernel.FORCE_JNP = True, False
        fused = jax.jit(lambda p, g, s, i: update_kernel.fused_apply(
            "adam", upd, p, g, s, i))
        hlo = fused.lower(params, grads, state, it).as_text()
        ref, got = plain(params, grads, state, it), fused(params, grads, state, it)
        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(ref), jax.tree_util.tree_leaves(got)))
        return {"mosaic_calls": hlo.count("tpu_custom_call"),
                "max_abs_err_vs_per_leaf": err,
                "fused_ms": round(_time(fused, params, grads, state, it) * 1e3, 4),
                "per_leaf_ms": round(_time(plain, params, grads, state, it) * 1e3, 4)}
    _attempt(table, "fused Adam update", "48 layers x 256 (3.2M params)",
             fused_update)

    def lstm():
        mb, n = 64, 512                           # docs/KERNELS.md shape
        z = jnp.asarray(rng.normal(size=(mb, 4 * n)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(mb, n)), jnp.float32)
        lstm_kernel.ENABLED = True

        def loss(cell):
            def f(z, c):
                h, cn = cell(z, c)
                return jnp.sum(h * h) + jnp.sum(jnp.tanh(cn))
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))
        gf, gp = loss(lstm_kernel.fused_lstm_cell), loss(lstm_kernel._plain_cell)
        hlo = gf.lower(z, c).as_text()
        (_, a), (_, b) = gf(z, c), gp(z, c)
        err = max(float(jnp.max(jnp.abs(x - y))) for x, y in zip(a, b))
        return {"mosaic_calls": hlo.count("tpu_custom_call"),
                "grad_max_abs_err": err,
                "fused_ms": round(_time(gf, z, c) * 1e3, 4),
                "plain_ms": round(_time(gp, z, c) * 1e3, 4)}
    _attempt(table, "fused LSTM cell fwd+bwd", "mb64 n512 f32", lstm)

    return table


def probe_tiles() -> list:
    """Forward, dk/dv and dq alone (bf16, D 64, causal), each over tile
    geometries put in place of ``flash_tiles``' choice: at the training
    cells' call (B·H 192, T 1024) and at the other lengths the rule has to
    serve.  A result nobody reads is dead code to XLA, so returning dq
    alone times the dq kernel alone."""
    from deeplearning4j_tpu.ops import attention

    chosen = attention.flash_tiles
    D = CELL_CALL[3]
    scale = D ** -0.5
    rng = np.random.default_rng(0)
    table = []

    def sweep(BH, T, S, geometries):
        mk = lambda n: jnp.asarray(rng.normal(size=(1, BH, n, D)), jnp.bfloat16)
        q, k, v, g = mk(T), mk(S), mk(S), mk(T)
        o, lse = jax.jit(lambda q, k, v: attention._flash_forward(
            q, k, v, None, True, scale))(q, k, v)
        bwd = lambda pick: (lambda q, k, v, o, lse, g: pick(
            attention._flash_backward(q, k, v, None, o, lse, g, True, scale)))
        calls = {
            "fwd_ms": (lambda q, k, v, o, lse, g: attention._flash_forward(
                q, k, v, None, True, scale)),
            "dkdv_ms": bwd(lambda grads: grads[1:]),
            "dq_ms": bwd(lambda grads: grads[0]),
        }
        for geo in [None] + geometries:
            row = {"BH": BH, "T": T, "S": S}
            if geo is None:
                row["tiles"] = "chosen: " + _flash_geometry(T, S, D, q.dtype, BH)
            else:
                r, c, h = geo
                row.update(rows=r, chunk=c, heads=h)
                # (n_blocked, n_walked): dk/dv asks with the axes swapped
                attention.flash_tiles = lambda nb, nw, D, isz, BH: \
                    attention.FlashTiles(min(r, nb), min(c, nw), nw, h,
                                         (BH // h, nb // min(r, nb), 1), 0)
            try:
                for name, fn in calls.items():
                    # a new function object: jit's trace cache is keyed by it
                    row[name] = round(_time(jax.jit(functools.partial(fn)),
                                            q, k, v, o, lse, g, n=20) * 1e3, 4)
            except Exception as e:   # a geometry Mosaic refuses is a row too
                row["refused"] = f"{type(e).__name__}: {e}"[:300]
            finally:
                attention.flash_tiles = chosen
            print(f"chip_probe: {row}", flush=True)
            table.append(row)

    B, H, T, _ = CELL_CALL
    sweep(B * H, T, T, [(128, 128, 1), (256, 512, 1), (256, 512, 4),
                        (512, 512, 1), (512, 1024, 1), (512, 1024, 2),
                        (1024, 1024, 1)])
    sweep(1536, 128, 128, [(128, 128, h) for h in (1, 4, 8)])
    sweep(384, 512, 512, [(256, 256, 1), (256, 512, 1), (512, 512, 1),
                          (512, 512, 2)])
    for n in (2048, 4096):
        sweep(16, n, n, [(256, 512, 1), (512, 512, 1), (512, 1024, 1),
                         (1024, 1024, 1), (1024, 2048, 1)])
    return table


def _expert_calls() -> dict:
    """The expert cells' grouped products, from their configurations'
    files: cell -> (rows a decode step sorts, rows of the largest prefill
    chunk, d, f, experts held, experts in all, k)."""
    calls = {}
    for path in sorted(glob.glob(os.path.join(_REPO, "benchmarks", "configs",
                                              "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if "n_routed_experts" not in cfg:
            continue
        k, prog = cfg["num_experts_per_tok"], cfg["program"]
        held = cfg["n_routed_experts"]
        calls[os.path.basename(path)[:-5]] = (
            prog["max_slots"] * k, prog["prefill_chunk"] * k,
            cfg["hidden_size"],
            cfg.get("moe_intermediate_size", cfg["intermediate_size"]), held,
            next(cfg[name] for name in (
                "n_routed_experts_published", "num_local_experts_published",
                "n_routed_experts") if name in cfg), k)
    return calls


def probe_experts() -> list:
    """``ops/grouped_ffn`` against the three ``jax.lax.ragged_dot``\\ s it
    replaced, alone, at the five expert cells' step and largest chunk
    shapes (bf16), each token's k picks drawn evenly over all experts:
    the two agree, the time of each, and the bandwidth the kernel reaches
    over the weights of the experts that were hit.  Then the step shapes
    again over tile geometries put in place of ``ffn_tiles``' choice."""
    from deeplearning4j_tpu.ops import grouped_ffn as G

    rng = np.random.default_rng(0)
    table = []

    def plain(xs, wg, wu, wd, sizes):
        rd = functools.partial(jax.lax.ragged_dot, group_sizes=sizes,
                               preferred_element_type=jnp.float32)
        return rd((jax.nn.silu(rd(xs, wg)) * rd(xs, wu)).astype(wd.dtype), wd)

    for cell, (m_step, m_chunk, d, f, held, n_all, k) in _expert_calls().items():
        mk = lambda *s: jnp.asarray(0.05 * rng.standard_normal(s, np.float32),
                                    jnp.bfloat16)
        wg, wu, wd = mk(held, d, f), mk(held, d, f), mk(held, f, d)
        for what, m in (("step", m_step), ("chunk", m_chunk)):
            picks = np.stack([rng.choice(n_all, k, replace=False)
                              for _ in range(m // k)]).reshape(-1)
            sizes = np.bincount(picks[picks < held], minlength=held)
            n, hit = int(sizes.sum()), int((sizes > 0).sum())
            xs = 20 * mk(m, d)
            sz = jnp.asarray(sizes, jnp.int32)
            chosen = G.ffn_tiles(m, d, f, held, 2)
            row = {"cell": cell, "call": what, "M": m, "d": d, "f": f,
                   "held": held, "rows_held": n, "experts_hit": hit,
                   "tiles": str(chosen)}
            try:
                want = np.asarray(jax.jit(plain)(xs, wg, wu, wd, sz))[:n]
                got = np.asarray(jax.jit(G.grouped_ffn)(xs, wg, wu, wd, sz))[:n]
                row["max_abs_gap"] = float(np.abs(got - want).max()) if n else 0.0
                row["max_abs"] = float(np.abs(want).max()) if n else 0.0
                row["ragged_dot_ms"] = round(_time(
                    jax.jit(functools.partial(plain)), xs, wg, wu, wd, sz,
                    n=20) * 1e3, 4)
                ms = _time(jax.jit(functools.partial(G.grouped_ffn)), xs, wg,
                           wu, wd, sz, n=20) * 1e3
                row["grouped_ffn_ms"] = round(ms, 4)
                row["hit_weights_gb_s"] = round(
                    hit * 3 * d * f * 2 / (ms * 1e-3) / 1e9, 1)
                row["tflop_s"] = round(n * 6 * d * f / (ms * 1e-3) / 1e12, 2)
            except Exception as e:
                traceback.print_exc()
                row["refused"] = f"{type(e).__name__}: {e}"[:600]
            print(f"chip_probe: {row}", flush=True)
            table.append(row)
            # other geometries at the same shapes
            windows = (32, 64, 128) if what == "step" else (64, 128, 256)
            for window in windows:
                for cols in sorted({c for c in (128, 256, 384, 512, 768)
                                    if f % c == 0 and d * c * 2 <= 6 << 20}):
                    if (window, cols) == (chosen.window, chosen.cols):
                        continue
                    rows = -(-chosen.rows // window) * window
                    t = G.FfnTiles(rows, -(-m // rows), window, cols,
                                   2 * (6 * d * cols + 6 * rows * d))
                    r = {"cell": cell, "call": what, "window": window,
                         "cols": cols, "rows": rows}
                    try:
                        r["grouped_ffn_ms"] = round(_time(
                            jax.jit(functools.partial(G.with_tiles, tiles=t)),
                            xs, wg, wu, wd, sz, n=10) * 1e3, 4)
                    except Exception as e:
                        r["refused"] = f"{type(e).__name__}: {e}"[:300]
                    print(f"chip_probe: {r}", flush=True)
                    table.append(r)
        del wg, wu, wd
    return table


def probe_precision() -> dict:
    """f32 x f32 matmul against a float64 host reference, per precision."""
    rng = np.random.default_rng(0)
    a = rng.normal(size=(1024, 1024)).astype(np.float32)
    b = rng.normal(size=(1024, 1024)).astype(np.float32)
    ref = a.astype(np.float64) @ b.astype(np.float64)
    out = {}
    for prec in ("default", "high", "highest"):
        got = np.asarray(jax.jit(lambda x, y: jnp.matmul(
            x, y, precision=prec))(a, b), np.float64)
        out[prec] = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    bf = np.asarray(jax.jit(lambda x, y: jnp.matmul(
        x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32))(a, b), np.float64)
    out["bf16_operands"] = float(np.max(np.abs(bf - ref)) / np.max(np.abs(ref)))
    return out


def probe_flash_vs_xla() -> dict:
    """The chip_smoke LM step, once per attention_impl."""
    from chip_smoke import FULL as cfg
    from deeplearning4j_tpu.nn.updaters import Adam
    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh

    n_dev = len(jax.devices())
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab"], (
        cfg["batch_per_device"] * n_dev, cfg["seq"])).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1)
    out = {}
    for impl in ("flash", "xla"):
        lm = ShardedTransformerLM(
            vocab_size=cfg["vocab"], n_layers=cfg["layers"],
            d_model=cfg["d_model"], n_heads=cfg["heads"],
            mesh=build_mesh({"data": n_dev}), max_len=cfg["seq"],
            n_microbatches=1, compute_dtype=jnp.bfloat16,
            attention_impl=impl,
            updater=Adam(lr=3e-4, moment_dtype="bfloat16"))
        t0 = time.perf_counter()
        float(lm.fit_batch(toks, tgts))
        first = time.perf_counter() - t0
        for _ in range(3):
            lm.fit_batch(toks, tgts)
        jax.block_until_ready(lm.params)
        t0 = time.perf_counter()
        for _ in range(20):
            lm.fit_batch(toks, tgts)
        jax.block_until_ready(lm.params)
        out[impl] = {"step_ms": round((time.perf_counter() - t0) / 20 * 1e3, 2),
                     "first_step_s": round(first, 2),
                     "peak_bytes": int(jax.devices()[0].memory_stats()
                                       ["peak_bytes_in_use"])}
        print(f"chip_probe: LM step attention_impl={impl}: {out[impl]}",
              flush=True)
        del lm
    return out


def probe_bundle() -> dict:
    """serialize() per decode executable, then the bundle round trip."""
    import tempfile

    from jax.experimental import serialize_executable as se

    from deeplearning4j_tpu.parallel import ShardedTransformerLM, build_mesh
    from deeplearning4j_tpu.serving import DecodeEngine

    out = {}
    meshes = {"one_device": build_mesh({"data": 1}, jax.devices()[:1])}
    if len(jax.devices()) > 1:
        meshes["all_devices_tp"] = build_mesh({"data": len(jax.devices())})
    for name, mesh in meshes.items():
        lm = ShardedTransformerLM(vocab_size=512, n_layers=2, d_model=128,
                                  n_heads=4, mesh=mesh, max_len=64, seed=11)
        kw = dict(max_slots=2, page_size=16, prompt_buckets=(16,),
                  default_max_new=4)
        cold = DecodeEngine(lm, **kw).load()
        row = {"serialize": {}}
        try:
            for key, exe in cold._compiled.items():
                try:
                    se.serialize(exe)
                    row["serialize"][":".join(map(str, key))] = "ok"
                except Exception as e:
                    row["serialize"][":".join(map(str, key))] = \
                        f"{type(e).__name__}: {e}"[:300]
            ref = cold.generate([1, 2, 3], max_new_tokens=4).tokens
            n_exec = cold.compile_cache_size()
            path = os.path.join(tempfile.mkdtemp(), "lm.zip.warm")
            cold.save_warmup_bundle(path)
        except Exception as e:
            traceback.print_exc()
            row["save_error"] = f"{type(e).__name__}: {e}"[:300]
            out[name] = row
            continue
        finally:
            cold.shutdown()
        warm = DecodeEngine(lm, **kw).load(warm_bundle=path)
        try:
            row.update(
                executables=n_exec,
                bundle_hits=int(warm.metrics.counter_value("bundle_hits")),
                bundle_misses=int(warm.metrics.counter_value("bundle_misses")),
                tokens_equal=warm.generate([1, 2, 3],
                                           max_new_tokens=4).tokens == ref)
        finally:
            warm.shutdown()
        print(f"chip_probe: bundle[{name}]: {row}", flush=True)
        out[name] = row
    return out


def probe_gspmd() -> dict:
    """What plain jit (GSPMD) does with a batch-sharded flash call."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chip_smoke import custom_call_shapes
    from deeplearning4j_tpu.ops.attention import flash_mha
    from deeplearning4j_tpu.parallel import build_mesh

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": "one device"}
    mesh = build_mesh({"data": n_dev})
    sh = NamedSharding(mesh, P("data"))
    q = jax.device_put(jnp.ones((2 * n_dev, 4, 1024, 64), jnp.bfloat16), sh)
    try:
        hlo = jax.jit(lambda q: flash_mha(q, q, q, True),
                      out_shardings=sh).lower(q).compile().as_text()
    except NotImplementedError as e:     # jax refuses; that is the answer
        return {"plain_jit": f"refused — {e}"}
    return {"global_BH": 2 * n_dev * 4, "per_chip_BH": 2 * 4,
            "custom_call_result_shapes": custom_call_shapes(hlo),
            "all_gathers": hlo.count(" all-gather(")
            + hlo.count(" all-gather-start(")}


SECTIONS = {"kernels": probe_kernels, "precision": probe_precision,
            "flash_xla": probe_flash_vs_xla, "bundle": probe_bundle,
            "gspmd": probe_gspmd}
ON_REQUEST = {"tiles": probe_tiles,       # run only when named
              "experts": probe_experts}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_probe: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    want = sys.argv[1:] or list(SECTIONS)
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "jax": jax.__version__}
    rc = 0
    for name in want:
        try:
            out[name] = {**SECTIONS, **ON_REQUEST}[name]()
        except Exception as e:
            traceback.print_exc()
            out[name] = {"error": f"{type(e).__name__}: {e}"[:1500]}
            rc = 1
    os.makedirs(os.path.join(_REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(_REPO, "chiprun_out", "chip_probe.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
