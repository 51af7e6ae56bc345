"""ShardedTransformerLM — the 4D-parallel (DP×TP×SP×PP) training step.

The north-star composition mandated by SURVEY.md §7-M5, with no reference
analog (DL4J's only distributed axis is DP — §2.3): one jitted XLA program
per step in which

  - ``data``  shards the batch (grad psum inserted by shard_map transpose),
  - ``model`` tensor-parallels attention heads + FFN columns
    (Megatron-style column/row split with an explicit psum),
  - ``seq``   shards the sequence; attention runs as ring attention with
    K/V blocks rotating over ICI (parallel/ring.py),
  - ``pipe``  pipelines the homogeneous block stack with a GPipe or
    interleaved-1F1B microbatch schedule (parallel/pipeline.py,
    ``schedule=`` ctor flag).

Embedding/head run under GSPMD outside the manual shard_map island; the
block math is models/transformer.block_apply — the same function the
single-chip TransformerBlock layer uses.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, set_mesh

from ..models.arch import EXPERT_BLOCKS, LMArch
from ..models.transformer import block_apply, block_params
from ..nn.updaters import Adam
from ..obs import startup as obs_startup
from ..obs import trace as obs_trace
from .pipeline import SCHEDULES, pipeline_apply, stack_stage_params
from .ring import ring_attention

Array = jax.Array


def _block_tp_specs(pipe: str = "pipe", model: str = "model"):
    """Per-leaf PartitionSpecs for stacked block params: column-parallel
    q/k/v/FFN-up, row-parallel o/FFN-down (psum after), norms replicated."""
    return {
        "ln1_g": P(pipe, None), "ln1_b": P(pipe, None),
        "Wq": P(pipe, None, model), "Wk": P(pipe, None, model),
        "Wv": P(pipe, None, model),
        "Wo": P(pipe, model, None), "bo": P(pipe, None),
        "ln2_g": P(pipe, None), "ln2_b": P(pipe, None),
        "W1": P(pipe, None, model), "b1": P(pipe, model),
        "W2": P(pipe, model, None), "b2": P(pipe, None),
    }


#: what JAX calls ``fit_batch``'s and ``fit_batches``' programs
_STEP_PROGRAMS = ("jit(step)", "jit(multi)")


class ShardedTransformerLM:
    """Decoder-only LM trained with DP×TP×SP×PP over a named mesh.

    >>> mesh = build_mesh({"data": 2, "model": 2, "seq": 2, "pipe": 1})
    >>> lm = ShardedTransformerLM(vocab_size=256, n_layers=4, d_model=128,
    ...                           n_heads=8, mesh=mesh)
    >>> loss = lm.fit_batch(tokens, targets)   # [B,T] int32 each

    The architecture is an :class:`~..models.arch.LMArch`: pass ``arch=``
    (e.g. ``LMArch.from_config(json.load(f))``) or the GPT-2 sizes by
    name as before, which build the same description.  ``params=`` hands
    in a ready tree (made elsewhere, e.g. on the device from a seed) in
    place of the constructor's own draw.  An architecture of the expert
    family (``models/arch.EXPERT_BLOCKS``: models/latent_moe.py,
    models/sparse_gqa.py) is SERVED (``decode_program``, ``logits``);
    its train step does not exist yet and ``fit_batch`` says so.
    """

    def __init__(self, vocab_size: Optional[int] = None,
                 n_layers: Optional[int] = None,
                 d_model: Optional[int] = None,
                 n_heads: Optional[int] = None, mesh: Optional[Mesh] = None,
                 d_ff: int = 0, max_len: int = 512,
                 n_microbatches: int = 2, seed: int = 0, updater=None,
                 compute_dtype=None, seq_parallel: str = "ring",
                 attention_impl: str = "flash", schedule: str = "gpipe",
                 arch: Optional[LMArch] = None, params=None):
        if mesh is None:
            raise ValueError("ShardedTransformerLM needs a mesh")
        if arch is None:
            if None in (vocab_size, n_layers, d_model, n_heads):
                raise ValueError("give arch= or vocab_size, n_layers, "
                                 "d_model and n_heads")
            arch = LMArch.gpt2(vocab_size, n_layers, d_model, n_heads,
                               d_ff=d_ff, max_len=max_len)
        self.arch = arch
        vocab_size, n_layers = arch.vocab_size, arch.n_layers
        d_model, n_heads = arch.d_model, arch.n_heads
        d_ff, max_len = arch.d_ff, arch.max_len
        # normalize to the canonical 4-axis mesh (absent axes = size 1) so
        # specs/collectives can reference every axis unconditionally
        canonical = ("data", "model", "seq", "pipe")
        unknown = [n for n in mesh.axis_names if n not in canonical]
        if unknown:
            raise ValueError(f"unexpected mesh axes {unknown}; use {canonical}")
        if tuple(mesh.axis_names) != canonical:
            from .mesh import build_mesh
            mesh = build_mesh({n: mesh.shape.get(n, 1) for n in canonical},
                              devices=mesh.devices.flatten())
        tp = mesh.shape.get("model", 1)
        if n_heads % tp:
            raise ValueError(f"n_heads {n_heads} not divisible by model={tp}")
        if seq_parallel not in ("ring", "ulysses"):
            raise ValueError(f"seq_parallel must be 'ring' or 'ulysses', "
                             f"got {seq_parallel!r}")
        if seq_parallel == "ulysses" and \
                (n_heads // tp) % mesh.shape.get("seq", 1):
            raise ValueError(
                f"ulysses scatters heads over seq={mesh.shape.get('seq', 1)} "
                f"but only {n_heads // tp} heads remain after TP — use "
                "seq_parallel='ring' or raise n_heads")
        self.seq_parallel = seq_parallel
        if attention_impl not in ("flash", "xla"):
            raise ValueError(f"attention_impl must be 'flash' or 'xla', "
                             f"got {attention_impl!r}")
        if attention_impl == "xla" and mesh.shape.get("seq", 1) > 1:
            raise ValueError(
                "attention_impl='xla' requires seq=1 — the sequence-"
                "parallel paths (ring/ulysses) are built on the blockwise/"
                "flash update and cannot honor plain einsum attention")
        # mirrors TransformerBlock.kernel: "flash" = fused pallas kernels;
        # "xla" = plain einsum attention on the single-device seq path
        self.attention_impl = attention_impl
        if n_layers % mesh.shape.get("pipe", 1):
            raise ValueError(
                f"n_layers {n_layers} not divisible by pipe={mesh.shape['pipe']}")
        if schedule not in SCHEDULES:
            raise ValueError(f"schedule must be one of {SCHEDULES}, "
                             f"got {schedule!r}")
        # microbatch order on the pipe axis: "gpipe" = all-forward-then-
        # all-backward; "1f1b" = interleaved, depth-bounded activation
        # memory at a recompute cost (parallel/pipeline.py docstring)
        self.schedule = schedule
        self.mesh = mesh
        self.vocab_size = vocab_size
        self.n_heads = n_heads
        self.n_heads_local = n_heads // tp
        self.n_microbatches = n_microbatches
        self.compute_dtype = compute_dtype
        self.updater = updater or Adam(lr=3e-4)
        self.iteration = 0

        self._jit_step = None
        self._jit_multi_step = None
        self._jit_logits = None
        self.token_sharding = NamedSharding(mesh, P("data", "seq"))
        # the weights and their placement, as the phase ``lm/init`` of the
        # start-up account (obs/startup.py)
        obs_startup.watch_compiles()
        with obs_startup.phase("lm/init", cat="train",
                               drew_params=params is None) as ph:
            if arch.block in EXPERT_BLOCKS:
                self._init_expert_family(seed, params)
            else:
                self._init_gpt2_family(seed, params)
            ph.set(leaves=len(jax.tree_util.tree_leaves(self.params)))

    def _init_gpt2_family(self, seed: int, params) -> None:
        """The GPT-2 family: parameters sharded over the mesh, and the
        optimizer state beside them."""
        arch, mesh = self.arch, self.mesh
        vocab_size, n_layers = arch.vocab_size, arch.n_layers
        d_model, n_heads = arch.d_model, arch.n_heads
        d_ff, max_len = arch.d_ff, arch.max_len
        rng = jax.random.PRNGKey(seed)
        if params is None:
            ke, kp, kh, *kb = jax.random.split(rng, 3 + n_layers)
            blocks = stack_stage_params(
                [block_params(k, d_model, n_heads, d_ff) for k in kb])
            params = {
                "embed": 0.02 * jax.random.normal(ke, (vocab_size, d_model)),
                "pos": 0.02 * jax.random.normal(kp, (max_len, d_model)),
                "blocks": blocks,
                "lnf_g": jnp.ones((d_model,)), "lnf_b": jnp.zeros((d_model,)),
                "head": 0.02 * jax.random.normal(kh, (d_model, vocab_size)),
            }
        self.block_specs = _block_tp_specs()
        shardings = {
            "embed": NamedSharding(mesh, P(None, None)),
            "pos": NamedSharding(mesh, P(None, None)),
            "blocks": {k: NamedSharding(mesh, s)
                       for k, s in self.block_specs.items()},
            "lnf_g": NamedSharding(mesh, P()), "lnf_b": NamedSharding(mesh, P()),
            "head": NamedSharding(mesh, P(None, "model")),
        }
        self.params = jax.device_put(params, shardings)
        # optimizer state mirrors params structurally → same shardings
        opt = self.updater.init_state(params)
        self.opt_state = jax.device_put(opt, self._opt_shardings(opt, shardings))

    def _init_expert_family(self, seed: int, params) -> None:
        """An architecture of the expert family: replicated parameters in
        the architecture's own type (bf16 weights are SERVED as bf16), no
        optimizer state — nothing here trains it yet."""
        from ..models.latent_moe import family_module

        arch, mesh = self.arch, self.mesh
        if any(mesh.shape.get(a, 1) > 1 for a in ("model", "seq", "pipe")):
            raise NotImplementedError(
                f"a {arch.block} architecture is not sharded over model / seq "
                f"/ pipe yet (got {dict(mesh.shape)}): the expert exchange "
                "across chips is ROADMAP M4's open half")
        if self.compute_dtype is not None:
            raise NotImplementedError(
                f"compute_dtype does not apply to a {arch.block} architecture: "
                "state param_dtype in its LMArch")
        if params is None:
            params = family_module(arch).init_params(
                jax.random.PRNGKey(seed), arch, jnp.dtype(arch.param_dtype))
        self.params = jax.device_put(params, NamedSharding(mesh, P()))
        self.opt_state = None
        self.block_specs = None

    def _refuse_training(self) -> None:
        if self.arch.block in EXPERT_BLOCKS:
            raise NotImplementedError(
                f"training a {self.arch.block} architecture is not "
                "implemented: the train step of its attention and of routed "
                "experts (ROADMAP M4, M5) is open; this LM is served "
                "(decode_program, logits)")

    def _opt_shardings(self, opt, param_shardings):
        """Each opt-state subtree ('m'/'v'/...) mirrors the params tree."""
        def place(sub):
            if jax.tree_util.tree_structure(sub) == \
                    jax.tree_util.tree_structure(param_shardings):
                return param_shardings
            return jax.tree_util.tree_map(
                lambda _: NamedSharding(self.mesh, P()), sub)
        return {k: place(v) for k, v in opt.items()}

    # -- forward -----------------------------------------------------------

    def _forward(self, params, tokens):
        if self.arch.block in EXPERT_BLOCKS:
            from ..models.latent_moe import family_module
            return family_module(self.arch).forward(params, tokens, self.arch)
        cd = self.compute_dtype
        embed = params["embed"] if cd is None else params["embed"].astype(cd)
        pos = params["pos"] if cd is None else params["pos"].astype(cd)
        h = embed[tokens] + pos[: tokens.shape[1]]
        blocks = params["blocks"] if cd is None else jax.tree_util.tree_map(
            lambda a: a.astype(cd), params["blocks"])

        # no pipeline/ring/TP stage structure → the block stack runs under
        # plain jit (GSPMD), not inside pipeline_apply's shard_map
        # (model==1 too: block_fn's TP psums need the axis bound, which
        # only pipeline_apply's shard_map provides)
        unrolled = all(self.mesh.shape.get(a, 1) == 1
                       for a in ("pipe", "seq", "model"))
        if self.mesh.shape.get("seq", 1) == 1:
            # degenerate SP: single-device attention — O(T) saved residuals
            # (o + lse) per layer, where the ring's blockwise-XLA path
            # would checkpoint full [T,T] probability tiles
            if self.attention_impl == "xla":
                from ..ops.attention import mha
                attn = functools.partial(mha, causal=True)
            else:
                from ..ops.attention import flash_mha
                attn = functools.partial(flash_mha, causal=True)
                if unrolled and self.mesh.shape.get("data", 1) > 1:
                    # a Mosaic call is opaque to GSPMD, which would
                    # all-gather the batch-sharded q/k/v and run the WHOLE
                    # batch's attention on every chip: hand each chip its
                    # own batch shard explicitly
                    attn = shard_map(attn, mesh=self.mesh,
                                     in_specs=(P("data"),) * 3,
                                     out_specs=P("data"), check_vma=False)
        elif self.seq_parallel == "ulysses":
            from .ulysses import ulysses_attention
            attn = functools.partial(ulysses_attention, axis_name="seq",
                                     causal=True)
        else:
            attn = functools.partial(ring_attention, axis_name="seq",
                                     causal=True)
        block_fn = functools.partial(
            block_apply, n_heads=self.n_heads_local, causal=True,
            attention_fn=attn,
            psum_axis="model" if self.mesh.shape.get("model", 1) > 1 else None)

        if unrolled:
            # unroll the block stack instead of scanning it: XLA schedules
            # each layer's fusions independently (no dynamic-update-slice stacking of residuals,
            # no loop-carried copies; the step-time effect on the current
            # chip is not measured)
            n_layers = jax.tree_util.tree_leaves(blocks)[0].shape[0]
            for i in range(n_layers):
                h = block_fn(jax.tree_util.tree_map(lambda a: a[i], blocks), h)
        else:
            h = pipeline_apply(
                lambda p, h: block_fn(p, h), blocks, h, self.mesh,
                n_microbatches=self.n_microbatches,
                schedule=self.schedule,
                param_specs=self.block_specs,
                x_spec=P("data", "seq", None))
        from ..nn.layers.normalization import layer_norm
        h = layer_norm(h, params["lnf_g"].astype(h.dtype),
                       params["lnf_b"].astype(h.dtype))
        head = params["head"] if cd is None else params["head"].astype(cd)
        return h @ head  # [B, T, V] logits

    def _loss(self, params, tokens, targets):
        from ..ops.losses import sparse_softmax_xent
        logits = self._forward(params, tokens)
        return sparse_softmax_xent(logits, targets)

    # -- training ----------------------------------------------------------

    def _build_step(self):
        updater = self.updater

        def step(params, opt_state, it, tokens, targets):
            loss, grads = jax.value_and_grad(self._loss)(params, tokens, targets)
            updates, new_opt = updater.update(grads, opt_state, it)
            new_params = jax.tree_util.tree_map(
                lambda p, u: (p - u.astype(p.dtype)), params, updates)
            return new_params, new_opt, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def _on_compile(self, event: dict) -> None:
        """JAX compiled a step program (or read it from its cache) outside
        every phase of the start-up account, so after this model's
        ``train/first_step``: a batch of a new shape.  (Two models that
        train in one process both hear of it, as two loaded engines both
        count a compile after load.)"""
        if event["fun_name"] in _STEP_PROGRAMS:
            obs_trace.instant("train/recompile", cat="train",
                              fun_name=event["fun_name"],
                              iteration=self.iteration + 1,
                              seconds=event["seconds"])

    def fit_batch(self, tokens: np.ndarray, targets: np.ndarray):
        self._refuse_training()
        if self._jit_step is None:
            self._jit_step = self._build_step()
            obs_startup.on_unphased_compile(self._on_compile)
            with obs_startup.phase("train/first_step", cat="train",
                                   fun="fit_batch"):
                return self.fit_batch(tokens, targets)
        with obs_trace.span("train/step", cat="train",
                            iteration=self.iteration + 1) as sp:
            with obs_trace.span("train/h2d", cat="train"):
                tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), self.token_sharding)
                targets = jax.device_put(jnp.asarray(targets, jnp.int32), self.token_sharding)
                with set_mesh(self.mesh):   # the counter goes to every chip
                    it = jnp.asarray(self.iteration, jnp.int32)
            sp.set(tokens=tokens.size)
            with obs_trace.span("train/dispatch", cat="train"), \
                    set_mesh(self.mesh):
                self.params, self.opt_state, loss = self._jit_step(
                    self.params, self.opt_state, it, tokens, targets)
        self.iteration += 1
        from ..optimize.score import LazyScore
        return LazyScore(loss)

    def _build_multi_step(self):
        """k train steps fused into one dispatch via lax.scan: k-chaining
        amortizes the per-step host dispatch gap to 1/k.  Identical math
        to k fit_batch calls — sequential optimizer steps, per-step
        iteration counter."""
        updater = self.updater

        def multi(params, opt_state, it0, toks, tgts):
            its = it0 + jnp.arange(toks.shape[0], dtype=jnp.int32)

            def body(carry, inp):
                params, opt = carry
                tok, tgt, it = inp
                loss, grads = jax.value_and_grad(self._loss)(params, tok, tgt)
                updates, opt = updater.update(grads, opt, it)
                params = jax.tree_util.tree_map(
                    lambda p, u: (p - u.astype(p.dtype)), params, updates)
                return (params, opt), loss

            (params, opt_state), losses = jax.lax.scan(
                body, (params, opt_state), (toks, tgts, its))
            return params, opt_state, losses

        return jax.jit(multi, donate_argnums=(0, 1))

    def fit_batches(self, tokens: np.ndarray, targets: np.ndarray):
        """k steps in ONE dispatch: ``tokens``/``targets`` are [k, B, T]
        (k stacked minibatches).  Returns [k] LazyScores."""
        self._refuse_training()
        if self._jit_multi_step is None:
            self._jit_multi_step = self._build_multi_step()
            obs_startup.on_unphased_compile(self._on_compile)
            with obs_startup.phase("train/first_step", cat="train",
                                   fun="fit_batches"):
                return self.fit_batches(tokens, targets)
        stacked = NamedSharding(self.mesh, P(None, "data", "seq"))
        with obs_trace.span("train/step", cat="train",
                            iteration=self.iteration + 1) as sp:
            with obs_trace.span("train/h2d", cat="train"):
                tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), stacked)
                targets = jax.device_put(jnp.asarray(targets, jnp.int32), stacked)
                with set_mesh(self.mesh):   # the counter goes to every chip
                    it = jnp.asarray(self.iteration, jnp.int32)
            k = tokens.shape[0]
            sp.set(steps=k, tokens=tokens.size)
            with obs_trace.span("train/dispatch", cat="train"), \
                    set_mesh(self.mesh):
                self.params, self.opt_state, losses = self._jit_multi_step(
                    self.params, self.opt_state, it, tokens, targets)
        self.iteration += k
        from ..optimize.score import LazyScore
        return [LazyScore(losses[i]) for i in range(k)]

    def logits(self, tokens: np.ndarray) -> Array:
        if self._jit_logits is None:
            self._jit_logits = jax.jit(self._forward)
        tokens = jax.device_put(jnp.asarray(tokens, jnp.int32), self.token_sharding)
        with set_mesh(self.mesh):
            return self._jit_logits(self.params, tokens)

    # -- autoregressive decode (serving/decode.py) -------------------------

    def decode_program(self, page_size: int = 16,
                       max_len: Optional[int] = None):
        """Pure prefill / decode-step / re-encode functions over the
        paged KV-cache (ops/kv_cache.py) for the serving decode engine:
        ``models/transformer.paged_decode_program`` of this LM's stacked
        block tree, with the final layer norm before the head.

        The decode path is a different execution mode from training —
        stateful, one query row per step — but shares the block weights
        and the block math split (block_kv_project / block_finish).
        Prefill attends as ``reencode`` does (ops/kv_cache.det_attention);
        a decode step through ops/paged_attention.py, over the pages a
        slot holds: tokens are equal and logits agree to rounding
        (``paged_decode_program``'s docstring; tests/_decode_checks.py
        holds the limit).

        On a multi-device mesh (all devices folded into the ``data``
        axis) the program is TENSOR-PARALLEL: the same entry points,
        built over each device's head group (column-slices of Wq/Wk/Wv,
        the matching row-slice of Wo) and shard_map'd with the page
        pool's lane axis sharded to match (a row's heads lie side by
        side, so each device holds whole heads, 1/n of the KV bytes),
        ONE psum per layer after the row-parallel output projection,
        and the FFN, the head and so the logits replicated: the
        samplers see the full vocabulary on every shard.  All
        shards run the identical psum in both the incremental and
        re-encode paths, so that contract holds PER SHARD LAYOUT (an
        n-way program is held to its own re-encode, not to a 1-way
        program's).  Int8 KV stays single-device: its per-row
        quantization scale is an amax over ALL heads, which a head
        shard cannot compute locally (the engine enforces this).
        """
        if self.arch.block in EXPERT_BLOCKS:
            from ..models.latent_moe import family_module
            if int(np.prod(list(self.mesh.shape.values()))) != 1:
                raise NotImplementedError(
                    "tensor-parallel decode is not carried by the "
                    f"{self.arch.block} decode program: serve it on a "
                    "one-device mesh")
            return family_module(self.arch).decode_program(self.arch, page_size,
                                                     max_len)
        from ..models.transformer import paged_decode_program
        from ..nn.layers.normalization import layer_norm
        from ..ops.kv_cache import QuantPages

        n_dev = int(np.prod(list(self.mesh.shape.values())))
        tp = 1
        if n_dev != 1:
            tp = int(self.mesh.shape.get("data", 1))
            if tp != n_dev:
                raise NotImplementedError(
                    "sharded decode shards attention heads over the "
                    "'data' axis only — fold all devices into data= "
                    f"(got {dict(self.mesh.shape)})")
            if self.n_heads % tp:
                raise ValueError(
                    f"n_heads {self.n_heads} not divisible by the decode "
                    f"mesh's data={tp}")
        if self.compute_dtype is not None:
            raise NotImplementedError(
                "decode_program serves the f32 params path; compute_dtype "
                "casting would break the re-encode bit-identity contract")
        n_heads = self.n_heads
        n_layers = int(jax.tree_util.tree_leaves(
            self.params["blocks"])[0].shape[0])
        d_model = int(self.params["embed"].shape[1])
        hl, dh = n_heads // tp, d_model // n_heads   # a device's heads

        def layer(params, i):
            return jax.tree_util.tree_map(lambda a: a[i], params["blocks"])

        def blocks(params):
            return [layer(params, i) for i in range(n_layers)]

        def local_blocks(params):
            idx = jax.lax.axis_index("data")
            out = []
            for i in range(n_layers):
                bp = layer(params, i)
                lb = dict(bp)
                for w in ("Wq", "Wk", "Wv"):
                    lb[w] = bp[w].reshape(d_model, tp, hl * dh)[:, idx]
                lb["Wo"] = bp["Wo"].reshape(tp, hl * dh, d_model)[idx]
                out.append(lb)
            return out

        def head(params, h):
            h = layer_norm(h, params["lnf_g"], params["lnf_b"])
            return h @ params["head"]

        prog = paged_decode_program(
            embed=lambda params, ids: params["embed"][ids],
            pos=lambda params: params["pos"],
            blocks=blocks if tp == 1 else local_blocks, head=head,
            heads=(hl, dh), n_layers=n_layers, vocab_size=self.vocab_size,
            pos_rows=int(self.params["pos"].shape[0]), page_size=page_size,
            max_len=max_len, psum_axis=None if tp == 1 else "data")
        if tp == 1:
            return prog

        mesh = self.mesh
        rep = P()

        def pool_spec(pool):
            # heads lie side by side in a row, so a shard of the lane
            # axis is whole heads
            full = P(None, None, None, "data")
            if isinstance(pool, QuantPages):
                return QuantPages(full, rep)
            return full

        def wrap(body, n_rep=1):
            # the pool specs depend on the pool KIND, so the shard_map is
            # built at trace time (inside the engine's jit) where the
            # pytree is known; n_rep = number of replicated outputs after
            # the two pool sides.  The wrapper keeps the entry point's
            # name: the engine's executables are called after it
            @functools.wraps(body)
            def fn(params, k_pages, v_pages, *rest):
                ks, vs = pool_spec(k_pages), pool_spec(v_pages)
                sm = shard_map(
                    body, mesh=mesh,
                    in_specs=(rep, ks, vs) + (rep,) * len(rest),
                    out_specs=(ks, vs) + (rep,) * n_rep)
                return sm(params, k_pages, v_pages, *rest)
            return fn

        return prog._replace(
            prefill=wrap(prog.prefill), step=wrap(prog.step),
            prefill_at=wrap(prog.prefill_at),
            spec_step=wrap(prog.spec_step),
            step_multi=wrap(prog.step_multi, n_rep=3),
            reencode=shard_map(prog.reencode, mesh=mesh,
                               in_specs=(rep, rep), out_specs=rep),
            n_heads=n_heads, tp=tp)
