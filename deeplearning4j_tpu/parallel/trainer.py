"""ShardedTrainer — DP/TP training over a mesh.

The reference's ParallelWrapper (parallelism/ParallelWrapper.java:58: clone
model per device, round-robin DataSets into per-worker queues, average
params every ``averagingFrequency`` iterations via Nd4j.averageAndPropagate
:326) collapses into: put params on the mesh with TP shardings, put the
batch on the data axis, call the SAME jit step the single-device path uses.
GSPMD partitions the program; gradient allreduce appears as a fused psum
over ICI (the Aeron/NCCL role).  Per-step summation ≡ averagingFrequency=1,
mathematically stronger than the reference's periodic averaging.

Multi-host: call jax.distributed.initialize() first (the Spark master's
process-placement role is played by the launcher — GKE/Ray/mpirun), then
build the mesh over jax.devices() spanning all hosts.

Two-tier gradient exchange: when the mesh carries a ``dcn`` axis (slices
joined by data-center network rather than ICI), ``grad_compression=``
swaps the cross-slice tier of the gradient allreduce for the reference's
compressed protocol — EncodingHandler thresholdEncode/bitmapEncode with a
per-slice error-feedback residual (ops/compression.py).  The step becomes
an explicit shard_map: per-device grads → dense psum over the ICI
``data`` axis (tier 1, unchanged math) → bucketed encode + all_gather of
the ENCODED buffers over ``dcn`` + decode-sum (tier 2) → optimizer
update.  ``grad_compression=None`` keeps the original GSPMD path
bit-for-bit.
"""

from __future__ import annotations

from typing import List, Optional, TYPE_CHECKING

if TYPE_CHECKING:
    from ..optimize.score import LazyScore

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, set_mesh

from ..datasets.dataset import DataSet
from ..obs import trace as obs_trace
from ..datasets.iterators import DataSetIterator
from .mesh import (
    DATA_AXIS, DCN_AXIS, MODEL_AXIS, build_mesh, build_two_tier_mesh,
    infer_param_shardings, put_global, replicated,
)


class ShardedTrainer:
    """Wraps a MultiLayerNetwork or ComputationGraph for mesh training.

    >>> mesh = build_mesh({"data": 4, "model": 2})
    >>> trainer = ShardedTrainer(net, mesh)
    >>> trainer.fit(iterator, epochs=2)

    The wrapped net keeps working as usual afterwards; its params simply
    live sharded on the mesh.
    """

    @classmethod
    def two_tier(cls, net, n_slices: Optional[int] = None,
                 axes: Optional[dict] = None, **kwargs) -> "ShardedTrainer":
        """The pod-launch ceremony in one line: a trainer over
        ``build_two_tier_mesh`` sized by the multislice runtime.

        ``n_slices`` defaults to ``distributed.detect_num_slices()`` —
        the MEGASCALE env contract every worker of a Cloud TPU multislice
        job carries (the ``launch`` subcommand propagates it to forked
        workers in distributed mode) — so the same program runs 1-slice
        and N-slice unchanged:

            distributed.initialize(...)            # or `launch --join`
            trainer = ShardedTrainer.two_tier(
                net, grad_compression="threshold")

        All ShardedTrainer kwargs pass through (pair with
        ``grad_compression=`` to compress the cross-slice tier)."""
        if n_slices is None:
            from .distributed import detect_num_slices
            n_slices = detect_num_slices()
        return cls(net, build_two_tier_mesh(n_slices, axes), **kwargs)

    def __init__(self, net, mesh: Optional[Mesh] = None,
                 data_axis: str = DATA_AXIS, model_axis: str = MODEL_AXIS,
                 pipeline_schedule: str = "gpipe",
                 grad_compression: Optional[str] = None,
                 dcn_axis: str = DCN_AXIS,
                 compression_threshold: Optional[float] = None,
                 compression_bucket_mb: float = 4.0,
                 nan_guard: Optional[int] = None):
        from .pipeline import SCHEDULES
        from ..ops import compression as _compression
        if pipeline_schedule not in SCHEDULES:
            raise ValueError(f"pipeline_schedule must be one of {SCHEDULES}, "
                             f"got {pipeline_schedule!r}")
        if grad_compression is not None \
                and grad_compression not in _compression.METHODS:
            raise ValueError(
                f"grad_compression must be one of {_compression.METHODS} or "
                f"None, got {grad_compression!r}")
        self.net = net
        self.mesh = mesh if mesh is not None else build_mesh()
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.dcn_axis = dcn_axis
        # DCN-tier compressed exchange (reference EncodingHandler behind
        # SharedTrainingMaster): None = dense GSPMD psum everywhere (the
        # original path, bit-identical); "threshold"/"bitmap" = dense psum
        # over the ICI data axis + compressed exchange over the dcn axis
        # with per-slice error-feedback residuals
        self.grad_compression = grad_compression
        self.compression_threshold = compression_threshold
        self.compression_bucket_bytes = max(4, int(compression_bucket_mb
                                                   * (1 << 20)))
        self._compressed_step = None
        if grad_compression is not None:
            if dcn_axis not in self.mesh.shape:
                raise ValueError(
                    f"grad_compression={grad_compression!r} needs a "
                    f"{dcn_axis!r} mesh axis (build_two_tier_mesh) — got "
                    f"axes {dict(self.mesh.shape)}")
            for ax, size in self.mesh.shape.items():
                if ax not in (dcn_axis, data_axis) and size > 1:
                    raise ValueError(
                        f"grad_compression composes with dcn×data parallelism "
                        f"only (axis {ax!r} has size {size}); drop the axis "
                        "or run grad_compression=None")
        # divergence guard (opt-in; None = the exact pre-guard programs):
        # dense path rides the net's own guarded step; the compressed path
        # builds its guard into the two-tier shard_map step so a skipped
        # step ALSO skips residual accumulation — otherwise the error-
        # feedback state would absorb the poisoned gradient and replay it
        # on the next (healthy) step
        self.nan_guard = nan_guard
        self._bad_steps = 0
        if nan_guard is not None:
            if grad_compression is None:
                if not hasattr(net, "set_nan_guard"):
                    raise NotImplementedError(
                        f"nan_guard is not supported for "
                        f"{type(net).__name__} yet (needs set_nan_guard)")
                net.set_nan_guard(nan_guard)
        # microbatch order for nets that pipeline over a `pipe` axis
        # (parallel/pipeline.py): forwarded to the wrapped net when it
        # carries a schedule knob (ShardedTransformerLM); layer-stack nets
        # without a pipe dimension ignore it
        self.pipeline_schedule = pipeline_schedule
        if hasattr(net, "schedule"):
            net.schedule = pipeline_schedule
        # any dcn axis present ⇒ the batch spans both DP tiers, so dense
        # (GSPMD) and compressed runs shard identically and differ only in
        # how the gradient crosses the slow tier
        if dcn_axis in self.mesh.shape:
            self.batch_sharding = NamedSharding(
                self.mesh, P((dcn_axis, data_axis)))
        else:
            self.batch_sharding = NamedSharding(self.mesh, P(data_axis))
        self._place_model()

    # -- placement ---------------------------------------------------------

    def _place_model(self) -> None:
        """Move params/opt-state onto the mesh (TP rules), replicate state."""
        net = self.net
        self.param_shardings = infer_param_shardings(net.params, self.mesh, self.model_axis)
        net.params = jax.tree_util.tree_map(put_global, net.params,
                                            self.param_shardings)
        # opt state mirrors param shapes (Adam m/v etc.) → same shardings
        net.opt_state = self._put_like_params(net.opt_state)
        rep = replicated(self.mesh)
        net.state = jax.tree_util.tree_map(lambda a: put_global(a, rep),
                                           net.state)
        # ephemeral device scalars (rng key, device iteration counter) may
        # be committed to a PREVIOUS mesh (elastic resize) — pull to host
        # and let the next step recommit them under this mesh
        if getattr(net, "_rng", None) is not None:
            net._rng = jnp.asarray(np.asarray(net._rng))
        if getattr(net, "_it_dev", None) is not None:
            net._it_dev = None
        if self.grad_compression is not None:
            self._place_residual()

    def _place_residual(self) -> None:
        """Error-feedback residual: one params-shaped f32 tree PER SLICE
        (leading axis = dcn size, sharded on the dcn axis, replicated
        within the slice).  Adopts a residual already on the net — a
        checkpoint restore (utils/serializer.py format v3) or an elastic
        re-place — when its slice count still matches; otherwise starts
        from zeros (mathematically safe: error feedback only defers
        compression error, dropping it costs one step's deferral)."""
        net = self.net
        n_dcn = self.mesh.shape[self.dcn_axis]
        spec = NamedSharding(self.mesh, P(self.dcn_axis))
        existing = getattr(net, "grad_residual", None)
        leaves = jax.tree_util.tree_leaves(existing)
        if leaves and all(l.shape[0] == n_dcn for l in leaves):
            net.grad_residual = jax.tree_util.tree_map(
                lambda a: put_global(np.asarray(a, np.float32), spec),
                existing)
        else:
            net.grad_residual = jax.tree_util.tree_map(
                lambda p: put_global(
                    np.zeros((n_dcn,) + tuple(p.shape), np.float32), spec),
                net.params)

    def _put_like_params(self, opt_state):
        """Shard optimizer state structurally: per layer, each state subtree
        whose pytree structure matches the layer's params (Adam m/v,
        Nesterovs momentum, ...) gets the params' shardings leaf-for-leaf;
        anything else (scalars, mismatched trees) is replicated.  Structural
        mapping — never keyed by leaf shape — so per-layer sharding
        overrides can't silently leak across same-shaped layers."""
        rep = replicated(self.mesh)

        def place_layer(os_layer, p_layer, s_layer):
            if not os_layer:
                return os_layer
            p_struct = jax.tree_util.tree_structure(p_layer)

            def place_sub(sub):
                if jax.tree_util.tree_structure(sub) == p_struct:
                    return jax.tree_util.tree_map(put_global, sub, s_layer)
                return jax.tree_util.tree_map(
                    lambda a: put_global(a, rep), sub)

            return {k: place_sub(v) for k, v in os_layer.items()}

        params, shardings = self.net.params, self.param_shardings
        if isinstance(opt_state, list):
            return [place_layer(os, p, s)
                    for os, p, s in zip(opt_state, params, shardings)]
        return {k: place_layer(v, params[k], shardings[k])
                for k, v in opt_state.items()}

    # -- batch placement ---------------------------------------------------

    @staticmethod
    def _to_host_array(a):
        """Zero-copy host view: a numpy array passes through IDENTICALLY
        (``np.asarray`` on an ndarray subclass or list would materialize a
        fresh buffer — a redundant host copy of the whole batch, paid
        every step before the real H2D transfer)."""
        return a if type(a) is np.ndarray else np.asarray(a)

    def _shard_batch_arr(self, a):
        if a is None:
            return None
        if isinstance(a, jax.Array):
            # already on device: re-place only if the sharding differs —
            # never round-trip through host (a 224² imagenet batch is ~77MB;
            # re-uploading it every step would dominate the step time).
            # DevicePrefetchIterator batches placed with this trainer's
            # ``batch_sharding`` hit the pass-through.
            if a.sharding.is_equivalent_to(self.batch_sharding, a.ndim):
                return a
            return jax.device_put(a, self.batch_sharding)
        arr = self._to_host_array(a)
        dp = self.mesh.shape.get(self.data_axis, 1) \
            * self.mesh.shape.get(self.dcn_axis, 1)
        if arr.shape[0] % dp != 0:
            raise ValueError(
                f"global batch {arr.shape[0]} not divisible by data axis {dp} "
                "(pad or drop the remainder — XLA needs static shapes)")
        return put_global(arr, self.batch_sharding)

    def shard_dataset(self, ds: DataSet) -> DataSet:
        """Pre-place a batch on the mesh (public so callers that reuse a
        batch — benchmarks, eval loops — pay the host→device transfer
        once, not per step)."""
        return DataSet(
            self._shard_batch_arr(ds.features),
            None if ds.labels is None else jax.tree_util.tree_map(self._shard_batch_arr, ds.labels),
            self._shard_batch_arr(ds.features_mask),
            self._shard_batch_arr(ds.labels_mask),
        )


    # -- compressed two-tier step ------------------------------------------

    def _make_compressed_step(self):
        """Build the explicit two-tier train step (shard_map over dcn×data).

        The dense path lets GSPMD insert ONE psum spanning every DP axis;
        here the collective is split by tier: per-device grads are psum'd
        densely over the ICI ``data`` axis (tier 1 — same math XLA would
        emit), then each slice adds its error-feedback residual, encodes
        per bucket, and all_gathers only the ENCODED buffers over ``dcn``
        (tier 2).  Buckets are independent collectives, so XLA's
        latency-hiding scheduler overlaps bucket k's exchange with bucket
        k+1's encode/decode and the update math.  The decoded mean feeds
        the net's own ``_apply_updates`` — updater math, normalization
        and constraints are untouched."""
        from ..ops import compression as C

        net, mesh = self.net, self.mesh
        dcn, data = self.dcn_axis, self.data_axis
        n_data = mesh.shape.get(data, 1)
        method, thr = self.grad_compression, self.compression_threshold
        bucketer = C.GradBucketer(net.params, self.compression_bucket_bytes)
        is_graph = isinstance(net.params, dict)
        guard = self.nan_guard is not None

        def device_step(params, state, opt_state, it, x, y, rng, m, lm,
                        residual):
            # decorrelate per-device stochasticity (dropout/noise) the way
            # independent workers would; deterministic nets are unaffected
            di = jax.lax.axis_index(dcn) * n_data + jax.lax.axis_index(data)
            key = jax.random.fold_in(rng, di)

            def loss_fn(p):
                if is_graph:
                    return net._loss(p, state, x, y, train=True, rng=key,
                                     masks=m, label_masks=lm)
                return net._loss(p, state, x, y, train=True, rng=key,
                                 mask=m, label_mask=lm)

            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # tier 1: dense ICI allreduce — free at ICI bandwidth
            grads = jax.lax.pmean(grads, data)
            if guard:
                # divergence guard: decided BEFORE the compressed exchange
                # and agreed GLOBALLY (pmin over both DP tiers) — one
                # slice skipping while another applies would fork the
                # replicated params across slices
                ok = jnp.isfinite(loss)
                for g in jax.tree_util.tree_leaves(grads):
                    ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
                ok = jax.lax.pmin(ok.astype(jnp.int32), (data, dcn)) > 0
            # tier 2: bucketed compressed DCN exchange with error feedback.
            # acc = slice gradient + what previous steps failed to send;
            # the un-transmitted part of acc becomes the next residual —
            # compression error is deferred, never dropped (the
            # reference's residual accumulator, the property the
            # convergence-parity tests pin).
            res = jax.tree_util.tree_map(lambda a: a[0], residual)
            out_g, out_r = [], []
            for gb, rb in zip(bucketer.flatten(grads), bucketer.flatten(res)):
                acc = gb + rb
                mean_dec, local_dec = C.compressed_pmean(
                    acc, dcn, method, threshold=thr)
                out_g.append(mean_dec)
                out_r.append(acc - local_dec)
            grads = bucketer.unflatten(out_g)
            new_res = bucketer.unflatten(out_r, cast=False)
            new_params, new_opt = net._apply_updates(
                grads, params, opt_state, it.astype(jnp.float32))
            # keep replicated things replicated: batch-dependent state (BN
            # running stats) is averaged across every DP shard; loss is
            # reported as the global-batch mean
            new_state = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, (data, dcn))
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact) else a,
                new_state)
            loss = jax.lax.pmean(jax.lax.pmean(loss, data), dcn)
            if guard:
                # skip the WHOLE step on a non-finite gradient: params,
                # opt state, bn state, AND the error-feedback residual
                # stay bit-identical (the residual otherwise absorbs the
                # poisoned acc and re-injects it next step)
                sel = lambda n, o: jax.tree_util.tree_map(  # noqa: E731
                    lambda a, b: jnp.where(ok, a, b), n, o)
                new_params = sel(new_params, params)
                new_state = sel(new_state, state)
                new_opt = sel(new_opt, opt_state)
                new_res = sel(new_res, res)
                new_res = jax.tree_util.tree_map(lambda a: a[None], new_res)
                return (new_params, new_state, new_opt, new_res, loss,
                        ok.astype(jnp.int32))
            new_res = jax.tree_util.tree_map(lambda a: a[None], new_res)
            return new_params, new_state, new_opt, new_res, loss

        pb = P((dcn, data))
        out_specs = (P(), P(), P(), P(dcn), P()) + ((P(),) if guard else ())
        stepped = shard_map(
            device_step, mesh=mesh,
            in_specs=(P(), P(), P(), P(), pb, pb, P(), pb, pb, P(dcn)),
            out_specs=out_specs, check_vma=False)
        return jax.jit(stepped, donate_argnums=(0, 1, 2, 9))

    def _fit_batch_compressed(self, ds: DataSet):
        from ..optimize.score import LazyScore
        net = self.net
        if getattr(net.conf, "backprop_type", "standard") == "tbptt":
            raise NotImplementedError(
                "grad_compression does not compose with TBPTT yet — the "
                "chunk scan applies updates inside the step; run "
                "grad_compression=None")
        with set_mesh(self.mesh):
            ds = self.shard_dataset(ds)
            if self._compressed_step is None:
                self._compressed_step = self._make_compressed_step()
            net._rng, sub = jax.random.split(net._rng)
            x, y = ds.features, ds.labels
            m, lm = ds.features_mask, ds.labels_mask
            if isinstance(net.params, dict):  # ComputationGraph calling
                x = {net.conf.network_inputs[0]: x}
                y = {net.conf.network_outputs[0]: y}
                m = {net.conf.network_inputs[0]: m}
                lm = {net.conf.network_outputs[0]: lm}
            # one span for the fused step: the two-tier grad exchange
            # (dense ICI psum + compressed DCN) runs INSIDE this program,
            # so the host-side span is the whole dispatch — use the XLA
            # profiler (ui/profiler.py) for the on-device breakdown
            with obs_trace.span("train/step", cat="train",
                                iteration=net.iteration + 1,
                                path="compressed_exchange"):
                with obs_trace.span("train/dispatch", cat="train"):
                    outs = self._compressed_step(
                        net.params, net.state, net.opt_state,
                        net._iter_scalar(1), x, y, sub, m, lm,
                        net.grad_residual)
            (net.params, net.state, net.opt_state, net.grad_residual,
             loss) = outs[:5]
            net.iteration += 1
            if self.nan_guard is not None:
                self._note_guarded_step(bool(outs[5]))
            score = LazyScore(loss)
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration, score)
            return score

    def _note_guarded_step(self, ok: bool) -> None:
        """Budget accounting for the compressed path's guard (the dense
        path uses the net's own counter — same semantics)."""
        from ..nn.multilayer import DivergenceError
        import logging

        if ok:
            self._bad_steps = 0
            return
        self._bad_steps += 1
        logging.getLogger("deeplearning4j_tpu").warning(
            "non-finite gradients at iteration %d (compressed exchange) — "
            "update + residual accumulation skipped (%d/%d bad steps)",
            self.net.iteration, self._bad_steps, self.nan_guard)
        if self._bad_steps > self.nan_guard:
            # self-resetting on escalation (same semantics as the net's
            # guard): the catcher restores a checkpoint and the fresh run
            # gets a fresh budget
            bad, self._bad_steps = self._bad_steps, 0
            raise DivergenceError(bad, self.nan_guard)

    # -- training ----------------------------------------------------------

    def fit_batch(self, ds: DataSet) -> float:
        """One global step: batch split over the DP axes; grads psum'd by
        GSPMD (dense) or exchanged per tier when ``grad_compression`` is
        set (dense ICI psum + compressed DCN exchange)."""
        if self.grad_compression is not None:
            return self._fit_batch_compressed(ds)
        with set_mesh(self.mesh):
            return self.net.fit_batch(self.shard_dataset(ds))

    def fit_batches(self, batches) -> List["LazyScore"]:
        """k steps in ONE dispatch (the container's scanned multi-step),
        each batch data-sharded on the mesh.  Returns [k] LazyScores
        (device-resident; float() forces the readback — the fit_batch
        contract).  Compressed runs fall back to per-batch steps: the
        residual threads THROUGH the exchange, so steps cannot be fused
        into one scan without replaying the whole tier-2 pipeline there."""
        if self.grad_compression is not None:
            return [self._fit_batch_compressed(ds) for ds in batches]
        with set_mesh(self.mesh):
            return self.net.fit_batches(
                [self.shard_dataset(ds) for ds in batches])

    def fit(self, data, epochs: int = 1) -> List[float]:
        losses = []
        it = self.net._as_iterator(data)
        synced = 0
        for _ in range(epochs):
            for ds in it:
                losses.append(self.fit_batch(ds))
            # the container's own epoch epilogue — mesh mode must not
            # diverge from plain training (scores, counter, epoch_done)
            synced = self.net._end_epoch(losses, synced)
        return losses

    def output(self, x, **kw):
        with set_mesh(self.mesh):
            return self.net.output(self._shard_batch_arr(x), **kw)
