"""MultiLayerNetwork — the sequential-stack model container.

Parity target: reference nn/multilayer/MultiLayerNetwork.java (3,177 LoC):
``init():545`` (param flattening), ``fit(DataSetIterator):1165``,
``backprop():1260``, ``output():1867``, score accumulation, masking, and the
Solver/updater wiring (optimize/solvers/StochasticGradientDescent.java:58).

Design inversion (SURVEY.md §7): instead of the reference's eager per-op
forward + hand-written ``calcBackpropGradients`` loop + mutable flat param
buffer, the entire step — forward, loss, backward (jax.grad), gradient
normalization (preApply parity), per-layer updater math, and the parameter
update — is ONE jit-compiled XLA program.  Params/state/opt-state are
pytrees (list of per-layer dicts, keys matching the reference's param names
"W"/"b"/"RW"/"gamma"/...); donation avoids double-buffering params in HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..datasets.dataset import DataSet
from ..datasets.iterators import DataSetIterator, ListDataSetIterator
from ..obs import trace as obs_trace
from .conf.inputs import InputType
from .conf.preprocessors import Preprocessor
from .conf.regularizers import apply_constraints, maybe_weight_noise
from .layers.base import Layer, config_from_dict, config_to_dict
from .updaters import Adam, GradientNormalization, Updater, normalize_gradients
from ..optimize.score import LazyScore, materialize_scores

Array = jax.Array


def _as_device(a):
    """Device-array passthrough for batch leaves: an already-device-resident
    array (DevicePrefetchIterator output, a pre-sharded mesh batch, a
    reused benchmark batch) enters the step untouched — no fresh host
    staging, no re-placement, and in particular never a device→host→device
    round trip.  Host arrays take the ordinary ``jnp.asarray`` upload."""
    if a is None or isinstance(a, jax.Array):
        return a
    return jnp.asarray(a)


class DivergenceError(RuntimeError):
    """The opt-in divergence guard exhausted its bad-step budget: too many
    consecutive steps produced non-finite gradients/loss, so skipping
    updates is no longer masking a transient (bad batch, overflow spike)
    but a diverged run.  The message carries the "non-finite gradient"
    marker the elastic FailureDetector recognizes, so an ElasticTrainer
    wrapping this net escalates to checkpoint-restore instead of dying."""

    def __init__(self, bad_steps: int, budget: int):
        super().__init__(
            f"non-finite gradients for {bad_steps} consecutive steps "
            f"(budget {budget}) — updates were skipped but the run is "
            "diverging; restore a checkpoint (ElasticTrainer recovers this "
            "automatically) or lower the learning rate")
        self.bad_steps = bad_steps
        self.budget = budget


@dataclasses.dataclass
class MultiLayerConfiguration:
    """Configs-as-data for a sequential net (reference
    MultiLayerConfiguration + per-layer NeuralNetConfiguration).  JSON
    round-trip via ``to_dict``/``from_dict`` is the serialization contract
    that checkpointing, transfer learning, and the zoo build on (reference
    nn/conf/serde/)."""

    layers: List[Layer] = dataclasses.field(default_factory=list)
    input_type: Optional[InputType] = None
    preprocessors: Dict[int, Preprocessor] = dataclasses.field(default_factory=dict)
    updater: Updater = dataclasses.field(default_factory=Adam)
    gradient_normalization: str = GradientNormalization.NONE
    gradient_normalization_threshold: float = 1.0
    seed: int = 12345
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    backprop_type: str = "standard"       # or "tbptt"
    tbptt_length: int = 20

    def to_dict(self) -> dict:
        d = config_to_dict(self)
        d["type"] = "MultiLayerConfiguration"
        d["preprocessors"] = {str(k): config_to_dict(v) for k, v in self.preprocessors.items()}
        d["input_type"] = None if self.input_type is None else self.input_type.to_dict()
        return d

    @staticmethod
    def from_dict(d: dict) -> "MultiLayerConfiguration":
        d = dict(d)
        d.pop("type", None)
        pre = {int(k): config_from_dict(v) for k, v in (d.pop("preprocessors") or {}).items()}
        it = d.pop("input_type")
        conf = MultiLayerConfiguration(
            layers=[config_from_dict(l) for l in d.pop("layers")],
            input_type=None if it is None else InputType.from_dict(it),
            preprocessors=pre,
            updater=config_from_dict(d.pop("updater")),
            **{k: v for k, v in d.items()},
        )
        return conf


class ListBuilder:
    """Fluent builder parity with NeuralNetConfiguration.Builder().list()
    (reference NeuralNetConfiguration.java:206-303)."""

    def __init__(self, **defaults):
        self._conf = MultiLayerConfiguration()
        self._defaults = defaults

    def seed(self, s: int) -> "ListBuilder":
        self._conf.seed = s
        return self

    def updater(self, u: Updater) -> "ListBuilder":
        self._conf.updater = u
        return self

    def gradient_normalization(self, mode: str, threshold: float = 1.0) -> "ListBuilder":
        self._conf.gradient_normalization = mode
        self._conf.gradient_normalization_threshold = threshold
        return self

    def layer(self, layer: Layer) -> "ListBuilder":
        for k, v in self._defaults.items():
            # apply builder-level defaults to layers that kept dataclass defaults
            if hasattr(layer, k) and getattr(layer, k) == type(layer).__dataclass_fields__[k].default:
                setattr(layer, k, v)
        self._conf.layers.append(layer)
        return self

    def preprocessor(self, index: int, pre: Preprocessor) -> "ListBuilder":
        self._conf.preprocessors[index] = pre
        return self

    def set_input_type(self, t: InputType) -> "ListBuilder":
        self._conf.input_type = t
        return self

    def tbptt(self, length: int) -> "ListBuilder":
        self._conf.backprop_type = "tbptt"
        self._conf.tbptt_length = length
        return self

    def dtype(self, param_dtype: str = "float32", compute_dtype: str = "float32") -> "ListBuilder":
        self._conf.param_dtype = param_dtype
        self._conf.compute_dtype = compute_dtype
        return self

    def build(self) -> MultiLayerConfiguration:
        return self._conf


class NeuralNetConfiguration:
    """Entry point mirroring the reference's builder DSL."""

    @staticmethod
    def builder(**defaults) -> ListBuilder:
        return ListBuilder(**defaults)


class MultiLayerNetwork:
    """Sequential model: init / fit / output / score / evaluate.

    Functional core, stateful shell: ``params``/``state``/``opt_state`` live
    on the object for the user-facing API (like the reference's mutable
    model), but every computation runs through pure jit'd functions.
    """

    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.params: List[Dict[str, Array]] = []
        self.state: List[Dict[str, Array]] = []
        self.opt_state: List[Dict] = []
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self.input_types: List[InputType] = []
        self._jit_step = None
        self._jit_step_guarded = None
        self._nan_guard_budget: Optional[int] = None
        self._bad_steps = 0
        self._jit_multi_step = None
        self._jit_step_tbptt = None
        self._jit_step_tbptt_scan = None
        self._it_dev = None        # device-resident iteration counter
        self._it_dev_val = -1      # python value _it_dev mirrors
        self._jit_output = None
        self._jit_score = None
        self._jit_score_examples = None
        self._jit_recon_logprob: Dict = {}
        self._jit_stream = None
        self._stream_carries = None
        self._rng = jax.random.PRNGKey(conf.seed)
        self._infer_types()

    # ------------------------------------------------------------------
    # shape inference + init
    # ------------------------------------------------------------------

    def _infer_types(self) -> None:
        """Propagate InputType through preprocessors+layers, auto-inserting
        shape adapters where the layer's expected kind mismatches (the
        reference's setInputType + getPreProcessorForInputType pass)."""
        from .conf.preprocessors import CnnToFeedForward, CnnToRnn, FeedForwardToCnn
        self.input_types = []
        t = self.conf.input_type
        if t is None:
            return
        for i, layer in enumerate(self.conf.layers):
            if i in self.conf.preprocessors:
                t = self.conf.preprocessors[i].output_type(t)
            elif layer.wants is not None and t.kind != layer.wants:
                pre = None
                if t.kind == "cnn" and layer.wants == "ff":
                    pre = CnnToFeedForward()
                elif t.kind == "cnn_flat" and layer.wants == "cnn":
                    pre = FeedForwardToCnn(t.height, t.width, t.channels)
                elif t.kind == "cnn_flat" and layer.wants == "ff":
                    t = InputType.feed_forward(t.flat_size())
                elif t.kind == "cnn" and layer.wants == "rnn":
                    pre = CnnToRnn()
                elif t.kind == "rnn" and layer.wants == "ff":
                    pre = None  # Dense-family layers broadcast over time
                if pre is not None:
                    self.conf.preprocessors[i] = pre
                    t = pre.output_type(t)
            self.input_types.append(t)
            layer.infer_nin(t)
            t = layer.output_type(t)
        self.output_type = t

    def init(self, rng: Optional[Array] = None) -> None:
        """Initialize params/state (reference init():545; param views become
        per-layer dicts — no flat buffer needed, XLA fuses updates)."""
        if not self.input_types:
            raise ValueError("conf.input_type must be set before init() "
                             "(or call set_input_type on the builder)")
        rng = rng if rng is not None else self._rng
        dtype = jnp.dtype(self.conf.param_dtype)
        keys = jax.random.split(rng, len(self.conf.layers))
        self.params, self.state, self.opt_state = [], [], []
        for layer, k, t in zip(self.conf.layers, keys, self.input_types):
            p = layer.init_params(k, t, dtype)
            s = layer.init_state(t, dtype)
            self.params.append(p)
            self.state.append(s)
            self.opt_state.append(self._updater_for(layer).init_state(p) if p else {})
        self.iteration = 0

    def _updater_for(self, layer: Layer) -> Updater:
        return layer.updater if layer.updater is not None else self.conf.updater

    def _iter_scalar(self, advance: int):
        from ..utils import device_iteration
        return device_iteration(self, advance)

    def num_params(self) -> int:
        return sum(int(np.prod(x.shape)) for p in self.params for x in jax.tree_util.tree_leaves(p))

    def summary(self) -> str:
        """Layer table: name, output shape, param count (reference
        MultiLayerNetwork.summary():3702)."""
        if not self.params:
            raise ValueError("call init() before summary()")
        rows = [("idx", "layer", "out", "params")]
        for i, (layer, p) in enumerate(zip(self.conf.layers, self.params)):
            n = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(p))
            # the layer's OWN output type — input_types[i+1] would show the
            # next layer's post-preprocessor input instead (e.g. a conv
            # layer reporting the flattened CnnToFeedForward shape)
            out = layer.output_type(self.input_types[i])
            rows.append((str(i), type(layer).__name__, str(out), f"{n:,}"))
        widths = [max(len(r[c]) for r in rows) for c in range(4)]
        lines = ["  ".join(v.ljust(w) for v, w in zip(r, widths)) for r in rows]
        lines.insert(1, "-" * len(lines[0]))
        lines.append(f"total params: {self.num_params():,}")
        return "\n".join(lines)

    # ------------------------------------------------------------------
    # pure forward / loss
    # ------------------------------------------------------------------

    def _apply_layers(self, params, state, x, *, train: bool, rng, mask,
                      upto: Optional[int] = None, carries=None):
        """Run layers [0, upto) returning (y, new_state, mask, activations,
        new_carries).

        ``upto=None`` runs all layers.  The activations list is the
        feedForwardToLayer capture (reference MultiLayerNetwork.java:893) —
        under jit, unused entries are DCE'd so capture is free unless used.
        ``carries`` (list per layer or None) threads recurrent hidden state
        for TBPTT / streaming (reference rnnActivateUsingStoredState).
        """
        n = len(self.conf.layers) if upto is None else upto
        # layers needing the compute dtype independent of their input's
        # dtype (integer-index LSTM inputs) read it from this attribute —
        # refreshed per trace because conf.compute_dtype is user-mutable
        for layer in self.conf.layers:
            layer._compute_dtype = self.conf.compute_dtype
        new_state = list(state)
        new_carries = list(carries) if carries is not None else [None] * len(self.conf.layers)
        acts: List[Array] = []
        x = x.astype(jnp.dtype(self.conf.compute_dtype)) if jnp.issubdtype(x.dtype, jnp.floating) else x
        keys = jax.random.split(rng, n) if (rng is not None and n > 0) else [None] * n
        for i in range(n):
            layer = self.conf.layers[i]
            if i in self.conf.preprocessors:
                pre = self.conf.preprocessors[i]
                if getattr(pre, "wants_rng", False) and keys[i] is not None:
                    # stochastic preprocessors (BinomialSampling) draw fresh
                    # noise from the per-step stream during training
                    x = pre.apply(x, rng=jax.random.fold_in(keys[i], 13))
                else:
                    x = pre.apply(x)
            kwargs = {}
            if layer.recurrent and carries is not None:
                kwargs["carry"] = carries[i]
            p_i = maybe_weight_noise(layer, params[i], train, keys[i])
            out = layer.forward(p_i, state[i], x, train=train, rng=keys[i],
                                mask=mask, **kwargs)
            x, mask = out.y, out.mask
            new_state[i] = out.state
            new_carries[i] = out.carry
            acts.append(x)
        return x, new_state, mask, acts, new_carries

    def _loss(self, params, state, x, labels, *, train: bool, rng,
              mask=None, label_mask=None, carries=None):
        """Full score: output-layer loss + L1/L2 (reference computeGradientAndScore)."""
        n = len(self.conf.layers)
        h, new_state, mask_out, _, new_carries = self._apply_layers(
            params, state, x, train=train, rng=rng, mask=mask, upto=n - 1, carries=carries)
        last = self.conf.layers[n - 1]
        if (n - 1) in self.conf.preprocessors:
            pre = self.conf.preprocessors[n - 1]
            if getattr(pre, "wants_rng", False) and rng is not None:
                h = pre.apply(h, rng=jax.random.fold_in(rng, 20_000 + n))
            else:
                h = pre.apply(h)
        if train and rng is not None:
            # output layers honor input dropout too (reference BaseOutputLayer);
            # _maybe_dropout no-ops when the layer has no dropout configured
            h = last._maybe_dropout(h, train, jax.random.fold_in(rng, n - 1))
        lm = label_mask if label_mask is not None else (mask_out if labels is not None and getattr(labels, "ndim", 0) == 3 else None)
        if not hasattr(last, "score"):
            raise ValueError(f"last layer {type(last).__name__} has no score(); "
                             "use OutputLayer/LossLayer/RnnOutputLayer")
        loss = last.score(params[n - 1], state[n - 1], h, labels, mask=lm)
        if train and hasattr(last, "update_centers"):
            # center-loss moving-average update rides the state path
            new_state[n - 1] = last.update_centers(
                state[n - 1], jax.lax.stop_gradient(h), jax.lax.stop_gradient(labels))
        # accumulate in f64 when computing in f64 (gradient checks), else f32
        acc = jnp.float64 if jnp.dtype(self.conf.compute_dtype) == jnp.float64 else jnp.float32
        reg = jnp.zeros((), acc)
        for layer, p in zip(self.conf.layers, params):
            if p:
                reg = reg + layer.regularization_score(p).astype(acc)
        if train:
            from .layers.base import AUX_LOSS_KEY
            for s in new_state:
                if isinstance(s, dict) and AUX_LOSS_KEY in s:
                    reg = reg + s[AUX_LOSS_KEY].astype(acc)
        total = loss.astype(acc) + reg
        if carries is not None:
            return total, (new_state, new_carries)
        return total, new_state

    # ------------------------------------------------------------------
    # train step (jit once, reuse across iterations)
    # ------------------------------------------------------------------

    def _apply_updates(self, grads, params, opt_state, itf):
        """Shared updater application (the reference's BaseMultiLayerUpdater
        update loop: preApply normalization + per-block updater math)."""
        conf = self.conf
        new_params, new_opt = [], []
        for i, layer in enumerate(conf.layers):
            g, p, os = grads[i], params[i], opt_state[i]
            if not p:
                new_params.append(p)
                new_opt.append(os)
                continue
            if conf.gradient_normalization != GradientNormalization.NONE:
                g = normalize_gradients(g, conf.gradient_normalization,
                                        conf.gradient_normalization_threshold)
            # L2/L1 gradient contribution comes via autodiff of the reg score.
            # apply = updater math + param step; Adam/Nadam route through
            # the fused one-pass kernel (ops/update_kernel.py) when enabled
            p2, os2 = self._updater_for(layer).apply(p, g, os, itf)
            if layer.constraints:
                p2 = apply_constraints(layer.constraints, p2)
            new_params.append(p2)
            new_opt.append(os2)
        return new_params, new_opt

    def _make_step(self):
        def step(params, state, opt_state, it, x, labels, rng, mask, label_mask):
            def loss_fn(p):
                loss, new_state = self._loss(p, state, x, labels, train=True, rng=rng,
                                             mask=mask, label_mask=label_mask)
                return loss, new_state

            (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(grads, params, opt_state,
                                                      it.astype(jnp.float32))
            return new_params, new_state, new_opt, loss

        return jax.jit(step, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    # divergence guard (opt-in)
    # ------------------------------------------------------------------

    def set_nan_guard(self, budget: Optional[int] = 3) -> None:
        """Opt-in divergence guard: every step checks loss + gradients for
        NaN/Inf in-program; a non-finite step applies NO update (params,
        optimizer state, and batch-norm state come back bit-identical) and
        burns one unit of ``budget``.  ``budget`` consecutive bad steps
        raise :class:`DivergenceError` — recoverable under ElasticTrainer,
        which restores the last checkpoint.  ``budget=None`` disables the
        guard; disabled (the default) the training step is the exact same
        jitted program as before — zero cost, bit-identical.

        Cost when enabled: the per-step skipped/ok flag is read on host,
        which turns the async fit_batch chain into one device sync per
        step.  Use it for runs where a poisoned step costs more than the
        sync (large-scale / long-horizon training), not for microbenchmarks.
        """
        if budget is not None and budget < 1:
            raise ValueError(f"nan guard budget must be >= 1, got {budget}")
        self._nan_guard_budget = budget
        self._bad_steps = 0

    @staticmethod
    def _grads_finite(loss, grads):
        """Scalar bool: loss and every gradient leaf are finite."""
        ok = jnp.isfinite(loss)
        for g in jax.tree_util.tree_leaves(grads):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
        return ok

    @staticmethod
    def _select_tree(ok, new, old):
        """tree of where(ok, new, old) — the guarded step's skip switch.
        jnp.where keeps the OLD bits exactly when ok is False (NaNs in the
        rejected branch do not propagate through a select)."""
        return jax.tree_util.tree_map(
            lambda n, o: jnp.where(ok, n, o), new, old)

    def _make_step_guarded(self):
        """_make_step plus the in-program non-finite check: same math on
        the good path, but a step whose loss or gradients contain NaN/Inf
        returns the INPUT params/state/opt-state unchanged (bit-identical)
        together with ok=False, so the host can count bad steps against
        the budget.  Built only when the guard is enabled — the default
        path keeps its exact pre-guard program."""
        def step(params, state, opt_state, it, x, labels, rng, mask, label_mask):
            def loss_fn(p):
                loss, new_state = self._loss(p, state, x, labels, train=True,
                                             rng=rng, mask=mask,
                                             label_mask=label_mask)
                return loss, new_state

            (loss, new_state), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            ok = self._grads_finite(loss, grads)
            new_params, new_opt = self._apply_updates(grads, params, opt_state,
                                                      it.astype(jnp.float32))
            return (self._select_tree(ok, new_params, params),
                    self._select_tree(ok, new_state, state),
                    self._select_tree(ok, new_opt, opt_state), loss, ok)

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _note_guarded_step(self, ok: bool) -> None:
        """Host-side budget accounting shared by the plain and sharded
        guarded steps: reset on a good step, escalate past the budget."""
        if ok:
            self._bad_steps = 0
            return
        self._bad_steps += 1
        import logging
        logging.getLogger("deeplearning4j_tpu").warning(
            "non-finite gradients at iteration %d — update skipped "
            "(%d/%d bad steps)", self.iteration, self._bad_steps,
            self._nan_guard_budget)
        if self._bad_steps > self._nan_guard_budget:
            # self-resetting: the raise IS the escalation — whoever catches
            # it (ElasticTrainer) restores a checkpoint, and the fresh run
            # deserves a fresh budget, not an instant re-raise
            bad, self._bad_steps = self._bad_steps, 0
            raise DivergenceError(bad, self._nan_guard_budget)

    def _fit_batch_guarded(self, ds: DataSet):
        """fit_batch through the guarded step (set_nan_guard enabled)."""
        if self._jit_step_guarded is None:
            self._jit_step_guarded = self._make_step_guarded()
        self._rng, sub = jax.random.split(self._rng)
        with obs_trace.span("train/step", cat="train", guarded=True,
                            iteration=self.iteration + 1):
            with obs_trace.span("train/h2d", cat="train"):
                x = _as_device(ds.features)
                y = (None if ds.labels is None
                     else jax.tree_util.tree_map(_as_device, ds.labels))
                m = _as_device(ds.features_mask)
                lm = _as_device(ds.labels_mask)
            with obs_trace.span("train/dispatch", cat="train"):
                self.params, self.state, self.opt_state, loss, ok = \
                    self._jit_step_guarded(
                        self.params, self.state, self.opt_state,
                        self._iter_scalar(1), x, y, sub, m, lm)
        self.iteration += 1
        # the guard's documented cost: reading the flag is a device sync
        self._note_guarded_step(bool(ok))
        score = LazyScore(loss)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, score)
        return score

    def _make_step_tbptt(self):
        """One TBPTT chunk step: like _make_step but threads recurrent
        carries; truncation is automatic because each chunk is its own
        value_and_grad (reference doTruncatedBPTT():1386).  Used for the
        tail chunk when T % tbptt_length != 0."""
        def step(params, state, opt_state, it, x, labels, rng, mask, label_mask, carries):
            def loss_fn(p):
                loss, aux = self._loss(p, state, x, labels, train=True, rng=rng,
                                       mask=mask, label_mask=label_mask, carries=carries)
                return loss, aux

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(grads, params, opt_state,
                                                      it.astype(jnp.float32))
            return new_params, new_state, new_opt, new_carries, loss

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _make_step_tbptt_scan(self):
        """Whole-batch TBPTT: every T//L chunk optimizer-step runs inside
        ONE jit via lax.scan — one upload + one dispatch per minibatch
        instead of per chunk.  The reference walks chunks in a Java loop
        (doTruncatedBPTT():1386); on a remote TPU each interleaved
        host→device upload costs ~45ms of serialized latency, so chunk
        steps must be fused device-side.  Semantics identical: sequential
        chunk steps, carries threaded, per-chunk iteration counter."""
        L = self.conf.tbptt_length

        def step(params, state, opt_state, it0, x, labels, rng, mask,
                 label_mask, carries):
            n = x.shape[1] // L
            mb = x.shape[0]
            if carries is None:
                # carry init traced into the program — no per-batch eager
                # zeros dispatches on the host
                dtype = jnp.dtype(self.conf.compute_dtype)
                carries = [l.init_carry(mb, dtype) if l.recurrent else None
                           for l in self.conf.layers]

            def chunkify(a):
                """[mb, n·L, ...] → [n, mb, L, ...] scan-major."""
                if a is None:
                    return None
                a2 = a.reshape((a.shape[0], n, L) + a.shape[2:])
                return jnp.moveaxis(a2, 1, 0)

            xs = chunkify(x)
            ys = jax.tree_util.tree_map(chunkify, labels)
            ms = chunkify(mask)
            lms = chunkify(label_mask)
            keys = jax.random.split(rng, n + 1)
            its = it0 + jnp.arange(n, dtype=jnp.int32)

            def body(carry, inp):
                params, state, opt_state, carries = carry
                xc, yc, mc, lmc, k, it = inp

                def loss_fn(p):
                    loss, aux = self._loss(p, state, xc, yc, train=True,
                                           rng=k, mask=mc, label_mask=lmc,
                                           carries=carries)
                    return loss, aux

                (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                new_params, new_opt = self._apply_updates(
                    grads, params, opt_state, it.astype(jnp.float32))
                return (new_params, new_state, new_opt, new_carries), loss

            (params, state, opt_state, carries), losses = jax.lax.scan(
                body, (params, state, opt_state, carries),
                (xs, ys, ms, lms, keys[:n], its))
            # mean + fresh rng computed in-program: a fit_batch with no
            # tail chunk runs exactly ONE device dispatch
            return (params, state, opt_state, carries, losses,
                    jnp.mean(losses), keys[n])

        return jax.jit(step, donate_argnums=(0, 1, 2))

    # ------------------------------------------------------------------
    # layerwise unsupervised pretraining
    # ------------------------------------------------------------------

    def pretrainable_layers(self) -> List[int]:
        """Indices of layers with an unsupervised objective (reference
        Layer.isPretrainLayer(): RBM, AutoEncoder, VariationalAutoencoder)."""
        return [i for i, l in enumerate(self.conf.layers)
                if hasattr(l, "contrastive_divergence")
                or hasattr(l, "reconstruction_score")]

    def pretrain(self, data, epochs: int = 1) -> Dict[int, List[float]]:
        """Greedy layerwise unsupervised pretraining (reference
        MultiLayerNetwork.pretrain(DataSetIterator):220): each pretrainable
        layer is trained on features produced by the (already-pretrained)
        layers below it, in order; supervised layers are skipped.  Labels
        in the iterator are ignored.  Follow with ``fit`` for the classic
        pretrain→fine-tune workflow.  Returns {layer_index: losses}."""
        return {i: self.pretrain_layer(i, data, epochs)
                for i in self.pretrainable_layers()}

    def pretrain_layer(self, i: int, data, epochs: int = 1) -> List[float]:
        """Unsupervised pretraining of layer ``i`` only (reference
        pretrainLayer:243): inputs are featurized through layers [0, i)
        in inference mode (no dropout — the layer's own corruption/sampling
        is the only noise source), then the layer's objective — CD-k for
        RBM, reconstruction loss for AutoEncoder, negative ELBO for VAE —
        is driven through the layer's REAL updater (schedules, momentum,
        Adam moments — the reference also routes RBM Gibbs statistics
        through the normal Solver/updater path).  Featurize + objective +
        update run as ONE jitted program per batch."""
        layer = self.conf.layers[i]
        is_rbm = hasattr(layer, "cd_gradients")
        if not is_rbm and not hasattr(layer, "reconstruction_score"):
            raise ValueError(
                f"layer {i} ({type(layer).__name__}) has no unsupervised "
                "objective — pretrainable layers: RBM (contrastive "
                "divergence), AutoEncoder / VariationalAutoencoder "
                "(reconstruction/ELBO)")
        updater = self._updater_for(layer)

        def step(params, state, opt_i, it, x, rng):
            feat, _, _, _, _ = self._apply_layers(
                params, state, x, train=False, rng=None, mask=None, upto=i)
            if i in self.conf.preprocessors:
                pre = self.conf.preprocessors[i]
                if getattr(pre, "wants_rng", False):
                    # stochastic preprocessors (BinomialSampling) must draw
                    # FRESH noise per batch, as in the fit path
                    feat = pre.apply(feat, rng=jax.random.fold_in(rng, 13))
                else:
                    feat = pre.apply(feat)
            if is_rbm:
                g, loss = layer.cd_gradients(params[i], feat, rng)
            else:
                loss, g = jax.value_and_grad(
                    lambda p: layer.reconstruction_score(
                        p, feat, rng=rng, train=True))(params[i])
            if self.conf.gradient_normalization != GradientNormalization.NONE:
                g = normalize_gradients(
                    g, self.conf.gradient_normalization,
                    self.conf.gradient_normalization_threshold)
            p2, opt2 = updater.apply(params[i], g, opt_i, it)
            if layer.constraints:
                p2 = apply_constraints(layer.constraints, p2)
            return p2, opt2, loss

        jit_step = jax.jit(step, donate_argnums=(2,))
        losses: List[float] = []
        it = 0
        for _ in range(epochs):
            for ds in self._as_iterator(data):
                self._rng, sub = jax.random.split(self._rng)
                self.params[i], self.opt_state[i], loss = jit_step(
                    self.params, self.state, self.opt_state[i],
                    np.float32(it), jnp.asarray(ds.features), sub)
                it += 1
                losses.append(LazyScore(loss))
        materialize_scores(losses)
        return losses

    def fit_batch(self, ds: DataSet):
        """One optimization step on one minibatch (reference fit(DataSet)).

        Returns the loss as a :class:`LazyScore` — a float-like view of the
        device scalar that only syncs when read, so chained ``fit_batch``
        calls keep the TPU busy with zero per-step host round trips (the
        readback the reference pays at MultiLayerNetwork.java:1165)."""
        if self.conf.backprop_type == "tbptt":
            if self._nan_guard_budget is not None:
                raise NotImplementedError(
                    "the nan guard does not compose with TBPTT yet — chunk "
                    "steps apply updates inside a scan; run with "
                    "set_nan_guard(None)")
            return self._fit_batch_tbptt(ds)
        if self._nan_guard_budget is not None:
            return self._fit_batch_guarded(ds)
        if self._jit_step is None:
            self._jit_step = self._make_step()
        self._rng, sub = jax.random.split(self._rng)
        # span taxonomy (docs/OBSERVABILITY.md): train/step wraps the
        # host side of one optimizer step; h2d is the batch staging,
        # dispatch the fused XLA program (fwd+bwd+grad-exchange+update
        # run on device inside it).  No-ops when tracing is off.
        with obs_trace.span("train/step", cat="train",
                            iteration=self.iteration + 1):
            with obs_trace.span("train/h2d", cat="train"):
                # device-resident batches (DevicePrefetchIterator /
                # pre-sharded mesh input) pass through _as_device untouched
                x = _as_device(ds.features)
                # labels may be a pytree (e.g. Yolo2OutputLayer's dict
                # targets)
                y = (None if ds.labels is None
                     else jax.tree_util.tree_map(_as_device, ds.labels))
                m = _as_device(ds.features_mask)
                lm = _as_device(ds.labels_mask)
            with obs_trace.span("train/dispatch", cat="train"):
                self.params, self.state, self.opt_state, loss = self._jit_step(
                    self.params, self.state, self.opt_state,
                    self._iter_scalar(1), x, y, sub, m, lm)
        self.iteration += 1
        score = LazyScore(loss)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, score)
        return score

    def _make_multi_step(self):
        """k optimizer steps fused into ONE dispatch via lax.scan over
        stacked batches: chaining k steps amortizes the per-step host
        dispatch gap to 1/k (its size on the current chip: not measured).
        Update math and iteration counters match k fit_batch calls
        exactly (bit-for-bit without dropout/noise); the rng STREAM
        differs — one base split fanned to k keys here vs k sequential
        splits there — so stochastic (dropout/weight-noise) runs are
        reproducible within each path but not across the two."""
        def multi(params, state, opt_state, it0, xs, ys, rng, masks, lmasks):
            n = xs.shape[0]
            keys = jax.random.split(rng, n)
            its = it0 + jnp.arange(n, dtype=jnp.int32)

            def body(carry, inp):
                params, state, opt = carry
                x, y, k, it, m, lm = inp

                def loss_fn(p):
                    loss, new_state = self._loss(p, state, x, y, train=True,
                                                 rng=k, mask=m, label_mask=lm)
                    return loss, new_state

                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                new_params, new_opt = self._apply_updates(
                    grads, params, opt, it.astype(jnp.float32))
                return (new_params, new_state, new_opt), loss

            (params, state, opt_state), losses = jax.lax.scan(
                body, (params, state, opt_state),
                (xs, ys, keys, its, masks, lmasks))
            return params, state, opt_state, losses

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def fit_batches(self, batches):
        """k optimizer steps in ONE device dispatch (lax.scan) over a list
        of same-shaped DataSets.  Per-step listeners fire after the fused
        dispatch with that step's device-resident loss.  TBPTT configs and
        stateful listeners fall back to per-batch fit_batch calls (their
        semantics need params on host mid-run).  Returns [k] LazyScores."""
        batches = list(batches)
        if not batches:
            return []
        if self.conf.backprop_type == "tbptt" or any(
                getattr(l, "requires_model_state", False)
                for l in self.listeners):
            return [self.fit_batch(ds) for ds in batches]
        if self._jit_multi_step is None:
            self._jit_multi_step = self._make_multi_step()

        def stack(get):
            vals = [get(ds) for ds in batches]
            if any(v is None for v in vals):
                if not all(v is None for v in vals):
                    raise ValueError("fit_batches needs uniform masks: "
                                     "all batches or none")
                return None
            return jax.tree_util.tree_map(
                lambda *leaves: jnp.stack([_as_device(a) for a in leaves]),
                *vals)

        self._rng, sub = jax.random.split(self._rng)
        n = len(batches)
        self.params, self.state, self.opt_state, losses = self._jit_multi_step(
            self.params, self.state, self.opt_state, self._iter_scalar(n),
            stack(lambda d: d.features), stack(lambda d: d.labels), sub,
            stack(lambda d: d.features_mask), stack(lambda d: d.labels_mask))
        self.iteration += n
        scores = [LazyScore(losses[i]) for i in range(n)]
        for i, score in enumerate(scores):
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration - n + i + 1, score)
        return scores

    def _fit_batch_tbptt(self, ds: DataSet) -> float:
        """Truncated BPTT: slice the time axis into tbptt_length chunks,
        carry recurrent state forward between chunks, one optimizer step per
        chunk (reference doTruncatedBPTT():1386 semantics).  All full
        chunks run in one scanned jit (_make_step_tbptt_scan); a ragged
        tail chunk runs through the per-chunk step."""
        # device arrays pass through untouched (np.asarray would force a
        # device→host round trip); [mb, time, features] dense — or
        # [mb, time] integer indices (sparse inputs gathered by the LSTM /
        # sparse labels one-hotted in the loss)
        def _keep(a):
            return a if isinstance(a, jax.Array) else (
                None if a is None else np.asarray(a))
        x = _keep(ds.features)
        y = _keep(ds.labels)

        def _rank_ok(a):
            return a.ndim == 3 or (a.ndim == 2
                                   and jnp.issubdtype(a.dtype, jnp.integer))
        if not _rank_ok(x) or (y is not None and not _rank_ok(y)):
            raise ValueError("TBPTT requires [mb, time, features] inputs and "
                             "[mb, time, classes] labels (or [mb, time] "
                             "integer index arrays)")
        L = self.conf.tbptt_length
        mb, T = x.shape[0], x.shape[1]
        fm = _keep(ds.features_mask)
        lm = _keep(ds.labels_mask)
        # Listeners that act on the model mid-run (checkpointing, eval)
        # need each chunk's params at callback time — the fused scan only
        # has end-of-batch params, so such listeners route through the
        # per-chunk step loop (slower: one dispatch per chunk).  Plain
        # score/throughput listeners keep the fused path; they get called
        # after the batch with per-chunk losses.
        if any(getattr(l, "requires_model_state", False) for l in self.listeners):
            return self._fit_batch_tbptt_chunked(x, y, fm, lm, mb, T, L)
        n = T // L
        tail = T % L
        carries = None
        chunk_losses = []
        mean_loss = None
        if n:
            if self._jit_step_tbptt_scan is None:
                self._jit_step_tbptt_scan = self._make_step_tbptt_scan()
            cut = None if tail == 0 else n * L
            clip = (lambda a: a) if cut is None else (
                lambda a: None if a is None else a[:, :cut])
            (self.params, self.state, self.opt_state, carries, losses,
             mean_loss, self._rng) = self._jit_step_tbptt_scan(
                self.params, self.state, self.opt_state,
                self._iter_scalar(n),
                jnp.asarray(clip(x)),
                None if y is None else jnp.asarray(clip(y)),
                self._rng, clip(fm), clip(lm), None)
            self.iteration += n
            if self.listeners:
                chunk_losses = [(self.iteration - n + i + 1, LazyScore(losses[i]))
                                for i in range(n)]
        if tail:
            if self._jit_step_tbptt is None:
                self._jit_step_tbptt = self._make_step_tbptt()
            if carries is None:
                dtype = jnp.dtype(self.conf.compute_dtype)
                carries = [l.init_carry(mb, dtype) if l.recurrent else None
                           for l in self.conf.layers]
            s = n * L
            self._rng, sub = jax.random.split(self._rng)
            self.params, self.state, self.opt_state, carries, loss = self._jit_step_tbptt(
                self.params, self.state, self.opt_state,
                self._iter_scalar(1),
                jnp.asarray(x[:, s:]),
                None if y is None else jnp.asarray(y[:, s:]), sub,
                None if fm is None else jnp.asarray(fm[:, s:]),
                None if lm is None else jnp.asarray(lm[:, s:]), carries)
            self.iteration += 1
            if self.listeners:
                chunk_losses.append((self.iteration, LazyScore(loss)))
            mean_loss = loss if mean_loss is None else (
                (mean_loss * n + loss) / (n + 1))
        for it, score in chunk_losses:
            for lst in self.listeners:
                lst.iteration_done(self, it, score)
        return LazyScore(mean_loss)

    def _fit_batch_tbptt_chunked(self, x, y, fm, lm, mb, T, L):
        """Per-chunk TBPTT loop: one dispatch per chunk so listeners with
        ``requires_model_state`` observe each chunk's params (the fused
        scan path only has end-of-batch params)."""
        if self._jit_step_tbptt is None:
            self._jit_step_tbptt = self._make_step_tbptt()
        dtype = jnp.dtype(self.conf.compute_dtype)
        carries = [l.init_carry(mb, dtype) if l.recurrent else None
                   for l in self.conf.layers]
        total, chunks = None, 0
        for s in range(0, T, L):
            self._rng, sub = jax.random.split(self._rng)
            self.params, self.state, self.opt_state, carries, loss = self._jit_step_tbptt(
                self.params, self.state, self.opt_state,
                self._iter_scalar(1),
                jnp.asarray(x[:, s:s + L]),
                None if y is None else jnp.asarray(y[:, s:s + L]), sub,
                None if fm is None else jnp.asarray(fm[:, s:s + L]),
                None if lm is None else jnp.asarray(lm[:, s:s + L]), carries)
            self.iteration += 1
            total = loss if total is None else total + loss
            chunks += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration, LazyScore(loss))
        return LazyScore(total / max(chunks, 1))

    # ------------------------------------------------------------------
    # streaming RNN inference (rnnTimeStep parity)
    # ------------------------------------------------------------------

    def rnn_time_step(self, x) -> np.ndarray:
        """Stateful streaming inference: feeds [mb, f] (one step) or
        [mb, t, f] — or [mb] / [mb, t] integer index inputs — and keeps
        hidden state across calls (reference rnnTimeStep():2636)."""
        xa = jnp.asarray(x)
        if jnp.issubdtype(xa.dtype, jnp.integer):
            squeeze = xa.ndim == 1
            if squeeze:
                xa = xa[:, None]
        else:
            squeeze = xa.ndim == 2
            if squeeze:
                xa = xa[:, None, :]
        mb = xa.shape[0]
        if self._stream_carries is not None:
            for c in jax.tree_util.tree_leaves(self._stream_carries):
                if c.shape[0] != mb:  # batch size changed → fresh state
                    self._stream_carries = None
                break
        if self._stream_carries is None:
            dtype = jnp.dtype(self.conf.compute_dtype)
            self._stream_carries = [l.init_carry(mb, dtype) if l.recurrent else None
                                    for l in self.conf.layers]
        if self._jit_stream is None:
            def fwd(params, state, xx, carries):
                y, _, _, _, new_carries = self._apply_layers(
                    params, state, xx, train=False, rng=None, mask=None, carries=carries)
                return y, new_carries
            self._jit_stream = jax.jit(fwd)
        y, self._stream_carries = self._jit_stream(self.params, self.state, xa,
                                                   self._stream_carries)
        out = np.asarray(y)
        return out[:, 0] if squeeze and out.ndim == 3 else out

    def rnn_clear_previous_state(self) -> None:
        """Reset streaming state (reference rnnClearPreviousState)."""
        self._stream_carries = None

    def fit(self, data, epochs: int = 1) -> List[float]:
        """Train over a DataSetIterator / DataSet / (x, y) for N epochs
        (reference fit(DataSetIterator):1165; async prefetch is the
        iterator's job — wrap with AsyncDataSetIterator for host-side
        parity, or DevicePrefetchIterator to keep batches already
        transferred/normalized on device: fit_batch accepts its
        device-resident pytrees without re-staging them)."""
        it = self._as_iterator(data)
        losses: List[float] = []
        synced = 0
        for _ in range(epochs):
            for ds in it:
                losses.append(self.fit_batch(ds))
            synced = self._end_epoch(losses, synced)
        return losses

    def _end_epoch(self, losses, synced: int) -> int:
        """Epoch epilogue shared by fit() and ShardedTrainer.fit — ONE
        place, so epoch semantics can't diverge between plain and mesh
        training: materialize the epoch's scores in one batched device
        transfer (keeps the intra-epoch loop async while freeing the
        per-step 0-d buffers), bump the counter, fire epoch_done
        listeners.  Returns the new synced watermark."""
        materialize_scores(losses[synced:])
        self.epoch += 1
        for lst in self.listeners:
            if hasattr(lst, "epoch_done"):
                lst.epoch_done(self, self.epoch)
        return len(losses)

    @staticmethod
    def _as_iterator(data) -> DataSetIterator:
        if isinstance(data, DataSetIterator):
            return data
        if isinstance(data, DataSet):
            return ListDataSetIterator([data])
        if isinstance(data, tuple) and len(data) == 2:
            return ListDataSetIterator([DataSet(np.asarray(data[0]), np.asarray(data[1]))])
        raise TypeError(f"cannot iterate {type(data)}")

    # ------------------------------------------------------------------
    # inference / scoring
    # ------------------------------------------------------------------

    def output(self, x, mask=None) -> np.ndarray:
        """Inference activations of the last layer (reference output():1867)."""
        if self._jit_output is None:
            def fwd(params, state, xx, m):
                y, _, _, _, _ = self._apply_layers(params, state, xx, train=False, rng=None, mask=m)
                return y
            self._jit_output = jax.jit(fwd)
        y = self._jit_output(self.params, self.state, jnp.asarray(x),
                             None if mask is None else jnp.asarray(mask))
        return np.asarray(y)

    def feed_forward(self, x, train: bool = False) -> List[np.ndarray]:
        """All layer activations (reference feedForward(); activation-capture
        mode for transfer learning / debugging)."""
        _, _, _, acts, _ = self._apply_layers(self.params, self.state, jnp.asarray(x),
                                              train=train, rng=None, mask=None)
        return [np.asarray(a) for a in acts]

    def score_examples(self, ds: DataSet,
                       add_regularization_terms: bool = True) -> np.ndarray:
        """Per-example scores [N] WITHOUT batch reduction (reference
        MultiLayerNetwork.scoreExamples:2139,2156).  With
        ``add_regularization_terms`` the network's L1/L2 score is added to
        every example (the reference's semantics).  For unmasked
        feed-forward outputs ``mean(score_examples(ds, True)) ==
        score(ds)`` exactly; RNN outputs sum the per-timestep loss over the
        sequence (reference semantics), so there mean == t·score, and
        per-timestep masks weight examples differently from score()'s
        present-entry normalization.  Runs as one jitted program."""
        if self._jit_score_examples is None:
            def fn(params, state, x, y, m, lm, add_reg):
                n = len(self.conf.layers)
                h, _, mask_out, _, _ = self._apply_layers(
                    params, state, x, train=False, rng=None, mask=m,
                    upto=n - 1)
                last = self.conf.layers[n - 1]
                if (n - 1) in self.conf.preprocessors:
                    h = self.conf.preprocessors[n - 1].apply(h)
                if not hasattr(last, "score_examples"):
                    raise ValueError(
                        f"last layer {type(last).__name__} has no "
                        "score_examples(); supported: OutputLayer, "
                        "LossLayer, RnnOutputLayer, CenterLossOutputLayer")
                lmask = lm if lm is not None else (
                    mask_out if y is not None and getattr(y, "ndim", 0) == 3
                    else None)
                pe = last.score_examples(params[n - 1], state[n - 1], h, y,
                                         mask=lmask)
                reg = jnp.zeros((), pe.dtype)
                for layer, p in zip(self.conf.layers, params):
                    if p:
                        reg = reg + layer.regularization_score(p).astype(pe.dtype)
                return jnp.where(add_reg, pe + reg, pe)

            self._jit_score_examples = jax.jit(fn, static_argnums=())
        pe = self._jit_score_examples(
            self.params, self.state, jnp.asarray(ds.features),
            None if ds.labels is None else jax.tree_util.tree_map(jnp.asarray, ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask),
            jnp.asarray(add_regularization_terms))
        return np.asarray(pe)

    def reconstruction_log_probability(self, x, layer_index: Optional[int] = None,
                                       num_samples: int = 5) -> np.ndarray:
        """Importance-weighted log p(x) per example from a
        VariationalAutoencoder layer (reference
        VariationalAutoencoder.reconstructionLogProbability:977): inputs are
        featurized through the layers below it, then the layer's IWAE bound
        runs with ``num_samples`` importance samples.  ``layer_index=None``
        uses the first VAE layer."""
        if layer_index is None:
            layer_index = next(
                (i for i, l in enumerate(self.conf.layers)
                 if hasattr(l, "reconstruction_log_probability")), None)
            if layer_index is None:
                raise ValueError("no VariationalAutoencoder layer in this network")
        layer = self.conf.layers[layer_index]
        if not hasattr(layer, "reconstruction_log_probability"):
            raise ValueError(f"layer {layer_index} ({type(layer).__name__}) "
                             "is not a VariationalAutoencoder")
        self._rng, sub = jax.random.split(self._rng)

        key = (layer_index, num_samples)
        if self._jit_recon_logprob.get(key) is None:
            def fn(params, state, xx, rng):
                feat, _, _, _, _ = self._apply_layers(
                    params, state, xx, train=False, rng=None, mask=None,
                    upto=layer_index)
                if layer_index in self.conf.preprocessors:
                    feat = self.conf.preprocessors[layer_index].apply(feat)
                return layer.reconstruction_log_probability(
                    params[layer_index], feat, rng=rng,
                    num_samples=num_samples)

            self._jit_recon_logprob[key] = jax.jit(fn)
        return np.asarray(self._jit_recon_logprob[key](
            self.params, self.state, jnp.asarray(x), sub))

    def reconstruction_probability(self, x, layer_index: Optional[int] = None,
                                   num_samples: int = 5) -> np.ndarray:
        """exp(reconstruction_log_probability) — reference
        reconstructionProbability; prefer the log form for high-dim data."""
        return np.exp(self.reconstruction_log_probability(
            x, layer_index, num_samples))

    def score(self, ds: DataSet) -> float:
        """Loss on a DataSet without updating (reference score(DataSet))."""
        if self._jit_score is None:
            def score_fn(params, state, x, y, m, lm):
                loss, _ = self._loss(params, state, x, y, train=False, rng=None,
                                     mask=m, label_mask=lm)
                return loss
            self._jit_score = jax.jit(score_fn)
        loss = self._jit_score(
            self.params, self.state, jnp.asarray(ds.features),
            None if ds.labels is None else jax.tree_util.tree_map(jnp.asarray, ds.labels),
            None if ds.features_mask is None else jnp.asarray(ds.features_mask),
            None if ds.labels_mask is None else jnp.asarray(ds.labels_mask))
        return float(loss)

    def evaluate(self, data, evaluation=None):
        """Accumulate classification metrics over an iterator (reference
        MultiLayerNetwork.evaluate → eval/Evaluation)."""
        from ..evaluation.evaluation import Evaluation
        ev = evaluation if evaluation is not None else Evaluation()
        for ds in self._as_iterator(data):
            out = self.output(ds.features, mask=ds.features_mask)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    # ------------------------------------------------------------------
    # listeners / serde
    # ------------------------------------------------------------------

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def add_listener(self, listener) -> None:
        self.listeners.append(listener)

    def clone_params(self):
        return jax.tree_util.tree_map(lambda a: a, self.params)

    def save(self, path: str, save_updater: bool = True) -> None:
        from ..utils.serializer import save_model
        save_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "MultiLayerNetwork":
        from ..utils.serializer import load_model
        return load_model(path, load_updater=load_updater)
