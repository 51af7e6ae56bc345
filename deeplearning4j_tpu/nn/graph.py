"""ComputationGraph — arbitrary-DAG model container.

Parity target: reference nn/graph/ComputationGraph.java (3,379 LoC; topo
sort :394,727-742, fit :866, computeGradientAndScore :1295) plus the 14
GraphVertex impls (nn/graph/vertex/: LayerVertex, MergeVertex,
ElementWiseVertex, SubsetVertex, StackVertex, UnstackVertex, ReshapeVertex,
ScaleVertex, ShiftVertex, L2Vertex, L2NormalizeVertex, PoolHelperVertex,
PreprocessorVertex, InputVertex) and the rnn vertices
(conf/graph/rnn/LastTimeStepVertex, DuplicateToTimeSeriesVertex).

Same design inversion as MultiLayerNetwork: the reference walks the topo
order twice per iteration calling eager doForward/doBackward per vertex
(GraphVertex.java:117-123); here one traced function evaluates the DAG and
jax.grad differentiates it, all fused into a single XLA program per step.

Vertices are registered dataclasses: ``forward(inputs, ...)`` for pure
shape/math vertices; LayerVertex wraps any Layer.  Multi-input/multi-output
training uses MultiDataSet; single-in/single-out works with plain DataSet.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..datasets.dataset import DataSet, MultiDataSet
from ..datasets.iterators import DataSetIterator, ListDataSetIterator
from .conf.inputs import InputType
from .conf.regularizers import apply_constraints, maybe_weight_noise
from .layers.base import Layer, config_from_dict, config_to_dict, register_config
from .updaters import Adam, GradientNormalization, Updater, normalize_gradients
from ..optimize.score import LazyScore, materialize_scores

Array = jax.Array


# ---------------------------------------------------------------------------
# graph vertices (non-layer)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GraphVertex:
    """Base for parameter-free DAG vertices."""

    def forward(self, inputs: List[Array], masks: List[Optional[Array]]):
        raise NotImplementedError

    def output_type(self, in_types: List[InputType]) -> InputType:
        return in_types[0]

    def output_mask(self, masks: List[Optional[Array]]) -> Optional[Array]:
        for m in masks:
            if m is not None:
                return m
        return None


@register_config
@dataclasses.dataclass
class MergeVertex(GraphVertex):
    """Concatenate along the feature/channel (last) axis (reference
    MergeVertex: NCHW channel concat ≡ NHWC last-axis concat)."""

    def forward(self, inputs, masks):
        return jnp.concatenate(inputs, axis=-1)

    def output_type(self, in_types):
        t0 = in_types[0]
        if t0.kind == "cnn":
            return InputType.convolutional(t0.height, t0.width,
                                           sum(t.channels for t in in_types))
        if t0.kind == "rnn":
            return InputType.recurrent(sum(t.size for t in in_types), t0.timesteps)
        return InputType.feed_forward(sum(t.size for t in in_types))


@register_config
@dataclasses.dataclass
class ElementWiseVertex(GraphVertex):
    """add / subtract / product / average / max of equal-shape inputs
    (reference ElementWiseVertex.Op)."""

    op: str = "add"

    def forward(self, inputs, masks):
        if self.op == "add":
            out = inputs[0]
            for x in inputs[1:]:
                out = out + x
            return out
        if self.op == "subtract":
            return inputs[0] - inputs[1]
        if self.op == "product":
            out = inputs[0]
            for x in inputs[1:]:
                out = out * x
            return out
        if self.op == "average":
            return sum(inputs) / len(inputs)
        if self.op == "max":
            out = inputs[0]
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
            return out
        raise ValueError(f"unknown ElementWise op {self.op}")


@register_config
@dataclasses.dataclass
class SubsetVertex(GraphVertex):
    """Feature-range slice [from, to] inclusive (reference SubsetVertex)."""

    from_idx: int = 0
    to_idx: int = 0

    def forward(self, inputs, masks):
        return inputs[0][..., self.from_idx:self.to_idx + 1]

    def output_type(self, in_types):
        n = self.to_idx - self.from_idx + 1
        t = in_types[0]
        if t.kind == "rnn":
            return InputType.recurrent(n, t.timesteps)
        if t.kind == "cnn":
            # forward() slices the channel (last, NHWC) axis
            return InputType.convolutional(t.height, t.width, n)
        return InputType.feed_forward(n)


@register_config
@dataclasses.dataclass
class StackVertex(GraphVertex):
    """Stack along the batch axis (reference StackVertex)."""

    def forward(self, inputs, masks):
        return jnp.concatenate(inputs, axis=0)


@register_config
@dataclasses.dataclass
class UnstackVertex(GraphVertex):
    """Take slice ``index`` of ``stack_size`` along batch (reference UnstackVertex)."""

    index: int = 0
    stack_size: int = 1

    def forward(self, inputs, masks):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        return x[self.index * step:(self.index + 1) * step]


@register_config
@dataclasses.dataclass
class ReshapeVertex(GraphVertex):
    """Reshape trailing dims, batch preserved (reference ReshapeVertex)."""

    shape: List[int] = dataclasses.field(default_factory=list)

    def forward(self, inputs, masks):
        return inputs[0].reshape((inputs[0].shape[0],) + tuple(self.shape))

    def output_type(self, in_types):
        if len(self.shape) == 1:
            return InputType.feed_forward(self.shape[0])
        if len(self.shape) == 3:
            return InputType.convolutional(*self.shape)
        if len(self.shape) == 2:
            return InputType.recurrent(self.shape[1], self.shape[0])
        return in_types[0]


@register_config
@dataclasses.dataclass
class ScaleVertex(GraphVertex):
    factor: float = 1.0

    def forward(self, inputs, masks):
        return inputs[0] * self.factor


@register_config
@dataclasses.dataclass
class ShiftVertex(GraphVertex):
    shift: float = 0.0

    def forward(self, inputs, masks):
        return inputs[0] + self.shift


@register_config
@dataclasses.dataclass
class L2NormalizeVertex(GraphVertex):
    eps: float = 1e-8

    def forward(self, inputs, masks):
        x = inputs[0]
        norm = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim)), keepdims=True))
        return x / jnp.maximum(norm, self.eps)


@register_config
@dataclasses.dataclass
class L2Vertex(GraphVertex):
    """Pairwise L2 distance between two inputs → [mb, 1] (reference L2Vertex)."""

    eps: float = 1e-8

    def forward(self, inputs, masks):
        a, b = inputs[0], inputs[1]
        d = (a - b).reshape((a.shape[0], -1))
        return jnp.sqrt(jnp.sum(d * d, axis=-1, keepdims=True) + self.eps)

    def output_type(self, in_types):
        return InputType.feed_forward(1)


@register_config
@dataclasses.dataclass
class PreprocessorVertex(GraphVertex):
    """Wraps an InputPreProcessor as a vertex (reference PreprocessorVertex)."""

    preprocessor: Any = None

    def forward(self, inputs, masks):
        return self.preprocessor.apply(inputs[0])

    def output_type(self, in_types):
        return self.preprocessor.output_type(in_types[0])


@register_config
@dataclasses.dataclass
class PoolHelperVertex(GraphVertex):
    """Strips the first row/col of a CNN activation (reference
    PoolHelperVertex — GoogLeNet ceil-pooling import shim)."""

    def forward(self, inputs, masks):
        return inputs[0][:, 1:, 1:, :]

    def output_type(self, in_types):
        t = in_types[0]
        return InputType.convolutional(t.height - 1, t.width - 1, t.channels)


@register_config
@dataclasses.dataclass
class LastTimeStepVertex(GraphVertex):
    """[mb,t,f] → [mb,f] last present timestep, honoring the input's mask
    (reference conf/graph/rnn/LastTimeStepVertex)."""

    def forward(self, inputs, masks):
        x = inputs[0]
        m = masks[0]
        if m is not None:
            idx = jnp.maximum(jnp.sum(m.astype(jnp.int32), axis=1) - 1, 0)
            return x[jnp.arange(x.shape[0]), idx]
        return x[:, -1]

    def output_type(self, in_types):
        return InputType.feed_forward(in_types[0].size)

    def output_mask(self, masks):
        return None


@register_config
@dataclasses.dataclass
class DuplicateToTimeSeriesVertex(GraphVertex):
    """[mb,f] → [mb,t,f], t taken from a reference rnn input (reference
    DuplicateToTimeSeriesVertex; the second input supplies the length)."""

    def forward(self, inputs, masks):
        x, ref = inputs[0], inputs[1]
        return jnp.broadcast_to(x[:, None, :], (x.shape[0], ref.shape[1], x.shape[1]))

    def output_type(self, in_types):
        return InputType.recurrent(in_types[0].size, in_types[1].timesteps)

    def output_mask(self, masks):
        return masks[1] if len(masks) > 1 else None


@register_config
@dataclasses.dataclass
class LayerVertex(GraphVertex):
    """Wraps any Layer as a DAG vertex (reference vertex/impl/LayerVertex)."""

    layer: Optional[Layer] = None


# ---------------------------------------------------------------------------
# configuration + builder
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class VertexSpec:
    name: str
    vertex: Any              # LayerVertex or GraphVertex subclass
    inputs: List[str] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ComputationGraphConfiguration:
    """DAG config (reference ComputationGraphConfiguration + GraphBuilder)."""

    network_inputs: List[str] = dataclasses.field(default_factory=list)
    input_types: Dict[str, InputType] = dataclasses.field(default_factory=dict)
    vertices: List[VertexSpec] = dataclasses.field(default_factory=list)
    network_outputs: List[str] = dataclasses.field(default_factory=list)
    updater: Updater = dataclasses.field(default_factory=Adam)
    gradient_normalization: str = GradientNormalization.NONE
    gradient_normalization_threshold: float = 1.0
    seed: int = 12345
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    backprop_type: str = "standard"       # or "tbptt"
    tbptt_length: int = 20

    def to_dict(self) -> dict:
        return {
            "type": "ComputationGraphConfiguration",
            "network_inputs": list(self.network_inputs),
            "input_types": {k: v.to_dict() for k, v in self.input_types.items()},
            "vertices": [
                {"name": v.name, "vertex": config_to_dict(v.vertex), "inputs": list(v.inputs)}
                for v in self.vertices
            ],
            "network_outputs": list(self.network_outputs),
            "updater": config_to_dict(self.updater),
            "gradient_normalization": self.gradient_normalization,
            "gradient_normalization_threshold": self.gradient_normalization_threshold,
            "seed": self.seed,
            "param_dtype": self.param_dtype,
            "compute_dtype": self.compute_dtype,
            "backprop_type": self.backprop_type,
            "tbptt_length": self.tbptt_length,
        }

    @staticmethod
    def from_dict(d: dict) -> "ComputationGraphConfiguration":
        return ComputationGraphConfiguration(
            network_inputs=list(d["network_inputs"]),
            input_types={k: InputType.from_dict(v) for k, v in d["input_types"].items()},
            vertices=[VertexSpec(v["name"], config_from_dict(v["vertex"]), list(v["inputs"]))
                      for v in d["vertices"]],
            network_outputs=list(d["network_outputs"]),
            updater=config_from_dict(d["updater"]),
            gradient_normalization=d.get("gradient_normalization", GradientNormalization.NONE),
            gradient_normalization_threshold=d.get("gradient_normalization_threshold", 1.0),
            seed=d.get("seed", 12345),
            param_dtype=d.get("param_dtype", "float32"),
            compute_dtype=d.get("compute_dtype", "float32"),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_length=d.get("tbptt_length", 20),
        )


class GraphBuilder:
    """Fluent DAG builder (reference ComputationGraphConfiguration.GraphBuilder)."""

    def __init__(self):
        self._conf = ComputationGraphConfiguration()

    def seed(self, s: int) -> "GraphBuilder":
        self._conf.seed = s
        return self

    def updater(self, u: Updater) -> "GraphBuilder":
        self._conf.updater = u
        return self

    def gradient_normalization(self, mode: str, threshold: float = 1.0) -> "GraphBuilder":
        self._conf.gradient_normalization = mode
        self._conf.gradient_normalization_threshold = threshold
        return self

    def add_inputs(self, *names: str) -> "GraphBuilder":
        self._conf.network_inputs.extend(names)
        return self

    def set_input_types(self, **types: InputType) -> "GraphBuilder":
        self._conf.input_types.update(types)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str) -> "GraphBuilder":
        self._conf.vertices.append(VertexSpec(name, LayerVertex(layer=layer), list(inputs)))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str) -> "GraphBuilder":
        self._conf.vertices.append(VertexSpec(name, vertex, list(inputs)))
        return self

    def set_outputs(self, *names: str) -> "GraphBuilder":
        self._conf.network_outputs.extend(names)
        return self

    def tbptt(self, length: int) -> "GraphBuilder":
        """Truncated BPTT over the time axis (reference GraphBuilder
        .backpropType(TruncatedBPTT).tBPTTLength)."""
        self._conf.backprop_type = "tbptt"
        self._conf.tbptt_length = length
        return self

    def build(self) -> ComputationGraphConfiguration:
        return self._conf


# ---------------------------------------------------------------------------
# runtime
# ---------------------------------------------------------------------------


class ComputationGraph:
    """DAG model with the MultiLayerNetwork training surface.

    Params/state/opt-state are dicts keyed by vertex name (vs. the
    reference's flattened views)."""

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params: Dict[str, Dict[str, Array]] = {}
        self.state: Dict[str, Dict[str, Array]] = {}
        self.opt_state: Dict[str, Dict] = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: List[Any] = []
        self._jit_step = None
        self._jit_step_tbptt = None
        self._jit_step_tbptt_scan = None
        self._jit_multi_step = None
        self._it_dev = None        # device-resident iteration counter
        self._it_dev_val = -1
        self._jit_output = None
        self._jit_score_examples = None
        self._jit_stream = None
        self._stream_carries = None
        self._rng = jax.random.PRNGKey(conf.seed)
        self._spec_by_name = {v.name: v for v in conf.vertices}
        self.topo_order = self._topological_sort()
        self.vertex_in_types: Dict[str, List[InputType]] = {}
        self.vertex_out_types: Dict[str, InputType] = {}
        self._infer_types()

    # -- structure ---------------------------------------------------------

    def _topological_sort(self) -> List[str]:
        """Kahn topo sort of vertex names (reference topo sort :394,727-742)."""
        spec_by_name = self._spec_by_name
        for s in self.conf.vertices:
            for inp in s.inputs:
                if inp not in spec_by_name and inp not in self.conf.network_inputs:
                    raise ValueError(f"vertex '{s.name}' references unknown input '{inp}'")
        indeg = {v.name: 0 for v in self.conf.vertices}
        dependents: Dict[str, List[str]] = {n: [] for n in indeg}
        for s in self.conf.vertices:
            for inp in s.inputs:
                if inp in spec_by_name:
                    indeg[s.name] += 1
                    dependents[inp].append(s.name)
        order = [n for n, d in sorted(indeg.items()) if d == 0]
        queue = list(order)
        seen = set(order)
        result = []
        while queue:
            n = queue.pop(0)
            result.append(n)
            for dep in dependents[n]:
                indeg[dep] -= 1
                if indeg[dep] == 0 and dep not in seen:
                    seen.add(dep)
                    queue.append(dep)
        if len(result) != len(self.conf.vertices):
            cyc = set(indeg) - set(result)
            raise ValueError(f"graph has a cycle involving {sorted(cyc)}")
        return result

    def _spec(self, name: str) -> VertexSpec:
        return self._spec_by_name[name]

    def _infer_types(self) -> None:
        types: Dict[str, InputType] = dict(self.conf.input_types)
        if not types:
            return
        for name in self.topo_order:
            spec = self._spec(name)
            in_types = [types[i] for i in spec.inputs]
            self.vertex_in_types[name] = in_types
            if isinstance(spec.vertex, LayerVertex):
                layer = spec.vertex.layer
                t = in_types[0]
                layer.infer_nin(t)
                types[name] = layer.output_type(t)
            else:
                types[name] = spec.vertex.output_type(in_types)
            self.vertex_out_types[name] = types[name]

    # -- init --------------------------------------------------------------

    def init(self, rng: Optional[Array] = None) -> None:
        if not self.vertex_out_types:
            raise ValueError("set_input_types(...) required before init()")
        rng = rng if rng is not None else self._rng
        dtype = jnp.dtype(self.conf.param_dtype)
        keys = jax.random.split(rng, max(len(self.conf.vertices), 1))
        self.params, self.state, self.opt_state = {}, {}, {}
        for k, spec in zip(keys, self.conf.vertices):
            if isinstance(spec.vertex, LayerVertex):
                layer = spec.vertex.layer
                t = self.vertex_in_types[spec.name][0]
                p = layer.init_params(k, t, dtype)
                self.params[spec.name] = p
                self.state[spec.name] = layer.init_state(t, dtype)
                self.opt_state[spec.name] = (
                    self._updater_for(layer).init_state(p) if p else {})
            else:
                self.params[spec.name] = {}
                self.state[spec.name] = {}
                self.opt_state[spec.name] = {}
        self.iteration = 0

    def _updater_for(self, layer: Layer) -> Updater:
        return layer.updater if layer.updater is not None else self.conf.updater

    def num_params(self) -> int:
        return sum(int(np.prod(x.shape))
                   for p in self.params.values()
                   for x in jax.tree_util.tree_leaves(p))

    def summary(self) -> str:
        """Vertex table in topological order: name, type, inputs, param
        count (reference ComputationGraph.summary():3967)."""
        if not self.params:
            raise ValueError("call init() before summary()")
        rows = [("vertex", "type", "inputs", "params")]
        for name in self.topo_order:
            spec = self._spec(name)
            v = spec.vertex
            tname = (type(v.layer).__name__ if isinstance(v, LayerVertex)
                     else type(v).__name__)
            n = sum(int(np.prod(x.shape)) for x in
                    jax.tree_util.tree_leaves(self.params.get(name, {})))
            rows.append((name, tname, ",".join(spec.inputs) or "-", f"{n:,}"))
        widths = [max(len(r[c]) for r in rows) for c in range(4)]
        lines = ["  ".join(val.ljust(w) for val, w in zip(r, widths))
                 for r in rows]
        lines.insert(1, "-" * len(lines[0]))
        lines.append(f"total params: {self.num_params():,}")
        return "\n".join(lines)

    # -- pure forward / loss ------------------------------------------------

    def _apply(self, params, state, inputs: Dict[str, Array], *, train: bool, rng,
               masks: Optional[Dict[str, Optional[Array]]] = None,
               stop_before_output_score: bool = False, carries=None):
        """Evaluate the DAG.  Returns (activations dict, new_state, masks
        dict, new_carries).

        When ``stop_before_output_score`` the output LayerVertices are NOT
        applied (their score() consumes the pre-layer activations).
        ``carries`` (dict name→carry, None entries for stateless vertices)
        threads recurrent hidden state through LayerVertices for TBPTT /
        streaming — the DAG analog of the reference's
        rnnActivateUsingStoredState (ComputationGraph.java:1602)."""
        compute = jnp.dtype(self.conf.compute_dtype)
        # integer-index inputs can't carry the compute dtype — stamp it on
        # layers so e.g. LSTM gathers in the right precision
        for spec in self.conf.vertices:
            if getattr(spec.vertex, "layer", None) is not None:
                spec.vertex.layer._compute_dtype = self.conf.compute_dtype
        acts: Dict[str, Array] = {}
        mks: Dict[str, Optional[Array]] = {}
        for k, v in inputs.items():
            acts[k] = v.astype(compute) if jnp.issubdtype(v.dtype, jnp.floating) else v
            mks[k] = (masks or {}).get(k)
        new_state = dict(state)
        new_carries = dict(carries) if carries is not None else {}
        keys = (jax.random.split(rng, len(self.topo_order))
                if rng is not None else [None] * len(self.topo_order))
        for key, name in zip(keys, self.topo_order):
            spec = self._spec(name)
            if stop_before_output_score and name in self.conf.network_outputs:
                continue
            xin = [acts[i] for i in spec.inputs]
            min_ = [mks[i] for i in spec.inputs]
            if isinstance(spec.vertex, LayerVertex):
                layer = spec.vertex.layer
                kwargs = {}
                if layer.recurrent and carries is not None:
                    kwargs["carry"] = carries.get(name)
                p_v = maybe_weight_noise(layer, params[name], train, key)
                out = layer.forward(
                    p_v, state[name], xin[0], train=train, rng=key,
                    mask=min_[0], **kwargs)
                acts[name], mks[name] = out.y, out.mask
                new_state[name] = out.state
                if layer.recurrent and carries is not None:
                    new_carries[name] = out.carry
            else:
                acts[name] = spec.vertex.forward(xin, min_)
                mks[name] = spec.vertex.output_mask(min_)
        return acts, new_state, mks, new_carries

    def _iter_scalar(self, advance: int):
        from ..utils import device_iteration
        return device_iteration(self, advance)

    def _init_carries(self, mb: int) -> Dict[str, Any]:
        """Zero carries for every recurrent LayerVertex (None elsewhere)."""
        dtype = jnp.dtype(self.conf.compute_dtype)
        carries: Dict[str, Any] = {}
        for spec in self.conf.vertices:
            if isinstance(spec.vertex, LayerVertex) and spec.vertex.layer.recurrent:
                carries[spec.name] = spec.vertex.layer.init_carry(mb, dtype)
        return carries

    def _loss(self, params, state, inputs: Dict[str, Array], labels: Dict[str, Any],
              *, train: bool, rng, masks=None, label_masks=None, carries=None):
        acts, new_state, mks, new_carries = self._apply(
            params, state, inputs, train=train, rng=rng,
            masks=masks, stop_before_output_score=True, carries=carries)
        acc = jnp.float64 if jnp.dtype(self.conf.compute_dtype) == jnp.float64 else jnp.float32
        total = jnp.zeros((), acc)
        for oi, out_name in enumerate(self.conf.network_outputs):
            spec = self._spec(out_name)
            layer = spec.vertex.layer
            if not hasattr(layer, "score"):
                raise ValueError(f"output vertex '{out_name}' has no score()")
            h = acts[spec.inputs[0]]
            if train and rng is not None:
                # output layers honor input dropout (parity w/ multilayer._loss);
                # _maybe_dropout no-ops when the layer has no dropout configured
                h = layer._maybe_dropout(h, train, jax.random.fold_in(rng, 10_000 + oi))
            lm = (label_masks or {}).get(out_name)
            total = total + layer.score(params[out_name], state[out_name], h,
                                        labels[out_name], mask=lm).astype(acc)
            if train and hasattr(layer, "update_centers"):
                new_state[out_name] = layer.update_centers(
                    state[out_name], jax.lax.stop_gradient(h),
                    jax.lax.stop_gradient(labels[out_name]))
        for spec in self.conf.vertices:
            if isinstance(spec.vertex, LayerVertex) and self.params.get(spec.name):
                total = total + spec.vertex.layer.regularization_score(
                    params[spec.name]).astype(acc)
        if train:
            from .layers.base import AUX_LOSS_KEY
            for s in new_state.values():
                if isinstance(s, dict) and AUX_LOSS_KEY in s:
                    total = total + s[AUX_LOSS_KEY].astype(acc)
        if carries is not None:
            return total, (new_state, new_carries)
        return total, new_state

    # -- training ----------------------------------------------------------

    def _apply_updates(self, grads, params, opt_state, itf):
        """Shared per-vertex updater application (grad normalization, updater
        math, dtype-preserving cast, post-update constraints) — used by both
        the standard and TBPTT jitted steps."""
        conf = self.conf
        new_params, new_opt = dict(params), dict(opt_state)
        for spec in conf.vertices:
            name = spec.name
            if not isinstance(spec.vertex, LayerVertex) or not params[name]:
                continue
            g = grads[name]
            if conf.gradient_normalization != GradientNormalization.NONE:
                g = normalize_gradients(g, conf.gradient_normalization,
                                        conf.gradient_normalization_threshold)
            upd = self._updater_for(spec.vertex.layer)
            # apply = updater math + param step; Adam/Nadam route through
            # the fused one-pass kernel (ops/update_kernel.py) when enabled
            new_params[name], os2 = upd.apply(params[name], g,
                                              opt_state[name], itf)
            if spec.vertex.layer.constraints:
                new_params[name] = apply_constraints(
                    spec.vertex.layer.constraints, new_params[name])
            new_opt[name] = os2
        return new_params, new_opt

    def _make_step(self):
        conf = self.conf

        def step(params, state, opt_state, it, inputs, labels, rng, masks, label_masks):
            def loss_fn(p):
                return self._loss(p, state, inputs, labels, train=True, rng=rng,
                                  masks=masks, label_masks=label_masks)

            (loss, new_state), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(
                grads, params, opt_state, it.astype(jnp.float32))
            return new_params, new_state, new_opt, loss

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _make_step_tbptt(self):
        """One TBPTT chunk step — used for the ragged tail chunk and the
        stateful-listener fallback (reference doTruncatedBPTT:1553)."""
        conf = self.conf

        def step(params, state, opt_state, it, inputs, labels, rng, masks,
                 label_masks, carries):
            def loss_fn(p):
                return self._loss(p, state, inputs, labels, train=True, rng=rng,
                                  masks=masks, label_masks=label_masks,
                                  carries=carries)

            (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            new_params, new_opt = self._apply_updates(
                grads, params, opt_state, it.astype(jnp.float32))
            return new_params, new_state, new_opt, new_carries, loss

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _make_step_tbptt_scan(self):
        """Whole-batch TBPTT for the DAG: all T//L chunk optimizer-steps in
        ONE jit via lax.scan (see multilayer._make_step_tbptt_scan for the
        per-chunk-upload cost this removes).  Temporal entries (rank-3
        features/labels, [mb,T] masks) are chunked into scan inputs;
        static entries (rank-2 inputs, per-sequence masks) ride the trace
        closure unchanged."""
        L = self.conf.tbptt_length

        def step(params, state, opt_state, it0, inputs, labels, rng,
                 masks_t, masks_s, lmasks_t, lmasks_s, carries):
            # masks arrive PRE-SPLIT into temporal/static dicts: the caller
            # classifies against the ORIGINAL T, because after tail
            # clipping a static rank-2 mask's dim-1 could coincidentally
            # equal the clipped n·L and be mistaken for temporal here
            T = next(a.shape[1]
                     for a in list(inputs.values()) + list(labels.values())
                     if a is not None and a.ndim == 3)
            n = T // L
            mb = next(iter(inputs.values())).shape[0]
            if carries is None:
                carries = self._init_carries(mb)

            def chunkify(a):
                a2 = a.reshape((a.shape[0], n, L) + a.shape[2:])
                return jnp.moveaxis(a2, 1, 0)

            def split_temporal(d, temporal_pred):
                xs = {k: chunkify(v) for k, v in (d or {}).items()
                      if temporal_pred(v)}
                static = {k: v for k, v in (d or {}).items()
                          if not temporal_pred(v)}
                return xs, static

            is_t = lambda a: a is not None and a.ndim == 3
            xs_in, st_in = split_temporal(inputs, is_t)
            xs_lab, st_lab = split_temporal(labels, is_t)
            xs_m = {k: chunkify(v) for k, v in (masks_t or {}).items()}
            st_m = dict(masks_s or {})
            xs_lm = {k: chunkify(v) for k, v in (lmasks_t or {}).items()}
            st_lm = dict(lmasks_s or {})
            keys = jax.random.split(rng, n + 1)
            its = it0 + jnp.arange(n, dtype=jnp.int32)

            def body(carry, xs):
                params, state, opt_state, carries = carry
                ci, cl, cm, clm, k, it = xs

                def loss_fn(p):
                    return self._loss(p, state, {**st_in, **ci},
                                      {**st_lab, **cl}, train=True, rng=k,
                                      masks={**st_m, **cm},
                                      label_masks={**st_lm, **clm},
                                      carries=carries)

                (loss, (new_state, new_carries)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                new_params, new_opt = self._apply_updates(
                    grads, params, opt_state, it.astype(jnp.float32))
                return (new_params, new_state, new_opt, new_carries), loss

            (params, state, opt_state, carries), losses = jax.lax.scan(
                body, (params, state, opt_state, carries),
                (xs_in, xs_lab, xs_m, xs_lm, keys[:n], its))
            return (params, state, opt_state, carries, losses,
                    jnp.mean(losses), keys[n])

        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _to_mds(self, ds) -> MultiDataSet:
        if isinstance(ds, MultiDataSet):
            return ds
        if isinstance(ds, DataSet):
            return MultiDataSet([ds.features], [ds.labels],
                                [ds.features_mask], [ds.labels_mask])
        raise TypeError(type(ds))

    def score_examples(self, ds, add_regularization_terms: bool = True) -> np.ndarray:
        """Per-example scores [N] (reference ComputationGraph.scoreExamples):
        each output layer's unreduced loss summed per example across
        outputs; with ``add_regularization_terms`` the network L1/L2 score
        is added to every example.  For unmasked feed-forward outputs
        ``mean(score_examples(ds, True)) == score(ds)``; RNN outputs sum
        over time (mean == t·score there)."""
        mds = self._to_mds(ds)
        if self._jit_score_examples is None:
            def fn(params, state, inputs, labels, masks, lmasks, add_reg):
                acts, _, mks, _ = self._apply(
                    params, state, inputs, train=False, rng=None,
                    masks=masks, stop_before_output_score=True)
                pe = None
                for out_name in self.conf.network_outputs:
                    spec = self._spec(out_name)
                    layer = spec.vertex.layer
                    if not hasattr(layer, "score_examples"):
                        raise ValueError(
                            f"output vertex '{out_name}' "
                            f"({type(layer).__name__}) has no score_examples()")
                    h = acts[spec.inputs[0]]
                    # mirror MultiLayerNetwork.score_examples: with no
                    # explicit label mask, rank-3 (RNN) labels fall back to
                    # the forward-propagated feature mask of this output's
                    # input — masked-sequence per-example scores must agree
                    # between the two containers
                    lmask = lmasks.get(out_name)
                    y_out = labels[out_name]
                    if lmask is None and getattr(y_out, "ndim", 0) == 3:
                        lmask = mks.get(spec.inputs[0])
                    s = layer.score_examples(params[out_name], state[out_name],
                                             h, y_out, mask=lmask)
                    pe = s if pe is None else pe + s
                reg = jnp.zeros((), pe.dtype)
                for spec in self.conf.vertices:
                    if isinstance(spec.vertex, LayerVertex) and params.get(spec.name):
                        reg = reg + spec.vertex.layer.regularization_score(
                            params[spec.name]).astype(pe.dtype)
                return jnp.where(add_reg, pe + reg, pe)

            self._jit_score_examples = jax.jit(fn)
        inputs = {n: jnp.asarray(f) for n, f in
                  zip(self.conf.network_inputs, mds.features)}
        labels = {n: jax.tree_util.tree_map(jnp.asarray, l)
                  for n, l in zip(self.conf.network_outputs, mds.labels)}
        masks = {n: (None if m is None else jnp.asarray(m))
                 for n, m in zip(self.conf.network_inputs, mds.features_masks or
                                 [None] * len(self.conf.network_inputs))}
        lmasks = {n: (None if m is None else jnp.asarray(m))
                  for n, m in zip(self.conf.network_outputs, mds.labels_masks or
                                  [None] * len(self.conf.network_outputs))}
        pe = self._jit_score_examples(self.params, self.state, inputs, labels,
                                      masks, lmasks,
                                      jnp.asarray(add_regularization_terms))
        return np.asarray(pe)

    # -- layerwise unsupervised pretraining --------------------------------

    def pretrainable_layers(self) -> List[str]:
        """Names of LayerVertices with an unsupervised objective (reference
        Layer.isPretrainLayer())."""
        return [s.name for s in self.conf.vertices
                if isinstance(s.vertex, LayerVertex)
                and (hasattr(s.vertex.layer, "contrastive_divergence")
                     or hasattr(s.vertex.layer, "reconstruction_score"))]

    def pretrain(self, data, epochs: int = 1) -> Dict[str, List[float]]:
        """Greedy layerwise unsupervised pretraining over the DAG in
        topological order (reference ComputationGraph.pretrain:651); labels
        are ignored.  Returns {vertex_name: losses}."""
        wanted = set(self.pretrainable_layers())
        order = [n for n in self.topo_order if n in wanted]
        return {n: self.pretrain_layer(n, data, epochs) for n in order}

    def pretrain_layer(self, name: str, data, epochs: int = 1) -> List[float]:
        """Unsupervised pretraining of one LayerVertex (reference
        pretrainLayer(String, MultiDataSetIterator)): the vertex's input is
        produced by an inference-mode DAG pass (XLA dead-code-eliminates
        everything downstream of it), then the layer's objective — CD-k /
        reconstruction / ELBO — runs with the layer's updater in the same
        jitted program."""
        spec = self._spec_by_name.get(name)
        if spec is None or not isinstance(spec.vertex, LayerVertex):
            raise ValueError(f"'{name}' is not a LayerVertex")
        layer = spec.vertex.layer
        is_rbm = hasattr(layer, "cd_gradients")
        if not is_rbm and not hasattr(layer, "reconstruction_score"):
            raise ValueError(
                f"vertex '{name}' ({type(layer).__name__}) has no "
                "unsupervised objective (RBM / AutoEncoder / VAE)")
        updater = self._updater_for(layer)

        def step(params, state, opt_v, it, inputs, rng):
            acts, _, _, _ = self._apply(params, state, inputs, train=False,
                                        rng=None, masks=None,
                                        stop_before_output_score=True)
            src = spec.inputs[0]
            feat = acts[src] if src in acts else inputs[src]
            if is_rbm:
                g, loss = layer.cd_gradients(params[name], feat, rng)
            else:
                loss, g = jax.value_and_grad(
                    lambda p: layer.reconstruction_score(
                        p, feat, rng=rng, train=True))(params[name])
            if self.conf.gradient_normalization != GradientNormalization.NONE:
                g = normalize_gradients(
                    g, self.conf.gradient_normalization,
                    self.conf.gradient_normalization_threshold)
            p2, opt2 = updater.apply(params[name], g, opt_v, it)
            if layer.constraints:
                p2 = apply_constraints(layer.constraints, p2)
            return p2, opt2, loss

        jit_step = jax.jit(step, donate_argnums=(2,))
        losses: List[float] = []
        it = 0
        for _ in range(epochs):
            for ds in self._as_iterator(data):
                mds = self._to_mds(ds)
                inputs = {n: jnp.asarray(f) for n, f in
                          zip(self.conf.network_inputs, mds.features)}
                self._rng, sub = jax.random.split(self._rng)
                self.params[name], self.opt_state[name], loss = jit_step(
                    self.params, self.state, self.opt_state[name],
                    np.float32(it), inputs, sub)
                it += 1
                losses.append(LazyScore(loss))
        materialize_scores(losses)
        return losses

    def fit_batch(self, ds):
        """One step; returns a :class:`LazyScore` (device-resident loss that
        syncs only when read — see optimize/score.py)."""
        mds = self._to_mds(ds)
        if self.conf.backprop_type == "tbptt":
            return self._fit_batch_tbptt(mds)
        if self._jit_step is None:
            self._jit_step = self._make_step()
        self._rng, sub = jax.random.split(self._rng)
        inputs = {n: jnp.asarray(f) for n, f in zip(self.conf.network_inputs, mds.features)}
        labels = {n: jax.tree_util.tree_map(jnp.asarray, l)
                  for n, l in zip(self.conf.network_outputs, mds.labels)}
        masks = {n: (None if m is None else jnp.asarray(m))
                 for n, m in zip(self.conf.network_inputs, mds.features_masks or
                                 [None] * len(self.conf.network_inputs))}
        lmasks = {n: (None if m is None else jnp.asarray(m))
                  for n, m in zip(self.conf.network_outputs, mds.labels_masks or
                                  [None] * len(self.conf.network_outputs))}
        self.params, self.state, self.opt_state, loss = self._jit_step(
            self.params, self.state, self.opt_state,
            jnp.asarray(self.iteration, jnp.int32), inputs, labels, sub, masks, lmasks)
        self.iteration += 1
        score = LazyScore(loss)
        for lst in self.listeners:
            lst.iteration_done(self, self.iteration, score)
        return score

    def _make_multi_step(self):
        """k optimizer steps fused into ONE dispatch via lax.scan over
        stacked batches — the graph-container twin of
        MultiLayerNetwork._make_multi_step (amortizes the per-step host
        dispatch gap to 1/k).  Same rng-stream caveat
        as the MLN twin: one base split fanned to k keys, so stochastic
        runs differ from k sequential fit_batch calls."""
        def multi(params, state, opt_state, it0, inputs, labels, rng,
                  masks, lmasks):
            n = jax.tree_util.tree_leaves(inputs)[0].shape[0]
            keys = jax.random.split(rng, n)
            its = it0 + jnp.arange(n, dtype=jnp.int32)

            def body(carry, inp):
                params, state, opt = carry
                xs, ys, k, it, ms, lms = inp

                def loss_fn(p):
                    return self._loss(p, state, xs, ys, train=True, rng=k,
                                      masks=ms, label_masks=lms)

                (loss, new_state), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params)
                new_params, new_opt = self._apply_updates(
                    grads, params, opt, it.astype(jnp.float32))
                return (new_params, new_state, new_opt), loss

            (params, state, opt_state), losses = jax.lax.scan(
                body, (params, state, opt_state),
                (inputs, labels, keys, its, masks, lmasks))
            return params, state, opt_state, losses

        return jax.jit(multi, donate_argnums=(0, 1, 2))

    def fit_batches(self, batches):
        """k steps in ONE device dispatch over same-shaped DataSets /
        MultiDataSets (see MultiLayerNetwork.fit_batches).  Returns [k]
        LazyScores; TBPTT configs fall back to per-batch calls."""
        mdss = [self._to_mds(ds) for ds in batches]
        if not mdss:
            return []
        # stateful listeners (checkpoint/eval) need params at EACH step's
        # callback time — the fused scan only has end-of-run params
        if self.conf.backprop_type == "tbptt" or any(
                getattr(l, "requires_model_state", False)
                for l in self.listeners):
            return [self.fit_batch(m) for m in mdss]
        if self._jit_multi_step is None:
            self._jit_multi_step = self._make_multi_step()

        def stack_named(names, get):
            out = {}
            for i, name in enumerate(names):
                vals = [get(m, i) for m in mdss]
                if any(v is None for v in vals):
                    if not all(v is None for v in vals):
                        raise ValueError("fit_batches needs uniform masks: "
                                         "all batches or none")
                    out[name] = None
                else:
                    out[name] = jax.tree_util.tree_map(
                        lambda *ls: jnp.stack([jnp.asarray(a) for a in ls]),
                        *vals)
            return out

        n_in = len(self.conf.network_inputs)
        n_out = len(self.conf.network_outputs)
        inputs = stack_named(self.conf.network_inputs,
                             lambda m, i: m.features[i])
        labels = stack_named(self.conf.network_outputs,
                             lambda m, i: m.labels[i])
        masks = stack_named(self.conf.network_inputs,
                            lambda m, i: (m.features_masks or [None] * n_in)[i])
        lmasks = stack_named(self.conf.network_outputs,
                             lambda m, i: (m.labels_masks or [None] * n_out)[i])
        self._rng, sub = jax.random.split(self._rng)
        n = len(mdss)
        self.params, self.state, self.opt_state, losses = self._jit_multi_step(
            self.params, self.state, self.opt_state,
            jnp.asarray(self.iteration, jnp.int32), inputs, labels, sub,
            masks, lmasks)
        self.iteration += n
        scores = [LazyScore(losses[i]) for i in range(n)]
        for i, score in enumerate(scores):
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration - n + i + 1, score)
        return scores

    def _fit_batch_tbptt(self, mds: MultiDataSet) -> float:
        """Slice the time axis into tbptt_length chunks, carry recurrent
        state forward, one optimizer step per chunk (reference
        doTruncatedBPTT:1553).  All rank-3 inputs/labels must share T.
        Full chunks run in one scanned jit; a ragged tail — and the
        stateful-listener case — use the per-chunk step."""
        feats = [np.asarray(f) for f in mds.features]
        labs = [None if l is None else np.asarray(l) for l in mds.labels]
        T = None
        for a in feats + [l for l in labs if l is not None]:
            if a.ndim == 3:
                if T is not None and a.shape[1] != T:
                    raise ValueError("TBPTT requires equal time lengths across "
                                     f"inputs/labels (got {a.shape[1]} vs {T})")
                T = a.shape[1]
        if T is None:
            raise ValueError("TBPTT requires at least one [mb, time, f] array")
        mb = feats[0].shape[0]
        L = self.conf.tbptt_length
        fmasks = mds.features_masks or [None] * len(feats)
        lmasks_l = mds.labels_masks or [None] * len(labs)

        def tslice(a, s, e):
            """Features/labels: only rank-3 arrays carry a time axis —
            rank-2 static inputs pass through whole (their dim-1 may
            coincidentally equal T)."""
            if a is None:
                return None
            return a[:, s:e] if a.ndim == 3 else a

        def mslice(m, s, e):
            """Masks are [mb, T] when temporal; other shapes pass through."""
            if m is None:
                return None
            m = np.asarray(m)
            return m[:, s:e] if m.ndim == 2 and m.shape[1] == T else m

        def dicts(s, e):
            inputs = {n: jnp.asarray(tslice(f, s, e))
                      for n, f in zip(self.conf.network_inputs, feats)}
            labels = {n: (None if l is None else jnp.asarray(tslice(l, s, e)))
                      for n, l in zip(self.conf.network_outputs, labs)}
            masks = {n: (None if m is None else jnp.asarray(mslice(m, s, e)))
                     for n, m in zip(self.conf.network_inputs, fmasks)}
            lmasks = {n: (None if m is None else jnp.asarray(mslice(m, s, e)))
                      for n, m in zip(self.conf.network_outputs, lmasks_l)}
            return inputs, labels, masks, lmasks

        stateful = any(getattr(l, "requires_model_state", False)
                       for l in self.listeners)
        n = T // L
        tail = T % L
        carries = None
        chunk_losses = []
        mean_loss = None
        if n and not stateful:
            if self._jit_step_tbptt_scan is None:
                self._jit_step_tbptt_scan = self._make_step_tbptt_scan()
            inputs, labels, masks, lmasks = dicts(0, n * L)

            def split_by_orig_T(slcd, originals, names):
                """Temporal = the ORIGINAL array was [mb, T]; a static
                mask whose dim-1 happens to equal the clipped n·L must
                not be chunkified (the scan can't tell them apart)."""
                t, s = {}, {}
                for name in names:
                    orig = originals.get(name)
                    m = slcd.get(name)
                    is_temporal = (orig is not None and orig.ndim == 2
                                   and orig.shape[1] == T)
                    (t if is_temporal else s)[name] = m
                return t, s

            orig_fm = {nm: (None if m is None else np.asarray(m))
                       for nm, m in zip(self.conf.network_inputs, fmasks)}
            orig_lm = {nm: (None if m is None else np.asarray(m))
                       for nm, m in zip(self.conf.network_outputs, lmasks_l)}
            masks_t, masks_s = split_by_orig_T(masks, orig_fm,
                                               self.conf.network_inputs)
            lm_t, lm_s = split_by_orig_T(lmasks, orig_lm,
                                         self.conf.network_outputs)
            (self.params, self.state, self.opt_state, carries, losses,
             mean_loss, self._rng) = self._jit_step_tbptt_scan(
                self.params, self.state, self.opt_state,
                self._iter_scalar(n), inputs, labels, self._rng,
                masks_t, masks_s, lm_t, lm_s, None)
            self.iteration += n
            if self.listeners:
                chunk_losses = [(self.iteration - n + i + 1, LazyScore(losses[i]))
                                for i in range(n)]
        if tail or stateful:
            if self._jit_step_tbptt is None:
                self._jit_step_tbptt = self._make_step_tbptt()
            if carries is None:
                carries = self._init_carries(mb)
            total, chunks = None, 0
            start = 0 if stateful else n * L
            for s in range(start, T, L):
                inputs, labels, masks, lmasks = dicts(s, s + L)
                self._rng, sub = jax.random.split(self._rng)
                (self.params, self.state, self.opt_state, carries, loss
                 ) = self._jit_step_tbptt(
                    self.params, self.state, self.opt_state,
                    self._iter_scalar(1), inputs, labels, sub,
                    masks, lmasks, carries)
                self.iteration += 1
                total = loss if total is None else total + loss
                chunks += 1
                if stateful:
                    # per-chunk callbacks with each chunk's params
                    for lst in self.listeners:
                        lst.iteration_done(self, self.iteration,
                                           LazyScore(loss))
                elif self.listeners:
                    chunk_losses.append((self.iteration, LazyScore(loss)))
            tail_mean = total / max(chunks, 1)
            if stateful:
                return LazyScore(tail_mean)
            mean_loss = tail_mean if mean_loss is None else (
                (mean_loss * n + total) / (n + chunks))
        for it, score in chunk_losses:
            for lst in self.listeners:
                lst.iteration_done(self, it, score)
        return LazyScore(mean_loss)

    def fit(self, data, epochs: int = 1) -> List[float]:
        losses = []
        it = self._as_iterator(data)
        synced = 0
        for _ in range(epochs):
            for ds in it:
                losses.append(self.fit_batch(ds))
            synced = self._end_epoch(losses, synced)
        return losses

    def _end_epoch(self, losses, synced: int) -> int:
        """Shared epoch epilogue (see MultiLayerNetwork._end_epoch):
        batched score materialization, epoch bump, epoch_done listeners —
        the graph container previously skipped the listener callbacks."""
        materialize_scores(losses[synced:])
        self.epoch += 1
        for lst in self.listeners:
            if hasattr(lst, "epoch_done"):
                lst.epoch_done(self, self.epoch)
        return len(losses)

    @staticmethod
    def _as_iterator(data):
        if isinstance(data, DataSetIterator):
            return data
        if isinstance(data, (DataSet, MultiDataSet)):
            return ListDataSetIterator([data])
        if isinstance(data, tuple) and len(data) == 2:
            return ListDataSetIterator([DataSet(np.asarray(data[0]), np.asarray(data[1]))])
        raise TypeError(type(data))

    # -- inference ----------------------------------------------------------

    def output(self, *features, masks=None) -> List[np.ndarray]:
        """Activations of all output vertices, in network_outputs order
        (reference ComputationGraph.output)."""
        if self._jit_output is None:
            def fwd(params, state, inputs, mks):
                acts, _, _, _ = self._apply(params, state, inputs, train=False,
                                            rng=None, masks=mks)
                return [acts[n] for n in self.conf.network_outputs]
            self._jit_output = jax.jit(fwd)
        inputs = {n: jnp.asarray(f) for n, f in zip(self.conf.network_inputs, features)}
        mks = {n: (None if masks is None or masks[i] is None else jnp.asarray(masks[i]))
               for i, n in enumerate(self.conf.network_inputs)} if masks else None
        outs = self._jit_output(self.params, self.state, inputs, mks)
        return [np.asarray(o) for o in outs]

    def rnn_time_step(self, *features) -> List[np.ndarray]:
        """Stateful streaming inference over the DAG: each rank-2 input
        [mb, f] is treated as one timestep, rank-3 inputs stream their
        chunk; recurrent vertex state persists across calls (reference
        ComputationGraph.rnnTimeStep:1500)."""
        arrs = []
        ranks = []
        for f in features:
            a = jnp.asarray(f)
            ranks.append(a.ndim)
            if a.ndim == 2:
                a = a[:, None, :]
            arrs.append(a)
        # single-step squeeze only when EVERY input was a single timestep;
        # mixed-rank calls keep full sequence outputs
        squeeze = all(r == 2 for r in ranks)
        mb = arrs[0].shape[0]
        if self._stream_carries is not None:
            for c in jax.tree_util.tree_leaves(self._stream_carries):
                if c.shape[0] != mb:  # batch size changed → fresh state
                    self._stream_carries = None
                break
        if self._stream_carries is None:
            self._stream_carries = self._init_carries(mb)
        if self._jit_stream is None:
            def fwd(params, state, inputs, carries):
                acts, _, _, new_carries = self._apply(
                    params, state, inputs, train=False, rng=None, carries=carries)
                return [acts[n] for n in self.conf.network_outputs], new_carries
            self._jit_stream = jax.jit(fwd)
        inputs = {n: a for n, a in zip(self.conf.network_inputs, arrs)}
        outs, self._stream_carries = self._jit_stream(
            self.params, self.state, inputs, self._stream_carries)
        result = []
        for o in outs:
            o = np.asarray(o)
            result.append(o[:, 0] if squeeze and o.ndim == 3 else o)
        return result

    def rnn_clear_previous_state(self) -> None:
        """Reset streaming state (reference rnnClearPreviousState)."""
        self._stream_carries = None

    def _mask_dicts(self, mds: MultiDataSet):
        masks = {n: (None if m is None else jnp.asarray(m))
                 for n, m in zip(self.conf.network_inputs, mds.features_masks or
                                 [None] * len(self.conf.network_inputs))}
        lmasks = {n: (None if m is None else jnp.asarray(m))
                  for n, m in zip(self.conf.network_outputs, mds.labels_masks or
                                  [None] * len(self.conf.network_outputs))}
        return masks, lmasks

    def score(self, ds) -> float:
        mds = self._to_mds(ds)
        inputs = {n: jnp.asarray(f) for n, f in zip(self.conf.network_inputs, mds.features)}
        labels = {n: jax.tree_util.tree_map(jnp.asarray, l)
                  for n, l in zip(self.conf.network_outputs, mds.labels)}
        masks, lmasks = self._mask_dicts(mds)
        loss, _ = self._loss(self.params, self.state, inputs, labels,
                             train=False, rng=None, masks=masks, label_masks=lmasks)
        return float(loss)

    def evaluate(self, data, evaluation=None, output_index: int = 0):
        """Classification metrics for ONE output head (``output_index``),
        with masks honored — evaluate each head separately for multi-output
        graphs (reference ComputationGraph.evaluate scores output 0 too)."""
        from ..evaluation.evaluation import Evaluation
        ev = evaluation if evaluation is not None else Evaluation()
        for ds in self._as_iterator(data):
            mds = self._to_mds(ds)
            outs = self.output(*mds.features, masks=mds.features_masks)
            lm = None if mds.labels_masks is None else mds.labels_masks[output_index]
            ev.eval(mds.labels[output_index], outs[output_index], mask=lm)
        return ev

    def set_listeners(self, *listeners) -> None:
        self.listeners = list(listeners)

    def save(self, path: str, save_updater: bool = True) -> None:
        from ..utils.serializer import save_model
        save_model(self, path, save_updater=save_updater)

    @staticmethod
    def load(path: str, load_updater: bool = True) -> "ComputationGraph":
        from ..utils.serializer import load_model
        return load_model(path, load_updater=load_updater)
