"""Graph layer: eager + define-and-run graphs with a compiled-plan pool.

TPU-native re-expression of the reference's graph stack
(``hetu/graph/graph.h:21-27`` graph types, ``define_and_run_graph.cc:912``
plan matching, ``executable_graph.cc:1788`` CrucialRun):

* ``EagerGraph``     — ops execute immediately on jax arrays
  (reference ``eager_graph.h:8``).
* ``DefineAndRunGraph`` — user builds a symbolic op DAG once;
  ``run(fetches, feed_dict, ...)`` matches (strategy_id, fetches,
  feed shapes) against an **executable-plan pool** and on miss traces the
  DAG into a pure jax function, jit-compiles it with sharding annotations,
  and caches it — the exact analogue of Hetu's ExecGraphPlan + shape-plan
  pools (``define_and_run_graph.h:23``, ``.cc:912-1068``), with XLA playing
  the role of the ExecutableGraph runtime.

Autodiff is reverse-mode via ``jax.grad`` over the traced DAG rather than
per-op DoGradient (``graph.cc:117``); grad-reduce insertion for partial(-2)
grads is subsumed by GSPMD once activations/params carry shardings.

Run levels mirror ``graph.h:29-35``: TOPO / ALLOC / COMPUTE_ONLY / GRAD /
UPDATE — GRAD accumulates gradients across ``run`` calls into persistent
device buffers; UPDATE folds them into the parameter update.
"""
from __future__ import annotations

import enum
import itertools
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..core.dtype import canonicalize_dtype
from ..obs.phases import current_phase, phase
from ..obs.tracer import get_tracer
from .tensor import SymbolicDim, Tensor, concrete_shape

_op_ids = itertools.count()

# dedicated stream for per-graph dropout seeds: ht.set_seed reseeds THIS
# (not numpy's process-global RNG), so framework reproducibility and user
# np.random usage never interfere with each other
_GRAPH_SEED_STREAM = [np.random.RandomState()]


class RunLevel(enum.Enum):
    TOPO = "topo"
    ALLOC = "alloc"
    COMPUTE_ONLY = "compute_only"
    GRAD = "grad"
    UPDATE = "update"


# ---------------------------------------------------------------------------
# executable registry (static-analysis hook, hetu_tpu/analysis)
# ---------------------------------------------------------------------------


class ExecutableHandle:
    """A lowerable reference to a compiled plan, registered for analysis.

    Wraps a jitted function plus the abstract argument specs it was (or
    will be) compiled for, so ``hetu_tpu.analysis`` can obtain the closed
    jaxpr / StableHLO / compiled HLO of any executable — train steps,
    serving prefill/decode, pipeline stages — WITHOUT running it.
    ``meta`` carries graph-level facts the jaxpr cannot express (param
    shardings, mesh axes, grad-comm plan, serving pool snapshot hooks).
    """

    def __init__(self, name: str, jit_fn, abstract_args: Tuple,
                 meta: Optional[Dict[str, Any]] = None):
        self.name = name
        self.jit_fn = jit_fn
        self.abstract_args = tuple(abstract_args)
        self.meta: Dict[str, Any] = dict(meta or {})
        self._traced = None
        self._lowered = None
        self._compiled = None
        self._compiled_text = None

    def trace(self):
        if self._traced is None:
            self._traced = self.jit_fn.trace(*self.abstract_args)
        return self._traced

    @property
    def jaxpr(self):
        return self.trace().jaxpr

    def lower(self):
        if self._lowered is None:
            self._lowered = self.trace().lower()
        return self._lowered

    def compile(self):
        """The compiled executable (cached): GSPMD accounting reads its
        HLO text, the memory pass its ``memory_analysis()``."""
        if self._compiled is None:
            self._compiled = self.lower().compile()
        return self._compiled

    def compiled_text(self) -> str:
        """Post-SPMD optimized HLO text (compiles on first call)."""
        if self._compiled_text is None:
            self._compiled_text = self.compile().as_text()
        return self._compiled_text

    def __repr__(self):
        return f"ExecutableHandle({self.name!r})"


_EXECUTABLE_REGISTRY: Dict[str, ExecutableHandle] = {}


def register_executable(name: str, jit_fn, abstract_args,
                        meta: Optional[Dict[str, Any]] = None
                        ) -> ExecutableHandle:
    """Register (or replace) an analyzable executable under ``name``."""
    h = ExecutableHandle(name, jit_fn, abstract_args, meta)
    _EXECUTABLE_REGISTRY[name] = h
    return h


def get_executable(name: str) -> ExecutableHandle:
    return _EXECUTABLE_REGISTRY[name]


def iter_executables(prefix: str = "") -> List[ExecutableHandle]:
    return [h for n, h in sorted(_EXECUTABLE_REGISTRY.items())
            if n.startswith(prefix)]


def clear_executables(prefix: str = "") -> None:
    for n in [n for n in _EXECUTABLE_REGISTRY if n.startswith(prefix)]:
        del _EXECUTABLE_REGISTRY[n]
    # the trace plane's prediction cache holds a strong ref to each
    # priced handle (whose meta may close over an engine's KV pool):
    # evict alongside the registry or retiring an engine leaks its pool
    from ..obs.reconcile import clear_prediction_cache
    clear_prediction_cache(prefix)


def _abstract_of(a) -> jax.ShapeDtypeStruct:
    """The abstract spec of one executable argument."""
    if not hasattr(a, "aval"):
        return jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
    sharding = getattr(a, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        sharding = None            # uncommitted / single-device: free
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _select_tree(flag, new, old):
    """Per-leaf ``jnp.where(flag, new, old)`` over matching pytrees —
    the on-device skip primitive the AMP scaler (overflow) and the
    numeric sentry (anomaly verdict) share: when ``flag`` is True the
    new values pass through bitwise."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(flag, n, o), new, old)


class OpNode:
    """A graph node (reference ``OpDef``, ``operator.h:304``)."""

    __slots__ = ("id", "op_type", "impl", "inputs", "outputs", "attrs",
                 "name")

    def __init__(self, op_type: str, impl: Optional[Callable],
                 inputs: List[Tensor], attrs: Dict[str, Any], name: str):
        self.id = next(_op_ids)
        self.op_type = op_type
        self.impl = impl
        self.inputs = inputs
        self.outputs: List[Tensor] = []
        self.attrs = attrs
        self.name = name or f"{op_type}_{self.id}"

    def __repr__(self):
        return f"OpNode({self.name}, inputs={[t.name for t in self.inputs]})"


class Graph:
    """Base graph: op/tensor registry + tracing evaluator."""

    def __init__(self, name: str = "graph"):
        self.name = name
        self.ops: List[OpNode] = []
        self.cur_strategy_id: int = 0
        self.num_strategy: int = 1
        self.mesh: Optional[Mesh] = None
        # variable/optimizer state: tensor.id -> jax.Array (device resident)
        self._var_data: Dict[int, jax.Array] = {}
        self._var_tensors: Dict[int, Tensor] = {}
        self._placeholders: Dict[int, Tensor] = {}
        self._grad_accum: Dict[int, jax.Array] = {}
        self._rng_tensor: Optional[Tensor] = None
        self._rng_seed = _GRAPH_SEED_STREAM[0].randint(0, 2**31 - 1)
        self._run_counter = 0
        # axes currently traced in shard_map manual mode (explicit
        # grad-comm path): pspec sharding constraints referencing manual
        # axes are illegal inside the region and are skipped there
        self._manual_axes: Tuple[str, ...] = ()
        # MoE layers built in this graph record their dispatch bounds
        # here (nn/moe.py) for the analyzer's capacity accounting
        self._moe_meta: List[Dict[str, Any]] = []

    # -- construction -------------------------------------------------------

    def set_num_strategy(self, n: int) -> None:
        self.num_strategy = n

    def _lift_constant(self, value, dtype=None) -> Tensor:
        arr = jnp.asarray(value, dtype=canonicalize_dtype(dtype).to_jnp()
                          if dtype is not None else None)
        t = Tensor(arr.shape, arr.dtype, name="const", graph=self)
        node = OpNode("constant", None, [], {"value": arr}, t.name)
        node.outputs = [t]
        t.producer = node
        self.ops.append(node)
        return t

    def as_tensor(self, value) -> Tensor:
        if isinstance(value, Tensor):
            return value
        return self._lift_constant(value)

    def make_op(self, op_type: str, impl: Callable,
                inputs: Sequence[Any], attrs: Optional[Dict[str, Any]] = None,
                name: str = "", num_outputs: int = 1) -> Union[Tensor, List[Tensor]]:
        attrs = dict(attrs or {})
        in_tensors = [self.as_tensor(x) for x in inputs]
        ph = current_phase()
        if ph is not None:
            # the model phase the node was built under (obs/phases.py):
            # entered again around impl when the plan is traced
            attrs["_phase"] = ph
        node = OpNode(op_type, impl, in_tensors, attrs, name)
        # shape/dtype inference via abstract evaluation (replaces the
        # reference's per-op DoInferMeta, operator.h:423).  Unbound symbolic
        # dims get a provisional binding — recorded shapes are advisory; the
        # real shapes come from the feed arrays at trace time (shape plans).
        for t in in_tensors:
            for d in t.shape:
                if isinstance(d, SymbolicDim) and not d.is_bound:
                    d.set(16)
        in_structs = [jax.ShapeDtypeStruct(t.concrete_shape(), t.dtype.to_jnp())
                      for t in in_tensors]
        # underscore attrs are node metadata, not impl kwargs (same
        # filtering _eval_targets applies at trace time)
        call_attrs = {k: v for k, v in attrs.items()
                      if not k.startswith("_")}
        out_struct = jax.eval_shape(lambda *xs: impl(*xs, **call_attrs),
                                    *in_structs)
        flat_outs, treedef = jax.tree_util.tree_flatten(out_struct)
        outputs = []
        for i, s in enumerate(flat_outs):
            t = Tensor(s.shape, s.dtype, producer=node,
                       name=f"{node.name}:{i}" if len(flat_outs) > 1 else node.name,
                       graph=self,
                       requires_grad=any(x.requires_grad for x in in_tensors))
            outputs.append(t)
        node.outputs = outputs
        node.attrs["_treedef"] = treedef
        self.ops.append(node)
        self._post_make_op(node)
        return outputs[0] if num_outputs == 1 and len(outputs) == 1 else outputs

    def _post_make_op(self, node: OpNode) -> None:
        pass

    # -- variables / placeholders -------------------------------------------

    def add_variable(self, t: Tensor, init_fn: Callable[[], jax.Array]) -> None:
        node = OpNode("variable", None, [], {"init_fn": init_fn}, t.name)
        node.outputs = [t]
        t.producer = node
        t.graph = self
        self.ops.append(node)
        self._var_tensors[t.id] = t

    def add_placeholder(self, t: Tensor) -> None:
        node = OpNode("placeholder", None, [], {}, t.name)
        node.outputs = [t]
        t.producer = node
        t.graph = self
        self.ops.append(node)
        self._placeholders[t.id] = t

    def next_rng_tensor(self) -> Tensor:
        """The per-run RNG key tensor (auto-fed with a fresh key each run);
        stochastic ops (dropout) fold a per-op salt into it.  Replaces the
        reference's per-device RNG state (hetu/impl/random/)."""
        if self._rng_tensor is None:
            t = Tensor((2,), "uint32", name="_rng", graph=self)
            self.add_placeholder(t)
            self._rng_tensor = t
        return self._rng_tensor

    def _fresh_rng_key(self) -> np.ndarray:
        self._run_counter += 1
        return np.asarray(
            jax.random.PRNGKey(self._rng_seed + self._run_counter),
            dtype=np.uint32)

    def _materialize_var(self, t: Tensor) -> jax.Array:
        if t.id not in self._var_data:
            init_fn = t.producer.attrs["init_fn"]
            val = init_fn()
            sharding = self._sharding_for(t)
            if sharding is not None:
                val = jax.device_put(val, sharding)
            self._var_data[t.id] = val
        return self._var_data[t.id]

    def get_tensor_value(self, t: Tensor):
        if t.id in self._var_data:
            return self._var_data[t.id]
        if t.id in self._var_tensors:
            return self._materialize_var(t)
        raise ValueError(f"{t.name} has no stored value; fetch it via run()")

    def reset_variable(self, t: Tensor, value) -> None:
        sharding = self._sharding_for(t)
        val = jnp.asarray(value, dtype=t.dtype.to_jnp())
        if sharding is not None:
            val = jax.device_put(val, sharding)
        self._var_data[t.id] = val
        # external param writes (load_model / user resets) invalidate
        # any flat-optimizer fp32 master packed from the OLD values —
        # flat optimizers watch this epoch and the per-tensor log
        # (_ensure_flat_state refreshes ONLY the written params'
        # masters, so untouched bf16 params keep their fp32 precision)
        self._var_writes = getattr(self, "_var_writes", 0) + 1
        if not hasattr(self, "_var_write_log"):
            self._var_write_log = {}
        self._var_write_log[t.id] = self._var_writes

    # -- sharding -----------------------------------------------------------

    def _pspec_for(self, t: Tensor) -> Optional[PartitionSpec]:
        spec = getattr(t, "pspec", None)
        if spec is None or self.mesh is None:
            return spec
        # drop axis names the current mesh doesn't have: after a hot
        # switch to a smaller/reshaped mesh (e.g. tp or pp removed) stale
        # annotations on intermediates must degrade to replication on the
        # missing axes, exactly as the reference re-deduces ds on the new
        # topology
        names = set(self.mesh.axis_names)

        def _fix(entry):
            if entry is None:
                return None
            ent = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(n for n in ent if n in names)
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]

        fixed = [_fix(e) for e in spec]
        if all(f == e for f, e in zip(fixed, spec)):
            return spec
        return PartitionSpec(*fixed)

    def _sharding_for(self, t: Tensor) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        spec = self._pspec_for(t)
        if spec is None:
            return None
        return NamedSharding(self.mesh, spec)

    # -- evaluation engine ---------------------------------------------------

    def _topo_from(self, targets: Sequence[Tensor]) -> List[OpNode]:
        """Reverse-DFS topo sort (reference Graph::TopoSort, graph.h:960)."""
        visited: Dict[int, bool] = {}
        order: List[OpNode] = []

        def visit(node: OpNode):
            if node.id in visited:
                return
            visited[node.id] = True
            for t in node.inputs:
                if t.producer is not None:
                    visit(t.producer)
            order.append(node)

        for t in targets:
            if t.producer is not None:
                visit(t.producer)
        return order

    def _eval_targets(self, targets: Sequence[Tensor],
                      env: Dict[int, Any],
                      out_env: Optional[Dict[int, Any]] = None) -> List[Any]:
        """Evaluate target tensors given env (tensor.id -> concrete value).

        Pure w.r.t. env: used both eagerly and under jit tracing.
        ``out_env``, when given, receives every value computed along the
        way (keyed by tensor id) so callers can cache intermediates.
        """
        base_env = dict(env)  # leaf values only (placeholders/variables)
        env = dict(env) if out_env is None else out_env
        if out_env is not None:
            out_env.update(base_env)
        for node in self._topo_from(targets):
            if all(t.id in env for t in node.outputs):
                continue
            if node.op_type == "constant":
                env[node.outputs[0].id] = node.attrs["value"]
            elif node.op_type in ("variable", "placeholder"):
                if node.outputs[0].id not in env:
                    raise ValueError(
                        f"{node.op_type} {node.name} not fed/materialized")
            elif node.op_type == "gradients":
                self._eval_gradients_node(node, env, base_env)
            else:
                args = [env[t.id] for t in node.inputs]
                attrs = {k: v for k, v in node.attrs.items()
                         if not k.startswith("_")}
                ph = node.attrs.get("_phase")
                if ph is None:
                    out = node.impl(*args, **attrs)
                else:
                    # metadata only: the phase reaches the HLO op_name
                    # of the node's instructions, forward and backward
                    with jax.named_scope(ph):
                        out = node.impl(*args, **attrs)
                flat = jax.tree_util.tree_leaves(out)
                for t, v in zip(node.outputs, flat):
                    spec = self._pspec_for(t)
                    if spec is not None and self.mesh is not None \
                            and not self._manual_axes:
                        v = jax.lax.with_sharding_constraint(
                            v, NamedSharding(self.mesh, spec))
                    env[t.id] = v
        return [env[t.id] for t in targets]

    def _eval_gradients_node(self, node: OpNode, env: Dict[int, Any],
                             base_env: Optional[Dict[int, Any]] = None) -> None:
        """Reverse-mode autodiff (reference Graph::Gradients, graph.cc:117).

        Implemented as jax.grad over the traced forward closure from the
        requested vars to the loss; multi-consumer grad summation and
        partial-grad reduction fall out of jax's vjp + GSPMD.  The closure
        re-evaluates the forward from *leaf* values only (base_env), so the
        differentiated variables actually flow into the loss.
        """
        loss_t: Tensor = node.attrs["loss"]
        xs: List[Tensor] = node.attrs["xs"]
        leaf_env = base_env if base_env is not None else env

        def loss_fn(var_vals: Dict[int, Any]):
            inner_env = {k: v for k, v in leaf_env.items()
                         if k not in var_vals}
            inner_env.update(var_vals)
            (loss_val,) = self._eval_targets([loss_t], inner_env)
            return jnp.sum(loss_val) if loss_val.ndim > 0 else loss_val

        var_vals = {t.id: env[t.id] for t in xs}
        grads = jax.grad(loss_fn)(var_vals)
        for t_out, t_x in zip(node.outputs, xs):
            env[t_out.id] = grads[t_x.id]

    def make_gradients(self, loss: Tensor, xs: Sequence[Tensor]) -> List[Tensor]:
        node = OpNode("gradients", None, [loss] + list(xs),
                      {"loss": loss, "xs": list(xs)}, f"grad_{loss.name}")
        outputs = []
        for x in xs:
            g = Tensor(x.shape, x.dtype, producer=node,
                       name=f"grad_{x.name}", graph=self, is_grad=True)
            if hasattr(x, "pspec"):
                g.pspec = x.pspec
            outputs.append(g)
        node.outputs = outputs
        self.ops.append(node)
        return outputs

    @property
    def trainable_variables(self) -> List[Tensor]:
        return [t for t in self._var_tensors.values() if t.trainable]


class EagerGraph(Graph):
    """Immediate execution (reference ``eager_graph.h:8``)."""

    def _post_make_op(self, node: OpNode) -> None:
        env: Dict[int, Any] = {}
        for t in node.inputs:
            env[t.id] = t.get_data() if t._data is not None else \
                self.get_tensor_value(t) if t.id in self._var_tensors else None
            if env[t.id] is None:
                env[t.id] = self._eval_with_deps(t)
        args = [env[t.id] for t in node.inputs]
        attrs = {k: v for k, v in node.attrs.items() if not k.startswith("_")}
        out = node.impl(*args, **attrs)
        flat = jax.tree_util.tree_leaves(out)
        for t, v in zip(node.outputs, flat):
            t.set_data(v)

    def _eval_with_deps(self, t: Tensor):
        env = {}
        for node in self._topo_from([t]):
            for it in node.inputs:
                if it._data is not None:
                    env[it.id] = it._data
            for vt_id in self._var_tensors:
                env[vt_id] = self._materialize_var(self._var_tensors[vt_id])
        (val,) = self._eval_targets([t], env)
        return val

    def get_tensor_value(self, t: Tensor):
        if t._data is not None:
            return t._data
        return super().get_tensor_value(t)

    def next_rng_tensor(self) -> Tensor:
        # eager: a fresh concrete key every call
        return self._lift_constant(self._fresh_rng_key())


class DefineByRunGraph(Graph):
    """Lazy trace variant (reference ``define_by_run_graph.h:9``): ops
    record symbolically like DefineAndRun, but values materialize on
    demand via :meth:`get_or_compute` (the reference's ``GetOrCompute``)
    with per-tensor caching — new ops invalidate nothing already
    computed, matching torch-like deferred execution without re-running
    the whole graph per fetch."""

    def __init__(self, name: str = "define_by_run"):
        super().__init__(name)
        self._computed: Dict[int, Any] = {}

    def get_or_compute(self, t: Tensor):
        if t.id in self._computed:
            return self._computed[t.id]
        env: Dict[int, Any] = dict(self._computed)
        for vt_id, vt in self._var_tensors.items():
            env.setdefault(vt_id, self._materialize_var(vt))
        # cache every intermediate computed for this fetch (reference
        # GetOrCompute caches per-tensor): separate fetches then reuse
        # one consistent set of values instead of re-running upstream.
        # Variable VALUES stay out of the cache — reset_variable /
        # optimizer updates must be visible to later fetches.
        full_env: Dict[int, Any] = {}
        (val,) = self._eval_targets([t], env, out_env=full_env)
        self._computed.update(
            {k: v for k, v in full_env.items()
             if k not in self._var_tensors})
        return val

    def feed(self, t: Tensor, value) -> None:
        """Bind a placeholder's value for subsequent get_or_compute."""
        self._computed[t.id] = jnp.asarray(value)

    def invalidate(self) -> None:
        """Drop cached activations (keep variables)."""
        self._computed.clear()

    def get_tensor_value(self, t: Tensor):
        if t.id in self._computed:
            return self._computed[t.id]
        if t.id in self._var_tensors:
            return super().get_tensor_value(t)
        return self.get_or_compute(t)


class DefineAndRunGraph(Graph):
    """Symbolic graph with an executable-plan pool."""

    def __init__(self, name: str = "define_and_run"):
        super().__init__(name)
        self._plan_pool: Dict[Tuple, Any] = {}
        self._abstract_pool: Dict[Tuple, Any] = {}  # plan key -> arg specs
        self._cost_cache: Dict[int, Any] = {}       # id(plan) -> cost dict
        self._shape_buckets: Optional[List[int]] = None
        self._bucket_pad_values: Dict[int, Any] = {}
        self._memory_profiler = None  # lazy (env-gated) MemoryProfiler
        # every DerivedDim ever seen in a feed/placeholder shape: stale
        # provisional overrides are cleared for ALL of them on every bind
        # pass, not only the ones the current feed_dict mentions
        self._derived_dims: Dict[int, Any] = {}
        # explicit grad-comm introspection (set at plan-build time)
        self._grad_comm_active: bool = False
        self._grad_comm_fallback: Optional[str] = None
        # plan key -> registered analysis-handle name (analysis hook)
        self._plan_names: Dict[Tuple, str] = {}
        # numeric-sentry chaos seam (resilience/sentry.py): an auto-fed
        # int32 code placeholder (0 = clean) the compiled step reads to
        # poison gradients/loss at the injection point — feed VALUE
        # only, so injections never retrace
        self._sentry_tensor: Optional[Tensor] = None
        self._sentry_next_code: int = 0
        # ZeRO-3 flat: (optimizer, xs) whose per-param working copies
        # went stale at the last update step (the flat fp32 master is
        # the authoritative storage); refreshed lazily on first read
        self._stale_flat_params: Optional[Tuple[Any, list]] = None

    def _refresh_stale_params(self) -> None:
        """Materialize ZeRO-3 flat working params from the flat master
        (bitwise the in-region gather's values), then clear the flag."""
        stale = self._stale_flat_params
        if stale is not None:
            self._stale_flat_params = None
            stale[0].materialize_flat_params(self, stale[1])

    def get_tensor_value(self, t: Tensor):
        if self._stale_flat_params is not None:
            self._refresh_stale_params()
        return super().get_tensor_value(t)

    # -- numeric sentry (resilience/sentry.py) -------------------------------

    def _sentry_code_tensor(self) -> Tensor:
        if self._sentry_tensor is None:
            t = Tensor((), "int32", name="_sentry_code", graph=self)
            self.add_placeholder(t)
            self._sentry_tensor = t
        return self._sentry_tensor

    def inject_numeric_fault(self, kind: str) -> None:
        """Arm a one-shot numeric chaos injection for the NEXT
        UPDATE-level run (FaultPlan ``grad_nan`` / ``grad_spike`` /
        ``loss_spike`` verdicts): the fed code makes the compiled step
        poison its own gradients/loss at the sentry's seam."""
        from ..resilience.sentry import INJECT_CODES
        if kind not in INJECT_CODES:
            raise ValueError(f"unknown numeric fault {kind!r}; have "
                             f"{sorted(INJECT_CODES)}")
        self._sentry_next_code = INJECT_CODES[kind]

    @staticmethod
    def _sentry_for(update_node, run_level) -> Optional[Any]:
        """The active NumericSentry for this plan, or None — ONE
        definition shared by plan build, feed marshalling and meta
        registration so the compiled program and its feeds can never
        disagree about whether the code input exists."""
        if update_node is None or run_level != RunLevel.UPDATE:
            return None
        return getattr(update_node.attrs["optimizer"], "sentry", None)

    # -- shape-plan bucketing ------------------------------------------------

    def set_shape_buckets(self, buckets, pad_values=None) -> None:
        """Bucket symbolic feed dims so varying shapes reuse compiled
        plans (reference DeduceShapePlan + shape-plan pool,
        define_and_run_graph.cc:273; SURVEY hard part #4).

        ``buckets``: sorted list of allowed sizes, or an int alignment
        (round symbolic dims up to a multiple — the data/bucket.py
        alignment convention).  Feeds are padded up to the bucket along
        every :class:`SymbolicDim` axis; ``pad_values`` maps placeholder
        Tensors to their pad fill (default 0 — use the loss ignore_index
        for label feeds so padded positions drop out of the loss).
        """
        if isinstance(buckets, int):
            self._shape_buckets = buckets
        else:
            self._shape_buckets = sorted(int(b) for b in buckets)
            if not self._shape_buckets:
                raise ValueError("shape bucket list must be non-empty")
        self._bucket_pad_values = {
            (t.id if isinstance(t, Tensor) else t): v
            for t, v in (pad_values or {}).items()}

    def _bucket_dim(self, size: int) -> int:
        b = self._shape_buckets
        if isinstance(b, int):
            return ((size + b - 1) // b) * b
        for cand in b:
            if cand >= size:
                return cand
        raise ValueError(
            f"feed dim {size} exceeds the largest shape bucket {b[-1]}")

    def _bucket_feeds(self, feed_dict: Dict[Tensor, Any]
                      ) -> Dict[Tensor, Any]:
        """Pad feeds up to bucket boundaries along symbolic dims."""
        out = {}
        for t, v in feed_dict.items():
            arr = np.asarray(v) if not isinstance(v, jax.Array) else v
            pads = []
            changed = False
            for i, dim in enumerate(t.shape):
                if isinstance(dim, SymbolicDim) and i < arr.ndim:
                    tgt = self._bucket_dim(arr.shape[i])
                    pads.append((0, tgt - arr.shape[i]))
                    changed = changed or tgt != arr.shape[i]
                else:
                    pads.append((0, 0))
            if changed:
                # np.pad keeps the feed host-side: _plan_key reads feed
                # dtypes/shapes and must not force a device sync; run()
                # device_puts the padded array once afterwards
                fill = self._bucket_pad_values.get(t.id, 0)
                arr = np.pad(np.asarray(arr), pads, constant_values=fill)
            out[t] = arr
        return out

    # -- plan construction ---------------------------------------------------

    @staticmethod
    def _leaf_dims(dim):
        from .tensor import DerivedDim
        out = []
        stack = [dim]
        while stack:
            d = stack.pop()
            if isinstance(d, DerivedDim):
                stack.extend(p for p in d._parents
                             if isinstance(p, SymbolicDim))
            elif isinstance(d, SymbolicDim):
                out.append(d)
        return out

    @staticmethod
    def _derived_nodes(dim):
        """Every DerivedDim on the expression DAG rooted at ``dim``
        (including itself) — overrides must clear along the WHOLE path,
        or a nested dim evaluates through a stale intermediate."""
        from .tensor import DerivedDim
        out = []
        stack = [dim]
        while stack:
            d = stack.pop()
            if isinstance(d, DerivedDim):
                out.append(d)
                stack.extend(p for p in d._parents
                             if isinstance(p, SymbolicDim))
        return out

    def _bind_symbolic_dims(self, feed_dict: Dict[Tensor, Any]) -> None:
        from .tensor import DerivedDim
        # two passes: leaf symbols bind from feeds first, then DERIVED
        # dims (IntSymbol arithmetic, e.g. seq // cp) are CHECKED against
        # their computed value — a mismatched feed must raise, not
        # silently override the expression.  The check only fires when
        # every leaf was bound by THIS feed pass (stale advisory
        # bindings from make_op's provisional set(16) must not reject
        # valid feeds) and shape buckets are off (independent padding
        # legitimately breaks arithmetic relations between dims).
        derived = []
        fresh: set = set()
        for t, v in feed_dict.items():
            v_shape = np.shape(v)
            if len(v_shape) != len(t.shape):
                raise ValueError(
                    f"feed for {t.name} has rank {len(v_shape)}, "
                    f"expected {len(t.shape)} ({t.shape})")
            for dim, d in zip(t.shape, v_shape):
                if isinstance(dim, DerivedDim):
                    derived.append((t, dim, d))
                elif isinstance(dim, SymbolicDim):
                    dim.set(d)
                    fresh.add(id(dim))
                elif int(dim) != d:
                    raise ValueError(
                        f"feed for {t.name} has shape {v_shape}, "
                        f"expected {t.shape}")
        # register derived dims reachable from this feed AND from every
        # placeholder, then clear provisional overrides on ALL of them: a
        # stale override from an earlier run (unbound leaves/bucketing)
        # must not shadow a re-evaluation after this pass rebinds leaves
        for t in itertools.chain(feed_dict.keys(),
                                 self._placeholders.values()):
            for dim in t.shape:
                if isinstance(dim, DerivedDim):
                    for node in self._derived_nodes(dim):
                        self._derived_dims[id(node)] = node
        for node in self._derived_dims.values():
            node.clear_override()
        seen: Dict[int, int] = {}
        for t, dim, d in derived:
            prev = seen.get(id(dim))
            if prev is not None and prev != d:
                raise ValueError(
                    f"conflicting feeds for derived dim {dim.name}: "
                    f"{prev} vs {d} (tensor {t.name})")
            seen[id(dim)] = d
            enforce = (self._shape_buckets is None
                       and all(id(l) in fresh
                               for l in self._leaf_dims(dim))
                       and dim.is_bound)
            if enforce:
                if dim.get() != d:
                    raise ValueError(
                        f"feed for {t.name} gives derived dim {dim.name} "
                        f"= {d}, but its expression evaluates to "
                        f"{dim.get()}")
            else:
                dim.set(d)  # provisional (unbound leaves / bucketing)

    def _plan_key(self, fetches, feed_dict, num_micro_batches, run_level,
                  update_node):
        feed_sig = tuple(sorted(
            (t.id, tuple(np.shape(v)), str(np.asarray(v).dtype))
            for t, v in feed_dict.items()))
        fetch_sig = tuple(t.id for t in fetches)
        return (self.cur_strategy_id, fetch_sig, feed_sig,
                num_micro_batches, run_level,
                update_node.id if update_node is not None else None,
                # remat/offload contexts are baked into the traced plan
                getattr(self, "_recompute_policy", None),
                getattr(self, "_offload", False))

    def _split_micro_batches(self, feeds: Dict[int, Any], n: int):
        """Stack feed arrays into [n, batch/n, ...] micro-batch form
        (reference NDArray::split at executable_graph.cc:1828) — the
        leading dim is consumed by the executor's ``lax.scan`` so the
        fwd+bwd graph is traced ONCE regardless of n (the reference loops
        micro-batches at runtime, executable_graph.cc:1424; a trace-time
        Python loop would duplicate the whole XLA program n times).
        Scalars (0-d feeds) are replicated; the rng key feed is folded
        with the micro-batch index so stochastic ops differ per
        micro-batch."""
        rng_id = self._rng_tensor.id if self._rng_tensor is not None else None
        if n == 1:
            return feeds
        out = {}
        for tid, v in feeds.items():
            if tid == rng_id:
                out[tid] = jnp.stack(
                    [jax.random.fold_in(v, i) for i in range(n)])
                continue
            if np.ndim(v) == 0:
                out[tid] = jnp.broadcast_to(jnp.asarray(v), (n,))
                continue
            b = v.shape[0]
            assert b % n == 0, f"batch {b} not divisible by {n} micro-batches"
            out[tid] = v.reshape(n, b // n, *v.shape[1:])
        return out

    def _plan_explicit_grad_comm(self, opt, fetches: List[Tensor],
                                 feed_tensors: List[Tensor],
                                 num_micro_batches: int,
                                 loss_t: Optional[Tensor] = None,
                                 sentry_active: bool = False):
        """Decide whether the explicit coalesced grad-comm path applies
        and build its shard_map specs.  Returns (plan, None) or
        (None, reason).

        The path runs fwd+bwd in shard_map MANUAL mode over the dp axis
        (so gradients stay local until the optimizer's bucketed
        collectives sync them).  It requires a pure-dp mesh, ZeRO<=2
        (params replicated over dp at rest), and every non-scalar fetch
        annotated with a pspec; anything else falls back to the implicit
        GSPMD per-tensor sync.
        """
        dpa = opt.dp_axis
        mesh = self.mesh
        if mesh is None:
            return None, "no mesh on the graph"
        if tuple(mesh.axis_names) != (dpa,):
            return None, (f"mesh axes {tuple(mesh.axis_names)} != "
                          f"({dpa!r},): explicit path needs a pure-dp mesh")
        if mesh.shape[dpa] <= 1:
            return None, "dp axis has size 1 (nothing to sync)"
        if opt.zero >= 3 and not getattr(opt, "flat_state", False):
            # per-param ZeRO-3 rides GSPMD (partitioner-inserted
            # gathers); the FLAT layout owns its gathers explicitly
            # (param_gather buckets), so flat zero-3 stays on this path
            return None, "zero-3 (FSDP) keeps params dp-sharded at rest"

        def _refs_dp(spec) -> bool:
            if spec is None:
                return False
            for e in spec:
                ents = e if isinstance(e, tuple) else (e,)
                if dpa in ents:
                    return True
            return False

        for t in self._var_tensors.values():
            if _refs_dp(self._pspec_for(t)):
                return None, f"variable {t.name} is sharded over {dpa!r}"
        # grad sync uses the data-parallel MEAN convention (torch-DDP
        # semantics): correct for mean-normalized losses (this repo's
        # convention), 1/dp-scaled for sum-reduced ones.  Mean-ness is
        # not structurally decidable for composed losses, so — like
        # torch DDP — the convention is documented (optimizer docstring,
        # DESIGN.md §7) and only the unambiguous top-level reduce_sum is
        # caught here as a best-effort guard.
        loss_id = loss_t.id if loss_t is not None else None
        if loss_t is not None and loss_t.producer is not None \
                and loss_t.producer.op_type == "reduce_sum":
            return None, (f"loss {loss_t.name} is sum-reduced; the "
                          f"explicit path's dp-mean grad sync assumes "
                          f"a mean-normalized loss")
        fetch_specs = []
        for t in fetches:
            if len(t.shape) == 0:
                # only the loss has known (mean) reduction semantics
                # under manual dp; pmean of an arbitrary scalar (a sum,
                # max, count...) would silently change its value
                if loss_id is not None and t.id != loss_id:
                    return None, (f"scalar fetch {t.name} is not the "
                                  f"loss (unknown reduction semantics "
                                  f"under manual dp)")
                fetch_specs.append(PartitionSpec())
            else:
                spec = self._pspec_for(t)
                # the spec must actually shard over dp: a replicated
                # annotation on a dp-dependent value would let each rank
                # return its own local shard as "the" result
                if spec is None or not _refs_dp(spec):
                    return None, (f"non-scalar fetch {t.name} has no "
                                  f"{dpa!r}-sharded pspec (manual region "
                                  f"cannot place it)")
                fetch_specs.append(spec)
        feed_specs = {}
        tensors = list(feed_tensors)
        if self._rng_tensor is not None and \
                all(t.id != self._rng_tensor.id for t in tensors):
            tensors.append(self._rng_tensor)
        if sentry_active:
            st = self._sentry_code_tensor()
            if all(t.id != st.id for t in tensors):
                tensors.append(st)
        M = num_micro_batches
        for t in tensors:
            base = self._pspec_for(t) or PartitionSpec()
            if t.ndim == 0:
                feed_specs[t.id] = PartitionSpec()  # (M,) replicated stack
            elif M > 1:
                feed_specs[t.id] = PartitionSpec(None, *base)
            else:
                feed_specs[t.id] = base
        return {"axis": dpa, "feed_specs": feed_specs,
                "fetch_specs": fetch_specs}, None

    def _build_executable(self, fetches: List[Tensor],
                          feed_tensors: List[Tensor],
                          num_micro_batches: int,
                          run_level: RunLevel,
                          update_node: Optional[OpNode]):
        """Trace the DAG into a pure jitted step function.

        Signature: step(var_state, opt_state, grad_accum, feeds)
                   -> (fetch_vals, new_var_state, new_opt_state, new_grad_accum)
        var/opt/grad_accum are donated (device-resident, updated in place) —
        the analogue of the reference's fused param/grad buffers
        (executable_graph.h:292-303).
        """
        graph = self
        # activation recompute / host offload (reference recompute +
        # activation_cpu_offload graph passes -> XLA remat policies)
        from .recompute import offload_policy, resolve_policy
        remat_policy = resolve_policy(getattr(self, "_recompute_policy", None))
        if getattr(self, "_offload", False):
            off = offload_policy()
            remat_policy = off if off is not None else (
                remat_policy or jax.checkpoint_policies.nothing_saveable)
        scaler = update_node.attrs.get("grad_scaler") \
            if update_node is not None else None
        if scaler is not None and not scaler.enabled:
            scaler = None

        # explicit coalesced/quantized gradient sync (optimizer
        # grad_comm): the fwd+bwd (incl. the micro-batch scan) runs in a
        # shard_map manual region over the dp axis, so gradients stay
        # LOCAL until the optimizer's bucketed collective syncs them —
        # once per step, not once per micro-batch or per parameter.
        # numeric sentry (resilience/sentry.py): fused finite/spike
        # verdict + on-device update skip, UPDATE-level plans only
        sentry = self._sentry_for(update_node, run_level)
        sentry_tid = None
        loss_fetch_idx = None
        if sentry is not None:
            loss_t_sentry = update_node.attrs["grad_node"].attrs["loss"]
            loss_fetch_idx = next(
                (i for i, f in enumerate(fetches)
                 if isinstance(f, Tensor) and f.id == loss_t_sentry.id),
                None)
            if loss_fetch_idx is None:
                raise ValueError(
                    "numeric sentry needs the loss among the fetches "
                    "(its spike/finite verdict reads the merged loss)")
            sentry_tid = self._sentry_code_tensor().id

        explicit = None
        flat_mode = False
        gc_state = (False, None)      # (active, fallback_reason) per plan
        if update_node is not None:
            opt_gc = update_node.attrs["optimizer"]
            if getattr(opt_gc, "grad_comm", None) is not None:
                if scaler is not None:
                    why = "dynamic loss scaler active"
                    explicit = None
                else:
                    explicit, why = self._plan_explicit_grad_comm(
                        opt_gc, fetches, feed_tensors, num_micro_batches,
                        loss_t=update_node.attrs["grad_node"]
                        .attrs["loss"],
                        sentry_active=sentry is not None)
                gc_state = (explicit is not None,
                            None if explicit else why)
                # reduce-scatter-only ZeRO-2: the update runs on the
                # locally-owned flat chunk INSIDE the manual region, so
                # the full gradient never materializes.  GRAD-level runs
                # keep the all-reduce sync — persistent accumulation
                # stores full (replicated) gradients.
                flat_mode = bool(explicit is not None
                                 and getattr(opt_gc, "flat_state", False)
                                 and run_level == RunLevel.UPDATE)

        def step(var_state, opt_state, grad_accum, feeds_mb):
            scale = opt_state["_scaler"]["scale"] if scaler is not None \
                else None

            # feeds_mb: list of per-micro-batch dicts
            def fwd_bwd(mb_feeds, vstate):
                env = {**vstate, **mb_feeds}
                if update_node is not None:
                    grad_node = update_node.attrs["grad_node"]
                    xs = grad_node.attrs["xs"]
                    loss_t = grad_node.attrs["loss"]

                    def loss_fn(vv):
                        inner = {**env, **vv}
                        (lv,) = graph._eval_targets([loss_t], inner)
                        lv = jnp.sum(lv) if lv.ndim > 0 else lv
                        if scaler is not None:
                            lv = scaler.scale_loss(lv, {"scale": scale})
                        return lv

                    if remat_policy is not None:
                        loss_fn = jax.checkpoint(loss_fn, policy=remat_policy)
                    var_vals = {t.id: env[t.id] for t in xs}
                    loss_val, grads = jax.value_and_grad(loss_fn)(var_vals)
                    if scaler is not None:
                        loss_val = scaler.unscale_loss(
                            loss_val, {"scale": scale})
                        grads = scaler.unscale_grads(
                            grads, {"scale": scale})
                    # evaluate non-loss fetches too
                    other = [f for f in fetches if f.id != loss_t.id]
                    other_vals = graph._eval_targets(other, env) if other else []
                    fetch_vals = []
                    oi = 0
                    for f in fetches:
                        if f.id == loss_t.id:
                            fetch_vals.append(loss_val)
                        else:
                            fetch_vals.append(other_vals[oi])
                            oi += 1
                    return fetch_vals, grads
                fetch_vals = graph._eval_targets(fetches, env)
                return fetch_vals, None

            # micro-batch loop as a runtime lax.scan over the stacked
            # [M, ...] feeds (reference ComputeFunc loop,
            # executable_graph.cc:1424): one traced fwd+bwd body for any
            # M, instead of unrolling M copies of the program.
            # Scalar fetches average over micro-batches; non-scalar
            # fetches return the last micro-batch's value.
            M = num_micro_batches

            def _merge_fetches(carry_fv, fv):
                return [c + f if f.ndim == 0 else f
                        for c, f in zip(carry_fv, fv)]

            def _zeros_of(sds):
                return jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), sds)

            if update_node is None:
                if M == 1:
                    fetch_vals, _ = fwd_bwd(feeds_mb, var_state)
                    return fetch_vals, var_state, opt_state, grad_accum

                def body(carry_fv, mb):
                    fv, _ = fwd_bwd(mb, var_state)
                    return _merge_fetches(carry_fv, fv), None

                first = jax.tree_util.tree_map(lambda v: v[0], feeds_mb)
                fv_sds, _ = jax.eval_shape(fwd_bwd, first, var_state)
                fetch_vals, _ = lax.scan(body, _zeros_of(fv_sds), feeds_mb)
                out = [v / M if v.ndim == 0 else v for v in fetch_vals]
                return out, var_state, opt_state, grad_accum

            def compute_grads(vstate, fmb):
                # grad accumulation across micro-batches; returns the
                # merged fetch values and the 1/M-normalized accumulated
                # grads (LOCAL grads inside a manual region)
                if M == 1:
                    fetch_vals, acc_grads = fwd_bwd(fmb, vstate)
                else:
                    def body(carry, mb):
                        carry_fv, carry_g = carry
                        fv, g = fwd_bwd(mb, vstate)
                        new_g = {k: carry_g[k] + g[k] for k in g}
                        return (_merge_fetches(carry_fv, fv), new_g), None

                    first = jax.tree_util.tree_map(lambda v: v[0], fmb)
                    fv_sds, g_sds = jax.eval_shape(fwd_bwd, first, vstate)
                    (fetch_vals, acc_grads), _ = lax.scan(
                        body, (_zeros_of(fv_sds), _zeros_of(g_sds)), fmb)
                acc_grads = {k: g / M for k, g in acc_grads.items()}
                fetch_vals = [v / M if v.ndim == 0 else v
                              for v in fetch_vals]
                return fetch_vals, acc_grads

            if explicit is not None and flat_mode:
                # flat ZeRO-2 fast path: fwd+bwd, reduce-scatter, the
                # local-chunk optimizer update AND the param all-gather
                # all happen inside ONE manual region — the gradients
                # cross the wire exactly once (scattered), the updated
                # params exactly once (weight dtype).
                dpa = explicit["axis"]
                opt_flat = update_node.attrs["optimizer"]
                # sentry state never enters the manual region: its
                # scalars update OUTSIDE from the psum-reduced signals
                # the region returns
                opt_region = {k: v for k, v in opt_state.items()
                              if k != "_sentry"}

                def flat_phase(vstate, fmb, fstate, gaccum):
                    graph._manual_axes = (dpa,)
                    try:
                        if opt_flat.zero >= 3:
                            # ZeRO-3: working params exist only as 1/dp
                            # master chunks at rest — gather each bucket
                            # just-in-time in the weight dtype
                            # (param_gather) before the fwd+bwd reads it
                            vstate = {**vstate,
                                      **opt_flat._flat_gather_params(
                                          fstate,
                                          update_node.attrs["xs"], dpa)}
                        fv, acc = compute_grads(vstate, fmb)
                        if gaccum:
                            # persistent GRAD-level grads arrive already
                            # mean-synced and replicated; the dp-mean of
                            # (local + replicated) preserves them exactly
                            acc = {k: acc[k] + gaccum[k] for k in acc}
                        if sentry is not None:
                            # the chaos seam: poison the accumulated
                            # gradients per the fed code (1.0 when clean)
                            code_l = jnp.reshape(fmb[sentry_tid],
                                                 (-1,))[0]
                            acc = sentry.inject_grads(acc, code_l)
                        new_vars, new_fstate, sqn = \
                            opt_flat._flat_sync_and_update(
                                vstate, fstate, acc,
                                update_node.attrs["xs"], dpa,
                                want_sq_norm=sentry is not None)
                    finally:
                        graph._manual_axes = ()
                    fv = [lax.pmean(v, dpa) if v.ndim == 0 else v
                          for v in fv]
                    if sentry is not None:
                        # sqn is psum-reduced (replicated by reduction),
                        # so it may leave the region un-linted
                        return fv, new_vars, new_fstate, sqn
                    return fv, new_vars, new_fstate

                from ..parallel import comm as _comm
                fspecs = opt_flat._flat_state_pspecs(opt_region)
                # the step counter never leaves the manual region (see
                # _flat_sync_and_update); it increments out here where
                # its replication is structural
                out_fspecs = {k: v for k, v in fspecs.items()
                              if k != "step"}
                gac_specs = {k: PartitionSpec() for k in grad_accum}
                out_specs = (explicit["fetch_specs"], PartitionSpec(),
                             out_fspecs)
                if sentry is not None:
                    out_specs = out_specs + (PartitionSpec(),)
                flat_fn = _comm.shard_map(
                    flat_phase, graph.mesh,
                    in_specs=(PartitionSpec(), explicit["feed_specs"],
                              fspecs, gac_specs),
                    out_specs=out_specs)
                outs = flat_fn(var_state, feeds_mb, opt_region,
                               grad_accum)
                if sentry is not None:
                    fetch_vals, new_vars, new_opt, grad_sq = outs
                else:
                    fetch_vals, new_vars, new_opt = outs
                new_opt = dict(new_opt)
                if sentry is not None:
                    code = jnp.reshape(feeds_mb[sentry_tid], (-1,))[0]
                    fetch_vals = list(fetch_vals)
                    fetch_vals[loss_fetch_idx] = sentry.inject_loss(
                        fetch_vals[loss_fetch_idx], code)
                    ok, new_sstate = sentry.update(
                        fetch_vals[loss_fetch_idx], grad_sq,
                        opt_state["_sentry"])
                    # anomalous verdict: select the OLD params, flat
                    # buffers and step counter — a skipped step leaves
                    # bitwise-zero residue
                    old_core = {k: v for k, v in opt_region.items()
                                if k != "step"}
                    new_vars = _select_tree(ok, new_vars, var_state)
                    new_opt = _select_tree(ok, new_opt, old_core)
                    new_opt["step"] = opt_state["step"] + \
                        jnp.where(ok, 1, 0).astype(jnp.int32)
                    new_opt["_sentry"] = new_sstate
                else:
                    new_opt["step"] = opt_state["step"] + 1
                new_accum = {k: jnp.zeros_like(v)
                             for k, v in grad_accum.items()} \
                    if grad_accum else {}
                return fetch_vals, new_vars, new_opt, new_accum

            if explicit is not None:
                dpa = explicit["axis"]
                opt_sync = update_node.attrs["optimizer"]

                def grad_phase(vstate, fmb):
                    graph._manual_axes = (dpa,)
                    try:
                        fv, acc = compute_grads(vstate, fmb)
                        # micro-batch-accumulated grads sync ONCE per
                        # step through fused (quantized) buckets
                        acc = opt_sync.sync_gradients(acc, dpa)
                    finally:
                        graph._manual_axes = ()
                    fv = [lax.pmean(v, dpa) if v.ndim == 0 else v
                          for v in fv]
                    return fv, acc

                from ..parallel import comm as _comm
                sync_fn = _comm.shard_map(
                    grad_phase, graph.mesh,
                    in_specs=(PartitionSpec(), explicit["feed_specs"]),
                    out_specs=(explicit["fetch_specs"], PartitionSpec()))
                fetch_vals, acc_grads = sync_fn(var_state, feeds_mb)
            else:
                fetch_vals, acc_grads = compute_grads(var_state, feeds_mb)

            # fold in persistent accumulation (RunLevel.GRAD across runs)
            if grad_accum:
                acc_grads = {k: acc_grads[k] + grad_accum.get(k, 0.0)
                             for k in acc_grads}

            if run_level == RunLevel.GRAD:
                return fetch_vals, var_state, opt_state, acc_grads

            # UPDATE: apply optimizer
            opt = update_node.attrs["optimizer"]
            opt_core = {k: v for k, v in opt_state.items()
                        if k not in ("_scaler", "_sentry")}
            if sentry is not None:
                # the chaos seam: poison the (accumulated, synced)
                # gradients per the fed code (multiply by 1.0 = bitwise
                # identity on a clean step)
                code = jnp.reshape(feeds_mb[sentry_tid], (-1,))[0]
                acc_grads = sentry.inject_grads(acc_grads, code)
            with phase("optimizer"):
                new_vars, new_opt = opt._apply_updates(
                    var_state, opt_core, acc_grads,
                    update_node.attrs["xs"])
            if scaler is not None:
                # skip the update (params AND optimizer state) on overflow,
                # then grow/backoff the scale (reference update_scale op)
                from .amp import check_finite
                finite = check_finite(acc_grads)
                new_vars = _select_tree(finite, new_vars, var_state)
                new_opt = _select_tree(finite, new_opt, opt_core)
            if sentry is not None:
                fetch_vals = list(fetch_vals)
                fetch_vals[loss_fetch_idx] = sentry.inject_loss(
                    fetch_vals[loss_fetch_idx], code)
                # the same fp32 sum-of-squares the global-norm clip
                # reads (Optimizer._grad_sq_norm; XLA CSE dedupes)
                grad_sq = opt._grad_sq_norm(acc_grads,
                                            update_node.attrs["xs"])
                ok, new_sstate = sentry.update(
                    fetch_vals[loss_fetch_idx], grad_sq,
                    opt_state["_sentry"])
                # anomalous verdict: keep OLD params, optimizer state
                # and step counter — bitwise-zero residue on skip
                new_vars = _select_tree(ok, new_vars, var_state)
                new_opt = _select_tree(ok, new_opt, opt_core)
                new_opt["_sentry"] = new_sstate
            if scaler is not None:
                new_opt["_scaler"] = scaler.update_state(
                    opt_state["_scaler"], finite)
            new_accum = {k: jnp.zeros_like(v) for k, v in grad_accum.items()} \
                if grad_accum else {}
            return fetch_vals, new_vars, new_opt, new_accum

        jit_step = jax.jit(step, donate_argnums=(0, 1, 2))
        return jit_step, gc_state, flat_mode

    # -- analysis hook -------------------------------------------------------

    def _collect_pspec_edges(self) -> List[Dict[str, Any]]:
        """Producer -> consumer pspec edges of this graph, for the
        per-edge attribution pass (hetu_tpu/analysis/edges).

        Every tensor carrying a pspec annotation is a constraint site
        (``_eval_targets`` applies ``with_sharding_constraint`` there);
        the edge runs from its nearest *annotated* dataflow ancestor to
        it, and ``dstates.deduce_pspec_transition`` names the collective
        GSPMD will insert for the transition.  Identity edges (the
        annotation merely restates the inherited layout) are dropped.
        """
        edges: List[Dict[str, Any]] = []
        if self.mesh is None:
            return edges
        mesh_axes = {str(a): int(s) for a, s in self.mesh.shape.items()}
        if max(mesh_axes.values(), default=1) <= 1:
            return edges
        from ..parallel.dstates import _spec_pairs, deduce_pspec_transition

        def _ancestor(t, limit: int = 128):
            """Nearest annotated tensor on the main dataflow chain."""
            for _ in range(limit):
                node = t.producer
                if node is None or not node.inputs:
                    return None
                t = node.inputs[0]
                if self._pspec_for(t) is not None:
                    return t
            return None

        for node in self.ops:
            for out in node.outputs:
                dst_spec = self._pspec_for(out)
                if dst_spec is None or node.op_type in ("variable",
                                                        "placeholder"):
                    continue    # leaf annotations constrain inputs only
                src_t = _ancestor(out)
                src_spec = self._pspec_for(src_t) \
                    if src_t is not None else None
                try:
                    src_shape = tuple(src_t.concrete_shape()) \
                        if src_t is not None else tuple(out.concrete_shape())
                    dst_shape = tuple(out.concrete_shape())
                    kind = deduce_pspec_transition(
                        src_spec, src_shape, dst_spec, dst_shape,
                        mesh_axes)
                except (ValueError, TypeError):
                    continue
                if kind == "identity":
                    continue
                nbytes = int(np.prod(dst_shape, dtype=np.int64)
                             * np.dtype(out.dtype.to_jnp()).itemsize)
                # the axes the transition MOVES (placement changed) —
                # spectator axes keep their dim and never communicate
                changed = {a for _d, a in
                           _spec_pairs(src_spec) ^ _spec_pairs(dst_spec)}
                edges.append({
                    "kind": kind,
                    "tensor": out.name,
                    "producer": src_t.name if src_t is not None
                    else node.inputs[0].name if node.inputs else "",
                    "consumer": node.attrs.get("_edge_tag") or node.name,
                    "src_spec": str(src_spec),
                    "dst_spec": str(dst_spec),
                    "axes": tuple(sorted(changed)),
                    "payload_bytes": nbytes,
                })
        return edges

    def _arg_memory_facts(self, abstract_pool, mesh_axes, update_node):
        """(divisors, kinds): pytrees mirroring the plan's abstract arg
        tuple ``(var_state, opt_state, grad_accum, feeds)``, carrying per
        leaf how many ways it is sharded (product of mesh axis sizes in
        its pspec) and what buffer class it is — the registered facts the
        static memory pass (analysis/memory) prices resident HBM from."""
        var_state, opt_state, grad_accum, feeds = abstract_pool

        from ..parallel.dstates import pspec_shard_divisor

        def _div(pspec) -> int:
            return pspec_shard_divisor(pspec, mesh_axes)

        def _tensor_div(tid) -> int:
            t = self._var_tensors.get(tid) or self._placeholders.get(tid)
            return _div(self._pspec_for(t)) if t is not None else 1

        opt = update_node.attrs["optimizer"] if update_node is not None \
            else None
        dp = int(mesh_axes.get(opt.dp_axis, 1)) if opt is not None else 1
        opt_shardings = getattr(opt, "_shardings", {}) if opt is not None \
            else {}

        def _slot_div(tid) -> int:
            # per-param slots ride the sharding the optimizer actually
            # device_put them with (the param's own pspec, plus ZeRO's
            # dp dim-0 shard when enabled) — recorded in _shardings
            sh = opt_shardings.get(tid)
            if sh is not None and getattr(sh, "spec", None) is not None:
                return _div(sh.spec)
            return _tensor_div(tid) if isinstance(tid, int) else 1

        def _opt_entry(name, sub):
            if isinstance(name, str) and name.startswith("flat_"):
                # flat buffers are sharded P(dp) in equal rank chunks
                return _mirror(sub, lambda _l, _k: dp), \
                    _mirror(sub, lambda _l, _k: "opt-state")
            div = _mirror(sub, lambda _l, k: _slot_div(k))
            return div, _mirror(sub, lambda _l, _k: "opt-state")

        def _mirror(obj, fn, key=None):
            if isinstance(obj, dict):
                return {k: _mirror(v, fn, k) for k, v in obj.items()}
            if isinstance(obj, tuple) and hasattr(obj, "_fields"):
                # NamedTuple states (optax-style, e.g. FactoredState)
                # construct positionally, not from one iterable
                return type(obj)(*(_mirror(v, fn, key) for v in obj))
            if isinstance(obj, (list, tuple)):
                return type(obj)(_mirror(v, fn, key) for v in obj)
            return fn(obj, key)

        var_div = {k: _tensor_div(k) for k in var_state}
        var_kind = {k: "param" for k in var_state}
        opt_div, opt_kind = {}, {}
        for name, sub in (opt_state or {}).items():
            opt_div[name], opt_kind[name] = _opt_entry(name, sub)
        accum_div = _mirror(grad_accum or {},
                            lambda _l, k: _tensor_div(k)
                            if isinstance(k, int) else 1)
        accum_kind = _mirror(grad_accum or {}, lambda _l, _k: "grad")
        feed_div = _mirror(feeds or {},
                           lambda _l, k: _tensor_div(k)
                           if isinstance(k, int) else 1)
        feed_kind = _mirror(feeds or {}, lambda _l, _k: "feed")
        return (var_div, opt_div, accum_div, feed_div), \
            (var_kind, opt_kind, accum_kind, feed_kind)

    def _register_plan_for_analysis(self, key, jit_step, gc_state,
                                    update_node, real_fetches,
                                    num_micro_batches,
                                    flat_mode: bool = False) -> None:
        """Expose this plan to the static analyzer (hetu_tpu/analysis):
        register an ExecutableHandle with the abstract arg specs plus the
        graph-level facts a jaxpr cannot carry — param shardings, mesh
        axes, and (when the explicit path is active) the grad-comm plan
        the dstates predictor can be run against."""
        name = self._plan_names.get(key)
        if name is not None and name in _EXECUTABLE_REGISTRY:
            return
        if name is None:
            # registry membership is re-checked (not just _plan_names):
            # after clear_executables() a cached plan must re-register
            # under its original name on its next run, or it would
            # silently vanish from analysis while still executing
            name = f"{self.name}/plan{len(self._plan_names)}"
            self._plan_names[key] = name
        mesh_axes = {str(a): int(s) for a, s in self.mesh.shape.items()} \
            if self.mesh is not None else {}
        params = []
        for t in self._var_tensors.values():
            params.append({"name": t.name,
                           "shape": tuple(t.concrete_shape()),
                           "dtype": np.dtype(t.dtype.to_jnp()).name,
                           "pspec": self._pspec_for(t),
                           "trainable": bool(t.trainable)})
        meta: Dict[str, Any] = {
            "kind": "train_step" if update_node is not None else "forward",
            "fetches": [getattr(f, "name", str(f)) for f in real_fetches],
            "num_micro_batches": num_micro_batches,
            "mesh_axes": mesh_axes,
            "params": params,
            "grad_comm_active": gc_state[0],
            # explicit path predicts EVERY collective -> strict reshard
            # gate; otherwise GSPMD owns the grad sync and no implicit-
            # reshard claim is made (allowed_gspmd None disables it)
            "allowed_gspmd": {} if gc_state[0] else None,
            # per-edge attribution (analysis/edges): the graph's
            # producer -> consumer pspec transitions, plus the facts the
            # edge synthesizers need (scalar fetch reductions, MoE
            # dispatch bounds)
            "pspec_edges": self._collect_pspec_edges(),
            "scalar_fetches": sum(
                1 for f in real_fetches
                if isinstance(f, Tensor) and len(f.shape) == 0),
            "moe": [dict(m) for m in getattr(self, "_moe_meta", ())],
            # step-time cost fact (analysis/cost overlap model): the
            # explicit coalesced grad sync is bucketed exactly so the
            # latency-hiding scheduler can run it behind backward
            # compute — its grad_comm/param_comm edges may hide under
            # the roofline.  Implicit GSPMD sync makes no such claim.
            "comm_overlap": bool(gc_state[0]),
        }
        # static memory model facts (analysis/memory): per-argument
        # sharding divisors + buffer kinds, mirroring the abstract arg
        # tree (var_state, opt_state, grad_accum, feeds).  Advisory:
        # an unmirrorable state container must degrade the memory pass
        # to its (shape, dtype) fallback, never break plan registration
        try:
            divisors, kinds = self._arg_memory_facts(
                self._abstract_pool[key], mesh_axes, update_node)
            meta["arg_divisors"] = divisors
            meta["arg_kinds"] = kinds
        except Exception:
            pass
        if update_node is not None:
            opt = update_node.attrs["optimizer"]
            meta["dp_axis"] = opt.dp_axis
            sentry_meta = self._sentry_for(update_node, key[4])
            if sentry_meta is not None:
                # registration meta: the thresholds the fused verdict
                # enforces + the fact the step carries the packed
                # verdict in its outputs (analysis/bench introspection)
                meta["sentry"] = sentry_meta.meta()
            # recorded for every train step (implicit-sync plans too):
            # the replicated-state-under-shard rule needs to know whether
            # the optimizer shards its state down by dp
            meta["zero"] = int(opt.zero)
            meta["flat_state"] = bool(flat_mode)
            if gc_state[0] and flat_mode:
                # reduce-scatter-only sync: the updated params leave the
                # manual region fully gathered, so the per-param
                # all-gather allowance is ZERO — any GSPMD regather is a
                # regression the implicit-reshard rule must flag.
                # Optimizer-declared in-region collectives (Adafactor's
                # factored-stat psums) are EXPLICIT lowered emissions,
                # accounted through grad_comm's opt_extra below, so the
                # GSPMD-insert claim stays exactly zero
                meta["allowed_gspmd"] = {}
            elif gc_state[0] and opt.zero in (1, 2):
                # ZeRO-1/2 keeps optimizer state dp-sharded but params
                # replicated at rest: GSPMD re-materializes each updated
                # param from its sharded update — one predictable
                # all_gather per dp-sharded state param (the flat_state
                # reduce-scatter-only sync removes these)
                meta["allowed_gspmd"] = {"all_gather": len(opt._shardings)}
            elif gc_state[0] and opt.zero >= 3:
                # FSDP: params sharded at rest, forward gathers them —
                # count depends on layer structure; no strict claim
                meta["allowed_gspmd"] = None
            if gc_state[0]:
                # entries in SYNC order (optim.flat_state.sync_order —
                # the one ordering every flat-geometry consumer shares),
                # so bucket planning in the predictor sees exactly the
                # runtime geometry
                from ..optim.flat_state import sync_order
                xs = sync_order(update_node.attrs["xs"])
                entries = [(t.name, tuple(t.concrete_shape()),
                            np.dtype(t.dtype.to_jnp()).name) for t in xs]
                meta["grad_comm"] = {
                    "entries": entries,
                    "dp_axis": opt.dp_axis,
                    "transport": opt.grad_comm,
                    "bucket_mb": opt.bucket_mb,
                    "device_num": mesh_axes.get(opt.dp_axis, 1),
                    "zero": opt.zero,
                    "flat": bool(flat_mode),
                    # the flat sentry's global grad-norm psum shares the
                    # clip's collective shape (same reduction whether
                    # clipping fires or not), so the predictor counts it
                    # under "clip"
                    "clip": opt.max_grad_norm is not None
                    or bool(flat_mode
                            and self._sentry_for(update_node, key[4])
                            is not None),
                    # each scalar fetch is pmean'd inside the manual
                    # region (one explicit all_reduce apiece)
                    "scalar_fetches": meta["scalar_fetches"],
                    # optimizer-declared in-region collectives beyond
                    # the grad/param chains (Adafactor's factored-stat
                    # psums) — folded into the predictor's "extra"
                    "opt_extra": dict(opt._flat_comm_extra())
                    if flat_mode else {},
                }
        register_executable(name, jit_step, self._abstract_pool[key], meta)

    def analysis_handles(self) -> List[ExecutableHandle]:
        """Handles of every plan this graph has registered."""
        return [get_executable(n) for n in self._plan_names.values()
                if n in _EXECUTABLE_REGISTRY]

    # -- hot switch ----------------------------------------------------------

    def cost_analysis(self):
        """XLA cost analysis of the last executed step program (flops,
        bytes accessed, ...): metrics from INSIDE the compiled program,
        complementing the eager-replay OpProfiler (reference op-level
        TimeCost + CUDAProfiler counters, hetu/graph/profiler.h:30-66).

        Returns the XLA cost dict (keys like "flops",
        "bytes accessed") or None when no step has run yet."""
        jit_step = getattr(self, "_last_plan", None)
        key = getattr(self, "_last_plan_key", None)
        spec = self._abstract_pool.get(key)
        if jit_step is None or spec is None:
            return None
        if id(jit_step) in self._cost_cache:       # invariant per plan
            return self._cost_cache[id(jit_step)]
        compiled = jit_step.lower(*spec).compile()
        costs = compiled.cost_analysis()
        # jax returns either a dict or a 1-element list of dicts
        if isinstance(costs, (list, tuple)):
            costs = costs[0] if costs else {}
        out = dict(costs) if costs else None
        self._cost_cache[id(jit_step)] = out
        return out

    def switch_strategy(self, new_mesh, pspec_overrides=None, optimizer=None,
                        mode=None, dtype=None):
        """Hot-switch params/optimizer states/grads to a new mesh and/or
        new per-param shardings, activating a fresh strategy id (reference
        DefineAndRunGraph plan-change -> SwitchExecGraph::SwitchParams,
        define_and_run_graph.cc:1073-1129).  Returns a SwitchProfile."""
        from ..parallel.switch import SwitchExecGraph, SwitchMode
        # ZeRO-3 flat keeps working params stale between update steps;
        # the switch migrates _var_data, so materialize first (bitwise
        # vs the in-region gather — the continuation stays exact)
        self._refresh_stale_params()
        if mode is None:
            mode = SwitchMode.ORIGIN_PARAM if optimizer is None \
                else SwitchMode.ORIGIN_PARAM_AND_OPTIMIZER
        tr = get_tracer()
        sp = tr.begin("switch_strategy", track="train",
                      from_strategy=self.cur_strategy_id) if tr.enabled \
            else None
        try:
            sw = SwitchExecGraph(self, new_mesh, pspec_overrides, mode,
                                 dtype)
            prof = sw.switch(optimizer)
            self.cur_strategy_id += 1
            self.num_strategy = max(self.num_strategy,
                                    self.cur_strategy_id + 1)
            if sp is not None:
                tr.end(sp, to_strategy=self.cur_strategy_id,
                       **prof.as_dict())
            return prof
        finally:
            if sp is not None:
                tr.end(sp)      # idempotent: only fires if we raised

    # -- run ----------------------------------------------------------------

    def run(self, loss_or_fetches, fetches=None, feed_dict=None,
            num_micro_batches: int = 1, cur_strategy_id: Optional[int] = None,
            run_level: Union[str, RunLevel, None] = None,
            save_checkpoint: bool = False):
        """Execute the graph (reference DefineAndRunGraph::Run,
        define_and_run_graph.cc:912).

        Accepts either ``run(fetches, feed_dict=...)`` or the reference's
        ``run(loss, fetches, feed_dict, num_micro_batches, ...)`` signature.
        """
        if fetches is None:
            fetches = loss_or_fetches
        if not isinstance(fetches, (list, tuple)):
            fetches = [fetches]
        fetches = list(fetches)
        feed_dict = dict(feed_dict or {})
        if run_level is None:
            run_level = _run_level_ctx._current  # ambient ht.run_level(...)
        if isinstance(run_level, str):
            run_level = RunLevel(run_level)
        if cur_strategy_id is not None:
            self.cur_strategy_id = cur_strategy_id

        if run_level == RunLevel.TOPO:
            return self._topo_from([f for f in fetches if isinstance(f, Tensor)])

        # find update node among fetches (optimizer.minimize output);
        # remember its positions so returned values align with fetches
        update_node = None
        real_fetches = []
        update_positions = []
        for i, f in enumerate(fetches):
            if isinstance(f, Tensor) and f.producer is not None \
                    and f.producer.op_type == "update":
                update_node = f.producer
                update_positions.append(i)
            else:
                real_fetches.append(f)
        if run_level in (RunLevel.COMPUTE_ONLY, RunLevel.ALLOC):
            update_node = None

        # trace plane (hetu_tpu/obs): per-step phase spans on the
        # "train" track — plan lookup, feed marshalling, state
        # assembly, the executable call, state commit — nested under
        # one step span.  NULL tracer: all guards read False and
        # nothing below allocates.  The try/finally closes the step
        # span even when the body raises (ending the outermost span
        # pops-and-discards any open children), so a caught-and-retried
        # failing step never corrupts the per-thread nesting stack.
        tr = get_tracer()
        step_sp = tr.begin(
            "train_step" if update_node is not None else "forward",
            track="train", run_level=run_level.value,
            strategy=self.cur_strategy_id) if tr.enabled else None
        try:
            return self._run_step(tr, run_level, update_node, real_fetches,
                                  update_positions, feed_dict,
                                  num_micro_batches)
        finally:
            if step_sp is not None:
                tr.end(step_sp)

    def _run_step(self, tr, run_level, update_node, real_fetches,
                  update_positions, feed_dict, num_micro_batches):
        """:meth:`run` under its step span: find (or build) the plan,
        then run it."""
        # bucketed feeds, symbolic dims, variables, the plan key and its
        # pool lookup — and, on a plan's first run, the trace + compile
        plan_sp = tr.begin("plan", track="train") if tr.enabled else None
        if self._shape_buckets is not None:
            feed_dict = self._bucket_feeds(feed_dict)
        self._bind_symbolic_dims(feed_dict)

        # materialize variables (ALLOC)
        for t in self._var_tensors.values():
            self._materialize_var(t)
        if run_level == RunLevel.ALLOC:
            return []

        key = self._plan_key(real_fetches, feed_dict, num_micro_batches,
                             run_level, update_node)
        if key not in self._plan_pool:
            feed_tensors = list(feed_dict.keys())
            self._plan_pool[key] = self._build_executable(
                real_fetches, feed_tensors, num_micro_batches, run_level,
                update_node)
        jit_step, gc_state, flat_mode = self._plan_pool[key]
        # introspection tracks the plan actually EXECUTED this run, not
        # the last grad-comm-requesting build
        self._grad_comm_active, self._grad_comm_fallback = gc_state
        self._last_plan = jit_step  # for cost_analysis()
        self._last_plan_key = key
        if plan_sp is not None:
            tr.end(plan_sp)
        return self._run_plan(tr, key, jit_step, gc_state, flat_mode,
                              update_node, real_fetches, update_positions,
                              feed_dict, num_micro_batches)

    def _run_plan(self, tr, key, jit_step, gc_state, flat_mode,
                  update_node, real_fetches, update_positions, feed_dict,
                  num_micro_batches):
        """The per-run tail of :meth:`run`: feed marshalling, state
        assembly, registration, the executable call, and state commit."""
        feed_sp = tr.begin("feed", track="train") if tr.enabled else None
        feeds = {}
        for t, v in feed_dict.items():
            arr = jnp.asarray(v, dtype=t.dtype.to_jnp())
            sharding = self._sharding_for(t)
            if sharding is not None:
                arr = jax.device_put(arr, sharding)
            feeds[t.id] = arr
        if self._rng_tensor is not None:
            feeds[self._rng_tensor.id] = jnp.asarray(self._fresh_rng_key())
        run_level = key[4]
        sentry = self._sentry_for(update_node, run_level)
        if sentry is not None:
            # the one-shot chaos code (0 = clean): a VALUE, never a
            # shape — injections can never retrace the plan
            feeds[self._sentry_code_tensor().id] = jnp.asarray(
                self._sentry_next_code, jnp.int32)
            self._sentry_next_code = 0
        feeds_mb = self._split_micro_batches(feeds, num_micro_batches)
        if feed_sp is not None:
            tr.end(feed_sp, n_feeds=len(feed_dict),
                   micro_batches=num_micro_batches)
        # state dicts, flat optimizer state, plan registration: host
        # work between the feeds and the call
        asm_sp = tr.begin("assemble", track="train") if tr.enabled \
            else None

        # ZeRO-3 flat leaves per-param working copies stale between
        # update steps (the flat master is authoritative); any OTHER
        # plan about to read parameter values must refresh them first
        stale = getattr(self, "_stale_flat_params", None)
        if stale is not None and not (
                flat_mode and update_node is not None
                and update_node.attrs["optimizer"] is stale[0]):
            self._refresh_stale_params()

        var_state = dict(self._var_data)
        opt_state = {}
        scaler = None
        zero3_flat = False
        if update_node is not None:
            opt = update_node.attrs["optimizer"]
            if flat_mode:
                # flat dp-sharded buffers matching the reduce-scatter
                # geometry (optim/flat_state.py); grafts restored
                # per-param checkpoints on the way
                opt_state = dict(opt._ensure_flat_state(
                    var_state, update_node.attrs["xs"], self))
                zero3_flat = opt.zero >= 3
                if zero3_flat:
                    # params at rest = the 1/dp flat master chunks; the
                    # full working copies never enter the step (the
                    # region re-gathers them per bucket, param_gather)
                    for t in update_node.attrs["xs"]:
                        var_state.pop(t.id, None)
            else:
                opt_state = dict(opt._ensure_state(
                    var_state, update_node.attrs["xs"], self))
            scaler = update_node.attrs.get("grad_scaler")
            if scaler is not None and not scaler.enabled:
                scaler = None
            if scaler is not None:
                opt_state["_scaler"] = scaler.init_state()
            if sentry is not None:
                opt_state["_sentry"] = sentry.init_state()
        grad_accum = dict(self._grad_accum)

        if key not in self._abstract_pool:
            # arg specs for cost_analysis() and the registered handle;
            # shapes are invariant per plan key, so this traversal runs
            # once per compiled plan.  Mesh-placed arrays keep their
            # sharding: the handle then compiles the program that RAN
            # (obs.device_phases joins its instruction names against
            # the device trace), not a replicated-input variant of it
            self._abstract_pool[key] = jax.tree_util.tree_map(
                _abstract_of, (var_state, opt_state, grad_accum, feeds_mb))
        self._register_plan_for_analysis(key, jit_step, gc_state,
                                         update_node, real_fetches,
                                         num_micro_batches,
                                         flat_mode=flat_mode)
        exec_sp = None
        if tr.enabled:
            tr.end(asm_sp)
            # the span reconciliation joins on: exec= is the registered
            # plan name (obs.reconcile looks the static predictions up
            # by it at report time); grad-comm/optimizer work happens
            # INSIDE the executable, attributed here via the plan's comm
            # meta (the per-bucket comm_tag plane names each collective
            # in the lowered program itself)
            plan_name = self._plan_names.get(key, self.name)
            attrs: Dict[str, Any] = {"exec": plan_name,
                                     "micro_batches": num_micro_batches}
            if update_node is not None:
                opt_tr = update_node.attrs["optimizer"]
                # explicit coalesced path: name the transport the
                # comm_tag'd buckets ride; otherwise GSPMD owns the sync
                attrs["grad_comm"] = getattr(opt_tr, "grad_comm", None) \
                    if gc_state[0] else "gspmd"
                attrs["zero"] = int(getattr(opt_tr, "zero", 0))
                attrs["flat_state"] = bool(flat_mode)
            exec_sp = tr.begin("executable", track="train", **attrs)
        fetch_vals, new_vars, new_opt, new_accum = jit_step(
            var_state, opt_state, grad_accum, feeds_mb)
        if exec_sp is not None:
            # the span times the CALL (dispatch up to the return of the
            # async futures), not the device: a traced run issues the
            # same host schedule as an untraced one, and the profiler's
            # device trace (obs.device_phases) gives the executable's
            # time
            tr.end(exec_sp)

        commit_sp = tr.begin("commit", track="train") if tr.enabled \
            else None
        if zero3_flat:
            # the step returns no trainables (they live only in the flat
            # master now): keep the existing dp-sharded working copies —
            # STALE until _refresh_stale_params materializes from master
            merged = dict(self._var_data)
            merged.update(new_vars)
            self._var_data = merged
            self._stale_flat_params = (update_node.attrs["optimizer"],
                                       list(update_node.attrs["xs"]))
        else:
            self._var_data = dict(new_vars)
        if update_node is not None:
            new_opt = dict(new_opt)
            if scaler is not None and "_scaler" in new_opt:
                scaler.store_state(new_opt.pop("_scaler"))
            if sentry is not None and "_sentry" in new_opt:
                # the verdict rode the step outputs; stash it for the
                # trainer's policy ladder (no extra device fetch)
                sentry.store_state(new_opt.pop("_sentry"))
            update_node.attrs["optimizer"]._store_state(new_opt)
        self._grad_accum = dict(new_accum)
        # per-step memory snapshot when HETU_MEMORY_PROFILE is set
        # (reference executable_graph.cc:1738 memory profile levels; the
        # SPMD micro-batch loop is one compiled program, so the runtime
        # granularity here is the step — the MPMD runtime snapshots per
        # micro-batch)
        if self._memory_profiler is None:
            from ..utils.profiler import MemoryProfiler
            self._memory_profiler = MemoryProfiler()
        if self._memory_profiler.enabled:
            self._memory_profiler.snapshot("step")
        if commit_sp is not None:
            tr.end(commit_sp)
        # restore fetch arity: update-op positions yield None
        out = list(fetch_vals)
        for i in update_positions:
            out.insert(i, None)
        return out


# ---------------------------------------------------------------------------
# graph context management (python/hetu/__init__.py:124 ht.graph())
# ---------------------------------------------------------------------------

_graph_stack: List[Graph] = []
_default_graphs: Dict[str, Graph] = {}


def get_default_graph() -> Graph:
    if _graph_stack:
        return _graph_stack[-1]
    if "eager" not in _default_graphs:
        _default_graphs["eager"] = EagerGraph("default_eager")
    return _default_graphs["eager"]


class graph:
    """``with ht.graph("define_and_run", num_strategy=N):`` context."""

    def __init__(self, kind: Union[str, Graph] = "define_and_run",
                 create_new: bool = False, prefix: str = "default",
                 num_strategy: int = -1, mesh: Optional[Mesh] = None):
        if isinstance(kind, Graph):
            self.g = kind
        else:
            cache_key = f"{prefix}_{kind}"
            if create_new or cache_key not in _default_graphs:
                if kind == "define_and_run":
                    g = DefineAndRunGraph(cache_key)
                elif kind == "define_by_run":
                    g = DefineByRunGraph(cache_key)
                else:
                    g = EagerGraph(cache_key)
                if create_new:
                    self.g = g
                else:
                    _default_graphs[cache_key] = g
                    self.g = g
            else:
                self.g = _default_graphs[cache_key]
        if num_strategy >= 1:
            self.g.set_num_strategy(num_strategy)
        if mesh is not None:
            self.g.mesh = mesh

    def __enter__(self) -> Graph:
        _graph_stack.append(self.g)
        return self.g

    def __exit__(self, *exc):
        _graph_stack.pop()


class run_level:
    """Context setting the ambient run level (ht.run_level(...)); consulted
    by ``DefineAndRunGraph.run`` when no explicit run_level is passed."""
    _current = RunLevel.UPDATE

    def __init__(self, level: Union[str, RunLevel]):
        self.level = RunLevel(level) if isinstance(level, str) else level

    def __enter__(self):
        self.prev = run_level._current
        run_level._current = self.level
        return self

    def __exit__(self, *exc):
        run_level._current = self.prev


_run_level_ctx = run_level
