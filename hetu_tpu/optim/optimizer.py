"""Optimizers.

Reference: ``hetu/graph/optim/optimizer.h:9-100`` (SGD w/ momentum, Adam,
``Minimize = ComputeGradients + ApplyDense``, ``MakeStates`` per-param
optimizer-state variables, multi-zero awareness) and the Python wrappers
(``python/hetu/optim/optimizer.py:43``).

``minimize(loss)`` builds a symbolic update node executed by
``DefineAndRunGraph.run``; under jit the whole fwd+bwd+update is one XLA
program with donated parameter/state buffers (the analogue of the
reference's fused param/grad buffers + fused Optimizers.cu kernels).
ZeRO levels (reference ``zero`` DS flag, ``distributed_states.h:69``,
grad reduce-scatter / param allgather comm ops ``Communication.h:583``),
expressed as GSPMD sharding annotations instead of explicit collectives —
the XLA partitioner then emits the reduce-scatter/all-gather pairs:

- ``zero=1`` — optimizer states sharded over the dp axis.
- ``zero=2`` — + gradients constrained to the same dp-sharded spec inside
  the update (XLA turns the dp grad all-reduce into reduce-scatter and
  gathers the updated params back).
- ``zero=3`` — + parameters stored dp-sharded at rest (FSDP); forward /
  backward all-gathers are inserted by the partitioner on demand.

``zero=True`` keeps its historical meaning of level 1.

``flat_state=True`` (with ``grad_comm=`` and ``zero`` 1/2) swaps the
per-parameter state arrays for flat dp-sharded buffers matching the
coalesced reduce-scatter geometry (optim/flat_state.py), turning the
explicit gradient sync into the reference's reduce-scatter-only ZeRO-2
pairing: RS -> local-chunk update -> weight-dtype param all-gather —
half the gradient wire bytes of the all-reduce path (DESIGN.md §10).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..graph.graph import DefineAndRunGraph, Graph, OpNode, get_default_graph
from ..graph.tensor import Tensor
from ..obs.phases import phase


class Optimizer:
    def __init__(self, params: Optional[Sequence[Tensor]] = None,
                 lr=0.01, zero: int = 0, dp_axis: str = "dp",
                 max_grad_norm: Optional[float] = None,
                 grad_comm: Optional[str] = None,
                 bucket_mb: float = 4.0,
                 flat_state: bool = False,
                 sentry=None):
        # lr: float, or a schedule callable step -> lr (optim.schedules)
        self.lr = lr
        self.params = list(params) if params is not None else None
        self.zero = int(zero)     # ZeRO level 0-3 (True -> 1)
        if not 0 <= self.zero <= 3:
            raise ValueError(f"zero level must be 0..3, got {zero}")
        self.dp_axis = dp_axis
        # global-norm gradient clipping (Megatron-style; applied inside
        # the jitted update, before any optimizer math)
        self.max_grad_norm = max_grad_norm
        # explicit gradient-communication transport (reference
        # AllReduceCoalesce + EQuARX quantized collectives): None keeps
        # the implicit GSPMD per-tensor sync; "fp32"/"bf16"/"int8"
        # switches the dp gradient sync to coalesced buckets over the
        # selected wire format (parallel/comm.py, graph explicit path).
        # Sync uses the data-parallel MEAN convention (torch-DDP
        # semantics) and therefore assumes a mean-normalized loss; a
        # literally sum-reduced loss makes the graph fall back to the
        # implicit path (graph._grad_comm_fallback records why).
        from ..parallel.comm import GRAD_COMM_TRANSPORTS
        if grad_comm is not None and grad_comm not in GRAD_COMM_TRANSPORTS:
            raise ValueError(f"grad_comm must be None or one of "
                             f"{GRAD_COMM_TRANSPORTS}, got {grad_comm!r}")
        self.grad_comm = grad_comm
        self.bucket_mb = float(bucket_mb)
        if self.bucket_mb <= 0:
            raise ValueError(f"bucket_mb must be > 0, got {bucket_mb}")
        # flat dp-sharded optimizer state (reduce-scatter-only ZeRO-2
        # gradient sync, reference SplitReduceScatter under zero): master
        # fp32 params + momentum/variance packed into per-bucket flat
        # buffers sharded P(dp) in equal per-rank chunks.  Requires the
        # explicit grad-comm path (the chunks ARE reduce_scatter_coalesced
        # shards) and ZeRO 1/2 semantics (params replicated at rest,
        # state sharded).
        self.flat_state = bool(flat_state)
        if self.flat_state:
            if grad_comm is None:
                raise ValueError(
                    "flat_state=True needs the explicit grad-comm path: "
                    "pass grad_comm='fp32'|'bf16'|'int8'")
            if self.zero not in (1, 2, 3):
                raise ValueError(
                    f"flat_state=True needs dp-sharded state (ZeRO "
                    f"1/2) or fully sharded params (ZeRO 3); got "
                    f"zero={self.zero}")
        # numeric sentry (resilience/sentry.py): on-device finite/spike
        # verdict fused into every UPDATE-level step, anomalous updates
        # skipped with bitwise-zero residue.  True / SentryConfig /
        # NumericSentry all accepted; None disables.
        if sentry:
            from ..resilience.sentry import NumericSentry, SentryConfig
            if sentry is True:
                sentry = NumericSentry()
            elif isinstance(sentry, SentryConfig):
                sentry = NumericSentry(sentry)
            elif not isinstance(sentry, NumericSentry):
                raise ValueError(
                    f"sentry must be True, a SentryConfig or a "
                    f"NumericSentry, got {sentry!r}")
        self.sentry = sentry or None
        self._flat_layout = None        # FlatStateLayout when flat+active
        self._packed_var_writes = -1    # graph._var_writes at last pack
        self._state: Dict[str, Any] = {}
        self._shardings: Dict[int, Any] = {}  # tid -> NamedSharding of states
        self._param_shardings: Dict[int, Any] = {}  # tid -> zero-3 sharding
        self._param_base_shardings: Dict[int, Any] = {}  # tid -> own spec

    # -- graph API (reference Optimizer::Minimize) ---------------------------

    def minimize(self, loss: Tensor,
                 var_list: Optional[Sequence[Tensor]] = None,
                 grad_scaler=None) -> Tensor:
        g = loss.graph or get_default_graph()
        xs = list(var_list or self.params or g.trainable_variables)
        assert xs, "no trainable variables to optimize"
        grad_node_outputs = g.make_gradients(loss, xs)
        grad_node = grad_node_outputs[0].producer
        node = OpNode("update", None, grad_node_outputs,
                      {"optimizer": self, "grad_node": grad_node, "xs": xs,
                       "grad_scaler": grad_scaler},
                      f"update_{loss.name}")
        t = Tensor((), "float32", producer=node, name=node.name, graph=g)
        node.outputs = [t]
        g.ops.append(node)
        return t

    # -- state management (reference MakeStates) -----------------------------

    def _state_sharding(self, t: Tensor, arr, graph: Graph):
        """Sharding for a per-param optimizer state: the param's own
        sharding, plus ZeRO dp-sharding of dim 0 when enabled (reference
        `zero` ds flag, distributed_states.h:69)."""
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = graph.mesh
        if mesh is None:
            return None
        base = graph._pspec_for(t)
        spec = list(base) if base is not None else []
        spec += [None] * (arr.ndim - len(spec))
        if self.zero and self.dp_axis in mesh.axis_names and arr.ndim > 0:
            dp = mesh.shape[self.dp_axis]
            used = {a for entry in spec if entry
                    for a in (entry if isinstance(entry, tuple) else (entry,))}
            if (self.dp_axis not in used and arr.shape[0] % dp == 0
                    and spec[0] is None):
                spec[0] = self.dp_axis
        if not any(spec):
            return None
        return NamedSharding(mesh, PartitionSpec(*spec))

    def _ensure_state(self, var_state: Dict[int, jax.Array],
                      xs: Sequence[Tensor], graph: Graph) -> Dict[str, Any]:
        # a flat checkpoint's fp32 master copy is meaningful only to
        # _ensure_flat_state; per-param math has no such slot, and
        # letting it ride along (SGD's dict(opt_state) carry) would
        # re-save a STALE master that a later flat restore prefers over
        # the trained params — silently reverting the weights
        self._state.pop("master", None)
        just_inited = False
        if not self._state:
            self._state = self._init_state(var_state, xs)
            just_inited = True
            for key, tree in self._state.items():
                if isinstance(tree, dict):
                    for tid, arr in tree.items():
                        t = next((x for x in xs if x.id == tid), None)
                        if t is None or not hasattr(arr, "shape") \
                                or arr.shape != var_state[tid].shape:
                            continue
                        sharding = self._state_sharding(t, arr, graph)
                        if sharding is not None:
                            tree[tid] = jax.device_put(arr, sharding)
                            self._shardings[tid] = sharding
        if getattr(self, "_pending_tree_state", None):
            # structured state loaded from a checkpoint as ordered leaves
            # (safetensors_io "@@leaf" entries): graft into the freshly
            # initialized structure, validating leaf count + shapes.
            # just-initialized state IS a fresh template; only rebuild
            # one when stepping had already populated self._state
            fresh = self._state if just_inited \
                else self._init_state(var_state, xs)
            for slot, leaves in self._pending_tree_state.items():
                if slot not in fresh or isinstance(fresh[slot], dict):
                    raise ValueError(
                        f"checkpoint carries structured optimizer state "
                        f"{slot!r} that this optimizer does not define — "
                        f"restoring into a different optimizer type?")
                tdef = jax.tree_util.tree_structure(fresh[slot])
                ref = jax.tree_util.tree_leaves(fresh[slot])
                if len(ref) != len(leaves) or any(
                        getattr(a, "shape", None) != getattr(b, "shape", None)
                        for a, b in zip(ref, leaves)):
                    raise ValueError(
                        f"checkpointed optimizer state {slot!r} does not "
                        f"match this optimizer/model (leaf count/shapes)")
                self._state[slot] = jax.tree_util.tree_unflatten(
                    tdef, [jnp.asarray(l, r.dtype)
                           for l, r in zip(leaves, ref)])
            self._pending_tree_state = None
        if self.zero in (1, 2) and graph.mesh is not None \
                and not self._param_base_shardings:
            # pin updated params to their OWN spec (replicated over dp):
            # with dp-sharded states XLA would otherwise freely emit
            # dp-sharded params, silently turning zero-1/2 into FSDP
            from jax.sharding import NamedSharding, PartitionSpec
            for t in xs:
                arr = var_state.get(t.id)
                if arr is None or not hasattr(arr, "ndim"):
                    continue
                base = graph._pspec_for(t)
                spec = list(base) if base is not None else []
                spec += [None] * (arr.ndim - len(spec))
                self._param_base_shardings[t.id] = NamedSharding(
                    graph.mesh, PartitionSpec(*spec))
        if self.zero >= 3:
            # FSDP: parameters live dp-sharded at rest.  Re-assert every
            # step (device_put on an already-sharded array is a no-op) so
            # checkpoint loads / hot switches can't silently unshard.
            for t in xs:
                arr = var_state.get(t.id)
                if arr is None or not hasattr(arr, "shape"):
                    continue
                sh = self._param_shardings.get(t.id)
                if sh is None:
                    sh = self._state_sharding(t, arr, graph)
                    if sh is None:
                        continue
                    self._param_shardings[t.id] = sh
                var_state[t.id] = jax.device_put(arr, sh)
                graph._var_data[t.id] = var_state[t.id]
        return self._state

    def _c(self, tid: int, arr):
        """Re-assert the optimizer-state sharding inside the jitted update
        (XLA would otherwise choose output shardings freely)."""
        sh = self._shardings.get(tid)
        return jax.lax.with_sharding_constraint(arr, sh) if sh is not None else arr

    def _c_grad(self, tid: int, g):
        """ZeRO>=2: constrain the gradient to the dp-sharded state spec —
        the partitioner then reduce-scatters the dp gradient sum instead
        of all-reducing it (reference SplitReduceScatter under zero,
        Communication.h:583).  Under the explicit grad-comm path the
        gradient arrives already reduced (coalesced collectives), so this
        constraint degrades to a local slice — the correct ZeRO-2 layout
        either way."""
        return self._c(tid, g) if self.zero >= 2 else g

    def sync_gradients(self, grads: Dict[int, jax.Array], axis: str):
        """Explicit DP gradient sync: coalesced (optionally quantized)
        mean-allreduce of the micro-batch-accumulated gradient dict —
        one collective chain per bucket instead of one psum per
        parameter.  Must run inside a manual (shard_map) region with
        ``axis`` in scope; the graph executor arranges that
        (DefineAndRunGraph._build_executable explicit path)."""
        from ..parallel import comm
        return comm.all_reduce_coalesced(
            grads, axis, op="mean", bucket_mb=self.bucket_mb,
            transport=self.grad_comm or "fp32")

    # -- flat dp-sharded state (ZeRO-2 reduce-scatter-only sync) -------------
    #
    # State geometry mirrors comm.reduce_scatter_coalesced exactly
    # (optim/flat_state.py): each rank's P(dp) shard of every flat buffer
    # IS its reduce-scattered gradient chunk, so the update is pure local
    # elementwise math and the only collectives per step are one
    # reduce-scatter chain plus one param-dtype all-gather per bucket.

    def _flat_slots(self):
        """Per-param state slots packed into flat buffers (beyond the
        fp32 master copy); subclasses that support flat_state override."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support flat_state=True")

    def _flat_update(self, p, slots, g, step, lr, **ctx):
        """Elementwise update on local fp32 chunks: (master, {slot:
        chunk}, grad, step, lr) -> (new master, {slot: new chunk}).
        ``ctx`` carries ``bucket`` (index), ``axis`` (the manual dp axis)
        and ``fstate`` (the full local flat state) for optimizers whose
        update needs cross-chunk reductions (Adafactor's factored
        stats); plain elementwise optimizers ignore it."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support flat_state=True")

    def _flat_extra_update(self, fstate) -> Dict[str, Any]:
        """New values for non-chunk state entries (anything outside the
        ``flat_*`` slots, e.g. Adafactor's replicated factored stats),
        collected after the per-bucket update loop.  Base: none."""
        return {}

    def _flat_repack_extra(self, key: str, val, old_lay, new_lay):
        """Hot-switch repack of one non-chunk state entry across a flat
        geometry change (dp resize).  Base: pass through unchanged —
        right for geometry-independent extras like the step counter AND
        for per-bucket extras like Adafactor's factored stats (bucket
        planning depends only on the entry set and bucket_mb, not on dp,
        so a dp resize leaves the bucket partition — and with it every
        row/col slot — untouched)."""
        return val

    def _flat_extra_init(self, lay, st: Dict[str, Any]) -> Dict[str, Any]:
        """Initial values for non-chunk state entries when the flat
        state is (re)built under layout ``lay`` (``st`` is the per-param
        starting point — a checkpoint or the unpacked previous state).
        Base: none."""
        return {}

    def _flat_comm_extra(self) -> Dict[str, int]:
        """Collectives the flat update emits in-region BEYOND the
        predicted grad/param chains, as ``{kind: count}`` per step
        (Adafactor's factored-stat psums).  Registered as the plan's
        ``grad_comm.opt_extra`` and folded into the emission
        predictor's/edge pass's ``extra``.  Base: none — the
        registration stays strict."""
        return {}

    def predicted_step_collectives(self, entries, device_num: int,
                                   scalar_fetches: int = 1):
        """The exact collective sequence ONE update step of this
        optimizer (as configured: transport, bucket size, clipping,
        ZeRO level, flat extras) emits over ``device_num`` dp shards —
        ``(predictions, extra)`` per
        ``dstates.predict_update_step_collectives``.

        Single source of truth for every consumer of the optimizer's
        comm contract: the graph's ``grad_comm`` registration, the edge
        pass that prices it, and the cross-rank schedule verifier
        (``analysis/schedule``) that checks it for rank consistency —
        so a config change here cannot drift from what the analysis
        plane verifies."""
        from ..parallel.dstates import predict_update_step_collectives
        return predict_update_step_collectives(
            list(entries), int(device_num),
            transport=self.grad_comm or "fp32",
            bucket_mb=self.bucket_mb,
            scalar_fetches=int(scalar_fetches),
            flat=self.flat_state,
            clip=self.max_grad_norm is not None,
            zero=self.zero,
            opt_extra=self._flat_comm_extra() if self.flat_state
            else None)

    def _flat_entries(self, xs: Sequence[Tensor], var_state):
        """(key, shape, dtype) of the gradient set in SYNC order
        (flat_state.sync_order — the one ordering every flat-geometry
        consumer shares)."""
        from .flat_state import sync_order
        return [(t.id, np.shape(var_state[t.id]),
                 np.dtype(jnp.result_type(var_state[t.id])).name)
                for t in sync_order(xs)]

    def _ensure_flat_state(self, var_state: Dict[int, jax.Array],
                           xs: Sequence[Tensor], graph: Graph
                           ) -> Dict[str, Any]:
        """Build (or graft a restored checkpoint into) the flat state.

        Accepts three starting points: empty (fresh init), per-parameter
        dicts (a checkpoint written by either the flat or the per-param
        path — checkpoints are always per-parameter keyed), or an
        existing flat state whose geometry changed (dp resize / hot
        switch), which is unpacked through the old index and repacked.
        """
        from jax.sharding import NamedSharding, PartitionSpec
        from .flat_state import FlatStateLayout, sync_order
        mesh = graph.mesh
        assert mesh is not None and self.dp_axis in mesh.axis_names, \
            "flat_state needs a mesh with the dp axis (explicit path)"
        slots = self._flat_slots()
        entries = self._flat_entries(xs, var_state)
        dp = mesh.shape[self.dp_axis]
        st = dict(self._state)
        # restored-but-ungrafted non-param state (a checkpoint's
        # ``@@leaf`` entries — Adafactor's per-bucket factored EMAs)
        # joins the starting point so _flat_extra_init can reuse it
        for k, v in (getattr(self, "_pending_tree_state", None)
                     or {}).items():
            st.setdefault(k, v)
        is_flat = any(k.startswith("flat_") for k in st)
        writes = getattr(graph, "_var_writes", 0)

        def _written_since_pack():
            # ONLY the params actually written since the last pack
            # (graph._var_write_log): refreshing every master from the
            # (possibly bf16) live values would throw away the fp32
            # precision of untouched params
            log = getattr(graph, "_var_write_log", {})
            return [t for t in sync_order(xs)
                    if log.get(t.id, -1) > self._packed_var_writes]

        if is_flat and self._flat_layout is not None \
                and self._flat_layout.matches(entries, dp,
                                              self.bucket_mb):
            # steady state: no bucket replanning.  But params written
            # OUTSIDE the update loop (reset_variable / load_model)
            # supersede their packed fp32 master slices, or the next
            # all-gather would silently revert the external write
            if writes != self._packed_var_writes:
                stale = _written_since_pack()
                if stale:
                    lay = self._flat_layout
                    masters = list(self._state["flat_master"])
                    touched = set()
                    for t in stale:
                        bi, off, numel, _shape = lay.index[t.id]
                        flat = jnp.asarray(masters[bi])
                        masters[bi] = flat.at[off:off + numel].set(
                            jnp.ravel(var_state[t.id])
                            .astype(jnp.float32))
                        touched.add(bi)
                    sh_m = NamedSharding(mesh,
                                         PartitionSpec(self.dp_axis))
                    self._state["flat_master"] = [
                        jax.device_put(m, sh_m) if i in touched else m
                        for i, m in enumerate(masters)]
                self._packed_var_writes = writes
            return self._state
        new_lay = FlatStateLayout(entries, dp, bucket_mb=self.bucket_mb)
        if is_flat:
            # geometry changed (dp size / param set): go through the
            # per-param view and repack under the new index; params
            # written since the last pack supersede their old master
            old = self._flat_layout
            per: Dict[str, Any] = {"step": st.get("step")}
            per["master"] = old.unpack(st["flat_master"])
            for t in _written_since_pack():
                per["master"][t.id] = var_state[t.id]
            for s in slots:
                per[s] = old.unpack(st[f"flat_{s}"])
            st = per
        xs_sorted = sync_order(xs)
        params = {t.id: var_state[t.id] for t in xs_sorted}

        def _per_param(tree, default):
            if not isinstance(tree, dict) or not tree:
                return {t.id: default(t) for t in xs_sorted}
            vals = {}
            for t in xs_sorted:
                arr = tree.get(t.id)
                if arr is not None and np.shape(arr) != np.shape(
                        var_state[t.id]):
                    raise ValueError(
                        f"checkpointed flat-state entry for {t.name} has "
                        f"shape {np.shape(arr)}, param is "
                        f"{np.shape(var_state[t.id])}")
                vals[t.id] = arr if arr is not None else default(t)
            return vals

        zeros = lambda t: jnp.zeros(  # noqa: E731
            np.shape(var_state[t.id]), jnp.float32)
        # master defaults to the current (possibly bf16) param values —
        # exactly what a flat_state=False checkpoint implies
        master = _per_param(st.get("master"), lambda t: var_state[t.id])
        flat: Dict[str, Any] = {
            "step": jnp.asarray(st.get("step")
                                if st.get("step") is not None else 0,
                                jnp.int32),
            "flat_master": new_lay.pack(master),
        }
        for s in slots:
            flat[f"flat_{s}"] = new_lay.pack(_per_param(st.get(s), zeros))
        flat.update(self._flat_extra_init(new_lay, st))
        sh = NamedSharding(mesh, PartitionSpec(self.dp_axis))
        for key, bufs in flat.items():
            if key.startswith("flat_"):
                flat[key] = [jax.device_put(a, sh) for a in bufs]
        self._flat_layout = new_lay
        self._state = flat
        self._pending_tree_state = None
        self._packed_var_writes = writes
        if self.zero >= 3:
            # ZeRO-3 at rest: the flat fp32 master IS the authoritative
            # parameter storage; the per-param working copies stay
            # dp-sharded (dim-0 when divisible) so nothing replicated
            # remains resident between steps
            for t in sync_order(xs):
                arr = var_state.get(t.id)
                if arr is None or not hasattr(arr, "shape"):
                    continue
                psh = self._param_shardings.get(t.id)
                if psh is None:
                    psh = self._state_sharding(t, arr, graph)
                    if psh is None:
                        continue
                    self._param_shardings[t.id] = psh
                var_state[t.id] = jax.device_put(arr, psh)
                graph._var_data[t.id] = var_state[t.id]
        return self._state

    def _flat_state_pspecs(self, opt_state: Dict[str, Any]):
        """shard_map specs matching ``opt_state``'s structure: flat
        buffers ride P(dp), everything else replicated."""
        from jax.sharding import PartitionSpec
        return {k: ([PartitionSpec(self.dp_axis)] * len(v)
                    if k.startswith("flat_") else PartitionSpec())
                for k, v in opt_state.items()}

    def _flat_gather_params(self, fstate, xs: Sequence[Tensor], axis: str):
        """ZeRO-3 just-in-time parameter materialization: all-gather
        every bucket of the flat fp32 master in the bucket's WEIGHT
        dtype (``all_gather_coalesced`` casts the chunk before the
        collective), tagged ``param_gather`` so parameter-gather traffic
        stays separable from gradient and param_comm traffic.  Returns
        ``{tid: full param}`` — bitwise the arrays ZeRO-2's post-update
        all-gather produced, since the chunks ARE the same fp32 master.
        Must run inside the shard_map manual region."""
        from ..parallel import comm
        lay = self._flat_layout
        return comm.all_gather_coalesced(
            list(fstate["flat_master"]), lay.comm_layout(), axis,
            tag="param_gather")

    def materialize_flat_params(self, graph: Graph,
                                xs: Sequence[Tensor]) -> None:
        """Refresh the per-param working copies from the flat fp32
        master (ZeRO-3's authoritative storage).  Called lazily when a
        consumer outside the flat update loop needs parameter VALUES —
        eval plans, checkpoint saves, hot switches — and stored back
        dp-sharded so the at-rest footprint stays 1/dp.  The cast
        fp32 -> weight dtype is exactly the in-region gather's, so a
        continuation from the materialized copies is bitwise."""
        lay = self._flat_layout
        if lay is None or "flat_master" not in self._state:
            return
        per = lay.unpack(self._state["flat_master"])
        for t in xs:
            if t.id not in per:
                continue
            arr = jnp.asarray(per[t.id]).astype(t.dtype.to_jnp())
            sh = self._param_shardings.get(t.id)
            if sh is None and self.zero >= 3:
                sh = self._state_sharding(t, arr, graph)
                if sh is not None:
                    self._param_shardings[t.id] = sh
            if sh is not None:
                arr = jax.device_put(arr, sh)
            graph._var_data[t.id] = arr

    def _flat_sync_and_update(self, var_state, fstate, grads,
                              xs: Sequence[Tensor], axis: str,
                              want_sq_norm: bool = False):
        """Reduce-scatter -> local-chunk update -> param-dtype all-gather
        (the reference's zero pairing, Communication.h:583, without ever
        materializing a full gradient).  Must run inside the shard_map
        manual region; ``fstate`` leaves arrive as LOCAL chunks.
        Returns (new param dict, new flat buffers, global grad sq-norm
        or None).  The sq-norm (``want_sq_norm`` or clipping) is the
        psum-reduced fp32 sum of squares of the SYNCED gradient — the
        quantity the clip and the numeric sentry share; psum on its
        def-chain keeps it legal to return from the region.  The step
        counter is NOT among the outputs: it is replicated arithmetic
        the caller increments outside the region (a scalar leaving a
        manual region with no reduction on its def-chain would —
        rightly — trip the unreduced-psum-scalar lint)."""
        from ..parallel import comm
        from .flat_state import sync_order
        lay = self._flat_layout
        xs_sorted = sync_order(xs)
        gdict = {t.id: grads[t.id] for t in xs_sorted}
        chunks, rs_layout = comm.reduce_scatter_coalesced(
            gdict, axis, op="mean", bucket_mb=self.bucket_mb,
            transport=self.grad_comm or "fp32")
        assert tuple(rs_layout.chunks) == tuple(lay.chunks), \
            "flat-state layout drifted from the reduce-scatter geometry"
        # the local-chunk update (clip, slots, master) is the step's
        # ``optimizer`` phase; the collectives on either side carry
        # their own comm_tag (grad_comm / param_comm / param_gather)
        with phase("optimizer"):
            sq_norm = None
            if self.max_grad_norm is not None or want_sq_norm:
                # global sum of squares over the scattered chunks: local
                # partial sums + one psum (padding lanes contribute exact
                # zeros) — pre-clip, shared by clip and sentry
                sq = sum(jnp.sum(jnp.square(c)) for c in chunks)
                sq_norm = jax.lax.psum(sq, axis)
            if self.max_grad_norm is not None:
                norm = jnp.sqrt(sq_norm)
                scale = jnp.minimum(
                    1.0, self.max_grad_norm / (norm + 1e-6))
                chunks = [c * scale for c in chunks]
            step = fstate["step"] + 1
            lr = self._lr_at(step)
            slots = self._flat_slots()
            new_master: list = []
            new_slots: Dict[str, list] = {s: [] for s in slots}
            for bi, g in enumerate(chunks):
                p = fstate["flat_master"][bi]
                cur = {s: fstate[f"flat_{s}"][bi] for s in slots}
                p_new, cur_new = self._flat_update(p, cur, g, step, lr,
                                                   bucket=bi, axis=axis,
                                                   fstate=fstate)
                new_master.append(p_new)
                for s in slots:
                    new_slots[s].append(cur_new[s])
            out: Dict[str, Any] = {"flat_master": new_master}
            for s in slots:
                out[f"flat_{s}"] = new_slots[s]
            for k, v in self._flat_extra_update(fstate).items():
                out[k] = v
        if self.zero >= 3:
            # ZeRO-3: nothing but the 1/dp master chunks survives the
            # step — the next step's forward re-gathers just-in-time
            # (param_gather), so there is no post-update all-gather and
            # the trainables drop out of the returned var set entirely
            xs_ids = {t.id for t in xs_sorted}
            new_vars = {k: v for k, v in var_state.items()
                        if k not in xs_ids}
            return new_vars, out, sq_norm
        # updated params ride the WEIGHT dtype across the wire (bucket
        # dtype == param dtype), tagged param_comm — gradient bytes and
        # parameter bytes stay separable in the accounting
        gathered = comm.all_gather_coalesced(new_master, rs_layout, axis,
                                             tag="param_comm")
        new_vars = dict(var_state)
        for t in xs_sorted:
            new_vars[t.id] = gathered[t.id]
        return new_vars, out, sq_norm

    def _c_param(self, tid: int, p):
        """ZeRO-3: keep the updated parameter dp-sharded at rest;
        ZeRO-1/2: pin it to its own (dp-replicated) spec — the param
        allgather of the reference's zero pairing."""
        sh = self._param_shardings.get(tid) if self.zero >= 3 \
            else self._param_base_shardings.get(tid)
        if sh is not None:
            return jax.lax.with_sharding_constraint(p, sh)
        return p

    def _store_state(self, state: Dict[str, Any]) -> None:
        self._state = dict(state)

    def reset_state_rows(self, param: Tensor, rows) -> None:
        """Zero the leading-dim rows of every per-param state array for
        ``param`` (momentum, Adam m/v).  Used by cache-backed embeddings
        when a slot's occupant changes (hetu_tpu/embedding/cached.py);
        subclasses with non-standard state layouts must override."""
        if self._flat_layout is not None:
            # rows of one param live at arbitrary offsets inside shared
            # flat buffers; silently skipping would corrupt cache-backed
            # embeddings — refuse loudly instead
            raise NotImplementedError(
                "reset_state_rows is not supported with flat_state=True "
                "(cache-backed embeddings need per-param state)")
        rows = np.asarray(rows)
        if rows.size == 0:
            return
        tid = param.id
        nrows = param.shape[0] if param.shape else 0
        rows_dev = jnp.asarray(rows)
        for state in self._state.values():
            if isinstance(state, dict) and tid in state:
                arr = state[tid]
                if hasattr(arr, "ndim") and arr.ndim >= 1 \
                        and arr.shape[0] == nrows:
                    # device-side masked update: preserves the array's
                    # sharding/placement (a numpy round-trip would gather
                    # and fail on non-fully-addressable arrays)
                    arr = jnp.asarray(arr)
                    state[tid] = arr.at[rows_dev].set(0)

    def _init_state(self, var_state, xs) -> Dict[str, Any]:
        return {}

    def _lr_at(self, step):
        """Resolve lr: plain float, or a schedule called with the
        (1-based, traced) step — see optim/schedules.py."""
        return self.lr(step) if callable(self.lr) else self.lr

    def _grad_sq_norm(self, grads: Dict[int, jax.Array],
                      xs: Sequence[Tensor]):
        """fp32 global sum of squared gradients — the ONE quantity the
        global-norm clip and the numeric sentry both read (shared here
        so XLA CSE makes the reuse literal).  Nonfinite iff any
        gradient lane is nonfinite."""
        return sum(jnp.sum(jnp.square(grads[t.id].astype(jnp.float32)))
                   for t in xs)

    def _clip_grads(self, grads: Dict[int, jax.Array],
                    xs: Sequence[Tensor]) -> Dict[int, jax.Array]:
        """Global-norm clip across ALL parameter grads (fp32 norm)."""
        if self.max_grad_norm is None:
            return grads
        norm = jnp.sqrt(self._grad_sq_norm(grads, xs))
        scale = jnp.minimum(1.0, self.max_grad_norm / (norm + 1e-6))
        return {t.id: (grads[t.id].astype(jnp.float32) * scale)
                .astype(grads[t.id].dtype) for t in xs}

    def _apply_updates(self, var_state: Dict[int, jax.Array],
                       opt_state: Dict[str, Any],
                       grads: Dict[int, jax.Array],
                       xs: Sequence[Tensor]):
        raise NotImplementedError

    # -- eager API (torch-style step) ----------------------------------------

    def step(self, grads: Dict[int, jax.Array]) -> None:
        assert self.params is not None, "eager step needs params list"
        assert not self.flat_state, \
            "eager step() has no manual dp region; flat_state needs the " \
            "graph explicit path (DefineAndRunGraph.run)"
        g = self.params[0].graph
        var_state = {p.id: g.get_tensor_value(p) for p in self.params}
        opt_state = self._ensure_state(var_state, self.params, g)
        new_vars, new_opt = self._apply_updates(var_state, opt_state, grads,
                                                self.params)
        for p in self.params:
            g._var_data[p.id] = new_vars[p.id]
        self._store_state(new_opt)


class SGDOptimizer(Optimizer):
    def __init__(self, params=None, lr: float = 0.01, momentum: float = 0.0,
                 nesterov: bool = False, **kw):
        super().__init__(params, lr, **kw)
        self.momentum = momentum
        self.nesterov = nesterov

    def _init_state(self, var_state, xs):
        state = {"step": jnp.zeros((), jnp.int32)}
        if self.momentum != 0.0:
            state["velocity"] = {t.id: jnp.zeros_like(var_state[t.id])
                                 for t in xs}
        return state

    def _flat_slots(self):
        return ("velocity",) if self.momentum != 0.0 else ()

    def _flat_update(self, p, slots, g, step, lr, **ctx):
        if self.momentum == 0.0:
            return p - lr * g, {}
        v = self.momentum * slots["velocity"] + g
        upd = g + self.momentum * v if self.nesterov else v
        return p - lr * upd, {"velocity": v}

    def _apply_updates(self, var_state, opt_state, grads, xs):
        grads = self._clip_grads(grads, xs)
        new_vars = dict(var_state)
        new_opt = dict(opt_state)
        # .get: checkpoints from before SGD carried a step counter have
        # no "step" entry — backfill instead of KeyError on restore
        step = opt_state.get("step", jnp.zeros((), jnp.int32)) + 1
        new_opt["step"] = step
        lr = self._lr_at(step)
        def apply(p, upd):
            # fp32 update math, cast back (a scheduled lr is an fp32
            # scalar; don't let promotion change the stored param dtype)
            return (p.astype(jnp.float32)
                    - lr * upd.astype(jnp.float32)).astype(p.dtype)

        if self.momentum == 0.0:
            for t in xs:
                g = self._c_grad(t.id, grads[t.id].astype(var_state[t.id].dtype))
                new_vars[t.id] = self._c_param(t.id, apply(var_state[t.id], g))
            return new_vars, new_opt
        vel = dict(opt_state["velocity"])
        for t in xs:
            g = self._c_grad(t.id, grads[t.id].astype(var_state[t.id].dtype))
            v = self._c(t.id, self.momentum * vel[t.id] + g)
            vel[t.id] = v
            upd = g + self.momentum * v if self.nesterov else v
            new_vars[t.id] = self._c_param(t.id, apply(var_state[t.id], upd))
        new_opt["velocity"] = vel
        return new_vars, new_opt


class AdamOptimizer(Optimizer):
    """Adam/AdamW (reference AdamOptimizer, optimizer.h:60; fused kernel
    impl/kernel/Optimizers.cu).  States kept in fp32 regardless of param
    dtype (mixed-precision master states)."""

    decoupled_weight_decay = False  # True in AdamW (decoupled, torch-style)

    def __init__(self, params=None, lr: float = 1e-3, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-8,
                 weight_decay: float = 0.0, **kw):
        super().__init__(params, lr, **kw)
        self.beta1, self.beta2, self.eps = beta1, beta2, eps
        self.weight_decay = weight_decay

    def _init_state(self, var_state, xs):
        return {
            "step": jnp.zeros((), jnp.int32),
            "m": {t.id: jnp.zeros(var_state[t.id].shape, jnp.float32)
                  for t in xs},
            "v": {t.id: jnp.zeros(var_state[t.id].shape, jnp.float32)
                  for t in xs},
        }

    def _flat_slots(self):
        return ("m", "v")

    def _flat_update(self, p, slots, g, step, lr, **ctx):
        # same math as _apply_updates on fp32 chunks; padding lanes have
        # g == 0 and p == 0, so every term stays exactly 0 there
        b1, b2 = self.beta1, self.beta2
        if self.weight_decay and not self.decoupled_weight_decay:
            g = g + self.weight_decay * p                      # Adam-L2
        m = b1 * slots["m"] + (1 - b1) * g
        v = b2 * slots["v"] + (1 - b2) * (g * g)
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        upd = lr * (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
        if self.weight_decay and self.decoupled_weight_decay:
            upd = upd + lr * self.weight_decay * p
        return p - upd, {"m": m, "v": v}

    def _apply_updates(self, var_state, opt_state, grads, xs):
        grads = self._clip_grads(grads, xs)
        new_vars = dict(var_state)
        step = opt_state["step"] + 1
        m = dict(opt_state["m"])
        v = dict(opt_state["v"])
        b1, b2 = self.beta1, self.beta2
        lr = self._lr_at(step)
        bc1 = 1.0 - b1 ** step.astype(jnp.float32)
        bc2 = 1.0 - b2 ** step.astype(jnp.float32)
        for t in xs:
            g = self._c_grad(t.id, grads[t.id].astype(jnp.float32))
            p = var_state[t.id]
            if self.weight_decay and not self.decoupled_weight_decay:
                g = g + self.weight_decay * p.astype(jnp.float32)  # Adam-L2
            m[t.id] = self._c(t.id, b1 * m[t.id] + (1 - b1) * g)
            v[t.id] = self._c(t.id, b2 * v[t.id] + (1 - b2) * (g * g))
            m_hat = m[t.id] / bc1
            v_hat = v[t.id] / bc2
            upd = lr * m_hat / (jnp.sqrt(v_hat) + self.eps)
            if self.weight_decay and self.decoupled_weight_decay:
                upd = upd + lr * self.weight_decay * p.astype(jnp.float32)
            new_vars[t.id] = self._c_param(
                t.id, (p.astype(jnp.float32) - upd).astype(p.dtype))
        return new_vars, {"step": step, "m": m, "v": v}


class AdamWOptimizer(AdamOptimizer):
    """AdamW: decoupled weight decay (torch.optim.AdamW semantics)."""
    decoupled_weight_decay = True


class AdafactorOptimizer(Optimizer):
    """Adafactor (Shazeer & Stern 2018) — the memory-efficient TPU
    pretraining optimizer (T5 recipe): second moments factored into
    row/col EMAs, so optimizer state is O(rows+cols) per matrix instead
    of O(rows*cols).  Beyond the reference (SGD/Adam only).

    The per-param path delegates the update math to ``optax.adafactor``
    (public, baked-in) under this framework's graph-update machinery, so
    it composes with define-and-run graphs, donation, and checkpointing
    like the native optimizers.  ``lr`` may be a float or an
    ``optim.schedules`` callable (1-based steps, adapted to optax's
    0-based count).

    ``flat_state=True`` is supported natively (same optax semantics,
    reimplemented on bucket chunks): the full second moment rides the
    flat dp-sharded ``v`` slot ONLY for parameters too small to factor;
    factored parameters keep row/col EMA vectors packed per-bucket in
    replicated ``fac_row``/``fac_col`` state (O(rows+cols) — tiny), and
    their lanes of ``v`` stay zero.  The factored stats need global
    row/col means of the squared gradient, which each rank computes from
    its chunk via static segment-sum plans plus ONE fp32 psum per bucket
    (a second when ``clipping_threshold`` adds the per-block update-RMS
    reduction); those extra collectives are declared through
    ``_flat_comm_extra`` so the strict emission verifier still holds
    exactly.  Deviation from optax: only 2-D parameters factor (ndim>2
    falls back to the full second moment).
    """

    def __init__(self, params=None, lr=None, min_dim_size_to_factor=128,
                 decay_rate: float = 0.8, clipping_threshold: float = 1.0,
                 momentum: Optional[float] = None,
                 weight_decay_rate: Optional[float] = None,
                 multiply_by_parameter_scale: bool = True,
                 max_grad_norm: Optional[float] = None, **kw):
        super().__init__(params, lr, max_grad_norm=max_grad_norm, **kw)
        import optax
        self.min_dim_size_to_factor = int(min_dim_size_to_factor)
        self.decay_rate = float(decay_rate)
        self.clipping_threshold = clipping_threshold
        self.momentum = momentum
        self.weight_decay_rate = weight_decay_rate
        self.multiply_by_parameter_scale = multiply_by_parameter_scale
        self.eps = 1e-30            # optax factorized epsilon[0]
        self._fac_cache = None      # (layout, per-bucket segment plans)
        self._pending_fac = None
        if callable(lr):
            schedule = lambda count: lr(count + 1)  # noqa: E731
        else:
            schedule = lr
        self._tx = optax.adafactor(
            learning_rate=schedule,
            min_dim_size_to_factor=min_dim_size_to_factor,
            decay_rate=decay_rate,
            clipping_threshold=clipping_threshold,
            momentum=momentum,
            weight_decay_rate=weight_decay_rate,
            multiply_by_parameter_scale=multiply_by_parameter_scale)

    def _init_state(self, var_state, xs):
        params = {t.id: var_state[t.id].astype(jnp.float32) for t in xs}
        return {"optax": self._tx.init(params)}

    # -- flat_state support ---------------------------------------------------

    def _factored_dims(self, shape):
        """(d1, d0) = (second-largest, largest) dim index when ``shape``
        factors — optax's rule restricted to ndim==2 (the flat plans
        index rows/cols of matrices; higher-rank tensors keep the full
        second moment)."""
        if len(shape) != 2 or min(shape) < self.min_dim_size_to_factor:
            return None
        order = np.argsort(shape)     # stable: square -> d1=0, d0=1
        return int(order[-2]), int(order[-1])

    def _flat_slots(self):
        return ("v",) + (("m",) if self.momentum else ())

    def _fac_plan(self, lay):
        """Per-bucket static segment plans mapping every flat-buffer
        lane to its factored row/col slot and owning param.  Pure numpy
        from the layout index (cached per layout object); rank-local
        views are sliced inside the update by ``axis_index``.

        Slot spaces per bucket (each with one trailing TRASH slot that
        absorbs padding lanes and non-factored params):
        ``row``  — concatenated per-factored-param vectors of length
        ``shape[d1]`` (the axis that survives the mean over d0);
        ``col``  — same with d0/d1 swapped; ``pid`` — one slot per
        param (clip blocks + parameter-scale RMS)."""
        if self._fac_cache is not None and self._fac_cache[0] is lay:
            return self._fac_cache[1]
        plans = []
        n = lay.device_num
        for bi, b in enumerate(lay.buckets):
            size = n * lay.chunks[bi]
            nparams = len(b.keys)
            row_div, rowslot_pid, col_div = [], [], []
            p_nrows = np.ones(nparams + 1, np.float32)
            p_numel = np.ones(nparams + 1, np.float32)
            # first pass: count row/col slots so trash ids are known
            n_rows = n_cols = 0
            facd = []
            for shape in b.shapes:
                fd = self._factored_dims(shape)
                facd.append(fd)
                if fd is not None:
                    d1, d0 = fd
                    n_rows += shape[d1]
                    n_cols += shape[d0]
            n_rows += 1               # trash slots
            n_cols += 1
            pid = np.full(size, nparams, np.int32)
            row_id = np.full(size, n_rows - 1, np.int32)
            col_id = np.full(size, n_cols - 1, np.int32)
            fac = np.zeros(size, np.float32)
            real = np.zeros(size, np.float32)
            off = row_base = col_base = 0
            for idx, (shape, numel, fd) in enumerate(
                    zip(b.shapes, b.numels, facd)):
                sl = slice(off, off + numel)
                real[sl] = 1.0
                pid[sl] = idx
                p_numel[idx] = numel
                if fd is not None:
                    d1, d0 = fd
                    q = np.arange(numel)
                    i, j = q // shape[1], q % shape[1]
                    row_id[sl] = row_base + (i if d0 == 1 else j)
                    col_id[sl] = col_base + (j if d0 == 1 else i)
                    fac[sl] = 1.0
                    row_div.extend([shape[d0]] * shape[d1])
                    rowslot_pid.extend([idx] * shape[d1])
                    col_div.extend([shape[d1]] * shape[d0])
                    p_nrows[idx] = shape[d1]
                    row_base += shape[d1]
                    col_base += shape[d0]
                off += numel
            row_div.append(1)
            rowslot_pid.append(nparams)
            col_div.append(1)
            plans.append({
                "pid": pid, "row_id": row_id, "col_id": col_id,
                "fac": fac, "real": real,
                "n_rows": n_rows, "n_cols": n_cols,
                "nparams": nparams,
                "row_div": np.asarray(row_div, np.float32),
                "rowslot_pid": np.asarray(rowslot_pid, np.int32),
                "col_div": np.asarray(col_div, np.float32),
                "p_nrows": p_nrows, "p_numel": p_numel,
            })
        self._fac_cache = (lay, plans)
        return plans

    def _flat_update(self, p, slots, g, step, lr, **ctx):
        """optax.adafactor's exact chain on one bucket's local chunk —
        factored stats via segment sums + one psum (two with clipping):
        scale_by_factored_rms -> clip_by_block_rms -> lr ->
        scale_by_param_block_rms -> ema(momentum) ->
        add_decayed_weights -> descent."""
        import jax.ops
        bi, axis = ctx["bucket"], ctx["axis"]
        fstate = ctx["fstate"]
        lay = self._flat_layout
        plan = self._fac_plan(lay)[bi]
        if bi == 0:
            self._pending_fac = ([], [])
        chunk = lay.chunks[bi]
        r = jax.lax.axis_index(axis)

        def local(arr):
            return jax.lax.dynamic_slice_in_dim(
                jnp.asarray(arr), r * chunk, chunk)

        pid_l = local(plan["pid"])
        row_l = local(plan["row_id"])
        col_l = local(plan["col_id"])
        fac_l = local(plan["fac"])
        real_l = local(plan["real"])
        n_rows, n_cols = plan["n_rows"], plan["n_cols"]
        nseg = plan["nparams"] + 1
        t = step.astype(jnp.float32)
        d = 1.0 - t ** (-self.decay_rate)      # decay_rate_t, 1-based t
        gsq = g * g + self.eps
        # round 1: rank-local segment sums -> ONE fp32 psum (row sums,
        # col sums, and pre-update param sq-norms ride one buffer)
        row_s = jax.ops.segment_sum(gsq, row_l, num_segments=n_rows)
        col_s = jax.ops.segment_sum(gsq, col_l, num_segments=n_cols)
        psq = jax.ops.segment_sum(p * p, pid_l, num_segments=nseg)
        stats = jax.lax.psum(jnp.concatenate([row_s, col_s, psq]), axis)
        row_s = stats[:n_rows]
        col_s = stats[n_rows:n_rows + n_cols]
        psq = stats[n_rows + n_cols:]
        # factored row/col EMAs (replicated — every rank computed the
        # same psum) and the factored update
        vr = d * fstate["fac_row"][bi] + (1 - d) * (row_s / plan["row_div"])
        vc = d * fstate["fac_col"][bi] + (1 - d) * (col_s / plan["col_div"])
        self._pending_fac[0].append(vr)
        self._pending_fac[1].append(vc)
        rsum = jax.ops.segment_sum(vr, jnp.asarray(plan["rowslot_pid"]),
                                   num_segments=nseg)
        rmean = rsum / plan["p_nrows"]
        rf = (jnp.maximum(vr, self.eps)
              / jnp.maximum(rmean[plan["rowslot_pid"]], self.eps)) ** -0.5
        cf = jnp.maximum(vc, self.eps) ** -0.5
        u_fac = g * rf[row_l] * cf[col_l]
        # non-factored lanes: full second moment on the flat v slot
        # (kept exactly zero on factored/padding lanes)
        vfull = d * slots["v"] + (1 - d) * gsq
        u_nf = g * jax.lax.rsqrt(jnp.maximum(vfull, self.eps))
        u = jnp.where(fac_l > 0, u_fac, u_nf)
        out = {"v": vfull * real_l * (1.0 - fac_l)}
        if self.clipping_threshold is not None:
            # round 2: per-param block RMS of the update
            usq = jax.lax.psum(
                jax.ops.segment_sum(u * u, pid_l, num_segments=nseg), axis)
            rms_u = jnp.sqrt(usq / plan["p_numel"])
            u = u / jnp.maximum(
                1.0, rms_u / self.clipping_threshold)[pid_l]
        if lr is not None:
            u = u * lr
        if self.multiply_by_parameter_scale:
            pscale = jnp.maximum(jnp.sqrt(psq / plan["p_numel"]), 1e-3)
            u = u * pscale[pid_l]
        if self.momentum:
            m = self.momentum * slots["m"] + (1 - self.momentum) * u
            u = m
            out["m"] = m * real_l
        if self.weight_decay_rate:
            u = u + self.weight_decay_rate * p
        u = u * real_l
        return p - u, out

    def _flat_extra_update(self, fstate):
        fr, fc = self._pending_fac
        self._pending_fac = None
        return {"fac_row": fr, "fac_col": fc}

    def _flat_extra_init(self, lay, st):
        """Zero row/col EMA vectors per bucket (reusing shape-matching
        vectors from ``st`` when a rebuild preserved them)."""
        plans = self._fac_plan(lay)
        out = {}
        for key, n_key in (("fac_row", "n_rows"), ("fac_col", "n_cols")):
            old = st.get(key)
            vecs = []
            for bi, plan in enumerate(plans):
                want = plan[n_key]
                if (isinstance(old, (list, tuple)) and bi < len(old)
                        and np.shape(old[bi]) == (want,)):
                    vecs.append(jnp.asarray(old[bi], jnp.float32))
                else:
                    vecs.append(jnp.zeros((want,), jnp.float32))
            out[key] = vecs
        return out

    def _flat_comm_extra(self):
        lay = self._flat_layout
        nb = len(lay.buckets) if lay is not None else 0
        per_bucket = 2 if self.clipping_threshold is not None else 1
        return {"all_reduce": nb * per_bucket} if nb else {}

    def _apply_updates(self, var_state, opt_state, grads, xs):
        grads = self._clip_grads(grads, xs)
        params = {t.id: var_state[t.id].astype(jnp.float32) for t in xs}
        gdict = {t.id: grads[t.id].astype(jnp.float32) for t in xs}
        updates, new_opt = self._tx.update(gdict, opt_state["optax"], params)
        new_vars = dict(var_state)
        for t in xs:
            p = var_state[t.id]
            new_vars[t.id] = self._c_param(
                t.id, (params[t.id] + updates[t.id]).astype(p.dtype))
        return new_vars, {"optax": new_opt}


# torch-style aliases
SGD = SGDOptimizer
Adam = AdamOptimizer
AdamW = AdamWOptimizer
