"""MPMD pipeline runtime — per-stage programs on device submeshes.

The reference executes pipelines as per-rank task loops over a generated
schedule (``ExecutableGraph::CrucialRun``, ``executable_graph.cc:1788``:
``GeneratePipedreamFlushSchedule`` + per-micro-batch ``ComputeFunc`` with
P2P at stage boundaries).  Under XLA's SPMD model a single program cannot
give different stages genuinely different amounts of work — masking makes
a slow device burn the same wall clock — so heterogeneous pipelines
(Malleus: unequal layers per stage, unequal micro-batches per pipeline)
are expressed here the multi-program way:

- every stage is its own jitted program compiled for its own
  ``jax.sharding.Mesh`` submesh (dp/tp inside the stage via GSPMD);
- a controller walks the 1F1B (or GPipe) schedule from
  :mod:`hetu_tpu.parallel.schedule`, enqueueing stage computations; JAX's
  async dispatch overlaps stages that live on disjoint devices (the
  analogue of the reference's per-rank CUDA streams);
- stage-boundary activations/grads move with ``jax.device_put`` between
  submeshes (ICI transfers; the reference's ``kP2PStream`` send/recv);
- backward stashes only the stage *input* and recomputes the forward
  inside the vjp (activation recompute by default, like running the
  reference with recompute on), so the live-memory profile is the
  schedule's in-flight bound: ``S - s`` for 1F1B vs ``M`` for GPipe.

Per-step memory/teardown accounting is kept in :class:`StepStats` so
tests can assert the 1F1B < GPipe activation high-water directly.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.tracer import get_tracer
from .schedule import (Task, generate_gpipe_schedule,
                       generate_interleaved_1f1b_schedule,
                       generate_pipedream_flush_schedule, max_in_flight,
                       validate_schedule)


def _put(tree, mesh: Optional[Mesh], spec: P):
    """Transfer a pytree onto ``mesh`` with ``spec`` (stage-boundary P2P)."""
    if mesh is None:
        return tree
    sh = NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh), tree)


def _tree_bytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(tree)
               if hasattr(a, "dtype"))


# grad scale+accumulate as ONE jitted call per B task (donated
# accumulator) — per-leaf eager dispatch here was the dominant controller
# cost per task (the reference keeps its hot loop free of per-tensor host
# work, executable_graph.cc:1424).  Module-level so every stage shares
# one jit cache; `w.astype(a.dtype)` keeps grad dtypes (bf16 stages must
# not be promoted to f32 by a strongly-typed scalar).
_scale_grads = jax.jit(
    lambda dp, w: jax.tree_util.tree_map(
        lambda a: a * w.astype(a.dtype), dp))
_accum_grads = jax.jit(
    lambda acc, dp, w: jax.tree_util.tree_map(
        lambda a, b: a + b * w.astype(b.dtype), acc, dp),
    donate_argnums=0)


class Stage:
    """One pipeline stage: a forward program (+ derived backward) on a
    device submesh.

    ``fwd(params, x, rng) -> y`` for non-last stages;
    ``loss_fwd(params, x, target, rng) -> scalar mean loss`` on the last
    stage (the loss lives with the last stage, as in the reference).
    ``act_spec`` is the PartitionSpec of the activation on this stage's
    submesh (usually ``P("dp", None, ...)``).
    """

    def __init__(self, fwd: Callable, params: Any,
                 mesh: Optional[Mesh] = None,
                 act_spec: P = P(),
                 is_last: bool = False):
        self.params = params
        self.mesh = mesh
        self.act_spec = act_spec
        self.is_last = is_last
        self._fwd = fwd
        if is_last:
            # fused F+B on the last stage: B(m) directly follows F(m) in
            # every schedule.  vjp rather than value_and_grad so an
            # integer x (S == 1: the stage input is the token ids) yields
            # a float0 cotangent instead of an error.
            def _loss_grads(params, x, target, rng):
                loss, vjp = jax.vjp(
                    lambda p, xx: fwd(p, xx, target, rng), params, x)
                dp, dx = vjp(jnp.ones_like(loss))
                return loss, dp, dx
            self.step_last = jax.jit(_loss_grads)
            self.fwd_only = jax.jit(lambda p, x, t, r: fwd(p, x, t, r))
        else:
            self.fwd_jit = jax.jit(fwd)

            def _bwd(params, x, rng, dy):
                _, vjp = jax.vjp(lambda p, xx: fwd(p, xx, rng), params, x)
                dp, dx = vjp(dy)
                return dp, dx
            self.bwd_jit = jax.jit(_bwd)


@dataclass
class StepStats:
    """Per-step accounting the tests assert on."""
    loss: float = 0.0
    stash_peak: List[int] = field(default_factory=list)      # per (pipe,stage)
    stash_peak_bytes: List[int] = field(default_factory=list)
    schedule: str = ""
    # controller dispatch accounting: wall time of the host task loop
    # (device work is dispatched async inside it) and the final
    # loss-fetch sync, so dispatch overhead is measurable (the per-stage
    # jit-call MPMD design trades this for flexibility)
    controller_seconds: float = 0.0
    sync_seconds: float = 0.0
    num_tasks: int = 0

    @property
    def max_stash(self) -> int:
        return max(self.stash_peak) if self.stash_peak else 0


class MPMDPipelineRuntime:
    """Drive P pipelines of S stages through a pipeline schedule.

    ``pipes[p]`` is the list of :class:`Stage` for pipeline ``p``
    (pipelines may have *different* per-stage layer counts — their
    programs are independent).  ``train_step`` takes per-pipeline lists of
    ``(x_mb, target_mb)`` micro-batches (lengths may differ per pipeline:
    Malleus micro-batch apportionment) and returns the sample-weighted
    mean loss plus per-stage parameter grads, already summed across
    pipelines per :meth:`reduce` keys.
    """

    def __init__(self, pipes: Sequence[Sequence[Stage]],
                 schedule: str = "1f1b", num_chunks: int = 1):
        assert pipes and all(len(p) == len(pipes[0]) for p in pipes), \
            "all pipelines must have the same number of stages"
        self.pipes = [list(p) for p in pipes]
        self.num_stages = len(self.pipes[0])
        if schedule not in ("1f1b", "gpipe", "interleaved"):
            raise ValueError(
                f"unknown schedule {schedule!r}; pick 1f1b | gpipe | "
                f"interleaved")
        self.schedule_name = schedule
        # interleaved virtual stages: pipes carry S*C entries whose meshes
        # repeat with period S (chunk c of physical stage s at c*S + s)
        self.num_chunks = int(num_chunks)
        if schedule == "interleaved":
            assert self.num_chunks > 1, \
                "schedule='interleaved' needs num_chunks > 1"
            assert self.num_stages % self.num_chunks == 0, \
                (self.num_stages, self.num_chunks)
        for p in self.pipes:
            assert p[-1].is_last and not any(st.is_last for st in p[:-1])
        # per-(pipe, stage, micro-batch) memory snapshots when enabled via
        # HETU_MEMORY_PROFILE=MICRO_BATCH (reference
        # executable_graph.cc:1738-1761 _all_micro_batches_memory_info)
        from ..utils.profiler import MemoryProfiler
        self.memory_profiler = MemoryProfiler()
        # per-(P, counts) jitted rng-table builders: every eager fold_in
        # is a host dispatch of its own, so the whole table is built in
        # ONE jit call per step instead of 2 fold_ins per task
        self._fold_cache: Dict[Tuple, Any] = {}
        # executed-order p2p tap: one ("send"|"recv", "F"|"B", pipe,
        # stage, micro_batch, peer_stage) entry per stage-boundary
        # transfer the controller actually performed, in execution
        # order.  Reset each train_step.  The schedule verifier's
        # symbolic projection (``schedule.p2p_events``) must match this
        # log exactly — the tap is what makes that claim testable.
        self.p2p_log: List[Tuple[str, str, int, int, int, int]] = []

    def _schedule(self, M: int) -> List[List[Task]]:
        if self.schedule_name == "interleaved":
            sched = generate_interleaved_1f1b_schedule(
                self.num_stages // self.num_chunks, M, self.num_chunks)
        else:
            gen = (generate_pipedream_flush_schedule if self.schedule_name
                   == "1f1b" else generate_gpipe_schedule)
            sched = gen(self.num_stages, M)
        validate_schedule(sched, M)
        return sched

    def train_step(self, data: Sequence[Sequence[Tuple[Any, Any]]],
                   rng: Optional[jax.Array] = None
                   ) -> Tuple[Any, List[List[Any]], StepStats]:
        """Run one step.  Returns (mean_loss, grads[p][s], stats).

        grads[p][s] matches pipes[p][s].params; each micro-batch's loss is
        a mean over its own samples, so grads are rescaled by
        ``m_p / M_total`` to make the step equivalent to one global-batch
        mean regardless of the per-pipeline micro-batch apportionment.
        """
        P_n = len(self.pipes)
        counts = [len(d) for d in data]
        assert len(data) == P_n and all(counts)
        M_total = sum(counts)
        stats = StepStats(schedule=self.schedule_name)

        # per-pipe schedules (each pipe has its own micro-batch count)
        scheds = [self._schedule(m) for m in counts]
        ptr = [[0] * self.num_stages for _ in range(P_n)]
        # in-flight state, keyed (pipe, stage, mb)
        acts: Dict[Tuple[int, int, int], Any] = {}
        stash: Dict[Tuple[int, int, int], Any] = {}
        gin: Dict[Tuple[int, int, int], Any] = {}
        stash_live = [[0] * self.num_stages for _ in range(P_n)]
        stash_peak = [[0] * self.num_stages for _ in range(P_n)]
        stash_bytes = [[0] * self.num_stages for _ in range(P_n)]
        grads: List[List[Any]] = [[None] * self.num_stages
                                  for _ in range(P_n)]
        losses: List[List[Any]] = [[] for _ in range(P_n)]
        if rng is None:
            rng = jax.random.PRNGKey(0)

        self.p2p_log = []
        # seed stage-0 inputs
        for p in range(P_n):
            for m, (x_mb, _) in enumerate(data[p]):
                acts[(p, 0, m)] = x_mb

        fold_key = (P_n, tuple(counts))
        fold_fn = self._fold_cache.get(fold_key)
        if fold_fn is None:
            def _rng_table(r, _counts=tuple(counts), _P=P_n):
                return [[jax.random.fold_in(jax.random.fold_in(r, p), m)
                         for m in range(_counts[p])] for p in range(_P)]
            fold_fn = jax.jit(_rng_table)
            self._fold_cache[fold_key] = fold_fn
        # host numpy keys: uncommitted inputs keep every stage's jit call
        # on the C++ fast path (a device-committed key from the default
        # device forces a slow-path reshard per call on the submeshes)
        rngs = jax.device_get(fold_fn(rng))

        def mb_rng(p, m):
            return rngs[p][m]

        def ready(p, s, t: Task) -> bool:
            if t.kind == "F":
                return (p, s, t.micro_batch) in acts
            if s == self.num_stages - 1:
                return (p, s, t.micro_batch) in acts
            return (p, s, t.micro_batch) in gin

        w_arr = jnp.float32(1.0 / M_total)   # hoisted: one host->dev put

        def run_task(p, s, t: Task) -> None:
            stage = self.pipes[p][s]
            m = t.micro_batch
            if t.kind == "F":
                if s > 0:
                    # the popped activation arrived from stage s-1's
                    # _put — the forward recv side of the boundary
                    self.p2p_log.append(("recv", "F", p, s, m, s - 1))
                x = acts.pop((p, s, m))
                if stage.is_last:
                    # loss+grads fused into the B task; keep the input
                    acts[(p, s, m)] = x
                    return
                y = stage.fwd_jit(stage.params, x, mb_rng(p, m))
                stash[(p, s, m)] = x
                stash_live[p][s] += 1
                stash_peak[p][s] = max(stash_peak[p][s], stash_live[p][s])
                stash_bytes[p][s] = max(stash_bytes[p][s],
                                        stash_live[p][s] * _tree_bytes(x))
                nxt = self.pipes[p][s + 1]
                acts[(p, s + 1, m)] = _put(y, nxt.mesh, nxt.act_spec)
                self.p2p_log.append(("send", "F", p, s, m, s + 1))
                return
            # backward
            if stage.is_last:
                x = acts.pop((p, s, m))
                tgt = data[p][m][1]
                loss, dp, dx = stage.step_last(stage.params, x, tgt,
                                               mb_rng(p, m))
                losses[p].append(loss)
            else:
                x = stash.pop((p, s, m))
                stash_live[p][s] -= 1
                self.p2p_log.append(("recv", "B", p, s, m, s + 1))
                dy = gin.pop((p, s, m))
                dp, dx = stage.bwd_jit(stage.params, x, mb_rng(p, m), dy)
            grads[p][s] = _scale_grads(dp, w_arr) \
                if grads[p][s] is None \
                else _accum_grads(grads[p][s], dp, w_arr)
            if s > 0:
                # dx has the shape/spec of THIS stage's input activation;
                # it lands on the previous stage's submesh
                prev = self.pipes[p][s - 1]
                gin[(p, s - 1, m)] = _put(dx, prev.mesh, stage.act_spec)
                self.p2p_log.append(("send", "B", p, s, m, s - 1))

        # controller loop: round-robin over (pipe, stage), executing the
        # next schedule task whenever its input is available (the
        # reference's CrucialRun task loop, one controller instead of one
        # process per rank)
        remaining = sum(len(s) for sch in scheds for s in sch)
        stats.num_tasks = remaining
        tracer = get_tracer()
        t_ctrl = time.perf_counter()
        while remaining:
            progress = False
            for p in range(P_n):
                for s in range(self.num_stages):
                    i = ptr[p][s]
                    if i >= len(scheds[p][s]):
                        continue
                    t = scheds[p][s][i]
                    if ready(p, s, t):
                        if tracer.enabled:
                            # per-stage-task span (trace plane): dispatch
                            # wall time per pipe/stage row — async XLA
                            # execution overlaps under it, so this shows
                            # the SCHEDULE shape, not device occupancy
                            _ts = tracer.now()
                            run_task(p, s, t)
                            tracer.complete(
                                f"{t.kind} mb{t.micro_batch}", _ts,
                                tracer.now() - _ts,
                                track=f"pipe{p}/stage{s}", pipe=p,
                                stage=s, micro_batch=t.micro_batch,
                                kind=t.kind)
                        else:
                            run_task(p, s, t)
                        if self.memory_profiler.enabled:
                            self.memory_profiler.snapshot(
                                f"pipe{p}.stage{s}.{t.kind}",
                                micro_batch_id=t.micro_batch)
                        ptr[p][s] = i + 1
                        remaining -= 1
                        progress = True
            assert progress, "pipeline schedule deadlocked"
        stats.controller_seconds = time.perf_counter() - t_ctrl

        # weighted mean loss (micro-batch losses are per-mb means); pipes
        # live on disjoint submeshes, so the cross-pipe sum happens on
        # host at the step boundary — ONE stacked fetch per pipe, not a
        # device->host sync per micro-batch
        t_sync = time.perf_counter()
        loss = sum(float(np.asarray(jnp.stack(l)).sum())
                   for l in losses if l) / M_total
        stats.sync_seconds = time.perf_counter() - t_sync
        for p in range(P_n):
            stats.stash_peak.extend(stash_peak[p])
            stats.stash_peak_bytes.extend(stash_bytes[p])
        stats.loss = float(loss)
        return loss, grads, stats


# ---------------------------------------------------------------------------
# static-analysis registration


def register_stage_executables(runtime: "MPMDPipelineRuntime", name: str,
                               stage_args, stage_meta=None) -> List[str]:
    """Register every stage program of an MPMD pipeline with the static
    analyzer (``hetu_tpu.analysis``): last stages register their fused
    loss+grads program (``step_last``, a train executable), the others
    their forward.

    ``stage_args(p, s, stage) -> tuple`` returns the abstract argument
    specs (ShapeDtypeStructs) the stage's jit is traced with;
    ``stage_meta(p, s, stage) -> dict`` optionally supplies extra
    registration meta (declared DS-transition edges, pipeline hop info,
    param pspecs) merged over the defaults.  Returns the registered
    names (``{name}/pipe{p}-stage{s}``).
    """
    from ..graph.graph import clear_executables, register_executable
    clear_executables(name)
    names: List[str] = []
    S = runtime.num_stages
    for p, pipe in enumerate(runtime.pipes):
        for s, stage in enumerate(pipe):
            mesh_axes = {str(a): int(sz)
                         for a, sz in stage.mesh.shape.items()} \
                if stage.mesh is not None else {}
            meta: Dict[str, Any] = {
                "kind": "pipeline_stage",
                "train": bool(stage.is_last),
                "mesh_axes": mesh_axes,
                "params": [],
                "scalar_fetches": 1 if stage.is_last else 0,
                # stage boundaries move via jax.device_put between
                # submeshes (the reference's kP2PStream), not via
                # in-program collectives — hops live in the controller
                "pipeline": {"num_stages": S, "stage": s, "hops": 0},
            }
            if stage_meta is not None:
                extra = stage_meta(p, s, stage) or {}
                pl = {**meta["pipeline"], **(extra.pop("pipeline", {}))}
                meta.update(extra)
                meta["pipeline"] = pl
            fn = stage.step_last if stage.is_last else stage.fwd_jit
            ex_name = f"{name}/pipe{p}-stage{s}"
            register_executable(ex_name, fn, stage_args(p, s, stage),
                                meta)
            names.append(ex_name)
    return names


# ---------------------------------------------------------------------------
# cross-pipeline (hetero-DP) grad reduction


def reduce_layer_grads(runtime: MPMDPipelineRuntime,
                       grads: List[List[Any]],
                       layer_keys: List[List[Sequence[Any]]]
                       ) -> List[List[Any]]:
    """Sum grads across pipelines for params shared by key.

    ``layer_keys[p][s]`` is a pytree-of-keys matching ``grads[p][s]``'s
    top-level dict entries: entries with equal keys across pipelines are
    the same logical parameter (e.g. global layer index, "wte") and their
    grads are summed (the hetero-DP grad exchange; reference hetero-ZeRO
    SplitAllReduce, ``ops/Communication.h:655``).  Entries keyed ``None``
    are pipeline-private.  Reduction happens on the owning stage's mesh of
    pipeline 0 and results are broadcast back to every pipeline's copy.
    """
    P_n = len(runtime.pipes)
    # collect: key -> list of (p, s, entry_name); note a key can repeat
    # across *stages* of one pipeline too (tied wte on first/last stage)

    locations: Dict[Any, List[Tuple[int, int, Any]]] = {}
    for p in range(P_n):
        for s, keys in enumerate(layer_keys[p]):
            for name, key in keys.items():
                if key is None:
                    continue
                locations.setdefault(key, []).append((p, s, name))
    for key, locs in locations.items():
        if len(locs) < 2:
            continue
        p0, s0, n0 = locs[0]
        home = runtime.pipes[p0][s0]
        total = grads[p0][s0][n0]
        for (p, s, n) in locs[1:]:
            g = _put(grads[p][s][n], home.mesh, P())
            total = jax.tree_util.tree_map(jnp.add, total, g)
        for (p, s, n) in locs:
            st = runtime.pipes[p][s]
            grads[p][s][n] = _put(total, st.mesh, P()) \
                if (p, s) != (p0, s0) else total
    return grads


# ---------------------------------------------------------------------------
# per-stage optimizer


class MPMDAdam:
    """Adam over MPMD stage params: one jitted update per stage program,
    states living on the stage's submesh with the params.

    After :func:`reduce_layer_grads`, replicated copies (DP replicas,
    tied weights) receive identical grads, so identical updates keep the
    copies consistent without any extra broadcast (the reference instead
    re-broadcasts after ZeRO updates; with full states per stage none is
    needed).
    """

    def __init__(self, runtime: MPMDPipelineRuntime, lr: float = 1e-3,
                 beta1: float = 0.9, beta2: float = 0.999,
                 eps: float = 1e-8, weight_decay: float = 0.0):
        self.runtime = runtime
        self.hp = (lr, beta1, beta2, eps, weight_decay)
        self.t = 0
        zeros = lambda tree: jax.tree_util.tree_map(jnp.zeros_like, tree)
        self.m = [[zeros(st.params) for st in pipe]
                  for pipe in runtime.pipes]
        self.v = [[zeros(st.params) for st in pipe]
                  for pipe in runtime.pipes]

        lr_, b1, b2, eps_, wd = self.hp

        def upd(params, g, m, v, t):
            m = jax.tree_util.tree_map(
                lambda mm, gg: b1 * mm + (1 - b1) * gg, m, g)
            v = jax.tree_util.tree_map(
                lambda vv, gg: b2 * vv + (1 - b2) * gg * gg, v, g)
            bc1 = 1 - b1 ** t
            bc2 = 1 - b2 ** t

            def one(p, mm, vv):
                step = lr_ * (mm / bc1) / (jnp.sqrt(vv / bc2) + eps_)
                if wd:
                    step = step + lr_ * wd * p
                return p - step
            params = jax.tree_util.tree_map(one, params, m, v)
            return params, m, v
        self._upd = jax.jit(upd)

    def apply(self, grads: List[List[Any]]) -> None:
        self.t += 1
        t = float(self.t)
        for p, pipe in enumerate(self.runtime.pipes):
            for s, stage in enumerate(pipe):
                if grads[p][s] is None:
                    continue
                stage.params, self.m[p][s], self.v[p][s] = self._upd(
                    stage.params, grads[p][s], self.m[p][s],
                    self.v[p][s], t)
