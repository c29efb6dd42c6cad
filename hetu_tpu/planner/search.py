"""Galvatron-style auto-parallel search over a TPU mesh.

Capability counterpart of the reference's Galvatron search engine
(``tools/Galvatron/galvatron/core/hybrid_parallel_config.py:13``
``get_hybrid_parallel_configs_api`` + the C++ DP core): enumerate global
(pp, tp, dp) decompositions of the chip grid, partition layers into
pipeline stages, then per-layer DP over (dp, tp, zero, recompute)
strategy candidates under the per-chip HBM budget — emitting a
reference-style ``ds_parallel_config`` JSON
(``examples/gpt/ds_parallel_config/generate_gpt_3d_config.py``).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .cost_model import (ClusterSpec, LayerSpec, Strategy,
                         embedding_layer_spec, grad_sync_time, layer_memory,
                         layer_time, pipeline_time, transformer_layer_spec)
from .dp_solver import solve_layer_strategies, solve_pipeline_partition

MEM_UNITS = 64  # memory discretization granularity for the DP


@dataclasses.dataclass
class PlanResult:
    """The chosen hybrid-parallel plan."""
    time: float
    pp: int
    stages: List[List[int]]              # layer indices per stage
    layer_strategies: List[Strategy]     # one per layer
    num_microbatches: int
    cluster: ClusterSpec
    micro_batch: Optional[int] = None    # set by plan_for_gpt's mb sweep

    def describe(self) -> str:
        lines = [f"pp={self.pp} m={self.num_microbatches} "
                 f"est_step_time={self.time * 1e3:.2f}ms"]
        for si, stage in enumerate(self.stages):
            sts = {str(self.layer_strategies[i]) for i in stage}
            lines.append(f"  stage{si}: layers {stage[0]}..{stage[-1]} "
                         f"{sorted(sts)}")
        return "\n".join(lines)

    def to_ds_parallel_config(self, layer_names: Optional[Sequence[str]]
                              = None) -> Dict:
        """Reference-style JSON ds_parallel_config (per-layer split/dup/
        device_group_union/zero/recompute keys, parseable by
        :func:`hetu_tpu.nn.parallel.config2ds`)."""
        chips = list(range(self.cluster.total_chips))
        per_stage = len(chips) // self.pp
        out: Dict = {"pp": self.pp, "num_layers": {}, "layers": {}}
        for si, stage in enumerate(self.stages):
            group = [chips[si * per_stage:(si + 1) * per_stage]]
            for li in stage:
                st = self.layer_strategies[li]
                name = (layer_names[li] if layer_names is not None
                        else f"blocks{li}")

                def _w(split):
                    # matches generate_gpt_3d_config's schema: column-
                    # parallel weights split dim 1, row-parallel dim 0,
                    # norms duplicated over the whole stage group
                    return {
                        "type": "variable",
                        "split": split,
                        "dup": ([st.dp] if split else [st.dp * st.tp]),
                        "device_group_union": group,
                        "zero": st.zero > 0,
                        # full searched level (0-3), recorded for
                        # downstream tooling (ds_config.parse_layout
                        # surfaces it); the bool "zero" stays the
                        # reference-schema ds flag
                        "zero_stage": int(st.zero),
                        "recompute": st.recompute,
                    }

                out["layers"][name] = {
                    "layernorm1": _w({}),
                    "attn": {"qkv": _w({"1": [st.tp]}),
                             "dense": _w({"0": [st.tp]})},
                    "layernorm2": _w({}),
                    "mlp": {"dense_h_to_4h": _w({"1": [st.tp]}),
                            "dense_4h_to_h": _w({"0": [st.tp]})},
                }
        return out


def gpt_layer_chain(cfg, global_batch: int, seq: int,
                    dtype_bytes: int) -> List[LayerSpec]:
    """The GPT model as the planner's layer chain: embedding +
    transformer blocks + untied LM head ([h, V] matmul per token)."""
    layers = [embedding_layer_spec(global_batch, seq, cfg.hidden_size,
                                   cfg.vocab_size, dtype_bytes, name="wte")]
    layers += [transformer_layer_spec(global_batch, seq, cfg.hidden_size,
                                      cfg.ffn_size, dtype_bytes,
                                      name=f"block{i}")
               for i in range(cfg.num_layers)]
    layers.append(LayerSpec(
        name="lm_head", flops=2.0 * global_batch * seq * cfg.hidden_size
        * cfg.vocab_size,
        param_bytes=cfg.vocab_size * cfg.hidden_size * dtype_bytes,
        act_bytes=global_batch * seq * cfg.hidden_size * dtype_bytes,
        act_io_bytes=global_batch * seq * cfg.hidden_size * dtype_bytes,
        boundary_bytes=global_batch * seq * cfg.hidden_size * dtype_bytes))
    return layers


#: the hand-written gate-family layouts (pp, dp, tp) of the analysis
#: CI gate, expressed on an 8-chip grid — what an engineer would write
#: down without the search.  hand_plan_times scores them with the SAME
#: calibrated cost model the search ranks candidates with, so "the
#: planner beats every hand plan" is a like-for-like comparison.
HAND_PLANS = {
    "dp8_zero2_flat": (1, 8, 1),        # gate_train: pure-dp ZeRO-2
    "dp2_tp4_sp": (1, 2, 4),            # gate_tp: Megatron-SP
    "pp4_dp2": (4, 2, 1),               # gate_pipe: 4-stage pipeline
    "pp2_dp2_tp2": (2, 2, 2),           # gate_pipe_mpmd submesh shape
}


def hand_plan_times(cfg, global_batch: int, seq: int, n_chips: int,
                    plans: Optional[Dict[str, Tuple[int, int, int]]]
                    = None,
                    cluster: Optional[ClusterSpec] = None,
                    micro_batch_options=None,
                    mem_fraction: float = 0.9,
                    memory_calibration=None,
                    time_calibration="auto") -> Dict[str, float]:
    """Best predicted step time of each hand-written (pp, dp, tp)
    layout, scored with the calibrated cost model — each hand plan
    still gets the per-layer ZeRO/recompute DP and the micro-batch
    sweep (its best possible showing), so beating it means beating the
    layout, not a strawman.  Infeasible layouts (don't fit HBM, don't
    divide the chip grid) are omitted from the result."""
    from .cost_model import calibrate_layer_time
    from .profile_hardware import local_chip

    if cluster is None:
        cluster = ClusterSpec(chip=local_chip(), num_chips=n_chips)
    dtype_bytes = 2 if "bf16" in str(cfg.dtype) or "bfloat16" in \
        str(cfg.dtype) else 4
    if time_calibration == "auto":
        try:
            time_calibration = calibrate_layer_time(
                dtype="bfloat16" if dtype_bytes == 2 else "float32",
                cluster=ClusterSpec(chip=cluster.chip, num_chips=1))
        except Exception:
            time_calibration = None
    layers = gpt_layer_chain(cfg, global_batch, seq, dtype_bytes)
    if micro_batch_options is None:
        micro_batch_options = sorted({
            mb for mb in (1, 2, 4, 8, 16, 32, 64)
            if mb <= global_batch and global_batch % mb == 0},
            reverse=True)
    out: Dict[str, float] = {}
    for name, (pp, dp, tp) in (plans or HAND_PLANS).items():
        if dp * tp * pp != n_chips or cfg.num_layers % pp:
            continue
        best = None
        for mb in micro_batch_options:
            eng = SearchEngine(cluster, layers, global_batch, mb,
                               mem_fraction=mem_fraction,
                               memory_calibration=memory_calibration,
                               time_calibration=time_calibration)
            if global_batch < mb * dp:
                continue
            plan = eng._search_layout(pp, dp, tp)
            if plan is not None and (best is None or plan.time < best):
                best = plan.time
        if best is not None:
            out[name] = float(best)
    return out


def plan_for_gpt(cfg, global_batch: int, seq: int, n_chips: int,
                 calibration=None, micro_batch_options=None,
                 num_slices: int = 1, mem_fraction: float = 0.9,
                 max_tp: Optional[int] = None,
                 memory_calibration="auto",
                 time_calibration="auto") -> PlanResult:
    """Close the planner loop for a GPT model: build the layer chain from
    a ``models.gpt.GPTConfig``, fold a live-hardware
    :class:`~hetu_tpu.planner.profile_hardware.Calibration` into the chip
    spec when given, and return the searched plan — the reference's
    ``get_hybrid_parallel_configs_api`` entry point
    (``tools/Galvatron/galvatron/core/hybrid_parallel_config.py:13``),
    consumed by ``examples/train_gpt.py --auto-parallel``.

    The search covers (pp, dp, tp, zero, recompute) jointly with the
    micro-batch size (``micro_batch_options`` defaults to the powers of
    two ≤ global_batch/dp candidates the schedule allows).

    ``memory_calibration`` feeds the HBM budget check: ``"auto"``
    (default) lowers a single-layer probe in the model's dtype and
    scales the closed-form ``layer_memory`` by the static peak-HBM
    pass's measurement (``cost_model.calibrate_layer_memory``), a
    :class:`~hetu_tpu.planner.cost_model.MemoryCalibration` is used as
    given, and ``None`` keeps the uncalibrated closed form.

    ``time_calibration`` feeds the step-time scoring the same way:
    ``"auto"`` (default) runs ``cost_model.calibrate_layer_time`` on
    the same probe shape (the static FLOP/HBM roofline pass over a
    lowered single-layer train step), so the DP search ranks candidate
    plans on the counted-cost model the analysis gate cross-checks
    against ``compiled.cost_analysis()``; pass a
    :class:`~hetu_tpu.planner.cost_model.TimeCalibration` to reuse a
    measurement, or ``None`` for the uncalibrated closed form.
    """
    from .cost_model import calibrate_layer_memory, calibrate_layer_time
    from .profile_hardware import local_chip

    if calibration is not None:
        chip = calibration.to_chip_spec()
    else:
        chip = local_chip()
    cluster = ClusterSpec(chip=chip, num_chips=max(1, n_chips // num_slices),
                          num_slices=num_slices)
    if calibration is not None and getattr(calibration, "collectives",
                                           None):
        # measured per-link alpha-beta fits feed the SAME formulas the
        # solver and the analysis step-time pass share (cost_model)
        cluster = calibration.to_cluster_spec(
            num_chips=cluster.num_chips, num_slices=num_slices)
    dtype_bytes = 2 if "bf16" in str(cfg.dtype) or "bfloat16" in \
        str(cfg.dtype) else 4
    probe_dtype = "bfloat16" if dtype_bytes == 2 else "float32"
    probe = None
    if memory_calibration == "auto" or time_calibration == "auto":
        # ONE probe trace shared by both calibrations — tracing it is
        # the dominant cost of calibrating
        from .cost_model import _layer_probe_handle
        try:
            probe = _layer_probe_handle(4, 64, 64, 256, probe_dtype,
                                        "planner_probe/layer")
        except Exception:
            probe = None
    if memory_calibration == "auto":
        # probe in the model's compute dtype so the scale carries the
        # right activation widths; failures (no jax, walk error) fall
        # back to the uncalibrated closed form rather than blocking
        try:
            memory_calibration = calibrate_layer_memory(
                dtype=probe_dtype, probe_handle=probe)
        except Exception:
            memory_calibration = None
    if time_calibration == "auto":
        try:
            time_calibration = calibrate_layer_time(
                dtype=probe_dtype,
                cluster=ClusterSpec(chip=cluster.chip, num_chips=1),
                probe_handle=probe)
        except Exception:
            time_calibration = None
    layers = gpt_layer_chain(cfg, global_batch, seq, dtype_bytes)

    if micro_batch_options is None:
        # descending so predicted-time ties keep the LARGEST micro-batch
        # (fewest micro-batches = least per-dispatch overhead on chip)
        micro_batch_options = sorted({
            mb for mb in (1, 2, 4, 8, 16, 32, 64)
            if mb <= global_batch and global_batch % mb == 0},
            reverse=True)
    # pp must divide the transformer stack (the pipelined model places
    # equal layer ranges; embed/head live outside the pipeline body)
    total = cluster.total_chips
    pp_options = [p for p in (1, 2, 4, 8, 16, 32)
                  if p <= min(total, cfg.num_layers)
                  and total % p == 0 and cfg.num_layers % p == 0]
    best: Optional[PlanResult] = None
    for mb in micro_batch_options:
        eng = SearchEngine(cluster, layers, global_batch, mb,
                           mem_fraction=mem_fraction, max_tp=max_tp,
                           memory_calibration=memory_calibration,
                           time_calibration=time_calibration)
        try:
            plan = eng.search(pp_options=pp_options)
        except RuntimeError:
            continue
        if best is None or plan.time < best.time:
            best = plan
            best.micro_batch = mb
    if best is None:
        raise RuntimeError(
            "no feasible plan found for any micro-batch size: model does "
            "not fit in HBM under any searched configuration")
    return best


def verify_plan_schedule(plan: PlanResult):
    """Cross-rank schedule verdict for a searched plan: build the
    symbolic :class:`~hetu_tpu.analysis.schedule.ProgramSpec` the plan
    implies (pp stages x dp x tp, ZeRO level, 1F1B micro-batching) and
    run the collective-schedule verifier over all its ranks.  Returns
    the violation list — empty means the plan's multi-rank program is
    hang-free BEFORE anyone commits a pod to it, which is the planner's
    side of the DESIGN.md §25 contract (a searched plan that deadlocks
    on hardware is worse than a slow one)."""
    from ..analysis.schedule import (ProgramSpec, extract_schedules,
                                     verify_schedules)
    first = plan.layer_strategies[0]
    zero = max(s.zero for s in plan.layer_strategies)
    spec = ProgramSpec(
        dp=int(first.dp), tp=int(first.tp), pp=int(plan.pp),
        zero=int(zero), flat=zero >= 2,
        num_micro_batches=max(1, int(plan.num_microbatches)),
        pipeline_mode="mpmd" if plan.pp > 1 else "none",
        layers=len(plan.layer_strategies))
    return verify_schedules(extract_schedules(spec))


def plan_summary(plan: PlanResult) -> Dict:
    """Flat JSON-able description of a plan (what ``--auto-parallel`` prints)."""
    from collections import Counter
    sts = Counter(str(s) for s in plan.layer_strategies)
    first = plan.layer_strategies[0]
    return {
        "pp": plan.pp,
        "dp": first.dp,
        "tp": first.tp,
        "zero": max(s.zero for s in plan.layer_strategies),
        "recompute_layers": sum(bool(s.recompute)
                                for s in plan.layer_strategies),
        "num_layers": len(plan.layer_strategies),
        "num_microbatches": plan.num_microbatches,
        "micro_batch": getattr(plan, "micro_batch", None),
        "est_step_time_ms": round(plan.time * 1e3, 3),
        "layer_strategy_counts": dict(sts),
        "schedule_hang_free": not verify_plan_schedule(plan),
    }


class SearchEngine:
    """Search (pp, per-layer dp/tp/zero/ckpt) for a layer chain.

    ``layers`` describe per-micro-batch costs; ``global_batch`` /
    ``micro_batch`` set the schedule length per DP shard.
    """

    def __init__(self, cluster: ClusterSpec, layers: Sequence[LayerSpec],
                 global_batch: int, micro_batch: int,
                 mem_fraction: float = 0.9,
                 allow_recompute: bool = True,
                 allow_zero: bool = True,
                 max_tp: Optional[int] = None,
                 memory_calibration=None,
                 time_calibration=None):
        self.cluster = cluster
        self.layers = list(layers)
        self.global_batch = global_batch
        self.micro_batch = micro_batch
        self.mem_cap = cluster.chip.hbm_bytes * mem_fraction
        self.allow_recompute = allow_recompute
        self.allow_zero = allow_zero
        self.max_tp = max_tp or cluster.num_chips
        # analysis-backed memory model: a MemoryCalibration from
        # cost_model.calibrate_layer_memory scales every layer_memory
        # number the DP budget check sees, so the planner is constrained
        # by the same statically-validated model the CI gate pins
        self.memory_calibration = memory_calibration
        # analysis-backed time model, same stance: a TimeCalibration
        # from cost_model.calibrate_layer_time scales every layer_time
        # roofline the DP search scores, so candidate plans compete on
        # the counted-FLOP/HBM numbers the CI gate cross-checks against
        # XLA — not on an unvalidated closed form
        self.time_calibration = time_calibration

    def _layer_time(self, layer: LayerSpec, st: Strategy,
                    include_grad_sync: bool = False) -> float:
        return layer_time(layer, st, self.cluster,
                          include_grad_sync=include_grad_sync,
                          dp_splits_batch=False,
                          calibration=self.time_calibration)

    # -- candidate (dp, tp) decompositions of a stage's chips --------------

    def _layouts(self, chips: int) -> List[Tuple[int, int]]:
        out = []
        tp = 1
        while tp <= min(chips, self.max_tp):
            if chips % tp == 0:
                out.append((chips // tp, tp))
            tp *= 2
        return out

    def _mem_variants(self, dp: int, tp: int) -> List[Strategy]:
        """Per-layer choices for a fixed (dp, tp) layout: ZeRO stage and
        recompute flag — the per-layer degrees of freedom Galvatron's DP
        optimizes (sdp/ckpt columns of its strategy table)."""
        zeros = [0, 1, 2, 3] if (self.allow_zero and dp > 1) else [0]
        ckpts = [False, True] if self.allow_recompute else [False]
        return [Strategy(dp=dp, tp=tp, zero=z, recompute=ck)
                for z, ck in itertools.product(zeros, ckpts)]

    # -- main search -------------------------------------------------------

    def search(self, pp_options: Optional[Sequence[int]] = None
               ) -> PlanResult:
        total = self.cluster.total_chips
        if pp_options is None:
            pp_options = [p for p in (1, 2, 4, 8, 16, 32)
                          if p <= min(total, len(self.layers))
                          and total % p == 0]
        best: Optional[PlanResult] = None
        for pp in pp_options:
            plan = self._search_pp(pp)
            if plan is not None and (best is None or plan.time < best.time):
                best = plan
        if best is None:
            raise RuntimeError(
                "no feasible plan found: model does not fit in HBM under "
                "any searched configuration")
        return best

    def _search_pp(self, pp: int) -> Optional[PlanResult]:
        chips_per_stage = self.cluster.total_chips // pp
        best: Optional[PlanResult] = None
        for dp, tp in self._layouts(chips_per_stage):
            plan = self._search_layout(pp, dp, tp)
            if plan is not None and (best is None or plan.time < best.time):
                best = plan
        return best

    def _search_layout(self, pp: int, dp: int, tp: int
                       ) -> Optional[PlanResult]:
        """Evaluate one global (pp, dp, tp) decomposition; per-layer DP
        chooses the ZeRO stage + recompute flag under the HBM budget."""
        cands = self._mem_variants(dp, tp)
        L, S = len(self.layers), len(cands)
        if self.global_batch < self.micro_batch * dp:
            return None
        m = max(1, self.global_batch // (self.micro_batch * dp))

        # stage partition on per-micro-batch costs for this layout
        base = [self._layer_time(l, Strategy(dp=dp, tp=tp))
                for l in self.layers]
        comm = [l.boundary_bytes / self.cluster.chip.ici_bw
                for l in self.layers]
        try:
            _, stages = solve_pipeline_partition(base, pp, comm)
        except AssertionError:
            return None

        # per-stage DP over memory-saving variants under the HBM budget
        unit = self.mem_cap / MEM_UNITS
        strategies: List[Strategy] = [None] * L  # type: ignore
        stage_times = []
        for stage in stages:
            mem = np.zeros((len(stage), S), np.int32)
            intra = np.zeros((len(stage), S))
            inter = np.zeros((len(stage), S, S))  # same layout: no reshard
            for i, li in enumerate(stage):
                lay = self.layers[li]
                for s, st in enumerate(cands):
                    need = layer_memory(lay, st, self.cluster,
                                        num_microbatches=min(m, pp),
                                        dp_splits_batch=False,
                                        calibration=self.memory_calibration)
                    # over-budget layers stay infeasible (> inclusive cap)
                    mem[i, s] = min(MEM_UNITS + 1,
                                    int(math.ceil(need / unit)))
                    # per-micro-batch compute + the once-per-step grad
                    # sync amortized over the schedule length
                    intra[i, s] = self._layer_time(lay, st) \
                        + grad_sync_time(lay, st, self.cluster) / m
            cost, picks = solve_layer_strategies(mem, intra, inter,
                                                 MEM_UNITS)
            if picks is None:
                return None
            for i, li in enumerate(stage):
                strategies[li] = cands[picks[i]]
            stage_times.append(cost)

        boundary = max(l.boundary_bytes for l in self.layers)
        t = pipeline_time(stage_times, m, boundary, self.cluster)
        return PlanResult(time=t, pp=pp, stages=stages,
                          layer_strategies=strategies, num_microbatches=m,
                          cluster=self.cluster)
