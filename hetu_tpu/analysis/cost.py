"""Static per-executable step-time model: FLOP/HBM roofline + comm.

The third leg of the static-analysis tripod: PR 5 explains what a
program *communicates*, PR 8 what it *holds* — this module predicts how
long it *takes*, from the same registered facts and without running
anything:

* **FLOP inventory** (:func:`cost_walk`) — a recursive walk over the
  closed jaxpr prices every primitive: ``dot_general``/``conv`` by
  contraction-dimension math (``2·|out|·|contract|``), elementwise
  arithmetic at one FLOP per output element, reductions at one FLOP per
  *input* element, transcendentals (exp/tanh/erf/...) counted in a
  separate column exactly as XLA's ``HloCostAnalysis`` does, threefry
  RNG at a measured per-element constant.  ``scan`` bodies multiply by
  the trip count, ``shard_map`` regions already carry per-device block
  shapes (scale resets to 1), and everything outside a manual region is
  divided by the mesh size — a GSPMD-partitioned program computes
  ``1/prod(mesh)`` of the global math per device.
* **HBM-traffic inventory** — the operand + result bytes of
  *materializing* primitives (contractions, data movement, collectives,
  RNG — the ops XLA cannot fuse away; slices at 2× their output,
  gather/scatter at a calibrated utilization of their big operand),
  plus a fusion model for everything else: fusible elementwise runs are
  grouped into connected components (XLA's loop fusions) that pay one
  read per unique external operand and one write per escaping output,
  with multi-consumer fusible producers duplicated into each consumer
  fusion (:data:`FUSION_DUP_CAP`) exactly as XLA's fusion pass does.
* **roofline** — compute time = FLOPs / (peak·MXU-efficiency), IO time
  = HBM bytes / bandwidth, against a :class:`~hetu_tpu.planner
  .cost_model.ChipSpec` (datasheet or measured via
  ``profile_hardware``); the executable is compute- or HBM-bound by
  whichever dominates.
* **comm time** — the per-edge collective set ``predict_edges`` already
  derives is priced through the planner's alpha-beta formulas
  (:func:`~hetu_tpu.planner.cost_model.collective_time` — ONE
  implementation for the linter and the DP solver, so they can never
  disagree).  Edge payloads are wire bytes, so EQuARX-style int8/bf16
  transports are priced at their real wire cost.  The overlap model:
  when the plan's grad-comm config is overlap-schedulable
  (``meta["comm_overlap"]``, written at registration for the explicit
  coalesced sync), grad-comm/param-comm edges hide under compute
  (``max``), everything else is exposed (added).

**XLA cross-check** (:func:`xla_cost_stats` + ``CostReport.xla``): the
compiled executable's own ``cost_analysis()`` reports flops / bytes
accessed / transcendentals for the post-optimization module.  The
comparable numbers differ from the native prediction in documented
ways (DESIGN.md §16): XLA counts a ``while``/``scan`` **body once**
(not × trips), so ``cmp_flops``/``cmp_bytes`` are computed with trip
multiplication off (conditionals need no split convention — both the
execution truth and, verified empirically, XLA's accounting charge the
per-property **max** branch); the CPU backend upcasts bf16/f16 and
brackets every narrow-float boundary with converts (comparable FLOPs
add the convert storm, comparable bytes price narrow floats at the
store-width + compute-width round trip); and the partitioner's
collective lowering materializes ring intermediates the jaxpr cannot
see (:func:`collective_traffic_adjustment`).  The native numbers —
trips multiplied, one branch, native widths, no partitioner terms —
are what the planner and the baseline use.  The gate bounds
|cmp − XLA| at ±10% per gate family (absolute floors for toy-sized
programs where constant-factor ops dominate).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..planner.cost_model import (ChipSpec, ClusterSpec, collective_time)
from .jaxpr_walk import source_line

#: elementwise arithmetic: 1 FLOP per output element (XLA counts int
#: ops too, and select/compare chains count per op)
ELEMENTWISE_FLOP_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "neg", "abs",
    "sign", "floor", "ceil", "round", "clamp", "select_n", "and", "or",
    "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "lt", "le", "gt", "ge",
    "add_any", "convert_element_type", "is_finite", "nextafter",
    "integer_pow", "population_count", "clz", "exp2",
})

#: priced in XLA's separate ``transcendentals`` column, NOT flops
TRANSCENDENTAL_PRIMS = frozenset({
    "exp", "log", "log1p", "expm1", "sin", "cos", "tan", "asin", "acos",
    "atan", "atan2", "sinh", "cosh", "tanh", "asinh", "acosh", "atanh",
    "erf", "erfc", "erf_inv", "logistic", "sqrt", "rsqrt", "cbrt",
    "pow", "digamma", "lgamma",
})

#: reductions: 1 FLOP per INPUT element (n-1 combines + epilogue)
REDUCE_FLOP_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "argmax", "argmin", "cumsum", "cummax",
    "cummin", "cumprod", "reduce_window", "select_and_scatter_add",
    "cumlogsumexp",
})

#: measured on the CPU backend (jax.random.uniform ≈ 25.5 flops/elem,
#: of which ~2 are the convert/scale epilogue the walk prices itself)
THREEFRY_FLOPS_PER_ELEM = 24.0

#: CPU-comparable only: how many convert instances the CPU backend ends
#: up executing per narrow-float operand/output element (fusion
#: duplication re-converts a value inside every consuming fusion) —
#: calibrated once against the frozen bf16 gate families, same stance
#: as memory.RESIDUAL_POOL_CAP
CPU_CONVERT_DUP = 2.0

#: XLA's instruction fusion DUPLICATES a cheap fusible producer into
#: each consumer fusion instead of materializing it; a multi-consumer
#: elementwise op therefore executes (and is counted by cost_analysis)
#: once per consumer.  Capped: duplication stops paying off for wide
#: fan-outs and XLA materializes instead.
FUSION_DUP_CAP = 4

#: shape-only ops XLA lowers to bitcasts / layout changes: free, and
#: transparent to the fusion grouping (output aliases the input)
TRANSPARENT_PRIMS = frozenset({
    "reshape", "squeeze", "expand_dims", "bitcast_convert_type",
    "stop_gradient", "copy", "real", "imag", "broadcast",
    # layout changes the consumer absorbs (dots take transposed
    # operands natively; loop fusions index through the permutation)
    "transpose",
    # shard_map replication-rewrite markers: no data moves
    "pbroadcast", "pvary",
})

#: primitives whose outputs always materialize as real HBM buffers —
#: same classification the peak-HBM pass uses (memory.MATERIALIZE_PRIMS)
#: minus the containers (recursed here, never priced as one op)
MATERIALIZE_COST_PRIMS = frozenset({
    "dot_general", "conv_general_dilated", "scatter", "scatter-add",
    "scatter_add", "gather", "concatenate", "sort", "top_k", "cumsum",
    "psum", "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "ppermute", "pmax", "pmin", "rng_bit_generator", "threefry2x32",
    "dynamic_update_slice", "dynamic_slice", "slice",
    "argmax", "argmin", "select_and_scatter_add", "reduce_window",
})
# NB: pad/rev/reduce_* are FUSIBLE — XLA's loop fusion absorbs them in
# real programs (a standalone toy pad materializes, but that regime is
# covered by the absolute cross-check floor); their FLOPs still count
# via REDUCE_FLOP_PRIMS / elementwise pricing.

#: containers: recurse into sub-jaxprs, never price the eqn itself
CONTAINER_PRIMS = frozenset({
    "scan", "while", "cond", "pjit", "remat", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "shard_map", "closed_call", "core_call", "named_call", "custom_root",
    "custom_linear_solve",
})

#: CPU cross-check only: the CPU backend upcasts narrow floats to f32,
#: and the convert round-trip at every boundary touches the value both
#: at its 2-byte stored width and its 4-byte compute width — effective
#: ~6 bytes/element of counted traffic per boundary crossing
CMP_NARROW_WIDTH = {"bfloat16": 6, "float16": 6}

#: absolute cross-check floors: below these, CPU fusion-duplication
#: noise and constant-factor scalar ops (loop counters, rng keys,
#: layout fix-ups) dominate toy programs.  Honesty note: at CI scale
#: the FLOPS floor means the flops leg of the ±10% gate binds only for
#: families whose totals are well above 2 MFLOP (train/tp at ~30 MFLOP
#: bind for real; the 1-2 MFLOP moe/mpmd toys ride the floor) — the
#: BYTES leg binds for every family, and real-model-scale programs
#: clear the floor by orders of magnitude.
XLA_FLOPS_ABS_TOL = 2_000_000.0
XLA_BYTES_ABS_TOL = float(1 << 18)


# ---------------------------------------------------------------------------
# data model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CostEntry:
    """One attributed compute/traffic contributor (top-k table row)."""
    prim: str
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes: float = 0.0            # HBM traffic (per device)
    count: int = 1                # enclosing trip multiplier
    source: str = ""              # file:line provenance
    detail: str = ""              # shape slug

    def time_s(self, chip: ChipSpec) -> float:
        """Roofline contribution: max of this entry's MXU and HBM time
        (transcendentals priced as flops on the vector unit)."""
        fl = (self.flops + self.transcendentals) * self.count
        by = self.bytes * self.count
        return max(fl / (chip.peak_flops * chip.mxu_efficiency),
                   by / chip.hbm_bw)

    def to_dict(self) -> dict:
        return {"prim": self.prim, "flops": float(self.flops),
                "bytes": float(self.bytes), "count": int(self.count),
                "source": self.source, "detail": self.detail}


@dataclasses.dataclass
class CommCost:
    """One predicted collective edge, priced."""
    kind: str
    payload_bytes: int = 0
    count: int = 1
    group: int = 1                # chips in the collective group
    time_s: float = 0.0           # per execution
    overlapped: bool = False      # hides under compute in the overlap model
    origin: str = ""
    tensor: str = ""

    @property
    def total_s(self) -> float:
        return self.time_s * max(self.count, 1)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class CostReport:
    """Static step-time prediction for one executable (per device)."""
    name: str = ""
    # native inventory: trips multiplied, one cond branch, native widths
    flops: float = 0.0
    transcendentals: float = 0.0
    hbm_bytes: float = 0.0
    # XLA-comparable inventory: body-once, branches summed, CPU upcast
    cmp_flops: float = 0.0
    cmp_bytes: float = 0.0
    cmp_transcendentals: float = 0.0
    # roofline + comm decomposition
    compute_time_s: float = 0.0
    io_time_s: float = 0.0
    comm_time_s: float = 0.0           # total collective time
    overlapped_comm_s: float = 0.0     # hides under compute (max)
    exposed_comm_s: float = 0.0        # serial with compute (added)
    step_time_s: float = 0.0
    bound: str = "compute"             # compute|hbm|comm
    overlap: bool = False              # plan declares overlap scheduling
    chip: str = ""
    entries: List[CostEntry] = dataclasses.field(default_factory=list)
    comm: List[CommCost] = dataclasses.field(default_factory=list)
    # flops/bytes accessed/transcendentals from compiled.cost_analysis()
    xla: Optional[Dict[str, float]] = None

    def top(self, k: int = 10, chip: Optional[ChipSpec] = None
            ) -> List[CostEntry]:
        chip = chip or ChipSpec()
        return sorted(self.entries, key=lambda e: -e.time_s(chip))[:k]

    def by_prim(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for e in self.entries:
            d = out.setdefault(e.prim, {"flops": 0.0, "bytes": 0.0})
            d["flops"] += (e.flops + e.transcendentals) * e.count
            d["bytes"] += e.bytes * e.count
        return out

    # -- XLA cross-check ---------------------------------------------------

    def xla_flops_delta(self) -> Optional[float]:
        if self.xla is None:
            return None
        want = float(self.xla.get("flops", 0.0)) \
            + float(self.xla.get("transcendentals", 0.0))
        got = self.cmp_flops + self.cmp_transcendentals
        if want <= 0:
            return None
        return (got - want) / want

    def xla_bytes_delta(self) -> Optional[float]:
        if self.xla is None:
            return None
        want = float(self.xla.get("bytes_accessed", 0.0))
        if want <= 0:
            return None
        return (self.cmp_bytes - want) / want

    def xla_within(self, rel: float = 0.1,
                   flops_floor: float = XLA_FLOPS_ABS_TOL,
                   bytes_floor: float = XLA_BYTES_ABS_TOL
                   ) -> Optional[bool]:
        """Both totals inside ±rel of XLA's (None: not compiled)."""
        if self.xla is None:
            return None
        want_f = float(self.xla.get("flops", 0.0)) \
            + float(self.xla.get("transcendentals", 0.0))
        got_f = self.cmp_flops + self.cmp_transcendentals
        ok_f = abs(got_f - want_f) <= max(rel * want_f, flops_floor)
        want_b = float(self.xla.get("bytes_accessed", 0.0))
        ok_b = abs(self.cmp_bytes - want_b) \
            <= max(rel * want_b, bytes_floor)
        return bool(ok_f and ok_b)

    def to_dict(self, entries: bool = False) -> dict:
        d: Dict[str, Any] = {
            "flops": int(self.flops),
            "transcendentals": int(self.transcendentals),
            "hbm_bytes": int(self.hbm_bytes),
            "compute_time_us": round(self.compute_time_s * 1e6, 3),
            "io_time_us": round(self.io_time_s * 1e6, 3),
            "comm_time_us": round(self.comm_time_s * 1e6, 3),
            "step_time_us": round(self.step_time_s * 1e6, 3),
            "bound": self.bound,
            "overlap": bool(self.overlap),
            "chip": self.chip,
        }
        if self.xla is not None:
            fd, bd = self.xla_flops_delta(), self.xla_bytes_delta()
            d["xla_flops"] = int(self.xla.get("flops", 0)
                                 + self.xla.get("transcendentals", 0))
            d["xla_bytes_accessed"] = int(self.xla.get(
                "bytes_accessed", 0))
            d["xla_flops_delta_pct"] = round(100.0 * fd, 1) \
                if fd is not None else None
            d["xla_bytes_delta_pct"] = round(100.0 * bd, 1) \
                if bd is not None else None
        if entries:
            d["top_entries"] = [e.to_dict() for e in self.top(10)]
            d["comm"] = [c.to_dict() for c in self.comm]
        return d

    def summary(self) -> str:
        s = (f"{_fmt_si(self.flops)}FLOP "
             f"{_fmt_si(self.hbm_bytes)}B -> "
             f"{self.step_time_s * 1e6:.1f}us "
             f"({self.bound}-bound: compute "
             f"{self.compute_time_s * 1e6:.1f}us, hbm "
             f"{self.io_time_s * 1e6:.1f}us, comm "
             f"{self.comm_time_s * 1e6:.1f}us"
             + (" overlapped" if self.overlap and self.comm_time_s
                else "") + ")")
        fd = self.xla_flops_delta()
        bd = self.xla_bytes_delta()
        if fd is not None or bd is not None:
            s += (f" (xla flops {fd:+.1%}, bytes {bd:+.1%})"
                  if fd is not None and bd is not None else " (xla n/a)")
        return s


def _fmt_si(n: float) -> str:
    n = float(n)
    for unit in ("", "K", "M", "G", "T"):
        if abs(n) < 1000.0 or unit == "T":
            return f"{n:.1f}{unit}" if unit else f"{n:.0f}"
        n /= 1000.0
    return f"{n:.1f}T"


# ---------------------------------------------------------------------------
# the jaxpr FLOP/HBM walk
# ---------------------------------------------------------------------------


def _as_jaxpr(j):
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _sub_jaxprs(eqn):
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if hasattr(v, "eqns"):
                yield v
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr


def _elems(aval) -> float:
    try:
        return float(np.prod(aval.shape, dtype=np.int64))
    except Exception:
        return 0.0


def _aval_bytes(aval, upcast: bool) -> float:
    try:
        dt = np.dtype(aval.dtype)
        item = CMP_NARROW_WIDTH.get(dt.name, dt.itemsize) if upcast \
            else dt.itemsize
        return _elems(aval) * item
    except Exception:
        return 0.0


def _is_narrow_float(aval) -> bool:
    try:
        return np.dtype(aval.dtype).name in CMP_NARROW_WIDTH
    except Exception:
        return False


def dot_general_flops(eqn) -> float:
    """``2 · |out| · |contracting dims|`` from the dimension numbers —
    the exact count XLA's cost analysis reports for a dot."""
    try:
        (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval
        out = eqn.outvars[0].aval
        contract = 1.0
        for d in lhs_c:
            contract *= float(lhs.shape[d])
        return 2.0 * _elems(out) * contract
    except Exception:
        return 0.0


def conv_flops(eqn) -> float:
    """``2 · |out| · kernel_spatial · in_channels / groups``."""
    try:
        rhs = eqn.invars[1].aval
        out = eqn.outvars[0].aval
        dn = eqn.params["dimension_numbers"]
        groups = float(eqn.params.get("feature_group_count", 1) or 1)
        k_spatial = 1.0
        for d in dn.rhs_spec[2:]:
            k_spatial *= float(rhs.shape[d])
        in_ch = float(rhs.shape[dn.rhs_spec[1]])
        return 2.0 * _elems(out) * k_spatial * in_ch / max(groups, 1.0)
    except Exception:
        return 0.0


def _prim_flops(eqn) -> Tuple[float, float]:
    """(flops, transcendentals) of one non-container eqn."""
    name = eqn.primitive.name
    if name == "dot_general":
        return dot_general_flops(eqn), 0.0
    if name == "conv_general_dilated":
        return conv_flops(eqn), 0.0
    out_elems = sum(_elems(ov.aval) for ov in eqn.outvars
                    if hasattr(ov, "aval"))
    if name in TRANSCENDENTAL_PRIMS:
        return 0.0, out_elems
    if name in ELEMENTWISE_FLOP_PRIMS:
        return out_elems, 0.0
    if name in REDUCE_FLOP_PRIMS:
        in_elems = sum(_elems(iv.aval) for iv in eqn.invars
                       if hasattr(iv, "aval"))
        return in_elems, 0.0
    if name in ("threefry2x32", "rng_bit_generator"):
        return THREEFRY_FLOPS_PER_ELEM * out_elems, 0.0
    if name in ("psum", "pmax", "pmin", "psum_scatter",
                "reduce_scatter"):
        return out_elems, 0.0
    return 0.0, 0.0


@dataclasses.dataclass
class _WalkTotals:
    flops: float = 0.0
    transcendentals: float = 0.0
    bytes: float = 0.0
    entries: List[CostEntry] = dataclasses.field(default_factory=list)

    def add(self, other: "_WalkTotals", mult: float = 1.0) -> None:
        self.flops += other.flops * mult
        self.transcendentals += other.transcendentals * mult
        self.bytes += other.bytes * mult
        for e in other.entries:
            self.entries.append(dataclasses.replace(
                e, count=int(max(1, round(e.count * mult)))))


def cost_walk(jaxpr, scale: float = 1.0, upcast: bool = False,
              multiply_trips: bool = True) -> _WalkTotals:
    """FLOP + HBM-traffic inventory of one (sub-)jaxpr.

    ``scale`` divides global aval costs down to per-device (GSPMD
    partitioning over the whole mesh); inside ``shard_map`` regions the
    avals are already per-device block shapes, so the scale resets to 1.
    ``multiply_trips`` toggles the native (× scan length) vs
    XLA-comparable (body once) convention.  ``cond`` charges the most
    expensive branch — both the execution truth (one branch runs) and
    XLA's convention (cost_analysis takes the per-property max over
    branch computations, verified empirically).

    The traffic model groups *fusible* eqns into connected components
    (a var produced by a fusible eqn and consumed by another fuses
    them) and prices each component once: unique external reads +
    escaping writes — the post-fusion ``bytes accessed`` convention.
    Materializing prims pay their full operand + result bytes
    (gather/scatter read the WHOLE operand, XLA's convention).
    """
    j = _as_jaxpr(jaxpr)
    out = _WalkTotals()

    # fusion components: var id -> component id for fusible-produced vars
    comp_of_var: Dict[int, int] = {}
    comp_reads: Dict[int, Dict[int, float]] = {}   # comp -> var id -> bytes
    comp_writes: Dict[int, float] = {}
    comp_src: Dict[int, str] = {}
    parent: Dict[int, int] = {}

    def find(c: int) -> int:
        while parent.get(c, c) != c:
            parent[c] = parent.get(parent[c], parent[c])
            c = parent[c]
        return c

    def union(a: int, b: int) -> int:
        ra, rb = find(a), find(b)
        if ra == rb:
            return ra
        parent[rb] = ra
        comp_reads.setdefault(ra, {}).update(comp_reads.pop(rb, {}))
        comp_writes[ra] = comp_writes.get(ra, 0.0) \
            + comp_writes.pop(rb, 0.0)
        return ra

    next_comp = [0]

    # transparent aliasing: reshape-like output vars point back at the
    # var they are a view of, so fusion grouping sees through them.
    # Built in a pre-pass so the consumer map below can attribute a
    # use THROUGH a reshape to the underlying var.
    alias: Dict[int, int] = {}
    for eqn in j.eqns:
        if _classify(eqn) == "transparent" and eqn.invars \
                and eqn.outvars and hasattr(eqn.invars[0], "count"):
            for ov in eqn.outvars:
                alias[id(ov)] = id(eqn.invars[0])

    def resolve(v) -> int:
        i = id(v)
        while i in alias:
            i = alias[i]
        return i

    # jaxpr outputs, seen through trailing reshapes/transposes: a value
    # that escapes via a transparent view still pays its fusion write
    outvar_ids = {resolve(v) for v in j.outvars if hasattr(v, "count")}

    # a fusible var consumed by a materializing/container eqn (or
    # escaping the jaxpr) forces its component to write it out.  Keyed
    # on RESOLVED ids (a use through a reshape is a use of the source)
    # and deduped per consuming eqn (x*x is ONE consumer, not two).
    consumers: Dict[int, List[str]] = {}
    for eqn in j.eqns:
        cls = _classify(eqn)
        if cls == "transparent":
            continue        # forwards its uses; not a consumer itself
        for ri in {resolve(iv) for iv in eqn.invars
                   if hasattr(iv, "count")}:
            consumers.setdefault(ri, []).append(cls)

    # repeated 1-D narrow-float unpack slices (the flat param-gather's
    # per-param dynamic_slice fan-out under ZeRO-3): when the sliced
    # values are consumed IN-program, XLA's fusion pass hoists the
    # operand's bf16<->f32 convert above the slices and duplicates the
    # FULL-buffer convert into every consuming fusion — visible in the
    # optimized HLO as one buffer-wide convert pair per unpacked param.
    # Charged per slice beyond the first on the same operand (ZeRO-2's
    # unpack escapes as plan outputs — zero consumers, zero charge).
    unpack_seen: Dict[int, int] = {}

    for eqn in j.eqns:
        name = eqn.primitive.name
        cls = _classify(eqn)
        src = None

        if cls == "transparent":
            continue        # aliased in the pre-pass: free, see-through

        if cls == "container":
            mult = 1.0
            if name == "scan" and multiply_trips:
                mult = float(eqn.params.get("length", 1) or 1)
            sub_scale = 1.0 if name == "shard_map" else scale
            subs = [cost_walk(s, sub_scale, upcast, multiply_trips)
                    for s in _sub_jaxprs(eqn)]
            if not subs:
                continue
            if name == "cond":
                # one branch executes — charge the costliest (matches
                # XLA's max-over-branches conditional accounting)
                best = max(subs, key=lambda t: (t.flops
                                                + t.transcendentals,
                                                t.bytes))
                out.add(best, mult)
            else:
                for t in subs:
                    out.add(t, mult)
            continue

        flops, trans = _prim_flops(eqn)
        flops *= scale
        trans *= scale
        if upcast:
            # CPU-comparable only: the CPU backend has no native
            # bf16/f16 and brackets every narrow-float operand read and
            # output write with a convert (~1 FLOP per element, times a
            # fusion-duplication factor — XLA's instruction fusion
            # re-converts a value inside every fusion that consumes it;
            # the convert-instruction storm visible in any bf16
            # module's optimized HLO, counted in XLA's `flops`)
            conv_elems = (
                sum(_elems(iv.aval) for iv in eqn.invars
                    if _is_narrow_float(getattr(iv, "aval", None)))
                + sum(_elems(ov.aval) for ov in eqn.outvars
                      if _is_narrow_float(getattr(ov, "aval", None))))
            flops += CPU_CONVERT_DUP * conv_elems * scale
        if cls == "materialize":
            if name in ("dynamic_slice", "slice"):
                # XLA prices slices at output read+write, NOT the full
                # operand (unlike gather, which walks the whole thing)
                nb = 2.0 * sum(_aval_bytes(ov.aval, upcast)
                               for ov in eqn.outvars
                               if hasattr(ov, "aval")) * scale
                big_av = getattr(eqn.invars[0], "aval", None) \
                    if eqn.invars and hasattr(eqn.invars[0], "count") \
                    else None
                if (upcast and big_av is not None
                        and _is_narrow_float(big_av)
                        and len(getattr(big_av, "shape", ())) == 1
                        and any(consumers.get(resolve(ov))
                                for ov in eqn.outvars
                                if hasattr(ov, "count"))):
                    key = resolve(eqn.invars[0])
                    if key in unpack_seen:
                        # both widths of the hoisted buffer convert,
                        # duplicated into this consumer's fusion
                        dup = 2.0 * CPU_CONVERT_DUP * _elems(big_av) \
                            * scale
                        flops += dup
                        nb += dup
                    unpack_seen[key] = unpack_seen.get(key, 0) + 1
            elif name in ("gather", "scatter", "scatter-add",
                          "scatter_add") and eqn.invars:
                # big operand at the calibrated fusion utilization;
                # indices/updates/outputs at full width
                big = _aval_bytes(eqn.invars[0].aval, upcast) \
                    if hasattr(eqn.invars[0], "aval") else 0.0
                rest = sum(_aval_bytes(iv.aval, upcast)
                           for iv in eqn.invars[1:]
                           if hasattr(iv, "aval"))
                outs = sum(_aval_bytes(ov.aval, upcast)
                           for ov in eqn.outvars
                           if hasattr(ov, "aval"))
                if name == "gather":
                    # XLA: operand read (utilization-weighted when
                    # fused) + indices + output written once.  A gather
                    # whose consumers all fuse is absorbed INTO the
                    # consumer loop fusion — its output never
                    # materializes (the consuming component's external
                    # read below stands in for the single pass).
                    absorbed = all(
                        c == "fusible"
                        for ov in eqn.outvars if hasattr(ov, "count")
                        for c in consumers.get(resolve(ov), ())) and any(
                        consumers.get(resolve(ov))
                        for ov in eqn.outvars if hasattr(ov, "count"))
                    nb = (SCATTER_GATHER_UTIL * big + rest
                          + (0.0 if absorbed else outs)) * scale
                else:
                    # scatter reads AND rewrites through the big
                    # operand in place (the output aliases it)
                    nb = (SCATTER_GATHER_UTIL * 2.0 * big + rest) \
                        * scale
            else:
                nb = (sum(_aval_bytes(iv.aval, upcast)
                          for iv in eqn.invars if hasattr(iv, "aval"))
                      + sum(_aval_bytes(ov.aval, upcast)
                            for ov in eqn.outvars
                            if hasattr(ov, "aval"))) * scale
            src = source_line(eqn)
            out.flops += flops
            out.transcendentals += trans
            out.bytes += nb
            if flops or trans or nb:
                shape = ""
                if eqn.outvars and hasattr(eqn.outvars[0], "aval"):
                    shape = str(getattr(eqn.outvars[0].aval, "shape", ""))
                out.entries.append(CostEntry(
                    prim=name, flops=flops, transcendentals=trans,
                    bytes=nb, source=src, detail=shape))
            continue

        # fusible: flops count, traffic via the fusion component model.
        # Multi-consumer outputs are DUPLICATED by XLA's fusion pass
        # (recomputed inside each consumer fusion), so the op executes
        # — and cost_analysis counts it — once per consumer.
        n_cons = max((len(consumers.get(resolve(ov), ()))
                      for ov in eqn.outvars if hasattr(ov, "count")),
                     default=1)
        dup = min(FUSION_DUP_CAP, max(1, n_cons))
        flops *= dup
        trans *= dup
        out.flops += flops
        out.transcendentals += trans
        comp = next_comp[0]
        next_comp[0] += 1
        joined = comp
        for iv in eqn.invars:
            if not hasattr(iv, "count"):
                continue
            ri = resolve(iv)
            # fuse with the producer only when we are its SOLE
            # consumer — a multi-consumer fusible var is either
            # duplicated (flops above) or materialized (its producer
            # component writes it; we read it externally below)
            if ri in comp_of_var and len(consumers.get(ri, ())) <= 1:
                joined = union(joined, comp_of_var[ri])
        joined = find(joined)
        if flops or trans:
            comp_src.setdefault(joined, source_line(eqn))
        for iv in eqn.invars:
            if not hasattr(iv, "count"):
                continue
            ri = resolve(iv)
            # external operand: a fusion read — either a var no fusible
            # eqn produced, or one produced in a DIFFERENT component
            # (the multi-consumer case above, where union was refused
            # and the producer writes it out)
            if ri not in comp_of_var or find(comp_of_var[ri]) != joined:
                comp_reads.setdefault(joined, {})[ri] = \
                    _aval_bytes(iv.aval, upcast) * scale
        for ov in eqn.outvars:
            if not hasattr(ov, "count"):
                continue
            comp_of_var[id(ov)] = joined
            ov_id = id(ov)
            esc = ov_id in outvar_ids or any(
                c != "fusible" for c in consumers.get(ov_id, ())) \
                or len(consumers.get(ov_id, ())) > 1
            if esc:                       # escaping output: fusion write
                comp_writes[joined] = comp_writes.get(joined, 0.0) \
                    + _aval_bytes(ov.aval, upcast) * scale
        if flops or trans:
            out.entries.append(CostEntry(
                prim=name, flops=flops, transcendentals=trans,
                bytes=0.0, source=comp_src.get(joined, "")))

    # settle the fusion components: one read per unique external var,
    # one write per escaping output
    roots = {find(c) for c in
             set(comp_reads) | set(comp_writes) | set(
                 comp_of_var.values())}
    fusion_bytes = 0.0
    for r in roots:
        reads = comp_reads.get(r, {})
        nb = sum(reads.values()) + comp_writes.get(r, 0.0)
        fusion_bytes += nb
        if nb:
            out.entries.append(CostEntry(
                prim="fusion", bytes=nb, source=comp_src.get(r, "")))
    out.bytes += fusion_bytes
    return out


def _classify(eqn) -> str:
    name = eqn.primitive.name
    if name in CONTAINER_PRIMS:
        return "container"
    if name in TRANSPARENT_PRIMS:
        return "transparent"
    if name in MATERIALIZE_COST_PRIMS:
        return "materialize"
    return "fusible"


# ---------------------------------------------------------------------------
# comm pricing over the predicted edge set
# ---------------------------------------------------------------------------


def price_edges(edges, mesh_axes: Dict[str, int],
                cluster: ClusterSpec,
                overlap_origins: frozenset = frozenset()
                ) -> List[CommCost]:
    """Alpha-beta time of every predicted comm edge, through the SAME
    :func:`~hetu_tpu.planner.cost_model.collective_time` formulas the
    planner's DP solver prices plans with.  Edge payloads are wire
    bytes (transport dtype already applied), so quantized transports
    cost their real narrow width."""
    out: List[CommCost] = []
    for e in edges or ():
        if e.kind in ("identity", "scatter"):
            continue
        n = 1
        for a in e.axes:
            n *= int(mesh_axes.get(str(a), 1))
        if n <= 1 and not e.axes:
            # axis-less declared edge: assume the whole mesh
            for s in mesh_axes.values():
                n *= int(s)
        t = collective_time(e.kind, float(e.payload_bytes), n, cluster)
        out.append(CommCost(
            kind=e.kind, payload_bytes=int(e.payload_bytes),
            count=int(max(e.count, 1)), group=n, time_s=float(t),
            overlapped=e.origin in overlap_origins,
            origin=e.origin, tensor=e.tensor))
    return out


#: edge origins the overlap model may hide under compute when the plan
#: declares overlap scheduling: the coalesced grad sync and its
#: sidecars/param regather are bucketed exactly so the latency-hiding
#: scheduler can run them behind the backward/update math; the ZeRO-3
#: just-in-time weight gather (param_gather) is per-bucket for the same
#: reason — bucket b+1's gather overlaps bucket b's forward compute
OVERLAPPABLE_ORIGINS = frozenset({"grad_comm", "param_comm",
                                  "param_gather"})


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


#: HLO dtype slug -> byte width (collective-traffic parsing)
_HLO_WIDTH = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8,
              "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
              "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLLECTIVE_PRIM_NAMES = frozenset({
    "psum", "pmax", "pmin", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter", "ppermute",
})

_HLO_COLLECTIVE_RE = None


#: how many extra buffer passes the ring lowering of one collective
#: materializes per ring step beyond the plain read+write: XLA
#: decomposes big all-gathers/all-reduces into (group−1) permute +
#: concat/accumulate rounds whose growing intermediates all count in
#: ``bytes accessed``.  Calibrated once against the frozen gate
#: families (same stance as memory.RESIDUAL_POOL_CAP); GSPMD-inserted
#: collectives decompose harder than explicit shard_map ones (the
#: partitioner adds halo/copy fix-ups around its own inserts).
RING_OVERHEAD_EXPLICIT = 1.0
RING_OVERHEAD_GSPMD = 2.0

#: fraction of a gather/scatter's LARGE operand XLA's fusion pricing
#: charges: a standalone gather reads its whole operand (toy-verified),
#: but real programs fuse the gather and HloCostAnalysis weights the
#: operand by utilization (≈ the gathered window).  One calibrated
#: blend for both regimes; indices/updates/outputs always price full.
SCATTER_GATHER_UTIL = 0.25


_HLO_KIND = {"all-reduce": "all_reduce", "all-gather": "all_gather",
             "all-to-all": "all_to_all",
             "reduce-scatter": "reduce_scatter",
             "collective-permute": "ppermute"}

_PRIM_KIND = {"psum": "all_reduce", "pmax": "all_reduce",
              "pmin": "all_reduce", "all_gather": "all_gather",
              "all_to_all": "all_to_all",
              "reduce_scatter": "reduce_scatter",
              "psum_scatter": "reduce_scatter", "ppermute": "ppermute"}


def collective_traffic_adjustment(hlo_text: str, walk_entries) -> float:
    """Extra comparable ``bytes accessed`` from the compiled module's
    collective lowering, beyond what the jaxpr walk already priced.

    Per collective kind: GSPMD-*inserted* instructions (those beyond
    the walk's explicit count) pay their read+write (the walk never saw
    them), and EVERY instruction pays the ring-lowering overhead —
    ``(group − 1)`` extra buffer passes for the permute/concat rounds
    of the decomposition, at :data:`RING_OVERHEAD_EXPLICIT` /
    :data:`RING_OVERHEAD_GSPMD`.

    Used ONLY for the XLA-*comparable* byte total: GSPMD-inserted
    collectives (implicit resharding on tp/sp meshes) materialize
    buffers the pre-partitioning jaxpr cannot see, exactly as the CPU
    bf16 upcast inserts converts the program never wrote.  Their
    *counts* are already pinned by the baseline and explained by the
    edge pass, so sizing them from the module under comparison adds no
    un-gated freedom — the walk's own (static) traffic remains the
    number the planner and the native report use.
    """
    import re
    from collections import defaultdict
    instrs = defaultdict(list)
    pat = re.compile(
        r"= *(\w+)\[([\d,]*)\][^ ]* (all-reduce|all-gather|"
        r"all-to-all|reduce-scatter|collective-permute)"
        r"(?:-start)?\(([^\n]*)")
    for m in pat.finditer(hlo_text):
        dt, sh, op, rest = m.groups()
        nb = 1
        for x in sh.split(","):
            if x:
                nb *= int(x)
        nb *= _HLO_WIDTH.get(dt, 4)
        if op == "collective-permute":
            group = 2
        else:
            group = 1
            g = re.search(r"replica_groups=\{\{([\d,]+)\}", rest)
            if g:
                group = g.group(1).count(",") + 1
            else:
                g = re.search(r"replica_groups=\[(\d+),(\d+)\]", rest)
                if g:
                    group = int(g.group(2))
        instrs[_HLO_KIND[op]].append((float(nb), group))
    explicit = defaultdict(int)
    for e in walk_entries:
        k = _PRIM_KIND.get(e.prim)
        if k:
            explicit[k] += e.count
    total = 0.0
    for k, lst in instrs.items():
        n_k = len(lst)
        fe = min(explicit.get(k, 0), n_k) / n_k if n_k else 0.0
        base2 = sum(2.0 * nb for nb, _g in lst)
        ring = sum(nb * max(0, g - 1) for nb, g in lst)
        total += (1.0 - fe) * base2 \
            + fe * RING_OVERHEAD_EXPLICIT * ring \
            + (1.0 - fe) * RING_OVERHEAD_GSPMD * ring
    return total


def xla_cost_stats(handle) -> Optional[Dict[str, float]]:
    """flops / bytes accessed / transcendentals from the compiled
    executable's own ``cost_analysis()`` (None when unavailable)."""
    try:
        ca = handle.compile().cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if ca is None:
        return None
    try:
        return {
            "flops": float(ca.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0) or 0.0),
            "transcendentals": float(ca.get("transcendentals", 0.0)
                                     or 0.0),
        }
    except Exception:
        return None


def predict_cost(handle, cluster: Optional[ClusterSpec] = None,
                 xla: bool = False) -> CostReport:
    """The static step-time model for one registered executable.

    ``step = max(compute_roofline, hbm_roofline, overlapped_comm)
           + exposed_comm``

    where the rooflines come from the jaxpr FLOP/HBM walk over
    ``cluster.chip`` (datasheet v5p by default; pass a
    ``profile_hardware``-calibrated cluster for measured numbers) and
    the comm terms from the predicted edge set priced through the
    planner's shared alpha-beta formulas.  With ``xla=True`` the
    compiled executable's ``cost_analysis()`` is attached for the
    cross-check (compiles on first call — the gate already pays this
    for GSPMD accounting).
    """
    from .edges import makes_edge_claim, predict_edges

    meta = handle.meta
    mesh_axes = {str(a): int(s)
                 for a, s in (meta.get("mesh_axes") or {}).items()}
    train = bool(meta.get("train", meta.get("kind") == "train_step"))
    cluster = cluster or ClusterSpec(
        num_chips=max(1, int(np.prod(list(mesh_axes.values()))
                             if mesh_axes else 1)))
    chip = cluster.chip

    gspmd_scale = 1.0
    for s in mesh_axes.values():
        gspmd_scale *= max(int(s), 1)
    scale = 1.0 / gspmd_scale

    rep = CostReport(name=handle.name, chip=chip.name)
    jaxpr = handle.jaxpr
    native = cost_walk(jaxpr, scale=scale, upcast=False,
                       multiply_trips=True)
    rep.flops = native.flops
    rep.transcendentals = native.transcendentals
    rep.hbm_bytes = native.bytes
    rep.entries = native.entries

    import jax
    upcast = jax.default_backend() == "cpu"
    cmp = cost_walk(jaxpr, scale=scale, upcast=upcast,
                    multiply_trips=False)
    rep.cmp_flops = cmp.flops
    rep.cmp_bytes = cmp.bytes
    rep.cmp_transcendentals = cmp.transcendentals

    rep.compute_time_s = (rep.flops + rep.transcendentals) \
        / (chip.peak_flops * chip.mxu_efficiency)
    rep.io_time_s = rep.hbm_bytes / chip.hbm_bw

    rep.overlap = bool(meta.get("comm_overlap", False))
    if makes_edge_claim(meta):
        edges = predict_edges(meta, mesh_axes, train)
        rep.comm = price_edges(
            edges, mesh_axes, cluster,
            overlap_origins=OVERLAPPABLE_ORIGINS if rep.overlap
            else frozenset())
    rep.comm_time_s = sum(c.total_s for c in rep.comm)
    rep.overlapped_comm_s = sum(c.total_s for c in rep.comm
                                if c.overlapped)
    rep.exposed_comm_s = rep.comm_time_s - rep.overlapped_comm_s

    roofline = max(rep.compute_time_s, rep.io_time_s)
    rep.step_time_s = max(roofline, rep.overlapped_comm_s) \
        + rep.exposed_comm_s
    if rep.exposed_comm_s > roofline:
        rep.bound = "comm"
    elif rep.io_time_s > rep.compute_time_s:
        rep.bound = "hbm"
    else:
        rep.bound = "compute"

    if xla:
        rep.xla = xla_cost_stats(handle)
        if rep.xla is not None:
            # comparable-only partitioner adjustment (docstring of
            # collective_traffic_adjustment): the GSPMD-materialized
            # collective traffic the jaxpr cannot see
            try:
                rep.cmp_bytes += collective_traffic_adjustment(
                    handle.compiled_text(), cmp.entries)
            except Exception:
                pass
    return rep
