"""Static per-executable peak-HBM model + XLA cross-check.

The collectives side of the analyzer (PR 3/5) statically explains 100%
of what a program *communicates*; this module does the same for what it
*holds*.  Every prediction is computed from facts the registry already
carries — no execution, no profiling:

* **resident state** — every argument leaf of the lowered program,
  sharded down by its registered divisor (param pspecs from the graph,
  the flat optimizer buffers' ``P(dp)`` layout, feed pspecs), classified
  as ``param`` / ``opt-state`` / ``grad`` / ``feed`` / ``kv-page``
  (serving-pool page arrays, recognized through the pool snapshot hook).
* **activation liveness** — a last-use interval walk over the closed
  jaxpr (:func:`liveness_walk`): buffers allocate at their defining eqn
  and free after their last consumer; scan body temporaries peak once
  (not × trips) and the final carry aliases the running carry buffer;
  remat regions need no special casing because the walk runs on the
  *post-AD* jaxpr, where rematerialization has already replaced the
  saved-activation intervals it eliminates.
* **donation-aware outputs** — donated input leaves are matched to
  output leaves by (shape, dtype); only the unmatched output bytes cost
  new HBM (XLA writes the rest in place, exactly what its alias table
  reports).

The sum is a :class:`MemoryReport`: peak bytes, a per-kind breakdown,
and an attribution table of the top contributors with file:line
provenance for activations.

**XLA cross-check** (:func:`xla_memory_stats` + ``MemoryReport.xla``):
the same compiled executable the GSPMD accounting already builds exposes
``compiled.memory_analysis()`` — argument/output/temp/alias bytes.  The
mapping is component-wise: resident ↔ ``argument``, unmatched outputs ↔
``output − alias``, activation peak ↔ ``temp``.  Two documented,
platform-only adjustments apply to the *comparable* number
(``cmp_peak_bytes``), never to the native prediction the planner and
the baseline use:

* CPU has no native bf16/f16 — XLA upcasts narrow-float intermediates
  to f32 buffers, so the cross-check counts them at 4 bytes;
* sub-64KB programs are alignment/fragmentation-dominated, so the gate
  tolerance has a small absolute floor.

Why XLA can still differ (DESIGN.md §14): fusion eliminates most
elementwise intermediates (the walk materializes only
:data:`MATERIALIZE_PRIMS` outputs), but XLA *keeps* a bounded set of
small long-lived fusible values (attention probabilities, norm
statistics) instead of recomputing them in their far-away backward
consumers — modeled by the capped residual pool
(:data:`RESIDUAL_FAR_EQNS` / :data:`RESIDUAL_SMALL_BYTES` /
:data:`RESIDUAL_POOL_CAP`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .jaxpr_walk import source_line

#: primitives whose outputs always materialize as real buffers (XLA
#: cannot fuse them away): contractions, data movement, collectives,
#: control-flow containers, reductions.  Everything else is assumed
#: fused into its consumer.
MATERIALIZE_PRIMS = frozenset({
    "dot_general", "conv_general_dilated", "scatter", "scatter-add",
    "scatter_add", "gather", "concatenate", "sort", "top_k", "cumsum",
    "psum", "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "ppermute", "pmax", "pmin", "rng_bit_generator", "threefry2x32",
    "scan", "while", "cond", "custom_vjp_call", "custom_jvp_call",
    "pjit", "remat", "remat2", "checkpoint", "shard_map",
    "dynamic_update_slice", "pad", "rev", "dynamic_slice",
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "argmax", "argmin", "reduce_and", "reduce_or", "add_any",
    "select_and_scatter_add", "reduce_window",
})

#: primitives XLA runs in place when the operand dies at the eqn: the
#: output reuses the input buffer (same-size collectives, DUS/scatter).
INPLACE_PRIMS = frozenset({
    "dynamic_update_slice", "scatter", "scatter_add", "scatter-add",
    "psum", "pmax", "pmin", "ppermute", "all_to_all",
})

#: residual-pool model: a *fusible* value consumed more than
#: RESIDUAL_FAR_EQNS equations after its definition and no larger than
#: RESIDUAL_SMALL_BYTES (post-sharding) is a candidate XLA materializes
#: rather than recomputes; the pool's live total is capped at
#: RESIDUAL_POOL_CAP x the materialized live set (XLA keeps *some* of
#: them, never all — calibrated once against the frozen gate families).
RESIDUAL_FAR_EQNS = 8
RESIDUAL_SMALL_BYTES = 8192
RESIDUAL_POOL_CAP = 0.3

#: CPU cross-check only: XLA's CPU backend has no native bf16/f16 and
#: materializes intermediates as f32.
NARROW_FLOAT_WIDTH = {"bfloat16": 4, "float16": 4}

#: absolute tolerance floor for the XLA cross-check: below this,
#: buffer-assignment alignment and fragmentation dominate.
XLA_ABS_TOLERANCE = 1 << 16


# ---------------------------------------------------------------------------
# data model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MemoryBuffer:
    """One attributed HBM contributor."""
    kind: str                 # param|opt-state|grad|feed|kv-page|
    #                           activation|output|input
    name: str                 # param name / arg path / primitive
    nbytes: int               # per-device bytes (sharding applied)
    source: str = ""          # file:line provenance (activations)
    detail: str = ""          # shape/dtype slug

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class MemoryReport:
    """Static peak-HBM prediction for one executable."""
    name: str = ""
    peak_bytes: int = 0            # native dtype widths (the TPU truth)
    cmp_peak_bytes: int = 0        # platform-comparable (CPU upcast)
    resident_bytes: int = 0
    activation_peak_bytes: int = 0
    output_extra_bytes: int = 0    # outputs no donated input absorbs
    by_kind: Dict[str, int] = dataclasses.field(default_factory=dict)
    buffers: List[MemoryBuffer] = dataclasses.field(default_factory=list)
    # XLA cross-check: argument/output/temp/alias/total bytes from
    # compiled.memory_analysis(), or None when not compiled
    xla: Optional[Dict[str, int]] = None

    def top(self, k: int = 10) -> List[MemoryBuffer]:
        return sorted(self.buffers, key=lambda b: -b.nbytes)[:k]

    def dominant_kind(self) -> str:
        if not self.by_kind:
            return "?"
        return max(self.by_kind.items(), key=lambda kv: kv[1])[0]

    @property
    def xla_total(self) -> Optional[int]:
        if self.xla is None:
            return None
        return (self.xla["argument"] + self.xla["output"]
                + self.xla["temp"] - self.xla["alias"])

    def xla_delta(self) -> Optional[float]:
        """Relative delta of the comparable prediction vs XLA's total
        (signed; None when the executable was not compiled)."""
        tot = self.xla_total
        if tot is None or tot <= 0:
            return None
        return (self.cmp_peak_bytes - tot) / tot

    def xla_within(self, rel: float = 0.1,
                   abs_floor: int = XLA_ABS_TOLERANCE) -> Optional[bool]:
        tot = self.xla_total
        if tot is None:
            return None
        return abs(self.cmp_peak_bytes - tot) <= max(rel * tot, abs_floor)

    def to_dict(self, buffers: bool = False) -> dict:
        d: Dict[str, Any] = {
            "peak_bytes": int(self.peak_bytes),
            "by_kind": {k: int(v) for k, v in sorted(self.by_kind.items())},
        }
        if self.xla is not None:
            d["xla_total_bytes"] = int(self.xla_total)
            delta = self.xla_delta()
            d["xla_delta_pct"] = round(100.0 * delta, 1) \
                if delta is not None else None
        if buffers:
            d["top_buffers"] = [b.to_dict() for b in self.top(10)]
        return d

    def summary(self) -> str:
        parts = [f"peak {_fmt_bytes(self.peak_bytes)}"]
        for k, v in sorted(self.by_kind.items(), key=lambda kv: -kv[1]):
            if v:
                parts.append(f"{k} {_fmt_bytes(v)}")
        s = ", ".join(parts)
        d = self.xla_delta()
        if d is not None:
            s += f" (xla {_fmt_bytes(self.xla_total)}, {d:+.1%})"
        return s


def _fmt_bytes(n) -> str:
    n = float(n)
    for unit in ("B", "KB", "MB", "GB"):
        if abs(n) < 1024.0 or unit == "GB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}GB"


# ---------------------------------------------------------------------------
# activation liveness walk
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


def _aval_bytes(aval, upcast: bool) -> int:
    try:
        dt = np.dtype(aval.dtype)
        item = NARROW_FLOAT_WIDTH.get(dt.name, dt.itemsize) if upcast \
            else dt.itemsize
        return int(np.prod(aval.shape, dtype=np.int64) * item)
    except Exception:
        return 0


@dataclasses.dataclass
class _LivePeak:
    """Result of one (sub-)jaxpr liveness walk."""
    peak: float = 0.0
    # materialized buffers live at the peak instant: (bytes, prim, src)
    at_peak: List[Tuple[float, str, str]] = dataclasses.field(
        default_factory=list)


def liveness_walk(jaxpr, scale: float = 1.0, upcast: bool = False,
                  param_shapes: frozenset = frozenset(),
                  param_scale: Optional[float] = None) -> _LivePeak:
    """Peak transient (activation/temp) bytes of a closed jaxpr.

    ``scale`` divides global aval bytes down to per-device (GSPMD batch
    sharding over dp); inside ``shard_map`` regions avals are already
    per-device block shapes, so the scale resets to 1.  ``param_shapes``
    marks shapes whose intermediates (weight gradients, optimizer math)
    are *replicated* over dp unless ZeRO shards them — their scale is
    ``param_scale``.

    Rules (module docstring): only :data:`MATERIALIZE_PRIMS` outputs
    allocate; :data:`INPLACE_PRIMS` reuse a dying operand's buffer;
    jaxpr outvars cost nothing here (they land in donated/output
    buffers, accounted by the resident/output components); a scan's
    final carry aliases the running carry; small far-consumed fusible
    values feed a capped residual pool.
    """
    if param_scale is None:
        param_scale = scale
    j = _as_jaxpr(jaxpr)
    eqns = j.eqns
    last_use: Dict[int, int] = {}
    invars = {id(v) for v in j.invars}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if hasattr(v, "count"):
                last_use[id(v)] = i
    held = {id(v) for v in j.outvars if hasattr(v, "count")}
    live = 0.0
    resid = 0.0
    out = _LivePeak()
    var_bytes: Dict[int, float] = {}
    resid_bytes: Dict[int, float] = {}
    live_desc: Dict[int, Tuple[float, str, str]] = {}
    for i, eqn in enumerate(eqns):
        pname = eqn.primitive.name
        sub_scale = 1.0 if pname == "shard_map" else scale
        sub_pscale = 1.0 if pname == "shard_map" else param_scale
        transient = _LivePeak()
        for sub in _sub_jaxprs(eqn):
            t = liveness_walk(sub, sub_scale, upcast, param_shapes,
                              sub_pscale)
            if t.peak > transient.peak:
                transient = t
        inplace = pname in INPLACE_PRIMS
        dying = [id(v) for v in {id(x): x for x in eqn.invars}.values()
                 if hasattr(v, "count") and last_use.get(id(v)) == i
                 and id(v) not in invars and id(v) not in held]
        if inplace:
            for v in dying:
                live -= var_bytes.pop(v, 0.0)
                resid -= resid_bytes.pop(v, 0.0)
                live_desc.pop(v, None)
        skip = set()
        if pname == "scan":
            # the final carry aliases the running carry buffer (updated
            # in place across trips) — only stacked ys are new memory
            nc = int(eqn.params.get("num_carry", 0))
            skip = {id(ov) for ov in eqn.outvars[:nc]
                    if hasattr(ov, "count")}
        out_b = 0.0
        mat = pname in MATERIALIZE_PRIMS
        src = None
        for ov in eqn.outvars:
            if not hasattr(ov, "count"):
                continue
            if id(ov) in held or id(ov) in skip:
                var_bytes[id(ov)] = 0.0
                continue
            sc = scale
            if tuple(getattr(ov.aval, "shape", ())) in param_shapes:
                sc = param_scale
            b = _aval_bytes(ov.aval, upcast) * sc
            if mat:
                var_bytes[id(ov)] = b
                out_b += b
                if b:
                    if src is None:
                        src = source_line(eqn)
                    live_desc[id(ov)] = (
                        b, pname,
                        src or str(getattr(ov.aval, "shape", "")))
            elif last_use.get(id(ov), i) - i > RESIDUAL_FAR_EQNS \
                    and b <= RESIDUAL_SMALL_BYTES:
                resid_bytes[id(ov)] = b
                resid += b
                var_bytes[id(ov)] = 0.0
            else:
                var_bytes[id(ov)] = 0.0
        live += out_b
        here = live + min(resid, RESIDUAL_POOL_CAP * live) + transient.peak
        if here > out.peak:
            out.peak = here
            out.at_peak = sorted(live_desc.values(),
                                 key=lambda t: -t[0])[:8] \
                + transient.at_peak[:4]
        if not inplace:
            for v in dying:
                live -= var_bytes.pop(v, 0.0)
                resid -= resid_bytes.pop(v, 0.0)
                live_desc.pop(v, None)
    return out


def has_remat_region(jaxpr, _depth: int = 0) -> bool:
    """Whether any remat/checkpoint region appears in the jaxpr tree
    (the ``remat-opportunity`` rule's 'already covered' probe)."""
    if _depth > 8:
        return False
    j = _as_jaxpr(jaxpr)
    for eqn in j.eqns:
        name = eqn.primitive.name
        if name in ("remat", "remat2", "checkpoint"):
            return True
        if name == "pjit" and eqn.params.get("name") == "checkpoint":
            return True
        for sub in _sub_jaxprs(eqn):
            if has_remat_region(sub, _depth + 1):
                return True
    return False


# ---------------------------------------------------------------------------
# resident-state + output accounting
# ---------------------------------------------------------------------------


def _leaf_bytes(leaf) -> int:
    try:
        return int(np.prod(leaf.shape, dtype=np.int64)
                   * np.dtype(leaf.dtype).itemsize)
    except Exception:
        return 0


def _kv_page_shapes(serving) -> set:
    """Page-array shapes of the serving pool (kv-page classification).

    Read from the pool's live arrays (``page_array_shapes``), not its
    constructor attrs: the MLA latent layout stores a compressed
    ``[.., 1, page_size, latent_dim]`` stream (k) next to a rope/scale
    sidecar (v) whose shapes differ from ``(num_pages, kv_heads,
    page_size, head_dim)`` — and from each other."""
    shapes = set()
    pool = (serving or {}).get("pool")
    if pool is not None:
        try:
            k_shapes, v_shapes = pool.page_array_shapes()
            for s in (*k_shapes, *v_shapes):
                shapes.add(tuple(int(d) for d in s))
        except AttributeError:      # foreign pool object: attr fallback
            shapes.add((int(pool.num_pages), int(pool.kv_heads),
                        int(pool.page_size), int(pool.head_dim)))
    return shapes


def classify_args(handle) -> List[MemoryBuffer]:
    """Per-argument resident buffers of a lowered executable.

    Divisors (how many ways each leaf is sharded) come from the
    registered ``arg_divisors`` tree when present (the graph writes it
    from param/optimizer/feed pspecs); otherwise leaves matching a
    registered param's (shape, dtype) use that param's pspec divisor and
    everything else counts replicated.  Kinds ride the parallel
    ``arg_kinds`` tree, kv-page arrays are recognized by the pool's page
    shape, and flat optimizer buffers by the grad-comm flat layout.
    """
    import jax

    meta = handle.meta
    lowered = handle.lower()
    flat, _ = jax.tree_util.tree_flatten_with_path(lowered.args_info)
    divisors = meta.get("arg_divisors")
    kinds = meta.get("arg_kinds")
    div_leaves = jax.tree_util.tree_leaves(divisors) \
        if divisors is not None else None
    kind_leaves = jax.tree_util.tree_leaves(kinds) \
        if kinds is not None else None
    if div_leaves is not None and len(div_leaves) != len(flat):
        div_leaves = None           # registration drifted: fall back
    if kind_leaves is not None and len(kind_leaves) != len(flat):
        kind_leaves = None

    mesh_axes = {str(a): int(s)
                 for a, s in (meta.get("mesh_axes") or {}).items()}

    from ..parallel.dstates import pspec_shard_divisor

    def _pspec_divisor(pspec) -> int:
        return pspec_shard_divisor(pspec, mesh_axes)

    # fallback maps: (shape, dtype) -> (divisor, name) from params meta
    param_by_sig: Dict[Tuple, List[Tuple[int, str]]] = {}
    for p in meta.get("params", ()):
        sig = (tuple(p["shape"]), str(p["dtype"]))
        param_by_sig.setdefault(sig, []).append(
            (_pspec_divisor(p.get("pspec")), p["name"]))

    serving = meta.get("serving")
    if callable(serving):
        try:
            serving = serving()
        except Exception:
            serving = None
    page_shapes = _kv_page_shapes(serving)

    gc = meta.get("grad_comm") or {}
    flat_sizes: set = set()
    if gc.get("flat"):
        try:
            from ..optim.flat_state import FlatStateLayout
            lay = FlatStateLayout(
                [(n, tuple(s), d) for n, s, d in gc["entries"]],
                gc["device_num"], bucket_mb=gc["bucket_mb"])
            flat_sizes = {(int(s),) for s in lay.padded_sizes}
        except Exception:
            flat_sizes = set()
    dp = mesh_axes.get(meta.get("dp_axis") or "dp", 1)

    out: List[MemoryBuffer] = []
    for idx, (path, leaf) in enumerate(flat):
        if not hasattr(leaf, "shape"):
            continue
        nb = _leaf_bytes(leaf)
        sig = (tuple(leaf.shape), np.dtype(leaf.dtype).name)
        div = None
        kind = None
        name = jax.tree_util.keystr(path)
        if div_leaves is not None:
            try:
                div = int(div_leaves[idx])
            except (TypeError, ValueError):
                div = None
        if kind_leaves is not None and isinstance(kind_leaves[idx], str):
            kind = kind_leaves[idx]
        if tuple(leaf.shape) in page_shapes:
            kind = "kv-page"
            div = div or 1
        elif tuple(leaf.shape) in flat_sizes \
                and np.dtype(leaf.dtype).name == "float32":
            kind = kind or "opt-state"
            div = div if div is not None else dp
        if div is None or kind is None:
            cands = param_by_sig.get(sig)
            if cands:
                d, pname = cands[0]
                if len(cands) > 1:
                    param_by_sig[sig] = cands[1:]
                div = div if div is not None else d
                kind = kind or "param"
                name = pname
        out.append(MemoryBuffer(
            kind=kind or "input", name=name,
            nbytes=int(np.ceil(nb / max(div or 1, 1))),
            detail=f"{sig[1]}{list(sig[0])}"))
    return out


def parse_input_output_aliases(hlo_text: str) -> List[Tuple[int, int]]:
    """``(output_index, parameter_number)`` pairs from a compiled HLO's
    ``input_output_alias`` directive — XLA's actual alias table, used to
    de-false-positive ``donation-miss`` (a shape-matched output that XLA
    already aliased to some *other* donated input is not reusable)."""
    import re
    key = "input_output_alias={"
    start = hlo_text.find(key)
    if start < 0:
        return []
    # the directive nests braces ({output index} / param shape-index
    # {}), so find its end by depth, not by regex
    i = start + len(key)
    depth = 1
    while i < len(hlo_text) and depth:
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        i += 1
    body = hlo_text[start + len(key):i - 1]
    # entries look like: {0}: (3, {}, may-alias) — {output index}:
    # (param number, param shape-index, kind)
    return [(int(om) if om else 0, int(pm))
            for om, pm in re.findall(r"\{(\d*)\}\s*:\s*\((\d+)", body)]


def output_accounting(handle, arg_buffers: Sequence[MemoryBuffer]
                      ) -> Tuple[int, int]:
    """(output_extra_bytes, donated_alias_bytes): outputs not absorbed
    by a donated input, and the bytes that are (the static counterpart
    of XLA's ``alias_size_in_bytes``).

    Outputs inherit the sharding divisor of the same-signature input
    (a train step's outputs mirror its state arguments); outputs with
    no matching input count replicated.
    """
    import jax

    lowered = handle.lower()
    try:
        out_avals = handle.jaxpr.out_avals
    except Exception:
        return 0, 0
    flat, _ = jax.tree_util.tree_flatten_with_path(lowered.args_info)
    shaped = [leaf for _p, leaf in flat if hasattr(leaf, "shape")]
    div_by_sig: Dict[Tuple, int] = {}
    donated: Dict[Tuple, int] = {}
    for leaf, buf in zip(shaped, arg_buffers):
        sig = (tuple(leaf.shape), np.dtype(leaf.dtype).name)
        if sig not in div_by_sig:
            div_by_sig[sig] = max(
                1, int(round(_leaf_bytes(leaf) / max(buf.nbytes, 1))))
        if getattr(leaf, "donated", False):
            donated[sig] = donated.get(sig, 0) + 1
    extra = 0
    alias = 0
    for o in jax.tree_util.tree_leaves(out_avals):
        if not hasattr(o, "shape"):
            continue
        sig = (tuple(o.shape), np.dtype(o.dtype).name)
        nb = _leaf_bytes(o)
        div = div_by_sig.get(sig, 1)
        if donated.get(sig, 0) > 0:
            donated[sig] -= 1
            alias += int(np.ceil(nb / div))
        else:
            extra += int(np.ceil(nb / div))
    return extra, alias


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------


def xla_memory_stats(handle) -> Optional[Dict[str, int]]:
    """argument/output/temp/alias bytes from the compiled executable's
    own ``memory_analysis()`` (None when unavailable)."""
    try:
        ma = handle.compile().memory_analysis()
    except Exception:
        return None
    if isinstance(ma, (list, tuple)):
        ma = ma[0] if ma else None
    if ma is None:
        return None
    try:
        return {
            "argument": int(ma.argument_size_in_bytes),
            "output": int(ma.output_size_in_bytes),
            "temp": int(ma.temp_size_in_bytes),
            "alias": int(ma.alias_size_in_bytes),
        }
    except AttributeError:
        return None


def predict_memory(handle, xla: bool = False) -> MemoryReport:
    """The static peak-HBM model for one registered executable.

    ``peak = resident(args, sharded by registered divisors)
           + activation liveness peak (jaxpr walk)
           + outputs no donated input absorbs``

    With ``xla=True`` the compiled executable's ``memory_analysis()``
    is attached for the cross-check (compiles on first call — the gate
    already pays this for GSPMD accounting).
    """
    meta = handle.meta
    mesh_axes = {str(a): int(s)
                 for a, s in (meta.get("mesh_axes") or {}).items()}
    dp = mesh_axes.get(meta.get("dp_axis") or "dp", 1)
    gc = meta.get("grad_comm") or {}
    # graph registration records zero/flat_state for EVERY train plan
    # (implicit-sync ones carry no grad_comm entry); same precedence as
    # the replicated-state-under-shard rule so the two passes agree
    zero = int(meta.get("zero", gc.get("zero", 0)) or 0)
    flat = bool(meta.get("flat_state", gc.get("flat", False)))

    rep = MemoryReport(name=handle.name)
    arg_buffers = classify_args(handle)
    rep.buffers.extend(arg_buffers)
    rep.resident_bytes = sum(b.nbytes for b in arg_buffers)

    rep.output_extra_bytes, _alias = output_accounting(handle, arg_buffers)
    if rep.output_extra_bytes:
        rep.buffers.append(MemoryBuffer(
            kind="output", name="un-donated outputs",
            nbytes=rep.output_extra_bytes,
            detail="outputs with no donated input to alias"))

    param_shapes = frozenset(tuple(p["shape"])
                             for p in meta.get("params", ()))
    # weight-gradient / optimizer intermediates are replicated over dp
    # (they have no batch dim) unless ZeRO shards the update
    pscale = 1.0 / max(dp, 1) if (zero >= 1 or flat) else 1.0
    scale = 1.0 / max(dp, 1)
    jaxpr = handle.jaxpr
    native = liveness_walk(jaxpr, scale=scale, upcast=False,
                           param_shapes=param_shapes, param_scale=pscale)
    rep.activation_peak_bytes = int(native.peak)
    for b, prim, src in native.at_peak:
        rep.buffers.append(MemoryBuffer(
            kind="activation", name=prim, nbytes=int(b),
            source=src if ":" in src else "", detail=src))
    if flat and zero >= 3:
        # ZeRO-3's just-in-time param gather: the per-bucket gathered
        # weight-dtype buffers AND their unpacked per-param views stay
        # live through fwd+bwd — at FULL size, not dp-sharded (the
        # liveness walk prices param-shaped intermediates at 1/dp,
        # right for weight grads but not for the gathered copies).
        # Transient, so by_kind keeps them out of the at-rest "param"
        # class the replicated-state-under-shard rule polices.  Bucket
        # padding is ignored (<= dp*block elems per bucket).
        gath = 2 * sum(
            int(np.prod(s) if s else 1) * np.dtype(d).itemsize
            for _, s, d in gc.get("entries", ()))
        if gath:
            rep.activation_peak_bytes += gath
            rep.buffers.append(MemoryBuffer(
                kind="activation", name="param_gather", nbytes=gath,
                detail="just-in-time gathered params + unpacked views "
                       "(full size, transient)"))
    rep.peak_bytes = (rep.resident_bytes + rep.activation_peak_bytes
                      + rep.output_extra_bytes)

    # platform-comparable peak: CPU upcasts narrow-float intermediates
    import jax
    upcast = jax.default_backend() == "cpu"
    if upcast:
        cmp_walk = liveness_walk(jaxpr, scale=scale, upcast=True,
                                 param_shapes=param_shapes,
                                 param_scale=pscale)
        gath = sum(b.nbytes for b in rep.buffers
                   if b.name == "param_gather")
        rep.cmp_peak_bytes = (rep.resident_bytes + int(cmp_walk.peak)
                              + gath + rep.output_extra_bytes)
    else:
        rep.cmp_peak_bytes = rep.peak_bytes

    by_kind: Dict[str, int] = {}
    for b in rep.buffers:
        by_kind[b.kind] = by_kind.get(b.kind, 0) + b.nbytes
    rep.by_kind = by_kind

    if xla:
        rep.xla = xla_memory_stats(handle)
    return rep
