"""Closed-jaxpr traversal: collective inventory + structural probes.

The static half of the analyzer: given the jaxpr of a compiled plan
(``jax.stages.Lowered``/``Traced`` expose it without running anything),
recursively walk every sub-jaxpr — ``shard_map`` manual regions, scan
bodies, pjit/remat calls, cond branches — and pull out:

* :func:`collect_collectives` — every communication primitive, with
  payload/wire-byte accounting (the :mod:`hetu_tpu.parallel.comm` ring
  conventions), the mesh-axis sizes resolved from the enclosing
  ``shard_map``'s mesh, loop trip counts folded into ``count``, and
  source attribution from eqn provenance (user frame + jax name stack,
  which carries the ``comm.comm_tag`` tags).
* :func:`compute_dtype_histogram` — what dtype the FLOP-heavy ops
  (dot_general/conv) run in, for the wide-collective rule.
* :func:`unreduced_scalar_outputs` — scalar outputs of manual-mode
  regions whose def-chain contains no cross-replica reduction (each rank
  would return its own local value as "the" result).
* :func:`donation_candidates` — large un-donated inputs whose
  shape/dtype reappears among the outputs (a buffer the caller could
  donate).

GSPMD-inserted collectives (implicit resharding from sharding
constraints) do NOT appear in the jaxpr — they only exist after SPMD
partitioning.  Rules that need them diff compiled-HLO counts against the
jaxpr inventory (``rules.implicit-reshard``).
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

from ..parallel.comm import ring_wire_bytes
from .report import CollectiveRecord

#: primitive name -> canonical collective kind (comm.py vocabulary)
COLLECTIVE_PRIMS = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_gather": "all_gather",
    "all_to_all": "all_to_all",
    "reduce_scatter": "reduce_scatter",
    "psum_scatter": "reduce_scatter",
    "ppermute": "ppermute",
    "pbroadcast": "all_reduce",
}

#: cross-replica reduction prims (for the unreduced-scalar probe)
REDUCTION_PRIMS = {"psum", "pmax", "pmin", "reduce_scatter", "psum_scatter"}

#: FLOP-dominant compute prims (for the dtype histogram)
COMPUTE_PRIMS = {"dot_general", "conv_general_dilated"}


def _sub_jaxprs(eqn) -> Iterator[Any]:
    """Every sub-jaxpr a primitive carries (Jaxpr or ClosedJaxpr)."""
    for val in eqn.params.values():
        vals = val if isinstance(val, (list, tuple)) else (val,)
        for v in vals:
            if hasattr(v, "eqns"):               # raw Jaxpr
                yield v
            elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                yield v.jaxpr                    # ClosedJaxpr


def _as_jaxpr(jaxpr):
    return jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)
                   * np.dtype(aval.dtype).itemsize)
    except Exception:
        return 0


def _axis_names(params: dict) -> Tuple[str, ...]:
    ax = params.get("axes", params.get("axis_name", ()))
    if isinstance(ax, str):
        return (ax,)
    return tuple(str(a) for a in ax)


def _name_stack_of(eqn) -> str:
    """The eqn's jax name-stack alone (no traceback walk)."""
    si = getattr(eqn, "source_info", None)
    if si is None:
        return ""
    try:
        return str(si.name_stack)
    except Exception:
        return ""


def source_line(eqn) -> str:
    """``file:line`` of the user frame that emitted ``eqn`` ("" when
    the eqn carries no source info or no user frame)."""
    si = getattr(eqn, "source_info", None)
    if si is None:
        return ""
    from jax._src import source_info_util as siu
    fr = siu.user_frame(si.traceback)
    if fr is None:
        return ""
    return f"{os.path.basename(fr.file_name)}:{fr.start_line}"


def _source_of(eqn) -> Tuple[str, str]:
    """(scope, file:line) from eqn provenance."""
    return _name_stack_of(eqn), source_line(eqn)


def iter_eqns(jaxpr, _trip: int = 1, _axis_sizes: Optional[Dict[str, int]]
              = None, _scope: str = ""
              ) -> Iterator[Tuple[Any, int, Dict[str, int], str]]:
    """Yield ``(eqn, trip_count, axis_sizes, scope_prefix)`` over the
    whole jaxpr tree.

    ``trip_count`` multiplies enclosing ``scan``/``while`` iterations
    (unbounded whiles count as 1 with the loop noted by the caller via
    the eqn itself); ``axis_sizes`` maps manual mesh axes in scope to
    their sizes, resolved from enclosing ``shard_map`` meshes.

    ``scope_prefix`` carries the name-stack of the enclosing *container*
    eqns: jax traces scan/pjit/cond bodies in a fresh name-stack frame,
    so a ``comm_tag`` entered AROUND a ``lax.scan`` lands on the scan
    eqn but NOT on the collectives inside its body — without the prefix
    a pipeline loop's ppermutes would show up untagged.  Callers join
    ``scope_prefix`` with the eqn's own name-stack for full attribution.
    """
    axis_sizes = dict(_axis_sizes or {})
    for eqn in _as_jaxpr(jaxpr).eqns:
        yield eqn, _trip, axis_sizes, _scope
        sub_trip = _trip
        sub_axes = axis_sizes
        if eqn.primitive.name == "scan":
            sub_trip = _trip * int(eqn.params.get("length", 1))
        elif eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            if mesh is not None:
                sub_axes = dict(axis_sizes)
                shape = getattr(mesh, "shape", {})
                items = shape.items() if hasattr(shape, "items") else \
                    zip(getattr(mesh, "axis_names", ()), shape)
                for name, size in items:
                    sub_axes[str(name)] = int(size)
        subs = list(_sub_jaxprs(eqn))
        if subs:
            # scope computed only for container eqns (name-stack read,
            # no traceback walk) — per-eqn cost would dominate the walk
            sub_scope = _join_scope(_scope, _name_stack_of(eqn))
            for sub in subs:
                yield from iter_eqns(sub, sub_trip, sub_axes, sub_scope)


def _join_scope(prefix: str, scope: str) -> str:
    """Compose an enclosing container's scope with an inner name-stack
    (skipping duplication when the inner stack already carries it)."""
    if not prefix:
        return scope
    if not scope or scope == prefix or scope.startswith(prefix + "/"):
        return scope or prefix
    return f"{prefix}/{scope}"


def collect_collectives(jaxpr) -> List[CollectiveRecord]:
    """The collective inventory of a closed jaxpr (see module doc)."""
    records: List[CollectiveRecord] = []
    for eqn, trip, axis_sizes, prefix in iter_eqns(jaxpr):
        kind = COLLECTIVE_PRIMS.get(eqn.primitive.name)
        if kind is None:
            continue
        axes = _axis_names(eqn.params)
        n = 1
        for a in axes:
            n *= axis_sizes.get(a, 1)
        groups = eqn.params.get("axis_index_groups")
        if groups:
            n = max(len(g) for g in groups)
        # psum is variadic: one record per eqn, bytes summed over operands
        op_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                       if hasattr(v, "aval"))
        if kind == "all_gather":
            payload = op_bytes * n   # comm.py convention: gathered size
        else:
            payload = op_bytes
        dtype = "unknown"
        for v in eqn.invars:
            if hasattr(v, "aval") and hasattr(v.aval, "dtype"):
                dtype = np.dtype(v.aval.dtype).name
                break
        scope, src = _source_of(eqn)
        # container-scope propagation: a comm_tag entered around the
        # enclosing scan/pjit lands on the container eqn, not the body
        # eqns — join it in so loop collectives keep their attribution
        # (ppermute hop chains inside the pipeline tick scan).
        scope = _join_scope(prefix, scope)
        try:
            wire = ring_wire_bytes(kind, payload, n)
        except ValueError:
            wire = 0.0
        records.append(CollectiveRecord(
            kind=kind, axes=axes, dtype=dtype, payload_bytes=int(payload),
            wire_bytes=wire, count=trip, scope=scope, source=src))
    return records


def compute_dtype_histogram(jaxpr) -> Dict[str, int]:
    """dtype name -> count of FLOP-dominant eqns producing it."""
    out: Dict[str, int] = {}
    for eqn, trip, _, _prefix in iter_eqns(jaxpr):
        if eqn.primitive.name in COMPUTE_PRIMS and eqn.outvars:
            aval = getattr(eqn.outvars[0], "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                name = np.dtype(aval.dtype).name
                out[name] = out.get(name, 0) + trip
    return out


def _contains_reduction(jaxpr, _depth: int = 0) -> bool:
    if _depth > 8:
        return False
    for eqn in _as_jaxpr(jaxpr).eqns:
        if eqn.primitive.name in REDUCTION_PRIMS:
            return True
        for sub in _sub_jaxprs(eqn):
            if _contains_reduction(sub, _depth + 1):
                return True
    return False


def unreduced_scalar_outputs(jaxpr) -> List[Tuple[str, str, str]]:
    """Scalar outputs of manual (shard_map) regions with no reduction on
    their def-chain: ``(var_name, scope, source)`` per offender.

    Each rank would return its own local value as "the" region result —
    the classic silently-wrong local mean.  Container eqns (scan, pjit,
    remat, cond) on the chain count as reduced when ANY reduction lives
    inside them (conservative: no false positives from merged carries).
    """
    offenders: List[Tuple[str, str, str]] = []
    for eqn, _trip, axis_sizes, _prefix in iter_eqns(jaxpr):
        if eqn.primitive.name != "shard_map":
            continue
        mesh = eqn.params.get("mesh")
        sizes = [int(s) for s in getattr(mesh, "shape", {}).values()] \
            if hasattr(getattr(mesh, "shape", None), "values") else []
        if sizes and max(sizes, default=1) <= 1:
            continue                        # single-device region
        region = _as_jaxpr(eqn.params["jaxpr"])
        produced = {}
        for ieqn in region.eqns:
            for ov in ieqn.outvars:
                produced[id(ov)] = ieqn
        region_invars = {id(v) for v in region.invars}
        for ov in region.outvars:
            aval = getattr(ov, "aval", None)
            if aval is None or getattr(aval, "shape", None) != ():
                continue
            if id(ov) in region_invars or not hasattr(ov, "count"):
                continue                    # pass-through / literal
            # BFS back through the def-chain looking for a reduction
            stack, seen, reduced = [ov], set(), False
            while stack and not reduced:
                v = stack.pop()
                if id(v) in seen or id(v) in region_invars:
                    continue
                seen.add(id(v))
                ieqn = produced.get(id(v))
                if ieqn is None:
                    continue
                if ieqn.primitive.name in REDUCTION_PRIMS:
                    reduced = True
                    break
                subs = list(_sub_jaxprs(ieqn))
                if subs and any(_contains_reduction(s) for s in subs):
                    reduced = True
                    break
                stack.extend(iv for iv in ieqn.invars
                             if hasattr(iv, "count"))
            if not reduced:
                producer = produced.get(id(ov))
                scope, src = _source_of(producer) if producer is not None \
                    else ("", "")
                offenders.append((str(ov), scope, src))
    return offenders


def donation_candidates(args_info, out_avals,
                        min_bytes: int = 1 << 20,
                        alias_pairs: Optional[List[Tuple[int, int]]] = None
                        ) -> List[Tuple[str, int]]:
    """Un-donated input buffers that could have been donated.

    ``args_info`` is ``jax.stages.Lowered.args_info`` (leaves carry
    ``.shape``/``.dtype``/``.donated``); an input leaf of at least
    ``min_bytes`` whose (shape, dtype) matches an output aval is a
    candidate — XLA could reuse its buffer in place.  Returns one
    ``(arg_path, total_bytes)`` per offending top-level argument.

    ``alias_pairs`` — ``(output_index, parameter_number)`` pairs from the
    compiled HLO's ``input_output_alias`` table
    (:func:`hetu_tpu.analysis.memory.parse_input_output_aliases`).  When
    given, output slots XLA *already* aliased are retired by exact index
    instead of the shape/dtype guess: a shape-matched output that is in
    fact absorbed by a different donated input stops producing a
    false-positive candidate.
    """
    import jax

    def _nbytes(x) -> int:
        try:
            return int(np.prod(x.shape, dtype=np.int64)
                       * np.dtype(x.dtype).itemsize)
        except Exception:
            return 0

    out_shapes: Dict[Tuple, int] = {}
    out_leaves = [o for o in jax.tree_util.tree_leaves(out_avals)
                  if hasattr(o, "shape")]
    aliased_outs = {oi for oi, _p in (alias_pairs or ())}
    for oi, o in enumerate(out_leaves):
        if oi in aliased_outs:
            continue        # XLA already writes this output in place
        key = (tuple(o.shape), np.dtype(o.dtype).name)
        out_shapes[key] = out_shapes.get(key, 0) + 1
    flat, _ = jax.tree_util.tree_flatten_with_path(args_info)
    # donated inputs claim their matching output slots FIRST: a second
    # same-shaped input has nothing left to alias and is not a
    # candidate (e.g. decode's tokens aliases the greedy output; pos,
    # the same [B] int32, cannot).  With the compiled alias table the
    # absorbed slots are already retired by index above, so only
    # donations the compiler DROPPED still consume a slot here —
    # honored ones (their parameter number appears in the table) must
    # not retire twice, which would hide a real candidate.
    honored_params = {p for _oi, p in (alias_pairs or ())}
    param_idx = -1
    for _path, leaf in flat:
        if not hasattr(leaf, "shape"):
            continue
        param_idx += 1
        if not getattr(leaf, "donated", False):
            continue
        if alias_pairs is not None and param_idx in honored_params:
            continue    # absorbed: its output already retired by index
        key = (tuple(leaf.shape), np.dtype(leaf.dtype).name)
        if out_shapes.get(key, 0) > 0:
            out_shapes[key] -= 1
    by_arg: Dict[str, int] = {}
    for path, leaf in flat:
        if getattr(leaf, "donated", False) or not hasattr(leaf, "shape"):
            continue
        nb = _nbytes(leaf)
        key = (tuple(leaf.shape), np.dtype(leaf.dtype).name)
        if nb >= min_bytes and out_shapes.get(key, 0) > 0:
            # args_info mirrors (args, kwargs): path[0] selects the
            # tuple, path[1] the argument — one finding per argument,
            # not per leaf (a pytree arg is donated as a unit)
            arg = jax.tree_util.keystr(path[:2]) or "arg"
            by_arg[arg] = by_arg.get(arg, 0) + nb
    return sorted(by_arg.items())
