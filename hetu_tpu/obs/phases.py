"""Device time by program phase.

The profiler names a device event by its HLO instruction (``fusion.12``),
which says nothing about the model.  Two pieces close the gap without
changing the compiled program:

* :func:`phase` declares, where the model is built, which part of the
  program an operation belongs to — a fixed vocabulary (:data:`PHASES`)
  entered as a ``jax.named_scope``, so the word lands on the HLO
  ``op_name`` metadata of every instruction traced inside it (backward
  instructions inherit it as ``transpose(jvp(<phase>))``).  Under a
  define-and-run graph the ambient phase is recorded on the node at
  build time (``attrs["_phase"]``) and entered again around the node's
  ``impl`` when the plan is traced (``Graph._eval_targets``).
* :func:`device_phases` reads the optimized HLO text of a registered
  executable back into ``{instruction name: phase}``, the join key
  between a device trace and the model.

Metadata only: a scope changes no instruction, so the executable and
its cache key are what they were.
"""
from __future__ import annotations

import contextlib
import re
from collections import Counter
from typing import Dict, List, Optional

__all__ = ["PHASES", "UNMAPPED", "phase", "current_phase",
           "hlo_phase_map", "device_phases"]

# training: embed .. param_gather; serving adds kv_scatter and sample; a
# hybrid stack's mixers (models/hybrid.py) add the state-space phases
# (in / out projections, conv, scan + gate + group norm, moves of state
# between the slot store and a row) and the expert layer's (router, latent
# down / up, routed experts, shared expert) and the latent attention's
# two folds of W_kvb into q and out of the latent output (mla_absorb);
# an indexed latent layer adds the indexer (its projections, scores and
# top-k: attn_index) and the attention over the selection (the gather of
# the selected positions and the softmax over them: attn_sparse), a
# window latent layer its attention (attn_window), both the head gate
# (attn_gate), and a leading dense layer its MLP (mlp_dense); a
# self-drafting step's MTP module (serving/decode.py) runs under four of
# its own, OUTSIDE its mixers' (the first word on a path wins), so that no
# share of the stack's phases holds its time: its way in (mtp_proj), its
# attention layer (mtp_attn), its FFN (mtp_moe), its norm and head
# (mtp_head); a block-wise model's step ends in its block head (block_head:
# logits at the block slots' positions, the choices, their confidences and
# the selection of what the pass unmasks)
PHASES = ("embed", "norm", "attn_proj", "attn_core", "mlp", "lm_head_ce",
          "optimizer", "grad_comm", "param_gather", "kv_scatter", "sample",
          "ssm_proj", "ssm_conv", "ssm_scan", "state_io",
          "moe_router", "moe_latent", "moe_routed", "moe_shared", "mla_absorb",
          "attn_index", "attn_sparse", "attn_window", "attn_gate",
          "mlp_dense", "mtp_proj", "mtp_attn", "mtp_moe", "mtp_head",
          "block_head")
UNMAPPED = "unmapped"

# scope names that predate the vocabulary (parallel/comm.py's comm_tag
# planes) and the phase each counts as
_ALIASES = {"param_comm": "param_gather"}

_STACK: List[str] = []


@contextlib.contextmanager
def phase(name: str):
    """Attribute everything built or traced inside to ``name``."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; the vocabulary is "
                         f"{PHASES}")
    import jax
    _STACK.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        _STACK.pop()


def current_phase() -> Optional[str]:
    """The innermost open :func:`phase` (None outside any)."""
    return _STACK[-1] if _STACK else None


# -- HLO text -> {instruction: phase} -----------------------------------------

_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# an operation that hands its operand on unchanged but for the layout
_RELABEL = re.compile(r"\s(?:bitcast|reshape|copy|transpose|"
                      r"get-tuple-element)\(([^)]*)\)")
_NAME = re.compile(r"%?([\w.\-]+)")
_WORD = re.compile(r"[A-Za-z_][A-Za-z_0-9]*")
_MOSAIC = 'custom_call_target="tpu_custom_call"'


def _phase_of_path(op_name: str) -> Optional[str]:
    """The first vocabulary word on an ``op_name`` path
    (``jit(step)/transpose(jvp(mlp))/dot_general`` -> ``mlp``)."""
    for part in op_name.split("/"):
        for word in _WORD.findall(part):
            word = _ALIASES.get(word, word)
            if word in PHASES:
                return word
    return None


def hlo_phase_map(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: phase}`` for every instruction of every
    computation of an optimized HLO module.  An instruction's phase is
    the first vocabulary word on its ``op_name`` path.  One whose own
    path names none (XLA's rewrites drop metadata: a fusion, a
    simplified scatter) takes, in this order: the phase of the root of
    the computation it calls; the phase of a bitcast / reshape / copy /
    transpose / get-tuple-element that consumes it (the same value under
    another layout); the commonest phase among the instructions it
    fuses; for a Pallas (Mosaic) kernel, its own name.  Anything else
    is :data:`UNMAPPED`."""
    out: Dict[str, str] = {}
    members: Dict[str, List[str]] = {}     # computation -> instructions
    roots: Dict[str, str] = {}             # computation -> root
    relabelled: Dict[str, str] = {}        # operand -> phase of its user
    pending = []                           # (instruction, called, kernel)
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            members[comp] = []
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        if comp is not None:
            members[comp].append(name)
            if m.group(1):
                roots[comp] = name
        path = _OP_NAME.search(line)
        ph = _phase_of_path(path.group(1)) if path else None
        if ph is None:
            called = _CALLS.search(line)
            pending.append((name, called.group(1) if called else None,
                            _MOSAIC in line))
        else:
            user_of = _RELABEL.search(line)
            if user_of:
                for operand in _NAME.findall(user_of.group(1)):
                    relabelled.setdefault(operand, ph)
        out[name] = ph or UNMAPPED
    for name, called, kernel in pending:
        root = out.get(roots.get(called), UNMAPPED)
        inner = Counter(out[i] for i in members.get(called, ())
                        if out[i] != UNMAPPED)
        if root != UNMAPPED:
            out[name] = root
        elif name in relabelled:
            out[name] = relabelled[name]
        elif inner:
            out[name] = inner.most_common(1)[0][0]
        elif kernel:
            out[name] = re.sub(r"[_.\d]+$", "", name) or name
    return out


def device_phases(exec_name: str) -> Dict[str, str]:
    """``{HLO instruction name: phase}`` of a registered executable
    (``graph.register_executable``; the ``exec=`` attribute of a traced
    ``executable`` / ``unified_step`` span names it), from the optimized
    HLO of the handle's own compile — with the persistent compilation
    cache on, the executable that ran.  Look an event's instruction up
    with ``.get(name, "unmapped")``.  Raises ``KeyError`` for a name that
    is not registered."""
    from ..graph.graph import get_executable
    return hlo_phase_map(get_executable(exec_name).compiled_text())
