"""SLO classes: the request-priority vocabulary of the traffic plane.

Three classes, strictly rank-ordered (DESIGN.md §22):

* ``interactive`` — chat-style traffic.  Tight TTFT/TBT targets; the
  scheduler packs its prefill chunks and decode slots ahead of
  everything else, and the router's backpressure never sheds it while
  a lower class is still holding backlog space.
* ``standard`` — the default.  API traffic with ordinary latency
  expectations; ranked between the two extremes.
* ``batch`` — offline/bulk work (eval sweeps, distillation dumps).
  No latency promise: it absorbs preemption, shedding and queueing so
  the higher classes never feel the pressure.

Rank order is POLICY ONLY — it decides which request waits, sheds, or
is preempted, never what any surviving request computes.  Temperature-0
outputs therefore stay bit-for-bit identical to an unmanaged run for
every request that completes in both (the position-keyed sampler makes
token values a function of the request's own history alone; asserted
in ``tests/test_slo.py``).

Per-class latency targets feed the autoscaler
(:class:`~hetu_tpu.serving.slo.autoscaler.Autoscaler` scales up when
interactive TTFT crosses its target); they are defaults, overridable
per cluster.
"""
from __future__ import annotations

from typing import Dict

# strict rank order: index IS the priority (lower = more urgent)
SLO_CLASSES = ("interactive", "standard", "batch")

CLASS_RANK: Dict[str, int] = {c: i for i, c in enumerate(SLO_CLASSES)}

#: per-class latency targets (seconds): TTFT = submit -> first token,
#: TBT = gap between consecutive tokens.  ``None`` = no promise.
DEFAULT_TARGETS: Dict[str, Dict[str, float]] = {
    "interactive": {"ttft_s": 0.5, "tbt_s": 0.1},
    "standard": {"ttft_s": 2.0, "tbt_s": 0.5},
    "batch": {"ttft_s": None, "tbt_s": None},
}


def class_rank(slo_class: str) -> int:
    """Priority rank of ``slo_class`` (0 = most urgent).  Raises on an
    unknown class — a typo'd class silently defaulting to batch would
    be an invisible SLO violation."""
    try:
        return CLASS_RANK[slo_class]
    except KeyError:
        raise ValueError(f"unknown slo_class {slo_class!r}; "
                         f"have {SLO_CLASSES}") from None
