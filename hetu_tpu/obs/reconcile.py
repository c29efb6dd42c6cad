"""Predicted-vs-observed reconciliation: close the analysis-plane loop.

The static analysis plane predicts, per registered executable, the
collective set + wire bytes (``analysis/edges.py``) and the peak HBM
(``analysis/memory.py``) — without running anything.  The trace plane
records, per executable *call*, the observed wall time (spans whose
attrs carry ``exec=<registered name>``) and the device allocator's peak
(``utils.profiler.device_memory_stats``).  This module joins the two
into one table — the artifact ROADMAP item 5's hardware-validation
sweep freezes as evidence, runnable today on CPU with honest
expectations (the CPU sim exposes no allocator stats, so the HBM column
reads ``n/a`` instead of a fake zero-delta pass).

    with trace() as tr:
        ... run serving / training ...
        rep = reconcile(tr.events())
    print(rep.summary())

Observed peak memory is a PROCESS-wide allocator high-water mark, not
per-executable: the per-row check is therefore one-sided — a predicted
peak LARGER than the observed process peak is a real model error
(flagged), a smaller one is expected (other executables share the
device).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

__all__ = ["predicted_stats", "reconcile",
           "ReconcileRow", "ReconcileReport", "clear_prediction_cache"]

# predictions require tracing+lowering the executable — done at report
# time only (no emission site calls this), cached per registered name; the
# entry remembers WHICH handle it priced, so a re-registered name
# (new engine, new graph plan) recomputes instead of serving stale
# numbers
_PRED_CACHE: Dict[str, Any] = {}


def clear_prediction_cache(prefix: str = "") -> None:
    """Drop cached predictions whose executable name starts with
    ``prefix``.  ``graph.clear_executables`` calls this with the same
    prefix, so retiring an engine (``unregister_analysis`` / same-name
    reconstruction) releases the handle — and the KV pool its meta
    closes over — instead of pinning it here forever."""
    for name in [n for n in _PRED_CACHE if n.startswith(prefix)]:
        del _PRED_CACHE[name]


def predicted_stats(name_or_handle) -> Dict[str, Optional[int]]:
    """Static per-executable cost facts: ``wire_bytes`` (sum over the
    predicted comm-edge set; None when the executable makes no edge
    claim), ``peak_hbm_bytes`` (native-dtype static peak) and
    ``cmp_peak_bytes`` (platform-comparable peak).  Cached by name;
    failures degrade to None fields — a broken prediction must never
    take down the traced run."""
    from ..graph.graph import get_executable
    handle = name_or_handle
    if isinstance(name_or_handle, str):
        try:
            handle = get_executable(name_or_handle)
        except KeyError:
            return {"wire_bytes": None, "peak_hbm_bytes": None,
                    "cmp_peak_bytes": None}
    cached = _PRED_CACHE.get(handle.name)
    if cached is not None and cached[0] is handle:
        return cached[1]
    from ..analysis import predicted_cost_stats
    try:
        stats = predicted_cost_stats(handle)
    except Exception:
        stats = {"wire_bytes": None, "peak_hbm_bytes": None,
                 "cmp_peak_bytes": None}
    _PRED_CACHE[handle.name] = (handle, stats)
    return stats


@dataclasses.dataclass
class ReconcileRow:
    """One executable's predicted-vs-observed join."""
    executable: str
    calls: int = 0
    total_wall_s: float = 0.0
    mean_wall_s: float = 0.0
    p90_wall_s: float = 0.0
    predicted_wire_bytes: Optional[int] = None
    predicted_peak_hbm_bytes: Optional[int] = None
    cmp_peak_bytes: Optional[int] = None
    observed_peak_hbm_bytes: int = 0          # process-wide allocator peak
    hbm_check: str = "n/a"                    # ok|over-predicted|n/a
    tokens: int = 0                           # serving spans carry tokens
    # static step-time prediction (analysis/cost roofline + comm) and
    # its decomposition; wall_ratio = observed mean wall / predicted.
    # Off-TPU the chip-spec prediction has no absolute meaning, so the
    # column reports the RATIO only — no pass/fail verdict (a CPU run
    # that "passed" an absolute-time gate would be lying)
    predicted_step_s: Optional[float] = None
    predicted_compute_s: Optional[float] = None
    predicted_comm_s: Optional[float] = None
    predicted_bound: Optional[str] = None
    wall_ratio: Optional[float] = None

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class ReconcileReport:
    def __init__(self, rows: List[ReconcileRow], platform: str = "",
                 observed_peak_hbm_bytes: int = 0):
        self.rows = rows
        self.platform = platform
        self.observed_peak_hbm_bytes = observed_peak_hbm_bytes

    @property
    def families(self) -> int:
        return len(self.rows)

    def to_dict(self) -> Dict[str, Any]:
        return {"platform": self.platform,
                "observed_peak_hbm_bytes": int(self.observed_peak_hbm_bytes),
                "rows": [r.to_dict() for r in self.rows]}

    def summary(self) -> str:
        def fmt_b(v) -> str:
            if v is None:
                return "-"
            from ..analysis.memory import _fmt_bytes
            return _fmt_bytes(v)

        def fmt_ms(v) -> str:
            return "-" if v is None else f"{v * 1e3:.2f}"

        def fmt_x(v) -> str:
            return "-" if v is None else f"{v:.1f}x"

        lines = [f"{'executable':<28}{'calls':>6}{'mean_ms':>9}"
                 f"{'p90_ms':>8}{'pred_ms':>9}{'wall/pred':>10}"
                 f"{'pred_wire':>11}{'pred_peak':>11}"
                 f"{'obs_peak':>10}  hbm"]
        for r in self.rows:
            lines.append(
                f"{r.executable[:27]:<28}{r.calls:>6}"
                f"{r.mean_wall_s * 1e3:>9.2f}{r.p90_wall_s * 1e3:>8.2f}"
                f"{fmt_ms(r.predicted_step_s):>9}"
                f"{fmt_x(r.wall_ratio):>10}"
                f"{fmt_b(r.predicted_wire_bytes):>11}"
                f"{fmt_b(r.predicted_peak_hbm_bytes):>11}"
                f"{fmt_b(r.observed_peak_hbm_bytes):>10}  {r.hbm_check}")
        if not self.observed_peak_hbm_bytes:
            lines.append("(no device allocator stats on this platform — "
                         "HBM reconciliation is n/a; run on TPU for the "
                         "memory verdict)")
        if any(r.wall_ratio is not None for r in self.rows):
            lines.append("(wall/pred is a RATIO against the chip-spec "
                         "step-time model — off-TPU it has no absolute "
                         "meaning and carries no pass/fail verdict)")
        return "\n".join(lines)


def reconcile(events: Sequence, prefix: str = "",
              device=None) -> ReconcileReport:
    """Join traced executable spans against the static predictions.

    ``events``: tracer events (a :class:`SpanTracer` works too).  Spans
    are grouped by their ``exec`` attr (the registered executable name,
    optionally filtered by ``prefix``); observed wall time is the span
    durations, observed memory the live allocator peak."""
    from ..utils.profiler import device_memory_stats
    if hasattr(events, "events"):
        events = events.events()
    walls: Dict[str, List[float]] = {}
    tokens: Dict[str, int] = {}
    for ev in events:
        name = ev.attrs.get("exec")
        if name is None or ev.ph != "X" or not str(name).startswith(prefix):
            continue
        walls.setdefault(str(name), []).append(ev.dur or 0.0)
        tokens[str(name)] = tokens.get(str(name), 0) \
            + int(ev.attrs.get("tokens", 0) or 0)
    mem = device_memory_stats(device)
    peak = int(mem.get("peak_bytes_in_use", 0))
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception:
        platform = "?"
    from ..utils.metrics import percentile_of
    rows: List[ReconcileRow] = []
    for name in sorted(walls):
        ws = sorted(walls[name])
        pred = predicted_stats(name)
        row = ReconcileRow(
            executable=name, calls=len(ws),
            total_wall_s=float(sum(ws)),
            mean_wall_s=float(sum(ws) / len(ws)),
            p90_wall_s=float(percentile_of(ws, 90)),
            predicted_wire_bytes=pred.get("wire_bytes"),
            predicted_peak_hbm_bytes=pred.get("peak_hbm_bytes"),
            cmp_peak_bytes=pred.get("cmp_peak_bytes"),
            observed_peak_hbm_bytes=peak,
            tokens=tokens.get(name, 0),
            predicted_step_s=pred.get("step_time_s"),
            predicted_compute_s=pred.get("compute_time_s"),
            predicted_comm_s=pred.get("comm_time_s"),
            predicted_bound=pred.get("bound"))
        if row.predicted_step_s and row.predicted_step_s > 0:
            row.wall_ratio = row.mean_wall_s / row.predicted_step_s
        if peak <= 0 or row.predicted_peak_hbm_bytes is None:
            row.hbm_check = "n/a"
        elif row.predicted_peak_hbm_bytes > peak:
            # one-sided: the static peak can never exceed what the
            # allocator actually high-watered across the whole process
            row.hbm_check = "over-predicted"
        else:
            row.hbm_check = "ok"
        rows.append(row)
    return ReconcileReport(rows, platform=platform,
                           observed_peak_hbm_bytes=peak)
