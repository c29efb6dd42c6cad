"""Runtime trace plane: span tracer, Perfetto export, reconciliation.

The observability counterpart of ``hetu_tpu/analysis`` (DESIGN.md §15):

* :mod:`.tracer` — low-overhead structured spans (monotonic clock,
  parent/child nesting, instant events, capped ring buffer,
  thread-safe) with a shared no-op ``NULL_TRACER`` so disabled tracing
  costs ~nothing in the serving/train hot loops;
* :mod:`.export` — Chrome trace-event JSON (loadable in Perfetto, one
  track per serving request / per training phase) and a JSONL journal
  readable with ``utils.metrics.load_jsonl``;
* :mod:`.reconcile` — joins observed per-executable wall time and
  device memory peaks against the analysis plane's static wire-byte and
  peak-HBM predictions (looked up at report time by the spans' ``exec=``);
* :mod:`.phases` — the model-phase vocabulary (``phase("mlp")`` scopes
  that reach HLO ``op_name`` metadata) and ``device_phases(exec)``, the
  ``{HLO instruction: phase}`` map that names a device trace's events.

* :mod:`.counters` — ``count(name, **labels)`` / ``counts(name)``: what
  the program chose while an executable was traced (``flash_calls`` by
  ``layout``), which the compiled program can no longer tell.

Real-time spans are mirrored into the ``jax.profiler`` trace
(``hetu:<name>`` annotations), so host phases and device operations
share the profiler's clock.

Instrumented out of the box: ``serving.Engine`` (full per-request
lifecycle: queue wait, admission + page accounting, prefix-cache
hit/evict, prefill chunks, decode tokens, preemption, finish, plus the
scheduler's per-step packing decision), ``DefineAndRunGraph.run``
(per-step feed / assemble / executable / commit phases with grad-comm
attribution; ``executable`` times the call, not the device),
``switch_strategy`` and the MPMD pipeline task loop.
"""
from .counters import count, counts, reset_counts
from .export import (chrome_trace, events_to_jsonl, request_timelines,
                     timeline_summary, validate_chrome_trace,
                     write_chrome_trace, write_jsonl)
from .phases import PHASES, device_phases, phase
from .reconcile import (ReconcileReport, ReconcileRow, predicted_stats,
                        reconcile)
from .tracer import (NOOP_SPAN, NULL_TRACER, PROFILER_PREFIX,
                     PrefixedTracer, Span, SpanTracer, get_tracer,
                     install_tracer, trace)

__all__ = [
    "Span", "SpanTracer", "PrefixedTracer", "NULL_TRACER", "NOOP_SPAN",
    "PROFILER_PREFIX", "get_tracer", "install_tracer", "trace",
    "PHASES", "phase", "device_phases",
    "count", "counts", "reset_counts",
    "chrome_trace", "write_chrome_trace", "events_to_jsonl", "write_jsonl",
    "validate_chrome_trace", "request_timelines", "timeline_summary",
    "ReconcileReport", "ReconcileRow", "predicted_stats", "reconcile",
]
