"""Training metrics recorder (v1 ``python/hetu/metrics.py`` capability).

Scalar time series with windowed smoothing, JSONL persistence, and a
CSV export — the observability layer between raw logging (TIK/TOK,
``logging_utils``) and external dashboards.  No TensorBoard/W&B
dependency (none is baked into the image); the JSONL stream is the
interchange format.

    rec = Metrics(log_file="run.jsonl")
    rec.log(step, loss=2.31, lr=3e-4, tokens_per_sec=1.1e5)
    rec.smoothed("loss")        # windowed mean
    rec.summary()               # per-key count/mean/min/max/last
    rec.to_csv("run.csv")
"""
from __future__ import annotations

import json
import os
from collections import defaultdict, deque
from typing import Any, Dict, List, Optional


class Metrics:
    def __init__(self, log_file: Optional[str] = None, window: int = 20):
        self.window = int(window)
        self._series: Dict[str, List[tuple]] = defaultdict(list)
        self._recent: Dict[str, deque] = defaultdict(
            lambda: deque(maxlen=self.window))
        self._log_file = log_file
        self._fh = None
        if log_file:
            os.makedirs(os.path.dirname(os.path.abspath(log_file)),
                        exist_ok=True)
            self._fh = open(log_file, "a")

    # -- recording -----------------------------------------------------------

    def log(self, step: int, **values: Any) -> None:
        """Record scalar values at ``step`` (jax/np scalars accepted)."""
        clean = {}
        for k, v in values.items():
            v = float(v)
            self._series[k].append((int(step), v))
            self._recent[k].append(v)
            clean[k] = v
        if self._fh is not None:
            self._fh.write(json.dumps({"step": int(step), **clean}) + "\n")
            self._fh.flush()

    # -- reading -------------------------------------------------------------

    def last(self, key: str) -> Optional[float]:
        s = self._series.get(key)
        return s[-1][1] if s else None

    def smoothed(self, key: str) -> Optional[float]:
        """Mean over the most recent ``window`` values."""
        r = self._recent.get(key)
        return sum(r) / len(r) if r else None

    def series(self, key: str) -> List[tuple]:
        return list(self._series.get(key, ()))

    def summary(self) -> Dict[str, Dict[str, float]]:
        out = {}
        for k, s in self._series.items():
            vals = [v for _, v in s]
            out[k] = {"count": len(vals), "mean": sum(vals) / len(vals),
                      "min": min(vals), "max": max(vals), "last": vals[-1]}
        return out

    # -- export --------------------------------------------------------------

    def to_csv(self, path: str) -> None:
        """One row per step, one column per key (blank when missing)."""
        keys = sorted(self._series)
        by_step: Dict[int, Dict[str, float]] = defaultdict(dict)
        for k in keys:
            for step, v in self._series[k]:
                by_step[step][k] = v
        with open(path, "w") as f:
            f.write(",".join(["step"] + keys) + "\n")
            for step in sorted(by_step):
                row = [str(step)] + [
                    (f"{by_step[step][k]!r}" if k in by_step[step] else "")
                    for k in keys]
                f.write(",".join(row) + "\n")

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


# -- serving instruments -----------------------------------------------------
#
# The Metrics recorder above is a step-keyed time series (training loops
# log once per step).  Serving needs instantaneous instruments instead:
# monotonically increasing counters (tokens out), point-in-time gauges
# (batch occupancy, page-pool utilization), and latency distributions
# (TTFT/TPOT percentiles).  All three share a no-op fallback so the
# engine's hot loop pays nothing when observability is disabled.


def percentile_of(xs_sorted, p: float) -> float:
    """Linear-interpolated percentile of an ascending-sorted sequence
    (numpy's default estimator; ``p`` in [0, 100]) — shared by
    :meth:`Histogram.percentile` and the trace-plane reconciliation so
    no consumer re-grows the old nearest-index tail bias."""
    if not xs_sorted:
        return 0.0
    rank = max(0.0, min(100.0, float(p))) / 100.0 * (len(xs_sorted) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(xs_sorted) - 1)
    return xs_sorted[lo] + (xs_sorted[hi] - xs_sorted[lo]) * (rank - lo)


class Counter:
    """Monotonically increasing count (tokens generated, preemptions)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str = ""):
        self.name = name
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        self.value += n


class Gauge:
    """Point-in-time value (queue depth, pool utilization)."""

    __slots__ = ("name", "value")

    def __init__(self, name: str = ""):
        self.name = name
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Latency/size distribution with exact percentiles.

    Serving cares about tails over bounded windows (a few thousand
    requests), so observations are kept raw (capped deque) and
    percentiles computed exactly — no bucket-boundary error, no bucket
    schema to choose per deployment.

    Optional Prometheus-style export: pass ``buckets`` (sorted upper
    bounds) and :meth:`bucket_counts` returns cumulative
    ``{le: count}`` with an implicit ``+Inf`` bucket.  Observations
    above the last finite bound still count toward ``+Inf``, ``count``
    and ``total`` — dropping the overflow tail silently under-reports
    exactly the latencies a histogram exists to expose.
    """

    __slots__ = ("name", "_obs", "count", "total", "buckets",
                 "_bucket_counts")

    def __init__(self, name: str = "", max_observations: int = 4096,
                 buckets: Optional[List[float]] = None):
        self.name = name
        self._obs = deque(maxlen=int(max_observations))
        self.count = 0
        self.total = 0.0
        self.buckets = tuple(sorted(float(b) for b in buckets)) \
            if buckets else ()
        # per-bucket (non-cumulative) tallies; slot -1 is +Inf overflow
        self._bucket_counts = [0] * (len(self.buckets) + 1)

    def observe(self, v: float) -> None:
        v = float(v)
        self._obs.append(v)
        self.count += 1
        self.total += v
        for i, bound in enumerate(self.buckets):
            if v <= bound:
                self._bucket_counts[i] += 1
                break
        else:
            # above every finite bound (or no buckets): +Inf slot, so
            # cumulative counts always sum to self.count
            self._bucket_counts[-1] += 1

    def bucket_counts(self) -> Dict[str, int]:
        """Cumulative Prometheus-style ``{le: count}`` incl. ``+Inf``."""
        out: Dict[str, int] = {}
        cum = 0
        for bound, c in zip(self.buckets, self._bucket_counts):
            cum += c
            out[repr(bound)] = cum
        out["+Inf"] = cum + self._bucket_counts[-1]
        return out

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def percentile(self, p: float) -> float:
        """Exact percentile over the retained window (p in [0, 100]),
        linearly interpolated between ranks (numpy's default).  The old
        nearest-index rounding biased small-window tails — p90 of
        [1..10] snapped to a sample instead of 9.1 — which made
        TTFT/TBT tails over a few dozen requests jumpy run-to-run."""
        return percentile_of(sorted(self._obs), p)

    def summary(self) -> Dict[str, float]:
        return {"count": self.count, "mean": self.mean,
                "p50": self.percentile(50), "p90": self.percentile(90),
                "p99": self.percentile(99)}


class _NullInstrument:
    """No-op stand-in for any instrument when metrics are disabled: every
    method swallows its arguments, every read returns zero."""

    name = ""
    value = 0.0
    count = 0
    total = 0.0
    mean = 0.0

    def inc(self, n: float = 1.0) -> None:
        pass

    def set(self, v: float) -> None:
        pass

    def observe(self, v: float) -> None:
        pass

    def percentile(self, p: float) -> float:
        return 0.0

    def bucket_counts(self) -> Dict[str, int]:
        return {"+Inf": 0}

    def summary(self) -> Dict[str, float]:
        # zeroed, same keys as Histogram.summary: consumers indexing
        # e.g. ["p90"] must not crash when metrics are disabled
        return {"count": 0, "mean": 0.0, "p50": 0.0, "p90": 0.0,
                "p99": 0.0}


NULL_INSTRUMENT = _NullInstrument()


def make_instrument(kind: str, name: str = "", enabled: bool = True,
                    **kwargs):
    """Factory with the disabled fallback: ``make_instrument("gauge",
    "occupancy", enabled=False)`` returns the shared no-op instrument.
    Extra kwargs flow to the instrument constructor (e.g.
    ``make_instrument("histogram", "ttft", buckets=[0.1, 1.0])`` for
    Prometheus-style bucketed latency histograms)."""
    if not enabled:
        return NULL_INSTRUMENT
    cls = {"counter": Counter, "gauge": Gauge, "histogram": Histogram}.get(
        kind.lower())
    if cls is None:
        raise ValueError(f"unknown instrument kind {kind!r}")
    return cls(name, **kwargs)


def _prom_name(name: str) -> str:
    """Prometheus metric names: [a-zA-Z_:][a-zA-Z0-9_:]*."""
    out = "".join(c if c.isalnum() or c in "_:" else "_" for c in name)
    if not out or not (out[0].isalpha() or out[0] in "_:"):
        out = "_" + out
    return out


def _prom_value(v: float) -> str:
    f = float(v)
    if f != f:
        return "NaN"                  # exposition-format spellings:
    if f in (float("inf"), float("-inf")):
        return "+Inf" if f > 0 else "-Inf"
    return str(int(f)) if f == int(f) else repr(f)


def render_prometheus(instruments) -> str:
    """Prometheus text exposition (v0.0.4) for a set of instruments.

    ``instruments``: a ``{name: instrument}`` dict (e.g. the engine's
    ``counters``/``gauges``/``histograms`` merged) or an iterable of
    instruments (named by their ``name`` attribute).  Counters and
    gauges render as-is; histograms render the standard
    ``_bucket``/``_sum``/``_count`` triple via :meth:`bucket_counts`
    (cumulative, ``+Inf`` included, so ``_bucket{le="+Inf"} == _count``
    by construction).  No-op instruments are skipped — disabled metrics
    expose nothing rather than fake zeros.
    """
    if isinstance(instruments, dict):
        items = list(instruments.items())
    else:
        items = [(getattr(inst, "name", "") or f"metric_{i}", inst)
                 for i, inst in enumerate(instruments)]
    lines: List[str] = []
    for name, inst in items:
        if isinstance(inst, _NullInstrument):
            continue
        name = _prom_name(name)
        if isinstance(inst, Counter):
            lines.append(f"# TYPE {name} counter")
            lines.append(f"{name} {_prom_value(inst.value)}")
        elif isinstance(inst, Gauge):
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {_prom_value(inst.value)}")
        elif isinstance(inst, Histogram):
            lines.append(f"# TYPE {name} histogram")
            for le, c in inst.bucket_counts().items():
                # bounds keep the float form ("1.0", not "1") so the
                # series identity is stable as buckets are retuned
                le_txt = le if le == "+Inf" else repr(float(le))
                lines.append(f'{name}_bucket{{le="{le_txt}"}} {int(c)}')
            lines.append(f"{name}_sum {_prom_value(inst.total)}")
            lines.append(f"{name}_count {int(inst.count)}")
    return "\n".join(lines) + "\n" if lines else ""


def merge_prometheus_texts(texts: Dict[str, str],
                           label: str = "replica") -> str:
    """Merge several Prometheus expositions into one, tagging every
    sample with ``label="<key>"`` — the cluster's ``metrics_text()``
    merges per-replica ``Engine.metrics_text()`` outputs this way, so
    one scrape endpoint serves the whole replica fleet and dashboards
    slice by the ``replica`` label.

    Samples are regrouped per metric (one ``# TYPE`` line per metric
    name, first-seen kind wins, then every labeled sample), which keeps
    the output a valid exposition: Prometheus requires all samples of a
    metric to be contiguous under its single TYPE header."""
    import re
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(.*)$")
    kinds: Dict[str, str] = {}
    samples: Dict[str, List[str]] = {}
    order: List[str] = []
    for key, text in texts.items():
        tag = f'{_prom_name(label)}="{key}"'
        for line in (text or "").splitlines():
            line = line.strip()
            if not line:
                continue
            if line.startswith("#"):
                parts = line.split()
                if len(parts) >= 4 and parts[1] == "TYPE":
                    kinds.setdefault(parts[2], parts[3])
                continue
            m = sample_re.match(line)
            if m is None:
                continue
            name, labels, value = m.groups()
            inner = (labels or "{}")[1:-1]
            labels = "{" + (f"{inner},{tag}" if inner else tag) + "}"
            # histogram series (_bucket/_sum/_count) group under the
            # base metric's TYPE header, like the scrape format expects
            base = name
            for suffix in ("_bucket", "_sum", "_count"):
                if name.endswith(suffix) and name[:-len(suffix)] in kinds:
                    base = name[:-len(suffix)]
                    break
            if base not in samples:
                samples[base] = []
                order.append(base)
            samples[base].append(f"{name}{labels} {value}")
    lines: List[str] = []
    for base in order:
        if base in kinds:
            lines.append(f"# TYPE {base} {kinds[base]}")
        lines.extend(samples[base])
    return "\n".join(lines) + "\n" if lines else ""


def load_jsonl(path: str) -> List[Dict[str, Any]]:
    """Read back a Metrics JSONL stream."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out
