"""Counters of what the program chose while it was being built.

A decision taken where an executable is traced (which layout a kernel
call took, which path an op lowered to) leaves nothing in the compiled
program to ask afterwards.  :func:`count` notes it at the moment it is
taken — once per traced call site, not once per step — in a
process-wide table a test or a driver reads back with :func:`counts`,
and as an instant event on the installed tracer.

    count("flash_calls", layout="native")
    counts("flash_calls")   # {(("layout", "native"),): 1}
"""
from __future__ import annotations

import threading
from collections import Counter
from typing import Any, Dict, Tuple

from .tracer import get_tracer

__all__ = ["count", "counts", "reset_counts"]

_LOCK = threading.Lock()
_COUNTS: Counter = Counter()


def count(name: str, **labels: Any) -> None:
    """Add one to ``name`` under ``labels``."""
    with _LOCK:
        _COUNTS[(name, tuple(sorted(labels.items())))] += 1
    tracer = get_tracer()
    if tracer.enabled:
        tracer.instant(name, track="lowering", **labels)


def counts(name: str) -> Dict[Tuple[Tuple[str, Any], ...], int]:
    """``{sorted (label, value) pairs: times counted}`` of ``name``."""
    with _LOCK:
        return {labels: n for (key, labels), n in _COUNTS.items()
                if key == name}


def reset_counts() -> None:
    with _LOCK:
        _COUNTS.clear()
