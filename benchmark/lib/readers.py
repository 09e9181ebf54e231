"""Helpers the per-layer metric readers share. A reader is
``benchmark/metrics/<metric>.py`` with ``read(view) -> float | None``; one
that finds nothing to read returns None and the harness leaves the metric
out of the line."""

from __future__ import annotations

import re
from typing import Optional

from benchmark.lib.stats import median, percentile


def pct(values, q: float) -> Optional[float]:
    return percentile(values, q) if len(values) else None


def span_ms(view, *stages: str, q: float = 50.0) -> Optional[float]:
    """The ``q``-th percentile, in ms, of the program's stage spans of these
    names recorded inside the window."""
    durs = [d for s in stages for d in view.spans(s)]
    return None if not durs else percentile(durs, q) * 1e3


def module_ms(view, pattern: str) -> Optional[float]:
    """Median device time, in ms, of the executions of the compiled programs
    whose name matches ``pattern``, from the profiler's trace (device 0)."""
    if not view.trace or not view.trace.get("modules"):
        return None
    rx = re.compile(pattern)
    durs = [d for name, ds in view.trace["modules"].items()
            if rx.search(name) for d in ds]
    return None if not durs else median(durs) * 1e3
