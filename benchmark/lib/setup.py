"""What the readers of start-up share. The program names every phase of its
own start-up (``arkflow_tpu/obs/startup.py``): stages ``setup_*`` in
``arkflow_stage_seconds{stage}``, the counter
``arkflow_setup_cold_seconds_total``, JAX's compiles in
``arkflow_jax_compile_seconds{phase, program}`` and
``arkflow_jax_compile_cache_total{result}``, and the gauge
``arkflow_process_start_time_seconds``. ``view.hist`` / ``view.counter`` are
deltas over the window, and set-up is over by then: these read the registry
snapshot taken at the window's OPEN. One rule for all: nothing where that
snapshot lacks the gauge (a program without the instrumentation), else 0.0
for a stage or series that was never observed."""

from __future__ import annotations

import time
from typing import Optional

START = "arkflow_process_start_time_seconds"
STAGES = "arkflow_stage_seconds"
COLD = "arkflow_setup_cold_seconds_total"
COMPILE = "arkflow_jax_compile_seconds"
CACHE = "arkflow_jax_compile_cache_total"
#: the phases whose seconds add up (``cache_retrieval`` lies inside
#: ``backend_compile``, which JAX records on a cache hit too)
COMPILE_PHASES = ("trace", "lower", "backend_compile")
#: what the program names of set-up, stage by stage; the cold steps are the
#: counter's (wall time with at least one in flight), not the histogram's
NAMED_STAGES = ("setup_init_params", "setup_restore", "setup_place",
                "setup_build", "setup_probe")


def open_snapshot(view) -> Optional[dict]:
    """The registry at the window's open, or None where the program has no
    start-up instrumentation (its snapshot lacks the process-start gauge)."""
    snap = getattr(view, "_open", None) or {}
    return snap if any(name == START for name, _ in snap) else None


def _values(snap: dict, name: str, **labels):
    for (n, lab), v in snap.items():
        if n == name and all(dict(lab).get(k) == val
                             for k, val in labels.items()):
            yield v


def stage_s(view, *stages: str) -> Optional[float]:
    """Seconds observed under these stages by the window's open."""
    snap = open_snapshot(view)
    if snap is None:
        return None
    return float(sum(s for stage in stages
                     for s, _ in _values(snap, STAGES, stage=stage)))


def counter_at_open(view, name: str, **labels) -> Optional[float]:
    snap = open_snapshot(view)
    return None if snap is None else float(sum(_values(snap, name, **labels)))


def compile_s(view) -> Optional[float]:
    """Seconds JAX spent tracing, lowering and compiling (or loading from the
    cache) by the window's open, over every program."""
    snap = open_snapshot(view)
    if snap is None:
        return None
    return float(sum(s for phase in COMPILE_PHASES
                     for s, _ in _values(snap, COMPILE, phase=phase)))


def named_s(view) -> Optional[float]:
    """Everything of set-up the program names: the stages and the cold steps."""
    stages, cold = stage_s(view, *NAMED_STAGES), counter_at_open(view, COLD)
    return None if stages is None else stages + cold


def since_process_start_s(view) -> Optional[float]:
    """Process start to window open: the window's open (the harness's
    ``perf_counter`` stamp) put on the unix clock, less the program's gauge."""
    start = counter_at_open(view, START)
    if start is None or not view.run.t_open:
        return None
    t_open_unix = time.time() - (time.perf_counter() - view.run.t_open)
    return t_open_unix - start
