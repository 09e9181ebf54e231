"""Start-up under the tracing that exists: what a replica's scale-out waits for.

Everything between process start and the first warm step is named here, in
the ``loop_stage`` form of ``obs/trace.py`` (work that belongs to no request:
a stage histogram ``arkflow_stage_seconds{stage}`` AND a profiler annotation
of the same name, no trace tree):

- ``setup_stage(name)``: one synchronous phase of a construction
  (``setup_init_params``, ``setup_restore``, ``setup_place``, ``setup_build``,
  ``setup_probe``). A stage observes its SELF time: what setup stages nested
  in it on the same thread took is theirs alone, so the stages of one
  construction add up to no more than its wall time.
- ``cold_step(program)``: a program's FIRST call (trace, lower, compile or
  cache load, first execution) as ``setup_cold_step{program}``; beside it the
  counter ``arkflow_setup_cold_seconds_total``, wall time with at least one
  cold step in flight (workers can each meet a first-seen shape at once: the
  histogram's sum would count that instant twice).
- One set of ``jax.monitoring`` listeners a process feeds
  ``arkflow_jax_compile_seconds{phase, program}`` and
  ``arkflow_jax_compile_cache_total{result}``. They fire on a compile and
  never in steady state. ``backend_compile`` wraps JAX's
  ``compile_or_get_cached``: it fires on a cache HIT too and a retrieval's
  time lies inside it, so sums take ``trace + lower + backend_compile`` and
  never add ``cache_retrieval``. A trace nests — every ``jnp`` function a
  program calls is a jitted one traced inside it, and an eager op met while
  tracing compiles there —, so what fires while a trace is OPEN on its
  thread lies inside that trace's seconds and is not observed again (JAX
  records a scalar where a trace opens: the third listener). ``program`` is
  a served program's name (``note_programs``) or ``other``: eager one-op
  programs make no labels.
- The gauge ``arkflow_process_start_time_seconds`` (unix seconds, the
  Prometheus convention) closes the account from inside: window open less
  process start less the named phases is what nobody names.

``startup_report()`` is the ``/health`` view of all of it.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Iterable, Optional

from arkflow_tpu.obs.metrics import Counter, Gauge, Histogram, global_registry
from arkflow_tpu.obs.trace import annotated, observe_stage

_IMPORTED_AT = time.time()

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_PHASES = {
    TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
OTHER = "other"


def process_start_time() -> float:
    """Unix seconds at which this process started: ``/proc/self/stat``'s
    start time (ticks since boot) laid on the boot clock, else this
    package's import."""
    try:
        with open("/proc/self/stat") as f:
            # the command (field 2) may hold spaces: count from its ")"
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        since_boot = time.clock_gettime(time.CLOCK_BOOTTIME)
        age = since_boot - ticks / os.sysconf("SC_CLK_TCK")
        started = time.time() - age
        if age >= 0.0 and started <= _IMPORTED_AT:  # else: another clock
            return started
    except (OSError, ValueError, IndexError, AttributeError):
        pass
    return _IMPORTED_AT


class _Startup:
    """The process's start-up state: the listeners' one registration, the
    served programs and which of them are still cold, and the cold steps in
    flight. One instance a process, beside the global registry it feeds."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._installed = False
        #: .stages: setup stages entered on this thread; .traces: JAX
        #: traces open on it
        self._stack = threading.local()
        #: served program -> has completed a call since it was (re)built
        self._programs: dict[str, bool] = {}
        self._cold_inflight = 0
        self._cold_since = 0.0

    # -- one registration a process ---------------------------------------

    def install(self) -> None:
        """Set the process-start gauge and register the listeners, once
        however many engines, runners and servers a process builds."""
        if self._installed:
            return
        with self._lock:
            if self._installed:
                return
            from jax import monitoring

            global_registry().gauge(
                "arkflow_process_start_time_seconds",
                "unix time at which this process started").set(
                    process_start_time())
            monitoring.register_scalar_listener(self._on_scalar)
            monitoring.register_event_duration_secs_listener(self._on_duration)
            monitoring.register_event_listener(self._on_event)
            self._installed = True

    def _on_scalar(self, event: str, _value, **_kw) -> None:
        if event == TRACE_EVENT:  # a trace opens on this thread
            self._stack.traces = getattr(self._stack, "traces", 0) + 1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        phase = COMPILE_PHASES.get(event)
        if phase is None:
            return
        open_traces = getattr(self._stack, "traces", 0)
        if event == TRACE_EVENT:
            open_traces = self._stack.traces = max(0, open_traces - 1)
        if open_traces:
            return  # inside an open trace's seconds
        name = str(kw.get("fun_name", ""))
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]  # lowering and compiling name the program so
        global_registry().histogram(
            "arkflow_jax_compile_seconds",
            "what JAX spent compiling, by phase and served program",
            {"phase": phase, "program": self.program_label(name)},
        ).observe(float(duration))

    @staticmethod
    def _on_event(event: str, **_kw) -> None:
        result = CACHE_RESULTS.get(event)
        if result is not None:
            global_registry().counter(
                "arkflow_jax_compile_cache_total",
                "persistent compile cache lookups, by result",
                {"result": result}).inc()

    # -- served programs ----------------------------------------------------

    def note_programs(self, names: Iterable[str]) -> None:
        """The programs a runner or server just (re)built: cold until each
        completes a call. Their names bound the ``program`` label."""
        with self._lock:
            for name in names:
                self._programs[name] = False

    def program_label(self, name) -> str:
        return name if name in self._programs else OTHER

    def cold_programs(self) -> list[str]:
        with self._lock:
            return sorted(n for n, ran in self._programs.items() if not ran)

    # -- cold steps in flight -------------------------------------------------

    def cold_enter(self) -> None:
        with self._lock:
            if self._cold_inflight == 0:
                self._cold_since = time.perf_counter()
            self._cold_inflight += 1

    def cold_exit(self, program: str, ran: bool) -> None:
        with self._lock:
            self._cold_inflight -= 1
            if ran and program in self._programs:
                self._programs[program] = True
            if self._cold_inflight == 0:
                global_registry().counter(
                    "arkflow_setup_cold_seconds_total",
                    "wall seconds with at least one program's first call "
                    "in flight").inc(time.perf_counter() - self._cold_since)

    def stages(self) -> list:
        stack = getattr(self._stack, "stages", None)
        if stack is None:
            stack = self._stack.stages = []
        return stack


_STATE = _Startup()
note_programs = _STATE.note_programs


class setup_stage(annotated):
    """``annotated`` + ``observe_stage`` for one synchronous phase of
    start-up, observed LESS the setup stages nested in it on this thread.
    Observed once a construction, so a hot swap or an incident rebuild that
    runs the same code observes again under the same name."""

    __slots__ = ("stage", "labels", "_nested")

    def __init__(self, stage: str, **labels: str):
        kind = labels.get("program")
        super().__init__(f"{stage}:{kind}" if kind else stage)
        self.stage, self.labels = stage, labels

    def __enter__(self) -> "setup_stage":
        _STATE.install()
        self._nested = 0.0
        _STATE.stages().append(self)
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        stack = _STATE.stages()
        stack.pop()
        if stack:
            stack[-1]._nested += self.dur_s
        observe_stage(self.stage, self.dur_s - self._nested, **self.labels)


class cold_step(setup_stage):
    """A served program's first call: ``setup_cold_step{program}`` (its
    annotation ``setup_cold_step:<program>``) and, from the first such step
    in flight to the last, ``arkflow_setup_cold_seconds_total``."""

    __slots__ = ()

    def __init__(self, program: str):
        super().__init__("setup_cold_step",
                         program=_STATE.program_label(program))

    def __enter__(self) -> "cold_step":
        _STATE.cold_enter()
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        _STATE.cold_exit(self.labels["program"], ran=exc[0] is None)


def startup_report() -> dict:
    """The ``/health`` view: seconds by stage (and by program for the cold
    steps), compile seconds by phase, cache hits and misses, and the served
    programs that have not run yet."""
    stages: dict[str, float] = {}
    cold: dict[str, float] = {}
    phases: dict[str, float] = {}
    cache = {"hit": 0, "miss": 0}
    start: Optional[float] = None
    cold_wall = 0.0
    for m in global_registry().collect():
        if isinstance(m, Histogram) and m.name == "arkflow_stage_seconds":
            stage = m.labels.get("stage", "")
            if stage.startswith("setup_"):
                stages[stage] = stages.get(stage, 0.0) + m.sum
                if "program" in m.labels:
                    cold[m.labels["program"]] = m.sum
        elif isinstance(m, Histogram) and m.name == "arkflow_jax_compile_seconds":
            phase = m.labels["phase"]
            phases[phase] = phases.get(phase, 0.0) + m.sum
        elif isinstance(m, Counter) and m.name == "arkflow_jax_compile_cache_total":
            cache[m.labels["result"]] = int(m.value)
        elif isinstance(m, Counter) and m.name == "arkflow_setup_cold_seconds_total":
            cold_wall = m.value
        elif isinstance(m, Gauge) and m.name == "arkflow_process_start_time_seconds":
            start = m.value
    return {
        "process_start_time_seconds": start,
        "stage_seconds": {k: round(v, 4) for k, v in sorted(stages.items())},
        "cold_step_seconds": {k: round(v, 4) for k, v in sorted(cold.items())},
        "cold_wall_seconds": round(cold_wall, 4),
        "compile_seconds": {k: round(v, 4) for k, v in sorted(phases.items())},
        "compile_cache": {"hits": cache["hit"], "misses": cache["miss"]},
        "cold_programs": _STATE.cold_programs(),
    }
