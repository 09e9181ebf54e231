"""Shared serving-runner core: the substrate every device-serving path sits on.

PRs 4-7 grew the ``tpu_inference`` runner a self-healing layer — health state
machine, step-deadline watchdog on abandonable threads, jit-rebuild
scheduling after an incident, chaos fault hooks, and the ``/health`` report
surface. All of it lived inside ``ModelRunner``, so the generation path
(``tpu/serving.py``) had none of it. This module extracts that layer into a
``ServingRunnerCore`` both the batch runner and the continuous-batching
``GenerationServer`` compose:

- **health**: a ``RunnerHealth`` state machine + the admission gates
  (``heal_gate`` / ``heal_gate_sync``) that wait out probe backoff, claim the
  recovery probe, and run a scheduled rebuild before the probe step.
- **deadlines**: ``run_deadlined`` / ``run_deadlined_sync`` execute one
  blocking device step on a borrowed dedicated watchdog thread and abandon it
  on a miss (the wedged thread goes with its discarded executor — never the
  shared default executor). A miss counts, marks UNHEALTHY, schedules a
  rebuild, and raises ``StepDeadlineExceeded`` so the batch NACKS for
  redelivery.
- **dispatch bookkeeping**: ``note_external_failure`` is the health marking a
  dispatcher (the device pool, or any future multi-runner front) applies to a
  member step that raised — shared policy instead of pool-local knowledge.
- **chaos**: ``inject_step_fault``/``apply_chaos`` arm one-shot hang/oom
  faults consumed inside the next step (the fault plugin's processor wrapper
  drives this through the owner's ``runner`` attribute).

The owner supplies ``rebuild_fn`` — how to distrust cached executables after
a hang (the runner rebuilds its jitted step and clears seen shapes; the
generation server rebuilds its four jitted steps).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import deque
from typing import Any, Callable, Mapping, Optional

from arkflow_tpu.errors import ConfigError, RunnerDead, StepDeadlineExceeded
from arkflow_tpu.obs import global_registry
from arkflow_tpu.tpu.health import CORRUPT as HEALTH_CORRUPT
from arkflow_tpu.tpu.health import DEAD as HEALTH_DEAD
from arkflow_tpu.tpu.health import HealthConfig, RunnerHealth

logger = logging.getLogger("arkflow.tpu")

#: an unseen shape compiles before it executes; the watchdog scales the step
#: deadline by this factor unless ``step_deadline_first`` pins an absolute
#: budget for first-compile steps
FIRST_COMPILE_DEADLINE_SCALE = 10.0


class InjectedOom(RuntimeError):
    """Chaos-injected device OOM (``inject_step_fault('oom')``): carries the
    RESOURCE_EXHAUSTED signature so it walks the real degradation path."""

    def __init__(self, msg: str = "RESOURCE_EXHAUSTED: chaos: injected device OOM"):
        super().__init__(msg)


#: substrings identifying an XLA allocation failure across backends/versions
_OOM_SIGNATURES = ("resource_exhausted", "resource exhausted", "out of memory", "oom")


def is_oom_error(e: BaseException) -> bool:
    """Device allocation failure? Matched on the message because jaxlib's
    ``XlaRuntimeError`` carries the gRPC status only as text (and the chaos
    layer fabricates the same signature). Word-boundary match: a bare
    substring test would classify any message containing e.g. "boom" as an
    OOM and route it into the degradation path."""
    if isinstance(e, InjectedOom):
        return True
    if isinstance(e, MemoryError):
        return True
    import re

    msg = str(e).lower()
    return any(re.search(rf"\b{re.escape(sig)}\b", msg) for sig in _OOM_SIGNATURES)


def on_tpu_backend(devices=None) -> bool:
    """Is the (first) execution device a TPU? The one backend probe the
    auto-resolved fast paths share (runner auto-flash, serving auto decode
    kernel) — a device_kind fix lands once, not per copy. A failing device
    query propagates: "no TPU" is an answer, a backend that cannot be
    asked is not."""
    import jax

    dev = devices[0] if devices else jax.devices()[0]
    return (dev.platform == "tpu"
            or "tpu" in getattr(dev, "device_kind", "").lower())


#: bfloat16 machine epsilon (8 bits of significand)
_BF16_EPS = 2.0 ** -7


def bf16_logit_tolerance(ref) -> float:
    """How far two bf16-served computations of the logits ``ref`` may sit
    apart: 4 bf16 ulps of the largest reference logit (on a v5e the paged
    and gather decode paths differ by about one: 0.019 at a largest logit
    of 2.6, PR 21's chip run)."""
    import numpy as np

    return 4 * _BF16_EPS * max(1.0, float(np.abs(np.asarray(ref)).max()))


def logits_parity(ref, got) -> dict:
    """Do two ``[..., vocab]`` logit arrays agree as far as bf16 serving can?

    Random-weight models produce near-tied logits, where a different
    accumulation order legitimately flips an argmax — bare argmax equality
    is the wrong test. ``ok`` needs every logit within
    ``bf16_logit_tolerance`` AND argmax equal on
    every row whose reference top-2 margin exceeds twice the tolerance (a
    flip there is a real disagreement, not a tie). Returns the verdict with
    its numbers so callers can print or raise with them."""
    import numpy as np

    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    tol = bf16_logit_tolerance(ref)
    top2 = np.partition(ref, -2, axis=-1)[..., -2:]
    decided = (top2[..., 1] - top2[..., 0]) > 2 * tol
    flips = int(((ref.argmax(-1) != got.argmax(-1)) & decided).sum())
    diff = float(np.abs(ref - got).max())
    return {"ok": bool(diff <= tol and flips == 0), "max_abs_diff": diff,
            "tol": tol, "rows": int(decided.size),
            "decided_rows": int(decided.sum()), "decided_flips": flips}


def parse_core_config(config: Mapping[str, Any]) -> dict:
    """Parse the shared self-healing keys a device processor config carries
    (``step_deadline`` / ``step_deadline_first`` / ``health``) into the
    kwargs ``ServingRunnerCore`` (and the runners that wrap it) accept.
    Shared by the ``tpu_inference`` and ``tpu_generate`` builders so both
    paths read the same knobs the same way."""
    from arkflow_tpu.utils.duration import parse_duration

    step_deadline = config.get("step_deadline")
    step_deadline_first = config.get("step_deadline_first")
    return dict(
        step_deadline_s=(parse_duration(step_deadline)
                         if step_deadline is not None else None),
        step_deadline_first_s=(parse_duration(step_deadline_first)
                               if step_deadline_first is not None else None),
        health_config=HealthConfig.from_config(config.get("health")),
    )


class ServingRunnerCore:
    """Health + deadline + chaos + rebuild substrate for one serving runner.

    Thread-safe where it must be: deadline misses arrive from executor
    threads and the event loop alike, watchdog executors are borrowed under a
    lock, and the rebuild flag is double-checked.
    """

    def __init__(
        self,
        *,
        name: str,
        labels: Optional[dict[str, str]] = None,
        step_deadline_s: Optional[float] = None,
        step_deadline_first_s: Optional[float] = None,
        health_config: Optional[HealthConfig] = None,
        rebuild_fn: Optional[Callable[[], None]] = None,
    ):
        if step_deadline_s is not None and step_deadline_s <= 0:
            raise ConfigError(f"step_deadline must be positive, got {step_deadline_s}")
        if step_deadline_first_s is not None and step_deadline_first_s <= 0:
            raise ConfigError(
                f"step_deadline_first must be positive, got {step_deadline_first_s}")
        self.name = name
        self.step_deadline_s = step_deadline_s
        #: first-compile steps trace + compile before executing; they get
        #: their own (much larger) budget so a cold bucket isn't misread as a
        #: hung device
        self.step_deadline_first_s = (
            step_deadline_first_s
            if step_deadline_first_s is not None
            else (step_deadline_s * FIRST_COMPILE_DEADLINE_SCALE
                  if step_deadline_s is not None else None))
        #: how the owner distrusts cached executables after a hang
        self.rebuild_fn = rebuild_fn

        reg = global_registry()
        self.health = RunnerHealth(
            health_config,
            gauge=reg.gauge(
                "arkflow_tpu_runner_health",
                "runner health state (0 healthy, 1 degraded, 2 unhealthy, 3 dead)",
                labels),
            name=name)
        self.m_deadline_miss = reg.counter(
            "arkflow_tpu_step_deadline_misses",
            "device steps abandoned after exceeding step_deadline", labels)
        self.m_rebuilds = reg.counter(
            "arkflow_tpu_runner_rebuilds_total",
            "jitted-step rebuilds after a deadline miss", labels)

        #: armed chaos faults consumed by the next device steps (fault plugin)
        self._chaos: deque = deque()
        #: persistent silent-data-corruption fault (``inject_step_fault('sdc')``):
        #: unlike the one-shot hang/oom, corruption keeps corrupting every
        #: step until the integrity repair path clears it
        self.sdc_armed = False
        #: set on a deadline miss: the jitted step(s) are rebuilt before the
        #: next dispatch (stale executables on a wedged device aren't trusted)
        self._needs_rebuild = False
        self._rebuild_lock = threading.Lock()
        #: recycled single-thread watchdog executors for deadlined steps —
        #: NEVER the shared default executor: an abandoned (hung) step would
        #: wedge a thread everyone else needs. A miss discards the executor
        #: with its wedged thread; the no-miss path reuses them.
        self._watchdog_free: list = []
        self._watchdog_lock = threading.Lock()

    # -- chaos hook ---------------------------------------------------------

    def inject_step_fault(self, kind: str, duration_s: float = 0.0) -> None:
        """Arm a fault on the device-step path: ``hang`` wedges the next step
        for ``duration_s`` of dead time (as a stuck device sync would) so the
        deadline watchdog fires; ``oom`` raises a fabricated
        RESOURCE_EXHAUSTED on the next step so the degradation path runs;
        ``sdc`` arms PERSISTENT silent data corruption — every step's float
        outputs are perturbed until the integrity repair path clears it
        (``clear_sdc``), because a corrupting chip doesn't stop after one
        wrong answer. ``bitflip`` is owner-level (it mutates the param tree,
        which the core doesn't hold) — runners intercept it before
        delegating here."""
        if kind == "sdc":
            self.sdc_armed = True
            return
        if kind not in ("hang", "oom"):
            raise ConfigError(f"unknown step fault kind {kind!r} (hang/oom/sdc)")
        self._chaos.append((kind, float(duration_s)))

    def apply_chaos(self) -> None:
        """Executor-thread side of ``inject_step_fault``."""
        try:
            kind, duration_s = self._chaos.popleft()
        except IndexError:
            return
        if kind == "hang":
            time.sleep(duration_s if duration_s > 0 else 30.0)
        else:
            raise InjectedOom()

    def corrupt_outputs(self, out):
        """Apply the armed ``sdc`` fault to fetched step outputs (executor
        thread): float arrays (logits and their kin) are negated so every
        downstream argmax flips, and integer arrays (device-computed labels
        / token ids — already argmaxed BEFORE this host-side hook could
        touch their logits) are shifted by one — wrong answers that look
        structurally healthy, which is exactly what the golden probe exists
        to catch. Identity when no fault is armed."""
        if not self.sdc_armed:
            return out
        import jax.numpy as jnp
        import numpy as np

        def _garble(v):
            arr = np.asarray(v)
            if arr.ndim < 1:
                return v
            # jnp.issubdtype: bfloat16 (ml_dtypes, numpy kind 'V') must
            # count as float — bf16 logits are the common serving case
            if jnp.issubdtype(arr.dtype, jnp.floating):
                return -arr
            if jnp.issubdtype(arr.dtype, jnp.integer):
                return arr + 1
            return v

        if isinstance(out, dict):
            return {k: _garble(v) for k, v in out.items()}
        import jax

        return jax.tree_util.tree_map(_garble, out)

    def clear_sdc(self) -> None:
        """Integrity-repair side: the corrupting 'hardware' was replaced."""
        self.sdc_armed = False

    # -- deadlines ----------------------------------------------------------

    def deadline_for(self, first_compile: bool) -> Optional[float]:
        """Per-step watchdog budget; first-compile shapes get the scaled-up
        budget so a cold bucket isn't misread as a hung device."""
        if self.step_deadline_s is None:
            return None
        return self.step_deadline_first_s if first_compile else self.step_deadline_s

    @staticmethod
    def deadline_remaining(deadline_s: float, dispatched_at: float,
                           *, floor: float = 0.05) -> float:
        """Watchdog budget left for an ALREADY-DISPATCHED step (pipelined
        dispatch, ``dispatch_depth`` > 1): each in-flight step's deadline
        runs from the moment IT was enqueued on the device, not from when
        the host gets around to fetching its outputs — otherwise a hung
        step N would silently spend step N+1's budget too, and a miss
        would be detected one full step late. Floored so host bookkeeping
        jitter between dispatch and fetch can never turn an on-time step
        into a spurious zero-budget miss."""
        return max(deadline_s - (time.monotonic() - dispatched_at), floor)

    def _borrow_watchdog(self):
        """A single-thread executor for one deadlined step: reused across
        steps in the no-miss steady state, discarded (with its wedged
        thread) on a miss. Concurrent steps each borrow their own, so the
        watchdog never serializes in-flight work."""
        import concurrent.futures

        with self._watchdog_lock:
            if self._watchdog_free:
                return self._watchdog_free.pop()
        return concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="arkflow-step-watchdog")

    def _return_watchdog(self, ex) -> None:
        with self._watchdog_lock:
            self._watchdog_free.append(ex)

    def _deadline_miss(self, fut, deadline: float,
                       on_zombie: Optional[Callable[[], None]]) -> StepDeadlineExceeded:
        """Bookkeeping for an abandoned step: count the miss, mark the runner
        UNHEALTHY (recovery probes re-admit it), schedule a rebuild, and wire
        the zombie future so its eventual exception is retrieved — and the
        owner's cleanup (``on_zombie``, e.g. staging-buffer recycling) runs —
        whenever the wedged step finally ends."""
        self.m_deadline_miss.inc()
        self.schedule_rebuild()
        self.health.mark_unhealthy(f"step exceeded its {deadline:.3g}s deadline")

        def _reap(f) -> None:
            try:
                f.exception()
            except Exception:
                pass
            if on_zombie is not None:
                on_zombie()

        fut.add_done_callback(_reap)
        return StepDeadlineExceeded(
            f"device step exceeded its {deadline:.3g}s deadline "
            "(runner marked unhealthy; batch nacked for redelivery)")

    def run_deadlined_sync(self, fn: Callable[[], Any], deadline: float,
                           on_zombie: Optional[Callable[[], None]] = None):
        """Run ``fn`` on a dedicated watchdog thread so a hang can be
        abandoned (the thread itself cannot be killed — its executor is
        dropped and the thread left to finish or leak; the shared default
        executor is never at risk)."""
        import concurrent.futures

        ex = self._borrow_watchdog()
        fut = ex.submit(fn)
        try:
            out = fut.result(timeout=deadline)
        except concurrent.futures.TimeoutError:
            ex.shutdown(wait=False)  # abandon: the wedged thread goes with it
            raise self._deadline_miss(fut, deadline, on_zombie) from None
        except Exception:
            self._return_watchdog(ex)  # step ended: its thread is idle again
            raise
        self._return_watchdog(ex)
        return out

    async def run_deadlined(self, fn: Callable[[], Any], deadline: float,
                            on_zombie: Optional[Callable[[], None]] = None):
        """Async twin: wait for the step, not forever, on a borrowed
        DEDICATED thread. On a miss the thread cannot be interrupted: its
        executor is dropped with it and the miss handler reaps the step's
        eventual result."""
        loop = asyncio.get_running_loop()
        ex = self._borrow_watchdog()
        cfut = ex.submit(fn)
        fut = asyncio.wrap_future(cfut, loop=loop)
        done, _ = await asyncio.wait({fut}, timeout=deadline)
        if not done:
            ex.shutdown(wait=False)
            raise self._deadline_miss(cfut, deadline, on_zombie)
        self._return_watchdog(ex)  # step ended; thread idle
        return fut.result()

    # -- rebuild scheduling -------------------------------------------------

    def schedule_rebuild(self) -> None:
        self._needs_rebuild = True

    def rebuild_if_needed(self) -> None:
        """Run the owner's rebuild after a deadline miss: executables cached
        across a device hang are not trusted, so the next (probe) step
        recompiles from scratch. Double-checked so concurrent probes rebuild
        once."""
        if not self._needs_rebuild or self.rebuild_fn is None:
            return
        with self._rebuild_lock:
            if not self._needs_rebuild:
                return
            self._needs_rebuild = False
            self.rebuild_fn()
        self.m_rebuilds.inc()

    # -- admission gates ----------------------------------------------------

    def heal_gate_sync(self) -> None:
        """Admission control for the runner's own callers (pool dispatch has
        its own health-aware pick): DEAD fails fast; UNHEALTHY waits out the
        probe backoff, claims the probe, and rebuilds if needed — the step
        that follows IS the recovery probe."""
        h = self.health
        while True:
            if h.state == HEALTH_DEAD:
                raise RunnerDead(f"runner {h.name} is DEAD; not serving")
            if h.state == HEALTH_CORRUPT:
                raise RunnerDead(
                    f"runner {h.name} is quarantined (CORRUPT) pending "
                    "integrity repair; not serving")
            if h.join_or_begin_probe():
                break
            time.sleep(min(max(h.seconds_until_probe(), 0.01), 0.5))
        self.rebuild_if_needed()

    async def heal_gate(self) -> None:
        """Async twin of ``heal_gate_sync`` (never blocks the event loop)."""
        h = self.health
        while True:
            if h.state == HEALTH_DEAD:
                raise RunnerDead(f"runner {h.name} is DEAD; not serving")
            if h.state == HEALTH_CORRUPT:
                raise RunnerDead(
                    f"runner {h.name} is quarantined (CORRUPT) pending "
                    "integrity repair; not serving")
            if h.join_or_begin_probe():
                break
            await asyncio.sleep(min(max(h.seconds_until_probe(), 0.01), 0.5))
        self.rebuild_if_needed()

    # -- dispatcher-side bookkeeping ----------------------------------------

    def note_external_failure(self, e: Exception) -> None:
        """Health bookkeeping a DISPATCHER applies to a step that raised.
        Deadline misses and OOMs self-mark inside the step (which also
        releases a probe claim); anything else — a raw XLA fault, a generic
        probe failure — must mark HERE, unconditionally: ``mark_unhealthy``
        both stops dispatch feeding the chip and clears the probing flag, so
        a FAILED probe re-arms its backoff instead of fencing the member
        forever."""
        if isinstance(e, (StepDeadlineExceeded, RunnerDead)) or is_oom_error(e):
            return
        self.health.mark_unhealthy(f"step failed: {e}")

    # -- /health surface ----------------------------------------------------

    def health_report(self) -> dict:
        """JSON-able snapshot for the engine's ``/health`` endpoint; owners
        extend it with their own serving detail."""
        rep = self.health.report()
        rep["deadline_misses"] = int(self.m_deadline_miss.value)
        if self.sdc_armed:
            rep["sdc_armed"] = True
        return rep
