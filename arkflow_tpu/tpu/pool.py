"""Replicated device pool: N independent single-device runners, one dispatcher.

The dp-mesh path (``ModelRunner`` + ``mesh: {dp: N}``) scales throughput by
splitting every batch over the chips with GSPMD — ideal for large buckets,
but every step pays collective/partitioning overhead and the whole pool runs
in lockstep. Small-bucket / latency-bound traffic scales better the dumb way:
``device_pool: N`` builds N fully independent single-device ``ModelRunner``s
with REPLICATED params (one host init/restore, N one-hop transfers) behind a
least-loaded round-robin dispatcher. No collectives, no GSPMD — each member
keeps flash attention, staging pools, input donation, and eager prefetch
exactly as in single-device serving, and concurrent stream workers fan out
across chips instead of queueing on one.

Failover preserves at-least-once delivery: a member that throws mid-step is
skipped for that batch and the batch retries on the remaining members; only
when EVERY member fails does the error propagate (and the stream nacks, so
the source redelivers). Deterministic config errors (bad input spec) are NOT
retried — they would fail identically on every chip.

Health-aware dispatch (the self-healing layer): every member carries a
``RunnerHealth`` state machine. ``_pick`` skips UNHEALTHY/DEAD members, and
when a suspect's recovery probe is due it is re-admitted by routing ONE real
batch to it first (claimed via ``try_begin_probe`` so concurrent workers
don't pile onto a maybe-still-hung chip); a successful probe promotes the
member back to HEALTHY, a failed one backs the probe schedule off further.
When nothing is dispatchable — every member mid-backoff — the dispatcher
waits for the earliest probe window instead of failing, so transient
whole-pool incidents heal without losing batches.

Per-chip observability: each member's runner metrics carry a ``device`` label
(``arkflow_tpu_device_busy_seconds_total{device="3"}`` ...), and the pool adds
dispatch/failover/skip/probe counters so imbalance or a limping chip shows up
directly.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Optional

import numpy as np

from arkflow_tpu.errors import ConfigError, RunnerDead
from arkflow_tpu.tpu.health import CORRUPT, DEAD, DEGRADED, HEALTHY, UNHEALTHY
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.startup import setup_stage
from arkflow_tpu.tpu.bucketing import BucketPolicy
from arkflow_tpu.tpu.runner import (ModelRunner, convert_for_serving,
                                    init_host_params)

logger = logging.getLogger("arkflow.tpu")


class ModelRunnerPool:
    """Drop-in for ``ModelRunner`` over N replicated single-device members.

    Exposes the same surface the ``tpu_inference`` processor uses (``spec``,
    ``buckets``, ``cfg``, ``family``, ``infer``/``infer_sync``/``warmup``),
    so processors don't branch on pool-vs-single beyond construction.
    """

    def __init__(
        self,
        model: str,
        model_config: Optional[dict] = None,
        *,
        pool_size: int,
        buckets: Optional[BucketPolicy] = None,
        checkpoint: Optional[str] = None,
        seed: int = 0,
        devices=None,
        serving_dtype: Optional[str] = None,
        max_in_flight: Optional[int] = None,
        dispatch_depth: Optional[int] = None,
        packed: bool = False,
        step_deadline_s: Optional[float] = None,
        step_deadline_first_s: Optional[float] = None,
        health_config=None,
    ):
        import jax

        if pool_size < 1:
            raise ConfigError(f"device_pool must be >= 1, got {pool_size}")
        devices = list(devices) if devices is not None else jax.devices()
        if pool_size > len(devices):
            raise ConfigError(
                f"device_pool: {pool_size} runners requested, "
                f"{len(devices)} devices visible")
        # one host-side init + checkpoint restore + dtype convert (bf16 cast /
        # int8 quantization); every member transfers the SAME finished tree to
        # its own chip — replication by construction, and the expensive
        # full-tree walks happen once instead of N times
        from arkflow_tpu.models import get_model

        family = get_model(model)
        cfg = family.make_config(**(model_config or {}))
        host_params = init_host_params(family, cfg, seed, checkpoint)
        with setup_stage("setup_place"):  # the one cast is placement's
            host_params = convert_for_serving(host_params, serving_dtype,
                                              family.name)
        self.members: list[ModelRunner] = [
            ModelRunner(
                model,
                model_config,
                buckets=buckets,
                seed=seed,
                devices=[devices[i]],
                serving_dtype=serving_dtype,
                max_in_flight=max_in_flight,
                dispatch_depth=dispatch_depth,
                packed=packed,
                host_params=host_params,
                device_label=str(i),
                step_deadline_s=step_deadline_s,
                step_deadline_first_s=step_deadline_first_s,
                health_config=health_config,
            )
            for i in range(pool_size)
        ]
        self.pool_size = pool_size
        #: outstanding infer calls per member (the least-loaded signal)
        self._loads = [0] * pool_size
        self._rr = 0  # round-robin cursor for ties
        self._chaos_rr = 0  # separate cursor for injected step faults

        reg = global_registry()
        self.m_dispatch = [
            reg.counter(
                "arkflow_tpu_pool_dispatch_total",
                "batches dispatched to this pool member",
                {"model": model, "device": str(i)})
            for i in range(pool_size)
        ]
        self.m_failover = reg.counter(
            "arkflow_tpu_pool_failover_total",
            "batches retried on another member after a member error",
            {"model": model})
        self.m_skipped = reg.counter(
            "arkflow_tpu_pool_skipped_unhealthy_total",
            "dispatch decisions that passed over >=1 unhealthy/dead member",
            {"model": model})
        self.m_probes = reg.counter(
            "arkflow_tpu_pool_probes_total",
            "recovery probes dispatched to unhealthy members",
            {"model": model})

    # -- ModelRunner surface (delegated) -----------------------------------

    @property
    def family(self):
        return self.members[0].family

    @property
    def cfg(self):
        return self.members[0].cfg

    @property
    def spec(self):
        return self.members[0].spec

    @property
    def buckets(self) -> BucketPolicy:
        return self.members[0].buckets

    @property
    def packed(self) -> bool:
        return self.members[0].packed

    @property
    def max_in_flight(self) -> int:
        # aggregate device-queue depth across the pool (bench worker sizing)
        return sum(m.max_in_flight for m in self.members)

    def duty_cycle(self) -> float:
        cycles = [m.duty_cycle() for m in self.members]
        return sum(cycles) / len(cycles) if cycles else 0.0

    def warmup(self, seq_lens: Optional[list[int]] = None) -> int:
        """Precompile every member's bucket grid. Serial on purpose: member 0
        pays the real compiles, members 1..N-1 replay them from the
        persistent compile cache (identical shapes, identical HLO)."""
        return sum(m.warmup(seq_lens) for m in self.members)

    def inject_step_fault(self, kind: str, duration_s: float = 0.0) -> None:
        """Chaos hook (fault plugin): arm a one-shot device-step fault on one
        member, round-robin across calls so repeated faults spread over the
        pool the way real per-chip incidents would."""
        i = self._chaos_rr % self.pool_size
        self._chaos_rr += 1
        self.members[i].inject_step_fault(kind, duration_s)

    def health_report(self) -> list[dict]:
        """Per-member health snapshots for the engine's ``/health``."""
        return [m.health_report() for m in self.members]

    def swap_units(self) -> list[tuple[str, "ModelRunner"]]:
        """Independently-flippable serving surfaces for a rolling hot-swap
        (tpu/swap.py): each member flips and probes ALONE, in pool order, so
        the dispatcher keeps serving on the other N-1 members throughout —
        the pool's replication is exactly what makes the roll zero-downtime."""
        return [(f"member {i}", m) for i, m in enumerate(self.members)]

    # -- live shape retune surface (tpu/tuner.py) ---------------------------

    def count_new_shapes(self, policy: BucketPolicy) -> int:
        """Executables a retune would still compile, pool-wide. Member 0's
        count is the honest COST estimate (the others replay member 0's
        compiles from the persistent cache, like ``warmup``)."""
        return self.members[0].count_new_shapes(policy)

    def warm_shapes(self, policy: BucketPolicy) -> int:
        """Pre-compile a proposed grid on every member (serial, like
        ``warmup``: member 0 pays the compiles, the rest replay them)."""
        return sum(m.warm_shapes(policy) for m in self.members)

    async def warm_shapes_live(self, policy: BucketPolicy) -> int:
        """Serving-safe warm (see ``ModelRunner.warm_shapes_live``),
        member by member."""
        count = 0
        for m in self.members:
            count += await m.warm_shapes_live(policy)
        return count

    def retarget_buckets(self, policy: BucketPolicy) -> BucketPolicy:
        """Flip every member onto the new grid; returns member 0's prior
        policy (all members share one grid by construction)."""
        old = self.members[0].buckets
        for m in self.members:
            m.retarget_buckets(policy)
        return old

    def dispatch_counts(self) -> dict[tuple, int]:
        """Pool-wide traffic dispatches per padded shape key."""
        out: dict[tuple, int] = {}
        for m in self.members:
            for k, v in m.dispatch_counts().items():
                out[k] = out.get(k, 0) + v
        return out

    def compiled_grid(self) -> set[tuple[int, int]]:
        """``(rows, seq)`` programs every member has compiled: a piece of a
        length split may land on any of them."""
        return set.intersection(*(m.compiled_grid() for m in self.members))

    # -- dispatch ----------------------------------------------------------

    def _pick(self, exclude: set[int]) -> Optional[int]:
        """Health-aware least-loaded pick, round-robin among ties (the
        cursor advances every pick, so equal-load members take strict
        turns). UNHEALTHY/DEAD members are skipped — except that an
        UNHEALTHY member whose recovery probe is due takes priority (one
        batch re-admits it on success); ``None`` when nothing is
        dispatchable right now."""
        best: Optional[int] = None
        probe: Optional[int] = None
        skipped = False
        now = time.monotonic()
        n = self.pool_size
        for off in range(n):
            i = (self._rr + off) % n
            if i in exclude:
                continue
            h = self.members[i].health
            state = h.state
            if state in (HEALTHY, DEGRADED):
                if best is None or self._loads[i] < self._loads[best]:
                    best = i
            elif state == UNHEALTHY:
                skipped = True
                if probe is None and h.probe_due(now):
                    probe = i
            else:  # DEAD, or CORRUPT (quarantined: only integrity repair
                skipped = True  # re-admits it — never the probe schedule)
        if probe is not None and self.members[probe].health.try_begin_probe(now):
            # the probe outranks healthy members: without routing one real
            # batch at it, a recovered chip would never be re-admitted
            self.m_probes.inc()
            self._rr = (self._rr + 1) % n
            return probe
        if skipped and best is not None:
            self.m_skipped.inc()
        if best is not None:
            self._rr = (self._rr + 1) % n
        return best

    def _all_dead(self, exclude: set[int]) -> bool:
        """Every remaining member is terminally out of dispatch: DEAD, or
        CORRUPT (quarantined for integrity). CORRUPT fails fast like DEAD
        rather than waiting — the batch nacks for redelivery and serves
        after the integrity monitor repairs a member, instead of parking
        live traffic on an unbounded repair."""
        return all(self.members[i].health.state in (DEAD, CORRUPT)
                   for i in range(self.pool_size) if i not in exclude)

    def _probe_wait_s(self, exclude: set[int]) -> float:
        """Time until the earliest untried member may be probed again."""
        waits = [self.members[i].health.seconds_until_probe()
                 for i in range(self.pool_size)
                 if i not in exclude and self.members[i].health.state == UNHEALTHY]
        return min(waits) if waits else 0.05

    def _note_member_failure(self, i: int, e: Exception) -> None:
        """Health bookkeeping for a member step that raised: shared policy on
        the member's serving core (deadline misses and OOMs self-mark inside
        the step; anything else marks UNHEALTHY here) — the same surface any
        dispatcher sitting on ``ServingRunnerCore`` members uses."""
        self.members[i].core.note_external_failure(e)

    def infer_sync(self, inputs: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        while True:
            i = self._pick(set())
            if i is not None:
                break
            if self._all_dead(set()):
                raise RunnerDead(
                    "device pool: every member is DEAD or quarantined CORRUPT")
            time.sleep(max(self._probe_wait_s(set()), 0.01))
        self._loads[i] += 1
        self.m_dispatch[i].inc()
        try:
            return self.members[i].infer_sync(inputs)
        except ConfigError:
            raise  # deterministic (bad input/spec), not a chip fault
        except Exception as e:
            self._note_member_failure(i, e)
            raise
        finally:
            self._loads[i] -= 1

    async def infer(self, inputs: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        """Route one batch to the least-loaded healthy member; fail over to
        the remaining members on a member error (at-least-once: the batch
        either completes on SOME chip or the error propagates and the stream
        nacks). When every untried member is mid-probe-backoff the dispatch
        waits for the earliest probe window rather than failing the batch.
        """
        tried: set[int] = set()
        last_err: Exception = RuntimeError("device pool has no members")
        while True:
            i = self._pick(tried)
            if i is None:
                if len(tried) >= self.pool_size:
                    raise last_err  # every member failed this batch
                if self._all_dead(tried):
                    raise RunnerDead(
                        "device pool: every remaining member is DEAD or "
                        "quarantined CORRUPT")
                # all untried members are unhealthy mid-backoff: wait for the
                # earliest probe window instead of dropping the batch
                await asyncio.sleep(max(self._probe_wait_s(tried), 0.01))
                continue
            self._loads[i] += 1
            self.m_dispatch[i].inc()
            try:
                return await self.members[i].infer(inputs)
            except (asyncio.CancelledError, ConfigError):
                # cancellation is not a chip fault; ConfigError is
                # deterministic (bad input/spec) and would fail on every chip
                raise
            except Exception as e:
                last_err = e
                tried.add(i)
                self._note_member_failure(i, e)
                if len(tried) >= self.pool_size:
                    raise
                self.m_failover.inc()
                logger.warning(
                    "device pool: member %d failed a step (%s); retrying on "
                    "another member (%d/%d tried)",
                    i, e, len(tried), self.pool_size)
            finally:
                self._loads[i] -= 1
