"""Seeded, time-bounded chaos soaks for the robustness layers.

Default mode soaks the self-healing device layer: a fault-wrapped
redelivering broker input, a memory buffer with bucket-exact coalescing, and
a ``device_pool`` tpu_inference stage whose steps are chaos-injected
(``hang`` / ``oom`` via the fault plugin's schedule, plus a ``disconnect``
on the input), run to completion under a wall-clock bound:

    python tools/chaos_soak.py --fast            # tier-1 smoke (~seconds)
    python tools/chaos_soak.py --seconds 120 --seed 3 --messages 256

``--burst`` soaks the overload-control layer instead (runtime/overload.py):
the ``burst`` input fault multiplies offered load past device throughput
(default 4x), once with the overload controller ON and once OFF:

    python tools/chaos_soak.py --burst --fast    # tier-1 smoke
    python tools/chaos_soak.py --burst --factor 4 --messages 96

Burst PASS means the accounting identity holds (every offered batch was
delivered or counted in ``arkflow_shed_total`` and routed to error_output —
zero silent loss), delivered-batch p99 end-to-end latency stays <= 2x the
configured deadline, AND the control run with the controller disabled
reproduces today's unbounded queue growth (p99 blows past the same bound).
Same seed => same fault schedule => same verdict; exit code 1 on FAIL.

``--noisy-tenant`` soaks the multi-tenant fairness layer: three tenants
share one stream, the noisy one offering 10x its configured rows/s quota
while the weighted-fair scheduler and per-tenant quotas protect the rest:

    python tools/chaos_soak.py --noisy-tenant --fast     # tier-1 smoke
    python tools/chaos_soak.py --noisy-tenant --seed 3

Noisy-tenant PASS means: every quiet tenant's DELIVERED p99 stays within
the deadline SLO, the noisy tenant's sheds are fully accounted
(``arkflow_shed_total{reason=quota}`` > 0 and offered == delivered + shed —
zero silent loss), and a duplicate-delivery burst against a response-cached
``tpu_inference`` stage shows cache hits > 0 with bitwise-identical
responses and exactly ONE device step for N concurrent duplicates.

``--swap`` soaks the zero-downtime model lifecycle (tpu/swap.py): under
sustained offered load, a rolling hot-swap runs across a ``device_pool: 2``
``tpu_inference`` stage AND a continuous ``tpu_generate`` server, with a
chaos-armed ``swap_corrupt`` checkpoint proving rollback first:

    python tools/chaos_soak.py --swap --fast     # tier-1 smoke
    python tools/chaos_soak.py --swap --seconds 120 --seed 3

Swap PASS means: the corrupt candidate was rejected/rolled back with the
old version serving throughout (version gauge unchanged, traffic
uninterrupted), the good swap then committed (version bumped, response
cache epoch-flushed), every offered row was delivered exactly where
expected with ZERO failed or lost requests (offered == delivered + shed,
and shed == 0 here), and delivered p99 stayed within the deadline SLO
across both swaps.

``--cluster`` soaks the disaggregated serving tier (runtime/cluster.py):
two local device-tier worker processes behind a ``remote_tpu`` ingest
stream — aggregate rows/s >= 1.7x one worker, byte-identical duplicates
hitting ONE worker's response cache cross-process, and a SIGKILL/restart of
a worker mid-load with zero silent loss::

    python tools/chaos_soak.py --cluster --fast    # tier-1 smoke
    python tools/chaos_soak.py --cluster --seed 3

``--preempt`` soaks the elastic fleet (runtime/fleet.py): three device-tier
worker processes behind a ``remote_tpu`` stream with the autoscaling
controller on — a preemption storm SIGKILLs workers one by one mid-load
(the controller detects each departure off missed heartbeats and respawns
to hold the floor), then a load ramp against a deliberately undersized
fleet must trigger a scale-out, with the newcomer warmed on the incumbent
shape grid::

    python tools/chaos_soak.py --preempt --fast    # tier-1 smoke
    python tools/chaos_soak.py --preempt --seed 3

Preempt PASS means: every kill was detected and counted, the fleet
respawned back to its floor under load, delivered p99 inter-arrival gap
stayed within the SLO (serving never wedged through a preemption), offered
== delivered + shed over distinct rows (zero silent loss), and the ramp
fired ``scale_out`` with zero dispatch failures before any shed.

``--disagg`` soaks prefill/decode disaggregation on the cluster plane
(runtime/cluster.py + tpu/serving.py): a mixed-length generation load
serves co-hosted (2 ``both`` workers) and disaggregated (1 prefill + 1
decode worker, KV pages streamed over ``kv_push``) at equal worker count,
then prefix-affinity on the prefill sub-ring and a mid-stream decode
SIGKILL::

    python tools/chaos_soak.py --disagg --fast    # tier-1 smoke
    python tools/chaos_soak.py --disagg --seed 3

Disagg PASS means: the disaggregated topology beats co-hosted on BOTH
worker-side TTFT p99 and tokens/sec when the host has >= 3 cores (on
smaller hosts everything timeshares — the verdict records the honest
ratios and gates on the invariants instead; so does ``--fast``, whose
phases last seconds and run beside a test suite), every KV page flowed
cross-process (``kv_pushed`` == ``kv_adopted``, zero refusals counted as
losses), duplicate prompts land on ONE prefill worker, and a decode worker
SIGKILLed mid-stream loses nothing — in-flight requests nack through
normal redelivery and re-prefill, offered == delivered + shed over
distinct rows, and the restarted decode worker adopts pages again.

``--partition`` soaks the partition-tolerant flight plane
(connect/chaoswire.py + runtime/cluster.py): two worker processes, one
fronted by a frame-aware chaos proxy that can black-hole one direction,
corrupt payload bytes under the crc32 trailer, or stall mid-frame — flipped
live mid-load::

    python tools/chaos_soak.py --partition --fast    # tier-1 smoke
    python tools/chaos_soak.py --partition --seed 3

Partition PASS means: a mid-load ONE-WAY partition of a worker (requests
flow, responses vanish) is detected within ``heartbeat_timeout``, hedged
dispatch keeps delivered p99 within max(2x, +250ms) of the no-fault
baseline with the hedge budget invariant intact; after the partition heals,
the zombie's fenced incarnation is rejected and counted
(``arkflow_cluster_fenced_total``) before the heal handshake re-admits it
under a fresh epoch; byte corruption is NEVER silent (counted crc failures
client- or worker-side, every row still delivered via ring failover); and a
corrupt-every-dispatch brownout with the retry budget ON keeps ring
retries/offered <= ratio + burst/offered with the overflow shed as
``reason=retry_budget``, while the budget-OFF control reproduces ~1.0x
retry amplification — zero silent loss (offered == delivered + shed over
distinct rows) in every phase.

Runs on the virtual-CPU JAX platform by default (no TPU needed; ``--burst``
never imports jax at all, and ``--cluster``/``--preempt``/``--disagg``/
``--partition`` parent processes don't either — only their worker
subprocesses); set ARKFLOW_SOAK_KEEP_ENV=1
to target whatever backend the environment provides.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _attach_tracing(verdict: dict, min_seq: int = 0,
                    forced_base: int = 0) -> dict:
    """Fold the trace layer's per-stage breakdown into a mode's verdict so
    every soak answers "WHERE did the time go", not just "how much". Also
    carries the forced-sample count — the fast modes assert shed/deadline
    traces were captured even when head sampling would have dropped them.
    ``min_seq``/``forced_base`` are per-run watermarks: the global store is
    process-wide, and absolute counters would let another mode's traces
    satisfy this mode's assertions (the registry-global flake class)."""
    from arkflow_tpu.obs.trace import global_tracer

    t = global_tracer()
    verdict["stage_breakdown"] = t.stage_breakdown(min_seq)
    verdict["tracing"] = {
        "forced_samples": max(
            0, t.summary()["forced_samples"] - forced_base),
        "pathological_retained": sum(
            1 for r in t.slowest(t.cfg.max_traces, min_seq)
            if r["status"] in ("shed", "deadline", "error")),
    }
    return verdict


def _tracing_watermark() -> tuple[int, int]:
    """(commit_seq, forced_samples) at a mode's start — the deltas feed
    ``_attach_tracing``."""
    from arkflow_tpu.obs.trace import global_tracer

    t = global_tracer()
    return t.commit_seq(), t.summary()["forced_samples"]


def _soak_config(seed: int, messages: int, pool: int, fast: bool) -> dict:
    """The soak pipeline as a plain config mapping (the fault schedule and
    every knob exercised here are exactly what a YAML stream would use)."""
    import random

    rng = random.Random(seed)
    payloads = [f"soak row {i:04d} {rng.randrange(1 << 30):08x}"
                for i in range(messages)]
    # fault positions are seeded so a verdict is reproducible bit-for-bit;
    # fast (smoke) mode pins them early — with only ~12 messages a seeded
    # position can exceed the total number of processor calls, and a fault
    # that never fires makes the smoke's "it really fired" assertions flaky
    if fast:
        hang_at, oom_at, disconnect_at = 2, 3, 4
    else:
        hang_at = rng.randrange(2, max(3, messages // 4))
        oom_at = hang_at + rng.randrange(2, 5)
        disconnect_at = rng.randrange(2, max(3, messages // 2))
    tiny_model = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
                  "ffn": 64, "max_positions": 64, "num_labels": 2}
    return {
        "name": "chaos-soak",
        "input": {
            "type": "fault",
            "seed": seed,
            "redeliver_unacked": True,
            "reconnect": {"initial_delay_ms": 1, "max_delay_ms": 50},
            "inner": {"type": "memory", "messages": payloads},
            "faults": [
                {"kind": "disconnect", "at": disconnect_at},
                {"kind": "latency", "every": 7, "duration": "1ms"},
            ],
        },
        "buffer": {
            "type": "memory",
            "capacity": 64,
            "timeout": "20ms",
            # bucket-exact coalescing: the OOM cap announcement must shrink
            # this grid mid-run (that's part of what the soak proves)
            "coalesce": {"batch_buckets": [2, 4], "deadline": "10ms"},
        },
        "pipeline": {
            "thread_num": 2,
            "max_delivery_attempts": 8,
            "processors": [{
                "type": "fault",
                "seed": seed,
                "faults": [
                    {"kind": "hang", "at": hang_at, "duration": "5s"},
                    {"kind": "oom", "at": oom_at},
                ] + ([] if fast else [
                    {"kind": "hang", "rate": 0.02, "times": 2, "duration": "5s"},
                    {"kind": "oom", "rate": 0.02, "times": 2},
                ]),
                "inner": {
                    "type": "tpu_inference",
                    "model": "bert_classifier",
                    "model_config": tiny_model,
                    "max_seq": 16,
                    "batch_buckets": [2, 4],
                    "seq_buckets": [16],
                    "device_pool": pool,
                    "warmup": True,  # honest steady-state step deadlines
                    "step_deadline": "500ms",
                    "step_deadline_first": "60s",
                    "health": {"probe_backoff": "100ms",
                               "probe_backoff_cap": "2s"},
                },
            }],
        },
        "output": {"type": "drop"},
    }


def run_soak(seconds: float = 60.0, seed: int = 7, messages: int = 48,
             pool: int = 2, fast: bool = False) -> dict:
    """Run the soak in-process and return the verdict dict. Importing this
    function does NOT touch jax; the caller owns platform env setup."""
    import asyncio

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.tpu.bucketing import bucket_cap_bus

    ensure_plugins_loaded()
    trace_seq0, trace_forced0 = _tracing_watermark()
    if fast:
        messages = min(messages, 12)
    cfg = StreamConfig.from_mapping(_soak_config(seed, messages, pool, fast))
    stream = build_stream(cfg)

    delivered: list[bytes] = []

    class _Collect(DropOutput):
        async def write(self, batch: MessageBatch) -> None:
            await super().write(batch)
            delivered.extend(batch.to_binary())

    stream.output = _Collect()
    pool_runner = stream.pipeline.processors[0]._inner.runner

    async def bounded_run() -> bool:
        cancel = asyncio.Event()
        task = asyncio.create_task(stream.run(cancel))
        done, _ = await asyncio.wait({task}, timeout=seconds)
        if done:
            task.result()  # surface a crashed stream as a FAIL with traceback
            return False
        cancel.set()  # wall-clock budget exhausted: drain and report wedged
        try:
            await asyncio.wait_for(task, timeout=15.0)
        except (asyncio.TimeoutError, Exception):
            task.cancel()
        return True

    async def heal_drain() -> None:
        """The finite message set may EOF inside a probe-backoff window;
        live traffic would keep probing, so emulate a few more batches until
        every member converges (bounded)."""
        import numpy as np

        members = getattr(pool_runner, "members", [pool_runner])
        probe_inputs = {"input_ids": np.ones((2, 16), np.int32),
                        "attention_mask": np.ones((2, 16), np.int32)}
        deadline = time.monotonic() + 10
        while (any(m.health.state not in ("healthy", "degraded") for m in members)
               and time.monotonic() < deadline):
            await asyncio.sleep(0.06)
            try:
                await pool_runner.infer(probe_inputs)
            except Exception:
                pass  # a failed probe re-arms the backoff; keep draining

    t0 = time.monotonic()
    try:
        wedged = asyncio.run(bounded_run())
        if not wedged:
            asyncio.run(heal_drain())
    finally:
        bucket_cap_bus().reset()  # in-process callers get a clean slate
    elapsed = time.monotonic() - t0

    expected = {f"soak row {i:04d}".encode() for i in range(messages)}
    got = [p.split(b" ", 3)[:3] for p in delivered]
    got_keys = [b" ".join(k) for k in got]
    missing = sorted(expected - set(got_keys))
    duplicates = len(got_keys) - len(set(got_keys))
    reg = global_registry()
    states = [m.health.state for m in getattr(pool_runner, "members", [pool_runner])]
    healthy_end = all(s in ("healthy", "degraded") for s in states)
    verdict = {
        "pass": bool(not wedged and not missing and healthy_end),
        "wedged": wedged,
        "elapsed_s": round(elapsed, 3),
        "seed": seed,
        "messages": messages,
        "delivered_rows": len(got_keys),
        "missing_rows": len(missing),
        "duplicate_rows": duplicates,
        "deadline_misses": reg.sum_values("arkflow_tpu_step_deadline_misses"),
        "oom_events": reg.sum_values("arkflow_tpu_oom_total"),
        "rebuilds": reg.sum_values("arkflow_tpu_runner_rebuilds_total"),
        "pool_failovers": reg.sum_values("arkflow_tpu_pool_failover_total"),
        "pool_probes": reg.sum_values("arkflow_tpu_pool_probes_total"),
        "pool_skips": reg.sum_values("arkflow_tpu_pool_skipped_unhealthy_total"),
        "runner_states": states,
    }
    if missing:
        verdict["missing_sample"] = [m.decode() for m in missing[:5]]
    return _attach_tracing(verdict, trace_seq0, trace_forced0)


def _burst_config(seed: int, messages: int, factor: int, fast: bool,
                  controlled: bool, name: str) -> dict:
    """Overload-soak pipeline: a redelivering broker whose ``burst`` fault
    amplifies every read ``factor``x, feeding a worker whose per-batch
    latency fault emulates a device step — offered load is structurally
    ``factor``x what the worker can absorb. ``controlled=False`` is the
    same pipeline minus the controller (the unbounded-queue baseline)."""
    step_ms = 10 if fast else 20
    payloads = [f"burst row {i:04d}" for i in range(messages)]
    pipeline = {
        "thread_num": 1 if fast else 2,
        # roomy fixed queue: deep enough that, uncontrolled, queue wait
        # grows far past the deadline (the pre-overload latency cliff);
        # controlled, the AIMD window is the effective limit instead
        "queue_size": 512,
        "processors": [{
            "type": "fault",
            "seed": seed,
            "faults": [
                {"kind": "latency", "every": 1, "times": 0,
                 "duration": f"{step_ms}ms"},
            ],
        }],
    }
    if controlled:
        pipeline["deadline_ms"] = _burst_deadline_ms(fast)
        pipeline["overload"] = {"max_window": 64, "interval": "10ms"}
    return {
        "name": name,
        "input": {
            "type": "fault",
            "seed": seed,
            "redeliver_unacked": True,
            "inner": {"type": "memory", "messages": payloads},
            "faults": [
                {"kind": "burst", "every": 1, "times": 0, "factor": factor},
            ],
        },
        "pipeline": pipeline,
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def _burst_deadline_ms(fast: bool) -> float:
    return 150.0 if fast else 250.0


def run_burst_soak(seconds: float = 60.0, seed: int = 7, messages: int = 48,
                   factor: int = 4, fast: bool = False) -> dict:
    """Run the overload soak (controller ON, then OFF) and return the
    verdict dict. Pure asyncio — never imports jax."""
    import asyncio

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream

    ensure_plugins_loaded()
    if fast:
        messages = min(messages, 12)
    deadline_ms = _burst_deadline_ms(fast)

    def run_variant(controlled: bool, name: str) -> dict:
        cfg = StreamConfig.from_mapping(
            _burst_config(seed, messages, factor, fast, controlled, name))
        stream = build_stream(cfg)

        delivered: list[bytes] = []
        shed: list[bytes] = []

        class _Collect(DropOutput):
            def __init__(self, sink: list[bytes]):
                self._sink = sink

            async def write(self, batch: MessageBatch) -> None:
                self._sink.extend(batch.to_binary())

        stream.output = _Collect(delivered)
        stream.error_output = _Collect(shed)

        async def bounded_run() -> bool:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            done, _ = await asyncio.wait({task}, timeout=seconds)
            if done:
                task.result()
                return False
            cancel.set()
            try:
                await asyncio.wait_for(task, timeout=15.0)
            except (asyncio.TimeoutError, Exception):
                task.cancel()
            return True

        t0 = time.monotonic()
        wedged = asyncio.run(bounded_run())
        elapsed = time.monotonic() - t0

        offered = int(stream.m_batches_in.value)
        shed_counts = ({r: int(c.value) for r, c in stream.overload.m_shed.items()}
                       if stream.overload is not None else {})
        expected = {f"burst row {i:04d}".encode() for i in range(messages)}
        seen = set(delivered) | set(shed)
        lost = sorted(expected - seen)
        p99_e2e_ms = stream.m_e2e_latency.quantile(0.99) * 1000.0
        p99_wait_ms = stream.m_queue_wait.quantile(0.99) * 1000.0
        out = {
            "wedged": wedged,
            "elapsed_s": round(elapsed, 3),
            "offered_batches": offered,
            "delivered_batches": len(delivered),
            "shed_batches": len(shed),
            "shed_by_reason": shed_counts,
            "lost_rows": len(lost),
            "e2e_p99_ms": round(p99_e2e_ms, 3),
            "queue_wait_p99_ms": round(p99_wait_ms, 3),
        }
        if controlled:
            # the accounting identity: every offered batch ended somewhere
            out["identity_ok"] = (
                offered == len(delivered) + len(shed)
                and sum(shed_counts.values()) == len(shed))
            out["p99_bounded"] = p99_e2e_ms <= 2.0 * deadline_ms
            out["overload_state"] = stream.overload.report()
        else:
            # no controller: everything is admitted and queue wait blows
            # straight past the bound the controlled run must hold
            out["overload_reproduced"] = p99_e2e_ms > 2.0 * deadline_ms
        if lost:
            out["lost_sample"] = [m.decode() for m in lost[:5]]
        return out

    import dataclasses

    from arkflow_tpu.obs.trace import global_tracer

    tracer = global_tracer()
    seq0, forced0 = _tracing_watermark()
    prev_cfg = tracer.cfg
    # run with head sampling OFF: any retained trace must then be a FORCED
    # one, proving shed/deadline-overrun traces are captured at ANY rate —
    # the diagnostic guarantee the trace layer exists for. replace() keeps
    # every other knob (incl. an operator's enabled=False) intact.
    tracer.configure(dataclasses.replace(prev_cfg, sample_rate=0.0))
    try:
        controlled = run_variant(True, "burst-soak-ctrl")
        uncontrolled = run_variant(False, "burst-soak-raw")
    finally:
        tracer.configure(prev_cfg)
    verdict = {
        "mode": "burst",
        "pass": bool(not controlled["wedged"]
                     and controlled["identity_ok"]
                     and controlled["p99_bounded"]
                     and controlled["lost_rows"] == 0
                     and controlled["shed_batches"] > 0
                     and uncontrolled["overload_reproduced"]),
        "seed": seed,
        "messages": messages,
        "factor": factor,
        "deadline_ms": deadline_ms,
        "controlled": controlled,
        "uncontrolled": uncontrolled,
    }
    _attach_tracing(verdict, seq0, forced0)
    # the soak shed batches (asserted above), so forced sampling MUST have
    # retained their traces; fast mode folds this into the verdict (unless
    # the operator disabled tracing outright — nothing to assert then)
    verdict["forced_sampling_ok"] = bool(
        not tracer.enabled
        or controlled["shed_batches"] == 0
        or (verdict["tracing"]["forced_samples"] > 0
            and verdict["tracing"]["pathological_retained"] > 0))
    if fast:
        verdict["pass"] = bool(verdict["pass"]
                               and verdict["forced_sampling_ok"])
    return verdict


QUIET_TENANTS = ("alpha", "beta")
NOISY_TENANT = "noisy"


def _noisy_config(seed: int, deadline_ms: float, step_ms: int, quota: int,
                  name: str) -> dict:
    """Multi-tenant overload pipeline: a per-batch latency fault emulates
    the device step; the overload controller meters the noisy tenant's
    rows/s quota and divides the admission window by weight. The input is
    swapped for the seeded tenant source after build (like the collectors)."""
    return {
        "name": name,
        "input": {"type": "memory", "messages": ["placeholder"]},
        "pipeline": {
            "thread_num": 2,
            "queue_size": 64,
            "deadline_ms": deadline_ms,
            "processors": [{
                "type": "fault",
                "seed": seed,
                "faults": [
                    {"kind": "latency", "every": 1, "times": 0,
                     "duration": f"{step_ms}ms"},
                ],
            }],
            "overload": {
                "max_window": 16,
                "interval": "10ms",
                "tenants": {
                    "burst": "1s",
                    "per_tenant": {
                        # the noisy tenant's CONTRACT: quota rows/s with a
                        # 1s burst allowance; quiet tenants are unmetered
                        # but their weight dominates the admission window
                        NOISY_TENANT: {"weight": 1, "rows_per_sec": quota},
                        QUIET_TENANTS[0]: {"weight": 4},
                        QUIET_TENANTS[1]: {"weight": 4},
                    },
                },
            },
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def run_noisy_tenant_soak(seconds: float = 60.0, seed: int = 7,
                          fast: bool = False) -> dict:
    """Run the multi-tenant fairness soak + the duplicate-burst cache phase
    and return the verdict dict. The fairness phase is pure asyncio; the
    cache phase builds a tiny ``tpu_inference`` stage (the caller owns jax
    platform env setup, like ``run_soak``)."""
    import asyncio
    import random
    from collections import deque

    trace_seq0, trace_forced0 = _tracing_watermark()

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import (
        Ack,
        Input,
        NoopAck,
        ensure_plugins_loaded,
    )
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.errors import EndOfInput
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream

    ensure_plugins_loaded()
    deadline_ms = 250.0
    step_ms = 3 if fast else 5
    quota = 16 if fast else 32          # noisy rows/s contract
    quiet_each = 16 if fast else 48     # per quiet tenant
    noisy_total = quota * 10            # the 10x-over-quota retry storm
    name = f"noisy-soak-{seed}"

    class _TenantSource(Input):
        """Seeded interleave of per-tenant single-row batches, tenant
        stamped input-side (static per-stream config analog). Reads are
        PACED: a 10x-over-quota offer is a sustained RATE, and on a warm
        host an unpaced deque would dump the whole schedule into admission
        in one burst — every noisy row sheds as fair-share ``queue`` before
        the rows/s TokenBucket can ever trip, and the ``quota`` assertion
        turns timing-flaky (it only passed on cold/slow runs)."""

        def __init__(self, schedule):
            self._items = deque(schedule)

        async def connect(self) -> None:
            return None

        async def read(self) -> tuple[MessageBatch, Ack]:
            if not self._items:
                raise EndOfInput()
            await asyncio.sleep(0.001)
            tenant, payload = self._items.popleft()
            batch = MessageBatch.new_binary([payload]).with_source(
                "tenant-soak").with_tenant(tenant)
            return batch, NoopAck()

    rng = random.Random(seed)
    schedule = [(NOISY_TENANT, f"{NOISY_TENANT} {i:05d}".encode())
                for i in range(noisy_total)]
    for t in QUIET_TENANTS:
        schedule += [(t, f"{t} {i:05d}".encode()) for i in range(quiet_each)]
    rng.shuffle(schedule)

    cfg = StreamConfig.from_mapping(
        _noisy_config(seed, deadline_ms, step_ms, quota, name))
    stream = build_stream(cfg)
    stream.input = _TenantSource(schedule)
    # metric series are registry-global (keyed on name+labels): a second
    # in-process run would otherwise read the first run's counts as its own
    offered0 = int(stream.m_batches_in.value)
    shed0 = {r: int(c.value) for r, c in stream.overload.m_shed.items()}

    delivered: list[tuple[str, bytes]] = []
    shed: list[tuple[str, bytes]] = []

    class _Collect(DropOutput):
        def __init__(self, sink):
            self._sink = sink

        async def write(self, batch: MessageBatch) -> None:
            tenant = batch.tenant("?")
            self._sink.extend((tenant, p) for p in batch.to_binary())

    stream.output = _Collect(delivered)
    stream.error_output = _Collect(shed)

    async def bounded_run() -> bool:
        cancel = asyncio.Event()
        task = asyncio.create_task(stream.run(cancel))
        done, _ = await asyncio.wait({task}, timeout=seconds)
        if done:
            task.result()
            return False
        cancel.set()
        try:
            await asyncio.wait_for(task, timeout=15.0)
        except (asyncio.TimeoutError, Exception):
            task.cancel()
        return True

    t0 = time.monotonic()
    wedged = asyncio.run(bounded_run())
    elapsed = time.monotonic() - t0

    ctrl = stream.overload
    offered = int(stream.m_batches_in.value) - offered0
    shed_by_reason = {r: int(c.value) - shed0.get(r, 0)
                      for r, c in ctrl.m_shed.items()}
    expected = {p for _, p in schedule}
    seen = {p for _, p in delivered} | {p for _, p in shed}
    lost = sorted(expected - seen)

    tenant_p99_ms = {}
    quiet_ok = True
    for t in QUIET_TENANTS:
        ts = ctrl.tenants.get(t)
        p99 = ts.m_e2e.quantile(0.99) * 1000.0 if ts is not None else float("nan")
        tenant_p99_ms[t] = round(p99, 3)
        delivered_t = sum(1 for tn, _ in delivered if tn == t)
        # the SLO is on DELIVERED batches; a quiet tenant must both deliver
        # and deliver fast — zero deliveries would vacuously "pass"
        quiet_ok = quiet_ok and delivered_t > 0 and p99 <= deadline_ms
    noisy = ctrl.tenants.get(NOISY_TENANT)
    noisy_sheds = ({r: int(c.value) for r, c in noisy.m_shed.items()}
                   if noisy is not None else {})

    fairness = {
        "wedged": wedged,
        "elapsed_s": round(elapsed, 3),
        "offered_batches": offered,
        "delivered_batches": len(delivered),
        "shed_batches": len(shed),
        "shed_by_reason": shed_by_reason,
        "noisy_shed_by_reason": noisy_sheds,
        "lost_rows": len(lost),
        "quiet_tenant_p99_ms": tenant_p99_ms,
        "deadline_ms": deadline_ms,
        # the accounting identity: every offered batch ended somewhere, and
        # every shed is reason-counted — zero silent loss
        "identity_ok": (offered == len(delivered) + len(shed)
                        and sum(shed_by_reason.values()) == len(shed)),
        "quota_sheds": shed_by_reason.get("quota", 0),
        "quiet_p99_ok": quiet_ok,
    }
    if lost:
        fairness["lost_sample"] = [p.decode() for p in lost[:5]]

    cache = asyncio.run(_duplicate_burst_cache_phase(fast))

    verdict = {
        "mode": "noisy-tenant",
        "pass": bool(not wedged
                     and fairness["identity_ok"]
                     and fairness["lost_rows"] == 0
                     and fairness["quota_sheds"] > 0
                     and fairness["quiet_p99_ok"]
                     and cache["pass"]),
        "seed": seed,
        "fairness": fairness,
        "cache": cache,
    }
    _attach_tracing(verdict, trace_seq0, trace_forced0)
    if fast and fairness["quota_sheds"] > 0:
        # quota sheds happened THIS run: their traces must be in the store
        # (delta-watermarked — another mode's traces can't satisfy this)
        from arkflow_tpu.obs.trace import global_tracer

        verdict["pass"] = bool(
            verdict["pass"]
            and (not global_tracer().enabled
                 or verdict["tracing"]["pathological_retained"] > 0))
    return verdict


async def _duplicate_burst_cache_phase(fast: bool) -> dict:
    """Duplicate-delivery burst against a response-cached tpu_inference
    stage: N concurrent identical batches must collapse onto ONE device
    step and every response must be bitwise-identical."""
    import asyncio

    duplicates = 4 if fast else 12

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, ensure_plugins_loaded
    from arkflow_tpu.components.registry import build_component

    ensure_plugins_loaded()
    tiny_model = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
                  "ffn": 64, "max_positions": 64, "num_labels": 2}
    proc = build_component("processor", {
        "type": "tpu_inference",
        "model": "bert_classifier",
        "model_config": tiny_model,
        "max_seq": 16,
        "batch_buckets": [2, 4],
        "seq_buckets": [16],
        "warmup": True,
        "response_cache": {"capacity": 64, "ttl": "60s"},
    }, Resource())

    # prime with a DIFFERENT payload so compiles/warmup steps are excluded
    # from the duplicate-burst step count
    await proc.process(MessageBatch.new_binary([b"prime row"]))
    base_steps = proc.runner.m_infer.count

    dup = MessageBatch.new_binary([b"dup row 0", b"dup row 1"]).with_tenant(
        NOISY_TENANT)
    results = await asyncio.gather(
        *[proc.process(dup) for _ in range(duplicates)])
    late = await proc.process(dup)  # post-in-flight: a pure cache hit
    steps = proc.runner.m_infer.count - base_steps

    first = results[0][0]
    identical = all(r[0] == first for r in results) and late[0] == first
    cache = proc.cache
    out = {
        "duplicates_offered": duplicates + 1,
        "device_steps_for_duplicates": int(steps),
        "hits": int(cache.m_hits.value),
        "collapsed": int(cache.m_collapsed.value),
        "misses": int(cache.m_misses.value),
        "bitwise_identical": bool(identical),
    }
    out["pass"] = bool(steps == 1 and identical
                       and out["hits"] + out["collapsed"] >= duplicates)
    return out


def _swap_pool_config(seed: int, messages: int) -> dict:
    """Swap-soak pipeline A: sustained paced load through a fault-wrapped
    redelivering broker into a ``device_pool: 2`` inference stage with a
    response cache. The processor fault schedule arms ``swap_corrupt`` on
    the SECOND processor call, so the first swap the driver triggers
    consumes a mangled candidate and must roll back under live traffic."""
    payloads = [f"swap row {i:04d}" for i in range(messages)]
    tiny_model = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
                  "ffn": 64, "max_positions": 64, "num_labels": 2}
    return {
        "name": "swap-soak-pool",
        "input": {
            "type": "fault",
            "seed": seed,
            "redeliver_unacked": True,
            "inner": {"type": "memory", "messages": payloads},
            "faults": [
                # pace reads so offered load SUSTAINS across both swaps
                {"kind": "latency", "every": 1, "times": 0, "duration": "4ms"},
            ],
        },
        "buffer": {
            "type": "memory",
            "capacity": 64,
            "timeout": "20ms",
            "coalesce": {"batch_buckets": [2, 4], "deadline": "10ms"},
        },
        "pipeline": {
            "thread_num": 2,
            "max_delivery_attempts": 4,
            "processors": [{
                "type": "fault",
                "seed": seed,
                "faults": [
                    {"kind": "swap_corrupt", "at": 2},
                ],
                "inner": {
                    "type": "tpu_inference",
                    "model": "bert_classifier",
                    "model_config": tiny_model,
                    "max_seq": 16,
                    "batch_buckets": [2, 4],
                    "seq_buckets": [16],
                    "device_pool": 2,
                    "warmup": True,
                    "step_deadline": "5s",
                    "step_deadline_first": "120s",
                    "response_cache": {"capacity": 64, "ttl": "60s"},
                    "swap": {"canary": {"rows": 4, "min_agreement": 1.0}},
                },
            }],
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def _swap_generate_config(seed: int, messages: int) -> dict:
    """Swap-soak pipeline B: continuous ``tpu_generate`` serving — the swap
    must wait for the slot grid to drain, flip, rebuild the jits, and reset
    the page pools + prefix cache, with every queued request completing."""
    payloads = [f"gen prompt {i:04d} lorem ipsum" for i in range(messages)]
    tiny_model = {"vocab_size": 128, "dim": 16, "layers": 1, "heads": 2,
                  "kv_heads": 2, "ffn": 32, "max_seq": 64}
    return {
        "name": "swap-soak-generate",
        "input": {
            "type": "fault",
            "seed": seed,
            "redeliver_unacked": True,
            "inner": {"type": "memory", "messages": payloads},
            "faults": [
                {"kind": "latency", "every": 1, "times": 0, "duration": "4ms"},
            ],
        },
        "pipeline": {
            "thread_num": 2,
            "max_delivery_attempts": 4,
            "processors": [{
                "type": "tpu_generate",
                "model": "decoder_lm",
                "model_config": tiny_model,
                "max_input": 16,
                "max_new_tokens": 4,
                "batch_buckets": [2],
                "seq_buckets": [16],
                "serving": "continuous",
                "slots": 2,
                "page_size": 4,
                "prefix_cache_pages": 8,
                "swap": {"canary": {"rows": 4}, "drain_timeout": "30s"},
            }],
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def run_swap_soak(seconds: float = 120.0, seed: int = 7, messages: int = 64,
                  fast: bool = False) -> dict:
    """Run the model-lifecycle soak and return the verdict dict: a corrupt
    candidate rolled back + a good rolling swap committed across a device
    pool (phase A) and a continuous generation server (phase B), both under
    sustained offered load with zero failed/lost requests and bounded
    delivered p99. The caller owns jax platform env setup (see main)."""
    trace_seq0, trace_forced0 = _tracing_watermark()
    import asyncio
    import tempfile

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.errors import SwapError
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.tpu import checkpoint

    ensure_plugins_loaded()
    if fast:
        messages = min(messages, 24)
    # generous on a 2-core CPU host: pool steps are ~ms but the soak shares
    # the host with coalescing/redelivery bookkeeping and the swap itself
    pool_slo_ms = 2000.0
    gen_slo_ms = 20000.0  # the drain+rebuild window queues requests briefly
    ckpt_dir = tempfile.mkdtemp(prefix="arkflow-swap-soak-")

    class _Collect(DropOutput):
        def __init__(self, sink: list):
            self._sink = sink

        async def write(self, batch: MessageBatch) -> None:
            self._sink.extend(batch.to_binary())

    def phase_pool() -> dict:
        cfg = StreamConfig.from_mapping(_swap_pool_config(seed, messages))
        stream = build_stream(cfg)
        delivered: list = []
        failed: list = []
        stream.output = _Collect(delivered)
        stream.error_output = _Collect(failed)
        proc = stream.pipeline.processors[0]  # the fault wrapper
        inner = getattr(proc, "_inner", proc)  # the tpu_inference stage
        swapper = proc.swapper
        pool = proc.runner
        import os

        ck = os.path.join(ckpt_dir, "pool")
        checkpoint.save(ck, pool.members[0].params)

        events: dict = {"corrupt_rolled_back": False, "good_committed": False}

        async def driver() -> None:
            # wait for live traffic AND the chaos schedule to arm the
            # corrupt fault (it fires on the second processor call)
            deadline = time.monotonic() + seconds
            while (len(delivered) < 4 or not swapper._chaos) \
                    and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            try:
                await swapper.swap(ck)
            except SwapError:
                events["corrupt_rolled_back"] = True
            events["version_after_corrupt"] = swapper.version
            try:
                await swapper.swap(ck)
                events["good_committed"] = True
            except SwapError as e:
                events["good_error"] = str(e)

        async def bounded() -> bool:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            drv = asyncio.create_task(driver())
            done, _ = await asyncio.wait({task}, timeout=seconds)
            wedged = not done
            if done:
                task.result()
            else:
                cancel.set()
                try:
                    await asyncio.wait_for(task, timeout=15.0)
                except (asyncio.TimeoutError, Exception):
                    task.cancel()
            try:
                await asyncio.wait_for(drv, timeout=10.0)
            except (asyncio.TimeoutError, Exception):
                drv.cancel()
            return wedged

        t0 = time.monotonic()
        wedged = asyncio.run(bounded())
        elapsed = time.monotonic() - t0
        expected = {f"swap row {i:04d}".encode() for i in range(messages)}
        lost = sorted(expected - set(delivered))
        p99_ms = stream.m_e2e_latency.quantile(0.99) * 1000.0
        rep = swapper.report()
        cache = inner.cache
        out = {
            "wedged": wedged,
            "elapsed_s": round(elapsed, 3),
            "offered_rows": messages,
            "delivered_rows": len(delivered),
            "failed_rows": len(failed),
            "lost_rows": len(lost),
            "e2e_p99_ms": round(p99_ms, 3),
            "slo_ms": pool_slo_ms,
            "corrupt_rolled_back": events["corrupt_rolled_back"],
            "version_after_corrupt": events.get("version_after_corrupt"),
            "good_committed": events["good_committed"],
            "swap": rep,
            "cache_epoch": cache.epoch if cache is not None else None,
            "runner_states": [m.health.state for m in pool.members],
        }
        if events.get("good_error"):
            out["good_error"] = events["good_error"]
        if lost:
            out["lost_sample"] = [x.decode() for x in lost[:5]]
        out["pass"] = bool(
            not wedged
            and out["corrupt_rolled_back"]
            and out["version_after_corrupt"] == 0
            and out["good_committed"]
            and rep["version"] == 1 and rep["rolled_back"] == 1
            and out["cache_epoch"] == 1  # flushed on commit, NOT on rollback
            and out["lost_rows"] == 0 and out["failed_rows"] == 0
            and p99_ms <= pool_slo_ms)
        return out

    def phase_generate() -> dict:
        cfg = StreamConfig.from_mapping(_swap_generate_config(seed, messages))
        stream = build_stream(cfg)
        delivered: list = []
        failed: list = []
        stream.output = _Collect(delivered)
        stream.error_output = _Collect(failed)
        proc = stream.pipeline.processors[0]
        swapper = proc.swapper
        import os

        ck = os.path.join(ckpt_dir, "generate")
        checkpoint.save(ck, proc.host_params)

        events: dict = {"good_committed": False}

        async def driver() -> None:
            deadline = time.monotonic() + seconds
            while len(delivered) < 4 and time.monotonic() < deadline:
                await asyncio.sleep(0.02)
            try:
                await swapper.swap(ck)
                events["good_committed"] = True
            except SwapError as e:
                events["good_error"] = str(e)

        async def bounded() -> bool:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            drv = asyncio.create_task(driver())
            done, _ = await asyncio.wait({task}, timeout=seconds)
            wedged = not done
            if done:
                task.result()
            else:
                cancel.set()
                try:
                    await asyncio.wait_for(task, timeout=15.0)
                except (asyncio.TimeoutError, Exception):
                    task.cancel()
            try:
                await asyncio.wait_for(drv, timeout=10.0)
            except (asyncio.TimeoutError, Exception):
                drv.cancel()
            return wedged

        t0 = time.monotonic()
        wedged = asyncio.run(bounded())
        elapsed = time.monotonic() - t0
        # delivered batches carry the original payload column; row count is
        # the loss check (the generated column rides along as extra data)
        expected = {f"gen prompt {i:04d} lorem ipsum".encode()
                    for i in range(messages)}
        lost = sorted(expected - set(delivered))
        p99_ms = stream.m_e2e_latency.quantile(0.99) * 1000.0
        rep = swapper.report()
        srv = proc._server
        out = {
            "wedged": wedged,
            "elapsed_s": round(elapsed, 3),
            "offered_rows": messages,
            "delivered_rows": len(delivered),
            "failed_rows": len(failed),
            "lost_rows": len(lost),
            "e2e_p99_ms": round(p99_ms, 3),
            "slo_ms": gen_slo_ms,
            "good_committed": events["good_committed"],
            "swap": rep,
            "prefix_cache_entries_after": len(srv._prefix_cache),
            "server_state": srv.core.health.state,
        }
        if events.get("good_error"):
            out["good_error"] = events["good_error"]
        if lost:
            out["lost_sample"] = [x.decode() for x in lost[:5]]
        out["pass"] = bool(
            not wedged
            and out["good_committed"]
            and rep["version"] == 1
            and out["lost_rows"] == 0 and out["failed_rows"] == 0
            and p99_ms <= gen_slo_ms)
        return out

    pool_phase = phase_pool()
    gen_phase = phase_generate()
    return _attach_tracing({
        "mode": "swap",
        "pass": bool(pool_phase["pass"] and gen_phase["pass"]),
        "seed": seed,
        "messages": messages,
        "pool": pool_phase,
        "generate": gen_phase,
    }, trace_seq0, trace_forced0)


# -- cluster soak (runtime/cluster.py): disaggregated ingest/device tiers --


def _cluster_worker_config(seed: int, step_ms: int) -> dict:
    """Device-tier worker config: a tiny response-cached bert behind a fixed
    per-batch latency fault. The sleep emulates a device step that DWARFS
    host compute, so the soak's scaling ratio measures the cluster's routing
    and pipelining rather than host-cpu contention (the same discipline as
    the burst soak's worker)."""
    tiny_model = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
                  "ffn": 64, "max_positions": 64, "num_labels": 2}
    return {
        "worker": {"max_in_flight": 1},
        "processors": [{
            "type": "fault",
            "seed": seed,
            "faults": [{"kind": "latency", "every": 1, "times": 0,
                        "duration": f"{step_ms}ms"}],
            "inner": {
                "type": "tpu_inference",
                "model": "bert_classifier",
                "model_config": tiny_model,
                "max_seq": 16,
                "batch_buckets": [2],
                "seq_buckets": [16],
                "warmup": True,
                "response_cache": {"capacity": 512},
            },
        }],
    }


def _cluster_ingest_config(name: str, urls: list[str], payloads: list[str],
                           *, threads: int = 4, redeliver_seed=None) -> dict:
    """Ingest-tier stream: memory source -> remote_tpu dispatch -> collect.
    ``redeliver_seed`` wraps the source in the in-process broker sim so a
    nacked batch is redelivered (the chaos phase's at-least-once leg)."""
    input_cfg: dict = {"type": "memory", "messages": payloads}
    if redeliver_seed is not None:
        input_cfg = {
            "type": "fault",
            "seed": redeliver_seed,
            "redeliver_unacked": True,
            "inner": input_cfg,
            "faults": [{"kind": "latency", "every": 7, "times": 0,
                        "duration": "1ms"}],
        }
    return {
        "name": name,
        "input": input_cfg,
        "pipeline": {
            "thread_num": threads,
            "max_delivery_attempts": 8,
            "processors": [{
                "type": "remote_tpu",
                "name": name,
                "workers": urls,
                "heartbeat": "250ms",
                "connect_timeout": "2s",
                "request_timeout": "30s",
            }],
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def run_cluster_soak(seconds: float = 60.0, seed: int = 7,
                     fast: bool = False) -> dict:
    """2-process device-tier soak (runtime/cluster.py): spawns two local
    cluster workers, then proves

    - near-linear scaling: aggregate rows/s with both workers >= 1.7x one
      worker (each worker's step is latency-emulated, so the ratio measures
      routing/pipelining, not host cpu);
    - hash affinity: byte-identical duplicate batches all route to ONE
      worker and hit its response cache cross-process;
    - chaos: a worker SIGKILLed mid-load loses nothing (in-flight batches
      fail over along the hash ring; the fleet serves on N-1) and, once
      restarted, registers and serves again.

    The parent process never imports jax — only the worker subprocesses do.
    """
    trace_seq0, trace_forced0 = _tracing_watermark()
    import asyncio
    import os
    import socket as socket_mod
    import subprocess
    import tempfile

    import yaml

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.runtime.cluster import ClusterDispatcher
    from arkflow_tpu.utils.cleanenv import pin_cpu_env

    ensure_plugins_loaded()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    step_ms = 50 if fast else 60
    n_single = 24 if fast else 48      # throughput phase, one worker
    n_dual = 2 * n_single              # throughput phase, both workers
    k_dup = 8 if fast else 12          # affinity phase duplicates
    m_chaos = 48 if fast else 96       # chaos phase messages
    startup_budget = 240.0

    def free_port() -> int:
        s = socket_mod.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="arkflow-cluster-soak-")
    cfg_path = os.path.join(tmp, "worker.yaml")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(_cluster_worker_config(seed, step_ms), f)

    ports = [free_port(), free_port()]
    urls = [f"arkflow://127.0.0.1:{p}" for p in ports]
    logs = [os.path.join(tmp, f"worker-{i}.log") for i in range(2)]

    def spawn(i: int) -> subprocess.Popen:
        env = dict(os.environ)
        pin_cpu_env(env, n_devices=1)
        return subprocess.Popen(
            [sys.executable, "-m", "arkflow_tpu", "--cluster-worker",
             "--config", cfg_path, "--host", "127.0.0.1",
             "--port", str(ports[i]), "--worker-id", f"soak-w{i}"],
            cwd=repo_root, env=env,
            stdout=open(logs[i], "ab"), stderr=subprocess.STDOUT)

    async def wait_ready(wait_urls: list[str], budget_s: float) -> None:
        """Poll register until every listed worker answers (warmup compiles
        happen before the port opens, so 'answers' means 'ready')."""
        probe = ClusterDispatcher(wait_urls, name="cluster-soak-probe",
                                  heartbeat_s=999.0, connect_timeout_s=1.0)
        deadline = time.monotonic() + budget_s
        while True:
            await asyncio.gather(
                *(probe._probe(w) for w in probe.workers.values()),
                return_exceptions=True)
            if all(w.alive for w in probe.workers.values()):
                return
            if time.monotonic() >= deadline:
                down = [w.url for w in probe.workers.values() if not w.alive]
                raise RuntimeError(
                    f"cluster workers not ready within {budget_s:.0f}s: {down} "
                    f"(see {tmp}/worker-*.log)")
            await asyncio.sleep(0.5)

    async def heartbeat(url: str) -> dict:
        probe = ClusterDispatcher([url], name="cluster-soak-probe",
                                  heartbeat_s=999.0, connect_timeout_s=1.0)
        return await probe._unary(probe.workers[url], {"action": "heartbeat"})

    class _Collect(DropOutput):
        def __init__(self, sink: list):
            self._sink = sink

        async def write(self, batch: MessageBatch) -> None:
            self._sink.extend(batch.to_binary())

    def run_phase(cfg_map: dict, budget_s: float, driver=None) -> dict:
        """Build + run one ingest stream to EOF (bounded); returns the
        collected rows, the stream and wall-clock of the run itself."""
        stream = build_stream(StreamConfig.from_mapping(cfg_map))
        delivered: list[bytes] = []
        shed: list[bytes] = []
        stream.output = _Collect(delivered)
        stream.error_output = _Collect(shed)

        out: dict = {"delivered": delivered, "shed": shed, "stream": stream}

        async def bounded() -> None:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            driver_task = (asyncio.create_task(driver(stream, delivered))
                           if driver is not None else None)
            t0 = time.monotonic()
            done, _ = await asyncio.wait({task}, timeout=budget_s)
            out["elapsed_s"] = time.monotonic() - t0
            out["wedged"] = not done
            if done:
                task.result()  # surface a crashed stream with its traceback
            else:
                cancel.set()
                try:
                    await asyncio.wait_for(task, timeout=15.0)
                except (asyncio.TimeoutError, Exception):
                    task.cancel()
            if driver_task is not None:
                try:
                    await asyncio.wait_for(driver_task, timeout=5.0)
                except (asyncio.TimeoutError, Exception):
                    driver_task.cancel()

        asyncio.run(bounded())
        return out

    procs: list = [None, None]
    verdict: dict = {"mode": "cluster", "seed": seed, "step_ms": step_ms,
                     "workers": urls}
    t_start = time.monotonic()
    try:
        procs[0] = spawn(0)
        procs[1] = spawn(1)
        asyncio.run(wait_ready(urls, startup_budget))
        verdict["startup_s"] = round(time.monotonic() - t_start, 3)

        # -- phase 1: aggregate throughput, 1 worker vs 2 ------------------
        pay1 = [f"tput-single {i:05d}" for i in range(n_single)]
        one = run_phase(_cluster_ingest_config(
            "cluster-soak-tput1", urls[:1], pay1), seconds)
        pay2 = [f"tput-dual {i:05d}" for i in range(n_dual)]
        two = run_phase(_cluster_ingest_config(
            "cluster-soak-tput2", urls, pay2), seconds)
        rows1 = len(one["delivered"]) / max(one["elapsed_s"], 1e-9)
        rows2 = len(two["delivered"]) / max(two["elapsed_s"], 1e-9)
        ratio = rows2 / max(rows1, 1e-9)
        throughput = {
            "single_rows_per_s": round(rows1, 2),
            "dual_rows_per_s": round(rows2, 2),
            "scaling_ratio": round(ratio, 3),
            "single_delivered": len(one["delivered"]),
            "dual_delivered": len(two["delivered"]),
            "ratio_ok": (ratio >= 1.7
                         and len(one["delivered"]) == n_single
                         and len(two["delivered"]) == n_dual),
        }
        verdict["throughput"] = throughput

        # -- phase 2: affinity — duplicates hit ONE worker's cache ---------
        hb_before = {u: asyncio.run(heartbeat(u)) for u in urls}
        dup = run_phase(_cluster_ingest_config(
            "cluster-soak-dup", urls, ["duplicate request"] * k_dup,
            threads=1), seconds)
        hb_after = {u: asyncio.run(heartbeat(u)) for u in urls}

        def cache_hits(hb: dict) -> int:
            return sum(int(c.get("hits", 0)) for c in hb.get("caches", []))

        served_delta = {u: int(hb_after[u].get("served", 0))
                        - int(hb_before[u].get("served", 0)) for u in urls}
        hits_delta = {u: cache_hits(hb_after[u]) - cache_hits(hb_before[u])
                      for u in urls}
        target = max(served_delta, key=lambda u: served_delta[u])
        affinity = {
            "delivered": len(dup["delivered"]),
            "served_by_worker": served_delta,
            "cache_hits_by_worker": hits_delta,
            "one_worker_took_all": served_delta[target] == k_dup and all(
                served_delta[u] == 0 for u in urls if u != target),
            # cross-process response-cache affinity: the first duplicate
            # misses, every later one hits the SAME worker's cache
            "cache_hits_ok": hits_delta[target] >= k_dup - 1,
        }
        affinity["pass"] = bool(len(dup["delivered"]) == k_dup
                                and affinity["one_worker_took_all"]
                                and affinity["cache_hits_ok"])
        verdict["affinity"] = affinity

        # -- phase 3: kill/restart a worker under load ---------------------
        kill_at = max(2, m_chaos // 4)
        chaos_events: dict = {"killed": False, "restarted": False}

        async def chaos_driver(stream, delivered) -> None:
            while len(delivered) < kill_at:
                await asyncio.sleep(0.01)
            procs[1].kill()
            procs[1].wait()
            chaos_events["killed"] = True
            chaos_events["killed_at_delivered"] = len(delivered)
            await asyncio.sleep(1.0)
            procs[1] = spawn(1)  # restart on the same port, same identity
            chaos_events["restarted"] = True

        pay3 = [f"chaos row {i:05d}" for i in range(m_chaos)]
        chaos = run_phase(_cluster_ingest_config(
            "cluster-soak-chaos", urls, pay3, redeliver_seed=seed),
            max(seconds, 60.0), driver=chaos_driver)
        expected = set(p.encode() for p in pay3)
        seen = set(chaos["delivered"]) | set(chaos["shed"])
        lost = sorted(expected - seen)
        dispatcher = chaos["stream"].pipeline.processors[0].dispatcher
        chaos_out = {
            **chaos_events,
            "wedged": chaos["wedged"],
            "offered_rows": m_chaos,
            "delivered_rows": len(chaos["delivered"]),
            "shed_rows": len(chaos["shed"]),
            "duplicate_rows": len(chaos["delivered"]) - len(set(chaos["delivered"])),
            "lost_rows": len(lost),
            "ring_retries": int(dispatcher.m_retries.value),
            # offered == delivered + shed over DISTINCT rows: at-least-once
            # may duplicate, but nothing vanishes silently
            "identity_ok": (len(lost) == 0
                            and len(expected & set(chaos["delivered"]))
                            + len(expected & set(chaos["shed"]) - set(chaos["delivered"]))
                            == m_chaos),
        }
        if lost:
            chaos_out["lost_sample"] = [x.decode() for x in lost[:5]]

        # the killed worker must come back: register again AND serve
        revived = False
        revive_error = None
        try:
            asyncio.run(wait_ready(urls[1:], startup_budget))
            post = run_phase(_cluster_ingest_config(
                "cluster-soak-revive", urls[1:],
                [f"revive row {i}" for i in range(2)], threads=1), seconds)
            revived = len(post["delivered"]) == 2
        except Exception as e:
            revive_error = f"{type(e).__name__}: {e}"
        chaos_out["revived"] = revived
        if revive_error:
            chaos_out["revive_error"] = revive_error
        chaos_out["pass"] = bool(not chaos["wedged"]
                                 and chaos_out["identity_ok"]
                                 and chaos_events["killed"]
                                 and revived)
        verdict["chaos"] = chaos_out

        verdict["pass"] = bool(throughput["ratio_ok"]
                               and affinity["pass"]
                               and chaos_out["pass"])
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=5)
                except Exception:
                    pass
    verdict["elapsed_s"] = round(time.monotonic() - t_start, 3)
    # ingest-side trace store: includes the worker-tier remote_* spans
    # adopted over the flight plane, so the breakdown spans BOTH tiers
    return _attach_tracing(verdict, trace_seq0, trace_forced0)


# -- partition-tolerance soak (connect/chaoswire.py + runtime/cluster.py) -----


def _partition_ingest_config(name: str, urls: list[str], payloads: list[str],
                             *, threads: int = 4, heartbeat: str = "250ms",
                             heartbeat_timeout: str = "1s",
                             request_timeout: str = "4s",
                             hedge=None, retry_budget=None,
                             net_faults=None, seed: int = 0) -> dict:
    """Ingest-tier stream for the partition soak: memory source ->
    remote_tpu (hedging / retry-budget knobs exposed) -> collect.
    ``net_faults`` wraps the dispatch stage in the fault plugin so
    ``net_*`` chaos arms on the dispatcher's own connections."""
    proc: dict = {
        "type": "remote_tpu",
        "name": name,
        "workers": urls,
        "heartbeat": heartbeat,
        "heartbeat_timeout": heartbeat_timeout,
        "connect_timeout": "2s",
        "request_timeout": request_timeout,
    }
    if hedge is not None:
        proc["hedge"] = hedge
    if retry_budget is not None:
        proc["retry_budget"] = retry_budget
    if net_faults is not None:
        proc = {"type": "fault", "seed": seed, "faults": net_faults,
                "inner": proc}
    return {
        "name": name,
        "input": {"type": "memory", "messages": payloads},
        "pipeline": {
            "thread_num": threads,
            "max_delivery_attempts": 8,
            "processors": [proc],
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def run_partition_soak(seconds: float = 90.0, seed: int = 7,
                       fast: bool = False) -> dict:
    """Partition-tolerance soak (connect/chaoswire.py + the flight-plane
    hardening in runtime/cluster.py): two local device-tier workers, one
    fronted by a frame-aware chaos proxy, prove

    - hedged dispatch rides out a mid-load ONE-WAY partition (requests
      flow, responses black-holed): the wedged owner is detected within
      ``heartbeat_timeout``, delivered p99 stays bounded against the
      no-fault baseline, the hedge budget invariant holds, and zero rows
      are lost (offered == delivered + shed over distinct rows);
    - incarnation fencing: the black-holed (never dead) worker's epoch is
      fenced on detection; after the partition heals, its zombie report is
      REJECTED and counted (``arkflow_cluster_fenced_total``), the heal
      handshake re-mints, and the worker is re-admitted under the fresh
      epoch;
    - corruption is never silent: with the proxy flipping one byte per
      frame, every damaged exchange surfaces as a counted crc32 failure
      (client ``arkflow_cluster_frame_error_total`` or the worker's
      ``crc_errors``) and every row still delivers via ring failover;
    - retry-budget brownout containment: a corrupt-every-dispatch storm
      (the ``net_corrupt`` fault kind, armed through the fault plugin)
      with the budget OFF reproduces retries/offered ~= 1.0; with the
      budget ON the ratio stays <= ratio + burst/offered and the overflow
      sheds as ``reason=retry_budget`` through error_output.

    The parent process never imports jax — only the worker subprocesses do.
    """
    trace_seq0, trace_forced0 = _tracing_watermark()
    import asyncio
    import os
    import socket as socket_mod
    import subprocess
    import tempfile

    import yaml

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.connect.chaoswire import ChaosProxy
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.runtime.cluster import ClusterDispatcher
    from arkflow_tpu.utils.cleanenv import pin_cpu_env

    ensure_plugins_loaded()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    step_ms = 40 if fast else 50
    n_base = 12 if fast else 24          # baseline phase messages
    # enough post-flip load that the stream OUTLIVES probe-based detection
    # (<= heartbeat + heartbeat_timeout ~ 1.3s; the surviving worker
    # serializes ~50ms/row, so ~38 post-flip rows ~ 2s of partitioned load)
    n_part = 48 if fast else 96          # partition phase messages
    flip_at = 10                         # >= 8: the hedge p99-EWMA is warm
    n_corrupt = 8 if fast else 16        # corruption phase messages
    n_brown = 12 if fast else 24         # brownout phase messages (per run)
    rb_ratio, rb_burst = 0.25, 2
    hb_s, ht_s = 0.25, 1.0
    hedge_cfg = {"delay": "auto", "max_fraction": 0.5, "burst": 16,
                 "min_delay": "10ms"}
    startup_budget = 240.0

    def free_port() -> int:
        s = socket_mod.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="arkflow-partition-soak-")
    cfg_path = os.path.join(tmp, "worker.yaml")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(_cluster_worker_config(seed, step_ms), f)

    ports = [free_port(), free_port()]
    urls = [f"arkflow://127.0.0.1:{p}" for p in ports]
    logs = [os.path.join(tmp, f"worker-{i}.log") for i in range(2)]

    def spawn(i: int) -> subprocess.Popen:
        env = dict(os.environ)
        pin_cpu_env(env, n_devices=1)
        return subprocess.Popen(
            [sys.executable, "-m", "arkflow_tpu", "--cluster-worker",
             "--config", cfg_path, "--host", "127.0.0.1",
             "--port", str(ports[i]), "--worker-id", f"part-w{i}"],
            cwd=repo_root, env=env,
            stdout=open(logs[i], "ab"), stderr=subprocess.STDOUT)

    async def wait_ready(wait_urls: list[str], budget_s: float) -> None:
        probe = ClusterDispatcher(wait_urls, name="partition-soak-probe",
                                  heartbeat_s=999.0, connect_timeout_s=1.0)
        deadline = time.monotonic() + budget_s
        while True:
            await asyncio.gather(
                *(probe._probe(w) for w in probe.workers.values()),
                return_exceptions=True)
            if all(w.alive for w in probe.workers.values()):
                return
            if time.monotonic() >= deadline:
                down = [w.url for w in probe.workers.values() if not w.alive]
                raise RuntimeError(
                    f"cluster workers not ready within {budget_s:.0f}s: {down} "
                    f"(see {tmp}/worker-*.log)")
            await asyncio.sleep(0.5)

    class _Collect(DropOutput):
        def __init__(self, sink: list):
            self._sink = sink

        async def write(self, batch: MessageBatch) -> None:
            self._sink.extend(batch.to_binary())

    async def phase(cfg_map: dict, budget_s: float, driver=None) -> dict:
        """Build + run one ingest stream to EOF (bounded), in the CURRENT
        loop — the chaos proxy's server lives in this loop, so every phase
        shares it (unlike the other soaks' one-loop-per-phase shape)."""
        stream = build_stream(StreamConfig.from_mapping(cfg_map))
        delivered: list[bytes] = []
        shed: list[bytes] = []
        stream.output = _Collect(delivered)
        stream.error_output = _Collect(shed)
        out: dict = {"delivered": delivered, "shed": shed, "stream": stream}
        cancel = asyncio.Event()
        task = asyncio.create_task(stream.run(cancel))
        driver_task = (asyncio.create_task(driver(stream, delivered))
                       if driver is not None else None)
        t0 = time.monotonic()
        done, _ = await asyncio.wait({task}, timeout=budget_s)
        out["elapsed_s"] = time.monotonic() - t0
        out["wedged"] = not done
        if done:
            task.result()  # surface a crashed stream with its traceback
        else:
            cancel.set()
            try:
                await asyncio.wait_for(task, timeout=15.0)
            except (asyncio.TimeoutError, Exception):
                task.cancel()
        if driver_task is not None:
            try:
                await asyncio.wait_for(driver_task, timeout=10.0)
            except (asyncio.TimeoutError, Exception):
                driver_task.cancel()
        return out

    def identity(payloads: list[str], ph: dict) -> dict:
        expected = {p.encode() for p in payloads}
        seen = set(ph["delivered"]) | set(ph["shed"])
        lost = sorted(expected - seen)
        out = {
            "offered_rows": len(payloads),
            "delivered_rows": len(ph["delivered"]),
            "shed_rows": len(ph["shed"]),
            "lost_rows": len(lost),
            "wedged": ph["wedged"],
            "identity_ok": not lost and not ph["wedged"],
        }
        if lost:
            out["lost_sample"] = [x.decode() for x in lost[:5]]
        return out

    def p99_of(samples: list) -> float:
        if not samples:
            return 0.0
        s = sorted(samples)
        return s[min(len(s) - 1, int(0.99 * len(s)))]

    verdict: dict = {"mode": "partition", "seed": seed, "step_ms": step_ms,
                     "workers": urls}
    procs: list = [None, None]
    t_start = time.monotonic()

    async def go() -> None:
        proxy = ChaosProxy("127.0.0.1", ports[0], seed=seed)
        await proxy.start()
        verdict["proxy"] = proxy.url
        cfg_urls = [proxy.url, urls[1]]
        try:
            # -- phase 1: no-fault baseline, hedging on --------------------
            pay_a = [f"baseline {i:05d}" for i in range(n_base)]
            ph_a = await phase(_partition_ingest_config(
                "partition-soak-base", cfg_urls, pay_a, threads=2,
                heartbeat_timeout=f"{ht_s}s", hedge=hedge_cfg), seconds)
            disp_a = ph_a["stream"].pipeline.processors[0].dispatcher
            base_p99 = p99_of(disp_a.latency_snapshot())
            baseline = {
                **identity(pay_a, ph_a),
                "p99_s": round(base_p99, 4),
                "hedge": disp_a.report().get("hedge"),
            }
            baseline["pass"] = bool(
                baseline["identity_ok"]
                and baseline["delivered_rows"] == n_base)
            verdict["baseline"] = baseline

            # -- phase 2: one-way partition mid-load ------------------------
            events: dict = {}

            async def partition_driver(stream, delivered) -> None:
                while len(delivered) < flip_at:
                    await asyncio.sleep(0.01)
                proxy.mode = "blackhole"
                events["flipped_at_delivered"] = len(delivered)
                t_flip = time.monotonic()
                disp = stream.pipeline.processors[0].dispatcher
                pw = disp.workers[proxy.url]
                while pw.alive and time.monotonic() - t_flip < 15.0:
                    await asyncio.sleep(0.02)
                events["detected"] = not pw.alive
                events["detected_s"] = round(time.monotonic() - t_flip, 3)
                events["fenced_epochs"] = list(pw.fenced)

            pay_b = [f"partition {i:05d}" for i in range(n_part)]
            # 2 threads: post-partition the whole offered load queues on the
            # one surviving max_in_flight=1 worker, and the p99 bound below
            # must not be dominated by self-inflicted queueing
            ph_b = await phase(_partition_ingest_config(
                "partition-soak-part", cfg_urls, pay_b, threads=2,
                heartbeat_timeout=f"{ht_s}s", hedge=hedge_cfg),
                max(seconds, 30.0), driver=partition_driver)
            disp_b = ph_b["stream"].pipeline.processors[0].dispatcher
            rep_b = disp_b.report()
            part_p99 = p99_of(disp_b.latency_snapshot())
            hed = rep_b.get("hedge") or {}
            # CI-jitter floor on the tiny-step p99 bound: with ~40ms steps,
            # 2x baseline can be a single scheduler hiccup wide
            p99_bound = max(2.0 * base_p99, base_p99 + 0.25)
            partition = {
                **identity(pay_b, ph_b),
                **events,
                "p99_s": round(part_p99, 4),
                "p99_bound_s": round(p99_bound, 4),
                "hedge": hed,
                "fenced_epochs_on_dispatcher": rep_b["fenced_rejections"],
            }
            partition["pass"] = bool(
                partition["identity_ok"]
                and events.get("detected")
                and events.get("detected_s", 99.0) <= ht_s + hb_s + 0.75
                and part_p99 <= p99_bound
                and hed.get("issued", 0) >= 1
                and hed.get("issued", 0)
                <= hedge_cfg["max_fraction"] * hed.get("dispatches", 0)
                + hedge_cfg["burst"])
            verdict["partition"] = partition

            # -- phase 3: fencing — the healed zombie is rejected -----------
            proxy.mode = None  # heal before the fresh register
            fence: dict = {}
            disp_c = ClusterDispatcher(
                [proxy.url], name="partition-soak-fence", heartbeat_s=0.2,
                heartbeat_timeout_s=1.0, connect_timeout_s=1.0)
            await disp_c.start()
            pw = disp_c.workers[proxy.url]
            fence["registered"] = pw.alive
            inc0 = pw.incarnation
            fence["incarnation"] = inc0
            proxy.mode = "blackhole"
            t_flip = time.monotonic()
            while pw.alive and time.monotonic() - t_flip < 10.0:
                await asyncio.sleep(0.02)
            fence["detected"] = not pw.alive
            fence["detected_s"] = round(time.monotonic() - t_flip, 3)
            fence["fenced_epochs"] = list(pw.fenced)
            proxy.mode = None  # partition heals; the zombie resurfaces
            t_heal = time.monotonic()
            while time.monotonic() - t_heal < 10.0:
                if disp_c.m_fenced.value >= 1 and pw.alive:
                    break
                await asyncio.sleep(0.05)
            fence["zombie_reports_rejected"] = int(disp_c.m_fenced.value)
            fence["healed_alive"] = pw.alive
            fence["re_minted_incarnation"] = pw.incarnation
            fence["incarnation_rotated"] = bool(
                pw.incarnation and pw.incarnation != inc0
                and inc0 in pw.fenced)
            await disp_c.close()
            fence["pass"] = bool(
                fence["registered"] and fence["detected"]
                and fence["detected_s"] <= 1.0 + 0.2 + 0.75
                and fence["zombie_reports_rejected"] >= 1
                and fence["healed_alive"]
                and fence["incarnation_rotated"])
            verdict["fencing"] = fence

            # -- phase 4: corruption is never silent -------------------------
            corrupt_events: dict = {}

            async def corrupt_driver(stream, delivered) -> None:
                while len(delivered) < 2:
                    await asyncio.sleep(0.01)
                proxy.mode = "corrupt"
                corrupt_events["corrupt_at_delivered"] = len(delivered)
                disp = stream.pipeline.processors[0].dispatcher
                t0 = time.monotonic()
                while time.monotonic() - t0 < 6.0:
                    # a heartbeat or infer through the proxy has been
                    # damaged once the client counts a frame error — or the
                    # worker does (its up-frames are corrupted too); worker
                    # crc_errors are read after the phase, direct
                    if disp.m_frame_errors.value >= 1:
                        break
                    await asyncio.sleep(0.05)
                corrupt_events["client_frame_errors"] = int(
                    disp.m_frame_errors.value)
                proxy.mode = None  # heal so the tail drains clean

            pay_d = [f"corrupt {i:05d}" for i in range(n_corrupt)]
            ph_d = await phase(_partition_ingest_config(
                "partition-soak-corrupt", cfg_urls, pay_d, threads=2,
                heartbeat_timeout=f"{ht_s}s", hedge=None),
                max(seconds, 30.0), driver=corrupt_driver)
            disp_d = ph_d["stream"].pipeline.processors[0].dispatcher
            # the worker's own count of corrupted frames it refused to
            # decode — read over a DIRECT connection, not the proxy
            probe = ClusterDispatcher([urls[0]],
                                      name="partition-soak-crcprobe",
                                      heartbeat_s=999.0, connect_timeout_s=1.0)
            try:
                hb = await probe._unary(probe.workers[urls[0]],
                                        {"action": "heartbeat"})
            except Exception:
                hb = {}
            corrupt = {
                **identity(pay_d, ph_d),
                **corrupt_events,
                "client_frame_errors": int(disp_d.m_frame_errors.value),
                "worker_crc_errors": int(hb.get("crc_errors", 0) or 0),
                "proxy_frames_corrupted": proxy.frames_corrupted,
            }
            corrupt["loud"] = (corrupt["client_frame_errors"]
                               + corrupt["worker_crc_errors"]) >= 1
            corrupt["pass"] = bool(
                corrupt["identity_ok"] and corrupt["loud"]
                and corrupt["proxy_frames_corrupted"] >= 1
                and corrupt["delivered_rows"] == n_corrupt)
            verdict["corruption"] = corrupt

            # -- phase 5: brownout retry storm, budget off vs on -------------
            async def brownout(name: str, budget) -> dict:
                pay = [f"{name} {i:05d}" for i in range(n_brown)]
                ph = await phase(_partition_ingest_config(
                    name, urls, pay, threads=1, heartbeat="30s",
                    heartbeat_timeout="150s", request_timeout="10s",
                    retry_budget=budget,
                    net_faults=[{"kind": "net_corrupt", "every": 1,
                                 "times": 0}], seed=seed),
                    max(seconds, 30.0))
                disp = ph["stream"].pipeline.processors[0].dispatcher
                return {
                    **identity(pay, ph),
                    "ring_retries": int(disp.m_retries.value),
                    "retry_amplification": round(
                        disp.m_retries.value / max(1, n_brown), 3),
                    "retry_budget_shed": int(disp.m_retry_shed.value),
                    "frame_errors": int(disp.m_frame_errors.value),
                }

            off = await brownout("partition-soak-brownoff", None)
            on = await brownout("partition-soak-brownon",
                                {"ratio": rb_ratio, "burst": rb_burst})
            amp_bound = rb_ratio + rb_burst / n_brown + 0.05
            brown = {
                "budget_off": off,
                "budget_on": on,
                "ratio": rb_ratio, "burst": rb_burst,
                "amplification_bound": round(amp_bound, 3),
            }
            brown["pass"] = bool(
                off["identity_ok"] and on["identity_ok"]
                # the control run reproduces the storm ...
                and off["retry_amplification"] >= 0.9
                and off["delivered_rows"] == n_brown
                # ... the budget contains it, shedding the overflow loudly
                and on["retry_amplification"] <= amp_bound
                and on["retry_budget_shed"] >= 1
                and on["shed_rows"] == on["retry_budget_shed"])
            verdict["brownout"] = brown
        finally:
            await proxy.stop()

    try:
        procs[0] = spawn(0)
        procs[1] = spawn(1)
        asyncio.run(wait_ready(urls, startup_budget))
        verdict["startup_s"] = round(time.monotonic() - t_start, 3)
        asyncio.run(go())
        verdict["pass"] = bool(verdict["baseline"]["pass"]
                               and verdict["partition"]["pass"]
                               and verdict["fencing"]["pass"]
                               and verdict["corruption"]["pass"]
                               and verdict["brownout"]["pass"])
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=5)
                except Exception:
                    pass
    verdict["elapsed_s"] = round(time.monotonic() - t_start, 3)
    return _attach_tracing(verdict, trace_seq0, trace_forced0)


# -- prefill/decode disaggregation soak (runtime/cluster.py + serving) --------


def _disagg_worker_config(role: str, seed: int) -> dict:
    """Role-tuned continuous-generation worker config. The point of the
    split IS the per-role tuning a co-hosted worker can't have: the
    prefill worker runs chunked prefill against a scratch pool (no decode
    slots to starve), the decode worker runs a wide slot grid (no prefill
    compute stealing its steps), and the ``both`` worker carries the
    compromise grid co-hosting forces."""
    gen: dict = {
        "type": "tpu_generate",
        "model": "decoder_lm",
        "model_config": {"vocab_size": 512, "dim": 64, "layers": 2,
                         "heads": 4, "kv_heads": 2, "ffn": 96,
                         "max_seq": 160},
        "serving": "continuous",
        "max_input": 96,
        "max_new_tokens": 24,
        "eos_id": -1,          # never emitted: fixed tokens per request,
        "seed": seed,          # so tokens/s compares apples to apples
        "page_size": 8,
        "seq_buckets": [32, 96],
        "prefill_chunk": 32,   # same chunking everywhere: the comparison
    }                          # measures the topology, not the kernel
    if role == "prefill":
        gen.update({"slots": 4, "prefix_cache_pages": 64})
        mif = 6
    elif role == "decode":
        gen.update({"slots": 12})
        mif = 12
    else:
        gen.update({"slots": 6, "prefix_cache_pages": 64})
        mif = 6
    return {"worker": {"max_in_flight": mif, "role": role},
            "processors": [gen]}


def _disagg_ingest_config(name: str, urls: list[str], payloads: list[str],
                          *, route_key: str = "fingerprint",
                          threads: int = 8, redeliver_seed=None) -> dict:
    """Ingest-tier stream for the disagg soak: memory source ->
    ``remote_tpu`` two-hop dispatch -> collect. Prefix routing keeps the
    affinity phase honest; the perf phases route by fingerprint so both
    topologies see a balanced spread."""
    input_cfg: dict = {"type": "memory", "messages": payloads}
    if redeliver_seed is not None:
        input_cfg = {
            "type": "fault",
            "seed": redeliver_seed,
            "redeliver_unacked": True,
            "inner": input_cfg,
            "faults": [{"kind": "latency", "every": 7, "times": 0,
                        "duration": "1ms"}],
        }
    return {
        "name": name,
        "input": input_cfg,
        "pipeline": {
            "thread_num": threads,
            "max_delivery_attempts": 8,
            "processors": [{
                "type": "remote_tpu",
                "name": name,
                "workers": urls,
                "route_key": route_key,
                "prefix_bytes": 32,
                "decode_candidates": 2,
                "heartbeat": "250ms",
                "connect_timeout": "2s",
                "request_timeout": "60s",
            }],
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def run_disagg_soak(seconds: float = 90.0, seed: int = 7,
                    fast: bool = False) -> dict:
    """Prefill/decode disaggregation soak (runtime/cluster.py +
    tpu/serving.py): real continuous-generation worker processes, proving

    - **the double win**: a mixed long-prompt/long-generation load serves
      co-hosted (2 ``both`` workers) then disaggregated (1 prefill + 1
      decode at the SAME worker count, KV pages streamed over ``kv_push``);
      disagg must beat co-hosted on BOTH worker-side TTFT p99 and
      tokens/sec. The ratio assertion is gated on >= 3 host cores
      (on smaller hosts the processes timeshare and the verdict records
      the honest ratios behind soft floors);
    - **prefill-ring affinity**: with 2 prefill workers on the ring,
      duplicate prompts under prefix routing all land on ONE prefill
      worker (prefix-cache affinity survives the role split verbatim);
    - **decode-kill chaos**: the decode worker is SIGKILLed mid-stream;
      in-flight requests nack through normal redelivery and re-prefill,
      offered == delivered + shed over distinct rows (zero silent loss),
      and the restarted decode worker registers and adopts pages again.

    The parent process never imports jax — only the worker subprocesses do.
    """
    trace_seq0, trace_forced0 = _tracing_watermark()
    import asyncio
    import os
    import socket as socket_mod
    import subprocess
    import tempfile

    import yaml

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.runtime.cluster import ClusterDispatcher
    from arkflow_tpu.utils.cleanenv import pin_cpu_env

    ensure_plugins_loaded()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cores = os.cpu_count() or 1
    cores_ok = cores >= 3          # parent + the 2 measured workers
    n_mix = 18 if fast else 48     # perf phases: mixed-length requests
    k_dup = 6 if fast else 16      # affinity phase duplicates
    n_chaos = 16 if fast else 64   # chaos phase messages
    max_new = 24                   # fixed decode budget per request
    startup_budget = 300.0

    def free_port() -> int:
        s = socket_mod.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="arkflow-disagg-soak-")
    roles = ["both", "both", "prefill", "prefill", "decode"]
    names = ["both0", "both1", "pre0", "pre1", "dec0"]
    cfg_paths = []
    for name, role in zip(names, roles):
        path = os.path.join(tmp, f"{name}.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(_disagg_worker_config(role, seed), f)
        cfg_paths.append(path)
    ports = [free_port() for _ in names]
    urls = {n: f"arkflow://127.0.0.1:{p}" for n, p in zip(names, ports)}

    def spawn(i: int) -> subprocess.Popen:
        env = dict(os.environ)
        pin_cpu_env(env, n_devices=1)
        return subprocess.Popen(
            [sys.executable, "-m", "arkflow_tpu", "--cluster-worker",
             "--config", cfg_paths[i], "--host", "127.0.0.1",
             "--port", str(ports[i]), "--worker-id", f"disagg-{names[i]}"],
            cwd=repo_root, env=env,
            stdout=open(os.path.join(tmp, f"{names[i]}.log"), "ab"),
            stderr=subprocess.STDOUT)

    async def wait_ready(wait_urls: list[str], budget_s: float) -> None:
        probe = ClusterDispatcher(wait_urls, name="disagg-soak-probe",
                                  heartbeat_s=999.0, connect_timeout_s=1.0)
        deadline = time.monotonic() + budget_s
        while True:
            await asyncio.gather(
                *(probe._probe(w) for w in probe.workers.values()),
                return_exceptions=True)
            if all(w.alive for w in probe.workers.values()):
                return
            if time.monotonic() >= deadline:
                down = [w.url for w in probe.workers.values() if not w.alive]
                raise RuntimeError(
                    f"disagg workers not ready within {budget_s:.0f}s: "
                    f"{down} (see {tmp}/*.log)")
            await asyncio.sleep(0.5)

    async def heartbeat(url: str) -> dict:
        probe = ClusterDispatcher([url], name="disagg-soak-probe",
                                  heartbeat_s=999.0, connect_timeout_s=1.0)
        return await probe._unary(probe.workers[url],
                                  {"action": "heartbeat"})

    def hb(url: str) -> dict:
        return asyncio.run(heartbeat(url))

    class _Collect(DropOutput):
        def __init__(self, sink: list):
            self._sink = sink
            self.t_first = None
            self.t_last = None

        async def write(self, batch: MessageBatch) -> None:
            now = time.monotonic()
            if self.t_first is None:
                self.t_first = now
            self.t_last = now
            self._sink.extend(batch.to_binary())

    def run_phase(cfg_map: dict, budget_s: float, driver=None) -> dict:
        stream = build_stream(StreamConfig.from_mapping(cfg_map))
        delivered: list[bytes] = []
        shed: list[bytes] = []
        out_sink, err_sink = _Collect(delivered), _Collect(shed)
        stream.output = out_sink
        stream.error_output = err_sink
        out: dict = {"delivered": delivered, "shed": shed, "stream": stream,
                     "out_sink": out_sink}

        async def bounded() -> None:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            driver_task = (asyncio.create_task(driver(stream, delivered))
                           if driver is not None else None)
            t0 = time.monotonic()
            done, _ = await asyncio.wait({task}, timeout=budget_s)
            out["elapsed_s"] = time.monotonic() - t0
            out["wedged"] = not done
            if done:
                task.result()
            else:
                cancel.set()
                try:
                    await asyncio.wait_for(task, timeout=15.0)
                except (asyncio.TimeoutError, Exception):
                    task.cancel()
            if driver_task is not None:
                try:
                    await asyncio.wait_for(driver_task, timeout=5.0)
                except (asyncio.TimeoutError, Exception):
                    driver_task.cancel()

        asyncio.run(bounded())
        return out

    def rows_per_s(phase: dict) -> float:
        sink = phase["out_sink"]
        if sink.t_first is None:
            return 0.0
        return len(phase["delivered"]) / max(
            sink.t_last - sink.t_first, 0.05)

    # mixed-length load: 1/3 long prompts (prefill-heavy), 2/3 short
    # (latency-bound) — the regime role specialization is for
    def mixed(tag: str, n: int) -> list[str]:
        out = []
        for i in range(n):
            if i % 3 == 0:
                out.append(f"{tag} {i:05d} " + "gamma delta " * 40)
            else:
                out.append(f"{tag} {i:05d} quick probe")
        return out

    procs: dict = {n: None for n in names}
    verdict: dict = {"mode": "disagg", "seed": seed, "host_cores": cores,
                     "cores_ok": cores_ok, "max_new_tokens": max_new}
    t_start = time.monotonic()
    budget = max(seconds, 120.0)
    try:
        for i in range(len(names)):
            procs[names[i]] = spawn(i)
        asyncio.run(wait_ready(list(urls.values()), startup_budget))
        verdict["startup_s"] = round(time.monotonic() - t_start, 3)

        # -- phase 1: co-hosted baseline (2 'both' workers) ----------------
        co = run_phase(_disagg_ingest_config(
            "disagg-soak-co", [urls["both0"], urls["both1"]],
            mixed("co", n_mix)), budget)
        co_hb = [hb(urls["both0"]), hb(urls["both1"])]
        co_ttft = max(float(h.get("ttft_p99_ms", 0.0) or 0.0)
                      for h in co_hb)
        # the both workers are done: free their cores before measuring
        # the disagg wave (equal worker count = equal live processes)
        for n in ("both0", "both1"):
            procs[n].kill()
            procs[n].wait()

        # -- phase 2: disaggregated, equal worker count (1 pre + 1 dec) ----
        di = run_phase(_disagg_ingest_config(
            "disagg-soak-di", [urls["pre0"], urls["dec0"]],
            mixed("di", n_mix)), budget)
        pre_hb, dec_hb = hb(urls["pre0"]), hb(urls["dec0"])
        di_ttft = float(pre_hb.get("ttft_p99_ms", 0.0) or 0.0)
        co_rows, di_rows = rows_per_s(co), rows_per_s(di)
        ttft_ratio = co_ttft / max(di_ttft, 1e-9)
        tput_ratio = di_rows / max(co_rows, 1e-9)
        # the ratio floors bind only when the host can actually run the
        # tiers in parallel; soft floors keep degraded hosts honest
        ttft_floor, tput_floor = (1.0, 1.0) if cores_ok else (0.2, 0.2)
        perf = {
            "cohosted_ttft_p99_ms": round(co_ttft, 3),
            "disagg_ttft_p99_ms": round(di_ttft, 3),
            "ttft_ratio": round(ttft_ratio, 3),
            "cohosted_tokens_per_s": round(co_rows * max_new, 2),
            "disagg_tokens_per_s": round(di_rows * max_new, 2),
            "tput_ratio": round(tput_ratio, 3),
            "cohosted_delivered": len(co["delivered"]),
            "disagg_delivered": len(di["delivered"]),
            "kv_pushed": int(pre_hb.get("kv_pushed", 0)),
            "kv_adopted": int(dec_hb.get("kv_adopted", 0)),
            "ratio_gated_on_cores": not cores_ok,
            "double_win": bool(ttft_ratio >= ttft_floor
                               and tput_ratio >= tput_floor),
        }
        perf["pass"] = bool(not co["wedged"] and not di["wedged"]
                            and len(co["delivered"]) == n_mix
                            and len(di["delivered"]) == n_mix
                            and co_ttft > 0.0 and di_ttft > 0.0
                            # every request's pages flowed cross-process
                            and perf["kv_pushed"] == n_mix
                            and perf["kv_adopted"] == n_mix
                            # two wall-clock rates of a few seconds each, on
                            # CPU workers that share their cores with whatever
                            # else runs: reported in fast mode, held only by
                            # the full soak
                            and (fast or perf["double_win"]))
        verdict["perf"] = perf

        # -- phase 3: prefix affinity on the prefill sub-ring --------------
        pre_urls = [urls["pre0"], urls["pre1"]]
        before = {u: hb(u) for u in pre_urls}
        aff = run_phase(_disagg_ingest_config(
            "disagg-soak-aff", pre_urls + [urls["dec0"]],
            ["affinity probe prompt"] * k_dup, route_key="prefix",
            threads=2), budget)
        after = {u: hb(u) for u in pre_urls}
        served = {u: int(after[u].get("served", 0))
                  - int(before[u].get("served", 0)) for u in pre_urls}
        target = max(served, key=lambda u: served[u])
        affinity = {
            "delivered": len(aff["delivered"]),
            "served_by_prefill_worker": served,
            "one_prefill_took_all": (served[target] == k_dup and all(
                served[u] == 0 for u in pre_urls if u != target)),
        }
        affinity["pass"] = bool(len(aff["delivered"]) == k_dup
                                and affinity["one_prefill_took_all"])
        verdict["affinity"] = affinity

        # -- phase 4: decode worker SIGKILLed mid-stream -------------------
        kill_at = max(2, n_chaos // 4)
        chaos_events: dict = {"killed": False, "restarted": False}
        dec_i = names.index("dec0")

        async def chaos_driver(stream, delivered) -> None:
            while len(delivered) < kill_at:
                await asyncio.sleep(0.01)
            procs["dec0"].kill()
            procs["dec0"].wait()
            chaos_events["killed"] = True
            chaos_events["killed_at_delivered"] = len(delivered)
            await asyncio.sleep(1.0)
            procs["dec0"] = spawn(dec_i)  # same port, same identity
            chaos_events["restarted"] = True

        pay = [f"chaos row {i:05d} tick" for i in range(n_chaos)]
        chaos = run_phase(_disagg_ingest_config(
            "disagg-soak-chaos", [urls["pre0"], urls["dec0"]], pay,
            redeliver_seed=seed), max(budget, 120.0), driver=chaos_driver)
        expected = set(p.encode() for p in pay)
        seen = set(chaos["delivered"]) | set(chaos["shed"])
        lost = sorted(expected - seen)
        chaos_out = {
            **chaos_events,
            "wedged": chaos["wedged"],
            "offered_rows": n_chaos,
            "delivered_rows": len(chaos["delivered"]),
            "shed_rows": len(chaos["shed"]),
            "lost_rows": len(lost),
            # offered == delivered + shed over DISTINCT rows: redelivery
            # may duplicate, nothing vanishes silently
            "identity_ok": (len(lost) == 0
                            and len(expected & set(chaos["delivered"]))
                            + len(expected & set(chaos["shed"])
                                  - set(chaos["delivered"])) == n_chaos),
        }
        if lost:
            chaos_out["lost_sample"] = [x.decode() for x in lost[:5]]

        # the decode worker must come back AND adopt pages again
        revived = False
        adopts_again = False
        revive_error = None
        try:
            asyncio.run(wait_ready([urls["dec0"]], startup_budget))
            post = run_phase(_disagg_ingest_config(
                "disagg-soak-revive", [urls["pre0"], urls["dec0"]],
                [f"revive row {i}" for i in range(3)], threads=1), budget)
            revived = len(post["delivered"]) == 3
            adopts_again = int(hb(urls["dec0"]).get("kv_adopted", 0)) >= 3
        except Exception as e:
            revive_error = f"{type(e).__name__}: {e}"
        chaos_out["revived"] = revived
        chaos_out["adopts_again"] = adopts_again
        if revive_error:
            chaos_out["revive_error"] = revive_error
        chaos_out["pass"] = bool(not chaos["wedged"]
                                 and chaos_out["identity_ok"]
                                 and chaos_events["killed"]
                                 and revived and adopts_again)
        verdict["chaos"] = chaos_out

        verdict["pass"] = bool(perf["pass"] and affinity["pass"]
                               and chaos_out["pass"])
    finally:
        for p in procs.values():
            if p is not None and p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=5)
                except Exception:
                    pass
    verdict["elapsed_s"] = round(time.monotonic() - t_start, 3)
    return _attach_tracing(verdict, trace_seq0, trace_forced0)


# -- elastic-fleet preemption soak (runtime/fleet.py) -------------------------


def run_preempt_soak(seconds: float = 120.0, seed: int = 7,
                     fast: bool = False) -> dict:
    """Elastic-fleet soak (runtime/fleet.py): 3 worker processes behind a
    ``remote_tpu`` stream with the autoscaling controller enabled, proving

    - **preemption storm**: workers SIGKILLed one by one mid-load are
      detected off missed heartbeats (not a transport error — the staleness
      sweep), counted as departures, and respawned from the template to hold
      ``min_workers``, while delivered rows keep flowing (p99 inter-delivery
      gap within the SLO) and offered == delivered + shed over distinct rows
      (zero silent loss through the ring-successor handoff + redelivery);
    - **load ramp scale-out**: sustained window exhaustion against a
      deliberately undersized fleet fires ``scale_out`` — the newcomer is
      spawned warm on the incumbent shape grid and adopted into the ring —
      with ZERO failed dispatches (scale-out beats shed).

    The parent process never imports jax — only worker subprocesses do.
    """
    trace_seq0, trace_forced0 = _tracing_watermark()
    import asyncio
    import os
    import subprocess
    import tempfile

    import yaml

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.runtime.cluster import ClusterDispatcher
    from arkflow_tpu.runtime.fleet import (FleetController, SubprocessSpawner,
                                           free_port, parse_fleet_config)
    from arkflow_tpu.utils.cleanenv import pin_cpu_env

    ensure_plugins_loaded()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    step_ms = 100
    n_static = 3
    rows = 800 if fast else 1600        # storm phase offered load
    first_kill_s = 3.0 if fast else 10.0
    kill_gap_s = 4.0 if fast else 20.0  # min spacing between kills
    slo_gap_s = 15.0                    # p99 inter-delivery gap SLO
    startup_budget = 240.0
    storm_budget = max(seconds, 90.0 if fast else 180.0)
    ramp_budget = 90.0

    template = _cluster_worker_config(seed, step_ms)
    tmp = tempfile.mkdtemp(prefix="arkflow-preempt-soak-")
    cfg_path = os.path.join(tmp, "worker.yaml")
    with open(cfg_path, "w") as f:
        yaml.safe_dump(template, f)

    # one child env for EVERY worker this soak starts — the static fleet and
    # the controller's template spawns alike pin the virtual-CPU platform
    # and see the repo on PYTHONPATH (the parent never imports jax)
    child_env = dict(os.environ)
    pin_cpu_env(child_env, n_devices=1)
    child_env["PYTHONPATH"] = repo_root + (
        os.pathsep + child_env["PYTHONPATH"]
        if child_env.get("PYTHONPATH") else "")

    ports = [free_port() for _ in range(n_static)]
    urls = [f"arkflow://127.0.0.1:{p}" for p in ports]

    def spawn(i: int) -> subprocess.Popen:
        log = open(os.path.join(tmp, f"static-w{i}.log"), "ab")
        return subprocess.Popen(
            [sys.executable, "-m", "arkflow_tpu", "--cluster-worker",
             "--config", cfg_path, "--host", "127.0.0.1",
             "--port", str(ports[i]), "--worker-id", f"preempt-w{i}"],
            cwd=repo_root, env=child_env, stdout=log,
            stderr=subprocess.STDOUT)

    async def wait_ready(wait_urls: list[str], budget_s: float) -> None:
        probe = ClusterDispatcher(wait_urls, name="preempt-soak-probe",
                                  heartbeat_s=999.0, connect_timeout_s=1.0)
        deadline = time.monotonic() + budget_s
        while True:
            await asyncio.gather(
                *(probe._probe(w) for w in probe.workers.values()),
                return_exceptions=True)
            if all(w.alive for w in probe.workers.values()):
                return
            if time.monotonic() >= deadline:
                down = [w.url for w in probe.workers.values() if not w.alive]
                raise RuntimeError(
                    f"workers not ready within {budget_s:.0f}s: {down} "
                    f"(see {tmp}/*.log)")
            await asyncio.sleep(0.5)

    class _Collect(DropOutput):
        """Collects rows WITH arrival timestamps (for the gap SLO)."""

        def __init__(self, sink: list, times: list):
            self._sink = sink
            self._times = times

        async def write(self, batch: MessageBatch) -> None:
            t = time.monotonic()
            rws = batch.to_binary()
            self._sink.extend(rws)
            self._times.extend([t] * len(rws))

    def p99_gap(times: list) -> float:
        gaps = sorted(b - a for a, b in zip(times, times[1:]))
        if not gaps:
            return 0.0
        return gaps[int(0.99 * (len(gaps) - 1))]

    # -- phase 1: preemption storm under load ------------------------------
    def storm_config(payloads: list[str]) -> dict:
        cfg = _cluster_ingest_config("preempt-soak-storm", urls, payloads,
                                     redeliver_seed=seed)
        rt = cfg["pipeline"]["processors"][0]
        # staleness on the heartbeat clock: a SIGKILLed worker must fall out
        # of the ring in ~1.25s, not at the 30s request timeout
        rt["heartbeat_timeout"] = "1250ms"
        rt["fleet"] = {
            "min_workers": n_static,
            "max_workers": n_static + 1,
            "interval": "400ms",
            "scale_out_sustain": "60s",   # storm phase tests RESPAWN only
            "cooldown": "1s",
            "template": cfg_path,
        }
        return cfg

    storm_events: dict = {"kills": [], "detected": 0, "respawned": False}
    procs: list = [None] * n_static

    async def storm_driver(stream, delivered) -> None:
        fleet = stream.pipeline.processors[0].fleet
        # the controller's spawns must pin the same child env the static
        # fleet got, and leave logs where the verdict points
        fleet.spawner.env = child_env
        fleet.spawner.log_dir = tmp
        t0 = time.monotonic()
        for k in range(2):
            target = t0 + first_kill_s + k * kill_gap_s
            while time.monotonic() < target and len(delivered) < rows:
                await asyncio.sleep(0.05)
            victim = procs[1 + k]
            victim.kill()
            victim.wait()
            storm_events["kills"].append(round(time.monotonic() - t0, 2))
            deadline = time.monotonic() + 25.0
            while time.monotonic() < deadline:
                if fleet.report()["departures"] > k:
                    storm_events["detected"] += 1
                    break
                await asyncio.sleep(0.1)
            if k == 0:
                # hold the storm until the controller respawned the floor —
                # rows keep serving on the survivors meanwhile
                deadline = time.monotonic() + 45.0
                while time.monotonic() < deadline:
                    if fleet.report()["size"] >= n_static:
                        storm_events["respawned"] = True
                        break
                    await asyncio.sleep(0.2)
        storm_events["fleet_report"] = fleet.report()

    def run_storm() -> dict:
        stream = build_stream(StreamConfig.from_mapping(
            storm_config([f"storm row {i:05d}" for i in range(rows)])))
        delivered: list = []
        times: list = []
        shed: list = []
        stream.output = _Collect(delivered, times)
        stream.error_output = _Collect(shed, [])
        out: dict = {"delivered": delivered, "times": times, "shed": shed}

        async def bounded() -> None:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            driver = asyncio.create_task(storm_driver(stream, delivered))
            done, _ = await asyncio.wait({task}, timeout=storm_budget)
            out["wedged"] = not done
            if done:
                task.result()
            else:
                cancel.set()
                try:
                    await asyncio.wait_for(task, timeout=15.0)
                except (asyncio.TimeoutError, Exception):
                    task.cancel()
            try:
                await asyncio.wait_for(driver, timeout=5.0)
            except (asyncio.TimeoutError, Exception):
                driver.cancel()

        asyncio.run(bounded())
        return out

    # -- phase 2: load ramp fires a scale-out ------------------------------
    async def run_ramp() -> dict:
        fc_cfg = parse_fleet_config({
            "min_workers": 1, "max_workers": 2,
            "interval": "300ms", "scale_out_sustain": "1500ms",
            "cooldown": "1s", "template": cfg_path,
        }, static_workers=1, who="preempt-soak")
        d = ClusterDispatcher(urls[:1], name="preempt-soak-ramp",
                              heartbeat_s=999.0, connect_timeout_s=2.0)
        spawner = SubprocessSpawner(cfg_path, host="127.0.0.1",
                                    env=child_env, log_dir=tmp)
        fc = FleetController(d, spawner, fc_cfg, name="preempt-soak-ramp")
        ok_rows = 0
        failed = 0
        pending: set = set()
        i = 0

        async def offer(n: int) -> None:
            nonlocal ok_rows, failed
            try:
                outs = await d.dispatch(
                    MessageBatch.new_binary([f"ramp row {n:05d}".encode()]))
                ok_rows += sum(len(o.to_binary()) for o in outs)
            except Exception:
                failed += 1

        scale_event = None
        try:
            for w in d.workers.values():
                await d._probe(w)
            deadline = time.monotonic() + ramp_budget
            while time.monotonic() < deadline:
                # sustained offered load: keep more dispatches outstanding
                # than the single worker's advertised window can ever cover
                while len(pending) < 8:
                    t = asyncio.create_task(offer(i))
                    i += 1
                    pending.add(t)
                    t.add_done_callback(pending.discard)
                for w in list(d.workers.values()):
                    try:
                        await d._probe(w)
                    except Exception:
                        pass
                ev = await fc.tick()
                if ev and ev.get("action") == "scale_out":
                    scale_event = ev
                    break
                await asyncio.sleep(0.25)
            if pending:
                await asyncio.wait(pending, timeout=60.0)
            report = fc.report()
            newcomer = [u for u in d.workers if u not in urls]
            newcomer_alive = bool(newcomer
                                  and d.workers[newcomer[0]].alive)
        finally:
            await fc.close()
            await spawner.close()
        return {
            "offered": i, "delivered": ok_rows, "failed_dispatches": failed,
            "scale_out_fired": scale_event is not None,
            "warm_shapes": bool(scale_event and scale_event.get("warm_shapes")),
            "newcomer_adopted": newcomer_alive,
            "scale_outs": report["scale_outs"],
            "events": report["events"],
        }

    verdict: dict = {"mode": "preempt", "seed": seed, "step_ms": step_ms,
                     "workers": urls, "logs": tmp}
    t_start = time.monotonic()
    try:
        for n in range(n_static):
            procs[n] = spawn(n)
        asyncio.run(wait_ready(urls, startup_budget))
        verdict["startup_s"] = round(time.monotonic() - t_start, 3)

        storm = run_storm()
        expected = set(f"storm row {i:05d}".encode() for i in range(rows))
        seen = set(storm["delivered"]) | set(storm["shed"])
        lost = sorted(expected - seen)
        gap99 = p99_gap(storm["times"])
        fleet_rep = storm_events.pop("fleet_report", {})
        storm_out = {
            **storm_events,
            "wedged": storm["wedged"],
            "offered_rows": rows,
            "delivered_rows": len(storm["delivered"]),
            "shed_rows": len(storm["shed"]),
            "duplicate_rows": len(storm["delivered"])
            - len(set(storm["delivered"])),
            "lost_rows": len(lost),
            "departures": fleet_rep.get("departures", 0),
            "fleet_events": fleet_rep.get("events", []),
            "p99_gap_s": round(gap99, 3),
            "identity_ok": len(lost) == 0,
            "gap_slo_ok": gap99 <= slo_gap_s,
        }
        if lost:
            storm_out["lost_sample"] = [x.decode() for x in lost[:5]]
        storm_out["pass"] = bool(not storm["wedged"]
                                 and storm_out["identity_ok"]
                                 and storm_out["gap_slo_ok"]
                                 and len(storm_events["kills"]) == 2
                                 and storm_events["detected"] == 2
                                 and storm_events["respawned"])
        verdict["storm"] = storm_out

        ramp = asyncio.run(run_ramp())
        ramp["pass"] = bool(ramp["scale_out_fired"]
                            and ramp["newcomer_adopted"]
                            and ramp["warm_shapes"]
                            and ramp["failed_dispatches"] == 0
                            and ramp["delivered"] == ramp["offered"])
        verdict["ramp"] = ramp

        verdict["pass"] = bool(storm_out["pass"] and ramp["pass"])
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=5)
                except Exception:
                    pass
    verdict["elapsed_s"] = round(time.monotonic() - t_start, 3)
    return _attach_tracing(verdict, trace_seq0, trace_forced0)


# -- traffic-adaptive shapes soak (tpu/tuner.py) ------------------------------

# wide enough that the DEVICE step dominates e2e (at hidden 32 the step is
# <10% of e2e and the tuned seq-grid compute win drowns in loop noise; at
# this size a b8 step measures ~5ms at seq 16 vs ~9ms at seq 32, so rows/s
# reflects the shapes, not the event loop)
_TUNER_TINY_BERT = {"vocab_size": 512, "hidden": 128, "layers": 4, "heads": 4,
                    "ffn": 512, "num_labels": 2}


def _tuner_soak_config(name: str, tuned: bool, fast: bool) -> dict:
    """Coalesced unpacked BERT serving on a deliberately-blind pow2 seq grid
    [32, 64]; the tuned variant adds the ``tuner:`` block (long autonomous
    interval — the soak drives cycles explicitly for determinism)."""
    proc = {
        "type": "tpu_inference", "model": "bert_classifier",
        "model_config": dict(_TUNER_TINY_BERT), "max_seq": 64,
        "batch_buckets": [8], "seq_buckets": [32, 64],
        "warmup": True,
    }
    if tuned:
        proc["tuner"] = {
            # longer than any phase: the autonomous loop never fires, so
            # the driver's forced cycles are the ONLY ones — a background
            # cycle could otherwise consume the armed probe fault and turn
            # the rollback assertion nondeterministic
            "interval": "60s", "min_samples": 48, "min_improvement": 0.02,
            "max_compiles": 16, "window": 128 if fast else 512,
            "deadline_min": "5ms", "deadline_max": "100ms",
        }
    return {
        "name": name,
        "input": {"type": "memory", "messages": ["placeholder"]},
        "buffer": {"type": "memory", "capacity": 64, "timeout": "200ms",
                   "coalesce": {"batch_buckets": [8], "deadline": "25ms"}},
        "pipeline": {"thread_num": 2, "processors": [proc]},
        "output": {"type": "drop"},
    }


def run_tuner_soak(seconds: float = 90.0, seed: int = 7,
                   fast: bool = False) -> dict:
    """Shifting-length-distribution soak for the runtime shape tuner.

    The same seeded schedule — a SHORT word-count mix that flips to a LONG
    mix mid-run — serves twice: once on the static pow2 default, once with
    the ``tuner:`` block enabled. The verdict asserts the tuned run beats
    the static default on BOTH rows/s and capacity-weighted
    ``padding_waste_frac``, that every tuner-minted shape compiled on the
    warm path (``arkflow_tpu_compiles_total`` flat on the serving path vs
    the static run), that a chaos-forced probe failure mid-run rolls back
    to the incumbent grid with zero lost rows, and that no row was silently
    lost across any flip."""
    import asyncio
    import random

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Ack, Input, NoopAck, ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.errors import EndOfInput, TunerError
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.tpu.bucketing import bucket_cap_bus

    ensure_plugins_loaded()
    trace_seq0, trace_forced0 = _tracing_watermark()
    reg = global_registry()

    # sized so each phase saturates for several seconds on a 2-core CPU —
    # long enough that the tuner's mid-run commits cover most of each mix
    rows_total = 6000 if fast else 16000
    rows_per_batch = 4
    half = rows_total // 2

    def make_schedule() -> list[bytes]:
        """Row i's payload: unique id + k filler words; k draws SHORT for
        the first half, LONG for the second (the mid-run mix flip). The
        hash tokenizer counts words, so token length == k + specials."""
        rng = random.Random(seed)
        rows = []
        for i in range(rows_total):
            k = rng.randint(4, 10) if i < half else rng.randint(34, 46)
            rows.append((f"t{i:05d} " + "w " * (k - 1)).strip().encode())
        return rows

    class _ShiftingSource(Input):
        def __init__(self, rows: list[bytes]):
            self._rows = list(rows)
            self._pos = 0

        async def connect(self) -> None:
            return None

        async def read(self) -> tuple[MessageBatch, Ack]:
            if self._pos >= len(self._rows):
                raise EndOfInput()
            chunk = self._rows[self._pos:self._pos + rows_per_batch]
            self._pos += len(chunk)
            await asyncio.sleep(0)  # saturating, but never starves the loop
            return (MessageBatch.new_binary(chunk).with_source("tuner-soak"),
                    NoopAck())

    def counters() -> dict:
        return {
            "tokens": reg.sum_values("arkflow_tpu_tokens_total"),
            "capacity": reg.sum_values("arkflow_tpu_token_capacity_total"),
            "compiles": reg.sum_values("arkflow_tpu_compiles_total"),
            "warm_compiles": reg.sum_values("arkflow_tpu_warm_compiles_total"),
            "rollbacks": reg.sum_values("arkflow_tuner_rollbacks_total"),
            "commits": reg.sum_values("arkflow_tuner_commits_total"),
        }

    def run_phase(tuned: bool, budget_s: float) -> dict:
        cfg = StreamConfig.from_mapping(
            _tuner_soak_config(f"tuner-soak-{'on' if tuned else 'off'}",
                               tuned, fast))
        stream = build_stream(cfg)
        stream.input = _ShiftingSource(make_schedule())
        delivered: list[bytes] = []
        t_first: list[float] = []

        class _Collect(DropOutput):
            async def write(self, batch: MessageBatch) -> None:
                if not t_first:
                    t_first.append(time.monotonic())
                delivered.extend(batch.to_binary())

        stream.output = _Collect()
        proc = stream.pipeline.processors[0]
        before = counters()
        phase: dict = {"tuned": tuned}

        async def driver() -> None:
            """Tuned phase only: force cycles at deterministic points —
            commit on the short mix, a chaos probe-failure rollback after
            the mix flips, then the real long-mix commit."""
            tuner = proc.tuner

            async def wait_rows(n: int, budget: float) -> None:
                deadline = time.monotonic() + budget
                while len(delivered) < n and time.monotonic() < deadline:
                    await asyncio.sleep(0.02)

            async def force() -> str:
                try:
                    rep = await tuner.run_cycle(force=True)
                    return rep["action"]
                except TunerError:
                    return "rolled_back"

            # 1. short mix: window full of short rows -> first commit, so
            # most of the short half serves on the retuned grid
            win = 128 if fast else 512
            await wait_rows(win + 8 * rows_per_batch, budget_s * 0.5)
            outcomes = [await force()]
            # 2. after the flip: window dominated by the long mix; arm the
            # probe fault so the beneficial flip ROLLS BACK...
            await wait_rows(half + win + 2 * rows_per_batch, budget_s * 0.5)
            for _ in range(3):
                tuner.inject_fault("probe_fail")
                grid_before = proc.runner.buckets.seq_buckets
                out = await force()
                outcomes.append(out)
                if out == "rolled_back":
                    phase["rollback_grid_restored"] = (
                        proc.runner.buckets.seq_buckets == grid_before)
                    break
                tuner._chaos.clear()  # proposal never probed; disarm
                await wait_rows(len(delivered) + 64, budget_s * 0.25)
            # 3. ...then commits cleanly once the chaos is gone
            for _ in range(3):
                out = await force()
                outcomes.append(out)
                if out == "committed":
                    break
                await wait_rows(len(delivered) + 64, budget_s * 0.25)
            phase["forced_outcomes"] = outcomes

        async def bounded() -> bool:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            drv = (asyncio.create_task(driver()) if tuned else None)
            done, _ = await asyncio.wait({task}, timeout=budget_s)
            if drv is not None:
                drv.cancel()
                try:
                    await drv
                except (asyncio.CancelledError, Exception):
                    pass
            if done:
                task.result()
                return False
            cancel.set()
            try:
                await asyncio.wait_for(task, timeout=15.0)
            except (asyncio.TimeoutError, Exception):
                task.cancel()
            return True

        t0 = time.monotonic()
        wedged = asyncio.run(bounded())
        t_end = time.monotonic()
        after = counters()
        expected = {f"t{i:05d}".encode() for i in range(rows_total)}
        got = {p.split(b" ", 1)[0] for p in delivered}
        serve_t = t_end - (t_first[0] if t_first else t0)
        d_cap = after["capacity"] - before["capacity"]
        phase.update({
            "wedged": wedged,
            "delivered_rows": len(delivered),
            "lost_rows": len(expected - got),
            "rows_per_sec": round(len(delivered) / max(serve_t, 1e-6), 1),
            "padding_waste_frac": round(
                1.0 - (after["tokens"] - before["tokens"]) / d_cap, 4)
            if d_cap > 0 else None,
            "serving_compiles": int(after["compiles"] - before["compiles"]),
            "warm_compiles": int(after["warm_compiles"] - before["warm_compiles"]),
        })
        if tuned:
            phase["tuner"] = proc.tuner.report()
            phase["commits"] = int(after["commits"] - before["commits"])
            phase["rollbacks"] = int(after["rollbacks"] - before["rollbacks"])
        return phase

    budget_each = max(20.0, seconds / 2)
    try:
        static = run_phase(tuned=False, budget_s=budget_each)
        tuned = run_phase(tuned=True, budget_s=budget_each)
    finally:
        bucket_cap_bus().reset()  # in-process callers get a clean slate

    beats_rows = (not static["wedged"] and not tuned["wedged"]
                  and tuned["rows_per_sec"] > static["rows_per_sec"])
    beats_waste = (static["padding_waste_frac"] is not None
                   and tuned["padding_waste_frac"] is not None
                   and tuned["padding_waste_frac"] < static["padding_waste_frac"])
    # the acceptance bar: every tuner-minted shape compiled on the warm
    # path — the tuned run's SERVING-path compile count is no higher than
    # the static run's (both pay only their connect-time warmup)
    zero_onpath = (tuned["serving_compiles"] <= static["serving_compiles"]
                   and tuned["warm_compiles"] > 0)
    rollback_ok = (tuned.get("rollbacks", 0) >= 1
                   and tuned.get("rollback_grid_restored") is True)
    verdict = {
        "mode": "tuner",
        "pass": bool(beats_rows and beats_waste and zero_onpath and rollback_ok
                     and tuned.get("commits", 0) >= 1
                     and static["lost_rows"] == 0 and tuned["lost_rows"] == 0),
        "seed": seed,
        "rows": rows_total,
        "static": static,
        "tuned": tuned,
        "tuned_beats_static_rows_per_sec": beats_rows,
        "tuned_beats_static_waste": beats_waste,
        "zero_onpath_recompiles": zero_onpath,
        "probe_failure_rollback_ok": rollback_ok,
    }
    return _attach_tracing(verdict, trace_seq0, trace_forced0)


# -- silent-data-corruption soak (tpu/integrity.py) ---------------------------


def _sdc_pool_config(seed: int, messages: int, step_ms: int) -> dict:
    """In-process pool phase: a 2-member device pool with the integrity
    plane on a fast probe cadence, paced by a per-batch latency fault so
    the stream outlives detection + repair."""
    tiny_model = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
                  "ffn": 64, "max_positions": 64, "num_labels": 2}
    return {
        "name": "sdc-pool",
        "input": {"type": "memory",
                  "messages": [f"sdc pool row {i:05d}" for i in range(messages)]},
        "pipeline": {
            "thread_num": 2,
            "processors": [{
                "type": "fault",
                "seed": seed,
                "faults": [{"kind": "latency", "every": 1, "times": 0,
                            "duration": f"{step_ms}ms"}],
                "inner": {
                    "type": "tpu_inference",
                    "model": "bert_classifier",
                    "model_config": tiny_model,
                    "max_seq": 16,
                    "batch_buckets": [2],
                    "seq_buckets": [16],
                    "warmup": True,
                    "device_pool": 2,
                    "integrity": {"probe_interval": "300ms",
                                  "digest_every": 1},
                },
            }],
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def _sdc_worker_config(seed: int, step_ms: int, arm_at: int) -> dict:
    """Device-tier worker for the cluster phase. ``arm_at`` > 0 arms a
    one-shot ``sdc`` fault on the worker's Nth processed batch — from then
    on its outputs are garbled until the integrity plane repairs it. The
    probe interval is parked high so detection is driven by the
    dispatcher's shadow-verify tiebreak, not a background-probe race."""
    tiny_model = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
                  "ffn": 64, "max_positions": 64, "num_labels": 2}
    faults = [{"kind": "latency", "every": 1, "times": 0,
               "duration": f"{step_ms}ms"}]
    if arm_at > 0:
        faults.append({"kind": "sdc", "at": arm_at})
    return {
        "processors": [{
            "type": "fault",
            "seed": seed,
            "faults": faults,
            "inner": {
                "type": "tpu_inference",
                "model": "bert_classifier",
                "model_config": tiny_model,
                "max_seq": 16,
                "batch_buckets": [2],
                "seq_buckets": [16],
                "warmup": True,
                "integrity": {"probe_interval": "999s"},
            },
        }],
    }


def _sdc_ingest_config(name: str, urls: list[str], payloads: list[str],
                       *, threads: int = 2, shadow_fraction=None,
                       response_cache: bool = False) -> dict:
    proc: dict = {
        "type": "remote_tpu",
        "name": name,
        "workers": urls,
        "heartbeat": "250ms",
        "connect_timeout": "2s",
        "request_timeout": "30s",
    }
    if shadow_fraction is not None:
        proc["shadow_verify"] = {"fraction": shadow_fraction}
    if response_cache:
        proc["response_cache"] = {"capacity": 256}
    return {
        "name": name,
        "input": {"type": "memory", "messages": payloads},
        "pipeline": {
            "thread_num": threads,
            "max_delivery_attempts": 8,
            "processors": [proc],
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }


def run_sdc_soak(seconds: float = 90.0, seed: int = 7,
                 fast: bool = False) -> dict:
    """Silent-data-corruption soak (tpu/integrity.py), two tiers:

    - pool phase (in-process): a ``bitflip`` corrupts one param leaf of a
      live 2-member device pool mid-load; the integrity monitor's digest
      pass detects it within a probe period, the golden probe proves it,
      the member is quarantined (CORRUPT), repaired from retained host
      params, re-verified, and re-admitted — zero rows lost.
    - cluster phase (2 worker subprocesses): one worker arms a persistent
      ``sdc`` fault mid-load; shadow-verify (fraction 1.0) dual-dispatches
      every batch, catches the divergence on the corrupt batch itself, the
      golden-probe tiebreak fences the corrupt worker (which repairs), and
      every delivered row's label matches a clean-worker reference — zero
      corrupted rows delivered, offered == delivered + shed, and the
      repaired worker re-registers and serves.
    """
    trace_seq0, trace_forced0 = _tracing_watermark()
    import asyncio
    import os
    import socket as socket_mod
    import subprocess
    import tempfile

    import yaml

    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.plugins.output.drop import DropOutput
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.runtime.cluster import ClusterDispatcher
    from arkflow_tpu.utils.cleanenv import pin_cpu_env

    ensure_plugins_loaded()
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    step_ms = 40 if fast else 50
    n_pool = 48 if fast else 96        # pool phase messages
    n_ref = 12 if fast else 24         # cluster reference rows
    n_chaos = 32 if fast else 64       # cluster chaos rows
    arm_at = 5                         # worker batch that arms the sdc fault
    startup_budget = 240.0
    verdict: dict = {"mode": "sdc", "seed": seed, "fast": fast}
    t_start = time.monotonic()

    # -- phase 1: pool-tier bitflip -> detect/quarantine/repair/re-admit ----
    pool_events: dict = {}

    async def pool_phase() -> dict:
        stream = build_stream(StreamConfig.from_mapping(
            _sdc_pool_config(seed, n_pool, step_ms)))
        delivered: list[bytes] = []

        class _Collect(DropOutput):
            async def write(self, batch: MessageBatch) -> None:
                delivered.extend(batch.to_binary())

        stream.output = _Collect()
        proc = stream.pipeline.processors[0]._inner
        mon = proc.integrity

        async def driver() -> None:
            while len(delivered) < 6:
                await asyncio.sleep(0.01)
            proc.runner.members[1].inject_step_fault("bitflip")
            t_arm, probes_at_arm = time.monotonic(), mon.n_probes
            pool_events["armed_at_delivered"] = len(delivered)
            while mon.n_quarantined < 1:
                await asyncio.sleep(0.01)
            pool_events["detect_s"] = round(time.monotonic() - t_arm, 3)
            pool_events["detect_probes"] = mon.n_probes - probes_at_arm
            while mon.n_repaired < 1:
                await asyncio.sleep(0.01)
            pool_events["repair_s"] = round(time.monotonic() - t_arm, 3)

        cancel = asyncio.Event()
        task = asyncio.create_task(stream.run(cancel))
        drv = asyncio.create_task(driver())
        t0 = time.monotonic()
        done, _ = await asyncio.wait({task}, timeout=max(seconds, 60.0))
        wedged = not done
        if done:
            task.result()
        else:
            cancel.set()
            try:
                await asyncio.wait_for(task, timeout=15.0)
            except (asyncio.TimeoutError, Exception):
                task.cancel()
        try:
            await asyncio.wait_for(drv, timeout=5.0)
        except (asyncio.TimeoutError, Exception):
            drv.cancel()
        states = [m.state() for m in mon.members]
        return {"delivered": len(delivered), "wedged": wedged,
                "elapsed_s": round(time.monotonic() - t0, 3),
                "monitor": mon.report(), "member_states": states}

    pool = asyncio.run(pool_phase())
    pool_out = {
        **pool_events,
        "offered_rows": n_pool,
        "delivered_rows": pool["delivered"],
        "member_states": pool["member_states"],
        "quarantined": pool["monitor"]["quarantined"],
        "repaired": pool["monitor"]["repaired"],
        # detection bound, in the monitor's own probes (a loaded CPU host
        # stretches a period's seconds, not its count): every pass checks
        # both members' digests, so the pass under way at the flip or the
        # one after it finds the drift
        "detect_within_ok": (pool_events.get("detect_probes") is not None
                             and pool_events["detect_probes"]
                             <= 2 * len(pool["member_states"])),
    }
    pool_out["pass"] = bool(not pool["wedged"]
                            and pool["delivered"] == n_pool
                            and pool_out["quarantined"] >= 1
                            and pool_out["repaired"] >= 1
                            and pool_out["detect_within_ok"]
                            and all(s == "healthy"
                                    for s in pool["member_states"]))
    verdict["pool"] = pool_out

    # -- phase 2: cluster-tier sdc under shadow-verify ----------------------
    def free_port() -> int:
        s = socket_mod.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    tmp = tempfile.mkdtemp(prefix="arkflow-sdc-soak-")
    cfg_paths = [os.path.join(tmp, f"worker-{i}.yaml") for i in range(2)]
    # worker 1 carries the armed sdc fault; worker 0 stays clean (the
    # reference + the shadow-verify tiebreak's healthy side)
    for i, path in enumerate(cfg_paths):
        with open(path, "w") as f:
            yaml.safe_dump(_sdc_worker_config(
                seed, step_ms, arm_at if i == 1 else 0), f)
    ports = [free_port(), free_port()]
    urls = [f"arkflow://127.0.0.1:{p}" for p in ports]
    logs = [os.path.join(tmp, f"worker-{i}.log") for i in range(2)]

    def spawn(i: int) -> subprocess.Popen:
        env = dict(os.environ)
        pin_cpu_env(env, n_devices=1)
        return subprocess.Popen(
            [sys.executable, "-m", "arkflow_tpu", "--cluster-worker",
             "--config", cfg_paths[i], "--host", "127.0.0.1",
             "--port", str(ports[i]), "--worker-id", f"sdc-w{i}"],
            cwd=repo_root, env=env,
            stdout=open(logs[i], "ab"), stderr=subprocess.STDOUT)

    async def wait_ready(wait_urls: list[str], budget_s: float) -> None:
        probe = ClusterDispatcher(wait_urls, name="sdc-soak-probe",
                                  heartbeat_s=999.0, connect_timeout_s=1.0)
        deadline = time.monotonic() + budget_s
        while True:
            await asyncio.gather(
                *(probe._probe(w) for w in probe.workers.values()),
                return_exceptions=True)
            if all(w.alive for w in probe.workers.values()):
                return
            if time.monotonic() >= deadline:
                down = [w.url for w in probe.workers.values() if not w.alive]
                raise RuntimeError(
                    f"sdc workers not ready within {budget_s:.0f}s: {down} "
                    f"(see {tmp}/worker-*.log)")
            await asyncio.sleep(0.5)

    class _LabelCollect(DropOutput):
        """Collects (payload, label) pairs — the corruption-delivery check
        compares delivered labels against a clean-worker reference."""

        def __init__(self, sink: list):
            self._sink = sink

        async def write(self, batch: MessageBatch) -> None:
            labels = batch.column("label").to_pylist()
            self._sink.extend(zip(batch.to_binary(), labels))

    def run_phase(cfg_map: dict, budget_s: float) -> dict:
        stream = build_stream(StreamConfig.from_mapping(cfg_map))
        delivered: list = []
        shed: list[bytes] = []
        stream.output = _LabelCollect(delivered)

        class _Shed(DropOutput):
            async def write(self, batch: MessageBatch) -> None:
                shed.extend(batch.to_binary())

        stream.error_output = _Shed()
        out: dict = {"delivered": delivered, "shed": shed, "stream": stream}

        async def bounded() -> None:
            cancel = asyncio.Event()
            task = asyncio.create_task(stream.run(cancel))
            t0 = time.monotonic()
            done, _ = await asyncio.wait({task}, timeout=budget_s)
            out["elapsed_s"] = time.monotonic() - t0
            out["wedged"] = not done
            if done:
                task.result()
            else:
                cancel.set()
                try:
                    await asyncio.wait_for(task, timeout=15.0)
                except (asyncio.TimeoutError, Exception):
                    task.cancel()

        asyncio.run(bounded())
        return out

    procs: list = [None, None]
    payloads = [f"sdc row {i:05d}" for i in range(n_chaos)]
    try:
        procs[0] = spawn(0)
        procs[1] = spawn(1)
        asyncio.run(wait_ready(urls, startup_budget))
        verdict["startup_s"] = round(time.monotonic() - t_start, 3)

        # reference: the clean worker's label for every chaos payload (a
        # subset is enough to pin the mapping; we reference ALL of them so
        # the corruption check covers every delivered row)
        ref = run_phase(_sdc_ingest_config(
            "sdc-ref", urls[:1], payloads, threads=2), max(seconds, 60.0))
        reference = dict(ref["delivered"])
        ref_ok = (not ref["wedged"] and len(reference) == n_chaos)
        verdict["reference"] = {"rows": len(reference), "ok": ref_ok}

        # chaos: both workers, shadow-verify on every batch; worker 1 arms
        # sdc on its 5th batch and garbles everything after
        chaos = run_phase(_sdc_ingest_config(
            "sdc-chaos", urls, payloads, threads=2, shadow_fraction=1.0,
            response_cache=True), max(seconds, 90.0))
        dispatcher = chaos["stream"].pipeline.processors[0].dispatcher
        cache = chaos["stream"].pipeline.processors[0].cache
        shadow = {k: int(c.value) for k, c in dispatcher.m_shadow.items()}
        delivered_payloads = [p for p, _ in chaos["delivered"]]
        corrupted = [p.decode() for p, lab in chaos["delivered"]
                     if reference.get(p) != lab]
        expected = set(p.encode() for p in payloads)
        seen = set(delivered_payloads) | set(chaos["shed"])
        lost = sorted(expected - seen)
        chaos_out = {
            "wedged": chaos["wedged"],
            "offered_rows": n_chaos,
            "delivered_rows": len(chaos["delivered"]),
            "shed_rows": len(chaos["shed"]),
            "lost_rows": len(lost),
            "corrupted_delivered_rows": len(corrupted),
            "shadow": shadow,
            "integrity_fences": int(dispatcher.m_integrity_fence.value),
            "cache_epoch_bumps": int(cache.epoch),
            "identity_ok": len(lost) == 0,
        }
        if corrupted:
            chaos_out["corrupted_sample"] = corrupted[:5]

        # the fenced worker must repair, re-register, and serve again
        revived = False
        revive_error = None
        try:
            asyncio.run(wait_ready(urls[1:], startup_budget))
            post = run_phase(_sdc_ingest_config(
                "sdc-revive", urls[1:],
                [f"revive row {i}" for i in range(2)], threads=1),
                max(seconds, 60.0))
            revived = len(post["delivered"]) == 2
        except Exception as e:
            revive_error = f"{type(e).__name__}: {e}"
        chaos_out["revived"] = revived
        if revive_error:
            chaos_out["revive_error"] = revive_error
        chaos_out["pass"] = bool(not chaos["wedged"]
                                 and ref_ok
                                 and chaos_out["identity_ok"]
                                 and chaos_out["corrupted_delivered_rows"] == 0
                                 and shadow["diverged"] >= 1
                                 and shadow["match"] >= 1
                                 and chaos_out["integrity_fences"] >= 1
                                 and chaos_out["cache_epoch_bumps"] >= 1
                                 and revived)
        verdict["chaos"] = chaos_out
        verdict["pass"] = bool(pool_out["pass"] and chaos_out["pass"])
    finally:
        for p in procs:
            if p is not None and p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=5)
                except Exception:
                    pass
    verdict["elapsed_s"] = round(time.monotonic() - t_start, 3)
    return _attach_tracing(verdict, trace_seq0, trace_forced0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seconds", type=float, default=60.0,
                    help="wall-clock bound for the whole soak (default 60)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--messages", type=int, default=48)
    ap.add_argument("--device-pool", type=int, default=2)
    ap.add_argument("--burst", action="store_true",
                    help="overload-control soak: burst fault drives offered "
                         "load past throughput; asserts bounded p99 + the "
                         "zero-silent-loss accounting identity")
    ap.add_argument("--noisy-tenant", action="store_true",
                    help="multi-tenant fairness soak: one tenant offers 10x "
                         "its quota; asserts quiet-tenant p99 within SLO, "
                         "quota sheds fully accounted, and duplicate-burst "
                         "cache hits with no extra device steps")
    ap.add_argument("--swap", action="store_true",
                    help="model-lifecycle soak: a corrupt checkpoint rolls "
                         "back and a good rolling hot-swap commits across a "
                         "device pool and a continuous generate server under "
                         "sustained load — zero failed/lost, bounded p99")
    ap.add_argument("--cluster", action="store_true",
                    help="disaggregated-serving soak: 2 local device-tier "
                         "worker processes behind a remote_tpu ingest "
                         "stream; asserts >=1.7x aggregate rows/s, "
                         "cross-process duplicate cache affinity, and zero "
                         "silent loss across a worker kill/restart")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode disaggregation soak: role-split "
                         "generation workers vs co-hosted at equal worker "
                         "count on a mixed-length load; asserts the TTFT-p99 "
                         "+ tokens/sec double win (core-count gated), "
                         "prefix affinity on the prefill sub-ring, and zero "
                         "silent loss through a mid-stream decode SIGKILL")
    ap.add_argument("--partition", action="store_true",
                    help="partition-tolerance soak: 2 worker processes, one "
                         "behind a frame-aware chaos proxy; asserts hedged "
                         "dispatch rides out a mid-load one-way partition "
                         "(bounded p99, detection within heartbeat_timeout), "
                         "the healed zombie's epoch stays fenced, corruption "
                         "is never silent, and the retry budget contains a "
                         "brownout retry storm with accounted sheds")
    ap.add_argument("--preempt", action="store_true",
                    help="elastic-fleet soak: 3 worker processes behind a "
                         "remote_tpu stream with the autoscaling controller "
                         "on; SIGKILLs workers mid-load (controller detects "
                         "+ respawns, zero silent loss, p99 gap within SLO) "
                         "then ramps load on an undersized fleet until a "
                         "warm-shape scale-out fires with zero failures")
    ap.add_argument("--tuner", action="store_true",
                    help="traffic-adaptive-shapes soak: a shifting-length "
                         "distribution (short->long mix flip mid-run) serves "
                         "on the static default AND with the runtime shape "
                         "tuner; asserts the tuned run beats static on rows/s "
                         "AND padding_waste_frac with zero on-path recompiles "
                         "after warmup, a forced probe-failure rollback, and "
                         "zero silent loss across flips")
    ap.add_argument("--sdc", action="store_true",
                    help="silent-data-corruption soak: a bitflipped pool "
                         "member is digest-detected, quarantined, repaired "
                         "and re-admitted within a probe period; a "
                         "sdc-corrupted cluster worker is caught by "
                         "shadow-verify, fenced via golden-probe tiebreak "
                         "and re-admitted after repair — zero corrupted "
                         "rows delivered, zero silent loss")
    ap.add_argument("--factor", type=int, default=4,
                    help="burst mode: offered-load multiplier (default 4)")
    ap.add_argument("--fast", action="store_true",
                    help="tier-1 smoke mode: <=12 messages, deterministic "
                         "faults only")
    args = ap.parse_args(argv)

    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if args.burst:
        # pure asyncio — no jax, no platform pinning needed
        verdict = run_burst_soak(seconds=args.seconds, seed=args.seed,
                                 messages=args.messages, factor=args.factor,
                                 fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.noisy_tenant:
        if os.environ.get("ARKFLOW_SOAK_KEEP_ENV") != "1":
            # the cache phase builds a tiny device stage: pin virtual CPU
            # BEFORE jax loads, like the default self-healing soak
            from arkflow_tpu.utils.cleanenv import pin_cpu_env

            pin_cpu_env(os.environ, n_devices=2)
        verdict = run_noisy_tenant_soak(seconds=args.seconds, seed=args.seed,
                                        fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.swap:
        if os.environ.get("ARKFLOW_SOAK_KEEP_ENV") != "1":
            from arkflow_tpu.utils.cleanenv import pin_cpu_env

            pin_cpu_env(os.environ, n_devices=2)
        verdict = run_swap_soak(seconds=args.seconds, seed=args.seed,
                                messages=args.messages, fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.cluster:
        # the INGEST process never imports jax; only the spawned device
        # workers do (each pins its own virtual-CPU env)
        verdict = run_cluster_soak(seconds=args.seconds, seed=args.seed,
                                   fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.partition:
        # like --cluster: the parent never imports jax — worker subprocesses
        # get their own pinned virtual-CPU env from the soak itself
        verdict = run_partition_soak(seconds=args.seconds, seed=args.seed,
                                     fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.disagg:
        # like --cluster: the parent never imports jax — worker subprocesses
        # get their own pinned virtual-CPU env from the soak itself
        verdict = run_disagg_soak(seconds=args.seconds, seed=args.seed,
                                  fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.preempt:
        # like --cluster: the parent never imports jax — worker subprocesses
        # get their own pinned virtual-CPU env from the soak itself
        verdict = run_preempt_soak(seconds=args.seconds, seed=args.seed,
                                   fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.sdc:
        if os.environ.get("ARKFLOW_SOAK_KEEP_ENV") != "1":
            # the pool phase builds a 2-member device pool in THIS process;
            # the cluster phase's worker subprocesses pin their own env
            from arkflow_tpu.utils.cleanenv import pin_cpu_env

            pin_cpu_env(os.environ, n_devices=2)
        verdict = run_sdc_soak(seconds=args.seconds, seed=args.seed,
                               fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if args.tuner:
        if os.environ.get("ARKFLOW_SOAK_KEEP_ENV") != "1":
            # tiny single-device serving: pin virtual CPU BEFORE jax loads
            from arkflow_tpu.utils.cleanenv import pin_cpu_env

            pin_cpu_env(os.environ, n_devices=1)
        verdict = run_tuner_soak(seconds=args.seconds, seed=args.seed,
                                 fast=args.fast)
        print(json.dumps(verdict, indent=2))
        return 0 if verdict["pass"] else 1

    if os.environ.get("ARKFLOW_SOAK_KEEP_ENV") != "1":
        # pin the virtual-CPU platform BEFORE jax loads (run_soak imports it)
        from arkflow_tpu.utils.cleanenv import pin_cpu_env

        pin_cpu_env(os.environ, n_devices=max(2, args.device_pool))

    verdict = run_soak(seconds=args.seconds, seed=args.seed,
                       messages=args.messages, pool=args.device_pool,
                       fast=args.fast)
    print(json.dumps(verdict, indent=2))
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
