"""Stream runtime: the 4-stage hot loop.

Functional clone of the reference's ``Stream::run`` (ref:
crates/arkflow-core/src/stream/mod.rs:79-398), re-expressed for asyncio:

    do_input -> [buffer] -> do_processor x N workers -> do_output

- Bounded queues of ``thread_num * 4`` between stages (ref :90-93).
- Workers stamp a sequence number at dequeue; the output task restores global
  order with a reorder map before writing (ref :280,319-353).
- Backpressure: when ``assigned - emitted > MAX_PENDING`` the workers pause
  (ref :34,263-273).
- Acks fire only after every produced batch was written (at-least-once,
  ref :379-396). A processor chain returning nothing acks immediately
  (ref :301-303).
- ``EndOfInput`` drains and shuts the stream down; ``Disconnection`` puts the
  input into a reconnect-forever loop with capped exponential backoff (the
  reference sleeps a fixed 5s, ref :176-203).
- Errors during processing route the original batch to ``error_output``:
  below ``max_delivery_attempts`` the batch is left unacked (nack) so the
  broker redelivers and the failure can heal; at the budget it is quarantined
  with attempt-count metadata. Output writes are retried with backoff behind
  an optional per-output circuit breaker; an ``error_output`` write failure
  falls back to retry-then-log instead of silently dropping the ack.
- Ordered close: input -> buffer -> pipeline -> output (ref :400-437).
"""

from __future__ import annotations

import asyncio
import logging
import time
from dataclasses import dataclass
from typing import Optional

from arkflow_tpu.batch import META_INGEST_TIME, MessageBatch, batch_fingerprint
from arkflow_tpu.components.base import Ack, Buffer, Input, Output, Resource, Temporary
from arkflow_tpu.components.registry import build_component
from arkflow_tpu.config import StreamConfig
from arkflow_tpu.errors import ArkError, Disconnection, EndOfInput
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.trace import activate, global_tracer, stage_span
from arkflow_tpu.runtime.overload import (
    FairQueue,
    OverloadConfig,
    OverloadController,
    attach_overload,
    input_pauses_on_overload,
)
from arkflow_tpu.runtime.pipeline import Pipeline
from arkflow_tpu.utils.circuit_breaker import CircuitBreaker, CircuitBreakerConfig
from arkflow_tpu.utils.retry import RetryConfig, retry_with_backoff

logger = logging.getLogger("arkflow.stream")

MAX_PENDING = 1024  # ref stream/mod.rs:34
RECONNECT_DELAY_S = 5.0  # cap of the reconnect backoff (the reference's fixed delay, ref stream/mod.rs:190)
#: bound on the delivery-attempt tracking table; entries clear on success,
#: so this only matters with thousands of concurrently failing batches
MAX_TRACKED_ATTEMPTS = 8192


@dataclass
class _WorkItem:
    batch: MessageBatch
    ack: Ack
    enqueued_at: float = 0.0  # loop-clock time it entered the worker queue
    #: capped tenant label (set at admission when tenant accounting is on);
    #: None routes FairQueue items to the control lane, so admission MUST
    #: stamp it before putting — the default only applies pre-admission
    tenant: Optional[str] = None
    #: the batch's parsed TraceContext (obs/trace.py), cached at creation so
    #: later stages never re-parse the metadata column; None = untraced
    trace: Optional[object] = None


class _Done:
    """Queue sentinel: upstream stage finished."""


_DONE = _Done()


class Stream:
    def __init__(
        self,
        input_: Input,
        pipeline: Pipeline,
        output: Output,
        error_output: Optional[Output] = None,
        buffer: Optional[Buffer] = None,
        temporaries: Optional[dict[str, Temporary]] = None,
        thread_num: int = 1,
        name: str = "stream",
        output_retry: Optional[RetryConfig] = None,
        output_breaker: Optional[CircuitBreakerConfig] = None,
        error_output_retry: Optional[RetryConfig] = None,
        error_output_breaker: Optional[CircuitBreakerConfig] = None,
        max_delivery_attempts: int = 1,
        reconnect_retry: Optional[RetryConfig] = None,
        queue_size: int = 0,
        overload: Optional[OverloadConfig] = None,
    ):
        self.input = input_
        self.pipeline = pipeline
        self.output = output
        self.error_output = error_output
        self.buffer = buffer
        self.temporaries = temporaries or {}
        self.thread_num = max(1, thread_num)
        self.name = name
        self.output_retry = output_retry or RetryConfig()
        self.error_output_retry = error_output_retry or self.output_retry
        self.max_delivery_attempts = max(1, max_delivery_attempts)
        self.reconnect_retry = reconnect_retry  # None -> default derived at run time
        #: stage-queue depth; 0 keeps the historical thread_num * 4
        self.queue_size = queue_size if queue_size > 0 else self.thread_num * 4
        #: overload controller (deadline admission / AIMD window / priority
        #: shedding); None = admit everything, the pre-overload behavior
        self.overload: Optional[OverloadController] = (
            OverloadController(overload, name=name, workers=self.thread_num,
                               max_window=self.queue_size)
            if overload is not None and overload.enabled else None)

        reg = global_registry()
        labels = {"stream": name}
        self.m_rows_in = reg.counter("arkflow_rows_in_total", "rows read from input", labels)
        self.m_rows_out = reg.counter("arkflow_rows_out_total", "rows written to output", labels)
        self.m_batches_in = reg.counter("arkflow_batches_in_total", "batches read from input", labels)
        self.m_batches_out = reg.counter("arkflow_batches_out_total", "batches written", labels)
        self.m_errors = reg.counter("arkflow_process_errors_total", "processor errors", labels)
        self.m_write_errors = reg.counter("arkflow_write_errors_total", "output write errors", labels)
        self.m_proc_latency = reg.histogram("arkflow_process_seconds", "pipeline latency", labels)
        self.m_e2e_latency = reg.histogram("arkflow_e2e_seconds", "read-to-written latency", labels)
        self.m_pending = reg.gauge("arkflow_pending_batches", "in-flight batches", labels)
        self.m_read_latency = reg.histogram(
            "arkflow_input_read_seconds", "time blocked in input.read()", labels)
        self.m_queue_wait = reg.histogram(
            "arkflow_queue_wait_seconds", "work-item wait between input and worker", labels)
        self.m_write_latency = reg.histogram(
            "arkflow_output_write_seconds", "output.write() latency per batch", labels)
        self.m_backpressure_s = reg.counter(
            "arkflow_backpressure_seconds_total",
            "worker seconds stalled on the reorder window", labels)
        self.m_out_retries = reg.counter(
            "arkflow_output_retries_total", "output write retry attempts", labels)
        self.m_quarantined = reg.counter(
            "arkflow_quarantined_batches_total",
            "batches quarantined to error_output after exhausting delivery attempts", labels)
        self.m_quarantine_drops = reg.counter(
            "arkflow_quarantine_drops_total",
            "batches dropped because the error_output write itself kept failing", labels)
        self.m_ack_failures = reg.counter(
            "arkflow_ack_failures_total", "ack callbacks that raised", labels)
        self._out_breaker = (
            CircuitBreaker(
                output_breaker,
                gauge=reg.gauge("arkflow_circuit_state",
                                "output circuit breaker state (0 closed, 1 open, 2 half-open)",
                                {**labels, "output": "main"}),
                trip_counter=reg.counter("arkflow_circuit_trips_total",
                                         "circuit breaker open transitions",
                                         {**labels, "output": "main"}),
            ) if output_breaker else None
        )
        self._err_breaker = (
            CircuitBreaker(
                error_output_breaker,
                gauge=reg.gauge("arkflow_circuit_state",
                                "output circuit breaker state (0 closed, 1 open, 2 half-open)",
                                {**labels, "output": "error"}),
                trip_counter=reg.counter("arkflow_circuit_trips_total",
                                         "circuit breaker open transitions",
                                         {**labels, "output": "error"}),
            ) if error_output_breaker else None
        )

        #: per-batch tracing (obs/trace.py): the process-global tracer — the
        #: engine configured it from the `tracing:` block before streams run
        self.tracer = global_tracer()

        # runtime state
        self._pause_source = False  # resolved at run() from the input chain
        self._seq_assigned = 0
        self._seq_emitted = 0
        #: delivery attempts per failing batch fingerprint; cleared on success
        self._attempts: dict[bytes, int] = {}
        #: trace identity of failing batches, keyed like _attempts: a broker
        #: redelivery re-reads the raw record (no metadata columns), so the
        #: retry re-enters the SAME trace via this table instead. Populated
        #: only on failure paths — the all-healthy hot path never hashes.
        self._trace_ids: dict[bytes, tuple[str, bool]] = {}
        #: set by the output stage when the reorder window drains below
        #: MAX_PENDING — backpressured workers wake on it instead of polling
        self._drained = asyncio.Event()

    # -- lifecycle ---------------------------------------------------------

    async def run(self, cancel: asyncio.Event) -> None:
        """Run until the input ends or ``cancel`` is set; drains before returning."""
        # processors first: model warmup compiles must finish before the
        # input starts producing, or the first batches queue behind a
        # multi-second compile and pollute e2e latency
        await self.pipeline.connect()
        await self.input.connect()
        await self.output.connect()
        if self.error_output is not None:
            await self.error_output.connect()
        for t in self.temporaries.values():
            await t.connect()
        # push-based inputs (HTTP) get the controller for their 429 path;
        # pull-based brokers opt into cooperative pause instead. The buffer
        # and processors get it too: tenant-lane capping and cache
        # tenant-hit labels must reserve/cap EXACTLY like admission labels
        attach_overload(self.input, self.overload)
        attach_overload(self.buffer, self.overload)
        for proc in getattr(self.pipeline, "processors", None) or []:
            attach_overload(proc, self.overload)
        # shape-tuner wiring (tpu/tuner.py): bind each adaptive processor's
        # tuner to THIS stream's buffer, so a committed flip retargets
        # exactly this stream's coalescer lanes — never another stream's
        # that merely configured the same grid (walks _inner chaos chains
        # like attach_overload)
        if self.buffer is not None and hasattr(self.buffer, "retarget_shapes"):
            for proc in getattr(self.pipeline, "processors", None) or []:
                node, seen = proc, set()
                while node is not None and id(node) not in seen:
                    seen.add(id(node))
                    tn = getattr(node, "tuner", None)
                    if tn is not None and hasattr(tn, "bind_listener"):
                        tn.bind_listener(self.buffer)
                        break
                    node = getattr(node, "_inner", None)
        self._pause_source = (self.overload is not None
                              and input_pauses_on_overload(self.input))

        qsize = self.queue_size  # pipeline.queue_size; default ref stream/mod.rs:90-93
        if self.overload is not None and self.overload.cfg.tenants is not None:
            # multi-tenant serving: the worker queue itself schedules by
            # weighted deficit round robin, so one tenant's admitted backlog
            # cannot sit in front of everyone else's dequeues
            input_q = FairQueue(self.overload, qsize)
        else:
            input_q = asyncio.Queue(maxsize=qsize)
        output_q: asyncio.Queue = asyncio.Queue(maxsize=qsize)

        tasks = [asyncio.create_task(self._do_input(input_q, cancel), name=f"{self.name}-input")]
        if self.buffer is not None:
            tasks.append(asyncio.create_task(self._do_buffer(input_q), name=f"{self.name}-buffer"))
        for i in range(self.thread_num):
            tasks.append(
                asyncio.create_task(self._do_processor(input_q, output_q), name=f"{self.name}-proc-{i}")
            )
        out_task = asyncio.create_task(self._do_output(output_q), name=f"{self.name}-output")

        try:
            await asyncio.gather(*tasks)
            # each worker sent its sentinel; output drains the reorder map and exits
            await out_task
        except BaseException:
            for t in [*tasks, out_task]:
                t.cancel()
            await asyncio.gather(*tasks, out_task, return_exceptions=True)
            raise
        finally:
            await self._close_all()

    async def _close_all(self) -> None:
        # ordered close: input -> buffer -> pipeline -> output (ref :400-437)
        for stage, closer in (
            ("input", self.input.close),
            *((("buffer", self.buffer.close),) if self.buffer else ()),
            ("pipeline", self.pipeline.close),
            *((f"temporary:{name}", t.close)
              for name, t in self.temporaries.items()),
            *((("error_output", self.error_output.close),)
              if self.error_output else ()),
            ("output", self.output.close),
        ):
            try:
                await closer()
            except Exception:
                comp = type(getattr(closer, "__self__", closer)).__name__
                logger.exception("[%s] error during close of %s (%s)",
                                 self.name, stage, comp)

    # -- stages ------------------------------------------------------------

    async def _do_input(self, input_q: asyncio.Queue, cancel: asyncio.Event) -> None:
        """Read loop; feeds the buffer (if any) or the worker queue directly."""
        cancel_wait = asyncio.ensure_future(cancel.wait())
        loop = asyncio.get_running_loop()
        try:
            while not cancel.is_set():
                if self._pause_source and self.overload.should_pause():
                    # cooperative backpressure: a pull-based broker keeps the
                    # backlog on its side — strictly better than fetching
                    # batches we would immediately shed and nack back
                    t_pause = loop.time()
                    while self.overload.should_pause() and not cancel.is_set():
                        await self.overload.wait_capacity(0.25)
                    self.overload.m_paused_s.inc(loop.time() - t_pause)
                    if cancel.is_set():
                        break
                t_read = loop.time()
                read_f = asyncio.ensure_future(self.input.read())
                done, _ = await asyncio.wait(
                    {read_f, cancel_wait}, return_when=asyncio.FIRST_COMPLETED
                )
                read_dt = loop.time() - t_read
                if read_f in done:
                    # only completed reads count: a cancel while idle must
                    # not record time-until-shutdown as read latency
                    self.m_read_latency.observe(read_dt)
                if read_f not in done:
                    read_f.cancel()
                    try:
                        await read_f
                    except (asyncio.CancelledError, Exception):
                        pass
                    break
                try:
                    batch, ack = read_f.result()
                except EndOfInput:
                    logger.info("[%s] input exhausted (EOF)", self.name)
                    break
                except Disconnection as e:
                    # reconnect-forever loop with capped exponential backoff
                    # (the reference sleeps a fixed 5s, ref :183-194); the cap
                    # defaults to the module-level RECONNECT_DELAY_S so the
                    # old knob still shortens test reconnects
                    schedule = self.reconnect_retry or RetryConfig(
                        max_delay_ms=max(1, int(RECONNECT_DELAY_S * 1000)))
                    attempt = 0
                    logger.warning("[%s] input disconnected (%s); reconnecting in %.2fs",
                                   self.name, e, schedule.delay_s(0))
                    while not cancel.is_set():
                        try:
                            await asyncio.sleep(schedule.delay_s(attempt))
                            await self.input.connect()
                            break
                        except Exception as re:
                            attempt += 1
                            logger.warning("[%s] reconnect failed (attempt %d): %s; backing off",
                                           self.name, attempt, re)
                    continue
                except ArkError as e:
                    logger.error("[%s] input read error: %s", self.name, e)
                    await asyncio.sleep(0.1)
                    continue
                ctx = None
                if self.tracer.enabled:
                    # a trace context already on the batch means redelivery
                    # (or an upstream tier stamped it): the SAME trace
                    # accumulates the retry's spans. First deliveries root a
                    # fresh trace here; input_decode covers read+decode.
                    ctx = batch.trace_context()
                    redelivered = ctx is not None
                    if ctx is None:
                        # a broker redelivery of a failed batch re-enters
                        # its original trace (fingerprint-keyed, failure
                        # paths only); fresh batches root a new one
                        ctx = self._redelivered_trace(batch)
                        redelivered = ctx is not None
                        if ctx is None:
                            ctx = self.tracer.begin()
                        batch = batch.with_trace(ctx)
                    self.tracer.record(
                        ctx, "input_decode", read_dt,
                        attrs=({"redelivered": True} if redelivered else None))
                item = _WorkItem(batch.with_ingest_time(), ack, loop.time(),
                                 trace=ctx)
                self.m_batches_in.inc()
                self.m_rows_in.inc(batch.num_rows)
                if self.buffer is not None:
                    # admission happens at the worker-queue boundary
                    # (_do_buffer), after windowing/coalescing
                    await self.buffer.write(item.batch, item.ack)
                elif await self._admit_or_shed(item):
                    await input_q.put(item)
        finally:
            cancel_wait.cancel()
            if self.buffer is not None:
                await self.buffer.close()  # buffer drains remaining windows, then its reader exits
            else:
                for _ in range(self.thread_num):
                    await input_q.put(_DONE)

    async def _do_buffer(self, input_q: asyncio.Queue) -> None:
        """Move merged window/micro-batches from the buffer into the worker queue."""
        loop_time = asyncio.get_running_loop().time
        while True:
            item = await self.buffer.read()
            if item is None:
                for _ in range(self.thread_num):
                    await input_q.put(_DONE)
                return
            batch, ack = item
            ctx = None
            if self.tracer.enabled:
                batch, ctx = self._trace_emission(batch)
            work = _WorkItem(batch, ack, loop_time(), trace=ctx)
            if await self._admit_or_shed(work):
                await input_q.put(work)

    def _trace_emission(self, batch: MessageBatch):
        """Trace bookkeeping for a buffer emission. A merged emission (rows
        from several source batches) starts a NEW trace whose root span
        records parent links to every source trace; the sources are closed
        with status ``coalesced`` pointing at the merged id. A pass-through
        emission keeps its context. Either way the buffer/coalescer wait is
        recorded — from the buffer's own monotonic measurement when it
        provides one (``last_emission_wait_s``), else from the oldest row's
        ingest time."""
        wait_s = getattr(self.buffer, "last_emission_wait_s", None)
        if wait_s is None:
            ingest = batch.get_meta(META_INGEST_TIME)
            wait_s = (max(0.0, time.time() - float(ingest) / 1000.0)
                      if ingest is not None else 0.0)
        contexts = batch.source_trace_contexts()
        if len(contexts) <= 1:
            # no trace column (e.g. a window buffer's SQL projected the
            # metadata away): trace via the work item only — re-stamping
            # would inject a metadata column into user-shaped query output
            ctx = contexts[0] if contexts else self.tracer.begin()
            self.tracer.record(ctx, "buffer_wait", wait_s)
            return batch, ctx
        # merged emission: fresh trace, parent links both ways
        sources = [c.trace_id for c in contexts]
        ctx = self.tracer.begin()
        self.tracer.record(ctx, "coalesce_wait", wait_s,
                           attrs={"links": sources})
        for src in contexts:
            self.tracer.finish(src, "coalesced",
                               attrs={"merged_into": ctx.trace_id})
        return batch.with_trace(ctx), ctx

    async def _do_processor(self, input_q: asyncio.Queue, output_q: asyncio.Queue) -> None:
        """Worker: pipeline.process with seq stamping + backpressure (THE hot loop).

        Every attribute chased per batch here shows up directly in the
        saturated-ingest headline, so loop-invariant lookups (bound methods,
        the overload controller, the clock) are hoisted once per worker and
        tracing calls are skipped outright for untraced items instead of
        paying the no-op call + context-manager entries per batch."""
        loop_time = asyncio.get_running_loop().time
        # the stage name distinguishes WDRR scheduling waits from plain
        # FIFO queue waits in the breakdown (same measurement point)
        queue_stage = ("fair_queue_wait" if isinstance(input_q, FairQueue)
                       else "queue_wait")
        q_get = input_q.get
        q_put = output_q.put
        process = self.pipeline.process
        tracer = self.tracer
        record = tracer.record
        overload = self.overload
        observe_wait = self.m_queue_wait.observe
        observe_proc = self.m_proc_latency.observe
        set_pending = self.m_pending.set
        while True:
            # backpressure: event-driven wakeup the moment the reorder window
            # drains (the reference sleeps 100-500ms, ref :263-273; a poll
            # adds up to 100ms of latency noise per stall)
            if (self._seq_assigned - self._seq_emitted) > MAX_PENDING:
                t_bp = loop_time()
                while (self._seq_assigned - self._seq_emitted) > MAX_PENDING:
                    self._drained.clear()
                    try:
                        # bounded wait: never deadlocks even if an emit is lost
                        await asyncio.wait_for(self._drained.wait(), 1.0)
                    except asyncio.TimeoutError:
                        pass
                self.m_backpressure_s.inc(loop_time() - t_bp)
            item = await q_get()
            if isinstance(item, _Done):
                await q_put(_DONE)
                return
            now = loop_time()
            wait = now - item.enqueued_at
            observe_wait(wait)
            trace = item.trace
            if trace is not None:
                record(trace, queue_stage, wait)
            if overload is not None:
                overload.on_dequeue(wait, now, tenant=item.tenant)
                remaining = item.batch.remaining_deadline_ms(
                    overload.cfg.deadline_ms)
                if remaining is not None and remaining <= 0:
                    # went stale in the queue: finishing it is strictly worse
                    # than shedding (the caller already gave up) — and the
                    # expiry check is what bounds delivered-batch latency
                    await self._shed_item(item, overload.expire(item.tenant))
                    continue
            seq = self._seq_assigned
            self._seq_assigned += 1
            set_pending(self._seq_assigned - self._seq_emitted)
            t0 = loop_time()
            try:
                if trace is not None:
                    # activate the batch's trace scope: runner/processor spans
                    # (infeed prep, device step, cluster hops) nest under the
                    # process span with zero API plumbing
                    with activate(tracer, trace):
                        with stage_span("process"):
                            results = await process(item.batch)
                else:
                    results = await process(item.batch)
                err = None
            except Exception as e:  # processor failure -> error path
                results = []
                err = e
            dt = loop_time() - t0
            observe_proc(dt)
            if overload is not None:
                overload.observe_step(dt)
            await q_put((seq, item, results, err))

    async def _do_output(self, output_q: asyncio.Queue) -> None:
        """Reorder by seq and write; ack only on full success (ref :319-397)."""
        reorder: dict[int, tuple] = {}
        next_seq = 0
        done_workers = 0
        total_workers = self.thread_num
        q_get = output_q.get
        while True:
            msg = await q_get()
            if isinstance(msg, _Done):
                done_workers += 1
                if done_workers >= total_workers:
                    if reorder:
                        # a seq gap at shutdown (worker died mid-batch):
                        # nack the stuck batches so their sources redeliver
                        # NOW instead of waiting out broker ack timeouts
                        logger.error(
                            "[%s] %d batches stuck in reorder at shutdown; "
                            "nacking for redelivery", self.name, len(reorder))
                        for seq in sorted(reorder):
                            item, _results, _err = reorder.pop(seq)
                            await self._safe_nack(item.ack)
                    return
                continue
            seq, item, results, err = msg
            reorder[seq] = (item, results, err)
            while next_seq in reorder:
                item, results, err = reorder.pop(next_seq)
                next_seq += 1
                self._seq_emitted = next_seq
                if (self._seq_assigned - self._seq_emitted) <= MAX_PENDING:
                    self._drained.set()  # wake backpressured workers now
                await self._emit(item, results, err)

    # -- overload admission (runtime/overload.py) --------------------------

    async def _admit_or_shed(self, item: _WorkItem) -> bool:
        """Admission gate at the worker-queue boundary: True to enqueue,
        False when the controller shed the batch (already dispatched to
        error_output / nack — the caller just skips the put)."""
        ctrl = self.overload
        if ctrl is None:
            return True
        remaining = item.batch.remaining_deadline_ms(ctrl.cfg.deadline_ms)
        tokens = 0.0
        if ctrl.cfg.tenants is not None:
            # capped label computed ONCE here; every later touch (fair
            # queue lane, dequeue accounting, expiry, latency) reuses it
            item.tenant = ctrl.tenant_label(item.batch.tenant())
            if ctrl.meters_tokens():
                tokens = self._estimate_tokens(item.batch, ctrl.cfg.tenants)
        reason = ctrl.admit(item.batch.priority_band(ctrl.cfg.priority), remaining,
                            tenant=item.tenant, rows=float(item.batch.num_rows),
                            tokens=tokens)
        if reason is None:
            ctrl.on_enqueue(item.tenant)
            return True
        await self._shed_item(item, reason)
        return False

    @staticmethod
    def _estimate_tokens(batch: MessageBatch, policy) -> float:
        """Estimated token cost for tokens/s quota metering — the PR-6
        vectorized payload estimator (one pass over the Arrow offsets),
        reading the policy's ``token_field``/``token_bytes`` (which must
        match the serving stage's payload column). Batches without a usable
        payload column meter one token per row, so malformed traffic still
        counts against SOMETHING instead of riding free."""
        from arkflow_tpu.batch import DEFAULT_BINARY_VALUE_FIELD
        from arkflow_tpu.tpu.extract import payload_token_estimates

        try:
            col = batch.column(policy.token_field or DEFAULT_BINARY_VALUE_FIELD)
            return float(payload_token_estimates(
                col, token_bytes=policy.token_bytes).sum())
        except Exception:
            return float(batch.num_rows)

    async def _shed_item(self, item: _WorkItem, reason: str) -> None:
        """Dispose of a shed batch without silent loss: route to
        error_output tagged ``overloaded`` (preferred — terminal, keeps the
        accounting identity), else nack so the broker redelivers after the
        brownout, else log-and-ack (counted in ``arkflow_shed_total``)."""
        # forced sampling: a shed/expired batch is exactly the trace an
        # operator needs — commit it regardless of the head-sampling draw
        self.tracer.finish(item.trace,
                           "deadline" if reason == "deadline" else "shed",
                           attrs={"reason": reason})
        if self.error_output is not None:
            await self._error_route_or_drop(
                item.batch, {"error": "overloaded", "shed_reason": reason},
                f"[{self.name}] shed write",
                "[%s] error_output rejected a shed batch (%s); dropping "
                "WITH ack", self.name, reason)
            # terminal disposition: drop the fingerprint's delivery-attempt
            # count so an identical later payload starts with a fresh budget
            # (the nack path below keeps it — redelivery continues)
            self._clear_attempts(item.batch)
            await self._safe_ack(item.ack)
            return
        # an ABSOLUTE deadline that has already passed can only get MORE
        # expired on redelivery (unlike a TTL, which the re-stamped ingest
        # time resets), so nacking would spin shed->redeliver->shed forever
        expired_abs = (item.batch.deadline_unix_ms() is not None
                       and (item.batch.remaining_deadline_ms() or 0.0) <= 0)
        if getattr(item.ack, "redeliverable", False) and not expired_abs:
            await self._safe_nack(item.ack)
            # in-process brokers requeue instantly; pace the respin so the
            # read loop doesn't spin hot on shed->redeliver->shed
            if self.overload is not None:
                await self.overload.wait_capacity(0.05)
            else:
                await asyncio.sleep(0.05)
            return
        logger.warning("[%s] shed batch (%s) with no error_output and %s; "
                       "dropping WITH ack", self.name, reason,
                       "an expired absolute deadline" if expired_abs
                       else "no redelivery")
        self._clear_attempts(item.batch)
        await self._safe_ack(item.ack)

    # -- delivery path (hardened) -----------------------------------------

    @staticmethod
    def _fingerprint(batch: MessageBatch) -> bytes:
        """Stable batch identity for the delivery-attempt budget — the
        shared ``batch_fingerprint`` definition, which the coalescer's
        poison-suspect table must match exactly. Computed on failure paths,
        plus on successes only while failures are being tracked (the table
        is non-empty); the all-healthy hot path never pays for it."""
        return batch_fingerprint(batch)

    def _bump_attempts(self, batch: MessageBatch, trace=None) -> int:
        key = self._fingerprint(batch)
        n = self._attempts.get(key, 0) + 1
        if key not in self._attempts and len(self._attempts) >= MAX_TRACKED_ATTEMPTS:
            evicted = next(iter(self._attempts))
            self._attempts.pop(evicted)
            self._trace_ids.pop(evicted, None)
        self._attempts[key] = n
        if trace is not None:
            # remember the failing batch's trace identity so its broker
            # redelivery (raw record, no columns) re-enters the same trace
            self._trace_ids[key] = (trace.trace_id, trace.sampled)
        return n

    def _clear_attempts(self, batch: MessageBatch) -> None:
        if self._attempts:
            key = self._fingerprint(batch)
            self._attempts.pop(key, None)
            self._trace_ids.pop(key, None)

    def _redelivered_trace(self, batch: MessageBatch):
        """Trace context of a previously-failed delivery of this batch, or
        None. Hashes only while failures are outstanding (the table is
        non-empty) — same discipline as the attempt budget."""
        if not self._trace_ids:
            return None
        from arkflow_tpu.obs.trace import TraceContext

        hit = self._trace_ids.get(self._fingerprint(batch))
        if hit is None:
            return None
        return TraceContext(trace_id=hit[0], sampled=hit[1])

    async def _safe_ack(self, ack: Ack) -> None:
        """Acks confirm work already durably written; a failing ack must not
        crash the output stage (the broker redelivers and dedup is the
        consumer's concern under at-least-once)."""
        try:
            await ack.ack()
        except Exception as e:
            self.m_ack_failures.inc()
            logger.warning("[%s] ack failed (duplicate delivery possible): %s", self.name, e)

    async def _safe_nack(self, ack: Ack) -> None:
        try:
            await ack.nack()
        except Exception as e:
            logger.warning("[%s] nack failed: %s", self.name, e)

    async def _write_guarded(self, output: Output, breaker: Optional[CircuitBreaker],
                             retry_cfg: RetryConfig, batch: MessageBatch, what: str) -> None:
        """One delivery: retry-with-backoff around write attempts, each
        attempt gated by the output's circuit breaker (when configured)."""

        async def attempt() -> None:
            if breaker is not None:
                await breaker.acquire()
            try:
                await output.write(batch)
            except Exception:
                if breaker is not None:
                    breaker.record_failure()
                raise
            if breaker is not None:
                breaker.record_success()

        await retry_with_backoff(attempt, retry_cfg, what=what,
                                 on_retry=self.m_out_retries.inc)

    async def _error_route_or_drop(self, batch: MessageBatch, meta: dict,
                                   what: str, fail_log: str, *fail_args) -> bool:
        """Shared error_output dispatch for quarantine and overload sheds:
        tag, write with retry + breaker; on persistent failure count a
        quarantine drop and log. The caller always acks afterwards — a batch
        that can no longer go anywhere must not wedge the stream on eternal
        redelivery."""
        tagged = batch.with_ext_metadata(meta)
        try:
            await self._write_guarded(self.error_output, self._err_breaker,
                                      self.error_output_retry, tagged, what)
            return True
        except Exception:
            self.m_quarantine_drops.inc()
            logger.exception(fail_log, *fail_args)
            return False

    async def _quarantine(self, item: _WorkItem, reason: str, attempts: int) -> None:
        """Route a poisoned batch to error_output with attempt-count metadata
        and ack it."""
        if await self._error_route_or_drop(
                item.batch, {"error": reason, "delivery_attempts": str(attempts)},
                f"[{self.name}] error_output write",
                "[%s] error_output write kept failing; DROPPING batch after %d "
                "delivery attempt(s) (reason: %s)", self.name, attempts, reason):
            self.m_quarantined.inc()
        self._clear_attempts(item.batch)
        await self._safe_ack(item.ack)

    async def _emit(self, item: _WorkItem, results: list[MessageBatch], err: Optional[Exception]) -> None:
        if err is not None:
            reason = getattr(err, "shed_reason", None)
            if reason is not None:
                # a load-shed raised from INSIDE the chain (e.g. the cluster
                # dispatcher's retry budget during a brownout): not a
                # processing failure — route through the shed path so the
                # offered == delivered + shed identity holds and the batch
                # doesn't burn delivery attempts toward quarantine
                if self.overload is not None:
                    c = self.overload.m_shed.get(reason)
                    if c is not None:
                        c.inc()
                await self._shed_item(item, reason)
                return
            self.m_errors.inc()
            attempts = self._bump_attempts(item.batch, trace=item.trace)
            # forced sampling: every failed attempt commits its trace (the
            # redelivery re-enters the SAME trace id at _do_input)
            self.tracer.finish(item.trace, "error",
                               attrs={"error": str(err)[:200],
                                      "attempt": attempts})
            if attempts < self.max_delivery_attempts and getattr(
                    item.ack, "redeliverable", False):
                # transient failures (model OOM, lookup table blip) heal via
                # redelivery; only a batch that keeps failing is quarantined.
                # Without in-session redelivery (Ack.redeliverable) leaving
                # the batch unacked would silently drop or strand it — those
                # sources quarantine right away.
                logger.warning("[%s] processing failed (delivery %d/%d); leaving "
                               "unacked for redelivery: %s", self.name, attempts,
                               self.max_delivery_attempts, err)
                await self._safe_nack(item.ack)
                return
            if self.error_output is not None:
                await self._quarantine(item, str(err), attempts)
            else:
                logger.error("[%s] processing error (no error_output): %s", self.name, err)
                self._clear_attempts(item.batch)
                await self._safe_ack(item.ack)
            return
        if not results:
            # ProcessResult::None -> drop + ack (ref :301-303)
            self.tracer.finish(item.trace, "ok", attrs={"results": 0})
            await self._safe_ack(item.ack)
            return
        loop = asyncio.get_running_loop()
        try:
            t_write0 = loop.time()
            for b in results:
                t_w = loop.time()
                await self._write_guarded(self.output, self._out_breaker,
                                          self.output_retry, b,
                                          f"[{self.name}] output write")
                self.m_write_latency.observe(loop.time() - t_w)
                self.m_batches_out.inc()
                self.m_rows_out.inc(b.num_rows)
            self.tracer.record(item.trace, "output_write",
                               loop.time() - t_write0,
                               attrs=({"batches": len(results)}
                                      if len(results) > 1 else None))
        except Exception as e:
            self.m_write_errors.inc()
            attempts = self._bump_attempts(item.batch, trace=item.trace)
            self.tracer.finish(item.trace, "error",
                               attrs={"error": f"output write failed: {e}"[:200],
                                      "attempt": attempts})
            if self.error_output is not None and (
                    attempts >= self.max_delivery_attempts
                    or not getattr(item.ack, "redeliverable", False)):
                logger.error("[%s] output write failed after %d delivery attempt(s); "
                             "quarantining: %s", self.name, attempts, e)
                await self._quarantine(item, f"output write failed: {e}", attempts)
            else:
                logger.error("[%s] output write failed (delivery %d/%d); not acking: %s",
                             self.name, attempts, self.max_delivery_attempts, e)
                await self._safe_nack(item.ack)
            return
        self._clear_attempts(item.batch)
        ingest = item.batch.get_meta("__meta_ingest_time")
        e2e = None
        if ingest is not None:
            e2e = max(0.0, time.time() - ingest / 1000.0)
            self.m_e2e_latency.observe(e2e)
            if self.overload is not None and item.tenant is not None:
                # tenant-labeled delivered latency: what the noisy-tenant
                # soak's per-tenant p99 SLO assertion reads
                self.overload.observe_tenant_latency(item.tenant, e2e)
        self.tracer.finish(item.trace, "ok", e2e_s=e2e)
        await self._safe_ack(item.ack)


def build_stream(cfg: StreamConfig, name: Optional[str] = None) -> Stream:
    """Construct a Stream from config via the builder registries
    (ref StreamConfig::build, stream/mod.rs:453-492)."""
    resource = Resource()
    # temporaries first, so processors can look them up (ref :459-467)
    for tcfg in cfg.temporary:
        resource.temporaries[tcfg.name] = build_component("temporary", tcfg.config, resource)
    input_ = build_component("input", cfg.input, resource)
    if cfg.pipeline.process_pool > 0:
        from arkflow_tpu.runtime.procpool import ProcessPoolPipeline

        # chain lives in the workers; nothing is built in-parent (a parent
        # copy would double-open connections the workers also hold)
        pipeline = ProcessPoolPipeline(
            cfg.pipeline.processors, cfg.pipeline.process_pool,
            temporary_configs=[(t.name, t.config) for t in cfg.temporary])
    else:
        processors = [build_component("processor", p, resource)
                      for p in cfg.pipeline.processors]
        pipeline = Pipeline(processors)
    output = build_component("output", cfg.output, resource)
    error_output = build_component("output", cfg.error_output, resource) if cfg.error_output else None
    buffer = build_component("buffer", cfg.buffer, resource) if cfg.buffer else None
    return Stream(
        input_=input_,
        pipeline=pipeline,
        output=output,
        error_output=error_output,
        buffer=buffer,
        temporaries=resource.temporaries,
        thread_num=cfg.pipeline.effective_threads(),
        name=name or cfg.name or "stream",
        output_retry=cfg.output_retry,
        output_breaker=cfg.output_circuit_breaker,
        error_output_retry=cfg.error_output_retry,
        error_output_breaker=cfg.error_output_circuit_breaker,
        max_delivery_attempts=cfg.pipeline.max_delivery_attempts,
        reconnect_retry=cfg.input_reconnect,
        queue_size=cfg.pipeline.effective_queue_size(),
        overload=cfg.pipeline.overload,
    )
