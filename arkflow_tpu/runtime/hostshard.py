"""Process-sharded ingest hot path behind one endpoint.

``pipeline.process_pool`` (runtime/procpool.py) escapes the GIL for the
processor chain only: decode, coalescing, admission and dispatch still run
in the parent process, and at saturation the profile shows the batch
spending most of its end-to-end time in ``queue_wait`` — the host wall is
the single-process hot loop, not the chain. ``pipeline.ingest_shards: N``
breaks that wall by running the ENTIRE hot path (coalesce -> admission ->
process) in N shard processes behind the parent's single endpoint:

- The parent keeps the input, the output and the error_output — one
  endpoint, one ack domain, one place where the zero-silent-loss identity
  (offered == delivered + shed) is enforced.
- The stage queue between input and workers becomes an Arrow-IPC flight
  hop over a unix socket (the same length-prefixed frames and zero-copy
  ``batch_to_ipc`` the cluster plane uses, connect/flight.py).
- Batches are partitioned by the existing ``batch_fingerprint`` (or the
  tenant hash when tenant accounting is on) over a ``HashRing``
  (runtime/cluster.py), so each shard owns a disjoint key range:
  byte-identical duplicates coalesce in ONE shard, response-cache entries
  stay hot in the shard that made them, and per-key poison/attempt state
  never needs cross-shard coordination.
- Each shard runs its own AIMD admission window / deadline / priority /
  WDRR fairness (``OverloadConfig.shard_local``), while tenant QUOTAS are
  granted exactly once in the parent's shared quota plane
  (``OverloadController.admit_quota``) — N shards each holding the full
  quota would over-grant every tenant's contract N times.
- The parent assigns one global sequence number per dispatched delivery
  and restores global output order with a reorder window keyed on those
  seqs; a merged (coalesced) shard emission anchors at the LOWEST covered
  seq, which is exactly where the single-process stream would have
  emitted it.
- A SIGKILLed shard is detected by socket EOF: its in-flight deliveries
  are redispatched in seq order to the ring survivors (the parent still
  holds every batch + ack until disposition). Respawning replacement
  shards is the fleet controller's job (runtime/fleet.py), not this
  plane's.

Tracing: the shard records ``shard_hop`` (send->receive), buffer/coalesce
waits, ``queue_wait`` and ``process`` spans into its own process-local
tracer and exports them with each disposition; the parent grafts them
into the batch's trace (``Tracer.adopt_spans``) before finishing it, so
``stage_breakdown`` shows the sharded pipeline end to end.

Device processors (``tpu_inference``/``tpu_generate``) are allowed in
shards where every shard owns an independent XLA client: on the CPU
platform (``JAX_PLATFORMS=cpu``, which the spawned shards inherit), or
with a single shard (the parent never touches jax). A chip belongs to one
process at a time, so N > 1 shards against an accelerator is refused at
config time (``build_sharded_stream``) instead of hanging at start-up;
use the cluster/remote_tpu plane for that split.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import logging
import os
import tempfile
import time
from dataclasses import dataclass, field
from typing import Optional

import pyarrow as pa

from arkflow_tpu.batch import META_INGEST_TIME, MessageBatch, batch_fingerprint
from arkflow_tpu.components.base import Input, NoopAck, Output, Resource
from arkflow_tpu.components.registry import build_component
from arkflow_tpu.config import StreamConfig
from arkflow_tpu.connect.flight import (
    DEFAULT_MAX_FRAME,
    _read_frame,
    _send_frame,
    batch_to_ipc,
    ipc_to_batches,
)
from arkflow_tpu.errors import ConfigError, EndOfInput, ProcessError
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.trace import TracingConfig, global_tracer
from arkflow_tpu.runtime.cluster import HashRing
from arkflow_tpu.runtime.overload import OverloadConfig, input_pauses_on_overload
from arkflow_tpu.runtime.pipeline import Pipeline
from arkflow_tpu.runtime.stream import MAX_PENDING, Stream, _Done, _WorkItem

logger = logging.getLogger("arkflow.hostshard")

#: ext-metadata key carrying the parent's delivery id across the hop
#: (column ``__meta_ext_shard_delivery``). Ext columns are excluded from
#: ``batch_fingerprint``, so stamping it perturbs neither routing nor the
#: shard-side coalescer/cache identity; the coalescer concatenates it
#: per-row, so a merged emission still names every covered delivery
#: (``MessageBatch.ext_values``).
SHARD_DELIVERY_KEY = "shard_delivery"

#: how long the parent waits for every shard's hello at startup
CONNECT_TIMEOUT_S = 30.0


@dataclass
class ShardSpec:
    """Everything one shard process needs to build its half of the stream
    (pickled through the spawn barrier — plain data only)."""

    shard_id: int
    socket_path: str
    name: str
    processors: list = field(default_factory=list)
    temporaries: list = field(default_factory=list)  # [(name, config), ...]
    buffer: Optional[dict] = None
    #: shard-local overload view (quotas stripped) — see shard_local()
    overload: Optional[OverloadConfig] = None
    thread_num: int = 1
    queue_size: int = 4
    max_frame: int = DEFAULT_MAX_FRAME
    tracing: Optional[TracingConfig] = None


# ---------------------------------------------------------------------------
# shard child process
# ---------------------------------------------------------------------------


class _ShardSocketInput(Input):
    """Child-side input: length-prefixed ``{"op": "batch"}`` header frames +
    one Arrow-IPC frame each, from the parent's dispatcher. ``drain`` (or
    parent EOF) ends the stream, which drains the shard's buffer and
    pipeline through the normal ``EndOfInput`` path."""

    def __init__(self, reader: asyncio.StreamReader, max_frame: int):
        self._reader = reader
        self._max_frame = max_frame
        self._done = False
        self.batches = 0
        self.rows = 0

    async def connect(self) -> None:
        return None

    async def read(self):
        if self._done:
            raise EndOfInput("shard input drained")
        tracer = global_tracer()
        while True:
            try:
                hdr = await _read_frame(self._reader, self._max_frame)
            except (asyncio.IncompleteReadError, ConnectionError, OSError) as e:
                self._done = True
                raise EndOfInput(f"parent endpoint closed: {e}")
            if hdr is None:
                continue
            msg = json.loads(hdr)
            op = msg.get("op")
            if op == "drain":
                self._done = True
                raise EndOfInput("drain requested")
            if op != "batch":
                continue
            data = await _read_frame(self._reader, self._max_frame)
            rbs = ipc_to_batches(data)
            batch = (MessageBatch(rbs[0]) if len(rbs) == 1
                     else MessageBatch.from_table(pa.Table.from_batches(rbs)))
            self.batches += 1
            self.rows += batch.num_rows
            ts = msg.get("ts")
            if ts is not None and tracer.enabled:
                ctx = batch.trace_context()
                if ctx is not None:
                    # wall-clock send->receive on ONE host: the queue-hop
                    # cost sharding added, visible in stage_breakdown
                    tracer.record(ctx, "shard_hop",
                                  max(0.0, time.time() - float(ts)))
            return batch, NoopAck()


class _NullOutput(Output):
    """The child stream never writes an output directly — dispositions go
    back over the socket from the ``_emit`` override. A write landing here
    means a code path was missed; fail loudly into the error protocol."""

    async def connect(self) -> None:
        return None

    async def write(self, batch: MessageBatch) -> None:
        raise ProcessError("shard-internal output should never be written")


class _ShardChildStream(Stream):
    """The shard's half of the stream: full hot loop (buffer/coalesce,
    fair queue, shard-local AIMD admission, pipeline), with every terminal
    disposition (results / shed / error) serialized back to the parent
    instead of written/acked locally. The parent owns the real acks, the
    delivery-attempt budget and the trace lifecycle; this class only
    exports its open spans alongside each disposition."""

    def __init__(self, writer: asyncio.StreamWriter, **kw):
        super().__init__(**kw)
        self._writer = writer
        #: one disposition is multiple frames; sheds can fire from the
        #: input/buffer tasks while a worker emits — serialize messages
        self._wlock = asyncio.Lock()
        self._emissions = 0

    def shard_stats(self) -> dict:
        return {"batches": getattr(self.input, "batches", 0),
                "rows": getattr(self.input, "rows", 0),
                "emissions": self._emissions}

    async def _send_msg(self, header: dict, frames=()) -> None:
        async with self._wlock:
            await _send_frame(self._writer,
                              json.dumps(header, separators=(",", ":")).encode())
            for f in frames:
                await _send_frame(self._writer, f)

    def _pop_spans(self, ctx) -> list:
        if ctx is None or not self.tracer.enabled:
            return []
        return self.tracer.export_open(ctx)

    def _trace_emission(self, batch: MessageBatch):
        # Same merge semantics as Stream._trace_emission, except the source
        # traces are NOT finished here: the parent owns every source trace
        # (it finishes them at ack time), so the shard grafts the sources'
        # open spans (shard_hop, input_decode) into the merged context so
        # they ride home with the emission instead of being stranded.
        wait_s = getattr(self.buffer, "last_emission_wait_s", None)
        if wait_s is None:
            ingest = batch.get_meta(META_INGEST_TIME)
            wait_s = (max(0.0, time.time() - float(ingest) / 1000.0)
                      if ingest is not None else 0.0)
        contexts = batch.source_trace_contexts()
        if len(contexts) <= 1:
            ctx = contexts[0] if contexts else self.tracer.begin()
            self.tracer.record(ctx, "buffer_wait", wait_s)
            return batch, ctx
        ctx = self.tracer.begin()
        for src in contexts:
            self.tracer.adopt_spans(ctx, self.tracer.export_open(src))
        self.tracer.record(ctx, "coalesce_wait", wait_s,
                           attrs={"links": [c.trace_id for c in contexts]})
        return batch.with_trace(ctx), ctx

    async def _emit(self, item: _WorkItem, results, err) -> None:
        deliveries = item.batch.ext_values(SHARD_DELIVERY_KEY)
        self._emissions += 1
        spans = self._pop_spans(item.trace)
        if err is not None:
            self.m_errors.inc()
            await self._send_msg({"op": "error", "deliveries": deliveries,
                                  "error": str(err)[:500], "spans": spans})
        else:
            ipcs = [batch_to_ipc(b.record_batch) for b in results]
            await self._send_msg({"op": "result", "deliveries": deliveries,
                                  "n": len(ipcs), "spans": spans}, ipcs)
        await self._safe_ack(item.ack)  # no-op socket acks; keeps counters sane

    async def _shed_item(self, item: _WorkItem, reason: str) -> None:
        deliveries = item.batch.ext_values(SHARD_DELIVERY_KEY)
        spans = self._pop_spans(item.trace)
        await self._send_msg({"op": "shed", "deliveries": deliveries,
                              "reason": reason, "spans": spans})
        await self._safe_ack(item.ack)


async def _shard_run(spec: ShardSpec) -> None:
    from arkflow_tpu.components import ensure_plugins_loaded

    ensure_plugins_loaded()
    tracer = global_tracer()
    if spec.tracing is not None:
        tracer.configure(spec.tracing, tier=f"shard{spec.shard_id}")
    reader, writer = await asyncio.open_unix_connection(spec.socket_path)
    await _send_frame(writer, json.dumps(
        {"op": "hello", "shard": spec.shard_id, "pid": os.getpid()}).encode())
    resource = Resource()
    for tname, tcfg in spec.temporaries:
        resource.temporaries[tname] = build_component("temporary", tcfg, resource)
    procs = [build_component("processor", p, resource) for p in spec.processors]
    buffer = build_component("buffer", spec.buffer, resource) if spec.buffer else None
    stream = _ShardChildStream(
        writer=writer,
        input_=_ShardSocketInput(reader, spec.max_frame),
        pipeline=Pipeline(procs),
        output=_NullOutput(),
        buffer=buffer,
        temporaries=resource.temporaries,
        thread_num=spec.thread_num,
        name=f"{spec.name}-shard{spec.shard_id}",
        queue_size=spec.queue_size,
        overload=spec.overload,
    )
    try:
        await stream.run(asyncio.Event())
    finally:
        try:
            await _send_frame(writer, json.dumps(
                {"op": "bye", "stats": stream.shard_stats()}).encode())
            writer.close()
        except Exception:
            pass  # parent gone; nothing left to report to


def _shard_main(spec: ShardSpec) -> None:
    """Spawn entry point for one ingest shard."""
    logging.basicConfig(level=logging.WARNING)
    try:
        asyncio.run(_shard_run(spec))
    except KeyboardInterrupt:
        pass


# ---------------------------------------------------------------------------
# parent: one endpoint, N shards
# ---------------------------------------------------------------------------


class _ShardConn:
    __slots__ = ("sid", "proc", "reader", "writer", "lock", "connected",
                 "alive", "clean", "stats")

    def __init__(self, sid: int, proc):
        self.sid = sid
        self.proc = proc
        self.reader: Optional[asyncio.StreamReader] = None
        self.writer: Optional[asyncio.StreamWriter] = None
        self.lock = asyncio.Lock()
        self.connected = asyncio.Event()
        self.alive = True
        self.clean = False  # saw a bye before EOF
        self.stats: dict = {}


class _Outstanding:
    __slots__ = ("d", "seq", "item", "shard", "key")

    def __init__(self, d: str, seq: int, item: _WorkItem, key: bytes):
        self.d = d
        self.seq = seq
        self.item = item
        self.shard: Optional[int] = None
        self.key = key


class _DispatchQueue:
    """Adapter with the one method ``Stream._do_input`` uses (``put``), so
    the parent reuses the battle-tested read/trace/admission loop verbatim
    while the 'queue' is really the flight hop router."""

    def __init__(self, stream: "ShardedIngestStream"):
        self._stream = stream

    async def put(self, item) -> None:
        await self._stream._dispatch(item)


_ORDER_EOF = object()
_RETIRED = object()


class ShardedIngestStream(Stream):
    """Parent endpoint of the sharded ingest plane. Inherits the input
    loop, shed/quarantine/ack plumbing and metrics from ``Stream``; replaces
    the in-process queue+workers with the shard router, per-shard readers
    and a global-seq reorder window."""

    def __init__(self, *, shards: int, spec: ShardSpec, **kw):
        super().__init__(**kw)
        self.num_shards = max(1, shards)
        self._spec = spec
        self._conns: dict[int, _ShardConn] = {}
        self._outstanding: dict[str, _Outstanding] = {}
        self._disp_q: asyncio.Queue = asyncio.Queue()
        self._ring = HashRing()
        self._input_done = 0
        self._tmpdir: Optional[str] = None
        self._server = None
        reg = global_registry()
        labels = {"stream": self.name}
        self.m_shard_dispatch = reg.counter(
            "arkflow_shard_dispatch_total",
            "batches dispatched over the ingest-shard hop", labels)
        self.m_redispatch = reg.counter(
            "arkflow_shard_redispatch_total",
            "in-flight deliveries re-sent to a surviving shard after a "
            "shard death", labels)
        self.m_shards_live = reg.gauge(
            "arkflow_ingest_shards_live", "ingest shard processes alive", labels)

    # -- admission: shared quota plane only --------------------------------

    async def _admit_or_shed(self, item: _WorkItem) -> bool:
        """Parent-side admission is the tenant QUOTA gate alone: quotas are
        a per-tenant contract and must be granted once globally, while the
        congestion controls (AIMD window, deadline, priority, fair share)
        run per shard against each shard's own backlog. NOTE: no
        ``on_enqueue`` here — the parent never dequeues, so window
        accounting would only ratchet upward."""
        ctrl = self.overload
        if ctrl is None:
            return True
        tokens = 0.0
        if ctrl.cfg.tenants is not None:
            item.tenant = ctrl.tenant_label(item.batch.tenant())
            if ctrl.meters_tokens():
                tokens = self._estimate_tokens(item.batch, ctrl.cfg.tenants)
        reason = ctrl.admit_quota(item.tenant, rows=float(item.batch.num_rows),
                                  tokens=tokens)
        if reason is None:
            return True
        await self._shed_item(item, reason)
        return False

    # -- routing -----------------------------------------------------------

    def _route_key(self, item: _WorkItem) -> bytes:
        """Tenant hash when the batch carries one (keeps one tenant's
        fairness lanes and coalescer state in one shard — whether or not
        tenant ACCOUNTING is on), else the batch fingerprint (keeps
        duplicates/cache keys in one shard)."""
        tenant = item.tenant or item.batch.tenant()
        if tenant is not None:
            return tenant.encode()
        return batch_fingerprint(item.batch)

    def _pick_shard(self, key: bytes) -> Optional[int]:
        for node in self._ring.candidates(key):
            conn = self._conns.get(int(node))
            if conn is not None and conn.alive:
                return conn.sid
        return None

    async def _dispatch(self, item) -> None:
        if isinstance(item, _Done):
            self._input_done += 1
            if self._input_done >= self.thread_num:
                await self._begin_drain()
            return
        # backpressure on in-flight deliveries, same bound and event as the
        # single-process reorder window
        while len(self._outstanding) > MAX_PENDING:
            self._drained.clear()
            try:
                await asyncio.wait_for(self._drained.wait(), 1.0)
            except asyncio.TimeoutError:
                pass
        seq = self._seq_assigned
        self._seq_assigned += 1
        d = str(seq)
        ent = _Outstanding(d, seq, item, self._route_key(item))
        self._outstanding[d] = ent
        self.m_pending.set(len(self._outstanding))
        await self._send_to_shard(ent)

    async def _send_to_shard(self, ent: _Outstanding) -> None:
        sid = self._pick_shard(ent.key)
        if sid is None:
            raise ProcessError("all ingest shards are down")
        conn = self._conns[sid]
        ent.shard = sid
        stamped = ent.item.batch.with_ext_metadata({SHARD_DELIVERY_KEY: ent.d})
        hdr = json.dumps({"op": "batch", "d": ent.d, "ts": time.time()},
                         separators=(",", ":")).encode()
        ipc = batch_to_ipc(stamped.record_batch)
        try:
            async with conn.lock:
                await _send_frame(conn.writer, hdr)
                await _send_frame(conn.writer, ipc)
            self.m_shard_dispatch.inc()
        except (ConnectionError, OSError) as e:
            # the shard died under the write; its reader task will reap the
            # connection and redispatch every delivery assigned to it
            # (including this one — ent.shard is already set)
            logger.warning("[%s] dispatch to shard %d failed (%s); awaiting "
                           "redispatch", self.name, sid, e)

    async def _begin_drain(self) -> None:
        # Input EOF does NOT mean the shards are done: a shard death after
        # this point redispatches its in-flight deliveries to the survivors,
        # and a drained survivor stops reading its socket — the redelivery
        # would be lost. Hold the drain op until every outstanding delivery
        # has a disposition (children emit results without needing drain;
        # the op only ends their input loop).
        while self._outstanding and any(c.alive for c in self._conns.values()):
            self._drained.clear()
            if self._outstanding and any(c.alive for c in self._conns.values()):
                try:
                    await asyncio.wait_for(self._drained.wait(), 0.25)
                except asyncio.TimeoutError:
                    pass
        for conn in self._conns.values():
            if not conn.alive:
                continue
            try:
                async with conn.lock:
                    await _send_frame(conn.writer, b'{"op":"drain"}')
            except (ConnectionError, OSError):
                pass

    # -- shard lifecycle ----------------------------------------------------

    async def _on_connect(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        try:
            hdr = await _read_frame(reader, self._spec.max_frame)
            sid = int(json.loads(hdr).get("shard", -1))
        except Exception:
            writer.close()
            return
        conn = self._conns.get(sid)
        if conn is None or conn.connected.is_set():
            writer.close()
            return
        conn.reader, conn.writer = reader, writer
        conn.connected.set()

    async def _read_shard(self, conn: _ShardConn) -> None:
        try:
            while True:
                hdr = await _read_frame(conn.reader, self._spec.max_frame)
                if hdr is None:
                    break
                msg = json.loads(hdr)
                op = msg.get("op")
                if op == "result":
                    batches: list[MessageBatch] = []
                    for _ in range(int(msg.get("n", 0))):
                        fr = await _read_frame(conn.reader, self._spec.max_frame)
                        batches.extend(MessageBatch(rb)
                                       for rb in ipc_to_batches(fr))
                    self._resolve(msg.get("deliveries") or [],
                                  ("result", batches, msg.get("spans") or []))
                elif op == "shed":
                    self._resolve(msg.get("deliveries") or [],
                                  ("shed", str(msg.get("reason") or "overloaded"),
                                   msg.get("spans") or []))
                elif op == "error":
                    self._resolve(msg.get("deliveries") or [],
                                  ("error", str(msg.get("error") or "shard error"),
                                   msg.get("spans") or []))
                elif op == "bye":
                    conn.clean = True
                    conn.stats = msg.get("stats") or {}
        except (asyncio.IncompleteReadError, ConnectionError, OSError) as e:
            if conn.clean:
                # expected: the child closes its socket right after the bye
                logger.debug("[%s] shard %d closed after bye", self.name,
                             conn.sid)
            else:
                logger.warning("[%s] shard %d connection lost: %s",
                               self.name, conn.sid, e)
        finally:
            await self._on_shard_down(conn)

    def _resolve(self, deliveries: list, disposition: tuple) -> None:
        entries = [self._outstanding.pop(d) for d in deliveries
                   if d in self._outstanding]
        if len(self._outstanding) <= MAX_PENDING:
            self._drained.set()
        self.m_pending.set(len(self._outstanding))
        if entries:
            self._disp_q.put_nowait((entries, disposition))

    async def _on_shard_down(self, conn: _ShardConn) -> None:
        if not conn.alive:
            return
        conn.alive = False
        self._ring.remove(str(conn.sid))
        self.m_shards_live.set(sum(1 for c in self._conns.values() if c.alive))
        pend = sorted((e for e in self._outstanding.values()
                       if e.shard == conn.sid), key=lambda e: e.seq)
        if not pend:
            return
        if not conn.clean:
            logger.error("[%s] shard %d died with %d in-flight deliveries; "
                         "redispatching to survivors", self.name, conn.sid,
                         len(pend))
        if any(c.alive for c in self._conns.values()):
            self.m_redispatch.inc(len(pend))
            for ent in pend:
                await self._send_to_shard(ent)
        else:
            # no survivors: fail the deliveries through the orderer so their
            # seqs release and the attempt/nack machinery disposes of them
            # (redelivery or quarantine — never silent loss)
            self._resolve([e.d for e in pend],
                          ("error", "all ingest shards died", []))

    # -- ordered emission ---------------------------------------------------

    async def _do_shard_output(self) -> None:
        """Reorder dispositions by global seq and execute them contiguously
        (the sharded analogue of ``Stream._do_output``). A multi-delivery
        disposition anchors at its lowest seq; the other covered seqs are
        marked retired and release as the window advances."""
        reorder: dict[int, object] = {}
        next_seq = 0
        while True:
            msg = await self._disp_q.get()
            if msg is _ORDER_EOF:
                for seq in sorted(reorder):
                    val = reorder.pop(seq)
                    if val is not _RETIRED:
                        await self._execute(*val)
                return
            entries, disp = msg
            entries.sort(key=lambda e: e.seq)
            reorder[entries[0].seq] = (entries, disp)
            for e in entries[1:]:
                reorder[e.seq] = _RETIRED
            while next_seq in reorder:
                val = reorder.pop(next_seq)
                next_seq += 1
                self._seq_emitted = next_seq
                if val is not _RETIRED:
                    await self._execute(*val)

    def _strip_delivery(self, batch: MessageBatch) -> MessageBatch:
        """Drop the internal delivery column before the batch reaches the
        user-facing output (column selection shares buffers — no copy)."""
        rb = batch.record_batch
        name = "__meta_ext_" + SHARD_DELIVERY_KEY
        if name not in rb.schema.names:
            return batch
        return MessageBatch(rb.select(
            [n for n in rb.schema.names if n != name]))

    async def _execute(self, entries: list, disp: tuple) -> None:
        kind = disp[0]
        anchor = entries[0]
        spans = disp[2] if len(disp) > 2 else []
        if spans and anchor.item.trace is not None:
            self.tracer.adopt_spans(anchor.item.trace, spans)
        if kind == "result":
            await self._execute_result(entries, disp[1])
        elif kind == "shed":
            for ent in entries:
                await self._shed_item(ent.item, disp[1])
        else:  # "error"
            err = ProcessError(disp[1])
            self.m_errors.inc()
            for ent in entries:
                await self._fail_entry(ent, err)

    async def _execute_result(self, entries: list,
                              batches: list[MessageBatch]) -> None:
        anchor = entries[0]
        loop = asyncio.get_running_loop()
        try:
            t0 = loop.time()
            for b in batches:
                t_w = loop.time()
                await self._write_guarded(self.output, self._out_breaker,
                                          self.output_retry,
                                          self._strip_delivery(b),
                                          f"[{self.name}] output write")
                self.m_write_latency.observe(loop.time() - t_w)
                self.m_batches_out.inc()
                self.m_rows_out.inc(b.num_rows)
            if batches and anchor.item.trace is not None:
                self.tracer.record(anchor.item.trace, "output_write",
                                   loop.time() - t0,
                                   attrs=({"batches": len(batches)}
                                          if len(batches) > 1 else None))
        except Exception as e:
            self.m_write_errors.inc()
            err = ProcessError(f"output write failed: {e}")
            for ent in entries:
                await self._fail_entry(ent, err)
            return
        now = time.time()
        for ent in entries:
            item = ent.item
            self._clear_attempts(item.batch)
            ingest = item.batch.get_meta(META_INGEST_TIME)
            e2e = None
            if ingest is not None:
                e2e = max(0.0, now - ingest / 1000.0)
                self.m_e2e_latency.observe(e2e)
                if self.overload is not None and item.tenant is not None:
                    self.overload.observe_tenant_latency(item.tenant, e2e)
            self.tracer.finish(item.trace, "ok", e2e_s=e2e)
            await self._safe_ack(item.ack)

    async def _fail_entry(self, ent: _Outstanding, err: Exception) -> None:
        """Per-delivery failure disposition — same budget/nack/quarantine
        ladder as ``Stream._emit``'s error path."""
        item = ent.item
        attempts = self._bump_attempts(item.batch, trace=item.trace)
        self.tracer.finish(item.trace, "error",
                           attrs={"error": str(err)[:200], "attempt": attempts})
        if attempts < self.max_delivery_attempts and getattr(
                item.ack, "redeliverable", False):
            await self._safe_nack(item.ack)
            return
        if self.error_output is not None:
            await self._quarantine(item, str(err), attempts)
        else:
            logger.error("[%s] shard processing error (no error_output): %s",
                         self.name, err)
            self._clear_attempts(item.batch)
            await self._safe_ack(item.ack)

    # -- lifecycle ----------------------------------------------------------

    def shard_pids(self) -> dict[int, int]:
        """Live shard pids (chaos tooling kills one mid-load)."""
        return {sid: c.proc.pid for sid, c in self._conns.items() if c.alive}

    def shard_stats(self) -> dict[int, dict]:
        """Per-shard bye stats (routing/affinity assertions in the soak)."""
        return {sid: dict(c.stats) for sid, c in self._conns.items()}

    async def run(self, cancel: asyncio.Event) -> None:
        import multiprocessing as mp

        await self.input.connect()
        await self.output.connect()
        if self.error_output is not None:
            await self.error_output.connect()
        self._pause_source = (self.overload is not None
                              and input_pauses_on_overload(self.input))
        self._tmpdir = tempfile.mkdtemp(prefix="arkflow-hostshard-")
        sock = os.path.join(self._tmpdir, "ingest.sock")
        self._server = await asyncio.start_unix_server(self._on_connect,
                                                       path=sock)
        ctx = mp.get_context("spawn")
        tracing = self.tracer.cfg if self.tracer.enabled else dataclasses.replace(
            self.tracer.cfg, enabled=False)
        for sid in range(self.num_shards):
            spec = dataclasses.replace(self._spec, shard_id=sid,
                                       socket_path=sock, tracing=tracing)
            proc = ctx.Process(target=_shard_main, args=(spec,), daemon=True)
            proc.start()
            self._conns[sid] = _ShardConn(sid, proc)
        readers: list[asyncio.Task] = []
        orderer: Optional[asyncio.Task] = None
        input_task: Optional[asyncio.Task] = None
        try:
            await asyncio.wait_for(
                asyncio.gather(*[c.connected.wait()
                                 for c in self._conns.values()]),
                CONNECT_TIMEOUT_S)
            for sid in self._conns:
                self._ring.add(str(sid))
            self.m_shards_live.set(self.num_shards)
            readers = [asyncio.create_task(self._read_shard(c),
                                           name=f"{self.name}-shard{c.sid}-rx")
                       for c in self._conns.values()]
            orderer = asyncio.create_task(self._do_shard_output(),
                                          name=f"{self.name}-order")
            input_task = asyncio.create_task(
                self._do_input(_DispatchQueue(self), cancel),
                name=f"{self.name}-input")
            await input_task
            await asyncio.gather(*readers)
            # belt-and-braces: anything still outstanding after every reader
            # exited can never get a disposition — fail it through the
            # orderer (nack/quarantine), never drop it silently
            if self._outstanding:
                stuck = sorted(self._outstanding.values(), key=lambda e: e.seq)
                self._outstanding.clear()
                self._disp_q.put_nowait(
                    (stuck, ("error", "shard plane shut down with in-flight "
                             "deliveries", [])))
            self._disp_q.put_nowait(_ORDER_EOF)
            await orderer
        except BaseException:
            for t in (input_task, orderer, *readers):
                if t is not None:
                    t.cancel()
            await asyncio.gather(*(t for t in (input_task, orderer, *readers)
                                   if t is not None), return_exceptions=True)
            raise
        finally:
            await self._teardown()

    async def _teardown(self) -> None:
        if self._server is not None:
            self._server.close()
            try:
                await self._server.wait_closed()
            except Exception:
                pass
            self._server = None
        for conn in self._conns.values():
            try:
                if conn.writer is not None:
                    conn.writer.close()
            except Exception:
                pass
            proc = conn.proc
            if proc.is_alive():
                proc.terminate()
        for conn in self._conns.values():
            conn.proc.join(timeout=5.0)
            if conn.proc.is_alive():
                conn.proc.kill()
                conn.proc.join(timeout=5.0)
        if self._tmpdir is not None:
            import shutil

            shutil.rmtree(self._tmpdir, ignore_errors=True)
            self._tmpdir = None
        await self._close_all()


def build_sharded_stream(cfg: StreamConfig, name: str) -> ShardedIngestStream:
    """Construct the parent endpoint + shard spec from a stream config
    (the ``build_stream`` seam for ``pipeline.ingest_shards > 0``)."""
    from arkflow_tpu.runtime.procpool import DEVICE_PROCESSORS

    device = sorted({p.get("type") for p in cfg.pipeline.processors}
                    & DEVICE_PROCESSORS)
    if (device and cfg.pipeline.ingest_shards > 1
            and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu"):
        raise ConfigError(
            f"pipeline.ingest_shards: {cfg.pipeline.ingest_shards} would "
            f"start that many processes each building {device}, and an "
            "accelerator belongs to one process at a time: run shards with "
            "device processors under JAX_PLATFORMS=cpu, use one shard, or "
            "split the device tier out with the cluster/remote_tpu plane")
    resource = Resource()
    input_ = build_component("input", cfg.input, resource)
    output = build_component("output", cfg.output, resource)
    error_output = (build_component("output", cfg.error_output, resource)
                    if cfg.error_output else None)
    overload_cfg: Optional[OverloadConfig] = cfg.pipeline.overload
    spec = ShardSpec(
        shard_id=-1,
        socket_path="",
        name=name,
        processors=[dict(p) for p in cfg.pipeline.processors],
        temporaries=[(t.name, dict(t.config)) for t in cfg.temporary],
        buffer=dict(cfg.buffer) if cfg.buffer else None,
        overload=(overload_cfg.shard_local()
                  if overload_cfg is not None and overload_cfg.enabled
                  else None),
        thread_num=cfg.pipeline.effective_threads(),
        queue_size=cfg.pipeline.effective_queue_size(),
    )
    return ShardedIngestStream(
        shards=cfg.pipeline.ingest_shards,
        spec=spec,
        input_=input_,
        pipeline=Pipeline([]),  # the chain lives in the shards
        output=output,
        error_output=error_output,
        buffer=None,  # the coalescer lives in the shards
        temporaries={},
        thread_num=cfg.pipeline.effective_threads(),
        name=name,
        output_retry=cfg.output_retry,
        output_breaker=cfg.output_circuit_breaker,
        error_output_retry=cfg.error_output_retry,
        error_output_breaker=cfg.error_output_circuit_breaker,
        max_delivery_attempts=cfg.pipeline.max_delivery_attempts,
        reconnect_retry=cfg.input_reconnect,
        queue_size=cfg.pipeline.effective_queue_size(),
        overload=overload_cfg,
    )
