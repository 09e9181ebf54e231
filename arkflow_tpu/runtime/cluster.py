"""Disaggregated serving cluster: ingest tier ⇄ device tier over the flight plane.

Everything the engine scaled so far (packed bf16, fair queues, tp continuous
batching, hot-swap) lives inside one process. This module is the
millions-of-users step: it splits serving into an **ingest tier** — the
ordinary stream runtime doing parse/SQL/coalesce/admission/response-cache —
and a **device tier** of worker processes each hosting a
``ServingRunnerCore``-backed processor chain (``tpu_inference`` runner pools
or ``tpu_generate`` generation servers). Batches travel between the tiers as
Arrow IPC over the framed wire protocol ``connect/flight.py`` already speaks
(the reference's Ballista analog), so prefill→decode page streaming later is
an extension of this plane, not a rewrite.

Wire protocol (extends the flight framing; ``arkflow://host:port``):

- ``register``  — handshake: the ingest side learns ``worker_id``, protocol
  version and the hosted processor types.
- ``heartbeat`` — liveness + load report: the worker's advertised AIMD
  admission window and drain estimate (the PR-5 overload signals, computed
  by a per-worker ``OverloadController``), in-flight depth, device health
  reports and response-cache stats. The ingest side re-exports them as
  per-worker autoscaling gauges.
- ``drain``     — ``{"drain": true|false}``: a draining worker refuses new
  ``infer`` requests (they re-route to the hash ring's next worker) while
  in-flight steps finish — the building block of rolling fleet swaps and
  graceful scale-in.
- ``swap``      — ``{"checkpoint": path}``: run the worker's own PR-10
  ``ModelSwapManager`` (canary + per-unit probe + rollback) on its hosted
  processors.
- ``infer``     — the request JSON frame is followed by ONE raw frame of
  Arrow IPC (the batch, metadata columns included); the worker replies a
  status frame, then tagged data frames (processed batches), then the
  zero-length end frame. A processing error after streaming began uses the
  0x01 error tag, exactly like remote scans.
- ``kv_push``   — prefill/decode disaggregation: a prefill-role worker
  streams one finished prompt's KV pages to a decode-role worker. The
  request frame carries the page-table metadata (prompt ids, first token,
  page geometry, shard count); ``2 * shards`` raw frames follow — the K
  then V page slabs, one frame per tp shard (split along kv_heads, the
  axis the receiving pool shards on). The receiver adopts the pages into
  its own pool and decodes to completion, answering ONE status frame with
  the full token list. A draining or role-mismatched receiver refuses
  retryably (after consuming the slab frames), so the prefill side
  re-plans to the next decode candidate.

Roles (``worker: {role: prefill|decode|both}``, default ``both``): prompts
route to prefill-capable workers by prefix hash (prefix-cache affinity
survives the split verbatim); the prefill worker picks its decode
destination from the occupancy-ordered candidate list the dispatcher
attaches to the request (slot/page pressure advertised in heartbeats). A
decode-role worker refuses ``infer`` retryably, a prefill-role worker
refuses ``kv_push`` retryably — misrouted work re-routes instead of
wedging.

Routing (``remote_tpu`` dispatch stage): consistent hashing on
``batch_fingerprint`` (or the prompt prefix) over a virtual-node ring, so a
redelivered or byte-identical duplicate batch lands on the SAME worker and
its response/prefix caches keep hitting after scale-out. The hash owner is
skipped only when it is dead, draining, or has no advertised window headroom
— then the dispatch spills to the next live worker on the ring (affinity
trades for throughput only under saturation). A worker death mid-dispatch
retries on the ring's successors; if every worker fails the error surfaces
to the stream, whose existing nack path redelivers — at-least-once is
preserved end to end.

Run a device worker with::

    python -m arkflow_tpu --cluster-worker --config worker.yaml --port 50052

and point an ingest stream's pipeline at the fleet::

    processors:
      - type: remote_tpu
        workers: ["arkflow://host-a:50052", "arkflow://host-b:50052"]
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import logging
import os
import socket
import uuid
from collections import Counter, deque
from typing import Any, Mapping, Optional, Sequence

from arkflow_tpu.batch import MessageBatch, batch_fingerprint
from arkflow_tpu.components.base import Resource
from arkflow_tpu.components.registry import build_component, ensure_plugins_loaded
from arkflow_tpu.connect.flight import (
    DEFAULT_MAX_FRAME,
    ERROR_TAG,
    TRACE_TAG,
    _end_stream,
    _read_frame,
    _send_data,
    _send_frame,
    _send_stream_error,
    batch_to_ipc,
    ipc_to_batches,
    parse_remote_url,
)
from arkflow_tpu.errors import (
    ConfigError,
    ConnectError,
    FrameIntegrityError,
    Overloaded,
    ProcessError,
    ReadError,
    SwapError,
)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.trace import (
    TraceContext,
    Tracer,
    TracingConfig,
    activate,
    global_tracer,
    stage_span,
)

logger = logging.getLogger("arkflow.cluster")

#: wire-protocol version carried in register responses; the ingest side
#: refuses a worker speaking a newer major protocol than it understands
PROTO_VERSION = 1

ROUTE_KEYS = ("fingerprint", "prefix")

#: prefill/decode disaggregation roles a worker can declare
WORKER_ROLES = ("prefill", "decode", "both")


# ---------------------------------------------------------------------------
# KV-page export wire codec (numpy only — the ingest tier must never
# import jax, and the slabs cross processes as raw frames)
# ---------------------------------------------------------------------------


def _wire_dtype(name: str):
    """Resolve a dtype name from the wire; bf16 lives in ml_dtypes (which
    ships with jax but imports without it)."""
    import numpy as np

    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def kv_export_to_wire(export: Mapping) -> tuple[dict, list[bytes]]:
    """Split a ``GenerationServer.prefill_export`` payload into the JSON
    metadata dict and the ordered raw slab frames (K shards then V shards,
    one frame per tp shard — the receiver reassembles along kv_heads)."""
    import numpy as np

    meta = {k: export[k] for k in
            ("prompt", "max_new_tokens", "first_token") if k in export}
    meta["tokens"] = [int(t) for t in export.get("tokens") or []]
    if export.get("done"):
        meta["done"] = True
        return meta, []
    meta["page_size"] = int(export["page_size"])
    meta["shards"] = int(export["shards"])
    meta["dtype"] = str(export["dtype"])
    meta["shape"] = [int(d) for d in export["k"][0].shape]
    frames = [np.ascontiguousarray(a).tobytes()
              for a in list(export["k"]) + list(export["v"])]
    return meta, frames


def kv_export_from_wire(meta: Mapping, frames: Sequence[bytes]) -> dict:
    """Inverse of :func:`kv_export_to_wire`: rebuild the export dict the
    decode side's ``generate_from_pages`` adopts. Bitwise: the slabs are
    reinterpreted at their original dtype/shape, never converted."""
    import numpy as np

    out = dict(meta)
    if out.get("done"):
        return out
    shards = int(meta["shards"])
    if len(frames) != 2 * shards:
        raise ConnectError(
            f"kv_push carried {len(frames)} slab frames, expected "
            f"{2 * shards} (K+V x {shards} shards)")
    shape = tuple(int(d) for d in meta["shape"])
    dt = _wire_dtype(str(meta["dtype"]))
    expect = int(np.prod(shape)) * dt.itemsize
    for i, fr in enumerate(frames):
        # the slabs are raw device memory with no Arrow IPC validation —
        # a truncated or padded frame must fail HERE with an attributable
        # error, not reshape into garbage pages downstream
        if len(fr) != expect:
            kind = "K" if i < shards else "V"
            raise ConnectError(
                f"kv_push slab {i + 1}/{2 * shards} ({kind} shard "
                f"{i % shards}) is {len(fr)} bytes, expected {expect} "
                f"({shape} x {dt.name}); refusing to adopt corrupt pages")
    out["k"] = [np.frombuffer(frames[i], dtype=dt).reshape(shape)
                for i in range(shards)]
    out["v"] = [np.frombuffer(frames[shards + i], dtype=dt).reshape(shape)
                for i in range(shards)]
    return out


# ---------------------------------------------------------------------------
# consistent hashing
# ---------------------------------------------------------------------------


def _ring_hash(data: bytes) -> int:
    """Stable 64-bit ring position (blake2b — NOT Python's randomized hash;
    affinity must survive process restarts on both tiers)."""
    return int.from_bytes(hashlib.blake2b(data, digest_size=8).digest(), "big")


class HashRing:
    """Consistent hash ring with virtual nodes.

    ``candidates(key)`` returns every distinct node in ring order starting
    at the key's position — index 0 is the affinity owner, the rest are the
    failover/spill order. Adding or removing one node only remaps the keys
    that hashed to it (the property that keeps response/prefix caches warm
    through scale-out)."""

    def __init__(self, nodes: Sequence[str] = (), virtual_nodes: int = 64):
        if virtual_nodes < 1:
            raise ConfigError(
                f"virtual_nodes must be >= 1, got {virtual_nodes}")
        self.virtual_nodes = virtual_nodes
        self._points: list[tuple[int, str]] = []  # sorted (position, node)
        for n in nodes:
            self.add(n)

    def __len__(self) -> int:
        return len({n for _, n in self._points})

    def add(self, node: str) -> None:
        import bisect

        for i in range(self.virtual_nodes):
            pt = (_ring_hash(f"{node}#{i}".encode()), node)
            idx = bisect.bisect_left(self._points, pt)
            if idx < len(self._points) and self._points[idx] == pt:
                continue  # idempotent
            self._points.insert(idx, pt)

    def remove(self, node: str) -> None:
        self._points = [p for p in self._points if p[1] != node]

    def candidates(self, key: bytes) -> list[str]:
        """All distinct nodes in ring order from the key's hash point."""
        if not self._points:
            return []
        import bisect

        # U+FFFF sorts after any node name: start strictly past every
        # point at this exact hash position
        start = bisect.bisect_right(self._points, (_ring_hash(key), "\uffff"))
        out: list[str] = []
        seen: set[str] = set()
        n = len(self._points)
        for i in range(n):
            node = self._points[(start + i) % n][1]
            if node not in seen:
                seen.add(node)
                out.append(node)
        return out


# ---------------------------------------------------------------------------
# shared introspection helpers (mirror engine.py's _inner-chain walks)
# ---------------------------------------------------------------------------


def _walk_inner(proc: Any, attr: str) -> Optional[Any]:
    """First ``attr`` found on a processor or its ``_inner`` wrapper chain."""
    node, seen = proc, set()
    while node is not None and id(node) not in seen:
        seen.add(id(node))
        val = getattr(node, attr, None)
        if val is not None:
            return val
        node = getattr(node, "_inner", None)
    return None


def _runner_reports(processors: Sequence[Any]) -> list[dict]:
    reports: list[dict] = []
    for proc in processors:
        runner = _walk_inner(proc, "runner")
        report = getattr(runner, "health_report", None)
        if report is None:
            continue
        try:
            rep = report()
        except Exception:  # a sick runner must not break heartbeats
            logger.exception("worker health_report failed")
            continue
        reports.extend(rep if isinstance(rep, list) else [rep])
    return reports


def _cache_reports(processors: Sequence[Any]) -> list[dict]:
    out = []
    for proc in processors:
        cache = _walk_inner(proc, "cache")
        report = getattr(cache, "report", None)
        if report is not None:
            try:
                out.append(report())
            except Exception:
                logger.exception("worker cache report failed")
    return out


def _swappers(processors: Sequence[Any]) -> list:
    out = []
    for proc in processors:
        sw = _walk_inner(proc, "swapper")
        if sw is not None and hasattr(sw, "swap"):
            out.append(sw)
    return out


def _combine_epochs(epochs: Sequence[str]) -> str:
    """One heartbeat-sized digest over every monitor's epoch (most workers
    host one monitor, where this is the identity-ish passthrough)."""
    if len(epochs) == 1:
        return epochs[0]
    h = hashlib.blake2b(digest_size=16)
    for e in epochs:
        h.update(e.encode())
    return h.hexdigest()


def _integrity_monitors(processors: Sequence[Any]) -> list:
    """SDC monitors (tpu/integrity.py) hosted by this worker's processors
    — the heartbeat's ``param_digest`` epoch + corrupt-member summary, and
    the targets of the dispatcher's ``integrity_probe`` tiebreak."""
    out = []
    for proc in processors:
        mon = _walk_inner(proc, "integrity")
        if mon is not None and hasattr(mon, "probe_now"):
            out.append(mon)
    return out


def _shape_reports(processors: Sequence[Any]) -> list:
    """Per-processor serving shape grids, positional (None = no model
    stage). Rides the heartbeat so the ingest fleet controller can replay
    the incumbent grid into a freshly spawned worker's warmup — the tuner's
    committed shapes win over the static config the template carries."""
    out: list = []
    for proc in processors:
        shape = None
        tuner = _walk_inner(proc, "tuner")
        incumbent = getattr(tuner, "_incumbent", None)
        if incumbent is not None and hasattr(incumbent, "report"):
            try:
                shape = incumbent.report()
            except Exception:
                logger.exception("worker shape report failed")
        if shape is None:
            runner = _walk_inner(proc, "runner")
            buckets = getattr(runner, "buckets", None)
            if buckets is not None and hasattr(buckets, "batch_buckets"):
                shape = {"batch_buckets": list(buckets.batch_buckets),
                         "seq_buckets": list(buckets.seq_buckets),
                         "example_scale": int(
                             getattr(buckets, "example_scale", 1))}
        out.append(shape)
    return out if any(s is not None for s in out) else []


# ---------------------------------------------------------------------------
# device tier: the cluster worker server
# ---------------------------------------------------------------------------


class ClusterWorkerServer:
    """A device-tier worker: hosts a processor chain behind the flight-framed
    ``infer`` action, with register/heartbeat/drain/swap lifecycle frames.

    Load discipline: ``max_in_flight`` device lanes guarded by a semaphore
    (device steps must not interleave unboundedly); a per-worker
    ``OverloadController`` observes the semaphore wait and step latency so
    the heartbeat can advertise a genuine AIMD window + drain estimate — the
    ingest tier's routing weights and autoscaling gauges."""

    def __init__(self, processors: Sequence[Any], *, host: str = "127.0.0.1",
                 port: int = 50052, worker_id: Optional[str] = None,
                 max_in_flight: int = 1, max_frame: int = DEFAULT_MAX_FRAME,
                 tracing: Optional[TracingConfig] = None,
                 grace_s: float = 30.0, role: str = "both",
                 io_deadline_s: float = 30.0, crc: bool = True):
        from arkflow_tpu.runtime.overload import OverloadConfig, OverloadController
        from arkflow_tpu.runtime.pipeline import Pipeline

        if max_in_flight < 1:
            raise ConfigError(
                f"worker.max_in_flight must be >= 1, got {max_in_flight}")
        if role not in WORKER_ROLES:
            raise ConfigError(
                f"worker.role must be one of {WORKER_ROLES}, got {role!r}")
        if io_deadline_s <= 0:
            raise ConfigError(
                f"worker.io_deadline must be > 0, got {io_deadline_s}")
        self.role = role
        self.pipeline = Pipeline(list(processors))
        self.host = host
        self.port = port
        self.worker_id = worker_id or f"{socket.gethostname()}-{os.getpid()}"
        #: incarnation epoch: minted fresh per server object (and re-minted
        #: when the ingest tier fences this one), so a partition-healed
        #: zombie is distinguishable from the worker it used to be. The
        #: worker_id names the IDENTITY; the incarnation names the EPOCH.
        self.incarnation = uuid.uuid4().hex[:12]
        #: advertise crc32 frame integrity at register; peers that saw the
        #: capability send crc-trailed frames and this worker echoes
        self.crc = bool(crc)
        #: per-frame read deadline: a peer stalling mid-frame (slow-loris)
        #: must not pin a connection task forever
        self.io_deadline_s = float(io_deadline_s)
        #: the worker's OWN tracer (never the process-global one): spans for
        #: an infer request accumulate here and export back to the ingest
        #: tier in a TRACE_TAG frame — per-instance so in-process test
        #: fleets keep their tiers separated exactly like real processes.
        #: No explicit config = the env-aware default (ARKFLOW_TRACE=0
        #: must silence device-tier workers too).
        from arkflow_tpu.obs.trace import _default_config

        self.tracer = Tracer(tier=f"worker:{self.worker_id}",
                             config=tracing or _default_config())
        self.max_in_flight = max_in_flight
        self.max_frame = int(max_frame)
        self.draining = False
        #: SIGTERM/SIGINT grace budget: how long a self-draining worker
        #: waits for in-flight batches before exiting anyway (spot
        #: preemption notices are time-boxed; blowing the budget means the
        #: still-running batches nack through redelivery, not vanish)
        self.grace_s = float(grace_s)
        self._stopping = asyncio.Event()
        self._drain_task: Optional[asyncio.Task] = None
        self._server: Optional[asyncio.base_events.Server] = None
        self._sem: Optional[asyncio.Semaphore] = None  # bound at start()
        self._inflight = 0  # accepted infer requests not yet answered
        self._served = 0  # completed OK since process start
        self._errors = 0
        # prefill/decode disaggregation counters (heartbeat-visible)
        self._kv_pushed = 0        # exports this worker shipped downstream
        self._kv_push_retries = 0  # decode candidates that refused/failed over
        self._kv_adopted = 0       # exports adopted + decoded locally
        self._kv_refused = 0       # kv_push receives refused (drain/role)
        # network-robustness counters (heartbeat-visible)
        self._stalled_reads = 0    # reads killed by the io_deadline
        self._crc_errors = 0       # frames that failed the crc32 check
        self._fence_refused = 0    # requests refused: this epoch was fenced
        self.m_stalled = global_registry().counter(
            "arkflow_cluster_stalled_reads_total",
            "worker-side frame reads that stalled past io_deadline "
            "(slow-loris / wedged peer)", {"worker": self.worker_id})
        # the PR-5 admission signals, re-used verbatim: window adapts by
        # AIMD on the semaphore wait, drain estimate = queued * step EWMA
        self.ctrl = OverloadController(
            OverloadConfig.from_config({"enabled": True,
                                        "max_window": max_in_flight * 4}),
            name=f"worker-{self.worker_id}", workers=max_in_flight)

    # -- lifecycle ---------------------------------------------------------

    async def connect(self) -> None:
        """Pre-flight the hosted chain (model warmup compiles) BEFORE the
        port opens: a worker that answers ``register`` is ready to serve."""
        await self.pipeline.connect()

    async def start(self) -> None:
        self._sem = asyncio.Semaphore(self.max_in_flight)
        self._server = await asyncio.start_server(self._serve, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        logger.info("cluster worker %s listening on %s:%d",
                    self.worker_id, self.host, self.port)

    async def serve_forever(self) -> None:
        """Serve until cancelled OR gracefully stopped (a SIGTERM-initiated
        self-drain completes by setting the stop event — see
        :meth:`begin_self_drain`)."""
        if self._server is None:
            await self.start()  # accepting from here on
        try:
            await self._stopping.wait()
        finally:
            # stop accepting and return. Not ``Server.serve_forever`` /
            # ``async with server``: leaving either awaits ``wait_closed()``,
            # which waits for every open connection — a handler holding a
            # batch past the grace budget would pin the exit the budget
            # exists to bound (``stop`` bounds that wait itself)
            self._server.close()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            try:
                await asyncio.wait_for(self._server.wait_closed(), 1.0)
            except asyncio.TimeoutError:
                pass
        await self.pipeline.close()

    # -- preemption-safe self-drain (the SIGTERM primitive) ----------------

    def begin_self_drain(self, reason: str = "signal") -> None:
        """Flip to draining and schedule the graceful exit: new ``infer``
        requests are refused (retryable → the ingest ring re-routes them),
        in-flight batches get ``grace_s`` to finish, then the serve loop
        stops. Idempotent — a double SIGTERM doesn't shorten the budget.

        Usable standalone (any embedder can call it); ``run_worker`` wires
        it to SIGTERM/SIGINT so a spot preemption or a fleet-controller
        retire is routine, not a mid-batch kill."""
        if self.draining and self._drain_task is not None:
            return
        self.draining = True
        logger.info("cluster worker %s self-draining (%s): %d in-flight, "
                    "grace %.1fs", self.worker_id, reason, self._inflight,
                    self.grace_s)
        self._drain_task = asyncio.get_running_loop().create_task(
            self._drain_then_stop())

    async def _drain_then_stop(self) -> None:
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self.grace_s
        while self._inflight > 0 and loop.time() < deadline:
            await asyncio.sleep(0.05)
        if self._inflight > 0:
            logger.warning(
                "cluster worker %s: %d batches still in flight after %.1fs "
                "grace; exiting anyway (they nack through redelivery)",
                self.worker_id, self._inflight, self.grace_s)
        else:
            logger.info("cluster worker %s drained clean; exiting",
                        self.worker_id)
        self._stopping.set()

    def install_signal_handlers(self) -> None:
        """SIGTERM/SIGINT = preemption notice, not a crash: self-drain
        under the grace budget instead of dying mid-batch."""
        import signal

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(
                    sig, self.begin_self_drain, sig.name)
            except (NotImplementedError, RuntimeError, ValueError):
                # non-main thread or platform without loop signal support:
                # the embedder owns signals then
                return

    # -- introspection -----------------------------------------------------

    def load_report(self) -> dict:
        """The heartbeat payload: identity + the advertised routing/
        autoscaling signals + nested device health and cache stats.

        Generation occupancy (``gen_slots_busy`` / ``page_pool_occupancy``)
        is lifted out of the nested health reports into first-class fields:
        decode placement and the fleet controller read REAL decode pressure
        from here, not just the AIMD window."""
        health = _runner_reports(self.pipeline.processors)
        rep = {
            "worker_id": self.worker_id,
            "proto": PROTO_VERSION,
            "role": self.role,
            "incarnation": self.incarnation,
            "crc": self.crc,
            "draining": self.draining,
            "stalled_reads": self._stalled_reads,
            "crc_errors": self._crc_errors,
            "fence_refused": self._fence_refused,
            "inflight": self._inflight,
            "served": self._served,
            "errors": self._errors,
            "window": int(self.ctrl.window),
            "drain_s": round(self.ctrl.estimated_drain_s(), 3),
            "step_ewma_ms": round(self.ctrl.step_s() * 1000.0, 3),
            "kv_pushed": self._kv_pushed,
            "kv_push_retries": self._kv_push_retries,
            "kv_adopted": self._kv_adopted,
            "kv_refused": self._kv_refused,
            "health": health,
            "caches": _cache_reports(self.pipeline.processors),
            "shapes": _shape_reports(self.pipeline.processors),
        }
        monitors = _integrity_monitors(self.pipeline.processors)
        if monitors:
            # SDC defense signals: the combined param-digest epoch (None
            # until every member is baselined) lets the dispatcher spot a
            # digest-outlier against same-model peers; a nonzero corrupt
            # count fences this worker outright
            epochs = [m.digest_epoch() for m in monitors]
            rep["param_digest"] = (_combine_epochs(epochs)
                                   if all(epochs) else None)
            rep["integrity_corrupt"] = sum(m.corrupt_members()
                                           for m in monitors)
        gen = [h for h in health if h.get("serving") == "continuous"]
        if gen:
            rep["gen_slots"] = sum(int(h.get("slots", 0)) for h in gen)
            rep["gen_slots_busy"] = sum(int(h.get("slots_busy", 0))
                                        for h in gen)
            rep["page_pool_occupancy"] = round(
                max(float(h.get("page_pool_occupancy", 0.0)) for h in gen), 4)
            ttfts = [h["ttft"] for h in gen if isinstance(h.get("ttft"), dict)]
            if ttfts:
                rep["ttft_p99_ms"] = max(float(t.get("p99_ms", 0.0))
                                         for t in ttfts)
        return rep

    # -- request handling --------------------------------------------------

    async def _read_bounded(self, reader, what: str):
        """One frame under the per-frame io_deadline: a peer stalling
        mid-frame (slow-loris) is cut loose and counted instead of pinning
        this connection task forever."""
        try:
            return await asyncio.wait_for(
                _read_frame(reader, self.max_frame, what=what),
                self.io_deadline_s)
        except asyncio.TimeoutError:
            self._stalled_reads += 1
            self.m_stalled.inc()
            raise ConnectError(
                f"read of {what} frame stalled past the "
                f"{self.io_deadline_s:.1f}s io_deadline (slow-loris or "
                "wedged peer); dropping the connection") from None

    def _fence_check(self, req: dict) -> bool:
        """True when the peer declared THIS incarnation fenced (it was
        staleness-declared dead, e.g. across a healed partition). The
        request is refused retryably and the worker re-mints its epoch, so
        the next heartbeat re-admits it as a provably fresh member instead
        of a zombie serving stale occupancy."""
        fenced = req.get("fenced") or []
        if self.incarnation not in fenced:
            return False
        self._fence_refused += 1
        old, self.incarnation = self.incarnation, uuid.uuid4().hex[:12]
        logger.warning(
            "cluster worker %s: incarnation %s was fenced by the ingest "
            "tier (stale after a partition?); re-minted as %s",
            self.worker_id, old, self.incarnation)
        return True

    async def _serve(self, reader, writer) -> None:
        crc = False
        try:
            raw = await self._read_bounded(reader, "request")
            if raw is None:
                return
            # echo negotiation: reply with crc trailers iff the request
            # frame carried one (the peer learned the capability from our
            # register report) and integrity is enabled locally
            crc = bool(getattr(reader, "_arkflow_crc", False)) and self.crc
            req = json.loads(raw.decode())
            action = req.get("action")
            if action == "register":
                fence = req.get("fence")
                if fence and fence == self.incarnation:
                    # explicit heal handshake: the ingest tier fenced this
                    # epoch and asks for a fresh one before re-admission
                    self._fence_refused += 1
                    self.incarnation = uuid.uuid4().hex[:12]
                    logger.info(
                        "cluster worker %s: fenced incarnation %s healed; "
                        "now %s", self.worker_id, fence, self.incarnation)
                await _send_frame(writer, json.dumps({
                    "ok": True,
                    "processors": [type(p).__name__
                                   for p in self.pipeline.processors],
                    **self.load_report(),
                }).encode(), crc=crc)
            elif action == "heartbeat":
                await _send_frame(writer, json.dumps(
                    {"ok": True, **self.load_report()}).encode(), crc=crc)
            elif action == "drain":
                self.draining = bool(req.get("drain", True))
                logger.info("cluster worker %s drain=%s (inflight=%d)",
                            self.worker_id, self.draining, self._inflight)
                await _send_frame(writer, json.dumps(
                    {"ok": True, **self.load_report()}).encode(), crc=crc)
            elif action == "integrity_probe":
                await self._do_integrity_probe(writer, crc=crc)
            elif action == "swap":
                await self._do_swap(req, writer)
            elif action == "infer":
                await self._do_infer(req, reader, writer, crc=crc)
            elif action == "kv_push":
                await self._do_kv_push(req, reader, writer, crc=crc)
            else:
                await _send_frame(writer, json.dumps(
                    {"ok": False, "error": f"unknown action {action!r}"}
                ).encode(), crc=crc)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        except Exception as e:
            if isinstance(e, FrameIntegrityError):
                self._crc_errors += 1
            # the reader records the crc negotiation BEFORE validating, so
            # even a refusal of a corrupted request carries a trailer — the
            # reply crosses the same corrupting link the request did, and
            # unprotected it would reach the peer as undecodable garbage
            crc = bool(getattr(reader, "_arkflow_crc", False)) and self.crc
            try:
                if getattr(writer, "_arkflow_streaming", False):
                    await _send_stream_error(writer, repr(e)[:500], crc=crc)
                    await _end_stream(writer)
                else:
                    status = {"ok": False, "error": repr(e)[:500]}
                    if isinstance(e, FrameIntegrityError):
                        # a corrupted REQUEST was never processed — refuse
                        # retryably so the ingest ring fails the batch over
                        # instead of quarantining it as a processing error;
                        # the reason lets the client count it as a frame
                        # error rather than a drain
                        status["retryable"] = True
                        status["reason"] = "frame_integrity"
                    await _send_frame(writer, json.dumps(status).encode(),
                                      crc=crc)
            except Exception:
                pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def _do_integrity_probe(self, writer, crc: bool = False) -> None:
        """On-demand full integrity pass — the dispatcher's shadow-verify
        tiebreak: when two workers disagree on one batch, each runs its
        golden probes NOW and the corrupt one self-identifies (and its
        local monitor quarantines + repairs it on the spot)."""
        monitors = _integrity_monitors(self.pipeline.processors)
        summaries: list[dict] = []
        ok = True
        for mon in monitors:
            try:
                summaries.append(await mon.probe_now())
            except Exception as e:
                ok = False
                summaries.append({"error": repr(e)[:200]})
        mismatches = sum(int(s.get("mismatches", 0)) for s in summaries)
        await _send_frame(writer, json.dumps({
            "ok": ok, "worker_id": self.worker_id,
            "probed": len(monitors),
            "mismatches": mismatches,
            "corrupt": sum(m.corrupt_members() for m in monitors),
            "summaries": summaries,
        }).encode(), crc=crc)

    async def _do_swap(self, req: dict, writer) -> None:
        """Apply a rolling hot-swap to the hosted processors via their own
        PR-10 managers (canary + probe + rollback happen worker-side)."""
        ckpt = req.get("checkpoint")
        if not ckpt or not isinstance(ckpt, str):
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "swap needs a 'checkpoint' path"}).encode())
            return
        swappers = _swappers(self.pipeline.processors)
        if not swappers:
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "no hot-swappable processors on this "
                                       "worker"}).encode())
            return
        results, ok_all = [], True
        for sw in swappers:
            try:
                results.append({"ok": True, **(await sw.swap(ckpt))})
            except SwapError as e:
                ok_all = False
                results.append({"ok": False, "error": str(e)})
            except Exception as e:  # an unexpected bug must still answer
                ok_all = False
                results.append({"ok": False,
                                "error": f"{type(e).__name__}: {e}"})
        await _send_frame(writer, json.dumps(
            {"ok": ok_all, "worker_id": self.worker_id,
             "results": results}).encode())

    async def _do_infer(self, req: dict, reader, writer,
                        crc: bool = False) -> None:
        ipc = await self._read_bounded(reader, "infer batch")
        if ipc is None:
            raise ConnectError("infer request carried no batch frame")
        if self._fence_check(req):
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "worker incarnation was fenced "
                 "(stale epoch); re-minted — retry on the ring",
                 "retryable": True}).encode(), crc=crc)
            return
        if self.draining:
            # retryable: the dispatcher re-routes to the ring's next worker
            # instead of surfacing a processing error
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "worker is draining",
                 "retryable": True, "incarnation": self.incarnation}
            ).encode(), crc=crc)
            return
        if self.role == "decode":
            # a decode-role worker only adopts kv_push pages; prompts
            # re-route to a prefill-capable worker on the ring
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "worker role is 'decode': accepts "
                 "kv_push only", "retryable": True,
                 "incarnation": self.incarnation}).encode(), crc=crc)
            return
        # cross-tier trace context: the ingest dispatcher parents the
        # worker's spans under its hop span; absent = untraced (old peer)
        tctx = (TraceContext.from_json(req.get("trace"))
                if self.tracer.enabled else None)
        t_deser = asyncio.get_running_loop().time()
        batches = ipc_to_batches(ipc)
        if not batches:
            raise ConnectError("infer batch frame decoded to zero batches")
        batch = MessageBatch(batches[0])
        await _send_frame(writer, json.dumps(
            {"ok": True, "incarnation": self.incarnation}).encode(), crc=crc)
        writer._arkflow_streaming = True
        loop = asyncio.get_running_loop()
        self.tracer.record(tctx, "remote_deserialize", loop.time() - t_deser)
        self._inflight += 1
        self.ctrl.on_enqueue()
        t_q = loop.time()
        try:
            async with self._sem:  # one device, max_in_flight lanes
                q_wait = loop.time() - t_q
                self.ctrl.on_dequeue(q_wait, loop.time())
                self.tracer.record(tctx, "remote_queue_wait", q_wait)
                t0 = loop.time()
                # activate the worker's tracer so the hosted chain's spans
                # (infeed prep, device step) nest under remote_step
                decode_urls = [str(u) for u in req.get("decode_workers") or []]
                decode_crc = {str(u) for u in req.get("decode_crc") or []}
                fenced = [str(f) for f in req.get("fenced") or []]
                disagg = (self._disagg_handle()
                          if self.role == "prefill" and decode_urls else None)
                with activate(self.tracer, tctx):
                    if disagg is not None:
                        # prefill role two-hop: prefill locally, stream the
                        # KV pages to a decode candidate, relay its tokens
                        with stage_span("remote_step"):
                            exports = await disagg.prefill_rows(batch)
                        with stage_span("remote_kv_push"):
                            token_lists = [await self._push_export(
                                e, decode_urls, crc_urls=decode_crc,
                                fenced=fenced) for e in exports]
                        results = disagg.finalize_rows(batch, token_lists)
                    else:
                        with stage_span("remote_step"):
                            results = await self.pipeline.process(batch)
                self.ctrl.observe_step(loop.time() - t0)
            t_ser = loop.time()
            for out in results:
                await _send_data(writer, batch_to_ipc(out.record_batch),
                                 crc=crc)
            self.tracer.record(tctx, "remote_serialize", loop.time() - t_ser)
            spans = self.tracer.export_open(tctx)
            if spans:
                await _send_frame(writer, TRACE_TAG + json.dumps(
                    {"spans": spans}).encode(), crc=crc)
            await _end_stream(writer)
            self._served += 1
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            self.tracer.export_open(tctx)  # don't strand the open entry
            raise
        except Exception:
            self._errors += 1
            # a FAILED step is exactly the trace forced sampling exists
            # for: ship the worker-tier spans ahead of the error frame the
            # outer handler will send (the connection is still alive here)
            spans = self.tracer.export_open(tctx)
            if spans:
                try:
                    await _send_frame(writer, TRACE_TAG + json.dumps(
                        {"spans": spans}).encode())
                except Exception:
                    pass  # the error frame still matters more
            raise
        finally:
            self._inflight -= 1

    # -- prefill/decode disaggregation -------------------------------------

    def _disagg_handle(self) -> Optional[Any]:
        """The hosted chain's disaggregation adapter (a continuous
        ``tpu_generate`` processor exposes itself as ``.disagg`` — same
        ``_inner``-chain convention as ``.runner``/``.swapper``)."""
        for proc in self.pipeline.processors:
            d = _walk_inner(proc, "disagg")
            if d is not None and hasattr(d, "prefill_rows"):
                return d
        return None

    def _generation_server(self) -> Optional[Any]:
        """The hosted continuous generation server (adopt target)."""
        for proc in self.pipeline.processors:
            runner = _walk_inner(proc, "runner")
            if runner is not None and hasattr(runner, "generate_from_pages"):
                return runner
        return None

    async def _push_export(self, export: Mapping, urls: Sequence[str],
                           crc_urls: Optional[set] = None,
                           fenced: Optional[Sequence[str]] = None) -> list[int]:
        """Ship one prompt's KV pages to the first decode candidate that
        accepts, in the occupancy order the dispatcher planned. A retryable
        refusal (draining / role mismatch) or a transport error re-plans to
        the next candidate; a processing failure on an ACCEPTED push is
        terminal (the decode side already owns the request). All candidates
        exhausted raises ConnectError — the infer stream errors, and the
        ingest tier's normal nack/redelivery re-prefills."""
        if export.get("done"):
            return [int(t) for t in export.get("tokens") or []]
        meta, frames = kv_export_to_wire(export)
        last: Optional[BaseException] = None
        for url in urls:
            host, port = parse_remote_url(url)
            try:
                reader, writer = await asyncio.wait_for(
                    asyncio.open_connection(host, port), 5.0)
            except (OSError, asyncio.TimeoutError) as e:
                self._kv_push_retries += 1
                last = e
                continue
            # crc per peer: the dispatcher tells us which decode candidates
            # advertised frame integrity — raw bf16 slabs bypass Arrow IPC
            # validation, so the trailer is the ONLY corruption check
            use_crc = self.crc and crc_urls is not None and url in crc_urls
            try:
                try:
                    push_req: dict = {"action": "kv_push", "meta": meta}
                    if fenced:
                        push_req["fenced"] = list(fenced)
                    await _send_frame(writer, json.dumps(push_req).encode(),
                                      crc=use_crc)
                    for fr in frames:
                        await _send_frame(writer, fr, crc=use_crc)
                    raw = await asyncio.wait_for(
                        _read_frame(reader, self.max_frame,
                                    what="kv_push status"), 120.0)
                    if raw is None:
                        raise ConnectError(
                            f"decode worker {url} closed before a status")
                    status = json.loads(raw.decode())
                except (ConnectionError, OSError, asyncio.TimeoutError,
                        asyncio.IncompleteReadError, ConnectError,
                        ReadError) as e:
                    self._kv_push_retries += 1
                    last = e
                    continue
            finally:
                try:
                    writer.close()
                except Exception:
                    pass
            if status.get("ok"):
                self._kv_pushed += 1
                return [int(t) for t in status.get("tokens") or []]
            if status.get("retryable"):
                self._kv_push_retries += 1
                last = ConnectError(
                    f"decode worker {url} refused kv_push: {status.get('error')}")
                continue
            raise ProcessError(
                f"decode worker {url} failed adopted decode: "
                f"{status.get('error')}")
        raise ConnectError(
            f"kv_push: no decode worker accepted the pages "
            f"({len(urls)} candidates tried; last: {last!r})")

    async def _do_kv_push(self, req: dict, reader, writer,
                          crc: bool = False) -> None:
        """Adopt a prefill worker's KV pages and decode to completion.

        The slab frames are consumed BEFORE any refusal (same ordering as
        ``infer`` under drain: the peer already committed the frames to the
        socket), then draining / role-mismatch / a fenced incarnation
        refuse RETRYABLY so the prefill side re-plans to the ring's next
        decode candidate instead of surfacing a processing error."""
        meta = req.get("meta")
        if not isinstance(meta, Mapping):
            await _send_frame(writer, json.dumps(
                {"ok": False,
                 "error": "kv_push needs a 'meta' mapping"}).encode(),
                crc=crc)
            return
        frames: list[bytes] = []
        if not meta.get("done"):
            shards = meta.get("shards", 1)
            if (isinstance(shards, bool) or not isinstance(shards, int)
                    or not 1 <= shards <= 64):
                await _send_frame(writer, json.dumps(
                    {"ok": False,
                     "error": f"kv_push shards invalid: {shards!r}"}
                ).encode(), crc=crc)
                return
            for i in range(2 * shards):
                fr = await self._read_bounded(
                    reader, f"kv_push slab {i + 1}/{2 * shards}")
                if fr is None:
                    raise ConnectError(
                        "kv_push ended before all page-slab frames")
                frames.append(bytes(fr))
        if self._fence_check(req):
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "worker incarnation was fenced "
                 "(stale epoch); re-minted — retry the next candidate",
                 "retryable": True}).encode(), crc=crc)
            return
        if self.draining:
            self._kv_refused += 1
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "worker is draining",
                 "retryable": True}).encode(), crc=crc)
            return
        if self.role == "prefill":
            self._kv_refused += 1
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "worker role is 'prefill': cannot "
                 "adopt KV pages it would never decode",
                 "retryable": True}).encode(), crc=crc)
            return
        server = self._generation_server()
        if server is None:
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": "no continuous generation server "
                 "hosted on this worker"}).encode(), crc=crc)
            return
        export = kv_export_from_wire(meta, frames)
        loop = asyncio.get_running_loop()
        self._inflight += 1
        self.ctrl.on_enqueue()
        t_q = loop.time()
        try:
            async with self._sem:  # adopted decode holds a device lane too
                self.ctrl.on_dequeue(loop.time() - t_q, loop.time())
                t0 = loop.time()
                tokens = await server.generate_from_pages(export)
                self.ctrl.observe_step(loop.time() - t0)
            self._kv_adopted += 1
            self._served += 1
            await _send_frame(writer, json.dumps(
                {"ok": True, "worker_id": self.worker_id,
                 "incarnation": self.incarnation,
                 "tokens": [int(t) for t in tokens]}).encode(), crc=crc)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            raise
        except Exception as e:
            self._errors += 1
            await _send_frame(writer, json.dumps(
                {"ok": False, "error": repr(e)[:500]}).encode(), crc=crc)
        finally:
            self._inflight -= 1


# -- worker config / entry point -------------------------------------------


def parse_worker_config(m: Any) -> tuple[list[dict], dict]:
    """Worker-mode config -> (processor config list, worker options).

    Accepts the natural shapes: ``{processors: [...]}``, a stream-style
    ``{pipeline: {processors: [...]}}``, or a full engine config (the FIRST
    stream's pipeline is hosted) — so a worker can reuse the exact
    processor block of the single-process config it was split out of.
    Options ride under ``worker: {id, max_in_flight, max_frame, grace,
    role, io_deadline, crc}`` (``grace`` = the SIGTERM self-drain budget,
    default 30s; ``io_deadline`` = the per-frame read deadline bounding
    slow-loris peers, default 30s; ``crc`` = advertise crc32 frame
    integrity, default true)."""
    if not isinstance(m, Mapping):
        raise ConfigError("cluster worker config must be a mapping")
    procs: Any = m.get("processors")
    if procs is None and isinstance(m.get("pipeline"), Mapping):
        procs = m["pipeline"].get("processors")
    if procs is None and isinstance(m.get("streams"), list) and m["streams"]:
        s0 = m["streams"][0]
        if isinstance(s0, Mapping) and isinstance(s0.get("pipeline"), Mapping):
            procs = s0["pipeline"].get("processors")
    if not isinstance(procs, list) or not procs:
        raise ConfigError(
            "cluster worker config needs a non-empty processor list "
            "(top-level 'processors:', 'pipeline.processors:', or the first "
            "stream of an engine config)")
    for p in procs:
        if not isinstance(p, Mapping) or not p.get("type"):
            raise ConfigError(f"worker processor config must be a mapping "
                              f"with a 'type' tag, got {p!r}")
    opts_raw = m.get("worker") or {}
    if not isinstance(opts_raw, Mapping):
        raise ConfigError("'worker' options must be a mapping")
    opts: dict = {}
    mif = opts_raw.get("max_in_flight", 1)
    if isinstance(mif, bool) or not isinstance(mif, int) or mif < 1:
        raise ConfigError(
            f"worker.max_in_flight must be an int >= 1, got {mif!r}")
    opts["max_in_flight"] = mif
    mf = opts_raw.get("max_frame", DEFAULT_MAX_FRAME)
    if isinstance(mf, bool) or not isinstance(mf, int) or mf < 1024:
        raise ConfigError(
            f"worker.max_frame must be an int >= 1024, got {mf!r}")
    opts["max_frame"] = mf
    wid = opts_raw.get("id")
    if wid is not None and not isinstance(wid, str):
        raise ConfigError(f"worker.id must be a string, got {wid!r}")
    opts["worker_id"] = wid
    role = opts_raw.get("role", "both")
    if role not in WORKER_ROLES:
        raise ConfigError(
            f"worker.role must be one of {WORKER_ROLES}, got {role!r}")
    opts["role"] = role
    from arkflow_tpu.utils.duration import parse_duration

    grace = opts_raw.get("grace", "30s")
    try:
        grace_s = parse_duration(grace)
    except (ConfigError, TypeError, ValueError) as e:
        raise ConfigError(f"worker.grace invalid: {e}") from e
    if grace_s <= 0:
        raise ConfigError(f"worker.grace must be > 0, got {grace!r}")
    opts["grace_s"] = grace_s
    io_deadline = opts_raw.get("io_deadline", "30s")
    try:
        io_deadline_s = parse_duration(io_deadline)
    except (ConfigError, TypeError, ValueError) as e:
        raise ConfigError(f"worker.io_deadline invalid: {e}") from e
    if io_deadline_s <= 0:
        raise ConfigError(
            f"worker.io_deadline must be > 0, got {io_deadline!r}")
    opts["io_deadline_s"] = io_deadline_s
    crc = opts_raw.get("crc", True)
    if not isinstance(crc, bool):
        raise ConfigError(f"worker.crc must be a bool, got {crc!r}")
    opts["crc"] = crc
    # a worker accepts the same top-level `tracing:` block as the engine
    # (sample knobs matter less here — the ingest tier owns the sampling
    # decision — but span caps and the kill switch do). Parsed even when
    # absent: from_mapping(None) is what consults the ARKFLOW_TRACE env
    # kill switch, which must bind device-tier workers too.
    opts["tracing"] = TracingConfig.from_mapping(m.get("tracing"))
    return [dict(p) for p in procs], opts


def build_worker_server(config: Mapping, *, host: str = "127.0.0.1",
                        port: int = 50052,
                        worker_id: Optional[str] = None,
                        max_frame: Optional[int] = None) -> ClusterWorkerServer:
    """Build (but don't start) a worker server from a parsed config mapping."""
    procs_cfg, opts = parse_worker_config(config)
    ensure_plugins_loaded()
    resource = Resource()
    processors = [build_component("processor", p, resource) for p in procs_cfg]
    return ClusterWorkerServer(
        processors, host=host, port=port,
        worker_id=worker_id or opts["worker_id"],
        max_in_flight=opts["max_in_flight"],
        max_frame=max_frame or opts["max_frame"],
        tracing=opts["tracing"],
        grace_s=opts["grace_s"],
        role=opts["role"],
        io_deadline_s=opts["io_deadline_s"],
        crc=opts["crc"])


async def run_worker(config: Mapping, *, host: str = "127.0.0.1",
                     port: int = 50052, worker_id: Optional[str] = None,
                     max_frame: Optional[int] = None) -> None:
    """CLI entry: build, warm up, then serve until cancelled, stopped by a
    SIGTERM self-drain, or (multi-host follower) released by the primary.

    With a ``distributed:`` block (or the ``ARKFLOW_*`` distributed env)
    naming more than one process, the worker joins a multi-host
    ``jax.distributed`` mesh: every process builds the IDENTICAL processor
    chain (so its ``mesh`` spans the global device list), process 0
    opens the serving port and broadcasts each infer batch, processes > 0
    run the lockstep follower loop (parallel/distributed.py) — one model
    too big for one process, served across several."""
    from arkflow_tpu.parallel.distributed import multihost_from_config

    mh = multihost_from_config(config)
    server = build_worker_server(config, host=host, port=port,
                                 worker_id=worker_id, max_frame=max_frame)
    if mh is not None and not mh.is_primary:
        from arkflow_tpu.parallel.distributed import run_follower

        # follower: same warmup (lockstep with the primary's), then replay
        # the primary's broadcast batches instead of serving a port
        await server.pipeline.connect()
        try:
            await run_follower(mh, server.pipeline)
        finally:
            await server.pipeline.close()
        return
    if mh is not None:
        from arkflow_tpu.parallel.distributed import LockstepPipeline

        # primary: every pipeline entry (warmup's compiles excepted — the
        # followers run connect() themselves, in the same order) fans the
        # batch out to the followers BEFORE processing, keeping the
        # multi-host collectives lockstep across processes
        server.pipeline = LockstepPipeline(mh, server.pipeline)
    await server.connect()  # warmup compiles BEFORE the port opens
    server.install_signal_handlers()
    try:
        await server.serve_forever()
    finally:
        await server.stop()


# ---------------------------------------------------------------------------
# ingest tier: worker handles, dispatcher, fleet swap
# ---------------------------------------------------------------------------


class _RemoteProcessingError(Exception):
    """The worker ran the batch and FAILED (model error, poison batch).

    Not retried on another worker: a deterministic failure would fail
    everywhere, and transient device faults heal through the stream's own
    nack/redelivery — which re-routes by hash to the same (by then probed
    and healed) worker."""


class _WorkerDraining(Exception):
    """The worker refused the batch because it is draining — routable."""


class RetryBudgetExhausted(Overloaded):
    """The dispatcher's ring-retry token bucket is empty: a fleet-wide
    brownout is amplifying offered load through failover retries, and the
    budget caps the amplification. The stream sheds the batch through the
    never-silent error-output path tagged ``reason=retry_budget`` (the
    ``shed_reason`` attribute is the stream's generic hook) instead of
    retry-storming a struggling fleet."""

    shed_reason = "retry_budget"


class RemoteWorker:
    """Ingest-side handle for one device worker: liveness, the advertised
    load signals, client-side in-flight accounting, and the per-worker
    autoscaling gauges."""

    def __init__(self, url: str, name: str):
        self.url = url
        self.host, self.port = parse_remote_url(url)
        self.worker_id: Optional[str] = None
        self.alive = False
        self.draining = False
        #: advertised AIMD window (heartbeat); routing headroom bound
        self.window = 1
        #: advertised queue-drain estimate (heartbeat)
        self.drain_s = 0.0
        #: client-side outstanding requests (fresh, unlike the heartbeat)
        self.inflight = 0
        self.dispatched = 0
        #: advertised disaggregation role (heartbeat; default both)
        self.role = "both"
        #: advertised incarnation epoch (register/heartbeat); fencing keys
        #: on it — a worker_id names the identity, this names the epoch
        self.incarnation: Optional[str] = None
        #: epochs declared dead by staleness/probe-timeout: frames from
        #: them are zombie frames and get rejected until the heal handshake
        #: re-mints (bounded — old fences age out, they only matter while
        #: the zombie could still be holding the stale epoch)
        self.fenced: deque = deque(maxlen=8)
        #: peer advertised crc32 frame-integrity support at register
        self.crc = False
        #: decode-side occupancy (heartbeat): generation slots and KV page
        #: pool pressure — real decode saturation, not just the AIMD window
        self.gen_slots = 0
        self.gen_slots_busy = 0
        self.page_occupancy = 0.0
        #: SDC defense signals (heartbeat; tpu/integrity.py): the combined
        #: param-digest epoch (None until the worker baselines), the
        #: worker's self-reported quarantined-member count, and the last
        #: digest value that passed an on-demand probe (so a legitimate
        #: weights-version outlier is not re-probed every beat)
        self.param_digest: Optional[str] = None
        self.integrity_corrupt = 0
        self.digest_cleared: Optional[str] = None
        self.last_report: dict = {}
        self.last_seen = 0.0
        self.last_error: Optional[str] = None
        reg = global_registry()
        labels = {"stream": name, "worker": url}
        self.m_alive = reg.gauge(
            "arkflow_cluster_worker_alive",
            "1 when the device worker answers register/heartbeat", labels)
        self.m_window = reg.gauge(
            "arkflow_cluster_worker_window",
            "worker-advertised AIMD admission window (autoscaling signal)",
            labels)
        self.m_drain = reg.gauge(
            "arkflow_cluster_worker_drain_seconds",
            "worker-advertised queue drain estimate (autoscaling signal)",
            labels)
        self.m_inflight = reg.gauge(
            "arkflow_cluster_worker_inflight",
            "ingest-side in-flight dispatches to this worker", labels)
        self.m_dispatched = reg.counter(
            "arkflow_cluster_dispatch_total",
            "batches dispatched to this worker", labels)

    def note_report(self, rep: dict, now: float) -> None:
        self.worker_id = rep.get("worker_id", self.worker_id)
        self.alive = True
        self.draining = bool(rep.get("draining", False))
        self.window = max(1, int(rep.get("window", 1)))
        self.drain_s = float(rep.get("drain_s", 0.0))
        inc = rep.get("incarnation")
        if isinstance(inc, str) and inc:
            self.incarnation = inc
        self.crc = bool(rep.get("crc", False))
        role = rep.get("role", "both")
        self.role = role if role in WORKER_ROLES else "both"
        self.gen_slots = int(rep.get("gen_slots", 0) or 0)
        self.gen_slots_busy = int(rep.get("gen_slots_busy", 0) or 0)
        self.page_occupancy = float(rep.get("page_pool_occupancy", 0.0) or 0.0)
        dig = rep.get("param_digest")
        self.param_digest = dig if isinstance(dig, str) and dig else None
        self.integrity_corrupt = int(rep.get("integrity_corrupt", 0) or 0)
        self.last_report = rep
        self.last_seen = now
        self.last_error = None
        self.m_alive.set(1.0)
        self.m_window.set(self.window)
        self.m_drain.set(self.drain_s)

    def note_down(self, err: BaseException) -> None:
        self.alive = False
        self.last_error = f"{type(err).__name__}: {err}"
        self.m_alive.set(0.0)

    def fence(self) -> Optional[str]:
        """Fence the current incarnation: it was declared dead while
        possibly still running (staleness / an unresponsive probe), so any
        later frame from it is a zombie's. Returns the fenced epoch."""
        inc = self.incarnation
        if inc and inc not in self.fenced:
            self.fenced.append(inc)
        return inc

    def is_fenced(self, incarnation: Optional[str]) -> bool:
        return bool(incarnation) and incarnation in self.fenced

    def serves(self, role: str) -> bool:
        """True when this worker accepts work of the given role."""
        return self.role == "both" or self.role == role

    def has_headroom(self) -> bool:
        if self.inflight >= self.window:
            return False
        # decode-side saturation folded in: every generation slot busy or
        # a nearly-full KV page pool means new work queues regardless of
        # what the AIMD window (which adapts a cycle behind) still admits
        if self.gen_slots and self.gen_slots_busy >= self.gen_slots:
            return False
        if self.page_occupancy >= 0.95:
            return False
        return True

    def report(self) -> dict:
        state = ("dead" if not self.alive
                 else "draining" if self.draining else "alive")
        out = {
            "worker": self.url,
            "worker_id": self.worker_id,
            "state": state,
            "role": self.role,
            "window": self.window,
            "drain_s": self.drain_s,
            "inflight": self.inflight,
            "dispatched": self.dispatched,
        }
        if self.gen_slots:
            out["gen_slots"] = self.gen_slots
            out["gen_slots_busy"] = self.gen_slots_busy
            out["page_pool_occupancy"] = self.page_occupancy
        if self.fenced:
            out["incarnation"] = self.incarnation
            out["fenced"] = list(self.fenced)
        if self.param_digest:
            out["param_digest"] = self.param_digest
        if self.integrity_corrupt:
            out["integrity_corrupt"] = self.integrity_corrupt
        if self.last_error:
            out["last_error"] = self.last_error
        remote_health = self.last_report.get("health")
        if remote_health:
            out["remote_health"] = remote_health
        remote_caches = self.last_report.get("caches")
        if remote_caches:
            out["remote_caches"] = remote_caches
        return out


class ClusterDispatcher:
    """The ingest tier's ``remote_tpu`` routing core.

    Owns the worker handles, the consistent-hash ring, the heartbeat loop,
    and the dispatch/retry discipline described in the module docstring."""

    def __init__(self, urls: Sequence[str], *, name: str = "cluster",
                 route_key: str = "fingerprint", prefix_bytes: int = 64,
                 text_field: Optional[str] = None, virtual_nodes: int = 64,
                 heartbeat_s: float = 2.0, request_timeout_s: float = 60.0,
                 connect_timeout_s: float = 5.0,
                 heartbeat_timeout_s: Optional[float] = None,
                 max_frame: int = DEFAULT_MAX_FRAME,
                 decode_candidates: int = 3,
                 crc: bool = True, io_deadline_floor_s: float = 0.1,
                 hedge: Optional[Mapping] = None,
                 retry_budget: Optional[Mapping] = None,
                 shadow_verify: Optional[Mapping] = None):
        from arkflow_tpu.batch import DEFAULT_BINARY_VALUE_FIELD

        if not urls:
            raise ConfigError("remote_tpu needs a non-empty 'workers' list")
        if len(set(urls)) != len(urls):
            raise ConfigError(f"remote_tpu workers must be distinct, got {urls}")
        if route_key not in ROUTE_KEYS:
            raise ConfigError(
                f"remote_tpu.route_key must be one of {ROUTE_KEYS}, "
                f"got {route_key!r}")
        self.name = name
        self.route_key = route_key
        self.prefix_bytes = prefix_bytes
        self.text_field = text_field or DEFAULT_BINARY_VALUE_FIELD
        self.heartbeat_s = heartbeat_s
        self.request_timeout_s = request_timeout_s
        self.connect_timeout_s = connect_timeout_s
        #: heartbeats older than this mark the member DEAD proactively — a
        #: SIGKILLed or network-wedged worker must fall out of the routing
        #: table on the heartbeat clock, not at the next 60s transport
        #: timeout. Also caps the probe round-trip itself, so one wedged
        #: member can't stall the whole heartbeat sweep.
        self.heartbeat_timeout_s = (
            heartbeat_timeout_s if heartbeat_timeout_s is not None
            else max(5.0 * heartbeat_s, 10.0))
        if self.heartbeat_timeout_s <= heartbeat_s:
            raise ConfigError(
                f"remote_tpu.heartbeat_timeout ({self.heartbeat_timeout_s}s) "
                f"must exceed the heartbeat period ({heartbeat_s}s)")
        if decode_candidates < 1:
            raise ConfigError(
                f"remote_tpu.decode_candidates must be >= 1, "
                f"got {decode_candidates}")
        #: how many occupancy-ordered decode destinations ride along with
        #: each prefill dispatch (failover depth for the second hop)
        self.decode_candidates = int(decode_candidates)
        self.virtual_nodes = virtual_nodes
        self.max_frame = int(max_frame)
        #: send crc32-trailed frames to workers that advertised support
        self.crc = bool(crc)
        #: floor under the deadline-derived per-hop I/O timeout: a batch
        #: with 3ms of budget left still gets a read window the transport
        #: can physically meet (it will shed at admission next hop anyway)
        self.io_deadline_floor_s = float(io_deadline_floor_s)
        # hedged dispatch (None = disabled): after a p99-EWMA delay (or the
        # configured fixed delay) re-send the infer to the ring successor,
        # first response wins — duplicates are safe because fingerprint
        # affinity + response caches make them idempotent under
        # at-least-once. Budget-capped so hedges can't melt spare capacity.
        self._hedge = dict(hedge) if hedge is not None else None
        if self._hedge is not None:
            self._hedge.setdefault("delay_s", None)  # None = auto (p99 EWMA)
            self._hedge.setdefault("max_fraction", 0.1)
            self._hedge.setdefault("burst", 4)
            self._hedge.setdefault("min_delay_s", 0.01)
        self._lat_samples: deque = deque(maxlen=128)
        self._p99_ewma: Optional[float] = None
        self._dispatch_count = 0
        self._hedges_issued = 0
        # ring-retry token bucket (None = unlimited, the historical
        # behavior): each dispatch deposits ``ratio`` tokens, each ring
        # failover spends one, so retries/offered <= ratio (+burst)
        self._retry_budget = (dict(retry_budget)
                              if retry_budget is not None else None)
        if self._retry_budget is not None:
            self._retry_budget.setdefault("ratio", 0.5)
            self._retry_budget.setdefault("burst", 8)
        self._retry_tokens = (float(self._retry_budget["burst"])
                              if self._retry_budget is not None else None)
        # shadow verification (None = disabled): every (1/fraction)-th
        # dispatch is ALSO sent to the ring successor and the two
        # responses' fingerprints compared — the defense against corruption
        # a worker cannot see in itself (its digests hash the corrupt tree
        # it already has; its golden probe runs on the corrupt chip).
        # Deterministic round-counting, not RNG: fraction 1.0 must shadow
        # EVERY batch (the soak's zero-corrupt-rows proof depends on it).
        self._shadow = dict(shadow_verify) if shadow_verify is not None else None
        if self._shadow is not None:
            self._shadow.setdefault("fraction", 0.05)
            self._shadow_every = max(
                1, round(1.0 / float(self._shadow["fraction"])))
        self._shadow_count = 0
        #: run when a worker is fenced for proven corruption — the ingest
        #: response cache epoch-bumps here (its cached answers from that
        #: worker may be poisoned)
        self.integrity_hooks: list = []
        #: in-process chaos transport (chaoswire.ChaosWire); armed by the
        #: fault plugin's net_* kinds, wraps the next opened connection
        self.chaos = None
        self.workers: dict[str, RemoteWorker] = {
            url: RemoteWorker(url, name) for url in urls}
        self.ring = HashRing(list(urls), virtual_nodes)
        self._hb_task: Optional[asyncio.Task] = None
        reg = global_registry()
        labels = {"stream": name}
        self.m_retries = reg.counter(
            "arkflow_cluster_retry_total",
            "dispatches that failed over to another ring worker", labels)
        self.m_spills = reg.counter(
            "arkflow_cluster_spill_total",
            "dispatches routed off the hash owner for load/drain reasons",
            labels)
        self.m_deaths = reg.counter(
            "arkflow_cluster_worker_down_total",
            "times a worker was marked down after a failed call", labels)
        self.m_fenced = reg.counter(
            "arkflow_cluster_fenced_total",
            "frames/reports rejected because they came from a fenced "
            "(staleness-declared-dead) worker incarnation", labels)
        self.m_frame_errors = reg.counter(
            "arkflow_cluster_frame_error_total",
            "flight frames that failed the crc32 integrity check", labels)
        self.m_retry_shed = reg.counter(
            "arkflow_cluster_retry_budget_exhausted_total",
            "dispatches shed because the ring-retry token bucket was empty",
            labels)
        self.m_hedge = {
            o: reg.counter(
                "arkflow_cluster_hedge_total",
                "hedged dispatch outcomes (issued / win = hedge beat the "
                "owner / primary_win = owner answered first / denied = "
                "budget cap / failed = both attempts failed)",
                {**labels, "outcome": o})
            for o in ("issued", "win", "primary_win", "denied", "failed")
        }
        self.m_shadow = {
            o: reg.counter(
                "arkflow_shadow_verify_total",
                "shadow-verify outcomes (issued / match / diverged / "
                "skipped = no partner or one attempt failed, so no "
                "comparison happened)",
                {**labels, "outcome": o})
            for o in ("issued", "match", "diverged", "skipped")
        }
        self.m_integrity_fence = reg.counter(
            "arkflow_cluster_integrity_fence_total",
            "workers fenced for proven or self-reported silent-data-"
            "corruption (heartbeat corrupt report, digest outlier confirmed "
            "by probe, or shadow-verify tiebreak)", labels)

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        """Register with the fleet and start the heartbeat loop. At least
        one worker must answer — a stream with zero reachable workers is a
        deployment error worth failing loudly at connect; workers that come
        up later are adopted by the heartbeat."""
        if self._hb_task is not None:
            return
        await asyncio.gather(*(self._probe(w) for w in self.workers.values()),
                             return_exceptions=True)
        alive = [w for w in self.workers.values() if w.alive]
        if not alive:
            errs = "; ".join(f"{w.url}: {w.last_error}"
                             for w in self.workers.values())
            raise ConnectError(
                f"remote_tpu[{self.name}]: no cluster worker reachable "
                f"({errs})")
        logger.info("remote_tpu[%s]: %d/%d workers registered", self.name,
                    len(alive), len(self.workers))
        self._hb_task = asyncio.create_task(
            self._heartbeat_loop(), name=f"{self.name}-cluster-heartbeat")

    async def close(self) -> None:
        if self._hb_task is not None:
            self._hb_task.cancel()
            try:
                await self._hb_task
            except (asyncio.CancelledError, Exception):
                pass
            self._hb_task = None

    async def _heartbeat_loop(self) -> None:
        # per-worker probe tasks, NOT a gathered round: a black-holed member
        # pins its probe for the full heartbeat_timeout, and waiting on it
        # would stretch the round past the staleness cutoff — stale-fencing
        # HEALTHY siblings that answered every probe they were sent
        inflight: dict[str, asyncio.Task] = {}
        try:
            while True:
                await asyncio.sleep(self.heartbeat_s)
                self._expire_stale()
                for w in list(self.workers.values()):
                    t = inflight.get(w.url)
                    if t is not None and not t.done():
                        continue  # previous probe still inside its timeout
                    inflight[w.url] = asyncio.create_task(self._probe(w))
        finally:
            for t in inflight.values():
                t.cancel()

    def _is_stale(self, w: RemoteWorker, now: float) -> bool:
        return (w.alive and w.last_seen > 0.0
                and now - w.last_seen > self.heartbeat_timeout_s)

    def _expire_stale(self, now: Optional[float] = None) -> None:
        """Proactively kill members whose heartbeats went quiet (the
        SIGKILL / network-wedge case: the socket may still accept, so no
        transport failure ever fires). Runs on the heartbeat clock AND at
        plan time, so routing never waits on the sweep."""
        if now is None:
            now = asyncio.get_running_loop().time()
        for w in self.workers.values():
            if self._is_stale(w, now):
                self.m_deaths.inc()
                fenced = w.fence()
                logger.warning(
                    "remote_tpu[%s]: worker %s heartbeats stale for %.1fs "
                    "(timeout %.1fs); marking dead, fencing incarnation %s",
                    self.name, w.url, now - w.last_seen,
                    self.heartbeat_timeout_s, fenced)
                w.note_down(ConnectError(
                    f"heartbeats stale for {now - w.last_seen:.1f}s"))

    async def _probe(self, w: RemoteWorker) -> None:
        """One register/heartbeat round-trip; flips liveness both ways.
        Bounded by the heartbeat timeout, NOT the request timeout — a
        wedged member answering nothing must not pin the sweep for the
        full infer budget."""
        action = "heartbeat" if w.worker_id is not None else "register"
        try:
            rep = await self._unary(w, {"action": action},
                                    timeout=self.heartbeat_timeout_s)
        except asyncio.TimeoutError as e:
            # answered nothing inside the probe bound: unresponsive but
            # possibly still RUNNING (one-way partition, wedge) — fence the
            # epoch so its frames are rejectable if it resurfaces
            if w.alive:
                self.m_deaths.inc()
                logger.warning(
                    "remote_tpu[%s]: worker %s probe timed out; marking "
                    "dead, fencing incarnation %s", self.name, w.url,
                    w.fence())
            w.note_down(e)
            return
        except Exception as e:
            if w.alive:
                self.m_deaths.inc()
                logger.warning("remote_tpu[%s]: worker %s down: %s",
                               self.name, w.url, e)
            w.note_down(e)
            return
        inc = rep.get("incarnation")
        if w.is_fenced(inc):
            # a partition-healed zombie heartbeating from its fenced epoch:
            # reject the report (its occupancy/window are stale), then heal
            # explicitly — ask it to re-mint, and admit the FRESH epoch
            self.m_fenced.inc()
            logger.warning(
                "remote_tpu[%s]: worker %s answered from fenced incarnation "
                "%s (partition-healed zombie); rejecting its report and "
                "requesting a re-mint", self.name, w.url, inc)
            try:
                rep = await self._unary(
                    w, {"action": "register", "fence": inc},
                    timeout=self.heartbeat_timeout_s)
            except Exception as e:
                w.note_down(e)
                return
            if w.is_fenced(rep.get("incarnation")):
                w.note_down(ConnectError(
                    f"worker {w.url} still answering from fenced "
                    f"incarnation {inc} after a heal handshake"))
                return
        if not rep.get("ok") or not rep.get("worker_id"):
            # answers-but-refuses is NOT alive: a scan-tier FlightWorker (or
            # any wrong endpoint) replies {"ok": false, "error": "unknown
            # action ..."} — marking it alive would pass the connect gate on
            # a fleet with zero usable workers
            w.note_down(ConnectError(
                f"worker {w.url} rejected {action}: {rep.get('error')!r} "
                "(is this really a cluster worker?)"))
            return
        proto = int(rep.get("proto", 1))
        if proto > PROTO_VERSION:
            w.note_down(ConnectError(
                f"worker speaks protocol {proto}, this engine speaks "
                f"{PROTO_VERSION}"))
            return
        if not w.alive:
            logger.info("remote_tpu[%s]: worker %s up (id=%s)", self.name,
                        w.url, rep.get("worker_id"))
        w.note_report(rep, asyncio.get_running_loop().time())
        await self._integrity_check(w)

    # -- SDC defense (tpu/integrity.py, cluster tier) ----------------------

    def _fence_for_integrity(self, w: RemoteWorker, reason: str) -> None:
        """Fence a worker on proven (or self-reported) corruption through
        the PR-19 incarnation path: its epoch is dead to the ring until the
        heal handshake re-mints it, and anything caching its past answers
        flushes. A worker whose member stays CORRUPT keeps re-reporting it
        on every heartbeat, so backoff alone never re-admits it — only a
        successful worker-side repair does."""
        self.m_integrity_fence.inc()
        self.m_deaths.inc()
        logger.error(
            "remote_tpu[%s]: fencing worker %s for integrity: %s "
            "(incarnation %s)", self.name, w.url, reason, w.fence())
        w.note_down(ProcessError(f"integrity: {reason}"))
        for hook in self.integrity_hooks:
            try:
                hook()
            except Exception:
                logger.exception("integrity fence hook failed")

    async def _integrity_check(self, w: RemoteWorker) -> None:
        """Heartbeat-time SDC fencing. A worker self-reporting quarantined
        (CORRUPT) members serves nothing until repaired. A worker whose
        param-digest epoch disagrees with the majority of digest-reporting
        peers (3+ reporting) is an OUTLIER — but an outlier is only proof
        of different weights, not corruption (a mid-roll hot-swap looks
        identical), so it is fenced only when its own on-demand golden
        probe confirms a mismatch; a clean probe clears that digest value
        until it changes again."""
        if w.integrity_corrupt:
            self._fence_for_integrity(
                w, f"{w.integrity_corrupt} corrupt member(s) self-reported")
            return
        dig = w.param_digest
        if not dig or dig == w.digest_cleared:
            return
        peers = [x.param_digest for x in self.workers.values()
                 if x.alive and x.param_digest]
        if len(peers) < 3:
            return  # no majority to compare against
        major, nmaj = Counter(peers).most_common(1)[0]
        if dig == major or nmaj <= len(peers) // 2:
            return
        try:
            rep = await self._unary(w, {"action": "integrity_probe"},
                                    timeout=self.request_timeout_s)
        except Exception as e:
            w.note_down(e)
            return
        if int(rep.get("mismatches", 0) or 0) or int(rep.get("corrupt", 0)
                                                     or 0):
            self._fence_for_integrity(
                w, f"digest outlier ({nmaj}/{len(peers)} peers agree on "
                   f"{major[:12]}, this worker reports {dig[:12]}) confirmed "
                   "by golden probe")
            return
        w.digest_cleared = dig
        logger.warning(
            "remote_tpu[%s]: worker %s is a param-digest outlier but passed "
            "its golden probe — different weights version (mid-swap?), not "
            "corruption; admitting", self.name, w.url)

    # -- wire helpers ------------------------------------------------------

    def chaos_arm(self, kind: str, *, duration_s: float = 0.0,
                  seed: int = 0) -> None:
        """Arm one network fault on the next flight connection this
        dispatcher opens (the ``fault`` plugin's ``net_*`` kinds land
        here). Lazily creates the seeded chaos transport."""
        if self.chaos is None:
            from arkflow_tpu.connect.chaoswire import ChaosWire

            self.chaos = ChaosWire(seed=seed)
        self.chaos.arm(kind, duration_s=duration_s)

    async def _open(self, w: RemoteWorker):
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(w.host, w.port),
                self.connect_timeout_s)
        except (OSError, asyncio.TimeoutError) as e:
            raise ConnectError(
                f"cluster worker {w.url} unreachable: {e}") from e
        if self.chaos is not None and self.chaos.pending():
            reader, writer = self.chaos.wrap(reader, writer)
        return reader, writer

    async def _unary(self, w: RemoteWorker, request: dict,
                     timeout: Optional[float] = None) -> dict:
        """One request frame -> one JSON status frame."""
        reader, writer = await self._open(w)
        what = f"{request.get('action', 'unary')} status"
        try:
            await _send_frame(writer, json.dumps(request).encode(),
                              crc=self.crc and w.crc)
            raw = await asyncio.wait_for(
                _read_frame(reader, self.max_frame, what=what),
                timeout or self.request_timeout_s)
            if raw is None:
                raise ConnectError(
                    f"cluster worker {w.url} closed before a status")
            return json.loads(raw.decode())
        finally:
            try:
                writer.close()
            except Exception:
                pass

    # -- routing -----------------------------------------------------------

    def routing_key(self, batch: MessageBatch) -> bytes:
        """``fingerprint`` keys on the batch's stable identity (dedup /
        response-cache affinity: redeliveries and byte-identical retries
        hash equal). ``prefix`` keys on the first ``prefix_bytes`` of the
        first row's payload (prompt-prefix affinity: conversations sharing
        a system prompt land where their KV prefix is cached)."""
        if self.route_key == "prefix":
            try:
                values, offsets = batch.payload_view(self.text_field)
                end = min(int(offsets[0]) + self.prefix_bytes, int(offsets[1]))
                return values[int(offsets[0]):end].tobytes()
            except Exception:
                pass  # no payload column: fall through to the fingerprint
        return batch_fingerprint(batch)

    def plan(self, key: bytes, *,
             role: Optional[str] = None) -> list[RemoteWorker]:
        """Candidate order for a key: ring order over live, non-draining
        workers, weighted by each worker's advertised load signals. The hash
        owner serves unless it has no headroom against its advertised AIMD
        window — then the dispatch spills to the successor with the least
        load (fewest outstanding dispatches, then smallest advertised drain
        estimate). Bounded-load consistent hashing: affinity is traded only
        under saturation, counted in ``arkflow_cluster_spill_total``.

        With ``role`` set (a role-split fleet), only workers serving that
        role are candidates — the ring walk skips the others, so prefix
        affinity over the PREFILL sub-ring survives exactly as it would on
        an undivided fleet.

        Stale members are expired here too (not only on the heartbeat
        clock): a dead worker's hash range falls to its ring successor the
        moment any batch routes, so affinity keys re-home deterministically
        with zero dispatches burned on the corpse."""
        try:
            self._expire_stale()
        except RuntimeError:
            pass  # no running loop (sync planning in tests): skip expiry
        live = [self.workers[u] for u in self.ring.candidates(key)
                if u in self.workers
                and self.workers[u].alive and not self.workers[u].draining
                and (role is None or self.workers[u].serves(role))]
        if len(live) < 2 or live[0].has_headroom():
            return live
        with_room = [w for w in live[1:] if w.has_headroom()]
        if with_room:
            best = min(with_room, key=lambda w: (w.inflight, w.drain_s))
            self.m_spills.inc()
            return [best] + [w for w in live if w is not best]
        # the whole fleet is saturated: queue on the owner (keeping
        # affinity) unless its advertised drain estimate is pathologically
        # worse than the best alternative's — a wedged-but-alive owner must
        # not absorb the queue forever
        floor = min(w.drain_s for w in live)
        if live[0].drain_s > 2.0 * floor + 1.0:
            best = min(live, key=lambda w: w.drain_s)
            self.m_spills.inc()
            return [best] + [w for w in live if w is not best]
        return live

    def role_split(self) -> bool:
        """True when any live worker declared a non-``both`` role — the
        fleet is running disaggregated and dispatch goes two-hop."""
        return any(w.role != "both"
                   for w in self.workers.values() if w.alive)

    def decode_targets(self) -> list[RemoteWorker]:
        """Decode placement order: live, non-draining decode-capable
        workers sorted by real decode pressure from the heartbeats — slot
        occupancy first, then KV page pressure, then outstanding
        dispatches. The prefill worker tries them in this order, so pages
        land where slots are actually free (capped at
        ``decode_candidates``)."""
        cands = [w for w in self.workers.values()
                 if w.alive and not w.draining and w.serves("decode")]
        cands.sort(key=lambda w: (
            (w.gen_slots_busy / w.gen_slots) if w.gen_slots else 0.0,
            w.page_occupancy, w.inflight, w.url))
        return cands[: self.decode_candidates]

    def _hop_timeout(self, batch: Optional[MessageBatch]) -> float:
        """Per-hop I/O deadline: the batch's remaining end-to-end budget
        (``__meta_ext_deadline_ms``) when it carries one, clamped between
        the floor and the flat request timeout. A wedged owner then costs
        the batch's own budget, not 30-60s of everyone's."""
        t = self.request_timeout_s
        if batch is None:
            return t
        try:
            rem = batch.remaining_deadline_ms()
        except Exception:
            rem = None
        if rem is None:
            return t
        return max(self.io_deadline_floor_s, min(t, rem / 1000.0))

    def _note_latency(self, dt: float) -> None:
        self._lat_samples.append(dt)
        if len(self._lat_samples) >= 8:
            s = sorted(self._lat_samples)
            p99 = s[min(len(s) - 1, int(0.99 * len(s)))]
            self._p99_ewma = (p99 if self._p99_ewma is None
                              else 0.8 * self._p99_ewma + 0.2 * p99)

    def latency_snapshot(self) -> list[float]:
        """Recent per-dispatch latencies (seconds) — soaks read p99 here."""
        return sorted(self._lat_samples)

    def _hedge_delay_s(self) -> float:
        assert self._hedge is not None
        fixed = self._hedge["delay_s"]
        if fixed is not None:
            return fixed
        floor = self._hedge["min_delay_s"]
        if self._p99_ewma is not None:
            return max(self._p99_ewma, floor)
        # cold start (no latency samples yet): hedge late rather than
        # doubling every warmup dispatch
        return max(self.request_timeout_s / 4.0, floor)

    def _hedge_budget_ok(self) -> bool:
        assert self._hedge is not None
        return (self._hedges_issued
                < self._hedge["max_fraction"] * self._dispatch_count
                + self._hedge["burst"])

    async def _attempt(self, w: RemoteWorker, batch: MessageBatch, *,
                       ctx, tracer, decode_urls: Sequence[str],
                       decode_crc: Sequence[str],
                       fenced: Sequence[str],
                       timeout_s: float) -> list[MessageBatch]:
        """One dispatch attempt on one worker, with the per-worker
        accounting that used to live inline in the dispatch loop. Raises
        classified: ``_WorkerDraining`` (marked), ``_RemoteProcessingError``
        (terminal), transport errors (worker marked down)."""
        w.inflight += 1
        w.m_inflight.set(w.inflight)
        try:
            out = await self._infer_on(w, batch, ctx=ctx, tracer=tracer,
                                       decode_urls=decode_urls,
                                       decode_crc=decode_crc, fenced=fenced,
                                       timeout_s=timeout_s)
        except _WorkerDraining:
            w.draining = True
            raise
        except _RemoteProcessingError:
            raise
        except FrameIntegrityError as e:
            # one corrupted frame is transport damage, not a dead worker:
            # fail over for THIS batch, keep the worker in the ring
            self.m_frame_errors.inc()
            logger.warning(
                "remote_tpu[%s]: corrupt frame from %s (%s); failing over "
                "without marking it down", self.name, w.url, e)
            raise
        except (ConnectError, ConnectionError, OSError,
                asyncio.IncompleteReadError, asyncio.TimeoutError) as e:
            if w.alive:
                self.m_deaths.inc()
                logger.warning(
                    "remote_tpu[%s]: worker %s failed mid-dispatch (%s); "
                    "retrying on the ring's next worker", self.name,
                    w.url, e)
            w.note_down(e)
            raise
        else:
            w.dispatched += 1
            w.m_dispatched.inc()
            return out
        finally:
            w.inflight -= 1
            w.m_inflight.set(w.inflight)

    async def _attempt_hedged(self, primary: RemoteWorker,
                              hedge_w: RemoteWorker, batch: MessageBatch,
                              **kw) -> list[MessageBatch]:
        """Race the owner against its ring successor: the hedge launches
        only after the hedge delay (p99 EWMA or configured) AND under the
        hedge budget; first success wins, the loser is cancelled. Safe
        duplicate execution: both workers compute the same fingerprint, so
        response caches keep the answers byte-identical."""
        p_task = asyncio.ensure_future(self._attempt(primary, batch, **kw))
        done, _ = await asyncio.wait({p_task}, timeout=self._hedge_delay_s())
        if p_task in done:
            return p_task.result()  # raises through, classified
        if not self._hedge_budget_ok():
            self.m_hedge["denied"].inc()
            return await p_task
        self._hedges_issued += 1
        self.m_hedge["issued"].inc()
        h_task = asyncio.ensure_future(self._attempt(hedge_w, batch, **kw))
        pending = {p_task, h_task}
        failures: list[BaseException] = []
        try:
            while pending:
                done, pending = await asyncio.wait(
                    pending, return_when=asyncio.FIRST_COMPLETED)
                for t in done:
                    try:
                        result = t.result()
                    except _RemoteProcessingError:
                        raise  # terminal: no point waiting on the sibling
                    except Exception as e:
                        failures.append(e)
                        continue
                    loser = primary if t is h_task else hedge_w
                    self.m_hedge["win" if t is h_task
                                 else "primary_win"].inc()
                    if t is h_task:
                        logger.info(
                            "remote_tpu[%s]: hedge to %s won the race; "
                            "cancelled the owner %s", self.name,
                            hedge_w.url, loser.url)
                    return result
            self.m_hedge["failed"].inc()
            raise failures[-1]
        finally:
            for t in (p_task, h_task):
                if not t.done():
                    t.cancel()
            # settle the cancelled loser so its inflight accounting and
            # connection teardown finish before we return
            await asyncio.gather(p_task, h_task, return_exceptions=True)

    async def _attempt_shadow(self, primary: RemoteWorker,
                              shadow_w: RemoteWorker, batch: MessageBatch,
                              **kw) -> list[MessageBatch]:
        """Dual-dispatch one sampled batch to the owner AND its ring
        successor and compare response signatures. Unlike a hedge (first
        success wins) shadow-verify needs BOTH answers: a lone corrupted
        worker produces a plausible, well-formed response that only
        disagreement can expose. On divergence neither side is trusted by
        fiat — each runs its golden probe, and whichever fails it is fenced
        as corrupt; the other's answer is delivered — where both pass
        or both fail, neither is: the batch fails over. Transport failure on
        either leg degrades to normal single delivery ("skipped")."""
        self.m_shadow["issued"].inc()
        p_task = asyncio.ensure_future(self._attempt(primary, batch, **kw))
        s_task = asyncio.ensure_future(self._attempt(shadow_w, batch, **kw))
        results = await asyncio.gather(p_task, s_task, return_exceptions=True)
        p_res, s_res = results
        if isinstance(p_res, _RemoteProcessingError):
            raise p_res  # terminal regardless of what the shadow said
        if isinstance(p_res, BaseException) and isinstance(s_res,
                                                           BaseException):
            raise p_res  # both legs died: classified failover as usual
        if isinstance(p_res, BaseException) or isinstance(s_res,
                                                          BaseException):
            # one leg lost transport — no comparison possible this round
            self.m_shadow["skipped"].inc()
            return s_res if isinstance(p_res, BaseException) else p_res
        p_sig = tuple(batch_fingerprint(b) for b in p_res)
        s_sig = tuple(batch_fingerprint(b) for b in s_res)
        if p_sig == s_sig:
            self.m_shadow["match"].inc()
            return p_res
        self.m_shadow["diverged"].inc()
        logger.error(
            "remote_tpu[%s]: shadow-verify divergence between %s and %s; "
            "running golden-probe tiebreak", self.name, primary.url,
            shadow_w.url)
        corrupt: list[RemoteWorker] = []
        passed: list[RemoteWorker] = []
        for w in (primary, shadow_w):
            try:
                rep = await self._unary(w, {"action": "integrity_probe"},
                                        timeout=self.request_timeout_s)
            except Exception as e:
                w.note_down(e)
                continue
            if int(rep.get("mismatches", 0) or 0) or int(
                    rep.get("corrupt", 0) or 0):
                self._fence_for_integrity(
                    w, "shadow-verify divergence confirmed by golden probe")
                corrupt.append(w)
            else:
                passed.append(w)
        # an answer is delivered only where its worker passed AND the other
        # was proven corrupt. A probe makes a corrupt worker repair on the
        # spot, so the tiebreak of a second batch that diverged beside the
        # first finds both sides clean AFTER the corrupt answer was given:
        # one answer is wrong and no probe says which
        if len(corrupt) == 1 and len(passed) == 1:
            return p_res if passed[0] is primary else s_res
        raise ConnectError(
            f"remote_tpu[{self.name}]: shadow-verify divergence between "
            f"{primary.url} and {shadow_w.url}, and the golden probes "
            f"({len(passed)} passed, {len(corrupt)} proven corrupt) do not "
            "single out one side: neither answer is delivered; failing over")

    async def dispatch(self, batch: MessageBatch) -> list[MessageBatch]:
        """Route one emission to the fleet; failover along the ring on
        transport errors, bounded by the retry budget; hedged against the
        ring successor when configured. Raises on remote PROCESSING errors
        (no sibling retry — see _RemoteProcessingError) and when every
        worker is down (the stream's nack path then preserves
        at-least-once).

        On a role-split fleet the plan is two-hop: prompts go to a
        prefill-capable worker chosen by prefix hash (hop 1), carrying the
        occupancy-ordered decode candidate list; the prefill worker streams
        finished KV pages to the first accepting decode worker (hop 2) and
        relays its tokens on this same infer stream."""
        decode_urls: list[str] = []
        decode_crc: list[str] = []
        if self.role_split():
            candidates = self.plan(self.routing_key(batch), role="prefill")
            targets = self.decode_targets()
            decode_urls = [w.url for w in targets]
            decode_crc = [w.url for w in targets if w.crc]
        else:
            candidates = self.plan(self.routing_key(batch))
        if not candidates:
            raise ConnectError(
                f"remote_tpu[{self.name}]: no live cluster worker "
                f"(fleet: {[w.report()['state'] for w in self.workers.values()]})")
        # fence list rides with the request: a worker (or its kv_push
        # peers) whose incarnation appears here knows it was declared dead
        # and refuses retryably instead of serving from a stale epoch
        fenced = sorted({f for w in self.workers.values() for f in w.fenced})
        # prefer the ambient stream scope (hops then parent under the
        # process span, and in-process test fleets keep tier separation);
        # fall back to the batch's own column for direct dispatcher use
        from arkflow_tpu.obs.trace import current_scope

        scope = current_scope()
        if scope is not None:
            tracer, ctx = scope.tracer, scope.ctx
        else:
            tracer = global_tracer()
            ctx = batch.trace_context() if tracer.enabled else None
        self._dispatch_count += 1
        if self._retry_tokens is not None:
            self._retry_tokens = min(
                self._retry_tokens + self._retry_budget["ratio"],
                float(self._retry_budget["burst"]))
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        kw = dict(ctx=ctx, tracer=tracer, decode_urls=decode_urls,
                  decode_crc=decode_crc, fenced=fenced,
                  timeout_s=self._hop_timeout(batch))
        last_exc: Optional[BaseException] = None
        i, n = 0, len(candidates)
        # deterministic every-Nth sampling (no RNG: fraction 1.0 must
        # shadow EVERY batch, and the soak's accounting depends on it);
        # role-split fleets skip it — prefill/decode answers aren't
        # comparable across the two-hop path
        do_shadow = False
        if self._shadow is not None and not self.role_split():
            self._shadow_count += 1
            if self._shadow_count % self._shadow_every == 0:
                if n >= 2:
                    do_shadow = True
                else:
                    self.m_shadow["skipped"].inc()
        while i < n:
            if i > 0:
                if self._retry_tokens is not None:
                    if self._retry_tokens < 1.0:
                        self.m_retry_shed.inc()
                        raise RetryBudgetExhausted(
                            f"remote_tpu[{self.name}]: ring retry budget "
                            f"exhausted after {i} attempt(s) (ratio "
                            f"{self._retry_budget['ratio']}, last: "
                            f"{last_exc}); shedding instead of amplifying "
                            "a fleet-wide brownout",
                            retry_after_s=self.heartbeat_s)
                    self._retry_tokens -= 1.0
                self.m_retries.inc()
            w = candidates[i]
            shadow_w = (candidates[i + 1]
                        if do_shadow and i + 1 < n else None)
            hedge_w = (candidates[i + 1]
                       if shadow_w is None and self._hedge is not None
                       and i + 1 < n else None)
            try:
                if shadow_w is not None:
                    out = await self._attempt_shadow(w, shadow_w, batch,
                                                     **kw)
                elif hedge_w is not None:
                    out = await self._attempt_hedged(w, hedge_w, batch, **kw)
                else:
                    out = await self._attempt(w, batch, **kw)
            except _RemoteProcessingError as e:
                raise ProcessError(
                    f"cluster worker {w.url} failed the batch: {e}") from e
            except (_WorkerDraining, ConnectError, ConnectionError, OSError,
                    asyncio.IncompleteReadError, asyncio.TimeoutError,
                    ReadError) as e:
                last_exc = (ConnectError(f"worker {w.url} draining")
                            if isinstance(e, _WorkerDraining) else e)
                # a shadowed/hedged round consumed two candidates; skip both
                i += (2 if (hedge_w is not None or shadow_w is not None)
                      else 1)
                continue
            else:
                self._note_latency(loop.time() - t0)
                return out
        raise ConnectError(
            f"remote_tpu[{self.name}]: all {n} candidate "
            f"workers failed for this batch (last: {last_exc}); leaving it "
            "to the redelivery path")

    async def _infer_on(self, w: RemoteWorker, batch: MessageBatch, *,
                        ctx: Optional[TraceContext] = None,
                        tracer: Optional[Tracer] = None,
                        decode_urls: Sequence[str] = (),
                        decode_crc: Sequence[str] = (),
                        fenced: Sequence[str] = (),
                        timeout_s: Optional[float] = None) -> list[MessageBatch]:
        import time as _time

        from arkflow_tpu.obs.trace import _new_id

        if timeout_s is None:
            timeout_s = self.request_timeout_s
        use_crc = self.crc and w.crc
        # per-hop tracing: the hop span's id is minted BEFORE the call so
        # the worker can parent its spans under it; serialize / transport /
        # deserialize are ingest-side children, remote_* spans arrive in the
        # worker's TRACE_TAG frame. A retried dispatch records one hop span
        # per attempted worker.
        hop_id = _new_id() if ctx is not None else ""
        t_hop = _time.perf_counter()
        hop_ok = False
        reader, writer = await self._open(w)
        try:
            req: dict = {"action": "infer"}
            if decode_urls:
                # two-hop disagg plan: the prefill worker pushes finished
                # KV pages to these, in this occupancy order (skipping
                # itself — a 'both' worker just decodes locally)
                req["decode_workers"] = [u for u in decode_urls if u != w.url]
                if decode_crc:
                    # subset of decode_workers that negotiated crc framing,
                    # so the prefill worker protects its kv_push slabs too
                    req["decode_crc"] = [u for u in decode_crc if u != w.url]
            if fenced:
                req["fenced"] = list(fenced)
            if ctx is not None:
                req["trace"] = ctx.with_parent(hop_id).to_dict()
            t0 = _time.perf_counter()
            ipc = batch_to_ipc(batch.record_batch)
            if tracer is not None:
                tracer.record(ctx, "flight_serialize",
                              _time.perf_counter() - t0, parent_id=hop_id)
            t_send = _time.perf_counter()
            await _send_frame(writer, json.dumps(req).encode(), crc=use_crc)
            await _send_frame(writer, ipc, crc=use_crc)
            raw = await asyncio.wait_for(
                _read_frame(reader, self.max_frame, what="infer status"),
                timeout_s)
            if raw is None:
                raise ConnectError(f"worker {w.url} closed before a status")
            if tracer is not None:
                # send -> status round trip: wire + the worker's accept path
                # (its own decode/queue/step costs arrive as remote_* spans)
                tracer.record(ctx, "flight_transport",
                              _time.perf_counter() - t_send, parent_id=hop_id)
            try:
                status = json.loads(raw.decode())
            except (UnicodeDecodeError, ValueError) as e:
                # a status frame that isn't JSON is wire damage from a peer
                # without crc trailers (negotiated-off, or a corrupted
                # register) — fail over loudly, don't quarantine the batch
                raise FrameIntegrityError(
                    f"undecodable infer status frame from {w.url}: "
                    f"{e!r}") from e
            inc = status.get("incarnation")
            if isinstance(inc, str) and w.is_fenced(inc):
                # a partition-healed zombie answered from its fenced epoch:
                # its caches and occupancy are stale — reject and fail over
                self.m_fenced.inc()
                raise ConnectError(
                    f"worker {w.url} answered from fenced incarnation "
                    f"{inc}; rejecting the zombie's response")
            if not status.get("ok"):
                if status.get("reason") == "frame_integrity":
                    # OUR request arrived corrupted; the worker refused it
                    # unprocessed — surface as the same loud integrity error
                    # a corrupted response raises (failover, counted, and no
                    # draining/death bookkeeping for a healthy worker)
                    raise FrameIntegrityError(status.get("error"))
                if status.get("retryable"):
                    raise _WorkerDraining(status.get("error"))
                raise _RemoteProcessingError(status.get("error"))
            results: list[MessageBatch] = []
            deser_s = 0.0
            while True:
                frame = await asyncio.wait_for(
                    _read_frame(reader, self.max_frame, what="infer frame"),
                    timeout_s)
                if frame is None:
                    if tracer is not None:
                        tracer.record(ctx, "flight_deserialize", deser_s,
                                      parent_id=hop_id)
                    hop_ok = True
                    return results
                tag, payload = frame[:1], frame[1:]
                if tag == ERROR_TAG:
                    raise _RemoteProcessingError(
                        json.loads(payload.decode()).get("error"))
                if tag == TRACE_TAG:
                    if tracer is not None:
                        try:
                            tracer.adopt_spans(
                                ctx, json.loads(payload.decode()).get("spans") or [])
                        except (ValueError, AttributeError, TypeError):
                            # a mangled trace frame must never fail a batch
                            # whose results already streamed fine
                            logger.warning("malformed trace frame from %s", w.url)
                    continue
                t_d = _time.perf_counter()
                for rb in ipc_to_batches(payload):
                    results.append(MessageBatch(rb))
                deser_s += _time.perf_counter() - t_d
        finally:
            if tracer is not None and ctx is not None:
                # EVERY attempt roots its subtree — a failed hop's
                # flight/worker children must not dangle, and the failure
                # itself is worth seeing in the tree
                tracer.record(
                    ctx, "cluster_hop", _time.perf_counter() - t_hop,
                    span_id=hop_id,
                    attrs={"worker": w.url,
                           **({} if hop_ok else {"error": True})})
            try:
                writer.close()
            except Exception:
                pass

    # -- fleet lifecycle (drain / swap legs / elastic membership) ----------

    def add_worker(self, url: str) -> RemoteWorker:
        """Adopt a worker into the routing table and hash ring at runtime
        (fleet scale-out). Idempotent on url. Virtual-node hashing means
        only the keys that land on the newcomer's points remap — existing
        workers' response/prefix caches stay warm."""
        existing = self.workers.get(url)
        if existing is not None:
            return existing
        parse_remote_url(url)  # raises ConfigError on malformed urls
        w = RemoteWorker(url, self.name)
        self.workers[url] = w
        self.ring.add(url)
        logger.info("remote_tpu[%s]: worker %s added to the ring (fleet "
                    "size %d)", self.name, url, len(self.workers))
        return w

    def remove_worker(self, url: str) -> None:
        """Retire a worker from the table and ring (fleet scale-in or a
        departed spawn). Its key ranges fall to the ring successors; no-op
        for unknown urls."""
        if self.workers.pop(url, None) is None:
            return
        self.ring.remove(url)
        logger.info("remote_tpu[%s]: worker %s removed from the ring "
                    "(fleet size %d)", self.name, url, len(self.workers))

    async def set_drain(self, w: RemoteWorker, drain: bool) -> dict:
        rep = await self._unary(w, {"action": "drain", "drain": drain})
        if rep.get("ok"):
            w.draining = drain
        return rep

    async def wait_drained(self, w: RemoteWorker, timeout_s: float) -> None:
        """Poll the worker until its in-flight steps finished."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout_s
        while True:
            rep = await self._unary(w, {"action": "heartbeat"})
            if int(rep.get("inflight", 0)) == 0:
                return
            if loop.time() >= deadline:
                raise SwapError(
                    f"worker {w.url} still has {rep.get('inflight')} "
                    f"in-flight steps after {timeout_s:.1f}s drain budget")
            await asyncio.sleep(min(0.1, timeout_s / 10.0))

    async def swap_on(self, w: RemoteWorker, checkpoint: str) -> dict:
        # restore+canary+probe can take a while: give it the drain budget
        # on top of the normal request timeout
        return await self._unary(w, {"action": "swap", "checkpoint": checkpoint},
                                 timeout=max(self.request_timeout_s, 300.0))

    # -- introspection -----------------------------------------------------

    def report(self) -> dict:
        out = {
            "workers": {u: w.report() for u, w in sorted(self.workers.items())},
            "alive": sum(1 for w in self.workers.values() if w.alive),
            "route_key": self.route_key,
            "retries": self.m_retries.value,
            "spills": self.m_spills.value,
            "fenced_rejections": self.m_fenced.value,
            "frame_errors": self.m_frame_errors.value,
        }
        if self._hedge is not None:
            out["hedge"] = {
                "dispatches": self._dispatch_count,
                "issued": self._hedges_issued,
                "outcomes": {k: c.value for k, c in self.m_hedge.items()},
                "p99_ewma_s": self._p99_ewma,
            }
        if self._retry_tokens is not None:
            out["retry_budget"] = {
                "tokens": self._retry_tokens,
                "shed": self.m_retry_shed.value,
            }
        if self._shadow is not None:
            out["shadow_verify"] = {
                "fraction": self._shadow["fraction"],
                "every": self._shadow_every,
                "outcomes": {k: c.value for k, c in self.m_shadow.items()},
            }
        out["integrity_fences"] = self.m_integrity_fence.value
        return out

    def health_reports(self) -> list[dict]:
        """Engine /health and /readiness aggregation: one report per worker
        in the shape the engine's runner walk expects (``state`` keys to
        the readiness check — an all-dead fleet flips the replica 503)."""
        return [w.report() for w in sorted(self.workers.values(),
                                           key=lambda w: w.url)]


class ClusterSwapper:
    """Fleet-wide rolling hot-swap: ``POST /admin/swap`` on the ingest
    engine reaches this via the processor's ``swapper`` attribute and rolls
    worker-by-worker — drain (the ring serves on N-1), swap via the
    worker's OWN canary/probe/rollback manager, undrain. A failed worker
    swap stops the roll: its own manager already rolled that worker back,
    committed workers keep the new version, and the raised SwapError names
    both sets so the operator can re-POST either checkpoint."""

    def __init__(self, dispatcher: ClusterDispatcher,
                 drain_timeout_s: float = 30.0):
        self.dispatcher = dispatcher
        self.drain_timeout_s = drain_timeout_s
        self._commit_hooks: list = []
        self._swapping = False
        self._last: dict = {}

    def add_commit_hook(self, hook) -> None:
        """Runs when any worker flipped (the PR-10 cache discipline: a
        flipped worker may have answered live traffic with new weights, so
        the ingest response cache must epoch-flush even on a partial roll)."""
        self._commit_hooks.append(hook)

    def _run_commit_hooks(self) -> None:
        for hook in self._commit_hooks:
            try:
                hook()
            except Exception:
                logger.exception("cluster swap commit hook failed")

    async def swap(self, checkpoint: str) -> dict:
        if self._swapping:
            raise SwapError("a cluster swap is already in progress")
        live = [w for w in self.dispatcher.workers.values() if w.alive]
        if not live:
            raise SwapError("no live cluster workers to swap")
        self._swapping = True
        committed: list[str] = []
        try:
            for w in sorted(live, key=lambda w: w.url):
                try:
                    await self.dispatcher.set_drain(w, True)
                    await self.dispatcher.wait_drained(w, self.drain_timeout_s)
                    rep = await self.dispatcher.swap_on(w, checkpoint)
                except SwapError:
                    raise
                except Exception as e:
                    raise SwapError(
                        f"cluster swap aborted at worker {w.url} "
                        f"({type(e).__name__}: {e}); committed: "
                        f"{committed or 'none'}") from e
                finally:
                    try:
                        await self.dispatcher.set_drain(w, False)
                    except Exception:
                        logger.exception("undrain of %s failed", w.url)
                if not rep.get("ok"):
                    raise SwapError(
                        f"worker {w.url} rejected the swap: "
                        f"{rep.get('error') or rep.get('results')}; that "
                        f"worker rolled itself back; committed workers "
                        f"({committed or 'none'}) keep the new version — "
                        "re-POST the previous checkpoint to converge back")
                committed.append(w.url)
            self._last = {"checkpoint": checkpoint, "committed": committed}
            return {"cluster": True, "committed": committed,
                    "workers": len(committed)}
        finally:
            self._swapping = False
            if committed:
                # even a partial roll changed what some answers were
                # computed with — flush the ingest-side response cache
                self._run_commit_hooks()

    def report(self) -> dict:
        return {"cluster": True, "swapping": self._swapping,
                "last": self._last or None}


# ---------------------------------------------------------------------------
# the remote_tpu processor (ingest dispatch stage)
# ---------------------------------------------------------------------------


class _ClusterRunnerView:
    """Adapter giving the engine's runner-health walk (`proc.runner
    .health_report()`) the per-worker fleet view."""

    def __init__(self, dispatcher: ClusterDispatcher):
        self._dispatcher = dispatcher

    def health_report(self) -> list[dict]:
        return self._dispatcher.health_reports()


class RemoteTpuProcessor:
    """Ingest-tier dispatch stage: ships each emission to the device tier
    over the flight plane, with hash-affine routing and failover.

    Composes with everything the ingest stream already does — admission /
    AIMD / fairness run before it, coalescing buffers feed it, and an
    optional ingest-side response cache short-circuits duplicates before
    they pay the network + device (config ``response_cache``, same
    semantics as ``tpu_inference``'s)."""

    def __init__(self, dispatcher: ClusterDispatcher, *, response_cache=None,
                 drain_timeout_s: float = 30.0, fleet=None):
        self.dispatcher = dispatcher
        self.cache = response_cache
        self.swapper = ClusterSwapper(dispatcher, drain_timeout_s)
        if self.cache is not None:
            self.swapper.add_commit_hook(self.cache.bump_epoch)
            # integrity satellite: a worker fenced for corruption may have
            # poisoned cached answers — epoch-flush so a byte-identical
            # duplicate recomputes on a healthy worker
            dispatcher.integrity_hooks.append(self.cache.bump_epoch)
        #: elastic-fleet controller (runtime/fleet.py); None = static fleet
        self.fleet = fleet
        #: engine /health + /readiness integration (runner-shaped view)
        self.runner = _ClusterRunnerView(dispatcher)

    def attach_overload_controller(self, controller) -> None:
        """Stream hook: align the cache's tenant-hit label capping with the
        admission controller (same contract as tpu_inference)."""
        if self.cache is not None:
            self.cache.set_tenant_policy(controller.cfg.tenants)

    def cluster_report(self) -> dict:
        """Fleet snapshot for the engine's /health payload (including the
        controller's per-event decision log when elastic)."""
        rep = self.dispatcher.report()
        if self.fleet is not None:
            rep["fleet"] = self.fleet.report()
        return rep

    async def connect(self) -> None:
        await self.dispatcher.start()
        if self.fleet is not None:
            await self.fleet.start()

    async def close(self) -> None:
        if self.fleet is not None:
            await self.fleet.close()
        await self.dispatcher.close()

    async def process(self, batch: MessageBatch) -> list[MessageBatch]:
        if batch.num_rows == 0:
            return []
        if self.cache is not None:
            key = batch_fingerprint(batch)
            rbs = await self.cache.get_or_compute(
                key, lambda: self._dispatch_ipc(batch), tenant=batch.tenant())
            # cached value holds Arrow record batches (bitwise-identical
            # responses); the wrapper is rebuilt per delivery
            return [MessageBatch(rb) for rb in rbs]
        return await self.dispatcher.dispatch(batch)

    async def _dispatch_ipc(self, batch: MessageBatch):
        return [b.record_batch for b in await self.dispatcher.dispatch(batch)]


def parse_remote_tpu_config(config: Mapping) -> dict:
    """Validate ``remote_tpu`` processor config -> dispatcher kwargs + the
    drain timeout. Pure parse (no sockets, no metric series) so config.py
    can run it at ``--validate`` time."""
    from arkflow_tpu.runtime.respcache import parse_response_cache_config
    from arkflow_tpu.utils.duration import parse_duration

    workers = config.get("workers")
    if not isinstance(workers, list) or not workers:
        raise ConfigError("remote_tpu needs a non-empty 'workers' list of "
                          "arkflow://host:port URLs")
    for u in workers:
        if not isinstance(u, str):
            raise ConfigError(f"remote_tpu.workers entries must be strings, "
                              f"got {u!r}")
        parse_remote_url(u)  # raises ConfigError with the offending URL
    if len(set(workers)) != len(workers):
        raise ConfigError(f"remote_tpu.workers must be distinct, got {workers}")
    route_key = config.get("route_key", "fingerprint")
    if route_key not in ROUTE_KEYS:
        raise ConfigError(f"remote_tpu.route_key must be one of "
                          f"{ROUTE_KEYS}, got {route_key!r}")
    out: dict = {"workers": [str(u) for u in workers],
                 "route_key": str(route_key)}

    def _int(key: str, default: int, minimum: int) -> int:
        v = config.get(key, default)
        if isinstance(v, bool) or not isinstance(v, int) or v < minimum:
            raise ConfigError(
                f"remote_tpu.{key} must be an int >= {minimum}, got {v!r}")
        return v

    def _dur(key: str, default: str) -> float:
        v = config.get(key, default)
        try:
            s = parse_duration(v)
        except (ConfigError, TypeError, ValueError) as e:
            raise ConfigError(f"remote_tpu.{key} invalid: {e}") from e
        if s <= 0:
            raise ConfigError(f"remote_tpu.{key} must be > 0, got {v!r}")
        return s

    out["prefix_bytes"] = _int("prefix_bytes", 64, 1)
    out["virtual_nodes"] = _int("virtual_nodes", 64, 1)
    out["max_frame"] = _int("max_frame", DEFAULT_MAX_FRAME, 1024)
    out["decode_candidates"] = _int("decode_candidates", 3, 1)
    out["heartbeat_s"] = _dur("heartbeat", "2s")
    out["request_timeout_s"] = _dur("request_timeout", "60s")
    out["connect_timeout_s"] = _dur("connect_timeout", "5s")
    out["drain_timeout_s"] = _dur("drain_timeout", "30s")
    # staleness bound: default 5 heartbeat periods (floor 10s); must exceed
    # one period or every member would flap dead between beats
    if config.get("heartbeat_timeout") is not None:
        ht = _dur("heartbeat_timeout", "10s")
    else:
        ht = max(5.0 * out["heartbeat_s"], 10.0)
    if ht <= out["heartbeat_s"]:
        raise ConfigError(
            f"remote_tpu.heartbeat_timeout ({ht}s) must exceed the "
            f"heartbeat period ({out['heartbeat_s']}s)")
    out["heartbeat_timeout_s"] = ht
    tf = config.get("text_field")
    if tf is not None and not isinstance(tf, str):
        raise ConfigError(f"remote_tpu.text_field must be a string, got {tf!r}")
    out["text_field"] = tf
    crc = config.get("crc", True)
    if not isinstance(crc, bool):
        raise ConfigError(f"remote_tpu.crc must be a bool, got {crc!r}")
    out["crc"] = crc
    out["io_deadline_floor_s"] = _dur("io_deadline_floor", "100ms")

    hedge = config.get("hedge")
    if hedge is not None:
        if not isinstance(hedge, Mapping):
            raise ConfigError(
                f"remote_tpu.hedge must be a mapping, got {hedge!r}")
        unknown = set(hedge) - {"delay", "max_fraction", "burst", "min_delay"}
        if unknown:
            raise ConfigError(
                f"remote_tpu.hedge: unknown keys {sorted(unknown)} "
                "(allowed: delay, max_fraction, burst, min_delay)")
        h: dict = {}
        delay = hedge.get("delay", "auto")
        if delay == "auto":
            h["delay_s"] = None  # p99-EWMA of recent dispatch latency
        else:
            try:
                d = parse_duration(delay)
            except (ConfigError, TypeError, ValueError) as e:
                raise ConfigError(
                    f"remote_tpu.hedge.delay must be 'auto' or a "
                    f"duration: {e}") from e
            if d <= 0:
                raise ConfigError(
                    f"remote_tpu.hedge.delay must be > 0, got {delay!r}")
            h["delay_s"] = d
        frac = hedge.get("max_fraction", 0.1)
        if isinstance(frac, bool) or not isinstance(frac, (int, float)) \
                or not 0.0 < frac <= 1.0:
            raise ConfigError(
                f"remote_tpu.hedge.max_fraction must be in (0, 1], "
                f"got {frac!r}")
        h["max_fraction"] = float(frac)
        burst = hedge.get("burst", 4)
        if isinstance(burst, bool) or not isinstance(burst, int) or burst < 0:
            raise ConfigError(
                f"remote_tpu.hedge.burst must be an int >= 0, got {burst!r}")
        h["burst"] = burst
        md = hedge.get("min_delay", "10ms")
        try:
            mds = parse_duration(md)
        except (ConfigError, TypeError, ValueError) as e:
            raise ConfigError(f"remote_tpu.hedge.min_delay invalid: {e}") from e
        if mds <= 0:
            raise ConfigError(
                f"remote_tpu.hedge.min_delay must be > 0, got {md!r}")
        h["min_delay_s"] = mds
        out["hedge"] = h
    else:
        out["hedge"] = None

    rb = config.get("retry_budget")
    if rb is not None:
        if not isinstance(rb, Mapping):
            raise ConfigError(
                f"remote_tpu.retry_budget must be a mapping, got {rb!r}")
        unknown = set(rb) - {"ratio", "burst"}
        if unknown:
            raise ConfigError(
                f"remote_tpu.retry_budget: unknown keys {sorted(unknown)} "
                "(allowed: ratio, burst)")
        ratio = rb.get("ratio", 0.5)
        if isinstance(ratio, bool) or not isinstance(ratio, (int, float)) \
                or ratio <= 0:
            raise ConfigError(
                f"remote_tpu.retry_budget.ratio must be > 0, got {ratio!r}")
        burst = rb.get("burst", 8)
        if isinstance(burst, bool) or not isinstance(burst, int) or burst < 1:
            raise ConfigError(
                f"remote_tpu.retry_budget.burst must be an int >= 1, "
                f"got {burst!r}")
        out["retry_budget"] = {"ratio": float(ratio), "burst": burst}
    else:
        out["retry_budget"] = None

    sv = config.get("shadow_verify")
    if sv is not None:
        if not isinstance(sv, Mapping):
            raise ConfigError(
                f"remote_tpu.shadow_verify must be a mapping, got {sv!r}")
        unknown = set(sv) - {"fraction"}
        if unknown:
            raise ConfigError(
                f"remote_tpu.shadow_verify: unknown keys {sorted(unknown)} "
                "(allowed: fraction)")
        frac = sv.get("fraction", 0.05)
        if isinstance(frac, bool) or not isinstance(frac, (int, float)) \
                or not 0.0 < frac <= 1.0:
            raise ConfigError(
                f"remote_tpu.shadow_verify.fraction must be in (0, 1], "
                f"got {frac!r}")
        out["shadow_verify"] = {"fraction": float(frac)}
    else:
        out["shadow_verify"] = None
    parse_response_cache_config(config.get("response_cache"))
    # elastic-fleet block (runtime/fleet.py owns the parse rules); pure —
    # config.py reaches this through fault.inner chains at --validate time
    from arkflow_tpu.runtime.fleet import parse_fleet_config

    out["fleet"] = parse_fleet_config(
        config.get("fleet"), static_workers=len(out["workers"]))
    return out


def build_remote_tpu(config: dict, resource: Resource) -> RemoteTpuProcessor:
    """Builder for ``type: remote_tpu`` (registered from
    plugins/processor/remote_tpu.py)."""
    from arkflow_tpu.runtime.respcache import build_response_cache

    parsed = parse_remote_tpu_config(config)
    name = str(config.get("name") or "cluster")
    dispatcher = ClusterDispatcher(
        parsed["workers"], name=name, route_key=parsed["route_key"],
        prefix_bytes=parsed["prefix_bytes"], text_field=parsed["text_field"],
        virtual_nodes=parsed["virtual_nodes"],
        heartbeat_s=parsed["heartbeat_s"],
        request_timeout_s=parsed["request_timeout_s"],
        connect_timeout_s=parsed["connect_timeout_s"],
        heartbeat_timeout_s=parsed["heartbeat_timeout_s"],
        max_frame=parsed["max_frame"],
        decode_candidates=parsed["decode_candidates"],
        crc=parsed["crc"],
        io_deadline_floor_s=parsed["io_deadline_floor_s"],
        hedge=parsed["hedge"],
        retry_budget=parsed["retry_budget"],
        shadow_verify=parsed["shadow_verify"])
    cache = build_response_cache(config.get("response_cache"), name=name)
    fleet = None
    fleet_cfg = parsed["fleet"]
    if fleet_cfg is not None:
        from arkflow_tpu.runtime.fleet import (
            FleetController,
            SubprocessSpawner,
        )

        spawner = None
        if fleet_cfg.template is not None:
            spawner = SubprocessSpawner(fleet_cfg.template,
                                        host=fleet_cfg.spawn_host)
        fleet = FleetController(dispatcher, spawner, fleet_cfg, name=name)
    return RemoteTpuProcessor(dispatcher, response_cache=cache,
                              drain_timeout_s=parsed["drain_timeout_s"],
                              fleet=fleet)
