"""Remote scan/query execution over Arrow IPC — the Ballista-analog tier.

The reference lets file/DB scans execute on a remote DataFusion cluster
via Ballista (Arrow Flight under the hood; ref input/file.rs:396-397,
input/sql.rs:313-315: ``SessionContext::remote(url)``). This module is the
same capability re-built on the engine's own pieces: a worker process runs
the scan + SQL where the data lives and streams Arrow record batches back;
only filtered/projected results cross the network.

Wire protocol (``arkflow://host:port``):

- request:  [u32 len][JSON] — {"action": "scan", "path": ..., "format": ...,
            "query": "SELECT ... FROM flow", "batch_rows": N}
            or {"action": "query", "sql": ..., "tables": {name: <ipc bytes b64>}}
- response: [u32 len][JSON status] — {"ok": true} | {"ok": false, "error": ...}
            then, when ok, a sequence of tagged frames
            [u32 len][tag u8][payload]: tag 0x00 = Arrow IPC stream chunk
            (schema + one batch, self-contained), tag 0x01 = mid-stream
            error JSON; a zero-length frame ends the stream. Tagging means
            an error after streaming began is still unambiguous, and the
            worker never buffers the whole result.

Run a worker with ``python -m arkflow_tpu --worker --port 50051``; point a
file/sql input at it with ``remote_url: arkflow://host:50051``.
"""

from __future__ import annotations

import asyncio
import base64
import json
import logging
import struct
import zlib
from typing import AsyncIterator, Optional

import pyarrow as pa

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.errors import (ConfigError, ConnectError,
                                FrameIntegrityError, ReadError)

logger = logging.getLogger("arkflow.flight")


def batch_to_ipc(rb: pa.RecordBatch) -> pa.Buffer:
    """One record batch as a self-contained IPC stream, returned as the
    Arrow buffer itself — NOT ``bytes``. ``.to_pybytes()`` here used to copy
    every payload a second time before the transport copied it onto the
    wire; a ``pa.Buffer`` supports the buffer protocol (``len``,
    ``memoryview``, pickle), so every consumer — flight frames, the
    process-pool submit — hands it on zero-copy. Callers that truly need
    ``bytes`` wrap with ``bytes(...)`` explicitly."""
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, rb.schema) as w:
        w.write_batch(rb)
    return sink.getvalue()


def ipc_to_batches(data) -> list[pa.RecordBatch]:
    """Inverse of ``batch_to_ipc``; accepts bytes or any buffer-protocol
    payload (memoryview of a wire frame, a ``pa.Buffer``)."""
    with pa.ipc.open_stream(pa.BufferReader(data)) as r:
        return list(r)


#: Default cap on a single wire frame. The u32 length header could name
#: anything up to 4 GiB and ``readexactly`` would dutifully buffer it all —
#: one malformed (or malicious) frame must not be able to balloon a worker
#: or client to gigabytes. The default keeps the historical 1 GiB bound
#: (large-row-group scans that worked keep working); tighten it per
#: endpoint via ``max_frame`` on FlightWorker/FlightClient, the remote
#: inputs' ``max_frame`` config key, or ``--max-frame`` on the CLI.
DEFAULT_MAX_FRAME = 1 << 30

#: Frame-integrity bit. Frame lengths are capped at 1 GiB (2**30), so the
#: top bit of the u32 length header is free to mark a frame that carries a
#: 4-byte crc32 trailer after the payload. The bit makes integrity
#: self-describing per frame: readers verify whenever the bit is set and
#: need no out-of-band negotiation, while writers only set it for peers
#: that advertised the capability at ``register`` — an old reader facing a
#: crc frame fails loudly on the oversized length rather than silently
#: mis-parsing, and an old writer's plain frames pass through unchanged.
CRC_BIT = 1 << 31


def _crc32(payload) -> int:
    if isinstance(payload, (bytes, bytearray, memoryview)):
        return zlib.crc32(payload)
    return zlib.crc32(memoryview(payload))


async def _send_frame(writer: asyncio.StreamWriter, payload,
                      crc: bool = False) -> None:
    """Write one length-prefixed frame. ``payload`` may be ``bytes`` or any
    buffer-protocol object (``pa.Buffer`` from ``batch_to_ipc`` rides
    through untouched — the only copy is the kernel's). With ``crc`` the
    frame carries a crc32 trailer and sets ``CRC_BIT`` in the header."""
    if isinstance(payload, (bytes, bytearray)):
        n = len(payload)
        hdr = struct.pack(">I", n | CRC_BIT) if crc else struct.pack(">I", n)
        writer.write(hdr + payload)
    else:
        view = memoryview(payload)
        n = view.nbytes
        writer.write(struct.pack(">I", n | CRC_BIT) if crc else struct.pack(">I", n))
        writer.write(view)
    if crc:
        writer.write(struct.pack(">I", _crc32(payload)))
    await writer.drain()


DATA_TAG = b"\x00"
ERROR_TAG = b"\x01"
#: cluster tracing (obs/trace.py): a worker's exported span list rides back
#: to the ingest tier as one tagged JSON frame before the end-of-stream
#: marker, so a batch's trace stitches across the flight hop. Absent when
#: the request carried no trace context — old/new peers interoperate.
TRACE_TAG = b"\x02"


async def _send_data(writer: asyncio.StreamWriter, payload,
                     crc: bool = False) -> None:
    """One tagged data frame; like ``_send_frame``, the payload may be a
    buffer-protocol object — tag and length go out as one small header
    write, the Arrow buffer follows without an intermediate concat copy.
    The crc32 trailer covers tag + payload."""
    if isinstance(payload, (bytes, bytearray)):
        n = len(payload) + 1
        hdr = struct.pack(">I", n | CRC_BIT) if crc else struct.pack(">I", n)
        writer.write(hdr + DATA_TAG + payload)
    else:
        view = memoryview(payload)
        n = view.nbytes + 1
        writer.write((struct.pack(">I", n | CRC_BIT) if crc
                      else struct.pack(">I", n)) + DATA_TAG)
        writer.write(view)
    if crc:
        writer.write(struct.pack(">I", zlib.crc32(
            memoryview(payload), zlib.crc32(DATA_TAG))))
    await writer.drain()


async def _send_stream_error(writer: asyncio.StreamWriter, err: str,
                             crc: bool = False) -> None:
    await _send_frame(writer, ERROR_TAG + json.dumps({"error": err}).encode(),
                      crc=crc)


async def _end_stream(writer: asyncio.StreamWriter) -> None:
    # the zero-length end marker is always plain: there is no payload to
    # protect, and old peers must keep recognising it
    writer.write(struct.pack(">I", 0))
    await writer.drain()


async def _read_frame(reader: asyncio.StreamReader,
                      limit: int = DEFAULT_MAX_FRAME,
                      what: str = "flight") -> Optional[bytes]:
    """One length-prefixed frame, or None for the zero-length end marker.

    The length header is untrusted input: a frame above ``limit`` raises a
    loud ``ConnectError`` *before* any payload byte is buffered, on both the
    client and worker sides (both read through here).

    Frames with ``CRC_BIT`` set carry a crc32 trailer; a mismatch raises a
    ``FrameIntegrityError`` naming the frame class (``what``) — corruption
    is loud, never silent garbage. Whether the peer spoke crc is recorded on
    the reader as ``_arkflow_crc`` so servers can echo the negotiation."""
    hdr = await reader.readexactly(4)
    (word,) = struct.unpack(">I", hdr)
    has_crc = bool(word & CRC_BIT)
    n = word & ~CRC_BIT
    if n == 0:
        if has_crc:
            # a crc-marked EMPTY frame is never sent (the end marker is
            # always plain): this word is either corruption or an old peer
            # announcing a >= 2 GiB length, which no cap admits
            raise ConnectError(
                f"flight frame header {word:#010x} is invalid: the end "
                f"marker is never crc-marked, and read as a length it "
                f"would exceed any max_frame cap (limit here: {limit} "
                "bytes)")
        return None
    if n > limit:
        raise ConnectError(
            f"flight frame of {n} bytes exceeds the configured max_frame "
            f"cap of {limit} bytes (raise max_frame / --max-frame if this "
            "payload is legitimate)")
    payload = await reader.readexactly(n)
    # record the negotiation BEFORE validating: the peer provably spoke crc
    # the moment the bit is seen, and a server answering a corrupted request
    # must protect its error reply too (else that reply is the one frame a
    # corrupting link can silently garble)
    reader._arkflow_crc = has_crc  # type: ignore[attr-defined]
    if has_crc:
        (want,) = struct.unpack(">I", await reader.readexactly(4))
        got = zlib.crc32(payload)
        if got != want:
            raise FrameIntegrityError(
                f"crc32 mismatch on {what} frame: {n}-byte payload hashed to "
                f"{got:#010x}, peer sent {want:#010x} — frame corrupted in "
                "transit, refusing to decode it")
    return payload


def parse_remote_url(url: str) -> tuple[str, int]:
    if not url.startswith("arkflow://"):
        raise ConfigError(f"remote_url must be arkflow://host:port (got {url!r})")
    rest = url[len("arkflow://"):]
    host, _, port = rest.partition(":")
    try:
        port_n = int(port)
    except ValueError:
        port_n = 0
    if not host or not 0 < port_n < 65536:
        raise ConfigError(f"remote_url must be arkflow://host:port (got {url!r})")
    return host, port_n


class FlightWorker:
    """The remote executor: scans files / runs SQL next to the data."""

    def __init__(self, host: str = "0.0.0.0", port: int = 50051,
                 allow_paths: Optional[list[str]] = None,
                 max_frame: int = DEFAULT_MAX_FRAME):
        self.host = host
        self.port = port
        #: optional allowlist of path prefixes workers may scan
        self.allow_paths = allow_paths
        #: cap on a single inbound frame (the u32 header is untrusted)
        self.max_frame = int(max_frame)
        self._server: Optional[asyncio.base_events.Server] = None

    async def start(self) -> None:
        self._server = await asyncio.start_server(self._serve, self.host, self.port)
        self.port = self._server.sockets[0].getsockname()[1]
        logger.info("flight worker listening on %s:%d", self.host, self.port)

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        async with self._server:
            await self._server.serve_forever()

    async def stop(self) -> None:
        if self._server is not None:
            self._server.close()
            try:
                await asyncio.wait_for(self._server.wait_closed(), 1.0)
            except asyncio.TimeoutError:
                pass

    def _check_path(self, path: str) -> None:
        if self.allow_paths is None:
            return
        from pathlib import Path

        resolved = Path(path).resolve()
        # component-wise containment: /database must NOT match --allow-path /data
        ok = any(resolved.is_relative_to(Path(p).resolve()) for p in self.allow_paths)
        if not ok:
            raise ConfigError(f"path {path!r} outside worker allow_paths")

    async def _serve(self, reader, writer) -> None:
        try:
            raw = await _read_frame(reader, self.max_frame)
            req = json.loads(raw.decode())
            action = req.get("action")
            if action == "scan":
                await self._do_scan(req, writer)
            elif action == "query":
                await self._do_query(req, writer)
            elif action == "sqlite":
                await self._do_sqlite(req, writer)
            else:
                await _send_frame(writer, json.dumps(
                    {"ok": False, "error": f"unknown action {action!r}"}).encode())
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        except Exception as e:
            try:
                if getattr(writer, "_arkflow_streaming", False):
                    await _send_stream_error(writer, repr(e)[:500])
                    await _end_stream(writer)
                else:
                    await _send_frame(writer, json.dumps(
                        {"ok": False, "error": repr(e)[:500]}).encode())
            except Exception:
                pass
        finally:
            try:
                writer.close()
            except Exception:
                pass

    async def _do_scan(self, req: dict, writer) -> None:
        """Scan a file local to the worker, optionally SQL-filter, stream."""
        from pathlib import Path

        from arkflow_tpu.plugins.input.file import _infer_format, _scan
        from arkflow_tpu.sql import SessionContext

        path = req.get("path")
        if not path:
            raise ConfigError("scan needs 'path'")
        self._check_path(path)
        p = Path(path)
        if not p.exists():
            raise ConfigError(f"worker: {path} does not exist")
        fmt = req.get("format") or _infer_format(p)
        query = req.get("query")
        batch_rows = int(req.get("batch_rows", 8192))
        await _send_frame(writer, json.dumps({"ok": True}).encode())
        writer._arkflow_streaming = True
        loop = asyncio.get_running_loop()
        it = _scan(p, fmt, batch_rows)
        while True:
            rb = await loop.run_in_executor(None, lambda: next(it, None))
            if rb is None:
                break
            if query:
                def _filter(rb=rb):
                    ctx = SessionContext()
                    ctx.register_batch("flow", MessageBatch(rb))
                    return ctx.sql(query)
                out = await loop.run_in_executor(None, _filter)
                if out.num_rows == 0:
                    continue
                rb = out.record_batch
            await _send_data(writer, batch_to_ipc(rb))
        await _end_stream(writer)

    async def _do_sqlite(self, req: dict, writer) -> None:
        """Run a sqlite query against a database file local to the worker."""
        import sqlite3

        path, query = req.get("path"), req.get("query")
        if not path or not query:
            raise ConfigError("sqlite action needs 'path' and 'query'")
        self._check_path(path)
        batch_rows = int(req.get("batch_rows", 8192))
        # check_same_thread=False: fetchmany runs in executor threads; access
        # is serialized by the per-connection handler
        conn = sqlite3.connect(path, check_same_thread=False)
        try:
            cur = conn.execute(query)
            names = [d[0] for d in cur.description or []]
            await _send_frame(writer, json.dumps({"ok": True}).encode())
            writer._arkflow_streaming = True
            loop = asyncio.get_running_loop()
            schema: Optional[pa.Schema] = None
            held: list[pa.RecordBatch] = []  # buffered until types resolve
            while True:
                rows = await loop.run_in_executor(None, cur.fetchmany, batch_rows)
                if not rows:
                    break
                # pa.array consumes the zip tuples directly — no per-column
                # list re-materialization of every value
                rb = pa.RecordBatch.from_arrays(
                    [pa.array(c) for c in zip(*rows)], names=names)
                if schema is None:
                    if any(pa.types.is_null(f.type) for f in rb.schema) and len(held) < 64:
                        # a leading all-NULL column would freeze as null-typed
                        # and clash with later chunks; hold until types appear
                        held.append(rb)
                        continue
                    # stragglers that never resolve (64-chunk cap) become string
                    schema = _merge_null_types(held + [rb], default=pa.string())
                    for h in held:
                        await _send_data(writer, batch_to_ipc(h.cast(schema)))
                    held = []
                await _send_data(writer, batch_to_ipc(rb.cast(schema)))
            if held:  # whole result was null-typed (or tiny): default to string
                schema = _merge_null_types(held, default=pa.string())
                for h in held:
                    await _send_data(writer, batch_to_ipc(h.cast(schema)))
            await _end_stream(writer)
        finally:
            conn.close()

    async def _do_query(self, req: dict, writer) -> None:
        """Run SQL over client-shipped tables (distributed join/shuffle leg)."""
        from arkflow_tpu.sql import SessionContext

        sql = req.get("sql")
        if not sql:
            raise ConfigError("query needs 'sql'")
        ctx = SessionContext()
        for name, b64 in (req.get("tables") or {}).items():
            batches = ipc_to_batches(base64.b64decode(b64))
            if batches:
                tbl = pa.Table.from_batches(batches)
                ctx.register_batch(
                    name, MessageBatch(tbl.combine_chunks().to_batches()[0]))
        # heavy joins must not stall other connections on this worker
        out = await asyncio.get_running_loop().run_in_executor(
            None, lambda: ctx.sql(sql))
        await _send_frame(writer, json.dumps({"ok": True}).encode())
        writer._arkflow_streaming = True
        if out.num_rows > 0:
            await _send_data(writer, batch_to_ipc(out.record_batch))
        await _end_stream(writer)


def _merge_null_types(batches: list[pa.RecordBatch],
                      default: Optional[pa.DataType] = None) -> pa.Schema:
    """One schema across chunks: null-typed columns adopt the first real
    type seen in any chunk (or ``default`` when none ever appears)."""
    fields: list[pa.Field] = list(batches[0].schema)
    for rb in batches[1:]:
        for i, f in enumerate(rb.schema):
            if pa.types.is_null(fields[i].type) and not pa.types.is_null(f.type):
                fields[i] = f
    if default is not None:
        fields = [pa.field(f.name, default) if pa.types.is_null(f.type) else f
                  for f in fields]
    return pa.schema(fields)


class FlightClient:
    """Client for a FlightWorker: remote scans stream back as batches."""

    def __init__(self, url: str, timeout: float = 30.0,
                 max_frame: int = DEFAULT_MAX_FRAME):
        self.host, self.port = parse_remote_url(url)
        self.timeout = timeout
        #: cap on a single inbound frame (a worker gone bad must not make
        #: the client buffer gigabytes off one length header)
        self.max_frame = int(max_frame)

    async def _open(self, request: dict):
        try:
            reader, writer = await asyncio.wait_for(
                asyncio.open_connection(self.host, self.port), self.timeout)
        except (OSError, asyncio.TimeoutError) as e:
            raise ConnectError(
                f"flight worker {self.host}:{self.port} unreachable: {e}") from e
        try:
            await _send_frame(writer, json.dumps(request).encode())
            status_raw = await asyncio.wait_for(
                _read_frame(reader, self.max_frame), self.timeout)
            if status_raw is None:
                raise ReadError("flight worker closed the stream before a status")
            status = json.loads(status_raw.decode())
            if not status.get("ok"):
                raise ReadError(f"flight worker error: {status.get('error')}")
        except BaseException:
            writer.close()  # a failed handshake must not leak the socket
            raise
        return reader, writer

    async def _stream(self, reader, writer) -> AsyncIterator[pa.RecordBatch]:
        try:
            while True:
                frame = await asyncio.wait_for(
                    _read_frame(reader, self.max_frame), self.timeout)
                if frame is None:
                    return
                tag, payload = frame[:1], frame[1:]
                if tag == ERROR_TAG:
                    err = json.loads(payload.decode()).get("error")
                    raise ReadError(f"flight worker stream error: {err}")
                for rb in ipc_to_batches(payload):
                    yield rb
        finally:
            writer.close()

    async def scan(self, path: str, *, fmt: Optional[str] = None,
                   query: Optional[str] = None,
                   batch_rows: int = 8192) -> AsyncIterator[pa.RecordBatch]:
        """Remote scan; yields record batches as they arrive."""
        reader, writer = await self._open({
            "action": "scan", "path": path, "format": fmt,
            "query": query, "batch_rows": batch_rows,
        })
        try:
            async for rb in self._stream(reader, writer):
                yield rb
        finally:
            # _stream closes once STARTED; this also covers a caller that
            # abandons the generator between _open and the first read —
            # otherwise the socket leaks until GC (close() is idempotent)
            writer.close()

    async def sqlite(self, path: str, query: str,
                     batch_rows: int = 8192) -> AsyncIterator[pa.RecordBatch]:
        """Remote sqlite query; yields record batches as they arrive."""
        reader, writer = await self._open({
            "action": "sqlite", "path": path, "query": query,
            "batch_rows": batch_rows,
        })
        try:
            async for rb in self._stream(reader, writer):
                yield rb
        finally:
            writer.close()  # see scan(): covers the never-started path

    async def query(self, sql: str,
                    tables: Optional[dict[str, MessageBatch]] = None) -> MessageBatch:
        """Ship small tables to the worker, run SQL there, get the result."""
        enc = {
            name: base64.b64encode(batch_to_ipc(b.record_batch)).decode()
            for name, b in (tables or {}).items()
        }
        reader, writer = await self._open(
            {"action": "query", "sql": sql, "tables": enc})
        try:
            batches = [rb async for rb in self._stream(reader, writer)]
        finally:
            writer.close()  # idempotent; guarantees release on every path
        return MessageBatch(batches[0]) if batches else MessageBatch.empty()
