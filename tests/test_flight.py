"""Remote scan/query execution tier (Ballista analog) tests.

A FlightWorker runs in-process; clients and the file/sql inputs scan
through it over real sockets with framed Arrow IPC streaming.
"""

from __future__ import annotations

import asyncio
import sqlite3

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
from arkflow_tpu.connect.flight import (
    FlightClient,
    FlightWorker,
    batch_to_ipc,
    ipc_to_batches,
    parse_remote_url,
)
from arkflow_tpu.errors import ConfigError, ConnectError, EndOfInput, ReadError

ensure_plugins_loaded()


def _write_parquet(path, rows=1000):
    tbl = pa.table({
        "id": list(range(rows)),
        "value": [float(i) * 0.5 for i in range(rows)],
        "city": ["sf" if i % 2 == 0 else "la" for i in range(rows)],
    })
    pq.write_table(tbl, path)


def test_ipc_roundtrip_and_url_parsing():
    rb = pa.record_batch({"a": [1, 2, 3], "b": ["x", "y", "z"]})
    out = ipc_to_batches(batch_to_ipc(rb))
    assert out[0].equals(rb)
    assert parse_remote_url("arkflow://h:50051") == ("h", 50051)
    with pytest.raises(ConfigError):
        parse_remote_url("grpc://h:1")
    with pytest.raises(ConfigError):
        parse_remote_url("arkflow://nohost")


def test_batch_to_ipc_zero_copy_buffer_roundtrip():
    """The shared IPC helper returns a pyarrow Buffer (no bytes() copy of
    the payload) and round-trips through ipc_to_batches."""
    b = MessageBatch.new_binary([b"alpha", b"beta"]).with_source("s")
    buf = batch_to_ipc(b.record_batch)
    assert isinstance(buf, pa.Buffer)
    out = ipc_to_batches(buf)
    assert len(out) == 1
    back = MessageBatch(out[0])
    assert back.to_binary() == [b"alpha", b"beta"]
    assert back.get_meta("__meta_source") == "s"


def test_remote_scan_streams_filtered_batches(tmp_path):
    f = tmp_path / "events.parquet"
    _write_parquet(f, rows=1000)

    async def go():
        worker = FlightWorker("127.0.0.1", 0)
        await worker.start()
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{worker.port}")
            got = []
            async for rb in client.scan(str(f), batch_rows=256):
                got.append(rb)
            assert sum(b.num_rows for b in got) == 1000
            assert len(got) >= 4  # streamed in chunks, not one blob
            # remote SQL filter: only matching rows cross the wire
            filtered = []
            async for rb in client.scan(
                    str(f), query="SELECT id, value FROM flow WHERE city = 'sf'"):
                filtered.append(rb)
            assert sum(b.num_rows for b in filtered) == 500
            assert filtered[0].schema.names == ["id", "value"]
        finally:
            await worker.stop()

    asyncio.run(go())


def test_remote_scan_errors_surface(tmp_path):
    async def go():
        worker = FlightWorker("127.0.0.1", 0, allow_paths=[str(tmp_path)])
        await worker.start()
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{worker.port}")
            with pytest.raises(ReadError, match="does not exist"):
                async for _ in client.scan(str(tmp_path / "missing.parquet")):
                    pass
            with pytest.raises(ReadError, match="allow_paths"):
                async for _ in client.scan("/etc/passwd"):
                    pass
            dead = FlightClient("arkflow://127.0.0.1:1")
            with pytest.raises(ConnectError):
                async for _ in dead.scan("x"):
                    pass
        finally:
            await worker.stop()

    asyncio.run(go())


def test_remote_query_ships_tables(tmp_path):
    async def go():
        worker = FlightWorker("127.0.0.1", 0)
        await worker.start()
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{worker.port}")
            left = MessageBatch.from_pydict({"k": [1, 2, 3], "v": ["a", "b", "c"]})
            out = await client.query(
                "SELECT k, v FROM t WHERE k > 1", tables={"t": left})
            assert out.column("k").to_pylist() == [2, 3]
        finally:
            await worker.stop()

    asyncio.run(go())


def test_file_input_remote_url(tmp_path):
    f = tmp_path / "events.parquet"
    _write_parquet(f, rows=100)

    async def go():
        worker = FlightWorker("127.0.0.1", 0)
        await worker.start()
        try:
            inp = build_component(
                "input",
                {"type": "file", "path": str(f),
                 "remote_url": f"arkflow://127.0.0.1:{worker.port}",
                 "query": "SELECT id FROM flow WHERE id < 10"},
                Resource(),
            )
            await inp.connect()
            batch, _ = await inp.read()
            assert batch.column("id").to_pylist() == list(range(10))
            assert batch.get_meta("__meta_source") == "file"
            with pytest.raises(EndOfInput):
                await inp.read()
            await inp.close()
        finally:
            await worker.stop()

    asyncio.run(go())


def test_sql_input_remote_sqlite(tmp_path):
    db = tmp_path / "events.db"
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE events (id INTEGER, name TEXT)")
    conn.executemany("INSERT INTO events VALUES (?, ?)",
                     [(i, f"n{i}") for i in range(20)])
    conn.commit()
    conn.close()

    async def go():
        worker = FlightWorker("127.0.0.1", 0)
        await worker.start()
        try:
            inp = build_component(
                "input",
                {"type": "sql", "driver": "sqlite", "path": str(db),
                 "remote_url": f"arkflow://127.0.0.1:{worker.port}",
                 "query": "SELECT * FROM events WHERE id >= 15"},
                Resource(),
            )
            await inp.connect()
            batch, _ = await inp.read()
            assert batch.column("id").to_pylist() == [15, 16, 17, 18, 19]
            with pytest.raises(EndOfInput):
                await inp.read()
            await inp.close()
        finally:
            await worker.stop()

    asyncio.run(go())


def test_remote_config_validation():
    r = Resource()
    with pytest.raises(ConfigError):
        build_component("input", {"type": "file", "path": "x.parquet",
                                  "remote_url": "http://h:1"}, r)
    with pytest.raises(ConfigError):
        build_component("input", {"type": "sql", "driver": "postgres",
                                  "uri": "postgres://u@h/db", "query": "q",
                                  "remote_url": "arkflow://h:1"}, r)


def test_remote_sqlite_null_leading_chunk_unifies_schema(tmp_path):
    """Leading all-NULL sqlite chunks must not freeze a null-typed column."""
    db = tmp_path / "n.db"
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE t (id INTEGER, v REAL)")
    conn.executemany("INSERT INTO t VALUES (?, ?)",
                     [(i, None) for i in range(5)] + [(i, i * 0.5) for i in range(5, 10)])
    conn.commit()
    conn.close()

    async def go():
        worker = FlightWorker("127.0.0.1", 0)
        await worker.start()
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{worker.port}")
            batches = [rb async for rb in client.sqlite(
                str(db), "SELECT * FROM t ORDER BY id", batch_rows=5)]
            pa.Table.from_batches(batches)  # consistent schema across chunks
            assert batches[0].schema.field("v").type == pa.float64()
        finally:
            await worker.stop()

    asyncio.run(go())


def test_remote_url_validation_errors_are_config_errors():
    for bad in ("arkflow://h:50051/", "arkflow://h:abc", "arkflow://h:0"):
        with pytest.raises(ConfigError):
            parse_remote_url(bad)


# -- mid-stream error frames (tag 0x01) and the max-frame cap ---------------


async def _fake_streaming_server(frames_after_status: list[bytes]):
    """A minimal flight-protocol peer: reads the request frame, answers
    ``{"ok": true}``, then plays back the given raw frames verbatim.
    Returns (server, port)."""
    import json
    import struct

    async def serve(reader, writer):
        # read the request frame (length header + payload)
        (n,) = struct.unpack(">I", await reader.readexactly(4))
        await reader.readexactly(n)
        status = json.dumps({"ok": True}).encode()
        writer.write(struct.pack(">I", len(status)) + status)
        for frame in frames_after_status:
            writer.write(frame)
        await writer.drain()
        writer.close()

    server = await asyncio.start_server(serve, "127.0.0.1", 0)
    return server, server.sockets[0].getsockname()[1]


def _frame(payload: bytes) -> bytes:
    import struct

    return struct.pack(">I", len(payload)) + payload


def test_mid_stream_error_frame_surfaces_without_hanging():
    """Satellite: an error raised AFTER batches have streamed must surface
    as ReadError on the consumer — with the already-streamed batches
    delivered and the stream not hanging."""
    import json

    rb = pa.RecordBatch.from_pydict({"a": [1, 2, 3]})
    err = b"\x01" + json.dumps({"error": "disk died mid-scan"}).encode()

    async def go():
        server, port = await _fake_streaming_server([
            _frame(b"\x00" + bytes(batch_to_ipc(rb))),  # one good data frame
            _frame(err),                          # then the tagged error
        ])
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{port}", timeout=5.0)
            got = []
            with pytest.raises(ReadError, match="disk died mid-scan"):
                async for b in client.scan("/whatever"):
                    got.append(b)
            assert len(got) == 1 and got[0].equals(rb)
        finally:
            server.close()

    asyncio.run(asyncio.wait_for(go(), timeout=10))


def test_zero_length_end_frame_terminates_cleanly():
    """Satellite: the zero-length end frame must terminate the stream with
    every data frame delivered and no error."""
    rb = pa.RecordBatch.from_pydict({"a": [1, 2]})

    async def go():
        server, port = await _fake_streaming_server([
            _frame(b"\x00" + bytes(batch_to_ipc(rb))),
            _frame(b"\x00" + bytes(batch_to_ipc(rb))),
            b"\x00\x00\x00\x00",  # end
        ])
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{port}", timeout=5.0)
            got = [b async for b in client.scan("/whatever")]
            assert len(got) == 2
        finally:
            server.close()

    asyncio.run(asyncio.wait_for(go(), timeout=10))


def test_worker_sends_error_tag_when_scan_fails_mid_stream(tmp_path, monkeypatch):
    """The WORKER side of the same contract: a scan that fails after
    yielding batches emits tag 0x01 (not a connection drop), so the client
    sees ReadError and the delivered prefix."""
    import arkflow_tpu.plugins.input.file as file_mod

    _write_parquet(tmp_path / "t.parquet", rows=10)
    real_scan = file_mod._scan

    def flaky_scan(path, fmt, batch_rows):
        it = real_scan(path, fmt, batch_rows)
        yield next(it)
        raise RuntimeError("emulated io failure after first batch")

    monkeypatch.setattr(file_mod, "_scan", flaky_scan)

    async def go():
        worker = FlightWorker("127.0.0.1", 0, allow_paths=[str(tmp_path)])
        await worker.start()
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{worker.port}",
                                  timeout=5.0)
            got = []
            with pytest.raises(ReadError, match="emulated io failure"):
                async for b in client.scan(str(tmp_path / "t.parquet"),
                                           batch_rows=4):
                    got.append(b)
            assert len(got) == 1  # the streamed prefix arrived intact
        finally:
            await worker.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=15))


def test_max_frame_cap_raises_connect_error_naming_the_limit():
    """Satellite: the u32 length header is untrusted — an oversized frame
    fails loudly with the configured cap in the message, client-side and
    worker-side, before any payload is buffered."""
    import struct

    async def go():
        # client side: the peer announces a frame far beyond the cap
        server, port = await _fake_streaming_server(
            [struct.pack(">I", 1 << 31)])
        try:
            client = FlightClient(f"arkflow://127.0.0.1:{port}",
                                  timeout=5.0, max_frame=1024)
            with pytest.raises(ConnectError, match="max_frame"):
                async for _ in client.scan("/whatever"):
                    pass
        finally:
            server.close()

        # worker side: a client announcing a huge request frame gets a loud
        # error status naming the cap instead of a 4 GiB readexactly
        worker = FlightWorker("127.0.0.1", 0, max_frame=1024)
        await worker.start()
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", worker.port)
            writer.write(struct.pack(">I", 1 << 30))
            await writer.drain()
            (n,) = struct.unpack(">I", await reader.readexactly(4))
            import json

            status = json.loads((await reader.readexactly(n)).decode())
            assert status["ok"] is False
            assert "max_frame" in status["error"]
            writer.close()
        finally:
            await worker.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=15))
