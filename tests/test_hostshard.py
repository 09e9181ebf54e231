"""Sharded ingest plane (runtime/hostshard.py): config validation, routing
affinity, global output order, quota-once admission, shard-death redelivery
(zero silent loss), and the zero-copy IPC helper it rides on."""

import asyncio
import os
import signal

import pyarrow as pa
import pytest

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import (
    Input,
    NoopAck,
    ensure_plugins_loaded,
    register_input,
)
from arkflow_tpu.config import StreamConfig
from arkflow_tpu.connect.flight import batch_to_ipc, ipc_to_batches
from arkflow_tpu.errors import ConfigError, EndOfInput
from arkflow_tpu.plugins.output.drop import DropOutput
from arkflow_tpu.runtime import build_stream
from arkflow_tpu.runtime.hostshard import (
    SHARD_DELIVERY_KEY,
    ShardedIngestStream,
    _ShardConn,
)
from arkflow_tpu.runtime.stream import _WorkItem

ensure_plugins_loaded()


class CollectOutput(DropOutput):
    """Test sink recording every written batch."""

    def __init__(self):
        super().__init__()
        self.batches: list[MessageBatch] = []

    async def write(self, batch: MessageBatch) -> None:
        await super().write(batch)
        self.batches.append(batch)


class SeqRowsInput(Input):
    """One single-row batch per read, payload ``row-%05d`` — every batch has
    a DISTINCT fingerprint, so traffic spreads over the shard ring and the
    output order is checkable row by row."""

    def __init__(self, count: int):
        self.count = count
        self._i = 0

    async def connect(self) -> None:
        self._i = 0

    async def read(self):
        if self._i >= self.count:
            raise EndOfInput()
        i = self._i
        self._i += 1
        return MessageBatch.new_binary([f"row-{i:05d}".encode()]), NoopAck()


@register_input("test_seq_rows")
def _build_seq_rows(config, resource):
    return SeqRowsInput(int(config.get("count", 10)))


def _sharded_cfg(shards: int, count: int, processors=None, overload=None):
    pipeline = {"thread_num": 2, "ingest_shards": shards,
                "processors": processors or []}
    if overload is not None:
        pipeline["overload"] = overload
    return StreamConfig.from_mapping({
        "name": f"hostshard-t{shards}",
        "input": {"type": "test_seq_rows", "count": count},
        "pipeline": pipeline,
        "output": {"type": "drop"},
    })


# -- config ------------------------------------------------------------------


def test_ingest_shards_config_validation():
    cfg = StreamConfig.from_mapping({
        "input": {"type": "generate", "payload": "x"},
        "pipeline": {"ingest_shards": 3, "processors": []},
        "output": {"type": "drop"},
    })
    assert cfg.pipeline.ingest_shards == 3
    for bad in (True, -1, "two"):
        with pytest.raises(ConfigError):
            StreamConfig.from_mapping({
                "input": {"type": "generate", "payload": "x"},
                "pipeline": {"ingest_shards": bad, "processors": []},
                "output": {"type": "drop"},
            })
    with pytest.raises(ConfigError, match="process_pool"):
        StreamConfig.from_mapping({
            "input": {"type": "generate", "payload": "x"},
            "pipeline": {"ingest_shards": 2, "process_pool": 2,
                         "processors": []},
            "output": {"type": "drop"},
        })


def test_device_shards_off_the_cpu_platform_fail_at_config_time(monkeypatch):
    """A chip belongs to one process: N > 1 shard processes each building a
    device processor would fail or hang at start-up on an accelerator, so
    the stream refuses at build time with a message. On the pinned CPU
    platform (every shard its own XLA client) and with one shard it builds."""
    def cfg(shards):
        return StreamConfig.from_mapping({
            "input": {"type": "generate", "payload": "x"},
            "pipeline": {"ingest_shards": shards, "processors": [
                {"type": "tpu_inference", "model": "bert_classifier"}]},
            "output": {"type": "drop"},
        })

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ConfigError, match="belongs to one process"):
        build_stream(cfg(2))
    assert isinstance(build_stream(cfg(1)), ShardedIngestStream)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert isinstance(build_stream(cfg(2)), ShardedIngestStream)


def test_generate_tenants_rotation():
    """generate.tenants stamps consecutive batches with rotating tenant ids
    (identical payloads otherwise share one fingerprint -> one shard)."""
    from arkflow_tpu.components import Resource, build_component

    gen = build_component("input", {"type": "generate", "payload": "x",
                                    "batch_size": 4, "tenants": 3}, Resource())

    async def go():
        seen = []
        for _ in range(6):
            b, _ack = await gen.read()
            seen.append(b.tenant())
        return seen

    seen = asyncio.run(go())
    assert seen == ["tenant0", "tenant1", "tenant2"] * 2


# -- routing (no processes) --------------------------------------------------


def _parent_only_stream(shards=2, count=4) -> ShardedIngestStream:
    stream = build_stream(_sharded_cfg(shards, count))
    assert isinstance(stream, ShardedIngestStream)
    return stream


def test_route_key_affinity_and_determinism():
    stream = _parent_only_stream()
    dup_a = _WorkItem(MessageBatch.new_binary([b"same-bytes"]), NoopAck())
    dup_b = _WorkItem(MessageBatch.new_binary([b"same-bytes"]), NoopAck())
    other = _WorkItem(MessageBatch.new_binary([b"different"]), NoopAck())
    # byte-identical duplicates share a key; distinct payloads don't
    assert stream._route_key(dup_a) == stream._route_key(dup_b)
    assert stream._route_key(dup_a) != stream._route_key(other)
    # a tenant stamp wins over the fingerprint (tenant-sticky shards),
    # whether it came from admission (item.tenant) or the batch column
    stamped = _WorkItem(
        MessageBatch.new_binary([b"same-bytes"]).with_tenant("acme"), NoopAck())
    assert stream._route_key(stamped) == b"acme"
    labeled = _WorkItem(MessageBatch.new_binary([b"same-bytes"]), NoopAck(),
                        tenant="beta")
    assert stream._route_key(labeled) == b"beta"

    # ring placement is deterministic and skips dead shards
    for sid in (0, 1):
        stream._conns[sid] = _ShardConn(sid, None)
        stream._ring.add(str(sid))
    key = stream._route_key(dup_a)
    first = stream._pick_shard(key)
    assert all(stream._pick_shard(key) == first for _ in range(5))
    stream._conns[first].alive = False
    moved = stream._pick_shard(key)
    assert moved is not None and moved != first


def test_shard_spec_strips_quotas_parent_keeps_them():
    """Tenant quotas are granted ONCE in the parent's shared plane; the
    per-shard overload view must not hold its own copy (N shards each
    holding the full quota would over-grant every contract N times)."""
    cfg = _sharded_cfg(2, 4, overload={
        "enabled": True,
        "tenants": {"default_quota": {"rows_per_sec": 50}},
    })
    stream = build_stream(cfg)
    assert stream.overload is not None
    assert stream.overload.cfg.tenants.default_quota is not None
    shard_view = stream._spec.overload
    assert shard_view is not None
    assert shard_view.tenants.default_quota is None
    assert shard_view.tenants.quotas == {}


# -- e2e through real shard processes ---------------------------------------


def _run_sharded(stream, timeout=120.0):
    async def go():
        await asyncio.wait_for(stream.run(asyncio.Event()), timeout)

    asyncio.run(go())


def test_sharded_e2e_ordered_output_no_loss():
    """2 shard processes, distinct-fingerprint batches: every row delivered
    exactly once, in GLOBAL dispatch order, with the internal delivery
    column stripped before the sink."""
    count = 40
    stream = _parent_only_stream(shards=2, count=count)
    sink = CollectOutput()
    stream.output = sink
    _run_sharded(stream)
    rows = [v for b in sink.batches for v in b.to_binary()]
    assert rows == [f"row-{i:05d}".encode() for i in range(count)]
    for b in sink.batches:
        assert ("__meta_ext_" + SHARD_DELIVERY_KEY) not in b.record_batch.schema.names
    stats = stream.shard_stats()
    assert sum(s.get("batches", 0) for s in stats.values()) == count
    # distinct fingerprints spread over the ring: no shard saw everything
    assert all(s.get("batches", 0) < count for s in stats.values())


def test_sharded_quota_identity_and_shed():
    """Offered == delivered + shed under a parent-side tenant quota; the
    quota gates in ONE place even with 2 shards (sheds carry reason=quota
    to the error output)."""
    count = 120
    cfg = StreamConfig.from_mapping({
        "name": "hostshard-quota",
        "input": {"type": "test_seq_rows", "count": count},
        "pipeline": {
            "thread_num": 2,
            "ingest_shards": 2,
            "processors": [],
            "overload": {
                "enabled": True,
                "tenants": {"default_quota": {"rows_per_sec": 5},
                            "burst": "2s"},
            },
        },
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    })
    stream = build_stream(cfg)
    sink, err_sink = CollectOutput(), CollectOutput()
    stream.output = sink
    stream.error_output = err_sink
    _run_sharded(stream)
    delivered = sum(b.num_rows for b in sink.batches)
    shed = sum(b.num_rows for b in err_sink.batches)
    assert delivered + shed == count
    assert shed > 0  # the quota actually gated
    assert delivered < count
    reasons = {b.get_meta("__meta_ext_shed_reason") for b in err_sink.batches}
    assert reasons <= {"quota"}


def test_shard_sigkill_redelivery_no_silent_loss():
    """SIGKILL one of two shards mid-load: its in-flight deliveries are
    redispatched to the survivor, every row still arrives exactly once and
    IN ORDER (the reorder window holds their seqs), and the redispatch
    counter proves the path ran."""
    count = 36
    stream = _parent_only_stream(shards=2, count=count)
    sink = CollectOutput()
    stream.output = sink
    # slow the shards down so a backlog exists when the kill lands
    stream._spec.processors = [{
        "type": "python",
        "script": ("import time\n"
                   "def process(batch):\n"
                   "    time.sleep(0.05)\n"
                   "    return batch\n"),
    }]

    async def go():
        cancel = asyncio.Event()
        runner = asyncio.create_task(stream.run(cancel))
        # wait until both shards hold in-flight work, then kill the one
        # owning the most of it
        victim = None
        for _ in range(600):
            await asyncio.sleep(0.05)
            owners = [e.shard for e in stream._outstanding.values()
                      if e.shard is not None]
            pids = stream.shard_pids()
            if stream.m_batches_out.value > 0 and len(set(owners)) == 2:
                victim = max(set(owners), key=owners.count)
                os.kill(pids[victim], signal.SIGKILL)
                break
        assert victim is not None, "shards never reached steady state"
        await asyncio.wait_for(runner, 120)
        return victim

    asyncio.run(go())
    rows = [v for b in sink.batches for v in b.to_binary()]
    assert rows == [f"row-{i:05d}".encode() for i in range(count)]
    assert stream.m_redispatch.value > 0


# -- zero-copy IPC helper (the hop's serializer) -----------------------------


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


def test_chaos_soak_hostshard_fast_mode_smoke():
    """Acceptance gate (tools/chaos_soak.py --hostshard --fast): the sharded
    ingest plane holds its invariants under a seeded soak — queue_wait
    collapse at 2 shards, whole duplicate groups on one shard, ordered
    exactly-once delivery through a shard SIGKILL with redispatches counted,
    and the SAME quota allowance sharded as single-process."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        from chaos_soak import run_hostshard_soak
    finally:
        sys.path.pop(0)

    verdict = run_hostshard_soak(seconds=60.0, seed=7, fast=True)
    assert verdict["pass"], verdict
    assert verdict["throughput"]["sharded_queue_wait_share"] < 0.30
    assert verdict["affinity"]["whole_groups_ok"]
    chaos = verdict["chaos"]
    assert chaos["killed"] and chaos["redispatched"] > 0
    assert chaos["lost_rows"] == 0 and chaos["ordered_exactly_once"]
    assert verdict["quota"]["identity_ok"] and verdict["quota"]["granted_once_ok"]


def test_ext_values_reads_delivery_ids_through_merge():
    """ext_values returns distinct per-row ext values in first-seen order —
    how a merged coalescer emission names every covered delivery."""
    a = MessageBatch.new_binary([b"x", b"y"]).with_ext_metadata(
        {SHARD_DELIVERY_KEY: "7"})
    b = MessageBatch.new_binary([b"z"]).with_ext_metadata(
        {SHARD_DELIVERY_KEY: "9"})
    merged = MessageBatch.from_table(
        pa.Table.from_batches([a.record_batch, b.record_batch]))
    assert merged.ext_values(SHARD_DELIVERY_KEY) == ["7", "9"]
    assert MessageBatch.new_binary([b"q"]).ext_values(SHARD_DELIVERY_KEY) == []
