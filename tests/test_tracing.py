"""End-to-end per-batch tracing (obs/trace.py): context plumbing, sampling,
the bounded span store, trace-context survival across redelivery /
split-ack / coalescer merges / quarantine, stage spans through a live
stream, and cross-tier stitching over the cluster flight plane."""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

import pyarrow as pa
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from arkflow_tpu.batch import META_EXT_TRACE, MessageBatch, batch_fingerprint
from arkflow_tpu.components import Processor, ensure_plugins_loaded
from arkflow_tpu.config import EngineConfig, StreamConfig
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.obs.trace import (
    FORCE_STATUSES,
    Span,
    TraceContext,
    Tracer,
    TracingConfig,
    activate,
    global_tracer,
    record_stage,
    stage_span,
)

ensure_plugins_loaded()


def _fresh_global(sample_rate: float = 1.0, **kw) -> "Tracer":
    t = global_tracer()
    t.configure(TracingConfig(sample_rate=sample_rate, **kw), tier="ingest")
    t.clear()
    return t


# -- context + config --------------------------------------------------------


def test_trace_context_roundtrip_and_tolerance():
    ctx = TraceContext("abc123", "span9", sampled=False)
    back = TraceContext.from_json(ctx.to_json())
    assert back == ctx
    # dict form (the flight request embeds it un-stringified)
    assert TraceContext.from_json(ctx.to_dict()) == ctx
    # malformed column values never raise — the batch continues untraced
    for bad in (None, "", "not json", "[]", '{"p":"x"}', b"\xff", 42):
        assert TraceContext.from_json(bad) is None


def test_tracing_config_validation():
    cfg = TracingConfig.from_mapping({"sample_rate": 0.5, "max_traces": 7})
    assert cfg.sample_rate == 0.5 and cfg.max_traces == 7 and cfg.enabled
    assert TracingConfig.from_mapping(None).enabled
    for bad in ({"sample_rate": 1.5}, {"sample_rate": -0.1},
                {"sample_rate": True}, {"max_traces": 0},
                {"max_spans_per_trace": "x"}, {"enabled": "yes"}, 3):
        with pytest.raises(ConfigError):
            TracingConfig.from_mapping(bad)


def test_batch_trace_column_survives_slice_concat_and_quarantine_tagging():
    ctx = TraceContext("feedbeef00000001")
    b = MessageBatch.new_binary([b"a", b"b", b"c", b"d"]).with_trace(ctx)
    assert b.trace_context() == ctx
    # split-ack share slices keep the context (coalescer carve path)
    head, tail = b.slice(0, 2), b.slice(2)
    assert head.trace_context() == ctx and tail.trace_context() == ctx
    # quarantine tagging (extra ext metadata) keeps it too
    tagged = b.with_ext_metadata({"error": "boom", "delivery_attempts": "3"})
    assert tagged.trace_context() == ctx
    # a merged batch exposes each source's trace id, first-seen order
    other = MessageBatch.new_binary([b"x"]).with_trace(
        TraceContext("feedbeef00000002"))
    merged = MessageBatch.concat([head, other])
    assert merged.source_trace_ids() == ["feedbeef00000001",
                                         "feedbeef00000002"]
    # the trace column is a per-delivery artifact: fingerprints (dedup,
    # routing affinity, attempt budgets) must not see it
    assert batch_fingerprint(b) == batch_fingerprint(
        MessageBatch.new_binary([b"a", b"b", b"c", b"d"]))


# -- tracer core -------------------------------------------------------------


def test_head_sampling_and_forced_commit():
    t = Tracer(config=TracingConfig(sample_rate=0.0))
    ctx = t.begin()
    assert ctx is not None and not ctx.sampled
    t.record(ctx, "stage_a", 0.01)
    assert t.finish(ctx, "ok") is False  # unsampled healthy trace drops
    for status in FORCE_STATUSES:
        ctx = t.begin()
        t.record(ctx, "stage_a", 0.02)
        assert t.finish(ctx, status) is True  # pathological always commits
    assert t.summary()["forced_samples"] == len(FORCE_STATUSES)
    assert all(r["forced"] for r in t.slowest(10))
    # sampled traces commit on ok
    t2 = Tracer(config=TracingConfig(sample_rate=1.0))
    ctx = t2.begin()
    assert ctx.sampled
    assert t2.finish(ctx, "ok", e2e_s=0.5) is True
    assert t2.slowest(1)[0]["e2e_ms"] == 500.0


def test_store_bounds_ring_spans_and_open_table():
    t = Tracer(config=TracingConfig(max_traces=3, max_open=4,
                                    max_spans_per_trace=2))
    for i in range(6):
        ctx = t.begin()
        for _ in range(5):  # 3 over the per-trace span cap
            t.record(ctx, "s", 0.001)
        t.finish(ctx, "ok")
    assert len(t.slowest(100)) == 3  # ring keeps the newest 3
    assert all(len(r["spans"]) == 2 and r["dropped_spans"] == 3
               for r in t.slowest(100))
    # open-table bound: unfinished traces evict oldest-first
    for i in range(10):
        t.record(TraceContext(f"open-{i}"), "s", 0.001)
    assert t.open_evicted > 0
    assert t.summary()["traces_open"] <= 4


def test_stage_breakdown_quantiles_and_share():
    t = Tracer(config=TracingConfig())
    for dur in (0.010, 0.020, 0.030):
        ctx = t.begin()
        t.record(ctx, "work", dur)
        t.record(ctx, "wait", 0.010)
        t.finish(ctx, "ok", e2e_s=dur + 0.010)
    bd = t.stage_breakdown()
    assert bd["traces"] == 3
    assert bd["stages"]["work"]["count"] == 3
    assert bd["stages"]["work"]["p50_ms"] == 20.0
    assert bd["stages"]["wait"]["total_ms"] == 30.0
    share = bd["stages"]["work"]["share_of_e2e"]
    assert 0.6 < share < 0.7  # 60ms of work over 90ms summed e2e
    # min_seq gives delta views (bench per-phase attribution)
    seq = t.commit_seq()
    ctx = t.begin()
    t.record(ctx, "late", 0.001)
    t.finish(ctx, "ok")
    delta = t.stage_breakdown(seq)
    assert delta["traces"] == 1 and list(delta["stages"]) == ["late"]


def test_stage_breakdown_nested_spans_do_not_inflate_share():
    """A nested span (device_step inside process) overlaps its parent;
    share_of_e2e must count top-level spans only, so the shares of
    disjoint top-level stages sum to <= 1.0 — a nested-only stage reports
    nested: true + its parent stage and a 0.0 top-level share instead."""
    t = Tracer(config=TracingConfig())
    ctx = t.begin()
    with activate(t, ctx):
        with stage_span("process"):
            record_stage("device_step", 0.08)
    t.record(ctx, "queue_wait", 0.02)
    t.finish(ctx, "ok", e2e_s=0.12)
    stages = t.stage_breakdown()["stages"]
    dev = stages["device_step"]
    assert dev["nested"] is True and dev["nested_under"] == "process"
    assert dev["share_of_e2e"] == 0.0  # no top-level spans
    assert dev["total_ms"] == pytest.approx(80.0, abs=1.0)  # cost visible
    assert sum(s["share_of_e2e"] for s in stages.values()) <= 1.0


def test_stage_span_scope_nesting_and_noop_off_scope():
    t = Tracer(config=TracingConfig())
    # outside any scope: helpers are no-ops, never errors
    assert record_stage("orphan", 0.1) == ""
    with stage_span("orphan2"):
        pass
    ctx = t.begin()
    with activate(t, ctx):
        with stage_span("outer"):
            record_stage("inner", 0.005)
    t.finish(ctx, "ok")
    spans = {s["stage"]: s for s in t.slowest(1)[0]["spans"]}
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["outer"]["parent_id"] == ""  # parented at the trace root


def test_adopt_and_export_cross_tier_spans():
    worker = Tracer(tier="worker:w1", config=TracingConfig())
    ingest = Tracer(tier="ingest", config=TracingConfig())
    ctx = ingest.begin()
    hop_ctx = ctx.with_parent("hopspan01")
    worker.record(hop_ctx, "remote_step", 0.042)
    exported = worker.export_open(hop_ctx)
    assert worker.summary()["traces_open"] == 0  # popped, not leaked
    ingest.record(ctx, "cluster_hop", 0.050, span_id="hopspan01")
    ingest.adopt_spans(ctx, exported)
    ingest.finish(ctx, "ok")
    spans = {s["stage"]: s for s in ingest.slowest(1)[0]["spans"]}
    assert spans["remote_step"]["tier"] == "worker:w1"
    assert spans["remote_step"]["parent_id"] == "hopspan01"
    # adopted durations survive the JSON hop
    assert spans["remote_step"]["dur_ms"] == 42.0
    # malformed frames are skipped, not fatal
    ingest.adopt_spans(ctx, [{"nope": 1}, None and {}])


def test_env_kill_switch_survives_config_application(monkeypatch):
    """ARKFLOW_TRACE=0 must hold through the engine applying a `tracing:`
    block that doesn't explicitly say enabled — only an explicit
    `enabled: true` overrides the env."""
    monkeypatch.setenv("ARKFLOW_TRACE", "0")
    assert TracingConfig.from_mapping(None).enabled is False
    assert TracingConfig.from_mapping({"sample_rate": 0.5}).enabled is False
    assert TracingConfig.from_mapping({"enabled": True}).enabled is True
    monkeypatch.delenv("ARKFLOW_TRACE")
    assert TracingConfig.from_mapping(None).enabled is True


def test_finish_fallback_e2e_counts_root_spans_only():
    """Without an explicit e2e, nested children (device step inside
    process) must not double-count the trace's latency."""
    t = Tracer(config=TracingConfig())
    ctx = t.begin()
    with activate(t, ctx):
        with stage_span("process"):
            record_stage("device_step", 0.04)
    # give the outer span a known size by recording a root sibling too
    t.record(ctx, "queue_wait", 0.01)
    t.finish(ctx, "error")  # forced path = the fallback's main consumer
    rec = t.slowest(1)[0]
    roots = sum(s["dur_ms"] for s in rec["spans"] if not s["parent_id"])
    assert rec["e2e_ms"] == pytest.approx(roots, abs=0.01)
    total = sum(s["dur_ms"] for s in rec["spans"])
    assert rec["e2e_ms"] < total  # the nested child was NOT double-counted


def test_disabled_tracer_is_fully_inert():
    t = Tracer(config=TracingConfig(enabled=False))
    assert t.begin() is None
    assert t.record(None, "s", 1.0) == ""
    assert t.finish(None, "error") is False
    assert t.slowest(5) == [] and t.stage_breakdown()["traces"] == 0


# -- stream-level: spans through a live pipeline -----------------------------


class _Sleep(Processor):
    """Deterministic ~stage cost so span sums are measurable."""

    def __init__(self, seconds: float = 0.02, fail_calls=()):
        self.seconds = seconds
        self.calls = 0
        self.fail_calls = set(fail_calls)

    async def process(self, batch: MessageBatch) -> list[MessageBatch]:
        self.calls += 1
        if self.calls in self.fail_calls:
            raise RuntimeError(f"injected failure on call {self.calls}")
        await asyncio.sleep(self.seconds)
        return [batch]


def _run_stream(cfg_map: dict, timeout: float = 30.0,
                patch=None) -> None:
    from arkflow_tpu.runtime import build_stream

    async def go():
        stream = build_stream(StreamConfig.from_mapping(cfg_map))
        if patch is not None:
            patch(stream)
        cancel = asyncio.Event()
        await asyncio.wait_for(stream.run(cancel), timeout=timeout)

    asyncio.run(asyncio.wait_for(go(), timeout=timeout + 5))


def test_stream_trace_covers_the_path_and_sums_to_e2e():
    tracer = _fresh_global()
    proc = _Sleep(0.03)
    _run_stream({
        "name": "t-covered",
        "input": {"type": "memory", "messages": ["m1", "m2", "m3"]},
        "pipeline": {"thread_num": 1, "processors": []},
        "output": {"type": "drop"},
    }, patch=lambda s: s.pipeline.processors.append(proc))
    recs = [r for r in tracer.slowest(10) if r["status"] == "ok"]
    assert len(recs) == 3
    for rec in recs:
        stages = {s["stage"] for s in rec["spans"]}
        assert {"input_decode", "queue_wait", "process",
                "output_write"} <= stages
        # top-level spans account for the delivered latency: their sum must
        # land within 10% of measured e2e (+2ms scheduling-noise floor)
        covered = sum(s["dur_ms"] for s in rec["spans"]
                      if s["stage"] in ("queue_wait", "process",
                                        "output_write"))
        assert covered <= rec["e2e_ms"] + 2.0
        assert covered >= rec["e2e_ms"] * 0.9 - 2.0, (covered, rec["e2e_ms"])


def test_stream_redelivery_keeps_the_trace_id_and_forces_error_commit():
    tracer = _fresh_global()
    proc = _Sleep(0.0, fail_calls={1})
    _run_stream({
        "name": "t-redeliver",
        "input": {"type": "fault", "seed": 5, "redeliver_unacked": True,
                  "inner": {"type": "memory", "messages": ["r1"]},
                  "faults": [{"kind": "latency", "every": 100,
                              "duration": "1ms"}]},
        "pipeline": {"thread_num": 1, "max_delivery_attempts": 3,
                     "processors": []},
        "output": {"type": "drop"},
    }, patch=lambda s: s.pipeline.processors.append(proc))
    assert proc.calls == 2  # failed once, redelivered, succeeded
    errors = [r for r in tracer.slowest(10) if r["status"] == "error"]
    oks = [r for r in tracer.slowest(10) if r["status"] == "ok"]
    assert len(errors) == 1 and len(oks) == 1
    # the redelivery re-entered the SAME trace: both attempts share the id,
    # and the retry's input_decode span is tagged redelivered
    assert errors[0]["trace_id"] == oks[0]["trace_id"]
    assert any(s.get("attrs", {}).get("redelivered")
               for s in oks[0]["spans"] if s["stage"] == "input_decode")


def test_stream_quarantine_preserves_trace_column_and_commits_error():
    tracer = _fresh_global(sample_rate=0.0)
    quarantined: list[MessageBatch] = []

    class _Collect(Processor):
        async def process(self, batch):
            raise RuntimeError("always poisoned")

    def patch(stream):
        stream.pipeline.processors.append(_Collect())

        class _Err:
            async def connect(self):
                pass

            async def close(self):
                pass

            async def write(self, batch):
                quarantined.append(batch)

        stream.error_output = _Err()

    _run_stream({
        "name": "t-quarantine",
        "input": {"type": "memory", "messages": ["p1"]},
        "pipeline": {"thread_num": 1, "max_delivery_attempts": 1,
                     "processors": []},
        "output": {"type": "drop"},
        "error_output": {"type": "drop"},
    }, patch=patch)
    assert len(quarantined) == 1
    # the quarantined batch still carries its trace context next to the
    # error tags — an operator can join error_output rows to /trace
    assert quarantined[0].has_column(META_EXT_TRACE)
    ctx = quarantined[0].trace_context()
    errors = [r for r in tracer.slowest(10) if r["status"] == "error"]
    assert len(errors) == 1 and errors[0]["trace_id"] == ctx.trace_id


def test_coalesced_emission_links_source_traces():
    tracer = _fresh_global()
    proc = _Sleep(0.0)
    # 6 single-row writes coalesce into 2-row bucket-exact emissions
    _run_stream({
        "name": "t-coalesce",
        "input": {"type": "memory", "messages": ["a", "b", "c", "d"]},
        "buffer": {"type": "memory", "capacity": 64, "timeout": "20ms",
                   "coalesce": {"batch_buckets": [4], "deadline": "20ms"}},
        "pipeline": {"thread_num": 1, "processors": []},
        "output": {"type": "drop"},
    }, patch=lambda s: s.pipeline.processors.append(proc))
    recs = tracer.slowest(50)
    merged = [r for r in recs if r["status"] == "ok"
              and any(s["stage"] == "coalesce_wait" for s in r["spans"])]
    coalesced = [r for r in recs if r["status"] == "coalesced"]
    assert merged, [r["status"] for r in recs]
    links = []
    for r in merged:
        for s in r["spans"]:
            if s["stage"] == "coalesce_wait":
                links.extend(s["attrs"]["links"])
    # every source trace the merged emissions link to is closed with
    # status=coalesced pointing back at its merged trace
    assert coalesced and {r["trace_id"] for r in coalesced} <= set(links)
    for r in coalesced:
        assert r["attrs"]["merged_into"] in {m["trace_id"] for m in merged}


def test_shed_trace_is_force_sampled():
    """An admission shed commits the trace with status shed even at
    sample_rate 0 — the burst soak asserts the same end to end."""
    tracer = _fresh_global(sample_rate=0.0)
    item_tr = []

    async def go():
        from arkflow_tpu.runtime.stream import Stream, _WorkItem

        class _NullAck:
            redeliverable = False

            async def ack(self):
                pass

            async def nack(self):
                pass

        from arkflow_tpu.runtime.overload import OverloadConfig
        from arkflow_tpu.runtime.pipeline import Pipeline
        from arkflow_tpu.plugins.output.drop import DropOutput
        from arkflow_tpu.plugins.input.memory import MemoryInput

        stream = Stream(MemoryInput([]), Pipeline([]), DropOutput(),
                        overload=OverloadConfig.from_config(
                            {"enabled": True}, deadline_ms=1.0))
        ctx = tracer.begin()
        batch = (MessageBatch.new_binary([b"stale"]).with_trace(ctx)
                 .with_deadline_ms(0))  # already expired
        item = _WorkItem(batch, _NullAck(), 0.0, trace=ctx)
        item_tr.append(ctx)
        assert await stream._admit_or_shed(item) is False

    asyncio.run(go())
    recs = tracer.slowest(5)
    assert len(recs) == 1 and recs[0]["status"] == "deadline"
    assert recs[0]["forced"] and recs[0]["trace_id"] == item_tr[0].trace_id


# -- cluster: cross-tier stitching over the flight plane ---------------------


class _RemoteSleep(Processor):
    """Worker-hosted stage with a deterministic device-ish cost."""

    def __init__(self, seconds: float = 0.05):
        self.seconds = seconds

    async def process(self, batch: MessageBatch) -> list[MessageBatch]:
        with stage_span("device_step"):  # nested like the real runner
            await asyncio.sleep(self.seconds)
        return [batch.with_column(
            "__value__",
            pa.array([v.upper() for v in batch.to_binary()],
                     type=pa.binary()))]


def test_cluster_trace_stitches_both_tiers_and_covers_e2e():
    """The ISSUE acceptance shape: a 2-worker cluster request yields ONE
    stitched trace covering ingest decode -> queue -> flight hop -> worker
    step -> response, with per-stage durations consistent with e2e."""
    from arkflow_tpu.runtime import build_stream
    from arkflow_tpu.runtime.cluster import ClusterWorkerServer

    tracer = _fresh_global()

    async def go():
        srvs = []
        for i in range(2):
            srv = ClusterWorkerServer([_RemoteSleep(0.05)], host="127.0.0.1",
                                      port=0, worker_id=f"w{i}")
            await srv.connect()
            await srv.start()
            srvs.append(srv)
        urls = [f"arkflow://127.0.0.1:{s.port}" for s in srvs]
        cfg = StreamConfig.from_mapping({
            "name": "t-cluster-trace",
            "input": {"type": "memory",
                      "messages": [f"row-{i}" for i in range(4)]},
            "pipeline": {"thread_num": 1,
                         "processors": [{"type": "remote_tpu",
                                         "name": "t-cluster-trace",
                                         "workers": urls,
                                         "heartbeat": "60s"}]},
            "output": {"type": "drop"},
        })
        stream = build_stream(cfg)
        cancel = asyncio.Event()
        try:
            await asyncio.wait_for(stream.run(cancel), timeout=30)
        finally:
            for s in srvs:
                await s.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=40))
    recs = [r for r in tracer.slowest(10) if r["status"] == "ok"]
    assert len(recs) == 4
    for rec in recs:
        by_stage: dict[str, dict] = {}
        for s in rec["spans"]:
            by_stage[s["stage"]] = s
        # the full path, one tree: ingest stages + flight hop + worker tier
        for stage in ("input_decode", "queue_wait", "process", "cluster_hop",
                      "flight_serialize", "flight_transport",
                      "flight_deserialize", "remote_deserialize",
                      "remote_queue_wait", "remote_step", "device_step",
                      "output_write"):
            assert stage in by_stage, (stage, sorted(by_stage))
        # worker spans are tier-tagged and parent under the hop span
        assert by_stage["remote_step"]["tier"].startswith("worker:w")
        assert (by_stage["remote_step"]["parent_id"]
                == by_stage["cluster_hop"]["span_id"])
        # device_step nests under remote_step on the WORKER side
        assert (by_stage["device_step"]["parent_id"]
                == by_stage["remote_step"]["span_id"])
        # per-stage durations consistent: top-level ingest spans sum to
        # within 10% of measured e2e (+2ms noise floor), and the worker's
        # step is inside the hop which is inside process
        covered = sum(by_stage[s]["dur_ms"] for s in
                      ("queue_wait", "process", "output_write"))
        assert covered >= rec["e2e_ms"] * 0.9 - 2.0, (covered, rec["e2e_ms"])
        assert covered <= rec["e2e_ms"] + 2.0
        assert (by_stage["device_step"]["dur_ms"]
                <= by_stage["remote_step"]["dur_ms"] + 1.0)
        assert (by_stage["remote_step"]["dur_ms"]
                <= by_stage["cluster_hop"]["dur_ms"] + 1.0)
        assert (by_stage["cluster_hop"]["dur_ms"]
                <= by_stage["process"]["dur_ms"] + 1.0)
    # the breakdown aggregates both tiers' stages
    stages = tracer.stage_breakdown()["stages"]
    assert "remote_step" in stages and "flight_transport" in stages


def test_failed_remote_step_still_ships_worker_spans():
    """A worker whose step FAILS exports its spans ahead of the error
    frame — the force-sampled error trace keeps its worker-tier timing."""
    from arkflow_tpu.errors import ProcessError
    from arkflow_tpu.runtime.cluster import ClusterDispatcher, ClusterWorkerServer

    tracer = _fresh_global()

    class _Fail(Processor):
        async def process(self, batch):
            raise RuntimeError("deterministic poison")

    async def go():
        srv = ClusterWorkerServer([_Fail()], host="127.0.0.1", port=0,
                                  worker_id="w-fail")
        await srv.connect()
        await srv.start()
        d = ClusterDispatcher([f"arkflow://127.0.0.1:{srv.port}"],
                              name="t-failspan", heartbeat_s=999)
        try:
            await d.start()
            ctx = tracer.begin()
            batch = MessageBatch.new_binary([b"poison"]).with_trace(ctx)
            with pytest.raises(ProcessError):
                await d.dispatch(batch)
            tracer.finish(ctx, "error")
        finally:
            await d.close()
            await srv.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=15))
    rec = [r for r in tracer.slowest(5) if r["status"] == "error"][0]
    stages = {s["stage"] for s in rec["spans"]}
    assert {"remote_deserialize", "remote_queue_wait"} <= stages, stages
    assert any(s["stage"] == "remote_step" and s["attrs"].get("error")
               for s in rec["spans"])


def test_engine_trace_endpoint_and_health_summary():
    """GET /trace serves the stitched store; /health embeds the one-line
    tracing summary."""
    import json as _json

    import aiohttp

    from arkflow_tpu.runtime.engine import Engine

    tracer = _fresh_global()

    async def go():
        cfg = EngineConfig.from_mapping({
            "health_check": {"host": "127.0.0.1", "port": 18972},
            "tracing": {"sample_rate": 1.0, "max_traces": 64},
            "streams": [{
                "name": "traced",
                "input": {"type": "generate", "payload": "live",
                          "interval": "20ms", "batch_size": 2},
                "pipeline": {"thread_num": 1, "processors": []},
                "output": {"type": "drop"},
            }],
        })
        engine = Engine(cfg)
        task = asyncio.create_task(engine.run())
        try:
            for _ in range(100):
                await asyncio.sleep(0.05)
                if engine._ready and tracer.commit_seq() > 2:
                    break
            async with aiohttp.ClientSession() as s:
                async with s.get("http://127.0.0.1:18972/trace?n=5") as r:
                    assert r.status == 200
                    body = _json.loads(await r.text())
                assert body["summary"]["enabled"] is True
                assert body["stage_breakdown"]["traces"] > 0
                assert 0 < len(body["slowest"]) <= 5
                spans = body["slowest"][0]["spans"]
                assert any(s["stage"] == "process" for s in spans)
                async with s.get("http://127.0.0.1:18972/trace?n=x") as r:
                    assert r.status == 400
                async with s.get("http://127.0.0.1:18972/health") as r:
                    health = _json.loads(await r.text())
                assert health["tracing"]["enabled"] is True
                assert health["tracing"]["traces_retained"] > 0
        finally:
            engine.shutdown()
            try:
                await asyncio.wait_for(task, timeout=10)
            except (asyncio.TimeoutError, Exception):
                task.cancel()

    asyncio.run(asyncio.wait_for(go(), timeout=40))


def test_device_idle_gap_histogram_exists():
    """The runner exports arkflow_tpu_device_idle_gap_seconds — ROADMAP
    item 5's before/after measurement — alongside the stall counter."""
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.tpu.bucketing import BucketPolicy
    from arkflow_tpu.tpu.runner import ModelRunner

    runner = ModelRunner(
        "bert_classifier",
        {"vocab_size": 128, "hidden": 16, "layers": 1, "heads": 2,
         "ffn": 32, "max_positions": 32, "num_labels": 2},
        buckets=BucketPolicy((2,), (16,)))
    import numpy as np

    async def go():
        # the gap tracks the ASYNC dispatch path (the serving hot loop):
        # two sequential steps leave one measurable idle gap between them
        inputs = {"input_ids": np.zeros((2, 16), dtype=np.int32),
                  "attention_mask": np.ones((2, 16), dtype=np.int32)}
        out = await runner.infer(inputs)
        assert out["label"].shape[0] == 2
        await runner.infer(inputs)

    asyncio.run(go())
    reg = global_registry()
    h = [m for m in reg.collect()
         if m.name == "arkflow_tpu_device_idle_gap_seconds"]
    assert h and h[0].count >= 1  # the second dispatch observed one gap


# -- the generation serve loop + the classify step (PR 25) --------------------

TINY_DECODER = dict(vocab_size=128, dim=32, layers=1, heads=2, kv_heads=1,
                    ffn=48, max_seq=64)
GEN_PROMPTS = [list(range(3, 25)),   # 22 tokens: chunked prefill
               [9, 4],                # admits one-shot
               list(range(40, 55)),
               [7]]
GEN_STAGES = ("gen_queue_wait", "gen_prefill", "gen_decode")
LOOP_STAGES = ("gen_admit", "gen_prepare", "gen_handoff", "gen_device_wait",
               "gen_apply")


def _tiny_generation_server(name: str, **kw):
    import jax

    from arkflow_tpu.models import get_model
    from arkflow_tpu.tpu.serving import GenerationServer

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY_DECODER)
    params = fam.init(jax.random.PRNGKey(11), cfg)
    kw.setdefault("prefill_chunk", 4)
    return GenerationServer(params, cfg, slots=2, page_size=4, max_seq=48,
                            eos_id=-1, name=name, **kw)


def _hist_counts(name: str, label: str) -> dict:
    from arkflow_tpu.obs import global_registry

    return {m.labels.get(label): m.count for m in global_registry().collect()
            if m.name == name}


def _delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


async def _traced_batch(tracer, server, prompts, max_new=5):
    """One batch as the stream runs it: its own trace, ambient around a
    ``process`` span, every row one ``generate`` call."""
    ctx = tracer.begin()
    with activate(tracer, ctx):
        with stage_span("process"):
            outs = await asyncio.gather(
                *[server.generate(p, max_new) for p in prompts])
    tracer.finish(ctx)
    return (ctx.trace_id if ctx is not None else None), outs


def _trace_by_id(tracer, trace_id: str) -> dict:
    return next(r for r in tracer.slowest(64) if r["trace_id"] == trace_id)


def test_generation_spans_land_in_each_requests_own_trace():
    """Every row of a generated batch gets gen_queue_wait / gen_prefill /
    gen_decode under ITS batch's ``process`` span — also when the serve loop
    was started by another batch (it used to live for ever inside the first
    caller's trace scope) — and the loop's own stages enter no trace."""
    import time

    tracer = _fresh_global()
    server = _tiny_generation_server("trace-own")

    async def go():
        t0 = time.perf_counter()
        # two batches in flight together: the loop the first one starts
        # serves the second one's rows too
        (id1, _), (id2, _) = await asyncio.gather(
            _traced_batch(tracer, server, GEN_PROMPTS[:2]),
            _traced_batch(tracer, server, GEN_PROMPTS[2:]))
        first_before = _trace_by_id(tracer, id1)
        n_first = len(first_before["spans"])
        # a third batch after both finished: nothing lands in a closed trace
        id3, _ = await _traced_batch(tracer, server, GEN_PROMPTS[:1])
        return t0, time.perf_counter(), (id1, id2, id3), n_first

    t0, t1, ids, n_first = asyncio.run(asyncio.wait_for(go(), timeout=120))
    for trace_id, rows in zip(ids, (2, 2, 1)):
        rec = _trace_by_id(tracer, trace_id)
        process = [s for s in rec["spans"] if s["stage"] == "process"]
        assert len(process) == 1
        for stage in GEN_STAGES:
            spans = [s for s in rec["spans"] if s["stage"] == stage]
            assert len(spans) == rows, (stage, rec["spans"])
            assert all(s["parent_id"] == process[0]["span_id"] for s in spans)
            # the monotonic start: on the clock this test stamped with
            assert all(t0 <= s["start_mono_s"] <= t1 for s in spans)
        assert {s["stage"] for s in rec["spans"]} == {"process", *GEN_STAGES}
        prefill = [s for s in rec["spans"] if s["stage"] == "gen_prefill"]
        assert all(s["attrs"]["chunks"] >= 1 and s["attrs"]["prompt_tokens"]
                   for s in prefill)
        decode = [s for s in rec["spans"] if s["stage"] == "gen_decode"]
        assert all(s["attrs"]["new_tokens"] == 5 for s in decode)
    assert len(_trace_by_id(tracer, ids[0])["spans"]) == n_first
    # a span recorded into a finished trace would reopen it
    assert tracer.summary()["traces_open"] == 0


GEN_SERVERS = pytest.mark.parametrize("server_kw", [
    dict(prefill_chunk=4),                    # chunked prefill + decode
    dict(prefill_chunk=0),                    # one-shot prefill
    dict(prefill_chunk=4, dispatch_depth=2),  # one step ahead (the default)
    dict(prefill_chunk=4, speculative_tokens=2),
    dict(prefill_chunk=4, dispatch_depth=1),  # lockstep
], ids=["chunked", "one_shot", "depth2", "speculative", "lockstep"])
HOP_STAGES = ("gen_dispatch", "gen_ready_wait", "gen_fetch")
STEP_KINDS = {"decode", "chunk", "prefill", "verify", "fused"}


def _stage_hists() -> dict:
    """``arkflow_stage_seconds`` by (stage, kind): (seconds, observations);
    kind None for a stage observed without one."""
    from arkflow_tpu.obs import global_registry

    return {(m.labels["stage"], m.labels.get("kind")): (m.sum, m.count)
            for m in global_registry().collect()
            if m.name == "arkflow_stage_seconds"}


def _stages_since(before: dict) -> dict:
    """What ``arkflow_stage_seconds`` gained since ``before``, same keys."""
    added = {}
    for key, (s, c) in _stage_hists().items():
        s0, c0 = before.get(key, (0.0, 0))
        if c - c0:
            added[key] = (s - s0, c - c0)
    return added


def _run_counted(tag: str, server_kw: dict):
    """Serve GEN_PROMPTS on a fresh tiny server named by ``tag`` and its
    options. Returns the name, the outputs, what the run added to
    ``arkflow_stage_seconds`` by (stage, kind), and the device steps it made
    with how many of them left their tokens on the device (``unfetched``)
    and how many of those nobody waited for (``unwaited``: a chunk that ran
    ahead of a step that was)."""
    _fresh_global()
    name = tag + "-".join(f"{k}{v}" for k, v in server_kw.items())
    server = _tiny_generation_server(name, **server_kw)
    made = {"steps": 0, "unfetched": 0, "unwaited": 0, "fused": 0}
    for step in ("_decode", "_chunk", "_prefill", "_verify", "_fused"):
        if getattr(server, step) is None:  # no chunk rides this server's steps
            continue

        def counted(*a, _fn=getattr(server, step), _step=step, **kw):
            made["steps"] += 1
            made["fused"] += _step == "_fused"
            return _fn(*a, **kw)

        setattr(server, step, counted)
    run_step = server._run_device_step

    def run_counted(*a, final=True, **kw):
        made["unfetched"] += not final
        return run_step(*a, final=final, **kw)

    server._run_device_step = run_counted
    run_ahead, land = server._run_ahead, server._land

    def ahead_counted(key, packed, dev, apply=None, **kw):
        made["unfetched"] += apply is None
        return run_ahead(key, packed, dev, apply, **kw)

    def land_counted(rec, behind=None):
        made["unwaited"] += (rec.apply is None and behind is not None
                             and behind.apply is not None)
        return land(rec, behind)

    server._run_ahead, server._land = ahead_counted, land_counted
    before = _stage_hists()

    async def go():
        return await asyncio.gather(
            *[server.generate(p, 6) for p in GEN_PROMPTS])

    outs = asyncio.run(asyncio.wait_for(go(), timeout=120))
    return name, outs, _stages_since(before), made


@GEN_SERVERS
def test_serve_loop_stages_count_device_steps_and_token_gaps(server_kw):
    """One gen_prepare / gen_device_wait / gen_handoff / gen_apply
    observation per device step, whatever its kind; one token-gap
    observation per token after a request's first."""
    gaps0 = _hist_counts("arkflow_gen_token_gap_seconds", "model")
    name, outs, added, made = _run_counted("trace-count-", server_kw)
    assert made["steps"] > 0
    for stage in ("gen_prepare", "gen_device_wait", "gen_handoff", "gen_apply"):
        assert added[stage, None][1] == made["steps"], (stage, added, made)
    assert added["gen_admit", None][1] >= len(GEN_PROMPTS)
    gaps = _delta(_hist_counts("arkflow_gen_token_gap_seconds", "model"), gaps0)
    assert gaps == {name: sum(len(o) for o in outs) - len(outs)}


@GEN_SERVERS
def test_hop_stages_count_device_steps_by_kind(server_kw):
    """Inside the hop: one gen_dispatch and one gen_ready_wait per device
    step, one gen_fetch per step that fetched its tokens, each under the
    step's kind; the stages around the hop carry no kind."""
    _, _, added, made = _run_counted("hop-count-", server_kw)
    assert made["steps"] > 0
    count = {st: sum(c for (stage, _), (_, c) in added.items() if stage == st)
             for st in HOP_STAGES}
    assert count["gen_dispatch"] == made["steps"]
    assert count["gen_ready_wait"] == made["steps"] - made["unwaited"]
    assert count["gen_fetch"] == made["steps"] - made["unfetched"]
    assert made["unwaited"] <= made["unfetched"]
    if server_kw["prefill_chunk"]:
        # the 22-token prompt: chunks before its last, alone (left on the
        # device) or riding a decode step (fetched with its lanes' tokens)
        assert made["unfetched"] + made["fused"] > 0
    if server_kw.get("dispatch_depth") == 1 or "speculative_tokens" in server_kw:
        assert made["unwaited"] == 0  # lockstep waits for every step
    for stage, kind in added:
        if stage in HOP_STAGES:
            assert kind in STEP_KINDS, (stage, kind)
        elif stage in LOOP_STAGES:
            assert kind is None, (stage, kind)
    # per kind too: a kind's steps dispatch and wait once each (but a
    # chunk nobody waited for)
    for kind in {k for (_, k) in added if k}:
        unwaited = made["unwaited"] if kind == "chunk" else 0
        assert (added["gen_dispatch", kind][1]
                == added.get(("gen_ready_wait", kind), (0, 0))[1] + unwaited)


@GEN_SERVERS
def test_hop_stages_lie_inside_gen_device_wait(server_kw):
    _, _, added, _ = _run_counted("hop-inside-", server_kw)
    inside = sum(s for (stage, _), (s, _) in added.items()
                 if stage in HOP_STAGES)
    assert 0.0 < inside <= added["gen_device_wait", None][0]


def test_observe_stage_labels_make_distinct_label_sets():
    from arkflow_tpu.obs.trace import observe_stage

    _fresh_global()
    before = _stage_hists()
    observe_stage("hop_label_probe", 0.25)
    observe_stage("hop_label_probe", 0.5, kind="decode")
    observe_stage("hop_label_probe", 1.0, kind="chunk")
    observe_stage("hop_label_probe", 2.0, kind="chunk")
    assert _stages_since(before) == {
        ("hop_label_probe", None): (0.25, 1),
        ("hop_label_probe", "decode"): (0.5, 1),
        ("hop_label_probe", "chunk"): (3.0, 2)}


def test_tracing_disabled_same_tokens_and_no_span():
    tracer = _fresh_global()
    server = _tiny_generation_server("trace-off")

    async def go():
        return await _traced_batch(tracer, server, GEN_PROMPTS)

    try:
        _, on = asyncio.run(asyncio.wait_for(go(), timeout=120))
        tracer.configure(TracingConfig(enabled=False))
        recorded = tracer.spans_recorded
        stages0 = _hist_counts("arkflow_stage_seconds", "stage")
        _, off = asyncio.run(asyncio.wait_for(go(), timeout=120))
        assert off == on
        assert tracer.spans_recorded == recorded
        assert _delta(_hist_counts("arkflow_stage_seconds", "stage"),
                      stages0) == {}
    finally:
        tracer.configure(TracingConfig())


@pytest.mark.parametrize("dp", [1, 2], ids=["one_device", "dp2"])
@pytest.mark.parametrize("depth", [1, 2])
def test_device_fetch_recorded_beside_device_step(depth, dp):
    """The classify step's fetch (copy + host conversion) is its own span
    on the depth-1 and the depth-2 path, inside the step that contains it —
    on one device and sharded over a dp mesh, under the same stage names."""
    import numpy as np

    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.bucketing import BucketPolicy
    from arkflow_tpu.tpu.runner import ModelRunner

    tracer = _fresh_global()
    runner = ModelRunner(
        "bert_classifier",
        {"vocab_size": 128, "hidden": 16, "layers": 1, "heads": 2,
         "ffn": 32, "max_positions": 32, "num_labels": 2},
        buckets=BucketPolicy((2,), (16,)), dispatch_depth=depth,
        mesh_spec=MeshSpec(dp=dp) if dp > 1 else None)
    inputs = {"input_ids": np.zeros((2, 16), dtype=np.int32),
              "attention_mask": np.ones((2, 16), dtype=np.int32)}

    async def go():
        ctx = tracer.begin()
        with activate(tracer, ctx):
            await runner.infer(inputs)  # first-seen shape: the watched path
            await runner.infer(inputs)  # warm: depth 2 splits enqueue / fetch
        tracer.finish(ctx)
        return ctx.trace_id

    rec = _trace_by_id(tracer, asyncio.run(go()))
    by_stage: dict = {}
    for s in rec["spans"]:
        by_stage.setdefault(s["stage"], []).append(s["dur_ms"])
    assert len(by_stage["device_fetch"]) == 2
    assert len(by_stage["device_step"]) == 1
    steps = by_stage["device_step_first"] + by_stage["device_step"]
    assert all(step >= fetch
               for step, fetch in zip(steps, by_stage["device_fetch"]))


def test_loop_stage_feeds_histogram_and_profiler_not_the_trace_tree(tmp_path):
    """The form for work that belongs to no request: stage histogram and a
    profiler annotation of the same name (kind appended), no span — even
    with a trace scope ambient."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    from arkflow_tpu.obs.trace import annotated, loop_stage

    tracer = _fresh_global()
    before = _hist_counts("arkflow_stage_seconds", "stage")
    ctx = tracer.begin()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with activate(tracer, ctx):
            with loop_stage("gen_prepare", "decode"):
                pass
            with annotated("gen_device_wait:decode") as wait:
                pass
    finally:
        jax.profiler.stop_trace()
    assert wait.dur_s >= 0.0
    assert _delta(_hist_counts("arkflow_stage_seconds", "stage"),
                  before) == {"gen_prepare": 1}
    assert tracer.spans_recorded == 0
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = {e.name for pl in ProfileData.from_file(path).planes
             for ln in pl.lines for e in ln.events}
    assert {"gen_prepare:decode", "gen_device_wait:decode"} <= names
