"""Overload-control suite (runtime/overload.py + the wiring around it).

Covers the tentpole invariants end to end:

- deadline-aware admission sheds stale batches BEFORE the worker queue and
  at dequeue, never silently (error_output tagged ``overloaded`` or nack)
- the AIMD window shrinks multiplicatively when queue wait overruns the
  deadline budget and re-grows additively on recovery
- strict-priority bands survive queue shedding and brownout escalation
- cooperative backpressure: pull sources pause, HTTP rejects with
  429 + ``Retry-After`` (controller drain estimate / token-bucket deficit)
- the ``burst`` chaos fault really multiplies offered load, and the soak
  proves bounded p99 + the zero-silent-loss accounting identity

plus the satellites: ``pipeline.queue_size``, ``TokenBucket.time_until``,
and the reorder-window backpressure metrics.
"""

import asyncio
import json
import math
import time

import pytest

from arkflow_tpu.batch import (
    META_EXT_DEADLINE_MS,
    META_EXT_PRIORITY,
    MessageBatch,
)
from arkflow_tpu.components import Ack, NoopAck, ensure_plugins_loaded
from arkflow_tpu.config import PipelineConfig, StreamConfig
from arkflow_tpu.errors import ConfigError, EndOfInput, Overloaded
from arkflow_tpu.plugins.fault.schedule import FaultSchedule, parse_faults
from arkflow_tpu.plugins.fault.wrappers import (
    INPUT_KINDS,
    OUTPUT_KINDS,
    FaultInjectingInput,
)
from arkflow_tpu.plugins.input.memory import MemoryInput
from arkflow_tpu.plugins.output.drop import DropOutput
from arkflow_tpu.runtime import OverloadConfig, OverloadController, Pipeline, Stream
from arkflow_tpu.runtime.overload import (
    STATE_ADMIT,
    STATE_SHED,
    STATE_THROTTLE,
    attach_overload,
    input_pauses_on_overload,
)
from arkflow_tpu.utils.rate_limiter import TokenBucket

ensure_plugins_loaded()


def make_batch(payloads=(b"x",)) -> MessageBatch:
    return MessageBatch.new_binary(list(payloads))


def make_ctrl(name, *, deadline_ms=100.0, priority=0, protect=1, max_window=8,
              min_window=1, escalate_after=0, workers=1) -> OverloadController:
    cfg = OverloadConfig(enabled=True, deadline_ms=deadline_ms, priority=priority,
                         protect_priority=protect, max_window=max_window,
                         min_window=min_window, interval_s=0.0,
                         escalate_after=escalate_after)
    cfg.validate()
    return OverloadController(cfg, name=name, workers=workers)


class CollectOutput(DropOutput):
    def __init__(self):
        super().__init__()
        self.batches: list[MessageBatch] = []

    async def write(self, batch: MessageBatch) -> None:
        await super().write(batch)
        self.batches.append(batch)


# ---------------------------------------------------------------------------
# config parsing (pipeline.queue_size / deadline_ms / priority / overload)
# ---------------------------------------------------------------------------

def test_queue_size_default_and_override():
    cfg = PipelineConfig.from_mapping({"thread_num": 3, "processors": []})
    assert cfg.queue_size == 0
    assert cfg.effective_queue_size() == 12  # historical thread_num * 4
    cfg = PipelineConfig.from_mapping(
        {"thread_num": 3, "queue_size": 7, "processors": []})
    assert cfg.effective_queue_size() == 7


@pytest.mark.parametrize("bad", [-1, 1.5, True, "8"])
def test_queue_size_validation(bad):
    with pytest.raises(ConfigError):
        PipelineConfig.from_mapping(
            {"thread_num": 1, "queue_size": bad, "processors": []})


@pytest.mark.parametrize("extra, rejected", [
    ({"ingest_shards": 0}, False),
    ({"ingest_shards": 1}, True),
    ({"ingest_shards": 2}, True),
    ({"ingest_shards": 2, "process_pool": 2}, True),
])
def test_removed_ingest_shards_key_is_rejected_not_ignored(extra, rejected):
    """``from_mapping`` reads keys with ``.get``: without the check a
    left-over ``ingest_shards: 2`` would run unsharded in silence. The
    error sends the user to the two tiers that remain."""
    mapping = {"thread_num": 1, "processors": [], **extra}
    if not rejected:
        assert PipelineConfig.from_mapping(mapping).process_pool == 0
        return
    with pytest.raises(ConfigError) as err:
        PipelineConfig.from_mapping(mapping)
    assert "ingest_shards" in str(err.value)
    assert "process_pool" in str(err.value)
    assert "remote_tpu" in str(err.value)


@pytest.mark.parametrize("bad", [0, -250, True, "250"])
def test_deadline_ms_validation(bad):
    with pytest.raises(ConfigError):
        PipelineConfig.from_mapping(
            {"thread_num": 1, "deadline_ms": bad, "processors": []})


def test_priority_validation():
    with pytest.raises(ConfigError):
        PipelineConfig.from_mapping(
            {"thread_num": 1, "priority": "high", "processors": []})


def test_overload_disabled_by_default_enabled_by_deadline():
    cfg = PipelineConfig.from_mapping({"thread_num": 1, "processors": []})
    assert cfg.overload is None  # pre-overload behavior: admit everything
    cfg = PipelineConfig.from_mapping(
        {"thread_num": 1, "deadline_ms": 250, "processors": []})
    assert cfg.overload is not None and cfg.overload.enabled
    assert cfg.overload.deadline_ms == 250.0
    # explicit enable without a deadline: AIMD window on target_wait only
    cfg = PipelineConfig.from_mapping(
        {"thread_num": 1, "overload": True, "processors": []})
    assert cfg.overload is not None and cfg.overload.enabled
    assert cfg.overload.deadline_ms is None
    # a deadline with an explicit opt-out stays disabled but parsed
    cfg = PipelineConfig.from_mapping(
        {"thread_num": 1, "deadline_ms": 250, "overload": {"enabled": False},
         "processors": []})
    assert cfg.overload is not None and not cfg.overload.enabled


def test_overload_knobs_parse_and_validate():
    cfg = PipelineConfig.from_mapping({
        "thread_num": 2, "deadline_ms": 100, "priority": 1,
        "overload": {"protect_priority": 3, "max_window": 32, "min_window": 2,
                     "headroom": 0.25, "decrease": 0.75, "increase": 2,
                     "interval": "50ms", "target_wait": "200ms",
                     "escalate_after": 5},
        "processors": []}).overload
    assert (cfg.protect_priority, cfg.max_window, cfg.min_window) == (3, 32, 2)
    assert (cfg.headroom, cfg.decrease, cfg.increase) == (0.25, 0.75, 2.0)
    assert cfg.interval_s == pytest.approx(0.05)
    assert cfg.target_wait_s == pytest.approx(0.2)
    assert cfg.escalate_after == 5 and cfg.priority == 1
    for bad in ({"headroom": 0.0}, {"headroom": 1.5}, {"decrease": 1.0},
                {"decrease": 0.0}, {"increase": 0}, {"min_window": 0},
                {"max_window": -1}, {"escalate_after": -1},
                # wrong types raise ConfigError naming the key (never a bare
                # ValueError), and bools never pass as numbers
                {"headroom": "half"}, {"max_window": "8"},
                {"protect_priority": True}, {"decrease": False}):
        with pytest.raises(ConfigError):
            OverloadConfig.from_config(bad)
    with pytest.raises(ConfigError):
        OverloadConfig.from_config("yes")


def test_protecting_the_default_band_is_rejected():
    """`pipeline.priority >= overload.protect_priority` would exempt ALL
    traffic from queue shedding — the AIMD window silently becomes a no-op.
    Refused at config time instead."""
    with pytest.raises(ConfigError):
        PipelineConfig.from_mapping({"thread_num": 1, "deadline_ms": 250,
                                     "priority": 5, "processors": []})
    cfg = PipelineConfig.from_mapping(
        {"thread_num": 1, "deadline_ms": 250, "priority": 5,
         "overload": {"protect_priority": 6}, "processors": []}).overload
    assert cfg.protect_priority == 6
    # disabled controller doesn't care (the deadline still only tags batches)
    cfg = PipelineConfig.from_mapping(
        {"thread_num": 1, "deadline_ms": 250, "priority": 5,
         "overload": {"enabled": False}, "processors": []}).overload
    assert not cfg.enabled


# ---------------------------------------------------------------------------
# batch deadline / priority metadata helpers
# ---------------------------------------------------------------------------

def test_deadline_metadata_absolute_and_ttl():
    b = make_batch()
    assert b.deadline_unix_ms() is None
    assert b.remaining_deadline_ms() is None
    # no deadline column, no configured TTL, no ingest time -> no enforcement
    assert b.remaining_deadline_ms(None, now_ms=1000.0) is None

    stamped = b.with_deadline_ms(5000)
    assert stamped.has_column(META_EXT_DEADLINE_MS)
    assert stamped.deadline_unix_ms() == 5000.0
    # the absolute column wins over any configured TTL
    assert stamped.remaining_deadline_ms(10.0, now_ms=4600.0) == 400.0
    assert stamped.remaining_deadline_ms(now_ms=5700.0) == -700.0  # stale

    # TTL measured from ingest time when no absolute column
    ttl = b.with_ingest_time(2000).remaining_deadline_ms(300.0, now_ms=2100.0)
    assert ttl == 200.0
    # TTL with no ingest time: full budget (nothing to measure from)
    assert b.remaining_deadline_ms(300.0, now_ms=99.0) == 300.0
    # unparseable column -> treated as absent
    bad = b.with_ext_metadata({"deadline_ms": "soon"})
    assert bad.deadline_unix_ms() is None


def test_priority_band_metadata():
    b = make_batch()
    assert b.priority_band() == 0
    assert b.priority_band(default=3) == 3
    assert b.with_priority(2).priority_band() == 2
    assert b.with_priority(2).has_column(META_EXT_PRIORITY)
    assert b.with_ext_metadata({"priority": "premium"}).priority_band(1) == 1


# ---------------------------------------------------------------------------
# OverloadController units
# ---------------------------------------------------------------------------

def test_aimd_shrinks_multiplicatively_and_regrows_additively():
    ctrl = make_ctrl("aimd-t", deadline_ms=100.0, max_window=8)
    assert ctrl.window == 8.0 and ctrl.state == STATE_ADMIT
    # budget = 100ms * headroom 0.5 = 50ms; an 80ms wait overruns it
    ctrl.on_dequeue(0.08, now=1.0)
    assert ctrl.window == 4.0 and ctrl.state == STATE_SHED
    ctrl.on_dequeue(0.08, now=2.0)
    assert ctrl.window == 2.0
    # recovery: flood the p50 window with near-zero waits
    for i in range(70):
        ctrl.on_dequeue(0.0, now=3.0 + i)
    assert ctrl.window == 8.0 and ctrl.state == STATE_ADMIT
    assert ctrl.m_window.value == 8.0


def test_deadline_admission_sheds_stale_budget():
    ctrl = make_ctrl("dl-t")
    ctrl.observe_step(0.05)  # 50ms service time, empty queue
    assert ctrl.admit(0, remaining_ms=40.0) == "deadline"
    assert ctrl.admit(0, remaining_ms=500.0) is None
    assert ctrl.m_shed["deadline"].value == 1.0
    # stale sheds even in a protected band: the caller already gave up
    assert ctrl.admit(9, remaining_ms=-1.0) == "deadline"
    # no deadline carried -> the deadline check simply doesn't apply
    assert ctrl.admit(0, remaining_ms=None) is None


def test_queue_window_sheds_bulk_but_protects_priority_band():
    ctrl = make_ctrl("qw-t", max_window=2, protect=1)
    for _ in range(2):
        assert ctrl.admit(0, None) is None
        ctrl.on_enqueue()
    assert ctrl.queued == 2
    assert ctrl.admit(0, None) == "queue"  # bulk beyond the window
    assert ctrl.admit(1, None) is None  # protected band still lands
    assert ctrl.m_shed["queue"].value == 1.0
    assert ctrl.state == STATE_SHED


def test_disabled_controller_admits_everything():
    cfg = OverloadConfig(enabled=False, max_window=1)
    ctrl = OverloadController(cfg, name="off-t")
    ctrl.queued = 99
    assert ctrl.admit(0, remaining_ms=-5.0) is None
    assert not ctrl.should_pause() and not ctrl.should_reject()


def test_brownout_escalates_bands_then_relaxes_before_regrowing():
    ctrl = make_ctrl("brown-t", max_window=2, min_window=1, protect=2,
                     escalate_after=2)
    # sustained overrun: window pins at min, then the floor escalates one
    # band per `escalate_after` over-budget intervals, capped at protect
    for i in range(10):
        ctrl.on_dequeue(0.5, now=float(i + 1))
    assert ctrl.window == 1.0
    assert ctrl.admit_floor == 2
    assert ctrl.admit(0, None) == "priority"
    assert ctrl.admit(1, None) == "priority"
    assert ctrl.admit(2, None) is None  # protected band rides out the brownout
    assert ctrl.m_shed["priority"].value == 2.0
    # recovery relaxes the floor one band at a time BEFORE window regrowth
    ctrl._waits.clear()
    ctrl.on_dequeue(0.0, now=100.0)
    assert ctrl.admit_floor == 1 and ctrl.window == 1.0
    ctrl.on_dequeue(0.0, now=101.0)
    assert ctrl.admit_floor is None and ctrl.window == 1.0
    ctrl.on_dequeue(0.0, now=102.0)
    assert ctrl.admit_floor is None and ctrl.window == 2.0
    assert ctrl.state == STATE_ADMIT


def test_brownout_floor_relaxes_via_idle_recovery_when_all_traffic_shed():
    """Regression: once the floor sheds 100% of offered traffic at
    admission, nothing is ever enqueued, so no dequeue drives
    ``_maybe_adjust`` — the lazy idle-recovery path must step the floor
    down (one band per idle period) instead of browning out forever."""
    ctrl = make_ctrl("brown-stuck-t", max_window=2, min_window=1, protect=2,
                     escalate_after=2)
    for i in range(10):
        ctrl.on_dequeue(0.5, now=float(i + 1))
    assert ctrl.admit_floor == 2
    # every offered batch is priority-shed: queue stays empty, zero dequeues
    assert ctrl.admit(0, None) == "priority"
    # simulate the idle period without sleeping
    ctrl._last_activity = time.monotonic() - 1.0
    assert ctrl.admit(0, None) == "priority"  # triggers _idle_recover first
    assert ctrl.admit_floor == 1  # stepped down one band
    ctrl._last_activity = time.monotonic() - 1.0
    assert ctrl.admit(1, None) is None  # band 1 readmitted after next period
    assert ctrl.admit_floor is None
    # and a fresh idle period must pass before each step (paced, not instant)
    for i in range(10):
        ctrl.on_dequeue(0.5, now=float(100 + i))
    assert ctrl.admit_floor == 2
    ctrl._last_activity = time.monotonic() - 1.0
    assert ctrl.admit(0, None) == "priority"
    assert ctrl.admit_floor == 1
    assert ctrl.admit(0, None) == "priority"
    assert ctrl.admit_floor == 1  # no second step until another idle period


def test_predicted_wait_uses_littles_law_before_any_slow_dequeue():
    ctrl = make_ctrl("pred-t", workers=2)
    ctrl.observe_step(0.1)
    for _ in range(6):
        ctrl.on_enqueue()
    # no dequeues observed yet: the depth model must still see the backlog
    assert ctrl.predicted_wait_s() == pytest.approx(6 * 0.1 / 2)
    assert ctrl.queue_wait_p50_s() == 0.0


def test_should_pause_and_retry_after_drain_estimate():
    ctrl = make_ctrl("pause-t", max_window=2)
    assert not ctrl.should_pause()
    ctrl.observe_step(0.2)
    for _ in range(2):
        ctrl.on_enqueue()
    ctrl.state = STATE_SHED
    assert ctrl.should_pause() and ctrl.should_reject()
    assert ctrl.retry_after_s() == pytest.approx(2 * 0.2)  # queued * step / workers
    assert 0.05 <= ctrl.estimated_drain_s() <= 60.0
    # a dequeue frees capacity below the window -> sources resume
    ctrl.on_dequeue(0.0, now=1.0)
    assert not ctrl.should_pause()


def test_expire_counts_as_deadline_shed():
    ctrl = make_ctrl("exp-t")
    assert ctrl.expire() == "deadline"
    assert ctrl.m_shed["deadline"].value == 1.0
    assert ctrl.state == STATE_SHED


async def test_wait_capacity_wakes_on_dequeue():
    ctrl = make_ctrl("wake-t", max_window=1)
    ctrl.on_enqueue()
    t0 = time.monotonic()

    async def free_soon():
        await asyncio.sleep(0.02)
        ctrl.on_dequeue(0.0, now=1.0)

    task = asyncio.create_task(free_soon())
    await ctrl.wait_capacity(timeout=5.0)
    await task
    assert time.monotonic() - t0 < 2.0  # woke on the dequeue, not the timeout
    assert not ctrl._capacity_waiters  # waiter cleaned up


def test_controller_report_shape():
    ctrl = make_ctrl("rep-t", deadline_ms=123.0)
    ctrl.on_enqueue()
    rep = ctrl.report()
    assert rep["state"] == "admit" and rep["queued"] == 1
    assert rep["deadline_ms"] == 123.0 and rep["max_window"] == 8
    assert set(rep["shed"]) == {"deadline", "queue", "priority", "quota", "retry_budget"}
    assert (STATE_ADMIT, STATE_THROTTLE, STATE_SHED) == (0, 1, 2)


def test_overloaded_error_carries_retry_after():
    err = Overloaded("busy", retry_after_s=2.5)
    assert err.retry_after_s == 2.5
    assert isinstance(err, Exception)


# ---------------------------------------------------------------------------
# TokenBucket.time_until (satellite)
# ---------------------------------------------------------------------------

def test_token_bucket_time_until_deficit_and_cap():
    bucket = TokenBucket(capacity=4, refill_per_sec=2.0)
    assert bucket.time_until(1.0) == 0.0  # full bucket: available now
    for _ in range(4):
        assert bucket.try_acquire()
    assert not bucket.try_acquire()
    # empty bucket refilling at 2/s: 1 token in ~0.5s, 4 in ~2s
    assert bucket.time_until(1.0) == pytest.approx(0.5, abs=0.05)
    assert bucket.time_until(4.0) == pytest.approx(2.0, abs=0.05)
    # time_until must NOT consume tokens
    before = bucket._tokens
    bucket.time_until(1.0)
    assert bucket._tokens == pytest.approx(before, abs=1e-3)
    # beyond capacity can never be satisfied
    assert bucket.time_until(5.0) == math.inf


def test_token_bucket_refill_caps_at_capacity():
    bucket = TokenBucket(capacity=2, refill_per_sec=1000.0)
    for _ in range(2):
        assert bucket.try_acquire()
    time.sleep(0.02)  # 20 tokens' worth of refill against capacity 2
    assert bucket.time_until(2.0) == 0.0
    assert bucket.try_acquire() and bucket.try_acquire()
    assert not bucket.try_acquire()  # cap really held at 2

def test_token_bucket_rejects_bad_config():
    with pytest.raises(ConfigError):
        TokenBucket(capacity=0, refill_per_sec=1.0)
    with pytest.raises(ConfigError):
        TokenBucket(capacity=1, refill_per_sec=0.0)


# ---------------------------------------------------------------------------
# HTTP 429 + Retry-After (satellite + push-side overload shedding)
# ---------------------------------------------------------------------------

def test_retry_after_header_formatting():
    from arkflow_tpu.plugins.input.http import HttpInput

    assert HttpInput._retry_after(0.0) == {"Retry-After": "1"}  # floor 1s
    assert HttpInput._retry_after(1.2) == {"Retry-After": "2"}  # ceil
    assert HttpInput._retry_after(7.0) == {"Retry-After": "7"}
    assert HttpInput._retry_after(math.inf) == {"Retry-After": "3600"}


async def test_http_rate_limit_and_overload_429_carry_retry_after():
    import aiohttp

    from arkflow_tpu.plugins.input.http import HttpInput

    inp = HttpInput("127.0.0.1", 18123, "/ingest",
                    limiter=TokenBucket(capacity=1, refill_per_sec=0.25))
    await inp.connect()
    try:
        url = "http://127.0.0.1:18123/ingest"
        async with aiohttp.ClientSession() as s:
            async with s.post(url, data=b"ok") as r:
                assert r.status == 200
            # bucket drained: 429 with the deficit-derived backoff
            # (1 token at 0.25/s -> ~4s, ceil >= 4)
            async with s.post(url, data=b"again") as r:
                assert r.status == 429
                assert int(r.headers["Retry-After"]) >= 4

            # engine-side overload: controller rejects regardless of the
            # client's own rate, with the queue-drain estimate
            ctrl = make_ctrl("http-t", max_window=1)
            ctrl.observe_step(2.0)
            ctrl.on_enqueue()
            ctrl.state = STATE_SHED
            attach_overload(inp, ctrl)
            assert inp._overload is ctrl
            inp.limiter = None
            async with s.post(url, data=b"shed me") as r:
                assert r.status == 429
                assert int(r.headers["Retry-After"]) == 2  # ceil(1 * 2.0s)
    finally:
        await inp.close()


# ---------------------------------------------------------------------------
# wiring helpers: wrapper-chain walk + cooperative-pause opt-in
# ---------------------------------------------------------------------------

def test_attach_and_pause_flags_walk_fault_wrapper_chains():
    from arkflow_tpu.plugins.input.http import HttpInput

    sched = FaultSchedule(parse_faults([], INPUT_KINDS, "input"), seed=1)
    inner = HttpInput("127.0.0.1", 0, "/x")
    wrapped = FaultInjectingInput(inner, sched)
    ctrl = make_ctrl("walk-t")
    attach_overload(wrapped, ctrl)  # must reach through ._inner
    assert inner._overload is ctrl
    attach_overload(wrapped, None)  # no controller: no-op, no error

    assert not input_pauses_on_overload(
        FaultInjectingInput(MemoryInput([b"a"]), sched))
    assert input_pauses_on_overload(
        FaultInjectingInput(MemoryInput([b"a"], pause_on_overload=True), sched))


def test_pull_inputs_declare_pause_and_push_inputs_do_not():
    from arkflow_tpu.plugins.input.http import HttpInput
    from arkflow_tpu.plugins.input.kafka import KafkaInput
    from arkflow_tpu.plugins.input.redis import RedisInput

    assert KafkaInput.pause_on_overload
    assert not HttpInput.pause_on_overload
    # redis: list mode is pull (LPOP, backlog on the server); pub/sub is not
    assert RedisInput("redis://r", "list", [], [], ["k"]).pause_on_overload
    assert not RedisInput("redis://r", "subscribe", ["c"], [], []).pause_on_overload


# ---------------------------------------------------------------------------
# burst chaos fault
# ---------------------------------------------------------------------------

async def test_burst_fault_multiplies_offered_load():
    msgs = [f"m{i}".encode() for i in range(4)]
    sched = FaultSchedule(
        parse_faults([{"kind": "burst", "every": 1, "times": 0, "factor": 3}],
                     INPUT_KINDS, "input"), seed=7)
    inp = FaultInjectingInput(MemoryInput(msgs), sched)
    await inp.connect()
    seen = []
    with pytest.raises(EndOfInput):
        while True:
            batch, ack = await inp.read()
            seen.extend(batch.to_binary())
            await ack.ack()  # duplicate deliveries carry NoopAcks: safe
    # every read amplified factor x: 4 originals + 8 duplicates
    assert len(seen) == 12
    assert {s.count(m) for m in msgs for s in [seen]} == {3}


def test_burst_fault_validation_and_family():
    with pytest.raises(ConfigError):
        parse_faults([{"kind": "burst", "factor": 1}], INPUT_KINDS, "input")
    with pytest.raises(ConfigError):
        parse_faults([{"kind": "burst", "factor": "4x"}], INPUT_KINDS, "input")
    with pytest.raises(ConfigError):  # input-family only
        parse_faults([{"kind": "burst"}], OUTPUT_KINDS, "output")
    spec = parse_faults([{"kind": "burst", "every": 1}], INPUT_KINDS, "input")[0]
    assert spec.factor == 4  # documented default multiplier


# ---------------------------------------------------------------------------
# stream integration: shed disposition is never silent
# ---------------------------------------------------------------------------

class StaleStampingInput(MemoryInput):
    """Memory source stamping alternate batches with an already-passed
    absolute deadline (odd indices survive un-stamped)."""

    def __init__(self, messages, stale_every_other=True):
        super().__init__(messages)
        self._n = 0
        self._every_other = stale_every_other

    async def read(self):
        batch, ack = await super().read()
        i = self._n
        self._n += 1
        if not self._every_other or i % 2 == 0:
            batch = batch.with_deadline_ms(time.time() * 1000.0 - 10_000)
        return batch, ack


async def test_stream_routes_shed_batches_to_error_output_tagged():
    msgs = [f"row{i}".encode() for i in range(8)]
    sink, shed = CollectOutput(), CollectOutput()
    stream = Stream(StaleStampingInput(msgs), Pipeline([]), sink,
                    error_output=shed, thread_num=1, name="shed-eo-t",
                    overload=OverloadConfig(enabled=True))
    await asyncio.wait_for(stream.run(asyncio.Event()), 30)

    delivered = [p for b in sink.batches for p in b.to_binary()]
    shed_rows = [p for b in shed.batches for p in b.to_binary()]
    assert sorted(delivered) == [f"row{i}".encode() for i in range(8) if i % 2]
    assert sorted(shed_rows) == [f"row{i}".encode() for i in range(8) if not i % 2]
    # accounting identity: offered == delivered + shed, all shed counted
    assert stream.m_batches_in.value == len(delivered) + len(shed_rows)
    assert stream.overload.m_shed["deadline"].value == len(shed_rows)
    for b in shed.batches:
        assert b.get_meta("__meta_ext_error") == "overloaded"
        assert b.get_meta("__meta_ext_shed_reason") == "deadline"


async def test_stream_nacks_shed_batch_without_error_output():
    from arkflow_tpu.runtime.stream import _WorkItem

    nacked, acked = [], []

    class RedeliverableAck(Ack):
        redeliverable = True

        async def ack(self):
            acked.append(1)

        async def nack(self):
            nacked.append(1)

    stream = Stream(MemoryInput([b"x"]), Pipeline([]), CollectOutput(),
                    thread_num=1, name="shed-nack-t",
                    overload=OverloadConfig(enabled=True))
    await stream._shed_item(_WorkItem(make_batch(), RedeliverableAck(), 0.0),
                            "queue")
    assert nacked == [1] and acked == []  # broker redelivers after brownout
    # non-redeliverable ack with no error_output: dropped WITH ack (counted,
    # logged — never a silently leaked in-flight delivery)
    await stream._shed_item(_WorkItem(make_batch(), NoopAck(), 0.0), "queue")


async def test_expired_absolute_deadline_is_acked_not_nacked():
    """Regression: an already-expired ABSOLUTE deadline can only get more
    expired on redelivery, so nacking it (no error_output) would respin
    shed->redeliver->shed forever — it must be dropped WITH ack instead.
    A TTL-based shed still nacks: redelivery re-stamps ingest time."""
    from arkflow_tpu.runtime.stream import _WorkItem

    nacked, acked = [], []

    class RedeliverableAck(Ack):
        redeliverable = True

        async def ack(self):
            acked.append(1)

        async def nack(self):
            nacked.append(1)

    stream = Stream(MemoryInput([b"x"]), Pipeline([]), CollectOutput(),
                    thread_num=1, name="shed-expired-t",
                    overload=OverloadConfig(enabled=True, deadline_ms=50.0))
    stale = make_batch().with_deadline_ms(time.time() * 1000.0 - 10_000)
    await stream._shed_item(_WorkItem(stale, RedeliverableAck(), 0.0),
                            "deadline")
    assert acked == [1] and nacked == []
    # unexpired absolute deadline: load may drop before it passes -> nack
    fresh = make_batch().with_deadline_ms(time.time() * 1000.0 + 60_000)
    await stream._shed_item(_WorkItem(fresh, RedeliverableAck(), 0.0),
                            "queue")
    assert nacked == [1] and acked == [1]


async def test_stream_expires_stale_batch_at_dequeue():
    """A batch admitted fresh but stale by dequeue time is shed by the
    worker-side expiry check (what bounds delivered-batch latency)."""
    from arkflow_tpu.runtime.stream import _WorkItem

    shed = CollectOutput()
    stream = Stream(MemoryInput([]), Pipeline([]), CollectOutput(),
                    error_output=shed, thread_num=1, name="expire-t",
                    overload=OverloadConfig(enabled=True, deadline_ms=10_000.0))
    stale = make_batch().with_deadline_ms(time.time() * 1000.0 - 1.0)
    inq, outq = asyncio.Queue(), asyncio.Queue()
    await inq.put(_WorkItem(stale, NoopAck(),
                            asyncio.get_running_loop().time()))
    from arkflow_tpu.runtime.stream import _DONE
    await inq.put(_DONE)
    await stream._do_processor(inq, outq)
    assert [b.get_meta("__meta_ext_shed_reason") for b in shed.batches] == ["deadline"]
    assert stream.overload.m_shed["deadline"].value == 1.0
    assert outq.qsize() == 1  # only the _DONE sentinel: nothing processed


def test_build_stream_wires_queue_size_and_controller():
    from arkflow_tpu.runtime import build_stream

    cfg = StreamConfig.from_mapping({
        "input": {"type": "memory", "messages": ["a"]},
        "pipeline": {"thread_num": 2, "queue_size": 6, "deadline_ms": 100,
                     "processors": []},
        "output": {"type": "drop"},
    })
    stream = build_stream(cfg, name="wire-t")
    assert stream.queue_size == 6
    assert stream.overload is not None
    assert stream.overload.cfg.deadline_ms == 100.0
    assert stream.overload.max_window == 6  # resolved from the queue size
    assert stream.overload.cfg.max_window == 0  # config keeps what was written

    cfg = StreamConfig.from_mapping({
        "input": {"type": "memory", "messages": ["a"]},
        "pipeline": {"thread_num": 2, "processors": []},
        "output": {"type": "drop"},
    })
    stream = build_stream(cfg, name="wire-off-t")
    assert stream.queue_size == 8 and stream.overload is None


# ---------------------------------------------------------------------------
# backpressure metrics pin-down (satellite)
# ---------------------------------------------------------------------------

async def test_reorder_window_fill_accumulates_backpressure_and_wait_metrics():
    """When the reorder window fills, stalled worker time lands in
    ``arkflow_backpressure_seconds_total`` AND every dequeue's wait lands in
    ``arkflow_queue_wait_seconds`` — the signals the AIMD controller and
    dashboards rely on."""
    import arkflow_tpu.runtime.stream as stream_mod

    n = 30
    old = stream_mod.MAX_PENDING
    stream_mod.MAX_PENDING = 2
    try:
        class SlowOutput(CollectOutput):
            async def write(self, batch):
                await asyncio.sleep(0.004)  # slow writer -> window fills
                await super().write(batch)

        sink = SlowOutput()
        stream = Stream(MemoryInput([str(i).encode() for i in range(n)]),
                        Pipeline([]), sink, thread_num=4, name="bp-metrics-t")
        await asyncio.wait_for(stream.run(asyncio.Event()), 30)
    finally:
        stream_mod.MAX_PENDING = old

    assert len(sink.batches) == n
    assert stream.m_backpressure_s.value > 0.0  # workers really stalled
    assert stream.m_queue_wait.count == n  # one observation per dequeue
    assert stream.m_queue_wait.sum > 0.0


# ---------------------------------------------------------------------------
# engine /health + the burst soak acceptance gate
# ---------------------------------------------------------------------------

def test_engine_health_reports_overload_controller_state():
    import aiohttp

    from arkflow_tpu.config import EngineConfig
    from arkflow_tpu.runtime.engine import Engine

    cfg = EngineConfig.from_mapping({
        "streams": [{
            "name": "ov-health",
            "input": {"type": "generate", "payload": "tick",
                      "interval": "20ms", "batch_size": 1},
            "pipeline": {"thread_num": 1, "deadline_ms": 500,
                         "processors": []},
            "output": {"type": "drop"},
        }],
        "health_check": {"enabled": True, "host": "127.0.0.1", "port": 18124},
    })
    engine = Engine(cfg)

    async def go():
        run_task = asyncio.create_task(engine.run())
        try:
            deadline = time.monotonic() + 20
            ov = None
            async with aiohttp.ClientSession() as s:
                while time.monotonic() < deadline:
                    await asyncio.sleep(0.1)
                    try:
                        async with s.get("http://127.0.0.1:18124/health") as r:
                            body = json.loads(await r.text())
                    except aiohttp.ClientError:
                        continue
                    ov = body.get("stream_health", {}).get(
                        "ov-health", {}).get("overload")
                    if ov is not None:
                        break
            assert ov is not None, "no overload report in /health"
            assert ov["state"] in ("admit", "throttle", "shed")
            assert ov["deadline_ms"] == 500.0
            assert set(ov["shed"]) == {"deadline", "queue", "priority", "quota", "retry_budget"}
        finally:
            engine.shutdown()
            await asyncio.wait_for(run_task, timeout=15)

    asyncio.run(go())


def test_chaos_soak_burst_fast_mode_smoke():
    """Acceptance gate (tools/chaos_soak.py --burst --fast): at sustained
    4x offered load the controlled run keeps delivered-batch p99 <= 2x the
    deadline with the zero-silent-loss accounting identity intact, while
    the uncontrolled run reproduces the unbounded-queue latency cliff."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        from chaos_soak import run_burst_soak
    finally:
        sys.path.pop(0)

    verdict = run_burst_soak(seconds=60.0, seed=7, factor=4, fast=True)
    assert verdict["pass"], verdict
    ctl = verdict["controlled"]
    assert ctl["identity_ok"] and ctl["p99_bounded"]
    assert ctl["lost_rows"] == 0
    assert ctl["shed_batches"] > 0  # the controller really shed load
    assert ctl["offered_batches"] == ctl["delivered_batches"] + ctl["shed_batches"]
    assert verdict["uncontrolled"]["overload_reproduced"], (
        "baseline failed to reproduce the latency cliff")
    assert verdict["uncontrolled"]["lost_rows"] == 0
