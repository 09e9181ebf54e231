"""Packed low-precision fast path (PR 6): token-budget coalescing, cascade
window carving, and the golden argmax-parity suite.

The packed + bf16 (and int8 W8A8) serving path is the measured default now,
so its parity against the float32 unpacked reference is pinned here — on
ragged mixes, empty/single-row edges, and under injected nacks where token-
carved split-ack shares must preserve at-least-once accounting.
"""

from __future__ import annotations

import asyncio

import numpy as np
import pyarrow as pa
import pytest

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import (
    Ack,
    Resource,
    build_component,
    ensure_plugins_loaded,
)
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.plugins.buffer.memory import MemoryBuffer
from arkflow_tpu.tpu.bucketing import (
    BucketPolicy,
    MicroBatchCoalescer,
    bucket_cap_bus,
)
from arkflow_tpu.tpu.extract import payload_token_estimates
from arkflow_tpu.tpu.packing import carve_row_windows, pack_tokens
from arkflow_tpu.tpu.tokenizer import HashTokenizer

ensure_plugins_loaded()

TINY_BERT = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4, "ffn": 64,
             "max_positions": 64, "num_labels": 2}

#: ragged text mix: mostly short, a long tail, plus empty and 1-char edges
WORD = b"sensor reading nominal "
RAGGED_TEXTS = ([WORD * k for k in (1, 2, 1, 3, 1, 2, 8, 1)] * 4
                + [b"", b"x", WORD * 12])


class RecAck(Ack):
    redeliverable = True

    def __init__(self, log, name):
        self.log, self.name = log, name

    async def ack(self):
        self.log.append(("ack", self.name))

    async def nack(self):
        self.log.append(("nack", self.name))


# ---------------------------------------------------------------------------
# golden argmax parity: packed low-precision vs unpacked float32
# ---------------------------------------------------------------------------

def _processor(dtype, packing):
    cfg = {
        "type": "tpu_inference",
        "model": "bert_classifier",
        "model_config": TINY_BERT,
        "max_seq": 32,
        "batch_buckets": [8, 16],
        "seq_buckets": [16, 32],
        "serving_dtype": dtype,
        "outputs": ["label"],
    }
    if packing:
        cfg["packing"] = True
    return build_component("processor", cfg, Resource())


def _labels(proc, texts):
    out = asyncio.run(proc.process(MessageBatch.new_binary(texts)))
    assert len(out) == 1
    return out[0].column("label").to_pylist()


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_packed_low_precision_argmax_parity(dtype):
    """The measured default (packed + bf16; int8 = W8A8) must label exactly
    like the float32 unpacked reference on ragged mixes and edge batches."""
    packed = _processor(dtype, packing=True)
    ref = _processor("float32", packing=False)

    for texts in (RAGGED_TEXTS, [b"single row"], [b""]):
        got = _labels(packed, texts)
        want = _labels(ref, texts)
        assert got == want, f"{dtype} packed labels diverge on {len(texts)} rows"


def test_packed_parity_empty_batch_short_circuits():
    packed = _processor("bfloat16", packing=True)
    assert asyncio.run(packed.process(MessageBatch.new_binary([]))) == []


# ---------------------------------------------------------------------------
# token estimation (extract.payload_token_estimates)
# ---------------------------------------------------------------------------

def test_token_estimates_match_hash_tokenizer_exactly():
    """Default mode mirrors the hash tokenizer's word/punct split: estimate
    == true token count (+2 specials) for every row, so budget-sized
    emissions pack to the predicted row count."""
    tok = HashTokenizer(512)
    texts = RAGGED_TEXTS + [b"a,b;c!", b"  spaced   out  ", b"123 abc 456"]
    col = pa.array(texts, pa.binary())
    est = payload_token_estimates(col)
    _, mask = tok.encode_batch(texts, 1024)
    true = mask.sum(axis=1)
    np.testing.assert_array_equal(est, true)


def test_token_estimates_bytes_mode_and_clamp():
    col = pa.array([b"x" * 10, b"y" * 3, b""], pa.binary())
    est = payload_token_estimates(col, token_bytes=4.0)
    np.testing.assert_array_equal(est, [5, 3, 2])  # ceil(n/4)+2, empty -> 2
    np.testing.assert_array_equal(
        payload_token_estimates(col, token_bytes=4.0, max_tokens=3), [3, 3, 2])


def test_token_estimates_nulls_and_slices():
    col = pa.array([b"one two", None, b"three"], pa.binary())
    est = payload_token_estimates(col)
    assert est[1] == 2  # null estimates as empty ([CLS][SEP])
    sliced = pa.array([b"pad pad", b"one two three", b"tail"]).slice(1, 2)
    np.testing.assert_array_equal(payload_token_estimates(sliced), [5, 3])


# ---------------------------------------------------------------------------
# token-budget coalescer semantics
# ---------------------------------------------------------------------------

def _batch(texts):
    return MessageBatch.new_binary(texts)


def test_token_coalescer_holds_until_budget_then_carves_rows():
    log = []
    c = MicroBatchCoalescer([64], token_budget=40)
    c.add(_batch([b"one two three"] * 3), RecAck(log, 0))  # 5 tokens/row = 15
    assert c.pop_exact() is None and c.tokens == 15
    c.add(_batch([b"one two three"] * 4), RecAck(log, 1))  # 35 held
    assert c.pop_exact() is None
    c.add(_batch([b"one two three"] * 4), RecAck(log, 2))  # 55 held
    out, ack = c.pop_exact()
    # 8 rows x 5 tokens = 40 fits; row 9 would overflow the budget
    assert out.num_rows == 8
    assert c.rows == 3 and c.tokens == 15
    asyncio.run(ack.ack())
    # batch 0 and 1 fully inside the emission; batch 2 split at a row edge,
    # so its shared ack waits for the tail
    assert log == [("ack", 0), ("ack", 1)]
    tail, tail_ack = c.pop_flush()
    assert tail.num_rows == 3
    asyncio.run(tail_ack.ack())
    assert log == [("ack", 0), ("ack", 1), ("ack", 2)]


def test_token_coalescer_single_over_budget_row_flows_solo():
    log = []
    c = MicroBatchCoalescer([8], token_budget=4)
    c.add(_batch([WORD * 20]), RecAck(log, "big"))  # ~62 tokens, budget 4
    out, ack = c.pop_exact()
    assert out.num_rows == 1  # over-long rows flow; truncation is downstream
    asyncio.run(ack.ack())
    assert log == [("ack", "big")]
    assert c.rows == 0 and c.tokens == 0


def test_token_coalescer_nacked_emission_isolates_suspect():
    """Suspect isolation carries over to token mode: after a nack, the
    failing source batch re-emits SOLO (stable fingerprint for the stream's
    attempt budget) instead of regrouping with fresh traffic."""
    log = []
    c = MicroBatchCoalescer([64], token_budget=20)
    poison = _batch([b"poison pill row"] * 2)
    c.add(poison, RecAck(log, "p"))
    c.add(_batch([b"clean row here"] * 2), RecAck(log, "c"))
    out, ack = c.pop_exact()
    assert out.num_rows == 4
    asyncio.run(ack.nack())  # whole emission fails -> both sources nacked
    assert ("nack", "p") in log and ("nack", "c") in log
    # redelivery: the previously-nacked batch emits alone and first
    c.add(_batch([b"fresh traffic x"] * 2), RecAck(log, "f"))
    c.add(poison, RecAck(log, "p2"))
    solo, solo_ack = c.pop_exact()
    assert solo.num_rows == 2
    assert solo.to_binary() == [b"poison pill row"] * 2
    asyncio.run(solo_ack.nack())
    assert ("nack", "p2") in log


def test_token_coalescer_cap_shrinks_budget_proportionally():
    """OOM degradation composes: a bucket cap announced by the runner must
    shrink the token budget by the same ratio — the budget was sized to fill
    the old top (rows, seq) shape the device just proved it cannot hold."""
    c = MicroBatchCoalescer([8, 16, 32], token_budget=1024)
    c.cap(16)
    assert c.buckets == (8, 16) and c.token_budget == 512
    c.cap(8)
    assert c.token_budget == 256


def test_cap_bus_shrinks_live_token_coalescer():
    c = MicroBatchCoalescer([8, 16, 32], token_budget=2048)
    bus = bucket_cap_bus()
    bus.register(c)
    try:
        bus.announce(16)
        assert c.token_budget == 1024 and c.target == 16
    finally:
        bus.reset()


def test_token_coalescer_config_validation():
    with pytest.raises(ConfigError):
        MicroBatchCoalescer([8], token_budget=0)
    with pytest.raises(ConfigError):
        MicroBatchCoalescer([8], token_budget=4, token_bytes=-1.0)
    with pytest.raises(ConfigError):
        MicroBatchCoalescer([8], token_budget=4, max_row_tokens=0)


# ---------------------------------------------------------------------------
# token-carved split-ack accounting under injected nacks (fault wrappers)
# ---------------------------------------------------------------------------

class ListInput:
    def __init__(self, batches):
        from arkflow_tpu.components import NoopAck

        self._batches = list(batches)
        self._noop = NoopAck()

    async def connect(self):
        return None

    async def read(self):
        from arkflow_tpu.errors import EndOfInput

        if not self._batches:
            raise EndOfInput()
        return self._batches.pop(0), self._noop

    async def close(self):
        return None


class CollectOutput:
    def __init__(self):
        self.batches = []

    async def connect(self):
        return None

    async def write(self, batch):
        self.batches.append(batch)

    async def close(self):
        return None


def _payloads(sink):
    return [p for b in sink.batches for p in b.to_binary()]


def test_token_carved_split_ack_zero_silent_loss_under_nacks():
    """End-to-end accounting identity on the token-budget path: with a
    poison row failing every delivery (PR-1 fault wrapper), every offered
    row is either delivered or quarantined to error_output — token-carved
    split-ack shares never strand a source delivery in the broker."""
    from arkflow_tpu.plugins.fault.schedule import FaultSchedule, parse_faults
    from arkflow_tpu.plugins.fault.wrappers import (
        INPUT_KINDS,
        PROCESSOR_KINDS,
        FaultInjectingInput,
        FaultInjectingProcessor,
    )
    from arkflow_tpu.runtime import Pipeline, Stream

    # 4-token rows; budget 24 carves 6-row emissions across batch boundaries
    batches = [
        MessageBatch.new_binary([b"clean one a", b"clean two b", b"clean three c"]),
        MessageBatch.new_binary([b"poison pill x", b"clean four d"]),
        MessageBatch.new_binary([b"clean five e"] * 5),
    ]
    inp = FaultInjectingInput(
        ListInput(batches),
        FaultSchedule(parse_faults([], INPUT_KINDS, "input"), seed=7),
        redeliver_unacked=True)
    proc = FaultInjectingProcessor(
        None, FaultSchedule(parse_faults(
            [{"kind": "error", "match": "poison"}], PROCESSOR_KINDS, "processor"),
            seed=7))
    sink, err_sink = CollectOutput(), CollectOutput()
    buffer = MemoryBuffer(capacity=64, timeout_s=0.5, coalesce_buckets=[64],
                          coalesce_deadline_s=0.05, token_budget=24)
    stream = Stream(inp, Pipeline([proc]), sink, error_output=err_sink,
                    buffer=buffer, thread_num=1, name="token-carve-chaos",
                    max_delivery_attempts=3)
    asyncio.run(asyncio.wait_for(stream.run(asyncio.Event()), timeout=30))

    delivered = _payloads(sink)
    quarantined = _payloads(err_sink)
    offered = {b"clean one a", b"clean two b", b"clean three c",
               b"poison pill x", b"clean four d", b"clean five e"}
    # zero silent loss: every offered row surfaced somewhere (at-least-once
    # allows duplicates for rows sharing a source batch with the poison row:
    # a head-share nack redelivers the WHOLE source batch)
    assert set(delivered) | set(quarantined) == offered
    # the poison row never reaches the sink; every clean row does
    assert b"poison pill x" in quarantined
    assert b"poison pill x" not in delivered
    assert offered - {b"poison pill x"} <= set(delivered) | set(quarantined)
    assert delivered.count(b"clean five e") >= 5
    assert stream.m_quarantined.value >= 1
    assert inp._outstanding == 0  # every broker delivery settled (ack/nack)


# ---------------------------------------------------------------------------
# cascade window carving (packing.carve_row_windows)
# ---------------------------------------------------------------------------

def _packed_layout(rng, n, smax, seq):
    lengths = rng.randint(1, smax + 1, n).astype(np.int64)
    ids = np.zeros((n, smax), np.int32)
    for i, l in enumerate(lengths):
        ids[i, :l] = rng.randint(1, 500, l)
    return pack_tokens(ids, lengths, seq)


def test_carve_windows_cascade_bucket_exact():
    """A layout bigger than the top bucket carves DOWN the grid: every
    window lands bucket-exact, only the sub-minimum residue pads."""
    rng = np.random.RandomState(11)
    pk = _packed_layout(rng, 200, 24, 32)
    assert pk.num_rows > 32
    buckets = (8, 16, 32)
    windows = carve_row_windows(pk, 32, 4096, buckets)
    sizes = [w["input_ids"].shape[0] for w, _ in windows]
    assert sum(sizes) == pk.num_rows
    for s in sizes[:-1]:
        assert s in buckets, f"non-terminal window {s} not bucket-exact"
    assert sizes[-1] <= 8 or sizes[-1] in buckets


def test_carve_windows_scatter_reassembles_original_order():
    rng = np.random.RandomState(12)
    pk = _packed_layout(rng, 120, 24, 32)
    windows = carve_row_windows(pk, 16, 64, (8, 16))
    seen = np.concatenate([idx for _, idx in windows])
    np.testing.assert_array_equal(np.sort(seen), np.arange(pk.num_examples))
    for inputs, idx in windows:
        p = inputs["input_ids"].shape[0]
        assert inputs["example_row"].shape[0] == len(idx)
        assert (inputs["example_row"] >= 0).all()
        assert (inputs["example_row"] < p).all()
        # each example's window-local coordinates point at its original row
        np.testing.assert_array_equal(
            inputs["example_pos"], pk.example_pos[idx])


def test_carve_windows_respects_max_examples():
    # realistic minimum example = 2 tokens ([CLS][SEP]), so a 32-wide row
    # holds <= 16: a max_examples at that bound must always be honored
    rng = np.random.RandomState(13)
    lengths = rng.randint(2, 5, 150).astype(np.int64)
    ids = np.zeros((150, 4), np.int32)
    for i, l in enumerate(lengths):
        ids[i, :l] = rng.randint(1, 500, l)
    pk = pack_tokens(ids, lengths, 32)
    windows = carve_row_windows(pk, 32, 16, (8, 16, 32))
    for inputs, idx in windows:
        assert len(idx) <= 16
        assert inputs["input_ids"].shape[0] <= 32
    seen = np.concatenate([idx for _, idx in windows])
    np.testing.assert_array_equal(np.sort(seen), np.arange(150))


def test_carve_windows_edges():
    rng = np.random.RandomState(14)
    pk = _packed_layout(rng, 10, 8, 32)
    single = carve_row_windows(pk, 1024, 4096)
    assert len(single) == 1
    # idx is row-sorted (the scatter target), not input order: the set must
    # cover every example exactly once
    np.testing.assert_array_equal(np.sort(single[0][1]),
                                  np.arange(pk.num_examples))
    empty = pack_tokens(np.zeros((0, 8), np.int32), np.zeros(0, np.int64), 8)
    assert carve_row_windows(empty, 8, 8) == []
    with pytest.raises(ValueError):
        carve_row_windows(pk, 0, 8)


def test_carved_windows_model_outputs_match_uncarved():
    """Serving the carved windows and scattering by example_idx reproduces
    the single-dispatch packed outputs exactly (same dtype, same shapes)."""
    from arkflow_tpu.tpu.runner import ModelRunner

    rng = np.random.RandomState(15)
    lengths = rng.randint(1, 25, 64).astype(np.int64)
    ids = np.zeros((64, 32), np.int32)
    for i, l in enumerate(lengths):
        ids[i, :l] = rng.randint(1, 500, l)
    pk = pack_tokens(ids, lengths, 32)
    buckets = BucketPolicy((8, 16, 32, 64), (32,))
    runner = ModelRunner("bert_classifier", TINY_BERT, buckets=buckets, packed=True)
    whole = runner.infer_sync({
        "input_ids": pk.input_ids, "segment_ids": pk.segment_ids,
        "position_ids": pk.position_ids, "example_row": pk.example_row,
        "example_pos": pk.example_pos,
    })
    windows = carve_row_windows(pk, 16, buckets.max_examples(),
                                buckets.batch_buckets)
    assert len(windows) > 1
    out = np.empty(64, np.int32)
    for inputs, idx in windows:
        out[idx] = runner.infer_sync(inputs)["label"]
    np.testing.assert_array_equal(out, whole["label"])


# ---------------------------------------------------------------------------
# BucketPolicy token grid + example grid
# ---------------------------------------------------------------------------

def test_token_buckets_and_budget():
    p = BucketPolicy((8, 16, 32), (16, 64))
    assert p.token_buckets(64) == (512, 1024, 2048)
    assert p.token_budget(64) == 2048
    with pytest.raises(ConfigError):
        p.token_buckets(0)


def test_capped_policy_shrinks_token_grid():
    """After an OOM at bucket 32, the capped policy's token grid loses the
    32-row bucket too — budgets derived from it shrink with the device."""
    p = BucketPolicy((8, 16, 32), (16,), example_scale=4)
    capped = p.capped(32)
    assert capped.batch_buckets == (8, 16)
    assert capped.token_budget(16) == 256  # was 512
    assert capped.example_scale == 4  # packed grid survives degradation
    assert p.capped(8) is None  # nothing below the smallest bucket


def test_dp_scaled_token_grid_keeps_per_chip_shards_bucket_exact():
    """dp-sharded serving: every global token bucket divides into dp
    per-chip shares that are themselves bucket-exact on the base grid."""
    p = BucketPolicy((8, 16, 32), (16,), example_scale=2)
    dp = p.dp_scaled(4)
    assert dp.batch_buckets == (32, 64, 128)
    assert dp.example_scale == 2
    for global_tokens, base_tokens in zip(dp.token_buckets(16), p.token_buckets(16)):
        assert global_tokens == base_tokens * 4
        per_chip = global_tokens // 4
        assert per_chip in p.token_buckets(16)
    assert p.dp_scaled(1) is p


def test_example_buckets_extend_row_grid():
    p = BucketPolicy((8, 16), (32,), example_scale=4)
    assert p.example_buckets() == (8, 16, 32, 64)
    assert p.max_examples() == 64
    assert p.example_bucket(17) == 32
    # scale 1: example grid == row grid (unpacked serving unchanged)
    p1 = BucketPolicy((8, 16), (32,))
    assert p1.example_buckets() == (8, 16)


def test_example_scale_config_validation():
    with pytest.raises(ConfigError):
        BucketPolicy.from_config({"batch_buckets": [8], "seq_buckets": [16],
                                  "example_scale": 0})
    with pytest.raises(ConfigError):
        BucketPolicy.from_config({"batch_buckets": [8], "seq_buckets": [16],
                                  "example_scale": True})
    p = BucketPolicy.from_config({"batch_buckets": [8], "seq_buckets": [16]},
                                 default_example_scale=4)
    assert p.example_scale == 4


# ---------------------------------------------------------------------------
# config cross-validation + buffer plumbing
# ---------------------------------------------------------------------------

def _stream_map(buffer=None, packing=None):
    proc = {"type": "tpu_inference", "model": "bert_classifier",
            "model_config": TINY_BERT}
    if packing is not None:
        proc["packing"] = packing
    m = {"input": {"type": "memory", "messages": ["a"]},
         "pipeline": {"thread_num": 1, "processors": [proc]},
         "output": {"type": "drop"}}
    if buffer is not None:
        m["buffer"] = buffer
    return m


def test_config_rejects_token_budget_without_packing():
    from arkflow_tpu.config import StreamConfig

    buf = {"type": "memory", "capacity": 64,
           "coalesce": {"batch_buckets": [8], "deadline": "10ms",
                        "token_budget": 256}}
    with pytest.raises(ConfigError, match="packing"):
        StreamConfig.from_mapping(_stream_map(buffer=buf, packing=False))
    # packing on: accepted
    StreamConfig.from_mapping(_stream_map(buffer=buf, packing=True))
    # no tpu_inference processor at all: nothing to cross-check
    m = _stream_map(buffer=buf)
    m["pipeline"]["processors"] = []
    StreamConfig.from_mapping(m)


@pytest.mark.parametrize("bad", [0, -5, True, "many"])
def test_config_rejects_bad_token_budget(bad):
    from arkflow_tpu.config import StreamConfig

    buf = {"type": "memory", "capacity": 64,
           "coalesce": {"batch_buckets": [8], "deadline": "10ms",
                        "token_budget": bad}}
    with pytest.raises(ConfigError, match="token_budget"):
        StreamConfig.from_mapping(_stream_map(buffer=buf, packing=True))


def test_config_sees_through_fault_wrapped_processor():
    """Chaos streams wrap tpu_inference in a fault processor: the
    token-budget cross-check must look through `inner` or the exact
    misconfiguration it exists for slips past in every chaos config."""
    from arkflow_tpu.config import StreamConfig

    buf = {"type": "memory", "capacity": 64,
           "coalesce": {"batch_buckets": [8], "deadline": "10ms",
                        "token_budget": 256}}
    m = _stream_map(buffer=buf)
    m["pipeline"]["processors"] = [
        {"type": "fault", "faults": [],
         "inner": {"type": "tpu_inference", "model": "bert_classifier",
                   "model_config": TINY_BERT, "packing": False}}]
    with pytest.raises(ConfigError, match="packing"):
        StreamConfig.from_mapping(m)
    m["pipeline"]["processors"][0]["inner"]["packing"] = True
    StreamConfig.from_mapping(m)


def test_memory_buffer_rejects_unattainable_token_budget():
    """A token budget above capacity*4*max_row_tokens can never fill
    (write() blocks first), so every emission would silently wait out the
    deadline and flush as a fragment — reject it at construction."""
    with pytest.raises(ConfigError, match="attainable"):
        MemoryBuffer(capacity=64, timeout_s=0.1, coalesce_buckets=[8],
                     coalesce_deadline_s=0.05, token_budget=64 * 4 * 16 + 1,
                     max_row_tokens=16)
    MemoryBuffer(capacity=64, timeout_s=0.1, coalesce_buckets=[8],
                 coalesce_deadline_s=0.05, token_budget=64 * 4 * 16,
                 max_row_tokens=16)


def test_config_rejects_non_bool_packing():
    from arkflow_tpu.config import StreamConfig

    with pytest.raises(ConfigError, match="packing"):
        StreamConfig.from_mapping(_stream_map(packing="yes"))


def test_memory_buffer_builder_scales_token_budget_by_dp():
    buf = build_component("buffer", {
        "type": "memory", "capacity": 64,
        "coalesce": {"batch_buckets": [8], "deadline": "10ms",
                     "token_budget": 100, "dp": 2, "max_row_tokens": 16},
    }, Resource())
    assert buf._coalescer.token_budget == 200  # global = per-chip x dp
    assert buf._coalescer.buckets == (16,)


def test_memory_buffer_builder_rejects_bad_token_knobs():
    for coalesce in (
        {"batch_buckets": [8], "deadline": "10ms", "token_budget": -1},
        {"batch_buckets": [8], "deadline": "10ms", "token_budget": 8,
         "token_bytes": 0},
        {"batch_buckets": [8], "deadline": "10ms", "token_budget": 8,
         "max_row_tokens": 0},
    ):
        with pytest.raises(ConfigError):
            build_component("buffer", {"type": "memory", "capacity": 64,
                                       "coalesce": coalesce}, Resource())
