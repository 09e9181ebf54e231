"""Vectorized infeed path: golden parity vs the per-row reference, zero-copy
payload views, split-ack/coalescer semantics, and the padding-waste win.

The reference implementations here ARE the old per-row code (``as_py`` loops,
``np.pad``/``np.stack``) — the vectorized paths must stay byte-identical to
them for every column kind, including nulls, empty batches, truncation,
slices, and the uint8->float32 normalize path.
"""

import asyncio
import pathlib

import numpy as np
import pyarrow as pa
import pytest

from arkflow_tpu import native
from arkflow_tpu.batch import MessageBatch, binary_column_view
from arkflow_tpu.components import Ack, VecAck, ensure_plugins_loaded, split_ack
from arkflow_tpu.errors import ProcessError
from arkflow_tpu.plugins.buffer.memory import MemoryBuffer
from arkflow_tpu.tpu.bucketing import BucketPolicy, MicroBatchCoalescer
from arkflow_tpu.tpu.extract import extract_tensor
from arkflow_tpu.tpu.tokenizer import HashTokenizer

ensure_plugins_loaded()

TINY_BERT = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4, "ffn": 64,
             "max_positions": 64, "num_labels": 2}


# -- golden per-row references (the code the vectorized paths replaced) ------

def ref_binary_extract(col, want, dtype):
    size = int(np.prod(want))
    rows = []
    for v in col:
        buf = v.as_py() or b""
        arr = np.frombuffer(buf, dtype=np.uint8)
        if arr.size < size:
            arr = np.pad(arr, (0, size - arr.size))
        rows.append(arr[:size].reshape(want).astype(dtype))
    out = np.stack(rows) if rows else np.zeros((0, *want), dtype)
    if dtype == "float32":
        out = out / np.float32(255.0)
    return out


def ref_to_binary(col):
    return [b"" if v is None else (v.encode("utf-8") if isinstance(v, str) else v)
            for v in col.to_pylist()]


def batch_of(col):
    return MessageBatch(pa.RecordBatch.from_arrays([col], names=["c"]))


BINARY_CASES = [
    pa.array([b"abc", b"defgh", b""], type=pa.binary()),
    pa.array([b"abc", None, b"defgh", b""], type=pa.binary()),          # nulls
    pa.array([], type=pa.binary()),                                     # empty
    pa.array([None, None], type=pa.binary()),                           # all-null
    pa.array([b"0123456789abcdef" * 4], type=pa.binary()),              # truncation
    pa.array([b"x" * 7, b"y" * 3, b"z" * 9], type=pa.binary()).slice(1, 2),  # sliced
    pa.array([b"large payload", b"q"], type=pa.large_binary()),         # 64-bit offsets
]


@pytest.mark.parametrize("col", BINARY_CASES, ids=range(len(BINARY_CASES)))
@pytest.mark.parametrize("want,dtype", [((4,), "int32"), ((2, 3), "float32"),
                                        ((8,), "uint8")])
def test_binary_extract_parity(col, want, dtype):
    got = extract_tensor(batch_of(col), "c", "x", dtype, want, who="t")
    exp = ref_binary_extract(col, want, dtype)
    assert got.dtype == exp.dtype and got.shape == exp.shape
    np.testing.assert_array_equal(got, exp)


def test_float32_normalize_parity():
    """uint8 bytes -> float32 divides by 255 exactly like the old path."""
    col = pa.array([bytes(range(16))], type=pa.binary())
    got = extract_tensor(batch_of(col), "c", "x", "float32", (4, 4), who="t")
    np.testing.assert_array_equal(
        got, np.arange(16, dtype=np.float32).reshape(1, 4, 4) / np.float32(255.0))


@pytest.mark.parametrize("col,want,dtype", [
    (pa.array([[1.0, 2.0], [3.0, 4.0]], type=pa.list_(pa.float64())), (2,), "float32"),
    (pa.array([[[1, 2], [3, 4]], [[5, 6], [7, 8]]],
              type=pa.list_(pa.list_(pa.int64()))), (2, 2), "int32"),   # nested
    (pa.array([[1, 2, 3], [4, 5, 6]], type=pa.list_(pa.int32())).slice(1, 1),
     (3,), "int64"),                                                    # sliced
])
def test_list_extract_parity(col, want, dtype):
    got = extract_tensor(batch_of(col), "c", "x", dtype, want, who="t")
    flat = np.array([x for row in col.to_pylist()
                     for x in (np.array(row).reshape(-1))], dtype=dtype)
    np.testing.assert_array_equal(got, flat.reshape(len(col), *want))


def test_fixed_size_list_extract():
    col = pa.array([[1, 2], [3, 4]], type=pa.list_(pa.int64(), 2))
    got = extract_tensor(batch_of(col), "c", "x", "int32", (2,), who="t")
    np.testing.assert_array_equal(got, [[1, 2], [3, 4]])


def test_scalar_extract_parity():
    col = pa.array([1.5, 2.5, None])
    got = extract_tensor(batch_of(col), "c", "x", "float32", (), who="t")
    assert got.shape == (3,)
    np.testing.assert_array_equal(got[:2], [1.5, 2.5])
    with pytest.raises(ProcessError):
        extract_tensor(batch_of(col), "c", "x", "float32", (2,), who="t")


def test_list_bad_reshape_raises():
    col = pa.array([[1, 2, 3]], type=pa.list_(pa.int64()))
    with pytest.raises(ProcessError):
        extract_tensor(batch_of(col), "c", "x", "int32", (2,), who="t")


def test_no_rowwise_python_left_in_extract():
    """Acceptance criterion: the binary/list fast paths contain zero per-row
    ``as_py`` calls (and no ``to_pylist`` either)."""
    src = (pathlib.Path(__file__).parent.parent
           / "arkflow_tpu" / "tpu" / "extract.py").read_text()
    assert ".as_py(" not in src
    assert ".to_pylist(" not in src


# -- zero-copy payload views ------------------------------------------------

STRING_AND_BINARY = [
    pa.array([b"abc", None, b""], type=pa.binary()),
    pa.array(["héllo", "x", None], type=pa.string()),
    pa.array(["aaa", "bbb", "ccc"], type=pa.large_string()).slice(1, 2),
    pa.array([b"zz"], type=pa.large_binary()),
    pa.array([], type=pa.string()),
]


@pytest.mark.parametrize("col", STRING_AND_BINARY, ids=range(len(STRING_AND_BINARY)))
def test_to_binary_parity(col):
    assert batch_of(col).to_binary("c") == ref_to_binary(col)


def test_payload_view_is_zero_copy():
    col = pa.array([b"abcd", b"efgh"], type=pa.binary())
    values, offsets = binary_column_view(col)
    assert values.tobytes() == b"abcdefgh"
    assert offsets.tolist() == [0, 4, 8]
    # the view aliases the Arrow buffer: no copy was made
    assert values.base is not None


def test_payload_view_sliced_column():
    col = pa.array([b"aa", b"bbb", b"c"], type=pa.binary()).slice(1, 2)
    values, offsets = binary_column_view(col)
    rows = [values[offsets[i]:offsets[i + 1]].tobytes() for i in range(2)]
    assert rows == [b"bbb", b"c"]


def test_tokenizer_view_matches_list_path():
    tok = HashTokenizer(256)
    payloads = [b"hello world", b"", b"Sensor READING, nominal!", b"x" * 300]
    mb = MessageBatch.new_binary(payloads)
    values, offsets = mb.payload_view()
    ids_list, mask_list = tok.encode_batch(payloads, 16)
    ids_view, mask_view = tok.encode_batch_view(values, offsets, 16)
    np.testing.assert_array_equal(ids_list, ids_view)
    np.testing.assert_array_equal(mask_list, mask_view)


def test_tokenizer_view_sliced_column_parity(monkeypatch):
    """A sliced payload column's view (non-zero base offset into a larger
    parent buffer) tokenizes identically on both python and native paths."""
    tok = HashTokenizer(256)
    col = pa.array([b"first row", b"second row", b"third row"], type=pa.binary())
    sliced = batch_of(col.slice(1, 2))
    values, offsets = sliced.payload_view("c")
    ids_ref, mask_ref = tok.encode_batch([b"second row", b"third row"], 12)
    ids_nat, mask_nat = tok.encode_batch_view(values, offsets, 12)
    np.testing.assert_array_equal(ids_ref, ids_nat)
    monkeypatch.setattr(native, "hash_tokenize_view", lambda *a, **k: None)
    ids_py, mask_py = tok.encode_batch_view(values, offsets, 12)
    np.testing.assert_array_equal(ids_ref, ids_py)
    np.testing.assert_array_equal(mask_ref, mask_py)


def test_tokenizer_view_python_fallback_parity(monkeypatch):
    """The pure-Python paths (no native lib) agree with each other too."""
    monkeypatch.setattr(native, "hash_tokenize_batch", lambda *a, **k: None)
    monkeypatch.setattr(native, "hash_tokenize_view", lambda *a, **k: None)
    tok = HashTokenizer(256)
    payloads = [b"alpha beta", b"Gamma, delta!"]
    values, offsets = MessageBatch.new_binary(payloads).payload_view()
    ids_list, mask_list = tok.encode_batch(payloads, 12)
    ids_view, mask_view = tok.encode_batch_view(values, offsets, 12)
    np.testing.assert_array_equal(ids_list, ids_view)
    np.testing.assert_array_equal(mask_list, mask_view)


# -- split acks & coalescer --------------------------------------------------

class RecAck(Ack):
    redeliverable = True

    def __init__(self, log, name):
        self.log, self.name = log, name

    async def ack(self):
        self.log.append(("ack", self.name))

    async def nack(self):
        self.log.append(("nack", self.name))


def test_split_ack_fires_source_only_when_all_parts_ack():
    log = []
    a, b = split_ack(RecAck(log, "s"), 2)
    asyncio.run(a.ack())
    assert log == []
    asyncio.run(b.ack())
    assert log == [("ack", "s")]


def test_split_ack_any_nack_redelivers_source():
    log = []
    parts = split_ack(RecAck(log, "s"), 3)
    asyncio.run(parts[0].ack())
    asyncio.run(parts[1].nack())
    assert log == []  # waits for every share to resolve
    asyncio.run(parts[2].ack())
    assert log == [("nack", "s")]
    assert parts[0].redeliverable  # passthrough for the stream's nack gate


def test_coalescer_carves_bucket_exact():
    log = []
    c = MicroBatchCoalescer([4, 8])
    for i in range(5):  # 15 rows held, target 8
        c.add(MessageBatch.new_binary([f"{i}-{j}".encode() for j in range(3)]),
              RecAck(log, i))
    batch, ack = c.pop_exact()
    assert batch.num_rows == 8
    assert c.rows == 7
    assert c.pop_exact() is None  # sub-target remainder
    # flush carves bucket-exact against the SMALLER buckets too: 7 -> 4 + 3
    mid, mid_ack = c.pop_flush()
    assert mid.num_rows == 4
    tail, tail_ack = c.pop_flush()
    assert tail.num_rows == 3 and c.rows == 0
    assert c.pop_flush() is None
    asyncio.run(ack.ack())
    asyncio.run(mid_ack.ack())
    asyncio.run(tail_ack.ack())
    # every source acked exactly once, in order (batches 2/3 were split)
    assert log == [("ack", 0), ("ack", 1), ("ack", 2), ("ack", 3), ("ack", 4)]


def test_coalescer_flush_uses_smaller_buckets():
    """40 rows at deadline against buckets [8,16,32] carve 32 + 8: zero
    padding, instead of one 40-row batch padding to the top bucket."""
    log = []
    c = MicroBatchCoalescer([8, 16, 32])
    for i in range(4):
        c.add(MessageBatch.new_binary([b"x"] * 10), RecAck(log, i))
    first, _ = c.pop_flush()
    second, _ = c.pop_flush()
    assert (first.num_rows, second.num_rows) == (32, 8)
    assert c.pop_flush() is None and c.rows == 0


def test_memory_buffer_coalesce_requires_deadline():
    from arkflow_tpu.errors import ConfigError

    with pytest.raises(ConfigError):
        MemoryBuffer(capacity=64, coalesce_buckets=[8])


def test_memory_buffer_coalesce_deadline_flush():
    async def go():
        log = []
        buf = MemoryBuffer(capacity=64, timeout_s=1.0,
                           coalesce_buckets=[8], coalesce_deadline_s=0.02)
        await buf.write(MessageBatch.new_binary([b"a"] * 3), RecAck(log, "a"))
        out = await asyncio.wait_for(buf.read(), timeout=5)
        assert out[0].num_rows == 3  # deadline flushed the sub-bucket tail
        await out[1].ack()
        assert log == [("ack", "a")]
        await buf.close()

    asyncio.run(go())


# -- the padding-waste win ---------------------------------------------------

def _waste_stats():
    from arkflow_tpu.obs import global_registry

    for m in global_registry().collect():
        if getattr(m, "name", "") == "arkflow_padding_waste_frac":
            return m.sum, m.count
    return 0.0, 0


def _run_buffered_phase(runner, coalesce: bool) -> float:
    """Stream 3-row batches through a memory buffer into the runner; returns
    the phase's mean padding waste. Uncoalesced, each sub-bucket batch emits
    alone (capacity 3 = one write, the streaming arrival pattern where every
    micro-batch pads to its bucket solo); coalesced, the same writes carve
    bucket-exact 8-row emissions."""

    async def infer_emission(item):
        batch, ack = item
        n = batch.num_rows
        runner.infer_sync({"input_ids": np.ones((n, 16), np.int32),
                           "attention_mask": np.ones((n, 16), np.int32)})
        await ack.ack()

    async def go():
        buf = MemoryBuffer(
            capacity=3, timeout_s=0.5,
            coalesce_buckets=list(runner.buckets.batch_buckets) if coalesce else None,
            coalesce_deadline_s=0.5 if coalesce else None)
        log = []
        if not coalesce:
            # lockstep write/read: every 3-row arrival emits alone (capacity
            # 3), the pattern where each micro-batch pads to its bucket solo
            for i in range(8):
                await buf.write(MessageBatch.new_binary([b"x"] * 3), RecAck(log, i))
                await infer_emission(await buf.read())
            await buf.close()
            assert await buf.read() is None
            return

        async def writer():
            for i in range(8):  # 24 rows: three bucket-exact 8-row emissions
                await buf.write(MessageBatch.new_binary([b"x"] * 3), RecAck(log, i))
            await buf.close()

        async def reader():
            while True:
                item = await buf.read()
                if item is None:
                    return
                await infer_emission(item)

        await asyncio.gather(writer(), reader())

    s0, c0 = _waste_stats()
    asyncio.run(asyncio.wait_for(go(), timeout=60))
    s1, c1 = _waste_stats()
    assert c1 > c0
    return (s1 - s0) / (c1 - c0)


def test_coalescing_strictly_reduces_padding_waste():
    """Acceptance criterion: same sub-bucket traffic, strictly lower
    ``arkflow_padding_waste_frac`` with coalescing on."""
    from arkflow_tpu.tpu.runner import ModelRunner

    runner = ModelRunner("bert_classifier", TINY_BERT,
                         buckets=BucketPolicy((4, 8), (16,)))
    waste_off = _run_buffered_phase(runner, coalesce=False)
    waste_on = _run_buffered_phase(runner, coalesce=True)
    assert waste_on < waste_off
    assert waste_on == 0.0  # every coalesced dispatch was bucket-exact


# -- merged-batch ack / quarantine under faults ------------------------------

class CollectOutput:
    def __init__(self):
        self.batches = []

    async def connect(self):
        return None

    async def write(self, batch):
        self.batches.append(batch)

    async def close(self):
        return None


class ListInput:
    """Minimal multi-row-batch source: each read hands out one batch."""

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


def _payloads(sink):
    return [p for b in sink.batches for p in b.to_binary()]


def _chaos_stream(batches, *, coalesce_buckets, max_delivery_attempts,
                  redeliver, deadline=0.05, name="coalesce-chaos"):
    from arkflow_tpu.plugins.fault.schedule import FaultSchedule, parse_faults
    from arkflow_tpu.plugins.fault.wrappers import (
        INPUT_KINDS, PROCESSOR_KINDS, FaultInjectingInput, FaultInjectingProcessor,
    )
    from arkflow_tpu.runtime import Pipeline, Stream

    inp = FaultInjectingInput(
        ListInput(batches),
        FaultSchedule(parse_faults([], INPUT_KINDS, "input"), seed=7),
        redeliver_unacked=redeliver)
    proc = FaultInjectingProcessor(
        None, FaultSchedule(parse_faults(
            [{"kind": "error", "match": "poison"}], PROCESSOR_KINDS, "processor"),
            seed=7))
    sink, err_sink = CollectOutput(), CollectOutput()
    buffer = MemoryBuffer(capacity=64, timeout_s=0.5,
                          coalesce_buckets=coalesce_buckets,
                          coalesce_deadline_s=deadline)
    # unique name per test: stream metrics live in the process-global
    # registry keyed by label, so a shared name would share the counters
    stream = Stream(inp, Pipeline([proc]), sink, error_output=err_sink,
                    buffer=buffer, thread_num=1, name=name,
                    max_delivery_attempts=max_delivery_attempts)
    asyncio.run(asyncio.wait_for(stream.run(asyncio.Event()), timeout=30))
    return inp, stream, sink, err_sink


def test_coalesced_quarantine_after_redelivery_budget():
    """A bucket-exact merged batch that keeps failing redelivers
    ``max_delivery_attempts`` times in-session, then quarantines exactly once
    with attempt metadata; the clean emission delivers exactly once and no
    source delivery is left dangling in the broker."""
    inp, stream, sink, err_sink = _chaos_stream(
        [MessageBatch.new_binary([b"m0", b"poison", b"m2", b"m3"]),
         MessageBatch.new_binary([b"c0", b"c1", b"c2", b"c3"])],
        coalesce_buckets=[4], max_delivery_attempts=3, redeliver=True,
        name="coalesce-chaos-redeliver")

    assert sorted(_payloads(sink)) == [b"c0", b"c1", b"c2", b"c3"]
    assert sorted(_payloads(err_sink)) == [b"m0", b"m2", b"m3", b"poison"]
    assert stream.m_quarantined.value == 1
    assert stream.m_errors.value == 3  # poison emission failed every delivery
    assert err_sink.batches[0].get_meta("__meta_ext_delivery_attempts") == "3"
    assert inp._outstanding == 0  # every broker delivery settled (ack/nack)


def test_poison_regrouping_isolated_and_quarantined():
    """A poison source batch whose redeliveries would regroup with fresh
    traffic gets isolated: after its first nack the coalescer emits it SOLO
    (stable fingerprint), so the stream's attempt budget converges and it
    quarantines instead of nack-looping forever. Innocent neighbors swept
    into the first failing emission deliver on their solo retry."""
    inp, stream, sink, err_sink = _chaos_stream(
        # 2-row batches, bucket 4: emission1 = poison-batch + clean-batch
        # merged; the poison-batch's redeliveries then mint NEW groupings
        # unless isolation kicks in
        [MessageBatch.new_binary([b"poison", b"p1"]),
         MessageBatch.new_binary([b"c0", b"c1"]),
         MessageBatch.new_binary([b"c2", b"c3"]),
         MessageBatch.new_binary([b"c4", b"c5"])],
        coalesce_buckets=[4], max_delivery_attempts=3, redeliver=True,
        name="coalesce-chaos-isolate")

    assert sorted(_payloads(sink)) == [b"c0", b"c1", b"c2", b"c3", b"c4", b"c5"]
    assert sorted(_payloads(err_sink)) == [b"p1", b"poison"]
    assert stream.m_quarantined.value == 1
    assert err_sink.batches[0].num_rows == 2  # quarantined SOLO, not merged
    assert inp._outstanding == 0


def test_prefetch_path_forced_on_cpu(monkeypatch):
    """ARKFLOW_PREFETCH=1 exercises the eager device_put path (accelerator
    default) on the CPU backend; results and staging recycling are intact."""
    monkeypatch.setenv("ARKFLOW_PREFETCH", "1")
    from arkflow_tpu.tpu.runner import ModelRunner

    runner = ModelRunner("bert_classifier", TINY_BERT,
                         buckets=BucketPolicy((4,), (16,)))
    assert runner._prefetch

    async def go():
        ids = np.ones((3, 16), np.int32)
        mask = np.ones((3, 16), np.int32)
        outs = [await runner.infer({"input_ids": ids, "attention_mask": mask})
                for _ in range(3)]
        return outs

    outs = asyncio.run(go())
    for out in outs:
        assert out["label"].shape == (3,)
        np.testing.assert_array_equal(out["logits"], outs[0]["logits"])


def test_split_emission_quarantine_preserves_ack_set():
    """When the straddling source batch's rows land in BOTH a quarantined
    emission and a delivered one, its shared ack still settles exactly once
    (non-redeliverable source => immediate quarantine, no redelivery loop)."""
    inp, stream, sink, err_sink = _chaos_stream(
        [MessageBatch.new_binary([b"m0", b"poison", b"m2"]),   # emission1: these 3
         MessageBatch.new_binary([b"m3", b"m4", b"m5"])],      # + m3; tail m4,m5
        coalesce_buckets=[4], max_delivery_attempts=3, redeliver=False,
        name="coalesce-chaos-split")

    assert sorted(_payloads(sink)) == [b"m4", b"m5"]
    assert sorted(_payloads(err_sink)) == [b"m0", b"m2", b"m3", b"poison"]
    assert stream.m_quarantined.value == 1
    assert stream.m_errors.value == 1  # not redeliverable: quarantined at once
    assert err_sink.batches[0].get_meta("__meta_ext_delivery_attempts") == "1"
    assert inp._outstanding == 0  # the split source ack resolved both shares
