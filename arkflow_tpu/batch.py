"""Data plane: Arrow-backed message batches.

The unit of data flowing through every stream is a ``MessageBatch``: an
immutable wrapper over a ``pyarrow.RecordBatch`` (ref:
crates/arkflow-core/src/lib.rs:237-240). Two conventions carry over from the
reference verbatim so SQL processors see the same table shape:

- Raw/opaque payloads live in a binary column named ``__value__``
  (``DEFAULT_BINARY_VALUE_FIELD``, ref lib.rs:46).
- Broker-provenance metadata lives in ``__meta_*`` columns that are ordinary
  Arrow columns, queryable from SQL (ref lib.rs:53-63, 464-789):
  ``__meta_source``, ``__meta_partition``, ``__meta_offset``, ``__meta_key``,
  ``__meta_timestamp``, ``__meta_ingest_time`` and free-form
  ``__meta_ext_<name>`` columns.

Batches are shared by reference through the pipeline (the Rust reference uses
``Arc<MessageBatch>``, lib.rs:139); mutation always produces a new wrapper over
new (or structurally shared) Arrow arrays — Arrow buffers themselves are never
copied when a column is carried over.

``split(max_rows)`` mirrors ``split_batch`` row-chunking with the same default
chunk of 8192 rows (ref lib.rs:432-458).
"""

from __future__ import annotations

import time
from typing import Any, Iterable, Mapping, Sequence

import numpy as np
import pyarrow as pa

from arkflow_tpu.errors import ArkError

DEFAULT_BINARY_VALUE_FIELD = "__value__"
DEFAULT_RECORD_BATCH_ROWS = 8192

META_SOURCE = "__meta_source"
META_PARTITION = "__meta_partition"
META_OFFSET = "__meta_offset"
META_KEY = "__meta_key"
META_TIMESTAMP = "__meta_timestamp"
META_INGEST_TIME = "__meta_ingest_time"
META_EXT_PREFIX = "__meta_ext_"

#: overload-control metadata (runtime/overload.py): an ABSOLUTE wall-clock
#: deadline in epoch millis stamped by whoever owns the request's latency
#: budget, and an integer priority band for brownout-surviving traffic.
#: Both live under the ext prefix so they survive redelivery (unlike
#: ``__meta_ingest_time``, which every delivery re-stamps).
META_EXT_DEADLINE_MS = META_EXT_PREFIX + "deadline_ms"
META_EXT_PRIORITY = META_EXT_PREFIX + "priority"
#: multi-tenant isolation (runtime/overload.py): the tenant id a batch is
#: accounted against — weighted-fair admission shares, per-tenant quotas and
#: tenant-labeled shed/latency metrics all key on it. Stamped input-side
#: (HTTP header / auth subject, Kafka header, or static per-input config);
#: an ext column so it survives redelivery like deadline/priority.
META_EXT_TENANT = META_EXT_PREFIX + "tenant"
#: per-batch tracing (obs/trace.py): the trace context — trace id, parent
#: span id, head-sampling decision — as a compact JSON string. An ext
#: column on purpose: it survives redelivery, ``split_ack`` shares,
#: coalescer carve/merge slices and quarantine exactly like tenant/
#: deadline/priority, and it is excluded from ``batch_fingerprint`` so
#: tracing never perturbs dedup, routing affinity or attempt budgets.
META_EXT_TRACE = META_EXT_PREFIX + "trace"

#: The fixed (non-ext) metadata columns, in canonical order (ref lib.rs:53-63).
META_COLUMNS = (
    META_SOURCE,
    META_PARTITION,
    META_OFFSET,
    META_KEY,
    META_TIMESTAMP,
    META_INGEST_TIME,
)


def is_meta_column(name: str) -> bool:
    return name in META_COLUMNS or name.startswith(META_EXT_PREFIX)


#: Arrow types whose payload lives in an (offsets, values) buffer pair and can
#: therefore be exposed as flat ndarray views without touching Python objects.
_VARLEN_TYPES = (
    pa.types.is_binary, pa.types.is_large_binary,
    pa.types.is_string, pa.types.is_large_string,
)


def is_varlen_payload(typ: pa.DataType) -> bool:
    return any(check(typ) for check in _VARLEN_TYPES)


def binary_column_view(col: pa.Array) -> tuple[np.ndarray, np.ndarray]:
    """Zero-copy ``(values, offsets)`` ndarray views over a binary/string column.

    ``values`` is the column's whole uint8 data buffer; ``offsets`` is the
    ``n+1`` int64 positions of each row's payload inside it (absolute — no
    base subtraction needed), correctly windowed for sliced arrays. 32-bit
    offset types pay one O(n) widening copy of the *offsets only*; the payload
    bytes are never copied and no per-row Python objects are created.

    Null rows are NOT collapsed here (the spec allows them to span garbage
    bytes); callers that must treat nulls as empty check ``col.null_count``
    and mask lengths via ``col.is_null()``.
    """
    if not is_varlen_payload(col.type):
        raise ArkError(f"column type {col.type} has no binary payload view")
    buffers = col.buffers()
    n = len(col)
    wide = pa.types.is_large_binary(col.type) or pa.types.is_large_string(col.type)
    if buffers[1] is None:  # length-0 arrays may carry no offsets buffer
        offsets = np.zeros(1, np.int64)
    else:
        offsets = np.frombuffer(buffers[1], dtype=np.int64 if wide else np.int32)
        offsets = offsets[col.offset : col.offset + n + 1]
        if not wide:
            offsets = offsets.astype(np.int64)
    if buffers[2] is None:  # all-null column: no data buffer was allocated
        values = np.empty(0, np.uint8)
    else:
        values = np.frombuffer(buffers[2], dtype=np.uint8)
    return values, offsets


def batch_fingerprint(batch: "MessageBatch") -> bytes:
    """Stable identity of a batch across redeliveries: data + broker
    provenance columns, excluding per-delivery noise (ingest time, ext
    metadata the error path itself stamps). The ONE definition shared by the
    stream's delivery-attempt budget and the coalescer's poison-suspect
    table — their convergence argument requires identical exclusions.

    Sources that stamp offset metadata (kafka, pulsar, ...) get fully
    distinct keys; content-only sources emitting byte-identical batches
    share one key — an accepted approximation, since entries clear on
    success.
    """
    import hashlib

    rb = batch.record_batch
    keep = [n for n in rb.schema.names
            if n != META_INGEST_TIME and not n.startswith(META_EXT_PREFIX)]
    rb = rb.select(keep)
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, rb.schema) as w:
        w.write_batch(rb)
    return hashlib.blake2b(sink.getvalue().to_pybytes(), digest_size=16).digest()


def _repeat_array(value: Any, typ: pa.DataType, n: int) -> pa.Array:
    """Constant column of length ``n`` without a Python-level loop."""
    if value is None:
        return pa.nulls(n, typ)
    return pa.repeat(pa.scalar(value, type=typ), n)


class MessageBatch:
    """Immutable Arrow record batch + helpers. The engine's unit of data."""

    __slots__ = ("_rb",)

    def __init__(self, record_batch: pa.RecordBatch):
        if not isinstance(record_batch, pa.RecordBatch):
            raise TypeError(f"expected pyarrow.RecordBatch, got {type(record_batch)!r}")
        self._rb = record_batch

    # -- constructors ------------------------------------------------------

    @classmethod
    def new_arrow(cls, record_batch: pa.RecordBatch) -> "MessageBatch":
        """Wrap an existing Arrow batch (ref lib.rs ``new_arrow``)."""
        return cls(record_batch)

    @classmethod
    def from_table(cls, table: pa.Table) -> "MessageBatch":
        return cls(table.combine_chunks().to_batches(max_chunksize=None)[0]) if table.num_rows else cls(
            pa.RecordBatch.from_arrays(
                [pa.array([], type=f.type) for f in table.schema], schema=table.schema
            )
        )

    @classmethod
    def new_binary(cls, payloads: Sequence[bytes]) -> "MessageBatch":
        """One row per opaque payload, in the ``__value__`` column (ref lib.rs ``new_binary``)."""
        arr = pa.array(list(payloads), type=pa.binary())
        rb = pa.RecordBatch.from_arrays([arr], names=[DEFAULT_BINARY_VALUE_FIELD])
        return cls(rb)

    @classmethod
    def from_pydict(cls, data: Mapping[str, Sequence[Any]]) -> "MessageBatch":
        return cls(pa.RecordBatch.from_pydict(dict(data)))

    @classmethod
    def empty(cls) -> "MessageBatch":
        return cls(pa.RecordBatch.from_arrays([], names=[]))

    # -- basic accessors ---------------------------------------------------

    @property
    def record_batch(self) -> pa.RecordBatch:
        return self._rb

    @property
    def schema(self) -> pa.Schema:
        return self._rb.schema

    @property
    def num_rows(self) -> int:
        return self._rb.num_rows

    def __len__(self) -> int:
        return self._rb.num_rows

    @property
    def column_names(self) -> list[str]:
        return self._rb.schema.names

    def column(self, name: str) -> pa.Array:
        idx = self._rb.schema.get_field_index(name)
        if idx < 0:
            raise ArkError(f"no such column: {name!r}")
        return self._rb.column(idx)

    def has_column(self, name: str) -> bool:
        return self._rb.schema.get_field_index(name) >= 0

    def to_pydict(self) -> dict[str, list[Any]]:
        return self._rb.to_pydict()

    def __repr__(self) -> str:
        return f"MessageBatch(rows={self.num_rows}, cols={self.column_names})"

    def __eq__(self, other: object) -> bool:
        return isinstance(other, MessageBatch) and self._rb.equals(other._rb)

    # -- binary convention -------------------------------------------------

    def payload_view(self, field: str = DEFAULT_BINARY_VALUE_FIELD) -> tuple[np.ndarray, np.ndarray]:
        """Zero-copy ``(values, offsets)`` ndarray views of a payload column.

        The vectorized infeed accessor: row ``i``'s payload is
        ``values[offsets[i]:offsets[i+1]]``. String columns expose their
        UTF-8 buffer directly, so no per-row encode happens either. Callers
        that care about nulls-as-empty must check ``col.null_count``
        themselves (see ``binary_column_view``); ``to_binary`` does.
        """
        col = self.column(field)
        if not is_varlen_payload(col.type):
            raise ArkError(f"column {field!r} is {col.type}, not binary/string")
        return binary_column_view(col)

    def to_binary(self, field: str = DEFAULT_BINARY_VALUE_FIELD) -> list[bytes]:
        """Extract the opaque payload column as Python bytes (ref lib.rs ``to_binary``).

        Built on the zero-copy view: one slice of the Arrow data buffer is
        materialized as ``bytes``, then rows are cheap bytes slices of it —
        no per-row Arrow scalar boxing, no per-row UTF-8 encode.
        """
        values, offsets = self.payload_view(field)
        n = self.num_rows
        base = int(offsets[0]) if n else 0
        buf = values[base : int(offsets[n]) if n else 0].tobytes()
        col = self.column(field)
        if col.null_count:
            valid = ~col.is_null().to_numpy(zero_copy_only=False)
            return [
                buf[offsets[i] - base : offsets[i + 1] - base] if valid[i] else b""
                for i in range(n)
            ]
        return [buf[offsets[i] - base : offsets[i + 1] - base] for i in range(n)]

    # -- column surgery ----------------------------------------------------

    def filter_columns(self, names: Iterable[str]) -> "MessageBatch":
        """Project to the given columns, preserving batch order (ref lib.rs ``filter_columns``)."""
        keep_set = set(names)
        keep = [n for n in self.column_names if n in keep_set]
        return MessageBatch(self._rb.select(keep))

    def drop_columns(self, names: Iterable[str]) -> "MessageBatch":
        drop = set(names)
        keep = [n for n in self.column_names if n not in drop]
        return MessageBatch(self._rb.select(keep))

    def with_column(self, name: str, array: pa.Array) -> "MessageBatch":
        """Add or replace a column. Existing Arrow buffers are shared, not copied."""
        if len(array) != self.num_rows and self._rb.num_columns > 0:
            raise ArkError(
                f"column {name!r} length {len(array)} != batch rows {self.num_rows}"
            )
        arrays = []
        fields = []
        replaced = False
        for i, f in enumerate(self._rb.schema):
            if f.name == name:
                arrays.append(array)
                fields.append(pa.field(name, array.type))
                replaced = True
            else:
                arrays.append(self._rb.column(i))
                fields.append(f)
        if not replaced:
            arrays.append(array)
            fields.append(pa.field(name, array.type))
        return MessageBatch(pa.RecordBatch.from_arrays(arrays, schema=pa.schema(fields)))

    # -- metadata columns (ref lib.rs:464-789) -----------------------------

    def with_source(self, source: str) -> "MessageBatch":
        return self.with_column(META_SOURCE, _repeat_array(source, pa.string(), self.num_rows))

    def with_partition(self, partition: int) -> "MessageBatch":
        return self.with_column(META_PARTITION, _repeat_array(partition, pa.int64(), self.num_rows))

    def with_offset(self, offset: int) -> "MessageBatch":
        return self.with_column(META_OFFSET, _repeat_array(offset, pa.int64(), self.num_rows))

    def with_key(self, key: bytes | None) -> "MessageBatch":
        return self.with_column(META_KEY, _repeat_array(key, pa.binary(), self.num_rows))

    def with_timestamp(self, ts_millis: int) -> "MessageBatch":
        """Broker-assigned event timestamp, epoch millis."""
        return self.with_column(META_TIMESTAMP, _repeat_array(ts_millis, pa.int64(), self.num_rows))

    def with_ingest_time(self, ts_millis: int | None = None) -> "MessageBatch":
        """Engine ingest wall-clock, epoch millis (defaults to now)."""
        if ts_millis is None:
            ts_millis = int(time.time() * 1000)
        return self.with_column(META_INGEST_TIME, _repeat_array(ts_millis, pa.int64(), self.num_rows))

    def with_ext_metadata(self, kv: Mapping[str, str]) -> "MessageBatch":
        """Constant free-form metadata columns ``__meta_ext_<k>`` (ref lib.rs ``with_ext_metadata``)."""
        out = self
        for k, v in kv.items():
            out = out.with_column(META_EXT_PREFIX + k, _repeat_array(v, pa.string(), out.num_rows))
        return out

    def with_ext_metadata_per_row(self, key: str, values: Sequence[str | None]) -> "MessageBatch":
        """Per-row free-form metadata (ref lib.rs ``with_ext_metadata_per_row``)."""
        return self.with_column(META_EXT_PREFIX + key, pa.array(list(values), type=pa.string()))

    # -- overload-control metadata (runtime/overload.py) -------------------

    def with_deadline_ms(self, deadline_unix_ms: float) -> "MessageBatch":
        """Stamp an ABSOLUTE delivery deadline (epoch millis). Survives
        redelivery — the remaining budget genuinely shrinks with every
        retry, unlike a TTL measured from the re-stamped ingest time."""
        return self.with_ext_metadata({META_EXT_DEADLINE_MS[len(META_EXT_PREFIX):]:
                                       str(int(deadline_unix_ms))})

    def with_priority(self, priority: int) -> "MessageBatch":
        """Stamp the batch's admission-priority band (higher = survives
        brownouts longer; bands >= the controller's ``protect_priority``
        are never queue-shed)."""
        return self.with_ext_metadata({META_EXT_PRIORITY[len(META_EXT_PREFIX):]:
                                       str(int(priority))})

    def with_tenant(self, tenant: str) -> "MessageBatch":
        """Stamp the tenant id this batch is accounted against (weighted-fair
        admission shares + per-tenant quotas, runtime/overload.py). Inputs
        stamp it from wherever the deployment keeps identity — an HTTP
        header, the auth subject, a Kafka header, or static config."""
        return self.with_ext_metadata({META_EXT_TENANT[len(META_EXT_PREFIX):]:
                                       str(tenant)})

    def with_trace(self, ctx) -> "MessageBatch":
        """Stamp (or replace) the batch's trace context
        (``obs.trace.TraceContext``); a constant column — every row of a
        batch shares one trace."""
        return self.with_column(
            META_EXT_TRACE, _repeat_array(ctx.to_json(), pa.string(),
                                          self.num_rows))

    def trace_context(self):
        """The batch's trace context, or None when untraced/malformed.
        Reads row 0 — a merged emission is re-stamped with its own trace
        (source contexts per row feed its parent links instead)."""
        from arkflow_tpu.obs.trace import TraceContext

        return TraceContext.from_json(self.get_meta(META_EXT_TRACE))

    def source_trace_contexts(self) -> list:
        """Distinct trace contexts across the rows of this batch, in
        first-seen row order — a merged emission carries one per source
        batch; the stream's coalesce parent links read them (and their
        sampled flags) before re-stamping."""
        from arkflow_tpu.obs.trace import TraceContext

        if not self.has_column(META_EXT_TRACE) or self.num_rows == 0:
            return []
        seen: dict[str, Any] = {}
        for v in self.column(META_EXT_TRACE).unique().to_pylist():
            ctx = TraceContext.from_json(v)
            if ctx is not None and ctx.trace_id not in seen:
                seen[ctx.trace_id] = ctx
        return list(seen.values())

    def source_trace_ids(self) -> list[str]:
        """Just the distinct trace ids (see ``source_trace_contexts``)."""
        return [c.trace_id for c in self.source_trace_contexts()]

    def tenant(self, default: str | None = None) -> str | None:
        """Tenant id from ``__meta_ext_tenant``, or ``default`` when the
        batch is untagged (single-tenant streams never pay for the column)."""
        raw = self.get_meta(META_EXT_TENANT)
        if raw is None:
            return default
        return str(raw)

    def deadline_unix_ms(self) -> float | None:
        """Absolute deadline from ``__meta_ext_deadline_ms``, or None."""
        raw = self.get_meta(META_EXT_DEADLINE_MS)
        if raw is None:
            return None
        try:
            return float(raw)
        except (TypeError, ValueError):
            return None

    def remaining_deadline_ms(self, default_ttl_ms: float | None = None,
                              now_ms: float | None = None) -> float | None:
        """Remaining latency budget in ms (possibly negative = already
        stale). The absolute deadline column wins; else ``default_ttl_ms``
        is measured from ``__meta_ingest_time``; None when the batch
        carries no deadline at all (admission skips the deadline check)."""
        if now_ms is None:
            now_ms = time.time() * 1000.0
        absolute = self.deadline_unix_ms()
        if absolute is not None:
            return absolute - now_ms
        if default_ttl_ms is not None:
            ingest = self.get_meta(META_INGEST_TIME)
            if ingest is not None:
                return default_ttl_ms - (now_ms - float(ingest))
            return default_ttl_ms
        return None

    def priority_band(self, default: int = 0) -> int:
        """Admission priority from ``__meta_ext_priority`` (int-parsed
        string column), falling back to the stream's configured default."""
        raw = self.get_meta(META_EXT_PRIORITY)
        if raw is None:
            return default
        try:
            return int(float(raw))
        except (TypeError, ValueError):
            return default

    def metadata_columns(self) -> list[str]:
        return [n for n in self.column_names if is_meta_column(n)]

    def data_columns(self) -> list[str]:
        return [n for n in self.column_names if not is_meta_column(n)]

    def strip_metadata(self) -> "MessageBatch":
        return MessageBatch(self._rb.select(self.data_columns()))

    def get_meta(self, name: str) -> Any:
        """First-row value of a metadata column, or None if absent/empty."""
        if not self.has_column(name) or self.num_rows == 0:
            return None
        return self.column(name)[0].as_py()

    # -- chunking / merge --------------------------------------------------

    def split(self, max_rows: int = DEFAULT_RECORD_BATCH_ROWS) -> list["MessageBatch"]:
        """Row-chunk into batches of at most ``max_rows`` (ref ``split_batch`` lib.rs:432-458).

        Zero-copy: uses Arrow slices over the same buffers.
        """
        if max_rows <= 0:
            raise ArkError("max_rows must be positive")
        n = self.num_rows
        if n <= max_rows:
            return [self]
        return [MessageBatch(self._rb.slice(i, min(max_rows, n - i))) for i in range(0, n, max_rows)]

    def slice(self, offset: int, length: int | None = None) -> "MessageBatch":
        return MessageBatch(self._rb.slice(offset, length))

    @staticmethod
    def concat(batches: Sequence["MessageBatch"]) -> "MessageBatch":
        """Concatenate schema-compatible batches (ref ``concat_batches`` usage, buffer/memory.rs:106-138)."""
        bs = [b for b in batches if b.num_rows > 0]
        if not bs:
            return batches[0] if batches else MessageBatch.empty()
        if len(bs) == 1:
            return bs[0]
        table = pa.Table.from_batches([b.record_batch for b in bs])
        rbs = table.combine_chunks().to_batches()
        assert len(rbs) == 1, "combine_chunks yields a single chunk per column"
        return MessageBatch(rbs[0])
