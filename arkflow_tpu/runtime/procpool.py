"""Process-pool pipeline execution: the GIL escape hatch.

The reference's ``thread_num`` workers are true multicore threads (Tokio,
ref crates/arkflow-core/src/stream/mod.rs:117-126). Ours share one GIL:
measured scaling was ~1.3x at 8 workers (CPU host, round 2) because the
Arrow/C++ kernels already release the GIL and the Python glue serializes
the rest. For pipelines whose
transforms are genuinely Python-bound (heavy `python`/`remap` logic,
many small batches), ``pipeline.process_pool: N`` runs the processor
chain in N worker PROCESSES instead:

- batches travel as Arrow IPC (zero-copy on the wire, metadata columns
  ride along verbatim);
- each worker builds its own processor chain from config once, at pool
  start (spawn context — never fork a process that may hold jax state);
- ack/ordering semantics are unchanged: the parent awaits the result
  before acking, sequence numbers are assigned in the parent.

Device processors (``tpu_inference``/``tpu_generate``) are rejected:
an XLA client per worker process would thrash the one real device —
device parallelism belongs to the mesh, not the host pool.
"""

from __future__ import annotations

import asyncio
from concurrent.futures import ProcessPoolExecutor
from typing import Optional, Sequence

import pyarrow as pa

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.connect.flight import batch_to_ipc as _rb_to_ipc
from arkflow_tpu.errors import ConfigError, ProcessError

#: processors that hold device/XLA state — never run them in pool workers
DEVICE_PROCESSORS = {"tpu_inference", "tpu_generate"}

_worker_pipeline = None  # per-process chain, built once by _init_worker
_worker_loop = None  # ONE persistent loop per worker: connections opened at
# connect() (redis temporaries, client sockets) are loop-bound; running each
# batch on a fresh asyncio.run loop would leave them attached to a dead loop


def batch_to_ipc(batch: MessageBatch) -> pa.Buffer:
    """Serialize for the process hop — the ONE IPC helper (connect/flight)
    shared with the cluster plane. Returns the Arrow buffer itself: pickle
    ships its bytes once; the old ``.to_pybytes()`` here copied every
    payload a second time first."""
    return _rb_to_ipc(batch.record_batch)


def ipc_to_batch(data) -> MessageBatch:
    with pa.ipc.open_stream(pa.BufferReader(data)) as reader:
        table = reader.read_all()
    return MessageBatch.from_table(table)


def _init_worker(processor_configs: list[dict],
                 temporary_configs: list[tuple[str, dict]]) -> None:
    """Pool-process initializer: build temporaries + the chain once per
    worker (each worker owns its own connections, like a worker thread in
    the reference owns its own client handles)."""
    global _worker_pipeline, _worker_loop
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
    from arkflow_tpu.runtime.pipeline import Pipeline

    ensure_plugins_loaded()
    resource = Resource()
    for tname, tcfg in temporary_configs:
        resource.temporaries[tname] = build_component("temporary", tcfg, resource)
    procs = [build_component("processor", p, resource) for p in processor_configs]
    _worker_pipeline = Pipeline(procs)
    _worker_loop = asyncio.new_event_loop()
    _worker_loop.run_until_complete(_worker_pipeline.connect())


def _ping() -> bool:
    return _worker_pipeline is not None


def _run_chain(ipc: bytes) -> list[bytes]:
    """Worker-side: one batch through the whole chain (on the worker's
    persistent loop, where the chain's connections live)."""
    outs = _worker_loop.run_until_complete(
        _worker_pipeline.process(ipc_to_batch(ipc)))
    return [batch_to_ipc(b) for b in outs]


class ProcessPoolPipeline:
    """Drop-in for ``runtime.pipeline.Pipeline`` backed by worker processes."""

    def __init__(self, processor_configs: Sequence[dict], workers: int,
                 temporary_configs: Sequence[tuple[str, dict]] = ()):
        for p in processor_configs:
            if p.get("type") in DEVICE_PROCESSORS:
                raise ConfigError(
                    f"process_pool cannot run device processor {p['type']!r} "
                    "(use mesh sharding for device parallelism)")
        if workers < 1:
            raise ConfigError("pipeline.process_pool must be >= 1")
        self._configs = [dict(p) for p in processor_configs]
        self._temporaries = [(n, dict(c)) for n, c in temporary_configs]
        self._workers = workers
        self._pool: Optional[ProcessPoolExecutor] = None

    def _ensure_pool(self) -> ProcessPoolExecutor:
        if self._pool is None:
            import multiprocessing as mp

            self._pool = ProcessPoolExecutor(
                max_workers=self._workers,
                mp_context=mp.get_context("spawn"),
                initializer=_init_worker,
                initargs=(self._configs, self._temporaries),
            )
        return self._pool

    async def connect(self) -> None:
        # spin the pool up (and surface chain build errors from the worker
        # initializer) before data flows
        pool = self._ensure_pool()
        loop = asyncio.get_running_loop()
        await asyncio.gather(*[
            loop.run_in_executor(None, lambda: pool.submit(_ping).result())
            for _ in range(self._workers)
        ])

    async def process(self, batch: MessageBatch) -> list[MessageBatch]:
        from concurrent.futures.process import BrokenProcessPool

        ipc = batch_to_ipc(batch)
        for attempt in (0, 1):
            pool = self._ensure_pool()
            try:
                outs = await asyncio.wrap_future(pool.submit(_run_chain, ipc))
                return [ipc_to_batch(o) for o in outs]
            except (ConfigError, ProcessError):
                raise
            except BrokenProcessPool as e:
                # a dead worker poisons the whole executor permanently —
                # rebuild it once and retry this batch; a second failure
                # goes to the stream's error path like any processor error
                pool.shutdown(wait=False, cancel_futures=True)
                if self._pool is pool:  # a concurrent caller may have
                    self._pool = None   # already rebuilt it — keep theirs
                if attempt == 1:
                    raise ProcessError(
                        f"process_pool broken twice; giving up on batch: {e}"
                    ) from e
            except Exception as e:  # unpicklable error etc.
                raise ProcessError(f"process_pool worker failed: {e}") from e

    async def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
