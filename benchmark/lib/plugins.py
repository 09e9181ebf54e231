"""The benchmark's input and sink, registered with the program's own plugin
registries (the pattern of ``chip_smoke.register_smoke_plugins``), and the
``Run`` they share with the harness.

The input offers the load and drives the phases of a run — warm-up rows,
fill, window, stop — by the host's monotonic clock; the sink stamps every
write. Both only count and stamp inside the window; everything that reduces
the stamps to metrics happens after the drain.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from benchmark.lib.traffic import DUE_NS, ROW_ID, Pool


def now_s() -> float:
    return time.perf_counter()


@dataclass
class Run:
    """State of one run, shared by the input, the sink and the harness."""

    traffic: dict
    pool: Pool
    warm: list  # pools of warm-up rows, sent one batch at a time
    seconds: float
    #: rows that must have been written before the window may open (one full
    #: turnover of the rows in flight), and the settle time after that
    fill_rows: int = 0
    settle_s: float = 1.0
    #: paced: arrival offsets (s) of every row from the start of pacing
    arrivals: Optional[np.ndarray] = None
    output_field: Optional[str] = None  # generate: the column of tokens

    t_open: Optional[float] = None
    t_close: Optional[float] = None

    reads: int = 0
    rows_read: int = 0
    acks: int = 0
    nacks: int = 0
    #: (time_s, rows, tokens) of every sink write
    writes: list = field(default_factory=list)
    rows_written: int = 0
    #: per-write arrays, window only: row ids, due stamps and the write time
    e2e_ms: list = field(default_factory=list)
    gen_late_ms: list = field(default_factory=list)
    input_lag_ms: list = field(default_factory=list)  # (time_s, array)
    #: what came out, for the comparison with the reference
    out_rows: list = field(default_factory=list)
    out_a: list = field(default_factory=list)  # labels, or token strings
    out_b: list = field(default_factory=list)  # scores

    def in_window(self, t: float) -> bool:
        return (self.t_open is not None and t >= self.t_open
                and (self.t_close is None or t <= self.t_close))

    def open_window(self, t: float) -> None:
        self.t_open = t
        self.t_close = t + self.seconds


def register_plugins() -> None:
    """Register ``bench_source`` and ``bench_sink`` once. Each takes the
    run's ``Run`` through its config mapping."""
    from arkflow_tpu.components import (Ack, Input, Output, register_input,
                                        register_output)
    from arkflow_tpu.components.registry import registered_types
    from arkflow_tpu.errors import EndOfInput

    if "bench_source" in registered_types("input"):
        return

    class CountedAck(Ack):
        def __init__(self, run: Run):
            self._run = run

        async def ack(self):
            self._run.acks += 1

        async def nack(self):
            self._run.nacks += 1

    class BenchSource(Input):
        """Warm-up rows first (one batch, then wait until they are written),
        then the traffic mix: a standing backlog, or rows paced by their due
        times. Opens the window once the fill is through, and ends the
        input at the first read after the window closed."""

        def __init__(self, run: Run):
            self.run = run
            self._cursor = 0
            self._warm_next = 0
            self._pace_t0: Optional[float] = None
            self._next = 0  # paced: index of the next arrival to deliver
            self._fill_done_at: Optional[float] = None

        async def connect(self):
            return None

        async def close(self):
            return None

        def _counted(self, batch):
            run = self.run
            run.reads += 1
            run.rows_read += batch.num_rows
            return batch.with_source("bench"), CountedAck(run)

        def _maybe_open(self) -> None:
            """Backlog cells: the window opens ``settle_s`` after
            ``fill_rows`` rows have been written. Paced cells: ``settle_s``
            after pacing began (their rows are short; the fill is the
            settle)."""
            run = self.run
            if run.t_open is not None:
                return
            t = now_s()
            if self._fill_done_at is None:
                if run.rows_written - self._warm_rows() >= run.fill_rows:
                    self._fill_done_at = t
                return
            if t - self._fill_done_at >= run.settle_s:
                run.open_window(t)

        def _warm_rows(self) -> int:
            return sum(w.n for w in self.run.warm)

        async def read(self):
            run = self.run
            if self._warm_next <= len(run.warm):
                # every warm-up batch is written (and its shape compiled)
                # before the next is read, and all before the traffic starts
                sent = sum(w.n for w in run.warm[:self._warm_next])
                while run.rows_written < sent:
                    await asyncio.sleep(0.005)
                j = self._warm_next
                self._warm_next += 1
                if j < len(run.warm):
                    due = np.full(run.warm[j].n, time.perf_counter_ns(), np.int64)
                    return self._counted(run.warm[j].window(0, run.warm[j].n, due))
            if run.t_close is not None and now_s() > run.t_close:
                raise EndOfInput()
            if run.traffic["arrival"] == "backlog":
                return await self._read_backlog()
            return await self._read_paced()

        async def _read_backlog(self):
            run = self.run
            self._maybe_open()
            rows = int(run.traffic["batch_rows"])
            due = np.full(rows, time.perf_counter_ns(), np.int64)
            batch = run.pool.window(self._cursor, rows, due)
            self._cursor += rows
            # yield once: a source that never awaits would starve the loop
            await asyncio.sleep(0)
            return self._counted(batch)

        async def _read_paced(self):
            run = self.run
            tick = float(run.traffic["tick_ms"]) / 1000.0
            if self._pace_t0 is None:
                self._pace_t0 = now_s()
                self._fill_done_at = self._pace_t0
            arr = run.arrivals
            while True:
                self._maybe_open()
                if self._next >= len(arr):
                        raise EndOfInput()
                # the tick in which the next undelivered row falls due
                k = int(arr[self._next] // tick) + 1
                due_at = self._pace_t0 + k * tick
                delay = due_at - now_s()
                if delay > 0:
                    await asyncio.sleep(delay)
                if run.t_close is not None and now_s() > run.t_close:
                        raise EndOfInput()
                # behind schedule (the stream did not read in time): hand
                # over everything that is due by now, not one tick of it
                k = max(k, int((now_s() - self._pace_t0) // tick))
                end = int(np.searchsorted(arr, k * tick, side="right"))
                rows = min(end - self._next, run.pool.n)
                if rows <= 0:
                    continue
                due_s = self._pace_t0 + arr[self._next:self._next + rows]
                due_ns = (due_s * 1e9).astype(np.int64)
                batch = run.pool.window(self._cursor, rows, due_ns)
                self._cursor += rows
                self._next += rows
                t = now_s()
                if run.in_window(t):
                    run.gen_late_ms.append((t - due_at) * 1e3)
                    run.input_lag_ms.append((t, (t - due_s) * 1e3))
                return self._counted(batch)

    class BenchSink(Output):
        def __init__(self, run: Run):
            self.run = run

        async def connect(self):
            return None

        async def close(self):
            return None

        async def write(self, batch):
            import pyarrow.compute as pc

            run = self.run
            t = now_s()
            n = batch.num_rows
            tokens = 0
            ids = batch.column(ROW_ID).to_numpy(zero_copy_only=False)
            if run.output_field is not None:
                col = batch.column(run.output_field)
                tokens = int(pc.sum(pc.add(pc.count_substring(col, " "), 1)
                                    ).as_py() or 0)
                run.out_a.append(col.to_pylist())
            else:
                run.out_a.append(
                    batch.column("label").to_numpy(zero_copy_only=False))
                run.out_b.append(
                    batch.column("score").to_numpy(zero_copy_only=False))
            run.out_rows.append(ids)
            run.rows_written += n
            run.writes.append((t, n, tokens))
            if run.in_window(t):
                due = batch.column(DUE_NS).to_numpy(zero_copy_only=False)
                run.e2e_ms.append(t * 1e3 - due / 1e6)

    @register_input("bench_source")
    def _source(config, resource):
        return BenchSource(config["run"])

    @register_output("bench_sink")
    def _sink(config, resource):
        return BenchSink(config["run"])
