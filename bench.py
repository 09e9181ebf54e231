"""Benchmark: streaming BERT-base classification throughput on one TPU chip.

Drives the real engine end-to-end (generate source -> memory-buffer
micro-batching -> tpu_inference BERT-base -> drop sink) — the hermetic stand-in
for BASELINE.json config 2 (Kafka -> BERT-base classify -> Kafka) with broker
I/O excluded so the number is rows/sec/chip. Prints ONE JSON line.

Env knobs: BENCH_SECONDS (default 15), BENCH_BATCH (1024), BENCH_SEQ (32),
BENCH_TINY=1 for a CPU-sized smoke run, BENCH_MODE=sql for the CPU reference
anchor (BASELINE.json config 1: generate -> json_to_arrow -> sql filter),
BENCH_PACKING (default 1: token-packed execution is the measured default —
several examples per model row, effective rows/s tracks real token count;
0 reverts to padded serving), BENCH_DTYPE (default bfloat16; int8 = W8A8),
BENCH_COALESCE (default follows BENCH_PACKING: token-budget coalescing in
the buffer carves emissions that fill the top compiled (rows, seq) shape
after packing), BENCH_RAGGED=1 for a mixed short/long payload distribution
(the realistic packing workload), BENCH_MODE=multichip for the multi-chip
scaling phase (1 chip vs BENCH_MC_DEVICES chips on a forced host mesh;
BENCH_MC_STYLE=dp|pool|pp picks dp-sharded dispatch vs replicated device
pool vs pipelined model segmentation — pp runs the full three-way dp/pool/pp
comparison with a latency-bound phase per style; emits scaling_efficiency). The packed default phase asserts argmax parity
against the float32 unpacked reference before its number becomes the
headline (BENCH_SKIP_PARITY=1 skips; a parity failure, like any phase that
throws, ends the run non-zero). Device modes need a TPU and exit non-zero
without one; BENCH_TINY=1 is the explicit CPU smoke.
"""

from __future__ import annotations

import asyncio
import json
import os
import time


def _backend() -> str:
    """The platform the bench actually executed on, read from the live jax
    backend at emit time — a tiny=0 run forced onto CPU (JAX_PLATFORMS=cpu)
    must not be labeled tpu by inference from flags."""
    import jax

    return jax.devices()[0].platform


def _require_tpu() -> None:
    """Device modes measure the chip or nothing: with no TPU the bench
    exits non-zero before any phase runs — a CPU number never appears under
    a device metric's name. ``BENCH_TINY=1`` is the explicit CPU smoke."""
    import sys

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench: found no TPU (jax reports platform {dev.platform!r}, "
              f"kind {dev.device_kind!r}); device modes do not fall back to "
              "the CPU — BENCH_TINY=1 is the explicit CPU smoke",
              file=sys.stderr, flush=True)
        sys.exit(1)


def _bench_dtype(tiny: bool) -> str:
    """The serving dtype every phase runs AND every artifact is tagged with
    — single source so the tags can never disagree with what was served.
    bf16 is the default on EVERY backend now (the measured fast path is
    packed + low-precision); BENCH_DTYPE=float32 reverts, =int8 serves W8A8."""
    return os.environ.get("BENCH_DTYPE", "bfloat16")


def _full_pow2_grid(batch: int) -> list[int]:
    """The packed processor's row-bucket grid: pow2 from 8 up to ``batch``
    (the runner's own grid helper, so bench and runner can never disagree
    on grid semantics)."""
    from arkflow_tpu.tpu.bucketing import pow2_buckets

    return pow2_buckets(8, batch)


def _bench_token_budget(batch: int, seq: int) -> int:
    """Tokens per coalesced emission: fills the top compiled (batch, seq)
    shape minus a 2-row margin for first-fit fragmentation. Single source
    for the stream config AND the BENCH_RESULT knob record, so the recorded
    budget can never diverge from what was served."""
    return batch * seq - 2 * seq


def _latency_dtype(tiny: bool) -> str:
    """Serving dtype for the bounded-load LATENCY phase: the bench dtype on
    accelerators, but float32 in tiny/CPU mode — XLA emulates bf16 on CPU
    (~9x worse committed p99 measured), and an emulated dtype is not what
    anyone deploys there, so it would only corrupt the <50ms target."""
    return "float32" if tiny else _bench_dtype(tiny)


def _bench_packing() -> bool:
    """Token packing is the measured default (ROADMAP item 3: the speed
    levers belong ON the measured path); BENCH_PACKING=0 reverts to padded
    serving."""
    return os.environ.get("BENCH_PACKING", "1") == "1"


def _bench_coalesce() -> bool:
    """Token-budget coalescing defaults on exactly when packing is on (its
    emissions are sized for the packer); BENCH_COALESCE forces either way."""
    default = "1" if _bench_packing() else "0"
    return os.environ.get("BENCH_COALESCE", default) == "1"


def _bench_integrity() -> str | None:
    """BENCH_INTEGRITY=<interval> runs the headline phase with the SDC
    integrity monitor probing on that cadence ("1" = 500ms; default 0 =
    probes off). Each probe fetches+hashes the param tree and runs the
    golden batch off-path while holding ONE in-flight permit, so any cost
    shows up as stolen device time in the headline — the overhead is
    recorded in the phase detail (integrity_probes) for the PERF.md
    probes-on vs probes-off comparison."""
    v = os.environ.get("BENCH_INTEGRITY", "0")
    if v in ("0", "", "off"):
        return None
    return "500ms" if v == "1" else v


# latency phase offered load: batch_size rows every interval. The artifact
# tags derive from these SAME constants, so tuning the phase cannot leave a
# stale literal in bench_logs/latest_latency.json.
LAT_BATCH = 8
LAT_INTERVAL_MS = 5
LAT_OFFERED_ROWS_PER_SEC = int(LAT_BATCH * 1000 / LAT_INTERVAL_MS)


def build_sql_config(batch: int) -> dict:
    """BASELINE config 1: the CPU reference anchor (no model)."""
    payload = '{"sensor": "temperature", "value": 42.5, "station": "eu-1"}'
    return {
        "name": "bench-sql",
        "input": {"type": "generate", "payload": payload, "interval": 0, "batch_size": batch},
        "pipeline": {
            "thread_num": int(os.environ.get("BENCH_SQL_WORKERS", "4")),
            # BENCH_SQL_POOL=N: run the chain in N worker processes instead
            # (GIL-escape comparison; see runtime/procpool.py)
            "process_pool": int(os.environ.get("BENCH_SQL_POOL", "0")),
            "processors": [
                {"type": "json_to_arrow"},
                {"type": "sql",
                 "query": "SELECT sensor, value * 1.8 + 32 AS fahrenheit, station "
                          "FROM flow WHERE value > 10"},
            ],
        },
        "output": {"type": "drop"},
    }


#: the CPU-sized smoke model every tiny phase (and the parity gate) serves
TINY_MODEL_CONFIG = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
                     "ffn": 64, "max_positions": 64, "num_labels": 2}


def build_stream_config(batch: int, seq: int, tiny: bool) -> dict:
    model_config = (
        dict(TINY_MODEL_CONFIG)
        if tiny
        # bf16 softmax halves scores bandwidth: ~11% of the step at b1024
        # (labels argmax-identical; BENCH_SOFTMAX_DTYPE=float32 reverts)
        else {"softmax_dtype": os.environ.get("BENCH_SOFTMAX_DTYPE", "bfloat16")}
    )
    payload = "stream processing on tpu: sensor reading nominal, no anomaly detected"
    packing = _bench_packing()
    ragged = os.environ.get("BENCH_RAGGED", "0") == "1"
    if ragged:
        # realistic length mix (mostly short, a long tail) — the workload
        # token packing exists for; rows rotate through the mix
        word = "sensor reading nominal "
        src = {"payloads": [word * 1, word * 2, word * 1, word * 3,
                            word * 1, word * 2, word * 8, word * 1]}
    else:
        src = {"payload": payload}
    if packing and _bench_coalesce():
        # token-budget coalescing: emissions carry the tokens that fill the
        # TOP compiled (rows, seq) shape after packing (minus a 2-row margin
        # for first-fit fragmentation), so the packed row count lands
        # bucket-exact instead of wherever the source batch size fell. The
        # deadline must cover the budget's fill time at device speed (short
        # payloads need several source batches per emission) or every
        # emission is a flush-sized fragment; 250ms only delays the FIRST
        # batches after an idle gap — at saturation the budget fills first.
        buffer = {"type": "memory", "capacity": batch, "timeout": "5ms",
                  "coalesce": {"batch_buckets": [batch], "deadline": "250ms",
                               "token_budget": _bench_token_budget(batch, seq),
                               "max_row_tokens": seq}}
    elif _bench_coalesce():
        # row mode: merged emissions land exactly on the compiled bucket
        buffer = {"type": "memory", "capacity": batch, "timeout": "5ms",
                  "coalesce": {"batch_buckets": [batch], "deadline": "5ms"}}
    else:
        buffer = {"type": "memory", "capacity": batch, "timeout": "5ms"}
    return {
        # per-phase stream name: metrics are labeled by stream, so the packed
        # phase must NOT share the padded phase's rows counter / e2e
        # histogram (a shared name would void the first-rows compile gate
        # and mix the two phases' quantiles)
        "name": "bench-packed" if packing else "bench",
        "input": {
            "type": "generate",
            **src,
            "interval": 0,
            "batch_size": batch,
        },
        "buffer": buffer,
        "pipeline": {
            # workers must cover the device queue depth or the semaphore
            # can't fill: each in-flight step is held by one processor call
            "thread_num": max(2, int(os.environ.get("BENCH_INFLIGHT", "6"))),
            "processors": [
                {
                    "type": "tpu_inference",
                    "model": "bert_classifier",
                    "model_config": model_config,
                    "max_seq": seq,
                    # packing shrinks the row dim to ~E*avg_len/seq and the
                    # cascade carve (tpu/packing.py carve_row_windows) emits
                    # bucket-exact windows down the grid, so the grid must
                    # reach SMALL buckets or every emission's sub-bucket
                    # residue pads up to the grid floor (a 48-row residue on
                    # a 128-floor grid is fill 0.37 — measured 20% capacity
                    # waste). Full pow2 grid: the warmup pair count grows,
                    # but the persistent compile cache makes it one-time
                    "batch_buckets": (_full_pow2_grid(batch)
                                      if packing else [batch]),
                    "seq_buckets": [seq],
                    "outputs": ["label", "score"],
                    "warmup": True,
                    # device queue depth: >2 hides per-dispatch latency
                    # (profile_step.py)
                    "max_in_flight": int(os.environ.get("BENCH_INFLIGHT", "6")),
                    # bf16 params on the chip: half the HBM + transfer,
                    # MXU-native; BENCH_DTYPE=int8 serves W8A8 (2x roofline)
                    "serving_dtype": _bench_dtype(tiny),
                    # token packing: several examples per model row, so the
                    # chip computes real tokens, not bucket padding
                    "packing": packing,
                    # BENCH_INTEGRITY: SDC probe cadence for the overhead
                    # phase (headline default is probes-off)
                    **({"integrity":
                        {"probe_interval": _bench_integrity()}}
                       if _bench_integrity() else {}),
                }
            ],
        },
        "output": {"type": "drop"},
    }


def build_latency_config(seq: int, tiny: bool) -> dict:
    """Latency mode: bounded input rate + small buckets + buffer-timeout
    micro-batching, so p50/p99 measure end-to-end latency rather than
    queueing under saturation (VERDICT r1 weak-point 3; target p99<50ms)."""
    model_config = dict(TINY_MODEL_CONFIG) if tiny else {}
    payload = "stream processing on tpu: sensor reading nominal, no anomaly detected"
    return {
        "name": "bench-lat",
        "input": {
            "type": "generate",
            "payload": payload,
            "interval": f"{LAT_INTERVAL_MS}ms",  # offered load far below saturation
            "batch_size": LAT_BATCH,
        },
        # timeout-driven micro-batching: emit whatever arrived every 10ms
        "buffer": {"type": "memory", "capacity": 64, "timeout": "10ms"},
        "pipeline": {
            "thread_num": 2,
            "processors": [
                {
                    "type": "tpu_inference",
                    "model": "bert_classifier",
                    "model_config": model_config,
                    "max_seq": seq,
                    # TPU: 2 buckets = 2 compiles before first rows
                    # (4 once blew the first-rows deadline -> no data)
                    "batch_buckets": [8, 16, 32, 64] if tiny else [8, 64],
                    "seq_buckets": [seq],
                    "outputs": ["label", "score"],
                    "warmup": True,
                    # headline precision on accelerators; float32 in tiny
                    # mode where CPU-emulated bf16 would 9x the p99
                    "serving_dtype": _latency_dtype(tiny),
                }
            ],
        },
        "output": {"type": "drop"},
    }


async def run_bench(seconds: float, batch: int, seq: int, tiny: bool,
                    mode: str = "bert", cfg_map: dict | None = None) -> dict:
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.runtime import build_stream

    import sys

    ensure_plugins_loaded()
    if cfg_map is not None:
        pass  # caller-built config (multichip phases)
    elif mode == "sql":
        cfg_map = build_sql_config(batch)
    elif mode == "latency":
        cfg_map = build_latency_config(seq, tiny)
    else:
        cfg_map = build_stream_config(batch, seq, tiny)
    cfg = StreamConfig.from_mapping(cfg_map)
    print("bench: building model...", file=sys.stderr, flush=True)
    # per-phase stream name: metrics are labeled by stream, so the latency
    # phase must NOT share the headline's e2e histogram (a shared "bench"
    # label once reported the headline's saturated p99 as the latency p99)
    stream = build_stream(cfg)  # labeled by cfg.name: per-phase metrics
    print("bench: model built; compiling + streaming...", file=sys.stderr, flush=True)
    cancel = asyncio.Event()

    # warmup phase: let the bucket executable compile, then reset counters
    reg = global_registry()
    rows_out = stream.m_rows_out
    e2e = stream.m_e2e_latency

    async def controller():
        # wait until the first rows flow (compile done), then time the window
        # (compiles of full-size models can take minutes each)
        t_deadline = time.time() + (300 if tiny else 900)
        while rows_out.value == 0 and time.time() < t_deadline:
            await asyncio.sleep(0.25)
        rows_start = rows_out.value
        t0 = time.perf_counter()
        await asyncio.sleep(seconds)
        elapsed = time.perf_counter() - t0
        cancel.set()
        controller.result = (rows_out.value - rows_start, elapsed)

    controller.result = (0, 1.0)
    from arkflow_tpu.obs.trace import global_tracer

    trace_seq0 = global_tracer().commit_seq()
    await asyncio.gather(stream.run(cancel), controller())
    rows, elapsed = controller.result
    # per-stage latency attribution for THIS phase only (trace-layer delta):
    # a rows/s regression names its stage instead of just shrinking a number
    breakdown = global_tracer().stage_breakdown(trace_seq0)
    return {
        "rows_per_sec": rows / elapsed if elapsed > 0 else 0.0,
        "p50_ms": e2e.quantile(0.50) * 1000.0,
        "p99_ms": e2e.quantile(0.99) * 1000.0,
        "rows": rows,
        "elapsed_s": elapsed,
        "stage_breakdown": {
            stage: {"p50_ms": s["p50_ms"], "p99_ms": s["p99_ms"],
                    "share_of_e2e": s["share_of_e2e"]}
            for stage, s in breakdown["stages"].items()},
    }


def _emit(obj: dict) -> None:
    """Print a metric JSON line AND persist it to BENCH_RESULT.json.

    The driver parses the last stdout JSON line; round 2 lost its number when
    a child's stderr spew got interleaved after it. The file is the
    belt-and-braces copy: always the most recent metric, always parseable."""
    import sys

    line = json.dumps(obj)
    print(line, flush=True)
    try:
        path = os.environ.get(
            "BENCH_RESULT_PATH",
            os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_RESULT.json"),
        )
        with open(path, "w") as f:
            f.write(line + "\n")
    except OSError as e:
        print(f"bench: could not write BENCH_RESULT file: {e}", file=sys.stderr)


def _relay_child(res) -> None:
    """Forward a re-exec'd child's output with the JSON line guaranteed last.

    stderr first (truncated if enormous — XLA warning spew once buried the
    metric), then stdout, so a driver reading merged output still finds the
    metric JSON as the tail."""
    import sys

    err = res.stderr.decode(errors="replace")
    if len(err) > 20000:
        err = err[:4000] + f"\n... [{len(err) - 8000} bytes elided] ...\n" + err[-4000:]
    sys.stderr.write(err)
    sys.stderr.flush()
    sys.stdout.write(res.stdout.decode(errors="replace"))
    sys.stdout.flush()


def main() -> None:
    import sys

    tiny = os.environ.get("BENCH_TINY", "0") == "1"
    mode = os.environ.get("BENCH_MODE", "bert")

    if mode == "multichip":
        _run_multichip_bench()
        return
    if mode == "generate" and os.environ.get("ARKFLOW_GEN_TP_CHILD") == "1":
        _generate_tp_child()
        return
    if tiny or mode == "sql":
        # the explicit CPU smoke and the host-only SQL anchor never touch
        # the chip: pin the CPU platform before jax is imported
        # (n_devices=1: a single-host-device number, comparable across
        # rounds, not a virtual-mesh run)
        from arkflow_tpu.utils.cleanenv import pin_cpu_env

        pin_cpu_env(os.environ, n_devices=1)
    else:
        _require_tpu()
    if mode == "generate":
        _run_generate_bench(tiny=tiny)
        return
    if mode == "sql":
        seconds = float(os.environ.get("BENCH_SECONDS", "15"))
        batch = int(os.environ.get("BENCH_BATCH", "1024"))
        infeed0 = _infeed_host_metrics()
        res = asyncio.run(run_bench(seconds, batch, 0, True, mode="sql"))
        _emit(
            {
                "metric": "sql_filter_rows_per_sec_cpu_ref",
                "value": round(res["rows_per_sec"], 1),
                "unit": "rows/s",
                "vs_baseline": 0.0,
                "detail": {"rows": res["rows"], "elapsed_s": round(res["elapsed_s"], 2),
                           "batch": batch, "backend": _backend(),
                           # knob record (uniform across phases): the SQL
                           # anchor has no model, so both are inert here
                           "packing": False, "serving_dtype": None,
                           "stage_breakdown": res.get("stage_breakdown", {}),
                           # no device infeed in the SQL anchor: both report 0
                           **_infeed_detail(infeed0, _infeed_host_metrics())},
            }
        )
        return
    seconds = float(os.environ.get("BENCH_SECONDS", "15"))
    batch = int(os.environ.get("BENCH_BATCH", "1024"))
    seq = int(os.environ.get("BENCH_SEQ", "32"))

    # Phase ORDER depends on backend: on CPU (tiny) the latency phase runs
    # first, cheap. On the chip each bucket compiles for tens of seconds
    # and the latency phase needs TWO extra buckets — so the saturated
    # headline (ONE compile) measures first, banking its number (and its
    # executable in the persistent cache) before latency is attempted.
    # Output order is fixed regardless: latency line first, headline LAST
    # for last-JSON-line parsers.
    # parity gate FIRST (before any measured phase): the packed
    # low-precision default only becomes the headline after proving argmax
    # parity against unpacked float32. A mismatch, like any phase that
    # throws, ends the run non-zero — no other configuration is measured in
    # its place under the same metric name.
    parity_detail: dict = {}
    if _bench_packing() and os.environ.get("BENCH_SKIP_PARITY", "0") != "1":
        parity_detail = _packed_parity_check(tiny, seq)
        print(f"bench: packed {_bench_dtype(tiny)} argmax parity OK "
              f"({parity_detail['parity_rows']} rows)",
              file=sys.stderr, flush=True)

    run_latency = os.environ.get("BENCH_SKIP_LATENCY", "0") != "1"
    lat = None
    if run_latency and tiny:
        lat_seconds = float(os.environ.get("BENCH_LAT_SECONDS", "10"))
        lat = asyncio.run(run_bench(lat_seconds, 8, seq, tiny, mode="latency"))

    # saturated throughput — the headline metric.
    # duty cycle is this phase's DELTA (the latency phase idles on purpose)
    def _headline_phase() -> tuple:
        busy0, stall0 = _busy_stall_from_registry()
        exec0, exrows0 = _exec_and_example_rows()
        infeed0 = _infeed_host_metrics()
        tok0 = _tokens_total()
        probes0 = _integrity_probes()
        res = asyncio.run(run_bench(seconds, batch, seq, tiny))
        busy1, stall1 = _busy_stall_from_registry()
        exec1, exrows1 = _exec_and_example_rows()
        detail = dict(_infeed_detail(infeed0, _infeed_host_metrics()))
        if _bench_integrity():
            # the SDC-probe overhead phase self-describes: cadence + how
            # many probes the measured window actually absorbed
            detail["integrity_probe_interval"] = _bench_integrity()
            detail["integrity_probes"] = int(_integrity_probes() - probes0)
        # examples/s -> device-rows/s via the phase's exec/example ratio
        # (both deltas span the same phase: the ratio is window-independent)
        exec_ratio = ((exec1 - exec0) / (exrows1 - exrows0)
                      if exrows1 > exrows0 else 1.0)
        if _bench_packing() and res["elapsed_s"] > 0:
            # effective token throughput: true (non-padding) tokens the
            # packed phase pushed through the device per second
            detail["tokens_per_sec"] = round(
                (_tokens_total() - tok0) / res["elapsed_s"], 1)
        return (res, busy1 - busy0, stall1 - stall0, detail,
                res["rows_per_sec"] * exec_ratio)

    res, d_busy, d_stall, infeed_detail, exec_rate = _headline_phase()
    infeed_detail.update(parity_detail)

    if run_latency and not tiny:
        # TPU: bank the headline BEFORE attempting latency — its bucket
        # compiles can outlive an external kill, and the last printed JSON
        # line must survive as the headline either way (it is re-printed,
        # with latency detail, after a successful latency phase)
        _print_headline(res, tiny, batch, seq, d_busy, d_stall,
                        dict(infeed_detail), exec_rate)
        lat_seconds = float(os.environ.get("BENCH_LAT_SECONDS", "10"))
        lat = asyncio.run(run_bench(lat_seconds, 8, seq, tiny, mode="latency"))

    if lat is not None and lat["rows"] == 0:
        # compile never finished inside the controller deadline: there is
        # no latency data — say so instead of printing stale quantiles
        print("bench: latency phase produced 0 rows (compile exceeded "
              "deadline); omitting latency metric", file=sys.stderr, flush=True)
        lat = None
    lat_detail = {}
    if lat is not None:
        lat_detail = {"latency_p50_ms": round(lat["p50_ms"], 2),
                      "latency_p99_ms": round(lat["p99_ms"], 2)}
        # the file artifact must self-describe: the CPU smoke's numbers
        # tagged as such can never be mistaken for chip data
        lat_tagged = dict(
            lat_detail,
            backend=_backend(),
            serving_dtype=_latency_dtype(tiny),
            seq=seq,
            offered_rows_per_sec=LAT_OFFERED_ROWS_PER_SEC,
        )
        print(
            json.dumps(
                {
                    "metric": "bert_e2e_latency_p99_ms"
                    + ("" if not tiny else "_cpu"),
                    "value": round(lat["p99_ms"], 2),
                    "unit": "ms",
                    # target: p99 < 50ms (BASELINE.json); >1.0 beats it
                    "vs_baseline": round(50.0 / lat["p99_ms"], 4) if lat["p99_ms"] > 0 else 0.0,
                    "detail": {
                        "p50_ms": round(lat["p50_ms"], 2),
                        "p99_ms": round(lat["p99_ms"], 2),
                        "offered_rows_per_sec": LAT_OFFERED_ROWS_PER_SEC,
                        "achieved_rows_per_sec": round(lat["rows_per_sec"], 1),
                        "buffer_timeout_ms": 10,
                        "seq": seq,
                        # knob record: the bounded-load phase is always
                        # unpacked (tiny batches); see _latency_dtype
                        "packing": False,
                        "serving_dtype": _latency_dtype(tiny),
                        "stage_breakdown": lat.get("stage_breakdown", {}),
                    },
                }
            ),
            flush=True,
        )
        # file copy too: if the driver run dies before the headline re-print,
        # at least the latency metric survives machine-readably
        try:
            with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                   "bench_logs", "latest_latency.json"), "w") as f:
                json.dump(lat_tagged, f)
        except OSError:
            pass
    if lat is not None and _bench_packing():
        # the latency numbers come from the bounded-load UNPACKED phase;
        # tag them so the packed headline artifact self-describes
        lat_detail = dict(lat_detail, latency_phase="unpacked")
    _print_headline(res, tiny, batch, seq, d_busy, d_stall,
                    {**infeed_detail, **lat_detail}, exec_rate)


def _packed_parity_check(tiny: bool, seq: int) -> dict:
    """Argmax-parity gate for the packed low-precision default: the packed
    processor at the bench dtype must produce the SAME labels as the
    float32 unpacked reference on a ragged text mix (plus empty- and
    single-row edges) before its throughput becomes the headline. Returns
    the detail tags on success; raises AssertionError on any mismatch."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    dtype = _bench_dtype(tiny)
    word = "sensor reading nominal "
    texts = [(word * k).encode() for k in (1, 2, 1, 3, 1, 2, 8, 1)] * 8 + [b"", b"x"]
    base = {"type": "tpu_inference", "model": "bert_classifier",
            "model_config": dict(TINY_MODEL_CONFIG) if tiny else {},
            "max_seq": seq, "batch_buckets": [8, 16], "seq_buckets": [seq],
            "outputs": ["label"]}
    packed = build_component(
        "processor", dict(base, packing=True, serving_dtype=dtype), Resource())
    ref = build_component(
        "processor", dict(base, serving_dtype="float32"), Resource())

    def labels(proc, payloads):
        out = asyncio.run(proc.process(MessageBatch.new_binary(payloads)))[0]
        return out.column("label").to_pylist()

    got = labels(packed, texts) + labels(packed, [b"solo probe"])
    want = labels(ref, texts) + labels(ref, [b"solo probe"])
    if got != want:
        mism = sum(1 for a, b in zip(got, want) if a != b)
        raise AssertionError(
            f"packed {dtype} argmax parity failed: {mism}/{len(want)} labels "
            "differ from the unpacked float32 reference")
    return {"parity": "argmax_vs_unpacked_float32", "parity_rows": len(want)}


def _tokens_total() -> float:
    """True (non-padding) tokens dispatched by packed runners so far."""
    from arkflow_tpu.obs import global_registry

    total = 0.0
    for m in global_registry().collect():
        if getattr(m, "name", "") == "arkflow_tpu_tokens_total":
            total += m.value
    return total


def _print_headline(res: dict, tiny: bool, batch: int, seq: int,
                    d_busy: float, d_stall: float, lat_detail: dict,
                    exec_rate: float) -> None:
    import math

    if res["rows"] == 0:
        # compile never finished inside the deadline: no data. Keep the
        # one-JSON-line contract with finite values (NaN quantiles from an
        # empty histogram would break strict parsers) and say why.
        for k in ("p50_ms", "p99_ms"):
            if math.isnan(res[k]):
                res[k] = 0.0
        lat_detail = dict(lat_detail, no_data="0 rows flowed before deadline")
    duty = round(d_busy / (d_busy + d_stall), 4) if (d_busy + d_stall) > 0 else 0.0
    baseline = 100_000.0  # BASELINE.json north-star rows/sec/chip
    _emit(
        {
            "metric": "bert_base_classify_rows_per_sec_chip"
            if not tiny
            else "bert_tiny_classify_rows_per_sec_cpu",
            "value": round(res["rows_per_sec"], 1),
            "unit": "rows/s",
            "vs_baseline": round(res["rows_per_sec"] / baseline, 4),
            "detail": {
                # quantiles of the SATURATED phase = queueing delay at full
                # offered load, NOT end-to-end latency (that is the separate
                # latency_p50/p99_ms keys from the bounded-load phase)
                "saturated_queueing_p50_ms": round(res["p50_ms"], 2),
                "saturated_queueing_p99_ms": round(res["p99_ms"], 2),
                "rows": res["rows"],
                "elapsed_s": round(res["elapsed_s"], 2),
                "batch": batch,
                "seq": seq,
                "device_duty_cycle": duty,
                # every artifact self-describes backend + precision, so a
                # CPU fallback can never masquerade as chip data (VERDICT r4)
                "backend": _backend(),
                "serving_dtype": _bench_dtype(tiny),
                "softmax_dtype": ("float32" if tiny
                                  else os.environ.get("BENCH_SOFTMAX_DTYPE", "bfloat16")),
                **_packing_detail(batch, seq),
                **_flops_detail(res["rows_per_sec"], exec_rate, seq, tiny),
                # trace-layer per-stage attribution for THIS phase: a
                # regression names the stage that slowed down
                "stage_breakdown": res.get("stage_breakdown", {}),
                **lat_detail,
            },
        }
    )


def _packing_detail(batch: int, seq: int) -> dict:
    """Packed-execution context: the knobs the phase ran with (packing,
    coalescing mode + token budget) plus the realized token-fill of packed
    rows (effective rows/s = the headline value; fill shows how much bucket
    padding the packer eliminated) — recorded in every BENCH_RESULT so
    plateau diagnosis never requires a rerun."""
    out = {"packing": _bench_packing(),
           "ragged_payloads": os.environ.get("BENCH_RAGGED", "0") == "1",
           "coalesce": _bench_coalesce()}
    if out["packing"] and out["coalesce"]:
        out["coalesce_token_budget"] = _bench_token_budget(batch, seq)
    if out["packing"]:
        from arkflow_tpu.obs import global_registry

        for m in global_registry().collect():
            # the packed runner's own reservoir only — the (unpacked)
            # latency-phase runner shares the metric name, not the labels
            if (getattr(m, "name", "") == "arkflow_tpu_batch_fill_ratio"
                    and getattr(m, "labels", {}).get("packed") == "1"):
                try:
                    out["packed_token_fill_p50"] = round(m.quantile(0.5), 3)
                except Exception:
                    pass
                break
    return out


def _bench_pp_mb(batch: int, n: int) -> int:
    """pp microbatch rows for a ``batch``-row bucket over ``n`` stages:
    BENCH_MC_MB, defaulting to the largest DIVISOR of ``batch`` that yields
    at least ~2 microbatches per stage (M >= 2n, analytic bubble
    (n-1)/(M+n-1) ~< 1/3). Divisor, not batch//(2n): the GPipe schedule
    needs bucket-exact microbatches, and e.g. batch 64 over 6 stages would
    otherwise pick mb=5, which 64 doesn't divide by — a ConfigError at
    phase build."""
    env = os.environ.get("BENCH_MC_MB")
    if env is not None:
        return int(env)
    target = max(1, batch // (2 * n))
    mb = 1
    while mb * 2 <= target and batch % (mb * 2) == 0:
        mb *= 2
    return mb


def build_multichip_config(batch: int, seq: int, n: int, style: str,
                           latency: bool = False,
                           layers: int | None = None) -> dict:
    """One phase of the multichip bench: the tiny classifier served over
    ``n`` chips — ``style="pool"`` (replicated device pool, no collectives),
    ``style="dp"`` (dp-sharded GSPMD dispatch), or ``style="pp"``
    (pipelined model segmentation: the layer stack cut across chips,
    microbatches streamed stage-to-stage). ``n=1`` is the single-chip
    reference phase the efficiency is computed against.

    ``latency=True`` builds the small-bucket LATENCY-BOUND variant: a paced
    trickle of ``LAT_BATCH``-row requests on a grid reaching down to the
    request size — the regime where dp starves (a small request still pads
    up to its dp-scaled smallest global bucket, so every chip burns a full
    per-chip bucket on 1/n of the rows) and pp keeps every chip busy on one
    request's layers."""
    model_config = {"vocab_size": 512, "hidden": 32, "layers": layers or 2,
                    "heads": 4, "ffn": 64, "max_positions": 64, "num_labels": 2}
    proc: dict = {
        "type": "tpu_inference",
        "model": "bert_classifier",
        "model_config": model_config,
        "max_seq": seq,
        "batch_buckets": [batch],  # per-chip bucket; dp scales it by n
        "seq_buckets": [seq],
        "outputs": ["label", "score"],
        "warmup": True,
        "max_in_flight": int(os.environ.get("BENCH_MC_INFLIGHT", "2")),
    }
    coalesce: dict = {"batch_buckets": [batch], "deadline": "5ms"}
    if n > 1:
        if style == "dp":
            proc["mesh"] = {"dp": n}
            # the runner compiles the dp-scaled global bucket (batch*n);
            # coalesce targets the same grid so emissions stay bucket-exact
            coalesce["dp"] = n
        elif style == "pp":
            # layers must cover the stage count (every chip owns >= 1
            # layer) — the three-way runner passes the deepened stack to
            # EVERY style so the comparison stays one model
            if model_config["layers"] < n:
                raise ValueError(
                    f"pp phase needs layers >= {n} stages "
                    f"(got {model_config['layers']}); pass layers=")
            proc["mesh"] = {"pp": n}
            proc["pp_microbatch_rows"] = _bench_pp_mb(batch, n)
            # ONE schedule in flight: a second interleaved GPipe schedule on
            # the same chips inflates each step's wall time with the other
            # schedule's ticks, double-counting the measured bubble (the
            # acceptance compares it against the analytic (S-1)/(M+S-1))
            proc["max_in_flight"] = int(
                os.environ.get("BENCH_MC_PP_INFLIGHT", "1"))
        else:
            proc["device_pool"] = n
    capacity = batch * (n if style == "dp" else 1)
    if latency:
        # bounded offered load, buffer-timeout micro-batching: p99 measures
        # end-to-end latency of small requests, not queueing under
        # saturation. The grid reaches down to the request size — but dp
        # STILL pads every request to its smallest dp-scaled global bucket
        # (LAT_BATCH x n rows for LAT_BATCH offered), which is exactly the
        # small-bucket starvation this phase exists to measure; pp serves
        # the same request as layer-stage microbatches with every chip busy
        from arkflow_tpu.tpu.bucketing import pow2_buckets

        proc["batch_buckets"] = pow2_buckets(LAT_BATCH, batch)
        if style == "pp" and n > 1:
            proc["pp_microbatch_rows"] = max(1, LAT_BATCH // 2)
        src = {"interval": f"{LAT_INTERVAL_MS}ms", "batch_size": LAT_BATCH}
        buffer = {"type": "memory", "capacity": capacity, "timeout": "10ms"}
    else:
        src = {"interval": 0, "batch_size": batch}
        buffer = {"type": "memory", "capacity": capacity, "timeout": "5ms",
                  "coalesce": coalesce}
    return {
        # per-phase stream name: rows/e2e metrics are labeled by stream, so
        # the 1-chip and n-chip phases never share counters
        "name": f"bench-mc{n}-{style}" + ("-lat" if latency else ""),
        "input": {"type": "generate",
                  "payload": "stream processing on tpu: sensor reading "
                             "nominal, no anomaly detected",
                  **src},
        "buffer": buffer,
        "pipeline": {
            # workers must cover the whole pool's queue depth (n members x
            # max_in_flight each) or the extra chips just idle
            "thread_num": max(4, 2 * n + 2),
            "processors": [proc],
        },
        "output": {"type": "drop"},
    }


def _per_device_busy_stall() -> dict[str, tuple[float, float]]:
    """(busy_s, stall_s) per ``device`` label ('' = unlabeled runner)."""
    from arkflow_tpu.obs import global_registry

    out: dict[str, list[float]] = {}
    for m in global_registry().collect():
        name = getattr(m, "name", "")
        if name in ("arkflow_tpu_device_busy_seconds_total",
                    "arkflow_tpu_infeed_stall_seconds_total"):
            dev = getattr(m, "labels", {}).get("device", "")
            slot = out.setdefault(dev, [0.0, 0.0])
            slot[0 if name.endswith("busy_seconds_total") else 1] += m.value
    return {k: (v[0], v[1]) for k, v in out.items()}


def _feature_gauges() -> tuple[bool, bool]:
    """(prefetch_active, donate_active): True when EVERY runner built so far
    reports the feature on — the assertable form of "the PR-2 wins stayed
    enabled under the mesh/pool"."""
    from arkflow_tpu.obs import global_registry

    prefetch, donate = [], []
    for m in global_registry().collect():
        name = getattr(m, "name", "")
        if name == "arkflow_tpu_prefetch_active":
            prefetch.append(m.value)
        elif name == "arkflow_tpu_donate_active":
            donate.append(m.value)
    return (bool(prefetch) and all(v == 1 for v in prefetch),
            bool(donate) and all(v == 1 for v in donate))


def _pp_bubble_gauge() -> float | None:
    """Last measured ``arkflow_pp_bubble_frac`` (None before any pp step)."""
    from arkflow_tpu.obs import global_registry

    for m in global_registry().collect():
        if getattr(m, "name", "") == "arkflow_pp_bubble_frac":
            return round(float(m.value), 4)
    return None


def _pp_knobs(style: str, batch: int, n: int, mb: int | None = None) -> dict:
    """pp knob record for a multichip phase detail (PR-6 convention: every
    phase names the knobs it ran with, so regressions stay attributable).
    Null on non-pp styles — the keys are still present so artifact diffs
    line up. ``batch`` is the bucket the phase's requests land in; ``mb``
    overrides the saturated-phase microbatch sizing (latency phases)."""
    if style != "pp" or n <= 1:
        return {"pp_stages": None, "microbatches": None,
                "pp_bubble_frac": None}
    mb = mb if mb is not None else _bench_pp_mb(batch, n)
    m = max(1, batch // mb)
    return {"pp_stages": n,
            "microbatches": m,
            "pp_microbatch_rows": mb,
            "pp_bubble_frac": _pp_bubble_gauge(),
            "pp_bubble_analytic": round((n - 1) / (m + n - 1), 4)}


def _run_multichip_bench() -> None:
    """BENCH_MODE=multichip: multi-chip serving-scaling on an n-device mesh.

    Phase 1 serves the workload on ONE device, phase 2 on all n, and the
    headline is ``scaling_efficiency`` = rows/s(n) / (n x rows/s(1)) — 1.0
    is linear scaling. BENCH_MC_STYLE picks the mechanism: ``dp``
    (dp-sharded GSPMD dispatch, the default), ``pool`` (replicated device
    pool, no collectives), or ``pp`` — which runs the full THREE-WAY
    dp/pool/pp comparison: saturated phases for all three styles at equal
    chip count plus a small-bucket latency-bound phase per style, emitting
    ``scaling_efficiency`` and p99 per style (the regime comparison the
    pipelined-segmentation paper makes: dp starves on requests that can't
    fill a shard; pp keeps every chip busy on one request's layers).

    Always re-execs into a clean forced-host-device child env (the phase
    validates SCALING MECHANICS hermetically; real-chip absolute numbers
    come from the main bench). NOTE: virtual host devices share the
    machine's physical cores, so CPU efficiency is bounded by cores/n, not
    by the serving stack — on a real n-chip slice each device is its own
    silicon and the same number reads as true scaling. The dp-vs-pp p99
    comparison survives this caveat in the dp-starved regime because dp's
    padding burns n x the TOTAL work (shared cores feel total work), but
    record it honestly.
    """
    import subprocess
    import sys

    n = int(os.environ.get("BENCH_MC_DEVICES", "8"))
    style = os.environ.get("BENCH_MC_STYLE", "dp")
    if style not in ("pool", "dp", "pp"):
        print(f"bench: BENCH_MC_STYLE must be pool|dp|pp, got {style!r}",
              file=sys.stderr)
        sys.exit(2)
    if os.environ.get("ARKFLOW_MC_CHILD") != "1":
        from arkflow_tpu.utils.cleanenv import cpu_child_env

        env = cpu_child_env(n_devices=n)
        env["ARKFLOW_MC_CHILD"] = "1"
        # prefetch is platform-gated off on CPU; force it so the sharded
        # eager device_put path actually runs (and the gauge asserts it)
        env.setdefault("ARKFLOW_PREFETCH", "1")
        res = subprocess.run([sys.executable, __file__], env=env,
                             capture_output=True)
        _relay_child(res)
        sys.exit(res.returncode)

    seconds = float(os.environ.get("BENCH_MC_SECONDS", "6"))
    batch = int(os.environ.get("BENCH_MC_BATCH", "64"))
    seq = int(os.environ.get("BENCH_MC_SEQ", "32"))

    if style == "pp":
        _run_multichip_threeway(n, seconds, batch, seq)
        return

    r1 = asyncio.run(run_bench(
        seconds, batch, seq, True,
        cfg_map=build_multichip_config(batch, seq, 1, style)))

    bs0 = _per_device_busy_stall()
    rn = asyncio.run(run_bench(
        seconds, batch, seq, True,
        cfg_map=build_multichip_config(batch, seq, n, style)))
    bs1 = _per_device_busy_stall()

    duty = {}
    for dev, (busy1, stall1) in bs1.items():
        busy0, stall0 = bs0.get(dev, (0.0, 0.0))
        d_busy, d_stall = busy1 - busy0, stall1 - stall0
        if d_busy + d_stall > 0:
            duty[dev or "mesh"] = round(d_busy / (d_busy + d_stall), 4)
    prefetch_on, donate_on = _feature_gauges()
    eff = (rn["rows_per_sec"] / (n * r1["rows_per_sec"])
           if r1["rows_per_sec"] > 0 else 0.0)
    _emit({
        "metric": "multichip_scaling_efficiency",
        "value": round(eff, 4),
        "unit": "ratio",
        # floor: 0.5 (half-linear scaling); >1.0 beats it
        "vs_baseline": round(eff / 0.5, 4),
        "detail": {
            "n_devices": n,
            "style": style,
            "rows_per_sec_1chip": round(r1["rows_per_sec"], 1),
            "rows_per_sec_nchip": round(rn["rows_per_sec"], 1),
            "batch_per_chip": batch,
            "seq": seq,
            "elapsed_s": round(r1["elapsed_s"] + rn["elapsed_s"], 2),
            "per_device_duty_cycle": duty,
            "prefetch_active": prefetch_on,
            "donate_active": donate_on,
            "backend": _backend(),
            "host_cores": os.cpu_count(),
            # knob record: the scaling phase serves unpacked float32 (it
            # measures dispatch mechanics, not precision/packing wins)
            "packing": False,
            "serving_dtype": "float32",
            **_pp_knobs(style, batch, n),
        },
    })


def _run_multichip_threeway(n: int, seconds: float, batch: int, seq: int) -> None:
    """BENCH_MC_STYLE=pp: the honest dp/pool/pp three-way comparison.

    Saturated phases per style at equal chip count (scaling_efficiency
    against the shared 1-chip reference), then a small-bucket latency-bound
    phase per style (paced LAT_BATCH-row requests; p99 per style, with the
    1-chip latency reference alongside). EVERY phase — including the 1-chip
    references — serves the same ``max(2, n)``-layer model, so pp's
    stage-per-chip requirement never tilts the model under any style. Every
    phase detail records the style + pp knobs; the pp detail additionally
    records the stage plan and the measured-vs-analytic bubble."""
    layers = max(2, n)
    r1 = asyncio.run(run_bench(
        seconds, batch, seq, True,
        cfg_map=build_multichip_config(batch, seq, 1, "pool", layers=layers)))
    styles = ("dp", "pool", "pp")
    saturated: dict[str, dict] = {}
    for s in styles:
        res = asyncio.run(run_bench(
            seconds, batch, seq, True,
            cfg_map=build_multichip_config(batch, seq, n, s, layers=layers)))
        eff = (res["rows_per_sec"] / (n * r1["rows_per_sec"])
               if r1["rows_per_sec"] > 0 else 0.0)
        saturated[s] = {
            "rows_per_sec": round(res["rows_per_sec"], 1),
            "scaling_efficiency": round(eff, 4),
            "p99_ms": round(res["p99_ms"], 2),
            "style": s,
            **_pp_knobs(s, batch, n),
        }

    lat_seconds = float(os.environ.get("BENCH_MC_LAT_SECONDS", str(seconds)))
    lat1 = asyncio.run(run_bench(
        lat_seconds, batch, seq, True,
        cfg_map=build_multichip_config(batch, seq, 1, "pool", latency=True,
                                       layers=layers)))
    latency: dict[str, dict] = {
        "1chip": {"p99_ms": round(lat1["p99_ms"], 2),
                  "p50_ms": round(lat1["p50_ms"], 2)}}
    for s in styles:
        res = asyncio.run(run_bench(
            lat_seconds, batch, seq, True,
            cfg_map=build_multichip_config(batch, seq, n, s, latency=True,
                                           layers=layers)))
        latency[s] = {"p99_ms": round(res["p99_ms"], 2),
                      "p50_ms": round(res["p50_ms"], 2),
                      **_pp_knobs(s, LAT_BATCH, n, mb=max(1, LAT_BATCH // 2))}
    # the acceptance comparison: at equal chip count, on latency-bound
    # small-bucket traffic, pipelined segmentation must beat dp
    # batch-splitting on p99 (dp pads every request to its scaled bucket)
    pp_beats_dp = latency["pp"]["p99_ms"] < latency["dp"]["p99_ms"]

    from arkflow_tpu.parallel.segment import uniform_plan

    mb = _bench_pp_mb(batch, n)
    plan = uniform_plan(layers, n)
    pp_eff = saturated["pp"]["scaling_efficiency"]
    _emit({
        "metric": "multichip_scaling_efficiency",
        "value": pp_eff,
        "unit": "ratio",
        "vs_baseline": round(pp_eff / 0.5, 4),
        "detail": {
            "n_devices": n,
            "style": "pp",
            "comparison": "threeway",
            "rows_per_sec_1chip": round(r1["rows_per_sec"], 1),
            "batch_per_chip": batch,
            "seq": seq,
            "saturated": saturated,
            "latency_bound": {
                "offered_batch": LAT_BATCH,
                "interval_ms": LAT_INTERVAL_MS,
                **latency,
                "pp_beats_dp_p99": pp_beats_dp,
            },
            "pp_plan": plan.report(),
            "pp_microbatch_rows": mb,
            # the STEADY-STATE pairing (the ISSUE-14 acceptance check):
            # saturated-phase measured bubble against the saturated-phase
            # analytic — the gauge's LAST value would be the latency
            # phase's, whose analytic is much higher (M=2)
            "pp_bubble_frac": saturated["pp"]["pp_bubble_frac"],
            "pp_bubble_analytic": round((n - 1) / (max(1, batch // mb) + n - 1), 4),
            "backend": _backend(),
            "host_cores": os.cpu_count(),
            "packing": False,
            "serving_dtype": "float32",
            # honest caveat: virtual host devices share physical cores, so
            # per-style absolute numbers are bounded by cores/n; the dp-pp
            # p99 gap in the starved regime reflects dp's padded TOTAL work
            "caveat": "forced host mesh: virtual devices share host cores",
        },
    })


def _run_generate_tp_phase() -> None:
    """Generate-mode TP phase: 1-chip vs tp=N continuous decode on a FORCED
    HOST mesh (always virtual CPU — it validates the sharded serving
    mechanics hermetically; real-chip numbers come from the main phase on
    real silicon). Emits ``generate_tp_scaling_efficiency`` with
    tokens/sec for both sides and the mesh knobs in the detail, so the
    multichip story reads as a dp/pool/tp comparison. ``BENCH_GEN_TP=0``
    skips; ``BENCH_GEN_TP_DEVICES`` sizes the mesh (default 2)."""
    import subprocess
    import sys

    from arkflow_tpu.utils.cleanenv import cpu_child_env

    n = int(os.environ.get("BENCH_GEN_TP_DEVICES", "2"))
    env = cpu_child_env(n_devices=n)
    env["ARKFLOW_GEN_TP_CHILD"] = "1"
    env["BENCH_MODE"] = "generate"
    try:
        res = subprocess.run([sys.executable, __file__], env=env,
                             capture_output=True, timeout=1200)
    except subprocess.TimeoutExpired:
        print("bench: generate TP phase timed out (main phase unaffected)",
              file=sys.stderr)
        return
    _relay_child(res)
    if res.returncode != 0:
        print("bench: generate TP phase failed (main phase unaffected)",
              file=sys.stderr)


def _generate_tp_child() -> None:
    """In-child measurement for the TP phase: same tiny decoder served
    continuous, once single-chip and once tensor-parallel over all N forced
    host devices (KV pages sharded over KV heads)."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    import jax

    ensure_plugins_loaded()
    n = len(jax.devices())
    rows = int(os.environ.get("BENCH_GEN_TP_ROWS", "16"))
    max_new = int(os.environ.get("BENCH_GEN_TP_TOKENS", "16"))
    model_config = {"vocab_size": 512, "dim": 64, "layers": 2, "heads": 4,
                    "kv_heads": 2, "ffn": 96, "max_seq": 256}
    base = {"type": "tpu_generate", "model": "decoder_lm",
            "model_config": model_config, "serving": "continuous",
            "slots": 8, "page_size": 16, "max_input": 64,
            "max_new_tokens": max_new, "eos_id": -1,
            "batch_buckets": [8], "seq_buckets": [64],
            **_gen_kernel_cfg()}

    def tps(cfg_map) -> tuple[float, dict]:
        proc = build_component("processor", cfg_map, Resource())
        batch = MessageBatch.new_binary(
            [f"sensor event {i} nominal reading".encode() for i in range(rows)])

        async def go() -> float:
            await proc.process(MessageBatch.new_binary([b"warmup prompt"]))
            t0 = time.perf_counter()
            await proc.process(batch)
            return time.perf_counter() - t0

        elapsed = asyncio.run(go())
        ttft = proc._server.health_report().get("ttft", {})
        return (rows * max_new / elapsed if elapsed > 0 else 0.0), ttft

    tps1, ttft1 = tps(base)
    tpsn, ttftn = tps({**base, "mesh": {"tp": n}})
    eff = tpsn / (n * tps1) if tps1 > 0 else 0.0
    _emit({
        "metric": "generate_tp_scaling_efficiency",
        "value": round(eff, 4),
        "unit": "ratio",
        # floor 0.5 = half-linear, same convention as the multichip phase
        "vs_baseline": round(eff / 0.5, 4),
        "detail": {
            "n_devices": n,
            "mesh": {"tp": n},
            "tokens_per_sec_1chip": round(tps1, 1),
            "tokens_per_sec_tp": round(tpsn, 1),
            "ttft_p99_ms_1chip": ttft1.get("p99_ms", 0.0),
            "ttft_p99_ms_tp": ttftn.get("p99_ms", 0.0),
            "rows": rows,
            "max_new_tokens": max_new,
            "serving": "continuous",
            "slots": 8,
            "backend": _backend(),
            "host_cores": os.cpu_count(),
            # knob record (PR-6 convention): the phase serves unpacked f32
            "packing": False,
            "serving_dtype": "float32",
            "decode_kernel": base["decode_kernel"],
            "dispatch_depth": 1,
            "caveat": "virtual host devices share physical cores; real-slice "
                      "efficiency reads higher",
        },
    })


class _GapRecorder:
    """Raw-sample stand-in for the idle-gap histogram: the Prometheus
    histogram's fixed buckets are too coarse for a p50/p99 readout, so the
    bench swaps the server's metric object for this recorder (same
    ``observe`` surface) and computes exact percentiles."""

    def __init__(self):
        self.samples: list[float] = []

    def observe(self, v: float) -> None:
        self.samples.append(float(v))

    def pct(self, q: float) -> float:
        if not self.samples:
            return 0.0
        s = sorted(self.samples)
        return s[min(len(s) - 1, int(q * len(s)))]


def _gen_kernel_cfg() -> dict:
    """The decode-kernel knobs every generate phase records: BENCH_GEN_KERNEL
    pins gather (reference) or paged (the Pallas page-table kernel); unset,
    the bench measures the server's auto default — paged on TPU, gather
    elsewhere — recorded explicitly so the phase detail never says "auto".
    Forcing paged on CPU runs it interpreted (functional, not
    representative of TPU speed — the phase detail carries the caveat)."""
    kernel = os.environ.get("BENCH_GEN_KERNEL") or (
        "paged" if _backend() == "tpu" else "gather")
    cfg = {"decode_kernel": kernel}
    if kernel == "paged" and _backend() != "tpu":
        cfg["kernel_interpret"] = True
    return cfg


def _run_generate_depth_phase(tiny: bool, model_config: dict) -> None:
    """Depth-1 vs depth-2 comparison on the SAME workload: the dispatch-depth
    win is a smaller device-idle gap (step N+1 queued before N completes)
    with bitwise-identical greedy outputs. ``BENCH_GEN_DEPTH=0`` skips."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, build_component

    rows = int(os.environ.get("BENCH_GEN_DEPTH_ROWS", "16"))
    max_new = int(os.environ.get("BENCH_GEN_DEPTH_TOKENS", "24"))
    base = {"type": "tpu_generate", "model": "decoder_lm",
            "model_config": model_config, "serving": "continuous",
            "slots": 8, "page_size": 16, "max_input": 64,
            "max_new_tokens": max_new, "eos_id": -1,
            "batch_buckets": [8], "seq_buckets": [64],
            **_gen_kernel_cfg()}

    def run(depth: int):
        proc = build_component("processor", {**base, "dispatch_depth": depth},
                               Resource())
        rec = _GapRecorder()
        proc._server.m_idle_gap = rec
        batch = MessageBatch.new_binary(
            [f"sensor event {i} nominal reading".encode() for i in range(rows)])

        async def go():
            await proc.process(MessageBatch.new_binary([b"warmup prompt"]))
            rec.samples.clear()  # warm-step gaps only
            t0 = time.perf_counter()
            out = await proc.process(batch)
            return time.perf_counter() - t0, out

        elapsed, out = asyncio.run(go())
        texts = out[0].column(proc.output_field).to_pylist() if out else []
        ttft = proc._server.health_report().get("ttft", {})
        return rows * max_new / elapsed if elapsed > 0 else 0.0, rec, texts, ttft

    tps1, rec1, out1, ttft1 = run(1)
    tps2, rec2, out2, ttft2 = run(2)
    _emit({
        "metric": "generate_dispatch_depth2_speedup",
        "value": round(tps2 / tps1, 4) if tps1 > 0 else 0.0,
        "unit": "ratio",
        "vs_baseline": 0.0,
        "detail": {
            "rows": rows, "max_new_tokens": max_new,
            "tokens_per_sec_depth1": round(tps1, 1),
            "tokens_per_sec_depth2": round(tps2, 1),
            "device_idle_gap_p50_ms_depth1": round(rec1.pct(0.5) * 1e3, 3),
            "device_idle_gap_p50_ms_depth2": round(rec2.pct(0.5) * 1e3, 3),
            "device_idle_gap_p99_ms_depth1": round(rec1.pct(0.99) * 1e3, 3),
            "device_idle_gap_p99_ms_depth2": round(rec2.pct(0.99) * 1e3, 3),
            "ttft_p99_ms_depth1": ttft1.get("p99_ms", 0.0),
            "ttft_p99_ms_depth2": ttft2.get("p99_ms", 0.0),
            # acceptance: pipelining must not change a single greedy token
            "identical_outputs": out1 == out2,
            **_gen_kernel_cfg(),
            "serving": "continuous", "backend": _backend(),
            "packing": False, "serving_dtype": "float32",
        },
    })


def _run_generate_bench(tiny: bool) -> None:
    """BENCH_MODE=generate: continuous-batching generation throughput
    (tokens/sec) through the tpu_generate processor's paged-KV server.
    A TP phase (1-chip vs tp=N on a forced host mesh) runs first unless
    BENCH_GEN_TP=0, then a dispatch-depth 1-vs-2 phase unless
    BENCH_GEN_DEPTH=0, so the headline metric stays tokens/sec. Every
    phase detail records the decode kernel, dispatch depth, the warm
    device-idle-gap p50, and the server's TTFT percentiles
    (``arkflow_gen_ttft_seconds``) so throughput wins never hide a
    first-token latency regression."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    if os.environ.get("BENCH_GEN_TP", "1") != "0":
        _run_generate_tp_phase()
    ensure_plugins_loaded()
    model_config = (
        {"vocab_size": 512, "dim": 64, "layers": 2, "heads": 4, "kv_heads": 2,
         "ffn": 96, "max_seq": 256}
        if tiny else {"max_seq": 2048}
    )
    if os.environ.get("BENCH_GEN_DEPTH", "1") != "0":
        _run_generate_depth_phase(tiny, model_config)
    max_new = int(os.environ.get("BENCH_GEN_TOKENS", "32"))
    rows = int(os.environ.get("BENCH_GEN_ROWS", "64"))
    dispatch_depth = int(os.environ.get("BENCH_GEN_DISPATCH", "1"))
    proc = build_component(
        "processor",
        {"type": "tpu_generate", "model": "decoder_lm", "model_config": model_config,
         "serving": "continuous", "slots": 8, "page_size": 16,
         "max_input": 64, "max_new_tokens": max_new, "eos_id": -1,
         "batch_buckets": [8], "seq_buckets": [64],
         "dispatch_depth": dispatch_depth, **_gen_kernel_cfg(),
         # BENCH_SPEC=k: self-drafted speculative decode (greedy-exact)
         "speculative_tokens": int(os.environ.get("BENCH_SPEC", "0"))},
        Resource(),
    )
    gap_rec = _GapRecorder()
    proc._server.m_idle_gap = gap_rec

    async def go() -> tuple[float, float]:
        batch = MessageBatch.new_binary(
            [f"sensor event {i} nominal reading".encode() for i in range(rows)])
        t_warm = time.perf_counter()
        await proc.process(MessageBatch.new_binary([b"warmup prompt"]))
        warm_s = time.perf_counter() - t_warm
        gap_rec.samples.clear()  # warm-step gaps only
        t0 = time.perf_counter()
        await proc.process(batch)
        return time.perf_counter() - t0, warm_s

    elapsed, warm_s = asyncio.run(go())
    total_tokens = rows * max_new
    server = proc._server
    detail = {"rows": rows, "max_new_tokens": max_new,
              "elapsed_s": round(elapsed, 2), "warmup_s": round(warm_s, 2),
              "serving": "continuous", "slots": 8, "backend": _backend(),
              # PR-13 knob record: which kernel + dispatch depth served, and
              # how idle the device sat between consecutive warm steps
              "decode_kernel": server.decode_kernel,
              "dispatch_depth": server.dispatch_depth,
              "device_idle_gap_p50_ms": round(gap_rec.pct(0.5) * 1e3, 3),
              # knob record: generation serves unpacked at default precision
              "packing": False, "serving_dtype": "float32"}
    # TTFT as the serving health report tells it (arkflow_gen_ttft_seconds):
    # the latency half of the throughput/latency trade every knob above
    # moves, and the headline the disagg topology optimises for.
    ttft = server.health_report().get("ttft")
    if ttft:
        detail["ttft"] = ttft
    if server.m_spec_drafted.value > 0:
        detail["speculative_tokens"] = server.speculative_tokens
        detail["spec_acceptance"] = round(
            server.m_spec_accepted.value / server.m_spec_drafted.value, 3)
    _emit({
        "metric": "decoder_generate_tokens_per_sec" + ("_cpu" if tiny else ""),
        "value": round(total_tokens / elapsed, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no reference number exists (ref has no LLM serving)
        "detail": detail,
    })


def _bert_flops_per_row(seq: int, tiny: bool) -> float:
    """Analytic forward FLOPs per row (2x MACs) for the benched classifier:
    per layer+token = 8h^2 (QKV+out proj) + 4*h*ffn (FFN) + 4*s*h (scores+PV).
    Embeddings/pooler are lookup- or batch-dim-dominated and excluded."""
    if tiny:
        h, ffn, layers = 32, 64, 2
    else:
        h, ffn, layers = 768, 3072, 12
    per_token = 8 * h * h + 4 * h * ffn + 4 * seq * h
    return float(seq * layers * per_token)


#: Peak dense matmul rates per chip, keyed by the EXACT
#: ``jax.devices()[0].device_kind``, each with its source. A kind that is not
#: listed is an error, never a guess from a substring.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_tflops": 197.0,
        "int8_tops": 393.0,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16 and 393 TOP/s int8 per chip",
    },
}


def _device_peak_tflops(device_kind: str | None = None) -> float:
    """Peak of the bench device at the serving dtype, for the MFU estimate
    (int8 serving reads the int8 rate, every other dtype the bf16 rate)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"bench: no peak on record for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add it to DEVICE_PEAKS with "
            "its source")
    peaks = DEVICE_PEAKS[device_kind]
    if os.environ.get("BENCH_DTYPE") == "int8":
        return peaks["int8_tops"]
    return peaks["bf16_tflops"]


def _flops_detail(rows_per_sec: float, exec_rate: float, seq: int,
                  tiny: bool) -> dict:
    """MFU/roofline context: the 100k rows/s/chip north star at seq 32
    implies ~5.4 TFLOP/row-batch-second scales past a v5e's bf16 peak, so
    report where the measurement sits against the physical ceiling.

    FLOPs are charged per DEVICE row (``exec_rate``: dispatched bucket rows
    incl. padding), not per example — under packing examples/s exceeds the
    padded-row roofline precisely because the device runs fewer rows, and
    charging full-seq FLOPs per example would report impossible MFU > 1.
    """
    fpr = _bert_flops_per_row(seq, tiny)
    out = {"model_flops_per_row": fpr,
           "device_rows_per_sec": round(exec_rate, 1),
           "achieved_model_tflops": round(exec_rate * fpr / 1e12, 3)}
    if not tiny:  # the CPU smoke has no device peak to compare against
        peak = _device_peak_tflops()
        out["device_peak_tflops_at_dtype"] = peak
        out["mfu"] = round(exec_rate * fpr / (peak * 1e12), 4)
        # padded-row ceiling; packed examples/s can legitimately exceed it
        out["roofline_rows_per_sec"] = round(peak * 1e12 / fpr, 1)
    return out


def _infeed_host_metrics() -> tuple[float, float, float, float, float, float]:
    """(prep_s_sum, prep_steps, extract_s_sum, waste_sum, tokens, capacity)
    totals across all runners/processors this process ran. prep covers the
    runner's pad/stage stage, extract the processor's Arrow->tensor +
    tokenize stage; waste_sum is the per-step padding fraction summed over
    prep_steps dispatches; tokens/capacity are the packed runners' true-token
    and dispatched-token-slot counters."""
    from arkflow_tpu.obs import global_registry

    prep_s = prep_n = extract_s = waste = tokens = capacity = 0.0
    for m in global_registry().collect():
        name = getattr(m, "name", "")
        if name == "arkflow_tpu_infeed_prep_seconds":
            prep_s += m.sum
            prep_n += m.count
        elif name == "arkflow_tpu_extract_seconds":
            extract_s += m.sum
        elif name == "arkflow_padding_waste_frac":
            waste += m.sum
        elif name == "arkflow_tpu_tokens_total":
            tokens += m.value
        elif name == "arkflow_tpu_token_capacity_total":
            capacity += m.value
    return prep_s, prep_n, extract_s, waste, tokens, capacity


def _infeed_detail(before: tuple, after: tuple) -> dict:
    """Phase-delta infeed numbers for the JSON detail: mean host prep ms per
    dispatched step (pad/stage + extract/tokenize) and the phase's padding
    waste. Packed phases report CAPACITY-WEIGHTED waste (1 - true tokens /
    dispatched token slots): the per-step mean over-weights small tail
    windows, which carry a sliver of the device time but the same histogram
    weight as a full bucket."""
    d_prep_s = after[0] - before[0]
    d_steps = after[1] - before[1]
    d_extract_s = after[2] - before[2]
    d_waste = after[3] - before[3]
    d_tokens = after[4] - before[4]
    d_capacity = after[5] - before[5]
    if d_steps <= 0:
        return {"infeed_prep_ms": 0.0, "padding_waste_frac": 0.0}
    waste = (1.0 - d_tokens / d_capacity) if d_capacity > 0 \
        else d_waste / d_steps
    return {
        "infeed_prep_ms": round((d_prep_s + d_extract_s) / d_steps * 1000.0, 3),
        "padding_waste_frac": round(waste, 4),
        # traffic-adaptive shapes (tpu/tuner.py): the committed shape epoch
        # plus the planner's predicted waste next to the MEASURED
        # padding_waste_frac above, so a retuned phase's artifact carries
        # its own predicted-vs-measured honesty check (0/absent = no tuner)
        **_tuner_detail(),
    }


def _tuner_detail() -> dict:
    """Shape-tuner state for phase detail: {} when no tuner ran."""
    from arkflow_tpu.obs import global_registry

    epoch = predicted = None
    for m in global_registry().collect():
        name = getattr(m, "name", "")
        if name == "arkflow_tuner_epoch":
            epoch = max(epoch or 0, int(m.value))
        elif name == "arkflow_tuner_predicted_waste":
            predicted = float(m.value)
    if epoch is None:
        return {}
    out = {"tuner_epoch": epoch}
    if predicted is not None:
        out["tuner_predicted_waste"] = round(predicted, 4)
    return out


def _integrity_probes() -> float:
    """Integrity probes completed (all results summed) this process — the
    delta across the headline phase records how many SDC probes the phase
    actually paid for (BENCH_INTEGRITY overhead satellite)."""
    from arkflow_tpu.obs import global_registry

    n = 0.0
    for m in global_registry().collect():
        if getattr(m, "name", "") == "arkflow_integrity_probe_total":
            n += m.value
    return n


def _busy_stall_from_registry() -> tuple[float, float]:
    """(busy_s, stall_s) totals across all runners this process ran."""
    from arkflow_tpu.obs import global_registry

    busy = stall = 0.0
    for m in global_registry().collect():
        name = getattr(m, "name", "")
        if name == "arkflow_tpu_device_busy_seconds_total":
            busy += m.value
        elif name == "arkflow_tpu_infeed_stall_seconds_total":
            stall += m.value
    return busy, stall


def _exec_and_example_rows() -> tuple[float, float]:
    """(exec_rows, example_rows) totals: bucket rows dispatched to the device
    (padding included — the honest FLOPs denominator) and true examples
    inferred. Their ratio converts examples/s into device rows/s; with
    packing the two diverge (that is the point). Warmup dispatches are
    excluded by the runner."""
    from arkflow_tpu.obs import global_registry

    ex = rows = 0.0
    for m in global_registry().collect():
        name = getattr(m, "name", "")
        if name == "arkflow_tpu_exec_rows_total":
            ex += m.value
        elif name == "arkflow_tpu_rows_total":
            rows += m.value
    return ex, rows


if __name__ == "__main__":
    main()
