#!/usr/bin/env python3
"""benchmark/run.py — one cell of the benchmark, on the machine it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Looks the cell up in ``BENCHMARK.json`` and finds everything that belongs to
it by name: ``benchmark/configs/<config>.json`` (the sizes and the engine
mapping as run), ``benchmark/traffic/<traffic>.json`` (parameters of the one
generator in ``lib/traffic.py``), ``benchmark/references/<reference>.py``
(the plain reference the configuration names) and, for a traced run, one
``benchmark/metrics/<metric>.py`` per per-layer metric listed for the cell.
A later PR adds a configuration, a mix, a metric or a cell as new files and
new entries; nothing here names one.

The system under test is driven exactly as ``chip_smoke.py`` drives it —
config mapping -> ``EngineConfig`` -> ``Engine`` -> ``build_stream`` — in
this one process, which holds the chip, with the benchmark's own input and
sink registered as plugins. Weights and rows come from ``--seed``. Set-up
(imports, weights, placement, warm-up of the cell's own shapes, fill to
steady state) ends when the window opens; nothing compiles inside it. After
``--seconds`` the input ends, the stream drains, and outputs and delivery
guarantees are checked against the plain reference outside the window.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` takes a
profiler trace of a few seconds inside the window and prints the cell's
per-layer metrics, the device's busy time and a breakdown. The last line of
stdout is the one JSON object of the contract; progress goes to stderr and
samples to ``benchmark_out/``.

Without a TPU (or with fewer chips than the cell asks for) the run exits
non-zero and prints no result. ``--rehearse`` (never passed by the driver)
shrinks every size and runs on the CPU to rehearse the control flow; its
line names ``cpu`` as the device and is not a measurement.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import copy  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, "benchmark_out")
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def log(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def deep_update(base: dict, over: dict) -> dict:
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            deep_update(base[k], v)
        else:
            base[k] = copy.deepcopy(v)
    return base


def lookup(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r} in "
                         f"BENCHMARK.json (known: {sorted(cells)})")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, conf


def metrics_for(bench: dict, section: str, workload: str) -> list[dict]:
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]


def build_engine_mapping(config: dict, seed: int, rehearse: bool
                         ) -> tuple[dict, dict]:
    """The engine mapping the cell runs — the file's ``engine`` section, the
    processor's ``model_config`` filled from the file's published keys and
    its ``seed`` from ``--seed`` — and the published keys as run. A
    rehearsal overlays the file's ``rehearse`` section on both."""
    sizes = dict(config)
    eng = copy.deepcopy(config["engine"])
    stream = eng["streams"][0]
    proc = stream["pipeline"]["processors"][0]
    if rehearse:
        over = config.get("rehearse") or {}
        sizes.update(over.get("model") or {})
        deep_update(proc, over.get("processor") or {})
        if stream.get("buffer") and over.get("buffer"):
            deep_update(stream["buffer"], over["buffer"])
        if proc["type"] == "tpu_generate":
            proc.setdefault("kernel_parity_check", False)
    proc["model_config"] = {
        ours: sizes[theirs] for ours, theirs in config["model_config_from"].items()}
    # PRNGKey takes 32 signed bits; the driver's seeds are larger
    proc["seed"] = int(seed) % (2 ** 31 - 1)
    return eng, sizes


def registry_snapshot() -> dict:
    """The program's metrics registry at one instant: counters and gauges
    by value, histograms by (sum, count), keyed by name and labels."""
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.obs.metrics import Histogram

    snap = {}
    for m in global_registry().collect():
        key = (m.name, tuple(sorted(m.labels.items())))
        snap[key] = ((m.sum, m.count) if isinstance(m, Histogram)
                     else float(m.value))
    return snap


def matching(snap: dict, name: str, labels: dict):
    """Values of every label set of ``name`` that carries ``labels``."""
    for (n, lab), v in snap.items():
        if n == name and all(dict(lab).get(k) == val
                             for k, val in labels.items()):
            yield v


class View:
    """What a per-layer metric's reader may look at."""

    def __init__(self, *, cell, config, traffic, sizes, proc_cfg, device,
                 peaks, run, snap_open, snap_close, gauges, spans, trace,
                 compiles, shapes, chips, memory_peak_bytes):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.sizes = sizes          # the published keys as run
        self.proc_cfg = proc_cfg    # the processor mapping as run
        self.device, self.peaks = device, peaks
        self.run = run
        self.seconds = (run.t_close - run.t_open) if run.t_open else 0.0
        self._open, self._close = snap_open, snap_close
        self._gauges, self._spans = gauges, spans
        self.trace = trace          # reduced profiler trace, or None
        self.compiles_in_window = compiles
        self.shapes_in_trace = shapes  # classify: {(batch, seq): dispatches}
        self.chips = chips
        self.memory_peak_bytes = memory_peak_bytes

    def counter(self, name: str, **labels) -> float:
        """Increase of a counter over the window, summed over label sets."""
        a = sum(matching(self._open, name, labels))
        b = sum(matching(self._close, name, labels))
        return b - a

    def hist(self, name: str, **labels) -> tuple[float, float]:
        """(seconds summed, observations) a histogram gained in the window."""
        s0 = c0 = s1 = c1 = 0.0
        for s, c in matching(self._open, name, labels):
            s0, c0 = s0 + s, c0 + c
        for s, c in matching(self._close, name, labels):
            s1, c1 = s1 + s, c1 + c
        return s1 - s0, c1 - c0

    def gauge(self, name: str) -> list[float]:
        """Samples of a gauge taken every 50 ms inside the window."""
        return self._gauges.get(name, [])

    def spans(self, stage: str) -> list[float]:
        """Durations (s) of the program's stage spans recorded in the window."""
        return self._spans.get(stage, [])

    def samples(self, name: str):
        """The harness's own stamps: ``e2e_ms``, ``gen_late_ms``,
        ``input_lag_ms`` (arrays over the window's rows or batches)."""
        import numpy as np

        r = self.run
        if name == "e2e_ms":
            return np.concatenate(r.e2e_ms) if r.e2e_ms else np.zeros(0)
        if name == "gen_late_ms":
            return np.asarray(r.gen_late_ms)
        if name == "input_lag_ms":
            return (np.concatenate([a for _, a in r.input_lag_ms])
                    if r.input_lag_ms else np.zeros(0))
        raise KeyError(name)


def end_to_end(name: str, run, setup_s: float):
    """The end-to-end metrics the harness takes itself, by host clock."""
    import numpy as np

    from benchmark.lib.stats import percentile, rate_between_writes

    if name == "setup_s":
        return setup_s
    if name == "rows_per_s":
        return rate_between_writes([(t, n) for t, n, _ in run.writes],
                                   run.t_open, run.t_close)
    if name == "tokens_per_s":
        return rate_between_writes([(t, k) for t, _, k in run.writes],
                                   run.t_open, run.t_close)
    if name in ("e2e_p50_ms", "e2e_p95_ms"):
        if not run.e2e_ms:
            return None
        return percentile(np.concatenate(run.e2e_ms),
                          50.0 if name == "e2e_p50_ms" else 95.0)
    return None


def check_delivery(stream, run) -> tuple[dict, int, bool]:
    """The at-least-once guarantees as far as a run can show them: the
    stream's error counters, how many rows failed (read and not written by
    the end of the drain, plus every nack, quarantine, process or write
    error), and whether every read was acked after all rows were written."""
    errors = {}
    for name in ("m_errors", "m_write_errors", "m_quarantined",
                 "m_ack_failures", "m_quarantine_drops"):
        c = getattr(stream, name, None)
        errors[name] = int(c.value) if c is not None else 0
    lost = max(0, run.rows_read - run.rows_written)
    failed = lost + run.nacks + sum(errors.values())
    ok = (stream is not None and run.reads > 0 and run.acks == run.reads
          and run.nacks == 0 and run.rows_written == run.rows_read
          and not any(errors.values()))
    return errors, failed, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at tiny sizes; not a measurement")
    ap.add_argument("--keep-trace", action="store_true",
                    help="with --trace 1, also keep the head of the raw "
                         "trace in benchmark_out/ (to read by hand)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override a key of the traffic mix, for a sweep "
                         "(never passed by the driver)")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, conf_entry = lookup(bench, args.workload)
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        config = json.load(f)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    chips = int(cell["chips"])

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")
        if chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={chips}").strip()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "arkflow_tpu")):
        print("benchmark: the system under test (arkflow_tpu/) is not in this "
              "checkout", file=sys.stderr)
        return 1

    import jax
    import numpy as np

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if not args.rehearse and dev["platform"] != "tpu":
        print(f"benchmark: found no TPU (jax reports {dev}); a measurement "
              "does not fall back to the CPU", file=sys.stderr)
        return 1
    if len(devices) < chips:
        print(f"benchmark: cell {cell['name']} needs {chips} chips, jax "
              f"reports {len(devices)}", file=sys.stderr)
        return 1

    from arkflow_tpu import native
    from arkflow_tpu.config import EngineConfig
    from arkflow_tpu.obs.trace import global_tracer
    from arkflow_tpu.runtime.cli import init_logging
    from arkflow_tpu.runtime.engine import Engine
    from arkflow_tpu.tpu.jaxcache import enable_persistent_cache
    from benchmark.lib import traffic as tr
    from benchmark.lib import xtrace
    from benchmark.lib.costs import device_peaks
    from benchmark.lib.plugins import Run, register_plugins

    # the compile cache: where JAX_COMPILATION_CACHE_DIR says, else the
    # program's fixed <checkout>/.jax_cache (tpu/jaxcache.py)
    cache_dir = enable_persistent_cache()
    peaks = None if args.rehearse else device_peaks(dev["kind"])
    if not native.available() and not args.rehearse:
        print("benchmark: the native tier fell back to Python (g++ build "
              "failed?)", file=sys.stderr)
        return 1

    compile_times: list[float] = []
    compile_names: list[str] = []

    def on_compile(name, _dur, **kw):
        if name == COMPILE_EVENT:
            compile_times.append(time.perf_counter())
            compile_names.append(str(kw.get("fun_name", kw or "?")))

    jax.monitoring.register_event_duration_secs_listener(on_compile)

    traffic = tr.load_traffic(cell["traffic"])
    for item in args.set:
        key, _, value = item.partition("=")
        traffic[key] = json.loads(value)
    scale = 1.0
    if args.rehearse:
        over = config.get("rehearse") or {}
        scale = float(over.get("length_scale", 1.0))
        traffic["pool_rows"] = min(int(traffic["pool_rows"]), 256)
        traffic["fill_rows"] = min(int(traffic.get("fill_rows", 0)), 64)
        if "rate_rows_per_s" in traffic:
            traffic["rate_rows_per_s"] = min(traffic["rate_rows_per_s"], 200)
        if traffic["arrival"] == "backlog" and traffic["kind"] == "classify":
            traffic["batch_rows"] = 64
    eng_map, sizes = build_engine_mapping(config, args.seed, args.rehearse)
    proc_cfg = eng_map["streams"][0]["pipeline"]["processors"][0]

    pool = tr.build_pool(traffic, args.seed, scale=scale)
    warm = tr.warmup_pools(traffic, args.seed, scale=scale)
    run = Run(traffic=traffic, pool=pool, warm=warm, seconds=seconds,
              fill_rows=int(traffic.get("fill_rows", 0)),
              settle_s=float(traffic.get("settle_s", 1.0)),
              output_field=proc_cfg.get("output_field")
              if traffic["kind"] == "generate" else None)
    if traffic["arrival"] == "paced":
        run.arrivals = tr.arrival_offsets(
            traffic, args.seed, run.settle_s + seconds + 10.0)
    log(f"cell {cell['name']} seed {args.seed} seconds {seconds} trace "
        f"{args.trace} device {dev} cache {cache_dir}; pool {pool.n} rows, "
        f"mean {pool.tokens.mean():.1f} tokens")

    register_plugins()
    cfg = EngineConfig.from_mapping(eng_map)
    for s in cfg.streams:
        s.input = {**s.input, "run": run}
        s.output = {**s.output, "run": run}
    init_logging(cfg.logging)
    engine = Engine(cfg)

    # -- the harness's own observers (threads, not tasks: they must not wait
    # on the engine's event loop) --------------------------------------------
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.obs.metrics import Gauge

    snaps: dict[str, dict] = {}
    gauges: dict[str, list[float]] = {}
    spans: dict[str, list[float]] = {}
    state = {"peak_bytes": 0, "trace_dir": None, "shapes0": None,
             "shapes1": None, "trace_error": None, "sampler_late_s": 0.0}
    done = threading.Event()

    def runner_of():
        try:
            procs = engine.streams[0].pipeline.processors
            return getattr(procs[0], "runner", None)
        except (IndexError, AttributeError):
            return None

    def dispatch_shapes() -> dict:
        r = runner_of()
        counts = getattr(r, "dispatch_counts", None)
        if counts is None:
            return {}
        return {tuple(dict(k)["input_ids"]): v for k, v in counts().items()
                if "input_ids" in dict(k)}

    def peak_bytes() -> int:
        """Peak on the fullest chip: buffers (``peak_bytes_in_use``) plus the
        region the runtime reserves for the compiled programs' temporaries
        (``peak_bytes_reserved``; on a v5e the two are separate pools)."""
        stats = [d.memory_stats() or {} for d in devices[:chips]]
        return max(int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)) for st in stats)

    def sampler():
        last = None
        while not done.is_set():
            t = time.perf_counter()
            if last is not None and run.in_window(t):
                # how late this thread itself woke: a stall of the whole
                # process (or machine) shows here, a stall inside the
                # pipeline or the device does not
                state["sampler_late_s"] = max(state["sampler_late_s"],
                                              t - last - 0.05)
            last = t
            if run.t_open is not None and "open" not in snaps:
                snaps["open"] = registry_snapshot()
            if run.t_close is not None and t >= run.t_close and "close" not in snaps:
                snaps["close"] = registry_snapshot()
                state["peak_bytes"] = peak_bytes()
            if args.trace and run.in_window(t):
                acc: dict[str, float] = {}
                for m in global_registry().collect():
                    if isinstance(m, Gauge):
                        acc[m.name] = acc.get(m.name, 0.0) + float(m.value)
                for k, v in acc.items():
                    gauges.setdefault(k, []).append(v)
            time.sleep(0.05 if "open" in snaps else 0.005)

    def tracer_thread():
        """A profiler trace of a few seconds inside the window."""
        while run.t_open is None and not done.is_set():
            time.sleep(0.01)
        if done.is_set():
            return
        length = min(float(traffic.get("trace_seconds", 4)), seconds - 1.5)
        if length <= 0:
            return
        time.sleep(max(0.0, run.t_open + 1.0 - time.perf_counter()))
        tdir = os.path.join(OUT_DIR, "trace", cell["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        os.makedirs(tdir, exist_ok=True)
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            state["shapes0"] = dispatch_shapes()
            jax.profiler.start_trace(tdir, profiler_options=opts)
            time.sleep(length)
            state["shapes1"] = dispatch_shapes()
            jax.profiler.stop_trace()
            state["trace_dir"] = tdir
        except Exception as e:  # a failed trace fails the run, after drain
            state["trace_error"] = repr(e)

    threads = [threading.Thread(target=sampler, daemon=True)]
    if args.trace:
        threads.append(threading.Thread(target=tracer_thread, daemon=True))
        # the program's stage spans, kept in memory for the window
        tracer = global_tracer()
        record = tracer.record

        def recording(ctx, stage, dur_s, **kw):
            if run.in_window(time.perf_counter()):
                spans.setdefault(stage, []).append(float(dur_s))
            return record(ctx, stage, dur_s, **kw)

        tracer.record = recording
    for th in threads:
        th.start()

    try:
        asyncio.run(engine.run())
    finally:
        done.set()
        for th in threads:
            th.join()
    t_end = time.perf_counter()
    if run.t_open is None:
        print("benchmark: the window never opened (the stream ended or "
              "crashed during set-up)", file=sys.stderr)
        return 1
    snaps.setdefault("close", registry_snapshot())
    if not state["peak_bytes"]:
        state["peak_bytes"] = peak_bytes()
    setup_s = run.t_open - T_START
    in_window = [n for t, n in zip(compile_times, compile_names)
                 if run.t_open <= t <= run.t_close]
    compiles = len(in_window)
    log(f"drained {t_end - run.t_close:.1f}s after the window; set-up "
        f"{setup_s:.1f}s; rows read {run.rows_read} written "
        f"{run.rows_written}; compiles in window {compiles}")

    # -- guarantees and the plain reference, outside the window --------------
    stream = engine.streams[0] if engine.streams else None
    errors, failed, delivery_ok = check_delivery(stream, run)
    processor = stream.pipeline.processors[0] if stream is not None else None
    ref_mod = load_module("references", config["reference"])

    ctx = types.SimpleNamespace(
        processor=processor, config=config, proc_cfg=proc_cfg, pool=pool,
        seed=args.seed, rehearse=args.rehearse, out_rows=run.out_rows,
        out_a=run.out_a, out_b=run.out_b)
    t_ref = time.perf_counter()
    try:
        verdict = ref_mod.judge(ctx)
    except Exception as e:
        verdict = {"ok": False, "why": f"reference failed: {e!r}"}
    log(f"reference {time.perf_counter() - t_ref:.1f}s: {verdict}")
    correct = bool(delivery_ok and verdict.get("ok"))

    # -- metrics ---------------------------------------------------------------
    trace = None
    if args.trace:
        if state["trace_error"] or not state["trace_dir"]:
            print(f"benchmark: the profiler trace failed: "
                  f"{state['trace_error']}", file=sys.stderr)
            return 1
        path = xtrace.find_xplane(state["trace_dir"])
        raw = xtrace.load_xplane(path)
        trace = xtrace.reduce_trace(raw)
        if args.keep_trace:
            with open(os.path.join(OUT_DIR, f"{cell['name']}.planes.json"), "w") as f:
                json.dump(xtrace.head(raw, 4000), f)
        shutil.rmtree(state["trace_dir"], ignore_errors=True)

    metrics: dict[str, dict] = {}
    if not args.trace:
        for m in metrics_for(bench, "end_to_end", cell["name"]):
            value = end_to_end(m["name"], run, setup_s)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        shapes = {}
        if state["shapes0"] is not None and state["shapes1"] is not None:
            shapes = {k: v - state["shapes0"].get(k, 0)
                      for k, v in state["shapes1"].items()
                      if v - state["shapes0"].get(k, 0) > 0}
        view = View(cell=cell, config=config, traffic=traffic, sizes=sizes,
                    proc_cfg=proc_cfg, device=dev, peaks=peaks, run=run,
                    snap_open=snaps.get("open", {}), snap_close=snaps["close"],
                    gauges=gauges, spans=spans, trace=trace, compiles=compiles,
                    shapes=shapes, chips=chips,
                    memory_peak_bytes=state["peak_bytes"])
        for m in metrics_for(bench, "per_layer", cell["name"]):
            mod = load_module("metrics", m["name"])
            if mod is None:
                log(f"per-layer metric {m['name']}: no reader file")
                continue
            try:
                value = mod.read(view)
            except Exception as e:
                log(f"per-layer metric {m['name']}: reader failed: {e!r}")
                value = None
            if value is not None and np.isfinite(value):
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": int(state["peak_bytes"])}
    result = {"correct": correct, "attempted": int(run.rows_read),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace is not None and trace.get("devices"):
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["detail"] = {
        "workload": cell["name"], "seed": args.seed, "seconds": seconds,
        "rehearsal": bool(args.rehearse), "setup_s": setup_s,
        "window_s": run.t_close - run.t_open,
        "drain_s": t_end - run.t_close, "reads": run.reads, "acks": run.acks,
        "nacks": run.nacks, "rows_read": run.rows_read,
        "rows_written": run.rows_written, "stream_errors": errors,
        "compiles_in_window": compiles, "compiled_in_window": in_window[:8],
        "reference": verdict,
        "compile_cache": cache_dir,
        "longest_gap_between_writes_s": max(
            (b[0] - a[0] for a, b in zip(run.writes, run.writes[1:])
             if run.in_window(a[0])), default=0.0),
        "sampler_thread_late_max_s": state["sampler_late_s"],
    }
    if run.input_lag_ms:
        # does the input fall behind? median lag of the window's first and
        # last fifth (a growing lag means the rate is not sustained)
        fifth = (run.t_close - run.t_open) / 5
        first = [a for t, a in run.input_lag_ms if t < run.t_open + fifth]
        last = [a for t, a in run.input_lag_ms if t > run.t_close - fifth]
        if first and last:
            result["detail"]["input_lag_p50_ms_first_last_fifth"] = [
                float(np.median(np.concatenate(first))),
                float(np.median(np.concatenate(last)))]
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{cell['name']}.seed{args.seed}."
                           f"trace{args.trace}.json"), "w") as f:
        keep = dict(result)
        keep["memory_stats"] = {
            k: int(v) for k, v in (devices[0].memory_stats() or {}).items()
            if isinstance(v, (int, float))}
        if trace is not None:
            keep["trace"] = {k: v for k, v in trace.items()
                             if k not in ("first_device",)}
        keep["writes"] = [[t - run.t_open, n, k] for t, n, k in run.writes]
        json.dump(keep, f)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
