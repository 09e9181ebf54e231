"""Engine: builds and supervises all streams + serves health/metrics.

Mirrors ``Engine::run`` (ref: crates/arkflow-core/src/engine/mod.rs:81-289):
build every stream from config, spawn them concurrently, install
SIGINT/SIGTERM handlers that flip a cancellation event (ref :246-262), and run
an HTTP server with ``/health``, ``/readiness``, ``/liveness`` endpoints
(ref :99-209) — here extended with the ``/metrics`` Prometheus endpoint the
reference declared a dependency for but never shipped (SURVEY.md section 5).

A crashed stream is logged without taking the engine down (ref :268-273);
with a ``restart:`` policy it is rebuilt from config and restarted with
backoff — elastic recovery the reference doesn't attempt.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import logging
import signal
from typing import Optional

from aiohttp import web

from arkflow_tpu.components.registry import ensure_plugins_loaded
from arkflow_tpu.config import EngineConfig
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.startup import startup_report
from arkflow_tpu.obs.trace import global_tracer
from arkflow_tpu.runtime.stream import Stream, build_stream

logger = logging.getLogger("arkflow.engine")


class Engine:
    def __init__(self, config: EngineConfig):
        self.config = config
        self.cancel = asyncio.Event()
        self.streams: list[Stream] = []
        self._ready = False
        self._runner: Optional[web.AppRunner] = None
        #: per-stream restart accounting surfaced on /health: cumulative
        #: restarts plus the remaining budget of the CURRENT crash window
        #: (the budget re-earns after reset_after_s of healthy run)
        self._restart_stats: dict[str, dict] = {}

    # -- introspection (health/readiness payloads) -------------------------

    @staticmethod
    def _stream_runner_reports(stream: Stream) -> list[dict]:
        """Per-runner health snapshots for every device-backed processor of
        a stream (``ModelRunner.health_report`` returns one dict, a pool
        returns one per member); non-device processors contribute nothing."""
        reports: list[dict] = []
        for proc in getattr(stream.pipeline, "processors", None) or []:
            runner = getattr(proc, "runner", None)
            report = getattr(runner, "health_report", None)
            if report is None:
                continue
            try:
                rep = report()
            except Exception:  # a sick runner must not break /health itself
                logger.exception("health_report failed for stream %s", stream.name)
                continue
            reports.extend(rep if isinstance(rep, list) else [rep])
        return reports

    @staticmethod
    def _stream_swappers(stream: Stream) -> list:
        """Hot-swap managers of every swappable processor of a stream
        (tpu/swap.py), walking ``_inner`` chains so chaos wrapping doesn't
        hide them — the surface POST /admin/swap and /health drive."""
        swappers = []
        for proc in getattr(stream.pipeline, "processors", None) or []:
            node, seen = proc, set()
            while node is not None and id(node) not in seen:
                seen.add(id(node))
                sw = getattr(node, "swapper", None)
                if sw is not None and hasattr(sw, "swap"):
                    swappers.append(sw)
                    break
                node = getattr(node, "_inner", None)
        return swappers

    @staticmethod
    def _stream_tuners(stream: Stream) -> list:
        """Shape tuners of every adaptive processor of a stream
        (tpu/tuner.py), walking ``_inner`` chains like the swap managers —
        the surface POST /admin/tune and /health drive."""
        tuners = []
        for proc in getattr(stream.pipeline, "processors", None) or []:
            node, seen = proc, set()
            while node is not None and id(node) not in seen:
                seen.add(id(node))
                tn = getattr(node, "tuner", None)
                if tn is not None and hasattr(tn, "run_cycle"):
                    tuners.append(tn)
                    break
                node = getattr(node, "_inner", None)
        return tuners

    def stream_health(self) -> dict:
        """Restart accounting + per-runner device health, per stream."""
        out: dict[str, dict] = {}
        for s in self.streams:
            info = dict(self._restart_stats.get(
                s.name, {"restarts": 0, "restart_budget_remaining": None}))
            runners = self._stream_runner_reports(s)
            if runners:
                info["runners"] = runners
            ctrl = getattr(s, "overload", None)
            if ctrl is not None:
                try:
                    info["overload"] = ctrl.report()
                except Exception:  # introspection must not break /health
                    logger.exception("overload report failed for stream %s", s.name)
            caches = []
            for proc in getattr(s.pipeline, "processors", None) or []:
                # walk fault/decorator wrappers via their _inner chain (the
                # attach_overload convention) so a chaos-wrapped inference
                # stage still reports its cache
                node, seen = proc, set()
                while node is not None and id(node) not in seen:
                    seen.add(id(node))
                    report = getattr(getattr(node, "cache", None), "report", None)
                    if report is not None:
                        try:
                            caches.append(report())
                        except Exception:
                            logger.exception("cache report failed for stream %s",
                                             s.name)
                        break
                    node = getattr(node, "_inner", None)
            if caches:
                info["response_caches"] = caches
            swaps = []
            for sw in self._stream_swappers(s):
                try:
                    swaps.append(sw.report())
                except Exception:  # introspection must not break /health
                    logger.exception("swap report failed for stream %s", s.name)
            if swaps:
                info["swap"] = swaps
            tuners = []
            for tn in self._stream_tuners(s):
                try:
                    tuners.append(tn.report())
                except Exception:  # introspection must not break /health
                    logger.exception("tuner report failed for stream %s", s.name)
            if tuners:
                info["tuner"] = tuners
            clusters = []
            for proc in getattr(s.pipeline, "processors", None) or []:
                # disaggregated serving (runtime/cluster.py): the remote_tpu
                # dispatch stage aggregates per-worker register/heartbeat
                # state — same _inner-chain walk as the cache/swap reports
                from arkflow_tpu.runtime.cluster import _walk_inner

                report = _walk_inner(proc, "cluster_report")
                if report is None:
                    continue
                try:
                    clusters.append(report())
                except Exception:
                    logger.exception("cluster report failed for stream %s",
                                     s.name)
            if clusters:
                info["cluster"] = clusters
            integrity = []
            for proc in getattr(s.pipeline, "processors", None) or []:
                # SDC defense plane (tpu/integrity.py): per-member state +
                # last-probe age — same _inner-chain walk as the others
                from arkflow_tpu.runtime.cluster import _walk_inner

                mon = _walk_inner(proc, "integrity")
                if mon is None or not hasattr(mon, "report"):
                    continue
                try:
                    integrity.append(mon.report())
                except Exception:
                    logger.exception("integrity report failed for stream %s",
                                     s.name)
            if integrity:
                info["integrity"] = integrity
            out[s.name] = info
        return out

    # -- health/metrics server (ref engine/mod.rs:99-209) ------------------

    async def _start_health_server(self) -> None:
        hc = self.config.health_check
        if not hc.enabled:
            return
        app = web.Application()

        def health(_req):
            body = {"status": "ok" if not self.cancel.is_set() else "shutting_down",
                    "streams": len(self.streams),
                    # one-line tracing liveness: retained spans/traces,
                    # sample rate and the forced-sample count — an operator
                    # can tell tracing is alive without hitting /trace
                    "tracing": global_tracer().summary(),
                    # how long start-up took, stage by stage, and which
                    # served programs are still cold (obs/startup.py)
                    "startup": startup_report(),
                    "stream_health": self.stream_health()}
            return web.Response(text=json.dumps(body), content_type="application/json")

        def readiness(_req):
            if not self._ready:
                return web.Response(status=503, text='{"status":"not_ready"}',
                                    content_type="application/json")
            # per-runner health instead of a binary flag: a stream whose
            # device runners are ALL dead — or ALL quarantined CORRUPT, the
            # DEAD-adjacent integrity state — cannot serve; report not_ready
            # so the orchestrator rotates this replica out
            dead = {}
            runners = {}
            for s in self.streams:
                reports = self._stream_runner_reports(s)
                if not reports:
                    continue
                runners[s.name] = [r.get("state") for r in reports]
                if all(r.get("state") in ("dead", "corrupt")
                       for r in reports):
                    dead[s.name] = len(reports)
            if dead:
                body = {"status": "not_ready", "dead_runner_streams": dead,
                        "runners": runners}
                return web.Response(status=503, text=json.dumps(body),
                                    content_type="application/json")
            body = {"status": "ready", **({"runners": runners} if runners else {})}
            return web.Response(text=json.dumps(body),
                                content_type="application/json")

        def liveness(_req):
            return web.Response(text='{"status":"alive"}', content_type="application/json")

        def metrics(_req):
            return web.Response(text=global_registry().exposition(),
                                content_type="text/plain", charset="utf-8")

        def trace(req):
            """GET /trace?n=16&min_seq=0 — the slowest-N retained traces
            (span trees, worker-tier spans stitched in) plus the per-stage
            latency breakdown: p50/p99 and each stage's share of summed
            end-to-end time. Sheds, deadline overruns and errors are always
            retained (forced sampling), so the pathological traces are here
            even at low sample rates."""
            tracer = global_tracer()
            try:
                n = int(req.query.get("n", 0)) or None
                min_seq = int(req.query.get("min_seq", 0))
            except ValueError:
                return web.Response(status=400,
                                    text='{"error":"n/min_seq must be ints"}',
                                    content_type="application/json")
            body = {"summary": tracer.summary(),
                    "stage_breakdown": tracer.stage_breakdown(min_seq),
                    "slowest": tracer.slowest(n, min_seq)}
            return web.Response(text=json.dumps(body),
                                content_type="application/json")

        profile_lock = asyncio.Lock()

        async def profile(req):
            """POST /debug/profile?seconds=5 — capture a JAX device trace
            under the configured ``profiling_dir`` (view with
            tensorboard/xprof). The reference has no profiler hooks at all
            (SURVEY.md section 5). Opt-in via config; duration capped at 60s;
            one capture at a time."""
            import time as _time

            import math

            try:
                seconds = float(req.query.get("seconds", "5"))
            except ValueError:
                return web.Response(status=400, text="seconds must be a number")
            if not math.isfinite(seconds):  # min/max don't clamp NaN
                return web.Response(status=400, text="seconds must be finite")
            seconds = min(max(seconds, 0.1), 60.0)
            if profile_lock.locked():
                return web.Response(status=409, text="a capture is already running")
            out_dir = f"{hc.profiling_dir.rstrip('/')}/trace-{int(_time.time())}"
            async with profile_lock:
                import jax

                try:
                    jax.profiler.start_trace(out_dir)
                    try:
                        await asyncio.sleep(seconds)
                    finally:
                        jax.profiler.stop_trace()  # never leave the profiler on
                except Exception as e:
                    return web.Response(status=500, text=f"profile failed: {e}")
            return web.Response(text=json.dumps({"trace_dir": out_dir, "seconds": seconds}),
                                content_type="application/json")

        async def admin_swap(req):
            """POST /admin/swap {"checkpoint": "/path", "stream": "name"?} —
            rolling model hot-swap (tpu/swap.py) on every swappable
            processor of the targeted stream(s), sequentially (the rolling
            discipline extends across streams). Each swap canary-verifies
            the candidate and rolls back on any failure with the old
            version serving throughout; the response carries the per-stream
            verdicts. 200 = every swap committed, 409 = no swap ran /
            some rolled back (old versions still serving)."""
            from arkflow_tpu.errors import SwapError

            try:
                body = await req.json()
            except Exception:
                return web.Response(
                    status=400, text='{"error":"body must be JSON"}',
                    content_type="application/json")
            ckpt = body.get("checkpoint") if isinstance(body, dict) else None
            if not ckpt or not isinstance(ckpt, str):
                return web.Response(
                    status=400,
                    text='{"error":"a \'checkpoint\' path is required"}',
                    content_type="application/json")
            target = body.get("stream")
            results: dict[str, list] = {}
            ok_all, found = True, False
            for s in self.streams:
                if target is not None and s.name != target:
                    continue
                for sw in self._stream_swappers(s):
                    found = True
                    try:
                        rep = {"ok": True, **(await sw.swap(ckpt))}
                    except SwapError as e:
                        ok_all, rep = False, {"ok": False, "error": str(e)}
                    except Exception as e:  # an unexpected bug must still answer
                        ok_all = False
                        rep = {"ok": False,
                               "error": f"{type(e).__name__}: {e}"}
                    results.setdefault(s.name, []).append(rep)
            if not found:
                return web.Response(
                    status=404,
                    text=json.dumps({"error": "no hot-swappable processors"
                                     + (f" in stream {target!r}" if target else "")}),
                    content_type="application/json")
            return web.Response(
                status=200 if ok_all else 409,
                text=json.dumps({"ok": ok_all, "results": results}),
                content_type="application/json")

        async def admin_tune(req):
            """POST /admin/tune {"stream": "name"?} — force one shape-tuner
            observe->propose->warm->flip cycle (tpu/tuner.py) on every
            adaptive processor of the targeted stream(s). The hysteresis
            margin still applies — a stable workload answers "rejected",
            not a flap. 200 = every cycle ran (committed, rejected or
            skipped are all valid outcomes), 409 = a flip rolled back
            (incumbent grid still serving), 404 = no adaptive processors."""
            from arkflow_tpu.errors import TunerError

            target = None
            if req.can_read_body:
                try:
                    body = await req.json()
                except Exception:
                    return web.Response(
                        status=400, text='{"error":"body must be JSON"}',
                        content_type="application/json")
                if body is not None and not isinstance(body, dict):
                    return web.Response(
                        status=400, text='{"error":"body must be an object"}',
                        content_type="application/json")
                target = (body or {}).get("stream")
            results: dict[str, list] = {}
            ok_all, found = True, False
            for s in self.streams:
                if target is not None and s.name != target:
                    continue
                for tn in self._stream_tuners(s):
                    found = True
                    try:
                        rep = {"ok": True, **(await tn.run_cycle(force=True))}
                    except TunerError as e:
                        ok_all, rep = False, {"ok": False, "error": str(e)}
                    except Exception as e:  # an unexpected bug must still answer
                        ok_all = False
                        rep = {"ok": False, "error": f"{type(e).__name__}: {e}"}
                    results.setdefault(s.name, []).append(rep)
            if not found:
                return web.Response(
                    status=404,
                    text=json.dumps({"error": "no shape-tunable processors"
                                     + (f" in stream {target!r}" if target else "")}),
                    content_type="application/json")
            return web.Response(
                status=200 if ok_all else 409,
                text=json.dumps({"ok": ok_all, "results": results}),
                content_type="application/json")

        app.router.add_get(hc.path, health)
        app.router.add_get("/readiness", readiness)
        app.router.add_get("/liveness", liveness)
        app.router.add_get("/metrics", metrics)
        app.router.add_get("/trace", trace)
        app.router.add_post("/admin/swap", admin_swap)
        app.router.add_post("/admin/tune", admin_tune)
        if hc.profiling_dir:
            app.router.add_post("/debug/profile", profile)
        runner = web.AppRunner(app, access_log=None)
        await runner.setup()
        site = web.TCPSite(runner, hc.host, hc.port)
        await site.start()
        self._runner = runner
        logger.info("health server on %s:%d", hc.host, hc.port)

    def _install_signal_handlers(self) -> None:
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, self.cancel.set)
            except (NotImplementedError, RuntimeError):  # non-main thread / platform
                pass

    # -- lifecycle ---------------------------------------------------------

    async def run(self) -> None:
        from arkflow_tpu.parallel.distributed import init_distributed

        init_distributed()  # no-op unless ARKFLOW_COORDINATOR is set
        ensure_plugins_loaded()
        if self.config.tracing is not None:
            # apply the `tracing:` block to the process-global tracer BEFORE
            # streams build (they capture it at construction)
            global_tracer().configure(self.config.tracing)
        await self._start_health_server()
        self._install_signal_handlers()

        async def backoff(seconds: float) -> bool:
            """Cancel-aware sleep; True if we should keep going."""
            cancel_wait = asyncio.ensure_future(self.cancel.wait())
            try:
                await asyncio.wait({cancel_wait}, timeout=seconds)
            finally:
                cancel_wait.cancel()
            return not self.cancel.is_set()

        async def run_one(stream: Stream, cfg, name: str) -> None:
            import time as _time

            # normalize once: tolerate policy dicts built without
            # _restart_config (programmatic StreamConfig) missing any key
            policy = cfg.restart
            if policy:
                policy = {"max_retries": policy.get("max_retries", 3),
                          "backoff_s": policy.get("backoff_s", 5.0),
                          "reset_after_s": policy.get("reset_after_s", 300.0)}
            else:
                policy = {}
            retries = 0
            stats = {"restarts": 0,
                     "restart_budget_remaining": (policy["max_retries"]
                                                  if policy else None)}
            self._restart_stats[name] = stats
            while True:
                run_started = _time.monotonic()
                try:
                    await stream.run(self.cancel)
                    logger.info("[%s] finished", stream.name)
                    return
                except Exception:
                    logger.exception("[%s] stream crashed", stream.name)
                if not policy or self.cancel.is_set():
                    return  # reference behavior: log, don't take the engine down
                # a long healthy run earns back the full budget, so a stream
                # that crashes once a day doesn't die permanently on the Nth
                if _time.monotonic() - run_started >= policy["reset_after_s"]:
                    retries = 0
                # retry loop: each attempt consumes budget and must yield a
                # FRESH instance — the crashed one's components are closed
                # and may hold broken connections, so it is never re-run
                while True:
                    stats["restart_budget_remaining"] = max(
                        0, policy["max_retries"] - retries)
                    if retries >= policy["max_retries"]:
                        logger.error("[%s] restart budget exhausted (%d)", name,
                                     policy["max_retries"])
                        return
                    retries += 1
                    stats["restarts"] += 1
                    stats["restart_budget_remaining"] = max(
                        0, policy["max_retries"] - retries)
                    logger.warning("[%s] restarting (%d/%d) in %.1fs", name,
                                   retries, policy["max_retries"], policy["backoff_s"])
                    if not await backoff(policy["backoff_s"]):
                        return
                    try:
                        stream = build_stream(cfg, name=name)
                        break
                    except Exception:
                        logger.exception("[%s] rebuild failed", name)
                # swap into self.streams so introspection/shutdown see the
                # LIVE instance
                for i, old in enumerate(self.streams):
                    if old.name == name:
                        self.streams[i] = stream
                        break

        try:
            named = [
                (build_stream(s, name=s.name or f"stream-{i}"), s,
                 s.name or f"stream-{i}")
                for i, s in enumerate(self.config.streams)
            ]
            self.streams = [st for st, _, _ in named]
            self._ready = True
            await asyncio.gather(*(run_one(st, cfg, name) for st, cfg, name in named))
        finally:
            self._ready = False
            if self._runner is not None:
                with contextlib.suppress(Exception):
                    await self._runner.cleanup()

    def shutdown(self) -> None:
        self.cancel.set()
