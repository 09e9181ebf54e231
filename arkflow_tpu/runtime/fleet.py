"""Elastic fleet: the autoscaling controller for the disaggregated device tier.

PR 11 made every cluster worker advertise autoscaling signals over its
heartbeat (the AIMD admission ``window``, the queue-drain estimate
``drain_s``, in-flight depth — see ``runtime/cluster.py``); this module is
the consumer. A :class:`FleetController` runs inside the ingest tier next to
the ``remote_tpu`` dispatcher and closes the loop:

- **scale-out** — when window exhaustion or queue-wait growth is sustained
  past the configured policy, spawn a new cluster-worker process from the
  worker template. The newcomer's processor configs are overlaid with the
  fleet's *incumbent shape grid* (the live workers' tuner-committed
  batch/seq buckets, carried on their heartbeats) so its ``warmup`` compiles
  exactly the shapes traffic settled on — the port opens warm.
- **scale-in** — when headroom is sustained and the fleet is above
  ``min_workers``, pick the least-loaded worker, drive the existing
  ``drain`` frame (in-flight batches finish; new ones re-route along the
  hash ring), retire the process after the drain completes.
- **preemption is routine** — a worker that vanishes (spot preemption,
  SIGKILL, network wedge) is detected by the dispatcher's heartbeat
  staleness check; the controller respawns a replacement to hold
  ``min_workers``. The hash ring needs no explicit handoff: dead workers are
  filtered at plan time, so the dead member's key range lands on its ring
  successor deterministically, and in-flight batches nack through the
  stream's normal redelivery path (at-least-once, zero silent loss).

Every decision is appended to a bounded event log (exported on ``/health``
through the processor's ``cluster_report``) with a human-readable reason,
and counted on ``arkflow_fleet_size`` / ``arkflow_fleet_scale_out_total`` /
``arkflow_fleet_scale_in_total`` / ``arkflow_fleet_preempt_total``.

The controller talks to processes through a small ``Spawner`` interface so
tests can run an in-process fleet; :class:`SubprocessSpawner` is the real
one (``python -m arkflow_tpu --cluster-worker`` from a template config).
"""

from __future__ import annotations

import asyncio
import collections
import logging
import os
import socket
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, Mapping, Optional, Sequence

from arkflow_tpu.errors import ConfigError

logger = logging.getLogger("arkflow.fleet")

#: controller-spawned workers get ids in this namespace so an operator can
#: tell a template spawn from the statically configured fleet at a glance
SPAWN_ID_PREFIX = "fleet"


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FleetConfig:
    """Parsed ``fleet:`` block of a ``remote_tpu`` processor."""

    enabled: bool = True
    #: floor the controller defends: preempted workers are respawned and
    #: scale-in never drops below it
    min_workers: int = 1
    #: ceiling for scale-out
    max_workers: int = 4
    #: control-loop period
    interval_s: float = 2.0
    #: how long window exhaustion / queue-wait growth must persist before a
    #: scale-out fires (absorbs single-batch blips)
    scale_out_sustain_s: float = 10.0
    #: advertised drain estimate (seconds of queued work) that counts as
    #: queue-wait growth even when windows still show nominal headroom
    drain_high_s: float = 3.0
    #: how long fleet-wide idleness must persist before a scale-in fires
    scale_in_sustain_s: float = 30.0
    #: fleet counts as idle when aggregate in-flight <= idle_frac * aggregate
    #: advertised window
    idle_frac: float = 0.25
    #: minimum gap between any two controller actions (lets the signals
    #: resettle after a membership change before the next decision)
    cooldown_s: float = 15.0
    #: respawn departed members to hold min_workers (spot preemption policy)
    respawn: bool = True
    #: worker template: a worker-mode config mapping (``processors:`` et al,
    #: exactly what ``--cluster-worker --config`` accepts) or a path to one
    template: Any = None
    #: bind host for spawned workers
    spawn_host: str = "127.0.0.1"
    #: budget for a spawned worker to warm up and answer register
    spawn_timeout_s: float = 240.0
    #: drain budget when retiring a worker on scale-in
    drain_s: float = 30.0
    #: per-role floors/ceilings for a disaggregated (prefill/decode) fleet:
    #: ``{role: (min, max)}``. When set, respawn floors, pressure-driven
    #: scale-out and idle scale-in are decided PER ROLE (spawned workers
    #: get ``worker.role`` overlaid on the template); None = role-blind.
    roles: Any = None

    def report(self) -> dict:
        rep = {
            "enabled": self.enabled,
            "min_workers": self.min_workers,
            "max_workers": self.max_workers,
            "interval_s": self.interval_s,
            "scale_out_sustain_s": self.scale_out_sustain_s,
            "scale_in_sustain_s": self.scale_in_sustain_s,
            "drain_high_s": self.drain_high_s,
            "idle_frac": self.idle_frac,
            "cooldown_s": self.cooldown_s,
            "respawn": self.respawn,
        }
        if self.roles:
            rep["roles"] = {r: {"min": lo, "max": hi}
                            for r, (lo, hi) in sorted(self.roles.items())}
        return rep


def parse_fleet_config(cfg: Any, *, static_workers: int = 1,
                       who: str = "remote_tpu") -> Optional[FleetConfig]:
    """Pure parse of a ``fleet:`` block (no sockets, no subprocesses, no
    metric series) so ``config.py`` can run it at ``--validate`` time
    through fault ``inner`` chains like every other block. ``None`` /
    ``enabled: false`` = no controller."""
    from arkflow_tpu.utils.duration import parse_duration

    if cfg is None:
        return None
    if cfg is False:
        return None
    if cfg is True:
        cfg = {}
    if not isinstance(cfg, Mapping):
        raise ConfigError(
            f"{who}.fleet must be a mapping or boolean, got {cfg!r}")
    known = {"enabled", "min_workers", "max_workers", "interval",
             "scale_out_sustain", "scale_in_sustain", "drain_high",
             "idle_frac", "cooldown", "respawn", "template", "spawn_host",
             "spawn_timeout", "drain_timeout", "roles"}
    unknown = set(cfg) - known
    if unknown:
        raise ConfigError(
            f"{who}.fleet: unknown keys {sorted(unknown)} "
            f"(known: {sorted(known)})")
    enabled = cfg.get("enabled", True)
    if not isinstance(enabled, bool):
        raise ConfigError(
            f"{who}.fleet.enabled must be a boolean, got {enabled!r}")
    if not enabled:
        return None

    def _int(key: str, default: int, minimum: int) -> int:
        v = cfg.get(key, default)
        if isinstance(v, bool) or not isinstance(v, int) or v < minimum:
            raise ConfigError(
                f"{who}.fleet.{key} must be an int >= {minimum}, got {v!r}")
        return v

    def _dur(key: str, default: str) -> float:
        v = cfg.get(key, default)
        try:
            s = parse_duration(v)
        except (ConfigError, TypeError, ValueError) as e:
            raise ConfigError(f"{who}.fleet.{key} invalid: {e}") from e
        if s <= 0:
            raise ConfigError(f"{who}.fleet.{key} must be > 0, got {v!r}")
        return s

    min_workers = _int("min_workers", static_workers, 1)
    max_workers = _int("max_workers", max(min_workers, static_workers) + 2, 1)
    if max_workers < min_workers:
        raise ConfigError(
            f"{who}.fleet.max_workers ({max_workers}) must be >= "
            f"min_workers ({min_workers})")
    idle_frac = cfg.get("idle_frac", 0.25)
    if isinstance(idle_frac, bool) or not isinstance(idle_frac, (int, float)) \
            or not 0.0 < float(idle_frac) <= 1.0:
        raise ConfigError(
            f"{who}.fleet.idle_frac must be a number in (0, 1], "
            f"got {idle_frac!r}")
    respawn = cfg.get("respawn", True)
    if not isinstance(respawn, bool):
        raise ConfigError(
            f"{who}.fleet.respawn must be a boolean, got {respawn!r}")
    template = cfg.get("template")
    if template is not None and not isinstance(template, (str, Mapping)):
        raise ConfigError(
            f"{who}.fleet.template must be a worker-config mapping or a "
            f"path string, got {type(template).__name__}")
    if isinstance(template, Mapping):
        # validate the embedded worker config NOW — a malformed template
        # otherwise only fails at the first scale-out, mid-incident
        from arkflow_tpu.runtime.cluster import parse_worker_config

        parse_worker_config(template)
    spawn_host = cfg.get("spawn_host", "127.0.0.1")
    if not isinstance(spawn_host, str) or not spawn_host:
        raise ConfigError(
            f"{who}.fleet.spawn_host must be a non-empty string, "
            f"got {spawn_host!r}")
    roles_raw = cfg.get("roles")
    roles = None
    if roles_raw is not None:
        from arkflow_tpu.runtime.cluster import WORKER_ROLES

        if not isinstance(roles_raw, Mapping) or not roles_raw:
            raise ConfigError(
                f"{who}.fleet.roles must be a non-empty mapping of "
                f"role -> {{min, max}}, got {roles_raw!r}")
        roles = {}
        for rname, spec in roles_raw.items():
            if rname not in WORKER_ROLES:
                raise ConfigError(
                    f"{who}.fleet.roles: unknown role {rname!r} "
                    f"(known: {list(WORKER_ROLES)})")
            if not isinstance(spec, Mapping):
                raise ConfigError(
                    f"{who}.fleet.roles.{rname} must be a mapping with "
                    f"min/max, got {spec!r}")
            bad = set(spec) - {"min", "max"}
            if bad:
                raise ConfigError(
                    f"{who}.fleet.roles.{rname}: unknown keys "
                    f"{sorted(bad)} (known: ['max', 'min'])")
            lo = spec.get("min", 0)
            if isinstance(lo, bool) or not isinstance(lo, int) or lo < 0:
                raise ConfigError(
                    f"{who}.fleet.roles.{rname}.min must be an int >= 0, "
                    f"got {lo!r}")
            hi = spec.get("max", max(lo, 1))
            if isinstance(hi, bool) or not isinstance(hi, int) or hi < lo:
                raise ConfigError(
                    f"{who}.fleet.roles.{rname}.max must be an int >= "
                    f"min ({lo}), got {hi!r}")
            roles[str(rname)] = (lo, hi)
        # A role split must be able to serve both sides: a fleet whose
        # ceilings only ever admit prefill-capable workers (or only
        # decode-capable ones) can never finish a request — catch it at
        # --validate instead of as an eternal ConnectError at runtime.
        def _cap(role: str) -> int:
            return sum(hi for r, (_lo, hi) in roles.items()
                       if r == role or r == "both")
        if _cap("prefill") == 0 or _cap("decode") == 0:
            missing = "prefill" if _cap("prefill") == 0 else "decode"
            raise ConfigError(
                f"{who}.fleet.roles is one-sided: no capacity for "
                f"{missing!r} (every request needs both a prefill- and a "
                f"decode-capable worker; add a {missing!r} or 'both' "
                f"entry with max >= 1)")
    return FleetConfig(
        enabled=True,
        min_workers=min_workers,
        max_workers=max_workers,
        interval_s=_dur("interval", "2s"),
        scale_out_sustain_s=_dur("scale_out_sustain", "10s"),
        scale_in_sustain_s=_dur("scale_in_sustain", "30s"),
        drain_high_s=_dur("drain_high", "3s"),
        idle_frac=float(idle_frac),
        cooldown_s=_dur("cooldown", "15s"),
        respawn=respawn,
        template=template,
        spawn_host=spawn_host,
        spawn_timeout_s=_dur("spawn_timeout", "240s"),
        drain_s=_dur("drain_timeout", "30s"),
        roles=roles,
    )


# ---------------------------------------------------------------------------
# spawners
# ---------------------------------------------------------------------------


def free_port(host: str = "127.0.0.1") -> int:
    s = socket.socket()
    s.bind((host, 0))
    port = s.getsockname()[1]
    s.close()
    return port


def overlay_shapes(worker_cfg: Mapping, shapes: Sequence[Optional[dict]]) -> dict:
    """Warm replay: graft the fleet's incumbent shape grid onto a worker
    template so the newcomer's ``warmup`` compiles the buckets traffic
    settled on, not the template's cold defaults.

    ``shapes`` is positional — entry *i* overlays processor *i* of the
    template (``None`` = leave alone), matching the order workers report
    them on heartbeats. The overlay follows the template's ``fault.inner``
    chains so a chaos-wrapped model stage still gets its grid."""
    import copy

    out = copy.deepcopy(dict(worker_cfg))
    procs = out.get("processors")
    if procs is None and isinstance(out.get("pipeline"), Mapping):
        procs = out["pipeline"].get("processors")
    if not isinstance(procs, list):
        return out
    for i, shape in enumerate(shapes):
        if not shape or i >= len(procs):
            continue
        node = procs[i]
        # descend wrapper chains to the component that owns bucket keys
        while isinstance(node, dict) and isinstance(node.get("inner"), dict):
            node = node["inner"]
        if not isinstance(node, dict):
            continue
        for key in ("batch_buckets", "seq_buckets", "example_scale"):
            if shape.get(key) is not None:
                node[key] = shape[key]
    return out


class SubprocessSpawner:
    """The real spawner: launches ``python -m arkflow_tpu --cluster-worker``
    from the template config and reaps the processes it started.

    Owns only its own children — statically configured workers (or anything
    else on the ring) are never touched by ``retire``.

    One process for each chip: every worker builds its own device
    processors, and an accelerator belongs to one process at a time. The
    controller's process must stay off jax (an ingest tier of ``remote_tpu``
    stages does), and ``env`` is where a deployment gives each worker its
    own device — or ``JAX_PLATFORMS=cpu``, as the soaks do. A worker that
    finds its chip taken fails its start-up, and the adopt probe reports
    it; it is never handed traffic."""

    def __init__(self, template: Any, *, host: str = "127.0.0.1",
                 env: Optional[Mapping[str, str]] = None,
                 log_dir: Optional[str] = None):
        if template is None:
            raise ConfigError(
                "fleet: scale-out needs a 'template' (worker-config mapping "
                "or path) to spawn workers from")
        self.template = template
        self.host = host
        self.env = dict(env) if env is not None else None
        self.log_dir = log_dir
        self._procs: dict[str, Any] = {}  # url -> Popen
        self._seq = 0
        self._tmpdir: Optional[str] = None

    def _template_mapping(self) -> dict:
        if isinstance(self.template, Mapping):
            return dict(self.template)
        import yaml

        try:
            with open(self.template) as f:
                raw = yaml.safe_load(f) or {}
        except OSError as e:
            raise ConfigError(
                f"fleet.template {self.template!r} unreadable: {e}") from e
        if not isinstance(raw, Mapping):
            raise ConfigError(
                f"fleet.template {self.template!r} must parse to a mapping")
        return dict(raw)

    def _write_config(self, cfg: dict) -> str:
        import tempfile

        import yaml

        if self._tmpdir is None:
            self._tmpdir = tempfile.mkdtemp(prefix="arkflow-fleet-")
        self._seq += 1
        path = os.path.join(self._tmpdir, f"worker-{self._seq}.yaml")
        with open(path, "w") as f:
            yaml.safe_dump(cfg, f)
        return path

    async def spawn(self, shapes: Sequence[Optional[dict]] = (),
                    role: Optional[str] = None) -> str:
        """Launch one worker; returns its ``arkflow://`` URL immediately —
        readiness (warmup compiles before the port opens) is the
        controller's adopt-probe's problem, with its own budget.

        ``role`` overlays ``worker.role`` on the template, so one template
        serves every role of a disaggregated fleet."""
        import subprocess

        cfg = overlay_shapes(self._template_mapping(), shapes)
        if role is not None:
            w = dict(cfg.get("worker") or {})
            w["role"] = role
            cfg["worker"] = w
        port = free_port(self.host)
        url = f"arkflow://{self.host}:{port}"
        cfg_path = self._write_config(cfg)
        worker_id = f"{SPAWN_ID_PREFIX}-{os.getpid()}-{self._seq}"
        cmd = [sys.executable, "-m", "arkflow_tpu", "--cluster-worker",
               "--config", cfg_path, "--host", self.host,
               "--port", str(port), "--worker-id", worker_id]
        stdout: Any = subprocess.DEVNULL
        if self.log_dir:
            stdout = open(os.path.join(
                self.log_dir, f"{worker_id}.log"), "ab")
        self._procs[url] = subprocess.Popen(
            cmd, env=self.env, stdout=stdout, stderr=subprocess.STDOUT)
        logger.info("fleet: spawned worker %s (pid %d, id %s)", url,
                    self._procs[url].pid, worker_id)
        return url

    async def retire(self, url: str, *, grace_s: float = 30.0) -> None:
        """SIGTERM (the worker self-drains — runtime/cluster.py) and, past
        the grace budget, SIGKILL. Unknown urls are ignored: the controller
        never retires workers it didn't spawn, but a double-retire after a
        preemption race must not raise."""
        proc = self._procs.pop(url, None)
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        deadline = time.monotonic() + grace_s
        while proc.poll() is None and time.monotonic() < deadline:
            await asyncio.sleep(0.05)
        if proc.poll() is None:
            logger.warning("fleet: worker %s ignored SIGTERM for %.1fs; "
                           "killing", url, grace_s)
            proc.kill()

    def owns(self, url: str) -> bool:
        return url in self._procs

    def reap(self, url: str) -> None:
        """Forget a departed child (its process already exited)."""
        proc = self._procs.pop(url, None)
        if proc is not None and proc.poll() is None:
            proc.kill()

    async def close(self) -> None:
        for url in list(self._procs):
            await self.retire(url, grace_s=5.0)


# ---------------------------------------------------------------------------
# the controller
# ---------------------------------------------------------------------------


@dataclass
class _Sustain:
    """Edge-triggered sustain tracker: ``since`` is the monotonic time the
    condition became continuously true, or None while false."""

    since: Optional[float] = None

    def observe(self, cond: bool, now: float) -> float:
        """Returns how long the condition has been continuously true."""
        if not cond:
            self.since = None
            return 0.0
        if self.since is None:
            self.since = now
        return now - self.since


class FleetController:
    """The control loop. One instance per ``remote_tpu`` processor, started
    after the dispatcher (it needs live heartbeat state to read).

    All decisions run in one task — there is never more than one membership
    change in flight, so the signals each action perturbs are re-sampled
    before the next one (enforced belt-and-braces by ``cooldown_s``)."""

    def __init__(self, dispatcher, spawner, cfg: FleetConfig, *,
                 name: str = "cluster",
                 clock: Optional[Callable[[], float]] = None):
        from arkflow_tpu.obs import global_registry

        self.dispatcher = dispatcher
        self.spawner = spawner
        self.cfg = cfg
        self.name = name
        self.clock = clock or time.monotonic
        self._task: Optional[asyncio.Task] = None
        self._pressure = _Sustain()
        self._idle = _Sustain()
        #: per-role sustain trackers (disaggregated fleets)
        self._role_pressure: dict[str, _Sustain] = {}
        self._role_idle: dict[str, _Sustain] = {}
        self._last_action_t: Optional[float] = None
        self._events: collections.deque = collections.deque(maxlen=64)
        self._known_dead: set[str] = set()
        reg = global_registry()
        labels = {"stream": name}
        self.m_size = reg.gauge(
            "arkflow_fleet_size", "live cluster workers under fleet control",
            labels)
        self.m_scale_out = reg.counter(
            "arkflow_fleet_scale_out_total",
            "workers spawned for sustained load", labels)
        self.m_scale_in = reg.counter(
            "arkflow_fleet_scale_in_total",
            "workers drained and retired for sustained headroom", labels)
        self.m_preempt = reg.counter(
            "arkflow_fleet_preempt_total",
            "worker departures detected (missed heartbeats / process exit)",
            labels)

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        if self._task is not None:
            return
        self._refresh_size()
        self._task = asyncio.create_task(
            self._loop(), name=f"{self.name}-fleet-controller")

    async def close(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):
                pass
            self._task = None
        close = getattr(self.spawner, "close", None)
        if close is not None:
            try:
                await close()
            except Exception:
                logger.exception("fleet[%s]: spawner close failed", self.name)

    async def _loop(self) -> None:
        while True:
            await asyncio.sleep(self.cfg.interval_s)
            try:
                await self.tick()
            except asyncio.CancelledError:
                raise
            except Exception:
                # a sick control loop must never take serving down with it
                logger.exception("fleet[%s]: tick failed", self.name)

    # -- the decision tick -------------------------------------------------

    def _live(self) -> list:
        return [w for w in self.dispatcher.workers.values()
                if w.alive and not w.draining]

    def _refresh_size(self) -> int:
        n = len(self._live())
        self.m_size.set(float(n))
        return n

    def _event(self, action: str, reason: str, **extra: Any) -> dict:
        ev = {"t": round(time.time(), 3), "action": action, "reason": reason,
              **extra}
        self._events.append(ev)
        logger.info("fleet[%s]: %s — %s %s", self.name, action, reason,
                    {k: v for k, v in extra.items()} or "")
        # decisions are trace-visible: a forced root span per action means
        # the decision survives head sampling and lands in /trace with its
        # reason attached, next to the serving spans it will reshape
        try:
            from arkflow_tpu.obs.trace import global_tracer

            tracer = global_tracer()
            if tracer.enabled:
                ctx = tracer.begin()
                tracer.record(ctx, f"fleet_{action}", 0.0,
                              attrs={"reason": reason, **{
                                  k: v for k, v in extra.items()
                                  if isinstance(v, (str, int, float, bool))}})
                # "fleet" is a forced status: a membership decision is rare
                # and always worth a trace slot, like a shed or an error
                tracer.finish(ctx, status="fleet")
        except Exception:
            pass  # tracing is best-effort by design
        return ev

    def incumbent_shapes(self) -> list:
        """Freshest live worker's advertised shape grid (heartbeat
        ``shapes``), positional per template processor. Empty when no live
        worker has reported one — the template then warms its own grid."""
        best: list = []
        best_seen = -1.0
        for w in self.dispatcher.workers.values():
            if not w.alive:
                continue
            shapes = w.last_report.get("shapes")
            if shapes and w.last_seen > best_seen:
                best, best_seen = shapes, w.last_seen
        return best

    async def tick(self) -> Optional[dict]:
        """One control decision; returns the event fired (None = no-op).
        Public so tests and the chaos soak can drive the loop headlessly."""
        now = self.clock()
        await self._note_departures()
        n_live = self._refresh_size()
        live = self._live()

        if self.cfg.roles:
            return await self._tick_roles(now, n_live, live)

        # preemption floor first: holding min_workers outranks policy timers
        if self.cfg.respawn and n_live < self.cfg.min_workers:
            return await self._scale_out(
                f"fleet below min_workers ({n_live} < "
                f"{self.cfg.min_workers}) after departure", kind="respawn")

        in_cooldown = (self._last_action_t is not None
                       and now - self._last_action_t < self.cfg.cooldown_s)

        # scale-out: window exhaustion (no live worker has headroom against
        # its advertised AIMD window) or queue-wait growth (advertised drain
        # estimate high fleet-wide), sustained past the policy
        exhausted = bool(live) and all(not w.has_headroom() for w in live)
        min_drain = min((w.drain_s for w in live), default=0.0)
        queue_growth = bool(live) and min_drain > self.cfg.drain_high_s
        pressured_for = self._pressure.observe(
            exhausted or queue_growth, now)
        if (pressured_for >= self.cfg.scale_out_sustain_s
                and not in_cooldown):
            if n_live >= self.cfg.max_workers:
                self._event(
                    "scale_out_capped",
                    f"pressure sustained {pressured_for:.1f}s but fleet at "
                    f"max_workers ({self.cfg.max_workers})")
                self._pressure.since = now  # re-arm, don't spam the log
                return None
            why = ("window exhaustion" if exhausted else
                   f"queue-wait growth (min drain_s "
                   f"{min_drain:.2f} > {self.cfg.drain_high_s})")
            return await self._scale_out(
                f"{why} sustained {pressured_for:.1f}s "
                f">= {self.cfg.scale_out_sustain_s:.1f}s")

        # scale-in: sustained fleet-wide idleness above the floor
        total_window = sum(w.window for w in live)
        total_inflight = sum(w.inflight for w in live)
        idle = (bool(live)
                and total_inflight <= self.cfg.idle_frac * total_window
                and all(w.drain_s <= self.cfg.drain_high_s for w in live))
        idle_for = self._idle.observe(idle, now)
        if (idle_for >= self.cfg.scale_in_sustain_s
                and n_live > self.cfg.min_workers and not in_cooldown):
            return await self._scale_in(
                f"headroom sustained {idle_for:.1f}s >= "
                f"{self.cfg.scale_in_sustain_s:.1f}s (inflight "
                f"{total_inflight} <= {self.cfg.idle_frac} * window "
                f"{total_window})")
        return None

    async def _tick_roles(self, now: float, n_live: int,
                          live: list) -> Optional[dict]:
        """Role-aware decision pass for a disaggregated fleet: floors,
        pressure and idleness are judged per role (a starved prefill tier
        must not be masked by idle decode slots, and vice versa). Spawned
        workers get the role overlaid on the template; the global
        ``max_workers`` ceiling still binds across roles."""
        in_cooldown = (self._last_action_t is not None
                       and now - self._last_action_t < self.cfg.cooldown_s)

        def _own(role: str) -> list:
            return [w for w in live if getattr(w, "role", "both") == role]

        # respawn floors first, in deterministic role order
        for role, (lo, _hi) in sorted(self.cfg.roles.items()):
            n_role = len(_own(role))
            if self.cfg.respawn and n_role < lo:
                return await self._scale_out(
                    f"role '{role}' below floor ({n_role} < {lo}) after "
                    f"departure", kind="respawn", role=role)

        # pressure scale-out: judged over the workers that can SERVE the
        # role ('both' members count for either side)
        for role, (_lo, hi) in sorted(self.cfg.roles.items()):
            capable = [w for w in live
                       if getattr(w, "role", "both") in (role, "both")]
            exhausted = bool(capable) and all(
                not w.has_headroom() for w in capable)
            min_drain = min((w.drain_s for w in capable), default=0.0)
            queue_growth = bool(capable) and min_drain > self.cfg.drain_high_s
            tr = self._role_pressure.setdefault(role, _Sustain())
            p_for = tr.observe(exhausted or queue_growth, now)
            if p_for < self.cfg.scale_out_sustain_s or in_cooldown:
                continue
            if len(_own(role)) >= hi or n_live >= self.cfg.max_workers:
                self._event(
                    "scale_out_capped",
                    f"role '{role}' pressure sustained {p_for:.1f}s but at "
                    f"role max ({hi}) or fleet max ({self.cfg.max_workers})")
                tr.since = now  # re-arm, don't spam the log
                return None
            why = ("window exhaustion" if exhausted else
                   f"queue-wait growth (min drain_s {min_drain:.2f} > "
                   f"{self.cfg.drain_high_s})")
            return await self._scale_out(
                f"role '{role}': {why} sustained {p_for:.1f}s "
                f">= {self.cfg.scale_out_sustain_s:.1f}s", role=role)

        # idle scale-in, per role, above each role's floor
        for role, (lo, _hi) in sorted(self.cfg.roles.items()):
            own = _own(role)
            if not own:
                continue
            total_window = sum(w.window for w in own)
            total_inflight = sum(w.inflight for w in own)
            idle = (total_inflight <= self.cfg.idle_frac * total_window
                    and all(w.drain_s <= self.cfg.drain_high_s for w in own))
            tr = self._role_idle.setdefault(role, _Sustain())
            i_for = tr.observe(idle, now)
            if (i_for >= self.cfg.scale_in_sustain_s
                    and len(own) > lo and not in_cooldown):
                return await self._scale_in(
                    f"role '{role}' headroom sustained {i_for:.1f}s >= "
                    f"{self.cfg.scale_in_sustain_s:.1f}s (inflight "
                    f"{total_inflight} <= {self.cfg.idle_frac} * window "
                    f"{total_window})", candidates=own)
        return None

    async def _note_departures(self) -> None:
        """Count workers newly seen dead (missed heartbeats flip them via
        the dispatcher's staleness check; a crashed child also shows here)
        and drop controller-spawned corpses from the routing table — a
        static member may come back on its address, a preempted spawn never
        does (its replacement gets a fresh port)."""
        for url, w in list(self.dispatcher.workers.items()):
            if w.alive:
                self._known_dead.discard(url)
                continue
            if url in self._known_dead:
                continue
            self._known_dead.add(url)
            self.m_preempt.inc()
            self._event("departure", w.last_error or "worker went dead",
                        worker=url)
            if self.spawner is not None and getattr(
                    self.spawner, "owns", lambda u: False)(url):
                reap = getattr(self.spawner, "reap", None)
                if reap is not None:
                    reap(url)
                self.dispatcher.remove_worker(url)
                self._known_dead.discard(url)

    async def _scale_out(self, reason: str, *,
                         kind: str = "scale_out",
                         role: Optional[str] = None) -> Optional[dict]:
        if self.spawner is None:
            self._event(f"{kind}_skipped", f"{reason}; no spawner/template "
                        "configured")
            self._last_action_t = self.clock()
            return None
        shapes = self.incumbent_shapes()
        try:
            # role passed only when set: role-blind spawners (tests, older
            # embedders) keep their (shapes)-only signature
            if role is not None:
                url = await self.spawner.spawn(shapes, role=role)
            else:
                url = await self.spawner.spawn(shapes)
        except Exception as e:
            self._event(f"{kind}_failed", f"{reason}; spawn failed: "
                        f"{type(e).__name__}: {e}")
            self._last_action_t = self.clock()
            return None
        ok = await self._adopt(url)
        self._last_action_t = self.clock()
        self._pressure.since = None
        self._idle.since = None
        if not ok:
            try:
                await self.spawner.retire(url, grace_s=5.0)
            except Exception:
                pass
            self.dispatcher.remove_worker(url)
            ev = self._event(
                f"{kind}_failed",
                f"{reason}; worker {url} never answered register within "
                f"{self.cfg.spawn_timeout_s:.0f}s")
            return ev
        if kind == "respawn":
            pass  # departures already counted on m_preempt
        else:
            self.m_scale_out.inc()
        self._refresh_size()
        return self._event(kind, reason, worker=url,
                           warm_shapes=bool(shapes))

    async def _adopt(self, url: str) -> bool:
        """Add the newcomer to the routing table and wait for its register
        (warmup compiles happen before its port opens, so answering means
        serving-ready and shape-warm)."""
        w = self.dispatcher.add_worker(url)
        deadline = self.clock() + self.cfg.spawn_timeout_s
        while True:
            try:
                await self.dispatcher._probe(w)
            except Exception:
                pass
            if w.alive:
                return True
            if self.clock() >= deadline:
                return False
            await asyncio.sleep(min(0.25, self.cfg.interval_s))

    async def _scale_in(self, reason: str,
                        candidates: Optional[list] = None) -> Optional[dict]:
        live = candidates if candidates is not None else self._live()
        # least-loaded: fewest outstanding dispatches, then smallest drain
        # estimate; prefer retiring our own spawns over static members (the
        # yaml fleet is the operator's floor topology)
        victim = min(live, key=lambda w: (
            0 if getattr(self.spawner, "owns", lambda u: False)(w.url) else 1,
            w.inflight, w.drain_s))
        self._last_action_t = self.clock()
        self._idle.since = None
        try:
            await self.dispatcher.set_drain(victim, True)
            await self.dispatcher.wait_drained(victim, self.cfg.drain_s)
        except Exception as e:
            # a worker that won't drain keeps serving; undrain and move on
            try:
                await self.dispatcher.set_drain(victim, False)
            except Exception:
                pass
            self._event("scale_in_failed",
                        f"{reason}; drain of {victim.url} failed: "
                        f"{type(e).__name__}: {e}")
            return None
        if getattr(self.spawner, "owns", lambda u: False)(victim.url):
            try:
                await self.spawner.retire(victim.url, grace_s=self.cfg.drain_s)
            except Exception:
                logger.exception("fleet[%s]: retire of %s failed", self.name,
                                 victim.url)
        self.dispatcher.remove_worker(victim.url)
        self._known_dead.discard(victim.url)
        self.m_scale_in.inc()
        self._refresh_size()
        return self._event("scale_in", reason, worker=victim.url)

    # -- introspection -----------------------------------------------------

    def report(self) -> dict:
        # workers that died with a fenced incarnation and have not
        # re-registered under a fresh one: partition-healed zombies the
        # dispatcher is actively rejecting (runtime/cluster.py fencing) —
        # the operator's first question after a partition event
        fenced = {w.url: list(w.fenced)
                  for w in self.dispatcher.workers.values()
                  if w.fenced and not w.alive}
        return {
            "size": len(self._live()),
            "policy": self.cfg.report(),
            "scale_outs": int(self.m_scale_out.value),
            "scale_ins": int(self.m_scale_in.value),
            "departures": int(self.m_preempt.value),
            "fenced": fenced,
            "events": list(self._events),
        }
