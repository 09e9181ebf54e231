"""Multi-host distributed runtime (DCN tier).

One-call bootstrap over ``jax.distributed``: every host runs the same engine
binary, the JAX runtime forms the global device mesh across hosts (ICI within
a slice, DCN between), and the existing ``MeshSpec``/``shard_params`` path
works unchanged on the global device list. This is the XLA-collective
equivalent of a NCCL/MPI communication backend — collectives are compiled
into the program rather than hand-driven (SURVEY.md section 2.7: the
reference's only cross-node mechanisms are broker protocols and Ballista).

Environment-variable driven so k8s/slurm launchers need no config changes:

    ARKFLOW_COORDINATOR=host0:1234 ARKFLOW_NUM_PROCESSES=4 ARKFLOW_PROCESS_ID=2

Beyond the bootstrap, this module carries the **multi-host serving plane**
for the cluster tier (``runtime/cluster.py``): one model too big for a
single worker process served by a ``mesh`` that spans several
``jax.distributed`` processes. The discipline is lockstep SPMD —

- every process builds the IDENTICAL processor chain (same config, same
  seed, same warmup order), so the jitted steps and their collectives are
  compiled and entered in the same order everywhere;
- host-side eager work pins to a process-LOCAL device
  (``pin_local_default_device``) — under ``jax.distributed`` the global
  device list leads with process 0's device, and an eager op placed on a
  non-addressable device is a hard error;
- process 0 (the **primary**) opens the serving port; before running each
  batch it fans the Arrow payload out over :class:`BroadcastChannel`, and
  every other process (a **follower**, :func:`run_follower`) replays the
  identical ``pipeline.process`` call — so the shards that live on the
  follower's devices execute their half of each collective in step.

The channel is two ``broadcast_one_to_all`` collectives per message (a
fixed-shape length header, then the exact-size payload), so followers never
need to know sizes in advance, and a negative header is the clean-shutdown
signal.
"""

from __future__ import annotations

import asyncio
import logging
import os
import socket
import time
from dataclasses import dataclass
from typing import Any, Mapping, Optional

logger = logging.getLogger("arkflow.distributed")

#: header value broadcast by the primary when the serving loop ends —
#: followers exit their replay loop cleanly instead of hanging on a recv
_CLOSE_SENTINEL = -1


def _split_coordinator(coordinator: str, where: str):
    """``host:port`` -> (host, port), with a ConfigError naming the knob."""
    from arkflow_tpu.errors import ConfigError

    host, sep, port_s = str(coordinator).rpartition(":")
    if not sep or not host:
        raise ConfigError(
            f"distributed bootstrap: coordinator must be host:port, "
            f"got {coordinator!r} ({where})")
    try:
        port = int(port_s)
    except ValueError as e:
        raise ConfigError(
            f"distributed bootstrap: coordinator port must be an integer, "
            f"got {coordinator!r} ({where})") from e
    if not 0 < port < 65536:
        raise ConfigError(
            f"distributed bootstrap: coordinator port out of range "
            f"({coordinator!r}, {where})")
    return host, port


def probe_coordinator(coordinator: str, *, timeout_s: float = 10.0,
                      where: str = "") -> None:
    """TCP-probe the coordinator before handing control to
    ``jax.distributed.initialize`` — a wrong address or a coordinator that
    never came up otherwise surfaces as a raw jax RuntimeError after a long
    opaque hang. Retries until ``timeout_s`` (the coordinator may still be
    binding), then raises :class:`ConfigError` naming the address."""
    from arkflow_tpu.errors import ConfigError

    host, port = _split_coordinator(coordinator, where or "probe")
    deadline = time.monotonic() + timeout_s
    last_err: Optional[Exception] = None
    while time.monotonic() < deadline:
        try:
            with socket.create_connection((host, port), timeout=1.0):
                return
        except OSError as e:
            last_err = e
            time.sleep(0.25)
    raise ConfigError(
        f"distributed bootstrap: coordinator {coordinator!r} unreachable "
        f"after {timeout_s:.0f}s ({where or 'probe'}): {last_err} — is "
        f"process 0 up and the address/port right?")


def pin_local_default_device() -> None:
    """Pin eager dispatch to a process-local device. Must run AFTER
    ``jax.distributed.initialize``: the global ``jax.devices()`` list leads
    with process 0's devices, and any eager op (even ``PRNGKey``) placed on
    a non-addressable device raises ``INVALID_ARGUMENT``."""
    import jax

    local = jax.local_devices()
    if local:
        jax.config.update("jax_default_device", local[0])


def init_distributed(coordinator: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     *, probe_timeout_s: float = 10.0,
                     cpu_collectives: Optional[str] = None) -> bool:
    """Initialize jax.distributed from args or ARKFLOW_* env; returns True if
    multi-process mode was activated (False = single host, no-op).

    Failures are wrapped in :class:`ConfigError` naming the effective
    ``ARKFLOW_COORDINATOR`` / ``ARKFLOW_NUM_PROCESSES`` /
    ``ARKFLOW_PROCESS_ID`` values — a raw RuntimeError out of
    ``jax.distributed.initialize`` (bad address, duplicate process id, a
    coordinator that never came up) tells an operator nothing about which
    knob on which host was wrong. Non-zero processes TCP-probe the
    coordinator first (``probe_timeout_s``) so an unreachable address fails
    in seconds with the offending value, not after an opaque hang.

    ``cpu_collectives`` selects the CPU cross-process collective backend
    (``"gloo"`` is the one this repo's virtual-CPU environments support);
    default: ``gloo`` when the process is pinned to the CPU platform and
    more than one process participates. TPU slices ignore it — their
    collectives ride ICI/DCN natively."""
    from arkflow_tpu.errors import ConfigError

    coordinator = coordinator or os.environ.get("ARKFLOW_COORDINATOR")
    if not coordinator:
        return False
    raw_np = (num_processes if num_processes is not None
              else os.environ.get("ARKFLOW_NUM_PROCESSES", "1"))
    raw_pid = (process_id if process_id is not None
               else os.environ.get("ARKFLOW_PROCESS_ID", "0"))
    where = (f"ARKFLOW_COORDINATOR={coordinator!r} "
             f"ARKFLOW_NUM_PROCESSES={raw_np!r} ARKFLOW_PROCESS_ID={raw_pid!r}")
    try:
        num_processes = int(raw_np)
        process_id = int(raw_pid)
    except (TypeError, ValueError) as e:
        raise ConfigError(
            f"distributed bootstrap: ARKFLOW_NUM_PROCESSES / "
            f"ARKFLOW_PROCESS_ID must be integers ({where}): {e}") from e
    if num_processes < 1:
        raise ConfigError(
            f"distributed bootstrap: num_processes must be >= 1 ({where})")
    if not 0 <= process_id < num_processes:
        # caught BEFORE jax.distributed.initialize: the coordinator would
        # otherwise hang waiting for a process id that can never arrive
        raise ConfigError(
            f"distributed bootstrap: process_id must be in "
            f"[0, num_processes) ({where})")
    _split_coordinator(coordinator, where)  # malformed address fails here
    if process_id > 0:
        # process 0 BINDS the address (no probe possible before it starts);
        # everyone else can and should fail fast on an unreachable one
        probe_coordinator(coordinator, timeout_s=probe_timeout_s, where=where)
    import jax  # deferred: single-host pipelines shouldn't touch jax here

    prev_collectives = None
    set_collectives = False
    if num_processes > 1:
        backend = cpu_collectives
        if backend is None and _cpu_platform_pinned():
            backend = "gloo"
        if backend:
            try:
                prev_collectives = getattr(
                    jax.config, "jax_cpu_collectives_implementation", None)
                jax.config.update(
                    "jax_cpu_collectives_implementation", backend)
                set_collectives = True
            except Exception as e:  # older jax without the knob
                logger.warning("cpu collectives %r not configurable: %s",
                               backend, e)
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
    except Exception as e:
        if set_collectives:
            # a cross-process collective backend with NO distributed client
            # breaks any later single-process backend init in this process
            try:
                jax.config.update("jax_cpu_collectives_implementation",
                                  prev_collectives)
            except Exception:
                pass
        raise ConfigError(
            f"distributed bootstrap failed ({where}): {e}") from e
    pin_local_default_device()
    logger.info(
        "distributed runtime up: process %d/%d, %d global / %d local devices",
        process_id, num_processes, jax.device_count(), jax.local_device_count(),
    )
    return True


def _cpu_platform_pinned() -> bool:
    """True when the env pins jax to CPU (the containers this repo's tests
    and soaks run in do, via ``JAX_PLATFORMS=cpu``); consulted BEFORE any
    backend initializes, so it reads env rather than ``jax.devices()``."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    return "cpu" in [p.strip() for p in plats.split(",") if p.strip()]


# ---------------------------------------------------------------------------
# multi-host serving plane (cluster workers spanning processes)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MultihostContext:
    """An activated multi-host group: identity + the broadcast role."""

    coordinator: str
    num_processes: int
    process_id: int

    @property
    def is_primary(self) -> bool:
        return self.process_id == 0


def parse_distributed_config(cfg: Any, *,
                             who: str = "cluster worker") -> Optional[dict]:
    """Pure parse of a ``distributed:`` block. Env (``ARKFLOW_*``) overrides
    config — launchers stamp per-process identity there, while the shared
    YAML carries the group shape. None = block absent AND env silent."""
    from arkflow_tpu.errors import ConfigError
    from arkflow_tpu.utils.duration import parse_duration

    if cfg is None:
        cfg = {}
    if not isinstance(cfg, Mapping):
        raise ConfigError(f"{who}: 'distributed' must be a mapping, got {cfg!r}")
    known = {"coordinator", "num_processes", "process_id",
             "coordinator_timeout", "cpu_collectives"}
    unknown = set(cfg) - known
    if unknown:
        raise ConfigError(
            f"{who}: distributed: unknown keys {sorted(unknown)} "
            f"(known: {sorted(known)})")
    coordinator = os.environ.get("ARKFLOW_COORDINATOR") \
        or cfg.get("coordinator")
    if not coordinator:
        if cfg:
            raise ConfigError(
                f"{who}: distributed: needs a 'coordinator' (host:port) or "
                "the ARKFLOW_COORDINATOR env")
        return None
    out: dict = {"coordinator": str(coordinator)}
    for key, env in (("num_processes", "ARKFLOW_NUM_PROCESSES"),
                     ("process_id", "ARKFLOW_PROCESS_ID")):
        raw = os.environ.get(env, cfg.get(key))
        if raw is None:
            raw = 1 if key == "num_processes" else 0
        try:
            out[key] = int(raw)
        except (TypeError, ValueError) as e:
            raise ConfigError(
                f"{who}: distributed.{key} must be an integer, "
                f"got {raw!r}") from e
    timeout = cfg.get("coordinator_timeout", "10s")
    try:
        out["coordinator_timeout_s"] = parse_duration(timeout)
    except (ConfigError, TypeError, ValueError) as e:
        raise ConfigError(
            f"{who}: distributed.coordinator_timeout invalid: {e}") from e
    cc = cfg.get("cpu_collectives")
    if cc is not None and not isinstance(cc, str):
        raise ConfigError(
            f"{who}: distributed.cpu_collectives must be a string, got {cc!r}")
    out["cpu_collectives"] = cc
    return out


def multihost_from_config(config: Mapping) -> Optional[MultihostContext]:
    """Activate multi-host mode for a cluster worker when its config (or the
    env) names a group larger than one process: runs the full
    ``init_distributed`` bootstrap and returns the group context. None =
    single-process worker, nothing initialized."""
    parsed = parse_distributed_config(
        config.get("distributed") if isinstance(config, Mapping) else None)
    if parsed is None or parsed["num_processes"] < 2:
        return None
    init_distributed(parsed["coordinator"], parsed["num_processes"],
                     parsed["process_id"],
                     probe_timeout_s=parsed["coordinator_timeout_s"],
                     cpu_collectives=parsed["cpu_collectives"])
    return MultihostContext(coordinator=parsed["coordinator"],
                            num_processes=parsed["num_processes"],
                            process_id=parsed["process_id"])


class BroadcastChannel:
    """Primary → followers byte-stream over jax collectives.

    Each message is two ``broadcast_one_to_all`` rounds: a fixed-shape
    int64 length header, then the payload at exactly that size (so the
    follower side can allocate its placeholder — ``broadcast_one_to_all``
    needs matching shapes on every process). Both sides MUST call in the
    same order: ``send`` on the primary pairs with ``recv`` on every
    follower; ``close`` pairs with the ``recv`` that returns None.

    Calls are blocking (collectives): drive them through a thread executor
    from async code, as :class:`LockstepPipeline`/:func:`run_follower` do."""

    def __init__(self, ctx: MultihostContext):
        self.ctx = ctx
        self._closed = False

    def _bcast(self, arr):
        from jax.experimental import multihost_utils

        return multihost_utils.broadcast_one_to_all(arr)

    def send(self, payload: bytes) -> None:
        import numpy as np

        if self._closed:
            raise RuntimeError("broadcast channel is closed")
        self._bcast(np.array([len(payload)], dtype=np.int64))
        if payload:
            self._bcast(np.frombuffer(payload, dtype=np.uint8))

    def recv(self) -> Optional[bytes]:
        import numpy as np

        header = self._bcast(np.zeros((1,), dtype=np.int64))
        n = int(header[0])
        if n < 0:
            self._closed = True
            return None
        if n == 0:
            return b""
        data = self._bcast(np.zeros((n,), dtype=np.uint8))
        # the collective may promote uint8 (it reduces through a wider
        # accumulator); values stay 0..255, so cast back before rebuilding
        return np.asarray(data).astype(np.uint8, copy=False).tobytes()

    def close(self) -> None:
        import numpy as np

        if self._closed:
            return
        self._closed = True
        try:
            self._bcast(np.array([_CLOSE_SENTINEL], dtype=np.int64))
        except Exception:
            logger.exception("broadcast close failed (followers may hang "
                             "until their own timeout)")


class LockstepPipeline:
    """Primary-side pipeline wrapper: fan each batch out to the followers
    BEFORE running it locally, so every process executes the identical
    ``process`` sequence and the model's cross-process collectives stay
    matched. Batches serialize through one lock — a multi-host model IS one
    device group; interleaving two batches' collectives would deadlock."""

    def __init__(self, ctx: MultihostContext, inner):
        self._ctx = ctx
        self._inner = inner
        self.channel = BroadcastChannel(ctx)
        self._lock = asyncio.Lock()

    @property
    def processors(self):
        return self._inner.processors

    async def connect(self) -> None:
        # warmup's compiles/collectives happen here on the primary; the
        # followers run the identical connect() themselves — same order
        await self._inner.connect()

    async def process(self, batch):
        from arkflow_tpu.connect.flight import batch_to_ipc

        async with self._lock:
            ipc = batch_to_ipc(batch.record_batch)
            await asyncio.to_thread(self.channel.send, ipc)
            return await self._inner.process(batch)

    async def close(self) -> None:
        async with self._lock:
            await asyncio.to_thread(self.channel.close)
        await self._inner.close()


async def run_follower(ctx: MultihostContext, pipeline) -> None:
    """The follower loop: replay every batch the primary broadcasts through
    the identical local pipeline, discarding outputs (the primary owns the
    wire). Exits when the primary closes the channel.

    A follower-side processing error is logged and the loop continues: the
    computation is deterministic and device-spanning, so the primary saw
    the same failure and answered the client; both sides stay in step for
    the next batch."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.connect.flight import ipc_to_batches

    chan = BroadcastChannel(ctx)
    logger.info("multihost follower %d/%d: replay loop up",
                ctx.process_id, ctx.num_processes)
    while True:
        payload = await asyncio.to_thread(chan.recv)
        if payload is None:
            logger.info("multihost follower %d: primary closed; exiting",
                        ctx.process_id)
            return
        try:
            for rb in ipc_to_batches(payload):
                await pipeline.process(MessageBatch(rb))
        except Exception:
            logger.exception("multihost follower %d: replay step failed "
                             "(primary saw the same outcome)",
                             ctx.process_id)
