"""ModelRunner: the XLA execution provider for streaming inference.

This is the TPU-native replacement for the reference's PyO3 Python-processor
slot (ref: crates/arkflow-plugin/src/processor/python.rs; SURVEY.md section
3.4): same pipeline position, but batch -> pad-to-bucket -> XLA-compiled
model -> unpad -> batch.

Responsibilities:
- Resolve a model family + config, init or restore params.
- Optionally shard params over a ``Mesh`` (tensor parallel serving). With a
  ``dp`` axis the dispatch is data-parallel for real: inputs/outputs carry
  explicit ``NamedSharding``s splitting the batch dim over dp, buckets scale
  by dp so per-chip shards stay bucket-exact, and the single-device wins
  (eager sharded prefetch, input donation) stay enabled under the mesh.
- Keep one compiled executable per (batch, seq) bucket warm; ``jax.jit``
  owns the cache, ``warmup()`` precompiles the bucket grid so steady-state
  never hits a compile.
- Run inference off the event loop (``asyncio`` executor) so device sync
  never stalls the stream's other stages.
- Keep the device pipeline full (SURVEY.md section 7.5): ``infer()`` splits
  host prep (pad, off-loop) from the non-blocking XLA dispatch, and bounds
  in-flight device steps with a semaphore — with >=2 stream workers, step
  n+1's infeed/dispatch overlaps step n's compute (double buffering), and
  duty-cycle / infeed-stall metrics report how full the device stayed.
"""

from __future__ import annotations

import asyncio
import logging
import os
from functools import partial
from typing import Any, Optional

import jax
import numpy as np

from arkflow_tpu.config import PP_SERVING_REMOVED
from arkflow_tpu.errors import ConfigError, RunnerDead, StepDeadlineExceeded
from arkflow_tpu.models import get_model
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.startup import cold_step, note_programs, setup_stage
from arkflow_tpu.obs.trace import annotated, record_stage
from arkflow_tpu.parallel.mesh import (
    MeshSpec,
    batch_sharding,
    create_mesh,
    dp_size,
    param_shardings,
    shard_params,
)
from arkflow_tpu.tpu.bucketing import BucketPolicy, bucket_cap_bus, pad_batch_dim, pad_seq_dim
from arkflow_tpu.tpu.health import HealthConfig
# the self-healing substrate (health gates, deadline watchdog, chaos hooks)
# lives in the shared serving core now; these re-exports keep the historical
# import surface (tests, fault plugin) stable
from arkflow_tpu.tpu.serving_core import (  # noqa: F401  (re-exported)
    FIRST_COMPILE_DEADLINE_SCALE,
    InjectedOom,
    ServingRunnerCore,
    is_oom_error,
)

logger = logging.getLogger("arkflow.tpu")


def _env_int(name: str, default: int, minimum: Optional[int] = None) -> int:
    """Tolerant int env knob: malformed or out-of-range values log a warning
    and fall back to the default (like the ARKFLOW_FLASH kill switch, a bad
    env value must not crash runner setup; explicit config values DO raise)."""
    raw = os.environ.get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError:
        logger.warning("%s=%r is not an int; using %d", name, raw, default)
        return default
    if minimum is not None and val < minimum:
        logger.warning("%s=%d is below %d; using %d", name, val, minimum, default)
        return default
    return val


def _env_flash_floor(default: int = 128) -> int:
    return _env_int("ARKFLOW_FLASH_MIN_SEQ", default)


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def convert_for_serving(params, serving_dtype: Optional[str], family_name: str = ""):
    """Cast/quantize a host param tree for the serving dtype.

    - ``int8``: W8A8 dynamic quantization — dense weights to per-channel int8
      (doubles the MXU roofline vs bf16), everything else to bf16.
    - ``bfloat16``/``float16``: full-tree float cast — halves param HBM +
      host->device transfer and keeps matmuls on the MXU's native dtype;
      logits/softmax layers still accumulate/cast to f32 inside the model.

    Shared by ``ModelRunner`` and the device pool, which converts ONCE and
    hands the result to N members (the walk over a large checkpoint is the
    expensive part, not the per-member device transfer)."""
    if serving_dtype == "int8":
        from arkflow_tpu.models.quantize import quantize_for_serving

        params, n_q = quantize_for_serving(params)
        logger.info("[%s] int8 serving: %d dense layers quantized",
                    family_name, n_q)
    elif serving_dtype and serving_dtype != "float32":
        import jax.numpy as jnp

        target = getattr(jnp, serving_dtype)
        params = jax.tree_util.tree_map(
            lambda a: a.astype(target)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            params,
        )
    return params


def init_host_params(family, cfg, seed: int, checkpoint: Optional[str] = None):
    """Init (and optionally restore) a param tree on host CPU — op-by-op init
    on the accelerator compiles one tiny program per op, so the tree is
    built on the host and transferred to the execution device(s) in one hop
    (where jax is held to the accelerator platform alone there is no host
    backend, and the init runs on the default device). Shared by
    ``ModelRunner`` and the device pool (which inits once for N members)."""
    try:
        # local_devices, not devices: under multi-host ``jax.distributed``
        # the global list leads with process 0's device, and pinning an
        # eager init op to a non-addressable device is a hard error.
        cpus = jax.local_devices(backend="cpu")
        cpu = cpus[0] if cpus else None
    except RuntimeError:
        cpu = None
    with setup_stage("setup_init_params"):
        with jax.default_device(cpu) if cpu is not None else _nullcontext():
            params = family.init(jax.random.PRNGKey(seed), cfg)
        # the eager ops are dispatched, not done: placement reads the tree
        # next and would wait for them under its own name
        jax.block_until_ready(params)
    if checkpoint:
        from arkflow_tpu.tpu.checkpoint import restore

        try:
            with setup_stage("setup_restore"):
                params = restore(checkpoint, params)
            logger.info("restored checkpoint from %s", checkpoint)
        except ConfigError:
            raise
        except Exception as e:
            raise ConfigError(
                f"failed to restore checkpoint {checkpoint!r}: {e}") from e
    return params


class _StagingPool:
    """Recycled host-side staging buffers, keyed by padded shape signature.

    ``np.pad`` allocates a fresh bucket-sized array per input per step; in
    steady state every step lands in an already-seen bucket, so the padded
    arrays are recycled instead — zero fresh allocations on the hot path.
    Buffers are checked out during prep and returned only after the step
    fully completes (outputs fetched), so on backends where ``device_put``
    may alias host memory a recycled buffer can never race an in-flight
    transfer. Thread-safe: prep runs on executor threads.

    Sizing invariant: ``max_per_key`` must cover every buffer set that can
    be simultaneously checked out on one key — the dispatched-not-fetched
    steps (``dispatch_depth`` of them at depth > 1, in-flight steps
    otherwise) plus one set in prep. The pool itself can NEVER deadlock —
    ``acquire`` returns None on an empty stack and the caller allocates
    fresh — but an undersized cap silently reintroduces a per-step
    allocation on the hot path (release drops buffers beyond the cap), so
    the runner asserts the derived size at construction instead of finding
    out from an allocation profile.
    """

    def __init__(self, max_per_key: int, min_required: int = 1):
        import threading

        # ``min_required`` is the owner's statement of how many sets can be
        # simultaneously checked out on one key (in-flight steps + one in
        # prep). The assert relates the CAP to that bound, so a future
        # change to the sizing formula that forgets the dispatch-depth term
        # fails here at construction instead of silently regressing the hot
        # path to one fresh bucket-sized allocation per step (release()
        # drops buffers beyond the cap; acquire() never blocks).
        assert max_per_key >= min_required >= 1, (
            f"staging max_per_key={max_per_key} cannot cover the "
            f"{min_required} concurrently-held buffer sets per key")
        self._free: dict[tuple, list[dict[str, np.ndarray]]] = {}
        self._max = max_per_key
        self._lock = threading.Lock()

    def acquire(self, key: tuple) -> Optional[dict[str, np.ndarray]]:
        with self._lock:
            stack = self._free.get(key)
            return stack.pop() if stack else None

    def release(self, key: tuple, bufs: dict[str, np.ndarray]) -> None:
        with self._lock:
            stack = self._free.setdefault(key, [])
            if len(stack) < self._max:
                stack.append(bufs)


class ModelRunner:
    def __init__(
        self,
        model: str,
        model_config: Optional[dict] = None,
        *,
        buckets: Optional[BucketPolicy] = None,
        mesh_spec: Optional[MeshSpec] = None,
        checkpoint: Optional[str] = None,
        seed: int = 0,
        devices=None,
        serving_dtype: Optional[str] = None,
        max_in_flight: Optional[int] = None,
        dispatch_depth: Optional[int] = None,
        packed: bool = False,
        host_params=None,
        device_label: Optional[str] = None,
        step_deadline_s: Optional[float] = None,
        step_deadline_first_s: Optional[float] = None,
        health_config: Optional[HealthConfig] = None,
    ):
        from arkflow_tpu.tpu.jaxcache import enable_persistent_cache

        enable_persistent_cache()
        self.family = get_model(model)
        self.cfg = self.family.make_config(**(model_config or {}))
        raw_flash = getattr(self.cfg, "use_flash_attention", False)
        self.cfg = self._resolve_auto_flags(self.cfg, devices, mesh_spec,
                                            packed=packed)
        #: flash explicitly requested in user config (never mutated): only
        #: then does an unservable mask raise; auto-chosen flash falls back
        #: to XLA instead of failing the stream. Immutable so concurrent
        #: _prep threads can't race a fallback into a spurious raise.
        self._flash_user_forced = raw_flash is True
        import threading

        self._flash_lock = threading.Lock()
        self.buckets = buckets or BucketPolicy()
        self.packed = packed
        if packed:
            # packed execution (tpu/packing.py): the family must publish a
            # packed apply + its input spec; rows carry several examples, so
            # flops/row tracks real token count instead of bucket padding
            extras = self.family.extras or {}
            if "apply_packed" not in extras:
                raise ConfigError(
                    f"model {model!r} has no packed execution support "
                    "(family extras lack apply_packed/packed_input_spec)")
            self.spec = extras["packed_input_spec"](self.cfg)
        else:
            self.spec = self.family.input_spec(self.cfg)
        if serving_dtype not in (None, "float32", "bfloat16", "float16", "int8"):
            raise ConfigError(
                f"serving_dtype {serving_dtype!r} invalid "
                "(float32/bfloat16/float16/int8)")
        self.serving_dtype = serving_dtype

        # shared host tree (device pool): the pool inits/restores AND
        # dtype-converts once; every member transfers the SAME finished
        # weights to its own chip — replication by construction, and no
        # N-fold init or full-tree cast/quantize walks
        converted = host_params is not None
        if not converted:
            host_params = init_host_params(self.family, self.cfg, seed,
                                           checkpoint)

        self.mesh = None
        self._device = None
        self._input_sharding = None
        #: PartitionSpecs the params were placed with (None off-mesh) — kept
        #: so a hot-swap (tpu/swap.py) can place a candidate tree EXACTLY
        #: like the original, including the int8 spec rewrite
        self._pspecs = None
        axes: dict[str, str] = {}
        if mesh_spec is not None and mesh_spec.pp > 1:
            raise ConfigError(PP_SERVING_REMOVED)
        if mesh_spec is not None and mesh_spec.num_devices > 1:
            self.mesh = create_mesh(mesh_spec, devices=devices)
            axes = {name: name for name in self.mesh.axis_names}
            pspecs = self.family.param_specs(self.cfg, axes) if self.family.param_specs else None
            if pspecs is not None and self.serving_dtype == "int8":
                # int8 params carry {"w_q","w_scale"} where the float tree had
                # {"w"}; rewrite the spec tree the same way so tp/ep layouts
                # (and the doubled int8 MXU roofline) survive quantization
                from arkflow_tpu.models.quantize import quantize_param_specs

                pspecs = quantize_param_specs(pspecs)
            self._pspecs = pspecs
            # dp-sharded dispatch: the batch dim splits over the dp axis, so
            # every GLOBAL bucket scales by dp — per-chip shards stay exactly
            # on the configured bucket grid, and divisibility is structural
            self.buckets = self.buckets.dp_scaled(dp_size(self.mesh))
            self._input_sharding = batch_sharding(self.mesh)
            platform = next(iter(self.mesh.devices.flat)).platform
        else:
            self._device = devices[0] if devices else jax.devices()[0]
            platform = self._device.platform
        with setup_stage("setup_place"):
            # the serving-dtype cast is placement's: transfer + cast
            if not converted:
                host_params = convert_for_serving(
                    host_params, self.serving_dtype, self.family.name)
            self.params = self._place(host_params)
        #: retained CONVERTED host tree — the known-good repair source the
        #: integrity plane (tpu/integrity.py) re-adopts from when a member
        #: is quarantined, and the reference tree its golden signature is
        #: computed against. Pool members share ONE tree (the pool passes
        #: ``host_params`` in), so retention costs one host copy per model,
        #: not per chip.
        self.host_params = host_params
        #: per-leaf blake2b baseline (tpu/integrity.py); None = not yet
        #: baselined, or invalidated by ``adopt_params`` — the integrity
        #: monitor recomputes it lazily off-path at its next digest pass
        #: (right after the adopt is the known-good moment)
        self.param_digests: Optional[dict[str, str]] = None
        self._axes = axes
        #: donate padded inputs to the jitted call so XLA reuses their HBM
        #: for outputs (input-output aliasing) — under a mesh the sharded
        #: input buffers donate per-chip the same way. Accelerator-only: the
        #: CPU backend has no donation and would warn per compile.
        #: ARKFLOW_DONATE=0 is the operator kill switch.
        self._donate = (
            platform in ("tpu", "gpu")
            and os.environ.get("ARKFLOW_DONATE", "1") != "0"
        )
        #: eager host->device prefetch (see _to_device): accelerator-only —
        #: on the CPU backend there is no transfer/compute overlap to win,
        #: only an extra executor hop per step. Under a mesh the prefetch is
        #: a sharded device_put (each chip receives only its dp shard).
        #: ARKFLOW_PREFETCH=1/0 forces.
        prefetch_env = os.environ.get("ARKFLOW_PREFETCH")
        self._prefetch = (
            prefetch_env != "0"
            and (platform in ("tpu", "gpu") or prefetch_env == "1")
        )

        if getattr(self.cfg, "use_ring_attention", False) and "sp" not in axes:
            raise ConfigError(
                "use_ring_attention requires a mesh with an 'sp' axis "
                "(set mesh: {sp: N} on the processor)"
            )
        self._build_jitted()

        reg = global_registry()
        # packed runners get their own metric family: fill/padding have
        # different semantics (token fill vs row fill), and sharing a
        # reservoir with an unpacked runner would mix the distributions.
        # Device-pool members add a ``device`` label so duty-cycle / stall /
        # throughput read PER CHIP instead of summing the pool into one line.
        labels = {"model": model, **({"packed": "1"} if packed else {}),
                  **({"device": device_label} if device_label is not None else {})}
        self.m_infer = reg.histogram("arkflow_tpu_infer_seconds", "device step latency", labels)
        self.m_rows = reg.counter("arkflow_tpu_rows_total", "rows inferred", labels)
        self.m_pad = reg.counter("arkflow_tpu_pad_rows_total", "padding rows (waste)", labels)
        self.m_fill = reg.histogram(
            "arkflow_tpu_batch_fill_ratio", "true rows / bucket rows", labels,
            buckets=[0.125, 0.25, 0.5, 0.75, 0.9, 1.0],
        )
        self.m_compiles = reg.counter("arkflow_tpu_compiles_total", "bucket compiles", labels)
        self.m_warm_compiles = reg.counter(
            "arkflow_tpu_warm_compiles_total",
            "bucket executables compiled OFF the serving path (shape-tuner "
            "warm/probe; compiles_total stays flat across a tuned flip)", labels)
        self.m_exec_rows = reg.counter(
            "arkflow_tpu_exec_rows_total",
            "bucket rows dispatched to the device, padding included (the "
            "honest FLOPs denominator; rows_total counts true examples)", labels)
        self.m_tokens = reg.counter(
            "arkflow_tpu_tokens_total",
            "true (non-padding) tokens dispatched — packed runners and "
            "unpacked token models (attention-mask sum) alike; the "
            "numerator of effective tokens/sec", labels)
        self.m_token_capacity = reg.counter(
            "arkflow_tpu_token_capacity_total",
            "token slots dispatched (bucket rows x padded seq): "
            "1 - tokens_total/capacity is the capacity-weighted padding "
            "waste INCLUDING seq padding — the honest aggregate; the "
            "per-step waste histogram over-weights small tail windows and "
            "reads row fill only for unpacked runners", labels)
        self.m_inflight = reg.gauge(
            "arkflow_tpu_steps_inflight", "device steps dispatched, not yet complete", labels)
        self.m_busy_s = reg.counter(
            "arkflow_tpu_device_busy_seconds_total",
            "wall seconds with >=1 step in flight (duty-cycle numerator)", labels)
        self.m_stall_s = reg.counter(
            "arkflow_tpu_infeed_stall_seconds_total",
            "wall seconds the device sat idle between steps (host-bound)", labels)
        # the per-gap distribution behind the stall total: the direct
        # before/after measurement for dispatch-depth / double-buffering
        # work (ROADMAP item 5) — p50 gap >> 0 means host prep serializes
        # with device compute
        self.m_idle_gap = reg.histogram(
            "arkflow_tpu_device_idle_gap_seconds",
            "gap between step N completing and step N+1 launching "
            "(device idle between consecutive steps)", labels)
        self.m_prep = reg.histogram(
            "arkflow_tpu_infeed_prep_seconds",
            "host-side infeed prep (pad/stage/validate) per step", labels)
        self.m_waste = reg.histogram(
            "arkflow_padding_waste_frac",
            "padding fraction of each dispatched bucket (pad rows / bucket rows; "
            "token padding frac for packed runners)", labels,
            buckets=[0.0, 0.125, 0.25, 0.5, 0.75, 0.9, 1.0],
        )
        # 0/1 gauges so "are the PR-2 wins actually on?" is answerable from
        # the metrics endpoint (and asserted by bench/tests) instead of
        # re-deriving the env/platform gates by hand
        self.m_prefetch_on = reg.gauge(
            "arkflow_tpu_prefetch_active",
            "1 when eager host->device prefetch is enabled for this runner", labels)
        self.m_prefetch_on.set(1 if self._prefetch else 0)
        self.m_donate_on = reg.gauge(
            "arkflow_tpu_donate_active",
            "1 when input donation (input-output aliasing) is enabled", labels)
        self.m_donate_on.set(1 if self._donate else 0)
        self._seen_shapes: set[tuple] = set()
        #: traffic dispatches per padded shape key (warmup excluded) — the
        #: shape tuner's observe-side ground truth for which compiled
        #: shapes live traffic actually lands on; guarded by the flash lock
        #: alongside _seen_shapes (same call site, same threads)
        self._dispatch_counts: dict[tuple, int] = {}
        self._in_warmup = False
        #: device queue depth. 2 = double buffering (prep/dispatch n+1
        #: overlaps compute of n) — enough when dispatch latency ~ 0. Where
        #: a step also pays a sizeable dispatch+sync round trip, keeping
        #: ceil((rtt+c)/c) steps in flight is what saturates the chip; not
        #: yet measured on a locally attached chip. Config ``max_in_flight``
        #: / env ARKFLOW_INFLIGHT override.
        if max_in_flight is None:
            max_in_flight = _env_int("ARKFLOW_INFLIGHT", 2, minimum=1)
        if max_in_flight < 1:  # explicit config/kwarg values DO raise
            raise ConfigError(f"max_in_flight must be >= 1, got {max_in_flight}")
        self.max_in_flight = max_in_flight
        #: dispatch depth: at 1 (default) a step holds its in-flight permit
        #: through dispatch AND output fetch — the device queue drains to
        #: empty before the next worker's step can dispatch whenever the
        #: workers run at the in-flight bound. At 2 the permit is released
        #: once the step is ENQUEUED: the fetch (device sync + host copy)
        #: happens outside the in-flight window, so the next step's infeed +
        #: dispatch overlaps this step's compute even at max_in_flight 1,
        #: and staging is double-buffered per step (one set in flight, one
        #: in prep). Env ARKFLOW_DISPATCH_DEPTH overrides the default.
        if dispatch_depth is None:
            dispatch_depth = _env_int("ARKFLOW_DISPATCH_DEPTH", 1, minimum=1)
        if dispatch_depth < 1:  # explicit config/kwarg values DO raise
            raise ConfigError(f"dispatch_depth must be >= 1, got {dispatch_depth}")
        self.dispatch_depth = dispatch_depth
        self._inflight_sem: Optional[asyncio.Semaphore] = None
        #: loop the semaphores are bound to: a runner outliving its loop
        #: (bench/profile phases, engine restarts) must rebuild them, or the
        #: next infer() dies with "bound to a different event loop"
        self._sem_loop: Optional[asyncio.AbstractEventLoop] = None
        #: bounds DEVICE-RESIDENT prefetched input batches (held across the
        #: whole step): one more than the in-flight depth, so exactly one
        #: batch sits staged ahead of the compute queue — otherwise every
        #: stream worker could park a padded batch in HBM
        self._prefetch_sem: Optional[asyncio.Semaphore] = None
        #: bounds dispatched-not-fetched steps at dispatch_depth > 1 (held
        #: enqueue -> outputs fetched); see _ensure_sems
        self._depth_sem: Optional[asyncio.Semaphore] = None
        self._inflight = 0
        self._busy_start = 0.0
        self._last_idle_start: Optional[float] = None
        #: per-bucket recycled host staging buffers (unpacked path only —
        #: packed layouts have data-dependent shapes). One set per possible
        #: concurrent step plus one in prep; at dispatch_depth > 1 each
        #: dispatched-not-fetched step ALSO holds its set (released only
        #: after the fetch), so the cap grows with the depth — the
        #: _StagingPool docstring states the invariant, the assert below
        #: pins it so a future resize can't silently regress depth-2 to a
        #: fresh allocation per step. ARKFLOW_STAGING=0 disables.
        self._staging: Optional[_StagingPool] = None
        if not packed and os.environ.get("ARKFLOW_STAGING", "1") != "0":
            # held sets per key: at depth > 1 the depth semaphore bounds
            # dispatched-not-fetched steps to dispatch_depth (each holds
            # its set until the fetch), depth 1 holds max_in_flight inside
            # the permit; plus one set in prep either way
            self._staging = _StagingPool(
                max_per_key=self.max_in_flight + self.dispatch_depth,
                min_required=(self.dispatch_depth if self.dispatch_depth > 1
                              else self.max_in_flight) + 1)

        # -- self-healing device layer (step deadlines / OOM degradation /
        # -- health state machine) — shared serving core ---------------------
        self.device_label = device_label
        health_name = f"{model}" + (f"[dev {device_label}]" if device_label else "")
        self.core = ServingRunnerCore(
            name=health_name,
            labels=labels,
            step_deadline_s=step_deadline_s,
            step_deadline_first_s=step_deadline_first_s,
            health_config=health_config,
            rebuild_fn=self._rebuild_after_incident,
        )
        self.health = self.core.health
        self.m_deadline_miss = self.core.m_deadline_miss
        self.m_rebuilds = self.core.m_rebuilds
        self.m_oom = reg.counter(
            "arkflow_tpu_oom_total",
            "device RESOURCE_EXHAUSTED / OOM failures observed in steps", labels)
        #: largest batch bucket this runner will still dispatch; shrinks
        #: permanently when the device OOMs on a bucket
        self.m_bucket_cap = reg.gauge(
            "arkflow_tpu_bucket_cap",
            "largest batch bucket currently served (shrinks after device OOM)",
            labels)
        self.m_bucket_cap.set(self.buckets.max_batch())

    @staticmethod
    def _resolve_auto_flags(cfg, devices, mesh_spec, packed: bool = False):
        """``use_flash_attention=None`` means auto: the ragged Pallas kernel
        on single-device TPU serving (it skips the fully-padded K tiles XLA
        attention burns MXU cycles on), XLA attention elsewhere (Pallas on
        CPU is interpret-only — orders of magnitude slower; under a mesh the
        kernel would need a shard_map wrapper, so sharded serving keeps the
        GSPMD-partitionable XLA path). ``ARKFLOW_FLASH=0`` is the operator
        kill switch: it forces the XLA path even over an explicit
        ``use_flash_attention: true`` in config — including the packed
        segment kernel. Packed mode: ``ARKFLOW_PACKED_FLASH=1`` opts packed
        serving into the segment flash kernel on TPU backends (cfg field
        ``packed_flash``, single-device only like auto flash)."""
        if not hasattr(cfg, "use_flash_attention"):
            return cfg
        import dataclasses

        def _on_tpu() -> bool:
            from arkflow_tpu.tpu.serving_core import on_tpu_backend

            return on_tpu_backend(devices)

        if (packed and hasattr(cfg, "packed_flash")
                and not cfg.packed_flash
                and os.environ.get("ARKFLOW_PACKED_FLASH", "0") == "1"
                and os.environ.get("ARKFLOW_FLASH", "1") != "0"
                and (mesh_spec is None or mesh_spec.num_devices <= 1)
                and (_on_tpu() or cfg.flash_interpret)):
            cfg = dataclasses.replace(cfg, packed_flash=True)

        if os.environ.get("ARKFLOW_FLASH", "1") == "0":
            return dataclasses.replace(cfg, use_flash_attention=False,
                                       **({"packed_flash": False}
                                          if hasattr(cfg, "packed_flash") else {}))
        if packed and getattr(cfg, "packed_flash", False):
            # an EXPLICIT packed_flash in config must meet the same guards
            # the env grant enforces — fail at construction, not with a
            # Mosaic lowering error on the first packed step
            if mesh_spec is not None and mesh_spec.num_devices > 1:
                raise ConfigError(
                    "packed_flash is single-device for now (the segment "
                    "kernel needs a shard_map wrapper under a mesh)")
            if not (_on_tpu() or cfg.flash_interpret):
                raise ConfigError(
                    "packed_flash requires a TPU backend "
                    "(or flash_interpret for CPU tests)")
        if cfg.use_flash_attention is not None:
            # explicit config keeps its own floor; when config left the
            # floor unset, a set ARKFLOW_FLASH_MIN_SEQ fills it (a
            # config-pinned flash_min_seq still wins over the env var —
            # weaker than the ARKFLOW_FLASH=0 kill switch, which overrides
            # config unconditionally)
            if (cfg.use_flash_attention
                    and getattr(cfg, "flash_min_seq", 0) is None
                    and os.environ.get("ARKFLOW_FLASH_MIN_SEQ")):
                return dataclasses.replace(
                    cfg, flash_min_seq=_env_flash_floor())
            return cfg
        if mesh_spec is not None and mesh_spec.num_devices > 1:
            return dataclasses.replace(cfg, use_flash_attention=False)
        on_tpu = _on_tpu()
        extra = {}
        if on_tpu and getattr(cfg, "flash_min_seq", 0) is None:
            # auto-chosen flash only engages at seqs where the kernel wins:
            # short buckets tile below the MXU (tile=seq<128) and the grid
            # overhead dominates — v5e A/B at seq 32 measured XLA 47% faster
            # end-to-end; on-chip the two are within ~5% from seq 128 up,
            # with Pallas ahead at low fill.
            # Only fills the floor when unset, so an operator-tuned
            # flash_min_seq in config survives auto-resolution.
            extra["flash_min_seq"] = _env_flash_floor()
        return dataclasses.replace(cfg, use_flash_attention=on_tpu, **extra)

    def _build_jitted(self) -> None:
        """(Re)build the jitted step from the CURRENT self.cfg. jax.jit keys
        executables on the function object, so any cfg change that alters
        tracing (e.g. disabling flash attention) must rebuild — mutating
        self.cfg alone would keep serving stale executables for seen shapes."""
        apply_fn = (self.family.extras["apply_packed"] if self.packed
                    else self.family.apply)
        # thread mesh/axes into families whose apply understands sharded
        # execution (e.g. decoder ring attention); others get plain calls
        import inspect

        sig = inspect.signature(apply_fn)
        extra_kwargs: dict[str, Any] = {}
        if "axes" in sig.parameters and self._axes:
            extra_kwargs["axes"] = self._axes
        if "mesh" in sig.parameters and self.mesh is not None:
            extra_kwargs["mesh"] = self.mesh
        cfg = self.cfg

        # the function's name is the compiled program's name in a profiler
        # trace (``jit_classify_step``): readers match it, keep it stable
        def classify_step(params, inputs):
            return apply_fn(params, cfg, **inputs, **extra_kwargs)

        # donate the padded inputs (argnum 1, never the params): XLA's
        # input-output aliasing reuses their device buffers for outputs,
        # trimming steady-state HBM churn on accelerator backends
        jit_kwargs: dict[str, Any] = {}
        if self._donate:
            jit_kwargs["donate_argnums"] = (1,)
        if self.mesh is not None:
            # dp-sharded dispatch: pin params to their placed shardings and
            # split every input/output batch dim over dp explicitly — host
            # numpy fed to jit is otherwise fully replicated, so each chip
            # would redundantly compute the whole batch. The single
            # ``_input_sharding`` is a pytree prefix: it broadcasts over the
            # inputs dict (all model inputs lead with the batch/example dim)
            # and over every output leaf.
            jit_kwargs["in_shardings"] = (param_shardings(self.params),
                                          self._input_sharding)
            jit_kwargs["out_shardings"] = self._input_sharding
        self._jitted = jax.jit(classify_step, **jit_kwargs)
        #: the program's name, as JAX's compile events and a trace carry it
        self._program = classify_step.__name__
        note_programs((self._program,))

    def _disable_flash(self) -> None:
        """Auto-fallback: serve with XLA attention from now on (one
        recompile per bucket; prior flash executables are abandoned).
        Concurrent _prep threads may call this together; the lock makes
        the cfg flip + jit rebuild happen once."""
        import dataclasses

        with self._flash_lock:
            if not getattr(self.cfg, "use_flash_attention", False):
                return  # another thread already fell back
            self.cfg = dataclasses.replace(self.cfg, use_flash_attention=False)
            self._seen_shapes.clear()
            self._build_jitted()

    # -- shape plumbing ----------------------------------------------------

    def _pad_inputs_packed(self, inputs: dict[str, np.ndarray]) -> tuple[dict[str, Any], int]:
        """Pad a packed layout (tpu/packing.py): [P, S] row arrays pad P to a
        batch bucket (dead rows: segment 0), [E] example-index arrays pad E
        to its own EXAMPLE bucket (they point at row 0/pos 0, sliced off by
        the true-count return; the example grid extends ``example_scale``
        past the row grid because a full row bucket of short texts carries
        several examples per row). Fill metric reports TOKEN fill — the
        quantity packing exists to maximize."""
        p = inputs["input_ids"].shape[0]
        e = inputs["example_row"].shape[0]
        mb = self.buckets.max_batch()
        me = self.buckets.max_examples()
        if p > mb or e > me:
            raise ConfigError(
                f"packed batch ({p} rows / {e} examples) exceeds the grid "
                f"(max {mb} rows / {me} examples); carve row windows that "
                "fit before dispatch (tpu/packing.py carve_row_windows)")
        pb = self.buckets.batch_bucket(p)
        eb = self.buckets.example_bucket(e)
        out = {}
        for name, (dtype, trailing) in self.spec.items():
            arr = inputs.get(name)
            if arr is None:
                raise ConfigError(f"model {self.family.name!r} missing input {name!r}")
            arr = np.asarray(arr, dtype=dtype)
            if "seq" in trailing:
                arr = pad_seq_dim(arr, self.buckets.seq_bucket(arr.shape[1]), axis=1)
                arr = pad_batch_dim(arr, pb)
            else:
                arr = pad_batch_dim(arr, eb)
            out[name] = arr
        sb = out["input_ids"].shape[1]
        true_tokens = int((np.asarray(inputs["segment_ids"]) > 0).sum())
        if not self._in_warmup:  # warmup shapes are not traffic
            self.m_pad.inc(pb - p)
            fill = true_tokens / (pb * sb) if pb * sb else 0.0
            self.m_fill.observe(fill)
            self.m_waste.observe(1.0 - fill)
            self.m_exec_rows.inc(pb)
            self.m_tokens.inc(true_tokens)
            self.m_token_capacity.inc(pb * sb)
        return out, e

    def _pad_inputs(self, inputs: dict[str, np.ndarray]) -> tuple[dict[str, Any], int]:
        """Pad every input to its bucket; returns (padded, true_batch).

        Allocation-free in steady state: the padded arrays come from the
        per-bucket staging pool and are filled in place (rows, then zeroed
        padding regions); ``np.pad``'s fresh bucket-sized allocations only
        happen the first few times a bucket is seen. The buffers go back to
        the pool via ``_release_staging`` after the step completes.
        """
        if self.packed:
            return self._pad_inputs_packed(inputs)
        n = next(iter(inputs.values())).shape[0]
        bb = self.buckets.batch_bucket(n)
        arrs: dict[str, np.ndarray] = {}
        shapes: dict[str, tuple] = {}
        for name, (dtype, trailing) in self.spec.items():
            arr = inputs.get(name)
            if arr is None:
                raise ConfigError(f"model {self.family.name!r} missing input {name!r}")
            arr = np.asarray(arr, dtype=dtype)
            if "seq" in trailing:
                sb = self.buckets.seq_bucket(arr.shape[1])
                if arr.shape[1] > sb:  # over-long rows truncate to the top bucket
                    arr = pad_seq_dim(arr, sb, axis=1)
                shapes[name] = (bb, sb, *arr.shape[2:])
            else:
                shapes[name] = (bb, *arr.shape[1:])
            if arr.shape[0] > bb:
                raise ValueError(f"batch {arr.shape[0]} exceeds bucket {bb}")
            arrs[name] = arr
        out = self._acquire_staging(shapes)
        for name, arr in arrs.items():
            buf = out[name]
            if arr.ndim >= 2 and arr.shape[1] < buf.shape[1]:
                buf[:n, : arr.shape[1]] = arr
                buf[:n, arr.shape[1]:] = 0
            else:
                buf[:n] = arr
            buf[n:] = 0
        if not self._in_warmup:  # warmup shapes are not traffic
            self.m_pad.inc(bb - n)
            self.m_fill.observe(n / bb)
            self.m_waste.observe((bb - n) / bb if bb else 0.0)
            self.m_exec_rows.inc(bb)
            if "attention_mask" in arrs:
                # token models: true tokens vs dispatched token slots, so
                # 1 - tokens/capacity is the capacity-weighted padding waste
                # INCLUDING seq padding — the quantity the shape tuner's
                # seq-edge retuning moves, invisible to the row-only
                # histogram above (bench/soak read these counters)
                mask_shape = shapes["attention_mask"]
                self.m_tokens.inc(int(arrs["attention_mask"].sum()))
                self.m_token_capacity.inc(int(bb * mask_shape[1]))
        return out, n

    # -- staging buffer recycling ------------------------------------------

    @staticmethod
    def _staging_key(shapes: dict[str, tuple]) -> tuple:
        return tuple(sorted(shapes.items()))

    def _acquire_staging(self, shapes: dict[str, tuple]) -> dict[str, np.ndarray]:
        if self._staging is not None:
            bufs = self._staging.acquire(self._staging_key(shapes))
            if bufs is not None:
                return bufs
        return {name: np.empty(shape, dtype=self.spec[name][0])
                for name, shape in shapes.items()}

    def _release_staging(self, padded: dict[str, Any]) -> None:
        """Return a step's staging buffers once nothing can still read them
        (the step's outputs were fetched). No-op for packed layouts and for
        dicts whose values were swapped for device arrays upstream."""
        if self._staging is None or self.packed or not padded:
            return
        if not all(isinstance(v, np.ndarray) for v in padded.values()):
            return
        self._staging.release(
            self._staging_key({k: v.shape for k, v in padded.items()}), padded)

    def _shape_key(self, padded: dict[str, np.ndarray]) -> tuple:
        return tuple((k, v.shape) for k, v in sorted(padded.items()))

    def _note_shape(self, padded: dict[str, Any]) -> bool:
        """First-seen-shape accounting for the compile counter; returns True
        when the shape is new (the step will compile — the deadline watchdog
        grants it the first-compile budget). Guarded by the flash lock:
        ``infer_sync`` (executor threads) and ``infer`` (the event loop) race
        here, and an unsynchronized check-then-add both double-counts
        compiles and can miss ``_disable_flash``'s concurrent
        ``_seen_shapes.clear()`` (which holds the same lock)."""
        key = self._shape_key(padded)
        with self._flash_lock:
            if not self._in_warmup:
                self._dispatch_counts[key] = self._dispatch_counts.get(key, 0) + 1
            if key not in self._seen_shapes:
                self._seen_shapes.add(key)
                self.m_compiles.inc()
                return True
        return False

    def dispatch_counts(self) -> dict[tuple, int]:
        """Traffic dispatches per padded shape key (warmup excluded)."""
        with self._flash_lock:
            return dict(self._dispatch_counts)

    def compiled_grid(self) -> set[tuple[int, int]]:
        """``(rows, seq)`` of every token program compiled so far — by
        warm-up, an off-path warm or traffic: what the length split
        (``bucketing.carve_by_length``) names without a first-sight compile."""
        with self._flash_lock:
            return {shape for key in self._seen_shapes
                    for name, shape in key if name == "input_ids"}

    # -- self-healing: chaos hook / watchdog / OOM degradation --------------
    # (the health state machine, deadline watchdog, and chaos queue live in
    # the shared ServingRunnerCore; the runner keeps the OOM degradation
    # policy, which is bucket-grid-specific)

    def inject_step_fault(self, kind: str, duration_s: float = 0.0) -> None:
        """Arm a fault on this runner (fault plugin's processor wrapper):
        ``hang``/``oom`` are one-shot step faults consumed by the next
        device step, ``sdc`` persistently garbles step outputs until the
        integrity repair clears it (both live in the shared core), and
        ``bitflip`` corrupts one param leaf of the LIVE placed tree in
        place — the HBM bit-flip / defective-chip failure mode the
        integrity plane (tpu/integrity.py) exists to catch."""
        if kind == "bitflip":
            self._bitflip_params()
            return
        self.core.inject_step_fault(kind, duration_s)

    def _bitflip_params(self) -> None:
        """Corrupt the largest float leaf of ``self.params`` in place (the
        leaf most likely to be a weight matrix every forward touches). The
        corruption persists until the integrity monitor repairs the member
        by re-adopting ``host_params`` — exactly like real HBM corruption,
        nothing on the serving path notices by itself."""
        import jax.numpy as jnp

        flat, treedef = jax.tree_util.tree_flatten_with_path(self.params)
        best: Optional[int] = None
        for i, (_, leaf) in enumerate(flat):
            dt = getattr(leaf, "dtype", None)
            if (dt is not None and jnp.issubdtype(dt, jnp.floating)
                    and getattr(leaf, "size", 0)
                    and (best is None or leaf.size > flat[best][1].size)):
                best = i
        if best is None:
            raise ConfigError(
                "bitflip: model has no float param leaf to corrupt")
        path, leaf = flat[best]
        host = np.asarray(jax.device_get(leaf))
        garbled = (np.asarray(host, np.float32) * -1000.0 + 3.7).astype(
            host.dtype)
        placed = jax.device_put(garbled, leaf.sharding)
        leaves = [l for _, l in flat]
        leaves[best] = placed
        # one-assignment flip, like adopt_params — but WITHOUT invalidating
        # the digest baseline: the whole point is that the drift is silent
        self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        logger.warning("[%s] chaos: bitflip corrupted param leaf %s",
                       self.family.name, jax.tree_util.keystr(path))

    @property
    def step_deadline_s(self) -> Optional[float]:
        return self.core.step_deadline_s

    @step_deadline_s.setter
    def step_deadline_s(self, v: Optional[float]) -> None:
        self.core.step_deadline_s = v

    @property
    def step_deadline_first_s(self) -> Optional[float]:
        return self.core.step_deadline_first_s

    @step_deadline_first_s.setter
    def step_deadline_first_s(self, v: Optional[float]) -> None:
        self.core.step_deadline_first_s = v

    def _step_blocking(self, padded: dict[str, Any]):
        """The full blocking device step (chaos hook -> dispatch -> fetch).
        Always runs on an executor/watchdog thread: warm shapes cost one
        sub-ms hop, cold shapes compile for seconds-to-minutes on remote
        backends — never on the event loop — and the deadline watchdog can
        abandon the thread if the device wedges. Returns ``(outputs,
        seconds of the fetch alone)``."""
        self.core.apply_chaos()
        return self._wait_and_fetch(self._enqueue_step(padded))

    def _cold_step_blocking(self, padded: dict[str, Any]):
        """``_step_blocking`` for a first-seen shape (``_note_shape``): the
        program's first call — trace, lower, compile or cache load, first
        execution — as ``setup_cold_step{program}``, timed on the thread
        that makes it."""
        with cold_step(self._program):
            return self._step_blocking(padded)

    def _wait_and_fetch(self, dev_out):
        """Wait for a dispatched step, then copy its outputs to the host.
        The copy (and host conversion) is timed apart from the wait, so
        ``device_fetch`` can be told from the step it follows; an executor
        thread carries no trace scope, so the seconds go back to the
        coroutine, which records them."""
        with annotated("device_wait"):
            jax.block_until_ready(dev_out)
        with annotated("device_fetch") as fetch:
            out = jax.device_get(dev_out)
        # corrupt_outputs: identity unless an sdc fault is armed (chaos)
        return self.core.corrupt_outputs(out), fetch.dur_s

    def _enqueue_step(self, padded: dict[str, Any]):
        """Dispatch half of a depth-split step (``dispatch_depth`` > 1):
        the jitted call only ENQUEUES on the device and returns its output
        futures — all waiting (and the chaos hook, so an injected hang is
        watched by the fetch deadline) happens in the fetch half. Runs on
        an executor thread: a warm dispatch is sub-ms, but a first-seen
        shape compiles synchronously here and must not block the loop."""
        with annotated("device_enqueue"):
            return self._dispatch(padded)

    def _note_oom(self, bucket_rows: int) -> bool:
        """Device OOM on a ``bucket_rows`` bucket: permanently cap the batch
        grid below it (``arkflow_tpu_bucket_cap``) and announce the cap so
        live coalescers stop merging emissions the device can't hold.
        Returns True when a smaller bucket exists (the caller re-chunks and
        retries); False when even the smallest bucket OOMs — the runner goes
        UNHEALTHY and the failure surfaces."""
        self.m_oom.inc()
        with self._flash_lock:
            capped = self.buckets.capped(bucket_rows)
            if capped is None:
                self.health.mark_unhealthy(
                    f"device OOM at the smallest bucket ({bucket_rows} rows)")
                return False
            self.buckets = capped
        cap = capped.max_batch()
        self.m_bucket_cap.set(cap)
        bucket_cap_bus().announce(cap)
        self.health.mark_degraded(f"device OOM: batch buckets capped at {cap}")
        logger.warning(
            "[%s] device OOM on a %d-row bucket: batch grid capped at %d; "
            "splitting the batch and retrying", self.family.name, bucket_rows, cap)
        return True

    def _rebuild_after_incident(self) -> None:
        """Core rebuild hook (runs inside the heal gate after a deadline
        miss): executables cached across a device hang are not trusted, so
        the next (probe) step recompiles from scratch. Shares the flash lock
        with the other cfg-flip/rebuild paths."""
        with self._flash_lock:
            self._seen_shapes.clear()
            self._build_jitted()
        logger.warning("[%s] rebuilt jitted step after a deadline miss",
                       self.family.name)

    # -- live hot-swap surface (tpu/swap.py) --------------------------------

    def place_params(self, host_params):
        """Place a (converted) host param tree exactly like ``__init__``
        placed the original: sharded with the same PartitionSpecs under a
        mesh, a one-hop transfer to the runner's device otherwise.
        Blocking (device transfer) — swap runs it on an executor thread,
        never the serving loop."""
        with setup_stage("setup_place"):
            return self._place(host_params)

    def _place(self, host_params):
        placed = (shard_params(host_params, self._pspecs, self.mesh)
                  if self.mesh is not None
                  else jax.device_put(host_params, self._device))
        # the transfers are enqueued, not done: the stage around this call
        # ends where the tree is on the device (its caller needs it next)
        return jax.block_until_ready(placed)

    def adopt_params(self, placed):
        """Atomically flip serving onto ``placed``; returns the prior tree
        (the rollback token). Params ride the jitted step as an ARGUMENT
        (never a traced constant), so the flip is one attribute assignment:
        in-flight steps finish on the tree they already read, the next
        dispatch serves the new weights, and — same structure/dtypes/
        shardings — no executable recompiles."""
        old, self.params = self.params, placed
        # the digest baseline described the OLD tree; the integrity monitor
        # re-baselines lazily at its next off-path pass (adopt must not pay
        # a synchronous full-tree device_get on the event loop)
        self.param_digests = None
        return old

    def swap_units(self) -> list[tuple[str, "ModelRunner"]]:
        """A single runner is one flippable unit (the pool overrides this
        with its per-member rolling order)."""
        return [("runner", self)]

    # -- integrity surface (tpu/integrity.py) -------------------------------

    def digest_params(self) -> dict[str, str]:
        """Per-leaf digests of the LIVE placed tree. Blocking (device_get
        of every leaf) — callers keep it off the event loop, holding the
        in-flight permit when serving (:meth:`verify_params_live`)."""
        from arkflow_tpu.tpu.integrity import tree_digests

        return tree_digests(self.params)

    def rebaseline_digests(self) -> dict[str, str]:
        """Recompute and store the digest baseline — at a known-good
        moment only (boot, committed swap, verified integrity repair).
        Blocking, like :meth:`digest_params`."""
        self.param_digests = self.digest_params()
        return self.param_digests

    async def verify_params_live(self) -> list[str]:
        """Off-path digest verification WHILE serving: fetch-and-hash on
        an executor thread holding the in-flight permit — serializing with
        live device schedules, the same discipline ``warm_shapes_live``
        follows — under the first-compile deadline so a wedged device
        abandons the verification instead of blocking the monitor forever.
        Returns the drifted leaf paths (empty = verified). The first call
        after boot/adopt takes the baseline instead (the tree was just
        placed from a known-good source)."""
        from arkflow_tpu.tpu.integrity import diff_digests

        self._ensure_sems()
        loop = asyncio.get_running_loop()
        async with self._inflight_sem:
            deadline = self.core.deadline_for(True)
            if deadline is None:
                digests = await loop.run_in_executor(None, self.digest_params)
            else:
                digests = await self.core.run_deadlined(
                    self.digest_params, deadline)
        if self.param_digests is None:
            self.param_digests = digests
            return []
        return diff_digests(self.param_digests, digests)

    # -- live shape retune surface (tpu/tuner.py) ---------------------------

    def grid_shapes(self, policy: BucketPolicy) -> list[dict[str, tuple]]:
        """Every padded-input shape signature ``policy`` can put on the
        device — the same reachable set ``warmup`` walks, but for an
        arbitrary (e.g. tuner-proposed) policy, without dispatching."""
        has_seq = any("seq" in t for _, t in self.spec.values())
        seqs = list(policy.seq_buckets) if has_seq else [None]
        if self.packed:
            pairs = [(pb, eb) for eb in policy.example_buckets()
                     for pb in policy.batch_buckets if pb <= eb]
        else:
            pairs = [(bb, bb) for bb in policy.batch_buckets]
        shapes: list[dict[str, tuple]] = []
        for pb, eb in pairs:
            for sl in seqs:
                shape: dict[str, tuple] = {}
                for name, (dtype, trailing) in self.spec.items():
                    lead = eb if self.packed and "seq" not in trailing else pb
                    dims = tuple(sl if d == "seq" else d for d in trailing)
                    shape[name] = (lead, *dims)
                shapes.append(shape)
        return shapes

    @staticmethod
    def _grid_shape_key(shape: dict[str, tuple]) -> tuple:
        # identical structure to _shape_key (name-sorted (name, shape)
        # pairs), so warm-marked shapes are exactly what _note_shape sees
        return tuple(sorted(shape.items()))

    def count_new_shapes(self, policy: BucketPolicy) -> int:
        """How many executables ``policy`` would still have to compile —
        the tuner's compile-cost gate reads this before proposing a flip."""
        shapes = self.grid_shapes(policy)
        with self._flash_lock:
            return sum(1 for s in shapes
                       if self._grid_shape_key(s) not in self._seen_shapes)

    def _compile_shape(self, shape: dict[str, tuple]) -> None:
        """Compile (and discard) one padded shape through the jitted step."""
        fake = {name: np.zeros(s, self.spec[name][0])
                for name, s in shape.items()}
        with cold_step(self._program):
            jax.device_get(self._dispatch(fake))

    def _mark_warmed(self, key: tuple) -> None:
        with self._flash_lock:
            if key not in self._seen_shapes:
                self._seen_shapes.add(key)
                self.m_warm_compiles.inc()

    def warm_shapes(self, policy: BucketPolicy) -> int:
        """Pre-compile every not-yet-seen shape of ``policy`` OFF the
        serving path (shape-tuner warm phase). Compiles go through the
        persistent XLA cache like any other, and each warmed shape is
        marked seen WITHOUT touching ``arkflow_tpu_compiles_total`` — so
        after the flip, live traffic on the new grid never compiles and
        the serving-path compile counter stays flat; warm compiles count in
        ``arkflow_tpu_warm_compiles_total`` instead. Blocking (XLA
        compiles) and un-deadlined: for use off live traffic (tests,
        tools); the tuner's cycle path uses :meth:`warm_shapes_live`."""
        count = 0
        for shape in self.grid_shapes(policy):
            key = self._grid_shape_key(shape)
            with self._flash_lock:
                if key in self._seen_shapes:
                    continue
            self._compile_shape(shape)
            self._mark_warmed(key)
            count += 1
        return count

    async def warm_shapes_live(self, policy: BucketPolicy) -> int:
        """``warm_shapes`` for use WHILE serving: each compile holds the
        in-flight permit — serializing with live device schedules — and runs
        under the first-compile deadline on a watchdog thread, so a wedged
        compile is abandoned (the runner heals through its normal probe
        path) instead of blocking the caller forever."""
        self._ensure_sems()
        loop = asyncio.get_running_loop()
        count = 0
        for shape in self.grid_shapes(policy):
            key = self._grid_shape_key(shape)
            with self._flash_lock:
                if key in self._seen_shapes:
                    continue
            async with self._inflight_sem:
                deadline = self.core.deadline_for(True)
                if deadline is None:
                    await loop.run_in_executor(
                        None, self._compile_shape, shape)
                else:
                    await self.core.run_deadlined(
                        partial(self._compile_shape, shape), deadline)
            self._mark_warmed(key)
            count += 1
        return count

    def retarget_buckets(self, policy: BucketPolicy) -> BucketPolicy:
        """Atomically flip the serving bucket grid (shape-tuner flip);
        returns the prior policy (the rollback token). In-flight steps
        already padded keep their old shapes — both grids are compiled, so
        the transition window serves both without a recompile."""
        with self._flash_lock:
            old, self.buckets = self.buckets, policy
        self.m_bucket_cap.set(policy.max_batch())
        return old

    def health_report(self) -> dict:
        """JSON-able health snapshot for the engine's ``/health`` endpoint."""
        rep = self.core.health_report()
        rep["model"] = self.family.name
        if self.device_label is not None:
            rep["device"] = self.device_label
        rep["bucket_cap"] = self.buckets.max_batch()
        return rep

    # -- execution ---------------------------------------------------------

    def infer_sync(self, inputs: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        """Blocking inference: pad -> device -> unpad.

        Batches larger than the biggest bucket are chunked and the outputs
        re-concatenated (upstream buffers may over-merge under backpressure).
        With ``step_deadline`` set the step runs on a watchdog thread and is
        abandoned on a miss; a device OOM caps the bucket grid and retries
        the batch split to the next-smaller bucket.
        """
        import time

        n_total = next(iter(inputs.values())).shape[0]
        mb = self.buckets.max_batch()
        if n_total > mb and not self.packed:
            # (packed layouts can't be sliced uniformly — row and example
            # dims differ; the packer pre-chunks, _pad_inputs_packed raises)
            chunks = [
                self.infer_sync({k: v[i : i + mb] for k, v in inputs.items()})
                for i in range(0, n_total, mb)
            ]
            return {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}

        self.core.heal_gate_sync()
        padded, n = self._prep(inputs)
        first = self._note_shape(padded)
        bucket_rows = next(iter(padded.values())).shape[0]
        deadline = self.core.deadline_for(first)
        step_blocking = (self._cold_step_blocking if first
                         else self._step_blocking)
        t0 = time.perf_counter()
        try:
            if deadline is None:
                out, _ = step_blocking(padded)
            else:
                out, _ = self.core.run_deadlined_sync(
                    partial(step_blocking, padded), deadline,
                    on_zombie=partial(self._release_staging, padded))
        except StepDeadlineExceeded:
            raise  # the zombie step still owns the staging buffers
        except Exception as e:
            # step ended (with an error) => the device consumed the inputs
            self._release_staging(padded)
            if is_oom_error(e):
                if not self.packed and self._note_oom(bucket_rows):
                    return self.infer_sync(inputs)  # re-chunk on the capped grid
                if self.packed:
                    # can't re-slice a packed layout here; cap the grid so the
                    # REDELIVERED batch repacks against servable buckets
                    self._note_oom(bucket_rows)
            raise
        # outputs fetched => the staging buffers are safe to recycle
        self._release_staging(padded)
        if not self._in_warmup:  # warmup compiles are not traffic latency
            dt = time.perf_counter() - t0
            self.m_infer.observe(dt)
            self.m_rows.inc(n)
        self.health.mark_success()
        return {k: np.asarray(v)[:n] for k, v in out.items()}

    def _prep(self, inputs: dict[str, np.ndarray]) -> tuple[dict[str, Any], int]:
        """Host-side stage: pad to buckets + validate masks (CPU only)."""
        prep = annotated("infeed_prep")
        try:
            with prep:
                return self._prep_inner(inputs)
        finally:
            if not self._in_warmup:
                self.m_prep.observe(prep.dur_s)

    def _prep_inner(self, inputs: dict[str, np.ndarray]) -> tuple[dict[str, Any], int]:
        padded, n = self._pad_inputs(inputs)
        if getattr(self.cfg, "use_flash_attention", False) and "attention_mask" in padded:
            # sub-floor buckets compile the XLA path (models gate on the
            # static seq), which serves arbitrary masks — don't fail or
            # globally disable flash over a batch the kernel never sees
            m = padded["attention_mask"]
            if m.shape[1] < (getattr(self.cfg, "flash_min_seq", None) or 0):
                return padded, n
            # the ragged kernel reads row sums as prefix lengths; a
            # non-contiguous mask (left padding) would silently mis-attend
            lengths = m.sum(axis=1)
            prefix = (np.arange(m.shape[1])[None, :] < lengths[:, None]).astype(m.dtype)
            if not np.array_equal(prefix, m):
                if self._flash_user_forced:
                    raise ConfigError(
                        "use_flash_attention requires right-padded attention "
                        "masks (contiguous prefix of ones)"
                    )
                # flash was an auto choice, not user config: serve the
                # batch via XLA attention instead of failing the stream
                logger.warning(
                    "[%s] non-right-padded attention mask: disabling auto "
                    "flash attention (XLA path; one recompile per bucket)",
                    self.family.name)
                self._disable_flash()
        return padded, n

    def _dispatch(self, padded: dict[str, Any]):
        """Non-blocking XLA dispatch (async device futures)."""
        if self.mesh is not None:
            with self.mesh:
                return self._jitted(self.params, padded)
        return self._jitted(self.params, padded)

    def _to_device(self, padded: dict[str, Any]) -> dict[str, Any]:
        """Eager host->device transfer of a prepped batch: runs on an
        executor thread BEFORE the in-flight semaphore, so batch n+1's
        infeed overlaps batch n's compute instead of paying the transfer
        inside its own device window. Under a mesh this is a SHARDED
        device_put — each chip receives only its dp shard of the batch dim
        (the dp-scaled buckets guarantee divisibility), and the dispatch
        then consumes already-placed arrays with zero re-layout. Waits for
        the copies so the subsequent dispatch never blocks on them."""
        target = self._input_sharding if self.mesh is not None else self._device
        dev = jax.device_put(padded, target)
        jax.block_until_ready(dev)
        return dev

    # -- in-flight accounting (duty cycle / infeed stall) -------------------

    def _track_dispatch(self, now: float) -> None:
        if self._inflight == 0:
            if self._last_idle_start is not None:
                gap = now - self._last_idle_start
                self.m_stall_s.inc(gap)
                self.m_idle_gap.observe(gap)
            self._busy_start = now
        self._inflight += 1
        self.m_inflight.set(self._inflight)

    def _track_complete(self, now: float) -> None:
        self._inflight -= 1
        self.m_inflight.set(self._inflight)
        if self._inflight == 0:
            self.m_busy_s.inc(now - self._busy_start)
            self._last_idle_start = now

    def duty_cycle(self) -> float:
        """Busy fraction since the first dispatch (1.0 = device never idle)."""
        busy, stall = self.m_busy_s.value, self.m_stall_s.value
        total = busy + stall
        return busy / total if total > 0 else 0.0

    def _ensure_sems(self) -> None:
        """(Re)bind the in-flight/prefetch/depth semaphores to the CURRENT
        loop."""
        loop = asyncio.get_running_loop()
        if self._sem_loop is not loop:
            self._inflight_sem = asyncio.Semaphore(self.max_in_flight)
            self._prefetch_sem = asyncio.Semaphore(self.max_in_flight + 1)
            # depth > 1: bounds DISPATCHED-NOT-FETCHED steps (each holds a
            # permit from before its enqueue until its outputs are fetched)
            # — without it, concurrent callers releasing the in-flight
            # permit at dispatch could queue arbitrarily many steps on the
            # device and defeat both backpressure and the staging-pool cap
            self._depth_sem = asyncio.Semaphore(self.dispatch_depth)
            self._sem_loop = loop

    async def infer(self, inputs: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
        """Pipelined inference: host prep off-loop, bounded async dispatch.

        Concurrent callers (the stream's processor workers) keep up to
        ``max_in_flight`` steps queued on the device, so step n+1's host
        prep + infeed overlaps step n's compute instead of serializing
        pad -> dispatch -> device_get per batch.
        """
        import time

        loop = asyncio.get_running_loop()
        n_total = next(iter(inputs.values())).shape[0]
        mb = self.buckets.max_batch()
        if n_total > mb and not self.packed:
            # concurrent chunks: the in-flight semaphore bounds device queue
            # depth, so chunk n+1 preps/dispatches while chunk n computes
            # (serial awaits would idle the device between chunks)
            chunks = await asyncio.gather(*[
                self.infer({k: v[i:i + mb] for k, v in inputs.items()})
                for i in range(0, n_total, mb)
            ])
            return {k: np.concatenate([c[k] for c in chunks]) for k in chunks[0]}
        await self.core.heal_gate()
        t_prep0 = time.perf_counter()
        padded, n = await loop.run_in_executor(None, self._prep, inputs)
        record_stage("infeed_prep", time.perf_counter() - t_prep0)
        first = self._note_shape(padded)
        bucket_rows = next(iter(padded.values())).shape[0]
        deadline = self.core.deadline_for(first)
        step_blocking = (self._cold_step_blocking if first
                         else self._step_blocking)
        staged = padded  # host staging buffers, recycled once the step ends

        self._ensure_sems()

        async def step(padded):
            t_sem = time.perf_counter()
            # first-seen shapes compile synchronously inside the dispatch;
            # they take the classic fully-watched path so the first-compile
            # deadline budget covers the compile, not just the fetch
            if self.dispatch_depth > 1 and not first:
                return await step_split(padded, t_sem)
            async with self._inflight_sem:
                t0 = time.perf_counter()
                if t0 - t_sem > 0.0005:
                    # waiting on the in-flight window is device queueing,
                    # not compute — its own stage so the breakdown shows it
                    record_stage("device_dispatch_wait", t0 - t_sem)
                self._track_dispatch(t0)
                try:
                    if deadline is None:
                        out, fetch_s = await loop.run_in_executor(
                            None, step_blocking, padded)
                    else:
                        # the shared core's watchdog: wait for the step, not
                        # forever, on a borrowed dedicated thread; on a miss
                        # the zombie's eventual end recycles the staging
                        # buffers (on_zombie)
                        out, fetch_s = await self.core.run_deadlined(
                            partial(step_blocking, padded), deadline,
                            on_zombie=partial(self._release_staging, staged))
                finally:
                    # an abandoned step counts as complete for duty-cycle
                    # accounting: the device is no longer doing useful work
                    self._track_complete(time.perf_counter())
                dt = time.perf_counter() - t0
                self.m_infer.observe(dt)
                # first-compile steps get their own stage: one compile can
                # be 1000x a warm step, and mixing the two makes both the
                # p99 and the share-of-e2e unreadable
                record_stage("device_step_first" if first else "device_step",
                             dt, attrs={"bucket_rows": bucket_rows})
                record_stage("device_fetch", fetch_s)
                return out

        async def step_split(padded, t_sem):
            # dispatch_depth > 1: the in-flight permit covers the DISPATCH
            # only — once the device queue holds the step, the permit frees
            # and the next worker's step dispatches while this one's output
            # fetch (device sync + host copy) proceeds off the critical
            # path. The outer DEPTH permit is held from before the enqueue
            # until the fetch completes, so dispatched-not-fetched steps
            # never exceed dispatch_depth no matter how many callers fan
            # out (chunked batches gather N concurrent infer calls) — that
            # is the device-memory backpressure AND the bound the staging
            # pool is sized against. Deadline semantics per in-flight step:
            # the fetch budget runs from this step's own enqueue, never
            # from when the host got around to waiting
            # (serving_core.deadline_remaining).
            async with self._depth_sem:
                async with self._inflight_sem:
                    t0 = time.perf_counter()
                    if t0 - t_sem > 0.0005:
                        record_stage("device_dispatch_wait", t0 - t_sem)
                    self._track_dispatch(t0)
                    try:
                        dev_out = await loop.run_in_executor(
                            None, self._enqueue_step, padded)
                    except BaseException:
                        self._track_complete(time.perf_counter())
                        raise
                    dispatched_at = time.monotonic()

                def fetch():
                    self.core.apply_chaos()
                    return self._wait_and_fetch(dev_out)

                try:
                    if deadline is None:
                        out, fetch_s = await loop.run_in_executor(None, fetch)
                    else:
                        out, fetch_s = await self.core.run_deadlined(
                            fetch,
                            self.core.deadline_remaining(
                                deadline, dispatched_at),
                            on_zombie=partial(self._release_staging, staged))
                finally:
                    self._track_complete(time.perf_counter())
            dt = time.perf_counter() - t0
            self.m_infer.observe(dt)
            record_stage("device_step_first" if first else "device_step",
                         dt, attrs={"bucket_rows": bucket_rows})
            record_stage("device_fetch", fetch_s)
            return out

        try:
            if self._prefetch:
                # eager infeed: batch n+1's host->device copies run here,
                # outside the in-flight semaphore, overlapping batch n's
                # compute (sharded per-chip copies under a mesh). The
                # prefetch semaphore (in_flight + 1 permits, held through
                # the step) caps how many padded batches can sit in device
                # memory ahead of the compute queue.
                async with self._prefetch_sem:
                    padded = await loop.run_in_executor(None, self._to_device, padded)
                    out = await step(padded)
            else:
                out = await step(padded)
        except StepDeadlineExceeded:
            staged = None  # the abandoned step still owns the buffers; the
            raise          # miss handler recycles them when it finally ends
        except Exception as e:
            if is_oom_error(e):
                if not self.packed and self._note_oom(bucket_rows):
                    # the finally below recycles the staging buffers (the
                    # step ended with an error, so nothing reads them)
                    return await self.infer(inputs)  # re-chunk on the capped grid
                if self.packed:
                    # can't re-slice a packed layout here; cap the grid so the
                    # REDELIVERED batch repacks against servable buckets
                    self._note_oom(bucket_rows)
            raise
        finally:
            # after device_get nothing can still read the host buffers —
            # even a CPU backend that aliased them zero-copy is done
            if staged is not None:
                self._release_staging(staged)
        self.m_rows.inc(n)
        self.health.mark_success()
        return {k: np.asarray(v)[:n] for k, v in out.items()}

    def warmup(self, seq_lens: Optional[list[int]] = None) -> int:
        """Precompile the bucket grid; returns number of executables built.

        Packed mode warms every reachable (row-bucket, example-bucket) pair:
        the row dim P lands in a smaller-or-equal bucket than the example dim
        E (each packed row holds >= 1 example), with E drawn from the
        extended example grid (``BucketPolicy.example_buckets``) — so the
        upper-triangular grid covers all shapes packed traffic can produce:
        full token-budget chunks (eb up to max_examples) and tail chunks
        alike. The persistent compile cache makes this a one-time cost per
        host.
        """
        count = 0
        has_seq = any("seq" in t for _, t in self.spec.values())
        seqs = seq_lens or (list(self.buckets.seq_buckets) if has_seq else [None])
        if self.packed:
            pairs = [(pb, eb) for eb in self.buckets.example_buckets()
                     for pb in self.buckets.batch_buckets if pb <= eb]
        else:
            pairs = [(bb, bb) for bb in self.buckets.batch_buckets]
        self._in_warmup = True
        try:
            for pb, eb in pairs:
                for sl in seqs:
                    fake = {}
                    for name, (dtype, trailing) in self.spec.items():
                        lead = eb if self.packed and "seq" not in trailing else pb
                        dims = tuple(sl if d == "seq" else d for d in trailing)
                        fake[name] = np.zeros((lead, *dims), dtype=dtype)
                    self.infer_sync(fake)
                    count += 1
        finally:
            self._in_warmup = False
        logger.info("[%s] warmed %d bucket executables", self.family.name, count)
        return count
