"""Engine configuration: single file, format by extension.

YAML / JSON / TOML parse into typed config objects (ref:
crates/arkflow-core/src/config.rs:87-107). Component configs stay as raw
``{"type": ..., **payload}`` mappings — the builder registry consumes them
(the serde-flatten equivalent, ref input/mod.rs:98-106).

Defaults mirror the reference: health server on ``0.0.0.0:8080``
(config.rs:26-172), pipeline ``thread_num`` = cpu count (pipeline/mod.rs:106).
"""

from __future__ import annotations

import json
import os

try:
    import tomllib
except ImportError:  # python < 3.11: the vendored fallback has the same API
    import tomli as tomllib
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Mapping, Optional

import yaml

from arkflow_tpu.errors import ConfigError


@dataclass
class PipelineConfig:
    thread_num: int = 0  # 0 -> cpu count
    processors: list[dict] = field(default_factory=list)
    #: >0 runs the chain in that many worker PROCESSES (GIL escape for
    #: Python-bound transforms; see runtime/procpool.py). 0 = in-process.
    process_pool: int = 0
    #: how many times a batch may be delivered (processed + written) before
    #: it is quarantined to error_output instead of redelivered. 1 keeps the
    #: quarantine-on-first-failure behavior; >1 lets transient processing
    #: failures heal through broker/nack redelivery.
    max_delivery_attempts: int = 1
    #: stage-queue depth between input/buffer and the workers; 0 keeps the
    #: historical ``thread_num * 4`` (ref stream/mod.rs:90-93)
    queue_size: int = 0
    #: per-batch latency budget in millis, measured from ingest time unless
    #: an absolute ``__meta_ext_deadline_ms`` column overrides it; setting
    #: it turns on deadline-aware admission (see runtime/overload.py)
    deadline_ms: Optional[float] = None
    #: default admission-priority band for batches without a
    #: ``__meta_ext_priority`` column
    priority: int = 0
    #: parsed ``pipeline.overload`` controller knobs (OverloadConfig), or
    #: None when overload control is fully disabled
    overload: Optional[object] = None

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "PipelineConfig":
        from arkflow_tpu.runtime.overload import OverloadConfig

        if not isinstance(m, Mapping):
            raise ConfigError("pipeline config must be a mapping")
        threads = m.get("thread_num", 0)
        if not isinstance(threads, int) or threads < 0:
            raise ConfigError(f"pipeline.thread_num must be a non-negative int, got {threads!r}")
        pool = m.get("process_pool", 0)
        if not isinstance(pool, int) or pool < 0:
            raise ConfigError(
                f"pipeline.process_pool must be a non-negative int, got {pool!r}")
        if m.get("ingest_shards", 0) != 0:
            # a removed key: from_mapping reads with .get, so without this a
            # left-over value would be ignored and the stream run unsharded
            raise ConfigError(
                "pipeline.ingest_shards is gone: a stream runs in one process. "
                "Move a CPU-bound chain off the GIL with pipeline.process_pool; "
                "put a device tier in other processes behind remote_tpu")
        procs = m.get("processors", [])
        if not isinstance(procs, list):
            raise ConfigError("pipeline.processors must be a list")
        attempts = m.get("max_delivery_attempts", 1)
        if not isinstance(attempts, int) or attempts < 1:
            raise ConfigError(
                f"pipeline.max_delivery_attempts must be an int >= 1, got {attempts!r}")
        qsize = m.get("queue_size", 0)
        if not isinstance(qsize, int) or isinstance(qsize, bool) or qsize < 0:
            raise ConfigError(
                f"pipeline.queue_size must be a non-negative int, got {qsize!r}")
        deadline = m.get("deadline_ms")
        if deadline is not None:
            if isinstance(deadline, bool) or not isinstance(deadline, (int, float)) \
                    or deadline <= 0:
                raise ConfigError(
                    f"pipeline.deadline_ms must be a positive number, got {deadline!r}")
            deadline = float(deadline)
        priority = m.get("priority", 0)
        if not isinstance(priority, int) or isinstance(priority, bool):
            raise ConfigError(f"pipeline.priority must be an int, got {priority!r}")
        overload = OverloadConfig.from_config(
            m.get("overload"), deadline_ms=deadline, priority=priority)
        return cls(thread_num=threads, processors=[dict(p) for p in procs],
                   process_pool=pool,
                   max_delivery_attempts=attempts,
                   queue_size=qsize, deadline_ms=deadline, priority=priority,
                   overload=overload)

    def effective_threads(self) -> int:
        return self.thread_num if self.thread_num > 0 else (os.cpu_count() or 1)

    def effective_queue_size(self) -> int:
        """Stage-queue depth: configured ``queue_size`` or the historical
        ``thread_num * 4`` default."""
        return self.queue_size if self.queue_size > 0 else self.effective_threads() * 4


@dataclass
class TemporaryConfig:
    name: str
    config: dict

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "TemporaryConfig":
        m = dict(m)
        name = m.pop("name", None)
        if not name:
            raise ConfigError("temporary config requires a 'name'")
        return cls(name=name, config=m)


@dataclass
class StreamConfig:
    input: dict
    pipeline: PipelineConfig
    output: dict
    error_output: Optional[dict] = None
    buffer: Optional[dict] = None
    temporary: list[TemporaryConfig] = field(default_factory=list)
    name: Optional[str] = None
    #: crash policy: {max_retries: N, backoff: "5s", reset_after: "5m"}
    #: rebuilds and restarts a crashed stream (the reference only logs,
    #: ref engine/mod.rs:268-273); a run longer than reset_after restores
    #: the full retry budget; None keeps log-and-stop behavior
    restart: Optional[dict] = None
    #: delivery-path retry for output.write (from ``output.retry``; the key
    #: also stays visible to connector builders that use it for connect-time
    #: retries, e.g. pulsar). None -> RetryConfig defaults.
    output_retry: Optional[object] = None
    #: circuit breaker over output.write (from ``output.circuit_breaker``);
    #: None -> disabled
    output_circuit_breaker: Optional[object] = None
    error_output_retry: Optional[object] = None
    error_output_circuit_breaker: Optional[object] = None
    #: capped-exponential reconnect schedule after input Disconnection (from
    #: ``input.reconnect``); None -> stream defaults (100ms doubling to 5s)
    input_reconnect: Optional[object] = None

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "StreamConfig":
        from arkflow_tpu.utils.circuit_breaker import CircuitBreakerConfig
        from arkflow_tpu.utils.retry import RetryConfig

        if not isinstance(m, Mapping):
            raise ConfigError("stream config must be a mapping")
        for req in ("input", "output"):
            if req not in m:
                raise ConfigError(f"stream config missing required section {req!r}")
        pipeline = PipelineConfig.from_mapping(m.get("pipeline", {}))
        _validate_token_coalesce(m.get("buffer"), pipeline.processors)
        _validate_response_cache(pipeline.processors)
        _validate_generate_mesh(pipeline.processors)
        _validate_inference_mesh(pipeline.processors)
        _validate_dispatch_knobs(pipeline.processors)
        _validate_swap(pipeline.processors)
        _validate_tuner(pipeline.processors)
        _validate_integrity(pipeline.processors)
        _validate_remote_tpu(pipeline.processors)
        temps = [TemporaryConfig.from_mapping(t) for t in m.get("temporary", [])]
        input_cfg = dict(m["input"])
        reconnect = input_cfg.pop("reconnect", None)
        output_cfg = dict(m["output"])
        out_breaker = CircuitBreakerConfig.from_config(output_cfg.pop("circuit_breaker", None))
        out_retry = RetryConfig.from_config(output_cfg["retry"]) if output_cfg.get("retry") else None
        err_cfg = dict(m["error_output"]) if m.get("error_output") else None
        err_breaker = err_retry = None
        if err_cfg is not None:
            err_breaker = CircuitBreakerConfig.from_config(err_cfg.pop("circuit_breaker", None))
            err_retry = RetryConfig.from_config(err_cfg["retry"]) if err_cfg.get("retry") else None
        return cls(
            input=input_cfg,
            pipeline=pipeline,
            output=output_cfg,
            error_output=err_cfg,
            buffer=dict(m["buffer"]) if m.get("buffer") else None,
            temporary=temps,
            name=m.get("name"),
            restart=_restart_config(m.get("restart")),
            output_retry=out_retry,
            output_circuit_breaker=out_breaker,
            error_output_retry=err_retry,
            error_output_circuit_breaker=err_breaker,
            input_reconnect=RetryConfig.from_config(reconnect) if reconnect else None,
        )


def _validate_token_coalesce(buffer_cfg: Any, processors: list[dict]) -> None:
    """Cross-component sanity for the packed fast path: a buffer carving
    token-budget emissions only makes sense feeding a packing-enabled
    ``tpu_inference`` processor (token-sized emissions fill a compiled
    (rows, seq) shape only AFTER pack_tokens; an unpacked runner would pad
    their oversized row counts straight back). Caught at parse time with a
    clear message — the component builders can't see across sections."""
    packing_vals = []
    for p in processors:
        # chaos streams wrap the real processor: look through `fault.inner`
        # so the cross-check still sees the tpu_inference config
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping) or p.get("type") != "tpu_inference":
            continue
        packing = p.get("packing", False)
        if not isinstance(packing, bool):
            raise ConfigError(
                f"tpu_inference.packing must be a bool, got {packing!r}")
        packing_vals.append(packing)
    if not isinstance(buffer_cfg, Mapping):
        return
    coalesce = buffer_cfg.get("coalesce")
    if not isinstance(coalesce, Mapping):
        return
    token_budget = coalesce.get("token_budget")
    if token_budget is None:
        return
    if isinstance(token_budget, bool) or not isinstance(token_budget, int) \
            or token_budget < 1:
        raise ConfigError(
            f"buffer.coalesce.token_budget must be a positive int, "
            f"got {token_budget!r}")
    if packing_vals and not any(packing_vals):
        raise ConfigError(
            "buffer.coalesce.token_budget requires 'packing: true' on the "
            "stream's tpu_inference processor (token-budget emissions only "
            "fill the compiled (rows, seq) shape after pack_tokens packing; "
            "set packing: true or drop token_budget)")


def _validate_response_cache(processors: list[dict]) -> None:
    """Parse-time validation of ``tpu_inference.response_cache`` knobs, so a
    bad cache config fails at ``--validate`` instead of at stream build —
    looking through ``fault.inner`` chaos wrappers like the coalesce check.
    The actual construction happens in the processor builder
    (runtime/respcache.py ``build_response_cache``); this shares its parse
    rules without instantiating a cache (or its metric series) per pass."""
    from arkflow_tpu.runtime.respcache import parse_response_cache_config

    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping) or p.get("type") != "tpu_inference":
            continue
        if p.get("response_cache") is not None:
            parse_response_cache_config(p["response_cache"])


def _validate_swap(processors: list[dict]) -> None:
    """Parse-time validation of the ``swap:`` hot-swap block on
    ``tpu_inference``/``tpu_generate`` (tpu/swap.py owns the parse rules; it
    imports no jax), looking through ``fault.inner`` chaos wrappers like the
    other cross-checks — a bad canary/drain knob fails at ``--validate``
    instead of at the first POST /admin/swap."""
    from arkflow_tpu.tpu.swap import parse_swap_config

    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping):
            continue
        ptype = p.get("type")
        if ptype in ("tpu_inference", "tpu_generate") and p.get("swap") is not None:
            parse_swap_config(p["swap"], who=str(ptype))


def _validate_integrity(processors: list[dict]) -> None:
    """Parse-time validation of the ``integrity:`` silent-data-corruption
    block on ``tpu_inference``/``tpu_generate`` (tpu/integrity.py owns the
    parse rules; it imports no jax), looking through ``fault.inner`` chaos
    wrappers like the other cross-checks — a bad probe cadence fails at
    ``--validate`` instead of at stream build."""
    from arkflow_tpu.tpu.integrity import parse_integrity_config

    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping):
            continue
        kind = p.get("type")
        if kind in ("tpu_inference", "tpu_generate") \
                and p.get("integrity") is not None:
            parse_integrity_config(p["integrity"], who=kind)
            if kind == "tpu_generate" \
                    and p.get("serving", "batch") != "continuous":
                raise ConfigError(
                    "tpu_generate: integrity requires serving: continuous "
                    "(batch mode holds no resident serving member to probe)")


def _validate_tuner(processors: list[dict]) -> None:
    """Parse-time validation of the ``tuner:`` traffic-adaptive-shapes block
    on ``tpu_inference`` (tpu/tuner.py owns the parse rules; it imports no
    jax), looking through ``fault.inner`` chaos wrappers like the other
    cross-checks — a bad interval/margin knob fails at ``--validate``
    instead of at stream build."""
    from arkflow_tpu.tpu.tuner import parse_tuner_config

    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping) or p.get("type") != "tpu_inference":
            continue
        if p.get("tuner") is None:
            continue
        parse_tuner_config(p["tuner"], who="tpu_inference")


def _validate_remote_tpu(processors: list[dict]) -> None:
    """Parse-time validation of the ``remote_tpu`` cluster-dispatch stage
    (runtime/cluster.py owns the parse rules; it imports no jax), looking
    through ``fault.inner`` chaos wrappers like the other cross-checks — a
    bad worker URL, routing knob, ``decode_candidates``, or one-sided
    ``fleet.roles`` split (prefill capacity with no decode capacity, or
    vice versa) fails at ``--validate`` instead of at stream connect."""
    from arkflow_tpu.runtime.cluster import parse_remote_tpu_config

    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if isinstance(p, Mapping) and p.get("type") == "remote_tpu":
            parse_remote_tpu_config(p)


#: decoder_lm's DecoderConfig default — mirrored here (not imported) so mesh
#: validation at parse time never drags jax into `--validate`
_DECODER_LM_DEFAULT_KV_HEADS = 4

#: what selected pipelined-segmentation serving on ``tpu_inference`` beside
#: ``mesh.pp``: a config that still carries one is refused, not served as
#: something else
_PP_SERVING_KEYS = ("pp_microbatch_rows", "pp_layer_costs", "pp_profile")

PP_SERVING_REMOVED = (
    "tpu_inference: pipelined-segmentation serving (mesh pp > 1, "
    + ", ".join(_PP_SERVING_KEYS) + ") was removed in PR 45; serve several "
    "chips with mesh: {dp: N} or device_pool: N (mesh pp remains tpu_train's)")


def refuse_pp_serving(p: Mapping) -> None:
    """The one refusal of the removed mode, for ``--validate`` and the
    processor's build alike."""
    mesh = p.get("mesh")
    pp = mesh.get("pp", 1) if isinstance(mesh, Mapping) else 1
    if (isinstance(pp, int) and pp > 1) or any(k in p for k in _PP_SERVING_KEYS):
        raise ConfigError(PP_SERVING_REMOVED)


def _validate_inference_mesh(processors: list[dict]) -> None:
    """Parse-time checks for multi-chip ``tpu_inference`` serving, looking
    through ``fault.inner`` chaos wrappers like the other cross-checks: mesh
    axis values must be positive ints, and the removed pipelined mode is
    refused by name."""
    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping) or p.get("type") != "tpu_inference":
            continue
        mesh = p.get("mesh")
        if mesh is not None and not isinstance(mesh, Mapping):
            raise ConfigError(
                f"tpu_inference.mesh must be a mapping, got {mesh!r}")
        for k in ("dp", "tp", "sp", "pp"):
            v = (mesh or {}).get(k, 1)
            if isinstance(v, bool) or not isinstance(v, int) or v < 1:
                raise ConfigError(
                    f"tpu_inference.mesh.{k} must be a positive int, got {v!r}")
        refuse_pp_serving(p)


def refuse_continuous_split(axes: Mapping) -> None:
    """``serving: continuous`` shards TENSOR-PARALLEL only: refuse a mesh
    whose ``dp`` or ``sp`` is above 1. The one place that says so — here for
    ``--validate`` (no jax), and called by the processor before its host
    init and by the server for a mesh it is handed."""
    for axis in ("dp", "sp"):
        if int(axes.get(axis, 1)) > 1:
            raise ConfigError(
                f"tpu_generate: serving: continuous + mesh {axis} > 1 is "
                "unsupported — continuous serving shards tensor-parallel "
                "only: the lockstep slot grid does not batch-split; shard tp "
                "(mesh: {tp: N}) or use serving: batch / tpu_inference for dp")


def _validate_generate_mesh(processors: list[dict]) -> None:
    """Parse-time checks for multi-chip ``tpu_generate`` serving, looking
    through ``fault.inner`` chaos wrappers like the other cross-checks:

    - mesh axis values must be positive ints;
    - ``serving: continuous`` shards TENSOR-PARALLEL only — the lockstep
      slot grid does not batch-split, so ``dp``/``sp`` > 1 fail here with a
      clear message instead of a shape error at stream build;
    - ``tp`` must divide the model's KV head count (the page pools shard
      over KV heads on the tp axis).
    """
    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping) or p.get("type") != "tpu_generate":
            continue
        mesh = p.get("mesh")
        if mesh is None:
            continue
        if not isinstance(mesh, Mapping):
            raise ConfigError(
                f"tpu_generate.mesh must be a mapping, got {mesh!r}")
        axes: dict[str, int] = {}
        for k in ("dp", "tp", "sp"):
            v = mesh.get(k, 1)
            if isinstance(v, bool) or not isinstance(v, int) or v < 1:
                raise ConfigError(
                    f"tpu_generate.mesh.{k} must be a positive int, got {v!r}")
            axes[k] = v
        if str(p.get("serving", "batch")) != "continuous":
            continue
        refuse_continuous_split(axes)
        tp = axes["tp"]
        if tp > 1:
            mc = p.get("model_config")
            kv_heads = (mc.get("kv_heads") if isinstance(mc, Mapping) else None)
            if kv_heads is None and p.get("model", "decoder_lm") == "decoder_lm":
                kv_heads = _DECODER_LM_DEFAULT_KV_HEADS
            if (isinstance(kv_heads, int) and not isinstance(kv_heads, bool)
                    and kv_heads % tp != 0):
                raise ConfigError(
                    f"tpu_generate: mesh tp={tp} must divide the model's "
                    f"kv_heads={kv_heads} (KV pages shard over heads on the "
                    "tp axis)")


def _validate_dispatch_knobs(processors: list[dict]) -> None:
    """Parse-time checks for the hot-path perf knobs (PR 13), looking
    through ``fault.inner`` chaos wrappers like the other cross-checks:

    - ``tpu_inference.dispatch_depth`` / ``tpu_generate.dispatch_depth``
      must be positive ints; the generate path caps at 2 (the serve loop
      keeps one step ahead of the device, never more). What depth 2 does
      not compose with — sampling, speculative decoding, a block or state
      on which a lane riding one step too long is not exact — is no error:
      such a server runs in lockstep (``GenerationServer._ahead``);
    - ``tpu_generate.decode_kernel`` must name a known kernel.
    """
    for p in processors:
        while (isinstance(p, Mapping) and p.get("type") == "fault"
               and isinstance(p.get("inner"), Mapping)):
            p = p["inner"]
        if not isinstance(p, Mapping):
            continue
        ptype = p.get("type")
        if ptype not in ("tpu_inference", "tpu_generate"):
            continue
        depth = p.get("dispatch_depth")
        if depth is not None:
            if isinstance(depth, bool) or not isinstance(depth, int) or depth < 1:
                raise ConfigError(
                    f"{ptype}.dispatch_depth must be a positive int, "
                    f"got {depth!r}")
        if ptype != "tpu_generate":
            continue
        kernel = p.get("decode_kernel")
        if kernel is not None and kernel not in ("auto", "gather", "paged"):
            raise ConfigError(
                f"tpu_generate.decode_kernel must be auto|gather|paged, "
                f"got {kernel!r}")
        if depth is not None and depth > 2:
            raise ConfigError(
                "tpu_generate.dispatch_depth caps at 2: the serve loop keeps "
                "one step ahead of the device, never more")


def _restart_config(m: Any) -> Optional[dict]:
    if m is None or m is False:
        return None  # `restart: {}` means "defaults", not "disabled"
    if not isinstance(m, Mapping):
        raise ConfigError("stream 'restart' must be a mapping")
    from arkflow_tpu.utils.duration import parse_duration

    try:
        out = {
            "max_retries": int(m.get("max_retries", 3)),
            "backoff_s": parse_duration(str(m.get("backoff", "5s"))),
            # a run at least this long resets the retry budget (supervisor
            # convention: occasional crashes over days shouldn't accumulate)
            "reset_after_s": parse_duration(str(m.get("reset_after", "5m"))),
        }
    except (TypeError, ValueError) as e:
        raise ConfigError(f"stream 'restart' values invalid: {e}") from e
    if out["max_retries"] < 0 or out["backoff_s"] < 0 or out["reset_after_s"] < 0:
        raise ConfigError("stream restart values must be non-negative")
    return out


@dataclass
class HealthCheckConfig:
    enabled: bool = True
    host: str = "0.0.0.0"
    port: int = 8080
    path: str = "/health"
    #: opt-in: directory for POST /debug/profile JAX traces (endpoint is
    #: absent when unset — it adds device overhead and writes to disk)
    profiling_dir: Optional[str] = None

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "HealthCheckConfig":
        c = cls()
        c.enabled = bool(m.get("enabled", True))
        c.host = str(m.get("host", c.host))
        c.port = int(m.get("port", c.port))
        c.path = str(m.get("path", c.path))
        c.profiling_dir = m.get("profiling_dir")
        return c


@dataclass
class LoggingConfig:
    level: str = "info"
    file_path: Optional[str] = None
    format: str = "plain"  # plain | json

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "LoggingConfig":
        c = cls()
        c.level = str(m.get("level", c.level)).lower()
        c.file_path = m.get("file_path") or m.get("file")
        c.format = str(m.get("format", c.format)).lower()
        if c.format not in ("plain", "json"):
            raise ConfigError(f"logging.format must be plain|json, got {c.format!r}")
        return c


@dataclass
class EngineConfig:
    streams: list[StreamConfig]
    health_check: HealthCheckConfig = field(default_factory=HealthCheckConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    #: per-batch tracing knobs (obs/trace.py TracingConfig): head-sampling
    #: rate + retention bounds for the /trace endpoint; always-on by
    #: default — the engine applies it to the process-global tracer
    tracing: Optional[object] = None

    @classmethod
    def from_mapping(cls, m: Mapping[str, Any]) -> "EngineConfig":
        from arkflow_tpu.obs.trace import TracingConfig

        if not isinstance(m, Mapping):
            raise ConfigError("engine config must be a mapping")
        raw_streams = m.get("streams")
        if not raw_streams or not isinstance(raw_streams, list):
            raise ConfigError("engine config requires a non-empty 'streams' list")
        streams = [StreamConfig.from_mapping(s) for s in raw_streams]
        health = HealthCheckConfig.from_mapping(m.get("health_check", {}) or {})
        logging_ = LoggingConfig.from_mapping(m.get("logging", {}) or {})
        tracing = TracingConfig.from_mapping(m.get("tracing"))
        return cls(streams=streams, health_check=health, logging=logging_,
                   tracing=tracing)

    def validate_components(self) -> list[str]:
        """Check every component's ``type`` tag resolves against the
        registries (goes beyond the reference's parse-only ``--validate``).
        Returns human-readable problems; empty = OK."""
        from arkflow_tpu.components.registry import ensure_plugins_loaded, registered_types

        ensure_plugins_loaded()
        problems: list[str] = []
        for i, s in enumerate(self.streams):
            for family, c in (
                ("input", s.input),
                ("output", s.output),
                *((("output", s.error_output),) if s.error_output else ()),
                *((("buffer", s.buffer),) if s.buffer else ()),
                *((("processor", p) for p in s.pipeline.processors)),
                *((("temporary", t.config) for t in s.temporary)),
            ):
                t = c.get("type")
                if t not in registered_types(family):
                    problems.append(f"stream[{i}]: unknown {family} type {t!r}")
        return problems

    @classmethod
    def from_file(cls, path: str | Path) -> "EngineConfig":
        p = Path(path)
        if not p.exists():
            raise ConfigError(f"config file not found: {p}")
        suffix = p.suffix.lower()
        text = p.read_text()
        try:
            if suffix in (".yaml", ".yml"):
                data = yaml.safe_load(text)
            elif suffix == ".json":
                data = json.loads(text)
            elif suffix == ".toml":
                data = tomllib.loads(text)
            else:
                raise ConfigError(f"unsupported config extension {suffix!r} (use .yaml/.json/.toml)")
        except (yaml.YAMLError, json.JSONDecodeError, tomllib.TOMLDecodeError) as e:
            raise ConfigError(f"failed to parse {p}: {e}") from e
        return cls.from_mapping(data or {})
