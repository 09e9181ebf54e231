"""``tpu_inference`` processor: streaming ML inference on XLA.

The reference's ML story is "run user Python under the GIL"
(ref: crates/arkflow-plugin/src/processor/python.rs); this processor replaces
that slot with a first-class model-execution provider (BASELINE.json north
star): resolve a model family from config, bucket/pad the in-flight batch,
execute the compiled model, and attach outputs as Arrow columns.

Input extraction is driven by the family's ``input_spec``:
- token models (``("seq",)`` inputs): tokenize ``text_field`` (default the raw
  ``__value__`` payload) with an HF fast tokenizer or the hermetic hashing
  fallback;
- fixed-shape float inputs: read ``tensor_field`` (an Arrow list column,
  reshaped) or decode raw bytes (images) from a binary column.

Config:

    type: tpu_inference
    model: bert_classifier
    model_config: {num_labels: 2}
    text_field: __value__          # token models
    tokenizer: bert-base-uncased   # optional (falls back to hashing)
    max_seq: 128
    tensor_field: window           # list/binary column for tensor models
    outputs: [label, score]        # default: all rank-1 outputs
    batch_buckets: [8, 32, 128]    # default pow2 grid
    seq_buckets: [32, 64, 128]     # a batch of token rows is split by length
                                   # across this (rows, seq) grid before the
                                   # step (tpu/bucketing.py carve_by_length):
                                   # short rows are not padded to the longest
    mesh: {dp: 1, tp: 4}           # optional multi-chip serving (GSPMD: one
                                   # sharded program; dp splits the batch dim
                                   # and scales every batch bucket by dp)
    device_pool: 4                 # ALTERNATIVE multi-chip serving: 4
                                   # independent single-device runners with
                                   # replicated params behind a least-loaded
                                   # dispatcher — no collectives, best for
                                   # small-bucket / latency-bound traffic
                                   # (mutually exclusive with mesh)
    checkpoint: /path/to/orbax     # optional
    warmup: false                  # precompile bucket grid at connect
    serving_dtype: bfloat16        # float32 | bfloat16 | float16 | int8
                                   # (int8 = dynamic W8A8, 2x MXU roofline)
    dispatch_depth: 2              # 2 = release the in-flight permit at
                                   # DISPATCH: the next step's infeed and
                                   # dispatch overlap this step's compute
                                   # while the output fetch runs off the
                                   # device's critical path (default 1;
                                   # env ARKFLOW_DISPATCH_DEPTH)
    packing: true                  # token packing (tpu/packing.py): bin-pack
                                   # short examples into dense model rows so
                                   # flops/row tracks real token count; the
                                   # batch packs ONCE and is carved into
                                   # row windows that fill the compiled grid
    example_scale: 4               # packed only: the example-dim bucket grid
                                   # extends this far past the row grid
                                   # (default 4 with packing; a full row
                                   # bucket of short texts holds several
                                   # examples per row)
    response_cache:                # exact-match dedup cache in front of the
      capacity: 1024               # device (runtime/respcache.py): keyed on
      ttl: 30s                     # batch_fingerprint, LRU + TTL bounded,
                                   # N concurrent duplicate deliveries
                                   # collapse onto ONE device step and hits
                                   # return bitwise-identical responses —
                                   # retry storms stop costing TPU dispatches
    step_deadline: 2s              # self-healing: per-step watchdog — a step
                                   # exceeding it is abandoned, the runner
                                   # goes UNHEALTHY (recovery probes re-admit
                                   # it) and the batch nacks for redelivery
    step_deadline_first: 60s       # budget for first-compile steps
                                   # (default: 10x step_deadline)
    health:                        # recovery-probe schedule (tpu/health.py)
      probe_backoff: 500ms         # first probe delay; doubles per incident
      probe_backoff_cap: 30s
      dead_after: 8                # consecutive incidents -> DEAD (0: never)
    swap:                          # live hot-swap knobs (tpu/swap.py; the
      canary:                      # manager itself is always on — POST
        rows: 4                    # /admin/swap works without this block):
        min_agreement: 1.0         # golden-batch rows + required argmax
      drain_timeout: 30s           # agreement; drain budget is generate-only
    tuner:                         # traffic-adaptive shapes (tpu/tuner.py):
      interval: 30s                # observe live token lengths, propose
      min_improvement: 0.02        # quantile-aligned seq edges + token
      target_fill: 0.97            # budget + deadline + example_scale, warm
      max_compiles: 64             # every new shape off-path, then flip with
                                   # a health-gated probe + rollback. A
                                   # proposal must beat the incumbent's
                                   # predicted waste by min_improvement
                                   # (hysteresis — no flapping); POST
                                   # /admin/tune forces a cycle
    integrity:                     # silent-data-corruption defense
      probe_interval: 10s          # (tpu/integrity.py): a tie-free golden
      digest_every: 3              # batch probes every member per interval
      golden: {rows: 2, seed: 42}  # (argmax vs a host-computed reference);
      repair: true                 # every Nth tick re-verifies per-leaf
                                   # param digests off-path. A mismatch
                                   # quarantines the member (CORRUPT) and
                                   # repairs it from the retained host tree
"""

from __future__ import annotations

import asyncio
from typing import TYPE_CHECKING, Optional

import numpy as np
import pyarrow as pa

from arkflow_tpu.batch import DEFAULT_BINARY_VALUE_FIELD, MessageBatch
from arkflow_tpu.components import Processor, Resource, register_processor
from arkflow_tpu.config import refuse_pp_serving
from arkflow_tpu.errors import ConfigError, ProcessError
from arkflow_tpu.obs.startup import setup_stage
from arkflow_tpu.tpu.bucketing import BucketPolicy, carve_by_length
from arkflow_tpu.tpu.tokenizer import build_tokenizer

if TYPE_CHECKING:  # jax-importing modules load lazily in the builder
    from arkflow_tpu.tpu.runner import ModelRunner


class TpuInferenceProcessor(Processor):
    def __init__(self, runner: ModelRunner, *, text_field: str, tensor_field: Optional[str],
                 tokenizer, max_seq: int, outputs: Optional[list[str]], warmup: bool = False,
                 packing: bool = False, response_cache=None, swapper=None,
                 tuner=None, integrity=None):
        self.runner = runner
        #: silent-data-corruption defense (tpu/integrity.py): periodic param
        #: digests + golden probes with quarantine-and-repair; None = off
        #: (opt-in via the ``integrity:`` block). The engine's /health reads
        #: its report here.
        self.integrity = integrity
        #: live hot-swap manager (tpu/swap.py): the engine's POST /admin/swap
        #: and the fault plugin's swap_corrupt/swap_crash arming reach it here
        self.swapper = swapper
        #: traffic-adaptive shape tuner (tpu/tuner.py): observes every
        #: batch's token lengths, and the engine's POST /admin/tune +
        #: /health reach it here; None = static shapes (the old behavior)
        self.tuner = tuner
        self.text_field = text_field
        self.tensor_field = tensor_field
        self.tokenizer = tokenizer
        self.max_seq = max_seq
        self.outputs = outputs
        self._warmed = not warmup
        self.packing = packing
        #: exact-match dedup cache (runtime/respcache.py); None = every
        #: batch pays a device step, the pre-cache behavior
        self.cache = response_cache
        from arkflow_tpu.obs import global_registry

        # extraction/tokenization is the other half of host infeed prep
        # (the runner's own histogram covers pad/stage); bench sums the two
        self.m_extract = global_registry().histogram(
            "arkflow_tpu_extract_seconds",
            "host-side Arrow->tensor extraction + tokenization per batch",
            {"model": runner.family.name})
        # the length split's counter: how many (rows, seq) steps one batch
        # was carved into (1 = not split); tensor and packed batches never
        # enter the carve and observe nothing
        self.m_steps = global_registry().histogram(
            "arkflow_tpu_steps_per_batch",
            "device steps one batch's rows were split into by token length",
            {"model": runner.family.name}, buckets=(1, 2, 4, 8, 16, 32, 64))

    def attach_overload_controller(self, controller) -> None:
        """Stream hook (runtime/overload.attach_overload): hand the tenant
        policy to the response cache so its tenant-hit labels cap with the
        same reserved set / bound as the admission controller, and the
        controller itself to the tuner (its step EWMA + AIMD window join
        the workload sketch's report)."""
        if self.cache is not None:
            self.cache.set_tenant_policy(controller.cfg.tenants)
        if self.tuner is not None:
            self.tuner.attach_overload_controller(controller)

    # -- input extraction --------------------------------------------------

    def _encode_texts(self, batch: MessageBatch, max_len: int) -> tuple[np.ndarray, np.ndarray]:
        """Tokenize the payload column, preferring the zero-copy buffer view
        (no per-row bytes materialization) over ``to_binary``'s list path."""
        from arkflow_tpu.errors import ArkError

        col = batch.column(self.text_field)
        if col.null_count == 0 and hasattr(self.tokenizer, "encode_batch_view"):
            try:
                values, offsets = batch.payload_view(self.text_field)
            except ArkError:
                pass  # non-varlen payload column: the list path raises clearly
            else:
                return self.tokenizer.encode_batch_view(values, offsets, max_len)
        return self.tokenizer.encode_batch(batch.to_binary(self.text_field), max_len)

    def _extract(self, batch: MessageBatch
                 ) -> tuple[dict[str, np.ndarray], Optional[np.ndarray]]:
        """The model's inputs for ``batch`` and, for token models, every
        row's token length (None for tensor rows). Token arrays come back
        ``max_seq`` wide: ``_infer`` cuts them to the seq bucket(s) the
        lengths call for."""
        inputs: dict[str, np.ndarray] = {}
        spec = self.runner.spec
        needs_tokens = any(t == ("seq",) for _, t in spec.values()) and "input_ids" in spec
        if needs_tokens:
            ids, mask = self._encode_texts(batch, self.max_seq)
            lengths = mask.sum(axis=1)
            if self.tuner is not None:
                # the tuner's workload sketch: true tokenized lengths, one
                # O(rows) ring insert — the observe half of the loop
                self.tuner.observe(lengths)
            inputs["input_ids"] = ids
            if "attention_mask" in spec:
                inputs["attention_mask"] = mask
            return inputs, lengths
        for name, (dtype, trailing) in spec.items():
            inputs[name] = self._extract_tensor(batch, name, dtype, trailing)
        return inputs, None

    def _extract_tensor(self, batch: MessageBatch, name: str, dtype: str, trailing: tuple) -> np.ndarray:
        from arkflow_tpu.tpu.extract import extract_tensor

        return extract_tensor(batch, self.tensor_field or name, name, dtype,
                              trailing, who="tpu_inference")

    # -- output attachment -------------------------------------------------

    def _attach(self, batch: MessageBatch, outputs: dict[str, np.ndarray]) -> MessageBatch:
        names = self.outputs or [k for k, v in outputs.items() if np.asarray(v).ndim == 1]
        out = batch
        for name in names:
            if name not in outputs:
                raise ProcessError(
                    f"tpu_inference: model produced {sorted(outputs)}, no output {name!r}"
                )
            v = np.asarray(outputs[name])
            if v.ndim == 1:
                out = out.with_column(name, pa.array(v))
            elif v.ndim == 2:
                flat = pa.array(v.reshape(-1))
                out = out.with_column(name, pa.FixedSizeListArray.from_arrays(flat, v.shape[1]))
            else:
                raise ProcessError(f"tpu_inference: cannot attach rank-{v.ndim} output {name!r}")
        return out

    # -- Processor ---------------------------------------------------------

    async def connect(self) -> None:
        """Precompile the bucket grid before the input starts producing, so
        no in-flight batch ever waits behind a compile."""
        if not self._warmed:
            self._warmed = True
            await asyncio.get_running_loop().run_in_executor(None, self.runner.warmup)
        if self.tuner is not None:
            self.tuner.start()
        if self.integrity is not None:
            self.integrity.start()

    async def close(self) -> None:
        if self.tuner is not None:
            await self.tuner.stop()
        if self.integrity is not None:
            await self.integrity.stop()

    async def process(self, batch: MessageBatch) -> list[MessageBatch]:
        if batch.num_rows == 0:
            return []
        if not self._warmed:  # direct use without a stream (tests, tools)
            await self.connect()
        if self.cache is not None:
            from arkflow_tpu.batch import batch_fingerprint

            # the shared stable identity: redeliveries and byte-identical
            # retries hash equal (ingest time / ext metadata excluded), so
            # a duplicate storm costs one fingerprint hash, zero dispatches
            key = batch_fingerprint(batch)
            outputs = await self.cache.get_or_compute(
                key, lambda: self._infer(batch), tenant=batch.tenant())
        else:
            outputs = await self._infer(batch)
        return [self._attach(batch, outputs)]

    async def _infer(self, batch: MessageBatch) -> dict[str, np.ndarray]:
        """One un-cached inference: extract -> device step(s).

        Token rows are carved by length across the declared (rows, seq) grid
        (``bucketing.carve_by_length``) so short rows are not padded to the
        batch's longest: the pieces serve concurrently, like the packed
        path's windows (the runner's in-flight semaphore pipelines them),
        and their outputs scatter back into the batch's row order. A batch
        whose rows share a seq bucket is one piece — the one step it always
        was; one failed piece fails the batch."""
        from arkflow_tpu.obs.trace import record_stage

        if self.packing:
            return await self._infer_packed(batch)
        import time as _time

        t0 = _time.perf_counter()
        with self.m_extract.time():
            inputs, lengths = self._extract(batch)
        # extraction/tokenization is infeed prep too — same stage name as
        # the runner's pad/stage span, so the breakdown shows ONE infeed
        # cost (the two sites sum)
        record_stage("infeed_prep", _time.perf_counter() - t0)
        if lengths is None:  # tensor rows have no length to split by
            return await self.runner.infer(inputs)
        t0 = _time.perf_counter()
        buckets = self.runner.buckets
        pieces = carve_by_length(
            lengths, buckets.batch_buckets, buckets.seq_buckets,
            compiled=self.runner.compiled_grid())
        self.m_steps.observe(len(pieces))
        if len(pieces) == 1:
            sb = pieces[0][2]
            record_stage("length_split", _time.perf_counter() - t0)
            return await self.runner.infer(
                {k: v[:, :sb] for k, v in inputs.items()})
        parts = [{k: v[idx, :sb] for k, v in inputs.items()}
                 for idx, _, sb in pieces]
        split_s = _time.perf_counter() - t0
        outs = await asyncio.gather(*[self.runner.infer(p) for p in parts])
        t0 = _time.perf_counter()
        merged = _scatter_rows(
            batch.num_rows, [idx for idx, _, _ in pieces], outs)
        # carve + scatter host time, so it has a name in the trace's idle
        # gaps should it ever hold the chip up
        record_stage("length_split", split_s + _time.perf_counter() - t0)
        return merged

    async def _infer_packed(self, batch: MessageBatch) -> dict[str, np.ndarray]:
        """Token-packed inference (tpu/packing.py): tokenize off the payload
        buffer view, first-fit-pack ALL examples into dense rows of the
        batch's seq bucket, then carve the packed layout into row windows
        that fill the compiled (rows, seq) grid (``carve_row_windows``) —
        pack-once-carve-after means a token-budget emission fills the
        largest bucket exactly, with only the final window as a tail on a
        smaller bucket. Windows serve concurrently (the runner's in-flight
        semaphore pipelines them) and per-example outputs scatter back into
        original row order. No per-row Python anywhere on this path."""
        from arkflow_tpu.tpu.packing import carve_row_windows, pack_tokens

        def tokenize_and_carve() -> list[tuple[dict[str, np.ndarray], np.ndarray]]:
            # host-side numpy work: off the event loop, like the runner's
            # own _prep, so a big batch never stalls other streams
            ids, mask = self._encode_texts(batch, self.max_seq)
            lengths = mask.sum(axis=1).astype(np.int64)
            if self.tuner is not None:  # executor thread: the sketch locks
                self.tuner.observe(lengths)
            sb = self.runner.buckets.seq_bucket(
                int(lengths.max()) if len(lengths) else 1)
            pk = pack_tokens(ids, lengths, sb)
            return carve_row_windows(pk, self.runner.buckets.max_batch(),
                                     self.runner.buckets.max_examples(),
                                     self.runner.buckets.batch_buckets)

        def timed_tokenize_and_carve():
            with self.m_extract.time():
                return tokenize_and_carve()

        import time as _time

        from arkflow_tpu.obs.trace import record_stage

        loop = asyncio.get_running_loop()
        t0 = _time.perf_counter()
        windows = await loop.run_in_executor(None, timed_tokenize_and_carve)
        record_stage("infeed_prep", _time.perf_counter() - t0)
        outs = await asyncio.gather(
            *[self.runner.infer(inputs) for inputs, _ in windows])
        # window examples are row-sorted, not input-ordered
        return _scatter_rows(batch.num_rows, [idx for _, idx in windows], outs)


def _scatter_rows(n: int, indices: list[np.ndarray],
                  outs: list[dict[str, np.ndarray]]) -> dict[str, np.ndarray]:
    """Per-piece ``[rows_i, ...]`` outputs back into the batch's original
    row order (``indices[i]`` are the batch rows piece ``i`` held)."""
    merged: dict[str, np.ndarray] = {}
    for key in outs[0]:
        first = np.asarray(outs[0][key])
        out = np.empty((n, *first.shape[1:]), first.dtype)
        for idx, chunk in zip(indices, outs):
            out[idx] = np.asarray(chunk[key])
        merged[key] = out
    return merged


@register_processor("tpu_inference")
def _build(config: dict, resource: Resource) -> TpuInferenceProcessor:
    # the whole construction, less the stages nested in it (init, restore,
    # placement): buckets, mesh, the jitted step, staging pools, the tuner
    with setup_stage("setup_build"):
        return _construct(config)


def _construct(config: dict) -> TpuInferenceProcessor:
    # deferred: importing jax (and the TPU plugin) only when a model is built
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.runner import ModelRunner

    refuse_pp_serving(config)
    model = config.get("model")
    if not model:
        raise ConfigError("tpu_inference requires 'model'")
    max_seq = int(config.get("max_seq", 128))
    packing_raw = config.get("packing", False)
    if not isinstance(packing_raw, bool):
        raise ConfigError(
            f"tpu_inference.packing must be a bool, got {packing_raw!r}")
    # packed serving: the EXAMPLE-dim grid defaults to 4x the row grid — a
    # full row bucket of short texts carries ~seq/len(example) examples per
    # row, so the example dim must extend past max_batch or token-budget
    # emissions would be capped by example count instead of tokens
    buckets = BucketPolicy.from_config(
        config, max_seq=max_seq,
        max_batch=int(config.get("max_batch", 256)),
        default_example_scale=4 if packing_raw else 1)
    mesh_cfg = config.get("mesh") or {}
    mesh_spec = None
    if mesh_cfg:
        mesh_spec = MeshSpec(dp=int(mesh_cfg.get("dp", 1)), tp=int(mesh_cfg.get("tp", 1)),
                             sp=int(mesh_cfg.get("sp", 1)))
    packing = packing_raw
    pool_size = int(config.get("device_pool", 0) or 0)
    if pool_size and mesh_cfg:
        raise ConfigError(
            "tpu_inference: 'device_pool' and 'mesh' are mutually exclusive "
            "(a pool member is a single-device runner; pick sharded dispatch "
            "OR replicated serving)")
    from arkflow_tpu.tpu.serving_core import parse_core_config

    common = dict(
        buckets=buckets,
        checkpoint=config.get("checkpoint"),
        seed=int(config.get("seed", 0)),
        serving_dtype=config.get("serving_dtype"),
        max_in_flight=(int(config["max_in_flight"])
                       if config.get("max_in_flight") is not None else None),
        # dispatch_depth: 2 releases the in-flight permit at DISPATCH so the
        # next step's infeed+dispatch overlaps this step's compute; output
        # fetch runs outside the window under its own per-step deadline
        dispatch_depth=(int(config["dispatch_depth"])
                        if config.get("dispatch_depth") is not None else None),
        packed=packing,
        # shared self-healing knobs (step_deadline / step_deadline_first /
        # health) — parsed by the serving core both device paths sit on
        **parse_core_config(config),
    )
    if pool_size > 1:
        from arkflow_tpu.tpu.pool import ModelRunnerPool

        runner = ModelRunnerPool(
            model, config.get("model_config"), pool_size=pool_size, **common)
    else:  # device_pool: 1 is just single-device serving
        runner = ModelRunner(
            model, config.get("model_config"), mesh_spec=mesh_spec, **common)
    vocab = getattr(runner.cfg, "vocab_size", 30522)
    tokenizer = build_tokenizer(config.get("tokenizer"), vocab_size=vocab)
    from arkflow_tpu.runtime.respcache import build_response_cache

    cache = build_response_cache(config.get("response_cache"), name=str(model))
    from arkflow_tpu.tpu.swap import build_batch_swapper, parse_swap_config

    swapper = build_batch_swapper(
        runner, model=str(model),
        serving_dtype=config.get("serving_dtype"),
        seed=int(config.get("seed", 0)),
        swap_cfg=parse_swap_config(config.get("swap"), who="tpu_inference"),
        checkpoint=config.get("checkpoint"))
    if cache is not None:
        # swap-aware cache: a committed swap epoch-flushes so a post-swap
        # duplicate can never be answered with pre-swap bytes
        swapper.add_commit_hook(cache.bump_epoch)
    from arkflow_tpu.tpu.tuner import build_shape_tuner, parse_tuner_config

    # traffic-adaptive shapes (tpu/tuner.py): observes live token lengths
    # and retunes seq edges / token budget / deadline / example_scale with
    # warm-then-flip discipline; the cache registers for the config epoch
    # so a post-flip duplicate never returns bytes from the old padding
    tuner = build_shape_tuner(
        runner, model=str(model),
        cfg=parse_tuner_config(config.get("tuner"), who="tpu_inference"),
        packed=packing, cache=cache)
    from arkflow_tpu.tpu.integrity import (build_integrity_monitor,
                                           parse_integrity_config)

    # silent-data-corruption defense (tpu/integrity.py): periodic golden
    # probes + param digests over every member, quarantine-and-repair on a
    # proven mismatch. Opt-in: no `integrity:` block, no monitor (a probe
    # is a real device step per member per interval).
    integrity = build_integrity_monitor(
        runner, model=str(model),
        cfg=parse_integrity_config(config.get("integrity"),
                                   who="tpu_inference"))
    if integrity is not None and cache is not None:
        # a quarantined member's cached answers may be corrupt: epoch-flush
        # so a post-quarantine byte-identical duplicate recomputes instead
        # of replaying poisoned bytes
        integrity.add_quarantine_hook(cache.bump_epoch)
    if integrity is not None and swapper is not None:
        # swaps and probes must coexist: probing quiesces across the roll
        # and the golden reference recomputes against committed weights
        swapper.integrity = integrity
    return TpuInferenceProcessor(
        runner,
        text_field=config.get("text_field", DEFAULT_BINARY_VALUE_FIELD),
        tensor_field=config.get("tensor_field"),
        tokenizer=tokenizer,
        max_seq=max_seq,
        outputs=config.get("outputs"),
        warmup=bool(config.get("warmup", False)),
        packing=packing,
        response_cache=cache,
        swapper=swapper,
        tuner=tuner,
        integrity=integrity,
    )
