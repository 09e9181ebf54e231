"""``tpu_generate`` processor: batched LLM generation over the stream.

BASELINE.json config 5 (Kafka CDC -> batched summarization -> NATS): prompts
are tokenized and padded to a bucket, the decoder LM prefills its KV cache in
one pass, then a jitted single-token greedy decode loop runs to
``max_new_tokens`` (early-exit when every sequence emitted EOS). Output text
attaches as a string column.

Note on tokenizers: with a real (HF) tokenizer the output is text; with the
hermetic hashing fallback there is no inverse mapping, so generated ids are
rendered as space-joined integers — the mechanics (prefill, cache, stop
conditions, throughput) are identical.

Weights on the device: the generation programs multiply in bfloat16, so the
tree this processor places holds each leaf in the dtype the forward consumes
it in — bfloat16 weights, float32 norm scales and MoE router
(``decoder.serve_dtypes``) — and no compiled step casts a weight. The cast
(round-to-nearest-even, the one ``cm.dense`` did at every use) happens once,
at placement, one leaf at a time, so the device never holds a float32 copy
of the whole tree. ``host_params`` and checkpoints stay the float32 masters:
the hot-swap manager restores and the integrity monitor repairs from them,
through the same placement. There is nothing to configure.

Config:

    type: tpu_generate
    model: decoder_lm
    model_config: {vocab_size: 2048, ...}   # latent attention (MLA) +
                             # routed experts: kv_lora_rank, n_routed_experts,
                             # ...; a layer pattern: layer_types, swa_*,
                             # index_*, experts_held (docs/CONFIG.md); on
                             # per-head K/V layers: layer_types,
                             # sliding_window, qk_norm, full_attention_rope,
                             # with or without n_routed_experts; such
                             # a model serves through serving: continuous
                             # on one chip only; so does the hybrid block
                             # (mamba_*: a Mamba-2 mixer beside attention,
                             # a recurrent state a slot; needs
                             # prefill_chunk > 0), conv layers
                             # (layer_types: conv, conv_L_cache: a window
                             # of gated inputs a slot) and Gated DeltaNet
                             # layers (layer_types: linear_attention,
                             # linear_*: a float32 matrix state a slot) —
                             # or, beside latent layers, Kimi Delta
                             # Attention layers (linear_attn_config)
    text_field: __value__
    tokenizer: meta-llama/Llama-3-8B     # optional (hash fallback otherwise)
    max_input: 256
    max_new_tokens: 64
    eos_id: 2
    output_field: generated
    batch_buckets: [8, 16]
    serving: continuous      # batch | continuous (paged KV + lockstep slots)
    mesh: {tp: 4}            # multi-chip serving. batch mode shards dp/tp/sp;
                             # continuous mode shards TENSOR-PARALLEL only:
                             # KV pages split over KV heads on the tp axis
                             # (tp must divide the model's kv_heads; dp/sp
                             # don't compose with the slot grid's lockstep)
    prefill_chunk: 128       # continuous mode: admit long prompts in chunks
                             # interleaved with decode steps (0 = one-shot)
    speculative_tokens: 3    # continuous+greedy: self-drafted (n-gram
                             # lookup) speculative decode, verified in one
                             # chunk call; exact greedy outputs (0 = off)
    prefix_cache_pages: 64   # continuous mode: LRU automatic prefix cache —
                             # finished prompts donate full KV pages, later
                             # requests with the same token prefix alias
                             # them and prefill only the rest (0 = off)
    decode_kernel: paged     # continuous mode: auto (default — paged on
                             # TPU, gather elsewhere) | gather (dense
                             # reference) | paged — the Pallas kernel reads
                             # the KV page table in place for decode +
                             # chunked prefill. Explicit paged needs a TPU
                             # backend (ConfigError otherwise); a logit-
                             # parity probe against gather gates it and a
                             # mismatch fails construction
                             # (kernel_parity_check: false skips the probe,
                             # kernel_interpret: true for CPU tests)
    dispatch_depth: 2        # continuous mode, the default: one step ahead
                             # of the device — step N+1 of any kind is
                             # enqueued before step N is waited for,
                             # fetched and applied, so host bookkeeping
                             # overlaps device compute. Exact same tokens
                             # as 1 (lockstep); where it would not be
                             # (sampling, speculation, ...) the server
                             # serves in lockstep by itself
    step_deadline: 2s        # continuous mode: per-step watchdog from the
                             # shared serving core (tpu/serving_core.py) — a
                             # hung step marks the server UNHEALTHY and the
                             # batch nacks for redelivery
    step_deadline_first: 60s # budget for first-compile steps (default 10x)
    health: {probe_backoff: 500ms, probe_backoff_cap: 30s, dead_after: 8}
    checkpoint: /path/to/orbax   # optional: restore params at build
    swap:                    # live hot-swap knobs (tpu/swap.py): continuous
      canary: {rows: 4}      # mode drains the slot grid, flips, rebuilds
      drain_timeout: 30s     # jits, and resets KV pools + prefix cache
    integrity:               # SDC defense (tpu/integrity.py; continuous
      probe_interval: 10s    # mode only): periodic golden forward-apply of
      digest_every: 3        # the live tree vs a host reference + digest
      golden: {rows: 2, seq: 16, seed: 2317}  # re-verification; mismatch
      repair: true           # quarantines (CORRUPT) and repairs via swap
"""

from __future__ import annotations

import asyncio
import functools
import logging
from collections import Counter
from typing import Optional

import numpy as np
import pyarrow as pa

from arkflow_tpu.batch import DEFAULT_BINARY_VALUE_FIELD, MessageBatch
from arkflow_tpu.components import Processor, Resource, register_processor
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.startup import setup_stage
from arkflow_tpu.tpu.bucketing import BucketPolicy, pad_batch_dim
from arkflow_tpu.tpu.tokenizer import build_tokenizer

logger = logging.getLogger("arkflow.generate")

#: a float32 master of this many bytes or more is placed a piece at a time
#: (``_put_in_pieces``). Measured on a v5e host (ledger PR 61, PERF.md PR 62):
#: trees whose largest leaf is 3.3 / 4.1 GB place in 2.0 / 2.2 s, one with a
#: leaf of 5.35 GB in 32 s, one with two of 6.8 GB in 30-42 s
_PIECES_OVER = 6 << 30


def _put_in_pieces(leaf: np.ndarray, dtype, device):
    """``leaf`` on ``device`` in ``dtype``, an index of its leading axis at a
    time: the piece is transferred, cast and written into the placed array in
    place (donated), and waited for — the device holds the placed leaf and one
    piece's float32 copy, not the leaf's."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def write(out, piece, i):
        return jax.lax.dynamic_update_index_in_dim(
            out, piece.astype(out.dtype), i, 0)

    out = jnp.zeros(leaf.shape, dtype, device=device)
    for i in range(leaf.shape[0]):
        out = write(out, jax.device_put(leaf[i], device),
                    np.int32(i)).block_until_ready()
    return out


class TpuGenerateProcessor(Processor):
    def __init__(self, model: str, model_config: Optional[dict], *, text_field: str,
                 tokenizer, max_input: int, max_new_tokens: int, eos_id: int,
                 output_field: str, buckets: BucketPolicy, seed: int = 0,
                 serving: str = "batch", slots: int = 8, page_size: int = 16,
                 temperature: float = 0.0, top_k: int = 0,
                 mesh_config: Optional[dict] = None, prefill_chunk: int = 0,
                 speculative_tokens: int = 0, prefix_cache_pages: int = 0,
                 decode_kernel: str = "auto", kernel_interpret: bool = False,
                 kernel_parity_check: bool = True, dispatch_depth: int = 2,
                 step_deadline_s: Optional[float] = None,
                 step_deadline_first_s: Optional[float] = None,
                 health_config=None, checkpoint: Optional[str] = None):
        import jax

        from arkflow_tpu.models import get_model
        from arkflow_tpu.tpu.jaxcache import enable_persistent_cache

        enable_persistent_cache()  # the whole-generation jit is the costliest compile
        if mesh_config:
            allowed = {"dp", "tp", "sp"}
            unknown = set(mesh_config) - allowed
            if unknown:
                raise ConfigError(
                    f"tpu_generate mesh keys {sorted(unknown)} not supported "
                    f"here (generation shards over {sorted(allowed)}; "
                    f"ep/pp apply to training/forward paths)")
        if serving == "continuous" and mesh_config:
            from arkflow_tpu.config import refuse_continuous_split

            refuse_continuous_split(mesh_config)
        self.family = get_model(model)
        if not {"generate", "serve_dtypes"} <= set(self.family.extras):
            raise ConfigError(f"model {model!r} does not support incremental decoding")
        self.cfg = self.family.make_config(**(model_config or {}))
        # what the model's cache is not served with (``paged_decode.UNSERVED``),
        # before the host init: seconds to minutes at real widths
        from arkflow_tpu.models.paged_decode import refuse, unserved

        if serving != "continuous":
            refuse(self.cfg, "batch")
        if mesh_config:
            refuse(self.cfg, "mesh_tp")
        self.text_field = text_field
        self.tokenizer = tokenizer
        self.max_input = max_input
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.output_field = output_field
        self.buckets = buckets

        # host init (+ optional checkpoint restore) on CPU, one transfer to
        # the execution devices — shared with the batch runner, and the same
        # restore path the hot-swap manager replays for candidate weights
        from arkflow_tpu.tpu.runner import init_host_params

        params = init_host_params(self.family, self.cfg, seed, checkpoint)
        #: retained known-good host tree — the integrity monitor's repair
        #: source and golden-reference input (tpu/integrity.py), same
        #: retention the batch ModelRunner keeps
        self.host_params = params
        # tensor-parallel serving: shard params over a Mesh so decode runs
        # multi-chip via GSPMD (the KV cache shards over heads implicitly)
        self.mesh = None
        self._pspecs = None
        if mesh_config:
            from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh

            try:
                spec = MeshSpec(dp=int(mesh_config.get("dp", 1)),
                                tp=int(mesh_config.get("tp", 1)),
                                sp=int(mesh_config.get("sp", 1)))
                self.mesh = create_mesh(spec)
            except ConfigError:
                raise
            except (TypeError, ValueError) as e:
                raise ConfigError(f"tpu_generate mesh config invalid: {e}") from e
            axes = {name: name for name in self.mesh.axis_names}
            self._pspecs = (self.family.param_specs(self.cfg, axes)
                            if self.family.param_specs else None)
        self.params = self._place_params(params)

        ex = self.family.extras
        # whole-generation jit: one device dispatch per batch (prefill +
        # while_loop decode with EOS early-exit), not one per token
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self._rng = jax.random.PRNGKey(seed + 1)
        self._generate = jax.jit(
            functools.partial(
                ex["generate"], cfg=self.cfg,
                max_new_tokens=self.max_new_tokens, eos_id=self.eos_id,
                temperature=self.temperature, top_k=self.top_k,
            )
        )

        # continuous mode: paged-KV lockstep server (vLLM-style); requests
        # from every stream worker share the slot grid, so long generations
        # never hold short ones hostage (per-row completion, not per-batch).
        # Under a mesh the server runs tensor-parallel (KV pages over tp);
        # it also sits on the shared serving core, so the engine's /health
        # and the fault plugin reach it through ``self.runner`` exactly like
        # a tpu_inference ModelRunner.
        self.serving = serving
        self._server = None
        if serving == "continuous":
            from arkflow_tpu.tpu.serving import GenerationServer

            self._server = GenerationServer(
                self.params, self.cfg, slots=slots, page_size=page_size,
                max_seq=self.max_input + self.max_new_tokens, eos_id=eos_id,
                prompt_buckets=list(buckets.seq_buckets),
                temperature=self.temperature, top_k=self.top_k, seed=seed + 1,
                prefill_chunk=prefill_chunk,
                speculative_tokens=speculative_tokens,
                prefix_cache_pages=prefix_cache_pages,
                decode_kernel=decode_kernel,
                kernel_interpret=kernel_interpret,
                kernel_parity_check=kernel_parity_check,
                dispatch_depth=dispatch_depth,
                mesh=self.mesh,
                step_deadline_s=step_deadline_s,
                step_deadline_first_s=step_deadline_first_s,
                health_config=health_config,
                name=model,
            )
            #: the engine's /health introspection and the fault plugin's
            #: step-fault arming both look for ``.runner`` — the generation
            #: server IS this processor's device runner
            self.runner = self._server
            #: prefill/decode disaggregation adapter: a prefill-role
            #: cluster worker (runtime/cluster.py) finds this through the
            #: same ``_inner``-chain walk as ``.runner``/``.swapper`` and
            #: drives prefill_rows -> kv_push -> finalize_rows. Where the
            #: model's pages have no wire form yet no adapter is offered,
            #: and the server's export / adopt calls raise ConfigError
            if unserved(self.cfg, "kv_push") is None:
                self.disagg = self

        reg = global_registry()
        self.m_tokens = reg.counter("arkflow_generated_tokens_total", "tokens generated",
                                    {"model": model})
        #: live hot-swap manager (tpu/swap.py), attached by the builder; the
        #: engine's POST /admin/swap and the fault plugin reach it here
        self.swapper = None
        #: silent-data-corruption monitor (tpu/integrity.py), attached by
        #: the builder for continuous serving; started/stopped with the
        #: processor lifecycle
        self.integrity = None

    async def connect(self) -> None:
        if self.integrity is not None:
            self.integrity.start()

    async def close(self) -> None:
        if self.integrity is not None:
            await self.integrity.stop()

    def _place_params(self, host_params):
        """Place a host tree of float32 masters in the dtypes the generation
        programs consume it in (the family's ``serve_dtypes``: bfloat16
        weights, float32 norm scales and MoE router), sharded under a mesh
        and on one device otherwise, so that no compiled step casts a weight.
        Leaf by leaf: transfer, cast on the device, wait — the float32 copy
        of one leaf is freed before the next leaf's arrives, so the device
        never holds both trees. (Measured on a v5e, PERF.md PR 26: 0.6 s this
        way at 1.44 B weights, 8 s with the cast on the host; without the
        wait the transfers run ahead of the casts and the peak is both
        trees.) A leaf on the host backend is handed over as its numpy view,
        which ``device_put`` slices on the host, one shard to each chip; as a
        CPU ``jax.Array`` it is staged whole through the mesh's first chip
        (tp=4, 3.76 B weights: 19 s and a 9 GB spike there, against 0.7 s and
        2.4 GB). On one chip a leaf of ``_PIECES_OVER`` bytes or more that
        needs the cast goes an index of its leading axis at a time
        (``_put_in_pieces``: 4.0 B weights of which two leaves are 6.8 GB
        float32 each, PERF.md PR 62: 30-42 s whole, with both copies of a
        leaf on the device). Construction, the hot-swap manager and the
        integrity monitor's repair all place through this."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        device = jax.devices()[0]

        def put(leaf, dtype, spec=None):
            to = (NamedSharding(self.mesh, spec or PartitionSpec())
                  if self.mesh is not None else device)
            if isinstance(leaf, jax.Array) and all(
                    d.platform == "cpu" for d in leaf.devices()):
                leaf = np.asarray(leaf)  # no copy
            if (self.mesh is None and leaf.dtype != dtype and leaf.ndim > 1
                    and leaf.nbytes >= _PIECES_OVER):
                return _put_in_pieces(leaf, dtype, device)
            placed = jax.device_put(leaf, to)
            if placed.dtype != dtype:
                placed = placed.astype(dtype).block_until_ready()
            return placed

        trees = [host_params, self.family.extras["serve_dtypes"](self.cfg)]
        if self._pspecs is not None:
            trees.append(self._pspecs)
        with setup_stage("setup_place"):
            # a leaf that needed no cast was not waited for above
            placed = jax.block_until_ready(
                jax.tree_util.tree_map(put, *trees))
        by_dtype: Counter = Counter()
        for leaf in jax.tree_util.tree_leaves(placed):
            by_dtype[str(leaf.dtype)] += leaf.nbytes
        for dtype, nbytes in by_dtype.items():
            global_registry().gauge(
                "arkflow_gen_param_bytes",
                "bytes of the generate param tree as placed (unsharded size)",
                {"model": self.family.name, "dtype": dtype}).set(nbytes)
        logger.info("[%s] generate params placed: %s", self.family.name,
                    ", ".join(f"{d} {n / 1e9:.3f} GB"
                              for d, n in sorted(by_dtype.items())))
        return placed

    # -- generation --------------------------------------------------------

    def _generate_sync(self, ids: np.ndarray, lengths: np.ndarray, n_real: int,
                       rng_key) -> tuple[np.ndarray, np.ndarray]:
        """Run the jitted generation and extract the ragged outputs as
        (flat values, offsets) — one boolean gather over the padded token
        grid instead of a per-row ``tolist`` loop (PR 2's ragged extract,
        reversed: device grid -> flat+offsets instead of Arrow -> tensor)."""
        import jax.numpy as jnp

        import contextlib

        ctx = self.mesh if self.mesh is not None else contextlib.nullcontext()
        with ctx:
            tokens, counts = self._generate(
                self.params, input_ids=jnp.asarray(ids),
                lengths=jnp.asarray(lengths, jnp.int32),
                n_real=jnp.asarray(n_real, jnp.int32),
                rng_key=rng_key,
            )
        tokens = np.asarray(tokens)[:n_real]
        counts = np.asarray(counts)[:n_real].astype(np.int64)
        mask = np.arange(tokens.shape[1])[None, :] < counts[:, None]
        flat = tokens[mask]  # single flat gather, row-major = offset order
        offsets = np.zeros(n_real + 1, np.int64)
        np.cumsum(counts, out=offsets[1:])
        self.m_tokens.inc(int(flat.size))
        return flat, offsets

    def _detok(self, ids) -> str:
        return self.tokenizer.decode(ids)

    def _detok_column(self, flat: np.ndarray, offsets: np.ndarray) -> pa.Array:
        """Ragged ids (flat + offsets) -> string column. The hashing
        tokenizer renders ids verbatim, which vectorizes as an Arrow list
        column + join kernel; real (HF) tokenizers decode row-wise off
        zero-copy views into the flat buffer."""
        decode_column = getattr(self.tokenizer, "decode_column", None)
        if decode_column is not None:
            return decode_column(flat, offsets)
        return pa.array(
            [self._detok(flat[offsets[i]:offsets[i + 1]])
             for i in range(len(offsets) - 1)],
            pa.string())

    # -- prefill/decode disaggregation (continuous mode only) --------------

    async def prefill_rows(self, batch: MessageBatch) -> list[dict]:
        """Prefill each row on the local scratch page pool and return the
        KV-page exports (one per row, in row order) for the cluster worker
        to stream to a decode destination."""
        texts = batch.to_binary(self.text_field)
        ids, mask = self.tokenizer.encode_batch(texts, self.max_input)
        lengths = mask.sum(axis=1).astype(np.int32)
        return list(await asyncio.gather(*[
            self._server.prefill_export(ids[i, :lengths[i]].tolist(),
                                        max_new_tokens=self.max_new_tokens)
            for i in range(ids.shape[0])
        ]))

    def finalize_rows(self, batch: MessageBatch,
                      token_lists: list) -> list[MessageBatch]:
        """Detokenize the decode worker's relayed token lists into the
        output column, exactly as the local continuous path would."""
        self.m_tokens.inc(sum(len(t) for t in token_lists))
        texts_out = [self._detok(list(t)) for t in token_lists]
        return [batch.with_column(self.output_field,
                                  pa.array(texts_out, pa.string()))]

    async def process(self, batch: MessageBatch) -> list[MessageBatch]:
        if batch.num_rows == 0:
            return []
        texts = batch.to_binary(self.text_field)
        ids, mask = self.tokenizer.encode_batch(texts, self.max_input)
        lengths = mask.sum(axis=1).astype(np.int32)
        if self._server is not None:
            outs = await asyncio.gather(*[
                self._server.generate(ids[i, :lengths[i]].tolist(),
                                      max_new_tokens=self.max_new_tokens)
                for i in range(ids.shape[0])
            ])
            self.m_tokens.inc(sum(len(o) for o in outs))
            texts_out = [self._detok(list(o)) for o in outs]
            return [batch.with_column(self.output_field, pa.array(texts_out, pa.string()))]
        used = int(lengths.max()) if lengths.size else 1
        sb = self.buckets.seq_bucket(used)
        ids = ids[:, :sb]
        lengths = np.minimum(lengths, sb)
        n = ids.shape[0]
        bb = self.buckets.batch_bucket(n)
        ids = pad_batch_dim(ids, bb)
        lengths = np.concatenate([lengths, np.ones(bb - n, np.int32)])
        import jax

        # split on the event loop: concurrent worker batches must not race
        # the key state in executor threads (duplicate keys = correlated samples)
        self._rng, sub = jax.random.split(self._rng)
        flat, offsets = await asyncio.get_running_loop().run_in_executor(
            None, self._generate_sync, ids, lengths, n, sub
        )
        # flat+offsets already trimmed to the n true rows
        return [batch.with_column(self.output_field, self._detok_column(flat, offsets))]


@register_processor("tpu_generate")
def _build(config: dict, resource: Resource) -> TpuGenerateProcessor:
    # the whole construction, less the stages nested in it (init, restore,
    # placement, the probe): tokenizer, mesh, pools and page tables, jits
    with setup_stage("setup_build"):
        return _construct(config)


def _construct(config: dict) -> TpuGenerateProcessor:
    from arkflow_tpu.tpu.serving_core import parse_core_config

    model = config.get("model", "decoder_lm")
    max_input = int(config.get("max_input", 256))
    buckets = BucketPolicy.from_config(config, max_batch=int(config.get("max_batch", 16)),
                                       max_seq=max_input)
    runner_cfg = config.get("model_config")
    vocab = (runner_cfg or {}).get("vocab_size", 2048)
    core_cfg = parse_core_config(config)
    proc = TpuGenerateProcessor(
        model,
        runner_cfg,
        text_field=config.get("text_field", DEFAULT_BINARY_VALUE_FIELD),
        tokenizer=build_tokenizer(config.get("tokenizer"), vocab_size=vocab),
        max_input=max_input,
        max_new_tokens=int(config.get("max_new_tokens", 64)),
        eos_id=int(config.get("eos_id", 2)),
        output_field=str(config.get("output_field", "generated")),
        buckets=buckets,
        seed=int(config.get("seed", 0)),
        serving=_serving_mode(config),
        slots=int(config.get("slots", 8)),
        page_size=int(config.get("page_size", 16)),
        temperature=float(config.get("temperature", 0.0)),
        top_k=int(config.get("top_k", 0)),
        mesh_config=config.get("mesh"),
        prefill_chunk=int(config.get("prefill_chunk", 0)),
        speculative_tokens=int(config.get("speculative_tokens", 0)),
        prefix_cache_pages=int(config.get("prefix_cache_pages", 0)),
        decode_kernel=str(config.get("decode_kernel", "auto")),
        kernel_interpret=bool(config.get("kernel_interpret", False)),
        kernel_parity_check=bool(config.get("kernel_parity_check", True)),
        dispatch_depth=int(config.get("dispatch_depth", 2)),
        step_deadline_s=core_cfg["step_deadline_s"],
        step_deadline_first_s=core_cfg["step_deadline_first_s"],
        health_config=core_cfg["health_config"],
        checkpoint=config.get("checkpoint"),
    )
    from arkflow_tpu.models.paged_decode import refuse, unserved

    # a model whose row refuses the key has neither attached
    # (``paged_decode.UNSERVED``, columns swap / integrity)
    for key in ("swap", "integrity"):
        if config.get(key) is not None:
            refuse(proc.cfg, key, who=f"tpu_generate: {key}")
    if unserved(proc.cfg, "swap") is None:
        from arkflow_tpu.tpu.swap import build_generate_swapper, parse_swap_config

        proc.swapper = build_generate_swapper(
            proc, model=str(model), seed=int(config.get("seed", 0)),
            swap_cfg=parse_swap_config(config.get("swap"), who="tpu_generate"),
            checkpoint=config.get("checkpoint"))
    if unserved(proc.cfg, "integrity") is None:
        from arkflow_tpu.tpu.integrity import (build_generate_integrity_monitor,
                                               parse_integrity_config)

        proc.integrity = build_generate_integrity_monitor(
            proc, model=str(model),
            cfg=parse_integrity_config(config.get("integrity"),
                                       who="tpu_generate"))
    if proc.integrity is not None and proc.swapper is not None:
        # swaps and probes must coexist: probing quiesces across the roll
        # and the golden reference recomputes against committed weights
        proc.swapper.integrity = proc.integrity
    return proc


def _serving_mode(config: dict) -> str:
    mode = str(config.get("serving", "batch"))
    if mode not in ("batch", "continuous"):
        raise ConfigError(f"tpu_generate serving must be batch|continuous, got {mode!r}")
    return mode
