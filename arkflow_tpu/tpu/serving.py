"""Continuous-batching generation server over the paged KV cache.

The serving pattern the reference cannot express (its processors are
stateless user code): a fixed grid of decode slots steps in lockstep under
one jitted ``paged_decode_step``; requests are admitted into free slots the
moment pages are available, finished sequences free their pages immediately,
and new work rides along mid-flight — the device never waits for the
longest sequence in a batch (continuous batching, as in vLLM/Orca).

Split of responsibilities (TPU-first):
- device: static-shaped jitted prefill/decode (models/paged_decode.py);
  compiled once per (slot-count, page-table-width) + per prompt bucket.
- host (this module): page allocation, slot bookkeeping, EOS/max-token
  tracking, admission — cheap numpy/python between steps.

Multi-chip (``mesh``): the server runs tensor-parallel over a Mesh's ``tp``
axis. The page pools shard over KV heads (``P(None, None, None, "tp",
None)``), params carry their tensor-parallel PartitionSpecs, and every jitted
step is built with explicit NamedSharding in/out shardings — page tables,
token ids, and lengths stay static-shaped and replicated, so the layer scan
lowers to GSPMD collectives with zero dynamic shapes. The host-side
scheduler is untouched: it only ever sees replicated scalars.

Self-healing: the server sits on the shared ``ServingRunnerCore``
(tpu/serving_core.py) — the same health state machine, step-deadline
watchdog, and chaos hooks the ``tpu_inference`` runner uses. A generate step
that blows its deadline marks the server UNHEALTHY, fails every in-flight
request (their batches NACK for redelivery), and the next step waits out the
probe backoff, rebuilds the jitted steps, and reinitializes the pools.
"""

from __future__ import annotations

import asyncio
import contextvars
import functools
import logging
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from arkflow_tpu.errors import ConfigError, StepDeadlineExceeded
from arkflow_tpu.models.decoder import DecoderConfig
from arkflow_tpu.models.paged_decode import (
    cache_spec,
    eva_rows,
    eva_table_pages,
    fusable,
    init_page_pool,
    kv_bytes_per_token,
    paged_decode_step,
    paged_prefill,
    refuse,
    unserved,
    window_ring_pages,
)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.startup import cold_step, note_programs, setup_stage
from arkflow_tpu.obs.trace import (annotated, current_scope, loop_stage,
                                   observe_stage, record_stage)
from arkflow_tpu.ops.ragged_attention import PAGE_RUN, pages_in_runs
from arkflow_tpu.tpu.health import HEALTHY
from arkflow_tpu.tpu.serving_core import ServingRunnerCore

logger = logging.getLogger("arkflow.serving")


@dataclass
class _Request:
    prompt: list[int]
    max_new_tokens: int
    #: created at submit (``_submit``), on the caller's running loop
    future: Optional[asyncio.Future] = None
    tokens: list[int] = field(default_factory=list)
    #: ``time.perf_counter()`` at submit, for the TTFT histogram and the
    #: request's spans (one clock for every stamp below)
    submitted_at: float = 0.0
    #: set once the first decoded token has been observed for this request
    ttft_stamped: bool = False
    #: disaggregated serving: stop after prefill and resolve the future
    #: with a KV-page export instead of decoding locally
    prefill_only: bool = False
    #: disaggregated serving: a received KV-page export to adopt instead
    #: of prefilling (the decode half of a prefill/decode split)
    adopt: Optional[dict] = None
    #: export payload built by ``_export_and_finish`` (prefill_only path)
    export: Optional[dict] = None
    #: the submitter's trace scope: the serve loop runs outside every trace
    #: and records this request's spans into the request's OWN trace
    scope: Optional[object] = None
    #: stamps behind gen_queue_wait / gen_prefill / gen_decode and the
    #: per-token gap: slot assigned, first token, latest token
    slot_at: float = 0.0
    first_token_at: float = 0.0
    last_token_at: float = 0.0
    chunks: int = 0
    shared_tokens: int = 0
    #: a routed-expert model's counters over this prompt's chunks so far
    #: (pairs, distinct experts, largest load, chunks, then what else the
    #: model's steps count: ``_chunk_counts``), kept on the device
    chunk_moe: Optional[object] = None


@dataclass
class _InFlightStep:
    """One step of any kind (decode, chunk, one-shot prefill) that is
    enqueued on the device and not yet applied on the host. The serve loop
    keeps ONE such step while it prepares and enqueues the next, from host
    state as it will stand once this one is applied.

    ``out`` is the step's DEVICE-resident token array: a decode step behind
    a decode step takes its lanes' tokens from it there, so the device never
    waits for a host round trip. ``apply`` takes the fetched tokens onto
    host state when the step lands; None for a prompt's chunk before its
    last, whose output nobody on the host reads (its bookkeeping is done at
    the enqueue). ``act`` / ``reqs`` (decode): the lanes that ride and whose
    they were at dispatch; a lane whose request is no longer that one at
    apply drops its token. ``slot`` (chunk / prefill): the slot whose prompt
    the step advances; with ``apply`` set it is the prompt's last step, and
    the slot joins decode only once its first token is applied. A fused
    step (a decode step that carries a chunk) is both: ``act`` / ``reqs``
    its lanes, and ``slot`` the chunk's only where the chunk was its
    prompt's last (else -1: a chunk before the last closes its books at the
    enqueue and nobody waits for it)."""

    kind: str
    out: object
    #: the enqueue's hop: its share of this step's ``gen_device_wait`` and
    #: ``gen_handoff`` and its ``gen_dispatch``, observed once with the
    #: fetch's hop when the step lands
    hop: "_Hop"
    #: ``time.monotonic()`` at the enqueue: the step's deadline runs from it
    dispatched_at: float
    apply: Optional[Callable] = None
    act: Optional["np.ndarray"] = None
    reqs: Optional[list] = None
    slot: int = -1

    @property
    def seeding(self) -> int:
        """The slot that waits for this step's token to join decode, or -1."""
        return self.slot if self.apply is not None else -1


class _Hop:
    """Stamps around one blocking call handed to an executor thread. The
    thread carries no trace scope, so it annotates and stamps and the
    coroutine reads the result: seconds inside the call
    (``gen_device_wait:<kind>``), seconds in the two thread hops around it
    (call -> the thread starts, the thread ends -> the coroutine resumes,
    whatever else the event loop ran in between included), and the call's
    own division into ``stage``s, which the call opens itself (it is handed
    the hop): ``gen_dispatch``, ``gen_ready_wait``, ``gen_fetch``."""

    __slots__ = ("kind", "wait", "t_call", "parts", "handoff_s")

    def __init__(self, kind: str):
        self.kind = kind
        self.wait = annotated(f"gen_device_wait:{kind}")
        self.parts: list[tuple[str, annotated]] = []
        self.t_call = time.perf_counter()

    def run(self, fn):
        with self.wait:
            return fn(self)

    def stage(self, stage: str) -> annotated:
        """``<stage>:<kind>`` around a stretch of the call, on its thread."""
        part = annotated(f"{stage}:{self.kind}")
        self.parts.append((stage, part))
        return part

    def done(self) -> "_Hop":
        """Back on the coroutine: close the second thread hop."""
        w = self.wait
        self.handoff_s = (w.t0 - self.t_call) + (time.perf_counter() - w.t1)
        return self


def pack_operands(ids, a, b, table) -> np.ndarray:
    """A device step's small operands as ONE host int32 array (one upload a
    step): ``[ids(rows x c) | a(rows) | b(rows) | table(rows x pages)]``.
    decode: tokens (c = 1), lengths, active 0/1; chunk and admission prefill
    (one row): ids, offset, tokens present; verify: ids, lengths, tokens."""
    return np.concatenate([np.asarray(part, np.int32).reshape(-1)
                           for part in (ids, a, b, table)])


def unpack_operands(packed, rows: int, pages: int, ring: int = 0,
                    state: int = 0):
    """Inside a step's program: ``pack_operands``' four parts again (static
    slices; ``c`` is whatever the array's size leaves). ``ring`` > 0: a
    table row is the kept pages and then so many columns of the window
    pool's ring, handed on as (kept, ring). ``state`` 1: a table row ends
    with the slot's row of the state pool, handed on as a fifth part."""
    c = packed.shape[0] // rows - 2 - pages - ring - state
    ids, a, b, table = jnp.split(
        packed, [rows * c, rows * (c + 1), rows * (c + 2)])
    table = table.reshape(rows, pages + ring + state)
    held = (table[:, -1],) if state else ()
    table = table[:, :pages + ring]
    if ring:
        table = (table[:, :pages], table[:, pages:])
    return ids.reshape(rows, c), a, b, table, *held


def _chunk_counts(so_far, stats):
    """A prompt's counters after one more chunk, on the device: ``so_far``
    is the chunk before's token and then (pairs, experts hit, largest load,
    chunks, the model's further counters); ``stats`` this chunk's (pairs,
    hit, load, further counters). Sums, but the largest load."""
    acc = so_far[1:]
    return jnp.concatenate([
        acc[:2] + stats[:2], jnp.maximum(acc[2:3], stats[2:3]), acc[3:4] + 1,
        acc[4:] + stats[3:]])


#: the label values of the routing series (``m_moe``). ``decode`` is a
#: ``_decode`` execution and takes nothing from a fused step (the
#: benchmark's roofline readers divide it into that program's kernel time);
#: ``chunk`` a prompt's chunks, fused or alone, summed on the device and
#: recorded with its first token; ``prefill`` a one-shot prefill. A fused
#: step's BLOCK — what lanes or chunk hit, what its expert products read —
#: is ``fused``, its lanes alone ``fused_lanes``. The further counters
#: (``_extra_counters``) have no one-shot series: ``_STEP_KINDS``.
_STEP_KINDS = ("decode", "chunk", "fused", "fused_lanes")
_MOE_KINDS = (*_STEP_KINDS, "prefill")

#: ``m_attn_walk[kind]``: pages walked, columns carried, pages walked in runs
_WALK_COUNTERS = (
    ("arkflow_gen_attn_pages_walked_total",
     "kept-pool pages the attention kernel's rows walked, a layer"),
    ("arkflow_gen_attn_table_columns_total",
     "kept page-table columns of the rows the attention kernel was called "
     "with, a layer"),
    ("arkflow_gen_attn_pages_in_runs_total",
     "kept-pool pages the attention kernel's rows walked in whole stretches "
     "of neighbours, a stretch a copy, a layer (a kernel that takes runs: "
     "the latent one, the per-head one over a layer that keeps every key)"),
)


@dataclass(frozen=True)
class _FusedLayout:
    """What EVERY step of a server that fuses a routed model returns: one
    int32 array of one shape whichever program made it, so that a step takes
    the step before's output on the device whatever that was — the lanes
    their tokens (``prev``), a prompt its counters so far
    (``req.chunk_moe``) — and one fetch brings all of it:

        [the lanes' tokens: slots | the prompt's token, its counters so far
         (``_chunk_counts``): 2 + n | the lanes' counters: n | the block's: n]

    ``n`` the counters of one ``moe_step_stats``. A decode step leaves the
    prompt's and the block's places empty, a chunk alone the lanes' and the
    block's."""

    slots: int
    n: int

    @property
    def size(self) -> int:
        return self.slots + 2 + 3 * self.n

    @property
    def lanes_at(self) -> int:
        return self.slots + 2 + self.n

    def decode(self, stats) -> list:
        """What follows a decode step's tokens."""
        return [jnp.zeros(2 + self.n, jnp.int32), stats, jnp.zeros(self.n, jnp.int32)]

    def so_far(self, out):
        """The prompt's place in the output of the step before."""
        return out[self.slots:self.lanes_at]

    def chunk(self, out):
        """A chunk's own output (its token, the prompt's counters) in place."""
        return jnp.concatenate([jnp.zeros(self.slots, jnp.int32), out,
                                jnp.zeros(2 * self.n, jnp.int32)])

    def fused(self, before, stats) -> list:
        """What follows a fused step's ``slots + 1`` tokens: the prompt's
        counters with this chunk's added (``stats`` [3, n]: the lanes', the
        chunk's, the block's), the lanes', the block's."""
        return [_chunk_counts(self.so_far(before), stats[1]), stats[0], stats[2]]


class _FreePages:
    """The kept pool's free pages (page 0 is the scratch page), handed out so
    that a slot's table holds RUNS: the pool is cut into blocks of ``run``
    neighbours from page 1 on (pages past the last whole block are single),
    and a slot's column c takes page c % run of a block — a new block's first
    page, the lowest block that is free whole, at c % run == 0, the page
    after its last one further on — so that an aligned stretch of its table
    names ``run`` consecutive pages, which the latent walk moves as one copy
    (``ops/ragged_attention._by_runs``). Nothing is set aside for a slot: the
    rest of its block stays free, counts as free and goes to whoever asks
    once no whole block is left, so the pool admits what it admitted as a
    plain list; a page is given back alone (sharing and eviction are a
    page's), and a block whose pages are all back is whole again. A take
    scans the blocks' counts (a few thousand: microseconds)."""

    def __init__(self, num_pages: int, run: int = PAGE_RUN):
        self.run = run
        self._free = np.ones(num_pages, bool)
        self._free[0] = False
        self._count = num_pages - 1
        #: free pages of each block (a short last block is never whole)
        self._left = np.bincount((np.arange(1, num_pages) - 1) // run)

    def __len__(self) -> int:
        return self._count

    def take(self, column: int, last: Optional[int]) -> int:
        """A free page for a slot's ``column``, ``last`` its page of the
        column before."""
        k = column % self.run
        p = last + 1 if k else None
        if not (k and p < len(self._free) and self._free[p]
                and (p - 1) % self.run == k):
            # no run to go on with: a new one where a block is whole (the
            # lowest), else the lowest page of a block that is not
            whole = self._left == self.run
            partial = (self._left > 0) & ~whole
            blocks = whole if whole.any() and not (k and partial.any()) else partial
            first = 1 + int(np.argmax(blocks)) * self.run
            p = first + int(np.argmax(self._free[first:first + self.run]))
        self._free[p] = False
        self._left[(p - 1) // self.run] -= 1
        self._count -= 1
        return p

    def give(self, p: int) -> None:
        self._free[p] = True
        self._left[(p - 1) // self.run] += 1
        self._count += 1


class GenerationServer:
    """Greedy continuous-batching decode over ``slots`` lockstep lanes."""

    def __init__(self, params, cfg: DecoderConfig, *, slots: int = 8,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_seq: int = 512, eos_id: int = 2,
                 prompt_buckets: Optional[list[int]] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 prefill_chunk: int = 0, speculative_tokens: int = 0,
                 prefix_cache_pages: int = 0, mesh=None,
                 decode_kernel: str = "auto", kernel_interpret: bool = False,
                 kernel_parity_check: bool = True, dispatch_depth: int = 2,
                 step_deadline_s: Optional[float] = None,
                 step_deadline_first_s: Optional[float] = None,
                 health_config=None, name: str = "decoder_lm"):
        from arkflow_tpu.tpu.jaxcache import enable_persistent_cache

        enable_persistent_cache()
        if cfg.use_ring_attention:
            raise ConfigError("paged serving does not support ring attention")
        # what the model's cache is not served with yet, asked once for
        # each feature the arguments turn on (``paged_decode.UNSERVED``)
        for feature, on in (("mesh_tp", mesh is not None),
                            ("prefix_cache", prefix_cache_pages),
                            ("speculation", speculative_tokens),
                            ("one_shot_prefill", int(prefill_chunk) <= 0)):
            if on:
                refuse(cfg, feature)
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.page_size = page_size
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.pages_per_slot = -(-max_seq // page_size)
        #: a compacting window cache (``cache_spec``: pool ``eva``): a slot's
        #: pages go by its cached length, not by its position (``_serve_eva``)
        self._eva = bool(cfg.eva)
        if self._eva:
            self._serve_eva(name, num_pages, int(prefill_chunk))
        # page 0 is scratch; default pool fits every slot at max_seq
        self.num_pages = num_pages or (1 + self.slots * self.pages_per_slot)
        if self.num_pages < 1 + self.pages_per_slot:
            raise ConfigError(
                f"num_pages={self.num_pages} cannot hold one sequence "
                f"({self.pages_per_slot} pages + scratch)")
        # always top out at max_seq so every admissible prompt has a bucket
        # (generate() rejects prompts longer than max_seq up front)
        self.prompt_buckets = sorted(
            {b for b in (prompt_buckets or [32, 128]) if b <= max_seq} | {max_seq})

        # tensor-parallel serving: the page pools shard over KV heads on the
        # mesh's tp axis; everything the host scheduler touches (page tables,
        # token ids, lengths, active masks) stays replicated, so admission /
        # page accounting is identical whether one chip serves or eight
        self.mesh = mesh
        # a layer pattern: rows of more than one kind and lifetime (the
        # model's ``cache_spec``). Window rows live in a pool of their own,
        # each slot's pages in a ring that a step's queries fit in
        self.prefill_chunk = int(prefill_chunk)
        self._layered = bool(cfg.layered)
        # what a held share and a layer pattern add to a step's counters,
        # in the order they follow the routing's three (``_note_moe``):
        # pairs routed to the experts HELD here; keys an indexed layer
        # attended and keys in its context, over queries and indexed layers
        reg = global_registry()
        self._extra_counters = [
            {kind: reg.counter(metric, text, {"model": name, "kind": kind})
             for kind in _STEP_KINDS}
            for has, metric, text in (
                (cfg.experts_held is not None,
                 "arkflow_gen_moe_held_assignments_total",
                 "(token, expert) pairs routed to the experts held here"),
                (cfg.index_topk, "arkflow_gen_dsa_selected_total",
                 "keys the indexed layers attended (their indexer's choice)"),
                (cfg.index_topk, "arkflow_gen_dsa_context_total",
                 "keys in context of the indexed layers' queries"))
            if has]
        if cfg.hc_mult > 1:  # what a token's residual costs
            reg.gauge("arkflow_gen_residual_streams",
                      "residual streams a token carries between sub-layers "
                      "(hc_mult)", {"model": name}).set(cfg.hc_mult)
            reg.gauge("arkflow_gen_residual_bytes_per_token",
                      "bytes of a token's residual between sub-layers (bfloat16 "
                      "streams: hc_mult x dim x 2)", {"model": name}).set(
                          cfg.hc_mult * cfg.dim * 2)
        #: a state a slot beside the K/V pages (``cache_spec``'s per-slot
        #: pool: the hybrid block's ``ssm``, conv layers' ``conv``, linear
        #: attention layers' ``gdn`` — or, beside latent pages, ``kda``)
        self._stateful = bool(cfg.stateful)
        self._win_cols = window_ring_pages(cfg, page_size, self.prefill_chunk)
        #: page 0 of the window pool is scratch too; every slot can hold a
        #: whole ring, so a window page is never waited for
        self.num_win_pages = 1 + self.slots * self._win_cols if self._win_cols else 0
        self._kv_io_sharding = None     # a pool: [L, pages, page, kv, dh]
        self._repl_sharding = None
        if mesh is not None:
            from arkflow_tpu.config import refuse_continuous_split
            from arkflow_tpu.parallel.mesh import (dp_size, kv_pool_sharding,
                                                   replicated, tp_size,
                                                   validate_tp_heads)

            refuse_continuous_split({"dp": dp_size(mesh)})
            validate_tp_heads(tp_size(mesh), cfg.kv_heads,
                              who="continuous serving")
            self._kv_io_sharding = kv_pool_sharding(
                mesh, cache_spec(cfg)[0].row_major)
            self._repl_sharding = replicated(mesh)
        self.k_pages, self.v_pages = self._init_pools()

        # chunked prefill (``prefill_chunk``, set above): prompts longer
        # than it admit in fixed-size chunks interleaved with decode steps,
        # so one long prompt never stalls every decode lane for a
        # monolithic prefill (0 = one-shot)
        #: slot -> next absolute prefill offset (present while admitting)
        self._prefill_pos: dict[int, int] = {}
        self._turn_prefill = True  # alternate chunk/decode under contention

        # automatic prefix caching (vLLM-style): finished requests donate
        # their prompt's FULL pages to an LRU keyed by the token prefix;
        # later requests alias those pages (refcounted, read-only by
        # construction — decode only ever writes positions >= its prompt
        # length, and RoPE positions are absolute, so cached K/V is exact
        # for any request sharing the token prefix) and prefill only the
        # remainder through the chunk kernel. 0 = off; N = max cached pages.
        self.prefix_cache_pages = int(prefix_cache_pages)
        if self.prefix_cache_pages < 0:
            raise ConfigError("prefix_cache_pages must be >= 0")
        from collections import OrderedDict

        self._prefix_cache: "OrderedDict[tuple, list[int]]" = OrderedDict()
        #: DISTINCT pages held by cache entries (page -> entry count):
        #: nested prefixes share pages, so capacity counts physical pages
        self._cache_pages: dict[int, int] = {}
        #: token-lengths present in the cache (length -> entry count), so
        #: lookup probes only stored lengths instead of every page multiple
        self._prefix_lengths: dict[int, int] = {}

        # host-side state
        self._free_pages = _FreePages(self.num_pages)
        self._page_refs: dict[int, int] = {}
        self._slot_req: list[Optional[_Request]] = [None] * slots
        self._slot_pages: list[list[int]] = [[] for _ in range(slots)]
        #: the window pool's ledger: free pages, and per slot the live pages
        #: by logical index (oldest first; ring column = index % columns)
        self._win_free: list[int] = list(range(1, self.num_win_pages))
        self._slot_win: list[dict[int, int]] = [{} for _ in range(slots)]
        self._lengths = np.zeros(slots, np.int32)
        self._cur_tokens = np.zeros(slots, np.int32)
        # plain deque: admission needs FIFO peek, which asyncio.Queue only
        # offers via private internals
        self._pending: deque[_Request] = deque()
        self._loop_task: Optional[asyncio.Task] = None
        self._closed = False
        #: hot-swap drain flag (``swap_params``): admission pauses, the slot
        #: grid runs dry, then params flip + jits rebuild + pools reset —
        #: queued requests wait through the flip instead of failing
        self._draining = False

        self.temperature = float(temperature)
        self.top_k = int(top_k)
        # a sampling server's key lives ON the device: a step's program
        # splits it and returns the successor. A greedy server has no key
        self._key = jax.random.PRNGKey(seed) if self.temperature > 0.0 else None
        # self-speculative greedy decode: draft k-1 tokens by n-gram lookup
        # over the sequence's own history, verify all k in ONE chunk call.
        # Decode steps are HBM-bandwidth-bound (weights + KV reads dominate),
        # so scoring k positions costs barely more than one — every accepted
        # draft is nearly-free throughput. Greedy only: acceptance compares
        # argmax, which preserves exact greedy outputs.
        self.speculative_tokens = int(speculative_tokens)
        if self.speculative_tokens < 0:
            raise ConfigError("speculative_tokens must be >= 0")
        if self.speculative_tokens > 0 and self.temperature != 0.0:
            raise ConfigError(
                "speculative_tokens requires greedy decoding (temperature 0); "
                "sampled acceptance is not implemented")

        # decode attention kernel: "gather" materializes each slot's context
        # from the page pools and masks (the reference path); "paged" runs
        # the Pallas kernel that reads the page table in place
        # (ops/ragged_attention.paged_flash_attention) for decode AND
        # chunked prefill. "auto" (default) picks paged on TPU backends and
        # gather elsewhere — same idiom as the runner's auto flash. Compiled
        # Pallas needs a TPU backend; CPU tests opt in via kernel_interpret.
        # The kernel serves only after a logit-parity probe against the
        # gather reference; a mismatch fails construction.
        self.decode_kernel = str(decode_kernel)
        if self.decode_kernel not in ("auto", "gather", "paged"):
            raise ConfigError(
                f"decode_kernel must be auto|gather|paged, got {decode_kernel!r}")
        self.kernel_interpret = bool(kernel_interpret)
        can_run_kernel = self.kernel_interpret or self._on_tpu()
        if self.decode_kernel == "auto":
            self.decode_kernel = "paged" if can_run_kernel else "gather"
        elif self.decode_kernel == "paged" and not can_run_kernel:
            raise ConfigError(
                "decode_kernel: paged requires a TPU backend (or "
                "kernel_interpret for CPU tests); leave it at auto to serve "
                "with the dense gather reference here")

        # dispatch depth: 2 (the default) keeps one step ahead of the device
        # — step N+1 of any kind is enqueued before step N is waited for,
        # fetched and applied, so the host's work on a step overlaps the
        # device's (``_run_ahead``). 1 forces lockstep: every step runs to
        # its end before the next is prepared. Running ahead must serve the
        # same tokens, so it is on only where it is exact, decided here
        # where it is the configuration's and per step where it is the
        # moment's (``_may_run_ahead``):
        # - a prompt's last chunk is applied one step later than in
        #   lockstep, so its lane joins decode one step later. Lanes of a
        #   dense or dropless-routed model are independent and no request's
        #   tokens change; a sampled lane would draw from another step's
        #   key, and the capacity-based Switch block (``num_experts``)
        #   queues a step's live lanes into shared expert capacity, so
        #   either serves in lockstep;
        # - a budget's end is known a step early (the lane is masked out of
        #   the step behind: it reads and writes the scratch row, a state's
        #   too), an EOS is not: with a live ``eos_id`` a lane that finished
        #   at N still rides N+1 and its token is dropped at apply. Exact
        #   for greedy dense and dropless-routed K/V lanes, whose stale row
        #   nobody reads; a recurrent state (``cfg.stateful``) would be
        #   advanced past its sequence's end, which ``slot_state`` shows. So
        #   a model that carries a state runs ahead where no EOS is live
        #   (``eos_id`` < 0: every end is a budget's) and serves in lockstep
        #   where one is;
        # - speculative decoding restructures the decode step: lockstep.
        # The model's halves of this are two columns of its cache's table
        # (``paged_decode.UNSERVED``: ``run_ahead``, ``run_ahead_eos``).
        self.dispatch_depth = int(dispatch_depth)
        if self.dispatch_depth < 1:
            raise ConfigError("dispatch_depth must be >= 1")
        if self.dispatch_depth > 2:
            raise ConfigError(
                "dispatch_depth > 2 is not supported: the serve loop keeps "
                "one step ahead of the device (deeper queues would admit "
                "tokens the host has never validated)")
        self._ahead = bool(
            self.dispatch_depth > 1 and self.temperature == 0.0
            and self.speculative_tokens == 0
            and unserved(cfg, "run_ahead" if self.eos_id < 0
                         else "run_ahead_eos") is None)
        # where a decode step is due and a slot is prefilling, ONE program
        # carries the step's lanes and the prompt's next chunk through one
        # pass over the weights (``paged_fused_step``; ``_step``): a greedy
        # server that prefills in chunks, on a model whose step takes no
        # operand beside the two programs' own (``fusable``). Speculation
        # restructures the decode step, and a sampling server's key would
        # have to split in the order the two steps ran: both alternate
        self._fuses = bool(
            self.prefill_chunk > 0 and self.temperature == 0.0
            and self.speculative_tokens == 0 and fusable(cfg))
        #: the one enqueued, not-yet-applied step (``_run_ahead``)
        self._pipeline: Optional[_InFlightStep] = None
        #: steps THIS server enqueued while another was still in flight
        #: (``m_ahead`` is registry-global) — unlike ``_pipeline`` (None
        #: while a step lands), a stable "did it engage" signal
        self._steps_ahead = 0

        #: first-seen jitted-step keys — a cold (kind, shape) compiles before
        #: it executes, so the deadline watchdog grants it the first-compile
        #: budget (cleared on rebuild, like the runner's seen-shape set)
        self._seen_steps: set[tuple] = set()
        #: verdict of the init-time parity probe (None: not run)
        self.kernel_parity: Optional[dict] = None
        if (self.decode_kernel == "paged" and kernel_parity_check
                and self.mesh is None):
            # one tiny golden batch through both kernels before the kernel
            # is trusted. A mismatch is a construction error — serving on
            # with gather would hide a kernel that is wrong on this device.
            # Under a mesh the gate is skipped — per-shard math is identical
            # and the tp parity suite covers it; the init-time check stays
            # local.
            # a program of its own: compiles and runs every kernel both ways
            with setup_stage("setup_probe"):
                self.kernel_parity = verdict = self._paged_kernel_parity()
            if not verdict["ok"]:
                raise ConfigError(
                    "paged decode kernel disagrees with the dense gather "
                    f"reference on this device ({verdict}); set "
                    "decode_kernel: gather to serve without it")
        self._build_jitted()

        # the shared serving-runner core: health state machine, step-deadline
        # watchdog, chaos hooks — the generate path inherits the PR-4/5
        # hardening instead of reimplementing it
        self.core = ServingRunnerCore(
            name=f"{name}[generate]",
            labels={"model": name, "path": "generate"},
            step_deadline_s=step_deadline_s,
            step_deadline_first_s=step_deadline_first_s,
            health_config=health_config,
            rebuild_fn=self._rebuild_after_incident,
        )

        reg = global_registry()
        self.m_steps = reg.counter("arkflow_gen_decode_steps_total", "lockstep decode steps")
        self.m_tokens = reg.counter("arkflow_gen_tokens_total", "tokens generated")
        # host arrays handed to the device for a step (``_build_jitted``):
        # 1 a step while every step's operands go up packed
        self.m_uploads = {
            kind: reg.counter("arkflow_gen_step_uploads_total", "host arrays "
                              "handed to a step", {"model": name, "kind": kind})
            for kind in ("decode", "chunk", "prefill", "verify", "fused")}
        # how often running ahead engages: beside the observations of
        # ``gen_device_wait`` (one a step) it is the share of steps that
        # found the device's queue occupied when they arrived
        self.m_ahead = {
            kind: reg.counter("arkflow_gen_steps_ahead_total", "steps "
                              "enqueued while another step of this server "
                              "was still in flight", {"model": name, "kind": kind})
            for kind in ("decode", "chunk", "prefill", "fused")}
        # a prompt's chunks by the step that carried them: a decode step
        # (one pass over the weights for both) or a step of their own
        self.m_chunks = {
            mode: reg.counter("arkflow_gen_chunks_total", "prefill chunks "
                              "issued, by the step that carried them",
                              {"model": name, "mode": mode})
            for mode in ("fused", "alone")}
        self.m_spec_drafted = reg.counter(
            "arkflow_gen_spec_drafted_total", "draft tokens offered for verification")
        self.m_spec_accepted = reg.counter(
            "arkflow_gen_spec_accepted_total", "draft tokens accepted")
        self.m_waiting = reg.gauge("arkflow_gen_waiting_requests", "admission queue depth")
        self.m_truncated = reg.counter(
            "arkflow_gen_truncated_total",
            "requests cut short by page-pool exhaustion (pool undersized)")
        self.m_prefix_hits = reg.counter(
            "arkflow_gen_prefix_cache_hits_total", "admissions that reused cached prefix pages")
        self.m_prefix_pages = reg.counter(
            "arkflow_gen_prefix_pages_shared_total", "pages aliased from the prefix cache")
        # observability satellites: the generation server used to be nearly
        # dark — these four answer "is the server keeping up" from /metrics
        self.m_slots_busy = reg.gauge(
            "arkflow_gen_slots_busy", "decode slots occupied (admitting + decoding)")
        self.m_pool_occupancy = reg.gauge(
            "arkflow_gen_page_pool_occupancy",
            "fraction of KV pages in use (scratch page excluded)")
        self.m_prefix_evictions = reg.counter(
            "arkflow_gen_prefix_cache_evictions_total",
            "prefix-cache entries evicted (LRU capacity or page pressure)")
        self.m_tps = reg.gauge(
            "arkflow_gen_tokens_per_sec",
            "windowed generation throughput (tokens/s over the serve loop)")
        # the dispatch-depth scoreboard (ROADMAP item 5): the same idle-gap
        # family the batch runner exports, labeled path=generate — depth 2
        # drives the p50 toward zero because step N+1 is already queued
        # when step N completes
        self.m_idle_gap = reg.histogram(
            "arkflow_tpu_device_idle_gap_seconds",
            "gap between step N completing and step N+1 launching "
            "(device idle between consecutive steps)",
            {"model": name, "path": "generate"})
        # time-to-first-token: the latency-bound regime's headline metric —
        # stamped once per request at its first decoded token (or at page
        # export on a prefill-role worker, where the first token ships with
        # the pages); adopted requests arrive already stamped upstream
        self.m_ttft = reg.histogram(
            "arkflow_gen_ttft_seconds",
            "submit-to-first-decoded-token latency per request",
            {"model": name})
        # the per-token stamp TTFT lacks: time between one request's
        # consecutive tokens (decode-step cadence as the caller feels it,
        # prefill chunks of other slots included)
        self.m_token_gap = reg.histogram(
            "arkflow_gen_token_gap_seconds",
            "gap between consecutive generated tokens of one request",
            {"model": name})
        reg.gauge("arkflow_gen_kv_bytes_per_token",
                  "bytes one cached token costs over all layers, as the page "
                  "pools hold it", {"model": name}).set(kv_bytes_per_token(cfg))
        # routed experts (dropless top-k): what each device step routed,
        # computed on the device inside the step and fetched with its
        # tokens — (token, expert) pairs, distinct experts hit (mean over
        # the expert layers) and the largest expert's load (over layers)
        self._moe_layers = cfg.expert_layers
        self.m_moe = {} if not self._moe_layers else {
            kind: (reg.counter("arkflow_gen_moe_assignments_total",
                               "(token, expert) pairs routed, summed over "
                               "expert layers", {"model": name, "kind": kind}),
                   reg.histogram("arkflow_gen_moe_experts_hit",
                                 "distinct experts hit a step, mean over "
                                 "expert layers", {"model": name, "kind": kind}),
                   reg.histogram("arkflow_gen_moe_max_load",
                                 "tokens routed to the busiest expert of a "
                                 "step, over expert layers",
                                 {"model": name, "kind": kind}))
            for kind in _MOE_KINDS}
        #: (gauge, what holds its rows: "window" / "pages" / "slots", bytes
        #: one of those holds) per pool, where there is more than one kind
        self.m_kv_live = [
            (reg.gauge("arkflow_gen_kv_live_bytes", "bytes of cached rows "
                       "live in a pool (pages held by slots or the prefix "
                       "cache; a state pool: busy slots)",
                       {"model": name, "pool": pool.name}),
             "slots" if pool.per_slot else "window" if pool.window else "pages",
             pool.bytes_per_slot or page_size * pool.bytes_per_token)
            for pool in cache_spec(cfg)] if self._layered or self._stateful else []
        # a recurrent state: what advanced it and what a step carried past
        # it, known on the host from lengths (no fetch), and how often a
        # slot was handed to a new tenant
        self.m_ssm = {} if not self._stateful else {
            kind: tuple(reg.counter(metric, text, {"model": name, "kind": kind})
                        for metric, text in (
                            ("arkflow_gen_ssm_tokens_total",
                             "valid tokens that advanced a recurrent state"),
                            ("arkflow_gen_ssm_masked_total",
                             "padded positions and idle lanes a step carried "
                             "past the states")))
            for kind in ("decode", "chunk")}
        # the attention kernel walks a row's kept pages up to its last query
        # and no further (ops/ragged_attention: paged_flash_attention, and
        # since PR 44 a latent model's mla_paged_attention): pages walked
        # beside the table's columns, a layer, from lengths on the host.
        # Their ratio is the live share of the table (a chunk's earlier
        # query tiles stop sooner than its last, which is counted). A
        # walk moves a whole aligned stretch of PAGE_RUN pages that sit
        # side by side in the pool as ONE copy (the latent kernel's since
        # PR 54, the per-head kernel's since PR 61): the walked pages of
        # such stretches are counted too, by the kernel's predicate over
        # the table rows the step carries, on a server whose kernel takes
        # runs — a latent model's, a per-head model's with a layer that
        # keeps every key at heads of 128 lanes' multiples (a window's ring
        # and the narrow-head walk take none and count none). Names and
        # texts: ``_WALK_COUNTERS``
        self.m_attn_walk = {} if self.decode_kernel != "paged" else {
            kind: tuple(reg.counter(metric, text, {"model": name, "kind": kind})
                        for metric, text in _WALK_COUNTERS)
            for kind in ("decode", "chunk")}
        self._walk_in_runs = cfg.latent or any(
            not (sp.window or sp.row_major)
            for sp in map(cfg.gqa, dict.fromkeys(cfg.attn_kinds)))
        # the (row, query tile) programs of the per-head kernel's calls, a
        # layer, by the product each makes: a K/V head at a time over that
        # head's own query rows, or all heads at once under a mask — the
        # kernel's own predicate on the step's shapes as one chip sees them
        # (a latent row has one shared head: nothing to cut, none counted) —
        # or, over a row-major pool, a 128-lane RUN of narrow heads at a time,
        # each head's queries zero-extended over its run (``head_run``: half
        # or more of such a contraction is zeros, so it is no per-head tile)
        self._tiles_of: dict[int, dict[str, int]] = {}
        self.m_attn_tiles = {
            (kind, product): reg.counter(
                "arkflow_gen_attn_tiles_total",
                "(row, query tile) programs of the attention kernel, summed "
                "over layers, by the product a tile makes",
                {"model": name, "kind": kind, "product": product})
            for kind in ({} if cfg.latent else self.m_attn_walk)
            for product in ("per_kv_head", "all_heads") + (
                ("head_run",) if any(cfg.gqa(k).row_major for k in cfg.attn_kinds)
                else ())}
        # a sink joins the softmax of every query of its kind's layers: the
        # rows (queries x layers of a kind with a sink) that went through
        # one, from lengths on the host (padding and idle lanes not counted)
        self._sink_layers = 0 if cfg.latent else sum(
            cfg.gqa(kind).sink for kind in cfg.kinds)
        self.m_sink_rows = {} if not self._sink_layers else {
            kind: reg.counter(
                "arkflow_gen_attn_sink_rows_total",
                "query rows (queries x layers with a sink) whose softmax "
                "had a sink logit beside its keys",
                {"model": name, "kind": kind})
            for kind in ("decode", "chunk")}
        self.m_ssm_resets = reg.counter(
            "arkflow_gen_ssm_state_resets_total",
            "slots whose recurrent state a prompt's first chunk reset",
            {"model": name})
        self.m_win_freed = reg.counter(
            "arkflow_gen_window_pages_freed_total",
            "window-pool pages freed because the window passed them "
            "(a finished request's pages are not counted)", {"model": name})
        #: per-server TTFT reservoir behind health_report() percentiles
        #: (m_ttft is registry-global and would mix servers in-process)
        self._ttft_samples: deque[float] = deque(maxlen=2048)
        self._ttft_count = 0
        #: device-step in-flight count + last-all-complete stamp behind the
        #: idle-gap histogram (mirrors the runner's _track_dispatch/_complete)
        self._gen_inflight = 0
        self._gen_idle_since: Optional[float] = None
        #: tokens emitted by THIS server (m_tokens is registry-global)
        self._tokens_emitted = 0
        self._rate_window: Optional[tuple[float, int]] = None

    # -- device plumbing (jit build / sharding / reset) --------------------

    def _serve_eva(self, name: str, num_pages, prefill_chunk: int) -> None:
        """A compacting window cache's geometry — its table's columns
        (``pages_per_slot``: by cached length, the summary pages of every
        window a slot can close and one whole window), a ConfigError by name
        where chunk, page or pool do not fit its windows — and its counters."""
        cfg, page = self.cfg, self.page_size
        w, c = cfg.window_size, cfg.chunk_size
        sp = cfg.gqa("full_attention")
        if w % prefill_chunk:
            raise ConfigError(
                "attention_class 'eva' prefills in chunks through the cache: set "
                f"prefill_chunk > 0 to a divisor of window_size {w} (a chunk "
                f"never straddles a window's end), got {prefill_chunk}")
        if w % page or (w // c) % page:
            raise ConfigError(
                f"attention_class 'eva': page_size {page} divides window_size "
                f"{w} and a closed window's {w // c} summary rows (whole pages "
                "turn into whole pages at a close)")
        if sp.key_parts > 1 or sp.split_heads:
            raise ConfigError(
                "attention_class 'eva' pools a window's rows whole: a key held "
                f"in parts or a head a pool layer (head_dim {sp.dk} on "
                f"{sp.kv_heads} K/V heads) is not served with it")
        self.pages_per_slot = eva_table_pages(cfg, page, self.max_seq)
        if num_pages and num_pages < 1 + self.slots * self.pages_per_slot:
            raise ConfigError(
                f"num_pages={num_pages}: a compacting window cache takes its "
                "pages step by step, so the pool holds every slot's worst case "
                f"({self.slots} slots x {self.pages_per_slot} pages + scratch)")
        reg = global_registry()
        self.m_eva_closes = {
            phase: reg.counter("arkflow_gen_eva_window_closes_total",
                               "windows closed (a slot's: every layer pools the "
                               "window's rows into summary rows, on the device), "
                               "by the step that wrote the window's last row",
                               {"model": name, "phase": phase})
            for phase in ("chunk", "decode")}
        self.m_eva_rows = {
            (phase, kind): reg.counter(
                "arkflow_gen_eva_rows_attended_total",
                "cache rows the queries of issued steps attended, a layer: exact "
                "rows of the open window, summary rows of closed windows",
                {"model": name, "kind": kind, "phase": phase})
            for phase in ("chunk", "decode") for kind in ("window", "summary")}
        # a name a kind: a sampler that sums a gauge over its labels keeps them apart
        self.m_eva_pages = {
            kind: reg.gauge(f"arkflow_gen_eva_live_pages_{kind}",
                            f"pages slots hold whose rows are {what}",
                            {"model": name})
            for kind, what in (("window", "the open window's exact rows"),
                               ("summary", "closed windows' summary rows"))}

    def _on_tpu(self) -> bool:
        """Backend check for the compiled Pallas path (the probe shared
        with the runner's auto-flash resolution)."""
        from arkflow_tpu.tpu.serving_core import on_tpu_backend

        devs = (list(self.mesh.devices.flat) if self.mesh is not None
                else None)
        return on_tpu_backend(devs)

    def _paged_kernel_parity(self) -> dict:
        """Logit-parity gate for the paged attention kernel: one tiny
        golden batch — prompts that cross a page boundary plus a
        single-token tail, on non-contiguous page tables — through prefill,
        then one decode step and one 2-token chunk with BOTH kernels,
        judged by ``logits_parity`` (bf16 tolerance; argmax must agree
        wherever the reference's top-2 margin decides it). Returns the
        worse of the two verdicts (a hybrid model's steps carry their states
        along, so the mixer's two kernels are held too). The steps are
        jitted with params as an argument, like the serving steps; one-time
        init cost."""
        from arkflow_tpu.models.paged_decode import paged_prefill_chunk
        from arkflow_tpu.tpu.serving_core import logits_parity

        if self.cfg.by_runs:
            # kernel by kernel on given routing (why: latent_kernel_probe);
            # the verdict is the worst kernel's
            from arkflow_tpu.models.paged_decode import (gqa_kernel_probe,
                                                         latent_kernel_probe)

            kernel_probe = (latent_kernel_probe if self.cfg.latent
                            else gqa_kernel_probe)

            # ONE program: op by op, the probes of a layer pattern's kernels
            # at published widths are hundreds of small compiles (minutes)
            names = []

            def probe(params):
                out = kernel_probe(params, self.cfg, self.page_size,
                                   self.kernel_interpret)
                names.extend(n for n, _, _ in out)
                return [(ref, got) for _, ref, got in out]

            pairs = jax.jit(probe)(self.params)
            verdicts = [{"kernel": name, **logits_parity(ref, got)}
                        for name, (ref, got) in zip(names, pairs)]
            worst = max(verdicts, key=lambda v: (not v["ok"],
                                                 v["max_abs_diff"] / v["tol"]))
            return {**worst, "kernels": [v["kernel"] for v in verdicts]}

        kernel_args = ("attention_kernel", "kernel_interpret")
        prefill = jax.jit(paged_prefill, static_argnums=1)
        decode = jax.jit(paged_decode_step, static_argnums=1,
                         static_argnames=("return_logits", *kernel_args))
        chunk = jax.jit(paged_prefill_chunk, static_argnums=1,
                        static_argnames=("return_all", *kernel_args))
        paged = dict(attention_kernel="paged",
                     kernel_interpret=self.kernel_interpret)

        page = self.page_size
        n0 = min(page + 1, self.max_seq)  # crosses a page boundary
        pages_per = -(-(n0 + 3) // page)  # room for prompt + decode + chunk
        kp, vp = init_page_pool(self.cfg, 1 + 2 * pages_per, page, slots=2)
        # a hybrid model's two rows hold slots 0 and 1: state rows 1 and 2
        held = {"ssm_rows": jnp.asarray([1, 2], jnp.int32)} if self._stateful else {}
        rng = np.random.RandomState(1234)
        ids = np.zeros((2, n0), np.int32)
        ids[0] = rng.randint(1, self.cfg.vocab_size, n0)
        ids[1, 0] = rng.randint(1, self.cfg.vocab_size)
        lens = jnp.asarray([n0, 1], jnp.int32)
        table = np.zeros((2, pages_per), np.int32)
        table[0] = np.arange(1, 2 * pages_per, 2)[::-1]  # non-contiguous
        table[1] = np.arange(2, 2 * pages_per + 1, 2)
        table = jnp.asarray(table)
        if self._stateful:  # prefills in chunks only: seed as it serves
            _, kp, vp = chunk(self.params, self.cfg, jnp.asarray(ids),
                              jnp.zeros_like(lens), lens, table, kp, vp, **held)
        else:
            _, kp, vp = prefill(
                self.params, self.cfg, jnp.asarray(ids), lens, table, kp, vp)
        tok = jnp.asarray(ids[:, 0])
        act = jnp.asarray([True, True])
        ref, *_ = decode(self.params, self.cfg, tok, lens, act, table, kp, vp,
                         return_logits=True)
        got, *_ = decode(self.params, self.cfg, tok, lens, act, table, kp, vp,
                         return_logits=True, **paged)
        # the tolerance is of the output head's own product: a model that
        # scales its logits (``lm_head_multiplier``) is judged before that
        parity = lambda a, b: logits_parity(  # noqa: E731
            a / self.cfg.lm_head_multiplier, b / self.cfg.lm_head_multiplier)
        verdict = parity(ref, got)
        if not verdict["ok"]:
            return verdict
        cids = jnp.asarray(rng.randint(1, self.cfg.vocab_size, (2, 2)),
                           jnp.int32)
        clen = jnp.asarray([2, 2], jnp.int32)
        ref, *_ = chunk(self.params, self.cfg, cids, lens, clen, table, kp, vp,
                        return_all=True, **held)
        got, *_ = chunk(self.params, self.cfg, cids, lens, clen, table, kp, vp,
                        return_all=True, **paged, **held)
        return parity(ref, got)

    def _init_pools(self):
        """Fresh KV page pools, placed with their tensor-parallel sharding
        under a mesh (KV heads over ``tp``; replicated otherwise)."""
        kp, vp = init_page_pool(self.cfg, self.num_pages, self.page_size,
                                self.num_win_pages, slots=self.slots)
        #: whose state each slot's row of the state pool holds — the
        #: tenant's (prompt, tokens), its own lists — which tenant of the
        #: slot that is, and the tenant's page list (its own too): set where
        #: a prompt's first chunk resets the row and KEPT when the tenant
        #: finishes (the row moves again only under the next tenant)
        self._state_tenant: list[tuple] = [(None, None, 0, ())] * self.slots
        if self._kv_io_sharding is not None:
            kp = jax.device_put(kp, self._kv_io_sharding)
            vp = jax.device_put(vp, self._kv_io_sharding)
        return kp, vp

    def _build_jitted(self) -> None:
        """(Re)build the jitted steps — four, and ``_fused`` on a server
        that lets a chunk ride a decode step (``_fuses``) —, each ``fn(params,
        packed, kp, vp, *device operands) -> (tokens, kp, vp, *key)``;
        ``packed`` is the step's ONE host array (``pack_operands``; a fused
        step's: the decode step's, then the chunk's). Such a server's decode
        steps all return ``slots + 1`` tokens — the last the prompt's, where
        a chunk rode and was its prompt's last; a routed model's, ONE array
        of ``_FusedLayout`` — so a step takes the step before's output
        whichever kind that was. Under a mesh every step carries explicit
        in/out shardings: the KV pools split over KV heads on ``tp``, the rest
        replicated: table gathers stay static-shaped, plain GSPMD collectives."""
        from arkflow_tpu.models.decoder import select_token
        from arkflow_tpu.models.paged_decode import (paged_fused_step,
                                                     paged_prefill_chunk)

        cfg = self.cfg
        kv = self._kv_io_sharding
        kern = dict(attention_kernel=self.decode_kernel,
                    kernel_interpret=self.kernel_interpret)
        pages, ring = self.pages_per_slot, self._win_cols
        state = int(self._stateful)
        # what else rides a step, on the device already: a sampling server's
        # key, the decode step before's output (a server that runs ahead),
        # a routed chunk's counters
        keyed = int(self._key is not None)
        piped = int(self._ahead)
        routed, fuses = int(cfg.routed), int(self._fuses)
        #: the one output layout of a server that fuses a routed model, fixed
        #: with the programs; every other server's steps return what they
        #: always did
        self._lay = lay = _FusedLayout(
            self.slots, 3 + len(self._extra_counters)) if fuses and routed else None

        def _pick(logits, keys, *behind):
            """The step's token array (``behind`` appended: a routed model's
            counters, so that one fetch brings both; the empty place of a
            prompt's token, ``_decode``) and the successor of the key in
            ``keys``, if any: split here, in the order the steps run."""
            sub = None
            if keys:
                key, sub = jax.random.split(keys[0])
                keys = (key,)
            nxt = select_token(logits, sub, self.temperature, self.top_k)
            return (jnp.concatenate([nxt, *behind]) if behind else nxt), *keys

        # params ride every step as an ARGUMENT (bound below): closed over,
        # they would be baked into each executable as constants — a copy of
        # the weights per compiled step, and gigabytes of literals to lower
        # at real widths. The KV pools donate: they are pure in->out state,
        # so XLA updates them in place instead of copying hundreds of MB per
        # decode step.
        def _decode(params, packed, kp, vp, *dev):
            tok, lens, act, table = unpack_operands(
                packed, self.slots, pages, ring, state)[:4]
            tok = tok[:, 0]
            if piped:  # a lane packed as -1 takes the step before's token
                prev, *dev = dev
                tok = jnp.where(tok < 0, prev[:self.slots], tok)
            logits, kp, vp, *stats = paged_decode_step(
                params, cfg, tok, lens, act != 0, table, kp, vp,
                return_logits=True, kv_sharding=kv, **kern)
            # a server that fuses: shaped as a fused step's output (no chunk rode)
            no_seed = [jnp.zeros(1, jnp.int32)] if fuses else []
            behind = lay.decode(*stats) if lay else (*stats, *no_seed)
            out, *key = _pick(logits, dev, *behind)
            return out, kp, vp, *key

        def _fused(params, packed, kp, vp, *dev):
            lanes = self.slots * (3 + pages + state)
            tok, lens, act, table = unpack_operands(
                packed[:lanes], self.slots, pages, state=state)[:4]
            # the chunk's held row of the state pool, as ``_chunk``'s
            ids, off, clen, its_table, *held = unpack_operands(
                packed[lanes:], 1, pages, state=state)
            tok = tok[:, 0]
            if piped:
                prev, *dev = dev
                tok = jnp.where(tok < 0, prev[:self.slots], tok)
            logits, kp, vp, *stats = paged_fused_step(
                params, cfg, tok, lens, act != 0, table, ids, off, clen,
                its_table, kp, vp, return_logits=True, kv_sharding=kv, **kern,
                ssm_rows=next(iter(held), None))
            dev, stats = (dev[1:], lay.fused(dev[0], *stats)) if lay else (dev, stats)
            out, *key = _pick(logits, dev, *stats)
            return out, kp, vp, *key

        def _prefill(params, packed, kp, vp, *key):
            ids, _, lens, table = unpack_operands(packed, 1, pages, ring)
            logits, kp, vp, *stats = paged_prefill(
                params, cfg, ids, lens, table, kp, vp, return_logits=True,
                kv_sharding=kv, **kern)
            out, *key = _pick(logits, key, *stats)
            return out, kp, vp, *key

        def _chunk(params, packed, kp, vp, *dev):
            ids, off, clen, table, *held = unpack_operands(
                packed, 1, pages, ring, state)
            logits, kp, vp, *stats = paged_prefill_chunk(
                params, cfg, ids, off, clen, table, kp, vp,
                kv_sharding=kv, **kern, ssm_rows=next(iter(held), None))
            if stats:
                # a routed model: the prompt's counters ride on the device
                # behind the chunk before's token (``_no_counts`` at first)
                so_far, *dev = dev
                stats = [_chunk_counts(lay.so_far(so_far) if lay else so_far, stats[0])]
            out, *key = _pick(logits, dev, *stats)
            return (lay.chunk(out) if lay else out), kp, vp, *key

        def _verify(params, packed, kp, vp):
            ids, lens, clen, table = unpack_operands(packed, self.slots, pages, ring)
            logits, kp, vp = paged_prefill_chunk(
                params, cfg, ids, lens, clen, table, kp, vp, return_all=True,
                kv_sharding=kv, **kern)[:3]
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), kp, vp

        def bind(fn, n_dev: int, n_key: int):
            """jit ``fn`` with the pools donated, bind the current params,
            and count what the host hands it."""
            kw = {}
            if self.mesh is not None:
                from arkflow_tpu.parallel.mesh import param_shardings

                r, kv = self._repl_sharding, self._kv_io_sharding
                kw = dict(
                    in_shardings=(param_shardings(self.params), r, kv, kv,
                                  *[r] * n_dev),
                    out_shardings=(r, kv, kv, *[r] * n_key))
            jitted = jax.jit(fn, donate_argnums=(2, 3), **kw)
            kind, params = fn.__name__[1:], self.params

            def step(packed, kp, vp, *dev):
                # the one place a step's operands are handed over: a host
                # array goes up inside the call, placed by ``in_shardings``
                self.m_uploads[kind].inc(sum(
                    not isinstance(x, jax.Array) for x in (packed, *dev)))
                return jitted(params, packed, kp, vp, *dev)

            step.jitted = jitted  # tests count its compiled programs
            return step

        self._decode = bind(_decode, piped + keyed, keyed)
        self._prefill = bind(_prefill, keyed, keyed)
        self._chunk = bind(_chunk, routed + keyed, keyed)
        self._verify = bind(_verify, 0, 0)
        self._fused = bind(_fused, piped + routed, 0) if fuses else None
        # the programs this configuration can reach: cold until each has run
        note_programs(fn.__name__ for fn, reached in (
            (_decode, True), (_chunk, True), (_fused, fuses),
            (_prefill, not (self._layered or self._stateful)),
            (_verify, self.speculative_tokens)) if reached)
        #: device stand-ins: no decode step in flight (shaped as one's
        #: output: tokens, then a routed model's counters), a first chunk
        zeros = functools.partial(jnp.zeros, dtype=jnp.int32,
                                  device=self._repl_sharding)
        counted = len(self._extra_counters)
        self._no_prev = (zeros(lay.size if lay else self.slots + fuses + routed * (
            3 + counted)),) if piped else ()
        self._no_counts = zeros(lay.size if lay else 5 + counted)

    def _note_grouped(self, kind: str, steps: int, rows: int) -> None:
        """Count the expert layers of ``steps`` device steps whose product ran
        grouped by expert (``ops/moe_grouped``): the step's row count decides,
        per program; none under ``decode_kernel: gather``, which runs no expert
        kernel. Registered at a model's first step, so it reads 0, not nothing,
        where every step is one token tile."""
        from arkflow_tpu.ops.moe_experts import runs_grouped

        if not rows:  # a part of a step: the step's product is counted once
            return
        grouped = self.decode_kernel == "paged" and runs_grouped(rows)
        global_registry().counter(
            "arkflow_gen_moe_grouped_products_total",
            "expert layers of device steps whose product ran grouped by expert "
            "(more rows than one token tile)",
            dict(self.m_moe[kind][0].labels),
        ).inc(steps * self._moe_layers if grouped else 0)

    def _note_moe(self, kind: str, stats, steps: int = 1, *, rows: int) -> None:
        """Record the routing counters (``moe_step_stats``, on the host) of one
        step of ``rows`` rows, or a prompt's ``steps`` chunks summed (each their mean)."""
        self._note_grouped(kind, steps, rows)
        pairs, hit, max_load = (int(v) for v in stats[:3])
        total, experts_hit, load = self.m_moe[kind]
        total.inc(pairs)
        for _ in range(steps):
            experts_hit.observe(hit / steps / self._moe_layers)
        load.observe(max_load)
        # a prompt's chunk count sits between the three and the rest
        rest = stats[4:] if kind == "chunk" else stats[3:]
        for counters, v in zip(self._extra_counters, rest):
            counters[kind].inc(int(v))

    def _rebuild_after_incident(self) -> None:
        """Core rebuild hook (runs inside the heal gate, before the recovery
        probe): executables cached across a hung step are not trusted —
        recompile everything from scratch under the first-compile budget."""
        self._seen_steps.clear()
        self._build_jitted()
        logger.warning("generation server rebuilt its jitted steps after a "
                       "deadline miss")

    def _reset_device_state(self) -> None:
        """Fresh pools + host page accounting after a crashed/abandoned step:
        a zombie step still owns the donated pool buffers, and the prefix
        cache's KV content died with them. Every future admission starts
        from a clean pool (leaked refs would wedge admission forever)."""
        self._pipeline = None  # a zombie step's tokens are never applied
        self._gen_inflight = 0
        self._prefix_cache.clear()
        self._cache_pages.clear()
        self._prefix_lengths.clear()
        self._page_refs.clear()
        self._free_pages = _FreePages(self.num_pages)
        self._win_free = list(range(1, self.num_win_pages))
        self._slot_win = [{} for _ in range(self.slots)]
        self.k_pages, self.v_pages = self._init_pools()

    # -- live hot-swap surface (tpu/swap.py) --------------------------------

    async def swap_params(self, placed, drain_timeout_s: float = 30.0):
        """Adopt a new (pre-placed) param tree with zero dropped requests.

        The four generation steps bind ``self.params`` at build time
        (``_build_jitted``), so a flip rebinds them. The sequence: pause
        admission, let the slot grid run dry (queued requests WAIT,
        they are never failed), flip params, rebuild the jits (the cleared
        ``_seen_steps`` grants the next step the first-compile budget), and
        reset the page pools + prefix cache — cached KV against new weights
        is a silent correctness bug. Returns the prior tree (the rollback
        token); raises ``SwapError`` (old params untouched, still serving)
        when the grid does not drain within ``drain_timeout_s``.
        """
        from arkflow_tpu.errors import SwapError

        self._draining = True
        try:
            deadline = time.monotonic() + drain_timeout_s
            while any(r is not None for r in self._slot_req):
                if time.monotonic() >= deadline:
                    raise SwapError(
                        f"slot grid did not drain within {drain_timeout_s:.3g}s "
                        f"({sum(1 for r in self._slot_req if r is not None)} "
                        "slots still busy); old params still serving")
                await asyncio.sleep(0.01)
            old, self.params = self.params, placed
            self._seen_steps.clear()
            self._build_jitted()
            self._reset_device_state()
            return old
        finally:
            self._draining = False

    # -- self-healing surface (fault plugin / engine /health) ---------------

    def inject_step_fault(self, kind: str, duration_s: float = 0.0) -> None:
        """Arm a one-shot ``hang``/``oom`` on the next device step (the fault
        plugin's processor wrapper drives this, same as for ModelRunner).
        ``bitflip`` corrupts a param leaf in place — the generation-tier SDC
        vector. ``sdc`` is rejected: decode picks tokens ON DEVICE (the
        logits never reach the host), so post-fetch output negation cannot
        model corruption honestly here; use ``bitflip`` instead."""
        if kind == "sdc":
            raise ConfigError(
                "chaos: 'sdc' is not supported on the generation server — "
                "decode argmax/sampling happens on device, so host-side "
                "output corruption would be a lie; arm 'bitflip' instead")
        if kind == "bitflip":
            self._bitflip_params()
            return
        self.core.inject_step_fault(kind, duration_s)

    def _bitflip_params(self) -> None:
        """Corrupt the largest float leaf of ``self.params`` in place. The
        generation steps bind params at build time, so the flip must also
        rebuild them (same sequence as ``swap_params``, minus the
        drain — arming and the serve loop share the event loop, and a
        corrupted tree mid-decode is exactly what real HBM corruption does).
        Nothing on the serving path notices by itself; only the integrity
        monitor's golden probe / digest verify can catch it."""
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
        self.params = jax.tree_util.tree_unflatten(treedef, leaves)
        self._seen_steps.clear()
        self._build_jitted()
        logger.warning("chaos: bitflip corrupted generation param leaf %s",
                       jax.tree_util.keystr(path))

    def health_report(self) -> dict:
        """JSON-able snapshot for the engine's ``/health``: health state +
        the serving detail that says whether the server is keeping up."""
        rep = self.core.health_report()
        rep["serving"] = "continuous"
        rep["decode_kernel"] = self.decode_kernel
        rep["dispatch_depth"] = self.dispatch_depth
        rep["runs_ahead"] = self._ahead
        rep["draining"] = self._draining
        rep["slots"] = self.slots
        rep["slots_busy"] = sum(1 for r in self._slot_req if r is not None)
        total = self.num_pages - 1
        rep["page_pool_occupancy"] = (
            round((total - len(self._free_pages)) / total, 4) if total else 0.0)
        rep["prefix_cache"] = {
            "entries": len(self._prefix_cache),
            "pages": self._cache_held,
            "capacity_pages": self.prefix_cache_pages,
        }
        rep["tokens_per_sec"] = round(float(self.m_tps.value), 1)
        if self._ttft_count:
            ordered = sorted(self._ttft_samples)

            def _pct(q: float) -> float:
                i = min(len(ordered) - 1, int(q * len(ordered)))
                return round(ordered[i] * 1000.0, 3)

            rep["ttft"] = {"count": self._ttft_count,
                           "p50_ms": _pct(0.50), "p99_ms": _pct(0.99)}
        if self.mesh is not None:
            from arkflow_tpu.parallel.mesh import tp_size

            rep["mesh"] = {"tp": tp_size(self.mesh)}
        return rep

    # -- gated device step --------------------------------------------------

    def _note_step(self, key: tuple) -> bool:
        """True when this (kind, shape) jitted step has not run yet — it will
        compile, so the watchdog grants the first-compile budget."""
        if key in self._seen_steps:
            return False
        self._seen_steps.add(key)
        return True

    def _track_gen_dispatch(self) -> None:
        """Device-idle-gap bookkeeping at step launch: an open idle window
        (no step in flight, or a drained device queue that ``_run_ahead``
        detected via ``is_ready``) closes here and records its gap."""
        if self._gen_idle_since is not None:
            self.m_idle_gap.observe(time.monotonic() - self._gen_idle_since)
            self._gen_idle_since = None
        self._gen_inflight += 1

    def _track_gen_complete(self) -> None:
        self._gen_inflight = max(0, self._gen_inflight - 1)
        # keep the EARLIER start when the drained-queue check already
        # opened the window (the device has been idle since then)
        if self._gen_inflight == 0 and self._gen_idle_since is None:
            self._gen_idle_since = time.monotonic()

    async def _run_device_step(self, key: tuple, packed, *dev,
                               final: bool = True):
        """One health-gated step of kind ``key[0]`` over its packed operands
        (and ``dev``, already on the device): the same admission gate pool
        dispatch uses, a first-compile-aware deadline watchdog, and the
        chaos hook. A deadline miss marks the server UNHEALTHY, schedules a
        rebuild, and raises — the serve loop fails every in-flight request,
        so their batches nack for redelivery; the next step waits out the
        probe backoff and runs as the recovery probe.

        Returns the step's token array ON THE HOST (copied inside the same
        executor hop). ``final=False``, a prompt's chunk before its last,
        leaves it on the device and a sampling server's key where it was."""
        core = self.core
        await core.heal_gate()
        cold = self._note_step(key)
        deadline = core.deadline_for(cold)
        keys = () if self._key is None else (self._key,)

        # pools bound EAGERLY: a deadline-abandoned zombie step waking after
        # a pool reset must consume the pools it already owned, never the
        # fresh ones. The jitted fn resolves LAZILY at call time: the probe
        # step must use the heal gate's rebuilt executable, not the cached one
        def blocking(hop, kp=self.k_pages, vp=self.v_pages):
            core.apply_chaos()
            # the hop's gen_device_wait, divided: the jitted call uploads
            # the packed array and enqueues; the wait is launch + the
            # device's step + this thread's wake; the fetch is the copy
            with hop.stage("gen_dispatch"):
                out = getattr(self, "_" + key[0])(packed, kp, vp, *dev, *keys)
            with hop.stage("gen_ready_wait"):
                if final:
                    out[0].copy_to_host_async()
                jax.block_until_ready(out)
            if not final:
                return out
            with hop.stage("gen_fetch"):
                tokens = np.asarray(out[0])
            return tokens, *out[1:]

        def first_call(hop):
            # the program's first call: trace, lower, compile or cache load,
            # first execution, on the thread that makes it
            with cold_step("_" + key[0]):
                return blocking(hop)

        self._track_gen_dispatch()
        tokens, self.k_pages, self.v_pages, *keys = await self._finish_step(
            key[0], first_call if cold else blocking, deadline)
        if final and keys:
            self._key = keys[0]
        return tokens

    async def _finish_step(self, kind: str, blocking, deadline,
                           earlier: Optional[_Hop] = None):
        """Run ``blocking``, the call that ends one step, on an executor
        thread under ``deadline`` (None: unwatched) and observe the step's
        ``gen_device_wait`` / ``gen_handoff`` and, by ``kind``, the stages
        inside the wait (plus those of ``earlier``, the hop that enqueued a
        pipelined step)."""
        core = self.core
        hop = _Hop(kind)
        try:
            if deadline is None:
                out = await asyncio.get_running_loop().run_in_executor(
                    None, hop.run, blocking)
            else:
                out = await core.run_deadlined(
                    functools.partial(hop.run, blocking), deadline)
        except StepDeadlineExceeded:
            raise  # the core already marked UNHEALTHY + scheduled rebuild
        except Exception as e:
            core.health.mark_unhealthy(f"generate step failed: {e}")
            raise
        finally:
            # an abandoned step counts complete: the device stopped doing
            # useful work, and the reset path rebuilds from fresh pools
            self._track_gen_complete()
        self._observe_hops(kind, *(() if earlier is None else (earlier,)),
                           hop.done())
        core.health.mark_success()
        return out

    @staticmethod
    def _observe_hops(kind: str, *hops: _Hop) -> None:
        """One device step's ``gen_device_wait`` and ``gen_handoff`` (one
        observation each, its hops summed) and, by ``kind``, the stages the
        hops were divided into."""
        observe_stage("gen_device_wait", sum(h.wait.dur_s for h in hops))
        observe_stage("gen_handoff", sum(h.handoff_s for h in hops))
        for h in hops:
            for stage, part in h.parts:
                observe_stage(stage, part.dur_s, kind=kind)

    # -- one step ahead of the device ---------------------------------------

    def _may_run_ahead(self, key: tuple) -> bool:
        """Whether the step ``key`` may be enqueued before the step in
        flight is waited for: where the configuration allows it at all
        (``_ahead``), and the moment does — its program is warm (a cold one
        compiles under the first-compile budget, with nothing queued before
        it), the core is HEALTHY (a probe step takes the gated path) and no
        hot swap is running the slot grid dry. Otherwise the step runs in
        lockstep: drain, then run to its end."""
        return (self._ahead and not self._draining
                and key in self._seen_steps
                and self.core.health.state == HEALTHY)

    async def _run_ahead(self, key: tuple, packed, dev, apply=None,
                         **riding):
        """Enqueue the step ``key`` (the jitted call only: upload + enqueue,
        stage ``gen_dispatch``) behind the step in flight, THEN wait for,
        fetch and apply that one. The pools chain on the device through
        donation; ``dev`` is what else the step takes there. The step stays
        in flight as ``_pipeline`` until the next step is enqueued behind it
        (or ``_drain_pipeline``). Returns its token array, on the device."""
        kind = key[0]
        pend = self._pipeline
        self._track_gen_dispatch()

        # pools bound eagerly (the zombie discipline of ``_run_device_step``)
        def enqueue(hop, kp=self.k_pages, vp=self.v_pages):
            with hop.stage("gen_dispatch"):
                out = getattr(self, "_" + kind)(packed, kp, vp, *dev)
            if apply is not None:
                out[0].copy_to_host_async()  # lands while the step still runs
            return out

        hop = _Hop(kind)
        try:
            out, self.k_pages, self.v_pages = (
                await asyncio.get_running_loop().run_in_executor(
                    None, hop.run, enqueue))
        except Exception as e:
            self.core.health.mark_unhealthy(f"generate step failed: {e}")
            raise
        rec = _InFlightStep(kind, out, hop.done(), time.monotonic(), apply,
                            **riding)
        self._pipeline = None
        if pend is not None:
            self._steps_ahead += 1
            self.m_ahead[kind].inc()
            await self._land(pend, rec)
            # honest idle accounting: one step is always nominally in
            # flight, so the count cannot see a drained device. If this
            # step's output is ALREADY computed, the device sits idle until
            # the next enqueue: open the idle window so the gap records
            if self._gen_idle_since is None:
                try:
                    drained = bool(rec.out.is_ready())
                except Exception:
                    drained = False
                if drained:
                    self._gen_idle_since = time.monotonic()
        self._pipeline = rec
        return out

    async def _land(self, rec: _InFlightStep,
                    behind: Optional[_InFlightStep] = None) -> None:
        """Wait for ``rec``, fetch its tokens and apply them, under its
        deadline counted from ITS dispatch. A prompt's chunk before its last
        (nothing of it is read on the host) is not waited for at all where
        the step ``behind`` it will be: that one cannot end before it, so its
        wait inherits the chunk's dispatch stamp and holds both deadlines."""
        if rec.apply is None and behind is not None and behind.apply is not None:
            behind.dispatched_at = rec.dispatched_at
            self._track_gen_complete()
            self._observe_hops(rec.kind, rec.hop)
            return
        core = self.core

        def blocking(hop):
            core.apply_chaos()
            with hop.stage("gen_ready_wait"):
                jax.block_until_ready(rec.out)
            if rec.apply is None:
                return None
            with hop.stage("gen_fetch"):
                return np.asarray(rec.out)

        deadline = core.deadline_for(False)  # a step that ran ahead is warm
        if deadline is not None:
            deadline = core.deadline_remaining(deadline, rec.dispatched_at)
        tokens = await self._finish_step(rec.kind, blocking, deadline, rec.hop)
        if rec.apply is not None:
            rec.apply(tokens)

    async def _drain_pipeline(self) -> None:
        """Land the step in flight, if any: whatever runs in lockstep (a
        cold or probe step, truncation under page pressure, a speculative
        step, an export, an adopted upload, the loop's exit) runs against
        caught-up host state and an empty device queue."""
        if self._pipeline is not None:
            rec, self._pipeline = self._pipeline, None
            await self._land(rec)

    # -- public API --------------------------------------------------------

    async def generate(self, prompt_ids: list[int],
                       max_new_tokens: int = 64) -> list[int]:
        """Submit one request; resolves with generated token ids (no EOS)."""
        if self._closed:
            raise ConfigError("generation server is closed")
        if len(prompt_ids) == 0:
            return []
        if len(prompt_ids) + max_new_tokens > self.max_seq:
            raise ConfigError(
                f"prompt({len(prompt_ids)}) + max_new({max_new_tokens}) exceeds "
                f"max_seq={self.max_seq}")
        return await self._submit(_Request(list(prompt_ids), max_new_tokens))

    def _submit(self, req: _Request) -> asyncio.Future:
        """Queue ``req`` for admission and make sure the serve loop runs."""
        req.future = asyncio.get_running_loop().create_future()
        req.submitted_at = time.perf_counter()
        req.scope = current_scope()
        self._pending.append(req)
        self.m_waiting.set(len(self._pending))
        if self._loop_task is None or self._loop_task.done():
            # an empty context: the loop outlives the request that starts it
            # and must not live inside that request's trace scope
            self._loop_task = asyncio.create_task(
                self._serve_loop(), context=contextvars.Context())
        return req.future

    async def prefill_export(self, prompt_ids: list[int],
                             max_new_tokens: int = 64) -> dict:
        """Disaggregated prefill: run (chunked) prefill for one prompt, then
        stop and resolve with a KV-page export instead of decoding — the
        prefill half of a prefill/decode role split.

        The export carries the prompt's KV pages as host numpy slabs, split
        one-per-tp-shard along the kv_heads axis so a host-mesh receiver can
        frame each shard separately, plus the first decoded token (prefill
        produces it for free). When generation is already complete at the
        first token (EOS, or ``max_new_tokens <= 1``) the export is marked
        ``done`` and ships no pages. Pages are unreffed (and donated to the
        prefix cache) locally once exported — the scratch pool recycles.
        """
        refuse(self.cfg, "kv_push", who="prefill_export (kv_push)")
        if self._closed:
            raise ConfigError("generation server is closed")
        if len(prompt_ids) == 0:
            return {"done": True, "tokens": [], "prompt": [],
                    "max_new_tokens": int(max_new_tokens)}
        if len(prompt_ids) + max_new_tokens > self.max_seq:
            raise ConfigError(
                f"prompt({len(prompt_ids)}) + max_new({max_new_tokens}) exceeds "
                f"max_seq={self.max_seq}")
        return await self._submit(
            _Request(list(prompt_ids), max_new_tokens, prefill_only=True))

    async def generate_from_pages(self, export: Mapping) -> list[int]:
        """Disaggregated decode: adopt a KV-page export produced by a
        prefill worker's :meth:`prefill_export` and decode to completion.

        Fresh pages are reserved from this server's pool and the slabs are
        uploaded through the same ``.at[pages].set`` path prefill writes
        through (re-sharded to the pool's kv io sharding under a mesh), so
        the paged kernel decodes from them with no relayout — the page
        table it is handed just points at the adopted pages. Returns the
        full token list including the shipped first token, exactly what
        :meth:`generate` would have returned locally."""
        refuse(self.cfg, "kv_push", who="generate_from_pages (kv_push)")
        if self._closed:
            raise ConfigError("generation server is closed")
        if export.get("done"):
            return [int(t) for t in export.get("tokens") or []]
        prompt = [int(t) for t in export["prompt"]]
        max_new = int(export["max_new_tokens"])
        if not prompt:
            return []
        if len(prompt) + max_new > self.max_seq:
            raise ConfigError(
                f"adopted prompt({len(prompt)}) + max_new({max_new}) exceeds "
                f"max_seq={self.max_seq}")
        if int(export["page_size"]) != self.page_size:
            raise ConfigError(
                f"adopted pages have page_size={export['page_size']}, "
                f"pool uses {self.page_size} (geometry must match end to end)")
        k_shards = export["k"]
        slab_shape = tuple(k_shards[0].shape)
        pool_shape = self._kv_geometry()
        kv_total = sum(int(s.shape[3]) for s in k_shards)
        expect = (pool_shape[0], self._pages_needed(len(prompt)),
                  pool_shape[2], pool_shape[3], pool_shape[4])
        if (slab_shape[0], slab_shape[1], slab_shape[2], kv_total,
                slab_shape[4]) != expect:
            raise ConfigError(
                f"adopted page slabs {slab_shape} x{len(k_shards)} shards do "
                f"not match pool geometry {pool_shape} for a "
                f"{len(prompt)}-token prompt")
        first = int(export["first_token"])
        if first == self.eos_id or max_new <= 1:
            # complete at the first token: nothing to decode, don't touch
            # the pool (mirrors _handle_token's EOS/budget handling)
            return [] if first == self.eos_id else [first]
        return await self._submit(_Request(
            prompt, max_new, tokens=[first], ttft_stamped=True,
            adopt=dict(export)))

    def _kv_geometry(self) -> tuple:
        """[layers, pages, page, kv heads, head width] of the K pool,
        however it holds a token's heads (row-major: side by side): the
        wire form of a page slab has the head axis either way."""
        shape = tuple(self.k_pages.shape)
        if len(shape) == 4:
            kvh = self.cfg.kv_heads
            shape = (*shape[:3], kvh, shape[3] // kvh)
        return shape

    async def close(self) -> None:
        self._closed = True
        if self._loop_task is not None:
            await self._loop_task

    # -- page accounting ---------------------------------------------------

    def _pages_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def _alloc_page(self, pages: list[int]) -> Optional[int]:
        """One fresh page (ref=1) for the next column of a slot that holds
        ``pages``, out of a block of neighbours where one is free
        (``_FreePages``); evicts LRU prefix entries under pressure."""
        while not self._free_pages:
            if not self._evict_one():
                return None
        p = self._free_pages.take(len(pages), pages[-1] if pages else None)
        self._page_refs[p] = 1
        return p

    def _ref_page(self, p: int) -> None:
        self._page_refs[p] += 1

    def _unref_page(self, p: int) -> None:
        self._page_refs[p] -= 1
        if self._page_refs[p] == 0:
            del self._page_refs[p]
            self._free_pages.give(p)

    @property
    def _cache_held(self) -> int:
        """Physical pages currently held by the prefix cache."""
        return len(self._cache_pages)

    def _evict_one(self) -> bool:
        if not self._prefix_cache:
            return False
        self.m_prefix_evictions.inc()
        key, pages = self._prefix_cache.popitem(last=False)  # LRU
        self._prefix_lengths[len(key)] -= 1
        if self._prefix_lengths[len(key)] == 0:
            del self._prefix_lengths[len(key)]
        for p in pages:
            self._cache_pages[p] -= 1
            if self._cache_pages[p] == 0:
                del self._cache_pages[p]
            self._unref_page(p)
        return True

    def _lookup_prefix(self, prompt: list[int]) -> Optional[tuple]:
        """Key of the longest cached full-page prefix (no side effects).
        At least one prompt token is always left to prefill (the last
        position's logits seed generation)."""
        if not self._prefix_cache:
            return None
        limit = ((len(prompt) - 1) // self.page_size) * self.page_size
        for length in sorted(self._prefix_lengths, reverse=True):
            if length > limit:
                continue
            key = tuple(prompt[:length])
            if key in self._prefix_cache:
                return key
        return None

    def _cache_prefix(self, req: _Request, pages: list[int]) -> None:
        """Donate the prompt's full pages to the cache (called at finish,
        before the slot's refs drop)."""
        if not self.prefix_cache_pages:
            return
        count = min(len(req.prompt) // self.page_size, len(pages))
        if count == 0:
            return
        key = tuple(req.prompt[:count * self.page_size])
        if key in self._prefix_cache:
            self._prefix_cache.move_to_end(key)
            return
        held = pages[:count]
        for p in held:
            self._ref_page(p)
            self._cache_pages[p] = self._cache_pages.get(p, 0) + 1
        self._prefix_cache[key] = list(held)
        self._prefix_lengths[len(key)] = self._prefix_lengths.get(len(key), 0) + 1
        while self._cache_held > self.prefix_cache_pages:
            if not self._evict_one():
                break

    def _evictable_pages(self, keep: Optional[tuple]) -> int:
        """DISTINCT pages the cache could free by evicting every entry
        other than ``keep``: pages whose refs all come from those entries
        (nested prefixes share pages — count physical pages once)."""
        keep_pages = set(self._prefix_cache.get(keep, ())) if keep is not None else set()
        counts: dict[int, int] = {}
        for key, pages in self._prefix_cache.items():
            if key == keep:
                continue
            for p in pages:
                counts[p] = counts.get(p, 0) + 1
        return sum(1 for p, c in counts.items()
                   if p not in keep_pages and self._page_refs.get(p) == c)

    def _try_reserve(self, req: _Request) -> Optional[tuple[list[int], int]]:
        """Reserve every page the request needs: aliased prefix pages plus
        fresh ones. Infeasible reservations return None WITHOUT side
        effects (no cache eviction, no metric counts) — a head-of-line
        stall must not wipe the cache's future savings."""
        n = len(req.prompt)
        # adopted page sets upload the FULL prompt KV: aliasing cached
        # prefix pages would scatter the upload into shared pages — fresh
        # pages only (the finished request still donates to the cache)
        key = None if req.adopt is not None else self._lookup_prefix(req.prompt)
        shared = list(self._prefix_cache[key]) if key is not None else []
        # a compacting window cache takes its pages step by step (its pool
        # holds every slot's worst case: ``_serve_eva``)
        fresh_needed = 0 if self._eva else self._pages_needed(n + 1) - len(shared)
        if len(self._free_pages) + self._evictable_pages(key) < fresh_needed:
            return None
        if key is not None:
            self._prefix_cache.move_to_end(key)
            for p in shared:
                self._ref_page(p)
        pages = list(shared)
        for _ in range(fresh_needed):
            p = self._alloc_page(pages)
            if p is None:  # shouldn't happen after the feasibility check
                for q in pages:
                    self._unref_page(q)
                return None
            pages.append(p)
        return pages, len(shared) * self.page_size

    # -- scheduler ---------------------------------------------------------

    def _table(self, *slots: int) -> np.ndarray:
        """Page table rows of ``slots`` (default: all), padded to slot width;
        with a window pool, the slot's ring of window pages follows (the
        page of logical index i in column ``i % columns``); with a state
        pool, the slot's row of it comes last."""
        slots = slots or range(self.slots)
        kept, ring = self.pages_per_slot, self._win_cols
        table = np.zeros((len(slots), kept + ring + self._stateful), np.int32)
        for row, s in enumerate(slots):
            table[row, :len(self._slot_pages[s])] = self._slot_pages[s]
            for i, p in self._slot_win[s].items():
                table[row, kept + i % ring] = p
            if self._stateful:  # slot s's state: row s + 1 (row 0 scratch)
                table[row, -1] = s + 1
        return table

    def slot_state(self, slot: int, latent: bool = False) -> dict:
        """What ``slot``'s row of the state pool holds, fetched from the
        device: ``prompt`` and ``tokens`` of the tenant whose first chunk
        reset the row last (None: never held), ``tenancy`` which tenant of
        the slot that is, ``state`` the row itself over the pool's layers
        (the hybrid block: [layers, heads, d_state, d_head] float32; conv
        layers: [conv layers, conv_L_cache - 1, dim], the last gated inputs
        oldest first; linear attention layers, either mixer's: [linear
        layers, value heads, key dim, value dim] float32, and under
        ``window`` the conv's last projected inputs [linear layers, taps - 1,
        channels], oldest first). A finished tenant's row stays as its last step left it —
        after its prompt and all but the last of its tokens — until the next
        tenant's first chunk. With ``latent``, beside latent pages: the rows
        the tenant's pages hold for the positions it fed, (latent rows
        [latent layers, positions, kv_lora_rank], shared keys [latent layers,
        positions, key lanes]) — the tenant's own only until another request
        takes a page it gave back, so right after it finished with nothing
        else in flight. Call between steps: a step in flight holds the
        donated pools."""
        if not self._stateful:
            raise ConfigError("slot_state: this model carries no recurrent state")
        prompt, tokens, tenancy, pages = self._state_tenant[slot]
        row = jnp.asarray(slot + 1, jnp.int32)  # an operand: one program
        pool = next(p.name for p in cache_spec(self.cfg) if p.per_slot)
        out = {"prompt": prompt, "tokens": tokens, "tenancy": tenancy,
               "state": jax.device_get(self.k_pages[pool][:, row])}
        if pool == "ssm":  # narrow heads ride side by side: a head a row here
            from arkflow_tpu.ops.ssm_scan import unpack_state

            out["state"] = np.asarray(unpack_state(
                out["state"], self.cfg.ssm_heads_packed))
        if self.cfg.linear:  # the pool's second array: the conv windows
            out["window"] = jax.device_get(self.v_pages[pool][:, row])
        if latent and self.cfg.latent and prompt is not None:
            fed = len(prompt) + len(tokens) - 1
            held = jnp.asarray(pages[:self._pages_needed(fed)], jnp.int32)
            out["latent"] = tuple(
                np.asarray(jax.device_get(rows[:, held])).reshape(
                    rows.shape[0], -1, rows.shape[-1])[:, :fed]
                for rows in (self.k_pages["latent"], self.v_pages["latent"]))
        return out

    def _slide_window(self, slot: int, first: int, last: int) -> None:
        """The slot's window pages for a step whose queries sit at positions
        ``first..last``: pages the window has passed (every token before
        ``first - (window - 1)``) go back to the pool, pages up to ``last``'s
        are taken. The pool holds a ring for every slot, so one is always
        free (``window_ring_pages``)."""
        if not self._win_cols:
            return
        live = self._slot_win[slot]
        oldest = max(first - (self.cfg.sliding_window - 1), 0) // self.page_size
        for i in [i for i in live if i < oldest]:
            self._win_free.append(live.pop(i))
            self.m_win_freed.inc()
        newest = last // self.page_size
        start = max(oldest, max(live, default=-1) + 1)
        for i in range(start, newest + 1):
            live[i] = self._win_free.pop()

    def _drop_window(self, slot: int) -> None:
        """A finished (or failed) slot's window pages, back to the pool."""
        self._win_free.extend(self._slot_win[slot].values())
        self._slot_win[slot] = {}

    def _bucket(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        return self.prompt_buckets[-1]

    async def _admit_one(self, slot: int, req: _Request,
                         pages: list[int], shared_len: int) -> None:
        """Seed the slot with its reserved pages and start prefill."""
        # register FIRST: if anything below throws, the loop's crash handler
        # fails this future instead of leaving its caller hanging
        self._slot_req[slot] = req
        n = len(req.prompt)
        self._slot_pages[slot] = pages
        if req.adopt is not None:
            await self._admit_adopted(slot, req)
            return
        req.shared_tokens = shared_len
        if shared_len > 0:
            self.m_prefix_hits.inc()
            self.m_prefix_pages.inc(shared_len // self.page_size)
        if (shared_len > 0 or self._layered or self._stateful or self._eva
                or (self.prefill_chunk and n > self.prefill_chunk)):
            # cooperative admission: the serve loop interleaves prefill
            # steps with decode; the slot joins decode once fully prefilled.
            # A cached prefix starts prefill at its boundary — only the
            # remainder is ever computed.
            self._prefill_pos[slot] = shared_len
            return
        # one bucketed step over the whole prompt, now: while it is in
        # flight the slot counts as prefilling (it decodes from its token)
        self._prefill_pos[slot] = 0
        await self._prefill_step(slot, "prefill")

    async def _admit_adopted(self, slot: int, req: _Request) -> None:
        """Seed the slot from a received KV-page export: upload the slabs
        into this pool's reserved pages and join decode directly — no
        prefill compute. The first token rode in with the pages."""
        exp = req.adopt
        n = len(req.prompt)
        pages = self._slot_pages[slot]
        idx = np.asarray(pages[: self._pages_needed(n)], np.int32)
        k_slab = np.concatenate([np.asarray(s) for s in exp["k"]], axis=3)
        v_slab = np.concatenate([np.asarray(s) for s in exp["v"]], axis=3)

        def upload(kp=self.k_pages, vp=self.v_pages):
            k = jnp.asarray(k_slab).astype(kp.dtype)
            v = jnp.asarray(v_slab).astype(vp.dtype)
            if kp.ndim == 4:  # row-major pools: a token's heads side by side
                k, v = (a.reshape(*a.shape[:3], -1) for a in (k, v))
            kp = kp.at[:, jnp.asarray(idx)].set(k)
            vp = vp.at[:, jnp.asarray(idx)].set(v)
            if self._kv_io_sharding is not None:
                kp = jax.device_put(kp, self._kv_io_sharding)
                vp = jax.device_put(vp, self._kv_io_sharding)
            return jax.block_until_ready(kp), jax.block_until_ready(vp)

        self.k_pages, self.v_pages = (
            await asyncio.get_running_loop().run_in_executor(None, upload))
        # drop the heavy slabs now that they're on device
        req.adopt = None
        self._lengths[slot] = n
        self._cur_tokens[slot] = int(exp["first_token"])
        # the first token is pre-seeded in req.tokens (counted on the
        # prefill side); the slot decodes from position n next step, and
        # its token gaps and gen_decode span run from here
        req.first_token_at = req.last_token_at = time.perf_counter()

    def _stamp_ttft(self, req: _Request, now: float) -> bool:
        """First decoded token for this request: record TTFT and the
        ``gen_prefill`` span exactly once (EOS-as-first-token still counts —
        the model answered). True when this was that token."""
        if req.ttft_stamped or req.submitted_at <= 0.0:
            return False
        req.ttft_stamped = True
        dt = now - req.submitted_at
        self.m_ttft.observe(dt)
        self._ttft_samples.append(dt)
        self._ttft_count += 1
        req.first_token_at = req.last_token_at = now
        record_stage("gen_prefill", now - req.slot_at, scope=req.scope,
                     start_mono=req.slot_at,
                     attrs={"prompt_tokens": len(req.prompt),
                            "chunks": req.chunks,
                            "shared_tokens": req.shared_tokens})
        return True

    def _handle_token(self, slot: int, token: int) -> None:
        """Record one generated token; completes the request on EOS/limit."""
        req = self._slot_req[slot]
        if req is None:
            return
        now = time.perf_counter()  # the one clock read a token costs
        first = self._stamp_ttft(req, now)
        if token == self.eos_id:
            self._finish(slot)
            return
        req.tokens.append(token)
        self.m_tokens.inc()
        self._tokens_emitted += 1
        if not first:
            self.m_token_gap.observe(now - req.last_token_at)
        req.last_token_at = now
        if len(req.tokens) >= req.max_new_tokens:
            self._finish(slot)

    def _finish(self, slot: int) -> None:
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        fully_prefilled = slot not in self._prefill_pos
        self._prefill_pos.pop(slot, None)
        if req is not None and fully_prefilled:
            # donate the prompt's full pages before the slot's refs drop
            self._cache_prefix(req, self._slot_pages[slot])
        for p in self._slot_pages[slot]:
            self._unref_page(p)
        self._slot_pages[slot] = []
        self._drop_window(slot)
        self._lengths[slot] = 0
        self._cur_tokens[slot] = 0
        if req is not None and not req.future.done():
            if req.first_token_at and not req.prefill_only:
                record_stage("gen_decode",
                             req.last_token_at - req.first_token_at,
                             scope=req.scope, start_mono=req.first_token_at,
                             attrs={"new_tokens": len(req.tokens)})
            req.future.set_result(
                req.tokens if req.export is None else req.export)

    async def _prefill_step(self, slot: int, kind: str = "chunk") -> None:
        """One prefill step for an admitting slot (one device call): a chunk
        of its prompt, or (``kind`` "prefill") all of it in one bucketed
        step; seeds the slot for decode after the prompt's last token.
        Enqueued behind the step in flight where ``_may_run_ahead`` allows
        (a chunk's operands depend on no other step's tokens); a prompt
        that stops after prefill exports its pages with nothing in flight."""
        req = self._slot_req[slot]
        if req is None:
            self._prefill_pos.pop(slot, None)
            return
        off, c, new_off, final = self._next_span(slot, kind)
        ahead = (self._may_run_ahead((kind, c))
                 and not (final and req.prefill_only))
        if not ahead:
            await self._drain_pipeline()
        with loop_stage("gen_prepare", kind):
            packed, so_far = self._span_operands(slot, req, kind, off, new_off, c)
        if kind == "chunk":
            self.m_chunks["alone"].inc()
        seed = (functools.partial(self._apply_prefill, slot, req, kind)
                if final else None)
        if ahead:
            out = await self._run_ahead((kind, c), packed, so_far, seed,
                                        slot=slot)
            if not final:  # nothing of it is read: its books close here
                self._apply_chunk(slot, req, new_off, out)
            return
        # off-loop + gated; only the prompt's last step's token is fetched
        nxt = await self._run_device_step(
            (kind, c), packed, *so_far, final=final)
        if not final:
            self._apply_chunk(slot, req, new_off, nxt)
        elif seed(nxt):
            await self._export_and_finish(slot)

    def _next_span(self, slot: int, kind: str = "chunk") -> tuple:
        """The slot's next prefill step: (its prompt's offset, the step's
        width — the configured chunk, or one bucketed span over the rest:
        one-shot, a prefix-cache remainder with chunking off —, the offset
        after it, whether it is the prompt's last)."""
        off = self._prefill_pos.get(slot, 0)
        n = len(self._slot_req[slot].prompt)
        c = (self.prefill_chunk if kind == "chunk" and self.prefill_chunk
             else self._bucket(n - off))
        return off, c, min(off + c, n), off + c >= n

    def _eva_account(self, phase: str, slot: int, first: int, last: int) -> None:
        """The books of a step that writes positions ``first..last`` of ``slot``
        (inside one window), behind the table it carries: the rows its queries
        attend and, where it writes the window's last row, the close — every
        page of the window but the first ``window / chunk / page``, which now
        hold its summaries, goes back to the pool (the device reads them in
        this step; whoever takes them next writes in a later one)."""
        cfg = self.cfg
        w, per = cfg.window_size, cfg.window_size // cfg.chunk_size
        n = last - first + 1
        self.m_eva_rows[phase, "summary"].inc(n * (first // w) * per)
        self.m_eva_rows[phase, "window"].inc(n * (first % w + 1) + n * (n - 1) // 2)
        if (last + 1) % w:
            return
        self.m_eva_closes[phase].inc()
        pages = self._slot_pages[slot]
        keep = (last // w + 1) * (per // self.page_size)
        for p in pages[keep:]:
            self._unref_page(p)
        del pages[keep:]

    def _span_operands(self, slot: int, req: _Request, kind: str, off: int,
                       new_off: int, c: int) -> tuple:
        """A prefill step's packed operands (ids, offset, tokens present, the
        slot's table row) and what it takes on the device (a routed prompt's
        counters so far), its window pages slid and its counters counted."""
        chunk = req.prompt[off:new_off]
        ids = np.zeros(c, np.int32)
        ids[:len(chunk)] = chunk
        self._slide_window(slot, off, new_off - 1)
        if self._eva:
            self._ensure_page_capacity(slot, new_off)
        table = self._table(slot)
        packed = pack_operands(ids, off, len(chunk), table)
        if self._eva:  # behind the table the step carries: a close's frees
            self._eva_account("chunk", slot, off, new_off - 1)
        if kind == "chunk":
            self._note_walk("chunk", np.asarray([off + c - 1]), len(chunk), c,
                            table=table)
        if self._stateful:
            valid, masked = self.m_ssm["chunk"]
            valid.inc(len(chunk))
            masked.inc(c - len(chunk))
            if off == 0:  # the chunk's program starts from a zero state
                self.m_ssm_resets.inc()
                self._state_tenant[slot] = (
                    req.prompt, req.tokens, self._state_tenant[slot][2] + 1,
                    self._slot_pages[slot])
        so_far = () if kind != "chunk" or not self._moe_layers else (
            self._no_counts if req.chunk_moe is None else req.chunk_moe,)
        return packed, so_far

    def _apply_chunk(self, slot: int, req: _Request, new_off: int, out) -> None:
        """A prompt's chunk before its last: the slot's next offset; its
        output stays on the device (a routed model's counters ride it)."""
        with loop_stage("gen_apply", "chunk"):
            self._advance_prompt(slot, req, new_off, out)

    def _advance_prompt(self, slot: int, req: _Request, new_off: int, out) -> None:
        req.chunks += 1
        self._prefill_pos[slot] = new_off
        req.chunk_moe = out

    def _apply_prefill(self, slot: int, req: _Request, kind: str, nxt) -> bool:
        """A prompt's last prefill step, fetched (its token, then a routed
        model's counters): the one fetch seeds the slot for decode. True
        where the request stops here and its pages are to be exported."""
        with loop_stage("gen_apply", kind):
            if kind == "chunk" and self._lay is not None:
                nxt = nxt[self.slots:]  # the prompt's place (``_FusedLayout``)
            return self._seed_slot(slot, req, kind, nxt)

    def _span_rows(self, kind: str, left: int) -> int:
        """Rows of the prefill steps of a prompt whose LAST step had ``left``
        tokens to go: the configured chunk, or the one bucketed span."""
        return (self.prefill_chunk if kind == "chunk" and self.prefill_chunk
                else self._bucket(left))

    def _seed_slot(self, slot: int, req: _Request, kind: str, nxt) -> bool:
        req.chunks += 1
        left = len(req.prompt) - self._prefill_pos.pop(slot, 0)
        if self._moe_layers:
            self._note_moe(kind, nxt[1:], int(nxt[4]) if kind == "chunk" else 1,
                           rows=self._span_rows(kind, left))
        self._lengths[slot] = len(req.prompt)
        self._cur_tokens[slot] = int(nxt[0])
        if req.prefill_only:
            return True
        self._handle_token(slot, int(nxt[0]))
        return False

    async def _export_and_finish(self, slot: int) -> None:
        """Prefill-only completion: fetch the prompt's KV pages to host,
        attach the export to the request, and finish the slot (which still
        donates the prompt pages to the prefix cache — repeat prefixes on
        this prefill worker skip their shared span like any local request).

        Only the pages covering prompt positions ``0..n-1`` ship: the page
        holding position ``n`` (where the first decode step writes) may be
        prefix-shared or unwritten, and the receiver allocates it fresh."""
        req = self._slot_req[slot]
        if req is None:
            return
        n = len(req.prompt)
        first = int(self._cur_tokens[slot])
        self._stamp_ttft(req, time.perf_counter())
        done = first == self.eos_id or req.max_new_tokens <= 1
        if not done:
            req.tokens.append(first)
            self.m_tokens.inc()
            self._tokens_emitted += 1
            pages = self._slot_pages[slot][: self._pages_needed(n)]
            idx = jnp.asarray(np.asarray(pages, np.int32))
            shards = 1
            if self.mesh is not None:
                from arkflow_tpu.parallel.mesh import tp_size

                shards = tp_size(self.mesh)

            heads = self._kv_geometry()[3]

            def fetch(kp=self.k_pages, vp=self.v_pages):
                # the wire form has a head axis, however the pools hold it
                return tuple(a.reshape(*a.shape[:3], heads, -1) for a in (
                    np.asarray(jax.device_get(kp[:, idx])),
                    np.asarray(jax.device_get(vp[:, idx]))))

            k_slab, v_slab = (
                await asyncio.get_running_loop().run_in_executor(None, fetch))
            req.export = {
                "prompt": list(req.prompt),
                "max_new_tokens": int(req.max_new_tokens),
                "first_token": first,
                "page_size": int(self.page_size),
                "shards": int(shards),
                "dtype": str(k_slab.dtype),
                "tokens": [first],
                # shard-per-frame along kv_heads (axis 3): each entry is
                # exactly one tp shard's slab, framed separately on the wire
                "k": np.split(k_slab, shards, axis=3),
                "v": np.split(v_slab, shards, axis=3),
            }
        else:
            req.export = {
                "done": True,
                "prompt": list(req.prompt),
                "max_new_tokens": int(req.max_new_tokens),
                "first_token": first,
                "tokens": [] if first == self.eos_id else [first],
            }
            if first != self.eos_id:
                self.m_tokens.inc()
                self._tokens_emitted += 1
        self._finish(slot)

    def _ensure_page_capacity(self, slot: int, total: Optional[int] = None) -> bool:
        """Grow the slot's page list to cover positions < ``total``
        (default: the next write position, lengths+1)."""
        if total is None:
            total = int(self._lengths[slot]) + 1
        if self._eva:  # the rows that hold positions < total
            total = int(eva_rows(self.cfg, total - 1)) + 1
        need = self._pages_needed(total)
        while len(self._slot_pages[slot]) < need:
            p = self._alloc_page(self._slot_pages[slot])
            if p is None:
                return False
            self._slot_pages[slot].append(p)
        return True

    def _reserve_or_truncate(self, s: int, act: np.ndarray) -> None:
        """Ensure slot ``s`` can write its next position; when the pool is
        dry, finish the longest active sequence (its tokens so far are its
        result) and RETRY, so the starved slot never scatters into the
        scratch page and silently corrupts its context."""
        while act[s] and not self._ensure_page_capacity(s):
            candidates = [i for i in range(self.slots)
                          if act[i] and self._slot_req[i] is not None]
            if not candidates:
                break
            longest = max(candidates, key=lambda i: int(self._lengths[i]))
            req = self._slot_req[longest]
            logger.warning(
                "page pool exhausted: truncating slot %d at %d tokens "
                "(%d/%d generated) — size num_pages for the workload",
                longest, int(self._lengths[longest]),
                len(req.tokens) if req else 0,
                req.max_new_tokens if req else 0)
            self.m_truncated.inc()
            self._finish(longest)
            act[longest] = False

    def _eva_gauges(self) -> None:
        """Pages held by slots, by kind: a slot's first columns are its summary
        pages (from where its next write sits), the rest its open window's."""
        cfg = self.cfg
        per = cfg.window_size // cfg.chunk_size // self.page_size
        summary = window = 0
        for s, pages in enumerate(self._slot_pages):
            if not pages:
                continue
            at = self._prefill_pos.get(s, int(self._lengths[s]))
            held = min(at // cfg.window_size * per, len(pages))
            summary += held
            window += len(pages) - held
        self.m_eva_pages["summary"].set(summary)
        self.m_eva_pages["window"].set(window)

    def _update_gauges(self, busy: int) -> None:
        self.m_slots_busy.set(busy)
        self.m_waiting.set(len(self._pending))
        total = self.num_pages - 1
        if total:
            self.m_pool_occupancy.set((total - len(self._free_pages)) / total)
        if self._eva:
            self._eva_gauges()
        for gauge, holder, unit_bytes in self.m_kv_live:
            held = {"window": self.num_win_pages - 1 - len(self._win_free),
                    "pages": total - len(self._free_pages),
                    "slots": busy}[holder]
            gauge.set(held * unit_bytes)
        # windowed tokens/sec: cheap enough to refresh every loop pass
        now = time.monotonic()
        if self._rate_window is None:
            self._rate_window = (now, self._tokens_emitted)
            return
        t0, tok0 = self._rate_window
        if now - t0 >= 0.25:
            self.m_tps.set((self._tokens_emitted - tok0) / (now - t0))
            self._rate_window = (now, self._tokens_emitted)

    async def _serve_loop(self) -> None:
        """Pick the next step from host state as it will stand once the step
        in flight is applied, for everything the host already knows, and
        hand it to the device before that one is waited for (the step
        methods; ``_run_ahead``).

        What a step carries: lanes decoding and no slot prefilling, a decode
        step; a slot prefilling and no lane decoding (the first fill), a
        chunk of the prompt admitted first; both, ONE fused step — the
        lanes and that prompt's next chunk through one pass over the weights
        — where the server fuses (``_fuses``: greedy, chunked prefill, a
        model that ``paged_decode.fusable`` admits: per-head K/V with a dense
        MLP or with routed experts, conv layers among its attention layers or
        none, or plain latent attention with routed experts), and else a
        chunk and a decode step in turn. A
        prompt that stops after prefill keeps its last chunk's own step, a
        one-shot prefill and a speculative step their own too."""
        try:
            while not self._closed:
                admitted = await self._admit_pending()
                # a prompt's last prefill step in flight: its slot has no
                # further chunk and decodes only once its token is applied
                pend = self._pipeline
                seeding = -1 if pend is None else pend.seeding
                # first admitted, first prefilled: by slot index a long
                # prompt in a high slot would wait out every later admission
                # to a lower one (and, outputs being written in read order,
                # hold their rows back with it)
                prefilling = sorted(
                    (s for s in range(self.slots)
                     if s in self._prefill_pos and self._slot_req[s]
                     and s != seeding),
                    key=lambda s: self._slot_req[s].slot_at)
                active = [s for s in range(self.slots)
                          if self._slot_req[s] and s not in self._prefill_pos]
                self._update_gauges(
                    len(active) + len(prefilling) + (seeding >= 0))
                if not active and not prefilling:
                    # nothing to enqueue behind the step in flight: land it
                    # (a seeding slot decodes from here; a step can outlive
                    # its lanes, every one of them finished by the step
                    # applied after it was dispatched) before idling or
                    # exiting, or it would leak in-flight accounting
                    if pend is not None:
                        await self._drain_pipeline()
                        continue
                    if not self._pending:
                        return  # drained; next generate() restarts the loop
                    if not admitted:
                        await asyncio.sleep(0.01)  # waiting on pages
                    continue
                # a decode step is due and a slot is prefilling: the
                # prompt's next chunk rides the step, one pass over the
                # weights for both (``_fuses``)
                if (self._fuses and active and prefilling
                        and self._chunk_rides(prefilling[0])):
                    await self._step(active, prefilling[0])
                    continue
                # else interleave under contention: alternate one prefill
                # chunk with one decode step so neither starves the other
                if prefilling and (not active or self._turn_prefill):
                    self._turn_prefill = False
                    await self._prefill_step(prefilling[0])
                    continue
                self._turn_prefill = True
                if self.speculative_tokens > 0:
                    await self._step_speculative(active)
                else:
                    await self._step(active)
            # closed with work in flight: fail it rather than hang awaiters
            self._fail_all(ConfigError("generation server closed"))
        except Exception as e:  # fail all in-flight requests, don't hang them
            logger.exception("generation serve loop failed")
            self._fail_all(e)
            # a crashed/abandoned step leaves the pools untrustworthy (a
            # deadline-missed zombie still owns the donated buffers): start
            # the next admission from fresh pools and a clean page ledger
            self._reset_device_state()

    def _fail_all(self, err: Exception) -> None:
        # both steps in flight (the one not yet applied and the successor
        # enqueued behind it) die with their requests: their tokens are
        # never applied, and the reset below rebuilds from fresh pools
        self._pipeline = None
        self._gen_inflight = 0
        self._prefill_pos.clear()
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is not None and not req.future.done():
                req.future.set_exception(err)
            self._slot_req[s] = None
            # return the slot's pages: a crash must not shrink the pool
            # (leaked refs would eventually wedge every future admission)
            for p in self._slot_pages[s]:
                self._unref_page(p)
            self._slot_pages[s] = []
            self._drop_window(s)
            self._lengths[s] = 0
            self._cur_tokens[s] = 0
        while self._pending:
            req = self._pending.popleft()
            if not req.future.done():
                req.future.set_exception(err)

    async def _admit_pending(self) -> bool:
        if self._draining:  # hot-swap in progress: let the slot grid run dry
            return False
        admitted = False
        for slot in range(self.slots):
            if self._slot_req[slot] is not None or not self._pending:
                continue
            req = self._pending[0]  # peek
            with loop_stage("gen_admit"):
                reserved = self._try_reserve(req)
                if reserved is not None:
                    self._pending.popleft()
                    req.slot_at = time.perf_counter()
                    record_stage("gen_queue_wait",
                                 req.slot_at - req.submitted_at,
                                 scope=req.scope, start_mono=req.submitted_at)
            if reserved is None:
                break  # head-of-line waits for pages (FIFO fairness)
            pages, shared_len = reserved
            # an adopted page set is a device call of its own (an upload
            # into the pools): it runs against a drained queue. Any other
            # admission is host bookkeeping; its first step drains by itself
            # where its program is cold (``_may_run_ahead``)
            if req.adopt is not None:
                await self._drain_pipeline()
            await self._admit_one(slot, req, pages, shared_len)
            admitted = True
        return admitted

    def _chunk_rides(self, slot: int) -> bool:
        """Whether the slot's next chunk may ride a decode step: all but
        the last chunk of a prompt that stops after prefill (its pages are
        exported off a drained queue right behind its own step)."""
        return not (self._slot_req[slot].prefill_only
                    and self._next_span(slot)[3])

    async def _step(self, active: list[int], riding: int = -1) -> None:
        """One decode step over all slots (inactive lanes masked), enqueued
        behind the step in flight where ``_may_run_ahead`` allows and the
        page pool covers every riding lane, else in lockstep (drain, then
        run to the end: ``_reserve_or_truncate`` owns the truncation).

        ``riding`` >= 0 (a server that fuses): the step carries the next
        chunk of that slot's prompt too — ONE program, one packed array (the
        decode step's operands, then the chunk's), ``slots + 1`` tokens back.
        In flight it is a decode step to the step behind it (its lanes
        ride) and, where the chunk was its prompt's last, a seeding slot to
        the loop.

        Behind a decode step its lanes ride at lengths + 1 and take their
        tokens from its output ON the device (packed as -1), so the queue
        holds the successor before the host fetches. What the host knows a
        step early it acts on: a lane whose budget the step in flight
        exhausts is masked out (its recurrent state, if the model carries
        one, stays as its last token left it). An EOS it cannot know: such a
        lane rides and its token is dropped at apply (request identity is
        snapshotted); where that is not exact — a state behind a live
        ``eos_id`` — the server never runs ahead (``_ahead``)."""
        key, span = ("decode",), None
        if riding >= 0:
            span = self._next_span(riding)
            key = ("fused", span[1])
        ahead = self._may_run_ahead(key)
        prepared = self._prepare_decode(active, True) if ahead else None
        if prepared is None:  # lockstep (under page pressure: it owns truncation)
            ahead = False
            await self._drain_pipeline()
            # the drain may have APPLIED a step whose tokens finished
            # requests in `active` (slot freed, pages returned): recompute,
            # or _reserve_or_truncate would feed a ghost lane — a page the
            # next admission leaks, or a live request truncated for no one
            active = [s for s in active if self._slot_req[s] is not None]
            prepared = self._prepare_decode(active, False)
        act, packed, prev, prep_s = prepared
        if packed is None:
            # no lane rides (every one finishes on the step in flight):
            # land it and let the loop re-evaluate (admission / drain / exit)
            await self._drain_pipeline()
            return
        req, seeds, rode = None, None, 0
        if span is not None:
            off, rode, new_off, final = span
            req = self._slot_req[riding]
            with annotated("gen_prepare:chunk") as prep:
                # a routed prompt's counters so far ride in beside the step
                # before's output
                its, so_far = self._span_operands(riding, req, "chunk", off,
                                                  new_off, rode)
                packed, prev = np.concatenate([packed, its]), (*prev, *so_far)
            prep_s += prep.dur_s
            self.m_chunks["fused"].inc()
            if final:  # the step's last token seeds the slot, in its apply
                seeds = functools.partial(self._seed_slot, riding, req, "chunk")
        # only a step that is issued observes its preparation, so
        # gen_prepare counts device steps
        observe_stage("gen_prepare", prep_s)
        if self._stateful:
            valid, masked = self.m_ssm["decode"]
            valid.inc(int(act.sum()))
            masked.inc(self.slots - int(act.sum()))
        if ahead:
            reqs = list(self._slot_req)
            out = await self._run_ahead(
                key, packed, prev,
                functools.partial(self._apply_decode, act, reqs=reqs, seeds=seeds,
                                  rode=rode),
                act=act, reqs=reqs, slot=riding if seeds else -1)
        else:
            # off-loop + gated: one device-step of wall time (plus first compile)
            kept = bool(rode and self._lay is not None)
            out = tokens = await self._run_device_step(key, packed, *prev,
                                                       final=not kept)
            if kept:  # a routed prompt's counters stay on the device: it is
                # handed on as it is, and the step's one fetch is made here
                with annotated("gen_fetch:fused") as fetch:
                    tokens = np.asarray(out)
                observe_stage("gen_fetch", fetch.dur_s, kind="fused")
            self._apply_decode(act, tokens, seeds=seeds, rode=rode)
        if req is not None and not seeds:  # the chunk's books close here
            self._advance_prompt(riding, req, new_off, out)

    def _prepare_decode(self, active: list[int], ahead: bool):
        """A decode step's lanes and packed operands from host state as it
        will stand once the step in flight is applied: (act, packed, what
        the step takes on the device, seconds spent); packed None where no
        lane rides. ``ahead``: None, nothing else done, where the page pool
        cannot cover every riding lane; else (lockstep, nothing in flight)
        ``_reserve_or_truncate`` makes room."""
        pend = self._pipeline
        with annotated("gen_prepare:decode") as prep:
            act = np.zeros(self.slots, bool)
            act[active] = True
            lens, cur, prev = self._lengths, self._cur_tokens, self._no_prev
            if pend is not None and pend.act is not None:
                # lanes of the step in flight: one token further, which
                # stays on the device; none of them if the pending token
                # completes the lane's budget
                rides = pend.act & act
                for s in map(int, np.flatnonzero(rides)):
                    req = self._slot_req[s]
                    if (req is not pend.reqs[s]
                            or len(req.tokens) + 1 >= req.max_new_tokens):
                        rides[s] = act[s] = False
                lens = lens + rides.astype(np.int32)
                cur = np.where(rides, -1, cur)
                prev = (pend.out,)
            if not ahead:
                for s in active:
                    self._reserve_or_truncate(s, act)
            elif not all(self._ensure_page_capacity(int(s), int(lens[s]) + 1)
                         for s in np.flatnonzero(act)):
                return None
            packed = None
            if act.any():
                for s in map(int, np.flatnonzero(act)):
                    self._slide_window(s, int(lens[s]), int(lens[s]))
                table = self._table()
                packed = pack_operands(cur, lens, act, table)
                # a packed step is issued
                self._note_walk("decode", lens, int(act.sum()), table=table)
                if self._eva:
                    for s in map(int, np.flatnonzero(act)):
                        self._eva_account("decode", s, int(lens[s]), int(lens[s]))
        return act, packed, prev, prep.dur_s

    def _note_walk(self, kind: str, last, queries: int, width: int = 1, *,
                   table) -> None:
        """Count the kept pages a step's rows walk: ``last`` [rows] each
        row's last query position as the kernel is given it (an idle lane's
        0 walks its one scratch page). ``queries``: the step's real queries
        (active lanes, a chunk's unpadded positions), each a row of every
        layer with a sink. ``width``: the positions a row of the step.
        ``table``: the rows of the page table the step carries, of which
        the pages a walk takes in runs are counted."""
        if self.m_attn_tiles:
            for product, tiles in (self._tiles_of.get(width)
                                   or self._attn_tiles(width)).items():
                self.m_attn_tiles[kind, product].inc(tiles * len(last))
        if self.m_sink_rows:
            self.m_sink_rows[kind].inc(queries * self._sink_layers)
        if self.m_attn_walk:
            walked, columns, in_runs = self.m_attn_walk[kind]
            cols = self.pages_per_slot
            if self._eva:  # the kernel's bound is a cache row
                last = eva_rows(self.cfg, np.asarray(last))
            pages = np.minimum(last // self.page_size + 1, cols)
            walked.inc(int(pages.sum()))
            columns.inc(cols * len(last))
            if self._walk_in_runs:
                in_runs.inc(pages_in_runs(table[:, :cols], pages))

    def _attn_tiles(self, width: int) -> dict[str, int]:
        """The attention kernel's query tiles of one row of ``width``
        positions, summed over the layers, by the product a tile makes: the
        kernel's own cut (``ops/ragged_attention``) of the shapes one chip
        sees. Worked out once a width."""
        from arkflow_tpu.ops.ragged_attention import (kernel_walks, per_kv_head,
                                                      query_tile)

        shards = 1
        if self.mesh is not None:
            from arkflow_tpu.parallel.mesh import tp_size

            shards = tp_size(self.mesh)
        tiles = self._tiles_of[width] = {p: 0 for _, p in self.m_attn_tiles}
        for spec in map(self.cfg.gqa, self.cfg.attn_kinds):
            heads, kvh = self.cfg.heads // shards, spec.kv_heads // shards
            tile_c = query_tile(width, heads)
            if spec.row_major:  # a run of heads at a time, whatever the tile
                product = "head_run"
            elif (kernel_walks(spec.dk_held, spec.dv, self.kernel_interpret)
                  and per_kv_head(tile_c, heads, kvh)):
                product = "per_kv_head"
            else:
                product = "all_heads"
            tiles[product] += -(-width // tile_c)
        return tiles

    def _note_step_moe(self, nxt, rode: int) -> None:
        """Record a decode-kind step's routing counters by the program that ran:
        a ``_decode`` execution under ``decode``; a fused step that carried
        ``rode`` chunk rows under ``fused`` (its block, whose rows decide the
        expert kernel) and ``fused_lanes`` — the chunk's part rides on with the
        prompt's counters (``chunk``, at its first token)."""
        lay = self._lay
        if not rode:
            self._note_moe("decode", nxt[lay.lanes_at if lay else self.slots:],
                           rows=self.slots)
            return
        self._note_moe("fused_lanes", nxt[lay.lanes_at:], rows=0)
        self._note_moe("fused", nxt[lay.lanes_at + lay.n:], rows=self.slots + rode)

    def _apply_decode(self, act, nxt, reqs=None, seeds=None, rode: int = 0) -> None:
        """One decode step's fetched tokens (then a routed model's counters)
        onto host state. A lane whose request is no longer the one in
        ``reqs`` (the snapshot of a step that ran ahead) rode one step too
        long: its token is dropped, and the step's routing counters, which
        counted the lane, are not recorded. ``seeds``: the step carried its
        prompt's last chunk, and the token behind the lanes' seeds that slot
        (``_seed_slot``). ``rode``: the rows of the chunk the step carried
        (a fused step), which say where its counters go (``_note_step_moe``)."""
        with loop_stage("gen_apply", "decode"):
            self.m_steps.inc()
            lanes = np.flatnonzero(act)
            if self._moe_layers and (reqs is None or all(
                    self._slot_req[s] is reqs[s] for s in lanes)):
                self._note_step_moe(nxt, rode)
            for s in map(int, lanes):
                req = self._slot_req[s]
                if req is None or (reqs is not None and req is not reqs[s]):
                    continue
                self._lengths[s] += 1
                self._cur_tokens[s] = nxt[s]
                self._handle_token(s, int(nxt[s]))
            if seeds is not None:
                seeds(nxt[self.slots:])

    # -- speculative decode -------------------------------------------------

    @staticmethod
    def _draft(req: _Request, n: int) -> list[int]:
        """n draft tokens by 2-gram lookup over the sequence's own history
        (prompt-lookup decoding): find the most recent earlier occurrence
        of the trailing bigram and copy what followed it. Falls back to
        repeating the last token — a wrong draft costs nothing, the verify
        step degenerates to a plain decode for that slot."""
        hist = req.prompt + req.tokens
        out: list[int] = []
        if len(hist) >= 2 and n > 0:
            a, b = hist[-2], hist[-1]
            for i in range(len(hist) - 3, -1, -1):
                if hist[i] == a and hist[i + 1] == b:
                    out = hist[i + 2:i + 2 + n]
                    break
        while len(out) < n:
            out.append(hist[-1] if hist else 0)
        return out[:n]

    async def _step_speculative(self, active: list[int]) -> None:
        """One verify step: each active slot scores its current token plus
        up to ``speculative_tokens`` drafts in a single chunk call; the
        accepted prefix (argmax-consistent) all lands this step."""
        k = self.speculative_tokens + 1
        with loop_stage("gen_prepare", "verify"):
            act = np.zeros(self.slots, bool)
            act[active] = True
            clen = np.zeros(self.slots, np.int32)
            ids = np.zeros((self.slots, k), np.int32)
            for s in active:
                # width-1 capacity first (truncation policy identical to _step)
                self._reserve_or_truncate(s, act)
                if not act[s] or self._slot_req[s] is None:
                    continue
                req = self._slot_req[s]
                remaining = req.max_new_tokens - len(req.tokens)
                room = self.max_seq - int(self._lengths[s])
                c = max(1, min(k, remaining, room))
                # widen only as far as free pages allow (never truncate for width)
                while c > 1 and not self._ensure_page_capacity(
                        s, int(self._lengths[s]) + c):
                    c -= 1
                clen[s] = c
                ids[s, 0] = self._cur_tokens[s]
                if c > 1:
                    ids[s, 1:c] = self._draft(req, c - 1)
            packed = pack_operands(ids, self._lengths, clen, self._table())
        # the program scores every position and picks its argmax there
        picked = await self._run_device_step(("verify", k), packed)
        with loop_stage("gen_apply", "verify"):
            self.m_steps.inc()
            for s in map(int, np.flatnonzero(clen)):
                if self._slot_req[s] is None:
                    continue
                c = int(clen[s])
                outs = picked[s, :c]
                accepted = 0
                while accepted < c - 1 and ids[s, accepted + 1] == outs[accepted]:
                    accepted += 1
                self.m_spec_drafted.inc(c - 1)
                self.m_spec_accepted.inc(accepted)
                self._lengths[s] += accepted + 1
                self._cur_tokens[s] = int(outs[accepted])
                for t in outs[:accepted + 1]:
                    self._handle_token(s, int(t))
                    if self._slot_req[s] is None:
                        break
