"""Paged KV-cache decode for the decoder LM (vLLM-style, TPU-native).

The contiguous cache in ``decoder.py`` preallocates ``[B, max_len]`` per
sequence; mixed-length workloads waste most of it. Here KV lives in a pool
of fixed-size pages — ``[layers, num_pages, page, kv_heads, dh]`` — and each
serving slot owns an int32 page table. Pages are allocated/freed by the
host-side scheduler (``arkflow_tpu.tpu.serving``) BETWEEN steps; device code
only ever reads/writes through static-shaped gathers and scatters, so every
step jits once and replays (no dynamic shapes, XLA-friendly).

Page 0 is a reserved scratch page: inactive slots and masked prompt padding
write there, which keeps the scatter free of conditionals.

The cache's shape is the model's to state and this module's to own
(``cache_spec``): per-head K/V (GQA) — a head narrower than 128 lanes in
ROW-MAJOR pools, ``[layers, pages, page, kv_heads * dh]``, which the
attention kernel's narrow-head walk copies a page at a time —, a state a
SEQUENCE beside them (a hybrid layer's Mamba-2 state, a conv layer's last
gated inputs, a Gated DeltaNet layer's matrix state: a row a slot), or latent rows (MLA, ``cfg.latent``;
Kimi Delta Attention layers' matrix states a SEQUENCE beside them, ``kda``:
one normed latent row and one rotated rope key a token for all heads;
attention runs in the absorbed form over them). Either kind's pools ride
whole through the layer loop (``_dense_layers``, ``_latent_layers``): a
layer writes at (layer, page, offset) in place and attends by index. A
latent model with a layer pattern has three kinds of row side by side: its
full layers' latent rows, their index keys, and its sliding layers' (wider)
latent rows, which live only while the window covers them — in a pool and
under a page table of their own. A per-head model with a layer pattern has two:
``kv`` rows of its full layers, kept, and ``kv_window`` rows of its sliding
layers, live while the window covers them (a pool and a ring of pages of
their own, as the latent window pool) — each pool at its kind's K/V heads
and widths, which may differ (``DecoderConfig.gqa``), a key wider than 128
lanes and no multiple of them held in parts of 128. The MLP half is dense
SwiGLU, the Switch top-1 layer, or dropless routed experts (``cfg.routed``),
on either kind of attention.

The reference has no serving layer at all (its python processor is
user-code); this implements the engine the `tpu_generate` processor's
continuous-batching mode runs on. Design follows the public PagedAttention
idea (Kwon et al., SOSP'23) re-expressed for XLA: page-table gather +
masked attention instead of custom CUDA paging.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import dataclasses
from typing import NamedTuple, Optional

from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import common as cm
from dataclasses import dataclass

from arkflow_tpu.models.decoder import (CONV, FULL, LINEAR, MAMBA, MOE, SLIDING,
                                        DecoderConfig, _mlp, _norm, _scaled,
                                        attn_out_gate, conv_gate, conv_taps,
                                        expert_products,
                                        hc_collapse,
                                        hc_expand, hc_post, hc_pre,
                                        index_project, index_scores,
                                        layer_runs, layer_stacks,
                                        linear_mixer, lm_logits, mla_absorb_query,
                                        mla_expanded_attention, mla_head_gate,
                                        mla_output, mla_project,
                                        mla_query_latent, moe_step_stats,
                                        qk_positioned, qkv_project,
                                        routed_mlp, short_conv, ssm_conv,
                                        ssm_operands, ssm_output, ssm_project)


@dataclass(frozen=True)
class CachePool:
    """One kind of cached row: how many layers hold it, the widths of its
    arrays (values a row a layer) and their item sizes (bf16 unless stated),
    and for how many tokens a row stays live (``window`` 0: for the
    request's life). A row is a TOKEN'S, addressed by (page, offset) — or,
    ``per_slot``, a SEQUENCE'S: one row a serving slot, overwritten by every
    token, addressed by slot. ``heads`` > 0: each array has a head axis of
    so many heads before its width's last (per-head K and V), and the
    first array (K) is held in ``key_parts`` parts of equal width, each a
    layer of the array's own (``GqaSpec.key_parts``: layer ``l``'s part
    ``p`` is the array's layer ``p * layers + l``, so part 0 is indexed as
    V is). ``row_major``: the heads have NO axis of their own, a token's
    are side by side on the last (``GqaSpec.row_major``: a head narrower
    than 128 lanes). ``split_heads``: each head has a LAYER of each array
    of its own (``GqaSpec.split_heads``: head ``j`` of layer ``l`` is the
    array's layer ``j * layers + l``, its head axis of one)."""
    name: str
    layers: int
    widths: tuple
    window: int = 0
    itemsizes: tuple = ()
    per_slot: bool = False
    heads: int = 0
    key_parts: int = 1
    row_major: bool = False
    split_heads: bool = False
    compact: tuple = ()

    def shapes(self, pages: int, page_size: int) -> list:
        """The shapes of a per-head pool's K and V over ``pages`` pages."""
        if self.row_major:
            return [(self.layers, pages, page_size, w) for w in self.widths]
        if self.split_heads:
            return [(self.layers * self.heads, pages, page_size, 1,
                     w // self.heads) for w in self.widths]
        return [(self.layers * parts, pages, page_size, self.heads,
                 width // self.heads // parts)
                for width, parts in zip(self.widths, (self.key_parts, 1))]

    @property
    def _row_bytes(self) -> int:
        sizes = self.itemsizes or (2,) * len(self.widths)
        return sum(w * b for w, b in zip(self.widths, sizes)) * self.layers

    @property
    def bytes_per_token(self) -> int:
        """Bytes one cached token costs over the pool's layers (0 for a
        pool whose rows are a sequence's: ``bytes_per_slot``)."""
        return 0 if self.per_slot else self._row_bytes

    @property
    def bytes_per_slot(self) -> int:
        """Bytes one busy slot holds over the pool's layers, whatever its
        context (0 for a pool of token rows)."""
        return self._row_bytes if self.per_slot else 0


def _held_lanes(width: int) -> int:
    """A latent model's rope key as its pools hold it: in whole 128-lane
    rows, zeros behind the key, written where the key is written. The chip
    tiles a narrower pool's rows to 128 lanes anyway, and a kernel's own
    copy takes a page out of a pool only in whole such rows
    (``ops/ragged_attention.mla_paged_attention`` walks the table by its
    own copies); the plain-XLA forms read the key's own lanes. One layout,
    whatever serves."""
    return -(-width // 128) * 128


def cache_spec(cfg: DecoderConfig) -> tuple:
    """The kinds of row the model caches — the one place that states them:

    - ``kv``: per-head K and V (GQA), every layer — with a layer pattern
      the full layers only —, at the full layers' sizes (``cfg.gqa``: K/V
      heads x the key's width AS HELD, x the value's);
    - ``kv_window``: a per-head sliding layer's K and V at the sliding
      layers' sizes, live for ``sliding_window`` tokens: its pages are
      freed as the window passes;
    - ``latent``: a full latent layer's normed latent row and rotated rope
      key, one each a token for ALL heads, the rope key AS HELD: in whole
      128-lane rows (``_held_lanes``), zeros behind the key;
    - ``index``: an indexed full layer's index key (the indexer scores it
      against every later query);
    - ``window``: a sliding latent layer's latent row and rope key (held
      likewise), live for ``sliding_window`` tokens: its pages are freed as
      the window passes;
    - ``ssm``: a hybrid layer's recurrent state — the mixer's float32 state
      matrices and the conv's last ``d_conv - 1`` inputs —, one row a
      SEQUENCE whatever its length, beside that layer's ``kv`` rows; among
      blocks of one mixer each, over the mamba layers only (``kv`` over the
      attention layers only, as beside ``conv``);
    - ``conv``: a conv layer's last ``conv_L_cache - 1`` gated inputs, one
      row a SEQUENCE too, over the conv layers only (which have no ``kv``
      rows: ``kv`` is over the attention layers only);
    - ``gdn``: a Gated DeltaNet layer's float32 state — a [key dim, value
      dim] matrix a value head — and its conv's last
      ``linear_conv_kernel_dim - 1`` projected inputs (bfloat16), one row a
      SEQUENCE, over the linear_attention layers only (``kv`` over the
      attention layers only, as beside ``conv``);
    - ``kda``: a Kimi Delta Attention layer's float32 state — a [head dim,
      head dim] matrix a head — and its three convs' last
      ``short_conv_kernel_size - 1`` projected inputs (bfloat16), one row a
      SEQUENCE, over a LATENT model's linear_attention layers (``latent``
      over its attention layers only);
    - ``eva``: per-head K and V of a compacting window cache
      (``attention_class`` "eva"), every layer: rows that are NOT positions.
      A slot's rows are the summary rows of its closed windows — ``window /
      chunk`` a window, whole pages —, then the exact rows of the window it
      is writing; position ``p`` is written at row ``eva_rows(p)`` and a
      window's rows are pooled in place when its last one is written."""
    if not cfg.latent:
        full, swa = (cfg.kinds.count(k) for k in (FULL, SLIDING))

        def widths(sp):
            return dict(widths=(sp.kv_heads * sp.dk_held, sp.kv_heads * sp.dv),
                        heads=sp.kv_heads, key_parts=sp.key_parts,
                        row_major=sp.row_major, split_heads=sp.split_heads)

        if cfg.eva:
            return (CachePool("eva", full, **widths(cfg.gqa(FULL)),
                              compact=(cfg.window_size, cfg.chunk_size)),)
        pools = (CachePool("kv", full, **widths(cfg.gqa(FULL))),)
        if swa:
            pools += (CachePool("kv_window", swa, window=cfg.sliding_window,
                                **widths(cfg.gqa(SLIDING))),)
        if cfg.hybrid or cfg.mamba:
            pools += (CachePool(
                "ssm", cfg.kinds.count(MAMBA) if cfg.mamba else cfg.layers,
                (cfg.mamba_d_ssm * cfg.mamba_d_state,
                 (cfg.mamba_d_conv - 1) * cfg.ssm_conv_dim),
                itemsizes=(4, 2), per_slot=True),)
        if cfg.conv:
            pools += (CachePool(
                "conv", cfg.kinds.count(CONV),
                ((cfg.conv_L_cache - 1) * cfg.dim,), per_slot=True),)
        if cfg.linear:
            pools += (CachePool(
                "gdn", cfg.kinds.count(LINEAR),
                (cfg.linear_num_value_heads * cfg.linear_key_head_dim
                 * cfg.linear_value_head_dim,
                 (cfg.linear_conv_kernel_dim - 1) * cfg.gdn_conv_dim),
                itemsizes=(4, 2), per_slot=True),)
        return pools
    full, swa = (cfg.kinds.count(k) for k in (FULL, SLIDING))
    pools = [CachePool("latent", full, (cfg.kv_lora_rank,
                                        _held_lanes(cfg.qk_rope_head_dim)))]
    if cfg.linear:
        pools.append(CachePool(
            "kda", cfg.kinds.count(LINEAR),
            (cfg.kda_heads * cfg.kda_head_dim ** 2,
             (cfg.kda_taps - 1) * cfg.kda_conv_dim),
            itemsizes=(4, 2), per_slot=True))
    if cfg.index_topk:
        pools.append(CachePool("index", full, (cfg.index_head_dim,)))
    if swa:
        pools.append(CachePool(
            "window", swa, (cfg.swa_kv_lora_rank,
                            _held_lanes(cfg.swa_qk_rope_head_dim)),
            cfg.sliding_window))
    return tuple(pools)


# -- what each kind of cache is served with ----------------------------------
#
# ``UNSERVED``: a row for every kind of pool ``cache_spec`` names and for
# every trait of a configuration that is not a pool's; a column for every
# serving feature; a cell is the sentence that says why the row is not served
# with the feature YET, and is left out where it is. Whoever turns a feature
# on asks ``unserved`` / ``refuse``, once; nothing else decides by kind. A new
# kind states its row here; a capability is added by taking a cell out, with
# its tests. The features:
#
# - ``mesh_tp``: the pools sharded over KV heads on a mesh's ``tp`` axis;
# - ``prefix_cache``: finished prompts' full pages aliased (``prefix_cache_pages``);
# - ``speculation``: drafts verified in one chunk call (``speculative_tokens``);
# - ``one_shot_prefill``: a prompt in one block (no ``prefill_chunk``);
# - ``kv_push``: pages exported / adopted (``prefill_export``,
#   ``generate_from_pages``: prefill and decode on different servers);
# - ``batch``: the contiguous cache of ``decoder.init_kv_cache``
#   (``serving: batch``);
# - ``swap`` / ``integrity``: the processor's hot swap and its golden probes;
# - ``fused_chunk``: a prompt's chunk rides a decode step (``paged_fused_step``);
# - ``run_ahead``: a step is enqueued before the one before it is applied, so
#   a prompt's lane joins decode a step later; ``run_ahead_eos``: the same
#   under a live ``eos_id``, where a lane that ended on an EOS rides one step
#   more (the server's own halves — greedy, depth, no speculation — are the
#   server's: ``GenerationServer._ahead`` / ``_fuses``).
#
# ``{pools}`` is the configuration's pools by name, ``{hc}`` its ``hc_mult``.

# the swap canary and the integrity golden run the family's batch forward
# against per-head-cache assumptions that were never checked for a model
# that stacks by runs: the key is refused and neither is attached
_UNVERIFIED = dict.fromkeys(("swap", "integrity"), (
    "is not supported for a latent-attention model, nor for a per-head K/V "
    "model with routed experts, a layer pattern or head sizes by kind, yet "
    "(its drain / flip / pool reset and golden forward are unverified for "
    "latent and window pages); remove the key"))
_BY_RUNS = {
    # a per-head K/V model whose tree stacks by runs (``cfg.by_runs``)
    **_UNVERIFIED,
    "mesh_tp": (
        "a per-head K/V model with routed experts, a layer pattern or head "
        "sizes by kind (pools {pools}) is served on one chip: its expert "
        "stack, its window pool and its stacks by kind have no sharding over "
        "a mesh yet (remove mesh)"),
    "batch": (
        "the contiguous KV cache (serving: batch) runs one stack of "
        "identical dense layers over keys and values of one width: a "
        "per-head K/V model with routed experts (n_routed_experts), a layer "
        "pattern (layer_types: window pages beside kept pages), qk_norm, or "
        "head sizes by kind (swa_kv_heads, v_head_dim, "
        "partial_rotary_factor, attention_value_scale, a sink, an output "
        "gate) generates through serving: continuous only"),
}
_FUSED = (
    "a chunk rides a decode step only on a per-head K/V model with a dense "
    "MLP or routed experts, with conv layers among its attention layers or "
    "none, or a plain latent-attention model with routed experts (pools "
    "{pools}): ")
_PATTERN = {
    # sliding or indexed layers: rows of more than one kind and lifetime
    "speculation": (
        "speculative_tokens does not compose with indexed or sliding layers "
        "(pools {pools}): a rejected draft leaves its index key behind, "
        "which a later query's indexer may select, and the verify step does "
        "not slide the window pool"),
    "one_shot_prefill": (
        "a model with a layer pattern (sliding or indexed layers: pools "
        "{pools}) prefills in chunks through the cache: set prefill_chunk > "
        "0 (its size bounds a slot's window pages; the one-shot prefill "
        "attends over its own block under one mask)"),
    "kv_push": (
        "ships the pages of ONE kept pool; a layer pattern's pools ({pools}) "
        "have no wire form yet — the window pool's live pages and their "
        "ring would have to ship beside the kept pages — so such a model "
        "prefills and decodes on the same server"),
    "fused_chunk": _FUSED + (
        "a sliding layer wants its ring coordinates, an indexed layer its "
        "choice a query, and the block carries neither"),
}
_WINDOW = {
    **_PATTERN,
    "prefix_cache": (
        "prefix_cache_pages does not compose with window pages (pools "
        "{pools}): a sliding layer's rows are freed as the window passes, so "
        "a finished prompt has no full pages of them to donate"),
}
_LATENT = {
    **_UNVERIFIED,
    "mesh_tp": (
        "continuous serving shards the KV pools over KV heads on the tp "
        "axis; a latent (MLA) pool has one shared row per token and no head "
        "axis to split — serve a latent-attention model on one chip (no "
        "mesh)"),
    "kv_push": (
        "ships per-head K/V page slabs split along the kv_heads axis; a "
        "latent (MLA) page has no head axis and no wire format yet — a "
        "latent-attention model prefills and decodes on the same server"),
    "batch": (
        "the contiguous KV cache (serving: batch) holds per-head K/V and "
        "carries no latent (MLA) cache: a latent-attention model "
        "(kv_lora_rank > 0) generates through serving: continuous only (the "
        "paged pool)"),
}
_STATE = {
    # a state a SEQUENCE: overwritten by every token, so what is benign for
    # K/V rows (a stale row, an aliased page, a lane that rides one step too
    # long) is not for it
    "mesh_tp": (
        "a model with the hybrid block (mamba_d_ssm > 0), conv or "
        "linear_attention layers (pools {pools}) is served on one chip: the "
        "state pool and the mixer's channels have no sharding over a mesh "
        "yet (remove mesh)"),
    "prefix_cache": (
        "prefix_cache_pages does not compose with a recurrent state (pools "
        "{pools}): aliased pages skip the very tokens whose state the rest "
        "of the prompt needs, and no state snapshot is kept beside a cached "
        "prefix yet"),
    "speculation": (
        "speculative_tokens does not compose with a recurrent state (pools "
        "{pools}): a rejected draft has already advanced the state (for K/V "
        "it only leaves a stale row), and there is no rollback yet"),
    "one_shot_prefill": (
        "a model that carries a recurrent state (pools {pools}) prefills in "
        "chunks through the cache: set prefill_chunk > 0 (the chunk's "
        "program is the one that is told its slot's row of the state pool "
        "and resets it)"),
    "kv_push": (
        "ships K/V page slabs; a recurrent state (pools {pools}) has no wire "
        "form yet, and pages without it cannot be decoded from — a model "
        "with the hybrid block, conv or linear_attention layers prefills and "
        "decodes on the same server"),
    "batch": (
        "the contiguous KV cache (serving: batch) carries no recurrent "
        "state: a model with the hybrid block (mamba_d_ssm > 0), conv layers "
        "or linear_attention layers (pools {pools}: a state a slot beside "
        "the K/V pages) generates through serving: continuous only"),
    "run_ahead_eos": (
        "a lane that ended on an EOS rides the step behind, which would "
        "advance its state past its sequence's end (for K/V it only leaves "
        "a stale row): under a live eos_id such a model serves in lockstep"),
}
# conv and linear_attention layers stand AMONG the attention layers: by runs
_STATE_BY_RUNS = {**_STATE, **_UNVERIFIED}
# the block carries each part's rows of a state pool (``_by_parts``); a conv
# layer's windows are all its state. A scan is not split yet:
_SCANNED = {"fused_chunk": _FUSED + (
    "the block carries each part's row of the state pool, but a mixer's "
    "chunk scan beside its one-token update in one program (the hybrid "
    "block's, a linear_attention layer's) is not written yet, and no served "
    "cell could show its gain")}
_SWITCH = (
    "the Switch layer queues a step's live lanes into shared expert "
    "capacity, so a lane that joins a step later, or a chunk beside the "
    "lanes, changes its neighbours' tokens")

#: row -> {feature: why not}. ``unserved`` reads the rows in THIS order, the
#: more particular reason first
UNSERVED = {
    "streams": {
        "mesh_tp": (
            "hc_mult {hc} (several residual streams) is served on one chip: "
            "neither the streams' mixing nor the latent pools have a "
            "sharding over a tp mesh yet (remove mesh)"),
        "prefix_cache": (
            "prefix_cache_pages does not compose with hc_mult {hc} yet: the "
            "prefix cache over latent pools has not been held to the "
            "streams' reference"),
        "speculation": (
            "speculative_tokens does not compose with hc_mult {hc} (several "
            "residual streams over latent pools): the verify step (_verify) "
            "has not been held to the streams' reference"),
        "fused_chunk": _FUSED + (
            "the streams' mixing kernels want row tiles the block does not "
            "keep (hc_mult {hc})"),
    },
    "kv": {},
    "kv_window": {**_BY_RUNS, **_WINDOW},
    "latent": _LATENT,
    "index": _PATTERN,    # beside ``latent`` only, which says the rest
    "window": _WINDOW,
    "ssm": {**_STATE, **_SCANNED},
    "conv": _STATE_BY_RUNS,
    "gdn": {**_STATE_BY_RUNS, **_SCANNED},
    "kda": {**_STATE_BY_RUNS, **_SCANNED},  # beside ``latent``: the union
    "eva": {
        **_BY_RUNS,
        "mesh_tp": (
            "attention_class 'eva' is served on one chip: the window close "
            "and the summary pages have no sharding over a mesh yet (remove "
            "mesh)"),
        "prefix_cache": (
            "prefix_cache_pages does not compose with attention_class 'eva': "
            "a closed window's pages are pooled in place and handed back, so "
            "a finished prompt has no pages by position to donate"),
        "speculation": (
            "speculative_tokens does not compose with attention_class 'eva': "
            "a verify step that crosses a window's end would pool rejected "
            "drafts into the summaries, and there is no rollback yet"),
        "one_shot_prefill": (
            "a compacting window cache (attention_class 'eva': pool {pools}) "
            "prefills in chunks through the cache: set prefill_chunk > 0 to "
            "a divisor of window_size (a chunk never straddles a window's "
            "end; the one-shot prefill attends over its own block and closes "
            "no window)"),
        "kv_push": (
            "ships a prompt's pages by position; a compacting window cache "
            "(pool {pools}) holds summary pages and an open window, which "
            "have no wire form yet — a model with attention_class 'eva' "
            "prefills and decodes on the same server"),
        "batch": (
            "the contiguous KV cache (serving: batch) keeps a row a "
            "position: attention_class 'eva' (a window that is compacted "
            "into chunk summaries when it closes) generates through serving: "
            "continuous only (the paged pool, by cached length)"),
        "fused_chunk": _FUSED + (
            "a compacting window cache (attention_class 'eva') wants to be "
            "told which rows close a window"),
    },
    "hetero": {
        **_BY_RUNS,
        "kv_push": (
            "ships K and V page slabs of one shape; this model's pools "
            "({pools}) hold keys and values of different widths (v_head_dim; "
            "a key held in parts) or sizes by kind, which have no wire form "
            "yet — such a model prefills and decodes on the same server"),
    },
    "routed": _BY_RUNS,
    # blocks of one mixer each: by runs, mamba layers (pool ``ssm``) among them
    "one_mixer": _STATE_BY_RUNS,
    "qk_norm": _BY_RUNS,
    "switch": {"fused_chunk": _FUSED + _SWITCH, "run_ahead": _SWITCH,
               "run_ahead_eos": _SWITCH},
}
FEATURES = ("mesh_tp", "prefix_cache", "speculation", "one_shot_prefill",
            "kv_push", "batch", "swap", "integrity", "fused_chunk",
            "run_ahead", "run_ahead_eos")


def cache_rows(cfg: DecoderConfig) -> tuple:
    """The rows of ``UNSERVED`` a configuration has, in the table's order:
    its pools (``cache_spec``) and the traits that are not a pool's —
    ``streams``: several residual streams (``hc_mult`` > 1); ``hetero``: keys
    and values of different widths, or sizes by kind; ``routed``: an expert
    stack on the PER-HEAD loop (a latent model always has one); ``qk_norm``:
    per-head norms (the tree stacks by runs for them alone); ``switch``: the
    capacity-based Switch layer (``num_experts``); ``one_mixer``: blocks of
    one mixer each (mamba / moe layers: stacked by runs, with or without
    experts)."""
    traits = dict(streams=cfg.hc_mult > 1, hetero=cfg.hetero,
                  routed=cfg.routed and not cfg.latent, qk_norm=cfg.qk_norm,
                  one_mixer=cfg.one_mixer,
                  switch=cfg.num_experts > 1)
    pools = {pool.name for pool in cache_spec(cfg)}
    return tuple(row for row in UNSERVED if row in pools or traits.get(row))


def unserved(cfg: DecoderConfig, feature: str) -> Optional[str]:
    """Why the configuration is not served with ``feature`` (``FEATURES``):
    the first reason among its rows, or None where it is served."""
    for row in cache_rows(cfg):
        why = UNSERVED[row].get(feature)
        if why is not None:
            return why.format(hc=cfg.hc_mult, pools=", ".join(
                pool.name for pool in cache_spec(cfg)))
    return None


def refuse(cfg: DecoderConfig, feature: str, who: str = "") -> None:
    """Raise ``unserved``'s reason as a ConfigError, ``who`` — the caller by
    the name its user knows, where the sentence is about the caller — in
    front."""
    why = unserved(cfg, feature)
    if why is not None:
        raise ConfigError(f"{who} {why}" if who else why)


def init_page_pool(cfg: DecoderConfig, num_pages: int, page_size: int,
                   window_pages: int = 0, slots: int = 0):
    """The page pools of ``cache_spec``, bf16, as the two values every step
    carries (and donates):

    - per-head K/V (GQA): K and V, each [layers, num_pages, page, kv_heads,
      its width a head] (``CachePool.shapes``; a key held in parts has a
      layer a part);
    - latent (MLA): the normed latent rows [layers, num_pages, page,
      kv_lora_rank] and the rotated rope keys [layers, num_pages, page,
      qk_rope_head_dim as held: ``cache_spec``], one row each per token for
      ALL heads (no head axis: a page's last two dims then tile the chip's
      memory as they are);
    - a latent model with a layer pattern: two dicts by pool name — the
      wide rows ``{"latent", "window"[, "index"]}`` and the rope keys
      ``{"latent", "window"}`` — each pool over its OWN layers, the window
      pool over ``window_pages`` pages of its own;
    - a per-head model with a layer pattern: two dicts by pool name —
      ``{"kv": K, "kv_window": K}`` and the same of V — ``kv`` over the full
      layers and ``num_pages``, ``kv_window`` over the sliding layers and
      ``window_pages`` pages of its own, each at its kind's heads and widths;
    - a hybrid model: two dicts by pool name — ``{"kv": K, "ssm": the
      states}`` and ``{"kv": V, "ssm": the conv windows}`` — the states
      float32 [layers, slots + 1, heads, d_state, d_head] (``ops/ssm_scan``
      says why in that order; a head narrower than 128 lanes is held
      ``heads_packed`` heads side by side: [.., heads / k, d_state, k
      d_head]), the windows [layers, slots + 1, d_conv - 1,
      conv channels]: row 0 scratch, as page 0 is, row ``s + 1`` slot
      ``s``'s; among blocks of one mixer each the same two, ``ssm`` over the
      mamba layers and ``kv`` over the attention layers;
    - a model with conv layers: ``{"kv": K, "conv": the windows}`` and
      ``{"kv": V}`` — ``kv`` over the attention layers, the windows [conv
      layers, slots + 1, conv_L_cache - 1, dim], rows as a hybrid model's;
    - a model with linear_attention layers: ``{"kv": K, "gdn": the states}``
      and ``{"kv": V, "gdn": the conv windows}`` — ``kv`` over the attention
      layers, the states float32 [linear layers, slots + 1, value heads, key
      dim, value dim] (``ops/gdn_scan``), the windows [linear layers, slots
      + 1, linear_conv_kernel_dim - 1, conv channels], rows as above;
    - a latent model with Kimi Delta Attention layers: ``{"latent": the
      latent rows, "kda": the states}`` and ``{"latent": the rope keys,
      "kda": the conv windows}`` — ``latent`` over the attention layers, the
      states float32 [linear layers, slots + 1, heads, head dim, head dim]
      (``ops/kda_scan``), the windows [linear layers, slots + 1,
      short_conv_kernel_size - 1, 3 heads x head dim], rows as above.

    A head narrower than 128 lanes has ROW-MAJOR K and V, [layers, pages,
    page, kv_heads * width] (``CachePool.row_major``)."""
    if cfg.latent and cfg.linear:
        latent, kda = cache_spec(cfg)
        c, r = (jnp.zeros((latent.layers, num_pages, page_size, w), jnp.bfloat16)
                for w in latent.widths)
        rows = (kda.layers, slots + 1)
        return ({"latent": c, "kda": jnp.zeros(
                    rows + (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim),
                    jnp.float32)},
                {"latent": r, "kda": jnp.zeros(
                    rows + (cfg.kda_taps - 1, cfg.kda_conv_dim), jnp.bfloat16)})
    if cfg.latent and cfg.layered:
        wide, rope = {}, {}
        for pool in cache_spec(cfg):
            pages = window_pages if pool.window else num_pages
            arrays = [jnp.zeros((pool.layers, pages, page_size, w), jnp.bfloat16)
                      for w in pool.widths]
            wide[pool.name] = arrays[0]
            if len(arrays) > 1:
                rope[pool.name] = arrays[1]
        return wide, rope
    if cfg.latent:
        return tuple(jnp.zeros((cfg.layers, num_pages, page_size, w), jnp.bfloat16)
                     for w in cache_spec(cfg)[0].widths)
    spec = cache_spec(cfg)
    if cfg.layered:
        return tuple({pool.name: jnp.zeros(
            pool.shapes(window_pages if pool.window else num_pages,
                        page_size)[i], jnp.bfloat16)
            for pool in spec} for i in range(2))
    k, v = (jnp.zeros(shape, jnp.bfloat16)
            for shape in spec[0].shapes(num_pages, page_size))
    if cfg.conv:
        return ({"kv": k, "conv": jnp.zeros(
            (spec[-1].layers, slots + 1, cfg.conv_L_cache - 1, cfg.dim),
            jnp.bfloat16)}, {"kv": v})
    if cfg.linear:
        rows = (spec[-1].layers, slots + 1)
        return ({"kv": k, "gdn": jnp.zeros(
                    rows + (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                            cfg.linear_value_head_dim), jnp.float32)},
                {"kv": v, "gdn": jnp.zeros(
                    rows + (cfg.linear_conv_kernel_dim - 1, cfg.gdn_conv_dim),
                    jnp.bfloat16)})
    if not (cfg.hybrid or cfg.mamba):
        return k, v
    rows = (spec[-1].layers, slots + 1)
    packed = cfg.ssm_heads_packed
    return ({"kv": k, "ssm": jnp.zeros(
                rows + (cfg.mamba_n_heads // packed, cfg.mamba_d_state,
                        packed * cfg.mamba_d_head), jnp.float32)},
            {"kv": v, "ssm": jnp.zeros(
                rows + (cfg.mamba_d_conv - 1, cfg.ssm_conv_dim), jnp.bfloat16)})


def kv_bytes_per_token(cfg: DecoderConfig) -> int:
    """Bytes one cached token costs over all layers and pools (bf16 values
    as the pools hold them, before any padding the device's tiling adds; a
    window row while it is live)."""
    return sum(pool.bytes_per_token for pool in cache_spec(cfg))


def window_ring_pages(cfg: DecoderConfig, page_size: int, step_tokens: int) -> int:
    """Columns of a row's window page table: the table is a ring — the
    page of logical index ``i`` sits in column ``i % columns`` — wide enough
    for every page a step of ``step_tokens`` queries can attend or write:
    the ``sliding_window - 1`` positions before its first query to its last,
    however they fall on page boundaries. 0 without sliding layers."""
    if SLIDING not in cfg.kinds:
        return 0
    return (cfg.sliding_window + max(step_tokens, 1) - 2) // page_size + 2


def eva_rows(cfg: DecoderConfig, positions):
    """The cache row position ``p`` of a compacting window cache is written
    at (ints, numpy or jax arrays alike): the summary rows of the windows
    closed before it, then its place in its own window. Also the rows a slot
    holds BEFORE that write, its cached length: a function of the position,
    so the host mirrors it without a fetch."""
    w = cfg.window_size
    return positions // w * (w // cfg.chunk_size) + positions % w


def eva_table_pages(cfg: DecoderConfig, page_size: int, max_seq: int) -> int:
    """Pages a slot of a compacting window cache holds at most over
    ``max_seq`` positions (its table's columns): the summary pages of every
    window it can close and one whole window."""
    w = cfg.window_size
    summary = w // cfg.chunk_size // page_size
    return max(max_seq - 1, 0) // w * summary + -(-min(w, max_seq) // page_size)


class _EvaClose(NamedTuple):
    """Which rows of a step close a window (``closing`` [B]: the step writes
    the window's last row) and the window's first column of the row's page
    table (``first`` [B])."""

    closing: object
    first: object


def _eva_close(lp: dict, kp, vp, layer, table, eva: _EvaClose,
               cfg: DecoderConfig, kernel: bool, interpret: bool):
    """The window close of one layer, on the device: where any row of the
    step wrote its window's last row, pool that window's rows to ``window /
    chunk`` summary rows (``ops/eva_summarise``) and write them over the
    window's FIRST pages, in place; the host hands the window's other pages
    back and starts the next window behind the summaries. A closing row at a
    time: rows that do not close cost nothing."""
    from arkflow_tpu.ops.eva_summarise import eva_summarise, eva_summarise_plain

    sp = cfg.gqa(FULL)
    page = kp.shape[2]
    w, c = cfg.window_size, cfg.chunk_size
    # the closing rows first: a loop over as many as there are (none, in
    # nearly every step) carries the pools in place, as the layer scan does
    # — a ``cond`` around the close copied both pools in the chunk's program
    order = jnp.argsort(jnp.logical_not(eva.closing))

    def close(carry):
        i, kp, vp = carry
        row = order[i]
        cols = jnp.minimum(eva.first[row] + jnp.arange(w // page),
                           table.shape[1] - 1)
        win = table[row][cols][None]                         # [1, w / page]
        k = _read_keys(kp, layer, win, sp.dk, sp.kv_heads)   # [1, w, kv, dk]
        v = _read_rows(vp, layer, win, sp.kv_heads)
        if kernel:
            ks, vs = eva_summarise(k, v, lp["eva_phi"], lp["eva_mu"], chunk=c,
                                   interpret=interpret)
        else:
            ks, vs = eva_summarise_plain(k, v, lp["eva_phi"], lp["eva_mu"], c)
        dest = win[:, :w // c // page]
        kp, vp = (pool.at[layer, dest].set(rows.reshape(
            1, dest.shape[1], page, *pool.shape[3:]).astype(pool.dtype))
            for pool, rows in ((kp, ks), (vp, vs)))
        return i + 1, kp, vp

    with jax.named_scope("eva_summarise"):
        closing = eva.closing.sum().astype(jnp.int32)
        return jax.lax.while_loop(lambda carry: carry[0] < closing, close,
                                  (jnp.int32(0), kp, vp))[1:]


def _page_size(pools) -> int:
    """Tokens a page of the pools a step carries (``init_page_pool``: one
    array, or a dict by pool name whose per-slot pools have no pages)."""
    if isinstance(pools, dict):  # a per-slot pool beside it has no pages
        for paged in ("kv", "latent"):
            if paged in pools:
                return pools[paged].shape[2]
    return jax.tree_util.tree_leaves(pools)[0].shape[2]


def _write_coords(table, positions, valid, page: int, ring: bool = False):
    """(page, offset) each token's row is written at: through ``table``
    (a ring of pages where ``ring``) for the tokens ``valid`` names, the
    scratch page 0 for the rest."""
    logical = positions // page
    col = logical % table.shape[1] if ring else jnp.minimum(
        logical, table.shape[1] - 1)
    return (jnp.where(valid, jnp.take_along_axis(table, col, axis=1), 0),
            jnp.where(valid, positions % page, 0))


def _latent_layers(params: dict, cfg: DecoderConfig, x, k_pages, v_pages,
                   positions, page_idx, offset, token_mask, *, page_table,
                   off, mask, block: bool, attention_kernel: str,
                   kernel_interpret: bool,
                   chunk: Optional[_RidingChunk] = None,
                   ssm_rows=None, ssm_fresh=None):
    """The layer loop of a latent-attention model over the paged cache: one
    scan per layer run (leading dense layers, then expert layers; a layer
    pattern alternates kinds), each layer reading its kind's sizes
    (``cfg.attn``) and writing its kind's pools. The pools ride in the carry
    whole — each layer scatters its tokens' rows at (its index among its
    kind's layers, page, offset) in place and hands the pools on, so runs
    share them without slicing or re-joining.

    ``page_idx`` / ``offset`` [B, S] place each token's row in the kept
    pools; ``page_table`` is the kept table [B, P] or, with sliding layers,
    (kept, window ring); query i of row b sits at ``off[b] + i``;
    ``token_mask`` [B, S] names the tokens that are written and that route
    (active lanes, unpadded positions). A plain full layer attends under
    ``mask`` — over the block's own keys in the published form where
    ``block`` (the one-shot prefill), else over the cache in the absorbed
    form —, a sliding layer over its window, an indexed layer over its
    indexer's choice. Returns (x, k_pages, v_pages, the step's counters:
    ``moe_step_stats``, then with indexed layers (keys attended, keys in
    context) summed over queries and indexed layers).

    ``chunk`` (``paged_fused_step``; a ``fusable`` model: plain full layers,
    one residual stream): the block is ONE row [1, lanes + C] — a decode
    step's lanes, then a prompt's chunk — through every norm, projection,
    router and expert product; it attends as ``chunk`` says
    (``_attend_latent``) once every token's row is written, and the
    counters come back by row range, [3, ...]: the lanes', the chunk's, and
    the block's own (their loads summed: what the expert products read).

    A model with Kimi Delta Attention layers carries ``{"latent", "kda"}``
    twice (the states beside the latent rows, the conv windows beside the
    rope keys): a ``linear_attention`` run's layers advance the ``kda``
    pool's rows (``_gdn_paged`` under ``ssm_rows`` [B] and ``ssm_fresh`` [B],
    None in a decode step, ``token_mask`` the tokens that advance a state:
    ``_dense_layers``' three operands) and touch no page; its runs are walked
    by index (``_scan_run``), a stack that two runs share never sliced."""
    kernel = attention_kernel == "paged"
    kern = dict(attention_kernel=attention_kernel, kernel_interpret=kernel_interpret)
    lanes = 0 if chunk is None else chunk.lanes
    # several residual streams (``hc_mult`` > 1): the carry is [B, S, n dim]
    # and every ``x + f(norm(x))`` is mix in -> sub-layer -> mix back
    hc = cfg.hc_mult > 1
    mix = dict(kernel=kernel, interpret=kernel_interpret)
    layered = isinstance(k_pages, dict)
    kept, ring = page_table if isinstance(page_table, tuple) else (page_table, None)
    page = (k_pages["latent"] if layered else k_pages).shape[2]
    where = {FULL: (page_idx, offset)}
    if ring is not None:
        where[SLIDING] = _write_coords(ring, positions, token_mask, page, ring=True)

    def make_layer(routed: bool, kind: str, experts):
        if kind == LINEAR:
            def kda_layer(carry, scanned):
                """A Kimi Delta Attention layer: its mixer over the kda
                pool's rows, no page written or read."""
                x, kp, vp, picked = carry
                lp, li, *ei = scanned
                mixed, states, windows = _gdn_paged(
                    lp, cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps), cfg,
                    kp["kda"], vp["kda"], li, ssm_rows, ssm_fresh, token_mask,
                    kernel, kernel_interpret)
                x = x + mixed
                y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
                if routed:
                    out, load = routed_mlp(lp, y, cfg, token_mask=token_mask,
                                           kernel=kernel, interpret=kernel_interpret,
                                           stacked=(experts, ei[0]))
                else:
                    out, load = _mlp(lp, y, cfg), None
                return (x + out, {**kp, "kda": states},
                        {**vp, "kda": windows}, picked), load

            return kda_layer

        sp = cfg.attn(kind)
        name = "window" if sp.window else "latent"
        pi, po = where[kind]

        # the stack's experts stay OUT of the scanned tree: scanned, each
        # layer's slice (1.2 GB at Kanana-2 widths) would be copied out for
        # the kernel every step; whole, the kernel indexes the layer itself
        def layer(carry, scanned):
            x, kp, vp, picked = carry
            lp, li, *ei = scanned
            ei = ei[0] if ei else None
            cp, rp = (kp[name], vp[name]) if layered else (kp, vp)
            if hc:
                streams, (x, h) = x, hc_pre(lp["mhc_attn"], x, cfg, **mix)
            y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
            cq = mla_query_latent(lp, y, sp)
            q_nope, q_rope, c, k_r = mla_project(lp, y, sp, positions, cq)
            cp = cp.at[li, pi, po].set(c.astype(cp.dtype))
            rp = rp.at[li, pi, po].set(jnp.pad(k_r.astype(rp.dtype), (
                (0, 0), (0, 0), (0, rp.shape[-1] - k_r.shape[-1]))))
            gate = mla_head_gate(lp, y, sp)
            if sp.index_topk:
                q_i, k_i, w = index_project(lp, y, cq, sp, positions)
                ip = kp["index"].at[li, pi, po].set(k_i.astype(cp.dtype))
                kp = {**kp, "index": ip}
                sel, ok = _index_select(q_i, w, ip, li, kept, positions,
                                        sp.index_topk, **kern)
                attn = _attend_selected(lp, q_nope, q_rope, cp, rp, li, kept,
                                        sel, ok, sp, gate, off=off, **kern)
                picked = picked + jnp.stack([
                    (ok & token_mask[..., None]).sum(),
                    ((positions + 1) * token_mask).sum()]).astype(jnp.int32)
            elif sp.window:
                attn = _attend_window(lp, q_nope, q_rope, cp, rp, li, ring,
                                      off, positions, sp, gate, **kern)
            elif block:
                attn = mla_expanded_attention(lp, q_nope, q_rope, c, k_r,
                                              mask, sp, gate)
            else:
                attn = _attend_latent(lp, q_nope, q_rope, cp, rp, li, kept,
                                      off, mask, sp, gate=gate, chunk=chunk,
                                      **kern)
            if layered:
                kp, vp = {**kp, name: cp}, {**vp, name: rp}
            else:
                kp, vp = cp, rp
            x = hc_post(streams, attn, h, **mix) if hc else x + attn
            if hc:
                streams, (x, h) = x, hc_pre(lp["mhc_mlp"], x, cfg, **mix)
            y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
            if routed:
                out, load = routed_mlp(lp, y, cfg, token_mask=token_mask,
                                       kernel=kernel, interpret=kernel_interpret,
                                       stacked=(experts, ei), lanes=lanes)
            else:
                out, load = _mlp(lp, y, cfg), None
            x = hc_post(streams, out, h, **mix) if hc else x + out
            return (x, kp, vp, picked), load
        return layer

    if hc:
        x = hc_expand(x, cfg)
    carry = (x, k_pages, v_pages, jnp.zeros((2,), jnp.int32))
    loads = []
    if cfg.linear:  # by index: a stack that two runs share is never sliced
        for name, first, stop, kind, routed, kind_first in layer_runs(cfg):
            carry, load = _scan_run(
                make_layer(routed, kind, params[name].get("experts")), carry,
                params[name], first, stop, kind_first, routed)
            if routed:
                loads.append(load)
    else:
        for stack, routed, kind, kind_first in layer_stacks(params, cfg):
            n = stack["attn_norm"]["scale"].shape[0]
            scanned = {k: v for k, v in stack.items() if k != "experts"}
            carry, load = jax.lax.scan(
                make_layer(routed, kind, stack.get("experts")), carry,
                (scanned, kind_first + jnp.arange(n), jnp.arange(n)))
            if routed:
                loads.append(load)
    x, k_pages, v_pages, picked = carry
    if hc:
        x = hc_collapse(x, cfg)
    stats = _routing_stats(loads, cfg, chunk)
    if cfg.index_topk:
        stats = jnp.concatenate([stats, picked])
    return x, k_pages, v_pages, stats


def _tiled(fn, tile: int, *per_query):
    """``fn`` over the queries (axis 1 of every array) ``tile`` at a time,
    one tile after another: a chunk's per-query intermediates ([queries,
    keys, width] gathers, [queries, heads, keys] scores) stay a tile's."""
    s = per_query[0].shape[1]
    if s <= tile or s % tile:
        return fn(*per_query)
    split = [jnp.moveaxis(a.reshape(a.shape[0], s // tile, tile, *a.shape[2:]), 1, 0)
             for a in per_query]
    out = jax.lax.map(lambda xs: fn(*xs), tuple(split))
    return jax.tree_util.tree_map(
        lambda o: jnp.moveaxis(o, 0, 1).reshape(o.shape[1], s, *o.shape[3:]), out)


#: queries of a chunk the XLA forms of the indexed layer take at a time
_QUERY_TILE = 64


def _index_select(q_i, w, index_pages, layer, page_table, positions, topk: int,
                  attention_kernel: str, kernel_interpret: bool):
    """The indexer's choice for each query: the ``topk`` positions of
    largest index score among the keys not after it (``decoder.
    index_scores`` against the row's cached index keys, read through the
    page table; exact: ``jax.lax.top_k`` of float32 scores, of equal scores
    the earlier position). Returns (positions [B, S, K] int32, ``ok`` [B, S,
    K]: False where the context has fewer than K keys and the entry names
    none) with K = min(topk, context) — or, ``"paged"``, the same choice as
    a float32 mask over the context [B, S, context] (1: attend) and ``ok``:
    what the latent kernel reads the pool in place under. No sort stands
    behind that mask: ``ops/topk_select.dsa_topk_select`` finds each
    query's K-th score and the position its equals are taken up to."""
    b, ctx = page_table.shape[0], page_table.shape[1] * index_pages.shape[2]
    k, key_pos = min(topk, ctx), jnp.arange(ctx)

    def select(q_i, w, positions):
        if attention_kernel == "paged":
            from arkflow_tpu.ops.ragged_attention import dsa_index_scores
            from arkflow_tpu.ops.topk_select import dsa_topk_select

            scores = dsa_index_scores(q_i, w, index_pages, layer, page_table,
                                      positions[:, 0], interpret=kernel_interpret)
            chosen = dsa_topk_select(scores, positions, k=k,
                                     interpret=kernel_interpret)
            # entry j names a key while the query has seen more than j
            return chosen, jnp.arange(k) <= positions[..., None]
        keys = index_pages[layer, page_table].reshape(b, ctx, -1)
        seen = key_pos <= positions[..., None]
        scores = jnp.where(seen, index_scores(q_i, w, keys), -jnp.inf)
        top, idx = jax.lax.top_k(scores, k)
        return idx.astype(jnp.int32), top > -jnp.inf

    return _tiled(select, _QUERY_TILE, q_i, w, positions)


def _attend_selected(lp, q_nope, q_rope, c_pages, r_pages, layer, page_table,
                     sel, ok, cfg, gate, attention_kernel: str,
                     kernel_interpret: bool, off=None):
    """Absorbed latent attention of each query over the positions chosen
    for it (``_index_select``). ``"paged"``: ``sel`` is the choice as a
    float32 mask over the context [B, S, context]; the latent kernel reads
    the pools in place, every page once for all of a chunk's queries, and
    the mask narrows each query's keys (``off``: each row's first query
    position). Otherwise the plain-XLA form the kernel is held to: ``sel``
    int32 [B, S, K] (valid entries first, ``ok``), their latent rows and
    rope keys gathered by token index out of the pools ([B, S, K, width])
    and scored as ``_attend_latent`` scores a context."""
    page = c_pages.shape[2]
    q_lat = mla_absorb_query(lp, q_nope, cfg)
    scale = cfg.softmax_scale
    if attention_kernel == "paged":
        from arkflow_tpu.ops.ragged_attention import mla_paged_attention

        o_lat = mla_paged_attention(
            q_lat, q_rope, c_pages, r_pages, layer, page_table, off,
            scale=scale, allowed=sel, interpret=kernel_interpret,
            name="dsa_sparse_attention")
        return mla_output(lp, o_lat, cfg, gate)
    phys = jnp.take_along_axis(page_table[:, None, :], sel // page, axis=2)

    def attend(q_lat, q_rope, phys, sel, ok):
        cc = c_pages[layer, phys, sel % page].astype(q_lat.dtype)  # [B, T, K, L]
        rr = r_pages[layer, phys, sel % page, :q_rope.shape[-1]].astype(q_lat.dtype)
        scores = (jnp.einsum("bqhl,bqkl->bqhk", q_lat, cc)
                  + jnp.einsum("bqhr,bqkr->bqhk", q_rope, rr)
                  ).astype(jnp.float32) * scale
        scores = jnp.where(ok[:, :, None, :], scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_lat.dtype)
        return jnp.einsum("bqhk,bqkl->bqhl", probs, cc)

    o_lat = _tiled(attend, _QUERY_TILE, q_lat, q_rope, phys, sel, ok)
    return mla_output(lp, o_lat, cfg, gate)


def _attend_window(lp, q_nope, q_rope, c_pages, r_pages, layer, ring, off,
                   positions, cfg, gate, attention_kernel: str,
                   kernel_interpret: bool):
    """Absorbed latent attention of a sliding layer: query at position t
    over the keys ``t - window < s <= t``, read through the row's ring of
    window pages (``window_ring_pages``: logical page i in column i %
    columns; pages the window has passed were freed and may be another
    page's by now — the bound hides them). ``"paged"`` reads the pool in
    place (``mla_paged_attention`` with its lower bound)."""
    q_lat = mla_absorb_query(lp, q_nope, cfg)
    scale = cfg.softmax_scale
    b, cols = ring.shape
    page = c_pages.shape[2]
    if attention_kernel == "paged":
        from arkflow_tpu.ops.ragged_attention import mla_paged_attention

        o_lat = mla_paged_attention(
            q_lat, q_rope, c_pages, r_pages, layer, ring, off, scale=scale,
            window=cfg.window, interpret=kernel_interpret,
            name="swa_latent_attention")
        return mla_output(lp, o_lat, cfg, gate)
    # column j holds the newest logical page i <= the step's last with
    # i % columns == j: its tokens' positions follow from that
    last = (off + positions.shape[1] - 1) // page                  # [B]
    j = jnp.arange(cols)[None, :]
    logical = last[:, None] - (last[:, None] - j) % cols           # [B, cols]
    key_pos = (logical[:, :, None] * page + jnp.arange(page)).reshape(b, -1)
    cc = c_pages[layer, ring].reshape(b, cols * page, -1).astype(q_lat.dtype)
    rr = r_pages[layer, ring, :, :q_rope.shape[-1]].reshape(
        b, cols * page, -1).astype(q_lat.dtype)
    qp, kpos = positions[:, None, :, None], key_pos[:, None, None, :]
    mask = (kpos <= qp) & (kpos > qp - cfg.window) & (kpos >= 0)
    return mla_output(lp, _masked_latent_attention(q_lat, q_rope, cc, rr, mask,
                                                   scale), cfg, gate)


def _attend_latent(lp, q_nope, q_rope, c_pages, r_pages, layer, page_table,
                   off, mask, cfg, attention_kernel: str,
                   kernel_interpret: bool, gate=None,
                   chunk: Optional[_RidingChunk] = None):
    """Absorbed latent attention over the paged cache: the queries are
    carried into the latent space (``W_uk`` absorbed), scored against the
    cached latent rows and rope keys of every head's ONE shared row per
    token, the value sum is taken over the latent rows, and ``W_uv`` /
    ``o_proj`` finish. ``"paged"`` reads the pools in place
    (ops/ragged_attention.mla_paged_attention; ``off`` is each row's first
    query position); ``"gather"`` materializes the layer's context and
    masks with ``mask`` — the reference.

    ``chunk`` (a fused step's block, ONE row [1, lanes + C]): the two weight
    products run once over the block; between them the lanes attend as
    [lanes, 1] queries under ``page_table`` / ``off`` / ``mask`` and the
    chunk as [1, C] under its own — the two steps' own two calls."""
    q_lat = mla_absorb_query(lp, q_nope, cfg)
    scale = cfg.softmax_scale

    def context(q_lat, q_rope, page_table, off, mask):
        """``sum p c`` of the queries [rows, positions] over their rows'
        cached context."""
        if attention_kernel == "paged":
            from arkflow_tpu.ops.ragged_attention import mla_paged_attention

            return mla_paged_attention(q_lat, q_rope, c_pages, r_pages, layer,
                                       page_table, off, scale=scale,
                                       interpret=kernel_interpret)
        b, ctx = page_table.shape[0], page_table.shape[1] * c_pages.shape[2]
        cc = c_pages[layer][page_table].reshape(b, ctx, -1).astype(q_lat.dtype)
        rr = r_pages[layer][page_table][..., :q_rope.shape[-1]].reshape(
            b, ctx, -1).astype(q_lat.dtype)
        return _masked_latent_attention(q_lat, q_rope, cc, rr, mask, scale)

    if chunk is None:
        o_lat = context(q_lat, q_rope, page_table, off, mask)
    else:  # the lanes a row each, then the chunk's row
        n = chunk.lanes
        o_lat = jnp.concatenate([
            context(q_lat[0, :n, None], q_rope[0, :n, None], page_table, off,
                    mask).reshape(1, n, *q_lat.shape[2:]),
            context(q_lat[:, n:], q_rope[:, n:], chunk.table, chunk.off,
                    chunk.mask)], axis=1)
    return mla_output(lp, o_lat, cfg, gate)


def _masked_latent_attention(q_lat, q_rope, cc, rr, mask, scale: float):
    """``sum p c`` of absorbed queries [B, Q, H, *] over a row's gathered
    latent rows ``cc`` and rope keys ``rr`` [B, K, *] under ``mask``
    [B, 1, Q, K]: the plain-XLA form the kernels are held to."""
    scores = (jnp.einsum("bqhl,bkl->bhqk", q_lat, cc)
              + jnp.einsum("bqhr,bkr->bhqk", q_rope, rr)
              ).astype(jnp.float32) * scale
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q_lat.dtype)
    return jnp.einsum("bhqk,bkl->bqhl", probs, cc)


def latent_kernel_probe(params: dict, cfg: DecoderConfig, page_size: int,
                        kernel_interpret: bool = False) -> list:
    """(name, reference, kernel output) for each Pallas kernel a latent
    model's ``decode_kernel: paged`` serves with, on the model's own first
    layers at their real widths and seeded inputs: the latent attention
    (decode and a 2-token chunk, rows on non-contiguous pages, one crossing
    a page boundary) against the gather path; with a layer pattern the
    sliding layer's window attention, the indexer's scores, the attention
    over a GIVEN selection and the choice of the largest GIVEN scores, each
    against its plain-XLA form; with linear_attention layers the per-channel
    delta rule's two kernels against their plain forms (``_gdn_probe``); and
    the expert product on GIVEN routing against plain XLA over the experts
    routed to.

    Kernel by kernel, not logits of the whole model as the per-head probe
    does: with routed experts two arithmetically different attention paths
    round a router's input differently, a near-tie then picks another
    expert, and the logits differ by tenths though no kernel is wrong
    (seen on the chip: 4 of 6 seeds, PERF.md PR 27)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(1234), 32))
    rand = lambda shape: jax.random.normal(  # noqa: E731
        next(keys), shape, jnp.float32).astype(jnp.bfloat16)
    kerns = [dict(attention_kernel=k, kernel_interpret=kernel_interpret)
             for k in ("gather", "paged")]
    n0 = page_size + 1
    pages_per = -(-(n0 + 3) // page_size)
    table = jnp.stack([jnp.arange(1, 2 * pages_per, 2)[::-1],
                       jnp.arange(2, 2 * pages_per + 1, 2)]).astype(jnp.int32)
    off = jnp.asarray([n0, 1], jnp.int32)
    ctx = pages_per * page_size
    steps = (("decode", 1), ("chunk", 2))
    out = []

    def first_layer(kind):
        name = next(r[0] for r in layer_runs(cfg) if r[3] == kind)
        return jax.tree_util.tree_map(lambda a: a[0], {
            k: v for k, v in params[name].items() if k != "experts"})

    def pools(sp, pages):  # the rope keys as held: zeros behind the key
        cp, rp = (rand((1, pages, page_size, w))
                  for w in (sp.kv_lora_rank, sp.qk_rope_head_dim))
        return cp, jnp.pad(rp, ((0, 0),) * 3 + (
            (0, _held_lanes(rp.shape[-1]) - rp.shape[-1]),))

    def queries(sp, c):
        return (rand((2, c, sp.heads, sp.qk_nope_head_dim)),
                rand((2, c, sp.heads, sp.qk_rope_head_dim)))

    sp, lp = cfg.attn(FULL), first_layer(FULL)
    cp, rp = pools(sp, 1 + 2 * pages_per)
    for name, c in steps:
        q_nope, q_rope = queries(sp, c)
        positions = off[:, None] + jnp.arange(c)[None, :]
        mask = jnp.arange(ctx)[None, None, None, :] <= positions[:, None, :, None]
        out.append((f"latent_attention_{name}", *(
            _attend_latent(lp, q_nope, q_rope, cp, rp, 0, table, off, mask, sp,
                           **kern) for kern in kerns)))
    if SLIDING in cfg.kinds:
        # rows deep into their window: one at the ring's wrap, one short
        ws, wl = cfg.attn(SLIDING), first_layer(SLIDING)
        cols = window_ring_pages(cfg, page_size, 2)
        ring = (1 + jnp.arange(2 * cols, dtype=jnp.int32)).reshape(2, cols)
        wcp, wrp = pools(ws, 1 + 2 * cols)
        woff = jnp.asarray([cols * page_size + ws.window // 2, 3], jnp.int32)
        for name, c in steps:
            q_nope, q_rope = queries(ws, c)
            positions = woff[:, None] + jnp.arange(c)[None, :]
            out.append((f"swa_latent_attention_{name}", *(
                _attend_window(wl, q_nope, q_rope, wcp, wrp, 0, ring, woff,
                               positions, ws, None, **kern) for kern in kerns)))
    if cfg.index_topk:
        from arkflow_tpu.ops.ragged_attention import dsa_index_scores

        ip = rand((1, 1 + 2 * pages_per, page_size, sp.index_head_dim))
        for name, c in steps:
            q_i = rand((2, c, sp.index_n_heads, sp.index_head_dim))
            w = jax.random.normal(next(keys), (2, c, sp.index_n_heads), jnp.float32)
            positions = off[:, None] + jnp.arange(c)[None, :]
            seen = jnp.arange(ctx)[None, None, :] <= positions[..., None]
            out.append((f"dsa_index_scores_{name}", *(
                jnp.where(seen, s, 0.0) for s in (
                    index_scores(q_i, w, ip[0, table].reshape(2, ctx, -1)),
                    dsa_index_scores(q_i, w, ip, 0, table, off,
                                     interpret=kernel_interpret)))))
        # attention over a GIVEN selection of ``page`` positions a query
        # (the first row's short of one entry): the kernel in place under
        # the choice as a mask against the rows gathered by index
        k = page_size
        for name, c in steps:
            q_nope, q_rope = queries(sp, c)
            sel = jnp.stack([jax.random.permutation(next(keys), n0)[:k]
                             for _ in range(2 * c)]).reshape(2, c, k)
            sel = jnp.where(jnp.arange(2)[:, None, None] == 1, 0, sel).astype(jnp.int32)
            ok = jnp.broadcast_to(jnp.arange(k)[None, None, :] < jnp.asarray(
                [k - 1, 1])[:, None, None], sel.shape)
            chosen = (jax.nn.one_hot(sel, ctx) * ok[..., None]).max(2)
            out.append((
                f"dsa_sparse_attention_{name}",
                _attend_selected(lp, q_nope, q_rope, cp, rp, 0, table, sel, ok,
                                 sp, None, **kerns[0]),
                _attend_selected(lp, q_nope, q_rope, cp, rp, 0, table,
                                 chosen.astype(jnp.float32), ok, sp, None,
                                 off=off, **kerns[1])))
        # the choice itself, of half a page's keys a query among scores in
        # steps of a half (equals across the threshold; the second row has
        # seen fewer keys than that): the threshold kernel's mask against
        # the keys ``jax.lax.top_k`` names (keys of their own: the other
        # probes' inputs stay those the chip has seen)
        from arkflow_tpu.ops.topk_select import dsa_topk_select

        k = max(1, page_size // 2)
        for (name, c), key in zip(steps, jax.random.split(jax.random.PRNGKey(4321))):
            s = jnp.round(2 * jax.random.normal(key, (2, c, ctx), jnp.float32)) / 2
            positions = off[:, None] + jnp.arange(c)[None, :]
            seen = jnp.arange(ctx)[None, None, :] <= positions[..., None]
            top, idx = jax.lax.top_k(jnp.where(seen, s, -jnp.inf), k)
            named = (jax.nn.one_hot(idx, ctx) * (top > -jnp.inf)[..., None]).max(2)
            out.append((f"dsa_topk_select_{name}", named, dsa_topk_select(
                s, positions, k=k, interpret=kernel_interpret)))
    if cfg.hc_mult > 1:
        out.extend(_mhc_probe(lp, cfg, rand, kernel_interpret))
    if cfg.linear:
        out.extend(_gdn_probe(cfg, kernel_interpret))
    out.append(_expert_probe(params, cfg, keys, rand, kernel_interpret))
    return out


def _mhc_probe(lp: dict, cfg: DecoderConfig, rand, kernel_interpret: bool) -> list:
    """(name, plain XLA, kernel) of the two mixing kernels (``ops/mhc_mix``)
    on one token tile of seeded streams under the first layer's own leaves:
    ``mhc_pre``'s sub-layer input beside its coefficients, ``mhc_post``'s
    streams from GIVEN coefficients (the plain form's)."""
    from arkflow_tpu.ops.mhc_mix import TOKEN_TILE, n_coefficients

    n = cfg.hc_mult
    x = rand((1, TOKEN_TILE, n * cfg.dim))
    y = rand((1, TOKEN_TILE, cfg.dim))
    u, h = hc_pre(lp["mhc_attn"], x, cfg)
    uk, hk = hc_pre(lp["mhc_attn"], x, cfg, kernel=True, interpret=kernel_interpret)
    assert hk.shape[-1] != h.shape[-1], "the probe's streams must reach the kernel"
    k = n_coefficients(n)
    joined = lambda u, h: jnp.concatenate(  # noqa: E731
        [u.astype(jnp.float32), h[..., :k]], axis=-1)
    given = jnp.pad(h, ((0, 0), (0, 0), (0, hk.shape[-1] - k)))
    return [("mhc_pre", joined(u, h), joined(uk, hk)),
            ("mhc_post", hc_post(x, y, h).reshape(1, TOKEN_TILE, -1),
             hc_post(x, y, given, kernel=True,
                     interpret=kernel_interpret).reshape(1, TOKEN_TILE, -1))]


def _expert_probe(params: dict, cfg: DecoderConfig, keys, rand,
                  kernel_interpret: bool) -> tuple:
    """(name, reference, kernel output) of the expert product on GIVEN
    routing: tokens routed among a HANDFUL of the first expert layer's
    experts, so that the XLA twin, which multiplies every expert it is
    given, copies those few (94 MB at Kanana-2 widths, by static slices: an
    index array over the stack cost 1.9 GB on a v5e) and not the layer
    (1.2 GB); the kernel reads the whole stack as when serving."""
    e, k = cfg.held[1], cfg.num_experts_per_tok
    few = min(e, 8)
    x = rand((16, cfg.dim))
    chosen = jnp.argsort(jax.random.uniform(next(keys), (16, few)), axis=-1)[:, :min(k, few)]
    cw = jax.nn.one_hot(chosen, e, dtype=jnp.float32).sum(1) * (
        cfg.routed_scaling_factor / k)
    cw = jnp.concatenate([cw, jnp.ones((16, cfg.shared_stack))], axis=-1)
    ex = params[next(r[0] for r in layer_runs(cfg) if r[4])]["experts"]
    cols = jnp.concatenate([jnp.arange(few), jnp.arange(e, e + cfg.shared_stack)])
    product, dense, names = expert_products(cfg)
    twin = [jnp.concatenate([ex[w][0, :few], ex[w][0, e:]])  # static slices
            for w in names]
    return ("expert_product", dense(x, cw[:, cols], *twin),
            product(x, cw, *(ex[w] for w in names), 0, interpret=kernel_interpret))


def gqa_kernel_probe(params: dict, cfg: DecoderConfig, page_size: int,
                     kernel_interpret: bool = False) -> list:
    """``latent_kernel_probe`` for a per-head K/V model that stacks by runs
    (routed experts, a layer pattern): (name, reference, kernel output) of
    the paged attention kernel on seeded queries and pools at the model's
    own head sizes — decode and a 2-token chunk, rows on non-contiguous
    pages, one crossing a page boundary — against the gathered context
    under its mask; with sliding layers the windowed call (rows deep into
    their window, one at the ring's wrap, one short) against
    ``_attend_ring``; the expert product on GIVEN routing; and with
    linear_attention layers the delta rule's two kernels against their plain
    forms (``_gdn_probe``); with a compacting window cache the chunk
    summariser against its plain form."""
    keys = iter(jax.random.split(jax.random.PRNGKey(1234), 32))
    rand = lambda shape: jax.random.normal(  # noqa: E731
        next(keys), shape, jnp.float32).astype(jnp.bfloat16)
    n0 = page_size + 1
    pages_per = -(-(n0 + 3) // page_size)
    table = jnp.stack([jnp.arange(1, 2 * pages_per, 2)[::-1],
                       jnp.arange(2, 2 * pages_per + 1, 2)]).astype(jnp.int32)
    off = jnp.asarray([n0, 1], jnp.int32)
    ctx = pages_per * page_size
    steps = (("decode", 1), ("chunk", 2))
    out = []

    def operands(kind, pages):
        """A kind's sizes, its seeded pools (a held key's padding is seeded
        too: the queries' zeros must hide it) and its first layer's sinks."""
        sp = cfg.gqa(kind)
        stack = params[next(r[0] for r in layer_runs(cfg) if r[3] == kind)]
        sink = stack["attn_sink"][0] if sp.sink else None
        pool = next(p for p in cache_spec(cfg) if bool(p.window) == bool(sp.window))
        kshape, vshape = dataclasses.replace(pool, layers=1).shapes(pages, page_size)
        return sp, rand(kshape), rand(vshape), sink

    sp, kp, vp, sink = operands(FULL, 1 + 2 * pages_per)
    group = cfg.heads // sp.kv_heads
    for name, c in steps:
        q = rand((2, c, cfg.heads, sp.dk))
        positions = off[:, None] + jnp.arange(c)[None, :]
        mask = jnp.arange(ctx)[None, None, None, :] <= positions[:, None, :, None]
        k = jnp.repeat(_read_keys(kp, 0, table, sp.dk, sp.kv_heads), group, axis=2)
        v = jnp.repeat(_read_rows(vp, 0, table, sp.kv_heads), group, axis=2)
        out.append((f"paged_attention_{name}",
                    cm.attention(q, k, v, mask, sink=sink),
                    _attend_paged(q, kp, vp, 0, table, off, cfg, None,
                                  kernel_interpret, sink=sink)))
    if SLIDING in cfg.kinds:
        window = cfg.sliding_window
        cols = window_ring_pages(cfg, page_size, 2)
        ring = (1 + jnp.arange(2 * cols, dtype=jnp.int32)).reshape(2, cols)
        sp, kp, vp, sink = operands(SLIDING, 1 + 2 * cols)
        woff = jnp.asarray([cols * page_size + window // 2, 3], jnp.int32)
        for name, c in steps:
            q = rand((2, c, cfg.heads, sp.dk))
            positions = woff[:, None] + jnp.arange(c)[None, :]
            out.append((f"paged_window_attention_{name}",
                        _attend_ring(q, kp, vp, 0, ring, positions, window, sink),
                        _attend_paged(q, kp, vp, 0, ring, woff, cfg, None,
                                      kernel_interpret, window, sink)))
    if cfg.routed:
        out.append(_expert_probe(params, cfg, keys, rand, kernel_interpret))
    if cfg.linear:
        out.extend(_gdn_probe(cfg, kernel_interpret))
    if cfg.mamba:
        out.extend(_ssm_probe(cfg, kernel_interpret))
    if cfg.eva:  # the summariser over two windows' worth of seeded rows
        from arkflow_tpu.ops.eva_summarise import (eva_summarise,
                                                   eva_summarise_plain)

        stack = params[layer_runs(cfg)[0][0]]
        phi, mu = stack["eva_phi"][0], stack["eva_mu"][0]
        k, v = (rand((2, cfg.window_size, sp.kv_heads, d))
                for d in (sp.dk, sp.dv))
        ref = eva_summarise_plain(k, v, phi, mu, cfg.chunk_size)
        got = eva_summarise(k, v, phi, mu, chunk=cfg.chunk_size,
                            interpret=kernel_interpret)
        out.extend((f"eva_summarise_{name}", r.reshape(2, -1), g.reshape(2, -1))
                   for name, r, g in zip(("keys", "values"), ref, got))
    return out


def _ssm_probe(cfg: DecoderConfig, kernel_interpret: bool) -> list:
    """(name, reference, kernel output) of the Mamba-2 recurrence's two
    kernels against their plain forms (``ops/ssm_scan``) at the model's own
    head count, head size, state size and groups, over a seeded pool AS HELD
    (``heads_packed``): a decode step of two lanes on rows 2 and 1, and a
    chunk of two blocks, its first row fresh, its last positions padded (a
    zero step). Each line is the outputs and the rows' states after, joined."""
    from arkflow_tpu.ops.ssm_scan import ssm_chunk_scan, ssm_state_update

    h, p, n, g = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
                  cfg.mamba_n_groups)
    k = cfg.ssm_heads_packed
    keys = iter(jax.random.split(jax.random.PRNGKey(4343), 8))
    t = 2 * cfg.mamba_chunk_size
    pool = jax.random.normal(next(keys), (1, 3, h // k, n, k * p), jnp.float32)
    x = jax.random.normal(next(keys), (2, t, h, p), jnp.float32)
    live = (jnp.arange(t) < t - 3)[None, :, None]
    dt = jnp.where(live, jnp.exp(jax.random.uniform(
        next(keys), (2, t, h), jnp.float32, jnp.log(1e-3), jnp.log(1e-1))), 0.0)
    a = -jax.random.uniform(next(keys), (h,), jnp.float32, 1.0, 16.0)
    bm, cmat = (jax.random.normal(next(keys), (2, t, g, n), jnp.float32)
                for _ in range(2))
    rows = jnp.asarray([2, 1], jnp.int32)
    fresh = jnp.asarray([True, False])

    def joined(o, states):
        return jnp.concatenate([o.reshape(-1, p), states[0, rows].reshape(-1, p)])

    def update(**kern):
        return joined(*ssm_state_update(pool, 0, rows, x[:, 0], dt[:, 0], a,
                                        bm[:, 0], cmat[:, 0], **kern))

    def scan(**kern):
        return joined(*ssm_chunk_scan(pool, 0, rows, fresh, x, dt, a, bm, cmat,
                                      cfg.mamba_chunk_size, **kern))

    kern = dict(kernel=True, interpret=kernel_interpret)
    return [("ssm_state_update", update(), update(**kern)),
            ("ssm_chunk_scan", scan(), scan(**kern))]


def _gdn_probe(cfg: DecoderConfig, kernel_interpret: bool) -> list:
    """(name, reference, kernel output) of the delta rule's two kernels
    against their plain forms (``ops/gdn_scan``; Kimi Delta Attention's:
    ``ops/kda_scan``, a gate a key channel) at the model's own head
    counts and widths, on seeded operands of the statistics the layer hands
    them (unit keys, scaled unit queries, gates of every strength): a decode
    step of two lanes on rows 2 and 1 of a seeded pool, and a chunk of two
    blocks and a ragged tail, its first row fresh, its last positions padded
    (``g = beta = 0``). Each line is the outputs and the rows' states after,
    joined."""
    from arkflow_tpu.ops.gdn_scan import BLOCK

    *_, state_update, chunk_scan = linear_mixer(cfg)
    nv, dk, dv = ((cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim) if cfg.kda
                  else (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                        cfg.linear_value_head_dim))
    keys = iter(jax.random.split(jax.random.PRNGKey(4242), 8))
    t = 2 * BLOCK + 5
    pool = jax.random.normal(next(keys), (1, 3, nv, dk, dv), jnp.float32)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.square(x).sum(-1, keepdims=True) + 1e-6)
    q = unit(jax.random.normal(next(keys), (2, t, nv, dk), jnp.float32)) * dk ** -0.5
    k = unit(jax.random.normal(next(keys), (2, t, nv, dk), jnp.float32))
    v = jax.random.normal(next(keys), (2, t, nv, dv), jnp.float32)
    live = (jnp.arange(t) < t - 3)[None, :, None]
    g = jnp.where(live, -jnp.exp(2 * jax.random.normal(next(keys), (2, t, nv)) - 2), 0.0)
    if cfg.kda:  # a gate a key channel: each head's spread over its channels
        g = g[..., None] * jnp.exp(0.5 * jax.random.normal(
            jax.random.PRNGKey(2424), (2, t, nv, dk)))
    beta = jnp.where(live, jax.nn.sigmoid(jax.random.normal(next(keys), (2, t, nv))), 0.0)
    rows = jnp.asarray([2, 1], jnp.int32)
    fresh = jnp.asarray([True, False])

    def joined(o, states):
        return jnp.concatenate([o.reshape(-1, dv), states[0, rows].reshape(-1, dv)])

    def update(**kern):
        return joined(*state_update(pool, 0, rows, q[:, 0], k[:, 0], v[:, 0],
                                    g[:, 0], beta[:, 0], **kern))

    def scan(**kern):
        return joined(*chunk_scan(pool, 0, rows, fresh, q, k, v, g, beta, **kern))

    kern = dict(kernel=True, interpret=kernel_interpret)
    name = "kda" if cfg.kda else "gdn"
    return [(f"{name}_state_update", update(), update(**kern)),
            (f"{name}_chunk_scan", scan(), scan(**kern))]


def _write_keys(kp, k, layer, pi, po, parts: int):
    """The K pool with the step's keys ``k`` [B, S, kv heads, dk] written
    at (``layer``, ``pi``, ``po``): whole, or — a key held in ``parts``
    (``GqaSpec.key_parts``) — padded with zeros to whole parts, part ``p``
    into the pool's layer ``p * layers + layer``."""
    if parts == 1:
        return _write_rows(kp, k, layer, pi, po)
    w, layers = kp.shape[-1], kp.shape[0] // parts
    k = jnp.pad(k, ((0, 0),) * 3 + ((0, parts * w - k.shape[-1]),)).astype(kp.dtype)
    for p in range(parts):
        kp = kp.at[p * layers + layer, pi, po].set(k[..., p * w:(p + 1) * w])
    return kp


def _write_rows(pool, x, layer, pi, po):
    """``pool`` with the step's K or V rows ``x`` [B, S, kv heads, width]
    written at (``layer``, ``pi``, ``po``): a row-major pool takes a
    token's heads side by side."""
    if pool.ndim == 4:
        x = x.reshape(*x.shape[:2], -1)
    elif pool.shape[3] == 1 < x.shape[2]:  # a head a layer (``split_heads``)
        layers = pool.shape[0] // x.shape[2]
        for j in range(x.shape[2]):
            pool = pool.at[j * layers + layer, pi, po].set(
                x[:, :, j:j + 1].astype(pool.dtype))
        return pool
    return pool.at[layer, pi, po].set(x.astype(pool.dtype))


def _read_rows(pool, layer, table, heads: int):
    """A layer's K or V rows [B, columns * page, kv heads, width] gathered
    through ``table`` [B, columns], however the pool holds a token's heads."""
    b, cols = table.shape
    if pool.ndim == 5 and pool.shape[3] == 1 < heads:  # a head a layer
        layers = pool.shape[0] // heads
        return jnp.concatenate([pool[j * layers + layer, table]
                                for j in range(heads)], axis=3).reshape(
            b, cols * pool.shape[2], heads, -1)
    return pool[layer, table].reshape(b, cols * pool.shape[2], heads, -1)


def _read_keys(kp, layer, table, dk: int, heads: int = 1):
    """A layer's keys [B, columns * page, kv heads, dk] gathered through
    ``table`` [B, columns], a key held in parts joined and cut to ``dk``
    (``heads``: the K/V heads of a pool that holds a head a layer)."""
    if kp.ndim == 4:
        return _read_rows(kp, layer, table, kp.shape[-1] // dk)
    if kp.shape[3] == 1 < heads:
        return _read_rows(kp, layer, table, heads)
    parts = -(-dk // kp.shape[-1])
    b, cols = table.shape
    k = jnp.concatenate(
        [kp[p * (kp.shape[0] // parts) + layer, table] for p in range(parts)],
        axis=-1) if parts > 1 else kp[layer, table]
    k = k.reshape(b, cols * kp.shape[2], kp.shape[3], -1)
    return k if k.shape[-1] == dk else k[..., :dk]


def _constrain(x, sharding):
    """Pin a carried pool to its tensor-parallel sharding (KV heads over
    ``tp``). Under GSPMD the layer scan would otherwise be free to
    all-gather the pools at every step — hundreds of MB of HBM churn; the
    constraint keeps scatter/gather partitioned. ``None`` (single-device
    serving) is a no-op so the unsharded path traces identically."""
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def _attend_paged(q, k_pages, v_pages, layer, page_table, off,
                  cfg: DecoderConfig, kv_sharding, interpret: bool,
                  window: int = 0, sink=None):
    """Page-table-indirect flash attention over layer ``layer`` of the
    WHOLE pools (ops/ragged_attention.paged_flash_attention): query i of
    row b sits at absolute position ``off[b] + i`` and attends keys
    0..off+i, read straight from the pools — neither the layer's slice nor
    the [B, ctx, heads, dh] gather+repeat the dense reference materializes
    per layer per step ever exists. ``window`` > 0: a sliding layer, the
    pools its window pools and ``page_table`` the rows' rings. ``sink``
    [heads]: the layer's sink logits, if its kind has them.

    Under tensor parallelism the kernel runs inside ``shard_map`` over the
    ``kv_sharding`` mesh's tp axis: attention is independent per KV head,
    q's head dim splits into the same contiguous head groups the pools
    shard by (tp | kv_heads is validated at server build), so each shard
    attends its local heads with zero collectives — the pools are never
    all-gathered."""
    from arkflow_tpu.ops.ragged_attention import paged_flash_attention

    if kv_sharding is None:
        sp = cfg.gqa(SLIDING if window else FULL)
        if sp.split_heads:
            # a head a layer of the pools (``GqaSpec.split_heads``): a call
            # a K/V head, over that head's own query heads
            kvh = sp.kv_heads
            layers, group = k_pages.shape[0] // kvh, q.shape[2] // kvh
            return jnp.concatenate([paged_flash_attention(
                q[:, :, j * group:(j + 1) * group], k_pages, v_pages,
                layer + j * layers, page_table, off, interpret=interpret,
                window=window,
                sink=None if sink is None else sink[j * group:(j + 1) * group])
                for j in range(kvh)], axis=2)
        return paged_flash_attention(q, k_pages, v_pages, layer, page_table,
                                     off, interpret=interpret, window=window,
                                     sink=sink)
    from jax.sharding import PartitionSpec as P

    mesh = kv_sharding.mesh
    head_spec = P(None, None, "tp", None)  # q/out: [B, C, H, dh], H over tp

    return jax.shard_map(
        functools.partial(paged_flash_attention, interpret=interpret), mesh=mesh,
        in_specs=(head_spec, kv_sharding.spec, kv_sharding.spec, P(), P(), P()),
        out_specs=head_spec,
        check_vma=False,
    )(q, k_pages, v_pages, layer, page_table, off)


def _attend_ring(q, k_pages, v_pages, layer, ring, positions, window: int,
                 sink=None):
    """The plain-XLA form of a per-head sliding layer's attention over its
    window pool (what ``paged_flash_attention(window=)`` is held to): the
    row's ring of pages gathered out of the layer, each column's positions
    worked out from the step's last query (column j holds the newest
    logical page i <= the last with i % columns == j), masked to
    ``t - window < s <= t``. Pages the window has passed were freed and may
    be another row's by now: the bound hides them. Keys held in parts are
    joined (``_read_keys``); ``sink`` [heads] joins the softmax."""
    b, cols = ring.shape
    page = k_pages.shape[2]
    last = positions[:, -1] // page                                # [B]
    logical = last[:, None] - (last[:, None] - jnp.arange(cols)[None, :]) % cols
    key_pos = (logical[:, :, None] * page + jnp.arange(page)).reshape(b, -1)
    k = _read_keys(k_pages, layer, ring, q.shape[-1]).astype(q.dtype)
    kvh = k.shape[2]
    v = _read_rows(v_pages, layer, ring, kvh).astype(q.dtype)
    qp, kpos = positions[:, None, :, None], key_pos[:, None, None, :]
    mask = (kpos <= qp) & (kpos > qp - window) & (kpos >= 0)
    group = q.shape[2] // kvh
    return cm.attention(q, jnp.repeat(k, group, axis=2),
                        jnp.repeat(v, group, axis=2), mask, sink=sink)


def _last_valid(ext, valid, keep: int):
    """A window's next rows: of ``ext`` [B, keep + S, channels] — the
    window before a step, then the step's inputs — the ``keep`` rows that
    end at each row's last VALID input (``valid`` [B, S], a prefix): a
    position that is not valid leaves the window as it is."""
    return jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, keep))(
        ext, valid.sum(axis=1).astype(jnp.int32))


def _mixer_paged(lp: dict, y, cfg: DecoderConfig, states, windows, layer, rows,
                 fresh, valid, kernel: bool, interpret: bool):
    """The Mamba-2 mixer of one layer over the state pool: ``states`` /
    ``windows`` whole (``init_page_pool``), ``rows`` [B] each row's pool row
    (0: scratch — an idle lane), ``valid`` [B, S] the tokens that advance
    the state (a prefix of each row: unpadded positions, active lanes).
    ``fresh`` None: a decode step, one token a lane (``ssm_state_update``);
    else [B] bool and a chunk from the row's state — a zero state and an
    empty window where ``fresh`` — to the row's state (``ssm_chunk_scan``).
    A position that is not valid leaves state and window as they are: its
    step is 0, and the window keeps the last ``d_conv - 1`` VALID inputs.
    Returns (the mixer's output [B, S, dim], states, windows)."""
    from arkflow_tpu.ops.ssm_scan import ssm_chunk_scan, ssm_state_update

    t, keep = y.shape[1], cfg.mamba_d_conv - 1
    z, u, dt = ssm_project(lp, y, cfg)
    before = windows[layer, rows]                                 # [B, K-1, C]
    if fresh is not None:
        before = jnp.where(fresh[:, None, None], 0, before)
    ext = jnp.concatenate([before, u.astype(before.dtype)], axis=1)
    windows = windows.at[layer, rows].set(_last_valid(ext, valid, keep))
    x, step, a, bm, cmat = ssm_operands(lp, ssm_conv(lp, ext, t, cfg), dt, cfg,
                                        valid)
    kern = dict(kernel=kernel, interpret=interpret)
    if fresh is None:
        o, states = ssm_state_update(states, layer, rows, x[:, 0], step[:, 0],
                                     a, bm[:, 0], cmat[:, 0], **kern)
        o = o[:, None]
    else:
        o, states = ssm_chunk_scan(states, layer, rows, fresh, x, step, a, bm,
                                   cmat, cfg.mamba_chunk_size, **kern)
    return ssm_output(lp, o, x, z, cfg, y.dtype), states, windows


def _conv_paged(lp: dict, y, cfg: DecoderConfig, windows, layer, rows, fresh,
                valid):
    """A conv layer's mixer over the conv pool: ``windows`` whole
    (``init_page_pool``: [conv layers, slots + 1, conv_L_cache - 1, dim]),
    ``rows`` [B] each row's pool row (0: scratch — an idle lane), ``valid``
    [B, S] the tokens that advance it (a prefix of each row), ``fresh``
    None in a decode step, else [B] bool: a chunk that starts its sequence
    reads zeros, not its slot's earlier tenant (``_mixer_paged``'s rules).
    The row keeps the last ``conv_L_cache - 1`` VALID gated inputs: a
    position that is not valid leaves it as it is. Plain XLA: the pool is
    9 MB at LFM2's widths and the update a scatter of ``B`` rows in place.
    Returns (the mixer's output [B, S, dim], windows)."""
    keep = cfg.conv_L_cache - 1
    before = windows[layer, rows]                                 # [B, L-1, dim]
    if fresh is not None:
        before = jnp.where(fresh[:, None, None], 0, before)
    out, ext = short_conv(lp, y, cfg, before)
    return out, windows.at[layer, rows].set(
        _last_valid(ext, valid, keep).astype(windows.dtype))


def _gdn_paged(lp: dict, y, cfg: DecoderConfig, states, windows, layer, rows,
               fresh, valid, kernel: bool, interpret: bool):
    """A linear_attention layer's mixer over its state pool — a Gated
    DeltaNet's over ``gdn``, Kimi Delta Attention's over ``kda``: the one
    seam, ``decoder.linear_mixer`` —: ``states`` /
    ``windows`` whole (``init_page_pool``), ``rows`` / ``fresh`` / ``valid``
    as ``_mixer_paged``'s: a decode step is one token a lane
    (``gdn_state_update`` / ``kda_state_update``), a chunk runs from the
    row's state — a zero state and an empty window where ``fresh`` — to the
    row's state (``gdn_chunk_scan`` / ``kda_chunk_scan``). A position that is not valid leaves state and
    window as they are: its ``g`` and ``beta`` are 0, and the window keeps the
    last ``taps - 1`` VALID inputs. Returns (the mixer's
    output [B, S, dim], states, windows)."""
    project, conv, operands, output, update, scan = linear_mixer(cfg)
    t, keep = y.shape[1], cfg.linear_taps - 1
    u, z, b, a = project(lp, y, cfg)
    before = windows[layer, rows]                                 # [B, K-1, C]
    if fresh is not None:
        before = jnp.where(fresh[:, None, None], 0, before)
    ext = jnp.concatenate([before, u.astype(before.dtype)], axis=1)
    windows = windows.at[layer, rows].set(_last_valid(ext, valid, keep))
    q, k, v, g, beta = operands(lp, conv(lp, ext, t), b, a, cfg, valid)
    kern = dict(kernel=kernel, interpret=interpret)
    if fresh is None:
        o, states = update(states, layer, rows, q[:, 0], k[:, 0], v[:, 0],
                           g[:, 0], beta[:, 0], **kern)
        o = o[:, None]
    else:
        o, states = scan(states, layer, rows, fresh, q, k, v, g, beta, **kern)
    return output(lp, o, z, cfg, y.dtype), states, windows


class _RidingChunk(NamedTuple):
    """How the chunk behind a fused step's lanes attends (``_dense_layers``,
    ``_latent_layers``): the block's first ``lanes`` tokens are the decode
    step's, a row each;
    the rest are one prompt's chunk, one row under its own ``table`` [1, P],
    ``off`` [1] and ``mask``. A model that caches a state a sequence: the
    chunk's row of the state pool ``rows`` [1] and whether it starts its
    sequence, ``fresh`` [1] (``paged_prefill_chunk``'s two; the lanes' are
    ``_dense_layers``' ``ssm_rows``, a decode step's)."""

    lanes: int
    table: object
    off: object
    mask: object
    rows: object = None
    fresh: object = None


def _by_parts(chunk: _RidingChunk, part, state, rows, valid, *per_token):
    """The half of a state mixer that is a SEQUENCE'S, over a fused step's
    block [1, lanes + C]: ``part(state, rows, fresh, valid, *per_token) ->
    (out, state)`` first over the lanes as [lanes, 1] rows under ``rows``
    [lanes] and no ``fresh`` (a decode step's rules), then over the chunk as
    one row [1, C] under ``chunk.rows`` / ``chunk.fresh`` (a chunk's), the
    state pool handed from the one to the other as the two steps hand it.
    ``valid`` [1, lanes + C] and every ``per_token`` array [1, lanes + C,
    ...] are cut likewise; the outputs come back joined, [1, lanes + C, ...].
    The prompt's own lane is not active, so the parts write no pool row in
    common but the scratch row 0."""
    n = chunk.lanes
    out, state = part(state, rows, None, valid[0, :n, None],
                      *(a[0, :n, None] for a in per_token))
    behind, state = part(state, chunk.rows, chunk.fresh, valid[:, n:],
                         *(a[:, n:] for a in per_token))
    return jax.tree_util.tree_map(
        lambda a, b: jnp.concatenate([a.reshape(1, n, *a.shape[2:]), b], axis=1),
        out, behind), state


def _conv_fused(lp: dict, y, cfg: DecoderConfig, windows, layer, rows, valid,
                chunk: _RidingChunk):
    """``_conv_paged`` over a fused step's block ``y`` [1, lanes + C, dim]:
    the mixer's two weight products (``conv_in``, ``conv_out``) run ONCE
    over the block's rows; between them each part reads its rows' windows,
    sums its taps and writes its windows back under its own step's rules
    (``_by_parts``: the lanes under ``rows``, the chunk under its own row
    and ``fresh``). Returns (the mixer's output, windows)."""
    keep = cfg.conv_L_cache - 1
    gate_c, v = conv_gate(lp, y)

    def part(windows, rows, fresh, valid, v):
        before = windows[layer, rows]                             # [B, L-1, dim]
        if fresh is not None:
            before = jnp.where(fresh[:, None, None], 0, before)
        ext = jnp.concatenate([before.astype(v.dtype), v], axis=1)
        return conv_taps(lp, ext, v.shape[1], cfg), windows.at[layer, rows].set(
            _last_valid(ext, valid, keep).astype(windows.dtype))

    conved, windows = _by_parts(chunk, part, windows, rows, valid, v)
    return cm.dense(lp["conv_out"], (gate_c * conved).astype(v.dtype)), windows


def _routing_stats(loads: list, cfg: DecoderConfig,
                   chunk: Optional[_RidingChunk]):
    """A step's routing counters from its expert layers' loads
    (``moe_step_stats``); of a fused step by row range, [3, ...]: the
    lanes', the chunk's, and the block's own (their loads summed: what the
    expert products read)."""
    loads, held = jnp.concatenate(loads), cfg.experts_held and cfg.held
    if chunk is None:
        return moe_step_stats(loads, held)
    # [layers, 2, E]: by row range (``route_topk``'s ``lanes``)
    return jnp.stack([moe_step_stats(part, held) for part in (
        loads[:, 0], loads[:, 1], loads.sum(axis=1))])


def _dense_layers(params: dict, cfg: DecoderConfig, x, k_pages, v_pages,
                  positions, page_idx, offset, token_mask, *, page_table,
                  off, mask, block: bool, kv_sharding, attention_kernel: str,
                  kernel_interpret: bool, ssm_rows=None, ssm_fresh=None,
                  chunk: Optional[_RidingChunk] = None,
                  eva: Optional[_EvaClose] = None):
    """The layer loop of a per-head K/V (GQA) model over the paged cache,
    with ``_latent_layers``' operands: one scan per run of layers of one
    shape and kind (``layer_runs``) — ONE, over ``layers``, for a model
    without routed experts or a layer pattern. The pools ride in the carry
    whole: each layer scatters its tokens' K and V at (its index among its
    kind's layers, page, offset) in place and hands the pools on, so no
    layer's slice is copied out, written into and stacked back, and runs
    share them without slicing or re-joining.

    ``page_idx`` / ``offset`` [B, S] place each token's row in the kept
    pool; ``page_table`` is the kept table [B, P] or, with sliding layers,
    (kept, window ring); query i of row b sits at ``off[b] + i``;
    ``token_mask`` [B, S] names the tokens that are written to a window
    pool, that route (routed experts) and that consume expert capacity
    (Switch MoE). A full layer attends under ``mask`` — over the block's
    own keys where ``block`` (the one-shot prefill), else over the cache,
    this step's keys included: ``"paged"`` reads the page table in place
    through the Pallas kernel (its causal bound key <= off + i is exactly
    ``mask``), ``"gather"`` (the reference) gathers the pages the table
    names out of the layer, [B, P * page] keys a row. A sliding layer
    attends the last ``sliding_window`` positions through its row's ring of
    window pages (``_attend_ring``, or the kernel with its lower bound).

    A hybrid model's pools are the two dicts of ``init_page_pool``: beside
    its attention, from the same normed input, each layer runs its mixer
    over the state pool (``_mixer_paged``: ``ssm_rows`` [B], ``ssm_fresh``
    [B] or None in a decode step, ``token_mask`` the tokens that advance a
    state) and the two outputs add into one residual. A model with conv
    layers carries ``{"kv", "conv"}`` and ``{"kv"}``: a ``conv`` run's layers
    read and write the conv pool's rows under the same three operands
    (``_conv_paged``) and no K/V, the attention runs' layers the ``kv``
    pools alone, each pool indexed by the layer's place among its kind's. A
    model with linear_attention layers carries ``{"kv", "gdn"}`` twice (the
    states beside K, the conv windows beside V): a ``linear_attention`` run's
    layers advance the ``gdn`` pool's rows (``_gdn_paged``) and touch no K/V.
    A model of one-mixer blocks carries ``{"kv", "ssm"}`` twice: a ``mamba``
    run's layers advance the ``ssm`` pool's rows (``_mixer_paged``), a ``moe``
    run's route under the block's own norm, an attention run's write and read
    ``kv`` — and no block has an MLP behind its mixer.

    ``chunk`` (``paged_fused_step``; a ``fusable`` model): the block is ONE
    row [1, lanes + C] — a decode step's lanes, a token each, then a prompt's
    chunk — through every weight product, and ``chunk`` says how it attends:
    the lanes as [lanes, 1] queries under ``page_table`` / ``off`` /
    ``mask``, the chunk as [1, C] under its own (``_RidingChunk``), both
    after every token's K/V is written. A conv layer's windows are read,
    summed and written a part at a time too (``_conv_fused``: the lanes
    under ``ssm_rows``, a decode step's, the chunk under its own row and
    ``fresh``), and a routed model's counters come back by row range, [3,
    ...] (``_routing_stats``).

    ``eva`` (a compacting window cache): ``page_idx`` / ``offset`` / ``off``
    / ``mask`` are in CACHE ROWS (``eva_rows``), ``positions`` stay the
    tokens' own (the rotary embedding's); the one softmax over summary rows
    and window rows is the causal bound over the row's table as it stands,
    and behind it each layer closes the windows that ``eva`` names
    (``_eva_close``).
    Returns (x, k_pages, v_pages) and, from a routed model, the step's
    counters (``moe_step_stats``)."""
    b, t = positions.shape
    kernel = attention_kernel == "paged"
    lanes = 0 if chunk is None else chunk.lanes
    kept, ring = page_table if isinstance(page_table, tuple) else (page_table, None)
    page = _page_size(k_pages)
    ctx = kept.shape[1] * page
    where = {FULL: (page_idx, offset)}
    if ring is not None:
        where[SLIDING] = _write_coords(ring, positions, token_mask, page, ring=True)

    def make_layer(routed: bool, kind: str, experts):
        def ffn(lp, x, kp, vp, ei, norm="mlp_norm"):
            y = _norm(lp[norm], x, cfg)
            if not routed:
                return (x + _mlp(lp, y, cfg, token_mask=token_mask), kp, vp), None
            # the stack's experts stay OUT of the scanned tree and whole:
            # the kernel indexes the layer itself (``_latent_layers``)
            out, load = routed_mlp(lp, y, cfg, token_mask=token_mask,
                                   kernel=kernel, interpret=kernel_interpret,
                                   stacked=(experts, ei[0]), lanes=lanes)
            return (x + out, kp, vp), load

        if kind == MOE:
            def moe_layer(carry, scanned):
                """A block whose one mixer is its routed experts, under the
                block's own norm; no pool read or written."""
                lp, _, *ei = scanned
                return ffn(lp, *carry, ei, "attn_norm")

            return moe_layer

        if kind == MAMBA:
            def mamba_layer(carry, scanned):
                """A block whose one mixer is a Mamba-2 mixer over the ssm
                pool's rows (``_mixer_paged``); no K/V, nothing after it."""
                x, kp, vp = carry
                lp, li = scanned
                mixed, states, windows = _mixer_paged(
                    lp, _norm(lp["attn_norm"], x, cfg), cfg, kp["ssm"],
                    vp["ssm"], li, ssm_rows, ssm_fresh, token_mask, kernel,
                    kernel_interpret)
                return (x + mixed, {**kp, "ssm": states},
                        {**vp, "ssm": windows}), None

            return mamba_layer

        if kind == CONV:
            def conv_layer(carry, scanned):
                """A conv layer: its mixer over the conv pool's rows
                (``_conv_paged``), no K/V written or read."""
                x, kp, vp = carry
                lp, li, *ei = scanned
                y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
                if chunk is None:
                    mixed, windows = _conv_paged(lp, y, cfg, kp["conv"], li,
                                                 ssm_rows, ssm_fresh, token_mask)
                else:  # the products once, the windows a part at a time
                    mixed, windows = _conv_fused(lp, y, cfg, kp["conv"], li,
                                                 ssm_rows, token_mask, chunk)
                return ffn(lp, x + mixed, {**kp, "conv": windows}, vp, ei)

            return conv_layer

        if kind == LINEAR:
            def gdn_layer(carry, scanned):
                """A Gated DeltaNet layer: its mixer over the gdn pool's
                rows (``_gdn_paged``), no K/V written or read."""
                x, kp, vp = carry
                lp, li, *ei = scanned
                mixed, states, windows = _gdn_paged(
                    lp, _norm(lp["attn_norm"], x, cfg), cfg, kp["gdn"],
                    vp["gdn"], li, ssm_rows, ssm_fresh, token_mask, kernel,
                    kernel_interpret)
                return ffn(lp, x + mixed, {**kp, "gdn": states},
                           {**vp, "gdn": windows}, ei)

            return gdn_layer

        sp = cfg.gqa(kind)
        window, group = sp.window, cfg.heads // sp.kv_heads
        name = "kv_window" if window else "kv"
        pi, po = where[kind]

        def layer(carry, scanned):
            x, kp, vp = carry
            lp, li, *ei = scanned
            pools = kp, vp
            if cfg.hybrid:
                (kp, states), (vp, windows) = ((p["kv"], p["ssm"]) for p in (kp, vp))
            elif cfg.layered or cfg.conv or cfg.linear or cfg.mamba:
                kp, vp = kp[name], vp[name]
            y = _norm(lp["attn_norm"], x, cfg)
            q, k, v = qkv_project(lp, y, cfg, kind)
            q, k = qk_positioned(lp, q, k, cfg, positions, kind)
            sink = lp.get("attn_sink")
            kp = _constrain(_write_keys(kp, k, li, pi, po, sp.key_parts),
                            kv_sharding)
            vp = _constrain(_write_rows(vp, v, li, pi, po), kv_sharding)

            def attend(q, table, off, mask):
                """Queries ``q`` [rows, positions] over the layer's rows as
                just written, through ``table`` / ``off`` / ``mask``."""
                if kernel and not block:
                    return _attend_paged(q, kp, vp, li, ring if window else table,
                                         off, cfg, kv_sharding, kernel_interpret,
                                         window, sink)
                if window:
                    return _attend_ring(q, kp, vp, li, ring, positions, window,
                                        sink)
                keys, values = k, v
                if not block:
                    keys = _read_keys(kp, li, table, sp.dk,
                                      sp.kv_heads).astype(x.dtype)
                    values = _read_rows(vp, li, table, sp.kv_heads).astype(x.dtype)
                return cm.attention(q, jnp.repeat(keys, group, axis=2),
                                    jnp.repeat(values, group, axis=2), mask,
                                    sink=sink)

            if eva is not None:
                with jax.named_scope("eva_attention"):
                    attn = attend(q, kept, off, mask)
                kp, vp = _eva_close(lp, kp, vp, li, kept, eva, cfg, kernel,
                                    kernel_interpret)
            elif chunk is None:
                attn = attend(q, kept, off, mask)
            else:  # the lanes a row each, then the chunk's row
                attn = jnp.concatenate([
                    attend(q[0, :lanes, None], kept, off, mask).reshape(
                        1, lanes, cfg.heads, sp.dv),
                    attend(q[:, lanes:], chunk.table, chunk.off, chunk.mask)],
                    axis=1)
            attn = attn_out_gate(lp, y, attn, cfg)
            out = _scaled(cm.dense(lp["wo"], attn.reshape(b, t, cfg.heads * sp.dv)),
                          cfg.attention_out_multiplier)
            if cfg.hybrid:
                mixed, states, windows = _mixer_paged(
                    lp, y, cfg, states, windows, li, ssm_rows, ssm_fresh,
                    token_mask, kernel, kernel_interpret)
                out = out + mixed
                kp, vp = {"kv": kp, "ssm": states}, {"kv": vp, "ssm": windows}
            elif cfg.layered or cfg.conv or cfg.linear or cfg.mamba:
                kp, vp = {**pools[0], name: kp}, {**pools[1], name: vp}
            if cfg.one_mixer:  # the block ends with its attention
                return (x + out, kp, vp), None
            return ffn(lp, x + out, kp, vp, ei)
        return layer

    carry = (x, k_pages, v_pages)
    loads = []
    for name, first, stop, kind, routed, kind_first in layer_runs(cfg):
        carry, load = _scan_run(
            make_layer(routed, kind, params[name].get("experts")), carry,
            params[name], first, stop, kind_first, routed)
        if routed:
            loads.append(load)
    if not loads:
        return carry
    return (*carry, _routing_stats(loads, cfg, chunk))


def _scan_run(layer, carry, stack: dict, first: int, stop: int,
              kind_first: int, routed: bool):
    """``lax.scan`` of ``layer(carry, (the layer's params, its index among
    its kind's layers[, its index in the stack]))`` over layers
    ``first..stop`` of ``stack`` (a routed stack's experts left out: the
    layer reads them whole, by the index). A run that is its whole stack
    scans the stack; a part of one scans indices and reads each layer's
    leaves out of the stack inside the loop — a static slice of the run
    would copy its weights once a step."""
    scanned = ({k: v for k, v in stack.items() if k != "experts"} if routed
               else stack)
    depth = scanned["attn_norm"]["scale"].shape[0]
    of_kind = jnp.arange(kind_first, kind_first + stop - first)
    in_stack = (jnp.arange(first, stop),) if routed else ()
    if (first, stop) == (0, depth):
        return jax.lax.scan(layer, carry, (scanned, of_kind, *in_stack))

    def part(carry, indices):
        lp = jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, indices[-1], keepdims=False),
            scanned)
        return layer(carry, (lp, *indices[:1 + routed]))
    return jax.lax.scan(part, carry, (of_kind, *in_stack, jnp.arange(first, stop)))


def _eva_step(cfg: DecoderConfig, page: int, first, count) -> dict:
    """``_dense_layers``' operand of a compacting window cache (none for any
    other model, and nothing traced for it): which rows of a step that
    writes ``count`` [B] positions (a decode lane: whether it is active) from
    ``first`` [B] on close their window, and that window's first column."""
    if not cfg.eva:
        return {}
    w = cfg.window_size
    last = first + count - 1
    return dict(eva=_EvaClose((count > 0) & ((last + 1) % w == 0),
                              last // w * (w // cfg.chunk_size // page)))


def _ssm_operands(cfg: DecoderConfig, rows, fresh) -> dict:
    """``_dense_layers``' state operands: none for a model that caches no
    state a sequence (``cfg.stateful``: a mixer, conv layers, linear
    attention layers)."""
    if not cfg.stateful:
        return {}
    if rows is None:
        raise ValueError("the chunk of a model that caches a state a "
                         "sequence names its rows of the state pool (ssm_rows)")
    return dict(ssm_rows=jnp.asarray(rows, jnp.int32), ssm_fresh=fresh)


def paged_prefill(params: dict, cfg: DecoderConfig, input_ids, lengths,
                  page_table, k_pages, v_pages, return_logits: bool = False,
                  kv_sharding=None, attention_kernel: str = "gather",
                  kernel_interpret: bool = False):
    """Prefill prompts and scatter their K/V into pages.

    input_ids: [B, T] right-padded; lengths: [B]; page_table: [B, P].
    Returns (next_ids [B], k_pages, v_pages) — pools updated for all
    positions < lengths (padding scatters to scratch page 0).

    ``kv_sharding``: optional ``NamedSharding`` of the whole pools (KV heads
    over ``tp``) for tensor-parallel serving; see ``_constrain``.

    A latent model attends in the published (expanded) form here — the
    block holds its own keys — and writes the latent rows the absorbed
    paths read later; ``attention_kernel`` picks only its expert product.
    A routed model's step returns its routing counters as a fourth value.
    """
    refuse(cfg, "one_shot_prefill")
    b, t = input_ids.shape
    page = k_pages.shape[2]
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    key_valid = (jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, :]
    mask = jnp.logical_and(causal, key_valid)
    x = _scaled(cm.embedding(params["embed"], input_ids), cfg.embedding_multiplier)

    # scatter coordinates for every (row, position): valid positions route
    # through the page table, padding goes to scratch page 0
    pos_valid = positions < lengths[:, None]                     # [B, T]
    logical_page = positions // page                             # [B, T]
    page_idx = jnp.where(
        pos_valid,
        jnp.take_along_axis(page_table, logical_page, axis=1),
        0,
    )                                                            # [B, T]
    offset = jnp.where(pos_valid, positions % page, 0)           # [B, T]

    moe = ()  # a routed model appends its counters (``moe_step_stats``)
    if cfg.latent:
        x, new_k, new_v, *moe = _latent_layers(
            params, cfg, x, k_pages, v_pages, positions, page_idx, offset,
            pos_valid, page_table=page_table, off=None, mask=mask, block=True,
            attention_kernel=attention_kernel,
            kernel_interpret=kernel_interpret)
    else:
        x, new_k, new_v, *moe = _dense_layers(
            params, cfg, x, k_pages, v_pages, positions, page_idx, offset,
            pos_valid, page_table=page_table, off=None, mask=mask, block=True,
            kv_sharding=kv_sharding, attention_kernel=attention_kernel,
            kernel_interpret=kernel_interpret)
    logits = lm_logits(params, x, cfg)
    last = jnp.clip(lengths - 1, 0, t - 1)
    last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0, :]
    if not return_logits:
        last_logits = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    return (last_logits, new_k, new_v, *moe)


def paged_prefill_chunk(params: dict, cfg: DecoderConfig, input_ids, chunk_off,
                        chunk_len, page_table, k_pages, v_pages,
                        return_all: bool = False, kv_sharding=None,
                        attention_kernel: str = "gather",
                        kernel_interpret: bool = False, ssm_rows=None):
    """Prefill ONE CHUNK of a prompt at absolute offset ``chunk_off``.

    Chunked prefill keeps continuous serving responsive: a long prompt no
    longer occupies the device for one monolithic prefill while every
    decode lane stalls — the scheduler interleaves fixed-size chunks with
    decode steps (same motivation as Sarathi/vLLM chunked prefill,
    re-expressed for XLA static shapes: one executable per chunk size).

    input_ids: [B, C] right-padded chunk; chunk_off: [B] absolute start
    position; chunk_len: [B] true tokens in this chunk; page_table: [B, P]
    must already map every page the chunk writes (plus all earlier ones).
    Earlier chunks' K/V are read back through the page-table gather, so
    attention is exact over positions 0..off+i for query i.

    Returns (last_logits [B, vocab] — at the chunk's final true position,
    meaningful only for the prompt's last chunk — , k_pages, v_pages).
    With ``return_all`` (speculative verification): logits for EVERY chunk
    position, [B, C, vocab].

    Doubles as the speculative-decode verifier: scoring k drafted tokens is
    one call with C=k. Rejected drafts leave stale K/V at their positions,
    which is benign — no mask ever admits a key position beyond the
    querying token's own position, and the position->page mapping is
    deterministic, so the true token overwrites the same cell when it arrives.

    ``attention_kernel``: ``"gather"`` (reference — materialize
    ``kp[page_table]`` and run masked dense attention) or ``"paged"`` (the
    Pallas kernel reads the page table in place; ``kernel_interpret`` runs
    it interpreted for CPU tests). Both produce the same attention to float
    tolerance; the serving layer gates the swap on argmax parity.

    A hybrid model's row carries its state on from the state pool's row
    ``ssm_rows[b]`` — from a ZERO state where ``chunk_off`` is 0: a prompt's
    first chunk resets its slot, so admission costs no device call and a
    slot's earlier tenant cannot leak. Its padded positions leave the state
    as it is. (Not a verifier for such a model: a rejected draft would have
    advanced the state.)
    """
    b, t = input_ids.shape
    # a layer pattern's table is (kept pages, the window pool's ring)
    tables, page_table = page_table, (
        page_table[0] if isinstance(page_table, tuple) else page_table)
    p_slots = page_table.shape[1]
    page = _page_size(k_pages)
    ctx = p_slots * page

    positions = chunk_off[:, None] + jnp.arange(t)[None, :]       # [B, C]
    pos_valid = jnp.arange(t)[None, :] < chunk_len[:, None]       # [B, C]
    # where a token's row sits in its slot's cache: its position, or — a
    # compacting window cache — behind the summaries of the windows closed
    # before it (the rotary embedding keeps ``positions``)
    rows = eva_rows(cfg, positions) if cfg.eva else positions
    logical_page = rows // page
    page_idx = jnp.where(
        pos_valid,
        jnp.take_along_axis(page_table, jnp.minimum(logical_page, p_slots - 1), axis=1),
        0,
    )
    offset = jnp.where(pos_valid, rows % page, 0)
    key_pos = jnp.arange(ctx)[None, None, None, :]                # [1,1,1,ctx]
    # query i attends keys 0..off+i. Padded queries keep this causal mask
    # rather than an all-False row: a fully-masked softmax is NaN, and a
    # NaN activation would leak through the MoE dispatch einsum (0 * NaN)
    # into real tokens' expert inputs. Their finite garbage output is
    # excluded from routing by token_mask and never read out.
    mask = key_pos <= rows[:, None, :, None]                      # [B,1,C,ctx]
    x = _scaled(cm.embedding(params["embed"], input_ids), cfg.embedding_multiplier)
    if cfg.fp32_skip_add:  # the residual is carried float32 between sub-layers
        x = x.astype(jnp.float32)

    moe = ()  # a routed model appends its counters (``moe_step_stats``)
    if cfg.latent:
        # the same causal rule over the latent rows: this chunk's own rows
        # were just scattered, earlier chunks' come back through the table
        x, new_k, new_v, *moe = _latent_layers(
            params, cfg, x, k_pages, v_pages, positions, page_idx, offset,
            pos_valid, page_table=tables, off=chunk_off, mask=mask,
            block=False, attention_kernel=attention_kernel,
            kernel_interpret=kernel_interpret,
            # traced for a latent model that caches a state only: the others'
            # programs stay as they were
            **(_ssm_operands(cfg, ssm_rows, chunk_off == 0)
               if cfg.stateful else {}))
    else:
        x, new_k, new_v, *moe = _dense_layers(
            params, cfg, x, k_pages, v_pages, positions, page_idx, offset,
            pos_valid, page_table=tables,
            off=eva_rows(cfg, chunk_off) if cfg.eva else chunk_off, mask=mask,
            block=False, kv_sharding=kv_sharding,
            attention_kernel=attention_kernel,
            kernel_interpret=kernel_interpret, **_ssm_operands(
                cfg, ssm_rows, chunk_off == 0), **_eva_step(
                    cfg, page, chunk_off, chunk_len))
    logits = lm_logits(params, x, cfg)
    if not return_all:
        last = jnp.clip(chunk_len - 1, 0, t - 1)
        logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0, :]
    return (logits, new_k, new_v, *moe)


def paged_decode_step(params: dict, cfg: DecoderConfig, token_ids, lengths,
                      active, page_table, k_pages, v_pages,
                      return_logits: bool = False, kv_sharding=None,
                      attention_kernel: str = "gather",
                      kernel_interpret: bool = False):
    """One decode step over all serving slots.

    token_ids: [S] current token per slot; lengths: [S] tokens already in
    cache (the new token writes at position lengths[s]); active: [S] bool;
    page_table: [S, P]. Returns (next_ids [S], k_pages, v_pages). A hybrid
    model's lane s advances the state pool's row s + 1 (an inactive lane
    the scratch row 0, by a zero step: nothing changes).

    ``attention_kernel="gather"`` (reference) gathers each slot's pages —
    a [S, P*page] dense context copy per layer — and masks positions
    >= lengths+1, so scratch-page garbage never contributes.
    ``"paged"`` reads the page table in place through the Pallas kernel
    (same mask, expressed as the causal bound q_pos = lengths): the dense
    context is never materialized and fully-invalid pages are skipped.
    """
    s = token_ids.shape[0]
    tables, page_table = page_table, (
        page_table[0] if isinstance(page_table, tuple) else page_table)
    p_slots = page_table.shape[1]
    page = _page_size(k_pages)
    ctx = p_slots * page

    positions = lengths[:, None]                                  # [S, 1]
    x = _scaled(cm.embedding(params["embed"], token_ids[:, None]),
                cfg.embedding_multiplier)                         # [S, 1, D]
    if cfg.fp32_skip_add:
        x = x.astype(jnp.float32)

    # the cached length: the tokens written, or — a compacting window
    # cache — the rows they are held in (``eva_rows``)
    rows = eva_rows(cfg, lengths) if cfg.eva else lengths
    write_logical = rows // page
    write_page = jnp.where(
        active,
        jnp.take_along_axis(page_table, write_logical[:, None], axis=1)[:, 0],
        0,
    )                                                             # [S]
    write_off = jnp.where(active, rows % page, 0)                 # [S]
    # keys valid after the write: positions 0..lengths (inclusive)
    key_pos = jnp.arange(ctx)[None, :]                            # [1, ctx]
    valid = (key_pos <= rows[:, None])[:, None, None, :]          # [S,1,1,ctx]

    moe = ()  # a routed model appends its counters (``moe_step_stats``)
    if cfg.latent:
        # inactive lanes write to the scratch page and route nowhere
        x, new_k, new_v, *moe = _latent_layers(
            params, cfg, x, k_pages, v_pages, positions, write_page[:, None],
            write_off[:, None], active[:, None], page_table=tables,
            off=lengths, mask=valid, block=False,
            attention_kernel=attention_kernel,
            kernel_interpret=kernel_interpret,
            **(_ssm_operands(cfg, jnp.where(active, jnp.arange(s) + 1, 0), None)
               if cfg.stateful else {}))
    else:
        # the single query sits at absolute position lengths[s]: the
        # kernel's causal bound (key <= lengths) is exactly ``valid``;
        # inactive lanes must not consume expert capacity (MoE)
        x, new_k, new_v, *moe = _dense_layers(
            params, cfg, x, k_pages, v_pages, positions, write_page[:, None],
            write_off[:, None], active[:, None], page_table=tables,
            off=rows, mask=valid, block=False, kv_sharding=kv_sharding,
            attention_kernel=attention_kernel,
            kernel_interpret=kernel_interpret,
            # lane s holds slot s: its state is row s + 1; an inactive lane
            # reads and writes the scratch row
            **_ssm_operands(cfg, jnp.where(active, jnp.arange(s) + 1, 0), None),
            **_eva_step(cfg, page, lengths, active))
    logits = lm_logits(params, x, cfg)[:, -1, :]
    if not return_logits:
        logits = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (logits, new_k, new_v, *moe)


def fusable(cfg: DecoderConfig) -> bool:
    """Whether a prompt's chunk can ride a decode step as one row block
    (``paged_fused_step``): a model whose tokens meet only in attention and
    in a state a sequence that the block carries a row of, and whose step
    takes no operand beside ``paged_decode_step``'s and
    ``paged_prefill_chunk``'s own. Three families, by the layer kinds and
    pools the configuration states:

    - per-head K/V, dense MLP (``_dense_layers``' ``chunk``);
    - plain latent attention with routed experts (``_latent_layers``'
      ``chunk``: dropless and per token, so the block's rows route as they
      do apart; the counters come back by row range);
    - per-head K/V with routed experts, and conv layers among the attention
      layers (the LFM2 layout; ``_dense_layers``' ``chunk`` again: the
      block carries each part's rows of the conv pool, ``_conv_fused``).

    Every other row of ``UNSERVED`` is left out, for an operand or a rule of
    its own that the block does not carry yet: its ``fused_chunk`` cell says
    which."""
    return unserved(cfg, "fused_chunk") is None


def paged_fused_step(params: dict, cfg: DecoderConfig, token_ids, lengths,
                     active, page_table, input_ids, chunk_off, chunk_len,
                     chunk_table, k_pages, v_pages, return_logits: bool = False,
                     kv_sharding=None, attention_kernel: str = "gather",
                     kernel_interpret: bool = False, ssm_rows=None):
    """One decode step over all serving slots AND one chunk of a prompt that
    is still prefilling, in one pass over the weights: ``paged_decode_step``'s
    operands (``token_ids`` / ``lengths`` / ``active`` [S], ``page_table``
    [S, P]) and ``paged_prefill_chunk``'s for one row (``input_ids`` [1, C],
    ``chunk_off`` / ``chunk_len`` [1], ``chunk_table`` [1, P], and of a model
    that caches a state a sequence ``ssm_rows`` [1], the chunk's row of the
    state pool). The prompt's own lane is not active, so the two parts write
    no page — and no row of a state pool but the scratch row — in common.

    The S + C tokens run as ONE row block [1, S + C, dim] through every
    norm and weight product, so a layer's weights are read once for both —
    of routed experts, every expert that lanes OR chunk hit once —;
    attention is the two steps' own two calls (``_dense_layers``' /
    ``_latent_layers``' ``chunk``), a conv layer's windows are each part's
    own under its own step's rules (lane s the pool's row s + 1, an idle
    lane the scratch row; the chunk its row, from zeros where ``chunk_off``
    is 0), and the head multiplies S + 1 rows: the lanes and the chunk's
    last true position. A decode step and a chunk are each bound by the
    weights' bytes; folded, the chunk's rows cost their attention.

    Returns (logits [S + 1, vocab] — tokens with ``return_logits`` false —,
    k_pages, v_pages): row S is the prompt's next token where the chunk was
    its last. A routed model's step returns its routing counters as a fourth
    value, by row range [3, ...] (``moe_step_stats`` of each): the lanes',
    the chunk's, the block's. Only for a ``fusable`` model."""
    refuse(cfg, "fused_chunk")
    s, c = token_ids.shape[0], input_ids.shape[1]
    page = _page_size(k_pages)
    ctx = page_table.shape[1] * page
    chunk_pos = chunk_off[:, None] + jnp.arange(c)[None, :]       # [1, C]
    chunk_valid = jnp.arange(c)[None, :] < chunk_len[:, None]     # [1, C]
    lane_page, lane_at = _write_coords(
        page_table, lengths[:, None], active[:, None], page)      # [S, 1]
    chunk_page, chunk_at = _write_coords(chunk_table, chunk_pos, chunk_valid,
                                         page)                    # [1, C]

    def row(lanes, chunk):  # the block's one row: [1, S + C]
        return jnp.concatenate([lanes.reshape(1, s), chunk], axis=1)

    key_pos = jnp.arange(ctx)
    # the two steps' masks (the gather form; the kernel's bound is its off)
    lane_mask = (key_pos[None, :] <= lengths[:, None])[:, None, None, :]
    chunk_mask = key_pos[None, None, None, :] <= chunk_pos[:, None, :, None]
    x = _scaled(cm.embedding(params["embed"], row(token_ids, input_ids)),
                cfg.embedding_multiplier)                         # [1, S + C, D]
    riding = _RidingChunk(s, chunk_table, chunk_off, chunk_mask)
    operands = dict(
        page_table=page_table, off=lengths, mask=lane_mask, block=False,
        attention_kernel=attention_kernel, kernel_interpret=kernel_interpret)
    if not cfg.latent:
        operands["kv_sharding"] = kv_sharding
    if cfg.stateful:
        # the state rows of each part, as its own step names them: the
        # chunk's under ``paged_prefill_chunk``'s rule, the lanes' under
        # ``paged_decode_step``'s
        its = _ssm_operands(cfg, ssm_rows, chunk_off == 0)
        riding = riding._replace(rows=its["ssm_rows"], fresh=its["ssm_fresh"])
        operands["ssm_rows"] = jnp.where(active, jnp.arange(s) + 1, 0)
    operands["chunk"] = riding
    # a routed model appends its counters, by row range
    x, new_k, new_v, *moe = (_latent_layers if cfg.latent else _dense_layers)(
        params, cfg, x, k_pages, v_pages, row(lengths, chunk_pos),
        row(lane_page, chunk_page), row(lane_at, chunk_at),
        row(active, chunk_valid), **operands)
    last = s + jnp.clip(chunk_len - 1, 0, c - 1)                  # [1]
    logits = lm_logits(params, jnp.concatenate(
        [x[0, :s], jnp.take(x[0], last, axis=0)]), cfg)           # [S + 1, V]
    if not return_logits:
        logits = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (logits, new_k, new_v, *moe)
