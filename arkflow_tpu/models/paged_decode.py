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

Two cache kinds live here, selected by the model config inside the one layer
body of each step: per-head K/V (GQA; the pools are scanned layer by layer)
and latent rows (MLA, ``cfg.latent``: one normed latent row and one rotated
rope key a token for all heads; the pools ride whole through the layer loop,
``_latent_layers``, and attention runs in the absorbed form over them). The
MLP half is dense SwiGLU, the Switch top-1 layer, or dropless routed experts
(``cfg.routed``) — the last only on latent layers so far.

The reference has no serving layer at all (its python processor is
user-code); this implements the engine the `tpu_generate` processor's
continuous-batching mode runs on. Design follows the public PagedAttention
idea (Kwon et al., SOSP'23) re-expressed for XLA: page-table gather +
masked attention instead of custom CUDA paging.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from arkflow_tpu.models import common as cm
from arkflow_tpu.models.decoder import (DecoderConfig, _mlp, _rope,
                                        layer_stacks, mla_absorb_query,
                                        mla_expanded_attention, mla_output,
                                        mla_project, moe_step_stats,
                                        routed_mlp)


def init_page_pool(cfg: DecoderConfig, num_pages: int, page_size: int):
    """The two page pools the model's cache needs, bf16 — the cache's shape
    is the model family's to state, not the server's to compute:

    - per-head K/V (GQA): K and V, each [layers, num_pages, page, kv_heads, dh];
    - latent (MLA): the normed latent rows [layers, num_pages, page,
      kv_lora_rank] and the rotated rope keys [layers, num_pages, page,
      qk_rope_head_dim], one row each per token for ALL heads (no head axis:
      a page's last two dims then tile the chip's memory as they are)."""
    if cfg.latent:
        shape = (cfg.layers, num_pages, page_size)
        return (jnp.zeros(shape + (cfg.kv_lora_rank,), jnp.bfloat16),
                jnp.zeros(shape + (cfg.qk_rope_head_dim,), jnp.bfloat16))
    dh = cfg.dim // cfg.heads
    shape = (cfg.layers, num_pages, page_size, cfg.kv_heads, dh)
    return jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)


def kv_bytes_per_token(cfg: DecoderConfig) -> int:
    """Bytes one cached token costs over all layers (bf16 values as the
    pools hold them, before any padding the device's tiling adds)."""
    per_layer = (cfg.kv_lora_rank + cfg.qk_rope_head_dim if cfg.latent
                 else 2 * cfg.kv_heads * (cfg.dim // cfg.heads))
    return 2 * per_layer * cfg.layers


def _latent_layers(params: dict, cfg: DecoderConfig, x, c_pages, r_pages,
                   positions, page_idx, offset, attend, token_mask,
                   attention_kernel: str, kernel_interpret: bool):
    """The layer loop of a latent-attention model over the paged cache: one
    scan per layer stack (leading dense layers, then expert layers), ONE
    layer index for the pools. The pools ride in the carry whole — each
    layer scatters its tokens' latent rows and rope keys at
    (layer, page_idx, offset) in place and hands the whole pools on, so two
    stacks share them without slicing or re-joining — and ``attend(lp,
    q_nope, q_rope, c, k_r, c_pages, r_pages, layer)`` is the caller's
    attention over them. ``token_mask`` [B, S] names the tokens that route
    (active lanes, unpadded positions). Returns (x, c_pages, r_pages, the
    step's routing counters ``moe_step_stats``)."""
    kernel = attention_kernel == "paged"

    def make_layer(routed: bool, experts, first: int):
        # the stack's experts stay OUT of the scanned tree: scanned, each
        # layer's slice (1.2 GB at Kanana-2 widths) would be copied out for
        # the kernel every step; whole, the kernel indexes the layer itself
        def layer(carry, lp):
            x, cp, rp, li = carry
            y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
            q_nope, q_rope, c, k_r = mla_project(lp, y, cfg, positions)
            cp = cp.at[li, page_idx, offset].set(c.astype(cp.dtype))
            rp = rp.at[li, page_idx, offset].set(k_r.astype(rp.dtype))
            x = x + attend(lp, q_nope, q_rope, c, k_r, cp, rp, li)
            y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
            if routed:
                out, load = routed_mlp(lp, y, cfg, token_mask=token_mask,
                                       kernel=kernel, interpret=kernel_interpret,
                                       stacked=(experts, li - first))
            else:
                out, load = _mlp(lp, y, cfg), None
            return (x + out, cp, rp, li + 1), load
        return layer

    carry = (x, c_pages, r_pages, jnp.zeros((), jnp.int32))
    first = 0
    for stack, routed in layer_stacks(params, cfg):
        scanned = {k: v for k, v in stack.items() if k != "experts"}
        carry, loads = jax.lax.scan(
            make_layer(routed, stack.get("experts"), first), carry, scanned)
        first += stack["attn_norm"]["scale"].shape[0]
    x, c_pages, r_pages, _ = carry
    return x, c_pages, r_pages, moe_step_stats(loads)  # the expert stack's


def _attend_latent(lp, q_nope, q_rope, c_pages, r_pages, layer, page_table,
                   off, mask, cfg: DecoderConfig, attention_kernel: str,
                   kernel_interpret: bool):
    """Absorbed latent attention over the paged cache: the queries are
    carried into the latent space (``W_uk`` absorbed), scored against the
    cached latent rows and rope keys of every head's ONE shared row per
    token, the value sum is taken over the latent rows, and ``W_uv`` /
    ``o_proj`` finish. ``"paged"`` reads the pools in place
    (ops/ragged_attention.mla_paged_attention; ``off`` is each row's first
    query position); ``"gather"`` materializes the layer's context and
    masks with ``mask`` — the reference."""
    q_lat = mla_absorb_query(lp, q_nope, cfg)
    scale = float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if attention_kernel == "paged":
        from arkflow_tpu.ops.ragged_attention import mla_paged_attention

        o_lat = mla_paged_attention(q_lat, q_rope, c_pages, r_pages, layer,
                                    page_table, off, scale=scale,
                                    interpret=kernel_interpret)
    else:
        b, ctx = page_table.shape[0], page_table.shape[1] * c_pages.shape[2]
        cc = c_pages[layer][page_table].reshape(b, ctx, -1).astype(q_lat.dtype)
        rr = r_pages[layer][page_table].reshape(b, ctx, -1).astype(q_lat.dtype)
        scores = (jnp.einsum("bqhl,bkl->bhqk", q_lat, cc)
                  + jnp.einsum("bqhr,bkr->bhqk", q_rope, rr)
                  ).astype(jnp.float32) * scale
        scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(q_lat.dtype)
        o_lat = jnp.einsum("bhqk,bkl->bqhl", probs, cc)
    return mla_output(lp, o_lat, cfg)


def latent_kernel_probe(params: dict, cfg: DecoderConfig, page_size: int,
                        kernel_interpret: bool = False) -> list:
    """(name, reference, kernel output) for each Pallas kernel a latent
    model's ``decode_kernel: paged`` serves with, on the model's own first
    layer at its real widths and seeded inputs: the latent attention
    (decode and a 2-token chunk, rows on non-contiguous pages, one crossing
    a page boundary) against the gather path, and the expert product on
    GIVEN routing against plain XLA over the experts routed to.

    Kernel by kernel, not logits of the whole model as the per-head probe
    does: with routed experts two arithmetically different attention paths
    round a router's input differently, a near-tie then picks another
    expert, and the logits differ by tenths though no kernel is wrong
    (seen on the chip: 4 of 6 seeds, PERF.md PR 27)."""
    from arkflow_tpu.ops.moe_experts import expert_swiglu_dense, moe_expert_swiglu

    lp = jax.tree_util.tree_map(lambda a: a[0], params["dense_layers"])
    keys = iter(jax.random.split(jax.random.PRNGKey(1234), 8))
    n0 = page_size + 1
    pages_per = -(-(n0 + 3) // page_size)
    pools = [jax.random.normal(next(keys), p.shape, jnp.float32).astype(p.dtype)
             for p in init_page_pool(cfg, 1 + 2 * pages_per, page_size)]
    table = jnp.stack([jnp.arange(1, 2 * pages_per, 2)[::-1],
                       jnp.arange(2, 2 * pages_per + 1, 2)]).astype(jnp.int32)
    off = jnp.asarray([n0, 1], jnp.int32)
    ctx = pages_per * page_size
    out = []
    for name, c in (("latent_attention_decode", 1), ("latent_attention_chunk", 2)):
        shape = (2, c, cfg.heads)
        q_nope = jax.random.normal(next(keys), shape + (cfg.qk_nope_head_dim,),
                                   jnp.float32).astype(jnp.bfloat16)
        q_rope = jax.random.normal(next(keys), shape + (cfg.qk_rope_head_dim,),
                                   jnp.float32).astype(jnp.bfloat16)
        positions = off[:, None] + jnp.arange(c)[None, :]
        mask = jnp.arange(ctx)[None, None, None, :] <= positions[:, None, :, None]
        ref, got = (_attend_latent(lp, q_nope, q_rope, *pools, 0, table, off,
                                   mask, cfg, kern, kernel_interpret)
                    for kern in ("gather", "paged"))
        out.append((name, ref, got))
    # the expert product: tokens routed among a HANDFUL of the first expert
    # layer's experts, so that the XLA twin, which multiplies every expert
    # it is given, copies those few (94 MB at Kanana-2 widths, by static
    # slices: an index array over the stack cost 1.9 GB on a v5e) and not
    # the layer (1.2 GB); the kernel reads the whole stack as when serving
    e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    few = min(e, 8)
    x = jax.random.normal(next(keys), (16, cfg.dim), jnp.float32).astype(jnp.bfloat16)
    chosen = jnp.argsort(jax.random.uniform(next(keys), (16, few)), axis=-1)[:, :min(k, few)]
    cw = jax.nn.one_hot(chosen, e, dtype=jnp.float32).sum(1) * (
        cfg.routed_scaling_factor / k)
    cw = jnp.concatenate([cw, jnp.ones((16, cfg.n_shared_experts))], axis=-1)
    ex = params["layers"]["experts"]
    cols = jnp.concatenate([jnp.arange(few), jnp.arange(e, e + cfg.n_shared_experts)])
    twin = [jnp.concatenate([ex[w][0, :few], ex[w][0, e:]])  # static slices
            for w in ("w_gate", "w_up", "w_down")]
    out.append(("expert_product",
                expert_swiglu_dense(x, cw[:, cols], *twin),
                moe_expert_swiglu(x, cw, ex["w_gate"], ex["w_up"],
                                  ex["w_down"], 0, interpret=kernel_interpret)))
    return out


def _constrain(x, sharding):
    """Pin a per-layer pool slice to its tensor-parallel sharding (KV heads
    over ``tp``). Under GSPMD the layer scan would otherwise be free to
    all-gather the pools at every step — hundreds of MB of HBM churn; the
    constraint keeps scatter/gather partitioned. ``None`` (single-device
    serving) is a no-op so the unsharded path traces identically."""
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


def _attend_paged(q, kp, vp, page_table, off, cfg: DecoderConfig,
                  kv_sharding, interpret: bool):
    """Page-table-indirect flash attention over one layer's pool slices
    (ops/ragged_attention.paged_flash_attention): query i of row b sits at
    absolute position ``off[b] + i`` and attends keys 0..off+i, read
    straight from the pools — the [B, ctx, heads, dh] gather+repeat the
    dense reference materializes per layer per step never exists.

    Under tensor parallelism the kernel runs inside ``shard_map`` over the
    ``kv_sharding`` mesh's tp axis: attention is independent per KV head,
    q's head dim splits into the same contiguous head groups the pools
    shard by (tp | kv_heads is validated at server build), so each shard
    attends its local heads with zero collectives — the pools are never
    all-gathered."""
    from arkflow_tpu.ops.ragged_attention import paged_flash_attention

    if kv_sharding is None:
        return paged_flash_attention(q, kp, vp, page_table, off,
                                     interpret=interpret)
    from jax.sharding import PartitionSpec as P

    mesh = kv_sharding.mesh
    head_spec = P(None, None, "tp", None)  # q/out: [B, C, H, dh], H over tp

    def local(q_, kp_, vp_, table_, off_):
        return paged_flash_attention(q_, kp_, vp_, table_, off_,
                                     interpret=interpret)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(head_spec, kv_sharding.spec, kv_sharding.spec, P(), P()),
        out_specs=head_spec,
        check_vma=False,
    )(q, kp, vp, page_table, off)


def paged_prefill(params: dict, cfg: DecoderConfig, input_ids, lengths,
                  page_table, k_pages, v_pages, return_logits: bool = False,
                  kv_sharding=None, attention_kernel: str = "gather",
                  kernel_interpret: bool = False):
    """Prefill prompts and scatter their K/V into pages.

    input_ids: [B, T] right-padded; lengths: [B]; page_table: [B, P].
    Returns (next_ids [B], k_pages, v_pages) — pools updated for all
    positions < lengths (padding scatters to scratch page 0).

    ``kv_sharding``: optional per-layer-pool ``NamedSharding`` (KV heads over
    ``tp``) for tensor-parallel serving; see ``_constrain``.

    A latent model attends in the published (expanded) form here — the
    block holds its own keys — and writes the latent rows the absorbed
    paths read later; ``attention_kernel`` picks only its expert product.
    A routed model's step returns its routing counters as a fourth value.
    """
    b, t = input_ids.shape
    page = k_pages.shape[2]
    dh = cfg.dim // cfg.heads
    group = cfg.heads // cfg.kv_heads
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    key_valid = (jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, :]
    mask = jnp.logical_and(causal, key_valid)
    x = cm.embedding(params["embed"], input_ids)

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

    def layer(carry, lp_and_pools):
        x, = carry
        lp, kp, vp = lp_and_pools
        y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q = cm.dense(lp["wq"], y).reshape(b, t, cfg.heads, dh)
        k = cm.dense(lp["wk"], y).reshape(b, t, cfg.kv_heads, dh)
        v = cm.dense(lp["wv"], y).reshape(b, t, cfg.kv_heads, dh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        kp = _constrain(kp.at[page_idx, offset].set(k.astype(jnp.bfloat16)),
                        kv_sharding)
        vp = _constrain(vp.at[page_idx, offset].set(v.astype(jnp.bfloat16)),
                        kv_sharding)
        kk = jnp.repeat(k, group, axis=2)
        vv = jnp.repeat(v, group, axis=2)
        attn = cm.attention(q, kk, vv, mask).reshape(b, t, cfg.heads * dh)
        x = x + cm.dense(lp["wo"], attn)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        x = x + _mlp(lp, y, cfg, token_mask=pos_valid)
        return (x,), (kp, vp)

    moe = ()  # a routed model appends its counters (``moe_step_stats``)
    if cfg.latent:
        def attend(lp, q_nope, q_rope, c, k_r, cp, rp, li):
            return mla_expanded_attention(lp, q_nope, q_rope, c, k_r, mask, cfg)

        x, new_k, new_v, *moe = _latent_layers(
            params, cfg, x, k_pages, v_pages, positions, page_idx, offset,
            attend, pos_valid, attention_kernel, kernel_interpret)
    else:
        (x,), (new_k, new_v) = jax.lax.scan(
            layer, (x,), (params["layers"], k_pages, v_pages))
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    last = jnp.clip(lengths - 1, 0, t - 1)
    last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0, :]
    if not return_logits:
        last_logits = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
    return (last_logits, new_k, new_v, *moe)


def paged_prefill_chunk(params: dict, cfg: DecoderConfig, input_ids, chunk_off,
                        chunk_len, page_table, k_pages, v_pages,
                        return_all: bool = False, kv_sharding=None,
                        attention_kernel: str = "gather",
                        kernel_interpret: bool = False):
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
    """
    b, t = input_ids.shape
    p_slots = page_table.shape[1]
    page = k_pages.shape[2]
    ctx = p_slots * page
    dh = cfg.dim // cfg.heads
    group = cfg.heads // cfg.kv_heads

    positions = chunk_off[:, None] + jnp.arange(t)[None, :]       # [B, C]
    pos_valid = jnp.arange(t)[None, :] < chunk_len[:, None]       # [B, C]
    logical_page = positions // page
    page_idx = jnp.where(
        pos_valid,
        jnp.take_along_axis(page_table, jnp.minimum(logical_page, p_slots - 1), axis=1),
        0,
    )
    offset = jnp.where(pos_valid, positions % page, 0)
    key_pos = jnp.arange(ctx)[None, None, None, :]                # [1,1,1,ctx]
    # query i attends keys 0..off+i. Padded queries keep this causal mask
    # rather than an all-False row: a fully-masked softmax is NaN, and a
    # NaN activation would leak through the MoE dispatch einsum (0 * NaN)
    # into real tokens' expert inputs. Their finite garbage output is
    # excluded from routing by token_mask and never read out.
    mask = key_pos <= positions[:, None, :, None]                 # [B,1,C,ctx]
    x = cm.embedding(params["embed"], input_ids)

    def layer(carry, lp_and_pools):
        x, = carry
        lp, kp, vp = lp_and_pools
        y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q = cm.dense(lp["wq"], y).reshape(b, t, cfg.heads, dh)
        k = cm.dense(lp["wk"], y).reshape(b, t, cfg.kv_heads, dh)
        v = cm.dense(lp["wv"], y).reshape(b, t, cfg.kv_heads, dh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        kp = _constrain(kp.at[page_idx, offset].set(k.astype(jnp.bfloat16)),
                        kv_sharding)
        vp = _constrain(vp.at[page_idx, offset].set(v.astype(jnp.bfloat16)),
                        kv_sharding)
        if attention_kernel == "paged":
            attn = _attend_paged(q, kp, vp, page_table, chunk_off, cfg,
                                 kv_sharding, kernel_interpret)
            attn = attn.reshape(b, t, cfg.heads * dh)
        else:
            # earlier chunks' keys come back through the page gather (this
            # chunk's own keys were just scattered, so they are included too)
            kk = kp[page_table].reshape(b, ctx, cfg.kv_heads, dh).astype(x.dtype)
            vv = vp[page_table].reshape(b, ctx, cfg.kv_heads, dh).astype(x.dtype)
            kk = jnp.repeat(kk, group, axis=2)
            vv = jnp.repeat(vv, group, axis=2)
            attn = cm.attention(q, kk, vv, mask).reshape(b, t, cfg.heads * dh)
        x = x + cm.dense(lp["wo"], attn)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        x = x + _mlp(lp, y, cfg, token_mask=pos_valid)
        return (x,), (kp, vp)

    moe = ()  # a routed model appends its counters (``moe_step_stats``)
    if cfg.latent:
        # the same causal rule over the latent rows: this chunk's own rows
        # were just scattered, earlier chunks' come back through the table
        def attend(lp, q_nope, q_rope, c, k_r, cp, rp, li):
            return _attend_latent(lp, q_nope, q_rope, cp, rp, li, page_table,
                                  chunk_off, mask, cfg, attention_kernel,
                                  kernel_interpret)

        x, new_k, new_v, *moe = _latent_layers(
            params, cfg, x, k_pages, v_pages, positions, page_idx, offset,
            attend, pos_valid, attention_kernel, kernel_interpret)
    else:
        (x,), (new_k, new_v) = jax.lax.scan(
            layer, (x,), (params["layers"], k_pages, v_pages))
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
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
    page_table: [S, P]. Returns (next_ids [S], k_pages, v_pages).

    ``attention_kernel="gather"`` (reference) gathers each slot's pages —
    a [S, P*page] dense context copy per layer — and masks positions
    >= lengths+1, so scratch-page garbage never contributes.
    ``"paged"`` reads the page table in place through the Pallas kernel
    (same mask, expressed as the causal bound q_pos = lengths): the dense
    context is never materialized and fully-invalid pages are skipped.
    """
    s = token_ids.shape[0]
    p_slots = page_table.shape[1]
    page = k_pages.shape[2]
    ctx = p_slots * page
    dh = cfg.dim // cfg.heads
    group = cfg.heads // cfg.kv_heads

    positions = lengths[:, None]                                  # [S, 1]
    x = cm.embedding(params["embed"], token_ids[:, None])         # [S, 1, D]

    write_logical = lengths // page
    write_page = jnp.where(
        active,
        jnp.take_along_axis(page_table, write_logical[:, None], axis=1)[:, 0],
        0,
    )                                                             # [S]
    write_off = jnp.where(active, lengths % page, 0)              # [S]
    # keys valid after the write: positions 0..lengths (inclusive)
    key_pos = jnp.arange(ctx)[None, :]                            # [1, ctx]
    valid = (key_pos <= lengths[:, None])[:, None, None, :]       # [S,1,1,ctx]

    def layer(carry, lp_and_pools):
        x, = carry
        lp, kp, vp = lp_and_pools
        y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q = cm.dense(lp["wq"], y).reshape(s, 1, cfg.heads, dh)
        k = cm.dense(lp["wk"], y).reshape(s, 1, cfg.kv_heads, dh)
        v = cm.dense(lp["wv"], y).reshape(s, 1, cfg.kv_heads, dh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        kp = _constrain(
            kp.at[write_page, write_off].set(k[:, 0].astype(jnp.bfloat16)),
            kv_sharding)
        vp = _constrain(
            vp.at[write_page, write_off].set(v[:, 0].astype(jnp.bfloat16)),
            kv_sharding)
        if attention_kernel == "paged":
            # the single query sits at absolute position lengths[s]; the
            # kernel's causal bound (key <= lengths) is exactly `valid`
            attn = _attend_paged(q, kp, vp, page_table, lengths, cfg,
                                 kv_sharding, kernel_interpret)
            attn = attn.reshape(s, 1, cfg.heads * dh)
        else:
            # gather each slot's context from the pool: [S, P, page, kh, dh]
            kk = kp[page_table].reshape(s, ctx, cfg.kv_heads, dh).astype(x.dtype)
            vv = vp[page_table].reshape(s, ctx, cfg.kv_heads, dh).astype(x.dtype)
            kk = jnp.repeat(kk, group, axis=2)
            vv = jnp.repeat(vv, group, axis=2)
            attn = cm.attention(q, kk, vv, valid).reshape(s, 1, cfg.heads * dh)
        x = x + cm.dense(lp["wo"], attn)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        # inactive lanes must not consume expert capacity (MoE)
        x = x + _mlp(lp, y, cfg, token_mask=active[:, None])
        return (x,), (kp, vp)

    moe = ()  # a routed model appends its counters (``moe_step_stats``)
    if cfg.latent:
        # inactive lanes write to the scratch page and route nowhere
        def attend(lp, q_nope, q_rope, c, k_r, cp, rp, li):
            return _attend_latent(lp, q_nope, q_rope, cp, rp, li, page_table,
                                  lengths, valid, cfg, attention_kernel,
                                  kernel_interpret)

        x, new_k, new_v, *moe = _latent_layers(
            params, cfg, x, k_pages, v_pages, positions, write_page[:, None],
            write_off[:, None], attend, active[:, None], attention_kernel,
            kernel_interpret)
    else:
        (x,), (new_k, new_v) = jax.lax.scan(
            layer, (x,), (params["layers"], k_pages, v_pages))
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)[:, -1, :]
    if not return_logits:
        logits = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return (logits, new_k, new_v, *moe)
