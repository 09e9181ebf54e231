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
from arkflow_tpu.models.decoder import DecoderConfig, _mlp, _rope


def init_page_pool(cfg: DecoderConfig, num_pages: int, page_size: int):
    """KV page pools: [layers, num_pages, page, kv_heads, dh] bf16."""
    dh = cfg.dim // cfg.heads
    shape = (cfg.layers, num_pages, page_size, cfg.kv_heads, dh)
    return jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16)


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
                  kv_sharding=None):
    """Prefill prompts and scatter their K/V into pages.

    input_ids: [B, T] right-padded; lengths: [B]; page_table: [B, P].
    Returns (next_ids [B], k_pages, v_pages) — pools updated for all
    positions < lengths (padding scatters to scratch page 0).

    ``kv_sharding``: optional per-layer-pool ``NamedSharding`` (KV heads over
    ``tp``) for tensor-parallel serving; see ``_constrain``.
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

    (x,), (new_k, new_v) = jax.lax.scan(
        layer, (x,), (params["layers"], k_pages, v_pages))
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    last = jnp.clip(lengths - 1, 0, t - 1)
    last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0, :]
    if return_logits:
        return last_logits, new_k, new_v
    return jnp.argmax(last_logits, axis=-1).astype(jnp.int32), new_k, new_v


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

    (x,), (new_k, new_v) = jax.lax.scan(
        layer, (x,), (params["layers"], k_pages, v_pages))
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    if return_all:
        return logits, new_k, new_v
    last = jnp.clip(chunk_len - 1, 0, t - 1)
    last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0, :]
    return last_logits, new_k, new_v


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

    (x,), (new_k, new_v) = jax.lax.scan(
        layer, (x,), (params["layers"], k_pages, v_pages))
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    if return_logits:
        return logits[:, -1, :], new_k, new_v
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), new_k, new_v
