"""Ragged flash attention: per-row sequence lengths, no wasted tiles.

The streaming engine pads variable-length batches to a bucket; plain attention
then burns MXU cycles on padding. This kernel (the ragged-attention pattern of
PAPERS.md "Ragged Paged Attention") takes the true ``lengths`` per row as a
scalar-prefetch argument and bounds the K/V tile loop per (batch, q-tile)
program at the row's real length — fully-padded tiles are never touched, and
padded key positions inside the last tile are masked. Output rows beyond a
row's length are zeros.

Same VMEM/online-softmax structure as ``flash_attention``; use it when batches
are bucketed well above their typical fill.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30


def flash_softmax_loop(q, k_ref, v_ref, n_tiles, tile_k: int, valid_at):
    """The online-softmax accumulation over K tiles shared by the ragged and
    segment kernels (ops/segment_attention.py) — ONE copy of the numerically
    delicate m/l/corr recurrence. ``valid_at(t) -> [TQ, TK] bool`` supplies
    each kernel's masking rule. Returns (o, m, l) after ``n_tiles`` tiles.
    """
    tq, d = q.shape
    scale = 1.0 / math.sqrt(d)

    def body(t, carry):
        o, m, l = carry
        k = k_ref[0, 0, pl.ds(t * tile_k, tile_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(t * tile_k, tile_k), :].astype(jnp.float32)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        scores = jnp.where(valid_at(t), scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((tq, d), jnp.float32)
    m0 = jnp.full((tq,), _NEG, jnp.float32)
    l0 = jnp.zeros((tq,), jnp.float32)
    return jax.lax.fori_loop(0, n_tiles, body, (o0, m0, l0))


def _ragged_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref, *, tile_k: int, causal: bool):
    bi = pl.program_id(0)
    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)  # [TQ, D]
    tq, d = q.shape
    s = k_ref.shape[2]
    length = lengths_ref[bi]

    # K tiles that contain any valid key for this row
    n_k_row = (length + tile_k - 1) // tile_k
    if causal:
        n_k_causal = ((qi + 1) * tq + tile_k - 1) // tile_k
        n_k_row = jnp.minimum(n_k_row, n_k_causal)
    n_k_row = jnp.minimum(n_k_row, s // tile_k)

    q_pos = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tile_k), 0)

    def valid_at(t):
        k_pos = t * tile_k + jax.lax.broadcasted_iota(jnp.int32, (tq, tile_k), 1)
        # mask padded keys AND padded queries (pad-query rows emit zeros)
        valid = jnp.logical_and(k_pos < length, q_pos < length)
        if causal:
            valid = jnp.logical_and(valid, k_pos <= q_pos)
        return valid

    o, m, l = flash_softmax_loop(q, k_ref, v_ref, n_k_row, tile_k, valid_at)
    # pad queries (beyond the row's true length) emit zeros; note a fully
    # masked softmax degenerates to uniform (exp(NEG-NEG)=1), so masking by
    # the accumulator alone is not sufficient — mask by query position.
    q_valid = (qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)) < length
    o_ref[0, 0] = jnp.where(
        q_valid, o / jnp.maximum(l[:, None], 1e-30), 0.0
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "tile_q", "tile_k", "interpret"))
def ragged_flash_attention(q, k, v, lengths, *, causal: bool = False,
                           tile_q: int = 128, tile_k: int = 128,
                           interpret: bool = False):
    """q/k/v: [B, H, S, D]; lengths: [B] int32 true sequence lengths."""
    b, h, s, d = q.shape
    tile_q = min(tile_q, s)
    tile_k = min(tile_k, s)
    if s % tile_q or s % tile_k:
        raise ValueError(f"seq len {s} must divide tiles ({tile_q}, {tile_k})")
    from jax.experimental.pallas import tpu as pltpu  # noqa: F401 (memory spaces default)

    grid = (b, h, s // tile_q)
    kernel = functools.partial(_ragged_kernel, tile_k=tile_k, causal=causal)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tile_q, d), lambda bi, hi, qi, *_: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi, *_: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_q, d), lambda bi, hi, qi, *_: (bi, hi, qi, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), q, k, v)


# -- paged flash attention ---------------------------------------------------
#
# The decode-time twin of the ragged kernel: K/V live in the serving page
# pools ([num_pages, page, kv_heads, dh], tpu/serving.py), and each row's
# context is named by an int32 page table instead of being contiguous. The
# dense-gather path in models/paged_decode.py materializes kp[page_table]
# — a [B, P*page, heads, dh] copy of the whole context per layer per step —
# then runs masked XLA attention over it. This kernel reads the page table
# in place ("Ragged Paged Attention", PAPERS.md): the grid walks
# (row, query tile, page), the BlockSpec index map resolves each row's p-th
# page through the scalar-prefetched table, and pages past the tile's causal
# bound resolve to the scratch page 0 so consecutive out-of-range steps
# reuse one block copy and skip the math.
#
# Layout (what the TPU tiling accepts): a block takes ALL kv heads of a
# page. The pool slice is viewed as [num_pages, page*kv_heads, dh] — a free
# bitcast of the pool's HBM layout, rows ordered (slot, kv head) — and the
# queries as [B, C*heads, dh], rows ordered (position, head): both views are
# plain reshapes, and every block's last two dims equal the array's or are
# (8k, dh). One [rows, dh] x [dh, page*kv_heads] product scores every query
# head against every kv head of the page; entries whose kv head is not the
# query head's own are masked with the causal bound. That is no more MXU or
# VPU work than per-head [.., page]-wide products, which would fill only
# page/128 of each lane tile, and GQA needs no ``jnp.repeat`` of K/V.

#: folded query rows (positions x heads) per program: bounds VMEM whatever
#: the chunk length is ([rows, 128] f32 score tiles of 512 KiB)
_PAGED_ROWS = 1024


def _paged_kernel(off_ref, table_ref, q_ref, k_ref, v_ref, o_ref,
                  o_acc, m_acc, l_acc, *, page: int, kvh: int, heads: int,
                  tile_c: int, pages_per: int):
    bi = pl.program_id(0)
    ci = pl.program_id(1)
    pi = pl.program_id(2)
    rows, d = q_ref.shape[1], q_ref.shape[2]
    cols = page * kvh
    group = heads // kvh

    @pl.when(pi == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    # folded row r is (chunk position ci*tile_c + r // heads, q head
    # r % heads) at absolute position off + that; the tile's last attendable
    # key is its last query's position, so later pages hold no admissible key
    first = off_ref[bi] + ci * tile_c
    max_pos = first + (tile_c - 1)

    @pl.when(pi * page <= max_pos)
    def _acc():
        q = q_ref[0].astype(jnp.float32)                          # [rows, D]
        k = k_ref[0].astype(jnp.float32)                          # [cols, D]
        v = v_ref[0].astype(jnp.float32)
        scale = 1.0 / math.sqrt(d)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale           # [rows, cols]
        r = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
        q_pos = first + r // heads
        k_pos = pi * page + c // kvh
        own_head = (r % heads) // group == c % kvh
        scores = jnp.where(jnp.logical_and(own_head, k_pos <= q_pos),
                           scores, _NEG)
        m = m_acc[:, :1]                                          # [rows, 1]
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        l_acc[:] = jnp.broadcast_to(
            l_acc[:, :1] * corr + p.sum(axis=-1, keepdims=True), l_acc.shape)
        m_acc[:] = jnp.broadcast_to(m_new, m_acc.shape)
        o_acc[:] = o_acc[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(pi == pages_per - 1)
    def _fin():
        # every query admits at least key 0 of its own kv head (k_pos=0 <=
        # q_pos always) and page 0 is always within the bound, so m is real
        # before any fully-masked page arrives and l is never truly zero;
        # the floor only guards numerical underflow
        o_ref[0] = (o_acc[:] / jnp.maximum(l_acc[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_attention(q, k_pages, v_pages, page_table, off, *,
                          interpret: bool = False):
    """Flash attention that reads K/V straight from the serving page pools.

    q: [B, C, H, dh] — C queries per row at absolute positions
    ``off[b] + i`` (decode: C=1, off=lengths; chunked prefill: off=chunk
    offset). k_pages/v_pages: [num_pages, page, kv_heads, dh] (one layer's
    pool slice). page_table: [B, P] int32 — entries past a row's context
    may be 0 (the scratch page; never read through the causal mask).
    off: [B] int32.

    Query i attends keys 0..off+i — exactly the dense-gather reference's
    ``key_pos <= positions`` mask — with GQA resolved inside the kernel
    (no ``jnp.repeat`` of K/V). Returns [B, C, H, dh] in q's dtype.
    """
    b, c, h, dh = q.shape
    n_pages, page, kvh, _ = k_pages.shape
    if h % kvh:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {kvh}")
    pages_per = page_table.shape[1]
    # query tile: the whole chunk when it is small (block == array, any C),
    # else a multiple of 8 positions so the block's row count tiles
    tile_c = c if c * h <= _PAGED_ROWS else max(8, _PAGED_ROWS // h // 8 * 8)
    c_pad = -(-c // tile_c) * tile_c
    if c_pad != c:
        # padded queries sit past the chunk: finite garbage, sliced off below
        q = jnp.pad(q, ((0, 0), (0, c_pad - c), (0, 0), (0, 0)))
    rows = tile_c * h
    from jax.experimental.pallas import tpu as pltpu

    grid = (b, c_pad // tile_c, pages_per)
    kernel = functools.partial(
        _paged_kernel, page=page, kvh=kvh, heads=h, tile_c=tile_c,
        pages_per=pages_per)

    def _page_index(bi, ci, pi, off_ref, table_ref):
        # pages past the tile's causal bound resolve to the scratch page 0:
        # the index stays constant across the remaining grid steps, so the
        # pipeline skips the re-copy, and pl.when skips the math
        max_pos = off_ref[bi] + ci * tile_c + (tile_c - 1)
        return (jnp.where(pi * page <= max_pos, table_ref[bi, pi], 0), 0, 0)

    def _q_index(bi, ci, pi, *_):
        return (bi, ci, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rows, dh), _q_index),
            pl.BlockSpec((1, page * kvh, dh), _page_index),
            pl.BlockSpec((1, page * kvh, dh), _page_index),
        ],
        out_specs=pl.BlockSpec((1, rows, dh), _q_index),
        scratch_shapes=[
            pltpu.VMEM((rows, dh), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c_pad * h, dh), q.dtype),
        interpret=interpret,
    )(jnp.asarray(off, jnp.int32), jnp.asarray(page_table, jnp.int32),
      q.reshape(b, c_pad * h, dh),
      k_pages.reshape(n_pages, page * kvh, dh),
      v_pages.reshape(n_pages, page * kvh, dh))
    return out.reshape(b, c_pad, h, dh)[:, :c]
