"""PR-13 perf-path tests: paged flash-attention kernel + dispatch depth.

Two coupled hot-path changes, each proven against its reference:

- the Pallas ``paged_flash_attention`` kernel (page-table indirection, GQA
  folded into the query tile) must match the dense-gather path on decode AND
  chunked prefill — including adversarial page tables (page-0 scratch rows,
  non-contiguous pages, stale entries past the causal bound as a slot
  mid-eviction leaves behind) and under tp sharding on a forced host mesh;
- ``dispatch_depth: 2`` (decode step N+1 dispatched from step N's
  device-resident tokens) must emit bitwise-identical greedy token streams,
  keep page accounting clean, and nack-and-heal through the shared
  ``ServingRunnerCore`` when a deadline miss lands with BOTH steps in flight.

Tie-free prompt convention (same as the tp parity suite): the tiny random
model produces near-tied logits on some prompts, where the two kernels'
different accumulation order legitimately flips an argmax — parity prompts
are chosen tie-free under their seed so assertions are exact and stable.
"""

from __future__ import annotations

import asyncio
import functools
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import get_model
from arkflow_tpu.models.paged_decode import (
    init_page_pool,
    paged_decode_step,
    paged_prefill,
    paged_prefill_chunk,
)
from arkflow_tpu.ops import ragged_attention
from arkflow_tpu.ops.ragged_attention import (
    _page_group,
    _walk_budget,
    paged_flash_attention,
)
from arkflow_tpu.tpu.serving import GenerationServer

TINY = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, ffn=96,
            max_seq=64)
#: tie-free under seed 3 (proven by the tp parity suite)
TP_PROMPTS = [[9], [55, 1, 2, 8, 13], [9, 4], [2, 77, 31, 5], [60, 61, 62]]


# -- kernel-level golden parity ----------------------------------------------


def _dense_paged_reference(q, kp, vp, table, off):
    """The gather-then-mask attention models/paged_decode.py runs: full
    context materialized through the page table, keys <= off+i admitted."""
    b, c, h, dh = q.shape
    kvh = kp.shape[2]
    group = h // kvh
    ctx = table.shape[1] * kp.shape[1]
    kk = kp[table].reshape(b, ctx, kvh, dh).astype(jnp.float32)
    vv = vp[table].reshape(b, ctx, kvh, dh).astype(jnp.float32)
    kk = jnp.repeat(kk, group, axis=2)
    vv = jnp.repeat(vv, group, axis=2)
    positions = off[:, None] + jnp.arange(c)[None, :]
    mask = jnp.arange(ctx)[None, None, None, :] <= positions[:, None, :, None]
    from arkflow_tpu.models import common as cm

    return cm.attention(q, kk, vv, mask)


#: the kernel takes the WHOLE pools and a layer: every case runs on the
#: first, a middle and the last layer of a 3-layer pool
LAYERS = pytest.mark.parametrize("layer", [0, 1, 2],
                                 ids=["first", "middle", "last"])


def _in_pool(layer, *slices):
    """Each one-layer slice as layer ``layer`` of a 3-layer pool whose OTHER
    layers are NaN, so a wrong layer index cannot pass."""
    return [jnp.full((3, *x.shape), jnp.nan, x.dtype).at[layer].set(x)
            for x in slices]


@LAYERS
def test_paged_flash_attention_chunked_prefill_regime(layer):
    """The chunked-prefill shape regime the ragged kernel family never had
    coverage for: C > 1 queries at NONZERO absolute offsets, ragged rows
    including an empty row (off 0) and a single-token tail, against the
    dense reference."""
    rng = np.random.RandomState(7)
    b, c, h, kvh, dh = 4, 4, 4, 2, 8
    page, pages_per = 4, 5
    n_pages = 1 + b * pages_per
    q = jnp.asarray(rng.randn(b, c, h, dh), jnp.float32) * 0.5
    kp = jnp.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, jnp.bfloat16)
    vp = jnp.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, jnp.bfloat16)
    table = jnp.asarray(
        [np.random.RandomState(i).permutation(np.arange(1, n_pages))[:pages_per]
         for i in range(b)], jnp.int32)
    # offsets: mid-page, page-aligned, EMPTY row (0), single-token tail
    # (last attendable position in the table)
    off = jnp.asarray([6, 8, 0, pages_per * page - c], jnp.int32)
    out = paged_flash_attention(q, *_in_pool(layer, kp, vp), layer, table, off,
                                interpret=True)
    ref = _dense_paged_reference(q, kp, vp, table, off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@LAYERS
def test_paged_flash_attention_tiles_long_chunks(layer):
    """A chunk whose folded rows (C x heads) exceed one program's budget is
    split into query tiles (C padded up to a tile multiple, the pad sliced
    off): same answers as the dense reference, per-tile causal bounds."""
    rng = np.random.RandomState(5)
    b, c, h, kvh, dh = 2, 37, 32, 8, 8   # 37*32 rows > 1024 -> tile_c 32
    page, pages_per = 4, 12
    n_pages = 1 + b * pages_per
    q = jnp.asarray(rng.randn(b, c, h, dh), jnp.float32) * 0.5
    kp = jnp.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, jnp.bfloat16)
    vp = jnp.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, jnp.bfloat16)
    table = jnp.asarray(
        [np.random.RandomState(i).permutation(np.arange(1, n_pages))[:pages_per]
         for i in range(b)], jnp.int32)
    off = jnp.asarray([3, pages_per * page - c], jnp.int32)
    out = paged_flash_attention(q, *_in_pool(layer, kp, vp), layer, table, off,
                                interpret=True)
    ref = _dense_paged_reference(q, kp, vp, table, off)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@LAYERS
def test_paged_flash_attention_decode_shape_and_gqa(layer):
    """Decode regime: C=1 queries, GQA group folded into the kernel tile
    (heads never repeated in memory) — bit-for-shape parity with the dense
    reference, including a zero-length (empty/inactive) row."""
    rng = np.random.RandomState(9)
    b, h, kvh, dh = 3, 8, 2, 8   # group = 4
    page, pages_per = 4, 3
    n_pages = 1 + b * pages_per
    q = jnp.asarray(rng.randn(b, 1, h, dh), jnp.float32)
    kp = jnp.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, jnp.bfloat16)
    vp = jnp.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, jnp.bfloat16)
    table = jnp.asarray([[1, 2, 3], [6, 4, 5], [7, 0, 0]], jnp.int32)
    off = jnp.asarray([9, 11, 0], jnp.int32)  # row 2: empty (one key only)
    out = paged_flash_attention(q, *_in_pool(layer, kp, vp), layer, table, off,
                                interpret=True)
    ref = _dense_paged_reference(q, kp, vp, table, off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


@LAYERS
def test_paged_flash_attention_ignores_stale_pages_past_bound(layer):
    """A slot mid-eviction leaves table entries past its causal bound
    pointing at pages another slot now owns. Whatever lives there must not
    contribute: poisoning those pages with huge values may not change the
    output."""
    rng = np.random.RandomState(11)
    b, c, h, kvh, dh = 2, 2, 4, 2, 8
    page, pages_per = 4, 4
    n_pages = 1 + b * pages_per
    q = jnp.asarray(rng.randn(b, c, h, dh), jnp.float32)
    kp = np.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, np.float32)
    vp = kp.copy()
    table = np.asarray([[1, 2, 7, 8], [3, 4, 5, 6]], np.int32)
    off = jnp.asarray([3, 2], jnp.int32)  # row 0 uses pages 0..1 only
    base = paged_flash_attention(
        q, *_in_pool(layer, jnp.asarray(kp, jnp.bfloat16),
                     jnp.asarray(vp, jnp.bfloat16)),
        layer, jnp.asarray(table), off, interpret=True)
    # poison the pages row 0 maps past its bound (7, 8) AND the scratch page
    kp[[0, 7, 8]] = 1e4
    vp[[0, 7, 8]] = -1e4
    poisoned = paged_flash_attention(
        q, *_in_pool(layer, jnp.asarray(kp, jnp.bfloat16),
                     jnp.asarray(vp, jnp.bfloat16)),
        layer, jnp.asarray(table), off, interpret=True)
    assert np.isfinite(np.asarray(base)).all()
    np.testing.assert_array_equal(np.asarray(base)[0, :, :],
                                  np.asarray(poisoned)[0, :, :])


def _walk_case(name):
    """(c, c padded to whole query tiles, heads, offsets, table columns):
    shapes of page 4 x 2 kv heads x 8, whose groups ``_page_group`` sizes;
    the offsets in units of its pages."""
    page, h = 4, 4
    if name == "chunk_bound_crosses_a_group":
        # 48 positions x 32 heads: tiles of 32 positions (1,024 folded rows);
        # row 0's first tile ends a page short of the seam between its
        # second and third groups and its second tile starts there, row 1's
        # first tile straddles the seam
        h, c = 32, 48
        g = _page_group(1024, page, 2, 8, 2)
        offs = [2 * g * page - 32 - page, 2 * g * page - 10]
        return c, 64, h, offs, 4 * g
    g = _page_group(h, page, 2, 8, 2)
    assert g > 1  # a decode step's few rows take many pages a group
    cols = 2 * g + 3
    offs = {
        # lanes of 1 token, exactly a group, a group + 1 page, the whole table
        "decode_lanes_of_unequal_length": [0, g * page - 1, g * page,
                                           cols * page - 1],
        # a context that ends inside a page: first slot, middle, last slot
        "context_ends_inside_a_page": [(g + 1) * page, (g + 1) * page + 2,
                                       (g + 2) * page - 1, 2],
    }[name]
    return 1, 1, h, offs, cols


def _walk_operands(name):
    """q, the K and V pages of one layer (the last a page of NaN), the table
    whose columns past a row's last (padded) query name that page, the same
    table naming page 0 there, the offsets."""
    rng = np.random.RandomState(13)
    c, c_pad, h, offs, cols = _walk_case(name)
    b, kvh, dh, page = len(offs), 2, 8, 4
    n_pages = 2 + b * cols
    q = jnp.asarray(rng.randn(b, c, h, dh), jnp.float32) * 0.5
    kp = np.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, np.float32)
    vp = np.asarray(rng.randn(n_pages, page, kvh, dh) * 0.5, np.float32)
    kp[-1] = vp[-1] = np.nan
    table = np.asarray(
        [np.random.RandomState(i).permutation(np.arange(1, n_pages - 1))[:cols]
         for i in range(b)], np.int32)
    clean = table.copy()
    for r, off in enumerate(offs):
        table[r, (off + c_pad - 1) // page + 1:] = n_pages - 1
        clean[r, (off + c_pad - 1) // page + 1:] = 0
    return (q, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16),
            jnp.asarray(table), jnp.asarray(clean), jnp.asarray(offs, jnp.int32))


WALK_CASES = ["decode_lanes_of_unequal_length", "context_ends_inside_a_page",
              "chunk_bound_crosses_a_group"]


@LAYERS
@pytest.mark.parametrize("name", WALK_CASES)
def test_paged_flash_attention_walks_live_pages_in_groups(name, layer):
    """The kernel walks each row's pages itself, a group at a time, and
    copies only those up to its last query: rows whose walks end at
    different groups in one call, the last group partly dead, every column
    past a row's bound naming a page of NaN (never copied: a NaN read there
    would show even under the mask, through 0 x NaN)."""
    q, kp, vp, table, clean, off = _walk_operands(name)
    out = paged_flash_attention(q, *_in_pool(layer, kp, vp), layer, table, off,
                                interpret=True)
    ref = _dense_paged_reference(q, kp, vp, clean, off)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def _row_major(*pools):
    """Pools [.., kv heads, width] as a head narrower than 128 lanes is
    served: a token's heads side by side on the last axis."""
    return [x.reshape(*x.shape[:-2], -1) for x in pools]


@pytest.mark.parametrize("name", WALK_CASES)
def test_the_walk_of_row_major_pools_gives_the_same(name):
    """The walk a narrow head takes (``_narrow_kernel``: a page copied as it
    is held, the products a run of heads at a time under zero-extended
    queries) against the dense reference on the kernel walk's own cases;
    dead columns name a page of NaN there too. The probabilities go to the
    values' product in the pools' type (bfloat16, as the latent kernel's):
    2e-3 of values of 0.5, where the float32 walk holds 3e-5."""
    q, kp, vp, table, clean, off = _walk_operands(name)
    out = paged_flash_attention(q, *_row_major(*_in_pool(1, kp, vp)), 1, table,
                                off, interpret=True)
    ref = _dense_paged_reference(q, kp, vp, clean, off)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-3)


#: (query heads, K/V heads, head width, page, chunk, offsets, window): heads
#: of 64 and 32 whose tokens are whole 128-lane runs (LFM2's 32 / 8 x 64: four
#: runs of two heads; the decoder default's 8 / 4 x 32: one run of four), a
#: head of 96 (Phi-3-mini's: runs of 384 lanes, four heads), a token that is
#: no whole run (interpreted only: one run), a decode step, a chunk of one
#: tile and of several (its last padded), a window over a ring
NARROW_CASES = {
    "lfm2_decode_8_kv_of_64": (32, 8, 64, 16, 1, (0, 15, 16, 700), 0),
    "lfm2_chunk_one_tile": (32, 8, 64, 16, 24, (0, 100, 333), 0),
    "lfm2_chunk_three_tiles": (32, 8, 64, 16, 70, (5, 260), 0),
    "default_4_kv_of_32": (8, 4, 32, 16, 40, (3, 200), 0),
    "mha_8_kv_of_96": (8, 8, 96, 4, 12, (0, 50), 0),
    "three_kv_of_8_one_run": (6, 3, 8, 4, 5, (2, 37), 0),
    "window_ring_of_64": (16, 4, 64, 16, 40, (0, 100, 4000), 128),
}


@pytest.mark.parametrize("name", list(NARROW_CASES))
def test_narrow_heads_walk_row_major_pools(name):
    """Heads of 64, 32 and 96 from row-major pools against attention over
    each row's logical keys: the zero-extended queries meet their own
    head's lanes only, a row keeps its own head's lanes of the values'
    product, GQA by the fold."""
    h, kvh, dh, page, c, offs, window = NARROW_CASES[name]
    rng = np.random.RandomState(len(name))
    b = len(offs)
    tile_c = ragged_attention.query_tile(c, h)
    c_pad = -(-c // tile_c) * tile_c
    t = -(-(max(offs) + c_pad) // page) * page
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    k, v = (bf(rng.randn(b, t, kvh, dh) * 0.5) for _ in range(2))
    cols = (window + c_pad - 2) // page + 2 if window else t // page
    n_pages = 2 + b * cols
    kp = np.zeros((3, n_pages, page, kvh * dh), np.float32)
    vp = np.zeros_like(kp)
    kp[[0, 2]] = vp[[0, 2]] = kp[1, -1] = vp[1, -1] = np.nan
    table = np.full((b, cols), 0 if window else n_pages - 1, np.int32)
    for r, off in enumerate(offs):
        first = max(off - (window - 1), 0) // page if window else 0
        own = 1 + r * cols + rng.permutation(cols)
        for i in range(first, (off + c_pad - 1) // page + 1):
            pg = own[i % cols]
            table[r, i % cols if window else i] = pg
            kp[1, pg] = np.asarray(k[r, i * page:(i + 1) * page], np.float32
                                   ).reshape(page, -1)
            vp[1, pg] = np.asarray(v[r, i * page:(i + 1) * page], np.float32
                                   ).reshape(page, -1)
    q = bf(rng.randn(b, c, h, dh) * 0.5)
    off = jnp.asarray(offs, jnp.int32)
    out = paged_flash_attention(q, bf(kp), bf(vp), 1, jnp.asarray(table), off,
                                interpret=True, window=window)
    from arkflow_tpu.models import common as cm

    pos = off[:, None] + jnp.arange(c)[None, :]
    keys = jnp.arange(t)[None, None, None, :]
    mask = keys <= pos[:, None, :, None]
    if window:
        mask = mask & (keys > pos[:, None, :, None] - window)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    ref = cm.attention(f32(q), jnp.repeat(f32(k), h // kvh, axis=2),
                       jnp.repeat(f32(v), h // kvh, axis=2), mask)
    assert out.shape == q.shape and np.isfinite(np.asarray(f32(out))).all()
    np.testing.assert_allclose(np.asarray(f32(out)), np.asarray(ref), atol=2e-2)


# -- a chunk's tile: each K/V head's keys by that head's own query rows ---------

#: (query heads, K/V heads, key width, value width, page, chunk, offsets,
#: window, sink) at the head geometries the benchmark's cells serve, small:
#: query heads a K/V head 4, 5, 8, 16; K/V heads 2, 4, 8; a key of 192 held
#: in two parts of 128 lanes beside values of 128; a window of 128 over a
#: ring; a sink; a chunk that is one query tile and one that is several
#: (its last padded); offsets past one group of the walk, whose last group
#: is partly dead
PER_HEAD_CASES = {
    "l6_4_a_head_8_kv_one_tile": (32, 8, 8, 8, 4, 16, (0, 37, 150), 0, False),
    "tp4_share_4_a_head_2_kv": (8, 2, 8, 8, 4, 24, (5, 190), 0, False),
    "falconh1_5_a_head_tiles_of_48": (20, 4, 8, 8, 4, 112, (3, 64), 0, False),
    "kexaone_full_8_a_head_three_tiles": (64, 8, 8, 8, 4, 40, (0, 129), 0, False),
    "kexaone_window_ring": (64, 8, 8, 8, 16, 40, (0, 100, 4000), 128, False),
    "mimo_full_16_a_head_key_in_parts": (64, 4, 192, 128, 4, 32, (7, 200), 0, False),
    "mimo_window_ring_sink_parts": (64, 8, 192, 128, 16, 32, (0, 130, 3000), 128, True),
    "full_layer_sink_one_kv_head": (8, 1, 8, 16, 4, 8, (2, 140), 0, True),
}


def _per_head_operands(name):
    """The logical keys and values of each row [rows, T, kv heads, width]
    (bfloat16 values), the pools that hold them by a scattered table — or a
    ring of just the pages a window needs —, K in parts of 128 lanes where
    it is wider, as layer 1 of three (the others NaN), every column past a
    row's last padded query a page of NaN."""
    h, kvh, dk, dv, page, c, offs, window, sink = CASES[name]
    rng = np.random.RandomState(len(name))
    b = len(offs)
    tile_c = ragged_attention.query_tile(c, h)
    c_pad = -(-c // tile_c) * tile_c
    t = -(-(max(offs) + c_pad) // page) * page
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))  # noqa: E731
    k, v = bf(rng.randn(b, t, kvh, dk) * 0.5), bf(rng.randn(b, t, kvh, dv) * 0.5)
    cols = (window + c_pad - 2) // page + 2 if window else t // page
    n_pages = 2 + b * cols
    parts = 1 if dk <= 128 or dk % 128 == 0 else -(-dk // 128)
    held = dk if parts == 1 else 128
    kp = np.zeros((n_pages, page, kvh, parts * held), np.float32)
    vp = np.zeros((n_pages, page, kvh, dv), np.float32)
    kp[-1] = vp[-1] = np.nan
    table = np.full((b, cols), n_pages - 1 if not window else 0, np.int32)
    for r, off in enumerate(offs):
        last = (off + c_pad - 1) // page
        first = max(off - (window - 1), 0) // page if window else 0
        own = 1 + r * cols + rng.permutation(cols)
        for i in range(first, last + 1):
            at = own[i % cols]
            table[r, i % cols] = at
            kp[at, :, :, :dk] = k[r, i * page:(i + 1) * page]
            vp[at] = v[r, i * page:(i + 1) * page]
    pool = lambda a: jnp.full((3, *a.shape), jnp.nan, jnp.bfloat16).at[1].set(  # noqa: E731
        jnp.asarray(a, jnp.bfloat16))
    k_pool = jnp.concatenate([pool(kp[..., p * held:(p + 1) * held])
                              for p in range(parts)])
    q = bf(rng.randn(b, c, h, dk) * 0.5)
    sinks = rng.randn(h).astype(np.float32) + 1.0 if sink else None
    return q, k, v, k_pool, pool(vp), jnp.asarray(table), np.asarray(offs), sinks


def _plain_attention(q, k, v, offs, window, sinks):
    """Float32, a row at a time: query i at ``off + i`` attends ``s <= t``
    (``t - window < s`` under a window); a sink joins the denominator."""
    b, c, h, dk = q.shape
    rep = h // k.shape[2]
    out = np.zeros((b, c, h, v.shape[-1]), np.float32)
    for r, off in enumerate(offs):
        kk, vv = np.repeat(k[r], rep, axis=1), np.repeat(v[r], rep, axis=1)
        s = np.einsum("chd,shd->hcs", q[r], kk) * dk ** -0.5
        pos, key = off + np.arange(c)[None, :, None], np.arange(kk.shape[0])[None, None, :]
        keep = key <= pos
        if window:
            keep &= key > pos - window
        s = np.where(keep, s, -np.inf)
        m = s.max(-1, keepdims=True)
        if sinks is not None:
            m = np.maximum(m, sinks[:, None, None])
        p = np.exp(s - m)
        den = p.sum(-1, keepdims=True)
        if sinks is not None:
            den = den + np.exp(sinks[:, None, None] - m)
        out[r] = np.einsum("hcs,shd->chd", p / den, vv)
    return out


@pytest.mark.parametrize("name", list(PER_HEAD_CASES))
def test_a_chunks_tile_multiplies_each_kv_head_by_its_own_rows(name):
    """Every geometry's chunk takes the per-head cut (the ONE predicate),
    walks more than one group where its offsets reach past one, and gives
    the plain attention's answer; no NaN page is ever copied."""
    h, kvh, dk, dv, page, c, offs, window, sink = PER_HEAD_CASES[name]
    tile_c = ragged_attention.query_tile(c, h)
    assert ragged_attention.per_kv_head(tile_c, h, kvh)
    q, k, v, k_pool, v_pool, table, offs, sinks = _per_head_operands(name)
    parts = k_pool.shape[0] // 3
    group = _page_group(tile_c * h, page, kvh, parts * k_pool.shape[-1], 2, dv,
                        per_head=True)
    walked = [(o + c - 1) // page + 1 - (max(o - (window - 1), 0) // page if window else 0)
              for o in offs]
    assert window or (max(walked) > group and max(walked) % group)
    out = paged_flash_attention(
        jnp.asarray(q), k_pool, v_pool, 1, table, jnp.asarray(offs, jnp.int32),
        interpret=True, window=window,
        sink=None if sinks is None else jnp.asarray(sinks))
    assert out.shape == (*q.shape[:3], dv) and np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), _plain_attention(
        q, k, v, offs, window, sinks), atol=3e-5)


# -- a run of neighbours is one copy ---------------------------------------------

#: (query heads, K/V heads, key width, value width): ONE K/V head of 256
#: lanes (a call of ``GqaSpec.split_heads``' pools), 2 and 8 heads of 128,
#: and keys of 192 held in two parts of 128 beside values of 128
RUN_GEOMETRIES = {"one_head_of_256": (4, 1, 256, 256), "two_heads_of_128": (8, 2, 128, 128),
                  "eight_heads_of_128": (16, 8, 128, 128),
                  "keys_in_two_parts": (16, 4, 192, 128)}

#: how a row's 40-column table lies in the pool (4 keys a page), and each
#: row's first query, as (decode, chunk of 8): every aligned stretch of 8
#: columns 8 neighbours, the walks ending where a stretch does (16 and 32
#: pages); any page anywhere; a live stretch with two of its entries
#: swapped; walks that end INSIDE a stretch, whose other pages lie beside
#: the live ones and hold NaN; and a pool of 6 pages, less than one run
RUN_LAYOUTS = {
    "runs": ((63, 127, 0), (56, 120)),
    "permuted": ((63, 130, 37), (56, 100)),
    "a_stretch_broken_in_its_middle": ((130, 90), (100, 61)),
    "last_stretch_part_dead": ((37, 130, 90), (30, 100)),
    "pool_shorter_than_a_run": ((17,), (9,)),
}
RUN_PAGE, RUN_COLS = 4, 40


def _run_operands(geometry, layouts, c):
    """``_per_head_operands``' pools over a table whose rows lie as
    ``layouts`` say, layout by layout: a row owns ``RUN_COLS`` neighbouring
    pages, column i names the i-th of them (``runs``) or what its layout
    makes of that; pages no query of the row attends hold NaN."""
    h, kvh, dk, dv = RUN_GEOMETRIES[geometry]
    rows = [(name, off) for name in layouts for off in RUN_LAYOUTS[name][c > 1]]
    offs = np.asarray([off for _, off in rows])
    short = layouts == ("pool_shorter_than_a_run",)
    b, page, cols = len(rows), RUN_PAGE, 5 if short else RUN_COLS
    rng = np.random.RandomState(len(geometry) + c)
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))  # noqa: E731
    t = cols * page
    k, v = bf(rng.randn(b, t, kvh, dk) * 0.5), bf(rng.randn(b, t, kvh, dv) * 0.5)
    parts = 1 if dk % 128 == 0 else -(-dk // 128)
    held = dk if parts == 1 else 128
    n_pages = 1 + b * cols
    kp = np.full((n_pages, page, kvh, parts * held), np.nan, np.float32)
    vp = np.full((n_pages, page, kvh, dv), np.nan, np.float32)
    table = np.zeros((b, cols), np.int32)
    for r, (layout, off) in enumerate(rows):
        own = np.arange(cols)
        if layout == "permuted":
            own = rng.permutation(cols)
        elif not short:  # the blocks of 8 in any order
            own = (rng.permutation(cols // 8)[:, None] * 8 + np.arange(8)).reshape(-1)
        if layout == "a_stretch_broken_in_its_middle":
            own[[10, 12]] = own[[12, 10]]
        table[r] = 1 + r * cols + own
        for i in range((off + c - 1) // page + 1):
            kp[table[r, i], :, :, dk:] = 0.0
            kp[table[r, i], :, :, :dk] = k[r, i * page:(i + 1) * page]
            vp[table[r, i]] = v[r, i * page:(i + 1) * page]
    # layer 1 of three, the others NaN; a short pool is ONE layer, or the
    # view of all layers' pages would hold a run
    pool = (lambda a: jnp.asarray(a, jnp.bfloat16)[None]) if short else (
        lambda a: jnp.full((3, *a.shape), jnp.nan, jnp.bfloat16).at[1].set(
            jnp.asarray(a, jnp.bfloat16)))
    k_pool = jnp.concatenate([pool(kp[..., p * held:(p + 1) * held])
                              for p in range(parts)])
    q = bf(rng.randn(b, c, h, dk) * 0.5)
    return q, k, v, k_pool, pool(vp), table, offs, 0 if short else 1


@functools.lru_cache(maxsize=None)
def _walked_both_ways(geometry, c, short):
    """ONE call a geometry and a tile, every layout's rows in it (a short
    pool: a call of its own), with runs and — ``PAGE_RUN`` 0 — a page a
    copy, groups of two stretches (a decode tile) and of one (a chunk
    tile); what each layout's case below looks at: the rows' layouts, the
    table and offsets, both outputs and the plain attention's."""
    layouts = ("pool_shorter_than_a_run",) if short else tuple(
        name for name in RUN_LAYOUTS if name != "pool_shorter_than_a_run")
    q, k, v, k_pool, v_pool, table, offs, layer = _run_operands(geometry, layouts, c)
    outs = []
    with pytest.MonkeyPatch.context() as mp:
        for name, most in (("_PAGED_GROUP_MAX", 16), ("_PAGED_ONE_HEAD_GROUP_MAX", 16),
                           ("_PAGED_CHUNK_GROUP_MAX", 8)):
            mp.setattr(ragged_attention, name, most)
        # (a short pool's call traces to ONE program whatever ``PAGE_RUN``
        # is — the test below —: made once)
        for run in (8,) if short else (8, 0):
            mp.setattr(ragged_attention, "PAGE_RUN", run)
            outs.append(np.asarray(paged_flash_attention.__wrapped__(
                jnp.asarray(q), k_pool, v_pool, layer, jnp.asarray(table),
                jnp.asarray(offs, jnp.int32), interpret=True)))
    outs = outs * 2 if short else outs
    of = [name for name in layouts for _ in RUN_LAYOUTS[name][c > 1]]
    return of, table, offs, outs, _plain_attention(q, k, v, offs, 0, None)


@pytest.mark.parametrize("c", [1, 8], ids=["decode_tile", "chunk_tile"])
@pytest.mark.parametrize("layout", list(RUN_LAYOUTS))
@pytest.mark.parametrize("geometry", list(RUN_GEOMETRIES))
def test_a_run_of_neighbours_is_one_copy_and_the_same_bits(geometry, layout, c):
    """The per-head kernel on ``_page_walk`` with runs (PR 61): over every
    layout of the table, at a decode tile (all heads at once) and a chunk
    tile (a K/V head at a time), the call gives BIT FOR BIT what the walk
    without runs gives (``PAGE_RUN`` 0: a page a copy), the plain
    attention's answer, and copies no page past a row's last live one (they
    hold NaN, beside the live ones)."""
    h, kvh, dk, dv = RUN_GEOMETRIES[geometry]
    assert ragged_attention.per_kv_head(ragged_attention.query_tile(c, h), h, kvh) == (c > 1)
    of, table, offs, (with_runs, without), plain = _walked_both_ways(
        geometry, c, layout == "pool_shorter_than_a_run")
    mine = [r for r, name in enumerate(of) if name == layout]
    walked = (offs[mine] + c - 1) // RUN_PAGE + 1
    in_runs = ragged_attention.pages_in_runs(table[mine], walked)
    assert (in_runs > 0) == (layout not in ("permuted", "pool_shorter_than_a_run"))
    if layout == "runs":  # every walked page but an idle lane's one
        assert in_runs == walked[walked >= 8].sum()
    got = with_runs[mine]
    assert got.shape == (len(mine), c, h, dv) and np.isfinite(got).all()
    np.testing.assert_array_equal(got, without[mine])
    np.testing.assert_allclose(got, plain[mine], atol=3e-5)


def _walk_conds(monkeypatch, run, **call):
    """The ``cond`` equations (``_by_runs``' test of a stretch) of the
    kernel a call traces to with ``PAGE_RUN`` held to ``run``."""
    monkeypatch.setattr(ragged_attention, "PAGE_RUN", run)
    q = jnp.zeros((2, 1, 8, 128), jnp.bfloat16)
    pool = jnp.zeros((2, call.pop("pages", 40), 16, 2, 128), jnp.bfloat16)
    traced = jax.make_jaxpr(lambda *a: paged_flash_attention.__wrapped__(*a, **call))(
        q, pool, pool, 1, jnp.zeros((2, 16), jnp.int32), jnp.zeros((2,), jnp.int32))
    return len(list(_eqns(traced.jaxpr, "cond")))


def test_the_per_head_walk_takes_runs_only_where_a_stretch_fits(monkeypatch):
    """``_takes_runs``, the latent kernel's rule: a call under a window (a
    ring's tile starts at an unaligned page) and one over a pool of less
    than ``PAGE_RUN`` pages trace to the walk WITHOUT runs — no test of a
    stretch in them, the program ``PAGE_RUN`` 0 gives —; a full layer's call
    holds the test where the call's first group is started, where a
    program's next is and where the next program's first is."""
    assert ragged_attention.PAGE_RUN == 8
    plain = _walk_conds(monkeypatch, 0)
    assert _walk_conds(monkeypatch, 8) == plain + 3
    assert _walk_conds(monkeypatch, 8, window=32) == _walk_conds(
        monkeypatch, 0, window=32)
    assert _walk_conds(monkeypatch, 8, pages=3) == plain
    monkeypatch.setattr(ragged_attention, "PAGE_RUN", 8)
    # a group the budget bounds is whole runs where it holds one
    assert [ragged_attention._whole_runs(n) for n in (1, 7, 8, 13, 45)] == [
        1, 7, 8, 8, 40]


# -- both products take what the pools hold -------------------------------------

#: ``PER_HEAD_CASES``' columns, served types: bfloat16 queries over bfloat16
#: pools. Decode tiles (the all-heads product), plain and under a window's
#: ring with a sink and a key in two parts; ONE K/V head of 256 lanes (a
#: head a layer of the pools: the slot is read whole), 256 lanes on two K/V
#: heads (a slot of two 128-lane runs: copied a run at a time for the
#: strided read), and a query head a K/V head (EvaByte's)
SERVED_TYPE_CASES = {
    "decode_4_a_head": (8, 2, 8, 8, 4, 1, (0, 37, 150), 0, False),
    "decode_window_ring_sink_parts": (64, 8, 192, 128, 16, 1, (0, 130, 3000), 128, True),
    "split_head_of_256_lanes": (8, 1, 256, 256, 4, 16, (5, 200), 0, False),
    "two_heads_of_256_lanes": (8, 2, 256, 256, 4, 8, (3, 100), 0, False),
    "a_query_head_a_kv_head": (8, 8, 128, 128, 4, 8, (3, 190), 0, False),
}
CASES = {**PER_HEAD_CASES, **SERVED_TYPE_CASES}   # what ``_per_head_operands`` builds
SERVED_TYPE = [*SERVED_TYPE_CASES, "l6_4_a_head_8_kv_one_tile",
               "kexaone_window_ring", "mimo_full_16_a_head_key_in_parts",
               "mimo_window_ring_sink_parts"]

#: two bfloat16 steps of the largest value: what an output rounded ONCE to
#: bfloat16 from float32 sums keeps, whichever type the products took
BF16_TOL = 2.0 ** -7


def _bf16_round(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


@pytest.mark.parametrize("name", SERVED_TYPE)
def test_bfloat16_operands_give_the_float32_reference(name):
    """Queries and pools both bfloat16, as a server's: the products take them
    as held and the probabilities rounded to bfloat16, the sums float32.
    Against the plain float32 attention the distance stays inside
    ``BF16_TOL`` — which the same call with float32 queries (float32
    operands: the old products), rounded to the output's type, meets too."""
    h, kvh, dk, dv, page, c, offs, window, sink = CASES[name]
    assert ragged_attention.per_kv_head(
        ragged_attention.query_tile(c, h), h, kvh) == (c > 1)
    q, k, v, k_pool, v_pool, table, offs, sinks = _per_head_operands(name)
    want = _plain_attention(q, k, v, offs, window, sinks)
    tol = BF16_TOL * np.abs(want).max()
    for dtype in (jnp.bfloat16, jnp.float32):
        out = paged_flash_attention(
            jnp.asarray(q, dtype), k_pool, v_pool, 1, table,
            jnp.asarray(offs, jnp.int32), interpret=True, window=window,
            sink=None if sinks is None else jnp.asarray(sinks))
        assert out.dtype == dtype and out.shape == (*q.shape[:3], dv)
        err = np.abs(_bf16_round(out) - want).max()
        assert err <= tol, (dtype, err, tol)


#: (batch, chunk, query heads, K/V heads, key width, window, sink): a decode
#: tile, a chunk tile a K/V head at a time, one over a slot read whole (one
#: K/V head), and one under a ring with a sink and a key in two parts
MECHANISM = {"decode": (2, 1, 8, 2, 128, 0, False),
             "chunk.per_head": (1, 8, 8, 2, 128, 0, False),
             "chunk.one_kv_head": (1, 8, 8, 1, 256, 0, False),
             "chunk.ring.sink.parts": (1, 8, 8, 4, 192, 32, True),
             "decode.ring.sink.parts": (2, 1, 8, 4, 192, 32, True)}


@pytest.mark.parametrize("pools", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(MECHANISM))
def test_the_kernels_products_take_the_pools_type(case, pools):
    """The mechanism itself, read off the traced kernel: under bfloat16
    queries and pools NO ``dot_general`` of ``_paged_kernel`` has a float32
    operand and every one sums in float32; float32 pools (the CPU tests'
    plain cases) keep float32 operands. Nothing chooses but the operands'
    types: no knob."""
    b, c, h, kvh, dk, window, sink = MECHANISM[case]
    dtype = jnp.dtype(pools)
    parts = 1 if dk % 128 == 0 else -(-dk // 128)
    q = jnp.zeros((b, c, h, dk), dtype)
    k_pool = jnp.zeros((2 * parts, 9, 16, kvh, dk if parts == 1 else 128), dtype)
    v_pool = jnp.zeros((2, 9, 16, kvh, 128), dtype)
    traced = jax.make_jaxpr(lambda q, k, v, t, o, s: paged_flash_attention(
        q, k, v, 1, t, o, window=window, sink=s))(
        q, k_pool, v_pool, jnp.zeros((b, 4), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((h,), jnp.float32) if sink else None)
    (call,) = _paged_calls(traced.jaxpr)
    dots = list(_eqns(call.params["jaxpr"], "dot_general"))
    # the scores' product a part of the key, and the values'
    per_head = ragged_attention.per_kv_head(ragged_attention.query_tile(c, h), h, kvh)
    assert len(dots) == (parts + 1) * (kvh if per_head else 1)
    for eqn in dots:
        assert [v.aval.dtype for v in eqn.invars] == [dtype, dtype], eqn
        assert eqn.outvars[0].aval.dtype == jnp.float32
        assert eqn.params["preferred_element_type"] == jnp.float32


def _sums_rounded(q, k, v, off, step: int):
    """What a kernel that kept its SUMS in bfloat16 would give: the online
    softmax over groups of ``step`` keys, the value sum and the denominator
    rounded to bfloat16 after every group (one row, every key attendable)."""
    h, rep = q.shape[1], q.shape[1] // k.shape[1]
    kk, vv = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
    s = np.einsum("chd,shd->hcs", q, kk[:off + 1]) * q.shape[-1] ** -0.5
    o = np.zeros((h, q.shape[0], v.shape[-1]), np.float32)
    m, l = np.full(s.shape[:2] + (1,), -1e30, np.float32), 0.0
    for a in range(0, s.shape[-1], step):
        m_new = np.maximum(m, s[..., a:a + step].max(-1, keepdims=True))
        p, corr = np.exp(s[..., a:a + step] - m_new), np.exp(m - m_new)
        o = _bf16_round(o * corr + np.einsum("hcs,shd->hcd", p, vv[a:a + step]))
        l = _bf16_round(l * corr + p.sum(-1, keepdims=True))
        m = m_new
    return (o / l).transpose(1, 0, 2)


@pytest.mark.parametrize("c", [1, 8], ids=["decode", "chunk"])
def test_a_row_of_many_small_probabilities_keeps_its_sum(c):
    """What rounding the probabilities could hurt: a row whose mass sits in
    4,096 terms of about 1 / 4,096 each (queries near zero: scores within a
    few hundredths of each other, none equal). Each term is rounded to eight
    bits on its own and the errors do not line up: the value sum stays
    inside ``BF16_TOL`` of the float32 attention's — where the same softmax
    with its SUMS kept in bfloat16 (``_sums_rounded``) does not, so the
    tolerance can tell the two."""
    rng = np.random.RandomState(60)
    h, kvh, dh, page, keys = 8, 2, 128, 16, 4096
    off = keys - c
    k, v = (_bf16_round(rng.randn(keys, kvh, dh) * 0.5) for _ in range(2))
    q = _bf16_round(rng.randn(1, c, h, dh) * 0.02)
    n_pages = keys // page
    table = 1 + rng.permutation(n_pages)
    pool = lambda a: jnp.zeros((2, 1 + n_pages, page, kvh, dh), jnp.bfloat16).at[  # noqa: E731
        1, table].set(jnp.asarray(a.reshape(n_pages, page, kvh, dh), jnp.bfloat16))
    out = paged_flash_attention(
        jnp.asarray(q, jnp.bfloat16), pool(k), pool(v), 1,
        jnp.asarray(table[None], jnp.int32), jnp.asarray([off], jnp.int32),
        interpret=True)
    want = _plain_attention(q, k[None], v[None], [off], 0, None)
    p_max = np.exp(np.einsum("chd,shd->hcs", q[0], np.repeat(k, h // kvh, 1))
                   * dh ** -0.5)
    assert (p_max / p_max.sum(-1, keepdims=True)).max() < 2.0 / keys
    tol = BF16_TOL * np.abs(want).max()
    assert np.abs(_bf16_round(out) - want).max() <= tol
    group = _page_group(ragged_attention.query_tile(c, h) * h, page, kvh, dh, 2,
                        per_head=c > 1) * page
    assert keys // group >= 8                     # many steps of the walk
    rounded = _sums_rounded(q[0, -1:], k, v, keys - 1, group)
    assert np.abs(rounded - want[0, -1:]).max() > tol


#: sha256 (first 16 hex, source positions stripped) of the jaxprs of calls
#: the predicate keeps on the all-heads product, recorded at PR 43's parent
#: (f4299c3): a decode tile, a decode tile under a window with a sink, and
#: chunk tiles whose rows a K/V head are no multiple of a sublane tile
#: PR 60 RE-RECORDED all four: it changed the
#: kernel's body on purpose (both products take the type the pools hold; a
#: chunk tile reads a head's rows out of the slot's own words), so every
#: program that holds ``_paged_kernel`` moved and nothing else did (recorded before it: 4cf27ca18560d7e8,
#: 9c5d04ac365f833f, 492d84aa8529d818, c9a9dc405233f779): what the test holds
#: since is that these tiles stay on the all-heads product
#: PR 61 RE-RECORDED all four (ea922909c22c8d02, 4ba87dd276930d01, cd476856d8a41b8a, a0f623dd5bf43b43 before it): its walk is ``_page_walk``'s (a run of ``PAGE_RUN`` neighbours a copy out of pools that
#: ride as flat rows, a program's last step starting the next program's first
#: group): every program that holds ``_paged_kernel`` moved — a window call's
#: too, whose walk takes no runs but shares the copies and the hand-on — and
#: nothing else did
ALL_HEADS_GOLDEN = {
    "decode": ((2, 1, 8, 2, 0, False), "e7d4eeaa6d183826"),
    "decode.window.sink": ((2, 1, 8, 4, 32, True), "7785e8990af24804"),
    "odd_rows": ((1, 3, 4, 2, 0, False), "7fa92c3bfeea186b"),
    "odd_rows.five_a_head": ((1, 7, 10, 2, 0, False), "bb8a50a130b69455")}


@pytest.mark.parametrize("case", sorted(ALL_HEADS_GOLDEN))
def test_the_predicate_keeps_decode_and_odd_tiles_on_the_all_heads_product(case):
    """A decode step's tile (one position: 4 rows a K/V head here, 2 under
    the window) and a chunk tile of 6 or 35 rows a head trace to exactly
    what they traced to before the per-head cut existed."""
    import hashlib
    import re

    (b, c, h, kvh, window, sink), want = ALL_HEADS_GOLDEN[case]
    assert not ragged_attention.per_kv_head(ragged_attention.query_tile(c, h), h, kvh)
    q = jnp.zeros((b, c, h, 128), jnp.bfloat16)
    pool = jnp.zeros((2, 9, 16, kvh, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, o, s: paged_flash_attention(
        q, k, v, 1, t, o, window=window, sink=s))(
        q, pool, pool, jnp.zeros((b, 4), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((h,), jnp.float32) if sink else None)
    text = re.sub(r"0x[0-9a-f]+", "0x", re.sub(r" at [^\s\]]+:\d+", "", str(jaxpr)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want


def _eqns(jaxpr, name):
    """Every equation of primitive ``name`` under a jaxpr, those inside
    its calls and loops too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for v in eqn.params.values():
            for inner in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(inner, "jaxpr", inner)
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, name)


def _paged_calls(jaxpr):
    return _eqns(jaxpr, "pallas_call")


def _paged_call(dh, row_major=False, **kw):
    """The kernel call ``paged_flash_attention`` traces to at head size ``dh``."""
    q = jnp.zeros((2, 1, 4, dh), jnp.bfloat16)
    pool = jnp.zeros((2, 9, 16, 4 * dh) if row_major else (2, 9, 16, 4, dh),
                     jnp.bfloat16)
    traced = jax.make_jaxpr(lambda *a: paged_flash_attention(*a, **kw))(
        q, pool, pool, 0, jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32))
    (call,) = _paged_calls(traced.jaxpr)
    return call


@pytest.mark.parametrize("dh", [128, 256, 32, 64, 96])
def test_who_walks_the_table_follows_the_head_size(dh):
    """The kernel walks the table — a grid of (row, query tile) — at every
    head size: a head of a multiple of 128 lanes from pools [.., kv heads,
    width], a narrower one from ROW-MAJOR pools [.., kv heads * width] (its
    token whole runs of lcm(width, 128) lanes; test_kernels_compile_tpu.py
    compiles both). Pools [.., kv heads, width < 128] are walked interpreted
    only (so the CPU tests above hold the wide head's walk to the reference
    at small widths) and compile for a chip to an error by name."""
    narrow = dh % 128 != 0
    assert len(_paged_call(dh, row_major=narrow).params["grid_mapping"].grid) == 2
    if narrow:
        assert len(_paged_call(dh, interpret=True).params["grid_mapping"].grid) == 2
        with pytest.raises(ValueError, match="row-major: cache_spec"):
            _paged_call(dh)
    from arkflow_tpu.ops.ragged_attention import kernel_walks, narrow_run

    assert kernel_walks(dh, dh, False, kvh=0 if not narrow else 4)
    assert not kernel_walks(64, 64, False, kvh=1)     # half a run a token
    assert (narrow_run(8, 64), narrow_run(4, 32), narrow_run(32, 96),
            narrow_run(3, 8)) == (128, 128, 384, 24)


def test_page_group_follows_the_rows_of_the_call():
    """Many pages a step of the walk where a decode step folds 8-32 rows,
    few where a chunk tile folds ~1,024; at least one whatever the shapes."""
    decode = [_page_group(rows, 16, kvh, 128, 2)
              for rows, kvh in ((32, 8), (8, 2), (20, 4), (64, 8))]
    chunk = [_page_group(rows, 16, kvh, 128, 2)
             for rows, kvh in ((1024, 8), (1024, 2), (960, 4), (1024, 8))]
    assert all(d >= 8 for d in decode) and all(c >= 1 for c in chunk)
    assert all(c <= d for c, d in zip(chunk, decode))
    assert chunk[0] < decode[0]  # 1,024 rows x 128 columns a page: the budget
    assert chunk[0] < chunk[1]   # a quarter of the kv heads: narrower pages
    assert _page_group(4096, 64, 8, 256, 4) == 1


@pytest.mark.parametrize("vmem_mib,budget_mib", [(128, 24.0), (64, 12.8), (16, 3.2)],
                         ids=["v5e", "v5p", "v4"])
def test_walk_budget_follows_the_chips_vmem(monkeypatch, vmem_mib, budget_mib):
    """The walk's budget is a fifth of a core's VMEM, at most 24 MiB, so the
    limit the call asks for (twice the budget and 8 MiB) fits the chip, and
    the groups shrink with it; with no TPU (this CI) it is a v5e's."""
    import types

    from jax.experimental.pallas import tpu as pltpu

    assert _walk_budget() == 24 << 20
    at_v5e = _page_group(1024, 16, 8, 128, 2)
    monkeypatch.setattr(pltpu, "get_tpu_info", lambda: types.SimpleNamespace(
        vmem_capacity_bytes=vmem_mib << 20))
    assert _walk_budget() == int(budget_mib * (1 << 20))
    assert 2 * _walk_budget() + (8 << 20) < vmem_mib << 20
    assert 1 <= _page_group(1024, 16, 8, 128, 2) <= at_v5e
    assert (_page_group(1024, 16, 8, 128, 2) < at_v5e) == (vmem_mib < 128)


def test_paged_kernel_programs_run_in_order():
    """The call's first program zeroes the V slots and every later one
    counts on it (a dead page's columns are masked, but 0 x NaN is NaN): the
    grid's dimensions have to stay ``arbitrary`` — one core, in order. A
    ``parallel`` dimension needs the dead tail zeroed a program first."""
    for window in (0, 32):
        params = _paged_call(128, window=window).params["compiler_params"]
        assert params["mosaic_tpu"].dimension_semantics == ("arbitrary", "arbitrary")


def test_ragged_flash_attention_empty_and_single_token_rows():
    """The packed-path ragged kernel on the degenerate rows chunked traffic
    produces: length 0 (fully padded — rows must emit zeros, never NaN) and
    length 1 (single-token tail) vs the masked dense reference."""
    from arkflow_tpu.ops.ragged_attention import ragged_flash_attention

    rng = np.random.RandomState(2)
    b, h, s, d = 3, 2, 16, 8
    q, k, v = (jnp.asarray(rng.randn(b, h, s, d), jnp.float32) * 0.5
               for _ in range(3))
    lengths = jnp.array([16, 1, 0], jnp.int32)
    out = ragged_flash_attention(q, k, v, lengths, tile_q=4, tile_k=4,
                                 interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    # empty row: all zeros
    assert np.allclose(np.asarray(out[2]), 0.0)
    # single-token row: position 0 attends exactly key 0 -> v[...,0,:]
    np.testing.assert_allclose(np.asarray(out[1, :, 0]),
                               np.asarray(v[1, :, 0]), atol=2e-5)
    assert np.allclose(np.asarray(out[1, :, 1:]), 0.0)
    # full row still matches the dense reference
    scores = jnp.einsum("hqd,hkd->hqk", q[0], k[0]) / math.sqrt(d)
    ref = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v[0])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref), atol=2e-5)


# -- model-level parity (decode + chunked prefill vs gather) ------------------


def _tiny_setup(seed=0):
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    return cfg, fam.init(jax.random.PRNGKey(seed), cfg)


def test_paged_kernel_decode_and_chunk_argmax_parity():
    """Full model steps (scatter + kernel + MLP stack) with adversarial page
    tables: scattered non-contiguous pages, an inactive slot parked on the
    scratch page row, and a chunk at a nonzero offset — every argmax must
    match the dense-gather reference."""
    cfg, params = _tiny_setup()
    kp, vp = init_page_pool(cfg, num_pages=11, page_size=4)
    table = jnp.asarray([[5, 2, 7, 9, 0, 0, 0, 0],
                         [1, 3, 4, 6, 8, 0, 0, 0],
                         [0, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)  # scratch row
    ids = jnp.asarray([[3, 17, 42, 7, 91, 0, 0, 0],
                       [5, 9, 1, 2, 3, 4, 5, 6],
                       [0, 0, 0, 0, 0, 0, 0, 0]], jnp.int32)
    lens = jnp.asarray([5, 8, 0], jnp.int32)
    nxt, kp, vp = paged_prefill(params, cfg, ids, lens, table, kp, vp)
    act = jnp.asarray([True, True, False])

    args = (params, cfg, nxt, lens, act, table, kp, vp)
    ref, kg, vg = paged_decode_step(*args, return_logits=True)
    got, kpp, vpp = paged_decode_step(*args, return_logits=True,
                                      attention_kernel="paged",
                                      kernel_interpret=True)
    assert (jnp.argmax(ref[:2], -1) == jnp.argmax(got[:2], -1)).all()
    # beyond argmax: logits agree to the bf16-ulp tolerance the different
    # softmax accumulation order can introduce across layers
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got), atol=0.05)

    cids = jnp.asarray([[7, 8, 3], [1, 2, 0], [0, 0, 0]], jnp.int32)
    clen = jnp.asarray([3, 2, 0], jnp.int32)  # incl. an EMPTY chunk row
    ref, *_ = paged_prefill_chunk(params, cfg, cids, lens, clen, table,
                                  kp, vp, return_all=True)
    got, *_ = paged_prefill_chunk(params, cfg, cids, lens, clen, table,
                                  kp, vp, return_all=True,
                                  attention_kernel="paged",
                                  kernel_interpret=True)
    # argmax parity on the REAL positions of the real rows
    for r, n in ((0, 3), (1, 2)):
        assert (jnp.argmax(ref[r, :n], -1) == jnp.argmax(got[r, :n], -1)).all()
    assert np.isfinite(np.asarray(got)).all()


def test_carried_pools_are_written_in_place_and_alike():
    """The pools ride whole through the layer scan: after a chunk and two
    decode steps every layer's writes touched only its own (layer, page,
    offset) cells — the rest of both pools (a random fill) is as it was,
    bit for bit — and ``gather`` and ``paged`` hold the same rows: the same
    bits at layer 0, whose keys no attention precedes, and within the
    rounding of the two kernels' accumulation orders below it."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**{**TINY, "layers": 3})
    params = fam.init(jax.random.PRNGKey(3), cfg)
    page, rng = 4, np.random.RandomState(0)
    zeros, _ = init_page_pool(cfg, num_pages=11, page_size=page)
    fill = [jnp.asarray(rng.randn(*zeros.shape) * 0.5, zeros.dtype)
            for _ in range(2)]
    table = jnp.asarray([[5, 2, 7, 9], [1, 3, 4, 6], [0, 0, 0, 0]], jnp.int32)
    ids = jnp.asarray([[3, 17, 42, 7, 91, 2], [5, 9, 1, 0, 0, 0],
                       [0, 0, 0, 0, 0, 0]], jnp.int32)
    off = jnp.asarray([2, 5, 0], jnp.int32)   # mid-page, across a boundary
    clen = jnp.asarray([6, 3, 0], jnp.int32)  # incl. an EMPTY row
    act = jnp.asarray([True, True, False])

    def run(kern):
        kw = dict(attention_kernel=kern, kernel_interpret=True)
        logits, kp, vp = paged_prefill_chunk(params, cfg, ids, off, clen,
                                             table, *fill, **kw)
        lens = off + clen
        for _ in range(2):
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            logits, kp, vp = paged_decode_step(params, cfg, tok, lens, act,
                                               table, kp, vp,
                                               return_logits=True, **kw)
            lens = lens + act
        return np.asarray(kp, np.float32), np.asarray(vp, np.float32)

    gather, paged = run("gather"), run("paged")
    # the cells a layer may write: positions off .. off+clen+2 of each live
    # row through its table, and the scratch page's first cell (page 0,
    # offset 0: padding and the inactive lane)
    written = np.zeros(zeros.shape[1:3], bool)
    written[0, 0] = True
    for row, (o, n) in enumerate(zip(np.asarray(off), np.asarray(clen))):
        for pos in range(o, o + n + 2) if n else ():
            written[int(table[row, pos // page]), pos % page] = True
    for got, ref, was in zip(paged, gather, fill):
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_allclose(got, ref, atol=0.05)
        was = np.asarray(was, np.float32)
        live = written.copy()
        live[0, 0] = False                               # scratch: any value
        for pool in (got, ref):
            # [layers, pages, page]: a token's heads on one axis or two
            changed = (pool != was).reshape(*pool.shape[:3], -1).any(axis=-1)
            assert not changed[:, ~written].any()
            assert changed[:, live].all()


def test_paged_kernel_tp_host_mesh_parity():
    """tp=2 forced host mesh: the kernel runs per-shard inside shard_map
    (pools sharded over KV heads, no all-gather) and must match the
    sharded gather path's argmax, jitted exactly like the serving steps."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    from arkflow_tpu.parallel.mesh import (MeshSpec, create_mesh,
                                           kv_pool_sharding, shard_params)

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    mesh = create_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])
    axes = {n: n for n in mesh.axis_names}
    sharded = shard_params(params, fam.param_specs(cfg, axes), mesh)
    kv = kv_pool_sharding(mesh, row_major=True)  # heads of 16: row-major

    kp, vp = init_page_pool(cfg, num_pages=9, page_size=4)
    assert kp.shape == (2, 9, 4, 2 * 16)
    kp = jax.device_put(kp, kv)
    vp = jax.device_put(vp, kv)
    table = jnp.asarray([[5, 2, 7, 0, 0, 0, 0, 0],
                         [1, 3, 4, 6, 8, 0, 0, 0]], jnp.int32)
    ids = jnp.asarray([[3, 17, 42, 7, 91, 0, 0, 0],
                       [5, 9, 1, 2, 3, 4, 5, 6]], jnp.int32)
    lens = jnp.asarray([5, 8], jnp.int32)
    nxt, kp, vp = paged_prefill(sharded, cfg, ids, lens, table, kp, vp,
                                kv_sharding=kv)
    act = jnp.asarray([True, True])

    def step(kern):
        fn = jax.jit(lambda kp, vp: paged_decode_step(
            sharded, cfg, nxt, lens, act, table, kp, vp, return_logits=True,
            kv_sharding=kv, attention_kernel=kern,
            kernel_interpret=True))
        lg, *_ = fn(kp, vp)
        return lg

    ref, got = step("gather"), step("paged")
    assert (jnp.argmax(ref, -1) == jnp.argmax(got, -1)).all()

    def chunk(kern):
        cids = jnp.asarray([[7, 8], [1, 2]], jnp.int32)
        clen = jnp.asarray([2, 2], jnp.int32)
        fn = jax.jit(lambda kp, vp: paged_prefill_chunk(
            sharded, cfg, cids, lens, clen, table, kp, vp, return_all=True,
            kv_sharding=kv, attention_kernel=kern,
            kernel_interpret=True))
        lg, *_ = fn(kp, vp)
        return lg

    ref, got = chunk("gather"), chunk("paged")
    assert (jnp.argmax(ref, -1) == jnp.argmax(got, -1)).all()


# -- server-level: kernel knob, parity gate, dispatch depth -------------------


def _serve(params, cfg, prompts, max_new, **kw):
    async def go():
        srv = GenerationServer(params, cfg, slots=2, page_size=4,
                               max_seq=40, **kw)
        free0 = len(srv._free_pages)
        outs = await asyncio.gather(*[
            srv.generate(p, max_new_tokens=max_new) for p in prompts])
        await srv.close()
        # every page returned (pages the prefix cache legitimately holds
        # are accounted, not leaked)
        assert len(srv._free_pages) == free0 - srv._cache_held
        assert srv._pipeline is None
        return outs, srv

    return asyncio.run(go())


def test_server_paged_kernel_matches_gather():
    cfg, params = _tiny_setup(seed=3)
    ref, _ = _serve(params, cfg, TP_PROMPTS, 6)
    got, srv = _serve(params, cfg, TP_PROMPTS, 6,
                      decode_kernel="paged", kernel_interpret=True)
    assert got == ref
    assert srv.decode_kernel == "paged"  # the parity gate kept the kernel
    assert srv.health_report()["decode_kernel"] == "paged"


def test_server_counts_the_pages_its_rows_walk():
    """``arkflow_gen_attn_pages_walked_total`` beside ``_table_columns_total``,
    from lengths on the host: one prompt of 13 tokens in chunks of 8, then
    decode steps over two lanes, one of them idle (it walks its one scratch
    page). A ``gather`` server, which walks nothing, counts nothing."""
    cfg, params = _tiny_setup(seed=3)
    plain = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=40)
    assert plain.m_attn_walk == {}
    asyncio.run(plain.close())

    async def go():
        srv = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=40,
                               prefill_chunk=8, decode_kernel="paged",
                               kernel_interpret=True)
        before = {k: [m.value for m in pair] for k, pair in srv.m_attn_walk.items()}
        out = await srv.generate(list(range(1, 14)), max_new_tokens=5)
        await srv.close()
        return out, {k: [m.value - v for m, v in zip(pair, before[k])]
                     for k, pair in srv.m_attn_walk.items()}

    out, got = asyncio.run(go())
    assert len(out) == 5
    cols = 10                                    # 40 positions of 4 a page
    # the chunks' last queries sit at 7 and 15: 2 and 4 pages
    # (the third counter: pages in runs, which the narrow-head walk of this
    # model's heads of 16 never takes: the test below)
    assert got["chunk"] == [2 + 4, 2 * cols, 0]
    # four decode steps at lengths 13..16 (4, 4, 4, 5 pages) + the idle lane
    assert got["decode"] == [4 + 4 + 4 + 5 + 4, 4 * 2 * cols, 0]


def test_server_counts_the_pages_a_per_head_walk_takes_in_runs():
    """``arkflow_gen_attn_pages_in_runs_total`` on a per-head server (PR 61):
    a model with a layer that keeps every key at heads of 128 lanes counts a
    hand-made table's walked pages by the kernel's predicate
    (``pages_in_runs``: row 0 walks 13 pages, its first stretch whole; row 1
    all 16, its first stretch two pages swapped; an idle lane its scratch
    page), beside a sliding layer too; a ``gather`` server counts nothing,
    and a model of sliding layers alone (a ring takes no runs; the paged
    kernel's parity probe wants a full layer, so it is asked as a ``gather``
    server) would count none."""
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.ops.ragged_attention import pages_in_runs

    table = np.zeros((3, 16), np.int32)
    table[0] = [*range(1, 9), *range(20, 28)]
    table[1] = [9, 10, 12, 11, 13, 14, 15, 16, *range(30, 38)]
    last = np.asarray([100, 127, 0])         # pages of 8 keys: 13, 16 and 1
    assert pages_in_runs(table, last // 8 + 1) == 8 + 8
    fam = get_model("decoder_lm")
    wide = {**TINY, "head_dim": 128, "max_seq": 128}
    sliding = dict(layer_types=("sliding_attention", "full_attention"),
                   sliding_window=9)
    sizes = dict(slots=3, page_size=8, max_seq=128, prefill_chunk=8)
    got = {}
    for name, more in (("full", {}), ("full_beside_sliding", sliding)):
        cfg = fam.make_config(**wide, **more)
        params = fam.init(jax.random.PRNGKey(1), cfg)
        server = GenerationServer(params, cfg, **sizes, decode_kernel="paged",
                                  kernel_interpret=True)
        counters = [global_registry().counter(
            f"arkflow_gen_attn_{what}_total",
            labels={"model": "decoder_lm", "kind": "decode"})
            for what in ("pages_walked", "pages_in_runs")]
        before = [m.value for m in counters]
        server._note_walk("decode", last, 2, table=table)
        got[name] = [m.value - v for m, v in zip(counters, before)]
        asyncio.run(server.close())
    assert got == {"full": [30, 16], "full_beside_sliding": [30, 16]}
    cfg = fam.make_config(**wide, **{**sliding, "layer_types": (
        "sliding_attention",) * 2})
    gather = GenerationServer(fam.init(jax.random.PRNGKey(1), cfg), cfg, **sizes)
    assert gather.m_attn_walk == {} and not gather._walk_in_runs
    asyncio.run(gather.close())


def test_server_counts_its_query_tiles_by_the_product_they_make():
    """``arkflow_gen_attn_tiles_total{kind, product}``: the kernel's (row,
    query tile) programs, a layer, by the kernel's own predicate on the
    step's shapes, at a head of 128 lanes (pools [.., kv heads, 128]). The
    same serve as above over two layers: two chunks of 8 positions x 2
    query heads a K/V head (16 rows a head: a K/V head at a time), four
    decode steps of two lanes (2 rows a head: all heads at once). A chunk
    of 4 positions x 1 query head is no multiple of a sublane tile and
    stays on the all-heads product. A narrower head's row-major pools are
    walked a run of heads at a time over those heads' own rows, whatever
    the tile: every program counts under a label of its own (``head_run``:
    queries zero-extended over their run are no per-head product)."""
    from arkflow_tpu.ops.ragged_attention import per_kv_head, query_tile

    model = get_model("decoder_lm")
    cfg = model.make_config(**{**TINY, "head_dim": 128})
    params = model.init(jax.random.PRNGKey(3), cfg)

    async def go(cfg, params, chunk):
        srv = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=40,
                               prefill_chunk=chunk, decode_kernel="paged",
                               kernel_interpret=True)
        before = {k: m.value for k, m in srv.m_attn_tiles.items()}
        await srv.generate(list(range(1, 14)), max_new_tokens=5)
        await srv.close()
        return {k: m.value - before[k] for k, m in srv.m_attn_tiles.items()}

    got = asyncio.run(go(cfg, params, 8))
    assert per_kv_head(query_tile(8, cfg.heads), cfg.heads, cfg.kv_heads)
    assert not per_kv_head(query_tile(1, cfg.heads), cfg.heads, cfg.kv_heads)
    assert got == {("chunk", "per_kv_head"): 2 * cfg.layers, ("chunk", "all_heads"): 0,
                   ("decode", "per_kv_head"): 0,
                   ("decode", "all_heads"): 4 * 2 * cfg.layers}
    mha = model.make_config(**{**TINY, "kv_heads": 4, "head_dim": 128})
    got = asyncio.run(go(mha, model.init(jax.random.PRNGKey(3), mha), 4))
    assert not per_kv_head(query_tile(4, 4), 4, 4)
    assert got["chunk", "per_kv_head"] == 0
    assert got["chunk", "all_heads"] == 4 * mha.layers   # 13 tokens: 4 chunks
    narrow, params = _tiny_setup(seed=3)                  # heads of 16
    got = asyncio.run(go(narrow, params, 8))
    assert got == {("chunk", "head_run"): 2 * narrow.layers,
                   ("decode", "head_run"): 4 * 2 * narrow.layers,
                   **{(kind, product): 0 for kind in ("chunk", "decode")
                      for product in ("per_kv_head", "all_heads")}}


def test_server_dispatch_depth2_bitwise_identical():
    """Depth 2 pipelines decode (step N+1 dispatched before N's tokens
    reach the host) yet must emit the same greedy streams — across plain
    decode, chunked prefill interleave, prefix-cache hits, and multi-wave
    admission (5 prompts on 2 slots)."""
    cfg, params = _tiny_setup(seed=3)
    ref, _ = _serve(params, cfg, TP_PROMPTS, 6, dispatch_depth=1)
    got, srv = _serve(params, cfg, TP_PROMPTS, 6, dispatch_depth=2)
    assert got == ref
    assert srv.health_report()["dispatch_depth"] == 2
    assert srv._steps_ahead > 0

    long_prompts = [list(range(3, 25)), [9, 4], list(range(40, 55)), [7],
                    list(range(3, 25))]
    ref, _ = _serve(params, cfg, long_prompts, 5, prefill_chunk=8,
                    dispatch_depth=1)
    got, _ = _serve(params, cfg, long_prompts, 5, prefill_chunk=8,
                    dispatch_depth=2, prefix_cache_pages=8)
    assert got == ref


def test_server_depth2_composes_with_paged_kernel():
    cfg, params = _tiny_setup(seed=3)
    ref, _ = _serve(params, cfg, TP_PROMPTS, 6, dispatch_depth=1)
    got, srv = _serve(params, cfg, TP_PROMPTS, 6, dispatch_depth=2,
                      decode_kernel="paged", kernel_interpret=True)
    assert got == ref
    assert srv.decode_kernel == "paged" and srv.dispatch_depth == 2


def test_depth2_page_pressure_no_leak():
    """Regression (review finding): a pipelined drain can finish requests
    between `active` being computed and the classic fallback running —
    the fallback must recompute from host truth, or it feeds a ghost lane
    (allocating a page the next admission silently leaks, or truncating a
    live request for a slot with no request). Under sustained page-pool
    pressure with mixed budgets, every page must come home."""
    cfg, params = _tiny_setup(seed=3)

    async def go():
        # 7 usable pages; two slots decoding to max_seq need 12 — the pool
        # runs dry mid-wave, so drains, truncation, and the classic
        # fallback all interleave with pipelined dispatch
        srv = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=24,
                               num_pages=8, dispatch_depth=2, eos_id=-1)
        outs = await asyncio.gather(*[
            srv.generate([7 + i], max_new_tokens=m)
            for i, m in enumerate((3, 20, 5, 20, 2, 20))])
        await srv.close()
        assert len(srv._free_pages) == srv.num_pages - 1
        assert not srv._page_refs
        assert srv._pipeline is None
        return outs

    outs = asyncio.run(go())
    # truncation under a dry pool is allowed (and counted); silent loss is
    # not — every request resolved with at least one token
    assert all(len(o) >= 1 for o in outs)


def test_server_explicit_paged_kernel_without_tpu_is_config_error():
    """An explicit ``decode_kernel: paged`` that cannot run here (no TPU, no
    kernel_interpret) fails construction instead of quietly serving gather."""
    cfg, params = _tiny_setup()
    with pytest.raises(ConfigError, match="requires a TPU backend"):
        GenerationServer(params, cfg, decode_kernel="paged")


def test_server_paged_kernel_parity_mismatch_raises(monkeypatch):
    """A kernel that disagrees with the gather reference is a construction
    error naming the numbers — never a warning and a quiet swap."""
    from arkflow_tpu.ops import ragged_attention

    real = ragged_attention.paged_flash_attention
    monkeypatch.setattr(
        ragged_attention, "paged_flash_attention",
        lambda q, *a, **kw: real(q, *a, **kw) * 0.0)
    # a config no other test traces: jit's trace cache is keyed on the step
    # function and its static cfg, and a cached trace holds the real kernel
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**{**TINY, "ffn": 80})
    params = fam.init(jax.random.PRNGKey(3), cfg)
    with pytest.raises(ConfigError, match="disagrees with the dense gather"):
        GenerationServer(params, cfg, decode_kernel="paged",
                         kernel_interpret=True)
    # the gate can still be skipped explicitly (and then serves the kernel)
    srv = GenerationServer(params, cfg, decode_kernel="paged",
                           kernel_interpret=True, kernel_parity_check=False)
    assert srv.decode_kernel == "paged"


def test_server_kernel_auto_resolution():
    """The default is "auto": paged on TPU backends (gather elsewhere —
    this CI runs CPU, so auto resolves to gather with no parity-gate cost);
    kernel_interpret opts a CPU test into the kernel."""
    cfg, params = _tiny_setup(seed=3)
    _, srv = _serve(params, cfg, [[9]], 2)
    assert srv.decode_kernel == "gather"
    _, srv = _serve(params, cfg, [[9]], 2, kernel_interpret=True)
    assert srv.decode_kernel == "paged"


@pytest.mark.parametrize("heads", [1, 4], ids=["dh128", "dh32"])
def test_server_kernel_auto_serves_the_kernel_on_a_tpu_at_any_head(monkeypatch, heads):
    """On a TPU ``auto`` is the kernel whatever the model's head size: who
    walks the table is the kernel module's own affair."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**{**TINY, "dim": 128, "heads": heads, "kv_heads": 1})
    params = fam.init(jax.random.PRNGKey(5), cfg)
    monkeypatch.setattr(GenerationServer, "_on_tpu", lambda self: True)
    srv = GenerationServer(params, cfg, kernel_parity_check=False)
    assert srv.decode_kernel == "paged"


def test_server_dispatch_depth_validation():
    cfg, params = _tiny_setup()
    with pytest.raises(ConfigError, match="dispatch_depth"):
        GenerationServer(params, cfg, dispatch_depth=0)
    with pytest.raises(ConfigError, match="dispatch_depth > 2"):
        GenerationServer(params, cfg, dispatch_depth=3)
    with pytest.raises(ConfigError, match="decode_kernel"):
        GenerationServer(params, cfg, decode_kernel="warp")
    # what depth 2 is not exact with is no error any more: the server is
    # built and serves in lockstep (tests/test_gen_run_ahead.py serves it)
    assert GenerationServer(params, cfg)._ahead  # the default is depth 2
    assert not GenerationServer(params, cfg, dispatch_depth=1)._ahead
    assert not GenerationServer(params, cfg, dispatch_depth=2,
                                temperature=0.8)._ahead
    assert not GenerationServer(params, cfg, dispatch_depth=2,
                                speculative_tokens=2)._ahead
    fam = get_model("decoder_lm")
    moe = fam.make_config(**{**TINY, "dim": 32, "heads": 2, "kv_heads": 1,
                             "ffn": 48, "num_experts": 4})
    srv = GenerationServer(fam.init(jax.random.PRNGKey(0), moe), moe,
                           dispatch_depth=2)
    assert not srv._ahead and not srv.health_report()["runs_ahead"]


def test_depth2_deadline_miss_fails_both_in_flight_steps_and_heals():
    """The depth-2 chaos acceptance: a hang consumed by the pipelined fetch
    lands with TWO steps in flight (the un-applied step and its dispatched
    successor). Both die: every in-flight request fails (nacks upstream),
    the pools reset with zero leaked pages, the pipeline is discarded, and
    the recovery probe serves the exact reference afterwards."""
    from arkflow_tpu.errors import StepDeadlineExceeded
    from arkflow_tpu.tpu.health import HealthConfig

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(3), cfg)

    async def go():
        srv = GenerationServer(
            params, cfg, slots=2, page_size=4, max_seq=32, dispatch_depth=2,
            eos_id=-1,  # no early EOS: the fault must land mid-decode
            step_deadline_s=0.25, step_deadline_first_s=60.0,
            health_config=HealthConfig(probe_backoff_s=0.05))
        ref = await srv.generate([9, 4], max_new_tokens=4)  # warm + reference
        misses0 = srv.core.m_deadline_miss.value
        tasks = [asyncio.ensure_future(srv.generate([9, 4], max_new_tokens=24)),
                 asyncio.ensure_future(srv.generate([55, 1, 2], max_new_tokens=24))]
        # wait until the pipelined path has dispatched at least one step
        # (the counter is stable; `_pipeline` itself is transiently None
        # while a fetch applies), THEN arm the hang: a pipelined fetch
        # always runs with its dispatched successor already on the device
        # queue, so the miss lands with both steps in flight
        for _ in range(2000):
            if srv._steps_ahead > 0:
                break
            await asyncio.sleep(0.002)
        assert srv._steps_ahead > 0, "pipelined path never engaged"
        srv.inject_step_fault("hang", 3.0)
        results = await asyncio.gather(*tasks, return_exceptions=True)
        assert all(isinstance(r, StepDeadlineExceeded) for r in results), results
        assert srv.core.m_deadline_miss.value == misses0 + 1
        assert srv._pipeline is None
        # zero leaked pages even though a zombie owned the donated pools
        assert len(srv._free_pages) == srv.num_pages - 1
        assert not srv._page_refs
        # recovery probe: backoff, rebuild, exact reference output
        out = await srv.generate([9, 4], max_new_tokens=4)
        assert out == ref
        assert srv.core.health.state == "healthy"
        await srv.close()

    asyncio.run(go())


def test_depth2_stream_deadline_miss_nacks_and_redelivery_heals():
    """Stream-level zero-silent-loss at depth 2: the deadline-missed step
    nacks its batch through ServingRunnerCore, the fault input redelivers,
    the probe re-admits — all rows delivered."""
    from arkflow_tpu.components import ensure_plugins_loaded
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.runtime import build_stream

    ensure_plugins_loaded()
    cfg = StreamConfig.from_mapping({
        "name": "gen-deadline-d2",
        "input": {
            "type": "fault",
            "redeliver_unacked": True,
            "inner": {"type": "memory", "messages": ["r0", "r1", "r2"]},
        },
        "pipeline": {
            "thread_num": 1,
            "max_delivery_attempts": 5,
            "processors": [
                {"type": "fault",
                 "faults": [{"kind": "hang", "at": 2, "duration": "3s"}],
                 "inner": {"type": "tpu_generate", "model": "decoder_lm",
                           "model_config": TINY, "serving": "continuous",
                           "slots": 2, "page_size": 4, "max_input": 16,
                           "max_new_tokens": 4, "eos_id": -1,
                           "dispatch_depth": 2,
                           "batch_buckets": [4], "seq_buckets": [16],
                           "step_deadline": "250ms",
                           "step_deadline_first": "60s",
                           "health": {"probe_backoff": "50ms"}}},
            ],
        },
        "output": {"type": "drop"},
    })
    stream = build_stream(cfg)
    server = stream.pipeline.processors[0].runner
    assert server.dispatch_depth == 2
    misses0 = server.core.m_deadline_miss.value
    asyncio.run(asyncio.wait_for(stream.run(asyncio.Event()), timeout=120))
    assert stream.m_rows_out.value == 3  # nothing lost
    assert stream.m_errors.value >= 1
    assert server.core.m_deadline_miss.value >= misses0 + 1
    assert server.core.health.state == "healthy"


def test_depth2_oom_chaos_zero_loss():
    """The oom fault kind at depth 2: an injected RESOURCE_EXHAUSTED in the
    pipelined fetch fails in-flight requests loudly (never silently), the
    server marks UNHEALTHY and recovers on the next request."""
    from arkflow_tpu.tpu.health import HealthConfig

    cfg, params = _tiny_setup(seed=3)

    async def go():
        srv = GenerationServer(
            params, cfg, slots=2, page_size=4, max_seq=32, dispatch_depth=2,
            eos_id=-1,  # no early EOS: the fault must land mid-decode
            health_config=HealthConfig(probe_backoff_s=0.05))
        ref = await srv.generate([9, 4], max_new_tokens=4)
        task = asyncio.ensure_future(srv.generate([9, 4], max_new_tokens=24))
        for _ in range(2000):
            if srv._steps_ahead > 0:
                break
            await asyncio.sleep(0.002)
        srv.inject_step_fault("oom")
        with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
            await task
        out = await srv.generate([9, 4], max_new_tokens=4)
        assert out == ref
        await srv.close()

    asyncio.run(go())


# -- runner dispatch depth ----------------------------------------------------


def _bert_runner(**kw):
    from arkflow_tpu.tpu.bucketing import BucketPolicy
    from arkflow_tpu.tpu.runner import ModelRunner

    return ModelRunner(
        "bert_classifier",
        {"num_labels": 2, "hidden": 32, "ffn": 64, "layers": 2, "heads": 2,
         "vocab_size": 512, "max_positions": 64},
        buckets=BucketPolicy(batch_buckets=[4, 8], seq_buckets=[16, 32]),
        **kw)


def test_runner_dispatch_depth2_outputs_identical():
    r1 = _bert_runner()
    r2 = _bert_runner(dispatch_depth=2)
    rng = np.random.RandomState(0)
    inp = {"input_ids": rng.randint(0, 500, (6, 16)).astype(np.int32),
           "attention_mask": np.ones((6, 16), np.int32)}

    async def go(r):
        # twice: the first call compiles (classic path), the second takes
        # the warm split-dispatch path
        a = await r.infer(dict(inp))
        b = await r.infer(dict(inp))
        return a, b

    a1, b1 = asyncio.run(go(r1))
    a2, b2 = asyncio.run(go(r2))
    for k in a1:
        np.testing.assert_array_equal(a1[k], a2[k])
        np.testing.assert_array_equal(b1[k], b2[k])
    # sync path agrees too
    s = r2.infer_sync(dict(inp))
    for k in a1:
        np.testing.assert_array_equal(a1[k], s[k])


def test_runner_staging_pool_sizing_invariant():
    """The _StagingPool cap must cover every concurrently-held buffer set:
    dispatch_depth in flight past the permit + max_in_flight inside it —
    sized at construction, not discovered from an allocation profile."""
    r = _bert_runner(dispatch_depth=2, max_in_flight=2)
    assert r._staging is not None
    assert r._staging._max == r.max_in_flight + r.dispatch_depth
    assert r._staging._max >= r.dispatch_depth + 1
    with pytest.raises(ConfigError, match="dispatch_depth"):
        _bert_runner(dispatch_depth=0)
    from arkflow_tpu.tpu.runner import _StagingPool

    with pytest.raises(AssertionError):
        _StagingPool(max_per_key=0)


def test_runner_depth2_deadline_miss_still_nacks():
    """A hang consumed by the split fetch must still trip the per-step
    deadline (budget runs from the step's own dispatch) and mark UNHEALTHY."""
    from arkflow_tpu.errors import StepDeadlineExceeded
    from arkflow_tpu.tpu.health import HealthConfig

    r = _bert_runner(dispatch_depth=2, step_deadline_s=0.25,
                     step_deadline_first_s=60.0,
                     health_config=HealthConfig(probe_backoff_s=0.05))
    rng = np.random.RandomState(0)
    inp = {"input_ids": rng.randint(0, 500, (4, 16)).astype(np.int32),
           "attention_mask": np.ones((4, 16), np.int32)}

    async def go():
        await r.infer(dict(inp))  # warm (classic path, compiles)
        r.inject_step_fault("hang", 3.0)
        with pytest.raises(StepDeadlineExceeded):
            await r.infer(dict(inp))
        assert r.core.health.state == "unhealthy"

    asyncio.run(go())


# -- config + processor plumbing ---------------------------------------------


def test_config_validates_dispatch_knobs_through_fault_wrappers():
    from arkflow_tpu.config import StreamConfig

    def stream(proc):
        return {"name": "s",
                "input": {"type": "memory", "messages": ["x"]},
                "pipeline": {"processors": [
                    {"type": "fault", "inner": proc}]},
                "output": {"type": "drop"}}

    gen = {"type": "tpu_generate", "model": "decoder_lm",
           "serving": "continuous"}
    StreamConfig.from_mapping(stream({**gen, "dispatch_depth": 2,
                                      "decode_kernel": "paged"}))
    # what depth 2 is not exact with parses: such a server runs in lockstep
    StreamConfig.from_mapping(stream({**gen, "dispatch_depth": 2,
                                      "speculative_tokens": 2}))
    StreamConfig.from_mapping(stream({**gen, "dispatch_depth": 2,
                                      "temperature": 0.7}))
    for bad, msg in (
            ({**gen, "dispatch_depth": 3}, "caps at 2"),
            ({**gen, "dispatch_depth": 0}, "positive int"),
            ({**gen, "dispatch_depth": True}, "positive int"),
            ({**gen, "decode_kernel": "warp"}, "gather|paged"),
            ({**gen, "dispatch_depth": 3, "speculative_tokens": 2},
             "caps at 2"),
            ({**gen, "dispatch_depth": "2", "temperature": 0.7},
             "positive int"),
            ({"type": "tpu_inference", "model": "bert_classifier",
              "dispatch_depth": -1}, "positive int")):
        with pytest.raises(ConfigError, match=msg.replace("|", r"\|")):
            StreamConfig.from_mapping(stream(bad))


def test_tpu_generate_processor_plumbs_kernel_and_depth():
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    proc = build_component(
        "processor",
        {"type": "tpu_generate", "model": "decoder_lm", "model_config": TINY,
         "serving": "continuous", "slots": 2, "page_size": 4, "max_input": 16,
         "max_new_tokens": 4, "decode_kernel": "paged",
         "kernel_interpret": True, "dispatch_depth": 2,
         "batch_buckets": [4], "seq_buckets": [16]},
        Resource())
    assert proc._server.decode_kernel == "paged"
    assert proc._server.dispatch_depth == 2
    rep = proc.runner.health_report()
    assert rep["decode_kernel"] == "paged" and rep["dispatch_depth"] == 2


@pytest.mark.parametrize("cell,decodes,chunks", [
    ("mistral_tp4_local", 1, 2), ("evabyte_l8", 3, 3)])
def test_profile_paged_attention_rehearses_on_the_cpu_and_times_nothing_there(
        cell, decodes, chunks):
    """``tools/profile_paged_attention.py``: without a TPU it refuses to
    time; ``--interpret`` rehearses a cell's points at tiny sizes — the
    decode call all heads at once, the chunk calls a K/V head at a time
    (EvaByte's 32 K/V heads, a query head each, too), each against the
    gather reference — and writes no time."""
    from arkflow_tpu.utils.cleanenv import cpu_child_env

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = [sys.executable, os.path.join(repo, "tools", "profile_paged_attention.py"),
            "--cell", cell]
    env = cpu_child_env(n_devices=1)
    res = subprocess.run(tool, env=env, capture_output=True, timeout=300, cwd=repo)
    assert res.returncode == 1 and b"found no TPU" in res.stderr and not res.stdout
    res = subprocess.run([*tool, "--interpret", "--check"], env=env,
                         capture_output=True, timeout=300, cwd=repo)
    assert res.returncode == 0, res.stderr.decode(errors="replace")[-2000:]
    lines = [json.loads(l) for l in res.stdout.decode().strip().splitlines()]
    assert [(l["kind"], l["per_kv_head"]) for l in lines] == [
        *[("decode", False)] * decodes, *[("chunk", True)] * chunks]
    assert all(l["rehearsal"] and "us_per_call" not in l for l in lines)
    assert all(l["max_abs_err"] < 2e-3 for l in lines)
